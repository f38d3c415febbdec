"""The ``mla_paged_attention`` kernel's share of its roofline for a model
whose decoder layer has two latent attentions (LongCat-Flash's
shortcut-connected double layer): ``readers/mla_roofline.py``'s count of
one latent attention's operations and bytes over the traced steps
(absorbed, as the served path computes: ``2 * heads * (2 * rank + rope)``
operations a row and attended position, every live slot's ``rank + rope``
values once a step, the queries and the outputs; the nope and value
widths enter only the absorption's own matmuls, which run outside the
kernel whose time this is held against), times the ``2 * num_layers``
attentions the configuration has, over the kernel's device time in the
trace, in percent. Says which bound holds. The published configuration
counts double layers under ``num_layers`` and has no
``num_hidden_layers``; ``readers/mla_roofline.py`` and ``readers/work.py``
stay as they are.
"""

import dataclasses

from readers import mla_roofline

ATTENTIONS_A_LAYER = 2


def _an_attention_a_layer(obs):
    """``obs`` as ``readers/mla_roofline.py`` reads a configuration: one
    latent attention a counted layer."""
    attentions = ATTENTIONS_A_LAYER * int(obs.config["num_layers"])
    return dataclasses.replace(
        obs, config={**obs.config, "num_hidden_layers": attentions})


def work(obs):
    """``(flops, bytes)`` of latent attention over the traced steps, all
    ``2 * num_layers`` attentions."""
    return mla_roofline.work(_an_attention_a_layer(obs))


def read(args: dict, obs):
    if "num_layers" not in obs.config:     # no double layers: nothing to read
        return None
    return mla_roofline.read(args, _an_attention_a_layer(obs))
