"""The granite_moe_hybrid family (``granitemoehybrid`` with routed
experts: granite-4.0-h-small): the granite_hybrid family's layers of two
kinds by ``layer_types`` (``families/granite_hybrid.py``: each kind's
parameters one stack of the served tree, the scan's own leaves through
its :func:`mamba2_init`), and after every layer of either kind a softmax
router over all the published experts, the routed experts this device
holds and the shared MLP.

A configuration file may give the chip's share of a deployment under
``share``: ``num_local_experts`` and ``vocab_size`` are then what is held
here (both listed in its ``reduced``), ``share.num_local_experts_published``
what the router scores, and ``share.first_expert`` the published index of
the first expert held.

Every published key is read or refused by the package's
``GraniteHybridConfig.from_published``; the dense models
(``num_local_experts`` 0) are ``families/granite_hybrid.py``'s and are
refused here.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Tuple

from families import granite_hybrid, laguna

def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import granite_hybrid as gh

    if not c["num_local_experts"]:
        raise ValueError("granite_moe_hybrid: num_local_experts 0 is a "
                         "dense model, which families/granite_hybrid.py "
                         "builds")
    config, forward = granite_hybrid._seeded_config()
    share = c.get("share", {})
    cfg = config.from_published(
        c, num_experts=int(share.get("num_local_experts_published",
                                     c["num_local_experts"])),
        experts_held=(int(share.get("first_expert", 0)),
                      int(c["num_local_experts"])),
        init_std=float(c["initializer_range"]), **kw)
    return cfg, gh.GraniteHybridForCausalLM(cfg), forward


#: a published checkpoint's tensor names -> the names the reference reads
#: (an expert's ``input_linear`` and ``output_linear`` are read with
#: ``expert=``, its published index: the checkpoint stacks the 72 as
#: ``[72, 1536, 4096]`` and ``[72, 4096, 768]``)
CHECKPOINT = {
    **{k: v for k, v in granite_hybrid.CHECKPOINT.items()
       if not k.startswith("shared_mlp.")},
    "shared_mlp.input_linear.weight": "shared_input_linear",
    "shared_mlp.output_linear.weight": "shared_output_linear",
    "block_sparse_moe.input_linear.weight": "input_linear",
    "block_sparse_moe.output_linear.weight": "output_linear",
    "block_sparse_moe.router.layer.weight": "router"}
_LAYER = re.compile(r"model\.layers\.(\d+)\.(.+)")


class Published(laguna.Published):
    """As Laguna's (a layer found in its kind's stack, the router over all
    the published experts, the routed experts an expert at a time by the
    expert's published index, the held ones alone, and the shared MLP),
    over the stacks ``model/layers_mamba2`` and ``model/layers_full``; the
    mamba stack's scan leaves through ``granite_hybrid.mamba2_init``. A
    tensor is read by the reference's name (``weights("input_linear", 3,
    expert=5)``) or by the checkpoint's
    (``weights("model.layers.3.block_sparse_moe.input_linear.weight",
    expert=5)``): ``input_linear [2I, H]`` gate rows then up rows,
    ``output_linear [H, I]``, ``shared_input_linear``,
    ``shared_output_linear`` the same at the shared width, ``router [E,
    H]``, the mixers' as ``families/granite_hybrid.py`` names them; the
    embedding is the head."""

    TOP = granite_hybrid.Published.TOP
    PER_LAYER = dict(granite_hybrid.Published.PER_LAYER,
                     router=("moe", "router", "kernel"))

    def __init__(self, params, config: dict):
        self.tree = granite_hybrid.with_mamba2_init(
            params, float(config["initializer_range"]))["params"]
        self.hidden = config["hidden_size"]
        self.widths = {"sparse": config["intermediate_size"],
                       "shared": config["shared_intermediate_size"]}
        self.first = int(config.get("share", {}).get("first_expert", 0))
        self.held = int(config["num_local_experts"])
        # a layer's kind, its feed-forward and its index in the kind's stack
        kinds = [granite_hybrid.KINDS[t] for t in config["layer_types"]]
        self.at = [(k, "sparse", kinds[:i].count(k))
                   for i, k in enumerate(kinds)]

    def __call__(self, name: str, layer: int = None, expert: int = None):
        import jax.numpy as jnp

        if name in CHECKPOINT:
            return self(CHECKPOINT[name], layer, expert)
        found = _LAYER.fullmatch(name)
        if found:
            return self(CHECKPOINT[found.group(2)], int(found.group(1)),
                        expert)
        if name == "conv_weight":
            kind, _, index = self.at[layer]
            stack = self.tree["model"][f"layers_{kind}"]["layer"]
            return laguna._at(stack["attn"]["conv_kernel"],
                              index)[:, None, :]
        base = name.removeprefix("shared_")
        shared = name[:len(name) - len(base)]
        if base == "input_linear":
            return jnp.concatenate([
                super().__call__(shared + "gate", layer, expert),
                super().__call__(shared + "up", layer, expert)])
        if base == "output_linear":
            return super().__call__(shared + "down", layer, expert)
        if layer is None and name not in self.TOP:
            raise KeyError(name)
        return super().__call__(name, layer, expert)


published = Published
