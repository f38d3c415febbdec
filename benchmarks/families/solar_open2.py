"""The solar_open2 family (Solar-Open2-250B): gated NoPE GQA layers among
Kimi Delta Attention mixers by ``gqa_layers``, one stack of parameters a
kind of layer, a sigmoid router with a selection bias over all the
published experts, the routed experts this device holds and one shared
expert.

A configuration file may give the chip's share of a deployment under
``share``: ``n_routed_experts`` and ``vocab_size`` are then what is held
here (both listed in its ``reduced``), ``share.n_routed_experts_published``
what the router scores, and ``share.first_expert`` the published index of
the first expert held.

**The decay's own parameters are not drawn N(0, std).**
``harness.make_weights`` draws every leaf that is no norm's scale from
``N(0, initializer_range)``; with ``A_log`` and ``dt_bias`` near 0 every
channel of every head forgets alike (``exp(g)`` about a half a position),
and a stale state, a missing decay or a state a step behind would pass
the logit check. So the configuration this family builds maps those two
leaves, value by value, onto KDA's own initialisation (:func:`kda_init`:
the leaf's normal quantile is the uniform draw), in front of the
package's paged forward and in front of what the reference reads alike:
``A = U[1, 16]``, ``dt = exp U[log 1e-3, log 1e-1]``. The package's model
stores and computes the published parameters as they are; the mapping is
this file's.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Tuple

from families import laguna


def kda_init(attn: dict, std: float) -> dict:
    """A KDA layer stack's ``attn`` subtree with the decay's own leaves
    (drawn ``N(0, std)``) mapped onto KDA's initialisation, each in its
    leaf's type."""
    import jax.numpy as jnp
    from jax.scipy.stats import norm

    def uniform(name):
        return norm.cdf(attn[name].astype(jnp.float32) / std)

    dt = jnp.exp(math.log(1e-3) + uniform("dt_bias")
                 * (math.log(1e-1) - math.log(1e-3)))
    new = {"A_log": jnp.log(1.0 + 15.0 * uniform("A_log")),
           "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}
    return {**attn, **{k: v.astype(attn[k].dtype) for k, v in new.items()}}


def with_kda_init(params, std: float):
    """``params`` with :func:`kda_init` over the KDA stack."""
    tree = params["params"]
    stack = tree["model"]["layers_kda"]
    layer = {**stack["layer"], "attn": kda_init(stack["layer"]["attn"], std)}
    return {**params, "params": {**tree, "model": {
        **tree["model"], "layers_kda": {**stack, "layer": layer}}}}


@functools.lru_cache(maxsize=None)
def _seeded_config():
    from neuronx_distributed_tpu.models import solar_open2 as so

    def forward(cfg, params, *args, **kw):
        return so.solar_open2_forward_with_cache(
            cfg, with_kda_init(params, cfg.init_std), *args, **kw)

    @dataclasses.dataclass(frozen=True)
    class SeededSolarOpen2Config(so.SolarOpen2Config):
        """The package's config, served from weights whose decay leaves
        are normal draws (``init_std``) to be read as KDA's."""

        init_std: float = 0.02

        def serving_family(self):
            return dataclasses.replace(super().serving_family(),
                                       forward=forward)

    return SeededSolarOpen2Config, forward


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    """Every published key is read or refused by the package's
    ``SolarOpen2Config.from_published``; what is built beside it: SiLU
    experts under a sigmoid router (the configuration's ``assumed``)."""
    from neuronx_distributed_tpu.models import solar_open2 as so

    config, forward = _seeded_config()
    share = c.get("share", {})
    cfg = config.from_published(
        c, num_experts=int(share.get("n_routed_experts_published",
                                     c["n_routed_experts"])),
        experts_held=(int(share.get("first_expert", 0)),
                      int(c["n_routed_experts"])),
        init_std=float(c["initializer_range"]), **kw)
    return cfg, so.SolarOpen2ForCausalLM(cfg), forward


@functools.lru_cache(maxsize=None)
def _columns():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames="width")
    def columns(leaf, index, first, width):
        rows = leaf.shape[1]
        return jax.lax.dynamic_slice(
            leaf[index], (0, first), (rows, width)).astype(jnp.float32).T

    return columns


class Published(laguna.Published):
    """As Laguna's (a layer found in its kind's stack, the router over all
    the published experts, the routed experts an expert at a time by the
    expert's published index, the held ones alone, and the shared
    expert), over the stacks ``model/layers_full`` and
    ``model/layers_kda``; the KDA stack's decay leaves through
    :func:`kda_init`; the fused projections a published tensor at a time
    (``qkv_proj`` is ``q_proj | k_proj | v_proj``, ``low_proj`` is
    ``f_a_proj | g_a_proj | b_proj``, ``conv_kernel`` the three
    ``*_conv`` ``[8192, 1, 4]``)."""

    PER_LAYER = {"input_norm": ("input_norm", "scale"),
                 "post_norm": ("post_norm", "scale"),
                 "q_proj": ("attn", "q_proj", "kernel"),
                 "k_proj": ("attn", "k_proj", "kernel"),
                 "v_proj": ("attn", "v_proj", "kernel"),
                 "g_proj": ("attn", "g_proj", "kernel"),
                 "o_proj": ("attn", "o_proj", "kernel"),
                 "f_b_proj": ("attn", "f_b_proj", "kernel"),
                 "g_b_proj": ("attn", "g_b_proj", "kernel"),
                 "g_b_bias": ("attn", "g_b_proj", "bias"),
                 "A_log": ("attn", "A_log"), "dt_bias": ("attn", "dt_bias"),
                 "o_norm": ("attn", "o_norm", "scale"),
                 "router": ("moe", "router", "kernel"),
                 "router_bias": ("moe", "router", "bias")}

    def __init__(self, params, config: dict):
        self.tree = with_kda_init(
            params, float(config["initializer_range"]))["params"]
        self.hidden = config["hidden_size"]
        self.widths = {"sparse": config["moe_intermediate_size"],
                       "shared": config["moe_intermediate_size"]}
        lin = config["linear_attn_config"]
        inner, d = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
        #: a KDA layer's fused leaves: published name -> (leaf, first
        #: column, columns)
        self.fused = {
            "q_proj": ("qkv_proj", 0, inner),
            "k_proj": ("qkv_proj", inner, inner),
            "v_proj": ("qkv_proj", 2 * inner, inner),
            "f_a_proj": ("low_proj", 0, d), "g_a_proj": ("low_proj", d, d),
            "b_proj": ("low_proj", 2 * d, lin["num_heads"])}
        self.conv = {"q_conv": 0, "k_conv": inner, "v_conv": 2 * inner}
        self.inner = inner
        self.first = int(config.get("share", {}).get("first_expert", 0))
        self.held = int(config["n_routed_experts"])
        # a layer's kind, its feed-forward and its index in the kind's stack
        kinds = ["full" if i in config["gqa_layers"] else "kda"
                 for i in range(config["num_hidden_layers"])]
        self.at = [(k, "sparse", kinds[:i].count(k))
                   for i, k in enumerate(kinds)]

    def __call__(self, name: str, layer: int = None, expert: int = None):
        import numpy as np

        if layer is not None and self.at[layer][0] == "kda":
            _, _, index = self.at[layer]
            attn = self.tree["model"]["layers_kda"]["layer"]["attn"]
            if name in self.fused:
                leaf, first, width = self.fused[name]
                return _columns()(attn[leaf]["kernel"], np.int32(index),
                                  np.int32(first), width=width)
            if name in self.conv:
                w = laguna._at(attn["conv_kernel"], index)
                return w[self.conv[name]:self.conv[name] + self.inner,
                         None, :]
        return super().__call__(name, layer, expert)


published = Published
