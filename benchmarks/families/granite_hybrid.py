"""The granite_hybrid family (``granitemoehybrid``, dense): layers of two
kinds by ``layer_types``, each kind's parameters one stack of the served
tree; a mamba layer's ``in_proj``, convolution, ``A_log``, ``D``,
``dt_bias``, gated norm and ``out_proj``, an attention layer's llama
projections, and the shared SwiGLU after both.

**The scan's own parameters are not drawn N(0, std).**
``harness.make_weights`` draws every leaf that is no norm weight from
``N(0, initializer_range)``; with ``A_log`` and ``dt_bias`` near 0 every
head's state halves each position, and a stale or dropped state would pass
the logit check. So the configuration this family builds maps those
leaves, value by value, onto Mamba-2's own initialisation
(:func:`mamba2_init`: the leaf's normal quantile is the uniform draw), in
front of the package's paged forward and in front of what the reference
reads alike: ``A = U[1, 16]``, ``dt = exp U[log 1e-3, log 1e-1]``, ``D =
1``, the convolution ``U(-1/2, 1/2)``. The package's model stores and
computes the published parameters as they are; the mapping is this
file's.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import re
from typing import Any, Callable, Tuple

from families import llama

KINDS = {"mamba": "mamba2", "attention": "full"}
SCAN_LEAVES = ("A_log", "dt_bias", "D", "conv_kernel")


def mamba2_init(attn: dict, std: float) -> dict:
    """A mamba layer stack's ``attn`` subtree with the scan's own leaves
    (drawn ``N(0, std)``) mapped onto Mamba-2's initialisation, each in
    its leaf's type."""
    import jax.numpy as jnp
    from jax.scipy.stats import norm

    def uniform(name):
        return norm.cdf(attn[name].astype(jnp.float32) / std)

    dt = jnp.exp(math.log(1e-3) + uniform("dt_bias")
                 * (math.log(1e-1) - math.log(1e-3)))
    new = {"A_log": jnp.log(1.0 + 15.0 * uniform("A_log")),
           "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
           "D": jnp.ones_like(attn["D"], jnp.float32),
           "conv_kernel": uniform("conv_kernel") - 0.5}
    return {**attn, **{k: v.astype(attn[k].dtype) for k, v in new.items()}}


def with_mamba2_init(params, std: float):
    """``params`` with :func:`mamba2_init` over the mamba stack."""
    tree = params["params"]
    stack = tree["model"]["layers_mamba2"]
    layer = {**stack["layer"], "attn": mamba2_init(stack["layer"]["attn"],
                                                   std)}
    return {**params, "params": {**tree, "model": {
        **tree["model"], "layers_mamba2": {**stack, "layer": layer}}}}


@functools.lru_cache(maxsize=None)
def _seeded_config():
    from neuronx_distributed_tpu.models import granite_hybrid as gh

    def forward(cfg, params, *args, **kw):
        return gh.granite_hybrid_forward_with_cache(
            cfg, with_mamba2_init(params, cfg.init_std), *args, **kw)

    @dataclasses.dataclass(frozen=True)
    class SeededGraniteHybridConfig(gh.GraniteHybridConfig):
        """The package's config, served from weights whose scan leaves
        are normal draws (``init_std``) to be read as Mamba-2's."""

        init_std: float = 0.02

        def serving_family(self):
            return dataclasses.replace(super().serving_family(),
                                       forward=forward)

    return SeededGraniteHybridConfig, forward


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import granite_hybrid as gh

    if (c.get("num_local_experts", 0) or c["position_embedding_type"]
            != "nope" or c["hidden_act"] != "silu" or c["attention_bias"]
            or c["mamba_proj_bias"] or not c["mamba_conv_bias"]
            or c["normalization_function"] != "rmsnorm"
            or c["mamba_expand"] * c["hidden_size"]
            != c["mamba_n_heads"] * c["mamba_d_head"]
            or c["shared_intermediate_size"] != c["intermediate_size"]):
        raise ValueError(
            "granite_hybrid: the dense models (no routed experts, the "
            "shared MLP of intermediate_size), NoPE, SiLU, RMSNorm, a "
            "convolution bias and no other, d_inner = mamba_expand x "
            "hidden_size")
    config, forward = _seeded_config()
    common = llama.common(c)
    common.pop("head_dim")
    cfg = config(**{
        **common, "layer_types": tuple(c["layer_types"]),
        "mamba_n_heads": c["mamba_n_heads"],
        "mamba_d_head": c["mamba_d_head"],
        "mamba_d_state": c["mamba_d_state"],
        "mamba_d_conv": c["mamba_d_conv"],
        "mamba_n_groups": c["mamba_n_groups"],
        "mamba_chunk_size": c["mamba_chunk_size"],
        "embedding_multiplier": float(c["embedding_multiplier"]),
        "residual_multiplier": float(c["residual_multiplier"]),
        "attention_multiplier": float(c["attention_multiplier"]),
        "logits_scaling": float(c["logits_scaling"]),
        "init_std": float(c["initializer_range"]), **kw})
    return cfg, gh.GraniteHybridForCausalLM(cfg), forward


#: a published checkpoint's tensor names -> the names the reference reads
CHECKPOINT = {
    "model.embed_tokens.weight": "embedding",
    "model.norm.weight": "final_norm",
    "input_layernorm.weight": "input_norm",
    "post_attention_layernorm.weight": "post_norm",
    "shared_mlp.input_linear.weight": "input_linear",
    "shared_mlp.output_linear.weight": "output_linear",
    "mamba.in_proj.weight": "in_proj",
    "mamba.out_proj.weight": "out_proj",
    "mamba.conv1d.weight": "conv_weight",
    "mamba.conv1d.bias": "conv_bias",
    "mamba.A_log": "A_log", "mamba.D": "D", "mamba.dt_bias": "dt_bias",
    "mamba.norm.weight": "mamba_norm",
    "self_attn.q_proj.weight": "q_proj",
    "self_attn.k_proj.weight": "k_proj",
    "self_attn.v_proj.weight": "v_proj",
    "self_attn.o_proj.weight": "o_proj"}
_LAYER = re.compile(r"model\.layers\.(\d+)\.(.+)")


class Published(llama.Published):
    """As the llama family's, a layer found in its kind's stack
    (``model/layers_mamba2``, ``model/layers_full``) at its index among
    the layers of that kind, the scan's own leaves through
    :func:`mamba2_init`. A tensor is read by the reference's name
    (``weights("in_proj", 3)``) or by the checkpoint's
    (``weights("model.layers.3.mamba.in_proj.weight")``): ``in_proj
    [2 d_inner + 2 N + heads, H]`` rows ``z | x | B | C | dt``,
    ``conv_weight [C, 1, W]``, ``input_linear [2I, H]`` gate rows then up
    rows, ``output_linear [H, I]``; the embedding is the head."""

    TOP = {"embedding": ("model", "embed", "embedding"),
           "final_norm": ("model", "norm", "scale")}
    PER_LAYER = dict(llama.Published.PER_LAYER,
                     in_proj=("attn", "in_proj", "kernel"),
                     out_proj=("attn", "out_proj", "kernel"),
                     conv_bias=("attn", "conv_bias"),
                     A_log=("attn", "A_log"), D=("attn", "D"),
                     dt_bias=("attn", "dt_bias"),
                     mamba_norm=("attn", "norm", "scale"))

    def __init__(self, params, config: dict):
        self.tree = with_mamba2_init(
            params, float(config["initializer_range"]))["params"]
        self.hidden = config["hidden_size"]
        self.inter = config["intermediate_size"]
        kinds = [KINDS[t] for t in config["layer_types"]]
        #: layer -> (its kind's stack, its index in it)
        self.where = [(self.tree["model"][f"layers_{k}"]["layer"],
                       kinds[:i].count(k)) for i, k in enumerate(kinds)]

    def mlp_at(self, layer, expert):
        stack, index = self.where[layer]
        return stack["mlp"], index

    def __call__(self, name: str, layer: int = None, expert: int = None):
        import jax.numpy as jnp

        if name in CHECKPOINT:
            return self(CHECKPOINT[name])
        found = _LAYER.fullmatch(name)
        if found:
            return self(CHECKPOINT[found.group(2)], int(found.group(1)))
        if name == "conv_weight":
            stack, index = self.where[layer]
            return llama._f32(stack["attn"]["conv_kernel"][index])[:, None, :]
        if name == "input_linear":
            return jnp.concatenate([super().__call__("gate", layer),
                                    super().__call__("up", layer)])
        if name == "output_linear":
            return super().__call__("down", layer)
        if name in self.PER_LAYER:
            stack, index = self.where[layer]
            w = llama._f32(self._get(stack, self.PER_LAYER[name])[index])
            return w.T if w.ndim == 2 else w
        if layer is None and name not in self.TOP:
            raise KeyError(name)
        return super().__call__(name, layer, expert)


published = Published
