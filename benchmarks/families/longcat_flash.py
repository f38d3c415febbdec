"""The longcat_flash family (LongCat-Flash-Chat): a shortcut-connected
double layer (two latent attentions whose ``kv_b`` the package stores
absorbed as ``k_up`` and ``v_up``, two dense feed-forwards, one expert
bank), a softmax router with a selection bias over the published real
experts and the identity experts, the real experts this device holds.

A configuration file may give the chip's share of a deployment under
``share``: ``n_routed_experts`` and ``vocab_size`` are then what is held
here (both listed in its ``reduced``), ``share.n_routed_experts_published``
the real experts the router scores, and ``share.first_expert`` the
published index of the first expert held. ``zero_expert_num`` is whole on
every chip.

**The selection bias is not drawn N(0, initializer_range).**
``harness.make_weights`` draws every leaf that is no norm's scale from
``N(0, 0.02)``; the router's probabilities over 768 slots are about 1/768
with a spread of 0.004 under such weights, so a bias of 0.02 would decide
every choice alone and weighing by ``p`` would be weighing by noise. So
the configuration this family builds reads the bias leaf times
:data:`BIAS_STD` ``/ initializer_range`` (``N(0, 0.004)``, the spread of
``p``: 3.3 of a row's 12 choices then differ from choosing by ``p`` alone
and 1.8 are the bias's alone), in front of the package's paged forward and
in front of what the reference reads alike (``families/solar_open2.py``'s
``kda_init`` is the pattern). The package's model stores and computes the
published parameters as they are; the mapping is this file's.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Tuple

from families import laguna, llama

#: the standard deviation the selection bias is served at
BIAS_STD = 0.004


def with_seeded_bias(params, std: float):
    """``params`` with the router's bias (drawn ``N(0, std)``) at
    :data:`BIAS_STD`."""
    tree = params["params"]
    stack = tree["model"]["layers_double"]
    layer = stack["layer"]
    router = layer["moe"]["router"]
    router = {**router, "bias": router["bias"] * (BIAS_STD / std)}
    return {**params, "params": {**tree, "model": {
        **tree["model"], "layers_double": {**stack, "layer": {
            **layer, "moe": {**layer["moe"], "router": router}}}}}}


@functools.lru_cache(maxsize=None)
def _seeded_config():
    from neuronx_distributed_tpu.models import longcat_flash as lc

    def forward(cfg, params, *args, **kw):
        return lc.longcat_flash_forward_with_cache(
            cfg, with_seeded_bias(params, cfg.init_std), *args, **kw)

    @dataclasses.dataclass(frozen=True)
    class SeededLongcatFlashConfig(lc.LongcatFlashConfig):
        """The package's config, served from weights whose selection bias
        is a normal draw at ``init_std`` to be read at :data:`BIAS_STD`."""

        init_std: float = 0.02

        def serving_family(self):
            return dataclasses.replace(super().serving_family(),
                                       forward=forward)

    return SeededLongcatFlashConfig, forward


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import longcat_flash as lc

    if (c["attention_bias"] or c["attention_method"] != "MLA"
            or c["zero_expert_type"] != "identity"
            or c.get("rope_scaling") or c.get("norm_topk_prob")):
        raise ValueError("longcat_flash: latent attention without bias, "
                         "identity zero experts, plain rotary and "
                         "unnormalised top-k weights are what is built")
    config, forward = _seeded_config()
    share = c.get("share", {})
    cfg = config(**{
        "vocab_size": c["vocab_size"], "hidden_size": c["hidden_size"],
        "intermediate_size": c["ffn_hidden_size"],
        "num_layers": c["num_layers"],
        "num_heads": c["num_attention_heads"],
        "rope_theta": float(c["rope_theta"]),
        "rms_eps": float(c["rms_norm_eps"]),
        "max_seq_len": int(c["max_position_embeddings"]),
        "q_lora_rank": c["q_lora_rank"], "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_head_dim": c["qk_nope_head_dim"],
        "qk_rope_head_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"],
        "mla_scale_q_lora": bool(c["mla_scale_q_lora"]),
        "mla_scale_kv_lora": bool(c["mla_scale_kv_lora"]),
        "num_experts": int(share.get("n_routed_experts_published",
                                     c["n_routed_experts"])),
        "identity_experts": c["zero_expert_num"],
        "top_k": c["moe_topk"],
        "moe_intermediate_size": c["expert_ffn_hidden_size"],
        "routed_scaling_factor": float(c["routed_scaling_factor"]),
        "experts_held": (int(share.get("first_expert", 0)),
                         int(c["n_routed_experts"])),
        "init_std": float(c["initializer_range"]), **kw})
    return cfg, lc.LongcatFlashForCausalLM(cfg), forward


class Published(llama.Published):
    """The served tree under the checkpoint's tensor names
    (``reference/longcat_flash_f32.py`` lists them), a layer's leaves by
    an index that is an operand (``families/laguna._at``); ``kv_b_proj``
    put together from the absorbed leaves in the checkpoint's shape (a
    head's key rows and then its value rows); the router's bias as
    :func:`with_seeded_bias` serves it; a real expert by its published
    index, the held ones alone (another is a ``KeyError``); the embedding
    and the head are the vocabulary's slice."""

    ATTENTION = {"q_a_proj": ("q_a",), "q_a_layernorm": ("q_a_norm", "scale"),
                 "q_b_proj": ("q_b", "kernel"),
                 "kv_a_proj_with_mqa": ("kv_a",),
                 "kv_a_layernorm": ("kv_a_norm", "scale"),
                 "o_proj": ("o_proj", "kernel")}
    NORMS = {"input_layernorm": "input_norm",
             "post_attention_layernorm": "post_norm"}
    ROUTER = {"classifier": "kernel", "e_score_correction_bias": "bias"}

    def __init__(self, params, config: dict):
        self.tree = with_seeded_bias(
            params, float(config["initializer_range"]))["params"]
        self.stack = self.tree["model"]["layers_double"]["layer"]
        self.hidden = config["hidden_size"]
        self.widths = {"mlps": config["ffn_hidden_size"],
                       "experts": config["expert_ffn_hidden_size"]}
        self.first = int(config.get("share", {}).get("first_expert", 0))
        self.held = int(config["n_routed_experts"])

    def _glu(self, node, at, name, inter):
        """``gate_proj``, ``up_proj`` or ``down_proj`` ``[out, in]`` of
        the node's leaves ``gate``, ``up`` ``[.., H, I]`` and ``down``
        (or ``down/kernel``) ``[.., I, H]``."""
        which = name.removesuffix("_proj")
        found = [v for k, v in llama._leaves(node).items()
                 if which in k.split("/")[0].split("_")]
        if which not in ("gate", "up", "down") or len(found) != 1:
            raise KeyError(name)
        w = laguna._at(found[0], at)
        if w.shape != ((inter, self.hidden) if which == "down"
                       else (self.hidden, inter)):
            raise ValueError(f"{name} at {at}: {w.shape}")
        return w.T

    def __call__(self, name: str, layer: int = None, expert: int = None):
        import jax.numpy as jnp

        if name == "lm_head":
            return laguna._transposed_f32(
                self._get(self.tree, self.TOP[name]))
        if name in self.TOP:
            return super().__call__(name)
        group, _, rest = name.partition(".")
        if group in self.NORMS:
            return laguna._at(
                self.stack[f"{self.NORMS[group]}_{rest}"]["scale"], layer)
        if group == "self_attn":
            which, _, tensor = rest.partition(".")
            attn = self.stack[f"attn_{which}"]
            if tensor == "kv_b_proj":
                k_up = laguna._at(attn["k_up"], layer)      # [N, nope, r]
                v_up = laguna._at(attn["v_up"], layer)      # [N, r, v]
                return jnp.concatenate([k_up, v_up.swapaxes(1, 2)],
                                       axis=1).reshape(-1, k_up.shape[-1])
            w = laguna._at(self._get(attn, self.ATTENTION[tensor]), layer)
            return w.T if w.ndim == 2 else w
        if group == "mlps":
            which, _, tensor = rest.partition(".")
            return self._glu(self.stack[f"mlp_{which}"], layer, tensor,
                             self.widths["mlps"])
        if name.startswith("mlp.router."):
            w = laguna._at(self.stack["moe"]["router"][
                self.ROUTER[name.removeprefix("mlp.router.")]], layer)
            return w.T if w.ndim == 2 else w
        if name.startswith("mlp.experts."):
            if not 0 <= expert - self.first < self.held:
                raise KeyError(f"expert {expert} is held elsewhere")
            return self._glu(self.stack["moe"]["experts"],
                             (layer, expert - self.first),
                             name.removeprefix("mlp.experts."),
                             self.widths["experts"])
        raise KeyError(name)


published = Published
