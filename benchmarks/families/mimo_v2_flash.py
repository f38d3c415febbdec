"""The mimo_v2_flash family (MiMo-V2-Flash): full-attention and
sliding-window layers of unlike K/V head counts, keys of 192 values beside
values of 128, a sink term in the sliding layers' softmax, one stack of
parameters a kind of layer, a sigmoid router with a selection bias over
all the published experts and the routed experts this device holds.

A configuration file may give the chip's share of a deployment under
``share``: ``n_routed_experts`` and ``vocab_size`` are then what is held
here (both listed in its ``reduced``), ``share.n_routed_experts_published``
what the router scores, and ``share.first_expert`` the published index of
the first expert held.

**The sinks' values.** ``harness.make_weights`` draws every leaf that is
no norm's scale N(0, ``initializer_range``): a sink of 0.02 is ``exp(b) =
1`` beside a window's sum of hundreds, and a program that left the term
out would pass every check. Where the configuration file gives
``seeded_sink_range: [lo, hi]`` the family serves each drawn sink leaf
``u`` as ``b = lo + (hi - lo) Phi(u / initializer_range)``, uniform in
the range ([2, 5]: ``exp(b)`` 7 to 148, of the order of a window's sum):
inside the step the engine times, in the probe of the logit check and in
``published`` alike (:func:`seeded_sinks`; the configuration's
``assumed`` lists it). Without the key the leaves are the sinks.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Tuple

from families import laguna, llama

ATTENTION = ("full", "sliding")
FEED_FORWARD = ("dense", "sparse")


def seeded_sinks(leaf, drawn):
    """The sink logits served for a drawn leaf: ``drawn = (std, lo, hi)``,
    uniform in ``[lo, hi]`` for a leaf N(0, std); None: the leaf."""
    import jax
    import jax.numpy as jnp

    if drawn is None:
        return leaf
    std, lo, hi = drawn
    return lo + (hi - lo) * jax.scipy.stats.norm.cdf(
        leaf.astype(jnp.float32) / std)


def _forward(drawn, cfg, params, *args, **kw):
    import jax

    from neuronx_distributed_tpu.models import mimo_v2

    params = jax.tree_util.tree_map_with_path(
        lambda path, x: (seeded_sinks(x, drawn) if jax.tree_util.keystr(
            path).endswith("['sink']") else x), params)
    return mimo_v2.mimo_v2_forward_with_cache(cfg, params, *args, **kw)


@functools.lru_cache(maxsize=None)
def _config_class(drawn):
    """The package's config; with ``drawn``, one whose served forward
    reads the sinks as :func:`seeded_sinks` of the leaves."""
    from neuronx_distributed_tpu.models import mimo_v2

    if drawn is None:
        return mimo_v2.MiMoV2Config

    @dataclasses.dataclass(frozen=True)
    class SeededSinks(mimo_v2.MiMoV2Config):
        def serving_family(self):
            return dataclasses.replace(
                super().serving_family(),
                forward=functools.partial(_forward, drawn))

    return SeededSinks


def _drawn(c: dict):
    lo_hi = c.get("seeded_sink_range")
    return lo_hi and (float(c["initializer_range"]),) + tuple(
        map(float, lo_hi))


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import mimo_v2

    if (c["attention_bias"] or c["scoring_func"] != "sigmoid"
            or c["n_group"] != 1 or c["topk_group"] != 1
            or c["topk_method"] != "noaux_tc" or c["n_shared_experts"]
            or c["add_full_attention_sink_bias"] or not c["norm_topk_prob"]
            or c["hidden_act"] != "silu"
            or (c["swa_num_attention_heads"], c["swa_head_dim"],
                c["swa_v_head_dim"]) != (
                c["num_attention_heads"], c["head_dim"], c["v_head_dim"])
            or c["sliding_window_size"] != c["sliding_window"]):
        raise ValueError(
            "mimo_v2_flash: no bias, a sigmoid router with the noaux_tc "
            "selection, no groups, normalised weights, no shared expert, "
            "no sink term in the full layers, SiLU, and one head count and "
            "one pair of head sizes for both layer types are what is built")
    share = c.get("share", {})
    cfg = _config_class(_drawn(c))(**{
        "vocab_size": c["vocab_size"], "hidden_size": c["hidden_size"],
        "intermediate_size": c["intermediate_size"],
        "num_layers": c["num_hidden_layers"],
        "num_heads": c["num_attention_heads"],
        "num_kv_heads": c["num_key_value_heads"],
        "swa_num_kv_heads": c["swa_num_key_value_heads"],
        "head_dim": c["head_dim"], "v_head_dim": c["v_head_dim"],
        "max_seq_len": int(c["max_position_embeddings"]),
        "rms_eps": float(c["layernorm_epsilon"]),
        "tie_embeddings": bool(c["tie_word_embeddings"]),
        "hybrid_layer_pattern": tuple(c["hybrid_layer_pattern"]),
        "moe_layer_freq": tuple(c["moe_layer_freq"]),
        "sliding_window": c["sliding_window"],
        "rotary_dim": int(c["head_dim"] * c["partial_rotary_factor"]),
        "rope_theta": float(c["rope_theta"]),
        "swa_rope_theta": float(c["swa_rope_theta"]),
        "attention_value_scale": float(c["attention_value_scale"]),
        "swa_sink": bool(c["add_swa_attention_sink_bias"]),
        "num_experts": int(share.get("n_routed_experts_published",
                                     c["n_routed_experts"])),
        "top_k": c["num_experts_per_tok"],
        "moe_intermediate_size": c["moe_intermediate_size"],
        "routed_scaling_factor": float(c["routed_scaling_factor"] or 1.0),
        "experts_held": (int(share.get("first_expert", 0)),
                         int(c["n_routed_experts"])), **kw})
    return (cfg, mimo_v2.MiMoV2ForCausalLM(cfg),
            cfg.serving_family().forward)


class Published(llama.Published):
    """As the llama family's, a layer found in its kind's stack
    (``model/layers_<attention>_<feed-forward>``), with a sliding layer's
    sinks as they are served (:func:`seeded_sinks`), the router over all
    the published experts and its bias, and the routed experts an expert
    at a time by the expert's published index (the held ones alone:
    another is a ``KeyError``); the embedding and the head are the
    vocabulary's slice."""

    PER_LAYER = {"input_norm": ("input_norm", "scale"),
                 "post_norm": ("post_norm", "scale"),
                 "q_proj": ("attn", "q_proj", "kernel"),
                 "k_proj": ("attn", "k_proj", "kernel"),
                 "v_proj": ("attn", "v_proj", "kernel"),
                 "o_proj": ("attn", "o_proj", "kernel"),
                 "attention_sink_bias": ("attn", "sink"),
                 "router": ("moe", "router", "kernel"),
                 "router_bias": ("moe", "router", "bias")}

    def __init__(self, params, config: dict):
        self.tree = params["params"]
        self.hidden = config["hidden_size"]
        self.drawn = _drawn(config)
        self.widths = {"dense": config["intermediate_size"],
                       "sparse": config["moe_intermediate_size"]}
        self.first = int(config.get("share", {}).get("first_expert", 0))
        self.held = int(config["n_routed_experts"])
        # a layer's stack and its index in it
        self.at, seen = [], {}
        for a, f in zip(config["hybrid_layer_pattern"],
                        config["moe_layer_freq"]):
            kind = f"{ATTENTION[a]}_{FEED_FORWARD[f]}"
            self.at.append((kind, FEED_FORWARD[f], seen.get(kind, 0)))
            seen[kind] = seen.get(kind, 0) + 1

    def __call__(self, name: str, layer: int = None, expert: int = None):
        if name == "lm_head":
            return laguna._transposed_f32(self._get(self.tree,
                                                    self.TOP[name]))
        if name in self.TOP:
            return super().__call__(name)
        kind, ff, index = self.at[layer]
        stack = self.tree["model"][f"layers_{kind}"]["layer"]
        if name in self.PER_LAYER:
            w = laguna._at(self._get(stack, self.PER_LAYER[name]), index)
            if name == "attention_sink_bias":
                return seeded_sinks(w, self.drawn)
            return w.T if w.ndim == 2 else w
        if ff == "dense":
            node, at = stack["mlp"], index
        elif not 0 <= expert - self.first < self.held:
            raise KeyError(f"expert {expert} is held elsewhere")
        else:
            node, at = stack["moe"]["experts"], (index, expert - self.first)
        # the package's leaves: ``down`` (or ``down/kernel``) ``[.., I, H]``
        # and the two leaves ``gate`` and ``up`` ``[.., H, I]``
        found = [v for k, v in llama._leaves(node).items()
                 if name in k.split("/")[0].split("_")]
        if name not in ("gate", "up", "down") or len(found) != 1:
            raise KeyError(name)
        w = laguna._at(found[0], at)
        inter = self.widths[ff]
        if w.shape != ((inter, self.hidden) if name == "down"
                       else (self.hidden, inter)):
            raise ValueError(f"{name} of layer {layer}: {w.shape}")
        return w.T


published = Published
