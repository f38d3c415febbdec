"""The laguna family (Laguna-S-2.1): full-attention and sliding-window
layers of unlike head counts with a per-head output gate, one stack of
parameters a kind of layer (attention type and feed-forward type), a
softmax router over all the published experts, the routed experts this
device holds and a shared expert.

A configuration file may give the chip's share of a deployment under
``share``: ``num_experts`` and ``vocab_size`` are then what is held here
(both listed in its ``reduced``), ``share.num_experts_published`` what the
router scores, and ``share.first_expert`` the published index of the
first expert held."""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

from families import llama

ATTENTION = {"full_attention": "full", "sliding_attention": "sliding"}


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import laguna

    rope = c["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if (c["gating"] != "per-head" or set(c["gating_types"]) != {"per_head"}
            or c["attention_bias"] or c["decoder_sparse_step"] != 1
            or c["moe_apply_router_weight_on_input"]
            or c["moe_router_logit_softcapping"] or not c["norm_topk_prob"]
            or full["rope_type"] != "yarn"
            or sliding["rope_type"] != "default"
            or sliding["partial_rotary_factor"] != 1
            or [i for i, t in enumerate(c["mlp_layer_types"])
                if t == "dense"] != list(c["mlp_only_layers"])):
        raise ValueError("laguna: a per-head gate on every layer, no bias, "
                         "every layer outside mlp_only_layers sparse, the "
                         "router's weight on the expert's output, no "
                         "softcap, normalised top-k weights, YaRN on the "
                         "full layers and plain rotary over the whole head "
                         "on the sliding ones are what is built")
    share = c.get("share", {})
    cfg = laguna.LagunaConfig(**{
        "vocab_size": c["vocab_size"], "hidden_size": c["hidden_size"],
        "intermediate_size": c["intermediate_size"],
        "num_layers": c["num_hidden_layers"],
        "num_heads": c["num_attention_heads"],
        "num_kv_heads": c["num_key_value_heads"],
        "head_dim": c["head_dim"],
        "max_seq_len": int(c["max_position_embeddings"]),
        "rms_eps": float(c["rms_norm_eps"]),
        "tie_embeddings": bool(c["tie_word_embeddings"]),
        "layer_types": tuple(c["layer_types"]),
        "mlp_layer_types": tuple(c["mlp_layer_types"]),
        "heads_per_layer": tuple(c["num_attention_heads_per_layer"]),
        "sliding_window": c["sliding_window"],
        "rope_theta": float(full["rope_theta"]),
        "full_rotary_dim": int(round(full["partial_rotary_factor"]
                                     * c["head_dim"])),
        "yarn_factor": float(full["factor"]),
        "yarn_original_max_position": int(
            full["original_max_position_embeddings"]),
        "yarn_beta_fast": float(full["beta_fast"]),
        "yarn_beta_slow": float(full["beta_slow"]),
        "yarn_attention_factor": float(full["attention_factor"]),
        "sliding_rope_theta": float(sliding["rope_theta"]),
        "num_experts": int(share.get("num_experts_published",
                                     c["num_experts"])),
        "top_k": c["num_experts_per_tok"],
        "moe_intermediate_size": c["moe_intermediate_size"],
        "shared_expert_intermediate_size":
            c["shared_expert_intermediate_size"],
        "routed_scaling_factor": float(c["moe_routed_scaling_factor"]),
        "experts_held": (int(share.get("first_expert", 0)),
                         int(c["num_experts"])), **kw})
    return (cfg, laguna.LagunaForCausalLM(cfg),
            laguna.laguna_forward_with_cache)


def _transposed_f32(kernel):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda k: k.T.astype(jnp.float32))(kernel)


@functools.lru_cache(maxsize=None)
def _take():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames="count")
    def take(leaf, index, count):
        for i in range(count):
            leaf = jax.lax.dynamic_index_in_dim(leaf, index[i], 0, False)
        return leaf.astype(jnp.float32)

    return take


def _at(leaf, index):
    """``leaf[index]`` in float32 (``index`` an int or a pair of ints) by
    one program a leaf's shape, the index an operand: an index that is a
    constant of the program is a program to compile an expert of a layer
    (1,536 of them a check at 128 experts in 4 layers: most of the 773 s
    that the first check on the chip took)."""
    import numpy as np

    index = (index,) if isinstance(index, int) else tuple(index)
    return _take()(leaf, np.asarray(index, np.int32), len(index))


class Published(llama.Published):
    """As the llama family's, a layer found in its kind's stack
    (``model/layers_<attention>_<feed-forward>``), with the gate's
    projection, the router over all the published experts, the routed
    experts an expert at a time by the expert's published index (the held
    ones alone: another is a ``KeyError``) and the shared expert; the
    embedding and the head are the vocabulary's slice."""

    PER_LAYER = dict(llama.Published.PER_LAYER,
                     g_proj=("attn", "g_proj", "kernel"),
                     router=("moe", "router", "kernel"))

    def __init__(self, params, config: dict):
        self.tree = params["params"]
        self.hidden = config["hidden_size"]
        self.widths = {"dense": config["intermediate_size"],
                       "sparse": config["moe_intermediate_size"],
                       "shared": config["shared_expert_intermediate_size"]}
        self.first = int(config.get("share", {}).get("first_expert", 0))
        self.held = int(config["num_experts"])
        # a layer's stack and its index in it
        self.at, seen = [], {}
        for a, f in zip(config["layer_types"], config["mlp_layer_types"]):
            kind = f"{ATTENTION[a]}_{f}"
            self.at.append((kind, f, seen.get(kind, 0)))
            seen[kind] = seen.get(kind, 0) + 1

    def __call__(self, name: str, layer: int = None, expert: int = None):
        if name == "lm_head":
            return _transposed_f32(self._get(self.tree, self.TOP[name]))
        if name in self.TOP:
            return super().__call__(name)
        kind, ff, index = self.at[layer]
        stack = self.tree["model"][f"layers_{kind}"]["layer"]
        if name in self.PER_LAYER:
            w = _at(self._get(stack, self.PER_LAYER[name]), index)
            return w.T if w.ndim == 2 else w
        shared = name.startswith("shared_")
        name = name.removeprefix("shared_")
        if ff == "dense":
            node, at, inter = stack["mlp"], index, self.widths["dense"]
        elif shared:
            node, at, inter = (stack["moe"]["shared"], index,
                               self.widths["shared"])
        else:
            if not 0 <= expert - self.first < self.held:
                raise KeyError(f"expert {expert} is held elsewhere")
            node, at, inter = (stack["moe"]["experts"],
                               (index, expert - self.first),
                               self.widths["sparse"])
        # the package's leaves: ``down`` (or ``down/kernel``) ``[.., I, H]``
        # and the two leaves ``gate`` and ``up`` ``[.., H, I]``
        found = [v for k, v in llama._leaves(node).items()
                 if name in k.split("/")[0].split("_")]
        if name not in ("gate", "up", "down") or len(found) != 1:
            raise KeyError(name)
        w = _at(found[0], at)
        if w.shape != ((inter, self.hidden) if name == "down"
                       else (self.hidden, inter)):
            raise ValueError(f"{name} of layer {layer}: {w.shape}")
        return w.T


published = Published
