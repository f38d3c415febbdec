"""The glm_moe_lite family (GLM-4.7-Flash): latent attention whose
``kv_b`` the package stores absorbed (``k_up``, ``v_up``), one stack of
leading dense layers and one of expert layers, a sigmoid router with a
selection bias, routed experts and a shared expert."""

from __future__ import annotations

from typing import Any, Callable, Tuple

from families import llama


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import glm_moe_lite

    if (c["n_group"] != 1 or c["topk_group"] != 1
            or c["topk_method"] != "noaux_tc" or c.get("rope_scaling")
            or c["partial_rotary_factor"] != 1
            or c.get("num_nextn_predict_layers")
            or not c["norm_topk_prob"] or c["hidden_act"] != "silu"
            or c["attention_bias"]):
        raise ValueError("glm_moe_lite: no group limit, the noaux_tc "
                         "selection with normalised weights, plain rotary "
                         "over the whole rotary key, SiLU, no bias and no "
                         "prediction module are what is built")
    cfg = glm_moe_lite.GlmMoeLiteConfig(**{
        "vocab_size": c["vocab_size"], "hidden_size": c["hidden_size"],
        "intermediate_size": c["intermediate_size"],
        "num_layers": c["num_hidden_layers"],
        "num_heads": c["num_attention_heads"],
        "rope_theta": float(c["rope_theta"]),
        "rms_eps": float(c["rms_norm_eps"]),
        "max_seq_len": int(c["max_position_embeddings"]),
        "q_lora_rank": c["q_lora_rank"], "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_head_dim": c["qk_nope_head_dim"],
        "qk_rope_head_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"],
        "first_k_dense": c["first_k_dense_replace"],
        "num_experts": c["n_routed_experts"],
        "top_k": c["num_experts_per_tok"],
        "moe_intermediate_size": c["moe_intermediate_size"],
        "num_shared_experts": c["n_shared_experts"],
        "routed_scaling_factor": float(c["routed_scaling_factor"]), **kw})
    return (cfg, glm_moe_lite.GlmMoeLiteForCausalLM(cfg),
            glm_moe_lite.glm_moe_lite_forward_with_cache)


def _transposed_f32(kernel):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda k: k.T.astype(jnp.float32))(kernel)


class Published(llama.Published):
    """As the llama family's, a layer found in its kind's stack
    (``model/layers_dense``, ``model/layers_moe``), with the latent
    attention's tensors (``kv_b_proj`` put together from the absorbed
    leaves in the checkpoint's shape, a head's key rows and then its value
    rows), the router and its bias, the routed experts an expert at a
    time and the shared expert."""

    PER_LAYER = {"input_norm": ("input_norm", "scale"),
                 "post_norm": ("post_norm", "scale"),
                 "q_a_proj": ("attn", "q_a"),
                 "q_a_norm": ("attn", "q_a_norm", "scale"),
                 "q_b_proj": ("attn", "q_b", "kernel"),
                 "kv_a_proj": ("attn", "kv_a"),
                 "kv_a_norm": ("attn", "kv_a_norm", "scale"),
                 "o_proj": ("attn", "o_proj", "kernel"),
                 "router": ("moe", "router", "kernel"),
                 "router_bias": ("moe", "router", "bias")}

    def __init__(self, params, config: dict):
        self.tree = params["params"]
        self.hidden = config["hidden_size"]
        self.dense = config["first_k_dense_replace"]
        self.widths = (config["intermediate_size"],
                       config["moe_intermediate_size"])

    def where(self, layer):
        """A layer's stack and its index in it."""
        kind, index = (("dense", layer) if layer < self.dense
                       else ("moe", layer - self.dense))
        return self.tree["model"][f"layers_{kind}"]["layer"], index

    def __call__(self, name: str, layer: int = None, expert: int = None):
        import jax.numpy as jnp

        if name == "lm_head":
            # [V, H] float32 in one program: no float32 copy of the
            # package's [H, V] beside it (1.18 GiB at 154,880 columns)
            return _transposed_f32(self._get(self.tree, self.TOP[name]))
        if name in self.TOP:
            return super().__call__(name)
        stack, index = self.where(layer)
        if name in self.PER_LAYER:
            w = llama._f32(self._get(stack, self.PER_LAYER[name])[index])
            return w.T if w.ndim == 2 else w
        if name == "kv_b_proj":
            k_up = llama._f32(stack["attn"]["k_up"][index])  # [N, nope, r]
            v_up = llama._f32(stack["attn"]["v_up"][index])  # [N, r, v]
            return jnp.concatenate([k_up, v_up.swapaxes(1, 2)],
                                   axis=1).reshape(-1, k_up.shape[-1])
        shared = name.startswith("shared_")
        name = name.removeprefix("shared_")
        if layer < self.dense:
            node, at, inter = stack["mlp"], index, self.widths[0]
        elif shared:
            node, at, inter = stack["moe"]["shared"], index, self.widths[1]
        else:
            node, at, inter = (stack["moe"]["experts"], (index, expert),
                               self.widths[1])
        if name == "down":
            (down,) = [v for k, v in llama._leaves(node).items()
                       if "down" in k]
            return llama._f32(down[at]).T
        if name in ("gate", "up"):
            return llama.gate_or_up(node, at, int(name == "up"),
                                    self.hidden, inter).T
        raise KeyError(name)


published = Published
