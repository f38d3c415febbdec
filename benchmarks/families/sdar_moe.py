"""The sdar_moe family (SDAR-30B-A3B-Chat): the Qwen3-MoE decoder (GQA with
an RMSNorm over each head of q and k, a softmax router over all the
experts, the top-k renormalised, no shared expert) under a block-causal
mask, generated from by diffusion over blocks.

The published config has no key for how a block is decoded. The
configuration file states ``block_length``, ``denoising_steps``,
``confidence_threshold`` and ``mask_token_id`` beside the published keys
(each under ``assumed``), and its serving check feeds the probe in groups
of ``logit_check.group`` rows: :func:`build` refuses a file in which that
group and ``block_length`` differ, since a row's logits depend on its
whole block."""

from __future__ import annotations

from typing import Any, Callable, Tuple

from families import laguna, llama


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.inference.sampling import BlockDecoding
    from neuronx_distributed_tpu.models import sdar

    if (c["attention_bias"] or c["decoder_sparse_step"] != 1
            or c["mlp_only_layers"] or c["use_sliding_window"]
            or c["rope_scaling"] is not None or not c["norm_topk_prob"]
            or c["hidden_act"] != "silu"):
        raise ValueError("sdar_moe: no bias, every layer sparse, no sliding "
                         "window, plain rotary, normalised top-k weights "
                         "and SiLU are what is built")
    group = c.get("serve", {}).get("logit_check", {}).get("group")
    if group is not None and int(group) != int(c["block_length"]):
        raise ValueError(
            f"sdar_moe: logit_check.group {group} is not block_length "
            f"{c['block_length']}: a row attends its whole block, so the "
            "serving check feeds a block's rows in one step")
    cfg = sdar.SdarConfig(**{
        **llama.common(c),
        # the experts' width is what the package calls intermediate_size
        "intermediate_size": c["moe_intermediate_size"],
        "num_experts": c["num_experts"], "top_k": c["num_experts_per_tok"],
        "block_decoding": BlockDecoding(
            block_length=int(c["block_length"]),
            denoising_steps=int(c["denoising_steps"]),
            confidence_threshold=float(c["confidence_threshold"]),
            mask_token_id=int(c["mask_token_id"])), **kw})
    return cfg, sdar.SdarForCausalLM(cfg), sdar.sdar_forward_with_cache


class Published(llama.Published):
    """As the llama family's under the Qwen3-MoE tensor names'
    meanings: ``q_norm`` and ``k_norm [head_dim]`` of a layer,
    ``weights("router", layer) [E, H]``, and ``gate``, ``up [I, H]`` and
    ``down [H, I]`` an expert at a time, ``I`` the experts' width. A layer
    or an expert is read by one program a leaf's shape, the index an
    operand (``families/laguna.py`` ``_at``)."""

    PER_LAYER = dict(llama.Published.PER_LAYER,
                     q_norm=("attn", "q_norm", "scale"),
                     k_norm=("attn", "k_norm", "scale"),
                     router=("moe", "router", "kernel"))

    def __init__(self, params, config: dict):
        super().__init__(params, config)
        self.inter = config["moe_intermediate_size"]

    def __call__(self, name: str, layer: int = None, expert: int = None):
        if name == "lm_head":
            return laguna._transposed_f32(self._get(self.tree,
                                                    self.TOP[name]))
        if name in self.TOP:
            return super().__call__(name)
        if name in self.PER_LAYER:
            w = laguna._at(self._get(self.layers, self.PER_LAYER[name]),
                           layer)
            return w.T if w.ndim == 2 else w
        if name not in ("gate", "up", "down"):
            raise KeyError(name)
        w = laguna._at(self.layers["moe"]["experts"][name], (layer, expert))
        if w.shape != ((self.inter, self.hidden) if name == "down"
                       else (self.hidden, self.inter)):
            raise ValueError(f"{name} of layer {layer}: {w.shape}")
        return w.T


published = Published
