"""The evabyte family: the llama family's projections, norms and MLP, with
EVA attention's per-head pooling vectors, norms that multiply by
``1 + w``, and one head of ``num_pred_heads * vocab_size`` outputs."""

from __future__ import annotations

from typing import Any, Callable, Tuple

from families import llama


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import evabyte

    cfg = evabyte.EvaByteConfig(**{
        **llama.common(c), "window_size": c["window_size"],
        "chunk_size": c["chunk_size"], "num_pred_heads": c["num_pred_heads"],
        "residual_fp32": bool(c["fp32_skip_add"]), **kw})
    return (cfg, evabyte.EvaByteForCausalLM(cfg),
            evabyte.evabyte_forward_with_cache)


class Published(llama.Published):
    """As the llama family's, with ``weights("phi", layer)`` and
    ``weights("mu", layer)`` ``[N, D]``; a norm reads as the checkpoint's
    ``w`` (the package stores the multiplier ``1 + w`` as ``scale``), and
    ``lm_head`` is ``[num_pred_heads * V, H]``."""

    PER_LAYER = dict(llama.Published.PER_LAYER,
                     phi=("attn", "eva_phi"), mu=("attn", "eva_mu"))
    NORMS = ("input_norm", "post_norm", "final_norm")

    def __call__(self, name: str, layer: int = None, expert: int = None):
        if name in ("phi", "mu"):
            return llama._f32(self._get(self.layers,
                                        self.PER_LAYER[name])[layer])
        w = super().__call__(name, layer, expert)
        return w - 1.0 if name in self.NORMS else w


published = Published
