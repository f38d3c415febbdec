"""EvaByte: a byte-level decoder with EVA chunked linearized attention
(huggingface.co/EvaByte/EvaByte; EVA: arXiv:2302.04542).

The llama stack with three departures, each a switch of what is there and
not a copy of it: ``attention_kind="eva"`` (``LlamaAttention`` gains the
per-head pooling vectors ``eva_phi`` and ``eva_mu`` and calls
:mod:`..ops.eva_attention`), ``residual_fp32`` (``fp32_skip_add``), and a
head of ``num_pred_heads * vocab_size`` outputs, output ``j`` at a
position predicting byte ``t + 1 + j``. The norms' unit offset
(``norm_add_unit_offset``: ``x / rms * (1 + w)``) is folded into the
stored parameter: ``RMSNorm.scale`` holds the multiplier ``1 + w``, so a
checkpoint's ``w`` loads as ``w + 1``.

Serving keeps, per sequence, exact K/V for its current window and one
summary row pair per chunk of every earlier window, as two kinds of row
of the one paged pool (:class:`..inference.paging.WindowSummaryCache`).
Multi-byte self-speculative decoding over the 8 heads is not here: the
serving forward computes the next-byte head only.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..obs.device_scopes import device_scope
from ..parallel import layers as pl
from .llama import LlamaConfig, LlamaModel, llama_forward_with_cache


@dataclass(frozen=True)
class EvaByteConfig(LlamaConfig):
    vocab_size: int = 320
    num_layers: int = 32
    max_seq_len: int = 32768
    rope_theta: float = 100000.0
    attention_kind: str = "eva"
    residual_fp32: bool = True
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.num_heads != self.num_kv_heads:
            raise ValueError("EVA pools per head: num_kv_heads must equal "
                             "num_heads")
        if self.window_size % self.chunk_size:
            raise ValueError(
                f"window_size {self.window_size} must be whole chunks of "
                f"{self.chunk_size}")

    def serving_family(self):
        from ..inference.paging import ServingFamily, WindowSummaryCache

        return ServingFamily(
            forward=evabyte_forward_with_cache,
            cache_kind=WindowSummaryCache(self.window_size, self.chunk_size),
            unsupported=dict.fromkeys(
                ("prefix_sharing", "speculation", "cp", "quantized",
                 "session_export"),
                "its window-summary cache does not keep position // "
                "block_size rows (a ring block is not a prefix, a lane "
                "clone, a cp shard, an int8 row or a shipped session)"))


def tiny_config(**kw) -> EvaByteConfig:
    """Test widths: a window is still 4 blocks of 8 and a window's
    summaries one block."""
    base = dict(hidden_size=64, intermediate_size=128, num_layers=2,
                num_heads=4, num_kv_heads=4, window_size=32, chunk_size=4,
                max_seq_len=1024)
    base.update(kw)
    return EvaByteConfig(**base)


class EvaByteForCausalLM(nn.Module):
    """Body + the multi-byte head: logits ``[B, S, num_pred_heads,
    vocab_size]`` float32 (``fp32_logits``). The full forward (no cache)
    is what training and the CPU tests use."""

    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array,
                 positions: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        x, _ = LlamaModel(cfg, name="model")(input_ids, positions)
        with device_scope("head"):
            logits = pl.ColumnParallelLinear(
                features=cfg.num_pred_heads * cfg.vocab_size, use_bias=False,
                gather_output=True, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="lm_head")(x)
            return logits.astype(jnp.float32).reshape(
                *logits.shape[:-1], cfg.num_pred_heads, cfg.vocab_size)


def evabyte_forward_with_cache(cfg: EvaByteConfig, params, input_ids,
                               positions, kv_cache, slot_ids=None, **kw):
    """:func:`.llama.llama_forward_with_cache` over the next-byte head
    (the first ``vocab_size`` of the head's outputs): ``(logits [B, S,
    vocab_size] float32, new_cache)``."""
    p = params["params"]
    head = {"kernel": p["lm_head"]["kernel"][:, :cfg.vocab_size]}
    out = llama_forward_with_cache(
        cfg, {"params": {**p, "lm_head": head}}, input_ids, positions,
        kv_cache, slot_ids=slot_ids, **kw)
    with device_scope("head"):
        return (out[0].astype(jnp.float32),) + tuple(out[1:])
