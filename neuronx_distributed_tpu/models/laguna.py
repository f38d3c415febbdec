"""Laguna (``model_type: laguna``; huggingface.co/poolside/Laguna-S-2.1):
full-attention and sliding-window layers in one decoder, a per-head
output gate, and a dense first layer ahead of expert layers.

``layer_types`` names each layer's attention: ``full_attention``, causal
over every earlier position, or ``sliding_attention``, causal over the
last ``sliding_window`` positions. The two have unlike head counts
(``heads_per_layer``: 48 and 72 query heads over the same 8 K/V heads) and
unlike rotary tables (``full_attention``: YaRN over the first
``full_rotary_dim`` values of a head, cos and sin times its
``attention_factor``; ``sliding_attention``: plain, over the whole head).
Either way a head's attended values are multiplied by ``sigmoid(g_proj(x))``,
one gate a head, ahead of ``o_proj``. ``mlp_layer_types`` names each
layer's feed-forward: ``dense``, the llama SwiGLU, or ``sparse``: a softmax
router over ``num_experts`` whose ``top_k`` renormalised weights are
multiplied by ``routed_scaling_factor``, the routed experts and one
always-on shared expert (:class:`..modules.moe.MoE`). ``experts_held =
(first, count)`` is the share of the routed experts this device holds: the
router scores them all, and a choice of an expert held elsewhere adds
nothing here.

The layer is :class:`.llama.LlamaDecoderLayer` under one derived config a
kind (attention type and feed-forward type: unlike parameter shapes), the
parameters one stack a kind, the layers one ``lax.scan`` a run of like
layers (:func:`.llama.run_layers`, as :mod:`.granite_hybrid`).

Served, a slot's sequence is in two places
(:class:`..inference.paging.WindowPoolCache`): blocks of the full layers'
pool for every position, and the slot's ring of the sliding layers' pool
for the last ``sliding_window``. The layer pattern's bookkeeping, the
model over it and the paged forward over the two pools are
:mod:`.window_pool`'s, which every family of such layers shares.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..modules import attention as attn_mod
from ..modules.moe import MoE
from ..obs.device_scopes import device_scope
from ..parallel import layers as pl
from .llama import LlamaConfig, LlamaMLP, _paged_cache_attend
from .window_pool import (WindowPoolForCausalLM, WindowPoolPattern,
                          rotate_leading, window_pool_forward_with_cache)

#: a published layer type -> the name it has here
ATTENTION = {"full_attention": "full", "sliding_attention": "sliding"}
FEED_FORWARD = ("dense", "sparse")


@dataclass(frozen=True)
class LagunaConfig(WindowPoolPattern, LlamaConfig):
    vocab_size: int = 100352
    hidden_size: int = 3072
    #: the dense layers' SwiGLU width
    intermediate_size: int = 12288
    num_layers: int = 48
    #: the full-attention layers' query heads (``num_attention_heads``)
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: Optional[int] = 128
    max_seq_len: int = 1048576
    rms_eps: float = 1e-6
    layer_types: Tuple[str, ...] = (
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention") * 12
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 47
    heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * 12
    sliding_window: int = 512
    # rope_parameters.full_attention: YaRN over the first full_rotary_dim
    # values of a head
    rope_theta: float = 500000.0
    full_rotary_dim: int = 64
    yarn_factor: float = 128.0
    yarn_original_max_position: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4852030263919618
    # rope_parameters.sliding_attention: plain, the whole head
    sliding_rope_theta: float = 10000.0
    num_experts: int = 256
    top_k: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.5
    #: ``(first, count)`` of the routed experts held here (None: all)
    experts_held: Optional[Tuple[int, int]] = None
    #: this layer's attention and feed-forward (set by :meth:`kind_config`)
    layer_attn: str = "full"
    ff_kind: str = "sparse"

    def __post_init__(self) -> None:
        super().__post_init__()
        lists = (self.layer_types, self.mlp_layer_types,
                 self.heads_per_layer)
        if any(len(x) != self.num_layers for x in lists):
            raise ValueError(
                f"layer_types, mlp_layer_types and heads_per_layer name "
                f"each of the {self.num_layers} layers; got "
                f"{[len(x) for x in lists]}")
        bad = ([t for t in self.layer_types if t not in ATTENTION]
               + [t for t in self.mlp_layer_types if t not in FEED_FORWARD])
        if bad:
            raise ValueError(f"layer types {sorted(set(bad))}: attention is "
                             f"one of {sorted(ATTENTION)}, a feed-forward "
                             f"one of {FEED_FORWARD}")
        for t in set(self.layer_types):
            if len({n for n, u in zip(self.heads_per_layer,
                                      self.layer_types) if u == t}) != 1:
                raise ValueError(f"the {t} layers are one stack a kind: "
                                 "they have one head count")
        held = self.experts_held
        if held is not None and not (
                0 <= held[0] and held[1] > 0
                and held[0] + held[1] <= self.num_experts):
            raise ValueError(f"experts_held {held} is no share of "
                             f"{self.num_experts} experts")

    # -- the layer pattern --------------------------------------------------

    def kinds(self) -> Tuple[str, ...]:
        """Each layer's kind, ``<attention>_<feed-forward>``."""
        return tuple(f"{ATTENTION[a]}_{f}" for a, f in
                     zip(self.layer_types, self.mlp_layer_types))

    def heads_of(self, attn: str) -> int:
        """Query heads of the layers of an attention type."""
        return next((n for t, n in zip(self.layer_types,
                                       self.heads_per_layer)
                     if ATTENTION[t] == attn), self.num_heads)

    def kind_config(self, kind: str) -> "LagunaConfig":
        """The config :class:`.llama.LlamaDecoderLayer` builds a layer of
        ``kind`` from: its attention type's head count."""
        attn, ff = kind.split("_")
        return dataclasses.replace(self, layer_attn=attn, ff_kind=ff,
                                   num_heads=self.heads_of(attn))

    # -- the layer's two hooks ----------------------------------------------

    def attention(self, tp_sync: bool = True):
        return GatedAttention(self, name="attn")

    def feed_forward(self, h: jax.Array, tp_sync: bool = True, valid=None):
        """``(output, [kept, dropped, elsewhere])``: the routed
        assignments of the real rows (zeros from a dense layer). The
        dispatch is by capacity over the held experts at the capacity of
        the step's rows, so nothing held can drop: at 128 rows the 128
        held experts' products are 309 GFLOP a layer, 1.6 ms of the MXU
        beside the 3.0 ms their 2.4 GB of weights take to stream."""
        if self.ff_kind == "dense":
            return (LlamaMLP(self, name="mlp")(h),
                    jnp.zeros((3,), jnp.int32))
        if valid is None:
            valid = jnp.ones(h.shape[:-1], bool)
        out, aux = MoE(
            num_experts=self.num_experts, hidden_size=self.hidden_size,
            intermediate_size=self.moe_intermediate_size,
            top_k=self.top_k, capacity_factor=None,
            router_scale=self.routed_scaling_factor,
            shared_expert_intermediate=self.shared_expert_intermediate_size,
            held=self.experts_held or (0, self.num_experts),
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="moe")(h, valid=valid)
        return out, aux["assignments"]

    # -- rotary -------------------------------------------------------------

    def rope_rows(self, positions: jax.Array):
        """``{attention type: (cos, sin)}`` at ``positions [T]``."""
        cos, sin = attn_mod.rope_rows(
            positions, self.full_rotary_dim, self.rope_theta,
            inv_freq=attn_mod.yarn_inv_freq(
                self.full_rotary_dim, self.rope_theta, self.yarn_factor,
                self.yarn_original_max_position, self.yarn_beta_fast,
                self.yarn_beta_slow))
        return {"full": (cos * self.yarn_attention_factor,
                         sin * self.yarn_attention_factor),
                "sliding": attn_mod.rope_rows(positions, self.head_dim_,
                                              self.sliding_rope_theta)}


def tiny_config(**kw) -> LagunaConfig:
    """Test widths: two head counts over two K/V heads, a window of two
    blocks of four, a dense first layer and half the experts held."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=5,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=4096,
        layer_types=("full_attention", "sliding_attention",
                     "sliding_attention", "sliding_attention",
                     "full_attention"),
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        heads_per_layer=(4, 6, 6, 6, 4), sliding_window=8,
        full_rotary_dim=8, yarn_original_max_position=16, yarn_factor=8.0,
        num_experts=8, top_k=3, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, experts_held=(0, 4))
    base.update(kw)
    return LagunaConfig(**base)


class GatedAttention(nn.Module):
    """Full or sliding-window GQA with a per-head output gate, behind
    :class:`.llama.LlamaAttention`'s call. ``rope`` is
    :meth:`LagunaConfig.rope_rows` at the rows' own positions. No cache:
    the whole sequence, positions ``0..S-1``. A
    :class:`..inference.paging.PagedCacheView`: this step's rows are
    written into the view's layer of its pool and attended through the
    view's tables, within the view's ``sliding`` window if it has one."""

    cfg: LagunaConfig

    @nn.compact
    def __call__(self, x, rope, sin=None, positions=None, cache=None,
                 cache_index=None):
        cfg = self.cfg
        d, sliding = cfg.head_dim_, cfg.layer_attn == "sliding"
        with device_scope("attn.proj"):
            q, k, v = pl.GQAQKVColumnParallelLinear(
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=d, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                tp_size=cfg.tp_size, name="qkv")(x)
            gate = pl.ColumnParallelLinear(
                features=cfg.num_heads, use_bias=False, gather_output=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="g_proj")(x)
            b, s = x.shape[:2]
            heads = q.shape[-1] // d
            q = rotate_leading(q.reshape(b, s, heads, d), *rope[cfg.layer_attn])
            k = rotate_leading(k.reshape(b, s, -1, d), *rope[cfg.layer_attn])
            v = v.reshape(b, s, -1, d)
        new_cache = None
        with device_scope("attn.kernel.window" if sliding
                          else "attn.kernel.full"):
            if cache is None:
                n_rep = heads // k.shape[2]
                scores = jnp.einsum(
                    "bqnd,bknd->bnqk", q.astype(jnp.float32),
                    attn_mod.repeat_kv(k, n_rep).astype(jnp.float32)
                ) * cfg.attn_scale_
                at = jnp.arange(s)
                behind = at[:, None] - at[None, :]
                mask = behind >= 0
                if sliding:
                    mask = mask & (behind < cfg.sliding_window)
                probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), -1)
                out = jnp.einsum(
                    "bnqk,bknd->bqnd", probs,
                    attn_mod.repeat_kv(v, n_rep).astype(jnp.float32)
                ).astype(cfg.dtype)
            else:
                out, new_cache = _paged_cache_attend(cfg, q, k, v, positions,
                                                     cache)
        with device_scope("attn.proj"):
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))[..., None]).astype(cfg.dtype)
            out = pl.RowParallelLinear(
                features=cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="o_proj")(
                out.reshape(b, s, heads * d))
        if cache is not None:
            return out, new_cache
        return out


LagunaForCausalLM = WindowPoolForCausalLM
laguna_forward_with_cache = window_pool_forward_with_cache
