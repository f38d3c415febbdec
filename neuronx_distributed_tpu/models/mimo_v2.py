"""MiMo-V2 (``model_type: mimo_v2_flash``;
huggingface.co/XiaomiMiMo/MiMo-V2-Flash): full-attention and
sliding-window layers whose K/V head counts differ, keys wider than the
values, a learnable sink term in the sliding layers' softmax, and a dense
first layer ahead of sigmoid-routed expert layers.

``hybrid_layer_pattern`` names each layer's attention, 0 full (causal
over every earlier position) or 1 sliding (causal over the last
``sliding_window``); ``moe_layer_freq`` its feed-forward, 0 the llama
SwiGLU or 1 the routed experts. Every layer has ``num_heads`` query heads
of ``head_dim`` (192) over K heads of the same size and V heads of
``v_head_dim`` (128): ``num_kv_heads`` of them in a full layer,
``swa_num_kv_heads`` in a sliding one. Rotary runs over the first
``rotary_dim`` values of a q or k head (theta ``rope_theta`` full,
``swa_rope_theta`` sliding); V is multiplied by ``attention_value_scale``
where it is projected. A sliding layer's softmax has one more column a
query head, the logit ``sink`` (a parameter ``[num_heads]``), which holds
no value: ``p_j = exp(s_j) / (exp(sink) + sum_j exp(s_j))``. An expert
layer scores all ``num_experts`` with a sigmoid in float32, chooses the
``top_k`` largest of score plus selection bias, and weighs the chosen by
their scores over the chosen scores' sum
(:class:`..modules.moe.routing.RouterSigmoid`); no shared expert.
``experts_held = (first, count)`` is the share of the routed experts this
device holds.

The pattern, the model and the paged forward over the two pools are
:mod:`.window_pool`'s; served, the full layers' pool and the rings have
unlike rows (:class:`..inference.paging.WindowPoolCache`, ``full_rows``
and ``window_rows``), the K rows in whole lanes
(:func:`..ops.paged_attention.keys_to_lanes`).

Not built: the multi-token-prediction modules the model is published
with (they do not enter the next-token logits).
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

ATTENTION = ("full", "sliding")
FEED_FORWARD = ("dense", "sparse")


@dataclass(frozen=True)
class MiMoV2Config(WindowPoolPattern, LlamaConfig):
    vocab_size: int = 152576
    hidden_size: int = 4096
    #: the dense layers' SwiGLU width
    intermediate_size: int = 16384
    num_layers: int = 48
    num_heads: int = 64
    #: the full-attention layers' K/V heads
    num_kv_heads: int = 4
    swa_num_kv_heads: int = 8
    head_dim: Optional[int] = 192
    v_head_dim: Optional[int] = 128
    max_seq_len: int = 262144
    rms_eps: float = 1e-5
    #: 0 full, 1 sliding, a layer
    hybrid_layer_pattern: Tuple[int, ...] = (
        (0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1) * 7 + (0,))
    #: 0 dense, 1 routed experts, a layer
    moe_layer_freq: Tuple[int, ...] = (0,) + (1,) * 47
    sliding_window: int = 128
    #: the leading values of a q or k head that are rotated
    rotary_dim: int = 64
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    attention_value_scale: float = 0.707
    #: a sink term in the sliding layers' softmax
    swa_sink: bool = True
    num_experts: int = 256
    top_k: int = 8
    moe_intermediate_size: int = 2048
    routed_scaling_factor: float = 1.0
    #: ``(first, count)`` of the routed experts held here (None: all)
    experts_held: Optional[Tuple[int, int]] = None
    #: this layer's attention and feed-forward (set by :meth:`kind_config`)
    layer_attn: str = "full"
    ff_kind: str = "sparse"

    def __post_init__(self) -> None:
        super().__post_init__()
        lists = (self.hybrid_layer_pattern, self.moe_layer_freq)
        if (any(len(x) != self.num_layers for x in lists)
                or any(t not in (0, 1) for x in lists for t in x)):
            raise ValueError(
                f"hybrid_layer_pattern and moe_layer_freq give 0 or 1 for "
                f"each of the {self.num_layers} layers; got {lists}")
        held = self.experts_held
        if held is not None and not (
                0 <= held[0] and held[1] > 0
                and held[0] + held[1] <= self.num_experts):
            raise ValueError(f"experts_held {held} is no share of "
                             f"{self.num_experts} experts")

    # -- the layer pattern --------------------------------------------------

    def kinds(self) -> Tuple[str, ...]:
        return tuple(f"{ATTENTION[a]}_{FEED_FORWARD[f]}" for a, f in
                     zip(self.hybrid_layer_pattern, self.moe_layer_freq))

    def kv_heads_of(self, attn: str) -> int:
        return (self.swa_num_kv_heads if attn == "sliding"
                else self.num_kv_heads)

    def kind_config(self, kind: str) -> "MiMoV2Config":
        """The config :class:`.llama.LlamaDecoderLayer` builds a layer of
        ``kind`` from."""
        attn, ff = kind.split("_")
        return dataclasses.replace(self, layer_attn=attn, ff_kind=ff)

    # -- the layer's two hooks ----------------------------------------------

    def attention(self, tp_sync: bool = True):
        return SinkAttention(self, name="attn")

    def feed_forward(self, h: jax.Array, tp_sync: bool = True, valid=None):
        """``(output, [kept, dropped, elsewhere])``: the routed
        assignments of the real rows (zeros from a dense layer), as
        :meth:`.laguna.LagunaConfig.feed_forward`: by capacity over the
        held experts at the capacity of the step's rows, so nothing held
        can drop."""
        if self.ff_kind == "dense":
            return (LlamaMLP(self, name="mlp")(h),
                    jnp.zeros((3,), jnp.int32))
        if valid is None:
            valid = jnp.ones(h.shape[:-1], bool)
        out, aux = MoE(
            num_experts=self.num_experts, hidden_size=self.hidden_size,
            intermediate_size=self.moe_intermediate_size,
            top_k=self.top_k, capacity_factor=None, router_type="sigmoid",
            router_scale=self.routed_scaling_factor,
            held=self.experts_held or (0, self.num_experts),
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="moe")(h, valid=valid)
        return out, aux["assignments"]

    # -- rotary -------------------------------------------------------------

    def rope_rows(self, positions: jax.Array):
        """``{attention type: (cos, sin)}`` at ``positions [T]``."""
        return {"full": attn_mod.rope_rows(positions, self.rotary_dim,
                                           self.rope_theta),
                "sliding": attn_mod.rope_rows(positions, self.rotary_dim,
                                              self.swa_rope_theta)}


def tiny_config(**kw) -> MiMoV2Config:
    """Test widths: the published head sizes (a wide-key pool's chunks are
    128 lanes) over few heads, two and four K/V heads, a window of two
    blocks of four, a dense first layer and half the experts held."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=5,
        num_heads=8, num_kv_heads=2, swa_num_kv_heads=4, max_seq_len=4096,
        hybrid_layer_pattern=(0, 1, 1, 0, 1), moe_layer_freq=(0, 1, 1, 1, 1),
        sliding_window=8, num_experts=8, top_k=3, moe_intermediate_size=32,
        experts_held=(0, 4))
    base.update(kw)
    return MiMoV2Config(**base)


class SinkAttention(nn.Module):
    """Full or sliding-window GQA with K heads of ``cfg.head_dim_`` beside
    V heads of ``cfg.v_head_dim_`` and, in a sliding layer, the sink term,
    behind :class:`.llama.LlamaAttention`'s call. ``rope`` is
    :meth:`MiMoV2Config.rope_rows` at the rows' own positions. No cache:
    the whole sequence, positions ``0..S-1``. A
    :class:`..inference.paging.PagedCacheView`: this step's rows are
    written into the view's layer of its pool and attended through the
    view's tables, within the view's ``sliding`` window if it has one."""

    cfg: MiMoV2Config

    @nn.compact
    def __call__(self, x, rope, sin=None, positions=None, cache=None,
                 cache_index=None):
        cfg = self.cfg
        d, dv = cfg.head_dim_, cfg.v_head_dim_
        heads, kv = cfg.num_heads, cfg.kv_heads_of(cfg.layer_attn)
        sliding = cfg.layer_attn == "sliding"

        def project(features, name):
            return pl.ColumnParallelLinear(
                features=features, use_bias=False, gather_output=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)(x)

        with device_scope("attn.proj"):
            b, s = x.shape[:2]
            q = rotate_leading(project(heads * d, "q_proj").reshape(
                b, s, heads, d), *rope[cfg.layer_attn])
            k = rotate_leading(project(kv * d, "k_proj").reshape(
                b, s, kv, d), *rope[cfg.layer_attn])
            v = (project(kv * dv, "v_proj") * cfg.attention_value_scale
                 ).reshape(b, s, kv, dv)
            sink = (self.param("sink", nn.initializers.zeros, (heads,),
                               jnp.float32).astype(jnp.float32)
                    if sliding and cfg.swa_sink else None)
        new_cache = None
        with device_scope("attn.kernel.window" if sliding
                          else "attn.kernel.full"):
            if cache is None:
                n_rep = heads // kv
                scores = jnp.einsum(
                    "bqnd,bknd->bnqk", q.astype(jnp.float32),
                    attn_mod.repeat_kv(k, n_rep).astype(jnp.float32)
                ) * cfg.attn_scale_
                at = jnp.arange(s)
                behind = at[:, None] - at[None, :]
                mask = behind >= 0
                if sliding:
                    mask = mask & (behind < cfg.sliding_window)
                scores = jnp.where(mask, scores, -1e30)
                if sink is not None:
                    scores = jnp.concatenate(
                        [scores, jnp.broadcast_to(
                            sink[None, :, None, None],
                            scores.shape[:3] + (1,))], axis=-1)
                probs = jax.nn.softmax(scores, -1)[..., :s]
                out = jnp.einsum(
                    "bnqk,bknd->bqnd", probs,
                    attn_mod.repeat_kv(v, n_rep).astype(jnp.float32)
                ).astype(cfg.dtype)
            else:
                out, new_cache = _paged_cache_attend(cfg, q, k, v, positions,
                                                     cache, sink=sink)
        with device_scope("attn.proj"):
            out = pl.RowParallelLinear(
                features=cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="o_proj")(
                out.reshape(b, s, heads * dv))
        if cache is not None:
            return out, new_cache
        return out


MiMoV2ForCausalLM = WindowPoolForCausalLM
mimo_v2_forward_with_cache = window_pool_forward_with_cache
