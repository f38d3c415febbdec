"""GLM-4.7-Flash (``model_type: glm4_moe_lite``;
huggingface.co/zai-org/GLM-4.7-Flash): latent attention and many small
experts on the llama decoder.

The layer is :class:`.llama.LlamaDecoderLayer` under one derived config a
feed-forward kind (:meth:`GlmMoeLiteConfig.kind_config`), with two
departures, each a hook of that layer and not a copy of it:

* **attention** (:class:`LatentAttention`, ``attention_kind="mla"``): a
  low-rank query (``q_a``, a norm, ``q_b``: heads of ``qk_nope + qk_rope``)
  and, for keys and values, one latent of ``kv_lora_rank`` values (normed)
  and one rotary key a position, shared by all heads (``kv_a``). ``kv_b``
  is stored absorbed, as the two leaves the served path multiplies by:
  ``k_up [N, nope, rank]`` into the query, ``v_up [N, rank, v]`` out of
  the attended latents (:mod:`..ops.mla_attention`), so a position's
  cached row ``[latent, rotary key]`` is all a step reads: 576 values
  where 20 heads of K and V would be 10,240
  (:class:`..inference.paging.LatentCache`).
* **feed-forward by kind**: ``first_k_dense`` leading layers with the
  llama SwiGLU, then expert layers: a sigmoid router with a selection
  bias (:class:`..modules.moe.routing.RouterSigmoid`), the routed experts
  and one always-on shared expert (:class:`..modules.moe.MoE`). In the
  packed serving step the pad rows take no expert's slot, and the real
  rows' kept and dropped assignments are counted on the device.

The two kinds have unlike parameter shapes, so the parameters are one
stack a kind (``layers_dense``, ``layers_moe``) and the layers run as
:func:`.llama.run_layers` runs them: one ``lax.scan`` a run of like
layers, the pool the carry across runs.

Left out: the multi-token-prediction module
(``num_nextn_predict_layers``): it does not enter the next-token logits,
and serving with it is self-drafting over a latent cache, which
``serving_family().unsupported`` names under ``speculation``.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax.core import meta

from ..modules import attention as attn_mod
from ..modules.attention import rope_rows
from ..modules.moe import MoE
from ..modules.norms import RMSNorm
from ..obs.device_scopes import device_scope
from ..ops import mla_attention as mla
from ..parallel import layers as pl
from ..parallel import loss_functions as lf
from ..parallel import mesh as ps
from .llama import LlamaConfig, LlamaMLP, _ScanBody, run_layers

KINDS = ("dense", "moe")


def carried(cfg, leaves=("rows", "moe_counts")):
    """What of the cache's stacks (``leaves``) a layer of a latent family
    reads and writes, whatever its kind."""
    return {kind: tuple(leaves) for kind, _, _ in cfg.runs()}


class LatentGeometry:
    """What :class:`LatentAttention` and :func:`latent_forward_with_cache`
    ask of a latent family's config beyond its fields
    (``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``rope_theta``), each with the plain family's answer: a mixin ahead
    of :class:`.llama.LlamaConfig` in the family's bases."""

    @property
    def head_dim_(self) -> int:
        """The width of a pool row as the kernel reads it, whole lanes:
        what the paged machinery asks a family's ``head_dim_`` for."""
        return mla.row_width(self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def score_scale(self) -> float:
        """What a head's scores are multiplied by ahead of the softmax."""
        return 1.0 / math.sqrt(self.qk_nope_head_dim
                               + self.qk_rope_head_dim)

    def rotary_rows(self, positions: jax.Array):
        """cos and sin ``[T, qk_rope_head_dim // 2]`` at ``positions
        [T]``, for the rotary key and the queries' rotary part."""
        return rope_rows(positions, self.qk_rope_head_dim, self.rope_theta)

    def step_walk(self, tables, q_pos, kv_cache):
        """The walk of one packed step that the family's attention kernel
        takes (once for all layers; None where the XLA path serves): the
        ``mla_paged_attention`` kernel's over the rows' whole contexts."""
        return mla.step_walk(tables, q_pos, kv_cache.block_size,
                             kv_cache.num_blocks, self.head_dim_,
                             self.kv_lora_rank, self.num_heads,
                             kv_cache.rows.dtype.itemsize,
                             force_pallas=self.attn_force_pallas)


@dataclass(frozen=True)
class GlmMoeLiteConfig(LatentGeometry, LlamaConfig):
    vocab_size: int = 154880
    hidden_size: int = 2048
    #: the leading dense layers' SwiGLU width
    intermediate_size: int = 10240
    num_layers: int = 47
    num_heads: int = 20
    #: of the pool: one row a position, shared by the heads
    num_kv_heads: int = 1
    max_seq_len: int = 202752
    rope_theta: float = 1e6
    attention_kind: str = "mla"
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    first_k_dense: int = 1
    num_experts: int = 64
    top_k: int = 4
    moe_intermediate_size: int = 1536
    num_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    #: what the normed low-rank query and the normed latent are multiplied
    #: by (:class:`LatentAttention`; 1.0 multiplies nothing): constants of
    #: this family, no fields
    q_lora_scale = 1.0
    kv_lora_scale = 1.0
    #: which feed-forward this layer has (set by :meth:`kind_config`)
    ff_kind: str = "moe"

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.ff_kind not in KINDS:
            raise ValueError(f"ff_kind must be one of {KINDS}")
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError("first_k_dense must lie within num_layers")

    def attention(self, tp_sync: bool = True):
        return LatentAttention(self, name="attn")

    def feed_forward(self, h: jax.Array, tp_sync: bool = True, valid=None):
        """``(output, [kept, dropped])``: the routed assignments of the
        real rows (zeros from a dense layer). The dispatch is by capacity
        at the factor where an expert's capacity is the step's rows
        (``num_experts / top_k``), so nothing can drop: the dropless
        blockwise kernels lose to it at these widths (ROADMAP R1)."""
        if self.ff_kind == "dense":
            return (LlamaMLP(self, name="mlp")(h),
                    jnp.zeros((2,), jnp.int32))
        if valid is None:
            valid = jnp.ones(h.shape[:-1], bool)
        out, aux = MoE(
            num_experts=self.num_experts, hidden_size=self.hidden_size,
            intermediate_size=self.moe_intermediate_size,
            top_k=self.top_k, capacity_factor=self.num_experts / self.top_k,
            router_type="sigmoid", router_scale=self.routed_scaling_factor,
            shared_expert_intermediate=(self.num_shared_experts
                                        * self.moe_intermediate_size),
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="moe")(h, valid=valid)
        return out, aux["assignments"]

    def kind_config(self, kind: str) -> "GlmMoeLiteConfig":
        return dataclasses.replace(self, ff_kind=kind)

    def layers_of(self, kind: str) -> int:
        dense = self.first_k_dense
        return dense if kind == "dense" else self.num_layers - dense

    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        return tuple((kind, 0, self.layers_of(kind)) for kind in KINDS
                     if self.layers_of(kind))

    def rows_layer(self, kind: str, layer):
        """A layer's rows in the cache's stack: the dense layers lead."""
        return layer + (0 if kind == "dense" else self.first_k_dense)

    def serving_family(self):
        from ..inference.paging import LatentCache, ServingFamily

        return ServingFamily(
            forward=glm_moe_lite_forward_with_cache,
            cache_kind=LatentCache(row=self.head_dim_), moe_counts=True,
            unsupported={
                "speculation": "the model's own prediction module "
                "(num_nextn_predict_layers) is left out, and a draft "
                "lane over latent rows (a step that yields more than one "
                "token) is not written",
                "cp": "the kernel computes no cross-rank combine and the "
                "XLA reference is no serving path for a latent pool",
                "quantized": "an int8 latent row wants scales of its own "
                "for the latent and the rotary key; no kernel reads them"})


class LatentAttention(nn.Module):
    """Multi-head latent attention, ``kv_b`` absorbed, behind
    :class:`.llama.LlamaAttention`'s call, for any config that has the
    latent fields (``num_heads``, ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    :class:`LatentGeometry`'s ``head_dim_`` (the pool row's lanes) and
    ``score_scale``, and ``q_lora_scale`` /
    ``kv_lora_scale``, what the normed low-rank query and the normed
    latent are multiplied by: the cached row holds the scaled latent).
    ``cos``/``sin`` are the rows'
    own (``[S, rope / 2]``: :func:`..modules.attention.rope_rows`). No cache:
    the whole sequence, causal, positions ``0..S-1``. A
    :class:`..inference.paging.LatentLayerView`: this step's rows are
    written into the view's layer of the row stack and attended through
    the table."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, cache=None,
                 cache_index=None):
        cfg = self.cfg
        nope, rope, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                            cfg.kv_lora_rank)
        heads = pl._maybe_local(cfg.num_heads, ps.TP_AXIS)
        b, s, _ = x.shape

        def dense(name, features):
            kernel = self.param(
                name, nn.with_partitioning(pl.default_kernel_init,
                                           (None, None)),
                (x.shape[-1], features), cfg.param_dtype)
            return jnp.dot(x.astype(cfg.dtype), kernel.astype(cfg.dtype))

        with device_scope("attn.proj"):
            c_q = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name="q_a_norm")(
                dense("q_a", cfg.q_lora_rank))
            if cfg.q_lora_scale != 1.0:
                # the factor in float32: rounded to bf16 it would be off
                # by a fixed part in a thousand at every position
                c_q = (c_q.astype(jnp.float32) * cfg.q_lora_scale
                       ).astype(c_q.dtype)
            q = pl.ColumnParallelLinear(
                features=cfg.num_heads * (nope + rope), use_bias=False,
                gather_output=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="q_b")(c_q)
            q = q.reshape(b, s, heads, nope + rope)
            kv = dense("kv_a", rank + rope)
            latent = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                             name="kv_a_norm")(kv[..., :rank])
            if cfg.kv_lora_scale != 1.0:
                latent = (latent.astype(jnp.float32) * cfg.kv_lora_scale
                          ).astype(latent.dtype)
            k_rope = attn_mod.apply_rotary(kv[..., None, rank:], cos, sin)
            q_rope = attn_mod.apply_rotary(q[..., nope:], cos, sin)
            k_up, v_up = (self.param(
                name, nn.with_partitioning(pl.default_kernel_init,
                                           (ps.TP_AXIS, None, None)),
                shape, cfg.param_dtype) for name, shape in (
                    ("k_up", (heads, nope, rank)),
                    ("v_up", (heads, rank, cfg.v_head_dim))))
            row = cfg.head_dim_
            rows = jnp.concatenate(
                [latent, k_rope[:, :, 0],
                 jnp.zeros((b, s, row - rank - rope), latent.dtype)], axis=-1)

            q_row = mla.absorb_queries(q[..., :nope], q_rope, k_up, row)
        ctx, new_cache = self.attend(x, c_q, cos, sin, q_row, rows, cache)
        with device_scope("attn.proj"):
            out = mla.expand_values(ctx, v_up)
            out = pl.RowParallelLinear(
                features=cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="o_proj")(
                out.reshape(b, s, heads * cfg.v_head_dim).astype(cfg.dtype))
        if cache is not None:
            return out, new_cache
        return out

    @nn.nowrap
    def attend(self, x, c_q, cos, sin, q_row, rows, cache):
        """``(ctx [B, S, N, rank], the view handed back or None)``: the
        absorbed queries ``q_row [B, S, N, row]`` over the step's own
        ``rows [B, S, row]`` (no cache: the whole sequence, causal) or,
        once those are written, over the pool through the view's table.
        ``x`` (the layer's input) and ``c_q`` (the normed low-rank query)
        are for a family whose rows choose what they attend
        (:mod:`.deepseek_v32`); called inside ``__call__``, so it may
        declare parameters."""
        cfg = self.cfg
        rank, scale = cfg.kv_lora_rank, cfg.score_scale
        if cache is None:
            with device_scope("attn.kernel"):
                s = q_row.shape[1]
                scores = jnp.einsum(
                    "btnw,bkw->bntk", q_row.astype(jnp.float32),
                    rows.astype(jnp.float32)) * scale
                causal = jnp.tril(jnp.ones((s, s), bool))
                probs = jax.nn.softmax(jnp.where(causal, scores, -1e30),
                                       axis=-1)
                ctx = jnp.einsum("bntk,bkr->btnr", probs,
                                 rows[..., :rank].astype(jnp.float32)
                                 ).astype(cfg.dtype)
            return ctx, None
        from ..inference import paging

        with device_scope("attn.pool_write"):
            pool = paging.write_pool_rows(cache.rows, rows[0],
                                          cache.write_idx, cache.layer)
        with device_scope("attn.kernel"):
            ctx = mla.mla_paged_attention(
                q_row[0], pool, cache.pos, cache.tables, cache.q_pos,
                cache.layer, rank, scale,
                force_pallas=cfg.attn_force_pallas,
                walk=cache.walk)[None]
        return ctx, cache.replace(rows=pool)


class GlmMoeLiteModel(nn.Module):
    """Embedding, the layer pattern, final norm: positions ``0..S-1``, no
    cache (tests, small training). Any latent family's: the config gives
    ``runs()``, ``kind_config()``, the layer module, the rotary rows and
    what the layers carry (``carry_in``, ``carry_out``)."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        with device_scope("embed"):
            x = pl.ParallelEmbedding(
                num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="embed")(input_ids)
        with device_scope("attn.proj"):
            cos, sin = cfg.rotary_rows(jnp.arange(input_ids.shape[1]))
        x = cfg.carry_in(x)
        if self.is_initializing():
            # the parameters: one stack a kind (the order the layers run
            # in is run_layers' business, and makes no parameter)
            for kind, _, count in cfg.runs():
                x, _ = nn.scan(
                    _ScanBody, variable_axes={"params": 0},
                    split_rngs={"params": True},
                    in_axes=(nn.broadcast,) * 3, length=count,
                    metadata_params={nn.PARTITION_NAME: "layers"},
                )(cfg.kind_config(kind), name=f"layers_{kind}")(
                    x, cos, sin, None)
        else:
            stacks = {kind: meta.unbox(
                self.variables["params"][f"layers_{kind}"])
                for kind, _, _ in cfg.runs()}
            x, _ = run_layers(cfg, stacks, x, cos, sin, carried(cfg))
        x = cfg.carry_out(x)
        with device_scope("norm"):
            return RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name="norm")(x)


class GlmMoeLiteForCausalLM(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array,
                 labels: Optional[jax.Array] = None,
                 ignore_index: int = -100) -> jax.Array:
        cfg = self.cfg
        x = GlmMoeLiteModel(cfg, name="model")(input_ids)
        with device_scope("head"):
            logits = pl.ColumnParallelLinear(
                features=cfg.vocab_size, use_bias=False, gather_output=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="lm_head")(x)
        if labels is not None:
            with device_scope("loss"):
                return lf.causal_lm_loss(logits, labels,
                                         ignore_index=ignore_index)
        return logits


def latent_forward_with_cache(cfg: LlamaConfig, params, input_ids,
                              positions, kv_cache, slot_ids=None,
                              **unsupported):
    """The paged forward of a latent family's packed serving step, with
    :func:`.llama.llama_forward_with_cache`'s paged signature:
    ``input_ids``, ``positions [1, T]``, ``slot_ids [T]``, ``kv_cache`` a
    :class:`..inference.paging.LatentPagedCache`; returns ``(logits [1,
    T, V], new cache)``. The row stack and the routed assignments' counts
    (of this step alone) are the carry of every run's scan;
    ``cfg.rows_layer(kind, layer)`` says where in the stack a layer keeps
    its (first attention's) rows, ``cfg.carry_in`` / ``carry_out`` what
    the layers hand on of the embedded rows (themselves, unless the
    family's residual is wider: ``models/xing4.py``)."""
    from ..inference import paging
    from ..inference.kv_cache import PAD_POSITION

    family = type(cfg).__name__
    if any(unsupported.values()):
        raise ValueError(f"{family} serves through the packed paged "
                         f"step only; got {sorted(unsupported)}")
    if not isinstance(kv_cache, paging.LatentPagedCache):
        raise ValueError(f"{family} is served from the cache its cache "
                         "kind builds (paging.init_serving_cache)")
    p = params["params"]
    q_pos = jnp.asarray(positions, jnp.int32)[0]
    slot_ids = jnp.asarray(slot_ids, jnp.int32)
    with device_scope("embed"):
        x = pl.ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype).apply(
            {"params": p["model"]["embed"]}, input_ids)
    with device_scope("attn.proj"):
        cos, sin = cfg.rotary_rows(jnp.minimum(q_pos, cfg.max_seq_len - 1))
    kind = cfg.serving_family().cache_kind.geometry(kv_cache.block_size)
    with device_scope("attn.walk"):
        tables = kv_cache.block_tables[
            jnp.clip(slot_ids, 0, kv_cache.max_slots - 1)]
        write_idx = paging.flat_write_indices(
            tables, q_pos, kv_cache.block_size, kv_cache.capacity, kind)
    with device_scope("attn.pool_write"):
        pool_pos = paging.write_pool_positions(kv_cache.pos, q_pos,
                                               write_idx)
    with device_scope("attn.walk"):
        walk = cfg.step_walk(tables, q_pos, kv_cache)
    # the stacks a layer writes (the pool's leaves) and the counts it adds
    # to, of this step alone (``counts``: of a family whose rows select)
    beside = tuple(name for name in ("counts",)
                   if getattr(kv_cache, name, None) is not None)
    written = kv_cache.POOL_LEAVES + beside

    def view_of(kind, carry, layer):
        return paging.LatentLayerView(
            layer=cfg.rows_layer(kind, layer), pos=pool_pos, tables=tables,
            write_idx=write_idx, q_pos=q_pos, walk=walk,
            slots=kv_cache.max_slots,
            **{name: carry[name] for name in written})

    def merge(carry, view, assignments):
        return dict({name: getattr(view, name) for name in written},
                    moe_counts=carry["moe_counts"] + assignments)

    carry = dict({name: getattr(kv_cache, name)
                  for name in kv_cache.POOL_LEAVES},
                 moe_counts=jnp.zeros_like(kv_cache.moe_counts),
                 **{name: jnp.zeros_like(getattr(kv_cache, name))
                    for name in beside})
    stacks = {kind: p["model"][f"layers_{kind}"]
              for kind, _, _ in cfg.runs()}
    x, carry = run_layers(cfg, stacks, cfg.carry_in(x), cos, sin,
                          carried(cfg, carry), carry, view_of, merge,
                          valid=(q_pos < PAD_POSITION)[None])
    x = cfg.carry_out(x)
    with device_scope("norm"):
        x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype).apply(
            {"params": p["model"]["norm"]}, x)
    with device_scope("head"):
        logits = pl.ColumnParallelLinear(
            features=cfg.vocab_size, use_bias=False, gather_output=True,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype).apply(
            {"params": p["lm_head"]}, x)
    return logits, kv_cache.replace(pos=pool_pos, **carry)


glm_moe_lite_forward_with_cache = latent_forward_with_cache
