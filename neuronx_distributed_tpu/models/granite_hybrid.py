"""Granite 4.0-H (``granitemoehybrid``): a decoder whose layers are Mamba-2
state-space mixers with a few attention layers among them
(huggingface.co/ibm-granite/granite-4.0-h-micro). ``layer_types`` names
each layer's mixer: ``mamba``, one ``in_proj`` to ``[z | x B C | dt]``, a
depthwise causal convolution of width ``mamba_d_conv`` over ``x B C``, the
selective scan (:mod:`..ops.ssd`), a gated RMSNorm and ``out_proj``; or
``attention``, GQA without rotary embedding (NoPE) whose scores are
multiplied by ``attention_multiplier`` and not by ``1 / sqrt(head_dim)``.
Both are followed by the llama SwiGLU (the *shared* MLP; the family's
routed experts are not built: ``num_local_experts`` is 0 in the dense
models), under Granite's four multipliers: the embedding times
``embedding_multiplier``, each residual branch times
``residual_multiplier``, the logits over ``logits_scaling``, tied to the
embedding.

The layer is :class:`.llama.LlamaDecoderLayer` under one derived config a
kind, the parameters one stack a kind, the layers one ``lax.scan`` a run
of like layers (:func:`.llama.run_layers`, as :mod:`.minicpm_sala`).

Served, a slot's sequence is in three places
(:class:`..inference.paging.StatePoolCache`): K/V blocks of the attention
layers alone, and two per-slot state leaves of the mamba layers, ``ssm
[Lm, J, d_state, d_inner]`` float32 and the convolution's tail ``conv
[Lm, d_conv - 1, J, d_inner + 2 d_state]``. Heads of 64 lie two a pool
row, so the Pallas paged kernel serves them.
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

from ..modules.norms import RMSNorm
from ..obs.device_scopes import device_scope
from ..ops import ssd
from ..parallel import layers as pl
from ..parallel import loss_functions as lf
from .llama import LlamaConfig, _ScanBody, run_layers, runs_of

#: a published layer type -> the layer's ``attention_kind``
LAYER_KINDS = {"mamba": "mamba2", "attention": "full"}
#: granite-4.0-h-micro: an attention layer at 5, 15, 25 and 35 of 40
PUBLISHED_LAYERS = tuple("attention" if i % 10 == 5 else "mamba"
                         for i in range(40))
#: what of the cache's stacks a layer of each kind reads and writes
CARRIED = {"full": ("k", "v"), "mamba2": ("ssm", "conv")}


@dataclass(frozen=True)
class GraniteHybridConfig(LlamaConfig):
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192        # shared_intermediate_size
    num_layers: int = 40
    num_heads: int = 32
    num_kv_heads: int = 8
    max_seq_len: int = 131072
    rms_eps: float = 1e-5
    tie_embeddings: bool = True
    use_rope: bool = False               # position_embedding_type "nope"
    #: each layer's mixer, a key of :data:`LAYER_KINDS`
    layer_types: Tuple[str, ...] = PUBLISHED_LAYERS
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0

    def __post_init__(self) -> None:
        super().__post_init__()
        bad = [t for t in self.layer_types if t not in LAYER_KINDS]
        if bad or len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types must name one of {sorted(LAYER_KINDS)} for "
                f"each of the {self.num_layers} layers, got "
                f"{self.layer_types}")
        if self.mamba_n_groups != 1:
            raise ValueError("one group of B and C is what ops/ssd.py "
                             "computes")

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def pool_pack(self) -> int:
        """K/V heads side by side on a pool row: as many as fit 128 lanes
        and divide the K/V heads."""
        pack = max(1, 128 // self.head_dim_)
        while self.num_kv_heads % pack:
            pack -= 1
        return pack

    def kind_config(self, kind: str) -> "GraniteHybridConfig":
        """The config :class:`.llama.LlamaDecoderLayer` builds a layer of
        ``kind`` from."""
        return dataclasses.replace(
            self, attention_kind=kind, attn_scale=self.attention_multiplier,
            residual_scale=self.residual_multiplier)

    def attention(self, tp_sync: bool = True):
        if self.attention_kind == "mamba2":
            return Mamba2Mixer(self, tp_sync=tp_sync, name="attn")
        return super().attention(tp_sync)

    def kinds(self) -> Tuple[str, ...]:
        return tuple(LAYER_KINDS[t] for t in self.layer_types)

    def layers_of(self, kind: str) -> int:
        return self.kinds().count(kind)

    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """``(kind, first, count)`` of each run of like layers, ``first``
        the run's first index in its kind's stack."""
        return runs_of(self.kinds())

    def serving_family(self):
        from ..inference.paging import (ServingFamily, StateLeaf,
                                        StatePoolCache)

        layers = self.layers_of("mamba2")
        return ServingFamily(
            forward=granite_hybrid_forward_with_cache,
            cache_kind=StatePoolCache(
                pool_layers=self.layers_of("full"), pack=self.pool_pack,
                leaves=(
                    StateLeaf("ssm", (layers,),
                              (self.mamba_d_state, self.d_inner),
                              jnp.float32),
                    StateLeaf("conv", (layers, self.mamba_d_conv - 1),
                              (self.conv_channels,), counted_as="tail"))),
            unsupported={
                "prefix_sharing": "a mamba layer's state and convolution "
                "tail are no blocks: a shared prefix's blocks carry "
                "neither to resume from",
                "speculation": "a lane clone copies blocks, and a rejected "
                "draft row has already advanced its slot's state and "
                "shifted its tail",
                "cp": "the per-slot states are not sharded over a cp axis",
                "quantized": "the states are float32 beside the pool, and "
                "two heads a pool row want scales of their own",
                "session_export": "a shipped session's blocks leave its "
                "states and tails behind"})


def tiny_config(**kw) -> GraniteHybridConfig:
    """Test widths: heads of 16 (two a pool row), a state of [8, 64]."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=5,
        num_heads=4, num_kv_heads=2, max_seq_len=4096,
        layer_types=("mamba", "attention", "mamba", "mamba", "attention"),
        mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8, mamba_d_conv=4,
        mamba_chunk_size=8, attention_multiplier=0.0625)
    base.update(kw)
    return GraniteHybridConfig(**base)


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    """The inverse softplus of a step drawn log-uniformly in [1e-3, 1e-1]
    (Mamba-2's ``dt_min``, ``dt_max``)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _conv_init(key, shape, dtype):
    bound = 1.0 / math.sqrt(shape[-1])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer in :class:`.llama.LlamaAttention`'s place.
    ``cache`` is None (a whole sequence at positions ``0..S-1``) or a
    :class:`..inference.paging.StateSpaceLayerView` of the packed step."""

    cfg: GraniteHybridConfig
    tp_sync: bool = True

    @nn.compact
    def __call__(self, x, cos=None, sin=None, positions=None, cache=None,
                 cache_index=None):
        cfg = self.cfg
        heads, width, n = cfg.mamba_n_heads, cfg.mamba_d_head, \
            cfg.mamba_d_state
        inner, chans, taps = cfg.d_inner, cfg.conv_channels, cfg.mamba_d_conv
        with device_scope("attn.proj"):
            zxbcdt = pl.ColumnParallelLinear(
                features=inner + chans + heads, use_bias=False,
                gather_output=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="in_proj")(x)
            z, xbc, dt = jnp.split(zxbcdt, (inner, inner + chans), axis=-1)
        conv_w = self.param("conv_kernel", _conv_init, (chans, taps),
                            cfg.param_dtype)
        conv_b = self.param("conv_bias", nn.initializers.zeros_init(),
                            (chans,), cfg.param_dtype)
        a = -jnp.exp(self.param("A_log", _a_log_init, (heads,),
                                cfg.param_dtype).astype(jnp.float32))
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,),
                             cfg.param_dtype)
        skip = self.param("D", nn.initializers.ones_init(), (heads,),
                          cfg.param_dtype)
        b_, s_ = x.shape[:2]
        new_cache = None
        if cache is None:
            with device_scope("attn.conv"):
                padded = jnp.pad(xbc.astype(jnp.float32),
                                 ((0, 0), (taps - 1, 0), (0, 0)))
                w = conv_w.astype(jnp.float32)
                conv = conv_b.astype(jnp.float32) + sum(
                    w[:, k] * padded[:, k:k + s_] for k in range(taps))
                xbc = jax.nn.silu(conv).astype(cfg.dtype)
        else:
            with device_scope("attn.conv"):
                conv, tails = ssd.causal_conv_step(
                    xbc[0], cache.conv, cache.layer, conv_w, conv_b,
                    cache.seg)
                xbc = conv.astype(cfg.dtype)[None]
        with device_scope("attn.state"):
            xs, b, c = jnp.split(xbc, (inner, inner + n), axis=-1)
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + dt_bias.astype(jnp.float32))
            xs = xs.reshape(b_, s_, heads, width)
            if cache is None:
                y = ssd.ssd_full(xs, dt, a, b, c, skip,
                                 chunk=cfg.mamba_chunk_size)
            else:
                y, states = ssd.ssd_packed(
                    xs[0], dt[0], a, b[0], c[0], skip, cache.ssm,
                    cache.layer, cache.seg,
                    force_pallas=cfg.attn_force_pallas)
                y = y[None]
                new_cache = cache.replace(ssm=states, conv=tails)
        with device_scope("attn.proj"):
            # gate first, then one norm over all of d_inner
            y = y.reshape(b_, s_, inner) * jax.nn.silu(z.astype(jnp.float32))
            y = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name="norm")(y)
            out = pl.RowParallelLinear(
                features=cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, tp_sync=self.tp_sync,
                name="out_proj")(y)
        if cache is not None:
            return out, new_cache
        return out


class GraniteHybridModel(nn.Module):
    """Embedding, the layer pattern, final norm: positions ``0..S-1``, no
    cache (tests, small training)."""

    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        with device_scope("embed"):
            x = pl.ParallelEmbedding(
                num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="embed")(input_ids) * cfg.embedding_multiplier
        if self.is_initializing():
            # the parameters: one stack a kind, each made by scanning the
            # kind's layer over its depth
            for kind in CARRIED:
                x, _ = nn.scan(
                    _ScanBody, variable_axes={"params": 0},
                    split_rngs={"params": True},
                    in_axes=(nn.broadcast,) * 3,
                    length=cfg.layers_of(kind),
                    metadata_params={nn.PARTITION_NAME: "layers"},
                )(cfg.kind_config(kind), name=f"layers_{kind}")(
                    x, None, None, None)
        else:
            stacks = {kind: meta.unbox(
                self.variables["params"][f"layers_{kind}"])
                for kind in CARRIED}
            x, _ = run_layers(cfg, stacks, x, None, None, CARRIED)
        with device_scope("norm"):
            return RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name="norm")(x)


class GraniteHybridForCausalLM(nn.Module):
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array,
                 labels: Optional[jax.Array] = None,
                 ignore_index: int = -100) -> jax.Array:
        cfg = self.cfg
        model = GraniteHybridModel(cfg, name="model")
        x = model(input_ids)
        table = meta.unbox(model.variables["params"]["embed"]["embedding"])
        with device_scope("head"):
            logits = pl.embedding_attend(
                table, x, dtype=cfg.dtype) / cfg.logits_scaling
        if labels is not None:
            with device_scope("loss"):
                return lf.causal_lm_loss(logits, labels,
                                         ignore_index=ignore_index)
        return logits


def granite_hybrid_forward_with_cache(cfg: GraniteHybridConfig, params,
                                      input_ids, positions, kv_cache,
                                      slot_ids=None, **unsupported):
    """The paged forward of the packed serving step, with
    :func:`.llama.llama_forward_with_cache`'s paged signature:
    ``input_ids``, ``positions [1, T]``, ``slot_ids [T]``, ``kv_cache`` a
    :class:`..inference.paging.StatePoolPagedCache`; returns ``(logits
    [1, T, V], new cache)``. The cache's stacks (K/V of the attention
    layers, the mamba layers' states and tails) are the carry of every
    run's scan."""
    from ..inference import paging
    from ..ops import paged_attention as pa

    if any(unsupported.values()):
        raise ValueError(f"granite_hybrid serves through the packed paged "
                         f"step only; got {sorted(unsupported)}")
    if not isinstance(kv_cache, paging.StatePoolPagedCache):
        raise ValueError("granite_hybrid is served from the cache its cache "
                         "kind builds (paging.init_serving_cache)")
    p = params["params"]
    q_pos = jnp.asarray(positions, jnp.int32)[0]
    slot_ids = jnp.asarray(slot_ids, jnp.int32)
    with device_scope("embed"):
        x = pl.ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype).apply(
            {"params": p["model"]["embed"]}, input_ids) \
            * cfg.embedding_multiplier
    kind = cfg.serving_family().cache_kind.geometry(kv_cache.block_size)
    with device_scope("attn.walk"):
        tables = kv_cache.block_tables[
            jnp.clip(slot_ids, 0, kv_cache.max_slots - 1)]
        write_idx = paging.flat_write_indices(
            tables, q_pos, kv_cache.block_size, kv_cache.capacity, kind)
        walk = pa.step_walk(
            tables, q_pos, kv_cache.block_size, kv_cache.num_blocks,
            cfg.head_dim_ * kind.pack,
            cfg.num_heads // cfg.num_kv_heads * kind.pack,
            force_pallas=cfg.attn_force_pallas,
            pools=(kv_cache.k, kv_cache.v))
        seg = ssd.step_segments(slot_ids, q_pos, kv_cache.max_slots)
    with device_scope("attn.pool_write"):
        pool_pos = paging.write_pool_positions(kv_cache.pos, q_pos,
                                               write_idx)

    def view_of(kind, carry, layer):
        if kind == "full":
            return paging.PagedCacheView(
                k=carry["k"], v=carry["v"], k_scale=None, v_scale=None,
                layer=layer, pos=pool_pos, tables=tables,
                write_idx=write_idx, walk=walk)
        return paging.StateSpaceLayerView(
            ssm=carry["ssm"], conv=carry["conv"], layer=layer, seg=seg)

    carry = dict(k=kv_cache.k, v=kv_cache.v, **kv_cache.states)
    stacks = {kind: p["model"][f"layers_{kind}"] for kind in CARRIED}
    x, carry = run_layers(cfg, stacks, x, None, None, CARRIED, carry,
                          view_of, positions=q_pos[None])
    with device_scope("norm"):
        x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype).apply(
            {"params": p["model"]["norm"]}, x)
    with device_scope("head"):
        logits = pl.embedding_attend(
            p["model"]["embed"]["embedding"], x, dtype=cfg.dtype,
            gather_output=True) / cfg.logits_scaling
    return logits, kv_cache.replace(
        k=carry["k"], v=carry["v"], pos=pool_pos,
        states={name: carry[name] for name in kv_cache.states})
