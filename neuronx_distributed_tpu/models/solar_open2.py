"""Solar Open 2 (``model_type: solar_open2``;
huggingface.co/upstage/Solar-Open2-250B): a decoder whose layers are Kimi
Delta Attention (KDA, arXiv:2510.26692) mixers with a gated NoPE GQA layer
every fourth, and sigmoid-routed experts beside one shared expert after
every mixer.

``gqa_layers`` names the softmax layers; every other layer is a KDA
mixer: ``q, k, v`` projections, a depthwise causal convolution of
``kda_conv`` taps and SiLU over each, ``q`` and ``k`` L2-normalised a
head, a decay a *channel* ``g = -exp(A_log[h]) softplus(W_fb (W_fa x) +
dt_bias)`` through a low-rank pair, ``beta = 2 sigmoid(W_b x)`` a head
(the 2: ``kda_allow_neg_eigval``), the delta rule (:mod:`..ops.kda`), a
per-head RMSNorm times a sigmoid output gate through a second low-rank
pair, and ``o_proj``. A GQA layer has no rotary embedding and an
elementwise sigmoid output gate ahead of ``o_proj``. The feed-forward of
every layer scores all ``num_experts`` with a sigmoid in float32, chooses
``top_k`` of score plus selection bias, weighs the chosen by their scores
over the chosen scores' sum (:class:`..modules.moe.routing.RouterSigmoid`)
and adds one shared SwiGLU expert. ``experts_held = (first, count)`` is
the share of the routed experts this device holds.

The layer is :class:`.llama.LlamaDecoderLayer` under one derived config a
kind, the parameters one stack a kind, the layers one ``lax.scan`` a run
of like layers (:func:`.llama.run_layers`).

Served, a slot's sequence is in three places
(:class:`..inference.paging.StatePoolCache`): K/V blocks of the GQA layers
alone, and two per-slot leaves of the KDA layers, ``kda [Lk, J, H, dk,
dv]`` float32 and the convolution's tail ``conv [Lk, kda_conv - 1, J, 3 H
dk]``; the routed assignments of a step are counted into the cache's
``moe_counts``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax.core import meta

from ..modules import attention as attn_mod
from ..modules.moe import MoE
from ..modules.norms import RMSNorm
from ..obs.device_scopes import device_scope
from ..ops import kda, ssd
from ..parallel import layers as pl
from ..parallel import loss_functions as lf
from .granite_hybrid import _a_log_init, _conv_init, _dt_bias_init
from .llama import (LlamaConfig, _paged_cache_attend, _ScanBody, run_layers,
                    runs_of)

#: what of the cache's stacks a layer of each kind reads and writes
CARRIED = {"full": ("k", "v", "moe_counts"),
           "kda": ("kda", "conv", "moe_counts")}
#: Solar-Open2-250B: a GQA layer at 0, 4, ..., 44 of 48
PUBLISHED_GQA_LAYERS = tuple(range(0, 48, 4))
#: what a published config must say for this module to be its model
_BUILT = {"model_type": "solar_open2", "use_rope": False,
          "use_gqa_gate": True, "kda_use_full_proj": False,
          "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
          "n_shared_experts": 1, "norm_topk_prob": True,
          "tie_word_embeddings": False}
#: published keys that nothing reads where the model is as :data:`_BUILT`
#: says: no rotary embedding, no dense layer, the pattern's list beside its
#: interval
_UNREAD = ("partial_rotary_factor", "rope_theta", "gqa_interval")
#: every key of a published config that :meth:`SolarOpen2Config.from_published`
#: reads, holds to :data:`_BUILT` or knows that nothing reads
PUBLISHED_KEYS = frozenset(_BUILT) | frozenset(_UNREAD) | frozenset((
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "max_position_embeddings", "rms_norm_eps", "gqa_layers",
    "linear_attn_config", "n_routed_experts", "num_experts_per_tok",
    "moe_intermediate_size", "routed_scaling_factor"))


@dataclass(frozen=True)
class SolarOpen2Config(LlamaConfig):
    vocab_size: int = 196608
    hidden_size: int = 4096
    #: read by nothing: every layer's feed-forward is the experts'
    intermediate_size: int = 10240
    num_layers: int = 48
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: Optional[int] = 128
    max_seq_len: int = 1048576
    rms_eps: float = 1e-5
    use_rope: bool = False
    #: the layers that are gated GQA; the others are KDA mixers
    gqa_layers: Tuple[int, ...] = PUBLISHED_GQA_LAYERS
    kda_heads: int = 64
    kda_head_dim: int = 128
    kda_conv: int = 4
    #: :func:`..ops.kda.kda_full`'s chunk (the no-cache forward)
    kda_chunk: int = 64
    num_experts: int = 320
    top_k: int = 8
    moe_intermediate_size: int = 1280
    shared_expert_intermediate_size: int = 1280
    routed_scaling_factor: float = 1.0
    #: ``(first, count)`` of the routed experts held here (None: all)
    experts_held: Optional[Tuple[int, int]] = None
    #: this layer's mixer, ``full`` or ``kda`` (set by :meth:`kind_config`)
    layer_mixer: str = "full"

    def __post_init__(self) -> None:
        super().__post_init__()
        if (sorted(set(self.gqa_layers)) != list(self.gqa_layers)
                or any(not 0 <= i < self.num_layers
                       for i in self.gqa_layers)):
            raise ValueError(f"gqa_layers names layers of 0.."
                             f"{self.num_layers - 1} in order, got "
                             f"{self.gqa_layers}")
        held = self.experts_held
        if held is not None and not (
                0 <= held[0] and held[1] > 0
                and held[0] + held[1] <= self.num_experts):
            raise ValueError(f"experts_held {held} is no share of "
                             f"{self.num_experts} experts")

    @classmethod
    def from_published(cls, c: dict, **kw) -> "SolarOpen2Config":
        """The config of a published ``config.json``'s keys
        (:data:`PUBLISHED_KEYS`): each is read here, is one that nothing
        reads (:data:`_UNREAD`), or must say what this module builds
        (:data:`_BUILT`: another value is refused by name). ``kw`` are
        this class's fields (dtype, ``experts_held``; ``num_experts``
        where the file's ``n_routed_experts`` is a share)."""
        lin = c["linear_attn_config"]
        wrong = {k: c.get(k) for k, v in _BUILT.items() if c.get(k) != v}
        if lin["num_kv_heads"] not in (None, lin["num_heads"]):
            wrong["linear_attn_config.num_kv_heads"] = lin["num_kv_heads"]
        if set(lin) - {"short_conv_kernel_size", "head_dim", "num_heads",
                       "num_kv_heads"}:
            wrong["linear_attn_config"] = sorted(lin)
        if wrong:
            raise ValueError(f"solar_open2 builds {_BUILT} and a KDA layer "
                             f"with a k and v head a q head; the config "
                             f"says {wrong}")
        return cls(**{**dict(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            intermediate_size=c["intermediate_size"],
            num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            max_seq_len=int(c["max_position_embeddings"]),
            rms_eps=float(c["rms_norm_eps"]),
            gqa_layers=tuple(c["gqa_layers"]),
            kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
            kda_conv=lin["short_conv_kernel_size"],
            num_experts=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"],
            moe_intermediate_size=c["moe_intermediate_size"],
            shared_expert_intermediate_size=(c["n_shared_experts"]
                                             * c["moe_intermediate_size"]),
            routed_scaling_factor=float(c["routed_scaling_factor"])), **kw})

    # -- the layer pattern --------------------------------------------------

    def kinds(self) -> Tuple[str, ...]:
        return tuple("full" if i in self.gqa_layers else "kda"
                     for i in range(self.num_layers))

    def layers_of(self, kind: str) -> int:
        return self.kinds().count(kind)

    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """``(kind, first, count)`` of each run of like layers, ``first``
        the run's first index in its kind's stack."""
        return runs_of(self.kinds())

    def kind_config(self, kind: str) -> "SolarOpen2Config":
        """The config :class:`.llama.LlamaDecoderLayer` builds a layer of
        ``kind`` from."""
        return dataclasses.replace(self, layer_mixer=kind)

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    # -- the layer's two hooks ----------------------------------------------

    def attention(self, tp_sync: bool = True):
        if self.layer_mixer == "kda":
            return KDAMixer(self, name="attn")
        return GatedNoPEAttention(self, name="attn")

    def feed_forward(self, h: jax.Array, tp_sync: bool = True, valid=None):
        """``(output, [kept, dropped, elsewhere])``: the routed
        assignments of the real rows. By capacity over the held experts at
        the capacity of the step's rows, so nothing held can drop."""
        if valid is None:
            valid = jnp.ones(h.shape[:-1], bool)
        out, aux = MoE(
            num_experts=self.num_experts, hidden_size=self.hidden_size,
            intermediate_size=self.moe_intermediate_size,
            top_k=self.top_k, capacity_factor=None, router_type="sigmoid",
            router_scale=self.routed_scaling_factor,
            shared_expert_intermediate=self.shared_expert_intermediate_size,
            held=self.experts_held or (0, self.num_experts),
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="moe")(h, valid=valid)
        return out, aux["assignments"]

    def serving_family(self):
        from ..inference.paging import (ServingFamily, StateLeaf,
                                        StatePoolCache)

        layers = self.layers_of("kda")
        state = "a KDA layer's state and convolution tail are no blocks"
        return ServingFamily(
            forward=solar_open2_forward_with_cache,
            cache_kind=StatePoolCache(
                pool_layers=self.layers_of("full"),
                leaves=(
                    StateLeaf("kda", (layers,),
                              (self.kda_heads, self.kda_head_dim,
                               self.kda_head_dim), jnp.float32),
                    StateLeaf("conv", (layers, self.kda_conv - 1),
                              (3 * self.kda_inner,), counted_as="tail"))),
            moe_counts=True,
            unsupported={
                "prefix_sharing": state + ": a shared prefix's blocks carry "
                "neither to resume from",
                "session_export": state + ": a shipped session's blocks "
                "leave them behind",
                "speculation": "a lane clone copies blocks, and a rejected "
                "draft row has already corrected its slot's state through "
                "its key and shifted its tail",
                "cp": "the per-slot states are not sharded over a cp axis",
                "quantized": "the states are float32 beside the pool, and "
                "no kernel reads an int8 pool's scales for them"})


def tiny_config(**kw) -> SolarOpen2Config:
    """Test widths: two periods of a GQA layer and three or two KDA
    layers, two KDA heads of the published 128 (the kernel's lanes), half
    the experts held."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=7,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=4096,
        gqa_layers=(0, 4), kda_heads=2, kda_head_dim=128, kda_chunk=8,
        num_experts=8, top_k=3, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, experts_held=(0, 4))
    base.update(kw)
    return SolarOpen2Config(**base)


def _output_gate(logits):
    """A mixer's output gate from its logits, elementwise, float32."""
    return jax.nn.sigmoid(logits.astype(jnp.float32))


def _project(cfg, features, name, x, use_bias=False):
    return pl.ColumnParallelLinear(
        features=features, use_bias=use_bias, gather_output=False,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)(x)


class KDAMixer(nn.Module):
    """The KDA mixer in :class:`.llama.LlamaAttention`'s place. ``cache``
    is None (a whole sequence at positions ``0..S-1``) or a
    :class:`..inference.paging.StateSpaceLayerView` of the packed step
    (``ssm`` the delta-rule states' stack)."""

    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, x, cos=None, sin=None, positions=None, cache=None,
                 cache_index=None):
        cfg = self.cfg
        heads, d, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
        inner = cfg.kda_inner
        b_, s_ = x.shape[:2]
        with device_scope("attn.proj"):
            qkv = _project(cfg, 3 * inner, "qkv_proj", x)
            # the decay's and the gate's low-rank inputs and beta's logits
            low = _project(cfg, 2 * d + heads, "low_proj", x)
            f_in, g_in, beta = jnp.split(low, (d, 2 * d), axis=-1)
            a = -jnp.exp(self.param("A_log", _a_log_init, (heads,),
                                    cfg.param_dtype).astype(jnp.float32))
            dt_bias = self.param("dt_bias", _dt_bias_init, (inner,),
                                 cfg.param_dtype)
            g = jnp.repeat(a, d) * jax.nn.softplus(
                _project(cfg, inner, "f_b_proj", f_in).astype(jnp.float32)
                + dt_bias.astype(jnp.float32))
            beta = 2.0 * jax.nn.sigmoid(beta.astype(jnp.float32))
            gate = _output_gate(_project(cfg, inner, "g_b_proj", g_in,
                                         use_bias=True))
        conv_w = self.param("conv_kernel", _conv_init, (3 * inner, taps),
                            cfg.param_dtype)
        new_cache = None
        with device_scope("attn.conv"):
            if cache is None:
                padded = jnp.pad(qkv.astype(jnp.float32),
                                 ((0, 0), (taps - 1, 0), (0, 0)))
                w = conv_w.astype(jnp.float32)
                qkv = jax.nn.silu(sum(w[:, i] * padded[:, i:i + s_]
                                      for i in range(taps)))
            else:
                qkv, tails = ssd.causal_conv_step(
                    qkv[0], cache.conv, cache.layer, conv_w,
                    jnp.zeros((3 * inner,), jnp.float32), cache.seg)
                qkv = qkv[None]
        with device_scope("attn.state"):
            q, k, v = (t.reshape(b_, s_, heads, d).astype(jnp.float32)
                       for t in jnp.split(qkv.astype(cfg.dtype), 3, axis=-1))
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True)
                                  + 1e-6) * d ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            g = g.reshape(b_, s_, heads, d)
            if cache is None:
                o = kda.kda_full(q, k, v, g, beta, chunk=cfg.kda_chunk)
            else:
                o, states = kda.kda_packed(
                    q[0], k[0], v[0], g[0], beta[0], cache.ssm, cache.layer,
                    cache.seg, force_pallas=cfg.attn_force_pallas)
                o = o[None]
                new_cache = cache.replace(ssm=states, conv=tails)
        with device_scope("attn.proj"):
            o = RMSNorm(eps=cfg.rms_eps, dtype=jnp.float32,
                        name="o_norm")(o)
            o = (o.reshape(b_, s_, inner) * gate).astype(cfg.dtype)
            out = pl.RowParallelLinear(
                features=cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="o_proj")(o)
        if cache is not None:
            return out, new_cache
        return out


class GatedNoPEAttention(nn.Module):
    """Causal GQA without rotary embedding, its output times an
    elementwise sigmoid gate ahead of ``o_proj``, behind
    :class:`.llama.LlamaAttention`'s call. No cache: the whole sequence.
    A :class:`..inference.paging.PagedCacheView`: this step's rows are
    written into the view's layer of the pool and attended through the
    view's tables."""

    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, x, cos=None, sin=None, positions=None, cache=None,
                 cache_index=None):
        cfg = self.cfg
        heads, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
        b, s = x.shape[:2]
        with device_scope("attn.proj"):
            q = _project(cfg, heads * d, "q_proj", x).reshape(b, s, heads, d)
            k = _project(cfg, kv * d, "k_proj", x).reshape(b, s, kv, d)
            v = _project(cfg, kv * d, "v_proj", x).reshape(b, s, kv, d)
            gate = _project(cfg, heads * d, "g_proj", x)
        new_cache = None
        with device_scope("attn.kernel"):
            if cache is None:
                n_rep = heads // kv
                scores = jnp.einsum(
                    "bqnd,bknd->bnqk", q.astype(jnp.float32),
                    attn_mod.repeat_kv(k, n_rep).astype(jnp.float32)
                ) * cfg.attn_scale_
                at = jnp.arange(s)
                probs = jax.nn.softmax(jnp.where(
                    at[:, None] >= at[None, :], scores, -1e30), -1)
                out = jnp.einsum(
                    "bnqk,bknd->bqnd", probs,
                    attn_mod.repeat_kv(v, n_rep).astype(jnp.float32)
                ).astype(cfg.dtype)
            else:
                out, new_cache = _paged_cache_attend(cfg, q, k, v, positions,
                                                     cache)
        with device_scope("attn.proj"):
            out = (out.reshape(b, s, heads * d).astype(jnp.float32)
                   * _output_gate(gate)).astype(cfg.dtype)
            out = pl.RowParallelLinear(
                features=cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="o_proj")(out)
        if cache is not None:
            return out, new_cache
        return out


class SolarOpen2Model(nn.Module):
    """Embedding, the layer pattern, final norm: positions ``0..S-1``, no
    cache (tests, small training)."""

    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        with device_scope("embed"):
            x = pl.ParallelEmbedding(
                num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="embed")(input_ids)
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


class SolarOpen2ForCausalLM(nn.Module):
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, input_ids: jax.Array,
                 labels: Optional[jax.Array] = None,
                 ignore_index: int = -100) -> jax.Array:
        cfg = self.cfg
        x = SolarOpen2Model(cfg, name="model")(input_ids)
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


def solar_open2_forward_with_cache(cfg: SolarOpen2Config, params, input_ids,
                                   positions, kv_cache, slot_ids=None,
                                   **unsupported):
    """The paged forward of the packed serving step, with
    :func:`.llama.llama_forward_with_cache`'s paged signature:
    ``input_ids``, ``positions [1, T]``, ``slot_ids [T]``, ``kv_cache`` a
    :class:`..inference.paging.StatePoolPagedCache`; returns ``(logits
    [1, T, V], new cache)``. The cache's stacks (K/V of the GQA layers,
    the KDA layers' states and tails) and the routed assignments' counts
    (of this step alone) are the carry of every run's scan."""
    from ..inference import paging
    from ..inference.kv_cache import PAD_POSITION
    from ..ops import paged_attention as pa

    if any(unsupported.values()):
        raise ValueError(f"solar_open2 serves through the packed paged "
                         f"step only; got {sorted(unsupported)}")
    if not isinstance(kv_cache, paging.StatePoolPagedCache):
        raise ValueError("solar_open2 is served from the cache its cache "
                         "kind builds (paging.init_serving_cache)")
    p = params["params"]
    q_pos = jnp.asarray(positions, jnp.int32)[0]
    slot_ids = jnp.asarray(slot_ids, jnp.int32)
    with device_scope("embed"):
        x = pl.ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype).apply(
            {"params": p["model"]["embed"]}, input_ids)
    kind = cfg.serving_family().cache_kind.geometry(kv_cache.block_size)
    with device_scope("attn.walk"):
        tables = kv_cache.block_tables[
            jnp.clip(slot_ids, 0, kv_cache.max_slots - 1)]
        write_idx = paging.flat_write_indices(
            tables, q_pos, kv_cache.block_size, kv_cache.capacity, kind)
        walk = pa.step_walk(
            tables, q_pos, kv_cache.block_size, kv_cache.num_blocks,
            cfg.head_dim_, cfg.num_heads // cfg.num_kv_heads,
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
            ssm=carry["kda"], conv=carry["conv"], layer=layer, seg=seg)

    def merge(carry, view, assignments):
        new = ({"k": view.k, "v": view.v} if "k" in carry
               else {"kda": view.ssm, "conv": view.conv})
        return {**new, "moe_counts": carry["moe_counts"] + assignments}

    carry = dict(k=kv_cache.k, v=kv_cache.v, **kv_cache.states,
                 moe_counts=jnp.zeros((3,), jnp.int32))
    stacks = {kind: p["model"][f"layers_{kind}"] for kind in CARRIED}
    x, carry = run_layers(cfg, stacks, x, None, None, CARRIED, carry,
                          view_of, merge,
                          valid=(q_pos < PAD_POSITION)[None],
                          positions=q_pos[None])
    with device_scope("norm"):
        x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype).apply(
            {"params": p["model"]["norm"]}, x)
    with device_scope("head"):
        logits = pl.ColumnParallelLinear(
            features=cfg.vocab_size, use_bias=False, gather_output=True,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype).apply(
            {"params": p["lm_head"]}, x)
    return logits, kv_cache.replace(
        k=carry["k"], v=carry["v"], pos=pool_pos,
        states={name: carry[name] for name in kv_cache.states},
        moe_counts=(None if kv_cache.moe_counts is None
                    else carry["moe_counts"]))
