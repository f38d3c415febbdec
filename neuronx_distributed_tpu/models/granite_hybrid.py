"""Granite 4.0-H (``granitemoehybrid``): a decoder whose layers are Mamba-2
state-space mixers with a few attention layers among them
(huggingface.co/ibm-granite/granite-4.0-h-micro). ``layer_types`` names
each layer's mixer: ``mamba``, one ``in_proj`` to ``[z | x B C | dt]``, a
depthwise causal convolution of width ``mamba_d_conv`` over ``x B C``, the
selective scan (:mod:`..ops.ssd`), a gated RMSNorm and ``out_proj``; or
``attention``, GQA without rotary embedding (NoPE) whose scores are
multiplied by ``attention_multiplier`` and not by ``1 / sqrt(head_dim)``.
Both are followed by the feed-forward. In the dense models
(``num_local_experts`` 0: granite-4.0-h-micro) it is the llama SwiGLU of
``shared_intermediate_size`` (the *shared* MLP); in the others
(granite-4.0-h-small: 72 experts, ten a row) it is
:class:`..modules.moe.MoE`: a softmax router over all ``num_experts`` in
float32 that keeps the ``top_k`` largest and weighs them by their
probabilities over the chosen's sum (a softmax over the chosen logits),
experts of ``expert_intermediate_size`` (the published
``intermediate_size``) and the shared MLP beside them, unweighted, on
every row. ``experts_held = (first, count)`` is the share of the routed
experts this device holds: the router scores them all, an assignment to
an expert held elsewhere takes no slot and adds nothing. All of it under
Granite's four multipliers: the embedding times ``embedding_multiplier``,
each residual branch times ``residual_multiplier``, the logits over
``logits_scaling``, tied to the embedding.

The layer is :class:`.llama.LlamaDecoderLayer` under one derived config a
kind, the parameters one stack a kind, the layers one ``lax.scan`` a run
of like layers (:func:`.llama.run_layers`, as :mod:`.minicpm_sala`).

Served, a slot's sequence is in three places
(:class:`..inference.paging.StatePoolCache`): K/V blocks of the attention
layers alone, and two per-slot state leaves of the mamba layers, ``ssm
[Lm, J, d_state, d_inner]`` float32 and the convolution's tail ``conv
[Lm, d_conv - 1, J, d_inner + 2 d_state]``. Heads of 64 lie two a pool
row, so the Pallas paged kernel serves them (heads of 128 one a row). A
model with routed experts also counts a step's assignments into the
cache's ``moe_counts`` (kept, dropped, held elsewhere), under both kinds
of layer; the dense models' tree, step and cache have no such leaf.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax.core import meta

from ..modules.moe import MoE
from ..modules.norms import GroupRMSNorm, RMSNorm
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
#: (and ``moe_counts`` where the model routes:
#: :meth:`GraniteHybridConfig.carried`)
CARRIED = {"full": ("k", "v"), "mamba2": ("ssm", "conv")}
#: what no family of per-slot Mamba-2 states serves, each with why
UNSUPPORTED = {
    "prefix_sharing": "a mamba layer's state and convolution tail are no "
    "blocks: a shared prefix's blocks carry neither to resume from",
    "speculation": "a lane clone copies blocks, and a rejected draft row "
    "has already advanced its slot's state and shifted its tail",
    "cp": "the per-slot states are not sharded over a cp axis",
    "quantized": "the states are float32 beside the pool, and two heads a "
    "pool row want scales of their own",
    "session_export": "a shipped session's blocks leave its states and "
    "tails behind"}
#: what a published config must say for this module to be its model
_BUILT = {"model_type": "granitemoehybrid", "position_embedding_type": "nope",
          "hidden_act": "silu", "normalization_function": "rmsnorm",
          "attention_bias": False, "mamba_proj_bias": False,
          "mamba_conv_bias": True, "tie_word_embeddings": True,
          "mamba_n_groups": 1}
#: published keys that nothing reads where the model is as :data:`_BUILT`
#: says (no rotary embedding)
_UNREAD = ("rope_theta", "rope_scaling")
#: every key of a published config that
#: :meth:`GraniteHybridConfig.from_published` reads, holds to
#: :data:`_BUILT` or knows that nothing reads
PUBLISHED_KEYS = frozenset(_BUILT) | frozenset(_UNREAD) | frozenset((
    "vocab_size", "hidden_size", "intermediate_size",
    "shared_intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "max_position_embeddings", "rms_norm_eps",
    "layer_types", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
    "mamba_d_conv", "mamba_expand", "mamba_chunk_size",
    "num_local_experts", "num_experts_per_tok", "embedding_multiplier",
    "residual_multiplier", "attention_multiplier", "logits_scaling"))


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
    #: routed experts the router scores (0: the dense models, whose
    #: feed-forward is the shared MLP of ``intermediate_size`` alone)
    num_experts: int = 0
    top_k: int = 0
    #: a routed expert's width (the published ``intermediate_size``; the
    #: field ``intermediate_size`` here is the shared MLP's)
    expert_intermediate_size: int = 0
    #: ``(first, count)`` of the routed experts held here (None: all)
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self) -> None:
        super().__post_init__()
        bad = [t for t in self.layer_types if t not in LAYER_KINDS]
        if bad or len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types must name one of {sorted(LAYER_KINDS)} for "
                f"each of the {self.num_layers} layers, got "
                f"{self.layer_types}")
        groups = self.mamba_n_groups
        if groups < 1 or self.mamba_n_heads % groups:
            raise ValueError(
                f"{self.mamba_n_heads} heads do not lie in "
                f"{self.mamba_n_groups} groups of B and C")
        held = self.experts_held
        if held is not None and not (
                0 <= held[0] and held[1] > 0
                and held[0] + held[1] <= self.num_experts):
            raise ValueError(f"experts_held {held} is no share of "
                             f"{self.num_experts} experts")
        if self.num_experts and not (
                0 < self.top_k <= self.num_experts
                and self.expert_intermediate_size > 0):
            raise ValueError(
                f"{self.num_experts} routed experts want top_k in 1.."
                f"{self.num_experts} and an expert width, got top_k "
                f"{self.top_k}, expert_intermediate_size "
                f"{self.expert_intermediate_size}")

    @classmethod
    def from_published(cls, c: dict, **kw) -> "GraniteHybridConfig":
        """The config of a published ``config.json``'s keys
        (:data:`PUBLISHED_KEYS`): each is read here, is one that nothing
        reads (:data:`_UNREAD`), or must say what this module builds
        (:data:`_BUILT`: another value is refused by name). ``kw`` are
        this class's fields (dtype, ``experts_held``; ``num_experts``
        where the file's ``num_local_experts`` is a share)."""
        wrong = {k: c.get(k) for k, v in _BUILT.items() if c.get(k) != v}
        if (c["mamba_expand"] * c["hidden_size"]
                != c["mamba_n_heads"] * c["mamba_d_head"]):
            wrong["mamba_expand"] = c["mamba_expand"]
        if bool(c["num_local_experts"]) != bool(c["num_experts_per_tok"]):
            wrong["num_experts_per_tok"] = c["num_experts_per_tok"]
        if wrong:
            raise ValueError(
                f"granite_hybrid builds {_BUILT}, d_inner = mamba_expand x "
                f"hidden_size, and experts with a top-k or neither; the "
                f"config says {wrong}")
        return cls(**{**dict(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            intermediate_size=c["shared_intermediate_size"],
            num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            max_seq_len=int(c["max_position_embeddings"]),
            rms_eps=float(c["rms_norm_eps"]),
            layer_types=tuple(c["layer_types"]),
            mamba_n_heads=c["mamba_n_heads"],
            mamba_d_head=c["mamba_d_head"],
            mamba_d_state=c["mamba_d_state"],
            mamba_d_conv=c["mamba_d_conv"],
            mamba_chunk_size=c["mamba_chunk_size"],
            embedding_multiplier=float(c["embedding_multiplier"]),
            residual_multiplier=float(c["residual_multiplier"]),
            attention_multiplier=float(c["attention_multiplier"]),
            logits_scaling=float(c["logits_scaling"]),
            num_experts=c["num_local_experts"],
            top_k=c["num_experts_per_tok"],
            expert_intermediate_size=(c["intermediate_size"]
                                      if c["num_local_experts"] else 0)),
            **kw})

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

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

    def feed_forward(self, h: jax.Array, tp_sync: bool = True, valid=None):
        """The dense models: the shared MLP alone
        (:class:`.llama.LlamaMLP`), ``(output, None)``. With routed
        experts: ``(output, [kept, dropped, elsewhere])``, the routed
        assignments of the real rows, by capacity over the held experts
        at the capacity of the step's rows, so nothing held can drop."""
        if not self.num_experts:
            return super().feed_forward(h, tp_sync, valid)
        if valid is None:
            valid = jnp.ones(h.shape[:-1], bool)
        out, aux = MoE(
            num_experts=self.num_experts, hidden_size=self.hidden_size,
            intermediate_size=self.expert_intermediate_size,
            top_k=self.top_k, capacity_factor=None, router_type="top_k",
            shared_expert_intermediate=self.intermediate_size,
            held=self.experts_held or (0, self.num_experts),
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="moe")(h, valid=valid)
        return out, aux["assignments"]

    def kinds(self) -> Tuple[str, ...]:
        return tuple(LAYER_KINDS[t] for t in self.layer_types)

    def carried(self):
        """:data:`CARRIED`, and the step's ``moe_counts`` under both kinds
        where the model routes."""
        counts = ("moe_counts",) if self.num_experts else ()
        return {kind: names + counts for kind, names in CARRIED.items()}

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
            moe_counts=bool(self.num_experts),
            unsupported=UNSUPPORTED)


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
    :class:`..inference.paging.StateSpaceLayerView` of the packed step.
    ``cfg`` is this family's config or another's with its ``mamba_*``
    fields (:mod:`.nemotron_h`). With ``mamba_n_groups`` ``G`` > 1 the
    heads read ``B`` and ``C`` by group (:mod:`..ops.ssd`) and the gated
    norm takes its mean square over each group's ``d_inner / G`` channels
    (one weight a channel, as with one group)."""

    cfg: GraniteHybridConfig
    tp_sync: bool = True

    @nn.compact
    def __call__(self, x, cos=None, sin=None, positions=None, cache=None,
                 cache_index=None):
        cfg = self.cfg
        heads, width, n = cfg.mamba_n_heads, cfg.mamba_d_head, \
            cfg.mamba_d_state
        inner, chans, taps = cfg.d_inner, cfg.conv_channels, cfg.mamba_d_conv
        groups = cfg.mamba_n_groups
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
            xs, b, c = jnp.split(xbc, (inner, inner + groups * n), axis=-1)
            if groups > 1:
                b, c = (v.reshape(b_, s_, groups, n) for v in (b, c))
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
            # gate first, then one norm over each group's share of d_inner
            # (all of it with one group)
            y = y.reshape(b_, s_, inner) * jax.nn.silu(z.astype(jnp.float32))
            norm = (RMSNorm if groups == 1
                    else functools.partial(GroupRMSNorm, groups=groups))
            y = norm(eps=cfg.rms_eps, dtype=cfg.dtype, name="norm")(y)
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


def mixer_views(cfg, kv_cache, q_pos, slot_ids):
    """What a packed step builds once for the mixers of a state-pool
    family (this one's, :mod:`.nemotron_h`'s): the rows' block tables,
    write indices and the paged kernel's walk, the step's segments, and
    the pool's positions with the step's rows written. ``(pool_pos,
    view_of)``: ``view_of(mixer, carry, layer)`` is the view a layer whose
    mixer is ``"full"`` or ``"mamba2"`` is given of the carried stacks at
    its index ``layer`` among the layers of that mixer."""
    from ..inference import paging
    from ..ops import paged_attention as pa

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

    def view_of(mixer, carry, layer):
        if mixer == "full":
            return paging.PagedCacheView(
                k=carry["k"], v=carry["v"], k_scale=None, v_scale=None,
                layer=layer, pos=pool_pos, tables=tables,
                write_idx=write_idx, walk=walk)
        return paging.StateSpaceLayerView(
            ssm=carry["ssm"], conv=carry["conv"], layer=layer, seg=seg)

    return pool_pos, view_of


def granite_hybrid_forward_with_cache(cfg: GraniteHybridConfig, params,
                                      input_ids, positions, kv_cache,
                                      slot_ids=None, **unsupported):
    """The paged forward of the packed serving step, with
    :func:`.llama.llama_forward_with_cache`'s paged signature:
    ``input_ids``, ``positions [1, T]``, ``slot_ids [T]``, ``kv_cache`` a
    :class:`..inference.paging.StatePoolPagedCache`; returns ``(logits
    [1, T, V], new cache)``. The cache's stacks (K/V of the attention
    layers, the mamba layers' states and tails) and, where the model
    routes, the routed assignments' counts (of this step alone) are the
    carry of every run's scan."""
    from ..inference import paging
    from ..inference.kv_cache import PAD_POSITION

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
    pool_pos, view_of = mixer_views(cfg, kv_cache, q_pos, slot_ids)
    carry = dict(k=kv_cache.k, v=kv_cache.v, **kv_cache.states)
    routed = {}
    if cfg.num_experts:
        def merge(carry, view, assignments):
            return {**{name: getattr(view, name) for name in carry
                       if name != "moe_counts"},
                    "moe_counts": carry["moe_counts"] + assignments}

        carry["moe_counts"] = jnp.zeros((3,), jnp.int32)
        routed = dict(merge=merge, valid=(q_pos < PAD_POSITION)[None])
    stacks = {kind: p["model"][f"layers_{kind}"] for kind in CARRIED}
    x, carry = run_layers(cfg, stacks, x, None, None, cfg.carried(), carry,
                          view_of, positions=q_pos[None], **routed)
    with device_scope("norm"):
        x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype).apply(
            {"params": p["model"]["norm"]}, x)
    with device_scope("head"):
        logits = pl.embedding_attend(
            p["model"]["embed"]["embedding"], x, dtype=cfg.dtype,
            gather_output=True) / cfg.logits_scaling
    return logits, kv_cache.replace(
        k=carry["k"], v=carry["v"], pos=pool_pos,
        states={name: carry[name] for name in kv_cache.states},
        moe_counts=(None if kv_cache.moe_counts is None
                    else carry["moe_counts"]))
