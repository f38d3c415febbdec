"""MiniCPM-SALA: a decoder whose layers differ in kind
(huggingface.co/openbmb/MiniCPM-SALA). ``mixer_types`` names each layer's
mixer: ``minicpm4``, block-sparse softmax attention over a few K/V heads
(QK-norm, no rotary, a top-k selection of blocks over compressed keys:
:mod:`..ops.sparse_attention`), or ``lightning-attn``, a decayed
outer-product state a head (QK-norm, rotary, an output norm:
:mod:`..ops.lightning_attention`); both end in a sigmoid output gate and
are followed by the llama SwiGLU, under the family's muP scalings (the
embedding times ``scale_emb``, each residual branch times ``scale_depth /
sqrt(published depth)``, the final hidden states over ``hidden_size /
dim_model_base``).

The layer is :class:`.llama.LlamaDecoderLayer` under one derived config a
kind (:meth:`MiniCPMSALAConfig.kind_config`). The **layer pattern** came
with this family: the two kinds have unlike parameter shapes, so the
parameters are one stack a kind (``layers_sparse``, ``layers_lightning``),
and the layers run as one ``lax.scan`` a run of like layers
(:func:`.llama.run_layers`, shared since by every family whose layers
differ in kind), over the layer's index in its kind's stack: the body
reads its weights, its K/V pool or its states at that index of stacks
that ride along whole, so no stack is sliced or copied between runs.
Remat is not threaded through.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax.core import meta

from ..modules.attention import rope_rows
from ..modules.norms import RMSNorm
from ..obs.device_scopes import device_scope
from ..ops.sparse_attention import SparseSpec, score_walk
from ..parallel import layers as pl
from ..parallel import loss_functions as lf
from .llama import LlamaConfig, _ScanBody, run_layers

#: a published mixer's name -> the layer's ``attention_kind``
MIXERS = {"minicpm4": "sparse", "lightning-attn": "lightning"}
_S, _L = "minicpm4", "lightning-attn"
#: the published model's 32 layers: 8 sparse, 24 lightning
PUBLISHED_MIXERS = ((_S,) + (_L,) * 8 + (_S,) + (_L,) * 6 + (_S, _S)
                    + (_L,) * 4 + (_S,) + (_L,) * 6 + (_S,) * 3)


@dataclass(frozen=True)
class MiniCPMSALAConfig(LlamaConfig):
    vocab_size: int = 73448
    intermediate_size: int = 16384
    num_kv_heads: int = 2
    head_dim: Optional[int] = 128
    max_seq_len: int = 524288
    rms_eps: float = 1e-6
    qk_norm: bool = True
    attn_output_gate: bool = True
    #: each layer's mixer, a key of :data:`MIXERS`; ``num_layers`` long
    mixer_types: Tuple[str, ...] = PUBLISHED_MIXERS
    lightning_heads: int = 32
    lightning_kv_heads: int = 32
    #: the block-sparse layers' selection (MiniCPM4's InfLLM-v2 sizes)
    sparse: SparseSpec = SparseSpec()
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    #: the depth the residual scale is taken at: the published model's,
    #: whatever slice of it ``mixer_types`` holds
    mup_depth: int = 32
    dim_model_base: int = 256

    def __post_init__(self) -> None:
        super().__post_init__()
        bad = [m for m in self.mixer_types if m not in MIXERS]
        if bad or len(self.mixer_types) != self.num_layers:
            raise ValueError(
                f"mixer_types must name one of {sorted(MIXERS)} for each of "
                f"the {self.num_layers} layers, got {self.mixer_types}")

    def kind_config(self, kind: str) -> "MiniCPMSALAConfig":
        """The config :class:`.llama.LlamaDecoderLayer` builds a layer of
        ``kind`` from."""
        scale = self.scale_depth / self.mup_depth ** 0.5
        if kind == "sparse":
            return dataclasses.replace(
                self, attention_kind="sparse", use_rope=False,
                residual_scale=scale)
        return dataclasses.replace(
            self, attention_kind="lightning", use_rope=True,
            attn_output_norm=True, num_heads=self.lightning_heads,
            num_kv_heads=self.lightning_kv_heads, residual_scale=scale)

    def kinds(self) -> Tuple[str, ...]:
        return tuple(MIXERS[m] for m in self.mixer_types)

    def layers_of(self, kind: str) -> int:
        return self.kinds().count(kind)

    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """``(kind, first, count)`` of each run of like layers, ``first``
        the run's first index in its kind's stack."""
        out, seen = [], {"sparse": 0, "lightning": 0}
        for kind in self.kinds():
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, seen[kind], 1])
            seen[kind] += 1
        return tuple(tuple(r) for r in out)

    def serving_family(self):
        from ..inference.paging import (ServingFamily, SparseStateCache,
                                        StateLeaf)

        d = self.head_dim_
        return ServingFamily(
            forward=minicpm_sala_forward_with_cache,
            cache_kind=SparseStateCache(
                sparse_layers=self.layers_of("sparse"),
                # a slot's S^T a head, heads before slots
                # (ops/lightning_attention.lightning_attention_packed)
                leaves=(StateLeaf(
                    "state", (self.layers_of("lightning"),
                              self.lightning_heads), (d, d), jnp.float32),),
                stride=self.sparse.stride, select_block=self.sparse.block),
            unsupported={
                "prefix_sharing": "a lightning layer's state is not a "
                "block: a shared prefix's blocks carry no state to resume "
                "from",
                "speculation": "a lane clone copies blocks, and a rejected "
                "draft row has already advanced its slot's state",
                "cp": "the per-slot state and the selection's scores are "
                "not sharded over a cp axis",
                "quantized": "a selection over an int8 pool is another "
                "kernel, and the state is float32",
                "session_export": "a shipped session's blocks leave its "
                "lightning states behind"})


def tiny_config(**kw) -> MiniCPMSALAConfig:
    """Test widths: the selection scaled down (blocks of 8, top 6, a
    window of 16, dense below 64) so that short sequences cross it."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=6,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=4096,
        lightning_heads=4, lightning_kv_heads=4,
        mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4", "minicpm4", "lightning-attn"),
        sparse=SparseSpec(kernel=4, stride=2, block=8, topk=6,
                          init_blocks=1, window=16, dense_len=64))
    base.update(kw)
    return MiniCPMSALAConfig(**base)


#: what of the cache's stacks a layer of each kind reads and writes: the
#: carry of its run's scan (the rest passes the run by)
CARRIED = {"sparse": ("k", "v", "ck", "counts"), "lightning": ("state",)}


class MiniCPMSALAModel(nn.Module):
    """Embedding, the layer pattern, final norm: positions ``0..S-1``, no
    cache (tests, small training)."""

    cfg: MiniCPMSALAConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        with device_scope("embed"):
            x = pl.ParallelEmbedding(
                num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="embed")(input_ids) * cfg.scale_emb
        with device_scope("attn.proj"):
            cos, sin = rope_rows(jnp.arange(input_ids.shape[1]),
                                 cfg.head_dim_, cfg.rope_theta)
        if self.is_initializing():
            # the parameters: one stack a kind, each made by scanning the
            # kind's layer over its depth (the order the layers run in is
            # run_layers' business, and makes no parameter)
            for kind in ("sparse", "lightning"):
                x, _ = nn.scan(
                    _ScanBody, variable_axes={"params": 0},
                    split_rngs={"params": True},
                    in_axes=(nn.broadcast,) * 3,
                    length=cfg.layers_of(kind),
                    metadata_params={nn.PARTITION_NAME: "layers"},
                )(cfg.kind_config(kind), name=f"layers_{kind}")(
                    x, cos, sin, None)
        else:
            stacks = {kind: meta.unbox(
                self.variables["params"][f"layers_{kind}"])
                for kind in ("sparse", "lightning")}
            x, _ = run_layers(cfg, stacks, x, cos, sin, CARRIED)
        with device_scope("norm"):
            return RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name="norm")(x)


class MiniCPMSALAForCausalLM(nn.Module):
    cfg: MiniCPMSALAConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array,
                 labels: Optional[jax.Array] = None,
                 ignore_index: int = -100) -> jax.Array:
        cfg = self.cfg
        x = MiniCPMSALAModel(cfg, name="model")(input_ids)
        with device_scope("head"):
            x = x / (cfg.hidden_size / cfg.dim_model_base)
            logits = pl.ColumnParallelLinear(
                features=cfg.vocab_size, use_bias=False, gather_output=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="lm_head")(x)
        if labels is not None:
            with device_scope("loss"):
                return lf.causal_lm_loss(logits, labels,
                                         ignore_index=ignore_index)
        return logits


def minicpm_sala_forward_with_cache(cfg: MiniCPMSALAConfig, params,
                                    input_ids, positions, kv_cache,
                                    slot_ids=None, **unsupported):
    """The paged forward of the packed serving step, with
    :func:`.llama.llama_forward_with_cache`'s paged signature:
    ``input_ids``, ``positions [1, T]``, ``slot_ids [T]``, ``kv_cache`` a
    :class:`..inference.paging.SparseStatePagedCache`; returns ``(logits
    [1, T, V], new cache)``. The cache's stacks (K/V and compressed keys
    of the sparse layers, the lightning layers' per-slot states, the
    selections' counts) are the carry of every run's scan."""
    from ..inference import paging

    if any(unsupported.values()):
        raise ValueError(f"minicpm_sala serves through the packed paged "
                         f"step only; got {sorted(unsupported)}")
    if not isinstance(kv_cache, paging.SparseStatePagedCache):
        raise ValueError("minicpm_sala is served from the cache its cache "
                         "kind builds (paging.init_serving_cache)")
    p = params["params"]
    q_pos = jnp.asarray(positions, jnp.int32)[0]
    slot_ids = jnp.asarray(slot_ids, jnp.int32)
    with device_scope("embed"):
        x = pl.ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype).apply(
            {"params": p["model"]["embed"]}, input_ids) * cfg.scale_emb
    with device_scope("attn.proj"):
        cos, sin = rope_rows(jnp.minimum(q_pos, cfg.max_seq_len - 1),
                             cfg.head_dim_, cfg.rope_theta)
    kind = cfg.serving_family().cache_kind.geometry(kv_cache.block_size)
    with device_scope("attn.walk"):
        tables = kv_cache.block_tables[
            jnp.clip(slot_ids, 0, kv_cache.max_slots - 1)]
        write_idx = paging.flat_write_indices(
            tables, q_pos, kv_cache.block_size, kv_cache.capacity, kind)
        # which compressed keys each tile of rows scores follows the
        # tables and positions alone: one walk for the sparse layers
        walk = score_walk(
            tables, q_pos, cfg.sparse, kv_cache.block_size,
            cfg.num_heads // cfg.num_kv_heads, cfg.head_dim_, cfg.dtype,
            cfg.attn_force_pallas)
    with device_scope("attn.pool_write"):
        pool_pos = paging.write_pool_positions(kv_cache.pos, q_pos,
                                               write_idx)

    def view_of(kind, carry, layer):
        if kind == "sparse":
            return paging.SparseLayerView(
                k=carry["k"], v=carry["v"], ck=carry["ck"],
                counts=carry["counts"], layer=layer, tables=tables,
                write_idx=write_idx, q_pos=q_pos, walk=walk)
        return paging.StateLayerView(state=carry["state"], layer=layer,
                                     slot_ids=slot_ids, q_pos=q_pos)

    carry = dict(k=kv_cache.k, v=kv_cache.v, ck=kv_cache.ck,
                 state=kv_cache.state,
                 counts=jnp.zeros_like(kv_cache.counts))
    stacks = {kind: p["model"][f"layers_{kind}"]
              for kind in ("sparse", "lightning")}
    x, carry = run_layers(cfg, stacks, x, cos, sin, CARRIED, carry, view_of)
    with device_scope("norm"):
        x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype).apply(
            {"params": p["model"]["norm"]}, x)
    with device_scope("head"):
        x = x / (cfg.hidden_size / cfg.dim_model_base)
        logits = pl.ColumnParallelLinear(
            features=cfg.vocab_size, use_bias=False, gather_output=True,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype).apply(
            {"params": p["lm_head"]}, x)
    return logits, kv_cache.replace(pos=pool_pos, **carry)
