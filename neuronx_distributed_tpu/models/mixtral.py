"""Mixtral (MoE llama) model family.

Parity target: the reference's mixtral training example
(``examples/training/mixtral``) built from its ``MoE`` module — here the
dense llama decoder with the MLP swapped for :class:`..modules.moe.MoE`,
plus router auxiliary losses accumulated through the scanned layer stack.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..modules import attention as attn_mod
from ..modules.moe import MoE
from ..modules.norms import RMSNorm
from ..parallel import layers as pl
from ..parallel import loss_functions as lf
from ..parallel import mappings
from ..parallel import mesh as ps
from .llama import (LlamaAttention, LlamaConfig, _act_kw, _quant_lm_head,
                    context_parallel_positions)


@dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 2.0
    # "capacity" or "blockwise" (dropless; reference expert_mlps_v2.py:691)
    moe_dispatch: str = "capacity"
    moe_block_size: int = 512
    # decode: DMA-elide unhit experts' weights (forward-only; the decode
    # serving path enables this via dataclasses.replace — see
    # mixtral_forward_with_cache)
    moe_sentinel_empty: bool = False
    # EP dispatch wire dtype ("fp32" | "int8" | "fp8"): quantizes the token
    # gather/combine payloads over ep (blockwise dispatch only; see
    # parallel/ep_dispatch.py)
    moe_ep_wire_dtype: str = "fp32"
    # decomposed (ppermute-ring) EP dispatch overlapping per-chunk expert
    # compute with later hops; None = auto-engage at ep >= 4
    moe_overlap_dispatch: Optional[bool] = None
    # expert bank implementation: "float" | "mx_fp4" | "mx_fp8" (packed
    # microscaling decode weights; convert with mx_pack_expert_params)
    moe_expert_impl: str = "float"
    router_type: str = "top_k"
    shared_expert_intermediate: int = 0
    router_aux_coef: float = 0.02
    router_z_coef: float = 0.001

    def serving_family(self):
        from ..inference.paging import ServingFamily

        return ServingFamily(forward=mixtral_forward_with_cache)

    def __post_init__(self):
        super().__post_init__()
        if (self.weight_quant is not None
                and self.moe_dispatch != "capacity"
                and self.moe_expert_impl == "float"):
            raise ValueError(
                f"weight_quant={self.weight_quant!r} serves experts "
                "quantized, which requires moe_dispatch='capacity' (got "
                f"{self.moe_dispatch!r}); set moe_dispatch='capacity' or "
                "pin moe_expert_impl explicitly")

    @property
    def moe_expert_impl_(self) -> str:
        """Effective expert bank impl: an active ``weight_quant`` tier
        quantizes the experts too unless ``moe_expert_impl`` was pinned."""
        if self.weight_quant is not None and self.moe_expert_impl == "float":
            return _WEIGHT_QUANT_EXPERT_IMPL[self.weight_quant]
        return self.moe_expert_impl


# weight_quant tier -> quantized expert bank implementation
_WEIGHT_QUANT_EXPERT_IMPL = {"int8": "int8", "fp8": "fp8",
                             "mxfp4": "mx_fp4", "mxfp8": "mx_fp8"}


MIXTRAL_8X7B = MixtralConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=14336,
    num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=1e6,
    num_experts=8, top_k=2)

# DBRX (reference: examples/training/dbrx): 16 fine-grained experts, top-4,
# GQA with 8 kv heads — same decoder skeleton, different routing width.
DBRX = MixtralConfig(
    vocab_size=100352, hidden_size=6144, intermediate_size=10752,
    num_layers=40, num_heads=48, num_kv_heads=8, rope_theta=5e5,
    max_seq_len=32768, num_experts=16, top_k=4)


def tiny_moe_config(**kw) -> MixtralConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
                num_experts=4, top_k=2)
    base.update(kw)
    return MixtralConfig(**base)


class MixtralDecoderLayer(nn.Module):
    cfg: MixtralConfig
    # Reduced-sync TP: False elides the attention exit all-reduce. The MoE
    # block keeps its internal tp reduction (its expert-combine psum also
    # moves tokens, so it cannot be elided); its replicated output is
    # scaled to a 1/n share instead, so an unsynced layer's deviation from
    # the last synced hidden state still sums to the true update under the
    # model's periodic resync psum.
    tp_sync: bool = True

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, cache=None,
                 cache_index=None):
        cfg = self.cfg
        h = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                    sequence_parallel=cfg.sequence_parallel,
                    name="input_norm")(x)
        attn_out = LlamaAttention(cfg, tp_sync=self.tp_sync, name="attn")(
            h, cos, sin, positions, cache=cache, cache_index=cache_index)
        new_cache = None
        if cache is not None:
            attn_out, new_cache = attn_out
        x = x + attn_out
        h = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                    sequence_parallel=cfg.sequence_parallel,
                    name="post_norm")(x)
        if cfg.sequence_parallel:
            # routing needs full sequences: gather with to_model_parallel=
            # False (bwd = split) because ExpertMLPs' internal copy_to
            # already psums grads over tp — a reduce-scatter pairing here
            # would double-reduce (cf. the lm_head composition note in
            # llama.py)
            h = mappings.gather_from_sequence_parallel_region(
                h, seq_dim=1, to_model_parallel=False)
        moe_out, aux = MoE(
            num_experts=cfg.num_experts, hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor,
            dispatch_mode=cfg.moe_dispatch,
            block_size=cfg.moe_block_size,
            sentinel_empty=cfg.moe_sentinel_empty,
            ep_wire_dtype=cfg.moe_ep_wire_dtype,
            ep_overlap=cfg.moe_overlap_dispatch,
            expert_impl=cfg.moe_expert_impl_,
            router_type=cfg.router_type,
            shared_expert_intermediate=cfg.shared_expert_intermediate,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="moe")(h)
        if cfg.sequence_parallel:
            # output is fully tp-reduced and replicated: re-shard the
            # sequence with a plain split (bwd all-gather)
            moe_out = mappings.scatter_to_sequence_parallel_region(
                moe_out, seq_dim=1)
        if not self.tp_sync:
            n = pl._bound_size(ps.TP_AXIS) or 1
            moe_out = moe_out / n
        x = x + moe_out
        aux_vec = jnp.stack([aux["load_balance_loss"], aux["z_loss"]])
        if cache is not None:
            return x, aux_vec, new_cache
        return x, aux_vec


class _MoEScanBody(nn.Module):
    cfg: MixtralConfig

    @nn.compact
    def __call__(self, x, cos, sin, positions):
        x, aux = MixtralDecoderLayer(self.cfg, name="layer")(
            x, cos, sin, positions)
        return x, aux


class _MoEDecodeScanBody(nn.Module):
    """Cached-decode scan body (the MoE analogue of llama's
    ``_DecodeScanBody``; reference mixtral serving uses the same base
    model_builder keys)."""

    cfg: MixtralConfig

    @nn.compact
    def __call__(self, x, cache_kv, slot_pos, cos, sin, positions,
                 cache_index):
        k_l, v_l = cache_kv
        x, _, new_cache = MixtralDecoderLayer(self.cfg, name="layer")(
            x, cos, sin, positions, cache=(k_l, v_l, slot_pos),
            cache_index=cache_index)
        return x, new_cache


class _MoEPagedScanBody(nn.Module):
    """nn.scan body for paged MoE decode — the mixtral analogue of llama's
    ``_PagedScanBody`` (same ``layer`` scope as :class:`_MoEDecodeScanBody`,
    so one checkpoint serves both cache protocols). The attention sublayer
    already understands :class:`..inference.paging.PagedCacheView`; the MoE
    sublayer is cache-free, so only the view plumbing differs."""

    cfg: MixtralConfig

    @nn.compact
    def __call__(self, x, cache_kv, pool_pos, tables, write_idx, cos, sin,
                 positions):
        from ..inference.paging import PagedCacheView

        if len(cache_kv) == 4:
            k_l, v_l, ks_l, vs_l = cache_kv
        else:
            (k_l, v_l), ks_l, vs_l = cache_kv, None, None
        view = PagedCacheView(k=k_l, v=v_l, k_scale=ks_l, v_scale=vs_l,
                              pos=pool_pos, tables=tables,
                              write_idx=write_idx)
        x, _, new_view = MixtralDecoderLayer(self.cfg, name="layer")(
            x, cos, sin, positions, cache=view, cache_index=None)
        if len(cache_kv) == 4:
            return x, (new_view.k, new_view.v, new_view.k_scale,
                       new_view.v_scale)
        return x, (new_view.k, new_view.v)


class MixtralModel(nn.Module):
    cfg: MixtralConfig

    @nn.compact
    def __call__(self, input_ids, positions=None):
        cfg = self.cfg
        x = pl.ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed")(
                input_ids)
        positions = context_parallel_positions(input_ids, positions)
        if cfg.sequence_parallel:
            x = mappings.scatter_to_sequence_parallel_region(x, seq_dim=1)
        cos, sin = attn_mod.precompute_rope(
            cfg.head_dim_, cfg.max_seq_len, cfg.rope_theta,
            use_scaled=cfg.rope_scaling)

        if cfg.scan_layers:
            body_cls = _MoEScanBody
            if cfg.remat:
                body_cls = nn.remat(
                    body_cls, prevent_cse=False,
                    policy=jax.checkpoint_policies.nothing_saveable)
            scanned = nn.scan(
                body_cls,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="layers")
            x, aux = scanned(x, cos, sin, positions)
            aux = jnp.sum(aux, axis=0)
        else:
            auxes = []
            layer_cls = MixtralDecoderLayer
            if cfg.remat:
                layer_cls = nn.remat(
                    layer_cls, prevent_cse=False,
                    policy=jax.checkpoint_policies.nothing_saveable)
            from ..ops import collective_matmul as cm

            sched = cm.tp_sync_schedule(cfg.num_layers,
                                        cfg.activation_sync_fraction)
            # see LlamaModel: only engage over a real bound tp axis
            n_tp = pl._bound_size(ps.TP_AXIS)
            reduced = (cfg.activation_sync_fraction < 1.0
                       and n_tp is not None and n_tp > 1)
            # reduced-sync resync (see LlamaModel): psum the accumulated
            # deviation from the last synced hidden state before every
            # synced layer
            x_ref = x
            pending = False
            for i in range(cfg.num_layers):
                if reduced and pending and sched[i]:
                    x = x_ref + mappings.reduce_from_tensor_parallel_region(
                        x - x_ref)
                    pending = False
                x, a = layer_cls(cfg, tp_sync=sched[i] if reduced else True,
                                 name=f"layer_{i}")(x, cos, sin, positions)
                auxes.append(a)
                if reduced:
                    if sched[i]:
                        x_ref = x
                    else:
                        pending = True
            aux = jnp.sum(jnp.stack(auxes), axis=0)
        x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                    sequence_parallel=cfg.sequence_parallel, name="norm")(x)
        return x, aux


class MixtralForCausalLM(nn.Module):
    cfg: MixtralConfig

    @nn.compact
    def __call__(self, input_ids, positions=None):
        cfg = self.cfg
        if cfg.tie_embeddings:
            raise ValueError(
                "tie_embeddings is not supported for Mixtral (HF Mixtral "
                "never ties); use an explicit lm_head")
        x, aux = MixtralModel(cfg, name="model")(input_ids, positions)
        if cfg.weight_quant is not None:
            logits = _quant_lm_head(cfg, False, name="lm_head")(x)
        else:
            logits = pl.ColumnParallelLinear(
                features=cfg.vocab_size, use_bias=False,
                gather_output=False,
                sequence_parallel=cfg.sequence_parallel,
                overlap_comm=cfg.overlap_comm, **_act_kw(cfg),
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="lm_head")(x)
        return logits, aux

    def loss(self, input_ids, labels, ignore_index: int = -100):
        cfg = self.cfg
        logits, aux = self(input_ids)
        ce = lf.causal_lm_loss(logits, labels, ignore_index=ignore_index)
        return (ce + cfg.router_aux_coef * aux[0]
                + cfg.router_z_coef * aux[1])


def mixtral_forward_with_cache(cfg: MixtralConfig, params,
                               input_ids: jax.Array,
                               positions: jax.Array, kv_cache,
                               slot_ids=None):
    """KV-cached forward for MoE serving ("context_encoding" /
    "token_generation" keys) — the mixtral analogue of
    :func:`.llama.llama_forward_with_cache` (the reference serves mixtral
    through the same base model_builder keys,
    ``examples/inference/modules/model_base.py``).

    At decode the tiny token count makes the dropless blockwise dispatch
    with a small block size the natural expert path
    (``cfg.moe_dispatch='blockwise'``); empty-block sentinels are enabled
    here so each step reads only the experts its tokens hit — the
    bandwidth-side equivalent of the reference's fused token-gen MoE
    kernel (``moe_fused_tkg.py:85``; forward-only, so the training-side dW
    constraint does not apply).

    Paged protocol (llama parity): pass a
    :class:`..inference.paging.PagedKVCache` plus ``slot_ids [T]`` mapping
    each packed token (``input_ids [1, T]``) to its cache slot; K/V land in
    the slot's block-table blocks. Contiguous callers are untouched.
    """
    import dataclasses

    from ..inference.kv_cache import KVCache
    from ..inference.paging import PagedKVCache, QuantizedPagedKVCache

    if not cfg.scan_layers:
        raise ValueError("cached decode requires scan_layers=True")
    paged = isinstance(kv_cache, (PagedKVCache, QuantizedPagedKVCache))
    if paged:
        if slot_ids is None:
            raise ValueError("paged cache forward requires slot_ids [T]")
        if input_ids.shape[0] != 1:
            raise ValueError(
                "paged decode packs requests into one row batch [1, T]; "
                f"got batch {input_ids.shape[0]}")
    # token-generation-sized calls only: at prefill (large batch*seq) most
    # experts are hit anyway and the decode kernel's partial-sum layout
    # would cost O(num_ib * tokens * H) HBM for nothing (a crossover near
    # T=4 tokens TOTAL was measured once, in a record since deleted — so
    # the batch dim counts; ROADMAP S6 re-measures it)
    total_tokens = input_ids.shape[0] * input_ids.shape[1]
    if (cfg.moe_dispatch == "blockwise" and not cfg.moe_sentinel_empty
            and total_tokens * cfg.top_k <= cfg.num_experts):
        cfg = dataclasses.replace(cfg, moe_sentinel_empty=True)
    p = params["params"]
    b, s = input_ids.shape
    positions = jnp.asarray(positions, jnp.int32)

    embed = pl.ParallelEmbedding(
        num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype)
    x = embed.apply({"params": p["model"]["embed"]}, input_ids)
    cos, sin = attn_mod.precompute_rope(
        cfg.head_dim_, cfg.max_seq_len, cfg.rope_theta,
        use_scaled=cfg.rope_scaling)

    rope_pos = jnp.minimum(positions, cfg.max_seq_len - 1)

    if paged:
        from ..inference import paging as _paging

        slot_ids = jnp.asarray(slot_ids, jnp.int32)
        # per-token routing (see llama_forward_with_cache paged branch):
        # each packed token carries its slot's block-table row and a flat
        # pool index for this step's K/V write
        tok_tables = kv_cache.block_tables[
            jnp.clip(slot_ids, 0, kv_cache.max_slots - 1)]
        write_idx = _paging.flat_write_indices(
            tok_tables, positions[0], kv_cache.block_size,
            kv_cache.capacity)
        slot_pos = _paging.write_pool_positions(kv_cache.pos, positions[0],
                                                write_idx)
        scanned = nn.scan(
            _MoEPagedScanBody,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast,
                     nn.broadcast, nn.broadcast, nn.broadcast),
            out_axes=0,
            length=cfg.num_layers,
        )(cfg)
        pool_quantized = isinstance(kv_cache, QuantizedPagedKVCache)
        cache_kv = ((kv_cache.k, kv_cache.v, kv_cache.k_scale,
                     kv_cache.v_scale) if pool_quantized
                    else (kv_cache.k, kv_cache.v))
        x, new_kv = scanned.apply(
            {"params": p["model"]["layers"]}, x,
            cache_kv, slot_pos, tok_tables, write_idx,
            cos, sin, rope_pos)
    else:
        slot_pos = jax.lax.dynamic_update_slice_in_dim(
            kv_cache.pos, positions, kv_cache.index, axis=1)
        scanned = nn.scan(
            _MoEDecodeScanBody,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast,
                     nn.broadcast, nn.broadcast),
            out_axes=0,
            length=cfg.num_layers,
        )(cfg)
        x, new_kv = scanned.apply(
            {"params": p["model"]["layers"]}, x, (kv_cache.k, kv_cache.v),
            slot_pos, cos, sin, rope_pos, kv_cache.index)

    x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype).apply(
        {"params": p["model"]["norm"]}, x)
    if cfg.weight_quant is not None:
        head = _quant_lm_head(cfg, True)
    else:
        head = pl.ColumnParallelLinear(
            features=cfg.vocab_size, use_bias=False, gather_output=True,
            overlap_comm=cfg.overlap_comm, **_act_kw(cfg),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype)
    logits = head.apply({"params": p["lm_head"]}, x)
    if paged:
        if isinstance(kv_cache, QuantizedPagedKVCache):
            new_k, new_v, nks, nvs = new_kv
            new_cache = kv_cache.replace(k=new_k, v=new_v, k_scale=nks,
                                         v_scale=nvs, pos=slot_pos)
        else:
            new_k, new_v = new_kv
            new_cache = kv_cache.replace(k=new_k, v=new_v, pos=slot_pos)
    else:
        new_k, new_v = new_kv
        new_cache = KVCache(k=new_k, v=new_v, pos=slot_pos,
                            index=kv_cache.index + s)
    return logits, new_cache
