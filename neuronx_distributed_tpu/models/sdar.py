"""SDAR (``sdar_moe``): a Qwen3-MoE decoder that generates by diffusion
over blocks.

The layer is the llama layer (:class:`.llama.LlamaDecoderLayer`) with an
RMSNorm over each head of ``q`` and ``k`` ahead of the rotary embedding
(``qk_norm``) and :class:`..modules.moe.MoE` for its feed-forward: a
softmax router over all the experts, the ``top_k`` largest, their
probabilities renormalised, the dropless blockwise dispatch. What is its
own is the mask: positions are blocks of ``block_length`` from 0, and a
row attends every position of its own block and of the blocks before it:
causal between blocks, whole inside one. Without a cache that is
:func:`..modules.attention.sdpa_reference` under the block's last position
in the row's place; over the paged pool the row's own position stays what
rotary and the pool write see, and the block's last position is what the
kernel's walk and mask see (``LlamaConfig.block_decoding``,
:func:`.llama._paged_cache_attend`). The forward writes a step's rows
before it attends, so a block fed whole in one step sees itself, and a
block fed again overwrites its first writing in place.

How a block is decoded (several passes, rows uncovered by confidence) is
:class:`..inference.sampling.BlockDecoding`, one frozen value on the
config, handed to the engine through :meth:`SdarConfig.serving_family`.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..inference.sampling import BlockDecoding
from ..modules import attention as attn_mod
from ..modules.moe import MoE
from ..modules.norms import RMSNorm
from ..obs.device_scopes import device_scope
from ..parallel import layers as pl
from .llama import run_layers
from .mixtral import MixtralConfig, MixtralForCausalLM

#: the one kind of layer, and what of the cache's stacks it reads and writes
CARRIED = {"layer": ("k", "v", "moe_counts")}


@dataclass(frozen=True)
class SdarConfig(MixtralConfig):
    num_experts: int = 128
    top_k: int = 8
    qk_norm: bool = True
    moe_dispatch: str = "blockwise"
    # rows of a block of the grouped product: a packed step of a few
    # hundred rows gives an expert a few dozen
    moe_block_size: int = 64
    block_decoding: BlockDecoding = BlockDecoding()

    def feed_forward(self, h: jax.Array, tp_sync: bool = True, valid=None):
        """The routed experts alone (no shared expert): ``(output, router
        aux pair)`` without ``valid``; under a packed step's ``valid``
        rows ``(output, [kept, dropped] of the real rows' choices)``."""
        if valid is None:
            return super().feed_forward(h, tp_sync)
        out, aux = MoE(
            num_experts=self.num_experts, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size, top_k=self.top_k,
            dispatch_mode=self.moe_dispatch, block_size=self.moe_block_size,
            sentinel_empty=self.moe_sentinel_empty,
            capacity_factor=None, router_type=self.router_type,
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="moe")(h, valid=valid)
        return out, aux["assignments"]

    def runs(self):
        return (("layer", 0, self.num_layers),)

    def kind_config(self, kind: str) -> "SdarConfig":
        return self

    def serving_family(self):
        from ..inference.paging import CountedFullCache, ServingFamily

        family = super().serving_family()
        return ServingFamily(
            forward=sdar_forward_with_cache, cache_kind=CountedFullCache(),
            moe_counts=True, block=self.block_decoding,
            unsupported={
                **family.unsupported,
                "speculation": "a slot already decodes a block of positions "
                "a step, several passes a block: a draft lane over a block "
                "that is rewritten in place is not written",
                "prefix_sharing": "a row's K/V depends on its whole block, "
                "and the trie's partial-tail match maps a donor block's "
                "first m positions for an m that the block length need not "
                "divide; sharing whole pool blocks alone (block_size a "
                "multiple of the block length) is not written",
                "quantized": "the cache is the counted full pool, which "
                "has no int8 blocks",
                "session_export": "a half-done block lives on the device "
                "and is no part of a ticket"})


SdarForCausalLM = MixtralForCausalLM


def tiny_config(**kw) -> SdarConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                max_seq_len=128, num_experts=8, top_k=2, moe_block_size=8,
                block_decoding=BlockDecoding(mask_token_id=255))
    base.update(kw)
    return SdarConfig(**base)


def sdar_forward_with_cache(cfg: SdarConfig, params, input_ids, positions,
                            kv_cache, slot_ids=None, **unsupported):
    """The paged forward of the packed serving step, with
    :func:`.llama.llama_forward_with_cache`'s paged signature:
    ``input_ids``, ``positions [1, T]``, ``slot_ids [T]``, ``kv_cache`` the
    :class:`..inference.paging.StatePoolPagedCache` that
    :class:`..inference.paging.CountedFullCache` builds; returns ``(logits
    [1, T, V], new cache)``. The llama forward with two differences: a row
    attends through its block's last position (the walk here, the mask in
    :func:`.llama._paged_cache_attend`; rotary, the pool write and the
    stored position keep the row's own), and the routed assignments of the
    step's real rows are counted into the cache's ``moe_counts``, carried
    through the layer scan beside the pool."""
    from ..inference import paging
    from ..inference.kv_cache import PAD_POSITION
    from ..ops import paged_attention as pa

    if any(unsupported.values()):
        raise ValueError("sdar serves through the packed paged step only; "
                         f"got {sorted(unsupported)}")
    if not isinstance(kv_cache, paging.StatePoolPagedCache):
        raise ValueError("sdar is served from the cache its cache kind "
                         "builds (paging.init_serving_cache)")
    p = params["params"]
    q_pos = jnp.asarray(positions, jnp.int32)[0]
    slot_ids = jnp.asarray(slot_ids, jnp.int32)
    with device_scope("embed"):
        x = pl.ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype).apply(
            {"params": p["model"]["embed"]}, input_ids)
    with device_scope("attn.proj"):
        cos, sin = attn_mod.precompute_rope(
            cfg.head_dim_, cfg.max_seq_len, cfg.rope_theta,
            use_scaled=cfg.rope_scaling)
        # a pad row's sentinel clamps to the table's last entry
        rope_pos = jnp.minimum(q_pos, cfg.max_seq_len - 1)[None]
    kind = cfg.serving_family().cache_kind.geometry(kv_cache.block_size)
    with device_scope("attn.walk"):
        tables = kv_cache.block_tables[
            jnp.clip(slot_ids, 0, kv_cache.max_slots - 1)]
        write_idx = paging.flat_write_indices(
            tables, q_pos, kv_cache.block_size, kv_cache.capacity, kind)
        walk = pa.step_walk(
            tables, jnp.where(q_pos < PAD_POSITION,
                              cfg.block_decoding.through(q_pos), q_pos),
            kv_cache.block_size, kv_cache.num_blocks, cfg.head_dim_,
            cfg.num_heads // cfg.num_kv_heads,
            force_pallas=cfg.attn_force_pallas,
            pools=(kv_cache.k, kv_cache.v),
            slot_rows=cfg.block_decoding.block_length)
    with device_scope("attn.pool_write"):
        pool_pos = paging.write_pool_positions(kv_cache.pos, q_pos,
                                               write_idx)

    def view_of(kind, carry, layer):
        return paging.PagedCacheView(
            k=carry["k"], v=carry["v"], k_scale=None, v_scale=None,
            layer=layer, pos=pool_pos, tables=tables, write_idx=write_idx,
            walk=walk)

    def merge(carry, view, assignments):
        return {"k": view.k, "v": view.v,
                "moe_counts": carry["moe_counts"] + assignments}

    carry = dict(k=kv_cache.k, v=kv_cache.v,
                 moe_counts=jnp.zeros((2,), jnp.int32))
    x, carry = run_layers(cfg, {"layer": p["model"]["layers"]}, x, cos, sin,
                          CARRIED, carry, view_of, merge,
                          valid=(q_pos < PAD_POSITION)[None],
                          positions=rope_pos)
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
        moe_counts=(None if kv_cache.moe_counts is None
                    else carry["moe_counts"]))
