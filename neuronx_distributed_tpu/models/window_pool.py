"""What the families of full-attention and sliding-window layers share
(:mod:`.laguna`, :mod:`.mimo_v2`): the bookkeeping of the layer pattern,
the model over it and the paged forward over the two pools of
:class:`..inference.paging.WindowPoolCache`.

A family's config names each layer's kind ``<attention>_<feed-forward>``
(:meth:`kinds`: attention ``full``, causal over every earlier position, or
``sliding``, causal over the last ``sliding_window``) and derives one
config a kind (``kind_config``: the kind's head counts, its attention and
feed-forward hooks). The layer is :class:`.llama.LlamaDecoderLayer` under
that config, the parameters one stack a kind, the layers one ``lax.scan``
a run of like layers (:func:`.llama.run_layers`).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax.core import meta

from ..modules import attention as attn_mod
from ..modules.norms import RMSNorm
from ..obs.device_scopes import device_scope
from ..parallel import layers as pl
from ..parallel import loss_functions as lf
from .llama import _ScanBody, run_layers, runs_of

#: the pool a layer's attention reads and writes, by attention type
POOL = {"full": ("k", "v"), "sliding": ("wk", "wv")}


class WindowPoolPattern:
    """The layer pattern of a config that names its layers' kinds
    (:meth:`kinds`), mixed into the family's config beside
    :class:`.llama.LlamaConfig`."""

    def kinds(self) -> Tuple[str, ...]:
        """Each layer's kind, ``<attention>_<feed-forward>``."""
        raise NotImplementedError

    def layers_of(self, kind: str) -> int:
        return self.kinds().count(kind)

    def attention_layers(self, attn: str) -> int:
        return sum(kind.split("_")[0] == attn for kind in self.kinds())

    def heads_of(self, attn: str) -> int:
        """Query heads of the layers of an attention type."""
        return self.num_heads

    def kv_heads_of(self, attn: str) -> int:
        """K/V heads of the layers of an attention type."""
        return self.num_kv_heads

    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """``(kind, first, count)`` of each run of like layers, ``first``
        the run's first index in its kind's stack."""
        return runs_of(self.kinds())

    def carried(self):
        """What of the cache's stacks a layer of each kind reads and
        writes: its attention type's pool and the expert layers' counts."""
        return {kind: POOL[kind.split("_")[0]] + ("moe_counts",)
                for kind in dict.fromkeys(self.kinds())}

    def pool_layers(self):
        """A kind's layers' indices in their attention type's pool, in the
        order of the kind's stack."""
        out, seen = {}, {}
        for kind in self.kinds():
            attn = kind.split("_")[0]
            out.setdefault(kind, []).append(seen.get(attn, 0))
            seen[attn] = seen.get(attn, 0) + 1
        return out

    def serving_family(self):
        """The family's :class:`..inference.paging.ServingFamily`: the
        paged forward over the two pools, the cache kind at the pattern's
        layer counts and window and each pool's rows (its attention
        type's K/V heads, a K head and a V head), and what a ring a slot
        cannot serve."""
        from ..inference.paging import ServingFamily, WindowPoolCache

        rows = {attn: (self.kv_heads_of(attn), self.head_dim_,
                       self.v_head_dim_) for attn in POOL}

        ring = "a sliding-window layer keeps a slot's last positions in " \
               "the slot's own ring"
        return ServingFamily(
            forward=window_pool_forward_with_cache,
            cache_kind=WindowPoolCache(
                full_layers=self.attention_layers("full"),
                window_layers=self.attention_layers("sliding"),
                window=self.sliding_window, full_rows=rows["full"],
                window_rows=rows["sliding"]),
            moe_counts=True,
            unsupported={
                "prefix_sharing": ring + ": a shared prefix's blocks carry "
                "no window rows to resume from, and the sharer's ring is "
                "empty",
                "session_export": ring + ": a shipped session's blocks "
                "leave it behind",
                "speculation": "a lane clone copies blocks, and a draft "
                "lane's rows would overwrite the ring of the slot they "
                "branch from",
                "cp": "the rings are not sharded over a cp axis, and the "
                "kernel computes no cross-rank combine",
                "quantized": "an int8 ring wants scales of its own; no "
                "kernel reads them"})


def rotate_leading(x, cos, sin):
    """Rotary over the first ``2 * cos.shape[-1]`` values of a head."""
    width = 2 * cos.shape[-1]
    if width == x.shape[-1]:
        return attn_mod.apply_rotary(x, cos, sin)
    return jnp.concatenate(
        [attn_mod.apply_rotary(x[..., :width], cos, sin), x[..., width:]],
        axis=-1)


class WindowPoolModel(nn.Module):
    """Embedding, the layer pattern, final norm: positions ``0..S-1``, no
    cache (tests, small training)."""

    cfg: Any

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        with device_scope("embed"):
            x = pl.ParallelEmbedding(
                num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="embed")(input_ids)
        with device_scope("attn.proj"):
            rope = cfg.rope_rows(jnp.arange(input_ids.shape[1]))
        carried = cfg.carried()
        if self.is_initializing():
            # the parameters: one stack a kind, each made by scanning the
            # kind's layer over its depth
            for kind in carried:
                x, _ = nn.scan(
                    _ScanBody, variable_axes={"params": 0},
                    split_rngs={"params": True},
                    in_axes=(nn.broadcast,) * 3,
                    length=cfg.layers_of(kind),
                    metadata_params={nn.PARTITION_NAME: "layers"},
                )(cfg.kind_config(kind), name=f"layers_{kind}")(
                    x, rope, None, None)
        else:
            stacks = {kind: meta.unbox(
                self.variables["params"][f"layers_{kind}"])
                for kind in carried}
            x, _ = run_layers(cfg, stacks, x, rope, None, carried)
        with device_scope("norm"):
            return RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name="norm")(x)


class WindowPoolForCausalLM(nn.Module):
    cfg: Any

    @nn.compact
    def __call__(self, input_ids: jax.Array,
                 labels: Optional[jax.Array] = None,
                 ignore_index: int = -100) -> jax.Array:
        cfg = self.cfg
        x = WindowPoolModel(cfg, name="model")(input_ids)
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


def window_pool_forward_with_cache(cfg, params, input_ids, positions,
                                   kv_cache, slot_ids=None, **unsupported):
    """The paged forward of the packed serving step, with
    :func:`.llama.llama_forward_with_cache`'s paged signature:
    ``input_ids``, ``positions [1, T]``, ``slot_ids [T]``, ``kv_cache`` a
    :class:`..inference.paging.WindowPoolPagedCache`; returns ``(logits
    [1, T, V], new cache)``. The two pools and the routed assignments'
    counts (of this step alone) are the carry of the runs' scans; the two
    kernels' walks are built once a step, one a head count."""
    from ..inference import paging
    from ..inference.kv_cache import PAD_POSITION
    from ..ops import paged_attention as pa

    if any(unsupported.values()):
        raise ValueError(f"a window-pool family serves through the packed "
                         f"paged step only; got {sorted(unsupported)}")
    if not isinstance(kv_cache, paging.WindowPoolPagedCache):
        raise ValueError("a window-pool family is served from the cache its "
                         "cache kind builds (paging.init_serving_cache)")
    p = params["params"]
    q_pos = jnp.asarray(positions, jnp.int32)[0]
    slot_ids = jnp.asarray(slot_ids, jnp.int32)
    bs, force = kv_cache.block_size, cfg.attn_force_pallas
    with device_scope("embed"):
        x = pl.ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype).apply(
            {"params": p["model"]["embed"]}, input_ids)
    with device_scope("attn.proj"):
        rope = cfg.rope_rows(jnp.minimum(q_pos, cfg.max_seq_len - 1))
    kind = cfg.serving_family().cache_kind.geometry(bs)
    # a pool of these sizes only the kernel can serve: on a TPU the XLA
    # gather of every row's whole table is an error and no fallback
    pa.paged_attention_impl(cfg.head_dim_, bs, force, kernel_only=True)
    ring = kv_cache.window_ring
    n_rep = {attn: cfg.heads_of(attn) // cfg.kv_heads_of(attn)
             for attn in POOL}
    with device_scope("attn.walk"):
        tables = {"full": kv_cache.block_tables[
            jnp.clip(slot_ids, 0, kv_cache.max_slots - 1)]}
        write_idx = {"full": paging.flat_write_indices(
            tables["full"], q_pos, bs, kv_cache.capacity, kind)}
        tables["sliding"], write_idx["sliding"] = paging.ring_write_indices(
            slot_ids, q_pos, bs, ring, kv_cache.max_slots)
        walk = {
            "full": pa.step_walk(tables["full"], q_pos, bs,
                                 kv_cache.num_blocks, cfg.head_dim_,
                                 n_rep["full"], force_pallas=force,
                                 pools=(kv_cache.k, kv_cache.v)),
            "sliding": pa.step_walk(tables["sliding"], q_pos, bs,
                                    kv_cache.wk.shape[1], cfg.head_dim_,
                                    n_rep["sliding"], force_pallas=force,
                                    sliding=cfg.sliding_window,
                                    pools=(kv_cache.wk, kv_cache.wv))}
    with device_scope("attn.pool_write"):
        pool_pos = {
            "full": paging.write_pool_positions(kv_cache.pos, q_pos,
                                                write_idx["full"]),
            "sliding": paging.write_pool_positions(kv_cache.wpos, q_pos,
                                                   write_idx["sliding"])}
    at = {k: jnp.asarray(v, jnp.int32) for k, v in cfg.pool_layers().items()}

    def view_of(kind, carry, layer):
        attn = kind.split("_")[0]
        k, v = POOL[attn]
        return paging.PagedCacheView(
            k=carry[k], v=carry[v], k_scale=None, v_scale=None,
            layer=at[kind][layer], pos=pool_pos[attn], tables=tables[attn],
            write_idx=write_idx[attn], walk=walk[attn],
            sliding=cfg.sliding_window if attn == "sliding" else None)

    def merge(carry, view, assignments):
        k, v = POOL["full" if "k" in carry else "sliding"]
        return {k: view.k, v: view.v,
                "moe_counts": carry["moe_counts"] + assignments}

    carry = dict(k=kv_cache.k, v=kv_cache.v, wk=kv_cache.wk, wv=kv_cache.wv,
                 moe_counts=jnp.zeros((3,), jnp.int32))
    carried = cfg.carried()
    stacks = {kind: p["model"][f"layers_{kind}"] for kind in carried}
    x, carry = run_layers(cfg, stacks, x, rope, None, carried, carry,
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
    if kv_cache.moe_counts is None:
        carry.pop("moe_counts")
    return logits, kv_cache.replace(pos=pool_pos["full"],
                                    wpos=pool_pos["sliding"], **carry)
