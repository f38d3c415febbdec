"""DeepSeek-V3.2 (``model_type: deepseek_v32``;
huggingface.co/deepseek-ai/DeepSeek-V3.2): the latent family's attention
over a learned selection of positions, under a router limited by groups.

Attention, feed-forward, layer pattern and served forward are
GLM-4.7-Flash's (:mod:`.glm_moe_lite`: :class:`.glm_moe_lite
.LatentAttention`, :func:`.glm_moe_lite.latent_forward_with_cache`) under
Xing4.0's YaRN (:mod:`.xing4`: the rotary rows and the score's scale),
with two departures, each a hook of that code and no copy of it:

* **a row attends a selection of its context**
  (:class:`IndexedLatentAttention`, over :meth:`.glm_moe_lite
  .LatentAttention.attend`; :mod:`..ops.indexed_attention`). Beside the
  latent row a position caches one *index key* a layer, ``k_I =
  LayerNorm(W_Ik x)`` (``index_head_dim`` values, weight and bias, the
  first ``qk_rope_head_dim`` rotated at the row's position). A query row
  has ``index_n_heads`` index queries ``q_I = W_Iq c_q`` from the same
  normed low-rank query its heads come from (rotated alike) and one
  weight a head ``w = W_Iw x * index_n_heads^-1/2``; its score of a
  causal position ``s`` is ``sum_h w_h relu(q_I,h . k_I[s]) *
  index_head_dim^-1/2``; it attends the ``index_topk`` positions of
  highest score (exact; equal scores: the lower position; a context of
  ``index_topk`` positions or fewer: all of it) and nothing else. The
  selection is a layer's own. Served, the key is a second leaf of the
  pool (:class:`..inference.paging.IndexedLatentCache`), the scores read
  a slot's keys through the table once a step a layer, and the attention
  gathers the selected rows and no whole block.
* **the router's choice is limited by groups** (``n_group``,
  ``topk_group``: :class:`..modules.moe.routing.RouterSigmoid`), a share
  of the routed experts may be held (``experts_held``), and the shared
  expert is whole.

Left out, as GLM's is: the multi-token-prediction module
(``num_nextn_predict_layers``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..modules import attention as attn_mod
from ..modules.moe import MoE
from ..modules.norms import LayerNorm
from ..obs.device_scopes import device_scope
from ..ops import indexed_attention as ia
from ..parallel import layers as pl
from .glm_moe_lite import (GlmMoeLiteConfig, GlmMoeLiteForCausalLM,
                           LatentAttention, latent_forward_with_cache)
from .llama import LlamaMLP
from .xing4 import _YARN_KEYS, Xing4Config

#: what a published config must say for this module to be its model
_BUILT = {"model_type": "deepseek_v32", "attention_bias": False,
          "hidden_act": "silu", "moe_layer_freq": 1, "norm_topk_prob": True,
          "scoring_func": "sigmoid", "topk_method": "noaux_tc",
          "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
#: published keys that nothing in a forward pass reads
_UNREAD = ("ep_size",)
#: every key of a published config that
#: :meth:`DeepseekV32Config.from_published` reads, holds to :data:`_BUILT`
#: or knows that nothing reads
PUBLISHED_KEYS = frozenset(_BUILT) | frozenset(_UNREAD) | frozenset((
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
    "rope_theta", "rope_scaling", "rms_norm_eps", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "first_k_dense_replace", "n_routed_experts", "num_experts_per_tok",
    "moe_intermediate_size", "n_shared_experts", "routed_scaling_factor",
    "n_group", "topk_group", "index_n_heads", "index_head_dim",
    "index_topk"))


@dataclass(frozen=True)
class DeepseekV32Config(GlmMoeLiteConfig):
    vocab_size: int = 129280
    hidden_size: int = 7168
    #: the leading dense layers' SwiGLU width
    intermediate_size: int = 18432
    num_layers: int = 61
    num_heads: int = 128
    max_seq_len: int = 163840
    rope_theta: float = 1e4
    rms_eps: float = 1e-6
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    first_k_dense: int = 3
    num_experts: int = 256
    top_k: int = 8
    moe_intermediate_size: int = 2048
    routed_scaling_factor: float = 2.5
    #: the router's groups, and how many of them a token may choose from
    n_group: int = 8
    topk_group: int = 4
    #: ``(first, count)`` of the routed experts held here (None: all)
    experts_held: Optional[Tuple[int, int]] = None
    #: the indexer: heads, a head's (and the key's) width, positions kept
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    #: ``rope_scaling`` (``type: yarn``)
    yarn_factor: float = 40.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0

    #: YaRN on the rotary key, the queries' rotary part and the indexer's,
    #: and the score's scale: Xing4.0's, from this config's fields
    score_scale = Xing4Config.score_scale
    rotary_rows = Xing4Config.rotary_rows

    def __post_init__(self) -> None:
        super().__post_init__()
        held = self.experts_held
        if held is not None and not (
                0 <= held[0] and held[1] > 0
                and held[0] + held[1] <= self.num_experts):
            raise ValueError(f"experts_held {held} is no share of "
                             f"{self.num_experts} experts")
        if not 0 < self.qk_rope_head_dim <= self.index_head_dim:
            raise ValueError("the indexer's rotary part is the first "
                             "qk_rope_head_dim values of index_head_dim")
        if self.index_n_heads < 1 or self.index_topk < 1:
            raise ValueError("index_n_heads and index_topk must be at "
                             "least 1")

    @classmethod
    def from_published(cls, c: dict, **kw) -> "DeepseekV32Config":
        """The config of a published ``config.json``'s keys
        (:data:`PUBLISHED_KEYS`): each is read here, is one that nothing
        reads, or must say what this module builds (:data:`_BUILT`:
        another value is refused by name). ``kw`` are this class's fields
        (dtype, ``experts_held``)."""
        wrong = {k: c.get(k) for k, v in _BUILT.items() if c.get(k) != v}
        yarn = c["rope_scaling"] or {}
        if yarn.get("type") != "yarn" or set(yarn) != _YARN_KEYS:
            wrong["rope_scaling"] = c["rope_scaling"]
        if c["num_key_value_heads"] != c["num_attention_heads"]:
            wrong["num_key_value_heads"] = c["num_key_value_heads"]
        if wrong:
            raise ValueError(
                f"deepseek_v32 builds {_BUILT}, rope_scaling of type yarn "
                f"with {sorted(_YARN_KEYS)} and as many expanded key/value "
                f"heads as heads; the config says {wrong}")
        return cls(**{**dict(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            intermediate_size=c["intermediate_size"],
            num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            max_seq_len=int(c["max_position_embeddings"]),
            rope_theta=float(c["rope_theta"]),
            rms_eps=float(c["rms_norm_eps"]),
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"],
            first_k_dense=c["first_k_dense_replace"],
            num_experts=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"],
            moe_intermediate_size=c["moe_intermediate_size"],
            num_shared_experts=c["n_shared_experts"],
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            n_group=c["n_group"], topk_group=c["topk_group"],
            index_n_heads=c["index_n_heads"],
            index_head_dim=c["index_head_dim"], index_topk=c["index_topk"],
            yarn_factor=float(yarn["factor"]),
            yarn_original_max_position=int(
                yarn["original_max_position_embeddings"]),
            yarn_beta_fast=float(yarn["beta_fast"]),
            yarn_beta_slow=float(yarn["beta_slow"]),
            yarn_mscale=float(yarn["mscale"]),
            yarn_mscale_all_dim=float(yarn["mscale_all_dim"])), **kw})

    @property
    def index_scale(self) -> float:
        """What a row's index scores are multiplied by."""
        return 1.0 / math.sqrt(self.index_head_dim)

    def attention(self, tp_sync: bool = True):
        return IndexedLatentAttention(self, name="attn")

    def feed_forward(self, h: jax.Array, tp_sync: bool = True, valid=None):
        """``(output, [kept, dropped, elsewhere])``: the routed
        assignments of the real rows (zeros from a dense layer). GLM's
        feed-forward with the router's groups, over the held experts at
        the capacity of the step's rows, so nothing held can drop."""
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
            n_group=self.n_group, topk_group=self.topk_group,
            held=self.experts_held or (0, self.num_experts),
            shared_expert_intermediate=(self.num_shared_experts
                                        * self.moe_intermediate_size),
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="moe")(h, valid=valid)
        return out, aux["assignments"]

    def step_walk(self, tables, q_pos, kv_cache):
        """The ``index_key_scores`` kernel's walk: the one kernel of this
        family's attention that walks the table (the attention itself
        gathers single rows)."""
        return ia.index_walk(tables, q_pos, kv_cache.block_size,
                             self.index_n_heads, self.index_head_dim,
                             kv_cache.max_slots,
                             force_pallas=self.attn_force_pallas)

    def serving_family(self):
        from ..inference.paging import (MOE_KEPT_DROPPED_ELSEWHERE,
                                        IndexedLatentCache, ServingFamily)

        return ServingFamily(
            forward=deepseek_v32_forward_with_cache,
            cache_kind=IndexedLatentCache(
                row=self.head_dim_, index_row=self.index_head_dim,
                moe_leaf=MOE_KEPT_DROPPED_ELSEWHERE),
            moe_counts=True,
            unsupported={
                "speculation": "the model's own prediction module "
                "(num_nextn_predict_layers) is left out, and a draft "
                "lane over latent rows and index keys (a step that "
                "yields more than one token) is not written",
                "cp": "the index scores and the selection read a row's "
                "whole context on one device; no cross-rank combine is "
                "written",
                "quantized": "an int8 latent row wants scales of its own "
                "for the latent and the rotary key, an int8 or FP8 index "
                "key another; no kernel reads them"})


class IndexedLatentAttention(LatentAttention):
    """:class:`.glm_moe_lite.LatentAttention` whose rows attend the
    positions their indexer selects (the module's docstring). The
    indexer's leaves: ``index_q_b [q_lora_rank, Hi * Di]``, ``index_k [H,
    Di]``, ``index_k_norm`` (a LayerNorm's ``scale`` and ``bias``) and
    ``index_w [H, Hi]``."""

    @nn.nowrap
    def index_rows(self, x, c_q, cos, sin):
        """``(q_I [B, S, Hi, Di], k_I [B, S, Di], w [B, S, Hi] float32)``
        of the layer's input ``x`` and its normed low-rank query."""
        cfg = self.cfg
        heads, width, rope = (cfg.index_n_heads, cfg.index_head_dim,
                              cfg.qk_rope_head_dim)
        b, s, _ = x.shape

        def weight(name, rows, features):
            return self.param(
                name, nn.with_partitioning(pl.default_kernel_init,
                                           (None, None)),
                (rows, features), cfg.param_dtype).astype(cfg.dtype)

        def rotated(v):
            # [B, S, n, Di]: the first ``rope`` values of every head
            return jnp.concatenate(
                [attn_mod.apply_rotary(v[..., :rope], cos, sin),
                 v[..., rope:]], axis=-1)

        q = jnp.dot(c_q, weight("index_q_b", c_q.shape[-1], heads * width))
        k = LayerNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                      param_dtype=cfg.param_dtype, name="index_k_norm")(
            jnp.dot(x.astype(cfg.dtype),
                    weight("index_k", x.shape[-1], width)))
        w = jnp.dot(x.astype(cfg.dtype),
                    weight("index_w", x.shape[-1], heads),
                    preferred_element_type=jnp.float32)
        return (rotated(q.reshape(b, s, heads, width)),
                rotated(k[:, :, None, :])[:, :, 0],
                w * (1.0 / math.sqrt(heads)))

    @nn.nowrap
    def attend(self, x, c_q, cos, sin, q_row, rows, cache):
        cfg = self.cfg
        rank, scale, top = cfg.kv_lora_rank, cfg.score_scale, cfg.index_topk
        with device_scope("attn.proj"):
            q_i, k_i, w = self.index_rows(x, c_q, cos, sin)
        if cache is None:
            s = q_row.shape[1]
            at = jnp.arange(s, dtype=jnp.int32)
            with device_scope("attn.index"):
                index = jnp.einsum("bthd,bkd->bthk", q_i, k_i,
                                   preferred_element_type=jnp.float32)
                index = jnp.sum(ia.positive(index) * w[..., None],
                                axis=2) * cfg.index_scale
                index = jnp.where(at[None, :] <= at[:, None], index,
                                  -jnp.inf)
            with device_scope("attn.select"):
                keep = ia.select_positions(
                    index.reshape(-1, s), top).member.reshape(index.shape)
            with device_scope("attn.kernel"):
                scores = jnp.einsum(
                    "btnw,bkw->bntk", q_row.astype(jnp.float32),
                    rows.astype(jnp.float32)) * scale
                probs = jax.nn.softmax(
                    jnp.where(keep[:, None], scores, -1e30), axis=-1)
                ctx = jnp.einsum("bntk,bkr->btnr", probs,
                                 rows[..., :rank].astype(jnp.float32)
                                 ).astype(cfg.dtype)
            return ctx, None
        from ..inference import paging

        with device_scope("attn.pool_write"):
            pool = paging.write_pool_rows(cache.rows, rows[0],
                                          cache.write_idx, cache.layer)
            keys = paging.write_pool_rows(cache.index_keys, k_i[0],
                                          cache.write_idx, cache.layer)
        with device_scope("attn.index"):
            index = ia.index_scores(
                q_i[0], w[0], keys, cache.layer, cache.tables, cache.q_pos,
                cfg.index_scale, cache.slots,
                force_pallas=cfg.attn_force_pallas, walk=cache.walk)
        with device_scope("attn.select"):
            selection = ia.select_positions(index, top)
            counts = ia.selection_counts(
                selection, cache.tables, cache.q_pos, pool.shape[2], top)
        with device_scope("attn.kernel"):
            ctx = ia.attend_selected(
                q_row[0], pool, cache.layer, cache.tables,
                selection.positions, selection.chosen, rank, scale)[None]
        return ctx, cache.replace(rows=pool, index_keys=keys,
                                  counts=cache.counts + counts)


class DeepseekV32ForCausalLM(GlmMoeLiteForCausalLM):
    """Embedding, the layer pattern, final norm and an untied head, no
    cache (tests, small training): the latent family's model, which takes
    its attention and its feed-forward from the config."""


#: the paged forward of the packed serving step: the latent family's,
#: over a :class:`..inference.paging.IndexedLatentPagedCache`
deepseek_v32_forward_with_cache = latent_forward_with_cache


def tiny_config(**kw) -> DeepseekV32Config:
    """Every mechanism at toy widths (the tests'): 4 heads of ``[24 | 8]``
    keys over a latent of 32, 2 index heads of 16 that keep 8 positions,
    one dense layer and two expert layers of 16 experts in 4 groups of
    which 2, YaRN by 8 over 16 positions."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                num_layers=3, num_heads=4, max_seq_len=4096, q_lora_rank=48,
                kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
                v_head_dim=16, first_k_dense=1, num_experts=16, top_k=3,
                moe_intermediate_size=32, n_group=4, topk_group=2,
                index_n_heads=2, index_head_dim=16, index_topk=8,
                yarn_factor=8.0, yarn_original_max_position=16)
    base.update(kw)
    return DeepseekV32Config(**base)
