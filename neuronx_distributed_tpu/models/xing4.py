"""Xing4.0 (``model_type: xing4_0``;
huggingface.co/XingChen-AGI/Xing4.0-29B-A4B): the latent family's
attention and experts under a residual path of several streams.

Attention, feed-forward, cache and served forward are GLM-4.7-Flash's
(:mod:`.glm_moe_lite`: :class:`.glm_moe_lite.LatentAttention`, the
sigmoid router with a selection bias, the capacity dispatch,
:func:`.glm_moe_lite.latent_forward_with_cache`) at other shapes, with
three departures, each a hook of that code and no copy of it:

* **the residual is no** ``x + f(norm(x))``: a token carries ``hc_mult``
  streams of ``hidden_size`` values between layers (``[B, T, hc_mult *
  hidden_size]``; :meth:`Xing4Config.carry_in` widens the embedding once,
  :meth:`Xing4Config.carry_out` sums the streams ahead of the final
  norm), and each sublayer reads, writes and mixes them by maps of the
  token's own streams (manifold-constrained hyper-connections,
  :class:`..modules.hyper_connections.HyperConnection`): the layer is
  :class:`Xing4DecoderLayer`, named by :meth:`Xing4Config.decoder_layer`.
* **YaRN on the rotary key and the queries' rotary part**
  (:meth:`Xing4Config.rotary_rows`: :func:`..modules.attention
  .yarn_inv_freq`, cos and sin times ``mscale(factor, mscale) /
  mscale(factor, mscale_all_dim)``), and
* **the score's scale** ``(nope + rope)^-1/2 * mscale(factor,
  mscale_all_dim)^2`` (:attr:`Xing4Config.score_scale`), ``mscale(f, m)
  = 0.1 m ln f + 1``, as DeepSeek-V3's modelling code has both.

Left out, as GLM's is: the multi-token-prediction module
(``num_nextn_predict_layers``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax

from flax import linen as nn

from ..modules import attention as attn_mod
from ..modules import hyper_connections as hc
from ..modules.norms import RMSNorm
from ..obs.device_scopes import device_scope
from .glm_moe_lite import (GlmMoeLiteConfig, GlmMoeLiteForCausalLM,
                           latent_forward_with_cache)


#: what a published config must say for this module to be its model
_BUILT = {"model_type": "xing4_0", "attention_bias": False,
          "hidden_act": "silu", "moe_layer_freq": 1, "n_group": 1,
          "topk_group": 1, "norm_topk_prob": True,
          "scoring_func": "sigmoid", "topk_method": "noaux_tc",
          "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
#: published keys that nothing in a forward pass reads: over how many
#: devices a deployment spreads the experts
_UNREAD = ("ep_size",)
#: what ``rope_scaling`` holds (``type`` must say ``yarn``)
_YARN_KEYS = frozenset(("type", "factor", "original_max_position_embeddings",
                        "beta_fast", "beta_slow", "mscale",
                        "mscale_all_dim"))
#: every key of a published config that :meth:`Xing4Config.from_published`
#: reads, holds to :data:`_BUILT` or knows that nothing reads
PUBLISHED_KEYS = frozenset(_BUILT) | frozenset(_UNREAD) | frozenset((
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "max_position_embeddings",
    "rope_theta", "rope_scaling", "rms_norm_eps", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "first_k_dense_replace", "n_routed_experts", "num_experts_per_tok",
    "moe_intermediate_size", "n_shared_experts", "routed_scaling_factor",
    "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
    "mhc_h_res_clamp_max"))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


@dataclass(frozen=True)
class Xing4Config(GlmMoeLiteConfig):
    vocab_size: int = 131072
    hidden_size: int = 3584
    #: the leading dense layers' SwiGLU width
    intermediate_size: int = 9216
    num_layers: int = 40
    num_heads: int = 32
    max_seq_len: int = 262144
    rope_theta: float = 1e4
    rms_eps: float = 1e-6
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    first_k_dense: int = 2
    moe_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.0
    #: residual streams a token (``hc_mult``)
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    #: ``mhc_h_res_clamp_min``, ``mhc_h_res_clamp_max``
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    #: ``rope_scaling`` (``type: yarn``)
    yarn_factor: float = 64.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.hc_mult < 1 or self.hc_sinkhorn_iters < 0:
            raise ValueError("hc_mult must be at least 1 and "
                             "hc_sinkhorn_iters at least 0")
        if not self.hc_clamp_min < self.hc_clamp_max:
            raise ValueError("hc_clamp_min must lie below hc_clamp_max")

    @classmethod
    def from_published(cls, c: dict, **kw) -> "Xing4Config":
        """The config of a published ``config.json``'s keys
        (:data:`PUBLISHED_KEYS`): each is read here, is one that nothing
        reads (:data:`_UNREAD`), or must say what this module builds
        (:data:`_BUILT`: another value is refused by name). ``kw`` are
        this class's fields (dtype)."""
        wrong = {k: c.get(k) for k, v in _BUILT.items() if c.get(k) != v}
        yarn = c["rope_scaling"] or {}
        if yarn.get("type") != "yarn" or set(yarn) != _YARN_KEYS:
            wrong["rope_scaling"] = c["rope_scaling"]
        if c["num_key_value_heads"] != c["num_attention_heads"]:
            wrong["num_key_value_heads"] = c["num_key_value_heads"]
        if wrong:
            raise ValueError(
                f"xing4 builds {_BUILT}, rope_scaling of type yarn with "
                f"{sorted(_YARN_KEYS)} and as many expanded key/value "
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
            hc_mult=c["hc_mult"], hc_sinkhorn_iters=c["hc_sinkhorn_iters"],
            hc_eps=float(c["hc_eps"]),
            hc_clamp_min=float(c["mhc_h_res_clamp_min"]),
            hc_clamp_max=float(c["mhc_h_res_clamp_max"]),
            yarn_factor=float(yarn["factor"]),
            yarn_original_max_position=int(
                yarn["original_max_position_embeddings"]),
            yarn_beta_fast=float(yarn["beta_fast"]),
            yarn_beta_slow=float(yarn["beta_slow"]),
            yarn_mscale=float(yarn["mscale"]),
            yarn_mscale_all_dim=float(yarn["mscale_all_dim"])), **kw})

    @property
    def score_scale(self) -> float:
        m = yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim)
        return m * m / math.sqrt(self.qk_nope_head_dim
                                 + self.qk_rope_head_dim)

    def rotary_rows(self, positions: jax.Array):
        cos, sin = attn_mod.rope_rows(
            positions, self.qk_rope_head_dim, self.rope_theta,
            inv_freq=attn_mod.yarn_inv_freq(
                self.qk_rope_head_dim, self.rope_theta, self.yarn_factor,
                self.yarn_original_max_position, self.yarn_beta_fast,
                self.yarn_beta_slow))
        ratio = (yarn_mscale(self.yarn_factor, self.yarn_mscale)
                 / yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim))
        return (cos, sin) if ratio == 1.0 else (cos * ratio, sin * ratio)

    def decoder_layer(self, **module):
        return Xing4DecoderLayer(self, **module)

    def carry_in(self, x: jax.Array) -> jax.Array:
        with device_scope("hc.apply"):
            return hc.widen(x, self.hc_mult)

    def carry_out(self, x: jax.Array) -> jax.Array:
        with device_scope("hc.apply"):
            return hc.read_out(x, self.hc_mult)

    def hyper_connection(self, name: str) -> hc.HyperConnection:
        return hc.HyperConnection(
            streams=self.hc_mult, hidden=self.hidden_size,
            sinkhorn_iters=self.hc_sinkhorn_iters, eps=self.hc_eps,
            clamp=(self.hc_clamp_min, self.hc_clamp_max),
            rms_eps=self.rms_eps, name=name)


class Xing4DecoderLayer(nn.Module):
    """Attention and feed-forward, each between the read and the write of
    its own :class:`..modules.hyper_connections.HyperConnection`
    (``hc_attn``, ``hc_ffn``), behind :class:`.llama.LlamaDecoderLayer`'s
    call: ``x`` is the streams side by side ``[B, T, hc_mult *
    hidden_size]``, the norms, the attention and the feed-forward are
    that layer's own under its names (``input_norm``, ``attn``,
    ``post_norm``, ``mlp`` or ``moe``), and ``(x, aux, new_cache)`` comes
    back as from it."""

    cfg: Xing4Config

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, cache=None,
                 cache_index=None, valid=None):
        cfg = self.cfg

        def sublayer(mixing, norm, block, x):
            """``(X', what the block returned beside its output)``."""
            connection = cfg.hyper_connection(mixing)
            with device_scope("hc.mix"):
                pre, post, res = connection.maps(x)
            with device_scope("hc.apply"):
                u = connection.read(x, pre)
            with device_scope("norm"):
                h = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name=norm)(u)
            y, beside = block(h)
            with device_scope("hc.apply"):
                return connection.write(x, y, post, res), beside

        def attend(h):
            with device_scope("attn"):
                out = cfg.attention()(h, cos, sin, positions, cache=cache,
                                      cache_index=cache_index)
            return out if cache is not None else (out, None)

        def feed_forward(h):
            with device_scope("ffn"):
                return cfg.feed_forward(h, True, valid)

        x, new_cache = sublayer("hc_attn", "input_norm", attend, x)
        x, aux = sublayer("hc_ffn", "post_norm", feed_forward, x)
        return x, aux, new_cache


class Xing4ForCausalLM(GlmMoeLiteForCausalLM):
    """Embedding, the streams widened, the layer pattern, the streams
    summed, final norm and an untied head, no cache (tests, small
    training): the latent family's model, which takes its layer module,
    its carry and its rotary rows from the config."""


#: the paged forward of the packed serving step: the latent family's,
#: over a :class:`..inference.paging.LatentPagedCache`
xing4_forward_with_cache = latent_forward_with_cache


def tiny_config(**kw) -> Xing4Config:
    """Every mechanism at toy widths (the tests'): 4 heads of ``[24 | 8]``
    keys over a latent of 32 (a pool row of 128 lanes), values of 16, two
    dense layers and two expert layers of 8 experts, 4 streams, YaRN by 8
    over 16 positions."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                num_layers=4, num_heads=4, max_seq_len=4096, q_lora_rank=48,
                kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
                v_head_dim=16, first_k_dense=2, num_experts=8, top_k=3,
                moe_intermediate_size=32, yarn_factor=8.0,
                yarn_original_max_position=16)
    base.update(kw)
    return Xing4Config(**base)
