"""LongCat-Flash (``LongCat-Flash-Chat``;
huggingface.co/meituan-longcat/LongCat-Flash-Chat): a shortcut-connected
double layer over a latent pool.

One decoder layer (:class:`LongcatFlashDecoderLayer`) is two latent
attentions, two dense feed-forwards and one expert bank whose input is
taken after the first attention and whose output is added after the
second feed-forward (ScMoE: in a deployment the bank's exchange between
chips runs under the second attention and feed-forward), pre-norm
everywhere::

    a  = x + MLA_0(n0a(x))
    h  = n0f(a)
    m  = MoE(h)                      # the shortcut: computed here, added last
    b  = a + FFN_0(h)
    c  = b + MLA_1(n1a(b))
    d  = c + FFN_1(n1f(c))
    out = d + m

It is no norm-attention-norm-feed-forward, so the config names its layer
module (:meth:`LongcatFlashConfig.decoder_layer`) and
:func:`.llama.run_layers` scans that: one ``lax.scan`` over the double
layers, the shortcut inside the body, nothing carried across it but the
rows and the counts. The parts are the repo's own:

* **attention**: :class:`.glm_moe_lite.LatentAttention` at 64 heads of
  ``[128 nope | 64 rope]`` keys and 128 values, the normed low-rank query
  times ``sqrt(hidden / q_lora_rank)`` and the normed latent times
  ``sqrt(hidden / kv_lora_rank)`` (``mla_scale_q_lora``,
  ``mla_scale_kv_lora``); the cached row holds the scaled latent. A
  decoder layer has two, so the row stack has two layers of rows a decoder
  layer (:class:`..inference.paging.LatentCache` ``attentions=2``).
* **dense feed-forwards**: :class:`.llama.LlamaMLP` at ``intermediate_size``.
* **expert bank**: :class:`..modules.moe.MoE` under the ``softmax_bias``
  router over ``num_experts + identity_experts`` slots: the ``top_k``
  largest ``p + bias`` are chosen and weighed by ``routed_scaling_factor
  * p``, not renormalised; a choice of a slot past the real experts is an
  identity (zero-computation) expert, its weight times the row's own
  input; ``experts_held`` the real experts this device holds. No shared
  expert.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
from flax import linen as nn

from ..modules.moe import MoE
from ..modules.norms import RMSNorm
from ..obs.device_scopes import device_scope
from .glm_moe_lite import (GlmMoeLiteForCausalLM, LatentAttention,
                           LatentGeometry, latent_forward_with_cache)
from .llama import LlamaConfig, LlamaMLP

KIND = "double"
#: latent attentions (and dense feed-forwards) a decoder layer
ATTENTIONS = 2


@dataclass(frozen=True)
class LongcatFlashConfig(LatentGeometry, LlamaConfig):
    vocab_size: int = 131072
    hidden_size: int = 6144
    #: the two dense feed-forwards' SwiGLU width (``ffn_hidden_size``)
    intermediate_size: int = 12288
    #: double layers
    num_layers: int = 28
    num_heads: int = 64
    #: of the pool: one row a position, shared by the heads
    num_kv_heads: int = 1
    max_seq_len: int = 131072
    rope_theta: float = 1e7
    attention_kind: str = "mla"
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    #: real experts the router scores (``n_routed_experts``)
    num_experts: int = 512
    #: router slots past them that are identity experts (``zero_expert_num``)
    identity_experts: int = 256
    top_k: int = 12
    moe_intermediate_size: int = 2048
    routed_scaling_factor: float = 6.0
    #: ``(first, count)`` of the real experts this device holds (None: all)
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.experts_held is not None:
            first, count = self.experts_held
            if not (0 <= first and count > 0
                    and first + count <= self.num_experts):
                raise ValueError("experts_held must lie within num_experts")

    @property
    def q_lora_scale(self) -> float:
        return (math.sqrt(self.hidden_size / self.q_lora_rank)
                if self.mla_scale_q_lora else 1.0)

    @property
    def kv_lora_scale(self) -> float:
        return (math.sqrt(self.hidden_size / self.kv_lora_rank)
                if self.mla_scale_kv_lora else 1.0)

    def decoder_layer(self, **module):
        return LongcatFlashDecoderLayer(self, **module)

    def kind_config(self, kind: str) -> "LongcatFlashConfig":
        return self

    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        return ((KIND, 0, self.num_layers),)

    def rows_layer(self, kind: str, layer):
        """Where in the cache's stack a layer's first attention keeps its
        rows (the second's lie next to them)."""
        return self.serving_family().cache_kind.stack_index(layer)

    def expert_bank(self, h: jax.Array, valid=None):
        """``(m, [kept, dropped, elsewhere, identity])`` of the expert bank
        over ``h``: the real rows' choices of a real expert by what the
        dispatch did with them and of an identity expert. An expert's
        capacity is the step's rows, so nothing can drop."""
        out, aux = MoE(
            num_experts=self.num_experts, hidden_size=self.hidden_size,
            intermediate_size=self.moe_intermediate_size,
            top_k=self.top_k, capacity_factor=None,
            router_type="softmax_bias",
            router_scale=self.routed_scaling_factor,
            identity_experts=self.identity_experts, held=self.experts_held,
            dtype=self.dtype, param_dtype=self.param_dtype,
            name="moe")(h, valid=valid)
        return out, aux["assignments"]

    def serving_family(self):
        from ..inference.paging import (MOE_KEPT_DROPPED_ELSEWHERE_IDENTITY,
                                        LatentCache, ServingFamily)

        return ServingFamily(
            forward=longcat_flash_forward_with_cache,
            cache_kind=LatentCache(
                row=self.head_dim_, attentions=ATTENTIONS,
                moe_leaf=MOE_KEPT_DROPPED_ELSEWHERE_IDENTITY),
            moe_counts=True,
            unsupported={
                "speculation": "a draft lane over latent rows (a step that "
                "yields more than one token) is not written",
                "cp": "the kernel computes no cross-rank combine and the "
                "XLA reference is no serving path for a latent pool",
                "quantized": "an int8 latent row wants scales of its own "
                "for the latent and the rotary key; no kernel reads them"})


class LongcatFlashDecoderLayer(nn.Module):
    """The shortcut-connected double layer, behind
    :class:`.llama.LlamaDecoderLayer`'s call: ``(x, aux, new_cache)``,
    ``aux`` the expert bank's ``[kept, dropped, elsewhere, identity]`` and
    ``cache`` a :class:`..inference.paging.LatentLayerView` of the layer's
    first attention's rows (the second's lie next to them)."""

    cfg: LongcatFlashConfig

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, cache=None,
                 cache_index=None, valid=None):
        cfg = self.cfg

        def norm(name, h):
            with device_scope("norm"):
                return RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                               name=name)(h)

        def attend(which, h, view):
            with device_scope("attn"):
                out = LatentAttention(cfg, name=f"attn_{which}")(
                    norm(f"input_norm_{which}", h), cos, sin, positions,
                    cache=view)
                if view is not None:
                    out, view = out
                return h + out, view

        a, view = attend(0, x, cache)
        h = norm("post_norm_0", a)
        with device_scope("ffn"):
            m, assignments = cfg.expert_bank(h, valid)
        with device_scope("ffn.dense"):
            b = a + LlamaMLP(cfg, name="mlp_0")(h)
        c, view = attend(1, b, view and view.next_attention())
        h = norm("post_norm_1", c)
        with device_scope("ffn.dense"):
            d = c + LlamaMLP(cfg, name="mlp_1")(h)
        with device_scope("ffn"):
            out = d + m
        return out, assignments, view


class LongcatFlashForCausalLM(GlmMoeLiteForCausalLM):
    """Embedding, the double layers, final norm and an untied head, no
    cache (tests, small training): the latent family's model, which takes
    its layer pattern and its layer module from the config."""


#: the paged forward of the packed serving step, with
#: :func:`.glm_moe_lite.glm_moe_lite_forward_with_cache`'s signature: the
#: latent family's, over a :class:`..inference.paging.LatentPagedCache` of
#: two layers of rows a decoder layer (``rows_layer``); the step's counts
#: are ``[kept, dropped, elsewhere, identity]``
longcat_flash_forward_with_cache = latent_forward_with_cache
