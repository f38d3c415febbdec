"""Mixtral (MoE llama) model family.

Parity target: the reference's mixtral training example
(``examples/training/mixtral``) built from its ``MoE`` module — here the
llama decoder (:class:`.llama.LlamaModel`, one layer and one cached
forward for every family) with :class:`..modules.moe.MoE` as the layer's
feed-forward, and the router auxiliary losses the stack sums added to the
loss.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..modules.moe import MoE
from ..obs.device_scopes import device_scope
from ..parallel import layers as pl
from ..parallel import loss_functions as lf
from ..parallel import mappings
from ..parallel import mesh as ps
from .llama import (LlamaConfig, LlamaModel, _act_kw, _quant_lm_head,
                    llama_forward_with_cache)


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

        return ServingFamily(
            forward=mixtral_forward_with_cache,
            unsupported={"cp": (
                "the ring-prefill worker hands each cp rank a slice of the "
                "prompt, and an MoE block inside that shard_map would "
                "route each slice against an expert capacity of its own; "
                "no test or measured cell has run that")})

    def feed_forward(self, h: jax.Array, tp_sync: bool = True, valid=None):
        """The MoE block under the scope name ``moe``: ``(output, [load
        balance loss, z loss])``. ``tp_sync=False`` (reduced-sync TP)
        cannot elide the block's internal tp reduction, because its
        expert-combine psum also moves tokens; the replicated output is
        scaled to a 1/n share instead, so an unsynced layer's deviation
        from the last synced hidden state still sums to the true update
        under the model's periodic resync psum."""
        if self.sequence_parallel:
            # routing needs full sequences: gather with to_model_parallel=
            # False (bwd = split) because ExpertMLPs' internal copy_to
            # already psums grads over tp — a reduce-scatter pairing here
            # would double-reduce (cf. the lm_head composition note in
            # llama.py)
            h = mappings.gather_from_sequence_parallel_region(
                h, seq_dim=1, to_model_parallel=False)
        out, aux = MoE(
            num_experts=self.num_experts, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size, top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            dispatch_mode=self.moe_dispatch,
            block_size=self.moe_block_size,
            sentinel_empty=self.moe_sentinel_empty,
            ep_wire_dtype=self.moe_ep_wire_dtype,
            ep_overlap=self.moe_overlap_dispatch,
            expert_impl=self.moe_expert_impl_,
            router_type=self.router_type,
            shared_expert_intermediate=self.shared_expert_intermediate,
            dtype=self.dtype, param_dtype=self.param_dtype, name="moe")(h)
        if self.sequence_parallel:
            # output is fully tp-reduced and replicated: re-shard the
            # sequence with a plain split (bwd all-gather)
            out = mappings.scatter_to_sequence_parallel_region(
                out, seq_dim=1)
        if not tp_sync:
            out = out / (pl._bound_size(ps.TP_AXIS) or 1)
        return out, jnp.stack([aux["load_balance_loss"], aux["z_loss"]])

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


class MixtralForCausalLM(nn.Module):
    cfg: MixtralConfig

    @nn.compact
    def __call__(self, input_ids, positions=None):
        cfg = self.cfg
        if cfg.tie_embeddings:
            raise ValueError(
                "tie_embeddings is not supported for Mixtral (HF Mixtral "
                "never ties); use an explicit lm_head")
        x, aux = LlamaModel(cfg, name="model")(input_ids, positions)
        with device_scope("head"):
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
        with device_scope("loss"):
            ce = lf.causal_lm_loss(logits, labels,
                                   ignore_index=ignore_index)
            return (ce + cfg.router_aux_coef * aux[0]
                    + cfg.router_z_coef * aux[1])


def mixtral_forward_with_cache(cfg: MixtralConfig, params,
                               input_ids: jax.Array,
                               positions: jax.Array, kv_cache, **kw):
    """:func:`.llama.llama_forward_with_cache` for MoE serving (the
    reference serves mixtral through the same base model_builder keys,
    ``examples/inference/modules/model_base.py``); every cache protocol
    and option (``slot_ids``, ``return_hidden``, ...) is that function's.

    At decode the tiny token count makes the dropless blockwise dispatch
    with a small block size the natural expert path
    (``cfg.moe_dispatch='blockwise'``); empty-block sentinels are enabled
    here so each step reads only the experts its tokens hit — the
    bandwidth-side equivalent of the reference's fused token-gen MoE
    kernel (``moe_fused_tkg.py:85``; forward-only, so the training-side dW
    constraint does not apply).
    """
    # token-generation-sized calls only: at prefill (large batch*seq) most
    # experts are hit anyway and the decode kernel's partial-sum layout
    # would cost O(num_ib * tokens * H) HBM for nothing (a crossover near
    # T=4 tokens TOTAL was measured once, in a record since deleted — so
    # the batch dim counts; ROADMAP S6 re-measures it)
    total_tokens = input_ids.shape[0] * input_ids.shape[1]
    if (cfg.moe_dispatch == "blockwise" and not cfg.moe_sentinel_empty
            and total_tokens * cfg.top_k <= cfg.num_experts):
        cfg = dataclasses.replace(cfg, moe_sentinel_empty=True)
    return llama_forward_with_cache(cfg, params, input_ids, positions,
                                    kv_cache, **kw)
