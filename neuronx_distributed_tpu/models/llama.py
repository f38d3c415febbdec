"""Llama model family (flagship), TP/SP/DP/CP-parallel, TPU-native.

Parity target: the reference's llama training examples
(``examples/training/llama/tp_zero1_llama_hf_pretrain``,
``tp_pp_llama_hf_pretrain``) which wrap HF ``LlamaForCausalLM`` with the
reference's parallel layers (``modeling_llama_nxd.py``). Here the model is
built natively from our parallel layers:

* embedding: :class:`ParallelEmbedding` (vocab-sharded over tp)
* attention: :class:`GQAQKVColumnParallelLinear` + rotary + flash/sdpa +
  :class:`RowParallelLinear`
* MLP: fused gate+up :class:`ColumnParallelLinear` + :class:`RowParallelLinear`
* loss: vocab-parallel cross-entropy over the tp-sharded lm head

Layers are stacked with ``nn.scan`` (single compiled layer body — the XLA
analogue of the reference's per-layer graph reuse) and optionally
rematerialised (activation checkpointing, reference
``utils/activation_checkpoint.py:55``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from ..modules import attention as attn_mod
from ..modules import glu, layer_stack
from ..modules.norms import RMSNorm
from ..obs.device_scopes import device_scope
from ..ops import collective_matmul as cm
from ..parallel import layers as pl
from ..parallel import loss_functions as lf
from ..parallel import mappings
from ..parallel import mesh as ps

from ..lora import LoraConfig
from ..utils.remat import resolve_remat_policy, validate_remat_policy


def _lora_kw(cfg: "LlamaConfig", name: str) -> dict:
    """lora_rank/alpha kwargs for a target sublayer (reference LoraModel
    walks the model matching target_modules; here targets select at
    construction)."""
    if cfg.lora is not None and name in cfg.lora.target_modules:
        return {"lora_rank": cfg.lora.r, "lora_alpha": cfg.lora.alpha,
                "lora_dropout": cfg.lora.dropout}
    return {}


def _act_kw(cfg: "LlamaConfig") -> dict:
    """Activation-wire kwargs threaded into every TP linear."""
    return {"activation_comm_dtype": cfg.activation_comm_dtype,
            "activation_comm_block_size": cfg.activation_comm_block_size}


# serving weight-quantization tiers (docs/quantization.md): int8/fp8 are
# per-out-channel symmetric w8a16, mxfp4/mxfp8 packed OCP microscaling
WEIGHT_QUANT_FORMATS = ("int8", "fp8", "mxfp4", "mxfp8")


def _weight_quant_dtype(fmt: str):
    """QuantizedDtype for the int8/fp8 tiers."""
    from ..quantization.quantization_utils import QuantizedDtype

    return (QuantizedDtype.INT8 if fmt == "int8"
            else QuantizedDtype.FP8E4M3)


def _quant_lm_head(cfg: "LlamaConfig", gather_output: bool, name=None):
    """The quantized ColumnParallel lm_head for ``cfg.weight_quant``."""
    kw = {} if name is None else {"name": name}
    if cfg.weight_quant.startswith("mx"):
        from ..quantization.mx_layers import MXQuantizedColumnParallel

        return MXQuantizedColumnParallel(
            features=cfg.vocab_size, mx_format=cfg.weight_quant[2:],
            gather_output=gather_output, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, **kw)
    from ..quantization.quantization_layers import QuantizedColumnParallel

    return QuantizedColumnParallel(
        features=cfg.vocab_size,
        quantized_dtype=_weight_quant_dtype(cfg.weight_quant),
        gather_output=gather_output, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, **kw)


ATTENTION_KINDS = ("full", "eva", "sparse", "lightning", "mla", "mamba2")


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rope_scaling: bool = False
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    sequence_parallel: bool = False
    remat: bool = False
    # what the rematerialised layer body keeps across fwd→bwd (names and
    # bytes in utils/remat.py). None keeps the flash kernel's own pair,
    # output and log-sum-exp, wherever a flash path ran (its backward takes
    # them as residuals, so the recomputed forward holds no attention
    # kernel), and gate's and up's products too where the train step finds
    # a chip has the bytes (``make_train_step``). A name pins that policy:
    # "nothing" recomputes everything and gives the bytes back.
    remat_policy: Optional[str] = None
    scan_layers: bool = True
    use_flash_attention: bool = False
    # force the Pallas flash kernel (interpret mode on CPU) instead of the
    # backend/shape auto-dispatch — lets CI exercise the kernel path (incl.
    # its named remat residuals) on the virtual CPU mesh. None = auto.
    attn_force_pallas: Optional[bool] = None
    # decode: shard the KV cache's SLOT dim over the cp axis and LSE-combine
    # partial attention (ops.flash_decoding; reference KV-shared groups,
    # parallel_state.py:1473 + trace/spmd.py:74). Long-context serving:
    # cache memory and decode attention FLOPs split over the decode group.
    use_flash_decoding: bool = False
    # context-parallel attention: "ring" (ppermute KV rotation),
    # "ring_pallas" (ring with the flash kernel fused into each step), or
    # "ulysses" (all-to-all seq<->head resharding; needs heads % cp == 0)
    cp_attn_impl: str = "ring"
    # wire dtype for the CP ring's KV ppermute hops (serving CP prefill;
    # ops/ring_attention wire= codec): "fp32" ships full precision and is
    # BITWISE identical to the pre-codec ring (the fallback knob);
    # "int8"/"fp8" blockwise-quantize each hop through wire_codec at
    # ~3.94x/~3.9x wire reduction (EQuARX, PAPERS.md). Serving threads
    # EngineConfig.cp_wire_dtype here; the training ring ignores it (the
    # quantizer has zero gradient).
    cp_wire_dtype: str = "fp32"
    cp_wire_block_size: int = 256
    # attention-probability dropout (training path only; active iff a
    # "dropout" rng is supplied to apply()). In-kernel on the flash path
    # via counter-based masks (reference seed plumbing:
    # kernels/flash_attn.py:30,54). Under CP: ring uses global-coordinate
    # masks (bit-identical to the cp=1 model at the same TP degree),
    # Ulysses per-rank deterministic masks.
    attention_dropout: float = 0.0
    tp_size: Optional[int] = None
    # decomposed collective-matmuls in every TP linear (qkv/o_proj/gate_up/
    # down/lm_head — docs/tp_overlap.md): None = auto (tp axis >= 4 and
    # shapes tile), True = on where shapes allow, False = monolithic.
    # Threaded from ParallelConfig.tp_overlap_comm by configure_model().
    overlap_comm: Optional[bool] = None
    # Activation-collective compression (docs/comm_compression.md): wire
    # dtype for every TP activation collective in the stack — "fp32" off,
    # "int8"/"fp8" blockwise-quantize the payloads (decomposed rings and
    # monolithic fallbacks alike). Threaded from
    # ParallelConfig.tp_activation_comm_dtype by configure_model().
    activation_comm_dtype: str = "fp32"
    activation_comm_block_size: int = 256
    # Reduced-sync TP (PAPERS.md "Tensor-Parallelism with Partially
    # Synchronized Activations"): fraction of decoder layers whose
    # row-parallel exits run the full all-reduce; the rest keep per-rank
    # partial sums, compensated by a residual resync before every synced
    # layer (cm.tp_sync_schedule). < 1.0 requires scan_layers=False (the
    # schedule varies per layer) and sequence_parallel=False (the
    # reduce-scatter also reshapes, so it cannot be elided).
    activation_sync_fraction: float = 1.0
    # Serving weight-quantization tier (docs/quantization.md): storage
    # format for every TP linear in the stack — None (fp weights),
    # "int8"/"fp8" (per-out-channel symmetric, w8a16 dequant-into-matmul)
    # or "mxfp4"/"mxfp8" (packed OCP microscaling, 32-element E8M0
    # blocks). Threaded from EngineConfig.weight_quant /
    # ParallelConfig.weight_quant; convert float checkpoints with
    # quantization.serving.quantize_params_for_serving.
    weight_quant: Optional[str] = None
    # LoRA adapters (see neuronx_distributed_tpu.lora); None = disabled
    lora: Optional["LoraConfig"] = None
    # sequence-chunked LM loss (fused_linear_cross_entropy): the loss path
    # streams `chunk`-token slices through head-matmul + CE so [B, S, V]
    # logits never materialise. None = classic full-logits path.
    loss_chunk: Optional[int] = None
    # attention kind of a layer: "full" (causal softmax over every
    # earlier position), "eva" (exact inside the query's window, chunk
    # summaries of the earlier windows: ops/eva_attention.py; the config
    # then carries window_size and chunk_size, models/evabyte.py),
    # "sparse" (exact softmax over the blocks a top-k selection over
    # compressed keys picks: ops/sparse_attention.py; the config carries
    # ``sparse``, its SparseSpec) or "lightning" (a decayed outer-product
    # state: ops/lightning_attention.py) or "mla" (a latent and a rotary
    # key shared by the heads, kv_b absorbed: models/glm_moe_lite.py's
    # LatentAttention in LlamaAttention's place) or "mamba2" (no attention:
    # a Mamba-2 state-space mixer, models/granite_hybrid.py's Mamba2Mixer
    # in LlamaAttention's place). A model whose layers differ in kind
    # (models/minicpm_sala.py, models/granite_hybrid.py) derives one
    # config a kind.
    attention_kind: str = "full"
    # what softmax attention multiplies q . k by (None: 1 / sqrt(head_dim))
    attn_scale: Optional[float] = None
    # the size of a V head where it is not a q/k head's (None: head_dim);
    # the attended values and o_proj's input are heads of this size
    v_head_dim: Optional[int] = None
    # per-head RMSNorm of q and k before the rotary embedding
    qk_norm: bool = False
    # rotary position embedding on q and k
    use_rope: bool = True
    # attention output times sigmoid(o_gate(x)) ahead of o_proj
    attn_output_gate: bool = False
    # per-head RMSNorm of the attention output, ahead of the gate
    attn_output_norm: bool = False
    # what a layer's two residual branches are multiplied by (muP depth
    # scaling); 1.0 multiplies nothing
    residual_scale: float = 1.0
    # carry the residual stream between layers in float32 (the adds run
    # in float32; norms, projections and the MLP still in ``dtype``)
    residual_fp32: bool = False

    @property
    def block_decoding(self):
        """How the family decodes a block of positions at a time
        (:class:`..inference.sampling.BlockDecoding`, a field of a config
        that does: ``models/sdar.py``): a row then attends through its
        block's last position, with or without a cache. None: a row
        attends through its own."""
        return None

    def serving_family(self):
        """What :class:`..inference.engine.ServingEngine` asks of a model
        family: its cached forward, its cache kind, what it cannot do."""
        from ..inference.paging import ServingFamily

        return ServingFamily(forward=llama_forward_with_cache)

    def feed_forward(self, h: jax.Array, tp_sync: bool = True, valid=None):
        """A decoder layer's feed-forward over its post-norm hidden
        states: ``(output, router aux pair or None)``. Called inside
        :class:`LlamaDecoderLayer`'s scope, which the block it builds
        thereby joins under its own name. ``valid`` (bool, ``h``'s shape
        without its last dimension, or None) marks the real rows of a
        packed serving step, for a feed-forward that must tell them from
        the pad rows."""
        return LlamaMLP(self, tp_sync=tp_sync, name="mlp")(h), None

    def attention(self, tp_sync: bool = True):
        """The decoder layer's attention module, under the scope name
        ``attn``."""
        return LlamaAttention(self, tp_sync=tp_sync, name="attn")

    def layer_blocks(self) -> Tuple[str, ...]:
        """The blocks of this config's (of this kind's) decoder layer,
        each under a norm of its own and into a residual add: ``"attn"``
        (the mixer :meth:`attention` builds) and ``"ffn"``
        (:meth:`feed_forward`), or one of the two where the family's
        layers are a mixer or a feed-forward alone
        (``models/nemotron_h.py``)."""
        return ("attn", "ffn")

    def decoder_layer(self, **module):
        """The decoder layer module of this config (of this kind's, for a
        config :meth:`kind_config` derived): :class:`LlamaDecoderLayer`
        unless the family's layer is no norm-attention-norm-feed-forward
        (``models/longcat_flash.py``'s double layer). Whatever it is takes
        that layer's call and returns its ``(x, aux, new_cache)``."""
        return LlamaDecoderLayer(self, **module)

    def carry_in(self, x: jax.Array) -> jax.Array:
        """The embedded tokens ``[B, T, hidden_size]`` as the carry the
        decoder layers hand on: themselves, unless the family's
        :meth:`decoder_layer` carries more a token than ``hidden_size``
        values (``models/xing4.py``'s residual streams). Called once
        behind the embedding by the models that run their layers through
        :func:`run_layers` (the latent family's, with and without a
        cache); such a family's layer takes and returns the wider ``x``."""
        return x

    def carry_out(self, x: jax.Array) -> jax.Array:
        """What the final norm reads of the last layer's carry:
        ``[B, T, hidden_size]`` again (:meth:`carry_in`'s way back)."""
        return x

    def __post_init__(self) -> None:
        if self.attention_kind not in ATTENTION_KINDS:
            raise ValueError(
                f"attention_kind must be one of {ATTENTION_KINDS}, got "
                f"{self.attention_kind!r}")
        if self.cp_attn_impl not in ("ring", "ring_pallas", "ulysses"):
            raise ValueError(
                f"cp_attn_impl must be 'ring', 'ring_pallas' or "
                f"'ulysses', got {self.cp_attn_impl!r}")
        if self.cp_wire_dtype not in ("fp32", "int8", "fp8"):
            raise ValueError(
                f"cp_wire_dtype must be 'fp32', 'int8' or 'fp8', got "
                f"{self.cp_wire_dtype!r}")
        validate_remat_policy(self.remat_policy)
        # raises on unknown wire dtypes / bad block sizes
        cm.wire_config(self.activation_comm_dtype,
                       self.activation_comm_block_size)
        if not 0.0 < self.activation_sync_fraction <= 1.0:
            raise ValueError(
                f"activation_sync_fraction must be in (0, 1], got "
                f"{self.activation_sync_fraction}")
        if self.activation_sync_fraction < 1.0:
            if self.scan_layers:
                raise ValueError(
                    "activation_sync_fraction < 1.0 requires "
                    "scan_layers=False: the sync schedule varies per layer "
                    "and scanned layers share one compiled body")
            if self.sequence_parallel:
                raise ValueError(
                    "activation_sync_fraction < 1.0 is incompatible with "
                    "sequence_parallel: the reduce-scatter exit reshapes "
                    "the activation and cannot be elided")
        if self.weight_quant is not None:
            if self.weight_quant not in WEIGHT_QUANT_FORMATS:
                raise ValueError(
                    f"weight_quant must be one of {WEIGHT_QUANT_FORMATS} "
                    f"or None, got {self.weight_quant!r}")
            incompatible = (
                "LoRA (adapters assume float kernels)"
                if self.lora is not None else
                "tie_embeddings=True (the embedding table stays float)"
                if self.tie_embeddings else
                "loss_chunk (the fused loss streams a float lm_head kernel)"
                if self.loss_chunk is not None else
                "sequence_parallel (quantized linears enter via copy_to "
                "and exit via all-reduce only)"
                if self.sequence_parallel else
                "activation_sync_fraction < 1.0"
                if self.activation_sync_fraction < 1.0 else None)
            if incompatible:
                raise ValueError(
                    f"weight_quant={self.weight_quant!r} is incompatible "
                    f"with {incompatible}")
            if self.weight_quant.startswith("mx"):
                from ..quantization.microscaling import MX_BLOCK

                q_features = self.num_heads * self.head_dim_
                bad = ("hidden_size" if self.hidden_size % MX_BLOCK else
                       "intermediate_size"
                       if self.intermediate_size % MX_BLOCK else
                       "num_heads * head_dim"
                       if q_features % MX_BLOCK else None)
                if bad:
                    raise ValueError(
                        f"weight_quant={self.weight_quant!r} needs {bad} "
                        f"divisible by the MX block ({MX_BLOCK}): all "
                        "contraction dims are block-scaled")
        if self.loss_chunk is not None:
            if self.loss_chunk <= 0:
                raise ValueError(
                    f"loss_chunk must be positive, got {self.loss_chunk}")
            unsupported = ("tie_embeddings=True" if self.tie_embeddings
                           else "LoRA targeting 'lm_head'"
                           if (self.lora is not None
                               and "lm_head" in self.lora.target_modules)
                           else None)
            if unsupported:
                # silently falling back to full logits would let users
                # believe they have the memory savings when they don't
                raise ValueError(
                    f"loss_chunk is incompatible with {unsupported}: the "
                    "fused chunked loss streams through a dedicated lm_head "
                    "kernel param; unset loss_chunk for this configuration")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def glu_products_bytes(self, tokens: int, tp: int) -> int:
        """Bytes of gate's and up's products over ``tokens`` rows in every
        layer, on one of ``tp`` ranks: what ``save_attention_and_glu``
        keeps over ``save_attention`` (``utils/remat.py``)."""
        return (self.num_layers * 2 * tokens * (self.intermediate_size // tp)
                * jnp.dtype(self.dtype).itemsize)

    def logits_bytes(self, batch: int, seq: int, tp: int) -> int:
        """Bytes of the logits the loss holds at once over ``batch`` rows
        of ``seq`` tokens on one of ``tp`` ranks: the head's product in
        the compute dtype and the float32 copy the cross-entropy reads;
        a chunk's where ``loss_chunk`` streams them."""
        seq = min(seq, self.loss_chunk or seq)
        return (batch * seq * -(-self.vocab_size // tp)
                * (jnp.dtype(self.dtype).itemsize + 4))

    def plain_layers(self) -> bool:
        """Every layer is this class's own: attention, norm, dense
        feed-forward. A family that brings its own layer, blocks, mixer or
        feed-forward holds experts' buffers and states per layer that the
        two methods above do not count."""
        return all(getattr(type(self), hook) is getattr(LlamaConfig, hook)
                   for hook in ("decoder_layer", "layer_blocks",
                                "attention", "feed_forward"))

    @property
    def v_head_dim_(self) -> int:
        return self.v_head_dim or self.head_dim_

    @property
    def attn_scale_(self) -> float:
        import math as _math

        return (1.0 / _math.sqrt(self.head_dim_) if self.attn_scale is None
                else self.attn_scale)


# Canonical configs (reference fixtures:
# examples/training/llama/tp_zero1_llama_hf_pretrain/7B_config_llama2 etc.)
LLAMA2_7B = LlamaConfig(num_layers=32, hidden_size=4096,
                        intermediate_size=11008, num_heads=32, num_kv_heads=32)
LLAMA2_70B = LlamaConfig(num_layers=80, hidden_size=8192,
                         intermediate_size=28672, num_heads=64, num_kv_heads=8)
LLAMA3_8B = LlamaConfig(vocab_size=128256, num_layers=32, hidden_size=4096,
                        intermediate_size=14336, num_heads=32, num_kv_heads=8,
                        rope_theta=500000.0)


def tiny_config(**kw) -> LlamaConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128)
    base.update(kw)
    return LlamaConfig(**base)


def _is_paged_cache_view(cache) -> bool:
    from ..inference.paging import PagedCacheView

    return isinstance(cache, PagedCacheView)


def _is_cp_prefill_view(cache) -> bool:
    from ..inference.paging import CPPrefillView

    return isinstance(cache, CPPrefillView)


def _cp_prefill_attend(cfg: LlamaConfig, q, k, v, positions, view):
    """Context-parallel ring prefill against the CP-sharded paged pool:
    scatter this rank's chunk of K/V rows into the view's layer of the
    LOCAL pool shard at the precomputed flat indices (rows another rank
    owns carry the drop sentinel), then attend the whole prompt with
    ring attention — the KV chunks rotate around the cp ring, quantized
    per ``cfg.cp_wire_dtype``. Called inside shard_map with the cp axis
    bound; the packed batch is this rank's ``[1, W_local]`` slice of the
    right-padded prompt, so ring's global arange coordinates equal the
    true token positions and causality is exact across ranks."""
    import math as _math

    from ..inference import paging
    from ..ops.ring_attention import ring_attention

    k_rows, v_rows = k[0], v[0]                      # [W_local, KV, D]
    with device_scope("attn.pool_write"):
        new_k = paging.write_pool_rows(view.k, k_rows, view.write_idx,
                                       view.layer)
        new_v = paging.write_pool_rows(view.v, v_rows, view.write_idx,
                                       view.layer)
    n_rep = q.shape[2] // k.shape[2]
    kf = attn_mod.repeat_kv(k, n_rep)
    vf = attn_mod.repeat_kv(v, n_rep)
    out = ring_attention(q, kf, vf, causal=True,
                         scale=1.0 / _math.sqrt(q.shape[-1]),
                         wire_dtype=cfg.cp_wire_dtype,
                         wire_block_size=cfg.cp_wire_block_size)
    new_view = view.replace(k=new_k, v=new_v)
    return out.astype(cfg.dtype), new_view


def _paged_cache_attend(cfg: LlamaConfig, q, k, v, positions, view,
                        sink=None):
    """Attention against the paged block pool: (optionally quantize and)
    scatter this step's K/V rows into the view's layer of the stacks at
    the precomputed flat indices, then attend that layer's blocks through
    the per-token block tables (:mod:`..ops.paged_attention`). The packed
    batch is ``[1, T]``; rows with a dropped write index (pads, preempted
    slots) never land in the pool and their outputs are discarded by the
    caller. ``v`` may be heads of ``cfg.v_head_dim_`` beside ``k``'s of
    ``cfg.head_dim_`` (the view's K stack is then a wide-key pool's, and
    the output heads of the V size); ``sink [N]`` is the layer's logit a
    query head in the softmax's denominator (None: none).
    """
    from ..inference import paging
    from ..inference.kv_cache import quantize_kv
    from ..ops.paged_attention import keys_to_lanes, paged_attention
    from ..parallel import comm

    # inside a cp shard_map the pool's block dim is sharded over the cp
    # axis: each rank writes only the rows it owns (the engine's wrapper
    # localises the tables, non-resident rows carry the drop sentinel)
    # and attends its resident blocks; partials merge with the
    # flash-decoding combine (paged/flash-decoding hybrid)
    cp = comm._axis_size(ps.CP_AXIS)
    combine = ps.CP_AXIS if cp not in (None, 1) else None
    k_rows, v_rows = k[0], v[0]                      # [T, KV_local, D]
    if view.k.ndim == 4:
        k_rows = keys_to_lanes(k_rows)

    def write(pool, rows):
        return paging.write_pool_rows(pool, rows, view.write_idx, view.layer)

    with device_scope("attn.pool_write"):
        if view.k_scale is not None:
            qk, ks = quantize_kv(k_rows)
            qv, vs = quantize_kv(v_rows)
            new_k, new_v = write(view.k, qk), write(view.v, qv)
            new_ks, new_vs = write(view.k_scale, ks), write(view.v_scale, vs)
        else:
            new_k, new_v = write(view.k, k_rows), write(view.v, v_rows)
            new_ks = new_vs = None
    # the position a row attends through: its own, or its block's last
    # where the family decodes blocks (rotary and the write keep its own)
    block = cfg.block_decoding
    out = paged_attention(
        q[0], new_k, new_v, view.pos, view.tables,
        positions[0] if block is None else block.through(positions[0]),
        view.layer, k_scale=new_ks, v_scale=new_vs,
        scale=cfg.attn_scale_,
        force_pallas=cfg.attn_force_pallas,
        combine_axis=combine, walk=view.walk, sliding=view.sliding,
        sink=sink,
        slot_rows=1 if block is None else block.block_length)[None]
    new_view = view.replace(k=new_k, v=new_v, k_scale=new_ks,
                            v_scale=new_vs)
    return out.astype(cfg.dtype), new_view


def _eva_attend(cfg: LlamaConfig, q, k, v, positions, cache, phi, mu):
    """EVA attention of one layer. No cache: the whole sequence, windows
    by reshape (positions ``0..S-1``). Paged pool: write this step's rows
    into the ring columns of the view's layer, summarise the windows the
    step completes into their summary blocks of the same layer, then
    attend both kinds of row through the table under one softmax."""
    import math as _math

    from ..ops import eva_attention as eva

    scale = 1.0 / _math.sqrt(q.shape[-1])
    if cache is None:
        out = eva.eva_attention_full(q, k, v, phi, mu, cfg.window_size,
                                     cfg.chunk_size, scale)
        return out.astype(cfg.dtype), None
    if not _is_paged_cache_view(cache) or cache.k_scale is not None:
        raise ValueError("attention_kind='eva' serves through the float "
                         "paged pool only")
    from ..inference import paging
    from ..ops.paged_attention import paged_attention

    kind = cfg.serving_family().cache_kind
    with device_scope("attn.pool_write"):
        new_k = paging.write_pool_rows(cache.k, k[0], cache.write_idx,
                                       cache.layer)
        new_v = paging.write_pool_rows(cache.v, v[0], cache.write_idx,
                                       cache.layer)
    with device_scope("attn.summarise"):
        new_k, new_v = eva.write_window_summaries(
            new_k, new_v, cache.layer, cache.roll, phi, mu, cfg.chunk_size,
            scale)
    out = paged_attention(
        q[0], new_k, new_v, cache.pos, cache.tables, positions[0],
        cache.layer, scale=scale, force_pallas=cfg.attn_force_pallas,
        window=(kind.window, kind.ring), walk=cache.walk)[None]
    return out.astype(cfg.dtype), cache.replace(k=new_k, v=new_v)


def _sparse_attend(cfg: LlamaConfig, q, k, v, view):
    """Block-sparse attention of one layer. No cache: the whole sequence
    under the selection's mask. Paged: write this step's K/V rows and the
    compressed keys of the kernels they complete into the view's layer,
    then select and attend through the table."""
    import math as _math

    from ..ops import sparse_attention as sp

    scale = 1.0 / _math.sqrt(q.shape[-1])
    if view is None:
        return sp.sparse_attention_full(q, k, v, cfg.sparse, scale).astype(
            cfg.dtype), None
    with device_scope("attn.pool_write"):
        new_k = sp.write_sparse_rows(view.k, k[0], view.write_idx,
                                     view.layer)
        new_v = sp.write_sparse_rows(view.v, v[0], view.write_idx,
                                     view.layer)
        new_ck = sp.write_compressed_keys(view.ck, new_k, view.layer,
                                          view.tables, view.q_pos,
                                          cfg.sparse)
    out, counts = sp.sparse_paged_attention(
        q[0], new_k, new_v, new_ck, view.layer, view.tables, view.q_pos,
        cfg.sparse, scale=scale, force_pallas=cfg.attn_force_pallas,
        walk=view.walk)
    return out[None].astype(cfg.dtype), view.replace(
        k=new_k, v=new_v, ck=new_ck, counts=view.counts + counts)


def _lightning_attend(cfg: LlamaConfig, q, k, v, view):
    """Lightning attention of one layer. No cache: the whole sequence in
    chunks. Paged: the packed rows continue and advance their slots'
    states in the view's layer of the state stack."""
    import math as _math

    from ..ops import lightning_attention as la

    scale = 1.0 / _math.sqrt(q.shape[-1])
    if view is None:
        with device_scope("attn.state"):
            return la.lightning_attention_full(q, k, v, scale), None
    with device_scope("attn.state"):
        out, state = la.lightning_attention_packed(
            q[0], k[0], v[0], view.state, view.layer, view.slot_ids,
            view.q_pos, scale)
    return out[None], view.replace(state=state)


class LlamaAttention(nn.Module):
    """Attention with optional KV cache for autoregressive decode.

    Training path: ``__call__(x, cos, sin, positions)``.
    Decode path (reference: KV-cache state buffers in
    ``trace/nxd_model`` + ``examples/inference/modules``): pass
    ``cache=(k_cache, v_cache)`` of shape ``[B, S_max, KV, D]`` and
    ``cache_index`` (scalar write offset); returns ``(out, new_cache)``.
    """

    cfg: LlamaConfig
    # False elides o_proj's exit all-reduce (reduced-sync TP; scheduled per
    # layer by LlamaModel via cm.tp_sync_schedule)
    tp_sync: bool = True

    @nn.compact
    def __call__(self, x: jax.Array, cos: jax.Array, sin: jax.Array,
                 positions: Optional[jax.Array] = None,
                 cache=None, cache_index=None):
        cfg = self.cfg
        head_dim = cfg.head_dim_
        with device_scope("attn.proj"):
            if cfg.weight_quant is not None and cfg.weight_quant.startswith(
                    "mx"):
                from ..quantization.mx_layers import \
                    MXGQAQKVColumnParallelLinear

                q, k, v = MXGQAQKVColumnParallelLinear(
                    num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                    head_dim=head_dim, mx_format=cfg.weight_quant[2:],
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    tp_size=cfg.tp_size, name="qkv")(x)
            elif cfg.weight_quant is not None:
                from ..quantization.quantization_layers import \
                    QuantizedGQAQKVColumnParallelLinear

                q, k, v = QuantizedGQAQKVColumnParallelLinear(
                    num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                    head_dim=head_dim,
                    quantized_dtype=_weight_quant_dtype(cfg.weight_quant),
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    tp_size=cfg.tp_size, name="qkv")(x)
            else:
                q, k, v = pl.GQAQKVColumnParallelLinear(
                    num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                    head_dim=head_dim, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    sequence_parallel=cfg.sequence_parallel,
                    tp_size=cfg.tp_size,
                    overlap_comm=cfg.overlap_comm, name="qkv",
                    **_act_kw(cfg), **_lora_kw(cfg, "qkv"))(x)
            b, s = q.shape[0], q.shape[1]
            n_q_local = q.shape[-1] // head_dim
            n_kv_local = k.shape[-1] // head_dim
            q = q.reshape(b, s, n_q_local, head_dim)
            k = k.reshape(b, s, n_kv_local, head_dim)
            v = v.reshape(b, s, n_kv_local, head_dim)
            if cfg.qk_norm:
                q = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name="q_norm")(q)
                k = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name="k_norm")(k)
            if cfg.use_rope:
                q = attn_mod.apply_rotary(q, cos, sin, positions)
                k = attn_mod.apply_rotary(k, cos, sin, positions)
        new_cache = None
        with device_scope("attn.kernel"):
            if cfg.attention_kind in ("sparse", "lightning"):
                attend = (_sparse_attend if cfg.attention_kind == "sparse"
                          else _lightning_attend)
                out, new_cache = attend(cfg, q, k, v, cache)
            elif cfg.attention_kind == "eva":
                # learned per-head pooling vectors (adaptive_phi,
                # adaptive_mu_k); the attention itself is ops/eva_attention.py
                # and, over the pool, the paged kernel with both masks
                pooling = [self.param(
                    nm, nn.with_partitioning(nn.initializers.normal(0.02),
                                             (ps.TP_AXIS, None)),
                    (n_kv_local, head_dim), cfg.param_dtype)
                    for nm in ("eva_phi", "eva_mu")]
                out, new_cache = _eva_attend(cfg, q, k, v, positions, cache,
                                             *pooling)
            elif cache is not None and _is_cp_prefill_view(cache):
                # CP ring prefill (inference/engine.py cp>1): write this
                # rank's rows into the local pool shard, ring-attend the
                # whole prompt across the cp axis
                out, new_cache = _cp_prefill_attend(cfg, q, k, v, positions,
                                                    cache)
            elif cache is not None and _is_paged_cache_view(cache):
                # paged pool (inference/paging.py): write this step's rows at
                # the precomputed flat indices, gather-attend via block tables
                out, new_cache = _paged_cache_attend(cfg, q, k, v, positions,
                                                     cache)
            elif cache is not None:
                # cache = (k_cache, v_cache, slot_positions); slot_positions
                # [B, S_max] holds each slot's true token position
                # (PAD_POSITION sentinel for pads), updated once per step by
                # the caller.
                k_cache, v_cache, slot_pos = cache
                if cfg.use_flash_decoding:
                    # slot-sharded cache (flash decoding): masked write into
                    # this rank's slot shard, partial attention + LSE combine
                    # over the decode group (ops.flash_decoding)
                    from ..inference.kv_cache import sharded_slot_update
                    from ..ops.flash_decoding import flash_decode_attention

                    with device_scope("attn.pool_write"):
                        k_cache = sharded_slot_update(
                            k_cache, k.astype(k_cache.dtype), cache_index,
                            ps.CP_AXIS)
                        v_cache = sharded_slot_update(
                            v_cache, v.astype(v_cache.dtype), cache_index,
                            ps.CP_AXIS)
                    new_cache = (k_cache, v_cache)
                    out = flash_decode_attention(
                        q, k_cache.astype(cfg.dtype),
                        v_cache.astype(cfg.dtype), slot_pos, positions,
                        axis=ps.CP_AXIS).astype(cfg.dtype)
                else:
                    with device_scope("attn.pool_write"):
                        k_cache = jax.lax.dynamic_update_slice_in_dim(
                            k_cache, k.astype(k_cache.dtype), cache_index,
                            axis=1)
                        v_cache = jax.lax.dynamic_update_slice_in_dim(
                            v_cache, v.astype(v_cache.dtype), cache_index,
                            axis=1)
                    new_cache = (k_cache, v_cache)
                    k_full = attn_mod.repeat_kv(k_cache.astype(cfg.dtype),
                                                n_q_local // n_kv_local)
                    v_full = attn_mod.repeat_kv(v_cache.astype(cfg.dtype),
                                                n_q_local // n_kv_local)
                    scores = jnp.einsum(
                        "bqnd,bknd->bnqk", q.astype(jnp.float32),
                        k_full.astype(jnp.float32)) * cfg.attn_scale_
                    # causal mask by stored positions: pads carry PAD_POSITION
                    # and are never attended, so ragged batches need no extra
                    # mask
                    mask = positions[:, :, None] >= slot_pos[:, None, :]
                    scores = jnp.where(mask[:, None], scores, -1e30)
                    probs = jax.nn.softmax(scores, axis=-1)
                    out = jnp.einsum("bnqk,bknd->bqnd", probs,
                                     v_full.astype(jnp.float32)
                                     ).astype(cfg.dtype)
            else:
                from ..parallel import comm

                # attention dropout: active iff the config rate > 0 AND the
                # caller supplied a "dropout" rng (training); eval calls
                # without the rng are deterministic with no flag-threading
                dropout_p, dropout_seed = attn_mod.attention_dropout_seed(
                    self, cfg.attention_dropout)
                cp = comm._axis_size(ps.CP_AXIS)
                if cp is not None and cp > 1 and cfg.cp_attn_impl == "ulysses":
                    # Ulysses moves the raw GQA kv heads through its
                    # all-to-alls and expands after the reshard; dropout masks
                    # there are per-rank-deterministic (see ulysses_attention)
                    from ..ops.ulysses import ulysses_attention

                    out = ulysses_attention(q, k, v, causal=True,
                                            dropout_p=dropout_p,
                                            dropout_seed=dropout_seed)
                elif cp is not None and cp > 1:
                    # context parallel: KV rotates around the cp ring
                    # (reference kernels/ring_attention_kernel.py); dropout
                    # masks use GLOBAL seq coordinates, bit-identical to the
                    # cp=1 model at the same TP degree ("ring"); "ring_pallas"
                    # fuses the flash kernel into each ring step and draws
                    # per-(rank, chunk) in-kernel masks instead
                    from ..ops.ring_attention import (ring_attention,
                                                      ring_attention_pallas)

                    k = attn_mod.repeat_kv(k, n_q_local // n_kv_local)
                    v = attn_mod.repeat_kv(v, n_q_local // n_kv_local)
                    if cfg.cp_attn_impl == "ring_pallas":
                        out = ring_attention_pallas(q, k, v,
                                                    dropout_p=dropout_p,
                                                    dropout_seed=dropout_seed)
                    else:
                        out = ring_attention(q, k, v, causal=True,
                                             dropout_p=dropout_p,
                                             dropout_seed=dropout_seed)
                elif cfg.use_flash_attention:
                    from ..ops.flash_attention import flash_attention

                    k = attn_mod.repeat_kv(k, n_q_local // n_kv_local)
                    v = attn_mod.repeat_kv(v, n_q_local // n_kv_local)
                    out = flash_attention(q, k, v, causal=True,
                                          scale=cfg.attn_scale,
                                          force_pallas=cfg.attn_force_pallas,
                                          dropout_p=dropout_p,
                                          dropout_seed=dropout_seed)
                else:
                    k = attn_mod.repeat_kv(k, n_q_local // n_kv_local)
                    v = attn_mod.repeat_kv(v, n_q_local // n_kv_local)
                    block = cfg.block_decoding
                    out = attn_mod.sdpa_reference(
                        q, k, v, causal=True,
                        # causal between blocks, whole inside one
                        segment_positions=None if block is None else
                        block.through(jnp.arange(s))[None],
                        scale=cfg.attn_scale, dropout_p=dropout_p,
                        dropout_seed=dropout_seed)
        with device_scope("attn.proj"):
            if cfg.attn_output_norm:
                out = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                              name="o_norm")(out)
            out = out.reshape(b, s, n_q_local * head_dim)
            if cfg.attn_output_gate:
                gate = pl.ColumnParallelLinear(
                    features=cfg.num_heads * head_dim, use_bias=False,
                    gather_output=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name="o_gate")(x)
                out = out.astype(cfg.dtype) * jax.nn.sigmoid(gate)
            if cfg.weight_quant is not None and cfg.weight_quant.startswith(
                    "mx"):
                from ..quantization.mx_layers import MXQuantizedRowParallel

                out = MXQuantizedRowParallel(
                    features=cfg.hidden_size,
                    mx_format=cfg.weight_quant[2:], dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name="o_proj")(out)
            elif cfg.weight_quant is not None:
                from ..quantization.quantization_layers import \
                    QuantizedRowParallel

                out = QuantizedRowParallel(
                    features=cfg.hidden_size,
                    quantized_dtype=_weight_quant_dtype(cfg.weight_quant),
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    name="o_proj")(out)
            else:
                out = pl.RowParallelLinear(
                    features=cfg.hidden_size, use_bias=False,
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    sequence_parallel=cfg.sequence_parallel,
                    overlap_comm=cfg.overlap_comm, name="o_proj",
                    tp_sync=self.tp_sync,
                    **_act_kw(cfg), **_lora_kw(cfg, "o_proj"))(out)
        if cache is not None:
            return out, new_cache
        return out


def _kept_glu(cfg: LlamaConfig, g: jax.Array, u: jax.Array):
    """Gate's and up's products under the names a rematerialised layer may
    keep (``utils/remat.py``: ``save_attention_and_glu``). Only a model
    that sets ``remat`` traces the names: a served model's program holds
    no trace of them."""
    if not cfg.remat:
        return g, u
    return checkpoint_name(g, "glu_gate"), checkpoint_name(u, "glu_up")


class LlamaMLP(nn.Module):
    cfg: LlamaConfig
    # False elides down's exit all-reduce (reduced-sync TP)
    tp_sync: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        with device_scope("ffn.dense"):
            if self.cfg.weight_quant is not None:
                return self._quantized_call(x)
            return self._float_call(x)

    def _float_call(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        # gate and up are two column-parallel kernels [H, I_local] (tp on
        # the last dim), stored and contracted as modules/glu.py says: one
        # fused [hidden, 2, intermediate] leaf made XLA copy a layer's
        # weights out of the scan's stack before every matmul on the chip
        i_local = pl._maybe_local(cfg.intermediate_size, ps.TP_AXIS)
        gate, up = glu.declare(
            self, glu.DENSE, pl.default_kernel_init, (None, ps.TP_AXIS),
            (cfg.hidden_size, i_local), cfg.param_dtype)
        lora_on = (cfg.lora is not None
                   and "gate_up" in cfg.lora.target_modules)
        lora_act = (lora_on and cfg.lora.dropout > 0.0
                    and self.has_rng("dropout"))
        if lora_on:
            # one adapter a projection, as q/k/v have
            def adapter(which):
                a = self.param(
                    f"{which}_lora_a", nn.with_partitioning(
                        pl.default_kernel_init, (None, None)),
                    (cfg.hidden_size, cfg.lora.r), cfg.param_dtype)
                b = self.param(
                    f"{which}_lora_b", nn.with_partitioning(
                        nn.initializers.zeros_init(), (None, ps.TP_AXIS)),
                    (cfg.lora.r, i_local), cfg.param_dtype)
                return a, b

            adapters = adapter("gate"), adapter("up")
            if not lora_act:
                gate, up = (w + cfg.lora.scale * jnp.dot(a, b)
                            for w, (a, b) in zip((gate, up), adapters))
        down = pl.RowParallelLinear(
            features=cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            sequence_parallel=cfg.sequence_parallel,
            overlap_comm=cfg.overlap_comm, name="down",
            tp_sync=self.tp_sync,
            **_act_kw(cfg), **_lora_kw(cfg, "down"))
        gate, up = gate.astype(cfg.dtype), up.astype(cfg.dtype)
        # both kernels ride one decomposed collective-matmul ring, as qkv
        # does; activation-space LoRA needs the gathered input, so it
        # falls back
        wire = cm.wire_config(cfg.activation_comm_dtype,
                              cfg.activation_comm_block_size)
        engaged = not lora_act and cm.overlap_engaged(
            cfg.overlap_comm, ps.TP_AXIS, x.shape, 1,
            needs_divisible=not cfg.sequence_parallel)
        if engaged or (wire is not None and not lora_act
                       and pl._bound_size(ps.TP_AXIS) is not None):
            impl = "decomposed" if engaged else "monolithic"
            matmul = (cm.all_gather_matmul if cfg.sequence_parallel
                      else cm.copy_matmul)
            g, u = matmul(x.astype(cfg.dtype), (gate, up), ps.TP_AXIS, 1,
                          impl=impl, wire=wire)
            return down(glu.gated(*_kept_glu(cfg, g, u)))
        if cfg.sequence_parallel:
            x = mappings.gather_from_sequence_parallel_region(
                x, seq_dim=1, to_model_parallel=True)
        else:
            x = mappings.copy_to_tensor_parallel_region(x)
        x = x.astype(cfg.dtype)
        g, u = glu.project(x, gate, up)
        if lora_act:
            # dropout on the adapter input cannot fold into the kernel
            x_l = nn.Dropout(rate=cfg.lora.dropout)(x, deterministic=False)
            g, u = (h + cfg.lora.scale * jnp.dot(
                jnp.dot(x_l, a.astype(cfg.dtype)), b.astype(cfg.dtype))
                    for h, (a, b) in zip((g, u), adapters))
        if pl._bound_size(ps.TP_AXIS) is None:
            g, u = (ps.with_sharding_constraint(h, None, None, ps.TP_AXIS)
                    for h in (g, u))
        return down(glu.gated(*_kept_glu(cfg, g, u)))

    def _quantized_call(self, x: jax.Array) -> jax.Array:
        """Weight-quantized (w8a16) gate_up + down: the fused [H, 2, I]
        kernel is stored quantized and dequantized into the einsum; no
        collective-matmul overlap (the packed kernel cannot ride the
        decomposed ring)."""
        cfg = self.cfg
        i_local = pl._maybe_local(cfg.intermediate_size, ps.TP_AXIS)
        x = mappings.copy_to_tensor_parallel_region(x)
        x = x.astype(cfg.dtype)
        if cfg.weight_quant.startswith("mx"):
            from ..quantization.microscaling import MX_BLOCK
            from ..quantization.mx_layers import (MXQuantizedRowParallel,
                                                  _mx_dequant, _mx_storage)

            fmt = cfg.weight_quant[2:]
            pack, store_dt = _mx_storage(fmt)
            packed = self.param(
                "gate_up_packed",
                nn.with_partitioning(lambda key, s, d: jnp.zeros(s, d),
                                     (None, ps.TP_AXIS, None)),
                (2, i_local, cfg.hidden_size // pack), store_dt)
            scale = self.param(
                "gate_up_scale",
                nn.with_partitioning(nn.initializers.ones_init(),
                                     (None, ps.TP_AXIS, None)),
                (2, i_local, cfg.hidden_size // MX_BLOCK), jnp.float32)
            w = _mx_dequant(packed, scale, fmt, cfg.dtype)   # [2, I, H]
            h = jnp.einsum("bsh,kih->bski", x, w)
            down = MXQuantizedRowParallel(
                features=cfg.hidden_size, mx_format=fmt, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name="down")
        else:
            from ..quantization.quantization_layers import \
                QuantizedRowParallel
            from ..quantization.quantization_utils import dequantize

            qdt = _weight_quant_dtype(cfg.weight_quant)
            gate_up_q = self.param(
                "gate_up_q",
                nn.with_partitioning(lambda key, s, d: jnp.zeros(s, d),
                                     (None, None, ps.TP_AXIS)),
                (cfg.hidden_size, 2, i_local), qdt.jnp_dtype)
            gate_up_scale = self.param(
                "gate_up_scale",
                nn.with_partitioning(nn.initializers.ones_init(),
                                     (None, ps.TP_AXIS)),
                (2, i_local), jnp.float32)
            w = dequantize(gate_up_q, gate_up_scale[None], cfg.dtype)
            h = jnp.einsum("bsh,hki->bski", x, w)
            down = QuantizedRowParallel(
                features=cfg.hidden_size, quantized_dtype=qdt,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="down")
        if pl._bound_size(ps.TP_AXIS) is None:
            h = ps.with_sharding_constraint(h, None, None, None, ps.TP_AXIS)
        h = nn.silu(h[..., 0, :]) * h[..., 1, :]
        return down(h)


class LlamaDecoderLayer(nn.Module):
    """The decoder layer of every family: norm, attention, residual,
    norm, the config's feed-forward, residual (either pair absent where
    the config's :meth:`LlamaConfig.layer_blocks` leaves it out). Returns
    ``(x, aux, new_cache)``: ``aux`` is the router's ``[load_balance, z]``
    pair where the feed-forward has a router and ``None`` where it has
    none, ``new_cache`` ``None`` without a cache or a mixer."""

    cfg: LlamaConfig
    # False elides this layer's row-parallel exit all-reduces (o_proj and
    # the feed-forward's); LlamaModel's non-scan loop schedules it per layer
    tp_sync: bool = True

    @nn.compact
    def __call__(self, x: jax.Array, cos: jax.Array, sin: jax.Array,
                 positions: Optional[jax.Array] = None,
                 cache=None, cache_index=None, valid=None):
        cfg = self.cfg
        blocks = cfg.layer_blocks()
        aux = new_cache = None
        # a block's scope takes in the residual add that takes its output:
        # XLA fuses a matmul's epilogue into that add and names the fusion
        # by its root (obs/device_scopes.py)
        if "attn" in blocks:
            with device_scope("norm"):
                h = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                            sequence_parallel=cfg.sequence_parallel,
                            name="input_norm")(x)
            with device_scope("attn"):
                attn_out = cfg.attention(self.tp_sync)(
                    h, cos, sin, positions, cache=cache,
                    cache_index=cache_index)
                if cache is not None:
                    attn_out, new_cache = attn_out
                if cfg.residual_scale != 1.0:
                    attn_out = attn_out * cfg.residual_scale
                x = x + attn_out
        if "ffn" in blocks:
            with device_scope("norm"):
                h = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                            sequence_parallel=cfg.sequence_parallel,
                            name="post_norm")(x)
            with device_scope("ffn"):
                ff_out, aux = cfg.feed_forward(h, self.tp_sync, valid)
                if cfg.residual_scale != 1.0:
                    ff_out = ff_out * cfg.residual_scale
                x = x + ff_out
        return x, aux, new_cache


def runs_of(kinds):
    """``(kind, first, count)`` of each run of like layers in ``kinds`` (a
    kind a layer), ``first`` the run's first index in its kind's stack:
    what a config's ``runs()`` hands :func:`run_layers`."""
    out, seen = [], {}
    for kind in kinds:
        if out and out[-1][0] == kind:
            out[-1][2] += 1
        else:
            out.append([kind, seen.get(kind, 0), 1])
        seen[kind] = seen.get(kind, 0) + 1
    return tuple(tuple(r) for r in out)


def run_layers(cfg, stacks, x, cos, sin, carried, carry=None, view_of=None,
               merge=None, valid=None, positions=None):
    """The layer pattern of a model whose layers differ in kind: one
    ``lax.scan`` a run of like layers (``cfg.runs()``: ``(kind, first,
    count)``, ``first`` the run's first index in its kind's stack), each
    kind's layer ``cfg.kind_config(kind).decoder_layer()``
    (:class:`LlamaDecoderLayer` unless the family says otherwise).
    ``stacks[kind]`` is that kind's parameter stack (leaves lead with the
    kind's depth); ``carry`` a dict of the cache's stacks, handed from
    layer to layer and run to run (None: no cache), ``carried[kind]`` the
    names of those a layer of ``kind`` reads and writes (the carry of its
    run's scan; the rest passes the run by) and ``view_of(kind, carried,
    layer)`` the view a layer is given of them; what the layer hands back
    under a carried name replaces it, or ``merge(carried, new view, aux)``
    says what does (``aux`` what the layer's feed-forward returned beside
    its output). ``valid`` (bool ``[1, T]`` or None) marks the packed
    step's real rows for the layers' feed-forward; ``positions`` (``[1,
    T]`` or None) are the rows' positions for a layer whose view does not
    carry them (:class:`..inference.paging.PagedCacheView`)."""
    for kind, first, count in cfg.runs():
        layer = cfg.kind_config(kind).decoder_layer()
        stack = stacks[kind]["layer"]

        def body(state, i, layer=layer, stack=stack, kind=kind):
            h, cache = state
            # a run of one layer is no loop once compiled, and its index a
            # constant: behind the barrier it stays an index, and the
            # layer's weights are read where they lie in the stack, as a
            # longer run reads them (a constant index made each a slice:
            # a copy of the layer's weights, every step). Every leaf's
            # stack and the index travel beside its slice, in a second
            # read-only collection (modules/layer_stack.py): a module
            # whose kernel is a custom call cannot take the slice without
            # that copy and reads its leaves there (the grouped product's
            # expert banks, gate, up and down: ExpertMLPs); nothing else
            # looks, and the slice of a leaf read as a stack is dead
            i = jax.lax.optimization_barrier(i)
            weights = jax.tree_util.tree_map(lambda w: w[i], stack)
            view = None if cache is None else view_of(kind, cache, i)
            h, aux, new = layer.apply(
                {"params": weights,
                 layer_stack.COLLECTION: layer_stack.beside(stack, i)},
                h, cos, sin, positions, cache=view, valid=valid)
            if cache is not None and merge is not None:
                cache = merge(cache, new, aux)
            elif cache is not None:
                cache = {name: getattr(new, name) for name in cache}
            return (h, cache), None

        run = (None if carry is None
               else {name: carry[name] for name in carried[kind]})
        (x, run), _ = jax.lax.scan(
            body, (x, run), jnp.arange(first, first + count,
                                       dtype=jnp.int32))
        if carry is not None:
            carry = {**carry, **run}
    return x, carry


def context_parallel_positions(input_ids: jax.Array,
                               positions: Optional[jax.Array]):
    """Global rope positions when the sequence is sliced over cp: this
    shard's tokens start at ``cp_rank * s_local`` (reference:
    ``utils/batch_utils.py:19`` slices the batch; the ring kernel gets global
    offsets). No-op when positions are given or cp is absent/1."""
    if positions is not None:
        return positions
    from ..parallel import comm

    cp = comm._axis_size(ps.CP_AXIS)
    if cp is None or cp <= 1:
        return None
    b, s_local = input_ids.shape
    start = jax.lax.axis_index(ps.CP_AXIS) * s_local
    return jnp.broadcast_to(start + jnp.arange(s_local), (b, s_local))




class _ScanBody(nn.Module):
    """nn.scan body: carries the hidden states, emits the layer's router
    aux pair (nothing where the layer has no router)."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, positions):
        x, aux, _ = self.cfg.decoder_layer(name="layer")(
            x, cos, sin, positions)
        return x, aux


class _DecodeScanBody(nn.Module):
    """nn.scan body for cached decode: carries hidden states, maps each
    layer's cache slice (leading layer dim) through, emits the new cache."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cache_kv, slot_pos, cos, sin, positions,
                 cache_index):
        if len(cache_kv) == 4:
            # quantized cache: dequant fuses into the attention read; only
            # this step's freshly written slots are (re)quantized, so
            # resident slots never accumulate requantization drift
            from ..inference.kv_cache import dequantize_kv, quantize_kv

            qk, qv, ks, vs = cache_kv
            k_l = dequantize_kv(qk, ks, self.cfg.dtype)
            v_l = dequantize_kv(qv, vs, self.cfg.dtype)
        else:
            k_l, v_l = cache_kv
        x, _, (nk, nv) = LlamaDecoderLayer(self.cfg, name="layer")(
            x, cos, sin, positions, cache=(k_l, v_l, slot_pos),
            cache_index=cache_index)
        if len(cache_kv) == 4:
            s_step = x.shape[1]
            nk_step = jax.lax.dynamic_slice_in_dim(nk, cache_index, s_step,
                                                   axis=1)
            nv_step = jax.lax.dynamic_slice_in_dim(nv, cache_index, s_step,
                                                   axis=1)
            qk_s, ks_s = quantize_kv(nk_step)
            qv_s, vs_s = quantize_kv(nv_step)
            return x, (
                jax.lax.dynamic_update_slice_in_dim(qk, qk_s, cache_index,
                                                    axis=1),
                jax.lax.dynamic_update_slice_in_dim(qv, qv_s, cache_index,
                                                    axis=1),
                jax.lax.dynamic_update_slice_in_dim(ks, ks_s, cache_index,
                                                    axis=1),
                jax.lax.dynamic_update_slice_in_dim(vs, vs_s, cache_index,
                                                    axis=1))
        return x, (nk, nv)


class _PagedScanBody(nn.Module):
    """nn.scan body for paged decode. The carry is the hidden states and
    the pool's whole stacks ``(k, v, k_scale, v_scale)`` (the scales
    ``None`` for a float pool); the scanned input is the layer's index,
    and the step's routing arrays (pool positions, per-token block
    tables, flat write indices) are broadcast. A layer writes and reads
    the stacks at ``(layer, block)`` and hands them on, so the loop
    updates the donated pool in place; scanned in and out instead, every
    layer's pool was sliced out of one stack and written into another.
    Parameter layout is identical to :class:`_DecodeScanBody` (same
    ``layer`` scope), so the same checkpoint serves both cache
    protocols."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, carry, layer, pool_pos, tables, write_idx, walk, cos,
                 sin, positions, roll=None):
        from ..inference.paging import PagedCacheView

        x, (k, v, k_scale, v_scale) = carry
        view = PagedCacheView(k=k, v=v, k_scale=k_scale, v_scale=v_scale,
                              layer=layer, pos=pool_pos, tables=tables,
                              write_idx=write_idx, walk=walk, roll=roll)
        x, _, new = LlamaDecoderLayer(self.cfg, name="layer")(
            x, cos, sin, positions, cache=view, cache_index=None)
        return (x, (new.k, new.v, new.k_scale, new.v_scale)), None


class _CPPrefillScanBody(nn.Module):
    """nn.scan body for context-parallel ring prefill: the carry and the
    scanned layer index of :class:`_PagedScanBody` over the LOCAL pool
    shard's stacks, the rank's write routing broadcast. Parameter layout
    is identical to :class:`_PagedScanBody` (same ``layer`` scope), so
    the same checkpoint serves the ring-prefill and paged-decode
    workers."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, carry, layer, pool_pos, write_idx, cos, sin,
                 positions):
        from ..inference.paging import CPPrefillView

        x, (k, v, *no_scales) = carry
        view = CPPrefillView(k=k, v=v, layer=layer, pos=pool_pos,
                             write_idx=write_idx)
        x, _, new = LlamaDecoderLayer(self.cfg, name="layer")(
            x, cos, sin, positions, cache=view, cache_index=None)
        return (x, (new.k, new.v, *no_scales)), None


class LlamaModel(nn.Module):
    """Transformer body: embedding + decoder stack + final norm. Returns
    ``(hidden states, aux)``: the layers' router aux pairs summed, ``None``
    for a stack without routers."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array,
                 positions: Optional[jax.Array] = None):
        cfg = self.cfg
        with device_scope("embed"):
            x = pl.ParallelEmbedding(
                num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="embed",
                **_lora_kw(cfg, "embed"))(input_ids)
            if cfg.residual_fp32:
                x = x.astype(jnp.float32)
            if cfg.sequence_parallel:
                x = mappings.scatter_to_sequence_parallel_region(x,
                                                                 seq_dim=1)
        positions = context_parallel_positions(input_ids, positions)
        with device_scope("attn.proj"):
            cos, sin = attn_mod.precompute_rope(
                cfg.head_dim_, cfg.max_seq_len, cfg.rope_theta,
                use_scaled=cfg.rope_scaling)

        if cfg.scan_layers:
            body_cls = _ScanBody
            if cfg.remat:
                body_cls = nn.remat(
                    body_cls, prevent_cse=False,
                    policy=resolve_remat_policy(cfg.remat_policy))
            scanned = nn.scan(
                body_cls,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="layers")
            x, aux = scanned(x, cos, sin, positions)
        else:
            auxes = []
            layer_cls = LlamaDecoderLayer
            if cfg.remat:
                layer_cls = nn.remat(
                    layer_cls, prevent_cse=False,
                    policy=resolve_remat_policy(cfg.remat_policy))
            sched = cm.tp_sync_schedule(cfg.num_layers,
                                        cfg.activation_sync_fraction)
            # only engage when there is a real bound tp axis: at size 1 (or
            # under GSPMD) the elided all-reduce is already a no-op, and the
            # resync arithmetic x_ref + psum(x - x_ref) is not a bitwise
            # identity, so stay on the plain path
            n_tp = pl._bound_size(ps.TP_AXIS)
            reduced = (cfg.activation_sync_fraction < 1.0
                       and n_tp is not None and n_tp > 1)
            # Reduced-sync resync: x_ref tracks the last fully-synchronized
            # hidden state. Unsynced layers leave each rank holding
            # x_ref + its own share of the elided all-reduce outputs, so a
            # single psum of the accumulated deviation (x - x_ref) before
            # the next synced layer recovers the full activation — one
            # collective amortized over 1/sync_fraction layers.
            x_ref = x
            pending = False
            for i in range(cfg.num_layers):
                if reduced and pending and sched[i]:
                    x = x_ref + mappings.reduce_from_tensor_parallel_region(
                        x - x_ref)
                    pending = False
                x, a, _ = layer_cls(
                    cfg, tp_sync=sched[i] if reduced else True,
                    name=f"layer_{i}")(x, cos, sin, positions)
                auxes.append(a)
                if reduced:
                    if sched[i]:
                        x_ref = x
                    else:
                        pending = True
            aux = None if auxes[0] is None else jnp.stack(auxes)
        if aux is not None:
            aux = jnp.sum(aux, axis=0)
        with device_scope("norm"):
            x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                        sequence_parallel=cfg.sequence_parallel,
                        name="norm")(x)
        # NOTE: when sequence_parallel, the returned hidden states are still
        # sequence-sharded; the LM head (a column-parallel linear with
        # sequence_parallel=True) performs the final gather itself, so the
        # gather's backward reduce-scatter correctly pairs with the head's
        # partial input-grads. Gathering here AND entering the head through
        # copy_to would double-reduce gradients (inflate by tp).
        return x, aux


class _LMHeadKernel(nn.Module):
    """LM-head kernel param only — name/shape/partitioning identical to the
    ``ColumnParallelLinear(name='lm_head')`` the full-logits path creates,
    so checkpoints interchange between the fused and unfused loss paths."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self) -> jax.Array:
        cfg = self.cfg
        out_local = pl._maybe_local(cfg.vocab_size, ps.TP_AXIS)
        return self.param(
            "kernel",
            pl._partitioned(pl.default_kernel_init, (None, ps.TP_AXIS)),
            (cfg.hidden_size, out_local), cfg.param_dtype)


class LlamaForCausalLM(nn.Module):
    """Body + tp-sharded LM head; ``loss()`` uses vocab-parallel CE so the
    full-vocab logits never materialise unsharded — and, with
    ``cfg.loss_chunk`` set, streams sequence chunks through the head matmul
    so even the vocab-*local* logits never materialise at full length
    (:func:`..parallel.loss_functions.fused_linear_cross_entropy`)."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array,
                 positions: Optional[jax.Array] = None,
                 labels: Optional[jax.Array] = None,
                 ignore_index: int = -100) -> jax.Array:
        cfg = self.cfg
        model = LlamaModel(cfg, name="model")
        x, _ = model(input_ids, positions)
        if cfg.tie_embeddings:
            if _lora_kw(cfg, "lm_head"):
                raise ValueError(
                    "LoRA on 'lm_head' is incompatible with "
                    "tie_embeddings=True (there is no lm_head param); "
                    "target 'embed' instead")
            # tied word embeddings (reference register_shared_weights,
            # pipeline/model.py:750): no lm_head param; logits re-use the
            # vocab-sharded embedding table. Gradients flow through both
            # uses of the one param.
            from flax.core import meta

            table = meta.unbox(
                model.variables["params"]["embed"]["embedding"])
            with device_scope("head"):
                logits = pl.embedding_attend(
                    table, x, sequence_parallel=cfg.sequence_parallel,
                    dtype=cfg.dtype)
            if labels is not None:
                with device_scope("loss"):
                    return lf.causal_lm_loss(logits, labels,
                                             ignore_index=ignore_index)
            return logits
        if (labels is not None and cfg.loss_chunk
                and not _lora_kw(cfg, "lm_head")):
            # fused chunked head+CE: enter the TP region exactly where
            # ColumnParallelLinear would, then stream chunks (the head's
            # matmul is inside the loss here, and reads as it)
            with device_scope("loss"):
                if cfg.sequence_parallel:
                    x = mappings.gather_from_sequence_parallel_region(
                        x, seq_dim=1, to_model_parallel=True)
                else:
                    x = mappings.copy_to_tensor_parallel_region(x)
                kernel = _LMHeadKernel(cfg, name="lm_head")()
                return lf.fused_linear_cross_entropy(
                    x.astype(cfg.dtype), kernel, labels,
                    ignore_index=ignore_index, chunk=cfg.loss_chunk,
                    dtype=cfg.dtype)
        with device_scope("head"):
            if cfg.weight_quant is not None:
                logits = _quant_lm_head(cfg, False, name="lm_head")(x)
            else:
                logits = pl.ColumnParallelLinear(
                    features=cfg.vocab_size, use_bias=False,
                    gather_output=False,
                    sequence_parallel=cfg.sequence_parallel,
                    overlap_comm=cfg.overlap_comm,
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    name="lm_head",
                    **_act_kw(cfg), **_lora_kw(cfg, "lm_head"))(x)
        if labels is not None:
            with device_scope("loss"):
                return lf.causal_lm_loss(logits, labels,
                                         ignore_index=ignore_index)
        return logits

    def loss(self, input_ids: jax.Array, labels: jax.Array,
             ignore_index: int = -100) -> jax.Array:
        return self(input_ids, labels=labels, ignore_index=ignore_index)


def llama_forward_with_cache(cfg: LlamaConfig, params, input_ids: jax.Array,
                             positions: jax.Array, kv_cache,
                             return_hidden: bool = False, slot_ids=None,
                             cp_prefill: bool = False):
    """KV-cached forward for prefill ("context_encoding") and decode
    ("token_generation") — the two compiled graphs of the reference's
    serving path (``trace/model_builder.py:495`` keys). The one cached
    forward of every family: the layer's feed-forward is the config's
    (:meth:`LlamaConfig.feed_forward`), and a family's own forward
    (``mixtral_forward_with_cache``, ``evabyte_forward_with_cache``) adds
    what is its own and calls this.

    ``params``: the family's causal-LM variables (scan_layers=True layout).
    ``kv_cache``: :class:`..inference.kv_cache.KVCache` or
    :class:`..inference.kv_cache.QuantizedKVCache` (int8 cache; reference
    kv_cache_quant, ``quantization_config.py:72``). Writes this step's K/V
    at ``kv_cache.index`` and returns ``(logits, new_cache)``.

    Paged protocol: pass a :class:`..inference.paging.PagedKVCache` /
    ``QuantizedPagedKVCache`` plus ``slot_ids [T]`` mapping each packed
    token (``input_ids [1, T]``) to its cache slot; K/V land in the slot's
    block-table blocks instead of at a contiguous write index. Contiguous
    callers are untouched.

    ``cp_prefill=True`` (paged caches only, inside shard_map with the cp
    axis bound): attention per layer is ring attention over the cp axis
    instead of the block-table gather — ``input_ids``/``positions``/
    ``slot_ids`` are this rank's ``[1, W_local]`` slice of the
    right-padded prompt, ``kv_cache`` the LOCAL pool shard with
    rank-local block tables, and each rank scatters only the K/V rows it
    computes. One pass prefills the whole prompt with compute split
    ``1/cp`` per rank (the CP prefill tier's TTFT lever).
    """
    from ..inference.kv_cache import KVCache, QuantizedKVCache
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
    p = params["params"]
    b, s = input_ids.shape
    positions = jnp.asarray(positions, jnp.int32)

    embed = pl.ParallelEmbedding(
        num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        **_lora_kw(cfg, "embed"))
    with device_scope("embed"):
        x = embed.apply({"params": p["model"]["embed"]}, input_ids)
        if cfg.residual_fp32:
            x = x.astype(jnp.float32)
    with device_scope("attn.proj"):
        cos, sin = attn_mod.precompute_rope(
            cfg.head_dim_, cfg.max_seq_len, cfg.rope_theta,
            use_scaled=cfg.rope_scaling)
        # rope lookup needs in-table indices; sentinel pads clamp to the
        # last entry (their K values are garbage but masked out)
        rope_pos = jnp.minimum(positions, cfg.max_seq_len - 1)

    if paged:
        from ..inference import paging as _paging
        from ..ops import paged_attention as _paged_attention

        slot_ids = jnp.asarray(slot_ids, jnp.int32)
        # where a position lives in its slot's table row is the family's
        # cache kind's to say (full: position // block_size)
        kind = cfg.serving_family().cache_kind.geometry(kv_cache.block_size)
        # per-token routing: each packed token carries its slot's block
        # table row and a flat pool index for this step's K/V write (==
        # capacity for pad rows -> dropped by the mode="drop" scatters)
        with device_scope("attn.walk"):
            tok_tables = kv_cache.block_tables[
                jnp.clip(slot_ids, 0, kv_cache.max_slots - 1)]
            write_idx = _paging.flat_write_indices(
                tok_tables, positions[0], kv_cache.block_size,
                kv_cache.capacity, kind)
        with device_scope("attn.pool_write"):
            slot_pos = _paging.write_pool_positions(
                kv_cache.pos, positions[0], write_idx)
        quantized = isinstance(kv_cache, QuantizedPagedKVCache)
        if cp_prefill and quantized:
            raise ValueError(
                "cp_prefill does not support quantized paged caches")
        # the pool rides the layer scan as its carry, beside x, and the
        # layers' indices are what is scanned: no layer's pool is sliced
        # out of the stacks or written back into them
        stacks = (kv_cache.k, kv_cache.v,
                  kv_cache.k_scale if quantized else None,
                  kv_cache.v_scale if quantized else None)
        rope = (cos, sin, rope_pos)
        if cp_prefill:
            body, routing = _CPPrefillScanBody, (slot_pos, write_idx) + rope
        else:
            # a window-summary kind also routes the summaries this step
            # writes, once for all layers
            with device_scope("attn.summarise"):
                roll = () if kind.ring is None else (_paging.window_roll(
                    kind, kv_cache.block_tables, slot_ids, positions[0],
                    kv_cache.block_size, kv_cache.num_blocks),)
            # so is the attention kernel's walk, which follows the tables
            # and the positions alone (None where the XLA path serves)
            with device_scope("attn.walk"):
                walk = _paged_attention.step_walk(
                    tok_tables, positions[0], kv_cache.block_size,
                    kv_cache.num_blocks, cfg.head_dim_,
                    cfg.num_heads // cfg.num_kv_heads,
                    window=None if kind.ring is None else (kind.window,
                                                           kind.ring),
                    force_pallas=cfg.attn_force_pallas,
                    pools=(kv_cache.k, kv_cache.v))
            body = _PagedScanBody
            routing = (slot_pos, tok_tables, write_idx, walk) + rope + roll
        scanned = nn.scan(
            body,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=(0,) + (nn.broadcast,) * len(routing),
            length=cfg.num_layers,
        )(cfg)
        (x, new_kv), _ = scanned.apply(
            {"params": p["model"]["layers"]}, (x, stacks),
            jnp.arange(cfg.num_layers, dtype=jnp.int32), *routing)
    else:
        # record this step's true positions in the slot-position table
        # (pads carry the PAD_POSITION sentinel and are thereby never
        # attended); shared by all layers, updated once here
        if cfg.use_flash_decoding:
            from ..inference.kv_cache import sharded_slot_update

            with device_scope("attn.pool_write"):
                slot_pos = sharded_slot_update(kv_cache.pos, positions,
                                               kv_cache.index, ps.CP_AXIS,
                                               slot_dim=1)
        else:
            with device_scope("attn.pool_write"):
                slot_pos = jax.lax.dynamic_update_slice_in_dim(
                    kv_cache.pos, positions, kv_cache.index, axis=1)

        scanned = nn.scan(
            _DecodeScanBody,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast,
                     nn.broadcast, nn.broadcast),
            out_axes=0,
            length=cfg.num_layers,
        )(cfg)
        quantized = isinstance(kv_cache, QuantizedKVCache)
        cache_kv = ((kv_cache.k, kv_cache.v, kv_cache.k_scale,
                     kv_cache.v_scale) if quantized
                    else (kv_cache.k, kv_cache.v))
        x, new_kv = scanned.apply(
            {"params": p["model"]["layers"]}, x, cache_kv,
            slot_pos, cos, sin, rope_pos, kv_cache.index)

    norm = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype)
    with device_scope("norm"):
        x = norm.apply({"params": p["model"]["norm"]}, x)
    with device_scope("head"):
        if cfg.tie_embeddings:
            logits = pl.embedding_attend(
                p["model"]["embed"]["embedding"], x, dtype=cfg.dtype,
                gather_output=True)
        elif cfg.weight_quant is not None:
            head = _quant_lm_head(cfg, True)
            logits = head.apply({"params": p["lm_head"]}, x)
        else:
            head = pl.ColumnParallelLinear(
                features=cfg.vocab_size, use_bias=False, gather_output=True,
                overlap_comm=cfg.overlap_comm,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                **_act_kw(cfg), **_lora_kw(cfg, "lm_head"))
            logits = head.apply({"params": p["lm_head"]}, x)
    if paged:
        new_k, new_v, nks, nvs = new_kv
        scales = dict(k_scale=nks, v_scale=nvs) if quantized else {}
        new_cache = kv_cache.replace(k=new_k, v=new_v, pos=slot_pos,
                                     **scales)
    elif quantized:
        new_k, new_v, nks, nvs = new_kv
        new_cache = QuantizedKVCache(
            k=new_k, v=new_v, k_scale=nks, v_scale=nvs, pos=slot_pos,
            index=kv_cache.index + s)
    else:
        new_k, new_v = new_kv
        new_cache = KVCache(k=new_k, v=new_v, pos=slot_pos,
                            index=kv_cache.index + s)
    if return_hidden:
        # post-norm hidden states — the medusa heads' input
        return logits, new_cache, x
    return logits, new_cache
