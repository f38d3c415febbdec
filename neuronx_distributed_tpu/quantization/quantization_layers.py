"""Quantized tensor-parallel linear layers.

Analogue of the reference's ``quantization/quantization_layers.py``
(``BaseQuantizeParallelLinear:73``, ``QuantizedColumnParallel:465``,
``QuantizedRowParallel:744``): weight-quantized variants of the parallel
linears with the same sharding and collective structure.

Two execution modes:

* ``w8a16`` (weight-only): dequantise the int8/fp8 kernel to the compute
  dtype and run a bf16 MXU matmul — HBM-bandwidth-bound decode gets the
  2-4x weight-size win.
* ``w8a8``: dynamically quantise activations per-tensor and run the matmul
  in the quantized dtype (int8 → int32 accumulate on the MXU; fp8 native),
  rescaling by ``act_scale * weight_scale``.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..modules import glu
from ..parallel import layers as pl
from ..parallel import mappings
from ..parallel import mesh as ps
from .quantization_utils import (QuantizationType, QuantizedDtype, dequantize,
                                 quantize)


class _QuantBase(nn.Module):
    features: int
    use_bias: bool = False
    quantized_dtype: QuantizedDtype = QuantizedDtype.INT8
    quantization_type: QuantizationType = (
        QuantizationType.PER_CHANNEL_SYMMETRIC)
    activation_quantization: bool = False  # w8a8 vs w8a16
    scale_block_size: int = 128  # PER_BLOCK_SYMMETRIC contraction block
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    axis: str = ps.TP_AXIS

    def _qparams(self, name: str, shape, out_names):
        """Quantized kernel + scale params (per-channel [out], per-tensor
        [1], or per-block [in/B, out] — reference blockwise int8 scheme,
        ``quantization_layers.py:356``)."""
        qkernel = self.param(
            f"{name}_q",
            nn.with_partitioning(
                lambda key, s, d: jnp.zeros(s, d), out_names),
            shape, self.quantized_dtype.jnp_dtype)
        if self.quantization_type == QuantizationType.PER_BLOCK_SYMMETRIC:
            if shape[0] % self.scale_block_size != 0:
                raise ValueError(
                    f"contraction dim {shape[0]} not divisible by "
                    f"scale_block_size {self.scale_block_size}")
            # the blocks dim shards WITH the kernel's contraction dim
            # (row-parallel: tp-sharded rows keep their own block scales)
            scale = self.param(
                f"{name}_scale",
                nn.with_partitioning(nn.initializers.ones_init(),
                                     (out_names[0], out_names[-1])),
                (shape[0] // self.scale_block_size, shape[-1]), jnp.float32)
        else:
            scale = self.param(
                f"{name}_scale",
                nn.with_partitioning(
                    nn.initializers.ones_init(),
                    (out_names[-1],) if self.quantization_type
                    == QuantizationType.PER_CHANNEL_SYMMETRIC else (None,)),
                (shape[-1],) if self.quantization_type
                == QuantizationType.PER_CHANNEL_SYMMETRIC else (1,),
                jnp.float32)
        return qkernel, scale

    def _matmul(self, x: jax.Array, qkernel: jax.Array,
                scale: jax.Array) -> jax.Array:
        if self.quantization_type == QuantizationType.PER_BLOCK_SYMMETRIC:
            if self.activation_quantization:
                raise ValueError(
                    "per-block weight quantisation is w8a16-only (block "
                    "rescale inside the accumulation is not worth the MXU "
                    "throughput loss)")
            from .quantization_utils import dequantize_blockwise

            w = dequantize_blockwise(qkernel, scale, self.dtype)
            return jnp.dot(x.astype(self.dtype), w)
        if not self.activation_quantization:
            w = dequantize(qkernel, scale[None, :], self.dtype)
            return jnp.dot(x.astype(self.dtype), w)
        # dynamic per-tensor activation quant (w8a8)
        qx, x_scale = quantize(x, self.quantized_dtype,
                               QuantizationType.PER_TENSOR_SYMMETRIC)
        if self.quantized_dtype == QuantizedDtype.INT8:
            acc = jax.lax.dot_general(
                qx, qkernel, (((qx.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        else:
            acc = jax.lax.dot_general(
                qx, qkernel, (((qx.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return (acc.astype(jnp.float32) * x_scale
                * scale[None, :]).astype(self.dtype)


class QuantizedColumnParallel(_QuantBase):
    """Reference ``QuantizedColumnParallel:465``."""

    gather_output: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        out_local = pl._maybe_local(self.features, self.axis)
        qkernel, scale = self._qparams(
            "kernel", (x.shape[-1], out_local), (None, self.axis))
        x = mappings.copy_to_tensor_parallel_region(x, self.axis)
        y = self._matmul(x, qkernel, scale)
        if self.use_bias:
            bias = self.param("bias", nn.with_partitioning(
                nn.initializers.zeros_init(), (self.axis,)),
                (out_local,), self.param_dtype)
            y = y + bias.astype(self.dtype)
        if self.gather_output:
            y = mappings.gather_from_tensor_parallel_region(y, self.axis, -1)
        return y


class QuantizedRowParallel(_QuantBase):
    """Reference ``QuantizedRowParallel:744``."""

    input_is_parallel: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if not self.input_is_parallel:
            x = mappings.scatter_to_tensor_parallel_region(x, self.axis, -1)
        qkernel, scale = self._qparams(
            "kernel", (x.shape[-1], self.features), (self.axis, None))
        y = self._matmul(x, qkernel, scale)
        y = mappings.reduce_from_tensor_parallel_region(y, self.axis)
        if self.use_bias:
            bias = self.param("bias", nn.with_partitioning(
                nn.initializers.zeros_init(), (None,)),
                (self.features,), self.param_dtype)
            y = y + bias.astype(self.dtype)
        return y


class QuantizedGQAQKVColumnParallelLinear(nn.Module):
    """Weight-quantized (w8a16) fused Q/K/V projection with GQA support —
    the quantized variant of
    :class:`...parallel.layers.GQAQKVColumnParallelLinear` the serving
    forward swaps in under ``weight_quant`` (reference
    ``modules/qkv_linear.py:371`` + ``quantization_layers.py:465``).

    Params: ``{q,k,v}_kernel_q`` int8/fp8 ``[in, out]`` + per-out-channel
    f32 ``{q,k,v}_kernel_scale``. Same KV replication contract as the float
    layer: when ``tp > num_kv_heads`` the KV kernels stay replicated (one
    stored copy per KV head), are dequantized, copied into the TP region
    and head-sliced per shard.
    """

    num_heads: int
    num_kv_heads: int
    head_dim: int
    quantized_dtype: QuantizedDtype = QuantizedDtype.INT8
    sequence_parallel: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    axis: str = ps.TP_AXIS
    seq_dim: int = 1
    tp_size: Optional[int] = None

    def _tp(self) -> int:
        s = pl._bound_size(self.axis)
        if s is not None:
            return s
        if self.tp_size is not None:
            return self.tp_size
        if ps.model_parallel_is_initialized():
            return ps.get_tensor_model_parallel_size()
        return 1

    def _qkv_param(self, name: str, shape, names):
        q = self.param(
            f"{name}_q",
            nn.with_partitioning(lambda key, s, d: jnp.zeros(s, d), names),
            shape, self.quantized_dtype.jnp_dtype)
        scale = self.param(
            f"{name}_scale",
            nn.with_partitioning(nn.initializers.ones_init(), (names[-1],)),
            (shape[-1],), jnp.float32)
        return q, scale

    @nn.compact
    def __call__(self, x: jax.Array):
        tp = self._tp()
        mult = max(1, tp // self.num_kv_heads)
        if mult > 1 and tp % self.num_kv_heads != 0:
            raise ValueError(
                f"tp size {tp} must be a multiple of num_kv_heads "
                f"{self.num_kv_heads} when tp > num_kv_heads")
        if mult == 1 and self.num_kv_heads % tp != 0:
            raise ValueError(
                f"num_kv_heads {self.num_kv_heads} not divisible by tp {tp}")
        q_features = self.num_heads * self.head_dim
        kv_features = self.num_kv_heads * self.head_dim
        q_local = pl._maybe_local(q_features, self.axis)

        qq, qs = self._qkv_param("q_kernel", (x.shape[-1], q_local),
                                 (None, self.axis))
        if mult == 1:
            kv_names = (None, self.axis)
            kv_shape = (x.shape[-1], pl._maybe_local(kv_features, self.axis))
        else:
            kv_names = (None, None)
            kv_shape = (x.shape[-1], kv_features)
        kq, ks = self._qkv_param("k_kernel", kv_shape, kv_names)
        vq, vs = self._qkv_param("v_kernel", kv_shape, kv_names)

        wq = dequantize(qq, qs[None, :], self.dtype)
        wk = dequantize(kq, ks[None, :], self.dtype)
        wv = dequantize(vq, vs[None, :], self.dtype)
        if mult > 1 and pl._bound_size(self.axis) is not None:
            wk = mappings.copy_to_tensor_parallel_region(wk, self.axis)
            wv = mappings.copy_to_tensor_parallel_region(wv, self.axis)
            head = jax.lax.axis_index(self.axis) // mult
            wk = jax.lax.dynamic_slice_in_dim(
                wk, head * self.head_dim, self.head_dim, axis=1)
            wv = jax.lax.dynamic_slice_in_dim(
                wv, head * self.head_dim, self.head_dim, axis=1)

        if self.sequence_parallel:
            x = mappings.gather_from_sequence_parallel_region(
                x, self.axis, self.seq_dim, to_model_parallel=True)
        else:
            x = mappings.copy_to_tensor_parallel_region(x, self.axis)
        x = x.astype(self.dtype)
        q = jnp.dot(x, wq)
        k = jnp.dot(x, wk)
        v = jnp.dot(x, wv)
        if pl._bound_size(self.axis) is None:
            spec = [None] * (q.ndim - 1) + [self.axis]
            q = ps.with_sharding_constraint(q, *spec)
            if mult == 1:
                k = ps.with_sharding_constraint(k, *spec)
                v = ps.with_sharding_constraint(v, *spec)
        return q, k, v


class QuantizedExpertMLPs(nn.Module):
    """Weight-quantized stacked expert GLU bank (w8a16).

    Analogue of the reference's expert-fused quantized layers
    (``quantization_layers.py:1013`` ``QuantizedExpertFusedColumnParallel``,
    ``:1215`` ``QuantizedExpertFusedRowParallel``): the 3-D ``[E, in, out]``
    expert kernels stored int8/fp8 with per-(expert, out-channel) scales,
    same ep/tp sharding and capacity-factor dispatch as
    :class:`...modules.moe.expert_mlps.ExpertMLPs` — MoE decode is
    HBM-bound on expert weights, so the 4x weight shrink is the win.
    """

    num_experts: int
    hidden_size: int
    intermediate_size: int
    top_k: int = 2
    capacity_factor: float = 2.0
    quantized_dtype: QuantizedDtype = QuantizedDtype.INT8
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    tp_axis: str = ps.TP_AXIS
    ep_axis: str = ps.EP_AXIS

    @nn.compact
    def __call__(self, x, gates, idx):
        from ..modules.moe.expert_mlps import (build_dispatch_combine,
                                               compute_capacity)

        t = x.shape[0]
        e_local = pl._maybe_local(self.num_experts, self.ep_axis)
        i_local = pl._maybe_local(self.intermediate_size, self.tp_axis)
        qdt = self.quantized_dtype.jnp_dtype

        gate_up_q = self.param(
            "gate_up_q",
            nn.with_partitioning(lambda key, s, d: jnp.zeros(s, d),
                                 (self.ep_axis, None, None, self.tp_axis)),
            (e_local, self.hidden_size, 2, i_local), qdt)
        gate_up_scale = self.param(
            "gate_up_scale",
            nn.with_partitioning(nn.initializers.ones_init(),
                                 (self.ep_axis, None, self.tp_axis)),
            (e_local, 2, i_local), jnp.float32)
        down_q = self.param(
            "down_q",
            nn.with_partitioning(lambda key, s, d: jnp.zeros(s, d),
                                 (self.ep_axis, self.tp_axis, None)),
            (e_local, i_local, self.hidden_size), qdt)
        down_scale = self.param(
            "down_scale",
            nn.with_partitioning(nn.initializers.ones_init(),
                                 (self.ep_axis, None)),
            (e_local, self.hidden_size), jnp.float32)

        gate_up = dequantize(gate_up_q, gate_up_scale[:, None], self.dtype)
        down = dequantize(down_q, down_scale[:, None], self.dtype)

        from ..parallel import comm

        ep = comm._axis_size(self.ep_axis)
        capacity = compute_capacity(t, self.num_experts, self.top_k,
                                    self.capacity_factor)
        dispatch, combine, dropped = build_dispatch_combine(
            gates, idx, self.num_experts, capacity)
        xin = jnp.einsum("tec,th->ech", dispatch.astype(self.dtype),
                         x.astype(self.dtype))
        if ep is not None and ep > 1:
            # same EP all-to-all pair as the float ExpertMLPs capacity path
            xin = mappings.enter_expert_parallel_region(
                xin, self.ep_axis, split_dim=0, concat_dim=1)
        xin = mappings.copy_to_tensor_parallel_region(xin, self.tp_axis)
        h = jnp.einsum("ech,ehki->ecki", xin, gate_up)
        h = nn.silu(h[..., 0, :]) * h[..., 1, :]
        out = jnp.einsum("eci,eih->ech", h, down)
        out = mappings.reduce_from_tensor_parallel_region(out, self.tp_axis)
        if ep is not None and ep > 1:
            out = mappings.exit_expert_parallel_region(
                out, self.ep_axis, split_dim=1, concat_dim=0)
        y = jnp.einsum("tec,ech->th", combine.astype(self.dtype), out)
        return y.astype(self.dtype), {"dropped_fraction": dropped}


def quantize_expert_params(params, quantized_dtype=QuantizedDtype.INT8):
    """Convert an :class:`ExpertMLPs` param subtree (``gate``/``up``/
    ``down``) into :class:`QuantizedExpertMLPs` params (per-expert,
    per-out-channel symmetric scales)."""
    import numpy as np

    gu = glu.fused(params, glu.EXPERTS)     # [E, H, 2, I]
    dn = np.asarray(params["down"])         # [E, I, H]
    out = {}
    # per (expert, gate/up, out-channel) over the contraction dim H
    scale_gu = np.abs(gu).max(axis=1) / quantized_dtype.max_value
    scale_gu = np.maximum(scale_gu, 1e-12)  # [E, 2, I]
    out["gate_up_q"] = _cast_q(gu / scale_gu[:, None], quantized_dtype)
    out["gate_up_scale"] = scale_gu.astype(np.float32)
    scale_dn = np.abs(dn).max(axis=1) / quantized_dtype.max_value  # [E, H]
    scale_dn = np.maximum(scale_dn, 1e-12)
    out["down_q"] = _cast_q(dn / scale_dn[:, None], quantized_dtype)
    out["down_scale"] = scale_dn.astype(np.float32)
    return out


def _cast_q(x, qdt: QuantizedDtype):
    import numpy as np

    if qdt == QuantizedDtype.INT8:
        return np.clip(np.rint(x), -127, 127).astype(np.int8)
    return jnp.asarray(x).astype(qdt.jnp_dtype)
