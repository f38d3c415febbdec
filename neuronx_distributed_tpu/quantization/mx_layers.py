"""MX (microscaling) weight-consuming layers.

Analogue of the reference's MX integration
(``experimental/expert_mlps_mx.py:299`` fp4/fp8 expert MLPs,
``quantization/microscaling/transform_weights.py`` weight transform,
``modules/moe/blockwise.py:1176`` MX blockwise kernels): layers whose
parameters ARE the packed MX payloads — fp4 codes two-per-byte (or fp8
elements) plus E8M0 per-32-block scales — so HBM holds 1/4 (fp4) or 1/2
(fp8) of the bf16 bytes and decode reads shrink accordingly.

TPU-native mapping: the MXU has no fp4/fp8 ALU, so dequantisation is a
nibble-unpack + 8-entry-grid gather + block-scale multiply that XLA fuses
into the consuming matmul's operand read; compute runs bf16 on the MXU.
Scales are exact powers of two (E8M0), matching the OCP MX spec.

Weight layout convention: packed kernels store the CONTRACTION dim last
(``[out, in_packed]``), because MX blocks run along the last axis and
quantisation error then stays bounded per dot product (the OCP layout the
reference's transform produces).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..modules import glu
from ..parallel import layers as pl
from ..parallel import mappings
from ..parallel import mesh as ps
from .microscaling import (MX_BLOCK, mx_dequantize_fp4, mx_dequantize_fp8,
                           mx_quantize_fp4, mx_quantize_fp8)


def _mx_dequant(packed, scales, mx_format: str, dtype):
    if mx_format == "fp4":
        return mx_dequantize_fp4(packed, scales, dtype=dtype)
    if mx_format == "fp8":
        return mx_dequantize_fp8(packed, scales, dtype=dtype)
    raise ValueError(f"unknown mx_format {mx_format!r}")


def _mx_storage(mx_format: str):
    """(pack_factor, storage_dtype) for an MX format: fp4 packs 2 codes per
    uint8 byte; fp8 stores e4m3 elements directly."""
    if mx_format == "fp4":
        return 2, jnp.uint8
    if mx_format == "fp8":
        import ml_dtypes

        return 1, jnp.dtype(ml_dtypes.float8_e4m3fn)
    raise ValueError(f"unknown mx_format {mx_format!r}")


def mx_pack_linear(w, mx_format: str = "fp4"):
    """Transform a float kernel ``[in, out]`` into MX params for the MX
    layers: ``{"kernel_packed": [out, in/2 (fp4) | in (fp8)] ,
    "kernel_scale": [out, in/32]}`` — contraction dim last, blocks along it
    (reference ``transform_weights.py``)."""
    wt = np.asarray(w, np.float32).T  # [out, in]
    if mx_format == "fp4":
        packed, scale = mx_quantize_fp4(wt)
    elif mx_format == "fp8":
        packed, scale = mx_quantize_fp8(wt)
    else:
        raise ValueError(f"unknown mx_format {mx_format!r}")
    return {"kernel_packed": packed, "kernel_scale": scale}


class MXQuantizedColumnParallel(nn.Module):
    """Column-parallel linear consuming packed MX weights (the MX variant of
    :class:`.quantization_layers.QuantizedColumnParallel`; reference MX
    layer integration ``expert_mlps_mx.py:299``).

    Params: ``kernel_packed [out_local, in_packed]`` (uint8 fp4 pairs, or
    fp8 elements), ``kernel_scale [out_local, in/32]`` f32 E8M0 values.
    """

    features: int
    mx_format: str = "fp4"
    use_bias: bool = False
    gather_output: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    axis: str = ps.TP_AXIS

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        in_dim = x.shape[-1]
        out_local = pl._maybe_local(self.features, self.axis)
        pack, store_dt = _mx_storage(self.mx_format)
        packed = self.param(
            "kernel_packed",
            nn.with_partitioning(lambda key, s, d: jnp.zeros(s, d),
                                 (self.axis, None)),
            (out_local, in_dim // pack), store_dt)
        scale = self.param(
            "kernel_scale",
            nn.with_partitioning(nn.initializers.ones_init(),
                                 (self.axis, None)),
            (out_local, in_dim // MX_BLOCK), jnp.float32)

        x = mappings.copy_to_tensor_parallel_region(x, self.axis)
        w = _mx_dequant(packed, scale, self.mx_format, self.dtype)
        # contract x's last dim with w's last (contraction-last layout)
        y = jax.lax.dot_general(
            x.astype(self.dtype), w,
            (((x.ndim - 1,), (1,)), ((), ())))
        if self.use_bias:
            bias = self.param("bias", nn.with_partitioning(
                nn.initializers.zeros_init(), (self.axis,)),
                (out_local,), self.param_dtype)
            y = y + bias.astype(self.dtype)
        if self.gather_output:
            y = mappings.gather_from_tensor_parallel_region(y, self.axis, -1)
        return y


class MXQuantizedRowParallel(nn.Module):
    """Row-parallel linear consuming packed MX weights.

    Params: ``kernel_packed [features, in_local_packed]``,
    ``kernel_scale [features, in_local/32]`` — the contraction (row) dim is
    tp-sharded, blocks along it stay within one shard."""

    features: int
    mx_format: str = "fp4"
    use_bias: bool = False
    input_is_parallel: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    axis: str = ps.TP_AXIS

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if not self.input_is_parallel:
            x = mappings.scatter_to_tensor_parallel_region(x, self.axis, -1)
        in_local = x.shape[-1]
        pack, store_dt = _mx_storage(self.mx_format)
        packed = self.param(
            "kernel_packed",
            nn.with_partitioning(lambda key, s, d: jnp.zeros(s, d),
                                 (None, self.axis)),
            (self.features, in_local // pack), store_dt)
        scale = self.param(
            "kernel_scale",
            nn.with_partitioning(nn.initializers.ones_init(),
                                 (None, self.axis)),
            (self.features, in_local // MX_BLOCK), jnp.float32)
        w = _mx_dequant(packed, scale, self.mx_format, self.dtype)
        y = jax.lax.dot_general(
            x.astype(self.dtype), w,
            (((x.ndim - 1,), (1,)), ((), ())))
        y = mappings.reduce_from_tensor_parallel_region(y, self.axis)
        if self.use_bias:
            bias = self.param("bias", nn.with_partitioning(
                nn.initializers.zeros_init(), (None,)),
                (self.features,), self.param_dtype)
            y = y + bias.astype(self.dtype)
        return y


class MXGQAQKVColumnParallelLinear(nn.Module):
    """Fused Q/K/V projection from packed MX weights with GQA support —
    the MX variant of
    :class:`...parallel.layers.GQAQKVColumnParallelLinear` (same KV
    replication contract; see
    :class:`.quantization_layers.QuantizedGQAQKVColumnParallelLinear`).

    Params (contraction dim last): ``{q,k,v}_kernel_packed
    [out, in_packed]`` + ``{q,k,v}_kernel_scale [out, in/32]``.
    """

    num_heads: int
    num_kv_heads: int
    head_dim: int
    mx_format: str = "fp4"
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

    def _mx_param(self, name: str, out_dim: int, in_dim: int, out_name):
        pack, store_dt = _mx_storage(self.mx_format)
        packed = self.param(
            f"{name}_packed",
            nn.with_partitioning(lambda key, s, d: jnp.zeros(s, d),
                                 (out_name, None)),
            (out_dim, in_dim // pack), store_dt)
        scale = self.param(
            f"{name}_scale",
            nn.with_partitioning(nn.initializers.ones_init(),
                                 (out_name, None)),
            (out_dim, in_dim // MX_BLOCK), jnp.float32)
        return packed, scale

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
        in_dim = x.shape[-1]
        q_features = self.num_heads * self.head_dim
        kv_features = self.num_kv_heads * self.head_dim
        q_local = pl._maybe_local(q_features, self.axis)

        qp, qs = self._mx_param("q_kernel", q_local, in_dim, self.axis)
        if mult == 1:
            kv_out = pl._maybe_local(kv_features, self.axis)
            kv_name: Optional[str] = self.axis
        else:
            kv_out, kv_name = kv_features, None
        kp, ks = self._mx_param("k_kernel", kv_out, in_dim, kv_name)
        vp, vs = self._mx_param("v_kernel", kv_out, in_dim, kv_name)

        wq = _mx_dequant(qp, qs, self.mx_format, self.dtype)  # [out, in]
        wk = _mx_dequant(kp, ks, self.mx_format, self.dtype)
        wv = _mx_dequant(vp, vs, self.mx_format, self.dtype)
        if mult > 1 and pl._bound_size(self.axis) is not None:
            wk = mappings.copy_to_tensor_parallel_region(wk, self.axis)
            wv = mappings.copy_to_tensor_parallel_region(wv, self.axis)
            head = jax.lax.axis_index(self.axis) // mult
            wk = jax.lax.dynamic_slice_in_dim(
                wk, head * self.head_dim, self.head_dim, axis=0)
            wv = jax.lax.dynamic_slice_in_dim(
                wv, head * self.head_dim, self.head_dim, axis=0)

        if self.sequence_parallel:
            x = mappings.gather_from_sequence_parallel_region(
                x, self.axis, self.seq_dim, to_model_parallel=True)
        else:
            x = mappings.copy_to_tensor_parallel_region(x, self.axis)
        x = x.astype(self.dtype)
        dims = (((x.ndim - 1,), (1,)), ((), ()))
        q = jax.lax.dot_general(x, wq, dims)
        k = jax.lax.dot_general(x, wk, dims)
        v = jax.lax.dot_general(x, wv, dims)
        if pl._bound_size(self.axis) is None:
            spec = [None] * (q.ndim - 1) + [self.axis]
            q = ps.with_sharding_constraint(q, *spec)
            if mult == 1:
                k = ps.with_sharding_constraint(k, *spec)
                v = ps.with_sharding_constraint(v, *spec)
        return q, k, v


class MXExpertMLPs(nn.Module):
    """Stacked expert GLU bank from packed MX weights — the reference's
    flagship MX consumer (``experimental/expert_mlps_mx.py:299``): MoE
    decode is HBM-bound on expert weights, so fp4 reads 1/4 the bytes.

    Params (contraction dim last, packed):
    ``gate_up_packed [E_local, 2, I_local, H_packed]``,
    ``gate_up_scale  [E_local, 2, I_local, H/32]``,
    ``down_packed    [E_local, H, I_local_packed]``,
    ``down_scale     [E_local, H, I_local/32]``.
    Dispatch is the capacity mask-einsum; ``dropless=True`` (default, the
    decode contract) raises capacity to T — an expert can receive at most
    one assignment per token, so T slots can never drop — keeping the MX
    output aligned with the float reference beyond quantisation error.
    """

    num_experts: int
    hidden_size: int
    intermediate_size: int
    top_k: int = 2
    capacity_factor: float = 2.0
    dropless: bool = True
    mx_format: str = "fp4"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    tp_axis: str = ps.TP_AXIS
    ep_axis: str = ps.EP_AXIS

    @nn.compact
    def __call__(self, x, gates, idx):
        from ..modules.moe.expert_mlps import (build_dispatch_combine,
                                               compute_capacity)
        from ..parallel import comm

        t = x.shape[0]
        e_local = pl._maybe_local(self.num_experts, self.ep_axis)
        i_local = pl._maybe_local(self.intermediate_size, self.tp_axis)
        h = self.hidden_size
        pack, store_dt = _mx_storage(self.mx_format)

        gu_packed = self.param(
            "gate_up_packed",
            nn.with_partitioning(lambda key, s, d: jnp.zeros(s, d),
                                 (self.ep_axis, None, self.tp_axis, None)),
            (e_local, 2, i_local, h // pack), store_dt)
        gu_scale = self.param(
            "gate_up_scale",
            nn.with_partitioning(nn.initializers.ones_init(),
                                 (self.ep_axis, None, self.tp_axis, None)),
            (e_local, 2, i_local, h // MX_BLOCK), jnp.float32)
        dn_packed = self.param(
            "down_packed",
            nn.with_partitioning(lambda key, s, d: jnp.zeros(s, d),
                                 (self.ep_axis, None, self.tp_axis)),
            (e_local, h, i_local // pack), store_dt)
        dn_scale = self.param(
            "down_scale",
            nn.with_partitioning(nn.initializers.ones_init(),
                                 (self.ep_axis, None, self.tp_axis)),
            (e_local, h, i_local // MX_BLOCK), jnp.float32)

        gate_up = _mx_dequant(gu_packed, gu_scale, self.mx_format,
                              self.dtype)  # [E, 2, I, H]
        down = _mx_dequant(dn_packed, dn_scale, self.mx_format,
                           self.dtype)    # [E, H, I]

        ep = comm._axis_size(self.ep_axis)
        capacity = compute_capacity(t, self.num_experts, self.top_k,
                                    self.capacity_factor)
        if self.dropless:
            capacity = max(capacity, t)
        dispatch, combine, dropped = build_dispatch_combine(
            gates, idx, self.num_experts, capacity)
        xin = jnp.einsum("tec,th->ech", dispatch.astype(self.dtype),
                         x.astype(self.dtype))
        if ep is not None and ep > 1:
            xin = mappings.enter_expert_parallel_region(
                xin, self.ep_axis, split_dim=0, concat_dim=1)
        xin = mappings.copy_to_tensor_parallel_region(xin, self.tp_axis)
        hmid = jnp.einsum("ech,ekih->ecki", xin, gate_up)
        hmid = nn.silu(hmid[..., 0, :]) * hmid[..., 1, :]
        out = jnp.einsum("eci,ehi->ech", hmid, down)
        out = mappings.reduce_from_tensor_parallel_region(out, self.tp_axis)
        if ep is not None and ep > 1:
            out = mappings.exit_expert_parallel_region(
                out, self.ep_axis, split_dim=1, concat_dim=0)
        y = jnp.einsum("tec,ech->th", combine.astype(self.dtype), out)
        return y.astype(self.dtype), {"dropped_fraction": dropped}


def mx_pack_expert_params(params, mx_format: str = "fp4"):
    """Transform an :class:`...modules.moe.ExpertMLPs` param subtree
    (``gate``, ``up [E,H,I]`` / ``down [E,I,H]``) into :class:`MXExpertMLPs`
    params (contraction-last packed layout) — the converter-side MX
    transform (reference ``microscaling/transform_weights.py``)."""
    gu = glu.fused(params, glu.EXPERTS).astype(np.float32)  # [E, H, 2, I]
    dn = np.asarray(params["down"], np.float32)      # [E, I, H]
    gu_t = np.transpose(gu, (0, 2, 3, 1))            # [E, 2, I, H]
    dn_t = np.transpose(dn, (0, 2, 1))               # [E, H, I]
    quant = mx_quantize_fp4 if mx_format == "fp4" else mx_quantize_fp8
    gu_packed, gu_scale = quant(gu_t)
    dn_packed, dn_scale = quant(dn_t)
    return {"gate_up_packed": gu_packed, "gate_up_scale": gu_scale,
            "down_packed": dn_packed, "down_scale": dn_scale}
