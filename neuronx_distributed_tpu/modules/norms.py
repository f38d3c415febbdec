"""Sequence-parallel-aware normalisation layers.

Analogue of the reference's ``parallel_layers/layer_norm.py:17`` and
``modules/rms_norm.py:36``. In the explicit shard_map path, when activations
are sequence-sharded across tp, the (replicated) norm weights receive a
different gradient on each tp shard; the reference marks such weights
``sequence_parallel_enabled`` and all-reduces their grads later
(``grads.py:330``). Here the same effect is local and composable: the weight
passes through ``copy_to_tensor_parallel_region`` (identity fwd, psum bwd),
so the summed gradient appears directly in autodiff — no deferred pass.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..parallel import mappings
from ..parallel import mesh as ps


class RMSNorm(nn.Module):
    """RMSNorm in fp32 accumulation (llama-style)."""

    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    sequence_parallel: bool = False
    axis: str = ps.TP_AXIS

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.with_partitioning(
            nn.initializers.ones_init(), (None,)), (x.shape[-1],),
            self.param_dtype)
        if self.sequence_parallel:
            scale = mappings.copy_to_tensor_parallel_region(scale, self.axis)
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.eps)
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


class GroupRMSNorm(nn.Module):
    """:class:`RMSNorm` whose mean square is taken over each of ``groups``
    runs of ``C / groups`` consecutive channels and not over all ``C``;
    one weight a channel, ``scale [C]`` (Mamba-2's gated norm with
    ``n_groups`` > 1)."""

    groups: int
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.with_partitioning(
            nn.initializers.ones_init(), (None,)), (x.shape[-1],),
            self.param_dtype)
        xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (self.groups, -1))
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = (xf * jax.lax.rsqrt(var + self.eps)).reshape(x.shape)
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


class LayerNorm(nn.Module):
    """LayerNorm with optional SP-aware weight grads (reference
    ``layer_norm.py:17``)."""

    eps: float = 1e-5
    use_bias: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    sequence_parallel: bool = False
    axis: str = ps.TP_AXIS

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h = x.shape[-1]
        scale = self.param("scale", nn.with_partitioning(
            nn.initializers.ones_init(), (None,)), (h,), self.param_dtype)
        bias = None
        if self.use_bias:
            bias = self.param("bias", nn.with_partitioning(
                nn.initializers.zeros_init(), (None,)), (h,), self.param_dtype)
        if self.sequence_parallel:
            scale = mappings.copy_to_tensor_parallel_region(scale, self.axis)
            if bias is not None:
                bias = mappings.copy_to_tensor_parallel_region(bias, self.axis)
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * scale.astype(jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return y.astype(self.dtype)
