"""Shared Mosaic/Pallas configuration for the TPU kernels."""

from __future__ import annotations


def compiler_params():
    """Mosaic params for the compiled TPU path. The default 16 MiB scoped
    VMEM limit rejects 7B-scale tiles (fp32 staging of an expert's gate
    and up tiles, (h, block_i) each, is already ~8 MiB); v5e has 128 MiB
    physical VMEM."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)
