"""Faults put into the DeepSeek-V3.2 program, each of which the comparison
with ``benchmarks/reference/deepseek_v32_f32.py`` must not pass: context
managers that patch the package for as long as they are open
(``tests/test_deepseek_v32.py`` at toy widths on the CPU; a builder's chip
probe at the cell's widths: the configuration file's ``logit_check.why``
has its readings)."""

import contextlib
import functools
import math

import jax.numpy as jnp

from neuronx_distributed_tpu.models import deepseek_v32 as ds
from neuronx_distributed_tpu.models.glm_moe_lite import (LatentAttention,
                                                         LatentGeometry)
from neuronx_distributed_tpu.modules import attention as attn_mod
from neuronx_distributed_tpu.modules.moe.routing import RouterSigmoid
from neuronx_distributed_tpu.ops import indexed_attention as ia


@contextlib.contextmanager
def _patched(*patches):
    sound = [(owner, name, getattr(owner, name))
             for owner, name, _ in patches]
    for owner, name, value in patches:
        setattr(owner, name, value)
    try:
        yield
    finally:
        for owner, name, value in sound:
            setattr(owner, name, value)


def _index_rows(change):
    """The indexer's rows as ``change(module, q, k, w, cos, sin)`` leaves
    them."""
    sound = ds.IndexedLatentAttention.index_rows

    def index_rows(self, x, c_q, cos, sin):
        return change(self, *sound(self, x, c_q, cos, sin), cos, sin)

    return _patched((ds.IndexedLatentAttention, "index_rows", index_rows))


def selection_dropped():
    """Every causal position attended: the latent family's attention over
    the table, the indexer unused."""
    return _patched(
        (ds.IndexedLatentAttention, "attend", LatentAttention.attend),
        (ds.DeepseekV32Config, "step_walk", LatentGeometry.step_walk))


def relu_left_out():
    """``I[t, s] = sum_h w_h (q_I,h . k_I[s])``."""
    return _patched((ia, "positive", lambda s: s))


def weights_left_out():
    """The heads' weights ``w`` at ``Hi^-1/2``, whatever the row."""
    return _index_rows(lambda self, q, k, w, cos, sin: (
        q, k, jnp.full_like(w, 1.0 / math.sqrt(w.shape[-1]))))


def key_without_rotary():
    """``k_I`` as the LayerNorm leaves it (rotated back)."""
    def change(self, q, k, w, cos, sin):
        rope = self.cfg.qk_rope_head_dim
        back = attn_mod.apply_rotary(k[:, :, None, :rope], cos, -sin)
        return q, jnp.concatenate([back[:, :, 0], k[..., rope:]], -1), w

    return _index_rows(change)


def key_one_position_late():
    """A step's index keys written a row on: position ``s`` holds the
    key of ``s - 1``."""
    return _index_rows(lambda self, q, k, w, cos, sin: (
        q, jnp.roll(k, 1, axis=1), w))


def plain_top_k_without_groups():
    """The ``num_experts_per_tok`` largest biased scores of all the
    experts."""
    return _patched((RouterSigmoid, "limit_to_groups",
                     lambda self, biased: biased))


def gates_without_scaling_factor():
    """The chosen experts' weights without ``routed_scaling_factor``."""
    sound = RouterSigmoid.__call__

    def unscaled(self, x):
        gates, idx, aux = sound(self, x)
        return gates / self.scale, idx, aux

    return _patched((RouterSigmoid, "__call__", unscaled))


def scale_without_mscale():
    """The scores times ``(nope + rope)^-1/2`` alone."""
    return _patched((ds.DeepseekV32Config, "score_scale",
                     LatentGeometry.score_scale))


def key_norm_without_bias():
    """``k_I`` from a LayerNorm with a weight and no bias."""
    return _patched((ds, "LayerNorm",
                     functools.partial(ds.LayerNorm, use_bias=False)))


#: name -> a fresh context manager
FAULTS = {"selection_dropped": selection_dropped,
          "relu_left_out": relu_left_out,
          "weights_left_out": weights_left_out,
          "key_without_rotary": key_without_rotary,
          "key_one_position_late": key_one_position_late,
          "plain_top_k_without_groups": plain_top_k_without_groups,
          "gates_without_scaling_factor": gates_without_scaling_factor,
          "scale_without_mscale": scale_without_mscale,
          "key_norm_without_bias": key_norm_without_bias}
