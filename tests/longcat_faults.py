"""Faults put into the LongCat-Flash program, each of which the
comparison with ``benchmarks/reference/longcat_flash_f32.py`` must not
pass: context managers that patch the package for as long as they are
open (``tests/test_longcat_flash.py`` at toy widths on the CPU; a
builder's chip probe at the cell's widths, ``PERF.md`` section 4)."""

import contextlib

import jax.numpy as jnp
from flax import linen as nn

from neuronx_distributed_tpu.models import longcat_flash as lc
from neuronx_distributed_tpu.models.glm_moe_lite import LatentAttention
from neuronx_distributed_tpu.models.llama import LlamaMLP
from neuronx_distributed_tpu.modules.moe import model as moe_model
from neuronx_distributed_tpu.modules.moe.routing import RouterSoftmaxBias
from neuronx_distributed_tpu.modules.norms import RMSNorm


@contextlib.contextmanager
def _router(cls):
    sound = moe_model.ROUTERS["softmax_bias"]
    moe_model.ROUTERS["softmax_bias"] = cls
    try:
        yield
    finally:
        moe_model.ROUTERS["softmax_bias"] = sound


def identity_left_out(num_experts: int):
    """The identity experts' choices weigh nothing: their term is gone,
    the routed experts' and the counts are as they were."""
    class Router(RouterSoftmaxBias):
        def __call__(self, x):
            gates, idx, aux = super().__call__(x)
            return jnp.where(idx >= num_experts, 0.0, gates), idx, aux

    return _router(Router)


def weights_renormalised():
    """The chosen slots' weights sum to ``scale``, as ``RouterSigmoid``'s
    and a ``norm_topk_prob`` checkpoint's do."""
    class Router(RouterSoftmaxBias):
        def __call__(self, x):
            gates, idx, aux = super().__call__(x)
            return (gates / jnp.sum(gates, axis=-1, keepdims=True)
                    * self.scale), idx, aux

    return _router(Router)


def bf16_router():
    """The router's logits, softmax and weights in bfloat16."""
    class Router(RouterSoftmaxBias):
        def logits(self, x):
            sound = super().logits(x.astype(jnp.bfloat16))
            return sound.astype(jnp.bfloat16).astype(jnp.float32)

        def __call__(self, x):
            gates, idx, aux = super().__call__(x)
            return gates.astype(jnp.bfloat16).astype(jnp.float32), idx, aux

    return _router(Router)


class _BankAfterFirstFeedForward(lc.LongcatFlashDecoderLayer):
    """The double layer with the bank's output added where it was
    computed, after ``FFN_0``, and not at the layer's end: the second
    attention and feed-forward then read it."""

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, cache=None,
                 cache_index=None, valid=None):
        cfg = self.cfg

        def norm(name, h):
            return RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name=name)(h)

        def attend(which, h, view):
            out = LatentAttention(cfg, name=f"attn_{which}")(
                norm(f"input_norm_{which}", h), cos, sin, positions,
                cache=view)
            if view is not None:
                out, view = out
            return h + out, view

        a, view = attend(0, x, cache)
        h = norm("post_norm_0", a)
        m, assignments = cfg.expert_bank(h, valid)
        b = a + LlamaMLP(cfg, name="mlp_0")(h) + m          # the fault
        c, view = attend(1, b, view and view.next_attention())
        d = c + LlamaMLP(cfg, name="mlp_1")(norm("post_norm_1", c))
        return d, assignments, view


@contextlib.contextmanager
def bank_added_after_first_feed_forward():
    sound = lc.LongcatFlashDecoderLayer
    lc.LongcatFlashDecoderLayer = _BankAfterFirstFeedForward
    try:
        yield
    finally:
        lc.LongcatFlashDecoderLayer = sound


def faults(num_experts: int) -> dict:
    """Name -> a fresh context manager, for the three faults the cell's
    check is held to and the router's precision."""
    return {"identity_left_out": lambda: identity_left_out(num_experts),
            "weights_renormalised": weights_renormalised,
            "bank_added_after_first_feed_forward":
                bank_added_after_first_feed_forward,
            "bf16_router": bf16_router}
