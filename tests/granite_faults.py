"""Faults put into the Granite-4.0-H program with routed experts, each of
which the comparison with ``benchmarks/reference/granite_moe_hybrid_f32.py``
must not pass: context managers that patch the package for as long as
they are open (``tests/test_granite_moe_hybrid.py`` at toy widths on the
CPU; a builder's chip probe at the cell's widths around
``serve.probe_logits``, ``PERF.md`` section 4)."""

from unittest import mock

import jax.numpy as jnp

from neuronx_distributed_tpu.models import granite_hybrid as gh
from neuronx_distributed_tpu.modules.moe import model as moe_model
from neuronx_distributed_tpu.modules.moe.routing import RouterTopK
from neuronx_distributed_tpu.ops import ssd


def _router(cls):
    return mock.patch.dict(moe_model.ROUTERS, {"top_k": cls})


def stale_state(rows: int):
    """A step whose first segment has ``rows`` rows leaves every mamba
    layer's states as it found them: the slots go on a step behind."""
    sound = ssd.ssd_packed

    def packed(x, dt, a, b, c, d, ssm, layer, seg, **kw):
        y, new = sound(x, dt, a, b, c, d, ssm, layer, seg, **kw)
        return y, jnp.where(seg.rows[0] == rows, ssm, new)

    return mock.patch.object(ssd, "ssd_packed", packed)


def tail_dropped():
    """The convolution's tails are never written: a chunk's first rows
    and every decode row convolve with zeros."""
    sound = ssd.causal_conv_step
    return mock.patch.object(ssd, "causal_conv_step", lambda x, tails, *a: (
        sound(x, tails, *a)[0], tails))


def shared_mlp_left_out():
    sound = moe_model.SharedExperts.__call__
    return mock.patch.object(moe_model.SharedExperts, "__call__", lambda self, x: (
        0 * sound(self, x)))


def gates_over_all_experts():
    """The chosen experts weighed by the softmax over every expert,
    without renormalising over the chosen."""
    class Router(RouterTopK):
        norm_topk: bool = False

    return _router(Router)


def nine_experts_a_row():
    """The last of a row's choices weighs nothing, and the others are a
    softmax over themselves."""
    class Router(RouterTopK):
        def __call__(self, x):
            gates, idx, aux = super().__call__(x)
            kept = gates.at[:, -1].set(0.0)
            return kept / jnp.sum(kept, -1, keepdims=True), idx, aux

    return _router(Router)


def other_half_expert_counted(first_elsewhere: int):
    """A choice of expert ``first_elsewhere`` (the first one past those
    held here) is served by the last expert held."""
    class Router(RouterTopK):
        def __call__(self, x):
            gates, idx, aux = super().__call__(x)
            return gates, jnp.where(idx == first_elsewhere,
                                    first_elsewhere - 1, idx), aux

    return _router(Router)


def residual_multiplier_off_the_feed_forward():
    sound = gh.GraniteHybridConfig.feed_forward

    def feed_forward(self, h, tp_sync=True, valid=None):
        out, aux = sound(self, h, tp_sync, valid)
        return out / self.residual_scale, aux

    return mock.patch.object(gh.GraniteHybridConfig, "feed_forward",
                             feed_forward)


def faults(held_end: int, stale_rows: int) -> dict:
    """Name -> a fresh context manager, for the faults the cell's check
    is held to: ``held_end`` the first expert past those held here,
    ``stale_rows`` the rows of the chunk after which the state is left
    stale."""
    return {
        "a state left stale for a step": lambda: stale_state(stale_rows),
        "the convolution's tail dropped": tail_dropped,
        "the shared MLP left out": shared_mlp_left_out,
        "the gates a softmax over all experts": gates_over_all_experts,
        "nine experts a row": nine_experts_a_row,
        "an expert of the other half counted":
            lambda: other_half_expert_counted(held_end),
        "the residual multiplier left off the feed-forward":
            residual_multiplier_off_the_feed_forward}
