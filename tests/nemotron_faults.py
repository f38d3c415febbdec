"""Faults put into the Nemotron-H program, each of which the comparison
with ``benchmarks/reference/nemotron_h_f32.py`` must not pass: context
managers that patch the package for as long as they are open
(``tests/test_nemotron_h.py`` at toy widths on the CPU; a builder's chip
probe at the cell's widths around ``serve.probe_logits``, the
configuration file's ``logit_check.why``). The scan's state and tail and
the shared expert are Granite's faults (``tests/granite_faults.py``)."""

from unittest import mock

import jax.numpy as jnp
from flax import linen as nn

import granite_faults
from neuronx_distributed_tpu.models import granite_hybrid as gh
from neuronx_distributed_tpu.modules import norms
from neuronx_distributed_tpu.modules.moe import expert_mlps
from neuronx_distributed_tpu.modules.moe import model as moe_model
from neuronx_distributed_tpu.modules.moe.routing import RouterSigmoid
from neuronx_distributed_tpu.ops import ssd


def _router(cls):
    return mock.patch.dict(moe_model.ROUTERS, {"sigmoid": cls})


def _param(cls, name, made):
    """``cls``'s parameter ``name`` read as ``made(it)``."""
    sound = cls.param

    def param(self, leaf, *a, **kw):
        w = sound(self, leaf, *a, **kw)
        return made(w) if leaf == name else w

    return mock.patch.object(cls, "param", param)


def neighbouring_groups_b_and_c():
    """Every head reads the ``B`` and ``C`` of the group before its own."""
    sound = ssd.ssd_packed

    def packed(x, dt, a, b, c, *rest, **kw):
        return sound(x, dt, a, jnp.roll(b, 1, axis=-2),
                     jnp.roll(c, 1, axis=-2), *rest, **kw)

    return mock.patch.object(ssd, "ssd_packed", packed)


def gated_norm_over_all_channels():
    """The gated norm's mean square over all of ``d_inner`` and not over
    each group's channels."""
    return mock.patch.object(
        gh, "GroupRMSNorm",
        lambda groups, **kw: norms.GroupRMSNorm(groups=1, **kw))


def latent_in_skipped():
    """The experts read the row's first ``latent`` dimensions and not
    ``latent_in``'s product."""
    return _param(moe_model.MoE, "latent_in",
                  lambda w: jnp.eye(*w.shape, dtype=w.dtype))


def _act(fn):
    both = [mock.patch.object(m, "relu2", fn)
            for m in (expert_mlps, moe_model)]

    class Both:
        def __enter__(self):
            for p in both:
                p.start()

        def __exit__(self, *exc):
            for p in both:
                p.stop()

    return Both()


def relu_for_its_square():
    return _act(nn.relu)


def a_gated_expert():
    """``silu(u) * u`` where an ungated expert has ``relu(u)^2``."""
    return _act(lambda u: nn.silu(u) * u)


def scaling_factor_left_out():
    class Router(RouterSigmoid):
        def __call__(self, x):
            gates, idx, aux = super().__call__(x)
            return gates / self.scale, idx, aux

    return _router(Router)


def one_expert_fewer_a_row():
    """The last of a row's choices weighs nothing, and the others are
    normalised over themselves."""
    class Router(RouterSigmoid):
        def __call__(self, x):
            gates, idx, aux = super().__call__(x)
            kept = gates.at[:, -1].set(0.0)
            return (kept * jnp.sum(gates, -1, keepdims=True)
                    / jnp.sum(kept, -1, keepdims=True)), idx, aux

    return _router(Router)


def another_quarters_expert_counted(first_elsewhere: int):
    """A choice of expert ``first_elsewhere`` (the first one past those
    held here) is served by the last expert held."""
    class Router(RouterSigmoid):
        def __call__(self, x):
            gates, idx, aux = super().__call__(x)
            return gates, jnp.where(idx == first_elsewhere,
                                    first_elsewhere - 1, idx), aux

    return _router(Router)


def selection_without_the_bias():
    """The experts chosen by ``s`` alone."""
    return _param(RouterSigmoid, "bias", jnp.zeros_like)


def faults(held_end: int, stale_rows: int) -> dict:
    """Name -> a fresh context manager, for the faults the cell's check
    is held to: ``held_end`` the first expert past those held here,
    ``stale_rows`` the rows of the chunk after which the state is left
    stale."""
    return {
        "a state left stale for a step":
            lambda: granite_faults.stale_state(stale_rows),
        "the convolution's tail dropped": granite_faults.tail_dropped,
        "the shared expert left out": granite_faults.shared_mlp_left_out,
        "B and C of the neighbouring group": neighbouring_groups_b_and_c,
        "the gated norm over all channels": gated_norm_over_all_channels,
        "latent_in skipped": latent_in_skipped,
        "relu for its square": relu_for_its_square,
        "a gated expert": a_gated_expert,
        "the scaling factor left out": scaling_factor_left_out,
        "one expert fewer a row": one_expert_fewer_a_row,
        "an expert of another quarter counted":
            lambda: another_quarters_expert_counted(held_end),
        "the selection made without the bias": selection_without_the_bias}
