"""Faults put into the SDAR program, each of which the comparison with
``benchmarks/reference/sdar_moe_f32.py`` must not pass: context managers
that patch the package for as long as they are open
(``tests/test_sdar.py`` at toy widths on the CPU; a builder's chip probe
at the cell's widths: the configuration file's ``logit_check.why`` has its
readings). A step traced under a fault must be traced anew."""

import contextlib

import jax
import jax.numpy as jnp

from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.inference.sampling import BlockDecoding
from neuronx_distributed_tpu.modules.moe import routing


@contextlib.contextmanager
def _patched(owner, name, value):
    sound = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, sound)


def own_query_position():
    """A row attends through its own position: a causal mask inside the
    block."""
    return _patched(BlockDecoding, "through",
                    lambda self, positions: positions)


def _router(choose):
    """``RouterTopK.__call__`` with ``choose(self, x, gates, idx)`` behind
    it, wrapped as flax wraps a module's compact method."""
    from flax import linen as nn
    from flax.linen.module import wrap_method_once

    sound = routing.RouterTopK.__call__

    def call(self, x):
        gates, idx, aux = sound(self, x)
        return (*choose(self, x, gates, idx), aux)

    call.__name__ = call.__qualname__ = "__call__"
    return _patched(routing.RouterTopK, "__call__",
                    wrap_method_once(nn.compact(call)))


def top_k_less_one():
    """The last of a row's chosen experts is left out, the others'
    weights renormalised (top-7 of 8)."""
    def choose(self, x, gates, idx):
        gates = gates.at[:, -1].set(0.0)
        return gates / jnp.sum(gates, axis=-1, keepdims=True), idx

    return _router(choose)


def gates_not_renormalised():
    """The chosen experts weigh their softmax probabilities over all the
    experts, not over the chosen."""
    def choose(self, x, gates, idx):
        # the renormalised gates times the chosen probabilities' sum
        top = jax.lax.top_k(jax.nn.softmax(self.logits(x), axis=-1),
                            gates.shape[-1])[0]
        return gates * jnp.sum(top, axis=-1, keepdims=True), idx

    return _router(choose)


def no_qk_norm():
    """q and k go to rotary as the projections give them."""
    from neuronx_distributed_tpu.models import llama

    class Identity:
        def __init__(self, **kw):
            pass

        def __call__(self, x):
            return x

    sound = llama.RMSNorm

    def norm(*a, name=None, **kw):
        return Identity() if name in ("q_norm", "k_norm") else sound(
            *a, name=name, **kw)

    return _patched(llama, "RMSNorm", norm)


def first_writing_left_live():
    """A row whose pool slot already holds its position is not written
    again: the first writing of a block stays what later passes and later
    blocks see, as in a cache that takes a written position for done (or
    appends where it should overwrite). The step's stored positions say
    which rows those are (``write_pool_positions`` sees the pool's
    positions before the step); the layers' writes then drop them."""
    rows_of, positions_of = paging.write_pool_rows, \
        paging.write_pool_positions
    written = []

    def positions(pos, step_positions, write_idx):
        capacity = pos.size
        held = pos.reshape(-1)[jnp.minimum(write_idx, capacity - 1)]
        written.append((write_idx < capacity) & (held == step_positions))
        return positions_of(pos, step_positions, write_idx)

    def rows(pool, new, write_idx, layer):
        capacity = pool.shape[1] * pool.shape[2]
        return rows_of(pool, new, jnp.where(written[-1], capacity,
                                            write_idx), layer)

    @contextlib.contextmanager
    def both():
        with _patched(paging, "write_pool_positions", positions), \
                _patched(paging, "write_pool_rows", rows):
            yield

    return both()


#: name -> a fresh context manager
FAULTS = {f.__name__: f for f in (
    own_query_position, top_k_less_one, gates_not_renormalised, no_qk_norm,
    first_writing_left_live)}
