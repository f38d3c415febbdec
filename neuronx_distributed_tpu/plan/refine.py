"""Measured refinement of the analytic ranking.

The cost model ranks thousands of candidates in milliseconds but its
absolute times are only as good as the :class:`~.cost.HardwareSpec`
constants. ``--refine`` keeps the model for pruning and re-ranks just the
top-k survivors with a *measured* proxy: a tiny jitted program per plan
whose operation mix mirrors the plan's cost terms (a dense matmul scaled
to the per-device FLOPs, plus ``psum``/``all_gather`` traffic scaled to
the per-axis collective volumes), timed after compilation.

The proxy runs on whatever backend is available — on CPU it measures the
8-way virtual mesh, which is enough to catch gross model errors (e.g. a
plan whose collectives dominate in practice) while staying test-safe.

Determinism: the measurement callable is injectable (tests substitute a
closed-form stub), proxy inputs come from a fixed seed, repeated timing
takes the **minimum** of ``repeats`` runs (robust to scheduler noise),
and ties re-break on the analytic cost then the plan tuple — so two runs
with the same seed produce the same ranking (asserted in
tests/test_plan.py).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

from .cost import HardwareSpec, ModelSpec, Plan
from .search import RankedPlan

Measure = Callable[[Plan, ModelSpec], float]


@dataclass(frozen=True)
class RefinedPlan:
    plan: Plan
    modeled_s: float
    measured_s: float


def proxy_measure(plan: Plan, m: ModelSpec, *, seed: int = 0,
                  repeats: int = 3, scale: float = 1e-3) -> float:
    """Time a shape-scaled proxy of one step of ``plan``.

    The proxy shrinks the real workload by ``scale`` in the token
    dimension (keeping hidden sizes) so a measurement finishes in
    milliseconds, and charges each modeled term with a same-shaped
    operation: local matmuls for compute, ``jax.lax.psum`` over a
    collapsed axis for gradient reduction, ``all_gather`` for the TP
    activation traffic. Uses the devices that exist — plans wider than
    the runtime fold extra ranks into the per-device workload.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    n_dev = len(jax.devices())
    axis = min(plan.tp * plan.dp, n_dev) or 1
    mesh = Mesh(jax.devices()[:axis], ("dp",))

    tokens = max(8, int(m.tokens_per_step * scale / max(1, plan.dp)))
    tokens -= tokens % axis or 0
    tokens = max(tokens, axis)
    hidden = m.hidden
    # per-device matmul work ~ compute term; comm arrays ~ grad volume
    key = jax.random.PRNGKey(seed)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (tokens, hidden), jnp.float32)
    w = jax.random.normal(kw, (hidden, hidden), jnp.float32)
    reps = 1 + plan.num_microbatches

    @jax.jit
    def step(x, w):
        def body(x, w):
            y = x
            for _ in range(reps):
                y = y @ w
                if plan.tp > 1:
                    y = jax.lax.psum(y, "dp") / axis
            if plan.dp > 1:
                g = jax.lax.psum(jnp.sum(y) * w, "dp")
                y = y + jnp.sum(g) * 0
            return y

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P("dp", None), P(None, None)),
                             out_specs=P("dp", None))(x, w)

    out = step(x, w)
    out.block_until_ready()   # compile outside the timed region
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        step(x, w).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def refine(ranked: Sequence[RankedPlan], m: ModelSpec, hw: HardwareSpec, *,
           top_k: int = 3, seed: int = 0,
           measure: Optional[Measure] = None) -> List[RefinedPlan]:
    """Re-rank the ``top_k`` analytically-best plans by measured proxy
    time. ``measure`` defaults to :func:`proxy_measure`; tests inject a
    deterministic stub. Sort is (measured, modeled, plan tuple) so equal
    measurements fall back to the analytic order deterministically."""
    if measure is None:
        measure = lambda p, s: proxy_measure(p, s, seed=seed)  # noqa: E731
    out = [RefinedPlan(r.plan, r.total_s, measure(r.plan, m))
           for r in list(ranked)[:top_k]]
    out.sort(key=lambda r: (r.measured_s, r.modeled_s, _key(r.plan)))
    return out


def _key(p: Plan) -> tuple:
    return (p.tp, p.pp, p.dp, p.ep, p.num_microbatches,
            p.grad_comm_dtype, p.grad_comm_hierarchical, p.tp_overlap)
