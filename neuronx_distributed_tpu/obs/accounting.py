"""Automatic accounting: compile tracking and wire-byte counters.

**Compile tracking.** JAX recompiles silently — a drifting shape in the
serving schedule or a weakly-typed scalar in the train step turns one
compile into one per step, and nothing in the program output changes
except wall-clock. ``CompileTracker`` polls a compiled callable's cache
size (``fn._cache_size()``, the same hook ``ServingEngine.compile_count``
uses) after calls, counts compiles, attributes the call's wall time to
compilation when the count grew, and on any compile *beyond the first*
raises an alert through the shared event channel — the same channel the
resilience watchdog emits on, so recompile storms surface next to stall
and loss-spike events.

**Wire bytes.** The compressed collectives (``parallel/comm_compressed``,
``ops/collective_matmul``) call ``record_wire`` from their *public
wrappers* — host code that runs at trace time, never inside the compiled
program (no host callbacks in traced code). Byte counts are therefore
**traced-bytes**: under ``jax.jit`` a collective is accounted once per
compile, not once per execution. The compressed/raw *ratio* — the number
EQuARX-style compression claims live or die on — is invariant to how many
times the program runs, so ratios from these counters match the codec's
``wire_bytes_per_element`` arithmetic regardless of step count.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

from .events import emit_event
from .metrics import MetricsRegistry, get_registry

# ---------------------------------------------------------------------------
# wire-byte accounting
# ---------------------------------------------------------------------------


def record_wire_bytes(kind: str, dtype: str, wire_bytes: float,
                      raw_bytes: float,
                      registry: Optional[MetricsRegistry] = None) -> None:
    """Account one logical collective: bytes actually shipped vs fp32.

    ``kind`` names the collective site (e.g. ``grad_all_reduce``,
    ``act_all_gather_matmul``); ``dtype`` is the wire dtype label.
    Callers compute the byte figures with ``wire_codec`` arithmetic so
    the counters and the codec can never disagree by construction drift.
    """
    reg = registry if registry is not None else get_registry()
    if not reg.enabled:
        return
    labels = ("collective", "dtype")
    reg.counter("nxd_wire_bytes_total",
                "Bytes shipped on the wire per collective kind "
                "(traced-bytes: counted once per trace, not per run).",
                labels=labels).labels(
                    collective=kind, dtype=dtype).inc(wire_bytes)
    reg.counter("nxd_wire_raw_bytes_total",
                "fp32-equivalent bytes for the same collectives.",
                labels=labels).labels(
                    collective=kind, dtype=dtype).inc(raw_bytes)
    reg.counter("nxd_wire_collectives_total",
                "Logical collective calls accounted.",
                labels=labels).labels(collective=kind, dtype=dtype).inc()


def record_collective_time(tier: str, nbytes: float, seconds: float,
                           registry: Optional[MetricsRegistry] = None
                           ) -> None:
    """Account one *timed* collective: (payload bytes, wall seconds).

    Unlike :func:`record_wire_bytes` (traced-bytes, counted at trace
    time), this records measured host wall time around an executed
    collective — the (bytes, time) pairs ``plan/calibrate.py`` fits α-β
    link constants from. ``tier`` is the link tier label ("ici"/"dcn");
    the payload size rides as a label so the calibrator recovers
    distinct sizes from a plain registry snapshot.
    """
    reg = registry if registry is not None else get_registry()
    if not reg.enabled:
        return
    reg.histogram("nxd_collective_seconds",
                  "Measured wall time of executed collectives, labeled "
                  "by link tier and payload bytes (calibration source).",
                  labels=("tier", "nbytes")).labels(
                      tier=tier, nbytes=str(int(nbytes))).observe(seconds)


def collective_samples(registry: Optional[MetricsRegistry] = None
                       ) -> Dict[str, list]:
    """Calibration view: ``{tier: [(nbytes, mean_seconds, count), ...]}``
    recovered from the ``nxd_collective_seconds`` histogram family."""
    reg = registry if registry is not None else get_registry()
    metric = reg.get("nxd_collective_seconds")
    out: Dict[str, list] = {}
    if metric is None:
        return out
    for child in metric.children():
        if child.count == 0:
            continue
        tier = child.labels.get("tier", "ici")
        try:
            nbytes = float(child.labels.get("nbytes", "0"))
        except ValueError:
            continue
        out.setdefault(tier, []).append(
            (nbytes, child.sum / child.count, child.count))
    for pairs in out.values():
        pairs.sort()
    return out


def wire_totals(registry: Optional[MetricsRegistry] = None
                ) -> Tuple[float, float]:
    """(wire_bytes, raw_bytes) summed over all collective kinds."""
    reg = registry if registry is not None else get_registry()
    wire = reg.get("nxd_wire_bytes_total")
    raw = reg.get("nxd_wire_raw_bytes_total")
    w = sum(c.value for c in wire.children()) if wire is not None else 0.0
    r = sum(c.value for c in raw.children()) if raw is not None else 0.0
    return w, r


def wire_compression_ratio(registry: Optional[MetricsRegistry] = None
                           ) -> float:
    """raw/wire over everything accounted so far (1.0 when empty)."""
    w, r = wire_totals(registry)
    return (r / w) if w > 0 else 1.0


# ---------------------------------------------------------------------------
# compile tracking
# ---------------------------------------------------------------------------


def cache_size(fn: Any) -> Optional[int]:
    """Best-effort compile-cache size of a jitted callable (None if the
    hook isn't there — e.g. a plain python function)."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:
        return None


class CompileTracker:
    """Tracks compile count for one site (a jitted function or worker).

    ``poll(wall_s=...)`` compares the current cache size against the last
    observation; growth means the preceding call compiled. The first
    compile per site is expected and merely counted; any further compile
    is a *recompile* — counted separately and alerted on through the
    event channel (``recompile_detected``), watchdog-style.
    """

    def __init__(self, site: str, cache_size_fn: Callable[[], Optional[int]],
                 registry: Optional[MetricsRegistry] = None,
                 alert: bool = True):
        self.site = site
        self._cache_size_fn = cache_size_fn
        self._registry = registry
        self._alert = alert
        self._last = 0

    @classmethod
    def for_function(cls, site: str, fn: Any, **kw: Any) -> "CompileTracker":
        return cls(site, lambda: cache_size(fn), **kw)

    @property
    def compiles(self) -> int:
        """Compiles this tracker has seen at its polls so far."""
        return self._last

    @property
    def _reg(self) -> MetricsRegistry:
        return (self._registry if self._registry is not None
                else get_registry())

    def poll(self, wall_s: Optional[float] = None) -> int:
        """Observe the cache size; record any compiles since last poll.

        Returns the current cache size (0 if unobservable). ``wall_s``,
        when given, is the wall time of the call that just finished and
        is attributed to compilation if the count grew.
        """
        n = self._cache_size_fn()
        if n is None:
            return 0
        grew = n - self._last
        if grew <= 0:
            return n
        self._last = n
        reg = self._reg
        if reg.enabled:
            reg.counter("nxd_compile_total",
                        "Compiles observed per site.",
                        labels=("site",)).labels(site=self.site).inc(grew)
            if wall_s is not None:
                reg.histogram("nxd_compile_wall_seconds",
                              "Wall time of calls that triggered a "
                              "compile.",
                              labels=("site",)).labels(
                                  site=self.site).observe(wall_s)
        if n > 1:
            recompiles = grew if self._last - grew >= 1 else n - 1
            if reg.enabled:
                reg.counter("nxd_recompile_total",
                            "Compiles beyond the first per site "
                            "(each one is a performance bug).",
                            labels=("site",)).labels(
                                site=self.site).inc(recompiles)
            if self._alert:
                emit_event("recompile_detected", site=self.site,
                           cache_size=n, new_compiles=grew,
                           wall_s=wall_s)
        return n

    def wrap(self, fn: Callable[..., Any]) -> Callable[..., Any]:
        """Wrap a compiled callable: time each call and poll afterwards."""

        def _wrapped(*args: Any, **kw: Any) -> Any:
            t0 = time.perf_counter()
            out = fn(*args, **kw)
            self.poll(wall_s=time.perf_counter() - t0)
            return out

        _wrapped.__name__ = getattr(fn, "__name__", "compiled")
        return _wrapped


def compile_events(registry: Optional[MetricsRegistry] = None) -> float:
    """Total compiles accounted across all sites."""
    reg = registry if registry is not None else get_registry()
    m = reg.get("nxd_compile_total")
    return sum(c.value for c in m.children()) if m is not None else 0.0
