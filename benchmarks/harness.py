"""What every runner, reader and test of the benchmark shares: the manifest
and the files it names, the device check, the compile count, weights from
a seed, and the statistics the metrics use.

Nothing here imports the package under test.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """The run cannot give a result; the process exits non-zero."""


def say(tag: str, **fields) -> None:
    """One of the earlier lines of standard output (never the last)."""
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


#: every number the run's check compared, beside its limit, by a short
#: plain name and in the order compared: ``{name: [number, limit]}``.
#: ``run.py`` prints it last, on standard error and in the result's line.
COMPARED: Dict[str, List[float]] = {}


def compared(name: str, number: float, limit: float) -> None:
    """Keep one number of the check of ``correct`` beside its limit."""
    COMPARED[name] = [float(number), float(limit)]


def read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: str = MANIFEST) -> dict:
    if not os.path.exists(path):
        raise BenchError(f"no manifest at {path}")
    return read_json(path)


def by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r}; there are "
                     f"{[e['name'] for e in entries]}")


def data_file(kind: str, name: str) -> str:
    """``benchmarks/<kind>/<name>.json`` — how a traffic mix or a per-layer
    metric is found from its name alone (the tests' rehearsal mixes sit
    under ``benchmarks/tests/<kind>/``)."""
    if not NAME.match(name):
        raise BenchError(f"bad {kind} name {name!r}")
    for base in (HERE, os.path.join(HERE, "tests")):
        path = os.path.join(base, kind, name + ".json")
        if os.path.exists(path):
            return path
    raise BenchError(f"no benchmarks/{kind}/{name}.json")


def load_plugin(package: str, kind: str):
    """``generators/<kind>.py``, ``readers/<kind>.py``, ``runners/<kind>.py``."""
    if not NAME.match(kind):
        raise BenchError(f"bad {package} kind {kind!r}")
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    try:
        return importlib.import_module(f"{package}.{kind}")
    except ModuleNotFoundError as e:
        if e.name in (package, f"{package}.{kind}"):
            raise BenchError(f"no {package}/{kind}.py") from e
        raise


def metrics_of(manifest: dict, group: str, workload: str) -> List[dict]:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports:
    those with no ``workloads`` key, and those that list the cell."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


# -- statistics ------------------------------------------------------------

def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of all the values (numpy's default)."""
    import numpy as np

    if len(values) == 0:
        raise BenchError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def stat(values, which: str) -> float:
    import numpy as np

    v = np.asarray(values, np.float64)
    if v.size == 0:
        raise BenchError(f"{which} of no values")
    if which == "mean":
        return float(v.mean())
    if which == "median":
        return float(np.median(v))
    if which == "sum":
        return float(v.sum())
    if which.startswith("p"):
        return percentile(v, float(which[1:]))
    raise BenchError(f"unknown statistic {which!r}")


# -- device ----------------------------------------------------------------

def peaks_for(device_kind: str) -> dict:
    table = read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"({sorted(table['devices'])}); a device that is not in the "
            "table is an error, not a default")
    return table["devices"][device_kind]


def require_device(chips: int, rehearsal: bool) -> dict:
    """A cell runs on a TPU with the chips it asks for and nothing else. A
    rehearsal configuration (under ``benchmarks/tests/configs``) takes the
    backend it finds and says so in every name it prints."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say("device", **info)
    if rehearsal:
        return info
    if info["platform"] != "tpu":
        raise BenchError(f"needs a TPU, JAX found platform "
                         f"{info['platform']!r}; no result on another backend")
    if info["count"] < chips:
        raise BenchError(f"the cell asks for {chips} chip(s), JAX reports "
                         f"{info['count']}")
    peaks_for(info["kind"])
    return info


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of the devices used."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def place_compile_cache() -> str:
    """The persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, otherwise the fixed path ``<checkout>/.jax_cache`` (the path is
    part of the cache's key). Every program is cached, however quick."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts the programs JAX compiles or loads (one
    ``backend_compile_duration`` event each, cache hit or miss)."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


# -- weights ---------------------------------------------------------------

def make_weights(shapes, seed: int, std: float, out_shardings=None):
    """The whole parameter tree on the device in one jitted call from the
    seed, each leaf made in the type it is served in: kernels and
    embeddings N(0, std) (the published ``initializer_range``), every norm
    ``scale`` 1. Fused random bits: the call needs no memory beyond its
    output."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, x) in enumerate(leaves):
            if jax.tree_util.keystr(path).endswith("['scale']"):
                out.append(jnp.ones(x.shape, x.dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((std * jax.random.normal(k, x.shape, x.dtype)
                            ).astype(x.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(build) if out_shardings is None else jax.jit(
        build, out_shardings=out_shardings)
    return fn(jax.random.key(seed % (2 ** 32)))


# -- what a runner hands back ---------------------------------------------

@dataclass
class Observations:
    """Everything the per-layer readers may read, gathered by the runner
    in a traced run. A reader that finds nothing to read returns None."""
    config: dict
    peaks: dict
    chips: int
    steps: int = 0                         # program steps inside the window
    window_s: float = 0.0                  # the measured window
    end_to_end: Dict[str, float] = field(default_factory=dict)
    series: Dict[str, list] = field(default_factory=dict)
    scalars: Dict[str, float] = field(default_factory=dict)
    span_self_s: Dict[str, float] = field(default_factory=dict)
    histograms: Dict[str, list] = field(default_factory=dict)
    trace: Any = None                      # tracereduce.xplane.Trace
    reduction: Any = None                  # tracereduce.xplane.Reduction
    traced_steps: int = 0                  # program steps inside the trace


@dataclass
class RunResult:
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]           # without setup_s
    setup_s: float
    devices: list
    memory_peak_bytes: int                 # when the window ended
    observations: Optional[Observations] = None
    why_incorrect: List[str] = field(default_factory=list)


def span_self_times(events: List[dict]) -> Dict[str, float]:
    """Self time per span name in seconds: a span's duration less what its
    child spans cover. ``events`` are the tracer's complete events
    (``name``, ``ts`` and ``dur`` in microseconds, one thread)."""
    evs = sorted(events, key=lambda e: (e["ts"], -e["dur"]))
    out: Dict[str, float] = {}
    stack: List[list] = []                 # [end, name, self_us]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, self_us = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_us, 0.0) * 1e-6

    for e in evs:
        close(e["ts"])
        if stack:
            stack[-1][2] -= e["dur"]
        stack.append([e["ts"] + e["dur"], e["name"], e["dur"]])
    close(float("inf"))
    return out


class Stopwatch:
    """Seconds since the process began (``run.py`` notes the time first)."""

    def __init__(self, t0: Optional[float] = None):
        self.t0 = time.perf_counter() if t0 is None else t0

    def __call__(self) -> float:
        return time.perf_counter() - self.t0
