"""From the profiler's ``.xplane.pb`` to device busy/idle, time per named
operation and the longest idle gaps with what the host was doing in them.

What the file looks like on a TPU v5e (``fixtures/matmul_loop.xplane.pb``):

* one plane ``/device:TPU:<n>`` per chip; its line ``XLA Ops`` holds one
  event per executed HLO operation (name = the HLO instruction's text,
  start and duration in nanoseconds), ``XLA Modules`` one event per
  program run with a ``run_id``, ``Async XLA Ops`` the start-to-done spans
  of asynchronous operations (copies, collectives);
* the plane ``/host:CPU``: one line per host thread; the benchmark's
  ``jax.profiler.TraceAnnotation`` spans (all named ``bench/...``) sit on
  the Python thread's line, whatever it is called, and the runtime's
  lines hold ``DoEnqueueProgram`` events with the same ``run_id``.
* a ``while`` (the scan over layers) is one operation on ``XLA Ops`` that
  spans its body's operations: such containers count for busy time and
  are left out of the time per operation.

The device's clock and the host's differ by a constant (1.3 ms in the
fixture). A program cannot start on the device before the host has
enqueued it, and starts at once on an idle device, so the offset is the
largest ``enqueue end - device start`` over the runs both sides name.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]            # seconds, [start, end)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
ANNOTATION_PREFIX = "bench/"
CONTAINERS = ("while", "conditional", "call")
ENQUEUE_EVENT = "DoEnqueueProgram"
# a pause shorter than this sits between two operations of one program
MIN_GAP_S = 20e-6


@dataclass
class Event:
    name: str
    start: float
    end: float
    run_id: Optional[int] = None


@dataclass
class DeviceTimeline:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    async_ops: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    """Events in seconds. Device times are already moved onto the host's
    clock by ``offset_s``."""
    devices: Dict[int, DeviceTimeline]
    annotations: List[Event]
    offset_s: float


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    head = hlo_text.split(" = ", 1)[0].strip()
    return head.lstrip("%") or hlo_text[:60]


def stable_name(hlo_text: str) -> str:
    """A name that survives recompilation: the instruction's name without
    its numeric suffix, and the type of its result
    (``%fusion.12 = bf16[8,128]{1,0} fusion(..)`` -> ``fusion bf16[8,128]``)."""
    name = re.sub(r"[.\d]+$", "", short_name(hlo_text)) or short_name(hlo_text)
    m = re.search(r" = \(?([a-z0-9]+\[[0-9,]*\])", hlo_text)
    return f"{name} {m.group(1)}" if m else name


def op_kind(hlo_text: str) -> str:
    return stable_name(hlo_text).split(" ", 1)[0]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` with nothing but JAX."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, DeviceTimeline] = {}
    annotations: List[Event] = []
    enqueue_end: Dict[int, float] = {}

    def events(line, want_run_id=False):
        out = []
        for e in line.events:
            run_id = None
            if want_run_id:
                for k, v in e.stats:
                    if k == "run_id":
                        run_id = int(v)
                        break
            start = float(e.start_ns) * 1e-9
            out.append(Event(e.name, start,
                             start + float(e.duration_ns) * 1e-9, run_id))
        return out

    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            tl = devices.setdefault(int(m.group(1)), DeviceTimeline())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tl.ops = events(line)
                elif line.name == MODULES_LINE:
                    tl.modules = events(line, want_run_id=True)
                elif line.name == ASYNC_LINE:
                    tl.async_ops = events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in events(line, want_run_id=True):
                    if e.name.startswith(ANNOTATION_PREFIX):
                        annotations.append(e)
                    elif e.name == ENQUEUE_EVENT and e.run_id is not None:
                        enqueue_end[e.run_id] = e.end

    lags = [enqueue_end[m.run_id] - m.start
            for tl in devices.values() for m in tl.modules
            if m.run_id in enqueue_end]
    offset = max(lags) if lags else 0.0
    for tl in devices.values():
        for e in tl.ops + tl.modules + tl.async_ops:
            e.start += offset
            e.end += offset
    annotations.sort(key=lambda e: e.start)
    return Trace(devices, annotations, offset)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the (merged) intervals ``a`` not covered by ``b``."""
    out: List[Interval] = []
    b = union(b)
    first = 0                  # both lists are sorted: never look back
    for lo, hi in union(a):
        cur = lo
        while first < len(b) and b[first][1] <= lo:
            first += 1
        j = first
        while j < len(b) and b[j][0] < hi:
            s, e = b[j]
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
            j += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def window_of(trace: Trace, annotation: str) -> Interval:
    """The span of the first annotation of that name: the traced window as
    the benchmark marked it."""
    for e in trace.annotations:
        if e.name == annotation:
            return (e.start, e.end)
    raise KeyError(f"no annotation {annotation!r} in the trace")


@dataclass
class Reduction:
    window_s: float
    busy_s: float                          # mean over devices
    busy_by_device: Dict[int, float]
    op_seconds: Dict[str, float]           # stable name -> seconds, device 0
    idle_gaps: List[Tuple[str, float]]     # (host annotation, seconds)
    window: Interval = (0.0, 0.0)


def reduce(trace: Trace, window: Interval, *, top: int = 10,
           gap_layer: Sequence[str] = ()) -> Reduction:
    """Busy time per device inside ``window``, seconds per operation on the
    first device, and the idle time of the first device summed by the
    innermost host annotation over each piece of it (``gap_layer``: only
    annotations with one of these prefixes take part; none = all)."""
    lo, hi = window
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    busy_by_device = {}
    for dev, tl in sorted(trace.devices.items()):
        busy = union(clip([(e.start, e.end) for e in tl.ops], lo, hi))
        busy_by_device[dev] = total(busy)
    first = min(trace.devices)
    tl = trace.devices[first]
    op_seconds: Dict[str, float] = {}
    for e in tl.ops:
        a, b = max(e.start, lo), min(e.end, hi)
        name = stable_name(e.name)
        if b > a and op_kind(e.name) not in CONTAINERS:
            op_seconds[name] = op_seconds.get(name, 0.0) + (b - a)
    busy0 = union(clip([(e.start, e.end) for e in tl.ops], lo, hi))
    gaps = subtract([(lo, hi)], busy0)
    hosts = [e for e in trace.annotations
             if (not gap_layer or e.name.startswith(tuple(gap_layer)))
             and e.end > lo and e.start < hi]
    by_host: Dict[str, float] = {}

    def credit(name, seconds):
        by_host[name] = by_host.get(name, 0.0) + seconds

    for a, b in gaps:
        if b - a < MIN_GAP_S:
            credit("(between operations)", b - a)
            continue
        inside = [e for e in hosts if e.start < b and e.end > a]
        cuts = sorted({a, b, *(min(max(t, a), b) for e in inside
                               for t in (e.start, e.end))})
        for s, t in zip(cuts, cuts[1:]):
            # the innermost span over this piece: the one that began last
            over = [e for e in inside if e.start <= s and e.end >= t]
            credit(max(over, key=lambda e: e.start).name if over
                   else "(no host span)", t - s)
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_s=hi - lo,
        busy_s=sum(busy_by_device.values()) / len(busy_by_device),
        busy_by_device=busy_by_device, op_seconds=op_seconds,
        idle_gaps=idle, window=window)


def matching(trace: Trace, pattern: str, window: Interval, *,
             device: Optional[int] = None) -> Dict[str, float]:
    """Seconds of the operations whose name (``%name`` of the HLO
    instruction, without its operands) matches ``pattern`` on one device
    inside ``window``: ``total`` (union of their intervals, the
    asynchronous start-to-done spans included), ``exposed`` (the part of
    it during which no other operation ran), ``count``."""
    lo, hi = window
    dev = min(trace.devices) if device is None else device
    tl = trace.devices[dev]
    rx = re.compile(pattern)

    def is_hit(e):
        return bool(rx.search(short_name(e.name)))

    hit = [(e.start, e.end) for e in tl.ops + tl.async_ops if is_hit(e)]
    other = [(e.start, e.end) for e in tl.ops if not is_hit(e)
             and op_kind(e.name) not in CONTAINERS]
    hit_u = union(clip(hit, lo, hi))
    return {"total": total(hit_u),
            "exposed": total(subtract(hit_u, clip(other, lo, hi))),
            "count": float(sum(1 for e in tl.ops if is_hit(e)
                               and lo <= e.start < hi))}


def top_ops(red: Reduction, n: int = 10) -> List[Tuple[str, float]]:
    return sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:n]
