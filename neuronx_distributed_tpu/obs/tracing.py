"""Host-side span tracer: nested spans, chrome-trace export, latency stats.

Spans are host-side only — the tracer must never be entered from inside a
jitted/shard_mapped function (the nxdlint ``observability`` rule enforces
this): a span around ``step_fn(...)`` measures dispatch+execution, a span
*inside* would measure trace time once and then lie forever.

Three surfaces:

* ``span(name, **attrs)`` — context manager, nests via a per-thread stack;
* ``request_*`` — request-scoped traces keyed by request uid. A serving
  request crosses threads and step boundaries (router admission → engine
  queue → chunked-prefill slices → per-step decode → retirement, possibly
  via failover/migration to another replica), so the per-thread span stack
  cannot follow it. Request traces instead accumulate per-phase time
  under an explicit uid: ``request_begin`` at admission,
  ``request_phase_begin/end`` for open-ended waits, ``request_mark`` /
  ``request_slices`` for step-sliced work, ``request_export`` /
  ``request_import`` to carry the trace across a live-migration ticket,
  and ``request_end(outcome=...)`` at retirement — which emits one
  chrome event per request with per-phase totals and critical-path
  attribution in ``args``.
* ``profile_step(logdir)`` — wraps ``jax.profiler`` start/stop_trace and
  records a host span carrying the logdir attribute, so the device trace
  is findable from the host timeline. While it is open every ``span()``
  also opens a ``jax.profiler.TraceAnnotation`` of the same name: the
  spans are then in the ``.xplane.pb`` itself, on the profiler's clock,
  beside the device operations.

Host pauses are spans too. ``watch_gc(True)`` (``obs.enable()`` calls it)
puts a hook into ``gc.callbacks``: every collection of the interpreter
becomes a span ``host/gc`` (``generation``, ``collected``) on the thread
that ran it. The hook runs wherever an allocation happened to trigger the
collection, the tracer's own ``_append_event`` under ``_lock`` included, so
it takes **no lock**: it appends its readings to a ``deque`` and the tracer
folds them into its events at its next record or snapshot.
``watch_host(True)`` (``obs.enable()`` calls it too) starts a witness of
the process: one daemon thread that sleeps ``WITNESS_PERIOD_NS`` and reads
the clock. A wake-up ``STOP_MIN_NS`` or more after it was due is a time in
which a thread that had nothing to do but wake did not run: a span
``host/stopped`` (from the moment the wake-up was due, as long as it was
late, with what the host's counters gained meanwhile: :mod:`.host`) through
the same ``deque`` and fold. While the host is watched the tracer also
reads what a thread did, for whoever asks (:meth:`SpanTracer.thread_reading`:
the serving engine, at each ``step()`` call's entry and return).
:meth:`SpanTracer.step_records` groups the events that carry a ``step``
attribute (the serving engine's, one number a ``step()`` call) into
per-call records: a view of the events held, not a second store.

``chrome_trace()`` / ``save()`` snapshot everything **under the lock**. A
``span()`` is recorded when it closes, so one still open at the snapshot
is not in it; a request trace still live is, as a zero-duration
``"incomplete"`` event.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import json
import math
import os
import random
import threading
import time
import zlib
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import host
from .metrics import HISTOGRAM_RESERVOIR, QUANTILES, get_registry

#: the span a collection of the interpreter leaves, outside ``engine/`` on
#: purpose: the benchmark's span metrics take ``engine/`` names only
GC_SPAN = "host/gc"
#: the span a stop of the process leaves, on the witness's thread; outside
#: ``engine/`` for the same reason (the benchmark nests the ``engine/``
#: events as one thread's)
STOPPED_SPAN = "host/stopped"
#: the spans that reach the events through ``_pending``, after they ended
PAUSE_SPANS = (GC_SPAN, STOPPED_SPAN)

#: the witness: its thread's name, how long it sleeps (a wake-up that
#: finds the stepping thread in Python takes the interpreter's lock from
#: it: at 5 ms that was most of 0.07-0.09 ms a step on the chip's host, PR
#: 69's traced pairs; a stop reads up to a period short), how late a
#: wake-up is a stop (PR 50's hand run: a thread that only sleeps never ran
#: more than 20 ms late in 190 sound seconds, and 108-113 ms late in every
#: stalled step; on the chip's host 1.1 ms at the most beside a sleeping
#: main thread and 7.1 beside one that runs Python), how old its baseline
#: of the host's counters may grow, and how long
#: :meth:`SpanTracer.stopped_since` lets it catch up
WITNESS_THREAD = "nxd-host-witness"
WITNESS_PERIOD_NS = 10_000_000
STOP_MIN_NS = 50_000_000
WITNESS_BASELINE_NS = 1_000_000_000
WITNESS_SETTLE_NS = 40_000_000

#: an event's own keys. A span's attributes lie flat beside them in the
#: tracer's list (``args`` is built at export): a dict of strings and numbers
#: alone is not tracked by the interpreter's collector, one that holds a
#: dict is, and some 45,000 tracked events a 40 s serving window brought a
#: generation-2 pass of 90 ms into every traced run (PR 50's first chip
#: runs: the parent's events, without attributes, had brought none)
_EVENT_KEYS = ("name", "ph", "ts", "dur", "pid", "tid")
_RESERVED = frozenset(_EVENT_KEYS + ("args",))


def attrs_of(ev: Dict[str, Any]) -> Dict[str, Any]:
    """An event's attributes, whichever way it holds them."""
    if "args" in ev:
        return ev["args"]
    return {k: v for k, v in ev.items() if k not in _RESERVED}


def _exported(ev: Dict[str, Any]) -> Dict[str, Any]:
    """A copy of an event in the chrome-trace form (``args`` nested)."""
    if len(ev) == len(_EVENT_KEYS) or "args" in ev:
        return dict(ev)
    out = {k: ev[k] for k in _EVENT_KEYS}
    out["args"] = attrs_of(ev)
    return out


#: live request traces kept before the oldest is evicted — a leak guard
#: for callers that begin traces and never retire them, not a window.
MAX_LIVE_REQUESTS = 10_000


class _RequestTrace:
    """Accumulated per-phase time for one in-flight request."""

    __slots__ = ("uid", "trace_id", "t0_us", "attrs", "phase_us",
                 "phase_n", "open_phases", "migrations")

    def __init__(self, uid: str, trace_id: str, t0_us: float,
                 attrs: Dict[str, Any]):
        self.uid = uid
        self.trace_id = trace_id
        self.t0_us = t0_us
        self.attrs = attrs
        self.phase_us: Dict[str, float] = {}
        self.phase_n: Dict[str, int] = {}
        self.open_phases: Dict[str, float] = {}
        self.migrations = 0

    def add(self, phase: str, dur_us: float, n: int = 1) -> None:
        self.phase_us[phase] = self.phase_us.get(phase, 0.0) + dur_us
        self.phase_n[phase] = self.phase_n.get(phase, 0) + n


class _SpanStats:
    """Running latency stats of one span name: exact count, total, min
    and max, and a uniform sample of ``HISTOGRAM_RESERVOIR`` durations
    for the quantiles (Algorithm R, as ``metrics._HistChild``), so an
    operator who leaves obs on holds a bounded number of floats a name."""

    __slots__ = ("count", "total", "min", "max", "reservoir", "_rng")

    def __init__(self, name: str):
        self.count = 0
        self.total = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.reservoir: List[float] = []
        self._rng = random.Random(zlib.crc32(name.encode("utf-8")))

    def add(self, dur: float) -> None:
        self.count += 1
        self.total += dur
        if dur < self.min:
            self.min = dur
        if dur > self.max:
            self.max = dur
        if len(self.reservoir) < HISTOGRAM_RESERVOIR:
            self.reservoir.append(dur)
        else:
            j = self._rng.randrange(self.count)
            if j < HISTOGRAM_RESERVOIR:
                self.reservoir[j] = dur


class _NullSpan:
    """Returned when tracing is disabled: one shared, reentrant no-op."""

    __slots__ = ()
    t0_us = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attribute(self, key: str, value: Any) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Span:
    __slots__ = ("tracer", "name", "attrs", "t0_us", "parent",
                 "_annotation")

    def __init__(self, tracer: "SpanTracer", name: str,
                 attrs: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.t0_us = 0.0
        self.parent: Optional[str] = None
        self._annotation = None

    def set_attribute(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def __enter__(self) -> "Span":
        stack = self.tracer._stack()
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        if self.tracer._annotate:
            # the annotation opens before and closes after the span's own
            # readings: what it costs is outside the recorded duration
            import jax

            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self.t0_us = time.perf_counter_ns() / 1000.0
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        end_us = time.perf_counter_ns() / 1000.0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        self.tracer._record(self, end_us)
        return False


class SpanTracer:
    """Thread-safe recorder for nested host spans.

    ``max_events`` bounds memory: beyond it the event list becomes a ring
    buffer of the most recent spans (per-name stats keep counting — they
    aggregate at record time, not from the buffer).
    """

    def __init__(self, enabled: bool = True, max_events: int = 200_000):
        self.enabled = enabled
        self.max_events = max_events
        self._events: List[Dict[str, Any]] = []
        self._next = 0
        self._stats: Dict[str, _SpanStats] = {}
        # on only while ``profile_step`` is open: spans then also open a
        # profiler annotation of their name
        self._annotate = False
        self._requests: Dict[str, _RequestTrace] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()
        # pauses that have ended and no record has folded in yet (the
        # collector's hook and the witness append, neither takes a lock):
        # (span name, start ns, end ns, thread, attributes)
        self._pending: collections.deque = collections.deque(maxlen=4096)
        self._gc_open: Optional[Tuple[int, Any]] = None
        # while the host is watched: the witness's thread and what stops
        # it, when it last woke, and the meter of the threads that ask
        self._witness: Optional[Tuple[threading.Thread,
                                      threading.Event]] = None
        self._witness_seen_ns: Optional[int] = None
        self._meter: Optional[host.ThreadMeter] = None

    # -- plumbing ---------------------------------------------------
    def _stack(self) -> List[Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _append_event(self, ev: Dict[str, Any]) -> None:
        # caller holds self._lock
        if len(self._events) < self.max_events:
            self._events.append(ev)
        else:
            self._events[self._next] = ev
            self._next = (self._next + 1) % self.max_events

    def _add_stat(self, name: str, dur: float) -> None:
        # caller holds self._lock
        st = self._stats.get(name)
        if st is None:
            st = self._stats[name] = _SpanStats(name)
        st.add(dur)

    def _record(self, span: Span, end_us: float) -> None:
        dur = end_us - span.t0_us
        ev = {
            "name": span.name, "ph": "X", "ts": span.t0_us, "dur": dur,
            "pid": os.getpid(), "tid": threading.get_ident() % 10000,
        }
        attrs = span.attrs
        if span.parent is not None:
            attrs["parent"] = span.parent
        if _RESERVED.isdisjoint(attrs):
            ev.update(attrs)
        else:
            ev["args"] = attrs
        with self._lock:
            if self._pending:
                self._fold_pauses()
            self._append_event(ev)
            self._add_stat(span.name, dur)

    # -- host pauses --------------------------------------------------
    def watch_gc(self, on: bool) -> None:
        """Install (or remove) the ``gc.callbacks`` hook that makes every
        collection a ``host/gc`` span. Idempotent."""
        installed = self._on_gc in gc.callbacks
        if on and not installed:
            gc.callbacks.append(self._on_gc)
        elif installed and not on:
            gc.callbacks.remove(self._on_gc)
            self._gc_open = None

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        """The hook: two clock readings a collection into ``_pending``.
        It runs in whatever thread allocated last, possibly under
        ``_lock`` or the registry's lock, and takes neither. Inside
        ``profile_step`` the pause is a profiler annotation as well, as
        every span is there."""
        if phase == "start":
            if not self.enabled:
                return
            annotation = None
            if self._annotate:
                import jax

                annotation = jax.profiler.TraceAnnotation(GC_SPAN)
                annotation.__enter__()
            self._gc_open = (time.perf_counter_ns(), annotation)
            return
        end = time.perf_counter_ns()
        opened, self._gc_open = self._gc_open, None
        if opened is None:
            return
        if opened[1] is not None:
            opened[1].__exit__(None, None, None)
        self._pending.append((
            GC_SPAN, opened[0], end, threading.get_ident(),
            {"generation": info["generation"],
             "collected": info["collected"]}))

    def watch_host(self, on: bool) -> None:
        """Start (or stop and join) the witness thread, and with it open
        (or close) the meter behind :meth:`thread_reading`. Idempotent."""
        witness = self._witness
        if on and witness is None:
            stop = threading.Event()
            thread = threading.Thread(
                target=self._witness_loop, args=(stop,), name=WITNESS_THREAD,
                daemon=True)
            self._meter = host.ThreadMeter()
            self._witness = (thread, stop)
            thread.start()
        elif witness is not None and not on:
            self._witness = None
            witness[1].set()
            witness[0].join(timeout=1.0)
            self._witness_seen_ns = None
            meter, self._meter = self._meter, None
            meter.close()

    def _witness_loop(self, stop: threading.Event,
                      clock=time.perf_counter_ns, sleep=time.sleep) -> None:
        """The witness: sleep a period, read the clock, and where the
        wake-up came ``STOP_MIN_NS`` late say so into ``_pending`` with
        what the host's counters gained since the baseline (read again
        about once a second, and after a stop). Like ``_on_gc`` it takes no
        lock, and a wake-up is some microseconds of the interpreter's
        (``time.sleep`` and no ``Event.wait``, which is a dozen calls of
        Python): it looks at ``stop`` once it is awake."""
        counters = host.HostCounters()
        base, base_at = counters.read(), clock()
        due = base_at + WITNESS_PERIOD_NS
        ident = threading.get_ident()
        while True:
            sleep(WITNESS_PERIOD_NS * 1e-9)
            if stop.is_set():
                return
            now = clock()
            if now - due >= STOP_MIN_NS:
                after = counters.read()
                if self.enabled:
                    self._pending.append((STOPPED_SPAN, due, now, ident,
                                          host.deltas(base, after)))
                base, base_at = after, now
            elif now - base_at >= WITNESS_BASELINE_NS:
                base, base_at = counters.read(), now
            # after the append: who sees a late wake-up sees its stop
            self._witness_seen_ns = now
            due = now + WITNESS_PERIOD_NS

    def thread_reading(self) -> Optional[host.Reading]:
        """The calling thread's cumulative CPU time, switches, faults and
        run-queue delay (:class:`.host.ThreadMeter`), or ``None`` while the
        host is not watched; :func:`.host.since` subtracts two of them."""
        meter = self._meter
        return None if meter is None else meter.read()

    def stopped_since(self, since_us: float) -> List[Dict[str, Any]]:
        """The ``host/stopped`` spans that ended at or after ``since_us``,
        oldest first. The witness wakes from a stop when the caller does
        and needs the interpreter's lock to say so: where it has not been
        seen for two periods the caller sleeps, half a millisecond at a
        time and ``WITNESS_SETTLE_NS`` at most, until it has. (A wake-up it
        has been seen at since is later than any stop that has ended: a
        stop keeps it away for ``STOP_MIN_NS`` at least.)"""
        give_up = time.perf_counter_ns() + WITNESS_SETTLE_NS
        while self._witness is not None:
            seen, now = self._witness_seen_ns, time.perf_counter_ns()
            if (seen is None or now - seen <= 2 * WITNESS_PERIOD_NS
                    or now >= give_up):
                break
            time.sleep(0.0005)
        return [ev for ev in reversed(self._closed_since(since_us))
                if ev["name"] == STOPPED_SPAN]

    def _fold_pauses(self) -> None:
        # caller holds self._lock
        pending = self._pending
        reg = get_registry()
        while pending:
            name, t0, end, ident, attrs = pending.popleft()
            dur = (end - t0) / 1000.0
            ev = {"name": name, "ph": "X", "ts": t0 / 1000.0, "dur": dur,
                  "pid": os.getpid(), "tid": ident % 10000}
            ev.update(attrs)
            self._append_event(ev)
            self._add_stat(name, dur)
            if not reg.enabled:
                continue
            if name == GC_SPAN:
                reg.counter(
                    "nxd_host_gc_seconds_total",
                    "Seconds the interpreter spent in garbage collections, "
                    "by generation (the host/gc spans' durations).",
                    labels=("generation",)).labels(
                        generation=str(attrs["generation"])).inc(dur * 1e-6)
            else:
                reg.counter(
                    "nxd_host_stopped_seconds_total",
                    "Seconds in which no thread of the process ran: the "
                    "host/stopped spans' durations, each a wake-up of the "
                    "witness thread that came 50 ms or more late.").inc(
                        dur * 1e-6)

    # -- per-call records ---------------------------------------------
    def _closed_since(self, since_us: float) -> List[Dict[str, Any]]:
        """The events held that closed at or after ``since_us``, newest
        first, the pauses that ended meanwhile folded in."""
        with self._lock:
            if self._pending:
                self._fold_pauses()
            n = len(self._events)
            newest = (self._next - 1) if n == self.max_events else n - 1
            tail = []
            for k in range(n):
                ev = self._events[(newest - k) % n]
                if ev["ts"] + ev["dur"] >= since_us:
                    tail.append(ev)
                elif ev["name"] not in PAUSE_SPANS:
                    break               # a pause is folded in late
        return tail

    def step_records(self, since_us: float = 0.0
                     ) -> Dict[int, Dict[str, Any]]:
        """The events that closed at or after ``since_us`` and carry a
        ``step`` attribute, grouped by it: one record a call,
        ``{"step", "entry_us", "return_us", "self_us": {span name: its
        time less what opened inside it}, "attrs": {span name: its other
        attributes}, "gc": [(generation, collected, us), ...]}``. The
        ``host/gc`` spans of the call's thread between its entry and its
        return are its ``gc`` and count as children of the span they fell
        in (``self_us["host/gc"]`` is their sum). A view of the events
        the tracer holds, built when asked for; a span still open is in
        no record yet."""
        tail = self._closed_since(since_us)
        calls: Dict[int, List[Dict[str, Any]]] = {}
        pauses = []
        for ev in tail:
            step = attrs_of(ev).get("step")
            if step is not None:
                calls.setdefault(step, []).append(ev)
            elif ev["name"] == GC_SPAN:
                pauses.append(ev)
        out = {}
        for step, evs in calls.items():
            entry = min(ev["ts"] for ev in evs)
            ret = max(ev["ts"] + ev["dur"] for ev in evs)
            tid = evs[0]["tid"]
            inside = [p for p in pauses if p["tid"] == tid
                      and entry <= p["ts"] and p["ts"] + p["dur"] <= ret]
            self_us: Dict[str, float] = {}
            attrs: Dict[str, Dict[str, Any]] = {}
            stack: List[list] = []              # [end, name, self_us]
            for ev in sorted(evs + inside,
                             key=lambda e: (e["ts"], -e["dur"])):
                while stack and stack[-1][0] <= ev["ts"]:
                    _, name, own = stack.pop()
                    self_us[name] = self_us.get(name, 0.0) + max(own, 0.0)
                if stack:
                    stack[-1][2] -= ev["dur"]
                stack.append([ev["ts"] + ev["dur"], ev["name"], ev["dur"]])
                if ev["name"] != GC_SPAN:
                    attrs.setdefault(ev["name"], {}).update(
                        (k, v) for k, v in attrs_of(ev).items()
                        if k not in ("step", "parent"))
            for _, name, own in stack:
                self_us[name] = self_us.get(name, 0.0) + max(own, 0.0)
            out[step] = {
                "step": step, "entry_us": entry, "return_us": ret,
                "self_us": self_us, "attrs": attrs,
                "gc": [(p["generation"], p["collected"], p["dur"])
                       for p in inside]}
        return out

    # -- span surface -----------------------------------------------
    def span(self, name: str, **attrs: Any):
        if not self.enabled:
            return _NULL_SPAN
        return Span(self, name, attrs)

    # -- request-scoped traces ---------------------------------------
    def request_begin(self, uid: str, trace_id: Optional[str] = None,
                      **attrs: Any) -> Optional[str]:
        """Open (or adopt) a request trace; returns its trace-id.

        Idempotent: a second ``request_begin`` for a live uid merges
        attributes and keeps the original trace-id, so the router can
        open the trace at admission and a standalone engine can call it
        again at ``submit`` without forking the request's identity.
        """
        if not self.enabled:
            return None
        now = time.perf_counter_ns() / 1000.0
        with self._lock:
            tr = self._requests.get(uid)
            if tr is not None:
                tr.attrs.update(attrs)
                return tr.trace_id
            if len(self._requests) >= MAX_LIVE_REQUESTS:
                # leak guard: drop the oldest live trace, not the newest
                self._requests.pop(next(iter(self._requests)))
            tr = _RequestTrace(uid, trace_id or ("trace-%s" % uid),
                               now, dict(attrs))
            self._requests[uid] = tr
            return tr.trace_id

    def request_trace_id(self, uid: str) -> Optional[str]:
        with self._lock:
            tr = self._requests.get(uid)
            return tr.trace_id if tr is not None else None

    def request_annotate(self, uid: str, **attrs: Any) -> None:
        if not self.enabled:
            return
        with self._lock:
            tr = self._requests.get(uid)
            if tr is not None:
                tr.attrs.update(attrs)

    def request_phase_begin(self, uid: str, phase: str) -> None:
        """Open-ended phase (queue waits) closed by ``request_phase_end``
        — or implicitly by ``request_end`` / ``request_export``."""
        if not self.enabled:
            return
        now = time.perf_counter_ns() / 1000.0
        with self._lock:
            tr = self._requests.get(uid)
            if tr is not None:
                tr.open_phases.setdefault(phase, now)

    def request_phase_end(self, uid: str, phase: str) -> None:
        if not self.enabled:
            return
        now = time.perf_counter_ns() / 1000.0
        with self._lock:
            tr = self._requests.get(uid)
            if tr is None:
                return
            start = tr.open_phases.pop(phase, None)
            if start is not None:
                tr.add(phase, now - start)

    def request_mark(self, uid: str, phase: str, dur_us: float = 0.0,
                     n: int = 1) -> None:
        """Accumulate a known duration (or a zero-duration marker such as
        ``resubmit``) into a request phase."""
        if not self.enabled:
            return
        with self._lock:
            tr = self._requests.get(uid)
            if tr is not None:
                tr.add(phase, dur_us, n)

    def request_slices(
            self, items: Iterable[Tuple[str, str, float]]) -> None:
        """Batch ``request_mark`` — one lock acquisition for a whole
        engine step's prefill/decode slice attribution."""
        if not self.enabled:
            return
        with self._lock:
            for uid, phase, dur_us in items:
                tr = self._requests.get(uid)
                if tr is not None:
                    tr.add(phase, dur_us)

    def request_export(self, uid: str) -> Optional[Dict[str, Any]]:
        """Pop a live trace into a portable dict (a ``SessionTicket``
        rider): the importing replica resumes the same trace-id and the
        accumulated phase totals survive the migration."""
        if not self.enabled:
            return None
        now = time.perf_counter_ns() / 1000.0
        with self._lock:
            tr = self._requests.pop(uid, None)
            if tr is None:
                return None
            for phase, start in tr.open_phases.items():
                tr.add(phase, now - start)
            return {
                "uid": tr.uid, "trace_id": tr.trace_id,
                "attrs": dict(tr.attrs),
                "phase_us": dict(tr.phase_us),
                "phase_n": dict(tr.phase_n),
                "elapsed_us": now - tr.t0_us,
                "migrations": tr.migrations + 1,
            }

    def request_import(self, state: Dict[str, Any]) -> None:
        """Adopt an exported request trace on the destination replica."""
        if not self.enabled or not state:
            return
        now = time.perf_counter_ns() / 1000.0
        with self._lock:
            uid = str(state.get("uid", ""))
            if not uid or uid in self._requests:
                return
            if len(self._requests) >= MAX_LIVE_REQUESTS:
                self._requests.pop(next(iter(self._requests)))
            tr = _RequestTrace(uid, str(state.get("trace_id", uid)),
                               now - float(state.get("elapsed_us", 0.0)),
                               dict(state.get("attrs", {})))
            tr.phase_us = {str(k): float(v)
                           for k, v in state.get("phase_us", {}).items()}
            tr.phase_n = {str(k): int(v)
                          for k, v in state.get("phase_n", {}).items()}
            tr.migrations = int(state.get("migrations", 1))
            self._requests[uid] = tr

    def request_end(self, uid: str, outcome: str = "completed",
                    **attrs: Any) -> Optional[Dict[str, Any]]:
        """Retire a request trace: emits one chrome event carrying the
        per-phase totals and critical-path attribution, and returns the
        summary (``None`` for unknown uids or when disabled)."""
        if not self.enabled:
            return None
        now = time.perf_counter_ns() / 1000.0
        with self._lock:
            tr = self._requests.pop(uid, None)
            if tr is None:
                return None
            for phase, start in tr.open_phases.items():
                tr.add(phase, now - start)
            total_us = max(0.0, now - tr.t0_us)
            attributed = sum(tr.phase_us.values())
            critical = max(tr.phase_us.items(), key=lambda kv: kv[1])[0] \
                if tr.phase_us else ""
            args: Dict[str, Any] = dict(tr.attrs)
            args.update(attrs)
            args.update({
                "trace_id": tr.trace_id, "outcome": outcome,
                "phase_us": {k: round(v, 3)
                             for k, v in sorted(tr.phase_us.items())},
                "phase_n": dict(sorted(tr.phase_n.items())),
                "critical_path": critical,
                "phase_share": {
                    k: round(v / total_us, 4) if total_us > 0 else 0.0
                    for k, v in sorted(tr.phase_us.items())},
                "unattributed_us": round(max(0.0, total_us - attributed),
                                         3),
            })
            if tr.migrations:
                args["migrations"] = tr.migrations
            self._append_event({
                "name": "request:%s" % uid, "ph": "X",
                "ts": tr.t0_us, "dur": total_us,
                "pid": os.getpid(),
                # stable per-request lane so each request gets its own
                # row in the chrome viewer regardless of serving thread
                "tid": zlib.crc32(uid.encode("utf-8")) % 10000,
                "args": args,
            })
            self._add_stat("request/%s" % outcome, total_us)
            return {"uid": uid, "trace_id": tr.trace_id,
                    "outcome": outcome, "total_us": total_us,
                    "phase_us": dict(tr.phase_us),
                    "critical_path": critical}

    # -- jax.profiler glue ------------------------------------------
    @contextlib.contextmanager
    def profile_step(self, logdir: str = "/tmp/nxd_profile",
                     profiler_options=None):
        """Attach an XLA device trace (viewable in Perfetto/TensorBoard)
        to a host span, so device and host timelines cross-reference.
        Every span opened while it is open (this one included) is also a
        ``jax.profiler.TraceAnnotation``: it lands on the host plane of
        the written ``.xplane.pb``, on the clock of the device events.
        ``profiler_options`` (a ``jax.profiler.ProfileOptions``) goes to
        ``start_trace``: a window of seconds wants the Python tracer off
        (``python_tracer_level = 0``)."""
        import jax

        jax.profiler.start_trace(logdir, profiler_options=profiler_options)
        was, self._annotate = self._annotate, True
        try:
            with self.span("profile_step", logdir=logdir):
                yield logdir
        finally:
            self._annotate = was
            jax.profiler.stop_trace()

    # -- export ------------------------------------------------------
    def chrome_trace(self) -> Dict[str, Any]:
        """Snapshot as a chrome-trace dict.

        Taken entirely under the lock so concurrent writers can't tear
        the event list. Request traces still live at snapshot time appear
        as zero-duration events tagged ``{"incomplete": true}``; a
        ``span()`` still open is recorded when it closes and not before.
        """
        now = time.perf_counter_ns() / 1000.0
        with self._lock:
            if self._pending:
                self._fold_pauses()
            if len(self._events) < self.max_events:
                events = list(self._events)
            else:  # unroll the ring into chronological order
                events = (self._events[self._next:]
                          + self._events[:self._next])
            open_requests = [
                (tr.uid, tr.trace_id, tr.t0_us, dict(tr.phase_us))
                for tr in self._requests.values()]
        events = [_exported(ev) for ev in events]
        for uid, trace_id, start, phase_us in sorted(open_requests):
            events.append({
                "name": "request:%s" % uid, "ph": "X", "ts": start,
                "dur": 0.0, "pid": os.getpid(),
                "tid": zlib.crc32(uid.encode("utf-8")) % 10000,
                "args": {"incomplete": True, "trace_id": trace_id,
                         "open_for_us": now - start,
                         "phase_us": phase_us},
            })
        return {"traceEvents": events}

    def save(self, path: str) -> str:
        trace = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(trace, f)
        return path

    def stats(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name latency stats (durations in microseconds): count,
        total, mean, min and max over every span recorded, quantiles over
        the name's reservoir."""
        with self._lock:
            if self._pending:
                self._fold_pauses()
            snap = {name: (st.count, st.total, st.min, st.max,
                           list(st.reservoir))
                    for name, st in self._stats.items()}
        out: Dict[str, Dict[str, float]] = {}
        for name, (count, total, lo, hi, durs) in sorted(snap.items()):
            durs.sort()
            entry = {
                "count": float(count),
                "total_us": total,
                "mean_us": total / count,
                "min_us": lo,
                "max_us": hi,
            }
            n = len(durs)
            for q in QUANTILES:
                idx = max(0, min(n - 1, int(math.ceil(q * n)) - 1))
                entry["p%g_us" % (q * 100)] = durs[idx]
            out[name] = entry
        return out

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._next = 0
            self._stats.clear()
            self._requests.clear()
            self._pending.clear()


#: process-wide default tracer; enabled/disabled in lockstep with the
#: default metrics registry by ``obs.enable()`` / ``obs.disable()``.
_DEFAULT: Optional[SpanTracer] = None
_DEFAULT_LOCK = threading.Lock()


def get_tracer() -> SpanTracer:
    global _DEFAULT
    if _DEFAULT is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                tracer = SpanTracer(
                    enabled=os.environ.get("NXD_OBS", "0") == "1")
                tracer.watch_gc(tracer.enabled)
                tracer.watch_host(tracer.enabled)
                _DEFAULT = tracer
    return _DEFAULT
