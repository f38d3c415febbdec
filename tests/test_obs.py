"""Unified observability subsystem (``obs/``): registry semantics,
Prometheus exposition, span tracer (save-race regression, engine spans),
compile tracking, wire-byte accounting vs the codec's predictions, the
event channel, and the logger satellites."""

import contextlib
import gc
import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu import obs
from neuronx_distributed_tpu.obs import host as obs_host
from neuronx_distributed_tpu.obs import tracing
from neuronx_distributed_tpu.obs.metrics import MetricsRegistry
from neuronx_distributed_tpu.obs.tracing import SpanTracer
from neuronx_distributed_tpu.parallel import mesh as ps
from counter_checks import check_registered_counters


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Isolate the process-wide registry/tracer and restore the enable
    switch, so obs-enabled tests don't leak state into the rest of the
    suite (which runs with obs disabled, the default)."""
    was = obs.enabled()
    obs.reset()
    yield
    obs.reset()
    if was:
        obs.enable()
    else:
        obs.disable()


@contextlib.contextmanager
def _capture(logger):
    """Collect records emitted on ``logger`` directly — the package
    loggers set ``propagate=False``, so caplog's root handler misses
    them."""
    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    logger.addHandler(handler)
    try:
        yield records
    finally:
        logger.removeHandler(handler)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_labels_and_get_or_create():
    reg = MetricsRegistry()
    c = reg.counter("nxd_reqs_total", "Requests.", labels=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc(5)
    assert c.labels(kind="a").value == 3.0
    assert c.labels(kind="b").value == 5.0
    # idempotent re-creation returns the same family
    assert reg.counter("nxd_reqs_total", labels=("kind",)) is c
    # counters only go up
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)
    # wrong label set
    with pytest.raises(ValueError):
        c.labels(nope="x")
    # unlabeled use of a labeled family
    with pytest.raises(ValueError):
        c.inc()


def test_duplicate_name_different_kind_or_labels_rejected():
    reg = MetricsRegistry()
    reg.counter("nxd_thing_total", labels=("kind",))
    with pytest.raises(ValueError):
        reg.gauge("nxd_thing_total", labels=("kind",))
    with pytest.raises(ValueError):
        reg.counter("nxd_thing_total", labels=("other",))
    with pytest.raises(ValueError):
        reg.counter("bad name")


def test_gauge_set_inc_dec():
    reg = MetricsRegistry()
    g = reg.gauge("nxd_depth")
    g.set(4.0)
    g.inc()
    g.dec(2.0)
    assert g.value == 3.0
    c = reg.counter("nxd_c_total", labels=("k",))
    with pytest.raises(TypeError):
        c.labels(k="a").dec()


def test_histogram_quantiles_and_bounds():
    reg = MetricsRegistry()
    h = reg.histogram("nxd_lat_seconds")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100
    assert h.sum == sum(range(1, 101))
    assert h.quantile(0.5) == 50.0
    assert h.quantile(0.9) == 90.0
    assert h.quantile(0.99) == 99.0
    assert h.quantile(0.0) == 1.0
    assert h.quantile(1.0) == 100.0


def test_disabled_registry_is_a_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("nxd_c_total")
    g = reg.gauge("nxd_g")
    h = reg.histogram("nxd_h_seconds")
    c.inc(100)
    g.set(7.0)
    h.observe(1.0)
    assert c.value == 0.0 and g.value == 0.0 and h.count == 0
    reg.enable()
    c.inc(2)
    assert c.value == 2.0


def test_reset_bumps_generation_and_drops_metrics():
    reg = MetricsRegistry()
    reg.counter("nxd_c_total").inc()
    gen = reg.generation
    reg.reset()
    assert reg.get("nxd_c_total") is None
    assert reg.generation == gen + 1


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("nxd_reqs_total", "Requests.",
                labels=("kind",)).labels(kind="a").inc(3)
    reg.gauge("nxd_depth", "Queue depth.").set(2.5)
    h = reg.histogram("nxd_lat_seconds", "Latency.")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert reg.to_prometheus() == """\
# HELP nxd_depth Queue depth.
# TYPE nxd_depth gauge
nxd_depth 2.5
# HELP nxd_lat_seconds Latency.
# TYPE nxd_lat_seconds summary
nxd_lat_seconds{quantile="0.5"} 2
nxd_lat_seconds{quantile="0.9"} 4
nxd_lat_seconds{quantile="0.99"} 4
nxd_lat_seconds_sum 10
nxd_lat_seconds_count 4
# HELP nxd_reqs_total Requests.
# TYPE nxd_reqs_total counter
nxd_reqs_total{kind="a"} 3
"""


def test_snapshot_nests_into_json():
    reg = MetricsRegistry()
    reg.counter("nxd_reqs_total", labels=("kind",)).labels(kind="a").inc(3)
    reg.histogram("nxd_lat_seconds").observe(2.0)
    snap = reg.snapshot()
    json.dumps(snap)  # must be JSON-serialisable as-is
    assert snap["nxd_reqs_total"]["type"] == "counter"
    assert snap["nxd_reqs_total"]["samples"] == [
        {"labels": {"kind": "a"}, "value": 3.0}]
    [hist] = snap["nxd_lat_seconds"]["samples"]
    assert hist["count"] == 1 and hist["p50"] == 2.0


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_span_nesting_and_chrome_export():
    tracer = SpanTracer()
    with tracer.span("outer", step=3):
        with tracer.span("inner", kind="x"):
            pass
    events = tracer.chrome_trace()["traceEvents"]
    inner, outer = events  # inner closes (records) first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["args"]["parent"] == "outer"
    assert inner["args"]["kind"] == "x"
    assert "parent" not in outer["args"] and outer["args"]["step"] == 3
    for ev in events:
        assert ev["ph"] == "X" and ev["dur"] >= 0.0


def test_span_records_error_attribute():
    tracer = SpanTracer()
    with pytest.raises(ValueError):
        with tracer.span("boom"):
            raise ValueError("nope")
    [ev] = tracer.chrome_trace()["traceEvents"]
    assert ev["args"]["error"] == "ValueError"


def test_tracer_stats_per_name():
    tracer = SpanTracer()
    for _ in range(5):
        with tracer.span("work"):
            pass
    stats = tracer.stats()
    assert stats["work"]["count"] == 5.0
    assert stats["work"]["min_us"] <= stats["work"]["p50_us"] \
        <= stats["work"]["max_us"]
    assert stats["work"]["total_us"] >= stats["work"]["max_us"]


def test_open_spans_and_incomplete_snapshot():
    """A span is recorded when it closes; a live request trace shows in
    the snapshot as an incomplete event and is closable afterwards."""
    tracer = SpanTracer()
    with tracer.span("closed"):
        pass
    tracer.request_begin("still_open")
    with tracer.span("open_span"):
        events = tracer.chrome_trace()["traceEvents"]
    by_name = {ev["name"]: ev for ev in events}
    assert by_name["closed"]["dur"] >= 0.0
    assert "args" not in by_name["closed"]
    assert "open_span" not in by_name
    assert by_name["request:still_open"]["dur"] == 0.0
    assert by_name["request:still_open"]["args"]["incomplete"] is True
    assert by_name["request:still_open"]["args"]["open_for_us"] >= 0.0
    # the open request is still closable after the snapshot
    tracer.request_end("still_open")
    closed = [ev for ev in tracer.chrome_trace()["traceEvents"]
              if ev["name"] == "request:still_open"]
    assert len(closed) == 1 and "incomplete" not in closed[0]["args"]
    assert "open_span" in {
        ev["name"] for ev in tracer.chrome_trace()["traceEvents"]}


def test_request_end_without_begin_is_ignored():
    tracer = SpanTracer()
    assert tracer.request_end("never_started") is None
    tracer.request_phase_end("never_started", "queue")
    assert tracer.chrome_trace()["traceEvents"] == []


def test_disabled_tracer_records_nothing():
    tracer = SpanTracer(enabled=False)
    s = tracer.span("x")
    assert s is tracer.span("y")  # one shared null span
    with s:
        pass
    tracer.request_begin("a")
    tracer.request_end("a")
    assert tracer.chrome_trace()["traceEvents"] == []
    assert tracer.stats() == {}


def test_tracer_stats_keep_a_bounded_reservoir(monkeypatch):
    """An operator who leaves obs on: a span name holds count, total, min,
    max and at most the reservoir's samples, however many spans ran."""
    from neuronx_distributed_tpu.obs import tracing

    monkeypatch.setattr(tracing, "HISTOGRAM_RESERVOIR", 16)
    tracer = SpanTracer(max_events=8)
    span = tracing.Span(tracer, "work", {})
    for i in range(1000):               # durations 1..1000 us, no clock
        span.t0_us = 0.0
        tracer._record(span, float(i + 1))
    assert len(tracer._stats["work"].reservoir) == 16
    st = tracer.stats()["work"]
    assert st["count"] == 1000.0 and st["total_us"] == 500500.0
    assert st["mean_us"] == 500.5
    assert st["min_us"] == 1.0 and st["max_us"] == 1000.0
    # quantiles come from a uniform sample of the whole run, not its tail
    assert st["min_us"] <= st["p50_us"] <= st["p90_us"] <= st["p99_us"] \
        <= st["max_us"]
    assert 200.0 < st["p50_us"] < 800.0
    assert set(st) == {"count", "total_us", "mean_us", "min_us", "max_us",
                       "p50_us", "p90_us", "p99_us"}


# ---------------------------------------------------------------------------
# chrome-trace export + save-race regression
# ---------------------------------------------------------------------------


def test_save_roundtrip_and_tracer_isolation(tmp_path):
    tl = SpanTracer()
    with tl.span("step"):
        pass
    with tl.span("manual"):
        pass
    with open(tl.save(str(tmp_path / "t.json"))) as f:
        names = {ev["name"] for ev in json.load(f)["traceEvents"]}
    assert names == {"step", "manual"}
    # per-tracer isolation: a second tracer sees none of it
    assert json.load(open(SpanTracer().save(str(tmp_path / "u.json")))) \
        == {"traceEvents": []}


def test_tracer_enabled_flag_toggles_recording(tmp_path):
    tl = SpanTracer(enabled=False)
    path = str(tmp_path / "t.json")
    with tl.span("ignored"):
        pass
    assert json.load(open(tl.save(path)))["traceEvents"] == []
    tl.enabled = True
    assert tl.enabled
    with tl.span("kept"):
        pass
    assert len(json.load(open(tl.save(path)))["traceEvents"]) == 1


def test_save_concurrent_with_writer_thread(tmp_path):
    """Regression: the old Timeline.save iterated the event list while a
    writer thread appended (RuntimeError / torn JSON). Every save must
    produce valid JSON."""
    tl = SpanTracer(max_events=64)      # the ring wraps under the saves
    path = str(tmp_path / "race.json")
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        try:
            while not stop.is_set():
                with tl.span(f"ev{i % 7}"):
                    pass
                i += 1
        except Exception as e:  # pragma: no cover - the regression
            errors.append(e)

    t = threading.Thread(target=writer)
    t.start()
    try:
        for _ in range(50):
            with open(tl.save(path)) as f:
                trace = json.load(f)  # torn writes would fail to parse
            assert "traceEvents" in trace
    finally:
        stop.set()
        t.join()
    assert errors == []


def test_save_emits_live_request_as_incomplete(tmp_path):
    tl = SpanTracer()
    tl.request_begin("open_req")
    with open(tl.save(str(tmp_path / "open.json"))) as f:
        [ev] = json.load(f)["traceEvents"]
    assert ev["name"] == "request:open_req" and ev["dur"] == 0.0
    assert ev["args"]["incomplete"] is True


def _host_plane_names(logdir):
    import glob

    [path] = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return {ev.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events}


def test_profile_step_puts_spans_on_the_profilers_host_plane(tmp_path):
    tracer = SpanTracer()
    with tracer.span("outside/before"):
        pass
    with tracer.profile_step(str(tmp_path / "prof")):
        assert tracer._annotate
        with tracer.span("inside/annotated", step=1):
            jnp.ones((4,)).block_until_ready()
    assert not tracer._annotate
    names = _host_plane_names(tmp_path / "prof")
    assert "inside/annotated" in names and "profile_step" in names
    assert "outside/before" not in names
    # the tracer's own record is the same with and without the profiler
    by_name = {ev["name"]: ev for ev in tracer.chrome_trace()["traceEvents"]}
    assert by_name["inside/annotated"]["args"] == {
        "step": 1, "parent": "profile_step"}
    assert by_name["profile_step"]["args"]["logdir"].endswith("prof")


def test_profiler_alone_does_not_annotate_spans(tmp_path):
    """Outside ``profile_step`` a span is no profiler annotation, even
    under a running profiler: the benchmark's traced segment starts the
    profiler itself and must not see the package's spans twice."""
    tracer = SpanTracer()
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with tracer.span("plain/span"):
            with jax.profiler.TraceAnnotation("bench/marker"):
                jnp.ones((4,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    names = _host_plane_names(tmp_path / "prof")
    assert "bench/marker" in names and "plain/span" not in names
    assert [ev["name"] for ev in tracer.chrome_trace()["traceEvents"]] \
        == ["plain/span"]


# ---------------------------------------------------------------------------
# host pauses: the gc hook, host/gc spans, per-call records
# ---------------------------------------------------------------------------


def _gc_hooks():
    import gc

    return [cb for cb in gc.callbacks
            if getattr(cb, "__func__", None) is SpanTracer._on_gc]


@pytest.mark.parametrize("switch", ["enable_disable", "enable_twice",
                                    "disable_twice", "reset_keeps_it"])
def test_gc_hook_is_installed_exactly_while_obs_is_enabled(switch):
    assert not obs.enabled() and _gc_hooks() == []
    obs.enable()
    assert [cb.__self__ for cb in _gc_hooks()] == [obs.get_tracer()]
    if switch == "enable_twice":
        obs.enable()
        assert len(_gc_hooks()) == 1
    if switch == "reset_keeps_it":
        obs.reset()
        assert len(_gc_hooks()) == 1
    obs.disable()
    assert _gc_hooks() == []
    if switch == "disable_twice":
        obs.disable()
        assert _gc_hooks() == []


@pytest.mark.parametrize("generation", [0, 2])
def test_a_collection_inside_a_span_leaves_a_host_gc_span(generation):
    import gc

    obs.enable()
    tracer = obs.get_tracer()
    gc.collect()                    # what is lying about is not the subject
    tracer.reset()
    obs.get_registry().reset()
    with tracer.span("outer", step=7):
        gc.collect(generation)
    events = tracer.chrome_trace()["traceEvents"]
    pauses = [ev for ev in events if ev["name"] == "host/gc"
              and ev["args"]["generation"] == generation]
    outer, = [ev for ev in events if ev["name"] == "outer"]
    assert pauses and set(pauses[0]["args"]) == {"generation", "collected"}
    assert all(outer["ts"] <= ev["ts"]
               and ev["ts"] + ev["dur"] <= outer["ts"] + outer["dur"]
               and ev["tid"] == outer["tid"] for ev in pauses)
    # the counter holds the spans' seconds, by generation
    seconds = {c.labels["generation"]: c.value for c in obs.get_registry()
               .get("nxd_host_gc_seconds_total").children()}
    in_events = sum(ev["dur"] for ev in events if ev["name"] == "host/gc"
                    and ev["args"]["generation"] == generation)
    assert seconds[str(generation)] == pytest.approx(in_events * 1e-6)
    # and the call's record takes the pause out of the span it fell in
    record = tracer.step_records()[7]
    in_call = sum(us for _, _, us in record["gc"])
    assert (generation, pauses[0]["args"]["collected"],
            pauses[0]["dur"]) in record["gc"]
    assert record["self_us"]["host/gc"] == pytest.approx(in_call)
    assert record["self_us"]["outer"] == pytest.approx(
        outer["dur"] - in_call)


@pytest.mark.parametrize("held", ["tracer", "registry"])
def test_a_collection_under_a_lock_of_ours_does_not_deadlock(held):
    """The hook runs wherever an allocation triggers a collection, the
    tracer's ``_append_event`` under its (non-reentrant) lock included:
    it must take neither that lock nor the registry's."""
    import gc

    obs.enable()
    tracer = obs.get_tracer()
    lock = tracer._lock if held == "tracer" else obs.get_registry()._lock
    done = threading.Event()

    def collect_under_the_lock():
        with lock:
            gc.collect()
        done.set()

    worker = threading.Thread(target=collect_under_the_lock, daemon=True)
    worker.start()
    worker.join(timeout=20)
    assert done.is_set() and not worker.is_alive()
    # the pause is folded in at the next record
    with tracer.span("after"):
        pass
    names = [ev["name"] for ev in tracer.chrome_trace()["traceEvents"]]
    assert "host/gc" in names and names[-1] == "after"
    assert tracer.stats()["host/gc"]["count"] >= 1.0


def test_a_collection_inside_profile_step_is_a_profiler_annotation(
        tmp_path):
    import gc

    obs.enable()
    tracer = obs.get_tracer()
    with tracer.profile_step(str(tmp_path / "prof")):
        with tracer.span("inside/annotated"):
            gc.collect()
            jnp.ones((4,)).block_until_ready()
    gc.collect()                    # outside: a span, no annotation
    assert "host/gc" in _host_plane_names(tmp_path / "prof")
    assert tracer.stats()["host/gc"]["count"] >= 2.0


def test_with_obs_off_a_collection_leaves_nothing():
    import gc

    assert not obs.enabled()
    gc.collect()
    tracer = obs.get_tracer()
    assert tracer.chrome_trace()["traceEvents"] == []
    assert not tracer._pending
    assert obs.get_registry().get("nxd_host_gc_seconds_total") is None


@pytest.mark.parametrize("attrs,tracked", [
    ({}, False), ({"step": 7}, False),
    ({"step": 7, "kind": "overlapped", "compiled": False, "us": 1.5}, False),
    ({"ts": "collides", "step": 7}, True),         # nested: args is a dict
    ({"rows": [1, 2]}, True)])                      # it holds a container
def test_a_spans_event_is_not_tracked_by_the_collector(attrs, tracked):
    """The tracer holds tens of thousands of events through a serving run.
    One that is a dict of strings and numbers alone is invisible to the
    interpreter's collector; one that holds a dict is not, and enough of
    those bring on the generation-2 pass that the tracer is there to
    find. So a span's attributes lie flat in the held event, and
    ``chrome_trace`` nests them."""
    import gc

    tracer = SpanTracer()
    with tracer.span("outer"):
        with tracer.span("inner", **attrs):
            pass
    inner, outer = tracer._events
    assert gc.is_tracked(inner) is tracked and not gc.is_tracked(outer)
    exported = tracer.chrome_trace()["traceEvents"][0]
    assert exported["args"] == dict(attrs, parent="outer")
    assert set(exported) == {"name", "ph", "ts", "dur", "pid", "tid", "args"}
    assert exported is not inner
    assert tracer.step_records().get(7, {}).get("step") == attrs.get("step")


@pytest.mark.parametrize("case", ["nested", "since", "ring"])
def test_step_records_group_the_events_by_their_step(case):
    """``step_records`` is a view of the events the tracer holds: spans by
    their ``step``, self time by nesting, the other attributes by name."""
    tracer = SpanTracer(max_events=8 if case == "ring" else 1000)
    marks = []
    for step in (1, 2, 3):
        marks.append(time.perf_counter_ns() / 1000.0)
        with tracer.span("a", step=step):
            pass
        with tracer.span("b", step=step, rows=step * 10) as b:
            with tracer.span("b/child", step=step):
                time.sleep(0.002)
            b.set_attribute("late", True)
        with tracer.span("unnumbered"):
            pass
    since = marks[1] if case == "since" else 0.0
    records = tracer.step_records(since_us=since)
    want = {"nested": {1, 2, 3}, "since": {2, 3}, "ring": {2, 3}}[case]
    assert set(records) == want
    by_name = {}
    for ev in tracer.chrome_trace()["traceEvents"]:
        by_name.setdefault(ev["name"], []).append(ev)
    for step, rec in records.items():
        child = next(ev for ev in by_name["b/child"]
                     if ev["args"]["step"] == step)
        b = next(ev for ev in by_name["b"] if ev["args"]["step"] == step)
        assert set(rec["self_us"]) == {"a", "b", "b/child"}
        assert rec["self_us"]["b/child"] == pytest.approx(child["dur"])
        assert rec["self_us"]["b"] == pytest.approx(b["dur"] - child["dur"])
        assert rec["attrs"]["b"] == {"rows": step * 10, "late": True}
        assert rec["attrs"]["a"] == {} and rec["gc"] == []
        assert rec["return_us"] == pytest.approx(b["ts"] + b["dur"])
        assert rec["entry_us"] <= b["ts"] and rec["step"] == step


# ---------------------------------------------------------------------------
# a stop of the process: the witness thread, host/stopped spans
# ---------------------------------------------------------------------------


def _witnesses():
    return [t for t in threading.enumerate()
            if t.name == tracing.WITNESS_THREAD]


@pytest.mark.parametrize("switch", ["enable_disable", "enable_twice",
                                    "disable_twice", "reset_keeps_it"])
def test_the_witness_lives_exactly_while_obs_is_enabled(switch):
    tracer = obs.get_tracer()
    assert not obs.enabled() and _witnesses() == []
    assert tracer.thread_reading() is None
    obs.enable()
    witness, = _witnesses()
    assert witness.daemon and witness is tracer._witness[0]
    if switch == "enable_twice":
        obs.enable()
        assert _witnesses() == [witness]
    if switch == "reset_keeps_it":
        obs.reset()
        assert _witnesses() == [witness]
    # while it lives a thread can ask what it has done so far
    began = tracer.thread_reading()
    sum(range(200_000))
    did = obs_host.since(began, tracer.thread_reading())
    assert did["cpu_us"] > 0 and did["faults_major"] >= 0
    assert {"switches_voluntary", "switches_involuntary",
            "faults_minor"} <= set(did)
    obs.disable()
    assert _witnesses() == [] and not witness.is_alive()
    assert tracer.thread_reading() is None and tracer._meter is None
    if switch == "disable_twice":
        obs.disable()
        assert _witnesses() == []


@pytest.mark.parametrize("counted", [0, 3])
def test_a_meter_asks_only_a_kernel_that_counts_switches(counted,
                                                        monkeypatch):
    """A sandbox's kernel reads five zeros for 5.7 us: the thread that
    makes the meter looks once, and no reading asks again."""
    import resource

    real, calls = resource.getrusage, []

    def getrusage(who):
        calls.append(who)
        ru = list(real(who))
        ru[14] = ru[15] = counted   # ru_nvcsw, ru_nivcsw
        return resource.struct_rusage(ru)

    monkeypatch.setattr(resource, "getrusage", getrusage)
    meter = obs_host.ThreadMeter()
    try:
        began = meter.read()
        time.sleep(0.002)
        did = obs_host.since(began, meter.read())
    finally:
        meter.close()
    assert len(calls) == (3 if counted else 1)
    assert did["cpu_us"] >= 0 and did["switches_voluntary"] == 0
    assert meter._fds == {}


class _Wakes:
    """The clock and the sleep of a witness that wakes as often as the
    clock has readings left, and then finds ``stop`` set."""

    def __init__(self, ticks_ms):
        # the loop reads the clock once before its first sleep
        self.ticks = [int(t * 1e6) for t in ticks_ms]
        self.at = -1
        self.stop = threading.Event()

    def clock(self):
        self.at += 1
        return self.ticks[self.at]

    def sleep(self, seconds):
        assert seconds == pytest.approx(tracing.WITNESS_PERIOD_NS * 1e-9)
        if self.at == len(self.ticks) - 1:
            self.stop.set()


@pytest.mark.parametrize("ticks_ms,stops", [
    ([0, 10, 20, 30.2], []),                    # on time
    ([0, 10, 59, 69], []),                      # 39 ms late: no stop
    ([0, 10, 140, 150], [(20, 120)]),           # due at 20, woke at 140
    ([0, 10, 140, 150, 320.5], [(20, 120), (160, 160.5)])])
def test_a_late_wake_up_leaves_one_host_stopped_span(ticks_ms, stops,
                                                     monkeypatch):
    counts = iter(range(100))
    monkeypatch.setattr(obs_host.HostCounters, "read", lambda self: {
        "throttled": float(next(counts)), "pressure_us": 7.0})
    obs.get_registry().enable()
    tracer = SpanTracer()
    wakes = _Wakes(ticks_ms)
    tracer._witness_loop(wakes.stop, clock=wakes.clock, sleep=wakes.sleep)
    assert tracer._witness_seen_ns == wakes.ticks[-1]
    events = [ev for ev in tracer.chrome_trace()["traceEvents"]
              if ev["name"] == "host/stopped"]
    assert [(ev["ts"] * 1e-3, ev["dur"] * 1e-3) for ev in events] == [
        pytest.approx(stop) for stop in stops]
    # what the host's counters gained since the baseline, flat numbers
    assert [ev["args"] for ev in events] == [
        {"throttled": 1.0, "pressure_us": 0.0}] * len(stops)
    held = [ev for ev in tracer._events if ev["name"] == "host/stopped"]
    assert all("args" not in ev and not gc.is_tracked(ev) for ev in held)
    assert len({ev["tid"] for ev in events}) <= 1
    assert tracer.stopped_since(0.0) == held
    assert tracer.stopped_since(150e3) == held[1:]
    counter = obs.get_registry().get("nxd_host_stopped_seconds_total")
    if not stops:
        assert counter is None
    else:
        assert counter.value == pytest.approx(
            sum(ms for _, ms in stops) * 1e-3)
        assert tracer.stats()["host/stopped"]["count"] == len(stops)
    # a span closing later does not hide a stop that was folded in late
    with tracer.span("later", step=1):
        pass
    assert tracer.stopped_since(0.0) == held
    assert set(tracer.step_records()) == {1}


def test_with_the_tracer_off_a_late_wake_up_leaves_nothing():
    tracer = SpanTracer(enabled=False)
    wakes = _Wakes([0, 10, 140, 150])
    tracer._witness_loop(wakes.stop, clock=wakes.clock, sleep=wakes.sleep)
    assert not tracer._pending and tracer._events == []


_STOPPED_CHILD = """
import json, sys
from neuronx_distributed_tpu import obs
obs.enable()
print("ready", flush=True)
sys.stdin.readline()
stops = obs.get_tracer().stopped_since(0.0)
seconds = obs.get_registry().get("nxd_host_stopped_seconds_total")
print(json.dumps({"stops": [ev["dur"] * 1e-6 for ev in stops],
                  "keys": sorted(set().union(*stops)),
                  "seconds": seconds and seconds.value}), flush=True)
"""


@pytest.mark.skipif(not hasattr(signal, "SIGSTOP"),
                    reason="no SIGSTOP on this platform")
def test_a_process_frozen_by_sigstop_reports_one_stop():
    """The real thing: a child with obs on, frozen for 0.3 s and
    continued, holds one ``host/stopped`` span of that length."""
    child = subprocess.Popen(
        [sys.executable, "-c", _STOPPED_CHILD], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", NXD_OBS="0"))
    limit = threading.Timer(60.0, child.kill)   # the test's own time limit
    limit.start()
    try:
        assert child.stdout.readline().strip() == "ready"
        time.sleep(0.2)
        child.send_signal(signal.SIGSTOP)
        time.sleep(0.3)
        child.send_signal(signal.SIGCONT)
        time.sleep(0.2)
        out, _ = child.communicate("done\n")
    finally:
        limit.cancel()
        child.kill()
    assert child.returncode == 0
    got = json.loads(out.splitlines()[-1])
    # (a loaded machine may stop a child for 50 ms of its own accord)
    long = [s for s in got["stops"] if s >= 0.2]
    assert len(long) == 1 and 0.25 <= long[0] <= 0.45, got
    assert got["seconds"] == pytest.approx(sum(got["stops"]))
    assert {"name", "ts", "dur", "tid"} <= set(got["keys"])
    assert "args" not in got["keys"]


# ---------------------------------------------------------------------------
# compile tracking
# ---------------------------------------------------------------------------


def test_compile_tracker_counts_and_alerts_on_recompile():
    obs.enable()
    seen = []
    unsub = obs.subscribe(lambda ev, fields: seen.append((ev, fields)))
    try:
        fn = jax.jit(lambda x: x * 2)
        tracker = obs.CompileTracker.for_function("test/fn", fn)
        fn(jnp.ones((4,)))
        tracker.poll(wall_s=0.5)
        reg = obs.get_registry()
        assert obs.compile_events(reg) == 1.0
        assert reg.get("nxd_recompile_total") is None
        assert seen == []  # first compile is expected, no alert

        fn(jnp.ones((8,)))  # shape change forces a recompile
        tracker.poll(wall_s=0.7)
        assert obs.compile_events(reg) == 2.0
        recomp = reg.get("nxd_recompile_total")
        assert recomp.labels(site="test/fn").value == 1.0
        [(ev, fields)] = seen
        assert ev == "recompile_detected"
        assert fields["site"] == "test/fn" and fields["cache_size"] == 2
        # compile wall time attributed via the histogram
        hist = reg.get("nxd_compile_wall_seconds")
        assert hist.labels(site="test/fn").count == 2
    finally:
        unsub()


def test_compile_tracker_wrap_times_calls():
    obs.enable()
    fn = jax.jit(lambda x: x + 1)
    tracker = obs.CompileTracker.for_function("test/wrapped", fn,
                                              alert=False)
    wrapped = tracker.wrap(fn)
    wrapped(jnp.ones((3,)))
    wrapped(jnp.ones((3,)))  # cached: no new compile
    reg = obs.get_registry()
    assert reg.get("nxd_compile_total").labels(
        site="test/wrapped").value == 1.0


def test_cache_size_best_effort():
    assert obs.cache_size(lambda x: x) is None
    fn = jax.jit(lambda x: x)
    fn(jnp.ones((2,)))
    assert obs.cache_size(fn) == 1


# ---------------------------------------------------------------------------
# engine: compile-once with obs enabled, stats bridged
# ---------------------------------------------------------------------------


def test_engine_compile_once_with_obs_enabled():
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          ServingEngine)
    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)

    ps.initialize_model_parallel()
    obs.enable()
    cfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                      num_layers=2)
    params = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    ecfg = EngineConfig(block_size=4, num_blocks=16, max_slots=2,
                        max_blocks_per_seq=8, token_budget=8,
                        kv_dtype=jnp.float32)
    eng = ServingEngine(cfg, params, ecfg)
    rng = np.random.RandomState(0)
    for i in range(5):  # ragged mix: prompt lengths and budgets vary
        eng.submit(rng.randint(0, cfg.vocab_size,
                               (int(rng.randint(3, 8)),)).tolist(),
                   int(rng.randint(2, 6)), uid=f"r{i}")
    results = eng.run()
    assert all(r.status == "completed" for r in results.values())

    # the invariant the tracker makes observable: still exactly 1 compile
    assert eng.compile_count() == 1
    reg = obs.get_registry()
    assert obs.compile_events(reg) == 1.0
    assert reg.get("nxd_recompile_total") is None

    # EngineStats bridged into gauges + step latency histogram
    fields = {c.labels["field"]: c.value
              for c in reg.get("nxd_engine_stats").children()}
    assert fields["completed"] == 5.0
    assert fields["tokens_generated"] > 0.0
    assert reg.get("nxd_engine_pool_free_blocks").value >= 0.0
    assert reg.get("nxd_engine_step_seconds").count > 0

    # phase spans recorded on the process tracer
    names = set(obs.get_tracer().stats())
    assert {"engine/admission", "engine/packed",
            "engine/retirement"} <= names


def _tiny_engine(**engine_kw):
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          ServingEngine)
    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)

    ps.initialize_model_parallel()
    cfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                      num_layers=2)
    params = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    ecfg = EngineConfig(block_size=4, num_blocks=16, max_slots=2,
                        max_blocks_per_seq=8, token_budget=8,
                        kv_dtype=jnp.float32, **engine_kw)
    eng = ServingEngine(cfg, params, ecfg)
    rng = np.random.RandomState(0)
    for i, (n, new) in enumerate([(11, 3), (5, 4), (7, 2)]):
        eng.submit(rng.randint(0, cfg.vocab_size, (n,)).tolist(), new,
                   uid=f"r{i}")
    return eng


def _engine_spans(tracer):
    """The tracer's ``engine/`` events in the order they opened."""
    events = [ev for ev in tracer.chrome_trace()["traceEvents"]
              if ev["name"].startswith("engine/")]
    return sorted(events, key=lambda ev: (ev["ts"], -ev["dur"]))


def test_engine_packed_step_is_covered_by_flat_spans():
    obs.enable()
    eng = _tiny_engine()
    eng.step()                          # compiles; the second is the subject
    tracer = obs.get_tracer()
    tracer.reset()
    assert eng.step() > 0
    spans = _engine_spans(tracer)
    assert [ev["name"] for ev in spans] == [
        "engine/admission", "engine/cow", "engine/hygiene",
        "engine/tables", "engine/packed", "engine/packed/pack",
        "engine/packed/dispatch", "engine/packed/fetch",
        "engine/slices", "engine/retirement", "engine/publish"]
    parents = {ev["name"]: ev.get("args", {}).get("parent")
               for ev in spans}
    children = {n for n in parents if n.startswith("engine/packed/")}
    assert all(parents[n] == "engine/packed" for n in children)
    # flat otherwise: sched_ms_per_step reads the self time of admission,
    # cow and retirement, so nothing may open inside them
    assert all(parents[n] is None for n in parents if n not in children)
    packed = next(ev for ev in spans if ev["name"] == "engine/packed")
    inside = sum(ev["dur"] for ev in spans if ev["name"] in children)
    assert inside <= packed["dur"]


def test_engine_disaggregated_workers_name_children_by_parent():
    obs.enable()
    eng = _tiny_engine(disaggregated=True, prefill_budget=8)
    eng.run()
    names = set(obs.get_tracer().stats())
    for parent in ("engine/prefill", "engine/decode"):
        assert {parent, parent + "/pack", parent + "/dispatch",
                parent + "/fetch"} <= names
    assert not any(n.startswith("engine/packed") for n in names)
    by_parent = {}
    for ev in _engine_spans(obs.get_tracer()):
        by_parent.setdefault(ev.get("args", {}).get("parent"),
                             set()).add(ev["name"])
    assert by_parent["engine/decode"] == {
        "engine/decode/pack", "engine/decode/dispatch",
        "engine/decode/fetch"}
    # pad is each worker's width less its rows
    rows = {c.labels["kind"]: c.value for c in obs.get_registry().get(
        "nxd_engine_rows_total").children()}
    st = obs.get_tracer().stats()
    assert sum(rows.values()) == (
        st["engine/prefill"]["count"] * 8
        + st["engine/decode"]["count"] * eng.ecfg.max_slots)


def test_engine_rows_counter_sums_to_steps_times_width():
    obs.enable()
    eng = _tiny_engine()
    returned = []
    while eng.has_work():
        steps = eng.stats.steps
        n = eng.step()
        # a call that packs nothing lands the step in flight: it counts
        # no step and no row (its return value is the rows it landed)
        returned.append(n if eng.stats.steps > steps else 0)
    assert all(r.status == "completed" for r in eng.results.values())
    rows = {c.labels["kind"]: c.value for c in obs.get_registry().get(
        "nxd_engine_rows_total").children()}
    assert set(rows) == {"decode", "prefill", "pad"}
    steps = sum(1 for n in returned if n)
    assert steps == eng.stats.steps
    assert sum(rows.values()) == steps * eng.ecfg.token_budget
    assert rows["decode"] + rows["prefill"] == sum(returned)
    assert rows["prefill"] == 11 + 5 + 7    # every prompt token, once
    assert rows["pad"] > 0


def test_engine_paged_columns_counter_sums_to_rows_times_columns():
    """Each packed step's ``nxd_paged_columns_total`` children sum to the
    worker's width x ``max_blocks_per_seq``, and ``live`` is what
    :func:`column_live` counts over the rows the step packed (a pad row
    is handed the last slot's table row and attends none of it).
    ``nxd_paged_block_visits_total``'s children sum, a step, to that live
    count: every live (row, column) is either the one its tile's fetch is
    counted for or shares it. A pad row adds to neither counter."""
    from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
    from neuronx_distributed_tpu.ops.paged_attention import column_live

    obs.enable()
    eng = _tiny_engine()
    width, maxb = eng.ecfg.token_budget, eng.ecfg.max_blocks_per_seq
    packed = []
    run_worker = eng._dispatch

    def spy(fn, rows, *args):
        pads = [(-1, PAD_POSITION)] * (width - len(rows))
        live = [column_live(eng._tables[slot], np.arange(maxb), pos,
                            eng.ecfg.block_size)
                for slot, pos in [(r[0].slot, r[2]) for r in rows] + pads]
        assert not np.asarray(live[len(rows):]).any()   # pad rows: nothing
        # one tile (8 rows): a block is fetched once a (column, block)
        distinct = {(c, int(eng._tables[r[0].slot, c]))
                    for r, row in zip(rows, live) for c in np.flatnonzero(row)}
        packed.append((int(np.sum(live)), len(distinct)))
        return run_worker(fn, rows, *args)

    def read(name):
        return {c.labels["kind"]: c.value
                for c in obs.get_registry().get(name).children()}

    eng._dispatch = spy
    before = {"live": 0, "skipped": 0}
    visits = {"fetched": 0, "shared": 0}
    while eng.has_work():
        if not eng.step() or not packed:
            continue    # nothing ran, or the call only landed a step
        now, seen = read("nxd_paged_columns_total"), read(
            "nxd_paged_block_visits_total")
        live, fetched = packed.pop()
        assert now["live"] - before["live"] == live > 0
        assert sum(now.values()) - sum(before.values()) == width * maxb
        assert seen["fetched"] - visits["fetched"] == fetched
        assert sum(seen.values()) - sum(visits.values()) == live
        before, visits = now, seen
    assert before["skipped"] > before["live"]
    assert visits["shared"] > 0         # a prefill chunk's rows share blocks
    check_registered_counters(obs.get_registry(),
                              eng.model_cfg.serving_family())


def test_engine_paged_pairs_counters_split_the_fetched_pairs_by_kind():
    """``nxd_paged_pairs_total``'s two kinds and
    ``nxd_paged_shared_pairs_total`` are, a step, the ``fetched`` of
    ``nxd_paged_block_visits_total`` by the rows the kernel computes
    them over, and :func:`pair_kinds` of the step's own tables; no pair
    that one row names runs over the whole tile."""
    from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
    from neuronx_distributed_tpu.ops.paged_attention import (column_live,
                                                             pair_kinds)

    obs.enable()
    eng = _tiny_engine()
    mcfg, width = eng.model_cfg, eng.ecfg.token_budget
    n_rep = mcfg.num_heads // mcfg.num_kv_heads
    packed = []
    run_worker = eng._dispatch

    def spy(fn, rows, *args):
        tables = np.full((width, eng.ecfg.max_blocks_per_seq), -1)
        q_pos = np.full((width,), PAD_POSITION)
        for i, r in enumerate(rows):
            tables[i], q_pos[i] = eng._tables[r[0].slot], r[2]
        live = column_live(tables, np.arange(tables.shape[1]),
                           q_pos[:, None], eng.ecfg.block_size)
        packed.append(pair_kinds(np.where(live, tables, -1), n_rep,
                                 eng._pool_blocks))
        return run_worker(fn, rows, *args)

    def read():
        reg = obs.get_registry()
        kinds = {c.labels["kind"]: c.value
                 for c in reg.get("nxd_paged_pairs_total").children()}
        shared, = reg.get("nxd_paged_shared_pairs_total").children()
        fetched = {c.labels["kind"]: c.value for c in reg.get(
            "nxd_paged_block_visits_total").children()}["fetched"]
        return np.array([kinds["narrow"], kinds["one_row_whole"],
                         shared.value]), fetched

    eng._dispatch = spy
    before = (np.zeros(3), 0)
    while eng.has_work():
        if not eng.step() or not packed:
            continue    # nothing ran, or the call only landed a step
        now = read()
        np.testing.assert_array_equal(now[0] - before[0], packed.pop())
        assert now[0].sum() == now[1]
        before = now
    assert before[0][0] > 0 and before[0][1] == 0
    check_registered_counters(obs.get_registry(), mcfg.serving_family())


def test_engine_with_obs_off_records_no_span_and_no_rows_counter():
    assert not obs.enabled()
    eng = _tiny_engine()
    eng.run()
    assert eng.stats.steps > 0
    tracer = obs.get_tracer()
    assert tracer.chrome_trace()["traceEvents"] == []
    assert tracer.stats() == {}
    assert obs.get_registry().get("nxd_engine_rows_total") is None
    assert obs.get_registry().get("nxd_paged_columns_total") is None
    assert eng._obs_cache is None


# ---------------------------------------------------------------------------
# wire-byte counters vs the codec's arithmetic
# ---------------------------------------------------------------------------


def test_grad_wire_counters_match_codec_prediction():
    from neuronx_distributed_tpu.parallel import comm_compressed as cc
    from neuronx_distributed_tpu.parallel.wire_codec import (
        CompressionConfig, blockwise_wire_bytes)

    ps.initialize_model_parallel()
    mesh = ps.get_mesh()
    group = dict(mesh.shape).get("dp", 1) * dict(mesh.shape).get("cp", 1)
    assert group == 8
    obs.enable()
    cfg8 = cc.CompressionConfig(dtype="int8", block_size=256)
    elems = 4096
    x = jnp.ones((elems,), jnp.float32)

    def inner(v):
        return cc.all_reduce(v, ("dp", "cp"), config=cfg8, op="mean")

    fn = jax.jit(ps.shard_map(inner, mesh, in_specs=(P(),), out_specs=P()))
    jax.block_until_ready(fn(x))

    wire, raw = obs.wire_totals()
    # compressed all_reduce = quantized RS + AG: 2 wire passes
    predicted_wire = 2 * blockwise_wire_bytes(elems, cfg8)
    predicted_raw = 2 * 4.0 * elems
    assert wire == pytest.approx(predicted_wire, rel=0.05)
    assert raw == pytest.approx(predicted_raw, rel=0.05)

    measured = obs.wire_compression_ratio()
    predicted = 4.0 / CompressionConfig(
        dtype="int8", block_size=256).wire_bytes_per_element
    assert measured == pytest.approx(predicted, rel=0.05)

    kinds = {c.labels["collective"]
             for c in obs.get_registry().get(
                 "nxd_wire_bytes_total").children()}
    assert kinds == {"grad_all_reduce"}


def test_act_wire_counters_match_payload_prediction():
    from neuronx_distributed_tpu.ops import collective_matmul as cm
    from neuronx_distributed_tpu.parallel.wire_codec import (
        payload_wire_bytes)

    ps.initialize_model_parallel(tensor_model_parallel_size=8)
    mesh = ps.get_mesh()
    tp = dict(mesh.shape)["tp"]
    obs.enable()
    wire = cm.wire_config("int8")
    batch, seq, hidden, inter = 2, 64, 32, 64
    # global shapes; in_specs shard seq over tp, so the per-shard block
    # the taps see is (batch, seq // tp, hidden)
    x = jnp.ones((batch, seq, hidden), jnp.float32)
    wu = jnp.ones((hidden, inter // tp), jnp.float32) * 0.01
    wd = jnp.ones((inter // tp, hidden), jnp.float32) * 0.01

    def mlp(xv, wuv, wdv):
        h = cm.all_gather_matmul(xv, wuv, "tp", 1, impl="decomposed",
                                 wire=wire)
        return cm.matmul_reduce_scatter(h, wdv, "tp", 1,
                                        impl="decomposed", wire=wire)

    fn = jax.jit(ps.shard_map(
        mlp, mesh,
        in_specs=(P(None, "tp", None), P(None, "tp"), P("tp", None)),
        out_specs=P(None, "tp", None)))
    jax.block_until_ready(fn(x, wu, wd))

    vals = {c.labels["collective"]: c.value
            for c in obs.get_registry().get(
                "nxd_wire_bytes_total").children()}
    # AG ring: each rank's [b, s/tp, h] shard takes tp-1 hops
    pred_ag = payload_wire_bytes((batch, seq // tp, hidden),
                                 wire) * (tp - 1)
    # RS ring: per-hop payload is the output block with dim 1 cut by tp
    pred_rs = payload_wire_bytes((batch, seq // tp, hidden),
                                 wire) * (tp - 1)
    assert vals["act_all_gather_matmul"] == pytest.approx(pred_ag,
                                                          rel=0.05)
    assert vals["act_matmul_reduce_scatter"] == pytest.approx(pred_rs,
                                                              rel=0.05)
    assert obs.wire_compression_ratio() > 3.0  # int8 wire engaged


def test_wire_accounting_disabled_is_silent():
    from neuronx_distributed_tpu.parallel import comm_compressed as cc

    ps.initialize_model_parallel()
    mesh = ps.get_mesh()
    assert not obs.enabled()
    cfg8 = cc.CompressionConfig(dtype="int8", block_size=256)

    def inner(v):
        return cc.all_reduce(v, ("dp", "cp"), config=cfg8, op="mean")

    fn = jax.jit(ps.shard_map(inner, mesh, in_specs=(P(),), out_specs=P()))
    jax.block_until_ready(fn(jnp.ones((512,), jnp.float32)))
    assert obs.wire_totals() == (0.0, 0.0)
    assert obs.wire_compression_ratio() == 1.0


# ---------------------------------------------------------------------------
# event channel
# ---------------------------------------------------------------------------


def test_log_event_emits_line_and_counts():
    from neuronx_distributed_tpu.utils.logger import get_logger, log_event

    obs.enable()
    logger = get_logger("neuronx_distributed_tpu.test_obs_events")
    with _capture(logger) as lines:
        log_event(logger, "unit_test_event", detail=1, who="test")
    [line] = [ln for ln in lines if ln.startswith("NXD_EVENT ")]
    payload = json.loads(line.split(" ", 1)[1])
    assert payload == {"detail": 1, "event": "unit_test_event",
                       "who": "test"}
    counter = obs.get_registry().get("nxd_events_total")
    assert counter.labels(event="unit_test_event").value == 1.0


def test_log_event_line_survives_disabled_registry():
    from neuronx_distributed_tpu.utils.logger import get_logger, log_event

    assert not obs.enabled()
    logger = get_logger("neuronx_distributed_tpu.test_obs_events")
    with _capture(logger) as lines:
        log_event(logger, "disabled_mode_event")
    assert any(ln.startswith("NXD_EVENT ") for ln in lines)
    assert obs.get_registry().get("nxd_events_total") is None


def test_subscriber_fanout_and_unsubscribe():
    seen = []
    unsub = obs.subscribe(lambda ev, fields: seen.append((ev, fields)))
    try:
        obs.emit_event("sub_test", a=1)
    finally:
        unsub()
    obs.emit_event("sub_test", a=2)  # after unsubscribe: not delivered
    assert seen == [("sub_test", {"a": 1})]
    unsub()  # idempotent


def test_subscriber_exception_does_not_break_emit():
    def bad(ev, fields):
        raise RuntimeError("subscriber bug")

    seen = []
    unsub_bad = obs.subscribe(bad)
    unsub_ok = obs.subscribe(lambda ev, fields: seen.append(ev))
    try:
        obs.emit_event("resilient_event")
    finally:
        unsub_bad()
        unsub_ok()
    assert seen == ["resilient_event"]


# ---------------------------------------------------------------------------
# logger satellites
# ---------------------------------------------------------------------------


def test_bad_log_level_warns_once_per_value(monkeypatch):
    from neuronx_distributed_tpu.utils import logger as lg

    pkg_logger = logging.getLogger("neuronx_distributed_tpu")
    monkeypatch.setenv("NXD_LOG_LEVEL", "VERBOSE")
    lg._WARNED_BAD_LEVELS.discard("VERBOSE")
    lg._WARNED_BAD_LEVELS.discard("NOPE")
    with _capture(pkg_logger) as lines:
        assert lg.get_log_level() == logging.INFO
        assert lg.get_log_level() == logging.INFO  # second call: silent
        monkeypatch.setenv("NXD_LOG_LEVEL", "NOPE")
        assert lg.get_log_level() == logging.INFO  # new value warns again
    warnings = [ln for ln in lines if "NXD_LOG_LEVEL" in ln]
    assert len(warnings) == 2
    assert "'VERBOSE'" in warnings[0] and "'NOPE'" in warnings[1]


def test_non_level_attribute_rejected(monkeypatch):
    # getattr(logging, ...) lookups that hit non-level attributes must not
    # leak through as "levels"
    from neuronx_distributed_tpu.utils import logger as lg

    monkeypatch.setenv("NXD_LOG_LEVEL", "raiseExceptions")  # bool attr
    lg._WARNED_BAD_LEVELS.discard("raiseExceptions")
    assert lg.get_log_level() == logging.INFO


def test_get_logger_tracks_env_level_changes(monkeypatch):
    from neuronx_distributed_tpu.utils.logger import get_logger

    monkeypatch.setenv("NXD_LOG_LEVEL", "INFO")
    lgr = get_logger("neuronx_distributed_tpu.test_obs_level")
    assert lgr.level == logging.INFO
    monkeypatch.setenv("NXD_LOG_LEVEL", "DEBUG")
    assert get_logger(
        "neuronx_distributed_tpu.test_obs_level").level == logging.DEBUG
    monkeypatch.setenv("NXD_LOG_LEVEL", "warning")  # case-insensitive
    assert get_logger(
        "neuronx_distributed_tpu.test_obs_level").level == logging.WARNING


# ---------------------------------------------------------------------------
# the single enable switch
# ---------------------------------------------------------------------------


def test_enable_disable_govern_registry_and_tracer():
    assert not obs.enabled()
    obs.enable()
    assert obs.enabled()
    assert obs.get_registry().enabled and obs.get_tracer().enabled
    obs.get_registry().counter("nxd_probe_total").inc()
    with obs.get_tracer().span("probe"):
        pass
    obs.disable()
    assert not obs.get_registry().enabled
    assert not obs.get_tracer().enabled
    obs.get_registry().counter("nxd_probe_total").inc(100)  # no-op now
    assert obs.get_registry().get("nxd_probe_total").value == 1.0
    assert obs.get_tracer().stats()["probe"]["count"] == 1.0
