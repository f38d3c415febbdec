"""The three metrics that say why a stalled step was lost (PR 69):
``stall_stopped_pct.batch`` and ``stall_cpu_wait_pct.batch`` read
``nxd_engine_stall_cause_seconds_total{cause}``, ``idle_stopped_pct.batch``
lays the tracer's ``host/stopped`` spans over the device's idle time.
Rehearsed on the CPU with the process frozen from outside
(``SIGSTOP``): ``pytest benchmarks/tests``. ``test_stall_metrics.py``'s
rehearsal is used as it stands, with these metrics added to its list."""

from __future__ import annotations

import os
import signal

import pytest

import test_stall_metrics
from manifest_checks import file_holds_entry, stand_together
from test_benchmark import BENCH, harness, xplane

CAUSE_METRICS = {"stall_stopped_pct.batch": ["process_stopped"],
                 "stall_cpu_wait_pct.batch": ["cpu_wait"]}
IDLE_METRIC = "idle_stopped_pct.batch"
TRAIN = test_stall_metrics.TRAIN
EVERY_S, FROZEN_S = 0.6, 0.15

#: ``benchmarks/run.py`` beside a child that freezes it for 150 ms every
#: 0.6 s for as long as it lives: nothing of the process runs meanwhile
FROZEN_RUN = f"""
import atexit, os, runpy, subprocess, sys
freezer = '''
import os, signal, sys, time
pid = int(sys.argv[1])
while os.getppid() == pid:              # an orphan has nobody to freeze
    time.sleep({EVERY_S})
    os.kill(pid, signal.SIGSTOP)
    time.sleep({FROZEN_S})
    os.kill(pid, signal.SIGCONT)
'''
# (its own output nowhere: a pipe of ours that it held would never close)
atexit.register(subprocess.Popen(
    [sys.executable, "-c", freezer, str(os.getpid())],
    stdin=subprocess.DEVNULL, stdout=subprocess.DEVNULL,
    stderr=subprocess.DEVNULL).kill)
sys.argv[0] = sys.argv[1]
del sys.argv[1]
runpy.run_path(sys.argv[0], run_name="__main__")
"""


def _spec(name):
    return harness.read_json(os.path.join(BENCH, "layer_metrics",
                                          name + ".json"))


@pytest.mark.parametrize("name", sorted(CAUSE_METRICS) + [IDLE_METRIC])
def test_a_cause_metric_keeps_its_form(name):
    spec = _spec(name)
    if name == IDLE_METRIC:
        assert spec["reader"] == {"kind": "idle_under_span",
                                  "spans": ["host/stopped"]}
        assert spec["source"] == "device_trace"
    else:
        assert spec["reader"] == {
            "kind": "counter_share",
            "counter": "nxd_engine_stall_cause_seconds_total",
            "label": "cause", "numerator": CAUSE_METRICS[name]}
        assert spec["source"] == "program_counter"
    assert (spec["layer"], spec["unit"], spec["better"], spec["moves"]) == (
        "server", "%", "lower", "serve_tok_s")
    manifest = harness.load_manifest()
    entry = harness.by_name(manifest["per_layer"], name, "metric")
    loss = harness.by_name(manifest["per_layer"], "stall_loss_pct.batch",
                           "metric")
    # wherever the place of a stall is read, its cause is
    assert TRAIN not in entry["workloads"]
    assert sorted(entry["workloads"]) == sorted(loss["workloads"])
    assert file_holds_entry(spec, entry)
    assert stand_together(manifest, ["stall_stopped_pct.batch",
                                     "stall_cpu_wait_pct.batch",
                                     IDLE_METRIC])
    assert harness.load_plugin("readers", spec["reader"]["kind"]).read


def test_the_old_metrics_read_what_they_read():
    for name, children in test_stall_metrics.STALL_METRICS.items():
        assert _spec(name)["reader"] == {
            "kind": "counter_share",
            "counter": "nxd_engine_step_wall_seconds_total",
            "label": "where", "numerator": children}


def test_the_cause_shares_are_over_the_cause_counter():
    from neuronx_distributed_tpu import obs

    was = obs.enabled()
    obs.reset()
    obs.enable()
    try:
        reader = harness.load_plugin("readers", "counter_share")
        args = {name: _spec(name)["reader"] for name in CAUSE_METRICS}
        # a program without the counter leaves the metrics out
        assert [reader.read(a, None) for a in args.values()] == [None, None]
        cause = obs.get_registry().counter(
            "nxd_engine_stall_cause_seconds_total", labels=("cause",))
        for why, s in (("steady", 9.0), ("process_stopped", 0.7),
                       ("cpu_wait", 0.1), ("other", 0.2)):
            cause.labels(cause=why).inc(s)
        got = {name: reader.read(a, None) for name, a in args.items()}
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()
    assert got == {"stall_stopped_pct.batch": pytest.approx(7.0),
                   "stall_cpu_wait_pct.batch": pytest.approx(1.0)}


def _rehearse(tmp_path, script, seconds, monkeypatch, cell):
    """``test_stall_metrics``' rehearsal with these metrics listed too, as
    a cell of its own name: a run empties ``benchmarks/out/<cell>``, and
    another worker may be rehearsing beside this one."""
    monkeypatch.setattr(test_stall_metrics, "STALL_METRICS", {
        **test_stall_metrics.STALL_METRICS, **CAUSE_METRICS,
        IDLE_METRIC: None})
    monkeypatch.setattr(test_stall_metrics, "CELL", cell)
    return test_stall_metrics._rehearse(tmp_path, script, seconds)


@pytest.mark.skipif(not hasattr(signal, "SIGSTOP"),
                    reason="no SIGSTOP on this platform")
def test_a_rehearsal_frozen_from_outside_reads_the_stops(tmp_path,
                                                         monkeypatch):
    got, window, slow = _rehearse(tmp_path, ["-c", FROZEN_RUN], 4,
                                  monkeypatch, "tiny-mixtral.serve-frozen")
    loss = got["rehearsal.stall_loss_pct.batch"]
    stopped = got["rehearsal.stall_stopped_pct.batch"]
    # what the [window] line shows from outside: a step in every 0.6 s of
    # it is as long as the freeze, and the loss is that much of the window
    assert window["step_ms_max"] > 0.9 * FROZEN_S * 1e3
    frozen = 100.0 * (window["seconds"] // (EVERY_S + FROZEN_S)) \
        * FROZEN_S / window["seconds"]
    # (the rehearsal's "device" is a shared machine's CPU, which makes
    # calls slow of its own accord: those are the loss's other half at most)
    assert stopped > 0.5 * frozen and 0.5 * loss <= stopped <= loss
    assert got["rehearsal.stall_cpu_wait_pct.batch"] <= loss - stopped + 1e-9
    # the old three name the place the stepping thread was frozen in
    assert got["rehearsal.stall_host_pause_pct.batch"] < 0.1 * loss
    # one event a frozen call, each with the witness's stop inside it; a
    # call that was frozen between two of its spans holds the stop too
    held = [e for e in slow if e["stopped_ms"] > 0.5 * FROZEN_S * 1e3]
    assert len(held) >= window["seconds"] // (EVERY_S + FROZEN_S)
    for e in held:
        assert sum(e["cause_ms"].values()) == pytest.approx(
            e["wall_ms"] - e["median_ms"], abs=0.01)
        assert e["stops"]
        assert max(e["cause_ms"], key=e["cause_ms"].get) == "process_stopped"
        assert e["cpu_ms"] < e["wall_ms"] - 0.5 * FROZEN_S * 1e3
        assert "memory" not in e
    # no device plane on the CPU: nothing to lay the spans over
    assert "rehearsal." + IDLE_METRIC not in got


def test_a_rehearsal_nobody_stopped_prints_zero(tmp_path, monkeypatch):
    got, window, slow = _rehearse(tmp_path, [], 1.5, monkeypatch,
                                  "tiny-mixtral.serve-unfrozen")
    loss = got["rehearsal.stall_loss_pct.batch"]
    stopped = got["rehearsal.stall_stopped_pct.batch"]
    waited = got["rehearsal.stall_cpu_wait_pct.batch"]
    assert 0.0 <= stopped + waited <= loss + 1e-9
    # (a shared machine may stop a rehearsal of its own accord: the event
    # then says so)
    if not any(e["stops"] for e in slow):
        assert stopped == 0.0
    if not any(e["cpu_wait_ms"] for e in slow):
        assert waited == 0.0


# -- the device's side ---------------------------------------------------------

def _traced(tracer, idle, spans, shift_s=-7.25):
    """A trace whose one device ran all of ``[10, 11)`` s but ``idle``,
    beside a tracer whose clock is ``shift_s`` off the trace's and which
    holds two ``engine/`` spans the runner placed and ``spans``."""
    ops = [xplane.Event("fusion.1", a, b) for a, b in
           zip([10.0] + [hi for _, hi in idle], [lo for lo, _ in idle] + [11.0])]
    trace = xplane.Trace({0: xplane.DeviceTimeline(ops=ops)}, [], 0.0)
    pid_tid = {"ph": "X", "pid": 1, "tid": 1}
    for name, start, dur in (("engine/packed", 10.1, 0.0313),
                             ("engine/packed/fetch", 10.105, 0.0207),
                             ("engine/packed", 10.2, 0.0311)):
        tracer._append_event(dict(pid_tid, name=name, dur=dur * 1e6,
                                  ts=(start - shift_s) * 1e6, step=1))
        trace.annotations.append(xplane.Event(name, start, start + dur))
    for name, start, end in spans:
        tracer._append_event(dict(pid_tid, name=name, dur=(end - start) * 1e6,
                                  ts=(start - shift_s) * 1e6))
    obs = harness.Observations(config={}, peaks={}, chips=1, trace=trace)
    obs.reduction = xplane.reduce(trace, (10.0, 11.0))
    return obs


@pytest.mark.parametrize("idle,spans,share", [
    # one gap of 100 ms, its second half under a stop of the process
    ([(10.4, 10.5)], [("host/stopped", 10.45, 10.56)], 50.0),
    # two gaps, one wholly under two stops that overlap, one bare
    ([(10.3, 10.35), (10.6, 10.75)],
     [("host/stopped", 10.29, 10.33), ("host/stopped", 10.32, 10.36)], 25.0),
    # spans of other names cover nothing; no stop reads 0, not nothing
    ([(10.4, 10.5)], [("host/gc", 10.4, 10.5)], 0.0),
    ([(10.4, 10.5)], [], 0.0)])
def test_idle_under_span_is_the_covered_share_of_the_idle(idle, spans,
                                                          share, monkeypatch):
    import neuronx_distributed_tpu.obs as program_obs
    from neuronx_distributed_tpu.obs.tracing import SpanTracer

    reader = harness.load_plugin("readers", "idle_under_span")
    tracer = SpanTracer()
    obs = _traced(tracer, idle, spans)
    assert reader.shift_of(obs.trace.annotations, tracer._events) \
        == pytest.approx(-7.25, abs=1e-6)
    monkeypatch.setattr(program_obs, "get_tracer", lambda: tracer)
    assert reader.read(_spec(IDLE_METRIC)["reader"], obs) == pytest.approx(
        share, abs=1e-3)


@pytest.mark.parametrize("missing", ["trace", "pair", "witness"])
def test_idle_under_span_finds_nothing_to_read(missing, monkeypatch):
    import neuronx_distributed_tpu.obs as program_obs
    from neuronx_distributed_tpu.obs.tracing import SpanTracer

    reader = harness.load_plugin("readers", "idle_under_span")
    tracer = SpanTracer()
    obs = _traced(tracer, [(10.4, 10.5)], [("host/stopped", 10.4, 10.5)])
    if missing == "trace":              # a rehearsal: no device plane
        obs.trace = obs.reduction = None
    elif missing == "pair":             # no engine/ span on both clocks
        obs.trace.annotations.clear()
    else:                               # the parent's tracer: no witness
        monkeypatch.delattr(SpanTracer, "watch_host")
    monkeypatch.setattr(program_obs, "get_tracer", lambda: tracer)
    assert reader.read(_spec(IDLE_METRIC)["reader"], obs) is None
