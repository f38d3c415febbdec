"""The three metrics that read ``nxd_engine_step_wall_seconds_total{where}``
(a stalled step accounted by cause), rehearsed on the CPU with a pause
injected from outside the program: ``pytest benchmarks/tests``. The
manifest that lists them for the rehearsal cell is built in ``tmp_path``;
no file that is there is edited."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

from manifest_checks import file_holds_entry
from test_benchmark import BENCH, HERE, ROOT, _last_json, harness

STALL_METRICS = {
    "stall_loss_pct.batch": ["host_pause", "device", "transfer", "compile",
                             "host"],
    "stall_host_pause_pct.batch": ["host_pause"],
    "stall_device_pct.batch": ["device"]}
BATCH = "tiny-mixtral.serve-batch"
#: the same rehearsal configuration under a mix of decode rows, whose steps
#: on the CPU are of one length (a prefill chunk's are not)
CELL = "tiny-mixtral.serve-longgen"
TRAIN = "mistral-7b.train-tp4"
EVERY, PAUSE_S = 25, 0.06

#: ``benchmarks/run.py`` with every 25th enqueue of the engine held up for
#: 60 ms before its spans open: a stall that no span names, so ``host``
PAUSED_RUN = f"""
import runpy, sys, time
from neuronx_distributed_tpu.inference import engine
dispatch, calls = engine.ServingEngine._dispatch, [0]
def paused(self, *args):
    calls[0] += 1
    if calls[0] % {EVERY} == 0:
        time.sleep({PAUSE_S})
    return dispatch(self, *args)
engine.ServingEngine._dispatch = paused
sys.argv[0] = sys.argv[1]
del sys.argv[1]
runpy.run_path(sys.argv[0], run_name="__main__")
"""


@pytest.mark.parametrize("name", sorted(STALL_METRICS))
def test_a_stall_metric_is_a_share_of_the_wall_counter(name):
    spec = harness.read_json(os.path.join(BENCH, "layer_metrics",
                                          name + ".json"))
    assert spec["reader"] == {
        "kind": "counter_share",
        "counter": "nxd_engine_step_wall_seconds_total", "label": "where",
        "numerator": STALL_METRICS[name]}
    assert (spec["layer"], spec["unit"], spec["better"], spec["source"],
            spec["moves"]) == ("server", "%", "lower", "program_counter",
                               "serve_tok_s")
    manifest = harness.load_manifest()
    entry = harness.by_name(manifest["per_layer"], name, "metric")
    serve = [w["name"] for w in manifest["workloads"] if w["name"] != TRAIN]
    assert serve and sorted(entry["workloads"]) == sorted(serve)
    assert TRAIN not in entry["workloads"]
    assert file_holds_entry(spec, entry)


def test_the_share_is_over_every_child_steady_among_them():
    from neuronx_distributed_tpu import obs

    was = obs.enabled()
    obs.reset()
    obs.enable()
    try:
        wall = obs.get_registry().counter(
            "nxd_engine_step_wall_seconds_total", labels=("where",))
        for where, s in (("steady", 9.0), ("host", 0.5), ("device", 0.3),
                         ("host_pause", 0.2)):
            wall.labels(where=where).inc(s)
        reader = harness.load_plugin("readers", "counter_share")
        got = {name: reader.read(harness.read_json(os.path.join(
            BENCH, "layer_metrics", name + ".json"))["reader"], None)
            for name in STALL_METRICS}
    finally:
        obs.reset()
        (obs.enable if was else obs.disable)()
    assert got == {"stall_loss_pct.batch": pytest.approx(10.0),
                   "stall_host_pause_pct.batch": pytest.approx(2.0),
                   "stall_device_pct.batch": pytest.approx(3.0)}


def _rehearse(tmp_path, script, seconds):
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = {x["name"]: x for x in harness.load_manifest()["per_layer"]}
    # the traced seconds cut short: the registry counts on through them,
    # and under the profiler a CPU step is another length
    entry = harness.by_name(m["configs"], "tiny-mixtral", "configuration")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    config["serve"]["trace_seconds"] = 0.3
    (tmp_path / "config.json").write_text(json.dumps(config))
    m["configs"].append(dict(entry, name="tiny-mixtral-short-trace",
                             file=str(tmp_path / "config.json")))
    m["workloads"].append(dict(
        harness.by_name(m["workloads"], BATCH, "workload"), name=CELL,
        config="tiny-mixtral-short-trace", traffic="tiny-longgen"))
    for metric in m["end_to_end"] + m["per_layer"]:
        if BATCH in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    for name in STALL_METRICS:
        m["per_layer"].append(dict(real[name], workloads=[CELL]))
    manifest = tmp_path / "manifest.json"
    manifest.write_text(json.dumps(m))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, *script, os.path.join(BENCH, "run.py"),
         "--manifest", str(manifest), "--workload", CELL, "--seed",
         str(2 ** 31 + 50), "--seconds", str(seconds), "--trace", "1"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = _last_json(p.stdout)
    assert line["correct"] is True
    window = dict(kv.split("=") for kv in next(
        ln for ln in p.stdout.splitlines()
        if ln.startswith("[window]")).split()[1:])
    slow = [json.loads(ln.split("NXD_EVENT ", 1)[1])
            for ln in p.stderr.splitlines() if '"event": "slow_step"' in ln]
    return ({k: v["value"] for k, v in line["metrics"].items()},
            {k: float(v) for k, v in window.items()}, slow)


def test_a_rehearsal_with_an_injected_pause_reads_the_loss(tmp_path):
    got, window, slow = _rehearse(tmp_path, ["-c", PAUSED_RUN], 4)
    loss = got["rehearsal.stall_loss_pct.batch"]
    assert loss > 0
    # what the [window] line shows from outside: a step in 25 of its steps
    # is paused, and loses what a paused call's event says it lost. (The
    # CPU makes a few other calls slow too, the lead-in's among them:
    # their events' excess is the room above.)
    assert window["steps_over_twice_p50"] >= window["steps"] // EVERY >= 1
    assert window["step_ms_max"] > max(3 * window["step_ms_p50"],
                                       PAUSE_S * 1e3)
    excess = [e["wall_ms"] - e["median_ms"] for e in slow]
    paused = sorted(x for x in excess if x >= PAUSE_S * 1e3)
    others = sum(x for x in excess if x < PAUSE_S * 1e3)
    outside = 100.0 * window["steps"] / EVERY * paused[len(paused) // 2] \
        * 1e-3 / window["seconds"]
    assert 0.9 * outside <= loss <= 1.1 * (
        outside + 100.0 * others * 1e-3 / window["seconds"])
    # a pause before the spans open is the host's, and no collection's
    # (the "device" of a rehearsal is the CPU the test shares: the calls
    # it makes slow wait in the fetch)
    assert got["rehearsal.stall_host_pause_pct.batch"] < 0.1 * loss
    assert got["rehearsal.stall_device_pct.batch"] < 0.3 * loss
    # one event a paused call, in the run's output, the lead-in's too
    paused = [e for e in slow if e["wall_ms"] > PAUSE_S * 1e3]
    assert len(paused) >= window["steps"] // EVERY
    # nine in ten: the rehearsal's "device" is a shared machine's CPU, and
    # now and then a paused call waits longer still in its fetch (1 run in
    # 3 to 18 held such an event, at the parent of PR 66 as on its tree)
    held = [e for e in paused
            if max(e["split_ms"], key=e["split_ms"].get) == "host"
            and (e["call_before"] or {}).get("kind") == "overlapped"]
    assert len(held) >= 0.9 * len(paused)


def test_a_rehearsal_nobody_paused_prints_the_three_metrics(tmp_path):
    got, window, slow = _rehearse(tmp_path, [], 1.5)
    for name in STALL_METRICS:
        assert 0.0 <= got["rehearsal." + name] <= 100.0
    if not slow:
        assert got["rehearsal.stall_loss_pct.batch"] == 0.0
    assert got["rehearsal.stall_loss_pct.batch"] >= max(
        got["rehearsal.stall_host_pause_pct.batch"],
        got["rehearsal.stall_device_pct.batch"])
    assert re.fullmatch(r"[0-9.]+", str(window["step_ms_max"]))
