"""The metrics that read the engine's host-phase spans and its row counter,
rehearsed on the CPU: ``pytest benchmarks/tests``. The manifest that lists
them for the rehearsal cell is built in ``tmp_path``; no file that is
there is edited."""

from __future__ import annotations

import json
import os

import pytest

from test_benchmark import BENCH, HERE, _last_json, _run, harness

BATCH_METRICS = ("host_ms_per_step.batch", "launch_ms_per_step.batch",
                 "device_wait_ms_per_step.batch",
                 "prefill_row_share_pct.batch")
CELL = "tiny-mixtral.serve-batch"
ROWS_SPEC = {"counter": "nxd_engine_rows_total", "label": "kind",
             "numerator": ["prefill"]}


def test_rehearsal_prints_the_span_and_counter_metrics(tmp_path):
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = {x["name"]: x for x in harness.load_manifest()["per_layer"]}
    for name in BATCH_METRICS:
        m["per_layer"].append(dict(real[name], workloads=[CELL]))
    manifest = tmp_path / "manifest.json"
    manifest.write_text(json.dumps(m))
    p = _run(["--manifest", str(manifest), "--workload", CELL, "--seed",
              str(2 ** 31 + 11), "--seconds", "1.5", "--trace", "1"])
    assert p.returncode == 0, p.stderr[-2000:]
    line = _last_json(p.stdout)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in BATCH_METRICS:
        assert got["rehearsal." + name] > 0.0, name
    # launch is a part of host; the fetch is in neither
    assert got["rehearsal.launch_ms_per_step.batch"] \
        < got["rehearsal.host_ms_per_step.batch"]
    assert 0.0 < got["rehearsal.prefill_row_share_pct.batch"] < 100.0
    # what the scheduler's metric reads is untouched by the new spans
    assert got["rehearsal.sched_ms_per_step.batch"] \
        < got["rehearsal.host_ms_per_step.batch"]


def test_the_host_metric_takes_every_span_of_a_step_but_the_fetch():
    spec = {n: harness.read_json(os.path.join(
        BENCH, "layer_metrics", n + ".json"))["reader"]
        for n in BATCH_METRICS[:3]}
    host, launch, wait = (set(spec[n]["spans"]) for n in BATCH_METRICS[:3])
    assert wait == {"engine/packed/fetch"} and not wait & host
    assert launch < host
    sched = set(harness.read_json(os.path.join(
        BENCH, "layer_metrics", "sched_ms_per_step.batch.json"))
        ["reader"]["spans"])
    assert sched < host and not sched & launch
    reader = harness.load_plugin("readers", "span_self_per_step")
    obs = harness.Observations(
        config={}, peaks=None, chips=1, steps=4,
        span_self_s={n: 0.001 for n in host | wait})
    assert reader.read(spec[BATCH_METRICS[0]], obs) == pytest.approx(
        len(host) * 0.25)
    assert reader.read(spec[BATCH_METRICS[2]], obs) == pytest.approx(0.25)


@pytest.fixture
def program_registry():
    from neuronx_distributed_tpu import obs

    was = obs.enabled()
    obs.reset()
    yield obs.get_registry()
    obs.reset()
    (obs.enable if was else obs.disable)()


def test_counter_share_of_an_empty_registry_is_none(program_registry):
    reader = harness.load_plugin("readers", "counter_share")
    empty = harness.Observations(config={}, peaks=None, chips=1)
    assert reader.read(ROWS_SPEC, empty) is None
    # a counter that is there and never counted is nothing to read either
    program_registry.enable()
    program_registry.counter("nxd_engine_rows_total", labels=("kind",)
                             ).labels(kind="prefill")
    assert reader.read(ROWS_SPEC, empty) is None


def test_counter_share_is_the_numerator_over_all_children(program_registry):
    reader = harness.load_plugin("readers", "counter_share")
    program_registry.enable()
    rows = program_registry.counter("nxd_engine_rows_total",
                                    labels=("kind",))
    for kind, n in (("decode", 23), ("prefill", 100), ("pad", 5)):
        rows.labels(kind=kind).inc(n)
    empty = harness.Observations(config={}, peaks=None, chips=1)
    assert reader.read(ROWS_SPEC, empty) == pytest.approx(100 * 100 / 128)
    both = dict(ROWS_SPEC, numerator=["prefill", "decode"])
    assert reader.read(both, empty) == pytest.approx(100 * 123 / 128)
