"""The benchmark's own tests, on the CPU: ``pytest benchmarks/tests``.

Not part of the repository's tier-1 suite (which collects ``tests/``).
The runners are rehearsed at toy sizes through
``benchmarks/tests/manifest.json``; nothing here is a measurement.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import harness  # noqa: E402
from generators import requests as gen_requests  # noqa: E402
from generators import token_batches  # noqa: E402
from tracereduce import xplane  # noqa: E402

MANIFESTS = [harness.MANIFEST, os.path.join(HERE, "manifest.json")]
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _run(args, env=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=timeout)


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


# -- the manifest -------------------------------------------------------------

@pytest.mark.parametrize("path", MANIFESTS)
def test_manifest_keeps_the_contract(path):
    m = harness.load_manifest(path)
    assert set(m) == TOP_KEYS
    assert os.path.getsize(path) <= 64 * 1024
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert all(not w.startswith("/") and ".." not in w for w in m["command"])
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[g]]
    for n in names:
        assert harness.NAME.match(n), n
    for g in ("configs", "workloads"):
        assert len({x["name"] for x in m[g]}) == len(m[g])
    metric_names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        assert 1 <= len(c["why"]) <= 200 and len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in m["workloads"])
    cells = {w["name"] for w in m["workloads"]}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert harness.NAME.match(w["traffic"])
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert harness.UNIT.match(x["unit"]) and 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
        assert x["better"] in ("lower", "higher")
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert harness.UNIT.match(x["unit"]) and x["source"] in SOURCES
        assert x["moves"] in e2e and x["better"] in ("lower", "higher")
        moved = e2e[x["moves"]]
        for cell in x.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (x["name"], cell)
    for cell in cells:
        assert len(harness.metrics_of(m, "end_to_end", cell)) >= 2
        assert len(harness.metrics_of(m, "per_layer", cell)) >= 1


@pytest.mark.parametrize("path", MANIFESTS)
def test_every_name_resolves_to_its_files(path):
    sys.path.insert(0, BENCH)
    import run as bench_run

    m = harness.load_manifest(path)
    for w in m["workloads"]:
        cell = bench_run.load_cell(path, w["name"])
        assert cell.config["runner"] in ("serve", "train")
        assert cell.config["chips"] == w["chips"]
        harness.load_plugin("runners", cell.config["runner"]).run
        gen = harness.load_plugin("generators", cell.traffic["kind"])
        assert callable(gen.generate)
    for x in m["per_layer"]:
        spec = harness.read_json(harness.data_file("layer_metrics", x["name"]))
        for k in ("name", "layer", "unit", "better", "source", "moves"):
            assert spec[k] == x[k], (x["name"], k)
        assert callable(harness.load_plugin(
            "readers", spec["reader"]["kind"]).read)


def test_configurations_cut_depth_only():
    m = harness.load_manifest()
    for c in m["configs"]:
        cfg = harness.read_json(os.path.join(ROOT, c["file"]))
        assert c["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
        assert cfg["source"] == c["source"] and "stands_for" in cfg
        assert "assumed" in cfg and not cfg.get("rehearsal")
        # the published widths of both models
        assert (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (
                    4096, 14336, 32, 8)
        assert cfg["reduced"]["num_hidden_layers"]["to"] == \
            cfg["num_hidden_layers"]
    peaks = harness.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v4")


# -- generators -----------------------------------------------------------------

def test_request_lengths_match_the_stated_distribution():
    chat = harness.read_json(harness.data_file("traffic", "chat"))
    offline = harness.read_json(harness.data_file("traffic", "offline-chat"))
    assert chat["prompt_tokens"] == offline["prompt_tokens"]
    assert chat["answer_tokens"] == offline["answer_tokens"]
    p = gen_requests.lengths(chat["prompt_tokens"], 1000)
    a = gen_requests.lengths(chat["answer_tokens"], 1000)
    assert abs(np.median(p) - 384) <= 2 and abs(np.median(a) - 96) <= 1
    assert p.min() == 32 and p.max() == 1920
    assert a.min() >= 8 and a.max() == 384
    assert p.max() + a.max() <= 20 * 128          # max_blocks_per_seq
    sigma = np.std(np.log(p[(p > 32) & (p < 1920)]))
    assert 0.6 < sigma < 0.8                      # 0.8, less the clipped tails


def test_requests_are_a_fixed_set_in_a_seeded_order():
    mix = harness.read_json(harness.data_file("traffic", "chat"))
    a = gen_requests.generate(mix, 7, 32000, 30)
    b = gen_requests.generate(mix, 7, 32000, 30)
    c = gen_requests.generate(mix, 2 ** 31 + 11, 32000, 30)
    key = lambda g: [(r.arrival_s, r.prompt, r.max_new_tokens)
                     for r in g["requests"]]
    assert key(a) == key(b) and key(a) != key(c)
    lens = lambda g: sorted(len(r.prompt) for r in g["requests"])
    outs = lambda g: sorted(r.max_new_tokens for r in g["requests"])
    gaps = lambda g: np.sort(np.diff([r.arrival_s for r in g["requests"]]))
    assert lens(a) == lens(c) and outs(a) == outs(c)
    np.testing.assert_allclose(gaps(a), gaps(c), rtol=0, atol=1e-9)
    n = len(a["requests"])
    rate = mix["arrivals"]["rate_per_s"]
    assert n == round(rate * (mix["lead_in_s"] + 30))
    assert a["requests"][-1].arrival_s < mix["lead_in_s"] + 30
    assert a["open_loop"] and all(
        0 <= t < 32000 for r in a["requests"][:5] for t in r.prompt)
    off = gen_requests.generate(
        harness.read_json(harness.data_file("traffic", "offline-chat")),
        3, 32000, 30)
    assert not off["open_loop"]
    assert all(r.arrival_s == 0.0 for r in off["requests"])


def test_token_batches_are_seeded_and_shifted():
    mix = harness.read_json(harness.data_file("traffic", "packed-4k"))
    assert mix["batch"] * mix["seq_len"] == 8192
    it = token_batches.generate(mix, 5, 32768, 10)
    b0, b1 = next(it), next(it)
    again = token_batches.batch(mix, 5, 32768, 1)
    assert b0["input_ids"].shape == (2, 4096)
    assert b0["input_ids"].dtype == np.int32
    np.testing.assert_array_equal(b0["input_ids"][:, 1:], b0["labels"][:, :-1])
    np.testing.assert_array_equal(b1["input_ids"], again["input_ids"])
    assert not np.array_equal(b0["input_ids"], b1["input_ids"])
    assert b0["input_ids"].max() < 32768


# -- trace reduction ----------------------------------------------------------------

FIXTURE = os.path.join(BENCH, "tracereduce", "fixtures",
                       "matmul_loop.xplane.pb")


def test_tracereduce_on_the_recorded_fixture():
    """Six runs of one 2048^3 bf16 matmul on a TPU v5e, 2 ms of host sleep
    after each (recorded by PR 23's probe)."""
    trace = xplane.load(FIXTURE)
    assert sorted(trace.devices) == [0]
    tl = trace.devices[0]
    assert len(tl.modules) == 6 and len(tl.ops) == 18
    assert trace.offset_s == pytest.approx(1.304e-3, abs=2e-6)
    steps = [e for e in trace.annotations if e.name == "bench/step"]
    gaps = [e for e in trace.annotations if e.name == "bench/host_gap"]
    assert len(steps) == 6 and len(gaps) == 6
    window = (steps[0].start, gaps[-1].end)
    red = xplane.reduce(trace, window, gap_layer=("bench/",))
    assert red.window_s == pytest.approx(19.82e-3, rel=1e-3)
    assert red.busy_s == pytest.approx(6 * 91.58e-6, rel=2e-3)
    assert red.op_seconds["convolution_tanh_fusion bf16[2048,2048]"] == pytest.approx(
        6 * 91.566e-6, rel=1e-3)
    idle = dict(red.idle_gaps)
    assert sum(idle.values()) + red.busy_s == pytest.approx(red.window_s)
    assert idle["bench/host_gap"] == pytest.approx(14.47e-3, rel=1e-2)
    assert idle["bench/host_gap"] > idle["bench/step"] > 0
    hit = xplane.matching(trace, "convolution", window)
    assert hit["count"] == 6
    assert hit["total"] == hit["exposed"] == pytest.approx(
        6 * 91.566e-6, rel=1e-3)
    # every run starts on the device after the host began its step
    for m, s in zip(tl.modules, steps):
        assert s.start < m.start < s.end


def test_interval_arithmetic():
    assert xplane.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert xplane.subtract([(0, 10), (20, 30)],
                           [(1, 2), (5, 6), (9, 21), (25, 26)]) == [
        (0, 1), (2, 5), (6, 9), (21, 25), (26, 30)]
    assert xplane.total(xplane.clip([(0, 5), (7, 9)], 4, 8)) == 2
    text = "%fusion.12 = bf16[8,4]{1,0:T(8,128)} fusion(bf16[8]{0} %p)"
    assert xplane.stable_name(text) == "fusion bf16[8,4]"
    assert xplane.op_kind("%while.2 = (s32[]{:T(128)}, bf16[1,8]) while(") \
        == "while"


def test_span_self_time_excludes_children():
    ev = [{"name": "a", "ts": 0.0, "dur": 100.0},
          {"name": "b", "ts": 10.0, "dur": 30.0},
          {"name": "c", "ts": 15.0, "dur": 10.0},
          {"name": "b", "ts": 50.0, "dur": 20.0}]
    got = harness.span_self_times(ev)
    assert got == pytest.approx({"a": 50e-6, "b": 40e-6, "c": 10e-6})


# -- readers and the yardstick's arithmetic -------------------------------------------

def _obs(**kw):
    cfg = harness.read_json(os.path.join(BENCH, "configs",
                                         "mixtral-8x7b.json"))
    return harness.Observations(config=cfg,
                                peaks=harness.peaks_for("TPU v5 lite"),
                                chips=1, **kw)


def test_work_functions():
    from readers import work

    # one slot decoding at 255 resident tokens, one new slot with a
    # 128-row chunk, over two steps
    obs = _obs(series={"traced_slot_lengths": [
        np.array([254, 0]), np.array([255, 128]), np.array([256, 256])]})
    flops, nbytes = work.paged_attention(obs)
    per_pos = 4 * 32 * 128
    want_attended = (256 + (128 * 128 - 128 * 127 / 2)
                     + 257 + (128 * 256 - 128 * 127 / 2))
    assert flops == pytest.approx(per_pos * want_attended * 4)
    assert nbytes == pytest.approx((256 + 128 + 257 + 256) * 8 * 128 * 4 * 4)
    tr = harness.Observations(
        config=harness.read_json(os.path.join(BENCH, "configs",
                                              "mistral-7b.json")),
        peaks=None, chips=4,
        scalars={"matmul_params": 2.5e9, "seq_len": 4096.0})
    assert work.decoder_train(tr) == pytest.approx(
        6 * 2.5e9 + 6 * 11 * 4096 * 4096)


def test_readers_return_none_when_there_is_nothing_to_read():
    obs = _obs()
    for m in harness.load_manifest()["per_layer"]:
        spec = harness.read_json(harness.data_file("layer_metrics", m["name"]))
        reader = harness.load_plugin("readers", spec["reader"]["kind"])
        assert reader.read(spec["reader"], obs) is None, m["name"]


def test_probe_schedule_feeds_every_position_once():
    from runners.serve import probe_schedule

    steps = probe_schedule(256, 8, 128)
    seen = [rp for rows in steps for rp in rows]
    assert sorted(seen) == [(s, p) for s in (0, 1) for p in range(264)]
    assert all(len(rows) <= 128 for rows in steps)
    # a prefill chunk beside a decode row, and decode rows among pads
    assert any(len(rows) == 128 and rows[0] == (0, 256) for rows in steps)
    assert steps[-1] == [(0, 263), (1, 263)] or len(steps[-1]) <= 2


# -- the reference against the package's models ---------------------------------------

@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_reference_agrees_with_the_package(family):
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from neuronx_distributed_tpu.models import llama, mixtral
    from neuronx_distributed_tpu.parallel import mesh as ps
    from reference import decoder_f32

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    kw = dict(dtype=jnp.float32, param_dtype=jnp.float32, rope_theta=1e6)
    if family == "llama":
        cfg = llama.tiny_config(**kw)
        model = llama.LlamaForCausalLM(cfg)
    else:
        # 8 experts at a capacity that drops nothing: the published block
        cfg = mixtral.tiny_moe_config(num_experts=8, capacity_factor=4.0, **kw)
        model = mixtral.MixtralForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 48))
    shapes = meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                       jnp.zeros((1, 8), jnp.int32)))
    params = harness.make_weights(shapes, 2 ** 31 + 5, 0.05)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, jnp.asarray(ids))
    got = got[0] if isinstance(got, tuple) else got
    want, margins = decoder_f32.forward(
        params, ids, num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        rope_theta=cfg.rope_theta, rms_eps=cfg.rms_eps,
        top_k=getattr(cfg, "top_k", 0))
    assert (margins is None) == (family == "llama")
    # float32 on both sides: only the order of sums differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5 * float(np.std(want)) * 50)
    labels = np.roll(ids, -1, axis=1)
    loss = float(decoder_f32.cross_entropy(want, labels))
    assert abs(loss - np.log(cfg.vocab_size)) < 0.5
    ps.destroy_model_parallel()


# -- the command -----------------------------------------------------------------------

def test_no_tpu_is_a_nonzero_exit_and_no_result():
    for cell in ("mixtral-8x7b.serve-batch", "mistral-7b.train-tp4"):
        p = _run(["--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
        assert p.returncode != 0
        assert "needs a TPU" in p.stderr
        assert not any(line.startswith("{") for line in p.stdout.splitlines())
    p = _run(["--workload", "no-such-cell"])
    assert p.returncode != 0 and "no workload named" in p.stderr


def test_rehearsal_configuration_cannot_be_a_real_cell(tmp_path):
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    p = _run(["--workload", "tiny-mixtral.serve-batch", "--seconds", "1"])
    assert p.returncode != 0          # not in BENCHMARK.json
    assert all(c["file"].startswith("benchmarks/tests/configs/")
               for c in m["configs"])


@pytest.mark.parametrize("cell,trace,env", [
    ("tiny-mixtral.serve-batch", "0", {}),
    ("tiny-mixtral.serve-chat", "1", {}),
    ("tiny-mistral.train-tp4", "0",
     {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}),
    ("tiny-mistral.train-tp4", "1",
     {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}),
])
def test_rehearsal_runs_end_to_end(cell, trace, env):
    manifest = os.path.join(HERE, "manifest.json")
    p = _run(["--manifest", manifest, "--workload", cell, "--seed",
              str(2 ** 31 + 7), "--seconds", "1.5", "--trace", trace], env)
    assert p.returncode == 0, p.stderr[-2000:]
    line = _last_json(p.stdout)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    # never under a device metric's name
    assert line["metrics"] and all(k.startswith("rehearsal.")
                                   for k in line["metrics"])
    m = harness.load_manifest(manifest)
    group = "per_layer" if trace == "1" else "end_to_end"
    allowed = {"rehearsal." + x["name"]
               for x in harness.metrics_of(m, group, cell)}
    assert set(line["metrics"]) <= allowed
    if trace == "0":
        assert set(line["metrics"]) == allowed
        assert "compiled_in_window=0" in p.stdout


def test_a_cell_a_mix_and_a_metric_are_added_as_files_only(tmp_path):
    """A new traffic mix, a new per-layer metric over an existing reader
    and a new cell: two data files and three manifest entries; no file
    that was there is edited."""
    added = {
        os.path.join(HERE, "traffic", "added-mix.json"): {
            "kind": "requests",
            "prompt_tokens": {"dist": "fixed", "value": 20},
            "answer_tokens": {"dist": "fixed", "value": 4},
            "arrivals": {"kind": "all_at_zero", "count": 3000},
            "lead_in_s": 0.2},
        os.path.join(HERE, "layer_metrics", "step_p90_ms.added.json"): {
            "name": "step_p90_ms.added", "layer": "model step", "unit": "ms",
            "better": "lower", "source": "program_span",
            "moves": "serve_tok_s",
            "workloads": ["tiny-mixtral.serve-added"],
            "reader": {"kind": "series_stat", "series": "step_latency_s",
                       "stat": "p90", "scale": 1000}}}
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    m["workloads"].append({"name": "tiny-mixtral.serve-added",
                           "config": "tiny-mixtral", "traffic": "added-mix",
                           "chips": 1, "why": "added by the test"})
    for x in m["end_to_end"]:
        if x["name"] == "serve_tok_s":
            x["workloads"].append("tiny-mixtral.serve-added")
    m["per_layer"].append({k: v for k, v in added[
        os.path.join(HERE, "layer_metrics", "step_p90_ms.added.json")].items()
        if k != "reader"})
    manifest = tmp_path / "manifest.json"
    manifest.write_text(json.dumps(m))
    try:
        for path, body in added.items():
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump(body, f)
        for trace in ("0", "1"):
            p = _run(["--manifest", str(manifest), "--workload",
                      "tiny-mixtral.serve-added", "--seconds", "1",
                      "--trace", trace])
            assert p.returncode == 0, p.stderr[-2000:]
            line = _last_json(p.stdout)
            want = ("rehearsal.step_p90_ms.added" if trace == "1"
                    else "rehearsal.serve_tok_s")
            assert want in line["metrics"] and line["correct"]
    finally:
        for path in added:
            if os.path.exists(path):
                os.remove(path)
        os.rmdir(os.path.join(HERE, "layer_metrics"))
