"""``granite-4.0-h-micro.serve-longgen`` rehearsed on the CPU: the cell's
runner, family, reference, traffic mix, per-layer metric files and readers
through ``run.py``, from a manifest written in ``tmp_path``
(``tests/manifest.json`` is not edited). The configuration is
``tests/configs/tiny-granite-hybrid.json``; the mix is the cell's in
little: short prompts, answers several times their length."""

import json
import os
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
from manifest_checks import begins_with  # noqa: E402

REAL = "granite-4.0-h-micro.serve-longgen"
CELL = "tiny-granite-hybrid.serve-longgen"
NEW_METRICS = ("ssm_state_share_pct.batch", "ssm_conv_share_pct.batch",
               "state_slots_advanced_pct.batch", "ssd_state_roofline")


def _shared(real):
    return [x["name"] for x in real["end_to_end"] + real["per_layer"]
            if REAL in x.get("workloads", ())]


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-granite-hybrid", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-granite-hybrid.json",
        "reduced": [], "why": "the granite_hybrid family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-granite-hybrid",
                           "traffic": "tiny-longgen", "chips": 1,
                           "why": "rehearsal of " + REAL})
    shared = _shared(real)
    have = {x["name"] for x in m["end_to_end"] + m["per_layer"]}
    for x in m["end_to_end"] + m["per_layer"]:
        if x["name"] in shared:
            x["workloads"].append(CELL)
    for x in real["per_layer"]:
        if x["name"] in shared and x["name"] not in have:
            m["per_layer"].append(dict(x, workloads=[CELL]))
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(m))
    return str(path)


def _run(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py")] + args,
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)


def test_the_cell_its_mix_and_its_metrics_are_in_the_manifest():
    """Counted from the manifest: the cell reports ``serve_tok_s`` (and
    ``setup_s``, which lists no cells), every metric that every other
    serving cell reports, the paged kernel's three, and its own four;
    every metric it lists has its file and its reader; the kernel's
    roofline, whose work function counts every layer as attending, does
    not list it."""
    real = harness.load_manifest()
    cell = harness.by_name(real["workloads"], REAL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro", "offline-longgen", 1)
    entry = harness.by_name(real["configs"], cell["config"], "configuration")
    assert entry["reduced"] == []
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    assert config["reduced"] == {} and config["family"] == "granite_hybrid"
    mix = harness.read_json(harness.data_file("traffic", cell["traffic"]))
    reasoning = harness.read_json(harness.data_file("traffic",
                                                    "offline-reasoning"))
    assert {k: mix[k] for k in ("prompt_tokens", "answer_tokens")} == {
        k: reasoning[k] for k in ("prompt_tokens", "answer_tokens")}
    assert mix["arrivals"]["count"] >= 4 * config["serve"]["max_slots"]
    shared = _shared(real)
    assert "serve_tok_s" in shared and set(NEW_METRICS) < set(shared)
    assert "paged_attention_roofline" not in shared
    others = [w["name"] for w in real["workloads"]
              if w["name"] != REAL and "serve_tok_s" in [
                  m["name"] for m in harness.metrics_of(
                      real, "end_to_end", w["name"])]]
    everywhere = [m["name"] for m in real["per_layer"]
                  if set(others) <= set(m.get("workloads", others))]
    assert everywhere and set(everywhere) < set(shared)
    for name in shared:
        if name == "serve_tok_s":
            continue
        spec = harness.read_json(harness.data_file("layer_metrics", name))
        assert harness.load_plugin("readers", spec["reader"]["kind"]).read
    for name in NEW_METRICS:
        listed = harness.by_name(real["per_layer"], name, "metric")
        assert begins_with(listed, [REAL])
        assert listed["moves"] == "serve_tok_s"


def test_the_cell_is_rehearsed_from_files_alone(tmp_path):
    manifest = _manifest(tmp_path)
    for trace in ("0", "1"):
        p = _run(["--manifest", manifest, "--workload", CELL, "--seed",
                  str(2 ** 31 + 38), "--seconds", "2", "--trace", trace])
        assert p.returncode == 0, p.stderr[-3000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert line["correct"], p.stdout[-3000:]
        assert line["failed"] == 0 and line["attempted"] > 0
        got = line["metrics"]
        if trace == "0":
            assert set(got) == {"rehearsal.serve_tok_s", "rehearsal.setup_s"}
            continue
        # the host's metrics read the program's counters; the CPU has no
        # device plane, so the device metrics find nothing
        for name in ("state_slots_advanced_pct.batch",
                     "paged_cols_live_pct.batch", "step_ms.batch",
                     "rows_per_step.batch", "prefill_row_share_pct.batch",
                     "overlapped_step_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        advanced = got["rehearsal.state_slots_advanced_pct.batch"]["value"]
        assert 50 < advanced <= 100
        assert "compiled_in_window=0" in p.stdout


def test_the_roofline_work_counts_the_states_advanced():
    """By hand at 4 heads of 32 over a state of 16, three mamba layers of
    five: a slot prefills 7 rows, then decodes one; the other is empty."""
    from readers import ssd_roofline

    obs = harness.Observations(
        config=dict(mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16,
                    layer_types=["mamba", "attention", "mamba", "mamba",
                                 "attention"]),
        peaks=None, chips=1)
    assert ssd_roofline.work(obs) is None             # nothing to read
    obs.series["traced_slot_lengths"] = [np.array([0, 0]), np.array([7, 0]),
                                         np.array([7, 0])]
    flops, nbytes = ssd_roofline.work(obs)
    inner, n, rows, advanced = 128, 16, 7 + 1, 2
    assert flops == 3 * 6 * inner * n * rows
    assert nbytes == 3 * (advanced * 2 * n * inner * 4
                          + rows * 2 * (3 * inner + 2 * n + 4))
    # a program that marks no scope, or a run without a trace: nothing
    assert ssd_roofline.read({"scopes": ["attn.state"]}, obs) is None
