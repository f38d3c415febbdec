"""``laguna-s-2.1.serve-agentic`` rehearsed on the CPU: the cell's runner,
family, reference, per-layer metric files and readers through ``run.py``,
from a manifest written in ``tmp_path`` (``tests/manifest.json`` is not
edited). The configuration is ``tests/configs/tiny-laguna.json``: a full
dense layer, three sliding sparse layers and a full sparse one, a window
of two pool blocks, half of 8 experts held; the mix is GLM's rehearsal's
(prompts of 16 to 128, answers of 12 to 48), so most contexts pass the
window of 32 and decode rows run beside prefill chunks."""

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

REAL = "laguna-s-2.1.serve-agentic"
GLM = "glm-4.7-flash.serve-agentic"
CELL = "tiny-laguna.serve-agentic"
NEW_METRICS = ("window_attn_share_pct.batch", "full_attn_share_pct.batch",
               "swa_attention_roofline", "window_cols_live_pct.batch",
               "window_blocks_held_pct.batch", "moe_held_pct.batch")


def _shared(real) -> list:
    return [x["name"] for x in real["end_to_end"] + real["per_layer"]
            if REAL in x.get("workloads", ())]


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-laguna", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-laguna.json",
        "reduced": [], "why": "the laguna family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-laguna",
                           "traffic": "tiny-agentic-code", "chips": 1,
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


def test_the_manifest_holds_the_cell_its_metrics_and_its_files():
    """Counted from the manifest: GLM's cell's traffic on another model,
    ``serve_tok_s``, every metric that every other serving cell reports,
    the paged kernel's three over the full layers' table, the dropped
    assignments, and its own six; every published width is the catalog's,
    and what is cut is listed."""
    real = harness.load_manifest()
    cell = harness.by_name(real["workloads"], REAL, "workload")
    glm = harness.by_name(real["workloads"], GLM, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2.1", glm["traffic"], 1)
    entry = harness.by_name(real["configs"], cell["config"], "configuration")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert set(entry["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "gating_types", "num_attention_heads_per_layer", "num_experts",
        "vocab_size"}
    widths = dict(hidden_size=3072, intermediate_size=12288, head_dim=128,
                  num_attention_heads=48, num_key_value_heads=8,
                  moe_intermediate_size=1024,
                  shared_expert_intermediate_size=1024,
                  num_experts_per_tok=10, sliding_window=512)
    assert {k: config[k] for k in widths} == widths
    assert config["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert config["layer_types"][0] == config["layer_types"][4] \
        == "full_attention"
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    share = config["share"]
    assert (config["num_experts"], share["num_experts_published"],
            share["first_expert"], share["chips_a_layer"]) == (128, 256, 0,
                                                               2)
    assert (config["vocab_size"], share["vocab_size_published"]) == (
        50176, 100352)
    for item in ("router", "shared_expert", "qk_norm", "gate", "rotary",
                 "initializer_range", "precision"):
        assert item in config["assumed"], item
    assert "two chips" in config["stands_for"]
    shared = _shared(real)
    assert "serve_tok_s" in shared and set(NEW_METRICS) < set(shared)
    assert "moe_dropped_pct.batch" in shared
    assert not {"paged_attention_roofline", "moe_expert_share_pct.batch"
                } & set(shared)
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
                  str(2 ** 31 + 40), "--seconds", "2", "--trace", trace])
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
        for name in ("window_cols_live_pct.batch",
                     "window_blocks_held_pct.batch", "moe_held_pct.batch",
                     "moe_dropped_pct.batch", "paged_cols_live_pct.batch",
                     "step_ms.batch", "rows_per_step.batch",
                     "overlapped_step_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        assert got["rehearsal.moe_dropped_pct.batch"]["value"] == 0
        assert 25 < got["rehearsal.moe_held_pct.batch"]["value"] < 75
        # a ring of 3 blocks beside contexts of up to 11: under a half
        assert 0 < got["rehearsal.window_blocks_held_pct.batch"]["value"] \
            < 65
        assert 0 < got["rehearsal.window_cols_live_pct.batch"]["value"] \
            < 100
        assert "compiled_in_window=0" in p.stdout


def test_the_roofline_work_counts_a_window_in_the_sliding_layers():
    """By hand: two K/V heads of 16, a window of 8, a full layer of 4
    heads and two sliding layers of 6; a slot prefills 11 rows, then
    decodes one; the other is empty."""
    from readers import swa_roofline

    obs = harness.Observations(
        config=dict(num_key_value_heads=2, head_dim=16, sliding_window=8,
                    layer_types=["full_attention", "sliding_attention",
                                 "sliding_attention"],
                    num_attention_heads_per_layer=[4, 6, 6]),
        peaks=None, chips=1)
    assert swa_roofline.work(obs) is None             # nothing to read
    obs.series["traced_slot_lengths"] = [
        np.array([0, 0]), np.array([11, 0]), np.array([11, 0])]
    flops, nbytes = swa_roofline.work(obs)
    full = sum(range(1, 12)) + 12                 # 11 rows, then row 11
    sliding = sum(min(t + 1, 8) for t in range(11)) + 8
    assert flops == 4 * 16 * (4 * full + 2 * 6 * sliding)
    # the chunk's 11 rows under a window of 8 see all 11; the decode row 8
    assert nbytes == 2 * 2 * 16 * 2 * ((11 + 12) + 2 * (11 + 8))
    assert swa_roofline.read({"scopes": ["attn.kernel.full"]}, obs) is None
