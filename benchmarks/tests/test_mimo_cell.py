"""``mimo-v2-flash.serve-reasoning-mixed`` rehearsed on the CPU: the cell's
runner, family, reference, per-layer metric files and readers through
``run.py``, from a manifest written in ``tmp_path`` (``tests/manifest.json``
is not edited). The configuration is ``tests/configs/tiny-mimo-v2-flash
.json``: a full dense layer, then sliding, sliding, full and sliding sparse
layers, 8 query heads of 192 over 2 (full) and 4 (sliding) K heads of 192
and V heads of 128, a sink a head, a window of two pool blocks, half of 8
sigmoid-routed experts held; the mix is the cell's at a toy size (prompts
of 8 to 128, answers of 24 to 56), so most contexts pass the window of 32
and decode rows run beside prefill chunks."""

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

REAL = "mimo-v2-flash.serve-reasoning-mixed"
LAGUNA = "laguna-s-2.1.serve-agentic"
CELL = "tiny-mimo-v2-flash.serve-reasoning-mixed"
NEW_METRICS = ("asym_attention_roofline", "window_kv_bytes_held_pct.batch",
               "long_context_row_pct.batch")


def _shared(real) -> list:
    return [x["name"] for x in real["end_to_end"] + real["per_layer"]
            if REAL in x.get("workloads", ())]


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-mimo-v2-flash", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-mimo-v2-flash.json",
        "reduced": [], "why": "the mimo_v2_flash family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-mimo-v2-flash",
                           "traffic": "tiny-mixed-reasoning", "chips": 1,
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
    """Counted from the manifest: the cell's own traffic, ``serve_tok_s``,
    every metric that Laguna's cell reports but its roofline (whose work
    function reads one K/V head count and one head size), and its own
    three; every published width is the catalog's, and what is cut is
    listed."""
    real = harness.load_manifest()
    cell = harness.by_name(real["workloads"], REAL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2-flash", "offline-mixed-reasoning", 1)
    assert "sixteen times its share" in cell["why"]
    traffic = harness.read_json(harness.data_file("traffic",
                                                  cell["traffic"]))
    assert traffic == {
        "kind": "requests",
        "prompt_tokens": {"dist": "lognormal", "median": 2048, "sigma": 1.0,
                          "min": 256, "max": 24576},
        "answer_tokens": {"dist": "lognormal", "median": 6144,
                          "sigma": 0.25, "min": 4096, "max": 8192},
        "arrivals": {"kind": "all_at_zero", "count": 256},
        "order_seed": 23, "lead_in_s": 75}
    entry = harness.by_name(real["configs"], cell["config"], "configuration")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert set(entry["reduced"]) == {
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"}
    widths = dict(hidden_size=4096, intermediate_size=16384, head_dim=192,
                  v_head_dim=128, swa_head_dim=192, swa_v_head_dim=128,
                  num_attention_heads=64, swa_num_attention_heads=64,
                  num_key_value_heads=4, swa_num_key_value_heads=8,
                  moe_intermediate_size=2048, num_experts_per_tok=8,
                  sliding_window=128, attention_value_scale=0.707,
                  partial_rotary_factor=0.334)
    assert {k: config[k] for k in widths} == widths
    assert config["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert config["moe_layer_freq"] == [0] + [1] * 6
    share = config["share"]
    assert (config["n_routed_experts"], share["n_routed_experts_published"],
            share["first_expert"], share["chips_a_layer"]) == (16, 256, 0,
                                                               16)
    assert (config["vocab_size"], share["vocab_size_published"]) == (
        19072, 152576)
    for item in ("qk_norm", "rotary", "value_scale", "attention_scale",
                 "window", "sink", "sink_values", "router", "mtp",
                 "initializer_range", "precision", "pools"):
        assert item in config["assumed"], item
    assert "sixteen chips" in config["stands_for"]
    shared = _shared(real)
    laguna = [x["name"] for x in real["end_to_end"] + real["per_layer"]
              if LAGUNA in x.get("workloads", ())]
    assert set(shared) == (set(laguna) - {"swa_attention_roofline"}) | set(
        NEW_METRICS)
    assert "paged_attention_roofline" not in shared
    for name in shared:
        if name == "serve_tok_s":
            continue
        spec = harness.read_json(harness.data_file("layer_metrics", name))
        assert harness.load_plugin("readers", spec["reader"]["kind"]).read
    for name in NEW_METRICS:
        listed = harness.by_name(real["per_layer"], name, "metric")
        assert begins_with(listed, [REAL])
        assert listed["moves"] == "serve_tok_s"


def test_the_family_refuses_what_it_does_not_build():
    import pytest

    family = harness.load_plugin("families", "mimo_v2_flash")
    config = harness.read_json(os.path.join(
        HERE, "configs", "tiny-mimo-v2-flash.json"))
    for key, value in (("attention_bias", True), ("scoring_func", "softmax"),
                       ("n_group", 2), ("topk_group", 2),
                       ("n_shared_experts", 1),
                       ("add_full_attention_sink_bias", True),
                       ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match="mimo_v2_flash"):
            family.build(dict(config, **{key: value}))


def test_the_cell_is_rehearsed_from_files_alone(tmp_path):
    manifest = _manifest(tmp_path)
    for trace in ("0", "1"):
        p = _run(["--manifest", manifest, "--workload", CELL, "--seed",
                  str(2 ** 31 + 45), "--seconds", "2", "--trace", trace])
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
        for name in ("window_kv_bytes_held_pct.batch",
                     "long_context_row_pct.batch",
                     "window_cols_live_pct.batch",
                     "window_blocks_held_pct.batch", "moe_held_pct.batch",
                     "moe_dropped_pct.batch", "paged_cols_live_pct.batch",
                     "step_ms.batch", "rows_per_step.batch",
                     "overlapped_step_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        assert "rehearsal.asym_attention_roofline" not in got
        assert got["rehearsal.moe_dropped_pct.batch"]["value"] == 0
        assert 25 < got["rehearsal.moe_held_pct.batch"]["value"] < 75
        # three sliding layers' rings of 3 blocks at twice a full layer's
        # bytes a block, beside two full layers' contexts of up to 12
        blocks = got["rehearsal.window_blocks_held_pct.batch"]["value"]
        held = got["rehearsal.window_kv_bytes_held_pct.batch"]["value"]
        assert 0 < blocks < held < 100
        assert abs(held - 100 * 2 * blocks / (100 + blocks)) < 1e-6
        # no toy context reaches 8,192
        assert got["rehearsal.long_context_row_pct.batch"]["value"] == 0
        assert "compiled_in_window=0" in p.stdout


def test_the_roofline_work_counts_each_layer_types_rows_and_heads():
    """By hand: 8 query heads, keys of 24 beside values of 16, a window of
    8, a full layer of 2 K/V heads and two sliding layers of 4; a slot
    prefills 11 rows, then decodes one; the other is empty."""
    from readers import asym_roofline

    obs = harness.Observations(
        config=dict(num_attention_heads=8, head_dim=24, v_head_dim=16,
                    num_key_value_heads=2, swa_num_key_value_heads=4,
                    sliding_window=8, hybrid_layer_pattern=[0, 1, 1]),
        peaks=None, chips=1)
    assert asym_roofline.work(obs) is None            # nothing to read
    obs.series["traced_slot_lengths"] = [
        np.array([0, 0]), np.array([11, 0]), np.array([11, 0])]
    flops, nbytes = asym_roofline.work(obs)
    full = sum(range(1, 12)) + 12                 # 11 rows, then row 11
    sliding = sum(min(t + 1, 8) for t in range(11)) + 8
    assert flops == 2 * 8 * (24 + 16) * (full + 2 * sliding)
    # the chunk's 11 rows under a window of 8 see all 11; the decode row 8
    assert nbytes == 2 * (24 + 16) * (2 * (11 + 12) + 2 * 4 * (11 + 8))
    assert asym_roofline.read({"scopes": ["attn.kernel.full"]}, obs) is None
