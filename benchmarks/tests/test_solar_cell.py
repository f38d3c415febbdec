"""``solar-open2-250b.serve-reasoning`` rehearsed on the CPU: the cell's
runner, family, reference, per-layer metric files and readers through
``run.py``, from a manifest written in ``tmp_path`` (``tests/manifest.json``
is not edited). The configuration is ``tests/configs/tiny-solar-open2
.json``: a gated NoPE GQA layer then three KDA mixers, twice less one, 2
KDA heads of the published ``[128, 128]`` state, half of 8 sigmoid-routed
experts held beside a shared expert; the mix is the cell's at a toy size
(prompts of 8 to 128, answers of 24 to 56), so decode rows run beside
prefill chunks and a slot's state outlives many steps."""

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

REAL = "solar-open2-250b.serve-reasoning"
GRANITE = "granite-4.0-h-micro.serve-longgen"
CELL = "tiny-solar-open2.serve-reasoning"
NEW_METRICS = ("kda_state_roofline", "state_bytes_held_pct.batch")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _listed(real, cell) -> list:
    return [x["name"] for x in real["end_to_end"] + real["per_layer"]
            if cell in x.get("workloads", ())]


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-solar-open2", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-solar-open2.json",
        "reduced": [], "why": "the solar_open2 family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-solar-open2",
                           "traffic": "tiny-reasoning-mid", "chips": 1,
                           "why": "rehearsal of " + REAL})
    shared = _listed(real, REAL)
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
    """Counted from the manifest: the cell's own traffic letter for
    letter, ``serve_tok_s``, every metric that Granite's cell reports but
    its two rooflines (Mamba-2's work, and a paged work function that
    multiplies by every layer), the routed experts' two counters, and its
    own two; every published number is the catalog's, and what is cut is
    listed."""
    real = harness.load_manifest()
    cell = harness.by_name(real["workloads"], REAL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b", "offline-reasoning-mid", 1)
    assert "sixteen times their share" in cell["why"]
    traffic = harness.read_json(harness.data_file("traffic",
                                                  cell["traffic"]))
    assert traffic == {
        "kind": "requests",
        "prompt_tokens": {"dist": "lognormal", "median": 1024, "sigma": 0.8,
                          "min": 128, "max": 8192},
        "answer_tokens": {"dist": "lognormal", "median": 3072, "sigma": 0.2,
                          "min": 2048, "max": 4096},
        "arrivals": {"kind": "all_at_zero", "count": 768},
        "order_seed": 23, "lead_in_s": 75}
    entry = harness.by_name(real["configs"], cell["config"], "configuration")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["source_url"] == entry["source"]]
        differ = {k for k, v in row["config"].items() if config[k] != v}
        assert differ == set(entry["reduced"])
    assert (config["num_hidden_layers"], config["gqa_layers"]) == (8, [0, 4])
    share = config["share"]
    assert (config["n_routed_experts"], share["n_routed_experts_published"],
            share["first_expert"], share["chips_a_layer"]) == (20, 320, 0,
                                                               16)
    assert (config["vocab_size"], share["vocab_size_published"]) == (
        24576, 196608)
    for item in ("gqa_gate", "router", "kda", "kda_init",
                 "initializer_range", "precision", "tensor_names", "serve",
                 "serve_aot_gib"):
        assert item in config["assumed"], item
    assert "sixteen chips" in config["stands_for"]
    assert "layers 0-7" in config["stands_for"]
    serve = config["serve"]
    assert (serve["max_slots"], serve["token_budget"], serve["block_size"],
            serve["max_blocks_per_seq"]) == (128, 128, 128, 96)
    assert serve["num_blocks"] >= 3072
    chk = serve["logit_check"]
    assert chk["prompt_tokens"] + chk["decode_steps"] >= 2048 + 64
    assert chk["prompt_tokens"] % serve["token_budget"]     # unaligned
    listed = _listed(real, REAL)
    granite = _listed(real, GRANITE)
    assert set(listed) == (set(granite) - {"ssd_state_roofline"}) | {
        "moe_dropped_pct.batch", "moe_held_pct.batch"} | set(NEW_METRICS)
    # a work function that multiplies by every layer, and a pattern of
    # another family's expert shapes (it read 0.09% here: PERF.md section 7)
    assert "paged_attention_roofline" not in listed
    assert "moe_expert_share_pct.batch" not in listed
    for name in listed:
        if name == "serve_tok_s":
            continue
        spec = harness.read_json(harness.data_file("layer_metrics", name))
        assert harness.load_plugin("readers", spec["reader"]["kind"]).read
    for name in NEW_METRICS:
        metric = harness.by_name(real["per_layer"], name, "metric")
        assert begins_with(metric, [REAL])
        assert metric["moves"] == "serve_tok_s"


def test_the_family_refuses_what_it_does_not_build():
    import pytest

    family = harness.load_plugin("families", "solar_open2")
    config = harness.read_json(os.path.join(
        HERE, "configs", "tiny-solar-open2.json"))
    for key, value in (("use_rope", True), ("use_gqa_gate", False),
                       ("kda_use_full_proj", True),
                       ("kda_allow_neg_eigval", False),
                       ("first_k_dense_replace", 1),
                       ("n_shared_experts", 0), ("norm_topk_prob", False),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="solar_open2"):
            family.build(dict(config, **{key: value}))
    reference = harness.load_plugin("reference", "solar_open2_f32")
    with pytest.raises(ValueError, match="solar_open2_f32"):
        reference.forward(None, np.zeros((1, 4), np.int64),
                          dict(config, use_rope=True))


def test_the_cell_is_rehearsed_from_files_alone(tmp_path):
    manifest = _manifest(tmp_path)
    for trace in ("0", "1"):
        p = _run(["--manifest", manifest, "--workload", CELL, "--seed",
                  str(2 ** 31 + 48), "--seconds", "2", "--trace", trace])
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
        for name in ("state_bytes_held_pct.batch",
                     "state_slots_advanced_pct.batch", "moe_held_pct.batch",
                     "moe_dropped_pct.batch", "paged_cols_live_pct.batch",
                     "paged_run_fetch_pct.batch", "step_ms.batch",
                     "rows_per_step.batch", "overlapped_step_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        for name in ("kda_state_roofline", "ssm_state_share_pct.batch",
                     "ssm_conv_share_pct.batch"):
            assert "rehearsal." + name not in got
        assert got["rehearsal.moe_dropped_pct.batch"]["value"] == 0
        assert 25 < got["rehearsal.moe_held_pct.batch"]["value"] < 75
        assert got["rehearsal.state_slots_advanced_pct.batch"]["value"] > 50
        # five layers' states of 128 KiB a head pair beside two layers'
        # K/V of 128 B a position: the toy's slots are nearly all state
        assert 90 < got["rehearsal.state_bytes_held_pct.batch"]["value"] < 100
        assert "compiled_in_window=0" in p.stdout


def test_the_roofline_work_counts_a_slots_state_once_and_its_rows_each():
    """By hand: 2 heads of ``[4, 4]``, three KDA layers of five; a slot
    prefills 11 rows, then decodes one; the other is empty."""
    from readers import kda_roofline

    obs = harness.Observations(
        config=dict(linear_attn_config=dict(num_heads=2, head_dim=4),
                    num_hidden_layers=5, gqa_layers=[0, 4]),
        peaks=None, chips=1)
    assert kda_roofline.work(obs) is None             # nothing to read
    obs.series["traced_slot_lengths"] = [
        np.array([0, 0]), np.array([11, 0]), np.array([11, 0])]
    flops, nbytes = kda_roofline.work(obs)
    assert flops == 7 * 2 * 4 * 4 * (11 + 1) * 3
    # two advanced (slot, step)s, each the state in and out in float32;
    # twelve rows of q, k, v, g, o and a beta a head at two bytes
    assert nbytes == 3 * (2 * 2 * (2 * 4 * 4) * 4
                          + 12 * 2 * (5 * 2 * 4 + 2))
    assert kda_roofline.read({"scopes": ["attn.state"]}, obs) is None
