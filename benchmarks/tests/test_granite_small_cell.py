"""``granite-4.0-h-small.serve-agentic`` rehearsed on the CPU: the cell's
runner, family, reference, per-layer metric files and readers through
``run.py``, from a manifest written in ``tmp_path`` (``tests/manifest.json``
is not edited). The configuration is ``tests/configs/tiny-granite-moe-hybrid
.json``: five layers in runs of 1, 1, 2, 1, four mamba heads over a state
of ``[16, 128]``, half of 8 softmax-routed experts held beside a shared
MLP after every layer of either kind; the mix is the cell's at a toy size
(``tiny-agentic-code``), so prefill chunks go through the scan beside
decode rows."""

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

import harness  # noqa: E402
from manifest_checks import begins_with, stand_together  # noqa: E402

REAL = "granite-4.0-h-small.serve-agentic"
MICRO = "granite-4.0-h-micro.serve-longgen"
SOLAR = "solar-open2-250b.serve-reasoning"
CELL = "tiny-granite-moe-hybrid.serve-agentic"
NEW_METRICS = ("hybrid_expert_share_pct.batch",
               "hybrid_shared_share_pct.batch", "moe_experts_roofline",
               "state_rows_in_chunk_pct.batch")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _listed(real, cell) -> list:
    return [x["name"] for x in real["end_to_end"] + real["per_layer"]
            if cell in x.get("workloads", ())]


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-granite-moe-hybrid", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-granite-moe-hybrid.json",
        "reduced": [], "why": "the granite_moe_hybrid family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-granite-moe-hybrid",
                           "traffic": "tiny-agentic-code", "chips": 1,
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
    """Counted from the manifest: GLM's replay letter for letter,
    ``serve_tok_s``, every metric that the micro cell and Solar's report
    but the delta rule's roofline, and its own four; every published
    number is the catalog's, and what is cut is listed."""
    real = harness.load_manifest()
    cell = harness.by_name(real["workloads"], REAL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-small", "offline-agentic-code", 1)
    assert "half a pair's load" in cell["why"]
    glm = harness.by_name(real["workloads"], "glm-4.7-flash.serve-agentic",
                          "workload")
    assert glm["traffic"] == cell["traffic"]
    entry = harness.by_name(real["configs"], cell["config"], "configuration")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_local_experts",
        "vocab_size"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["source_url"] == entry["source"]]
        differ = {k for k, v in row["config"].items() if config[k] != v}
        assert differ == set(entry["reduced"])
        assert config["layer_types"] == row["config"]["layer_types"][:10]
    assert config["num_hidden_layers"] == 10
    assert config["layer_types"].index("attention") == 5
    share = config["share"]
    assert (config["num_local_experts"],
            share["num_local_experts_published"], share["first_expert"],
            share["chips_a_layer"]) == (36, 72, 0, 2)
    assert (config["vocab_size"], share["vocab_size_published"]) == (
        50176, 100352)
    assert (config["intermediate_size"], config["shared_intermediate_size"],
            config["num_experts_per_tok"]) == (768, 1536, 10)
    for item in ("router", "experts", "mamba2_init", "in_proj_rows",
                 "initializer_range", "precision", "tensor_names",
                 "dispatch", "serve", "serve_aot_gib"):
        assert item in config["assumed"], item
    assert "unchecked" in config["assumed"]["tensor_names"]
    for said in ("two chips", "four pipeline stages", "half the"):
        assert said in config["stands_for"], said
    serve = config["serve"]
    assert (serve["max_slots"], serve["token_budget"], serve["block_size"],
            serve["max_blocks_per_seq"]) == (64, 128, 128, 160)
    assert serve["num_blocks"] >= 4096
    assert config["assumed"]["serve_aot_gib"]["of_chip"] >= 0.85
    chk = serve["logit_check"]
    assert chk["prompt_tokens"] % serve["token_budget"]     # unaligned
    assert len(chk["why"]) > 1000
    listed = _listed(real, REAL)
    others = set(_listed(real, MICRO)) | set(_listed(real, SOLAR))
    assert set(listed) == (others - {"kda_state_roofline"}) | set(
        NEW_METRICS)
    # a work function that multiplies by every layer, and a pattern of
    # another family's expert shapes
    assert "paged_attention_roofline" not in listed
    assert "moe_expert_share_pct.batch" not in listed
    for name in listed:
        if name == "serve_tok_s":
            continue
        spec = harness.read_json(harness.data_file("layer_metrics", name))
        assert harness.load_plugin("readers", spec["reader"]["kind"]).read
    for name in NEW_METRICS:
        metric = harness.by_name(real["per_layer"], name, "metric")
        assert metric["moves"] == "serve_tok_s"
        assert begins_with(metric, (
            [MICRO, SOLAR, REAL] if name == "state_rows_in_chunk_pct.batch"
            else [REAL]))
    assert stand_together(real, NEW_METRICS)


def test_the_family_refuses_what_it_does_not_build():
    family = harness.load_plugin("families", "granite_moe_hybrid")
    config = harness.read_json(os.path.join(
        HERE, "configs", "tiny-granite-moe-hybrid.json"))
    for key, value in (("position_embedding_type", "rope"),
                       ("hidden_act", "gelu"),
                       ("normalization_function", "layernorm"),
                       ("attention_bias", True), ("mamba_proj_bias", True),
                       ("mamba_conv_bias", False),
                       ("tie_word_embeddings", False),
                       ("mamba_n_groups", 8), ("mamba_expand", 4),
                       ("num_experts_per_tok", 0),
                       ("model_type", "granitemoe")):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))
    with pytest.raises(ValueError, match="dense model"):
        family.build(dict(config, num_local_experts=0,
                          num_experts_per_tok=0))
    reference = harness.load_plugin("reference", "granite_moe_hybrid_f32")
    for wrong in (dict(num_local_experts=0), dict(mamba_n_groups=8)):
        with pytest.raises(ValueError, match="granite_moe_hybrid_f32"):
            reference.forward(None, np.zeros((1, 4), np.int64),
                              dict(config, **wrong))


def test_the_cell_is_rehearsed_from_files_alone(tmp_path):
    manifest = _manifest(tmp_path)
    for trace in ("0", "1"):
        p = _run(["--manifest", manifest, "--workload", CELL, "--seed",
                  str(2 ** 31 + 55), "--seconds", "2", "--trace", trace])
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
                     "state_slots_advanced_pct.batch",
                     "state_rows_in_chunk_pct.batch", "moe_held_pct.batch",
                     "moe_dropped_pct.batch", "paged_cols_live_pct.batch",
                     "paged_run_fetch_pct.batch", "step_ms.batch",
                     "rows_per_step.batch", "prefill_row_share_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        for name in ("moe_experts_roofline", "ssd_state_roofline",
                     "hybrid_expert_share_pct.batch",
                     "hybrid_shared_share_pct.batch",
                     "ssm_state_share_pct.batch"):
            assert "rehearsal." + name not in got
        assert got["rehearsal.moe_dropped_pct.batch"]["value"] == 0
        assert 25 < got["rehearsal.moe_held_pct.batch"]["value"] < 75
        # a chunk's rows after its first are most of a step's rows where
        # prompts of 16 to 128 prefill beside a few decode rows
        chunk = got["rehearsal.state_rows_in_chunk_pct.batch"]["value"]
        prefill = got["rehearsal.prefill_row_share_pct.batch"]["value"]
        assert 0 < chunk < 100 and chunk < prefill + 20
        assert "compiled_in_window=0" in p.stdout


def test_the_roofline_work_counts_a_held_experts_bytes_once_a_step_a_layer():
    """By hand: 3 experts held of width 4 at hidden 8, top 2, five
    layers; two steps of 12 real rows in all, of whose assignments half
    were kept."""
    from readers import moe_roofline

    config = dict(hidden_size=8, intermediate_size=4, num_hidden_layers=5,
                  num_local_experts=3, num_experts_per_tok=2)
    flops, nbytes = moe_roofline.work(config, steps=2, rows=12, kept=0.5)
    kept = 12 * 2 * 0.5 * 5
    assert flops == 6 * 8 * 4 * kept
    # the bank's three matrices once a step a layer whatever the rows;
    # a kept assignment's row in and product out, bf16
    assert nbytes == 2 * 5 * 3 * (3 * 8 * 4) * 2 + kept * 2 * 8 * 2
    more, same = moe_roofline.work(config, steps=2, rows=24, kept=0.5)
    assert more == 2 * flops and same - nbytes == kept * 2 * 8 * 2
    obs = harness.Observations(config=config, peaks=None, chips=1)
    assert moe_roofline.traced_rows(obs) is None       # nothing to read
    obs.series["traced_slot_lengths"] = [
        np.array([0, 0]), np.array([11, 0]), np.array([11, 0])]
    assert moe_roofline.traced_rows(obs) == (2, 12)
    assert moe_roofline.read({"scopes": ["ffn.experts"]}, obs) is None
