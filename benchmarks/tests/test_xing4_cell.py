"""``xing4.0-29b-a4b.serve-longdocs`` rehearsed on the CPU: the cell's
runner, family, reference, traffic mix, per-layer metric file and readers
through ``run.py``, from a manifest written in ``tmp_path``
(``tests/manifest.json`` is not edited). The configuration is
``tests/configs/tiny-xing4.json``: two dense layers and two expert layers
under four residual streams, a pool row of 128 lanes, YaRN by 8 over 16
positions; prompts of 40 to 160 and answers of 4 to 16, so prefill rows
are nearly all of a step, as the cell's are. Everything is counted from
the manifest: no number of metrics is written here."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
from manifest_checks import begins_with, file_holds_entry  # noqa: E402

REAL = "xing4.0-29b-a4b.serve-longdocs"
CELL = "tiny-xing4.serve-longdocs"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _listed(real, cell) -> list:
    return [x["name"] for x in real["end_to_end"] + real["per_layer"]
            if cell in x.get("workloads", ())]


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-xing4", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-xing4.json",
        "reduced": [], "why": "the xing4 family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-xing4",
                           "traffic": "tiny-long-docs-64k", "chips": 1,
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
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=1800)


def test_the_manifest_holds_the_cell_its_mix_its_metric_and_its_files():
    real = harness.load_manifest()
    cell = harness.by_name(real["workloads"], REAL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4.0-29b-a4b", "offline-long-docs-64k", 1)
    assert len(cell["why"]) <= 200 and "7 of 40 layers" in cell["why"]
    # found by name: a later cell or metric comes behind these
    hc = harness.by_name(real["per_layer"], "hc_share_pct.batch", "metric")
    assert begins_with(hc, [REAL])
    assert file_holds_entry({
        "name": "hc_share_pct.batch", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_tok_s"}, hc)
    listed = _listed(real, REAL)
    assert {"serve_tok_s", "hc_share_pct.batch", "mla_attn_share_pct.batch",
            "mla_attention_roofline", "mla_run_fetch_pct.batch",
            "mla_shared_unit_pct.batch", "moe_dropped_pct.batch",
            "long_context_row_pct.batch", "paged_cols_live_pct.batch",
            "paged_block_shared_pct.batch", "peak_hbm_gib.batch",
            "step_ms.batch"} <= set(listed)
    # the routed experts' text pattern names GLM's shapes, and finds only
    # the dispatch's masks at these: the cell is not on its list
    assert "moe_expert_share_pct.batch" not in listed
    spec = harness.read_json(harness.data_file("layer_metrics",
                                               "hc_share_pct.batch"))
    assert spec["reader"] == {"kind": "device_scope_share",
                              "scopes": ["hc"]}
    assert REAL in spec["workloads"]
    traffic = harness.read_json(harness.data_file("traffic",
                                                  cell["traffic"]))
    assert traffic == {
        "kind": "requests",
        "prompt_tokens": dict(dist="lognormal", median=24576, sigma=0.5,
                              min=8192, max=65536),
        "answer_tokens": dict(dist="lognormal", median=384, sigma=0.4,
                              min=128, max=768),
        "arrivals": dict(kind="all_at_zero", count=128),
        "order_seed": 23, "lead_in_s": 90}
    entry = harness.by_name(real["configs"], cell["config"], "configuration")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "num_nextn_predict_layers"}
    assert all(set(cut) == {"from", "to", "why"}
               for cut in config["reduced"].values())
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "Xing4.0-29B-A4B"]
        assert row["source_url"] == entry["source"] == config["source"]
        differ = {k for k, v in row["config"].items() if config[k] != v}
        assert differ == set(entry["reduced"])
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["vocab_size"],
            config["hc_mult"], config["hc_sinkhorn_iters"]) == (
        7, 2, 64, 131072, 4, 20)
    for item in ("initializer_range", "hc_read_in_and_out", "hc_statistic",
                 "hc_sinkhorn_order", "hc_eps", "hc_alpha",
                 "hc_streams_dtype", "hc_phi", "hc_alpha_and_bias",
                 "hc_checkpoint_names", "rotary", "head_dim", "lane_layout",
                 "precision", "router", "serve", "serve_aot_gib"):
        assert item in config["assumed"], item
    assert "eight pipeline stages" in config["stands_for"]
    serve = config["serve"]
    assert (serve["token_budget"], serve["max_slots"]) == (128, 8)
    # every request fits its table row, and the pool every table row
    assert serve["max_blocks_per_seq"] * serve["block_size"] >= 65536 + 768
    assert serve["num_blocks"] == (serve["max_slots"]
                                   * serve["max_blocks_per_seq"])
    chk = serve["logit_check"]
    assert chk["prompt_tokens"] + chk["decode_steps"] > config[
        "rope_scaling"]["original_max_position_embeddings"]
    assert serve["paged_attention"] == "pallas"


def test_the_cell_is_rehearsed_from_files_alone(tmp_path):
    manifest = _manifest(tmp_path)
    for trace in ("0", "1"):
        p = _run(["--manifest", manifest, "--workload", CELL, "--seed",
                  str(2 ** 31 + 63), "--seconds", "2", "--trace", trace])
        assert p.returncode == 0, p.stderr[-3000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert line["correct"], p.stdout[-3000:]
        assert line["failed"] == 0 and line["attempted"] > 0
        got = line["metrics"]
        if trace == "0":
            assert set(got) == {"rehearsal.serve_tok_s", "rehearsal.setup_s"}
            continue
        # the host's metrics read the counters; the CPU has no device
        # plane, so the device metrics (hc_share_pct.batch among them)
        # find nothing and are left out
        for name in ("moe_dropped_pct.batch", "paged_cols_live_pct.batch",
                     "long_context_row_pct.batch", "mla_run_fetch_pct.batch",
                     "step_ms.batch", "prefill_row_share_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        assert "rehearsal.hc_share_pct.batch" not in got
        assert "name=hc_share_pct.batch value=nothing to read" in p.stdout
        assert got["rehearsal.moe_dropped_pct.batch"]["value"] == 0
        assert got["rehearsal.long_context_row_pct.batch"]["value"] == 0
        assert got["rehearsal.prefill_row_share_pct.batch"]["value"] > 60
        assert "compiled_in_window=0" in p.stdout
        assert "router_margin_p01" in p.stdout
