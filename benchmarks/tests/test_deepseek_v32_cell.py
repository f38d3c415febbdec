"""``deepseek-v3.2.serve-longdocs`` rehearsed on the CPU: the cell's
runner, family, reference, traffic mix, per-layer metric files and readers
through ``run.py``, from a manifest written in ``tmp_path``
(``tests/manifest.json`` is not edited). The configuration is
``tests/configs/tiny-deepseek-v32.json``: a dense layer and two expert
layers under a router of 4 groups, half the experts held, 2 index heads
that keep 8 positions, a pool row of 128 lanes beside index keys of 16;
prompts of 40 to 160 and answers of 4 to 16, so nearly every row is a
prefill row that selects, as the cell's are. Everything is counted from
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

REAL = "deepseek-v3.2.serve-longdocs"
CELL = "tiny-deepseek-v32.serve-longdocs"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = ("dsa_index_share_pct.batch", "dsa_context_kept_pct.batch",
       "dsa_blocks_named_pct.batch", "dsa_selection_shared_pct.batch",
       "dsa_index_roofline", "dsa_attention_roofline")
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"}


def _listed(real, cell) -> list:
    return [x["name"] for x in real["end_to_end"] + real["per_layer"]
            if cell in x.get("workloads", ())]


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-deepseek-v32", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-deepseek-v32.json",
        "reduced": [], "why": "the deepseek_v32 family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-deepseek-v32",
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


def test_the_manifest_holds_the_cell_its_mix_its_metrics_and_its_files():
    real = harness.load_manifest()
    cell = harness.by_name(real["workloads"], REAL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v3.2", "offline-long-docs-32k", 1)
    assert len(cell["why"]) <= 200 and "5 of 61 layers" in cell["why"]
    for name in OWN:
        metric = harness.by_name(real["per_layer"], name, "metric")
        assert begins_with(metric, [REAL])
        spec = harness.read_json(harness.data_file("layer_metrics", name))
        assert file_holds_entry(
            {k: v for k, v in metric.items() if k != "workloads"}, metric)
        assert spec["moves"] == "serve_tok_s" and REAL in spec["workloads"]
    listed = _listed(real, REAL)
    assert {"serve_tok_s", "step_ms.batch", "peak_hbm_gib.batch",
            "moe_dropped_pct.batch", "moe_held_pct.batch",
            "select_scope_share_pct.batch", "long_context_row_pct.batch",
            "attn_share_pct.batch"} | set(OWN) <= set(listed)
    # the latent kernel does not run in this family's step
    assert not {"mla_attention_roofline", "mla_attn_share_pct.batch",
                "mla_run_fetch_pct.batch", "mla_shared_unit_pct.batch",
                "paged_cols_live_pct.batch"} & set(listed)
    traffic = harness.read_json(harness.data_file("traffic",
                                                  cell["traffic"]))
    assert traffic["prompt_tokens"] == dict(
        dist="lognormal", median=32768, sigma=0.35, min=16384, max=65536)
    assert traffic["answer_tokens"] == dict(
        dist="lognormal", median=384, sigma=0.4, min=128, max=768)
    assert (traffic["kind"], traffic["arrivals"]["kind"],
            traffic["order_seed"]) == ("requests", "all_at_zero", 23)
    entry = harness.by_name(real["configs"], cell["config"], "configuration")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    assert set(entry["reduced"]) == set(config["reduced"]) == REDUCED
    assert all(set(cut) == {"from", "to", "why"}
               for cut in config["reduced"].values())
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "DeepSeek-V3.2"]
        assert row["source_url"] == entry["source"] == config["source"]
        differ = {k for k, v in row["config"].items() if config[k] != v}
        assert differ == REDUCED
    assert (config["hidden_size"], config["num_attention_heads"],
            config["q_lora_rank"], config["kv_lora_rank"],
            config["index_n_heads"], config["index_head_dim"],
            config["index_topk"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["n_group"],
            config["topk_group"], config["intermediate_size"]) == (
        7168, 128, 1536, 512, 64, 128, 2048, 2048, 8, 8, 4, 18432)
    assert config["share"] == {
        "chips_a_layer": 16, "this_chip": 0,
        "n_routed_experts_published": 256, "first_expert": 0,
        "vocab_size_published": 129280, "first_vocab_row": 0,
        "num_hidden_layers_published": 61}
    for item in ("indexer", "indexer_precision", "rotary", "router",
                 "key_norm_bias", "initializer_range", "precision",
                 "tensor_names", "dispatch", "serve", "serve_aot_gib"):
        assert item in config["assumed"], item
    assert "chip 0 of the first sixteen" in config["stands_for"]
    serve = config["serve"]
    assert (serve["max_slots"], serve["block_size"]) == (8, 256)
    assert serve["max_blocks_per_seq"] * serve["block_size"] >= 65536 + 768
    assert serve["num_blocks"] == (serve["max_slots"]
                                   * serve["max_blocks_per_seq"])
    chk = serve["logit_check"]
    assert chk["prompt_tokens"] > config["index_topk"]
    assert chk["prompt_tokens"] > config["rope_scaling"][
        "original_max_position_embeddings"]
    assert serve["paged_attention"] == "pallas"


def test_the_cell_is_rehearsed_from_files_alone(tmp_path):
    manifest = _manifest(tmp_path)
    for trace in ("0", "1"):
        p = _run(["--manifest", manifest, "--workload", CELL, "--seed",
                  str(2 ** 31 + 72), "--seconds", "2", "--trace", trace])
        assert p.returncode == 0, p.stderr[-3000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert line["correct"], p.stdout[-3000:]
        assert line["failed"] == 0 and line["attempted"] > 0
        got = line["metrics"]
        if trace == "0":
            assert set(got) == {"rehearsal.serve_tok_s", "rehearsal.setup_s"}
            continue
        # the host's metrics read the counters; the CPU has no device
        # plane, so the device metrics (the scopes' shares and the two
        # rooflines) find nothing and are left out
        for name in ("moe_dropped_pct.batch", "moe_held_pct.batch",
                     "dsa_context_kept_pct.batch",
                     "dsa_blocks_named_pct.batch",
                     "dsa_selection_shared_pct.batch",
                     "long_context_row_pct.batch", "step_ms.batch",
                     "prefill_row_share_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        for name in ("dsa_index_share_pct.batch", "dsa_index_roofline",
                     "dsa_attention_roofline"):
            assert "rehearsal." + name not in got
            assert f"name={name} value=nothing to read" in p.stdout
        assert got["rehearsal.moe_dropped_pct.batch"]["value"] == 0
        assert 40 < got["rehearsal.moe_held_pct.batch"]["value"] < 60
        # 8 of 40 to 160 positions
        assert 5 < got["rehearsal.dsa_context_kept_pct.batch"]["value"] < 25
        assert 20 < got["rehearsal.dsa_blocks_named_pct.batch"]["value"] < 100
        assert got["rehearsal.dsa_selection_shared_pct.batch"]["value"] > 10
        assert got["rehearsal.prefill_row_share_pct.batch"]["value"] > 60
        assert "compiled_in_window=0" in p.stdout
        assert "router_margin_p01" in p.stdout
