"""``sdar-30b-a3b-chat.serve-blockgen`` rehearsed on the CPU: the cell's
runner, family, reference, traffic mix, per-layer metric files and readers
through ``run.py``, from a manifest written in ``tmp_path``
(``tests/manifest.json`` is not edited). The configuration is
``tests/configs/tiny-sdar.json``: two layers of 8 softmax experts under
the block-causal mask, blocks of 4 positions in pool blocks of 16, 8
slots of a step of 48 rows; prompts of 3 to 60 and answers of 14 to 50
that 4 does not divide, so decode groups are most of a step, as the
cell's are. Everything is counted from the manifest: no number of metrics
is written here."""

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
from manifest_checks import begins_with, file_holds_entry  # noqa: E402

REAL = "sdar-30b-a3b-chat.serve-blockgen"
CELL = "tiny-sdar.serve-blockgen"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"block_rows_uncovered_pct.batch": ("server", "program_counter"),
       "block_store_pass_pct.batch": ("server", "program_counter"),
       "uncover_share_pct.batch": ("model step", "device_trace"),
       "sdar_moe_experts_roofline": ("kernels", "device_trace"),
       "block_paged_attention_roofline": ("kernels", "device_trace")}


def _listed(real, cell) -> list:
    return [x["name"] for x in real["end_to_end"] + real["per_layer"]
            if cell in x.get("workloads", ())]


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-sdar", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-sdar.json",
        "reduced": [], "why": "the sdar_moe family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-sdar",
                           "traffic": "tiny-blockgen", "chips": 1,
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
        "sdar-30b-a3b-chat", "offline-blockgen", 1)
    assert len(cell["why"]) <= 200
    for said in ("4 rows a slot", "5 passes a block", "32 assignments",
                 "6 of 48 layers"):
        assert said in cell["why"], said
    for name, (layer, source) in NEW.items():
        entry = harness.by_name(real["per_layer"], name, "metric")
        assert begins_with(entry, [REAL])
        assert (entry["layer"], entry["source"], entry["moves"],
                entry["unit"]) == (layer, source, "serve_tok_s", "%")
        spec = harness.read_json(harness.data_file("layer_metrics", name))
        assert file_holds_entry(
            {k: v for k, v in entry.items() if k != "workloads"}, entry)
        assert {k: spec[k] for k in entry if k != "workloads"} == {
            k: v for k, v in entry.items() if k != "workloads"}
        assert REAL in spec["workloads"] and spec["what"]
    listed = _listed(real, REAL)
    assert set(NEW) | {
        "serve_tok_s", "step_ms.batch", "rows_per_step.batch",
        "sched_ms_per_step.batch", "host_ms_per_step.batch",
        "device_idle_pct.batch", "peak_hbm_gib.batch",
        "prefill_row_share_pct.batch", "paged_attn_share_pct.batch",
        "ffn_share_pct.batch", "head_sample_share_pct.batch",
        "moe_dropped_pct.batch", "stall_loss_pct.batch"} <= set(listed)
    # readers/work.py infers one decode row a slot, readers/moe_roofline.py
    # reads another family's keys: the cell is on neither's list
    assert "paged_attention_roofline" not in listed
    assert "moe_experts_roofline" not in listed
    traffic = harness.read_json(harness.data_file("traffic",
                                                  cell["traffic"]))
    assert traffic == {
        "kind": "requests",
        "prompt_tokens": dict(dist="lognormal", median=512, sigma=0.6,
                              min=64, max=2048),
        "answer_tokens": dict(dist="lognormal", median=1024, sigma=0.3,
                              min=512, max=1536),
        "arrivals": dict(kind="all_at_zero", count=traffic["arrivals"][
            "count"]),
        "order_seed": 23, "lead_in_s": traffic["lead_in_s"]}
    assert traffic["arrivals"]["count"] >= 1536
    assert traffic["lead_in_s"] >= 45
    entry = harness.by_name(real["configs"], cell["config"], "configuration")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers"}
    assert all(set(cut) == {"from", "to", "why"}
               for cut in config["reduced"].values())
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "SDAR-30B-A3B-Chat"]
        assert row["source_url"] == entry["source"] == config["source"]
        differ = {k for k, v in row["config"].items() if config[k] != v}
        assert differ == set(entry["reduced"])
    assert 4 <= config["num_hidden_layers"] <= 7
    assert (config["num_experts"], config["num_experts_per_tok"],
            config["moe_intermediate_size"], config["vocab_size"],
            config["hidden_size"], config["head_dim"]) == (
        128, 8, 768, 151936, 2048, 128)
    assert "share" not in config
    for item in ("block_length", "denoising_steps", "confidence_threshold",
                 "mask_token_id", "qk_norm", "tensor_names", "sampling",
                 "dispatch", "serve", "serve_aot_gib", "initializer_range"):
        assert item in config["assumed"], item
    assert (config["block_length"], config["denoising_steps"],
            config["confidence_threshold"], config["mask_token_id"]) == (
        4, 4, 0.9, 151669)
    assert "eight pipeline stages" in config["stands_for"]
    serve = config["serve"]
    chk = serve["logit_check"]
    assert (chk["group"], chk["rewrite"]) == (config["block_length"], True)
    assert chk["prompt_tokens"] % 4 == 0 and chk["decode_steps"] % 4 == 0
    assert chk["compare"] == {"every": 8, "tail": 128} and chk["why"]
    assert (serve["token_budget"], serve["max_slots"], serve["block_size"]
            ) == (640, 128, 128)
    assert serve["token_budget"] == 4 * serve["max_slots"] + 128
    assert serve["max_blocks_per_seq"] * serve["block_size"] >= 2048 + 1536 + 4
    assert serve["paged_attention"] == "pallas"


def test_the_family_refuses_a_group_that_is_not_the_block():
    import pytest

    config = harness.read_json(os.path.join(HERE, "configs",
                                            "tiny-sdar.json"))
    family = harness.load_plugin("families", "sdar_moe")
    config["serve"]["logit_check"]["group"] = 2
    with pytest.raises(ValueError, match="logit_check.group"):
        family.build(config)


def test_the_new_readers_find_nothing_where_there_is_nothing():
    obs = harness.Observations(config={"hidden_size": 64}, peaks=None,
                               chips=1, steps=3, window_s=1.0)
    for name in NEW:
        spec = harness.read_json(harness.data_file("layer_metrics", name))
        reader = harness.load_plugin("readers", spec["reader"]["kind"])
        assert reader.read(spec["reader"], obs) is None, name


def test_block_attention_work_from_a_fixture_of_lengths():
    from readers import block_paged_roofline as bpr

    # a slot: two chunks of a prompt (a step between them without rows),
    # then decoding, a block stored at the fourth decode step; then the
    # slot is handed on
    lengths = [0, 128, 128, 200, 200, 200, 204, 204, 64]
    got = bpr.slot_rows(lengths, 4)
    chunk = lambda lo, hi: (hi - lo, sum(p // 4 * 4 + 4
                                         for p in range(lo, hi)), hi)
    assert got == [chunk(0, 128), (0, 0, 0), chunk(128, 200),
                   (4, 4 * 204, 204), (4, 4 * 204, 204),
                   (4, 4 * 208, 208), (4, 4 * 208, 208), chunk(0, 64)]
    config = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                  hidden_size=64, num_hidden_layers=2, block_length=4)
    obs = harness.Observations(config=config, peaks=None, chips=1, steps=8,
                               window_s=1.0)
    obs.series["traced_slot_lengths"] = [np.asarray([n, 0]) for n in lengths]
    flops, nbytes = bpr.work(obs)
    rows, attended, read = map(sum, zip(*got))
    assert flops == 4.0 * 4 * 32 * attended * 2
    assert nbytes == (2.0 * 2 * 32 * read + 2.0 * 4 * 32 * rows) * 2 * 2
    # one decode row a slot, as readers/work.py counts, is a quarter of
    # the rows and the bytes of the context as often
    assert bpr.work(harness.Observations(
        config={"hidden_size": 64}, peaks=None, chips=1, steps=1,
        window_s=1.0)) is None


def test_the_cell_is_rehearsed_from_files_alone(tmp_path):
    manifest = _manifest(tmp_path)
    for trace in ("0", "1"):
        p = _run(["--manifest", manifest, "--workload", CELL, "--seed",
                  str(2 ** 31 + 67), "--seconds", "2", "--trace", trace])
        assert p.returncode == 0, p.stderr[-3000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert line["correct"], p.stdout[-3000:]
        assert line["failed"] == 0 and line["attempted"] > 0
        got = line["metrics"]
        assert "group=4 rewrite=True" in p.stdout
        if trace == "0":
            assert set(got) == {"rehearsal.serve_tok_s", "rehearsal.setup_s"}
            continue
        # the host's metrics read the counters; the CPU has no device
        # plane, so the device metrics find nothing and are left out
        for name in ("block_rows_uncovered_pct.batch",
                     "block_store_pass_pct.batch", "moe_dropped_pct.batch",
                     "paged_cols_live_pct.batch", "step_ms.batch",
                     "prefill_row_share_pct.batch",
                     "overlapped_step_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        for name in ("uncover_share_pct.batch", "sdar_moe_experts_roofline",
                     "block_paged_attention_roofline"):
            assert "rehearsal." + name not in got
            assert f"name={name} value=nothing to read" in p.stdout
        assert got["rehearsal.moe_dropped_pct.batch"]["value"] == 0
        # random weights: the quota alone uncovers, a row a pass, and a
        # block's fifth pass stores it (fewer passes for a request's first
        # block, which holds the prompt's remainder)
        uncovered = got["rehearsal.block_rows_uncovered_pct.batch"]["value"]
        stores = got["rehearsal.block_store_pass_pct.batch"]["value"]
        assert 19.5 <= uncovered <= 20.5 and 20 <= stores <= 22
        assert got["rehearsal.prefill_row_share_pct.batch"]["value"] < 25
        assert "compiled_in_window=0" in p.stdout
        assert "router_margin_p01" in p.stdout
