"""``nemotron-3-super.serve-reasoning`` rehearsed on the CPU: the cell's
runner, family, reference, per-layer metric files and readers through
``run.py``, from a manifest written in ``tmp_path`` (``tests/manifest.json``
is not edited). The configuration is ``tests/configs/tiny-nemotron-h.json``:
nine published layers ``MEM*EEME*`` (a paired and a lone mixer of each
sort, a feed-forward with no mixer ahead), eight mamba heads over a state
of ``[16, 256]`` in two groups, half of 8 sigmoid-routed squared-ReLU
experts held in a latent of 32 beside a shared expert; the mix is the
cell's at a toy size (``tiny-reasoning-mid``)."""

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

REAL = "nemotron-3-super.serve-reasoning"
GRANITE = "granite-4.0-h-small.serve-agentic"
SOLAR = "solar-open2-250b.serve-reasoning"
CELL = "tiny-nemotron-h.serve-reasoning"
NEW_METRICS = ("latent_moe_roofline", "grouped_ssd_state_roofline",
               "latent_proj_share_pct.batch", "moe_experts_idle_pct.batch")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = os.path.join(HERE, "configs", "tiny-nemotron-h.json")


def _listed(real, cell) -> list:
    return [x["name"] for x in real["end_to_end"] + real["per_layer"]
            if cell in x.get("workloads", ())]


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-nemotron-h", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-nemotron-h.json",
        "reduced": [], "why": "the nemotron_h family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-nemotron-h",
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
    """Counted from the manifest: Solar's backlog letter for letter,
    ``serve_tok_s``, every metric that Granite-Small's cell reports but
    the two rooflines whose work functions read Granite's keys, and its
    own four; every published number is the catalog's, and what is cut is
    listed."""
    real = harness.load_manifest()
    cell = harness.by_name(real["workloads"], REAL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-super-120b-a12b", "offline-reasoning-mid", 1)
    assert "1/4 load" in cell["why"]
    solar = harness.by_name(real["workloads"], SOLAR, "workload")
    assert solar["traffic"] == cell["traffic"]
    entry = harness.by_name(real["configs"], cell["config"], "configuration")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    cut = {"num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size", "num_nextn_predict_layers"}
    assert set(entry["reduced"]) == set(config["reduced"]) == cut
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["source_url"] == entry["source"]]
        assert {k for k, v in row["config"].items()
                if config[k] != v} == cut
        assert config["hybrid_override_pattern"] == row["config"][
            "hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    share = config["share"]
    assert (config["n_routed_experts"], share["n_routed_experts_published"],
            share["first_expert"], share["chips_a_layer"]) == (128, 512, 0, 4)
    assert (config["vocab_size"], share["vocab_size_published"]) == (
        32768, 131072)
    assert (config["hidden_size"], config["mamba_num_heads"],
            config["mamba_head_dim"], config["ssm_state_size"],
            config["n_groups"], config["conv_kernel"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["moe_intermediate_size"],
            config["moe_latent_size"], config["num_experts_per_tok"],
            config["routed_scaling_factor"],
            config["moe_shared_expert_intermediate_size"]) == (
        4096, 128, 64, 128, 8, 4, 32, 2, 128, 2688, 1024, 22, 5, 5376)
    for item in ("attention", "latent_moe", "router", "mamba2",
                 "mamba2_init", "layers", "initializer_range", "precision",
                 "tensor_names", "dispatch", "serve", "serve_aot_gib"):
        assert item in config["assumed"], item
    assert "unchecked" in config["assumed"]["tensor_names"]
    for said in ("32 v5e chips", "eight pipeline stages", "a quarter of"):
        assert said in config["stands_for"], said
    serve = config["serve"]
    assert (serve["max_slots"], serve["token_budget"], serve["block_size"],
            serve["max_blocks_per_seq"], serve["num_blocks"]) == (
        128, 128, 128, 96, 12288)
    assert config["assumed"]["serve_aot_gib"]["of_chip"] >= 0.80
    chk = serve["logit_check"]
    assert chk["prompt_tokens"] % serve["token_budget"]     # unaligned
    assert len(chk["why"]) > 1000
    listed = _listed(real, REAL)
    assert set(listed) == (set(_listed(real, GRANITE)) - {
        "ssd_state_roofline", "moe_experts_roofline"}) | set(NEW_METRICS)
    assert "paged_attention_roofline" not in listed
    for name in listed:
        if name == "serve_tok_s":
            continue
        spec = harness.read_json(harness.data_file("layer_metrics", name))
        assert harness.load_plugin("readers", spec["reader"]["kind"]).read
    for name in NEW_METRICS:
        metric = harness.by_name(real["per_layer"], name, "metric")
        assert metric["moves"] == "serve_tok_s"
        assert begins_with(metric, [REAL])
    assert stand_together(real, NEW_METRICS)


def test_the_family_refuses_what_it_does_not_build():
    family = harness.load_plugin("families", "nemotron_h")
    config = harness.read_json(TINY)
    for key, value in (("hybrid_override_pattern", "MEM-EEME*"),
                       ("n_group", 2), ("topk_group", 2),
                       ("residual_in_fp32", True), ("use_bias", True),
                       ("mlp_bias", True), ("attention_bias", True),
                       ("mamba_proj_bias", True), ("use_conv_bias", False),
                       ("mlp_hidden_act", "silu"), ("expand", 2),
                       ("tie_word_embeddings", True),
                       ("num_nextn_predict_layers", 1),
                       ("norm_topk_prob", False), ("n_shared_experts", 2),
                       ("num_hidden_layers", 8),
                       ("model_type", "granitemoehybrid")):
        with pytest.raises(ValueError, match=key):
            family.build(dict(config, **{key: value}))
    reference = harness.load_plugin("reference", "nemotron_h_f32")
    for wrong in (dict(hybrid_override_pattern="MEM-EEME*"),
                  dict(n_group=2), dict(num_nextn_predict_layers=1)):
        with pytest.raises(ValueError, match="nemotron_h_f32"):
            reference.forward(None, np.zeros((1, 4), np.int64),
                              dict(config, **wrong))


def test_the_cell_is_rehearsed_from_files_alone(tmp_path):
    manifest = _manifest(tmp_path)
    for trace in ("0", "1"):
        p = _run(["--manifest", manifest, "--workload", CELL, "--seed",
                  str(2 ** 31 + 59), "--seconds", "2", "--trace", trace])
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
                     "moe_dropped_pct.batch", "moe_experts_idle_pct.batch",
                     "paged_cols_live_pct.batch",
                     "paged_run_fetch_pct.batch", "step_ms.batch",
                     "rows_per_step.batch", "prefill_row_share_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        for name in ("latent_moe_roofline", "grouped_ssd_state_roofline",
                     "latent_proj_share_pct.batch",
                     "hybrid_expert_share_pct.batch",
                     "ssm_state_share_pct.batch"):
            assert "rehearsal." + name not in got
        assert got["rehearsal.moe_dropped_pct.batch"]["value"] == 0
        assert 25 < got["rehearsal.moe_held_pct.batch"]["value"] < 75
        assert 0 <= got["rehearsal.moe_experts_idle_pct.batch"]["value"] < 90
        assert "compiled_in_window=0" in p.stdout


def test_the_roofline_work_counts_a_latent_banks_bytes_once_a_step_a_layer():
    """By hand: 3 experts held of width 4 in a latent of 8, top 2, a
    pattern with two ``E`` and three ``M``; two steps of 12 real rows in
    all, of whose assignments half were kept."""
    from readers import grouped_ssd_roofline, latent_moe_roofline

    config = dict(moe_latent_size=8, moe_intermediate_size=4,
                  hybrid_override_pattern="MEM*EM", n_routed_experts=3,
                  num_experts_per_tok=2, mamba_num_heads=2,
                  mamba_head_dim=4, ssm_state_size=16, n_groups=2)
    flops, nbytes = latent_moe_roofline.work(config, steps=2, rows=12,
                                             kept=0.5)
    kept = 12 * 2 * 0.5 * 2
    assert flops == 4 * 8 * 4 * kept
    # the bank's two matrices once a step a layer whatever the rows; a
    # kept assignment's latent row in and product out, bf16
    assert nbytes == 2 * 2 * 3 * (2 * 8 * 4) * 2 + kept * 2 * 8 * 2
    more, same = latent_moe_roofline.work(config, steps=2, rows=24,
                                          kept=0.5)
    assert more == 2 * flops and same - nbytes == kept * 2 * 8 * 2
    obs = harness.Observations(config=config, peaks=None, chips=1)
    assert grouped_ssd_roofline.work(obs) is None      # nothing to read
    assert latent_moe_roofline.read({"scopes": ["ffn.experts"]},
                                    obs) is None
    obs.series["traced_slot_lengths"] = [
        np.array([0, 0]), np.array([11, 0]), np.array([12, 0])]
    flops, nbytes = grouped_ssd_roofline.work(obs)
    # three M layers; two slot-steps advanced, 12 rows: a state of
    # [16, 8] float32 in and out a slot-step, a row's x, z, y, both
    # groups' B and C and dt at two bytes a value
    assert flops == 6 * 8 * 16 * 12 * 3
    assert nbytes == 3 * (2 * 2 * 16 * 8 * 4
                          + 12 * 2 * (3 * 8 + 2 * 2 * 16 + 2))
    # another family's configuration: nothing to read, nothing raised
    other = harness.Observations(config=dict(mamba_n_heads=2), peaks=None,
                                 chips=1)
    other.series = obs.series
    assert grouped_ssd_roofline.work(other) is None
