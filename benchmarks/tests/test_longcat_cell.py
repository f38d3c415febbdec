"""``longcat-flash-chat.serve-agentic`` rehearsed on the CPU: the cell's
runner, family, reference, per-layer metric files and readers through
``run.py``, from a manifest written in ``tmp_path`` (``tests/manifest.json``
is not edited). The configuration is ``tests/configs/tiny-longcat-flash
.json``: two shortcut-connected double layers over a latent cache of two
layers of rows a decoder layer, half of 8 real experts held beside 4
identity experts; the mix is GLM's rehearsal's. Everything is counted
from the manifest: no number of metrics is written here."""

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

REAL = "longcat-flash-chat.serve-agentic"
GLM = "glm-4.7-flash.serve-agentic"
CELL = "tiny-longcat-flash.serve-agentic"
NEW_METRICS = ("moe_identity_pct.batch", "scmoe_expert_share_pct.batch",
               "scmoe_dense_share_pct.batch", "moe_identity_share_pct.batch",
               "dual_mla_attention_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _listed(real, cell) -> list:
    return [x["name"] for x in real["end_to_end"] + real["per_layer"]
            if cell in x.get("workloads", ())]


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-longcat-flash", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-longcat-flash.json",
        "reduced": [], "why": "the longcat_flash family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-longcat-flash",
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
    """Counted from the manifest: GLM's replay letter for letter (the
    file GLM's cell names), ``serve_tok_s``, every metric GLM's cell
    reports but the two whose readers are GLM's own shapes (the roofline
    that multiplies by ``num_hidden_layers``, the routed experts' text
    pattern), the held experts' counter, and its own; every published
    number is the catalog's, and what is cut is listed."""
    real = harness.load_manifest()
    cell = harness.by_name(real["workloads"], REAL, "workload")
    glm = harness.by_name(real["workloads"], GLM, "workload")
    assert (cell["config"], cell["chips"]) == ("longcat-flash-chat", 1)
    assert cell["traffic"] == glm["traffic"] == "offline-agentic-code"
    assert "1/32 its load" in cell["why"] and len(cell["why"]) <= 200
    entry = harness.by_name(real["configs"], cell["config"], "configuration")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_layers", "n_routed_experts", "vocab_size"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "LongCat-Flash-Chat"]
        assert row["source_url"] == entry["source"]
        differ = {k for k, v in row["config"].items() if config[k] != v}
        assert differ == set(entry["reduced"])
    share = config["share"]
    assert (config["num_layers"], share["num_layers_published"]) == (4, 28)
    assert (config["n_routed_experts"], share["n_routed_experts_published"],
            share["first_expert"], share["chips_a_layer"]) == (16, 512, 0,
                                                               32)
    assert (config["zero_expert_num"],
            share["zero_expert_num_published"]) == (256, 256)
    assert (config["vocab_size"], share["vocab_size_published"]) == (
        16384, 131072)
    for item in ("mla_scale", "norm_topk_prob", "router",
                 "tie_word_embeddings", "rotary", "initializer_range",
                 "lane_layout", "precision", "tensor_names", "serve",
                 "serve_aot_gib"):
        assert item in config["assumed"], item
    assert "32 chips share each layer" in config["stands_for"]
    serve = config["serve"]
    glm_serve = harness.read_json(os.path.join(ROOT, harness.by_name(
        real["configs"], glm["config"], "configuration")["file"]))["serve"]
    for key in ("max_slots", "token_budget", "block_size",
                "max_blocks_per_seq"):
        assert serve[key] == glm_serve[key], key
    aot = config["assumed"]["serve_aot_gib"]
    assert 0.85 <= aot["of_chip"] <= 0.90
    chk = serve["logit_check"]
    assert chk["prompt_tokens"] + chk["decode_steps"] >= 4096 + 32
    listed, of_glm = _listed(real, REAL), _listed(real, GLM)
    assert set(listed) == (set(of_glm) - {
        "mla_attention_roofline", "moe_expert_share_pct.batch"}) | {
        "moe_held_pct.batch"} | set(NEW_METRICS)
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

    family = harness.load_plugin("families", "longcat_flash")
    config = harness.read_json(os.path.join(
        HERE, "configs", "tiny-longcat-flash.json"))
    for key, value in (("attention_bias", True), ("attention_method", "MHA"),
                       ("zero_expert_type", "zero"),
                       ("norm_topk_prob", True),
                       ("rope_scaling", {"rope_type": "yarn"})):
        with pytest.raises(ValueError, match="longcat_flash"):
            family.build(dict(config, **{key: value}))


def test_the_cell_is_rehearsed_from_files_alone(tmp_path):
    manifest = _manifest(tmp_path)
    for trace in ("0", "1"):
        p = _run(["--manifest", manifest, "--workload", CELL, "--seed",
                  str(2 ** 31 + 53), "--seconds", "2", "--trace", trace])
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
        for name in ("moe_identity_pct.batch", "moe_held_pct.batch",
                     "moe_dropped_pct.batch", "paged_cols_live_pct.batch",
                     "mla_run_fetch_pct.batch", "step_ms.batch",
                     "rows_per_step.batch", "overlapped_step_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        for name in ("dual_mla_attention_roofline",
                     "scmoe_expert_share_pct.batch",
                     "moe_identity_share_pct.batch"):
            assert "rehearsal." + name not in got
        assert got["rehearsal.moe_dropped_pct.batch"]["value"] == 0
        # 4 identity slots of 12, 4 of 8 real experts held, under seeded
        # weights and a seeded bias
        assert 15 < got["rehearsal.moe_identity_pct.batch"]["value"] < 55
        assert 25 < got["rehearsal.moe_held_pct.batch"]["value"] < 75
        assert "compiled_in_window=0" in p.stdout


def test_the_roofline_work_is_one_attentions_times_two_a_layer():
    """By hand: 2 heads over a latent of 4 and a rotary key of 2, three
    double layers; a slot prefills 5 rows, then decodes one at resident
    length 6 (position 6: ``rows_of``); the other is empty. ``readers/mla_roofline.py``'s count at six attentions."""
    from readers import dual_mla_roofline, mla_roofline

    config = dict(num_attention_heads=2, kv_lora_rank=4, qk_rope_head_dim=2,
                  num_layers=3)
    obs = harness.Observations(config=config, peaks=None, chips=1)
    assert dual_mla_roofline.work(obs) is None            # nothing to read
    obs.series["traced_slot_lengths"] = [
        np.array([0, 0]), np.array([5, 0]), np.array([6, 0])]
    flops, nbytes = dual_mla_roofline.work(obs)
    attended = sum(range(1, 6)) + 7
    assert flops == 2 * 2 * (2 * 4 + 2) * attended * 6
    assert nbytes == 6 * 2 * ((4 + 2) * (5 + 7) + 2 * (2 * 4 + 2) * 6)
    one = harness.Observations(config=dict(config, num_hidden_layers=1),
                               peaks=None, chips=1)
    one.series = obs.series
    assert mla_roofline.work(one) == (flops / 6, nbytes / 6)
    assert dual_mla_roofline.read({"match": "mla_paged_attention"},
                                  obs) is None
