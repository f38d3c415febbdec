"""Tier-1 guard of the on-chip benchmark's manifest: ``BENCHMARK.json`` keeps
its contract, and every name in it (configuration, family, reference,
traffic, generator, runner, metric, reader) resolves to a file. Nothing
here runs a model: ``benchmarks/tests`` rehearses the runners, outside
tier-1, and would not notice a manifest that names a missing file.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)

MANIFEST = harness.load_manifest()
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# keys that name a width: never to be listed under ``reduced``
WIDTHS = ("hidden_size", "intermediate_size", "head_dim", "window_size",
          "chunk_size", "num_experts_per_tok")


def _ids(entries):
    return [e["name"] for e in entries]


def test_the_manifest_has_the_contracts_shape():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(harness.MANIFEST) <= 64 * 1024
    assert 1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
    assert 1 <= len(m["per_layer"]) <= 128
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[g]]
    assert all(harness.NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        assert len(set(_ids(m[group]))) == len(m[group])
    metrics = _ids(m["end_to_end"] + m["per_layer"])
    assert len(set(metrics)) == len(metrics)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    # a four-chip cell costs four times the chip time of every later check
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 4)
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]
    # a full check of the driver fits its limit
    cells = len(m["workloads"])
    assert ((2 + 14 * cells) * (m["run_seconds"] + 60) + 2 * 90 * cells
            + 1200) <= 43200


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=_ids(MANIFEST["configs"]))
def test_a_configuration_resolves_and_cuts_depth_only(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith(tuple(
        p + "/" for p in MANIFEST["paths"]))
    assert 1 <= len(entry["why"]) <= 200 and 1 <= len(entry["source"]) <= 200
    assert any(w["config"] == entry["name"] for w in MANIFEST["workloads"])
    files = [c["file"] for c in MANIFEST["configs"]]
    assert files.count(entry["file"]) == 1
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    assert config["source"] == entry["source"]
    assert not config.get("rehearsal")
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert key not in WIDTHS and not key.endswith(("_dim", "_rank"))
        cut = config["reduced"][key]
        assert config[key] == cut["to"] != cut["from"] and cut["why"]
    for key in ("assumed", "stands_for", "family", "runner", "chips"):
        assert config.get(key), key
    family = harness.load_plugin("families", config["family"])
    assert callable(family.build) and callable(family.published)
    reference = harness.load_plugin(
        "reference", config.get("reference", "decoder_f32"))
    assert callable(reference.forward) and callable(reference.cross_entropy)
    assert callable(harness.load_plugin("runners", config["runner"]).run)


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=_ids(MANIFEST["configs"]))
def test_a_catalogued_configuration_keeps_every_published_number(entry):
    if not os.path.exists(CATALOG):
        pytest.skip("no architecture catalog on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = [r for r in rows if r["source_url"] == entry["source"]]
    if not row:
        pytest.skip("the configuration's source is not in the catalog")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    for key, value in row[0]["config"].items():
        if key in entry["reduced"]:
            continue
        assert key in config and config[key] == value, key


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=_ids(MANIFEST["workloads"]))
def test_a_cell_resolves_to_its_files_and_reports_enough(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert "\n" not in cell["why"] and "\t" not in cell["why"]
    entry = harness.by_name(MANIFEST["configs"], cell["config"],
                            "configuration")
    config = harness.read_json(os.path.join(ROOT, entry["file"]))
    assert config["chips"] == cell["chips"]
    traffic = harness.read_json(harness.data_file("traffic",
                                                  cell["traffic"]))
    generator = harness.load_plugin("generators", traffic["kind"])
    assert callable(generator.generate)
    e2e = harness.metrics_of(MANIFEST, "end_to_end", cell["name"])
    assert "setup_s" in _ids(e2e) and len(e2e) >= 2
    assert len(harness.metrics_of(MANIFEST, "per_layer", cell["name"])) >= 1
    if traffic["kind"] == "requests":
        # the mix's longest request fits the engine's table and model
        serve = config["serve"]

        def most(spec):
            return spec["max"] if "max" in spec else spec["value"]

        longest = (most(traffic["prompt_tokens"])
                   + most(traffic["answer_tokens"]))
        assert longest <= config["max_position_embeddings"]
        if config["family"] != "evabyte":
            assert longest <= (serve["max_blocks_per_seq"]
                               * serve["block_size"])


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"],
                         ids=_ids(MANIFEST["end_to_end"]))
def test_an_end_to_end_metric_keeps_its_form(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert harness.UNIT.match(metric["unit"])
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")
    assert metric["better"] in ("lower", "higher")
    assert set(metric.get("workloads", ())) <= set(CELLS)


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=_ids(MANIFEST["per_layer"]))
def test_a_per_layer_metric_resolves_to_its_file_and_reader(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert harness.UNIT.match(metric["unit"])
    assert metric["source"] in SOURCES
    assert metric["better"] in ("lower", "higher")
    assert 1 <= len(metric["layer"]) <= 200
    moved = E2E[metric["moves"]]
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in moved.get("workloads", CELLS), (metric["name"], cell)
    spec = harness.read_json(harness.data_file("layer_metrics",
                                               metric["name"]))
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert spec[key] == metric[key], key
    reader = harness.load_plugin("readers", spec["reader"]["kind"])
    assert callable(reader.read)
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_the_evabyte_cell_is_sized_by_its_cache_kind():
    """The mix's longest request holds the blocks the configuration says:
    the ring and the summaries of the windows it completes, with one to
    spare a slot, so the pool never preempts."""
    from neuronx_distributed_tpu.inference.paging import WindowSummaryCache

    config = harness.read_json(os.path.join(
        BENCH, "configs", "evabyte-6.5b.json"))
    traffic = harness.read_json(harness.data_file("traffic",
                                                  "offline-docs-bytes"))
    serve = config["serve"]
    kind = WindowSummaryCache(config["window_size"], config["chunk_size"]
                              ).geometry(serve["block_size"],
                                         serve["token_budget"])
    longest = (traffic["prompt_tokens"]["max"]
               + traffic["answer_tokens"]["max"])
    assert longest == 8576
    held = kind.blocks_for(longest, serve["block_size"])
    assert held == 17 + 4
    assert serve["num_blocks"] == serve["max_slots"] * (held + 1)
    assert longest <= kind.max_positions(serve["max_blocks_per_seq"],
                                         serve["block_size"])
    # the logit check's two sequences cross two window ends
    chk = serve["logit_check"]
    assert chk["prompt_tokens"] // config["window_size"] == 2
    assert ((chk["prompt_tokens"] + chk["decode_steps"])
            <= serve["max_blocks_per_seq"] * serve["block_size"])


def test_the_nemotron_cell_holds_every_slot_at_its_longest():
    """The mix's longest request holds the blocks the configuration says,
    a slot's states are the bytes it says, and the cell lists its own
    four metrics and none whose work function reads another family's
    keys."""
    from neuronx_distributed_tpu.models import nemotron_h as nh

    name = "nemotron-3-super.serve-reasoning"
    cell = harness.by_name(MANIFEST["workloads"], name, "workload")
    assert (cell["traffic"], cell["chips"]) == ("offline-reasoning-mid", 1)
    config = harness.read_json(os.path.join(
        BENCH, "configs", "nemotron-3-super-120b-a12b.json"))
    traffic = harness.read_json(harness.data_file("traffic",
                                                  cell["traffic"]))
    serve = config["serve"]
    longest = (traffic["prompt_tokens"]["max"]
               + traffic["answer_tokens"]["max"])
    assert longest == 12288 == (serve["max_blocks_per_seq"]
                                * serve["block_size"])
    assert serve["num_blocks"] == (serve["max_slots"]
                                   * serve["max_blocks_per_seq"])
    cfg = nh.NemotronHConfig.from_published(
        {k: config[k] for k in nh.PUBLISHED_KEYS}, num_experts=512,
        experts_held=(0, config["n_routed_experts"]))
    assert cfg == nh.NemotronHConfig(vocab_size=32768,
                                     experts_held=(0, 128))
    kind = cfg.serving_family().cache_kind
    state, tail = (sum(leaf.slot_bytes(2) for leaf in kind.leaves
                       if leaf.counted_as == what)
                   for what in ("state", "tail"))
    assert (state, tail) == (5 * 128 * 8192 * 4, 5 * 3 * 10240 * 2)
    aot = config["assumed"]["serve_aot_gib"]
    gib = 2.0 ** 30
    assert abs(serve["max_slots"] * state / gib - aot["ssm_states"]) < 0.01
    assert abs(serve["num_blocks"] * 128 * 2 * 128 * 2 * 2 / gib
               - aot["kv_pool"]) < 0.01
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if name in m.get("workloads", ())}
    assert {"latent_moe_roofline", "grouped_ssd_state_roofline",
            "latent_proj_share_pct.batch", "moe_experts_idle_pct.batch",
            "moe_held_pct.batch", "moe_dropped_pct.batch",
            "ssm_state_share_pct.batch", "state_bytes_held_pct.batch",
            "paged_attn_share_pct.batch", "unscoped_share_pct.batch"
            } <= listed and len(listed) >= 35   # later PRs append theirs
    assert not listed & {"ssd_state_roofline", "moe_experts_roofline",
                         "paged_attention_roofline", "kda_state_roofline"}
    assert name in E2E["serve_tok_s"]["workloads"]
    # the fourteenth cell: later ones come behind it
    assert [w["name"] for w in MANIFEST["workloads"]].index(name) == 13


def test_the_block_cell_fits_its_pool_and_lists_its_own_rooflines():
    """The mix's longest request and its last block fit a table row, the
    step is every slot's block and a prefill chunk, the family is built
    from the file with its block, and the cell lists its five metrics and
    neither roofline whose work function counts another family's step."""
    from neuronx_distributed_tpu.inference.sampling import BlockDecoding
    from runners import models

    name = "sdar-30b-a3b-chat.serve-blockgen"
    cell = harness.by_name(MANIFEST["workloads"], name, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b-chat", "offline-blockgen", 1)
    config = harness.read_json(os.path.join(
        BENCH, "configs", "sdar-30b-a3b-chat.json"))
    traffic = harness.read_json(harness.data_file("traffic",
                                                  cell["traffic"]))
    serve = config["serve"]
    block = config["block_length"]
    longest = (traffic["prompt_tokens"]["max"]
               + traffic["answer_tokens"]["max"])
    assert longest + block <= (serve["max_blocks_per_seq"]
                               * serve["block_size"])
    assert serve["token_budget"] == serve["max_slots"] * block + 128
    assert serve["block_size"] % block == 0
    assert serve["logit_check"]["group"] == block
    cfg, _, _ = models.build(config)
    assert cfg.block_decoding == BlockDecoding(4, 4, 0.9, 151669)
    assert cfg.serving_family().block is cfg.block_decoding
    assert (cfg.num_experts, cfg.top_k, cfg.intermediate_size,
            cfg.num_heads * cfg.head_dim_, cfg.hidden_size) == (
        128, 8, 768, 4096, 2048)
    aot = config["assumed"]["serve_aot_gib"]
    gib = 2.0 ** 30
    pool = (config["num_hidden_layers"] * serve["num_blocks"]
            * serve["block_size"] * 2 * 4 * 128 * 2)
    assert abs(pool / gib - aot["pool"]) < 0.01
    assert 0.60 <= aot["peak"] / 15.75 <= 0.90
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if name in m.get("workloads", ())}
    assert {"block_rows_uncovered_pct.batch", "block_store_pass_pct.batch",
            "uncover_share_pct.batch", "sdar_moe_experts_roofline",
            "block_paged_attention_roofline", "moe_dropped_pct.batch",
            "paged_attn_share_pct.batch", "step_ms.batch"} <= listed
    assert not listed & {"moe_experts_roofline", "paged_attention_roofline",
                         "moe_held_pct.batch"}
    assert name in E2E["serve_tok_s"]["workloads"]
    # the sixteenth cell: later ones come behind it
    assert [w["name"] for w in MANIFEST["workloads"]].index(name) == 15


def test_the_indexed_latent_cell_fits_two_leaves_and_comes_last_in_its_lists():
    """A position of the cell holds a latent row and an index key a layer;
    every request fits a table row and the pool every table row; the
    family is built from the file with its share; the cell's entries
    stand behind the ones that were there; it lists its own six metrics
    and none that reads the latent kernel, which does not run."""
    from neuronx_distributed_tpu.inference import paging
    from runners import models

    name = "deepseek-v3.2.serve-longdocs"
    cell = harness.by_name(MANIFEST["workloads"], name, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v3.2", "offline-long-docs-32k", 1)
    config = harness.read_json(os.path.join(BENCH, "configs",
                                            "deepseek-v3.2.json"))
    traffic = harness.read_json(harness.data_file("traffic",
                                                  cell["traffic"]))
    serve = config["serve"]
    longest = (traffic["prompt_tokens"]["max"]
               + traffic["answer_tokens"]["max"])
    assert longest <= serve["max_blocks_per_seq"] * serve["block_size"]
    assert serve["num_blocks"] == (serve["max_slots"]
                                   * serve["max_blocks_per_seq"])
    cfg, _, _ = models.build(config)
    kind = cfg.serving_family().cache_kind
    assert isinstance(kind, paging.IndexedLatentCache)
    assert (kind.row, kind.index_row) == (640, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.top_k, cfg.n_group,
            cfg.topk_group, cfg.num_heads, cfg.index_n_heads,
            cfg.index_topk, cfg.first_k_dense, cfg.num_layers) == (
        256, (0, 16), 8, 8, 4, 128, 64, 2048, 1, 5)
    aot = config["assumed"]["serve_aot_gib"]
    pool = (config["num_hidden_layers"] * serve["num_blocks"]
            * serve["block_size"] * (kind.row + kind.index_row) * 2)
    assert abs(pool / 2.0 ** 30 - aot["pool"]) < 0.01
    assert 0.25 <= aot["peak"] / 15.75 <= 0.90
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if name in m.get("workloads", ())}
    own = {"dsa_index_share_pct.batch", "dsa_context_kept_pct.batch",
           "dsa_blocks_named_pct.batch", "dsa_selection_shared_pct.batch",
           "dsa_index_roofline", "dsa_attention_roofline"}
    assert own | {"moe_held_pct.batch", "select_scope_share_pct.batch",
                  "long_context_row_pct.batch", "step_ms.batch"} <= listed
    assert not {m for m in listed if m.startswith(("mla_", "paged_"))}
    assert name in E2E["serve_tok_s"]["workloads"]
    # the seventeenth cell and configuration, the last six metrics as
    # they came: later ones come behind them
    assert [w["name"] for w in MANIFEST["workloads"]].index(name) == 16
    assert [c["name"] for c in MANIFEST["configs"]].index(
        "deepseek-v3.2") == 15
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert {names.index(m) for m in own} == set(range(92, 98))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if name in m.get("workloads", ()) and m["name"] not in own:
            assert m["workloads"].index(name) >= 1
