"""``glm-4.7-flash.serve-agentic`` rehearsed on the CPU: the cell's runner,
family, reference, per-layer metric files and readers through ``run.py``,
from a manifest written in ``tmp_path`` (``tests/manifest.json`` is not
edited). The configuration is ``tests/configs/tiny-glm-moe-lite.json``:
a dense layer and two expert layers of 8 experts, a pool row of 128 lanes,
prompts of 16 to 128 and answers of 12 to 48, so decode rows run beside
prefill chunks as the cell's do."""

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
from manifest_checks import brought_for  # noqa: E402

REAL = "glm-4.7-flash.serve-agentic"
CELL = "tiny-glm-moe-lite.serve-agentic"
NEW_METRICS = ("mla_attn_share_pct.batch", "mla_attention_roofline",
               "moe_expert_share_pct.batch", "moe_dropped_pct.batch")


def _shared(real) -> list:
    """What the manifest holds for the real cell, counted and not a
    literal."""
    return [x["name"] for x in real["end_to_end"] + real["per_layer"]
            if REAL in x.get("workloads", ())]


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-glm-moe-lite", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-glm-moe-lite.json",
        "reduced": [], "why": "the glm_moe_lite family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-glm-moe-lite",
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
    real = harness.load_manifest()
    shared = _shared(real)
    assert set(NEW_METRICS) < set(shared) and "serve_tok_s" in shared
    # a later PR's metrics of the same kernel head their lists with it too
    assert set(NEW_METRICS) <= set(brought_for(real, REAL))
    cell = harness.by_name(real["workloads"], REAL, "cell")
    assert (cell["chips"], cell["traffic"]) == (1, "offline-agentic-code")
    traffic = harness.read_json(harness.data_file("traffic",
                                                  cell["traffic"]))
    assert traffic["prompt_tokens"] == dict(
        dist="lognormal", median=4096, sigma=0.6, min=1024, max=16384)
    assert traffic["answer_tokens"] == dict(
        dist="lognormal", median=2048, sigma=0.3, min=1024, max=4096)
    assert traffic["arrivals"] == dict(kind="all_at_zero", count=256)
    assert (traffic["order_seed"], traffic["lead_in_s"]) == (23, 60)
    config = harness.read_json(os.path.join(
        ROOT, harness.by_name(real["configs"], cell["config"],
                              "configuration")["file"]))
    # every width as published; the pool row is what the kernel reads
    assert (config["kv_lora_rank"], config["qk_rope_head_dim"],
            config["n_routed_experts"], config["vocab_size"]) == (
        512, 64, 64, 154880)
    assert sorted(config["reduced"]) == ["num_hidden_layers",
                                         "num_nextn_predict_layers"]
    assert config["serve"]["paged_attention"] == "pallas"
    for key in ("lane_layout", "initializer_range", "rotary", "precision"):
        assert key in config["assumed"], key


def test_the_cell_is_rehearsed_from_files_alone(tmp_path):
    manifest = _manifest(tmp_path)
    for trace in ("0", "1"):
        p = _run(["--manifest", manifest, "--workload", CELL, "--seed",
                  str(2 ** 31 + 34), "--seconds", "2", "--trace", trace])
        assert p.returncode == 0, p.stderr[-3000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert line["correct"], p.stdout[-3000:]
        assert line["failed"] == 0 and line["attempted"] > 0
        got = line["metrics"]
        if trace == "0":
            assert set(got) == {"rehearsal.serve_tok_s", "rehearsal.setup_s"}
            continue
        # the host's metrics read the counters; the CPU has no device
        # plane, so the device metrics find nothing
        for name in ("moe_dropped_pct.batch", "paged_cols_live_pct.batch",
                     "paged_block_shared_pct.batch", "step_ms.batch",
                     "rows_per_step.batch", "prefill_row_share_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        assert got["rehearsal.moe_dropped_pct.batch"]["value"] == 0
        assert 0 < got["rehearsal.paged_cols_live_pct.batch"]["value"] < 100
        assert "compiled_in_window=0" in p.stdout
        assert "router_margin_p01" in p.stdout


def test_the_roofline_work_counts_rows_once_a_slot_and_every_head():
    """By hand at 4 heads over a latent of 32 and a rotary key of 8, three
    layers: a prefill chunk at positions 60..66, then a decode row at 67
    beside a new request's chunk 0..4."""
    from readers import mla_roofline

    obs = harness.Observations(
        config=dict(num_attention_heads=4, kv_lora_rank=32,
                    qk_rope_head_dim=8, num_hidden_layers=3),
        peaks=None, chips=1)
    assert mla_roofline.work(obs) is None             # nothing to read
    obs.series["traced_slot_lengths"] = [np.array([60, 0]),
                                         np.array([67, 0]),
                                         np.array([67, 5])]
    flops, nbytes = mla_roofline.work(obs)
    attended = sum(range(61, 68)) + 68 + sum(range(1, 6))
    assert flops == 3 * 2 * 4 * (2 * 32 + 8) * attended
    # a slot's step reads what its last row attends, 40 values in 2 bytes,
    # and every row its queries and outputs
    rows = 7 + 1 + 5
    assert nbytes == 3 * 2 * (40 * (67 + 68 + 5) + 4 * 72 * rows)


def test_the_expert_share_reads_gate_up_and_the_down_matmul_by_its_stack():
    """A layer of the cell's packed step as the profiler names its events
    (the operands with their types), a millisecond each: the routed
    experts are the dispatched rows, gate, up and the down matmul, whose
    result type is o_proj's and the shared expert's too; the ``while``
    that carries every stack is no operation of its own."""
    from readers import device_text_share
    from tracereduce import xplane

    t = "{2,1,0:T(8,128)(2,1)}"
    routed = [
        f"%fusion.383 = bf16[64,128,2048]{t} fusion(bf16[128,64,128]{t} "
        f"%gte.1227, bf16[1,128,2048]{t} %gte.1216), kind=kOutput",
        f"%fusion.384 = bf16[64,128,1536]{t} fusion(bf16[64,128,2048]{t} "
        f"%fusion.383, bf16[6,64,2048,1536]{t} %gte.1328, s32[] %i), "
        f"kind=kOutput",
        f"%fusion.385 = bf16[64,128,1536]{t} fusion(bf16[64,128,2048]{t} "
        f"%fusion.383, bf16[6,64,2048,1536]{t} %gte.1329, s32[] %i, "
        f"bf16[64,128,1536]{t} %fusion.384), kind=kOutput",
        f"%fusion.386 = bf16[1,128,2048]{t} fusion(bf16[1,128,2048]{t} "
        f"%gte.1216, bf16[128,2048]{t} %fusion.376, bf16[128,64,128]{t} "
        f"%gte.1226, bf16[64,128,1536]{t} %fusion.385, "
        f"bf16[6,64,1536,2048]{t} %gte.1327, s32[] %i), kind=kOutput"]
    rest = [
        f"%fusion.376 = bf16[128,2048]{t} fusion(bf16[128,1536]{t} "
        f"%fusion.375, bf16[6,1536,2048]{t} %gte.1332, s32[] %i), "
        f"kind=kOutput",
        f"%fusion.360 = bf16[1,128,2048]{t} fusion(bf16[1,128,5120]{t} "
        f"%fusion.359, bf16[6,5120,2048]{t} %gte.1321, s32[] %i), "
        f"kind=kOutput",
        f"%fusion.370 = f32[128,64]{t} fusion(bf16[1,128,2048]{t} %x, "
        f"bf16[6,2048,64]{t} %gte.1331), kind=kOutput",
        f"%mla_paged_attention = bf16[16,192,512]{t} custom-call(s32[16] "
        f"%a, bf16[7,4096,128,640]{t} %rows), custom_call_target="
        f'"tpu_custom_call"']
    loop = (f"%while.7 = (s32[], bf16[1,128,2048]{t}, "
            f"bf16[6,64,1536,2048]{t}, bf16[6,64,2048,1536]{t}) "
            f"while((s32[], bf16[1,128,2048]{t}, bf16[6,64,1536,2048]{t}, "
            f"bf16[6,64,2048,1536]{t}) %tuple), condition=%c, body=%b")
    ms = 1e-3
    ops = [xplane.Event(loop, 0.0, 8 * ms)] + [
        xplane.Event(text, i * ms, (i + 1) * ms)
        for i, text in enumerate(routed + rest)]
    trace = xplane.Trace({0: xplane.DeviceTimeline(ops=ops)}, [], 0.0)
    obs = harness.Observations(config={}, peaks=None, chips=1, trace=trace,
                               reduction=xplane.reduce(trace, (0.0, 8 * ms)))
    args = harness.read_json(os.path.join(
        BENCH, "layer_metrics", "moe_expert_share_pct.batch.json"))["reader"]
    assert args["kind"] == "device_text_share"
    np.testing.assert_allclose(device_text_share.read(args, obs), 50.0)
    # the result types alone (readers/device_result_share.py) lose the
    # down matmul
    from readers import device_result_share

    by_result = device_result_share.read(
        {"match": r"\[64,128,(2048|1536)\]"}, obs)
    np.testing.assert_allclose(by_result, 37.5)
    obs.trace = None
    assert device_text_share.read(args, obs) is None
