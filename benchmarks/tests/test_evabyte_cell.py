"""``evabyte.serve-docs`` rehearsed on the CPU: the cell's runner, family,
reference, per-layer metric files and reader through ``run.py``, from a
manifest written in ``tmp_path`` (``tests/manifest.json`` is not edited).
The configuration is ``tests/configs/tiny-evabyte.json``: window 32, chunk
4, blocks of 8, so requests cross window ends as the cell's do."""

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

CELL = "tiny-evabyte.serve-docs"
NEW_METRICS = ("eva_attn_share_pct.batch", "eva_attention_roofline",
               "eva_summary_col_share_pct.batch", "eva_cols_live_pct.batch",
               "roll_ms_per_step.batch")


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-evabyte", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-evabyte.json",
        "reduced": [], "why": "the evabyte family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-evabyte",
                           "traffic": "tiny-docs-bytes", "chips": 1,
                           "why": "rehearsal of evabyte.serve-docs"})
    shared = [x["name"] for x in real["end_to_end"] + real["per_layer"]
              if "evabyte.serve-docs" in x.get("workloads", ())]
    assert set(NEW_METRICS) < set(shared)
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


def test_the_cell_is_rehearsed_from_files_alone(tmp_path):
    manifest = _manifest(tmp_path)
    for trace in ("0", "1"):
        p = _run(["--manifest", manifest, "--workload", CELL, "--seed",
                  str(2 ** 31 + 27), "--seconds", "2", "--trace", trace])
        assert p.returncode == 0, p.stderr[-3000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert line["correct"], p.stdout[-3000:]
        assert line["failed"] == 0 and line["attempted"] > 0
        got = line["metrics"]
        if trace == "0":
            assert set(got) == {"rehearsal.serve_tok_s", "rehearsal.setup_s"}
            continue
        # the host's metrics read the new spans and counters; the CPU has
        # no device plane, so the two device metrics find nothing to read
        for name in ("eva_summary_col_share_pct.batch",
                     "eva_cols_live_pct.batch", "roll_ms_per_step.batch",
                     "step_ms.batch", "rows_per_step.batch",
                     "prefill_row_share_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        live = got["rehearsal.eva_cols_live_pct.batch"]["value"]
        linear = got["rehearsal.eva_summary_col_share_pct.batch"]["value"]
        assert 0 < linear < live < 100
        assert "compiled_in_window=0" in p.stdout


def test_the_roofline_work_counts_exact_and_summary_rows():
    """By hand at window 32, chunk 4 (8 summaries a window), 2 heads of 4,
    one layer: a prefill chunk of 6 rows at positions 32..37 (the start of
    window 1), then a decode row at 38; last, a chunk that straddles the
    window's end at 31."""
    from readers import eva_roofline

    obs = harness.Observations(
        config=dict(num_attention_heads=2, num_key_value_heads=2,
                    hidden_size=8, window_size=32, chunk_size=4,
                    num_hidden_layers=1),
        peaks=None, chips=1)
    assert eva_roofline.work(obs) is None           # nothing to read
    obs.series["traced_slot_lengths"] = [np.array([32, 0]),
                                         np.array([38, 0]),
                                         np.array([38, 0])]
    flops, nbytes = eva_roofline.work(obs)
    # rows 32..37 of window 1: 1..6 exact rows and 8 summaries each;
    # then the decode row at 38: 7 exact and 8 summaries
    attended = sum(range(1, 7)) + 6 * 8 + 7 + 8
    assert flops == 4 * 2 * 4 * attended
    # the chunk reads 6 exact rows and 8 summary rows, the decode row 7
    # and 8, K and V in 2 bytes
    assert nbytes == 2 * ((6 + 8) + (7 + 8)) * 2 * 4 * 2
    np.testing.assert_array_equal(eva_roofline.rows_of(26, 34),
                                  np.arange(26, 34))
    obs.series["traced_slot_lengths"] = [np.array([26]), np.array([34])]
    _, straddle = eva_roofline.work(obs)            # 26..33 crosses 31|32
    assert straddle == 2 * (32 + 2 + 8) * 2 * 4 * 2
