"""``minicpm-sala.serve-longdocs`` rehearsed on the CPU: the cell's runner,
family, reference, per-layer metric files and readers through ``run.py``,
from a manifest written in ``tmp_path`` (``tests/manifest.json`` is not
edited). The configuration is ``tests/configs/tiny-minicpm-sala.json``:
selection blocks of 8, dense below 64, prompts of 64 to 160, so every
request crosses the dense threshold as the cell's do."""

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

REAL = "minicpm-sala.serve-longdocs"
CELL = "tiny-minicpm-sala.serve-longdocs"
NEW_METRICS = ("sparse_attn_share_pct.batch", "sparse_attention_roofline",
               "sparse_cols_live_pct.batch", "sparse_context_kept_pct.batch",
               "select_share_pct.batch", "lightning_share_pct.batch")


def _manifest(tmp_path) -> str:
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    real = harness.load_manifest()
    m["configs"].append({
        "name": "tiny-minicpm-sala", "source": "none (rehearsal)",
        "file": "benchmarks/tests/configs/tiny-minicpm-sala.json",
        "reduced": [], "why": "the minicpm_sala family at toy widths"})
    m["workloads"].append({"name": CELL, "config": "tiny-minicpm-sala",
                           "traffic": "tiny-long-docs", "chips": 1,
                           "why": "rehearsal of " + REAL})
    shared = [x["name"] for x in real["end_to_end"] + real["per_layer"]
              if REAL in x.get("workloads", ())]
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
                  str(2 ** 31 + 32), "--seconds", "2", "--trace", trace])
        assert p.returncode == 0, p.stderr[-3000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert line["correct"], p.stdout[-3000:]
        assert line["failed"] == 0 and line["attempted"] > 0
        got = line["metrics"]
        if trace == "0":
            assert set(got) == {"rehearsal.serve_tok_s", "rehearsal.setup_s"}
            continue
        # the host's metrics read the counters the device handed back; the
        # CPU has no device plane, so the device metrics find nothing
        for name in ("sparse_cols_live_pct.batch",
                     "sparse_context_kept_pct.batch", "step_ms.batch",
                     "rows_per_step.batch", "prefill_row_share_pct.batch"):
            assert "rehearsal." + name in got, (name, sorted(got))
        live = got["rehearsal.sparse_cols_live_pct.batch"]["value"]
        kept = got["rehearsal.sparse_context_kept_pct.batch"]["value"]
        assert 0 < live < 100 and 0 < kept < 100
        assert "compiled_in_window=0" in p.stdout


def test_the_roofline_work_counts_the_positions_attended():
    """By hand at blocks of 8, top 6, dense below 64, 4 heads over 2 K/V
    heads of 16, three sparse layers: a prefill chunk at positions 60..66
    (four rows dense, three selecting), then a decode row at 67."""
    from readers import sparse_roofline

    obs = harness.Observations(
        config=dict(num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16,
                    mixer_types=["minicpm4", "lightning-attn", "minicpm4",
                                 "minicpm4"],
                    sparse=dict(block=8, topk=6, dense_len=64)),
        peaks=None, chips=1)
    assert sparse_roofline.work(obs) is None          # nothing to read
    obs.series["traced_slot_lengths"] = [np.array([60, 0]),
                                         np.array([67, 0]),
                                         np.array([67, 0])]
    flops, nbytes = sparse_roofline.work(obs)
    # 60..63 attend every causal position; 64..66 five whole blocks and
    # their own up to themselves; the decode row at 67 likewise
    attended = sum(range(61, 65)) + (41 + 42 + 43) + 44
    assert flops == 3 * 4 * 4 * 16 * attended
    # a slot's step reads what its last row attends, K and V in 2 bytes
    assert nbytes == 3 * 2 * (43 + 44) * 2 * 16 * 2
