"""Run one cell of ``BENCHMARK.json`` on the machine this is started on.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no child. It finds the cell in the manifest, the
configuration file the manifest names, the traffic mix at
``benchmarks/traffic/<mix>.json``, the runner at
``benchmarks/runners/<config.runner>.py`` and, in a traced run, each
per-layer metric at ``benchmarks/layer_metrics/<name>.json`` with its
reader at ``benchmarks/readers/<kind>.py``. The last line of standard
output is the result, whose last key ``compared`` holds each number the
check compared beside its limit (they are the last lines of standard
error too); anything else worth keeping is on the lines before it or
under ``benchmarks/out/``. No TPU, too few chips, or a device that
``peaks.json`` does not know, is a non-zero exit and no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # the process's start, as near as Python gets

import argparse
import json
import os
import shutil
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))     # the package under test

import harness
from harness import BenchError, say


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: the "
                         "manifest's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=harness.MANIFEST,
                    help="another manifest of the same schema (the tests' "
                         "rehearsal cells)")
    ap.add_argument("--rate-per-s", type=float, default=None,
                    help="sweep only: offer an open-loop mix at this rate "
                         "instead of the file's; metric names then start "
                         "with 'sweep.'")
    return ap.parse_args(argv)


def load_cell(manifest_path: str, workload: str) -> SimpleNamespace:
    """The cell with everything it names resolved to files."""
    manifest = harness.load_manifest(manifest_path)
    cell = harness.by_name(manifest["workloads"], workload, "workload")
    entry = harness.by_name(manifest["configs"], cell["config"],
                            "configuration")
    config = harness.read_json(os.path.join(harness.ROOT, entry["file"]))
    traffic = harness.read_json(harness.data_file("traffic", cell["traffic"]))
    return SimpleNamespace(manifest=manifest, name=workload, config=config,
                           traffic=traffic, chips=int(cell["chips"]))


def layer_metrics(cell, observations) -> dict:
    """Every per-layer metric of the cell through its reader. A reader that
    finds nothing to read gives None and the metric is left out."""
    out = {}
    for m in harness.metrics_of(cell.manifest, "per_layer", cell.name):
        spec = harness.read_json(harness.data_file("layer_metrics",
                                                   m["name"]))
        reader = harness.load_plugin("readers", spec["reader"]["kind"])
        value = reader.read(spec["reader"], observations)
        if value is None:
            say("metric", name=m["name"], value="nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = _args(argv)
    cell = load_cell(args.manifest, args.workload)
    rehearsal = bool(cell.config.get("rehearsal"))
    if rehearsal and os.path.abspath(args.manifest) == harness.MANIFEST:
        raise BenchError("a rehearsal configuration cannot be a cell of "
                         "BENCHMARK.json")
    seconds = (float(cell.manifest["run_seconds"]) if args.seconds is None
               else args.seconds)
    if args.rate_per_s is not None:
        if cell.traffic.get("arrivals", {}).get("kind") != "poisson":
            raise BenchError("--rate-per-s needs an open-loop mix")
        cell.traffic["arrivals"]["rate_per_s"] = args.rate_per_s

    device = harness.require_device(cell.chips, rehearsal)
    out_dir = os.path.join(harness.HERE, "out", cell.name)

    def out_path(name: str) -> str:
        path = os.path.join(out_dir, name)
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path, exist_ok=True)
        return path

    say("run", workload=cell.name, seed=args.seed, seconds=seconds,
        trace=args.trace, compile_cache=harness.place_compile_cache())
    runner = harness.load_plugin("runners", cell.config["runner"])
    result = runner.run(SimpleNamespace(
        name=cell.name, config=cell.config, traffic=cell.traffic,
        seed=args.seed, seconds=seconds, trace=bool(args.trace),
        chips=cell.chips, rehearsal=rehearsal, out_path=out_path,
        clock=harness.Stopwatch(_T0), compiles=harness.CompileCounter(),
        peaks=None if rehearsal else harness.peaks_for(device["kind"])))
    for reason in result.why_incorrect:
        say("incorrect", why=reason)

    unit = {m["name"]: m["unit"] for m in cell.manifest["end_to_end"]}
    if args.trace:
        metrics = layer_metrics(cell, result.observations)
    else:
        values = dict(result.end_to_end, setup_s=result.setup_s)
        metrics = {
            m["name"]: {"value": float(values[m["name"]]),
                        "unit": unit[m["name"]]}
            for m in harness.metrics_of(cell.manifest, "end_to_end",
                                        cell.name)}
    prefix = ("rehearsal." if rehearsal      # never a device metric's name
              else "sweep." if args.rate_per_s is not None else "")
    metrics = {prefix + k: v for k, v in metrics.items()}
    device = dict(device, memory_peak_bytes=int(result.memory_peak_bytes))
    line = {"correct": bool(result.correct),
            "attempted": int(result.attempted), "failed": int(result.failed),
            "metrics": metrics, "device": device}
    red = result.observations and result.observations.reduction
    if args.trace and red is not None:
        from tracereduce import xplane

        device["busy_s"], device["window_s"] = red.busy_s, red.window_s
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in xplane.top_ops(red, 10)],
            "idle_gaps": [[n, s] for n, s in red.idle_gaps[:10]]}
    # each number compared beside its limit: the result's last key, and
    # the last lines of standard error
    line["compared"] = dict(harness.COMPARED)
    print(json.dumps(line), flush=True)
    for name, (number, limit) in line["compared"].items():
        print(f"compared {name} {number} limit {limit}", file=sys.stderr,
              flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
