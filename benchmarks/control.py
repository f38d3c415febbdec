"""The control of a cell's ``correct``: the plain reference, put in the
program's place and computed one precision below the one the
configuration states, has to come out as not correct.

    python benchmarks/control.py --workload <cell> --seeds 1,2,3 [--kinds fp8,int8,tier-int8]

For each seed, in one process and with no timed window, a serve cell's
model and weights as ``runners/serve.py`` makes them (``serve.prepare``),
the program's logits for the logit check's two sequences (the paged
forward), the reference's, and the controls':

- ``fp8``, ``int8``: the same reference over the same published tensors
  with every matrix rounded to the lower type (cast to float8_e4m3fn and
  back; symmetric int8 with one scale to an output channel), norms
  untouched, arithmetic still float32. That is the mildest form of such a
  step (activations keep their precision), so whatever else a
  lower-precision path does reads farther off.
- ``tier-int8``: the program itself with its own weight-only int8 tier
  switched on (``weight_quant="int8"``), through the same paged forward.

Each is held to the configuration's ``logit_check`` by
``serve.judge_logits``; the last line is one JSON object of the readings.
For a train cell the control's loss of each sequence of the first batch
is held to the reference's by the configuration's ``loss_check.atol``, on
one chip and on bf16 weights of ``harness.make_weights`` (fp32 ones and a
4,096-token sequence's float32 scores do not fit one chip together; the
program's own differences are on the ``[check]`` lines of every training
run).
Not part of a benchmark run; ``PERF.md`` has the readings the limits were
set from.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import harness
from harness import say


def lower_precision(weights, kind: str):
    """``weights`` with every matrix rounded through ``kind``."""
    import jax.numpy as jnp

    def rounded(w):
        if kind == "fp8":
            return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        scale = jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0
        return jnp.round(w / scale) * scale

    def read(name, layer=None, expert=None):
        w = weights(name, layer, expert)
        return rounded(w) if w.ndim == 2 else w

    return read


def readings(got, want, chk) -> dict:
    """The numbers ``judge_logits`` compares, and whether they pass."""
    import numpy as np

    from runners import serve

    out = {"correct": not serve.judge_logits(got, want, chk)}
    for part, e in serve.logit_errors(got, want, chk)[2].items():
        out[part] = {"median": float(np.median(e)), "max": float(e.max()),
                     "share_over": float(np.mean(e > chk["outlier_rtol"]))}
    return out


def tier_params(qcfg, params):
    """``params`` through the program's own converter, as a tree on the
    host. A layer at a time: the converter works on the host in float32,
    and over the whole stacked tree its copies pass a one-chip machine's
    40 GiB; and the device cannot hold both trees beside the pool."""
    import jax
    import numpy as np

    from neuronx_distributed_tpu.quantization.serving import (
        quantize_params_for_serving)

    def in_layers(path):
        return "layers" in jax.tree_util.keystr(path)

    parts = [jax.device_get(quantize_params_for_serving(
        qcfg, jax.tree_util.tree_map_with_path(
            lambda path, x: x[i:i + 1] if in_layers(path) else x, params)))
        for i in range(qcfg.num_layers)]
    return jax.tree_util.tree_map_with_path(
        lambda path, *xs: np.concatenate(xs) if in_layers(path) else xs[0],
        *parts)


def serve_control(cell, seeds, kinds) -> dict:
    """The program's and each control's readings against the reference."""
    import dataclasses

    import jax
    import numpy as np

    from runners import models, serve

    chk = cell.config["serve"]["logit_check"]
    reference = models.reference(cell.config)
    out = {}
    for seed in seeds:
        cell.seed = seed
        mcfg, forward, params, ecfg = serve.prepare(cell)
        say("control", seed=seed, what="program")
        seqs, got = serve.probe_logits(seed, mcfg, forward, params, ecfg, chk)
        weights = models.published(params, cell.config)
        want = np.asarray(serve.reference_logits(
            reference, weights, seqs, cell.config, chk)[0])
        out[seed] = {"program": readings(got, want, chk)}
        for kind in kinds:
            if kind == "tier-int8":     # below: it gives up the float weights
                continue
            say("control", seed=seed, what=kind)
            ctl = np.asarray(serve.reference_logits(
                reference, lower_precision(weights, kind), seqs,
                cell.config, chk)[0])
            out[seed][kind] = readings(ctl, want, chk)
        if "tier-int8" in kinds:
            say("control", seed=seed, what="tier-int8")
            qcfg = dataclasses.replace(mcfg, weight_quant="int8")
            qparams = tier_params(qcfg, params)
            params = weights = None
            out[seed]["tier-int8"] = readings(serve.probe_logits(
                seed, qcfg, forward, jax.device_put(qparams), ecfg, chk)[1],
                want, chk)
        params = weights = qparams = None
    return out


def train_control(cell, seeds, kinds) -> dict:
    """|control loss - reference loss| of each checked sequence of the
    first batch, against ``loss_check.atol``."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from neuronx_distributed_tpu.parallel import mesh as ps
    from runners import models

    config, mix = cell.config, cell.traffic
    chk = config["train"]["loss_check"]
    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    _, model, _ = models.build(config, max_seq_len=int(mix["seq_len"]),
                               dtype=jnp.float32, param_dtype=jnp.bfloat16)
    shapes = meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    reference = models.reference(config)
    gen = harness.load_plugin("generators", mix["kind"])
    out = {}
    for seed in seeds:
        params = harness.make_weights(shapes, seed,
                                      float(config["initializer_range"]))
        weights = models.published(params, config)
        first = next(gen.generate(mix, seed, config["vocab_size"], 1.0))
        out[seed] = {k: {"abs_diff": []} for k in kinds}
        for i in range(int(chk["sequences"])):
            ids, labels = (first[k][i:i + 1] for k in ("input_ids", "labels"))
            want = float(reference.cross_entropy(
                reference.forward(weights, ids, config)[0], labels))
            for kind in kinds:
                got = float(reference.cross_entropy(reference.forward(
                    lower_precision(weights, kind), ids, config)[0], labels))
                say("control", seed=seed, what=kind, sequence=i, loss=got,
                    reference_loss=want, abs_diff=abs(got - want),
                    atol=chk["atol"])
                out[seed][kind]["abs_diff"].append(abs(got - want))
        for r in out[seed].values():
            r["correct"] = all(d <= float(chk["atol"]) for d in r["abs_diff"])
        del params, weights
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--kinds", default="fp8",
                    help="comma-separated: fp8, int8, tier-int8")
    ap.add_argument("--manifest", default=harness.MANIFEST)
    args = ap.parse_args(argv)

    import run as bench_run

    cell = bench_run.load_cell(args.manifest, args.workload)
    cell.clock = harness.Stopwatch()
    harness.require_device(1, bool(cell.config.get("rehearsal")))
    harness.place_compile_cache()
    control = {"serve": serve_control, "train": train_control}[
        cell.config["runner"]]
    print(json.dumps(control(cell, [int(s) for s in args.seeds.split(",")],
                             args.kinds.split(","))), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.BenchError as e:
        print(f"benchmarks/control.py: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
