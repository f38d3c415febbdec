"""Train one cell through the trainer's entry points
(``neuronx_distributed_config`` -> ``initialize_parallel_model`` ->
``initialize_parallel_optimizer`` -> ``make_train_step``): the first
batch's loss against the plain reference before the optimizer state is
built, warm-up, then steps back to back for the window, each ended by
``block_until_ready``, on batches a host thread makes ahead of the step.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import List

import numpy as np

import harness
from harness import BenchError, Observations, RunResult, say
from runners import models

TRACE_WINDOW = "bench/trace_window"
TRACE_STEP = "bench/train_step"
TRACE_INPUT = "bench/input_wait"


class Prefetcher:
    """Batches from the generator on a thread of their own, ``depth`` ahead
    of the step. ``get`` returns the batch and the seconds it waited."""

    def __init__(self, batches, depth: int):
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._error = None
        self._thread = threading.Thread(target=self._fill, args=(batches,),
                                        name="bench-input", daemon=True)
        self._thread.start()

    def _fill(self, batches) -> None:
        try:
            for b in batches:
                while not self._stop.is_set():
                    try:
                        self._q.put(b, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
        except BaseException as e:      # surfaces in get(); thread ends
            self._error = e

    def get(self):
        t0 = time.perf_counter()
        while True:
            if self._error is not None:
                raise BenchError(f"the input thread failed: {self._error!r}")
            try:
                return self._q.get(timeout=0.1), time.perf_counter() - t0
            except queue.Empty:
                continue

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            raise BenchError("the input thread did not stop")


def run(cell) -> RunResult:
    import jax

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model, initialize_parallel_optimizer,
        make_train_step)
    from neuronx_distributed_tpu.utils.device import on_tpu

    settings, mix = cell.config["train"], cell.traffic
    seq, per_step = int(mix["seq_len"]), int(mix["batch"]) * int(mix["seq_len"])
    devices = jax.devices()[:cell.chips]
    ps.destroy_model_parallel()
    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=settings["tensor_parallel_size"],
        optimizer_config=nxd.OptimizerConfig(
            zero_one_enabled=settings["zero1"]),
        activation_checkpoint_config=nxd.ActivationCheckpointConfig(
            mode=settings["activation_checkpoint"]),
        sequence_parallel=settings["sequence_parallel"], devices=devices)
    base, module, _ = models.build(
        cell.config, max_seq_len=seq,
        dtype=models.dtype_of(settings["compute_dtype"]),
        param_dtype=models.dtype_of(settings["param_dtype"]),
        use_flash_attention=settings["flash_attention"])
    mcfg = nxd.configure_model(cfg, base)
    model = type(module)(mcfg)

    gen = harness.load_plugin("generators", mix["kind"])
    batches = gen.generate(mix, cell.seed, mcfg.vocab_size, cell.seconds)
    first = next(batches)
    pm, params = initialize_parallel_model(
        cfg, model, jax.random.key(cell.seed % (2 ** 32)),
        first["input_ids"])
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    say("train", depth=mcfg.num_layers, params=n_params,
        tokens_per_step=per_step, tp=settings["tensor_parallel_size"],
        sequence_parallel=settings["sequence_parallel"],
        remat=mcfg.remat, weights_s=round(cell.clock(), 2))

    # ---- the loss check, while the optimizer state is not there yet ----
    t_check = time.perf_counter()
    atol = float(settings["loss_check"]["atol"])
    reference = models.reference(cell.config)
    loss_fn = jax.jit(lambda p, b: pm.module.apply(
        p, b["input_ids"], b["labels"], method="loss"))
    why = []
    # one sequence at a time: with random labels two different models
    # give mean losses about sigma / sqrt(seq) apart, so each sequence is
    # its own test of the whole forward pass
    for i in range(int(settings["loss_check"]["sequences"])):
        one = {k: v[i:i + 1] for k, v in first.items()}
        got = float(loss_fn(params, one))
        want_logits, _ = reference.forward(
            models.published(params, cell.config), one["input_ids"],
            cell.config)
        want = float(reference.cross_entropy(want_logits, one["labels"]))
        del want_logits
        say("check", sequence=i, loss=got, reference_loss=want,
            abs_diff=abs(got - want), atol=atol)
        harness.compared(f"sequence_{i}.abs_loss_diff", abs(got - want), atol)
        if not abs(got - want) <= atol:
            why.append(f"loss of sequence {i} of the first batch, {got}, "
                       f"differs from the reference's {want} by more "
                       f"than {atol}")
    check_s = time.perf_counter() - t_check
    say("check", seconds=round(check_s, 2))

    tx, state, state_shardings = initialize_parallel_optimizer(
        pm, params, learning_rate=float(settings["learning_rate"]))
    del params
    step = make_train_step(pm, tx, state_shardings)
    feed = Prefetcher(itertools.chain([first], batches),
                      int(mix.get("prefetch", 2)))
    losses: List[float] = []
    try:
        for _ in range(int(settings["warmup_steps"])):
            batch, _ = feed.get()
            state, metrics = step(state, batch)
            jax.block_until_ready(metrics["loss"])
            losses.append(float(metrics["loss"]))
        if on_tpu() != ("tpu_custom_call" in step.lower(
                state, batch).compile().as_text()):
            why.append("the flash kernel is in the step's program exactly "
                       "when the backend is not a TPU")
        say("train", warm_s=round(cell.clock() - check_s, 2),
            first_loss=repr(losses[0]))

        # ---- the measured window ---------------------------------------
        compiles = cell.compiles.count
        setup_s = cell.clock() - check_s
        step_s, wait_s = [], []
        t0 = time.perf_counter()
        while True:
            batch, waited = feed.get()
            t_step = time.perf_counter()
            state, metrics = step(state, batch)
            jax.block_until_ready(metrics["loss"])
            now = time.perf_counter()
            step_s.append(now - t_step)
            wait_s.append(waited)
            losses.append(float(metrics["loss"]))
            if now - t0 >= cell.seconds:
                break
        window_s = now - t0
        compiled_in_window = cell.compiles.count - compiles
        steps = len(step_s)
        say("window", seconds=round(window_s, 3), steps=steps,
            last_loss=losses[-1], compiled_in_window=compiled_in_window)

        observations = None
        if cell.trace:
            observations = Observations(
                config=cell.config, peaks=cell.peaks, chips=cell.chips,
                steps=steps, window_s=window_s)
            observations.series["step_s"] = step_s
            observations.series["input_wait_s"] = wait_s
            observations.scalars["seq_len"] = float(seq)
            observations.scalars["matmul_params"] = float(
                n_params - mcfg.vocab_size * mcfg.hidden_size)
            state = _traced_steps(cell, step, state, feed,
                                  int(settings.get("trace_steps", 4)),
                                  observations, losses)
    finally:
        feed.close()

    bad = sum(1 for x in losses if not np.isfinite(x))
    if compiled_in_window:
        why.append(f"{compiled_in_window} program(s) compiled inside the "
                   "window")
    if bad:
        why.append(f"{bad} step(s) with a non-finite loss")
    e2e = {"train_tok_s": steps * per_step / window_s}
    peak = harness.memory_peak_bytes(devices)
    if observations is not None:
        observations.end_to_end = dict(e2e)
        observations.scalars["memory_peak_bytes"] = float(peak)
    del state
    ps.destroy_model_parallel()
    return RunResult(correct=not why, attempted=len(losses), failed=bad,
                     end_to_end=e2e, setup_s=setup_s, devices=devices,
                     memory_peak_bytes=peak,
                     observations=observations, why_incorrect=why)


def _traced_steps(cell, step, state, feed, n, observations, losses):
    """A few more steps under the profiler, after the window."""
    import jax

    from tracereduce import xplane

    trace_dir = cell.out_path("trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(TRACE_WINDOW):
            for _ in range(n):
                with jax.profiler.TraceAnnotation(TRACE_INPUT):
                    batch, _ = feed.get()
                with jax.profiler.TraceAnnotation(TRACE_STEP):
                    state, metrics = step(state, batch)
                    jax.block_until_ready(metrics["loss"])
                losses.append(float(metrics["loss"]))
    finally:
        jax.profiler.stop_trace()
    trace = xplane.load(xplane.find_xplane(trace_dir))
    observations.traced_steps = n
    if not trace.devices and cell.rehearsal:
        say("trace", device_planes=0, note="rehearsal: no device metrics")
        return state
    observations.trace = trace
    observations.reduction = xplane.reduce(
        trace, xplane.window_of(trace, TRACE_WINDOW), gap_layer=("bench/",))
    say("trace", window_s=round(observations.reduction.window_s, 3),
        busy_s=round(observations.reduction.busy_s, 3), steps=n,
        clock_offset_ms=round(trace.offset_s * 1e3, 3))
    return state
