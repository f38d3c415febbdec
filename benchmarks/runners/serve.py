"""Serve one cell through ``ServingEngine``: weights from the seed, warm-up,
lead-in, the measured window, the drain, and the logit check against the
plain reference.

The engine is driven by ``step()`` from this one thread. Every request is
submitted before the lead-in with the time at which it is due
(``arrival_time``): the engine admits it no earlier and times it from
then, so the load is an open loop whatever the engine's pace, and a
backlog (``all_at_zero``) is the same code with every request due at once.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Tuple

import numpy as np

import harness
from harness import BenchError, Observations, RunResult, say
from runners import models

TRACE_WINDOW = "bench/trace_window"
TRACE_STEP = "bench/engine_step"
TRACE_IDLE = "bench/no_request_due"


class EngineClock:
    """The engine's clock, injected, so the runner knows the engine's zero:
    the first reading is the one the engine keeps as its start."""

    def __init__(self):
        self.t0 = None

    def __call__(self) -> float:
        t = time.monotonic()
        if self.t0 is None:
            self.t0 = t
        return t

    def now(self) -> float:
        return time.monotonic() - self.t0


def _engine_config(s: dict, dtype):
    from neuronx_distributed_tpu.inference.engine import EngineConfig

    return EngineConfig(
        block_size=s["block_size"], num_blocks=s["num_blocks"],
        max_slots=s["max_slots"],
        max_blocks_per_seq=s["max_blocks_per_seq"],
        token_budget=s["token_budget"], kv_dtype=dtype)


def prepare(cell):
    """``(mcfg, forward, params, ecfg)``: the cell's model under its serve
    settings, its weights from ``cell.seed`` and the engine's geometry."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from neuronx_distributed_tpu.parallel import mesh as ps

    settings = cell.config["serve"]
    dtype = models.dtype_of(settings["dtype"])
    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    mcfg, model, forward = models.build(
        cell.config, dtype=dtype, param_dtype=dtype,
        **settings.get("model", {}))
    say("serve", device_ready_s=round(cell.clock(), 2))
    shapes = meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = harness.make_weights(shapes, cell.seed,
                                  float(cell.config["initializer_range"]))
    jax.block_until_ready(params)
    say("serve", weights_s=round(cell.clock(), 2),
        params=sum(x.size for x in jax.tree_util.tree_leaves(params)))
    return mcfg, forward, params, _engine_config(settings, dtype)


def run(cell) -> RunResult:
    import jax

    from neuronx_distributed_tpu import obs
    from neuronx_distributed_tpu.inference.engine import ServingEngine
    from neuronx_distributed_tpu.ops.paged_attention import (
        paged_attention_impl)

    settings = cell.config["serve"]
    mcfg, forward, params, ecfg = prepare(cell)
    impl = paged_attention_impl(mcfg.head_dim_, ecfg.block_size,
                                mcfg.attn_force_pallas)
    want_impl = settings["paged_attention"]
    say("serve", depth=mcfg.num_layers, paged_attention=impl,
        block_size=ecfg.block_size, num_blocks=ecfg.num_blocks,
        max_slots=ecfg.max_slots, token_budget=ecfg.token_budget)

    gen = harness.load_plugin("generators", cell.traffic["kind"])
    trace_s = float(settings.get("trace_seconds", 3.0)) if cell.trace else 0.0
    traffic = gen.generate(cell.traffic, cell.seed, mcfg.vocab_size,
                           cell.seconds + trace_s)
    requests, lead_in = traffic["requests"], traffic["lead_in_s"]
    open_loop = traffic["open_loop"]

    if cell.trace:
        obs.enable()
    clock = EngineClock()
    engine = ServingEngine(mcfg, params, ecfg, clock=clock)

    # warm-up: the packed step, the retirement of a request (which clears
    # the freed blocks' positions) and every small host-side program
    rng = np.random.default_rng(cell.seed)
    warm = [engine.submit(rng.integers(0, mcfg.vocab_size, n).tolist(), 3)
            for n in (ecfg.block_size + 2, 5)]
    while engine.has_work():
        engine.step()
    for uid in warm:
        if engine.results[uid].status != "completed":
            raise BenchError(f"warm-up request {uid} did not complete")
    say("serve", warm_s=round(cell.clock(), 2),
        compile_count=engine.compile_count())

    base = clock.now() + 0.05 + 2e-4 * len(requests)
    uids = [engine.submit(r.prompt, r.max_new_tokens,
                          arrival_time=base + r.arrival_s)
            for r in requests]
    t_start, t_end = base + lead_in, base + lead_in + cell.seconds
    sample = [u for u, r in zip(uids, requests)
              if lead_in <= r.arrival_s < lead_in + cell.seconds]

    def drive(until: float, rows: List[int], lens: List[np.ndarray] = None):
        """Step until the engine's clock passes ``until``; returns the time
        of the step boundary it stopped at. With ``lens`` (the traced
        segment) every step is annotated for the profiler and the slots'
        resident lengths are kept."""
        annotate = (jax.profiler.TraceAnnotation if lens is not None
                    else lambda name: contextlib.nullcontext())
        while True:
            now = clock.now()
            if now >= until:
                return now
            with annotate(TRACE_STEP):
                n = engine.step()
            if n:
                rows.append(n)
                if lens is not None:
                    lens.append(np.asarray(engine.cache.lengths))
            elif not engine.has_work():
                return now
            else:
                with annotate(TRACE_IDLE):
                    time.sleep(0.001)

    arrivals = np.array([base + r.arrival_s for r in requests])

    def backlog() -> int:
        """Requests due by now and not finished (in a slot or waiting)."""
        return int((arrivals <= clock.now()).sum()) - (
            len(engine.results) - len(warm))

    drive(t_start, [])
    # ---- the measured window -------------------------------------------
    backlog_start = backlog()
    if cell.trace:
        obs.get_tracer().reset()
        obs.get_registry().reset()
    compiles = cell.compiles.count
    st = engine.stats
    at_start = (st.steps, st.tokens_generated, st.preempted, st.rejected,
                st.completed, len(st.step_latency_s))
    setup_s = cell.clock()
    rows: List[int] = []
    t0 = clock.now()
    t1 = drive(t_end, rows)
    window_s = t1 - t0
    steps = st.steps - at_start[0]
    tokens = st.tokens_generated - at_start[1]
    compiled_in_window = cell.compiles.count - compiles
    step_latency = list(st.step_latency_s[at_start[5]:])
    say("window", seconds=round(window_s, 3), steps=steps, tokens=tokens,
        completed=st.completed - at_start[4],
        preempted=st.preempted - at_start[2],
        backlog_start=backlog_start, backlog_end=backlog(),
        compiled_in_window=compiled_in_window,
        step_ms_p50=round(harness.percentile(step_latency, 50) * 1e3, 2),
        step_ms_max=round(max(step_latency) * 1e3, 2),
        steps_over_twice_p50=int(np.sum(
            np.asarray(step_latency)
            > 2 * harness.percentile(step_latency, 50))),
        between_steps_s=round(window_s - sum(step_latency), 3))

    observations = None
    if cell.trace:
        observations = Observations(config=cell.config, peaks=cell.peaks,
                                    chips=cell.chips, steps=steps,
                                    window_s=window_s)
        tracer = obs.get_tracer()
        events = [e for e in tracer.chrome_trace()["traceEvents"]
                  if e.get("ph") == "X" and e["name"].startswith("engine/")]
        observations.span_self_s = harness.span_self_times(events)
        observations.series["step_latency_s"] = step_latency
        observations.series["rows_per_step"] = rows
        _traced_segment(cell, engine, clock, drive, t1 + trace_s,
                        observations)

    # ---- the drain: no more arrive; the sampled requests finish --------
    t_drain = clock.now()
    if open_loop:
        limit = t_drain + float(settings.get("drain_max_s", 90.0))
        while (clock.now() < limit
               and any(u not in engine.results for u in sample)):
            if not engine.step():
                time.sleep(0.001)
    say("drain", seconds=round(clock.now() - t_drain, 3))

    attempted, failed, e2e = _end_to_end(
        cell, engine, sample, requests, uids, open_loop, tokens, window_s,
        st.preempted - at_start[2], st.rejected - at_start[3],
        st.completed - at_start[4])
    if cell.trace:
        reg = obs.get_registry()
        for name in ("nxd_request_queue_seconds", "nxd_request_ttft_seconds",
                     "nxd_request_tpot_seconds"):
            metric = reg.get(name)
            if metric is not None:
                observations.histograms[name] = [
                    v for child in metric.children()
                    for v in child.samples()]
        observations.end_to_end = dict(e2e)
        obs.disable()

    why = []
    if compiled_in_window or engine.compile_count() != 1:
        why.append(f"{compiled_in_window} program(s) compiled inside the "
                   f"window; packed step compiled "
                   f"{engine.compile_count()} time(s)")
    if impl != want_impl:
        why.append(f"paged attention ran {impl!r}, the cell wants "
                   f"{want_impl!r}")
    peak = harness.memory_peak_bytes(jax.devices()[:cell.chips])
    pool_free = engine.pool_free_blocks()
    del engine
    t_check = time.perf_counter()
    why += check_logits(cell, mcfg, forward, params, ecfg, settings)
    say("check", seconds=round(time.perf_counter() - t_check, 2),
        pool_free_blocks_at_end=pool_free)
    if observations is not None:
        observations.scalars["memory_peak_bytes"] = float(peak)
    return RunResult(correct=not why, attempted=attempted, failed=failed,
                     end_to_end=e2e, setup_s=setup_s,
                     devices=jax.devices()[:cell.chips],
                     memory_peak_bytes=peak,
                     observations=observations, why_incorrect=why)


def _traced_segment(cell, engine, clock, drive, until, observations) -> None:
    """A few more seconds of the same load under the profiler, after the
    window, so that the window's host numbers are taken with it off."""
    import jax

    from tracereduce import xplane

    trace_dir = cell.out_path("trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    rows, lens = [], [np.asarray(engine.cache.lengths)]
    anchor = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(TRACE_WINDOW):
            drive(until, rows, lens)
    finally:
        jax.profiler.stop_trace()
    trace = xplane.load(xplane.find_xplane(trace_dir))
    window = xplane.window_of(trace, TRACE_WINDOW)
    # the package's own spans, moved onto the trace's host clock
    from neuronx_distributed_tpu import obs

    spans = [e for e in obs.get_tracer().chrome_trace()["traceEvents"]
             if e.get("ph") == "X" and e["name"].startswith("engine/")
             and e["ts"] * 1e-6 >= anchor]
    shift = window[0] - anchor
    for e in spans:
        s = e["ts"] * 1e-6 + shift
        trace.annotations.append(
            xplane.Event(e["name"], s, s + e["dur"] * 1e-6))
    trace.annotations.sort(key=lambda e: e.start)
    observations.traced_steps = len(rows)
    observations.series["traced_slot_lengths"] = lens
    if not trace.devices and cell.rehearsal:
        say("trace", device_planes=0, note="rehearsal: no device metrics")
        return
    observations.trace = trace
    observations.reduction = xplane.reduce(
        trace, window, gap_layer=("engine/", "bench/"))
    say("trace", window_s=round(observations.reduction.window_s, 3),
        busy_s=round(observations.reduction.busy_s, 3), steps=len(rows),
        clock_offset_ms=round(trace.offset_s * 1e3, 3))


def _end_to_end(cell, engine, sample, requests, uids, open_loop, tokens,
                window_s, preempted, rejected, completed
                ) -> Tuple[int, int, Dict[str, float]]:
    results = engine.results
    want = {u: r.max_new_tokens for u, r in zip(uids, requests)}
    if open_loop:
        done = [results[u] for u in sample if u in results
                and results[u].status == "completed"
                and len(results[u].tokens) == want[u]]
        failed = len(sample) - len(done)
        ttft = [r.ttft_s * 1e3 for r in done]
        tpot = [r.tpot_s * 1e3 for r in done if r.tpot_s is not None]
        say("requests", sampled=len(sample), finished=len(done),
            ttft_p50_ms=round(harness.percentile(ttft, 50), 2),
            tpot_p50_ms=round(harness.percentile(tpot, 50), 2),
            preempted_in_window=preempted)
        return len(sample), failed, {
            "ttft_p90_ms": harness.percentile(ttft, 90),
            "tpot_p90_ms": harness.percentile(tpot, 90)}
    # a backlog: the requests the window finished are the attempts; one
    # that ended short, was rejected, or the queue running dry, fails
    bad = sum(1 for u in uids if u in results and (
        results[u].status != "completed"
        or len(results[u].tokens) != want[u]))
    if engine.queue_depth() < engine.ecfg.max_slots:
        raise BenchError("the backlog ran dry before the window ended: "
                         "raise arrivals.count in the traffic file")
    return completed, bad + rejected, {"serve_tok_s": tokens / window_s}


# -- the logit check ---------------------------------------------------------

def probe_schedule(prompt_len: int, decode: int, width: int, group: int = 1,
                   rewrite: bool = False) -> List[List[Tuple[int, int]]]:
    """Rows ``(sequence, position)`` of each packed step for two sequences,
    packed as the engine packs: decode rows first, prefill chunks in what
    is left of ``width``. Sequence 0 prefills alone; sequence 1 prefills
    beside sequence 0's decode rows; then both decode among pad rows.

    With ``group`` g the positions ``[k * g, (k + 1) * g)`` of a sequence
    enter in one step: a decoding sequence gives its next g rows a step,
    and a prefill chunk is what is left of ``width`` cut down to a multiple
    of g. A length that g does not divide, or a width that cannot hold two
    groups beside a chunk of one, is refused (g = 1 takes every width, as
    it always did). Under ``rewrite`` a decode group enters twice: the step
    before its own rows as ``(sequence + 2, position)``, a first writing
    of the same positions of the same slot under other tokens."""
    if group < 1:
        raise BenchError(f"logit_check.group wants 1 or more, got {group}")
    if group > 1:
        for key, n in (("prompt_tokens", prompt_len),
                       ("decode_steps", decode)):
            if n % group:
                raise BenchError(f"logit_check.{key} {n} is not a multiple "
                                 f"of logit_check.group {group}")
        if width < 3 * group:
            raise BenchError(
                f"token_budget {width} is under three of logit_check.group "
                f"{group}: two decode groups leave no room for a chunk")
    steps, done, total = [], [0, 0], prompt_len + decode
    drafted = [False, False]
    while min(done) < total:
        rows = []
        for s in (0, 1):
            if prompt_len <= done[s] < total and (s == 0 or done[0] > 0):
                drafted[s] = rewrite and not drafted[s]
                rows += [(s + 2 * drafted[s], done[s] + i)
                         for i in range(group)]
        for s in (0, 1):
            if done[s] < prompt_len and (s == 0 or done[0] >= prompt_len):
                n = min(width - len(rows), prompt_len - done[s])
                rows += [(s, done[s] + i) for i in range(n - n % group)]
                break
        for s, p in rows:
            if s < 2:
                done[s] = max(done[s], p + 1)
        steps.append(rows)
    return steps


def compared_positions(chk) -> np.ndarray:
    """The positions of a sequence at which the check compares logits,
    ascending: all of them, or under ``logit_check.compare = {"every": n,
    "tail": m}`` every ``n``-th prompt position, the last ``m`` prompt
    positions and every decode position, so that a context of thousands
    at a vocabulary of tens of thousands is not held as ``[2, S, V]``."""
    plen, total = chk["prompt_tokens"], \
        chk["prompt_tokens"] + chk["decode_steps"]
    compare = chk.get("compare")
    if compare is None:
        return np.arange(total)
    every, tail = int(compare["every"]), int(compare["tail"])
    if every < 1 or tail < 0:
        raise BenchError(f"logit_check.compare wants every >= 1 and "
                         f"tail >= 0, got {compare}")
    keep = np.zeros(total, bool)
    keep[0:plen:every] = True
    keep[max(plen - tail, 0):] = True
    return np.flatnonzero(keep)


def serving_cache(mcfg, params, ecfg):
    """``(cache, kind)``: the cache the family is served from, as the
    engine's own constructor builds it, and the cache kind whose columns
    the engine maps. The harness builds neither."""
    from neuronx_distributed_tpu.inference.engine import ServingEngine

    kind = mcfg.serving_family().cache_kind.geometry(ecfg.block_size,
                                                     ecfg.token_budget)
    return ServingEngine(mcfg, params, ecfg).cache, kind


def probe_logits(seed: int, mcfg, forward, params, ecfg, chk):
    """Two seeded sequences through the paged forward the engine's packed
    step runs (the engine's own cache, same width, prefill chunks beside
    decode rows and pad rows; before a row runs, the columns its cache
    kind names for its position are mapped to fresh blocks in order, as
    the engine maps them): ``(tokens [2, S], logits [2, P, V])``, the
    logits at the ``P`` positions of :func:`compared_positions`.

    ``logit_check.group`` (default 1) is the row group of
    :func:`probe_schedule`; under ``logit_check.rewrite`` each decode
    group is written first with other tokens drawn from ``[seed, 2]``,
    whose logits are dropped, and in the next step with its own."""
    import functools

    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION

    plen, ndec, width = chk["prompt_tokens"], chk["decode_steps"], \
        ecfg.token_budget
    group, rewrite = int(chk.get("group", 1)), bool(chk.get("rewrite"))
    if "group" in chk or "rewrite" in chk:
        say("check", group=group, rewrite=rewrite)
    rng = np.random.default_rng([seed, 1])
    seqs = rng.integers(0, mcfg.vocab_size, (2, plen + ndec))
    fed = seqs
    if rewrite:
        # rows (s + 2, p) of the schedule: a first writing, never sequence
        # s's own token
        fed = np.concatenate([seqs, (seqs + np.random.default_rng(
            [seed, 2]).integers(1, mcfg.vocab_size, seqs.shape))
            % mcfg.vocab_size])

    @functools.partial(jax.jit, donate_argnums=(1,))
    def probe(params, cache, tokens, positions, slot_ids):
        logits, cache = forward(mcfg, params, tokens, positions, cache,
                                slot_ids=slot_ids)
        return logits[0].astype(jnp.float32), cache

    cache, kind = serving_cache(mcfg, params, ecfg)
    table = np.array(cache.block_tables)
    mapped = 0
    compared = compared_positions(chk)
    row_of = np.full(plen + ndec, -1)
    row_of[compared] = np.arange(compared.size)
    got = np.zeros((2, compared.size, mcfg.vocab_size), np.float32)
    for rows in probe_schedule(plen, ndec, width, group, rewrite):
        tok = np.zeros((1, width), np.int32)
        pos = np.full((1, width), PAD_POSITION, np.int32)
        slot = np.full((width,), ecfg.max_slots, np.int32)
        before = mapped
        for i, (s, p) in enumerate(rows):
            tok[0, i], pos[0, i], slot[i] = fed[s, p], p, s % 2
            for column in kind.columns_to_map(p, ecfg.block_size):
                if table[s % 2, column] < 0:
                    table[s % 2, column], mapped = mapped, mapped + 1
        if mapped > ecfg.num_blocks:
            raise BenchError(
                f"the logit check's two sequences need {mapped} blocks "
                f"and the pool has {ecfg.num_blocks}")
        if mapped > before:
            cache = cache.replace(block_tables=jnp.asarray(table))
        logits, cache = probe(params, cache, jnp.asarray(tok),
                              jnp.asarray(pos), jnp.asarray(slot))
        logits = np.asarray(logits)
        for i, (s, p) in enumerate(rows):
            if s < 2 and row_of[p] >= 0:
                got[s, row_of[p]] = logits[i]
    del cache
    return seqs, got


def reference_logits(reference, weights, seqs, config, chk):
    """The reference's ``(logits [2, P, V], router margins or None)`` at
    the compared positions. Without ``compare`` the call is the one every
    reference has always taken, ``forward(weights, tokens, config)``."""
    if chk.get("compare") is None:
        return reference.forward(weights, seqs, config)
    return reference.forward(weights, seqs, config,
                             positions=compared_positions(chk))


def logit_errors(got, want, chk):
    """``(scale, err [2, P], parts)``: the largest logit difference at each
    compared position in units of the reference logits' spread, so that
    one pair of tolerances serves every width; ``parts`` are the prefill
    and the decode positions' errors."""
    prefill = int(np.sum(compared_positions(chk) < chk["prompt_tokens"]))
    scale = float(np.std(want))
    err = np.abs(got - want).max(axis=-1) / scale      # [2, P]
    return scale, err, {"prefill": err[:, :prefill].ravel(),
                        "decode": err[:, prefill:].ravel()}


def judge_logits(got, want, chk) -> List[str]:
    """Hold logits ``[2, P, V]`` at the compared positions to the
    configuration's ``logit_check`` against the reference's: the reasons
    they fail it, if any. Each number compared is printed beside its
    limit."""
    compared = compared_positions(chk)
    scale, err, parts = logit_errors(got, want, chk)
    why = []
    for part, e in parts.items():
        typical = float(np.median(e))
        outliers = float(np.mean(e > chk["outlier_rtol"]))
        say("check", part=part, positions=e.size, logit_std=round(scale, 4),
            median_rel_err=round(typical, 5),
            p99_rel_err=round(float(np.percentile(e, 99)), 5),
            max_rel_err=round(float(e.max()), 5),
            share_over_outlier_rtol=round(outliers, 5))
        say("limits", part=part, median_rel_err=chk["typical_rtol"],
            share_over_outlier_rtol=chk["outlier_share"][part],
            outlier_rtol=chk["outlier_rtol"])
        harness.compared(f"{part}.median_rel_err", typical,
                         chk["typical_rtol"])
        harness.compared(f"{part}.share_over_outlier_rtol", outliers,
                         chk["outlier_share"][part])
        if not np.isfinite(e).all():
            why.append(f"logit check ({part}): non-finite logits")
        if typical > chk["typical_rtol"]:
            why.append(f"logit check ({part}): median error {typical:.4f} "
                       f"of the logits' spread > {chk['typical_rtol']}")
        if outliers > chk["outlier_share"][part]:
            why.append(f"logit check ({part}): {outliers:.3%} of positions "
                       f"differ by more than {chk['outlier_rtol']} of the "
                       f"logits' spread (allowed "
                       f"{chk['outlier_share'][part]:.1%})")
    # the prompt's quarters by position, whichever of them are compared
    quarters = np.array_split(np.arange(chk["prompt_tokens"]), 4)
    say("check", **{
        "rel_err_at_positions_" + "_".join(map(str, compared[:4])): [
            round(float(x), 4) for x in err[0, :4]]},
        median_by_quarter=[
            round(float(np.median(err[0, np.isin(compared, q)])), 4)
            for q in quarters])
    return why


def check_logits(cell, mcfg, forward, params, ecfg, settings) -> List[str]:
    """The compared positions' logits of ``probe_logits`` against the
    plain reference's forward over the whole of both sequences."""
    chk = settings["logit_check"]
    seqs, got = probe_logits(cell.seed, mcfg, forward, params, ecfg, chk)
    want, margins = reference_logits(
        models.reference(cell.config),
        models.published(params, cell.config), seqs, cell.config, chk)
    why = judge_logits(got, np.asarray(want), chk)
    if margins is not None:
        m = np.asarray(margins)
        say("check", router_margin_p01=round(float(np.percentile(m, 1)), 5),
            decisions=m.size)
    return why
