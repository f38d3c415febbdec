"""Benchmark entry point.

Prints exactly ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "aux": {...}}
— the headline is training throughput; decode/speculative/cold-start ride
inside "aux" keyed by metric name.

Runs on a TPU or not at all: ``main()`` exits non-zero when JAX's first
device is anything else, and any metric that raises makes the exit code
non-zero. Measures training throughput (tokens/sec/chip) of the flagship
Llama model on the available chips; the model is scaled to fit the chip
count (1 chip -> a ~300M-param llama slice; 8 chips -> Llama-2-7B TP=8, the
reference's canonical config,
``examples/training/llama/tp_zero1_llama_hf_pretrain``).

The reference repo publishes no in-tree numbers, so ``vs_baseline`` is
reported against the recorded value in BENCH_BASELINE.json (created on
first run) — i.e. it tracks our own progression. ``chip_smoke.py`` is the
quicker proof that the trainer and the server start on the chip.
"""

import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.abspath(__file__))


def _regress_main(regress_dir, tolerance) -> int:
    """``--regress``: audit recorded ``BENCH_*.json`` history for metric
    regressions without running anything. Prints exactly ONE JSON line
    ``{"metric": "bench_regressions", "value": N, ..., "regressions":
    [...]}`` where each entry names a metric whose newest record fell
    more than ``tolerance`` below (throughput-like units) or above
    (time-like units) the best earlier record. Handled before ``main()``
    touches a backend — a history audit never needs a TPU. A directory
    with no history says so and audits nothing."""
    from neuronx_distributed_tpu.plan.calibrate import load_bench_history

    records = load_bench_history(regress_dir)
    if not records:
        print(f"bench: no BENCH_*.json history under {regress_dir}; "
              "nothing to audit", file=sys.stderr)
    by_metric = {}
    for rec in records:                       # files sort by run number
        by_metric.setdefault(rec["metric"], []).append(rec)
    regressions = []
    checked = 0
    for metric, recs in sorted(by_metric.items()):
        if len(recs) < 2:
            continue
        checked += 1
        latest, earlier = recs[-1], recs[:-1]
        unit = str(latest.get("unit") or "")
        lower_is_better = unit in ("ms", "s", "seconds") \
            or unit.endswith("_ms") or metric.endswith("_ms")
        vals = [r["value"] for r in earlier]
        best = min(vals) if lower_is_better else max(vals)
        v = latest["value"]
        if lower_is_better:
            bad = v > best * (1.0 + tolerance) and best > 0
            ratio = v / best if best else 1.0
        else:
            bad = v < best * (1.0 - tolerance)
            ratio = v / best if best else 1.0
        if bad:
            regressions.append(dict(
                metric=metric, latest=v, best=best,
                ratio=round(ratio, 4), unit=latest.get("unit"),
                file=latest.get("file")))
    print(json.dumps({
        "metric": "bench_regressions", "value": len(regressions),
        "unit": "count", "vs_baseline": 0.0,
        "tolerance": tolerance,
        "metrics_checked": checked,
        "regressions": regressions}))
    return 1 if regressions else 0



def main(chaos_spec=None, serving=False, overlap=False, router=False,
         prefix_heavy=False, plan_mode=False, obs_mode=False,
         elastic=False, sdc=False, moe=False, lint_mode=False,
         disagg_fabric=False, speculative=False, long_context=False,
         quantized=False) -> int:
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model,
        initialize_parallel_optimizer,
        make_train_step,
    )
    from neuronx_distributed_tpu.utils.device import place_compile_cache

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench: needs a TPU, found platform={platform!r}; no metric "
              "is measured on another backend", file=sys.stderr)
        return 1
    cache_dir = place_compile_cache(_ROOT)
    print(f"bench: device={jax.devices()[0].device_kind} x{n_dev} "
          f"compile_cache={cache_dir}", file=sys.stderr)

    if n_dev >= 8:
        # Llama-2-7B TP=8 + ZeRO-1 + remat: the reference's canonical config
        mcfg = llama.LLAMA2_7B
        tp = 8
        batch, seq = 4, 2048
        mcfg = llama.LlamaConfig(
            **{**mcfg.__dict__, "max_seq_len": seq, "remat": True,
               "use_flash_attention": True,
               "remat_policy": "save_attention", "loss_chunk": 512})
    else:
        # single-chip slice: ~350M params, bf16 compute; head_dim 128 so
        # the Pallas flash kernel path tiles (d % 128 == 0)
        # remat_policy="save_attention" saves flash out+lse across fwd→bwd
        # (skips re-running the attention forward in the backward);
        # loss_chunk streams 512-token slices through head+CE so [B,S,V]
        # logits never materialise
        mcfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=16, num_heads=8, num_kv_heads=8, max_seq_len=2048,
            remat=True, use_flash_attention=True,
            remat_policy="save_attention", loss_chunk=512)
        tp = 1
        batch, seq = 8, 2048
    print(f"bench: flash_attention={mcfg.use_flash_attention} "
          f"head_dim={mcfg.head_dim_} remat={mcfg.remat_policy} "
          f"loss_chunk={mcfg.loss_chunk}", file=sys.stderr)

    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=tp,
        optimizer_config=nxd.OptimizerConfig(zero_one_enabled=True),
        sequence_parallel=False,
    )

    model = llama.LlamaForCausalLM(mcfg)
    rng = jax.random.key(0)
    loader = _make_loader(mcfg.vocab_size, batch, seq)
    batch_data = {k: jnp.asarray(v) for k, v in loader.next_batch().items()}

    pm, params = initialize_parallel_model(cfg, model, rng,
                                           batch_data["input_ids"])
    tx, state, state_shardings = initialize_parallel_optimizer(
        pm, params, learning_rate=1e-4)
    # The iteration loop runs ON DEVICE (scan_steps) and is timed
    # dispatch-to-fetch with a host fetch (float()) as the barrier; the
    # per-dispatch round trip is cancelled by differencing a 1-step and an
    # iters-step run. chip_smoke.py prints a step timed both with
    # block_until_ready and with a host fetch; ROADMAP S1 decides from
    # that whether the differencing stays.
    iters = 10
    step1 = make_train_step(pm, tx, state_shardings, donate=False)
    stepN = make_train_step(pm, tx, state_shardings, donate=False,
                            scan_steps=iters)
    # feed the scanned steps from the native C++ loader (mmap + shuffled
    # prefetch off the GIL) — the loader is in the hot path, not a fixture
    import numpy as np

    batchN_host = [loader.next_batch() for _ in range(iters)]
    batchN = {k: jnp.asarray(np.stack([b[k] for b in batchN_host]))
              for k in batch_data}

    def run(step, batch):
        t0 = time.perf_counter()
        _, m = step(state, batch)
        float(m["loss"])
        return time.perf_counter() - t0

    run(step1, batch_data)  # compile
    run(stepN, batchN)      # compile
    t1 = min(run(step1, batch_data) for _ in range(2))
    tN = min(run(stepN, batchN) for _ in range(2))
    dt = tN - t1
    steps_covered = iters - 1  # the difference cancels 1 step + round trip
    if dt <= 0:
        # noise inversion: use the undifferenced N-step time —
        # under-reports rather than publishing ~1e13 tok/s
        print(f"bench: differential timing inverted (t1={t1:.3f} "
              f"tN={tN:.3f}); using tN undifferenced", file=sys.stderr)
        dt, steps_covered = tN, iters

    tokens = batch * seq * steps_covered
    tok_per_sec_per_chip = tokens / dt / n_dev

    vs_baseline = _vs_baseline("BENCH_BASELINE.json", tok_per_sec_per_chip,
                               platform, n_dev)

    # Everything below rides as aux metrics nested in the single output
    # line. A metric that raises loses only its aux entries, but it is
    # recorded in "failed" and the exit code is non-zero. Each drill is
    # documented at its function; the opt-in ones follow their flags.
    aux, failed = {}, []
    drills = [
        ("decode", True, decode_metric, (platform, n_dev)),
        ("resilience", True, resilience_metric, (platform, chaos_spec)),
        ("serving", serving, serving_metric, (platform,)),
        ("router", router, router_metric, (platform,)),
        ("speculative", speculative, speculative_metric, (platform,)),
        ("quantized", quantized, quantized_metric, (platform,)),
        ("long-context", long_context, long_context_metric, (platform,)),
        ("elastic", elastic, elastic_metric, (platform,)),
        ("fabric", disagg_fabric, fabric_metric, (platform,)),
        ("sdc", sdc, sdc_metric, (platform, n_dev)),
        ("prefix", prefix_heavy, prefix_metric, (platform,)),
        ("tp-overlap", overlap, tp_overlap_metric, (platform, n_dev)),
        ("tp-act", overlap, tp_act_metric, (platform, n_dev)),
        ("moe", moe, moe_metric, (platform, n_dev)),
        ("plan", plan_mode, plan_metric, (platform, n_dev)),
        ("obs", obs_mode, obs_metric, (platform, n_dev)),
        ("lint", lint_mode, lint_metric, ()),
        ("comm", True, comm_metric, (platform, n_dev)),
    ]
    for name, enabled, fn, args in drills:
        if not enabled:
            continue
        try:
            aux.update(fn(*args))
        except Exception as e:
            traceback.print_exc()
            print(f"bench: {name} metric failed: {e!r}", file=sys.stderr)
            failed.append(name)

    print(json.dumps({
        "metric": f"llama_train_tokens_per_sec_per_chip_{platform}{n_dev}",
        "value": round(tok_per_sec_per_chip, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(vs_baseline, 4),
        "aux": aux,
        "failed": failed,
    }), flush=True)
    if failed:
        print(f"bench: metrics failed: {failed}", file=sys.stderr)
        return 1
    return 0


def lint_metric():
    """Static-analysis self-measurement (docs/analysis.md): wall time and
    unsuppressed finding count of the full nxdlint run over the package +
    tests + examples (fixture corpus excluded), plus the wall time of the
    jaxpr-level entry-point audit and of the tier-4 mesh-protocol
    verifier (with its finding count). All run as subprocess CLI invocations
    — the auditor's entry builders construct their own meshes and must
    not collide with the bench's parallel state — pinned to the CPU
    backend: this process holds the chip, and a chip belongs to one
    process. RETURNS aux entries keyed by metric name — never prints a
    JSON line."""
    import subprocess

    root, env = _ROOT, {**os.environ, "JAX_PLATFORMS": "cpu"}
    cli = [sys.executable, "-m", "neuronx_distributed_tpu.analysis"]
    t0 = time.perf_counter()
    r = subprocess.run(
        cli + ["neuronx_distributed_tpu", "tests", "examples",
               "--exclude", "analysis_fixtures", "--format", "json"],
        cwd=root, env=env, capture_output=True, text=True)
    lint_ms = (time.perf_counter() - t0) * 1000.0
    n_findings = (len(json.loads(r.stdout)["findings"])
                  if r.stdout.strip() else -1)
    t1 = time.perf_counter()
    subprocess.run(cli + ["--jaxpr"], cwd=root, env=env, capture_output=True,
                   text=True)
    jaxpr_ms = (time.perf_counter() - t1) * 1000.0
    t2 = time.perf_counter()
    r_mp = subprocess.run(cli + ["--mesh-protocol", "--format", "json"],
                          cwd=root, env=env, capture_output=True, text=True)
    mp_ms = (time.perf_counter() - t2) * 1000.0
    mp_findings = (len(json.loads(r_mp.stdout)["findings"])
                   if r_mp.stdout.strip() else -1)
    return {
        "lint_wall_ms": {
            "value": round(lint_ms, 1), "unit": "ms", "vs_baseline": 1.0},
        "lint_findings": {
            "value": n_findings, "unit": "findings", "vs_baseline": 1.0},
        "jaxpr_audit_wall_ms": {
            "value": round(jaxpr_ms, 1), "unit": "ms", "vs_baseline": 1.0},
        "mesh_protocol_wall_ms": {
            "value": round(mp_ms, 1), "unit": "ms", "vs_baseline": 1.0},
        "mesh_protocol_findings": {
            "value": mp_findings, "unit": "findings", "vs_baseline": 1.0},
    }


def _vs_baseline(fname: str, value: float, platform: str,
                 n_dev: int) -> float:
    """Per-platform self-progression baseline: compare when one exists for
    this platform, seed it on the first real-hardware run (a CPU run of a
    metric function, as the tests make, must neither seed nor be compared
    against the TPU baseline)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), fname)
    try:
        if os.path.exists(path):
            base = json.load(open(path))
            if base.get("value") and base.get("platform") == platform:
                return value / base["value"]
        elif platform != "cpu":
            json.dump({"value": value, "platform": platform,
                       "n_dev": n_dev}, open(path, "w"))
    except Exception:
        pass
    return 1.0


def _make_loader(vocab: int, batch: int, seq: int):
    """Synthesize a token file and open it through the native C++ loader
    (csrc/data_loader.cpp via data/native_loader.py) — bench feeds training
    from the same IO path real runs use. Reports the loader's standalone
    sustained rate so an IO regression below model throughput is visible.
    The library is rebuilt from ``csrc/data_loader.cpp`` in this run (never
    a leftover ``.so``), and the Python loader serving instead is an
    error: the benchmark measures the native path or fails."""
    import tempfile

    import numpy as np

    from neuronx_distributed_tpu.data.native_loader import (
        TokenBatchLoader, build_native)

    build_native()

    dtype = np.uint16 if vocab <= 0xFFFF else np.uint32
    n_seq = max(2 * batch, 64)
    path = os.path.join(tempfile.gettempdir(), "nxd_bench_tokens.bin")
    rng = np.random.RandomState(0)
    rng.randint(0, vocab, n_seq * (seq + 1)).astype(dtype).tofile(path)
    loader = TokenBatchLoader(path, batch, seq,
                              dtype=np.dtype(dtype).name, nthreads=2)
    t0 = time.perf_counter()
    probe = 20
    for _ in range(probe):
        loader.next_batch()
    rate = probe * batch * seq / (time.perf_counter() - t0)
    print(f"bench: native_loader={loader.native} sustained "
          f"{rate:,.0f} tok/s", file=sys.stderr)
    if not loader.native:
        raise RuntimeError("the Python loader served instead of the native "
                           "one built from csrc/data_loader.cpp")
    return loader


def decode_metric(platform: str, n_dev: int) -> dict:
    """Measure the serving-side aux metrics and RETURN them (keyed by
    metric name) for nesting under the headline line — never print."""
    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.inference.generation import generate
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    if platform == "cpu":
        cfg = llama.LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=704,
            num_layers=4, num_heads=8, num_kv_heads=8, max_seq_len=512)
        batch, prompt_len, new_tokens = 1, 64, 32
    else:
        # ~350M slice, matching the single-chip train config
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=16, num_heads=8, num_kv_heads=8, max_seq_len=4096)
        batch, prompt_len, new_tokens = 1, 128, 128
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, prompt_len)))
    plen = jnp.full((batch,), prompt_len, jnp.int32)
    params = meta.unbox(llama.LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))

    def run():
        t0 = time.perf_counter()
        toks = generate(cfg, params, ids, plen, new_tokens,
                        buckets=(prompt_len,))
        np.asarray(toks)  # host fetch as the barrier
        return time.perf_counter() - t0

    run()  # compile + warm
    best = min(run() for _ in range(3))
    tok_per_sec = batch * new_tokens / best

    # decode runs single-chip (tp=1, default mesh) regardless of n_dev —
    # the label and baseline say so explicitly
    vs_baseline = _vs_baseline("BENCH_DECODE_BASELINE.json", tok_per_sec,
                               platform, 1)
    aux = {
        f"llama_greedy_decode_tokens_per_sec_{platform}1": {
            "value": round(tok_per_sec, 2),
            "unit": "tokens/sec",
            "vs_baseline": round(vs_baseline, 4),
        },
    }
    acc = _speculative_accept_rate(cfg, params, ids, plen, prompt_len)
    aux[f"llama_speculative_accepted_per_round_{platform}1"] = {
        "value": round(acc, 3), "unit": "drafts/round", "vs_baseline": 1.0}
    cold = _bundle_cold_start_ms()
    aux[f"bundle_cold_start_ms_{platform}1"] = {
        "value": round(cold, 1), "unit": "ms", "vs_baseline": 1.0}
    return aux


def _speculative_accept_rate(cfg, params, ids, plen, prompt_len) -> float:
    """Mean accepted drafts per speculation round, SELF-drafting (the
    mechanical ceiling: acceptance is 100% of speculation_length)."""
    from neuronx_distributed_tpu.inference.speculative import (
        speculative_generate)

    _, stats = speculative_generate(
        cfg, params, cfg, params, ids, plen, 16, speculation_length=4,
        buckets=(prompt_len,))
    return float(stats["mean_accepted"])


def _bundle_cold_start_ms() -> float:
    """Serving-bundle cold start: save a prefill bundle, load it
    in-process, first forward timed end to end (reference treats cold
    start as a first-class serving number,
    examples/inference/modules/benchmark.py). A small FIXED config on
    every platform — this measures the bundle machinery (zip, StableHLO
    deserialize, packaged-executable load), not weight volume; the bundle
    lives in a private mkdtemp dir because the trusted load unpickles it."""
    import tempfile

    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.inference.model_builder import (
        ModelBuilder, NxDModel)
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

    cfg = llama.tiny_config(num_layers=2)
    model = LlamaForCausalLM(cfg)
    ids0 = jnp.zeros((1, 32), jnp.int32)
    params = meta.unbox(model.init(jax.random.key(0), ids0))

    def ce_fn(ids_):
        return model.apply(params, ids_)

    nxd_model = (ModelBuilder()
                 .add("ce", ce_fn, [(ids0,)])
                 .trace().compile())
    path = os.path.join(tempfile.mkdtemp(prefix="nxd_bench_"),
                        "bundle.nxd")
    nxd_model.save(path)
    ids = np.zeros((1, 32), np.int32)
    t0 = time.perf_counter()
    loaded = NxDModel.load(path, trust_packaged_executables=True)
    out = loaded.forward("ce", jnp.asarray(ids))
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e3


def _modeled_drill_tps(plan, span_s, total_new, total_rows, mean_new):
    """The serving cost model's prediction of a *finite* drill's
    makespan throughput (the number the drills below measure): the
    arrival span plus the last request's modeled latency, floored by
    the capacity-limited drain of every row the drill must compute.
    Steady-state goodput is the wrong comparator for an 8-request
    burst — the modeled-vs-measured error reported in aux is on this
    quantity."""
    c = plan.cost
    cap_rows = plan.engine["token_budget"] / c.step_s
    makespan = max(span_s + c.ttft_s + mean_new * c.tpot_s,
                   total_rows / cap_rows + c.ttft_s)
    return total_new / makespan


def serving_metric(platform: str) -> dict:
    """Continuous-batching serving vs static batched decode (docs/serving.md).

    A ragged Poisson-arrival workload (mixed prompt lengths and
    ``max_new_tokens``) is served two ways on the same model:

    * **static**: collect every request, pad the batch square (longest
      prompt, longest max_new), run :func:`generate` per ``max_slots``-
      sized batch — the head-of-line-blocking baseline. Its makespan is
      charged from t=0, so it includes the wait for the last arrival.
    * **engine**: :class:`ServingEngine` admits mid-flight, chunks
      prefill, retires finished slots immediately; one compiled step.

    Throughput counts only the tokens each request asked for, so the
    static baseline pays for its padding in time, not in credit."""
    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          ServingEngine)
    from neuronx_distributed_tpu.inference.engine import EngineStats
    from neuronx_distributed_tpu.inference.generation import generate
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    if platform == "cpu":
        cfg = llama.LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=704,
            num_layers=4, num_heads=8, num_kv_heads=8, max_seq_len=512)
        n_req, max_slots, budget = 8, 4, 16
        plen_range, new_range = (8, 33), (4, 17)
        block_size, num_blocks = 8, 64
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=16, num_heads=8, num_kv_heads=8, max_seq_len=4096)
        n_req, max_slots, budget = 16, 8, 64
        plen_range, new_range = (32, 129), (16, 65)
        block_size, num_blocks = 16, 256
    params = meta.unbox(llama.LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, cfg.vocab_size,
                         (rng.randint(*plen_range),)).tolist(),
             int(rng.randint(*new_range))) for _ in range(n_req)]
    total_tokens = sum(n for _, n in reqs)

    # -- static baseline: square batches of max_slots ---------------------
    def run_static():
        elapsed = 0.0
        for i in range(0, n_req, max_slots):
            batch = reqs[i:i + max_slots]
            pmax = max(len(p) for p, _ in batch)
            nmax = max(n for _, n in batch)
            ids = np.zeros((len(batch), pmax), np.int32)
            for j, (p, _) in enumerate(batch):
                ids[j, :len(p)] = p
            plen = jnp.asarray([len(p) for p, _ in batch], jnp.int32)
            t0 = time.perf_counter()
            np.asarray(generate(cfg, params, jnp.asarray(ids), plen, nmax,
                                buckets=(pmax,)))
            elapsed += time.perf_counter() - t0
        return elapsed

    run_static()                       # compile + warm
    static_gen_s = min(run_static() for _ in range(2))

    ecfg = EngineConfig(block_size=block_size, num_blocks=num_blocks,
                        max_slots=max_slots,
                        max_blocks_per_seq=-(-cfg.max_seq_len // block_size),
                        token_budget=budget, kv_dtype=cfg.dtype)
    eng = ServingEngine(cfg, params, ecfg)
    eng.submit(reqs[0][0], reqs[0][1], uid="warm")   # compile + warm
    eng.run()
    eng.stats = EngineStats()
    eng.results = {}

    # Poisson arrivals spanning ~75% of the static busy time: the static
    # server must wait for the full batch, the engine starts immediately
    gaps = rng.exponential(0.75 * static_gen_s / n_req, n_req)
    arrivals = np.concatenate([[0.0], gaps.cumsum()[:-1]])
    eng._t0 = eng._clock()
    for (p, n), at in zip(reqs, arrivals):
        eng.submit(p, n, arrival_time=float(at))
    results = eng.run()
    done = [r for r in results.values() if r.status == "completed"]
    makespan = max(r.finish_s for r in done)
    rep = eng.stats.report()
    serving_tps = sum(len(r.tokens) for r in done) / makespan
    static_tps = total_tokens / (float(arrivals[-1]) + static_gen_s)
    speedup = serving_tps / static_tps

    # --- close the measurement loop (ISSUE 15): calibrate the planner's
    # serving cost model from this run's measured step latencies, let
    # `plan --serving` pick an EngineConfig for the measured traffic mix,
    # and run the SAME drill on the emitted config. The planner earns its
    # keep if it lands within ~10% of the hand-tuned config above.
    import dataclasses as _dc

    from neuronx_distributed_tpu.plan import (ModelSpec, TrafficSpec,
                                              calibrate, default_hardware,
                                              serving_search,
                                              serving_token_s)

    spec = ModelSpec.from_model_config(cfg, global_batch=8,
                                       name="bench-serving")
    steps_s = [s for s in eng.stats.step_latency_s if s > 0]
    hw = calibrate(default_hardware(platform),
                   serve_step_seconds=steps_s).hardware
    # refit mfu so the modeled marginal row time matches the measured
    # packed-step slope: (total step wall - n·overhead) / rows computed
    rows = eng.stats.prefill_tokens + sum(len(r.tokens) for r in done)
    meas_tok = max(1e-9, (sum(steps_s)
                          - hw.serve_overhead_s * len(steps_s))
                   / max(1, rows))
    mean_prompt = float(np.mean([len(p) for p, _ in reqs]))
    mean_new = float(np.mean([n for _, n in reqs]))
    model_tok = serving_token_s(spec, hw, context=mean_prompt)
    hw = _dc.replace(hw, mfu=min(1.0, max(1e-4,
                                          hw.mfu * model_tok / meas_tok)))
    traffic = TrafficSpec(
        request_rate=n_req / max(1e-9, float(arrivals[-1])),
        prompt_tokens=mean_prompt, new_tokens=mean_new)
    planned = serving_search(spec, hw, traffic, block_size=block_size,
                             budgets=(4, 8, 16, 32, 64),
                             slots=(1, 2, 4, 8, 16), top_k=1)
    plan_aux = {}
    tag = f"{platform}1"
    if planned:
        pe = dict(planned[0].engine)
        pe.pop("prefix_sharing", None)         # no shared prefix here
        peng = ServingEngine(cfg, params, EngineConfig(
            kv_dtype=cfg.dtype, **pe))
        peng.submit(reqs[0][0], reqs[0][1], uid="warm")
        peng.run()
        peng.stats, peng.results = EngineStats(), {}
        peng._t0 = peng._clock()
        for (p, n), at in zip(reqs, arrivals):
            peng.submit(p, n, arrival_time=float(at))
        pdone = [r for r in peng.run().values()
                 if r.status == "completed"]
        if pdone:
            plan_tps = (sum(len(r.tokens) for r in pdone)
                        / max(r.finish_s for r in pdone))
            plan_ratio = plan_tps / serving_tps
            modeled_tps = _modeled_drill_tps(
                planned[0], float(arrivals[-1]), total_tokens,
                sum(len(p) + n for p, n in reqs), mean_new)
            model_err = abs(modeled_tps - plan_tps) / plan_tps
            print(f"bench: serving planner picked "
                  f"{planned[0].describe()} -> {plan_tps:.1f} tok/s "
                  f"({plan_ratio:.3f}x hand-tuned), modeled "
                  f"{modeled_tps:.1f} tok/s "
                  f"(err {model_err:.1%})", file=sys.stderr)
            plan_aux = {
                f"serving_plan_tokens_per_s_{tag}": {
                    "value": round(plan_tps, 2), "unit": "tokens/sec",
                    "vs_baseline": round(plan_ratio, 3)},
                f"serving_plan_vs_hand_ratio_{tag}": {
                    "value": round(plan_ratio, 3), "unit": "x",
                    "vs_baseline": round(plan_ratio, 3)},
                f"serving_plan_model_err_{tag}": {
                    "value": round(model_err, 4), "unit": "frac",
                    "vs_baseline": 1.0},
            }
    return {
        **plan_aux,
        f"serving_tokens_per_s_{tag}": {
            "value": round(serving_tps, 2), "unit": "tokens/sec",
            "vs_baseline": round(speedup, 3)},
        f"serving_ttft_p50_{tag}": {
            "value": round(rep["ttft_p50_ms"], 2), "unit": "ms",
            "vs_baseline": 1.0},
        f"serving_ttft_p99_{tag}": {
            "value": round(rep["ttft_p99_ms"], 2), "unit": "ms",
            "vs_baseline": 1.0},
        f"serving_speedup_vs_static_{tag}": {
            "value": round(speedup, 3), "unit": "x",
            "vs_baseline": round(speedup / 1.5, 3)},
        f"serving_pool_occupancy_{tag}": {
            "value": round(rep["pool_occupancy_mean"], 4), "unit": "frac",
            "vs_baseline": 1.0},
    }


def speculative_metric(platform: str) -> dict:
    """Speculative-decoding serving drill (docs/serving.md).

    The same ragged Poisson-arrival workload is served twice on one
    engine config — speculation off (one token per slot per step) and
    speculation on with an EARLY-EXIT draft: the target's residual tail
    (every layer past the first ``draft_layers``) has its o_proj /
    down_proj contributions zeroed, so the full-depth target computes
    bit-identically to its shallow prefix and the cheap draft's greedy
    choices are always ratified — the accept-rate ceiling with a draft
    that is genuinely cheaper than the target (the LayerSkip /
    self-speculative construction). Reports the decode tokens/s ratio
    (acceptance criterion: >=1.5x at this accept rate), the measured
    mean accept length, and the greedy match rate (fraction of requests
    whose token streams are bit-identical between the two runs — must
    be 1.0: speculation is an execution strategy, not an
    approximation)."""
    import dataclasses as _dc

    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          EngineStats,
                                                          ServingEngine)
    from neuronx_distributed_tpu.inference.speculative import (
        SpeculationConfig)
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    if platform == "cpu":
        cfg = llama.LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=704,
            num_layers=12, num_heads=8, num_kv_heads=8, max_seq_len=512)
        n_req, max_slots, budget = 8, 4, 16
        plen_range, new_range = (4, 17), (24, 49)
        block_size, num_blocks, spec_k = 8, 192, 6
        draft_layers = 2
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=16, num_heads=8, num_kv_heads=8, max_seq_len=4096)
        n_req, max_slots, budget = 16, 8, 64
        plen_range, new_range = (16, 65), (48, 129)
        block_size, num_blocks, spec_k = 16, 768, 6
        draft_layers = 2
    params = meta.unbox(llama.LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    # early-exit surgery: layers >= draft_layers contribute exactly 0.0
    # to the residual stream, so target(h) == draft(h) bitwise
    layers = params["params"]["model"]["layers"]["layer"]
    layers["attn"]["o_proj"]["kernel"] = (
        layers["attn"]["o_proj"]["kernel"].at[draft_layers:].set(0.0))
    layers["mlp"]["down"]["kernel"] = (
        layers["mlp"]["down"]["kernel"].at[draft_layers:].set(0.0))
    draft_cfg = _dc.replace(cfg, num_layers=draft_layers)
    draft_params = jax.tree_util.tree_map(lambda x: x, params)
    draft_params["params"]["model"]["layers"] = {
        "layer": jax.tree_util.tree_map(
            lambda x: x[:draft_layers],
            params["params"]["model"]["layers"]["layer"])}
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, cfg.vocab_size,
                         (rng.randint(*plen_range),)).tolist(),
             int(rng.randint(*new_range))) for _ in range(n_req)]
    # short Poisson gaps: the drill measures decode throughput, so the
    # arrival span must not dominate the makespan
    arrivals = np.concatenate(
        [[0.0], rng.exponential(0.005, n_req).cumsum()[:-1]])

    base = dict(block_size=block_size, num_blocks=num_blocks,
                max_slots=max_slots,
                max_blocks_per_seq=-(-cfg.max_seq_len // block_size),
                token_budget=budget, kv_dtype=cfg.dtype)

    def drill(ecfg, **eng_kw):
        eng = ServingEngine(cfg, params, ecfg, **eng_kw)
        eng.submit(reqs[0][0], reqs[0][1], uid="warm")  # compile + warm
        eng.run()
        eng.stats, eng.results = EngineStats(), {}
        eng._t0 = eng._clock()
        for i, ((p, n), at) in enumerate(zip(reqs, arrivals)):
            eng.submit(p, n, uid=f"r{i}", arrival_time=float(at))
        results = eng.run()
        done = {u: r for u, r in results.items()
                if r.status == "completed"}
        makespan = max(r.finish_s for r in done.values())
        tps = sum(len(r.tokens) for r in done.values()) / makespan
        leaked = (eng.allocator.num_allocated
                  if hasattr(eng, "allocator") else 0)
        return eng, done, tps, leaked

    eng0, done0, tps0, _ = drill(EngineConfig(**base))
    spec = SpeculationConfig(speculation_length=spec_k)
    eng1, done1, tps1, leaked = drill(
        EngineConfig(speculation=spec, **base),
        draft_cfg=draft_cfg, draft_params=draft_params)

    rep = eng1.stats.report()
    match = float(np.mean([done1[u].tokens == done0[u].tokens
                           for u in done0 if u in done1]))
    speedup = tps1 / max(1e-9, tps0)
    compile_ok = eng1.compile_count() == 1
    print(f"bench: speculative drill spec-on {tps1:.1f} tok/s vs "
          f"spec-off {tps0:.1f} tok/s ({speedup:.2f}x), accept_mean "
          f"{rep['spec_accept_mean']:.2f}/{spec_k}, match "
          f"{match:.2f}, compile_count==1 {compile_ok}, leaked "
          f"{leaked} blocks", file=sys.stderr)
    tag = f"{platform}1"
    return {
        f"speculative_decode_tokens_per_s_{tag}": {
            "value": round(tps1, 2), "unit": "tokens/sec",
            "vs_baseline": round(speedup, 3)},
        f"speculative_speedup_{tag}": {
            "value": round(speedup, 3), "unit": "x",
            "vs_baseline": round(speedup / 1.5, 3)},
        f"speculative_accept_mean_{tag}": {
            "value": round(rep["spec_accept_mean"], 3),
            "unit": "drafts/round",
            "vs_baseline": round(rep["spec_accept_mean"] / spec_k, 3)},
        f"speculative_match_rate_{tag}": {
            "value": round(match, 4), "unit": "frac",
            "vs_baseline": round(match, 4)},
        f"speculative_leaked_blocks_{tag}": {
            "value": int(leaked), "unit": "blocks",
            "vs_baseline": 1.0 if leaked == 0 else 0.0},
    }


def quantized_metric(platform: str) -> dict:
    """Weight-quantized serving drill (docs/quantization.md).

    The same ragged Poisson-arrival workload is served by the float
    engine and by each weight-quant tier **at an equal HBM budget**: the
    bytes a tier's packed weights free (measured from the actual arrays,
    not the storage-ratio table) are spent on extra paged-KV blocks, so
    the comparison is weights+pool against weights+pool, not weights
    against weights. Reports, per tier:

    * ``capacity`` — pool blocks affordable at the float run's budget
      (acceptance: >=1.5x for int8, whose weights shrink 4x);
    * serving tokens/s vs float (dequant overhead vs bandwidth win —
      on CPU the overhead usually wins; the capacity column is the
      tier's reason to exist there);
    * ``greedy_match`` — fraction of requests whose token streams are
      identical to the float engine's, and ``max_logit_div`` — max
      |logits_tier - logits_fp32| over a fixed prefill batch. These are
      the records ``plan --quality-file`` gates tiers on;
    * ``compile_count()==1`` under the ragged load swings.
    """
    import dataclasses as _dc

    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          EngineStats,
                                                          ServingEngine)
    from neuronx_distributed_tpu.inference.kv_cache import init_kv_cache
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.quantization.serving import (
        quantize_params_for_serving)

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    if platform == "cpu":
        cfg = llama.LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=704,
            num_layers=12, num_heads=8, num_kv_heads=8, max_seq_len=512)
        n_req, max_slots, budget = 8, 4, 16
        plen_range, new_range = (8, 33), (4, 17)
        block_size, num_blocks = 8, 64
        tiers = ("int8", "mxfp4")
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=16, num_heads=8, num_kv_heads=8, max_seq_len=4096)
        n_req, max_slots, budget = 16, 8, 64
        plen_range, new_range = (32, 129), (16, 65)
        block_size, num_blocks = 16, 256
        tiers = ("int8", "mxfp4")
    params = meta.unbox(llama.LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, cfg.vocab_size,
                         (rng.randint(*plen_range),)).tolist(),
             int(rng.randint(*new_range))) for _ in range(n_req)]
    arrivals = np.concatenate(
        [[0.0], rng.exponential(0.005, n_req).cumsum()[:-1]])

    def tree_bytes(tree):
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))

    # one pool block's bytes: K and V rows for every layer (fp32 pool —
    # the drill isolates the WEIGHT tier; the int8 pool stacks on top)
    block_bytes = (cfg.num_layers * 2 * block_size * cfg.num_kv_heads
                   * cfg.head_dim_ * 4)
    w_fp32 = tree_bytes(params)
    hbm_budget = w_fp32 + num_blocks * block_bytes

    def drill(model_cfg, model_params, nb, wq=None):
        ecfg = EngineConfig(
            block_size=block_size, num_blocks=nb, max_slots=max_slots,
            max_blocks_per_seq=-(-cfg.max_seq_len // block_size),
            token_budget=budget, kv_dtype=cfg.dtype, weight_quant=wq)
        eng = ServingEngine(model_cfg, model_params, ecfg)
        eng.submit(reqs[0][0], reqs[0][1], uid="warm")   # compile + warm
        eng.run()
        eng.stats, eng.results = EngineStats(), {}
        eng._t0 = eng._clock()
        for i, ((p, n), at) in enumerate(zip(reqs, arrivals)):
            eng.submit(p, n, uid=f"r{i}", arrival_time=float(at))
        done = {u: r for u, r in eng.run().items()
                if r.status == "completed"}
        makespan = max(r.finish_s for r in done.values())
        tps = sum(len(r.tokens) for r in done.values()) / makespan
        return eng, done, tps

    eng0, done0, tps0 = drill(cfg, params, num_blocks)

    # fixed prefill batch for logit divergence (the quality record the
    # planner's --quality-file gate consumes alongside greedy_match)
    probe = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 32)), jnp.int32)
    probe_pos = jnp.arange(32, dtype=jnp.int32)[None]

    def probe_logits(model_cfg, model_params):
        cache = init_kv_cache(cfg.num_layers, 1, 64, cfg.num_kv_heads,
                              cfg.head_dim_, dtype=cfg.dtype)
        logits, _ = llama.llama_forward_with_cache(
            model_cfg, model_params, probe, probe_pos, cache)
        return np.asarray(logits, np.float32)

    ref_logits = probe_logits(cfg, params)

    tag = f"{platform}1"
    aux = {}
    for wq in tiers:
        cfg_q = _dc.replace(cfg, weight_quant=wq)
        params_q = quantize_params_for_serving(cfg_q, params)
        w_q = tree_bytes(params_q)
        nb_q = int((hbm_budget - w_q) // block_bytes)
        capacity = nb_q / num_blocks
        eng_q, done_q, tps_q = drill(cfg_q, params_q, nb_q, wq=wq)
        match = float(np.mean([done_q[u].tokens == done0[u].tokens
                               for u in done0 if u in done_q]))
        div = float(np.max(np.abs(probe_logits(cfg_q, params_q)
                                  - ref_logits)))
        compile_ok = eng_q.compile_count() == 1
        print(f"bench: quantized drill w:{wq} {tps_q:.1f} tok/s vs fp32 "
              f"{tps0:.1f} ({tps_q / tps0:.2f}x), capacity {nb_q}/"
              f"{num_blocks} blocks ({capacity:.2f}x) at equal "
              f"{hbm_budget / 2**20:.1f} MiB, greedy_match {match:.3f}, "
              f"max_logit_div {div:.3f}, compile_count==1 {compile_ok}",
              file=sys.stderr)
        aux.update({
            f"quantized_{wq}_tokens_per_s_{tag}": {
                "value": round(tps_q, 2), "unit": "tokens/sec",
                "vs_baseline": round(tps_q / max(1e-9, tps0), 3)},
            f"quantized_{wq}_capacity_{tag}": {
                "value": round(capacity, 3), "unit": "x",
                "vs_baseline": round(capacity / 1.5, 3)},
            f"quantized_{wq}_greedy_match_{tag}": {
                "value": round(match, 4), "unit": "frac",
                "vs_baseline": round(match, 4)},
            f"quantized_{wq}_max_logit_div_{tag}": {
                "value": round(div, 4), "unit": "abs",
                "vs_baseline": 1.0},
            f"quantized_{wq}_compile_once_{tag}": {
                "value": 1 if compile_ok else 0, "unit": "bool",
                "vs_baseline": 1.0 if compile_ok else 0.0},
        })
    return aux


def long_context_metric(platform: str) -> dict:
    """Million-token-tier drill (docs/serving.md "Long-context tier").

    A prompt that OVERFLOWS a single mesh's paged pool is thrown at a
    cp=1 engine (must refuse: ``RequestRejected(never_fits)`` at the
    door, ``CacheExhaustedError`` from the allocator itself) and then
    served by cp=4 and cp=8 context-parallel engines whose global pool
    is ``cp * num_blocks`` — same model weights, same greedy sampling.
    Reports TTFT scaling cp4->cp8 (the ring prefill divides the
    per-rank attention wall), the static ring-hop wire ratio of the
    int8 codec (acceptance: >=3.5x vs fp32 hops), long-context decode
    tokens/s at cp=4, greedy parity of a FITTABLE prompt across cp=1 /
    cp=4-fp32 / cp=4-int8 (must be 1.0 — CP is an execution strategy,
    not an approximation), and the one-executable invariant
    (compile_count()==1 after sessions of wildly different lengths)."""
    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          EngineStats,
                                                          RequestRejected,
                                                          ServingEngine)
    from neuronx_distributed_tpu.inference.paging import CacheExhaustedError
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.parallel.wire_codec import (
        wire_bytes_per_element)

    n_dev = len(jax.devices())
    if platform == "cpu":
        cfg = llama.LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=704,
            num_layers=4, num_heads=8, num_kv_heads=8, max_seq_len=4096,
            dtype=jnp.float32, param_dtype=jnp.float32)
        block_size, num_blocks = 8, 72       # per rank: 576 tokens at cp=1
        mbps, width = 256, 2048              # width % (8*8) == 0
        long_plen, long_new = 1536, 32       # 1568 > 576, fits cp>=4
        short_plen, short_new = 96, 24       # fits everywhere
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=16, num_heads=8, num_kv_heads=8, max_seq_len=131072)
        block_size, num_blocks = 32, 1280    # per rank: 40960 tokens at cp=1
        mbps, width = 4096, 131072
        long_plen, long_new = 120000, 64     # the 128k-class prompt
        short_plen, short_new = 512, 32
    cps = [c for c in (4, 8) if c <= n_dev]
    if not cps:
        raise RuntimeError(f"long-context drill needs >=4 devices, "
                           f"have {n_dev}")

    # params are built MESH-FREE (uncommitted arrays): every engine in
    # the cp ladder tears the mesh down and rebuilds it at its own
    # degree, and arrays committed to a destroyed mesh re-key the jit
    # cache on every step (compile_count explodes)
    ps.destroy_model_parallel()
    params = meta.unbox(llama.LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    rng = np.random.RandomState(7)
    long_prompt = rng.randint(0, cfg.vocab_size, (long_plen,)).tolist()
    short_prompt = rng.randint(0, cfg.vocab_size, (short_plen,)).tolist()
    base = dict(block_size=block_size, num_blocks=num_blocks,
                max_slots=4, max_blocks_per_seq=mbps,
                token_budget=16, kv_dtype=cfg.dtype)

    def build(cp, wire="int8"):
        ps.destroy_model_parallel()
        if cp > 1:
            ps.initialize_model_parallel(context_parallel_size=cp)
            ecfg = EngineConfig(cp=cp, cp_prefill_width=width,
                                cp_wire_dtype=wire, **base)
        else:
            ps.initialize_model_parallel()
            ecfg = EngineConfig(**base)
        return ServingEngine(cfg, params, ecfg)

    def serve(eng, prompt, new, warm=True):
        if warm:                 # compile on a short session, then reset
            eng.submit(short_prompt, 4, uid="warm")
            eng.run()
            eng.stats, eng.results = EngineStats(), {}
            eng._t0 = eng._clock()
        eng.submit(prompt, new, uid="req", arrival_time=0.0)
        res = eng.run()["req"]
        assert res.status == "completed", res
        return res

    # -- cp=1: the long prompt must be REFUSED, not mangled ---------------
    eng1 = build(1)
    cp1_rejected = cp1_exhausted = False
    try:
        eng1.submit(long_prompt, long_new, uid="long")
    except RequestRejected as e:
        cp1_rejected = e.reason == "never_fits"
    try:        # the pool itself is the binding constraint
        eng1.allocator.alloc(-(-(long_plen + long_new) // block_size))
    except CacheExhaustedError:
        cp1_exhausted = True
    cp1_oom = 1.0 if (cp1_rejected and cp1_exhausted) else 0.0

    # greedy parity leg 1: a fittable prompt on the single-mesh engine
    ref = serve(eng1, short_prompt, short_new, warm=False)

    # -- cp ladder: serve the long prompt, time the first token -----------
    ttft, tps_long, compile_ok, parity = {}, 0.0, True, {}
    for cp in cps:
        eng = build(cp)
        res = serve(eng, long_prompt, long_new)
        ttft[cp] = float(res.ttft_s)
        if cp == 4:
            tps_long = len(res.tokens) / max(1e-9, float(res.finish_s))
            # mixed session lengths through the same executables
            short = serve(eng, short_prompt, short_new, warm=False)
            parity["int8"] = float(short.tokens == ref.tokens)
        compile_ok = compile_ok and eng.compile_count() == 1
    eng_fp = build(4, wire="fp32")
    parity["fp32"] = float(
        serve(eng_fp, short_prompt, short_new).tokens == ref.tokens)
    parity_frac = float(np.mean(list(parity.values())))

    scaling = (ttft[4] / max(1e-9, ttft[8])) if 8 in ttft else 1.0
    wire_ratio = 4.0 / wire_bytes_per_element("int8",
                                              cfg.cp_wire_block_size)
    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    print(f"bench: long-context drill cp1_oom={cp1_oom:.0f} "
          f"ttft={{{', '.join(f'cp{c}: {t:.3f}s' for c, t in ttft.items())}}} "
          f"scaling_cp4/cp8={scaling:.2f}x wire_ratio={wire_ratio:.2f}x "
          f"long_tokens/s={tps_long:.1f} parity={parity_frac:.2f} "
          f"compile_count==1 {compile_ok}", file=sys.stderr)
    tag = f"{platform}1"
    return {
        f"long_context_cp1_oom_{tag}": {
            "value": cp1_oom, "unit": "bool", "vs_baseline": cp1_oom},
        f"long_context_ttft_scaling_vs_cp_{tag}": {
            "value": round(scaling, 3), "unit": "x",
            "vs_baseline": round(scaling, 3)},
        f"long_context_cp_wire_ratio_{tag}": {
            "value": round(wire_ratio, 3), "unit": "x",
            "vs_baseline": round(wire_ratio / 3.5, 3)},
        f"long_context_tokens_per_s_{tag}": {
            "value": round(tps_long, 2), "unit": "tokens/sec",
            "vs_baseline": 1.0},
        f"long_context_greedy_parity_{tag}": {
            "value": parity_frac, "unit": "frac",
            "vs_baseline": parity_frac},
        f"long_context_compile_once_{tag}": {
            "value": 1.0 if compile_ok else 0.0, "unit": "bool",
            "vs_baseline": 1.0 if compile_ok else 0.0},
    }


def prefix_metric(platform: str) -> dict:
    """Prefix-heavy serving drill (docs/serving.md): 64 requests sharing a
    long system prompt with unique tails, ragged Poisson arrivals paced so
    the no-sharing baseline backlogs on prefill. Served three ways on the
    same model: prefix sharing off (baseline), on (trie + copy-on-write),
    and on + disaggregated prefill/decode workers. Greedy outputs must be
    bit-identical across all three; reports the TTFT p99 improvement
    factor, the hit rate, prompt tokens never recomputed, and the
    disaggregated throughput ratio. RETURNS aux entries keyed by metric
    name — never prints the JSON line itself."""
    import numpy as np
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          EngineStats,
                                                          ServingEngine)
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    if platform == "cpu":
        cfg = llama.LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=704,
            num_layers=4, num_heads=8, num_kv_heads=8, max_seq_len=256)
        n_req, sys_len, max_slots, budget = 64, 100, 12, 64
        tail_range, new_range = (4, 9), (5, 11)
        block_size, num_blocks = 8, 224
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=16, num_heads=8, num_kv_heads=8, max_seq_len=4096)
        n_req, sys_len, max_slots, budget = 64, 256, 8, 256
        tail_range, new_range = (8, 33), (8, 33)
        block_size, num_blocks = 16, 512
    params = meta.unbox(llama.LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    rng = np.random.RandomState(0)
    sys_prompt = rng.randint(1, cfg.vocab_size, (sys_len,)).tolist()
    reqs = [(sys_prompt
             + rng.randint(1, cfg.vocab_size,
                           (rng.randint(*tail_range),)).tolist(),
             int(rng.randint(*new_range))) for _ in range(n_req)]

    base_ecfg = dict(block_size=block_size, num_blocks=num_blocks,
                     max_slots=max_slots, token_budget=budget,
                     max_blocks_per_seq=-(-cfg.max_seq_len // block_size),
                     kv_dtype=cfg.dtype)

    def run_engine(arrivals=None, **extra):
        eng = ServingEngine(cfg, params, EngineConfig(**base_ecfg, **extra))
        # warm: compiles the worker(s) and, when sharing is on, seeds the
        # trie with the system prompt — the production steady state
        eng.submit(sys_prompt, 1, uid="warm")
        eng.run()
        eng.stats, eng.results = EngineStats(), {}
        eng._t0 = eng._clock()
        t0 = time.perf_counter()
        for i, (p, n) in enumerate(reqs):
            at = 0.0 if arrivals is None else float(arrivals[i])
            eng.submit(p, n, uid=f"r{i}", arrival_time=at)
        results = eng.run()
        wall = time.perf_counter() - t0
        done = {u: r.tokens for u, r in results.items()
                if r.status == "completed"}
        toks = sum(len(t) for t in done.values())
        return eng, done, eng.stats.report(), toks / wall

    # pace arrivals off an all-at-zero baseline run: gaps summing to ~35%
    # of its busy time guarantee the no-sharing server backlogs on prefill
    _, _, _, base_tps0 = run_engine()
    busy_s = sum(n for _, n in reqs) / base_tps0
    arrivals = np.concatenate(
        [[0.0], rng.exponential(0.2 * busy_s / n_req, n_req).cumsum()[:-1]])

    base_eng, base_done, base_rep, base_tps = run_engine(arrivals)
    shr_eng, shr_done, shr_rep, shr_tps = run_engine(
        arrivals, prefix_sharing=True)
    # disaggregation earns its keep by right-sizing each worker: with the
    # trie absorbing the system prompt only short tails ever prefill, so
    # the prefill worker runs at a quarter of the packed width while the
    # decode worker is max_slots wide — the packed step must stay
    # token_budget wide for every row kind
    dis_eng, dis_done, dis_rep, dis_tps = run_engine(
        arrivals, prefix_sharing=True, disaggregated=True,
        prefill_budget=max(max_slots, budget // 4))

    greedy_ok = (base_done == shr_done == dis_done
                 and len(base_done) == n_req)
    saved = base_eng.stats.prefill_tokens - shr_eng.stats.prefill_tokens
    ttft_gain = base_rep["ttft_p99_ms"] / max(1e-9, shr_rep["ttft_p99_ms"])

    # --- planner cross-check on the prefix-heavy mix (ISSUE 15): state
    # the shared prefix in the TrafficSpec, calibrate from the sharing
    # run's measured steps, and drill the emitted (prefix_sharing [+
    # disaggregated]) config against the hand-tuned one.
    import dataclasses as _dc

    from neuronx_distributed_tpu.plan import (ModelSpec, TrafficSpec,
                                              calibrate, default_hardware,
                                              serving_search,
                                              serving_token_s)

    spec = ModelSpec.from_model_config(cfg, global_batch=8,
                                       name="bench-prefix")
    steps_s = [s for s in shr_eng.stats.step_latency_s if s > 0]
    hw = calibrate(default_hardware(platform),
                   serve_step_seconds=steps_s).hardware
    rows = shr_eng.stats.prefill_tokens + sum(
        len(t) for t in shr_done.values())
    meas_tok = max(1e-9, (sum(steps_s)
                          - hw.serve_overhead_s * len(steps_s))
                   / max(1, rows))
    mean_prompt = float(np.mean([len(p) for p, _ in reqs]))
    mean_new = float(np.mean([n for _, n in reqs]))
    model_tok = serving_token_s(spec, hw, context=mean_prompt)
    hw = _dc.replace(hw, mfu=min(1.0, max(1e-4,
                                          hw.mfu * model_tok / meas_tok)))
    traffic = TrafficSpec(
        request_rate=n_req / max(1e-9, float(arrivals[-1])),
        prompt_tokens=mean_prompt, new_tokens=mean_new,
        shared_prefix_tokens=float(sys_len))
    planned = serving_search(spec, hw, traffic, block_size=block_size,
                             budgets=(8, 16, 32, 64, 128),
                             slots=(2, 4, 8, 12, 16),
                             disaggregated=True, top_k=1)
    plan_aux = {}
    ptag = f"{platform}1"
    if planned:
        peng = ServingEngine(cfg, params, EngineConfig(
            kv_dtype=cfg.dtype, **planned[0].engine))
        peng.submit(sys_prompt, 1, uid="warm")
        peng.run()
        peng.stats, peng.results = EngineStats(), {}
        peng._t0 = peng._clock()
        t0 = time.perf_counter()
        for i, (p, n) in enumerate(reqs):
            peng.submit(p, n, uid=f"r{i}", arrival_time=float(arrivals[i]))
        pres = peng.run()
        pwall = time.perf_counter() - t0
        pdone = {u: r.tokens for u, r in pres.items()
                 if r.status == "completed"}
        if pdone:
            plan_tps = sum(len(t) for t in pdone.values()) / pwall
            plan_ratio = plan_tps / dis_tps
            # with the trie hot, only unique tails prefill; the shared
            # prompt is computed once at warm time
            rows_total = sys_len + sum(len(p) - sys_len + n
                                       for p, n in reqs)
            modeled_tps = _modeled_drill_tps(
                planned[0], float(arrivals[-1]),
                sum(n for _, n in reqs), rows_total, mean_new)
            model_err = abs(modeled_tps - plan_tps) / plan_tps
            print(f"bench: prefix planner picked "
                  f"{planned[0].describe()} -> {plan_tps:.1f} tok/s "
                  f"({plan_ratio:.3f}x hand-tuned disagg), modeled "
                  f"{modeled_tps:.1f} tok/s "
                  f"(err {model_err:.1%}) "
                  f"greedy_match={pdone == dis_done}", file=sys.stderr)
            plan_aux = {
                f"prefix_plan_tokens_per_s_{ptag}": {
                    "value": round(plan_tps, 2), "unit": "tokens/sec",
                    "vs_baseline": round(plan_ratio, 3)},
                f"prefix_plan_vs_hand_ratio_{ptag}": {
                    "value": round(plan_ratio, 3), "unit": "x",
                    "vs_baseline": round(plan_ratio, 3)},
                f"prefix_plan_model_err_{ptag}": {
                    "value": round(model_err, 4), "unit": "frac",
                    "vs_baseline": 1.0},
            }
    print(f"bench: prefix drill hit_rate={shr_rep['prefix_hit_rate']:.3f} "
          f"ttft_p99 base={base_rep['ttft_p99_ms']:.1f}ms "
          f"shared={shr_rep['ttft_p99_ms']:.1f}ms ({ttft_gain:.2f}x) "
          f"prefill_tokens {base_eng.stats.prefill_tokens}->"
          f"{shr_eng.stats.prefill_tokens} "
          f"cow={shr_rep['cow_copies']} disagg/packed="
          f"{dis_tps / shr_tps:.3f} greedy_match={greedy_ok}",
          file=sys.stderr)
    tag = f"{platform}1"
    return {
        **plan_aux,
        f"prefix_hit_rate_{tag}": {
            "value": round(shr_rep["prefix_hit_rate"], 4), "unit": "frac",
            "vs_baseline": 1.0},
        f"ttft_p99_ms_prefix_{tag}": {
            "value": round(shr_rep["ttft_p99_ms"], 2), "unit": "ms",
            "vs_baseline": round(ttft_gain, 3)},
        f"serving_tokens_per_s_disagg_{tag}": {
            "value": round(dis_tps, 2), "unit": "tokens/sec",
            "vs_baseline": round(dis_tps / shr_tps, 3)},
        f"prefix_prefill_tokens_saved_{tag}": {
            "value": int(saved), "unit": "tokens", "vs_baseline": 1.0},
        f"prefix_cow_copies_{tag}": {
            "value": int(shr_rep["cow_copies"]), "unit": "copies",
            "vs_baseline": 1.0},
        f"prefix_greedy_match_{tag}": {
            "value": 1.0 if greedy_ok else 0.0, "unit": "frac",
            "vs_baseline": 1.0},
    }


def router_metric(platform: str) -> dict:
    """Multi-replica failover drill (docs/serving.md): run the router's
    :func:`chaos_drill` — a fault plan crashes replica ``r1`` mid-decode;
    its in-flight requests fail over to the survivor and must finish with
    tokens bit-identical to a fault-free reference run. RETURNS aux
    entries keyed by metric name — never prints the JSON line itself."""
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import EngineConfig
    from neuronx_distributed_tpu.inference.router import chaos_drill
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    if platform == "cpu":
        cfg = llama.tiny_config(num_layers=2, dtype=jnp.float32,
                                param_dtype=jnp.float32)
        n_req, prompt_len, max_new = 6, 6, 4
        ecfg = EngineConfig(block_size=4, num_blocks=16, max_slots=2,
                            max_blocks_per_seq=8, token_budget=8,
                            kv_dtype=jnp.float32)
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=16, num_heads=8, num_kv_heads=8, max_seq_len=4096)
        n_req, prompt_len, max_new = 12, 32, 16
        ecfg = EngineConfig(block_size=16, num_blocks=128, max_slots=4,
                            max_blocks_per_seq=16, token_budget=64,
                            kv_dtype=cfg.dtype)
    params = meta.unbox(llama.LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    drill = chaos_drill(cfg, params, ecfg, n_requests=n_req,
                        prompt_len=prompt_len, max_new_tokens=max_new)
    print(f"bench: router drill availability={drill['router_availability']} "
          f"failovers={drill['router_failovers']} "
          f"resubmitted_tokens={drill['router_resubmitted_tokens']} "
          f"greedy_match_ref={drill['router_greedy_match_ref']}",
          file=sys.stderr)
    tag = f"{platform}1"
    return {
        f"router_availability_{tag}": {
            "value": round(drill["router_availability"], 4), "unit": "frac",
            "vs_baseline": 1.0},
        f"router_failovers_{tag}": {
            "value": int(drill["router_failovers"]), "unit": "failovers",
            "vs_baseline": 1.0},
        f"router_ttft_p99_ms_chaos_{tag}": {
            "value": round(drill["router_ttft_p99_ms_chaos"], 2),
            "unit": "ms", "vs_baseline": 1.0},
        f"router_resubmitted_tokens_{tag}": {
            "value": int(drill["router_resubmitted_tokens"]),
            "unit": "tokens", "vs_baseline": 1.0},
        f"router_greedy_match_ref_{tag}": {
            "value": round(drill["router_greedy_match_ref"], 4),
            "unit": "frac", "vs_baseline": 1.0},
    }


def elastic_metric(platform: str) -> dict:
    """Elastic-fleet drill (docs/serving.md "Elastic fleet"): run
    :func:`elastic_chaos_drill` — chaos preempts a replica (its live
    KV sessions migrate to survivors with zero re-prefill), a
    ``scale_burst`` forces an AOT-cache-warm scale-up, a scale-down
    retires a replica by migration, and the preempted replica revives
    through the cache. RETURNS aux entries keyed by metric name —
    never prints the JSON line itself."""
    import tempfile

    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import EngineConfig
    from neuronx_distributed_tpu.inference.router import elastic_chaos_drill
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    if platform == "cpu":
        cfg = llama.tiny_config(num_layers=2, dtype=jnp.float32,
                                param_dtype=jnp.float32)
        n_req, prompt_len, max_new = 8, 8, 4
        ecfg = EngineConfig(block_size=4, num_blocks=16, max_slots=4,
                            max_blocks_per_seq=8, token_budget=8,
                            kv_dtype=jnp.float32)
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=16, num_heads=8, num_kv_heads=8, max_seq_len=4096)
        n_req, prompt_len, max_new = 12, 32, 16
        ecfg = EngineConfig(block_size=16, num_blocks=128, max_slots=8,
                            max_blocks_per_seq=16, token_budget=64,
                            kv_dtype=cfg.dtype)
    params = meta.unbox(llama.LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    with tempfile.TemporaryDirectory(prefix="nxd-aot-") as cache_dir:
        drill = elastic_chaos_drill(cfg, params, ecfg, n_requests=n_req,
                                    prompt_len=prompt_len,
                                    max_new_tokens=max_new,
                                    clock=lambda: 0.0,
                                    cache_dir=cache_dir)
    print(f"bench: elastic drill "
          f"availability={drill['elastic_availability']} "
          f"migrated_tokens={drill['migrated_tokens']} "
          f"reprefilled_tokens={drill['reprefilled_tokens']} "
          f"cold_ms={drill['bundle_cold_start_ms']:.1f} "
          f"warm_ms={drill['bundle_cold_start_warm_ms']:.1f}",
          file=sys.stderr)
    tag = f"{platform}1"
    return {
        f"elastic_availability_{tag}": {
            "value": round(drill["elastic_availability"], 4),
            "unit": "frac", "vs_baseline": 1.0},
        f"bundle_cold_start_warm_ms_{tag}": {
            "value": round(drill["bundle_cold_start_warm_ms"], 2),
            "unit": "ms", "vs_baseline": 1.0},
        f"bundle_cold_start_speedup_{tag}": {
            "value": round(drill["bundle_cold_start_speedup"], 2),
            "unit": "x", "vs_baseline": 1.0},
        f"migrated_tokens_{tag}": {
            "value": int(drill["migrated_tokens"]), "unit": "tokens",
            "vs_baseline": 1.0},
        f"reprefilled_tokens_{tag}": {
            "value": int(drill["reprefilled_tokens"]), "unit": "tokens",
            "vs_baseline": 1.0},
        f"elastic_greedy_match_ref_{tag}": {
            "value": round(drill["elastic_greedy_match_ref"], 4),
            "unit": "frac", "vs_baseline": 1.0},
        f"elastic_scale_events_{tag}": {
            "value": int(drill["elastic_scale_ups"]
                         + drill["elastic_scale_downs"]
                         + drill["elastic_preemptions"]),
            "unit": "events", "vs_baseline": 1.0},
        f"elastic_max_compile_count_{tag}": {
            "value": int(drill["max_compile_count"]), "unit": "compiles",
            "vs_baseline": 1.0},
    }


def fabric_metric(platform: str) -> dict:
    """Cross-host fabric drill (docs/serving.md "Cross-host fabric"):
    run :func:`fabric_chaos_drill` twice — clean, then under
    ``link_partition`` chaos (every stream torn mid-flight, every
    request healed by the re-prefill fallback). RETURNS aux entries
    keyed by metric name — never prints the JSON line itself.

    The tiny config pins ``num_heads=num_kv_heads=1`` (head_dim 64):
    the per-row scale tax of the int8 wire layout amortizes over the
    row, so the measured ``handoff_wire_ratio`` clears the >=3.5x bar
    the quantized codec promises vs fp32."""
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import EngineConfig
    from neuronx_distributed_tpu.inference.router import fabric_chaos_drill
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    if platform == "cpu":
        cfg = llama.tiny_config(num_layers=2, num_heads=1,
                                num_kv_heads=1, dtype=jnp.float32,
                                param_dtype=jnp.float32)
        n_req, prompt_len, max_new = 6, 8, 5
        ecfg = EngineConfig(block_size=4, num_blocks=32, max_slots=6,
                            max_blocks_per_seq=8, token_budget=8,
                            kv_dtype=jnp.float32, quantized=True)
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=16, num_heads=8, num_kv_heads=8, max_seq_len=4096)
        n_req, prompt_len, max_new = 12, 32, 16
        ecfg = EngineConfig(block_size=16, num_blocks=256, max_slots=12,
                            max_blocks_per_seq=16, token_budget=64,
                            kv_dtype=cfg.dtype, quantized=True)
    params = meta.unbox(llama.LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    clean = fabric_chaos_drill(cfg, params, ecfg, n_requests=n_req,
                               prompt_len=prompt_len,
                               max_new_tokens=max_new,
                               clock=lambda: 0.0)
    torn = fabric_chaos_drill(
        cfg, params, ecfg, n_requests=n_req, prompt_len=prompt_len,
        max_new_tokens=max_new, clock=lambda: 0.0,
        plan_spec="seed=3; link|* : link_partition, after=8, times=1")
    print(f"bench: fabric drill "
          f"availability={clean['fabric_availability']}"
          f"/{torn['fabric_availability']} "
          f"handoffs={clean['handoffs']} "
          f"wire_ratio={clean['handoff_wire_ratio']:.2f} "
          f"partition_aborts={torn['handoff_aborts']} "
          f"reprefilled={torn['reprefilled_tokens']}",
          file=sys.stderr)
    tag = f"{platform}1"
    return {
        f"fabric_availability_{tag}": {
            "value": round(clean["fabric_availability"], 4),
            "unit": "frac", "vs_baseline": 1.0},
        f"fabric_availability_partition_{tag}": {
            "value": round(torn["fabric_availability"], 4),
            "unit": "frac", "vs_baseline": 1.0},
        f"fabric_greedy_match_ref_{tag}": {
            "value": round(clean["fabric_greedy_match_ref"], 4),
            "unit": "frac", "vs_baseline": 1.0},
        f"handoff_wire_ratio_{tag}": {
            "value": round(clean["handoff_wire_ratio"], 3),
            "unit": "x", "vs_baseline": 1.0},
        f"handoff_retries_{tag}": {
            "value": int(clean["handoff_retries"]), "unit": "retries",
            "vs_baseline": 1.0},
        f"handoffs_{tag}": {
            "value": int(clean["handoffs"]), "unit": "sessions",
            "vs_baseline": 1.0},
        f"ttft_p99_ms_handoff_{tag}": {
            "value": round(clean["ttft_p99_ms_handoff"], 2),
            "unit": "ms", "vs_baseline": 1.0},
        f"fabric_reprefilled_tokens_partition_{tag}": {
            "value": int(torn["reprefilled_tokens"]), "unit": "tokens",
            "vs_baseline": 1.0},
        f"fabric_decode_compile_count_{tag}": {
            "value": int(max(clean["decode_compile_count"],
                             torn["decode_compile_count"])),
            "unit": "compiles", "vs_baseline": 1.0},
        f"fabric_pool_leak_blocks_{tag}": {
            "value": int(clean["pool_leak_blocks"]
                         + torn["pool_leak_blocks"]),
            "unit": "blocks", "vs_baseline": 1.0},
    }


def sdc_metric(platform: str, n_dev: int) -> dict:
    """Silent-data-corruption drill, both halves of the defense
    (docs/resilience.md "Silent data corruption"). RETURNS aux entries
    keyed by metric name — never prints a JSON line.

    **Train:** a tiny llama trains with ``integrity_every=2``; for each
    of three chaos seeds one param bit is flipped at a cadence boundary.
    The drill reports the detection rate (every flip must be caught at
    the boundary it landed on — within one cadence window by
    construction), whether the watchdog rewind restored a
    content-verified checkpoint, and whether the final loss is
    bit-identical to a fault-free run over the same batches. The
    fingerprint's cost rides as ``sdc_fp_overhead_pct`` (steady-state
    step time with the in-step fingerprint at the default cadence vs
    without — CPU timing is noisy, the structural numbers are the
    headline) and ``sdc_integrity_extra_compiles`` (cadence lives inside
    ``lax.cond``, so it must be 0).

    **Serve:** ``sdc_serving_drill`` — a chaos bitflip corrupts one
    decoded token (the request *completes*; no crash/latency signal),
    the greedy shadow spot-check catches the divergence, the corrupted
    replica is quarantined and revived, and every served answer stays
    bit-identical to the fault-free reference at availability 1.0.
    """
    import shutil
    import tempfile

    import numpy as np

    import neuronx_distributed_tpu as nxd
    from flax.core import meta
    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.resilience import (FaultPlan,
                                                    IntegrityMonitor,
                                                    Watchdog)
    from neuronx_distributed_tpu.trainer import (
        checkpoint as ckpt,
        initialize_parallel_model,
        initialize_parallel_optimizer,
        make_train_step,
    )
    from neuronx_distributed_tpu.trainer.loop import (CheckpointCallback,
                                                      Trainer)

    cfg = nxd.neuronx_distributed_config(tensor_parallel_size=1)
    mcfg = tiny_config(num_layers=1, dtype=jnp.float32,
                       param_dtype=jnp.float32)
    model = LlamaForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0),
                             (len(jax.devices()), 17), 0, mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           batch["input_ids"])
    tx, state0, sh = initialize_parallel_optimizer(pm, params, 1e-3)

    n_steps, every = 6, 2
    step = make_train_step(pm, tx, sh, donate=False, integrity_every=every)

    # fault-free reference over the same fixed batches
    s, m = state0, None
    for _ in range(n_steps):
        s, m = step(s, batch)
    ref_loss = float(m["loss"])

    detected = rewound_verified = loss_matched = 0
    seeds = (0, 1, 2)
    for seed in seeds:
        ckpt_dir = tempfile.mkdtemp(prefix="nxd_bench_sdc_")
        wd = Watchdog(policy="rewind", checkpoint_path=ckpt_dir)
        mon = IntegrityMonitor(
            every=every, watchdog=wd,
            chaos=FaultPlan.parse(
                f"seed={seed}; integrity|params : bitflip, after=1, "
                "times=1"))
        trainer = Trainer(step, state0, callbacks=[
            CheckpointCallback(ckpt_dir, every=every), mon])
        st, metrics = trainer.fit(iter([batch] * (3 * n_steps)),
                                  max_steps=n_steps)
        # one flip -> one mismatch at the boundary it landed on
        detected += int(mon.flips_injected == 1 and mon.mismatches == 1)
        tags = ckpt.list_complete_tags(ckpt_dir)
        rewound_verified += int(
            wd.anomalies == 1
            and all(ckpt.verify_checkpoint(ckpt_dir, t)[0] for t in tags))
        loss_matched += int(int(st.step) == n_steps
                            and float(metrics["loss"]) == ref_loss)
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    detection_rate = detected / len(seeds)

    # steady-state per-step cost of the in-step fingerprint: every=1 is
    # the worst case (paid every step); the default-cadence overhead is
    # this divided by the cadence
    base_step = make_train_step(pm, tx, sh, donate=False)
    fp_step = make_train_step(pm, tx, sh, donate=False, integrity_every=1)

    def timed(f):
        s = state0
        for _ in range(2):  # compile initial + steady layouts
            s, _ = f(s, batch)
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            s, m = f(s, batch)
            jax.block_until_ready(m["loss"])
            best = min(best, time.perf_counter() - t0)
        return best, f._cache_size()

    t_base, cc_base = timed(base_step)
    t_fp, cc_fp = timed(fp_step)
    default_cadence = 50
    overhead_pct = max(t_fp - t_base, 0.0) / t_base * 100.0
    amortized_pct = overhead_pct / default_cadence

    # serving half: bitflip -> shadow catch -> quarantine -> revive
    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    from neuronx_distributed_tpu.inference.engine import EngineConfig
    from neuronx_distributed_tpu.inference.router import sdc_serving_drill

    scfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                       num_layers=2)
    sparams = meta.unbox(LlamaForCausalLM(scfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    drill = sdc_serving_drill(
        scfg, sparams,
        EngineConfig(block_size=4, num_blocks=16, max_slots=2,
                     max_blocks_per_seq=8, token_budget=8,
                     kv_dtype=jnp.float32))

    print(f"bench: sdc drill detection={detection_rate:.2f} "
          f"rewind_verified={rewound_verified}/{len(seeds)} "
          f"loss_match={loss_matched}/{len(seeds)} "
          f"fp_overhead@1={overhead_pct:.2f}% "
          f"(@{default_cadence}={amortized_pct:.3f}%) "
          f"extra_compiles={cc_fp - cc_base} "
          f"serve_avail={drill['sdc_serving_availability']} "
          f"serve_mismatch={drill['sdc_serving_mismatches']} "
          f"serve_quarantine={drill['sdc_serving_quarantines']}",
          file=sys.stderr)
    tag = f"{platform}{n_dev}"
    return {
        f"sdc_detection_rate_{tag}": {
            "value": round(detection_rate, 4), "unit": "frac",
            "vs_baseline": 1.0},
        f"sdc_rewind_verified_{tag}": {
            "value": int(rewound_verified == len(seeds)), "unit": "bool",
            "vs_baseline": 1.0},
        f"sdc_final_loss_match_{tag}": {
            "value": int(loss_matched == len(seeds)), "unit": "bool",
            "vs_baseline": 1.0},
        f"sdc_fp_overhead_pct_{tag}": {
            "value": round(amortized_pct, 4), "unit": "pct",
            "vs_baseline": 1.0},
        f"sdc_integrity_extra_compiles_{tag}": {
            "value": int(cc_fp - cc_base), "unit": "compiles",
            "vs_baseline": 1.0},
        f"sdc_serving_availability_{platform}1": {
            "value": round(drill["sdc_serving_availability"], 4),
            "unit": "frac", "vs_baseline": 1.0},
        f"sdc_serving_mismatches_{platform}1": {
            "value": int(drill["sdc_serving_mismatches"]),
            "unit": "events", "vs_baseline": 1.0},
        f"sdc_serving_quarantines_{platform}1": {
            "value": int(drill["sdc_serving_quarantines"]),
            "unit": "events", "vs_baseline": 1.0},
        f"sdc_serving_greedy_match_ref_{platform}1": {
            "value": round(drill["sdc_serving_greedy_match_ref"], 4),
            "unit": "frac", "vs_baseline": 1.0},
        f"sdc_serving_max_compile_count_{platform}1": {
            "value": int(drill["sdc_serving_max_compile_count"]),
            "unit": "compiles", "vs_baseline": 1.0},
    }


def comm_metric(platform: str, n_dev: int) -> dict:
    """Gradient-collective microbenchmark: step time of a gradient-sized
    ``all_reduce`` over the data axes at fp32 vs blockwise int8
    (``parallel/comm_compressed.py``) plus the bytes-on-wire ratio.
    RETURNS aux entries keyed by metric name — never prints a JSON line.

    On a 1-device mesh both collectives are no-ops, so the speedup is
    reported as 1.0 (``vs_baseline`` 1.0) instead of timing noise; on CPU
    the quantize arithmetic usually outweighs the memcpy "wire", so values
    below 1.0 there are honest, not a bug — the wire-byte ratio is the
    hardware-independent number.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from neuronx_distributed_tpu.parallel import comm_compressed as cc
    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()  # every chip on the dp axis
    mesh = ps.get_mesh()
    group = dict(mesh.shape).get("dp", 1) * dict(mesh.shape).get("cp", 1)
    elems = 1 << (22 if platform != "cpu" else 20)  # 16 MiB / 4 MiB of f32
    x = jnp.asarray(np.random.RandomState(0).randn(elems).astype(np.float32))
    cfg8 = cc.CompressionConfig(dtype="int8", block_size=256)

    def make(cfgv):
        def inner(v):
            return cc.all_reduce(v, ("dp", "cp"), config=cfgv, op="mean")

        return jax.jit(ps.shard_map(inner, mesh, in_specs=(P(),),
                                    out_specs=P()))

    def timed(f):
        jax.block_until_ready(f(x))  # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            best = min(best, time.perf_counter() - t0)
        return best

    t_fp32 = timed(make(None))
    t_int8 = timed(make(cfg8))
    speedup = (t_fp32 / t_int8) if group > 1 else 1.0
    print(f"bench: comm allreduce {elems} f32 over {group} ranks: "
          f"fp32={t_fp32 * 1e3:.2f}ms int8={t_int8 * 1e3:.2f}ms "
          f"wire_ratio={cfg8.ratio:.2f}x", file=sys.stderr)
    return {
        f"comm_allreduce_int8_speedup_{platform}{n_dev}": {
            "value": round(speedup, 3), "unit": "x_vs_fp32",
            "vs_baseline": 1.0},
        f"comm_allreduce_int8_wire_ratio_{platform}{n_dev}": {
            "value": round(cfg8.ratio, 3), "unit": "x_fewer_bytes",
            "vs_baseline": 1.0},
    }


def obs_metric(platform: str, n_dev: int) -> dict:
    """Observability self-measurement drill (docs/observability.md):

    * **obs_overhead_pct** — the same tiny serving workload through
      :class:`ServingEngine` with the tracer+metrics enabled vs disabled
      (min-of-N each, interleaved, to damp host timing noise). Disabled is
      the default mode, so this is the price of *leaving the hooks in*.
    * **obs_compile_events** — ``nxd_compile_total`` after the drill; the
      packed worker compiles exactly once, and any recompile the engine
      sneaks in shows up here (and as a ``recompile_detected`` event).
    * **obs_wire_bytes_int8_ratio** — run a quantized ``all_reduce``
      under ``shard_map`` on the real mesh and read the compressed-vs-raw
      ratio back from the *runtime counters*; ``vs_baseline`` is measured
      over the codec's ``wire_bytes_per_element`` prediction (~3.94x), so
      1.0 means the accounting and the codec agree. On a 1-device mesh
      the collectives are no-ops, so the codec arithmetic is pushed
      through the same accounting path instead.

    RETURNS aux entries keyed by metric name — never prints a JSON line.
    """
    import numpy as np
    from flax.core import meta
    from jax.sharding import PartitionSpec as P

    from neuronx_distributed_tpu import obs
    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          EngineStats,
                                                          ServingEngine)
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import comm_compressed as cc
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.parallel.wire_codec import (
        CompressionConfig, blockwise_wire_bytes)

    was_enabled = obs.enabled()
    try:
        ps.destroy_model_parallel()
        ps.initialize_model_parallel()
        obs.reset()
        obs.enable()  # on for the warm run so the first compile is counted

        # the serving drill's model size, not the 2-layer test toy: the
        # overhead is per-step host work, so a toy step inflates the
        # percentage far beyond what any real deployment would see
        if platform == "cpu":
            cfg = llama.LlamaConfig(
                vocab_size=1024, hidden_size=256, intermediate_size=704,
                num_layers=4, num_heads=8, num_kv_heads=8, max_seq_len=512)
            n_req, max_slots, budget = 6, 4, 16
            plen_range, new_range = (8, 25), (4, 13)
            block_size, num_blocks = 8, 64
        else:
            cfg = llama.LlamaConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_layers=16, num_heads=8, num_kv_heads=8,
                max_seq_len=4096)
            n_req, max_slots, budget = 16, 8, 64
            plen_range, new_range = (32, 129), (16, 65)
            block_size, num_blocks = 16, 256
        ecfg = EngineConfig(
            block_size=block_size, num_blocks=num_blocks,
            max_slots=max_slots,
            max_blocks_per_seq=-(-cfg.max_seq_len // block_size),
            token_budget=budget, kv_dtype=cfg.dtype)
        params = meta.unbox(llama.LlamaForCausalLM(cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
        rng = np.random.RandomState(0)
        reqs = [(rng.randint(0, cfg.vocab_size,
                             (rng.randint(*plen_range),)).tolist(),
                 int(rng.randint(*new_range))) for _ in range(n_req)]
        eng = ServingEngine(cfg, params, ecfg)
        eng.submit(reqs[0][0], reqs[0][1], uid="warm")  # compile + warm
        eng.run()

        def run_once():
            eng.stats, eng.results = EngineStats(), {}
            eng._t0 = eng._clock()
            for i, (p, n) in enumerate(reqs):
                eng.submit(p, n, uid=f"r{i}")
            t0 = time.perf_counter()
            eng.run()
            return time.perf_counter() - t0

        # interleave on/off runs, alternating which goes first each round,
        # so warm-up drift (page cache, thermal) cancels instead of
        # systematically favouring whichever mode runs second
        t_on, t_off = float("inf"), float("inf")
        for r in range(4):
            for on in ((False, True) if r % 2 == 0 else (True, False)):
                if on:
                    obs.enable()
                    t_on = min(t_on, run_once())
                else:
                    obs.disable()
                    t_off = min(t_off, run_once())
        obs.enable()
        overhead_pct = (t_on - t_off) / t_off * 100.0

        events = obs.compile_events()
        compile_once = eng.compile_count() == 1

        # wire-byte counters vs the codec's arithmetic, on the live mesh
        mesh = ps.get_mesh()
        group = (dict(mesh.shape).get("dp", 1)
                 * dict(mesh.shape).get("cp", 1))
        cfg8 = cc.CompressionConfig(dtype="int8", block_size=256)
        predicted = 4.0 / CompressionConfig(dtype="int8",
                                            block_size=256
                                            ).wire_bytes_per_element
        elems = 1 << 16
        if group > 1:
            x = jnp.asarray(np.random.RandomState(0)
                            .randn(elems).astype(np.float32))

            def inner(v):
                return cc.all_reduce(v, ("dp", "cp"), config=cfg8,
                                     op="mean")

            fn = jax.jit(ps.shard_map(inner, mesh, in_specs=(P(),),
                                      out_specs=P()))
            jax.block_until_ready(fn(x))
        else:
            # 1-device mesh: the collective is a no-op, so exercise the
            # accounting with the codec's own byte arithmetic (2 wire
            # passes, as compressed all_reduce = RS + AG)
            obs.record_wire_bytes(
                "grad_all_reduce", "int8",
                2 * blockwise_wire_bytes(elems, cfg8), 2 * 4.0 * elems)
        ratio = obs.wire_compression_ratio()
    finally:
        if was_enabled:
            obs.enable()
        else:
            obs.disable()

    print(f"bench: obs drill overhead={overhead_pct:+.2f}% "
          f"(on={t_on * 1e3:.1f}ms off={t_off * 1e3:.1f}ms) "
          f"compile_events={events:.0f} compile_once={compile_once} "
          f"wire_ratio={ratio:.3f} (predicted {predicted:.3f})",
          file=sys.stderr)
    tag = f"{platform}{n_dev}"
    return {
        f"obs_overhead_pct_{tag}": {
            "value": round(overhead_pct, 3), "unit": "pct",
            "vs_baseline": 1.0},
        f"obs_compile_events_{tag}": {
            "value": int(events), "unit": "compiles",
            "vs_baseline": 1.0 if compile_once else 0.0},
        f"obs_wire_bytes_int8_ratio_{tag}": {
            "value": round(ratio, 4), "unit": "x_fewer_bytes",
            "vs_baseline": round(ratio / predicted, 4)},
    }


def plan_metric(platform: str, n_dev: int) -> dict:
    """Placement-planner drill (docs/planner.md): run the analytic search
    at this host's device count over the bench model shape and compare the
    winner's modeled step cost against the hand-picked layout main() hard
    codes. RETURNS aux entries keyed by metric name — never prints a JSON
    line.

    ``plan_advantage_ratio`` >= 1.0 means the planner's plan models at
    least as fast as the hand-picked one (the planner enumerates the
    hand-picked point, so < 1.0 would be a search bug). Costs are the
    analytic model's — deterministic by construction; the measured
    refinement pass re-ranks with a fixed seed and stable tie-breaks, so
    the reported best plan is identical across runs on the same host.
    """
    from neuronx_distributed_tpu import plan as planner
    from neuronx_distributed_tpu.models import llama

    if platform == "cpu":
        mcfg = llama.LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=704,
            num_layers=4, num_heads=8, num_kv_heads=8, max_seq_len=512)
        batch, seq = 4, 512
    elif n_dev >= 8:
        mcfg, batch, seq = llama.LLAMA2_7B, 4, 2048
    else:
        mcfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=16, num_heads=8, num_kv_heads=8, max_seq_len=2048)
        batch, seq = 8, 2048
    spec = planner.ModelSpec.from_model_config(
        mcfg, seq=seq, global_batch=max(batch, n_dev), name="bench")
    hw = planner.default_hardware(platform)

    t0 = time.perf_counter()
    result = planner.search(spec, hw, n_dev)
    refined = planner.refine(result.ranked, spec, hw, seed=0)
    search_ms = (time.perf_counter() - t0) * 1e3

    best = result.best
    hand = planner.handpicked_plan(n_dev, platform=platform)
    hand_cost = planner.step_cost(hand, spec, hw)
    ratio = (hand_cost.total_s / best.total_s) if best else 0.0
    print(f"bench: plan search {result.n_enumerated} candidates in "
          f"{search_ms:.1f}ms: best={best.plan.describe() if best else None} "
          f"({best.total_s * 1e3:.2f}ms modeled) vs handpicked "
          f"{hand.describe()} ({hand_cost.total_s * 1e3:.2f}ms); "
          f"refined winner={refined[0].plan.describe() if refined else None}",
          file=sys.stderr)
    return {
        f"plan_best_cost_{platform}{n_dev}": {
            "value": round(best.total_s * 1e3, 3) if best else -1.0,
            "unit": "modeled_ms_per_step", "vs_baseline": 1.0},
        f"plan_handpicked_cost_{platform}{n_dev}": {
            "value": round(hand_cost.total_s * 1e3, 3),
            "unit": "modeled_ms_per_step", "vs_baseline": 1.0},
        f"plan_advantage_ratio_{platform}{n_dev}": {
            "value": round(ratio, 4), "unit": "x_vs_handpicked",
            "vs_baseline": 1.0},
        f"plan_search_ms_{platform}{n_dev}": {
            "value": round(search_ms, 1), "unit": "ms",
            "vs_baseline": 1.0},
    }


def tp_overlap_metric(platform: str, n_dev: int) -> dict:
    """Decomposed collective-matmul microbenchmark (docs/tp_overlap.md):
    time the sequence-parallel llama MLP pair — all-gather→matmul entry and
    matmul→reduce-scatter exit — with the ppermute-ring decomposition vs
    the monolithic collectives, at the CPU train shapes (hidden
    256, intermediate 704). RETURNS aux entries keyed by metric name.

    ``tp_overlap_engaged`` reports whether the auto knob would actually
    decompose at these shapes (the trace-time ``will_decompose``
    resolution); on a mesh without a tp axis ≥ 2 the speedup degrades to
    1.0. On CPU the ring's extra dispatches usually outweigh the memcpy
    "wire", so values below 1.0 there are honest, not a bug — overlap
    only pays where transfers have real latency to hide.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from neuronx_distributed_tpu.ops import collective_matmul as cm
    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.destroy_model_parallel()
    tp = 1
    while tp * 2 <= min(n_dev, 8) and n_dev % (tp * 2) == 0:
        tp *= 2
    ps.initialize_model_parallel(tensor_model_parallel_size=tp)
    mesh = ps.get_mesh()
    batch, seq, hidden, inter = 4, 512, 256, 704
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, seq // tp, hidden)
                    .astype(np.float32) * 0.1)
    wu = jnp.asarray(rng.randn(hidden, inter // tp)
                     .astype(np.float32) * 0.1)
    wd = jnp.asarray(rng.randn(inter // tp, hidden)
                     .astype(np.float32) * 0.1)
    engaged = {}

    def make(impl):
        def mlp(xv, wuv, wdv):
            if impl == "decomposed":
                # trace-time record of the auto-knob resolution at these
                # exact shapes (the layers ask the same question)
                engaged["entry"] = cm.will_decompose(
                    "auto", "tp", xv.shape, 1, needs_divisible=False)
            h = jax.nn.silu(cm.all_gather_matmul(xv, wuv, "tp", 1,
                                                 impl=impl))
            if impl == "decomposed":
                engaged["exit"] = cm.will_decompose(
                    "auto", "tp", h.shape, 1, needs_divisible=True)
            return cm.matmul_reduce_scatter(h, wdv, "tp", 1, impl=impl)

        return jax.jit(ps.shard_map(
            mlp, mesh,
            in_specs=(P(None, "tp", None), P(None, "tp"), P("tp", None)),
            out_specs=P(None, "tp", None)))

    def timed(f):
        jax.block_until_ready(f(x, wu, wd))  # compile + warm
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x, wu, wd))
            best = min(best, time.perf_counter() - t0)
        return best

    t_deco = timed(make("decomposed"))
    t_mono = timed(make("monolithic"))
    speedup = (t_mono / t_deco) if tp > 1 else 1.0
    is_engaged = tp > 1 and engaged.get("entry", False) \
        and engaged.get("exit", False)
    print(f"bench: tp-overlap mlp [{batch},{seq},{hidden}]x{inter} tp={tp}: "
          f"mono={t_mono * 1e3:.2f}ms deco={t_deco * 1e3:.2f}ms "
          f"engaged={is_engaged}", file=sys.stderr)
    return {
        f"tp_overlap_speedup_{platform}{n_dev}": {
            "value": round(speedup, 3), "unit": "x_vs_monolithic",
            "vs_baseline": 1.0},
        f"tp_overlap_engaged_{platform}{n_dev}": {
            "value": bool(is_engaged), "unit": "bool",
            "vs_baseline": 1.0},
    }


def tp_act_metric(platform: str, n_dev: int) -> dict:
    """Activation-collective compression (docs/comm_compression.md,
    activations section): the quantized-wire llama MLP pair vs the fp32
    rings, plus an e2e loss-delta drill — a short tiny-llama training run
    at int8 activation wires vs fp32 on the explicit shard_map path
    (tp bound, so the quantized collectives actually engage). RETURNS aux
    entries keyed by metric name.

    ``tp_act_wire_ratio`` is the hardware-independent number (bytes on the
    fp32 wire / bytes on the quantized wire at the codec's accounting);
    on CPU the quantize arithmetic usually outweighs the memcpy "wire",
    so ``tp_act_quant_speedup`` below 1.0 there is honest, not a bug.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from neuronx_distributed_tpu.ops import collective_matmul as cm
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.parallel.wire_codec import CompressionConfig

    wire = cm.wire_config("int8")
    ratio = 4.0 / CompressionConfig(dtype="int8").wire_bytes_per_element

    ps.destroy_model_parallel()
    tp = 1
    while tp * 2 <= min(n_dev, 8) and n_dev % (tp * 2) == 0:
        tp *= 2
    ps.initialize_model_parallel(tensor_model_parallel_size=tp)
    mesh = ps.get_mesh()
    batch, seq, hidden, inter = 4, 512, 256, 704
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, seq // tp, hidden)
                    .astype(np.float32) * 0.1)
    wu = jnp.asarray(rng.randn(hidden, inter // tp)
                     .astype(np.float32) * 0.1)
    wd = jnp.asarray(rng.randn(inter // tp, hidden)
                     .astype(np.float32) * 0.1)

    def make(wirev):
        def mlp(xv, wuv, wdv):
            h = jax.nn.silu(cm.all_gather_matmul(
                xv, wuv, "tp", 1, impl="decomposed", wire=wirev))
            return cm.matmul_reduce_scatter(h, wdv, "tp", 1,
                                            impl="decomposed", wire=wirev)

        return jax.jit(ps.shard_map(
            mlp, mesh,
            in_specs=(P(None, "tp", None), P(None, "tp"), P("tp", None)),
            out_specs=P(None, "tp", None)))

    def timed(f):
        jax.block_until_ready(f(x, wu, wd))  # compile + warm
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x, wu, wd))
            best = min(best, time.perf_counter() - t0)
        return best

    t_fp = timed(make(None))
    t_q = timed(make(wire))
    speedup = (t_fp / t_q) if tp > 1 else 1.0

    # e2e loss delta: the explicit shard_map gradient path binds tp, so
    # the int8 run really ships quantized activation collectives
    def drill(act_dtype, steps=10):
        import neuronx_distributed_tpu as nxd
        from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                          tiny_config)
        from neuronx_distributed_tpu.parallel import comm_compressed as cc
        from neuronx_distributed_tpu.trainer import (
            initialize_parallel_model, initialize_parallel_optimizer,
            make_train_step)

        ps.destroy_model_parallel()
        cfg = nxd.neuronx_distributed_config(
            tensor_parallel_size=min(2, n_dev))
        mcfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           activation_comm_dtype=act_dtype)
        model = LlamaForCausalLM(mcfg)
        ids = jax.random.randint(jax.random.key(0), (8, 33), 0,
                                 mcfg.vocab_size)
        b = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                               b["input_ids"])
        tx, state, sh = initialize_parallel_optimizer(pm, params,
                                                      learning_rate=1e-3)
        step = make_train_step(pm, tx, sh,
                               compression=cc.CompressionConfig(dtype="fp32"),
                               donate=False)
        loss = float("nan")
        for _ in range(steps):
            state, metrics = step(state, b)
            loss = float(metrics["loss"])
        return loss

    loss_fp = drill("fp32")
    loss_q = drill("int8")
    delta = abs(loss_q - loss_fp) / max(abs(loss_fp), 1e-9)
    ps.destroy_model_parallel()
    print(f"bench: tp-act mlp tp={tp}: fp32={t_fp * 1e3:.2f}ms "
          f"int8={t_q * 1e3:.2f}ms wire_ratio={ratio:.2f}x "
          f"loss fp32={loss_fp:.4f} int8={loss_q:.4f} "
          f"delta={delta:.4%}", file=sys.stderr)
    return {
        f"tp_act_wire_ratio_{platform}{n_dev}": {
            "value": round(ratio, 3), "unit": "x_fewer_bytes",
            "vs_baseline": 1.0},
        f"tp_act_quant_speedup_{platform}{n_dev}": {
            "value": round(speedup, 3), "unit": "x_vs_fp32_wire",
            "vs_baseline": 1.0},
        f"tp_act_loss_delta_{platform}{n_dev}": {
            "value": round(delta, 5), "unit": "rel_final_loss_vs_fp32",
            "vs_baseline": 0.0},
    }


def moe_metric(platform: str, n_dev: int) -> dict:
    """Dropless blockwise MoE drill (docs/moe.md): opt-in via --moe.

    Four measurements, RETURNED as aux entries keyed by metric name:

    * ``moe_blockwise_tokens_per_sec`` / ``moe_capacity_tokens_per_sec`` —
      fwd+bwd token throughput of the blockwise (dropless grouped-GLU)
      expert bank vs the capacity mask-einsum path at the same shapes;
    * ``moe_dropped_tokens`` — routed (token, k) assignments the blockwise
      run dropped: 0 by construction, asserted against the aux the layer
      itself reports (the capacity contrast at factor 1.0 drops for real);
    * ``moe_ep_wire_ratio`` — fp32 bytes / quantized bytes on the EP
      dispatch wire at the codec's accounting (hardware-independent);
    * ``moe_overlap_speedup`` — int8 ppermute-ring dispatch (per-chunk
      compute overlapping later hops) vs the int8 monolithic collectives
      on the largest power-of-two ep mesh this host supports. On CPU the
      ring's extra dispatches usually outweigh the overlap, so a value
      below 1.0 there is honest, not a bug;
    * ``moe_max_compile_count`` — executable count of a mixtral blockwise
      ServingEngine across submissions with shifting expert load (the
      one-executable invariant: must be 1).
    """
    import numpy as np
    from flax.core import meta
    from jax.sharding import PartitionSpec as P

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.modules.moe import ExpertMLPs
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.parallel.wire_codec import CompressionConfig

    ratio = 4.0 / CompressionConfig(dtype="int8").wire_bytes_per_element

    if platform == "cpu":
        t, h, inter, e, k, block = 512, 64, 128, 4, 2, 64
    else:
        t, h, inter, e, k, block = 2048, 256, 704, 8, 2, 128
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(t, h).astype(np.float32) * 0.1)
    gates = jnp.full((t, k), 1.0 / k, jnp.float32)
    idx = jnp.asarray(rng.randint(0, e, (t, k)))

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()

    def build(mode):
        m = ExpertMLPs(num_experts=e, hidden_size=h, intermediate_size=inter,
                       top_k=k, capacity_factor=1.0, dispatch_mode=mode,
                       block_size=block, dtype=jnp.float32,
                       param_dtype=jnp.float32)
        params = meta.unbox(m.init(jax.random.key(0), x, gates, idx))

        def loss(p, xv):
            y, aux = m.apply(p, xv, gates, idx)
            return jnp.sum(y * y), aux["dropped_fraction"]

        return params, jax.jit(jax.grad(loss, has_aux=True))

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))  # compile + warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            best = min(best, time.perf_counter() - t0)
        return best

    p_b, step_b = build("blockwise")
    p_c, step_c = build("capacity")
    _, dropped_frac = step_b(p_b, x)
    dropped_tokens = float(dropped_frac) * t * k
    _, dropped_cap = step_c(p_c, x)
    t_b = timed(step_b, p_b, x)
    t_c = timed(step_c, p_c, x)

    # --- int8 ring overlap vs int8 monolithic on the widest ep mesh ---
    ep = 1
    while ep * 2 <= min(n_dev, e) and e % (ep * 2) == 0:
        ep *= 2
    overlap_speedup = 1.0
    if ep > 1:
        ps.destroy_model_parallel()
        nxd.neuronx_distributed_config(expert_parallel_size=ep)
        em = ps.get_expert_mesh()
        from neuronx_distributed_tpu.modules import glu

        pspec = {"params": {
            **dict.fromkeys(glu.EXPERTS, P("ep", None, None)),
            "down": P("ep", None, None)}}

        def run_ep(overlap):
            m = ExpertMLPs(
                num_experts=e, hidden_size=h, intermediate_size=inter,
                top_k=k, dispatch_mode="blockwise", block_size=block,
                ep_wire_dtype="int8", ep_overlap=overlap,
                dtype=jnp.float32, param_dtype=jnp.float32)
            params = meta.unbox(m.init(jax.random.key(0), x, gates, idx))
            f = jax.jit(ps.shard_map(
                lambda p, xv, g, i: m.apply(p, xv, g, i)[0], em,
                in_specs=(pspec, P("ep", None), P("ep", None),
                          P("ep", None)),
                out_specs=P("ep", None)))
            return timed(f, params, x, gates, idx)

        t_mono = run_ep(False)
        t_ring = run_ep(True)
        overlap_speedup = t_mono / t_ring

    # --- serving: one executable across shifting expert load ---
    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          ServingEngine)
    from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                        tiny_moe_config)

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    mcfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           moe_dispatch="blockwise", moe_block_size=32)
    params = meta.unbox(MixtralForCausalLM(mcfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    eng = ServingEngine(mcfg, params, EngineConfig(
        block_size=4, num_blocks=32, max_slots=2, max_blocks_per_seq=8,
        token_budget=8, kv_dtype=jnp.float32))
    erng = np.random.RandomState(1)
    # prompts drawn from disjoint vocab bands shift which experts the
    # router lights up between submissions
    for i, (lo, hi) in enumerate(((0, 64), (128, 192), (192, 256))):
        eng.submit(erng.randint(lo, hi, (5 + i,)).tolist(), 4, uid=str(i))
        eng.step()
    eng.run()
    compile_count = eng.compile_count()
    ps.destroy_model_parallel()

    print(f"bench: moe blockwise={t * k / t_b:,.0f} tok/s "
          f"capacity={t * k / t_c:,.0f} tok/s dropped(blockwise)="
          f"{dropped_tokens:.0f} dropped(capacity)="
          f"{float(dropped_cap) * t * k:.0f} wire_ratio={ratio:.2f}x "
          f"ep={ep} overlap_speedup={overlap_speedup:.3f} "
          f"compile_count={compile_count}", file=sys.stderr)
    return {
        f"moe_blockwise_tokens_per_sec_{platform}{n_dev}": {
            "value": round(t * k / t_b, 1), "unit": "routed_tokens/sec",
            "vs_baseline": 1.0},
        f"moe_capacity_tokens_per_sec_{platform}{n_dev}": {
            "value": round(t * k / t_c, 1), "unit": "routed_tokens/sec",
            "vs_baseline": 1.0},
        f"moe_dropped_tokens_{platform}{n_dev}": {
            "value": int(dropped_tokens), "unit": "tokens",
            "vs_baseline": 0.0},
        f"moe_ep_wire_ratio_{platform}{n_dev}": {
            "value": round(ratio, 3), "unit": "x_fewer_bytes",
            "vs_baseline": 1.0},
        f"moe_overlap_speedup_{platform}{n_dev}": {
            "value": round(overlap_speedup, 3), "unit": "x_vs_monolithic",
            "vs_baseline": 1.0},
        f"moe_max_compile_count_{platform}{n_dev}": {
            "value": int(compile_count), "unit": "executables",
            "vs_baseline": 1.0},
    }


def resilience_metric(platform: str, chaos_spec=None) -> dict:
    """Preemption drill: train a tiny llama with periodic checkpointing,
    deliver a real SIGTERM mid-run, catch the resumable exit, then resume
    and run one more step. Reports ``recovery_time_s`` (SIGTERM delivery to
    first post-resume step) and ``steps_lost`` (optimizer steps the
    preemption cost — 0 when the emergency save landed). ``chaos_spec``
    (--chaos) additionally injects storage faults per the FaultPlan DSL for
    the whole drill; retries must heal transient ones."""
    import shutil
    import signal as _signal
    import tempfile

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)
    from neuronx_distributed_tpu.resilience import (FaultPlan,
                                                    PreemptionGuard,
                                                    TrainingPreempted)
    from neuronx_distributed_tpu.resilience.chaos import wrapper_for_plan
    from neuronx_distributed_tpu.trainer import (
        checkpoint_storage as cs,
        initialize_parallel_model,
        initialize_parallel_optimizer,
        make_train_step,
    )
    from neuronx_distributed_tpu.trainer.loop import (Callback,
                                                      CheckpointCallback,
                                                      Trainer)

    plan = None
    if chaos_spec:
        plan = FaultPlan.parse(chaos_spec)
        cs.install_storage_wrapper(
            wrapper_for_plan(plan, base_delay=0.01, max_delay=0.05))
    ckpt_dir = tempfile.mkdtemp(prefix="nxd_bench_resilience_")
    guard = PreemptionGuard(checkpoint_path=ckpt_dir, grace_s=120.0)
    try:
        cfg = nxd.neuronx_distributed_config(tensor_parallel_size=1)
        mcfg = tiny_config(num_layers=2, dtype=jnp.float32,
                           param_dtype=jnp.float32)
        model = LlamaForCausalLM(mcfg)
        # batch divisible by the dp axis (= all devices at tp=1)
        ids = jax.random.randint(jax.random.key(0),
                                 (len(jax.devices()), 17), 0,
                                 mcfg.vocab_size)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                               batch["input_ids"])
        tx, state, sh = initialize_parallel_optimizer(pm, params, 1e-3)
        step = make_train_step(pm, tx, sh, donate=False)

        kill_at = 3

        class Kill(Callback):
            def on_step_end(self, trainer, metrics):
                if trainer.host_step == kill_at:
                    os.kill(os.getpid(), _signal.SIGTERM)

        trainer = Trainer(step, state, callbacks=[
            CheckpointCallback(ckpt_dir, every=100), Kill(),
        ], preemption_guard=guard)
        t_kill = None
        try:
            trainer.fit(iter([batch] * 10), max_steps=10)
        except TrainingPreempted:
            t_kill = time.perf_counter()
        if t_kill is None:
            raise RuntimeError("SIGTERM drill never raised "
                               "TrainingPreempted")
        trainer2 = Trainer(step, state, resume_path=ckpt_dir)
        steps_lost = kill_at - int(trainer2.state.step)
        trainer2.fit(iter([batch] * 1), max_steps=int(trainer2.state.step)
                     + 1)
        recovery_s = time.perf_counter() - t_kill
    finally:
        guard.uninstall()
        if chaos_spec:
            cs.clear_storage_wrapper()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    aux = {
        f"resilience_recovery_time_s_{platform}": {
            "value": round(recovery_s, 3), "unit": "s", "vs_baseline": 1.0},
        f"resilience_steps_lost_{platform}": {
            "value": int(steps_lost), "unit": "steps", "vs_baseline": 1.0},
    }
    if plan is not None:
        aux[f"resilience_faults_injected_{platform}"] = {
            "value": plan.fire_count(), "unit": "faults",
            "vs_baseline": 1.0}
    print(f"bench: resilience drill recovery={recovery_s:.3f}s "
          f"steps_lost={steps_lost}"
          + (f" faults_injected={plan.fire_count()}" if plan else ""),
          file=sys.stderr)
    return aux


if __name__ == "__main__":
    import argparse

    _p = argparse.ArgumentParser(description=__doc__)
    _p.add_argument(
        "--chaos", nargs="?", metavar="SPEC",
        const="seed=0; save_text|* : transient, times=2; "
              "load_text|* : transient, times=1",
        default=None,
        help="inject storage faults during the resilience drill; optional "
             "SPEC is a FaultPlan DSL string (docs/resilience.md), default "
             "a deterministic transient-fault mix (first saves/loads fail "
             "once, then heal through the retry path)")
    _p.add_argument(
        "--serving", action="store_true",
        help="also run the continuous-batching serving drill (paged-cache "
             "engine vs static batched generate under a ragged Poisson "
             "arrival workload; docs/serving.md)")
    _p.add_argument(
        "--speculative", action="store_true",
        help="also run the speculative-decoding drill (ragged Poisson "
             "arrivals served spec-on vs spec-off on one engine config; "
             "reports decode tokens/s speedup, mean accept length, and "
             "greedy match rate; docs/serving.md)")
    _p.add_argument(
        "--quantized", action="store_true",
        help="also run the weight-quantized serving drill (int8/mxfp4 "
             "tiers vs fp32 at an equal HBM budget — freed weight bytes "
             "buy extra pool blocks; reports tokens/s, concurrent-session "
             "capacity, per-tier greedy match-rate and max logit "
             "divergence, compile_count()==1; docs/quantization.md)")
    _p.add_argument(
        "--long-context", action="store_true",
        help="also run the million-token-tier drill (a prompt that "
             "overflows one mesh's paged pool refused at cp=1, served by "
             "cp=4/cp=8 ring-prefill engines; TTFT scaling vs cp, int8 "
             "hop wire ratio, greedy parity, compile_count()==1; "
             "docs/serving.md)")
    _p.add_argument(
        "--router", action="store_true",
        help="also run the multi-replica failover drill (chaos plan kills "
             "a replica mid-decode; reports availability, failovers, and "
             "chaos TTFT p99; docs/serving.md)")
    _p.add_argument(
        "--elastic", action="store_true",
        help="also run the elastic-fleet drill (chaos preempt -> live KV "
             "session migration, scale_burst -> AOT-warm scale-up, "
             "graceful scale-down, revival through the executable cache; "
             "docs/serving.md)")
    _p.add_argument(
        "--disagg-fabric", action="store_true",
        help="also run the cross-host fabric drill (prefill->decode KV "
             "handoff streamed int8 over a simulated DCN link, clean and "
             "under link_partition chaos; reports handoff_wire_ratio, "
             "handoff_retries, ttft_p99_ms_handoff; docs/serving.md)")
    _p.add_argument(
        "--sdc", action="store_true",
        help="also run the silent-data-corruption drill (chaos bitflips "
             "on train params and served tokens; fingerprint detection "
             "rate, watchdog verified rewind, shadow-quarantine serving "
             "path, fingerprint overhead; docs/resilience.md)")
    _p.add_argument(
        "--prefix-heavy", action="store_true",
        help="also run the prefix-heavy serving drill (64 requests sharing "
             "a system prompt; prefix trie + copy-on-write vs no-sharing "
             "vs disaggregated prefill/decode; docs/serving.md)")
    _p.add_argument(
        "--overlap", action="store_true",
        help="also run the tensor-parallel overlap microbenchmark "
             "(decomposed collective-matmul vs monolithic gather+matmul at "
             "llama MLP shapes; docs/tp_overlap.md)")
    _p.add_argument(
        "--moe", action="store_true",
        help="also run the dropless blockwise MoE drill (blockwise vs "
             "capacity fwd+bwd throughput, dropped-token count, EP "
             "dispatch wire ratio, int8 ring-overlap speedup, mixtral "
             "serving compile count under shifting expert load; "
             "docs/moe.md)")
    _p.add_argument(
        "--plan", action="store_true",
        help="also run the placement-planner drill (analytic search at "
             "this device count vs the hand-picked bench layout; reports "
             "plan_best_cost / plan_handpicked_cost / "
             "plan_advantage_ratio / plan_search_ms; docs/planner.md)")
    _p.add_argument(
        "--obs", action="store_true",
        help="also run the observability drill (obs on-vs-off overhead on "
             "the serving path, compile events from the tracker, wire-byte "
             "counters vs the codec's predicted int8 ratio; "
             "docs/observability.md)")
    _p.add_argument(
        "--regress", action="store_true",
        help="audit BENCH_*.json history for metric regressions and exit "
             "(touches no backend; prints one JSON line with "
             "regressions=[...]; see --regress-tolerance/--regress-dir)")
    _p.add_argument("--regress-tolerance", type=float, default=0.10,
                    metavar="FRAC")
    _p.add_argument("--regress-dir", default=_ROOT,
                    help="directory holding BENCH_*.json history")
    _p.add_argument(
        "--lint", action="store_true",
        help="also self-measure the static-analysis toolchain (nxdlint "
             "wall time + finding count over the repo, jaxpr entry-point "
             "audit wall time; docs/analysis.md)")
    _args = _p.parse_args()
    if _args.regress:
        sys.exit(_regress_main(_args.regress_dir, _args.regress_tolerance))
    sys.exit(main(chaos_spec=_args.chaos, serving=_args.serving,
         overlap=_args.overlap, router=_args.router,
         prefix_heavy=_args.prefix_heavy, plan_mode=_args.plan,
         obs_mode=_args.obs, elastic=_args.elastic, sdc=_args.sdc,
         moe=_args.moe, lint_mode=_args.lint,
         disagg_fabric=_args.disagg_fabric,
         speculative=_args.speculative, long_context=_args.long_context,
         quantized=_args.quantized))
