"""CLI: rank parallelism placements and emit the winning config.

::

    python -m neuronx_distributed_tpu.plan --model llama2-7b --devices 32
    python -m neuronx_distributed_tpu.plan --model bench-cpu --devices 8 \
        --refine --yaml
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional

from . import (ModelSpec, ServingSpec, SpeculationSpec, TrafficSpec,
               calibrate, default_hardware, handpicked_plan, refine,
               render_kwargs, search, serving_search, step_cost)
from .cost import TPOT_P99_OVER_MEAN, TTFT_P99_OVER_MEAN
from .emit import plan_to_config, plan_to_yaml_dict


def _model_spec(name: str, *, seq: Optional[int], batch: int) -> ModelSpec:
    from ..models import llama

    key = name.lower().replace("_", "-")
    presets = {
        "llama2-7b": llama.LLAMA2_7B,
        "llama2-70b": llama.LLAMA2_70B,
        "llama3-8b": llama.LLAMA3_8B,
        "tiny": llama.tiny_config(),
        # a 4-layer, hidden-256 model for CPU hosts (the planner's tests
        # and docs/planner.md use it; ROADMAP D9)
        "bench-cpu": llama.LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=704,
            num_layers=4, num_heads=8, num_kv_heads=8, max_seq_len=512),
    }
    if key not in presets:
        raise SystemExit(
            f"unknown --model {name!r}; choose from {sorted(presets)}")
    return ModelSpec.from_model_config(presets[key], seq=seq,
                                       global_batch=batch, name=key)


def _fmt_row(rank, plan, cost) -> str:
    mem_gib = cost.memory["total"] / 2**30
    return (f"{rank:>3}  {cost.total_s * 1e3:>10.3f}  "
            f"{cost.compute_s * 1e3:>8.3f}  {cost.bubble_s * 1e3:>7.3f}  "
            f"{cost.tp_comm_s * 1e3:>8.3f}  {cost.pp_comm_s * 1e3:>8.3f}  "
            f"{cost.grad_comm_s * 1e3:>9.3f}  {mem_gib:>7.2f}  "
            f"{plan.describe()}")


_HEADER = (f"{'#':>3}  {'total ms':>10}  {'comp ms':>8}  {'bub ms':>7}  "
           f"{'tp ms':>8}  {'pp ms':>8}  {'grad ms':>9}  {'GiB':>7}  plan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m neuronx_distributed_tpu.plan",
        description="Rank parallelism placements over the hierarchical "
                    "mesh and emit the best one as a framework config "
                    "(docs/planner.md)")
    ap.add_argument("--model", default="bench-cpu",
                    help="model preset (llama2-7b, llama2-70b, llama3-8b, "
                         "tiny, bench-cpu)")
    ap.add_argument("--devices", type=int, required=True,
                    help="total device count to plan for")
    ap.add_argument("--dcn", type=int, default=1, metavar="N",
                    help="cross-slice (DCN) data-parallel degree of the "
                         "fleet; 1 = single slice")
    ap.add_argument("--batch", type=int, default=8,
                    help="global batch (sequences per step)")
    ap.add_argument("--seq", type=int, default=None,
                    help="sequence length (default: model's max_seq_len)")
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                    help="hardware constants to model")
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="override per-device memory budget, GiB")
    ap.add_argument("--serving", action="store_true",
                    help="plan a serving deployment: single-stage layouts "
                         "only, paged-KV pool charged to memory, and an "
                         "EngineConfig/router search for the stated "
                         "traffic mix and SLO")
    ap.add_argument("--serving-rate", type=float, default=8.0,
                    metavar="RPS", help="offered request rate (Poisson)")
    ap.add_argument("--serving-prompt", type=float, default=64.0,
                    metavar="TOK", help="mean prompt tokens per request")
    ap.add_argument("--serving-new", type=float, default=16.0,
                    metavar="TOK", help="mean generated tokens per request")
    ap.add_argument("--serving-shared", type=float, default=0.0,
                    metavar="TOK", help="shared prompt-prefix tokens "
                    "(enables prefix sharing in the emitted config)")
    ap.add_argument("--serving-block", type=int, default=8,
                    help="paged-KV block size for the serving search")
    ap.add_argument("--serving-spec-k", type=int, default=None,
                    metavar="K", help="model speculative decoding with "
                    "draft chains of depth K (adds the accept-rate-"
                    "parameterized speculation term to the search)")
    ap.add_argument("--serving-spec-branches", type=int, default=1,
                    metavar="B", help="speculation tree branches "
                    "(default 1)")
    ap.add_argument("--serving-spec-accept", type=float, default=0.6,
                    metavar="RATE", help="expected draft accept rate in "
                    "[0,1]; calibrate from the engine's measured "
                    "spec_accept_mean / K (default 0.6)")
    ap.add_argument("--serving-spec-draft-cost", type=float, default=0.15,
                    metavar="RATIO", help="draft-model step wall relative "
                    "to the target step (default 0.15)")
    ap.add_argument("--disaggregated", action="store_true",
                    help="search disaggregated prefill/decode configs")
    ap.add_argument("--cross-host", action="store_true",
                    help="rank colocated vs two-tier fabric configs "
                         "(disagg candidates pay the DCN KV-handoff "
                         "term)")
    ap.add_argument("--weight-quant", default=None, metavar="FMT[,FMT...]",
                    help="comma-separated weight-quant tiers to rank next "
                         "to float (int8, fp8, mxfp4, mxfp8); float always "
                         "competes in the same ranking")
    ap.add_argument("--quality-bar", type=float, default=None,
                    metavar="RATE", help="minimum recorded greedy "
                    "match-rate a quantized tier must clear; tiers with "
                    "no recorded quality are refused (fail closed)")
    ap.add_argument("--quality-file", default=None, metavar="JSON",
                    help="per-tier quality records (a JSON object "
                         "mapping tier name to a match-rate or a "
                         "{'greedy_match': ...} record)")
    ap.add_argument("--slo-ttft-p99-ms", type=float, default=None,
                    help="TTFT p99 target (ms) the serving config must "
                         "meet")
    ap.add_argument("--slo-tpot-p99-ms", type=float, default=None,
                    help="TPOT p99 target (ms) the serving config must "
                         "meet")
    ap.add_argument("--calibrate-bench", metavar="DIR", default=None,
                    help="refit hardware constants from BENCH_*.json "
                         "history under DIR before planning "
                         "(plan/calibrate.py)")
    ap.add_argument("--refine", action="store_true",
                    help="re-rank the analytic top-k with measured jitted "
                         "proxies (uses whatever backend is available)")
    ap.add_argument("--top-k", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--yaml", action="store_true",
                    help="print the winning plan as converter-compatible "
                         "YAML instead of a config call site")
    ap.add_argument("--show-pruned", type=int, default=0, metavar="N",
                    help="also list the first N pruned candidates with "
                         "their machine-readable reasons")
    args = ap.parse_args(argv)

    spec = _model_spec(args.model, seq=args.seq, batch=args.batch)
    hw = default_hardware(args.platform)
    if args.hbm_gb is not None:
        import dataclasses

        hw = dataclasses.replace(hw, hbm_bytes=args.hbm_gb * 2**30)
    if args.calibrate_bench is not None:
        cal = calibrate(hw, bench=args.calibrate_bench, model=spec)
        for w in cal.warnings:
            print(f"calibrate: {w}")
        if cal.hardware is not hw:
            print(f"calibrate: {hw.name} -> {cal.hardware.name} "
                  f"(mfu={cal.hardware.mfu:.3f})")
        hw = cal.hardware
    serving = ServingSpec() if args.serving else None

    result = search(spec, hw, args.devices, dcn_dp=args.dcn,
                    serving=serving, top_k=args.top_k)
    print(f"plan: {spec.name} on {args.devices} device(s) "
          f"[{args.platform}], dcn={args.dcn}, batch={args.batch}, "
          f"seq={spec.seq}: {result.n_enumerated} candidates, "
          f"{len(result.ranked)} ranked, "
          f"{len(result.rejected_with('indivisible'))} indivisible, "
          f"{len(result.rejected_with('oom'))} oom, "
          f"{len(result.rejected_with('dominated'))} dominated")
    if not result.ranked:
        print("plan: no feasible layout — every candidate was pruned "
              "(raise --hbm-gb or change --devices)")
        for p in result.rejected[:10]:
            print(f"  pruned[{p.code}] {p.plan.describe()}: {p.detail}")
        return 1

    print(_HEADER)
    for i, r in enumerate(result.ranked, 1):
        print(_fmt_row(i, r.plan, r.cost))

    best = result.best.plan
    if args.refine:
        refined = refine(result.ranked, spec, hw, seed=args.seed)
        print("refined (measured proxy, min of 3):")
        for i, r in enumerate(refined, 1):
            print(f"{i:>3}  measured {r.measured_s * 1e3:10.3f} ms  "
                  f"modeled {r.modeled_s * 1e3:10.3f} ms  "
                  f"{r.plan.describe()}")
        best = refined[0].plan

    hand = handpicked_plan(args.devices, platform=args.platform,
                           dcn_dp=args.dcn)
    hand_cost = step_cost(hand, spec, hw, serving)
    best_cost = step_cost(best, spec, hw, serving)
    ratio = hand_cost.total_s / best_cost.total_s if best_cost.total_s else 1.0
    print(f"handpicked baseline ({hand.describe()}): "
          f"{hand_cost.total_s * 1e3:.3f} ms/step; best plan "
          f"{best_cost.total_s * 1e3:.3f} ms/step "
          f"({ratio:.2f}x advantage)")

    if args.show_pruned:
        for p in result.rejected[:args.show_pruned]:
            by = f" (by {p.by.describe()})" if p.by else ""
            print(f"  pruned[{p.code}] {p.plan.describe()}: {p.detail}{by}")

    cfg = plan_to_config(best, init_mesh=False)   # validates
    if args.yaml:
        import json

        print("emitted YAML config:")
        print(json.dumps(plan_to_yaml_dict(best), indent=2))
    else:
        print("emitted config:")
        print(render_kwargs(best))

    if args.serving:
        import json as _json
        import math as _math

        traffic = TrafficSpec(request_rate=args.serving_rate,
                              prompt_tokens=args.serving_prompt,
                              new_tokens=args.serving_new,
                              shared_prefix_tokens=args.serving_shared)
        ttft_tgt = (args.slo_ttft_p99_ms / 1e3
                    if args.slo_ttft_p99_ms is not None else _math.inf)
        tpot_tgt = (args.slo_tpot_p99_ms / 1e3
                    if args.slo_tpot_p99_ms is not None else _math.inf)
        spec_term = None
        if args.serving_spec_k is not None:
            spec_term = SpeculationSpec(
                length=args.serving_spec_k,
                branches=args.serving_spec_branches,
                accept_rate=args.serving_spec_accept,
                draft_cost_ratio=args.serving_spec_draft_cost)
        # context-parallel ladder: every cp degree the device count can
        # host next to the chosen tp — long-context mixes whose pool
        # cannot fit one device surface a cp>1 engine, short mixes
        # keep picking cp=1
        free = max(1, args.devices // best.tp)
        cps = tuple(c for c in range(1, free + 1) if free % c == 0)
        weight_quants = (None,)
        if args.weight_quant:
            weight_quants += tuple(
                w.strip() for w in args.weight_quant.split(",") if w.strip())
        quality = None
        if args.quality_file is not None:
            with open(args.quality_file) as f:
                quality = _json.load(f)
        plans = serving_search(spec, hw, traffic,
                               slo_ttft_p99_s=ttft_tgt,
                               slo_tpot_p99_s=tpot_tgt,
                               tp=best.tp, block_size=args.serving_block,
                               disaggregated=args.disaggregated,
                               cross_host=args.cross_host,
                               speculation=spec_term,
                               cps=cps,
                               weight_quants=weight_quants,
                               quality=quality,
                               quality_bar=args.quality_bar,
                               top_k=args.top_k)
        print(f"serving plan: rate={traffic.request_rate:g} req/s, "
              f"prompt={traffic.prompt_tokens:g}, "
              f"new={traffic.new_tokens:g}, "
              f"shared={traffic.shared_prefix_tokens:g}"
              + (f", ttft_p99<={ttft_tgt * 1e3:g}ms"
                 if _math.isfinite(ttft_tgt) else "")
              + (f", tpot_p99<={tpot_tgt * 1e3:g}ms"
                 if _math.isfinite(tpot_tgt) else "")
              + (f", spec k={spec_term.length} b={spec_term.branches} "
                 f"accept={spec_term.accept_rate:g} "
                 f"(mean accept {spec_term.accept_mean:g}, "
                 f"{spec_term.row_efficiency:.2f} tok/row)"
                 if spec_term is not None else ""))
        if not plans:
            print("serving plan: no feasible engine config "
                  "(pool never fits — raise --hbm-gb)")
            return 1
        print(f"{'#':>3}  {'ttft ms':>9}  {'tpot ms':>9}  {'tok/s':>8}  "
              f"{'util':>5}  {'slo':>4}  config")
        for i, p in enumerate(plans, 1):
            c = p.cost
            print(f"{i:>3}  {c.ttft_s * 1e3:>9.2f}  {c.tpot_s * 1e3:>9.2f}"
                  f"  {c.tokens_per_s:>8.1f}  {c.utilization:>5.2f}  "
                  f"{'ok' if p.meets_slo else 'MISS':>4}  {p.describe()}")
        chosen = plans[0]
        if _math.isfinite(ttft_tgt) or _math.isfinite(tpot_tgt):
            if not chosen.meets_slo:
                print("serving plan: stated SLO is unattainable at this "
                      "rate on one replica — emitting the closest config; "
                      "scale replicas or relax the target")
        print("emitted serving config (modeled p99: "
              f"ttft={chosen.cost.ttft_s * TTFT_P99_OVER_MEAN * 1e3:.2f}ms"
              f", tpot={chosen.cost.tpot_s * TPOT_P99_OVER_MEAN * 1e3:.2f}"
              "ms):")
        kw = ", ".join(f"{k}={v!r}" for k, v in chosen.engine.items())
        print(f"EngineConfig({kw})")
        cp_deg = chosen.engine.get("cp", 1)
        if cp_deg > 1:
            print(f"serving mesh: initialize_model_parallel("
                  f"context_parallel_size={cp_deg}, "
                  f"tensor_parallel_size={best.tp})")
        if chosen.router:
            print(f"router: {_json.dumps(chosen.router)}")

    # prove the emitted config really initializes when the runtime matches
    import jax

    if args.devices == len(jax.devices()):
        plan_to_config(best, init_mesh=True)
        from ..parallel import mesh as _mesh

        print(f"mesh initialized: {dict(_mesh.get_mesh().shape)}")
    else:
        del cfg
    return 0


if __name__ == "__main__":
    sys.exit(main())
