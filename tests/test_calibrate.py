"""Obs-calibrated planner constants (``plan/calibrate.py``) and the
request-level serving cost model (``plan/cost.py``).

Pins the robustness contract: degenerate measurement — a single point,
zero-byte collectives, clock-skewed durations, a non-physical slope —
degrades to the hand-set defaults with a recorded warning, and the
fitted α/β are never negative. Calibration can refuse; it must never
make the planner worse than uncalibrated.
"""

import json
import math

import pytest

from neuronx_distributed_tpu.plan import (CalibrationResult, LinkFit,
                                          ModelSpec, TrafficSpec,
                                          calibrate, default_hardware,
                                          fit_alpha_beta, fit_mfu,
                                          load_bench_history,
                                          mfu_from_bench, serving_cost,
                                          serving_pool_blocks,
                                          serving_search, serving_token_s)
from neuronx_distributed_tpu.plan.cost import (HardwareSpec, LinkSpec,
                                               step_flops)

TINY = ModelSpec(name="tiny", vocab=1024, hidden=256, intermediate=704,
                 layers=4, heads=8, kv_heads=8, seq=512, global_batch=8)
HW = default_hardware("cpu")


def _line(alpha, beta, sizes, count=4):
    return [(b, alpha + beta * b, count) for b in sizes]


# ---------------------------------------------------------------------------
# α-β link fitting
# ---------------------------------------------------------------------------

def test_fit_alpha_beta_recovers_exact_line():
    sizes = [1 << k for k in range(10, 20)]
    fit = fit_alpha_beta(_line(2e-6, 1.25e-10, sizes), tier="ici")
    assert fit.source == "samples"
    assert fit.alpha == pytest.approx(2e-6, rel=1e-6)
    assert fit.beta == pytest.approx(1.25e-10, rel=1e-6)
    assert fit.residual < 1e-9
    # and the LinkSpec mapping inverts the slope
    assert fit.link.bandwidth == pytest.approx(8e9, rel=1e-6)
    assert fit.link.latency == pytest.approx(2e-6, rel=1e-6)


def test_fit_single_point_keeps_defaults_with_warning():
    warn = []
    default = LinkSpec(bandwidth=4e10, latency=3e-6)
    fit = fit_alpha_beta([(4096, 1e-5, 8)], tier="ici", default=default,
                         warn=warn)
    assert fit.source == "default"
    assert fit.alpha == 3e-6 and fit.beta == pytest.approx(1 / 4e10)
    assert any("distinct payload size" in w for w in warn)


def test_fit_zero_byte_only_keeps_defaults():
    warn = []
    # all-zero payloads: one distinct size, nothing to regress on
    fit = fit_alpha_beta([(0, 1e-5, 4), (0, 1.1e-5, 4)], tier="dcn",
                         warn=warn)
    assert fit.source == "default"
    assert fit.alpha >= 0 and fit.beta >= 0
    assert warn


def test_fit_survives_clock_skew_samples():
    """NTP-step artifacts (negative / zero / NaN durations) are dropped
    with a warning; the fit proceeds from the surviving samples."""
    sizes = [1 << k for k in range(12, 18)]
    pairs = _line(5e-5, 1e-9, sizes) + [
        (8192, -3.0, 2), (8192, 0.0, 2), (8192, math.nan, 2),
        (math.inf, 1e-3, 2)]
    warn = []
    fit = fit_alpha_beta(pairs, tier="dcn", warn=warn)
    assert fit.source == "samples"
    assert fit.alpha == pytest.approx(5e-5, rel=1e-6)
    assert fit.beta == pytest.approx(1e-9, rel=1e-6)
    assert any("unusable" in w for w in warn)


def test_fit_all_skewed_keeps_defaults():
    warn = []
    fit = fit_alpha_beta([(4096, -1.0, 1), (8192, float("nan"), 1)],
                         tier="ici", warn=warn)
    assert fit.source == "default"
    assert fit.alpha >= 0 and fit.beta >= 0


def test_fit_negative_slope_keeps_defaults():
    """Bigger payloads measured *faster* is contention, not a link law."""
    warn = []
    fit = fit_alpha_beta([(1024, 1e-3, 4), (1 << 20, 1e-5, 4)],
                         tier="ici", warn=warn)
    assert fit.source == "default"
    assert any("non-positive fitted slope" in w for w in warn)


def test_fit_negative_intercept_clamped_to_origin():
    """A slightly negative fitted intercept clamps to α=0 with a
    through-origin β refit — never a negative latency."""
    # two points whose exact line has a negative intercept
    fit = fit_alpha_beta([(1000, 0.5e-6, 1), (2000, 1.6e-6, 1)],
                         tier="ici")
    assert fit.source == "samples"
    assert fit.alpha == 0.0
    assert fit.beta > 0


def test_fit_huge_residual_keeps_defaults():
    warn = []
    pairs = [(1024, 1e-6, 1), (2048, 9e-4, 1), (4096, 2e-6, 1),
             (8192, 1.1e-3, 1), (16384, 3e-6, 1), (32768, 1.3e-3, 1)]
    fit = fit_alpha_beta(pairs, tier="ici", warn=warn)
    assert fit.source == "default"
    assert any("residual" in w for w in warn)


def test_fit_trims_single_outlier():
    sizes = [1 << k for k in range(10, 16)]
    # one sample measured ~3x the line (a GC pause), low count weight
    pairs = _line(2e-6, 1.25e-10, sizes) + [(1 << 13, 9e-6, 1)]
    fit = fit_alpha_beta(pairs, tier="ici")
    assert fit.source == "samples"
    assert fit.alpha == pytest.approx(2e-6, rel=1e-3)
    assert fit.beta == pytest.approx(1.25e-10, rel=1e-3)


# ---------------------------------------------------------------------------
# mfu + bench history
# ---------------------------------------------------------------------------

def test_fit_mfu_median_and_bounds():
    hw = HardwareSpec()  # tpu defaults
    fps = 1e12
    # median of [0.1, 0.2, 50.0] is 0.2 -> compile outlier ignored
    eff = fit_mfu([50.0, 0.1, 0.2], fps, hw, devices=1)
    assert eff == pytest.approx(fps / (0.2 * hw.flops))
    warn = []
    # implausibly fast steps imply mfu > 1 -> refused
    assert fit_mfu([1e-9], fps, hw, warn=warn) is None
    assert any("contradicts" in w for w in warn)
    warn = []
    assert fit_mfu([], fps, hw, warn=warn) is None
    assert any("no usable" in w for w in warn)


def test_load_bench_history_skips_malformed(tmp_path):
    good = {"n": 1, "cmd": "x", "rc": 0, "tail": "",
            "parsed": {"metric": "llama_tokens_per_sec_per_chip_cpu8",
                       "value": 42.5, "unit": "tok/s/chip"}}
    (tmp_path / "BENCH_001.json").write_text(json.dumps(good))
    (tmp_path / "BENCH_002.json").write_text("{not json")
    (tmp_path / "BENCH_003.json").write_text(json.dumps({"parsed": {}}))
    recs = load_bench_history(str(tmp_path))
    assert len(recs) == 1
    assert recs[0]["metric"] == "llama_tokens_per_sec_per_chip_cpu8"
    assert recs[0]["value"] == 42.5
    assert load_bench_history(str(tmp_path / "nope")) == []


def test_mfu_from_bench_prefers_matching_hardware():
    fpt = step_flops(TINY, remat=True) / TINY.tokens_per_step
    target = 0.3 * HW.flops / fpt  # throughput implying mfu = 0.3
    recs = [
        {"metric": "llama_tokens_per_sec_per_chip_cpu8", "value": target},
        {"metric": "llama_tokens_per_sec_per_chip_tpu8",
         "value": target * 100}]
    eff = mfu_from_bench(recs, TINY, HW)
    assert eff == pytest.approx(0.3, rel=1e-6)
    warn = []
    assert mfu_from_bench([], TINY, HW, warn=warn) is None
    assert warn


# ---------------------------------------------------------------------------
# calibrate(): composition + registry source
# ---------------------------------------------------------------------------

def test_calibrate_composes_all_sources():
    sizes = [1 << k for k in range(10, 18)]
    res = calibrate(
        HW,
        samples={"ici": _line(2e-6, 1.25e-10, sizes),
                 "dcn": _line(5e-5, 1e-9, sizes)},
        step_seconds=[0.2, 0.21, 0.19],
        flops_per_step=0.05 * 0.2 * HW.flops,  # implies mfu = 0.05
        serve_step_seconds=[0.004, 0.002, 0.003])
    assert isinstance(res, CalibrationResult)
    hw = res.hardware
    assert hw.name == HW.name + "+cal"
    assert hw.ici.latency == pytest.approx(2e-6, rel=1e-5)
    assert hw.ici.bandwidth == pytest.approx(8e9, rel=1e-5)
    assert hw.dcn.latency == pytest.approx(5e-5, rel=1e-5)
    assert hw.mfu == pytest.approx(0.05, rel=1e-6)
    assert hw.serve_overhead_s == 0.002  # the emptiest observed step
    assert res.links["ici"].source == "samples"
    # round-trips through to_dict for the CLI evidence trail
    d = res.to_dict()
    assert d["links"]["dcn"]["alpha"] == pytest.approx(5e-5, rel=1e-5)


def test_calibrate_degenerate_never_worse_than_base():
    """Every degenerate source refuses: the returned spec is the base,
    un-renamed, and all α/β stay the hand-set (non-negative) values."""
    res = calibrate(HW, samples={"ici": [(4096, 1e-5, 1)],
                                 "dcn": [(0, -1.0, 1)]},
                    step_seconds=[1e-12], flops_per_step=1e18)
    assert res.hardware == HW  # nothing replaced, not even the name
    assert res.warnings
    for fit in res.links.values():
        assert fit.source == "default"
        assert fit.alpha >= 0 and fit.beta >= 0


def test_calibrate_from_live_registry():
    """The registry path: timed collectives recorded through obs
    accounting feed the same fit."""
    from neuronx_distributed_tpu.obs.accounting import \
        record_collective_time
    from neuronx_distributed_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.enable()
    for b in (1 << 12, 1 << 14, 1 << 16, 1 << 18):
        for _ in range(3):
            record_collective_time("ici", b, 2e-6 + 1.25e-10 * b,
                                   registry=reg)
    res = calibrate(HW, registry=reg)
    assert res.links["ici"].source == "registry"
    assert res.hardware.ici.latency == pytest.approx(2e-6, rel=1e-3)
    assert res.hardware.ici.bandwidth == pytest.approx(8e9, rel=1e-3)


# ---------------------------------------------------------------------------
# serving cost model
# ---------------------------------------------------------------------------

def test_serving_token_s_quantized_and_tp():
    base = serving_token_s(TINY, HW)
    assert serving_token_s(TINY, HW, quantized=True) > base
    assert serving_token_s(TINY, HW, tp=2) == pytest.approx(base / 2)
    assert serving_token_s(TINY, HW, context=512.0) > base


def test_serving_cost_padded_step_width():
    """The packed step is padded to the full budget: step_s does not
    depend on offered load, only on the budget — and a wider budget
    costs every step more."""
    t_lo = TrafficSpec(request_rate=1.0)
    t_hi = TrafficSpec(request_rate=50.0)
    a = serving_cost(TINY, HW, t_lo, token_budget=16, max_slots=4)
    b = serving_cost(TINY, HW, t_hi, token_budget=16, max_slots=4)
    assert a.step_s == b.step_s
    wide = serving_cost(TINY, HW, t_lo, token_budget=64, max_slots=4)
    assert wide.step_s > a.step_s


def test_serving_cost_saturation_monotone():
    rates = [0.5, 2.0, 8.0, 32.0, 128.0, 512.0]
    costs = [serving_cost(TINY, HW, TrafficSpec(request_rate=r),
                          token_budget=16, max_slots=4) for r in rates]
    utils = [c.utilization for c in costs]
    assert utils == sorted(utils)
    assert not costs[0].saturated and costs[-1].saturated
    # TTFT grows with load; unsaturated goodput tracks offered load,
    # saturated goodput is capped at capacity and stops growing
    ttfts = [c.ttft_s for c in costs]
    assert ttfts == sorted(ttfts)
    assert costs[0].tokens_per_s == pytest.approx(0.5 * 16.0)
    assert costs[-1].tokens_per_s == pytest.approx(costs[-2].tokens_per_s)


def test_serving_cost_slot_pressure_stretches_tpot():
    t = TrafficSpec(request_rate=20.0, new_tokens=32.0)
    few = serving_cost(TINY, HW, t, token_budget=32, max_slots=1)
    many = serving_cost(TINY, HW, t, token_budget=32, max_slots=32)
    assert few.tpot_s > many.tpot_s
    assert few.tpot_s >= few.step_s and many.tpot_s >= many.step_s


def test_serving_pool_blocks_covers_mix():
    t = TrafficSpec(request_rate=1.0, prompt_tokens=60.0, new_tokens=20.0,
                    shared_prefix_tokens=16.0)
    n = serving_pool_blocks(TINY, t, block_size=8, max_slots=4)
    # 4 slots x ceil(80/8) + ceil(16/8) shared, x1.25 slack
    assert n == math.ceil((4 * 10 + 2) * 1.25)


def test_traffic_spec_validation():
    with pytest.raises(ValueError):
        TrafficSpec(request_rate=-1.0)
    with pytest.raises(ValueError):
        TrafficSpec(request_rate=1.0, prompt_tokens=8.0,
                    shared_prefix_tokens=16.0)
    t = TrafficSpec(request_rate=1.0, prompt_tokens=64.0,
                    shared_prefix_tokens=24.0)
    assert t.unique_prompt_tokens == 40.0


# ---------------------------------------------------------------------------
# serving_search: valid configs, SLO verdicts
# ---------------------------------------------------------------------------

def test_serving_search_emits_constructible_engine_config():
    from neuronx_distributed_tpu.inference.engine import EngineConfig

    t = TrafficSpec(request_rate=8.0, shared_prefix_tokens=16.0)
    plans = serving_search(TINY, HW, t, disaggregated=True, top_k=5)
    assert plans
    for p in plans:
        cfg = EngineConfig(**p.engine)  # every plan is constructible
        assert cfg.prefix_sharing  # shared prefix in the mix
        assert cfg.disaggregated and cfg.prefill_budget >= 1
        # admission headroom: the emitted per-seq cap fits a request
        # twice the stated mean, so the tail is not never_fits
        assert (cfg.max_blocks_per_seq * cfg.block_size
                >= min(2 * (t.prompt_tokens + t.new_tokens), TINY.seq))
        assert "budget=" in p.describe()


def test_serving_search_slo_verdicts_and_router_plumb():
    t = TrafficSpec(request_rate=4.0)
    loose = serving_search(TINY, HW, t, slo_ttft_p99_s=1e6,
                           slo_tpot_p99_s=1e6, top_k=3)
    assert loose and loose[0].meets_slo
    assert loose[0].router["slo"] == {"ttft_p99_s": 1e6,
                                      "tpot_p99_s": 1e6}
    tight = serving_search(TINY, HW, t, slo_ttft_p99_s=1e-12, top_k=3)
    assert tight and not tight[0].meets_slo
    # without a stated SLO there is nothing to plumb to the router
    free = serving_search(TINY, HW, t, top_k=1)
    assert free[0].router == {} and free[0].meets_slo


def test_serving_search_ranked_by_goodput_then_latency():
    t = TrafficSpec(request_rate=16.0)
    plans = serving_search(TINY, HW, t, top_k=5)
    assert len(plans) >= 2
    best = plans[0]
    assert all(best.cost.tokens_per_s >= p.cost.tokens_per_s * 0.98
               for p in plans if p.meets_slo == best.meets_slo
               and p.cost.saturated == best.cost.saturated)
