"""plan/ — parallelism placement auto-tuner over the hierarchical mesh.

Reproduces the synthesis loop of "Synthesizing Optimal Parallelism
Placement and Reduction Strategies on Hierarchical Systems" (PAPERS.md,
arXiv:2110.10548) for this framework's knob set: an analytic α-β cost
model over the two link tiers (:mod:`.cost`), an exhaustive
enumerate-and-prune search with machine-readable rejection reasons
(:mod:`.search`), emission of the winning plan as a validated
``neuronx_distributed_config(...)``/YAML config (:mod:`.emit`), and
optional measured re-ranking of the analytic top-k (:mod:`.refine`).

CLI::

    python -m neuronx_distributed_tpu.plan --model llama2-7b --devices 32

See docs/planner.md.
"""

from .calibrate import (CalibrationResult, LinkFit, calibrate,
                        fit_alpha_beta, fit_mfu, load_bench_history,
                        mfu_from_bench)
from .cost import (CostBreakdown, HardwareSpec, LinkSpec, ModelSpec, Plan,
                   ServingCost, ServingPlan, ServingSpec, SpeculationSpec,
                   TrafficSpec,
                   cold_start_s, dcn_handoff_bytes, dcn_handoff_s,
                   default_hardware, memory_bytes,
                   param_count, serving_cost, serving_pool_blocks,
                   serving_search, serving_token_s, step_cost, step_flops,
                   tp_overlap_engagement, wire_bytes_per_element)
from .emit import (plan_to_config, plan_to_config_kwargs, plan_to_yaml_dict,
                   render_kwargs)
from .refine import RefinedPlan, proxy_measure, refine
from .search import (PRUNE_DOMINATED, PRUNE_INDIVISIBLE, PRUNE_OOM, Pruned,
                     RankedPlan, SearchResult, enumerate_plans, search)


def handpicked_plan(devices: int, *, platform: str = "cpu",
                    dcn_dp: int = 1) -> Plan:
    """A static hand-picked layout for this device count (tp=2 on CPU
    hosts, tp up to 8 on a TPU slice, ZeRO-1, flat fp32 rings) — the
    baseline the CLI prints the search's winner beside. ``dcn_dp`` is
    the fleet's cross-slice degree: the baseline runs on the same fleet
    as the search, it just doesn't adapt to it (flat fp32 rings)."""
    if platform == "cpu" or devices < 8:
        tp = 2 if devices % 2 == 0 else 1
    else:
        tp = min(8, devices)
    dp = devices // tp
    return Plan(devices=devices, tp=tp, pp=1, dp=dp,
                dcn_dp=dcn_dp if dcn_dp > 1 and dp % dcn_dp == 0 else 1,
                zero1=True, grad_comm_dtype="fp32",
                grad_comm_hierarchical=False, tp_overlap=False,
                sequence_parallel=False, remat=platform != "cpu")


__all__ = [
    "CalibrationResult", "CostBreakdown", "HardwareSpec", "LinkFit",
    "LinkSpec", "ModelSpec", "Plan", "ServingCost", "ServingPlan",
    "ServingSpec", "SpeculationSpec", "TrafficSpec", "calibrate",
    "cold_start_s",
    "dcn_handoff_bytes", "dcn_handoff_s",
    "default_hardware", "fit_alpha_beta", "fit_mfu",
    "load_bench_history", "memory_bytes", "mfu_from_bench",
    "param_count", "serving_cost", "serving_pool_blocks",
    "serving_search", "serving_token_s", "step_cost", "step_flops",
    "tp_overlap_engagement", "wire_bytes_per_element",
    "plan_to_config", "plan_to_config_kwargs", "plan_to_yaml_dict",
    "render_kwargs",
    "RefinedPlan", "proxy_measure", "refine",
    "PRUNE_DOMINATED", "PRUNE_INDIVISIBLE", "PRUNE_OOM", "Pruned",
    "RankedPlan", "SearchResult", "enumerate_plans", "search",
    "handpicked_plan",
]
