"""The routed experts' share of their roofline where the configuration
names its experts ``num_experts`` of ``moe_intermediate_size`` (the
Qwen3-MoE keys, SDAR's) and its step's real rows are not what a slot's
resident length grew by (a slot that decodes a block runs the block's rows
several passes before the block is stored).

``readers/moe_roofline.py``'s own :func:`work` over the device time of the
``ffn.experts`` scope, whatever implements it, handed this configuration's
keys in the places of ``intermediate_size`` and ``num_local_experts``
(under which it would count the experts eight times too wide), and the
routed assignments as the device counted them: the traced steps' share, by
steps, of ``nxd_moe_assignments_total`` (window and traced seconds, the
same load), which is the real rows' choices over all the layers whatever
pass a row was in. The kept share is kept over kept and dropped of the
same counter (every expert is held here). A program without the scope or
the counters gives ``None``.
"""

import harness
from readers import device_scope_share, moe_roofline


def counted():
    """``(assignments a step over all the layers, kept share)`` from the
    program's registry, or None."""
    from neuronx_distributed_tpu import obs as program_obs

    reg = program_obs.get_registry()
    assignments, steps = (reg.get("nxd_moe_assignments_total"),
                          reg.get("nxd_engine_steps_total"))
    if assignments is None or steps is None:
        return None
    by_kind = {c.labels.get("kind"): c.value
               for c in assignments.children()}
    asked = sum(by_kind.values())
    ran = sum(c.value for c in steps.children())
    if asked <= 0 or ran <= 0:
        return None
    return asked / ran, by_kind.get("kept", 0.0) / asked


def read(args: dict, obs):
    if obs.trace is None or obs.peaks is None or obs.reduction is None:
        return None
    c = obs.config
    if "moe_intermediate_size" not in c or "num_experts" not in c:
        return None
    share = device_scope_share.read({"scopes": args["scopes"]}, obs)
    found = counted()
    if not share or found is None or not obs.traced_steps:
        return None
    a_step, kept = found
    config = dict(c, intermediate_size=c["moe_intermediate_size"],
                  num_local_experts=c["num_experts"])
    # moe_roofline.work multiplies the rows by the choices a row and the
    # layers: hand it the rows that the counted assignments are
    rows = (a_step * obs.traced_steps
            / (c["num_experts_per_tok"] * c["num_hidden_layers"]))
    flops, nbytes = moe_roofline.work(config, obs.traced_steps, rows, kept)
    device = min(obs.trace.devices)
    scope_s = share / 100.0 * obs.reduction.busy_by_device[device]
    t_compute = flops / obs.peaks["bf16_flops_per_s"]
    t_memory = nbytes / obs.peaks["hbm_bytes_per_s"]
    harness.say("metric", scope=args["scopes"], flops=flops, bytes=nbytes,
                steps=obs.traced_steps, rows=round(rows, 1),
                kept_share=round(kept, 4),
                least_s=max(t_compute, t_memory), scope_s=scope_s,
                bound="memory" if t_memory >= t_compute else "compute")
    return 100.0 * max(t_compute, t_memory) / scope_s
