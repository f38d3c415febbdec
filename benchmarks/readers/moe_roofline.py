"""The held experts' share of their roofline: the least time the chip
could take for the bytes and operations of the routed experts over the
traced steps (:func:`work`, from the configuration, the slots' resident
lengths and the device's own counts of the routed assignments, and
``peaks.json``) over the device time of the ``ffn.experts`` scope in the
trace, whatever implements it (a capacity dispatch's matmuls over every
slot of every held expert, or a grouped kernel), in percent. Says which
bound holds. The work function is kept here; the scope's time is
``readers/device_scope_share.py``'s share of the device's busy time, put
back into seconds. A program without the scope or without the counter
gives ``None``.
"""

import numpy as np

import harness
from readers import device_scope_share
from readers.eva_roofline import rows_of


def kept_share():
    """Of the routed assignments the device counted (window and traced
    seconds: a share, never a count), those that took a slot of an expert
    held here."""
    from neuronx_distributed_tpu import obs as program_obs

    reg = program_obs.get_registry()
    kept_dropped, held = (reg.get("nxd_moe_assignments_total"),
                          reg.get("nxd_moe_held_total"))
    if kept_dropped is None or held is None:
        return None
    kept = sum(c.value for c in kept_dropped.children()
               if c.labels.get("kind") == "kept")
    total = sum(c.value for c in held.children())
    return kept / total if total > 0 else None


def work(config: dict, steps: int, rows: int, kept: float):
    """``(flops, bytes)`` of the held experts over ``steps`` steps whose
    real rows number ``rows``, of whose routed assignments the share
    ``kept`` took a slot here. A step and an expert layer: the held
    experts' three matrices (``input_linear``'s gate and up halves and
    ``output_linear``: ``3 * hidden * intermediate`` values an expert)
    are read once at two bytes a value, however many rows chose them; a
    kept assignment brings its row in and takes its product out
    (``hidden`` values each at two bytes) and costs ``6 * hidden *
    intermediate`` operations (three products, a multiply and an add
    each). Left out: the router and the shared MLP, which lie outside the
    scope, and whatever a dispatch computes over slots no row took."""
    hidden, inter = config["hidden_size"], config["intermediate_size"]
    layers = config["num_hidden_layers"]
    assignments = rows * config["num_experts_per_tok"] * kept * layers
    nbytes = (steps * layers * config["num_local_experts"] * 3.0 * hidden
              * inter * 2 + assignments * 2.0 * hidden * 2)
    return 6.0 * hidden * inter * assignments, nbytes


def traced_rows(obs):
    """``(steps, real rows)`` of the traced steps, from the slots'
    resident lengths before and at each."""
    lens = obs.series.get("traced_slot_lengths")
    if not lens or len(lens) < 2:
        return None
    rows = 0
    prev = np.asarray(lens[0], np.int64)
    for cur in lens[1:]:
        cur = np.asarray(cur, np.int64)
        rows += sum(rows_of(int(before), int(now)).size
                    for before, now in zip(prev, cur) if now > 0)
        prev = cur
    return len(lens) - 1, rows


def read(args: dict, obs):
    if obs.trace is None or obs.peaks is None or obs.reduction is None:
        return None
    share = device_scope_share.read({"scopes": args["scopes"]}, obs)
    traced, kept = traced_rows(obs), kept_share()
    if not share or traced is None or kept is None:
        return None
    device = min(obs.trace.devices)
    scope_s = share / 100.0 * obs.reduction.busy_by_device[device]
    flops, nbytes = work(obs.config, *traced, kept)
    t_compute = flops / obs.peaks["bf16_flops_per_s"]
    t_memory = nbytes / obs.peaks["hbm_bytes_per_s"]
    harness.say("metric", scope=args["scopes"], flops=flops, bytes=nbytes,
                steps=traced[0], rows=traced[1], kept_share=round(kept, 4),
                least_s=max(t_compute, t_memory), scope_s=scope_s,
                bound="memory" if t_memory >= t_compute else "compute")
    return 100.0 * max(t_compute, t_memory) / scope_s
