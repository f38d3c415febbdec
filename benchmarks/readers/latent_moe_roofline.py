"""The held latent experts' share of their roofline: the least time the
chip could take for the bytes and operations of a bank of ungated experts
that work in a latent, over the traced steps (:func:`work`, from the
configuration, the slots' resident lengths and the device's own counts of
the routed assignments, and ``peaks.json``) over the device time of the
``ffn.experts`` scope in the trace, whatever implements it (a capacity
dispatch's matmuls over every slot of every held expert, or a grouped
kernel), in percent. Says which bound holds. The work function is kept
here; the traced rows and the kept share are ``readers/moe_roofline.py``'s,
the scope's time ``readers/device_scope_share.py``'s share of the device's
busy time, put back into seconds. A program without the scope or without
the counter, or a configuration without the latent's keys, gives
``None``.
"""

import harness
from readers import device_scope_share
from readers.moe_roofline import kept_share, traced_rows


def work(config: dict, steps: int, rows: int, kept: float):
    """``(flops, bytes)`` of the held experts over ``steps`` steps whose
    real rows number ``rows``, of whose routed assignments the share
    ``kept`` took a slot here. A step and an ``E`` layer: the held
    experts' two matrices (``up_proj`` and ``down_proj``: ``2 * latent *
    intermediate`` values an expert) are read once at two bytes a value,
    however many rows chose them; a kept assignment brings its latent row
    in and takes its product out (``latent`` values each at two bytes)
    and costs ``4 * latent * intermediate`` operations (two products, a
    multiply and an add each). Left out: the router, the latent pair and
    the shared expert, which lie outside the scope, and whatever a
    dispatch computes over slots no row took."""
    latent, inter = config["moe_latent_size"], config["moe_intermediate_size"]
    layers = config["hybrid_override_pattern"].count("E")
    assignments = rows * config["num_experts_per_tok"] * kept * layers
    nbytes = (steps * layers * config["n_routed_experts"] * 2.0 * latent
              * inter * 2 + assignments * 2.0 * latent * 2)
    return 4.0 * latent * inter * assignments, nbytes


def read(args: dict, obs):
    if obs.trace is None or obs.peaks is None or obs.reduction is None:
        return None
    if not {"moe_latent_size", "hybrid_override_pattern"} <= set(obs.config):
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
