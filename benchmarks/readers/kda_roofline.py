"""The delta rule's share of its roofline: the least time the chip could
take for the bytes and operations of the KDA layers' state update over
the traced steps (:func:`work`, from the configuration and the slots'
resident lengths, and ``peaks.json``) over the device time of the
``attn.state`` scope in the trace, whatever implements it (XLA operations
or a Mosaic call), in percent. Says which bound holds. ``readers/work.py``
stays as it is; the scope's time is ``readers/device_scope_share.py``'s
share of the device's busy time, put back into seconds. A program without
the scope gives ``None``.
"""

import numpy as np

import harness
from readers import device_scope_share
from readers.eva_roofline import rows_of


def work(obs):
    """``(flops, bytes)`` over the traced steps and the KDA layers. Per
    step and layer, a slot that had rows in the step (``rows_of`` of its
    resident length before and at the step) has its float32 state ``[N,
    dk, dv]`` read once and written once, however many its rows; each row
    brings its ``q``, ``k``, ``v``, ``g`` and output (``N dk`` values
    each) and ``beta`` (a head each) at two bytes a value, and costs ``7 N
    dk dv`` operations: the decay (1), ``S'^T k`` (2), the rank-1 add (2)
    and the read-out (2). Left out: the projections, the convolution, the
    norms and the gate, which lie outside the scope."""
    lens = obs.series.get("traced_slot_lengths")
    if not lens or len(lens) < 2:
        return None
    c = obs.config
    lin = c["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    advanced = rows = 0
    prev = np.asarray(lens[0], np.int64)
    for cur in lens[1:]:
        cur = np.asarray(cur, np.int64)
        for before, now in zip(prev, cur):
            if now > 0:
                advanced += 1
                rows += rows_of(int(before), int(now)).size
        prev = cur
    layers = c["num_hidden_layers"] - len(c["gqa_layers"])
    nbytes = advanced * 2.0 * heads * d * d * 4 + rows * 2.0 * (
        5 * heads * d + heads)
    return 7.0 * heads * d * d * rows * layers, nbytes * layers


def read(args: dict, obs):
    if obs.trace is None or obs.peaks is None or obs.reduction is None:
        return None
    share = device_scope_share.read({"scopes": args["scopes"]}, obs)
    needs = work(obs)
    if not share or needs is None:
        return None
    device = min(obs.trace.devices)
    scope_s = share / 100.0 * obs.reduction.busy_by_device[device]
    flops, nbytes = needs
    t_compute = flops / obs.peaks["bf16_flops_per_s"]
    t_memory = nbytes / obs.peaks["hbm_bytes_per_s"]
    harness.say("metric", scope=args["scopes"], flops=flops, bytes=nbytes,
                least_s=max(t_compute, t_memory), scope_s=scope_s,
                bound="memory" if t_memory >= t_compute else "compute")
    return 100.0 * max(t_compute, t_memory) / scope_s
