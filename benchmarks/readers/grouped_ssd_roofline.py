"""The grouped state-space scan's share of its roofline:
``readers/ssd_roofline.py``'s count from the keys of a family whose
Mamba-2 layers are named by ``hybrid_override_pattern`` and read ``B`` and
``C`` in ``n_groups`` groups (:func:`work`), over the device time of the
``attn.state`` scope in the trace, whatever implements it, in percent.
Says which bound holds. A program without the scope, or a configuration
without those keys, gives ``None``.
"""

import numpy as np

import harness
from readers import device_scope_share
from readers.eva_roofline import rows_of


def work(obs):
    """``(flops, bytes)`` over the traced steps and the ``M`` layers. Per
    step and layer, a slot that had rows in the step (``rows_of`` of its
    resident length before and at the step) has its float32 state
    ``[d_state, d_inner]`` read once and written once, however many its
    rows; each row brings its ``x``, ``z`` and output (``d_inner`` values
    each), ``B`` and ``C`` (``n_groups * d_state`` each) and ``dt`` (a
    head each) at two bytes a value, and costs ``6 * d_inner * d_state``
    operations (the decay, the outer-product add and the read-out, a
    multiply and an add each). Left out: the projections, the convolution
    and the gated norm, which lie outside the scope."""
    lens = obs.series.get("traced_slot_lengths")
    c = obs.config
    keys = {"mamba_num_heads", "n_groups", "hybrid_override_pattern"}
    if not lens or len(lens) < 2 or not keys <= set(c):
        return None
    heads, n = c["mamba_num_heads"], c["ssm_state_size"]
    inner = heads * c["mamba_head_dim"]
    advanced = rows = 0
    prev = np.asarray(lens[0], np.int64)
    for cur in lens[1:]:
        cur = np.asarray(cur, np.int64)
        for before, now in zip(prev, cur):
            if now > 0:
                advanced += 1
                rows += rows_of(int(before), int(now)).size
        prev = cur
    layers = c["hybrid_override_pattern"].count("M")
    nbytes = advanced * 2.0 * n * inner * 4 + rows * 2.0 * (
        3 * inner + 2 * c["n_groups"] * n + heads)
    return 6.0 * inner * n * rows * layers, nbytes * layers


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
