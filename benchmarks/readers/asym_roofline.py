"""Attention's share of its roofline in a model whose full-attention and
sliding-window layers have unlike K/V head counts and keys wider than the
values: the least time the chip could take for the bytes and operations of
the positions each row may attend over the traced steps (:func:`work`,
from the configuration and the slots' resident lengths, and
``peaks.json``) over the device time of the attention kernels' scopes in
the trace (``attn.kernel`` and its children), whatever implements them,
in percent. Says which bound holds. ``readers/swa_roofline.py`` reads one
``num_key_value_heads`` and one ``head_dim`` for every layer and for both
operands, so it would misread such a configuration; the scopes' time is
``readers/device_scope_share.py``'s share of the device's busy time, put
back into seconds. A program without the scopes gives ``None``.
"""

import numpy as np

import harness
from readers import device_scope_share
from readers.eva_roofline import rows_of


def work(obs):
    """``(flops, bytes)`` over the traced steps and the attention layers.
    A row at position ``t`` may attend ``t + 1`` positions in a full layer
    (``hybrid_layer_pattern`` 0) and ``min(t + 1, sliding_window)`` in a
    sliding one (1): ``2 * heads * (head_dim + v_head_dim)`` operations
    each (QK^T over a key of ``head_dim`` values, PV over a value of
    ``v_head_dim``, a multiply and an add). Per slot, step and layer the K
    and V of the positions its rows may attend are read once, however many
    rows of the slot are in the step (a chunk of ``r`` rows under a window
    sees ``window + r - 1``): ``positions * kv_heads * (head_dim +
    v_head_dim) * 2`` bytes at the layer type's own K/V head count. The
    sink term is free: no byte of the pool and one exponential a head and
    row. Left out: the writes of the new rows and of the output, the
    projections and the rotary, which lie outside the scopes."""
    lens = obs.series.get("traced_slot_lengths")
    if not lens or len(lens) < 2:
        return None
    c = obs.config
    heads, d, dv, window = (c["num_attention_heads"], c["head_dim"],
                            c["v_head_dim"], c["sliding_window"])
    kv_of = (c["num_key_value_heads"], c["swa_num_key_value_heads"])
    flops = nbytes = 0.0
    prev = np.asarray(lens[0], np.int64)
    for cur in lens[1:]:
        cur = np.asarray(cur, np.int64)
        for before, now in zip(prev, cur):
            if now <= 0:
                continue
            pos = rows_of(int(before), int(now))
            if pos.size == 0:
                continue
            last = int(pos[-1]) + 1
            for kind in c["hybrid_layer_pattern"]:
                if kind == 1:
                    attended = float(np.minimum(pos + 1, window).sum())
                    read = min(last, window + pos.size - 1)
                else:
                    attended, read = float((pos + 1).sum()), last
                flops += 2.0 * heads * (d + dv) * attended
                nbytes += 2.0 * read * kv_of[kind] * (d + dv)
        prev = cur
    return flops, nbytes


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
