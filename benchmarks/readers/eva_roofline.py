"""The ``eva_attention`` kernel's share of its roofline: the least time the
chip could take for the operations and bytes EVA attention needs over the
traced steps (:func:`work`, from the configuration and the slots' true
lengths, and ``peaks.json``) over the kernel's device time in the trace, in
percent. Says which bound holds. ``readers/kernel_roofline.py`` with a work
function of its own: ``readers/work.py`` stays as it is.
"""

import numpy as np

import harness
from tracereduce import xplane


def rows_of(prev: int, now: int):
    """Positions of the rows a slot had in a step, from its resident
    length before (``prev``) and at (``now``) the step, as
    ``work.paged_attention`` reads them: a prefill chunk's are included in
    ``now``, a decode row's is not."""
    grew = now - prev
    if grew > 1 or grew < 0:            # a prefill chunk (a new request: all)
        rows = grew if grew > 1 else now
        return np.arange(now - rows, now, dtype=np.int64)
    return np.array([now], np.int64)    # one decode row


def work(obs):
    """``(flops, bytes)`` of EVA attention over the traced steps, all
    layers. A row at position ``i`` in window ``w = i // W`` attends ``i -
    wW + 1`` exact rows and ``(W / C) * w`` summary rows, ``4 * heads *
    head_dim`` operations each (QK^T and PV, a multiply and an add). Per
    slot, step and layer the exact rows and the summary rows its rows
    attend are read once, however many rows of the slot are in the step:
    ``2 * rows * kv_heads * head_dim * 2`` bytes. A chunk that straddles a
    window's end reads the whole of the ending window and the start of
    the next. Left out: the writes of the new rows and of the output, and
    the summarisation of a completed window (its K and V read once more):
    that runs in the step's own operations under a ``cond``, outside the
    kernel whose time this is held against, so counting it here would
    overstate the share."""
    lens = obs.series.get("traced_slot_lengths")
    if not lens or len(lens) < 2:
        return None
    c = obs.config
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c.get("head_dim") or c["hidden_size"] // heads
    window, per_window = c["window_size"], c["window_size"] // c["chunk_size"]
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
            w = pos // window
            flops += 4.0 * heads * d * float(
                np.sum(pos - w * window + 1 + per_window * w))
            last = int(pos[-1])
            exact = last - int(w[-1]) * window + 1
            if w[0] != w[-1]:
                exact += window
            nbytes += 2.0 * (exact + per_window * int(w[-1])) * kv * d * 2
        prev = cur
    layers = c["num_hidden_layers"]
    return flops * layers, nbytes * layers


def read(args: dict, obs):
    if obs.trace is None or obs.peaks is None:
        return None
    hit = xplane.matching(obs.trace, args["match"], obs.reduction.window)
    needs = work(obs)
    if not hit["count"] or needs is None:
        return None
    flops, nbytes = needs
    t_compute = flops / obs.peaks["bf16_flops_per_s"]
    t_memory = nbytes / obs.peaks["hbm_bytes_per_s"]
    harness.say("metric", kernel=args["match"], flops=flops, bytes=nbytes,
                least_s=max(t_compute, t_memory), kernel_s=hit["total"],
                bound="memory" if t_memory >= t_compute else "compute")
    return 100.0 * max(t_compute, t_memory) / hit["total"]
