"""The ``sparse_paged_attention`` kernel's share of its roofline: the least
time the chip could take for the operations and bytes of the positions the
rows really attended over the traced steps (:func:`work`, from the
configuration and the slots' true lengths, and ``peaks.json``) over the
kernel's device time in the trace, in percent. Says which bound holds.
``readers/kernel_roofline.py`` with a work function of its own:
``readers/work.py`` stays as it is.
"""

import numpy as np

import harness
from readers.eva_roofline import rows_of
from tracereduce import xplane


def work(obs):
    """``(flops, bytes)`` of the block-sparse layers' attention over the
    traced steps. A row at position ``t`` attends ``t + 1`` positions
    while ``t < dense_len`` and ``(topk - 1) * block + t % block + 1``
    beyond it (the selection always holds ``topk`` blocks, the row's own
    among them, cut at the row): ``4 * heads * head_dim`` operations each
    (QK^T and PV, a multiply and an add). Bytes, a lower bound: per slot,
    step and layer the K and V of the positions its last row attends,
    read once however many rows of the slot are in the step and however
    their selections differ: ``2 * positions * kv_heads * head_dim * 2``.
    Left out: the scoring of the compressed keys and the top-k, which run
    in the step's own operations outside the kernel whose time this is
    held against (``select_share_pct.batch``), the writes of the new rows
    and of the output."""
    lens = obs.series.get("traced_slot_lengths")
    if not lens or len(lens) < 2:
        return None
    c = obs.config
    sp = c["sparse"]
    heads, kv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"])
    kept = (sp["topk"] - 1) * sp["block"]
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
            attended = np.where(pos < sp["dense_len"], pos + 1,
                                kept + pos % sp["block"] + 1)
            flops += 4.0 * heads * d * float(attended.sum())
            nbytes += 2.0 * float(attended[-1]) * kv * d * 2
        prev = cur
    layers = sum(m == "minicpm4" for m in c["mixer_types"])
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
