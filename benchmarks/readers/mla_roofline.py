"""The ``mla_paged_attention`` kernel's share of its roofline: the least
time the chip could take for the operations and bytes latent (MLA)
attention needs over the traced steps (:func:`work`, from the
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
    """``(flops, bytes)`` of latent attention over the traced steps, all
    layers. A row at position ``t`` attends ``t + 1`` cached rows, each
    head scoring ``kv_lora_rank + qk_rope_head_dim`` values and summing
    ``kv_lora_rank``: ``2 * heads * (2 * rank + rope)`` operations a
    position (absorbed, as the served path computes; the absorption's own
    matmuls run outside the kernel whose time this is held against).
    Bytes, the least any kernel can fetch: per slot, step and layer the
    ``rank + rope`` values of the positions its last row attends, read
    once however many rows and heads of the slot are in the step, in 2
    bytes (the idle lanes of a stored row are not counted: a kernel that
    skipped them would read less, one that reads them reads more than
    this), and a row's queries and outputs, ``heads * (2 * rank + rope)``
    values."""
    lens = obs.series.get("traced_slot_lengths")
    if not lens or len(lens) < 2:
        return None
    c = obs.config
    heads, rank, rope = (c["num_attention_heads"], c["kv_lora_rank"],
                         c["qk_rope_head_dim"])
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
            flops += 2.0 * heads * (2 * rank + rope) * float(
                np.sum(pos + 1))
            nbytes += 2.0 * ((rank + rope) * float(pos[-1] + 1)
                             + heads * (2 * rank + rope) * pos.size)
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
