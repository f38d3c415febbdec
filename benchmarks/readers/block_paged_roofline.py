"""The ``paged_attention`` kernel's share of its roofline for a family
that decodes a block of positions at a time: the least time the chip
could take for the operations and bytes block-causal attention needs over
the traced steps (:func:`work`, from the configuration and the slots'
resident lengths, and ``peaks.json``) over the kernel's device time in the
trace, in percent. ``readers/kernel_roofline.py`` with a work function of
its own: ``readers/work.py`` ``paged_attention`` infers one decode row
from a length that grew by at most one, and here a decoding slot has its
block's ``block_length`` rows in every step while its resident length
grows by a block only when a store pass has landed.
"""

import numpy as np

import harness
from tracereduce import xplane


def slot_rows(lengths, block: int):
    """``(rows, attended positions summed over the rows, positions read)``
    of one slot at each traced step, from its resident length before the
    first step and at each (``lengths [steps + 1]``). A length that grew
    by more than a block is a prefill chunk (its rows are in the new
    length, each attending through its own block's end); one that stayed
    or grew by a block is a decoding slot's ``block`` rows, which attend
    through the end of the block behind the resident length; a slot that
    waits between two prefill chunks (its next growth is a chunk's) has no
    row."""
    lengths = np.asarray(lengths, np.int64)
    grew = np.diff(lengths)
    out = []
    # the next growth of the same request, looking ahead: a chunk's or a
    # block's (unknown at the trace's end: a block's)
    ahead, nxt = np.zeros(len(grew), np.int64), block
    for i in range(len(grew) - 1, -1, -1):
        if grew[i] < 0:
            nxt = block             # another request takes the slot here
        elif grew[i] > 0:
            nxt = grew[i]
        ahead[i] = nxt
    for i, g in enumerate(grew):
        now, before = int(lengths[i + 1]), int(lengths[i])
        if g < 0:                   # a new request's first chunk
            g, before = now, 0
        if now <= 0:
            out.append((0, 0, 0))
        elif g > block:
            pos = np.arange(before, now, dtype=np.int64)
            through = pos // block * block + block
            out.append((pos.size, int(np.sum(through)), now))
        elif g == 0 and ahead[i] > block:
            out.append((0, 0, 0))   # between two chunks
        else:
            out.append((block, block * (now + block), now + block))
    return out


def work(obs):
    """``(flops, bytes)`` of block-causal attention over the traced
    steps, all layers: ``4 * heads * head_dim`` operations a row and
    attended position (QK^T and PV, a multiply and an add); per slot, step
    and layer the K and V of the positions its rows attend read once,
    however many of its rows are in the step (``2 * kv_heads * head_dim``
    values a position at two bytes), and a row's queries and outputs."""
    lens = obs.series.get("traced_slot_lengths")
    c = obs.config
    if not lens or len(lens) < 2 or "block_length" not in c:
        return None
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c.get("head_dim") or c["hidden_size"] // heads
    lens = np.asarray(lens, np.int64)                  # [steps + 1, slots]
    rows = attended = read = 0
    for slot in range(lens.shape[1]):
        for r, a, p in slot_rows(lens[:, slot], int(c["block_length"])):
            rows, attended, read = rows + r, attended + a, read + p
    layers = c["num_hidden_layers"]
    flops = 4.0 * heads * d * attended * layers
    nbytes = (2.0 * kv * d * read + 2.0 * heads * d * rows) * 2 * layers
    return flops, nbytes


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
