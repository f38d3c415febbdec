"""The indexer's and the selected attention's shares of their rooflines
in a model whose rows attend a learned selection of their context
(``deepseek_v32``): the least time the chip could take for the operations
and bytes the work *needs* over the traced steps (:data:`WORK`, from the
configuration and the slots' resident lengths, and ``peaks.json``) over
the device time of the work's scope in the trace (``attn.index``: the
index scores; ``attn.kernel``: the attention over the selected rows),
whatever implements it, in percent. Says which bound holds.
``readers/kernel_roofline.py`` with work functions of its own:
``readers/work.py`` stays as it is. The counts are of the work needed,
never of bytes moved or positions masked: an implementation that scored
or attended a row's whole context under a mask would read under 10 here.
A program without the scopes gives ``None``.
"""

import numpy as np

import harness
from readers import device_scope_share
from readers.eva_roofline import rows_of


def _steps(obs):
    """``(positions of a slot's rows, its last row's context)`` for every
    slot with rows in every traced step."""
    lens = obs.series.get("traced_slot_lengths")
    if not lens or len(lens) < 2:
        return
    prev = np.asarray(lens[0], np.int64)
    for cur in lens[1:]:
        cur = np.asarray(cur, np.int64)
        for before, now in zip(prev, cur):
            if now <= 0:
                continue
            pos = rows_of(int(before), int(now))
            if pos.size:
                yield pos, int(pos[-1]) + 1
        prev = cur


def index_work(obs):
    """``(flops, bytes)`` of the index scores, all layers: a row at
    position ``t`` scores ``t + 1`` positions with ``index_n_heads`` heads
    of ``index_head_dim`` values, a multiply and an add each; per slot,
    step and layer the index keys of the positions its last row scores are
    read once however many rows of the slot are in the step
    (``index_head_dim`` values of 2 bytes a position), a row's index
    queries are read and its scores written (float32)."""
    c = obs.config
    if "index_n_heads" not in c:
        return None
    heads, width = c["index_n_heads"], c["index_head_dim"]
    steps = list(_steps(obs))
    if not steps:
        return None
    flops = nbytes = 0.0
    for pos, last in steps:
        scored = float(np.sum(pos + 1))
        flops += 2.0 * heads * width * scored
        nbytes += (2.0 * width * last + 2.0 * heads * width * pos.size
                   + 4.0 * scored)
    layers = c["num_hidden_layers"]
    return flops * layers, nbytes * layers


def attention_work(obs):
    """``(flops, bytes)`` of latent attention over the selected rows, all
    layers: a row at position ``t`` attends ``min(t + 1, index_topk)``
    cached rows, each head scoring ``kv_lora_rank + qk_rope_head_dim``
    values and summing ``kv_lora_rank`` (absorbed, as the served path
    computes): ``2 * heads * (2 * rank + rope)`` operations a selected
    position; a row's selected rows' ``rank + rope`` values are read once
    a row (a selection is the row's own), in 2 bytes, beside its queries
    and outputs, ``heads * (2 * rank + rope)`` values."""
    c = obs.config
    if "index_topk" not in c:
        return None
    heads, rank, rope = (c["num_attention_heads"], c["kv_lora_rank"],
                         c["qk_rope_head_dim"])
    steps = list(_steps(obs))
    if not steps:
        return None
    flops = nbytes = 0.0
    for pos, _ in steps:
        attended = float(np.sum(np.minimum(pos + 1, c["index_topk"])))
        flops += 2.0 * heads * (2 * rank + rope) * attended
        nbytes += 2.0 * ((rank + rope) * attended
                         + heads * (2 * rank + rope) * pos.size)
    layers = c["num_hidden_layers"]
    return flops * layers, nbytes * layers


WORK = {"index": index_work, "attention": attention_work}


def read(args: dict, obs):
    if obs.trace is None or obs.peaks is None or obs.reduction is None:
        return None
    needs = WORK[args["work"]](obs)
    if needs is None:
        return None
    share = device_scope_share.read({"scopes": args["scopes"]}, obs)
    if not share:
        return None
    device = min(obs.trace.devices)
    scope_s = share / 100.0 * obs.reduction.busy_by_device[device]
    flops, nbytes = needs
    t_compute = flops / obs.peaks["bf16_flops_per_s"]
    t_memory = nbytes / obs.peaks["hbm_bytes_per_s"]
    harness.say("metric", scope=args["scopes"], work=args["work"],
                flops=flops, bytes=nbytes,
                least_s=max(t_compute, t_memory), scope_s=scope_s,
                bound="memory" if t_memory >= t_compute else "compute")
    return 100.0 * max(t_compute, t_memory) / scope_s
