"""Operations and bytes the algorithms need, from shapes alone. Kept with
the benchmark so that no PR that claims a gain can change the yardstick."""

from __future__ import annotations

import numpy as np


def paged_attention(obs):
    """``(flops, bytes)`` of paged attention over the traced steps, all
    layers. ``traced_slot_lengths`` holds each slot's resident tokens as
    the step before the trace and then every traced step saw them (a slot
    in prefill: its chunk included; a decoding slot: without the row it
    decodes).

    Needed per slot and step, per layer: read the context's K and V once
    (2 * ctx * kv_heads * head_dim * 2 B), however many rows of the slot
    are in the step; 4 * heads * head_dim operations per (row, attended
    position): QK^T and PV, a multiply and an add each. Writes of the new
    K/V rows and of the output are left out (under 2% at these lengths)."""
    lens = obs.series.get("traced_slot_lengths")
    if not lens or len(lens) < 2:
        return None
    c = obs.config
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c.get("head_dim") or c["hidden_size"] // heads
    flops = nbytes = 0.0
    prev = np.asarray(lens[0], np.int64)
    for cur in lens[1:]:
        cur = np.asarray(cur, np.int64)
        grew = cur - prev
        for now, g in zip(cur, grew):
            if now <= 0:
                continue
            if g > 1 or g < 0:
                # a prefill chunk: g rows (a new request: all it holds)
                rows = g if g > 1 else now
                ctx = now
                attended = rows * ctx - rows * (rows - 1) / 2
            else:
                ctx = now + 1               # one decode row
                attended = ctx
            flops += 4.0 * heads * d * attended
            nbytes += 2.0 * ctx * kv * d * 2
        prev = cur
    layers = c["num_hidden_layers"]
    return flops * layers, nbytes * layers


def decoder_train(obs):
    """Operations per trained token: 6 per matmul parameter (the head
    included, the embedding lookup not), plus causal attention's
    6 * layers * seq * hidden (QK^T and PV, forward and backward, half
    the square)."""
    c = obs.config
    return (6.0 * obs.scalars["matmul_params"]
            + 6.0 * c["num_hidden_layers"] * obs.scalars["seq_len"]
            * c["hidden_size"])
