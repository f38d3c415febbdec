"""Training traffic: a fresh batch of token ids every step.

A mix is ``{"kind": "token_batches", "batch": b, "seq_len": s}``: the
global batch is fixed in tokens (``b * s``), ids are uniform over the
vocabulary, labels are the ids shifted by one. Batch ``i`` of seed ``n``
is always the same array; every step's work is the same whatever the
seed, since shapes never change.
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


def batch(params: dict, seed: int, vocab_size: int, index: int
          ) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng([seed, index])
    ids = rng.integers(0, vocab_size,
                       (int(params["batch"]), int(params["seq_len"]) + 1),
                       dtype=np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def generate(params: dict, seed: int, vocab_size: int, seconds: float
             ) -> Iterator[Dict[str, np.ndarray]]:
    index = 0
    while True:
        yield batch(params, seed, vocab_size, index)
        index += 1
