"""Speculative decoding, head padding, fp32 masters."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from neuronx_distributed_tpu.inference.speculative import (
    build_medusa_tree, medusa_accept_longest, verify_draft_greedy)
from neuronx_distributed_tpu.parallel.pad import (get_number_of_extra_heads,
                                                  pad_attention_params)
from neuronx_distributed_tpu.trainer.mixed_precision import (
    with_fp32_master_weights)


def test_verify_draft_greedy():
    v = 16
    # target greedy tokens: [3, 5, 7, 9] at the 4 positions (K=3 drafts)
    logits = jnp.zeros((1, 4, v))
    for j, t in enumerate([3, 5, 7, 9]):
        logits = logits.at[0, j, t].set(10.0)
    # draft matches 2 then diverges
    accepted, nxt = verify_draft_greedy(logits, jnp.array([[3, 5, 0]]))
    assert int(accepted[0]) == 2
    np.testing.assert_array_equal(np.asarray(nxt[0]), [3, 5, 7, 9])
    # all match
    accepted, _ = verify_draft_greedy(logits, jnp.array([[3, 5, 7]]))
    assert int(accepted[0]) == 3
    # immediate mismatch
    accepted, _ = verify_draft_greedy(logits, jnp.array([[0, 5, 7]]))
    assert int(accepted[0]) == 0


def test_medusa_tree_acceptance():
    buffers = build_medusa_tree(((0,), (1,), (0, 0), (0, 1)))
    t = buffers.tree_mask.shape[0]
    assert t == 5  # root + 4 nodes
    # target greedy at root picks node-1's token; at node 1 picks node-3's
    v = 8
    tree_tokens = jnp.array([[2, 4, 5, 6, 7]])  # root committed=2
    logits = jnp.zeros((1, t, v))
    logits = logits.at[0, 0, 4].set(9.0)   # at root, target says 4 (node 1)
    logits = logits.at[0, 1, 6].set(9.0)   # at node 1, target says 6 (node 3)
    best, depth = medusa_accept_longest(logits, tree_tokens, buffers)
    assert int(best[0]) == 3 and int(depth[0]) == 2


def test_head_padding():
    assert get_number_of_extra_heads(30, 8) == 2
    assert get_number_of_extra_heads(32, 8) == 0
    q = np.ones((16, 30 * 4))
    o = np.ones((30 * 4, 16))
    qp, op, padded = pad_attention_params(q, o, 30, 4, 8)
    assert padded == 32
    assert qp.shape == (16, 128) and op.shape == (128, 16)
    assert (qp[:, 120:] == 0).all() and (op[120:] == 0).all()


def test_fp32_master_weights_optimizer():
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    tx = with_fp32_master_weights(optax.sgd(0.1))
    state = tx.init(params)
    assert state.master["w"].dtype == jnp.float32
    grads = {"w": jnp.full((4,), 1e-3, jnp.bfloat16)}
    p = params
    for _ in range(10):
        updates, state = tx.update(grads, state, p)
        p = optax.apply_updates(p, updates)
    # bf16-only SGD with lr*g = 1e-4 steps would lose most updates to
    # rounding; masters accumulate in fp32
    np.testing.assert_allclose(np.asarray(state.master["w"]),
                               1.0 - 10 * 0.1 * 1e-3, rtol=1e-3)
    assert p["w"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(p["w"], np.float32),
                               np.asarray(state.master["w"].astype(
                                   jnp.bfloat16), np.float32))
