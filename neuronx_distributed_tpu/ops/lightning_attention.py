"""Lightning (decayed linear) attention: a per-head outer-product state
``S_t = lambda_h S_{t-1} + k_t^T v_t`` read as ``o_t = q_t S_t``.

Two forms of the one recurrence, both in float32 at ``highest`` (the
state is float32 and a default TPU matmul would round it to bfloat16):

* :func:`lightning_attention_full` - a whole sequence, no cache: chunks
  of queries, one masked ``[C, C]`` product a chunk plus the state the
  earlier chunks left (tests, small training).
* :func:`lightning_attention_packed` - the serving step: its ``T`` rows
  belong to several cache slots (decode rows beside prefill chunks), each
  row continues its own slot's state, and each slot's state advances by
  its rows. A slot whose rows start at position 0 starts from a zero
  state inside the step, so admission, preemption and re-prefill clear
  nothing on the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..inference.kv_cache import PAD_POSITION

_HI = jax.lax.Precision.HIGHEST


def log_decay(num_heads: int) -> jax.Array:
    """``log lambda_h = -2^(-8 h / H)``, ``h = 1..H`` (the Lightning
    Attention slopes: the same in every layer, not learned)."""
    h = jnp.arange(1, num_heads + 1, dtype=jnp.float32)
    return -jnp.exp2(-8.0 * h / num_heads)


def lightning_attention_full(q: jax.Array, k: jax.Array, v: jax.Array,
                             scale: float, chunk: int = 128) -> jax.Array:
    """``q, k, v [B, S, H, D]`` at positions ``0..S-1`` -> ``[B, S, H, D]``
    float32."""
    b, s, h, d = q.shape
    c = min(chunk, s)
    pad = -s % c
    q, k, v = (jnp.pad(x.astype(jnp.float32),
                       ((0, 0), (0, pad), (0, 0), (0, 0)))
               .reshape(b, -1, c, h, d).swapaxes(0, 1) for x in (q, k, v))
    ld = log_decay(h)                                        # [H]
    i = jnp.arange(c)
    gap = i[:, None] - i[None, :]
    within = jnp.where(gap >= 0,
                       jnp.exp(ld[:, None, None] * jnp.maximum(gap, 0)),
                       0.0)                                  # [H, C, C]
    carried = jnp.exp(ld[None, :] * (i[:, None] + 1.0))      # [C, H]
    to_end = jnp.exp(ld[None, :] * (c - 1.0 - i[:, None]))   # [C, H]

    def step(state, qkv):
        qc, kc, vc = qkv                                     # [B, C, H, D]
        scores = jnp.einsum("bihd,bjhd->bhij", qc, kc, precision=_HI)
        out = jnp.einsum("bhij,bjhe->bihe", scores * within, vc,
                         precision=_HI)
        out += jnp.einsum("bihd,bhde->bihe", qc * carried[None, :, :, None],
                          state, precision=_HI)
        state = (jnp.exp(ld * c)[None, :, None, None] * state
                 + jnp.einsum("bjhd,bjhe->bhde",
                              kc * to_end[None, :, :, None], vc,
                              precision=_HI))
        return state, out

    _, out = jax.lax.scan(step, jnp.zeros((b, h, d, d), jnp.float32),
                          (q, k, v))
    return out.swapaxes(0, 1).reshape(b, -1, h, d)[:, :s] * scale


def lightning_attention_packed(q: jax.Array, k: jax.Array, v: jax.Array,
                               state: jax.Array, layer, slot_ids: jax.Array,
                               positions: jax.Array, scale: float):
    """One packed step of one layer. ``q, k, v [T, H, D]``; ``state [L, H,
    J, D, D]`` float32, the stack of every lightning layer's per-slot
    states, read and written at ``layer``: ``state[l, h, j]`` is slot
    ``j``'s ``S^T`` (``[e, d]``: value index before key index), heads
    before slots, which is how the step's matmuls read and write it (kept
    as ``[J, H, d, e]`` the compiler changed the whole stack's layout on
    the way into the layer loops and back, every step). ``slot_ids [T]``
    (a row outside ``0..J-1`` is padding), ``positions [T]`` (a slot's
    rows are consecutive positions). Returns ``(out [T, H, D] float32,
    state)``:

    ``o_t = q_t (lambda^(t - t0 + 1) S_slot + sum over rows s <= t of the
    same slot of lambda^(t - s) k_s^T v_s)``, ``t0`` the slot's first
    position in the step, ``S_slot`` zero where ``t0 == 0``."""
    t, h, d = q.shape
    slots = state.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    ld = log_decay(h)
    real = (positions < PAD_POSITION) & (slot_ids >= 0) & (slot_ids < slots)
    mine = (slot_ids[:, None] == jnp.arange(slots)[None, :]) & real[:, None]
    first = jnp.min(jnp.where(mine, positions[:, None], PAD_POSITION), 0)
    last = jnp.max(jnp.where(mine, positions[:, None], -1), 0)   # [J]
    rows = jnp.where(last >= 0, last - first + 1, 0)
    old = jnp.where((first == 0)[None, :, None, None], 0.0,
                    jax.lax.dynamic_index_in_dim(state, layer, 0, False))
    onehot = mine.astype(jnp.float32)                            # [T, J]
    slot = jnp.clip(slot_ids, 0, slots - 1)
    since = jnp.where(real, positions - first[slot] + 1, 0)      # [T]
    until = jnp.where(real, last[slot] - positions, 0)
    # the slots' carried states: every row against every slot's state,
    # its own picked by the one-hot (a gather of a state a row would hold
    # 64 KiB x H a row)
    qd = q * jnp.exp(ld[None, :] * since[:, None])[:, :, None]
    carried = jnp.einsum(
        "tj,tjhe->the", onehot,
        jnp.einsum("thd,hjed->tjhe", qd, old, precision=_HI),
        precision=_HI)
    gap = positions[:, None] - positions[None, :]
    same = (real[:, None] & real[None, :] & (gap >= 0)
            & (slot_ids[:, None] == slot_ids[None, :]))
    within = jnp.where(
        same[None], jnp.exp(ld[:, None, None] * jnp.where(same, gap, 0)),
        0.0)                                                     # [H, T, T]
    scores = jnp.einsum("thd,shd->hts", q, k, precision=_HI)
    out = carried + jnp.einsum("hts,she->the", scores * within, v,
                               precision=_HI)
    kd = k * jnp.exp(ld[None, :] * until[:, None])[:, :, None]
    new = (jnp.exp(ld[:, None] * rows[None, :])[:, :, None, None] * old
           + jnp.einsum("sjhd,she->hjed",
                        onehot[:, :, None, None] * kd[:, None], v,
                        precision=_HI))
    return out * scale, jax.lax.dynamic_update_index_in_dim(
        state, new, layer, 0)
