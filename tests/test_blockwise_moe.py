"""ops/blockwise_moe kernel parity: interpret-mode Pallas vs jnp reference.

The grouped-GLU kernel's contract is *bit-exactness* against the pure-jnp
reference (`grouped_glu_reference`) — forward and every gradient — so the
CPU auto-dispatch fallback and the TPU kernel are the same numerics. The
interpret-mode hook (`force_pallas=True` off-TPU) runs the real kernel
body through the Pallas interpreter, which is what these tests pin.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from neuronx_distributed_tpu.modules.moe import blockwise as bw
from neuronx_distributed_tpu.ops import blockwise_moe as ops_bw


def _problem(T=16, H=8, I=16, E=4, K=2, B=8, seed=0, sentinel_empty=False,
             idx=None):
    """Block-scattered inputs + weights for the grouped GLU."""
    ks = jax.random.split(jax.random.key(seed), 4)
    if idx is None:
        idx = jax.random.randint(ks[0], (T, K), 0, E)
    x = jax.random.normal(ks[1], (T, H), jnp.float32)
    order, src, dest, be, num_blocks, padded = bw.compute_block_metadata(
        idx, E, B, sentinel_empty=sentinel_empty)
    xs = bw.scatter_to_blocks(x, src, dest, padded)
    # gate and up: the two operands, in the stored form (modules/glu.py)
    gate_up = tuple(jax.random.normal(ks[2], (2, E, H, I), jnp.float32) * 0.3)
    down = jax.random.normal(ks[3], (E, I, H), jnp.float32) * 0.3
    return xs, gate_up, down, be, B, num_blocks


@pytest.mark.parametrize("bi_frac", [1, 2])
def test_grouped_glu_interpret_bitwise_forward(bi_frac):
    xs, gate_up, down, be, B, _ = _problem()
    bi = gate_up[0].shape[-1] // bi_frac  # exercise intermediate-dim tiling
    y_k = ops_bw.grouped_glu(xs, *gate_up, down, be, B, bi,
                             force_pallas=True)
    y_r = ops_bw.grouped_glu(xs, *gate_up, down, be, B, bi,
                             force_pallas=False)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))
    # force_pallas=False is literally the reference
    y_ref = ops_bw.grouped_glu_reference(xs, *gate_up, down, be, B, bi)
    np.testing.assert_array_equal(np.asarray(y_r), np.asarray(y_ref))


def test_grouped_glu_interpret_bitwise_grads():
    xs, gate_up, down, be, B, _ = _problem()
    bi = gate_up[0].shape[-1] // 2
    cot = jax.random.normal(jax.random.key(9), xs.shape, jnp.float32)

    def loss(force):
        def f(xs_, g_, u_, dn_):
            y = ops_bw.grouped_glu(xs_, g_, u_, dn_, be, B, bi,
                                   force_pallas=force)
            return jnp.sum(y * cot)  # non-uniform cotangent
        return jax.grad(f, argnums=(0, 1, 2, 3))(xs, *gate_up, down)

    for g_k, g_r in zip(loss(True), loss(False)):
        np.testing.assert_array_equal(np.asarray(g_k), np.asarray(g_r))


def test_grouped_glu_decode_interpret_bitwise_with_sentinels():
    # skew routing so some experts see zero tokens -> sentinel blocks
    T, K, E = 8, 1, 4
    idx = jnp.zeros((T, K), jnp.int32).at[0, 0].set(2)
    xs, gate_up, down, be, B, _ = _problem(T=T, K=K, E=E, B=4,
                                           sentinel_empty=True, idx=idx)
    assert bool(jnp.any(be >= E)), "fixture must produce sentinel blocks"
    bi = gate_up[0].shape[-1]
    y_k = ops_bw.grouped_glu_decode(xs, *gate_up, down, be, B, bi,
                                    force_pallas=True)
    y_r = ops_bw.grouped_glu_decode(xs, *gate_up, down, be, B, bi,
                                    force_pallas=False)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))
    # sentinel blocks' rows are hard zero in both impls
    sent = np.repeat(np.asarray(be) >= E, B)
    assert np.all(np.asarray(y_k)[sent] == 0.0)


def test_cpu_auto_dispatch_is_the_reference():
    assert jax.default_backend() == "cpu"
    assert ops_bw.use_pallas(None) is False
    assert ops_bw.use_pallas(True) is True
    xs, gate_up, down, be, B, _ = _problem(seed=3)
    bi = gate_up[0].shape[-1]
    y_auto = ops_bw.grouped_glu(xs, *gate_up, down, be, B, bi)
    y_ref = ops_bw.grouped_glu_reference(xs, *gate_up, down, be, B, bi)
    np.testing.assert_array_equal(np.asarray(y_auto), np.asarray(y_ref))


def test_every_real_expert_owns_a_block_training_metadata():
    # training metadata (sentinel_empty=False): even a zero-token expert
    # owns >= 1 block, the dW zero-init contract of the backward kernel
    idx = jnp.zeros((8, 1), jnp.int32)  # all tokens -> expert 0
    _, _, _, be, _, _ = bw.compute_block_metadata(idx, 4, 4)
    owned = set(np.asarray(be).tolist())
    assert {0, 1, 2, 3} <= owned


# -- the layers' stacks: [L, E, H, I] and the layer's index -------------------

def _stacked_problem(decode: bool, depth=3):
    """A stack of ``depth`` banks that differ, with the blocks the case
    needs: sentinel blocks (``sentinel_empty`` metadata: experts 1 and 3
    take no row) and a run of two blocks of expert 0."""
    T, K, E, B = 12, 1, 4, 4
    idx = jnp.zeros((T, K), jnp.int32).at[0, 0].set(2)   # 11 rows on expert 0
    xs, _, _, be, B, _ = _problem(T=T, K=K, E=E, B=B, sentinel_empty=True,
                                  idx=idx)
    be_np = np.asarray(be)
    assert np.any(be_np >= E), "the fixture must produce sentinel blocks"
    assert np.any((be_np[1:] == be_np[:-1]) & (be_np[1:] < E)), \
        "the fixture must produce a run of two blocks of one expert"
    ks = jax.random.split(jax.random.key(11), 3)
    H, I = xs.shape[1], 16
    gate, up = (jax.random.normal(k, (depth, E, H, I), jnp.float32) * 0.3
                for k in ks[:2])
    down = jax.random.normal(ks[2], (depth, E, I, H), jnp.float32) * 0.3
    fn = ops_bw.grouped_glu_decode if decode else ops_bw.grouped_glu
    return fn, xs, (gate, up, down), be, B, I // 2


@pytest.mark.parametrize("force_pallas", [True, False],
                         ids=["interpret", "reference"])
@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("decode", [False, True],
                         ids=["grouped_glu", "grouped_glu_decode"])
def test_stacked_entry_is_the_bank_entry_on_the_layers_slice(
        decode, layer, force_pallas):
    """``fn(xs, stacks, ..., layer=l)`` is ``fn(xs, stacks[l], ...)`` to
    the bit, kernel (interpret mode) and reference alike, for the first, a
    middle and the last layer, the index traced (as a scan's is)."""
    fn, xs, stacks, be, B, bi = _stacked_problem(decode)
    stacked = jax.jit(lambda l: fn(xs, *stacks, be, B, bi,
                                   force_pallas=force_pallas, layer=l))
    y_s = stacked(jnp.int32(layer))
    y_b = fn(xs, *(w[layer] for w in stacks), be, B, bi,
             force_pallas=force_pallas)
    np.testing.assert_array_equal(np.asarray(y_s), np.asarray(y_b))
    # the layers differ, so a layer read from the wrong place would show
    other = fn(xs, *(w[(layer + 1) % 3] for w in stacks), be, B, bi,
               force_pallas=force_pallas)
    assert not np.array_equal(np.asarray(y_s), np.asarray(other))
    sent = np.repeat(np.asarray(be) >= stacks[0].shape[1], B)
    assert np.all(np.asarray(y_s)[sent] == 0.0)


@pytest.mark.parametrize("force_pallas", [True, False],
                         ids=["interpret", "reference"])
@pytest.mark.parametrize("decode", [False, True],
                         ids=["grouped_glu", "grouped_glu_decode"])
def test_stacked_entry_refuses_to_be_differentiated(decode, force_pallas):
    fn, xs, stacks, be, B, bi = _stacked_problem(decode)

    def loss(gate):
        return jnp.sum(fn(xs, gate, *stacks[1:], be, B, bi,
                          force_pallas=force_pallas, layer=1))

    with pytest.raises(TypeError, match="forward-only"):
        jax.grad(loss)(stacks[0])


def test_stacked_entry_refuses_a_bank():
    fn, xs, stacks, be, B, bi = _stacked_problem(False)
    with pytest.raises(ValueError, match=r"\[L, E, H, I\]"):
        fn(xs, *(w[0] for w in stacks), be, B, bi, layer=0)
