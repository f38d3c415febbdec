"""parallel/ep_dispatch: ring == monolithic bitwise, fp32 and quantized.

Pins the dispatch layer's two contracts (module docstring of
`parallel/ep_dispatch.py`):

* the decomposed `ppermute` ring and the monolithic collective deliver
  bitwise-identical chunks / combined shards — fp32 AND int8, forward
  and (through the custom-vjp duals) backward;
* the fp32 paths reduce exactly like the raw collectives they replace
  (`all_gather` slices / `psum_scatter` of the destination-ordered
  concat), so turning the knob on cannot move training numerics.

Plus the layer-level consequence on `ExpertMLPs`: the fp32 ring is
bitwise the monolithic EP baseline, and int8 ring == int8 monolithic,
forward and every gradient leaf.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta
from jax import lax
from jax.sharding import PartitionSpec as P

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu.modules import glu
from neuronx_distributed_tpu.modules.moe.expert_mlps import ExpertMLPs
from neuronx_distributed_tpu.parallel import comm
from neuronx_distributed_tpu.parallel import ep_dispatch as epd
from neuronx_distributed_tpu.parallel import mesh as ps

N = 4
T, H = 8, 64


def _ep_mesh():
    nxd.neuronx_distributed_config(expert_parallel_size=N)
    return ps.get_expert_mesh()


def _wire(name):
    return None if name == "fp32" else epd.wire_config(name)


def _run_gather(em, x, wire, overlap):
    def f(xs):
        return epd.gather_token_chunks(xs, "ep", wire=wire, overlap=overlap)
    return jax.jit(ps.shard_map(
        f, em, in_specs=P("ep", None),
        out_specs=tuple(P("ep", None) for _ in range(N))))(x)


def _run_combine(em, ys_global, wire, overlap):
    def f(ysl):
        ys = tuple(ysl[t] for t in range(N))
        return epd.combine_token_chunks(ys, "ep", wire=wire, overlap=overlap)
    return jax.jit(ps.shard_map(f, em, in_specs=P(None, "ep", None),
                                out_specs=P("ep", None)))(ys_global)


@pytest.mark.parametrize("wire_name", ["fp32", "int8"])
def test_gather_ring_equals_monolithic_bitwise(wire_name):
    em = _ep_mesh()
    x = jax.random.normal(jax.random.key(0), (N * T, H), jnp.float32)
    ring = _run_gather(em, x, _wire(wire_name), True)
    mono = _run_gather(em, x, _wire(wire_name), False)
    for a, b in zip(ring, mono):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gather_fp32_equals_all_gather_slices():
    em = _ep_mesh()
    x = jax.random.normal(jax.random.key(0), (N * T, H), jnp.float32)

    def ag(xs):
        g = comm.all_gather(xs, "ep", dim=0).reshape((N, T, H))
        me = comm.combined_axis_index("ep")
        return tuple(
            lax.dynamic_index_in_dim(g, (me + t) % N, 0, keepdims=False)
            for t in range(N))

    ref = jax.jit(ps.shard_map(
        ag, em, in_specs=P("ep", None),
        out_specs=tuple(P("ep", None) for _ in range(N))))(x)
    for overlap in (True, False):
        got = _run_gather(em, x, None, overlap)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("wire_name", ["fp32", "int8"])
def test_combine_ring_equals_monolithic_bitwise(wire_name):
    em = _ep_mesh()
    ys = jax.random.normal(jax.random.key(1), (N, N * T, H), jnp.float32)
    ring = _run_combine(em, ys, _wire(wire_name), True)
    mono = _run_combine(em, ys, _wire(wire_name), False)
    np.testing.assert_array_equal(np.asarray(ring), np.asarray(mono))


def test_combine_fp32_equals_psum_scatter():
    em = _ep_mesh()
    ys = jax.random.normal(jax.random.key(1), (N, N * T, H), jnp.float32)

    def rs(ysl):
        me = comm.combined_axis_index("ep")
        stacked = jnp.stack(tuple(ysl[t] for t in range(N)))
        dest = jnp.roll(stacked, shift=me, axis=0).reshape(N * T, H)
        return comm.reduce_scatter(dest, "ep", dim=0)

    ref = jax.jit(ps.shard_map(rs, em, in_specs=P(None, "ep", None),
                               out_specs=P("ep", None)))(ys)
    for overlap in (True, False):
        got = _run_combine(em, ys, None, overlap)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("wire_name", ["fp32", "int8"])
def test_gather_backward_ring_equals_monolithic(wire_name):
    # gather's custom-vjp backward is the chunked combine of cotangents
    em = _ep_mesh()
    x = jax.random.normal(jax.random.key(0), (N * T, H), jnp.float32)

    def run(overlap):
        def loss(xs):
            chunks = epd.gather_token_chunks(
                xs, "ep", wire=_wire(wire_name), overlap=overlap)
            return sum(jnp.sum(jnp.tanh(c) * (t + 1))
                       for t, c in enumerate(chunks))
        return jax.jit(ps.shard_map(
            lambda xs: jax.grad(loss)(xs), em,
            in_specs=P("ep", None), out_specs=P("ep", None)))(x)

    np.testing.assert_array_equal(np.asarray(run(True)),
                                  np.asarray(run(False)))


@pytest.mark.parametrize("wire_name", ["fp32", "int8"])
def test_combine_backward_ring_equals_monolithic(wire_name):
    # combine's custom-vjp backward is the chunked gather of cotangents
    em = _ep_mesh()
    ys = jax.random.normal(jax.random.key(1), (N, N * T, H), jnp.float32)

    def run(overlap):
        def loss(ysl):
            y = epd.combine_token_chunks(
                tuple(ysl[t] for t in range(N)), "ep",
                wire=_wire(wire_name), overlap=overlap)
            return jnp.sum(jnp.tanh(y))
        return jax.jit(ps.shard_map(
            lambda ysl: jax.grad(loss)(ysl), em,
            in_specs=P(None, "ep", None),
            out_specs=P(None, "ep", None)))(ys)

    np.testing.assert_array_equal(np.asarray(run(True)),
                                  np.asarray(run(False)))


def test_unbound_axis_is_identity():
    # plain jit, no mesh: gather returns (x,), combine returns ys[0] —
    # the same code runs on a 1-device / GSPMD trace untouched
    x = jax.random.normal(jax.random.key(2), (T, H), jnp.float32)
    chunks = jax.jit(lambda a: epd.gather_token_chunks(a, "ep"))(x)
    assert len(chunks) == 1
    np.testing.assert_array_equal(np.asarray(chunks[0]), np.asarray(x))
    y = jax.jit(lambda a: epd.combine_token_chunks(
        (a,), "ep", wire=epd.wire_config("int8"), overlap=True))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_overlap_engaged_predicate():
    # outside shard_map the axis is unbound -> never engages
    assert epd.overlap_engaged(None, "ep") is False
    assert epd.overlap_engaged(True, "ep") is False
    em = _ep_mesh()

    def probe(knob):
        def f(x):
            return jnp.float32(epd.overlap_engaged(knob, "ep")) + x * 0
        return float(jax.jit(ps.shard_map(
            f, em, in_specs=P(), out_specs=P()))(jnp.float32(0)))

    assert probe(None) == 1.0      # auto: N == MIN_AUTO_AXIS_SIZE == 4
    assert probe(True) == 1.0
    assert probe(False) == 0.0


# ---------------------------------------------------------------------------
# layer-level: ExpertMLPs blockwise-EP over the dispatch module
# ---------------------------------------------------------------------------

_PSPEC = {"params": {**dict.fromkeys(glu.EXPERTS, P("ep", None, None)),
                     "down": P("ep", None, None)}}


def _mlp(wire, overlap):
    return ExpertMLPs(num_experts=4, hidden_size=16, intermediate_size=32,
                      top_k=2, dispatch_mode="blockwise", block_size=8,
                      block_i=32, dtype=jnp.float32,
                      ep_wire_dtype=wire, ep_overlap=overlap)


def _mlp_problem():
    em = _ep_mesh()
    x = jax.random.normal(jax.random.key(0), (32, 16))
    gates = jax.nn.softmax(
        jax.random.normal(jax.random.key(3), (32, 2)), axis=-1)
    idx = jax.random.randint(jax.random.key(1), (32, 2), 0, 4)
    m0 = _mlp("fp32", False)
    params = meta.unbox(m0.init(jax.random.key(2), x, gates, idx))
    return em, m0, params, x, gates, idx


def _mlp_fwd(em, m, params, x, gates, idx):
    def fwd(p, a, g, i):
        return m.apply(p, a, g, i)
    return jax.jit(ps.shard_map(
        fwd, em,
        in_specs=(_PSPEC, P("ep", None), P("ep", None), P("ep", None)),
        out_specs=(P("ep", None), P())))(params, x, gates, idx)[0]


def _mlp_grads(em, m, params, x, gates, idx):
    def loss(p, a, g, i):
        y, _ = m.apply(p, a, g, i)
        return jnp.sum(jnp.tanh(y))
    return jax.jit(ps.shard_map(
        lambda p, a, g, i: jax.grad(loss, argnums=(0, 1, 2))(p, a, g, i),
        em,
        in_specs=(_PSPEC, P("ep", None), P("ep", None), P("ep", None)),
        out_specs=(_PSPEC, P("ep", None), P("ep", None))))(
            params, x, gates, idx)


def _leaves(g):
    return [*(g[0]["params"][name] for name in glu.EXPERTS),
            g[0]["params"]["down"], g[1], g[2]]


def test_expert_mlps_fp32_ring_bitwise_vs_baseline():
    em, m0, params, x, gates, idx = _mlp_problem()
    y_base = _mlp_fwd(em, m0, params, x, gates, idx)
    y_ring = _mlp_fwd(em, _mlp("fp32", True), params, x, gates, idx)
    np.testing.assert_array_equal(np.asarray(y_ring), np.asarray(y_base))
    # ... and the unsharded dense forward agrees to tolerance (the EP
    # split is a reduction-order change, not a numeric one)
    dense, _ = m0.apply(params, x, gates, idx)
    np.testing.assert_allclose(np.asarray(y_base), np.asarray(dense),
                               atol=2e-5)


def test_expert_mlps_int8_ring_bitwise_vs_monolithic():
    em, m0, params, x, gates, idx = _mlp_problem()
    y_ring = _mlp_fwd(em, _mlp("int8", True), params, x, gates, idx)
    y_mono = _mlp_fwd(em, _mlp("int8", False), params, x, gates, idx)
    np.testing.assert_array_equal(np.asarray(y_ring), np.asarray(y_mono))
    # int8 stays close to the fp32 baseline (quantization noise only)
    y_base = _mlp_fwd(em, m0, params, x, gates, idx)
    np.testing.assert_allclose(np.asarray(y_ring), np.asarray(y_base),
                               atol=0.05, rtol=0.05)


def test_expert_mlps_grads_ring_vs_monolithic():
    em, m0, params, x, gates, idx = _mlp_problem()
    g_base = _mlp_grads(em, m0, params, x, gates, idx)
    g_ring = _mlp_grads(em, _mlp("fp32", True), params, x, gates, idx)
    # fp32 ring: every gradient leaf matches the baseline to fp32
    # round-off (dx/dgates are bitwise; dW crosses a different
    # contraction split)
    for a, b in zip(_leaves(g_base), _leaves(g_ring)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(g_base[1]),
                                  np.asarray(g_ring[1]))
    np.testing.assert_array_equal(np.asarray(g_base[2]),
                                  np.asarray(g_ring[2]))
    # int8: ring vs monolithic is bitwise for EVERY leaf — same codec
    # round-trips, same ordered sums
    g8r = _mlp_grads(em, _mlp("int8", True), params, x, gates, idx)
    g8m = _mlp_grads(em, _mlp("int8", False), params, x, gates, idx)
    for a, b in zip(_leaves(g8r), _leaves(g8m)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
