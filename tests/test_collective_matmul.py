"""Decomposed collective-matmul tests (docs/tp_overlap.md).

The contract under test: the ppermute-ring decomposition is **bit-exact in
fp32** against the ascending-rank sum of the per-block partial products — at
every supported tp size, uni- and bidirectional — because it buffers by
source rank and adds in that order. The monolithic collective multiplies the
whole sequence at once, and a backend's product of a row block need not be
the rows of the whole product to the last bit (the CPU backend's is not for
blocks of 2 rows), nor does ``psum`` promise an order: it is held to 2 ulp
forward and to rounding in the gradients; the gather forms move data only
and stay bit-exact. Non-tileable
shapes silently fall back to the monolithic path (never an error), the
``overlap_comm`` knob resolves statically from shapes (no recompiles), and
the sequence-parallel mappings fail with named shapes when a sequence
cannot tile.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.ops import collective_matmul as cm
from neuronx_distributed_tpu.parallel import mappings, mesh as ps


def _tp_mesh(tp):
    return ps.initialize_model_parallel(tensor_model_parallel_size=tp)


def _jit_shard(f, mesh, in_specs, out_specs):
    return jax.jit(ps.shard_map(f, mesh, in_specs=in_specs,
                                out_specs=out_specs))


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _ordered_all_reduce(x, w, tp):
    """The sum the ring promises: every rank's partial product, computed a
    destination's block of rows at a time as the ring does, gathered by
    source rank and added in ascending order."""
    l = x.shape[1] // tp
    y = jnp.concatenate(
        [jax.lax.dynamic_slice_in_dim(x, j * l, l, axis=1) @ w
         for j in range(tp)], axis=1)
    parts = jax.lax.all_gather(y, "tp")
    acc = parts[0]
    for r in range(1, tp):
        acc = acc + parts[r]
    return acc


def _assert_ring_matches_psum(ring, mono):
    """``(y, grads)`` of the ring against the monolithic collective: the
    forward sum to 2 ulp of its largest value (the whole product's rows and
    ``psum``'s order are the backend's own, and a sum that cancels keeps
    its terms' rounding), the gradients, which see that last bit through
    ``cos(y)``, to rounding."""
    y, y_mono = np.asarray(ring[0]), np.asarray(mono[0])
    np.testing.assert_allclose(
        y, y_mono, rtol=0,
        atol=2 * np.finfo(np.float32).eps * np.abs(y_mono).max())
    for g, h in zip(jax.tree_util.tree_leaves(ring[1:]),
                    jax.tree_util.tree_leaves(mono[1:])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(h), rtol=1e-5,
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# op-level bit-exactness: decomposed vs monolithic, forward + backward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tp", [2, 4, 8])
def test_all_gather_matmul_bit_exact_fwd_bwd(tp):
    """SP-entry column linear: gather(x, seq) @ w — value and both grads
    identical to the last bit at every supported axis size (bidi auto-
    engages at tp>=4, so this covers both ring variants)."""
    mesh = _tp_mesh(tp)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 16, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(8, 5 * tp).astype(np.float32))

    def run(impl):
        def f(xl, wl):
            def loss(xv, wv):
                y = cm.all_gather_matmul(xv, wv, "tp", 1, impl=impl)
                return jnp.sum(jnp.sin(y)), y

            (_, y), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(xl, wl)
            return y, grads

        return _jit_shard(
            f, mesh,
            (P(None, "tp", None), P(None, "tp")),
            ((P(None, None, "tp")),
             (P(None, "tp", None), P(None, "tp"))))(x, w)

    _assert_trees_equal(run("decomposed"), run("monolithic"))


@pytest.mark.parametrize("tp", [2, 4, 8])
def test_matmul_reduce_scatter_bit_exact_fwd_bwd(tp):
    """SP-exit row linear: reduce_scatter(x @ w, seq) — the buffered
    ascending-rank sum is exact against the ordered sum of the per-block
    partials, and within 2 ulp of ``psum_scatter`` of the whole product."""
    mesh = _tp_mesh(tp)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 16, 4 * tp).astype(np.float32))
    w = jnp.asarray(rng.randn(4 * tp, 6).astype(np.float32))

    def run(impl):
        def f(xl, wl):
            def loss(xv, wv):
                y = cm.matmul_reduce_scatter(xv, wv, "tp", 1, impl=impl)
                return jnp.sum(jnp.sin(y)), y

            (_, y), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(xl, wl)
            return y, grads

        return _jit_shard(
            f, mesh,
            (P(None, None, "tp"), P("tp", None)),
            ((P(None, "tp", None)),
             (P(None, None, "tp"), P("tp", None))))(x, w)

    def ordered(xl, wl):
        y = _ordered_all_reduce(xl, wl, tp)
        l = y.shape[1] // tp
        return jax.lax.dynamic_slice_in_dim(
            y, jax.lax.axis_index("tp") * l, l, axis=1)

    ring = run("decomposed")
    _assert_trees_equal(ring[0], _jit_shard(
        ordered, mesh, (P(None, None, "tp"), P("tp", None)),
        P(None, "tp", None))(x, w))
    _assert_ring_matches_psum(ring, run("monolithic"))


@pytest.mark.parametrize("op", ["matmul_all_reduce", "copy_matmul"])
def test_plain_tp_ops_bit_exact_fwd_bwd(op):
    """The non-SP pair: matmul_all_reduce (row exit) decomposes its forward
    as RS+AG; copy_matmul (column entry) decomposes only its backward dx."""
    tp = 4
    mesh = _tp_mesh(tp)
    rng = np.random.RandomState(2)
    if op == "matmul_all_reduce":
        x = jnp.asarray(rng.randn(2, 8, 4 * tp).astype(np.float32))
        w = jnp.asarray(rng.randn(4 * tp, 6).astype(np.float32))
        in_specs = (P(None, None, "tp"), P("tp", None))
        grad_specs = in_specs
        y_spec = P(None, None, None)
        fn = cm.matmul_all_reduce
    else:
        x = jnp.asarray(rng.randn(2, 8, 8).astype(np.float32))
        w = jnp.asarray(rng.randn(8, 5 * tp).astype(np.float32))
        in_specs = (P(None, None, None), P(None, "tp"))
        grad_specs = (P(None, None, None), P(None, "tp"))
        y_spec = P(None, None, "tp")
        fn = cm.copy_matmul

    def run(impl):
        def f(xl, wl):
            def loss(xv, wv):
                y = fn(xv, wv, "tp", 1, impl=impl)
                return jnp.sum(jnp.sin(y)), y

            (_, y), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(xl, wl)
            return y, grads

        out = _jit_shard(f, mesh, in_specs, (y_spec, grad_specs))(x, w)
        if op == "copy_matmul":
            # dx cotangents per rank differ (each rank's local loss sees
            # its own kernel slice); sum them like the trainer's grad psum
            # would before comparing
            (y, (dx, dw)) = out
            return y, dx, dw
        return out

    if op == "copy_matmul":
        _assert_trees_equal(run("decomposed"), run("monolithic"))
        return
    ring = run("decomposed")
    _assert_trees_equal(ring[0], _jit_shard(
        lambda xl, wl: _ordered_all_reduce(xl, wl, tp), mesh, in_specs,
        y_spec)(x, w))
    _assert_ring_matches_psum(ring, run("monolithic"))


@pytest.mark.parametrize("bidi", [False, None], ids=["uni", "auto"])
@pytest.mark.parametrize("tp,rows", [(2, 8), (4, 16), (4, 12), (8, 16)])
def test_forwarding_ring_below_32_bits(tp, rows, bidi):
    """bf16 on a full-precision wire has no order to keep: the ring adds
    as it forwards, over neighbour hops alone and in two half-block streams
    where the axis is even and at least 4 (``rows`` 12 at tp=4: blocks of 3
    rows do not halve, one stream). Forward and both gradients equal the
    monolithic collective's to bf16 rounding."""
    mesh = _tp_mesh(tp)
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, rows, 4 * tp), jnp.bfloat16)
    w = jnp.asarray(rng.randn(4 * tp, 6), jnp.bfloat16)
    assert cm._sums_in_transit(x.dtype, None)
    assert not cm._sums_in_transit(jnp.float32, None)

    def run(fn, impl, y_spec):
        def f(xl, wl):
            def loss(xv, wv):
                y = fn(xv, wv, "tp", 1, impl=impl, bidirectional=bidi)
                return jnp.sum(jnp.sin(y.astype(jnp.float32))), y

            (_, y), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(xl, wl)
            return y, grads

        specs = (P(None, None, "tp"), P("tp", None))
        text = jax.jit(ps.shard_map(f, mesh, in_specs=specs, out_specs=(
            y_spec, specs))).lower(x, w).as_text()
        return _jit_shard(f, mesh, specs, (y_spec, specs))(x, w), text

    for fn, y_spec in ((cm.matmul_reduce_scatter, P(None, "tp", None)),
                       (cm.matmul_all_reduce, P(None, None, None))):
        ring, text = run(fn, "decomposed", y_spec)
        mono, _ = run(fn, "monolithic", y_spec)
        # every hop goes to a neighbour
        pairs = set(re.findall(r"source_target_pairs = dense<\[\[(\d+), "
                               r"(\d+)\]", text))
        assert pairs and all((int(b) - int(a)) % tp in (1, tp - 1)
                             for a, b in pairs), pairs
        for g, h in zip(jax.tree_util.tree_leaves(ring),
                        jax.tree_util.tree_leaves(mono)):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(h, np.float32),
                rtol=0, atol=0.03 * np.abs(np.asarray(h, np.float32)).max())


@pytest.mark.parametrize("bidi", [False, True])
def test_bidirectional_ring_matches_unidirectional(bidi):
    """Two-stream rings (even tp) are order-independent thanks to the
    buffered ascending sum: forcing bidi on/off never changes a bit."""
    tp = 4
    mesh = _tp_mesh(tp)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 16, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(8, 5 * tp).astype(np.float32))

    def run(bidirectional):
        def f(xl, wl):
            return cm.all_gather_matmul(xl, wl, "tp", 1, impl="decomposed",
                                        bidirectional=bidirectional)

        return _jit_shard(f, mesh, (P(None, "tp", None), P(None, "tp")),
                          P(None, None, "tp"))(x, w)

    _assert_trees_equal(run(bidi), run(None))


def test_tuple_kernels_share_one_gathered_stream():
    """The GQA entry: Q/K/V kernels ride a single gathered activation
    stream; each output matches its own monolithic gather+matmul."""
    tp = 4
    mesh = _tp_mesh(tp)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 16, 8).astype(np.float32))
    wq = jnp.asarray(rng.randn(8, 6 * tp).astype(np.float32))
    wk = jnp.asarray(rng.randn(8, 3 * tp).astype(np.float32))
    wv = jnp.asarray(rng.randn(8, 3 * tp).astype(np.float32))

    def run(impl):
        def f(xl, q, k, v):
            return cm.all_gather_matmul(xl, (q, k, v), "tp", 1, impl=impl)

        return _jit_shard(
            f, mesh,
            (P(None, "tp", None), P(None, "tp"), P(None, "tp"),
             P(None, "tp")),
            (P(None, None, "tp"),) * 3)(x, wq, wk, wv)

    _assert_trees_equal(run("decomposed"), run("monolithic"))


def _gather_ring_case(tp, dtype, bidi, kernels, wire):
    """Inputs of one all-gather ring: ``x`` sharded over the sequence,
    ``kernels`` column-parallel kernels of unlike widths, the wire."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(2, 4 * tp, 32), dtype)
    ws = tuple(jnp.asarray(rng.randn(32, f * tp), dtype)
               for f in (6, 3, 5)[:kernels])
    return x, ws, cm.wire_config(wire, 16)


@pytest.mark.parametrize("wire", [None, "int8"], ids=["full_wire", "int8"])
@pytest.mark.parametrize("kernels", [1, 3], ids=["one_kernel", "three"])
@pytest.mark.parametrize("bidi", [False, True], ids=["one_stream", "two"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("tp", [2, 4])
def test_the_gather_ring_places_every_product_where_the_gather_has_it(
        tp, dtype, bidi, kernels, wire):
    """The ring writes each block's product once, into its own index of a
    block dimension: the outputs and every kernel's gradient (the gather
    forms) are the monolithic gather's to the bit, whatever the dtype, the
    streams, the kernels that share the ring and the wire. ``x``'s gradient
    is the dual, a reduce-scatter: the bit in float32, rounding below it,
    where that ring adds as it forwards."""
    mesh = _tp_mesh(tp)
    x, ws, wire = _gather_ring_case(tp, dtype, bidi, kernels, wire)

    def run(impl):
        def f(xl, wl):
            def loss(xv, wv):
                ys = cm.all_gather_matmul(xv, wv, "tp", 1, impl=impl,
                                          bidirectional=bidi, wire=wire)
                return sum(jnp.sum(jnp.sin(y.astype(jnp.float32)))
                           for y in ys), ys

            (_, ys), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(xl, wl)
            return ys, grads

        w_specs = (P(None, "tp"),) * kernels
        return _jit_shard(
            f, mesh, (P(None, "tp", None), w_specs),
            ((P(None, None, "tp"),) * kernels,
             (P(None, "tp", None), w_specs)))(x, ws)

    (ys, (dx, dws)), (ys_mono, (dx_mono, dws_mono)) = (
        run("decomposed"), run("monolithic"))
    assert ys[0].shape == (2, 4 * tp, 6 * tp)
    _assert_trees_equal((ys, dws), (ys_mono, dws_mono))
    if dtype == jnp.float32:
        _assert_trees_equal(dx, dx_mono)
    else:
        want = np.asarray(dx_mono, np.float32)
        np.testing.assert_allclose(np.asarray(dx, np.float32), want, rtol=0,
                                   atol=0.03 * np.abs(want).max())


@pytest.mark.parametrize("bidi", [False, True], ids=["one_stream", "two"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("tp", [2, 4])
def test_a_row_exits_input_gradient_rides_the_same_ring(tp, dtype, bidi):
    """``matmul_reduce_scatter``'s backward runs the all-gather ring for
    ``dx`` (``_mm_rs_bwd``): under one cotangent it is the monolithic
    gather's to the bit, and ``dw`` with it."""
    mesh = _tp_mesh(tp)
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(2, 4 * tp, 8 * tp), dtype)
    w = jnp.asarray(rng.randn(8 * tp, 6), dtype)
    c = jnp.asarray(rng.randn(2, 4 * tp, 6), dtype)

    def run(impl):
        def f(xl, wl, cl):
            # linear in y: the cotangent is ``cl`` under either forward
            return jax.grad(lambda xv, wv: jnp.sum(
                (cm.matmul_reduce_scatter(xv, wv, "tp", 1, impl=impl,
                                          bidirectional=bidi)
                 * cl).astype(jnp.float32)), argnums=(0, 1))(xl, wl)

        specs = (P(None, None, "tp"), P("tp", None))
        return _jit_shard(f, mesh, specs + (P(None, "tp", None),),
                          specs)(x, w, c)

    _assert_trees_equal(run("decomposed"), run("monolithic"))


def test_the_gather_ring_writes_a_product_into_its_own_block():
    """The lowered ring at tp=4, three kernels: a product is one index of a
    block dimension ``[2, 4, l, f]`` and the output is that buffer
    reshaped, so the compiler's matmul writes the block where it stays. The
    parent updated rows ``src * l`` of ``[2, 4 * l, f]``, four updates a
    kernel into a zeroed output of the output's shape, and XLA copied the
    whole output at each (26 ms of the train cell's step: PERF.md)."""
    tp, kernels = 4, 3
    mesh = _tp_mesh(tp)
    x, ws, _ = _gather_ring_case(tp, jnp.float32, None, kernels, None)

    def f(xl, wl):
        return cm.all_gather_matmul(xl, wl, "tp", 1, impl="decomposed")

    w_specs = (P(None, "tp"),) * kernels
    text = jax.jit(ps.shard_map(
        f, mesh, in_specs=(P(None, "tp", None), w_specs),
        out_specs=(P(None, None, "tp"),) * kernels)).lower(x, ws).as_text()
    updates = re.findall(
        r"stablehlo\.dynamic_update_slice .*: \(tensor<([\dx]+)xf32>, "
        r"tensor<([\dx]+)xf32>", text)
    widths = sorted(w.shape[1] // tp for w in ws)
    # one update a product, each a whole block of its buffer
    assert sorted(updates) == sorted(
        (f"2x{tp}x4x{f}", f"2x1x4x{f}") for f in widths for _ in range(tp))
    for f in widths:
        # nothing of the output's shape is zeroed or updated in place
        assert not re.search(
            rf"(broadcast_in_dim|constant|dynamic_update_slice).*"
            rf"-> tensor<2x{4 * tp}x{f}xf32>", text)
        assert len(re.findall(
            rf"stablehlo\.reshape .*tensor<2x{tp}x4x{f}xf32>\) -> "
            rf"tensor<2x{4 * tp}x{f}xf32>", text)) == 1


# ---------------------------------------------------------------------------
# fallback + engagement resolution (static on shapes, never an error)
# ---------------------------------------------------------------------------

def test_uneven_shapes_silently_fall_back():
    """seq 6 over tp=4 cannot tile: impl='auto' must produce the monolithic
    result (not raise), and will_decompose must say so."""
    tp = 4
    mesh = _tp_mesh(tp)
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 6, 4 * tp).astype(np.float32))
    w = jnp.asarray(rng.randn(4 * tp, 6).astype(np.float32))
    seen = {}

    def run(impl):
        def f(xl, wl):
            seen["decomposes"] = cm.will_decompose(
                "auto", "tp", xl.shape, 1, needs_divisible=True)
            return cm.matmul_all_reduce(xl, wl, "tp", 1, impl=impl)

        return _jit_shard(f, mesh, (P(None, None, "tp"), P("tp", None)),
                          P(None, None, None))(x, w)

    auto = run("auto")
    assert seen["decomposes"] is False
    _assert_trees_equal(auto, run("monolithic"))


def test_overlap_engaged_resolution():
    """The knob matrix: auto needs axis >= MIN_AUTO_AXIS_SIZE; True engages
    whenever shapes tile; False and non-tileable shapes never engage."""
    mesh = _tp_mesh(2)
    seen = {}

    def f(x):
        shape = x.shape
        seen["auto_tp2"] = cm.overlap_engaged(
            None, "tp", shape, 1, needs_divisible=True)
        seen["on_tp2"] = cm.overlap_engaged(
            True, "tp", shape, 1, needs_divisible=True)
        seen["off"] = cm.overlap_engaged(
            False, "tp", shape, 1, needs_divisible=True)
        seen["uneven"] = cm.overlap_engaged(
            True, "tp", (2, 7, 8), 1, needs_divisible=True)
        seen["decode_s1"] = cm.overlap_engaged(
            True, "tp", (2, 1, 8), 1, needs_divisible=True)
        return x

    _jit_shard(f, mesh, (P(None, None, None),),
               P(None, None, None))(jnp.zeros((2, 8, 4)))
    assert seen == {"auto_tp2": False, "on_tp2": True, "off": False,
                    "uneven": False, "decode_s1": False}
    # unbound axis (plain jit / GSPMD): the mappings are identities there,
    # so the decomposition must never engage either
    assert cm.overlap_engaged(True, "tp", (2, 8, 4), 1,
                              needs_divisible=True) is False


def test_bad_impl_name_raises():
    with pytest.raises(ValueError, match="impl must be one of"):
        cm.will_decompose("fused", "tp", (2, 8, 4), 1, needs_divisible=True)


# ---------------------------------------------------------------------------
# sequence-parallel mapping entries: pointed shape errors
# ---------------------------------------------------------------------------

def test_sp_reduce_scatter_uneven_raises_pointed_error():
    mesh = _tp_mesh(4)
    x = jnp.zeros((2, 6, 8))

    def f(xv):
        return mappings.reduce_scatter_to_sequence_parallel_region(xv)

    with pytest.raises(
            ValueError,
            match=r"sequence length 6 \(dim 1\) does not divide evenly "
                  r"over mesh axis 'tp' of size 4"):
        _jit_shard(f, mesh, (P(None, None, None),),
                   P(None, "tp", None))(x)


def test_sp_reduce_scatter_uneven_raises_under_grad_too():
    """The custom_vjp fwd skips the primal body, so the named check must
    live on both paths."""
    mesh = _tp_mesh(4)
    x = jnp.zeros((2, 6, 8))

    def f(xv):
        return jax.grad(lambda t: jnp.sum(
            mappings.reduce_scatter_to_sequence_parallel_region(t)))(xv)

    with pytest.raises(ValueError, match="pad or trim the sequence"):
        _jit_shard(f, mesh, (P(None, None, None),),
                   P(None, None, None))(x)


# ---------------------------------------------------------------------------
# end-to-end: llama train step with the knob on is bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sp", [False, True])
def test_llama_train_step_overlap_parity(sp):
    """Full tiny-llama value_and_grad under shard_map TP=4: loss AND every
    gradient leaf with ``overlap_comm=True`` equal the ``False`` run to the
    last bit — the decomposition is a scheduling change, not a numeric
    one."""
    import neuronx_distributed_tpu as nxd
    from flax import linen as nn
    from flax.core import meta

    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)

    nxd.neuronx_distributed_config(tensor_parallel_size=4)
    mesh = ps.get_mesh()
    ids = jax.random.randint(jax.random.key(2), (2, 17), 0, 256)
    batch_ids, labels = ids[:, :-1], ids[:, 1:]

    def run(overlap):
        mcfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           sequence_parallel=sp, scan_layers=False,
                           tp_size=4, overlap_comm=overlap)
        model = LlamaForCausalLM(mcfg)
        boxed = model.init(jax.random.key(1), batch_ids)
        specs = nn.get_partition_spec(boxed)
        params = meta.unbox(boxed)

        def val_and_grad(p, i, l):
            return jax.value_and_grad(
                lambda q: model.apply(q, i, l, method="loss"))(p)

        loss, grads = jax.jit(ps.shard_map(
            val_and_grad, mesh,
            in_specs=(specs, P(None, None), P(None, None)),
            out_specs=(P(), specs)))(params, batch_ids, labels)
        return loss, grads

    loss_off, grads_off = run(False)
    loss_on, grads_on = run(True)
    assert float(loss_on) == float(loss_off)
    _assert_trees_equal(grads_on, grads_off)


def _engine_compile_count(tp, overlap):
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          ServingEngine)
    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)

    ps.destroy_model_parallel()
    ps.initialize_model_parallel(tensor_model_parallel_size=tp)
    cfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                      num_layers=2, tp_size=tp, overlap_comm=overlap)
    params = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    eng = ServingEngine(cfg, params, EngineConfig(
        block_size=4, num_blocks=16, max_slots=2, max_blocks_per_seq=8,
        token_budget=8, kv_dtype=jnp.float32))
    rng = np.random.RandomState(0)
    eng.submit(rng.randint(0, cfg.vocab_size, (6,)).tolist(), 4, uid="a")
    eng.step()
    eng.submit(rng.randint(0, cfg.vocab_size, (3,)).tolist(), 4, uid="b")
    res = eng.run()
    assert {r.status for r in res.values()} == {"completed"}
    return eng.compile_count()


def test_engine_compiles_once_with_overlap_enabled():
    """The serving engine's one-executable invariant survives the knob:
    decode steps (S=1) resolve to the fallback statically, so
    ``overlap_comm=True`` never forks the compiled step — count stays 1
    on the default mesh, and on a TP mesh the knob adds exactly zero
    compiles over the knob-off run."""
    assert _engine_compile_count(1, True) == 1
    assert (_engine_compile_count(4, True)
            == _engine_compile_count(4, False))
