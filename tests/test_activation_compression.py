"""Activation-collective compression (docs/comm_compression.md,
activations section; docs/tp_overlap.md, quantized wire format).

The contract under test: a quantized ``wire`` makes the decomposed
ppermute ring and the quantized monolithic collective **bitwise
identical** (same per-source block boundaries, same ascending-rank
accumulation, dequantize multiplies materialized so fp contraction
cannot skew one path); the layer/config plumbing engages statically
(no recompiles — the serving engine keeps its one-executable
invariant); reduced-sync TP is a no-op at fraction 1.0 and bitwise
inert where the tp axis is unbound; and the e2e tiny-llama drill holds
int8 activations within 1% of fp32 final loss.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu.ops import collective_matmul as cm
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.parallel.wire_codec import (
    CompressionConfig, wire_bytes_per_element)


def _tp_mesh(tp):
    return ps.initialize_model_parallel(tensor_model_parallel_size=tp)


def _jit_shard(f, mesh, in_specs, out_specs):
    return jax.jit(ps.shard_map(f, mesh, in_specs=in_specs,
                                out_specs=out_specs))


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# wire codec accounting
# ---------------------------------------------------------------------------

def test_wire_config_accounting_and_validation():
    assert cm.wire_config(None) is None
    assert cm.wire_config("fp32") is None
    w = cm.wire_config("int8", 128)
    assert isinstance(w, CompressionConfig)
    assert w.dtype == "int8" and w.block_size == 128
    assert not w.hierarchical and not w.error_feedback
    # the wire-byte accounting the planner and bench charge
    assert wire_bytes_per_element("fp32") == 4.0
    assert 4.0 / wire_bytes_per_element("int8", 256) > 3.9
    with pytest.raises(ValueError):
        cm.wire_config("int4")


def test_tp_sync_schedule():
    assert cm.tp_sync_schedule(4, 1.0) == (True,) * 4
    assert cm.tp_sync_schedule(0, 0.5) == ()
    # fraction 0.5 -> period 2, last layer forced on
    assert cm.tp_sync_schedule(6, 0.5) == (False, True, False, True,
                                           False, True)
    assert cm.tp_sync_schedule(5, 0.5)[-1] is True
    # fraction 0.25 -> period 4
    sched = cm.tp_sync_schedule(8, 0.25)
    assert sched == (False, False, False, True, False, False, False, True)
    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError):
            cm.tp_sync_schedule(4, bad)


# ---------------------------------------------------------------------------
# quantized ring == quantized monolithic, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,block", [("int8", 256), ("int8", 16),
                                         ("fp8", 64)])
def test_quantized_all_gather_matmul_ring_matches_monolithic(dtype, block):
    """Per-source quantization at identical block boundaries + ordered
    dequantize-accumulate: the quantized ring must equal the quantized
    monolithic collective to the last bit, fwd and bwd."""
    tp = 4
    mesh = _tp_mesh(tp)
    wire = cm.wire_config(dtype, block)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 16, 32).astype(np.float32))
    w = jnp.asarray(rng.randn(32, 5 * tp).astype(np.float32))

    def run(impl):
        def f(xl, wl):
            def loss(xv, wv):
                y = cm.all_gather_matmul(xv, wv, "tp", 1, impl=impl,
                                         wire=wire)
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


@pytest.mark.parametrize("tp", [3, 4])
@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_quantized_matmul_reduce_scatter_ring_matches_monolithic(tp, dtype):
    """RS parity covers both ring variants (tp=3 unidirectional, tp=4
    bidirectional) — the contribution-buffer materialization in the
    monolithic path is what keeps XLA's fma contraction from skewing
    one program but not the other."""
    if jax.device_count() % tp:
        pytest.skip(f"device count not divisible by {tp}")
    mesh = _tp_mesh(tp)
    wire = cm.wire_config(dtype, 64)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 4 * tp, 4 * tp).astype(np.float32))
    w = jnp.asarray(rng.randn(4 * tp, 6).astype(np.float32))

    def run(impl):
        def f(xl, wl):
            def loss(xv, wv):
                y = cm.matmul_reduce_scatter(xv, wv, "tp", 1, impl=impl,
                                             wire=wire)
                return jnp.sum(jnp.sin(y)), y

            (_, y), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(xl, wl)
            return y, grads

        return _jit_shard(
            f, mesh,
            (P(None, None, "tp"), P("tp", None)),
            ((P(None, "tp", None)),
             (P(None, None, "tp"), P("tp", None))))(x, w)

    _assert_trees_equal(run("decomposed"), run("monolithic"))


def test_quantized_all_reduce_close_to_fp32():
    """matmul_all_reduce's decomposed RS+AG and the monolithic psum are
    different algorithms (documented) — quantized they stay within the
    codec's error bound of the fp32 result."""
    tp = 4
    mesh = _tp_mesh(tp)
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 8, 4 * tp).astype(np.float32) * 0.1)
    w = jnp.asarray(rng.randn(4 * tp, 6).astype(np.float32) * 0.1)

    def run(wire):
        def f(xl, wl):
            return cm.matmul_all_reduce(xl, wl, "tp", 1,
                                        impl="monolithic", wire=wire)

        return _jit_shard(f, mesh, (P(None, None, "tp"), P("tp", None)),
                          P(None, None, None))(x, w)

    ref = np.asarray(run(None))
    got = np.asarray(run(cm.wire_config("int8", 64)))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.05)


def test_all_gather_matmul_error_feedback_api():
    """``error=`` threads the cross-step residue: quantized wire returns
    a nonzero residue equal to x − DQ(Q(x)); fp32 wire returns zeros."""
    tp = 4
    mesh = _tp_mesh(tp)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 8, 16).astype(np.float32))
    w = jnp.asarray(rng.randn(16, 3 * tp).astype(np.float32))
    wire = cm.wire_config("int8", 16)

    def run(wirev):
        def f(xl, wl, el):
            y, ne = cm.all_gather_matmul(xl, wl, "tp", 1, impl="decomposed",
                                         wire=wirev, error=el)
            return y, ne

        err0 = jnp.zeros_like(x)
        return _jit_shard(
            f, mesh,
            (P(None, "tp", None), P(None, "tp"), P(None, "tp", None)),
            (P(None, None, "tp"), P(None, "tp", None)))(x, w, err0)

    y_q, ne_q = run(wire)
    assert np.isfinite(np.asarray(y_q)).all()
    assert float(jnp.sum(jnp.abs(ne_q))) > 0.0
    y_fp, ne_fp = run(None)
    assert float(jnp.sum(jnp.abs(ne_fp))) == 0.0
    # fp32 wire with error= is numerically the plain op
    np.testing.assert_array_equal(
        np.asarray(y_fp),
        np.asarray(_jit_shard(
            lambda xl, wl: cm.all_gather_matmul(xl, wl, "tp", 1,
                                                impl="decomposed"),
            mesh, (P(None, "tp", None), P(None, "tp")),
            P(None, None, "tp"))(x, w)))


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_config_plumbing_and_validation():
    from neuronx_distributed_tpu.config import configure_model
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=2, tp_activation_comm_dtype="int8",
        tp_activation_sync_fraction=0.5, init_mesh=False)
    assert cfg.parallel.tp_activation_comm_dtype == "int8"
    assert cfg.parallel.tp_activation_sync_fraction == 0.5
    mcfg = configure_model(cfg, LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=64,
        scan_layers=False))
    assert mcfg.activation_comm_dtype == "int8"
    assert mcfg.activation_sync_fraction == 0.5
    # round trip through kwargs and the YAML converter
    from neuronx_distributed_tpu.scripts.yaml_converter import (
        config_to_dict, dict_to_config_kwargs)

    assert nxd.neuronx_distributed_config(
        init_mesh=False, **cfg.to_config_kwargs()) == cfg
    doc = config_to_dict(cfg)
    assert doc["tp_activation_comm_dtype"] == "int8"
    assert doc["tp_activation_sync_fraction"] == 0.5
    assert nxd.neuronx_distributed_config(
        init_mesh=False, **dict_to_config_kwargs(doc)) == cfg
    # defaults are elided from the YAML document
    plain = nxd.neuronx_distributed_config(init_mesh=False)
    assert "tp_activation_comm_dtype" not in config_to_dict(plain)
    # validation
    with pytest.raises(ValueError):
        nxd.neuronx_distributed_config(tp_activation_comm_dtype="int4",
                                       init_mesh=False)
    with pytest.raises(ValueError):
        nxd.neuronx_distributed_config(tp_activation_sync_fraction=0.0,
                                       init_mesh=False)


def test_model_config_rejects_bad_combinations():
    from neuronx_distributed_tpu.models.llama import tiny_config

    with pytest.raises(ValueError):
        tiny_config(activation_comm_dtype="int4")
    with pytest.raises(ValueError):
        tiny_config(activation_sync_fraction=0.5, scan_layers=True)
    with pytest.raises(ValueError):
        tiny_config(activation_sync_fraction=0.5, sequence_parallel=True)
    with pytest.raises(ValueError):
        tiny_config(activation_sync_fraction=1.5)


# ---------------------------------------------------------------------------
# model forward: quantized + reduced-sync
# ---------------------------------------------------------------------------

def _llama_logits(mcfg, ids, tp):
    from flax import linen as nn
    from flax.core import meta

    from neuronx_distributed_tpu.models.llama import LlamaForCausalLM

    ps.destroy_model_parallel()
    mesh = _tp_mesh(tp)
    model = LlamaForCausalLM(mcfg)
    boxed = model.init(jax.random.key(1), ids)
    specs = nn.get_partition_spec(boxed)
    params = meta.unbox(boxed)
    return _jit_shard(
        lambda p, i: model.apply(p, i), mesh,
        (specs, P(None, None)), P(None, None, "tp"))(params, ids)


@pytest.mark.parametrize("fam", ["llama", "mixtral"])
def test_reduced_sync_and_int8_forward_finite_tp4(fam):
    """tp=4 shard_map forward with int8 activation wires AND a 0.5 sync
    fraction stays finite and close to the fully-synced fp32 run."""
    if fam == "llama":
        from neuronx_distributed_tpu.models.llama import (  # noqa: F401
            LlamaForCausalLM as Model, tiny_config)
    else:
        from neuronx_distributed_tpu.models.mixtral import (
            MixtralForCausalLM as Model, tiny_moe_config as tiny_config)
    from flax.core import meta

    from neuronx_distributed_tpu.trainer.trainer import _spec_tree

    ps.destroy_model_parallel()
    mesh = _tp_mesh(4)
    ids = jax.random.randint(jax.random.key(0), (2, 16), 0, 256)

    def run(**kw):
        mcfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           num_layers=4, scan_layers=False, **kw)
        model = Model(mcfg)

        # init outside the bound axis (flax's Partitioned.unbox constrains
        # a sharding, which a Manual mesh refuses) and hand each rank its
        # shards; mixtral's expert specs name the ep axis, which a tp-only
        # mesh does not carry: _spec_tree replicates over it
        boxed = model.init(jax.random.key(1), ids)
        specs = _spec_tree(boxed)

        def fwd(params, i):
            out = model.apply(params, i)
            return out[0] if isinstance(out, tuple) else out

        return jax.jit(ps.shard_map(
            fwd, mesh, in_specs=(specs, P()),
            out_specs=P(None, None, "tp"), check_vma=False))(
                meta.unbox(boxed), ids)

    ref = np.asarray(run())
    got = np.asarray(run(activation_comm_dtype="int8",
                         activation_sync_fraction=0.5))
    assert np.isfinite(got).all()
    # quantization + reduced sync perturb the (untrained, random-weight)
    # logits but stay the same order of magnitude as the reference
    assert np.max(np.abs(got - ref)) < 2.0 + np.max(np.abs(ref))


def test_reduced_sync_is_identity_when_axis_unbound():
    """Outside any tp mesh the resync algebra must not engage: fraction
    0.5 is bit-identical to 1.0 (the elide shares equal the sum only
    under a real axis; at tp=1 the plain path must be taken)."""
    from flax.core import meta

    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)

    ps.destroy_model_parallel()
    ids = jax.random.randint(jax.random.key(0), (2, 12), 0, 256)

    def run(frac):
        mcfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           scan_layers=False,
                           activation_sync_fraction=frac)
        model = LlamaForCausalLM(mcfg)
        params = meta.unbox(model.init(jax.random.key(1), ids))
        return model.apply(params, ids)

    np.testing.assert_array_equal(np.asarray(run(1.0)),
                                  np.asarray(run(0.5)))


# ---------------------------------------------------------------------------
# serving engine: one executable + greedy parity under quantization
# ---------------------------------------------------------------------------

def _engine_run(tp, act_dtype):
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          ServingEngine)
    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)

    ps.destroy_model_parallel()
    ps.initialize_model_parallel(tensor_model_parallel_size=tp)
    cfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                      num_layers=2, tp_size=tp,
                      activation_comm_dtype=act_dtype)
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
    return eng.compile_count(), {k: r.tokens for k, r in res.items()}


def test_engine_compiles_once_with_activation_quantization():
    """The wire routing is static on shapes: int8 activation wires never
    fork the compiled step — count stays 1 on the default mesh and adds
    exactly zero compiles over the fp32 run on a TP mesh (the same
    framing as the overlap-knob invariant). Greedy decode returns the
    same tokens as the fp32 run — quantization noise at fp16-level
    tolerance does not flip the argmax on this model."""
    compiles1, _ = _engine_run(1, "int8")
    assert compiles1 == 1
    compiles, toks = _engine_run(4, "int8")
    compiles_fp, toks_fp = _engine_run(4, "fp32")
    assert compiles == compiles_fp
    assert toks == toks_fp


# ---------------------------------------------------------------------------
# acceptance: 20-step e2e, int8 activations within 1% of fp32
# ---------------------------------------------------------------------------

def _train(act_dtype, steps=20):
    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)
    from neuronx_distributed_tpu.parallel import comm_compressed as cc
    from neuronx_distributed_tpu.trainer import (initialize_parallel_model,
                                                 initialize_parallel_optimizer,
                                                 make_train_step)

    ps.destroy_model_parallel()
    cfg = nxd.neuronx_distributed_config(tensor_parallel_size=2)
    mcfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                       activation_comm_dtype=act_dtype)
    model = LlamaForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0), (8, 33), 0, mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           batch["input_ids"])
    tx, state, sh = initialize_parallel_optimizer(pm, params,
                                                  learning_rate=1e-3)
    # the explicit shard_map path binds tp, so the quantized activation
    # collectives actually engage during training
    step = make_train_step(pm, tx, sh,
                           compression=cc.CompressionConfig(dtype="fp32"),
                           donate=False)
    losses = []
    for _ in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


@pytest.mark.slow
def test_int8_activation_training_within_1pct_of_fp32():
    losses_ref = _train("fp32")
    losses_8 = _train("int8")
    assert np.isfinite(losses_8).all()
    assert losses_ref != losses_8  # quantization engaged (tp bound)
    rel = abs(losses_8[-1] - losses_ref[-1]) / abs(losses_ref[-1])
    assert rel < 0.01, (losses_ref[-1], losses_8[-1])
