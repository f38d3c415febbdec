"""Compressed & hierarchical gradient collectives
(``parallel/comm_compressed.py``) — numerics gates on the 8-device CPU mesh.

Covers the PR-3 acceptance criteria: quantize→dequantize round-trip error
bounds, end-to-end mean preservation vs the fp32 reference, hierarchical ==
flat composition, the 20-step int8+error-feedback training run within 1%
final-loss of fp32, the ZeRO-1 reduce-scatter/all-gather dataflow, plus the
``allreduce_gradients(specs=...)`` FSDP-skip / tuple-axes coverage and the
NaN-safe ``clip_grad_norm`` satellites.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu.parallel import comm_compressed as cc
from neuronx_distributed_tpu.parallel import grads as grads_mod
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.trainer import optimizer as opt_mod

INT8 = cc.CompressionConfig(dtype="int8", block_size=64)
FP8 = cc.CompressionConfig(dtype="fp8", block_size=64)
FP32 = cc.CompressionConfig(dtype="fp32")


# ---------------------------------------------------------------------------
# quantizer unit tests (no mesh)
# ---------------------------------------------------------------------------

def test_roundtrip_error_bound_int8():
    x = jax.random.normal(jax.random.key(0), (777,)) * 3.0
    y = cc.quantize_dequantize(x, INT8)
    # symmetric int8: per-block error <= scale/2 = amax/254
    amax = jnp.max(jnp.abs(x))
    assert float(jnp.max(jnp.abs(y - x))) <= float(amax) / 254.0 + 1e-7


def test_roundtrip_error_bound_fp8():
    x = jax.random.normal(jax.random.key(1), (512,))
    y = cc.quantize_dequantize(x, FP8)
    # e4m3 keeps ~3 mantissa bits: relative error <= 2^-3 of the element
    # magnitude (scaled blockwise to the e4m3 range)
    bound = jnp.maximum(jnp.abs(x) * 0.0625, 1e-3)
    assert bool(jnp.all(jnp.abs(y - x) <= bound))


def test_roundtrip_exact_cases():
    # zeros quantize exactly (amax==0 -> scale 1.0), fp32 is identity
    z = jnp.zeros((130,))
    assert float(jnp.max(jnp.abs(cc.quantize_dequantize(z, INT8)))) == 0.0
    x = jax.random.normal(jax.random.key(2), (100,))
    np.testing.assert_array_equal(np.asarray(cc.quantize_dequantize(x, FP32)),
                                  np.asarray(x))


def test_blockwise_scales_are_per_block():
    # one huge block must not wash out a small one: blockwise beats
    # per-tensor exactly when magnitudes are imbalanced
    x = jnp.concatenate([jnp.full((64,), 1e4), jnp.full((64,), 1e-2)])
    y = cc.quantize_dequantize(x, INT8)
    small = y[64:]
    assert float(jnp.max(jnp.abs(small - 1e-2) / 1e-2)) < 0.01


def test_config_validation():
    with pytest.raises(ValueError):
        cc.CompressionConfig(dtype="int4")
    with pytest.raises(ValueError):
        cc.CompressionConfig(block_size=0)
    assert INT8.ratio > 3.5  # ~4x minus the per-block scale overhead
    assert FP32.ratio == 1.0


def test_from_config():
    oc = nxd.OptimizerConfig()
    cfgn = type("C", (), {"optimizer": oc})
    assert cc.from_config(cfgn) is None
    oc8 = nxd.OptimizerConfig(grad_comm_dtype="int8", grad_comm_block_size=32)
    got = cc.from_config(type("C", (), {"optimizer": oc8}))
    assert got == cc.CompressionConfig(dtype="int8", block_size=32)
    with pytest.raises(ValueError):
        nxd.OptimizerConfig(grad_comm_dtype="bf16")
    with pytest.raises(ValueError):
        nxd.OptimizerConfig(grad_comm_block_size=-1)


# ---------------------------------------------------------------------------
# collective numerics on the 8-device mesh
# ---------------------------------------------------------------------------

def _data_mesh(dp=4, cp=2):
    ps.destroy_model_parallel()
    return ps.initialize_model_parallel(data_parallel_size=dp,
                                        context_parallel_size=cp)


def _per_rank(n=8, m=1000, scale=True):
    x = jax.random.normal(jax.random.key(0), (n, m))
    if scale:  # rank-dependent magnitudes exercise the blockwise scales
        x = x * (1.0 + jnp.arange(n)[:, None].astype(jnp.float32))
    return x


def _allreduce(xs, config, mesh, error=None):
    if error is None:
        def inner(x):
            return cc.all_reduce(x[0], ("dp", "cp"), config=config,
                                 op="mean")[None]
        return ps.shard_map(inner, mesh, in_specs=(P(("dp", "cp")),),
                            out_specs=P(("dp", "cp")))(xs)

    def inner(x, e):
        y, ne = cc.all_reduce(x[0], ("dp", "cp"), config=config,
                              op="mean", error=e[0])
        return y[None], ne[None]
    return ps.shard_map(inner, mesh,
                        in_specs=(P(("dp", "cp")), P(("dp", "cp"))),
                        out_specs=(P(("dp", "cp")), P(("dp", "cp"))))(
        xs, error)


def test_compressed_allreduce_mean_preservation():
    mesh = _data_mesh()
    xs = _per_rank()
    ref = np.mean(np.asarray(xs), axis=0)
    exact = np.asarray(_allreduce(xs, FP32, mesh))
    np.testing.assert_allclose(exact, np.broadcast_to(ref, exact.shape),
                               atol=1e-6)
    for cfg, tol in ((INT8, 0.02), (FP8, 0.1)):
        got = np.asarray(_allreduce(xs, cfg, mesh))
        # every rank reconstructs the same reduced tensor...
        np.testing.assert_allclose(got, np.broadcast_to(got[0], got.shape),
                                   atol=1e-6)
        # ...close to the fp32 mean relative to its magnitude
        denom = np.abs(ref).max()
        assert np.abs(got[0] - ref).max() / denom < tol, cfg.dtype


def test_hierarchical_matches_flat():
    mesh = _data_mesh()  # dp=4 (slow by convention) x cp=2 (fast)
    xs = _per_rank()
    # identity quantizer: hierarchical routing must agree with flat up to
    # fp32 summation-order effects
    flat = np.asarray(_allreduce(xs, FP32, mesh))
    hier = np.asarray(_allreduce(
        xs, dataclasses.replace(FP32, hierarchical=True), mesh))
    np.testing.assert_allclose(hier, flat, rtol=1e-6, atol=1e-6)
    # quantized: both within quantization tolerance of the true mean
    ref = np.mean(np.asarray(xs), axis=0)
    hier8 = np.asarray(_allreduce(
        xs, dataclasses.replace(INT8, hierarchical=True), mesh))
    assert np.abs(hier8[0] - ref).max() / np.abs(ref).max() < 0.03


def test_declared_hierarchy_overrides_convention():
    mesh = _data_mesh()
    ps.declare_axis_hierarchy(fast=("dp",), slow=("cp",))
    assert cc.split_axis_hierarchy(("dp", "cp")) == (("dp",), ("cp",))
    with pytest.raises(ValueError):
        ps.declare_axis_hierarchy(fast=("dp",), slow=("dp",))
    with pytest.raises(ValueError):
        ps.declare_axis_hierarchy(fast=("nope",), slow=())
    # numerics unchanged under the swapped staging
    xs = _per_rank()
    ref = np.mean(np.asarray(xs), axis=0)
    got = np.asarray(_allreduce(
        xs, dataclasses.replace(INT8, hierarchical=True), mesh))
    assert np.abs(got[0] - ref).max() / np.abs(ref).max() < 0.03


def test_dcn_mesh_auto_declares_hierarchy():
    ps.destroy_model_parallel()
    ps.initialize_model_parallel(context_parallel_size=2,
                                 dcn_data_parallel_size=2)
    assert ps.get_axis_hierarchy() == (("cp",), ("dp",))
    ps.destroy_model_parallel()
    assert ps.get_axis_hierarchy() is None


def test_error_feedback_converges_over_steps():
    """EF makes the *averaged* quantization error vanish: repeatedly
    reducing the SAME per-rank tensors with the residue carried forward
    must drive the time-mean of the outputs to the true mean."""
    mesh = _data_mesh()
    xs = _per_rank(m=512)
    ref = np.asarray(jnp.mean(xs, axis=0))
    err = jnp.zeros_like(xs)
    # one traced step, called 24 times: each ``shard_map`` closure of
    # ``_allreduce`` is otherwise traced and compiled again
    step = jax.jit(lambda x, e: _allreduce(x, INT8, mesh, error=e))
    outs = []
    for _ in range(24):
        y, err = step(xs, err)
        outs.append(np.asarray(y)[0])
    single = np.abs(outs[0] - ref).max()
    avged = np.abs(np.mean(outs, axis=0) - ref).max()
    assert avged < single / 4, (single, avged)


def test_reduce_scatter_allgather_flat_zero1():
    mesh = _data_mesh()
    xs = _per_rank(m=1000)  # not block-divisible: exercises padding
    ref = np.mean(np.asarray(xs), axis=0)

    def rs(x):
        return opt_mod.zero1_reduce_scatter_gradients(
            {"w": x[0]}, ("dp", "cp"), compression=INT8)["w"][None]

    chunks = ps.shard_map(rs, mesh, in_specs=(P(("dp", "cp")),),
                          out_specs=P(("dp", "cp")))(xs)

    def ag(c):
        return opt_mod.zero1_all_gather_params(
            {"w": c[0]}, {"w": (1000,)}, ("dp", "cp"),
            compression=INT8)["w"][None]

    full = np.asarray(ps.shard_map(ag, mesh, in_specs=(P(("dp", "cp")),),
                                   out_specs=P(("dp", "cp")))(chunks))
    np.testing.assert_allclose(full, np.broadcast_to(full[0], full.shape),
                               atol=1e-6)
    assert np.abs(full[0] - ref).max() / np.abs(ref).max() < 0.03


def test_collectives_noop_without_mesh_axes():
    # outside shard_map / with unbound axes every collective is identity —
    # the 1-device CPU degrade path
    x = jax.random.normal(jax.random.key(3), (40,))
    np.testing.assert_array_equal(
        np.asarray(cc.all_reduce(x, ("dp", "cp"), config=INT8)),
        np.asarray(x))
    chunk = cc.reduce_scatter_flat(x, ("dp", "cp"), config=INT8)
    np.testing.assert_array_equal(np.asarray(chunk), np.asarray(x))
    y = cc.all_gather_flat(chunk, (40,), ("dp", "cp"), config=INT8)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ---------------------------------------------------------------------------
# allreduce_gradients: specs coverage + compression wiring
# ---------------------------------------------------------------------------

def test_allreduce_gradients_fsdp_spec_skips_axis():
    """A leaf sharded over dp (FSDP-style) must NOT be reduced over dp —
    each dp rank owns a distinct shard; averaging would corrupt it."""
    mesh = _data_mesh(dp=4, cp=2)
    xs = _per_rank(n=4, m=8, scale=False)  # one value per dp rank

    def f(g):
        out = grads_mod.allreduce_gradients(
            {"fsdp": g[0], "dense": g[0]},
            specs={"fsdp": P("dp"), "dense": P()}, axes=("dp",))
        return out["fsdp"][None], out["dense"][None]

    fs, dn = ps.shard_map(
        f, mesh, in_specs=(P("dp"),), out_specs=(P("dp"), P("dp")))(xs)
    # fsdp leaf untouched; dense leaf averaged over dp
    np.testing.assert_array_equal(np.asarray(fs), np.asarray(xs))
    ref = np.mean(np.asarray(xs), axis=0)
    np.testing.assert_allclose(np.asarray(dn),
                               np.broadcast_to(ref, dn.shape), atol=1e-6)


def test_allreduce_gradients_tuple_axes_spec():
    """PartitionSpec entries that are TUPLES of axes (merged-axis sharding,
    the `_spec_axes` tuple branch) must skip every named axis."""
    mesh = _data_mesh(dp=4, cp=2)
    xs = _per_rank(n=8, m=8, scale=False)

    def f(g):
        out = grads_mod.allreduce_gradients(
            {"merged": g[0]}, specs={"merged": P(("dp", "cp"))})
        return out["merged"][None]

    got = ps.shard_map(f, mesh, in_specs=(P(("dp", "cp")),),
                       out_specs=P(("dp", "cp")))(xs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(xs))
    # sanity: without the spec the same leaf IS reduced
    def g(gr):
        return grads_mod.allreduce_gradients({"m": gr[0]})["m"][None]
    red = ps.shard_map(g, mesh, in_specs=(P(("dp", "cp")),),
                       out_specs=P(("dp", "cp")))(xs)
    ref = np.mean(np.asarray(xs), axis=0)
    np.testing.assert_allclose(np.asarray(red),
                               np.broadcast_to(ref, red.shape), atol=1e-6)


def test_allreduce_gradients_compressed_matches_fp32():
    mesh = _data_mesh()
    xs = _per_rank(m=300)

    def f(g):
        out = grads_mod.allreduce_gradients({"w": g[0]}, compression=INT8)
        return out["w"][None]

    got = np.asarray(ps.shard_map(f, mesh, in_specs=(P(("dp", "cp")),),
                                  out_specs=P(("dp", "cp")))(xs))
    ref = np.mean(np.asarray(xs), axis=0)
    assert np.abs(got[0] - ref).max() / np.abs(ref).max() < 0.02


# ---------------------------------------------------------------------------
# clip_grad_norm satellites
# ---------------------------------------------------------------------------

def test_clip_grad_norm_rejects_nonpositive_max_norm():
    g = {"w": jnp.ones((4,))}
    with pytest.raises(ValueError):
        grads_mod.clip_grad_norm(g, 0.0)
    with pytest.raises(ValueError):
        grads_mod.clip_grad_norm(g, -1.0)


def test_clip_grad_norm_nan_safe():
    g = {"a": jnp.array([jnp.nan, 1.0]), "b": jnp.ones((2,))}
    clipped, norm = grads_mod.clip_grad_norm(g, 1.0)
    assert not bool(jnp.isfinite(norm))
    # scale fell back to 1.0: finite leaves pass through unpoisoned so
    # skip_nonfinite can drop the step cleanly
    np.testing.assert_array_equal(np.asarray(clipped["b"]),
                                  np.asarray(g["b"]))

    ginf = {"a": jnp.array([jnp.inf, 1.0])}
    clipped, norm = grads_mod.clip_grad_norm(ginf, 1.0)
    assert not bool(jnp.isfinite(norm))

    # finite path still clips
    gbig = {"w": jnp.full((4,), 10.0)}
    clipped, norm = grads_mod.clip_grad_norm(gbig, 1.0)
    assert float(norm) == pytest.approx(20.0)
    assert float(jnp.linalg.norm(clipped["w"])) == pytest.approx(1.0,
                                                                 rel=1e-4)


# ---------------------------------------------------------------------------
# error-feedback buffer layout helpers
# ---------------------------------------------------------------------------

def test_error_feedback_specs_and_init():
    _data_mesh(dp=4, cp=2)
    specs = {"dense": P(), "tp_row": P(None, "tp"), "fsdp": P("dp")}
    ef = cc.error_feedback_specs(specs, ("dp", "cp"))
    # dense reduces over both axes -> merged leading rank dim
    assert ef["dense"] == P(("dp", "cp"))
    assert ef["tp_row"] == P(("dp", "cp"), None, "tp")
    # fsdp leaf only reduces over cp
    assert ef["fsdp"] == P("cp", "dp")
    params = {"dense": jnp.zeros((6,)), "tp_row": jnp.zeros((2, 4)),
              "fsdp": jnp.zeros((8,))}
    bufs = cc.init_error_feedback(params, specs, ("dp", "cp"))
    assert bufs["dense"].shape == (8, 6)
    assert bufs["tp_row"].shape == (8, 2, 4)
    assert bufs["fsdp"].shape == (2, 8)


# ---------------------------------------------------------------------------
# acceptance: 20-step training, int8+EF vs fp32 within 1% final loss
# ---------------------------------------------------------------------------

def _train(opt_cfg, compression, steps=20):
    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)
    from neuronx_distributed_tpu.trainer import (initialize_parallel_model,
                                                 initialize_parallel_optimizer,
                                                 make_train_step)

    ps.destroy_model_parallel()
    cfg = nxd.neuronx_distributed_config(tensor_parallel_size=2,
                                         optimizer_config=opt_cfg)
    mcfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32)
    model = LlamaForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0), (8, 33), 0, mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           batch["input_ids"])
    tx, state, sh = initialize_parallel_optimizer(pm, params,
                                                  learning_rate=1e-3)
    step = make_train_step(pm, tx, sh, compression=compression, donate=False)
    losses = []
    for _ in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses, metrics, state


@pytest.mark.slow
def test_int8_error_feedback_training_matches_fp32():
    losses_ref, _, ref_state = _train(nxd.OptimizerConfig(), None)
    oc = nxd.OptimizerConfig(grad_comm_dtype="int8",
                             grad_comm_block_size=128)
    comp = cc.from_config(type("C", (), {"optimizer": oc}))
    losses_8, metrics, st = _train(oc, comp)
    rel = abs(losses_8[-1] - losses_ref[-1]) / abs(losses_ref[-1])
    assert rel < 0.01, (losses_ref[-1], losses_8[-1])
    assert np.isfinite(losses_8).all()
    # EF buffers were allocated, threaded, and are nonzero after training
    assert st.comm_error is not None
    assert ref_state.comm_error is None
    total = sum(float(jnp.sum(jnp.abs(e)))
                for e in jax.tree_util.tree_leaves(st.comm_error))
    assert total > 0.0
    assert float(metrics["grad_comm_ratio"]) > 3.5


@pytest.mark.slow
def test_compressed_explicit_path_fp32_matches_gspmd():
    """The internal shard_map gradient path with the identity quantizer
    must reproduce the GSPMD step almost exactly — isolates routing bugs
    from quantization noise."""
    losses_ref, _, _ = _train(nxd.OptimizerConfig(), None, steps=6)
    oc = nxd.OptimizerConfig(grad_comm_dtype="fp32",
                             grad_comm_hierarchical=True)
    comp = cc.from_config(type("C", (), {"optimizer": oc}))
    losses_h, _, st = _train(oc, comp, steps=6)
    np.testing.assert_allclose(losses_h, losses_ref, rtol=1e-4)
    assert st.comm_error is None  # fp32 carries no residue buffers


def test_explicit_path_identity_quantizer_matches_gspmd_in_two_steps():
    """The slow case above in two steps, so that the fast gate runs the
    gradient function the compressed and the bound default step share
    (``make_train_step``'s ``inner``): tp=2 x dp=4, a hierarchical fp32
    reduce on the data axes against GSPMD's own."""
    losses_ref, _, _ = _train(nxd.OptimizerConfig(), None, steps=2)
    oc = nxd.OptimizerConfig(grad_comm_dtype="fp32",
                             grad_comm_hierarchical=True)
    comp = cc.from_config(type("C", (), {"optimizer": oc}))
    losses_h, metrics, st = _train(oc, comp, steps=2)
    np.testing.assert_allclose(losses_h, losses_ref, rtol=1e-4)
    assert st.comm_error is None and "grad_comm_ratio" in metrics


def test_make_train_step_compression_rejects_custom_grad_fn():
    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)
    from neuronx_distributed_tpu.trainer import (initialize_parallel_model,
                                                 initialize_parallel_optimizer,
                                                 make_train_step)

    cfg = nxd.neuronx_distributed_config(tensor_parallel_size=2)
    mcfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32)
    model = LlamaForCausalLM(mcfg)
    ids = jnp.zeros((8, 16), jnp.int32)
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           ids)
    tx, state, sh = initialize_parallel_optimizer(pm, params)
    with pytest.raises(ValueError, match="compression"):
        make_train_step(pm, tx, sh, grad_fn=lambda p, b: (0.0, p),
                        compression=INT8)
