"""Round-4 perf levers, pinned (VERDICT r4 next #1b):

* ``fused_linear_cross_entropy`` — loss/grad parity vs the classic
  full-logits ``causal_lm_loss`` path (chunk dividing and not dividing S,
  tp>1 shard_map, sequence-parallel), plus checkpoint interchange between
  the fused ``_LMHeadKernel`` and ``ColumnParallelLinear`` head paths.
* ``remat_policy="save_attention"`` — grad parity vs ``"nothing"`` on the
  forced-Pallas path, and a saved-residuals assertion that the policy
  actually saves ``flash_out``/``flash_lse`` (catches the silent-no-op
  failure mode from ADVICE r4 #3).
"""

import contextlib
import dataclasses
import io

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import print_saved_residuals
from jax.sharding import PartitionSpec as P

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu.models.llama import (LlamaConfig,
                                                  LlamaForCausalLM,
                                                  tiny_config)
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.trainer import initialize_parallel_model
from neuronx_distributed_tpu.utils.remat import resolve_remat_policy
from remat_checks import assert_flash_forward_runs, flash_kernel_calls


def _batch(cfg, b=2, s=32, seed=0):
    ids = jax.random.randint(jax.random.key(seed), (b, s + 1), 0,
                             cfg.vocab_size)
    return ids[:, :-1], ids[:, 1:]


def _fp32(**kw):
    return tiny_config(dtype=jnp.float32, param_dtype=jnp.float32, **kw)


def _loss_and_grads(cfg, params, ids, labels):
    model = LlamaForCausalLM(cfg)

    def loss_fn(p):
        return model.apply(p, ids, labels=labels)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    return float(loss), grads


@pytest.mark.parametrize("chunk", [
    16, pytest.param(24, marks=pytest.mark.slow)])  # 24: non-dividing pad case
def test_fused_loss_matches_classic_tp1(chunk):
    ps.initialize_model_parallel(tensor_model_parallel_size=1)
    base = _fp32()
    ids, labels = _batch(base)
    params = LlamaForCausalLM(base).init(jax.random.key(1), ids)
    params = jax.tree.map(lambda x: x, params)  # unboxed by init? keep as-is
    from flax.core import meta

    params = meta.unbox(params)
    loss_ref, grads_ref = _loss_and_grads(base, params, ids, labels)
    fused_cfg = _fp32(loss_chunk=chunk)
    loss_f, grads_f = _loss_and_grads(fused_cfg, params, ids, labels)
    assert abs(loss_f - loss_ref) < 1e-5, (loss_f, loss_ref)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4),
        grads_f, grads_ref)


def test_fused_loss_checkpoint_interchange():
    """The fused path's _LMHeadKernel param tree must be structurally
    identical to the ColumnParallelLinear head's (same names, shapes,
    partitioning) so checkpoints interchange between the two loss paths."""
    ps.initialize_model_parallel(tensor_model_parallel_size=1)
    ids, _ = _batch(_fp32())
    from flax.core import meta

    classic = meta.unbox(
        LlamaForCausalLM(_fp32()).init(jax.random.key(1), ids))
    # init the fused path WITH labels so the fused branch traces
    labels = jnp.zeros(ids.shape, jnp.int32)
    fused = meta.unbox(LlamaForCausalLM(_fp32(loss_chunk=16)).init(
        jax.random.key(1), ids, labels=labels))
    ref_paths = {jax.tree_util.keystr(k): v.shape
                 for k, v in jax.tree_util.tree_leaves_with_path(classic)}
    fused_paths = {jax.tree_util.keystr(k): v.shape
                   for k, v in jax.tree_util.tree_leaves_with_path(fused)}
    assert ref_paths == fused_paths
    # and partition metadata matches too
    from flax import linen as nn

    c_spec = nn.get_partition_spec(
        LlamaForCausalLM(_fp32()).init(jax.random.key(1), ids))
    f_spec = nn.get_partition_spec(
        LlamaForCausalLM(_fp32(loss_chunk=16)).init(
            jax.random.key(1), ids, labels=labels))
    c_head = c_spec["params"]["lm_head"]
    f_head = f_spec["params"]["lm_head"]
    assert c_head == f_head, (c_head, f_head)


@pytest.mark.slow
@pytest.mark.parametrize("sp", [False, True])
def test_fused_loss_matches_classic_tp4(sp):
    """tp=4 shard_map: fused loss ≡ classic loss to fp32 tolerance,
    including the sequence-parallel entry into the TP region."""
    cfg = nxd.neuronx_distributed_config(tensor_parallel_size=4)
    mesh = ps.get_mesh()
    base = _fp32(tp_size=4, sequence_parallel=sp, num_layers=1)
    fused_cfg = _fp32(tp_size=4, sequence_parallel=sp, num_layers=1,
                      loss_chunk=8)
    ids, labels = _batch(base)
    model = LlamaForCausalLM(base)
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           ids)
    fmodel = LlamaForCausalLM(fused_cfg)

    def run(m):
        return jax.jit(ps.shard_map(
            lambda p, i, l: jax.value_and_grad(
                lambda pp: m.apply(pp, i, labels=l))(p),
            mesh,
            in_specs=(pm.param_specs, P(None, None), P(None, None)),
            out_specs=(P(), pm.param_specs)))(params, ids, labels)

    loss_ref, grads_ref = run(model)
    loss_f, grads_f = run(fmodel)
    assert abs(float(loss_f) - float(loss_ref)) < 1e-5
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4),
        grads_f, grads_ref)


def test_loss_chunk_invalid_configs_raise():
    with pytest.raises(ValueError, match="tie_embeddings"):
        tiny_config(loss_chunk=16, tie_embeddings=True)
    with pytest.raises(ValueError, match="positive"):
        tiny_config(loss_chunk=0)
    from neuronx_distributed_tpu.lora import LoraConfig

    with pytest.raises(ValueError, match="lm_head"):
        tiny_config(loss_chunk=16,
                    lora=LoraConfig(r=4, target_modules=("lm_head",)))


def _pallas_cfg(**kw):
    # head_dim 128 so the forced Pallas kernel tiles (d % 128 == 0);
    # interpret mode on the CPU mesh
    base = dict(dtype=jnp.float32, param_dtype=jnp.float32,
                hidden_size=256, num_heads=2, num_kv_heads=2,
                intermediate_size=256, vocab_size=128,
                use_flash_attention=True, attn_force_pallas=True,
                remat=True)
    base.update(kw)
    return tiny_config(**base)


def _cell_cfg(policy=None, pipeline_parallel_size=1, **kw):
    """``(framework config, model config)`` of the forced-Pallas toy built
    the way the train cell builds its model: full activation checkpointing
    asked of the framework, then ``configure_model``; what a layer keeps
    is said only where ``policy`` says it."""
    ps.destroy_model_parallel()
    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=1,
        pipeline_parallel_size=pipeline_parallel_size,
        activation_checkpoint_config=nxd.ActivationCheckpointConfig(
            mode="full"))
    if policy:
        kw["remat_policy"] = policy
    mcfg = nxd.configure_model(cfg, _pallas_cfg(remat=False, **kw))
    assert mcfg.remat
    # configure_model sets the config's compute dtype (bf16): float32 here
    return cfg, dataclasses.replace(mcfg, dtype=jnp.float32)


@pytest.mark.parametrize("policy", ["save_attention", "dots_and_attention",
                                    None], ids=lambda p: p or "default")
def test_remat_policy_grads_match_nothing(policy):
    ps.initialize_model_parallel(tensor_model_parallel_size=1)
    cfg_n = _pallas_cfg(remat_policy="nothing")
    cfg_s = _pallas_cfg(remat_policy=policy) if policy else _cell_cfg()[1]
    ids, labels = _batch(cfg_n, b=1, s=64)
    from flax.core import meta

    params = meta.unbox(
        LlamaForCausalLM(cfg_n).init(jax.random.key(1), ids))
    loss_n, grads_n = _loss_and_grads(cfg_n, params, ids, labels)
    loss_s, grads_s = _loss_and_grads(cfg_s, params, ids, labels)
    assert abs(loss_n - loss_s) < 1e-6
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4),
        grads_n, grads_s)


@pytest.mark.parametrize("policy", [None, "nothing"],
                         ids=["default", "nothing"])
@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_a_rematerialised_layer_runs_the_flash_forward_once(scan, policy):
    """Asked for full checkpointing and nothing else, a layer keeps the
    kernel's output and log-sum-exp: its backward pass recomputes no
    ``flash_attention_fwd``. ``remat_policy="nothing"`` still does."""
    _, cfg = _cell_cfg(policy, scan_layers=scan)
    ids, labels = _batch(cfg, b=1, s=64)
    model = LlamaForCausalLM(cfg)
    params = jax.eval_shape(model.init, jax.random.key(1), ids)
    calls = flash_kernel_calls(jax.make_jaxpr(jax.value_and_grad(
        lambda p: model.apply(p, ids, labels=labels)))(params).jaxpr)
    assert_flash_forward_runs(calls, 1 if scan else cfg.num_layers,
                              recomputed=policy == "nothing")


@pytest.mark.parametrize("policy", [None, "nothing"],
                         ids=["default", "nothing"])
@pytest.mark.parametrize("schedule,runs", [("gpipe", 1), ("1f1b", 2)])
def test_a_pipeline_stage_keeps_what_the_layer_keeps(schedule, runs, policy):
    """The pipeline engines checkpoint their stage by the same resolution:
    differentiating a stage runs the forward kernel ``runs`` times (1F1B
    runs a stage forward once in its own slot and once under ``vjp``), and
    once more only where the model was told to keep nothing."""
    from neuronx_distributed_tpu.models import llama_pipeline as lpp

    cfg, mcfg = _cell_cfg(policy, pipeline_parallel_size=2, num_layers=4)
    ids, labels = _batch(mcfg, b=8, s=64)
    batch = {"input_ids": ids, "labels": labels}
    pm, params = initialize_parallel_model(
        cfg, LlamaForCausalLM(mcfg), jax.random.key(1), ids,
        logical_axis_rules=lpp.PIPELINE_LOGICAL_RULES)
    grad_fn = lpp.make_pipeline_grad_fn(
        mcfg, num_microbatches=2, param_specs=pm.param_specs,
        schedule=schedule)
    calls = flash_kernel_calls(jax.make_jaxpr(grad_fn)(params, batch).jaxpr)
    forward = sum(n for (name, _), n in calls.items()
                  if name == "flash_attention_fwd")
    assert forward == runs + (policy == "nothing"), calls
    assert calls["flash_attention_bwd_dq", True] == 1, calls


def _saved_residual_report(cfg, params, ids, labels):
    model = LlamaForCausalLM(cfg)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        print_saved_residuals(
            lambda p: model.apply(p, ids, labels=labels), params)
    return buf.getvalue()


@pytest.mark.parametrize("policy", ["save_attention", "dots_and_attention"])
def test_remat_policy_saves_flash_residuals(policy):
    """The policy must actually pin the flash out+lse across fwd→bwd at
    MODEL level (not just in a direct kernel call) — the silent-no-op
    regression mode flagged in VERDICT r4 weak #3 / ADVICE r4 #3. The
    combined dots_and_attention union must keep the named residuals."""
    ps.initialize_model_parallel(tensor_model_parallel_size=1)
    cfg_n = _pallas_cfg(remat_policy="nothing")
    cfg_s = _pallas_cfg(remat_policy=policy)
    ids, labels = _batch(cfg_n, b=1, s=64)
    from flax.core import meta

    params = meta.unbox(
        LlamaForCausalLM(cfg_n).init(jax.random.key(1), ids))
    rep_n = _saved_residual_report(cfg_n, params, ids, labels)
    rep_s = _saved_residual_report(cfg_s, params, ids, labels)
    # inside nn.scan the per-layer named residuals surface stacked over the
    # layer dim: lse [L, B, N, S] = [2,1,2,64], out [L, B, S, N, D] =
    # [2,1,64,2,128]. Under "nothing" neither may be saved.
    assert "f32[2,1,2,64]" not in rep_n and "f32[2,1,64,2,128]" not in rep_n
    assert "f32[2,1,2,64]" in rep_s, rep_s
    assert "f32[2,1,64,2,128]" in rep_s, rep_s
    # the policy strictly grows the saved set
    assert len(rep_s.splitlines()) > len(rep_n.splitlines())


def test_save_attention_not_a_noop_on_xla_fallback():
    """When shapes/backends demote dispatch to flash_attention_xla, the
    policy must still save out+lse (the fallback carries the same
    checkpoint_name tags via its custom_vjp) — review finding r5."""
    ps.initialize_model_parallel(tensor_model_parallel_size=1)
    cfg_n = _pallas_cfg(remat_policy="nothing", attn_force_pallas=None)
    cfg_s = _pallas_cfg(remat_policy="save_attention",
                        attn_force_pallas=None)  # CPU -> XLA fallback
    ids, labels = _batch(cfg_n, b=1, s=64)
    from flax.core import meta

    params = meta.unbox(
        LlamaForCausalLM(cfg_n).init(jax.random.key(1), ids))
    rep_n = _saved_residual_report(cfg_n, params, ids, labels)
    rep_s = _saved_residual_report(cfg_s, params, ids, labels)
    assert "f32[2,1,2,64]" not in rep_n
    assert "f32[2,1,2,64]" in rep_s, rep_s


def test_direct_kernel_saves_named_residuals():
    """Direct flash_attention call under jax.checkpoint(save_attention):
    both named residuals survive custom_vjp partial-eval."""
    from neuronx_distributed_tpu.ops.flash_attention import flash_attention

    q = jax.random.normal(jax.random.key(0), (1, 64, 2, 128), jnp.float32)

    def f(q):
        return jnp.sum(
            flash_attention(q, q, q, causal=True, force_pallas=True) ** 2)

    for pol, expect in (("nothing", False), ("save_attention", True)):
        ck = jax.checkpoint(f, policy=resolve_remat_policy(pol))
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            print_saved_residuals(ck, q)
        has_lse = "f32[1,2,64]" in buf.getvalue()
        assert has_lse == expect, (pol, buf.getvalue())


def test_loss_chunk_reduces_compiled_peak_memory():
    """The memory claim itself, pinned via XLA's compiled memory analysis:
    with loss_chunk the [B, S, V] logits (+fp32 CE intermediates) never
    materialise, so the differentiated step's temp allocation drops
    substantially at a vocab-dominated config."""
    ps.initialize_model_parallel(tensor_model_parallel_size=1)
    kw = dict(dtype=jnp.float32, param_dtype=jnp.float32,
              vocab_size=8192, hidden_size=64, intermediate_size=128,
              num_layers=2, max_seq_len=256)
    base = tiny_config(**kw)
    fused = tiny_config(**kw, loss_chunk=32)
    ids, labels = _batch(base, b=4, s=256)
    from flax.core import meta

    params = meta.unbox(LlamaForCausalLM(base).init(jax.random.key(1), ids))

    def temps(cfg):
        model = LlamaForCausalLM(cfg)
        f = jax.jit(jax.value_and_grad(
            lambda p: model.apply(p, ids, labels=labels)))
        ma = f.lower(params).compile().memory_analysis()
        return ma.temp_size_in_bytes

    t_classic = temps(base)
    t_fused = temps(fused)
    # full-logits path holds multiple fp32 [4, 256, 8192] buffers (33 MB
    # each); the chunked path holds [4, 32, 8192] slices. Require a >=40%
    # drop — far above noise, well below the theoretical ratio
    assert t_fused < 0.6 * t_classic, (t_fused, t_classic)
