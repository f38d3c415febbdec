"""Combinatorial config smoke matrix.

Analogue of the reference's ``test/integration/combinatorial_tests``
(``test_TP8_SP1_SC0_PP4_Zero1Opt1_FP32.txt`` style): a matrix of
TP × SP × PP × ZeRO × remat configs, each running one full train step on the
virtual mesh and checking a finite loss.
"""

import itertools

import numpy as np
import pytest

# heavyweight sweep tier: excluded from the fast gate (pytest -m 'not slow')
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_config
from neuronx_distributed_tpu.models import llama_pipeline as lpp
from neuronx_distributed_tpu.modules import glu
from neuronx_distributed_tpu.trainer import (initialize_parallel_model,
                                             initialize_parallel_optimizer,
                                             make_train_step)

MATRIX = [
    # (tp, pp, sp, zero1, remat)
    (1, 1, False, False, False),
    (2, 1, False, True, False),
    (2, 1, True, True, True),
    (4, 1, True, False, False),
    (2, 2, False, True, False),
    (2, 2, True, True, True),
    (1, 2, False, False, True),
    (8, 1, False, True, False),
]


@pytest.mark.parametrize("tp,pp,sp,zero1,remat", MATRIX)
def test_config_matrix_one_step(tp, pp, sp, zero1, remat):
    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=tp,
        pipeline_parallel_size=pp,
        optimizer_config=nxd.OptimizerConfig(zero_one_enabled=zero1),
        activation_checkpoint_config=nxd.ActivationCheckpointConfig(
            mode="full" if remat else "none"),
        sequence_parallel=sp,
    )
    mcfg = nxd.configure_model(cfg, tiny_config(
        dtype=jnp.float32, param_dtype=jnp.float32))
    model = LlamaForCausalLM(mcfg)
    dp = 8 // (tp * pp)
    ids = jax.random.randint(jax.random.key(0), (max(4, 2 * dp), 33), 0,
                             mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    rules = lpp.PIPELINE_LOGICAL_RULES if pp > 1 else None
    pm, params = initialize_parallel_model(
        cfg, model, jax.random.key(1), batch["input_ids"],
        logical_axis_rules=rules)
    tx, state, sh = initialize_parallel_optimizer(pm, params, 1e-3)
    grad_fn = None
    if pp > 1:
        grad_fn = lpp.make_pipeline_grad_fn(mcfg, num_microbatches=2,
                                            param_specs=pm.param_specs)
    step = make_train_step(pm, tx, sh, grad_fn=grad_fn)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), (tp, pp, sp, zero1, remat)


# ---------------------------------------------------------------------------
# cp / ep columns (r2: the reference's matrix style exists to catch
# cross-dimension interactions — cp x zero1, ep x cp, 1f1b x sp, ...)
# ---------------------------------------------------------------------------

def _cp_grad_fn(model, pm):
    """shard_map grad fn slicing the batch over dp x cp (the ring-attention
    training path, cf. __graft_entry__ phase 2)."""
    from jax.sharding import PartitionSpec as P

    from neuronx_distributed_tpu.parallel import grads as grads_mod
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.pipeline import spmd_engine as eng

    def grad_fn(params, batch):
        def inner(p, i, lb):
            def local_loss(p):
                return eng.data_parallel_mean(
                    model.apply(p, i, lb, method="loss"))

            loss, g = jax.value_and_grad(local_loss)(p)
            return loss, grads_mod.allreduce_gradients(g,
                                                       specs=pm.param_specs)

        return ps.shard_map(
            inner, ps.get_mesh(),
            in_specs=(pm.param_specs, P("dp", "cp"), P("dp", "cp")),
            out_specs=(P(), pm.param_specs))(
                params, batch["input_ids"], batch["labels"])

    return grad_fn


CP_MATRIX = [
    # (tp, cp, zero1, remat, impl)
    (1, 2, True, False, "ring"),   # cp x zero1 (opt state over dp x cp)
    (2, 2, True, True, "ring"),
    (1, 4, False, False, "ring"),
    (2, 4, False, False, "ring"),
    (2, 2, True, False, "ulysses"),     # cp impl x zero1 interactions
    (2, 2, False, True, "ring_pallas"),  # falls back on tiny head_dim;
                                         # pins config x remat plumbing
]


@pytest.mark.parametrize("tp,cp,zero1,remat,impl", CP_MATRIX)
def test_cp_matrix_one_step(tp, cp, zero1, remat, impl):
    from jax.sharding import PartitionSpec as P

    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=tp, context_parallel_size=cp,
        optimizer_config=nxd.OptimizerConfig(zero_one_enabled=zero1),
        activation_checkpoint_config=nxd.ActivationCheckpointConfig(
            mode="full" if remat else "none"))
    mcfg = nxd.configure_model(cfg, tiny_config(
        dtype=jnp.float32, param_dtype=jnp.float32, num_layers=2,
        cp_attn_impl=impl))
    model = LlamaForCausalLM(mcfg)
    dp = 8 // (tp * cp)
    ids = jax.random.randint(jax.random.key(0), (max(2, 2 * dp), 33), 0,
                             mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           batch["input_ids"])
    tx, state, sh = initialize_parallel_optimizer(pm, params, 1e-3)
    step = make_train_step(pm, tx, sh, grad_fn=_cp_grad_fn(model, pm),
                           batch_spec=P("dp", "cp"))
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), (tp, cp, zero1, remat, impl)


EP_MATRIX = [
    # (tp, ep, zero1, dispatch)
    (2, 2, False, "capacity"),
    (1, 2, True, "capacity"),   # ep x zero1
    (1, 4, False, "capacity"),
    (2, 2, False, "blockwise"),  # ep(GSPMD) x dropless
    (2, 2, True, "blockwise"),   # ep(GSPMD) x dropless x zero1
]


@pytest.mark.parametrize("tp,ep,zero1,dispatch", EP_MATRIX)
def test_ep_matrix_one_step(tp, ep, zero1, dispatch):
    from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                        tiny_moe_config)

    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=tp, expert_parallel_size=ep,
        optimizer_config=nxd.OptimizerConfig(zero_one_enabled=zero1))
    mcfg = nxd.configure_model(cfg, tiny_moe_config(
        dtype=jnp.float32, param_dtype=jnp.float32,
        moe_dispatch=dispatch, moe_block_size=16))
    model = MixtralForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0), (8, 33), 0,
                             mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           batch["input_ids"])
    tx, state, sh = initialize_parallel_optimizer(pm, params, 1e-3)

    # GSPMD EP is real: expert weights shard over ep on the expert mesh view
    experts = state.params["params"]["model"]["layers"]["layer"]["moe"][
        "experts"]
    for name in glu.EXPERTS:
        assert "ep" in jax.tree_util.tree_leaves(
            [list(experts[name].sharding.spec)]), experts[name].sharding
    if zero1:
        # expert optimizer state is ZeRO-sharded over expert-DP (reference
        # NeuronEPZero1Optimizer, zero_redundancy_optimizer.py:163)
        def find_mu(tree):
            return [s for path, s in
                    jax.tree_util.tree_leaves_with_path(tree)
                    if any(f"experts'][{name!r}]"
                           in jax.tree_util.keystr(path)
                           for name in glu.EXPERTS)]
        mu_shardings = find_mu(sh.opt_state)
        assert mu_shardings and all(
            "dp_exp" in [a for p in s.spec if p is not None
                         for a in (p if isinstance(p, tuple) else (p,))]
            for s in mu_shardings), mu_shardings

    step = make_train_step(pm, tx, sh)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), (tp, ep, zero1, dispatch)


@pytest.mark.parametrize("schedule", ["1f1b", "interleaved"])
def test_pp_schedule_matrix(schedule):
    """1F1B / interleaved x sp x zero1 x remat one-step smoke."""
    from neuronx_distributed_tpu.models.llama_pipeline import (
        interleave_pipeline_params)

    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=2, pipeline_parallel_size=2,
        optimizer_config=nxd.OptimizerConfig(zero_one_enabled=True),
        activation_checkpoint_config=nxd.ActivationCheckpointConfig(
            mode="full"),
        sequence_parallel=True)
    mcfg = nxd.configure_model(cfg, tiny_config(
        dtype=jnp.float32, param_dtype=jnp.float32, num_layers=4))
    model = LlamaForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0), (8, 33), 0, mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    pm, params = initialize_parallel_model(
        cfg, model, jax.random.key(1), batch["input_ids"],
        logical_axis_rules=lpp.PIPELINE_LOGICAL_RULES)
    chunks = 2 if schedule == "interleaved" else 1
    if schedule == "interleaved":
        params = interleave_pipeline_params(params, mcfg, 2, 2)
    grad_fn = lpp.make_pipeline_grad_fn(
        mcfg, num_microbatches=4, param_specs=pm.param_specs,
        schedule=schedule, num_chunks=chunks)
    tx, state, sh = initialize_parallel_optimizer(pm, params, 1e-3)
    step = make_train_step(pm, tx, sh, grad_fn=grad_fn)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), schedule


def test_dcn_hybrid_mesh_layout_and_step():
    """Multi-slice layout: dp factors (dcn outer, ici inner) so only DP
    crosses the slow links; the train step runs unchanged (multi-host
    analogue of the reference's torchrun+EFA DP groups)."""
    from neuronx_distributed_tpu.parallel import mesh as ps

    cfg = nxd.neuronx_distributed_config(tensor_parallel_size=2,
                                         dcn_data_parallel_size=2)
    arr = ps._STATE.device_array  # [pp=1, dp=4, cp=1, tp=2]
    assert arr.shape == (1, 4, 1, 2)
    # the first two dp rows form "slice 0" (devices 0..3 on the virtual
    # mesh), the last two "slice 1" — only dp spans slices
    first = {d.id for d in arr[0, :2].flatten()}
    second = {d.id for d in arr[0, 2:].flatten()}
    assert first == {0, 1, 2, 3} and second == {4, 5, 6, 7}

    mcfg = nxd.configure_model(cfg, tiny_config(
        dtype=jnp.float32, param_dtype=jnp.float32, num_layers=2))
    model = LlamaForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0), (8, 33), 0,
                             mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           batch["input_ids"])
    tx, state, sh = initialize_parallel_optimizer(pm, params, 1e-3)
    step = make_train_step(pm, tx, sh)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
