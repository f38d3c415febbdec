"""The default train step binds its mesh when the tp rings would engage.

``make_train_step`` with the default loss computes loss and gradients
inside ``shard_map`` when the tp axis has ``cm.MIN_AUTO_AXIS_SIZE`` ranks
and the batch's sequence tiles over it (``trainer._tp_rings_engage``), so
the projections take their decomposed collective-matmuls; below that it is
the GSPMD step, text for text. There it also shards the residual stream
over the sequence (``trainer._sharded_stream_cfg``): a row-parallel exit is
its reduce-scatter ring and the next entry's all-gather ring carries the
rest, where the model can take it and no dropout stream is threaded. Tiny
llama, float32, the 8-device CPU mesh (tp=4 x dp=2), full remat and ZeRO-1
as the train cell has them.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu import obs
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_config
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.trainer import (initialize_parallel_model,
                                             initialize_parallel_optimizer,
                                             make_train_step)
from neuronx_distributed_tpu.trainer import trainer as tr
from remat_checks import assert_flash_forward_runs, flash_kernel_calls


def _step_and_state(tp=4, seq=16, model_kw=None, step_kw=None, **cfg_kw):
    ps.destroy_model_parallel()
    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=tp,
        optimizer_config=nxd.OptimizerConfig(zero_one_enabled=True),
        activation_checkpoint_config=nxd.ActivationCheckpointConfig(
            mode="full"), **cfg_kw)
    # four K/V heads, one a rank as in the train cell (8 over tp=4): fewer
    # heads than ranks replicate them, and a replicated head's q/k/v entry
    # keeps its monolithic all-reduce
    mcfg = nxd.configure_model(
        cfg, tiny_config(**{"param_dtype": jnp.float32, "num_kv_heads": 4,
                            **(model_kw or {})}))
    # configure_model sets the config's compute dtype (bf16): float32 here
    mcfg = dataclasses.replace(mcfg, dtype=jnp.float32)
    model = LlamaForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0), (4, seq + 1), 0,
                             mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           batch["input_ids"])
    tx, state, sh = initialize_parallel_optimizer(pm, params,
                                                  learning_rate=1e-2)
    return make_train_step(pm, tx, sh, donate=False,
                           **(step_kw or {})), state, batch


def _gspmd(monkeypatch):
    """The step as it was: the rule says no, whatever the mesh."""
    monkeypatch.setattr(tr, "_tp_rings_engage", lambda *a: False)


def _run(step, state, batch, steps=3):
    """Losses and, a step, the optimizer's moments: AdamW's ``mu`` is
    linear in the gradients, so every leaf of it is every gradient leaf."""
    losses, moments = [], []
    for _ in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        moments.append([np.asarray(m) for m in jax.tree_util.tree_leaves(
            state.opt_state) if getattr(m, "ndim", 0) > 0])
    return losses, moments


def _scan_bodies(jaxpr, out=None):
    """``[{(primitive, result shapes)}]`` of a jaxpr's ``scan`` bodies, one
    set a scan in program order with what its body nests: the layer scan's
    forward, then its backward with the recomputation."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(_inside(eqn, set()))
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _scan_bodies(sub, out)
    return out


def _inside(eqn, seen):
    for sub in jax.core.jaxprs_in_params(eqn.params):
        for inner in sub.eqns:
            seen.add((inner.primitive.name,
                      tuple(tuple(v.aval.shape) for v in inner.outvars)))
            _inside(inner, seen)
    return seen


def _scan_body_primitives(jaxpr):
    return {name for body in _scan_bodies(jaxpr) for name, _ in body}


def _of_shape(body, primitive, shape):
    return [s for name, shapes in body if name == primitive
            for s in shapes if s == shape]


def test_default_step_at_tp4_is_the_bound_step_and_equals_gspmd(monkeypatch):
    step, state, batch = _step_and_state()
    forward, backward = _scan_bodies(
        jax.make_jaxpr(step)(state, batch).jaxpr)
    # the residual of one data-parallel rank, whole: [2, 16, hidden]. The
    # forward's layers reduce and gather nothing of that shape, only hops
    # of a ring; the backward all-gathers it for dW alone (section 7 of
    # PERF.md: the entries' x, the exits' cotangent), and no scan
    # all-reduces it
    residual = (2, 16, tiny_config().hidden_size)
    for body in (forward, backward):
        assert any(name == "ppermute" for name, _ in body)
        assert not _of_shape(body, "psum", residual), sorted(body)
    assert not _of_shape(forward, "all_gather", residual), sorted(forward)
    assert "collective_permute" in step.lower(state, batch).as_text()
    bound = _run(step, state, batch)

    _gspmd(monkeypatch)
    step, state, batch = _step_and_state()
    assert "collective_permute" not in step.lower(state, batch).as_text()
    gspmd = _run(step, state, batch)
    np.testing.assert_allclose(bound[0], gspmd[0], rtol=1e-5)
    for got, want in zip(bound[1], gspmd[1]):
        assert len(got) == len(want) and got
        for g, w in zip(got, want):
            # a leaf's small elements are sums that cancel: they keep the
            # rounding of its large ones
            np.testing.assert_allclose(g, w, rtol=1e-5,
                                       atol=5e-5 * np.abs(w).max())


@pytest.mark.parametrize("policy", [None, "nothing"],
                         ids=["default", "nothing"])
def test_the_bound_step_runs_the_flash_forward_once_a_layer(policy):
    """The train cell's path (full checkpointing, the flash kernel inside
    ``shard_map`` with the tp axis bound, one head a rank): the layer scan's
    backward pass recomputes no ``flash_attention_fwd`` unless the model was
    told to keep nothing."""
    model_kw = dict(hidden_size=512, head_dim=128, use_flash_attention=True,
                    attn_force_pallas=True)
    if policy:
        model_kw["remat_policy"] = policy
    step, state, batch = _step_and_state(model_kw=model_kw)
    jaxpr = jax.make_jaxpr(step)(state, batch).jaxpr
    assert "ppermute" in _scan_body_primitives(jaxpr)
    assert_flash_forward_runs(flash_kernel_calls(jaxpr), 1,
                              recomputed=policy == "nothing")


@pytest.mark.parametrize("kw", [
    dict(tp=2), dict(tp=4, seq=18), dict(tp=4, tp_overlap_comm=False)],
    ids=["tp2", "sequence_does_not_tile", "overlap_off"])
def test_below_the_rule_the_step_is_the_gspmd_step(monkeypatch, kw):
    step, state, batch = _step_and_state(**kw)
    text = step.lower(state, batch).as_text()
    _gspmd(monkeypatch)
    step, state, batch = _step_and_state(**kw)
    assert text == step.lower(state, batch).as_text()
    assert "collective_permute" not in text


def _reduced_sync():
    return dict(model_kw=dict(scan_layers=False),
                tp_activation_sync_fraction=0.5)


def _lora():
    from neuronx_distributed_tpu.lora import LoraConfig

    return dict(model_kw=dict(lora=LoraConfig(
        r=4, target_modules=("qkv", "o_proj"))))


def _dropout_rng():
    return dict(step_kw=dict(dropout_rng=jax.random.key(0)),
                model_kw=dict(attention_dropout=0.1))


def _the_models_own_setting():
    return dict(sequence_parallel=True)


def _compression_alone():
    from neuronx_distributed_tpu.parallel import comm_compressed

    return dict(tp=2, step_kw=dict(
        compression=comm_compressed.CompressionConfig(
            dtype="int8", error_feedback=False)))


@pytest.mark.parametrize("case", [
    _reduced_sync, _lora, _dropout_rng, _the_models_own_setting,
    _compression_alone], ids=lambda case: case.__name__.strip("_"))
def test_a_step_that_cannot_shard_its_stream_is_the_step_it_was(
        monkeypatch, case):
    """The bound step where the residual stream stays as the model has it:
    exits that a reduce-scatter cannot elide, adapters that read the
    gathered activations, a dropout stream shared across tp, a model that
    shards the stream itself, and ``compression=`` below the rings' rule
    (the explicit path with no ring to hide a gather in). Each lowers the
    text it lowers with the layout's choice taken out."""
    step, state, batch = _step_and_state(**case())
    text = step.lower(state, batch).as_text()
    # the explicit path all the same: shard_map's manual axes
    assert "sdy.manual_computation" in text or "shard_map" in text
    monkeypatch.setattr(tr, "_sharded_stream_cfg", lambda cfg: None)
    step, state, batch = _step_and_state(**case())
    assert text == step.lower(state, batch).as_text()


@pytest.mark.parametrize("case,ops,shards", [
    (dict, {"all_gather_matmul", "matmul_reduce_scatter"}, 4),
    (_dropout_rng, {"copy_matmul", "matmul_all_reduce"}, 1)],
    ids=["sharded", "replicated"])
def test_the_bound_step_counts_its_ring_decisions(case, ops, shards):
    """With the stream sharded over the sequence q/k/v, gate/up and the
    head enter through ``all_gather_matmul`` and ``o_proj`` and ``down``
    leave through ``matmul_reduce_scatter`` (the layer scan's body is one
    site each): no exit is an all-reduce with a gather standing behind it.
    The step as it was (a ``dropout_rng``) enters through ``copy_matmul``
    and leaves through ``matmul_all_reduce``. The gauge says which."""
    obs.reset()
    obs.enable()
    try:
        step, state, batch = _step_and_state(**case())
        step.lower(state, batch)
        registry = obs.get_registry()
        seen = {(c.labels["impl"], c.labels["op"]): c.value for c in
                registry.get("nxd_tp_collective_matmuls_total").children()}
        gauge = registry.get("nxd_train_residual_sequence_shards").value
    finally:
        obs.disable()
        obs.reset()
    assert set(seen) == {("decomposed", op) for op in ops}, seen
    assert all(v >= 2 for v in seen.values()), seen
    assert gauge == shards
