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
                                    "save_attention_and_glu", None],
                         ids=lambda p: p or "default")
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


def test_the_rich_policy_saves_gate_and_up_products():
    """``save_attention_and_glu`` pins the feed-forward's two products at
    model level, stacked over the layers by the scan ([L, B, S, I] =
    [2,1,64,384]), beside the flash pair; ``save_attention`` pins
    neither."""
    ps.initialize_model_parallel(tensor_model_parallel_size=1)
    cfg_l = _pallas_cfg(remat_policy="save_attention",
                        intermediate_size=384)
    cfg_r = _pallas_cfg(remat_policy="save_attention_and_glu",
                        intermediate_size=384)
    ids, labels = _batch(cfg_l, b=1, s=64)
    from flax.core import meta

    params = meta.unbox(
        LlamaForCausalLM(cfg_l).init(jax.random.key(1), ids))
    rep_l = _saved_residual_report(cfg_l, params, ids, labels)
    rep_r = _saved_residual_report(cfg_r, params, ids, labels)
    glu = [line for line in rep_r.splitlines() if "f32[2,1,64,384]" in line]
    assert "f32[2,1,64,384]" not in rep_l
    assert len(glu) == 2, rep_r
    assert "f32[2,1,2,64]" in rep_r and "f32[2,1,64,2,128]" in rep_r


@pytest.mark.parametrize("remat", [False, True],
                         ids=["served", "rematerialised"])
def test_gate_and_up_are_named_only_in_a_model_that_rematerialises(
        monkeypatch, remat):
    """``LlamaMLP`` traces ``glu_gate``/``glu_up`` only under ``cfg.remat``,
    which a served model never sets: its jaxpr holds no ``name`` and its
    lowered text is the text of a module that names nothing (the serving
    families' recorded hashes in ``tests/test_device_scopes.py`` stand)."""
    from neuronx_distributed_tpu.models import llama

    ps.initialize_model_parallel(tensor_model_parallel_size=1)
    cfg = _fp32(remat=remat)
    mlp = llama.LlamaMLP(cfg)
    x = jnp.ones((1, 8, cfg.hidden_size), jnp.float32)
    params = mlp.init(jax.random.key(0), x)

    def traced():
        return (str(jax.make_jaxpr(mlp.apply)(params, x)),
                jax.jit(mlp.apply).lower(params, x).as_text())

    jaxpr, text = traced()
    assert all((f"name={n}" in jaxpr) == remat
               for n in ("glu_gate", "glu_up")), jaxpr
    monkeypatch.setattr(llama, "_kept_glu", lambda cfg, g, u: (g, u))
    unnamed_jaxpr, unnamed_text = traced()
    assert "name=glu" not in unnamed_jaxpr
    if not remat:
        assert (jaxpr, text) == (unnamed_jaxpr, unnamed_text)


# mistral-7b.train-tp4 on one v5e chip of a 2x2 (tp=4, fp32 parameters and
# AdamW moments, bf16 compute, 2 x 4,096 tokens): bytes a chip
_V5E_LIMIT = 16909334528          # memory_stats()["bytes_limit"], 15.75 GiB


def _cell_bytes(layers, accum=1):
    """``(kept, step)`` bytes of the train cell at ``layers`` layers and
    ``accum`` microbatches a step: the feed-forward pair, and the state
    with its gradients (and their accumulator), bf16 copies and logits."""
    cfg = LlamaConfig(hidden_size=4096, intermediate_size=14336,
                      num_layers=layers, num_heads=32, num_kv_heads=8,
                      vocab_size=32768, dtype=jnp.bfloat16)
    sharded = layers * (2 * 4096 * 4096 + 2 * 4096 * 1024
                        + 3 * 4096 * 14336) + 2 * 32768 * 4096
    params = sharded // 4 + (2 * layers + 1) * 4096
    # fp32 parameters and two moments; gradients; one bf16 copy
    held = params * (3 * 4 + 4 * (1 + (accum > 1)) + 2)
    return (cfg.glu_products_bytes(2 // accum * 4096, 4),
            held + cfg.logits_bytes(2 // accum, 4096, 4))


@pytest.mark.parametrize("layers,accum,named,limit,chosen", [
    (11, 1, None, _V5E_LIMIT, "save_attention_and_glu"),
    (12, 1, None, _V5E_LIMIT, "save_attention"),
    (13, 1, None, _V5E_LIMIT, "save_attention"),
    (11, 2, None, _V5E_LIMIT, "save_attention"),
    (9, 2, None, _V5E_LIMIT, "save_attention_and_glu"),
    (11, 1, None, None, "save_attention"),
    (11, 1, None, 0, "save_attention"),
    (13, 1, "save_attention_and_glu", _V5E_LIMIT, "save_attention_and_glu"),
    (11, 1, "nothing", _V5E_LIMIT, "nothing"),
    (11, 1, "dots", None, "dots"),
], ids=["cell", "12-layers", "13-layers", "accumulating", "9-accumulating",
        "no-limit", "zero-limit", "named-rich", "named-nothing",
        "named-no-limit"])
def test_the_rule_reads_the_bytes(layers, accum, named, limit, chosen):
    """``choose_remat_policy``: the cell's 11 layers keep gate's and up's
    products (1.20 GiB a chip beside 11.56 of state, gradients, copies
    and logits, of 15.75), the same job at 12 and 13 layers does not, nor
    at 11 where the step sums two microbatches' gradients into a second
    copy of them (2.48 GiB more), a backend that reports no limit gets
    ``save_attention``, and a name pins its policy whatever the bytes."""
    from neuronx_distributed_tpu.utils.remat import choose_remat_policy

    kept, step = _cell_bytes(layers, accum)
    if (layers, accum) == (11, 1):
        assert kept == 11 * 112 * 2 ** 20
        assert abs(step / 2 ** 30 - 11.56) < 0.01
    assert choose_remat_policy(named, kept_bytes=kept, step_bytes=step,
                               limit_bytes=limit) == chosen


class _Chip:
    """A device as ``memory_limit_bytes`` sees one: whose client holds it
    or not, and what its ``memory_stats()`` says or raises."""

    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        if isinstance(self.stats, Exception):
            raise self.stats
        return self.stats


def _chips(held, stats):
    """Devices of one client: ``held[i]`` says whether this process holds
    the i-th, ``stats[i]`` what it answers."""
    chips = [_Chip(s) for s in stats]
    client = type("Client", (), {"local_devices": lambda self: [
        c for c, mine in zip(chips, held) if mine]})()
    for c in chips:
        c.client = client
    return chips


_NOT_MINE = RuntimeError(
    "MemoryStats is only supported for addressable PjRt devices.")


@pytest.mark.parametrize("held,stats,limit", [
    ([True, True], [{"bytes_limit": 5}, {"bytes_limit": 7}], 5),
    ([False, False, True, True],
     [_NOT_MINE, _NOT_MINE, {"bytes_limit": 7}, {"bytes_limit": 7}], 7),
    ([False, False], [_NOT_MINE, _NOT_MINE], None),
    ([True], [None], None),
    ([True], [{"bytes_in_use": 1}], None),
], ids=["first-held", "device-0-on-another-host", "described", "cpu",
        "no-limit-key"])
def test_the_limit_is_read_from_a_device_this_process_holds(
        held, stats, limit):
    """On several hosts the mesh's first device belongs to one of them:
    every process asks a chip of its own, so all read the same limit and
    trace the same step. A described topology's client holds no device
    and the CPU reports no statistics: no limit."""
    from neuronx_distributed_tpu.utils.device import memory_limit_bytes

    assert memory_limit_bytes(iter(_chips(held, stats))) == limit


def test_a_held_device_that_cannot_answer_is_an_error_not_no_limit():
    from neuronx_distributed_tpu.utils.device import memory_limit_bytes

    with pytest.raises(RuntimeError, match="allocator is gone"):
        memory_limit_bytes(_chips([False, True], [
            _NOT_MINE, RuntimeError("allocator is gone")]))


def test_the_cpu_reports_no_limit():
    """Its devices are held and have no statistics (a described v5e's are
    nobody's: ``tests/test_chip_compile.py``)."""
    from neuronx_distributed_tpu.utils.device import memory_limit_bytes

    assert memory_limit_bytes(jax.devices()) is None


@pytest.mark.parametrize("family,plain", [
    ("llama", True), ("mixtral", False), ("glm_moe_lite", False),
    ("longcat_flash", False), ("nemotron_h", False)])
def test_only_a_plain_layer_is_priced(family, plain):
    """A family whose layer, blocks or feed-forward are its own holds
    bytes a layer that the train step's builder does not count: its
    rematerialised layers keep what they kept, whatever the chip has."""
    import importlib
    import types

    from neuronx_distributed_tpu.trainer import trainer

    module = importlib.import_module(
        "neuronx_distributed_tpu.models." + family)
    config = next(c for c in vars(module).values()
                  if isinstance(c, type) and issubclass(c, LlamaConfig)
                  and c.__module__ == module.__name__)
    assert (config.plain_layers(object.__new__(config))) == plain
    if plain:
        return
    ps.initialize_model_parallel(tensor_model_parallel_size=1)
    cfg = types.SimpleNamespace(remat=True, remat_policy=None,
                                plain_layers=lambda: False)
    pm = types.SimpleNamespace(module=types.SimpleNamespace(cfg=cfg))
    assert trainer._module_for_step(
        pm, ps.get_mesh(), None, None, (2, 64), False) is pm.module


@pytest.mark.parametrize("limit,named,accum,chosen,kept", [
    (None, None, 1, "save_attention", 0),
    (1 << 30, None, 1, "save_attention_and_glu", 65536),
    (1 << 30, None, 2, "save_attention_and_glu", 32768),
    (1 << 16, None, 1, "save_attention", 0),
    (1 << 30, "nothing", 1, "nothing", 0),
], ids=["no-limit", "room", "room-accumulating", "no-room", "named"])
def test_the_bound_step_keeps_what_the_chip_has_bytes_for(
        monkeypatch, limit, named, accum, chosen, kept):
    """``make_train_step`` over a bound tp axis: the CPU reports no limit
    and traces today's program; told a limit with room, the step's layers
    keep gate's and up's products (2 layers x 2 x 128 rows x 32 columns
    of float32 a chip; half the rows a pass where the step sums two
    microbatches), and the gauge says which policy and how many bytes;
    the step's loss and gradient norm are the same either way."""
    from neuronx_distributed_tpu.obs.metrics import get_registry
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_optimizer, make_train_step, trainer)

    ps.destroy_model_parallel()
    # nxdlint: disable=plan  -- the rings engage from tp=4: the bound step
    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=4,
        activation_checkpoint_config=nxd.ActivationCheckpointConfig(
            mode="full"))
    mcfg = dataclasses.replace(
        nxd.configure_model(cfg, _fp32(remat_policy=named)),
        dtype=jnp.float32)
    ids, labels = _batch(mcfg, b=4, s=64)
    batch = {"input_ids": ids, "labels": labels}
    pm, params = initialize_parallel_model(
        cfg, LlamaForCausalLM(mcfg), jax.random.key(0), ids)
    tx, state, shardings = initialize_parallel_optimizer(pm, params)
    _, lean = make_train_step(pm, tx, shardings, donate=False,
                              grad_accum_steps=accum)(state, batch)
    monkeypatch.setattr(trainer, "memory_limit_bytes", lambda devices: limit)
    reg = get_registry()
    was = reg.enabled
    reg.enable()
    try:
        _, metrics = make_train_step(pm, tx, shardings, donate=False,
                                     grad_accum_steps=accum)(state, batch)
        gauge = reg.get("nxd_train_remat_kept_bytes")
        assert gauge.labels(policy=chosen).value == kept
    finally:
        if not was:
            reg.disable()
    for name in ("loss", "grad_norm"):
        np.testing.assert_allclose(float(metrics[name]), float(lean[name]),
                                   rtol=1e-5)


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
