"""MoE tests: routing, dispatch math, TP/EP parity, mixtral training."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta
from jax.sharding import PartitionSpec as P

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu.modules import glu
from neuronx_distributed_tpu.modules.moe import (
    ExpertMLPs, MoE, RouterSinkhorn, RouterTopK, GroupLimitedRouter,
    build_dispatch_combine, compute_capacity)
from neuronx_distributed_tpu.parallel import mesh as ps


def test_dispatch_combine_basic():
    gates = jnp.array([[0.7, 0.3], [0.6, 0.4], [1.0, 0.0]])
    idx = jnp.array([[0, 1], [0, 2], [1, 3]])
    d, c, dropped = build_dispatch_combine(gates, idx, num_experts=4,
                                           capacity=2)
    assert d.shape == (3, 4, 2)
    # expert 0 receives tokens 0 (slot 0) and 1 (slot 1)
    assert float(d[0, 0, 0]) == 1.0 and float(d[1, 0, 1]) == 1.0
    # combine carries the gate values
    assert float(c[0, 0, 0]) == pytest.approx(0.7)
    assert float(c[2, 1, 0]) == pytest.approx(1.0)
    assert float(dropped) == 0.0


def test_dispatch_capacity_drops():
    # 4 tokens all pick expert 0 first; capacity 2 -> 2 dropped first-choices
    gates = jnp.ones((4, 1))
    idx = jnp.zeros((4, 1), jnp.int32)
    d, c, dropped = build_dispatch_combine(gates, idx, num_experts=2,
                                           capacity=2)
    assert float(jnp.sum(d)) == 2.0
    assert float(dropped) == pytest.approx(0.5)


@pytest.mark.parametrize("router_cls,kw", [
    (RouterTopK, dict(top_k=2)),
    (RouterSinkhorn, dict()),
    (GroupLimitedRouter, dict(top_k=2, num_groups=2, topk_groups=1)),
])
def test_routers(router_cls, kw):
    ps.initialize_model_parallel()
    r = router_cls(num_experts=4, dtype=jnp.float32, **kw)
    x = jax.random.normal(jax.random.key(0), (16, 8))
    params = meta.unbox(r.init(jax.random.key(1), x))
    gates, idx, aux = r.apply(params, x)
    assert idx.shape[0] == 16
    assert np.all(np.asarray(idx) >= 0) and np.all(np.asarray(idx) < 4)
    if router_cls is RouterSinkhorn:
        # top-1 gate is the raw softmax prob of the chosen expert
        g = np.asarray(gates)
        assert ((g > 0) & (g <= 1)).all()
    else:
        np.testing.assert_allclose(np.sum(np.asarray(gates), -1), 1.0,
                                   rtol=1e-5)
    assert np.isfinite(float(aux["load_balance_loss"]))
    assert np.isfinite(float(aux["z_loss"]))


def test_group_limited_router_respects_groups():
    ps.initialize_model_parallel()
    r = GroupLimitedRouter(num_experts=8, top_k=2, num_groups=4,
                           topk_groups=1, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (32, 8))
    params = meta.unbox(r.init(jax.random.key(1), x))
    gates, idx, aux = r.apply(params, x)
    # both chosen experts of a token must come from one group of 2
    groups = np.asarray(idx) // 2
    assert (groups[:, 0] == groups[:, 1]).all()


def test_expert_mlps_tp_parity():
    """Experts with tp=4 sharding match the unsharded computation."""
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size=4)
    m = ExpertMLPs(num_experts=4, hidden_size=16, intermediate_size=32,
                   top_k=2, capacity_factor=4.0, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (24, 16))
    gates = jnp.full((24, 2), 0.5)
    idx = jax.random.randint(jax.random.key(1), (24, 2), 0, 4)
    params = meta.unbox(m.init(jax.random.key(2), x, gates, idx))
    dense, _ = m.apply(params, x, gates, idx)

    pspec = {"params": {**dict.fromkeys(glu.EXPERTS, P(None, None, "tp")),
                        "down": P(None, "tp", None)}}
    y, _ = jax.jit(ps.shard_map(
        lambda p, x, g, i: m.apply(p, x, g, i), mesh,
        in_specs=(pspec, P(None, None), P(None, None), P(None, None)),
        out_specs=(P(None, None), P())))(params, x, gates, idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_expert_mlps_ep_parity():
    """ep=4 expert-parallel dispatch (all-to-all) matches unsharded."""
    nxd.neuronx_distributed_config(expert_parallel_size=4)
    em = ps.get_expert_mesh()
    m = ExpertMLPs(num_experts=4, hidden_size=16, intermediate_size=32,
                   top_k=2, capacity_factor=4.0, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (32, 16))
    gates = jnp.full((32, 2), 0.5)
    idx = jax.random.randint(jax.random.key(1), (32, 2), 0, 4)
    params = meta.unbox(m.init(jax.random.key(2), x, gates, idx))
    dense, _ = m.apply(params, x, gates, idx)

    pspec = {"params": {**dict.fromkeys(glu.EXPERTS, P("ep", None, None)),
                        "down": P("ep", None, None)}}
    # tokens sharded over the ep axis (each shard routes its own tokens)
    y, _ = jax.jit(ps.shard_map(
        lambda p, x, g, i: m.apply(p, x, g, i), em,
        in_specs=(pspec, P("ep", None), P("ep", None), P("ep", None)),
        out_specs=(P("ep", None), P())))(params, x, gates, idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_moe_layer_and_mixtral_training():
    from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                        tiny_moe_config)
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model, initialize_parallel_optimizer,
        make_train_step)

    cfg = nxd.neuronx_distributed_config(tensor_parallel_size=2)
    mcfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           capacity_factor=4.0)
    model = MixtralForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0), (8, 33), 0, mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           batch["input_ids"])
    tx, state, sh = initialize_parallel_optimizer(pm, params, 3e-3)
    step = make_train_step(pm, tx, sh)
    losses = []
    for _ in range(10):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses
    assert np.isfinite(losses).all()


@pytest.mark.slow
def test_mixtral_cp_positions_match_dense():
    """Regression: Mixtral under cp must use global rope positions."""
    from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                        tiny_moe_config)
    from neuronx_distributed_tpu.pipeline import spmd_engine as eng
    from neuronx_distributed_tpu.trainer import initialize_parallel_model

    cfg = nxd.neuronx_distributed_config(context_parallel_size=2)
    mesh = ps.get_mesh()
    mcfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           num_layers=1, capacity_factor=4.0)
    model = MixtralForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0), (4, 33), 0, mcfg.vocab_size)
    batch_ids, labels = ids[:, :-1], ids[:, 1:]
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           batch_ids)
    host = jax.tree_util.tree_map(np.asarray, params)
    dense = model.apply(host, batch_ids, labels, method="loss")

    def inner(p, i, l):
        return eng.data_parallel_mean(model.apply(p, i, l, method="loss"))

    sharded = jax.jit(ps.shard_map(
        inner, mesh, in_specs=(pm.param_specs, P(None, "cp"), P(None, "cp")),
        out_specs=P()))(params, batch_ids, labels)
    np.testing.assert_allclose(float(sharded), float(dense), rtol=2e-4)


def test_mixtral_sequence_parallel_matches_dense():
    """Regression: Mixtral SP must gather sequences before routing."""
    from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                        tiny_moe_config)
    from neuronx_distributed_tpu.trainer import initialize_parallel_model
    from neuronx_distributed_tpu.trainer.trainer import _spec_tree

    cfg = nxd.neuronx_distributed_config(tensor_parallel_size=4)
    mesh = ps.get_mesh()
    mcfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           num_layers=1, capacity_factor=4.0,
                           sequence_parallel=True, tp_size=4)
    model = MixtralForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0), (2, 16), 0, mcfg.vocab_size)
    labels = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                mcfg.vocab_size)
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(2),
                                           ids)
    host = jax.tree_util.tree_map(np.asarray, params)
    # dense reference without SP (same params)
    dense_model = MixtralForCausalLM(tiny_moe_config(
        dtype=jnp.float32, param_dtype=jnp.float32, num_layers=1,
        capacity_factor=4.0))
    dense = dense_model.apply(host, ids, labels, method="loss")

    sharded = jax.jit(ps.shard_map(
        lambda p, i, l: model.apply(p, i, l, method="loss"), mesh,
        in_specs=(pm.param_specs, P(None, None), P(None, None)),
        out_specs=P()))(params, ids, labels)
    np.testing.assert_allclose(float(sharded), float(dense), rtol=2e-4)


def test_token_shuffle_roundtrip():
    from neuronx_distributed_tpu.modules.moe.token_shuffling import (
        token_shuffle, token_unshuffle)

    nxd.neuronx_distributed_config(expert_parallel_size=2)
    em = ps.get_expert_mesh()
    x = jnp.arange(32.0).reshape(16, 2)

    def f(x):
        sh, perm = token_shuffle(x, jax.random.key(0))
        back = token_unshuffle(sh, perm)
        return sh, back

    sh, back = jax.jit(ps.shard_map(
        f, em, in_specs=P("dp_exp", None),
        out_specs=(P("dp_exp", None), P("dp_exp", None))))(x)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x))
    assert not np.allclose(np.asarray(sh), np.asarray(x))


def test_token_shuffle_deterministic_per_step():
    """`step=` folds the training step into the key: a fixed (seed, step)
    always shuffles the same way — checkpoint resume or an SDC rewind
    replays the exact permutation — while distinct steps decorrelate."""
    from neuronx_distributed_tpu.modules.moe.token_shuffling import (
        token_shuffle, token_unshuffle)

    nxd.neuronx_distributed_config(expert_parallel_size=2)
    em = ps.get_expert_mesh()
    x = jax.random.normal(jax.random.key(5), (16, 4))

    def run(step):
        def f(xl):
            sh, perm = token_shuffle(xl, jax.random.key(0), step=step)
            return sh, token_unshuffle(sh, perm)
        return jax.jit(ps.shard_map(
            f, em, in_specs=P("dp_exp", None),
            out_specs=(P("dp_exp", None), P("dp_exp", None))))(x)

    sh_a, back_a = run(jnp.uint32(7))
    sh_b, _ = run(jnp.uint32(7))
    # replaying step 7 reproduces the exact shuffle, and it still inverts
    np.testing.assert_array_equal(np.asarray(sh_a), np.asarray(sh_b))
    np.testing.assert_allclose(np.asarray(back_a), np.asarray(x))
    # a different step (and the step-less call) shuffle differently
    sh_c, _ = run(jnp.uint32(8))
    assert not np.array_equal(np.asarray(sh_a), np.asarray(sh_c))
    sh_none, _ = run(None)
    assert not np.array_equal(np.asarray(sh_a), np.asarray(sh_none))


@pytest.mark.slow
def test_dbrx_config_trains():
    from neuronx_distributed_tpu.models.mixtral import (DBRX,
                                                        MixtralForCausalLM)
    import dataclasses

    cfg = nxd.neuronx_distributed_config(tensor_parallel_size=2)
    mcfg = dataclasses.replace(
        DBRX, vocab_size=256, hidden_size=64, intermediate_size=64,
        num_layers=1, num_heads=4, num_kv_heads=2, max_seq_len=64,
        dtype=jnp.float32, param_dtype=jnp.float32, capacity_factor=4.0)
    assert mcfg.num_experts == 16 and mcfg.top_k == 4
    model = MixtralForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0), (4, 17), 0, 256)
    from neuronx_distributed_tpu.trainer import (initialize_parallel_model,
                                                 initialize_parallel_optimizer,
                                                 make_train_step)

    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           ids[:, :-1])
    tx, state, sh = initialize_parallel_optimizer(pm, params, 3e-3)
    step = make_train_step(pm, tx, sh)
    state, m = step(state, {"input_ids": ids[:, :-1], "labels": ids[:, 1:]})
    assert np.isfinite(float(m["loss"]))


def test_token_shuffle_decorrelated_across_shards():
    """Each dp_exp shard must apply a different local permutation (advisor
    finding r1: identical keys degenerate mixing to the fixed all-to-all)."""
    from neuronx_distributed_tpu.modules.moe.token_shuffling import (
        token_shuffle)

    nxd.neuronx_distributed_config(expert_parallel_size=2)
    em = ps.get_expert_mesh()
    x = jnp.arange(64.0).reshape(32, 2)

    def f(x):
        _, perm = token_shuffle(x, jax.random.key(0))
        return perm[None]

    perms = np.asarray(jax.jit(ps.shard_map(
        f, em, in_specs=P("dp_exp", None),
        out_specs=P("dp_exp", None)))(x))
    assert perms.shape[0] > 1
    assert not all((perms[i] == perms[0]).all()
                   for i in range(1, perms.shape[0]))


# ---------------------------------------------------------------------------
# dropless (blockwise) dispatch
# ---------------------------------------------------------------------------

def _blockwise_pair(T=32, H=16, I=32, E=4, K=2, seed=0):
    x = jax.random.normal(jax.random.key(seed), (T, H))
    gates = jax.random.uniform(jax.random.key(seed + 1), (T, K))
    idx = jax.random.randint(jax.random.key(seed + 2), (T, K), 0, E)
    cap = ExpertMLPs(num_experts=E, hidden_size=H, intermediate_size=I,
                     top_k=K, capacity_factor=float(T * K),
                     dtype=jnp.float32)
    blk = ExpertMLPs(num_experts=E, hidden_size=H, intermediate_size=I,
                     top_k=K, dispatch_mode="blockwise", block_size=16,
                     block_i=16, dtype=jnp.float32)
    params = meta.unbox(cap.init(jax.random.key(seed + 3), x, gates, idx))
    return cap, blk, params, x, gates, idx


def test_blockwise_matches_capacity_at_infinite_capacity():
    """Dropless parity gate: with capacity >= T*K the capacity path drops
    nothing, so the Pallas blockwise path must agree exactly — fwd and all
    grads (VERDICT r1 'Done =' criterion)."""
    cap, blk, params, x, gates, idx = _blockwise_pair()
    y_cap, _ = cap.apply(params, x, gates, idx)
    y_blk, aux = blk.apply(params, x, gates, idx)
    np.testing.assert_allclose(np.asarray(y_blk), np.asarray(y_cap),
                               rtol=1e-5, atol=1e-6)
    assert float(aux["dropped_fraction"]) == 0.0

    def loss(m):
        return lambda p, x: jnp.sum(m.apply(p, x, gates, idx)[0] ** 2)

    gc = jax.grad(loss(cap), argnums=(0, 1))(params, x)
    gb = jax.grad(loss(blk), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(gc),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)


def test_blockwise_zero_drop_on_skewed_routing():
    """All tokens routed to one expert: capacity_factor=1 drops most of
    them; blockwise drops none."""
    T, H, I, E, K = 32, 16, 32, 4, 1
    x = jax.random.normal(jax.random.key(9), (T, H))
    gates = jnp.ones((T, K))
    idx = jnp.zeros((T, K), jnp.int32)  # everyone -> expert 0
    blk = ExpertMLPs(num_experts=E, hidden_size=H, intermediate_size=I,
                     top_k=K, dispatch_mode="blockwise", block_size=16,
                     block_i=16, dtype=jnp.float32)
    nodrop = ExpertMLPs(num_experts=E, hidden_size=H, intermediate_size=I,
                        top_k=K, capacity_factor=float(T * K),
                        dtype=jnp.float32)
    dropping = ExpertMLPs(num_experts=E, hidden_size=H, intermediate_size=I,
                          top_k=K, capacity_factor=1.0, dtype=jnp.float32)
    params = meta.unbox(blk.init(jax.random.key(10), x, gates, idx))
    y_blk, aux = blk.apply(params, x, gates, idx)
    y_ref, _ = nodrop.apply(params, x, gates, idx)
    _, aux_drop = dropping.apply(params, x, gates, idx)
    assert float(aux_drop["dropped_fraction"]) > 0.5  # capacity drops
    assert float(aux["dropped_fraction"]) == 0.0      # blockwise doesn't
    np.testing.assert_allclose(np.asarray(y_blk), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-6)


def test_blockwise_tp_parity():
    """Blockwise under shard_map tp=2 (local I shard in the kernel, row-
    parallel exit) matches the unsharded blockwise output."""
    cap, blk, params, x, gates, idx = _blockwise_pair()
    dense, _ = blk.apply(params, x, gates, idx)

    mesh = ps.initialize_model_parallel(tensor_model_parallel_size=2)
    pspec = {"params": {**dict.fromkeys(glu.EXPERTS, P(None, None, "tp")),
                        "down": P(None, "tp", None)}}
    y, _ = jax.jit(ps.shard_map(
        lambda p, x, g, i: blk.apply(p, x, g, i), mesh,
        in_specs=(pspec, P(), P(), P()),
        out_specs=(P(), P())))(params, x, gates, idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_blockwise_decode_small_blocks():
    """Decode-shaped workload (few tokens): small blocks make the grouped
    kernel compute only the routed (token, expert) pairs — the TPU-native
    counterpart of the reference's selective expert loading + fused
    token-gen kernel (expert_mlps_v2.py:595, moe_fused_tkg.py:85)."""
    cap, blk, params, x, gates, idx = _blockwise_pair(T=8)
    blk8 = ExpertMLPs(num_experts=4, hidden_size=16, intermediate_size=32,
                      top_k=2, dispatch_mode="blockwise", block_size=8,
                      block_i=16, dtype=jnp.float32)
    y_ref, _ = cap.apply(params, x, gates, idx)
    y, _ = blk8.apply(params, x, gates, idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_mixtral_blockwise_trains():
    from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                        tiny_moe_config)
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model, initialize_parallel_optimizer,
        make_train_step)

    cfg = nxd.neuronx_distributed_config(tensor_parallel_size=2)
    mcfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           moe_dispatch="blockwise", moe_block_size=16)
    model = MixtralForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(0), (8, 33), 0, mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(1),
                                           batch["input_ids"])
    tx, state, sh = initialize_parallel_optimizer(pm, params, 3e-3)
    step = make_train_step(pm, tx, sh)
    losses = []
    for _ in range(10):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses
    assert np.isfinite(losses).all()


def test_blockwise_every_expert_owns_a_block():
    """Regression (r2 review): an expert with zero routed tokens must still
    own >= 1 block, else the dW kernel never zero-initializes its gradient
    slice and leaves uninitialized memory on TPU."""
    from neuronx_distributed_tpu.modules.moe.blockwise import (
        compute_block_metadata)

    idx = jnp.concatenate([jnp.zeros((8, 1), jnp.int32),
                           jnp.full((8, 1), 2, jnp.int32)])  # expert 1 empty
    _, _, _, block_expert, _, _ = compute_block_metadata(idx, 3, 8)
    owners = set(np.asarray(block_expert).tolist())
    assert {0, 1, 2} <= owners
    # and grads for the empty expert are exactly zero
    cap, blk, params, x, gates, _ = _blockwise_pair(T=16, E=3, K=1)
    idx2 = jnp.where(jnp.arange(16)[:, None] < 8, 0, 2).astype(jnp.int32)
    g = jax.grad(lambda p: jnp.sum(blk.apply(p, x, gates[:, :1], idx2)[0]
                                   ** 2))(params)
    for name in glu.EXPERTS:
        np.testing.assert_array_equal(np.asarray(g["params"][name][1]), 0.0)


@pytest.mark.slow
def test_mixtral_cached_decode_matches_full_forward():
    """MoE serving path: incremental cached decode reproduces the full
    forward logits (the llama decode-parity gate, for mixtral)."""
    from neuronx_distributed_tpu.inference.kv_cache import init_kv_cache
    from neuronx_distributed_tpu.models.mixtral import (
        MixtralForCausalLM, mixtral_forward_with_cache, tiny_moe_config)

    nxd.neuronx_distributed_config()
    cfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                          moe_dispatch="blockwise", moe_block_size=8)
    model = MixtralForCausalLM(cfg)
    ids = jax.random.randint(jax.random.key(60), (1, 8), 0, cfg.vocab_size)
    params = meta.unbox(model.init(jax.random.key(61), ids))
    full, _ = model.apply(params, ids)  # [1, 8, V] (tp-sharded? no, tp=1)

    cache = init_kv_cache(cfg.num_layers, 1, 16, cfg.num_kv_heads,
                          cfg.head_dim_, dtype=jnp.float32)
    outs = []
    for t in range(8):
        logits, cache = mixtral_forward_with_cache(
            cfg, params, ids[:, t:t + 1], jnp.full((1, 1), t, jnp.int32),
            cache)
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.slow
@pytest.mark.parametrize("sp", [False, True])
def test_mixtral_pipeline_matches_dense(sp):
    """MoE x PP: pipelined mixtral (GPipe engine, router aux accumulated
    across stages) matches the dense model's loss and every grad leaf —
    dropless dispatch so per-microbatch grouping can't change drops;
    sp=True covers the SP scatter-after-embed + sp-aware head."""
    from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                        tiny_moe_config)
    from neuronx_distributed_tpu.models import mixtral_pipeline as mpp
    from neuronx_distributed_tpu.trainer import initialize_parallel_model

    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=2, pipeline_parallel_size=2,
        sequence_parallel=sp)
    mcfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           tp_size=2, sequence_parallel=sp,
                           moe_dispatch="blockwise",
                           moe_block_size=16)
    model = MixtralForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(90), (8, 17), 0,
                             mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    pm, params = initialize_parallel_model(
        cfg, model, jax.random.key(91), batch["input_ids"],
        logical_axis_rules=mpp.PIPELINE_LOGICAL_RULES)
    grad_fn = mpp.make_moe_pipeline_grad_fn(mcfg, num_microbatches=4,
                                            param_specs=pm.param_specs)

    host_params = jax.tree_util.tree_map(np.asarray, params)

    # exact dense reference: router aux is nonlinear in tokens, so the
    # pipelined loss is global CE + the MEAN of per-microbatch aux (the
    # reference's microbatched training computes aux per microbatch the
    # same way). dp=2 shards of 4 rows, M=4 -> 8 single-row microbatches.
    from neuronx_distributed_tpu.parallel import loss_functions as lf_mod

    def composite(p):
        ids_, lb = batch["input_ids"], batch["labels"]
        logits, _ = model.apply(p, ids_)
        per_tok = lf_mod.parallel_cross_entropy(logits, lb,
                                                ignore_index=-100)
        ce = jnp.sum(per_tok) / jnp.sum(
            (lb != -100).astype(jnp.float32))
        auxes = []
        for r in range(ids_.shape[0]):
            _, aux = model.apply(p, ids_[r:r + 1])
            auxes.append(aux)
        aux = jnp.mean(jnp.stack(auxes), axis=0)
        return (ce + mcfg.router_aux_coef * aux[0]
                + mcfg.router_z_coef * aux[1])

    dense_loss, dense_grads = jax.value_and_grad(composite)(host_params)
    pp_loss, pp_grads = jax.jit(grad_fn)(params, batch)

    np.testing.assert_allclose(float(pp_loss), float(dense_loss), rtol=2e-4)
    flat_ref = dict(jax.tree_util.tree_leaves_with_path(dense_grads))
    for path, g in jax.tree_util.tree_leaves_with_path(pp_grads):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(flat_ref[path]), rtol=5e-3,
            atol=5e-5, err_msg=jax.tree_util.keystr(path))


@pytest.mark.slow
def test_blockwise_sentinel_empty_decode_parity():
    """Decode mode (sentinel_empty): blocks of experts no token hit become
    sentinels — compute skipped, weight DMA elided — and the forward is
    bit-identical to the default metadata (the measured fused-decode path;
    reference moe_fused_tkg.py:85)."""
    from neuronx_distributed_tpu.modules.moe import blockwise as bw
    from neuronx_distributed_tpu.modules.moe import ExpertMLPs

    H, I, E, K, T = 16, 32, 8, 2, 4
    x = jax.random.normal(jax.random.key(3), (T, H))
    gates = jax.nn.softmax(
        jax.random.normal(jax.random.key(4), (T, K)), axis=-1)
    # routing concentrated on experts {1, 6}: most experts empty
    idx = jnp.asarray([[1, 6], [6, 1], [1, 6], [1, 1]], jnp.int32)

    # metadata: empty experts' blocks are sentinels (id == E)
    *_, be_s, _, _ = bw.compute_block_metadata(idx, E, 4,
                                               sentinel_empty=True)
    *_, be_d, _, _ = bw.compute_block_metadata(idx, E, 4)
    assert int(jnp.sum(be_s == E)) > 0          # some sentinel blocks
    hit = {1, 6}
    real = set(np.asarray(be_s[be_s < E]).tolist())
    assert real == hit, (real, hit)             # only hit experts remain
    assert int(jnp.sum(be_d == E)) == 0         # default keeps all owners

    mk = lambda sent: ExpertMLPs(
        num_experts=E, hidden_size=H, intermediate_size=I, top_k=K,
        dispatch_mode="blockwise", block_size=4, sentinel_empty=sent,
        dtype=jnp.float32, param_dtype=jnp.float32)
    params = meta.unbox(mk(False).init(jax.random.key(5), x, gates, idx))
    y_ref, _ = mk(False).apply(params, x, gates, idx)
    y_dec, _ = mk(True).apply(params, x, gates, idx)
    np.testing.assert_array_equal(np.asarray(y_dec), np.asarray(y_ref))


@pytest.mark.slow
def test_blockwise_router_grads_under_tp():
    """Regression (r2): the blockwise path must tp-reduce expert outputs
    BEFORE the gate combine — reducing after is forward-equivalent but
    silently leaves the gates'/router's gradient shard-partial."""
    from neuronx_distributed_tpu.modules.moe import MoE

    H, I, E, K = 16, 32, 4, 2
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size=2)
    moe = MoE(num_experts=E, hidden_size=H, intermediate_size=I, top_k=K,
              dispatch_mode="blockwise", block_size=16,
              dtype=jnp.float32, param_dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (2, 16, H))
    params = meta.unbox(moe.init(jax.random.key(1), x))
    gd = jax.grad(lambda p, x: jnp.sum(moe.apply(p, x)[0] ** 2),
                  argnums=(0, 1))(params, x)
    pspec = jax.tree_util.tree_map(lambda _: P(), params)
    for name in glu.EXPERTS:
        pspec["params"]["experts"][name] = P(None, None, "tp")
    pspec["params"]["experts"]["down"] = P(None, "tp", None)

    def inner(p, x):
        return jax.grad(lambda p, x: jnp.sum(moe.apply(p, x)[0] ** 2),
                        argnums=(0, 1))(p, x)

    gs = jax.jit(ps.shard_map(inner, mesh, in_specs=(pspec, P()),
                              out_specs=(pspec, P())))(params, x)
    for (pa, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(gs),
                               jax.tree_util.tree_leaves_with_path(gd)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
            err_msg=jax.tree_util.keystr(pa))


def _dense_moe_composite(model, mcfg, batch):
    """Exact dense reference for microbatched MoE training: global CE +
    coef-weighted MEAN of per-row aux (aux is nonlinear in tokens; see
    test_mixtral_pipeline_matches_dense)."""
    from neuronx_distributed_tpu.parallel import loss_functions as lf_mod

    def composite(p):
        ids_, lb = batch["input_ids"], batch["labels"]
        logits, _ = model.apply(p, ids_)
        per_tok = lf_mod.parallel_cross_entropy(logits, lb,
                                                ignore_index=-100)
        ce = jnp.sum(per_tok) / jnp.sum((lb != -100).astype(jnp.float32))
        auxes = [model.apply(p, ids_[r:r + 1])[1]
                 for r in range(ids_.shape[0])]
        aux = jnp.mean(jnp.stack(auxes), axis=0)
        return (ce + mcfg.router_aux_coef * aux[0]
                + mcfg.router_z_coef * aux[1])

    return composite


@pytest.mark.slow
@pytest.mark.parametrize("num_chunks,sp", [(1, False), (2, False), (1, True),
                                           (2, True)])
def test_mixtral_1f1b_matches_dense(num_chunks, sp):
    """MoE x 1F1B (C=1) and interleaved VPP (C=2): the explicit executor
    with aux_weight-seeded router cotangents matches the dense composite
    exactly (C=2 also covers chunk selection in the reversed backward
    drain; sp=True rides SP-sharded activations through the ring with the
    MoE block's own gather/scatter inside each stage — reference
    moe/model.py:154 under NxDPPModel)."""
    from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                        tiny_moe_config)
    from neuronx_distributed_tpu.models import mixtral_pipeline as mpp
    from neuronx_distributed_tpu.models.llama_pipeline import (
        deinterleave_pipeline_params, interleave_pipeline_params)
    from neuronx_distributed_tpu.trainer import initialize_parallel_model

    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=2, pipeline_parallel_size=2,
        sequence_parallel=sp)
    mcfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           num_layers=2 * num_chunks, tp_size=2,
                           sequence_parallel=sp,
                           moe_dispatch="blockwise", moe_block_size=16)
    model = MixtralForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(95), (8, 17), 0,
                             mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    pm, params = initialize_parallel_model(
        cfg, model, jax.random.key(96), batch["input_ids"],
        logical_axis_rules=mpp.PIPELINE_LOGICAL_RULES)
    grad_fn = mpp.make_moe_1f1b_grad_fn(mcfg, num_microbatches=4,
                                        param_specs=pm.param_specs,
                                        num_chunks=num_chunks)
    host_params = jax.tree_util.tree_map(np.asarray, params)
    dense_loss, dense_grads = jax.value_and_grad(
        _dense_moe_composite(model, mcfg, batch))(host_params)

    run_params = params
    if num_chunks > 1:
        run_params = interleave_pipeline_params(host_params, mcfg, 2,
                                                num_chunks)
    pp_loss, pp_grads = jax.jit(grad_fn)(run_params, batch)
    if num_chunks > 1:
        pp_grads = deinterleave_pipeline_params(
            jax.tree_util.tree_map(np.asarray, pp_grads), mcfg, 2,
            num_chunks)
    np.testing.assert_allclose(float(pp_loss), float(dense_loss), rtol=2e-4)
    flat_ref = dict(jax.tree_util.tree_leaves_with_path(dense_grads))
    for path, g in jax.tree_util.tree_leaves_with_path(pp_grads):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(flat_ref[path]), rtol=5e-3,
            atol=5e-5, err_msg=jax.tree_util.keystr(path))


@pytest.mark.slow
def test_mixtral_interleaved_m_not_divisible_matches_dense():
    """MoE interleaved with M % S != 0 (M=6, S=2, C=2): pad microbatches
    run the router on garbage activations, so their aux contribution must
    be masked in BOTH the forward accumulation (f < M_real) and the
    backward aux seeding (b < M_real) — grads stay exact vs the dense
    composite."""
    from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                        tiny_moe_config)
    from neuronx_distributed_tpu.models import mixtral_pipeline as mpp
    from neuronx_distributed_tpu.models.llama_pipeline import (
        deinterleave_pipeline_params, interleave_pipeline_params)
    from neuronx_distributed_tpu.trainer import initialize_parallel_model

    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=2, pipeline_parallel_size=2)
    mcfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                           num_layers=4, tp_size=2,
                           moe_dispatch="blockwise", moe_block_size=16)
    model = MixtralForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(97), (12, 17), 0,
                             mcfg.vocab_size)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    pm, params = initialize_parallel_model(
        cfg, model, jax.random.key(98), batch["input_ids"],
        logical_axis_rules=mpp.PIPELINE_LOGICAL_RULES)
    grad_fn = mpp.make_moe_1f1b_grad_fn(mcfg, num_microbatches=6,
                                        param_specs=pm.param_specs,
                                        num_chunks=2)
    host_params = jax.tree_util.tree_map(np.asarray, params)
    dense_loss, dense_grads = jax.value_and_grad(
        _dense_moe_composite(model, mcfg, batch))(host_params)
    run_params = interleave_pipeline_params(host_params, mcfg, 2, 2)
    pp_loss, pp_grads = jax.jit(grad_fn)(run_params, batch)
    pp_grads = deinterleave_pipeline_params(
        jax.tree_util.tree_map(np.asarray, pp_grads), mcfg, 2, 2)
    np.testing.assert_allclose(float(pp_loss), float(dense_loss), rtol=2e-4)
    flat_ref = dict(jax.tree_util.tree_leaves_with_path(dense_grads))
    for path, g in jax.tree_util.tree_leaves_with_path(pp_grads):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(flat_ref[path]), rtol=5e-3,
            atol=5e-5, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("tp,ep", [(1, 4), (2, 2)])
def test_blockwise_bound_ep_parity_and_grads(tp, ep):
    """Dropless blockwise under a BOUND ep axis (shard_map, optionally x tp)
    must match the unsharded blockwise result exactly — forward, param
    grads, x grads and router-gate grads (reference forward_blockwise EP
    local-expert masking, expert_mlps_v2.py:779-817)."""
    nxd.neuronx_distributed_config(tensor_parallel_size=tp,
                                   expert_parallel_size=ep)
    em = ps.get_expert_mesh()
    cap, blk, params, x, gates, idx = _blockwise_pair()
    dense, _ = blk.apply(params, x, gates, idx)

    pspec = {"params": {**dict.fromkeys(glu.EXPERTS, P("ep", None, "tp")),
                        "down": P("ep", "tp", None)}}
    sharded = jax.jit(ps.shard_map(
        lambda p, x, g, i: blk.apply(p, x, g, i), em,
        in_specs=(pspec, P("ep", None), P("ep", None), P("ep", None)),
        out_specs=(P("ep", None), P())))
    y, aux = sharded(params, x, gates, idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)
    assert float(aux["dropped_fraction"]) == 0.0

    def loss_dense(p, x, g):
        y, _ = blk.apply(p, x, g, idx)
        return jnp.sum(y ** 2)

    # gradients are computed INSIDE the shard_map (the framework's grad_fn
    # convention, trainer.make_train_step): differentiating THROUGH a
    # check_vma=False shard_map boundary from outside deflates sharded-param
    # cotangents by 1/tp (replicated out_specs split the cotangent per rank;
    # weight-grad paths cross no compensating psum) — see
    # parallel/mappings.py docstring
    def inner_grads(p, x, g, i):
        def loss(p, x, g):
            y, _ = blk.apply(p, x, g, i)
            return jnp.sum(y ** 2)  # local token shard's partial loss
        return jax.grad(loss, argnums=(0, 1, 2))(p, x, g)

    ep_grads = jax.jit(ps.shard_map(
        inner_grads, em,
        in_specs=(pspec, P("ep", None), P("ep", None), P("ep", None)),
        out_specs=(pspec, P("ep", None), P("ep", None))))

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(params, x, gates)
    ge = ep_grads(params, x, gates, idx)
    paths_d = jax.tree_util.tree_leaves_with_path(gd)
    paths_e = jax.tree_util.tree_leaves_with_path(ge)
    assert len(paths_d) == len(paths_e) == 5  # gate, up, down, dx, dgates
    for (path, a), (_, b) in zip(paths_d, paths_e):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=5e-4, atol=5e-4,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.slow
def test_moe_phase_meshes_serve_parity():
    """Per-phase TP x EP meshes (VERDICT r4 missing #3, third ask): prefill
    under a wide-TP CTE mesh view and decode under a wide-EP TKG view
    reproduce the single-mesh greedy tokens exactly — the consumer for
    ps.get_moe_phase_mesh (reference moe_process_group.py:12 <-
    expert_mlps_v2.py)."""
    from neuronx_distributed_tpu.inference.kv_cache import init_kv_cache
    from neuronx_distributed_tpu.inference.moe_serving import (
        moe_phase_generate)
    from neuronx_distributed_tpu.models.mixtral import (
        MixtralForCausalLM, mixtral_forward_with_cache, tiny_moe_config)
    from neuronx_distributed_tpu.trainer import initialize_parallel_model

    cfg = nxd.neuronx_distributed_config(tensor_parallel_size=2,
                                         expert_parallel_size=2)
    mcfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                          moe_dispatch="blockwise", moe_block_size=8)
    model = MixtralForCausalLM(mcfg)
    ids = jax.random.randint(jax.random.key(7), (2, 8), 0, mcfg.vocab_size)
    pm, params = initialize_parallel_model(cfg, model, jax.random.key(8),
                                           ids)
    host = jax.tree_util.tree_map(np.asarray, params)
    plen = jnp.full((2,), 8, jnp.int32)

    # single-mesh (tp=1 host) greedy reference via the plain cached path
    cache = init_kv_cache(mcfg.num_layers, 2, 16, mcfg.num_kv_heads,
                          mcfg.head_dim_, dtype=jnp.float32)
    ar = jnp.broadcast_to(jnp.arange(8), (2, 8))
    logits, cache = mixtral_forward_with_cache(mcfg, host, ids, ar, cache)
    ref_toks = []
    tok = jnp.argmax(logits[:, -1], axis=-1)
    pos = plen
    for _ in range(4):
        ref_toks.append(tok)
        logits, cache = mixtral_forward_with_cache(
            mcfg, host, tok[:, None], pos[:, None], cache)
        tok = jnp.argmax(logits[:, 0], axis=-1)
        pos = pos + 1
    ref = np.stack([np.asarray(t) for t in ref_toks], axis=1)

    # phase path: CTE wider-TP (tp=2, ep=2), TKG wide-EP (tp=1, ep=4)
    got = moe_phase_generate(mcfg, params, pm.param_specs, ids, plen, 4,
                             cte=(2, 2), tkg=(1, 4), buckets=(8,),
                             kv_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), ref)
