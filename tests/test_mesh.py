"""Mesh / parallel-state tests.

Golden-layout style follows the reference's
``test/unit_test/parallel_layers/test_parallel_state.py`` (replica-group
fixtures for fixed world sizes).
"""

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.modules import glu
from neuronx_distributed_tpu.parallel import mesh as ps


def test_init_tp8():
    m = ps.initialize_model_parallel(tensor_model_parallel_size=8)
    assert ps.get_tensor_model_parallel_size() == 8
    assert ps.get_data_parallel_size() == 1
    assert m.shape == {"pp": 1, "dp": 1, "cp": 1, "tp": 8}
    assert ps.get_tensor_model_parallel_replica_groups() == [
        [0, 1, 2, 3, 4, 5, 6, 7]]


def test_init_tp2_dp4():
    ps.initialize_model_parallel(tensor_model_parallel_size=2)
    assert ps.get_data_parallel_size() == 4
    tp_groups = ps.get_tensor_model_parallel_replica_groups()
    assert tp_groups == [[0, 1], [2, 3], [4, 5], [6, 7]]
    dp_groups = ps.get_data_parallel_replica_groups()
    assert dp_groups == [[0, 2, 4, 6], [1, 3, 5, 7]]


def test_init_pp2_tp2_dp2():
    ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                 pipeline_model_parallel_size=2)
    assert ps.get_pipeline_model_parallel_size() == 2
    assert ps.get_data_parallel_size() == 2
    pp_groups = ps.get_pipeline_model_parallel_replica_groups()
    # pp is outermost: partner ranks are 4 apart
    assert pp_groups == [[0, 4], [1, 5], [2, 6], [3, 7]]


def test_cp_groups_and_ring():
    ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                 context_parallel_size=2)
    assert ps.get_context_parallel_size() == 2
    assert ps.get_data_parallel_size() == 2
    assert ps.get_context_parallel_ring_pairs() == [(0, 1), (1, 0)]
    cp_groups = ps.get_context_parallel_replica_groups()
    assert cp_groups == [[0, 2], [1, 3], [4, 6], [5, 7]]


def test_expert_mesh_view():
    ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                 expert_model_parallel_size=4)
    # dp = 4, ep = 4 -> dp_exp = 1
    assert ps.get_expert_model_parallel_size() == 4
    assert ps.get_expert_data_parallel_size() == 1
    em = ps.get_expert_mesh()
    assert em.shape == {"pp": 1, "dp_exp": 1, "ep": 4, "tp": 2}
    # TP groups must be identical in both views
    ep_groups = ps.get_expert_model_parallel_replica_groups()
    assert ep_groups == [[0, 2, 4, 6], [1, 3, 5, 7]]


def test_zero1_groups_merge_dp_cp():
    ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                 context_parallel_size=2)
    z = ps.get_zero1_sharding_replica_groups()
    # dp=2, cp=2 merged -> groups of 4
    assert z == [[0, 2, 4, 6], [1, 3, 5, 7]]


def test_invalid_sizes():
    with pytest.raises(ValueError):
        ps.initialize_model_parallel(tensor_model_parallel_size=3)
    with pytest.raises(ValueError):
        ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                     expert_model_parallel_size=8)


def test_uninitialized_raises():
    with pytest.raises(RuntimeError):
        ps.get_mesh()


def test_rank_getters_in_shard_map():
    import jax.numpy as jnp

    mesh = ps.initialize_model_parallel(tensor_model_parallel_size=4)

    def f(x):
        return x + ps.get_tensor_model_parallel_rank()

    out = jax.jit(ps.shard_map(f, mesh,
                                in_specs=P(None, "tp"),
                                out_specs=P(None, "tp")))(jnp.zeros((2, 8)))
    np.testing.assert_array_equal(
        np.asarray(out)[0], [0, 0, 1, 1, 2, 2, 3, 3])


def test_rank_getter_outside_shard_map_raises():
    ps.initialize_model_parallel(tensor_model_parallel_size=4)
    with pytest.raises(RuntimeError):
        ps.get_tensor_model_parallel_rank()


def test_moe_phase_mesh_views():
    """Per-phase (prefill vs decode) TP x EP mesh views (reference
    moe_process_group.py:12): two factorisations of the SAME devices
    coexist without re-initialisation, axis names match the global mesh so
    the expert layers run unchanged, and parity vs the unsharded forward
    holds under both."""
    import numpy as np

    import jax.numpy as jnp
    from flax.core import meta

    from neuronx_distributed_tpu.modules.moe import ExpertMLPs

    ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                 expert_model_parallel_size=2)
    cte = ps.get_moe_phase_mesh(4, 2)   # prefill: wide tp
    tkg = ps.get_moe_phase_mesh(2, 4)   # decode: wide ep
    assert cte is ps.get_moe_phase_mesh(4, 2)  # cached view
    assert dict(cte.shape) == {"dp": 1, "ep": 2, "tp": 4}
    assert dict(tkg.shape) == {"dp": 1, "ep": 4, "tp": 2}
    # same flat device order as the global mesh — views, not new worlds
    flat = [d.id for d in ps._STATE.device_array.reshape(-1)]
    assert [d.id for d in np.asarray(cte.devices).reshape(-1)] == flat
    assert [d.id for d in np.asarray(tkg.devices).reshape(-1)] == flat

    H, I, E, K, T = 16, 32, 8, 2, 8
    x = jax.random.normal(jax.random.key(70), (T, H))
    gates = jax.nn.softmax(
        jax.random.normal(jax.random.key(71), (T, K)), axis=-1)
    idx = jax.random.randint(jax.random.key(72), (T, K), 0, E)
    mod = ExpertMLPs(num_experts=E, hidden_size=H, intermediate_size=I,
                     top_k=K, dtype=jnp.float32, param_dtype=jnp.float32)
    params = meta.unbox(mod.init(jax.random.key(73), x, gates, idx))
    ref, _ = mod.apply(params, x, gates, idx)

    for mesh in (cte, tkg):
        spec = {"params": {
            **dict.fromkeys(glu.EXPERTS, P("ep", None, "tp")),
            "down": P("ep", "tp", None)}}
        got, _ = jax.jit(ps.shard_map(
            lambda p, a, g, i: mod.apply(p, a, g, i), mesh,
            in_specs=(spec, P(), P(), P()), out_specs=(P(), P())))(
                params, x, gates, idx)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=str(dict(mesh.shape)))
