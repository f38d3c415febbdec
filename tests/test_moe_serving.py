"""MoE serving: one-executable invariant + quantized-dispatch parity.

Routing is data, not shape: a blockwise mixtral `ServingEngine` must keep
`compile_count() == 1` while successive requests light up disjoint expert
sets. And the quantized EP dispatch wire must not change what the server
emits: greedy tokens under `moe_ep_wire_dtype="int8"` match fp32 on the
phase-mesh path (`inference/moe_serving.py`).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                      ServingEngine)
from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                    tiny_moe_config)
from neuronx_distributed_tpu.parallel import mesh as ps


def _blockwise_engine(num_blocks=32):
    ps.initialize_model_parallel()
    cfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                          moe_dispatch="blockwise", moe_block_size=32)
    params = meta.unbox(MixtralForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    eng = ServingEngine(cfg, params, EngineConfig(
        block_size=4, num_blocks=num_blocks, max_slots=2,
        max_blocks_per_seq=8, token_budget=8, kv_dtype=jnp.float32))
    return cfg, eng


def test_blockwise_engine_compiles_once_under_shifting_expert_load():
    cfg, eng = _blockwise_engine()
    rng = np.random.RandomState(1)
    # prompts from disjoint vocab bands shift which experts the router
    # lights up between submissions; blockwise metadata keeps every shape
    # static, so no submission may add an executable
    for i, (lo, hi) in enumerate(((0, 64), (128, 192), (192, 256))):
        eng.submit(rng.randint(lo, hi, (5 + i,)).tolist(), 4, uid=str(i))
        eng.step()
    res = eng.run()
    assert {r.status for r in res.values()} == {"completed"}
    assert all(len(r.tokens) == 4 for r in res.values())
    assert eng.compile_count() == 1


def test_blockwise_engine_matches_capacity_engine_tokens():
    # at tiny_moe_config's default capacity (factor 2.0, no drops at
    # these lengths) the two dispatch programs serve the same checkpoint
    # to the same greedy tokens
    ps.initialize_model_parallel()
    base = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32)
    params = meta.unbox(MixtralForCausalLM(base).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    prompt = np.random.RandomState(3).randint(0, 256, (7,)).tolist()

    toks = {}
    for mode in ("capacity", "blockwise"):
        cfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                              moe_dispatch=mode, moe_block_size=32)
        eng = ServingEngine(cfg, params, EngineConfig(
            block_size=4, num_blocks=16, max_slots=2,
            max_blocks_per_seq=8, token_budget=8, kv_dtype=jnp.float32))
        eng.submit(list(prompt), 6, uid="p")
        res = eng.run()
        assert res["p"].status == "completed"
        toks[mode] = res["p"].tokens
    assert toks["blockwise"] == toks["capacity"]


@pytest.mark.slow
def test_phase_generate_int8_dispatch_matches_fp32_tokens():
    """The quantized EP wire engages on the TKG phase mesh (bound ep=4)
    yet greedy tokens match the fp32 wire — dispatch quantization noise
    stays below the argmax margin at serving scale, and the executables
    differ only in wire format, not routing."""
    from neuronx_distributed_tpu.inference.moe_serving import (
        moe_phase_generate)
    from neuronx_distributed_tpu.trainer import initialize_parallel_model

    toks = {}
    for wire in ("fp32", "int8"):
        ps.destroy_model_parallel()
        cfg = nxd.neuronx_distributed_config(tensor_parallel_size=2,
                                             expert_parallel_size=2)
        mcfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32,
                               moe_dispatch="blockwise", moe_block_size=8,
                               moe_ep_wire_dtype=wire)
        model = MixtralForCausalLM(mcfg)
        ids = jax.random.randint(jax.random.key(7), (2, 8), 0,
                                 mcfg.vocab_size)
        pm, params = initialize_parallel_model(cfg, model,
                                               jax.random.key(8), ids)
        plen = jnp.full((2,), 8, jnp.int32)
        got = moe_phase_generate(mcfg, params, pm.param_specs, ids, plen,
                                 4, cte=(2, 2), tkg=(1, 4), buckets=(8,),
                                 kv_dtype=jnp.float32)
        toks[wire] = np.asarray(got)
    np.testing.assert_array_equal(toks["int8"], toks["fp32"])
