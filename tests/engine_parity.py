"""What ``test_evabyte.py::test_llama_and_mixtral_serve_as_the_parent_did``
records of a tiny llama or Mixtral engine run on a fixed seed: block
counts and tables step by step, greedy tokens, and the logits of one
packed paged forward. ``python tests/engine_parity.py <out.json>`` wrote
``tests/fixtures/engine_parity_pr27.json`` from the parent commit of PR 27
(3e70e00), before the engine asked a cache kind where a position lives."""

import json
import sys

import numpy as np


def record(family: str) -> dict:
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from neuronx_distributed_tpu.inference import paging
    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          ServingEngine)
    from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    kw = dict(dtype=jnp.float32, param_dtype=jnp.float32)
    if family == "mixtral":
        from neuronx_distributed_tpu.models import mixtral as mod

        cfg = mod.tiny_moe_config(capacity_factor=4.0, **kw)
        model, forward = (mod.MixtralForCausalLM(cfg),
                          mod.mixtral_forward_with_cache)
    else:
        from neuronx_distributed_tpu.models import llama as mod

        cfg = mod.tiny_config(num_layers=2, **kw)
        model, forward = (mod.LlamaForCausalLM(cfg),
                          mod.llama_forward_with_cache)
    params = meta.unbox(model.init(jax.random.key(0),
                                   jnp.zeros((1, 8), jnp.int32)))
    ecfg = EngineConfig(block_size=4, num_blocks=24, max_slots=3,
                        max_blocks_per_seq=8, token_budget=8,
                        kv_dtype=jnp.float32)
    eng = ServingEngine(cfg, params, ecfg)
    rng = np.random.RandomState(27)
    for i, (n, new) in enumerate([(13, 6), (5, 8), (9, 4), (21, 5)]):
        eng.submit(rng.randint(0, cfg.vocab_size, (n,)).tolist(), new,
                   uid=f"r{i}")
    allocated, tables = [], []
    while eng.has_work():
        eng.step()
        allocated.append(int(eng.allocator.num_allocated))
        tables.append(np.asarray(eng._tables).tolist())
    out = {"max_model_len": int(eng.max_model_len()),
           "allocated": allocated, "tables": tables,
           "tokens": {u: r.tokens for u, r in sorted(eng.results.items())}}
    # one packed forward: a prefill chunk of slot 0 beside pad rows
    cache = paging.init_paged_kv_cache(
        cfg.num_layers, 24, 4, cfg.num_kv_heads, cfg.head_dim_, 3, 8,
        dtype=jnp.float32)
    table = np.full((3, 8), -1, np.int32)
    table[0, :3] = [5, 2, 9]
    cache = cache.replace(block_tables=jnp.asarray(table))
    tok = np.zeros((1, 8), np.int32)
    pos = np.full((1, 8), PAD_POSITION, np.int32)
    slot = np.full((8,), 3, np.int32)
    tok[0, :6] = rng.randint(0, cfg.vocab_size, (6,))
    pos[0, :6], slot[:6] = np.arange(6), 0
    logits, _ = forward(cfg, params, jnp.asarray(tok), jnp.asarray(pos),
                        cache, slot_ids=jnp.asarray(slot))
    out["logits"] = np.asarray(logits[0, :6], np.float32).tolist()
    ps.destroy_model_parallel()
    return out


if __name__ == "__main__":
    from neuronx_distributed_tpu.utils.cpu_mesh import force_cpu_platform

    force_cpu_platform(8)
    with open(sys.argv[1], "w") as f:
        json.dump({fam: record(fam) for fam in ("llama", "mixtral")}, f)
