"""What ``test_evabyte.py::test_llama_and_mixtral_serve_as_the_parent_did``
records of a tiny llama or Mixtral engine run on a fixed seed: block
counts and tables step by step, greedy tokens, and the logits of one
packed paged forward. The weights are a fixed set of published tensors
(``gate_proj``, ``w1``, ...) through the checkpoint converter, so a commit
that stores them in another form serves the same model.
``python tests/engine_parity.py <out.json>`` wrote
``tests/fixtures/engine_parity_pr28.json`` from the parent commit of PR 28
(d96f757), where gate and up were one fused leaf; PR 27's fixture held that
commit to 3e70e00, before the engine asked a cache kind where a position
lives, on weights drawn by ``init``, which no tree in the new form draws."""

import json
import sys

import numpy as np


def published(cfg, family: str) -> dict:
    """A published checkpoint's tensors (``[out, in]``) at ``cfg``'s widths
    from a fixed seed (``RandomState`` draws alike on every numpy)."""
    r = np.random.RandomState(28)
    h, i, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim_

    def w(*shape):
        return (r.standard_normal(shape) * 0.1).astype(np.float32)

    sd = {"model.embed_tokens.weight": w(cfg.vocab_size, h),
          "model.norm.weight": 1 + w(h),
          "lm_head.weight": w(cfg.vocab_size, h)}
    for layer in range(cfg.num_layers):
        p = f"model.layers.{layer}."
        sd[p + "self_attn.q_proj.weight"] = w(cfg.num_heads * d, h)
        sd[p + "self_attn.k_proj.weight"] = w(cfg.num_kv_heads * d, h)
        sd[p + "self_attn.v_proj.weight"] = w(cfg.num_kv_heads * d, h)
        sd[p + "self_attn.o_proj.weight"] = w(h, cfg.num_heads * d)
        sd[p + "input_layernorm.weight"] = 1 + w(h)
        sd[p + "post_attention_layernorm.weight"] = 1 + w(h)
        if family == "llama":
            sd[p + "mlp.gate_proj.weight"] = w(i, h)
            sd[p + "mlp.up_proj.weight"] = w(i, h)
            sd[p + "mlp.down_proj.weight"] = w(h, i)
            continue
        sd[p + "block_sparse_moe.gate.weight"] = w(cfg.num_experts, h)
        for e in range(cfg.num_experts):
            q = p + f"block_sparse_moe.experts.{e}."
            sd[q + "w1.weight"] = w(i, h)
            sd[q + "w3.weight"] = w(i, h)
            sd[q + "w2.weight"] = w(h, i)
    return sd


def record(family: str) -> dict:
    import jax
    import jax.numpy as jnp
    from neuronx_distributed_tpu.inference import paging
    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          ServingEngine)
    from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.scripts import checkpoint_converter as cc

    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    kw = dict(dtype=jnp.float32, param_dtype=jnp.float32)
    if family == "mixtral":
        from neuronx_distributed_tpu.models import mixtral as mod

        cfg = mod.tiny_moe_config(capacity_factor=4.0, **kw)
        forward = mod.mixtral_forward_with_cache
        to_nxd = cc.convert_hf_mixtral_to_nxd
    else:
        from neuronx_distributed_tpu.models import llama as mod

        cfg = mod.tiny_config(num_layers=2, **kw)
        forward = mod.llama_forward_with_cache
        to_nxd = cc.convert_hf_llama_to_nxd
    params = jax.tree_util.tree_map(jnp.asarray,
                                    to_nxd(published(cfg, family), cfg))
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
        steps = eng.stats.steps
        eng.step()
        if eng.stats.steps == steps:
            continue    # the last call packs nothing: it lands a step
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
