"""Start the trainer and the paged server on the chip, at Llama-2-7B widths.

The quickest proof that the system's two entry points still run on a TPU:

* ``train`` — ``neuronx_distributed_config`` → ``initialize_parallel_model``
  → ``initialize_parallel_optimizer`` → ``make_train_step`` at sequence 2048
  with the flash kernel and remat, a few steps on one fixed batch;
* ``serve`` — ``ServingEngine`` over a paged pool of 128-slot blocks (fp,
  then int8), prompts of unequal length, greedy tokens compared with a second
  engine that runs the XLA gather reference;
* ``--chips 4`` — only the tensor-parallel trainer (tp=4, sequence parallel,
  ZeRO-1) and the same steps on one device.

Widths are Llama-2-7B's and never cut; depth is the only cut (see
``TRAIN_DEPTH`` / ``SERVE_DEPTH``). Weights and data are made from
``--seed``. One process, no child that needs a device, no other backend: a
platform that is not a TPU is a non-zero exit, and so is any phase that
fails. The last line of standard output is one JSON object
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Depth is the one cut, chosen from ``compiled.memory_analysis()`` of an AOT
# compile of the whole step for a described v5e (15.75 GiB of HBM usable).
TRAIN_DEPTH = 3
SERVE_DEPTH = 16
DEPTH_WHY = (
    "widths are Llama-2-7B's, depth is cut by AOT memory analysis for one "
    "v5e (15.75 GiB): train holds fp32 params + AdamW moments (12 B/param, "
    "9.7 GiB at 3 layers) and 4.2 GiB of gradients and activations at batch "
    "1 x 2048, 13.9 GiB, and is refused at 4 layers; serve is bf16 (0.38 GiB "
    "of params and 128 MiB of pool a layer) and bounded by the XLA reference "
    "engine it is compared with, whose gathers and second pool copy bring "
    "it to 13.1 GiB at 16 layers, refused at 24")
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 1, 2048, 5
BLOCK_SIZE, NUM_BLOCKS, MAX_BLOCKS_PER_SEQ = 128, 64, 12
TOKEN_BUDGET, MAX_SLOTS, NEW_TOKENS = 32, 8, 32
PROMPT_LENS = (100, 260, 515, 770, 1030, 1290, 1500)
# bf16 activations carry 8 bits of mantissa: two correct attention
# implementations agree on a logit (std ~1.3 here) to about 1e-2; a wrong
# mask or scale moves logits by O(1). The int8 pool re-quantises K/V whose
# inputs already differ by a bf16 ulp, which can flip a code (1/127 of a
# row's max), so it gets twice the room.
LOGIT_ATOL = {False: 0.125, True: 0.25}
# tp=4 and tp=1 sum the same bf16 products in another order
TP_LOSS_ATOL = 0.05


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def device_report() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def check_device(chips: int) -> dict:
    """The only accepted platform is a TPU with the chips asked for."""
    dev = device_report()
    say("device", **dev)
    check(dev["platform"] == "tpu",
          f"needs a TPU, found platform={dev['platform']!r}; this script "
          "never runs on another backend")
    check(dev["count"] == chips,
          f"asked for {chips} chip(s), JAX reports {dev['count']}")
    return dev


def llama2_7b(depth: int, **kw):
    from neuronx_distributed_tpu.models import llama

    return dataclasses.replace(llama.LLAMA2_7B, num_layers=depth, **kw)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _fixed_batch(vocab: int, batch: int, seq: int, seed: int) -> dict:
    ids = np.random.RandomState(seed).randint(
        0, vocab, (batch, seq + 1)).astype(np.int32)
    return {"input_ids": jnp.asarray(ids[:, :-1]),
            "labels": jnp.asarray(ids[:, 1:])}


def _collectives(hlo: str) -> dict:
    return {op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
            for op in ("all-gather", "reduce-scatter", "all-reduce",
                       "collective-permute", "all-to-all")}


def train_phase(mcfg, *, batch: int, seq: int, seed: int, tp: int = 1,
                sequence_parallel: bool = False, devices=None,
                phase: str = "train") -> dict:
    """Five optimizer steps on one fixed batch through the trainer's entry
    points. Passes when every loss is finite, the last is below the first,
    and the compiled step holds the flash kernel exactly when the
    dispatcher says these shapes take it on this backend."""
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.trainer import (
        initialize_parallel_model, initialize_parallel_optimizer,
        make_train_step)
    from neuronx_distributed_tpu.utils.device import on_tpu

    ps.destroy_model_parallel()
    cfg = nxd.neuronx_distributed_config(
        tensor_parallel_size=tp,
        optimizer_config=nxd.OptimizerConfig(zero_one_enabled=True),
        activation_checkpoint_config=nxd.ActivationCheckpointConfig(
            mode="full"),
        sequence_parallel=sequence_parallel,
        devices=devices)
    mcfg = nxd.configure_model(cfg, dataclasses.replace(
        mcfg, max_seq_len=seq, use_flash_attention=True))
    model = llama.LlamaForCausalLM(mcfg)
    data = _fixed_batch(mcfg.vocab_size, batch, seq, seed)
    pm, params = initialize_parallel_model(
        cfg, model, jax.random.key(seed), data["input_ids"])
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    tx, state, state_shardings = initialize_parallel_optimizer(
        pm, params, learning_rate=1e-4)
    shard_report = _tp_shard_report(params, pm.param_specs, tp) \
        if tp > 1 else None
    del params
    step = make_train_step(pm, tx, state_shardings)

    t0 = time.perf_counter()
    compiled = step.lower(state, data).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    kernel_in_hlo = "tpu_custom_call" in hlo
    attn = "pallas-flash" if kernel_in_hlo else "xla"
    say(phase, depth=mcfg.num_layers, params=n_params, batch=batch, seq=seq,
        tp=tp, sequence_parallel=sequence_parallel, zero1=True,
        remat=mcfg.remat, attention=attn,
        compile_s=round(compile_s, 2))
    # flash_attention's auto-dispatch takes the kernel on a TPU and only
    # there; a TPU step without it has fallen to the XLA path in silence
    check(kernel_in_hlo == on_tpu(),
          f"{phase}: tpu_custom_call in the step's HLO is {kernel_in_hlo} "
          f"on backend {jax.default_backend()!r}")

    # steps 0-2 end in block_until_ready, steps 3.. in a host fetch of the
    # loss: if the first were no barrier its time would be the dispatch's
    losses, step_s = [], []
    for i in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        state, metrics = compiled(state, data)
        if i < 3:
            jax.block_until_ready((state, metrics))
        else:
            float(metrics["loss"])
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    bur, fetch = min(step_s[1:3]), min(step_s[3:])
    say(phase, losses=[round(x, 4) for x in losses],
        step_s=[round(x, 4) for x in step_s],
        step_s_block_until_ready=round(bur, 4),
        step_s_host_fetch=round(fetch, 4),
        block_until_ready_is_barrier=bool(bur > 0.5 * fetch))
    check(all(np.isfinite(losses)), f"{phase}: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"{phase}: loss did not fall over {TRAIN_STEPS} steps: {losses}")
    del state, compiled
    ps.destroy_model_parallel()
    return {"losses": losses, "compile_s": compile_s, "step_s": step_s,
            "attention": attn, "collectives": _collectives(hlo),
            "shards": shard_report, "depth": mcfg.num_layers}


def _tp_shard_report(params, specs, tp: int) -> dict:
    """Every weight whose spec names the tp axis must sit as ``tp`` equal
    shards on ``tp`` distinct devices."""
    from jax.sharding import PartitionSpec

    from neuronx_distributed_tpu.parallel import mesh as ps

    leaves = jax.tree_util.tree_leaves_with_path(params)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, PartitionSpec))
    check(len(leaves) == len(spec_leaves), "params and specs trees differ")
    sharded, devices = 0, set()
    for (path, x), spec in zip(leaves, spec_leaves):
        axes = [a for part in spec if part is not None
                for a in ((part,) if isinstance(part, str) else part)]
        if ps.TP_AXIS not in axes:
            continue
        name = jax.tree_util.keystr(path)
        shards = x.addressable_shards
        held = {s.device for s in shards}
        check(len(held) == tp,
              f"{name}: sits on {len(held)} device(s), expected {tp}")
        for s in shards:
            check(s.data.size * tp == x.size,
                  f"{name}: device {s.device} holds {s.data.size} of "
                  f"{x.size} elements, expected 1/{tp}")
        sharded += 1
        devices |= held
    check(sharded > 0, "no parameter is sharded over tp")
    return {"tp_sharded_weights": sharded,
            "devices": sorted(d.id for d in devices)}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _prompts(vocab: int, lens, seed: int):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, n).tolist() for n in lens]


def _serve_once(mcfg, params, ecfg, prompts, new_tokens: int, phase: str):
    """One engine, all prompts to completion. Returns per-prompt tokens."""
    from neuronx_distributed_tpu.inference.engine import ServingEngine
    from neuronx_distributed_tpu.ops.paged_attention import (
        paged_attention_impl)

    impl = paged_attention_impl(mcfg.head_dim_, ecfg.block_size,
                                mcfg.attn_force_pallas)
    t0 = time.perf_counter()
    engine = ServingEngine(mcfg, params, ecfg)
    uids = [engine.submit(p, new_tokens) for p in prompts]
    results = engine.run()
    run_s = time.perf_counter() - t0
    tokens = []
    for uid, p in zip(uids, prompts):
        r = results[uid]
        check(r.status == "completed" and len(r.tokens) == new_tokens,
              f"{phase}: request {uid} (prompt {len(p)}) ended "
              f"{r.status} with {len(r.tokens)} of {new_tokens} tokens")
        tokens.append(list(r.tokens))
    # the packed step's optimised HLO, from an AOT lower+compile of the
    # engine's own step builder beside the jitted step (a persistent-cache
    # hit; it does not count in compile_count())
    hlo = engine._build_step().lower(
        *engine._example_args(ecfg.token_budget)).compile().as_text()
    kernel_in_hlo = "tpu_custom_call" in hlo
    say(phase, attention=impl, kernel_in_hlo=kernel_in_hlo,
        quantized_pool=ecfg.quantized, requests=len(prompts),
        steps=engine.stats.steps, compile_count=engine.compile_count(),
        run_s_with_compile=round(run_s, 2))
    check(engine.compile_count() == 1,
          f"{phase}: packed step compiled {engine.compile_count()} times")
    check(kernel_in_hlo == (impl == "pallas"),
          f"{phase}: dispatcher says {impl} but tpu_custom_call in the "
          f"packed step's HLO is {kernel_in_hlo}")
    return tokens, impl


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def _probe_chunk(mcfg, params, cache, tokens, positions, slot_ids):
    from neuronx_distributed_tpu.models.llama import llama_forward_with_cache

    logits, cache = llama_forward_with_cache(
        mcfg, params, tokens, positions, cache, slot_ids=slot_ids)
    return logits[0].astype(jnp.float32), cache


def _next_token_logits(mcfg, params, ecfg, tokens) -> np.ndarray:
    """Next-token logits after ``tokens``, through the same paged forward
    the engine's packed step runs (same pool geometry, same chunk width)."""
    from neuronx_distributed_tpu.inference import paging
    from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION

    init = (paging.init_quantized_paged_kv_cache if ecfg.quantized
            else functools.partial(paging.init_paged_kv_cache,
                                   dtype=mcfg.dtype))
    cache = init(mcfg.num_layers, ecfg.num_blocks, ecfg.block_size,
                 mcfg.num_kv_heads, mcfg.head_dim_, 1,
                 ecfg.max_blocks_per_seq)
    n_blocks = -(-len(tokens) // ecfg.block_size)
    table = np.full((1, ecfg.max_blocks_per_seq), -1, np.int32)
    table[0, :n_blocks] = np.arange(n_blocks)
    cache = cache.replace(block_tables=jnp.asarray(table))
    w = ecfg.token_budget
    for a in range(0, len(tokens), w):
        chunk = tokens[a:a + w]
        tok = np.zeros((1, w), np.int32)
        pos = np.full((1, w), PAD_POSITION, np.int32)
        slot = np.full((w,), 1, np.int32)          # 1 = out of range = pad
        tok[0, :len(chunk)] = chunk
        pos[0, :len(chunk)] = np.arange(a, a + len(chunk))
        slot[:len(chunk)] = 0
        logits, cache = _probe_chunk(mcfg, params, cache, jnp.asarray(tok),
                                     jnp.asarray(pos), jnp.asarray(slot))
    return np.asarray(logits[len(chunk) - 1])


def serve_phase(mcfg, *, quantized: bool, seed: int, prompt_lens,
                new_tokens: int, block_size: int, num_blocks: int,
                max_blocks_per_seq: int, token_budget: int,
                max_slots: int) -> dict:
    """The paged server on the auto-dispatched attention against a second
    engine on the XLA gather reference. Passes when every request
    finishes, each engine compiled its packed step once, each step's HLO
    holds the kernel exactly when its dispatcher says so, and the greedy
    tokens agree — or, where a sequence departs, the two paths' logits at
    the first differing step agree within ``LOGIT_ATOL``."""
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import EngineConfig
    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.parallel import mesh as ps
    from neuronx_distributed_tpu.utils.device import on_tpu

    phase = "serve-int8" if quantized else "serve-fp"
    ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    mcfg = dataclasses.replace(mcfg, param_dtype=mcfg.dtype,
                               attn_force_pallas=None)
    ref_cfg = dataclasses.replace(mcfg, attn_force_pallas=False)
    model = llama.LlamaForCausalLM(mcfg)
    params = meta.unbox(jax.jit(model.init)(
        jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)))
    ecfg = EngineConfig(block_size=block_size, num_blocks=num_blocks,
                        max_slots=max_slots,
                        max_blocks_per_seq=max_blocks_per_seq,
                        token_budget=token_budget, quantized=quantized)
    prompts = _prompts(mcfg.vocab_size, prompt_lens, seed)
    say(phase, depth=mcfg.num_layers, block_size=block_size,
        num_blocks=num_blocks, token_budget=token_budget,
        prompt_lens=list(prompt_lens), new_tokens=new_tokens)
    got, impl = _serve_once(mcfg, params, ecfg, prompts, new_tokens, phase)
    ref, ref_impl = _serve_once(ref_cfg, params, ecfg, prompts, new_tokens,
                                phase + "-reference")
    check(impl == ("pallas" if on_tpu() else "xla"),
          f"{phase}: the auto-dispatched engine ran {impl} on backend "
          f"{jax.default_backend()!r}")
    check(ref_impl == "xla", f"{phase}: the reference engine ran {ref_impl}")

    atol = LOGIT_ATOL[quantized]
    same_tokens = total = identical = 0
    worst = 0.0
    for p, a, b in zip(prompts, got, ref):
        first = next((i for i in range(new_tokens) if a[i] != b[i]), None)
        total += new_tokens
        if first is None:
            identical += 1
            same_tokens += new_tokens
            continue
        same_tokens += first
        prefix = p + a[:first]
        la = _next_token_logits(mcfg, params, ecfg, prefix)
        lb = _next_token_logits(ref_cfg, params, ecfg, prefix)
        diff = float(np.max(np.abs(la - lb)))
        worst = max(worst, diff)
        say(phase, departs_at=first, prompt_len=len(p),
            tokens=(a[first], b[first]), max_abs_logit_diff=round(diff, 5),
            top_gap=round(float(la[a[first]] - la[b[first]]), 5),
            atol=atol)
        check(int(np.argmax(la)) == a[first] and int(np.argmax(lb)) == b[first],
              f"{phase}: the probe's argmax does not reproduce the "
              f"engines' tokens at step {first}")
        check(diff <= atol,
              f"{phase}: logits of the two attention paths differ by "
              f"{diff} (> {atol}) at the first departing step")
    say(phase, identical_sequences=f"{identical}/{len(prompts)}",
        token_match_rate=round(same_tokens / total, 4),
        worst_logit_diff=round(worst, 5), atol=atol)
    del params
    ps.destroy_model_parallel()
    return {"attention": impl, "identical": identical,
            "match_rate": same_tokens / total, "worst_logit_diff": worst}


# ---------------------------------------------------------------------------
# --chips 4: tensor parallel against one device
# ---------------------------------------------------------------------------

def tp_phase(mcfg, *, batch: int, seq: int, seed: int, devices) -> dict:
    """tp=len(devices) with sequence parallelism and ZeRO-1, then the same
    steps with tp=1 on the first device. Passes when both pass as
    ``train`` does, every tp-sharded weight sits in equal parts on
    distinct devices, the first losses agree within ``TP_LOSS_ATOL`` and
    the tp step's HLO reduces across devices (which collectives it took
    is printed)."""
    tp = len(devices)
    par = train_phase(mcfg, batch=batch, seq=seq, seed=seed, tp=tp,
                      sequence_parallel=True, devices=list(devices),
                      phase=f"tp{tp}")
    say(f"tp{tp}", **par["shards"])
    # a row-parallel matmul's partial sums must meet somewhere: on the
    # decomposed rings (collective-permute; tp_overlap_comm=None engages
    # them only on a bound axis), as reduce-scatter + all-gather (sequence
    # parallel as written), or as the all-reduce GSPMD may make of both
    coll = par["collectives"]
    if coll["collective-permute"] > 0:
        path = "collective-permute rings"
    elif coll["reduce-scatter"] > 0 and coll["all-gather"] > 0:
        path = "reduce-scatter + all-gather"
    else:
        path = "all-reduce"
    say(f"tp{tp}", collectives=coll, tp_path=path)
    check(coll["collective-permute"] + coll["reduce-scatter"]
          + coll["all-reduce"] > 0,
          f"tp{tp}: the step's HLO reduces nothing across devices: {coll}")
    one = train_phase(mcfg, batch=batch, seq=seq, seed=seed, tp=1,
                      devices=[devices[0]], phase="tp1")
    d0 = abs(par["losses"][0] - one["losses"][0])
    say(f"tp{tp}", first_loss_tp=round(par["losses"][0], 5),
        first_loss_one_device=round(one["losses"][0], 5),
        abs_diff=round(d0, 5), atol=TP_LOSS_ATOL)
    check(d0 <= TP_LOSS_ATOL,
          f"tp{tp}: first-step loss differs from one device by {d0} "
          f"(> {TP_LOSS_ATOL})")
    return {"tp": par, "one": one}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, the batch and the prompts")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve on one chip (default); 4: only "
                         "the tensor-parallel trainer and its one-device "
                         "comparison")
    args = ap.parse_args(argv)

    from neuronx_distributed_tpu.utils.device import place_compile_cache

    dev = check_device(args.chips)
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            cache_events["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    say("device", compile_cache=place_compile_cache(ROOT))

    t0 = time.perf_counter()
    if args.chips == 4:
        say("depth", train=TRAIN_DEPTH, why=DEPTH_WHY)
        tp_phase(llama2_7b(TRAIN_DEPTH), batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                 seed=args.seed, devices=jax.devices())
    else:
        say("depth", train=TRAIN_DEPTH, serve=SERVE_DEPTH, why=DEPTH_WHY)
        train_phase(llama2_7b(TRAIN_DEPTH), batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                    seed=args.seed)
        for quantized in (False, True):
            serve_phase(llama2_7b(SERVE_DEPTH), quantized=quantized,
                        seed=args.seed, prompt_lens=PROMPT_LENS,
                        new_tokens=NEW_TOKENS, block_size=BLOCK_SIZE,
                        num_blocks=NUM_BLOCKS,
                        max_blocks_per_seq=MAX_BLOCKS_PER_SEQ,
                        token_budget=TOKEN_BUDGET, max_slots=MAX_SLOTS)
    say("done", wall_s=round(time.perf_counter() - t0, 1),
        compile_cache_hits=cache_events["hits"],
        compile_cache_misses=cache_events["misses"])
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
