"""SDAR (``sdar_moe``: the Qwen3-MoE layer under a block-causal mask,
generated from by diffusion over blocks) through the model, the paged
forward fed in groups of the block length, the uncover rule on the device
and ``ServingEngine``'s block path, against the benchmark's plain
reference ``benchmarks/reference/sdar_moe_f32.py`` and a plain loop of the
generation rule over it.

Tiny widths, float32, seeded weights: hidden 64, 4 heads over 2 K/V heads
of 32 (so the heads are twice the hidden size, as published), 8 experts
of 32 with the 2 largest, vocabulary 256, blocks of 4
positions in pool blocks of 8.
"""

import dataclasses
import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

from neuronx_distributed_tpu import obs
from neuronx_distributed_tpu.inference import block_serving, paging
from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                      ServingEngine)
from neuronx_distributed_tpu.inference.sampling import (BlockDecoding,
                                                        SamplingConfig,
                                                        sample_with_confidence,
                                                        uncover)
from neuronx_distributed_tpu.models import sdar
from neuronx_distributed_tpu.models.llama import LlamaForCausalLM, tiny_config
from neuronx_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                    tiny_moe_config)
from neuronx_distributed_tpu.parallel import mesh as ps

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402  (benchmarks/)
import family_checks as fc  # noqa: E402  (tests/)
from runners import serve  # noqa: E402
from sdar_faults import FAULTS  # noqa: E402  (tests/)

B, MASK, VOCAB, MAX_LEN = 4, 255, 256, 48
PUBLISHED = dict(
    model_type="sdar_moe", vocab_size=VOCAB, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], attention_bias=False,
    hidden_act="silu", rope_theta=10000, rope_scaling=None,
    sliding_window=None, use_sliding_window=False, rms_norm_eps=1e-6,
    tie_word_embeddings=False, max_position_embeddings=128,
    block_length=B, denoising_steps=4, confidence_threshold=0.9,
    mask_token_id=MASK, initializer_range=0.08, family="sdar_moe",
    reference="sdar_moe_f32")
SOUND = 3e-5        # of the logits' spread: float32 against float32
SURE = 1e-3         # a greedy choice is compared while its margin is over


def _family():
    return harness.load_plugin("families", "sdar_moe")


@fc.once_a_module
def _model(**kw):
    cfg, model, forward = _family().build(
        PUBLISHED, dtype=jnp.float32, param_dtype=jnp.float32,
        moe_block_size=8, **kw)
    shapes = meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                       jnp.zeros((1, 8), jnp.int32)))
    return cfg, model, forward, fc.seeded_weights(shapes)


@fc.once_a_module
def _reference_logits_fn():
    """The reference's logits ``[S, V]`` of one sequence padded to
    ``MAX_LEN`` (what follows a block does not reach it), one program."""
    _, _, _, params = _model()
    ref = harness.load_plugin("reference", "sdar_moe_f32")
    weights = _family().published(params, PUBLISHED)
    fn = jax.jit(lambda toks: ref.forward(weights, toks, PUBLISHED)[0][0])

    def logits(tokens):
        ids = np.zeros((1, MAX_LEN), np.int32)
        ids[0, :len(tokens)] = tokens
        return np.asarray(fn(jnp.asarray(ids)))[:len(tokens)]

    return logits


def _with(cfg, **block):
    return dataclasses.replace(cfg, block_decoding=dataclasses.replace(
        cfg.block_decoding, **block))


# -- the model ---------------------------------------------------------------

def test_the_config_carries_the_block_and_every_other_family_none():
    cfg, _, forward, _ = _model()
    family = cfg.serving_family()
    assert cfg.block_decoding == BlockDecoding(B, 4, 0.9, MASK)
    assert family.block is cfg.block_decoding and family.moe_counts
    assert forward is family.forward is sdar.sdar_forward_with_cache
    assert isinstance(family.cache_kind, paging.CountedFullCache)
    assert cfg.qk_norm and cfg.moe_dispatch == "blockwise"
    assert (cfg.num_experts, cfg.top_k, cfg.intermediate_size) == (8, 2, 32)
    assert set(paging.BLOCK_COUNTERS) <= set(family.counters())
    for other in (tiny_config(), tiny_moe_config()):
        assert other.block_decoding is None
        assert other.serving_family().block is None
        assert not set(paging.BLOCK_COUNTERS) & set(
            other.serving_family().counters())


def test_the_family_refuses_a_check_that_cuts_a_block():
    bad = dict(PUBLISHED, serve=dict(logit_check=dict(group=2)))
    with pytest.raises(ValueError, match="logit_check.group 2"):
        _family().build(bad)
    _family().build(dict(PUBLISHED, serve=dict(logit_check=dict(group=B))))


def test_no_cache_forward_equals_the_reference():
    _, model, _, params = _model()
    tokens = np.random.RandomState(3).randint(0, VOCAB, (2, 22))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(tokens))[0])
    ref = harness.load_plugin("reference", "sdar_moe_f32")
    want, margins = ref.forward(_family().published(params, PUBLISHED),
                                tokens, PUBLISHED)
    assert fc.worst(got, np.asarray(want)) < SOUND
    assert margins.shape == (2, 2, 22)
    # the mask is the block's: a causal mask inside a block is another model
    causal = dataclasses.replace(model.cfg, block_decoding=BlockDecoding(
        1, 1, 0.9, MASK))
    with jax.default_matmul_precision("highest"):
        other = np.asarray(MixtralForCausalLM(causal).apply(
            params, jnp.asarray(tokens))[0])
    assert fc.worst(other, np.asarray(want)) > 100 * SOUND


CHECK = dict(prompt_tokens=16, decode_steps=8, typical_rtol=1e-4,
             outlier_rtol=1e-3, outlier_share=dict(prefill=0.0, decode=0.0))


def _probe(cfg, forward, params, **chk):
    ecfg = EngineConfig(block_size=8, num_blocks=16, max_slots=2,
                        max_blocks_per_seq=4, token_budget=16,
                        kv_dtype=jnp.float32)
    chk = dict(CHECK, **chk)
    with jax.default_matmul_precision("highest"):
        seqs, got = serve.probe_logits(7, cfg, forward, params, ecfg, chk)
    want, _ = serve.reference_logits(
        harness.load_plugin("reference", "sdar_moe_f32"),
        _family().published(params, PUBLISHED), seqs, PUBLISHED, chk)
    return serve.judge_logits(got, np.asarray(want), chk)


def test_paged_forward_in_groups_written_twice_equals_the_reference():
    cfg, _, forward, params = _model()
    assert _probe(cfg, forward, params, group=B, rewrite=True) == []
    assert _probe(cfg, forward, params, group=B, rewrite=True,
                  compare=dict(every=4, tail=4)) == []


def test_the_kernel_takes_a_slots_rows_as_one_group(monkeypatch):
    """The paged forward through the kernel (interpret mode) equals the
    reference as the gather path does, and what reaches the walk and the
    kernel is the family's block length, from the config alone: the step's
    walk is built with ``slot_rows`` 4 and every layer's call carries the
    same (at the toy's two heads a K/V head a group of 8 stacked rows, a
    slot's four rows' heads; at the published 8, 32)."""
    from neuronx_distributed_tpu.ops import paged_attention as pa

    cfg, _, forward, params = _model(attn_force_pallas=True)
    assert cfg.attn_force_pallas and cfg.block_decoding.block_length == B
    seen = {"walk": [], "kernel": []}
    step_walk, kernel = pa.step_walk, pa._paged_attention_pallas

    def walk_spy(*a, **kw):
        seen["walk"].append(kw.get("slot_rows"))
        return step_walk(*a, **kw)

    def kernel_spy(*a, **kw):
        seen["kernel"].append((kw.get("slot_rows"),
                               type(kw["walk"]).__name__))
        return kernel(*a, **kw)

    monkeypatch.setattr(pa, "step_walk", walk_spy)
    monkeypatch.setattr(pa, "_paged_attention_pallas", kernel_spy)
    assert _probe(cfg, forward, params, group=B, rewrite=True) == []
    assert set(seen["walk"]) == {B}
    assert set(seen["kernel"]) == {(B, "RunWalk")}
    assert len(seen["kernel"]) == len(seen["walk"])    # once a traced scan
    assert pa.narrow_rows(2, B) == 8 and pa.narrow_rows(8, B) == 32
    # the toy's widths, a decode group of one slot: narrow on its group
    served = np.tile(np.array([[3, 5, -1, -1]]), (B, 1))
    assert pa.pair_kinds(served, 8, 8, slot_rows=B).tolist() == [2, 0, 0]
    assert pa.pair_kinds(served, 8, 8).tolist() == [0, 0, 2]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_put_into_the_program_fails_the_check(fault):
    """A causal mask inside the block, the last chosen expert left out,
    gates that are not renormalised, q and k without their norm, and a
    first writing left live each fail the serving check that the sound
    program passes (the decode part's limits, and for all but the last
    the prefill part's)."""
    cfg, _, forward, params = _model()
    with FAULTS[fault]():
        # a config of its own, so the step is traced under the fault
        why = _probe(dataclasses.replace(cfg, rope_theta=10000.0 + 1e-3),
                     forward, params, group=B, rewrite=True)
    assert any("decode" in w for w in why), (fault, why)
    assert any("prefill" in w for w in why) == (
        fault != "first_writing_left_live"), (fault, why)


# -- the expert banks' stacks through the layer scan --------------------------

def _probe_logits(cfg, forward, params):
    ecfg = EngineConfig(block_size=8, num_blocks=16, max_slots=2,
                        max_blocks_per_seq=4, token_budget=16,
                        kv_dtype=cfg.dtype)
    chk = dict(CHECK, group=B, rewrite=True)
    with jax.default_matmul_precision("highest"):
        return serve.probe_logits(7, cfg, forward, params, ecfg, chk)[1]


def _grouped_calls():
    """The ``operands`` that ``nxd_moe_grouped_calls_total`` counted since
    the registry was last emptied (a trace counts its call site once, and
    a jit may trace twice), which this empties again."""
    family = obs.get_registry().get("nxd_moe_grouped_calls_total")
    seen = set() if family is None else {
        child.labels["operands"] for child in family.children()
        if child.value > 0}
    obs.get_registry().reset()
    return seen


@pytest.fixture
def kernel_forced(monkeypatch):
    """The grouped product's Mosaic kernel (interpret mode here) where the
    dispatcher would take its reference, and the registry on and empty."""
    from neuronx_distributed_tpu.ops import blockwise_moe

    monkeypatch.setattr(blockwise_moe, "use_pallas", lambda force=None: True)
    obs.enable()
    obs.get_registry().reset()


def test_the_scan_hands_the_kernel_the_stacks_and_each_layer_reads_its_own(
        kernel_forced, monkeypatch):
    """Under ``run_layers`` the forced kernel is handed the banks' stacks
    and the layer's index (the counter says ``stack``) and the packed step
    delivers the reference path's logits and tokens, at layers whose banks
    differ; a scan that names layer 0 to every layer passes the kernel's
    own parity and fails here."""
    from neuronx_distributed_tpu.modules import layer_stack
    from neuronx_distributed_tpu.ops import blockwise_moe

    cfg, _, forward, params = _model()
    _grouped_calls()            # the model's own init, outside any scan
    bank = params["params"]["model"]["layers"]["layer"]["moe"]["experts"]
    assert all(not np.array_equal(bank[n][0], bank[n][1])
               for n in ("gate", "up", "down"))
    assert _probe(cfg, forward, params, group=B, rewrite=True) == []
    kernel = _probe_logits(cfg, forward, params)
    assert _grouped_calls() == {"stack"}

    monkeypatch.setattr(blockwise_moe, "use_pallas", lambda force=None: False)
    reference = _probe_logits(cfg, forward, params)
    assert _grouped_calls() == {"stack"}
    np.testing.assert_array_equal(kernel, reference)   # so the tokens too
    # without the second collection the slices go to the [E, H, I] entry:
    # the same logits, to the bit
    monkeypatch.setattr(layer_stack, "beside", lambda stack, layer: {})
    sliced = _probe_logits(cfg, forward, params)
    np.testing.assert_array_equal(sliced, reference)
    assert _grouped_calls() == {"slice"}

    monkeypatch.setattr(blockwise_moe, "use_pallas", lambda force=None: True)
    beside = layer_stack.LayerStack
    monkeypatch.setattr(
        layer_stack, "beside", lambda stack, layer: jax.tree_util.tree_map(
            lambda w: beside(w, jnp.zeros_like(layer)), stack))
    why = _probe(cfg, forward, params, group=B, rewrite=True)
    assert any("prefill" in w for w in why) and any("decode" in w
                                                    for w in why), why


def test_a_tree_stored_in_another_dtype_keeps_the_slice(kernel_forced):
    """float32 leaves under a bfloat16 step: a cast of the stack would
    convert every layer's bank for the one the kernel reads, so the
    kernel gets the cast slice as before, and the counter says so."""
    cfg, _, forward, params = _model()
    _grouped_calls()
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    got = _probe_logits(low, forward, params)
    assert _grouped_calls() == {"slice"}
    assert np.isfinite(got).all()
    stored = jax.tree_util.tree_map_with_path(
        lambda path, w: w.astype(jnp.bfloat16)
        if "experts" in jax.tree_util.keystr(path) else w, params)
    same = _probe_logits(low, forward, stored)
    assert _grouped_calls() == {"stack"}
    # bf16 weights either way: cast from the slice, or stored and read
    # where they lie
    np.testing.assert_array_equal(got, same)


# -- the uncover rule --------------------------------------------------------

def _uncover_np(conf, masked, quota, threshold):
    """The rule by hand: a row a block, Python loops."""
    by_t, by_q = np.zeros_like(masked), np.zeros_like(masked)
    for g in range(len(conf)):
        rows = [i for i in range(conf.shape[1]) if masked[g, i]]
        over = [i for i in rows if conf[g, i] > threshold]
        if len(over) >= quota[g]:
            by_t[g, over] = True
        else:
            best = sorted(rows, key=lambda i: (-conf[g, i], i))
            by_q[g, best[:quota[g]]] = True
    return by_t, by_q


UNCOVER_CASES = {
    "all over the threshold": ([.95, .99, .91, .97], [1, 1, 1, 1], 1),
    "none over it": ([.1, .3, .2, .05], [1, 1, 1, 1], 1),
    "fewer over it than the quota": ([.95, .3, .2, .5], [1, 1, 1, 1], 2),
    "ties go to the lower row": ([.4, .4, .4, .4], [1, 1, 1, 1], 2),
    "rows already uncovered stay": ([.99, .2, .99, .3], [0, 1, 0, 1], 1),
    "a block of prompt remainder": ([.99, .99, .99, .1], [0, 0, 0, 1], 1),
    "nothing masked": ([.99, .99, .99, .99], [0, 0, 0, 0], 1),
    "a quota past the masked rows": ([.1, .2, .3, .4], [0, 0, 1, 1], 4),
}


@pytest.mark.parametrize("case", list(UNCOVER_CASES))
def test_uncover_rule_on_the_device_equals_the_rule_by_hand(case):
    conf, masked, quota = UNCOVER_CASES[case]
    conf = np.asarray([conf, conf[::-1]], np.float32)
    masked = np.asarray([masked, masked[::-1]], bool)
    quota = np.asarray([quota, quota], np.int32)
    got = jax.jit(uncover, static_argnums=3)(conf, masked, quota, 0.9)
    want = _uncover_np(conf, masked, quota, 0.9)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)
    opened = np.asarray(got[0]) | np.asarray(got[1])
    assert not (opened & ~masked).any()
    assert not (np.asarray(got[0]) & np.asarray(got[1])).any()


def test_confidence_is_the_drawn_tokens_probability():
    logits = np.random.RandomState(0).randn(5, 40).astype(np.float32) * 3
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    tok, conf = sample_with_confidence(jnp.asarray(logits),
                                       jax.random.key(0),
                                       SamplingConfig(greedy=True))
    np.testing.assert_array_equal(tok, logits.argmax(-1))
    np.testing.assert_allclose(conf, probs.max(-1), rtol=1e-5)
    tok, conf = sample_with_confidence(jnp.asarray(logits),
                                       jax.random.key(1), SamplingConfig())
    np.testing.assert_allclose(conf, probs[np.arange(5), np.asarray(tok)],
                               rtol=1e-5)
    assert BlockDecoding(8, 3).quotas() == (3, 3, 2)
    with pytest.raises(ValueError, match="denoising_steps"):
        BlockDecoding(4, 5)


# -- the engine against a plain loop of the generation rule ------------------

def _loop(prompt, new, block):
    """The generation rule by hand over the reference's logits, greedy:
    ``(tokens, how many of them every choice before was sure, counts)``.
    A choice is sure while the top two logits of every masked row, and
    lie further apart than ``SURE`` (and every confidence is 1e-5 from
    the threshold and 1e-6 from the next)."""
    logits_of = _reference_logits_fn()
    whole = len(prompt) - len(prompt) % B
    stored, rest = list(prompt[:whole]), list(prompt[whole:])
    out, sure, trusted = [], 0, True
    counts = dict(denoise=0, store=0, by_threshold=0, by_quota=0,
                  left_masked=0, already_uncovered=0, stored=0, passes=[])
    while len(out) < new:
        tok = rest + [MASK] * (B - len(rest))
        masked = np.asarray([False] * len(rest) + [True] * (B - len(rest)))
        first, rest, npass = len(tok) - int(masked.sum()), [], 0
        while masked.any():
            lg = logits_of(stored + tok)[len(stored):]
            top = np.sort(lg, -1)[:, -2:]
            conf = np.exp(lg - lg.max(-1, keepdims=True))
            conf = (conf / conf.sum(-1, keepdims=True)).max(-1)
            trusted &= bool(((top[:, 1] - top[:, 0])[masked] > SURE).all())
            trusted &= bool((np.abs(conf - block.confidence_threshold)[masked]
                             > 1e-5).all())
            ranked = np.sort(conf[masked])
            trusted &= bool((np.diff(ranked) > 1e-6).all())
            by_t, by_q = _uncover_np(
                conf[None], masked[None],
                [block.quotas()[npass]], block.confidence_threshold)
            opened = (by_t | by_q)[0]
            counts["denoise"] += 1
            counts["by_threshold"] += int(by_t.sum())
            counts["by_quota"] += int(by_q.sum())
            counts["left_masked"] += int((masked & ~opened).sum())
            counts["already_uncovered"] += int((~masked).sum())
            tok = [int(lg[i].argmax()) if opened[i] else tok[i]
                   for i in range(B)]
            masked, npass = masked & ~opened, npass + 1
        counts["store"] += 1
        counts["stored"] += B
        counts["passes"].append(npass + 1)
        stored += tok
        out += tok[first:]
        if trusted:
            sure = min(len(out), new)
    return out[:new], sure, counts


def _engine(cfg, params, depth=1, **kw):
    base = dict(block_size=8, num_blocks=24, max_slots=3,
                max_blocks_per_seq=6, token_budget=16, kv_dtype=jnp.float32)
    base.update(kw)
    eng = ServingEngine(cfg, params, EngineConfig(**base))
    assert eng._depth == 1
    eng._depth = depth
    return eng


def _prompts(lengths, seed=11):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB - 1, (n,)).tolist() for n in lengths]


def _serve(cfg, params, lengths, new, depth=1, before=None, seed=11, **kw):
    eng = _engine(cfg, params, depth, **kw)
    obs.enable()
    obs.get_registry().reset()
    prompts = _prompts(lengths, seed)
    uids = [eng.submit(p, n) for p, n in zip(prompts, new)]
    steps = 0
    while eng.has_work():
        if before is not None:
            before(eng, steps)
        eng.step()
        steps += 1
        assert steps < 400
    reg = obs.get_registry()
    read = {c.name: [child.value for child in reg.get(c.name).children()]
            for c in paging.BLOCK_COUNTERS}
    hist = reg.get(block_serving.PASSES_HISTOGRAM)
    read["passes"] = sorted(v for child in hist.children()
                            for v in child.samples())
    obs.disable()
    return eng, prompts, [eng.results[u] for u in uids], read


@pytest.fixture(autouse=True)
def _obs_off():
    yield
    obs.disable()
    obs.reset()


# prompt lengths and answers that 4 does not divide, a prompt shorter than
# a block, one that is whole blocks
LENGTHS, NEW = (10, 3, 8), (7, 5, 6)
THRESHOLDS = {"every row at once": 0.0, "the quota alone": 1.5,
              "between": 0.018}


@pytest.mark.parametrize("name", list(THRESHOLDS))
def test_engine_delivers_the_loops_tokens_and_counts_its_passes(name):
    cfg, _, _, params = _model()
    cfg = _with(cfg, confidence_threshold=THRESHOLDS[name])
    eng, prompts, results, read = _serve(cfg, params, LENGTHS, NEW)
    total = dict(denoise=0, store=0, by_threshold=0, by_quota=0,
                 left_masked=0, already_uncovered=0, stored=0, passes=[])
    for prompt, new, result in zip(prompts, NEW, results):
        want, sure, counts = _loop(prompt, new, cfg.block_decoding)
        assert result.status == "completed" and len(result.tokens) == new
        assert sure == new, "the toy's choices are sure at this seed"
        assert result.tokens == want
        for key in total:
            total[key] += counts[key]
    assert eng.compile_count() == 1
    assert eng.stats.tokens_generated == sum(NEW)
    assert eng.allocator.num_allocated == 0 and (eng._tables == -1).all()
    assert read[paging.BLOCK_PASSES.name] == [total["denoise"],
                                              total["store"]]
    assert read[paging.BLOCK_ROWS.name] == [
        total[k] for k in paging.BLOCK_ROWS.kinds]
    assert read[paging.BLOCKS_FINISHED.name] == [total["store"]]
    assert read["passes"] == sorted(total["passes"])
    # every real row of a decode group once; rows uncovered are the tokens
    # delivered and the rows cut from the last blocks
    assert sum(read[paging.BLOCK_ROWS.name]) == B * (total["denoise"]
                                                     + total["store"])
    cut = sum(-(len(p) + n) % B for p, n in zip(prompts, NEW))
    assert total["by_threshold"] + total["by_quota"] == sum(NEW) + cut
    if name == "every row at once":
        assert set(read["passes"]) == {2} and total["by_quota"] == 0
    if name == "the quota alone":
        assert total["by_threshold"] == 0 and max(read["passes"]) == 5
    if name == "between":
        assert total["by_threshold"] and total["by_quota"]


def test_depth_0_delivers_what_depth_1_does():
    cfg, _, _, params = _model()
    cfg = _with(cfg, confidence_threshold=0.018)
    one = _serve(cfg, params, LENGTHS, NEW, depth=1)
    zero = _serve(cfg, params, LENGTHS, NEW, depth=0)
    assert [r.tokens for r in one[2]] == [r.tokens for r in zero[2]]
    assert one[3] == zero[3]
    assert zero[0].compile_count() == 1


def test_a_preemption_in_mid_block_begins_the_block_again():
    cfg, _, _, params = _model()

    def preempt(eng, step):
        victim = eng._slots[1]
        if step == 4 and victim is not None:
            assert victim.block_started and victim.decoding
            eng._preempt_youngest(victim)

    eng, prompts, results, _ = _serve(cfg, params, LENGTHS, NEW,
                                      before=preempt)
    assert eng.stats.preempted == 1
    for prompt, new, result in zip(prompts, NEW, results):
        want, sure, _ = _loop(prompt, new, cfg.block_decoding)
        assert sure == new and result.tokens == want
    assert eng.allocator.num_allocated == 0 and eng.compile_count() == 1


def test_a_pool_that_cannot_hold_every_request_preempts_and_finishes():
    cfg, _, _, params = _model()
    eng, prompts, results, _ = _serve(cfg, params, (20, 18, 8), (9, 10, 6),
                                      num_blocks=7, seed=13)
    assert eng.stats.preempted >= 1
    for prompt, new, result in zip(prompts, (9, 10, 6), results):
        want, sure, _ = _loop(prompt, new, cfg.block_decoding)
        assert sure == new and result.tokens == want
    assert eng.allocator.num_allocated == 0 and (eng._tables == -1).all()
    assert eng.compile_count() == 1


def test_what_the_family_cannot_serve_is_refused_by_name():
    cfg, _, _, params = _model()
    fc.check_refused_features(
        cfg, params, {k: dict(fc.REFUSED_FEATURES)[k]
                      for k in ("speculation", "prefix_sharing")},
        reason=True, block_size=8)
    with pytest.raises(ValueError, match="token_budget"):
        ServingEngine(cfg, params, fc.engine_config(block_size=8,
                                                    token_budget=18))
    with pytest.raises(ValueError, match="block_size"):
        ServingEngine(cfg, params, fc.engine_config(block_size=6,
                                                    token_budget=16))
    with pytest.raises(ValueError, match="disaggregated"):
        ServingEngine(cfg, params, fc.engine_config(block_size=8,
                                                    disaggregated=True))
    fc.check_session_export_is_refused(
        cfg, params, paging.StatePoolPagedCache, paging.CountedFullCache,
        block_size=8)


# -- a family without a block ------------------------------------------------

def _plain(kind):
    ps.initialize_model_parallel()
    if kind == "llama":
        cfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                          num_layers=2)
        model = LlamaForCausalLM(cfg)
    else:
        cfg = tiny_moe_config(dtype=jnp.float32, param_dtype=jnp.float32)
        model = MixtralForCausalLM(cfg)
    return cfg, meta.unbox(model.init(jax.random.key(0),
                                      jnp.zeros((1, 8), jnp.int32)))


def _run_plain(cfg, params):
    eng = ServingEngine(cfg, params, EngineConfig(
        block_size=4, num_blocks=24, max_slots=3, max_blocks_per_seq=8,
        token_budget=8, kv_dtype=jnp.float32))
    schedules = []
    build = eng._build_schedule

    def recorded(*a, **kw):
        decode, prefill = build(*a, **kw)
        schedules.append([(r.uid, tok, pos, produce)
                          for r, tok, pos, produce in decode + prefill])
        return decode, prefill

    eng._build_schedule = recorded
    uids = [eng.submit(p, n)
            for p, n in zip(_prompts((10, 3, 8)), (7, 5, 6))]
    while eng.has_work():
        eng.step()
    lowered = eng._step_fn.lower(*eng._example_args(8))
    scopes = lowered.as_text(debug_info=True)
    assert "nxd.sample" in scopes and "uncover" not in scopes
    return ([eng.results[u].tokens for u in uids], schedules,
            hashlib.sha256(lowered.as_text().encode()).hexdigest())


@pytest.mark.parametrize("kind", ["llama", "mixtral"])
def test_a_family_without_a_block_runs_none_of_the_block_path(kind,
                                                              monkeypatch):
    cfg, params = _plain(kind)
    tokens, schedules, text = _run_plain(cfg, params)
    # a request has one row a step once its prompt is in, and the step
    # holds nothing of the block's
    for rows in schedules:
        for uid in {r[0] for r in rows}:
            mine = [r for r in rows if r[0] == uid]
            assert len(mine) == 1 or not any(r[1] is None for r in mine)
    assert any(r[1] is None for rows in schedules for r in rows)

    def never(*a, **kw):
        raise AssertionError("the block path ran for a family without one")

    for name, member in vars(block_serving.BlockServing).items():
        if callable(member):
            monkeypatch.setattr(block_serving.BlockServing, name, never)
    again = _run_plain(cfg, params)
    assert again[0] == tokens and again[1] == schedules
    assert again[2] == text
