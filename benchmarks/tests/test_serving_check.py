"""The serving cells' check of ``correct`` (``runners/serve.py``:
``serving_cache``, ``probe_logits``, ``compared_positions``,
``reference_logits``, ``logit_errors``), in this process on the CPU at toy
widths: the cache comes from the engine's own constructor, the columns
from the cache kind, and a long context is compared at chosen positions.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import harness  # noqa: E402
import run as bench_run  # noqa: E402
from runners import models, serve  # noqa: E402
from test_evabyte_cell import _manifest  # noqa: E402  (adds tiny-evabyte)

SEED = 2 ** 31 + 31

FAMILY = '''\
"""The llama family served from a cache that is not one uniform K/V pool
(added by a test): the paged pool and one more per-slot leaf, built by
the family's own cache kind and carried through every packed step."""

import dataclasses

import jax
import jax.numpy as jnp

from families import llama
from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.models import llama as package

published = llama.published


class StateCache(paging.PagedKVCache):
    """``state [table rows]``: the packed steps the cache has been
    through."""

    state: jax.Array = None


@dataclasses.dataclass(frozen=True)
class StateKind(paging.FullCache):
    name = "full_with_state"

    def init_cache(self, model_cfg, **geometry):
        pool = super().init_cache(model_cfg, **geometry)
        return StateCache(
            state=jnp.zeros((geometry["table_rows"],), jnp.float32),
            **{f.name: getattr(pool, f.name)
               for f in dataclasses.fields(pool)})


def forward(cfg, params, tokens, positions, cache, slot_ids=None):
    logits, cache = package.llama_forward_with_cache(
        cfg, params, tokens, positions, cache, slot_ids=slot_ids)
    return logits, cache.replace(state=cache.state + 1.0)


@dataclasses.dataclass(frozen=True)
class Config(package.LlamaConfig):
    def serving_family(self):
        return paging.ServingFamily(forward=forward, cache_kind=StateKind())


def build(c, **kw):
    cfg = Config(**{**llama.common(c), **kw})
    return cfg, package.LlamaForCausalLM(cfg), forward
'''

REFERENCE = '''\
from reference import decoder_f32

cross_entropy = decoder_f32.cross_entropy
forward = decoder_f32.forward
'''


def _cell(manifest: str, workload: str, seed: int = SEED):
    cell = bench_run.load_cell(manifest, workload)
    cell.seed, cell.clock = seed, harness.Stopwatch()
    return cell


def _spy(forward, leaf: str, seen: list):
    """``forward`` that also hands ``leaf`` of the cache it returns to the
    host, a packed step at a time: what the probe's cache holds."""
    import jax

    def spying(cfg, params, tokens, positions, cache, slot_ids=None):
        logits, cache = forward(cfg, params, tokens, positions, cache,
                                slot_ids=slot_ids)
        jax.debug.callback(lambda x: seen.append(np.asarray(x)),
                           getattr(cache, leaf), ordered=True)
        return logits, cache

    return spying


@contextlib.contextmanager
def _added(tmp_path, config: dict, family: str, reference: str):
    """A family file, a reference file, a configuration and two manifest
    entries, added and taken away again; no file that was there is edited.
    Yields the manifest and the cell's name."""
    name = config["family"].replace("_", "-")
    added = {
        os.path.join(BENCH, "families", config["family"] + ".py"): family,
        os.path.join(BENCH, "reference", config["reference"] + ".py"):
            reference,
        os.path.join(HERE, "configs", name + ".json"): json.dumps(config)}
    m = harness.load_manifest(os.path.join(HERE, "manifest.json"))
    m["configs"].append({"name": name, "source": "none (rehearsal)",
                         "file": f"benchmarks/tests/configs/{name}.json",
                         "reduced": [], "why": "added by the test"})
    m["workloads"].append({"name": name + ".serve-batch", "config": name,
                           "traffic": "tiny-offline", "chips": 1,
                           "why": "added by the test"})
    manifest = tmp_path / "manifest.json"
    manifest.write_text(json.dumps(m))
    try:
        for path, body in added.items():
            assert not os.path.exists(path)
            with open(path, "w") as f:
                f.write(body)
        yield str(manifest), name + ".serve-batch"
    finally:
        for path in added:
            if os.path.exists(path):
                os.remove(path)
        sys.modules.pop("families." + config["family"], None)
        sys.modules.pop("reference." + config["reference"], None)


def _tiny_config(**keys) -> dict:
    config = harness.read_json(os.path.join(HERE, "configs",
                                            "tiny-mistral-serve.json"))
    check = keys.pop("logit_check", {})
    config.update(keys)
    config["serve"]["logit_check"].update(check)
    return config


def test_a_family_with_a_cache_of_its_own_is_checked_from_files_only(
        tmp_path):
    """A family file (with its cache kind over ``FullCache`` and the cache
    that kind builds), a reference file, a configuration and two manifest
    entries. The probe's cache is the family's, because the probe takes it
    from ``ServingEngine``'s constructor, which asks the family's kind
    (``inference/paging.init_serving_cache``), and its extra leaf comes
    through every packed step."""
    config = _tiny_config(family="stateful_family",
                          reference="stateful_reference",
                          logit_check={"compare": {"every": 4, "tail": 6}})
    with _added(tmp_path, config, FAMILY, REFERENCE) as (manifest, name):
        cell = _cell(manifest, name)
        family = harness.load_plugin("families", "stateful_family")
        mcfg, forward, params, ecfg = serve.prepare(cell)
        cache, kind = serve.serving_cache(mcfg, params, ecfg)
        assert type(cache) is family.StateCache
        assert type(kind) is family.StateKind
        assert cache.state.shape == (ecfg.max_slots,)
        del cache
        states = []
        settings = cell.config["serve"]
        why = serve.check_logits(cell, mcfg, _spy(forward, "state", states),
                                 params, ecfg, settings)
        assert why == []
        chk = settings["logit_check"]
        steps = len(serve.probe_schedule(
            chk["prompt_tokens"], chk["decode_steps"], ecfg.token_budget))
        assert [float(s[0]) for s in states] == list(range(1, steps + 1))


@pytest.mark.parametrize("cell_name,compare", [
    ("tiny-mistral.serve-batch", {"every": 8, "tail": 6}),
    ("tiny-evabyte.serve-docs", {"every": 16, "tail": 9}),
])
def test_compare_reads_the_full_comparison_at_its_positions(
        tmp_path, cell_name, compare):
    """Under ``compare`` the program's and the reference's logits are the
    full comparison's at the compared positions, and so are the errors
    and their parts; ``positions=None`` is the reference's old call."""
    cell = _cell(_manifest(tmp_path), cell_name)
    mcfg, forward, params, ecfg = serve.prepare(cell)
    chk = cell.config["serve"]["logit_check"]
    plen, ndec = chk["prompt_tokens"], chk["decode_steps"]
    some = dict(chk, compare=compare)
    at = serve.compared_positions(some)
    want_at = sorted(set(range(0, plen, compare["every"]))
                     | set(range(plen - compare["tail"], plen + ndec)))
    assert at.tolist() == want_at and 0 < at.size < plen + ndec
    np.testing.assert_array_equal(serve.compared_positions(chk),
                                  np.arange(plen + ndec))

    seqs, got = serve.probe_logits(cell.seed, mcfg, forward, params, ecfg,
                                   chk)
    seqs_some, got_some = serve.probe_logits(cell.seed, mcfg, forward,
                                             params, ecfg, some)
    np.testing.assert_array_equal(seqs_some, seqs)
    assert got.shape == (2, plen + ndec, mcfg.vocab_size)
    np.testing.assert_array_equal(got_some, got[:, at])

    reference = models.reference(cell.config)
    weights = models.published(params, cell.config)
    old = np.asarray(reference.forward(weights, seqs, cell.config)[0])
    for positions in (None, np.arange(plen + ndec)):
        np.testing.assert_array_equal(
            np.asarray(reference.forward(weights, seqs, cell.config,
                                         positions=positions)[0]), old)
    want = np.asarray(serve.reference_logits(reference, weights, seqs,
                                             cell.config, chk)[0])
    np.testing.assert_array_equal(want, old)
    want_some = np.asarray(serve.reference_logits(
        reference, weights, seqs, cell.config, some)[0])
    assert want_some.shape == got_some.shape
    # a head over fewer rows may sum in another order: float32's last digits
    np.testing.assert_allclose(want_some, old[:, at], rtol=0,
                               atol=1e-5 * float(np.std(old)))

    scale, err, parts = serve.logit_errors(got_some, want_some, some)
    scale_at, err_at, parts_at = serve.logit_errors(got[:, at], old[:, at],
                                                    some)
    prefill = int((at < plen).sum())
    assert parts["prefill"].size == 2 * prefill
    assert parts["decode"].size == 2 * ndec
    assert scale == pytest.approx(scale_at, rel=1e-5)
    np.testing.assert_allclose(err, err_at, rtol=0, atol=1e-4)
    for part in parts:
        np.testing.assert_allclose(parts[part], parts_at[part], rtol=0,
                                   atol=1e-4)
    # the full comparison's own errors at those positions, in its own scale
    full = serve.logit_errors(got, old, chk)
    np.testing.assert_allclose(err * scale, full[1][:, at] * full[0],
                               rtol=0, atol=1e-4 * scale)
    assert serve.judge_logits(got_some, want_some, some) == []
    with pytest.raises(harness.BenchError, match="every"):
        serve.compared_positions(dict(chk, compare={"every": 0, "tail": 1}))


def test_the_probe_maps_the_columns_the_engine_maps(tmp_path):
    """``tiny-evabyte``: a ring of 5 columns and a summary column a whole
    window, not ``position // block_size``. The columns the probe has
    mapped for its sequences at the end are the columns the engine has
    mapped for a request that reaches the same positions, and each block
    is mapped once."""
    from neuronx_distributed_tpu.inference.engine import ServingEngine

    cell = _cell(_manifest(tmp_path), "tiny-evabyte.serve-docs")
    mcfg, forward, params, ecfg = serve.prepare(cell)
    chk = cell.config["serve"]["logit_check"]
    plen, ndec = chk["prompt_tokens"], chk["decode_steps"]
    tables = []
    seqs, _ = serve.probe_logits(cell.seed, mcfg,
                                 _spy(forward, "block_tables", tables),
                                 params, ecfg, chk)
    probe = tables[-1]
    assert (probe[2:] < 0).all()
    blocks = probe[probe >= 0]
    assert sorted(blocks.tolist()) == list(range(blocks.size))

    # the last of ndec + 1 sampled tokens is never fed: the same positions
    engine = ServingEngine(mcfg, params, ecfg)
    uid = engine.submit(seqs[0, :plen].tolist(), ndec + 1)
    while engine.has_work():
        engine.step()
    assert engine.results[uid].status == "completed"
    served = np.asarray(engine.cache.block_tables)
    (slot,) = np.flatnonzero((served >= 0).any(axis=1))
    want = np.flatnonzero(served[slot] >= 0)
    kind = mcfg.serving_family().cache_kind
    assert want.size == kind.blocks_for(plen + ndec, ecfg.block_size)
    assert want.size < -(-(plen + ndec) // ecfg.block_size)
    for s in (0, 1):
        np.testing.assert_array_equal(np.flatnonzero(probe[s] >= 0), want)
