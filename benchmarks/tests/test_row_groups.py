"""The serving check's row groups (``runners/serve.py``: ``probe_schedule``
under ``group`` and ``rewrite``, ``logit_check.group`` and
``logit_check.rewrite`` through ``probe_logits``), in this process on the
CPU: the schedule against a copy of the one it replaces, and a family
whose rows see their whole block, added as files only.
"""

from __future__ import annotations

import itertools

import pytest

from test_serving_check import _added, _cell, _tiny_config, harness, serve

GRID = list(itertools.product((5, 64, 75, 256), (1, 8, 30), (8, 16, 128)))


def _schedule_before(prompt_len, decode, width):
    """``probe_schedule`` as it stood before it took ``group`` (PR 65's
    tree), kept here to hold the new one to."""
    steps, done, total = [], [0, 0], prompt_len + decode
    while min(done) < total:
        rows = []
        for s in (0, 1):
            if prompt_len <= done[s] < total and (s == 0 or done[0] > 0):
                rows.append((s, done[s]))
        for s in (0, 1):
            if done[s] < prompt_len and (s == 0 or done[0] >= prompt_len):
                n = min(width - len(rows), prompt_len - done[s])
                rows += [(s, done[s] + i) for i in range(n)]
                break
        for s, p in rows:
            done[s] = max(done[s], p + 1)
        steps.append(rows)
    return steps


@pytest.mark.parametrize("plen,ndec,width", GRID)
def test_without_a_group_the_schedule_is_the_one_it_was(plen, ndec, width):
    want = _schedule_before(plen, ndec, width)
    assert serve.probe_schedule(plen, ndec, width) == want
    assert serve.probe_schedule(plen, ndec, width, group=1) == want
    assert serve.probe_schedule(plen, ndec, width, 1, False) == want


def _runs(rows, s):
    """Sequence ``s``'s positions of a step, as fed."""
    return [p for q, p in rows if q == s]


@pytest.mark.parametrize("g,plen,ndec,width", [
    (4, 40, 8, 32), (4, 64, 4, 12), (4, 256, 32, 128), (4, 76, 8, 30),
    (16, 64, 16, 48), (16, 256, 32, 128), (16, 80, 48, 100)])
def test_a_group_enters_whole(g, plen, ndec, width):
    steps = serve.probe_schedule(plen, ndec, width, group=g)
    fed = [rp for rows in steps for rp in rows]
    assert sorted(fed) == [(s, p) for s in (0, 1) for p in range(plen + ndec)]
    assert all(len(rows) <= width for rows in steps)
    at = [0, 0]
    for rows in steps:
        for s in (0, 1):
            run = _runs(rows, s)
            # whole groups, in order, from where the sequence stood
            assert run == list(range(at[s], at[s] + len(run)))
            assert len(run) % g == 0
            # a decoding sequence gives one group a step
            assert not run or run[0] < plen or len(run) == g
            at[s] += len(run)
        # decode groups first, then one chunk
        kinds = [p >= plen for _, p in rows]
        assert kinds == sorted(kinds, reverse=True)
    # a chunk of sequence 1 beside a group of sequence 0, and at the end
    # two groups among pad rows
    assert any(_runs(rows, 0) and _runs(rows, 0)[0] >= plen
               and _runs(rows, 1) and _runs(rows, 1)[-1] < plen
               for rows in steps)
    assert len(steps[-1]) in (g, 2 * g) and 2 * g < width


@pytest.mark.parametrize("g", [1, 4, 16])
def test_under_rewrite_a_decode_group_enters_twice(g):
    plen, ndec, width = 16 * 5, 32, 64
    plain = serve.probe_schedule(plen, ndec, width, group=g)
    steps = serve.probe_schedule(plen, ndec, width, group=g, rewrite=True)
    own = [[(s, p) for s, p in rows if s < 2] for rows in steps]
    first = [[(s - 2, p) for s, p in rows if s >= 2] for rows in steps]
    assert sorted(rp for rows in own for rp in rows) == sorted(
        rp for rows in plain for rp in rows)
    # a first writing is a whole decode group, and the step after it holds
    # the same rows as the sequence's own
    for i, rows in enumerate(first):
        assert all(p >= plen for _, p in rows)
        assert not rows or set(rows) <= set(own[i + 1])
    for s in (0, 1):
        decoding = [sum(1 for rows in some
                        if any(q % 2 == s and p >= plen for q, p in rows))
                    for some in (plain, steps)]
        assert decoding == [ndec // g, 2 * ndec // g]
    assert sorted(rp for rows in first for rp in rows) == [
        (s, p) for s in (0, 1) for p in range(plen, plen + ndec)]
    # a prefill chunk beside a first writing
    assert any(first[i] and any(p < plen for _, p in own[i])
               for i in range(len(steps)))
    assert all(len(rows) <= width for rows in steps)


def test_the_schedule_refuses_what_it_cannot_group():
    for args, said in (((42, 8, 32, 4), "prompt_tokens 42"),
                       ((40, 6, 32, 4), "decode_steps 6"),
                       ((48, 16, 47, 16), "token_budget 47"),
                       ((40, 8, 32, 0), "group")):
        with pytest.raises(harness.BenchError, match=said):
            serve.probe_schedule(*args)
    assert serve.probe_schedule(48, 16, 48, 16)
    assert serve.probe_schedule(5, 1, 2) == _schedule_before(5, 1, 2)


# -- a family whose rows see their whole block ---------------------------------

BLOCK = 4

FAMILY = '''\
"""A family that decodes a block of positions at a time (added by a test):
a row's logits come from the tokens written at every position up to the
end of the row's block of ``block_length``, its own later neighbours
among them. A per-slot leaf keeps the token written at each position; a
position written again holds the last writing alone (``block_writes:
"add"`` is the fault: a cache that adds to a row where it should
overwrite it)."""

import dataclasses

import jax
import jax.numpy as jnp

from families import llama
from neuronx_distributed_tpu.inference import paging
from neuronx_distributed_tpu.inference.kv_cache import PAD_POSITION
from neuronx_distributed_tpu.models import llama as package

published = llama.published


class TokenCache(paging.PagedKVCache):
    """``written [table rows, positions]``: token id + 1, 0 where none."""

    written: jax.Array = None


@dataclasses.dataclass(frozen=True)
class TokenKind(paging.FullCache):
    name = "full_with_tokens"

    def init_cache(self, model_cfg, **geometry):
        pool = super().init_cache(model_cfg, **geometry)
        return TokenCache(
            written=jnp.zeros(
                (geometry["table_rows"],
                 geometry["max_blocks_per_seq"] * geometry["block_size"]),
                jnp.int32),
            **{f.name: getattr(pool, f.name)
               for f in dataclasses.fields(pool)})


def forward(cfg, params, tokens, positions, cache, slot_ids=None):
    tok, pos = tokens[0], positions[0]
    slots, held = cache.written.shape
    # a pad row writes nowhere
    at = (jnp.where(pos == PAD_POSITION, slots, slot_ids),
          jnp.where(pos == PAD_POSITION, 0, pos))
    written = (cache.written.at[at].add(tok + 1, mode="drop")
               if cfg.block_writes == "add"
               else cache.written.at[at].set(tok + 1, mode="drop"))
    mine = written[jnp.minimum(slot_ids, slots - 1)]            # [W, held]
    j = jnp.arange(held)
    end = (pos // cfg.block_length + 1) * cfg.block_length
    weight = ((mine > 0) & (j[None] < end[:, None])) / (
        1.0 + jnp.abs(pos[:, None] - j[None]))
    tree = params["params"]
    rows = tree["model"]["embed"]["embedding"].astype(jnp.float32)[
        jnp.clip(mine - 1, 0, cfg.vocab_size - 1)]              # [W, held, H]
    hidden = jnp.einsum("wj,wjh->wh", weight, rows, precision="highest")
    logits = jnp.dot(hidden, tree["lm_head"]["kernel"].astype(jnp.float32),
                     precision="highest")
    return logits[None], cache.replace(written=written)


@dataclasses.dataclass(frozen=True)
class Config(package.LlamaConfig):
    block_length: int = 1
    block_writes: str = "set"

    def serving_family(self):
        return paging.ServingFamily(forward=forward, cache_kind=TokenKind())


def build(c, **kw):
    cfg = Config(**{**llama.common(c), "block_length": c["block_length"],
                    "block_writes": c.get("block_writes", "set"), **kw})
    return cfg, package.LlamaForCausalLM(cfg), forward
'''

REFERENCE = '''\
"""The same sum over the whole sequence: row i sees every position before
the end of its block of ``block_length``. Imports nothing of the package."""

import jax.numpy as jnp

from reference import decoder_f32

cross_entropy = decoder_f32.cross_entropy


def forward(weights, tokens, config, positions=None):
    tokens = jnp.asarray(tokens)
    i = jnp.arange(tokens.shape[1])
    end = (i // config["block_length"] + 1) * config["block_length"]
    weight = (i[None] < end[:, None]) / (1.0 + jnp.abs(i[:, None] - i[None]))
    hidden = jnp.einsum("ij,bjh->bih", weight, weights("embedding")[tokens],
                        precision="highest")
    if positions is not None:
        hidden = hidden[:, jnp.asarray(positions)]
    return jnp.einsum("bih,vh->biv", hidden, weights("lm_head"),
                      precision="highest"), None
'''

#: the program is the reference's arithmetic: nothing may differ
CHECK = {"prompt_tokens": 40, "decode_steps": 8, "typical_rtol": 1e-3,
         "outlier_rtol": 1e-2, "outlier_share": {"prefill": 0.0,
                                                 "decode": 0.0}}


def _check(tmp_path, capsys, **keys):
    """``(why, printed)`` of ``check_logits`` over the block family
    under ``logit_check`` + ``keys`` (``block_writes`` goes to the
    configuration)."""
    writes = keys.pop("block_writes", "set")
    config = _tiny_config(family="block_rows", reference="block_rows_f32",
                          block_length=BLOCK, block_writes=writes,
                          logit_check=dict(CHECK, **keys))
    with _added(tmp_path, config, FAMILY, REFERENCE) as (manifest, name):
        cell = _cell(manifest, name)
        mcfg, forward, params, ecfg = serve.prepare(cell)
        capsys.readouterr()
        why = serve.check_logits(cell, mcfg, forward, params, ecfg,
                                 cell.config["serve"])
        return why, capsys.readouterr().out


def _parts(why):
    return sorted({w.split("(")[1].split(")")[0] for w in why})


def test_a_block_family_is_correct_only_fed_in_its_groups(tmp_path, capsys):
    """Fed in groups of its block the family is the reference to rounding.
    Fed a row a step (no key: what the check did before it took groups) a
    decode row sees none of its later neighbours, and sequence 1's chunks
    of 31 rows cut a block: both parts fail."""
    why, out = _check(tmp_path, capsys, group=BLOCK)
    assert why == []
    assert f"[check] group={BLOCK} rewrite=False" in out
    why, out = _check(tmp_path, capsys)
    assert _parts(why) == ["decode", "prefill"]
    assert "group=" not in out and "rewrite=" not in out


def test_a_rewritten_group_is_seen_as_last_written(tmp_path, capsys):
    """Under ``rewrite`` every decode group is written with other tokens
    first. The family that overwrites passes; the one that adds to the
    leaf fails the decode part, and only under ``rewrite``."""
    why, out = _check(tmp_path, capsys, group=BLOCK, rewrite=True)
    assert why == []
    assert f"[check] group={BLOCK} rewrite=True" in out
    why, _ = _check(tmp_path, capsys, group=BLOCK, rewrite=True,
                    block_writes="add")
    assert _parts(why) == ["decode"]
    why, _ = _check(tmp_path, capsys, group=BLOCK, block_writes="add")
    assert why == []
    plain, twice = (serve.probe_schedule(
        CHECK["prompt_tokens"], CHECK["decode_steps"], 32, BLOCK, rewrite)
        for rewrite in (False, True))
    assert len(twice) == len(plain) + CHECK["decode_steps"] // BLOCK
