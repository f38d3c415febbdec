"""What :class:`.engine.ServingEngine` does for a family that decodes a
block of positions at a time (``serving_family().block``, a
:class:`.sampling.BlockDecoding`): generation by diffusion over blocks.

**The schedule.** Positions are blocks of ``B = block_length`` from 0. A
prefill chunk is a multiple of ``B`` and only the prompt's whole blocks are
prefilled; what is left of the prompt (``prompt_len % B`` rows) enters the
first decoded block uncovered. A decoding slot packs its block's ``B``
rows a step, decode groups first, each on a multiple of ``B`` of the
step's rows, so the packed step is ``token_budget / B`` groups.

**The block lives on the device.** Between passes the device keeps, a
slot: the block's start, its ``B`` tokens, which rows are still masked,
the passes it has had and whether the request has had its last block
(``_block_state``, ``[max_slots + 1, ...]``, the last row for groups that
are no decode group). A pass that begins with a masked row is a *denoise*
pass: every still-masked row samples a token with its confidence
(:func:`.sampling.sample_with_confidence`) and :func:`.sampling.uncover`
says which rows keep theirs. A pass that begins with none is the *store*
pass: the K/V it writes under the block's final tokens is what later
blocks see, its tokens are the block's, and the slot goes on to the next
block, all rows masked. Every pass writes the block's rows into the pool
at the same positions, so a later pass overwrites an earlier one's.

**A step late.** The host enqueues step n+1 before it reads step n, so it
learns that a block finished a step late, as it learns a sampled token a
step late in the other families. It therefore sends no positions for a
block in progress (the device derives them from its own start), maps pool
blocks for the block it knows of and the next one (a block takes two
passes at least, so the device is never further), and tells each group
where its request ends: a slot that stores its last block goes idle on
the device by itself, and the one pass too many that the host may still
pack for it runs on pad positions, writes nothing and counts nothing. The
first group of a request is *fresh*: the host sends its start, the
prompt's remainder and how many rows that is, and the device takes those
in its state's place, which is also how a slot is handed to the next
request and how a preempted request begins again.

**What is delivered.** When a store pass lands the host appends the
block's tokens past the prompt to the request, cut to ``max_new_tokens``
(and at ``eos_id``), and retires it when it has them all:
``tokens_generated``, TTFT and TPOT are in tokens. Delivered tokens depend
on the request's own passes alone, so they are the same at overlap depth 0
and 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.device_scopes import device_scope
from ..obs.metrics import get_registry
from ..obs.tracing import get_tracer
from ..utils.device import on_tpu
from .kv_cache import PAD_POSITION
from .paging import (BLOCK_PASSES, BLOCK_ROWS, BLOCKS_FINISHED,
                     CacheExhaustedError)
from .sampling import sample_with_confidence, uncover

#: the entries of each counter's kinds (``paging.BLOCK_COUNTERS``) among
#: the step's counts, as the device lays them out behind its tokens
_COUNT_ENTRIES = {BLOCK_PASSES.name: (0, 1), BLOCK_ROWS.name: (2, 3, 4, 5, 6),
                  BLOCKS_FINISHED.name: (1,)}
PASSES_HISTOGRAM = "nxd_block_passes_per_block"

#: a group of the packed step: no decode group, a block in progress, a
#: request's first group (``ctl[0]``; ``ctl[1]`` the rows the prompt's
#: remainder takes of a fresh group, ``ctl[2]`` where the request ends)
NO_GROUP, CONTINUES, FRESH = 0, 1, 2


class BlockServing:
    """The methods of :class:`.engine.ServingEngine` that serve a family
    with a block; none runs for a family without one."""

    def _init_block(self) -> None:
        e, b = self.ecfg, self._block.block_length
        if e.disaggregated:
            raise ValueError(
                f"{type(self.model_cfg).__name__} decodes blocks in the "
                "packed step; disaggregated workers are not supported")
        for name, value in (("token_budget", e.token_budget),
                            ("block_size", e.block_size)):
            if value % b:
                raise ValueError(
                    f"EngineConfig.{name}={value} is no multiple of the "
                    f"family's block length {b}: a block lies in one pool "
                    "block and a step is whole groups")
        if e.token_budget < 2 * b:
            raise ValueError(f"token_budget {e.token_budget} holds no "
                             f"decode group beside a chunk of {b}")
        s = e.max_slots + 1
        self._block_state = jax.device_put(
            dict(start=jnp.zeros((s,), jnp.int32),
                 tok=jnp.zeros((s, b), jnp.int32),
                 masked=jnp.zeros((s, b), bool),
                 npass=jnp.zeros((s,), jnp.int32),
                 done=jnp.ones((s,), bool)), self._sharding)

    def _build_block_step(self):
        from .engine import _HOST_WRITTEN, _hold_out

        model_cfg, sampling = self.model_cfg, self.ecfg.sampling
        forward, block = self._forward_fn, self._block
        b, slots = block.block_length, self.ecfg.max_slots
        # a pass past the last quota uncovers what is left (none can be:
        # the quotas sum to the block)
        quotas = jnp.asarray(block.quotas() + (b,), jnp.int32)

        def step_fn(params, pool, held, tokens, positions, slot_ids, state,
                    ctl, rng):
            groups = tokens.shape[1] // b
            kind, rest, end = ctl[0], ctl[1], ctl[2]
            row = jnp.arange(b, dtype=jnp.int32)[None, :]
            with device_scope("embed"):
                host_tok = tokens[0].reshape(groups, b)
                host_pos = positions[0].reshape(groups, b)
                gslot = slot_ids.reshape(groups, b)[:, 0]
                fresh, decodes = kind == FRESH, kind != NO_GROUP
                start = jnp.where(fresh, host_pos[:, 0],
                                  state["start"][gslot])
                tok = jnp.where(fresh[:, None], host_tok,
                                state["tok"][gslot])
                masked = jnp.where(fresh[:, None], row >= rest[:, None],
                                   state["masked"][gslot])
                npass = jnp.where(fresh, 0, state["npass"][gslot])
                live = decodes & (fresh | ~state["done"][gslot])
                tokens = jnp.where(decodes[:, None], tok,
                                   host_tok).reshape(1, -1)
                positions = jnp.where(
                    decodes[:, None],
                    jnp.where(live[:, None], start[:, None] + row,
                              PAD_POSITION), host_pos).reshape(1, -1)
            logits, cache = forward(
                model_cfg, params, tokens, positions, pool.replace(**held),
                slot_ids=slot_ids)
            with device_scope("sample"):
                drawn, confidence = sample_with_confidence(
                    logits[0], rng, sampling)
            with device_scope("sample.uncover"):
                denoise = live & jnp.any(masked, axis=-1)
                store = live & ~jnp.any(masked, axis=-1)
                to_uncover = masked & denoise[:, None]
                by_threshold, by_quota = uncover(
                    confidence.reshape(groups, b), to_uncover,
                    quotas[jnp.minimum(npass, quotas.shape[0] - 1)],
                    block.confidence_threshold)
                opened = by_threshold | by_quota
                # a stored block's slot goes on to the next block, or idle
                new = dict(
                    start=jnp.where(store, start + b, start),
                    tok=jnp.where(store[:, None], block.mask_token_id,
                                  jnp.where(opened, drawn.reshape(groups, b),
                                            tok)),
                    masked=store[:, None] | (masked & ~opened),
                    npass=jnp.where(store, 0, npass + live),
                    done=~live | (store & (start + b >= end)))
                at = jnp.where(decodes, gslot, slots)
                state = {name: state[name].at[at].set(
                    new[name].astype(state[name].dtype)) for name in state}
                counts = jnp.stack([jnp.sum(x).astype(jnp.int32) for x in (
                    denoise, store, by_threshold, by_quota,
                    to_uncover & ~opened, denoise[:, None] & ~masked,
                    jnp.repeat(store, b))])
                # the tokens the pass was fed (a store pass's: the
                # block's), the passes of each block it finished, counts
                out = jnp.concatenate([
                    jnp.where(decodes[:, None], tok, 0).reshape(-1),
                    jnp.where(store, npass + 1, 0), counts])
            pool, held = _hold_out(cache)
            return out, pool, {n: held[n] for n in held
                               if n not in _HOST_WRITTEN}, state

        return jax.jit(step_fn, donate_argnums=(1,) if on_tpu() else ())

    def _block_example_args(self, width: int):
        from .engine import _hold_out

        groups = width // self._block.block_length
        return (self.params, *_hold_out(self.cache),
                jnp.zeros((1, width), jnp.int32),
                jnp.full((1, width), PAD_POSITION, jnp.int32),
                jnp.full((width,), self.ecfg.max_slots, jnp.int32),
                self._block_state, jnp.zeros((3, groups), jnp.int32),
                self._rng)

    # -- the schedule -----------------------------------------------------

    def _block_end(self, req) -> int:
        """One past the last position of the request's last block."""
        b = self._block.block_length
        return -(-(req.prompt_len + req.max_new_tokens) // b) * b

    def _build_block_schedule(self):
        """:meth:`.engine.ServingEngine._build_schedule` for a family with
        a block: ``(decode_rows, prefill_rows)`` of ``(req, token,
        position, in a decode group)``, the decode rows in groups of the
        block length (a fresh group's tokens are the prompt's remainder
        and mask tokens; a block in progress is fed from the device, and
        its positions here are where the host last knew it to be). Blocks
        of the pool are mapped for the block the host knows of and the
        next. Preempts (youngest first) when a group cannot get them;
        prefill chunks merely truncate."""
        e, block = self.ecfg, self._block
        b = block.block_length
        while True:
            try:
                decode_rows = []
                for req in sorted((s for s in self._slots
                                   if s is not None and s.decoding),
                                  key=lambda r: r.admit_seq):
                    if len(decode_rows) + b > e.token_budget:
                        break
                    start = req.n_cached
                    for pos in range(start, min(start + 2 * b,
                                                self._block_end(req)), b):
                        self._ensure_block(req, pos)
                    for pos in range(start, start + b):
                        tok = 0 if req.block_started else (
                            req.prompt[pos] if pos < req.prompt_len
                            else block.mask_token_id)
                        decode_rows.append((req, tok, pos, True))
                break
            except CacheExhaustedError:
                self._preempt_youngest(req)
        prefill_rows = []
        for req in sorted((s for s in self._slots
                           if s is not None and not s.decoding),
                          key=lambda r: r.admit_seq):
            room = e.token_budget - len(decode_rows) - len(prefill_rows)
            if room < b:
                break
            chunk = min(room - room % b, req.prefill_len - req.n_cached)
            for i in range(chunk):
                pos = req.n_cached + i
                try:
                    self._ensure_block(req, pos)
                except CacheExhaustedError:
                    # a pool block holds whole blocks: the pool runs dry
                    # between two of them
                    chunk = i
                    break
                prefill_rows.append((req, req.prompt[pos], pos, False))
            req.n_cached += chunk
            self.stats.prefill_tokens += chunk
        return decode_rows, prefill_rows

    def _dispatch_block(self, fn, rows, width: int, rng, span: str,
                        step: int):
        """:meth:`.engine.ServingEngine._dispatch` for a family with a
        block: the same spans and counts, the groups' control beside the
        rows, and the device's block state carried from step to step."""
        from .engine import _InFlight, _hold_out

        tracer = get_tracer()
        b = self._block.block_length
        with tracer.span(span + "/pack", step=step):
            tokens = np.zeros((1, width), np.int32)
            positions = np.full((1, width), PAD_POSITION, np.int32)
            slot_ids = np.full((width,), self.ecfg.max_slots, np.int32)
            ctl = np.zeros((3, width // b), np.int32)
            groups = 0
            for i, (req, tok, pos, decodes) in enumerate(rows):
                tokens[0, i], positions[0, i] = tok, pos
                slot_ids[i] = req.slot
                if decodes and i % b == 0:
                    groups += 1
                    ctl[:, i // b] = (
                        CONTINUES if req.block_started else FRESH,
                        max(0, req.prompt_len - pos), self._block_end(req))
            counted = get_registry().enabled
            rolled, self._rolled = self._rolled, 0
            if counted:
                self._add_counts(self._count_step(
                    positions[0], slot_ids, self._tables,
                    [len(self._slot_blocks[r.slot]) for r in self._slots
                     if r is not None], rolled))
        with tracer.span(span + "/dispatch", step=step):
            pool, held = _hold_out(self.cache)
            sampled, pool, counts, self._block_state = fn(
                self.params, pool, held, jnp.asarray(tokens),
                jnp.asarray(positions), jnp.asarray(slot_ids),
                self._block_state, jnp.asarray(ctl), rng)
            self.cache = pool.replace(**{**held, **counts})
            flight = _InFlight(rows, [r[0].epoch for r in rows], sampled,
                               groups=groups)
            sampled.copy_to_host_async()
            if counted:
                flight.counts = [(leaf, getattr(self.cache, leaf.leaf))
                                 for leaf in self._device_counts]
                for _, on_device in flight.counts:
                    on_device.copy_to_host_async()
        return flight

    def _note_block_enqueued(self, rows) -> None:
        """What the host knows of a step once it is enqueued: a request
        whose first group it holds has its block on the device from here
        on. Whether a block finishes, and a request with it, is learnt
        when the step lands."""
        for req, _, _, decodes in rows:
            if decodes:
                req.block_started = True

    def _land_block(self, flight, now: float) -> None:
        """Deliver the blocks whose store pass ``flight`` ran: their
        tokens past the prompt, cut to ``max_new_tokens`` and at
        ``eos_id``, and retire the requests they finish. A request
        preempted or finished since the step was enqueued takes
        nothing."""
        b = self._block.block_length
        width = self.ecfg.token_budget
        out = flight.sampled
        fed = out[:width].reshape(-1, b)
        passes = out[width:width + width // b]
        reg = get_registry()
        if reg.enabled:
            counts = out[width + width // b:]
            self._add_counts({name: [int(counts[i]) for i in entries]
                              for name, entries in _COUNT_ENTRIES.items()})
            finished = passes[passes > 0]
            if finished.size:
                hist = reg.histogram(
                    PASSES_HISTOGRAM,
                    "Passes a finished block took, the store pass among "
                    "them (2 to denoising_steps + 1).")
                for n in finished:
                    hist.observe(float(n))
        eos = self.ecfg.eos_id
        for g in range(flight.groups):
            req, epoch = flight.rows[g * b][0], flight.epochs[g * b]
            if not passes[g] or req.finished or epoch != req.epoch:
                continue
            start = req.n_cached
            new = [int(t) for t in fed[g][max(0, req.prompt_len - start):]]
            new = new[:req.max_new_tokens - len(req.generated)]
            if eos is not None and eos in new:
                new = new[:new.index(eos) + 1]
            req.n_cached += b
            req.generated.extend(new)
            self.stats.tokens_generated += len(new)
            if req.first_token_time is None and new:
                req.first_token_time = now
                self.stats.ttft_s.append(now - req.arrival_time)
            if (len(req.generated) >= req.max_new_tokens
                    or (eos is not None and new and new[-1] == eos)):
                self._retire(req, now)

