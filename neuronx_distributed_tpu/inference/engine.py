"""Continuous-batching serving engine over the paged KV cache.

vLLM/Orca-style serving on fixed-shape JAX: one compiled step serves any
mix of live requests. Each step the host scheduler packs, into a single
``[1, token_budget]`` token batch,

* one decode token for every slot that is actively generating, and
* chunked prefill rows for newly admitted requests (a prompt may take
  several steps, ``token_budget`` tokens at a time),

then runs the jitted step (:func:`..models.llama.llama_forward_with_cache`
on the paged cache protocol). Every device array the step sees —
tokens, positions, slot ids, block tables, the pool — has a fixed shape,
so the step compiles exactly once per (model, budget) no matter how the
load varies; nxdlint's recompile-hazard rule polices the opposite
anti-pattern (shapes derived from ``len(requests)``).

Block allocation is lazy and host-side: a slot gets pool blocks as its
positions first touch them. When the pool runs dry the youngest running
request is preempted (blocks freed, restarted from its prompt later) —
admission control rejects requests that could never fit. Finished slots
(EOS / max tokens) free their blocks at the same step boundary, so new
requests are admitted mid-flight.

A family that decodes a block of positions at a time
(``serving_family().block``: generation by diffusion over blocks) packs a
decoding slot's whole block a step, keeps the block on the device between
its passes and delivers it when its store pass has run:
:mod:`.block_serving` holds what differs, and nothing of it runs for any
other family.
"""

from __future__ import annotations

import dataclasses
import itertools
import statistics
import time
import types
from collections import deque
from typing import (Any, Callable, Deque, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from ..models.llama import LlamaConfig
from ..obs import host as obs_host
from ..obs.accounting import CompileTracker
from ..obs.device_scopes import device_scope
from ..obs.events import emit_event
from ..obs.metrics import get_registry
from ..obs.tracing import GC_SPAN, attrs_of, get_tracer
from ..utils.device import on_tpu
from ..resilience.integrity import (
    IntegrityError,
    fingerprint_array_np,
    kv_payload_fingerprints,
)
from .aot_cache import AotExecutableCache, AotWorker, source_fingerprint
from .block_serving import BlockServing
from .kv_cache import PAD_POSITION
from .paging import (COUNT_LEAVES, PAYLOAD_BLOCK_AXES, BlockAllocator,
                     CacheExhaustedError, PrefixCache, cow_copy_blocks,
                     extract_blocks, flat_write_indices, init_serving_cache,
                     inject_blocks, mask_pool_positions, step_counter)
from .sampling import SamplingConfig, sample
from .speculative import (SpeculationConfig, branch_of_nodes,
                          build_medusa_tree, medusa_accept_longest)


#: leaves of a serving cache that the host writes before a step (and the
#: forward hands back as it got them). Neither they nor those a step leaves
#: for the host to read after it (``paging.COUNT_LEAVES``: what its kernels'
#: walks and its router did) are donated to the packed step: the host may
#: hold such an array, and read it, while the step after the one that made
#: it runs.
_HOST_WRITTEN = ("block_tables", "lengths")

#: engines of this process, counted: each numbers its ``step()`` calls from
#: a base of its own, so two replicas' calls never carry the same ``step``
_ENGINE_SEQ = itertools.count()

#: the stalled-step rule (``_account_call``): a call is slow when its wall
#: is over ``STALL_FACTOR`` x the median of the last ``STALL_WINDOW`` calls'
#: walls; the median is taken at the ``STALL_FIRST``-th accounted call and
#: at every ``STALL_REFRESH``-th, so that no step sorts
STALL_FACTOR, STALL_WINDOW, STALL_FIRST, STALL_REFRESH = 3.0, 256, 8, 64

#: where a slow call's time over the median went
#: (``nxd_engine_step_wall_seconds_total{where}``; ``steady`` is the rest)
STALL_CAUSES = ("host_pause", "device", "transfer", "compile", "host")

#: and why, whatever span the stepping thread sat in
#: (``nxd_engine_stall_cause_seconds_total{cause}``, the same seconds):
#: nothing of the process ran, the stepping thread was runnable and had no
#: core, or neither
STALL_WHYS = ("process_stopped", "cpu_wait", "other")


def _hold_out(cache):
    """``(pool, held)``: ``cache`` with the leaves the host writes or reads
    set to ``None``, and those leaves by name; ``pool.replace(**held)`` is
    ``cache`` again."""
    held = {n: getattr(cache, n) for n in _HOST_WRITTEN + COUNT_LEAVES
            if getattr(cache, n, None) is not None}
    return cache.replace(**dict.fromkeys(held)), held


@jax.jit
def _clear_freed_positions(pos, freed_mask):
    """Reset freed blocks' stored positions to the pad sentinel.

    A freed block keeps its old per-entry positions; if it is later
    remapped at a *different* block index of another sequence, those
    stale small positions pass the ``q_pos >= stored_pos`` causal mask
    and leak the previous owner's K/V into attention. Fixed shapes
    (``[num_blocks, block_size]`` pool positions, ``[num_blocks]`` bool
    mask), so this compiles once alongside the serving step."""
    return jnp.where(freed_mask[:, None], PAD_POSITION, pos)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving-side knobs (the model config stays in ``LlamaConfig``).

    ``token_budget`` is the packed step width: decode rows (one per
    running slot) plus prefill chunk rows, padded up to this fixed size.
    ``max_slots`` bounds concurrent requests; the pool is ``num_blocks *
    block_size`` KV slots shared by all of them."""

    block_size: int = 16
    num_blocks: int = 64
    max_slots: int = 8
    max_blocks_per_seq: int = 16
    token_budget: int = 32
    quantized: bool = False
    kv_dtype: Any = None            # None -> model dtype (fp pool only)
    # weight-quantization serving tier (docs/quantization.md): serve every
    # projection kernel quantized — "int8" | "fp8" (per-out-channel w8a16)
    # | "mxfp4" | "mxfp8" (packed OCP microscaling). The engine stamps the
    # model config's ``weight_quant`` and, when handed a float checkpoint,
    # converts it at construction (quantize_params_for_serving); with
    # speculation on the draft serves quantized too. Orthogonal to
    # ``quantized`` (the KV pool's int8 blocks); incompatible with cp>1.
    weight_quant: Optional[str] = None
    eos_id: Optional[int] = None
    sampling: SamplingConfig = SamplingConfig(greedy=True)
    # prefix sharing: full prompt blocks are published to a trie so later
    # requests map them (refcounted, copy-on-write) instead of
    # re-prefilling. Off by default: the trie deliberately keeps blocks
    # allocated past request retirement.
    prefix_sharing: bool = False
    # disaggregation: prefill and decode run as two separately compiled
    # workers (decode width = max_slots, prefill width = prefill_budget
    # or token_budget) handing KV off through the shared pool.
    disaggregated: bool = False
    prefill_budget: Optional[int] = None
    # speculative decoding: draft branches propose k tokens per slot per
    # round into COW lane clones of the slot's blocks; one target forward
    # tree-verifies every branch; rejected branches free atomically. The
    # packed worker, the draft worker and the verify worker each see one
    # fixed shape, so speculation keeps compile_count()==1 whatever the
    # accept rate does. Requires greedy sampling; incompatible with
    # disaggregated (speculation is a decode-side feature of the packed
    # step).
    speculation: Optional[SpeculationConfig] = None
    # context parallelism (the long-context tier): cp>1 shards the paged
    # pool's *block* dimension over the mesh's "cp" axis — ``num_blocks``
    # stays PER RANK, so the global pool is ``cp * num_blocks`` and the
    # servable context grows linearly with the CP degree. Prefill runs
    # the whole prompt in ONE ring-attention pass (each rank holds its
    # contiguous sequence slice; KV hops ship quantized per
    # ``cp_wire_dtype``); decode runs paged attention per rank over its
    # resident blocks and merges partials with the flash-decoding
    # max/sum combine. Requires a mesh initialized with
    # ``context_parallel_size == cp``; incompatible with prefix_sharing
    # (trie blocks aren't CP-sharded), speculation, quantized pools and
    # ``disaggregated`` (cp is its own prefill/decode split — cross-host
    # handoff to plain decode workers goes through export_session /
    # the streamed transport instead).
    cp: int = 1
    # global width of the ring-prefill worker (the longest prompt one
    # ring pass covers). None -> max_blocks_per_seq * block_size, i.e.
    # any admissible prompt in one pass. Must split evenly into
    # cp * block_size chunks.
    cp_prefill_width: Optional[int] = None
    # wire dtype for the ring's ppermute KV hops: "int8" (default,
    # ~3.9x wire reduction) | "fp8" | "fp32" (bitwise fallback — hops
    # ship unquantized)
    cp_wire_dtype: str = "int8"
    # SDC defense on the migration path: export_session fingerprints the
    # shipped KV blocks (host-side int32 bit-folds over the extracted
    # payload) and import_session verifies them before touching the pool.
    # Host-only — the compiled step is untouched, so compile_count and
    # AOT cache keys are integrity-agnostic. Fail-closed: a ticket that
    # ships KV *without* fingerprints is rejected when integrity is on —
    # unverifiable blocks don't get to ride in under the radar.
    integrity: bool = True


class RequestRejected(RuntimeError):
    """Typed admission rejection raised at ``submit`` time.

    ``reason`` is machine-readable so routers/clients can branch on it:

    * ``never_fits`` — the request could not fit the pool / block table /
      model context even running alone; resubmitting is pointless.
    * ``over_budget`` — the global token budget is exhausted (router).
    * ``draining`` — the target is draining and admits nothing new.
    * ``tenant_throttled`` — the tenant's token bucket is empty (router).
    """

    REASONS = ("never_fits", "over_budget", "draining", "tenant_throttled")

    def __init__(self, reason: str, detail: str = "",
                 trace_id: Optional[str] = None):
        if reason not in self.REASONS:
            raise ValueError(f"unknown rejection reason {reason!r}")
        super().__init__(f"request rejected ({reason})"
                         + (f": {detail}" if detail else ""))
        self.reason = reason
        # request trace-id (when tracing is on): a rejection closes the
        # request's trace with outcome="rejected", and the id lets the
        # caller correlate the exception with that span
        self.trace_id = trace_id


@dataclasses.dataclass
class _RequestState:
    uid: str
    prompt: List[int]
    max_new_tokens: int
    arrival_time: float
    generated: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    n_cached: int = 0               # tokens whose K/V are in the pool
    first_token_time: Optional[float] = None
    admit_time: Optional[float] = None  # when the request got its slot
    admit_seq: int = -1             # admission order, for preemption choice
    shared_tokens: int = 0          # prompt tokens mapped from the trie
    chain: Optional[int] = None     # trie chain hash for continued insert
    trie_blocks: int = 0            # prompt blocks walked/inserted so far
    trie_dead: bool = False         # stop inserting (collision/eviction)
    spec_rounds: int = 0            # speculation rounds this request ran
    spec_accepted: int = 0          # draft tokens accepted across rounds
    spec_ok: bool = True            # False: draft KV cold (imported KV)
    # one step deep in flight: tokens sampled for this request by steps the
    # host has enqueued and not read yet, the row of the newest such step
    # that samples its next token, and whether it has been retired
    in_flight: int = 0
    take_row: int = -1
    finished: bool = False
    epoch: int = 0                  # restarts so far: older rows land stale
    # a family that decodes blocks (``block_serving``): the prompt's whole
    # blocks, which alone are prefilled (None: the whole prompt), and
    # whether the device holds the request's block in progress
    prefill_len: Optional[int] = None
    block_started: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def tokens(self) -> List[int]:
        return self.prompt + self.generated

    @property
    def decoding(self) -> bool:
        # prefill done and one sampled token waits to be fed back
        return self.n_cached >= (self.prompt_len if self.prefill_len is None
                                 else self.prefill_len)

    def restart(self) -> None:
        self.generated = []
        self.slot = None
        self.n_cached = 0
        self.first_token_time = None
        self.admit_time = None
        self.shared_tokens = 0
        self.chain = None
        self.trie_blocks = 0
        self.trie_dead = False
        self.spec_rounds = 0
        self.spec_accepted = 0
        # a restart re-prefills, which re-warms the draft pool too
        self.spec_ok = True
        # the token a row in flight samples is discarded with the others,
        # and so is a half-done block
        self.in_flight = 0
        self.block_started = False
        self.epoch += 1


#: SessionTicket wire format magic — same shape as the AOT cache's
#: ``NXDAOT1``: ASCII magic + format version + newline, so version skew
#: is detectable from the first 8 bytes.
TICKET_MAGIC = b"NXDTKT1\n"


class TicketWireError(RuntimeError):
    """A serialized :class:`SessionTicket` failed to parse: wrong magic,
    version skew, truncation, or payload corruption. Typed so transports
    and drills can branch on 'bad bytes' without catching the world."""


@dataclasses.dataclass
class SessionTicket:
    """A live request lifted off one engine for landing on another
    (:meth:`ServingEngine.export_session` → ``import_session``).

    Carries everything the destination needs to continue the session
    with *zero re-prefill*: the scheduler state plus the session's KV
    blocks as a portable :func:`~.paging.extract_blocks` payload
    (``kv``/``n_blocks`` are ``None``/0 for a still-queued request —
    nothing was prefilled, nothing ships). ``age_s``/``ttft_s`` are
    relative, so the destination rebuilds arrival/first-token times
    against its own epoch and latency accounting stays honest across
    the move.

    ``kv_fp`` (when the exporter runs with ``EngineConfig.integrity``)
    maps each payload tensor name to its per-block integrity
    fingerprints, computed over the exact bytes extracted —
    ``import_session`` recomputes them over the bytes that *arrived* and
    rejects the whole ticket atomically on any mismatch, naming the
    corrupted (tensor, block)."""

    uid: str
    prompt: List[int]
    generated: List[int]
    max_new_tokens: int
    n_cached: int
    age_s: float
    ttft_s: Optional[float]
    n_blocks: int = 0
    kv: Optional[Dict[str, Any]] = None
    kv_fp: Optional[Dict[str, List[int]]] = None
    # exported request trace (tracer.request_export): the destination
    # resumes the same trace-id with accumulated phase totals, so a
    # migrated request still yields one complete end-to-end span. None
    # with tracing off (and for tickets from older exporters).
    trace: Optional[Dict[str, Any]] = None

    # -- wire format ------------------------------------------------------
    #
    # magic+version line, one JSON header line (scheduler state, kv_fp,
    # trace, and an array manifest: name/dtype/shape/nbytes in payload
    # order plus a whole-payload fingerprint), then the concatenated raw
    # array bytes. Mirrors the ``.aotx`` ``NXDAOT1`` layout so both wire
    # formats are versioned and self-describing; unlike the AOT cache's
    # degrade-to-miss read path, a bad ticket is *rejected* with a typed
    # :class:`TicketWireError` — silently continuing a torn session is
    # exactly what the integrity layer exists to prevent.

    def to_bytes(self) -> bytes:
        """Serialize into the versioned ``NXDTKT1`` wire format."""
        manifest = []
        payload = b""
        for name in sorted(self.kv or {}):
            arr = np.ascontiguousarray(np.asarray(self.kv[name]))
            manifest.append({"name": name, "dtype": str(arr.dtype),
                             "shape": list(arr.shape),
                             "nbytes": int(arr.nbytes)})
            payload += arr.tobytes()
        header = {
            "uid": self.uid, "prompt": list(self.prompt),
            "generated": list(self.generated),
            "max_new_tokens": int(self.max_new_tokens),
            "n_cached": int(self.n_cached), "age_s": float(self.age_s),
            "ttft_s": (None if self.ttft_s is None
                       else float(self.ttft_s)),
            "n_blocks": int(self.n_blocks), "kv_fp": self.kv_fp,
            "trace": self.trace, "arrays": manifest,
            "payload_fp": int(fingerprint_array_np(
                np.frombuffer(payload, np.uint8))[0]),
        }
        import json

        return (TICKET_MAGIC + json.dumps(header).encode("utf-8")
                + b"\n" + payload)

    @classmethod
    def from_bytes(cls, data: bytes) -> "SessionTicket":
        """Parse :meth:`to_bytes` output; raises :class:`TicketWireError`
        on bad magic, version skew, truncation, or a payload that does
        not fingerprint to what the header promised."""
        import json

        if len(data) < len(TICKET_MAGIC) \
                or data[:6] != TICKET_MAGIC[:6]:
            raise TicketWireError(
                "not a session ticket (bad magic)")
        if data[:len(TICKET_MAGIC)] != TICKET_MAGIC:
            got = data[:len(TICKET_MAGIC)].rstrip(b"\n").decode(
                "ascii", "replace")
            raise TicketWireError(
                f"ticket version skew: got {got!r}, this reader speaks "
                f"{TICKET_MAGIC.rstrip().decode('ascii')!r} — refusing "
                "to guess at a foreign layout")
        nl = data.find(b"\n", len(TICKET_MAGIC))
        if nl < 0:
            raise TicketWireError("truncated ticket: no header line")
        try:
            header = json.loads(data[len(TICKET_MAGIC):nl])
        except ValueError as e:
            raise TicketWireError(f"corrupt ticket header: {e}") from e
        payload = data[nl + 1:]
        want = sum(a["nbytes"] for a in header.get("arrays", []))
        if len(payload) != want:
            raise TicketWireError(
                f"truncated ticket payload: header promises {want} "
                f"byte(s), {len(payload)} arrived")
        got_fp = int(fingerprint_array_np(
            np.frombuffer(payload, np.uint8))[0])
        if got_fp != int(header.get("payload_fp", got_fp)):
            raise TicketWireError(
                "ticket payload failed its integrity fingerprint — the "
                "bytes that arrived are not the bytes that were sent")
        kv: Optional[Dict[str, Any]] = None
        off = 0
        for a in header.get("arrays", []):
            arr = np.frombuffer(
                payload[off:off + a["nbytes"]],
                dtype=np.dtype(a["dtype"])).reshape(a["shape"]).copy()
            kv = kv or {}
            kv[a["name"]] = arr
            off += a["nbytes"]
        kv_fp = header.get("kv_fp")
        if kv_fp is not None:
            kv_fp = {k: [int(x) for x in v] for k, v in kv_fp.items()}
        return cls(
            uid=header["uid"], prompt=list(header["prompt"]),
            generated=list(header["generated"]),
            max_new_tokens=int(header["max_new_tokens"]),
            n_cached=int(header["n_cached"]),
            age_s=float(header["age_s"]),
            ttft_s=(None if header["ttft_s"] is None
                    else float(header["ttft_s"])),
            n_blocks=int(header["n_blocks"]), kv=kv, kv_fp=kv_fp,
            trace=header.get("trace"))


#: label set shared by the four per-request histograms.
_REQUEST_LABELS = ("tenant", "replica", "outcome")


def observe_request_metrics(outcome: str, *, tenant: str = "-",
                            replica: str = "-",
                            ttft_s: Optional[float] = None,
                            tpot_s: Optional[float] = None,
                            queue_s: Optional[float] = None,
                            e2e_s: Optional[float] = None,
                            registry=None) -> None:
    """Record one retired request into the per-request histograms
    (``nxd_request_{ttft,tpot,queue,e2e}_seconds``), labeled by
    tenant/replica/outcome. Called once per request at retirement — by
    the router when the engine is fleet-managed, by the engine itself
    when standalone — so samples are never double-counted."""
    reg = registry if registry is not None else get_registry()
    if not reg.enabled:
        return

    def _observe(name: str, help: str, value: Optional[float]) -> None:
        if value is None:
            return
        reg.histogram(name, help, labels=_REQUEST_LABELS).labels(
            tenant=tenant, replica=replica,
            outcome=outcome).observe(max(0.0, float(value)))

    _observe("nxd_request_ttft_seconds",
             "Per-request time to first token.", ttft_s)
    _observe("nxd_request_tpot_seconds",
             "Per-request mean time per output token after the first.",
             tpot_s)
    _observe("nxd_request_queue_seconds",
             "Per-request wait from arrival to slot admission.", queue_s)
    _observe("nxd_request_e2e_seconds",
             "Per-request end-to-end latency, arrival to retirement.",
             e2e_s)


@dataclasses.dataclass
class RequestResult:
    uid: str
    prompt_len: int
    tokens: List[int]
    status: str                     # "completed" | "rejected"
    ttft_s: Optional[float] = None
    finish_s: Optional[float] = None
    tpot_s: Optional[float] = None  # mean time per token after the first
    accept_rate: Optional[float] = None  # accepted/offered draft tokens
                                         # (None: never speculated)


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    completed: int = 0
    rejected: int = 0
    preempted: int = 0
    resubmitted: int = 0            # evicted for resubmission elsewhere
    queue_depth: int = 0            # gauge: live requests right now
    tokens_generated: int = 0
    cow_copies: int = 0             # shared blocks cloned before a write
    prefix_hit_tokens: int = 0      # prompt tokens mapped from the trie
    prefill_tokens: int = 0         # prompt tokens actually computed
    migrated_in: int = 0            # sessions landed via import_session
    migrated_out: int = 0           # sessions shipped via export_session
    migrated_tokens: int = 0        # cached tokens landed without prefill
    integrity_rejects: int = 0      # tickets refused: KV fingerprint bad
    spec_rounds: int = 0            # (slot, round) speculation attempts
    spec_accepted_tokens: int = 0   # draft tokens accepted by the target
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    step_latency_s: List[float] = dataclasses.field(default_factory=list)
    occupancy: List[float] = dataclasses.field(default_factory=list)
    shared_fraction: List[float] = dataclasses.field(default_factory=list)
    first_step_t: Optional[float] = None
    last_step_t: Optional[float] = None

    def report(self) -> Dict[str, float]:
        span = ((self.last_step_t - self.first_step_t)
                if self.steps and self.last_step_t > self.first_step_t
                else 0.0)
        lat = np.asarray(self.step_latency_s or [0.0])
        ttft = np.asarray(self.ttft_s or [0.0])
        return {
            "steps": self.steps,
            "completed": self.completed,
            "rejected": self.rejected,
            "preempted": self.preempted,
            "tokens_generated": self.tokens_generated,
            "tokens_per_s": (self.tokens_generated / span) if span else 0.0,
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p99_ms": float(np.percentile(ttft, 99)) * 1e3,
            "step_latency_p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "step_latency_p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "pool_occupancy_mean": (float(np.mean(self.occupancy))
                                    if self.occupancy else 0.0),
            "prefix_hit_rate": (
                self.prefix_hit_tokens
                / max(1, self.prefix_hit_tokens + self.prefill_tokens)),
            "shared_block_fraction": (float(np.mean(self.shared_fraction))
                                      if self.shared_fraction else 0.0),
            "cow_copies": self.cow_copies,
            "spec_rounds": self.spec_rounds,
            "spec_accept_mean": (self.spec_accepted_tokens
                                 / max(1, self.spec_rounds)),
        }

    def to_dict(self) -> Dict[str, float]:
        """:meth:`report` plus the composable counters the router folds
        into its own stats (``rejected`` / ``resubmitted`` /
        ``queue_depth``)."""
        d = self.report()
        d["rejected"] = self.rejected
        d["resubmitted"] = self.resubmitted
        d["queue_depth"] = self.queue_depth
        d["migrated_in"] = self.migrated_in
        d["migrated_out"] = self.migrated_out
        d["migrated_tokens"] = self.migrated_tokens
        d["spec_accepted_tokens"] = self.spec_accepted_tokens
        return d


@dataclasses.dataclass
class _InFlight:
    """A worker's step between its enqueue and the host's reading of it:
    the rows it packed, each with its request's ``epoch`` then, and what
    the device returns for them (``sampled``, a device array until the
    fetch and a host array after it, and, where the family counts on the
    device and obs is on, each declared leaf with its declaration)."""
    rows: List[Tuple]
    epochs: List[int]
    sampled: Any
    counts: Sequence[Tuple[Any, Any]] = ()
    groups: int = 0     # decode groups leading ``rows`` (``block_serving``)


class ServingEngine(BlockServing):
    """Request queue + slot map + token-budget scheduler over one
    compiled fixed-shape step.

    The packed step runs one deep in flight: :meth:`step` schedules and
    enqueues step n+1 while the device still runs step n, and only then
    reads step n's tokens (a decode row whose token is still on the device
    takes it there, from the row of step n that samples it). Disaggregated
    workers, ``cp > 1`` and speculation read a step before they schedule
    the next: the same :meth:`step`, at depth 0."""

    def __init__(self, model_cfg: LlamaConfig, params,
                 engine_cfg: EngineConfig = EngineConfig(),
                 rng: Optional[jax.Array] = None,
                 clock: Optional[Callable[[], float]] = None,
                 aot_cache: Optional[AotExecutableCache] = None,
                 name: Optional[str] = None,
                 draft_cfg: Optional[LlamaConfig] = None,
                 draft_params=None):
        self.model_cfg = model_cfg
        self.params = params
        self.ecfg = engine_cfg
        # the model family says how it is served: its cached forward (any
        # callable with the llama_forward_with_cache paged signature
        # ``(cfg, params, tokens, positions, cache, slot_ids=...) ->
        # (logits, cache)``), the cache kind its table rows follow, and
        # the engine features it cannot serve, each with why
        family = model_cfg.serving_family()
        self._cache_kind = family.cache_kind.geometry(
            engine_cfg.block_size,
            max(engine_cfg.token_budget, engine_cfg.prefill_budget or 0))
        self._unsupported = dict(family.unsupported)
        asked = {
            "prefix_sharing": engine_cfg.prefix_sharing,
            "speculation": engine_cfg.speculation is not None,
            "cp": int(getattr(engine_cfg, "cp", 1)) > 1,
            "quantized": engine_cfg.quantized}
        for feature in sorted(self._unsupported):
            if asked.get(feature):
                self._refuse(feature)
        self._forward_fn = family.forward
        #: how the family decodes a block of positions at a time (None: a
        #: token a sequence a step); ``block_serving`` has what then differs
        self._block = family.block
        # elastic-fleet hooks: an AOT cache makes worker construction
        # load-or-compile (replicas after the first spin up without
        # compiling); a name scopes this engine's obs compile-tracker
        # sites so a fleet's replicas don't alias one site
        self.name = name
        self._aot = aot_cache
        # weight-quantization tier: stamp the format onto the model config
        # (the forward branches on cfg.weight_quant) and convert a float
        # checkpoint in place — callers hand the same tree either way
        wq = getattr(engine_cfg, "weight_quant", None)
        if wq is not None:
            from ..models.llama import WEIGHT_QUANT_FORMATS
            from ..quantization.serving import (params_are_quantized,
                                                quantize_params_for_serving)

            if wq not in WEIGHT_QUANT_FORMATS:
                raise ValueError(
                    f"EngineConfig.weight_quant must be one of "
                    f"{WEIGHT_QUANT_FORMATS} or None, got {wq!r}")
            if int(getattr(engine_cfg, "cp", 1)) > 1:
                raise ValueError(
                    "EngineConfig(cp>1, weight_quant=...): the ring "
                    "prefill worker runs the float forward, so a "
                    "weight-quantized step would serve two different "
                    "models; the long-context tier and the low-precision "
                    "tier are separate for now — drop one of them")
            if getattr(model_cfg, "weight_quant", None) != wq:
                model_cfg = dataclasses.replace(model_cfg, weight_quant=wq)
            self.model_cfg = model_cfg
            if not params_are_quantized(params):
                params = quantize_params_for_serving(model_cfg, params)
            self.params = params
        # context parallelism: validate the long-context tier's contract
        # up front — every restriction here is a config error, not a
        # runtime surprise three steps into a 512k-token session
        cp = max(1, int(getattr(engine_cfg, "cp", 1)))
        self._cp = cp
        self._cp_width: Optional[int] = None
        if cp > 1:
            from ..parallel import mesh as ps

            if engine_cfg.prefix_sharing:
                raise ValueError(
                    "EngineConfig(cp>1, prefix_sharing=True): prefix-trie "
                    "entries pin whole pool blocks, but a CP-sharded pool "
                    "scatters a sequence's blocks across the cp ranks — a "
                    "trie hit on one rank would map blocks the other "
                    "ranks' attention cannot see. The trie is not "
                    "CP-sharded yet; run the long-context tier with "
                    "prefix_sharing=False")
            if engine_cfg.speculation is not None:
                raise ValueError(
                    "cp>1 does not support speculative decoding: lane "
                    "clones assume a single-rank pool")
            if engine_cfg.disaggregated:
                raise ValueError(
                    "cp>1 is already a prefill/decode split (ring prefill "
                    "worker + combined decode worker); cross-engine "
                    "disaggregation hands sessions off through "
                    "export_session / the streamed transport")
            if engine_cfg.quantized:
                raise ValueError(
                    "cp>1 does not support quantized pools yet (the ring "
                    "prefill writes fp rows)")
            if (not ps.model_parallel_is_initialized()
                    or ps.get_context_parallel_size() != cp):
                raise ValueError(
                    f"EngineConfig(cp={cp}) needs an initialized mesh "
                    f"with context_parallel_size={cp}; call "
                    "initialize_model_parallel(context_parallel_size=...) "
                    "first")
            width = (engine_cfg.cp_prefill_width
                     or engine_cfg.max_blocks_per_seq
                     * engine_cfg.block_size)
            if width % (cp * engine_cfg.block_size):
                raise ValueError(
                    f"cp_prefill_width={width} must split into {cp} "
                    f"per-rank slices of whole {engine_cfg.block_size}-"
                    "token blocks")
            self._cp_width = width
            # the ring hops read the wire dtype off the model config
            self.model_cfg = model_cfg = dataclasses.replace(
                model_cfg, cp_wire_dtype=engine_cfg.cp_wire_dtype)
        #: global pool size in blocks (== num_blocks at cp=1; the pool's
        #: block dimension is sharded cp-ways otherwise)
        self._pool_blocks = cp * engine_cfg.num_blocks
        self.allocator = BlockAllocator(self._pool_blocks, cp_size=cp)
        self.stats = EngineStats()
        self.results: Dict[str, RequestResult] = {}
        self._queue: Deque[_RequestState] = deque()
        self._slots: List[Optional[_RequestState]] = (
            [None] * engine_cfg.max_slots)
        # speculative decoding: the draft model defaults to the target
        # itself (self-draft — the mechanical-ceiling configuration the
        # drills use; a real deployment passes a small draft_cfg/params).
        # Lanes: S speculating slots x B branches each get their own
        # block-table row past max_slots, so draft/verify rows route into
        # per-branch COW clones while every non-speculating slot is
        # untouched.
        spec = engine_cfg.speculation
        self._spec = spec
        self._spec_on = bool(spec.start_on) if spec else False
        if spec is not None:
            if not engine_cfg.sampling.greedy:
                raise ValueError(
                    "speculation requires greedy sampling (the accept "
                    "rule compares the target's greedy choice)")
            if engine_cfg.disaggregated:
                raise ValueError(
                    "speculation runs inside the packed worker; "
                    "disaggregated prefill/decode is not supported")
            if wq is not None and draft_cfg is not None:
                # an active tier serves the draft quantized by default:
                # draft forwards dominate step count, so a float draft
                # would forfeit most of the tier's bandwidth win
                from ..quantization.serving import (
                    params_are_quantized, quantize_params_for_serving)

                if getattr(draft_cfg, "weight_quant", None) != wq:
                    draft_cfg = dataclasses.replace(draft_cfg,
                                                    weight_quant=wq)
                if (draft_params is not None
                        and not params_are_quantized(draft_params)):
                    draft_params = quantize_params_for_serving(
                        draft_cfg, draft_params)
            self._draft_cfg = draft_cfg or model_cfg
            self._draft_params = (draft_params if draft_params is not None
                                  else params)
            self._draft_forward_fn = (
                self._draft_cfg.serving_family().forward)
            k, nb = spec.speculation_length, spec.num_branches
            self._spec_slots = spec.max_spec_slots or min(
                engine_cfg.max_slots,
                max(1, engine_cfg.token_budget // (nb * (k + 1))))
            self._table_rows = (engine_cfg.max_slots
                                + self._spec_slots * nb)
            self._spec_buffers = build_medusa_tree(spec.tree_choices())
            self._spec_branch_of = branch_of_nodes(spec)
        else:
            self._draft_cfg = None
            self._draft_params = None
            self._spec_slots = 0
            self._table_rows = engine_cfg.max_slots
        self._tables = np.full(
            (self._table_rows, engine_cfg.max_blocks_per_seq), -1,
            np.int32)
        self._slot_blocks: List[List[int]] = (
            [[] for _ in range(engine_cfg.max_slots)])
        #: what the family's step counts, obs on only (``paging``, "A step's
        #: counters"): the kind's hook, bound here to the engine's geometry
        #: and called once a dispatched step with the step's own host
        #: arrays; the leaves of the cache that the step counts into on the
        #: device, fetched with its tokens; and what both have counted
        #: since the last publish, by counter name and in its kinds' order
        self._count_step = step_counter(
            self._cache_kind, model_cfg, block_size=engine_cfg.block_size,
            pool_blocks=self._pool_blocks,
            itemsize=jnp.dtype(engine_cfg.kv_dtype
                               or model_cfg.dtype).itemsize)
        self._device_counts = family.device_counts()
        self._counters = family.counters()
        self._counted = {c.name: np.zeros((max(1, len(c.kinds)),), np.int64)
                         for c in self._counters}
        #: summary blocks ``engine/roll`` mapped for the step being packed
        self._rolled = 0
        #: summary blocks taken by the schedule for windows that the next
        #: step completes, ``(slot, column) -> block``: ``engine/roll``
        self._pending_roll: Dict[Tuple[int, int], int] = {}
        self._rng = rng if rng is not None else jax.random.key(0)
        self._clock = clock or time.monotonic
        self._t0 = self._clock()
        self._admit_counter = 0
        self._uid_counter = 0
        self._draining = False
        self._freed_dirty: set = set()  # freed blocks with stale positions
        self._pending_cow: List[Tuple[int, int, int]] = []  # (src, dst, keep)
        #: how many steps may be enqueued and not yet read (0 where the
        #: next schedule needs the step's values: a speculation round's
        #: verdict, the cp and disaggregated workers' own handoffs), the
        #: packed step that is, and per worker width the sampled tokens
        #: of its last step as the device holds them
        self._depth = int(spec is None and cp == 1
                          and not engine_cfg.disaggregated)
        self._inflight: Optional[_InFlight] = None
        self._last_sampled: Dict[int, jax.Array] = {}
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.allocator, engine_cfg.block_size)
            if engine_cfg.prefix_sharing else None)
        self.cache = self._init_cache()
        self.dcache = self._init_draft_cache()
        if self._block is not None:
            self._init_block()
        if cp > 1:
            # two workers, two fixed widths: the packed worker decodes
            # (and could chunk-prefill short prompts) at token_budget,
            # the ring worker prefills a whole prompt per pass at
            # cp_prefill_width — each compiles exactly once, so
            # compile_count() stays 1 across wildly different sessions
            self._step_fn = self._build_worker(
                "packed", engine_cfg.token_budget)
            self._prefill_fn = self._build_worker(
                "cp_prefill", self._cp_width)
            self._decode_fn = None
            workers = {"packed": self._step_fn,
                       "cp_prefill": self._prefill_fn}
        elif engine_cfg.disaggregated:
            # two workers, two jit/AOT instances: each sees exactly one
            # input shape, so each compiles exactly once
            self._step_fn = None
            self._prefill_fn = self._build_worker(
                "prefill",
                engine_cfg.prefill_budget or engine_cfg.token_budget)
            self._decode_fn = self._build_worker(
                "decode", engine_cfg.max_slots)
            workers = {"prefill": self._prefill_fn,
                       "decode": self._decode_fn}
        else:
            self._step_fn = self._build_worker(
                "packed", engine_cfg.token_budget)
            self._prefill_fn = self._decode_fn = None
            workers = {"packed": self._step_fn}
        self._spec_draft_fn = self._spec_verify_fn = None
        if spec is not None:
            self._spec_draft_fn = self._build_worker("spec_draft", 0)
            self._spec_verify_fn = self._build_worker("spec_verify", 0)
            workers["spec_draft"] = self._spec_draft_fn
            workers["spec_verify"] = self._spec_verify_fn
        # observability: per-worker compile trackers (any compile beyond
        # the first alerts through the event channel — the no-recompile
        # invariant made observable) + phase spans in step(). All of it
        # is host-side and polls the jit cache from outside, so the
        # compile-once behaviour itself is untouched.
        site = f"engine/{name}" if name else "engine"
        self._compile_trackers = {
            wn: CompileTracker.for_function(f"{site}/{wn}", fn)
            for wn, fn in workers.items()}
        #: the registry handles ``_publish_obs`` writes to, registered once a
        #: registry generation
        self._obs_cache: Optional[types.SimpleNamespace] = None
        #: this engine's ``step()`` calls, counted: the ``step`` attribute
        #: of every span a call opens
        self._calls = next(_ENGINE_SEQ) << 32
        #: the stalled-step rule's state, made by the first call that is
        #: accounted (obs on): the last walls, their median, the call before
        self._stall: Optional[types.SimpleNamespace] = None
        # request-lifecycle ownership: a fleet router retires request
        # traces and histograms itself (it knows tenant and outcome);
        # it clears this flag on engines it manages so samples are
        # recorded exactly once
        self._standalone_obs = True

    # -- construction -----------------------------------------------------

    def _init_cache(self):
        e, m = self.ecfg, self.model_cfg
        # the family's cache kind builds what it is served from; speculation
        # widens the table with lane rows, the pool itself (num_blocks) is
        # unchanged: lanes borrow blocks per round
        cache = init_serving_cache(
            m, num_blocks=self._pool_blocks, block_size=e.block_size,
            table_rows=self._table_rows,
            max_blocks_per_seq=e.max_blocks_per_seq,
            dtype=e.kv_dtype or m.dtype, quantized=e.quantized)
        # commit to the sharding the jitted step will leave its outputs
        # on (replicated over the active mesh, else the default device):
        # an uncommitted first-step cache has a different sharding key
        # than the committed cache every later step carries, which would
        # cost a second (identical) compile
        from ..parallel import mesh as ps

        if ps.model_parallel_is_initialized():
            sharding = jax.sharding.NamedSharding(
                ps.get_mesh(), jax.sharding.PartitionSpec())
        else:
            sharding = jax.devices()[0]
        self._sharding = sharding
        cache = jax.device_put(cache, sharding)
        if self._cp > 1:
            # the pool itself shards block-wise over cp: rank r
            # physically holds global blocks [r*num_blocks,
            # (r+1)*num_blocks) — exactly the allocator's rank slices.
            # Tables and lengths stay replicated (tiny, host-written).
            P = jax.sharding.PartitionSpec
            mesh = ps.get_mesh()

            def ns(spec):
                return jax.sharding.NamedSharding(mesh, spec)

            cache = cache.replace(
                k=jax.device_put(cache.k, ns(P(None, ps.CP_AXIS))),
                v=jax.device_put(cache.v, ns(P(None, ps.CP_AXIS))),
                pos=jax.device_put(cache.pos, ns(P(ps.CP_AXIS))))
        return cache

    def _init_draft_cache(self):
        """The draft model's own pool, mirroring the target pool's block
        geometry exactly (same num_blocks / block_size / tables): block
        ids, COW clones, frees and the stale-position wipe apply to both
        pools in lockstep, so one host allocator governs both."""
        if self._spec is None:
            return None
        e, d = self.ecfg, self._draft_cfg
        dc = init_serving_cache(
            d, num_blocks=e.num_blocks, block_size=e.block_size,
            table_rows=self._table_rows,
            max_blocks_per_seq=e.max_blocks_per_seq,
            dtype=e.kv_dtype or d.dtype, quantized=e.quantized)
        return jax.device_put(dc, self._sharding)

    def _cp_cache_specs(self):
        """The CP cache's shard_map spec pytree: pool tensors split
        block-wise over ``cp``, tables/lengths replicated. Built by
        substituting specs for arrays in the live cache pytree, so it
        tracks the cache's exact structure."""
        from ..parallel import mesh as ps
        P = jax.sharding.PartitionSpec
        return self.cache.replace(
            k=P(None, ps.CP_AXIS), v=P(None, ps.CP_AXIS),
            pos=P(ps.CP_AXIS), block_tables=P(), lengths=P())

    @staticmethod
    def _cp_local_tables(tables, rank, blocks_per_rank):
        """Global block ids -> this rank's pool-shard indices (``-1``
        where another rank owns the block, so gathers position-mask out
        and K/V scatters drop — each row lands exactly once, on its
        owner)."""
        loc = tables - rank * blocks_per_rank
        ok = (tables >= 0) & (loc >= 0) & (loc < blocks_per_rank)
        return jnp.where(ok, loc, -1)

    def _build_cp_step(self, prefill: bool):
        """One CP worker under ``shard_map`` over the ``cp`` axis.

        Decode/packed (``prefill=False``): every rank runs the full
        token batch against its local pool shard (tables rewritten to
        rank-local ids) and the per-rank paged partials merge inside
        attention with the flash-decoding max/sum combine — one gather
        plus three small collectives per layer; activations and sampled
        tokens come out replicated.

        Ring prefill (``prefill=True``): tokens/positions arrive
        sharded along the sequence, each rank prefills its contiguous
        prompt slice with ring attention (KV hops quantized per the
        model config's ``cp_wire_dtype``) and writes K/V rows into the
        blocks its pool shard owns; sampled tokens come out sharded so
        the host reads exactly the ``prompt_len - 1`` entry."""
        from ..parallel import mesh as ps
        model_cfg, sampling = self.model_cfg, self.ecfg.sampling
        forward = self._forward_fn
        nloc = self.ecfg.num_blocks
        P = jax.sharding.PartitionSpec
        cache_specs = self._cp_cache_specs()

        def cp_step(params, cache, tokens, positions, slot_ids, rng):
            r = jax.lax.axis_index(ps.CP_AXIS)
            tbl = cache.block_tables
            local = cache.replace(
                block_tables=self._cp_local_tables(tbl, r, nloc))
            kw = {"cp_prefill": True} if prefill else {}
            logits, new_cache = forward(
                model_cfg, params, tokens, positions, local,
                slot_ids=slot_ids, **kw)
            with device_scope("sample"):
                toks = sample(logits[0], rng, sampling)
            return toks, new_cache.replace(block_tables=tbl)

        row = P(None, ps.CP_AXIS) if prefill else P()
        flat = P(ps.CP_AXIS) if prefill else P()
        fn = ps.shard_map(
            cp_step,
            in_specs=(P(), cache_specs, row, row, flat, P()),
            out_specs=(flat, cache_specs))
        # no donation: the CPU/tier-1 path doesn't donate either, and
        # shard_map + donation of the sharded pool needs per-backend
        # care that the TPU tier picks up via the AOT path
        return jax.jit(fn)

    def _build_step(self):
        model_cfg, sampling = self.model_cfg, self.ecfg.sampling
        forward = self._forward_fn
        # donation gives in-place pool update on TPU; CPU donation only
        # warns, so keep it off there
        on_accel = on_tpu()
        if self._cp > 1:
            return self._build_cp_step(prefill=False)
        if self._block is not None:
            return self._build_block_step()
        if self._spec is None:
            def step_fn(params, pool, held, tokens, positions, slot_ids,
                        prev, take, rng):
                # ``pool`` is the cache without the leaves ``held`` (see
                # :func:`_hold_out`): they are not donated, so the arrays
                # the host keeps stay readable while a later step runs.
                # ``take[i] >= 0``: row i is a decode row whose token the
                # step before this one sampled in its row ``take[i]`` and
                # the host has not read: it is taken from ``prev``, that
                # step's output, here on the device.
                with device_scope("embed"):
                    tokens = jnp.where(take >= 0, prev[jnp.maximum(take, 0)],
                                       tokens[0])[None, :]
                logits, cache = forward(
                    model_cfg, params, tokens, positions,
                    pool.replace(**held), slot_ids=slot_ids)
                with device_scope("sample"):
                    toks = sample(logits[0], rng, sampling)
                pool, held = _hold_out(cache)
                return toks, pool, {n: held[n] for n in held
                                    if n not in _HOST_WRITTEN}

            return jax.jit(step_fn,
                           donate_argnums=(1,) if on_accel else ())

        # speculation: the packed step also runs the draft model over the
        # same rows, so the draft pool stays warm for every token the
        # target caches (prefill included) — the draft never re-reads
        # context it hasn't written
        draft_cfg = self._draft_cfg
        draft_forward = self._draft_forward_fn

        def spec_step_fn(params, draft_params, cache, dcache, tokens,
                         positions, slot_ids, rng):
            logits, cache = forward(
                model_cfg, params, tokens, positions, cache,
                slot_ids=slot_ids)
            _, dcache = draft_forward(
                draft_cfg, draft_params, tokens, positions, dcache,
                slot_ids=slot_ids)
            with device_scope("sample"):
                toks = sample(logits[0], rng, sampling)
            return toks, cache, dcache

        return jax.jit(spec_step_fn,
                       donate_argnums=(2, 3) if on_accel else ())

    def _build_spec_draft(self):
        """The draft worker: one jitted call proposes ``k`` tokens for
        each of ``B`` branches of each speculating slot. Depth 0 writes
        the committed token's draft K/V into every lane clone and splits
        branches via top-B; a ``lax.scan`` walks depths 1..k. The scan
        runs through depth ``k`` so the last drafted token's K/V lands
        too (its own proposal is discarded) — the
        ``speculation_length``-boundary lesson from
        :func:`..speculative.make_speculation_round_fn`."""
        spec, e = self._spec, self.ecfg
        k, nb, s = spec.speculation_length, spec.num_branches, \
            self._spec_slots
        dcfg, forward = self._draft_cfg, self._draft_forward_fn
        base = e.max_slots

        def draft_fn(draft_params, dcache, committed, pos):
            lanes = base + jnp.arange(s * nb, dtype=jnp.int32)
            pos0 = jnp.repeat(pos, nb)                       # [S*B]
            tok0 = jnp.repeat(committed, nb)
            logits, dcache = forward(
                dcfg, draft_params, tok0[None, :], pos0[None, :], dcache,
                slot_ids=lanes)
            # branch split: lane (s, b) continues from the b-th most
            # likely draft token (rows of one slot are identical — read
            # lane b=0's row)
            with device_scope("sample"):
                _, top = jax.lax.top_k(logits[0], nb)        # [S*B, B]
                d1 = top.reshape(s, nb, nb)[:, 0, :].reshape(s * nb)

            def body(carry, d):
                dc, tok = carry
                p = jnp.where(pos0 < PAD_POSITION, pos0 + d, PAD_POSITION)
                lg, dc = forward(dcfg, draft_params, tok[None, :],
                                 p[None, :], dc, slot_ids=lanes)
                with device_scope("sample"):
                    nxt = jnp.argmax(lg[0], axis=-1)
                return (dc, nxt), tok

            (dcache, _), toks = jax.lax.scan(
                body, (dcache, d1), jnp.arange(1, k + 1))
            drafted = jnp.swapaxes(toks, 0, 1).reshape(s, nb, k)
            return drafted, dcache

        on_accel = on_tpu()
        return jax.jit(draft_fn, donate_argnums=(1,) if on_accel else ())

    def _build_spec_verify(self):
        """The verify worker: ONE target forward tree-attends every
        branch of every speculating slot ([committed, d_1..d_k] per lane
        — in-step causal attention over the lane's packed rows), accepts
        the deepest target-consistent path via
        :func:`..speculative.medusa_accept_longest`, and atomically
        un-publishes every rejected row's stored position in BOTH pools
        (one fixed-shape scatter each — the COW-lane rollback). Returns
        per-slot ``(emit [k+1], accept_len, best_branch)``; the host
        adopts the winner lane's blocks and frees the losers."""
        spec, e = self._spec, self.ecfg
        k, nb, s = spec.speculation_length, spec.num_branches, \
            self._spec_slots
        cfg, forward = self.model_cfg, self._forward_fn
        buffers, branch_of = self._spec_buffers, self._spec_branch_of
        base = e.max_slots
        rows = s * nb * (k + 1)

        def verify_fn(params, cache, dcache, committed, drafted, pos):
            offs = jnp.arange(k + 1)
            lane_tok = jnp.concatenate(
                [jnp.repeat(committed, nb).reshape(s, nb, 1), drafted],
                axis=2)                                      # [S, B, k+1]
            lane_pos = jnp.broadcast_to(jnp.where(
                pos[:, None, None] < PAD_POSITION,
                pos[:, None, None] + offs[None, None, :], PAD_POSITION),
                (s, nb, k + 1))
            lanes = (base + jnp.arange(s * nb)).reshape(s, nb)
            slot_ids = jnp.broadcast_to(
                lanes[:, :, None], (s, nb, k + 1)).reshape(rows)
            positions = lane_pos.reshape(1, rows)
            logits, cache = forward(
                cfg, params, lane_tok.reshape(1, rows), positions, cache,
                slot_ids=slot_ids)
            with device_scope("sample"):
                lg = logits[0].reshape(s, nb, k + 1, logits.shape[-1])
                # tree node order matches SpeculationConfig.tree_choices():
                # root, then branch-major chains — node (b, d) at 1 + b*k+d-1
                tree_logits = jnp.concatenate(
                    [lg[:, 0, :1], lg[:, :, 1:].reshape(s, nb * k, -1)],
                    axis=1)
                tree_tokens = jnp.concatenate(
                    [committed[:, None], drafted.reshape(s, nb * k)], axis=1)
                best, alen = medusa_accept_longest(tree_logits, tree_tokens,
                                                   buffers)
                bonus = jnp.take_along_axis(
                    jnp.argmax(tree_logits, axis=-1), best[:, None],
                    axis=1)[:, 0]
                bstar = jnp.maximum(branch_of[best], 0)
                sel = jnp.take_along_axis(
                    drafted, bstar[:, None, None], axis=1)[:, 0]  # [S, k]
                jj = offs[None, :]
                emit = jnp.where(jj < alen[:, None],
                                 jnp.pad(sel, ((0, 0), (0, 1))),
                                 bonus[:, None])
            # rollback: un-publish every row outside the accepted path of
            # the winning branch, in both pools (same tables, same flat
            # indices — the pools share block geometry by construction)
            with device_scope("attn.pool_write"):
                brow = jnp.broadcast_to(
                    jnp.arange(nb)[None, :, None], (s, nb, k + 1))
                keep = ((brow == bstar[:, None, None])
                        & (offs[None, None, :] <= alen[:, None, None]))
                tok_tables = cache.block_tables[
                    jnp.clip(slot_ids, 0, cache.max_slots - 1)]
                flat_idx = flat_write_indices(tok_tables, positions[0],
                                              cache.block_size,
                                              cache.capacity)
                reject = (~keep).reshape(rows)
                cache = cache.replace(pos=mask_pool_positions(
                    cache.pos, flat_idx, reject))
                dcache = dcache.replace(pos=mask_pool_positions(
                    dcache.pos, flat_idx, reject))
            return cache, dcache, emit, alen, bstar

        on_accel = on_tpu()
        return jax.jit(verify_fn,
                       donate_argnums=(1, 2) if on_accel else ())

    def _build_worker(self, worker: str, width: int):
        """One serving worker: the jitted step, or — with an AOT cache —
        a load-or-compile :class:`~.aot_cache.AotWorker`. Workers are
        fully determined by (program, config, shapes), so the cache key
        folds all of :meth:`_worker_fingerprint` plus the packed width;
        the first replica per key compiles, every later replica (a
        scale-up, a probation revival, a restarted process with a disk
        cache) loads the serialized executable instead. The speculation
        workers (``spec_draft``/``spec_verify``) have fixed widths of
        their own (folded into the fingerprint via the speculation
        config), so ``width`` is 0 for them."""
        if worker == "spec_draft":
            jitted = self._build_spec_draft()
        elif worker == "spec_verify":
            jitted = self._build_spec_verify()
        elif worker == "cp_prefill":
            jitted = self._build_cp_step(prefill=True)
        else:
            jitted = self._build_step()
        if self._aot is None:
            return jitted
        key = self._aot.key_for("engine-step", worker, width,
                                *self._worker_fingerprint())
        compiled, from_cache = self._aot.compile_or_load(
            key, jitted, self._spec_example_args(worker)
            if worker.startswith("spec_") else self._example_args(width))
        return AotWorker(compiled, from_cache)

    def _worker_fingerprint(self) -> Tuple[Any, ...]:
        """Everything besides shape width that changes the compiled step:
        model config, engine knobs the traced program reads, the source
        of the forward + sampler, and the params treedef/shapes/dtypes
        (values don't matter — params are a runtime operand)."""
        e = self.ecfg
        params_spec = tuple(
            (jax.tree_util.keystr(path), tuple(x.shape), str(x.dtype))
            for path, x in jax.tree_util.tree_flatten_with_path(
                self.params)[0])
        spec_fp: Tuple[Any, ...] = ()
        if self._spec is not None:
            spec_fp = (repr(self._spec), self._spec_slots,
                       repr(self._draft_cfg), tuple(
                           (jax.tree_util.keystr(path), tuple(x.shape),
                            str(x.dtype))
                           for path, x in
                           jax.tree_util.tree_flatten_with_path(
                               self._draft_params)[0]))
        cp_fp: Tuple[Any, ...] = ()
        if self._cp > 1:
            cp_fp = (self._cp, self._cp_width, e.cp_wire_dtype)
        return (repr(self.model_cfg), e.block_size, e.num_blocks,
                e.max_slots, e.max_blocks_per_seq, e.quantized,
                str(e.kv_dtype), repr(e.sampling),
                # the step's own operands are part of the program: an
                # executable cached for another signature must miss
                source_fingerprint(self._forward_fn, sample,
                                   ServingEngine._build_step, _hold_out,
                                   *((BlockServing._build_block_step,)
                                     if self._block is not None else ())),
                params_spec) + spec_fp + cp_fp

    def _example_args(self, width: int):
        """Abstract-equivalent inputs for AOT lowering: exactly the
        shapes/dtypes/shardings ``_run_worker`` passes at ``width``
        (an all-pad batch — only avals matter for lowering)."""
        tokens = jnp.zeros((1, width), jnp.int32)
        positions = jnp.full((1, width), PAD_POSITION, jnp.int32)
        slot_ids = jnp.full((width,), self.ecfg.max_slots, jnp.int32)
        if self._spec is not None:
            return (self.params, self._draft_params, self.cache,
                    self.dcache, tokens, positions, slot_ids, self._rng)
        if self._cp > 1:
            return (self.params, self.cache, tokens, positions, slot_ids,
                    self._rng)
        if self._block is not None:
            return self._block_example_args(width)
        return (self.params, *_hold_out(self.cache), tokens, positions,
                slot_ids, self._prev_sampled(width),
                jnp.full((width,), -1, jnp.int32), self._rng)

    def _prev_sampled(self, width: int):
        """The tokens the worker of ``width`` sampled in its last step, as
        the device holds them; before its first step, zeros of that
        output's type on the cache's sharding (one sharding key, one
        compile)."""
        if width not in self._last_sampled:
            self._last_sampled[width] = jax.device_put(
                jnp.zeros((width,), jnp.int32), self._sharding)
        return self._last_sampled[width]

    def _spec_example_args(self, worker: str):
        """AOT lowering inputs for the two speculation workers (all-pad
        round — avals only)."""
        spec, s = self._spec, self._spec_slots
        committed = jnp.zeros((s,), jnp.int32)
        pos = jnp.full((s,), PAD_POSITION, jnp.int32)
        if worker == "spec_draft":
            return (self._draft_params, self.dcache, committed, pos)
        drafted = jnp.zeros(
            (s, spec.num_branches, spec.speculation_length), jnp.int32)
        return (self.params, self.cache, self.dcache, committed, drafted,
                pos)

    def worker_compile_counts(self) -> Dict[str, int]:
        """Per-worker compile counts: ``{"packed": n}`` or, when
        disaggregated, ``{"prefill": n, "decode": n}``."""
        def size(fn):
            try:
                return int(fn._cache_size())
            except Exception:  # pragma: no cover - jit internals moved
                return -1
        if self._cp > 1:
            return {"packed": size(self._step_fn),
                    "cp_prefill": size(self._prefill_fn)}
        if self.ecfg.disaggregated:
            return {"prefill": size(self._prefill_fn),
                    "decode": size(self._decode_fn)}
        counts = {"packed": size(self._step_fn)}
        if self._spec is not None:
            counts["spec_draft"] = size(self._spec_draft_fn)
            counts["spec_verify"] = size(self._spec_verify_fn)
        return counts

    def compile_count(self) -> int:
        """Number of distinct compilations of the serving step (the
        no-recompile invariant: stays 1 per worker as the live-request
        mix — and the prefix-hit rate — varies)."""
        return max(self.worker_compile_counts().values())

    # -- public API -------------------------------------------------------

    def _now(self) -> float:
        return self._clock() - self._t0

    def _refuse(self, feature: str) -> None:
        raise ValueError(
            f"{type(self.model_cfg).__name__} cannot be served with "
            f"{feature}: {self._unsupported[feature]}")

    def _refuse_session_export(self) -> None:
        if "session_export" in self._unsupported:
            self._refuse("session_export")

    def max_model_len(self) -> int:
        """Longest request (prompt + new tokens) this engine can ever
        serve: the model's rope/context bound, the block-table width,
        and the pool — where cp>1 lifts the pool cap to the GLOBAL
        ``cp * num_blocks`` blocks (a single mesh's slice is no longer
        the ceiling; that is the whole point of the long-context
        tier)."""
        e = self.ecfg
        return min(self.model_cfg.max_seq_len,
                   self._cache_kind.max_positions(
                       min(e.max_blocks_per_seq, self._pool_blocks),
                       e.block_size))

    def fits(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Whether a request of this size could ever run on this engine
        (alone, with the whole pool to itself)."""
        total = int(prompt_len) + int(max_new_tokens)
        if self._block is not None:
            # the last block is run whole
            total += -total % self._block.block_length
        if self._cp > 1 and prompt_len > self._cp_width:
            return False    # one ring pass must cover the whole prompt
        return prompt_len > 0 and total <= self.max_model_len()

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               uid: Optional[str] = None,
               arrival_time: Optional[float] = None) -> str:
        """Enqueue a request. Raises :class:`RequestRejected` — with
        ``reason="never_fits"`` for over-capacity requests (could never
        fit the pool / block table / model context even alone) or
        ``reason="draining"`` after :meth:`drain` — after recording the
        rejection in ``results``/``stats``."""
        if uid is None:
            uid = f"req{self._uid_counter}"
            self._uid_counter += 1
        prompt = [int(t) for t in prompt]
        req = _RequestState(
            uid=uid, prompt=prompt, max_new_tokens=int(max_new_tokens),
            arrival_time=(self._now() if arrival_time is None
                          else float(arrival_time)))
        tracer = get_tracer()
        if tracer.enabled:
            # idempotent: adopts the router's trace when fleet-managed,
            # opens a fresh one standalone — before the admission checks
            # so a rejection still closes a complete span
            tracer.request_begin(uid, replica=self.name or "engine")
            tracer.request_phase_begin(uid, "engine_queue")
        if self._draining:
            self._reject(req, "draining",
                         f"{uid}: engine is draining, not admitting")
        if not self.fits(req.prompt_len, req.max_new_tokens):
            self._reject(
                req, "never_fits",
                f"{uid}: prompt_len={req.prompt_len} "
                f"max_new={req.max_new_tokens} cannot fit this engine")
        if self._block is not None:
            req.prefill_len = (req.prompt_len
                               - req.prompt_len % self._block.block_length)
        self._queue.append(req)
        self.stats.queue_depth = self.queue_depth()
        return uid

    def _reject(self, req: _RequestState, reason: str, detail: str):
        self.stats.rejected += 1
        self.results[req.uid] = RequestResult(
            uid=req.uid, prompt_len=req.prompt_len, tokens=[],
            status="rejected")
        tracer = get_tracer()
        trace_id = tracer.request_trace_id(req.uid) if tracer.enabled \
            else None
        if self._standalone_obs:
            observe_request_metrics(
                "rejected", replica=self.name or "engine",
                queue_s=0.0, e2e_s=0.0)
            if tracer.enabled:
                tracer.request_end(req.uid, outcome="rejected",
                                   reason=reason)
        raise RequestRejected(reason, detail, trace_id=trace_id)

    def has_work(self) -> bool:
        """Whether a :meth:`step` has anything left to do: a request
        queued or in a slot, or a step in flight whose tokens (and the
        results of the requests they finish) have not landed."""
        return (bool(self._queue) or self._inflight is not None
                or any(s is not None for s in self._slots))

    # -- router hooks -----------------------------------------------------

    def queue_depth(self) -> int:
        """Live requests on this engine (queued + running slots) — the
        router's join-shortest-queue load signal."""
        return (len(self._queue)
                + sum(1 for s in self._slots if s is not None))

    def pool_free_blocks(self) -> int:
        """Unallocated KV blocks in the pool (occupancy = 1 - free/total)."""
        return self.allocator.num_blocks - self.allocator.num_allocated

    @property
    def speculating(self) -> bool:
        """Whether decode steps currently run speculation rounds."""
        return self._spec is not None and self._spec_on

    def set_speculation(self, on: bool) -> None:
        """Toggle speculation at a step boundary (the router's SLO
        auto-toggle hook). Toggling only changes *which* compiled workers
        the host invokes — never any traced shape — so flapping it does
        not recompile anything. A no-op on engines built without a
        :class:`~.speculative.SpeculationConfig`."""
        if self._spec is not None:
            self._spec_on = bool(on)

    def prefix_lookup(self, prompt: Sequence[int]) -> int:
        """How many tokens of ``prompt`` this engine's prefix cache
        already holds (0 without ``prefix_sharing``) — the router's
        prefix-locality placement and admission-credit signal. Capped at
        ``len(prompt) - 1``: the last prompt row always runs so the
        request produces logits."""
        if self.prefix_cache is None or len(prompt) <= 1:
            return 0
        return self.prefix_cache.lookup([int(t) for t in prompt],
                                        len(prompt) - 1)

    def release_prefix_cache(self) -> None:
        """Drop the trie's own block references (blocks that live slots
        still map stay allocated); blocks that actually free get the
        usual stale-position hygiene on the next step."""
        if self.prefix_cache is not None:
            self._freed_dirty.update(self.prefix_cache.clear())

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self) -> None:
        """Stop admitting new requests; in-flight work keeps stepping to
        completion (``submit`` now rejects with ``reason="draining"``)."""
        self._settle()
        self._draining = True

    def evict(self, request_id: str):
        """Forcibly remove a live request (queued or running), freeing any
        blocks it holds. Returns ``(prompt, generated_so_far)`` so the
        caller can resubmit it elsewhere; raises ``KeyError`` if the
        request is not live here. The request leaves no entry in
        ``results`` — its fate now belongs to the resubmitter."""
        self._settle()
        for req in self._queue:
            if req.uid == request_id:
                self._queue.remove(req)
                self.stats.resubmitted += 1
                self.stats.queue_depth = self.queue_depth()
                return list(req.prompt), list(req.generated)
        for req in self._slots:
            if req is not None and req.uid == request_id:
                self._release(req)
                self.stats.resubmitted += 1
                self.stats.queue_depth = self.queue_depth()
                return list(req.prompt), list(req.generated)
        raise KeyError(f"request {request_id!r} is not live on this engine")

    # -- live migration ---------------------------------------------------

    def aot_warm(self) -> bool:
        """True when every worker loaded from the AOT cache — this
        engine spun up without compiling anything."""
        if self._cp > 1:
            fns = [self._step_fn, self._prefill_fn]
        elif self.ecfg.disaggregated:
            fns = [self._prefill_fn, self._decode_fn]
        else:
            fns = [self._step_fn]
        if self._spec is not None:
            fns += [self._spec_draft_fn, self._spec_verify_fn]
        return all(getattr(fn, "from_cache", False) for fn in fns)

    def export_session(self, request_id: str) -> SessionTicket:
        """Lift a live request off this engine as a :class:`SessionTicket`
        — scheduler state plus its KV blocks — leaving no trace here
        (blocks freed, no ``results`` entry; the session's fate belongs
        to the importer). Unlike :meth:`evict`, generated tokens and
        cached KV *survive*: landing the ticket elsewhere re-prefills
        nothing. Raises ``KeyError`` if the request is not live here."""
        self._refuse_session_export()
        self._settle()
        now = self._now()
        for req in self._queue:
            if req.uid == request_id:
                self._queue.remove(req)
                self.stats.migrated_out += 1
                self.stats.queue_depth = self.queue_depth()
                return SessionTicket(
                    uid=req.uid, prompt=list(req.prompt),
                    generated=list(req.generated),
                    max_new_tokens=req.max_new_tokens,
                    n_cached=0, age_s=now - req.arrival_time,
                    ttft_s=None,
                    trace=get_tracer().request_export(req.uid))
        for req in self._slots:
            if req is not None and req.uid == request_id:
                blocks = [int(b) for b in self._tables[req.slot]
                          if b >= 0]
                # keep_upto=n_cached: a partially-shared donor block
                # ships only this session's rows, never the donor's tail
                kv = extract_blocks(self.cache, blocks,
                                    keep_upto=req.n_cached)
                kv_fp = (kv_payload_fingerprints(kv, PAYLOAD_BLOCK_AXES)
                         if self.ecfg.integrity else None)
                ticket = SessionTicket(
                    uid=req.uid, prompt=list(req.prompt),
                    generated=list(req.generated),
                    max_new_tokens=req.max_new_tokens,
                    n_cached=req.n_cached,
                    age_s=now - req.arrival_time,
                    ttft_s=(req.first_token_time - req.arrival_time
                            if req.first_token_time is not None
                            else None),
                    n_blocks=len(blocks), kv=kv, kv_fp=kv_fp,
                    trace=get_tracer().request_export(req.uid))
                self._release(req)
                self.stats.migrated_out += 1
                self.stats.queue_depth = self.queue_depth()
                return ticket
        raise KeyError(f"request {request_id!r} is not live on this engine")

    def import_session(self, ticket: SessionTicket) -> None:
        """Land a :class:`SessionTicket` here and continue it with zero
        re-prefill: allocate fresh blocks, inject the shipped KV, rebuild
        the scheduler state at its exported position. All-or-nothing —
        :class:`RequestRejected` (draining / never-fits, raised *without*
        recording a result: the ticket still belongs to the caller) or
        :class:`CacheExhaustedError` (no slot / no blocks) leave this
        engine untouched so the caller can try another destination or
        fall back to resubmission — as does
        :class:`~..resilience.integrity.IntegrityError` when the shipped
        KV blocks fail their fingerprint check (a corrupted session must
        never be continued, and a *partially* imported one would be
        worse: the verify runs before any pool mutation). With
        ``integrity`` on, a ticket that ships KV *without* fingerprints
        is also rejected — fail closed; importing unverifiable blocks
        would silently disable the very check the config asked for."""
        self._refuse_session_export()
        if self._draining:
            raise RequestRejected(
                "draining", f"{ticket.uid}: engine is draining")
        if not self.fits(len(ticket.prompt), ticket.max_new_tokens):
            raise RequestRejected(
                "never_fits", f"{ticket.uid}: cannot fit this engine")
        if (self.ecfg.integrity and ticket.kv is not None
                and ticket.kv_fp is None):
            self.stats.integrity_rejects += 1
            emit_event("integrity_mismatch", scope="kv_ticket",
                       uid=ticket.uid,
                       corrupt=[("<unfingerprinted>", -1)])
            raise IntegrityError(
                f"{ticket.uid}: ticket ships KV with no fingerprints "
                "while this engine enforces integrity — importing "
                "unverifiable blocks would silently skip the check; "
                "re-export with integrity on (or turn it off here "
                "explicitly)")
        if ticket.kv is not None and ticket.kv_fp is not None:
            arrived = kv_payload_fingerprints(ticket.kv, PAYLOAD_BLOCK_AXES)
            bad: List[Tuple[str, int]] = []
            for name, fps in ticket.kv_fp.items():
                got = arrived.get(name, [])
                if len(got) != len(fps):
                    bad.append((name, -1))  # tensor missing/reshaped
                    continue
                bad.extend((name, i) for i, (want, have)
                           in enumerate(zip(fps, got)) if want != have)
            bad.extend((name, -1) for name in arrived
                       if name not in ticket.kv_fp)
            if bad:
                self.stats.integrity_rejects += 1
                emit_event("integrity_mismatch", scope="kv_ticket",
                           uid=ticket.uid, corrupt=bad[:8])
                raise IntegrityError(
                    f"{ticket.uid}: shipped KV blocks failed their "
                    f"integrity fingerprints at (tensor, block) {bad[:8]} "
                    "— ticket rejected, nothing imported")
        self._land_session(ticket, blocks=None)

    def _land_session(self, ticket: SessionTicket,
                      blocks: Optional[List[int]]) -> None:
        """Shared landing tail of :meth:`import_session` and
        :meth:`commit_stream_import`: rebuild scheduler state at the
        ticket's exported position. ``blocks=None`` means the KV rides
        in ``ticket.kv`` and blocks are allocated+injected here;
        otherwise ``blocks`` are already allocated and hold the streamed
        payload, and only the slot wiring happens."""
        now = self._now()
        req = _RequestState(
            uid=ticket.uid, prompt=[int(t) for t in ticket.prompt],
            max_new_tokens=int(ticket.max_new_tokens),
            arrival_time=now - ticket.age_s,
            generated=[int(t) for t in ticket.generated])
        tracer = get_tracer()
        if tracer.enabled:
            # resume the request's trace under its original trace-id (or
            # open one for tickets from a pre-tracing exporter) and mark
            # the hop, so the final span shows the migration count
            if ticket.trace is not None:
                tracer.request_import(ticket.trace)
            else:
                tracer.request_begin(req.uid)
            tracer.request_mark(req.uid, "migrate")
            tracer.request_annotate(req.uid,
                                    replica=self.name or "engine")
        if ticket.n_blocks == 0:
            self._queue.append(req)
            self.stats.migrated_in += 1
            self.stats.queue_depth = self.queue_depth()
            return
        free = self._free_slots()
        if not free:
            raise CacheExhaustedError(
                f"{ticket.uid}: no free slot on this engine")
        if blocks is None:
            blocks = self._alloc_blocks(ticket.n_blocks)
            self.cache = inject_blocks(self.cache, blocks, ticket.kv)
            # injected blocks are fully overwritten (K/V and positions)
            # — a pending freed-position wipe would null real rows
            self._freed_dirty.difference_update(blocks)
        slot = free[0]
        req.slot = slot
        req.admit_seq = self._admit_counter
        self._admit_counter += 1
        req.admit_time = now
        req.n_cached = int(ticket.n_cached)
        # tickets ship only the TARGET pool's KV: the draft pool has no
        # rows for the imported context, so speculating on this request
        # would draft from holes. It decodes normally (spec_ok flips back
        # if it is ever preempted and re-prefilled here).
        req.spec_ok = False
        if ticket.ttft_s is not None:
            req.first_token_time = req.arrival_time + ticket.ttft_s
        for i, blk in enumerate(blocks):
            self._tables[slot, i] = blk
        self._slot_blocks[slot] = list(blocks)
        self._slots[slot] = req
        self.stats.migrated_in += 1
        self.stats.migrated_tokens += req.n_cached
        self.stats.queue_depth = self.queue_depth()
        # the landed prompt blocks are publishable prefix state here too
        self._maybe_insert_prefix(req)

    # -- streamed import (cross-host handoff) -----------------------------
    #
    # Three-phase landing for KV that arrives chunk-by-chunk over a DCN
    # stream instead of inside one ticket: reserve blocks up front,
    # inject each per-layer chunk as it clears its wire fingerprint, and
    # wire the scheduler state only once the whole stream committed. The
    # reserved blocks are never mapped into any slot's table until
    # commit, so half-landed state cannot reach attention; a torn stream
    # aborts and the blocks free (back through the stale-position wipe)
    # with the pool exactly as before ``begin``.

    def begin_stream_import(self, ticket: SessionTicket
                            ) -> Dict[str, Any]:
        """Open a streamed import for ``ticket`` (the stream's *meta*:
        scheduler state with ``kv`` stripped — the payload follows chunk
        by chunk via :meth:`stream_inject`). Reserves ``ticket.n_blocks``
        pool blocks and returns an opaque handle for the other three
        phases. Raises like :meth:`import_session`'s admission checks;
        nothing is reserved on failure."""
        self._refuse_session_export()
        if self._draining:
            raise RequestRejected(
                "draining", f"{ticket.uid}: engine is draining")
        if not self.fits(len(ticket.prompt), ticket.max_new_tokens):
            raise RequestRejected(
                "never_fits", f"{ticket.uid}: cannot fit this engine")
        if ticket.n_blocks <= 0:
            raise ValueError(
                f"{ticket.uid}: streamed import needs KV blocks; "
                "queued-state tickets go through import_session")
        if not self._free_slots():
            raise CacheExhaustedError(
                f"{ticket.uid}: no free slot on this engine")
        blocks = self._alloc_blocks(ticket.n_blocks)
        # chunks overwrite every row of these blocks before commit maps
        # them anywhere — a pending freed-position wipe between the pos
        # chunk landing and commit would null real positions
        self._freed_dirty.difference_update(blocks)
        return {"uid": ticket.uid, "blocks": list(blocks),
                "ticket": ticket}

    def stream_inject(self, handle: Dict[str, Any], name: str,
                      layer: int, arr: Any,
                      blocks: Optional[Sequence[int]] = None) -> None:
        """Land one verified chunk into the reserved blocks: tensor
        ``name`` (``k``/``v``/``k_scale``/``v_scale`` at ``layer``, or
        the layer-less ``pos``). Chunks may land in any order; each
        fully overwrites its rows. ``blocks`` (indices into the
        reserved block list) lands a CP shard chunk — one source rank's
        resident slice of the slab — instead of the whole slab."""
        sel = (handle["blocks"] if blocks is None
               else [handle["blocks"][int(i)] for i in blocks])
        idx = jnp.asarray(sel, jnp.int32)
        if name == "pos":
            self.cache = self.cache.replace(
                pos=self.cache.pos.at[idx].set(
                    jnp.asarray(arr, jnp.int32)))
            return
        pool = getattr(self.cache, name)
        self.cache = self.cache.replace(**{
            name: pool.at[layer, idx].set(jnp.asarray(arr, pool.dtype))})

    def commit_stream_import(self, handle: Dict[str, Any]) -> None:
        """Atomically publish a completed stream: wire the scheduler
        state onto the (already-populated) reserved blocks. Re-checks
        admission — the engine may have started draining or filled its
        slots since ``begin`` — and raises without publishing anything;
        the caller must then :meth:`abort_stream_import`."""
        if self._draining:
            raise RequestRejected(
                "draining",
                f"{handle['uid']}: engine is draining")
        self._land_session(handle["ticket"], blocks=handle["blocks"])

    def abort_stream_import(self, handle: Dict[str, Any]) -> None:
        """Tear down a failed stream: free every reserved block (they
        were never mapped into a table, so nothing else references
        them). Idempotence is the caller's job — abort once."""
        self._freed_dirty.update(self.allocator.free(handle["blocks"]))

    def handoff_ready(self, request_id: str) -> bool:
        """True once ``request_id`` has finished prefill *and* produced
        its first token here — the earliest point where exporting it
        ships a complete prompt KV and an honest ``ttft_s``. A first
        token still in flight has not been produced yet: a poll, it waits
        for no step."""
        for req in self._slots:
            if req is not None and req.uid == request_id:
                return req.decoding and bool(req.generated)
        return False

    def export_prefixes(self, max_blocks: Optional[int] = None
                        ) -> Optional[Dict[str, Any]]:
        """Ship (up to ``max_blocks``) hottest prefix-trie subtrees with
        their pool blocks — warm-start material for a fresh replica, so
        scale-up doesn't start with a cold trie. ``None`` when there is
        nothing to ship."""
        self._settle()
        if self.prefix_cache is None or self.prefix_cache.size == 0:
            return None
        nodes = self.prefix_cache.snapshot(max_blocks)
        blocks = [n["block"] for n in nodes]
        kv = extract_blocks(self.cache, blocks, keep_upto=PAD_POSITION)
        return {"nodes": nodes, "kv": kv}

    def import_prefixes(self, shipment: Optional[Dict[str, Any]]) -> int:
        """Land an :meth:`export_prefixes` shipment into this engine's
        trie; returns the number of nodes inserted. Best-effort: a full
        pool imports nothing (0), nodes the trie already holds keep the
        local block and the shipped copy frees."""
        if (self.prefix_cache is None or not shipment
                or not shipment["nodes"]):
            return 0
        nodes = shipment["nodes"]
        try:
            blocks = self._alloc_blocks(len(nodes))
        except CacheExhaustedError:
            return 0
        self.cache = inject_blocks(self.cache, blocks, shipment["kv"])
        self._freed_dirty.difference_update(blocks)
        chains: List[Optional[int]] = []
        imported = 0
        for node, blk in zip(nodes, blocks):
            parent = (None if node["parent"] is None
                      else chains[node["parent"]])
            if node["parent"] is not None and parent is None:
                chains.append(None)   # orphaned by a collision upstream
            else:
                chain, inserted = self.prefix_cache.insert(
                    parent, node["tokens"], blk)
                chains.append(chain)
                imported += inserted
            # drop the import's own ref: the trie (or nobody) owns the
            # block now; blocks that actually freed need the stale-
            # position wipe like any other free
            self._freed_dirty.update(self.allocator.free([blk]))
        return imported

    def run(self) -> Dict[str, RequestResult]:
        """Drive :meth:`step` until queue and slots drain. With the real
        clock, waits out gaps before future ``arrival_time``s; an injected
        clock should drive :meth:`step` directly instead."""
        while self.has_work():
            if (self._inflight is None
                    and not any(s is not None for s in self._slots)):
                pending = [r.arrival_time for r in self._queue]
                gap = min(pending) - self._now() if pending else 0.0
                if gap > 0:
                    if self._clock is not time.monotonic:
                        self._t0 -= gap  # fake clock: fast-forward
                    else:
                        time.sleep(min(gap, 0.05))
                        continue
            self.step()
        return self.results

    # -- scheduling -------------------------------------------------------

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _admit(self) -> None:
        free = self._free_slots()
        now = self._now()
        tracer = get_tracer()
        while free and self._queue and self._queue[0].arrival_time <= now:
            req = self._queue.popleft()
            slot = free.pop(0)
            req.slot = slot
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            if req.admit_time is None:
                req.admit_time = now
            if tracer.enabled:
                tracer.request_phase_end(req.uid, "engine_queue")
            self._slots[slot] = req
            self._apply_prefix(req)

    def _apply_prefix(self, req: _RequestState) -> None:
        """Map the longest cached prefix of the prompt into the slot's
        table — one allocator ref per mapped block, no prefill work —
        capped at ``prompt_len - 1`` so at least one prompt row runs and
        produces logits. A partial-tail match maps a donor block whose
        first ``m`` tokens we share; our first divergent write into it
        triggers copy-on-write (:meth:`_ensure_block`)."""
        req.chain = None
        req.trie_blocks = 0
        req.trie_dead = False
        if self.prefix_cache is None or req.n_cached:
            return
        full, matched, partial, chain = self.prefix_cache.match(
            req.prompt, req.prompt_len - 1)
        for i, blk in enumerate(full):
            self.allocator.ref(blk)
            self._tables[req.slot, i] = blk
            self._slot_blocks[req.slot].append(blk)
        req.chain = chain
        req.trie_blocks = len(full)
        req.n_cached = matched
        if partial is not None:
            blk, m = partial
            self.allocator.ref(blk)
            self._tables[req.slot, len(full)] = blk
            self._slot_blocks[req.slot].append(blk)
            req.n_cached += m
        req.shared_tokens = req.n_cached
        self.stats.prefix_hit_tokens += req.n_cached

    def _alloc_blocks(self, n: int) -> List[int]:
        """Pool allocation with prefix-cache relief: before giving up,
        evict least-recently-matched cached prefixes until enough blocks
        actually free (the caller's preemption path handles the rest)."""
        try:
            return self.allocator.alloc(n)
        except CacheExhaustedError:
            if self.prefix_cache is None or self.prefix_cache.size == 0:
                raise
            self._freed_dirty.update(
                self.prefix_cache.evict(n - self.allocator.num_free))
            return self.allocator.alloc(n)

    def _ensure_block(self, req: _RequestState, position: int) -> None:
        """Map the blocks a row at ``position`` needs into the slot's
        table (the cache kind says which columns: the one covering the
        position and, where the row completes a window of a
        window-summary cache, that window's summary column), allocating
        from the pool (raises CacheExhaustedError dry). A write landing
        in a block other owners also reference clones it first
        (copy-on-write): the clone replaces the shared block in this
        slot's table and the copy itself runs as a fixed-shape jitted
        pass at the next step boundary."""
        columns = self._cache_kind.columns_to_map(position,
                                                  self.ecfg.block_size)
        self._map_column(req, columns[0], position)
        for col in columns[1:]:
            # this row ends a window: the step writes the window's
            # summaries into a block of their own. It is taken here, where
            # a dry pool is the schedule's to handle, and mapped by
            # ``engine/roll``, once the schedule stands
            if (req.slot, col) not in self._pending_roll:
                blk = self._alloc_blocks(1)[0]
                self._slot_blocks[req.slot].append(blk)
                self._pending_roll[(req.slot, col)] = blk

    def _roll(self) -> None:
        """Map the summary blocks of the windows this step completes (the
        ring columns are reused as they are: mapped, refcount 1)."""
        for (slot, col), blk in self._pending_roll.items():
            self._tables[slot, col] = blk
        self._rolled += len(self._pending_roll)
        self._pending_roll.clear()

    def _map_column(self, req: _RequestState, blk_i: int,
                    position: int) -> None:
        cur = int(self._tables[req.slot, blk_i])
        if cur >= 0:
            if self.allocator.refcount(cur) <= 1:
                return
            dst = self._alloc_blocks(1)[0]
            self._pending_cow.append((cur, dst, position))
            # dst's stale positions are fully overwritten by the copy;
            # exempt it from the freed-position wipe that runs after
            self._freed_dirty.discard(dst)
            self._tables[req.slot, blk_i] = dst
            sb = self._slot_blocks[req.slot]
            sb[sb.index(cur)] = dst
            self._freed_dirty.update(self.allocator.free([cur]))
            self.stats.cow_copies += 1
            return
        blk = self._alloc_blocks(1)[0]
        self._tables[req.slot, blk_i] = blk
        self._slot_blocks[req.slot].append(blk)

    def _release(self, req: _RequestState) -> None:
        slot = req.slot
        # only blocks whose last reference dropped get their positions
        # wiped — clearing a still-shared block would blind its sharers
        self._freed_dirty.update(
            self.allocator.free(self._slot_blocks[slot]))
        self._slot_blocks[slot] = []
        self._tables[slot, :] = -1
        self._slots[slot] = None
        for key in [k for k in self._pending_roll if k[0] == slot]:
            del self._pending_roll[key]
        req.slot = None

    def _preempt_youngest(self, keep: _RequestState) -> None:
        """Evict the most recently admitted running request — possibly
        ``keep`` itself — back to the queue front; its generated tokens
        are discarded and it restarts from the prompt. Always taking the
        true youngest means the oldest running request is never evicted,
        so it monotonically advances and the schedule cannot livelock
        (two requests ping-ponging each other's blocks)."""
        candidates = [s for s in self._slots if s is not None]
        if not candidates:
            raise CacheExhaustedError(
                "pool exhausted with no running request to preempt")
        victim = max(candidates, key=lambda r: r.admit_seq)
        self._release(victim)
        victim.restart()
        self._queue.appendleft(victim)
        self.stats.preempted += 1

    def _build_schedule(self, skip=frozenset()):
        """Pack this step's rows: (req, token, position, produce) — one
        decode row per decoding slot, then prefill chunks. A decode row
        whose token the step in flight samples carries ``None`` for it:
        the device takes it from that step's row ``req.take_row``.
        Preempts (youngest first) when a decode row can't get its next
        block; prefill chunks merely truncate. Returns ``(decode_rows,
        prefill_rows)``: packed mode shares one ``token_budget`` across
        both lists; disaggregated mode gives each worker its own width
        (decode = ``max_slots``, prefill = ``prefill_budget``). ``skip``
        (request ids) excludes this round's speculation participants —
        their decode advances through the draft/verify workers instead
        of a packed decode row."""
        e = self.ecfg
        if self._block is not None:
            return self._build_block_schedule()
        if self._cp > 1:
            decode_budget = e.token_budget
            prefill_budget = 0      # prompts go through the ring worker
            shared_budget = False
        elif e.disaggregated:
            decode_budget = e.max_slots
            prefill_budget = e.prefill_budget or e.token_budget
            shared_budget = False
        else:
            decode_budget = prefill_budget = e.token_budget
            shared_budget = True
        while True:
            try:
                decode_rows = []
                for req in sorted(
                        (s for s in self._slots
                         if s is not None and s.decoding
                         and id(s) not in skip),
                        key=lambda r: r.admit_seq):
                    if len(decode_rows) >= decode_budget:
                        break
                    pos = req.n_cached
                    self._ensure_block(req, pos)
                    tok = (None if req.in_flight
                           else req.generated[pos - req.prompt_len])
                    decode_rows.append((req, tok, pos, True))
                break
            except CacheExhaustedError:
                self._preempt_youngest(req)
        if self._cp > 1:
            return decode_rows, self._build_cp_prefill_rows()
        prefill_rows = []
        used = len(decode_rows) if shared_budget else 0
        for req in sorted((s for s in self._slots
                           if s is not None and not s.decoding),
                          key=lambda r: r.admit_seq):
            room = prefill_budget - used - len(prefill_rows)
            if room <= 0:
                break
            chunk = min(room, req.prompt_len - req.n_cached)
            for i in range(chunk):
                pos = req.n_cached + i
                try:
                    self._ensure_block(req, pos)
                except CacheExhaustedError:
                    chunk = i  # defer the rest of this prompt
                    break
                produce = (pos == req.prompt_len - 1)
                prefill_rows.append((req, req.prompt[pos], pos, produce))
            req.n_cached += chunk
            self.stats.prefill_tokens += chunk
        return decode_rows, prefill_rows

    def _build_cp_prefill_rows(self):
        """One whole-prompt ring pass per step: take the oldest
        not-yet-prefilled slot, allocate EVERY prompt block rank-strictly
        (block ``b`` of the sequence lands on the rank whose token slice
        writes it — the ring worker's scatter drops the row everywhere
        else), and emit its rows for the ``cp_prefill`` worker. A prompt
        whose per-rank slices don't all fit right now simply waits
        (head-of-line; decode traffic retiring frees blocks) — deferral
        over preemption keeps the long-context tier livelock-free."""
        for req in sorted((s for s in self._slots
                           if s is not None and not s.decoding),
                          key=lambda r: r.admit_seq):
            if not self._cp_alloc_prompt(req):
                return []
            rows = [(req, req.prompt[pos], pos,
                     pos == req.prompt_len - 1)
                    for pos in range(req.prompt_len)]
            req.n_cached = req.prompt_len
            self.stats.prefill_tokens += req.prompt_len
            return rows
        return []

    def _cp_alloc_prompt(self, req: _RequestState) -> bool:
        """Rank-strict allocation of all of ``req``'s prompt blocks, or
        nothing: sequence block ``b`` (positions ``[b*bs, (b+1)*bs)``)
        belongs to the rank whose contiguous ``cp_prefill_width/cp``
        token slice covers it. All-or-nothing so a deferred prompt never
        holds a partial claim."""
        e = self.ecfg
        w_loc = self._cp_width // self._cp
        n_blocks = -(-req.prompt_len // e.block_size)
        per_rank: Dict[int, List[int]] = {}
        for b in range(n_blocks):
            per_rank.setdefault((b * e.block_size) // w_loc, []).append(b)
        free = self.allocator.free_per_rank()
        if any(len(bs) > free[r] for r, bs in per_rank.items()):
            return False
        for r, bs in per_rank.items():
            for b, blk in zip(bs, self.allocator.alloc(len(bs), rank=r)):
                self._tables[req.slot, b] = blk
                self._slot_blocks[req.slot].append(blk)
        return True

    def _apply_pending_cow(self) -> None:
        """Run the copy-on-write clones registered during scheduling as
        fixed-shape ``[max_slots]`` batches (pad entries: dst ==
        num_blocks, dropped). Must run *before* the freed-position wipe:
        a COW source freed in this same scheduling pass still needs its
        positions readable for the clone."""
        if not self._pending_cow:
            return
        m = self.ecfg.max_slots
        for start in range(0, len(self._pending_cow), m):
            chunk = self._pending_cow[start:start + m]
            src = np.zeros((m,), np.int32)
            dst = np.full((m,), self._pool_blocks, np.int32)
            keep = np.zeros((m,), np.int32)
            for i, (s, d, k) in enumerate(chunk):
                src[i], dst[i], keep[i] = s, d, k
            src, dst, keep = (jnp.asarray(src), jnp.asarray(dst),
                              jnp.asarray(keep))
            self.cache = cow_copy_blocks(self.cache, src, dst, keep)
            if self.dcache is not None:
                # both pools share block ids: the same clone list keeps
                # the draft pool's view of every block bit-consistent
                self.dcache = cow_copy_blocks(self.dcache, src, dst, keep)
        self._pending_cow.clear()

    def _dispatch(self, fn, rows, width: int, rng, span: str,
                  step: int) -> _InFlight:
        """Pack ``rows`` into a fixed ``width`` batch and enqueue one
        jitted worker; returns the step in flight (its sampled tokens
        aligned with ``rows``, still on the device). ``span`` is the
        caller's open span: two of its children split the host's packing
        from the uploads and the enqueue, the third is :meth:`_fetch`'s;
        ``step`` is the number of the ``step()`` call they belong to."""
        if self._block is not None:
            return self._dispatch_block(fn, rows, width, rng, span, step)
        tracer = get_tracer()
        with tracer.span(span + "/pack", step=step):
            tokens = np.zeros((1, width), np.int32)
            positions = np.full((1, width), PAD_POSITION, np.int32)
            slot_ids = np.full((width,), self.ecfg.max_slots, np.int32)
            take = np.full((width,), -1, np.int32)
            for i, (req, tok, pos, _) in enumerate(rows):
                if tok is None:
                    take[i] = req.take_row
                else:
                    tokens[0, i] = tok
                positions[0, i] = pos
                slot_ids[i] = req.slot
            counted = get_registry().enabled
            rolled, self._rolled = self._rolled, 0
            if counted:
                self._add_counts(self._count_step(
                    positions[0], slot_ids, self._tables,
                    [len(self._slot_blocks[r.slot]) for r in self._slots
                     if r is not None], rolled))
        with tracer.span(span + "/dispatch", step=step):
            if self._spec is not None:
                sampled, self.cache, self.dcache = fn(
                    self.params, self._draft_params, self.cache,
                    self.dcache, jnp.asarray(tokens),
                    jnp.asarray(positions), jnp.asarray(slot_ids), rng)
            elif self._cp > 1:
                sampled, self.cache = fn(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(positions), jnp.asarray(slot_ids), rng)
            else:
                pool, held = _hold_out(self.cache)
                sampled, pool, counts = fn(
                    self.params, pool, held, jnp.asarray(tokens),
                    jnp.asarray(positions), jnp.asarray(slot_ids),
                    self._prev_sampled(width), jnp.asarray(take), rng)
                # the tables and lengths are the arrays the host uploaded:
                # they are no result of the step, and reading them waits
                # for none
                self.cache = pool.replace(**{**held, **counts})
                self._last_sampled[width] = sampled
            flight = _InFlight(rows, [r[0].epoch for r in rows], sampled)
            # on their way to the host before the next step is enqueued
            # behind them
            sampled.copy_to_host_async()
            if counted:
                flight.counts = [(leaf, getattr(self.cache, leaf.leaf))
                                 for leaf in self._device_counts]
                for _, on_device in flight.counts:
                    on_device.copy_to_host_async()
        return flight

    def _fetch(self, flight: _InFlight, span: str, **attrs) -> None:
        """Read what the device returned for ``flight``: the one place the
        host blocks until the device has finished a step (with a step in
        flight, the one before the step it has just enqueued). With the
        tracer on the span says which wait it was: ``ready_us`` until the
        step's tokens are ready on the device, ``copy_us`` the copies to
        the host after that (issued at the enqueue, so near 0 unless a
        copy itself stalls); ``attrs`` are the span's (the call's
        ``step``)."""
        tracer = get_tracer()
        timed = tracer.enabled
        with tracer.span(span + "/fetch", **attrs) as sp:
            if timed:
                flight.sampled.block_until_ready()
                ready = time.perf_counter_ns() / 1000.0
            flight.sampled = np.asarray(flight.sampled)
            for leaf, on_device in flight.counts:
                self._add_counts(leaf.read(np.asarray(on_device)))
            if timed:
                sp.set_attribute("ready_us", ready - sp.t0_us)
                sp.set_attribute(
                    "copy_us", time.perf_counter_ns() / 1000.0 - ready)

    def _add_counts(self, counts) -> None:
        """Add what a step counted, by counter name, to what the next
        publish increments."""
        for name, n in counts.items():
            self._counted[name] += n

    def _maybe_insert_prefix(self, req: _RequestState) -> None:
        """Publish this request's fully-written prompt blocks into the
        trie (post-step: the pool rows exist now). Stops for good on a
        hash collision or an evicted parent chain."""
        if self.prefix_cache is None or req.trie_dead:
            return
        bs = self.ecfg.block_size
        target = min(req.n_cached, req.prompt_len) // bs
        while req.trie_blocks < target:
            i = req.trie_blocks
            chain, _ = self.prefix_cache.insert(
                req.chain, req.prompt[i * bs:(i + 1) * bs],
                int(self._tables[req.slot, i]))
            if chain is None:
                req.trie_dead = True
                return
            req.chain = chain
            req.trie_blocks += 1

    # -- speculation round lifecycle (host side) --------------------------

    def _begin_spec_round(self) -> List[Optional[Tuple]]:
        """Pick this round's speculating slots (oldest decoding first)
        and allocate their branch lanes: each lane's table row is a copy
        of the slot's row with every block the round will write replaced
        by a branch-private clone (COW for live blocks, fresh allocations
        for not-yet-mapped tail blocks). Prefix blocks below the write
        window stay shared by reference. A slot that cannot get its lane
        blocks simply decodes normally this step — lane allocation never
        preempts anyone. Returns a dense list indexed by lane group
        (``None`` = unused group)."""
        if not self.speculating:
            return []
        spec, e = self._spec, self.ecfg
        k, nb, bs = spec.speculation_length, spec.num_branches, \
            e.block_size
        round_state: List[Optional[Tuple]] = []
        for req in sorted((s for s in self._slots
                           if s is not None and s.decoding and s.spec_ok
                           and len(s.generated) < s.max_new_tokens),
                          key=lambda r: r.admit_seq):
            if len(round_state) >= self._spec_slots:
                break
            pos = req.n_cached
            blk0, blk_last = pos // bs, (pos + k) // bs
            if (blk_last >= e.max_blocks_per_seq
                    or pos + k >= self.model_cfg.max_seq_len):
                continue        # no lane room at the table/context end
            mapped = [(bi, int(self._tables[req.slot, bi]))
                      for bi in range(blk0, blk_last + 1)]
            try:
                blocks = self._alloc_blocks(nb * len(mapped))
            except CacheExhaustedError:
                continue        # pool pressure: decode normally instead
            it = iter(blocks)
            lane_blocks: List[List[int]] = []
            for b in range(nb):
                lane = e.max_slots + len(round_state) * nb + b
                self._tables[lane, :] = self._tables[req.slot, :]
                blks = []
                for bi, cur in mapped:
                    dst = next(it)
                    if cur >= 0:
                        # branch-private clone: rows below pos are the
                        # shared committed prefix, rows >= pos are this
                        # lane's to write (the slot's own block stays
                        # untouched until adoption)
                        self._pending_cow.append((cur, dst, pos))
                        self._freed_dirty.discard(dst)
                        self.stats.cow_copies += 1
                    self._tables[lane, bi] = dst
                    blks.append(dst)
                lane_blocks.append(blks)
            round_state.append((req, lane_blocks, blk0, blk_last))
        return round_state

    def _filter_spec_round(self, round_state):
        """Drop participants whose slot the scheduling pass preempted
        after lane allocation, freeing their lanes (positions wiped
        through the usual freed-block hygiene). Keeps ``None`` holes so
        surviving entries stay aligned with their lane rows."""
        out: List[Optional[Tuple]] = []
        for entry in round_state:
            if entry is None:
                out.append(None)
                continue
            req, lane_blocks = entry[0], entry[1]
            if req.slot is not None and self._slots[req.slot] is req:
                out.append(entry)
            else:
                for blks in lane_blocks:
                    self._freed_dirty.update(self.allocator.free(blks))
                out.append(None)
        return out

    def _land_spec_round(self, round_state, emit, alen, bstar,
                         now: float) -> None:
        """Adopt each participant's verification verdict: swap the
        winning branch's lane blocks into the slot's table, free the
        displaced originals plus every losing branch in ONE allocator
        call (atomic — pool accounting never observes a half-freed
        round), append the accepted tokens + bonus, and retire on
        EOS/max_new as usual. Device values arrive as host ints exactly
        once per round (the single fetch in :meth:`step`)."""
        spec, e = self._spec, self.ecfg
        k, nb = spec.speculation_length, spec.num_branches
        for i, entry in enumerate(round_state):
            if entry is None:
                continue
            req, lane_blocks, blk0, blk_last = entry
            a = max(0, min(int(alen[i]), k))
            b = max(0, min(int(bstar[i]), nb - 1))
            sb = self._slot_blocks[req.slot]
            drop: List[int] = []
            for j, bi in enumerate(range(blk0, blk_last + 1)):
                old = int(self._tables[req.slot, bi])
                if old >= 0:
                    drop.append(old)
                    sb.remove(old)
                win = lane_blocks[b][j]
                self._tables[req.slot, bi] = win
                sb.append(win)
            for bb in range(nb):
                if bb != b:
                    drop.extend(lane_blocks[bb])
            self._freed_dirty.update(self.allocator.free(drop))
            req.spec_rounds += 1
            req.spec_accepted += a
            self.stats.spec_rounds += 1
            self.stats.spec_accepted_tokens += a
            done = False
            n_emit = 0
            for tok in (int(t) for t in emit[i, :a + 1]):
                req.generated.append(tok)
                n_emit += 1
                self.stats.tokens_generated += 1
                if req.first_token_time is None:
                    req.first_token_time = now
                    self.stats.ttft_s.append(now - req.arrival_time)
                if (len(req.generated) >= req.max_new_tokens
                        or (e.eos_id is not None
                            and tok == e.eos_id)):
                    done = True
                    break
            req.n_cached += n_emit
            if done:
                self._retire(req, now)
        # lane rows only route one round's writes; park them afterwards
        self._tables[e.max_slots:, :] = -1

    def step(self) -> int:
        """One serving step. Returns the number of live rows packed by
        this call, or landed by it when it had nothing to pack and a step
        was in flight (0 = nothing was runnable and nothing in flight).
        Packed mode enqueues one fixed-shape worker and then reads the
        step enqueued by the call before (:meth:`_fetch`: the device goes
        from one step to the next with the next already queued; what the
        host does in between is under the device's work, not beside it);
        disaggregated mode runs the prefill worker then the decode worker
        and reads each at once — the KV handoff between them is the shared
        block pool itself (table-row surgery, no tensor copies)."""
        tracer = get_tracer()
        self._calls += 1
        call = self._calls
        # what the stepping thread has done so far (its CPU time, switches,
        # faults, run-queue delay): ``_account_call`` reads it again
        began = tracer.thread_reading() if tracer.enabled else None
        with tracer.span("engine/admission", step=call) as entered:
            self._admit()
            round_state = self._begin_spec_round()
            decode_rows, prefill_rows = self._build_schedule(
                {id(x[0]) for x in round_state if x is not None})
            round_state = self._filter_spec_round(round_state)
        rows = decode_rows + prefill_rows
        spec_live = [x for x in round_state if x is not None]
        if not rows and not spec_live:
            return self._settle(call)
        t_start = self._now()
        if self.stats.first_step_t is None:
            self.stats.first_step_t = t_start
        rolled = cleared = 0
        if self._pending_roll:
            with tracer.span("engine/roll", step=call):
                rolled = len(self._pending_roll)
                self._roll()
        with tracer.span("engine/cow", step=call):
            self._apply_pending_cow()
        with tracer.span("engine/hygiene", step=call):
            if self._freed_dirty:
                cleared = len(self._freed_dirty)
                mask = np.zeros((self._pool_blocks,), np.bool_)
                mask[list(self._freed_dirty)] = True
                self._freed_dirty.clear()
                fmask = jnp.asarray(mask)
                self.cache = self.cache.replace(pos=_clear_freed_positions(
                    self.cache.pos, fmask))
                if self.dcache is not None:
                    self.dcache = self.dcache.replace(
                        pos=_clear_freed_positions(self.dcache.pos, fmask))
        with tracer.span("engine/tables", step=call):
            # committed to the cache's sharding: the disaggregated decode
            # worker otherwise sees two sharding keys for its cache operand
            # (prefill's committed output vs a fresh uncommitted replace)
            # and compiles twice
            lengths = np.zeros((self._table_rows,), np.int32)
            for i, s in enumerate(self._slots):
                if s is not None:
                    lengths[i] = s.n_cached
            # a copy: the step that reads it may still be running when the
            # next schedule writes the host's tables, and an upload may
            # alias or read its source after it returns
            tbl = jax.device_put(jnp.asarray(self._tables.copy()),
                                 self._sharding)
            lens = jax.device_put(jnp.asarray(lengths), self._sharding)
            self.cache = self.cache.replace(block_tables=tbl, lengths=lens)
            if self.dcache is not None:
                self.dcache = self.dcache.replace(block_tables=tbl,
                                                  lengths=lens)
            self._rng, sub = jax.random.split(self._rng)
        pad_rows = 0
        # what this call reads: at depth 0 the steps it enqueues, at depth
        # 1 the step the call before it enqueued
        landing: List[_InFlight] = []
        overlapped = self._inflight is not None
        if self.ecfg.disaggregated or self._cp > 1:
            cp = self._cp > 1
            p_width = (self._cp_width if cp
                       else self.ecfg.prefill_budget
                       or self.ecfg.token_budget)
            d_fn = self._step_fn if cp else self._decode_fn
            d_width = self.ecfg.token_budget if cp else self.ecfg.max_slots
            p_name = "engine/cp_prefill" if cp else "engine/prefill"
            # prefill first: TTFT, and new KV lands before decode reads
            for fn, worker_rows, width, name in (
                    (self._prefill_fn, prefill_rows, p_width, p_name),
                    (d_fn, decode_rows, d_width, "engine/decode")):
                if worker_rows:
                    with tracer.span(name, step=call):
                        flight = self._dispatch(fn, worker_rows, width,
                                                sub, name, call)
                        self._fetch(flight, name, step=call)
                    landing.insert(0, flight)   # lands decode rows first
                    pad_rows += width - len(worker_rows)
        else:
            with tracer.span("engine/packed", step=call):
                flight = None
                if rows:
                    flight = self._dispatch(
                        self._step_fn, rows, self.ecfg.token_budget, sub,
                        "engine/packed", call)
                    pad_rows = self.ecfg.token_budget - len(rows)
                if self._depth:
                    flight, self._inflight = self._inflight, flight
                if flight is not None:
                    self._fetch(flight, "engine/packed", step=call)
                    landing.append(flight)
        emit = alen = bstar = None
        if spec_live:
            # one speculation round: draft proposes k tokens per branch
            # into the lane clones, one target forward tree-verifies
            # every branch, and the rejected rows are already
            # un-published when the worker returns
            sw = self._spec_slots
            committed = np.zeros((sw,), np.int32)
            posv = np.full((sw,), PAD_POSITION, np.int32)
            for i, entry in enumerate(round_state):
                if entry is None:
                    continue
                req = entry[0]
                committed[i] = req.tokens[req.n_cached]
                posv[i] = req.n_cached
            cm, pv = jnp.asarray(committed), jnp.asarray(posv)
            with tracer.span("engine/spec_draft", step=call):
                drafted, self.dcache = self._spec_draft_fn(
                    self._draft_params, self.dcache, cm, pv)
            with tracer.span("engine/spec_verify", step=call):
                (self.cache, self.dcache, emit_d, alen_d,
                 bstar_d) = self._spec_verify_fn(
                     self.params, self.cache, self.dcache, cm, drafted,
                     pv)
            # the round's ONE host sync: three small arrays, fetched
            # together after both workers were dispatched
            emit, alen, bstar = (np.asarray(emit_d), np.asarray(alen_d),
                                 np.asarray(bstar_d))
        if self.prefix_cache is not None and prefill_rows:
            # the rows are enqueued: whoever maps these blocks reads them
            # in a later step, which the device runs after this one
            with tracer.span("engine/prefix_insert", step=call):
                for req in {id(r[0]): r[0] for r in prefill_rows}.values():
                    self._maybe_insert_prefix(req)

        now = self._now()
        if tracer.enabled:
            # per-request slice attribution: a request served this step
            # spent the whole step waiting on it (request-clock, not CPU
            # share), so each participant's phase accumulates the full
            # step wall time. One batched tracer call per step.
            step_us = (now - t_start) * 1e6
            with tracer.span("engine/slices", step=call):
                tracer.request_slices(
                    [(req.uid, "decode_step", step_us) for req in
                     {id(r[0]): r[0] for r in decode_rows}.values()]
                    + [(req.uid, "prefill_slice", step_us) for req in
                       {id(r[0]): r[0] for r in prefill_rows}.values()]
                    + [(x[0].uid, "decode_step", step_us)
                       for x in spec_live])
        with tracer.span("engine/retirement", step=call):
            self._note_enqueued(rows)
            for flight in landing:
                self._land(flight, now)
            if spec_live:
                self._land_spec_round(round_state, emit, alen, bstar,
                                      now)
        with tracer.span("engine/publish", step=call) as published:
            self.stats.steps += 1
            self.stats.step_latency_s.append(now - t_start)
            self.stats.last_step_t = now
            self.stats.occupancy.append(
                self.allocator.num_allocated / self.allocator.num_blocks)
            self.stats.shared_fraction.append(
                self.allocator.num_shared
                / max(1, self.allocator.num_allocated))
            self.stats.queue_depth = self.queue_depth()
            self._publish_obs(now - t_start, len(decode_rows),
                              len(prefill_rows), pad_rows,
                              "overlapped" if overlapped else "serial",
                              (call, entered.t0_us, published, cleared,
                               rolled, began))
        return len(rows) + len(spec_live)

    def _note_enqueued(self, rows) -> None:
        """What the host knows of a step once it is enqueued, before it
        has run: a decode row cached its token; the request of a row that
        samples has one more token in flight, in that row; and one whose
        ``max_new_tokens`` that token reaches leaves its slot now (its
        blocks can be handed on: the device runs steps in order, so the
        next step's writes come after this one's reads). Its result is
        published when the token lands."""
        if self._block is not None:
            return self._note_block_enqueued(rows)
        for i, (req, _, pos, produce) in enumerate(rows):
            if req.decoding and pos == req.n_cached:
                req.n_cached += 1
            if not produce:
                continue
            req.in_flight += 1
            req.take_row = i
            if len(req.generated) + req.in_flight >= req.max_new_tokens:
                self._release(req)

    def _land(self, flight: _InFlight, now: float) -> None:
        """Append the tokens of a step the host has read to their
        requests, and retire those they finish. A request that sampled
        ``eos_id`` a step ago was given one more row before the host knew:
        that row's token is dropped. A request preempted since the row was
        enqueued restarts from its prompt: the token is counted as
        generated, as the others it loses were, and dropped."""
        if self._block is not None:
            return self._land_block(flight, now)
        eos = self.ecfg.eos_id
        for i, ((req, _, _, produce), epoch) in enumerate(
                zip(flight.rows, flight.epochs)):
            if not produce or req.finished:
                continue
            self.stats.tokens_generated += 1
            if epoch != req.epoch:
                continue
            tok = int(flight.sampled[i])
            req.in_flight -= 1
            req.generated.append(tok)
            if req.first_token_time is None:
                req.first_token_time = now
                self.stats.ttft_s.append(now - req.arrival_time)
            if (len(req.generated) >= req.max_new_tokens
                    or (eos is not None and tok == eos)):
                self._retire(req, now)

    def _settle(self, step: Optional[int] = None) -> int:
        """Read and land the step in flight, if there is one; returns its
        rows. :meth:`step` ends here when it has nothing to enqueue (its
        spans then carry the call's ``step``), and whatever reads or moves
        request state between two steps (:meth:`evict`,
        :meth:`export_session`, :meth:`export_prefixes`, :meth:`drain`)
        starts here."""
        flight, self._inflight = self._inflight, None
        if flight is None:
            return 0
        tracer = get_tracer()
        attrs = {} if step is None else {"step": step}
        with tracer.span("engine/packed", **attrs):
            self._fetch(flight, "engine/packed", **attrs)
        now = self._now()
        with tracer.span("engine/retirement", **attrs):
            self._land(flight, now)
        with tracer.span("engine/publish", **attrs):
            self.stats.last_step_t = now
            self.stats.queue_depth = self.queue_depth()
            self._publish_obs(None, 0, 0, 0, None)
        return len(flight.rows)

    #: EngineStats scalar fields bridged into ``nxd_engine_stats`` each
    #: step. Derived percentiles (ttft_p50 etc.) stay in
    #: ``stats.report()`` — recomputing them per step would dominate the
    #: publish cost; latency quantiles come from the
    #: ``nxd_engine_step_seconds`` histogram instead.
    _OBS_SCALAR_FIELDS = (
        "steps", "completed", "rejected", "preempted", "resubmitted",
        "queue_depth", "tokens_generated", "cow_copies",
        "prefix_hit_tokens", "prefill_tokens", "migrated_in",
        "migrated_out", "migrated_tokens", "spec_rounds",
        "spec_accepted_tokens")

    def _publish_obs(self, step_latency_s: Optional[float],
                     decode_rows: int, prefill_rows: int, pad_rows: int,
                     kind: Optional[str],
                     call: Optional[Tuple] = None) -> None:
        """Bridge :class:`EngineStats` into registry gauges, count the
        step by ``kind`` (``overlapped``: enqueued while the step before
        it had not been read; ``serial``; ``None`` with no latency: the
        call enqueued nothing and only landed a step), count the
        step's rows by kind where they were packed (over the steps that
        ran a worker the three kinds sum to steps x worker width),
        increment the family's declared counters by what its steps
        counted since the last publish (``_counted``), poll the
        per-worker compile trackers, and account the call's wall by cause
        (:meth:`_account_call`; ``call`` is what ``step()`` knows of the
        call: its number, its entry, its open publish span, the blocks its
        hygiene cleared, the windows it rolled and its thread's reading at
        the entry). One bool check when obs is
        disabled; the no-host-callback invariant holds — everything here
        runs after the compiled workers returned. Child handles are
        cached against the registry's reset generation so the steady
        state is one attribute read + set per field."""
        reg = get_registry()
        if not reg.enabled:
            return
        compiled = False
        for tracker in self._compile_trackers.values():
            seen = tracker.compiles
            tracker.poll()
            compiled |= tracker.compiles > seen
        cache = self._obs_cache
        if (cache is None or cache.registry is not reg
                or cache.generation != reg.generation):
            stats_g = reg.gauge(
                "nxd_engine_stats",
                "EngineStats scalar counters bridged per step "
                "(monotonic fields included — they mirror the engine's "
                "own counters).",
                labels=("field",))
            step_h = reg.histogram("nxd_engine_step_seconds",
                                   "Serving step wall time.")
            # a registry reset() mid-run restarts the histogram empty
            # while EngineStats keeps its full sample lists — replaying
            # the retained window (all but this step's sample, observed
            # below) keeps the histogram quantiles and the stats-derived
            # percentiles telling the same story after the bump
            from ..obs.metrics import HISTOGRAM_RESERVOIR

            for v in self.stats.step_latency_s[-HISTOGRAM_RESERVOIR:-1]:
                step_h.observe(v)
            steps_c = reg.counter(
                "nxd_engine_steps_total",
                "Steps the engine enqueued by whether the step before was "
                "still unread then (overlapped: the device had its next "
                "program queued before the host fetched the last one's "
                "tokens) or not (serial: the first step, a step after the "
                "engine ran dry, and every step of a disaggregated, cp or "
                "speculating engine).",
                labels=("kind",))
            rows_c = reg.counter(
                "nxd_engine_rows_total",
                "Rows of the serving workers' fixed-width batches by what "
                "filled them: a decoding slot's token, a prefill chunk's "
                "token, or padding.",
                labels=("kind",))
            wall_c = reg.counter(
                "nxd_engine_step_wall_seconds_total",
                "Wall time of the step() calls that packed rows, entry to "
                "return, by where it went: steady (a call under 3 x the "
                "running median of the last 256 calls' walls, whole; of a "
                "slower call the median), and the slower call's time over "
                "the median by cause: host_pause (host/gc spans), device "
                "(the fetch's ready_us over its median), transfer (copy_us, "
                "engine/tables and /dispatch over theirs), compile, host "
                "(every other span over its median and what no span "
                "covers). The children sum to the calls' walls.",
                labels=("where",))
            cause_c = reg.counter(
                "nxd_engine_stall_cause_seconds_total",
                "The same walls as nxd_engine_step_wall_seconds_total, the "
                "slow calls' time over the median by why and not by where: "
                "process_stopped (the host/stopped spans inside the call, "
                "as far as the call's wall less its thread's CPU time "
                "covers them: nothing of the process ran), cpu_wait (of the "
                "rest, the stepping thread's run-queue delay beyond the "
                "stops: runnable, and no core), other (a wait the device or "
                "the runtime imposed, a collection, a compile, host work). "
                "steady is steady; the other children sum to the same "
                "seconds as the first counter's.",
                labels=("cause",))
            counted = {}
            for c in self._counters:
                metric = reg.counter(c.name, c.help,
                                     labels=("kind",) if c.kinds else ())
                counted[c.name] = tuple(
                    metric.labels(kind=k) for k in c.kinds) or (metric,)
            cache = self._obs_cache = types.SimpleNamespace(
                registry=reg, generation=reg.generation,
                fields={f: stats_g.labels(field=f)
                        for f in self._OBS_SCALAR_FIELDS},
                free=reg.gauge("nxd_engine_pool_free_blocks",
                               "Unallocated KV blocks."),
                step_seconds=step_h,
                rows=tuple(rows_c.labels(kind=k)
                           for k in ("decode", "prefill", "pad")),
                steps={k: steps_c.labels(kind=k)
                       for k in ("overlapped", "serial")},
                wall={k: wall_c.labels(where=k)
                      for k in ("steady",) + STALL_CAUSES},
                cause={k: cause_c.labels(cause=k)
                       for k in ("steady",) + STALL_WHYS},
                counted=counted)
        st = self.stats
        for f, child in cache.fields.items():
            child.set(float(getattr(st, f)))
        cache.free.set(self.pool_free_blocks())
        if kind is not None:
            cache.step_seconds.observe(step_latency_s)
            cache.steps[kind].inc(1)
        for child, n in zip(cache.rows,
                            (decode_rows, prefill_rows, pad_rows)):
            child.inc(n)
        for name, children in cache.counted.items():
            since = self._counted[name]
            for child, n in zip(children, since):
                child.inc(int(n))
            since[:] = 0
        if call is not None and get_tracer().enabled:
            self._account_call(cache, {
                "decode_rows": decode_rows, "prefill_rows": prefill_rows,
                "pad_rows": pad_rows, "kind": kind, "compiled": compiled},
                *call)

    def _account_call(self, counters, facts: Dict[str, Any], step: int,
                      entry_us: float, published, cleared: int,
                      rolled: int, began) -> None:
        """Add this call's wall (entry to now, microseconds before it
        returns) to ``nxd_engine_step_wall_seconds_total{where}`` and to
        ``nxd_engine_stall_cause_seconds_total{cause}``: all of it to
        ``steady`` unless it is over ``STALL_FACTOR`` x the running
        median, and then the median to ``steady`` and the rest by place
        and by cause (:meth:`_slow_call`). What the engine knows of the
        call goes onto its publish span, the stepping thread's CPU time
        over the call (``cpu_us``) with it: the call after it may wait for
        what this one enqueued."""
        ended = get_tracer().thread_reading() if began is not None else None
        if ended is not None:
            facts["cpu_us"] = round((ended[0] - began[0]) * 1e-3, 1)
        st = self._stall
        totals = (self._admit_counter, self.stats.completed,
                  self.stats.preempted, self.stats.cow_copies)
        if st is None:
            st = self._stall = types.SimpleNamespace(
                walls=deque(maxlen=STALL_WINDOW), median=None, calls=0,
                totals=(0, 0, 0, 0), before=(step, entry_us))
        admitted, retired, preempted, cow_copies = (
            now - was for now, was in zip(totals, st.totals))
        st.totals = totals
        facts.update(admitted=admitted, retired=retired, preempted=preempted,
                     cow_copies=cow_copies, cleared=cleared, rolled=rolled)
        for key, value in facts.items():
            published.set_attribute(key, value)
        wall = time.perf_counter_ns() / 1000.0 - entry_us
        st.walls.append(wall)
        st.calls += 1
        if st.calls == STALL_FIRST or st.calls % STALL_REFRESH == 0:
            st.median = statistics.median(st.walls)
        before, st.before = st.before, (step, entry_us)
        steady = wall
        if st.median is not None and wall > STALL_FACTOR * st.median:
            steady = st.median
            self._slow_call(counters, facts, step, entry_us, wall, steady,
                            before, began, ended)
        counters.wall["steady"].inc(steady * 1e-6)
        counters.cause["steady"].inc(steady * 1e-6)

    def _slow_call(self, counters, facts: Dict[str, Any], step: int,
                   entry_us: float, wall: float, median: float,
                   before: Tuple[int, float], began, ended) -> None:
        """Split a slow call's time over the median twice, and emit one
        ``slow_step`` event with both splits, this call's facts and those
        of the call before it (a long ``ready_us`` is the wait for the
        step *that* call enqueued). All times in microseconds until the
        event.

        **By place** (``where``): where the stepping thread sat, from the
        call's own spans against each span's median (the tracer's
        reservoirs, read here and nowhere else).

        **By cause** (``cause``), in this order: ``process_stopped`` is
        what the witness's ``host/stopped`` spans cover of the call
        (entry to now), capped by the excess and by the call's wall less
        what its thread ran, its CPU time or its own collections' spans,
        whichever is more (a stepping thread that burned CPU while the
        witness starved on the interpreter's lock ran); ``cpu_wait`` is,
        of what is left, the thread's run-queue delay over the call beyond
        those stops (a throttled process's threads wait on the run queue
        through the stop: counted once); ``other`` is the rest. A stop
        that ends as the call does is waited for, 40 ms at most
        (``tracer.stopped_since``), and not accounted a call late."""
        tracer = get_tracer()
        records = tracer.step_records(since_us=before[1])
        this = records.get(step, {"self_us": {}, "attrs": {}, "gc": []})
        p50 = {name: s["p50_us"] for name, s in tracer.stats().items()}
        own = dict(this["self_us"])
        raw = dict.fromkeys(STALL_CAUSES, 0.0)
        raw["host_pause"] = own.pop(GC_SPAN, 0.0)
        # what no span covers: the publish span, still open, is part of it
        raw["host"] = max(0.0, wall - sum(this["self_us"].values()))
        ready = copy = 0.0
        for name, us in own.items():
            if name.endswith("/fetch"):
                # the fetch is the ready wait but for the copies
                attrs = this["attrs"][name]
                waited = attrs.get("ready_us", us)
                copied = attrs.get("copy_us", 0.0)
                ready, copy = ready + waited, copy + copied
                raw["device"] += max(0.0, waited - p50.get(name, 0.0))
                raw["transfer"] += copied
            elif name == "engine/tables" or name.endswith("/dispatch"):
                raw["transfer"] += max(0.0, us - p50.get(name, 0.0))
            else:
                raw["host"] += max(0.0, us - p50.get(name, 0.0))
        excess, total = wall - median, sum(raw.values())
        if facts["compiled"]:
            split = {"compile": excess}
        elif total > 0.0:
            split = {k: excess * v / total for k, v in raw.items() if v}
        else:
            split = {"host": excess}
        for where, us in split.items():
            counters.wall[where].inc(us * 1e-6)

        now_us = entry_us + wall
        stops = tracer.stopped_since(entry_us)
        stopped = sum(max(0.0, min(ev["ts"] + ev["dur"], now_us)
                          - max(ev["ts"], entry_us)) for ev in stops)
        thread = obs_host.since(began, ended) if ended is not None else {}
        # what the thread is known to have run: its CPU time, or its own
        # collections where the host's CPU clock is the coarser (10 ms ticks
        # under a sandbox's kernel)
        ran = max(thread.get("cpu_us", 0.0), raw["host_pause"])
        why = {"process_stopped": max(0.0, min(stopped, excess, wall - ran))}
        left = excess - why["process_stopped"]
        why["cpu_wait"] = min(left, max(
            0.0, thread.get("runq_us", 0.0) - stopped))
        why["other"] = left - why["cpu_wait"]
        for cause, us in why.items():
            counters.cause[cause].inc(us * 1e-6)

        def ms(us):
            return None if us is None else round(us * 1e-3, 3)

        prev = records.get(before[0]) if before[0] != step else None
        emit_event(
            "slow_step", replica=self.name or "engine", step=step,
            steps=self.stats.steps, wall_ms=ms(wall), median_ms=ms(median),
            split_ms={k: ms(v) for k, v in split.items()},
            spans_ms={k: ms(v) for k, v in sorted(own.items())},
            ready_ms=ms(ready), copy_ms=ms(copy), call=facts,
            call_before=None if prev is None else dict(
                prev["attrs"].get("engine/publish", {}), step=before[0],
                spans_ms={k: ms(v)
                          for k, v in sorted(prev["self_us"].items())}),
            gc_generation=max((g for g, _, _ in this["gc"]), default=None),
            gc_ms=ms(raw["host_pause"]),
            cause_ms={k: ms(v) for k, v in why.items()},
            cpu_ms=ms(thread.get("cpu_us")), stopped_ms=ms(stopped),
            cpu_wait_ms=ms(thread.get("runq_us")),
            core=obs_host.current_core(),
            stops=[dict(attrs_of(ev), at_ms=ms(ev["ts"] - entry_us),
                        ms=ms(ev["dur"])) for ev in stops],
            **{k: v for k, v in thread.items() if not k.endswith("_us")})

    def _retire(self, req: _RequestState, now: float) -> None:
        if req.slot is not None:    # else it left its slot at the enqueue
            self._release(req)
        req.finished = True
        self.stats.completed += 1
        ttft = (req.first_token_time - req.arrival_time
                if req.first_token_time is not None else None)
        n_gen = len(req.generated)
        tpot = ((now - req.first_token_time) / (n_gen - 1)
                if req.first_token_time is not None and n_gen > 1
                else None)
        k = self._spec.speculation_length if self._spec else 0
        self.results[req.uid] = RequestResult(
            uid=req.uid, prompt_len=req.prompt_len,
            tokens=list(req.generated), status="completed",
            ttft_s=ttft, finish_s=now, tpot_s=tpot,
            accept_rate=(req.spec_accepted / (req.spec_rounds * k)
                         if req.spec_rounds and k else None))
        if self._standalone_obs:
            observe_request_metrics(
                "completed", replica=self.name or "engine",
                ttft_s=ttft,
                tpot_s=tpot,
                queue_s=(req.admit_time - req.arrival_time
                         if req.admit_time is not None else None),
                e2e_s=now - req.arrival_time)
            tracer = get_tracer()
            if tracer.enabled:
                tracer.request_end(req.uid, outcome="completed",
                                   replica=self.name or "engine",
                                   tokens=n_gen)


# -- nxdlint jaxpr-audit entry point ---------------------------------------

from ..analysis.audit_registry import BuiltEntry, register_entry_point


@register_entry_point(
    "engine-step",
    description="packed continuous-batching serving step (paged KV), "
                "same construction path as the engine tests",
    tags=("serve",),
)
def _audit_engine_step() -> BuiltEntry:
    """Builder for ``analysis --jaxpr``: the packed serving step on a
    tiny model. No donation expectation — the engine only donates the
    pool on a TPU backend — and no wire dtype; the audit's value
    here is the host-callback and collective-scope contracts."""
    from flax.core import meta

    from ..models.llama import LlamaForCausalLM, tiny_config
    from ..parallel import mesh as ps

    if ps.model_parallel_is_initialized():
        ps.destroy_model_parallel()
    ps.initialize_model_parallel()
    cfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32,
                      num_layers=2)
    params = meta.unbox(LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    ecfg = EngineConfig(block_size=4, num_blocks=16, max_slots=2,
                        max_blocks_per_seq=8, token_budget=8,
                        kv_dtype=jnp.float32)
    eng = ServingEngine(cfg, params, ecfg, aot_cache=None)
    return BuiltEntry(fn=eng._step_fn,
                      args=eng._example_args(ecfg.token_budget))
