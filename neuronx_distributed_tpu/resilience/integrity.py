"""Silent-data-corruption (SDC) defense: jit-safe integrity fingerprints.

At production scale, silent data corruption — flaky cores, bad HBM rows,
lossy links — is a when-not-if event, and this stack is *more* exposed
than most: every gradient/activation collective rides an int8/fp8 wire
(``parallel/wire_codec.py``) and live KV-session migration ships raw
blocks between replicas (``inference/engine.py``). The watchdog only sees
the downstream *symptom* (a loss-spike z-score); this module detects
corruption at its source. Three layers:

* **On-device fingerprints** — :func:`fingerprint_array` folds the raw
  bits of an array (uint32 view) into a small int32 digest with pure
  ``jnp`` ops, so it traces under ``jit``/``shard_map`` and runs inside
  the compiled train step at a cadence ``integrity_every=K`` (see
  ``make_train_step``). One host readback per cadence boundary;
  ``compile_count()`` is unchanged because the cadence gate is a
  ``lax.cond`` on the step counter, not a Python branch.
  :func:`fingerprint_array_np` is the bit-exact host (numpy) mirror, used
  to verify KV-session tickets and checkpoint payloads without touching
  the device.
* **Cross-dp-replica consensus** — post-allreduce params are bit-identical
  across data-parallel replicas *by construction*, so an ``all_gather`` of
  per-replica fingerprint vectors (:func:`dp_consensus_fingerprints`)
  plus :func:`majority_vote` localizes a divergent replica/leaf without
  keeping any reference copy of the params.
* **Wire spot checks** — :func:`payload_fingerprint` digests an encoded
  ``wire_codec`` payload ``(q, scales)`` so sampled ring hops can compare
  a sender-side fingerprint against a receiver-side recompute (see
  ``wire_codec.spot_check_roundtrip``); 4 bytes of overhead per sampled
  hop.

:class:`IntegrityMonitor` wires detection into the training loop: at each
cadence boundary it compares the step-reported fingerprint against an
independent host-triggered recompute of the live params, emits an
``integrity_mismatch`` obs event on divergence, and composes with the
:class:`~neuronx_distributed_tpu.resilience.watchdog.Watchdog`'s rewind
discipline (``report_anomaly``) to restore the newest *content-verified*
checkpoint (manifests carry per-shard digests; see ``manifest.py``). The
chaos ``bitflip`` fault kind drives deterministic drills end to end
(``tests/test_integrity.py``).

See docs/resilience.md ("Silent data corruption").
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.events import emit_event
from ..obs.metrics import get_registry
from ..utils.logger import get_logger

logger = get_logger(__name__)

# Odd multiplicative constants (Knuth / splitmix-style). The fold is
# position-weighted so permutations don't cancel, and avalanched so a
# single flipped bit flips ~half the digest. Not cryptographic — SDC is
# random, not adversarial.
_C_WORD = 2654435761   # 0x9E3779B1
_C_POS = 2654435769    # 0x9E3779B9
_C_MIX1 = 2246822519   # 0x85EBCA77
_C_MIX2 = 3266489917   # 0xC2B2AE3D


class IntegrityError(RuntimeError):
    """An integrity fingerprint mismatch that no recovery policy absorbed
    (no watchdog to rewind through, or a corrupted KV-session ticket)."""


# ---------------------------------------------------------------------------
# device-side (jnp) fingerprints — trace-safe, usable inside jit/shard_map
# ---------------------------------------------------------------------------


def _as_words(x: jax.Array) -> jax.Array:
    """Flatten ``x`` to a uint32 bit view. Floats are bitcast through
    float32 (exact for bf16/fp16/fp32 — a flipped mantissa/exponent bit
    survives the widening); bools/ints wrap into uint32."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.floating):
        bits = jax.lax.bitcast_convert_type(
            x.astype(jnp.float32), jnp.uint32)
    else:
        bits = x.astype(jnp.uint32)
    return bits.reshape(-1)


def _fold(bits: jax.Array, blocks: int) -> jax.Array:
    """Position-weighted additive fold of a flat uint32 vector into
    ``blocks`` uint32 words, with a final avalanche."""
    n = bits.size
    pad = (-n) % blocks if blocks else 0
    if pad:
        bits = jnp.concatenate([bits, jnp.zeros((pad,), jnp.uint32)])
    bits = bits.reshape(blocks, -1)
    pos = jnp.arange(1, bits.shape[1] + 1, dtype=jnp.uint32)
    mixed = (bits * jnp.uint32(_C_WORD)) ^ (pos * jnp.uint32(_C_POS))
    # the reduction is ADD mod 2**32, not xor: integer add is exactly
    # associative/commutative (any partitioning gives the same words),
    # and partitioned add-reduce is XLA's first-class path on every
    # backend — xor reduce computations are rejected or mis-assembled
    # by the CPU SPMD partitioner inside sharded train steps
    h = jnp.sum(mixed, axis=1, dtype=jnp.uint32) ^ jnp.uint32(n)
    h = (h ^ (h >> 15)) * jnp.uint32(_C_MIX1)
    h = (h ^ (h >> 13)) * jnp.uint32(_C_MIX2)
    return h ^ (h >> 16)


def fingerprint_array(x: jax.Array, blocks: int = 1) -> jax.Array:
    """Blockwise int32 fingerprint of ``x``'s raw bits — pure ``jnp``, so
    it is trace-safe (use this, never ``hashlib``/host digests, inside
    jitted code; the nxdlint ``integrity`` rule enforces it). Returns an
    ``int32[blocks]`` vector; element ``b`` digests the ``b``-th
    contiguous slice of the flattened array, localizing corruption to a
    block. Empty arrays fingerprint to the avalanche of zero."""
    if blocks < 1:
        raise ValueError(f"blocks must be >= 1, got {blocks}")
    words = _as_words(x)
    if words.size == 0:
        words = jnp.zeros((blocks,), jnp.uint32)
    return jax.lax.bitcast_convert_type(_fold(words, blocks), jnp.int32)


def fingerprint_tree(tree: Any) -> jax.Array:
    """Per-leaf scalar fingerprints of a pytree, stacked into an
    ``int32[n_leaves]`` vector (leaf order = ``tree_leaves`` order). The
    fixed shape makes it a legal train-step metric at every step."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:  # nxdlint: disable=trace-safety  -- structure is static
        return jnp.zeros((0,), jnp.int32)
    return jnp.concatenate([fingerprint_array(leaf) for leaf in leaves])


def combine_fingerprints(fps: jax.Array) -> jax.Array:
    """Fold a vector of fingerprints into one scalar int32 (e.g. a whole
    param-tree digest, or a ``(q, scales)`` wire-payload pair)."""
    return fingerprint_array(jnp.asarray(fps))[0]


def payload_fingerprint(q: jax.Array,
                        scales: Optional[jax.Array] = None) -> jax.Array:
    """Scalar fingerprint of an encoded ``wire_codec`` payload — digests
    the quantized words and (when present) the per-block scales, so a
    flipped bit in either leg of the wire is visible. Trace-safe; this is
    what sampled ring hops ship alongside the payload (4 bytes)."""
    fp_q = fingerprint_array(q)
    if scales is None:
        return fp_q[0]
    return combine_fingerprints(
        jnp.concatenate([fp_q, fingerprint_array(scales)]))


# ---------------------------------------------------------------------------
# host-side (numpy) mirror — bit-exact parity with the jnp fold
# ---------------------------------------------------------------------------


def _as_words_np(x: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(x)
    if a.dtype == np.bool_:
        return a.astype(np.uint32).reshape(-1)
    # jnp.issubdtype (not np.) so ml_dtypes floats (bf16, fp8) route
    # through the float32 bitcast exactly like the device fold
    if jnp.issubdtype(a.dtype, jnp.floating):
        return a.astype(np.float32).view(np.uint32).reshape(-1)
    with np.errstate(over="ignore"):
        return a.astype(np.uint32).reshape(-1)


def fingerprint_array_np(x: np.ndarray, blocks: int = 1) -> np.ndarray:
    """Host mirror of :func:`fingerprint_array`: same fold, same
    constants, bit-identical output — so a fingerprint computed on-device
    inside the train step can be verified against host bytes (checkpoint
    payloads, KV-session tickets) without re-staging them."""
    if blocks < 1:
        raise ValueError(f"blocks must be >= 1, got {blocks}")
    words = _as_words_np(np.asarray(x))
    if words.size == 0:
        words = np.zeros((blocks,), np.uint32)
    n = words.size
    pad = (-n) % blocks
    if pad:
        words = np.concatenate([words, np.zeros((pad,), np.uint32)])
    words = words.reshape(blocks, -1)
    with np.errstate(over="ignore"):
        pos = np.arange(1, words.shape[1] + 1, dtype=np.uint32)
        mixed = (words * np.uint32(_C_WORD)) ^ (pos * np.uint32(_C_POS))
        # dtype pinned: np.sum would widen uint32 to uint64 and break
        # bit-parity with the device fold's mod-2**32 wraparound
        h = np.add.reduce(mixed, axis=1, dtype=np.uint32) ^ np.uint32(n)
        h = (h ^ (h >> np.uint32(15))) * np.uint32(_C_MIX1)
        h = (h ^ (h >> np.uint32(13))) * np.uint32(_C_MIX2)
        h = h ^ (h >> np.uint32(16))
    return h.view(np.int32)


def fingerprint_blocks_np(arr: np.ndarray, axis: int) -> List[int]:
    """Per-slice fingerprints of a host array along ``axis`` (e.g. the
    block axis of an extracted KV payload): one int per block, so a
    corrupted shipped block is localized, not just detected."""
    moved = np.moveaxis(np.asarray(arr), axis, 0)
    return [int(fingerprint_array_np(moved[i])[0])
            for i in range(moved.shape[0])]


def kv_payload_fingerprints(payload: Dict[str, np.ndarray],
                            block_axes: Dict[str, int]) -> Dict[str, List[int]]:
    """Fingerprint every tensor of an extracted KV payload per block.
    ``block_axes`` maps payload key -> block axis (``paging.extract_blocks``
    layouts differ: ``k``/``v`` carry blocks on axis 1, ``pos``/scales on
    axis 0)."""
    return {name: fingerprint_blocks_np(arr, block_axes[name])
            for name, arr in payload.items()}


# ---------------------------------------------------------------------------
# cross-dp-replica consensus
# ---------------------------------------------------------------------------


def dp_consensus_fingerprints(tree: Any, axis_name: str) -> jax.Array:
    """Inside ``shard_map``/``pmap`` over the dp axis: fingerprint the
    local replica's (replicated) params and all-gather the vectors along
    ``axis_name``. Returns ``int32[dp, n_leaves]`` — every replica holds
    the full matrix, so the majority vote needs no designated leader and
    no reference copy of the params."""
    fp = fingerprint_tree(tree)
    return jax.lax.all_gather(fp, axis_name)


def majority_vote(fp_matrix: np.ndarray) -> Tuple[np.ndarray,
                                                  Dict[int, List[int]]]:
    """Majority vote over an ``[replicas, n_leaves]`` fingerprint matrix.

    Returns ``(consensus[n_leaves], divergent)`` where ``divergent`` maps
    replica index -> leaf indices disagreeing with the majority. Because
    post-allreduce params are bit-identical across dp by construction, any
    nonempty ``divergent`` is evidence of corruption on that replica's
    slice (ties blame every holdout — with 2 replicas you get detection
    but not localization, which the docs call out)."""
    fps = np.asarray(fp_matrix)
    if fps.ndim != 2:
        raise ValueError(f"expected [replicas, n_leaves], got {fps.shape}")
    n_rep, n_leaves = fps.shape
    consensus = np.empty((n_leaves,), fps.dtype)
    divergent: Dict[int, List[int]] = {}
    for col in range(n_leaves):
        values, counts = np.unique(fps[:, col], return_counts=True)
        maj = values[int(np.argmax(counts))]
        consensus[col] = maj
        for rep in np.nonzero(fps[:, col] != maj)[0]:
            divergent.setdefault(int(rep), []).append(col)
    return consensus, divergent


# ---------------------------------------------------------------------------
# training-loop monitor
# ---------------------------------------------------------------------------


def _metrics():
    reg = get_registry()
    return (
        reg.counter("nxd_integrity_checks_total",
                    "Integrity fingerprint verifications performed"),
        reg.counter("nxd_integrity_mismatch_total",
                    "Integrity fingerprint mismatches detected",
                    labels=("scope",)),
    )


class IntegrityMonitor:
    """Trainer callback closing the detection loop at cadence boundaries.

    ``make_train_step(integrity_every=K)`` computes the params fingerprint
    *inside* the compiled step (metric ``integrity_fp``, populated on
    steps where ``step % K == 0``). At each boundary this callback
    re-fingerprints the live ``trainer.state.params`` with an independent
    jitted recompute and compares: the step-reported vector digests the
    params the device *wrote*, the recompute digests the params the next
    step will *read* — any corruption landing between the two (bad HBM,
    a flipped readback bit) surfaces as a mismatch within one cadence
    window. On mismatch it emits the ``integrity_mismatch`` obs event and
    delegates recovery to the watchdog's rewind discipline
    (``Watchdog.report_anomaly``), which restores the newest
    content-verified checkpoint; without a watchdog it raises
    :class:`IntegrityError` (fail-stop beats training on garbage).

    ``chaos`` hooks the deterministic drill: at each boundary the plan is
    consulted at ``("integrity", "params")`` and a ``bitflip`` directive
    flips the seeded bit in the largest param leaf *before* verification —
    modeling corruption at rest between device write and host read.
    Mid-window flips are the dp-consensus layer's job
    (:func:`dp_consensus_fingerprints`); see the failure matrix in
    docs/resilience.md.
    """

    needs_prev_state = False

    def __init__(self, every: int, watchdog: Any = None,
                 chaos: Any = None) -> None:
        if every < 1:
            raise ValueError(f"integrity cadence must be >= 1, got {every}")
        self.every = every
        self.watchdog = watchdog
        self.chaos = chaos
        self.checks = 0
        self.mismatches = 0
        self.flips_injected = 0
        self._fp_fn = None

    # -- Callback protocol -------------------------------------------------

    def on_train_start(self, trainer) -> None: ...

    def on_eval_end(self, trainer, metrics) -> None: ...

    def on_train_end(self, trainer) -> None: ...

    def on_step_end(self, trainer, metrics: Dict) -> None:
        step = trainer.host_step
        if step % self.every != 0:
            return
        if "integrity_fp" not in metrics:
            raise IntegrityError(
                "IntegrityMonitor needs the in-step fingerprint metric: "
                "build the step with make_train_step(..., "
                f"integrity_every={self.every})")
        if self.chaos is not None:
            kind, _lat, detail = self.chaos.consult_detail(
                "integrity", "params")
            if kind == "bitflip":
                self._flip_param_bit(trainer, int(detail.get("bit", 0)))
        reported = np.asarray(jax.device_get(metrics["integrity_fp"]))
        actual = self._host_fingerprint(trainer.state.params)
        self.checks += 1
        checks, mismatches = _metrics()
        checks.inc()
        if np.array_equal(reported, actual):
            return
        bad = [int(i) for i in np.nonzero(reported != actual)[0]]
        self.mismatches += 1
        mismatches.labels(scope="params").inc()
        emit_event("integrity_mismatch", scope="params", step=step,
                   leaves=bad, cadence=self.every)
        reason = (f"integrity fingerprint mismatch at step {step} "
                  f"(divergent leaves {bad})")
        if self.watchdog is not None:
            self.watchdog.report_anomaly(trainer, reason)
        else:
            raise IntegrityError(reason)

    # -- internals ---------------------------------------------------------

    def _host_fingerprint(self, params) -> np.ndarray:
        if self._fp_fn is None:
            self._fp_fn = jax.jit(fingerprint_tree)
        return np.asarray(jax.device_get(self._fp_fn(params)))

    def _flip_param_bit(self, trainer, bit: int) -> None:
        """Chaos drill injection: flip one (seeded) bit in the largest
        param leaf, host-side, and write it back — simulating an HBM/
        readback corruption between the step's device write and the next
        read. Deterministic given the plan seed."""
        leaves, treedef = jax.tree_util.tree_flatten(trainer.state.params)
        li = max(range(len(leaves)), key=lambda i: leaves[i].size)
        host = np.array(jax.device_get(leaves[li]))
        flat = host.reshape(-1).view(np.uint8)
        pos = (bit // 8) % flat.size
        flat[pos] ^= np.uint8(1 << (bit % 8))
        leaves[li] = jax.device_put(host, leaves[li].sharding)
        trainer.state = trainer.state.replace(
            params=jax.tree_util.tree_unflatten(treedef, leaves))
        self.flips_injected += 1
        logger.info("chaos: flipped bit %d of param leaf %d", bit, li)
