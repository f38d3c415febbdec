"""Resilience subsystem: fault injection, preemption-safe checkpointing,
training watchdog, and verified resume.

The reference NxD stack treats durability as a first-class concern (async
commit protocol with done-markers, tenacity-style storage retries,
``finalize_checkpoint`` atexit flush). This package makes those guarantees
*provable* and *actionable*:

* :mod:`chaos` — :class:`FaultPlan` / :class:`ChaosCheckpointStorage`:
  deterministic, seed-driven fault injection over any
  ``BaseCheckpointStorage`` so the retry/backoff and commit-protocol
  invariants are testable.
* :mod:`preemption` — :class:`PreemptionGuard`: SIGTERM/SIGINT turns into a
  synchronous emergency checkpoint at the next step boundary, then a
  resumable exit (:data:`EXIT_PREEMPTED`), with a grace deadline.
* :mod:`watchdog` — :class:`Watchdog`: non-finite loss/grad detection with
  ``halt`` / ``skip_step`` / ``rewind`` policies, loss-spike z-score
  detection, and a host-side stall timer for hung collectives or stalled
  data loaders.
* :mod:`manifest` — per-tag save manifests (file list + sizes +
  per-shard content digests + metadata checksum) behind verified resume:
  ``load_checkpoint`` falls back to the newest *prior* complete tag on
  corruption.
* :mod:`integrity` — silent-data-corruption defense: jit-safe on-device
  fingerprints at a train-step cadence, cross-dp-replica consensus with
  majority vote, wire-payload spot checks, and the
  :class:`IntegrityMonitor` callback composing detection with the
  watchdog's rewind (driven by the chaos ``bitflip`` fault kind).

See ``docs/resilience.md``.
"""

from .chaos import (ChaosCheckpointStorage, FaultPlan, FaultRule,
                    InjectedFault, ReplicaCrashed)
from .integrity import (IntegrityError, IntegrityMonitor,
                        dp_consensus_fingerprints, fingerprint_array,
                        fingerprint_array_np, fingerprint_tree,
                        kv_payload_fingerprints, majority_vote,
                        payload_fingerprint)
from .manifest import (MANIFEST_FILE, build_manifest, verify_manifest)
from .preemption import (EXIT_PREEMPTED, PreemptionGuard, TrainingPreempted)
from .watchdog import SpikeDetector, StallTimer, Watchdog, WatchdogHalt

__all__ = [
    "IntegrityError",
    "IntegrityMonitor",
    "dp_consensus_fingerprints",
    "fingerprint_array",
    "fingerprint_array_np",
    "fingerprint_tree",
    "kv_payload_fingerprints",
    "majority_vote",
    "payload_fingerprint",
    "ChaosCheckpointStorage",
    "FaultPlan",
    "FaultRule",
    "InjectedFault",
    "ReplicaCrashed",
    "MANIFEST_FILE",
    "build_manifest",
    "verify_manifest",
    "EXIT_PREEMPTED",
    "PreemptionGuard",
    "TrainingPreempted",
    "SpikeDetector",
    "StallTimer",
    "Watchdog",
    "WatchdogHalt",
]
