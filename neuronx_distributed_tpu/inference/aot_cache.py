"""AOT executable cache: serving replicas load compiled programs.

The reference's ``trace/`` stack exists so serving workers *load*
serialized executables instead of compiling; this is the native JAX
analogue. An elastic fleet births and kills replicas constantly — paying
a full trace+compile per spin-up (probation revival, autoscale-up, a
fresh serving process) turns every scale event into seconds of dead
time. :class:`AotExecutableCache` keeps compiled executables behind a
content key so the *first* replica per program compiles and everyone
after it — including a revived replica in the same process, and a fresh
process pointed at the same ``cache_dir`` — loads.

Two layers:

* **memory** — loaded ``jax.stages.Compiled`` objects keyed by the hex
  digest; replicas in one process (the router's fleet) share executables
  outright.
* **disk** (optional ``cache_dir``) — ``jax.experimental
  .serialize_executable`` payloads, one file per key, written to a temp
  file and published with ``os.replace`` so concurrent writers never
  tear an entry (last writer wins, readers see old-or-new, never half).

The key folds in the runtime environment (jax + jaxlib version, backend,
device count, mesh shape) plus caller-supplied program identity parts,
so version skew and topology changes are *misses*, not crashes. Every
failure mode on the read path — unreadable file, truncated pickle,
environment-header mismatch, a runtime that refuses to deserialize —
degrades to "evict the entry, emit a warn event, return None" and the
caller compiles normally. The cache can make a cold start slower by at
most one failed read; it can never take serving down.
"""

from __future__ import annotations

import hashlib
import inspect
import json
import logging
import os
import pickle
import tempfile
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax

from ..obs.events import emit_event

logger = logging.getLogger(__name__)

#: disk entry layout: magic line, env-header JSON line, pickled
#: (payload, in_tree, out_tree) from ``serialize_executable.serialize``.
_MAGIC = b"NXDAOT1\n"
_SUFFIX = ".aotx"


def runtime_environment() -> Dict[str, str]:
    """Everything that invalidates a serialized executable: jax/jaxlib
    (compiler) versions, backend platform, device count, and the active
    mesh shape. Folded into every key, so an upgrade or a topology
    change produces a clean miss instead of a deserialization crash."""
    import jaxlib

    from ..parallel import mesh as ps

    env = {
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib, "__version__", "unknown"),
        "backend": jax.default_backend(),
        "devices": str(jax.device_count()),
    }
    if ps.model_parallel_is_initialized():
        mesh = ps.get_mesh()
        env["mesh"] = ",".join(
            f"{a}={s}" for a, s in zip(mesh.axis_names, mesh.devices.shape))
    else:
        env["mesh"] = "none"
    return env


def source_fingerprint(*fns: Any) -> str:
    """sha256 over the source text of ``fns`` — a trace-free proxy for
    "the program changed". Engine warm-start keys hash the model forward
    and the sampler through this instead of tracing (tracing to get a
    program hash would spend exactly the time the cache exists to save);
    objects without retrievable source fall back to ``repr``."""
    h = hashlib.sha256()
    for fn in fns:
        try:
            h.update(inspect.getsource(fn).encode())
        except (OSError, TypeError):
            h.update(repr(fn).encode())
    return h.hexdigest()


class AotWorker:
    """A serving worker backed by exactly one AOT executable.

    Quacks enough like a jitted function for the engine's bookkeeping:
    ``_cache_size()`` reports 1 (there is exactly one program behind it,
    whether it was compiled here or loaded), so ``compile_count()`` and
    the obs :class:`~..obs.accounting.CompileTracker` keep working
    unchanged. ``from_cache`` records whether spin-up skipped the
    compile."""

    def __init__(self, compiled: Any, from_cache: bool):
        self.compiled = compiled
        self.from_cache = from_cache

    def __call__(self, *args: Any) -> Any:
        return self.compiled(*args)

    def _cache_size(self) -> int:
        return 1


class AotExecutableCache:
    """Memory + optional-disk cache of compiled executables. See module
    docstring; all read-path failures degrade to a miss (evict + warn
    event), never an exception."""

    def __init__(self, cache_dir: Optional[str] = None, *,
                 env: Optional[Mapping[str, str]] = None):
        self.cache_dir = cache_dir
        # injectable for version-skew tests; None = live environment
        self._env_override = dict(env) if env is not None else None
        self._mem: Dict[str, Any] = {}
        self.hits = 0
        self.misses = 0
        self.puts = 0
        self.evictions = 0
        self.serialize_skips = 0
        if cache_dir:
            os.makedirs(cache_dir, exist_ok=True)

    # -- keys -------------------------------------------------------------

    def environment(self) -> Dict[str, str]:
        return (dict(self._env_override) if self._env_override is not None
                else runtime_environment())

    def key_for(self, *parts: Any) -> str:
        """Content key: the runtime environment plus caller parts —
        ``bytes`` parts (e.g. an exported MLIR module) hash raw, anything
        else through ``repr``."""
        h = hashlib.sha256()
        for k, v in sorted(self.environment().items()):
            h.update(f"{k}={v}\n".encode())
        for part in parts:
            h.update(b"\x00")
            h.update(part if isinstance(part, bytes) else repr(part).encode())
        return h.hexdigest()

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "puts": self.puts, "evictions": self.evictions,
                "serialize_skips": self.serialize_skips,
                "mem_entries": len(self._mem)}

    # -- read path --------------------------------------------------------

    def _path(self, key: str) -> str:
        return os.path.join(self.cache_dir, key + _SUFFIX)

    def _evict(self, key: str, why: str) -> None:
        self.evictions += 1
        try:
            os.remove(self._path(key))
        except OSError:
            pass
        emit_event("aot_cache_evicted", key=key[:16], error=why)

    def get(self, key: str) -> Optional[Any]:
        """Loaded executable for ``key``, or None. A disk entry that
        cannot be read/verified/deserialized is evicted with a warn
        event and reported as a miss — the caller compiles normally."""
        hit = self._mem.get(key)
        if hit is not None:
            self.hits += 1
            return hit
        if not self.cache_dir or not os.path.exists(self._path(key)):
            self.misses += 1
            return None
        try:
            with open(self._path(key), "rb") as fh:
                blob = fh.read()
            if not blob.startswith(_MAGIC):
                raise ValueError("bad magic (truncated or foreign file)")
            header_end = blob.index(b"\n", len(_MAGIC))
            header = json.loads(blob[len(_MAGIC):header_end])
            if header != self.environment():
                raise ValueError(
                    f"environment skew: entry built under {header}")
            payload, in_tree, out_tree, device_ids = pickle.loads(
                blob[header_end + 1:])
            from jax.experimental import serialize_executable

            # onto the devices it was compiled for: the default is every
            # device of the backend, which a program of fewer cannot run on
            by_id = {d.id: d for d in jax.devices()}
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except Exception as e:  # any read failure degrades to a miss
            self._evict(key, f"{type(e).__name__}: {e}")
            self.misses += 1
            return None
        self._mem[key] = compiled
        self.hits += 1
        return compiled

    # -- write path -------------------------------------------------------

    def put(self, key: str, compiled: Any) -> None:
        """Publish ``compiled`` under ``key``. Disk write is
        temp-file + atomic rename; a runtime that refuses to serialize
        (no AOT support) skips the disk layer with a warn event — the
        memory layer still serves this process."""
        self._mem[key] = compiled
        self.puts += 1
        if not self.cache_dir:
            return
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            blob = (_MAGIC
                    + json.dumps(self.environment(),
                                 sort_keys=True).encode() + b"\n"
                    + pickle.dumps((payload, in_tree, out_tree,
                                    device_ids)))
        except Exception as e:
            self.serialize_skips += 1
            emit_event("aot_cache_serialize_skipped", key=key[:16],
                       error=f"{type(e).__name__}: {e}")
            return
        fd, tmp = tempfile.mkstemp(dir=self.cache_dir, prefix=key[:16],
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as fh:
                fh.write(blob)
            os.replace(tmp, self._path(key))
        except OSError as e:  # disk full etc: memory layer still serves
            logger.warning("aot cache write failed for %s: %s", key[:16], e)
            try:
                os.remove(tmp)
            except OSError:
                pass

    # -- the one compile site ---------------------------------------------

    def compile_or_load(self, key: str, jitted: Callable[..., Any],
                        example_args: Tuple[Any, ...]
                        ) -> Tuple[Any, bool]:
        """``(executable, loaded_from_cache)`` for ``key`` — the single
        place serving code AOT-compiles (nxdlint's elasticity rule flags
        ``.lower().compile()`` chains elsewhere in ``inference/``). A
        miss lowers ``jitted`` on ``example_args``, compiles, and
        publishes the result for the next replica."""
        got = self.get(key)
        if got is not None:
            return got, True
        compiled = jitted.lower(*example_args).compile()
        self.put(key, compiled)
        return compiled, False
