"""Rank-aware logging.

Analogue of the reference's ``utils/logger.py`` (``get_logger:52``,
env-controlled level via ``NXD_LOG_LEVEL``) and the ``rmsg`` rank-prefix
helper (``parallel_state.py:1648-1682``). In single-controller JAX there is
one process per host (not per chip); "rank 0" gating maps to
``jax.process_index() == 0``.
"""

from __future__ import annotations

import logging
import os

_LOGGERS = {}


class _Rank0Filter(logging.Filter):
    """Drop sub-WARNING records on non-zero processes.

    The process-index check runs lazily at emit time: ``get_logger`` is
    called at module import all over the package, and ``jax.process_index``
    initializes the XLA backend — which would freeze device flags (e.g.
    ``--xla_force_host_platform_device_count``) before callers get a chance
    to set them. An uninitialized backend means we can't know the rank yet,
    so the record passes through rather than forcing initialization.
    """

    def filter(self, record: logging.LogRecord) -> bool:
        if record.levelno >= logging.WARNING:
            return True
        try:
            from jax._src import xla_bridge

            if not xla_bridge._backends:
                return True
            import jax

            return jax.process_index() == 0
        except Exception:
            return True


_RANK0_FILTER = _Rank0Filter()


_WARNED_BAD_LEVELS = set()


def get_log_level() -> int:
    raw = os.environ.get("NXD_LOG_LEVEL", "INFO")
    level = getattr(logging, raw.upper(), None)
    if isinstance(level, int) and not isinstance(level, bool):
        return level
    # Bad value: fall back to INFO, but say so (once per offending value)
    # instead of silently swallowing the typo forever.
    if raw not in _WARNED_BAD_LEVELS:
        _WARNED_BAD_LEVELS.add(raw)
        logging.getLogger("neuronx_distributed_tpu").warning(
            "NXD_LOG_LEVEL=%r is not a valid logging level; "
            "falling back to INFO", raw)
    return logging.INFO


def get_logger(name: str = "neuronx_distributed_tpu",
               rank0_only: bool = True) -> logging.Logger:
    """Reference ``get_logger:52``: on non-zero processes, rank0_only
    loggers drop everything below WARNING."""
    key = (name, rank0_only)
    if key in _LOGGERS:
        logger = _LOGGERS[key]
        # Re-resolve the level on every call: NXD_LOG_LEVEL may have
        # changed since the logger was first built (tests, notebooks,
        # long-lived drivers) and caching the first value forever made
        # the env knob a one-shot.
        level = get_log_level()
        if logger.level != level:
            logger.setLevel(level)
        return logger
    logger = logging.getLogger(name)
    logger.setLevel(get_log_level())
    if not logger.handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter(
            "%(asctime)s [%(levelname)s] %(name)s: %(message)s"))
        logger.addHandler(h)
        logger.propagate = False
    if rank0_only and _RANK0_FILTER not in logger.filters:
        logger.addFilter(_RANK0_FILTER)
    _LOGGERS[key] = logger
    return logger


def log_event(logger: logging.Logger, event: str, **fields) -> None:
    """One-line machine-parseable event record: ``NXD_EVENT {json}``.

    The resilience subsystem (preemption, watchdog, chaos drills) emits its
    operational events through this so launch tooling can
    grep/parse them without scraping free-form log text. WARNING level:
    rank0_only loggers on non-zero processes drop below WARNING, and a
    resilience event from *any* rank must stay visible.

    Routed through the ``obs`` event channel: the same call also bumps
    ``nxd_events_total{event=...}`` and fans out to subscribers, so the
    NXD_EVENT log lines and the metrics registry share one source of
    truth. The log-line format is unchanged.
    """
    from ..obs.events import emit_event  # lazy: obs imports this module

    emit_event(event, logger=logger, **fields)


def rmsg(msg: str) -> str:
    """Prefix a message with the mesh position (reference ``rmsg``:
    tp/pp/dp rank prefix). Host-side: reports process index and mesh shape;
    per-shard ranks only exist inside shard_map."""
    try:
        import jax

        from ..parallel import mesh as ps

        if ps.model_parallel_is_initialized():
            shape = dict(ps.get_mesh().shape)
            return f"[proc {jax.process_index()} mesh {shape}] {msg}"
        return f"[proc {jax.process_index()}] {msg}"
    except Exception:
        return msg
