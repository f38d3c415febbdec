"""Which device the process runs on, and where its compiled programs go.

The single home for the two questions every entry script and kernel
dispatcher asks: "is the default backend a TPU?" and "where does JAX's
persistent compilation cache live?".
"""

from __future__ import annotations

import os
from typing import Optional


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU (the Pallas kernels run
    compiled; everywhere else they run in interpret mode or not at all)."""
    import jax

    return jax.default_backend() == "tpu"


def memory_limit_bytes(devices) -> Optional[int]:
    """``memory_stats()["bytes_limit"]``, the bytes a device's allocator
    may hand out, of the first of ``devices`` that this process holds:
    on several hosts each asks a chip of its own, and chips of one kind
    answer alike. None where the backend reports none (the CPU) or no
    device is attached (a described topology, whose client holds none).
    An attached device that cannot answer raises: a limit that one host
    read and another did not would trace two programs."""
    devices = list(devices)
    held = devices[0].client.local_devices()
    for device in devices:
        if device in held:
            return (device.memory_stats() or {}).get("bytes_limit")
    return None


def place_compile_cache(checkout: str) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set in code. Otherwise the cache goes to the fixed path
    ``<checkout>/.jax_cache`` — the path is part of the cache key, so it is
    never a temporary name. Call before the first compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
