"""Pin JAX to the CPU backend with several virtual devices.

The tests, ``__graft_entry__.dryrun_multichip`` and the jaxpr auditor need
eight (or ``n``) devices to build real meshes on a machine that has none.
``XLA_FLAGS`` and the platform are both read when the backend initialises,
so :func:`force_cpu_platform` must run before the first backend use in the
process.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_cpu_platform(n_devices: int = 8) -> None:
    """Pin jax to the CPU backend with >= ``n_devices`` virtual devices.

    Must be called before the first backend use in the process.  If an
    ``xla_force_host_platform_device_count`` flag is already present with a
    smaller count, it is raised to ``n_devices``; a larger count is kept.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(_COUNT_FLAG + r"=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" {_COUNT_FLAG}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"{_COUNT_FLAG}={n_devices}")

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # backend already initialised; caller's device check decides
