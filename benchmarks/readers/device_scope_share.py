"""Device time of the operations the program traced under one of
``scopes`` (or under a child of one: ``attn`` takes in ``attn.kernel``)
over the first device's busy time in the traced window, in percent.

A scope is a ``jax.named_scope`` the program opens from its own fixed
list (``neuronx_distributed_tpu/obs/device_scopes.py``); the profiler
keeps an operation's path of scopes as the ``tf_op`` of its event's
metadata, and ``scope_of`` reads the innermost marker of the path:
nothing here knows a shape. An instruction the compiler made itself (a
layout copy, a fusion it cloned) has no ``tf_op``: it takes the scope of
the instructions inside its fused computation, else of its nearest
operand or user, out of the program's ``HloProto`` that the same file
holds (``tracereduce/scopes.py``). ``"(unscoped)"`` names the operations
no marker is found for. Containers (``while``, ``conditional``, ``call``)
span their bodies' operations and are left out, as everywhere; they are
known by their opcode (``lax.cond``'s instruction is named ``cond``).

The runner hands a reader no path: the trace is the newest
``*.xplane.pb`` under ``benchmarks/out/*/trace/``, and it is refused
(``None``, and a line that says why) unless every operation of
``obs.trace`` is found in its metadata by its text, and unless some
operation of it carries a marker at all (an executable compiled before
the markers were there, and read back from a compile cache, carries
none). A program without ``obs.device_scopes`` gives ``None`` too."""

import os

from harness import HERE, say
from tracereduce import scopes, xplane

_read = {}                     # (path, mtime, device) -> the events' scopes


def _event_scopes(path: str, device: int, scope_of):
    key = (path, os.path.getmtime(path), device, scope_of)
    if key not in _read:
        _read.clear()
        _read[key] = scopes.event_scopes(path, device, scope_of)
    return _read[key]


def read(args: dict, obs, out_dir: str = os.path.join(HERE, "out")):
    if obs.trace is None or not obs.trace.devices:
        return None
    try:
        from neuronx_distributed_tpu.obs.device_scopes import (scope_of,
                                                               within)
    except ImportError:
        return None                      # a program that marks no scope
    path = scopes.newest_trace(out_dir)
    device = min(obs.trace.devices)
    scope = _event_scopes(path, device, scope_of) if path else None
    ops = obs.trace.devices[device].ops
    if not scope:
        say("scopes", refused=f"no device plane {device} in {path}")
        return None
    missing = {e.name for e in ops if e.name not in scope}
    if missing:
        say("scopes", refused=f"{len(missing)} operation(s) of the trace "
            f"are not in the metadata of {path}: another run's file?",
            first=xplane.stable_name(sorted(missing)[0]))
        return None
    if not any(route == "tf_op" for _, route in scope.values()):
        say("scopes", refused="no operation of the trace carries a device "
            "scope: the executable was compiled before the markers were "
            "there (a compile cache keeps an executable's metadata)")
        return None
    lo, hi = obs.reduction.window
    hit = [(e.start, e.end) for e in ops
           if not scopes.is_container(e.name)
           and within(scope[e.name][0], args["scopes"])]
    busy = obs.reduction.busy_by_device[device]
    if busy <= 0:
        return None
    return 100.0 * xplane.total(xplane.union(xplane.clip(hit, lo, hi))) / busy
