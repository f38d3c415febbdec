"""nxdlint tier 3: jaxpr-level program auditor (``--jaxpr``).

Abstract-traces registered entry points (:mod:`.audit_registry`) with
``jax.make_jaxpr`` on the CPU backend — tracing evaluates shapes and
dtypes only, the entry function itself is never executed — then walks
the ClosedJaxpr for contracts the syntactic tiers cannot see:

* ``jaxpr-host-callback`` — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` (``jax.debug.print``) equations reachable from
  compiled code: host round-trips that stall the device every step and
  violate the no-callbacks serving invariant.
* ``jaxpr-collective-scope`` — collective equations (``psum``,
  ``ppermute``, ``all_gather``, ``all_to_all``, ...) outside any
  ``shard_map`` scope: axis semantics smuggled in via ``vmap(...,
  axis_name=...)`` or stale manual-collective code paths that GSPMD will
  not partition the way the mesh intends.
* ``jaxpr-undonated-buffer`` — entry points tagged
  ``expects_donation`` (train steps) whose top-level ``pjit`` donates
  none of its large input buffers: optimizer state is double-buffered
  and HBM headroom silently halves.
* ``jaxpr-wire-precision`` — ring hops (``ppermute``/``all_to_all``)
  shipping >= 4-byte float payloads in an entry registered with a wire
  codec (``wire_dtype=``): the ring moves 4x the bytes the codec
  promises.

Each violation maps to a stable rule ID (above) and is reported at the
entry point's registration site, so baselines and SARIF work the same
as for the syntactic tiers.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

from .audit_registry import (EntryPoint, all_entry_points,
                             load_default_entry_points)
from .core import Finding

#: stable rule IDs -> short description (merged into ``--list-rules``,
#: ``--explain`` and the SARIF rule catalog)
RULES: Dict[str, str] = {
    "jaxpr-host-callback":
        "pure_callback/io_callback/debug_callback reachable from compiled "
        "code — a host round-trip on every step; compute on-device or "
        "record host-side around the call",
    "jaxpr-collective-scope":
        "collective equation outside any shard_map scope — axis semantics "
        "via vmap(axis_name=...) or manual collectives that GSPMD will "
        "not partition as the mesh intends; wrap the region in "
        "parallel.mesh.shard_map",
    "jaxpr-undonated-buffer":
        "train-step entry whose top-level pjit donates none of its large "
        "input buffers — state is double-buffered and HBM headroom "
        "halves; pass donate_argnums for the state argument",
    "jaxpr-wire-precision":
        "full-precision ring hop (ppermute/all_to_all on >=4-byte "
        "floats) in an entry registered with a wire codec — ships 4x "
        "the bytes the codec promises; route the hop through the wire "
        "quantizer",
    "jaxpr-audit-error":
        "the entry point's builder or abstract trace failed — the "
        "contract cannot be audited until the build is fixed",
}

_CALLBACK_PRIMS = frozenset(
    {"pure_callback", "io_callback", "debug_callback"})
_COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
    "pmean",
})
_RING_PRIMS = frozenset({"ppermute", "all_to_all"})
#: primitives that establish a legitimate manual-collective scope
_SCOPE_PRIMS = frozenset({"shard_map", "xla_pmap", "pmap"})


def ensure_cpu_backend(n_devices: int = 8) -> None:
    """Pin the audit to the host backend with a virtual multi-device
    mesh. Effective as long as no backend initialised yet in this
    process (importing jax alone is fine); afterwards the caller's
    backend stands."""
    from ..utils.cpu_mesh import force_cpu_platform
    force_cpu_platform(n_devices)


def _entry_location(ep: EntryPoint) -> Tuple[str, int]:
    path, _, line = ep.source.rpartition(":")
    try:
        return (path or ep.source), int(line)
    except ValueError:
        return ep.source, 1


def _subjaxprs(params: Dict[str, Any]) -> Iterator[Any]:
    """Inner jaxprs of an equation: pjit/shard_map bodies, scan/while
    bodies, cond branches — found structurally in the eqn params."""
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr"):     # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):    # raw Jaxpr
                yield x


def _iter_eqns(jaxpr: Any,
               in_scope: bool = False) -> Iterator[Tuple[Any, bool]]:
    for eqn in jaxpr.eqns:
        yield eqn, in_scope
        inner_scope = in_scope or eqn.primitive.name in _SCOPE_PRIMS
        for sub in _subjaxprs(eqn.params):
            yield from _iter_eqns(sub, inner_scope)


def _aval_bytes(aval: Any) -> int:
    try:
        return int(aval.size) * int(aval.dtype.itemsize)
    except (AttributeError, TypeError):
        return 0


def _aval_str(aval: Any) -> str:
    try:
        return f"{aval.dtype.name}[{','.join(str(d) for d in aval.shape)}]"
    except AttributeError:
        return str(aval)


def _is_wide_float(aval: Any) -> bool:
    try:
        import numpy as np
        return (np.issubdtype(aval.dtype, np.floating)
                and aval.dtype.itemsize >= 4)
    except (AttributeError, TypeError):
        return False


def audit_entry_point(ep: EntryPoint) -> List[Finding]:
    """Build, abstract-trace and audit one entry point."""
    import jax

    path, line = _entry_location(ep)
    try:
        built = ep.build()
        closed = jax.make_jaxpr(built.fn)(*built.args)
    except Exception as e:  # surfaced as a finding, not a crash
        return [Finding(path, line, 0, "jaxpr-audit-error",
                        f"entry point '{ep.name}': build/trace failed: "
                        f"{type(e).__name__}: {e}")]

    findings: List[Finding] = []

    def flag(rule: str, message: str) -> None:
        findings.append(Finding(path, line, 0, rule,
                                f"entry point '{ep.name}': {message}"))

    top_pjit = [eqn for eqn in closed.jaxpr.eqns
                if eqn.primitive.name == "jit"]

    for eqn, in_scope in _iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMS:
            flag("jaxpr-host-callback",
                 f"{name} reachable from compiled code — every step "
                 "round-trips to the host; compute on-device or move the "
                 "host work outside the compiled call")
        elif name in _COLLECTIVE_PRIMS and not in_scope:
            opnd = _aval_str(eqn.invars[0].aval) if eqn.invars else "?"
            flag("jaxpr-collective-scope",
                 f"collective '{name}' on {opnd} outside any shard_map "
                 "scope — wrap the region in parallel.mesh.shard_map so "
                 "the axis semantics match the mesh instead of being "
                 "smuggled in via vmap(axis_name=...)")
        if name in _RING_PRIMS and ep.wire_dtype is not None:
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                if aval is None or not _is_wide_float(aval):
                    continue
                try:
                    elems = int(aval.size)
                except (AttributeError, TypeError):
                    continue
                if elems >= ep.wire_min_elems:
                    flag("jaxpr-wire-precision",
                         f"ring hop '{name}' ships {_aval_str(aval)} at "
                         f"full precision while the entry is registered "
                         f"with wire_dtype='{ep.wire_dtype}' — quantize "
                         "the hop through the wire codec")

    if ep.expects_donation:
        if top_pjit:
            for eqn in top_pjit:
                donated = tuple(eqn.params.get("donated_invars", ()))
                large = [i for i, v in enumerate(eqn.invars)
                         if _aval_bytes(getattr(v, "aval", None))
                         >= ep.donation_min_bytes]
                if large and not any(donated[i] for i in large
                                     if i < len(donated)):
                    biggest = max(
                        large,
                        key=lambda i: _aval_bytes(eqn.invars[i].aval))
                    flag("jaxpr-undonated-buffer",
                         f"no large input buffer is donated (largest: "
                         f"{_aval_str(eqn.invars[biggest].aval)}) — the "
                         "step double-buffers its state; pass "
                         "donate_argnums for the state argument")
        elif not built.donate_argnums:
            large_avals = [v.aval for v in closed.jaxpr.invars
                           if _aval_bytes(v.aval) >= ep.donation_min_bytes]
            if large_avals:
                flag("jaxpr-undonated-buffer",
                     f"no large input buffer is donated (largest: "
                     f"{_aval_str(max(large_avals, key=_aval_bytes))}) — "
                     "the step double-buffers its state; pass "
                     "donate_argnums for the state argument")
    return findings


def audit_entry_points(names: Optional[Iterable[str]] = None,
                       include_defaults: bool = True) -> List[Finding]:
    """Audit the selected (default: all registered) entry points."""
    entries = (load_default_entry_points() if include_defaults
               else all_entry_points())
    if names is not None:
        names = list(names)
        unknown = [n for n in names if n not in entries]
        if unknown:
            raise ValueError(
                f"unknown entry point(s): {unknown}; "
                f"known: {sorted(entries)}")
        entries = {n: entries[n] for n in names}
    findings: List[Finding] = []
    for name in sorted(entries):
        findings.extend(audit_entry_point(entries[name]))
    return findings
