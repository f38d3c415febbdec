"""nxdlint tier 4: mesh-protocol verifier (``--mesh-protocol``).

Abstract-traces every registered entry point (:mod:`.audit_registry`) on
the virtual CPU mesh — like tier 3, tracing evaluates shapes/dtypes
only, the entry function is never executed — and verifies the
*rank-coordinated protocol* the jaxpr encodes, the class of contract
whose violation hangs real multi-host hardware instead of raising:

* ``jaxpr-collective-divergence`` — the per-axis collective schedule
  (ordered ``psum``/``ppermute``/``all_gather``/... with payload shape,
  dtype and axis) is extracted by walking nested pjit/shard_map/scan/
  while bodies and every ``cond`` branch. A ``cond`` whose branches
  issue *different* collective sequences is a static deadlock hazard:
  under SPMD every rank takes its own branch, so some ranks arrive at a
  collective their peers never post. (``pbroadcast`` bookkeeping that
  ``shard_map``'s replication checker inserts moves zero wire bytes and
  is excluded.)
* ``jaxpr-ring-malformed`` — every ``ppermute`` perm must be a
  bijection over the named axis that covers it exactly once: duplicate
  sources drop data, duplicate destinations race, and a ring that skips
  a rank stalls that rank's recv forever.
* ``jaxpr-silent-replication`` — entry points registered with
  ``max_replicated_bytes=`` are lowered (``jit(fn).lower(...).
  compile()``) with *uncommitted* avals so XLA's sharding propagation
  picks the layouts; any input/output at or above the ceiling that ends
  up fully replicated across a multi-device mesh is flagged — the
  megatensor quietly costs ``n_devices`` copies of HBM.
* ``jaxpr-implicit-gather`` — entry points registered with
  ``in_shardings=`` declare a per-argument sharding contract; a
  propagated input sharding that does not match it means XLA inserted
  an implicit all-gather/reshard on every call to reconcile the layout
  the body actually wants.

The extracted schedule itself is a reviewable artifact:
``--emit-schedule FILE`` writes it as deterministic JSON (ordered
collectives with axis, prim, payload bytes, wire dtype, trip count and
lexical scope) so schedule diffs show up in PRs before they show up as
hangs.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .audit_registry import (EntryPoint, all_entry_points,
                             load_default_entry_points)
from .core import Finding
from .jaxpr_audit import (_COLLECTIVE_PRIMS, _aval_bytes, _entry_location,
                          _subjaxprs)

#: stable rule IDs -> short description (merged into ``--list-rules``,
#: ``--explain`` and the SARIF rule catalog)
RULES: Dict[str, str] = {
    "jaxpr-collective-divergence":
        "cond branches issue different collective sequences — under SPMD "
        "each rank takes its own branch, so ranks block on collectives "
        "their peers never post (static deadlock/hang hazard); hoist the "
        "collectives out of the cond or make the branches symmetric",
    "jaxpr-ring-malformed":
        "ppermute perm is not a bijection covering the named axis "
        "exactly once — duplicate sources drop data, duplicate "
        "destinations race, and an uncovered rank stalls its recv "
        "forever; build the ring as [(i, (i+1) % size) for i in "
        "range(size)]",
    "jaxpr-silent-replication":
        "tensor at or above the entry's max_replicated_bytes lowers to a "
        "fully replicated sharding on a multi-device mesh — it silently "
        "costs one HBM copy per device; shard it (with_sharding_"
        "constraint) or raise the registered ceiling",
    "jaxpr-implicit-gather":
        "propagated input sharding disagrees with the entry's declared "
        "in_shardings contract — XLA reconciles the layouts with an "
        "implicit all-gather/reshard on every call; fix the in_specs or "
        "pin the layout with with_sharding_constraint",
}

#: collectives that move bytes on the wire. ``pbroadcast`` is excluded:
#: shard_map's check_vma rewrite inserts it as zero-wire replication
#: bookkeeping (including into cond branches with no collectives), so
#: counting it would make every benign cond look divergent.
WIRE_COLLECTIVES = frozenset(_COLLECTIVE_PRIMS - {"pbroadcast"})

#: primitives with inner jaxprs that we walk with explicit semantics
#: (everything else with sub-jaxprs is walked generically)
_RING_PRIM = "ppermute"


@dataclasses.dataclass
class CollectiveOp:
    """One wire collective in an entry point's extracted schedule."""

    seq: int
    prim: str
    axes: Tuple[str, ...]
    shape: Tuple[int, ...]
    dtype: str
    payload_bytes: int
    #: static execution count (scan lengths multiplied through); ``None``
    #: under a ``while`` whose trip count is data-dependent
    trips: Optional[int]
    #: lexical scope path, e.g. ``"shard_map/scan"``
    scope: str

    def signature(self) -> Tuple[Any, ...]:
        """Identity used for cross-branch schedule comparison: what the
        peer ranks must match for the collective to complete."""
        return (self.prim, self.axes, self.shape, self.dtype, self.trips)

    def describe(self) -> str:
        ax = ",".join(self.axes) or "?"
        return f"{self.prim}@{ax} {self.dtype}[{'x'.join(map(str, self.shape))}]"

    def to_json(self) -> Dict[str, Any]:
        return {
            "seq": self.seq,
            "prim": self.prim,
            "axes": list(self.axes),
            "shape": list(self.shape),
            "dtype": self.dtype,
            "payload_bytes": self.payload_bytes,
            "trips": self.trips,
            "scope": self.scope,
        }


def _op_axes(params: Dict[str, Any]) -> Tuple[str, ...]:
    axes = params.get("axes")
    if axes is None:
        axes = params.get("axis_name")
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _record_collective(eqn: Any, scope: str, trips: Optional[int],
                       ops: List[CollectiveOp]) -> None:
    avals = [v.aval for v in eqn.invars if hasattr(v, "aval")
             and hasattr(getattr(v, "aval"), "shape")]
    first = avals[0] if avals else None
    ops.append(CollectiveOp(
        seq=-1,  # renumbered after the walk
        prim=eqn.primitive.name,
        axes=_op_axes(eqn.params),
        shape=tuple(int(d) for d in first.shape) if first is not None else (),
        dtype=getattr(getattr(first, "dtype", None), "name", "?"),
        payload_bytes=sum(_aval_bytes(a) for a in avals),
        trips=trips,
        scope=scope))


def _check_perm(eqn: Any, axis_sizes: Dict[str, int], scope: str,
                defects: List[Tuple[str, str]]) -> None:
    perm = tuple(tuple(p) for p in eqn.params.get("perm", ()))
    if not perm:
        return
    axes = _op_axes(eqn.params)
    srcs = [p[0] for p in perm]
    dsts = [p[1] for p in perm]
    issues: List[str] = []
    dup_src = sorted({s for s in srcs if srcs.count(s) > 1})
    dup_dst = sorted({d for d in dsts if dsts.count(d) > 1})
    if dup_src:
        issues.append(f"duplicate source rank(s) {dup_src}")
    if dup_dst:
        issues.append(f"duplicate destination rank(s) {dup_dst}")
    size = next((axis_sizes[a] for a in axes if a in axis_sizes), None)
    if size is not None:
        oob = sorted({r for r in srcs + dsts if not 0 <= r < size})
        if oob:
            issues.append(f"rank(s) {oob} out of range for axis size {size}")
        full = set(range(size))
        if not oob and (set(srcs) != full or set(dsts) != full):
            missing = sorted((full - set(srcs)) | (full - set(dsts)))
            issues.append(
                f"ring covers the axis incompletely (rank(s) {missing} "
                "never send and/or never receive)")
    elif set(srcs) != set(dsts):
        issues.append("source and destination rank sets differ")
    if issues:
        ax = ",".join(axes) or "?"
        defects.append((
            "jaxpr-ring-malformed",
            f"ppermute over axis '{ax}' in scope '{scope}' with perm "
            f"{list(perm)}: " + "; ".join(issues)))


def _branch_summary(branch_ops: List[CollectiveOp]) -> str:
    if not branch_ops:
        return "(no collectives)"
    return ", ".join(op.describe() for op in branch_ops)


def _closed_inner(x: Any) -> Any:
    """The raw Jaxpr inside either a ClosedJaxpr or a raw Jaxpr."""
    return x.jaxpr if hasattr(x, "jaxpr") else x


def _visit(jaxpr: Any, axis_sizes: Dict[str, int], scope: str,
           trips: Optional[int], ops: List[CollectiveOp],
           defects: List[Tuple[str, str]]) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in WIRE_COLLECTIVES:
            _record_collective(eqn, scope, trips, ops)
            if prim == _RING_PRIM:
                _check_perm(eqn, axis_sizes, scope, defects)
            continue
        if prim == "cond":
            branches = eqn.params.get("branches", ())
            per_branch: List[List[CollectiveOp]] = []
            for bi, br in enumerate(branches):
                b_ops: List[CollectiveOp] = []
                _visit(_closed_inner(br), axis_sizes,
                       f"{scope}/cond.b{bi}" if scope else f"cond.b{bi}",
                       trips, b_ops, defects)
                per_branch.append(b_ops)
            sigs = {tuple(op.signature() for op in b) for b in per_branch}
            if len(sigs) > 1:
                detail = "; ".join(
                    f"branch {bi}: {_branch_summary(b)}"
                    for bi, b in enumerate(per_branch))
                defects.append((
                    "jaxpr-collective-divergence",
                    f"cond in scope '{scope or '<top>'}' issues a "
                    f"different collective sequence per branch — {detail}"))
            if per_branch:
                # representative schedule: branches agree when clean, and
                # a divergence is already flagged when they do not
                ops.extend(per_branch[0])
            continue
        if prim == "shard_map":
            inner_sizes = dict(axis_sizes)
            mesh_shape = getattr(eqn.params.get("mesh"), "shape", None)
            if mesh_shape:
                inner_sizes.update({str(k): int(v)
                                    for k, v in dict(mesh_shape).items()})
            _visit(_closed_inner(eqn.params["jaxpr"]), inner_sizes,
                   f"{scope}/shard_map" if scope else "shard_map",
                   trips, ops, defects)
            continue
        if prim in ("xla_pmap", "pmap"):
            inner_sizes = dict(axis_sizes)
            ax, sz = eqn.params.get("axis_name"), eqn.params.get("axis_size")
            if ax is not None and sz is not None:
                inner_sizes[str(ax)] = int(sz)
            _visit(_closed_inner(eqn.params["call_jaxpr"]), inner_sizes,
                   f"{scope}/pmap" if scope else "pmap", trips, ops, defects)
            continue
        if prim == "scan":
            length = eqn.params.get("length")
            inner_trips = (None if trips is None or length is None
                           else trips * int(length))
            _visit(_closed_inner(eqn.params["jaxpr"]), axis_sizes,
                   f"{scope}/scan" if scope else "scan",
                   inner_trips, ops, defects)
            continue
        if prim == "while":
            for key in ("cond_jaxpr", "body_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None:
                    # data-dependent trip count: statically unbounded
                    _visit(_closed_inner(sub), axis_sizes,
                           f"{scope}/while" if scope else "while",
                           None, ops, defects)
            continue
        # jit is transparent; other higher-order prims (remat, custom
        # vjp/jvp, ...) contribute their lexical name to the scope path
        inner_scope = scope
        if prim != "jit":
            inner_scope = f"{scope}/{prim}" if scope else prim
        for sub in _subjaxprs(eqn.params):
            _visit(sub, axis_sizes, inner_scope, trips, ops, defects)


def extract_schedule(closed: Any) -> Tuple[List[CollectiveOp],
                                           List[Tuple[str, str]]]:
    """Walk a ClosedJaxpr and return ``(schedule, defects)``: the ordered
    wire collectives and the ``(rule, message)`` protocol violations
    found along the way."""
    ops: List[CollectiveOp] = []
    defects: List[Tuple[str, str]] = []
    _visit(_closed_inner(closed), {}, "", 1, ops, defects)
    for i, op in enumerate(ops):
        op.seq = i
    return ops, defects


# --------------------------------------------------------------------------
# Sharding-contract audit (lowered entry points)
# --------------------------------------------------------------------------

def _leaf_nbytes(leaf: Any) -> int:
    try:
        size = 1
        for d in leaf.shape:
            size *= int(d)
        return size * int(leaf.dtype.itemsize)
    except (AttributeError, TypeError):
        return 0


def _leaf_str(leaf: Any) -> str:
    try:
        return (f"{leaf.dtype.name}"
                f"[{','.join(str(d) for d in leaf.shape)}]")
    except AttributeError:
        return str(leaf)


def _audit_shardings(ep: EntryPoint, built: Any, closed: Any,
                     flag: Any) -> None:
    """Lower the entry with uncommitted avals so XLA's sharding
    propagation chooses the layouts, then check them against the
    registered contract (``in_shardings`` / ``max_replicated_bytes``)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    try:
        fn = built.fn if hasattr(built.fn, "lower") else jax.jit(built.fn)
        sds_args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype),
            built.args)
        compiled = fn.lower(*sds_args).compile()
        in_sh = jax.tree_util.tree_leaves(compiled.input_shardings[0])
        out_sh = jax.tree_util.tree_leaves(compiled.output_shardings)
    except Exception as e:  # surfaced as a finding, not a crash
        flag("jaxpr-audit-error",
             f"sharding audit (lower+compile) failed: "
             f"{type(e).__name__}: {e}")
        return

    in_leaves = jax.tree_util.tree_leaves(built.args)
    out_leaves = list(closed.out_avals)

    mesh = built.mesh
    if mesh is None:
        for s in list(in_sh) + list(out_sh):
            m = getattr(s, "mesh", None)
            if m is not None:
                mesh = m
                break

    if ep.in_shardings is not None:
        if len(ep.in_shardings) != len(in_sh):
            flag("jaxpr-audit-error",
                 f"in_shardings contract lists {len(ep.in_shardings)} "
                 f"entries but the entry lowers to {len(in_sh)} input "
                 "leaves — fix the registration")
        elif mesh is None:
            flag("jaxpr-audit-error",
                 "no mesh available to evaluate the sharding contract — "
                 "return the mesh via BuiltEntry(mesh=...)")
        else:
            for i, (spec, actual) in enumerate(zip(ep.in_shardings, in_sh)):
                if spec is None:
                    continue
                ndim = len(in_leaves[i].shape)
                expected = NamedSharding(mesh, PartitionSpec(*spec))
                if actual.is_equivalent_to(expected, ndim):
                    continue
                if (getattr(actual, "is_fully_replicated", False)
                        and any(d is not None for d in spec)):
                    flag("jaxpr-implicit-gather",
                         f"input {i} ({_leaf_str(in_leaves[i])}) lowers "
                         f"fully replicated against declared sharding "
                         f"{tuple(spec)!r} — XLA all-gathers it on every "
                         "call; pin the layout with "
                         "with_sharding_constraint or fix the in_specs")
                else:
                    flag("jaxpr-implicit-gather",
                         f"input {i} ({_leaf_str(in_leaves[i])}) lowers "
                         f"to {actual} against declared sharding "
                         f"{tuple(spec)!r} — the propagated layout "
                         "implies an implicit reshard on every call")

    if ep.max_replicated_bytes is not None:
        for kind, leaves, shardings in (("input", in_leaves, in_sh),
                                        ("output", out_leaves, out_sh)):
            for i, (leaf, s) in enumerate(zip(leaves, shardings)):
                nbytes = _leaf_nbytes(leaf)
                ndev = len(getattr(s, "device_set", ()))
                if (nbytes >= ep.max_replicated_bytes and ndev > 1
                        and getattr(s, "is_fully_replicated", False)):
                    flag("jaxpr-silent-replication",
                         f"{kind} {i} ({_leaf_str(leaf)}, {nbytes} bytes) "
                         f"lowers fully replicated across {ndev} devices "
                         f"— {ndev}x HBM for a tensor above the "
                         f"registered ceiling of "
                         f"{ep.max_replicated_bytes} bytes; shard it or "
                         "raise max_replicated_bytes")


# --------------------------------------------------------------------------
# Entry-point drivers
# --------------------------------------------------------------------------

def audit_entry_point(ep: EntryPoint) -> Tuple[List[Finding],
                                               Optional[List[CollectiveOp]]]:
    """Build, trace and protocol-verify one entry point. Returns the
    findings plus the extracted collective schedule (``None`` when the
    build/trace itself failed)."""
    import jax

    path, line = _entry_location(ep)

    def flag(rule: str, message: str) -> None:
        findings.append(Finding(path, line, 0, rule,
                                f"entry point '{ep.name}': {message}"))

    findings: List[Finding] = []
    try:
        built = ep.build()
        closed = jax.make_jaxpr(built.fn)(*built.args)
    except Exception as e:
        flag("jaxpr-audit-error",
             f"build/trace failed: {type(e).__name__}: {e}")
        return findings, None

    schedule, defects = extract_schedule(closed)
    for rule, message in defects:
        flag(rule, message)

    if ep.in_shardings is not None or ep.max_replicated_bytes is not None:
        _audit_shardings(ep, built, closed, flag)
    return findings, schedule


def audit_entry_points(names: Optional[Iterable[str]] = None,
                       include_defaults: bool = True,
                       ) -> Tuple[List[Finding],
                                  Dict[str, List[CollectiveOp]]]:
    """Protocol-verify the selected (default: all registered) entry
    points. Returns ``(findings, schedules)``; ``schedules`` maps entry
    name -> extracted collective schedule."""
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
    schedules: Dict[str, List[CollectiveOp]] = {}
    for name in sorted(entries):
        fs, schedule = audit_entry_point(entries[name])
        findings.extend(fs)
        if schedule is not None:
            schedules[name] = schedule
    return findings, schedules


def schedules_to_json(schedules: Dict[str, List[CollectiveOp]]) -> str:
    """Deterministic JSON for ``--emit-schedule``: same registry state in,
    byte-identical artifact out (keys sorted, no timestamps)."""
    doc = {
        "version": 1,
        "entries": {name: [op.to_json() for op in ops]
                    for name, ops in sorted(schedules.items())},
    }
    return json.dumps(doc, indent=2, sort_keys=True)
