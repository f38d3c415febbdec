"""What a device event's *metadata* says of the operation: the path of
scopes the program traced it under, XLA's category, operations and bytes.

``xplane.load`` reads events through ``jax.profiler.ProfileData``, which
yields an event's own stats (start, duration) and not its metadata's. In
the file each device plane carries, beside its lines,

* ``event_metadata``: ``{id: XEventMetadata}``, one an HLO instruction,
  ``name`` the instruction's text (the event's name) and ``stats`` what
  the compiler knew of it: ``tf_op`` (the instruction's ``op_name``: the
  ``jax.named_scope`` path, ``jit(f)/my_block/dot_general:`` in
  ``fixtures/matmul_loop.xplane.pb``), ``hlo_category``, ``flops``,
  ``bytes_accessed``, ``source``;
* ``stat_metadata``: ``{id: XStatMetadata}``, the stats' names, and the
  strings that a ``ref_value`` stat points at.

They are read here from the protobuf wire format itself (varints and
length-delimited fields; ``tsl/profiler/protobuf/xplane.proto``), with
nothing but the standard library: the machine with the chip is not known
to have a protobuf module for it.

An instruction the compiler made itself carries no ``tf_op``: a layout
``copy``, a fusion it cloned or merged (``%fusion.195``, the rotary of a
packed step). The file also holds every program's ``HloProto`` (plane
``/host:metadata``, stat ``Hlo Proto``), and in it every instruction
*inside* such a fusion still has its ``op_name``. ``resolved_paths`` gives
each instruction of a program a path from there: its own, else that of
the instructions of its fused computation (the scope that the most result
elements fall under, matmuls first), else its nearest operand's or
user's. ``readers/device_scope_share.py`` takes ``tf_op`` where it holds a
marker of the program's, and this where it does not.

``python benchmarks/tracereduce/scopes.py <trace.xplane.pb>`` prints, as
one JSON object, the traced window's device time by scope and the largest
operations of each (``neuronx_distributed_tpu.obs.device_scopes`` says
what a scope is).
"""

from __future__ import annotations

import glob
import os
import re
import struct
from dataclasses import dataclass
from typing import Dict, Iterator, Optional, Tuple

try:
    from tracereduce import xplane
except ImportError:                      # run as a script
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tracereduce import xplane

# field numbers of tsl/profiler/protobuf/xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
META_ID, META_NAME, META_STATS = 1, 2, 5
METADATA_PLANE, HLO_PROTO_STAT = "/host:metadata", "Hlo Proto"
# xla/service/hlo.proto and xla/xla_data.proto
HLO_MODULE, MODULE_COMPUTATIONS = 1, 3
COMPUTATION_INSTRUCTIONS, COMPUTATION_ID = 2, 5
(INSTR_NAME, INSTR_OPCODE, INSTR_SHAPE, INSTR_METADATA, INSTR_ID,
 INSTR_OPERANDS, INSTR_CALLED) = 1, 2, 3, 7, 35, 36, 38
OPMETA_OP_NAME = 2
SHAPE_DIMENSIONS, SHAPE_TUPLE = 3, 4
MATMULS = ("dot", "convolution", "custom-call")
# what takes the scope of the computations it calls, where it has none
BODIES = ("fusion", "conditional", "call")
STAT_ID, STAT_DOUBLE, STAT_UINT, STAT_INT, STAT_STR, STAT_BYTES, STAT_REF = (
    1, 2, 3, 4, 5, 6, 7)
VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


@dataclass(frozen=True)
class OpMeta:
    tf_op: str = ""
    hlo_category: str = ""
    flops: int = 0
    bytes_accessed: int = 0
    program_id: int = 0


@dataclass
class Instruction:
    name: str = ""
    opcode: str = ""
    op_name: str = ""
    elements: int = 0                    # of its result
    id: int = 0
    operands: tuple = ()
    called: tuple = ()


def _varint(buf, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: an ``int`` for
    a varint or a fixed-width field, a ``memoryview`` of the bytes for a
    length-delimited one (a string, a sub-message)."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == VARINT:
            value, at = _varint(buf, at)
        elif wire == BYTES:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == FIXED64:
            value, at = struct.unpack_from("<Q", buf, at)[0], at + 8
        elif wire == FIXED32:
            value, at = struct.unpack_from("<I", buf, at)[0], at + 4
        else:
            raise ValueError(f"wire type {wire} of field {number}")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _map_entries(plane, field: int) -> Iterator[Tuple[int, object]]:
    for number, _, entry in fields(plane):
        if number != field:
            continue
        key, value = 0, b""
        for n, _, v in fields(entry):
            if n == MAP_KEY:
                key = v
            elif n == MAP_VALUE:
                value = v
        yield key, value


def _stat(stat, names: Dict[int, str]):
    """``(stat's name, value)``; a ``ref_value`` is the string it names."""
    name, value = "", None
    for n, wire, v in fields(stat):
        if n == STAT_ID:
            name = names.get(v, "")
        elif n == STAT_STR:
            value = _text(v)
        elif n == STAT_REF:
            value = names.get(v, "")
        elif n == STAT_INT:
            value = _signed(v)
        elif n == STAT_UINT:
            value = v
        elif n == STAT_DOUBLE:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif n == STAT_BYTES:
            value = bytes(v)
    return name, value


def _stat_names(plane) -> Dict[int, str]:
    """``{id: name}`` of a plane's ``stat_metadata``."""
    return {key: _text(v)
            for key, meta in _map_entries(plane, PLANE_STAT_METADATA)
            for n, _, v in fields(meta) if n == META_NAME}


def plane_metadata(plane) -> Dict[str, OpMeta]:
    """``{instruction text: OpMeta}`` of one ``XPlane`` message."""
    names = _stat_names(plane)
    out = {}
    for _, meta in _map_entries(plane, PLANE_EVENT_METADATA):
        name, stats = "", {}
        for n, _, v in fields(meta):
            if n == META_NAME:
                name = _text(v)
            elif n == META_STATS:
                key, value = _stat(v, names)
                stats[key] = value
        out[name] = OpMeta(
            tf_op=str(stats.get("tf_op") or ""),
            hlo_category=str(stats.get("hlo_category") or ""),
            flops=int(stats.get("flops") or 0),
            bytes_accessed=int(stats.get("bytes_accessed") or 0),
            program_id=int(stats.get("program_id") or 0))
    return out


def _planes(path: str) -> Iterator[Tuple[str, object]]:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, _, plane in fields(space):
        if number == SPACE_PLANES:
            yield next((_text(v) for n, _, v in fields(plane)
                        if n == PLANE_NAME), ""), plane


def device_metadata(path: str, only: Optional[int] = None
                    ) -> Dict[int, Dict[str, OpMeta]]:
    """``{chip: {instruction text: OpMeta}}`` of the file's device planes
    (``/device:TPU:<n>``), or of the one plane ``only``."""
    out = {}
    for name, plane in _planes(path):
        m = xplane.DEVICE_PLANE.match(name)
        if m and only in (None, int(m.group(1))):
            out[int(m.group(1))] = plane_metadata(plane)
    return out


def _packed(value, wire) -> Tuple[int, ...]:
    """A repeated integer field's values: one a field, or packed."""
    if wire == VARINT:
        return (value,)
    out, at = [], 0
    while at < len(value):
        v, at = _varint(value, at)
        out.append(v)
    return tuple(out)


def _elements(shape) -> int:
    dims, parts = (), []
    for n, wire, v in fields(shape):
        if n == SHAPE_DIMENSIONS:
            dims += _packed(v, wire)
        elif n == SHAPE_TUPLE:
            parts.append(_elements(v))
    if parts:
        return sum(parts)
    total = 1
    for d in dims:
        total *= d
    return total


def _instruction(message) -> Instruction:
    out = Instruction()
    for n, wire, v in fields(message):
        if n == INSTR_NAME:
            out.name = _text(v)
        elif n == INSTR_OPCODE:
            out.opcode = _text(v)
        elif n == INSTR_SHAPE:
            out.elements = _elements(v)
        elif n == INSTR_METADATA:
            out.op_name = next((_text(x) for k, _, x in fields(v)
                                if k == OPMETA_OP_NAME), "")
        elif n == INSTR_ID:
            out.id = v
        elif n == INSTR_OPERANDS:
            out.operands += _packed(v, wire)
        elif n == INSTR_CALLED:
            out.called += _packed(v, wire)
    return out


def hlo_programs(path: str) -> Dict[int, Dict[int, list]]:
    """``{program id: {computation id: [Instruction]}}`` of the programs
    whose ``HloProto`` the file holds (the plane ``/host:metadata``: one
    event metadata a program, named ``<module>(<program id>)``)."""
    out = {}
    for name, plane in _planes(path):
        if name != METADATA_PLANE:
            continue
        names = _stat_names(plane)
        for _, meta in _map_entries(plane, PLANE_EVENT_METADATA):
            program, proto = None, None
            for n, _, v in fields(meta):
                if n == META_NAME:
                    m = re.search(r"\((\d+)\)$", _text(v))
                    program = int(m.group(1)) if m else None
                elif n == META_STATS:
                    key, value = _stat(v, names)
                    if key == HLO_PROTO_STAT:
                        proto = value
            if program is None or proto is None:
                continue
            module = next((v for n, _, v in fields(memoryview(proto))
                           if n == HLO_MODULE), None)
            computations = {}
            for n, _, comp in fields(module if module is not None else b""):
                if n != MODULE_COMPUTATIONS:
                    continue
                cid, instructions = 0, []
                for k, _, v in fields(comp):
                    if k == COMPUTATION_ID:
                        cid = v
                    elif k == COMPUTATION_INSTRUCTIONS:
                        instructions.append(_instruction(v))
                computations[cid] = instructions
            out[program] = computations
    return out


def resolved_paths(computations: Dict[int, list], scope_of,
                   unscoped: str = "(unscoped)") -> Dict[str, Tuple[str, str]]:
    """``{instruction name: (path, route)}`` of one program: ``route``
    says where the path is from: ``own`` (the instruction's ``op_name``
    holds a marker), ``body`` (a fusion's, a conditional's or a call's:
    of the scopes the instructions of the computations it calls carry, the
    one most result elements fall under, a matmul's operands counted with
    it), ``neighbour`` (the nearest operand's, else user's, within three
    steps), ``caller`` (that of the ``while``, ``conditional`` or ``call``
    whose body or branch the instruction sits in: the zeros of the branch
    not taken), ``none``."""
    def weigh(instructions, into):
        by_id = {i.id: i for i in instructions}
        for i in instructions:
            if i.called and i.opcode in BODIES:
                for cid in i.called:
                    weigh(computations.get(cid, ()), into)
                continue
            scope = scope_of(i.op_name)
            if scope == unscoped:
                continue
            weight = i.elements
            if i.opcode in MATMULS:
                weight += sum(by_id[o].elements for o in i.operands
                              if o in by_id)
            best = into.get(scope)
            into[scope] = (weight + (best[0] if best else 0),
                           best[1] if best else i.op_name)

    out = {}
    for instructions in computations.values():
        for i in instructions:
            if scope_of(i.op_name) != unscoped:
                out[i.name] = (i.op_name, "own")
            elif i.opcode in BODIES:
                inside = {}
                for cid in i.called:
                    weigh(computations.get(cid, ()), inside)
                if inside:
                    out[i.name] = (max(inside.values())[1], "body")
    callers = {cid: out[i.name] for instructions in computations.values()
               for i in instructions
               if i.opcode in xplane.CONTAINERS and i.name in out
               for cid in i.called}
    for cid, instructions in computations.items():
        by_id = {i.id: i for i in instructions}
        users = {}
        for i in instructions:
            for o in i.operands:
                users.setdefault(o, []).append(i)
        for i in instructions:
            if i.name in out:
                continue
            found = None
            for step in (lambda x: [by_id[o] for o in x.operands
                                    if o in by_id],
                         lambda x: users.get(x.id, [])):
                front = [i]
                for _ in range(3):
                    front = [n for x in front for n in step(x)]
                    found = next((out[n.name] for n in front
                                  if out.get(n.name, ("", "none"))[1]
                                  in ("own", "body")), None)
                    if found or not front:
                        break
                if found:
                    break
            if found:
                out[i.name] = (found[0], "neighbour")
            elif cid in callers:
                out[i.name] = (callers[cid][0], "caller")
            else:
                out[i.name] = (i.op_name, "none")
    return out


def newest_trace(out_dir: str) -> Optional[str]:
    """The newest ``*.xplane.pb`` under ``<out_dir>/*/trace/``: where the
    runners put a traced run's file (``cell.out_path("trace")``)."""
    paths = glob.glob(os.path.join(out_dir, "*", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def event_scopes(path: str, device: int, scope_of,
                 unscoped: str = "(unscoped)") -> Dict[str, Tuple[str, str]]:
    """``{instruction text: (scope, route)}`` of one device plane's event
    metadata: the innermost marker of ``tf_op`` (route ``tf_op``), else of
    the path :func:`resolved_paths` finds the instruction in its program's
    ``HloProto`` (``body``, ``neighbour``), else ``unscoped`` (``none``)."""
    meta = device_metadata(path, device).get(device, {})
    programs, resolved = None, {}
    out = {}
    for name, m in meta.items():
        scope, route = scope_of(m.tf_op), "tf_op"
        if scope == unscoped and m.program_id:
            if programs is None:
                programs = hlo_programs(path)
            pid = m.program_id
            if pid in programs and pid not in resolved:
                resolved[pid] = resolved_paths(programs[pid], scope_of,
                                               unscoped)
            found = resolved.get(pid, {}).get(xplane.short_name(name))
            if found and found[1] != "none":
                scope, route = scope_of(found[0]), found[1]
        out[name] = (scope, route if scope != unscoped else "none")
    return out


_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")


def is_container(hlo_text: str) -> bool:
    """Whether a device event spans the events of the computations it
    calls (``xplane.CONTAINERS``), by its opcode and not by its name:
    ``lax.cond``'s instruction is ``%cond.3 = (..) conditional(..)``, and
    ``xplane.op_kind`` reads ``cond``."""
    m = _OPCODE.search(hlo_text.split(" = ", 1)[-1])
    return (m.group(1) if m else xplane.op_kind(hlo_text)) in (
        xplane.CONTAINERS)


def seconds_by_scope(trace: xplane.Trace, window, scopes_of_events,
                     device: Optional[int] = None):
    """``({scope: seconds}, {scope: {stable name: seconds}}, {route:
    seconds})`` of the operations of one device inside ``window``,
    containers left out; ``scopes_of_events`` is :func:`event_scopes`'s."""
    lo, hi = window
    dev = min(trace.devices) if device is None else device
    by_scope, ops, by_route = {}, {}, {}
    for e in trace.devices[dev].ops:
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a or is_container(e.name):
            continue
        scope, route = scopes_of_events.get(e.name, ("(not in metadata)",
                                                     "none"))
        by_scope[scope] = by_scope.get(scope, 0.0) + (b - a)
        by_route[route] = by_route.get(route, 0.0) + (b - a)
        per = ops.setdefault(scope, {})
        name = xplane.stable_name(e.name)
        per[name] = per.get(name, 0.0) + (b - a)
    return by_scope, ops, by_route


def main(argv=None) -> int:
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a .xplane.pb, or a directory of them "
                    "(benchmarks/out): the newest is read")
    ap.add_argument("--window", default="bench/trace_window")
    ap.add_argument("--step", default=None,
                    help="the annotation of one step (bench/engine_step, "
                         "bench/train_step): seconds become ms a step")
    ap.add_argument("--top", type=int, default=6)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from neuronx_distributed_tpu.obs.device_scopes import scope_of

    path = (newest_trace(args.trace) if os.path.isdir(args.trace)
            else args.trace)
    trace = xplane.load(path)
    window = xplane.window_of(trace, args.window)
    device = min(trace.devices)
    meta = device_metadata(path, device)[device]
    scoped = event_scopes(path, device, scope_of)
    red = xplane.reduce(trace, window)
    by_scope, ops, by_route = seconds_by_scope(trace, window, scoped)
    moved = {}                      # the bytes XLA reckons a scope accesses
    for e in trace.devices[device].ops:
        if (e.name in meta and window[0] <= e.start < window[1]
                and not is_container(e.name)):
            scope = scoped[e.name][0]
            moved[scope] = moved.get(scope, 0) + meta[e.name].bytes_accessed
    steps = sum(1 for e in trace.annotations
                if e.name == args.step and window[0] <= e.start < window[1])
    unit = 1e3 / steps if steps else 1.0
    busy = red.busy_by_device[device]
    out = {"trace": path, "steps": steps, "window_s": red.window_s,
           "busy_s": busy, "unit": "ms a step" if steps else "s",
           "not_in_metadata": sorted(
               {xplane.stable_name(e.name)
                for e in trace.devices[device].ops if e.name not in meta}),
           "pct_of_busy_by_route": {r: 100.0 * v / busy
                                    for r, v in sorted(by_route.items())},
           "scopes": {s: {"time": v * unit, "pct_of_busy": 100.0 * v / busy,
                          "xla_gb_per_s": moved.get(s, 0) / v / 1e9,
                          "ops": [[n, t * unit, scoped_route(scoped, n)]
                                  for n, t in sorted(
                                      ops[s].items(),
                                      key=lambda kv: -kv[1])[:args.top]]}
                      for s, v in sorted(by_scope.items(),
                                         key=lambda kv: -kv[1])}}
    out["sum_pct_of_busy"] = sum(v["pct_of_busy"]
                                 for v in out["scopes"].values())
    print(json.dumps(out, indent=1))
    return 0


def scoped_route(scoped, stable: str) -> str:
    """The routes by which the operations of one stable name got their
    scope (``tf_op``, ``body``, ``neighbour``, ``none``)."""
    return "+".join(sorted({route for name, (_, route) in scoped.items()
                            if xplane.stable_name(name) == stable}))


if __name__ == "__main__":
    raise SystemExit(main())
