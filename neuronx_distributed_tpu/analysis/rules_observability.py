"""Rule ``observability``: instrumentation that lies, and prints that
bypass it.

Three failure classes the ``obs`` subsystem makes tempting:

* **Host clock reads inside JAX-traced code** — ``time.time()`` /
  ``time.perf_counter()`` (and friends) in a ``jit``/``shard_map``/
  ``scan`` body run once at *trace* time: the recorded "timestamp" is a
  compile-time constant baked into every execution, so the measurement
  is silently wrong forever. Spans and timers belong *around* the
  compiled call, on the host.

* **Metric-record calls inside traced code** — ``counter.inc()``,
  ``gauge.dec()``, ``histogram.observe()`` and ``tracer.span()`` are
  host-side APIs; inside traced code they fire once per trace (counting
  compiles, not events) and are exactly the host callbacks the
  no-callbacks invariant forbids. Only attribute calls (``x.inc(...)``)
  are matched — ``.set`` is deliberately not in the list
  (``x.at[i].set(...)`` is core JAX).

* **Bare ``print()`` in library modules** — output that bypasses the
  logger (rank-0 gating, levels) and the event channel (metrics, NXD_EVENT
  parsing). ``print(..., file=...)`` is considered deliberate stream
  writing and allowed. Exempt: ``obs``/``scripts``/``examples`` path
  segments, ``__main__.py`` CLI entry points, and test files
  (``test_*.py`` / ``conftest.py``).
"""

from __future__ import annotations

import ast
import os
from typing import Iterator, List

from . import astutil, dataflow
from .core import Finding, LintContext, register
from .rules_trace_safety import _traced_function_nodes

#: zero-arg wall/CPU clock reads that become trace-time constants.
#: ``time.sleep`` is NOT here — the resilience rule owns it.
_CLOCKS = frozenset({
    "time", "time_ns", "perf_counter", "perf_counter_ns",
    "monotonic", "monotonic_ns", "process_time", "process_time_ns",
})

#: method tails of the obs record surface (attribute calls only).
_METRIC_TAILS = frozenset({
    "inc", "dec", "observe", "span",
})

_PRINT_EXEMPT_SEGMENTS = ("obs", "scripts", "examples")


def _is_clock_call(call: ast.Call) -> bool:
    tail = astutil.tail_name(call.func)
    if tail not in _CLOCKS:
        return False
    root = astutil.root_name(call.func)
    # time.perf_counter(...) or `from time import perf_counter` bare form
    return root == "time" or root == tail


def _is_metric_call(call: ast.Call) -> bool:
    return (isinstance(call.func, ast.Attribute)
            and call.func.attr in _METRIC_TAILS)


def _is_bare_print(call: ast.Call) -> bool:
    return (isinstance(call.func, ast.Name) and call.func.id == "print"
            and not any(kw.arg == "file" for kw in call.keywords))


def _print_exempt(path: str) -> bool:
    norm = path.replace("\\", "/")
    base = os.path.basename(norm)
    if base == "__main__.py" or base == "conftest.py" \
            or base.startswith("test_"):
        return True
    parts = norm.split("/")
    return any(seg in _PRINT_EXEMPT_SEGMENTS for seg in parts)


@register(
    "observability",
    "host clock reads / metric-record calls inside JAX-traced code "
    "(trace-time constants, not measurements) and bare print() in "
    "library modules (bypasses the logger and the obs event channel)")
def check(ctx: LintContext) -> Iterator[Finding]:
    findings: List[Finding] = []

    traced = _traced_function_nodes(ctx.tree)
    if traced:
        seen = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                continue
            if id(node) not in traced:
                continue
            body = node.body if isinstance(node, ast.Lambda) else node
            for sub in ast.walk(body):
                if not isinstance(sub, ast.Call) or id(sub) in seen:
                    continue
                if _is_clock_call(sub):
                    seen.add(id(sub))
                    findings.append(Finding(
                        ctx.path, sub.lineno, sub.col_offset,
                        "observability",
                        "host clock read inside a JAX-traced function is "
                        "a trace-time constant, not a measurement — time "
                        "the compiled call from the host (obs tracer "
                        "span) instead"))
                elif _is_metric_call(sub):
                    seen.add(id(sub))
                    findings.append(Finding(
                        ctx.path, sub.lineno, sub.col_offset,
                        "observability",
                        f".{sub.func.attr}() inside a JAX-traced function "
                        "records once per trace, not per execution — and "
                        "is a host callback in compiled code; move the "
                        "metric/span to the host side around the call"))
                elif ctx.dataflow is not None and dataflow.HOST_TIME \
                        in ctx.dataflow.call_intrinsic(sub):
                    # tier-2 taint: a local helper whose body reads the
                    # host clock — the indirection hides the same
                    # trace-time constant from the name-level check
                    seen.add(id(sub))
                    findings.append(Finding(
                        ctx.path, sub.lineno, sub.col_offset,
                        "observability",
                        "call to a local helper that reads the host "
                        "clock, inside a JAX-traced function — the clock "
                        "read still happens once at trace time; time the "
                        "compiled call from the host (obs tracer span) "
                        "instead"))

    if not _print_exempt(ctx.path):
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and _is_bare_print(node):
                findings.append(Finding(
                    ctx.path, node.lineno, node.col_offset,
                    "observability",
                    "bare print() in a library module bypasses the "
                    "rank-aware logger and the obs event channel — use "
                    "utils.logger.get_logger / log_event (or print with "
                    "an explicit file= for deliberate stream output)"))

    yield from findings
