"""Device scopes: the names the program gives the layers of its step.

A host span (``obs/tracing.py``) times what the host does. What the
device does inside one compiled step is a list of fusions, and the only
thing a fusion carries from the program that made it is its metadata:
the ``op_name`` of the lowered operation, which is the path of
``jax.named_scope`` names it was traced under. The profiler keeps that
path as the ``tf_op`` of the device event's metadata, so a scope opened
here is what XProf and Perfetto show for an operation, and what
``benchmarks/readers/device_scope_share.py`` sums device time by.

The markers are metadata of the lowered program and no operation: they
cost nothing when nothing is traced, so there is no switch. They are one
fixed list, because a reader can build on names the program owns and on
nothing a refactoring renames (a flax module's name, a wrapper's)::

    with device_scope("attn.kernel"):
        out = paged_attention(...)

    scope_of("jit(step_fn)/while/body/closed_call/_PagedScanBody/layer/"
             "nxd.attn/attn/nxd.attn.kernel/pallas_call")  # "attn.kernel"

A fusion is one device event and takes its root's metadata; the
residual add that takes a block's output is therefore opened inside the
block's scope (``models/llama.py``).

An executable read back from JAX's persistent compile cache carries the
metadata of the process that compiled it, and the cache's key leaves
metadata out: after a marker moves, a cached step shows the old names
until it is compiled again (``jax_compilation_cache_include_metadata_in_key``,
or another cache directory).
"""

from __future__ import annotations

import re

import jax

PREFIX = "nxd."
UNSCOPED = "(unscoped)"

#: every scope the program opens; a dotted name is a child of its stem
SCOPES = (
    "embed", "norm",
    "attn", "attn.proj", "attn.kernel", "attn.kernel.full",
    "attn.kernel.window", "attn.walk", "attn.select", "attn.index",
    "attn.state", "attn.conv", "attn.summarise", "attn.pool_write",
    "ffn", "ffn.dense", "ffn.router", "ffn.experts", "ffn.shared",
    "ffn.identity", "ffn.latent",
    "hc", "hc.mix", "hc.apply",
    "head", "sample", "sample.uncover",
    "loss", "optimizer",
)

_MARKER = re.compile(re.escape(PREFIX) + r"([a-z_]+(?:\.[a-z_]+)*)")


def device_scope(name: str):
    """``jax.named_scope("nxd.<name>")`` for a name of :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is no device scope; there are {SCOPES}")
    return jax.named_scope(PREFIX + name)


def scope_of(op_name: str) -> str:
    """The innermost marker of an operation's path (its ``op_name`` in
    HLO metadata, ``tf_op`` in a device trace), whatever wraps it:
    ``jit(..)``, ``while/body/closed_call``, ``transpose(jvp(..))``,
    ``checkpoint/rematted_computation``, a flax module's name. A path
    with no marker, or no path, is :data:`UNSCOPED`."""
    found = _MARKER.findall(op_name or "")
    return found[-1] if found else UNSCOPED


def within(scope: str, names) -> bool:
    """Whether ``scope`` is one of ``names`` or a child of one."""
    return any(scope == n or scope.startswith(n + ".") for n in names)
