"""Gate and up of a gated feed-forward: how the two projections are stored
and contracted, in one place.

**The form.** Two leaves of one shape, ``[..., H, I]`` each (``[H, I]``
dense, ``[E, H, I]`` for an expert bank; tp shards the last dimension, ep
the expert dimension), contracted by two dots: the two are ordinary
column-parallel kernels, like ``q``/``k``/``v``. They used to be one fused
leaf with a dimension of 2 second from last. The chip tiles such a leaf
``T(2,128)``; the matmul can re-lay that operand but cannot then also take
the layer scan's ``dynamic-slice`` into its fusion, so XLA copied one
layer's gate and up out of the stack in front of every matmul (1.75 GiB a
Mixtral layer, 22 ms of a 55 ms serving step on a v5e; AOT listings in
``PERF.md``, PR 28). In toy scans at the published widths (AOT, issue 28)
a fused leaf with the 2 in front of ``H`` serves without the copy but gets
a per-layer gradient copy in training; two leaves compile clean in the real
serving steps and the real train step.

Everything that stores, declares, contracts or converts gate and up goes
through here: :class:`..models.llama.LlamaMLP`,
:class:`.moe.expert_mlps.ExpertMLPs`, :class:`.moe.model.SharedExperts`,
``scripts/checkpoint_converter.py`` and the quantized tiers' converters
(their own stored forms, ``gate_up_q``/``gate_up_packed``, are made from
:func:`fused` and are not this module's).
"""

from __future__ import annotations

from typing import Any, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

# (gate, up) leaf names: a dense MLP's beside its ``down`` module, an
# expert bank's beside its ``down`` leaf
DENSE = ("gate_kernel", "up_kernel")
EXPERTS = ("gate", "up")

_OLD_FORM = (
    "a tree in the old fused form ([..., hidden, 2, intermediate], one leaf "
    "{old!r}): this package stores gate and up as two leaves {names} of "
    "[..., H, I] each; rebuild the tree from the published gate_proj/up_proj "
    "(w1/w3) with scripts/checkpoint_converter.py")


# what the one fused leaf of the old form was called
_FUSED = {DENSE: "gate_up_kernel", EXPERTS: "gate_up"}


def _refuse_old_form(has_fused: bool, names: Tuple[str, str]) -> None:
    if has_fused:
        raise ValueError(_OLD_FORM.format(old=_FUSED[names], names=names))


def declare(module: nn.Module, names: Tuple[str, str], init, axes,
            shape, dtype) -> Tuple[jax.Array, jax.Array]:
    """Declare gate and up on ``module``: two params of ``shape``
    (``[..., H, I_local]``) partitioned alike over ``axes``."""
    _refuse_old_form(module.has_variable("params", _FUSED[names]), names)
    return tuple(
        module.param(n, nn.with_partitioning(init, axes), shape, dtype)
        for n in names)


def project(x: jax.Array, gate: jax.Array, up: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """``(x @ gate, x @ up)``: ``x [..., H]`` against ``[H, I]``, or
    ``[E, C, H]`` against an expert bank ``[E, H, I]``."""
    return jnp.matmul(x, gate), jnp.matmul(x, up)


def gated(g: jax.Array, u: jax.Array) -> jax.Array:
    """``silu(gate's product) * up's product``."""
    return nn.silu(g) * u


def _t(w) -> np.ndarray:
    # published [..., out, in] <-> stored [..., in, out]
    return np.ascontiguousarray(np.swapaxes(np.asarray(w), -1, -2))


def from_published(gate_proj, up_proj, names: Tuple[str, str]
                   ) -> Mapping[str, np.ndarray]:
    """The two leaves from the published ``gate_proj``/``up_proj``
    (``w1``/``w3``) weights, ``[..., I, H]`` each (any leading stack)."""
    return {names[0]: _t(gate_proj), names[1]: _t(up_proj)}


def to_published(node: Mapping[str, Any], names: Tuple[str, str]
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Inverse of :func:`from_published`: ``(gate_proj, up_proj)``."""
    _refuse_old_form(_FUSED[names] in node, names)
    return _t(node[names[0]]), _t(node[names[1]])


def fused(node: Mapping[str, Any], names: Tuple[str, str]) -> np.ndarray:
    """Gate and up as one float array ``[..., hidden, 2, intermediate]``,
    gate first: what the quantized tiers' converters quantize into their own
    stored forms."""
    _refuse_old_form(_FUSED[names] in node, names)
    return np.stack([np.asarray(node[names[0]]), np.asarray(node[names[1]])],
                    axis=-2)
