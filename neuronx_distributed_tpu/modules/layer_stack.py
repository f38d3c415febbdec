"""A layer's weights where they lie in the layers' stack.

A layer scan (:func:`..models.llama.run_layers`) applies a layer with
``stack[i]`` of every leaf under ``"params"``. XLA takes that slice into the
fusion that reads it; a Pallas kernel is a custom call whose operands must
be buffers, so a slice handed to one is written out first: a copy of the
layer's weights in front of every call. Such a kernel takes the stack and
the index instead (as the paged kernels take the pool and the layer:
:class:`..inference.paging.PagedCacheView`), and the scan hands both beside
the slice, in a second, read-only collection of the same tree: under
:data:`COLLECTION`, where ``"params"`` holds a leaf ``w[i]``, a
:class:`LayerStack` holds ``w`` and ``i``. A module that has such a kernel
declares its params as ever and reads its own leaves' stacks there
(:func:`of`); every other module never looks, and what nobody reads costs
nothing (the unused slice of a leaf read as a stack is dead code too).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
from flax import linen as nn

#: the variable collection a layer scan hands the stacks in
COLLECTION = "layer_stack"


class LayerStack(NamedTuple):
    """One leaf of a run of like layers: ``stack [L, ...]`` and ``layer``,
    the int32 index of the layer being applied."""
    stack: jax.Array
    layer: jax.Array


def beside(stack, layer) -> dict:
    """The variables of :data:`COLLECTION` for layer ``layer`` of the
    parameter stack ``stack`` (a tree whose leaves lead with the depth)."""
    return jax.tree_util.tree_map(lambda w: LayerStack(w, layer), stack)


def of(module: nn.Module, names: Sequence[str], slices: Sequence[jax.Array],
       dtype) -> Optional[Tuple[Tuple[jax.Array, ...], jax.Array]]:
    """``(stacks, layer)`` of ``module``'s params ``names`` where a layer
    scan handed them in and a kernel can read them as they lie: each is
    there, is ``slices``' leaf with the depth in front, and is stored in
    ``dtype`` already (a cast of a stack converts every layer's leaf for
    the one the kernel reads: such a tree keeps the slice). Else None."""
    if not all(module.has_variable(COLLECTION, n) for n in names):
        return None
    held = [module.get_variable(COLLECTION, n) for n in names]
    if any(h.stack.dtype != dtype or h.stack.shape[1:] != w.shape
           for h, w in zip(held, slices)):
        return None
    return tuple(h.stack for h in held), held[0].layer
