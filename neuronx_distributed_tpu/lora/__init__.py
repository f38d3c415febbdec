"""LoRA adapters.

Analogue of the reference's ``modules/lora/`` (``LoraConfig`` config.py:6,
``LoraModel`` model.py:74, TP-aware ``LoraParallelLinear`` /
``LoraGQAQKVParallelLinear`` tp_layer.py:15,62, adapter-only checkpointing).

TPU-native mapping: the adapters live *inside* the parallel layers
(``lora_rank`` field — A/B sharded consistently with the base kernel, the
LoRA partial sums riding the layer's existing collectives), and the
"model wrapping" of the reference becomes pytree utilities:

* :func:`lora_mask` — boolean pytree marking adapter params (for
  ``optax.masked`` base-freezing, the analogue of requires_grad=False);
* :func:`make_lora_optimizer` — optimizer that updates only adapters;
* :func:`extract_lora_state` / :func:`merge_lora_state` — adapter-only
  checkpoints (reference ``save_lora_base=False`` path);
* :func:`merge_lora_params` — fold ``scale * A @ B`` into the base kernels
  for adapter-free serving (reference merge option).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax

LORA_KEYS = ("lora_a", "lora_b", "q_lora_a", "q_lora_b", "k_lora_a",
             "k_lora_b", "v_lora_a", "v_lora_b", "gate_lora_a",
             "gate_lora_b", "up_lora_a", "up_lora_b")

# (kernel key, A key, B key) triples that merge_lora_params folds together
_MERGE_TRIPLES = (
    ("kernel", "lora_a", "lora_b"),
    ("embedding", "lora_a", "lora_b"),
    ("q_kernel", "q_lora_a", "q_lora_b"),
    ("k_kernel", "k_lora_a", "k_lora_b"),
    ("v_kernel", "v_lora_a", "v_lora_b"),
    ("gate_kernel", "gate_lora_a", "gate_lora_b"),
    ("up_kernel", "up_lora_a", "up_lora_b"),
)


@dataclass(frozen=True)
class LoraConfig:
    """Reference: ``modules/lora/config.py:6``."""

    r: int = 8
    alpha: float = 16.0
    dropout: float = 0.0
    # which sublayers get adapters (matched against llama module names)
    target_modules: Tuple[str, ...] = ("qkv", "o_proj")
    save_lora_base: bool = False

    @property
    def scale(self) -> float:
        return self.alpha / self.r


def is_lora_path(path) -> bool:
    keys = {getattr(p, "key", getattr(p, "name", None)) for p in path}
    return bool(keys & set(LORA_KEYS))


def lora_mask(params: Any) -> Any:
    """Boolean pytree: True for adapter leaves."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: is_lora_path(path), params)


def make_lora_optimizer(tx: optax.GradientTransformation,
                        params: Any) -> optax.GradientTransformation:
    """Update only adapter params; base weights are frozen (reference: the
    LoraModel marks base params non-trainable)."""
    mask = lora_mask(params)
    label = jax.tree_util.tree_map(
        lambda m: "lora" if m else "frozen", mask)
    return optax.multi_transform(
        {"lora": tx, "frozen": optax.set_to_zero()}, label)


def extract_lora_state(params: Any) -> Any:
    """Adapter-only sub-pytree (for adapter checkpoints)."""
    def prune(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k in LORA_KEYS:
                    out[k] = v
                elif isinstance(v, dict):
                    sub = prune(v)
                    if sub:
                        out[k] = sub
            return out
        return {}

    return prune(params)


def merge_lora_state(params: Any, lora_state: Any) -> Any:
    """Insert adapter leaves back into a base param tree."""
    def merge(base, lo):
        if not isinstance(lo, dict):
            return base
        out = dict(base)
        for k, v in lo.items():
            if isinstance(v, dict):
                out[k] = merge(base.get(k, {}), v)
            else:
                out[k] = v
        return out

    return merge(params, lora_state)


def merge_lora_params(params: Any, cfg: LoraConfig) -> Any:
    """Fold adapters into base kernels and drop them (reference merge-and-
    unload). Handles 2-D kernels, the embedding table, fused GQA kernels and
    the llama MLP's ``gate_kernel``/``up_kernel`` (one adapter each)."""
    scale = cfg.scale

    def ab(a, b):
        # a: [h, r] or [L, h, r] (stacked scan layers), b: [r, o] or
        # [L, r, o]. Conv pairs: a [kh, kw, cin, r] with a 1x1 b
        # [1, 1, r, cout] compose into one conv kernel (B is pointwise, so
        # the composition is exact).
        if a.ndim == 4 and b.ndim == 4:
            return jnp.einsum("hwir,ro->hwio", a, b[0, 0])
        return jnp.matmul(a, b)

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {k: walk(v) for k, v in node.items()
               if k not in LORA_KEYS}
        for kern, a_key, b_key in _MERGE_TRIPLES:
            if kern in node and a_key in node and b_key in node:
                out[kern] = node[kern] + scale * ab(node[a_key], node[b_key])
        return out

    return walk(params)
