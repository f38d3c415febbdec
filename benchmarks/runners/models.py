"""From a configuration file (the published keys of a HuggingFace
``config.json``) to what the runners need of its model: the package's
model config, module and cached forward, the served weights under their
published names, and the plain reference to hold them against.

Both are files found by the names the configuration gives:
``families/<family>.py`` and ``reference/<reference>.py``.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import harness


def build(config: dict, **overrides) -> Tuple[Any, Any, Callable]:
    """``(model config, flax module, forward_with_cache)``. ``overrides``
    are fields of the package's config (dtype, runner settings)."""
    return harness.load_plugin("families", config["family"]).build(
        config, **overrides)


def published(params, config: dict):
    """The served ``params`` under the published checkpoint's tensor names:
    ``weights(name, layer=None, expert=None)``, what a reference reads."""
    return harness.load_plugin("families", config["family"]).published(
        params, config)


def reference(config: dict):
    """The configuration's plain reference, ``reference/<module>.py``
    (``decoder_f32`` unless it names another): ``forward(weights, tokens,
    config)`` and ``cross_entropy(logits, labels)``."""
    return harness.load_plugin("reference",
                               config.get("reference", "decoder_f32"))


def dtype_of(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]
