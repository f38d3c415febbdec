"""Remat (activation checkpointing) policy resolution.

Analogue of the reference's activation-checkpoint config plumbing
(``trainer/trainer.py:147`` applying ``activation_checkpoint_config``): a
single place mapping policy NAMES to ``jax.checkpoint_policies`` so model
configs stay JSON-serialisable.

Policies (what a rematerialised layer keeps across forward and backward):

* ``"save_attention"``, the default: keep the flash kernel's output and
  log-sum-exp (the residuals ``flash_out``/``flash_lse`` that
  ``ops/flash_attention.py`` names in its Pallas and its XLA vjp) and
  recompute the rest. The flash backward takes exactly that pair, so the
  recomputed forward holds no attention kernel; where no flash path ran
  nothing carries the names and the layer is ``"nothing"``'s. A layer
  holds B*S*N*D/tp compute-dtype bytes and B*N*S/tp float32: at
  Mistral-7B's widths and 2 x 4,096 tokens, 16 MiB + 0.25 MiB a chip at
  tp=4, a quarter of the 64 MiB layer boundary that full checkpointing
  keeps anyway; 64 MiB + 1 MiB at tp=1, as much again as the boundary.
  On ``mistral-7b.train-tp4`` (11 layers, v5e 2x2; ``PERF.md``, PR 56)
  the step went from 385.7 to 370.9 ms and 21,187 to 22,034 tokens/s:
  the forward kernel's second run was 19.6 ms of it, moving the pair
  and a later all-gather gave 4.6 back;
* ``"nothing"``: recompute everything, the attention kernel too (it
  then runs twice a layer). For the user who needs those bytes back,
  tp=1 first;
* ``"dots"``: keep matmul outputs without batch dims
  (``dots_with_no_batch_dims_saveable``);
* ``"dots_and_attention"``: the union of "dots" and "save_attention"
  (``save_from_both_policies``);
* any other name resolves via ``getattr(jax.checkpoint_policies, name)``.
"""

from __future__ import annotations

import jax

DEFAULT_REMAT_POLICY = "save_attention"

_ALIASES = {
    "nothing": "nothing_saveable",
    "dots": "dots_with_no_batch_dims_saveable",
    "dots_batch": "dots_saveable",
}

# named-residual policies: factory calls, not plain attributes
_NAMED = {
    "save_attention": ("flash_out", "flash_lse"),
}

# unions of other registry entries (save_from_both_policies)
_COMBINED = {
    "dots_and_attention": ("dots", "save_attention"),
}


def resolve_remat_policy(name: str = DEFAULT_REMAT_POLICY):
    """Policy name -> jax.checkpoint policy callable."""
    if name in _COMBINED:
        return jax.checkpoint_policies.save_from_both_policies(
            *(resolve_remat_policy(part) for part in _COMBINED[name]))
    if name in _NAMED:
        return jax.checkpoint_policies.save_only_these_names(*_NAMED[name])
    resolved = _ALIASES.get(name, name)
    try:
        return getattr(jax.checkpoint_policies, resolved)
    except AttributeError as e:
        raise ValueError(
            f"unknown remat policy {name!r} (aliases: "
            f"{sorted(_ALIASES) + sorted(_NAMED) + sorted(_COMBINED)}; "
            "else any jax.checkpoint_policies name)") from e


def validate_remat_policy(name: str) -> None:
    """Raise ValueError for unknown policy names (config __post_init__
    hook); resolution itself is deferred to model build time."""
    resolve_remat_policy(name)
