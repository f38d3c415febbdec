"""Remat (activation checkpointing) policy resolution.

Analogue of the reference's activation-checkpoint config plumbing
(``trainer/trainer.py:147`` applying ``activation_checkpoint_config``): a
single place mapping policy NAMES to ``jax.checkpoint_policies`` so model
configs stay JSON-serialisable.

Policies (what a rematerialised layer keeps across forward and backward):

* ``"save_attention"``, what a model that names no policy keeps at
  least (the rule below): keep the flash kernel's output and
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
* ``"save_attention_and_glu"``: the flash pair and gate's and up's
  products of the dense feed-forward (``glu_gate``/``glu_up``, named at
  ``models/llama.LlamaMLP``'s call sites in a model that sets ``remat``
  and in no other: a served model traces no name and lowers the text it
  lowered before the names existed; an expert bank carries no names).
  What is left to recompute a layer: the two norms, q/k/v with
  rotary, ``o_proj`` with its ring and all-gather, and ``silu(g) * u``.
  A layer holds 2 * B*S*I/tp compute-dtype bytes more: at Mistral-7B's
  widths and 2 x 4,096 tokens 112 MiB a chip at tp=4 (1.75 of the
  64 MiB boundary), 448 MiB at tp=1. The backward scan's body then runs
  one product of width I/tp (``down``'s transpose) where it ran three;
* ``"nothing"``: recompute everything, the attention kernel too (it
  then runs twice a layer). For the user who needs those bytes back,
  tp=1 first;
* ``"dots"``: keep matmul outputs without batch dims
  (``dots_with_no_batch_dims_saveable``);
* ``"dots_and_attention"``: the union of "dots" and "save_attention"
  (``save_from_both_policies``);
* any other name resolves via ``getattr(jax.checkpoint_policies, name)``.

**The rule** (:func:`choose_remat_policy`). A model that names no policy
(``remat_policy=None``, the default) gets ``save_attention``, and
``save_attention_and_glu`` where the bound train step
(``trainer.make_train_step``, at trace time, once a compiled step) finds
that a chip has the bytes:

    state + gradients + compute-dtype copies + logits + 2 x kept
        <= 9/10 x limit

``kept`` is ``layers x 2 x B_local x S x I/tp x itemsize`` (a pass's rows
from the batch's shape, a microbatch's where the step accumulates; the
model's widths; the mesh's tp size). The state is the ``TrainState``'s
leaves as ``state_shardings`` places them on one chip; the gradients one
more copy of the parameters in their own dtype, and a second where
``grad_accum_steps > 1`` sums microbatches' into an accumulator; the
copies one in the compute dtype where the two differ (XLA hoists the
casts of a scanned stack out of the loop); the logits a pass's rows by
V/tp in the compute dtype and in float32 (a chunk's under
``loss_chunk``); the limit the ``memory_stats()["bytes_limit"]`` of a
device the process holds (every host asks a chip of its own, so all
trace one program). The pair is counted twice, once for itself and once
for what the lean policy already stacks a layer (the 64 MiB boundary and
the flash pair: 0.72 of the pair at Mistral's widths and tp=4) and one
layer's working set, which the rule does not price; gradients and logits
are summed though they are never live together. So the sum errs high: it
was over the compiler's peak for the rich step in all 26 jobs that
compiled of the 28 tried (below). What the builder cannot price keeps
``save_attention``: a backend that reports no limit (the CPU, a
described device), a GSPMD step, a custom ``loss_fn``/``grad_fn``, any
mesh with pipeline, context or expert parallelism, and a family whose
layer, blocks, mixer or feed-forward are its own
(``LlamaConfig.plain_layers``: experts' buffers and mixers' states a
layer are in none of the terms). A name pins its policy. The choice is
for the whole scan.
``nxd_train_remat_kept_bytes{policy}`` says what was chosen.

On ``mistral-7b.train-tp4`` (fp32 parameters and AdamW, bf16 compute,
2 x 4,096 tokens, tp=4 on a v5e 2x2 of 15.75 GiB a chip; AOT, PR 61,
and again PR 62 at 11 to 13 layers, to the digit; the compiler's
``peak_memory_in_bytes``, which is what it refuses a program by and what
the chip then holds: ``temp_size_in_bytes`` counts what the forward scan
hands the backward scan twice), and the same job summing two
microbatches of 4,096 tokens:

    layers  passes  state   rule's sum  chosen  peak lean  peak rich
    11      1       7.455   13.96       rich    12.18      13.40 GiB
    12      1       8.064   15.10       lean    13.18      14.51
    13      1       8.673   16.23       lean    14.18      15.46
    9       2       6.236   12.60       rich    11.80      12.18
    10      2       6.845   13.83       rich    12.96      13.39
    11      2       7.455   15.06       lean    14.10      14.60

against 9/10 of the limit, 14.17 GiB. (Since PR 65 the bound step shards
the residual stream over the sequence where its rings engage,
``trainer._module_for_step``: the boundary "full" keeps is 16 MiB a layer
for 64 and the 11-layer job peaks at 11.71 GiB lean and 12.81 rich. The
pair's bytes do not depend on that layout, so the rule's terms stand and
its sum errs higher by 48 MiB a layer.) With these, over 7, 9 and 10
layers, one and two passes, a vocabulary of 32,768 and of 131,072 and
the loss whole and in chunks of 512, 28 jobs: the rule chose rich in
15, whose rich step leaves 2.35 GiB or more, and lean in 13: two that
the compiler refuses under either policy, three whose rich step would
leave 0.29, 0.26 and 0.07 GiB (13 layers; 9 layers, two passes, the wide
vocabulary: without the accumulator's term the rule took those two),
eight with 1.0 to 2.6 to spare. Whether the loss is chunked moved no
peak by more than 0.2 GiB: the logits are gone when the backward scan,
where the peak is, holds the gradients. On the
chip at 11 layers (``PERF.md``, PR 62) the step went from 359.3 to
332.1 ms and 22,750 to 24,610 tokens/s (24,365 in a process whose host
runs at half speed): the two products' second run was 24.3 ms of it
after the 5.2 that stacking and re-gating the pair cost, and the
recomputed ``o_proj``'s all-gathers, asynchronous behind the shorter
recomputation, 6.0 more; the allocator's ``bytes_reserved`` rose from
4.74 to 5.96 GiB and the gauge read 1,291,845,632.
"""

from __future__ import annotations

from typing import Optional

import jax

DEFAULT_REMAT_POLICY = "save_attention"
#: what a step with the bytes for it keeps (:func:`choose_remat_policy`)
RICH_REMAT_POLICY = "save_attention_and_glu"

_ALIASES = {
    "nothing": "nothing_saveable",
    "dots": "dots_with_no_batch_dims_saveable",
    "dots_batch": "dots_saveable",
}

# named-residual policies: factory calls, not plain attributes
_NAMED = {
    "save_attention": ("flash_out", "flash_lse"),
    "save_attention_and_glu": ("flash_out", "flash_lse",
                               "glu_gate", "glu_up"),
}

# unions of other registry entries (save_from_both_policies)
_COMBINED = {
    "dots_and_attention": ("dots", "save_attention"),
}


def choose_remat_policy(name: Optional[str], *, kept_bytes: int,
                        step_bytes: int, limit_bytes: Optional[int]) -> str:
    """The policy a train step's layers get: ``name`` where the model was
    given one, else the rich policy exactly when a chip has the bytes.

    ``kept_bytes``: gate's and up's products of every layer on one chip;
    ``step_bytes``: what the step holds there whatever a layer keeps (the
    state, the gradients and their accumulator, the compute-dtype copies
    of the parameters, the logits); a caller that cannot count one of
    them does not ask. ``limit_bytes``: the device's
    ``memory_stats()["bytes_limit"]``, None where the backend reports
    none. The pair is counted twice, once more for what the lean policy
    already stacks a layer and for one layer's working set, and a tenth
    of the chip stays free."""
    if name is not None:
        return name
    if not limit_bytes:
        return DEFAULT_REMAT_POLICY
    fits = step_bytes + 2 * kept_bytes <= limit_bytes - limit_bytes // 10
    return RICH_REMAT_POLICY if fits else DEFAULT_REMAT_POLICY


def resolve_remat_policy(name: Optional[str] = None):
    """Policy name -> jax.checkpoint policy callable; ``None`` is the
    default's."""
    if name is None:
        name = DEFAULT_REMAT_POLICY
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


def validate_remat_policy(name: Optional[str]) -> None:
    """Raise ValueError for unknown policy names (config __post_init__
    hook); resolution itself is deferred to model build time."""
    resolve_remat_policy(name)
