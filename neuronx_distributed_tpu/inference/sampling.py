"""Token sampling.

Analogue of the reference's ``utils/sampling.py`` (``Sampler:6``: greedy /
top-k / top-p with temperature).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SamplingConfig:
    temperature: float = 1.0
    top_k: int = 0       # 0 = disabled
    top_p: float = 1.0   # 1.0 = disabled
    greedy: bool = False


@dataclass(frozen=True)
class BlockDecoding:
    """How a family that generates by diffusion over blocks decodes: a slot
    runs its block's ``block_length`` positions a pass, each still-masked
    row samples a token and takes as its confidence the probability of
    that token under the distribution it was sampled from, and a pass
    uncovers the rows over ``confidence_threshold`` or, where they are
    fewer than the pass's quota (``block_length`` spread over
    ``denoising_steps`` passes, the remainder on the first ones), the
    quota's most confident (:func:`uncover`). ``mask_token_id`` is what a
    masked row is fed. One frozen value on the family's config
    (``block_decoding``; None for every other family), handed to the
    engine through ``serving_family().block``."""

    block_length: int = 4
    denoising_steps: int = 4
    confidence_threshold: float = 0.9
    mask_token_id: int = 0

    def __post_init__(self):
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} wants 1 to "
                f"block_length {self.block_length}: every pass uncovers at "
                "least one row")

    def quotas(self) -> Tuple[int, ...]:
        """Rows a pass uncovers at least, by the pass's number in its
        block."""
        base, rest = divmod(self.block_length, self.denoising_steps)
        return tuple(base + (i < rest) for i in range(self.denoising_steps))

    def through(self, positions):
        """The last position of each position's block: what a row attends
        through (ints, NumPy or jnp)."""
        b = self.block_length
        return positions // b * b + (b - 1)


def _shaped(logits: jax.Array, cfg: SamplingConfig) -> jax.Array:
    """The float32 logits a token is drawn from: temperature, top-k and
    top-p applied."""
    logits = logits.astype(jnp.float32)
    if cfg.temperature != 1.0:
        logits = logits / jnp.maximum(cfg.temperature, 1e-6)
    if cfg.top_k > 0:
        kth = jax.lax.top_k(logits, cfg.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if cfg.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest set with cumulative prob >= top_p
        cutoff_idx = jnp.sum(cum < cfg.top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def sample(logits: jax.Array, rng: jax.Array,
           cfg: SamplingConfig = SamplingConfig()) -> jax.Array:
    """Sample token ids from ``[B, V]`` logits."""
    if cfg.greedy:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(rng, _shaped(logits, cfg), axis=-1)


def sample_with_confidence(logits: jax.Array, rng: jax.Array,
                           cfg: SamplingConfig = SamplingConfig()
                           ) -> Tuple[jax.Array, jax.Array]:
    """:func:`sample`, and each drawn token's probability under the
    distribution it was drawn from (float32): the softmax of the shaped
    logits; under ``greedy`` of the logits as they are."""
    if cfg.greedy:
        shaped = logits.astype(jnp.float32)
        tokens = jnp.argmax(shaped, axis=-1)
    else:
        shaped = _shaped(logits, cfg)
        tokens = jax.random.categorical(rng, shaped, axis=-1)
    picked = jnp.take_along_axis(shaped, tokens[..., None], axis=-1)[..., 0]
    return tokens, jnp.exp(picked - jax.nn.logsumexp(shaped, axis=-1))


def uncover(confidence: jax.Array, masked: jax.Array, quota: jax.Array,
            threshold: float) -> Tuple[jax.Array, jax.Array]:
    """The rows one pass uncovers, a block a row of ``confidence`` and
    ``masked [G, B]`` (``quota [G]``, the pass's): the still-masked rows
    whose confidence is over ``threshold`` or, where they are fewer than
    the quota, the quota's most confident masked rows (``lax.top_k``: of
    equal confidences the lower row). Returns ``(by_threshold, by_quota)``
    bool ``[G, B]``, disjoint: a row is counted under the rule that
    uncovered it. A row that is not masked is never uncovered."""
    width = confidence.shape[-1]
    held = jnp.where(masked, confidence.astype(jnp.float32), -jnp.inf)
    over = masked & (held > threshold)
    enough = jnp.sum(over, axis=-1) >= quota
    _, order = jax.lax.top_k(held, width)
    rank = jnp.argsort(order, axis=-1)
    best = masked & (rank < quota[:, None])
    return over & enough[:, None], best & ~enough[:, None]
