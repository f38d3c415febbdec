"""MoE routers.

Analogue of the reference's ``modules/moe/routing.py`` (``RouterBase:12``,
``RouterTopK:155``, ``RouterSinkhorn:213``, ``GroupLimitedRouter:316``).
Router math runs in fp32 regardless of compute dtype (reference RouterBase
casts to fp32), and every router returns auxiliary losses (load-balance +
router z-loss) for the training objective.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn


def _load_balance_loss(probs: jax.Array, expert_mask: jax.Array) -> jax.Array:
    """Switch/Mixtral-style load-balancing loss: E * Σ_e f_e · p_e where
    ``f_e`` is the fraction of tokens dispatched to expert e and ``p_e`` the
    mean router probability of e. probs: [T, E]; expert_mask: [T, E] (0/1
    over selected experts)."""
    e = probs.shape[-1]
    f = jnp.mean(expert_mask, axis=0)
    p = jnp.mean(probs, axis=0)
    return e * jnp.sum(f * p)


def _z_loss(logits: jax.Array) -> jax.Array:
    """Router z-loss (St-MoE): mean(logsumexp(logits)^2)."""
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)


class RouterBase(nn.Module):
    """fp32 linear router (reference ``RouterBase:12``)."""

    num_experts: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def logits(self, x: jax.Array) -> jax.Array:
        kernel = self.param(
            "kernel",
            nn.with_partitioning(nn.initializers.lecun_normal(),
                                 (None, None)),
            (x.shape[-1], self.num_experts), self.param_dtype)
        # router always computes in fp32 (reference RouterBase)
        return jnp.dot(x.astype(jnp.float32), kernel.astype(jnp.float32))


class RouterTopK(RouterBase):
    """Top-k softmax router (reference ``RouterTopK:155``).

    Returns ``(gates [T, k], indices [T, k], aux)`` where gates are the
    renormalised top-k probabilities, times ``scale`` (a checkpoint's
    ``moe_routed_scaling_factor``).
    """

    top_k: int = 2
    norm_topk: bool = True
    scale: float = 1.0

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array, Dict]:
        logits = self.logits(x)  # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, self.top_k)
        if self.norm_topk:
            gates = gates / jnp.maximum(
                jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
        if self.scale != 1.0:
            gates = gates * self.scale
        mask = jnp.sum(jax.nn.one_hot(idx, self.num_experts,
                                      dtype=jnp.float32), axis=1)
        aux = {"load_balance_loss": _load_balance_loss(probs, mask),
               "z_loss": _z_loss(logits)}
        return gates.astype(jnp.float32), idx, aux


class RouterSigmoid(RouterBase):
    """Sigmoid router with a selection bias (DeepSeek-V3's ``noaux_tc``,
    GLM-4.x): every expert scores ``s_e = sigmoid(logit_e)`` on its own;
    the ``top_k`` largest ``s_e + bias_e`` are chosen (``bias``, the
    load-balancing correction, is a parameter that takes no gradient from
    the task) and weighed by ``s_e`` alone, normalised over the chosen
    (the checkpoints' ``norm_topk_prob``) and times ``scale``
    (``routed_scaling_factor``). Equal scores take the lower expert
    index, as ``lax.top_k`` does. With ``n_group`` > 1 the choice is
    limited by groups (DeepSeek-V3): the experts are ``n_group`` runs of
    consecutive indices, a group scores the sum of its two largest ``s_e +
    bias_e``, the ``topk_group`` highest groups stay and the others'
    biased scores are ``-inf`` ahead of the choice; ``n_group`` 1 limits
    nothing and is the router without groups to the bit."""

    top_k: int = 2
    scale: float = 1.0
    n_group: int = 1
    topk_group: int = 1

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array, Dict]:
        logits = self.logits(x)  # [T, E]
        bias = self.param(
            "bias", nn.with_partitioning(nn.initializers.zeros_init(),
                                         (None,)),
            (self.num_experts,), jnp.float32)
        scores = jax.nn.sigmoid(logits)
        biased = scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
        if self.n_group > 1:
            biased = self.limit_to_groups(biased)
        _, idx = jax.lax.top_k(biased, self.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        gates = gates * self.scale
        mask = jnp.sum(jax.nn.one_hot(idx, self.num_experts,
                                      dtype=jnp.float32), axis=1)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        aux = {"load_balance_loss": _load_balance_loss(probs, mask),
               "z_loss": _z_loss(logits)}
        return gates.astype(jnp.float32), idx, aux

    @nn.nowrap
    def limit_to_groups(self, biased: jax.Array) -> jax.Array:
        """``biased [T, E]`` with the experts of every group but the
        ``topk_group`` highest at ``-inf`` (equal groups: the lower)."""
        per, rest = divmod(self.num_experts, self.n_group)
        if rest or per < 2 or not 0 < self.topk_group <= self.n_group:
            raise ValueError(
                f"RouterSigmoid: {self.num_experts} experts are no "
                f"{self.n_group} groups of two or more, or topk_group "
                f"{self.topk_group} is none of them")
        if self.top_k > self.topk_group * per:
            raise ValueError(
                f"RouterSigmoid: top_k {self.top_k} exceeds the "
                f"{self.topk_group * per} experts of topk_group groups")
        grouped = biased.reshape(biased.shape[:-1] + (self.n_group, per))
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, stay = jax.lax.top_k(group_score, self.topk_group)
        kept = jnp.sum(jax.nn.one_hot(stay, self.n_group, dtype=jnp.int32),
                       axis=-2) > 0
        return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
            biased.shape)


class RouterSoftmaxBias(RouterBase):
    """Softmax router with a selection bias over slots that need not all
    be experts (LongCat-Flash): ``p = softmax(logits)`` over every slot;
    the ``top_k`` largest ``p + bias`` are chosen (``bias``, the
    load-balancing correction, takes no gradient from the task; equal
    scores take the lower index, as ``lax.top_k`` does) and weighed by
    ``p`` alone, **not** normalised over the chosen, times ``scale``
    (``routed_scaling_factor``). What a slot is (an expert held here, one
    held elsewhere, an identity expert) is :class:`.model.MoE`'s business:
    ``num_experts`` here counts the slots."""

    top_k: int = 2
    scale: float = 1.0

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array, Dict]:
        logits = self.logits(x)  # [T, slots]
        bias = self.param(
            "bias", nn.with_partitioning(nn.initializers.zeros_init(),
                                         (None,)),
            (self.num_experts,), jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias.astype(jnp.float32)),
            self.top_k)
        gates = jnp.take_along_axis(probs, idx, axis=-1) * self.scale
        mask = jnp.sum(jax.nn.one_hot(idx, self.num_experts,
                                      dtype=jnp.float32), axis=1)
        aux = {"load_balance_loss": _load_balance_loss(probs, mask),
               "z_loss": _z_loss(logits)}
        return gates.astype(jnp.float32), idx, aux


class RouterSinkhorn(RouterBase):
    """Sinkhorn-balanced top-1 router (reference ``RouterSinkhorn:213``):
    iteratively normalise the token×expert matrix toward doubly-stochastic
    before the argmax, equalising expert load; gates come from the raw
    softmax (straight-through style)."""

    num_iters: int = 4

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array, Dict]:
        logits = self.logits(x)
        probs = jax.nn.softmax(logits, axis=-1)

        pi = jnp.exp(logits - jax.nn.logsumexp(logits))

        def sinkhorn_iter(pi, _):
            pi = pi / jnp.maximum(jnp.sum(pi, axis=0, keepdims=True), 1e-9)
            pi = pi / jnp.maximum(jnp.sum(pi, axis=1, keepdims=True), 1e-9)
            return pi, None

        pi, _ = jax.lax.scan(sinkhorn_iter, pi, None, length=self.num_iters)
        idx = jnp.argmax(pi, axis=-1)[:, None]  # [T, 1]
        gates = jnp.take_along_axis(probs, idx, axis=-1)
        mask = jax.nn.one_hot(idx[:, 0], self.num_experts, dtype=jnp.float32)
        aux = {"load_balance_loss": _load_balance_loss(probs, mask),
               "z_loss": _z_loss(logits)}
        return gates.astype(jnp.float32), idx, aux


class GroupLimitedRouter(RouterBase):
    """DeepSeek-style node-limited routing (reference
    ``GroupLimitedRouter:316``): experts are partitioned into groups (nodes);
    each token first picks its best ``topk_groups`` groups by group score,
    then top-k experts within the allowed groups — bounding cross-node
    dispatch fan-out."""

    top_k: int = 2
    num_groups: int = 2
    topk_groups: int = 1
    norm_topk: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array, Dict]:
        if self.num_experts % self.num_groups != 0:
            raise ValueError("num_experts must divide into num_groups")
        allowed = self.topk_groups * (self.num_experts // self.num_groups)
        if self.top_k > allowed:
            raise ValueError(
                f"top_k {self.top_k} exceeds the {allowed} experts reachable "
                f"through topk_groups={self.topk_groups} (zero-gated -inf "
                "picks would waste expert capacity)")
        logits = self.logits(x)  # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        t = logits.shape[0]
        per_group = self.num_experts // self.num_groups
        grouped = probs.reshape(t, self.num_groups, per_group)
        group_score = jnp.max(grouped, axis=-1)  # [T, G]
        _, top_groups = jax.lax.top_k(group_score, self.topk_groups)
        group_allowed = jnp.sum(
            jax.nn.one_hot(top_groups, self.num_groups, dtype=jnp.float32),
            axis=1)  # [T, G]
        expert_allowed = jnp.repeat(group_allowed, per_group, axis=-1)
        masked = jnp.where(expert_allowed > 0, probs, -jnp.inf)
        gates, idx = jax.lax.top_k(masked, self.top_k)
        gates = jnp.where(jnp.isfinite(gates), gates, 0.0)
        if self.norm_topk:
            gates = gates / jnp.maximum(
                jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
        mask = jnp.sum(jax.nn.one_hot(idx, self.num_experts,
                                      dtype=jnp.float32), axis=1)
        aux = {"load_balance_loss": _load_balance_loss(probs, mask),
               "z_loss": _z_loss(logits)}
        return gates.astype(jnp.float32), idx, aux
