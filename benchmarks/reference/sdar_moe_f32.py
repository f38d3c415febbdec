"""The plain reference of SDAR (``sdar_moe``): a decoder that generates by
diffusion over blocks, in ``jax.numpy`` and float32.

No kernel, no cache, no scan, no batching of requests, and no import from
the package under test. Its equations, for a sequence of positions
``0..S-1`` in blocks of ``B``, the configuration's ``block_length`` (an
assumed key: the published config gives none):

* Layer, pre-norm: ``h += Attn(RMSNorm(h))``, ``h += MoE(RMSNorm(h))``.
* Attention: ``q = W_q x`` (heads of ``head_dim``), ``k = W_k x``,
  ``v = W_v x`` (the K/V heads), an RMSNorm over each head's values of
  ``q`` and of ``k`` before rotary (the Qwen3 layer that SDAR continues
  from), rotary on the row's own position in the half-split form at
  ``rope_theta``, scores over ``sqrt(head_dim)``, softmax, ``W_o``.
* **The mask.** With ``b(p) = p // B``, row ``i`` attends row ``j`` iff
  ``b(j) <= b(i)``: causal between blocks, whole inside one. The same
  mask holds over the prompt.
* MoE: ``l = W_r x`` in float32 over all the experts, ``p = softmax(l)``,
  the ``num_experts_per_tok`` largest (of equal ones the lower index),
  gates ``p / sum of the chosen`` (``norm_topk_prob``); an expert is
  ``W_down(silu(W_gate x) * W_up x)``; no shared term; every token reaches
  every expert it chose (no capacity).

It reads ``weights(name, layer=None, expert=None)``: the published
checkpoint's tensors (the Qwen3-MoE layout) in float32 and in the
checkpoint's orientation (a projection is ``[out, in]``): ``embedding``,
``final_norm``, ``lm_head``; a layer's ``input_norm``, ``post_norm``,
``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``, ``q_norm``, ``k_norm``,
``router``; ``gate``, ``up`` and ``down`` of one of its experts, an expert
at a time. Attention runs in blocks of queries so that a context of
thousands fits. Everything runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: query rows a product of the attention (``[heads, rows, S]`` scores)
QUERY_ROWS = 256


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotary(x, theta):
    """x ``[S, N, D]`` at positions 0..S-1; half-split pairing."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, weights, li, config):
    """Grouped-query attention over one sequence ``[S, H]`` under the
    block-causal mask, a block of queries at a time."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    b = int(config["block_length"])
    s = x.shape[0]
    q = (x @ weights("q_proj", li).T).reshape(s, heads, -1)
    k = (x @ weights("k_proj", li).T).reshape(s, kv, -1)
    v = (x @ weights("v_proj", li).T).reshape(s, kv, -1)
    d = q.shape[-1]
    q = rotary(rms_norm(q, weights("q_norm", li), eps), theta)
    k = rotary(rms_norm(k, weights("k_norm", li), eps), theta)
    rep = heads // kv                      # query head i reads kv head i // rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    block_of = jnp.arange(s) // b
    out = []
    for lo in range(0, s, QUERY_ROWS):
        rows = slice(lo, min(lo + QUERY_ROWS, s))
        scores = jnp.einsum("qnd,knd->nqk", q[rows], k) / jnp.sqrt(
            jnp.float32(d))
        seen = block_of[None, :] <= block_of[rows, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        out.append(jnp.einsum("nqk,knd->qnd",
                              jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out).reshape(s, heads * d) @ weights(
        "o_proj", li).T


def expert(x, weights, li, e):
    """``down(silu(gate(x)) * up(x))`` of one expert."""
    return (jax.nn.silu(x @ weights("gate", li, e).T)
            * (x @ weights("up", li, e).T)) @ weights("down", li, e).T


def moe(x, weights, li, config):
    """``(output, router margin [S])``: each token's chosen experts
    weighted by their renormalised probabilities, an expert at a time;
    the margin is the gap between the last chosen and the first unchosen
    probability's logit."""
    top_k = int(config["num_experts_per_tok"])
    logits = x @ weights("router", li).T                    # [S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    if config.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(logits.shape[-1]):
        # the expert's index an operand: one program for all of them
        gate = jnp.sum(jnp.where(idx == e, top, 0.0), axis=-1)      # [S]
        y = y + gate[:, None] * expert(x, weights, li, e)
    ranked = jnp.sort(logits, axis=-1)[:, ::-1]
    return y, ranked[:, top_k - 1] - ranked[:, top_k]


def forward(weights, tokens, config, positions=None):
    """Logits ``[B, S, V]`` (float32) for ``tokens [B, S]`` under the
    block-causal mask; ``config`` is the configuration file's dict. With
    ``positions`` (ascending indices into ``S``) the final norm and the
    head run on those rows of the last layer's output only, and the
    logits are ``[B, len(positions), V]``: every position still passes
    every layer. Also returns each layer's router margin ``[B, L, S]``."""
    eps = float(config["rms_norm_eps"])
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        embedding = weights("embedding")
        for seq in tokens:
            x = embedding[jnp.asarray(seq)]
            seq_margins = []
            for li in range(config["num_hidden_layers"]):
                h = rms_norm(x, weights("input_norm", li), eps)
                x = x + attention(h, weights, li, config)
                h = rms_norm(x, weights("post_norm", li), eps)
                y, margin = moe(h, weights, li, config)
                seq_margins.append(margin)
                x = x + y
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = rms_norm(x, weights("final_norm"), eps)
            out.append(x @ weights("lm_head").T)
            margins.append(jnp.stack(seq_margins))
    return jnp.stack(out), jnp.stack(margins)


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
