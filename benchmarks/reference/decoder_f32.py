"""The plain reference: a decoder's forward pass in ``jax.numpy`` and float32.

No kernel, no cache, no scan, no batching of requests, and no import from
the package under test. It follows the published descriptions:

* Mistral-7B / Llama family: pre-norm residual blocks of RMSNorm, rotary
  grouped-query attention under a causal mask, and a SwiGLU MLP;
* Mixtral-8x7B (arXiv:2401.04088, section 2.1): the MLP is replaced by
  ``sum_i softmax(top2(x W_g))_i * SwiGLU_i(x)`` — the softmax runs over the
  two selected router logits, and every token reaches both of its experts
  (no capacity, no dropped token).

It reads ``weights(name, layer=None, expert=None)``: the published
checkpoint's tensors in float32 and in the checkpoint's orientation (a
projection is ``[out, in]``) -- ``embedding``, ``final_norm``, ``lm_head``;
a layer's ``input_norm``, ``post_norm``, ``q_proj``, ``k_proj``, ``v_proj``,
``o_proj``, ``router``; ``gate``, ``up`` and ``down`` of a layer or of one
of its experts -- one layer and one expert at a time, so the published
widths fit beside the served weights. How the system stores them is the
business of ``families/<family>.py`` ``published``; sizes and constants
come from the configuration file's published keys.
On a TPU a float32 matmul runs in reduced precision unless told otherwise:
everything here runs under ``jax.default_matmul_precision("highest")``.

Departure from the papers: rotary embedding in the half-split form (the
HuggingFace layout of these checkpoints).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def attention(x, weights, li, n_heads, n_kv, theta):
    """Causal grouped-query attention over one sequence ``[S, H]``."""
    s = x.shape[0]
    q = (x @ weights("q_proj", li).T).reshape(s, n_heads, -1)
    k = (x @ weights("k_proj", li).T).reshape(s, n_kv, -1)
    v = (x @ weights("v_proj", li).T).reshape(s, n_kv, -1)
    d = q.shape[-1]
    q, k = rotary(q, theta), rotary(k, theta)
    rep = n_heads // n_kv                      # query head i reads kv head i // rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qnd,knd->nqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(s, n_heads * d) @ weights("o_proj", li).T


def swiglu(x, weights, li, expert=None):
    """``down(silu(gate(x)) * up(x))`` of a layer's MLP or of one expert;
    gate and up in one contraction over the two stacked ``[H, 2, I]``."""
    gate_up = jnp.stack([weights("gate", li, expert).T,
                         weights("up", li, expert).T], axis=1)
    gu = jnp.einsum("sh,hki->ski", x, gate_up)
    return (jax.nn.silu(gu[:, 0]) * gu[:, 1]) @ weights("down", li, expert).T


def mixtral_block(x, weights, li, top_k):
    """Every expert's SwiGLU, weighted by the softmax over the token's
    ``top_k`` router logits; an unselected expert weighs 0."""
    logits = x @ weights("router", li).T                    # [S, E]
    top, idx = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top, axis=-1)                    # [S, K]
    n_experts = logits.shape[-1]
    weight = jnp.sum(jax.nn.one_hot(idx, n_experts) * gates[..., None], 1)
    y = jnp.zeros_like(x)
    for e in range(n_experts):
        y = y + weight[:, e:e + 1] * swiglu(x, weights, li, e)
    return y, logits


def forward(weights, tokens, config, positions=None):
    """Logits ``[B, S, V]`` (float32) for ``tokens [B, S]``; ``config`` is
    the configuration file's dict (the published keys). With ``positions``
    (ascending indices into ``S``) the final norm and the head run on
    those rows of the last layer's output only, and the logits are
    ``[B, len(positions), V]``: every position still passes every layer.

    Also returns, for a mixture of experts, each layer's router margin
    ``[B, L, S]``: the gap between the last selected and the first
    unselected router logit, so the comparison can tell a token whose
    routing is decided by rounding."""
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    top_k = int(config.get("num_experts_per_tok", 0))
    sparse = bool(config.get("num_local_experts"))
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        embedding = weights("embedding")
        for seq in tokens:
            x = embedding[jnp.asarray(seq)]
            seq_margins = []
            for li in range(config["num_hidden_layers"]):
                h = rms_norm(x, weights("input_norm", li), eps)
                x = x + attention(h, weights, li, heads, kv_heads, theta)
                h = rms_norm(x, weights("post_norm", li), eps)
                if sparse:
                    y, logits = mixtral_block(h, weights, li, top_k)
                    ranked = jnp.sort(logits, axis=-1)[:, ::-1]
                    seq_margins.append(ranked[:, top_k - 1] - ranked[:, top_k])
                else:
                    y = swiglu(h, weights, li)
                x = x + y
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = rms_norm(x, weights("final_norm"), eps)
            out.append(x @ weights("lm_head").T)
            if seq_margins:
                margins.append(jnp.stack(seq_margins))
    return jnp.stack(out), (jnp.stack(margins) if margins else None)


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
