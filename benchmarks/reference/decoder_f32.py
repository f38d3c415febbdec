"""The plain reference: a decoder's forward pass in ``jax.numpy`` and float32.

No kernel, no cache, no scan, no batching of requests, and no import from
the package under test. It follows the published descriptions:

* Mistral-7B / Llama family: pre-norm residual blocks of RMSNorm, rotary
  grouped-query attention under a causal mask, and a SwiGLU MLP;
* Mixtral-8x7B (arXiv:2401.04088, section 2.1): the MLP is replaced by
  ``sum_i softmax(top2(x W_g))_i * SwiGLU_i(x)`` — the softmax runs over the
  two selected router logits, and every token reaches both of its experts
  (no capacity, no dropped token).

It reads the system's own parameter tree (the names and layouts listed in
``_LAYOUT``), one layer at a time and for experts one expert at a time,
upcast to float32, so the published widths fit beside the served weights.
On a TPU a float32 matmul runs in reduced precision unless told otherwise:
everything here runs under ``jax.default_matmul_precision("highest")``.

Departures from the papers, each because the system's checkpoint layout
asks for it: rotary embedding in the half-split form (the HuggingFace
layout of these checkpoints), gate and up projections read from one fused
``[hidden, 2, intermediate]`` kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# where each tensor sits in the system's tree: params["params"][...]
_LAYOUT = {
    "embedding": ("model", "embed", "embedding"),        # [V, H]
    "final_norm": ("model", "norm", "scale"),            # [H]
    "lm_head": ("lm_head", "kernel"),                    # [H, V]
    "layers": ("model", "layers", "layer"),              # leaves lead with [L]
}


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _at(layers, index, *path):
    """One layer's (or one expert's) tensor, sliced out and upcast only
    when it is used, so no more than one of them is held in float32."""
    return _f32(_get(layers, path)[index])


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


def attention(x, layers, li, n_heads, n_kv, theta):
    """Causal grouped-query attention over one sequence ``[S, H]``."""
    s = x.shape[0]
    q = (x @ _at(layers, li, "attn", "qkv", "q_kernel")).reshape(
        s, n_heads, -1)
    k = (x @ _at(layers, li, "attn", "qkv", "k_kernel")).reshape(s, n_kv, -1)
    v = (x @ _at(layers, li, "attn", "qkv", "v_kernel")).reshape(s, n_kv, -1)
    d = q.shape[-1]
    q, k = rotary(q, theta), rotary(k, theta)
    rep = n_heads // n_kv                      # query head i reads kv head i // rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qnd,knd->nqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(s, n_heads * d) @ _at(
        layers, li, "attn", "o_proj", "kernel")


def swiglu(x, gate_up, down):
    """``gate_up [H, 2, I]`` (gate first), ``down [I, H]``."""
    gu = jnp.einsum("sh,hki->ski", x, gate_up)
    return (jax.nn.silu(gu[:, 0]) * gu[:, 1]) @ down


def mixtral_block(x, layers, li, top_k):
    """Every expert's SwiGLU, weighted by the softmax over the token's
    ``top_k`` router logits; an unselected expert weighs 0."""
    logits = x @ _at(layers, li, "moe", "router", "kernel")     # [S, E]
    top, idx = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top, axis=-1)                    # [S, K]
    n_experts = logits.shape[-1]
    weight = jnp.sum(jax.nn.one_hot(idx, n_experts) * gates[..., None], 1)
    y = jnp.zeros_like(x)
    for e in range(n_experts):
        y = y + weight[:, e:e + 1] * swiglu(
            x, _at(layers, (li, e), "moe", "experts", "gate_up"),
            _at(layers, (li, e), "moe", "experts", "down"))
    return y, logits


def forward(params, tokens, *, num_heads, num_kv_heads, rope_theta, rms_eps,
            top_k=0):
    """Logits ``[B, S, V]`` (float32) for ``tokens [B, S]``.

    Also returns, for a mixture-of-experts tree, each layer's router
    margin ``[B, L, S]``: the gap between the last selected and the first
    unselected router logit, so the comparison can tell a token whose
    routing is decided by rounding."""
    p = params["params"]
    layers = _get(p, _LAYOUT["layers"])
    depth = jax.tree_util.tree_leaves(layers)[0].shape[0]
    embedding = _f32(_get(p, _LAYOUT["embedding"]))
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        for seq in tokens:
            x = embedding[jnp.asarray(seq)]
            seq_margins = []
            for li in range(depth):
                h = rms_norm(x, _at(layers, li, "input_norm", "scale"),
                             rms_eps)
                x = x + attention(h, layers, li, num_heads, num_kv_heads,
                                  rope_theta)
                h = rms_norm(x, _at(layers, li, "post_norm", "scale"),
                             rms_eps)
                if "moe" in layers:
                    y, logits = mixtral_block(h, layers, li, top_k)
                    ranked = jnp.sort(logits, axis=-1)[:, ::-1]
                    seq_margins.append(ranked[:, top_k - 1] - ranked[:, top_k])
                else:
                    y = swiglu(h, _at(layers, li, "mlp", "gate_up_kernel"),
                               _at(layers, li, "mlp", "down", "kernel"))
                x = x + y
            x = rms_norm(x, _f32(_get(p, _LAYOUT["final_norm"])), rms_eps)
            out.append(x @ _f32(_get(p, _LAYOUT["lm_head"])))
            if seq_margins:
                margins.append(jnp.stack(seq_margins))
    return jnp.stack(out), (jnp.stack(margins) if margins else None)


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
