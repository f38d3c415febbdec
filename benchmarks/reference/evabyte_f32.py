"""The plain reference of EvaByte: a byte-level decoder with EVA chunked
linearized attention, in ``jax.numpy`` and float32.

No kernel, no cache, no scan, no batching of requests, and no import from
the package under test. It reads ``weights(name, layer=None)``: the
published checkpoint's tensors in float32 and in the checkpoint's
orientation (``families/evabyte.py`` ``published``): ``embedding [V, H]``,
``final_norm [H]``, ``lm_head [P * V, H]``; a layer's ``input_norm``,
``post_norm`` (the checkpoint's ``w``: the norm multiplies by ``1 + w``),
``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``, ``gate``, ``up``, ``down``,
``phi [N, D]`` and ``mu [N, D]``. Sizes and constants come from the
configuration file's published keys. Everything runs under
``jax.default_matmul_precision("highest")``; ``fp32_skip_add`` and
``mixedp_attn`` say where the released code computes in float32 (the
residual adds; the softmax): float32 throughout covers both.

The layer. Pre-norm decoder: ``h = x + Attn(norm1(x))``, ``y = h +
MLP(norm2(h))``, ``MLP(u) = down(silu(gate u) * (up u))``, no biases.
``norm(u) = u / sqrt(mean(u^2) + 1e-5) * (1 + w)``
(``norm_add_unit_offset``), also before the head. Head: one matrix
``[8 * 320, 4096]``; output ``j`` of 8 at position ``t`` predicts byte
``t + 1 + j``; logits float32 (``fp32_logits``); embeddings not tied.
Attention, per head, ``d`` = 128, ``s`` = d^-1/2, ``W`` = 2048, ``C`` = 16,
rotary (theta 1e5, whole head) on q and k by absolute position:

- chunk ``j`` = positions ``[jC, (j+1)C)``; learned per-head vectors
  ``phi``, ``mu`` in R^d (``adaptive_phi``, ``adaptive_mu_k``);
  ``a_m = softmax over m in chunk j of (s * k_m . phi)``;
  ``ktil_j = sum_m a_m k_m + mu``; ``vtil_j = sum_m a_m v_m``.
- for query ``i`` in window ``w = floor(i / W)``: ``E = {m : wW <= m <= i}``
  (exact), ``S = {j : (j+1)C <= wW}`` (every chunk of every earlier
  window); ``o_i = (sum_{m in E} e^{s q_i.k_m} v_m + sum_{j in S}
  e^{s q_i.ktil_j} vtil_j) / (sum_E e^{s q_i.k_m} + sum_S e^{s q_i.ktil_j})``.

This is EVA (Zheng et al., ICLR 2023, arXiv:2302.04542: exact attention on
the query's own window, one control variate per chunk elsewhere, one
shared normaliser) in the deterministic form of EvaByte's released code,
where learned ``phi`` and ``mu`` stand where the paper samples.

Assumed, since the catalog has the sizes and not the code (the
configuration's ``assumed`` lists the same): the pooling logits carry the
same scale ``s``; keys are pooled after rotary; ``mu`` is added to the
pooled key and not to the value; a chunk of the query's own window is
attended exactly and never through its summary. Departure: rotary in the
half-split form (the HuggingFace layout), as ``decoder_f32`` has it.

Computed in blocks: a window of queries at a time against its own window's
keys and the summaries of the windows before it, so that 4,384 positions
at 32 heads never hold a ``[S, S]`` score matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def unit_offset_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def rotary(x, theta):
    """x ``[S, N, D]`` at positions 0..S-1; half-split pairing."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def eva_attention(q, k, v, phi, mu, window, chunk):
    """``q``, ``k``, ``v`` ``[S, N, D]`` after rotary -> ``[S, N, D]``."""
    s, n, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    out = []
    ktil, vtil = [], []                     # summaries of the windows before
    for lo in range(0, s, window):
        qw, kw, vw = (x[lo:lo + window] for x in (q, k, v))
        length = qw.shape[0]
        exact = jnp.einsum("qnd,knd->nqk", qw, kw) * scale
        causal = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]
        scores = [jnp.where(causal[None], exact, -jnp.inf)]
        values = [vw]
        if ktil:
            kt, vt = jnp.concatenate(ktil), jnp.concatenate(vtil)
            scores.append(jnp.einsum("qnd,jnd->nqj", qw, kt) * scale)
            values.append(vt)
        probs = jax.nn.softmax(jnp.concatenate(scores, axis=-1), axis=-1)
        out.append(jnp.einsum("nqk,knd->qnd", probs,
                              jnp.concatenate(values)))
        if length == window:                # a whole window: summarise it
            kc = kw.reshape(window // chunk, chunk, n, d)
            vc = vw.reshape(window // chunk, chunk, n, d)
            a = jax.nn.softmax(
                jnp.einsum("jcnd,nd->jcn", kc, phi) * scale, axis=1)
            ktil.append(jnp.einsum("jcn,jcnd->jnd", a, kc) + mu[None])
            vtil.append(jnp.einsum("jcn,jcnd->jnd", a, vc))
    return jnp.concatenate(out)


def layer(x, weights, li, config):
    heads = config["num_attention_heads"]
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    s = x.shape[0]
    h = unit_offset_norm(x, weights("input_norm", li), eps)
    q = (h @ weights("q_proj", li).T).reshape(s, heads, -1)
    k = (h @ weights("k_proj", li).T).reshape(s, heads, -1)
    v = (h @ weights("v_proj", li).T).reshape(s, heads, -1)
    attn = eva_attention(rotary(q, theta), rotary(k, theta), v,
                         weights("phi", li), weights("mu", li),
                         int(config["window_size"]),
                         int(config["chunk_size"]))
    x = x + attn.reshape(s, -1) @ weights("o_proj", li).T
    h = unit_offset_norm(x, weights("post_norm", li), eps)
    gate, up = h @ weights("gate", li).T, h @ weights("up", li).T
    return x + (jax.nn.silu(gate) * up) @ weights("down", li).T


def forward_all_heads(weights, tokens, config, positions=None):
    """Logits ``[B, S, P, V]`` (float32) for ``tokens [B, S]``: output
    ``j`` of the ``P = num_pred_heads`` at position ``t`` is over byte
    ``t + 1 + j``. With ``positions`` (ascending indices into ``S``) the
    final norm and the head run on those rows of the last layer's output
    only: ``[B, len(positions), P, V]``."""
    out = []
    with jax.default_matmul_precision("highest"):
        embedding = weights("embedding")
        for seq in tokens:
            x = embedding[jnp.asarray(seq)]
            for li in range(config["num_hidden_layers"]):
                x = layer(x, weights, li, config)
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = unit_offset_norm(x, weights("final_norm"),
                                 float(config["rms_norm_eps"]))
            logits = x @ weights("lm_head").T
            out.append(logits.reshape(x.shape[0], config["num_pred_heads"],
                                      config["vocab_size"]))
    return jnp.stack(out)


def forward(weights, tokens, config, positions=None):
    """The next-byte head's logits ``[B, S, V]``, which serving samples
    (at ``positions`` only, where given), and ``None`` (no router)."""
    return forward_all_heads(weights, tokens, config, positions)[:, :, 0], None


def cross_entropy(logits, labels):
    """Mean next-byte cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
