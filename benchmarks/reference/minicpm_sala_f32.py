"""The plain reference of MiniCPM-SALA: a decoder whose layers alternate
between block-sparse softmax attention and lightning (decayed linear)
attention, in ``jax.numpy`` and float32.

No kernel, no cache, no scan, no batching of requests, and no import from
the package under test. It reads ``weights(name, layer=None)``: the
published checkpoint's tensors in float32 and in the checkpoint's
orientation (``families/minicpm_sala.py`` ``published``): ``embedding [V,
H]``, ``final_norm [H]``, ``lm_head [V, H]``; a layer's ``input_norm``,
``post_norm``, ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``, ``g_proj``
(the output gate), ``q_norm [D]``, ``k_norm [D]``, ``o_norm [D]`` (lightning
layers), ``gate``, ``up``, ``down``. Sizes and constants come from the
configuration file's keys; ``config["mixer_types"]`` names each layer's
mixer and ``config["sparse"]`` holds the selection's sizes. Everything
runs under ``jax.default_matmul_precision("highest")``.

Trunk (``scale_emb``, ``scale_depth``, ``dim_model_base``: the family's
muP). ``x0 = scale_emb * embed(ids)``; a layer is ``x <- x + c *
mixer(norm(x))``, ``x <- x + c * swiglu(norm(x))`` with ``c = scale_depth /
sqrt(published depth)`` (the depth before the configuration's cut:
``reduced.num_hidden_layers.from``); logits ``= head(norm(x) / (hidden_size
/ dim_model_base))``. ``norm(u) = u / sqrt(mean(u^2) + eps) * w``; a
``_head`` norm runs over one head's ``D`` values with one ``w [D]`` shared
by the heads.

``lightning-attn``, per head ``h = 1..N``: ``q, k = rope(norm_head(W_q x)),
rope(norm_head(W_k x))``, ``v = W_v x``; ``S_t = lambda_h S_{t-1} + k_t^T
v_t``; ``o_t = q_t S_t / sqrt(D)``; ``y = W_o (sigmoid(W_g x) *
norm_head(o))``; ``lambda_h = exp(-2^(-8 h / N))``. Computed as a
per-sequence recurrence over blocks of queries.

``minicpm4``: ``q, k = norm_head(W_q x), norm_head(W_k x)`` (no rotary), ``v
= W_v x``, ``G`` K/V heads each read by ``N / G`` query heads. Selection
(``sparse``: kernel, stride, block, topk, init_blocks, window, dense_len):
compressed key ``c_i = mean(k[stride * i : stride * i + kernel])`` over
whole kernels; for a query at ``t``, ``p_h = softmax_i(q_h c_i / sqrt(D))``
over the ``c_i`` that end at or before ``t``; ``r_i`` the sum of ``p_h``
over a group's heads; a block's score the largest ``r_i`` of the kernels
that overlap it; the first ``init_blocks`` blocks and the blocks of the
last ``window`` positions score infinity; the ``topk`` highest causal
blocks are attended (equal scores: the earlier block first), with exact
softmax over their keys at positions ``<= t``. While ``t < dense_len``
every causal position is attended. ``y = W_o (sigmoid(W_g x) * attn)``.
The selection comes from full score rows, a block of queries at a time.

Departure: rotary in the half-split form (the HuggingFace layout), as
``decoder_f32`` has it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rotary(x, theta):
    """x ``[S, N, D]`` at positions 0..S-1; half-split pairing."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def lightning_attention(q, k, v):
    """``q, k, v [S, N, D]`` -> ``[S, N, D]``: the recurrence, a block of
    queries at a time."""
    s, n, d = q.shape
    slope = jnp.exp2(-8.0 * jnp.arange(1, n + 1, dtype=jnp.float32) / n)
    state = jnp.zeros((n, d, d), jnp.float32)
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        qb, kb, vb = (x[lo:lo + QUERY_BLOCK] for x in (q, k, v))
        i = jnp.arange(qb.shape[0], dtype=jnp.float32)
        gap = i[:, None] - i[None, :]
        decay = jnp.where(gap >= 0, jnp.exp(
            -slope[:, None, None] * jnp.maximum(gap, 0.0)), 0.0)
        scores = jnp.einsum("ind,jnd->nij", qb, kb) * decay
        o = jnp.einsum("nij,jne->ine", scores, vb)
        o += jnp.einsum("ind,nde->ine", qb, state) * jnp.exp(
            -slope[None, :] * (i[:, None] + 1.0))[:, :, None]
        left = jnp.exp(-slope[None, :] * (i[-1] - i)[:, None])
        state = (jnp.exp(-slope * qb.shape[0])[:, None, None] * state
                 + jnp.einsum("jnd,jne->nde", kb * left[:, :, None], vb))
        out.append(o)
    return jnp.concatenate(out) / jnp.sqrt(jnp.float32(d))


def selected_blocks(q, k, sp):
    """``q [S, G, R, D]``, ``k [S, G, D]`` -> ``[S, G, NB]`` bool: the
    ``sp["block"]``-position blocks each (position, group) attends."""
    s, g, r, d = q.shape
    kernel, stride, block = sp["kernel"], sp["stride"], sp["block"]
    nb = -(-s // block)
    n_c = max((s - kernel) // stride + 1, 0)
    starts = jnp.arange(n_c) * stride
    inside = starts[:, None] + jnp.arange(kernel)[None, :]     # [NC, kernel]
    c = k[inside].mean(1)                                      # [NC, G, D]
    blocks = jnp.arange(nb)
    overlap = ((starts[None, :] < (blocks[:, None] + 1) * block)
               & (starts[None, :] + kernel > blocks[:, None] * block))
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        t = jnp.arange(lo, min(lo + QUERY_BLOCK, s))
        causal = blocks[None, :] <= (t // block)[:, None]      # [T, NB]
        seen = (starts + kernel - 1)[None, :] <= t[:, None]    # [T, NC]
        scores = jnp.einsum("tgrd,cgd->tgrc", q[lo:lo + QUERY_BLOCK], c
                            ) / jnp.sqrt(jnp.float32(d))
        scores = jnp.where(seen[:, None, None, :], scores, -jnp.inf)
        p = jnp.where(seen[:, None, None, :],
                      jnp.exp(scores - jnp.max(scores, -1, keepdims=True,
                                               initial=-1e30)), 0.0)
        p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        r_i = p.sum(2)                                          # [T, G, NC]
        both = overlap[None, None] & seen[:, None, None, :]
        score = jnp.max(jnp.where(both, r_i[:, :, None, :], -1.0), -1,
                        initial=-1.0)                           # [T, G, NB]
        forced = (blocks[None, :] < sp["init_blocks"]) | (
            blocks[None, :] >= jnp.maximum(t - sp["window"] + 1, 0)[:, None]
            // block)
        score = jnp.where(causal[:, None],
                          jnp.where(forced[:, None], jnp.inf, score),
                          -jnp.inf)
        vals, idx = jax.lax.top_k(score, min(sp["topk"], nb))
        picked = jnp.zeros(score.shape, bool)
        picked = jnp.put_along_axis(picked, idx, vals > -jnp.inf, -1,
                                    inplace=False)
        out.append(jnp.where((t < sp["dense_len"])[:, None, None],
                             causal[:, None], picked))
    return jnp.concatenate(out)


def sparse_attention(q, k, v, sp):
    """``q [S, N, D]``, ``k, v [S, G, D]`` -> ``[S, N, D]``."""
    s, n, d = q.shape
    g = k.shape[1]
    qg = q.reshape(s, g, n // g, d)
    sel = selected_blocks(qg, k, sp)
    pos = jnp.arange(s)
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        # every block of queries against all S keys under the mask: one
        # shape for all blocks, where keys cut at the block's end would be
        # a program to compile for each
        hi = min(lo + QUERY_BLOCK, s)
        allowed = (jnp.repeat(sel[lo:hi], sp["block"], -1)[..., :s]
                   & (pos[None, :] <= pos[lo:hi, None])[:, None])
        scores = jnp.einsum("tgrd,sgd->tgrs", qg[lo:hi], k
                            ) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(
            jnp.where(allowed[:, :, None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("tgrs,sgd->tgrd", probs, v))
    return jnp.concatenate(out).reshape(s, n, d)


def published_depth(config) -> int:
    cut = config.get("reduced", {}).get("num_hidden_layers")
    return int(cut["from"]) if cut else int(config["num_hidden_layers"])


def layer(x, weights, li, config):
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    d = int(config["head_dim"])
    s = x.shape[0]
    c = float(config["scale_depth"]) / published_depth(config) ** 0.5
    h = rms_norm(x, weights("input_norm", li), eps)
    q = rms_norm((h @ weights("q_proj", li).T).reshape(s, -1, d),
                 weights("q_norm", li), eps)
    k = rms_norm((h @ weights("k_proj", li).T).reshape(s, -1, d),
                 weights("k_norm", li), eps)
    v = (h @ weights("v_proj", li).T).reshape(s, -1, d)
    if config["mixer_types"][li] == "lightning-attn":
        if config["lightning_use_rope"]:
            q, k = rotary(q, theta), rotary(k, theta)
        attn = rms_norm(lightning_attention(q, k, v), weights("o_norm", li),
                        eps)
    else:
        if config["attn_use_rope"]:
            q, k = rotary(q, theta), rotary(k, theta)
        attn = sparse_attention(q, k, v, config["sparse"])
    gated = jax.nn.sigmoid(h @ weights("g_proj", li).T) * attn.reshape(s, -1)
    x = x + c * (gated @ weights("o_proj", li).T)
    h = rms_norm(x, weights("post_norm", li), eps)
    gate, up = h @ weights("gate", li).T, h @ weights("up", li).T
    return x + c * ((jax.nn.silu(gate) * up) @ weights("down", li).T)


def forward(weights, tokens, config, positions=None):
    """``(logits [B, S, V] float32, None)`` for ``tokens [B, S]``; with
    ``positions`` (ascending indices into ``S``) the final norm and the
    head run on those rows of the last layer's output only: ``[B,
    len(positions), V]``."""
    out = []
    with jax.default_matmul_precision("highest"):
        for seq in tokens:
            x = float(config["scale_emb"]) * weights("embedding")[
                jnp.asarray(seq)]
            for li in range(config["num_hidden_layers"]):
                x = layer(x, weights, li, config)
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = rms_norm(x, weights("final_norm"),
                         float(config["rms_norm_eps"]))
            x = x / (config["hidden_size"] / config["dim_model_base"])
            out.append(x @ weights("lm_head").T)
    return jnp.stack(out), None


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
