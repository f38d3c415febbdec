"""The plain reference of Xing4.0 (``xing4_0``; Xing4.0-29B-A4B): latent
(MLA) attention under YaRN, leading dense layers and then expert layers
under a sigmoid router with a selection bias, and a residual path of
``hc_mult`` streams a token that every sublayer reads, writes and mixes
by manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606), in ``jax.numpy`` and float32.

No kernel, no cache, no scan, no capacity, no absorption of ``kv_b``, no
batching of requests, the streams float32 throughout, the Sinkhorn
iterations a Python loop, and no import from the package under test. It
reads ``weights(name, layer=None, expert=None)``: the published
checkpoint's tensors in float32 and in the checkpoint's orientation (a
projection is ``[out, in]``; ``families/xing4.py`` ``published``). The
attention's and the experts' names are DeepSeek-V3's: ``embedding [V,
H]``, ``final_norm [H]``, ``lm_head`` (read in blocks of
:data:`HEAD_BLOCK` rows of the vocabulary: ``weights("lm_head", k)`` is
rows ``k * HEAD_BLOCK`` on, ``[<= HEAD_BLOCK, H]``: a float32 head of
131,072 rows whole is 1.75 GiB); of a layer ``input_layernorm``,
``post_attention_layernorm``, ``self_attn.q_a_proj [q_lora_rank, H]``,
``self_attn.q_a_layernorm``, ``self_attn.q_b_proj [N * (nope + rope),
q_lora_rank]``, ``self_attn.kv_a_proj_with_mqa [kv_lora_rank + rope,
H]``, ``self_attn.kv_a_layernorm``, ``self_attn.kv_b_proj [N * (nope +
v), kv_lora_rank]`` (a head's rows: its ``nope`` key rows, then its ``v``
value rows), ``self_attn.o_proj [H, N * v]``; a dense layer's
``mlp.gate_proj``, ``mlp.up_proj``, ``mlp.down_proj``; an expert layer's
``mlp.gate [E, H]``, ``mlp.gate.e_score_correction_bias [E]``,
``mlp.experts.gate_proj`` / ``up_proj`` / ``down_proj`` an expert at a
time and ``mlp.shared_experts.gate_proj`` / ``up_proj`` / ``down_proj``.
The mixing's names are this repository's (the checkpoint's are not
known here: the configuration's ``assumed``), for ``s`` in ``hc_attn``,
``hc_ffn``: ``s.phi [2n + n^2, n H]`` (rows: pre, post, then ``H_res``
row by row), ``s.alpha [3]`` (pre, post, res), ``s.bias [2n + n^2]``.
Sizes and constants come from the configuration file's keys. Everything
runs under ``jax.default_matmul_precision("highest")``.

A token's residual is ``X`` in ``R^{n x H}``, ``n = hc_mult``; ``X[i]``
starts as the token's embedding for every ``i``. A sublayer ``F``
(attention, then the feed-forward), with its own ``Phi``, ``alpha``,
``b`` and input norm ``w``::

    v      = vec(X);  r = rsqrt(mean(v^2) + rms_norm_eps)
    m      = Phi (r v)
    H_pre  = sigmoid(a_pre  m[0:n]      + b[0:n])
    H_post = 2 sigmoid(a_post m[n:2n]   + b[n:2n])
    M_0    = exp(clip(a_res mat(m[2n:]) + mat(b[2n:]), clamp_min, clamp_max))
    M_t    = cols(rows(M_{t-1})),  t = 1..hc_sinkhorn_iters;  H_res = M_last
             rows(M)_ij = M_ij / (sum_j M_ij + hc_eps)
             cols(M)_ij = M_ij / (sum_i M_ij + hc_eps)
    u      = sum_i H_pre[i] X[i]
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] F(rms_norm_w(u))

The final norm and the head read ``sum_i X[i]``.

Attention, for ``x = rms_norm(u)``: ``c_q = norm(W_qa x)``; head ``i``'s
query ``W_qb,i c_q = [q_nope (nope), q_rope (rope)]``; ``[c_kv, k_rope] =
W_kva x``, ``c_kv <- norm(c_kv)``; ``k_rope`` (one rotary key for all
heads) and ``q_rope`` rotated by YaRN's frequencies (``rope_scaling``:
with ``f_i = theta^(-2i / rope)`` the pairs that turn more than
``beta_fast`` times in ``original_max_position_embeddings`` keep ``f_i``,
those that turn fewer than ``beta_slow`` times take ``f_i / factor``, a
linear ramp over the pair's index between), cos and sin times
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, ``mscale(f,
m) = 0.1 m ln f + 1``; ``[k_nope,i, v_i] = W_kvb,i c_kv`` expanded for
every position and head; ``p = softmax_{j<=t}((q_nope . k_nope + q_rope .
k_rope) (nope + rope)^-1/2 mscale(factor, mscale_all_dim)^2)``; ``W_o
[sum_j p v]``. Feed-forward: the first ``first_k_dense_replace`` layers
``swiglu(x)``; the others ``s = sigmoid(W_g x)``, the
``num_experts_per_tok`` experts with the largest ``s + b`` (equal: the
lower index), ``g = routed_scaling_factor * s / (sum of the chosen s +
1e-20)``, ``sum_chosen g_e swiglu_e(x) + swiglu_shared(x)``: every token
reaches every expert it chose.

Also returned: each expert layer's router margin ``[B, L_moe, S]``, the
gap between the last chosen and the first unchosen ``s + b``.

Departures from the published description, each under the
configuration's ``assumed``: the read-in and read-out of the streams
(arXiv:2409.19606's), no weight on the statistic ``r``, rows before
columns, ``hc_eps`` in both denominators, scalar ``alpha``; rotary in
the half-split form (the HuggingFace runtime layout); ``n_group =
topk_group = 1`` read as no group limit; the multi-token-prediction
module (``num_nextn_predict_layers``) left out: it does not enter the
next-token logits.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
HEAD_BLOCK = 16384


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dim, theta, rope):
    """``[dim // 2]`` inverse frequencies under ``rope_scaling``."""
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def pair_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair_of(float(rope["beta_fast"]))), 0)
    hi = min(math.ceil(pair_of(float(rope["beta_slow"]))), dim - 1)
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return f / factor * ramp + f * (1 - ramp)


def rotary(x, theta, rope):
    """``x [S, ..., D]`` at positions ``0..S-1``, half-split pairing."""
    s, d = x.shape[0], x.shape[-1]
    factor = float(rope["factor"])
    inv = jnp.asarray(yarn_frequencies(d, theta, rope), jnp.float32)
    amp = (mscale(factor, float(rope["mscale"]))
           / mscale(factor, float(rope["mscale_all_dim"])))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, weights, li, config):
    n = config["num_attention_heads"]
    nope, rope, dv = (config["qk_nope_head_dim"],
                      config["qk_rope_head_dim"], config["v_head_dim"])
    rank = config["kv_lora_rank"]
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    scaling = config["rope_scaling"]
    m = mscale(float(scaling["factor"]), float(scaling["mscale_all_dim"]))
    scale = m * m / math.sqrt(nope + rope)
    s = x.shape[0]

    def w(name):
        return weights("self_attn." + name, li)

    c_q = rms_norm(x @ w("q_a_proj").T, w("q_a_layernorm"), eps)
    q = (c_q @ w("q_b_proj").T).reshape(s, n, nope + rope)
    q_nope, q_rope = q[..., :nope], rotary(q[..., nope:], theta, scaling)
    kv = x @ w("kv_a_proj_with_mqa").T
    c_kv = rms_norm(kv[:, :rank], w("kv_a_layernorm"), eps)
    k_rope = rotary(kv[:, rank:], theta, scaling)              # [S, rope]
    expanded = (c_kv @ w("kv_b_proj").T).reshape(s, n, nope + dv)
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    pos = jnp.arange(s)
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = (jnp.einsum("tnd,snd->tns", q_nope[lo:hi], k_nope)
                  + jnp.einsum("tnd,sd->tns", q_rope[lo:hi], k_rope)
                  ) * jnp.float32(scale)
        causal = pos[None, None, :] <= pos[lo:hi, None, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        out.append(jnp.einsum("tns,snd->tnd", probs, v))
    return jnp.concatenate(out).reshape(s, n * dv) @ w("o_proj").T


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def expert_layer(x, weights, li, config):
    """``(output, margin [S])`` of one expert layer's feed-forward."""
    top_k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ weights("mlp.gate", li).T)       # [S, E]
    biased = scores + weights("mlp.gate.e_score_correction_bias", li)
    ranked, chosen = jax.lax.top_k(biased, top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    chosen = chosen[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * float(config["routed_scaling_factor"])
    y = swiglu(x, *(weights(f"mlp.shared_experts.{p}_proj", li)
                    for p in ("gate", "up", "down")))
    for e in range(config["n_routed_experts"]):
        g = jnp.sum(jnp.where(chosen == e, picked, 0.0), axis=-1)  # [S]
        y = y + g[:, None] * swiglu(
            x, *(weights(f"mlp.experts.{p}_proj", li, e)
                 for p in ("gate", "up", "down")))
    return y, margin


def mixing_maps(X, weights, which, li, config):
    """``(H_pre [S, n], H_post [S, n], H_res [S, n, n])`` of the streams
    ``X [S, n, H]`` for the sublayer ``which``."""
    s, n, _ = X.shape
    v = X.reshape(s, -1)
    r = jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                      + float(config["rms_norm_eps"]))
    m = (r * v) @ weights(which + ".phi", li).T              # [S, 2n + n^2]
    alpha, b = weights(which + ".alpha", li), weights(which + ".bias", li)
    pre = jax.nn.sigmoid(alpha[0] * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(alpha[2] * m[:, 2 * n:] + b[2 * n:],
                         float(config["mhc_h_res_clamp_min"]),
                         float(config["mhc_h_res_clamp_max"]))
                ).reshape(s, n, n)
    eps = float(config["hc_eps"])
    for _ in range(int(config["hc_sinkhorn_iters"])):
        M = M / (M.sum(axis=-1, keepdims=True) + eps)            # rows
        M = M / (M.sum(axis=-2, keepdims=True) + eps)            # columns
    return pre, post, M


def sublayer(X, weights, which, norm, li, config, block):
    """``(X', what the block returned beside its output)``."""
    pre, post, res = mixing_maps(X, weights, which, li, config)
    u = jnp.einsum("sn,snh->sh", pre, X)
    y, beside = block(rms_norm(u, weights(norm, li),
                               float(config["rms_norm_eps"])))
    return (jnp.einsum("sij,sjh->sih", res, X)
            + post[:, :, None] * y[:, None, :]), beside


def head(x, weights, vocab):
    """``x @ lm_head.T`` on the host, ``HEAD_BLOCK`` columns at a time."""
    return np.concatenate([
        np.asarray(jnp.einsum("sh,vh->sv", x, weights("lm_head", k)))
        for k in range(-(-vocab // HEAD_BLOCK))], axis=-1)


def forward(weights, tokens, config, positions=None):
    """``(logits [B, S, V] float32, router margins [B, L_moe, S])`` for
    ``tokens [B, S]``; with ``positions`` (ascending indices into ``S``)
    the final norm and the head run on those rows of the last layer's
    output only: ``[B, len(positions), V]``. The logits are handed back
    on the host, a sequence's and a block of the head's columns as soon
    as they are computed."""
    eps = float(config["rms_norm_eps"])
    dense = int(config["first_k_dense_replace"])
    n = int(config["hc_mult"])
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        for seq in tokens:
            x = weights("embedding")[jnp.asarray(seq)]
            X = jnp.tile(x[:, None, :], (1, n, 1))              # [S, n, H]
            seq_margins = []
            for li in range(config["num_hidden_layers"]):
                X, _ = sublayer(
                    X, weights, "hc_attn", "input_layernorm", li, config,
                    lambda h: (attention(h, weights, li, config), None))
                if li < dense:
                    def feed_forward(h):
                        return swiglu(h, *(weights(f"mlp.{p}_proj", li)
                                           for p in ("gate", "up", "down"))
                                      ), None
                else:
                    def feed_forward(h):
                        return expert_layer(h, weights, li, config)
                X, margin = sublayer(
                    X, weights, "hc_ffn", "post_attention_layernorm", li,
                    config, feed_forward)
                if margin is not None:
                    seq_margins.append(margin)
            x = X.sum(axis=1)
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = rms_norm(x, weights("final_norm"), eps)
            out.append(head(x, weights, int(config["vocab_size"])))
            if seq_margins:
                margins.append(jnp.stack(seq_margins))
    return np.stack(out), (jnp.stack(margins) if margins else None)


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
