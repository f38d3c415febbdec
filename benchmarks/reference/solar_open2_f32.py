"""The plain reference of Solar-Open2 (``model_type: solar_open2``): Kimi
Delta Attention (KDA, arXiv:2510.26692) mixers with a gated NoPE GQA
layer every fourth, and after every mixer sigmoid-routed experts beside
one shared expert, in ``jax.numpy`` and float32.

No kernel, no cache, no chunk, no triangular solve, no capacity, no
batching of requests, and no import from the package under test. It reads
``weights(name, layer=None, expert=None)``: the published checkpoint's
tensors in float32 and in the checkpoint's orientation (a projection is
``[out, in]``; ``families/solar_open2.py`` ``published``): ``embedding
[V, H]``, ``final_norm [H]``, ``lm_head [V, H]``; a layer's
``input_norm``, ``post_norm``, ``router [E, H]`` and ``router_bias [E]``
over all ``E`` published experts, ``gate``/``up``/``down`` an expert at a
time by the expert's published index, ``shared_gate``/``shared_up``/
``shared_down``; a KDA layer's ``q_proj``, ``k_proj``, ``v_proj [N dk,
H]``, ``q_conv``, ``k_conv``, ``v_conv [N dk, 1, W]``, ``f_a_proj [dk,
H]``, ``f_b_proj [N dk, dk]``, ``A_log [N]``, ``dt_bias [N dk]``,
``b_proj [N, H]``, ``g_a_proj [dk, H]``, ``g_b_proj [N dk, dk]``,
``g_b_bias [N dk]``, ``o_norm [dk]``, ``o_proj [H, N dk]``; a GQA
layer's ``q_proj``, ``k_proj``, ``v_proj``, ``g_proj [N d, H]``,
``o_proj``. Sizes and constants come from the configuration file's keys.
Everything runs under ``jax.default_matmul_precision("highest")``.

A layer, for the residual stream ``h``: ``h <- h + mixer(norm(h))``, ``h
<- h + ffn(norm(h))``, ``norm(u) = u / sqrt(mean(u^2) + eps) * w``.

*KDA* (a layer not in ``gqa_layers``), ``x = norm(h)``: ``q, k, v = W x``,
each through a depthwise causal convolution of ``short_conv_kernel_size``
taps (zeros before position 0, no bias) and SiLU; ``q`` and ``k`` divided
by ``sqrt(sum of squares + 1e-6)`` a head, ``q`` times ``dk^-0.5``; ``g =
-exp(A_log[n]) softplus(W_fb (W_fa x) + dt_bias)`` a channel; ``beta =
sigmoid(W_b x)`` a head, doubled where ``kda_allow_neg_eigval``; a head's
state ``S in R^{dk x dv}``, zero at position 0, **position by position**
(a ``lax.scan`` over the sequence): ``S' = exp(g_t)[:, None] S``, ``S = S'
+ beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S^T q_t``; ``out = W_o (norm_dk(o)
w_o * sigmoid(W_gb (W_ga x) + b_g))``.

*GQA* (a layer in ``gqa_layers``): ``q, k, v`` without bias or rotary
embedding, ``G`` K/V heads each read by ``N / G`` query heads, ``softmax(q
k^T / sqrt(d))`` over the causal positions in blocks of queries, ``out =
W_o (attn * sigmoid(W_g x))``, the gate elementwise.

*Feed-forward* (every layer): ``s = sigmoid(W_r x)`` over all ``E``, the
``num_experts_per_tok`` largest of ``s + router_bias`` (equal: the lower
index), ``w = s_chosen / sum(s chosen)`` times ``routed_scaling_factor``,
``sum_chosen w_e swiglu_e(x)`` plus the shared expert's ``swiglu(x)``,
unweighted.

**The chip's share** (``share`` in the configuration file, absent for the
whole model): this device holds the experts ``first_expert ..
first_expert + n_routed_experts - 1`` of the ``n_routed_experts_published``
that the router scores. The sum over the chosen experts then runs over
the held ones alone: what an expert held elsewhere would add is left out,
and that partial result goes on to the next layer, as in the program; the
shared expert is whole. The vocabulary's slice is a smaller vocabulary.

Also returned: each layer's router margin ``[B, L, S]``, the gap between
the last chosen and the first unchosen biased score.

Departures from the published description (the configuration's
``assumed``): the gate of a GQA layer elementwise; the router's scoring a
sigmoid with a selection bias and one group; the L2 norm's epsilon 1e-6;
the decay through ``A_log`` a head and ``dt_bias`` a channel; the low-rank
width of the decay's and the gate's projections the head size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def causal_conv(x, weight):
    """``x [S, C]``, ``weight [C, 1, W]`` -> ``[S, C]``: tap ``W - 1``
    meets the position itself, tap ``k`` the position ``W - 1 - k`` before
    it."""
    s, taps = x.shape[0], weight.shape[-1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(weight[:, 0, k][None, :] * padded[k:k + s]
               for k in range(taps))


def delta_rule(q, k, v, g, beta):
    """``q, k, g [S, N, dk]``, ``v [S, N, dv]``, ``beta [S, N]`` -> ``o [S,
    N, dv]``: the recurrence, one position a step."""

    def step(state, row):
        q_t, k_t, v_t, g_t, b_t = row
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("ncd,nc->nd", state, k_t)
        state = state + k_t[:, :, None] * (b_t[:, None]
                                           * (v_t - read))[:, None, :]
        return state, jnp.einsum("ncd,nc->nd", state, q_t)

    zero = jnp.zeros(q.shape[1:] + v.shape[-1:], jnp.float32)
    return jax.lax.scan(step, zero, (q, k, v, g, beta))[1]


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_mixer(x, weights, li, config):
    lin = config["linear_attn_config"]
    n, d = lin["num_heads"], lin["head_dim"]
    s = x.shape[0]

    def branch(name):
        return jax.nn.silu(causal_conv(
            x @ weights(name + "_proj", li).T,
            weights(name + "_conv", li))).reshape(s, n, d)

    q = l2_norm(branch("q")) * d ** -0.5
    k, v = l2_norm(branch("k")), branch("v")
    g = -jnp.exp(weights("A_log", li))[None, :, None] * jax.nn.softplus(
        (x @ weights("f_a_proj", li).T) @ weights("f_b_proj", li).T
        + weights("dt_bias", li)).reshape(s, n, d)
    beta = jax.nn.sigmoid(x @ weights("b_proj", li).T) * (
        2.0 if config["kda_allow_neg_eigval"] else 1.0)
    o = rms_norm(delta_rule(q, k, v, g, beta), weights("o_norm", li),
                 float(config["rms_norm_eps"]))
    gate = jax.nn.sigmoid(
        (x @ weights("g_a_proj", li).T) @ weights("g_b_proj", li).T
        + weights("g_b_bias", li))
    return (o.reshape(s, n * d) * gate) @ weights("o_proj", li).T


def gqa_mixer(x, weights, li, config):
    n, g, d = (config["num_attention_heads"], config["num_key_value_heads"],
               config["head_dim"])
    s = x.shape[0]
    q = (x @ weights("q_proj", li).T).reshape(s, g, n // g, d)
    k = (x @ weights("k_proj", li).T).reshape(s, g, d)
    v = (x @ weights("v_proj", li).T).reshape(s, g, d)
    # blocks of queries against every key: one shape of block whatever its
    # place, so that the eager programs compile once
    q = jnp.pad(q, ((0, -s % QUERY_BLOCK), (0, 0), (0, 0), (0, 0)))
    at = jnp.arange(s)
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        scores = jnp.einsum("tgrd,sgd->tgrs", q[lo:lo + QUERY_BLOCK], k
                            ) / jnp.sqrt(jnp.float32(d))
        seen = at[None, :] <= (lo + jnp.arange(QUERY_BLOCK))[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, None, :], scores, -jnp.inf), -1)
        out.append(jnp.einsum("tgrs,sgd->tgrd", probs, v))
    a = jnp.concatenate(out)[:s].reshape(s, n * d)
    if config["use_gqa_gate"]:
        a = a * jax.nn.sigmoid(x @ weights("g_proj", li).T)
    return a @ weights("o_proj", li).T


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def held_experts(config):
    """The published indices of the routed experts held here."""
    first = int(config.get("share", {}).get("first_expert", 0))
    return range(first, first + int(config["n_routed_experts"]))


def feed_forward(x, weights, li, config):
    """``(output, margin [S])`` of one layer's experts."""
    top_k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ weights("router", li).T)          # [S, E]
    ranked, chosen = jax.lax.top_k(scores + weights("router_bias", li),
                                   top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    chosen = chosen[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    picked = picked * float(config["routed_scaling_factor"])
    y = jnp.zeros_like(x)
    for e in held_experts(config):
        w = jnp.sum(jnp.where(chosen == e, picked, 0.0), axis=-1)  # [S]
        y = y + w[:, None] * swiglu(x, weights("gate", li, e),
                                    weights("up", li, e),
                                    weights("down", li, e))
    for _ in range(config["n_shared_experts"]):
        y = y + swiglu(x, weights("shared_gate", li),
                       weights("shared_up", li), weights("shared_down", li))
    return y, margin


def forward(weights, tokens, config, positions=None):
    """``(logits [B, S, V] float32, router margins [B, L, S])`` for
    ``tokens [B, S]``; with ``positions`` (ascending indices into ``S``)
    the final norm and the head run on those rows of the last layer's
    output only: ``[B, len(positions), V]``. The logits are handed back
    on the host, a sequence's as soon as they are computed."""
    if (config["use_rope"] or config["kda_use_full_proj"]
            or config["first_k_dense_replace"]
            or config["n_shared_experts"] != 1
            or config["tie_word_embeddings"]):
        raise ValueError("solar_open2_f32 computes NoPE GQA layers, the "
                         "low-rank decay and gate, experts in every layer "
                         "beside one shared expert and an untied head")
    eps = float(config["rms_norm_eps"])
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        for seq in tokens:
            x = weights("embedding")[jnp.asarray(seq)]
            seq_margins = []
            for li in range(config["num_hidden_layers"]):
                h = rms_norm(x, weights("input_norm", li), eps)
                mixer = gqa_mixer if li in config["gqa_layers"] else kda_mixer
                x = x + mixer(h, weights, li, config)
                h = rms_norm(x, weights("post_norm", li), eps)
                y, margin = feed_forward(h, weights, li, config)
                seq_margins.append(margin)
                x = x + y
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = rms_norm(x, weights("final_norm"), eps)
            out.append(np.asarray(jnp.einsum("sh,vh->sv", x,
                                             weights("lm_head"))))
            margins.append(jnp.stack(seq_margins))
    return np.stack(out), jnp.stack(margins)


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
