"""The plain reference of Granite 4.0-H with routed experts
(``granitemoehybrid``, ``num_local_experts`` > 0: granite-4.0-h-small):
Mamba-2 state-space layers with a few NoPE attention layers among them,
and after every layer of either kind a top-k softmax router over routed
experts beside a shared MLP, in ``jax.numpy`` and float32.

No kernel, no cache, no chunk, no capacity, no batching of requests, and
no import from the package under test. It reads ``weights(name,
layer=None, expert=None)``: the published checkpoint's tensors in float32
and in the checkpoint's orientation (a projection is ``[out, in]``;
``families/granite_moe_hybrid.py`` ``published``): ``embedding [V, H]``
(also the head: ``tie_word_embeddings``), ``final_norm [H]``; a layer's
``input_norm``, ``post_norm``, ``router [E, H]`` over all ``E`` published
experts, ``input_linear [2I, H]`` (gate rows, then up rows) and
``output_linear [H, I]`` an expert at a time by the expert's published
index, ``shared_input_linear [2Is, H]`` and ``shared_output_linear [H,
Is]``; a mamba layer's ``in_proj [2 d_inner + 2 N + heads, H]`` (rows ``z |
x | B | C | dt``), ``conv_weight [d_inner + 2 N, 1, d_conv]``,
``conv_bias``, ``A_log``, ``D``, ``dt_bias [heads]``, ``mamba_norm
[d_inner]``, ``out_proj [H, d_inner]``; an attention layer's ``q_proj``,
``k_proj``, ``v_proj``, ``o_proj``. Sizes and constants come from the
configuration file's keys; ``config["layer_types"]`` names each layer's
mixer. Everything runs under ``jax.default_matmul_precision("highest")``.

Trunk: ``x0 = embedding_multiplier * embed(ids)``; a layer is ``x <- x +
residual_multiplier * mixer(norm(x))``, ``x <- x + residual_multiplier *
(routed(norm(x)) + shared(norm(x)))``; logits ``= (norm(x) E^T) /
logits_scaling``. ``norm(u) = u / sqrt(mean(u^2) + eps) * w``.

``mamba`` (Mamba-2, one group): ``[z | xBC | dt] = W_inproj h``; ``xBC_t =
silu(sum_k w[:, k] xBC_{t - (d_conv - 1) + k} + b)``, zeros before position
0; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``S_t =
exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``, ``y_t = S_t C_t + D x_t``,
**position by position** (a ``lax.scan`` over the sequence: no chunk, no
cumulative sum of decays); ``out = W_outproj (norm_{d_inner}(y * silu(z)) *
w_norm)``, the gate before the one norm over all of ``d_inner``.

``attention``: ``q, k, v`` without bias or rotary embedding (NoPE), ``G``
K/V heads each read by ``N / G`` query heads, ``softmax(q k^T *
attention_multiplier)`` over the causal positions in blocks of queries.

``routed``: ``l = W_r h`` over all ``E``; the ``num_experts_per_tok``
largest of ``l`` (equal: the lower index); ``g = softmax`` over those
logits alone; ``sum_e g_e W_out,e (silu(a_e) * b_e)`` with ``[a_e; b_e] =
W_in,e h``. ``shared``: the same GLU at ``shared_intermediate_size``,
unweighted, on every row.

**The chip's share** (``share`` in the configuration file, absent for the
whole model): this device holds the experts ``first_expert ..
first_expert + num_local_experts - 1`` of the
``num_local_experts_published`` that the router scores. The sum over the
chosen experts then runs over the held ones alone: what an expert held
elsewhere would add is left out, and that partial result goes on to the
next layer, as in the program; the shared MLP is whole. The vocabulary's
slice is a smaller vocabulary.

Also returned: each layer's router margin ``[B, L, S]``, the gap between
the last chosen and the first unchosen logit.

Departures from the published description: none in the mathematics;
``mamba_n_groups`` other than 1 and a configuration without routed
experts are refused.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def causal_conv(x, weight, bias):
    """``x [S, C]``, ``weight [C, 1, W]``, ``bias [C]`` -> ``[S, C]``: tap
    ``W - 1`` meets the position itself, tap ``k`` the position ``W - 1 -
    k`` before it."""
    s, taps = x.shape[0], weight.shape[-1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    out = bias[None, :]
    for k in range(taps):
        out = out + weight[:, 0, k][None, :] * padded[k:k + s]
    return out


def selective_scan(x, dt, a, b, c, d):
    """``x [S, H, P]``, ``dt [S, H]``, ``a [H]``, ``b, c [S, N]``, ``d
    [H]`` -> ``y [S, H, P]``: the recurrence, one position a step."""
    h, p = x.shape[1:]

    def step(state, row):
        x_t, dt_t, b_t, c_t = row
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.einsum("hpn,n->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((h, p, b.shape[-1]), jnp.float32),
                        (x, dt, b, c))
    return y + d[None, :, None] * x


def mamba_mixer(h, weights, li, config):
    s = h.shape[0]
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    n, inner = config["mamba_d_state"], heads * width
    zxbcdt = h @ weights("in_proj", li).T
    z, xbc, dt = jnp.split(zxbcdt, (inner, 2 * inner + 2 * n), axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, weights("conv_weight", li),
                                  weights("conv_bias", li)))
    x, b, c = jnp.split(xbc, (inner, inner + n), axis=-1)
    dt = jax.nn.softplus(dt + weights("dt_bias", li))
    y = selective_scan(x.reshape(s, heads, width), dt,
                       -jnp.exp(weights("A_log", li)), b, c,
                       weights("D", li))
    y = rms_norm(y.reshape(s, inner) * jax.nn.silu(z),
                 weights("mamba_norm", li), float(config["rms_norm_eps"]))
    return y @ weights("out_proj", li).T


def attention_mixer(h, weights, li, config):
    s = h.shape[0]
    n, g = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // n
    q = (h @ weights("q_proj", li).T).reshape(s, g, n // g, d)
    k = (h @ weights("k_proj", li).T).reshape(s, g, d)
    v = (h @ weights("v_proj", li).T).reshape(s, g, d)
    # blocks of queries against every key: one shape of block whatever its
    # place, so that the eager programs compile once
    q = jnp.pad(q, ((0, -s % QUERY_BLOCK), (0, 0), (0, 0), (0, 0)))
    at = jnp.arange(s)
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        scores = jnp.einsum("tgrd,sgd->tgrs", q[lo:lo + QUERY_BLOCK], k
                            ) * float(config["attention_multiplier"])
        seen = at[None, :] <= (lo + jnp.arange(QUERY_BLOCK))[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, None, :], scores, -jnp.inf), -1)
        out.append(jnp.einsum("tgrs,sgd->tgrd", probs, v))
    return jnp.concatenate(out)[:s].reshape(s, n * d) @ weights(
        "o_proj", li).T


def glu(x, w_in, w_out):
    gate, up = jnp.split(x @ w_in.T, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out.T


def held_experts(config):
    """The published indices of the routed experts held here."""
    first = int(config.get("share", {}).get("first_expert", 0))
    return range(first, first + int(config["num_local_experts"]))


def route(x, weights, li, config):
    """``(chosen [S, k], gates [S, k], margin [S])``: the chosen experts'
    published indices, a softmax over their logits alone, and the gap to
    the first unchosen logit."""
    top_k = config["num_experts_per_tok"]
    ranked, chosen = jax.lax.top_k(x @ weights("router", li).T, top_k + 1)
    return (chosen[:, :top_k], jax.nn.softmax(ranked[:, :top_k], axis=-1),
            ranked[:, top_k - 1] - ranked[:, top_k])


def feed_forward(x, weights, li, config):
    """``(output, margin [S])`` of one layer's routed experts and shared
    MLP."""
    chosen, gates, margin = route(x, weights, li, config)
    y = glu(x, weights("shared_input_linear", li),
            weights("shared_output_linear", li))
    for e in held_experts(config):
        w = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)   # [S]
        y = y + w[:, None] * glu(x, weights("input_linear", li, e),
                                 weights("output_linear", li, e))
    return y, margin


def forward(weights, tokens, config, positions=None):
    """``(logits [B, S, V] float32, router margins [B, L, S])`` for
    ``tokens [B, S]``; with ``positions`` (ascending indices into ``S``)
    the final norm and the head run on those rows of the last layer's
    output only: ``[B, len(positions), V]``. The logits are handed back
    on the host, a sequence's as soon as they are computed."""
    if not config.get("num_local_experts") or config["mamba_n_groups"] != 1:
        raise ValueError("granite_moe_hybrid_f32 computes the models with "
                         "routed experts and one group of B and C")
    eps, c = float(config["rms_norm_eps"]), float(
        config["residual_multiplier"])
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        table = weights("embedding")
        for seq in tokens:
            x = float(config["embedding_multiplier"]) * table[
                jnp.asarray(seq)]
            seq_margins = []
            for li in range(config["num_hidden_layers"]):
                h = rms_norm(x, weights("input_norm", li), eps)
                mixer = (mamba_mixer if config["layer_types"][li] == "mamba"
                         else attention_mixer)
                x = x + c * mixer(h, weights, li, config)
                h = rms_norm(x, weights("post_norm", li), eps)
                y, margin = feed_forward(h, weights, li, config)
                seq_margins.append(margin)
                x = x + c * y
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = rms_norm(x, weights("final_norm"), eps)
            out.append(np.asarray((x @ table.T)
                                  / float(config["logits_scaling"])))
            margins.append(jnp.stack(seq_margins))
    return np.stack(out), jnp.stack(margins)


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
