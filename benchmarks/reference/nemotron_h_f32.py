"""The plain reference of Nemotron-H (``nemotron_h``:
NVIDIA-Nemotron-3-Super-120B-A12B): layers of one norm and one block each,
a Mamba-2 mixer with several groups of ``B`` and ``C``, a NoPE attention,
or a LatentMoE feed-forward (a sigmoid router with a selection bias,
ungated squared-ReLU experts that work in a latent, a shared expert on
the row itself), in ``jax.numpy`` and float32.

No kernel, no cache, no chunk, no capacity, no batching of requests, and
no import from the package under test. It reads ``weights(name,
layer=None, expert=None)``: the published checkpoint's tensors in float32
and in the checkpoint's orientation (a projection is ``[out, in]``;
``families/nemotron_h.py`` ``published``): ``embedding [V, H]``,
``final_norm [H]``, ``lm_head [V, H]`` (untied); of the published layer
``layer`` its ``norm [H]`` and, by its letter in
``config["hybrid_override_pattern"]``:

* ``M``: ``in_proj [2 d_inner + 2 G N + heads, H]`` (rows ``z | x | B | C
  | dt``, ``B`` and ``C`` group by group), ``conv_weight [d_inner + 2 G N,
  1, d_conv]``, ``conv_bias``, ``A_log``, ``D``, ``dt_bias [heads]``,
  ``mamba_norm [d_inner]``, ``out_proj [H, d_inner]``;
* ``*``: ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``;
* ``E``: ``router [E, H]`` over all ``E`` published experts and
  ``router_bias [E]``, ``latent_in [L, H]`` and ``latent_out [H, L]``,
  ``up_proj [I, L]`` and ``down_proj [L, I]`` an expert at a time by the
  expert's published index, ``shared_up_proj [Is, H]`` and
  ``shared_down_proj [H, Is]``.

Sizes and constants come from the configuration file's keys. Everything
runs under ``jax.default_matmul_precision("highest")``.

Trunk: ``x0 = embed(ids)``; layer ``i`` is ``x <- x + F_i(norm_i(x))``;
logits ``= lm_head norm_f(x)``. ``norm(u) = u / sqrt(mean(u^2) + eps) *
w``, eps ``norm_eps``. Nothing is multiplied.

``M`` (Mamba-2, ``G = n_groups``): ``[z | xBC | dt] = W_in h``; ``xBC_t =
silu(sum_k w[:, k] xBC_{t - (d_conv - 1) + k} + b)``, zeros before position
0; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; head ``j`` of
group ``g = j // (heads / G)``: ``S_t[j] = exp(dt_t[j] A[j]) S_{t-1}[j] +
dt_t[j] x_t[j] (outer) B_t[g]``, ``y_t[j] = S_t[j] C_t[g] + D[j] x_t[j]``,
**position by position** (a ``lax.scan`` over the sequence: no chunk, no
cumulative sum of decays); ``y <- y * silu(z)``, then the RMS norm over
each of the ``G`` groups of ``d_inner / G`` channels (the gate first, one
weight a channel); ``F = W_out y``.

``*``: ``q, k, v`` without bias or rotary embedding (NoPE), ``num_key_
value_heads`` K/V heads each read by their share of the query heads,
``softmax(q k^T / sqrt(head_dim))`` over the causal positions in blocks
of queries.

``E`` (LatentMoE): ``s = sigmoid(W_r h)`` over all ``E``; the
``num_experts_per_tok`` largest of ``s + b`` are chosen (equal: the lower
index); ``g_e = routed_scaling_factor * s_e / sum of the chosen s``; ``l =
W_lat_in h``; ``F = W_lat_out (sum_e g_e D_e relu(U_e l)^2) + D_s
relu(U_s h)^2``.

**The chip's share** (``share`` in the configuration file, absent for the
whole model): this device holds the experts ``first_expert ..
first_expert + n_routed_experts - 1`` of the
``n_routed_experts_published`` that the router scores. The sum over the
chosen experts then runs over the held ones alone: what an expert held
elsewhere would add is left out (before ``W_lat_out``, which is linear),
and that partial result goes on to the next layer, as in the program; the
shared expert is whole. The vocabulary's slice is a smaller vocabulary.

Also returned: each ``E`` layer's router margin ``[B, Le, S]``, the gap
between the last chosen and the first unchosen ``s + b``.

Departures from the published description: the multi-token prediction
module is not computed (``num_nextn_predict_layers`` must be 0: it does
not enter the next-token logits); a ``-`` layer, a group limit on the
router (``n_group``, ``topk_group`` over 1) and a bias are refused.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def group_rms_norm(x, w, eps, groups):
    """``x [S, C]``: the mean square over each of ``groups`` runs of ``C /
    groups`` channels, one weight a channel."""
    s = x.shape[0]
    grouped = x.reshape(s, groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return grouped.reshape(s, -1) * w


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


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
    """``x [S, H, P]``, ``dt [S, H]``, ``a [H]``, ``b, c [S, G, N]``, ``d
    [H]`` -> ``y [S, H, P]``: the recurrence, one position a step, head
    ``j`` reading group ``j // (H / G)``."""
    h, p = x.shape[1:]
    each = h // b.shape[1]

    def step(state, row):
        x_t, dt_t, b_t, c_t = row
        b_h, c_h = (jnp.repeat(v, each, axis=0) for v in (b_t, c_t))
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_h)

    _, y = jax.lax.scan(step, jnp.zeros((h, p, b.shape[-1]), jnp.float32),
                        (x, dt, b, c))
    return y + d[None, :, None] * x


def mamba_mixer(h, weights, li, config):
    s = h.shape[0]
    heads, width = config["mamba_num_heads"], config["mamba_head_dim"]
    n, groups, inner = config["ssm_state_size"], config["n_groups"], \
        heads * width
    zxbcdt = h @ weights("in_proj", li).T
    z, xbc, dt = jnp.split(zxbcdt, (inner, 2 * inner + 2 * groups * n),
                           axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, weights("conv_weight", li),
                                  weights("conv_bias", li)))
    x, b, c = jnp.split(xbc, (inner, inner + groups * n), axis=-1)
    dt = jax.nn.softplus(dt + weights("dt_bias", li))
    y = selective_scan(x.reshape(s, heads, width), dt,
                       -jnp.exp(weights("A_log", li)),
                       b.reshape(s, groups, n), c.reshape(s, groups, n),
                       weights("D", li))
    y = group_rms_norm(y.reshape(s, inner) * jax.nn.silu(z),
                       weights("mamba_norm", li), float(config["norm_eps"]),
                       groups)
    return y @ weights("out_proj", li).T


def attention_mixer(h, weights, li, config):
    s = h.shape[0]
    n, g, d = config["num_attention_heads"], config["num_key_value_heads"], \
        config["head_dim"]
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
                            ) / math.sqrt(d)
        seen = at[None, :] <= (lo + jnp.arange(QUERY_BLOCK))[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, None, :], scores, -jnp.inf), -1)
        out.append(jnp.einsum("tgrs,sgd->tgrd", probs, v))
    return jnp.concatenate(out)[:s].reshape(s, n * d) @ weights(
        "o_proj", li).T


def held_experts(config):
    """The published indices of the routed experts held here."""
    first = int(config.get("share", {}).get("first_expert", 0))
    return range(first, first + int(config["n_routed_experts"]))


def route(x, weights, li, config):
    """``(chosen [S, k], gates [S, k], margin [S])``: the chosen experts'
    published indices (by ``s + b``), their weights (``s`` over the
    chosen's sum, times the factor) and the gap to the first unchosen
    ``s + b``."""
    top_k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ weights("router", li).T)
    ranked, chosen = jax.lax.top_k(scores + weights("router_bias", li),
                                   top_k + 1)
    chosen = chosen[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return (chosen, gates * float(config["routed_scaling_factor"]),
            ranked[:, top_k - 1] - ranked[:, top_k])


def latent_moe(x, weights, li, config):
    """``(output, margin [S])`` of one ``E`` layer."""
    chosen, gates, margin = route(x, weights, li, config)
    latent = x @ weights("latent_in", li).T
    routed = jnp.zeros_like(latent)
    for e in held_experts(config):
        w = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)   # [S]
        routed = routed + w[:, None] * (
            relu2(latent @ weights("up_proj", li, e).T)
            @ weights("down_proj", li, e).T)
    shared = relu2(x @ weights("shared_up_proj", li).T) @ weights(
        "shared_down_proj", li).T
    return routed @ weights("latent_out", li).T + shared, margin


def forward(weights, tokens, config, positions=None):
    """``(logits [B, S, V] float32, router margins [B, Le, S])`` for
    ``tokens [B, S]``; with ``positions`` (ascending indices into ``S``)
    the final norm and the head run on those rows of the last layer's
    output only: ``[B, len(positions), V]``. The logits are handed back
    on the host, a sequence's as soon as they are computed."""
    pattern = config["hybrid_override_pattern"]
    if (set(pattern) - set("M*E") or len(pattern)
            != config["num_hidden_layers"] or config["n_group"] != 1
            or config["topk_group"] != 1
            or config["num_nextn_predict_layers"] or config["use_bias"]
            or config["mlp_bias"] or config["attention_bias"]
            or config["mamba_proj_bias"]
            or config["mlp_hidden_act"] != "relu2"):
        raise ValueError("nemotron_h_f32 computes M, * and E layers, one "
                         "a letter, squared-ReLU experts without a group "
                         "limit, a bias or a prediction module")
    eps = float(config["norm_eps"])
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        table = weights("embedding")
        head = weights("lm_head")
        for seq in tokens:
            x = table[jnp.asarray(seq)]
            seq_margins = []
            for li, letter in enumerate(pattern):
                h = rms_norm(x, weights("norm", li), eps)
                if letter == "M":
                    x = x + mamba_mixer(h, weights, li, config)
                elif letter == "*":
                    x = x + attention_mixer(h, weights, li, config)
                else:
                    y, margin = latent_moe(h, weights, li, config)
                    seq_margins.append(margin)
                    x = x + y
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = rms_norm(x, weights("final_norm"), eps)
            out.append(np.asarray(x @ head.T))
            margins.append(jnp.stack(seq_margins))
    return np.stack(out), jnp.stack(margins)


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
