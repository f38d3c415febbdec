"""The plain reference of Laguna (``model_type: laguna``; Laguna-S-2.1):
full-attention and sliding-window layers with a per-head output gate, a
dense first layer and then expert layers under a softmax router with a
shared expert, in ``jax.numpy`` and float32.

No kernel, no cache, no ring, no scan, no capacity, no batching of
requests, and no import from the package under test. It reads
``weights(name, layer=None, expert=None)``: the published checkpoint's
tensors in float32 and in the checkpoint's orientation (a projection is
``[out, in]``; ``families/laguna.py`` ``published``): ``embedding [V,
H]``, ``final_norm [H]``, ``lm_head [V, H]``; a layer's ``input_norm``,
``post_norm``, ``q_proj [N_l * D, H]``, ``k_proj``, ``v_proj [KV * D,
H]``, ``g_proj [N_l, H]``, ``o_proj [H, N_l * D]`` with ``N_l =
num_attention_heads_per_layer[l]``; a dense layer's ``gate``, ``up``,
``down``; a sparse layer's ``router [E, H]`` over all ``E`` published
experts, ``gate``/``up``/``down`` an expert at a time by the expert's
published index, and ``shared_gate``, ``shared_up``, ``shared_down``.
Sizes and constants come from the configuration file's keys. Everything
runs under ``jax.default_matmul_precision("highest")``.

A layer ``l``, for the residual stream ``h`` and ``x = norm(h)``: ``q =
W_q x`` as ``N_l`` heads of ``D``, ``k, v = W_k x, W_v x`` as ``KV`` heads
each shared by ``N_l / KV`` query heads, ``g = sigmoid(W_g x)`` one value
a head; ``q`` and ``k`` rotated by the layer type's table
(``rope_parameters``: ``full_attention`` YaRN over the first
``partial_rotary_factor * D`` values of a head, cos and sin times
``attention_factor``; ``sliding_attention`` plain over all ``D``);
``a = softmax_j(q . k_j / sqrt(D)) v_j`` over ``j <= t``
(``full_attention``) or ``t - sliding_window < j <= t``
(``sliding_attention``); ``h <- h + W_o concat_heads(g * a)``. Then ``x2 =
norm(h)``: a ``dense`` layer adds ``swiglu(x2)``; a ``sparse`` one ``s =
softmax(W_r x2)`` over all ``E``, the ``num_experts_per_tok`` largest
(equal: the lower index), ``w = moe_routed_scaling_factor * s / (sum of
the chosen s)``, and adds ``sum_chosen w_e swiglu_e(x2) +
swiglu_shared(x2)``.

**The chip's share** (``share`` in the configuration file, absent for the
whole model): this device holds the experts ``first_expert ..
first_expert + num_experts - 1`` of the ``num_experts_published`` that the
router scores. The sum over the chosen experts then runs over the held
ones alone: what an expert held elsewhere would add is left out, and that
partial result goes on to the next layer, as in the program. The
vocabulary's slice is a smaller vocabulary: ``embedding`` and ``lm_head``
have ``vocab_size`` rows.

Also returned: each sparse layer's router margin ``[B, L_sparse, S]``, the
gap between the last chosen and the first unchosen score, so the
comparison can tell a token whose routing is decided by rounding.

Departures from the published description (the configuration's
``assumed``): rotary in the half-split form, as ``decoder_f32`` has it; the
router's scores a softmax, no selection bias; no gate on the shared
expert; no norm on q or k; the gate reads the same normed ``x`` as
``W_q``.
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


def inverse_frequencies(rope: dict, dim: int):
    """``[dim / 2]`` of one layer type's ``rope_parameters`` over ``dim``
    rotated values, and what cos and sin are multiplied by."""
    theta = float(rope["rope_theta"])
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return f, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor, orig = float(rope["factor"]), \
        float(rope["original_max_position_embeddings"])

    def pair_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair_of(float(rope["beta_fast"]))), 0)
    hi = min(math.ceil(pair_of(float(rope["beta_slow"]))), dim - 1)
    r = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return f / factor * r + f * (1 - r), float(scale)


def rotary(x, rope: dict):
    """``x [S, N, D]`` at positions ``0..S-1``: the first
    ``partial_rotary_factor * D`` values of a head rotated, half-split
    pairing, the rest as they are."""
    s, d = x.shape[0], x.shape[-1]
    dim = int(round(float(rope.get("partial_rotary_factor", 1)) * d))
    inv, scale = inverse_frequencies(rope, dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def attention(x, weights, li, config):
    kind = config["layer_types"][li]
    n = config["num_attention_heads_per_layer"][li]
    kv, d = config["num_key_value_heads"], config["head_dim"]
    rope = config["rope_parameters"][kind]
    window = (config["sliding_window"] if kind == "sliding_attention"
              else None)
    s = x.shape[0]
    q = rotary((x @ weights("q_proj", li).T).reshape(s, n, d), rope)
    k = rotary((x @ weights("k_proj", li).T).reshape(s, kv, d), rope)
    v = (x @ weights("v_proj", li).T).reshape(s, kv, d)
    gate = jax.nn.sigmoid(x @ weights("g_proj", li).T)          # [S, N]
    q = q.reshape(s, kv, n // kv, d)
    # a block of queries against every key (a full layer) or against the
    # band of ``window - 1 + QUERY_BLOCK`` keys that ends with the block
    # (a sliding layer; the keys in front of position 0 are zeros that no
    # query sees): one shape of block whatever its place, so that the
    # eager programs compile once
    lead = 0 if window is None else window - 1
    pad = ((lead, -s % QUERY_BLOCK), (0, 0), (0, 0))
    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    q = jnp.pad(q, ((0, -s % QUERY_BLOCK), (0, 0), (0, 0), (0, 0)))
    at = jnp.arange(k.shape[0]) - lead               # a key's position
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = lo + QUERY_BLOCK
        keys = slice(0, k.shape[0]) if window is None else slice(lo,
                                                                  hi + lead)
        scores = jnp.einsum("tgrd,sgd->tgrs", q[lo:hi], k[keys]
                            ) / jnp.sqrt(jnp.float32(d))
        behind = jnp.arange(lo, hi)[:, None] - at[None, keys]    # [T, S']
        seen = (behind >= 0) & (at[None, keys] >= 0)
        if window is not None:
            seen = seen & (behind < window)
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, None, :], scores, -jnp.inf), -1)
        out.append(jnp.einsum("tgrs,sgd->tgrd", probs, v[keys]))
    a = jnp.concatenate(out)[:s].reshape(s, n, d) * gate[:, :, None]
    return a.reshape(s, n * d) @ weights("o_proj", li).T


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def held_experts(config):
    """The published indices of the routed experts held here."""
    first = int(config.get("share", {}).get("first_expert", 0))
    return range(first, first + int(config["num_experts"]))


def sparse_layer(x, weights, li, config):
    """``(output, margin [S])`` of one sparse layer's feed-forward."""
    top_k = config["num_experts_per_tok"]
    if float(config.get("moe_router_logit_softcapping", 0)):
        raise ValueError("a router softcap is not built")
    scores = jax.nn.softmax(x @ weights("router", li).T, axis=-1)  # [S, E]
    ranked, chosen = jax.lax.top_k(scores, top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    picked, chosen = ranked[:, :top_k], chosen[:, :top_k]
    if config.get("norm_topk_prob", True):
        picked = picked / picked.sum(-1, keepdims=True)
    picked = picked * float(config["moe_routed_scaling_factor"])
    y = swiglu(x, weights("shared_gate", li), weights("shared_up", li),
               weights("shared_down", li))
    for e in held_experts(config):
        g = jnp.sum(jnp.where(chosen == e, picked, 0.0), axis=-1)  # [S]
        y = y + g[:, None] * swiglu(x, weights("gate", li, e),
                                    weights("up", li, e),
                                    weights("down", li, e))
    return y, margin


def forward(weights, tokens, config, positions=None):
    """``(logits [B, S, V] float32, router margins [B, L_sparse, S])`` for
    ``tokens [B, S]``; with ``positions`` (ascending indices into ``S``)
    the final norm and the head run on those rows of the last layer's
    output only: ``[B, len(positions), V]``. The logits are handed back
    on the host, a sequence's as soon as they are computed."""
    eps = float(config["rms_norm_eps"])
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        for seq in tokens:
            x = weights("embedding")[jnp.asarray(seq)]
            seq_margins = []
            for li in range(config["num_hidden_layers"]):
                h = rms_norm(x, weights("input_norm", li), eps)
                x = x + attention(h, weights, li, config)
                h = rms_norm(x, weights("post_norm", li), eps)
                if config["mlp_layer_types"][li] == "dense":
                    y = swiglu(h, weights("gate", li), weights("up", li),
                               weights("down", li))
                else:
                    y, margin = sparse_layer(h, weights, li, config)
                    seq_margins.append(margin)
                x = x + y
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = rms_norm(x, weights("final_norm"), eps)
            out.append(np.asarray(jnp.einsum("sh,vh->sv", x,
                                             weights("lm_head"))))
            if seq_margins:
                margins.append(jnp.stack(seq_margins))
    return np.stack(out), (jnp.stack(margins) if margins else None)


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
