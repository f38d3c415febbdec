"""The plain reference of MiMo-V2-Flash (``model_type: mimo_v2_flash``):
full-attention and sliding-window layers with keys of 192 values beside
values of 128, unlike K/V head counts by layer type, a sink term in the
sliding layers' softmax, a dense first layer and then expert layers under
a sigmoid router with a selection bias, in ``jax.numpy`` and float32.

No kernel, no cache, no ring, no scan, no capacity, no batching of
requests, and no import from the package under test. It reads
``weights(name, layer=None, expert=None)``: the published checkpoint's
tensors in float32 and in the checkpoint's orientation (a projection is
``[out, in]``; ``families/mimo_v2_flash.py`` ``published``): ``embedding
[V, H]``, ``final_norm [H]``, ``lm_head [V, H]``; a layer's
``input_norm``, ``post_norm``, ``q_proj [N * 192, H]``, ``k_proj [KV_t *
192, H]``, ``v_proj [KV_t * 128, H]``, ``o_proj [H, N * 128]`` and, in a
sliding layer, ``attention_sink_bias [N]``; a dense layer's ``gate``,
``up``, ``down``; a sparse layer's ``router [E, H]`` and ``router_bias
[E]`` over all ``E`` published experts and ``gate``/``up``/``down`` an
expert at a time by the expert's published index. Sizes and constants
come from the configuration file's keys. Everything runs under
``jax.default_matmul_precision("highest")``.

A layer ``l`` of type ``t = hybrid_layer_pattern[l]`` (0 full, 1
sliding), for the residual stream ``h`` and ``x = norm(h)``: ``q = W_q x``
as ``N`` heads of ``head_dim`` 192, ``k = W_k x`` as ``KV_t`` heads of
192 and ``v = attention_value_scale * W_v x`` as ``KV_t`` heads of
``v_head_dim`` 128 (``KV_0 = num_key_value_heads``, ``KV_1 =
swa_num_key_value_heads``), a K/V head shared by ``N / KV_t`` query heads;
the first ``int(192 * partial_rotary_factor) = 64`` values of every q and
k head rotated (``rope_theta`` full, ``swa_rope_theta`` sliding), the
others as they are; ``s_ij = q_i . k_j / sqrt(192)`` over ``j <= i``
(full) or ``i - sliding_window < j <= i`` (sliding); full: ``p =
softmax_j(s)``; sliding: ``p_ij = exp(s_ij) / (exp(b) + sum_j
exp(s_ij))`` with ``b`` the query head's ``attention_sink_bias``; ``h <- h
+ W_o concat_heads(sum_j p_ij v_j)``. Then ``x2 = norm(h)``: a dense layer
(``moe_layer_freq[l] == 0``) adds ``swiglu(x2)``; a sparse one ``s =
sigmoid(W_r x2)`` over all ``E``, the ``num_experts_per_tok`` largest of
``s + router_bias`` (equal: the lower index), ``w = s_chosen / sum(s
chosen)`` times ``routed_scaling_factor`` (null: 1), and adds ``sum_chosen
w_e swiglu_e(x2)``; no shared expert.

**The chip's share** (``share`` in the configuration file, absent for the
whole model): this device holds the experts ``first_expert ..
first_expert + n_routed_experts - 1`` of the ``n_routed_experts_published``
that the router scores. The sum over the chosen experts then runs over
the held ones alone: what an expert held elsewhere would add is left out,
and that partial result goes on to the next layer, as in the program. The
vocabulary's slice is a smaller vocabulary: ``embedding`` and ``lm_head``
have ``vocab_size`` rows.

Also returned: each sparse layer's router margin ``[B, L_sparse, S]``, the
gap between the last chosen and the first unchosen biased score, so the
comparison can tell a token whose routing is decided by rounding.

Departures from the published description (the configuration's
``assumed``): rotary in the half-split form; no norm on q or k; the scale
``1 / sqrt(192)``; the value scale on V before the product;
``attention_chunk_size`` unused; the multi-token-prediction modules not
built.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rotary(x, theta: float, dim: int):
    """``x [S, N, D]`` at positions ``0..S-1``: the first ``dim`` values
    of a head rotated, half-split pairing, the rest as they are."""
    s = x.shape[0]
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def attention(x, weights, li, config):
    sliding = config["hybrid_layer_pattern"][li] == 1
    n, d, dv = (config["num_attention_heads"], config["head_dim"],
                config["v_head_dim"])
    kv = config["swa_num_key_value_heads" if sliding
                else "num_key_value_heads"]
    theta = float(config["swa_rope_theta" if sliding else "rope_theta"])
    dim = int(d * float(config["partial_rotary_factor"]))
    window = config["sliding_window"] if sliding else None
    sink = (weights("attention_sink_bias", li)
            if sliding and config["add_swa_attention_sink_bias"] else None)
    if not sliding and config["add_full_attention_sink_bias"]:
        raise ValueError("a sink term in the full layers is not built")
    s = x.shape[0]
    q = rotary((x @ weights("q_proj", li).T).reshape(s, n, d), theta, dim)
    k = rotary((x @ weights("k_proj", li).T).reshape(s, kv, d), theta, dim)
    v = (x @ weights("v_proj", li).T).reshape(s, kv, dv) * float(
        config["attention_value_scale"])
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
        scores = jnp.where(seen[:, None, None, :], scores, -jnp.inf)
        if sink is not None:
            # one more column a query head, which holds no value
            scores = jnp.concatenate(
                [scores, jnp.broadcast_to(
                    sink.reshape(1, kv, n // kv, 1),
                    scores.shape[:3] + (1,))], -1)
        probs = jax.nn.softmax(scores, -1)[..., :seen.shape[1]]
        out.append(jnp.einsum("tgrs,sgd->tgrd", probs, v[keys]))
    a = jnp.concatenate(out)[:s].reshape(s, n * dv)
    return a @ weights("o_proj", li).T


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def held_experts(config):
    """The published indices of the routed experts held here."""
    first = int(config.get("share", {}).get("first_expert", 0))
    return range(first, first + int(config["n_routed_experts"]))


def sparse_layer(x, weights, li, config):
    """``(output, margin [S])`` of one sparse layer's feed-forward."""
    top_k = config["num_experts_per_tok"]
    if (config["scoring_func"] != "sigmoid" or config["n_group"] != 1
            or config["topk_group"] != 1 or config["n_shared_experts"]):
        raise ValueError("a sigmoid router without groups and no shared "
                         "expert are what is built")
    scores = jax.nn.sigmoid(x @ weights("router", li).T)          # [S, E]
    ranked, chosen = jax.lax.top_k(scores + weights("router_bias", li),
                                   top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    chosen = chosen[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    picked = picked * float(config["routed_scaling_factor"] or 1.0)
    y = jnp.zeros_like(x)
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
    eps = float(config["layernorm_epsilon"])
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        for seq in tokens:
            x = weights("embedding")[jnp.asarray(seq)]
            seq_margins = []
            for li in range(config["num_hidden_layers"]):
                h = rms_norm(x, weights("input_norm", li), eps)
                x = x + attention(h, weights, li, config)
                h = rms_norm(x, weights("post_norm", li), eps)
                if config["moe_layer_freq"][li] == 0:
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
