"""The plain reference of DeepSeek-V3.2 (``deepseek_v32``): latent (MLA)
attention under YaRN over the positions a learned indexer selects,
leading dense layers and then expert layers under a sigmoid router with a
selection bias whose choice is limited by groups, and a shared expert, in
``jax.numpy`` and float32.

No kernel, no cache, no scan, no capacity, no absorption of ``kv_b``, no
batching of requests, and no import from the package under test. It reads
``weights(name, layer=None, expert=None)``: the published checkpoint's
tensors in float32 and in the checkpoint's orientation (a projection is
``[out, in]``; ``families/deepseek_v32.py`` ``published``), under
DeepSeek-V3's names: ``embedding [V, H]``, ``final_norm [H]``, ``lm_head
[V, H]``; of a layer ``input_layernorm``, ``post_attention_layernorm``,
``self_attn.q_a_proj [q_lora_rank, H]``, ``self_attn.q_a_layernorm``,
``self_attn.q_b_proj [N * (nope + rope), q_lora_rank]``,
``self_attn.kv_a_proj_with_mqa [kv_lora_rank + rope, H]``,
``self_attn.kv_a_layernorm``, ``self_attn.kv_b_proj [N * (nope + v),
kv_lora_rank]`` (a head's rows: its ``nope`` key rows, then its ``v``
value rows), ``self_attn.o_proj [H, N * v]``; the indexer's
``self_attn.indexer.wq_b [Hi * Di, q_lora_rank]``, ``self_attn.indexer.wk
[Di, H]``, ``self_attn.indexer.k_norm.weight [Di]``,
``self_attn.indexer.k_norm.bias [Di]``, ``self_attn.indexer.weights_proj
[Hi, H]``; a dense layer's ``mlp.gate_proj``, ``mlp.up_proj``,
``mlp.down_proj``; an expert layer's ``mlp.gate [E, H]`` and
``mlp.gate.e_score_correction_bias [E]`` over all ``E`` published experts,
``mlp.experts.gate_proj`` / ``up_proj`` / ``down_proj`` an expert at a
time by the expert's published index and ``mlp.shared_experts.gate_proj``
/ ``up_proj`` / ``down_proj``. Sizes and constants come from the
configuration file's keys. Everything runs under
``jax.default_matmul_precision("highest")``.

A layer, pre-norm: ``h += Attn(rms_norm(h))``, ``h += FFN(rms_norm(h))``.

Attention, for ``x = rms_norm(h)``: ``c_q = norm(W_qa x)``; head ``i``'s
query ``W_qb,i c_q = [q_nope (nope), q_rope (rope)]``; ``[c_kv, k_rope] =
W_kva x``, ``c_kv <- norm(c_kv)``; ``k_rope`` (one rotary key for all
heads) and ``q_rope`` rotated by YaRN's frequencies (``rope_scaling``:
with ``f_i = theta^(-2i / rope)`` the pairs that turn more than
``beta_fast`` times in ``original_max_position_embeddings`` keep ``f_i``,
those that turn fewer than ``beta_slow`` times take ``f_i / factor``, a
linear ramp over the pair's index between), cos and sin times
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, ``mscale(f,
m) = 0.1 m ln f + 1``; ``[k_nope,i, v_i] = W_kvb,i c_kv`` expanded for
every position and head.

**The indexer** (:func:`index_scores`): ``q_I = W_Iq c_q`` (``Hi`` heads
of ``Di``), ``k_I = layer_norm(W_Ik x)`` (weight and bias,
``rms_norm_eps``), the first ``rope`` values of every ``q_I`` head and of
``k_I`` rotated as above at the row's own position, ``w = W_Iw x *
Hi^-1/2``; ``I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s]) * Di^-1/2``
for ``s <= t``. **The selection** (:func:`selected`): ``S_t`` = the
``min(t + 1, index_topk)`` positions ``s <= t`` of highest ``I[t, s]``
(equal scores: the lower position). Row ``t`` attends ``S_t`` and nothing
else: ``p = softmax_{s in S_t}((q_nope . k_nope + q_rope . k_rope) (nope
+ rope)^-1/2 mscale(factor, mscale_all_dim)^2)``; ``W_o [sum_s p v]``. A
layer has its own indexer and its own selection.

Feed-forward: the first ``first_k_dense_replace`` layers ``swiglu(x)``;
the others ``s = sigmoid(W_g x)`` over all ``E``, ``c = s + b``; the
experts are ``n_group`` runs of consecutive indices, a group scores the
sum of its two largest ``c``, the ``topk_group`` highest groups stay and
the others' ``c`` are ``-inf``; the ``num_experts_per_tok`` largest ``c``
are chosen (equal: the lower index), ``g = routed_scaling_factor * s /
(sum of the chosen s + 1e-20)``, ``sum_chosen g_e swiglu_e(x) +
swiglu_shared(x)``.

**The chip's share** (``share`` in the configuration file, absent for the
whole model): this device holds the experts ``first_expert ..
first_expert + n_routed_experts - 1`` of the
``n_routed_experts_published`` that the router scores. The sum over the
chosen experts then runs over the held ones alone: what an expert held
elsewhere would add is left out, and that partial result goes on to the
next layer, as in the program; the shared expert is whole. The
vocabulary's slice is a smaller vocabulary: ``embedding`` and ``lm_head``
have ``vocab_size`` rows.

Also returned: each expert layer's router margin ``[B, L_moe, S]``, the
gap between the last chosen and the first unchosen ``c`` (within the
groups that stay).

Departures from the published description, each under the
configuration's ``assumed``: the indexer in float32 from unrotated,
unquantized operands (the release rotates ``q_I`` and ``k_I`` by a
Hadamard matrix, which cancels in their product, and quantizes both to
FP8); rotary in the half-split form in the attention and in the indexer
alike; the multi-token-prediction module (``num_nextn_predict_layers``)
left out: it does not enter the next-token logits.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256
HEAD_GROUP = 32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


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


def rows(x, lo, hi):
    """``x[lo:hi]`` by an index that is an operand: a slice at a constant
    is a program to compile a block of queries (33 of them a sequence of
    8,480 positions, for every array that is cut)."""
    return jax.lax.dynamic_slice_in_dim(x, jnp.int32(lo), hi - lo)


def index_rows(x, c_q, weights, li, config):
    """``(q_I [S, Hi, Di], k_I [S, Di], w [S, Hi])`` of a layer's normed
    input ``x`` and its normed low-rank query ``c_q``."""
    heads, width = config["index_n_heads"], config["index_head_dim"]
    rope = config["qk_rope_head_dim"]
    theta, scaling = float(config["rope_theta"]), config["rope_scaling"]
    s = x.shape[0]

    def w(name):
        return weights("self_attn.indexer." + name, li)

    def rotated(v):
        return jnp.concatenate(
            [rotary(v[..., :rope], theta, scaling), v[..., rope:]], -1)

    q = rotated((c_q @ w("wq_b").T).reshape(s, heads, width))
    k = rotated(layer_norm(x @ w("wk").T, w("k_norm.weight"),
                           w("k_norm.bias"), float(config["rms_norm_eps"])))
    return q, k, (x @ w("weights_proj").T) / math.sqrt(heads)


def index_scores(q, k, w, lo, hi, config):
    """``I[t, s]`` for the rows ``lo..hi-1`` over every position, ``-inf``
    past the row's own."""
    s = k.shape[0]
    dots = jnp.einsum("thd,sd->ths", rows(q, lo, hi), k)
    scores = jnp.sum(jax.nn.relu(dots) * rows(w, lo, hi)[:, :, None],
                     axis=1) / math.sqrt(config["index_head_dim"])
    pos = jnp.arange(s)
    return jnp.where(pos[None, :] <= rows(pos, lo, hi)[:, None], scores,
                     -jnp.inf)


def selected(scores, config):
    """``[T, S]`` bool: a row's ``index_topk`` positions of highest score
    (equal: the lower position), all of its scored positions where it has
    no more."""
    k = min(int(config["index_topk"]), scores.shape[-1])
    # a position's place among the row's scores from the highest down,
    # equal scores in the order of their positions (a stable sort)
    order = jnp.argsort(-scores, axis=-1, stable=True)
    place = jnp.argsort(order, axis=-1, stable=True)
    return (place < k) & (scores > -jnp.inf)


def attention(x, weights, li, config, selections=None):
    """A layer's attention of ``x [S, H]``; ``selections`` (a list) is
    handed the layer's selected sets ``[S, S]`` bool. The heads run
    :data:`HEAD_GROUP` at a time (their queries, expanded keys and values
    and scores are what a sequence of 8k positions and 128 heads holds
    beside the served weights), the selection once for all of them."""
    n = config["num_attention_heads"]
    nope, rope, dv = (config["qk_nope_head_dim"],
                      config["qk_rope_head_dim"], config["v_head_dim"])
    rank = config["kv_lora_rank"]
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    scaling = config["rope_scaling"]
    m = mscale(float(scaling["factor"]), float(scaling["mscale_all_dim"]))
    scale = m * m / math.sqrt(nope + rope)
    s = x.shape[0]
    blocks = [(lo, min(lo + QUERY_BLOCK, s))
              for lo in range(0, s, QUERY_BLOCK)]

    def w(name):
        return weights("self_attn." + name, li)

    c_q = rms_norm(x @ w("q_a_proj").T, w("q_a_layernorm"), eps)
    kv = x @ w("kv_a_proj_with_mqa").T
    c_kv = rms_norm(kv[:, :rank], w("kv_a_layernorm"), eps)
    k_rope = rotary(kv[:, rank:], theta, scaling)              # [S, rope]
    q_i, k_i, w_i = index_rows(x, c_q, weights, li, config)
    keep = jnp.concatenate([
        selected(index_scores(q_i, k_i, w_i, lo, hi, config), config)
        for lo, hi in blocks])                                 # [S, S]
    del q_i, k_i, w_i
    if selections is not None:
        selections.append(np.asarray(keep))
    q_b = w("q_b_proj").reshape(n, nope + rope, -1)
    kv_b = w("kv_b_proj").reshape(n, nope + dv, -1)
    heads = []
    for first in range(0, n, HEAD_GROUP):
        group = slice(first, min(first + HEAD_GROUP, n))
        q = jnp.einsum("sr,ndr->snd", c_q, q_b[group])
        q_nope, q_rope = q[..., :nope], rotary(q[..., nope:], theta,
                                               scaling)
        expanded = jnp.einsum("sr,ndr->snd", c_kv, kv_b[group])
        k_nope, v = expanded[..., :nope], expanded[..., nope:]
        out = []
        for lo, hi in blocks:
            scores = (jnp.einsum("tnd,snd->tns", rows(q_nope, lo, hi),
                                 k_nope)
                      + jnp.einsum("tnd,sd->tns", rows(q_rope, lo, hi),
                                   k_rope)) * jnp.float32(scale)
            probs = jax.nn.softmax(
                jnp.where(rows(keep, lo, hi)[:, None, :], scores, -jnp.inf),
                -1)
            out.append(jnp.einsum("tns,snd->tnd", probs, v))
        heads.append(jnp.concatenate(out))
    return jnp.concatenate(heads, axis=1).reshape(s, n * dv) @ w("o_proj").T


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def held_experts(config):
    """The published indices of the routed experts held here."""
    first = int(config.get("share", {}).get("first_expert", 0))
    return range(first, first + int(config["n_routed_experts"]))


def route(x, weights, li, config):
    """``(chosen [S, k], gates [S, k], margin [S])`` over all the
    published experts."""
    top_k = config["num_experts_per_tok"]
    groups, stay = int(config["n_group"]), int(config["topk_group"])
    scores = jax.nn.sigmoid(x @ weights("mlp.gate", li).T)       # [S, E]
    biased = scores + weights("mlp.gate.e_score_correction_bias", li)
    if groups > 1:
        by_group = biased.reshape(biased.shape[0], groups, -1)
        group_score = jax.lax.top_k(by_group, 2)[0].sum(-1)  # [S, groups]
        _, best = jax.lax.top_k(group_score, stay)
        stays = jnp.zeros(group_score.shape, bool).at[
            jnp.arange(biased.shape[0])[:, None], best].set(True)
        biased = jnp.where(stays[:, :, None], by_group, -jnp.inf).reshape(
            biased.shape)
    ranked, chosen = jax.lax.top_k(biased, top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    chosen = chosen[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen, picked * float(config["routed_scaling_factor"]), margin


def expert_layer(x, weights, li, config):
    """``(output, margin [S])`` of one expert layer's feed-forward: the
    shared expert and the held experts' part of the routed sum."""
    chosen, gates, margin = route(x, weights, li, config)
    y = swiglu(x, *(weights(f"mlp.shared_experts.{p}_proj", li)
                    for p in ("gate", "up", "down")))
    for e in held_experts(config):
        g = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)   # [S]
        y = y + g[:, None] * swiglu(
            x, *(weights(f"mlp.experts.{p}_proj", li, e)
                 for p in ("gate", "up", "down")))
    return y, margin


def forward(weights, tokens, config, positions=None, selections=None):
    """``(logits [B, S, V] float32, router margins [B, L_moe, S])`` for
    ``tokens [B, S]``; with ``positions`` (ascending indices into ``S``)
    the final norm and the head run on those rows of the last layer's
    output only: ``[B, len(positions), V]``. ``selections`` (a list) is
    handed every sequence's every layer's selected sets, ``[S, S]`` bool
    each, in that order. The logits are handed back on the host, a
    sequence's as soon as they are computed."""
    eps = float(config["rms_norm_eps"])
    dense = int(config["first_k_dense_replace"])
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        for seq in tokens:
            x = weights("embedding")[jnp.asarray(seq)]
            seq_margins = []
            for li in range(config["num_hidden_layers"]):
                h = rms_norm(x, weights("input_layernorm", li), eps)
                x = x + attention(h, weights, li, config, selections)
                h = rms_norm(x, weights("post_attention_layernorm", li),
                             eps)
                if li < dense:
                    y = swiglu(h, *(weights(f"mlp.{p}_proj", li)
                                    for p in ("gate", "up", "down")))
                else:
                    y, margin = expert_layer(h, weights, li, config)
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
