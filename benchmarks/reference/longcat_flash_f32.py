"""The plain reference of LongCat-Flash (``LongCat-Flash-Chat``): a
shortcut-connected double layer (two latent attentions, two dense
feed-forwards, one expert bank read after the first attention and added
after the second feed-forward) under a softmax router with a selection
bias whose slots are real experts and identity experts, in ``jax.numpy``
and float32.

No kernel, no cache, no scan, no capacity, no absorption of ``kv_b``, no
batching of requests, and no import from the package under test. It reads
``weights(name, layer=None, expert=None)``: the published checkpoint's
tensors in float32 and in the checkpoint's orientation (a projection is
``[out, in]``; ``families/longcat_flash.py`` ``published``): ``embedding
[V, H]``, ``final_norm [H]``, ``lm_head [V, H]``; of a layer, for ``i`` in
``0, 1``: ``input_layernorm.i``, ``post_attention_layernorm.i``,
``self_attn.i.q_a_proj [q_lora_rank, H]``, ``self_attn.i.q_a_layernorm``,
``self_attn.i.q_b_proj [N * (nope + rope), q_lora_rank]``,
``self_attn.i.kv_a_proj_with_mqa [kv_lora_rank + rope, H]``,
``self_attn.i.kv_a_layernorm``, ``self_attn.i.kv_b_proj [N * (nope + v),
kv_lora_rank]`` (a head's rows: its ``nope`` key rows, then its ``v``
value rows), ``self_attn.i.o_proj [H, N * v]``, ``mlps.i.gate_proj``,
``mlps.i.up_proj [ffn, H]``, ``mlps.i.down_proj [H, ffn]``; and once a
layer ``mlp.router.classifier [slots, H]``,
``mlp.router.e_score_correction_bias [slots]`` and
``mlp.experts.gate_proj`` / ``up_proj`` / ``down_proj`` an expert at a
time by the expert's published index. Sizes and constants come from the
configuration file's keys. Everything runs under
``jax.default_matmul_precision("highest")``.

A layer, for the residual stream ``x``::

    a  = x + MLA_0(norm(x))
    h  = norm(a)
    m  = MoE(h)
    b  = a + FFN_0(h)
    c  = b + MLA_1(norm(b))
    d  = c + FFN_1(norm(c))
    out = d + m

``MLA``: ``c_q = s_q norm(W_qa x)`` with ``s_q = sqrt(H / q_lora_rank)``
(``mla_scale_q_lora``); head ``i``'s query ``W_qb,i c_q = [q_nope, q_rope]``;
``[c, k_r] = W_kva x``, ``c_kv = s_kv norm(c)`` with ``s_kv = sqrt(H /
kv_lora_rank)`` (``mla_scale_kv_lora``), rotary on ``q_rope`` and on
``k_r`` (one key for all heads, not scaled); ``[k_nope,i, v_i] = W_kvb,i
c_kv`` expanded for every position and head; ``p = softmax_{j<=t}((q_nope
. k_nope + q_rope . k_r) / sqrt(nope + rope))``; ``W_o [sum_j p v]``.

``MoE``: ``p = softmax(W_r h)`` over ``n_routed_experts (published) +
zero_expert_num`` slots; the ``moe_topk`` largest ``p + bias`` are chosen
(equal: the lower index); ``w_j = routed_scaling_factor p_j``, not
renormalised; ``m = sum over chosen real experts j of w_j swiglu_j(h) +
(sum over chosen identity slots of w_j) h``: every token reaches every
expert it chose. Given the chip's share of a deployment (``share``:
``n_routed_experts`` is then the experts held here from
``share.first_expert`` on, ``share.n_routed_experts_published`` the real
experts the router scores), the terms of the experts held elsewhere are
left out, as in the program; the identity term is whole.

Also returned: each layer's router margin ``[B, L, S]``, the gap between
the last chosen and the first unchosen ``p + bias``.

Departures from the published description (the configuration's
``assumed``): ``s_q`` and ``s_kv`` as above (the config gives two
booleans); ``norm_topk_prob`` false; rotary in the half-split form;
no ``rope_scaling``; an untied head.
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


def rotary(x, theta):
    """``x [S, ..., D]`` at positions ``0..S-1``, half-split pairing."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention(x, weights, li, which, config):
    n = config["num_attention_heads"]
    nope, rope, dv = (config["qk_nope_head_dim"],
                      config["qk_rope_head_dim"], config["v_head_dim"])
    rank, hidden = config["kv_lora_rank"], config["hidden_size"]
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    s_q = (math.sqrt(hidden / config["q_lora_rank"])
           if config["mla_scale_q_lora"] else 1.0)
    s_kv = math.sqrt(hidden / rank) if config["mla_scale_kv_lora"] else 1.0

    def w(name):
        return weights(f"self_attn.{which}.{name}", li)

    s = x.shape[0]
    c_q = s_q * rms_norm(x @ w("q_a_proj").T, w("q_a_layernorm"), eps)
    q = (c_q @ w("q_b_proj").T).reshape(s, n, nope + rope)
    q_nope, q_rope = q[..., :nope], rotary(q[..., nope:], theta)
    kv = x @ w("kv_a_proj_with_mqa").T
    c_kv = s_kv * rms_norm(kv[:, :rank], w("kv_a_layernorm"), eps)
    k_rope = rotary(kv[:, rank:], theta)                       # [S, rope]
    expanded = (c_kv @ w("kv_b_proj").T).reshape(s, n, nope + dv)
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    pos = jnp.arange(s)
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        scores = (jnp.einsum("tnd,snd->tns", q_nope[lo:hi], k_nope)
                  + jnp.einsum("tnd,sd->tns", q_rope[lo:hi], k_rope)
                  ) / jnp.sqrt(jnp.float32(nope + rope))
        causal = pos[None, None, :] <= pos[lo:hi, None, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        out.append(jnp.einsum("tns,snd->tnd", probs, v))
    return jnp.concatenate(out).reshape(s, n * dv) @ w("o_proj").T


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def dense(x, weights, li, which):
    return swiglu(x, *(weights(f"mlps.{which}.{name}_proj", li)
                       for name in ("gate", "up", "down")))


def route(x, weights, li, config):
    """``(chosen [S, k], w [S, k], margin [S])``: the slots each position
    chose, their weights and the gap to the first unchosen."""
    top_k = config["moe_topk"]
    p = jax.nn.softmax(x @ weights("mlp.router.classifier", li).T, axis=-1)
    biased = p + weights("mlp.router.e_score_correction_bias", li)
    ranked, chosen = jax.lax.top_k(biased, top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    chosen = chosen[:, :top_k]
    w = jnp.take_along_axis(p, chosen, axis=-1) * float(
        config["routed_scaling_factor"])
    return chosen, w, margin


def real_experts(config):
    """``(published, first, held)``: the real experts the router scores
    and those of them this share holds."""
    share = config.get("share", {})
    held = int(config["n_routed_experts"])
    return (int(share.get("n_routed_experts_published", held)),
            int(share.get("first_expert", 0)), held)


def routed_term(x, chosen, w, weights, li, config):
    """The held real experts' part of the bank's output."""
    _, first, held = real_experts(config)
    y = jnp.zeros_like(x)
    for e in range(first, first + held):
        g = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)       # [S]
        y = y + g[:, None] * swiglu(
            x, *(weights(f"mlp.experts.{name}_proj", li, e)
                 for name in ("gate", "up", "down")))
    return y


def identity_term(x, chosen, w, config):
    """The identity experts' part: a slot past the published real experts
    hands the row back, times its weight."""
    published, _, _ = real_experts(config)
    return jnp.sum(jnp.where(chosen >= published, w, 0.0),
                   axis=-1)[:, None] * x


def expert_bank(x, weights, li, config):
    """``(m, margin [S])`` of one layer's expert bank."""
    if config["zero_expert_type"] != "identity":
        raise ValueError("zero experts are identity experts here")
    chosen, w, margin = route(x, weights, li, config)
    return (routed_term(x, chosen, w, weights, li, config)
            + identity_term(x, chosen, w, config)), margin


def layer(x, weights, li, config):
    """``(out, margin [S])`` of one double layer."""
    eps = float(config["rms_norm_eps"])
    a = x + attention(rms_norm(x, weights("input_layernorm.0", li), eps),
                      weights, li, 0, config)
    h = rms_norm(a, weights("post_attention_layernorm.0", li), eps)
    m, margin = expert_bank(h, weights, li, config)
    b = a + dense(h, weights, li, 0)
    c = b + attention(rms_norm(b, weights("input_layernorm.1", li), eps),
                      weights, li, 1, config)
    d = c + dense(rms_norm(c, weights("post_attention_layernorm.1", li),
                           eps), weights, li, 1)
    return d + m, margin


def forward(weights, tokens, config, positions=None):
    """``(logits [B, S, V] float32, router margins [B, L, S])`` for
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
            for li in range(config["num_layers"]):
                x, margin = layer(x, weights, li, config)
                seq_margins.append(margin)
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = rms_norm(x, weights("final_norm"), eps)
            # x @ lm_head.T without the transposed copy of the head
            out.append(np.asarray(jnp.einsum("sh,vh->sv", x,
                                             weights("lm_head"))))
            margins.append(jnp.stack(seq_margins))
    return np.stack(out), jnp.stack(margins)


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
