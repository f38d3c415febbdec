"""The plain reference of GLM-4.7-Flash (``glm4_moe_lite``): latent (MLA)
attention, one leading dense layer and then expert layers under a sigmoid
router with a selection bias, in ``jax.numpy`` and float32.

No kernel, no cache, no scan, no capacity, no absorption of ``kv_b``, no
batching of requests, and no import from the package under test. It reads
``weights(name, layer=None, expert=None)``: the published checkpoint's
tensors in float32 and in the checkpoint's orientation (a projection is
``[out, in]``; ``families/glm_moe_lite.py`` ``published``): ``embedding
[V, H]``, ``final_norm [H]``, ``lm_head [V, H]``; a layer's ``input_norm``,
``post_norm``, ``q_a_proj [q_lora_rank, H]``, ``q_a_norm``, ``q_b_proj [N
* (nope + rope), q_lora_rank]``, ``kv_a_proj [kv_lora_rank + rope, H]``
(the checkpoint's ``kv_a_proj_with_mqa``), ``kv_a_norm``, ``kv_b_proj [N *
(nope + v), kv_lora_rank]`` (a head's rows: its ``nope`` key rows, then
its ``v`` value rows), ``o_proj [H, N * v]``; a dense layer's ``gate``,
``up``, ``down``; an expert layer's ``router [E, H]``, ``router_bias [E]``
(``e_score_correction_bias``), ``gate``/``up``/``down`` an expert at a
time, and ``shared_gate``, ``shared_up``, ``shared_down``. Sizes and
constants come from the configuration file's keys. Everything runs under
``jax.default_matmul_precision("highest")``.

A layer, for the residual stream ``h`` and ``x = norm(h)``:
``c_q = norm(W_qa x)``; head ``i``'s query ``W_qb,i c_q = [q_nope (nope),
q_rope (rope)]``; ``[c_kv, k_rope] = W_kva x``, ``c_kv <- norm(c_kv)``,
``k_rope <- rotary(k_rope)`` (one rotary key for all heads), ``q_rope <-
rotary(q_rope)``; ``[k_nope,i, v_i] = W_kvb,i c_kv`` expanded for every
position and head; ``p = softmax_{j<=t}((q_nope . k_nope + q_rope .
k_rope) / sqrt(nope + rope))``; ``h <- h + W_o [sum_j p v]``. Then ``x2 =
norm(h)``: the first ``first_k_dense_replace`` layers add
``swiglu(x2)``; the others ``s = sigmoid(W_g x2)``, the
``num_experts_per_tok`` experts with the largest ``s + b`` (equal: the
lower index), ``g = routed_scaling_factor * s / (sum of the chosen s +
1e-20)``, and add ``sum_chosen g_e swiglu_e(x2) + swiglu_shared(x2)``:
every token reaches every expert it chose.

Also returned: each expert layer's router margin ``[B, L_moe, S]``, the
gap between the last chosen and the first unchosen ``s + b``, so the
comparison can tell a token whose routing is decided by rounding.

Departures from the published description: rotary in the half-split form
(the HuggingFace runtime layout), as ``decoder_f32`` has it;
``n_group = topk_group = 1`` is read as no group limit; the
multi-token-prediction module (``num_nextn_predict_layers``) is left out:
it does not enter the next-token logits.
"""

from __future__ import annotations

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


def attention(x, weights, li, config):
    n = config["num_attention_heads"]
    nope, rope, dv = (config["qk_nope_head_dim"],
                      config["qk_rope_head_dim"], config["v_head_dim"])
    rank = config["kv_lora_rank"]
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    s = x.shape[0]
    c_q = rms_norm(x @ weights("q_a_proj", li).T, weights("q_a_norm", li),
                   eps)
    q = (c_q @ weights("q_b_proj", li).T).reshape(s, n, nope + rope)
    q_nope, q_rope = q[..., :nope], rotary(q[..., nope:], theta)
    kv = x @ weights("kv_a_proj", li).T
    c_kv = rms_norm(kv[:, :rank], weights("kv_a_norm", li), eps)
    k_rope = rotary(kv[:, rank:], theta)                       # [S, rope]
    expanded = (c_kv @ weights("kv_b_proj", li).T).reshape(s, n, nope + dv)
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
    return jnp.concatenate(out).reshape(s, n * dv) @ weights("o_proj", li).T


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def expert_layer(x, weights, li, config):
    """``(output, margin [S])`` of one expert layer's feed-forward."""
    top_k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ weights("router", li).T)         # [S, E]
    biased = scores + weights("router_bias", li)
    ranked, chosen = jax.lax.top_k(biased, top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    chosen = chosen[:, :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * float(config["routed_scaling_factor"])
    y = swiglu(x, weights("shared_gate", li), weights("shared_up", li),
               weights("shared_down", li))
    for e in range(config["n_routed_experts"]):
        g = jnp.sum(jnp.where(chosen == e, picked, 0.0), axis=-1)  # [S]
        y = y + g[:, None] * swiglu(x, weights("gate", li, e),
                                    weights("up", li, e),
                                    weights("down", li, e))
    return y, margin


def forward(weights, tokens, config, positions=None):
    """``(logits [B, S, V] float32, router margins [B, L_moe, S])`` for
    ``tokens [B, S]``; with ``positions`` (ascending indices into ``S``)
    the final norm and the head run on those rows of the last layer's
    output only: ``[B, len(positions), V]``. The logits are handed back
    on the host, a sequence's as soon as they are computed: at a
    vocabulary of 154,880 two sequences' would not fit on the device
    beside the head."""
    eps = float(config["rms_norm_eps"])
    dense = int(config["first_k_dense_replace"])
    out, margins = [], []
    with jax.default_matmul_precision("highest"):
        for seq in tokens:
            x = weights("embedding")[jnp.asarray(seq)]
            seq_margins = []
            for li in range(config["num_hidden_layers"]):
                h = rms_norm(x, weights("input_norm", li), eps)
                x = x + attention(h, weights, li, config)
                h = rms_norm(x, weights("post_norm", li), eps)
                if li < dense:
                    y = swiglu(h, weights("gate", li), weights("up", li),
                               weights("down", li))
                else:
                    y, margin = expert_layer(h, weights, li, config)
                    seq_margins.append(margin)
                x = x + y
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = rms_norm(x, weights("final_norm"), eps)
            # x @ lm_head.T without the transposed copy of the head
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
