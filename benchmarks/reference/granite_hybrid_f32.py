"""The plain reference of Granite 4.0-H (``granitemoehybrid``): Mamba-2
state-space layers with a few attention layers among them, in ``jax.numpy``
and float32.

No kernel, no cache, no chunks, no batching of requests, and no import
from the package under test. It reads ``weights(name, layer=None)``: the
published checkpoint's tensors in float32 and in the checkpoint's
orientation (``families/granite_hybrid.py`` ``published``): ``embedding
[V, H]`` (also the head: ``tie_word_embeddings``), ``final_norm [H]``; a
layer's ``input_norm``, ``post_norm``, ``input_linear [2I, H]`` (gate
rows, then up rows), ``output_linear [H, I]``; a mamba layer's ``in_proj
[2 d_inner + 2 N + heads, H]`` (rows ``z | x | B | C | dt``), ``conv_weight
[d_inner + 2 N, 1, d_conv]``, ``conv_bias``, ``A_log``, ``D``, ``dt_bias
[heads]``, ``mamba_norm [d_inner]``, ``out_proj [H, d_inner]``; an
attention layer's ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj``. Sizes and
constants come from the configuration file's keys;
``config["layer_types"]`` names each layer's mixer. Everything runs under
``jax.default_matmul_precision("highest")``.

Trunk: ``x0 = embedding_multiplier * embed(ids)``; a layer is ``x <- x +
residual_multiplier * mixer(norm(x))``, ``x <- x + residual_multiplier *
W_out(silu(g) * u)`` with ``[g, u] = W_in norm(x)``; logits ``= (norm(x)
E^T) / logits_scaling``. ``norm(u) = u / sqrt(mean(u^2) + eps) * w``.

``mamba`` (Mamba-2, one group): ``[z | xBC | dt] = W_inproj h``; ``xBC_t =
silu(sum_k w[:, k] xBC_{t - (d_conv - 1) + k} + b)``, zeros before position
0, computed as ``d_conv`` shifted adds; ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer)
B_t``, ``y_t = S_t C_t + D x_t``, **position by position** (a ``lax.scan``
over the sequence: no chunk, no cumulative sum of decays); ``out = W_outproj
(norm_{d_inner}(y * silu(z)) * w_norm)``, the gate before the one norm over
all of ``d_inner``.

``attention``: ``q, k, v`` without bias or rotary embedding (NoPE), ``G``
K/V heads each read by ``N / G`` query heads, ``softmax(q k^T *
attention_multiplier)`` over the causal positions, dense.

Departures from the published description: none in the mathematics. The
routed experts of the ``granitemoehybrid`` family are not computed
(``num_local_experts`` is 0 here, and a configuration that has some is
refused); ``mamba_n_groups`` other than 1 is refused.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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
    n, inner = config["mamba_d_state"], config["mamba_n_heads"] * \
        config["mamba_d_head"]
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
    scores = jnp.einsum("tgrd,sgd->tgrs", q, k) * float(
        config["attention_multiplier"])
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    probs = jax.nn.softmax(
        jnp.where(causal[:, None, None, :], scores, -jnp.inf), -1)
    out = jnp.einsum("tgrs,sgd->tgrd", probs, v).reshape(s, n * d)
    return out @ weights("o_proj", li).T


def layer(x, weights, li, config):
    eps, c = float(config["rms_norm_eps"]), float(
        config["residual_multiplier"])
    h = rms_norm(x, weights("input_norm", li), eps)
    mixer = (mamba_mixer if config["layer_types"][li] == "mamba"
             else attention_mixer)
    x = x + c * mixer(h, weights, li, config)
    h = rms_norm(x, weights("post_norm", li), eps)
    gate, up = jnp.split(h @ weights("input_linear", li).T, 2, axis=-1)
    return x + c * ((jax.nn.silu(gate) * up) @ weights("output_linear", li).T)


def forward(weights, tokens, config, positions=None):
    """``(logits [B, S, V] float32, None)`` for ``tokens [B, S]``; with
    ``positions`` (ascending indices into ``S``) the final norm and the
    head run on those rows of the last layer's output only: ``[B,
    len(positions), V]``."""
    if config.get("num_local_experts", 0) or config["mamba_n_groups"] != 1:
        raise ValueError("granite_hybrid_f32 computes the dense models: no "
                         "routed experts, one group of B and C")
    out = []
    with jax.default_matmul_precision("highest"):
        table = weights("embedding")
        for seq in tokens:
            x = float(config["embedding_multiplier"]) * table[
                jnp.asarray(seq)]
            for li in range(config["num_hidden_layers"]):
                x = layer(x, weights, li, config)
            if positions is not None:
                x = x[jnp.asarray(positions)]
            x = rms_norm(x, weights("final_norm"),
                         float(config["rms_norm_eps"]))
            out.append((x @ table.T) / float(config["logits_scaling"]))
    return jnp.stack(out), None


def cross_entropy(logits, labels):
    """Mean next-token cross-entropy; ``labels`` are already shifted."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1)
    return -jnp.mean(picked)
