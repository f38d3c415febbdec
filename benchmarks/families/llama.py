"""The llama family (Llama, Mistral): from a configuration file's published
keys to the package's ``LlamaConfig``, model and cached forward, and from
the served parameter tree back to the published checkpoint's tensors.

``families/<family>.py`` is found by the configuration's ``family``. It
exposes ``build(config, **overrides)`` and ``published(params, config)``.
This side knows the package's tree; the reference knows only the
published names.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple


def common(c: dict) -> dict:
    return dict(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim"),
        rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        max_seq_len=int(c["max_position_embeddings"]))


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    """``(model config, flax module, forward_with_cache)``. ``kw`` are
    fields of the package's config (dtype, runner settings)."""
    from neuronx_distributed_tpu.models import llama

    cfg = llama.LlamaConfig(**{**common(c), **kw})
    return cfg, llama.LlamaForCausalLM(cfg), llama.llama_forward_with_cache


# -- the served tree under the published names ---------------------------------

def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def _leaves(node, prefix=""):
    """``{"a/b": leaf}`` of a nested dict of arrays."""
    if not hasattr(node, "items"):
        return {prefix.rstrip("/"): node}
    out = {}
    for k, v in node.items():
        out.update(_leaves(v, f"{prefix}{k}/"))
    return out


def gate_or_up(node, index, which: int, hidden: int, inter: int):
    """The gate (``which`` 0) or up (1) projection of one layer (or one
    expert: ``index`` a tuple), ``[hidden, inter]`` float32, from whichever
    form ``node`` stores the two in, told apart by name and by shape: one
    fused leaf (``gate`` and ``up`` both in its name) with a dimension of
    2 in any position (``[H, 2, I]``, ``[2, H, I]``), gate first; one fused
    leaf ``[H, 2I]``, gate first; or two leaves."""
    flat = _leaves(node)
    fused = [k for k in flat if "gate" in k and "up" in k]
    if len(fused) == 1:
        w = flat[fused[0]][index]
        if w.ndim == 3 and 2 in w.shape:
            axis = w.shape.index(2)
            if w.shape[:axis] + w.shape[axis + 1:] == (hidden, inter):
                take = [slice(None)] * 3
                take[axis] = which
                return _f32(w[tuple(take)])
        if w.shape == (hidden, 2 * inter):
            return _f32(w[:, which * inter:(which + 1) * inter])
        raise ValueError(f"fused gate/up leaf {fused[0]!r} of shape "
                         f"{w.shape}: not [H,2,I], [2,H,I] or [H,2I] at "
                         f"H={hidden}, I={inter}")
    want, other = ("gate", "up") if which == 0 else ("up", "gate")
    found = [k for k in flat if want in k and other not in k]
    if len(found) == 1 and not fused:
        w = flat[found[0]][index]
        if w.shape == (hidden, inter):
            return _f32(w)
    raise ValueError(f"no {want} projection [{hidden}, {inter}] among "
                     f"{sorted(flat)}")


class Published:
    """The served tree as the published checkpoint's tensors, float32, in
    the checkpoint's orientation (a projection is ``[out, in]``), one layer
    at a time: sliced and upcast only when read, so the published widths
    fit beside the served weights.

    ``weights(name)`` for ``embedding [V, H]``, ``final_norm [H]`` and
    ``lm_head [V, H]``; ``weights(name, layer)`` for ``input_norm``,
    ``post_norm``, ``q_proj``, ``k_proj``, ``v_proj``, ``o_proj`` and the
    dense MLP's ``gate [I, H]``, ``up [I, H]``, ``down [H, I]``."""

    # where each tensor sits in the package's tree: params["params"][...];
    # the layer scan's leaves lead with [L]
    TOP = {"embedding": ("model", "embed", "embedding"),
           "final_norm": ("model", "norm", "scale"),
           "lm_head": ("lm_head", "kernel")}
    LAYERS = ("model", "layers", "layer")
    PER_LAYER = {"input_norm": ("input_norm", "scale"),
                 "post_norm": ("post_norm", "scale"),
                 "q_proj": ("attn", "qkv", "q_kernel"),
                 "k_proj": ("attn", "qkv", "k_kernel"),
                 "v_proj": ("attn", "qkv", "v_kernel"),
                 "o_proj": ("attn", "o_proj", "kernel")}

    def __init__(self, params, config: dict):
        self.tree = params["params"]
        self.layers = self._get(self.tree, self.LAYERS)
        self.hidden = config["hidden_size"]
        self.inter = config["intermediate_size"]

    @staticmethod
    def _get(tree, path):
        for k in path:
            tree = tree[k]
        return tree

    def mlp_at(self, layer, expert):
        """The node that holds gate, up and down, and the index of this
        layer's (or expert's) slice of its leaves."""
        return self.layers["mlp"], layer

    def __call__(self, name: str, layer: int = None, expert: int = None):
        if name in self.TOP:
            w = _f32(self._get(self.tree, self.TOP[name]))
            return w.T if name == "lm_head" else w
        if name in self.PER_LAYER:
            w = _f32(self._get(self.layers, self.PER_LAYER[name])[layer])
            return w.T if w.ndim == 2 else w
        node, index = self.mlp_at(layer, expert)
        if name == "down":
            (down,) = [v for k, v in _leaves(node).items() if "down" in k]
            return _f32(down[index]).T
        if name in ("gate", "up"):
            return gate_or_up(node, index, int(name == "up"), self.hidden,
                              self.inter).T
        raise KeyError(name)


published = Published
