"""The xing4 family (Xing4.0-29B-A4B, ``xing4_0``): the latent family's
attention (``kv_b`` stored absorbed as ``k_up`` and ``v_up``) under YaRN,
one stack of leading dense layers and one of expert layers, a sigmoid
router with a selection bias, and a residual path of ``hc_mult`` streams
that every sublayer mixes by maps of its own (``hc_attn``, ``hc_ffn``:
``phi``, ``alpha``, ``bias``). Every published key is read or refused by
the package's ``Xing4Config.from_published``.

**The mixing's ``alpha`` and ``bias`` are not served as drawn.**
``harness.make_weights`` draws every leaf that is no norm's scale from
``N(0, initializer_range)``; an ``alpha`` of 0.02 would leave the three
maps all but static (a check that a static map passes guards nothing).
So the configuration this family builds reads ``alpha`` as ``1 + leaf``
(a column of ``(r v) Phi`` then has a standard deviation of
``initializer_range x sqrt(hc_mult x hidden_size)``: 2.4 at the published
widths, so ``H_pre`` runs over most of (0, 1) between tokens and
``H_res`` between near-uniform and near-permutation matrices) and
``bias`` as the leaf times :data:`BIAS_STD` ``/ initializer_range``
(``N(0, 1)``: the streams differ in their static part too), in front of
the package's paged forward and in front of what the reference reads
alike (``families/longcat_flash.py``'s ``with_seeded_bias`` is the
pattern). ``phi`` and the selection bias are served as drawn. The
package's model stores and computes the parameters as they are; the
mapping is this file's.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Tuple

import harness
from families import laguna, longcat_flash

#: the standard deviation the mixing's static part is served at
BIAS_STD = 1.0
STACKS = ("layers_dense", "layers_moe")
MIXINGS = ("hc_attn", "hc_ffn")


def with_seeded_mixing(params, std: float):
    """``params`` with every mixing's ``alpha`` (drawn ``N(0, std)``) at
    ``1 + leaf`` and its ``bias`` at :data:`BIAS_STD`."""
    model = dict(params["params"]["model"])
    for name in STACKS:
        if name not in model:
            continue
        layer = dict(model[name]["layer"])
        for mixing in MIXINGS:
            leaves = layer[mixing]
            layer[mixing] = {**leaves, "alpha": 1.0 + leaves["alpha"],
                             "bias": leaves["bias"] * (BIAS_STD / std)}
        model[name] = {**model[name], "layer": layer}
    return {**params, "params": {**params["params"], "model": model}}


@functools.lru_cache(maxsize=None)
def _seeded_config():
    from neuronx_distributed_tpu.models import xing4

    def forward(cfg, params, *args, **kw):
        return xing4.xing4_forward_with_cache(
            cfg, with_seeded_mixing(params, cfg.init_std), *args, **kw)

    @dataclasses.dataclass(frozen=True)
    class SeededXing4Config(xing4.Xing4Config):
        """The package's config, served from weights whose mixing leaves
        are normal draws at ``init_std`` to be read as above."""

        init_std: float = 0.02

        def serving_family(self):
            return dataclasses.replace(super().serving_family(),
                                       forward=forward)

    return SeededXing4Config, forward


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import xing4

    config, forward = _seeded_config()
    cfg = config.from_published(
        c, init_std=float(c["initializer_range"]), **kw)
    return cfg, xing4.Xing4ForCausalLM(cfg), forward


@functools.lru_cache(maxsize=None)
def _head_rows():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames="rows")
    def take(kernel, block, rows):
        """Rows ``block * rows`` on of the published ``[V, H]`` head,
        float32, from the package's ``[H, V]``."""
        return jax.lax.dynamic_slice_in_dim(
            kernel, block * rows, rows, axis=1).T.astype(jnp.float32)

    return take


class Published(longcat_flash.Published):
    """The served tree under the names ``reference/xing4_f32.py`` lists
    (DeepSeek-V3's for attention and experts, as the other latent
    family's ``ATTENTION``, ``NORMS`` and ``_glu`` find them; the
    mixing's are this repository's), a layer's leaves by an index that is
    an operand (``families/laguna._at``): a layer found in its kind's stack
    (``model/layers_dense``, ``model/layers_moe``); ``kv_b_proj`` put
    together from the absorbed leaves in the checkpoint's shape (a head's
    key rows and then its value rows); the mixing's ``alpha`` and
    ``bias`` as :func:`with_seeded_mixing` serves them; the head in
    blocks of the reference's ``HEAD_BLOCK`` rows of the vocabulary."""

    ROUTER = {"mlp.gate": "kernel",
              "mlp.gate.e_score_correction_bias": "bias"}

    def __init__(self, params, config: dict):
        self.tree = with_seeded_mixing(
            params, float(config["initializer_range"]))["params"]
        self.hidden = config["hidden_size"]
        self.dense = config["first_k_dense_replace"]
        self.widths = {"mlp": config["intermediate_size"],
                       "experts": config["moe_intermediate_size"],
                       "shared_experts": config["moe_intermediate_size"]
                       * config["n_shared_experts"]}
        block = harness.load_plugin(
            "reference", config["reference"]).HEAD_BLOCK
        self.head_rows = min(block, config["vocab_size"])
        if config["vocab_size"] % self.head_rows:
            raise ValueError(f"vocab_size {config['vocab_size']} is no "
                             f"multiple of the head's block {block}")

    def where(self, layer):
        """A layer's stack and its index in it."""
        kind, index = (("dense", layer) if layer < self.dense
                       else ("moe", layer - self.dense))
        return self.tree["model"][f"layers_{kind}"]["layer"], index

    def __call__(self, name: str, layer: int = None, expert: int = None):
        import jax.numpy as jnp

        if name == "lm_head":
            kernel = self._get(self.tree, self.TOP[name])
            if layer is None:
                return laguna._transposed_f32(kernel)
            return _head_rows()(kernel, jnp.int32(layer),
                                rows=self.head_rows)
        if name in self.TOP:
            return super().__call__(name)
        stack, index = self.where(layer)
        if name in self.NORMS:
            return laguna._at(stack[self.NORMS[name]]["scale"], index)
        if name in self.ROUTER:
            w = laguna._at(stack["moe"]["router"][self.ROUTER[name]], index)
            return w.T if w.ndim == 2 else w
        group, _, tensor = name.rpartition(".")
        if group in MIXINGS:
            w = laguna._at(stack[group][tensor], index)
            return w.T if w.ndim == 2 else w
        if group == "self_attn":
            attn = stack["attn"]
            if tensor == "kv_b_proj":
                k_up = laguna._at(attn["k_up"], index)      # [N, nope, r]
                v_up = laguna._at(attn["v_up"], index)      # [N, r, v]
                return jnp.concatenate([k_up, v_up.swapaxes(1, 2)],
                                       axis=1).reshape(-1, k_up.shape[-1])
            w = laguna._at(self._get(attn, self.ATTENTION[tensor]), index)
            return w.T if w.ndim == 2 else w
        if group == "mlp" and layer < self.dense:
            return self._glu(stack["mlp"], index, tensor, self.widths["mlp"])
        if group == "mlp.shared_experts":
            return self._glu(stack["moe"]["shared"], index, tensor,
                             self.widths["shared_experts"])
        if group == "mlp.experts":
            return self._glu(stack["moe"]["experts"], (index, expert),
                             tensor, self.widths["experts"])
        raise KeyError(name)


published = Published
