"""The deepseek_v32 family (DeepSeek-V3.2): the latent family's attention
(``kv_b`` stored absorbed as ``k_up`` and ``v_up``) under YaRN over the
positions an indexer selects (``index_q_b``, ``index_k``,
``index_k_norm``, ``index_w`` beside the attention's leaves), one stack of
leading dense layers and one of expert layers, a sigmoid router with a
selection bias whose choice is limited by groups, the routed experts this
device holds and a shared expert. Every published key is read or refused
by the package's ``DeepseekV32Config.from_published``.

A configuration file may give the chip's share of a deployment under
``share``: ``n_routed_experts`` and ``vocab_size`` are then what is held
here (both listed in its ``reduced``), ``share.n_routed_experts_published``
the experts the router scores, and ``share.first_expert`` the published
index of the first expert held.

**The index key's LayerNorm bias is not served as drawn.**
``harness.make_weights`` draws every leaf that is no norm's scale from
``N(0, initializer_range)``; a bias of 0.02 beside a normed key of order
one would move no selection, and a check that passes without the bias
guards nothing of it. So the configuration this family builds reads the
bias leaf times :data:`KEY_BIAS_STD` ``/ initializer_range`` (``N(0,
0.5)``: of the order of the normed key), in front of the package's paged
forward (inside the timed step and the probe alike) and in front of what
the reference reads (``families/longcat_flash.py``'s ``with_seeded_bias``
is the pattern). The router's selection bias is served as drawn, as
GLM's is. The package's model stores and computes the parameters as they
are; the mapping is this file's.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Tuple

from families import laguna, longcat_flash

#: the standard deviation the index key's LayerNorm bias is served at
KEY_BIAS_STD = 0.5
STACKS = ("layers_dense", "layers_moe")


def with_seeded_key_bias(params, std: float):
    """``params`` with every layer's ``index_k_norm`` bias (drawn ``N(0,
    std)``) at :data:`KEY_BIAS_STD`."""
    model = dict(params["params"]["model"])
    for name in STACKS:
        if name not in model:
            continue
        layer = dict(model[name]["layer"])
        attn = dict(layer["attn"])
        norm = attn["index_k_norm"]
        attn["index_k_norm"] = {**norm,
                                "bias": norm["bias"] * (KEY_BIAS_STD / std)}
        layer["attn"] = attn
        model[name] = {**model[name], "layer": layer}
    return {**params, "params": {**params["params"], "model": model}}


@functools.lru_cache(maxsize=None)
def _seeded_config():
    from neuronx_distributed_tpu.models import deepseek_v32 as ds

    def forward(cfg, params, *args, **kw):
        return ds.deepseek_v32_forward_with_cache(
            cfg, with_seeded_key_bias(params, cfg.init_std), *args, **kw)

    @dataclasses.dataclass(frozen=True)
    class SeededDeepseekV32Config(ds.DeepseekV32Config):
        """The package's config, served from weights whose index keys'
        LayerNorm bias is a normal draw at ``init_std`` to be read at
        :data:`KEY_BIAS_STD`."""

        init_std: float = 0.02

        def serving_family(self):
            return dataclasses.replace(super().serving_family(),
                                       forward=forward)

    return SeededDeepseekV32Config, forward


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import deepseek_v32 as ds

    config, forward = _seeded_config()
    share = c.get("share", {})
    published = {k: v for k, v in c.items() if k in ds.PUBLISHED_KEYS}
    published["n_routed_experts"] = int(share.get(
        "n_routed_experts_published", c["n_routed_experts"]))
    cfg = config.from_published(
        published, init_std=float(c["initializer_range"]),
        experts_held=(int(share.get("first_expert", 0)),
                      int(c["n_routed_experts"])), **kw)
    return cfg, ds.DeepseekV32ForCausalLM(cfg), forward


class Published(longcat_flash.Published):
    """The served tree under the names ``reference/deepseek_v32_f32.py``
    lists (DeepSeek-V3's, the indexer's beside them), a layer's leaves by
    an index that is an operand (``families/laguna._at``): a layer found
    in its kind's stack (``model/layers_dense``, ``model/layers_moe``);
    ``kv_b_proj`` put together from the absorbed leaves in the
    checkpoint's shape (a head's key rows and then its value rows); the
    index key's LayerNorm bias as :func:`with_seeded_key_bias` serves it;
    a routed expert by its published index, the held ones alone (another
    is a ``KeyError``); the embedding and the head are the vocabulary's
    slice."""

    ROUTER = {"mlp.gate": "kernel",
              "mlp.gate.e_score_correction_bias": "bias"}
    INDEXER = {"wq_b": ("index_q_b",), "wk": ("index_k",),
               "k_norm.weight": ("index_k_norm", "scale"),
               "k_norm.bias": ("index_k_norm", "bias"),
               "weights_proj": ("index_w",)}

    def __init__(self, params, config: dict):
        self.tree = with_seeded_key_bias(
            params, float(config["initializer_range"]))["params"]
        self.hidden = config["hidden_size"]
        self.dense = config["first_k_dense_replace"]
        self.widths = {"mlp": config["intermediate_size"],
                       "experts": config["moe_intermediate_size"],
                       "shared_experts": config["moe_intermediate_size"]
                       * config["n_shared_experts"]}
        self.first = int(config.get("share", {}).get("first_expert", 0))
        self.held = int(config["n_routed_experts"])

    def where(self, layer):
        """A layer's stack and its index in it."""
        kind, index = (("dense", layer) if layer < self.dense
                       else ("moe", layer - self.dense))
        return self.tree["model"][f"layers_{kind}"]["layer"], index

    def __call__(self, name: str, layer: int = None, expert: int = None):
        import jax.numpy as jnp

        if name == "lm_head":
            return laguna._transposed_f32(
                self._get(self.tree, self.TOP[name]))
        if name in self.TOP:
            return super().__call__(name)
        stack, index = self.where(layer)
        if name in self.NORMS:
            return laguna._at(stack[self.NORMS[name]]["scale"], index)
        if name in self.ROUTER:
            w = laguna._at(stack["moe"]["router"][self.ROUTER[name]], index)
            return w.T if w.ndim == 2 else w
        group, _, tensor = name.rpartition(".")
        if name.startswith("self_attn.indexer."):
            w = laguna._at(self._get(stack["attn"], self.INDEXER[
                name.removeprefix("self_attn.indexer.")]), index)
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
            if not 0 <= expert - self.first < self.held:
                raise KeyError(f"expert {expert} is held elsewhere")
            return self._glu(stack["moe"]["experts"],
                             (index, expert - self.first), tensor,
                             self.widths["experts"])
        raise KeyError(name)


published = Published
