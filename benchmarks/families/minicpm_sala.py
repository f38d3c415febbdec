"""The minicpm_sala family: layers of two kinds by ``mixer_types``, each
kind's parameters one stack of the served tree, the llama family's
projections, norms and MLP, with QK norms, an output gate and (lightning
layers) an output norm."""

from __future__ import annotations

from typing import Any, Callable, Tuple

from families import llama

KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def published_depth(c: dict) -> int:
    cut = c.get("reduced", {}).get("num_hidden_layers")
    return int(cut["from"]) if cut else int(c["num_hidden_layers"])


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import minicpm_sala
    from neuronx_distributed_tpu.ops.sparse_attention import SparseSpec

    if (c["lightning_head_dim"] != c["head_dim"] or c["attn_use_rope"]
            or not c["lightning_use_rope"]):
        raise ValueError("minicpm_sala: one head_dim for both mixers, rotary "
                         "on the lightning layers only")
    cfg = minicpm_sala.MiniCPMSALAConfig(**{
        **llama.common(c), "mixer_types": tuple(c["mixer_types"]),
        "lightning_heads": c["lightning_nh"],
        "lightning_kv_heads": c["lightning_nkv"],
        "qk_norm": bool(c["qk_norm"]),
        "attn_output_gate": bool(c["attn_use_output_gate"]),
        "scale_emb": float(c["scale_emb"]),
        "scale_depth": float(c["scale_depth"]),
        "mup_depth": published_depth(c),
        "dim_model_base": int(c["dim_model_base"]),
        "sparse": SparseSpec(**c["sparse"]), **kw})
    return (cfg, minicpm_sala.MiniCPMSALAForCausalLM(cfg),
            minicpm_sala.minicpm_sala_forward_with_cache)


class Published(llama.Published):
    """As the llama family's, a layer found in its kind's stack
    (``model/layers_sparse``, ``model/layers_lightning``) at its index
    among the layers of that kind, with ``g_proj [N * D, H]`` (the output
    gate), ``q_norm``, ``k_norm`` and, for a lightning layer, ``o_norm``
    ``[D]``."""

    PER_LAYER = dict(llama.Published.PER_LAYER,
                     g_proj=("attn", "o_gate", "kernel"),
                     q_norm=("attn", "q_norm", "scale"),
                     k_norm=("attn", "k_norm", "scale"),
                     o_norm=("attn", "o_norm", "scale"))

    def __init__(self, params, config: dict):
        self.tree = params["params"]
        self.hidden = config["hidden_size"]
        self.inter = config["intermediate_size"]
        kinds = [KINDS[m] for m in config["mixer_types"]]
        #: layer -> (its kind's stack, its index in it)
        self.where = [(self.tree["model"][f"layers_{k}"]["layer"],
                       kinds[:i].count(k)) for i, k in enumerate(kinds)]

    def mlp_at(self, layer, expert):
        stack, index = self.where[layer]
        return stack["mlp"], index

    def __call__(self, name: str, layer: int = None, expert: int = None):
        if name in self.PER_LAYER:
            stack, index = self.where[layer]
            w = llama._f32(self._get(stack, self.PER_LAYER[name])[index])
            return w.T if w.ndim == 2 else w
        return super().__call__(name, layer, expert)


published = Published
