"""HuggingFace ↔ framework checkpoint conversion.

Analogue of the reference's ``scripts/checkpoint_converter.py``
(``CheckpointConverterBase:23``: full↔TP/PP-sharded conversion, QKV
fuse/split with the GQA kv multiplier ``convert_full_state_to_tp:513``,
``merge_tp_checkpoints:317``).

TPU-native simplification: sharding is NOT baked into files — the framework
checkpoint is the *unsharded* param pytree (placement happens at load via
NamedSharding, and resharding between parallel configs is automatic, see
``trainer/checkpoint.py``). So conversion here is pure *naming/layout*
translation between the HF llama state dict and our scanned param tree:

=============================================  =============================
HF (torch ``[out, in]`` layout)                ours (``[in, out]``; layers
                                               stacked on a leading L dim)
=============================================  =============================
model.embed_tokens.weight                      model/embed/embedding
model.layers.N.self_attn.{q,k,v}_proj.weight   model/layers/layer/attn/qkv/
                                               {q,k,v}_kernel
model.layers.N.self_attn.o_proj.weight         model/layers/layer/attn/o_proj
model.layers.N.mlp.{gate,up}_proj.weight       .../mlp/{gate,up}_kernel [H, I]
model.layers.N.mlp.down_proj.weight            model/layers/layer/mlp/down
model.layers.N.input_layernorm.weight          .../input_norm/scale
model.layers.N.post_attention_layernorm.weight .../post_norm/scale
model.norm.weight                              model/norm/scale
lm_head.weight                                 lm_head/kernel
=============================================  =============================

Gate and up are two leaves (Mixtral's ``w1``/``w3``: ``experts/gate``,
``experts/up`` ``[L, E, H, I]``) through ``modules/glu.py``, which owns the
stored form and says why it is not one fused leaf (the chip's tiling); it
refuses a tree in the fused form by name, and converting from the
published tensors is the way across.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from ..modules import glu


def _t(w) -> np.ndarray:
    """torch [out, in] -> [in, out]."""
    return np.ascontiguousarray(np.asarray(w).T)


def convert_hf_llama_to_nxd(state_dict: Dict[str, Any], cfg) -> Dict:
    """HF llama state dict (numpy/torch tensors) → our param tree
    (``LlamaForCausalLM`` with ``scan_layers=True``)."""
    sd = {k: np.asarray(v.float().numpy() if hasattr(v, "numpy") else v)
          for k, v in state_dict.items()}
    L = cfg.num_layers

    def stack(fmt: str, transform=_t) -> np.ndarray:
        return np.stack([transform(sd[fmt.format(i)]) for i in range(L)])

    layers = {
        "attn": {
            "qkv": {
                "q_kernel": stack(
                    "model.layers.{}.self_attn.q_proj.weight"),
                "k_kernel": stack(
                    "model.layers.{}.self_attn.k_proj.weight"),
                "v_kernel": stack(
                    "model.layers.{}.self_attn.v_proj.weight"),
            },
            "o_proj": {"kernel": stack(
                "model.layers.{}.self_attn.o_proj.weight")},
        },
        "mlp": {
            **glu.from_published(
                stack("model.layers.{}.mlp.gate_proj.weight", np.asarray),
                stack("model.layers.{}.mlp.up_proj.weight", np.asarray),
                glu.DENSE),
            "down": {"kernel": stack("model.layers.{}.mlp.down_proj.weight")},
        },
        "input_norm": {"scale": stack(
            "model.layers.{}.input_layernorm.weight", np.asarray)},
        "post_norm": {"scale": stack(
            "model.layers.{}.post_attention_layernorm.weight", np.asarray)},
    }
    tree = {"params": {
        "model": {
            "embed": {"embedding": sd["model.embed_tokens.weight"]},
            "layers": {"layer": layers},
            "norm": {"scale": sd["model.norm.weight"]},
        },
    }}
    if getattr(cfg, "tie_embeddings", False):
        # tied models carry no lm_head param (llama.py tie_embeddings);
        # matches HF's tie_word_embeddings checkpoints omitting
        # lm_head.weight
        return tree
    lm_head = (sd["lm_head.weight"] if "lm_head.weight" in sd
               else sd["model.embed_tokens.weight"])
    tree["params"]["lm_head"] = {"kernel": _t(lm_head)}
    return tree


def convert_nxd_to_hf_llama(params: Dict, cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`convert_hf_llama_to_nxd`."""
    p = params["params"]
    layers = p["model"]["layers"]["layer"]
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(
            p["model"]["embed"]["embedding"]),
        "model.norm.weight": np.asarray(p["model"]["norm"]["scale"]),
    }
    if "lm_head" in p:
        out["lm_head.weight"] = _t(p["lm_head"]["kernel"])
    # tied models (no lm_head param) export the HF tie_word_embeddings
    # convention: lm_head.weight omitted, embed_tokens carries the table
    L = cfg.num_layers
    gate_proj, up_proj = glu.to_published(layers["mlp"], glu.DENSE)
    for i in range(L):
        pre = f"model.layers.{i}."
        qkv = layers["attn"]["qkv"]
        out[pre + "self_attn.q_proj.weight"] = _t(qkv["q_kernel"][i])
        out[pre + "self_attn.k_proj.weight"] = _t(qkv["k_kernel"][i])
        out[pre + "self_attn.v_proj.weight"] = _t(qkv["v_kernel"][i])
        out[pre + "self_attn.o_proj.weight"] = _t(
            layers["attn"]["o_proj"]["kernel"][i])
        out[pre + "mlp.gate_proj.weight"] = gate_proj[i]
        out[pre + "mlp.up_proj.weight"] = up_proj[i]
        out[pre + "mlp.down_proj.weight"] = _t(
            layers["mlp"]["down"]["kernel"][i])
        out[pre + "input_layernorm.weight"] = np.asarray(
            layers["input_norm"]["scale"][i])
        out[pre + "post_attention_layernorm.weight"] = np.asarray(
            layers["post_norm"]["scale"][i])
    return out


def _stack(sd: Dict[str, Any], fmt: str, num_layers: int,
           transform=_t) -> np.ndarray:
    """Stack per-layer HF tensors onto the leading scan dim (the analogue of
    the reference ``CheckpointConverterBase`` layer loops,
    ``scripts/checkpoint_converter.py:171-266``)."""
    return np.stack([transform(sd[fmt.format(i)])
                     for i in range(num_layers)])


def _asnp(w) -> np.ndarray:
    return np.asarray(w)


def convert_hf_mixtral_to_nxd(state_dict: Dict[str, Any], cfg) -> Dict:
    """HF Mixtral state dict → our param tree (``MixtralForCausalLM``,
    ``scan_layers=True``). Expert stacking: HF's per-expert ``w1``
    (gate) / ``w3`` (up) stack to ``gate``, ``up`` ``[L, E, H, I]``; ``w2``
    (down) stacks to ``[L, E, I, H]`` (reference Mixtral conversion)."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, E = cfg.num_layers, cfg.num_experts

    def experts(w: str, transform=_t) -> np.ndarray:
        """One published expert tensor, stacked ``[L, E, ...]``."""
        return np.stack([np.stack([transform(sd[
            f"model.layers.{i}.block_sparse_moe.experts.{e}.{w}.weight"])
            for e in range(E)]) for i in range(L)])

    layers = {
        "attn": {
            "qkv": {
                "q_kernel": _stack(
                    sd, "model.layers.{}.self_attn.q_proj.weight", L),
                "k_kernel": _stack(
                    sd, "model.layers.{}.self_attn.k_proj.weight", L),
                "v_kernel": _stack(
                    sd, "model.layers.{}.self_attn.v_proj.weight", L),
            },
            "o_proj": {"kernel": _stack(
                sd, "model.layers.{}.self_attn.o_proj.weight", L)},
        },
        "moe": {
            "router": {"kernel": _stack(
                sd, "model.layers.{}.block_sparse_moe.gate.weight", L)},
            "experts": {
                **glu.from_published(experts("w1", _asnp),
                                      experts("w3", _asnp), glu.EXPERTS),
                "down": experts("w2"),  # [L, E, I, H]
            },
        },
        "input_norm": {"scale": _stack(
            sd, "model.layers.{}.input_layernorm.weight", L, _asnp)},
        "post_norm": {"scale": _stack(
            sd, "model.layers.{}.post_attention_layernorm.weight", L,
            _asnp)},
    }
    # MixtralForCausalLM has no tied-head path (HF Mixtral never ties);
    # always materialise lm_head (from embed_tokens when the HF checkpoint
    # omits it)
    lm_head = (sd["lm_head.weight"] if "lm_head.weight" in sd
               else sd["model.embed_tokens.weight"])
    return {"params": {
        "model": {
            "embed": {"embedding": sd["model.embed_tokens.weight"]},
            "layers": {"layer": layers},
            "norm": {"scale": sd["model.norm.weight"]},
        },
        "lm_head": {"kernel": _t(lm_head)},
    }}


def convert_hf_neox_to_nxd(state_dict: Dict[str, Any], cfg) -> Dict:
    """HF GPT-NeoX state dict → our param tree (``GPTNeoXForCausalLM``).

    The HF fused ``query_key_value`` is laid out head-major
    ``[heads, 3, head_dim]`` on the output dim — the split/fuse the
    reference's converter handles with its qkv helpers
    (``checkpoint_converter.py:513``)."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, n, hd = cfg.num_layers, cfg.num_heads, cfg.head_dim
    h = cfg.hidden_size

    def qkv_w(i, j):
        w = sd[f"gpt_neox.layers.{i}.attention.query_key_value.weight"]
        return _t(w.reshape(n, 3, hd, h)[:, j].reshape(n * hd, h))

    def qkv_b(i, j):
        b = sd[f"gpt_neox.layers.{i}.attention.query_key_value.bias"]
        return b.reshape(n, 3, hd)[:, j].reshape(n * hd)

    layers = {
        "attn": {
            "qkv": {
                "q_kernel": np.stack([qkv_w(i, 0) for i in range(L)]),
                "k_kernel": np.stack([qkv_w(i, 1) for i in range(L)]),
                "v_kernel": np.stack([qkv_w(i, 2) for i in range(L)]),
                "q_bias": np.stack([qkv_b(i, 0) for i in range(L)]),
                "k_bias": np.stack([qkv_b(i, 1) for i in range(L)]),
                "v_bias": np.stack([qkv_b(i, 2) for i in range(L)]),
            },
            "o_proj": {
                "kernel": _stack(
                    sd, "gpt_neox.layers.{}.attention.dense.weight", L),
                "bias": _stack(
                    sd, "gpt_neox.layers.{}.attention.dense.bias", L,
                    _asnp),
            },
        },
        "mlp": {
            "up": {
                "kernel": _stack(
                    sd, "gpt_neox.layers.{}.mlp.dense_h_to_4h.weight", L),
                "bias": _stack(
                    sd, "gpt_neox.layers.{}.mlp.dense_h_to_4h.bias", L,
                    _asnp),
            },
            "down": {
                "kernel": _stack(
                    sd, "gpt_neox.layers.{}.mlp.dense_4h_to_h.weight", L),
                "bias": _stack(
                    sd, "gpt_neox.layers.{}.mlp.dense_4h_to_h.bias", L,
                    _asnp),
            },
        },
        "ln1": {
            "scale": _stack(
                sd, "gpt_neox.layers.{}.input_layernorm.weight", L, _asnp),
            "bias": _stack(
                sd, "gpt_neox.layers.{}.input_layernorm.bias", L, _asnp),
        },
        "ln2": {
            "scale": _stack(
                sd, "gpt_neox.layers.{}.post_attention_layernorm.weight",
                L, _asnp),
            "bias": _stack(
                sd, "gpt_neox.layers.{}.post_attention_layernorm.bias", L,
                _asnp),
        },
    }
    return {"params": {
        "embed": {"embedding": sd["gpt_neox.embed_in.weight"]},
        "layers": {"layer": layers},
        "final_norm": {"scale": sd["gpt_neox.final_layer_norm.weight"],
                       "bias": sd["gpt_neox.final_layer_norm.bias"]},
        "lm_head": {"kernel": _t(sd["embed_out.weight"])},
    }}


def convert_hf_bert_to_nxd(state_dict: Dict[str, Any], cfg) -> Dict:
    """HF BertForMaskedLM state dict → our param tree
    (``BertForPreTraining`` with ``mlm_transform=True``)."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L = cfg.num_layers
    pre = "bert.encoder.layer.{}."

    def attn(part, what):
        return _stack(sd, pre + f"attention.self.{part}.{what}", L,
                      _t if what == "weight" else _asnp)

    layers = {
        "qkv": {
            "q_kernel": attn("query", "weight"),
            "k_kernel": attn("key", "weight"),
            "v_kernel": attn("value", "weight"),
            "q_bias": attn("query", "bias"),
            "k_bias": attn("key", "bias"),
            "v_bias": attn("value", "bias"),
        },
        "o_proj": {
            "kernel": _stack(sd, pre + "attention.output.dense.weight", L),
            "bias": _stack(sd, pre + "attention.output.dense.bias", L,
                           _asnp),
        },
        "ln_attn": {
            "scale": _stack(sd, pre + "attention.output.LayerNorm.weight",
                            L, _asnp),
            "bias": _stack(sd, pre + "attention.output.LayerNorm.bias", L,
                           _asnp),
        },
        "up": {
            "kernel": _stack(sd, pre + "intermediate.dense.weight", L),
            "bias": _stack(sd, pre + "intermediate.dense.bias", L, _asnp),
        },
        "down": {
            "kernel": _stack(sd, pre + "output.dense.weight", L),
            "bias": _stack(sd, pre + "output.dense.bias", L, _asnp),
        },
        "ln_mlp": {
            "scale": _stack(sd, pre + "output.LayerNorm.weight", L, _asnp),
            "bias": _stack(sd, pre + "output.LayerNorm.bias", L, _asnp),
        },
    }
    return {"params": {
        "embed": {
            "embedding": sd["bert.embeddings.word_embeddings.weight"]},
        "position_embedding":
            sd["bert.embeddings.position_embeddings.weight"],
        "type_embedding":
            sd["bert.embeddings.token_type_embeddings.weight"],
        "embed_norm": {"scale": sd["bert.embeddings.LayerNorm.weight"],
                       "bias": sd["bert.embeddings.LayerNorm.bias"]},
        "layers": {"layer": layers},
        "mlm_transform": {
            "kernel": _t(sd["cls.predictions.transform.dense.weight"]),
            "bias": sd["cls.predictions.transform.dense.bias"],
        },
        "mlm_norm": {
            "scale": sd["cls.predictions.transform.LayerNorm.weight"],
            "bias": sd["cls.predictions.transform.LayerNorm.bias"],
        },
        "mlm_bias": sd["cls.predictions.bias"],
    }}


def convert_hf_vit_to_nxd(state_dict: Dict[str, Any], cfg) -> Dict:
    """HF ``ViTForImageClassification`` state dict → our param tree
    (``models.vit.ViTForImageClassification``). The stride-``p`` Conv2d
    patch projection flattens to the dense kernel ``[C*p*p, hidden]`` in
    (c, i, j) element order — see ``models.vit.patchify``."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L = cfg.num_layers
    pre = "vit.encoder.layer.{}."

    def attn(part, what):
        return _stack(sd, pre + f"attention.attention.{part}.{what}", L,
                      _t if what == "weight" else _asnp)

    def ln(which, what):
        return _stack(sd, pre + f"layernorm_{which}.{what}", L, _asnp)

    layers = {
        "ln_before": {"scale": ln("before", "weight"),
                      "bias": ln("before", "bias")},
        "qkv": {
            "q_kernel": attn("query", "weight"),
            "k_kernel": attn("key", "weight"),
            "v_kernel": attn("value", "weight"),
            "q_bias": attn("query", "bias"),
            "k_bias": attn("key", "bias"),
            "v_bias": attn("value", "bias"),
        },
        "o_proj": {
            "kernel": _stack(sd, pre + "attention.output.dense.weight", L),
            "bias": _stack(sd, pre + "attention.output.dense.bias", L,
                           _asnp),
        },
        "ln_after": {"scale": ln("after", "weight"),
                     "bias": ln("after", "bias")},
        "up": {
            "kernel": _stack(sd, pre + "intermediate.dense.weight", L),
            "bias": _stack(sd, pre + "intermediate.dense.bias", L, _asnp),
        },
        "down": {
            "kernel": _stack(sd, pre + "output.dense.weight", L),
            "bias": _stack(sd, pre + "output.dense.bias", L, _asnp),
        },
    }
    proj = sd["vit.embeddings.patch_embeddings.projection.weight"]
    return {"params": {
        "patch_proj": {
            "kernel": proj.reshape(proj.shape[0], -1).T,
            "bias": sd["vit.embeddings.patch_embeddings.projection.bias"],
        },
        "cls_token": sd["vit.embeddings.cls_token"],
        "position_embedding": sd["vit.embeddings.position_embeddings"][0],
        "layers": {"layer": layers},
        "final_norm": {"scale": sd["vit.layernorm.weight"],
                       "bias": sd["vit.layernorm.bias"]},
        "classifier": {"kernel": _t(sd["classifier.weight"]),
                       "bias": sd["classifier.bias"]},
    }}


def convert_nxd_to_hf_mixtral(params: Dict, cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`convert_hf_mixtral_to_nxd` (per-expert w1/w3/w2
    unstacked from the ``gate``/``up``/``down`` banks)."""
    p = params["params"]
    layers = p["model"]["layers"]["layer"]
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(
            p["model"]["embed"]["embedding"]),
        "model.norm.weight": np.asarray(p["model"]["norm"]["scale"]),
        "lm_head.weight": _t(p["lm_head"]["kernel"]),
    }
    w1, w3 = glu.to_published(layers["moe"]["experts"], glu.EXPERTS)
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        qkv = layers["attn"]["qkv"]
        out[pre + "self_attn.q_proj.weight"] = _t(qkv["q_kernel"][i])
        out[pre + "self_attn.k_proj.weight"] = _t(qkv["k_kernel"][i])
        out[pre + "self_attn.v_proj.weight"] = _t(qkv["v_kernel"][i])
        out[pre + "self_attn.o_proj.weight"] = _t(
            layers["attn"]["o_proj"]["kernel"][i])
        out[pre + "block_sparse_moe.gate.weight"] = _t(
            layers["moe"]["router"]["kernel"][i])
        dn = np.asarray(layers["moe"]["experts"]["down"][i])     # [E,I,H]
        for e in range(cfg.num_experts):
            epre = pre + f"block_sparse_moe.experts.{e}."
            out[epre + "w1.weight"] = w1[i, e]
            out[epre + "w3.weight"] = w3[i, e]
            out[epre + "w2.weight"] = _t(dn[e])
        out[pre + "input_layernorm.weight"] = np.asarray(
            layers["input_norm"]["scale"][i])
        out[pre + "post_attention_layernorm.weight"] = np.asarray(
            layers["post_norm"]["scale"][i])
    return out


def convert_nxd_to_hf_neox(params: Dict, cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`convert_hf_neox_to_nxd` (re-fuses q/k/v into the
    HF head-major ``query_key_value`` layout ``[heads, 3, head_dim]``)."""
    p = params["params"]
    layers = p["layers"]["layer"]
    n, hd, h = cfg.num_heads, cfg.head_dim, cfg.hidden_size
    out: Dict[str, np.ndarray] = {
        "gpt_neox.embed_in.weight": np.asarray(p["embed"]["embedding"]),
        "gpt_neox.final_layer_norm.weight": np.asarray(
            p["final_norm"]["scale"]),
        "gpt_neox.final_layer_norm.bias": np.asarray(
            p["final_norm"]["bias"]),
        "embed_out.weight": _t(p["lm_head"]["kernel"]),
    }
    qkv = layers["attn"]["qkv"]
    for i in range(cfg.num_layers):
        pre = f"gpt_neox.layers.{i}."
        w = np.stack([_t(qkv[f"{j}_kernel"][i]).reshape(n, hd, h)
                      for j in ("q", "k", "v")], axis=1)  # [n, 3, hd, h]
        out[pre + "attention.query_key_value.weight"] = w.reshape(
            3 * n * hd, h)
        b = np.stack([np.asarray(qkv[f"{j}_bias"][i]).reshape(n, hd)
                      for j in ("q", "k", "v")], axis=1)
        out[pre + "attention.query_key_value.bias"] = b.reshape(3 * n * hd)
        out[pre + "attention.dense.weight"] = _t(
            layers["attn"]["o_proj"]["kernel"][i])
        out[pre + "attention.dense.bias"] = np.asarray(
            layers["attn"]["o_proj"]["bias"][i])
        out[pre + "mlp.dense_h_to_4h.weight"] = _t(
            layers["mlp"]["up"]["kernel"][i])
        out[pre + "mlp.dense_h_to_4h.bias"] = np.asarray(
            layers["mlp"]["up"]["bias"][i])
        out[pre + "mlp.dense_4h_to_h.weight"] = _t(
            layers["mlp"]["down"]["kernel"][i])
        out[pre + "mlp.dense_4h_to_h.bias"] = np.asarray(
            layers["mlp"]["down"]["bias"][i])
        for ours, hf in (("ln1", "input_layernorm"),
                         ("ln2", "post_attention_layernorm")):
            out[pre + hf + ".weight"] = np.asarray(layers[ours]["scale"][i])
            out[pre + hf + ".bias"] = np.asarray(layers[ours]["bias"][i])
    return out


def convert_nxd_to_hf_bert(params: Dict, cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`convert_hf_bert_to_nxd`; emits the tied
    ``cls.predictions.decoder.*`` aliases HF checkpoints carry."""
    p = params["params"]
    layers = p["layers"]["layer"]
    embed = np.asarray(p["embed"]["embedding"])
    mlm_bias = np.asarray(p["mlm_bias"])
    out: Dict[str, np.ndarray] = {
        "bert.embeddings.word_embeddings.weight": embed,
        "bert.embeddings.position_embeddings.weight": np.asarray(
            p["position_embedding"]),
        "bert.embeddings.token_type_embeddings.weight": np.asarray(
            p["type_embedding"]),
        "bert.embeddings.LayerNorm.weight": np.asarray(
            p["embed_norm"]["scale"]),
        "bert.embeddings.LayerNorm.bias": np.asarray(
            p["embed_norm"]["bias"]),
        "cls.predictions.transform.dense.weight": _t(
            p["mlm_transform"]["kernel"]),
        "cls.predictions.transform.dense.bias": np.asarray(
            p["mlm_transform"]["bias"]),
        "cls.predictions.transform.LayerNorm.weight": np.asarray(
            p["mlm_norm"]["scale"]),
        "cls.predictions.transform.LayerNorm.bias": np.asarray(
            p["mlm_norm"]["bias"]),
        "cls.predictions.bias": mlm_bias,
        "cls.predictions.decoder.weight": embed,
        "cls.predictions.decoder.bias": mlm_bias,
    }
    for i in range(cfg.num_layers):
        pre = f"bert.encoder.layer.{i}."
        qkv = layers["qkv"]
        for j, part in (("q", "query"), ("k", "key"), ("v", "value")):
            out[pre + f"attention.self.{part}.weight"] = _t(
                qkv[f"{j}_kernel"][i])
            out[pre + f"attention.self.{part}.bias"] = np.asarray(
                qkv[f"{j}_bias"][i])
        out[pre + "attention.output.dense.weight"] = _t(
            layers["o_proj"]["kernel"][i])
        out[pre + "attention.output.dense.bias"] = np.asarray(
            layers["o_proj"]["bias"][i])
        out[pre + "attention.output.LayerNorm.weight"] = np.asarray(
            layers["ln_attn"]["scale"][i])
        out[pre + "attention.output.LayerNorm.bias"] = np.asarray(
            layers["ln_attn"]["bias"][i])
        out[pre + "intermediate.dense.weight"] = _t(
            layers["up"]["kernel"][i])
        out[pre + "intermediate.dense.bias"] = np.asarray(
            layers["up"]["bias"][i])
        out[pre + "output.dense.weight"] = _t(layers["down"]["kernel"][i])
        out[pre + "output.dense.bias"] = np.asarray(
            layers["down"]["bias"][i])
        out[pre + "output.LayerNorm.weight"] = np.asarray(
            layers["ln_mlp"]["scale"][i])
        out[pre + "output.LayerNorm.bias"] = np.asarray(
            layers["ln_mlp"]["bias"][i])
    return out


def convert_nxd_to_hf_vit(params: Dict, cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`convert_hf_vit_to_nxd` (dense patch kernel folds
    back into the HF Conv2d layout ``[hidden, C, p, p]``)."""
    p = params["params"]
    layers = p["layers"]["layer"]
    c, pp = cfg.num_channels, cfg.patch_size
    out: Dict[str, np.ndarray] = {
        "vit.embeddings.cls_token": np.asarray(p["cls_token"]),
        "vit.embeddings.position_embeddings": np.asarray(
            p["position_embedding"])[None],
        "vit.embeddings.patch_embeddings.projection.weight": np.asarray(
            p["patch_proj"]["kernel"]).T.reshape(
                cfg.hidden_size, c, pp, pp),
        "vit.embeddings.patch_embeddings.projection.bias": np.asarray(
            p["patch_proj"]["bias"]),
        "vit.layernorm.weight": np.asarray(p["final_norm"]["scale"]),
        "vit.layernorm.bias": np.asarray(p["final_norm"]["bias"]),
        "classifier.weight": _t(p["classifier"]["kernel"]),
        "classifier.bias": np.asarray(p["classifier"]["bias"]),
    }
    for i in range(cfg.num_layers):
        pre = f"vit.encoder.layer.{i}."
        qkv = layers["qkv"]
        for j, part in (("q", "query"), ("k", "key"), ("v", "value")):
            out[pre + f"attention.attention.{part}.weight"] = _t(
                qkv[f"{j}_kernel"][i])
            out[pre + f"attention.attention.{part}.bias"] = np.asarray(
                qkv[f"{j}_bias"][i])
        out[pre + "attention.output.dense.weight"] = _t(
            layers["o_proj"]["kernel"][i])
        out[pre + "attention.output.dense.bias"] = np.asarray(
            layers["o_proj"]["bias"][i])
        out[pre + "intermediate.dense.weight"] = _t(
            layers["up"]["kernel"][i])
        out[pre + "intermediate.dense.bias"] = np.asarray(
            layers["up"]["bias"][i])
        out[pre + "output.dense.weight"] = _t(layers["down"]["kernel"][i])
        out[pre + "output.dense.bias"] = np.asarray(
            layers["down"]["bias"][i])
        for ours, hf in (("ln_before", "layernorm_before"),
                         ("ln_after", "layernorm_after")):
            out[pre + hf + ".weight"] = np.asarray(layers[ours]["scale"][i])
            out[pre + hf + ".bias"] = np.asarray(layers[ours]["bias"][i])
    return out


_NXD2HF = {"llama": convert_nxd_to_hf_llama,
           "mixtral": convert_nxd_to_hf_mixtral,
           "neox": convert_nxd_to_hf_neox,
           "bert": convert_nxd_to_hf_bert,
           "vit": convert_nxd_to_hf_vit}


def _cli_config(family: str, **overrides):
    """Family config with CLI shape overrides (None values dropped — the
    converters read num_experts/num_heads/hidden_size off the config, so
    non-default checkpoints must be able to set them). Overrides a family
    has no field for raise instead of being silently ignored."""
    import dataclasses

    if family == "llama":
        from ..models.llama import LlamaConfig as cls

        extra = {}
    elif family == "mixtral":
        from ..models.mixtral import MixtralConfig as cls

        extra = {}
    elif family == "neox":
        from ..models.gpt_neox import GPTNeoXConfig as cls

        extra = {}
    elif family == "bert":
        from ..models.bert import BertConfig as cls

        extra = {"mlm_transform": True}
    elif family == "vit":
        from ..models.vit import ViTConfig as cls

        extra = {}
    else:
        raise ValueError(f"unknown family {family!r}")  # sync: _HF2NXD
    kw = {k: v for k, v in overrides.items() if v is not None}
    fields = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(kw) - fields)
    if unknown:
        raise SystemExit(
            f"--family {family} has no config field(s) {unknown}")
    return cls(**extra, **kw)


_HF2NXD = {"llama": convert_hf_llama_to_nxd,
           "mixtral": convert_hf_mixtral_to_nxd,
           "neox": convert_hf_neox_to_nxd,
           "bert": convert_hf_bert_to_nxd,
           "vit": convert_hf_vit_to_nxd}


def main(argv=None) -> None:
    """CLI (reference: the ``CheckpointConverterBase`` argparse driver,
    one subclass per model family)."""
    import argparse
    import pickle

    ap = argparse.ArgumentParser(
        description="Convert HF checkpoints to/from the framework "
                    "param-tree format")
    ap.add_argument("--input", required=True,
                    help=".safetensors / torch .bin / pickled tree")
    ap.add_argument("--output", required=True)
    ap.add_argument("--family", choices=sorted(_HF2NXD), default="llama")
    ap.add_argument("--direction", choices=["hf2nxd", "nxd2hf"],
                    default="hf2nxd")
    ap.add_argument("--num-layers", type=int, required=True)
    # shape fields the converters read off the config; defaults are each
    # family's flagship shape — set them for any other checkpoint size
    ap.add_argument("--hidden-size", type=int)
    ap.add_argument("--intermediate-size", type=int)
    ap.add_argument("--num-heads", type=int)
    ap.add_argument("--num-kv-heads", type=int)
    ap.add_argument("--num-experts", type=int)
    ap.add_argument("--vocab-size", type=int)
    ap.add_argument("--image-size", type=int)
    ap.add_argument("--patch-size", type=int)
    ap.add_argument("--num-channels", type=int)
    ap.add_argument("--num-labels", type=int)
    args = ap.parse_args(argv)

    cfg = _cli_config(args.family, num_layers=args.num_layers,
                      hidden_size=args.hidden_size,
                      intermediate_size=args.intermediate_size,
                      num_heads=args.num_heads,
                      num_kv_heads=args.num_kv_heads,
                      num_experts=args.num_experts,
                      vocab_size=args.vocab_size,
                      image_size=args.image_size,
                      patch_size=args.patch_size,
                      num_channels=args.num_channels,
                      num_labels=args.num_labels)

    if args.input.endswith(".safetensors"):
        from safetensors.numpy import load_file

        sd = load_file(args.input)
    else:
        with open(args.input, "rb") as f:
            sd = pickle.load(f)

    out = (_HF2NXD if args.direction == "hf2nxd"
           else _NXD2HF)[args.family](sd, cfg)
    with open(args.output, "wb") as f:
        pickle.dump(out, f)
    print(f"wrote {args.output}")


if __name__ == "__main__":  # pragma: no cover
    main()
