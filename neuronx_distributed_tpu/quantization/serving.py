"""Offline weight quantization for the serving tiers.

Converts a float serving checkpoint (the scan-stacked param tree
:func:`..models.llama.llama_forward_with_cache` /
:func:`..models.mixtral.mixtral_forward_with_cache` consume) into the
quantized tree the ``weight_quant`` forward expects — per-out-channel
symmetric int8/fp8 pairs (``*_q`` + ``*_scale``) or packed OCP
microscaling pairs (``*_packed`` + ``*_scale``, contraction-dim-last).

The existing converters (:func:`.quantization_utils.quantize`,
:func:`.mx_layers.mx_pack_expert_params`) assume fixed per-layer axes;
serving params carry a leading scanned-layer dim (and experts an expert
dim), so every site here names its contraction axis explicitly.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any, Dict

import jax.numpy as jnp
import numpy as np

from ..modules import glu
from .microscaling import mx_quantize_fp4, mx_quantize_fp8
from .quantization_utils import QuantizedDtype


def params_are_quantized(params) -> bool:
    """True if the tree already holds quantized kernels (any leaf named
    ``*_q`` or ``*_packed``)."""
    found = False

    def walk(t):
        nonlocal found
        for k, v in t.items():
            if isinstance(v, Mapping):
                walk(v)
            elif k.endswith(("_q", "_packed")):
                found = True

    walk(params)
    return found


def _symmetric_pair(w, contract_axis: int, qdt: QuantizedDtype):
    """Per-out-channel symmetric quantization along ``contract_axis``.

    Returns ``(q, scale)`` with ``q.shape == w.shape`` and ``scale`` =
    ``w.shape`` minus the contraction axis. All-zero channels keep scale
    1 and round-trip to exact zeros.
    """
    w = np.asarray(jnp.asarray(w), dtype=np.float32)
    amax = np.abs(w).max(axis=contract_axis)
    scale = np.where(amax == 0.0, 1.0,
                     amax / qdt.max_value).astype(np.float32)
    q = w / np.expand_dims(scale, contract_axis)
    if qdt == QuantizedDtype.INT8:
        return (jnp.asarray(np.clip(np.rint(q), -127, 127).astype(np.int8)),
                jnp.asarray(scale))
    return (jnp.asarray(q).astype(qdt.jnp_dtype), jnp.asarray(scale))


def _mx_pair(w, contract_axis: int, fmt: str):
    """Pack ``w`` into MX format, contraction axis moved last (the layout
    every MX serving module stores)."""
    w = np.moveaxis(np.asarray(jnp.asarray(w), dtype=np.float32),
                    contract_axis, -1)
    packed, scale = (mx_quantize_fp4 if fmt == "fp4"
                     else mx_quantize_fp8)(w)
    return jnp.asarray(packed), jnp.asarray(scale)


def quantize_params_for_serving(cfg, params) -> Dict[str, Any]:
    """Quantize a float serving tree to ``cfg.weight_quant``'s format.

    ``params`` is the serving tree (``{"params": {"model": ..,
    "lm_head": ..}}`` or the inner dict); returns the same nesting with
    every projection kernel replaced by its quantized pair. Trees that
    are already quantized pass through unchanged.
    """
    fmt = getattr(cfg, "weight_quant", None)
    if fmt is None:
        raise ValueError(
            "quantize_params_for_serving needs cfg.weight_quant set")
    if not getattr(cfg, "scan_layers", True):
        raise ValueError(
            "serving quantization expects the scan-stacked layer tree "
            "(cfg.scan_layers=True)")
    if params_are_quantized(params):
        return params

    mx = fmt.startswith("mx")
    sub = fmt[2:] if mx else None
    qdt = (None if mx else
           (QuantizedDtype.INT8 if fmt == "int8"
            else QuantizedDtype.FP8E4M3))

    def pair(w, axis: int, base: str) -> Dict[str, Any]:
        if mx:
            p, s = _mx_pair(w, axis, sub)
            return {f"{base}_packed": p, f"{base}_scale": s}
        q, s = _symmetric_pair(w, axis, qdt)
        return {f"{base}_q": q, f"{base}_scale": s}

    wrapped = "params" in params
    root = dict(params["params"] if wrapped else params)
    layers = root["model"]["layers"]["layer"]

    new_layer: Dict[str, Any] = {}
    for name, mod in layers.items():
        if name == "attn":
            attn = dict(mod)
            qkv: Dict[str, Any] = {}
            for k in ("q_kernel", "k_kernel", "v_kernel"):
                # stacked [L, hidden, out]: contract over hidden (axis 1)
                qkv.update(pair(mod["qkv"][k], 1, k))
            attn["qkv"] = qkv
            # [L, q_features, hidden]
            attn["o_proj"] = pair(mod["o_proj"]["kernel"], 1, "kernel")
            new_layer[name] = attn
        elif name == "mlp":
            mlp: Dict[str, Any] = {}
            # [L, hidden, 2, intermediate]
            mlp.update(pair(glu.fused(mod, glu.DENSE), 1, "gate_up"))
            # [L, intermediate, hidden]
            mlp["down"] = pair(mod["down"]["kernel"], 1, "kernel")
            new_layer[name] = mlp
        elif name == "moe":
            moe = dict(mod)  # router / shared stay float
            experts: Dict[str, Any] = {}
            # [L, E, hidden, 2, intermediate]
            experts.update(pair(glu.fused(mod["experts"], glu.EXPERTS), 2,
                                "gate_up"))
            # [L, E, intermediate, hidden]
            experts.update(pair(mod["experts"]["down"], 2, "down"))
            moe["experts"] = experts
            new_layer[name] = moe
        else:
            new_layer[name] = mod  # norms

    model = dict(root["model"])
    model["layers"] = {"layer": new_layer}
    root["model"] = model
    if "lm_head" in root:
        # [hidden, vocab]: contract over hidden (axis 0)
        root["lm_head"] = pair(root["lm_head"]["kernel"], 0, "kernel")
    return {"params": root} if wrapped else root
