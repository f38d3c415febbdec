"""Gate and up of a gated feed-forward (``modules/glu.py``): two leaves
``[..., H, I]``, made from and read back to the published
``gate_proj``/``up_proj`` (``w1``/``w3``) by the converter; a tree in the
old fused form is refused by name.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.models.mixtral import (MixtralConfig,
                                                    MixtralForCausalLM)
from neuronx_distributed_tpu.modules import glu
from neuronx_distributed_tpu.modules.moe import ExpertMLPs
from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.scripts import checkpoint_converter as cc

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import engine_parity  # noqa: E402  (the fixed published tensors)

V, H, I, L, N, KV, E = 32, 16, 24, 2, 4, 2, 4
_WIDTHS = dict(vocab_size=V, hidden_size=H, intermediate_size=I, num_layers=L,
               num_heads=N, num_kv_heads=KV, max_seq_len=16,
               dtype=jnp.float32, param_dtype=jnp.float32)


def _config(family):
    if family == "llama":
        return LlamaConfig(**_WIDTHS), LlamaForCausalLM
    # capacity = tokens: the capacity dispatch drops nothing
    return (MixtralConfig(num_experts=E, top_k=2, capacity_factor=E / 2,
                          **_WIDTHS), MixtralForCausalLM)


def _published(family):
    return engine_parity.published(_config(family)[0], family)


_TO_NXD = {"llama": cc.convert_hf_llama_to_nxd,
           "mixtral": cc.convert_hf_mixtral_to_nxd}
_TO_HF = {"llama": cc.convert_nxd_to_hf_llama,
          "mixtral": cc.convert_nxd_to_hf_mixtral}

# logits[:, -1, :8] of the commit before the two-leaf form (fused
# gate_up_kernel [H, 2, I] / gate_up [E, H, 2, I], one einsum), from the
# same published tensors and ids, float32 on the CPU
_PARENT_LOGITS = {
    "llama": [[0.28574255, 0.0058380025, 0.16264853, 0.1799279,
               0.21859612, 0.58417195, 0.1305351, -0.58033025],
              [-0.3038752, -0.41883677, 0.21571793, 0.45984882,
               0.1731392, 0.073781095, -0.6021228, -0.65533346]],
    "mixtral": [[0.25556847, 0.38872275, 0.22674735, 0.26308367,
                 0.6484087, -0.14570501, 0.45941553, -0.23150201],
                [0.17564009, -0.39025208, 0.45314166, 0.10916366,
                 0.22287218, 0.16669227, -0.88055724, -0.1433866]],
}


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_published_round_trip_is_exact(family):
    cfg, _ = _config(family)
    sd = _published(family)
    tree = _TO_NXD[family](sd, cfg)
    node = tree["params"]["model"]["layers"]["layer"]
    node, names, lead = ((node["mlp"], glu.DENSE, (L,)) if family == "llama"
                         else (node["moe"]["experts"], glu.EXPERTS, (L, E)))
    for name in names:
        assert node[name].shape == lead + (H, I)
    back = _TO_HF[family](tree, cfg)
    assert set(back) == set(sd)
    for k, v in sd.items():
        np.testing.assert_array_equal(back[k], v, err_msg=k)


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_logits_equal_the_fused_forms(family):
    """The two dots compute what the one einsum over the fused leaf did."""
    ps.initialize_model_parallel()
    cfg, model = _config(family)
    params = jax.tree_util.tree_map(
        jnp.asarray, _TO_NXD[family](_published(family), cfg))
    ids = jnp.asarray(np.random.RandomState(7).randint(0, V, (2, 6)))
    out = model(cfg).apply(params, ids)
    out = out[0] if family == "mixtral" else out
    np.testing.assert_allclose(
        np.asarray(out)[:, -1, :8],
        np.asarray(_PARENT_LOGITS[family], np.float32), rtol=1e-5, atol=1e-6)


def _fuse(node, names, old):
    """``node`` as the old tree had it: one leaf ``old``, a 2 second from
    last."""
    rest = {k: v for k, v in node.items() if k not in names}
    return {**rest, old: glu.fused(node, names)}


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_old_form_tree_is_refused_by_name(family):
    ps.initialize_model_parallel()
    cfg, model = _config(family)
    tree = _TO_NXD[family](_published(family), cfg)
    layer = tree["params"]["model"]["layers"]["layer"]
    if family == "llama":
        old = "gate_up_kernel"
        layer["mlp"] = _fuse(layer["mlp"], glu.DENSE, old)
    else:
        old = "gate_up"
        layer["moe"]["experts"] = _fuse(layer["moe"]["experts"],
                                        glu.EXPERTS, old)
    # the message names the leaf, the form and the way across
    match = rf"hidden, 2, intermediate.*'{old}'.*checkpoint_converter"
    with pytest.raises(ValueError, match=match):
        _TO_HF[family](tree, cfg)
    params = jax.tree_util.tree_map(jnp.asarray, tree)
    with pytest.raises(ValueError, match=match):
        model(cfg).apply(params, jnp.zeros((1, 4), jnp.int32))


def test_old_form_expert_bank_is_refused_at_apply():
    m = ExpertMLPs(num_experts=E, hidden_size=H, intermediate_size=I,
                   dtype=jnp.float32)
    x, gates = jnp.ones((8, H)), jnp.full((8, 2), 0.5)
    idx = jnp.zeros((8, 2), jnp.int32)
    params = meta.unbox(m.init(jax.random.key(0), x, gates, idx))
    assert set(params["params"]) == {*glu.EXPERTS, "down"}
    old = {"params": _fuse(params["params"], glu.EXPERTS, "gate_up")}
    with pytest.raises(ValueError, match="hidden, 2, intermediate.*'gate_up'"):
        m.apply(old, x, gates, idx)
