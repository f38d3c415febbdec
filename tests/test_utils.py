"""Aux utils: logger, chrome-trace export, tensor capture/replacement."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax.core import meta

from neuronx_distributed_tpu.parallel import mesh as ps
from neuronx_distributed_tpu.utils import tensor_capture as tc
from neuronx_distributed_tpu.utils.logger import get_logger, rmsg
from neuronx_distributed_tpu.obs.tracing import SpanTracer


def test_logger_and_rmsg():
    lg = get_logger("nxd-test")
    lg.info("hello")
    ps.initialize_model_parallel(tensor_model_parallel_size=2)
    msg = rmsg("step done")
    assert "mesh" in msg and "step done" in msg


def test_timeline_chrome_trace(tmp_path):
    t = SpanTracer()
    with t.span("fwd"):
        pass
    with t.span("bwd"):
        pass
    p = t.save(str(tmp_path / "tl.json"))
    data = json.load(open(p))
    names = [e["name"] for e in data["traceEvents"]]
    assert names == ["fwd", "bwd"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in data["traceEvents"])


@pytest.mark.slow
def test_tensor_capture_and_replacement():
    from neuronx_distributed_tpu.models.llama import (LlamaForCausalLM,
                                                      tiny_config)

    ps.initialize_model_parallel()
    cfg = tiny_config(num_layers=1, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = meta.unbox(model.init(jax.random.key(0), ids))

    out, inter = tc.capture_intermediates(model, params, ids)
    assert inter, "no intermediates captured"

    # replacement: zero the final norm scale -> logits must change
    ref = model.apply(params, ids)
    zeroed = tc.apply_with_replacements(
        model, params,
        {"params/model/norm/scale": jnp.zeros((cfg.hidden_size,))}, ids)
    assert not np.allclose(np.asarray(ref), np.asarray(zeroed))
    diff = tc.max_diff(params, params)
    assert max(diff.values()) == 0.0

    import pytest

    with pytest.raises(KeyError):
        tc.apply_with_replacements(model, params, {"params/nope": ids}, ids)


def test_checkpoint_converter_cli_families(tmp_path, monkeypatch):
    """The converter CLI accepts every family (reference ships one
    CheckpointConverterBase subclass per family); smoke vit end to end."""
    import pickle

    # the test's time is its imports: torch's ViT needs no TensorFlow,
    # which transformers would import (with keras) because it is installed
    monkeypatch.setenv("USE_TF", "0")
    import torch
    import transformers

    from neuronx_distributed_tpu.scripts import checkpoint_converter as cc

    hf_cfg = transformers.ViTConfig(
        hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        intermediate_size=64, image_size=32, patch_size=16, num_labels=4)
    torch.manual_seed(0)
    sd = {k: v.numpy() for k, v in
          transformers.ViTForImageClassification(hf_cfg).state_dict().items()}
    src = tmp_path / "vit_hf.pkl"
    dst = tmp_path / "vit_nxd.pkl"
    with open(src, "wb") as f:
        pickle.dump(sd, f)
    cc.main(["--input", str(src), "--output", str(dst), "--family", "vit",
             "--num-layers", "2"])
    with open(dst, "rb") as f:
        tree = pickle.load(f)
    assert tree["params"]["layers"]["layer"]["qkv"]["q_kernel"].shape == \
        (2, 32, 32)
