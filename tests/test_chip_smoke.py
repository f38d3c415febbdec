"""CPU rehearsal of ``chip_smoke.py``: its phase functions at
``tiny_config`` widths on the virtual CPU mesh. Only the platform check
(``check_device``) is bypassed, by calling the phases directly; every
other check of the script runs as it does on the chip, with the kernels'
dispatchers answering for the CPU backend."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from neuronx_distributed_tpu.models import llama

SERVE = dict(prompt_lens=(5, 12, 21, 33), new_tokens=6, block_size=8,
             num_blocks=32, max_blocks_per_seq=8, token_budget=8,
             max_slots=4)


def test_train_phase_tiny():
    rep = chip_smoke.train_phase(llama.tiny_config(), batch=2, seq=64, seed=0,
                                 devices=jax.devices()[:1])
    assert rep["attention"] == "xla" and len(rep["losses"]) == 5
    assert rep["losses"][-1] < rep["losses"][0]


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_serve_phase_tiny(quantized):
    rep = chip_smoke.serve_phase(llama.tiny_config(), quantized=quantized,
                                 seed=0, **SERVE)
    # on the CPU both engines take the XLA reference: tokens are identical
    assert rep["attention"] == "xla" and rep["match_rate"] == 1.0


def test_logit_probe_reproduces_the_engine():
    """The probe that judges a departing sequence must itself agree with
    the engine: its argmax after a prompt is the engine's first token."""
    from flax.core import meta

    from neuronx_distributed_tpu.inference.engine import (EngineConfig,
                                                          ServingEngine)
    from neuronx_distributed_tpu.parallel import mesh as ps

    ps.initialize_model_parallel()
    cfg = llama.tiny_config(dtype=jnp.float32)
    params = meta.unbox(llama.LlamaForCausalLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    ecfg = EngineConfig(block_size=8, num_blocks=16, max_slots=2,
                        max_blocks_per_seq=4, token_budget=8)
    prompt = np.random.RandomState(3).randint(0, cfg.vocab_size, 19).tolist()
    engine = ServingEngine(cfg, params, ecfg)
    uid = engine.submit(prompt, 3)
    toks = engine.run()[uid].tokens
    for i in range(3):
        logits = chip_smoke._next_token_logits(cfg, params, ecfg,
                                               prompt + toks[:i])
        assert int(np.argmax(logits)) == toks[i]


def test_tp_phase_four_virtual_devices():
    rep = chip_smoke.tp_phase(llama.tiny_config(), batch=2, seq=64, seed=0,
                              devices=jax.devices()[:4])
    assert rep["tp"]["shards"]["devices"] == [0, 1, 2, 3]
    assert abs(rep["tp"]["losses"][0] - rep["one"]["losses"][0]) < 1e-2


def test_failed_phase_reaches_the_exit_code(monkeypatch, capsys):
    """``main`` has no handler between a phase and the interpreter: a
    failed check propagates (non-zero exit) and no result line is
    printed."""
    from neuronx_distributed_tpu.utils import device

    def failing_phase(*a, **k):
        chip_smoke.check(False, "made to fail")

    monkeypatch.setattr(chip_smoke, "check_device",
                        lambda chips: chip_smoke.device_report())
    monkeypatch.setattr(device, "place_compile_cache", lambda root: "off")
    monkeypatch.setattr(chip_smoke, "train_phase", failing_phase)
    with pytest.raises(chip_smoke.SmokeFailure, match="made to fail"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_no_tpu_is_a_nonzero_exit_without_a_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, chip_smoke.__file__], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "needs a TPU" in r.stderr


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path,
                                              from_env):
    """``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it, the code sets no
    directory. Unset: the fixed ``<checkout>/.jax_cache``."""
    from neuronx_distributed_tpu.utils.device import place_compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        assert place_compile_cache("/checkout") == str(tmp_path / "c")
        assert "jax_compilation_cache_dir" not in updates
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert place_compile_cache("/checkout") == "/checkout/.jax_cache"
        assert updates["jax_compilation_cache_dir"] == "/checkout/.jax_cache"
