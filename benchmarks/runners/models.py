"""From a configuration file (the published keys of a HuggingFace
``config.json``) to the package's model config, model and cached forward.

The one place that knows both vocabularies. A new family the package
supports is a new entry of ``FAMILIES``.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple


def _common(c: dict) -> dict:
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


def _llama(c: dict, **kw):
    from neuronx_distributed_tpu.models import llama

    cfg = llama.LlamaConfig(**{**_common(c), **kw})
    return cfg, llama.LlamaForCausalLM(cfg), llama.llama_forward_with_cache


def _mixtral(c: dict, **kw):
    from neuronx_distributed_tpu.models import mixtral

    cfg = mixtral.MixtralConfig(**{
        **_common(c), "num_experts": c["num_local_experts"],
        "top_k": c["num_experts_per_tok"],
        "router_aux_coef": float(c.get("router_aux_loss_coef", 0.02)), **kw})
    return (cfg, mixtral.MixtralForCausalLM(cfg),
            mixtral.mixtral_forward_with_cache)


FAMILIES = {"llama": _llama, "mixtral": _mixtral}


def build(config: dict, **overrides) -> Tuple[Any, Any, Callable]:
    """``(model config, flax module, forward_with_cache)``. ``overrides``
    are fields of the package's config (dtype, runner settings)."""
    family = config["family"]
    if family not in FAMILIES:
        raise ValueError(f"unknown model family {family!r}; "
                         f"known: {sorted(FAMILIES)}")
    return FAMILIES[family](config, **overrides)


def reference_kwargs(config: dict) -> dict:
    """The arguments ``reference.decoder_f32.forward`` needs, read from the
    published keys alone."""
    return dict(num_heads=config["num_attention_heads"],
                num_kv_heads=config["num_key_value_heads"],
                rope_theta=float(config["rope_theta"]),
                rms_eps=float(config["rms_norm_eps"]),
                top_k=int(config.get("num_experts_per_tok", 0)))


def dtype_of(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]
