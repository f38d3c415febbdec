"""The mixtral family: the llama family's attention and norms, with the
MLP replaced by a router and experts."""

from __future__ import annotations

from typing import Any, Callable, Tuple

from families import llama


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import mixtral

    cfg = mixtral.MixtralConfig(**{
        **llama.common(c), "num_experts": c["num_local_experts"],
        "top_k": c["num_experts_per_tok"],
        "router_aux_coef": float(c.get("router_aux_loss_coef", 0.02)), **kw})
    return (cfg, mixtral.MixtralForCausalLM(cfg),
            mixtral.mixtral_forward_with_cache)


class Published(llama.Published):
    """As the llama family's, with ``weights("router", layer) [E, H]`` and
    ``gate``, ``up`` and ``down`` read an expert at a time:
    ``weights(name, layer, expert)``."""

    PER_LAYER = dict(llama.Published.PER_LAYER,
                     router=("moe", "router", "kernel"))

    def mlp_at(self, layer, expert):
        return self.layers["moe"]["experts"], (layer, expert)


published = Published
