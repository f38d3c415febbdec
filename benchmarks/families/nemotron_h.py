"""The nemotron_h family (``nemotron_h``:
NVIDIA-Nemotron-3-Super-120B-A12B): layers that are a Mamba-2 mixer
(several groups of ``B`` and ``C``), a NoPE attention or a LatentMoE
feed-forward alone, by ``hybrid_override_pattern``. The package lays a
mixer and the ``E`` behind it in one decoder layer and keeps one
parameter stack a kind of layer
(``neuronx_distributed_tpu/models/nemotron_h.py``); its
``published_names`` says where each published tensor lies, and this file
serves the tree under those names and under the reference's.

A configuration file may give the chip's share of a deployment under
``share``: ``n_routed_experts`` and ``vocab_size`` are then what is held
here (both listed in its ``reduced``), ``share.n_routed_experts_published``
what the router scores, and ``share.first_expert`` the published index of
the first expert held.

**The scan's own parameters are not drawn N(0, std)**: as the Granite
families', ``A_log``, ``dt_bias``, ``D`` and the convolution's weight are
read through ``families/granite_hybrid.py``'s ``mamba2_init`` (Mamba-2's
own initialisation, value by value from the normal draw), in front of the
package's paged forward and of what the reference reads alike. **The
selection bias is served as drawn**, ``N(0, initializer_range)``: under
such weights a row's sigmoid scores near its 22nd largest lie about
0.0025 apart, so a bias of 0.02 decides several of a row's choices while
the weights stay the scores', and choosing by ``s`` alone or weighing by
``s + b`` would show.

Every published key is read or refused by the package's
``NemotronHConfig.from_published``.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable, Tuple

from families import granite_hybrid, laguna, llama


def with_mamba2_init(params, std: float):
    """``params`` with ``granite_hybrid.mamba2_init`` over every stack
    whose layers have a Mamba-2 mixer."""
    model = dict(params["params"]["model"])
    for name, stack in model.items():
        if name.startswith("layers_mamba2"):
            layer = {**stack["layer"], "attn": granite_hybrid.mamba2_init(
                stack["layer"]["attn"], std)}
            model[name] = {**stack, "layer": layer}
    return {**params, "params": {**params["params"], "model": model}}


@functools.lru_cache(maxsize=None)
def _seeded_config():
    from neuronx_distributed_tpu.models import nemotron_h as nh

    def forward(cfg, params, *args, **kw):
        return nh.nemotron_h_forward_with_cache(
            cfg, with_mamba2_init(params, cfg.init_std), *args, **kw)

    @dataclasses.dataclass(frozen=True)
    class SeededNemotronHConfig(nh.NemotronHConfig):
        """The package's config, served from weights whose scan leaves
        are normal draws (``init_std``) to be read as Mamba-2's."""

        init_std: float = 0.02

        def serving_family(self):
            return dataclasses.replace(super().serving_family(),
                                       forward=forward)

    return SeededNemotronHConfig, forward


def build(c: dict, **kw) -> Tuple[Any, Any, Callable]:
    from neuronx_distributed_tpu.models import nemotron_h as nh

    config, forward = _seeded_config()
    share = c.get("share", {})
    cfg = config.from_published(
        c, num_experts=int(share.get("n_routed_experts_published",
                                     c["n_routed_experts"])),
        experts_held=(int(share.get("first_expert", 0)),
                      int(c["n_routed_experts"])),
        init_std=float(c["initializer_range"]), **kw)
    return cfg, nh.NemotronHForCausalLM(cfg), forward


#: the reference's names -> the checkpoint's, within
#: ``backbone.layers.{i}.`` (``{e}``: the expert's published index)
CHECKPOINT = {
    "norm": "norm.weight",
    "in_proj": "mixer.in_proj.weight", "out_proj": "mixer.out_proj.weight",
    "conv_weight": "mixer.conv1d.weight", "conv_bias": "mixer.conv1d.bias",
    "A_log": "mixer.A_log", "D": "mixer.D", "dt_bias": "mixer.dt_bias",
    "mamba_norm": "mixer.norm.weight",
    "q_proj": "mixer.q_proj.weight", "k_proj": "mixer.k_proj.weight",
    "v_proj": "mixer.v_proj.weight", "o_proj": "mixer.o_proj.weight",
    "router": "mixer.gate.weight",
    "router_bias": "mixer.gate.e_score_correction_bias",
    "up_proj": "mixer.experts.{e}.up_proj.weight",
    "down_proj": "mixer.experts.{e}.down_proj.weight",
    "shared_up_proj": "mixer.shared_experts.up_proj.weight",
    "shared_down_proj": "mixer.shared_experts.down_proj.weight",
    "latent_in": "mixer.fc1_latent_proj.weight",
    "latent_out": "mixer.fc2_latent_proj.weight"}
#: the tensors outside the layers, by either name
TOP = {"embedding": ("model", "embed", "embedding"),
       "final_norm": ("model", "norm", "scale"),
       "lm_head": ("lm_head", "kernel")}
TOP_CHECKPOINT = {"backbone.embeddings.weight": "embedding",
                  "backbone.norm_f.weight": "final_norm",
                  "lm_head.weight": "lm_head"}
_LAYER = re.compile(r"backbone\.layers\.(\d+)\.(.+)")
_EXPERT = re.compile(r"mixer\.experts\.(\d+)\.(.+)")


class Published:
    """The served tree as the published checkpoint's tensors, float32, in
    the checkpoint's orientation (a projection ``[out, in]``,
    ``conv1d.weight [C, 1, W]``), a tensor at a time: a layer's by the
    package's ``published_names`` (its kind's stack under
    ``model/layers_<kind>/layer`` and its index there), the scan's own
    leaves through ``mamba2_init``, the routed experts an expert at a time
    by the expert's published index (the held ones alone: another is a
    ``KeyError``). A tensor is read by the reference's name
    (``weights("up_proj", 3, expert=5)``) or by the checkpoint's
    (``weights("backbone.layers.3.mixer.experts.5.up_proj.weight")``)."""

    def __init__(self, params, config: dict):
        from neuronx_distributed_tpu.models import nemotron_h as nh

        self.tree = with_mamba2_init(
            params, float(config["initializer_range"]))["params"]
        self.first = int(config.get("share", {}).get("first_expert", 0))
        self.held = int(config["n_routed_experts"])
        self.names = nh.published_names(nh.NemotronHConfig(
            pattern=config["hybrid_override_pattern"],
            num_layers=config["num_hidden_layers"]))

    def __call__(self, name: str, layer: int = None, expert: int = None):
        name = TOP_CHECKPOINT.get(name, name)
        if name in TOP:
            leaf = llama.Published._get(self.tree, TOP[name])
            return (laguna._transposed_f32(leaf) if name == "lm_head"
                    else llama._f32(leaf))
        found = _LAYER.fullmatch(name)
        if found:
            layer, name = int(found.group(1)), found.group(2)
            of_expert = _EXPERT.fullmatch(name)
            if of_expert:
                expert = int(of_expert.group(1))
                name = "mixer.experts.{e}." + of_expert.group(2)
        else:
            name = CHECKPOINT[name]
        kind, index, path = self.names[f"backbone.layers.{layer}.{name}"]
        leaf = llama.Published._get(
            self.tree["model"][f"layers_{kind}"]["layer"], path)
        if "{e}" in name:
            if not 0 <= expert - self.first < self.held:
                raise KeyError(f"expert {expert} is held elsewhere")
            index = (index, expert - self.first)
        w = laguna._at(leaf, index)
        if name == "mixer.conv1d.weight":
            return w[:, None, :]
        return w.T if w.ndim == 2 else w


published = Published
