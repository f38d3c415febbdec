"""Nemotron-H (``nemotron_h``): a decoder whose layers are one norm and one
block each, ``h <- h + F(RMSNorm(h))``
(huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16).
``hybrid_override_pattern`` names each layer's block: ``M`` a Mamba-2 mixer
(:class:`.granite_hybrid.Mamba2Mixer` with ``n_groups`` groups of ``B`` and
``C`` and the gated norm by group), ``*`` GQA without rotary embedding, ``E``
a LatentMoE feed-forward (:class:`..modules.moe.MoE`): a sigmoid router
with a selection bias over all ``n_routed_experts`` in float32 that keeps
the ``top_k`` largest ``s + b`` and weighs them by ``s`` over the chosen's
sum times ``routed_scaling_factor``; ungated experts ``down(relu(up l)^2)``
that work on ``l = latent_in h`` in ``moe_latent_size`` dimensions, their
weighted sum through ``latent_out`` back to the hidden size; and beside
them a shared expert of the same form on the row itself. ``experts_held =
(first, count)`` is the share of the routed experts this device holds: the
router scores them all, an assignment to an expert held elsewhere takes no
slot and adds nothing. The head is untied; nothing is multiplied.

**Layers.** A mixer with the feed-forward behind it is
:class:`.llama.LlamaDecoderLayer` as every family has it (norm, mixer,
residual, norm, feed-forward, residual: two of the published layers); a
mixer with none behind it, or a feed-forward with no mixer ahead of it, is
the same layer with one block (:meth:`.llama.LlamaConfig.layer_blocks`). A
*kind* is a mixer, a feed-forward or both (:data:`KINDS`); the parameters
are one stack a kind, the layers one ``lax.scan`` a run of like layers
(:func:`.llama.run_layers`): ``MEMEMEM*EME`` is three ``mamba2_moe``, one
``mamba2``, one ``full_moe`` and one ``mamba2_moe``. :func:`published_names`
gives the published tensor names (``backbone.layers.{i}.norm``,
``.mixer.*``) their places in those stacks.

Served, a slot's sequence is in three places
(:class:`..inference.paging.StatePoolCache`): K/V blocks of the attention
layers alone (two heads of 128, one a pool row), and the Mamba-2 layers'
two per-slot state leaves, ``ssm [Lm, J, d_state, d_inner]`` float32 and
``conv [Lm, d_conv - 1, J, d_inner + 2 G d_state]``, indexed by a layer's
place among the Mamba-2 layers whatever its kind's stack. A step's routed
assignments (kept, dropped, held elsewhere) and the held experts it hit
and left idle are counted into the cache's ``moe_counts [5]``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax.core import meta

from ..modules.moe import MoE
from ..modules.norms import RMSNorm
from ..obs.device_scopes import device_scope
from ..parallel import layers as pl
from ..parallel import loss_functions as lf
from .granite_hybrid import UNSUPPORTED, Mamba2Mixer, mixer_views
from .llama import LlamaConfig, _ScanBody, run_layers, runs_of

#: a kind of layer -> (its mixer's ``attention_kind`` or None, whether a
#: feed-forward follows)
KINDS = {"mamba2_moe": ("mamba2", True), "full_moe": ("full", True),
         "mamba2": ("mamba2", False), "full": ("full", False),
         "moe": (None, True)}
#: a pattern's letter -> the mixer it names
MIXERS = {"M": "mamba2", "*": "full"}
#: what of the cache's stacks a mixer reads and writes
MIXER_CARRIED = {"mamba2": ("ssm", "conv"), "full": ("k", "v"), None: ()}
#: the first eleven layers of Nemotron-3-Super's 88
PUBLISHED_PATTERN = "MEMEMEM*EME"
#: what a published config must say for this module to be its model
_BUILT = {"model_type": "nemotron_h", "attention_bias": False,
          "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
          "use_conv_bias": True, "mamba_hidden_act": "silu",
          "mlp_hidden_act": "relu2", "n_group": 1, "topk_group": 1,
          "norm_topk_prob": True, "residual_in_fp32": False,
          "tie_word_embeddings": False, "n_shared_experts": 1,
          "moe_shared_expert_overlap": False, "sliding_window": None,
          "num_nextn_predict_layers": 0}
#: published keys that nothing in a forward pass reads: the rotary
#: embedding's (the family's attention applies none), the chunked
#: algorithm's and the initialiser's, the prediction module's pattern
#: (``num_nextn_predict_layers`` 0), what a caller keeps of the logits and
#: which implementation computes the scan
_UNREAD = ("rope_theta", "partial_rotary_factor", "chunk_size",
           "time_step_floor", "time_step_max", "time_step_min",
           "rescale_prenorm_residual", "mtp_hybrid_override_pattern",
           "num_logits_to_keep", "use_mamba_kernels")
#: every key of a published config that
#: :meth:`NemotronHConfig.from_published` reads, holds to :data:`_BUILT`
#: or knows that nothing reads
PUBLISHED_KEYS = frozenset(_BUILT) | frozenset(_UNREAD) | frozenset((
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "num_attention_heads", "num_key_value_heads",
    "head_dim", "max_position_embeddings", "norm_eps", "layer_norm_epsilon",
    "mamba_num_heads",
    "mamba_head_dim", "ssm_state_size", "conv_kernel", "n_groups", "expand",
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "intermediate_size", "moe_latent_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor"))


def layers_of_pattern(pattern: str) -> Tuple[str, ...]:
    """The kinds (:data:`KINDS`) of the decoder layers a published
    pattern makes: a mixer takes the ``E`` behind it into its layer."""
    kinds, i = [], 0
    while i < len(pattern):
        mixer = MIXERS.get(pattern[i])
        if mixer is None and pattern[i] != "E":
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: {pattern[i]!r} at "
                f"{i} is no block this module builds (M, * and E are)")
        paired = mixer is not None and pattern[i + 1:i + 2] == "E"
        kinds.append("moe" if mixer is None
                     else mixer + "_moe" if paired else mixer)
        i += 2 if paired else 1
    return tuple(kinds)


@dataclass(frozen=True)
class NemotronHConfig(LlamaConfig):
    vocab_size: int = 131072
    hidden_size: int = 4096
    #: the shared expert's width (``moe_shared_expert_intermediate_size``)
    intermediate_size: int = 5376
    #: the published layers: the pattern's length
    num_layers: int = 11
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: Optional[int] = 128
    max_seq_len: int = 262144
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    use_rope: bool = False
    #: each published layer's block: ``M``, ``*`` or ``E``
    pattern: str = PUBLISHED_PATTERN
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 8
    mamba_chunk_size: int = 128
    #: routed experts the router scores
    num_experts: int = 512
    top_k: int = 22
    #: a routed expert's width, in the latent
    expert_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    routed_scaling_factor: float = 5.0
    #: ``(first, count)`` of the routed experts held here (None: all)
    experts_held: Optional[Tuple[int, int]] = None
    #: the blocks of this kind's layer (:meth:`kind_config` sets it)
    blocks: Tuple[str, ...] = ("attn", "ffn")

    def __post_init__(self) -> None:
        super().__post_init__()
        if len(self.pattern) != self.num_layers:
            raise ValueError(
                f"hybrid_override_pattern {self.pattern!r} names "
                f"{len(self.pattern)} layers, num_hidden_layers is "
                f"{self.num_layers}")
        layers_of_pattern(self.pattern)
        groups = self.mamba_n_groups
        if groups < 1 or self.mamba_n_heads % groups:
            raise ValueError(
                f"{self.mamba_n_heads} heads do not lie in "
                f"{self.mamba_n_groups} groups of B and C")
        held = self.experts_held
        if held is not None and not (
                0 <= held[0] and held[1] > 0
                and held[0] + held[1] <= self.num_experts):
            raise ValueError(f"experts_held {held} is no share of "
                             f"{self.num_experts} experts")
        if not 0 < self.top_k <= self.num_experts:
            raise ValueError(f"top_k {self.top_k} of {self.num_experts} "
                             "experts")

    @classmethod
    def from_published(cls, c: dict, **kw) -> "NemotronHConfig":
        """The config of a published ``config.json``'s keys
        (:data:`PUBLISHED_KEYS`): each is read here, is one that nothing
        reads (:data:`_UNREAD`), or must say what this module builds
        (:data:`_BUILT`: another value is refused by name). ``kw`` are
        this class's fields (dtype, ``experts_held``; ``num_experts``
        where the file's ``n_routed_experts`` is a share)."""
        wrong = {k: c.get(k) for k, v in _BUILT.items() if c.get(k) != v}
        if (c["expand"] * c["hidden_size"]
                != c["mamba_num_heads"] * c["mamba_head_dim"]):
            wrong["expand"] = c["expand"]
        if c["moe_intermediate_size"] != c["intermediate_size"]:
            wrong["intermediate_size"] = c["intermediate_size"]
        if c["layer_norm_epsilon"] != c["norm_eps"]:
            wrong["layer_norm_epsilon"] = c["layer_norm_epsilon"]
        if len(c["hybrid_override_pattern"]) != c["num_hidden_layers"]:
            wrong["num_hidden_layers"] = c["num_hidden_layers"]
        if wrong:
            raise ValueError(
                f"nemotron_h builds {_BUILT}, d_inner = expand x "
                f"hidden_size, experts of intermediate_size, one epsilon "
                f"and a pattern of num_hidden_layers letters; the config "
                f"says {wrong}")
        return cls(**{**dict(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            intermediate_size=c["moe_shared_expert_intermediate_size"],
            num_layers=c["num_hidden_layers"],
            pattern=c["hybrid_override_pattern"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            max_seq_len=int(c["max_position_embeddings"]),
            rms_eps=float(c["norm_eps"]),
            mamba_n_heads=c["mamba_num_heads"],
            mamba_d_head=c["mamba_head_dim"],
            mamba_d_state=c["ssm_state_size"],
            mamba_d_conv=c["conv_kernel"], mamba_n_groups=c["n_groups"],
            num_experts=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"],
            expert_intermediate_size=c["moe_intermediate_size"],
            moe_latent_size=c["moe_latent_size"],
            routed_scaling_factor=float(c["routed_scaling_factor"])),
            **kw})

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def kinds(self) -> Tuple[str, ...]:
        return layers_of_pattern(self.pattern)

    def stacks(self) -> Tuple[str, ...]:
        """The kinds the pattern has, in :data:`KINDS`' order: one
        parameter stack each."""
        return tuple(k for k in KINDS if k in self.kinds())

    def kind_config(self, kind: str) -> "NemotronHConfig":
        """The config :class:`.llama.LlamaDecoderLayer` builds a layer of
        ``kind`` from."""
        mixer, ffn = KINDS[kind]
        return dataclasses.replace(
            self, attention_kind=mixer or "full",
            blocks=(("attn",) if mixer else ()) + (("ffn",) if ffn else ()))

    def layer_blocks(self) -> Tuple[str, ...]:
        return self.blocks

    def attention(self, tp_sync: bool = True):
        if self.attention_kind == "mamba2":
            return Mamba2Mixer(self, tp_sync=tp_sync, name="attn")
        return super().attention(tp_sync)

    def feed_forward(self, h: jax.Array, tp_sync: bool = True, valid=None):
        """``(output, [kept, dropped, elsewhere, hit, idle])``: the routed
        assignments of the real rows, by capacity over the held experts at
        the capacity of the step's rows, so nothing held can drop, and
        the held experts that took a row and that took none."""
        if valid is None:
            valid = jnp.ones(h.shape[:-1], bool)
        out, aux = MoE(
            num_experts=self.num_experts, hidden_size=self.hidden_size,
            intermediate_size=self.expert_intermediate_size,
            top_k=self.top_k, capacity_factor=None, router_type="sigmoid",
            router_scale=self.routed_scaling_factor,
            shared_expert_intermediate=self.intermediate_size,
            held=self.experts_held or (0, self.num_experts),
            expert_act="relu2", latent_size=self.moe_latent_size,
            count_hit=True, dtype=self.dtype, param_dtype=self.param_dtype,
            name="moe")(h, valid=valid)
        return out, jnp.concatenate([aux["assignments"],
                                     aux["experts_hit"]])

    def carried(self) -> Dict[str, Tuple[str, ...]]:
        """What of the cache's stacks a layer of each kind reads and
        writes."""
        return {kind: MIXER_CARRIED[mixer] + (("moe_counts",) if ffn else ())
                for kind, (mixer, ffn) in KINDS.items()}

    def mixers_of(self, mixer: str) -> int:
        """The layers whose mixer is ``mixer``, whatever follows it."""
        return sum(KINDS[k][0] == mixer for k in self.kinds())

    def cache_layers(self) -> Dict[str, Tuple[int, ...]]:
        """For each kind, the place of each of its layers (in its stack's
        order) among the layers of the same mixer: the layer's index in
        the cache's stacks."""
        out, seen = {k: [] for k in KINDS}, {}
        for kind in self.kinds():
            mixer = KINDS[kind][0]
            out[kind].append(seen.get(mixer, 0))
            seen[mixer] = seen.get(mixer, 0) + 1
        return {k: tuple(v) for k, v in out.items()}

    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """``(kind, first, count)`` of each run of like layers, ``first``
        the run's first index in its kind's stack."""
        return runs_of(self.kinds())

    def serving_family(self):
        from ..inference.paging import (MOE_KEPT_DROPPED_ELSEWHERE_HIT,
                                        ServingFamily, StateLeaf,
                                        StatePoolCache)

        layers = self.mixers_of("mamba2")
        return ServingFamily(
            forward=nemotron_h_forward_with_cache,
            cache_kind=StatePoolCache(
                pool_layers=self.mixers_of("full"), pack=1,
                leaves=(
                    StateLeaf("ssm", (layers,),
                              (self.mamba_d_state, self.d_inner),
                              jnp.float32),
                    StateLeaf("conv", (layers, self.mamba_d_conv - 1),
                              (self.conv_channels,), counted_as="tail")),
                moe_leaf=MOE_KEPT_DROPPED_ELSEWHERE_HIT),
            moe_counts=True,
            unsupported={**UNSUPPORTED, "quantized": "the states are "
                         "float32 beside the pool"})


def tiny_config(**kw) -> NemotronHConfig:
    """Test widths: a state of [8, 64] in two groups, heads of 16, eight
    experts of 24 in a latent of 32; the pattern has a paired and a lone
    mixer of each sort and a feed-forward with no mixer."""
    base = dict(
        vocab_size=256, hidden_size=64, intermediate_size=48,
        num_layers=9, pattern="MEM*EEME*", num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=4096, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=8, mamba_d_conv=4, mamba_n_groups=2,
        mamba_chunk_size=8, num_experts=8, top_k=3,
        expert_intermediate_size=24, moe_latent_size=32,
        routed_scaling_factor=2.5)
    base.update(kw)
    return NemotronHConfig(**base)


#: a published tensor's name within ``backbone.layers.{i}.`` -> its path
#: in a layer's parameters, by the block it belongs to; ``{e}`` is an
#: expert's index in the bank held here (the leaf's leading dimension)
_PUBLISHED_LEAVES = {
    "M": {"mixer.in_proj.weight": ("attn", "in_proj", "kernel"),
          "mixer.out_proj.weight": ("attn", "out_proj", "kernel"),
          "mixer.conv1d.weight": ("attn", "conv_kernel"),
          "mixer.conv1d.bias": ("attn", "conv_bias"),
          "mixer.A_log": ("attn", "A_log"), "mixer.D": ("attn", "D"),
          "mixer.dt_bias": ("attn", "dt_bias"),
          "mixer.norm.weight": ("attn", "norm", "scale")},
    "*": {"mixer.q_proj.weight": ("attn", "qkv", "q_kernel"),
          "mixer.k_proj.weight": ("attn", "qkv", "k_kernel"),
          "mixer.v_proj.weight": ("attn", "qkv", "v_kernel"),
          "mixer.o_proj.weight": ("attn", "o_proj", "kernel")},
    "E": {"mixer.gate.weight": ("moe", "router", "kernel"),
          "mixer.gate.e_score_correction_bias": ("moe", "router", "bias"),
          "mixer.experts.{e}.up_proj.weight": ("moe", "experts", "up"),
          "mixer.experts.{e}.down_proj.weight": ("moe", "experts", "down"),
          "mixer.shared_experts.up_proj.weight":
              ("moe", "shared", "up_kernel"),
          "mixer.shared_experts.down_proj.weight":
              ("moe", "shared", "down", "kernel"),
          "mixer.fc1_latent_proj.weight": ("moe", "latent_in"),
          "mixer.fc2_latent_proj.weight": ("moe", "latent_out")}}


def published_names(cfg: NemotronHConfig) -> Dict[str, Tuple]:
    """Every published per-layer tensor name
    (``backbone.layers.{i}.norm.weight``, ``.mixer.*``; an expert's with
    ``{e}`` for its index) -> ``(kind, index in the kind's stack, path in
    the layer's parameters)`` under ``model/layers_<kind>/layer``. A
    projection is stored ``[in, out]``, the published one ``[out, in]``;
    ``conv1d.weight`` is stored ``[C, W]``, published ``[C, 1, W]``."""
    names, seen, i = {}, {}, 0
    for kind in cfg.kinds():
        index = seen.get(kind, 0)
        seen[kind] = index + 1
        mixer, ffn = KINDS[kind]
        blocks = ([("M" if mixer == "mamba2" else "*", "input_norm")]
                  if mixer else []) + ([("E", "post_norm")] if ffn else [])
        for letter, norm in blocks:
            at = f"backbone.layers.{i}."
            names[at + "norm.weight"] = (kind, index, (norm, "scale"))
            for name, path in _PUBLISHED_LEAVES[letter].items():
                names[at + name] = (kind, index, path)
            i += 1
    return names


class NemotronHModel(nn.Module):
    """Embedding, the layer pattern, final norm: positions ``0..S-1``, no
    cache (tests, small training)."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        with device_scope("embed"):
            x = pl.ParallelEmbedding(
                num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="embed")(input_ids)
        if self.is_initializing():
            # the parameters: one stack a kind, each made by scanning the
            # kind's layer over its depth
            for kind in cfg.stacks():
                x, _ = nn.scan(
                    _ScanBody, variable_axes={"params": 0},
                    split_rngs={"params": True},
                    in_axes=(nn.broadcast,) * 3,
                    length=cfg.kinds().count(kind),
                    metadata_params={nn.PARTITION_NAME: "layers"},
                )(cfg.kind_config(kind), name=f"layers_{kind}")(
                    x, None, None, None)
        else:
            stacks = {kind: meta.unbox(
                self.variables["params"][f"layers_{kind}"])
                for kind in cfg.stacks()}
            x, _ = run_layers(cfg, stacks, x, None, None, cfg.carried())
        with device_scope("norm"):
            return RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype, name="norm")(x)


def _head(cfg: NemotronHConfig, **module):
    return pl.ColumnParallelLinear(
        features=cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, **module)


class NemotronHForCausalLM(nn.Module):
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids: jax.Array,
                 labels: Optional[jax.Array] = None,
                 ignore_index: int = -100) -> jax.Array:
        x = NemotronHModel(self.cfg, name="model")(input_ids)
        with device_scope("head"):
            logits = _head(self.cfg, gather_output=False,
                           name="lm_head")(x)
        if labels is not None:
            with device_scope("loss"):
                return lf.causal_lm_loss(logits, labels,
                                         ignore_index=ignore_index)
        return logits


def nemotron_h_forward_with_cache(cfg: NemotronHConfig, params, input_ids,
                                  positions, kv_cache, slot_ids=None,
                                  **unsupported):
    """The paged forward of the packed serving step, with
    :func:`.llama.llama_forward_with_cache`'s paged signature:
    ``input_ids``, ``positions [1, T]``, ``slot_ids [T]``, ``kv_cache`` a
    :class:`..inference.paging.StatePoolPagedCache`; returns ``(logits
    [1, T, V], new cache)``. The cache's stacks (K/V of the attention
    layers, the Mamba-2 layers' states and tails) and the step's counts of
    routed assignments and of held experts hit are the carry of every
    run's scan."""
    from ..inference import paging
    from ..inference.kv_cache import PAD_POSITION

    if any(unsupported.values()):
        raise ValueError(f"nemotron_h serves through the packed paged "
                         f"step only; got {sorted(unsupported)}")
    if not isinstance(kv_cache, paging.StatePoolPagedCache):
        raise ValueError("nemotron_h is served from the cache its cache "
                         "kind builds (paging.init_serving_cache)")
    p = params["params"]
    q_pos = jnp.asarray(positions, jnp.int32)[0]
    slot_ids = jnp.asarray(slot_ids, jnp.int32)
    with device_scope("embed"):
        x = pl.ParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype).apply(
            {"params": p["model"]["embed"]}, input_ids)
    pool_pos, mixer_view = mixer_views(cfg, kv_cache, q_pos, slot_ids)
    cache_layers = {k: jnp.asarray(v, jnp.int32)
                    for k, v in cfg.cache_layers().items() if v}

    def view_of(kind, carry, index):
        mixer = KINDS[kind][0]
        return mixer and mixer_view(mixer, carry, cache_layers[kind][index])

    def merge(carry, view, counts):
        new = {name: getattr(view, name) for name in carry
               if name != "moe_counts"}
        if "moe_counts" in carry:
            new["moe_counts"] = carry["moe_counts"] + counts
        return new

    carry = dict(k=kv_cache.k, v=kv_cache.v, **kv_cache.states,
                 moe_counts=jnp.zeros_like(kv_cache.moe_counts))
    stacks = {kind: p["model"][f"layers_{kind}"] for kind in cfg.stacks()}
    x, carry = run_layers(cfg, stacks, x, None, None, cfg.carried(), carry,
                          view_of, merge=merge,
                          valid=(q_pos < PAD_POSITION)[None],
                          positions=q_pos[None])
    with device_scope("norm"):
        x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype).apply(
            {"params": p["model"]["norm"]}, x)
    with device_scope("head"):
        logits = _head(cfg, gather_output=True).apply(
            {"params": p["lm_head"]}, x)
    return logits, kv_cache.replace(
        k=carry["k"], v=carry["v"], pos=pool_pos,
        states={name: carry[name] for name in kv_cache.states},
        moe_counts=carry["moe_counts"])
