"""MoE layer: router + expert bank + optional shared experts.

Analogue of the reference's ``modules/moe/model.py`` (``MoE:14``) and
``modules/moe/shared_experts.py`` (``SharedExperts:73``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ...obs.device_scopes import device_scope
from ...parallel import layers as pl
from ...parallel import mesh as ps
from .. import glu
from .expert_mlps import ExpertMLPs, relu2
from .routing import (GroupLimitedRouter, RouterSigmoid, RouterSinkhorn,
                      RouterSoftmaxBias, RouterTopK)

ROUTERS = {
    "top_k": RouterTopK,
    "sinkhorn": RouterSinkhorn,
    "group_limited": GroupLimitedRouter,
    "sigmoid": RouterSigmoid,
    "softmax_bias": RouterSoftmaxBias,
}


class SharedExperts(nn.Module):
    """Always-on dense MLP added to the routed output (reference
    ``shared_experts.py:73``): a GLU, or with ``act="relu2"`` ungated,
    ``down(relu(up x)^2)``, as :class:`.expert_mlps.ExpertMLPs` has it."""

    hidden_size: int
    intermediate_size: int
    act: str = "swiglu"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from ...parallel import mappings

        i_local = pl._maybe_local(self.intermediate_size, ps.TP_AXIS)
        axes, shape = (None, ps.TP_AXIS), (self.hidden_size, i_local)
        h = mappings.copy_to_tensor_parallel_region(x).astype(self.dtype)
        if self.act == "swiglu":
            gate, up = glu.declare(self, glu.DENSE, pl.default_kernel_init,
                                   axes, shape, self.param_dtype)
            g = glu.gated(*glu.project(h, gate.astype(self.dtype),
                                       up.astype(self.dtype)))
        else:
            up = self.param(glu.DENSE[1], nn.with_partitioning(
                pl.default_kernel_init, axes), shape, self.param_dtype)
            g = relu2(jnp.matmul(h, up.astype(self.dtype)))
        return pl.RowParallelLinear(
            features=self.hidden_size, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name="down")(g)


class MoE(nn.Module):
    """Mixture-of-experts block over flat or [B, S, H] inputs (reference
    ``MoE:14``). Returns ``(y, aux_losses)``."""

    num_experts: int
    hidden_size: int
    intermediate_size: int
    top_k: int = 2
    # None: an expert's capacity is the step's rows (float experts)
    capacity_factor: Optional[float] = 2.0
    dispatch_mode: str = "capacity"  # or "blockwise" (dropless)
    block_size: int = 512
    sentinel_empty: bool = False  # decode: DMA-elide unhit experts
    # EP dispatch wire ("fp32" | "int8" | "fp8") + ring overlap (None =
    # auto); blockwise-EP only — see parallel/ep_dispatch.py
    ep_wire_dtype: str = "fp32"
    ep_overlap: Optional[bool] = None
    # expert bank implementation: "float" (ExpertMLPs), "mx_fp4"/"mx_fp8"
    # (packed microscaling weights, quantization.mx_layers.MXExpertMLPs)
    expert_impl: str = "float"
    router_type: str = "top_k"
    # what the chosen experts' weights are multiplied by (a checkpoint's
    # ``routed_scaling_factor``; the sigmoid and the top-k router)
    router_scale: float = 1.0
    # the sigmoid router's limit by groups
    # (:class:`.routing.RouterSigmoid`: 1 limits nothing)
    n_group: int = 1
    topk_group: int = 1
    shared_expert_intermediate: int = 0
    # ``(first, count)``: the experts this device holds of the
    # ``num_experts`` real experts (None: all of them), whatever else the
    # router scores beside them (``identity_experts``). The bank has
    # ``count`` experts, an assignment to an expert held elsewhere takes
    # no slot and adds nothing, the shared expert is whole; capacity
    # dispatch of float experts, no ep axis (:class:`ExpertMLPs`)
    held: Optional[Tuple[int, int]] = None
    # router slots past the ``num_experts`` real ones that are identity
    # (zero-computation) experts: the router scores ``num_experts +
    # identity_experts`` slots, a choice of slot ``num_experts`` or later
    # takes no slot of the bank, is nowhere else either, and adds its
    # weight times the row's own input, on the device that owns the row
    identity_experts: int = 0
    # the routed and the shared experts' form
    # (:attr:`.expert_mlps.ExpertMLPs.act`; float experts)
    expert_act: str = "swiglu"
    # > 0: the routed experts work in a latent of this width: ``latent_in
    # [H, latent]`` ahead of the bank and ``latent_out [latent, H]`` behind
    # its weighted sum, linear, one pair a layer (what an exchange between
    # the devices that share the layer would carry is latent rows); the
    # router and the shared expert read the row itself
    latent_size: int = 0
    # ``aux["experts_hit"]``: :attr:`.expert_mlps.ExpertMLPs.count_hit`
    count_hit: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, valid: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Dict]:
        """``valid`` (bool, ``x``'s shape without its last dimension; float
        experts only) marks the real rows of a packed serving step: the
        others take no expert's slot and ``aux["assignments"]`` counts the
        real rows' ``[kept, dropped]`` (with ``held``: ``[kept, dropped,
        elsewhere]``, of the real experts; with ``identity_experts``, given
        ``valid`` or not: ``[kept, dropped, elsewhere, identity]``)."""
        orig_shape = x.shape
        h = self.hidden_size
        flat = x.reshape(-1, h)
        held = self.held
        if self.identity_experts:
            # a choice past the real experts is one of an expert not held
            # here as far as the bank goes: the held dispatch leaves it out
            held = held or (0, self.num_experts)
            if valid is None:
                valid = jnp.ones(orig_shape[:-1], bool)

        router_cls = ROUTERS[self.router_type]
        router_kw = dict(num_experts=self.num_experts + self.identity_experts,
                         dtype=self.dtype, param_dtype=self.param_dtype,
                         name="router")
        if self.router_type != "sinkhorn":
            router_kw["top_k"] = self.top_k
        if (self.router_type in ("sigmoid", "softmax_bias")
                or self.router_scale != 1.0):
            router_kw["scale"] = self.router_scale
        if self.n_group > 1:
            if self.router_type != "sigmoid":
                raise ValueError("MoE: n_group is the sigmoid router's")
            router_kw.update(n_group=self.n_group,
                             topk_group=self.topk_group)
        if held is not None and (self.expert_impl != "float"
                                 or valid is None):
            raise ValueError("MoE: a share of the experts (held) is float "
                             "experts under the packed step's valid rows")
        if self.expert_impl != "float" and (
                self.expert_act != "swiglu" or self.latent_size
                or self.count_hit):
            raise ValueError("MoE: expert_act, latent_size and count_hit "
                             "are the float experts'")
        with device_scope("ffn.router"):
            gates, idx, aux = router_cls(**router_kw)(flat)

        if self.expert_impl.startswith("mx_"):
            if self.dispatch_mode != "capacity":
                # MXExpertMLPs only implements the capacity path; silently
                # ignoring a requested blockwise dispatch would change the
                # drop behaviour without telling the user (advisor r3)
                raise ValueError(
                    f"expert_impl={self.expert_impl!r} supports only "
                    f"dispatch_mode='capacity' (got "
                    f"{self.dispatch_mode!r}); use float experts for "
                    "blockwise/dropless dispatch")
            from ...quantization.mx_layers import MXExpertMLPs

            experts = MXExpertMLPs(
                num_experts=self.num_experts, hidden_size=h,
                intermediate_size=self.intermediate_size,
                top_k=gates.shape[-1], capacity_factor=self.capacity_factor,
                mx_format=self.expert_impl[len("mx_"):],
                dtype=self.dtype, param_dtype=self.param_dtype,
                name="experts")
        elif self.expert_impl in ("int8", "fp8"):
            if self.dispatch_mode != "capacity":
                raise ValueError(
                    f"expert_impl={self.expert_impl!r} supports only "
                    f"dispatch_mode='capacity' (got "
                    f"{self.dispatch_mode!r}); use float experts for "
                    "blockwise/dropless dispatch")
            from ...quantization.quantization_layers import \
                QuantizedExpertMLPs
            from ...quantization.quantization_utils import QuantizedDtype

            experts = QuantizedExpertMLPs(
                num_experts=self.num_experts, hidden_size=h,
                intermediate_size=self.intermediate_size,
                top_k=gates.shape[-1], capacity_factor=self.capacity_factor,
                quantized_dtype=(QuantizedDtype.INT8
                                 if self.expert_impl == "int8"
                                 else QuantizedDtype.FP8E4M3),
                dtype=self.dtype, param_dtype=self.param_dtype,
                name="experts")
        elif self.expert_impl != "float":
            raise ValueError(f"unknown expert_impl {self.expert_impl!r}")
        else:
            experts = ExpertMLPs(
                num_experts=(self.num_experts if held is None
                             else held[1]),
                hidden_size=self.latent_size or h,
                intermediate_size=self.intermediate_size,
                top_k=gates.shape[-1], capacity_factor=self.capacity_factor,
                dispatch_mode=self.dispatch_mode,
                block_size=self.block_size,
                sentinel_empty=self.sentinel_empty,
                ep_wire_dtype=self.ep_wire_dtype,
                ep_overlap=self.ep_overlap,
                dtype=self.dtype, param_dtype=self.param_dtype,
                held=held, act=self.expert_act, count_hit=self.count_hit,
                name="experts")
        rows = flat
        if self.latent_size:
            latent_in, latent_out = (
                self.param(name, nn.with_partitioning(
                    pl.default_kernel_init, (None, None)), shape,
                    self.param_dtype).astype(self.dtype)
                for name, shape in (("latent_in", (h, self.latent_size)),
                                    ("latent_out", (self.latent_size, h))))
            with device_scope("ffn.latent"):
                rows = jnp.matmul(flat.astype(self.dtype), latent_in)
        # the routed experts: dispatch, the bank's products, combine
        with device_scope("ffn.experts"):
            if valid is None:
                y, eaux = experts(rows, gates, idx)
            else:
                # the older name of the same scope: the benchmark's
                # moe_expert_share_pct.batch is held to it
                # (tests/test_chip_compile.py)
                with jax.named_scope("routed_experts"):
                    y, eaux = experts(rows, gates, idx,
                                      valid=valid.reshape(-1))
        aux.update(eaux)
        if self.latent_size:
            with device_scope("ffn.latent"):
                y = jnp.matmul(y, latent_out)

        if self.identity_experts:
            with device_scope("ffn.identity"):
                chose = (idx >= self.num_experts) & valid.reshape(-1, 1)
                weight = jnp.sum(jnp.where(chose, gates, 0.0), axis=-1)
                y = (y.astype(jnp.float32) + weight[:, None]
                     * flat.astype(jnp.float32)).astype(y.dtype)
                identity = jnp.sum(chose).astype(jnp.int32)
                kept, dropped, elsewhere = aux["assignments"]
                aux["assignments"] = jnp.stack(
                    [kept, dropped, elsewhere - identity, identity])

        if self.shared_expert_intermediate > 0:
            with device_scope("ffn.shared"):
                y = y + SharedExperts(
                    hidden_size=h,
                    intermediate_size=self.shared_expert_intermediate,
                    act=self.expert_act, dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    name="shared")(flat)
        return y.reshape(orig_shape), aux
