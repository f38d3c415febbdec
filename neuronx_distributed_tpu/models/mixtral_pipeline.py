"""Pipeline-parallel Mixtral (MoE) training path.

MoE × PP composition (the reference's mixtral example runs under
``NxDPPModel`` the same way its llama one does): the MoE decoder stack is
partitioned over the ``pp`` mesh axis exactly like
:mod:`.llama_pipeline`, with the router auxiliary losses accumulated
per-stage inside the scanned GPipe engine (``pipeline_spmd(with_aux=True)``)
and psum'd over pp into the loss — the analogue of the reference
broadcasting/averaging user outputs across the pipeline
(``pipeline/model.py`` loss reduction).

Params are byte-compatible with :class:`.mixtral.MixtralForCausalLM`
(``scan_layers=True``).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from ..modules import attention as attn_mod
from ..modules.norms import RMSNorm
from ..parallel import layers as pl
from ..parallel import loss_functions as lf
from ..parallel import mappings
from ..parallel import mesh as ps
from ..pipeline import spmd_engine as eng
from ..utils.remat import resolve_remat_policy
from .llama import _ScanBody
from .llama_pipeline import PIPELINE_LOGICAL_RULES  # noqa: F401 (re-export)
from .mixtral import MixtralConfig


def pipelined_moe_loss_fn(cfg: MixtralConfig, num_microbatches: int,
                          ignore_index: int = -100):
    """Build ``pp_loss(params, ids, labels) -> scalar`` (GPipe engine) for
    the MoE decoder; includes the router aux losses."""
    if not cfg.scan_layers:
        raise ValueError("pipeline path requires scan_layers=True")
    if getattr(cfg, "attention_dropout", 0.0) > 0.0:
        # the MoE pipeline paths carry no per-microbatch rng channel yet
        # (the llama 1F1B executor does — llama_pipeline.make_1f1b_grad_fn
        # slot-keys the masks); a silent skip would fake regularization
        raise ValueError(
            "attention_dropout is not threaded through the MoE pipeline "
            "engines; set attention_dropout=0 for MoE PP configs")

    embed_mod = pl.ParallelEmbedding(
        num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype)
    norm_mod = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                       sequence_parallel=cfg.sequence_parallel)
    head_mod = pl.ColumnParallelLinear(
        features=cfg.vocab_size, use_bias=False, gather_output=False,
        sequence_parallel=cfg.sequence_parallel,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype)

    def pp_loss(params, ids, labels):
        p = params["params"]
        S = ps.get_pipeline_model_parallel_size()
        M = num_microbatches
        if cfg.num_layers % S != 0:
            raise ValueError(
                f"num_layers {cfg.num_layers} not divisible by pp {S}")
        l_local = cfg.num_layers // S

        cos, sin = attn_mod.precompute_rope(
            cfg.head_dim_, cfg.max_seq_len, cfg.rope_theta,
            use_scaled=cfg.rope_scaling)

        embed_p = jax.tree_util.tree_map(eng.stage_replicated_param,
                                         p["model"]["embed"])
        ids_mb = eng.microbatch(ids, M)

        def input_fn(ids_):
            x = embed_mod.apply({"params": embed_p}, ids_)
            if cfg.sequence_parallel:
                # stage activations ride the ring SP-sharded; the MoE
                # block's own gather/scatter (MixtralConfig.feed_forward)
                # handles the regather inside each stage (reference
                # moe/model.py:154 delayed reduce-scatter inside NxDPPModel)
                x = mappings.scatter_to_sequence_parallel_region(x,
                                                                 seq_dim=1)
            return x

        body = nn.scan(
            _ScanBody,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
            length=l_local,
        )(cfg)

        def stage_fn(act):
            out, aux = body.apply({"params": p["model"]["layers"]}, act,
                                  cos, sin, None)
            # aux: [l_local, 2] per-layer (load_balance, z) — sum layers
            return out, jnp.sum(aux, axis=0)

        if cfg.remat:
            stage_fn = jax.checkpoint(
                stage_fn, policy=resolve_remat_policy(cfg.remat_policy))

        outs, aux_local = eng.pipeline_spmd(stage_fn, ids_mb, S, M,
                                            with_aux=True,
                                            input_fn=input_fn)
        # global router aux: sum over stages with the fwd-psum/bwd-identity
        # mapping (raw psum would transpose to psum and hand every stage
        # S copies of the cotangent), then mean over microbatches
        aux_total = mappings.reduce_from_tensor_parallel_region(
            aux_local, ps.PP_AXIS) / M

        norm_p = jax.tree_util.tree_map(eng.stage_replicated_param,
                                        p["model"]["norm"])
        head_p = jax.tree_util.tree_map(eng.stage_replicated_param,
                                        p["lm_head"])
        labels_mb = eng.microbatch(labels, M)

        def mb_loss(carry, om):
            o, lb = om
            h = norm_mod.apply({"params": norm_p}, o)
            logits = head_mod.apply({"params": head_p}, h)
            per_tok = lf.parallel_cross_entropy(logits, lb,
                                                ignore_index=ignore_index)
            n_valid = jnp.sum((lb != ignore_index).astype(jnp.float32))
            return (carry[0] + jnp.sum(per_tok), carry[1] + n_valid), None

        (loss_sum, denom), _ = jax.lax.scan(
            mb_loss,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (outs, labels_mb))
        ce = eng.last_stage_value(loss_sum / jnp.maximum(denom, 1.0))
        loss = (ce + cfg.router_aux_coef * aux_total[0]
                + cfg.router_z_coef * aux_total[1])
        return eng.data_parallel_mean(loss)

    return pp_loss


def make_moe_pipeline_grad_fn(cfg: MixtralConfig, num_microbatches: int,
                              param_specs: Any, ignore_index: int = -100):
    """``grad_fn(params, batch) -> (loss, grads)`` for
    :func:`..trainer.make_train_step` (GPipe schedule; cf.
    :func:`.llama_pipeline.make_pipeline_grad_fn`)."""
    from ..parallel import grads as grads_mod

    pp_loss = pipelined_moe_loss_fn(cfg, num_microbatches, ignore_index)

    def inner(params, ids, labels):
        loss, g = jax.value_and_grad(pp_loss)(params, ids, labels)
        g = grads_mod.allreduce_gradients(g, specs=param_specs)
        return loss, g

    def grad_fn(params, batch):
        mesh = ps.get_mesh()
        return ps.shard_map(
            inner, mesh,
            in_specs=(param_specs, P(ps.DP_AXIS, None), P(ps.DP_AXIS, None)),
            out_specs=(P(), param_specs))(
                params, batch["input_ids"], batch["labels"])

    return grad_fn


def make_moe_1f1b_grad_fn(cfg: MixtralConfig, num_microbatches: int,
                          param_specs: Any, num_chunks: int = 1,
                          ignore_index: int = -100):
    """Explicit 1F1B / interleaved executor for the MoE decoder
    (:mod:`..pipeline.engine_1f1b` with ``aux_weight`` seeding the router
    aux cotangents) — the memory profile DBRX-scale MoE needs under pp.

    For ``num_chunks > 1`` the layer-stack params must already be in
    *interleaved* order — convert with
    :func:`.llama_pipeline.interleave_pipeline_params` (generic over the
    scanned ``model/layers`` subtree); a canonical-order tree would
    silently train a layer-permuted model.

    NOTE: mirrors :func:`.llama_pipeline.make_1f1b_grad_fn` (which adds
    sequence-parallel + tied embeddings but no aux); keep the scaffolding
    of the two in sync."""
    from ..parallel import grads as grads_mod
    from ..pipeline import engine_1f1b as e1

    if not cfg.scan_layers:
        raise ValueError("pipeline path requires scan_layers=True")
    if getattr(cfg, "attention_dropout", 0.0) > 0.0:
        # the MoE 1F1B path does not pass the engine's slot through its
        # stage_fn yet; adopt llama_pipeline.make_1f1b_grad_fn's slot-keyed
        # rng (stage_takes_slot=True) before lifting this guard — a silent
        # skip would fake regularization
        raise ValueError(
            "attention_dropout is not threaded through the MoE pipeline "
            "engines; set attention_dropout=0 for MoE PP configs")
    C = num_chunks

    embed_mod = pl.ParallelEmbedding(
        num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype)
    norm_mod = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                       sequence_parallel=cfg.sequence_parallel)
    head_mod = pl.ColumnParallelLinear(
        features=cfg.vocab_size, use_bias=False, gather_output=False,
        sequence_parallel=cfg.sequence_parallel,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype)

    def inner(params, ids, labels):
        p = params["params"]
        S = ps.get_pipeline_model_parallel_size()
        M = num_microbatches
        if cfg.num_layers % (S * C) != 0:
            raise ValueError(
                f"num_layers {cfg.num_layers} not divisible by "
                f"stages*chunks {S * C}")
        lv = cfg.num_layers // (S * C)
        denom = jnp.maximum(
            jnp.sum(labels != ignore_index).astype(jnp.float32), 1.0)
        cos, sin = attn_mod.precompute_rope(
            cfg.head_dim_, cfg.max_seq_len, cfg.rope_theta,
            use_scaled=cfg.rope_scaling)

        def embed_fn(ep, ids_):
            x = embed_mod.apply({"params": ep}, ids_)
            if cfg.sequence_parallel:
                x = mappings.scatter_to_sequence_parallel_region(x, seq_dim=1)
            return x

        body = nn.scan(
            _ScanBody,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
            length=lv,
        )(cfg)

        def stage_fn(chunk_p, act):
            out, aux = body.apply({"params": chunk_p}, act, cos, sin, None)
            return out, jnp.sum(aux, axis=0).astype(jnp.float32)

        if cfg.remat:
            stage_fn = jax.checkpoint(
                stage_fn, policy=resolve_remat_policy(cfg.remat_policy))

        def head_loss_fn(hp, act, lb):
            h = norm_mod.apply({"params": hp["norm"]}, act)
            logits = head_mod.apply({"params": hp["lm_head"]}, h)
            per_tok = lf.parallel_cross_entropy(logits, lb,
                                                ignore_index=ignore_index)
            return jnp.sum(per_tok) / denom

        layers_c = jax.tree_util.tree_map(
            lambda x: x.reshape((C, lv) + x.shape[1:]), p["model"]["layers"])
        eng_params = {"embed": p["model"]["embed"], "layers": layers_c,
                      "head": {"norm": p["model"]["norm"],
                               "lm_head": p["lm_head"]}}
        ids_mb = eng.microbatch(ids, M)
        labels_mb = eng.microbatch(labels, M)
        m_run = M
        if C > 1 and M % S != 0:
            # pad microbatches with all-ignore labels (cf. llama_pipeline);
            # their router aux is masked via num_real_microbatches
            m_run = -(-M // S) * S
            ids_mb = jnp.concatenate(
                [ids_mb, jnp.zeros((m_run - M,) + ids_mb.shape[1:],
                                   ids_mb.dtype)])
            labels_mb = jnp.concatenate(
                [labels_mb, jnp.full((m_run - M,) + labels_mb.shape[1:],
                                     ignore_index, labels_mb.dtype)])
        aux_weight = jnp.asarray(
            [cfg.router_aux_coef, cfg.router_z_coef], jnp.float32) / M

        loss, g = e1.pipeline_1f1b_grads(
            embed_fn, stage_fn, head_loss_fn, eng_params, ids_mb, labels_mb,
            num_stages=S, num_microbatches=m_run, num_chunks=C,
            aux_weight=aux_weight, num_real_microbatches=M)

        g_layers = jax.tree_util.tree_map(
            lambda x: x.reshape((C * lv,) + x.shape[2:]), g["layers"])
        grads = {"params": {
            "model": {"embed": g["embed"], "layers": g_layers,
                      "norm": g["head"]["norm"]},
            "lm_head": g["head"]["lm_head"]}}
        grads = grads_mod.allreduce_gradients(grads, specs=param_specs)
        return eng.data_parallel_mean(loss), grads

    def grad_fn(params, batch):
        mesh = ps.get_mesh()
        return ps.shard_map(
            inner, mesh,
            in_specs=(param_specs, P(ps.DP_AXIS, None), P(ps.DP_AXIS, None)),
            out_specs=(P(), param_specs))(
                params, batch["input_ids"], batch["labels"])

    return grad_fn
