"""Training orchestration.

Analogue of the reference's ``trainer/trainer.py``:
``initialize_parallel_model:147`` (build model sharded over the mesh),
``initialize_parallel_optimizer:237`` (ZeRO-1-aware optimizer state), and the
per-step path of ``trainer/optimizer.py`` / ``NxDModel.run_train``.

TPU-native shape: one jitted SPMD ``train_step`` (loss → grad → update) with
``NamedSharding``-annotated params and optimizer state. Sharded-grad
reduction and ZeRO-1's reduce-scatter/all-gather come from GSPMD + the XLA
latency-hiding scheduler rather than hand-written bucketed all-reduce
(reference ``grads.py:259``). The tensor-parallel collectives do not: a
row-parallel exit's all-reduce is a true dependence no scheduler hides, so
where the projections' decomposed rings (``ops/collective_matmul``) would
engage, the default step computes loss and gradients inside ``shard_map``
with the mesh's axes bound (``_tp_rings_engage``) and GSPMD keeps the
optimizer around it. There the step also lays the residual stream out
(``_sharded_stream_cfg``): between a row-parallel exit and the next
column-parallel entry it is sharded over the sequence
(``sequence_parallel=True`` on the clone the step differentiates), so the
exit is the reduce-scatter ring alone and its all-gather half rides inside
the next projection's ring (``cm.all_gather_matmul``) instead of standing
exposed behind the exit. The layout changes no number the model computes
and no parameter, spec, optimizer state or checkpoint: like what a
rematerialised layer keeps, it is the step's to choose from what it can
see at trace time, with no option.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax import struct
from flax.core import meta
from jax.sharding import NamedSharding, PartitionSpec

from ..config import NxDConfig
from ..obs.device_scopes import device_scope
from ..ops import collective_matmul as cm
from ..parallel import comm
from ..parallel import comm_compressed as cc
from ..parallel import grads as grads_mod
from ..parallel import mesh as ps
from ..utils import remat
from ..utils.device import memory_limit_bytes
from . import optimizer as opt_mod


class TrainState(struct.PyTreeNode):
    """Step + params + optimizer state (flax TrainState without the apply_fn
    closure, so it stays a clean pytree for checkpointing).

    ``comm_error``: gradient-compression error-feedback buffers (the
    per-reduce-rank quantization residue re-injected next step; see
    ``parallel/comm_compressed.py``). None unless the config enables a
    quantized ``grad_comm_dtype`` with error feedback — None flattens to
    an empty subtree, so checkpoints and pytree structure are unchanged
    for uncompressed runs. When present it is *checkpointed state*
    (docs/resilience.md): dropping it on restore silently replays one
    step of quantization residue.
    """

    step: jax.Array
    params: Any
    opt_state: Any
    comm_error: Any = None


@struct.dataclass
class ParallelModel:
    """Bundle returned by :func:`initialize_parallel_model` — the analogue of
    the reference's ``NxDModel`` wrapper (``trainer/model.py:8``)."""

    module: nn.Module = struct.field(pytree_node=False)
    config: NxDConfig = struct.field(pytree_node=False)
    param_specs: Any = struct.field(pytree_node=False)
    param_shapes: Any = struct.field(pytree_node=False)

    def param_shardings(self):
        return jax.tree_util.tree_map(
            ps.named_sharding_for_spec, self.param_specs,
            is_leaf=lambda s: isinstance(s, PartitionSpec))


def _spec_tree(boxed_variables, logical_axis_rules=None) -> Any:
    """PartitionSpec tree from flax Partitioned metadata. Logical axis names
    that are not mesh axes are mapped through ``logical_axis_rules`` (e.g.
    ``{"layers": "pp"}`` for pipeline parallelism) and otherwise replicated.

    RULE-mapped axes keep their mesh axis even when the dim is not
    divisible by the axis size: GSPMD shards uneven dims by padding the
    last shard, so an odd layer count over pp still stores ~1/S of the
    stack per stage (reference partitions unevenly, partition.py:280; the
    pipeline grad_fn zero-pads to a divisible length before entering its
    shard_map). Direct mesh-axis annotations (e.g. tp on a hidden dim)
    keep failing loudly on indivisibility — those are genuine
    misconfigurations.
    """
    specs = nn.get_partition_spec(boxed_variables)
    mesh = ps.get_mesh()
    mesh_axes = set(mesh.axis_names)
    sizes = dict(mesh.shape)
    if ps.get_expert_model_parallel_size() > 1:
        # expert-view axes stay in the spec: such params are placed on the
        # expert mesh view (ps.named_sharding_for_spec), making GSPMD EP
        # shard expert weights over ep instead of replicating them
        em = ps.get_expert_mesh()
        mesh_axes |= set(em.axis_names)
        sizes.update(em.shape)
    rules = logical_axis_rules or {}

    def map_axis(a, dim_size):
        if a in mesh_axes:
            return a
        return rules.get(a)

    def clean(spec, shape):
        if not isinstance(spec, PartitionSpec):
            return PartitionSpec()
        dims = list(shape) + [None] * (len(spec) - len(shape))
        out = []
        for p, d in zip(spec, dims):
            if p is None:
                out.append(None)
            elif isinstance(p, tuple):
                kept = tuple(m for m in (map_axis(a, d) for a in p)
                             if m is not None)
                out.append(kept if kept else None)
            else:
                out.append(map_axis(p, d))
        return PartitionSpec(*out)

    shapes = jax.tree_util.tree_map(
        lambda x: tuple(jnp.shape(x)), meta.unbox(boxed_variables))
    return jax.tree_util.tree_map(
        clean, specs, shapes, is_leaf=lambda s: isinstance(s, PartitionSpec))


def initialize_parallel_model(
    cfg: NxDConfig,
    module: nn.Module,
    rng: jax.Array,
    *sample_args,
    method: Optional[Any] = None,
    logical_axis_rules: Optional[dict] = None,
) -> Tuple[ParallelModel, Any]:
    """Shape-evaluate the model, derive param shardings from the layer
    partitioning metadata, and initialise params *already sharded* (XLA
    materialises each shard on its device — the analogue of the reference's
    meta-device init + sequential move, ``utils/model_utils.py:257,335``).

    Returns ``(ParallelModel, params)``.
    """
    init_fn = functools.partial(module.init, method=method)
    boxed_shapes = jax.eval_shape(init_fn, rng, *sample_args)
    specs = _spec_tree(boxed_shapes, logical_axis_rules)
    shapes = jax.tree_util.tree_map(
        lambda x: tuple(x.shape), meta.unbox(boxed_shapes))

    # Uneven RULE-mapped stacks (odd layer count over pp): NamedSharding
    # requires divisible dims, so the STORAGE is zero-padded up to the next
    # multiple — inside the jitted init, so GSPMD materialises only each
    # device's shard, never a replicated [L] stack. Per-stage param and
    # optimizer bytes are ~1/S of dense (reference partitions unevenly,
    # partition.py:280). Pad rows are zero, their grads are masked zero by
    # the pipeline grad_fn, and ``llama_pipeline.unpad_pipeline_params``
    # strips them for export/serving. ONLY logical-rule axes (e.g.
    # "layers"→pp) pad; direct mesh-axis annotations (tp on a vocab or
    # feature dim) keep failing loudly — padding those would silently
    # change model numerics (e.g. pad vocab columns entering the CE
    # logsumexp of a tied head).
    sizes = dict(ps.get_mesh().shape)
    rules = logical_axis_rules or {}
    raw_specs = nn.get_partition_spec(boxed_shapes)

    def _pad_amount(raw, spec, shape):
        rule_mapped = (isinstance(raw, PartitionSpec) and len(raw)
                       and isinstance(raw[0], str) and raw[0] in rules)
        if (rule_mapped and isinstance(spec, PartitionSpec) and len(spec)
                and shape and isinstance(spec[0], str)):
            n = sizes.get(spec[0])
            if n and shape[0] % n != 0:
                return (-(-shape[0] // n)) * n - shape[0]
        return 0

    pads = jax.tree_util.tree_map(
        _pad_amount, raw_specs, specs, shapes,
        is_leaf=lambda s: isinstance(s, PartitionSpec))
    needs_pad = any(jax.tree_util.tree_leaves(pads))
    if needs_pad:
        def unboxed_init(r, *a):
            p = meta.unbox(init_fn(r, *a))
            return jax.tree_util.tree_map(
                lambda x, n: jnp.pad(
                    x, [(0, n)] + [(0, 0)] * (x.ndim - 1)) if n else x,
                p, pads)
        # pads leads: its int leaves are true leaves, while shapes' tuple
        # leaves would be descended into as containers
        shapes = jax.tree_util.tree_map(
            lambda n, s: (s[0] + n,) + tuple(s[1:]) if n else s,
            pads, shapes)
    else:
        def unboxed_init(r, *a):
            return meta.unbox(init_fn(r, *a))

    shardings = jax.tree_util.tree_map(
        ps.named_sharding_for_spec, specs,
        is_leaf=lambda s: isinstance(s, PartitionSpec))
    init_jit = jax.jit(unboxed_init, out_shardings=shardings)
    params = init_jit(rng, *sample_args)
    pm = ParallelModel(module=module, config=cfg, param_specs=specs,
                       param_shapes=shapes)
    return pm, params


def initialize_parallel_optimizer(
    pm: ParallelModel,
    params: Any,
    learning_rate: Any = 1e-4,
    weight_decay: float = 0.01,
    **adam_kw,
) -> Tuple[optax.GradientTransformation, TrainState, Any]:
    """Create the optimizer and a sharded :class:`TrainState`.

    ZeRO-1 (reference ``NeuronZero1Optimizer``): when enabled in the config,
    optimizer-state shardings are extended over the merged dp×cp axes.
    Returns ``(tx, state, state_shardings)``.
    """
    cfg = pm.config
    tx = opt_mod.make_optimizer(cfg, learning_rate=learning_rate,
                                weight_decay=weight_decay, **adam_kw)
    opt_shape = jax.eval_shape(tx.init, params)
    opt_specs = opt_mod.zero1_state_specs(
        opt_shape, pm.param_specs, pm.param_shapes,
        enabled=cfg.optimizer.zero_one_enabled)
    mesh = ps.get_mesh()
    to_shard = lambda tree: jax.tree_util.tree_map(
        ps.named_sharding_for_spec, tree,
        is_leaf=lambda s: isinstance(s, PartitionSpec))
    opt_shardings = to_shard(opt_specs)
    opt_state = jax.jit(tx.init, out_shardings=opt_shardings)(params)

    # Gradient-compression error feedback: allocate the per-reduce-rank
    # residue buffers alongside the optimizer state so they are carried
    # (and checkpointed) in the TrainState.
    comm_error = None
    err_shardings = None
    comp = cc.from_config(cfg)
    if comp is not None and comp.quantized and comp.error_feedback:
        red_axes = tuple(ax for ax in (ps.DP_AXIS, ps.CP_AXIS)
                         if dict(mesh.shape).get(ax, 1) > 1)
        if red_axes:
            ef_specs = cc.error_feedback_specs(pm.param_specs, red_axes)
            err_shardings = to_shard(ef_specs)
            comm_error = jax.jit(
                lambda p: cc.init_error_feedback(p, pm.param_specs,
                                                 red_axes),
                out_shardings=err_shardings)(params)

    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=opt_state, comm_error=comm_error)
    state_shardings = TrainState(
        step=NamedSharding(mesh, PartitionSpec()),
        params=to_shard(pm.param_specs),
        opt_state=opt_shardings,
        comm_error=err_shardings)
    return tx, state, state_shardings


def _tp_rings_engage(pm: ParallelModel, mesh, batch) -> bool:
    """Whether the default step binds the mesh's axes for this batch: the
    layers' own rule (``cm.overlap_engaged``) read from the mesh instead of
    a bound axis — the tp axis has ``cm.MIN_AUTO_AXIS_SIZE`` ranks or more
    (any size with ``tp_overlap_comm=True``, never with ``False``) and the
    batch's sequence tiles over it. Only tensor x data meshes: pipeline,
    context and expert parallelism keep their GSPMD step."""
    if not _tensor_by_data(mesh):
        return False
    shape = tuple(jnp.shape(batch["input_ids"]))
    return len(shape) == 2 and cm.overlap_engaged_at(
        pm.config.parallel.tp_overlap_comm,
        dict(mesh.shape).get(ps.TP_AXIS, 1), shape + (1,), 1,
        needs_divisible=True)


def _tensor_by_data(mesh) -> bool:
    """No pipeline, context or expert parallelism on ``mesh``."""
    sizes = dict(mesh.shape)
    return not (any(sizes.get(ax, 1) > 1 for ax in (ps.PP_AXIS, ps.CP_AXIS))
                or ps.get_expert_model_parallel_size() > 1)


def _bytes_a_chip(tree, shardings, cast=None) -> int:
    """Bytes one chip holds of ``tree`` placed as ``shardings``, a tree
    prefix of it, says; with ``cast``, of the copies in that dtype of the
    leaves that have another."""
    def itemsize(x):
        if cast is None:
            return x.dtype.itemsize
        return jnp.dtype(cast).itemsize * (x.dtype != cast)

    return sum(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda s, sub: sum(math.prod(s.shard_shape(x.shape)) * itemsize(x)
                           for x in jax.tree_util.tree_leaves(sub)),
        shardings, tree)))


def _record_remat_choice(policy: str, kept_bytes: int) -> None:
    """The bound step's trace-time decision, once a trace like
    ``cm._record_decision``: ``nxd_train_remat_kept_bytes{policy}``."""
    from ..obs.metrics import get_registry

    reg = get_registry()
    if not reg.enabled:
        return
    reg.gauge("nxd_train_remat_kept_bytes",
              "Bytes of gate's and up's products a chip keeps across "
              "forward and backward under the remat policy the bound "
              "train step chose from the chip's memory (set once per "
              "trace; 0 under save_attention).",
              labels=("policy",)).labels(policy=policy).set(
                  kept_bytes if policy == remat.RICH_REMAT_POLICY else 0)


def _record_stream_layout(shards: int) -> None:
    """The bound step's other trace-time decision, beside
    :func:`_record_remat_choice`: ``nxd_train_residual_sequence_shards``."""
    from ..obs.metrics import get_registry

    reg = get_registry()
    if not reg.enabled:
        return
    reg.gauge("nxd_train_residual_sequence_shards",
              "Tensor-parallel ranks the bound train step's residual "
              "stream is sharded over along the sequence between two "
              "projections (set once per trace; 1: replicated, every "
              "row-parallel exit all-gathers).").set(shards)


def _sharded_stream_cfg(cfg):
    """``cfg`` with the residual stream sharded over the sequence, or None
    where the model cannot take it or has it already: no such field, a
    config whose own checks refuse it (``activation_sync_fraction < 1``
    elides exits that a reduce-scatter cannot, quantized linears enter
    through ``copy_to``), or LoRA (an adapted projection reads the
    gathered activations and keeps its monolithic entry: no ring would
    hide the gather)."""
    if (not dataclasses.is_dataclass(cfg)
            or getattr(cfg, "sequence_parallel", None) is not False
            or getattr(cfg, "lora", None) is not None):
        return None
    try:
        return dataclasses.replace(cfg, sequence_parallel=True)
    except ValueError:
        return None


def _module_for_step(pm: ParallelModel, mesh, state, state_shardings,
                     rows: Tuple[int, int], accumulating: bool,
                     rings: bool = False) -> nn.Module:
    """``pm.module`` as the explicit path differentiates it: a clone with
    the step's two trace-time choices, parameters and their specs
    untouched.

    **The residual stream's layout.** Where the projections' rings engage
    (``rings``: :func:`_tp_rings_engage`, and no dropout stream, which the
    step shares across tp) and the model can take it
    (:func:`_sharded_stream_cfg`), the stream is sharded over the
    sequence: a row-parallel exit is then its reduce-scatter ring alone
    and the next entry's all-gather ring carries what the exit's
    all-gather did, under that projection's matmuls. A model that sets
    ``sequence_parallel`` itself keeps its setting.

    **What its rematerialised layers keep**
    (``utils/remat.choose_remat_policy``): a model that
    checkpoints its layers and names no policy keeps gate's and up's
    products when a chip has the bytes. Priced from ``rows`` (the batch
    and sequence one chip's layers see a pass), the model's own widths,
    the state a chip holds under ``state_shardings``, its gradients (twice
    where the step sums microbatches' into an accumulator), compute-dtype
    copies and logits, and the limit of a device this process holds; a
    policy the model names stays. The pair's bytes do not depend on the
    stream's layout (the products span the whole sequence and a rank's
    share of the width); the layer boundary that "full" keeps is inside
    the rule's second count of the pair and is a quarter under the sharded
    stream, so the sum errs higher there. A model that checkpoints nothing
    or whose layers the builder cannot price (a family's own:
    ``LlamaConfig.plain_layers``), and any on a mesh with pipeline,
    context or expert parallelism, keeps its policy."""
    cfg = getattr(pm.module, "cfg", None)
    tp = dict(mesh.shape).get(ps.TP_AXIS, 1)
    sharded = _sharded_stream_cfg(cfg) if rings else None
    if sharded is not None:
        cfg = sharded
    _record_stream_layout(
        tp if getattr(cfg, "sequence_parallel", False) else 1)
    if (_tensor_by_data(mesh) and getattr(cfg, "remat", False)
            and hasattr(cfg, "plain_layers") and cfg.plain_layers()):
        kept = cfg.glu_products_bytes(math.prod(rows), tp)
        params = state.params, state_shardings.params
        step_bytes = (
            _bytes_a_chip(state, state_shardings)            # the state
            + _bytes_a_chip(*params) * (1 + accumulating)    # gradients
            + _bytes_a_chip(*params, cast=cfg.dtype)         # casts
            + cfg.logits_bytes(*rows, tp))
        policy = remat.choose_remat_policy(
            cfg.remat_policy, kept_bytes=kept, step_bytes=step_bytes,
            limit_bytes=memory_limit_bytes(mesh.devices.flat))
        _record_remat_choice(policy, kept)
        cfg = dataclasses.replace(cfg, remat_policy=policy)
    if cfg is getattr(pm.module, "cfg", None):
        return pm.module
    return pm.module.clone(cfg=cfg)


def make_train_step(
    pm: ParallelModel,
    tx: optax.GradientTransformation,
    state_shardings: TrainState,
    loss_fn: Optional[Callable] = None,
    grad_fn: Optional[Callable] = None,
    batch_spec: PartitionSpec = PartitionSpec(ps.DP_AXIS),
    donate: bool = True,
    grad_accum_steps: int = 1,
    scan_steps: int = 1,
    dropout_rng: Optional[jax.Array] = None,
    skip_nonfinite: bool = False,
    compression: Optional[cc.CompressionConfig] = None,
    integrity_every: Optional[int] = None,
):
    """Build the jitted SPMD train step.

    Either ``loss_fn(module, params, batch) -> scalar`` (differentiated here
    under GSPMD; default calls ``module.apply(..., method="loss")``, under
    GSPMD or the explicit path below) or
    ``grad_fn(params, batch) -> (loss, grads)`` for paths that must compute
    gradients themselves (e.g. the shard_map pipeline engine, whose gradients
    may not cross the shard_map boundary as cotangents — see
    ``parallel/grads.py``).

    ``grad_accum_steps``: split the batch's leading dim into that many
    microbatches, accumulating grads in a ``lax.scan`` before the single
    optimizer update (the reference trainer's gradient_accumulation_steps;
    activations live for one microbatch at a time). Composes with either
    loss_fn or grad_fn. Note: the result is the *mean over microbatch
    means* — identical to the full-batch step when microbatches carry equal
    valid-token counts (the reference accumulates the same way).

    ``dropout_rng``: base PRNG key enabling dropout (attention/hidden/LoRA —
    any module gated on the "dropout" rng). Folded with ``state.step`` each
    step so masks differ per step while the compiled program stays one
    program. Only the default loss_fn threads it; custom loss_fn/grad_fn
    callers manage their own rngs.

    ``skip_nonfinite``: guard the update ON DEVICE — when loss or global
    grad-norm is non-finite, params and optimizer state pass through
    unchanged (the step counter still advances) and the skip is reported in
    ``metrics["nonfinite_skipped"]``. This is the donation-compatible
    counterpart of the resilience ``Watchdog(policy="skip_step")`` host
    rollback: no extra state copy, no host sync, works with ``donate=True``
    and inside ``scan_steps``.

    ``integrity_every``: compute an on-device integrity fingerprint of the
    *updated* params inside the compiled step, every K steps
    (``resilience.integrity.fingerprint_tree`` — an int32 bit-fold, not a
    host hash). Reported as ``metrics["integrity_fp"]`` (fixed-shape
    ``int32[n_leaves]``, zeros off-cadence) so the metrics stay one
    structure and the program count stays one: the cadence gate is a
    ``lax.cond`` on the step counter, like the ``skip_nonfinite`` select.
    ``resilience.IntegrityMonitor`` consumes it at cadence boundaries to
    detect silent data corruption between device write and next read; with
    ``scan_steps > 1`` only the scan's last step's metric surfaces, so
    keep ``integrity_every`` a multiple of ``scan_steps`` (or 1) for a
    usable cadence.

    The default loss has an *explicit* path too: loss and grads computed
    inside ``shard_map`` over the mesh, gradients averaged over the data
    axes by hand. The step takes it when binding the tp axis lets the
    layers' decomposed collective-matmuls engage (``_tp_rings_engage``: the
    axis has ``cm.MIN_AUTO_AXIS_SIZE`` ranks or ``tp_overlap_comm=True``,
    the batch's sequence tiles over it, the mesh is tensor x data), decided
    from the batch's shape at trace time; otherwise, and always with a
    custom ``loss_fn``/``grad_fn``, loss and grads come from GSPMD. Like
    ``grad_accum_steps``, the explicit path's loss over data-parallel ranks
    is the mean of their means: the global mean when ranks carry equal
    valid-token counts. Where the rings engage the step also shards the
    residual stream over the sequence (``_module_for_step``: the model's
    ``sequence_parallel`` on the clone it differentiates, unless a
    ``dropout_rng`` is threaded or the model cannot take it), so that a
    row-parallel exit's all-gather rides inside the next projection's ring.

    ``compression``: a ``parallel.CompressionConfig`` (typically
    ``comm_compressed.from_config(pm.config)``) switching gradient
    synchronisation to the quantized / hierarchical collectives. It always
    takes the explicit path, with the compressed all-reduce on the data
    axes (GSPMD cannot be told to quantize its implicit reductions), so it
    composes only with the default loss (``loss_fn=None, grad_fn=None``); pipeline
    ``grad_fn``s own their collectives and stay uncompressed. With a
    quantized dtype + error feedback, the state must carry ``comm_error``
    buffers (``initialize_parallel_optimizer`` allocates them when the
    config asks for compression).
    """
    mesh = ps.get_mesh()

    if loss_fn is not None and grad_fn is not None:
        raise ValueError(
            "pass either loss_fn (differentiated here) or grad_fn "
            "(self-differentiating, e.g. the pipeline engine), not both")
    if compression is not None and (loss_fn is not None
                                    or grad_fn is not None):
        raise ValueError(
            "compression= builds its own shard_map gradient path and only "
            "composes with the default loss; custom loss_fn/grad_fn "
            "callers should call parallel.grads.allreduce_gradients("
            "compression=...) themselves")
    if dropout_rng is not None and (loss_fn is not None
                                    or grad_fn is not None):
        raise ValueError(
            "dropout_rng is only threaded through the default loss_fn; "
            "custom loss_fn/grad_fn callers must manage their own rngs "
            "(fold state.step in and pass rngs= to apply)")
    if grad_accum_steps < 1:
        raise ValueError(f"grad_accum_steps must be >= 1, got "
                         f"{grad_accum_steps}")
    if scan_steps < 1:
        raise ValueError(f"scan_steps must be >= 1, got {scan_steps}")
    if integrity_every is not None and integrity_every < 1:
        raise ValueError(f"integrity_every must be >= 1, got "
                         f"{integrity_every}")
    if loss_fn is None and grad_fn is None:
        def loss_fn(module, params, batch, rngs=None):
            input_ids, labels = batch["input_ids"], batch["labels"]
            if rngs is not None:
                return module.apply(params, input_ids, labels,
                                    method="loss", rngs=rngs)
            return module.apply(params, input_ids, labels, method="loss")
        default_loss = True
    else:
        default_loss = False

    # One explicit path: loss and gradients inside ``shard_map`` over the
    # mesh, every axis bound. ``compression=`` always takes it (GSPMD cannot
    # be told to quantize its implicit reductions); the default step takes
    # it when binding the tp axis lets the projections' decomposed rings
    # engage (``_tp_rings_engage``, decided per batch shape at trace time),
    # with a plain mean over the data axes.
    explicit_grad = None
    if default_loss:
        use_ef = (compression is not None and compression.quantized
                  and compression.error_feedback)
        with_rng = dropout_rng is not None
        red_axes = tuple(ax for ax in (ps.DP_AXIS, ps.CP_AXIS)
                         if dict(mesh.shape).get(ax, 1) > 1)
        ef_specs = (cc.error_feedback_specs(pm.param_specs, red_axes)
                    if use_ef and red_axes else None)
        use_ef = use_ef and ef_specs is not None

        def inner(module, *args):
            p, input_ids, labels = args[:3]
            idx = 3
            rngs_in = None
            if with_rng:
                # distinct dropout streams per data-parallel rank, shared
                # across tp (the parallel.random contract)
                rngs_in = {"dropout": jax.random.fold_in(
                    args[idx], comm.combined_axis_index(red_axes)
                    if red_axes else 0)}
                idx += 1
            err = None
            if use_ef:
                # EF buffers carry a leading reduce-rank dim outside the
                # shard_map (so each rank's residue is real, addressable,
                # checkpointable state); locally that dim is 1 — peel it
                err = jax.tree_util.tree_map(
                    lambda t: jnp.squeeze(t, 0), args[idx])

            def local_loss(pp):
                if rngs_in is not None:
                    return module.apply(pp, input_ids, labels,
                                        method="loss", rngs=rngs_in)
                return module.apply(pp, input_ids, labels, method="loss")

            loss, g = jax.value_and_grad(local_loss)(p)
            if use_ef:
                g, ne = grads_mod.allreduce_gradients(
                    g, specs=pm.param_specs, axes=red_axes,
                    compression=compression, error=err)
                ne = jax.tree_util.tree_map(lambda t: t[None], ne)
            else:
                g = grads_mod.allreduce_gradients(
                    g, specs=pm.param_specs, axes=red_axes,
                    compression=compression)
            for ax in red_axes:
                loss = jax.lax.pmean(loss, ax)
            return (loss, g, ne) if use_ef else (loss, g)

        in_specs = [pm.param_specs, batch_spec, batch_spec]
        if with_rng:
            in_specs.append(PartitionSpec())
        if use_ef:
            in_specs.append(ef_specs)
        out_specs = (PartitionSpec(), pm.param_specs)
        if use_ef:
            out_specs = out_specs + (ef_specs,)

        def explicit_grad(module, params, batch, rngs, err):
            args = [params, batch["input_ids"], batch["labels"]]
            if with_rng:
                args.append(rngs["dropout"])
            if use_ef:
                args.append(err)
            outs = ps.shard_map(
                functools.partial(inner, module), mesh,
                in_specs=tuple(in_specs), out_specs=out_specs)(*args)
            if use_ef:
                return outs
            return outs[0], outs[1], err

    batch_shardings = NamedSharding(mesh, batch_spec)

    def bound_module(state, batch):
        """The module the explicit path differentiates where this step
        takes it (the shapes decide, at trace time), else None. There the
        axes are bound and a chip's rows are known: what the layers keep
        follows the chip's bytes, and where the rings engage the residual
        stream is sharded over the sequence (a dropout stream is shared
        across tp, so a step that threads one keeps the stream whole;
        ``compression=`` alone engages no ring and changes no layout)."""
        if explicit_grad is None:
            return None
        rings = _tp_rings_engage(pm, mesh, batch)
        if compression is None and not rings:
            return None
        batch_rows, seq = batch_shardings.shard_shape(
            jnp.shape(batch["input_ids"]))
        return _module_for_step(
            pm, mesh, state, state_shardings,
            (batch_rows // grad_accum_steps, seq), grad_accum_steps > 1,
            rings=rings and dropout_rng is None)

    def one_grad(params, batch, rngs=None, err=None, bound=None):
        """→ ``(loss, grads, new_err)``; ``err`` passes through untouched
        on the uncompressed paths (None stays None). ``bound``: the module
        of the explicit path where the step takes it."""
        if bound is not None:
            return explicit_grad(bound, params, batch, rngs, err)
        if grad_fn is not None:
            loss, g = grad_fn(params, batch)
            return loss, g, err
        if default_loss:
            loss, g = jax.value_and_grad(
                lambda p: loss_fn(pm.module, p, batch, rngs))(params)
            return loss, g, err
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(pm.module, p, batch))(params)
        return loss, g, err

    def accum_grad(params, batch, rngs=None, err=None, bound=None):
        a = grad_accum_steps

        def slice_mb(x):
            if x.shape[0] % a != 0:
                raise ValueError(
                    f"batch dim {x.shape[0]} not divisible by "
                    f"grad_accum_steps {a}")
            return x.reshape(a, x.shape[0] // a, *x.shape[1:])

        mbs = jax.tree_util.tree_map(slice_mb, batch)
        # keep every microbatch spread over the full dp axis — without the
        # constraint GSPMD may localize the new leading dim and serialize
        # data parallelism inside the scan
        mb_sharding = NamedSharding(mesh, PartitionSpec(None, *batch_spec))
        mbs = jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, mb_sharding), mbs)

        def body(carry, xs):
            loss_sum, gacc, e = carry
            mb, i = xs
            mb_rngs = (None if rngs is None else
                       {k: jax.random.fold_in(r, i)
                        for k, r in rngs.items()})
            # with compression each microbatch reduce consumes/produces
            # the error-feedback residue through the scan carry
            loss, g, e = one_grad(params, mb, mb_rngs, e, bound)
            gacc = jax.tree_util.tree_map(jnp.add, gacc, g)
            return (loss_sum + loss, gacc, e), None

        zero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(jnp.shape(p), jnp.result_type(p)), params)
        (loss_sum, gsum, err), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zero, err),
            (mbs, jnp.arange(a)))
        scale = 1.0 / a
        return loss_sum * scale, jax.tree_util.tree_map(
            lambda g: g * scale, gsum), err

    def step_fn(state: TrainState, batch) -> Tuple[TrainState, dict]:
        rngs = (None if dropout_rng is None else
                {"dropout": jax.random.fold_in(dropout_rng, state.step)})
        grad = accum_grad if grad_accum_steps > 1 else one_grad
        loss, grads, new_err = grad(state.params, batch, rngs,
                                    state.comm_error,
                                    bound_module(state, batch))
        with device_scope("optimizer"):
            grad_norm = optax.global_norm(grads)
            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            new_params = optax.apply_updates(state.params, updates)
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
        }
        if compression is not None:
            metrics["grad_comm_ratio"] = jnp.asarray(compression.ratio,
                                                     jnp.float32)
        if skip_nonfinite:
            # select, don't branch: one compiled program either way, and
            # the guard composes with donation and scan_steps
            ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
            keep = lambda new, old: jnp.where(ok, new, old)  # noqa: E731
            new_params = jax.tree_util.tree_map(keep, new_params,
                                                state.params)
            new_opt = jax.tree_util.tree_map(keep, new_opt, state.opt_state)
            # a skipped step must also discard the residue the bad reduce
            # wrote, or one NaN grad poisons every later step through EF
            new_err = jax.tree_util.tree_map(keep, new_err,
                                             state.comm_error)
            metrics["nonfinite_skipped"] = (~ok).astype(jnp.int32)
        if integrity_every is not None:
            # lazy import: resilience pulls in chaos/storage machinery the
            # hot path doesn't need unless integrity is on
            from ..resilience.integrity import fingerprint_tree

            n_leaves = len(jax.tree_util.tree_leaves(new_params))
            # cond, not select: off-cadence steps must not pay the
            # fingerprint fold; both branches live in the ONE compiled
            # program (compile_count unchanged), like skip_nonfinite
            metrics["integrity_fp"] = jax.lax.cond(
                (state.step + 1) % integrity_every == 0,
                lambda p: fingerprint_tree(p),
                lambda p: jnp.zeros((n_leaves,), jnp.int32),
                new_params)
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=new_opt, comm_error=new_err), metrics

    if scan_steps > 1:
        # run `scan_steps` optimizer steps in ONE dispatch: batch leaves gain
        # a leading scan dim. Keeps host round-trips and dispatch latency
        # out of the training loop — the XLA program is the same per-step
        # program, iterated on device.
        def multi_step_fn(state: TrainState, batches):
            def body(s, mb):
                s2, metrics = step_fn(s, mb)
                return s2, metrics
            state, ms = jax.lax.scan(body, state, batches)
            last = jax.tree_util.tree_map(lambda x: x[-1], ms)
            return state, last

        multi_batch_shardings = NamedSharding(
            mesh, PartitionSpec(None, *batch_spec))
        return jax.jit(
            multi_step_fn,
            in_shardings=(state_shardings, multi_batch_shardings),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,) if donate else (),
        )
    return jax.jit(
        step_fn,
        in_shardings=(state_shardings, batch_shardings),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate else (),
    )


# -- nxdlint jaxpr-audit entry point ---------------------------------------

from ..analysis.audit_registry import BuiltEntry, register_entry_point


@register_entry_point(
    "train-step",
    description="tiny-Llama SPMD train step (donating jit), same "
                "construction path as the e2e training tests",
    tags=("train",),
    expects_donation=True,
    donation_min_bytes=1 << 14,
)
def _audit_train_step() -> BuiltEntry:
    """Builder for ``analysis --jaxpr``: the smallest real train step,
    sized for the virtual CPU mesh. The returned step is only
    abstract-traced by the auditor, never executed."""
    from ..config import neuronx_distributed_config
    from ..models.llama import LlamaForCausalLM, tiny_config

    if ps.model_parallel_is_initialized():
        ps.destroy_model_parallel()
    cfg = neuronx_distributed_config(tensor_parallel_size=1)
    mcfg = tiny_config(dtype=jnp.float32, param_dtype=jnp.float32)
    model = LlamaForCausalLM(mcfg)
    ids = jnp.zeros((8, 16), jnp.int32)
    pm, params = initialize_parallel_model(
        cfg, model, jax.random.key(0), ids)
    tx, state, state_shardings = initialize_parallel_optimizer(pm, params)
    step = make_train_step(pm, tx, state_shardings)
    batch = {"input_ids": ids, "labels": ids}
    return BuiltEntry(fn=step, args=(state, batch), donate_argnums=(0,))
