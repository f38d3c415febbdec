"""Device mesh construction and parallel state.

TPU-native analogue of the reference's ``parallel_layers/parallel_state.py``.
Where the reference builds ``torch.distributed`` process groups plus raw SPMD
replica-group lists from a rank tensor reshaped ``[PP, DP, CP, TP]``
(``parallel_state.py:620-636``), we build a single ``jax.sharding.Mesh`` with
axes ``("pp", "dp", "cp", "tp")`` — XLA's GSPMD partitioner and ``shard_map``
collectives replace explicit process groups entirely (one SPMD program, not
one process per rank).

The expert-parallel view (``[PP, DP_exp, EP, TP]``, ``parallel_state.py:629``)
is a *reshape of the same device array*: the ``dp`` and ``cp`` axes merge and
re-split into ``(dp_exp, ep)``, keeping TP groups identical across both views.

Topology-aware device ordering (the reference's ``ascending_ring_PG_group`` /
``ascending_descending_ring_PG_group`` layouts, ``parallel_state.py:107,177``)
maps to ``mesh_utils.create_device_mesh``-style placement: the innermost mesh
axis (``tp``) is laid out along the fastest ICI rings of the TPU torus.

Rank getters come in two flavours:

* mesh-level (host side): sizes, replica-group lists (for tests / parity with
  the reference's ``get_*_replica_groups``);
* in-graph (inside ``shard_map``): ``get_*_rank()`` returns a traced
  ``lax.axis_index`` — the SPMD analogue of the per-process rank.
"""

from __future__ import annotations

import logging
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

logger = logging.getLogger(__name__)

# Canonical axis names. Order is [pp, dp, cp, tp] — tp innermost so tensor
# parallel collectives ride nearest-neighbour ICI links (reference orders the
# rank tensor the same way for NeuronLink rings, parallel_state.py:620-636).
PP_AXIS = "pp"
DP_AXIS = "dp"
CP_AXIS = "cp"
TP_AXIS = "tp"
# Expert view axes (reference: [PP, DP_exp, EP, TP], parallel_state.py:629).
EP_AXIS = "ep"
EXP_DP_AXIS = "dp_exp"

MESH_AXES = (PP_AXIS, DP_AXIS, CP_AXIS, TP_AXIS)
EXPERT_MESH_AXES = (PP_AXIS, EXP_DP_AXIS, EP_AXIS, TP_AXIS)


class _ParallelState:
    """Singleton holding the constructed meshes (cf. the module-level group
    globals in the reference's parallel_state)."""

    def __init__(self) -> None:
        self.mesh: Optional[Mesh] = None
        self.expert_mesh: Optional[Mesh] = None
        self.device_array: Optional[np.ndarray] = None  # [pp, dp, cp, tp]
        self.sizes: dict = {}
        self.aot_mode: bool = False
        self.phase_meshes: dict = {}  # (tp, ep) -> Mesh view
        # (fast_axes, slow_axes) link-speed split for hierarchical
        # collectives; None = undeclared (MESH_AXES-order convention).
        self.axis_hierarchy: Optional[Tuple[Tuple[str, ...],
                                            Tuple[str, ...]]] = None


_STATE = _ParallelState()


def _topology_device_order(devices: Sequence[Any], shape: Tuple[int, ...]) -> np.ndarray:
    """Arrange devices into ``shape`` with ICI-topology awareness.

    On real TPU slices delegates to ``mesh_utils.create_device_mesh`` (which
    plays the role of the reference's LOGIC1/LOGIC2 ring layouts,
    ``parallel_state.py:107,177,341``); a shape the topology solver rejects
    is an error there, not an id-ordered guess. CPU/virtual devices (tests)
    have no topology and take the id-sorted reshape.
    """
    devs = sorted(devices, key=lambda d: d.id)
    if int(np.prod(shape)) != len(devs):
        raise ValueError(
            f"mesh shape {shape} does not match device count {len(devs)}")
    plat = getattr(devs[0], "platform", "cpu")
    if plat == "tpu" and len(devs) > 1:
        from jax.experimental import mesh_utils

        return np.asarray(mesh_utils.create_device_mesh(shape, devices=devs))
    return np.asarray(devs, dtype=object).reshape(shape)


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host bootstrap: one JAX process per host.

    The analogue of the reference's torchrun/MPI world initialisation
    (SURVEY §5 "comm backend"): after this, ``jax.devices()`` spans every
    host and XLA collectives ride ICI within a slice and DCN across
    slices. Arguments default to the TPU metadata / environment discovery
    built into ``jax.distributed.initialize`` (``JAX_COORDINATOR_ADDRESS``
    etc.); pass them explicitly on non-TPU clusters.
    """
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def _hybrid_device_order(devices: Sequence[Any], shape: Tuple[int, ...],
                         dcn_dp: int) -> np.ndarray:
    """Multi-slice layout: the dp axis factors as (dcn outer, ici inner) so
    only data-parallel collectives cross DCN."""
    pp, dp, cp, tp = shape
    if dp % dcn_dp != 0:
        raise ValueError(
            f"dp {dp} not divisible by dcn_data_parallel_size {dcn_dp}")
    devs = sorted(devices, key=lambda d: (getattr(d, "process_index", 0),
                                          d.id))
    plat = getattr(devs[0], "platform", "cpu")
    if plat == "tpu":
        from jax.experimental import mesh_utils

        return np.asarray(mesh_utils.create_hybrid_device_mesh(
            (pp, dp // dcn_dp, cp, tp), (1, dcn_dp, 1, 1), devices=devs))
    # virtual/CPU devices: contiguous per-slice blocks stacked on dp
    per = len(devs) // dcn_dp
    blocks = [np.asarray(devs[i * per:(i + 1) * per], dtype=object)
              .reshape(pp, dp // dcn_dp, cp, tp) for i in range(dcn_dp)]
    return np.concatenate(blocks, axis=1)


def initialize_model_parallel(
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    context_parallel_size: int = 1,
    expert_model_parallel_size: int = 1,
    devices: Optional[Sequence[Any]] = None,
    data_parallel_size: Optional[int] = None,
    dcn_data_parallel_size: Optional[int] = None,
) -> Mesh:
    """Build the global meshes.

    Analogue of the reference's ``initialize_model_parallel``
    (``parallel_state.py:391``). Degree validation and the ``[PP, DP, CP, TP]``
    factorisation follow ``parallel_state.py:560-636``. There is no collective
    warm-up (``:647-657``) — XLA initialises collectives at first compile.

    ``dcn_data_parallel_size``: multi-slice/multi-host layouts — that many
    data-parallel groups are placed *across* slices (DCN), everything else
    stays within a slice (ICI). The standard TPU recipe: only DP gradients
    cross the slow links (the reference's multi-node analogue is its
    EFA/NCCL DP process groups over torchrun nodes).
    """
    if devices is None:
        devices = jax.devices()
    world = len(devices)
    # shared divisibility rules — the placement planner prunes layouts by
    # the same function, so a plan it emits always initializes here
    from ..config import mesh_factorization

    sizes = mesh_factorization(
        world,
        tensor_parallel_size=tensor_model_parallel_size,
        pipeline_parallel_size=pipeline_model_parallel_size,
        context_parallel_size=context_parallel_size,
        expert_parallel_size=expert_model_parallel_size,
        data_parallel_size=data_parallel_size,
        dcn_data_parallel_size=dcn_data_parallel_size)
    tp, pp, cp, ep = sizes["tp"], sizes["pp"], sizes["cp"], sizes["ep"]
    dp, dp_exp = sizes["dp"], sizes["dp_exp"]

    if dcn_data_parallel_size and dcn_data_parallel_size > 1:
        arr = _hybrid_device_order(devices, (pp, dp, cp, tp),
                                   dcn_data_parallel_size)
    else:
        arr = _topology_device_order(devices, (pp, dp, cp, tp))
    _STATE.device_array = arr
    _STATE.mesh = Mesh(arr, MESH_AXES)
    _STATE.expert_mesh = Mesh(arr.reshape(pp, dp_exp, ep, tp), EXPERT_MESH_AXES)
    _STATE.sizes = dict(pp=pp, dp=dp, cp=cp, tp=tp, ep=ep, dp_exp=dp_exp,
                        world=world)
    if dcn_data_parallel_size and dcn_data_parallel_size > 1:
        # dp crosses DCN in the hybrid layout: every other data axis rides
        # ICI, so hierarchical gradient collectives should stage through
        # them first (comm_compressed.split_axis_hierarchy consumes this).
        fast = tuple(a for a, s in ((CP_AXIS, cp),) if s > 1)
        _STATE.axis_hierarchy = (fast, (DP_AXIS,))
    logger.info("initialized mesh: pp=%d dp=%d cp=%d tp=%d (ep=%d dp_exp=%d)",
                pp, dp, cp, tp, ep, dp_exp)
    return _STATE.mesh


def model_parallel_is_initialized() -> bool:
    """Reference: ``parallel_state.py`` ``model_parallel_is_initialized``."""
    return _STATE.mesh is not None


def destroy_model_parallel() -> None:
    """Reference: ``parallel_state.py:1226``."""
    _STATE.mesh = None
    _STATE.expert_mesh = None
    _STATE.device_array = None
    _STATE.sizes = {}
    _STATE.aot_mode = False
    _STATE.phase_meshes = {}
    _STATE.axis_hierarchy = None


def _require_init() -> None:
    if _STATE.mesh is None:
        raise RuntimeError(
            "model parallel mesh not initialized; call "
            "initialize_model_parallel() first")


def get_mesh() -> Mesh:
    _require_init()
    return _STATE.mesh  # type: ignore[return-value]


def get_expert_mesh() -> Mesh:
    _require_init()
    return _STATE.expert_mesh  # type: ignore[return-value]


def declare_axis_hierarchy(fast: Sequence[str],
                           slow: Sequence[str]) -> None:
    """Declare which mesh axes ride fast links (ICI) vs slow links (DCN).

    Hierarchical collectives (``parallel.comm_compressed``) stage through
    the fast axes first, so only 1/N_fast of the payload crosses the slow
    axes. ``initialize_model_parallel(dcn_data_parallel_size=...)``
    auto-declares ``dp`` slow; call this to override or for custom
    topologies. Axes must be mesh axis names and the two sets disjoint.
    """
    _require_init()
    fast = tuple(fast)
    slow = tuple(slow)
    valid = set(MESH_AXES) | set(EXPERT_MESH_AXES)
    unknown = [a for a in fast + slow if a not in valid]
    if unknown:
        raise ValueError(f"unknown mesh axes in hierarchy: {unknown}; "
                         f"valid axes: {sorted(valid)}")
    overlap = set(fast) & set(slow)
    if overlap:
        raise ValueError(f"axes cannot be both fast and slow: "
                         f"{sorted(overlap)}")
    _STATE.axis_hierarchy = (fast, slow)


def get_axis_hierarchy() -> Optional[Tuple[Tuple[str, ...],
                                           Tuple[str, ...]]]:
    """The declared ``(fast_axes, slow_axes)`` split, or None when
    undeclared (consumers fall back to mesh-axis-order conventions)."""
    return _STATE.axis_hierarchy


def get_moe_phase_mesh(tensor_parallel_size: int,
                       expert_parallel_size: int) -> Mesh:
    """Per-phase (prefill vs decode) TP x EP mesh view.

    Analogue of the reference's prefill/token-gen MoE process groups
    (``moe_process_group.py:12`` — separate CTE and TKG tp x ep groups over
    the same cores): a RESHAPED VIEW of the already-initialised device
    array with axes ``("dp", "ep", "tp")``, cached per (tp, ep). No
    re-initialisation and no manual mesh juggling between phases — serve
    context encoding under ``get_moe_phase_mesh(cte_tp, cte_ep)`` and token
    generation under ``get_moe_phase_mesh(tkg_tp, tkg_ep)`` in the same
    process. Axis names match the global mesh so the parallel layers work
    unchanged inside ``shard_map`` over the view.
    """
    _require_init()
    key = (int(tensor_parallel_size), int(expert_parallel_size))
    if key not in _STATE.phase_meshes:
        tp, ep = key
        world = int(_STATE.sizes["world"])
        if tp < 1 or ep < 1 or world % (tp * ep) != 0:
            raise ValueError(
                f"world size {world} not divisible by phase tp*ep = "
                f"{tp}*{ep}")
        flat = _STATE.device_array.reshape(-1)
        _STATE.phase_meshes[key] = Mesh(
            flat.reshape(world // (tp * ep), ep, tp),
            (DP_AXIS, EP_AXIS, TP_AXIS))
    return _STATE.phase_meshes[key]


def set_aot_mode(flag: bool) -> None:
    """Reference: ``parallel_state.py:1593-1602`` (AOT trace mode for
    inference builds on abstract meshes)."""
    _STATE.aot_mode = flag


def get_aot_mode() -> bool:
    return _STATE.aot_mode


# --------------------------------------------------------------------------
# Size getters (host-side; reference getters at parallel_state.py:826-1684)
# --------------------------------------------------------------------------

def _size(name: str) -> int:
    _require_init()
    return int(_STATE.sizes[name])


def get_tensor_model_parallel_size() -> int:
    return _size("tp")


def get_pipeline_model_parallel_size() -> int:
    return _size("pp")


def get_data_parallel_size() -> int:
    return _size("dp")


def get_context_parallel_size() -> int:
    return _size("cp")


def get_expert_model_parallel_size() -> int:
    return _size("ep")


def get_expert_data_parallel_size() -> int:
    return _size("dp_exp")


def get_world_size() -> int:
    return _size("world")


# --------------------------------------------------------------------------
# In-graph rank getters (traced; only valid under shard_map over the mesh)
# --------------------------------------------------------------------------

# Imported once at module load so JAX private-API drift fails LOUDLY here
# (a silent "axis unbound" fallback would skip every collective and produce
# garbage numerics instead of an error).
try:
    from jax._src.core import get_axis_env as _get_axis_env
    _get_axis_env().axis_exists("_nxd_probe_")
except (ImportError, AttributeError) as _e:  # pragma: no cover
    raise ImportError(
        "neuronx_distributed_tpu requires jax._src.core.get_axis_env with "
        "an axis_exists method (present in jax 0.9.x). This JAX version "
        f"changed the private axis-env API: {_e}") from _e


def _axis_bound(name: str) -> bool:
    return bool(_get_axis_env().axis_exists(name))


def axis_bound(name: str) -> bool:
    """True when ``name`` is a bound (shard_map-mapped) axis in the current
    trace. Used by the collective mappings layer to pick the explicit
    (collective) vs GSPMD (annotation) path."""
    return _axis_bound(name)


def _rank(axis: str):
    if not _axis_bound(axis):
        raise RuntimeError(
            f"get rank of axis {axis!r} requires a shard_map context binding "
            "that axis (SPMD programs have no ambient rank)")
    return jax.lax.axis_index(axis)


def get_tensor_model_parallel_rank():
    return _rank(TP_AXIS)


def get_pipeline_model_parallel_rank():
    return _rank(PP_AXIS)


def get_data_parallel_rank():
    return _rank(DP_AXIS)


def get_context_parallel_rank():
    return _rank(CP_AXIS)


def get_expert_model_parallel_rank():
    return _rank(EP_AXIS)


# --------------------------------------------------------------------------
# Replica groups (host-side; for tests and parity with the reference's
# ``get_*_replica_groups``, parallel_state.py:785-823)
# --------------------------------------------------------------------------

def _device_ids() -> np.ndarray:
    _require_init()
    ids = np.vectorize(lambda d: d.id)(_STATE.device_array)
    return ids  # [pp, dp, cp, tp]


def _groups_over(ids: np.ndarray, axis: int) -> List[List[int]]:
    moved = np.moveaxis(ids, axis, -1)
    return [list(map(int, row)) for row in moved.reshape(-1, moved.shape[-1])]


def get_tensor_model_parallel_replica_groups() -> List[List[int]]:
    return _groups_over(_device_ids(), 3)


def get_data_parallel_replica_groups() -> List[List[int]]:
    return _groups_over(_device_ids(), 1)


def get_pipeline_model_parallel_replica_groups() -> List[List[int]]:
    return _groups_over(_device_ids(), 0)


def get_context_parallel_replica_groups() -> List[List[int]]:
    return _groups_over(_device_ids(), 2)


def get_expert_model_parallel_replica_groups() -> List[List[int]]:
    ids = _device_ids()
    pp, dp, cp, tp = ids.shape
    ep = _size("ep")
    dp_exp = _size("dp_exp")
    resh = ids.reshape(pp, dp_exp, ep, tp)
    return _groups_over(resh, 2)


def get_expert_data_parallel_replica_groups() -> List[List[int]]:
    ids = _device_ids()
    pp, dp, cp, tp = ids.shape
    ep = _size("ep")
    dp_exp = _size("dp_exp")
    resh = ids.reshape(pp, dp_exp, ep, tp)
    return _groups_over(resh, 1)


def get_zero1_sharding_replica_groups() -> List[List[int]]:
    """ZeRO-1 shards optimizer state over merged DP×CP (reference:
    ``parallel_state.py:1684``)."""
    ids = _device_ids()
    pp, dp, cp, tp = ids.shape
    merged = ids.reshape(pp, dp * cp, tp)
    return _groups_over(merged, 1)


def get_context_parallel_ring_pairs() -> List[Tuple[int, int]]:
    """Ring edges (src, tgt) over the cp axis for ring attention, expressed
    as cp-axis indices for ``jax.lax.ppermute`` (reference precomputes device
    src/tgt pairs from CollectivesConfig, ``parallel_state.py:737-742``)."""
    cp = get_context_parallel_size()
    return [(i, (i + 1) % cp) for i in range(cp)]


# --------------------------------------------------------------------------
# Sharding helpers
# --------------------------------------------------------------------------

def named_sharding(*spec: Any) -> NamedSharding:
    """NamedSharding over the global mesh from a PartitionSpec-like tuple."""
    return NamedSharding(get_mesh(), PartitionSpec(*spec))


_EXPERT_ONLY_AXES = frozenset((EP_AXIS, EXP_DP_AXIS))


def spec_uses_expert_axes(spec: PartitionSpec) -> bool:
    """True when a PartitionSpec names an expert-view axis (``ep`` /
    ``dp_exp``) — such specs must be placed on the expert mesh view."""
    for p in spec:
        if p is None:
            continue
        names = p if isinstance(p, tuple) else (p,)
        if any(n in _EXPERT_ONLY_AXES for n in names):
            return True
    return False


def named_sharding_for_spec(spec: PartitionSpec) -> NamedSharding:
    """NamedSharding on the mesh view matching the spec's axis names.

    Expert-view specs (naming ``ep``/``dp_exp``) land on the expert mesh,
    everything else on the dense mesh. Both views are reshapes of the SAME
    flat device order, so their NamedShardings are mutually compatible
    inside one ``jit`` — the TPU analogue of the reference holding dense and
    expert process groups side by side (``parallel_state.py:629``).
    """
    mesh = get_expert_mesh() if spec_uses_expert_axes(spec) else get_mesh()
    return NamedSharding(mesh, spec)


def with_sharding_constraint(x, *spec: Any):
    """``lax.with_sharding_constraint`` against the global mesh; no-op when
    the mesh is uninitialised (single-device eager use)."""
    if _STATE.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, named_sharding(*spec))


def shard_map(f, mesh: Optional[Mesh] = None, *, in_specs, out_specs,
              check_vma: bool = False, **kw):
    """``jax.shard_map`` over the global mesh.

    ``check_vma`` defaults to False: TP-style programs routinely all-gather a
    sharded value and treat the result as replicated (e.g. the output of
    ``gather_from_tensor_parallel_region``), which JAX's static
    varying-manual-axes analysis cannot prove replicated.
    """
    if mesh is None:
        mesh = get_mesh()
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)
