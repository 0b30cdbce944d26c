"""Global device-mesh bookkeeping — TPU-native `parallel_state`.

The reference (apex/transformer/parallel_state.py:36-419) maintains a
registry of torch.distributed process groups for data/tensor/pipeline/
virtual-pipeline/model/embedding parallelism.  On TPU there are no
process-group objects: parallel dimensions are *named axes of one
`jax.sharding.Mesh`*, collectives are emitted by the compiler against
those axis names, and "groups" become sub-axes.  This module is the
single place that builds and queries that mesh.

Axis layout follows Megatron rank ordering (tensor-parallel innermost so
TP collectives ride the fastest ICI links, then data-parallel, pipeline
outermost):  mesh shape = (pp, dp, tp) over `jax.devices()` in row-major
order — the same rank→group mapping as parallel_state.py:266-346.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis names.  (dp, pp, tp) mirrors the reference's
# data-/pipeline-/tensor-parallel groups; "sp" is not a separate axis —
# Megatron sequence parallelism shards the sequence dim over the tp axis.
# "ep" is the expert-parallel axis (apex_tpu.moe): present in the mesh
# ONLY when initialize_model_parallel is asked for
# expert_model_parallel_size > 1, so dense programs trace over the
# identical 3-axis mesh they always did.
DP_AXIS = "dp"
PP_AXIS = "pp"
TP_AXIS = "tp"
EP_AXIS = "ep"

_GLOBAL_STATE = None


@dataclasses.dataclass
class _MeshState:
    mesh: Mesh
    tensor_model_parallel_size: int
    pipeline_model_parallel_size: int
    data_parallel_size: int
    expert_model_parallel_size: int = 1
    virtual_pipeline_model_parallel_size: Optional[int] = None
    # Mutable "current rank" cursors used by host-driven pipeline code,
    # mirroring the reference's get/set_virtual_pipeline_model_parallel_rank
    # (parallel_state.py:700-712).
    virtual_pipeline_model_parallel_rank: int = 0
    pipeline_model_parallel_split_rank: Optional[int] = None
    use_fp8: bool = False


class MeshNotInitializedError(RuntimeError):
    pass


def initialize_model_parallel(
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    virtual_pipeline_model_parallel_size: Optional[int] = None,
    pipeline_model_parallel_split_rank: Optional[int] = None,
    expert_model_parallel_size: int = 1,
    use_fp8: bool = False,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the global (pp, dp[, ep], tp) mesh.

    ≡ parallel_state.initialize_model_parallel (parallel_state.py:155-419),
    with process groups replaced by named mesh axes.  The data-parallel
    size is inferred as n_devices // (tp * pp * ep), exactly like the
    reference's `data_parallel_size = world_size // (tp*pp)`
    (parallel_state.py:242-244).

    expert_model_parallel_size > 1 inserts the expert-parallel axis
    between dp and tp — inner to dp so the MoE dispatch/combine
    all-to-alls (apex_tpu.moe) ride faster ICI links than the dp grad
    sync, outer to tp so each expert's GEMMs can still shard over tp.
    With the default (1) the mesh is the exact 3-axis (pp, dp, tp)
    layout every dense program has always traced over — no ep axis
    appears, so compiled programs, comms fixtures, and lint traces of
    dense steps are byte-identical to the pre-MoE framework.
    """
    global _GLOBAL_STATE
    # every job's first call into the program: the set-up ledger
    # (monitor.compile.startup) listens from here on
    from apex_tpu.monitor.compile import startup

    startup.arm()
    with startup.span("initialize_model_parallel"):
        if devices is None:
            devices = jax.devices()
        world_size = len(devices)
        tp, pp = tensor_model_parallel_size, pipeline_model_parallel_size
        ep = expert_model_parallel_size
        if ep < 1:
            raise ValueError(f"expert_model_parallel_size must be >= 1, got {ep}")
        if world_size % (tp * pp * ep) != 0:
            raise ValueError(
                f"world size {world_size} is not divisible by tp({tp}) x pp({pp})"
                f" x ep({ep})"
            )
        dp = world_size // (tp * pp * ep)
        if virtual_pipeline_model_parallel_size is not None and pp < 2:
            raise ValueError(
                "virtual pipeline parallelism requires pipeline_model_parallel_size >= 2"
            )
        if ep > 1:
            dev_array = np.asarray(devices).reshape(pp, dp, ep, tp)
            mesh = Mesh(dev_array, (PP_AXIS, DP_AXIS, EP_AXIS, TP_AXIS))
        else:
            dev_array = np.asarray(devices).reshape(pp, dp, tp)
            mesh = Mesh(dev_array, (PP_AXIS, DP_AXIS, TP_AXIS))
        _GLOBAL_STATE = _MeshState(
            mesh=mesh,
            tensor_model_parallel_size=tp,
            pipeline_model_parallel_size=pp,
            data_parallel_size=dp,
            expert_model_parallel_size=ep,
            virtual_pipeline_model_parallel_size=virtual_pipeline_model_parallel_size,
            pipeline_model_parallel_split_rank=pipeline_model_parallel_split_rank,
            use_fp8=use_fp8,
        )
    return mesh


def model_parallel_is_initialized() -> bool:
    """≡ parallel_state.model_parallel_is_initialized (parallel_state.py:424)."""
    return _GLOBAL_STATE is not None


def destroy_model_parallel() -> None:
    """≡ parallel_state.destroy_model_parallel (parallel_state.py:761-792)."""
    global _GLOBAL_STATE
    _GLOBAL_STATE = None


def _state() -> _MeshState:
    if _GLOBAL_STATE is None:
        raise MeshNotInitializedError(
            "mesh is not initialized; call apex_tpu.parallel.initialize_model_parallel first"
        )
    return _GLOBAL_STATE


def get_mesh() -> Mesh:
    return _state().mesh


def get_tensor_model_parallel_world_size() -> int:
    return _state().tensor_model_parallel_size


def get_pipeline_model_parallel_world_size() -> int:
    return _state().pipeline_model_parallel_size


def get_data_parallel_world_size() -> int:
    return _state().data_parallel_size


def get_expert_model_parallel_world_size() -> int:
    return _state().expert_model_parallel_size


def get_data_parallel_axis_names() -> tuple:
    """The mesh axes a data batch (and its grad sync) spans.

    Without expert parallelism this is ("dp",).  With an ep axis the
    batch shards over BOTH ("dp", "ep") — expert parallelism rides
    inside the data-parallel world: each ep shard routes its own
    tokens and the all-to-all exchanges them with its ep peers, so for
    every non-expert parameter the ep axis is just more data
    parallelism (docs/moe.md, the routing contract).  Feed the tuple
    to `ddp.make_train_step(axis_name=...)` / `lax.pmean` — collective
    primitives take the tuple directly.
    """
    if _state().expert_model_parallel_size > 1:
        return (DP_AXIS, EP_AXIS)
    return (DP_AXIS,)


def get_virtual_pipeline_model_parallel_world_size() -> Optional[int]:
    return _state().virtual_pipeline_model_parallel_size


def get_virtual_pipeline_model_parallel_rank() -> int:
    return _state().virtual_pipeline_model_parallel_rank


def set_virtual_pipeline_model_parallel_rank(rank: int) -> None:
    _state().virtual_pipeline_model_parallel_rank = rank


def get_pipeline_model_parallel_split_rank() -> Optional[int]:
    return _state().pipeline_model_parallel_split_rank


# --- axis_index helpers: valid inside shard_map/pjit over the global mesh ---

def get_tensor_model_parallel_rank():
    """Per-shard tp coordinate; use inside shard_map (≡ get_tensor_model_parallel_rank)."""
    return jax.lax.axis_index(TP_AXIS)


def get_data_parallel_rank():
    return jax.lax.axis_index(DP_AXIS)


def get_expert_model_parallel_rank():
    """Per-shard ep coordinate; use inside shard_map.  Only valid when
    the mesh was built with expert_model_parallel_size > 1 (the ep
    axis does not exist otherwise)."""
    return jax.lax.axis_index(EP_AXIS)


def get_pipeline_model_parallel_rank():
    return jax.lax.axis_index(PP_AXIS)


def is_pipeline_first_stage(stage: int) -> bool:
    """Host-side check for a host-driven pipeline stage index.

    ≡ parallel_state.is_pipeline_first_stage (parallel_state.py:590) for the
    non-virtual case; virtual chunks are handled by the schedule driver.
    """
    return stage == 0


def is_pipeline_last_stage(stage: int) -> bool:
    return stage == _state().pipeline_model_parallel_size - 1


def get_rank_info() -> str:
    """(dp, tp, pp) info string for log prefixes ≡ parallel_state.get_rank_info
    (parallel_state.py:421-430).  Host-level: reports process index and mesh
    shape (per-device coordinates are a compile-time notion under SPMD)."""
    if _GLOBAL_STATE is None:
        return f"proc{jax.process_index()}"
    s = _GLOBAL_STATE
    ep = (f"/ep{s.expert_model_parallel_size}"
          if s.expert_model_parallel_size > 1 else "")
    return (
        f"proc{jax.process_index()} dp{s.data_parallel_size}"
        f"/tp{s.tensor_model_parallel_size}"
        f"/pp{s.pipeline_model_parallel_size}{ep}"
    )


# --- sharding constructors -------------------------------------------------

def named_sharding(*spec) -> NamedSharding:
    """NamedSharding over the global mesh from PartitionSpec entries."""
    return NamedSharding(get_mesh(), P(*spec))


def data_parallel_sharding(ndim: int) -> NamedSharding:
    """Batch-dim sharding over dp (and pp folded in when pp==1 is absent)."""
    spec = [DP_AXIS] + [None] * (ndim - 1)
    return named_sharding(*spec)


# --- group membership (pipeline-stage sets replacing process groups) -------
#
# The reference builds dedicated process groups for tied-embedding /
# position-embedding / relative-position-embedding gradient exchange
# (parallel_state.py:321-407) and fp8 amax reduction (280-292).  Under one
# SPMD mesh those become *sets of pipeline stages* (every (dp, tp)
# coordinate participates alike) plus the mesh axes to reduce over.

def _split(s: _MeshState) -> Optional[int]:
    return s.pipeline_model_parallel_split_rank


def get_embedding_group_stages() -> list:
    """Pipeline stages that hold tied input/output embeddings.

    ≡ embedding_ranks construction (parallel_state.py:352-370): [first,
    last], with the encoder/decoder split stage inserted when set.
    """
    s = _state()
    pp = s.pipeline_model_parallel_size
    if pp == 1:
        return [0]
    stages = [0, pp - 1]
    sp = _split(s)
    if sp is not None and sp not in stages:
        stages = [0, sp, pp - 1]
    return stages


def get_position_embedding_group_stages() -> list:
    """≡ position_embedding_ranks (parallel_state.py:355,367-370)."""
    s = _state()
    if s.pipeline_model_parallel_size == 1:
        return [0]
    sp = _split(s)
    return [0] if sp in (None, 0) else [0, sp]


def get_encoder_relative_position_embedding_group_stages() -> list:
    """≡ encoder_relative_position_embedding_ranks (parallel_state.py:356-363)."""
    s = _state()
    pp = s.pipeline_model_parallel_size
    if pp == 1:
        return [0]
    sp = _split(s)
    return [0] if sp is None else list(range(sp))


def get_decoder_relative_position_embedding_group_stages() -> list:
    """≡ decoder_relative_position_embedding_ranks (parallel_state.py:356-365)."""
    s = _state()
    pp = s.pipeline_model_parallel_size
    if pp == 1:
        return [0]
    sp = _split(s)
    return [0] if sp is None else list(range(sp, pp))


def is_rank_in_embedding_group(stage: int) -> bool:
    """≡ parallel_state.is_rank_in_embedding_group for a host-driven stage."""
    return stage in get_embedding_group_stages()


def is_rank_in_position_embedding_group(stage: int) -> bool:
    return stage in get_position_embedding_group_stages()


def is_pipeline_stage_before_split(stage: Optional[int] = None) -> bool:
    """≡ parallel_state.is_pipeline_stage_before_split: True when the stage
    executes encoder layers (always True without an encoder/decoder split)."""
    s = _state()
    sp = _split(s)
    if sp is None:
        return True
    if stage is None:
        raise ValueError("stage index required under SPMD (no implicit rank)")
    return stage < sp


def is_pipeline_stage_after_split(stage: Optional[int] = None) -> bool:
    s = _state()
    sp = _split(s)
    if sp is None:
        return True
    if stage is None:
        raise ValueError("stage index required under SPMD (no implicit rank)")
    return stage >= sp


def is_pipeline_stage_at_split(stage: int) -> bool:
    """True when `stage` runs the last encoder block and `stage+1` the first
    decoder block (≡ parallel_state.is_pipeline_stage_at_split)."""
    return is_pipeline_stage_before_split(stage) and is_pipeline_stage_after_split(
        stage + 1
    )


def set_pipeline_model_parallel_split_rank(rank: Optional[int]) -> None:
    _state().pipeline_model_parallel_split_rank = rank


# --- pipeline rank math ----------------------------------------------------

def get_pipeline_model_parallel_next_rank(stage: int) -> int:
    """Next stage index, wrapping — the ppermute source/dest math that
    replaces _PIPELINE_GLOBAL_RANKS lookups (parallel_state.py:737-752)."""
    return (stage + 1) % _state().pipeline_model_parallel_size


def get_pipeline_model_parallel_prev_rank(stage: int) -> int:
    return (stage - 1) % _state().pipeline_model_parallel_size


def get_pipeline_model_parallel_first_rank() -> int:
    return 0


def get_pipeline_model_parallel_last_rank() -> int:
    return _state().pipeline_model_parallel_size - 1


def get_pipeline_global_device_ranks(dp_index: int = 0, tp_index: int = 0) -> list:
    """Flat device indices of one pipeline group — range(i, world,
    world//pp) in the reference's rank ordering (parallel_state.py:345-348).
    With the (pp, dp, tp) row-major mesh this is stage*dp*tp + dp_index*tp
    + tp_index for each stage."""
    s = _state()
    stride = s.data_parallel_size * s.tensor_model_parallel_size
    base = dp_index * s.tensor_model_parallel_size + tp_index
    return [base + stage * stride for stage in
            range(s.pipeline_model_parallel_size)]


def get_tensor_model_parallel_src_rank(device_rank: int) -> int:
    """First flat device index of `device_rank`'s TP group
    (≡ parallel_state.get_tensor_model_parallel_src_rank:713-718)."""
    tp = _state().tensor_model_parallel_size
    return (device_rank // tp) * tp


def get_data_parallel_src_rank(device_rank: int) -> int:
    """First flat device index of `device_rank`'s DP group.

    ≡ parallel_state.get_data_parallel_src_rank:721-726 in intent.  The
    reference computes ``rank % num_dp_groups``, which only names the
    group's first member when pp == 1; here the first member is derived
    from the (pp, dp, tp) coordinates directly so it is correct for any
    pipeline depth: same stage, dp index 0, same tp index.
    """
    s = _state()
    stage_size = s.data_parallel_size * s.tensor_model_parallel_size
    stage_base = (device_rank // stage_size) * stage_size
    return stage_base + device_rank % s.tensor_model_parallel_size


# --- fp8 amax reduction ----------------------------------------------------

def fp8_is_enabled() -> bool:
    return _state().use_fp8


def get_amax_reduction_axes() -> tuple:
    """Mesh axes spanning one amax-reduction group.

    The reference's amax group is tp*dp contiguous ranks — exactly one
    pipeline stage's (dp, tp) plane under this mesh layout
    (parallel_state.py:280-292).  Reduce over these axes inside
    shard_map, e.g. ``lax.pmax(amax, get_amax_reduction_axes())``.
    """
    if not _state().use_fp8:
        raise MeshNotInitializedError(
            "AMAX reduction group is not initialized; pass use_fp8=True to "
            "initialize_model_parallel"
        )
    return (DP_AXIS, TP_AXIS)


def reduce_amax(x):
    """pmax of a per-shard amax over the amax-reduction group; call inside
    shard_map over the global mesh."""
    return jax.lax.pmax(x, get_amax_reduction_axes())


def get_model_parallel_axes() -> tuple:
    """Axes of the model-parallel group (pp × tp plane) — e.g. for the
    MP-aware GradScaler's found_inf reduction (amp/grad_scaler.py:44-55)."""
    return (PP_AXIS, TP_AXIS)


def new_process_group(axes) -> tuple:
    """≡ parallel_state.new_process_group (parallel_state.py:108-153).

    The reference creates a torch.distributed group from a rank list,
    choosing NCCL-vs-UCC and IB/socket transports.  Under one SPMD mesh a
    "group" is just a validated tuple of mesh axis names to hand to a
    collective; transport selection is XLA's (ICI within a slice, DCN
    across).  Accepts a single axis name or an iterable of them.
    """
    if isinstance(axes, str):
        axes = (axes,)
    axes = tuple(axes)
    valid = set(get_mesh().axis_names)
    unknown = [a for a in axes if a not in valid]
    if unknown:
        raise ValueError(f"unknown mesh axes {unknown}; have {sorted(valid)}")
    return axes
