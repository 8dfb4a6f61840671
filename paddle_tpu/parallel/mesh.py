"""Device mesh bookkeeping.

Replaces NCCLContextMap / NCCLCommunicator ring bookkeeping
(ref: platform/nccl_helper.h:90,179 — flat + hierarchical comm groups;
platform/collective_helper.h named comms). On TPU the runtime knows the
topology; a mesh names axes (data/model/pipe/seq) and XLA lowers
collectives onto ICI rings per axis. The BuildStrategy knobs
(hierarchical allreduce, multi-ring, ref: details/build_strategy.h:129-138)
correspond to how axes are laid out over the physical topology.

Canonical axis names:
  "data"  — data parallel (the reference's trainer replicas)
  "model" — tensor/op parallelism (not in the reference; free via GSPMD)
  "pipe"  — pipeline stages (ref: PipelineTrainer)
  "seq"   — sequence/context parallelism (ring attention)
"""

import contextlib
from dataclasses import dataclass, field

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"    # MoE expert parallelism (all_to_all routing)
DCN_AXIS = "dcn_data"     # cross-slice data parallelism (rides DCN)


@dataclass
class MeshConfig:
    data: int = -1     # -1 = all remaining devices
    model: int = 1
    pipe: int = 1
    seq: int = 1
    # cross-slice (DCN) data-parallel degree. > 1 prepends an OUTERMOST
    # "dcn_data" axis: gradient sync over ("dcn_data", "data") is then
    # hierarchical — XLA reduces within each slice over ICI first and
    # crosses DCN once per slice, the TPU-native form of the
    # reference's inter/exter two-level rings
    # (nccl_helper.h:179 NCCLCommunicator, build_strategy.h:132-138
    # use_hierarchical_allreduce).
    dcn_data: int = 1
    # MoE expert parallelism; > 1 appends an "expert" axis to
    # axis_order (kept out of the default order so non-MoE meshes are
    # unchanged)
    expert: int = 1
    axis_order: tuple = (DATA_AXIS, PIPE_AXIS, MODEL_AXIS, SEQ_AXIS)


def _effective_order(cfg):
    order = tuple(cfg.axis_order)
    if max(getattr(cfg, "expert", 1), 1) > 1 and EXPERT_AXIS not in order:
        order = order + (EXPERT_AXIS,)
    return order


def mesh_shape_for(n_devices, cfg):
    sizes = {DATA_AXIS: cfg.data, MODEL_AXIS: cfg.model,
             PIPE_AXIS: cfg.pipe, SEQ_AXIS: cfg.seq,
             EXPERT_AXIS: max(getattr(cfg, "expert", 1), 1)}
    order = _effective_order(cfg)
    fixed = max(getattr(cfg, "dcn_data", 1), 1)
    for a in order:
        if sizes.get(a, 1) != -1:
            fixed *= sizes.get(a, 1)
    for a in sizes:
        if sizes[a] == -1:
            sizes[a] = n_devices // fixed
    return tuple(sizes.get(a, 1) for a in order)


def make_mesh(config=None, devices=None):
    """Build a Mesh over the given (default: all) devices.

    Axis layout policy (the DCN-vs-ICI placement the reference tunes
    with hierarchical/multi-ring knobs, build_strategy.h:129-138):
    - the OUTERMOST axis strides across the largest device distances —
      config.dcn_data puts cross-slice data parallelism there, so only
      that axis's collectives cross DCN;
    - the INNERMOST mesh axis maps to adjacent devices, so the
      highest-bandwidth-demand axis ("model", default axis_order) sits
      innermost on the tightest ICI ring (the inter/exter ring split of
      parallel_executor.cc:158-180).
    On a TPU the layout is taken from the platform topology
    (mesh_utils.create_device_mesh; create_hybrid_device_mesh across
    slices); virtual/CPU platforms use the order of jax.devices().
    """
    devices = devices if devices is not None else jax.devices()
    config = config or MeshConfig()
    dcn = max(getattr(config, "dcn_data", 1), 1)
    shape = mesh_shape_for(len(devices), config)
    names = _effective_order(config)
    if dcn > 1:
        names = (DCN_AXIS,) + names
        per_slice = tuple(shape)
        slice_ids = {getattr(d, "slice_index", None) for d in devices}
        if len(slice_ids - {None}) > 1:
            # real multi-slice fleet: the hybrid layout must respect
            # slice boundaries (errors here are config errors and must
            # surface — a silent reshape would route intra-slice
            # collectives over DCN)
            from jax.experimental import mesh_utils
            arr = mesh_utils.create_hybrid_device_mesh(
                (1,) + per_slice,
                dcn_mesh_shape=(dcn,) + (1,) * len(per_slice),
                devices=devices)
            return Mesh(arr, names)
        # single-slice / virtual platforms: outermost-axis reshape
        shape = (dcn,) + per_slice
    used = 1
    for s in shape:
        used *= s
    # topology-aware on a TPU (the mesh axes follow the chips' ICI
    # coordinates); a plain reshape of the device list elsewhere
    from jax.experimental import mesh_utils
    arr = mesh_utils.create_device_mesh(shape, devices[:used])
    return Mesh(arr, names)


def data_axes(mesh):
    """The data-parallel axes present in the mesh, DCN-outermost:
    gradient psum over this tuple is the hierarchical allreduce."""
    return tuple(a for a in (DCN_AXIS, DATA_AXIS)
                 if a in mesh.shape)


_current_mesh = [None]


def set_mesh(mesh):
    _current_mesh[0] = mesh
    return mesh


def get_mesh():
    if _current_mesh[0] is None:
        set_mesh(make_mesh())
    return _current_mesh[0]


@contextlib.contextmanager
def mesh_guard(mesh):
    old = _current_mesh[0]
    _current_mesh[0] = mesh
    try:
        yield mesh
    finally:
        _current_mesh[0] = old


def named_sharding(mesh, *spec):
    return NamedSharding(mesh, P(*spec))
