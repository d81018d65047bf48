"""Device mesh construction and canonical shardings.

The reference's "mesh" is implicit: one process per GPU, rank == device id
(``/root/reference/multi_proc_single_gpu.py:180-181``), world_size asserted
== local GPU count (``:351``), and the only parallel axis is data
(SURVEY.md section 2c). Here the mesh is explicit and N-dimensional from day
one: data parallelism is ``Mesh(devices, ('data',))``, and adding model/fsdp
axes later is a ``PartitionSpec`` change, not new machinery.

On TPU, mesh construction uses ``jax.devices()`` in their default order,
which XLA lays out so that neighboring mesh positions are ICI neighbors —
the gradient AllReduce over ``data`` therefore rides ICI, not DCN, exactly
the property NCCL rings give the reference on NVLink.

Multi-slice worlds break that flat picture: chips within a slice talk
over ICI, chips in different slices over DCN, 10-100x slower.
``make_hier_mesh`` builds the two-tier ``('dcn', 'ici', ...)`` mesh for
that topology — data-major like every mesh here, with the DATA axis
*composed* of both tiers (batch rows shard over ``('dcn', 'ici')``
jointly) so tier-aware schedules (``parallel/zero_overlap.py``) can
address each tier by name while tier-oblivious GSPMD paths treat the
pair as one axis. Slice assignment comes from real topology
(``device.slice_index``) when the runtime reports one, else from the
emulated map ``TPUMNIST_DCN_SLICES`` / ``--dcn-slices`` (contiguous
blocks of the device order), so CPU worlds and tests exercise the
hierarchy.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_mnist_tpu.utils.profiling import compile_log

# The two tiers of a hierarchical mesh, leading (data-major) — together
# they ARE the data axis; model axes follow.
HIER_DATA_AXES: Tuple[str, str] = ("dcn", "ici")

# Emulated slice map: N contiguous equal blocks of the device order.
DCN_SLICES_ENV = "TPUMNIST_DCN_SLICES"


def make_mesh(
    axes: Tuple[str, ...] = ("data",),
    shape: Optional[Tuple[int, ...]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a mesh over ``devices`` (default: all global devices).

    With the default 1-D ``('data',)`` axes and no shape, every device joins
    the data axis — the DDP-equivalent topology. Pass e.g.
    ``axes=('data', 'model'), shape=(4, 2)`` for a 2-D layout.
    """
    devs = np.asarray(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (devs.size,) if len(axes) == 1 else None
        if shape is None:
            raise ValueError("shape is required for multi-axis meshes")
    if int(np.prod(shape)) != devs.size:
        raise ValueError(f"mesh shape {shape} != device count {devs.size}")
    # Every entry point passes here (or make_hier_mesh) once the devices
    # answer: where set-up's ``startup`` span is split.
    compile_log.backend_ready()
    return Mesh(devs.reshape(shape), axes)


def device_slice_index(device) -> Optional[int]:
    """The device's real slice assignment (TPU multi-slice runtimes
    stamp ``slice_index``), or None when the runtime reports none."""
    idx = getattr(device, "slice_index", None)
    return int(idx) if isinstance(idx, (int, np.integer)) else None


def infer_dcn_slices(devices: Optional[Sequence] = None) -> int:
    """How many DCN slices this world spans: the ``TPUMNIST_DCN_SLICES``
    emulation env when set, else the count of distinct real
    ``device.slice_index`` values, else 1 (a flat single-slice world).
    """
    env = os.environ.get(DCN_SLICES_ENV, "")
    if env:
        try:
            return int(env)
        except ValueError:
            raise ValueError(
                f"{DCN_SLICES_ENV}={env!r} is not an integer slice count")
    devs = list(devices) if devices is not None else jax.devices()
    real = {device_slice_index(d) for d in devs}
    if None in real or len(real) < 2:
        return 1
    return len(real)


def _slice_blocks(devices: Sequence, dcn_slices: int) -> list:
    """Order ``devices`` slice-major and validate the slice topology
    (pure: drivable with fake device objects). With real ``slice_index``
    stamps the devices are grouped by slice (equal sizes required, slice
    count must match); without them the given order is the emulated map
    — ``dcn_slices`` contiguous equal blocks."""
    devices = list(devices)
    n = len(devices)
    if dcn_slices < 1:
        raise ValueError(f"dcn_slices must be >= 1, got {dcn_slices}")
    if n % dcn_slices:
        raise ValueError(
            f"{n} device(s) do not split into {dcn_slices} equal DCN "
            f"slices")
    per = n // dcn_slices
    real = [device_slice_index(d) for d in devices]
    if all(r is not None for r in real) and len(set(real)) > 1:
        groups: dict = {}
        for d, r in zip(devices, real):
            groups.setdefault(r, []).append(d)
        if len(groups) != dcn_slices:
            raise ValueError(
                f"devices report {len(groups)} distinct slice_index "
                f"value(s), not the requested {dcn_slices} DCN slices")
        bad = {k: len(v) for k, v in groups.items() if len(v) != per}
        if bad:
            raise ValueError(
                f"unequal slice sizes (expected {per} chips/slice, got "
                f"{bad}): every DCN slice must contribute the same chip "
                f"count")
        return [d for k in sorted(groups) for d in groups[k]]
    return devices


def validate_dcn_slices(dcn_slices: int,
                        devices: Optional[Sequence] = None) -> None:
    """Raise ``ValueError`` unless ``devices`` (default: the world) can
    form ``dcn_slices`` equal slices — the SAME checks ``make_hier_mesh``
    runs (count divisibility AND, with real ``slice_index`` stamps,
    slice-count match and equal sizes), so callers that want flag-level
    rejection (cli.py) or graceful degradation (the elastic flat
    fallback) can decide BEFORE construction; a later ``make_hier_mesh``
    on the same inputs cannot fail for slice reasons."""
    devs = list(devices) if devices is not None else jax.devices()
    _slice_blocks(devs, dcn_slices)


def make_hier_mesh(
    dcn_slices: Optional[int] = None,
    extra_axes: Tuple[str, ...] = (),
    extra_shape: Tuple[int, ...] = (),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the data-major two-tier ``('dcn', 'ici', *extra_axes)`` mesh.

    Axis 0 (``dcn``) indexes the slice, axis 1 (``ici``) the data
    position within it; together they compose the data axis (batch rows
    shard over the pair — ``data_sharding``/``data_replica_coords``
    understand the composition). ``extra_axes``/``extra_shape`` append
    model axes (model/seq/expert), which nest INSIDE one slice: the
    total model width must divide the per-slice chip count, so no
    TP/EP group ever straddles the slow DCN tier — a straddling layout
    is rejected here, not discovered as a slow program.

    ``dcn_slices=None`` resolves via :func:`infer_dcn_slices` (env map,
    then real ``device.slice_index`` topology) and refuses a flat world
    — callers that want flat build ``make_mesh`` instead.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if dcn_slices is None:
        dcn_slices = infer_dcn_slices(devs)
        if dcn_slices < 2:
            raise ValueError(
                f"no DCN slice topology: devices carry no slice_index "
                f"and {DCN_SLICES_ENV} is unset — pass dcn_slices "
                f"explicitly (or build a flat make_mesh)")
    if len(extra_axes) != len(extra_shape):
        raise ValueError(
            f"extra_axes {extra_axes} and extra_shape {extra_shape} "
            f"must pair up")
    for ax in extra_axes:
        if ax in HIER_DATA_AXES + ("data",):
            raise ValueError(
                f"extra axis {ax!r} collides with the hierarchical "
                f"data axes {HIER_DATA_AXES}")
    ordered = _slice_blocks(devs, dcn_slices)
    per_slice = len(ordered) // dcn_slices
    model = int(np.prod(extra_shape, dtype=np.int64)) if extra_shape else 1
    if model < 1 or per_slice % model:
        raise ValueError(
            f"model axes {dict(zip(extra_axes, extra_shape))} (width "
            f"{model}) would straddle the DCN boundary: each slice has "
            f"{per_slice} chip(s), and model-parallel groups must nest "
            f"inside one slice's ICI domain")
    shape = (dcn_slices, per_slice // model) + tuple(extra_shape)
    grid = np.empty(len(ordered), dtype=object)
    grid[:] = ordered
    compile_log.backend_ready()
    return Mesh(grid.reshape(shape), HIER_DATA_AXES + tuple(extra_axes))


def is_hier_mesh(mesh: Mesh) -> bool:
    """Whether ``mesh`` is a two-tier ``('dcn', 'ici', ...)`` mesh."""
    return tuple(mesh.axis_names[:2]) == HIER_DATA_AXES


def resolve_data_axis(mesh: Optional[Mesh], axis="data"):
    """The axis (name or composed name tuple) batch rows shard over:
    the requested ``axis`` as-is, except that the default ``'data'`` on
    a hierarchical mesh resolves to the composed ``('dcn', 'ici')``
    pair — so every tier-oblivious call site (steps, loader, staging)
    follows the mesh without knowing about tiers."""
    if mesh is not None and axis == "data" and is_hier_mesh(mesh):
        return HIER_DATA_AXES
    return axis


def device_slice_map(devices: Sequence) -> Optional[list]:
    """Per-device slice assignment for ``devices`` (any subset of the
    world), or None when no slice topology exists. Real ``slice_index``
    stamps win; the emulated ``TPUMNIST_DCN_SLICES`` map assigns by
    global device id (contiguous equal blocks of the world), matching
    ``make_hier_mesh``'s emulated blocks. Serving uses this to prefer
    single-slice mesh groups (``serve/programs.py partition_groups``)
    and to flag groups that straddle slices."""
    devs = list(devices)
    if not devs:
        return None
    real = [device_slice_index(d) for d in devs]
    if all(r is not None for r in real):
        world_real = {device_slice_index(d) for d in jax.devices()}
        if None not in world_real and len(world_real) > 1:
            return real
    env = os.environ.get(DCN_SLICES_ENV, "")
    if not env:
        return None
    try:
        n_slices = int(env)
    except ValueError:
        return None
    world = jax.device_count()
    if n_slices < 2 or world % n_slices:
        return None
    per = world // n_slices
    return [int(getattr(d, "id", 0)) // per for d in devs]


def data_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Sharding for a batch: leading (batch) dim split across ``axis``
    (the composed ``('dcn', 'ici')`` pair on hierarchical meshes)."""
    return NamedSharding(mesh, P(resolve_data_axis(mesh, axis)))


def data_replica_coords(mesh: Mesh, process_index: Optional[int] = None):
    """How this process's devices partition the leading (data) mesh axis:
    ``(num_replicas, rank)`` for the host-side batch sharder.

    Batch rows shard over the DATA axis, not over processes. In classic
    multi-host DP the two coincide (each host's devices sit at their own
    data coordinates), but when another axis spans hosts — multi-host TP,
    PP, SP: mesh ``data=1 x stage=2`` over 2 processes, say — the batch
    is *replicated* with respect to those processes, and each must feed
    IDENTICAL rows: ``jax.make_array_from_process_local_data`` builds an
    ill-defined global array if nominal replicas disagree (no cross-host
    value check exists, so the divergence is silent). Grouping processes
    by the data coordinates their devices cover makes every composition
    feed consistent input; pure DP degenerates to
    ``(process_count, process_index)``.

    Relies on the data-major device order ``make_mesh`` uses (the data
    axis is axis 0 of every mesh this framework builds — or, on a
    hierarchical mesh, the composed ``('dcn', 'ici')`` leading pair,
    collapsed here into one data axis before grouping), and raises if a
    process's devices do not cover a contiguous uniform block of it.
    """
    names = tuple(mesh.axis_names)
    devices = mesh.devices
    if names[:2] == HIER_DATA_AXES:
        # The composed data axis: dcn-major x ici-minor is exactly the
        # device order make_hier_mesh laid out, so collapsing the two
        # leading axes yields the flat data axis the sharder needs.
        devices = devices.reshape((-1,) + devices.shape[2:])
    elif names[0] != "data":
        # Grouping by axis 0 of a mesh whose data axis lives elsewhere
        # would shard the batch over the wrong axis — the same silent
        # divergence this function exists to prevent. Every mesh this
        # framework builds is data-major; refuse anything else loudly.
        raise ValueError(
            f"data_replica_coords requires a data-major mesh; got axes "
            f"{mesh.axis_names}")
    if process_index is None:
        process_index = jax.process_index()
    return _data_groups(devices, process_index)


def _data_groups(devices: np.ndarray, process_index: int):
    """Core of ``data_replica_coords`` over a raw device ndarray (axis 0 =
    data); split out so tests can drive it with fake device objects."""
    data_size = devices.shape[0]
    owned = [
        i for i in range(data_size)
        if any(d.process_index == process_index
               for d in np.asarray(devices[i], dtype=object).flat)
    ]
    if not owned:
        raise ValueError(
            f"process {process_index} owns no devices in this mesh")
    span = len(owned)
    # Contiguous, uniform, AND block-aligned: coordinates [1,2] of 4 are
    # contiguous with a dividing span yet straddle the shard boundary —
    # rank 1//2 would feed shard-0 rows for shard-1 devices.
    if (owned[-1] - owned[0] + 1 != span or data_size % span
            or owned[0] % span):
        raise ValueError(
            f"process {process_index}'s devices cover data coordinates "
            f"{owned} of {data_size}: not an aligned contiguous uniform "
            "block — host batch sharding requires the data-major device "
            "order make_mesh produces")
    return data_size // span, owned[0] // span


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for params/opt state: fully replicated (DDP-style weights)."""
    return NamedSharding(mesh, P())


def place_state(state, sharding_tree):
    """Place a pytree onto a sharding tree, multi-host safe.

    Single process: plain ``jax.device_put``. Multi-host: ``device_put`` of
    a committed per-host array onto a cross-host sharding demands backend
    cross-host transfer support, but every caller here holds the FULL value
    on every host (fresh replicated init, or a checkpoint stitched on each
    host), so each host just materializes its own shards from its host copy
    via ``make_array_from_callback`` — no bytes cross the network. Shared by
    the TP/EP (``parallel.tensor.shard_state``) and ZeRO
    (``parallel.zero.shard_state_zero1``) placement paths.
    """
    if jax.process_count() == 1:
        return jax.device_put(state, sharding_tree)

    def place(leaf, sh):
        host = np.asarray(leaf)  # replicated/addressable on every host
        return jax.make_array_from_callback(
            host.shape, sh, lambda idx, a=host: a[idx]
        )

    return jax.tree_util.tree_map(place, state, sharding_tree)
