"""Named-axis device mesh fabric.

The TPU-native replacement for the reference's named process-group
fabric (atorch/distributed/distributed.py:320 ``create_parallel_group``
building strided NCCL groups per name): here one
``jax.sharding.Mesh`` with named axes is the single source of truth for
DP/FSDP/PP/TP/SP/EP topology, and XLA compiles the collectives onto
ICI/DCN — no wrapper modules, no group bookkeeping.

Axis order encodes the physical hierarchy: the innermost axes change
fastest across physically-adjacent chips, so put bandwidth-hungry axes
(``tensor``) innermost (ICI neighbors) and gradient-sync axes
(``data``) outermost where they may ride DCN across slices.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from dlrover_tpu.common.log import get_logger

logger = get_logger("mesh")

# Canonical axis order, outermost (DCN-friendly) to innermost (ICI).
AXIS_ORDER: Tuple[str, ...] = (
    "data",
    "fsdp",
    "pipe",
    "seq",
    "expert",
    "tensor",
)


@dataclasses.dataclass
class MeshConfig:
    """Sizes of every parallel axis. ``-1`` on one axis = absorb all
    remaining devices (like torchrun's nnodes inference)."""

    data: int = 1
    fsdp: int = 1
    pipe: int = 1
    seq: int = 1
    expert: int = 1
    tensor: int = 1
    # Number of TPU slices the job spans; >1 splits the outermost axis
    # over DCN (multi-slice training).
    num_slices: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {name: getattr(self, name) for name in AXIS_ORDER}

    def resolve(self, n_devices: int) -> "MeshConfig":
        """Fill a single -1 axis so the product equals n_devices."""
        sizes = self.axis_sizes()
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError("at most one axis may be -1")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices, have {n_devices}"
            )
        return MeshConfig(**sizes, num_slices=self.num_slices)

    @property
    def total(self) -> int:
        return math.prod(self.axis_sizes().values())


def group_devices_by_slice(
    devices: Sequence[jax.Device],
    num_slices: int,
    slice_ids: Optional[Sequence[int]] = None,
) -> Tuple[List[jax.Device], List[int]]:
    """Order devices so slice members are contiguous blocks.

    ``slice_ids`` overrides per-device slice assignment (virtual
    slices on CPU tests); otherwise the TPU runtime's
    ``device.slice_index`` is used. When neither distinguishes slices
    (single-slice hardware faked into num_slices), the list is split
    into equal contiguous blocks. Returns (ordered_devices,
    slice_id_per_ordered_device).
    """
    n = len(devices)
    if n % num_slices:
        raise ValueError(
            f"{n} devices not divisible into {num_slices} slices"
        )
    per_slice = n // num_slices
    if slice_ids is None:
        slice_ids = [
            getattr(d, "slice_index", 0) or 0 for d in devices
        ]
    distinct = sorted(set(slice_ids))
    if len(distinct) == num_slices:
        groups: Dict[int, List[jax.Device]] = {s: [] for s in distinct}
        for d, s in zip(devices, slice_ids):
            groups[s].append(d)
        bad = {
            s: len(g) for s, g in groups.items() if len(g) != per_slice
        }
        if bad:
            raise ValueError(
                f"uneven slices (want {per_slice}/slice): {bad}"
            )
        ordered: List[jax.Device] = []
        ordered_ids: List[int] = []
        for s in distinct:
            ordered.extend(groups[s])
            ordered_ids.extend([s] * per_slice)
        return ordered, ordered_ids
    if len(distinct) == 1:
        # no slice info: contiguous equal split (virtual slices)
        ids = [i // per_slice for i in range(n)]
        return list(devices), ids
    raise ValueError(
        f"devices span {len(distinct)} slices but num_slices="
        f"{num_slices}"
    )


def build_mesh(
    config: MeshConfig,
    devices: Optional[Sequence[jax.Device]] = None,
    slice_ids: Optional[Sequence[int]] = None,
) -> Mesh:
    """Build the job mesh.

    Single-slice: devices are reshaped in canonical axis order. The
    device list from ``jax.devices()`` enumerates ICI-adjacent chips
    contiguously, so innermost mesh axes land on ICI neighbors.

    Multi-slice (num_slices > 1): devices are grouped so each slice is
    one contiguous block of the outermost non-trivial axis (which must
    be divisible by num_slices) — only that axis's collectives cross
    DCN, everything inner stays on ICI. Slice membership comes from
    the TPU runtime (``device.slice_index``) or an explicit
    ``slice_ids`` list (virtual slices in CPU tests). This is the
    capability the reference reaches via per-group NCCL bootstrap
    across nodes (atorch/distributed/distributed.py:587).
    """
    devices = list(devices if devices is not None else jax.devices())
    config = config.resolve(len(devices))
    sizes = config.axis_sizes()
    if config.num_slices > 1:
        outer = next(
            (a for a in AXIS_ORDER if sizes[a] > 1), AXIS_ORDER[0]
        )
        if sizes[outer] % config.num_slices:
            raise ValueError(
                f"outermost axis {outer}={sizes[outer]} not divisible "
                f"by num_slices={config.num_slices}"
            )
        devices, _ = group_devices_by_slice(
            devices, config.num_slices, slice_ids
        )
        per_slice = len(devices) // config.num_slices
        logger.info(
            "multi-slice mesh: %d slices x %d devices; axis %r "
            "crosses DCN",
            config.num_slices,
            per_slice,
            outer,
        )
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    dev_array = np.asarray(devices).reshape(shape)
    mesh = Mesh(dev_array, AXIS_ORDER)
    logger.info(
        "mesh: %s over %d devices",
        {a: s for a, s in sizes.items() if s > 1} or {"data": 1},
        len(devices),
    )
    return mesh


def under_mesh(fn: Callable, mesh: Mesh) -> Callable:
    """``fn``, traced with ``mesh`` as the ambient mesh.

    jit learns the mesh from its arguments' shardings, after the
    trace; code that must know it DURING the trace reads the ambient
    one. The Pallas kernels do (:func:`per_device`, below): XLA
    cannot partition a Mosaic call, so under a mesh of several
    devices they split themselves over batch rows and heads. The step
    builders wrap the loss in this, so a bare model loss compiles on
    any mesh; one device needs nothing, and a trace that already has
    a mesh (a caller's ``jax.set_mesh``, the inside of a
    ``shard_map``) keeps its own."""
    if mesh.size == 1:
        return fn

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        if not jax.sharding.get_abstract_mesh().empty:
            return fn(*args, **kwargs)
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return traced


# -- how a Pallas kernel meets the backend and the ambient mesh -----------


def use_interpret() -> bool:
    """Off the TPU the package's kernels run interpreted."""
    return jax.default_backend() != "tpu"


# The mesh axes that split an activation's batch rows and its heads
# (parallel/sharding.py DEFAULT_RULES: "batch" and "heads").
_BATCH_AXES = ("data", "fsdp")
_HEAD_AXIS = "tensor"


def _ambient_mesh():
    """The mesh the trace is under, or None where :func:`per_device`
    makes a plain call: no mesh, one device, or the inside of a
    ``shard_map`` (the operands already are one device's blocks)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return None
    return mesh


def batch_axes(rows: int):
    """(the ambient mesh's batch axes that :func:`per_device` splits
    ``rows`` rows over, the rows one device then holds): every batch
    axis larger than one that divides what the axes before it left."""
    mesh = _ambient_mesh()
    batch = []
    for axis in _BATCH_AXES if mesh is not None else ():
        n = mesh.shape.get(axis, 1)
        if n > 1 and rows % n == 0:
            batch.append(axis)
            rows //= n
    return tuple(batch), rows


def per_device(call, *operands, split, heads_dim=None, head_size=1,
               out_heads_dims=None, summed=(), manual_all=True):
    """``call(*operands)``, run once per device of the mesh the trace
    is under.

    A Pallas kernel is a Mosaic custom call, and XLA refuses to
    partition one ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"). The kernels
    of this package treat every batch row, and the attention ones
    every head, on its own, so under an ambient mesh (``jax.set_mesh``
    or the step builders' :func:`under_mesh`) the call goes
    through ``shard_map``: dim 0 of each operand flagged in ``split``
    over the batch axes, dim ``heads_dim`` over ``tensor`` (it holds
    heads of ``head_size`` entries each, every split operand a count
    of its own, and is split only into whole heads of all of them),
    the other operands (weights) and dims whole on every device;
    outputs are split like the operands (``out_heads_dims``: the
    heads' dim of each output, where it is not the operands'), and
    autodiff sums the weights' gradients over the mesh. An axis that
    does not divide its dim is left out, and XLA gathers that dim
    instead.

    ``summed`` flags outputs (``call`` then returns a tuple) that are
    one device's share of a sum over the batch rows, a weight's
    gradient formed by hand: they are summed over the batch axes in
    the dtype they have and come back whole. ``manual_all=False``
    leaves the mesh axes no spec names (``tensor``, ``seq``) to XLA
    inside the call, which nothing but a Mosaic kernel forbids.

    With no ambient mesh or one device it is a plain call, and so it
    is inside somebody else's ``shard_map`` (ring attention, the
    overlapped-reduce steps), where the operands already are one
    device's blocks."""
    mesh = _ambient_mesh()
    if mesh is None:
        return call(*operands)
    shape = operands[split.index(True)].shape
    batch, _ = batch_axes(shape[0])
    heads = False
    if heads_dim is not None:
        n = mesh.shape.get(_HEAD_AXIS, 1)
        heads = n > 1 and all(
            op.shape[heads_dim] // head_size % n == 0
            for op, s in zip(operands, split) if s
        )
    if not batch and not heads:
        return call(*operands)

    def spec_at(dim):
        """Batch rows over the batch axes, dim ``dim`` over the heads'."""
        if dim is None:
            return P(batch or None)
        return P(
            batch or None, *[None] * (dim - 1), _HEAD_AXIS if heads else None
        )

    spec = spec_at(heads_dim)
    out_specs, body = spec, call
    if out_heads_dims is not None:
        out_specs = tuple(spec_at(dim) for dim in out_heads_dims)
    if summed:
        out_specs = tuple(P() if s else spec for s in summed)

        def body(*blocks):
            return tuple(
                jax.lax.psum(o, batch) if s else o
                for o, s in zip(call(*blocks), summed)
            )

    # No names: every mesh axis is manual, shard_map's default.
    named = set()
    if not manual_all:
        named = set(batch) | ({_HEAD_AXIS} if heads else set())
    return jax.shard_map(
        body,
        in_specs=tuple(spec if s else P() for s in split),
        out_specs=out_specs,
        axis_names=frozenset(named),
        check_vma=False,
    )(*operands)


def mesh_slice_blocks(mesh: Mesh, num_slices: int) -> List[List]:
    """The per-slice device blocks of a multi-slice mesh (flat device
    order), for asserting slice purity and for slice-aware ops."""
    flat = list(mesh.devices.flat)
    per_slice = len(flat) // num_slices
    return [
        flat[i * per_slice:(i + 1) * per_slice]
        for i in range(num_slices)
    ]


def single_device_mesh() -> Mesh:
    """A trivial mesh over one device (bench / single-chip paths)."""
    return build_mesh(MeshConfig(), devices=jax.devices()[:1])
