"""Device-mesh topology: the TPU-native replacement for DeepSpeed process groups.

Reference analogues:
  - ``deepspeed/utils/groups.py:53-707`` (DP/TP/EP/SP group construction)
  - ``deepspeed/runtime/pipe/topology.py:12,244,251`` (ProcessTopology /
    PipeModelDataParallelTopology / PipelineParallelGrid)

Instead of building torch.distributed process groups, we build a single
``jax.sharding.Mesh`` with named axes.  Every "group" in DeepSpeed maps to a
mesh axis (or a tuple of axes) here; XLA collectives over a named axis are the
group collectives.

Axis semantics (sizes multiply to the device count):

  ====== ===========================================================
  pipe   pipeline-parallel stages (PipelineModule)
  data   pure data parallel / ZeRO partitioning ("dp")
  expert expert-parallel sub-axis of data parallelism (MoE ``ep_size``)
  seq    Ulysses/ring sequence parallelism ("sp")
  tensor tensor (model) parallelism ("tp"/"mp")
  ====== ===========================================================

Group mapping (DeepSpeed name -> mesh axes):

  data_parallel_group          -> ("data", "expert")   # batch sharding axes
  expert_parallel_group        -> ("expert",)
  expert_data_parallel_group   -> ("data",)
  sequence_parallel_group      -> ("seq",)
  tensor_parallel_group        -> ("tensor",)
  pipe_parallel_group          -> ("pipe",)
  model_parallel_group         -> ("pipe", "tensor")
  zero_partition_group         -> ("data", "expert", "seq")  # ZeRO shards over full DP×SP

Axis order is chosen for ICI locality: "tensor" innermost (fastest-varying
device index, shortest links), "pipe" outermost.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

PIPE = "pipe"
DATA_OUTER = "data_outer"  # MiCS replica groups (size dp/zero_shard_size)
DATA = "data"
EXPERT = "expert"
SEQ = "seq"
TENSOR = "tensor"

#: Canonical outer→inner axis order of every mesh built here.
AXIS_ORDER: Tuple[str, ...] = (PIPE, DATA_OUTER, DATA, EXPERT, SEQ, TENSOR)

#: DeepSpeed group name → mesh axes.
GROUP_AXES: Dict[str, Tuple[str, ...]] = {
    "data_parallel": (DATA_OUTER, DATA, EXPERT),
    "expert_parallel": (EXPERT,),
    "expert_data_parallel": (DATA_OUTER, DATA),
    "sequence_parallel": (SEQ,),
    "sequence_data_parallel": (DATA_OUTER, DATA, EXPERT, SEQ),
    "tensor_parallel": (TENSOR,),
    "model_parallel": (PIPE, TENSOR),
    "pipe_parallel": (PIPE,),
    #: ZeRO shards over the INNER data axes only; with zero_shard_size set
    #: (MiCS, runtime/zero/mics.py:64) the outer axis replicates shards and
    #: gradient allreduce spans it (allreduce_mics_shard_grads :432).
    "zero_partition": (DATA, EXPERT, SEQ),
    "zero_replica": (DATA_OUTER,),
    "world": AXIS_ORDER,
}


class ProcessTopology:
    """Named-axes cartesian rank grid (reference: runtime/pipe/topology.py:12).

    Pure-python coordinate bookkeeping over flat rank ids; used by the pipeline
    partitioner, checkpoint naming, and the launcher.  ``axes`` is outer→inner.
    """

    def __init__(self, axes: Sequence[str], dims: Sequence[int]):
        if len(axes) != len(dims):
            raise ValueError("axes and dims must have equal length")
        self.axes = tuple(axes)
        self.dims = tuple(int(d) for d in dims)
        self._strides = []
        stride = 1
        for d in reversed(self.dims):
            self._strides.append(stride)
            stride *= d
        self._strides = list(reversed(self._strides))

    def world_size(self) -> int:
        return int(np.prod(self.dims)) if self.dims else 1

    def get_dim(self, axis: str) -> int:
        return self.dims[self.axes.index(axis)]

    def get_rank(self, **coords: int) -> int:
        if set(coords) != set(self.axes):
            raise ValueError(f"need all coords {self.axes}, got {tuple(coords)}")
        return sum(coords[a] * s for a, s in zip(self.axes, self._strides))

    def get_coord(self, rank: int):
        coord = {}
        for axis, stride, dim in zip(self.axes, self._strides, self.dims):
            coord[axis] = (rank // stride) % dim
        return dataclasses.make_dataclass("Coord", coord.keys())(**coord)

    def get_axis_comm_lists(self, axis: str) -> List[List[int]]:
        """Lists of ranks that differ only along ``axis`` (a "process group")."""
        if axis not in self.axes:
            return []
        others = [a for a in self.axes if a != axis]
        lists = []
        for combo in np.ndindex(*[self.get_dim(a) for a in others]):
            fixed = dict(zip(others, (int(c) for c in combo)))
            ranks = [self.get_rank(**{axis: i, **fixed}) for i in range(self.get_dim(axis))]
            lists.append(ranks)
        return lists

    def filter_match(self, **filter_kwargs: int) -> List[int]:
        out = []
        for rank in range(self.world_size()):
            coord = self.get_coord(rank)
            if all(getattr(coord, k) == v for k, v in filter_kwargs.items()):
                out.append(rank)
        return out

    def get_axis_list(self, axis: str, idx: int) -> List[int]:
        return self.filter_match(**{axis: idx})

    def __repr__(self) -> str:  # pragma: no cover
        return f"ProcessTopology(axes={self.axes}, dims={self.dims})"


class PipeModelDataParallelTopology(ProcessTopology):
    """3D pipe×model(tensor)×data grid (reference: runtime/pipe/topology.py:244)."""

    def __init__(self, num_pp: int, num_mp: int, num_dp: int):
        super().__init__(axes=[PIPE, DATA, TENSOR], dims=[num_pp, num_dp, num_mp])


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Parallelism degrees; sizes not given default to 1, data absorbs the rest.

    ``zero_shard_size`` (MiCS ``mics_shard_size`` / hpZ partition size): caps
    the ZeRO shard group — the data dimension splits into
    (data_outer × data) with data = zero_shard_size; shards replicate across
    data_outer.
    """

    pipe: int = 1
    data: int = -1  # -1: infer from device count
    expert: int = 1
    seq: int = 1
    tensor: int = 1
    zero_shard_size: int = -1  # -1: shard over the full data extent

    def resolve(self, n_devices: int) -> Dict[str, int]:
        dims = {PIPE: self.pipe, DATA_OUTER: 1, DATA: self.data,
                EXPERT: self.expert, SEQ: self.seq, TENSOR: self.tensor}
        fixed = int(np.prod([d for k, d in dims.items() if d > 0 and k != DATA]))
        if self.data == -1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"device count {n_devices} not divisible by pipe*expert*seq*tensor={fixed}")
            dims[DATA] = n_devices // fixed
        if self.zero_shard_size > 0:
            if dims[DATA] % self.zero_shard_size != 0:
                raise ValueError(
                    f"data dim {dims[DATA]} not divisible by zero_shard_size "
                    f"{self.zero_shard_size}")
            dims[DATA_OUTER] = dims[DATA] // self.zero_shard_size
            dims[DATA] = self.zero_shard_size
        total = int(np.prod(list(dims.values())))
        if total != n_devices:
            raise ValueError(f"mesh dims {dims} product {total} != device count {n_devices}")
        return dims


class MeshTopology:
    """Owns the global ``jax.sharding.Mesh`` and group-name → axis resolution.

    This is the object the engine, ZeRO shardings, MoE, Ulysses, and the
    pipeline engine all consult.  One instance per training job.
    """

    def __init__(
        self,
        config: Optional[TopologyConfig] = None,
        devices: Optional[Sequence[Any]] = None,
        axis_types: Optional[Dict[str, Any]] = None,
    ):
        import jax
        from jax.sharding import Mesh

        self.config = config or TopologyConfig()
        if devices is None:
            devices = jax.devices()
        self.dims = self.config.resolve(len(devices))
        shape = tuple(self.dims[a] for a in AXIS_ORDER)
        device_grid = np.asarray(devices).reshape(shape)
        self.mesh = Mesh(device_grid, AXIS_ORDER)
        self.process_topology = ProcessTopology(AXIS_ORDER, shape)

    # -------------------------------------------------------------- #
    # Group resolution (deepspeed.utils.groups equivalents)
    # -------------------------------------------------------------- #
    def axes_for(self, group: str) -> Tuple[str, ...]:
        if group not in GROUP_AXES:
            raise KeyError(f"unknown group {group!r}; known: {sorted(GROUP_AXES)}")
        return GROUP_AXES[group]

    def group_size(self, group: str) -> int:
        return int(np.prod([self.dims[a] for a in self.axes_for(group)]))

    # Named accessors mirroring deepspeed/utils/groups.py
    def get_data_parallel_world_size(self) -> int:
        return self.group_size("data_parallel")

    def get_sequence_parallel_world_size(self) -> int:
        return self.group_size("sequence_parallel")

    def get_tensor_parallel_world_size(self) -> int:
        return self.group_size("tensor_parallel")

    def get_expert_parallel_world_size(self) -> int:
        return self.group_size("expert_parallel")

    def get_pipe_parallel_world_size(self) -> int:
        return self.group_size("pipe_parallel")

    def world_size(self) -> int:
        return self.mesh.size

    # -------------------------------------------------------------- #
    # Sharding helpers
    # -------------------------------------------------------------- #
    def named_sharding(self, *spec: Any):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec())

    def batch_spec(self):
        """PartitionSpec for a [batch, seq, ...] input array."""
        from jax.sharding import PartitionSpec

        batch_axes = tuple(a for a in (DATA_OUTER, DATA, EXPERT)
                           if self.dims[a] > 1) or (DATA,)
        seq_axis = SEQ if self.dims[SEQ] > 1 else None
        return PartitionSpec(batch_axes, seq_axis)

    def zero_axes(self) -> Tuple[str, ...]:
        """Axes over which ZeRO partitions params/grads/optimizer state."""
        return tuple(a for a in self.axes_for("zero_partition") if self.dims[a] > 1)

    # -------------------------------------------------------------- #
    # Slice model (ICI vs DCN) — hierarchical collectives
    # -------------------------------------------------------------- #
    def set_cross_slice_axes(self, axes: Optional[Sequence[str]]) -> None:
        """Explicit override of which mesh axes cross a slice (DCN)
        boundary — for the CPU sim and tests, or when the config says so
        (``overlap.cross_slice_axes``).  ``None`` restores derivation."""
        if axes is not None:
            bad = sorted(set(axes) - set(AXIS_ORDER))
            if bad:
                raise ValueError(f"unknown mesh axes {bad}; "
                                 f"known: {list(AXIS_ORDER)}")
            axes = tuple(a for a in AXIS_ORDER if a in set(axes))
        self._cross_slice_override = axes

    def cross_slice_axes(self) -> Tuple[str, ...]:
        """Mesh axes whose neighbors live in a DIFFERENT TPU slice — hops
        along these cross DCN, not ICI (the slow domain of the 2-hop
        hierarchical collectives in ``runtime/comm/hierarchical.py``).

        Resolution order: :meth:`set_cross_slice_axes` override →
        ``DSTPU_CROSS_SLICE_AXES`` env (comma list; how the CPU sim and the
        comm_sweep bench model a multislice job) → derived from each
        device's ``slice_index`` (multislice TPU runtimes expose it; absent
        or uniform → single slice, no cross axes).  Only nontrivial axes
        are ever returned."""
        import os

        override = getattr(self, "_cross_slice_override", None)
        if override is None:
            env = os.environ.get("DSTPU_CROSS_SLICE_AXES", "").strip()
            if env:
                override = tuple(a.strip() for a in env.split(",")
                                 if a.strip())
                bad = sorted(set(override) - set(AXIS_ORDER))
                if bad:
                    raise ValueError(
                        f"DSTPU_CROSS_SLICE_AXES names unknown axes {bad}; "
                        f"known: {list(AXIS_ORDER)}")
        if override is not None:
            return tuple(a for a in AXIS_ORDER
                         if a in set(override) and self.dims[a] > 1)
        return self._derived_cross_slice_axes()

    def _derived_cross_slice_axes(self) -> Tuple[str, ...]:
        grid = np.asarray(self.mesh.devices)
        slice_ids = np.asarray(
            [getattr(d, "slice_index", None) for d in grid.ravel()],
            dtype=object).reshape(grid.shape)
        if all(s is None for s in slice_ids.ravel()) or \
                len({s for s in slice_ids.ravel()}) <= 1:
            return ()
        out = []
        for k, axis in enumerate(AXIS_ORDER):
            if self.dims[axis] <= 1:
                continue
            first = np.take(slice_ids, 0, axis=k)
            if any((np.take(slice_ids, i, axis=k) != first).any()
                   for i in range(1, grid.shape[k])):
                out.append(axis)
        return tuple(out)

    def slice_axes(self) -> Tuple[str, ...]:
        """Nontrivial mesh axes fully inside one slice (all hops ride
        ICI)."""
        cross = set(self.cross_slice_axes())
        return tuple(a for a in AXIS_ORDER
                     if self.dims[a] > 1 and a not in cross)

    def __repr__(self) -> str:  # pragma: no cover
        return f"MeshTopology({self.dims})"


def compat_shard_map(f, mesh, in_specs, out_specs, manual_axes=None):
    """``jax.shard_map`` with this repo's conventions: ``check_vma`` off and
    ``manual_axes`` (``None`` = fully manual) spelled as ``axis_names``.

    A partial-manual map (``manual_axes`` a strict subset of the mesh axes)
    must be called under ``jax.jit``: jax 0.9's eager implementation
    rejects it (its internal un-match names every mesh axis in
    ``out_specs``).  Every step builder already jits; tests wrap theirs.
    """
    import jax

    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=False)
    if manual_axes is not None:
        kwargs["axis_names"] = set(manual_axes)
    return jax.shard_map(f, **kwargs)


def shard_map_context(topo: "MeshTopology"):
    """(mesh, already_manual_axes) for building a possibly-nested shard_map.

    Inside an enclosing partial-manual region (e.g. the explicit-comm train
    step, manual over the data axes) jax requires nested shard_maps to be
    built against the *context* abstract mesh — its axis_types record which
    axes are already Manual — and to name only still-Auto axes.  At top
    level the concrete mesh is the right thing.
    """
    import jax

    try:
        manual_t = jax.sharding.AxisType.Manual
        am = jax.sharding.get_abstract_mesh()
        types = getattr(am, "axis_types", None)
        if types is not None and any(t == manual_t for t in types):
            already = {n for n, t in zip(am.axis_names, types)
                       if t == manual_t}
            return am, already
    except Exception:  # noqa: BLE001 - introspection is best-effort
        pass
    return topo.mesh, set()


def shard_kernel(fn, in_specs, out_specs):
    """``fn`` (a Pallas kernel call) made safe inside a multi-device jitted
    program.

    Mosaic kernels cannot be partitioned by GSPMD — lowering a multi-chip
    step that calls one raises "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map" — so the call must sit
    in a ``shard_map`` that is manual over every mesh axis and hands the
    kernel its device-local block.  ``in_specs``/``out_specs`` (one
    ``PartitionSpec`` per operand / for the single output) say how the
    caller lays the operands out; GSPMD reshards to them at the boundary
    (e.g. the ZeRO-3 gather of a weight stored sharded over ``data``).
    An axis named in ``out_specs`` must sit on an input dimension of the
    same size (batch rows, heads, weight columns), so divisibility is
    decided from the inputs alone.

    Adapts to what it can observe at trace time:

      * no global topology, or a one-device mesh: ``fn`` runs as is;
      * an axis that does not divide a dimension it is put on (batch 1 on
        four data shards) is dropped from the specs — that operand is
        replicated over the axis and every shard computes it whole;
      * inside a region already manual over some axes (the explicit-comm
        step), those axes are dropped and the map covers the rest.
    """
    from jax.sharding import PartitionSpec

    def names_of(entry):
        if entry is None:
            return ()
        return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)

    def mapped(*args):
        topo = _TOPOLOGY
        if topo is None or topo.mesh.size <= 1:
            return fn(*args)
        mesh, already = shard_map_context(topo)
        manual = {a for a in mesh.axis_names if a not in already}
        if not manual:
            return fn(*args)
        unusable = {a for a in manual if topo.dims[a] <= 1} | set(already)
        for spec, x in zip(in_specs, args):
            for dim, entry in zip(x.shape, spec):
                names = [a for a in names_of(entry) if a not in unusable]
                if dim % int(np.prod([topo.dims[a] for a in names] or [1])):
                    unusable.update(names)

        def fit(spec):
            dims = []
            for entry in spec:
                names = tuple(a for a in names_of(entry)
                              if a not in unusable)
                dims.append(names if len(names) > 1
                            else (names[0] if names else None))
            return PartitionSpec(*dims)

        return compat_shard_map(
            fn, mesh, tuple(fit(s) for s in in_specs), fit(out_specs),
            manual_axes=manual)(*args)

    return mapped


def mesh_shape_str(dims: Dict[str, int]) -> str:
    """Mesh dims -> compact ``axis:size`` string (``data:4,tensor:2``) —
    the wire format of ``DSTPU_ELASTIC_MESH_SHAPE``.  Trivial axes are
    elided; an all-trivial mesh renders its world size on ``data``.  A
    MiCS mesh (``data_outer`` > 1) renders as the FULL data extent plus
    ``zero_shard:<inner>``, mirroring how :class:`TopologyConfig` spells
    it (``zero_shard_size``), so the string parses back losslessly."""
    data_outer = int(dims.get(DATA_OUTER, 1))
    parts = []
    for a, n in dims.items():
        n = int(n)
        if a == DATA_OUTER or a not in AXIS_ORDER or n <= 1:
            continue
        if a == DATA and data_outer > 1:
            parts.append(f"{DATA}:{n * data_outer}")
            parts.append(f"zero_shard:{n}")
        else:
            parts.append(f"{a}:{n}")
    if data_outer > 1 and not any(p.startswith(f"{DATA}:") for p in parts):
        # outer replication over a trivial inner data axis
        parts.insert(0, f"zero_shard:{int(dims.get(DATA, 1))}")
        parts.insert(0, f"{DATA}:{data_outer * int(dims.get(DATA, 1))}")
    if not parts:
        total = int(np.prod([int(n) for n in dims.values()])) if dims else 1
        parts = [f"{DATA}:{total}"]
    return ",".join(parts)


def parse_mesh_shape(text: str) -> TopologyConfig:
    """``data:4,tensor:2`` (or a bare world size ``8``) -> TopologyConfig.

    The inverse of :func:`mesh_shape_str`; how a restarted worker turns the
    elastic agent's re-planned shape into its mesh."""
    text = (text or "").strip()
    if not text:
        raise ValueError("empty mesh shape")
    if text.isdigit():
        return TopologyConfig(data=int(text))
    field_by_axis = {PIPE: "pipe", DATA: "data", EXPERT: "expert",
                     SEQ: "seq", TENSOR: "tensor",
                     "zero_shard": "zero_shard_size"}
    kw: Dict[str, int] = {}
    for part in text.split(","):
        axis, _, size = part.partition(":")
        axis = axis.strip()
        if axis not in field_by_axis:
            raise ValueError(f"unknown mesh axis {axis!r} in {text!r}; "
                             f"known: {sorted(field_by_axis)}")
        kw[field_by_axis[axis]] = int(size)
    if "data" not in kw:
        kw["data"] = -1   # absorb the remaining devices, as usual
    return TopologyConfig(**kw)


def topology_config_from_env() -> Optional[TopologyConfig]:
    """The elastic agent's re-planned mesh, if this worker was restarted
    with ``--allow-reshape`` onto different capacity (None otherwise)."""
    import os

    text = os.environ.get("DSTPU_ELASTIC_MESH_SHAPE")
    return parse_mesh_shape(text) if text else None


_TOPOLOGY: Optional[MeshTopology] = None


def initialize_mesh(
    config: Optional[TopologyConfig] = None,
    devices: Optional[Sequence[Any]] = None,
    force: bool = False,
) -> MeshTopology:
    """Create (or return) the global mesh topology.

    Reference analogue: ``deepspeed.utils.groups.initialize`` +
    ``comm/comm.py:609 initialize_mesh_device``.
    """
    global _TOPOLOGY
    if _TOPOLOGY is None or force:
        _TOPOLOGY = MeshTopology(config, devices)
    return _TOPOLOGY


def get_topology() -> MeshTopology:
    if _TOPOLOGY is None:
        return initialize_mesh()
    return _TOPOLOGY


def reset_topology() -> None:
    global _TOPOLOGY
    _TOPOLOGY = None
