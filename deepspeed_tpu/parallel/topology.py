"""Process/device topology over a JAX device mesh.

TPU-native analogue of the reference's ``deepspeed/runtime/pipe/topology.py``
(``ProcessTopology`` at topology.py:12, ``PipelineParallelGrid`` at 251) and
``deepspeed/utils/groups.py``. Instead of building torch process groups, we
build one ``jax.sharding.Mesh`` whose named axes stand in for process
groups; collectives address axes by name inside ``shard_map``/``pjit``.

The rank bookkeeping here is array-based: ranks form an ndarray of shape
``dims`` (row-major, so the last axis varies fastest, matching how
``jax.sharding.Mesh`` linearises its device grid), and every query is an
indexing or reduction over that array rather than a dict walk.

Canonical axis order (outermost → innermost):

    ('pipe', 'data', 'expert', 'sequence', 'tensor')

- ``pipe``     — pipeline stages (cross-slice/DCN friendly).
- ``data``     — pure data parallel replicas.
- ``expert``   — expert parallelism; part of the data-parallel set for
                 non-expert params (DeepSpeed carves EP groups out of DP,
                 groups.py:114-254).
- ``sequence`` — Ulysses sequence parallelism; part of the ZeRO sharding
                 set (DeepSpeed's ``seq_data_parallel_group``).
- ``tensor``   — Megatron-style tensor parallelism; innermost so its
                 heavy collectives ride the fastest ICI dimension.
"""

import numpy as np

MESH_AXES = ("pipe", "data", "expert", "sequence", "tensor")

# Axes over which dense (non-expert) model state is sharded by ZeRO.
ZERO_AXES = ("data", "expert", "sequence")
# Axes over which the global batch is sharded.
BATCH_AXES = ("data", "expert", "sequence")
# Axes over which expert parameters' ZeRO sharding happens.
EXPERT_ZERO_AXES = ("data", "sequence")


class ProcessTopology:
    """Named-axis coordinate system over a linear rank space.

    ``ProcessTopology(axes=['x', 'y'], dims=[2, 2])`` arranges ranks 0..3 in
    a row-major 2x2 grid: rank = x*2 + y, i.e. the trailing axis is the
    fastest-varying one. All lookups go through ``self.grid``, an int ndarray
    of shape ``dims`` holding the global rank at each coordinate.
    """

    def __init__(self, axes, dims):
        if len(axes) != len(dims):
            raise ValueError(f"axes {axes} and dims {dims} must have equal length")
        self.axes = list(axes)
        self.dims = list(int(d) for d in dims)
        self.grid = np.arange(int(np.prod(self.dims))).reshape(self.dims)

    def _axis_index(self, axis):
        try:
            return self.axes.index(axis)
        except ValueError:
            raise ValueError(f"unknown axis {axis!r}; topology axes are {self.axes}") from None

    def _index_for(self, coord_kwargs):
        """Build an ndarray index tuple from axis->value kwargs, slice(None)
        for unspecified axes."""
        for name, val in coord_kwargs.items():
            if name not in self.axes:
                raise ValueError(f"unknown axis {name!r}; topology axes are {self.axes}")
            dim = self.get_dim(name)
            if not 0 <= int(val) < dim:
                raise ValueError(f"coordinate {name}={val} out of range [0, {dim})")
        return tuple(coord_kwargs.get(a, slice(None)) for a in self.axes)

    def get_rank(self, **coord_kwargs):
        """Global rank at a fully-specified coordinate."""
        if len(coord_kwargs) != len(self.axes):
            missing = [a for a in self.axes if a not in coord_kwargs]
            raise ValueError(f"get_rank needs every axis; missing {missing} (use filter_match for slices)")
        return int(self.grid[self._index_for(coord_kwargs)])

    def get_axis_names(self):
        return list(self.axes)

    def get_coord(self, rank):
        """Coordinate of ``rank`` as an attribute-accessible object."""
        idx = np.unravel_index(int(rank), self.grid.shape)
        return _Coord(self.axes, [int(i) for i in idx])

    def get_rank_repr(self, rank, omit_axes=("data", "pipe"), inner_sep="_", outer_sep="-"):
        """Stable string id for a rank, e.g. for checkpoint shard names."""
        coord = self.get_coord(rank)
        parts = [f"{a}{inner_sep}{getattr(coord, a):02d}" for a in self.axes if a not in set(omit_axes)]
        return outer_sep.join(parts)

    def get_dim(self, axis):
        if axis not in self.axes:
            return 0
        return self.dims[self._axis_index(axis)]

    def get_axis_comm_lists(self, axis):
        """Rank groups that communicate along ``axis``: move that axis last,
        flatten everything else — each row is one group."""
        if axis not in self.axes:
            return []
        rolled = np.moveaxis(self.grid, self._axis_index(axis), -1)
        return rolled.reshape(-1, self.get_dim(axis)).tolist()

    def filter_match(self, **filter_kwargs):
        """Ranks whose coordinates match every given axis=value constraint."""
        sub = self.grid[self._index_for(filter_kwargs)]
        return sorted(int(r) for r in np.asarray(sub).ravel())

    def get_axis_list(self, axis, idx):
        """Ranks whose coordinate along ``axis`` equals ``idx``."""
        return self.filter_match(**{axis: idx})

    def world_size(self):
        return int(self.grid.size)

    def __str__(self):
        coords = ", ".join(f"{self.get_coord(r)}={r}" for r in range(self.world_size()))
        return f"ProcessTopology({coords})"


class _Coord:
    """Lightweight attribute bag for a topology coordinate."""

    __slots__ = ("_axes", "_values")

    def __init__(self, axes, values):
        object.__setattr__(self, "_axes", tuple(axes))
        object.__setattr__(self, "_values", tuple(values))

    def __getattr__(self, name):
        try:
            return self._values[self._axes.index(name)]
        except ValueError:
            raise AttributeError(name) from None

    def _asdict(self):
        return dict(zip(self._axes, self._values))

    def __iter__(self):
        return iter(self._values)

    def __eq__(self, other):
        try:
            return tuple(self) == tuple(other)
        except TypeError:
            return NotImplemented

    def __hash__(self):
        return hash(self._values)

    def __repr__(self):
        inner = ", ".join(f"{a}={v}" for a, v in zip(self._axes, self._values))
        return f"Coord({inner})"


class PipeDataParallelTopology(ProcessTopology):
    """A topology specialization for hybrid data and pipeline parallelism."""

    def __init__(self, num_pp, num_dp):
        super().__init__(axes=["pipe", "data"], dims=[num_pp, num_dp])


class PipeModelDataParallelTopology(ProcessTopology):
    """A topology for hybrid pipeline, model, and data parallelism."""

    def __init__(self, num_pp, num_mp, num_dp):
        super().__init__(axes=["pipe", "data", "model"], dims=[num_pp, num_dp, num_mp])


def make_mesh_topology(world_size=None,
                       pipe=1,
                       data=-1,
                       expert=1,
                       sequence=1,
                       tensor=1,
                       devices=None,
                       allow_split_physical_axes=True):
    """Build a ``jax.sharding.Mesh`` with the canonical axis layout.

    One axis may be -1 and is inferred from the device count. The device
    assignment is delegated to ``jax.make_mesh``, which lays axes out so
    that inner axes map to physically adjacent devices (ICI rings).
    """
    import jax

    if devices is None:
        devices = jax.devices()
    ndev = len(devices)
    dims = {"pipe": pipe, "data": data, "expert": expert, "sequence": sequence, "tensor": tensor}
    unknown = [k for k, v in dims.items() if v == -1]
    assert len(unknown) <= 1, f"only one mesh axis may be -1, got {dims}"
    known = int(np.prod([v for v in dims.values() if v != -1]))
    if unknown:
        assert ndev % known == 0, f"device count {ndev} not divisible by {known}"
        dims[unknown[0]] = ndev // known
    total = int(np.prod(list(dims.values())))
    assert total == ndev, (f"mesh {dims} requires {total} devices but {ndev} are available")

    shape = tuple(dims[a] for a in MESH_AXES)
    # Auto axis types: classic pjit-style sharding propagation (the
    # jax 0.9 default of Explicit would demand sharding-typed programs).
    axis_types = (jax.sharding.AxisType.Auto,) * len(MESH_AXES)
    return jax.make_mesh(shape, MESH_AXES, axis_types=axis_types, devices=devices)
