"""Named-axis process/device topology and the JAX mesh that realizes it.

Reference parity: deepspeed/runtime/pipe/topology.py (ProcessTopology :12,
PipeDataParallelTopology :235, PipeModelDataParallelTopology :246,
PipelineParallelGrid :252). Where the reference builds torch process groups
per axis, here a single ``jax.sharding.Mesh`` carries all axes and the
"groups" become mesh-axis names used by collectives inside jit.
"""
from collections import namedtuple
from itertools import product as cartesian_product

import numpy as np

# Mesh axis-name conventions used across the framework.
DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"
SEQUENCE_AXIS = "sequence"
# hpZ (ZeRO++ hierarchical partitioning): the data axis factored into
# (replica, shard) sub-axes. Shard is INNER (stride 1 in device order →
# ICI-adjacent chips), so the per-step weight all-gathers that cross only
# the shard sub-axis ride the short hop; replica-crossing traffic
# (optimizer-state partition) is the rarer, cheaper-to-amortize one.
DATA_REPLICA_AXIS = "data_replica"
DATA_SHARD_AXIS = "data_shard"


def factor_data_axis(mesh, shard_size):
    """Factor a mesh's ``data`` axis into (``data_replica``,
    ``data_shard``) sub-axes of sizes ``(dp // shard_size, shard_size)``.

    The device assignment is preserved — only the naming changes — so any
    sharding that names BOTH sub-axes (as a tuple) is placement-identical
    to one naming the original ``data`` axis, while shardings naming only
    ``data_shard`` stay within ICI-adjacent groups of ``shard_size``.
    """
    from jax.sharding import Mesh
    axes = list(mesh.axis_names)
    if DATA_AXIS not in axes:
        raise ValueError(
            "mesh {} has no '{}' axis to factor".format(
                dict(mesh.shape), DATA_AXIS))
    dp = int(mesh.shape[DATA_AXIS])
    shard_size = int(shard_size)
    if shard_size <= 1 or dp % shard_size != 0:
        raise ValueError(
            "zero_hierarchical_partition={} must be >1 and divide the "
            "data-parallel degree {}".format(shard_size, dp))
    i = axes.index(DATA_AXIS)
    devices = mesh.devices
    new_shape = devices.shape[:i] + (dp // shard_size, shard_size) + \
        devices.shape[i + 1:]
    new_axes = axes[:i] + [DATA_REPLICA_AXIS, DATA_SHARD_AXIS] + \
        axes[i + 1:]
    return Mesh(devices.reshape(new_shape), tuple(new_axes))


def shard_map_compat(fn, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with replication checking off: callers return
    values they know to be replica-invariant (post-psum/post-gather).
    ``axis_names`` (optional) makes the region manual over just those
    axes."""
    import jax
    kwargs = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kwargs)


def mesh_axis_groups(mesh, axes):
    """Ground-truth device-id groups for a collective spanning ``axes``
    (one axis name or a tuple): vary the named axes, fix every other —
    each returned ``frozenset`` is one replica group a collective over
    those axes addresses. The shard-lint HLO census
    (``analysis/hlo.py``) matches XLA's ``replica_groups`` against
    these to attribute each collective to its mesh axis."""
    import numpy as np
    if isinstance(axes, str):
        axes = (axes,)
    names = list(mesh.axis_names)
    for ax in axes:
        if ax not in names:
            raise ValueError("mesh {} has no axis {!r}".format(
                dict(mesh.shape), ax))
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    order = [i for i, n in enumerate(names) if n not in axes] + \
        [names.index(ax) for ax in axes]
    moved = ids.transpose(order)
    group_elems = int(np.prod([mesh.shape[ax] for ax in axes],
                              dtype=np.int64))
    rows = moved.reshape(-1, group_elems)
    return [frozenset(int(d) for d in row) for row in rows]


def _prime_factors(N):
    """Prime factorization in ascending order (reference topology.py)."""
    if N <= 0:
        raise ValueError("Factorize on non-positive number: {}".format(N))
    primes = []
    while N % 2 == 0:
        primes.append(2)
        N //= 2
    p = 3
    while p * p <= N:
        while N % p == 0:
            primes.append(p)
            N //= p
        p += 2
    if N > 1:
        primes.append(N)
    return primes


class ProcessTopology:
    """Cartesian rank <-> coordinate mapping over named axes.

    The axes are ordered outermost-first: the LAST axis has stride 1 in rank
    order (so put the bandwidth-hungry axis last — the reference makes 'data'
    innermost for the same reason).
    """

    def __init__(self, axes, dims):
        self.axes = list(axes)
        self.dims = list(dims)
        assert len(self.axes) == len(self.dims)
        self.ProcessCoord = namedtuple("ProcessCoord", self.axes)
        self.mapping = {}
        for coord in cartesian_product(*[range(d) for d in self.dims]):
            key = dict(zip(self.axes, coord))
            self.mapping[self.ProcessCoord(**key)] = len(self.mapping)

    def get_rank(self, **coord_kwargs):
        if len(coord_kwargs) != len(self.axes):
            raise ValueError("get_rank() does not support slices, use filter_match()")
        key = self.ProcessCoord(**coord_kwargs)
        assert key in self.mapping, "coord {} not in topology".format(key)
        return self.mapping[key]

    def get_axis_names(self):
        return self.axes

    def get_rank_repr(self, rank, omit_axes=("data", "pipe"), inner_sep="_",
                      outer_sep="-"):
        """String like 'model_00' identifying a rank's non-omitted coords
        (used for checkpoint file naming)."""
        omit_axes = list(omit_axes)
        axes = [a for a in self.get_axis_names() if a not in omit_axes]
        names = []
        for ax in axes:
            ax_rank = getattr(self.get_coord(rank=rank), ax)
            names.append("{}{}{:02d}".format(ax, inner_sep, ax_rank))
        return outer_sep.join(names)

    def get_dim(self, axis):
        if axis not in self.axes:
            return 0
        return self.dims[self.axes.index(axis)]

    def get_coord(self, rank):
        for coord, idx in self.mapping.items():
            if idx == rank:
                return coord
        raise ValueError("rank {} not found in topology".format(rank))

    def get_axis_comm_lists(self, axis):
        """Lists of ranks that vary only along ``axis`` (the reference's
        per-axis communicator groups)."""
        if axis not in self.axes:
            return []
        other_axes = [a for a in self.axes if a != axis]
        lists = []
        for other_coord in cartesian_product(
                *[range(self.get_dim(a)) for a in other_axes]):
            fixed = dict(zip(other_axes, other_coord))
            ranks = [self.get_rank(**{axis: i, **fixed})
                     for i in range(self.get_dim(axis))]
            lists.append(ranks)
        return lists

    def filter_match(self, **filter_kwargs):
        """Ranks whose coordinates match all given axis=value filters."""
        def matches(coord):
            return all(getattr(coord, key) == val
                       for key, val in filter_kwargs.items())
        return [rank for coord, rank in self.mapping.items() if matches(coord)]

    def get_axis_list(self, axis, idx):
        return [rank for coord, rank in self.mapping.items()
                if getattr(coord, axis) == idx]

    def world_size(self):
        return len(self.mapping)

    def __str__(self):
        return str(self.mapping)


class PipeDataParallelTopology(ProcessTopology):
    """['pipe', 'data'] topology: DP innermost to keep gradient reductions on
    the fastest links (reference topology.py:235-241)."""

    def __init__(self, num_pp, num_dp):
        super().__init__(axes=[PIPE_AXIS, DATA_AXIS], dims=[num_pp, num_dp])


class PipeModelDataParallelTopology(ProcessTopology):
    """['pipe', 'data', 'model'] 3D topology (reference topology.py:246)."""

    def __init__(self, num_pp, num_mp, num_dp):
        super().__init__(axes=[PIPE_AXIS, DATA_AXIS, MODEL_AXIS],
                         dims=[num_pp, num_dp, num_mp])


def build_mesh(topology=None, data=None, model=None, pipe=None, devices=None,
               sequence=None):
    """Build a ``jax.sharding.Mesh`` realizing a named-axis topology.

    Axis order follows the topology (outermost first); on real hardware
    ``jax.experimental.mesh_utils`` is used so the innermost axes land on
    ICI-adjacent chips.
    """
    import jax
    from jax.sharding import Mesh

    if topology is not None:
        axes = topology.get_axis_names()
        dims = [topology.get_dim(a) for a in axes]
    else:
        axes, dims = [], []
        for name, size in ((PIPE_AXIS, pipe), (DATA_AXIS, data),
                           (SEQUENCE_AXIS, sequence), (MODEL_AXIS, model)):
            if size is not None and size > 1:
                axes.append(name)
                dims.append(size)
        if not axes:
            axes, dims = [DATA_AXIS], [data or jax.device_count()]

    if devices is None:
        devices = jax.devices()
    n_needed = int(np.prod(dims))
    assert n_needed <= len(devices), \
        "topology needs {} devices, have {}".format(n_needed, len(devices))
    devices = devices[:n_needed]

    try:
        from jax.experimental import mesh_utils
        device_array = mesh_utils.create_device_mesh(tuple(dims),
                                                     devices=devices)
    except Exception:
        device_array = np.array(devices).reshape(tuple(dims))
    return Mesh(device_array, tuple(axes))


class MeshGrid:
    """MPU-compatible view of a mesh+topology.

    Implements the interface the reference delegates to Megatron's ``mpu``
    and to PipelineParallelGrid (reference topology.py:252-455):
    ``get_{data,model,pipe}_parallel_{rank,world_size}`` plus stage helpers.
    "Groups" are mesh axis names — collectives inside jit take the axis name.
    """

    def __init__(self, topology=None, mesh=None, process_rank=None):
        import jax
        if topology is None:
            topology = PipeDataParallelTopology(num_pp=1,
                                                num_dp=jax.device_count())
        self._topo = topology
        self.mesh = mesh if mesh is not None else build_mesh(topology)
        # In SPMD-land every process runs the same program; "rank" is only
        # meaningful for IO/checkpoint naming. Use process_index by default.
        self.global_rank = (process_rank if process_rank is not None
                            else jax.process_index())
        self.world_size = topology.world_size()

        self.data_parallel_size = max(topology.get_dim(DATA_AXIS), 1)
        self.pipe_parallel_size = max(topology.get_dim(PIPE_AXIS), 1)
        self.model_parallel_size = max(topology.get_dim(MODEL_AXIS), 1)
        assert self._is_grid_valid(), "Invalid Grid"

    def _is_grid_valid(self):
        ranks = self.data_parallel_size * self.pipe_parallel_size * \
            self.model_parallel_size
        return ranks == self._topo.world_size()

    @property
    def topology(self):
        return self._topo

    # --- stage/coordinate helpers (device-coordinate based, for IO naming) ---
    def _coord(self, rank=None):
        rank = self.global_rank if rank is None else rank
        return self._topo.get_coord(rank)

    def get_stage_id(self, rank=None):
        if PIPE_AXIS not in self._topo.get_axis_names():
            return 0
        return getattr(self._coord(rank), PIPE_AXIS)

    def get_pipe_parallel_rank(self, rank=None):
        return self.get_stage_id(rank)

    def get_pipe_parallel_world_size(self):
        return self.pipe_parallel_size

    def get_data_parallel_rank(self, rank=None):
        if DATA_AXIS not in self._topo.get_axis_names():
            return 0
        return getattr(self._coord(rank), DATA_AXIS)

    def get_data_parallel_world_size(self):
        return self.data_parallel_size

    def get_model_parallel_rank(self, rank=None):
        if MODEL_AXIS not in self._topo.get_axis_names():
            return 0
        return getattr(self._coord(rank), MODEL_AXIS)

    def get_model_parallel_world_size(self):
        return self.model_parallel_size

    def get_global_rank(self):
        return self.global_rank

    # Axis names for collectives inside jit.
    def get_data_parallel_group(self):
        return DATA_AXIS

    def get_model_parallel_group(self):
        return MODEL_AXIS

    def get_pipe_parallel_group(self):
        return PIPE_AXIS

    def is_first_stage(self, rank=None):
        return self.get_stage_id(rank) == 0

    def is_last_stage(self, rank=None):
        return self.get_stage_id(rank) == self.pipe_parallel_size - 1

    def stage_to_global(self, stage_id, data=0, model=0):
        kwargs = {}
        axes = self._topo.get_axis_names()
        if PIPE_AXIS in axes:
            kwargs[PIPE_AXIS] = stage_id
        if DATA_AXIS in axes:
            kwargs[DATA_AXIS] = data
        if MODEL_AXIS in axes:
            kwargs[MODEL_AXIS] = model
        return self._topo.get_rank(**kwargs)
