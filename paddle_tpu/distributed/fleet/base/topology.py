"""N-D communication topology.

Reference parity: python/paddle/distributed/fleet/base/topology.py —
CommunicateTopology:36 (cartesian rank mesh over axes
["data","pipe","sharding","model"]) and HybridCommunicateGroup:117 (per-axis
comm groups, p2p pipe pairs get_p2p_groups:307). TPU-native: the same rank
math, but each axis additionally names a jax Mesh axis; groups carry
axis_name so collectives lower to XLA collectives on that axis. This unified
axis registry replaces the reference's per-meta-optimizer magic ring ids
(SURVEY.md A.3c).
"""
import collections
import itertools

import numpy as np

from ...collective import new_group
from ...env import get_rank, get_world_size
from ... import topology_runtime

# paddle axis name -> canonical short mesh-axis name
_MESH_AXIS = {'data': 'dp', 'pipe': 'pp', 'sharding': 'sharding',
              'model': 'mp', 'sep': 'sep'}


class CommunicateTopology:
    """Parity: topology.py:36."""

    def __init__(self, hybrid_group_names=("data", "pipe", "sharding",
                                           "model"),
                 dims=(1, 1, 1, 1)):
        self._parallel_names = list(hybrid_group_names)
        self._dims = list(dims)
        self.coordinate = collections.namedtuple(
            'Coordinate', self._parallel_names)
        self._world_size = int(np.prod(self._dims))
        ranges = [range(d) for d in self._dims]
        all_coords = [self.coordinate(*c)
                      for c in itertools.product(*ranges)]
        self._coord2rank = dict(zip(all_coords, range(len(all_coords))))
        self._rank2coord = dict(
            zip(self._coord2rank.values(), self._coord2rank.keys()))

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name):
        return self._dims[self._parallel_names.index(axis_name)]

    get_dim_size = get_dim

    def world_size(self):
        return self._world_size

    def get_rank(self, **args):
        assert len(args) == len(self._dims)
        key = self.coordinate(**args)
        return self._coord2rank[key]

    def get_coord(self, rank):
        return self._rank2coord[rank]

    def get_axis_list(self, axis_name, index):
        axis = self._parallel_names.index(axis_name)
        ranks = [self._coord2rank[c] for c in self._coord2rank
                 if c[axis] == index]
        return sorted(ranks)

    def get_comm_list(self, axis_name):
        """All rank-groups along `axis_name` (one per setting of the other
        axes). Parity: topology.py get_comm_list."""
        other_axes = [n for n in self._parallel_names if n != axis_name]
        ranges = [range(self.get_dim(n)) for n in other_axes]
        all_result = []
        for coord in itertools.product(*ranges):
            fixed = dict(zip(other_axes, coord))
            group = []
            for i in range(self.get_dim(axis_name)):
                fixed[axis_name] = i
                group.append(self.get_rank(**fixed))
            all_result.append(group)
        return all_result

    def get_rank_from_stage(self, global_rank, **kwargs):
        coord = self.get_coord(global_rank)
        tf = coord._replace(**kwargs)._asdict()
        return self.get_rank(**tf)


class HybridCommunicateGroup:
    """Parity: topology.py:117. Builds per-axis Groups; on TPU each Group
    points at the mesh axis, and a single jax Mesh (dp, pp, sharding, mp) is
    registered for the SPMD engines."""

    def __init__(self, topology):
        self._topo = topology
        self.global_rank = get_rank()
        self._dp_degree = self._topo.get_dim('data')
        self._mp_degree = self._topo.get_dim('model')
        self._pp_degree = self._topo.get_dim('pipe')
        self._sharding_degree = self._topo.get_dim('sharding')

        self._data_parallel_id = self._get_parallel_id('data')
        self._model_parallel_id = self._get_parallel_id('model')
        self._sharding_parallel_id = self._get_parallel_id('sharding')
        self.stage_id = self._get_parallel_id('pipe')

        if self.global_rank >= self._topo.world_size():
            raise ValueError("rank outside topology")

        # build groups per axis (parity with _set_comm_group calls)
        self._dp_group, self._dp_comm_group = self._make_group('data')
        self._mp_group, self._mp_comm_group = self._make_group('model')
        self._pp_group, self._pp_comm_group = self._make_group('pipe')
        self._sharding_group, self._sharding_comm_group = \
            self._make_group('sharding')

        # check-group spanning dp+sharding (amp found_inf sync, parity
        # topology.py _set_check_group)
        self._check_group, self._check_comm_group = None, None

        # p2p neighbors for pipeline
        if self._pp_degree > 1:
            self.next_rank = self._topo.get_rank_from_stage(
                self.global_rank, pipe=(self.stage_id + 1) % self._pp_degree)
            self.prev_rank = self._topo.get_rank_from_stage(
                self.global_rank, pipe=(self.stage_id - 1) % self._pp_degree)
        else:
            self.next_rank = self.prev_rank = self.global_rank

        # register the jax mesh for SPMD engines (virtual or real devices)
        self._register_mesh()

    def _register_mesh(self):
        names, sizes = [], []
        for pname in self._topo.get_hybrid_group_names():
            d = self._topo.get_dim(pname)
            names.append(_MESH_AXIS.get(pname, pname))
            sizes.append(d)
        # raises when the topology asks for more devices than there
        # are: the engines must never run on a stale, smaller mesh
        topology_runtime.build_mesh(names, sizes)

    def _get_parallel_id(self, axis):
        coord = self._topo.get_coord(self.global_rank)
        return getattr(coord, axis)

    def _make_group(self, axis):
        parallel_lists = self._topo.get_comm_list(axis)
        mine = None
        for ranks in parallel_lists:
            if self.global_rank in ranks:
                mine = ranks
        g = new_group(ranks=mine or parallel_lists[0],
                      axis_name=_MESH_AXIS.get(axis, axis))
        return mine, g

    # -- parity accessors (topology.py names) -------------------------------
    def get_parallel_mode(self):
        if self._mp_degree == 1 and self._pp_degree == 1 \
                and self._dp_degree == 1 and self._sharding_degree > 1:
            return ParallelMode.SHARDING_PARALLEL
        if self._mp_degree == 1 and self._pp_degree == 1:
            return ParallelMode.DATA_PARALLEL
        if self._mp_degree > 1 and self._pp_degree == 1:
            return ParallelMode.TENSOR_PARALLEL
        if self._pp_degree > 1:
            return ParallelMode.PIPELINE_PARALLEL
        return ParallelMode.DATA_PARALLEL

    def topology(self):
        return self._topo

    def get_global_rank(self):
        return self.global_rank

    # dp
    def get_data_parallel_rank(self):
        return self._data_parallel_id

    def get_data_parallel_world_size(self):
        return self._dp_degree

    def get_data_parallel_group(self):
        return self._dp_comm_group

    def get_data_parallel_group_src_rank(self):
        return self._dp_group[0] if self._dp_group else 0

    # mp
    def get_model_parallel_rank(self):
        return self._model_parallel_id

    def get_model_parallel_world_size(self):
        return self._mp_degree

    def get_model_parallel_group(self):
        return self._mp_comm_group

    def get_model_parallel_group_src_rank(self):
        return self._mp_group[0] if self._mp_group else 0

    # pp
    def get_stage_id(self):
        return self.stage_id

    def get_pipe_parallel_rank(self):
        return self.stage_id

    def get_pipe_parallel_world_size(self):
        return self._pp_degree

    def get_pipe_parallel_group(self):
        return self._pp_comm_group

    def is_first_stage(self):
        return self.stage_id == 0

    def is_last_stage(self):
        return self.stage_id == self._pp_degree - 1

    def get_p2p_groups(self):
        return (self.prev_rank, self.next_rank)

    # sharding
    def get_sharding_parallel_rank(self):
        return self._sharding_parallel_id

    def get_sharding_parallel_world_size(self):
        return self._sharding_degree

    def get_sharding_parallel_group(self):
        return self._sharding_comm_group

    def get_sharding_parallel_group_src_rank(self):
        return self._sharding_group[0] if self._sharding_group else 0

    def get_check_parallel_group(self):
        return self._check_comm_group

    def get_rank_from_stage(self, stage_id, **kwargs):
        return self._topo.get_rank_from_stage(self.global_rank,
                                              pipe=stage_id, **kwargs)


class ParallelMode:
    """Parity: paddle.distributed.ParallelMode."""
    DATA_PARALLEL = 0
    TENSOR_PARALLEL = 1
    PIPELINE_PARALLEL = 2
    SHARDING_PARALLEL = 3
