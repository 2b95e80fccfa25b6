"""Device mesh topology (reference: python/paddle/distributed/fleet/base/
topology.py:70 CommunicateTopology / :189 HybridCommunicateGroup).

TPU-native: one global `jax.sharding.Mesh` whose named axes are the
parallelism dimensions (dp, sharding, pp, sep, mp, ep). Axis order follows the
reference's hybrid order (topology.py hybrid_group_names) so that adjacent
ranks share the fastest-varying axis (mp innermost → rides ICI nearest
neighbors, exactly the reference's NCCL ring placement logic).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh

_DEFAULT_MESH: Optional[Mesh] = None
_HYBRID_ORDER = ["dp", "pp", "sharding", "sep", "mp"]


def build_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """Create a named mesh over the device grid. Axis sizes must multiply to
    the device count (singleton axes allowed)."""
    devices = list(devices if devices is not None else jax.devices())
    names = [n for n in axes]
    sizes = [int(axes[n]) for n in names]
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError(f"mesh {axes} needs {total} devices, have {len(devices)}")
    # no reshape fallback: on a TPU a device set mesh_utils cannot lay out
    # (not a sub-box of the physical topology) would otherwise become a
    # mesh whose "neighbours" are not ICI neighbours, silently
    grid = mesh_utils.create_device_mesh(sizes, devices=devices)
    return Mesh(grid, axis_names=tuple(names))


def get_default_mesh() -> Mesh:
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        reset_default_mesh()
    return _DEFAULT_MESH


def set_default_mesh(mesh: Mesh):
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh
    return mesh


def reset_default_mesh():
    """Default: 1-D data-parallel mesh over all devices."""
    global _DEFAULT_MESH
    _DEFAULT_MESH = build_mesh({"dp": jax.device_count()})
    return _DEFAULT_MESH


class CommunicateTopology:
    """nd rank grid helper (reference topology.py:70)."""

    def __init__(self, hybrid_group_names=("data", "pipe", "sharding", "sep", "model"),
                 dims=(1, 1, 1, 1, 1)):
        self._names = list(hybrid_group_names)
        self._dims = list(dims)
        self._world = int(np.prod(self._dims))
        self._grid = np.arange(self._world).reshape(self._dims)

    def get_hybrid_group_names(self):
        return self._names

    def get_dim(self, name):
        return self._dims[self._names.index(name)]

    get_dim_size = get_dim

    def world_size(self):
        return self._world

    def get_rank(self, **kwargs):
        idx = tuple(kwargs[n] for n in self._names)
        return int(self._grid[idx])

    def get_coord(self, rank):
        coords = np.unravel_index(rank, self._dims)
        import collections
        C = collections.namedtuple("Coord", self._names)
        return C(*[int(c) for c in coords])

    def get_axis_list(self, axis_name, index):
        axis = self._names.index(axis_name)
        sl = [slice(None)] * len(self._dims)
        sl[axis] = index
        return sorted(self._grid[tuple(sl)].reshape(-1).tolist())

    def get_comm_list(self, axis_name):
        """All groups along `axis_name`: list of rank lists."""
        axis = self._names.index(axis_name)
        moved = np.moveaxis(self._grid, axis, -1)
        return moved.reshape(-1, self._dims[axis]).tolist()


class HybridCommunicateGroup:
    """reference topology.py:189 — holds per-axis group info; on TPU the
    'groups' are mesh axes of the global mesh rather than NCCL communicators."""

    def __init__(self, topology: CommunicateTopology):
        self._topo = topology
        from .env import global_rank
        self.global_rank = global_rank()
        self._dp_degree = topology.get_dim("data")
        self._pp_degree = topology.get_dim("pipe")
        self._sharding_degree = topology.get_dim("sharding")
        self._sep_degree = topology.get_dim("sep")
        self._mp_degree = topology.get_dim("model")
        axes = {}
        for ref_name, mesh_name in (("data", "dp"), ("pipe", "pp"),
                                    ("sharding", "sharding"), ("sep", "sep"),
                                    ("model", "mp")):
            axes[mesh_name] = topology.get_dim(ref_name)
        # drop singleton axes? keep all — pjit handles size-1 axes fine
        self.mesh = build_mesh(axes) if int(np.prod(list(axes.values()))) == \
            len(jax.devices()) else None
        if self.mesh is not None:
            set_default_mesh(self.mesh)

    # degree queries (reference API)
    def get_data_parallel_world_size(self):
        return self._dp_degree

    def get_model_parallel_world_size(self):
        return self._mp_degree

    def get_pipe_parallel_world_size(self):
        return self._pp_degree

    def get_sharding_parallel_world_size(self):
        return self._sharding_degree

    def get_sep_parallel_world_size(self):
        return self._sep_degree

    def _coord(self):
        return self._topo.get_coord(self.global_rank)

    def get_data_parallel_rank(self):
        return self._coord().data

    def get_model_parallel_rank(self):
        return self._coord().model

    def get_stage_id(self):
        return self._coord().pipe

    def get_sharding_parallel_rank(self):
        return self._coord().sharding

    def get_sep_parallel_rank(self):
        return self._coord().sep

    def topology(self):
        return self._topo

    # group objects (mesh-axis handles)
    def get_data_parallel_group(self):
        from .communication.group import Group
        return Group(self._topo.get_axis_list("data", 0), axis_name="dp")

    def get_model_parallel_group(self):
        from .communication.group import Group
        return Group(self._topo.get_axis_list("model", 0), axis_name="mp")

    def get_pipe_parallel_group(self):
        from .communication.group import Group
        return Group(self._topo.get_axis_list("pipe", 0), axis_name="pp")

    def get_sharding_parallel_group(self):
        from .communication.group import Group
        return Group(self._topo.get_axis_list("sharding", 0), axis_name="sharding")

    def get_sep_parallel_group(self):
        from .communication.group import Group
        return Group(self._topo.get_axis_list("sep", 0), axis_name="sep")

    def get_check_parallel_group(self, *a, **k):
        from .communication.group import Group
        return Group(list(range(self._topo.world_size())), axis_name=None)
