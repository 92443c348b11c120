"""Distributed search: device meshes, island-model sharding, collectives.

The population dimension is sharded over the mesh's ``i`` (island) axis;
fitness statistics ride ``psum`` and elite migration rides ``ppermute`` —
all ICI traffic, never the host (SURVEY.md section 5.8's TPU-native
communication design).
"""

from namazu_tpu.parallel.mesh import make_mesh, default_device_count
from namazu_tpu.parallel.islands import (
    IslandState,
    init_island_state,
    make_fused_island_step,
)

__all__ = [
    "make_mesh",
    "default_device_count",
    "IslandState",
    "init_island_state",
    "make_fused_island_step",
]
