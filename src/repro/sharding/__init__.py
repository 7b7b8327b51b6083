from repro.sharding.logical import (  # noqa: F401
    folded_axis_index,
    mesh_axis_size,
)
