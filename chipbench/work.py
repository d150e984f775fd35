"""Operations and bytes a kernel's call needs, from its logical sizes.

These counts are the benchmark's yardstick for roofline shares.  They
follow from what a call computes -- the cells of the staged ROI, the
triangles of its surface, the vertices handed to the pair sweep -- and
never from the bucket, block, variant or padding an implementation runs
at, so padding and redundant work read as lost share, and a change of
variant or block leaves the count as it is.

Marching cubes, per real cell of the ROI cropped and padded by one voxel
(``prod(roi_dims + 1)`` cells): 16 operations to form the corner case
(8 compares, 8 shifted adds); per surface triangle 39 (two edge vectors 6,
their cross product 9, its norm 5, the half 1, the second cross product
9, the dot 5, the sixth 1, two accumulations 2).  Bytes: the staged f32
mask read once, ``4 * prod(roi_dims + 2)``, and 8 bytes written.

Diameter sweep over ``m`` vertices: every unordered pair once,
``m (m - 1) / 2`` pairs of 14 operations (3 differences, 3 squares, 4
sums for the 3-D and three plane distances, 4 running maxima).  Bytes:
each vertex's three f32 coordinates and its mask read once, ``16 m``, and
16 bytes written.
"""
from __future__ import annotations

import math

MC_OPS_PER_CELL = 16
MC_OPS_PER_TRIANGLE = 39
DIAMETER_OPS_PER_PAIR = 14


def mc(roi_dims, triangles: int) -> tuple:
    """(operations, bytes) of marching cubes over one ROI."""
    cells = math.prod(d + 1 for d in roi_dims)
    voxels = math.prod(d + 2 for d in roi_dims)
    return (MC_OPS_PER_CELL * cells + MC_OPS_PER_TRIANGLE * triangles,
            4 * voxels + 8)


def diameter(m: int) -> tuple:
    """(operations, bytes) of the four-way farthest-pair sweep."""
    return DIAMETER_OPS_PER_PAIR * m * (m - 1) // 2, 16 * m + 16


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time one call can take on a chip with ``peaks``, and the
    bound that sets it (``compute`` or ``memory``)."""
    compute = ops / peaks["peak_flops_bf16"]
    memory = nbytes / peaks["hbm_bw"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
