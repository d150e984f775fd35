"""The first-order kernel's share of its roofline: the least time of the
histogram and moments of every study launched in the traced window over the
device time of ``firstorder_packed_batch_pallas`` (``chipbench/kernels.json``).

The work is logical and comes from the plan's shapes: each study's ROI
cropped and padded by one voxel (the plan census's ``roi_shape``), per voxel
8 operations (the mask count 1, the sum 1, the sum of squares for Energy 2,
the centred second moment 3: deviation, square, add; the histogram bin 1)
and 8 bytes (its f32 intensity and f32 mask read once), and per study the
36 statistics written once (32 bins and 4 moments, f32).  No chunk, merge or
one-hot compare is counted, so the count is the same whatever fold computes
the moments, and bucket padding reads as lost share.
"""
import math

from chipbench import metrics_common

KERNEL = "firstorder"
N_BINS = 32  # the configurations' fixed bin count
OPS_PER_VOXEL = 8
BYTES_PER_VOXEL = 8
BYTES_PER_STUDY = 4 * (N_BINS + 4)


def work(roi_shape) -> tuple:
    """(operations, bytes) of one study's histogram and moments."""
    voxels = math.prod(roi_shape)
    return (OPS_PER_VOXEL * voxels,
            BYTES_PER_VOXEL * voxels + BYTES_PER_STUDY)


def read(run):
    calls = [work(m.roi_shape) for plan in run.record.get("plans", [])
             for m in plan.metas if m.shape is not None]
    return metrics_common._share(run, KERNEL, calls)
