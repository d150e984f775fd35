"""Share of the staged mask voxels that are shape-bucket padding, weighted
by voxels, over the windows launched in the measured window (the program's
plan census)."""
import math


def read(run):
    roi = pad = 0
    for plan in run.record.get("plans", []):
        for m in plan.metas:
            if m.shape is not None:
                roi += math.prod(m.roi_shape)
                pad += math.prod(m.shape)
    return 100.0 * (1.0 - roi / pad) if pad else None
