"""Readings shared by metrics of the same kind in different cells.

A roofline share is the sum of the least times of the kernel's calls in
the traced window over the kernel's summed device time there, as a
percentage.  The calls are the ones the driver recorded as launched inside
the window; their operations and bytes come from ``work.py``.
"""
from __future__ import annotations

from chipbench import work

MC_KERNEL = "mc"
DIAMETER_KERNEL = "diameter"


def idle_share(run):
    s = run.summary
    return None if s is None else 100.0 * s.idle_share


def _share(run, kernel, calls):
    s = run.summary
    if s is None or run.peaks is None or not calls:
        return None
    busy = s.kernel_s.get(kernel, 0.0)
    if busy <= 0:
        return None
    least = sum(work.least_seconds(ops, nbytes, run.peaks)[0]
                for ops, nbytes in calls)
    return 100.0 * least / busy


def mc_roofline(run):
    calls = [work.mc(run.cases[pos].roi_dims, run.refs[pos]["n_triangles"])
             for pos in run.record.get("mc_cases", [])]
    return _share(run, MC_KERNEL, calls)


def diameter_roofline(run):
    calls = [work.diameter(m) for _, m in run.record.get("sweeps", [])]
    return _share(run, DIAMETER_KERNEL, calls)
