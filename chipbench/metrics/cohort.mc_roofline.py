"""Marching cubes' share of its roofline: the least time of every call in
the traced window (``chipbench/work.py``) over the kernel's device time."""
from chipbench.metrics_common import mc_roofline as read  # noqa: F401
