"""The pair sweep's share of its roofline: the least time of every sweep in
the traced window (``chipbench/work.py``) over the kernel's device time."""
from chipbench.metrics_common import diameter_roofline as read  # noqa: F401
