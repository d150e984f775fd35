"""Device time of the pass-0 programs (``jit__fields_count``,
``jit__compact_cap``) per study prepped (``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    return spans.reading(run, spans.pass0_device_ms)
