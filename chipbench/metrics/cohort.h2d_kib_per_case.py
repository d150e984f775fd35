"""Host-to-device bytes per study: the ``bytes`` stat of the program's
``repro.prep.stage`` spans (``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    return spans.reading(run, spans.h2d_kib_per_case)
