"""Share of the traced window in which the device is idle while a
``repro.prep*`` span is the innermost program span (``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    return spans.reading(run, spans.idle_in_prep)
