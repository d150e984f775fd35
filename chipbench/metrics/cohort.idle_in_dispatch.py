"""Share of the traced window in which the device is idle while
``repro.plan`` or a ``repro.launch.*`` span is the innermost program span
(``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    return spans.reading(run, spans.idle_in_dispatch)
