"""Host time blocked in the program's ``repro.fetch`` spans per study
prepped in the traced window (``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    return spans.reading(run, spans.fetch_wait_ms)
