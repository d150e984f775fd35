"""Host time of pass 0 per study: the self time of the program's
``repro.prep`` spans and their children, fetches left out
(``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    return spans.reading(run, spans.prep_host_ms)
