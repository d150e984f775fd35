"""Device-to-host fetches per study: the executor's ``transfer_log``
over the measured window, divided by the rows it returned."""


def read(run):
    rec = run.record
    if "host_fetches" not in rec or not rec["cases"]:
        return None
    return rec["host_fetches"] / rec["cases"]
