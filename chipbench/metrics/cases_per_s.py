"""Studies returned in the window over the window's seconds (host clock).

Counts every row the stream returned, failed ones too, so a stall shows."""


def read(run):
    rec = run.record
    if not rec["cases"]:
        return None
    return rec["cases"] / rec["elapsed_s"]
