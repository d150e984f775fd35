"""Set-up: from process start to the first timed request (host clock).

Generation of the studies, compiles or cache loads, and the warm pass."""


def read(run):
    return run.setup_s
