"""A whole run of a cell on the CPU, on the smallest study its mix sends.

Skips only the harness's look for a chip: generation, set-up, the window,
the reference and the comparison run as on the chip, through the program's
own CPU path.
"""
import contextlib
import io
import json
import math
import os


def run_cell(monkeypatch, workload, seed=2**31 + 3, seconds=1.0):
    from chipbench import run
    from chipbench.traffic import generate

    real = generate.select

    def one_study(config, traffic):
        return [min(real(config, traffic), key=lambda im: math.prod(im[2]))]

    monkeypatch.setattr(generate, "select", one_study)
    env = dict(os.environ)
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", "0"],
                          need_tpu=False)
    finally:
        os.environ.clear()
        os.environ.update(env)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
