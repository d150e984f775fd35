"""Readings that the limits of a cell's comparison are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 --seconds 5

One process on the chip, so the programs compile once.  For each of
``--seeds`` it makes the cell's studies, warms and runs the timed path for
a short window exactly as ``run.py`` does, and holds every answer against
the reference: the lower readings.  For each of ``--control-seeds`` it puts
the reference computed in bfloat16 in the program's place at the cell's own
size: the upper readings.  One JSON line per seed on standard output, each
number with the study that set it.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, harness, reference, run  # noqa: E402
from chipbench.traffic import generate  # noqa: E402


def _worst(check, answers, refs, cases):
    """``{number: [widest gap, study, feature that set it]}``."""
    out = {}
    for name, spec in check.items():
        best = [0.0, None, None]
        for pos, ans in answers:
            for feat in spec["features"]:
                g = compare.gap(ans.get(feat, math.nan), refs[pos][feat],
                                spec["gap"])
                if g > best[0] or best[1] is None:
                    best = [g, cases[pos].name, feat]
        out[name] = best
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench = harness.benchmark()
    cell = harness.cell(bench, args.workload)
    cfg = harness.config(cell["config"])
    traffic = generate.load(cell["traffic"])
    families = tuple(cfg["extractor"].get("families", ("shape",)))
    check = cfg["check"]
    run._environment(cfg)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    for seed in seeds:
        t0 = time.perf_counter()
        cases = generate.build_cases(cfg, traffic, seed)
        driver = harness.load_module("drivers", cfg["driver"]).Driver(
            cfg, cases, generate.order(traffic, len(cases), seed), False)
        driver.warm()
        rec = driver.window(args.seconds)
        driver.close()
        del driver
        gc.collect()
        refs = [reference.features(c, families) for c in cases]
        print(json.dumps({
            "kind": "program", "seed": seed, "answers": len(rec["answers"]),
            "errors": rec["errors"][:3],
            "numbers": _worst(check, rec["answers"], refs, cases),
            "seconds": time.perf_counter() - t0}), flush=True)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        t0 = time.perf_counter()
        cases = generate.build_cases(cfg, traffic, seed)
        refs, ctl = [], []
        for c in cases:
            refs.append(reference.features(c, families))
            ctl.append(reference.features(c, families, dtype="bfloat16"))
        worst = _worst(check, list(enumerate(ctl)), refs, cases)
        print(json.dumps({
            "kind": "control", "seed": seed, "numbers": worst,
            "fails": [n for n, (v, _, _) in worst.items()
                      if not v <= check[n]["limit"] or math.isinf(v)],
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
