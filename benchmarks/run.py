"""Benchmark harness entry point: ``python -m benchmarks.run``.

One benchmark per paper table/figure (+ the roofline report):

    table2   -- per-case stage breakdown            (paper Table 2)
    fig1     -- diameter kernel variant comparison  (paper Fig. 1)
    fig2     -- size scaling + projected speedup    (paper Fig. 2)
    pipeline -- batched multi-case throughput       (paper §3 workflow)
    soak     -- faulted/preempted/resumed soak      (resilience gate)
    serve    -- service mixed-traffic p50/p99       (serving-tier gate)
    roofline -- per-kernel roofline efficiency      (CI efficiency gate)

Prints ``name,us_per_call,derived`` CSV.  Select suites with --only.
``--json PATH`` additionally writes a ``BENCH_diameter.json`` trajectory
record (per-variant us_per_call, M, M', structural FLOP/byte estimates)
from the fig1 suite, and ``--json-pipeline PATH`` a ``BENCH_pipeline.json``
record (cases/sec for the single loop, the unpruned batched baseline, the
host-compaction two-pass pipeline, and the default device-compaction
two-pass pipeline) from the pipeline suite, so successive PRs can track
both perf curves.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.runtime.compile_cache import use_compile_cache

SUITES = ("table2", "fig1", "fig2", "pipeline", "soak", "serve", "roofline")


def _write_record(path: str, bench: str, suite: str, rows: list, ok: bool):
    if ok:
        record = {
            "bench": bench,
            "suite": suite,
            "written_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "rows": rows,
        }
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        print(f"# wrote {path} ({len(rows)} rows)", file=sys.stderr)
    else:  # keep any previous record rather than clobber it
        print(f"# {suite} failed; NOT overwriting {path}", file=sys.stderr)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", metavar="SUITE",
                    default=list(SUITES),
                    help=f"suites to run (any of: {', '.join(SUITES)})")
    ap.add_argument("--full", action="store_true",
                    help="table2: run all 20 cases incl. the O(M^2) giants")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the diameter perf-trajectory record here")
    ap.add_argument("--json-pipeline", metavar="PATH", default=None,
                    help="write the batched-throughput trajectory record here")
    args = ap.parse_args(argv)
    use_compile_cache()
    # validate by hand: a bare ``--only`` (empty list) used to silently
    # run NOTHING and exit 0, and an unknown name must die loudly
    if not args.only:
        ap.error(f"--only needs at least one suite name; valid suites: "
                 f"{', '.join(SUITES)}")
    unknown = [s for s in args.only if s not in SUITES]
    if unknown:
        ap.error(f"unknown suite(s) {', '.join(unknown)}; valid suites: "
                 f"{', '.join(SUITES)}")
    if args.json is not None and "fig1" not in args.only:
        ap.error("--json records the fig1 suite; add fig1 to --only")
    if args.json_pipeline is not None and "pipeline" not in args.only:
        ap.error("--json-pipeline records the pipeline suite; add pipeline "
                 "to --only")
    for path in (args.json, args.json_pipeline):
        if path is not None:
            # fail on an unwritable path BEFORE benching -- append mode so
            # an existing trajectory record is not clobbered until the new
            # one is ready
            open(path, "a").close()

    print("name,us_per_call,derived")
    failures = 0
    diameter_records: list[dict] = []
    pipeline_records: list[dict] = []
    fig1_ok = pipeline_ok = False
    for suite in args.only:
        t0 = time.time()
        try:
            if suite == "table2":
                from benchmarks import table2_breakdown
                rows = table2_breakdown.run(full=args.full)
            elif suite == "fig1":
                from benchmarks import fig1_variants
                rows = fig1_variants.run(records=diameter_records)
                fig1_ok = True
            elif suite == "fig2":
                from benchmarks import fig2_scaling
                rows = fig2_scaling.run()
            elif suite == "pipeline":
                from benchmarks import pipeline_throughput
                rows = pipeline_throughput.run(records=pipeline_records)
                pipeline_ok = True
            elif suite == "soak":
                # the resilience soak rides the pipeline trajectory record
                # (its soak_resilience row is cases/sec like the others)
                from benchmarks import soak
                rows = soak.run(records=pipeline_records)
            elif suite == "serve":
                # serving-tier mixed-traffic rows ride the same record:
                # throughput is cases/sec, and the p50/p99 latency rows
                # encode 1/latency as cases_per_second so the gate's
                # higher-is-better rule catches latency regressions too
                from benchmarks import serve_latency
                rows = serve_latency.run(records=pipeline_records)
            else:
                # per-kernel roofline-efficiency rows ride the pipeline
                # record too: each row's cases_per_second carries the
                # achieved fraction of the kernel's roofline bound (a
                # same-host ratio), so the committed trajectory gates
                # silent efficiency regressions under the same >30% rule
                from benchmarks import roofline_report
                rows = roofline_report.run(records=pipeline_records)
        except Exception as e:  # pragma: no cover
            print(f"{suite}/ERROR,0,{type(e).__name__}: {e}", file=sys.stderr)
            failures += 1
            continue
        for r in rows:
            print(r)
        print(f"# {suite} done in {time.time() - t0:.1f}s", file=sys.stderr)

    if args.json is not None:
        _write_record(args.json, "diameter", "fig1", diameter_records, fig1_ok)
    if args.json_pipeline is not None:
        _write_record(args.json_pipeline, "pipeline", "pipeline",
                      pipeline_records, pipeline_ok)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
