"""Radiomics-as-a-service: concurrent tenants sharing one device pipeline.

The cluster example (``cluster_pipeline.py``) is the BATCH story -- one
job, 40k cases, a manifest.  This example is the SERVICE story (ROADMAP
direction 3): several independent clients -- think a clinical viewer
asking for one study's features next to a research sweep chewing through
a cohort -- submit cases concurrently to one ``ExtractionService``, and
the driver fuses their cases into shared device windows:

  * the **viewer** tenant submits single cases with a deadline: if the
    queue cannot serve a case in time it gets a ``DeadlineExceeded``
    error row back immediately instead of silently waiting forever (and
    its expired request never occupies a window slot);
  * the **cohort** tenant submits batches with no deadline and simply
    rides along -- its cases pad out the viewer's windows, so device
    utilisation stays high without hurting viewer latency (the cost
    model closes a window early when the oldest pending deadline is at
    risk: ``CostModel.deadline_at_risk``);
  * admission control bounds the ESTIMATED bytes queued on the host
    (``--queue-mb``); when the cohort outruns the device its submits
    BLOCK -- backpressure, not OOM;
  * every row is bit-identical to what ``extract_stream`` would have
    produced for the same case (the serving parity contract,
    tier-1-locked in ``tests/test_service.py``).

    PYTHONPATH=src python examples/serve_clients.py
    PYTHONPATH=src python examples/serve_clients.py \\
        --viewer-cases 8 --cohort-cases 24 --deadline-ms 2000
"""
import argparse
import threading
import time

import numpy as np

from repro.core.pipeline import BatchedExtractor
from repro.runtime.compile_cache import use_compile_cache
from repro.data.synthetic import mixed_traffic_stream, stream_cases


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="two tenants (deadline viewer + batch cohort) sharing "
                    "one extraction service")
    ap.add_argument("--backend", default=None)
    ap.add_argument("--viewer-cases", type=int, default=6)
    ap.add_argument("--cohort-cases", type=int, default=12)
    ap.add_argument("--cohort-batch", type=int, default=4)
    ap.add_argument("--deadline-ms", type=float, default=5000.0)
    ap.add_argument("--queue-mb", type=float, default=64.0)
    args = ap.parse_args(argv)
    use_compile_cache()

    bx = BatchedExtractor(backend=args.backend, prep="hint",
                          schedule="static")
    viewer_cases = [(i, m, s) for _, i, m, s in
                    mixed_traffic_stream(args.viewer_cases, huge_every=0)]
    # clinic-sized cohort shapes: the full Table-2 pool has 300-voxel
    # giants that take minutes per case on a CPU ref backend
    cohort_cases = [(i, m, s) for _, i, m, s in
                    stream_cases(args.cohort_cases, seed=7,
                                 dims_pool=[(40, 44, 36), (48, 48, 48),
                                            (36, 52, 40), (44, 40, 48)])]

    def viewer(svc, out):
        for i, case in enumerate(viewer_cases):
            t0 = time.perf_counter()
            res = svc.submit_case(case, tenant="viewer",
                                  deadline_s=args.deadline_ms / 1e3
                                  ).result(timeout=600)
            dt = (time.perf_counter() - t0) * 1e3
            verdict = ("EXPIRED" if res.errors
                       else f"MeshVolume={float(res.rows[0][0]):.1f}")
            print(f"[viewer] case {i}: {dt:7.1f} ms  {verdict}")
            out.append(res)

    def cohort(svc, out):
        for lo in range(0, len(cohort_cases), args.cohort_batch):
            res = svc.submit(cohort_cases[lo:lo + args.cohort_batch],
                             tenant="cohort").result(timeout=600)
            print(f"[cohort] batch {lo // args.cohort_batch}: "
                  f"{len(res.rows)} rows, errors={len(res.errors)}")
            out.append(res)

    v_out, c_out = [], []
    with bx.serve(max_queue_bytes=args.queue_mb * 2**20) as svc:
        threads = [threading.Thread(target=viewer, args=(svc, v_out)),
                   threading.Thread(target=cohort, args=(svc, c_out))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stats = svc.stats()

    # parity spot-check: the cohort's served rows == the batch pipeline's
    ref, _ = bx.run(cohort_cases)
    got = [np.asarray(r) for res in c_out for r in res.rows]
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), b)

    cross = sum(1 for t in stats["window_tenants"] if t > 1)
    print(f"\n[serve] {stats['served_cases']} cases in {wall:.2f}s "
          f"({stats['served_cases'] / wall:.1f} cases/s), "
          f"{stats['windows']} windows ({cross} cross-tenant), "
          f"{stats['expired_cases']} expired, parity OK")


if __name__ == "__main__":
    main()
