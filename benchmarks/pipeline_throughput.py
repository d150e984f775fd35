"""Paper §3 'workflow' analogue: batched multi-case pipeline throughput.

The paper's motivating workload is ~40 000 CT scans on a cluster (xLUNGS);
its discussion notes that for complete workflows data loading dominates
small cases and DMA/compute overlap is the open opportunity.  This
benchmark runs the BatchedExtractor over a batch of synthetic cases in
six modes -- the single-case loop, the device-resident counted pipeline
(the default), the sync-free ``schedule='static'`` pipeline (zero
pass-1 host fetches, padded pair-sweep work instead), the
cost-model-driven auto configuration
(PR 5: ``schedule='auto'`` + sync-free ``prep='hint'``), the streaming
front-end (``extract_stream``, window overlap), and the fully
self-configuring stream (``window='auto'``) -- and reports cases/second
for each, the throughput story GPU/TPU acceleration exists to serve.

PR 7 adds the feature-family rows: ``first_order_batch`` and
``glcm_batch`` run each intensity family alone on the same windows, and
``multi_family_batch`` runs shape+firstorder+glcm together; the
multi-family rows are asserted bit-identical per ``plan.family_slices``
slice against the shape-only and single-family runs before timing is
reported, so the throughput rows double as a batch-scale parity gate.

PR 9 adds the out-of-core rows: ``tiled_sparse_prune`` measures the
tiled engine on a sparse two-blob mask with hierarchical tile pruning
on vs the naive full-tiling baseline (the >= 2x speedup is asserted
before the row is reported, and occupancy-pruned rows are asserted
bit-identical to naive), and ``tiled_out_of_core`` streams an analytic
192^3 sphere through the engine under a staged-bytes budget ~28x below
the materialized volume.

``run(records=...)`` appends one dict per mode; ``benchmarks.run
--json-pipeline`` serialises them as the ``BENCH_pipeline.json``
perf-trajectory record (cases/sec per mode across PRs; the
``two_pass_auto`` and ``streaming_auto`` rows are PR 5's additions, and
``scripts/check_bench.py`` gates fresh rows against the committed
trajectory).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import row
from repro.core import plan as planlib
from repro.core.pipeline import BatchedExtractor
from repro.core.shape_features import ShapeFeatureExtractor
from repro.data.synthetic import make_case


def _cases(n: int, dims=(48, 48, 48)):
    return [make_case(dims, seed=100 + i) for i in range(n)]


def _best_interleaved(exts, cases, repeat):
    """Warmup + interleaved best-of-``repeat`` runs per extractor.

    The first run of each mode pays its sub-batch compilations (and the
    runtime's allocator/dispatch caches settle over the next); a
    throughput record that mixed those one-time costs into cases/sec
    would charge the 40k-case sweep's setup to every 12-case window, so
    warmup runs are excluded and each mode reports its best measured run
    (same best-of policy as the autotune sweeps).  Measured runs are
    INTERLEAVED round-robin across the modes so slow machine-load drift
    lands on all of them equally instead of biasing whichever mode ran
    last.
    """
    best = [None] * len(exts)
    for ext in exts:
        ext.run(cases)  # warmup: compile + settle, excluded
    order = list(range(len(exts)))
    for r in range(max(1, repeat)):
        for k in order if r % 2 == 0 else reversed(order):  # ABBA: a load
            # burst spanning a round boundary hits both orderings equally
            res, stats = exts[k].run(cases)
            if best[k] is None or stats["seconds"] < best[k][1]["seconds"]:
                best[k] = (res, stats)
    return best


def run(n_cases: int = 12, records=None, repeat: int = 8):
    cases = _cases(n_cases)
    rows = []

    ext = ShapeFeatureExtractor(backend="ref")
    t0 = time.perf_counter()
    for img, msk, sp in cases:
        ext.execute(img, msk, sp)
    t_loop = time.perf_counter() - t0

    device = BatchedExtractor(backend="ref")
    static = BatchedExtractor(backend="ref", schedule="static")
    auto = BatchedExtractor(backend="ref", schedule="auto", prep="hint")
    # the counted vs static schedule vs the cost-model-driven auto
    # configuration are close contests: interleave their runs so
    # machine-load drift cannot bias the winner
    ((res_d, stats_d), (res_s, stats_s),
     (res_a, stats_a)) = _best_interleaved(
        (device, static, auto), cases, repeat
    )
    assert all(r is not None for r in res_d + res_s + res_a)
    for a, b in zip(res_d, res_s):  # the static schedule must not move a bit
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(res_d, res_a):  # nor hint prep + the auto schedule
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert stats_s["host_fetches"].get("pass1", 0) == 0  # the claim measured
    # the sync-free-prep claim, measured the same way: hint prep performed
    # zero per-case pass-0 syncs across every run of the auto mode
    assert auto.executor.transfer_log.get("prep", 0) == 0

    # streaming front-end: same windows, prep of k+1 overlapping exec of k
    def stream_once():
        t0 = time.perf_counter()
        rows = list(static.extract_stream(iter(cases), window=max(4, n_cases // 2)))
        return rows, time.perf_counter() - t0

    stream_once()  # warmup (compiles shared with static, but settle anyway)
    res_st, t_stream = min(
        (stream_once() for _ in range(max(2, repeat // 2))), key=lambda r: r[1]
    )
    for a, b in zip(res_d, res_st):  # streaming must not move a bit either
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # fully self-configuring stream: census-sized windows, cost-model
    # schedule, sync-free hint prep (the PR 5 acceptance configuration)
    def stream_auto_once():
        t0 = time.perf_counter()
        rows = list(auto.extract_stream(iter(cases), window="auto"))
        return rows, time.perf_counter() - t0

    stream_auto_once()  # warmup
    res_sa, t_stream_auto = min(
        (stream_auto_once() for _ in range(max(2, repeat // 2))),
        key=lambda r: r[1],
    )
    for a, b in zip(res_d, res_sa):  # nor the auto-everything stream
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert auto.executor.transfer_log.get("prep", 0) == 0

    # feature families (PR 7): first-order / GLCM texture rows on the
    # same sync-free windows.  Family launches ride inside the window
    # (staged intensity shared by both), so their cost shows up as extra
    # per-window work, not extra sync round-trips.
    fo = BatchedExtractor(backend="ref", families="firstorder")
    gl = BatchedExtractor(backend="ref", families="glcm")
    multi = BatchedExtractor(
        backend="ref", families=("shape", "firstorder", "glcm")
    )
    ((res_f, stats_f), (res_g, stats_g), (res_m, stats_m)) = _best_interleaved(
        (fo, gl, multi), cases, max(2, repeat // 2)
    )
    # family parity at bench scale: the multi-family run's shape slice is
    # bit-identical to the shape-only device rows (families never perturb
    # the shape pipeline), and each intensity slice is bit-identical to
    # the corresponding single-family run (host-side derivation makes the
    # rows independent of which families ride along)
    sl = planlib.family_slices(multi.families)
    for m, d, f, g in zip(res_m, res_d, res_f, res_g):
        np.testing.assert_array_equal(np.asarray(m)[sl["shape"]], np.asarray(d))
        np.testing.assert_array_equal(np.asarray(m)[sl["firstorder"]],
                                      np.asarray(f))
        np.testing.assert_array_equal(np.asarray(m)[sl["glcm"]], np.asarray(g))

    # out-of-core tiling (PR 9): hierarchical tile pruning on a sparse
    # mask, and a volume streamed through the engine under a device
    # budget far below its materialized size.  The pruning row's speedup
    # claim (>= 2x vs naive full-tiling) is asserted before it is
    # reported, and the parity ladder (occupancy bitwise, bounds
    # allclose on ref) re-checks the tier-1 contract at bench scale.
    from repro.core.tiled import TiledExtractor
    from repro.data.tiles import FnSlabSource, TiledCase

    X, Y, Z = 48, 48, 576
    sparse = np.zeros((X, Y, Z), np.float32)
    xs, ys = np.meshgrid(np.arange(X), np.arange(Y), indexing="ij")
    for zc in (24, Z - 24):  # two blobs at the z extremes, empty middle
        for z in range(zc - 12, zc + 12):
            r2 = ((xs - X / 2) / 14.0) ** 2 + ((ys - Y / 2) / 14.0) ** 2 \
                + ((z - zc) / 12.0) ** 2
            sparse[:, :, z][r2 < 1.0] = 1.0
    sp = np.asarray([1.0, 1.0, 1.0], np.float32)
    tcase = TiledCase(sparse, spacing=sp)
    shape_only = BatchedExtractor(backend="ref")
    budget = 288 * 1024  # single-granule tiles: 18 on this frame, ~16 empty
    t_naive = TiledExtractor(shape_only.executor, budget_bytes=budget,
                             tile_prune="none")
    t_occ = TiledExtractor(shape_only.executor, budget_bytes=budget,
                           tile_prune="occupancy")
    t_bnd = TiledExtractor(shape_only.executor, budget_bytes=budget,
                           tile_prune="bounds")

    def best_tiled(tx, k=3):
        best = None
        res = tx.extract(tcase)  # warmup: compiles excluded, as above
        for _ in range(k):
            t0 = time.perf_counter()
            res = tx.extract(tcase)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return res, best

    res_naive, dt_naive = best_tiled(t_naive)
    res_occ, dt_occ = best_tiled(t_occ)
    res_bnd, dt_bnd = best_tiled(t_bnd)
    np.testing.assert_array_equal(res_naive.row, res_occ.row)
    np.testing.assert_allclose(res_naive.row, res_bnd.row,
                               rtol=1e-5, atol=1e-5)
    prune_speedup = dt_naive / dt_bnd
    assert prune_speedup >= 2.0, (
        f"tile pruning speedup {prune_speedup:.2f}x < 2x on the sparse "
        f"mask (naive {dt_naive:.3f}s vs bounds {dt_bnd:.3f}s)"
    )

    # out-of-core: a 192^3 analytic sphere (28 MiB materialized x2 for
    # the frame+halo staging) under a 2 MiB staged budget -- the volume
    # never exists whole on host or device
    N = 192

    def sphere_slab(z0, z1):
        zz = np.arange(z0, z1)
        r2 = (((np.arange(N) - N / 2) / (N * 0.42)) ** 2)[:, None, None] \
            + (((np.arange(N) - N / 2) / (N * 0.42)) ** 2)[None, :, None] \
            + (((zz - N / 2) / (N * 0.42)) ** 2)[None, None, :]
        return (r2 < 1.0).astype(np.float32)

    ooc_budget = 2 * 1024 * 1024
    ooc = TiledCase(FnSlabSource(sphere_slab, (N, N, N)), spacing=sp)
    t_ooc = TiledExtractor(shape_only.executor, budget_bytes=ooc_budget,
                           tile_prune="bounds")
    res_ooc, dt_ooc = best_tiled(t_ooc, k=2)
    assert res_ooc.stats["staged_bytes_peak"] <= 2 * ooc_budget
    ooc_ratio = 4 * N ** 3 / ooc_budget

    def emit(name, seconds, stats=None, **extra):
        derived = dict(
            cases=n_cases, cases_per_s=f"{n_cases / seconds:.2f}", **extra
        )
        rows.append(row(f"pipeline/{name}", seconds / n_cases * 1e6, **derived))
        if records is not None:
            rec = {
                "name": name,
                "cases": n_cases,
                "seconds": seconds,
                "cases_per_second": n_cases / seconds,
            }
            if stats is not None:
                rec.update(
                    buckets=stats["buckets"],
                    vertex_buckets=stats["vertex_buckets"],
                    pruned_cases=stats["pruned_cases"],
                    mean_keep_fraction=stats["mean_keep_fraction"],
                )
            records.append(rec)

    emit("single_case_loop", t_loop)
    emit(
        "two_pass_device_compact", stats_d["seconds"], stats_d,
        buckets=stats_d["buckets"],
        vertex_buckets=stats_d["vertex_buckets"],
        keep_frac=f"{stats_d['mean_keep_fraction']:.3f}",
        speedup_vs_loop=f"{t_loop / stats_d['seconds']:.2f}",
    )
    emit(
        "two_pass_static", stats_s["seconds"], stats_s,
        buckets=stats_s["buckets"],
        vertex_buckets=stats_s["vertex_buckets"],
        pass1_syncs=0,
        speedup_vs_loop=f"{t_loop / stats_s['seconds']:.2f}",
        speedup_vs_counted=f"{stats_d['seconds'] / stats_s['seconds']:.2f}",
    )
    emit(
        "two_pass_auto", stats_a["seconds"], stats_a,
        buckets=stats_a["buckets"],
        vertex_buckets=stats_a["vertex_buckets"],
        prep="hint",
        resolved_schedule=stats_a["plan"]["schedule"],
        pass0_syncs=0,
        speedup_vs_loop=f"{t_loop / stats_a['seconds']:.2f}",
        speedup_vs_counted=f"{stats_d['seconds'] / stats_a['seconds']:.2f}",
    )
    emit(
        "streaming", t_stream,
        speedup_vs_loop=f"{t_loop / t_stream:.2f}",
        speedup_vs_batched=f"{stats_s['seconds'] / t_stream:.2f}",
        window=max(4, n_cases // 2),
    )
    emit(
        "streaming_auto", t_stream_auto,
        speedup_vs_loop=f"{t_loop / t_stream_auto:.2f}",
        speedup_vs_fixed_stream=f"{t_stream / t_stream_auto:.2f}",
        window="auto",
    )
    emit(
        "first_order_batch", stats_f["seconds"], stats_f,
        families="firstorder",
        row_width=planlib.row_width(fo.families),
        speedup_vs_loop=f"{t_loop / stats_f['seconds']:.2f}",
    )
    emit(
        "glcm_batch", stats_g["seconds"], stats_g,
        families="glcm",
        row_width=planlib.row_width(gl.families),
        speedup_vs_loop=f"{t_loop / stats_g['seconds']:.2f}",
    )
    emit(
        "multi_family_batch", stats_m["seconds"], stats_m,
        families="shape+firstorder+glcm",
        row_width=planlib.row_width(multi.families),
        vs_shape_only=f"{stats_m['seconds'] / stats_d['seconds']:.2f}",
    )

    def emit_tiled(name, seconds, tstats, **extra):
        derived = dict(cases=1, cases_per_s=f"{1 / seconds:.2f}",
                       tiles=tstats["tiles"],
                       tiles_skipped=tstats["tiles_skipped"], **extra)
        rows.append(row(f"pipeline/{name}", seconds * 1e6, **derived))
        if records is not None:
            records.append({
                "name": name, "cases": 1, "seconds": seconds,
                "cases_per_second": 1 / seconds,
                "tiles": tstats["tiles"],
                "tiles_skipped": tstats["tiles_skipped"],
                "tiles_bounds_pruned": tstats["tiles_bounds_pruned"],
            })

    emit_tiled(
        "tiled_sparse_prune", dt_bnd, res_bnd.stats,
        speedup_vs_naive=f"{prune_speedup:.2f}",
        naive_seconds=f"{dt_naive:.3f}",
        budget_kb=budget // 1024,
    )
    emit_tiled(
        "tiled_out_of_core", dt_ooc, res_ooc.stats,
        volume=f"{N}^3",
        budget_over_volume=f"1/{ooc_ratio:.0f}",
        staged_peak_mb=f"{res_ooc.stats['staged_bytes_peak'] / 2**20:.1f}",
    )
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=12)
    args = ap.parse_args(argv)
    for r in run(args.n):
        print(r)


if __name__ == "__main__":
    main()
