#!/usr/bin/env python3
"""Chip smoke: drive the extraction path once on a TPU and check it.

    python chip_smoke.py             # one chip: four phases + an autotune phase
    python chip_smoke.py --chips 4   # the cohort phase on a 4-chip data mesh,
                                     # compared with its one-chip rows

Every phase runs the compiled Pallas kernels (``backend="pallas"``) through
the entry points a user calls, and compares what comes out with the ``ref``
backend on the same inputs, on the same chip, under the tolerances of the
repository's cross-backend tests:

* single study -- ``ShapeFeatureExtractor`` on Table 2's largest image
  (322x126x219) with a synthetic ROI whose mesh lands in the M = 2^18
  vertex bucket, once as the paper's drop-in (pruned) and once with the
  full unpruned pair sweep at that bucket;
* cohort -- ``BatchedExtractor(families=shape, firstorder, glcm)`` through
  ``extract_stream`` over 16 ``stream_cases`` with the dimensions of
  Table 2's patient 00003 (kidney and tumour ROIs);
* service -- ``bx.serve()`` answering requests from ``mixed_traffic_stream``,
  one of them a huge case;
* out-of-core -- the cohort's first kidney case through ``extract_tiled``
  under a budget that forces several slabs, equal to its in-core row;
* autotune -- one measured sweep of a diameter and an MC bucket.

The oracle is ``ref``'s single-case path (``extract_one``), whose compiled
programs are keyed by shape bucket alone, so phases that share a bucket
share its compilation; the ``ref`` marching cubes takes ~40 s to compile
per shape on a v5e, which is why the cohort spans four shape buckets and
not more.  Each phase runs twice: the first pass is reported as set-up (it compiles),
the second as steady.  These are smoke timings, not a benchmark.  The main
phases run on the default kernel configurations (``REPRO_AUTOTUNE=0``);
each line names the configurations the launches used.

The script exits non-zero and prints no result when JAX finds no TPU, when
``REPRO_BACKEND`` names another backend, or when any phase raises, returns
an error or quarantined row, or disagrees with the oracle.  On success the
last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# cross-backend tolerances (rtol, atol) by feature, from the tests:
# MC volume/area tests/test_kernels_mc.py, diameters
# tests/test_kernels_diameter.py, first-order moments
# tests/test_features_families.py (numpy oracle); vertex counts and GLCM
# features come from integer-exact quantities.
TOL_MC = (1e-4, 1e-3)
TOL_DIAM = (1e-5, 1e-5)
TOL_FO = (1e-3, 1e-6)
TOL_GLCM = (1e-5, 1e-6)
EXACT = (0.0, 0.0)
COHORT_FAMILIES = ("shape", "firstorder", "glcm")
# the ref oracle's marching-cubes z-slab depth: its dense per-slab edge
# tables for Table 2's largest ROI need 27 GB at the default 32 planes
REF_MC_CHUNK = 4
# Table 2's patient 00003: kidney (237x122x135) and tumour (39x35x31)
# dimensions; stream seed 1 spreads the 16 cases over 4 shape buckets and
# 4 vertex buckets (2,048 to 65,536)
COHORT_DIMS = ((237, 122, 135), (39, 35, 31))
COHORT_SEED = 1


def log(msg: str) -> None:
    print(msg, flush=True)


def tolerance(name: str):
    if name in ("MeshVolume", "SurfaceArea"):
        return TOL_MC
    if name.startswith("Maximum") and "Diameter" in name:
        return TOL_DIAM
    if name in ("n_vertices", "_n_mesh_vertices", "VoxelVolume"):
        return EXACT
    if name in ("Contrast", "Correlation", "Idm", "JointEnergy"):
        return TOL_GLCM
    if name in ("Mean", "StdDev", "Minimum", "Maximum", "Percentile10",
                "Median", "Percentile90", "Energy", "Entropy"):
        return TOL_FO
    return TOL_MC  # shape quantities derived from mesh volume / area


class Check:
    """Row comparisons against the oracle; raises on the first mismatch."""

    def __init__(self, np):
        self.np = np
        self.worst = {}

    def rows(self, phase, names, got, want):
        np = self.np
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        if got.shape != want.shape:
            raise AssertionError(f"{phase}: shape {got.shape} != {want.shape}")
        if not np.isfinite(got).all():
            raise AssertionError(f"{phase}: non-finite (error) row: {got}")
        for c, name in enumerate(names):
            rtol, atol = tolerance(name)
            g, w = got[..., c], want[..., c]
            err = np.abs(g - w)
            bad = err > atol + rtol * np.abs(w)
            rel = float(np.max(err / np.maximum(np.abs(w), 1e-30)))
            self.worst[name] = max(self.worst.get(name, 0.0), rel)
            if bad.any():
                raise AssertionError(
                    f"{phase}: {name} differs from ref beyond rtol={rtol} "
                    f"atol={atol}: got {g[bad][:4]} want {w[bad][:4]}")

    def report(self, phase):
        worst = ", ".join(f"{k}={v:.2e}" for k, v in self.worst.items())
        log(f"[{phase}] max relative deviation from the oracle: {worst}")
        self.worst = {}


class Census:
    """Compilations (per program name) and kernel configurations seen
    while a phase runs.  A persistent-cache load counts as a compilation."""

    def __init__(self, jax, dispatcher):
        self.compiles = 0
        self.configs = {}
        self.programs = {}  # jit name -> [count, seconds]
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        for name in ("diameter_config", "mc_config", "compact_config",
                     "firstorder_config", "glcm_config"):
            setattr(dispatcher, name, self._spy(name, getattr(dispatcher, name)))

    def _on_event(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            seen = self.programs.setdefault(kw.get("fun_name", "?"), [0, 0.0])
            seen[0] += 1
            seen[1] += duration

    def _spy(self, name, fn):
        def wrapped(backend, key, *a, **kw):
            out = fn(backend, key, *a, **kw)
            if backend == "pallas":
                k = tuple(key) if isinstance(key, (tuple, list)) else key
                self.configs.setdefault(name.replace("_config", ""), {})[
                    (k, kw.get("batch", 1))] = out
            return out
        return wrapped

    def take(self):
        out = (self.compiles, self.configs, self.programs)
        self.compiles, self.configs, self.programs = 0, {}, {}
        return out


def timed_twice(census, fn):
    """Run ``fn`` cold then warm: (result, setup s, steady s, compiles)."""
    census.take()
    t0 = time.perf_counter()
    fn()
    setup = time.perf_counter() - t0
    cold_compiles, configs, programs = census.take()
    t0 = time.perf_counter()
    out = fn()
    steady = time.perf_counter() - t0
    warm_compiles, _, _ = census.take()
    return (out, setup, steady, cold_compiles, warm_compiles, configs,
            programs)


def report(phase, setup, steady, cold, warm, configs, programs):
    log(f"[{phase}] setup_s={setup:.3f} steady_s={steady:.3f} "
        f"compiles_setup={cold} compiles_steady={warm}")
    for kind, seen in sorted(configs.items()):
        for (key, batch), cfg in sorted(seen.items(), key=str):
            log(f"[{phase}]   config {kind} bucket={key} batch={batch}: {cfg}")
    by_count = sorted(programs.items(), key=lambda kv: (-kv[1][0], kv[0]))
    log(f"[{phase}]   set-up compiles by program: " + ", ".join(
        f"{name}={n} ({s:.1f}s)" for name, (n, s) in by_count))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_single_study(ctx):
    np, synthetic, ops = ctx["np"], ctx["synthetic"], ctx["ops"]
    from repro.core.shape_features import ShapeFeatureExtractor, crop_to_roi

    # Table 2's largest image; a 16-blob ROI puts its mesh past 2^17
    # vertices, into the M = 2^18 bucket of the paper's largest case
    image, mask, sp = synthetic.make_case((322, 126, 219), seed=0, n_blobs=16)
    _, m, _ = crop_to_roi(image, mask)
    n = int(ops.count_vertices(ops.vertex_fields(m, 0.5, sp)))
    bucket = ops.vertex_bucket(n)
    log(f"[single] case 00001-1 dims (322, 126, 219) roi {m.shape} "
        f"vertices={n} vertex_bucket={bucket}")
    if bucket != 1 << 18:
        raise AssertionError(f"single study must reach M=2^18, got {bucket}")
    for prune in (True, False):
        label = f"single prune={prune}"
        ext = ShapeFeatureExtractor(backend="pallas", prune=prune)
        feats, *t = timed_twice(ctx["census"],
                                lambda: ext.execute(image, mask, sp))
        report(label, *t)
        if not prune:
            log(f"[{label}] diameter sweep at M={bucket} (unpruned)")
        want = ShapeFeatureExtractor(backend="ref", prune=prune,
                                     mc_chunk=REF_MC_CHUNK).execute(
            image, mask, sp)
        names = sorted(k for k in want if np.isfinite(want[k]))
        ctx["check"].rows(label, names, [feats[k] for k in names],
                          [want[k] for k in names])
        ctx["check"].report(label)


def cohort_cases(synthetic, n=16):
    return [(img, msk, sp) for _, img, msk, sp in synthetic.stream_cases(
        n, dims_pool=list(COHORT_DIMS), seed=COHORT_SEED)]


def run_stream(bx, cases):
    return list(bx.extract_stream(iter(cases), window=8))


def oracle_rows(ctx, families, cases):
    """``ref`` rows of ``cases``, one ``extract_one`` each, on the chip."""
    from repro.core.pipeline import BatchedExtractor

    key = ("oracle", families)
    if key not in ctx:
        ctx[key] = BatchedExtractor(backend="ref", families=families,
                                    mc_chunk=REF_MC_CHUNK)
    return [ctx[key].extract_one(*c) for c in cases]


def phase_cohort(ctx):
    np, synthetic, planlib = ctx["np"], ctx["synthetic"], ctx["plan"]
    from repro.core.pipeline import BatchedExtractor
    from repro.core.shape_features import crop_to_roi

    cases = cohort_cases(synthetic)
    shapes = sorted({planlib.shape_bucket(crop_to_roi(*c[:2])[1].shape)
                     for c in cases})
    log(f"[cohort] {len(cases)} cases, shape buckets {shapes}")
    if len(shapes) < 2:
        raise AssertionError("cohort must span more than one shape bucket")
    bx = BatchedExtractor(backend="pallas", families=COHORT_FAMILIES)
    rows, *t = timed_twice(ctx["census"], lambda: run_stream(bx, cases))
    report("cohort", *t)
    want = oracle_rows(ctx, COHORT_FAMILIES, cases)
    names = planlib.feature_names(COHORT_FAMILIES)
    ctx["check"].rows("cohort", names, rows, want)
    caps = sorted({int(planlib.vertex_bucket(r[6])) for r in want})
    log(f"[cohort] vertex buckets of the rows: {caps}")
    if len(caps) < 2:
        raise AssertionError("cohort must span more than one vertex bucket")
    ctx["check"].report("cohort")


def phase_service(ctx):
    synthetic = ctx["synthetic"]
    from repro.core.pipeline import BatchedExtractor

    cases = [(img, msk, sp) for _, img, msk, sp in
             synthetic.mixed_traffic_stream(8, seed=3, huge_every=8)]
    requests = [cases[i:i + 2] for i in range(0, len(cases), 2)]
    bx = BatchedExtractor(backend="pallas", prep="hint", schedule="static")

    def serve_all():
        with bx.serve() as svc:
            futs = [svc.submit(r, tenant=f"client-{i % 2}")
                    for i, r in enumerate(requests)]
            results = [f.result(timeout=900) for f in futs]
            stats = svc.stats()
        return results, stats

    (results, stats), *t = timed_twice(ctx["census"], serve_all)
    report("service", *t)
    log(f"[service] {len(requests)} requests, {stats['served_cases']} cases "
        f"in {stats['windows']} windows; expired={stats['expired_cases']} "
        f"quarantined={stats['quarantined_cases']} "
        f"failed={stats['failed_cases']}")
    for res in results:
        if res.errors:
            raise AssertionError(f"service returned error rows: {res.errors}")
    if stats["expired_cases"] or stats["quarantined_cases"] or \
            stats["failed_cases"]:
        raise AssertionError(f"service census not clean: {stats}")
    want = oracle_rows(ctx, ("shape",), cases)
    got = [row for res in results for row in res.rows]
    ctx["check"].rows("service", ctx["plan"].feature_names(), got, want)
    ctx["check"].report("service")


def phase_tiled(ctx):
    np, synthetic, planlib = ctx["np"], ctx["synthetic"], ctx["plan"]
    from repro.core.pipeline import BatchedExtractor

    from repro.core.shape_features import crop_to_roi
    from repro.kernels import marching_cubes as mck

    fams = ("shape", "firstorder")
    image, mask, sp = cohort_cases(synthetic, n=1)[0]
    # half of what the case needs in core: its staged f32 mask + intensity
    # and one marching-cubes call's temporaries, at its shape bucket
    frame = planlib.shape_bucket(crop_to_roi(image, mask)[1].shape)
    incore_mb = (2 * 4 * int(np.prod(frame)) + mck.work_bytes(frame)) / 2**20
    budget_mb = incore_mb / 2
    bx = BatchedExtractor(backend="pallas", families=fams,
                          tile_mem_mb=budget_mb)
    res, *t = timed_twice(ctx["census"],
                          lambda: bx.extract_tiled((image, mask, sp)))
    report("tiled", *t)
    st = res.stats
    used_mb = (st["staged_bytes_peak"] + st["mc_work_bytes"]) / 2**20
    log(f"[tiled] dims {mask.shape} frame {frame}: budget {budget_mb:.1f} "
        f"MiB of {incore_mb:.1f} MiB in core, {used_mb:.1f} MiB planned; "
        f"{st['tiles']} tiles, {st['tiles_skipped']} skipped")
    if st["tiles"] < 2:
        raise AssertionError(f"budget must force >= 2 slabs, got {st['tiles']}")
    if used_mb > budget_mb:
        raise AssertionError("tiles planned over the device-memory budget")
    incore = bx.extract_one(image, mask, sp)
    if not np.array_equal(np.asarray(res.row), np.asarray(incore)):
        raise AssertionError(f"tiled row {res.row} != in-core row {incore}")
    want = oracle_rows(ctx, fams, [(image, mask, sp)])[0]
    ctx["check"].rows("tiled", planlib.feature_names(fams), res.row, want)
    ctx["check"].report("tiled")


def phase_autotune(ctx):
    from repro.runtime import autotune

    cache = autotune.AutotuneCache(os.path.join(ctx["tmp"], "sweep.json"))
    os.environ["REPRO_AUTOTUNE"] = "1"
    try:
        census = ctx["census"]
        census.take()
        t0 = time.perf_counter()
        d = autotune.get_diameter_config(4096, "pallas", cache=cache)
        m = autotune.get_mc_config((64, 64, 64), "pallas", cache=cache)
        sweep_s = time.perf_counter() - t0
        compiles = census.take()[0]
        again = (autotune.get_diameter_config(4096, "pallas", cache=cache),
                 autotune.get_mc_config((64, 64, 64), "pallas", cache=cache))
        recompiles = census.take()[0]
    finally:
        os.environ["REPRO_AUTOTUNE"] = "0"
    dt = cache.get(autotune.sweep_key(4096, "pallas"))["table"]
    mt = cache.get(autotune.mc_key((64, 64, 64), "pallas"))["table"]
    log(f"[autotune] swept diameter M=4096 ({len(dt)} configs) and MC "
        f"64^3 ({len(mt)} configs) in {sweep_s:.1f}s, {compiles} compiles")
    log(f"[autotune] diameter winner {d} table_us={dt}")
    log(f"[autotune] mc winner {m} table_us={mt}")
    if again != (d, m) or recompiles:
        raise AssertionError("autotune cache hit re-measured or changed")


def phase_cohort_mesh(ctx):
    import jax
    from repro.core.pipeline import BatchedExtractor
    from repro.launch.mesh import make_host_mesh

    np, synthetic, planlib = ctx["np"], ctx["synthetic"], ctx["plan"]
    cases = cohort_cases(synthetic)
    mesh = make_host_mesh()
    log(f"[cohort-mesh] mesh {dict(mesh.shape)} over "
        f"{len(jax.devices())} chips")
    bx4 = BatchedExtractor(backend="pallas", families=COHORT_FAMILIES,
                           mesh=mesh)
    rows4, *t = timed_twice(ctx["census"], lambda: run_stream(bx4, cases))
    report("cohort-mesh", *t)
    bx1 = BatchedExtractor(backend="pallas", families=COHORT_FAMILIES)
    rows1, *t = timed_twice(ctx["census"], lambda: run_stream(bx1, cases))
    report("cohort-1chip", *t)
    names = planlib.feature_names(COHORT_FAMILIES)
    ctx["check"].rows("cohort-mesh", names, rows4, rows1)
    equal = np.array_equal(np.asarray(rows4), np.asarray(rows1))
    log(f"[cohort-mesh] rows bit-identical to one chip: {equal}")
    if not equal:
        raise AssertionError("4-chip rows differ from the one-chip rows")
    ctx["check"].report("cohort-mesh")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cohort phase, on a 4-chip data mesh")
    args = ap.parse_args(argv)

    requested = os.environ.get("REPRO_BACKEND", "")
    if requested not in ("", "pallas"):
        print(f"chip_smoke: refuses REPRO_BACKEND={requested!r}; the path "
              "under test is backend='pallas'", file=sys.stderr)
        return 2
    # default kernel configurations, read from no autotune cache on disk
    os.environ["REPRO_AUTOTUNE"] = "0"
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(tmp, "autotune.json")
    sys.path.insert(0, os.path.join(HERE, "src"))

    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's default device is {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.core import dispatcher
    from repro.core import plan
    from repro.data import synthetic
    from repro.kernels import ops
    from repro.runtime.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    log(f"[device] compile cache {cache_dir}")
    ctx = {"np": np, "synthetic": synthetic, "ops": ops, "plan": plan,
           "check": Check(np), "census": Census(jax, dispatcher), "tmp": tmp}

    phases = ([phase_cohort_mesh] if args.chips == 4 else
              [phase_single_study, phase_cohort, phase_service, phase_tiled,
               phase_autotune])
    t_all = time.perf_counter()
    for phase in phases:
        t0 = time.perf_counter()
        phase(ctx)
        log(f"[{phase.__name__}] PASS in {time.perf_counter() - t0:.1f}s")
    log(f"[done] all phases passed in {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
