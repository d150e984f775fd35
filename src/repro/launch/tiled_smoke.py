"""Out-of-core tiled extraction smoke: ``python -m repro.launch.tiled_smoke``.

The CI ``tiled`` stage's executable half (the other half is the
``tests/test_tiled_pipeline.py`` tier-1 parity suite): runs one small
case through the tiled engine at a deliberately tiny staged-bytes
budget -- many single-granule tiles, every prune level -- and asserts
the rows against the in-core ``extract_one`` oracle; then streams a
128^3 analytic sphere that the budget could never materialize.  Fast
(seconds, ref backend) and loud: any parity break or budget breach is a
nonzero exit.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.core.pipeline import BatchedExtractor
from repro.core.tiled import TiledExtractor
from repro.data.tiles import FnSlabSource, TiledCase
from repro.runtime.compile_cache import use_compile_cache


def _blobby_case(shape=(36, 40, 150), seed=7):
    rng = np.random.default_rng(seed)
    X, Y, Z = shape
    mask = np.zeros(shape, np.float32)
    xs, ys, zs = np.meshgrid(np.arange(X), np.arange(Y), np.arange(Z),
                             indexing="ij")
    for cx, cy, cz, r in ((18, 20, 22, 11), (16, 19, 128, 9)):
        d2 = ((xs - cx) / r) ** 2 + ((ys - cy) / r) ** 2 + ((zs - cz) / r) ** 2
        mask[d2 < 1.0] = 1.0
    image = rng.normal(size=shape).astype(np.float32)
    spacing = np.asarray([1.0, 1.1, 0.9], np.float32)
    return image, mask, spacing


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", default="ref")
    ap.add_argument("--budget-kb", type=int, default=192,
                    help="forced staged-bytes budget (tiny => many tiles)")
    args = ap.parse_args(argv)
    use_compile_cache()
    budget = args.budget_kb * 1024
    t_start = time.perf_counter()

    image, mask, spacing = _blobby_case()
    bx = BatchedExtractor(backend=args.backend,
                          families=["shape", "firstorder"])
    oracle = bx.extract_one(image, mask, spacing)
    case = TiledCase(mask, image=image, spacing=spacing)
    import warnings
    for level in ("none", "occupancy", "bounds"):
        tx = TiledExtractor(bx.executor, budget_bytes=budget,
                            tile_prune=level)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            res = tx.extract(case)
        bitwise = np.array_equal(oracle, res.row)
        close = np.allclose(oracle, res.row, rtol=1e-5, atol=1e-5)
        s = res.stats
        print(f"tiled_smoke {level:9s}: tiles={s['tiles']} "
              f"skipped={s['tiles_skipped']} "
              f"bounds_pruned={s['tiles_bounds_pruned']} "
              f"bitwise={bitwise} close={close}")
        # occupancy pruning is fully bitwise on every backend; bounds
        # relaxes only the ref diameters to f32 rounding
        ok = close if (level == "bounds" and args.backend == "ref") else bitwise
        if not ok:
            print(f"tiled_smoke FAIL: {level} parity broke "
                  f"(oracle={oracle!r} tiled={res.row!r})", file=sys.stderr)
            return 1

    # out-of-core: the sphere exists only as an analytic slab fn; the
    # materialized volume would be 8 MiB vs the ~192 KiB staged budget
    N = 128

    def sphere(z0, z1):
        ax = ((np.arange(N) - N / 2) / (N * 0.42)) ** 2
        az = ((np.arange(z0, z1) - N / 2) / (N * 0.42)) ** 2
        r2 = ax[:, None, None] + ax[None, :, None] + az[None, None, :]
        return (r2 < 1.0).astype(np.float32)

    ooc = TiledCase(FnSlabSource(sphere, (N, N, N)))
    # mc_chunk=4 shrinks the granule to 5 staged planes, so two tiles of
    # this frame genuinely fit the 1 MiB budget (8x below the volume)
    ooc_budget = 1 << 20
    tx = TiledExtractor(
        BatchedExtractor(backend=args.backend,
                         mc_chunk=4 if args.backend == "ref" else None)
        .executor,
        budget_bytes=ooc_budget, tile_prune="bounds",
    )
    res = tx.extract(ooc)
    if (args.backend == "ref"
            and res.stats["staged_bytes_peak"] > ooc_budget):
        print("tiled_smoke FAIL: staged peak "
              f"{res.stats['staged_bytes_peak']} B over the {ooc_budget} B "
              "budget", file=sys.stderr)
        return 1
    vol_bytes = 4 * N ** 3
    print(f"tiled_smoke out_of_core: {N}^3 volume ({vol_bytes >> 20} MiB) "
          f"through {res.stats['tiles']} tiles, staged peak "
          f"{res.stats['staged_bytes_peak'] / 2**10:.0f} KiB, "
          f"mesh volume {res.row[0]:.1f}")
    if not np.isfinite(res.row).all() or res.row[0] <= 0:
        print("tiled_smoke FAIL: degenerate out-of-core row", file=sys.stderr)
        return 1
    print(f"tiled_smoke OK in {time.perf_counter() - t_start:.1f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
