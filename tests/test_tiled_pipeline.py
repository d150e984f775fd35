"""Out-of-core tiled extraction: parity, pruning, halo and routing gates.

Tier-1 contract (ROADMAP "Out-of-core tiling"): on any case both paths
can run, the tiled engine's row is bit-identical to the in-core
``extract_one`` oracle -- for every tile size (budget), for
``tile_prune`` in {'none', 'occupancy'} on every backend, and for
'bounds' on the gram-kernel backends; 'bounds' on the ref backend may
move only the diameters, within f32 rounding (the same contract vertex
pruning already has).  The suite also locks the slab-source contracts,
the routing facade (``tiled=`` / ``TiledCase``), and the budget
accounting the out-of-core claim rests on.
"""
import os
import warnings

import numpy as np
import pytest

from repro.core.executor import PlanExecutor
from repro.core.pipeline import BatchedExtractor
from repro.core.tiled import TiledExtractor, tile_budget_bytes
from repro.data.nifti import write_nifti
from repro.data.tiles import (
    ArraySlabSource,
    FnSlabSource,
    NiftiSlabSource,
    TiledCase,
)

pytestmark = pytest.mark.tier1

SP = np.asarray([1.0, 1.25, 0.75], np.float32)


def _ellipsoid(shape=(40, 44, 57), radii=(12, 15, 20), seed=0):
    X, Y, Z = shape
    xs, ys, zs = np.meshgrid(np.arange(X), np.arange(Y), np.arange(Z),
                             indexing="ij")
    c = (X / 2, Y / 2, Z / 2)
    r2 = (((xs - c[0]) / radii[0]) ** 2 + ((ys - c[1]) / radii[1]) ** 2
          + ((zs - c[2]) / radii[2]) ** 2)
    mask = (r2 < 1.0).astype(np.float32)
    image = np.random.default_rng(seed).normal(size=shape).astype(np.float32)
    return image, mask


def _two_blob(shape=(36, 40, 180)):
    """Sparse mask: blobs at the z extremes, a long empty middle."""
    X, Y, Z = shape
    mask = np.zeros(shape, np.float32)
    xs, ys, zs = np.meshgrid(np.arange(X), np.arange(Y), np.arange(Z),
                             indexing="ij")
    for cx, cy, cz, rx, ry, rz in ((18, 20, 15, 8, 9, 10),
                                   (16, 18, 165, 7, 8, 9)):
        r2 = (((xs - cx) / rx) ** 2 + ((ys - cy) / ry) ** 2
              + ((zs - cz) / rz) ** 2)
        mask[r2 < 1.0] = 1.0
    image = np.random.default_rng(1).normal(size=shape).astype(np.float32)
    return image, mask


def _tiled_row(ex, image, mask, budget, prune="occupancy", spacing=SP):
    tx = TiledExtractor(ex, budget_bytes=budget, tile_prune=prune)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        return tx.extract(TiledCase(mask, image=image, spacing=spacing))


# -- bit-parity across tile sizes and prune levels --------------------------


@pytest.mark.parametrize("budget", [1 << 30, 200_000, 60_000])
@pytest.mark.parametrize("prune", ["none", "occupancy"])
def test_ref_bitwise_across_tile_sizes(budget, prune):
    image, mask = _ellipsoid()
    ex = PlanExecutor(backend="ref", families=["shape", "firstorder"])
    oracle = ex.extract_one(image, mask, SP)
    res = _tiled_row(ex, image, mask, budget, prune)
    np.testing.assert_array_equal(oracle, res.row)


def test_ref_bounds_allclose_and_exact_nonshape_columns():
    image, mask = _two_blob()
    ex = PlanExecutor(backend="ref", families=["shape", "firstorder"])
    oracle = ex.extract_one(image, mask, SP)
    res = _tiled_row(ex, image, mask, 400_000, "bounds")
    # ref diameter path is shape-dependent in the candidate count: the
    # bounds level may move the 4 diameter columns within f32 rounding
    np.testing.assert_allclose(oracle, res.row, rtol=1e-5, atol=1e-5)
    d = slice(2, 6)
    np.testing.assert_array_equal(oracle[:2], res.row[:2])   # MC vol/area
    np.testing.assert_array_equal(oracle[6:], res.row[6:])   # count + fo


def test_interpret_backend_bitwise_incl_bounds():
    image, mask = _two_blob()
    ex = PlanExecutor(backend="interpret", families=["shape", "firstorder"])
    oracle = ex.extract_one(image, mask, SP)
    for prune in ("none", "occupancy", "bounds"):
        res = _tiled_row(ex, image, mask, 400_000, prune)
        np.testing.assert_array_equal(oracle, res.row)


def test_halo_straddling_mask_bitwise():
    # a rod spanning z, so every internal tile boundary cuts through the
    # surface and correctness rides on the halo planes
    mask = np.zeros((24, 24, 130), np.float32)
    mask[8:14, 9:15, 10:120] = 1.0
    image = np.random.default_rng(3).normal(size=mask.shape).astype(np.float32)
    ex = PlanExecutor(backend="ref", families=["shape", "firstorder"])
    oracle = ex.extract_one(image, mask, SP)
    for budget in (300_000, 150_000):
        res = _tiled_row(ex, image, mask, budget, "occupancy")
        assert res.stats["tiles"] > 1
        np.testing.assert_array_equal(oracle, res.row)


def test_occupancy_skips_without_dropping_vertices():
    image, mask = _two_blob()
    ex = PlanExecutor(backend="ref")
    oracle = ex.extract_one(None, mask, SP)
    res = _tiled_row(ex, image, mask, 400_000, "occupancy")
    assert res.stats["tiles_skipped"] > 0          # middle tiles skipped
    assert res.stats["emitted_vertices"] == res.meta.n_vertices
    assert res.row[6] == oracle[6]                 # global vertex count
    np.testing.assert_array_equal(oracle, res.row)


def test_bounds_prunes_interior_tile_keeps_count_exact():
    # two wide plates at the z extremes (the farthest-pair endpoints for
    # every combo) and a small centred dot between them: the dot's tile
    # is occupied but provably endpoint-free
    mask = np.zeros((36, 36, 170), np.float32)
    mask[4:32, 4:32, 4:8] = 1.0
    mask[4:32, 4:32, 162:166] = 1.0
    mask[16:19, 16:19, 80:83] = 1.0
    ex = PlanExecutor(backend="ref")
    oracle = ex.extract_one(None, mask, SP)
    res = _tiled_row(ex, None, mask, 300_000, "bounds", spacing=SP)
    assert res.stats["tiles_bounds_pruned"] >= 1
    assert res.stats["emitted_vertices"] < res.meta.n_vertices
    np.testing.assert_allclose(oracle, res.row, rtol=1e-5, atol=1e-5)
    assert res.row[6] == oracle[6]                 # n_vertices stays global
    # the gram-kernel backends stay fully bitwise under bounds pruning
    exi = PlanExecutor(backend="interpret")
    res_i = _tiled_row(exi, None, mask, 300_000, "bounds", spacing=SP)
    np.testing.assert_array_equal(exi.extract_one(None, mask, SP), res_i.row)


@pytest.mark.parametrize("prune", ["none", "occupancy", "bounds"])
def test_degenerate_one_voxel_and_empty(prune):
    ex = PlanExecutor(backend="ref", families=["shape", "firstorder"])
    one = np.zeros((20, 20, 40), np.float32)
    one[10, 11, 21] = 1.0
    img = np.random.default_rng(4).normal(size=one.shape).astype(np.float32)
    oracle = ex.extract_one(img, one, SP)
    res = _tiled_row(ex, img, one, 1 << 30, prune)
    np.testing.assert_array_equal(oracle, res.row)

    empty = np.zeros((16, 16, 40), np.float32)
    res_e = _tiled_row(ex, img[:16, :16, :], empty, 1 << 30, prune)
    np.testing.assert_array_equal(
        ex.extract_one(img[:16, :16, :], empty, SP), res_e.row)
    assert res_e.meta.empty


def test_ref_mc_chunk_lever_parity():
    # mc_chunk on the ref backend shrinks the scan granule (the tiled
    # engine's plane budget lever); tiled and in-core agree bitwise at
    # the same setting
    image, mask = _ellipsoid(shape=(30, 30, 66), radii=(10, 10, 25))
    ex = PlanExecutor(backend="ref", mc_chunk=4,
                      families=["shape", "firstorder"])
    oracle = ex.extract_one(image, mask, SP)
    res = _tiled_row(ex, image, mask, 120_000, "occupancy")
    assert res.stats["granule_cz"] == 4
    assert res.stats["tiles"] > 2
    np.testing.assert_array_equal(oracle, res.row)


# -- engine guards -----------------------------------------------------------


def test_glcm_and_missing_image_rejected():
    ex = PlanExecutor(backend="ref", families=["shape", "glcm"])
    with pytest.raises(ValueError, match="glcm"):
        TiledExtractor(ex)
    exf = PlanExecutor(backend="ref", families=["firstorder"])
    tx = TiledExtractor(exf, budget_bytes=1 << 30)
    mask = np.zeros((8, 8, 8), np.float32)
    mask[3:5, 3:5, 3:5] = 1.0
    with pytest.raises(ValueError, match="image source"):
        tx.extract(TiledCase(mask, spacing=SP))
    with pytest.raises(ValueError, match="tile_prune"):
        TiledExtractor(PlanExecutor(backend="ref"), tile_prune="bogus")


def test_budget_accounting_and_env_default(monkeypatch):
    image, mask = _ellipsoid()
    ex = PlanExecutor(backend="ref")
    res = _tiled_row(ex, None, mask, 200_000, "occupancy")
    assert res.stats["staged_bytes_peak"] == 2 * res.stats["tile_bytes"]
    monkeypatch.setenv("REPRO_TILE_MEM_MB", "64")
    assert tile_budget_bytes() == 64 * 2**20
    tx = TiledExtractor(ex)
    assert tx.budget_bytes == 64 * 2**20


def test_budget_counts_brick_mc_temporaries():
    # on the brick kernel a tile's MC temporaries (several times its slab)
    # share the budget with the two staged tiles; ref stages slabs only
    image, mask = _ellipsoid(shape=(26, 28, 44), radii=(8, 9, 15))
    budget = 3 << 20
    ref = _tiled_row(PlanExecutor(backend="ref"), None, mask, budget)
    assert ref.stats["mc_work_bytes"] == 0
    ex = PlanExecutor(backend="interpret")
    res = _tiled_row(ex, None, mask, budget)
    st = res.stats
    assert st["mc_work_bytes"] > st["staged_bytes_peak"] > 0
    assert st["staged_bytes_peak"] + st["mc_work_bytes"] <= budget
    assert st["tiles"] >= 2
    assert st["granules_per_tile"] < ref.stats["granules_per_tile"]
    np.testing.assert_array_equal(ex.extract_one(None, mask, SP), res.row)


def test_over_budget_minimum_tile_warns():
    mask = np.zeros((40, 44, 57), np.float32)
    mask[4:36, 4:40, 4:53] = 1.0
    ex = PlanExecutor(backend="ref")
    tx = TiledExtractor(ex, budget_bytes=10_000, tile_prune="occupancy")
    with pytest.warns(RuntimeWarning, match="cannot hold two minimal"):
        tx.extract(TiledCase(mask, spacing=SP))


# -- slab sources ------------------------------------------------------------


def test_array_and_fn_sources_agree(tmp_path):
    image, mask = _ellipsoid(shape=(26, 28, 44), radii=(8, 9, 15))
    ex = PlanExecutor(backend="ref", families=["shape", "firstorder"])
    oracle = ex.extract_one(image, mask, SP)

    fn_case = TiledCase(
        FnSlabSource(lambda z0, z1: mask[:, :, z0:z1], mask.shape),
        image=FnSlabSource(lambda z0, z1: image[:, :, z0:z1], image.shape),
        spacing=SP,
    )
    tx = TiledExtractor(ex, budget_bytes=150_000, tile_prune="occupancy")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        np.testing.assert_array_equal(oracle, tx.extract(fn_case).row)

    mp, ip = tmp_path / "mask.nii", tmp_path / "img.nii"
    write_nifti(mp, mask, SP)
    write_nifti(ip, image, SP)
    nifti_case = TiledCase(NiftiSlabSource(mp), image=NiftiSlabSource(ip))
    np.testing.assert_allclose(nifti_case.spacing, SP, rtol=1e-6)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        np.testing.assert_array_equal(oracle, tx.extract(nifti_case).row)

    img2, msk2, sp2 = nifti_case.materialize()
    np.testing.assert_array_equal(msk2, mask)
    np.testing.assert_array_equal(img2, image)


def test_fn_source_shape_validated():
    src = FnSlabSource(lambda z0, z1: np.zeros((4, 4, z1 - z0 + 1)), (4, 4, 8))
    with pytest.raises(ValueError, match="slab fn returned shape"):
        src.read(0, 2)
    with pytest.raises(ValueError, match="3D"):
        ArraySlabSource(np.zeros((4, 4)))


def test_gz_slab_source_rejected_with_workaround(tmp_path):
    mask = np.zeros((6, 6, 6), np.float32)
    mask[2:4, 2:4, 2:4] = 1.0
    p = tmp_path / "m.nii.gz"
    write_nifti(p, mask, SP)
    with pytest.raises(ValueError, match="gunzip"):
        NiftiSlabSource(p)


# -- routing facade ----------------------------------------------------------


def test_run_merges_tiled_rows_in_order():
    image, mask = _ellipsoid(shape=(26, 28, 44), radii=(8, 9, 15))
    small = [(image, mask, SP)] * 2
    big_img, big_mask = _two_blob()
    bx = BatchedExtractor(backend="ref", families=["shape", "firstorder"],
                          tiled=True, tile_mem_mb=0.4)
    cases = [small[0], (big_img, big_mask, SP), small[1],
             TiledCase(big_mask, image=big_img, spacing=SP)]
    oracle = [bx.extract_one(*c) for c in cases[:3]]
    oracle.append(bx.extract_one(big_img, big_mask, SP))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rows, stats = bx.run(cases)
    assert stats["tiled"]["cases"] == 2
    assert stats["tiled"]["census"].cases == 2
    assert stats["tiled"]["tiles_skipped"] > 0
    for a, b in zip(oracle, rows):
        np.testing.assert_array_equal(a, b)


def test_stream_handles_tiled_cases_between_segments():
    image, mask = _ellipsoid(shape=(26, 28, 44), radii=(8, 9, 15))
    big_img, big_mask = _two_blob()
    bx = BatchedExtractor(backend="ref", families=["shape", "firstorder"])
    cases = [(image, mask, SP), (image, mask, SP),
             TiledCase(big_mask, image=big_img, spacing=SP),
             (image, mask, SP)]
    oracle = ([bx.extract_one(image, mask, SP)] * 2
              + [bx.extract_one(big_img, big_mask, SP)]
              + [bx.extract_one(image, mask, SP)])
    rows = list(bx.extract_stream(iter(cases), window=2))
    assert len(rows) == 4
    for a, b in zip(oracle, rows):
        np.testing.assert_array_equal(a, b)


def test_default_extractor_leaves_tuples_incore():
    image, mask = _ellipsoid(shape=(26, 28, 44), radii=(8, 9, 15))
    bx = BatchedExtractor(backend="ref")
    assert not bx._route_tiled((image, mask, SP))
    assert bx._route_tiled(TiledCase(mask, spacing=SP))
    bxt = BatchedExtractor(backend="ref", tiled=True, tile_mem_mb=0.01)
    assert bxt._route_tiled((image, mask, SP))
    # the staged mask (125 KiB) fits 1 MiB; off ref, its marching-cubes
    # temporaries do not
    assert not BatchedExtractor(backend="ref", tiled=True, tile_mem_mb=1.0
                                )._route_tiled((image, mask, SP))
    assert BatchedExtractor(backend="interpret", tiled=True, tile_mem_mb=1.0
                            )._route_tiled((image, mask, SP))


# -- the out-of-core acceptance case ----------------------------------------


def test_out_of_core_sphere_under_budget():
    # 160^3 analytic sphere: 16 MiB materialized (mask alone), run under
    # a 1 MiB staged budget with the ref mc_chunk granule lever -- the
    # same configuration the 1024^3 demo scales up (REPRO_TILED_BIG=1)
    N = 160

    def sphere(z0, z1):
        ax = ((np.arange(N) - N / 2) / (N * 0.42)) ** 2
        az = ((np.arange(z0, z1) - N / 2) / (N * 0.42)) ** 2
        return (ax[:, None, None] + ax[None, :, None]
                + az[None, None, :] < 1.0).astype(np.float32)

    ex = PlanExecutor(backend="ref", mc_chunk=4)
    tx = TiledExtractor(ex, budget_bytes=1 << 20, tile_prune="bounds")
    res = tx.extract(TiledCase(FnSlabSource(sphere, (N, N, N))))
    assert res.stats["staged_bytes_peak"] <= 1 << 20
    assert 4 * N ** 3 / res.stats["staged_bytes_peak"] >= 16
    r = N * 0.42
    assert res.row[0] == pytest.approx(4 / 3 * np.pi * r**3, rel=0.01)
    # MC over a binary mask overestimates a smooth sphere's area by the
    # usual ~8% staircase bias; gate loosely, the parity tests do the
    # exactness work
    assert res.row[1] == pytest.approx(4 * np.pi * r**2, rel=0.12)
    assert res.row[2] == pytest.approx(2 * r, rel=0.02)


@pytest.mark.skipif(os.environ.get("REPRO_TILED_BIG") != "1",
                    reason="1024^3 demo: set REPRO_TILED_BIG=1 (minutes)")
def test_gib_scale_volume_streams_under_64x_budget():
    # the ISSUE acceptance case: a 1024^3 synthetic (4 GiB materialized)
    # through the tiled path under a budget >= 64x smaller
    N = 1024

    def sphere(z0, z1):
        ax = ((np.arange(N) - N / 2) / (N * 0.45)) ** 2
        az = ((np.arange(z0, z1) - N / 2) / (N * 0.45)) ** 2
        return (ax[:, None, None] + ax[None, :, None]
                + az[None, None, :] < 1.0).astype(np.float32)

    budget = (4 * N ** 3) // 64  # 64 MiB
    ex = PlanExecutor(backend="ref", mc_chunk=4)
    tx = TiledExtractor(ex, budget_bytes=budget, tile_prune="bounds")
    res = tx.extract(TiledCase(FnSlabSource(sphere, (N, N, N))))
    assert res.stats["staged_bytes_peak"] <= budget
    r = N * 0.45
    assert res.row[0] == pytest.approx(4 / 3 * np.pi * r**3, rel=0.005)
    assert res.row[2] == pytest.approx(2 * r, rel=0.01)
