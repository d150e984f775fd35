"""The first-order fold's centred second moment (``kernels/firstorder.py``).

StdDev comes from ``m2``, the sum of squared deviations that the canonical
chunk fold merges by Chan's pairwise update, and not from the one-pass
``sum_sq/n - mean**2``.  Locked here:

* the reference fold and the Pallas kernel (interpret mode) stay
  bit-identical for every block size and batch depth;
* at a kidney-sized ROI (millions of voxels at a mean of 100 and a spread
  of 15, as the benchmark's Table 2 kidneys) StdDev stays within float32
  rounding of float64, where the one-pass formula on the same sums misses;
* empty, constant, single-voxel and chunk-straddling masks give the exact
  moment;
* the out-of-core re-fold (``fold_packed_chunks``) gives the in-core bits.
"""
import warnings

import numpy as np
import pytest

from repro.core.executor import PlanExecutor
from repro.core.tiled import TiledExtractor
from repro.data.tiles import TiledCase
from repro.kernels import firstorder as fok

pytestmark = pytest.mark.tier1

STD = fok.FEATURES.index("StdDev")


@pytest.fixture(autouse=True)
def _isolated_autotune(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


def _ct(shape, seed, mean=100.0, sd=15.0):
    """CT-like intensities: float32 normal(mean, sd)."""
    rng = np.random.default_rng(seed)
    return (mean + sd * rng.standard_normal(shape)).astype(np.float32)


def _ellipsoid_mask(shape, fill=0.9):
    g = np.meshgrid(*(np.linspace(-1.0, 1.0, s) for s in shape),
                    indexing="ij")
    ball = sum(a * a for a in g) < 1.0
    box = np.all([np.abs(a) < fill for a in g], axis=0)
    return ball | box


def _packed(image, mask, backend, block=None):
    img = np.asarray(image, np.float32)[None]
    msk = np.asarray(mask, np.float32)[None]
    if backend == "ref":
        return np.asarray(fok.firstorder_packed_batch_ref(img, msk))[0]
    return np.asarray(fok.firstorder_packed_batch_pallas(
        img, msk, block=block or fok.DEFAULT_BLOCK, interpret=True))[0]


@pytest.mark.parametrize("depth", [1, 2, 4])
@pytest.mark.parametrize("block", [1024, 2048, 4096])
def test_reference_fold_and_kernel_agree_bitwise(block, depth):
    shape = (20, 22, 18)
    mask = _ellipsoid_mask(shape, fill=0.6)
    imgs = np.stack([_ct(shape, seed) for seed in range(depth)])
    msks = np.stack([mask.astype(np.float32)] * depth)
    ref = np.asarray(fok.firstorder_packed_batch_ref(imgs, msks))
    pal = np.asarray(fok.firstorder_packed_batch_pallas(
        imgs, msks, block=block, interpret=True))
    np.testing.assert_array_equal(ref, pal)
    np.testing.assert_array_equal(fok.features_from_packed_np(ref),
                                  fok.features_from_packed_np(pal))
    assert ref.shape == (depth, fok.packed_width())
    # the m2 lane holds the centred moment of each case
    for i in range(depth):
        v = imgs[i][mask].astype(np.float64)
        assert ref[i, fok.M2] == pytest.approx(np.sum((v - v.mean()) ** 2),
                                               rel=1e-5)


def test_stddev_stays_within_float32_rounding_at_kidney_size():
    """About 2.7 M masked voxels at a mean of 100 and a spread of 15: the
    merged moment comes within 5e-6 of float64 on every seed; the one-pass
    formula, on the very sums the fold carries, misses by more than 2e-5
    on the widest seed (its gap is a random walk of the rounding of
    ``sum_sq``, from 5e-6 to 9e-5 over these seeds)."""
    shape = (160, 160, 140)
    mask = _ellipsoid_mask(shape)
    assert 2.6e6 < mask.sum() < 2.8e6
    merged, one_pass = [], []
    for seed in range(6):
        image = _ct(shape, seed)
        p = _packed(image, mask, "ref")
        want = image[mask].astype(np.float64).std()
        merged.append(abs(float(fok.features_from_packed_np(p)[STD]) - want)
                      / want)
        n, s1, s2 = p[0], p[1], p[2]
        mean = s1 / n
        old = np.sqrt(np.maximum(s2 / n - mean * mean, np.float32(0.0)))
        one_pass.append(abs(float(old) - want) / want)
    assert max(merged) <= 5e-6, merged
    assert max(one_pass) > 2e-5, one_pass


def _straddling(shape=(16, 16, 16)):
    """Two voxels on either side of the first chunk boundary (flat indices
    1023 and 1024), 10 and 14: mean 12, m2 exactly 8, StdDev exactly 2."""
    image = np.zeros(shape, np.float32)
    mask = np.zeros(shape, np.float32)
    for flat, value in ((fok.CANON_CHUNK - 1, 10.0), (fok.CANON_CHUNK, 14.0)):
        idx = np.unravel_index(flat, shape)
        image[idx], mask[idx] = value, 1.0
    return image, mask, 8.0, 2.0


def _constant(shape=(16, 16, 16)):
    """Value 7 over 3,375 voxels, across four chunks: m2 exactly 0."""
    image = np.full(shape, 7.0, np.float32)
    mask = np.zeros(shape, np.float32)
    mask[:15, :15, :15] = 1.0
    return image, mask, 0.0, 0.0


def _single(shape=(16, 16, 16)):
    image = np.zeros(shape, np.float32)
    mask = np.zeros(shape, np.float32)
    image[9, 3, 5], mask[9, 3, 5] = 42.5, 1.0
    return image, mask, 0.0, 0.0


def _empty(shape=(16, 16, 16)):
    return (_ct(shape, 0), np.zeros(shape, np.float32), 0.0, 0.0)


@pytest.mark.parametrize("block", [1024, 4096])
@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("case", [_empty, _constant, _single, _straddling],
                         ids=["empty", "constant", "single", "straddling"])
def test_edge_masks_give_the_exact_moment(case, backend, block):
    image, mask, m2, sd = case()
    p = _packed(image, mask, backend, block)
    assert p[0] == mask.sum()
    assert p[fok.M2] == m2
    row = fok.features_from_packed_np(p)
    assert row[STD] == sd
    if not mask.any():
        np.testing.assert_array_equal(row, np.zeros(fok.N_FEATURES))


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_tiled_first_order_rows_equal_in_core_bitwise(backend):
    shape = (40, 44, 57)
    mask = _ellipsoid_mask(shape, fill=0.5).astype(np.float32)
    image = _ct(shape, 7)
    spacing = np.asarray([1.0, 1.25, 0.75], np.float32)
    ex = PlanExecutor(backend=backend, families=["firstorder"])
    incore = ex.extract_one(image, mask, spacing)
    for budget in (200_000, 60_000):
        tx = TiledExtractor(ex, budget_bytes=budget)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            res = tx.extract(TiledCase(mask, image=image, spacing=spacing))
        assert res.stats["tiles"] > 1
        np.testing.assert_array_equal(incore, res.row)
    v = image[mask > 0].astype(np.float64)
    assert incore[STD] == pytest.approx(v.std(), rel=1e-6)
