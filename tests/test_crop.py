"""Pass-0 host crop lockdown: ``roi_box`` and ``PlanExecutor._crop_case``.

The crop finds the ROI box from axis projections and writes the crop, its
float32 cast, the 1-voxel pad and the shape-bucket pad in one copy per
array.  Its output must be byte for byte what the plain composition gives
(``np.nonzero`` box, ``astype``, ``np.pad`` by one, ``np.pad`` to
``plan.shape_bucket``), kept here as the reference, for every mask and
image dtype the loaders give, in C and Fortran order.  The validations
still read the whole input: a NaN outside the ROI quarantines the study.
"""
import numpy as np
import pytest

from repro.core import plan as planlib
from repro.core.executor import PlanExecutor
from repro.core.shape_features import roi_box
from repro.data.synthetic import make_case

pytestmark = pytest.mark.tier1

SHAPE = (21, 18, 15)
FAMILIES = ("shape", "firstorder")


@pytest.fixture(autouse=True)
def _isolated_autotune(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


def _reference_crop(image, mask):
    """The plain composition: nonzero box, cast, pad by one, bucket pad."""
    idx = np.nonzero(mask)
    lo = [int(i.min()) for i in idx]
    hi = [int(i.max()) + 1 for i in idx]
    sl = tuple(slice(l, h) for l, h in zip(lo, hi))
    m = np.pad(np.ascontiguousarray(mask[sl]).astype(np.float32), 1)
    im = None if image is None else np.pad(
        np.ascontiguousarray(image[sl]).astype(np.float32), 1)
    roi_shape = m.shape
    bshape = planlib.shape_bucket(tuple(s - 2 for s in roi_shape))
    pad = [(0, bs - ms) for bs, ms in zip(bshape, roi_shape)]
    return (np.pad(m, pad), None if im is None else np.pad(im, pad),
            bshape, roi_shape)


def _blob(lo, hi, seed, shape=SHAPE, value=True, dtype=bool):
    """A random mask whose bounding box is exactly ``[lo, hi)``."""
    rng = np.random.default_rng(seed)
    mask = np.zeros(shape, dtype)
    sl = tuple(slice(l, h) for l, h in zip(lo, hi))
    mask[sl] = np.where(rng.random(mask[sl].shape) < 0.4, value, 0)
    # pin the box: one voxel on each of its six faces
    for ax in range(3):
        for edge in (lo[ax], hi[ax] - 1):
            at = [(l + h - 1) // 2 for l, h in zip(lo, hi)]
            at[ax] = edge
            mask[tuple(at)] = value
    return mask


def _image(dtype, seed, shape=SHAPE):
    rng = np.random.default_rng(seed)
    if np.issubdtype(dtype, np.integer):
        return rng.integers(-1000, 3000, shape).astype(dtype)
    return (rng.normal(40.0, 300.0, shape)).astype(dtype)


def _assert_matches_reference(p, image, mask):
    m0, i0, b0, r0 = _reference_crop(image, mask)
    assert p.mask.dtype == m0.dtype and np.array_equal(p.mask, m0)
    if i0 is None:
        assert p.image is None
    else:
        assert p.image.dtype == i0.dtype and np.array_equal(p.image, i0)
    assert p.shape == b0 and p.roi_shape == r0


MASK_DTYPES = {
    "bool": (bool, True),
    "uint8": (np.uint8, 1),
    "int16-label2": (np.int16, 2),
    "float32": (np.float32, 1.0),
    "float64": (np.float64, 1.0),
}
IMAGE_DTYPES = {"float32": np.float32, "int16": np.int16,
                "float64": np.float64}


@pytest.mark.parametrize("image_dtype", IMAGE_DTYPES)
@pytest.mark.parametrize("mask_dtype", MASK_DTYPES)
def test_crop_case_matches_reference_dtypes(mask_dtype, image_dtype):
    dt, value = MASK_DTYPES[mask_dtype]
    mask = _blob((3, 2, 4), (17, 12, 11), seed=1, value=value, dtype=dt)
    image = _image(IMAGE_DTYPES[image_dtype], seed=2)
    p = PlanExecutor(families=FAMILIES)._crop_case(image, mask, (1, 1, 1))
    _assert_matches_reference(p, image, mask)
    assert isinstance(p.mask, np.ndarray) and p.mask.flags.c_contiguous


@pytest.mark.parametrize("order", ["mask", "image", "both"])
def test_crop_case_matches_reference_fortran_order(order):
    mask = _blob((2, 5, 1), (19, 16, 9), seed=3, dtype=np.uint8, value=1)
    image = _image(np.float64, seed=4)
    if order in ("mask", "both"):
        mask = np.asfortranarray(mask)
    if order in ("image", "both"):
        image = np.asfortranarray(image)
    p = PlanExecutor(families=FAMILIES)._crop_case(image, mask, (1, 1, 1))
    _assert_matches_reference(p, image, mask)
    assert p.mask.flags.c_contiguous and p.image.flags.c_contiguous


# ROIs touching each face of the volume, the whole volume, one voxel
PLACEMENTS = {
    "x-lo": ((0, 4, 3), (9, 12, 10)),
    "x-hi": ((12, 4, 3), (21, 12, 10)),
    "y-lo": ((5, 0, 3), (15, 7, 10)),
    "y-hi": ((5, 11, 3), (15, 18, 10)),
    "z-lo": ((5, 4, 0), (15, 12, 6)),
    "z-hi": ((5, 4, 9), (15, 12, 15)),
    "whole": ((0, 0, 0), SHAPE),
}


@pytest.mark.parametrize("place", PLACEMENTS)
def test_crop_case_matches_reference_roi_placement(place):
    lo, hi = PLACEMENTS[place]
    mask = _blob(lo, hi, seed=5)
    image = _image(np.float32, seed=6)
    p = PlanExecutor(families=FAMILIES)._crop_case(image, mask, (1, 1, 1))
    _assert_matches_reference(p, image, mask)


@pytest.mark.parametrize("at", [(0, 0, 0), (20, 17, 14), (7, 9, 3)])
def test_crop_case_matches_reference_single_voxel(at):
    mask = np.zeros(SHAPE, bool)
    mask[at] = True
    image = _image(np.int16, seed=7)
    p = PlanExecutor(families=FAMILIES)._crop_case(image, mask, (1, 1, 1))
    _assert_matches_reference(p, image, mask)
    assert p.roi_shape == (3, 3, 3) and p.mask.sum() == 1


def test_crop_case_empty_mask():
    sp = (0.7, 0.7, 2.5)
    p = PlanExecutor(families=FAMILIES)._crop_case(
        _image(np.float32, seed=8), np.zeros(SHAPE, bool), sp)
    assert p.mask is None and p.image is None and p.shape is None
    np.testing.assert_array_equal(p.spacing, np.asarray(sp, np.float32))


@pytest.mark.parametrize("image", ["none", "given"])
def test_crop_case_shape_only(image):
    mask = _blob((4, 3, 2), (16, 14, 12), seed=9)
    img = None if image == "none" else _image(np.float32, seed=10)
    p = PlanExecutor()._crop_case(img, mask, (1, 1, 1))
    assert p.image is None
    _assert_matches_reference(p, None, mask)


# ---------------------------------------------------------------------------
# roi_box: the np.nonzero box, from projections
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("order", ["C", "F"])
@pytest.mark.parametrize("density", [0.0, 1e-3, 0.02, 0.3, 1.0])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_roi_box_matches_nonzero(seed, density, order):
    rng = np.random.default_rng(seed)
    shape = tuple(int(s) for s in rng.integers(1, 40, 3))
    mask = rng.random(shape) < density
    if not mask.any():  # density 0: the single-voxel mask
        mask[tuple(int(rng.integers(0, s)) for s in shape)] = True
    mask = np.asarray(mask, order=order)
    idx = np.nonzero(mask)
    assert roi_box(mask) == ([int(i.min()) for i in idx],
                             [int(i.max()) + 1 for i in idx])


@pytest.mark.parametrize("shape", [(5, 6, 7), (4, 9), (11,)])
def test_roi_box_empty_is_none(shape):
    assert roi_box(np.zeros(shape, bool)) is None
    assert roi_box(np.zeros(shape, np.float32, order="F")) is None


# ---------------------------------------------------------------------------
# validations still read the whole input
# ---------------------------------------------------------------------------


def test_nan_image_outside_roi_quarantines():
    img, msk, sp = make_case((16, 16, 16), seed=11)
    # a margin of 2 voxels puts (0, 0, 0) outside the ROI's box
    img = np.pad(np.asarray(img, np.float32), 2, constant_values=40.0)
    msk = np.pad(np.asarray(msk), 2)
    assert min(roi_box(msk)[0]) >= 2
    bad = img.copy()
    bad[0, 0, 0] = np.nan
    ex = PlanExecutor(families=FAMILIES)
    rows, stats = ex.run([(img, msk, sp), (bad, msk, sp)])
    assert np.isnan(rows[1]).all() and not np.isnan(rows[0]).any()
    assert set(stats["errors"]) == {1}
    assert "non-finite intensity" in stats["errors"][1]


@pytest.mark.parametrize("where", ["inside", "outside"])
def test_nan_float_mask_quarantines(where):
    img, msk, sp = make_case((16, 16, 16), seed=13)
    bad = np.asarray(msk, np.float32).copy()
    pick = np.argwhere(bad != 0 if where == "inside" else bad == 0)[0]
    bad[tuple(pick)] = np.nan
    ex = PlanExecutor(families=FAMILIES)
    p = ex._prep_case_safe((img, bad, sp))
    assert p.mask is None and "non-finite mask" in p.error
    rows, stats = ex.run([(img, msk, sp), (img, bad, sp)])
    assert np.isnan(rows[1]).all() and not np.isnan(rows[0]).any()
    assert set(stats["errors"]) == {1}
    assert "non-finite mask" in stats["errors"][1]
