"""The plain reference, and the control that the comparison must fail."""
import itertools

import numpy as np
import pytest

from chipbench import compare, harness, reference as R
from chipbench.traffic import generate

F64 = R.Arith("float64")
SMALL = ("00004-2", "00007-2", "00009-2")  # the three smallest tumours


def _ball(n, r):
    g = np.arange(n) - (n - 1) / 2
    x, y, z = np.meshgrid(g, g, g, indexing="ij")
    return x * x + y * y + z * z <= r * r


def test_mesh_of_a_ball_approaches_the_sphere():
    m = np.pad(_ball(64, 25.0), 1)
    vol, area, n_tri = R.mesh(m, (1.0, 1.0, 1.0), F64)
    assert vol == pytest.approx(4 / 3 * np.pi * 25.0 ** 3, rel=0.01)
    assert area == pytest.approx(4 * np.pi * 25.0 ** 2, rel=0.1)
    assert n_tri > 0


def test_mesh_is_closed_translation_and_spacing_consistent():
    rng = np.random.default_rng(0)
    m = np.pad(rng.random((9, 8, 7)) > 0.5, 1)
    v1, a1, _ = R.mesh(m, (1.0, 1.0, 1.0), F64)
    v2, a2, _ = R.mesh(np.pad(m, ((3, 0), (0, 5), (2, 2))), (1, 1, 1), F64)
    assert (v2, a2) == (pytest.approx(v1, rel=1e-12), pytest.approx(a1))
    v3, _, _ = R.mesh(m, (2.0, 1.0, 0.5), F64)
    assert v3 == pytest.approx(v1, rel=1e-12)  # volume scales by 2*1*0.5


def test_vertices_are_the_midpoints_of_the_crossed_edges():
    m = np.zeros((4, 4, 4), bool)
    m[1:3, 1:2, 1:2] = True  # two voxels along x
    v = R.vertices(m, (1.0, 1.0, 1.0), F64)
    # 2 x-edges at the ends, 2 y- and 2 z-edges at each voxel
    assert len(v) == 2 + 4 + 4
    assert {tuple(p) for p in v} >= {(0.5, 1.0, 1.0), (2.5, 1.0, 1.0),
                                     (1.0, 0.5, 1.0), (2.0, 1.0, 1.5)}


def test_diameters_match_every_pair():
    rng = np.random.default_rng(3)
    v = np.round(rng.random((300, 3)) * 40) / 2
    want = []
    for axes in ((0, 1, 2), (0, 1), (0, 2), (1, 2)):
        p = v[:, axes]
        want.append(max(np.linalg.norm(a - b) for a, b in
                        itertools.combinations(p, 2)))
    assert R.diameters(v, F64) == pytest.approx(want, rel=1e-12)


def test_firstorder_on_known_values():
    x = np.arange(1, 33, dtype=np.float32)  # one value per bin
    f = dict(zip(R.FIRSTORDER, R.firstorder(x, F64)))
    assert f["Mean"] == pytest.approx(16.5)
    assert f["StdDev"] == pytest.approx(np.std(np.arange(1, 33)))
    assert (f["Minimum"], f["Maximum"]) == (1.0, 32.0)
    assert f["Entropy"] == pytest.approx(5.0)
    assert f["Energy"] == pytest.approx(np.sum(np.arange(1, 33) ** 2))
    width = 31 / 32
    assert f["Median"] == pytest.approx(1 + 15.5 * width)


def test_axes_of_a_box_are_its_uniform_spreads():
    m = np.zeros((12, 9, 7), bool)
    m[1:11, 1:8, 1:6] = True  # 10 x 7 x 5 voxels
    want = sorted(4.0 * np.sqrt((n * n - 1) / 12.0) for n in (10, 7, 5))
    assert R.axes(m, (1.0, 1.0, 1.0), F64) == pytest.approx(want, rel=1e-12)
    sp = (0.7, 1.1, 0.3)
    want = sorted(4.0 * s * np.sqrt((n * n - 1) / 12.0)
                  for n, s in zip((10, 7, 5), sp))
    assert R.axes(m, sp, F64) == pytest.approx(want, rel=1e-12)
    low = R.axes(m, sp, R.Arith("bfloat16"))
    assert low == pytest.approx(want, rel=1e-2) and low != tuple(want)


@pytest.fixture(scope="module")
def small_cases():
    cfg = harness.config("kits19-cohort")
    return generate.build_cases(cfg, {"cases": list(SMALL)}, 5)


def test_reference_agrees_with_the_programs_own_reference_path(small_cases):
    """The program's pure-jnp path, on the CPU, agrees to float32 rounding
    (vertex counts and diameters exactly)."""
    from repro.core import plan
    from repro.core.pipeline import BatchedExtractor

    fams = ("shape", "firstorder", "glcm")
    bx = BatchedExtractor(backend="ref", families=fams)
    names = plan.feature_names(fams)
    for c in small_cases:
        got = dict(zip(names, bx.extract_one(c.image, c.mask, c.spacing)))
        want = R.features(c, fams)
        for name in names:
            tol = 0 if name == "n_vertices" or "Diameter" in name else 5e-5
            assert got[name] == pytest.approx(want[name], rel=tol, abs=0), \
                (c.name, name)


def test_reference_agrees_with_the_drop_in_on_its_reference_path(small_cases):
    """``ShapeFeatureExtractor`` on its pure-jnp path, on the CPU, agrees
    to float32 rounding, the PCA axis lengths included."""
    from repro.core.shape_features import ShapeFeatureExtractor

    ext = ShapeFeatureExtractor(backend="ref")
    for c in small_cases:
        got = ext.execute(c.image, c.mask, c.spacing)
        want = R.features(c, ("shape",))
        for name in R.AXES + R.DIAMETERS + ("MeshVolume", "SurfaceArea"):
            assert got[name] == pytest.approx(want[name], rel=5e-5), \
                (c.name, name)
        assert got["_n_mesh_vertices"] == want["n_vertices"]


@pytest.mark.parametrize("config", ["kits19-cohort"])
def test_the_bfloat16_control_fails_the_configured_limits(config,
                                                          small_cases):
    cfg = harness.config(config)
    fams = tuple(cfg["extractor"].get("families", ("shape",)))
    refs = [R.features(c, fams) for c in small_cases]
    ctl = [R.features(c, fams, dtype="bfloat16") for c in small_cases]
    found = compare.numbers(cfg["check"], list(enumerate(ctl)), refs)
    ok, table = compare.verdict(cfg["check"], found)
    assert not ok, table
    # and the reference against itself passes every limit
    ok, _ = compare.verdict(cfg["check"], compare.numbers(
        cfg["check"], list(enumerate(refs)), refs))
    assert ok


def test_gaps():
    assert compare.gap(1.1, 1.0, "rel") == pytest.approx(0.1)
    assert compare.gap(3.0, 0.0, "rel") == 3.0
    assert compare.gap(2.0, 5.0, "abs") == 3.0
    assert compare.gap(float("nan"), 1.0, "abs") == float("inf")
    with pytest.raises(ValueError):
        compare.gap(1.0, 1.0, "ulp")
