"""First-order + GLCM feature families: parity, registry, executor wiring.

The contracts under test (see kernels/firstorder.py, kernels/glcm.py,
core/plan.py, core/executor.py):

* FIRST-ORDER BITWISE parity: the Pallas kernel's packed stats equal the
  reference oracle's bit-for-bit, for every block size (the canonical-
  chunk left-fold contract makes ``block`` a pure performance axis), and
  batched extraction equals single-case extraction bit-for-bit;
* GLCM EXACTNESS: count matrices are integer-valued f32 and exactly
  equal across backends and blocks (one-hot-matmul scatter), so the
  host-derived Haralick rows are bitwise identical too (well inside the
  1e-5 tolerance the family promises);
* both reference paths match independent NUMPY oracles (float64 stats,
  ``np.add.at`` scatter);
* edge cases: empty mask, single voxel, constant intensity, bin-edge
  straddling values -- no NaNs, documented values;
* the family REGISTRY (plan.FAMILIES) resolves requests to canonical
  order, derives row widths/slices/names, and rejects unknown names;
* the EXECUTOR schedules family launches inside the sync-free window:
  enabling families never adds a prep/pass-1/pass-2 host fetch (each
  family drains through its own transfer stage), the shape columns of a
  multi-family run equal a shape-only run bit-for-bit, quarantined cases
  produce FULL-WIDTH NaN rows, and ``extract_stream`` == ``run`` ==
  ``extract_one`` per family;
* the ``firstorder/<backend>`` / ``glcm/<backend>`` autotune namespaces
  round-trip through the v3 cache.
"""
import numpy as np
import pytest

from repro.core import plan as planlib
from repro.core.executor import PlanExecutor
from repro.core.pipeline import BatchedExtractor
from repro.data.synthetic import make_case
from repro.kernels import firstorder as fok
from repro.kernels import glcm as gk
from repro.kernels import ops
from repro.runtime import autotune

pytestmark = pytest.mark.tier1

N_BINS = 32


@pytest.fixture(autouse=True)
def _isolated_autotune(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


def _stack(cases):
    imgs = np.stack([np.asarray(c[0], np.float32) for c in cases])
    msks = np.stack([np.asarray(c[1], np.float32) for c in cases])
    return imgs, msks


def _cases(n=3, shape=(20, 22, 18)):
    return [make_case(shape, seed=i) for i in range(n)]


# ---------------------------------------------------------------------------
# numpy oracles (independent of jax)
# ---------------------------------------------------------------------------


def np_quantize(image, mask, n_bins=N_BINS):
    """Bit-replica of ref.quantize_intensity in numpy f32."""
    img = np.asarray(image, np.float32).reshape(-1)
    m = np.asarray(mask).reshape(-1) > 0
    if not m.any():
        return np.zeros_like(img), np.float32(0), np.float32(0), np.float32(0)
    lo = np.float32(img[m].min())
    hi = np.float32(img[m].max())
    width = np.float32((hi - lo) / np.float32(n_bins))
    safe = width if width > 0 else np.float32(1.0)
    q = np.clip(np.floor((img - lo) / safe), 0.0, n_bins - 1).astype(np.float32)
    return np.where(m, q, np.float32(0)), lo, hi, width


def np_firstorder(image, mask, n_bins=N_BINS):
    """Float64 first-order oracle (histogram features off np_quantize)."""
    img = np.asarray(image, np.float64).reshape(-1)
    m = np.asarray(mask).reshape(-1) > 0
    if not m.any():
        return np.zeros(fok.N_FEATURES, np.float64)
    v = img[m]
    q, lo, hi, width = np_quantize(image, mask, n_bins)
    hist = np.bincount(q[m].astype(np.int64), minlength=n_bins).astype(np.float64)
    n = float(m.sum())
    p = hist / n
    ent = -np.sum(np.where(p > 0, p * np.log2(np.where(p > 0, p, 1.0)), 0.0))
    centers = lo + (np.arange(n_bins) + 0.5) * float(width)
    cum = np.cumsum(hist)

    def pct(f):
        return centers[int(np.argmax(cum >= f * n))]

    return np.array([
        v.mean(), np.sqrt(np.maximum(v.var(), 0.0)), v.min(), v.max(),
        pct(0.1), pct(0.5), pct(0.9),
        float(np.sum(np.float32(v) * np.float32(v), dtype=np.float64)),
        ent,
    ])


def np_glcm_matrix(image, mask, n_bins=N_BINS):
    """np.add.at scatter oracle for the symmetric count matrix."""
    q, _, _, _ = np_quantize(image, mask, n_bins)
    shape = np.asarray(image).shape
    q = q.reshape(shape)
    m = (np.asarray(mask) > 0).astype(np.float32)
    g = np.zeros((n_bins, n_bins), np.float64)
    for off in gk.OFFSETS:
        a = tuple(slice(None, -o) if o else slice(None) for o in off)
        b = tuple(slice(o, None) for o in off)
        valid = (m[a] * m[b]) > 0
        np.add.at(g, (q[a][valid].astype(np.int64),
                      q[b][valid].astype(np.int64)), 1.0)
    return (g + g.T).astype(np.float32)


# ---------------------------------------------------------------------------
# first-order: bitwise parity, block invariance, batched == single
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,seed", [((20, 22, 18), 1), ((33, 17, 25), 7)])
def test_fo_ref_vs_pallas_bitwise(shape, seed):
    imgs, msks = _stack([make_case(shape, seed=seed)])
    ref = np.asarray(fok.firstorder_packed_batch_ref(imgs, msks))
    pal = np.asarray(
        fok.firstorder_packed_batch_pallas(imgs, msks, interpret=True)
    )
    np.testing.assert_array_equal(ref, pal)
    np.testing.assert_array_equal(
        fok.features_from_packed_np(ref), fok.features_from_packed_np(pal)
    )


def test_fo_block_invariance_bitwise():
    imgs, msks = _stack(_cases(2))
    outs = [
        np.asarray(fok.firstorder_packed_batch_pallas(
            imgs, msks, block=b, interpret=True
        ))
        for b in (1024, 2048, 4096)
    ]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


def test_fo_block_must_tile_canonical_chunk():
    imgs, msks = _stack(_cases(1))
    with pytest.raises(ValueError, match="CANON_CHUNK"):
        fok.firstorder_packed_batch_pallas(imgs, msks, block=1536,
                                           interpret=True)


def test_fo_batched_equals_single_bitwise():
    cases = _cases(4, (18, 20, 16))
    imgs, msks = _stack(cases)
    batched = np.asarray(
        fok.firstorder_packed_batch_pallas(imgs, msks, interpret=True)
    )
    for i in range(len(cases)):
        single = np.asarray(fok.firstorder_packed_batch_pallas(
            imgs[i:i + 1], msks[i:i + 1], interpret=True
        ))[0]
        np.testing.assert_array_equal(batched[i], single)


def test_fo_matches_numpy_oracle():
    img, msk, _ = make_case((24, 21, 19), seed=3)
    row = ops.firstorder_features_batch(img[None], msk[None],
                                       backend="ref")[0]
    want = np_firstorder(img, msk)
    # f32 chunk-fold sums vs float64: loose on the moments, exact-ish on
    # order statistics (min/max/percentiles are picked, not accumulated)
    np.testing.assert_allclose(row, want, rtol=1e-3)
    np.testing.assert_allclose(row[2:7], want[2:7], rtol=1e-6)


# ---------------------------------------------------------------------------
# glcm: integer-exact matrices, scatter oracle, batched == single
# ---------------------------------------------------------------------------


def _constant_cube(n=48):
    """One constant-intensity n^3 ROI filling its volume: every pair lands
    in cell (0, 0), 3 * (n - 1) * n^2 of them (324,864 at n = 48, past
    2**16 and spread over many blocks)."""
    return (np.full((1, n, n, n), 100.0, np.float32),
            np.ones((1, n, n, n), np.float32))


@pytest.mark.parametrize("block,n_bins,case", [
    pytest.param(512, N_BINS, "random", id="512"),
    pytest.param(2048, N_BINS, "random", id="2048"),
    pytest.param(512, 16, "random", id="512-bins16"),
    pytest.param(2048, 16, "random", id="2048-bins16"),
    pytest.param(512, 64, "random", id="512-bins64"),
    pytest.param(2048, 64, "random", id="2048-bins64"),
    pytest.param(2048, N_BINS, "constant48", id="2048-constant48"),
])
def test_glcm_ref_vs_pallas_exact(block, n_bins, case):
    imgs, msks = _stack(_cases(2)) if case == "random" else _constant_cube()
    ref = np.asarray(gk.glcm_matrix_batch_ref(imgs, msks, n_bins=n_bins))
    pal = np.asarray(gk.glcm_matrix_batch_pallas(
        imgs, msks, n_bins=n_bins, block=block, interpret=True))
    np.testing.assert_array_equal(ref, pal)
    for i in range(len(imgs)):
        np.testing.assert_array_equal(
            pal[i], np_glcm_matrix(imgs[i], msks[i], n_bins))
    # integer-valued counts, symmetric
    np.testing.assert_array_equal(ref, np.round(ref))
    np.testing.assert_array_equal(ref, np.transpose(ref, (0, 2, 1)))
    np.testing.assert_array_equal(
        gk.glcm_features_from_matrix_np(ref, n_bins),
        gk.glcm_features_from_matrix_np(pal, n_bins),
    )
    if case == "constant48":
        assert pal[0, 0, 0] == 2 * 3 * 47 * 48 * 48  # symmetrised
        assert pal.sum() == pal[0, 0, 0]


def test_glcm_matches_numpy_scatter():
    img, msk, _ = make_case((19, 23, 17), seed=5)
    ref = np.asarray(gk.glcm_matrix_batch_ref(img[None], msk[None]))[0]
    np.testing.assert_array_equal(ref, np_glcm_matrix(img, msk))


def test_glcm_batched_equals_single_exact():
    cases = _cases(3, (16, 18, 20))
    imgs, msks = _stack(cases)
    batched = np.asarray(gk.glcm_matrix_batch_pallas(imgs, msks,
                                                     interpret=True))
    for i in range(len(cases)):
        single = np.asarray(gk.glcm_matrix_batch_pallas(
            imgs[i:i + 1], msks[i:i + 1], interpret=True
        ))[0]
        np.testing.assert_array_equal(batched[i], single)


# ---------------------------------------------------------------------------
# edge cases (both backends)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_empty_mask_zero_rows(backend):
    img = np.zeros((12, 12, 12), np.float32)
    msk = np.zeros((12, 12, 12), np.float32)
    kw = {} if backend == "ref" else {"block": 2048}
    fo = ops.firstorder_features_batch(img[None], msk[None], backend=backend,
                                       **kw)[0]
    gl = ops.glcm_features_batch(img[None], msk[None], backend=backend,
                                 **kw)[0]
    np.testing.assert_array_equal(fo, np.zeros(fok.N_FEATURES))
    np.testing.assert_array_equal(gl, np.zeros(gk.N_FEATURES))


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_single_voxel(backend):
    img = np.zeros((10, 10, 10), np.float32)
    msk = np.zeros((10, 10, 10), np.float32)
    img[4, 5, 6] = 42.5
    msk[4, 5, 6] = 1.0
    kw = {} if backend == "ref" else {"block": 2048}
    fo = ops.firstorder_features_batch(img[None], msk[None], backend=backend,
                                       **kw)[0]
    x = np.float32(42.5)
    np.testing.assert_array_equal(
        fo, [x, 0.0, x, x, x, x, x, x * x, 0.0]
    )
    # one voxel has no co-occurring neighbour inside the mask
    gl = ops.glcm_features_batch(img[None], msk[None], backend=backend,
                                 **kw)[0]
    np.testing.assert_array_equal(gl, np.zeros(gk.N_FEATURES))


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_constant_intensity(backend):
    img = np.full((10, 12, 9), 7.0, np.float32)
    msk = np.zeros((10, 12, 9), np.float32)
    msk[2:7, 3:9, 2:6] = 1.0
    n = msk.sum()
    kw = {} if backend == "ref" else {"block": 2048}
    fo = ops.firstorder_features_batch(img[None], msk[None], backend=backend,
                                       **kw)[0]
    np.testing.assert_array_equal(
        fo, [7.0, 0.0, 7.0, 7.0, 7.0, 7.0, 7.0, 49.0 * n, 0.0]
    )
    # single gray level: contrast 0, correlation defined as 1, idm/energy 1
    gl = ops.glcm_features_batch(img[None], msk[None], backend=backend,
                                 **kw)[0]
    np.testing.assert_array_equal(gl, [0.0, 1.0, 1.0, 1.0])


def test_bin_edge_straddling_values():
    # integer intensities 0..31 put the masked max EXACTLY on the top
    # edge: floor((hi-lo)/width) == n_bins must clip into the last bin,
    # and the histogram must still count every masked voxel
    img = np.tile(np.arange(32, dtype=np.float32), 32).reshape(8, 16, 8)
    msk = np.ones((8, 16, 8), np.float32)
    packed = np.asarray(
        fok.firstorder_packed_batch_ref(img[None], msk[None])
    )[0]
    hist = packed[fok.N_MOMENTS:fok.N_MOMENTS + N_BINS]
    assert packed[0] == img.size
    assert hist.sum() == img.size
    np.testing.assert_array_equal(hist, np.full(N_BINS, img.size / N_BINS))
    q, lo, hi, width = np_quantize(img, msk)
    assert (lo, hi) == (0.0, 31.0) and q.max() == N_BINS - 1


# ---------------------------------------------------------------------------
# registry (plan layer)
# ---------------------------------------------------------------------------


def test_registry_resolution_and_layout():
    assert planlib.resolve_families(None) == ("shape",)
    assert planlib.resolve_families("glcm") == ("glcm",)
    # canonical order is registry order, independent of request order
    fams = planlib.resolve_families(("glcm", "shape", "firstorder"))
    assert fams == ("shape", "firstorder", "glcm")
    assert planlib.row_width(fams) == 7 + 9 + 4
    sl = planlib.family_slices(fams)
    assert sl["shape"] == slice(0, 7)
    assert sl["firstorder"] == slice(7, 16)
    assert sl["glcm"] == slice(16, 20)
    names = planlib.feature_names(fams)
    assert len(names) == 20 and names[7] == "Mean" and names[16] == "Contrast"
    assert planlib.needs_intensity(fams)
    assert not planlib.needs_intensity(("shape",))
    with pytest.raises(ValueError, match="unknown"):
        planlib.resolve_families(("shape", "wavelet"))
    with pytest.raises(ValueError):
        planlib.resolve_families(())


def test_meta_bytes_counts_intensity_volume():
    base = planlib.CaseMeta((32, 32, 32), (20, 20, 20), 1024, 500)
    with_img = planlib.CaseMeta((32, 32, 32), (20, 20, 20), 1024, 500,
                                intensity=True)
    assert (planlib.meta_bytes(with_img) - planlib.meta_bytes(base)
            == 4 * 32 * 32 * 32)


def test_plan_carries_families():
    metas = [planlib.CaseMeta((32, 32, 32), (20, 20, 20), 1024, 500,
                              intensity=True)]
    plan = planlib.build_plan(metas, families=("glcm", "shape"))
    assert plan.families == ("shape", "glcm")
    assert plan.stats()["families"] == ["shape", "glcm"]


# ---------------------------------------------------------------------------
# executor: sync-free windows, quarantine, stream/run/one parity
# ---------------------------------------------------------------------------


def test_families_ride_the_window_sync_free():
    cases = _cases(4) + [make_case((33, 17, 25), seed=9)]
    shape_only = PlanExecutor(backend="interpret")
    rows_s, stats_s = shape_only.run(cases)
    multi = PlanExecutor(backend="interpret",
                         families=("shape", "firstorder", "glcm"))
    rows_m, stats_m = multi.run(cases)

    # enabling families must not add a single shape-pass host fetch:
    # the transfer_log census of every pre-existing stage is unchanged
    for stage in ("prep", "pass1", "pass2a", "pass2b"):
        assert stats_m["host_fetches"].get(stage, 0) == \
            stats_s["host_fetches"].get(stage, 0), stage
    # family drains ride their own stages
    assert stats_m["host_fetches"]["firstorder"] >= 1
    assert stats_m["host_fetches"]["glcm"] >= 1

    sl = planlib.family_slices(multi.families)
    for rs, rm in zip(rows_s, rows_m):
        np.testing.assert_array_equal(rs, rm[sl["shape"]])


def test_stream_equals_run_equals_one_multi_family():
    cases = _cases(5, (18, 20, 16))
    ex = BatchedExtractor(backend="interpret",
                          families=("shape", "firstorder", "glcm"))
    rows, _ = ex.run(cases)
    streamed = list(ex.extract_stream(iter(cases), window=2))
    assert len(streamed) == len(rows)
    for a, b in zip(rows, streamed):
        np.testing.assert_array_equal(a, b)
    one = ex.extract_one(*cases[0])
    np.testing.assert_array_equal(rows[0], one)


def test_intensity_only_request_skips_shape_passes():
    cases = _cases(3)
    ex = PlanExecutor(backend="interpret", families="firstorder")
    rows, stats = ex.run(cases)
    assert rows[0].shape == (fok.N_FEATURES,)
    for stage in ("pass1", "pass2a", "pass2b"):
        assert stats["host_fetches"].get(stage, 0) == 0, stage
    full = PlanExecutor(
        backend="interpret", families=("shape", "firstorder")
    )
    rows_f, _ = full.run(cases)
    for r, rf in zip(rows, rows_f):
        np.testing.assert_array_equal(r, rf[7:])


def test_quarantine_multi_family_full_width_nan():
    good = _cases(3)
    img, msk, sp = make_case((16, 16, 16), seed=9)
    poisoned = (img, np.full_like(np.asarray(msk, np.float32), np.nan), sp)
    no_image = (None, msk, sp)
    fams = ("shape", "firstorder", "glcm")
    ex = PlanExecutor(backend="interpret", families=fams)
    rows, stats = ex.run(good + [poisoned, no_image])
    width = planlib.row_width(fams)
    for i in (3, 4):
        assert rows[i].shape == (width,)
        assert np.isnan(rows[i]).all()
    assert set(stats["errors"]) == {3, 4}
    assert "intensity" in stats["errors"][4]
    # the quarantined cases must not perturb their window-mates
    clean, _ = PlanExecutor(backend="interpret", families=fams).run(good)
    for a, b in zip(clean, rows[:3]):
        np.testing.assert_array_equal(a, b)


def test_missing_image_ok_when_shape_only():
    img, msk, sp = make_case((16, 16, 16), seed=2)
    ex = PlanExecutor(backend="interpret")
    rows, stats = ex.run([(None, msk, sp), (img, msk, sp)])
    assert not stats["errors"]
    np.testing.assert_array_equal(rows[0], rows[1])


# ---------------------------------------------------------------------------
# autotune namespaces
# ---------------------------------------------------------------------------


def test_family_autotune_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    shape = (16, 16, 16)
    cfg = autotune.get_family_config(
        "firstorder", shape, "interpret", blocks=(1024, 2048), repeat=1
    )
    assert cfg.block in (1024, 2048)
    cache = autotune.AutotuneCache()
    entry = cache.get(autotune.family_key("firstorder", shape, "interpret"))
    assert entry is not None and entry["block"] == cfg.block
    assert set(entry["table"]) == {"1024", "2048"}
    # a poisoned cache entry whose block violates the canonical-chunk
    # contract is rejected, not trusted
    cache.put(autotune.family_key("firstorder", shape, "interpret"),
              {"block": 1536, "us": 1.0, "table": {}})
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    cfg2 = autotune.get_family_config("firstorder", shape, "interpret")
    assert cfg2.block % fok.CANON_CHUNK == 0

    glcfg = autotune.get_family_config("glcm", shape, "ref")
    assert glcfg == autotune.DEFAULT_GLCM_CONFIG


def test_dispatcher_family_config_passthrough():
    from repro.core import dispatcher

    assert dispatcher.firstorder_config("interpret", (16, 16, 16), 4096) == 4096
    assert dispatcher.glcm_config("ref", (16, 16, 16)) == \
        autotune.DEFAULT_GLCM_CONFIG.block
