"""Two-pass pruned batched pipeline: batched-vs-single parity lockdown.

The contract under test (see core/pipeline.py): batching may never change a
feature value.  ``BatchedExtractor.extract_one`` runs the identical stages
case-by-case (same bucket padding, pruning bound, tuned configs, kernels)
and is the oracle; on the Pallas ('interpret') backend the batched rows
must be **bit-identical** to it, on the pure-jnp 'ref' backend identical up
to f32 rounding (XLA fuses shape-dependently -- the documented ulp caveat
of kernels/prune).  Plain-pytest seeded property mirrors of the hypothesis
suite (tests/test_prune_properties.py) ride along so the invariants are
exercised even in the minimal container without hypothesis.
"""
import functools

import numpy as np
import pytest

from repro.core.pipeline import BatchedExtractor, group_indices
from repro.core.shape_features import ShapeFeatureExtractor
from repro.data.synthetic import make_case
from repro.kernels import diameter as dk
from repro.kernels import ops, prune

pytestmark = pytest.mark.tier1


@pytest.fixture(autouse=True)
def _isolated_autotune(tmp_path, monkeypatch):
    # parity must not depend on (or pollute) the user's autotune cache
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


@functools.lru_cache(maxsize=None)
def _case(shape, seed):
    return make_case(shape, seed=seed)


def _blob_cases():
    # 48^3 blobs: ~3-4k vertices (cap 4096) pruning to the 512-bucket floor,
    # plus an elongated case landing in a different shape bucket
    return [
        _case((48, 48, 48), 1),
        _case((48, 48, 48), 2),
        _case((70, 20, 20), 4),
    ]


# ---------------------------------------------------------------------------
# batched == single, bit-for-bit (Pallas semantics)
# ---------------------------------------------------------------------------


def test_two_pass_bit_identical_to_single_interpret():
    bx = BatchedExtractor(backend="interpret")
    cases = _blob_cases()
    results, stats = bx.run(cases)
    assert stats["pruned_cases"] >= 2
    assert stats["buckets"] >= 2  # the elongated case straddles shapes
    for case, row in zip(cases, results):
        single = bx.extract_one(*case)
        np.testing.assert_array_equal(row, single)


def test_two_pass_matches_gold_extractor_interpret():
    """Against the user-facing single-case extractor: diameters bit-equal
    (same vertex point set; pruning exactness), volume/area to f32
    rounding (the bucket padding moves the MC centring origin)."""
    bx = BatchedExtractor(backend="interpret")
    cases = _blob_cases()[:2]
    results, _ = bx.run(cases)
    gold = ShapeFeatureExtractor(backend="interpret")
    for (img, msk, sp), row in zip(cases, results):
        f = gold.execute(img, msk, sp)
        want_d = np.asarray(
            [f["Maximum3DDiameter"], f["Maximum2DDiameterSlice"],
             f["Maximum2DDiameterRow"], f["Maximum2DDiameterColumn"]],
            np.float32,
        )
        np.testing.assert_array_equal(row[2:6], want_d)
        np.testing.assert_allclose(row[0], f["MeshVolume"], rtol=1e-6)
        np.testing.assert_allclose(row[1], f["SurfaceArea"], rtol=1e-6)
        assert row[6] == f["_n_mesh_vertices"]


def test_ref_backend_parity():
    bx = BatchedExtractor(backend="ref")
    cases = _blob_cases() + [_case((20, 18, 16), 5)]
    results, stats = bx.run(cases)
    assert stats["vertex_buckets"] >= 1
    for case, row in zip(cases, results):
        np.testing.assert_allclose(
            row, bx.extract_one(*case), rtol=1e-6, atol=1e-6
        )


# ---------------------------------------------------------------------------
# re-bucketing edge cases
# ---------------------------------------------------------------------------


def test_empty_mask_yields_zero_row_not_crash():
    """A 40k-case sweep must not die on one degenerate segmentation."""
    img = np.zeros((12, 12, 12), np.float32)
    empty = (img, np.zeros((12, 12, 12), np.float32), (1.0, 1.0, 1.0))
    good = _case((20, 18, 16), 5)
    bx = BatchedExtractor(backend="ref")
    results, stats = bx.run([empty, good, empty])
    assert stats["empty_cases"] == 2
    np.testing.assert_array_equal(results[0], np.zeros(7, np.float32))
    np.testing.assert_array_equal(results[2], np.zeros(7, np.float32))
    assert np.all(np.isfinite(results[1])) and results[1][0] > 0
    np.testing.assert_array_equal(
        bx.extract_one(*empty), np.zeros(7, np.float32)
    )
    # the strict single-case extractor keeps its documented ValueError
    with pytest.raises(ValueError, match="empty"):
        ShapeFeatureExtractor(backend="ref").execute(empty[0], empty[1])


def test_single_voxel_case():
    msk = np.zeros((9, 9, 9), np.float32)
    msk[4, 4, 4] = 1.0
    case = (np.zeros((9, 9, 9), np.float32), msk, (1.0, 1.0, 1.0))
    bx = BatchedExtractor(backend="ref")
    results, _ = bx.run([case, _case((20, 18, 16), 5)])
    np.testing.assert_allclose(results[0], bx.extract_one(*case), rtol=1e-6)
    assert np.all(np.isfinite(results[0]))
    assert 0.0 < results[0][2] < 4.0  # one-voxel surface: ~voxel-scale d3


def test_all_cases_pruned_to_same_bucket():
    """Identical-geometry cases must collapse to ONE pruned sub-batch."""
    case = _case((48, 48, 48), 7)
    bx = BatchedExtractor(backend="ref")
    results, stats = bx.run([case] * 3)
    assert stats["buckets"] == 1 and stats["vertex_buckets"] == 1
    assert stats["pruned_cases"] == 3
    np.testing.assert_array_equal(results[0], results[1])
    np.testing.assert_array_equal(results[1], results[2])


def test_bucket_straddling_with_batch_padding():
    """Mixed M' buckets + batch_size that forces a padded trailing chunk."""
    cases = [_blob_cases()[0], _case((20, 18, 16), 5), _blob_cases()[1],
             _case((48, 48, 48), 3), _case((16, 16, 16), 6)]
    bx = BatchedExtractor(backend="ref")
    want = [bx.extract_one(*c) for c in cases]
    results, stats = bx.run(cases, batch_size=2)
    assert len(results) == len(cases) and all(r is not None for r in results)
    for w, r in zip(want, results):
        np.testing.assert_allclose(r, w, rtol=1e-6, atol=1e-6)


def test_permutation_invariance_of_outputs():
    """Re-bucketing never drops, duplicates, or cross-contaminates a case."""
    cases = _blob_cases() + [_case((20, 18, 16), 5)]
    bx = BatchedExtractor(backend="ref")
    base, _ = bx.run(cases)
    perm = [2, 0, 3, 1]
    permuted, _ = bx.run([cases[i] for i in perm])
    for j, i in enumerate(perm):
        np.testing.assert_array_equal(permuted[j], base[i])


def _features_one(mask, spacing, vertex_cap, backend):
    """The unpruned one-pass row of one bucket-padded case: MC, then the
    pair sweep over every compacted vertex (no pruning bound)."""
    vol, area = ops.mc_volume_area(mask, 0.5, spacing, backend=backend)
    fields = ops.vertex_fields(mask, 0.5, spacing)
    verts, vmask, n = ops.compact_vertices(fields, vertex_cap)
    d = ops.max_diameters(verts, vmask, backend=backend)
    return np.concatenate([np.asarray([vol, area], np.float32),
                           np.asarray(d, np.float32),
                           np.asarray([n], np.float32)])


def test_one_pass_two_pass_agree():
    """The unpruned one-pass computation stays a valid oracle."""
    cases = _blob_cases()[:2]
    bx = BatchedExtractor(backend="ref")
    two, stats = bx.run(cases)
    assert stats["pruned_cases"] >= 1  # the bound really shrank a case
    for case, row in zip(cases, two):
        p = bx.executor._prep_case(*case)
        one = _features_one(p.mask, p.spacing, p.vertex_cap, "ref")
        np.testing.assert_allclose(row, one, rtol=1e-5, atol=1e-5)


def test_stats_record_prune_trajectory():
    results, stats = BatchedExtractor(backend="ref").run(_blob_cases())
    assert stats["cases"] == 3 and stats["cases_per_second"] > 0
    assert 0.0 < stats["mean_keep_fraction"] <= 1.0
    assert stats["host_fetches"].get("pass1", 0) >= 1  # pass 1 ran
    assert stats["pruned_cases"] >= 2  # 48^3 blobs must actually shrink


# ---------------------------------------------------------------------------
# seeded mirrors of the hypothesis pruning-invariant properties
# ---------------------------------------------------------------------------


def _cloud(seed, m):
    rng = np.random.default_rng(seed)
    verts = (rng.normal(size=(m, 3)) * rng.uniform(1.0, 60.0)).astype(np.float32)
    mask = rng.random(m) > 0.2
    if mask.sum() < 2:
        mask[:2] = True
    return verts, mask


@pytest.mark.parametrize("seed", range(6))
def test_pruned_set_contains_farthest_pair_endpoints(seed):
    verts, mask = _cloud(seed, 128 + 16 * seed)
    keep, _ = prune.candidate_keep_mask(verts, mask)
    keep = np.asarray(keep)
    valid = np.nonzero(mask)[0]
    v = verts[valid]
    d = v[:, None, :] - v[None, :, :]
    q = (d * d).astype(np.float32)
    planes = (q[..., 0] + q[..., 1] + q[..., 2], q[..., 0] + q[..., 1],
              q[..., 0] + q[..., 2], q[..., 1] + q[..., 2])
    for s in planes:
        ii, jj = np.nonzero(s == s.max())
        for i in np.unique(np.concatenate([valid[ii], valid[jj]])):
            assert keep[i], f"true endpoint {i} pruned away (seed {seed})"


@pytest.mark.parametrize("seed", range(4))
def test_m_prime_never_exceeds_m(seed):
    verts, mask = _cloud(seed, 200)
    _, _, info = prune.prune_vertices(verts, mask)
    assert info.m_kept <= info.m_valid <= info.m_total


@pytest.mark.parametrize("seed", range(3))
def test_batched_prune_matches_single_prune_diameters(seed):
    """The vmapped pass-1 bound may tie-break differently from the single
    path, but both surviving sets must yield bit-identical diameters."""
    stack_v, stack_m = zip(*(_cloud(seed * 10 + j, 96) for j in range(3)))
    batch = ops.prune_candidates_batch(np.stack(stack_v), np.stack(stack_m))
    assert len(batch) == 3  # no case dropped or duplicated
    for (v, m), (v2, m2, info) in zip(zip(stack_v, stack_m), batch):
        assert info.m_kept <= info.m_valid
        sv, sm, _ = ops.prune_candidates(v, m)
        a = np.asarray(dk.max_diameters_sq_pallas(v2, m2, block=64, interpret=True))
        b = np.asarray(dk.max_diameters_sq_pallas(sv, sm, block=64, interpret=True))
        np.testing.assert_array_equal(a, b)


def test_group_indices_is_a_partition():
    keys = ["a", None, "b", "a", "c", None, "b", "a"]
    groups = group_indices(keys)
    flat = sorted(i for idxs in groups.values() for i in idxs)
    assert flat == [i for i, k in enumerate(keys) if k is not None]
    assert groups["a"] == [0, 3, 7]  # order-preserving within a group
