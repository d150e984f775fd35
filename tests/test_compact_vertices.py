"""Pass-0 vertex compaction: the sort-free form against a stable argsort.

``ref.compact_vertices`` finds the first ``cap`` active edges of the
dense vertex fields by a prefix count.  Its contract, pinned here against
the stable-argsort formulation it replaced (kept below as the oracle):

  * the first ``min(n, cap)`` slots hold the same vertices, in the same
    order (x-field, y-field, z-field, row-major), bit for bit;
  * the slots after them are zero with a False mask (the argsort filled
    them with inactive edge positions that no consumer reads);
  * ``n`` is the total active count, also when ``cap < n``.

The second half runs the cohort stream and the drop-in with the argsort
patched in, and asserts bit-identical rows: pruning and the pair sweep
do not read padded slots, so the zero padding changes no feature.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ShapeFeatureExtractor, crop_to_roi
from repro.core import plan as planlib
from repro.core.pipeline import BatchedExtractor
from repro.data.synthetic import make_case
from repro.kernels import ops
from repro.kernels import ref

pytestmark = pytest.mark.tier1


def _argsort_compact(f, max_vertices):
    """The stable-argsort compaction: the oracle of the active slots."""
    pos = jnp.concatenate([f.vx.reshape(-1, 3), f.vy.reshape(-1, 3),
                           f.vz.reshape(-1, 3)])
    act = jnp.concatenate([f.ax.reshape(-1), f.ay.reshape(-1),
                           f.az.reshape(-1)])
    n = jnp.sum(act.astype(jnp.int32))
    order = jnp.argsort(~act, stable=True)[:max_vertices]
    return pos[order], act[order], n


def _fields(mask):
    return ref.vertex_fields(jnp.asarray(mask, jnp.float32), 0.5,
                             (0.8, 1.0, 1.25))


def _assert_matches_argsort(f, cap):
    verts, vmask, n = (np.asarray(a) for a in ref.compact_vertices(f, cap))
    want_v, want_m, want_n = (np.asarray(a) for a in _argsort_compact(f, cap))
    assert int(n) == int(want_n)
    assert verts.shape == (cap, 3) and verts.dtype == np.float32
    assert vmask.shape == (cap,) and vmask.dtype == np.bool_
    k = min(int(n), cap)
    np.testing.assert_array_equal(verts[:k], want_v[:k])
    np.testing.assert_array_equal(vmask[:k], want_m[:k])
    assert vmask[:k].all()
    assert not vmask[k:].any()
    assert not verts[k:].any()  # padded slots are zero
    return int(n)


# ---------------------------------------------------------------------------
# the kernel: seeded masks, caps around n, edge fields
# ---------------------------------------------------------------------------

BUCKETS = ((24, 20, 16), (40, 33, 17), (64, 48, 40))


@pytest.mark.parametrize("where", ["below", "at", "above"])
@pytest.mark.parametrize("shape", BUCKETS, ids=lambda s: "x".join(map(str, s)))
def test_seeded_masks_match_argsort(shape, where):
    _, mask, _ = make_case(shape, seed=sum(shape))
    f = _fields(mask)
    n = int(ref.count_vertices(f))
    assert n > 8
    cap = {"below": n // 3, "at": n, "above": ops.vertex_bucket(n) + 5}[where]
    assert _assert_matches_argsort(f, cap) == n


@pytest.mark.parametrize("shape", [(12, 10, 9), (1, 1, 1)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("cap", [1, 7, 512])
def test_empty_field_is_all_padding(shape, cap):
    f = _fields(np.zeros(shape, np.float32))
    verts, vmask, n = ref.compact_vertices(f, cap)
    assert int(n) == 0
    assert not np.asarray(vmask).any() and not np.asarray(verts).any()
    _assert_matches_argsort(f, cap)


@pytest.mark.parametrize("frac", [0.25, 1.0])
def test_all_active_field(frac):
    # a 3-D checkerboard flips across every edge: each slot is active
    i, j, k = np.indices((11, 9, 8))
    mask = ((i + j + k) % 2).astype(np.float32)
    f = _fields(mask)
    total = sum(int(np.prod(a.shape)) for a in (f.ax, f.ay, f.az))
    assert int(ref.count_vertices(f)) == total
    assert _assert_matches_argsort(f, int(total * frac)) == total


@pytest.mark.parametrize("shape", [(9, 7, 1), (1, 8, 6), (6, 1, 5)],
                         ids=lambda s: "x".join(map(str, s)))
def test_flat_volume(shape):
    # an axis of one voxel leaves that axis' edge field empty
    mask = np.zeros(shape, np.float32)
    mask[tuple(slice(min(2, s - 1), max(s - 2, 1)) for s in shape)] = 1.0
    f = _fields(mask)
    n = int(ref.count_vertices(f))
    assert n > 0
    for cap in (n // 2, n, n + 9):
        _assert_matches_argsort(f, cap)


@pytest.mark.parametrize("face", range(6))
def test_roi_on_each_face(face):
    # a box flush with one face of the bucket: the active edges sit in the
    # first or last rows of the fields, where the prefix count starts or ends
    shape = (20, 17, 14)
    mask = np.zeros(shape, np.float32)
    axis, high = divmod(face, 2)
    sl = [slice(3, s - 3) for s in shape]
    sl[axis] = slice(shape[axis] - 6, None) if high else slice(0, 6)
    mask[tuple(sl)] = 1.0
    f = _fields(mask)
    n = int(ref.count_vertices(f))
    for cap in (n // 2, n, n + 100):
        _assert_matches_argsort(f, cap)


# ---------------------------------------------------------------------------
# end to end: rows bit-identical to the argsort's
# ---------------------------------------------------------------------------

STUDIES = ((40, 36, 30), (24, 20, 16), (32, 28, 20), (36, 30, 26))


def _studies():
    return [make_case(s, seed=100 + i) for i, s in enumerate(STUDIES)]


def _rows_with(compact, monkeypatch, **kw):
    """Stream the studies with ``compact`` as pass 0's compaction.

    Every compiled program is dropped first: ``_compact_cap`` and the
    one-pass path look ``ops.compact_vertices`` up when they trace.
    """
    calls = []

    def spy(fields, cap):
        calls.append(cap)
        return compact(fields, cap)

    monkeypatch.setattr(ops, "compact_vertices", spy)
    jax.clear_caches()
    bx = BatchedExtractor(backend="ref", families=("shape", "firstorder",
                                                   "glcm"), **kw)
    rows = [np.asarray(r) for r in bx.extract_stream(_studies(), window=4)]
    monkeypatch.undo()
    jax.clear_caches()
    assert calls, "the compaction under test never ran"
    return rows, bx.executor.transfer_log


@pytest.mark.parametrize("kw", [
    {"prep": "count"},
    {"prep": "hint"},
    {"prep": "count", "device_compact": False},
], ids=["count", "hint-overflow", "host-compact"])
def test_stream_rows_bit_identical_to_argsort(monkeypatch, kw):
    if kw["prep"] == "hint":
        # every hint collapses to the bucket floor: pass 0 keeps the first
        # cap actives of each study and the collector retries count-sized
        monkeypatch.setattr(planlib, "vertex_hint", lambda *a, **k: 1)
    want, _ = _rows_with(_argsort_compact, monkeypatch, **kw)
    if kw["prep"] == "hint":
        monkeypatch.setattr(planlib, "vertex_hint", lambda *a, **k: 1)
    got, log = _rows_with(ref.compact_vertices, monkeypatch, **kw)
    if kw["prep"] == "hint":
        assert log.get("hint_retry", 0) >= 1  # the overflow path really ran
    assert len(got) == len(want) == len(STUDIES)
    for i, (a, b) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(a, b, err_msg=f"study {i}")


def test_dropin_diameter_row_bit_identical_to_argsort(monkeypatch):
    img, msk, sp = make_case((44, 38, 30), seed=9)
    _, m_roi, _ = crop_to_roi(img, msk)  # cropped and zero-padded by one
    ext = ShapeFeatureExtractor(backend="ref")
    got_d, got_n = ext.diameter_features(jnp.asarray(m_roi), sp)
    monkeypatch.setattr(ops, "compact_vertices", _argsort_compact)
    want_d, want_n = ext.diameter_features(jnp.asarray(m_roi), sp)
    assert got_n == want_n
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
