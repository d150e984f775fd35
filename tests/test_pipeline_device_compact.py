"""Device-resident pass-1 compaction: device == host bit-identity lockdown.

The contract under test (see core/pipeline.py and kernels/compact.py): the
batched pipeline's device compaction must be **bit-identical** to host
compaction -- same survivors, same stable order, same zero padding, same
features -- on every edge the host path handles: empty masks,
zero-survivor keeps, all-survivor keeps, exact cap-boundary counts, and
case permutations.  Kernel-level parity (Pallas interpret == jnp ref ==
host numpy) is asserted directly; pipeline-level parity compares the
batched rows with the host-compaction oracle: per case,
``ops.prune_candidates`` and the pair sweep (``extract_one``).
Seeded plain-pytest mirrors of the hypothesis compaction invariants
(tests/test_prune_properties.py) ride along for the minimal container.
"""
import functools
import json

import numpy as np
import pytest

from repro.core.pipeline import BatchedExtractor
from repro.data.synthetic import make_case
from repro.kernels import compact as ck
from repro.kernels import ops
from repro.kernels import prune
from repro.runtime import autotune

pytestmark = pytest.mark.tier1


@pytest.fixture(autouse=True)
def _isolated_autotune(tmp_path, monkeypatch):
    # parity must not depend on (or pollute) the user's autotune cache
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


@functools.lru_cache(maxsize=None)
def _case(shape, seed):
    return make_case(shape, seed=seed)


def _host_compact(verts, keep, cap):
    """The PR 2 host-side semantics: np.nonzero gather + zero pad."""
    idx = np.nonzero(keep)[0][:cap]
    out = np.zeros((cap, 3), np.float32)
    out[: len(idx)] = verts[idx]
    mask = np.zeros((cap,), bool)
    mask[: len(idx)] = True
    return out, mask, int(keep.sum())


def _keep_for(case: str, m: int, cap: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    if case == "random":
        return rng.random(m) < 0.3
    if case == "zero-survivor":
        return np.zeros(m, bool)
    if case == "all-survivor":
        return np.ones(m, bool)
    if case == "cap-boundary":  # exactly M' == cap survivors
        keep = np.zeros(m, bool)
        keep[rng.choice(m, size=cap, replace=False)] = True
        return keep
    if case == "overflow":  # more survivors than the cap: excess dropped
        keep = np.zeros(m, bool)
        keep[rng.choice(m, size=cap + 57, replace=False)] = True
        return keep
    raise ValueError(case)


# ---------------------------------------------------------------------------
# kernel-level parity: Pallas interpret == jnp ref == host numpy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "case", ["random", "zero-survivor", "all-survivor", "cap-boundary",
             "overflow"]
)
def test_compact_kernel_matches_host(case):
    m, cap = 1024, 512
    rng = np.random.default_rng(7)
    verts = rng.normal(size=(m, 3)).astype(np.float32) * 20.0
    keep = _keep_for(case, m, cap)
    ro, rm, rn = (np.asarray(x) for x in
                  ck.compact_batch_ref(verts[None], keep[None], cap))
    po, pm, pn = (np.asarray(x) for x in ck.compact_batch_pallas(
        verts[None], keep[None], cap, block=128, interpret=True))
    ho, hm, hn = _host_compact(verts, keep, cap)
    for o, mk, n in ((ro[0], rm[0], rn[0]), (po[0], pm[0], pn[0])):
        np.testing.assert_array_equal(o, ho)
        np.testing.assert_array_equal(mk, hm)
        assert n == hn  # total survivor count, pre-drop


def test_compact_batch_offset_resets_between_cases():
    """The SMEM running offset must reset per case: a batch of ragged keeps
    compacts identically to three single-case launches."""
    m, cap = 768, 256
    rng = np.random.default_rng(3)
    verts = rng.normal(size=(3, m, 3)).astype(np.float32)
    keep = np.stack([rng.random(m) < f for f in (0.05, 0.6, 0.0)])
    bo, bm, bn = (np.asarray(x) for x in ck.compact_batch_pallas(
        verts, keep, cap, block=128, interpret=True))
    for b in range(3):
        so, sm, sn = (np.asarray(x) for x in ck.compact_batch_pallas(
            verts[b][None], keep[b][None], cap, block=128, interpret=True))
        np.testing.assert_array_equal(bo[b], so[0])
        np.testing.assert_array_equal(bm[b], sm[0])
        assert bn[b] == sn[0] == keep[b].sum()


@pytest.mark.parametrize("block", [64, 128, 512])
def test_compact_block_size_is_value_invariant(block):
    """The scatter block (the autotuned axis) must never change the result."""
    m, cap = 512, 512
    rng = np.random.default_rng(11)
    verts = rng.normal(size=(2, m, 3)).astype(np.float32)
    keep = rng.random((2, m)) < 0.4
    want = [np.asarray(x) for x in ck.compact_batch_ref(verts, keep, cap)]
    got = [np.asarray(x) for x in ck.compact_batch_pallas(
        verts, keep, cap, block=block, interpret=True)]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


# ---------------------------------------------------------------------------
# plan_compaction: the shared pruned/keep-originals decision
# ---------------------------------------------------------------------------


def test_plan_compaction_degenerate_rules():
    plan = lambda mt, mv, mk: prune.plan_compaction(
        mt, mv, mk, ops.vertex_bucket
    )
    # < 2 valid vertices, < 2 survivors, nothing pruned: keep originals
    for mv, mk in ((1, 1), (100, 1), (100, 100), (100, 120)):
        cap, info = plan(4096, mv, mk)
        assert cap is None and not info.pruned and info.m_kept == mv
    # survivor bucket >= input cap: re-bucketing wins nothing
    cap, info = plan(512, 400, 100)
    assert cap is None and not info.pruned and info.m_kept == 400
    # a genuine shrink
    cap, info = plan(4096, 3000, 100)
    assert cap == 512 and info.pruned and info.m_kept == 100
    # cap boundary: M' == bucket exactly still shrinks 4096 -> 512
    cap, info = plan(4096, 3000, 512)
    assert cap == 512 and info.pruned


@pytest.mark.parametrize("seed", range(4))
def test_plan_matches_host_path_info(seed):
    """plan_compaction must reproduce the host path's PruneInfo exactly."""
    rng = np.random.default_rng(seed)
    m = 96 + 32 * seed
    verts = (rng.normal(size=(m, 3)) * 15.0).astype(np.float32)
    mask = rng.random(m) > 0.2
    if mask.sum() < 2:
        mask[:2] = True
    _, _, host_info = ops.prune_candidates(verts, mask)
    keep, _ = prune.candidate_keep_mask(verts, mask)
    _, info = prune.plan_compaction(
        m, int(mask.sum()), int(np.asarray(keep).sum()), ops.vertex_bucket
    )
    assert info == host_info


# ---------------------------------------------------------------------------
# pipeline-level parity: batched device compaction == host-compaction oracle
# ---------------------------------------------------------------------------


def _edge_cases():
    empty = (np.zeros((10, 10, 10), np.float32),
             np.zeros((10, 10, 10), np.float32), (1.0, 1.0, 1.0))
    voxel_m = np.zeros((9, 9, 9), np.float32)
    voxel_m[4, 4, 4] = 1.0
    voxel = (np.zeros((9, 9, 9), np.float32), voxel_m, (1.0, 1.0, 1.0))
    return [
        _case((48, 48, 48), 1),   # prunes to a smaller bucket
        empty,                    # empty mask: zero row
        _case((20, 18, 16), 5),   # small: keep-originals path
        voxel,                    # single voxel: degenerate prune
        _case((70, 20, 20), 4),   # different shape bucket
    ]


def _host_oracle(bx, cases):
    """Host compaction per case: the rows and the window stats it implies.

    Each non-empty case is prepped count-sized and pruned on the host by
    ``ops.prune_candidates``; its ``PruneInfo`` and pruned vertex bucket
    give the stats the batched window must report, and ``extract_one``
    (the same host pruning, then the pair sweep) gives its row.
    """
    infos, caps = [], set()
    for case in cases:
        p = bx.executor._prep_case(*case, prep="count")
        if p.mask is None:
            continue
        v2, _, info = ops.prune_candidates(p.verts, p.vmask)
        infos.append(info)
        caps.add(len(v2))
    stats = {
        "pruned_cases": sum(1 for inf in infos if inf.pruned),
        "vertex_buckets": len(caps),
        "mean_keep_fraction": float(
            np.mean([inf.keep_fraction for inf in infos])),
    }
    return [bx.extract_one(*c) for c in cases], stats


def _assert_matches_host_oracle(bx, cases):
    rows, stats = bx.run(cases)
    want_rows, want = _host_oracle(bx, cases)
    for key, value in want.items():
        assert stats[key] == value, key
    for i, (a, b) in enumerate(zip(rows, want_rows)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"case {i}")
    return stats


def test_device_compact_is_the_default():
    """Device compaction is the only path: the vertex data never leaves
    the device, so pass 0 fetches one count per non-empty case and
    nothing else, and the removed host-path options are refused."""
    cases = _edge_cases()
    _, stats = BatchedExtractor(backend="ref").run(cases)
    assert stats["empty_cases"] == 1
    assert stats["host_fetches"]["prep"] == len(cases) - 1
    for option in ("prune", "device_compact", "k_dirs", "compact_block"):
        with pytest.raises(TypeError, match=option):
            BatchedExtractor(backend="ref", **{option: None})


def test_device_vs_host_bit_identical_ref():
    stats = _assert_matches_host_oracle(BatchedExtractor(backend="ref"),
                                        _edge_cases())
    assert stats["empty_cases"] == 1 and stats["buckets"] == 2


def test_device_vs_host_bit_identical_interpret():
    """Pallas semantics: the compaction kernel itself runs (interpret) and
    the features must still match host compaction bit-for-bit."""
    cases = [_case((48, 48, 48), 2), _case((20, 18, 16), 5)]
    stats = _assert_matches_host_oracle(
        BatchedExtractor(backend="interpret"), cases)
    assert stats["pruned_cases"] >= 1  # the compaction kernel actually ran


def test_device_permutation_invariance():
    """Device re-bucketing never drops, duplicates, or cross-contaminates."""
    cases = _edge_cases()
    bx = BatchedExtractor(backend="ref")
    base, _ = bx.run(cases)
    perm = [3, 0, 4, 1, 2]
    permuted, _ = bx.run([cases[i] for i in perm])
    for j, i in enumerate(perm):
        np.testing.assert_array_equal(
            np.asarray(permuted[j]), np.asarray(base[i])
        )


def test_ambient_mesh_without_data_axis_is_ignored():
    """A train/serve use_mesh context (no 'data' axis) must not hijack the
    pipeline: the ambient mesh is adopted only when it can shard the batch."""
    import jax

    from repro.parallel.sharding import use_mesh

    mesh = jax.make_mesh((1,), ("model",))
    with use_mesh(mesh):
        bx = BatchedExtractor(backend="ref")
    assert bx.mesh is None  # not adopted: it cannot shard the data axis
    res, stats = bx.run([_case((20, 18, 16), 5)])
    assert stats["data_parallel"] == 1 and np.all(np.isfinite(res[0]))
    # a mesh WITH the data axis is still picked up
    dmesh = jax.make_mesh((1,), ("data",))
    with use_mesh(dmesh):
        bx2 = BatchedExtractor(backend="ref")
    assert bx2.mesh is dmesh


def test_device_batch_padding_chunks():
    """batch_size forcing padded trailing chunks must not corrupt rows."""
    cases = _edge_cases()
    bx = BatchedExtractor(backend="ref")
    want = [bx.extract_one(*c) for c in cases]
    got, _ = bx.run(cases, batch_size=2)
    for w, r in zip(want, got):
        np.testing.assert_allclose(np.asarray(r), w, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# seeded mirrors of the hypothesis segmented-compaction invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(5))
def test_compaction_invariants_seeded(seed):
    rng = np.random.default_rng(seed)
    m, cap = 64 + 96 * seed, 128
    verts = rng.normal(size=(m, 3)).astype(np.float32)
    keep = rng.random(m) < rng.uniform(0.0, 1.0)
    out, mask, n = (np.asarray(x) for x in
                    ck.compact_batch_ref(verts[None], keep[None], cap))
    out, mask, n = out[0], mask[0], int(n[0])
    k = min(n, cap)
    assert n == keep.sum()                       # survivor count preserved
    np.testing.assert_array_equal(               # stable original order
        out[:k], verts[keep][:cap]
    )
    assert mask[:k].all() and not mask[k:].any() # no leak past M'
    assert np.all(out[k:] == 0.0)                # padding is exactly zero


# ---------------------------------------------------------------------------
# autotune: the compaction scatter block rides in the v2 cache
# ---------------------------------------------------------------------------


def test_compact_sweep_round_trip(tmp_path, monkeypatch):
    path = tmp_path / "compact_cache.json"
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")  # force-sweep on interpret
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    cfg = autotune.get_compact_config(512, "interpret", blocks=(128, 256),
                                      repeat=1)
    assert cfg.block in (128, 256)
    data = json.loads(path.read_text())
    assert data["schema"] == autotune.SCHEMA_VERSION
    rec = data["entries"]["compact/interpret/M512/B1"]
    assert rec["block"] == cfg.block and set(rec["table"]) == {"128", "256"}
    # second resolution is a pure cache hit even with sweeping disabled
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    assert autotune.get_compact_config(512, "interpret") == cfg
    # and the ref backend has no configuration axis at all
    assert autotune.get_compact_config(512, "ref") == \
        autotune.DEFAULT_COMPACT_CONFIG
