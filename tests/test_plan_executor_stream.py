"""Plan/executor split: streaming, static schedule, device-pool MC lockdown.

The contracts under test (see core/plan.py + core/executor.py):

* ``extract_stream`` == ``run`` == ``extract_one`` bit-identically -- in
  input order, across window boundaries, with empty-mask cases mid-stream;
* ``schedule='static'`` == ``schedule='counted'`` bit-identically on
  ref + interpret, INCLUDING the keep-originals retry path (the static
  target is the counted win boundary -- ``plan.static_bucket``);
* static pass 1 performs ZERO host fetches: asserted by the executor's
  ``transfer_log`` sync census AND by a guard that intercepts every
  device-array materialisation inside the pass-1 phase (the acceptance
  criterion is a counter, not a docstring);
* pass 2a consumes bucket-keyed device pools: device-pool MC must equal
  the host-stacked feed it replaced, bit-for-bit, on ref + interpret;
* the plan layer's metadata functions (spacing-aware memoised vertex
  hint, static bucket ladder, grouping, pad-waste stats) hold their
  invariants.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executor as exmod
from repro.core import plan as planlib
from repro.core.pipeline import BatchedExtractor
from repro.data.synthetic import make_case
from repro.kernels import ops
from repro.kernels import prune as prune_kernels

pytestmark = pytest.mark.tier1


@pytest.fixture(autouse=True)
def _isolated_autotune(tmp_path, monkeypatch):
    # parity must not depend on (or pollute) the user's autotune cache
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


@functools.lru_cache(maxsize=None)
def _case(shape, seed):
    return make_case(shape, seed=seed)


def _empty():
    z = np.zeros((10, 10, 10), np.float32)
    return (z, z.copy(), (1.0, 1.0, 1.0))


def _edge_cases():
    voxel_m = np.zeros((9, 9, 9), np.float32)
    voxel_m[4, 4, 4] = 1.0
    return [
        _case((48, 48, 48), 1),   # prunes to a smaller bucket
        _empty(),                 # empty mask mid-stream: zero row
        _case((20, 18, 16), 5),   # small: floor-cap keep-originals path
        (np.zeros((9, 9, 9), np.float32), voxel_m, (1.0, 1.0, 1.0)),
        _case((70, 20, 20), 4),   # different shape bucket
        _case((48, 48, 48), 2),   # same buckets as case 0, later window
    ]


# ---------------------------------------------------------------------------
# plan layer: vertex hint, static ladder, grouping, pad stats
# ---------------------------------------------------------------------------


def test_vertex_hint_spacing_aware_memoised_and_capped():
    iso = planlib.vertex_hint((40, 40, 40))
    assert iso == planlib.vertex_hint((40, 40, 40), (2.0, 2.0, 2.0))
    # anisotropic spacing cuts more voxel planes per unit physical surface
    aniso = planlib.vertex_hint((40, 40, 40), (1.0, 1.0, 5.0))
    assert aniso > iso
    # memoised: the second identical query is a pure cache hit
    planlib._vertex_hint.cache_clear()
    planlib.vertex_hint((17, 19, 23), (1.0, 1.5, 3.0))
    planlib.vertex_hint((17, 19, 23), (1.0, 1.5, 3.0))
    info = planlib._vertex_hint.cache_info()
    assert info.hits == 1 and info.misses == 1
    # capped at the volume's total edge count: a degenerate hint can never
    # allocate a cap group past what the mesh could physically produce
    tiny = planlib.vertex_hint((2, 2, 2), (1.0, 1.0, 1000.0))
    assert tiny <= 3 * 4 * 4 * 4
    for shape in ((3, 3, 3), (8, 64, 8), (100, 100, 100)):
        edges = 3 * np.prod([s + 2 for s in shape])
        assert 0 < planlib.vertex_hint(shape, (1.0, 1.0, 9.0)) <= edges


def test_static_bucket_is_the_counted_win_boundary():
    assert planlib.static_bucket(512) is None  # floor: no shrink possible
    assert planlib.static_bucket(1024) == 512
    assert planlib.static_bucket(4096) == 2048
    # alignment: for every cap, fitting the static target is EXACTLY the
    # counted schedule's re-bucketing decision -- the property that makes
    # the sync-free schedule safe (no survivor can overflow a case the
    # counted path would have compacted)
    for cap in (1024, 2048, 4096, 8192):
        t = planlib.static_bucket(cap)
        for m in (2, 3, 100, t - 1, t, t + 1, cap - 1, cap):
            counted_wins = ops.vertex_bucket(m) < cap
            assert counted_wins == (m <= t), (cap, m)


def test_build_plan_grouping_partition_and_stats():
    metas = [
        planlib.CaseMeta((64, 64, 64), (50, 50, 50), 4096, 3000),
        planlib.CaseMeta(None, None, 0, 0),  # empty case: excluded
        planlib.CaseMeta((64, 64, 64), (40, 60, 62), 512, 300),
        planlib.CaseMeta((96, 32, 32), (70, 22, 22), 4096, 2500),
    ]
    plan = planlib.build_plan(metas, "static")
    # every non-empty index lands in exactly one group of each pass
    for groups in (plan.shape_groups, plan.cap_groups):
        flat = sorted(i for idxs in groups.values() for i in idxs)
        assert flat == [0, 2, 3]
    assert plan.shape_groups[(64, 64, 64)] == [0, 2]
    assert plan.cap_groups[4096] == [0, 3]
    assert plan.static_targets == {4096: 2048, 512: None}
    s = plan.stats()
    assert s["cases"] == 4 and s["empty_cases"] == 1
    assert s["shape_buckets"] == 2 and s["cap_buckets"] == 2
    assert 0.0 < s["mask_pad_waste"] < 1.0
    assert 0.0 < s["vertex_pad_waste"] < 1.0
    # counted plans carry no static targets (they come from run-time counts)
    assert planlib.build_plan(metas, "counted").static_targets == {}
    with pytest.raises(ValueError, match="schedule"):
        planlib.build_plan(metas, "bogus")
    # metadata-only planning: same machinery, hint-sized caps
    mplan = planlib.plan_from_metadata(
        [(50, 50, 50), (20, 20, 20)], [(1.0, 1.0, 1.0)] * 2, "static"
    )
    assert mplan.n_cases == 2 and mplan.stats()["shape_buckets"] >= 1


def test_unknown_schedule_is_refused():
    with pytest.raises(ValueError, match="schedule"):
        BatchedExtractor(backend="ref", schedule="eager")


# ---------------------------------------------------------------------------
# static == counted bit-identity (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_static_equals_counted_bit_identical_ref():
    cases = _edge_cases()
    counted = BatchedExtractor(backend="ref", schedule="counted")
    static = BatchedExtractor(backend="ref", schedule="static")
    rc, sc = counted.run(cases)
    rs, ss = static.run(cases)
    # the schedules make the SAME prune decision (deferred vs synced)
    for key in ("pruned_cases", "empty_cases", "mean_keep_fraction",
                "buckets"):
        assert sc[key] == ss[key], key
    for i, (a, b) in enumerate(zip(rc, rs)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"case {i}"
        )


def test_static_equals_counted_bit_identical_interpret():
    cases = [_case((48, 48, 48), 2), _case((20, 18, 16), 5)]
    counted = BatchedExtractor(backend="interpret", schedule="counted")
    static = BatchedExtractor(backend="interpret", schedule="static")
    rc, _ = counted.run(cases)
    rs, ss = static.run(cases)
    assert ss["pruned_cases"] >= 1  # the static chain actually compacted
    for a, b in zip(rc, rs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # extract_one stays the oracle of the static path too
    np.testing.assert_array_equal(
        np.asarray(rs[0]), static.extract_one(*cases[0])
    )


def _sphere_prepped(cap, n, seed=0):
    """Fabricated pass-0 state whose vertices all lie ON a sphere.

    Antipodal pairs make the centre upper bound tight (ub == L == 2R for
    every vertex), so the pruning bound provably keeps everything:
    ``m_kept == m_valid`` -- exactly a keep-originals case at a cap above
    the floor, which is the static schedule's deferred-retry path.
    """
    rng = np.random.default_rng(seed)
    u = rng.normal(size=(n // 2, 3))
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    pts = np.concatenate([u, -u]) * 37.0
    verts = np.zeros((cap, 3), np.float32)
    verts[: len(pts)] = pts
    vmask = np.zeros((cap,), bool)
    vmask[: len(pts)] = True
    return exmod._Prepped(
        mask=jnp.zeros((8, 8, 8)), spacing=np.ones(3, np.float32),
        shape=(8, 8, 8), roi_shape=(8, 8, 8),
        verts=jnp.asarray(verts), vmask=jnp.asarray(vmask),
        n_vertices=len(pts), vertex_cap=cap,
    )


def test_static_retry_resolves_keep_originals_exactly():
    """A cap group the counted schedule keeps at its original cap must come
    out of the static schedule bit-identical, via the deferred re-sweep."""
    prepped_s = [_sphere_prepped(1024, 600), _sphere_prepped(1024, 700, 1)]
    prepped_c = [_sphere_prepped(1024, 600), _sphere_prepped(1024, 700, 1)]
    ex_s = BatchedExtractor(backend="ref", schedule="static").executor
    ex_c = BatchedExtractor(backend="ref", schedule="counted").executor
    metas = [ex_s._meta(p) for p in prepped_s]

    entries_s, aux = ex_s._pass1_static(
        planlib.build_plan(metas, "static"), prepped_s
    )
    assert aux, "the sphere cloud must take the static chain path"
    futs = ex_s._submit("pass2b", entries_s, ex_s._diam_fn,
                        ex_s._stacked_chunk)
    d_s = ex_s._drain(futs, "pass2b")
    window = exmod._Window(prepped_s, planlib.build_plan(metas, "static"),
                           [], [], aux, 0)
    ex_s._resolve_static_aux(window, d_s)
    assert ex_s.transfer_log.get("pass2b_retry", 0) >= 1  # retry really ran
    assert ex_s.transfer_log.get("pass1", 0) == 0

    entries_c, _ = ex_c._pass1_counted(
        planlib.build_plan(metas, "counted"), prepped_c
    )
    d_c = ex_c._drain(
        ex_c._submit("pass2b", entries_c, ex_c._diam_fn,
                     ex_c._stacked_chunk), "pass2b"
    )
    for i in range(2):
        # both schedules conclude keep-originals with identical PruneInfo...
        assert not prepped_s[i].prune_info.pruned
        assert prepped_s[i].prune_info == prepped_c[i].prune_info
        assert prepped_s[i].vertex_cap == prepped_c[i].vertex_cap == 1024
        # ...and bit-identical diameters
        np.testing.assert_array_equal(np.asarray(d_s[i]), np.asarray(d_c[i]))


# ---------------------------------------------------------------------------
# zero pass-1 host fetches under the static schedule (transfer counter)
# ---------------------------------------------------------------------------


class _GuardedNp:
    """numpy facade that records every device-array materialisation."""

    def __init__(self, real, log):
        self._real = real
        self._log = log

    def __getattr__(self, name):
        attr = getattr(self._real, name)
        if name in ("asarray", "array"):
            def guarded(x, *a, **kw):
                if isinstance(x, jax.Array):
                    self._log.append(name)
                return attr(x, *a, **kw)
            return guarded
        return attr


def test_static_pass1_performs_zero_host_fetches(monkeypatch):
    cases = [_case((48, 48, 48), 1), _case((20, 18, 16), 5),
             _case((70, 20, 20), 4)]
    stages = []
    bx = BatchedExtractor(backend="ref", schedule="static",
                          transfer_callback=lambda s, x: stages.append(s))
    _, stats = bx.run(cases)
    # the executor's sync census: not one pass-1 fetch happened
    assert "pass1" not in stats["host_fetches"]
    assert bx.executor.transfer_log.get("pass1", 0) == 0
    assert "pass1" not in stages
    # the deferred count fetch happened at collect time instead
    assert stats["host_fetches"].get("pass2b_counts", 0) >= 1

    # hardened guard: run the pass-1 phase alone with EVERY numpy
    # materialisation of a jax array intercepted -- the phase must not
    # touch one, whatever path it takes
    ex = bx.executor
    prepped = [ex._prep_case(*c) for c in cases]
    plan = planlib.build_plan([ex._meta(p) for p in prepped], "static")
    fetched = []
    monkeypatch.setattr(exmod, "np", _GuardedNp(np, fetched))
    entries, aux = ex._pass1_static(plan, prepped)
    monkeypatch.undo()
    assert fetched == [] and entries and aux

    # control: the counted schedule's pass 1 IS the count sync
    bc = BatchedExtractor(backend="ref", schedule="counted")
    exc = bc.executor
    prepped_c = [exc._prep_case(*c) for c in cases]
    plan_c = planlib.build_plan([exc._meta(p) for p in prepped_c], "counted")
    fetched_c = []
    monkeypatch.setattr(exmod, "np", _GuardedNp(np, fetched_c))
    exc._pass1_counted(plan_c, prepped_c)
    monkeypatch.undo()
    assert fetched_c  # the (B, 2) fetch was observed by the same guard
    assert exc.transfer_log.get("pass1", 0) == len(plan_c.cap_groups)


# ---------------------------------------------------------------------------
# streaming == batched == single, in input order
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", ["counted", "static"])
def test_stream_equals_batched_bit_identical(schedule):
    cases = _edge_cases()
    bx = BatchedExtractor(backend="ref", schedule=schedule)
    batched, _ = bx.run(cases)
    # window=4 straddles: [blob, empty, small, voxel] | [elongated, blob2]
    streamed = list(bx.extract_stream(iter(cases), window=4))
    assert len(streamed) == len(cases)
    for i, (a, b) in enumerate(zip(batched, streamed)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"case {i}"
        )
    # the single-case oracle holds through the streaming front-end too
    for case, row in zip(cases, streamed):
        np.testing.assert_array_equal(np.asarray(row), bx.extract_one(*case))


def test_stream_window_edges():
    cases = _edge_cases()[:3]
    bx = BatchedExtractor(backend="ref")
    want, _ = bx.run(cases)
    for window in (1, 2, 3, 16):  # incl. window > n and window == n
        got = list(bx.extract_stream(iter(cases), window=window))
        assert len(got) == 3
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert list(bx.extract_stream(iter([]), window=4)) == []  # empty stream
    with pytest.raises(ValueError, match="window"):
        next(bx.extract_stream(iter(cases), window=0))


def test_stream_interpret_backend_bit_identical():
    cases = [_case((48, 48, 48), 2), _empty(), _case((20, 18, 16), 5)]
    bx = BatchedExtractor(backend="interpret", schedule="static")
    want, _ = bx.run(cases)
    got = list(bx.extract_stream(iter(cases), window=2))
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stream_stats_callback_reports_plan_census():
    cases = _edge_cases()
    bx = BatchedExtractor(backend="ref")
    seen = []
    list(bx.extract_stream(iter(cases), window=4,
                           stats_callback=lambda i, s: seen.append((i, s))))
    assert [i for i, _ in seen] == [0, 1]  # 6 cases / window 4 -> 2 windows
    for _, s in seen:
        assert {"shape_buckets", "cap_buckets", "mask_pad_waste",
                "vertex_pad_waste", "cases"} <= set(s)
    assert seen[0][1]["cases"] == 4 and seen[1][1]["cases"] == 2
    assert seen[0][1]["empty_cases"] == 1


# ---------------------------------------------------------------------------
# device-pool MC == the host-stacked feed it replaced
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_device_pool_mc_equals_host_stacked(backend):
    cases = [_case((48, 48, 48), 1), _case((20, 18, 16), 5),
             _case((48, 48, 48), 2)]
    bx = BatchedExtractor(backend=backend)
    rows, _ = bx.run(cases)
    ex = bx.executor
    prepped = [ex._prep_case(*c) for c in cases]
    plan = planlib.build_plan([ex._meta(p) for p in prepped], "counted")
    for shape, idxs in plan.shape_groups.items():
        # the PR 2/3 feed: per-chunk HOST re-stacking of the padded masks
        masks = jnp.asarray(np.stack([np.asarray(prepped[i].mask)
                                      for i in idxs]))
        sps = jnp.asarray(np.stack([prepped[i].spacing for i in idxs]))
        depth = len(idxs)
        want = np.asarray(ex._mc_fn(shape, depth)(masks, sps))
        for j, i in enumerate(idxs):
            np.testing.assert_array_equal(
                want[j], np.asarray(rows[i][:2], np.float32),
                err_msg=f"case {i} ({backend})",
            )


def test_masks_are_device_staged_once():
    """The pool entries ARE the staged per-case arrays: pass 2a must not
    re-materialise masks from host numpy."""
    bx = BatchedExtractor(backend="ref")
    ex = bx.executor
    p = ex._prep_case(*_case((20, 18, 16), 5))
    assert isinstance(p.mask, jax.Array)
    masks, sps = ex._pool([p], [0])
    assert isinstance(masks, jax.Array) and masks.shape[0] == 1


# ---------------------------------------------------------------------------
# plan-aware batch-depth autotune keys reach the kernels
# ---------------------------------------------------------------------------


def test_pipeline_resolves_depth_bucketed_configs(tmp_path, monkeypatch):
    """A cached depth-keyed diameter entry must be honoured by the batched
    path (and the depth-1 slot by the single-case oracle)."""
    from repro.runtime import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    cache = autotune.AutotuneCache()
    for b in (1, 2, 4):
        cache.put(
            autotune.sweep_key(512, "interpret", batch=b),
            {"variant": "gram", "block": 128, "us": 1.0, "table": {}},
        )
    calls = []
    from repro.core import dispatcher
    orig = dispatcher.diameter_config

    def spy(backend, bucket, variant="auto", block=None, batch=1):
        calls.append((int(bucket), int(batch)))
        return orig(backend, bucket, variant, block, batch)

    monkeypatch.setattr(dispatcher, "diameter_config", spy)
    bx = BatchedExtractor(backend="interpret")
    # identical cases: guaranteed same cap group -> one depth-2 sub-batch
    cases = [_case((20, 18, 16), 5), _case((20, 18, 16), 5)]
    rows, _ = bx.run(cases)
    assert all(np.all(np.isfinite(r)) for r in rows)
    # the batched pass-2b resolution carried the sub-batch depth (2), the
    # oracle path resolves depth 1
    assert any(b == 2 for _, b in calls)
    bx.extract_one(*cases[0])
    assert calls[-1][1] == 1
