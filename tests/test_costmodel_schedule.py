"""Cost-model-driven scheduling lockdown: hint prep, auto windows, auto schedule.

The contracts under test (see runtime/costmodel.py + core/executor.py):

* ``prep='hint'`` == ``prep='count'`` bit-identically on ref + interpret,
  with ZERO per-case pass-0 host syncs (``transfer_log``-asserted), and
  a FORCED hint-overflow case resolves through the count-sized retry to
  the same bits;
* ``window='auto'`` == any fixed window bit-identically, and a census
  fragmentation case (new shape bucket arriving at a window whose
  sub-batches are all past break-even depth) PROVABLY splits the window;
* ``schedule='auto'`` resolves to counted on this container (cheap d2h
  sync) and to static under a spied expensive ``sync/<backend>`` cache
  entry -- either way bit-identical to the fixed schedules;
* the cost model is a deterministic pure function of (backend, cache
  file, metadata): identical queries return identical answers and never
  write the cache when probing is disabled.
"""
import functools
import json
import os

import numpy as np
import pytest

from repro.core import plan as planlib
from repro.core.pipeline import BatchedExtractor
from repro.data.synthetic import make_case
from repro.runtime import autotune, costmodel
from repro.runtime import roofline as rooflib

pytestmark = pytest.mark.tier1


@pytest.fixture(autouse=True)
def _isolated_autotune(tmp_path, monkeypatch):
    # decisions must not depend on (or pollute) the user's autotune cache
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


@functools.lru_cache(maxsize=None)
def _case(shape, seed):
    return make_case(shape, seed=seed)


def _empty():
    z = np.zeros((10, 10, 10), np.float32)
    return (z, z.copy(), (1.0, 1.0, 1.0))


def _mixed_cases():
    return [
        _case((48, 48, 48), 1),
        _empty(),                # empty mask mid-batch: zero row, no n_fut
        _case((20, 18, 16), 5),  # floor-cap case
        _case((70, 20, 20), 4),  # different shape bucket
        _case((48, 48, 48), 2),
    ]


def _assert_rows_equal(want, got):
    assert len(want) == len(got)
    for i, (a, b) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"case {i}"
        )


# ---------------------------------------------------------------------------
# prep='hint': sync-free pass 0, bit-identical, overflow retried
# ---------------------------------------------------------------------------


def test_hint_prep_equals_count_prep_bit_identical_ref():
    cases = _mixed_cases()
    count = BatchedExtractor(backend="ref", prep="count")
    hint = BatchedExtractor(backend="ref", prep="hint")
    rc, _ = count.run(cases)
    rh, sh = hint.run(cases)
    _assert_rows_equal(rc, rh)
    # the acceptance criterion is a counter: count prep syncs once per
    # non-empty case, hint prep NEVER syncs in pass 0
    assert count.executor.transfer_log["prep"] == 4
    assert hint.executor.transfer_log.get("prep", 0) == 0
    assert "prep" not in sh["host_fetches"]
    # the true counts were drained at collect time instead (a feature of
    # the row, and the overflow detector)
    assert hint.executor.transfer_log["collect_counts"] == 4
    # no overflow on this cohort: the hint over-allocates, never retries
    assert hint.executor.transfer_log.get("hint_retry", 0) == 0


def test_hint_prep_equals_count_prep_bit_identical_interpret():
    cases = [_case((48, 48, 48), 2), _case((20, 18, 16), 5)]
    count = BatchedExtractor(backend="interpret", prep="count")
    hint = BatchedExtractor(backend="interpret", prep="hint")
    rc, _ = count.run(cases)
    rh, _ = hint.run(cases)
    _assert_rows_equal(rc, rh)
    assert hint.executor.transfer_log.get("prep", 0) == 0
    # extract_one stays the (count-sized) oracle of the hint path
    np.testing.assert_array_equal(
        np.asarray(rh[0]), hint.extract_one(*cases[0])
    )


@pytest.mark.parametrize("schedule", ["counted", "static"])
def test_hint_overflow_retries_count_sized(monkeypatch, schedule):
    """A hint that UNDER-estimates drops vertices in pass 0; the collector
    must detect the overflow from the deferred count and re-run the case
    count-sized -- bit-identical to the count-prep baseline."""
    cases = [_case((48, 48, 48), 1), _case((20, 18, 16), 5)]
    baseline = BatchedExtractor(backend="ref", prep="count",
                                schedule=schedule)
    rc, _ = baseline.run(cases)

    # force the overflow: every hint collapses to the bucket floor (512),
    # far below the 48^3 blob's real dedup count
    monkeypatch.setattr(planlib, "vertex_hint", lambda *a, **k: 1)
    hint = BatchedExtractor(backend="ref", prep="hint", schedule=schedule)
    rh, _ = hint.run(cases)
    _assert_rows_equal(rc, rh)
    ex = hint.executor
    assert ex.transfer_log.get("prep", 0) == 0
    assert ex.transfer_log.get("hint_retry", 0) >= 1  # the retry really ran
    if schedule == "static":
        assert ex.transfer_log.get("pass1", 0) == 0  # still sync-free


def test_unknown_prep_is_refused():
    with pytest.raises(ValueError, match="prep"):
        BatchedExtractor(backend="ref", prep="guess")


# ---------------------------------------------------------------------------
# window='auto': census-driven boundaries, bit-identical to fixed windows
# ---------------------------------------------------------------------------


def test_window_auto_equals_fixed_and_splits_on_fragmentation():
    """Four same-bucket cases then a new shape bucket: with the default
    break-even depth (4) the census says the open window's sub-batches
    are all healthy, so the newcomer must START WINDOW 2 -- and the rows
    must equal the fixed-window run bit for bit."""
    a = _case((48, 48, 48), 1)
    b = _case((70, 20, 20), 4)  # new shape bucket -> fragments the census
    cases = [a, a, a, a, b]
    bx = BatchedExtractor(backend="ref")
    want, _ = bx.run(cases)
    seen = []
    got = list(bx.extract_stream(iter(cases), window="auto",
                                 stats_callback=lambda i, s: seen.append((i, s))))
    _assert_rows_equal(want, got)
    assert [(i, s["cases"]) for i, s in seen] == [(0, 4), (1, 1)]
    assert seen[0][1]["shape_buckets"] == 1  # the split kept window 0 pure


def test_window_auto_absorbs_heterogeneity_below_break_even():
    """A fragmenting case arriving while the window is still shallow must
    be ABSORBED (windows must be allowed to grow past one bucket)."""
    cases = [_case((48, 48, 48), 1), _case((70, 20, 20), 4),
             _empty(), _case((20, 18, 16), 5)]
    bx = BatchedExtractor(backend="ref")
    want, _ = bx.run(cases)
    seen = []
    got = list(bx.extract_stream(iter(cases), window="auto",
                                 stats_callback=lambda i, s: seen.append(s)))
    _assert_rows_equal(want, got)
    assert len(seen) == 1 and seen[0]["cases"] == 4
    assert seen[0]["shape_buckets"] >= 2  # heterogeneous, by design


def test_window_auto_respects_memory_budget():
    cases = [_case((48, 48, 48), 1)] * 3
    bx = BatchedExtractor(backend="ref")
    want, _ = bx.run(cases)
    # a one-byte budget forces every window down to a single case
    bx.executor._cost_model = costmodel.CostModel("ref", window_mem_bytes=1)
    seen = []
    got = list(bx.extract_stream(iter(cases), window="auto",
                                 stats_callback=lambda i, s: seen.append(s)))
    _assert_rows_equal(want, got)
    assert [s["cases"] for s in seen] == [1, 1, 1]


def test_window_rejects_junk():
    bx = BatchedExtractor(backend="ref")
    with pytest.raises(ValueError, match="window"):
        next(bx.extract_stream(iter([]), window="adaptive"))
    with pytest.raises(ValueError, match="window"):
        next(bx.extract_stream(iter([]), window=0))


# ---------------------------------------------------------------------------
# schedule='auto': sync-cost-calibrated counted/static selection
# ---------------------------------------------------------------------------


def test_schedule_auto_resolves_counted_on_this_container():
    cases = [_case((48, 48, 48), 1), _case((48, 48, 48), 2)]
    bx = BatchedExtractor(backend="ref", schedule="auto")
    rows, stats = bx.run(cases)
    # cheap local sync (the uncalibrated default): counted wins, exactly
    # the measured PR 4 trade-off on a zero-latency device
    assert stats["schedule"] == "auto"
    assert stats["plan"]["schedule"] == "counted"
    want, _ = BatchedExtractor(backend="ref", schedule="counted").run(cases)
    _assert_rows_equal(want, rows)


def test_schedule_auto_forced_static_by_spied_sync_entry():
    """Positive control: a calibrated ``sync/<backend>`` entry recording an
    expensive link must flip the same window to the sync-free schedule."""
    cases = [_case((48, 48, 48), 1), _case((48, 48, 48), 2)]
    want, _ = BatchedExtractor(backend="ref", schedule="counted").run(cases)
    autotune.AutotuneCache().put(autotune.sync_key("ref"), {"us": 1e9})
    bx = BatchedExtractor(backend="ref", schedule="auto")
    rows, stats = bx.run(cases)
    assert stats["plan"]["schedule"] == "static"
    assert bx.executor.transfer_log.get("pass1", 0) == 0  # it really was
    _assert_rows_equal(want, rows)


def test_choose_schedule_census_sensitivity():
    cm = costmodel.CostModel("ref")
    # nothing to schedule: the zero-latency default
    assert cm.choose_schedule([planlib.CaseMeta(None, None, 0, 0)]) == "counted"
    # an all-floor-cap window: the static targets equal the caps, so the
    # counted schedule's sync buys nothing -- static must win
    floor = [planlib.CaseMeta((32, 32, 32), (20, 20, 20), 512, 300)] * 4
    assert cm.choose_schedule(floor) == "static"
    # a big-cap window on a cheap link: tight buckets beat the sync cost
    big = [planlib.CaseMeta((64, 64, 64), (50, 50, 50), 8192, 6000)] * 4
    assert cm.choose_schedule(big) == "counted"


# ---------------------------------------------------------------------------
# cost-model determinism given a fixed cache file
# ---------------------------------------------------------------------------


def test_cost_model_deterministic_given_fixed_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "fixed.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    cache = autotune.AutotuneCache()
    cache.put(autotune.sync_key("ref"), {"us": 777.0})
    for depth, us in ((1, 100.0), (2, 120.0), (4, 160.0), (8, 300.0)):
        cache.put(
            autotune.sweep_key(1024, "ref", depth),
            {"variant": "gram", "block": 128, "us": us, "table": {}},
        )
    before = open(path).read()

    def snapshot():
        cm = costmodel.CostModel("ref")
        metas = [planlib.CaseMeta((64,) * 3, (50,) * 3, 1024, 900)] * 3
        return (
            cm.sync_cost_us(),
            cm.diameter_case_us(1024, 1),
            cm.diameter_case_us(1024, 8),
            cm.diameter_case_us(1024, 16),  # nearest shallower: the B8 row
            cm.diameter_case_us(2048, 1),   # unmeasured: roofline fallback
            cm.break_even_depth(1024),
            cm.break_even_depth(4096),      # unmeasured: the default ladder
            cm.choose_schedule(metas),
        )

    first, second = snapshot(), snapshot()
    assert first == second
    assert first[0] == 777.0        # the calibrated sync entry, verbatim
    assert first[1] == 100.0        # B1: per-case == per-launch
    assert first[2] == 300.0 / 8    # B8: launch us / depth bucket
    assert first[3] == 300.0 / 8    # depth 16 falls back to the B8 row
    # an unmeasured bucket rides the roofline estimate under the default
    # 'ref' hardware profile, NOT the analytic constant
    profile = autotune.DEFAULT_HW_PROFILES["ref"]
    flops, nbytes = rooflib.diameter_cost(2048, 1)
    assert first[4] == rooflib.roofline_us(flops, nbytes, profile)
    # per-case ladder 100/60/40/37.5: depth 4 is the first within 1.25x
    assert first[5] == 4
    assert first[6] == costmodel.DEFAULT_BREAK_EVEN_DEPTH
    # pure reads: the fixed cache file was never rewritten
    assert open(path).read() == before


def test_unmeasured_bucket_rides_roofline_with_empty_cache():
    # empty cache + probing disabled: the default 'ref' profile prices
    # the bucket via the roofline bound (estimate hierarchy step 2)
    cm = costmodel.CostModel("ref")
    profile = autotune.DEFAULT_HW_PROFILES["ref"]
    for cap in (512, 2048, 8192):
        flops, nbytes = rooflib.diameter_cost(cap, 1)
        assert cm.diameter_case_us(cap, 1) == rooflib.roofline_us(
            flops, nbytes, profile
        )
        assert cm.diameter_case_us(cap, 1) != (
            cap / 1024.0
        ) ** 2 * costmodel.PAIR_SWEEP_US


def test_analytic_constant_only_without_hw_profile(monkeypatch):
    # REPRO_ROOFLINE=0 removes the hardware profile: the analytic
    # constant (estimate hierarchy step 3) must take over -- and an
    # unknown backend string has no default profile either
    monkeypatch.setenv("REPRO_ROOFLINE", "0")
    cm = costmodel.CostModel("ref")
    assert cm.hw_profile() is None
    assert cm.diameter_case_us(2048, 1) == (
        2048 / 1024.0
    ) ** 2 * costmodel.PAIR_SWEEP_US
    monkeypatch.delenv("REPRO_ROOFLINE")
    assert autotune.get_hw_profile("not-a-backend") is None


def test_sync_cost_defaults_without_calibration():
    # REPRO_AUTOTUNE=0 (fixture): no probe may run, no entry exists
    assert autotune.get_sync_cost("ref") == autotune.DEFAULT_SYNC_US
    cm = costmodel.CostModel("ref")
    assert cm.sync_cost_us() == autotune.DEFAULT_SYNC_US
    assert cm.hw_profile() == autotune.DEFAULT_HW_PROFILES["ref"]
    assert not os.path.exists(os.environ["REPRO_AUTOTUNE_CACHE"])


# ---------------------------------------------------------------------------
# the acceptance criterion, end to end
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_full_auto_stream_equals_fixed_counted_count_baseline(backend):
    """``extract_stream(window='auto', schedule='auto', prep='hint')`` must
    be bit-identical to the fixed-window counted count-sized baseline and
    perform zero per-case pass-0 host syncs."""
    cases = _mixed_cases() if backend == "ref" else _mixed_cases()[:3]
    baseline = BatchedExtractor(backend=backend, schedule="counted",
                                prep="count")
    want = list(baseline.extract_stream(iter(cases), window=2))
    auto = BatchedExtractor(backend=backend, schedule="auto", prep="hint")
    got = list(auto.extract_stream(iter(cases), window="auto"))
    _assert_rows_equal(want, got)
    assert auto.executor.transfer_log.get("prep", 0) == 0
    assert auto.executor.transfer_log["collect_counts"] >= 1


def test_plan_census_and_meta_bytes():
    m = planlib.CaseMeta((64, 64, 64), (50, 50, 50), 4096, 3000)
    empty = planlib.CaseMeta(None, None, 0, 0)
    assert planlib.meta_bytes(m) == 4 * 64**3 + 16 * 4096
    assert planlib.meta_bytes(empty) == 0
    c = planlib.WindowCensus()
    assert c.fragments(m)  # any bucket is new to an empty census (the
    # never-close-an-empty-window guard lives in CostModel.should_close)
    c.add(m)
    assert c.cases == 1 and c.bytes == planlib.meta_bytes(m)
    assert not c.fragments(m)      # same buckets: homogeneous
    assert not c.fragments(empty)  # empty cases never fragment
    c.add(empty)
    assert c.cases == 2 and c.shape_depths == {(64, 64, 64): 1}
    other = planlib.CaseMeta((96, 32, 32), (70, 22, 22), 4096, 2500)
    assert c.fragments(other)  # new shape bucket (same cap bucket)
    c.add(other)
    assert c.cap_depths == {4096: 2}


@pytest.mark.parametrize("backend", ["ref", "interpret", "pallas"])
def test_window_budget_counts_brick_mc_temporaries(backend):
    from repro.kernels import marching_cubes as mck
    m = planlib.CaseMeta((64, 64, 64), (50, 50, 50), 4096, 3000)
    work = mck.work_bytes(m.shape)
    # two cases' staged bytes fit the budget; the MC temporaries do not
    budget = 2 * planlib.meta_bytes(m) + work // 2
    cm = costmodel.CostModel(backend, cache=autotune.AutotuneCache(),
                             window_mem_bytes=budget)
    c = planlib.WindowCensus()
    c.add(m)
    brick = backend != "ref"
    assert cm.mc_work_bytes(c, m) == (work if brick else 0)
    assert cm.should_close(c, m) == brick


@pytest.mark.parametrize("shape", [(32, 32, 32), (100, 70, 51),
                                   (352, 128, 224)], ids=str)
def test_mc_work_bound_covers_every_candidate_block(shape):
    from repro.kernels import marching_cubes as mck
    bound = mck.work_bytes(shape)
    for block in autotune.DEFAULT_MC_BLOCKS:
        assert mck.work_bytes(shape, block) <= bound


def test_env_float_warns_once_on_malformed(monkeypatch):
    import warnings

    monkeypatch.setenv("REPRO_STREAM_MEM_MB", "lots")
    costmodel._warned_env.discard("REPRO_STREAM_MEM_MB")
    # malformed: warn ONCE naming the variable, fall back to the default
    with pytest.warns(RuntimeWarning, match="REPRO_STREAM_MEM_MB"):
        assert costmodel._env_float("REPRO_STREAM_MEM_MB", 512.0) == 512.0
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        # once per process: the second malformed read is silent
        assert costmodel._env_float("REPRO_STREAM_MEM_MB", 512.0) == 512.0
        # unset and well-formed values never warn
        monkeypatch.delenv("REPRO_STREAM_MEM_MB")
        assert costmodel._env_float("REPRO_STREAM_MEM_MB", 1.5) == 1.5
        monkeypatch.setenv("REPRO_STREAM_MEM_MB", "256")
        assert costmodel._env_float("REPRO_STREAM_MEM_MB", 1.5) == 256.0
    costmodel._warned_env.discard("REPRO_STREAM_MEM_MB")


def test_malformed_stream_env_falls_back_in_cost_model(monkeypatch):
    monkeypatch.setenv("REPRO_STREAM_MAX_CASES", "many")
    costmodel._warned_env.discard("REPRO_STREAM_MAX_CASES")
    with pytest.warns(RuntimeWarning, match="REPRO_STREAM_MAX_CASES"):
        cm = costmodel.CostModel("ref")
    assert cm.window_max_cases == costmodel.DEFAULT_WINDOW_MAX_CASES
    costmodel._warned_env.discard("REPRO_STREAM_MAX_CASES")
