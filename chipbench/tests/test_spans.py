"""The reduction of the program's ``repro.`` spans on hand-built events of
the profiler's format."""
import os
from types import SimpleNamespace as NS

import pytest

from chipbench import harness, run, spans, trace
from chipbench.tests.test_trace import KERNELS, MC, ev, hlo, plane

WINDOW = ev("chipbench.window", 100, 1100)
# one study: pass 0 with its fetch, then planning and one launch
PREP = [ev("repro.prep", 150, 650, window=0, case=0),
        ev("repro.prep.crop", 160, 250, voxels=4096),
        ev("repro.prep.stage", 250, 300, bytes=20480),
        ev("repro.prep.fields", 300, 600, cap=512),
        ev("repro.fetch", 400, 550, stage="prep", bytes=4)]
DISPATCH = [ev("repro.window.submit", 700, 900, window=0, cases=1),
            ev("repro.plan", 700, 750, schedule="counted", buckets=1),
            ev("repro.launch.pass2a", 750, 900, launches=1)]
SIX = ["cohort.prep_host_ms", "cohort.fetch_wait_ms",
       "cohort.h2d_kib_per_case", "cohort.idle_in_prep",
       "cohort.idle_in_dispatch", "cohort.pass0_device_ms"]


def profile(ops, host, modules=()):
    return NS(planes=[
        plane("/host:CPU", [("python", host)]),
        plane("/device:TPU:0", [("XLA Modules", list(modules)),
                                ("XLA Ops", ops)]),
    ])


def test_self_time_leaves_out_nested_children():
    s = spans.summarize(profile([ev(MC, 100, 150)],
                                [WINDOW] + PREP + DISPATCH))
    assert s.total_s["repro.prep"] == pytest.approx(500e-9)
    # 500 less crop 90, stage 50, fields 300
    assert s.self_s["repro.prep"] == pytest.approx(60e-9)
    assert s.self_s["repro.prep.fields"] == pytest.approx(150e-9)
    assert s.self_s["repro.fetch"] == pytest.approx(150e-9)
    assert s.self_s["repro.window.submit"] == pytest.approx(0.0)
    assert s.stats["repro.prep.stage"] == {"bytes": 20480}
    assert "repro.prep" not in s.stats  # identifiers are not summed
    assert spans.prep_host_ms(s) == pytest.approx(350e-6)
    assert spans.fetch_wait_ms(s) == pytest.approx(150e-6)
    assert spans.h2d_kib_per_case(s) == pytest.approx(20.0)


def test_idle_goes_to_the_innermost_repro_span():
    ops = [ev(hlo("a"), 100, 200), ev(hlo("b"), 300, 380),
           ev(hlo("c"), 500, 560), ev(hlo("d"), 760, 1000)]
    s = spans.summarize(profile(ops, [WINDOW] + PREP + DISPATCH))
    idle = {k: v * 1e9 for k, v in s.idle_s.items()}
    # gaps: [200, 300) mid 250 -> stage starts there (latest start);
    # [380, 500) mid 440 -> fetch; [560, 760) mid 660 -> none;
    # [1000, 1100) mid 1050 -> none
    assert idle == pytest.approx({"repro.prep.stage": 100,
                                  "repro.fetch": 120,
                                  spans.NONE: 300})
    assert spans.idle_in_prep(s) == pytest.approx(10.0)
    assert spans.idle_in_dispatch(s) == pytest.approx(0.0)


def test_idle_by_span_adds_up_to_the_device_idle():
    ops = [ev(hlo("a"), 120, 160), ev(hlo("b"), 420, 440),
           ev(hlo("c"), 720, 730), ev(hlo("d"), 1000, 1090)]
    pd = profile(ops, [WINDOW] + PREP + DISPATCH)
    s = spans.summarize(pd)
    device_idle = 100 * trace.summarize(pd, KERNELS).idle_share
    unattributed = 100 * s.idle_s.get(spans.NONE, 0.0) / s.window_s
    parts = spans.idle_in_prep(s) + spans.idle_in_dispatch(s) + unattributed
    assert parts <= device_idle + 1e-9
    assert parts > 0 and spans.idle_in_dispatch(s) > 0
    every = 100 * sum(s.idle_s.values()) / s.window_s
    assert every == pytest.approx(device_idle)


def test_everything_is_clipped_to_the_window():
    host = [WINDOW,
            ev("repro.prep", 0, 200, window=0, case=0),  # starts before
            ev("repro.prep.stage", 50, 150, bytes=999),
            ev("repro.prep", 300, 400, window=1, case=0),
            ev("repro.prep.stage", 300, 350, bytes=1024),
            ev("repro.prep", 1050, 1300, window=2, case=0)]
    ops = [ev(hlo("a"), 0, 120), ev(hlo("b"), 1090, 1500)]
    s = spans.summarize(profile(ops, host))
    assert s.window_s == pytest.approx(1000e-9)
    assert s.count["repro.prep"] == 2  # those that start in the window
    assert s.stats["repro.prep.stage"] == {"bytes": 1024}
    # [100, 200) + [300, 400) + [1050, 1100)
    assert s.total_s["repro.prep"] == pytest.approx(250e-9)
    assert s.self_s["repro.prep"] == pytest.approx(150e-9)
    assert sum(s.idle_s.values()) == pytest.approx(970e-9)


def test_a_trace_without_the_window_spans_its_repro_spans():
    s = spans.summarize(profile([ev(hlo("a"), 0, 10)], PREP))
    assert s.window_s == pytest.approx(500e-9)
    assert s.count["repro.prep"] == 1


def test_device_time_goes_to_the_program_around_it():
    modules = [ev("jit__fields_count(17)", 100, 300),
               ev("jit_pass2a_mc(4)", 400, 800),
               ev("jit__compact_cap(5)", 900, 950)]
    ops = [ev(hlo("sort.1", "sort"), 110, 210),
           ev(hlo("fusion.2"), 210, 290),
           ev(MC, 400, 700), ev(hlo("while.3", "while"), 700, 800),
           ev(hlo("copy.1", "copy"), 900, 940),
           # outside every module: its own stat names the program
           ev(hlo("sort.2", "sort"), 960, 990, hlo_module="jit__compact_cap"),
           ev(hlo("reshape", "reshape"), 1000, 1010)]
    s = spans.summarize(profile(ops, [WINDOW] + PREP, modules))
    assert s.program_s == pytest.approx({
        "jit__fields_count": 180e-9, "jit_pass2a_mc": 400e-9,
        "jit__compact_cap": 70e-9, spans.NO_MODULE: 10e-9})
    assert s.program_ops["jit__fields_count"] == pytest.approx(
        {"sort": 100e-9, "fusion": 80e-9})
    assert s.program_ops["jit_pass2a_mc"] == pytest.approx(
        {"mc_volume_area_pallas": 300e-9, "while": 100e-9})
    assert spans.pass0_device_ms(s) == pytest.approx(250e-6)
    assert spans.program_name("jit_pass1_bound(123)") == "jit_pass1_bound"


def test_readers_read_nothing_without_repro_spans(monkeypatch):
    bare = profile([ev(hlo("a"), 200, 300)],
                   [WINDOW, ev("cohort.submit_window", 150, 900)])
    assert spans.summarize(bare) is None
    monkeypatch.setattr(spans, "read", lambda d: spans.summarize(bare))
    traced = NS(trace=True, cell={"name": "cohort-tumour"})
    for name in SIX:
        assert harness.load_module("metrics", name).read(traced) is None
    # a traced program: each reads a number; an untraced run reads none
    full = profile([ev(hlo("a"), 200, 300)], [WINDOW] + PREP + DISPATCH,
                   [ev("jit__fields_count(1)", 200, 300)])
    monkeypatch.setattr(spans, "read", lambda d: spans.summarize(full))
    for name in SIX:
        mod = harness.load_module("metrics", name)
        assert mod.read(traced) is not None, name
        assert mod.read(NS(trace=False, cell=traced.cell)) is None, name


def test_the_report_prints_every_table_and_metric():
    modules = [ev("jit__fields_count(1)", 200, 300)]
    s = spans.summarize(profile([ev(hlo("sort.1", "sort"), 200, 300)],
                                [WINDOW] + PREP + DISPATCH, modules))
    text = spans.report(s)
    for want in ("repro.prep.fields", "repro.launch.pass2a",
                 "device idle by innermost span", "all idle",
                 "jit__fields_count", "sort 0.000000", *SIX):
        assert want in text, want
    host_only = spans.summarize(profile([], PREP))
    assert "no device ran" in spans.report(host_only)


def test_the_six_metrics_are_the_benchmark_entries():
    entries = {m["name"]: m for m in harness.benchmark()["per_layer"]}
    assert set(SIX) == set(spans.METRICS) <= set(entries)
    for name in SIX:
        assert entries[name]["workloads"] == ["cohort-tumour"]
        assert entries[name]["moves"] == "cases_per_s"


def test_the_trace_directory_is_the_one_run_writes():
    for cell in ("cohort-tumour", "another-cell"):
        assert spans.trace_dir(cell) == os.path.join(run.STATE, "trace",
                                                      cell)
