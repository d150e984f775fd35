"""The executor's profiler spans and program names.

Two windows of a three-family stream and a static-schedule window run
under ``jax.profiler.trace``; the trace is read back
with ``ProfileData``.  The spans carry the counts the benchmark reads
(``chipbench/spans.py``), so what they say is checked against what the
program did: its ``transfer_log``, the arrays it staged, its windows and
the programs it compiled.
"""
import collections
import functools
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.pipeline import BatchedExtractor
from repro.data.synthetic import make_case

pytestmark = pytest.mark.tier1

FAMILIES = ("shape", "firstorder", "glcm")
SPANS = {
    "repro.window.submit", "repro.prep", "repro.prep.crop",
    "repro.prep.stage", "repro.prep.fields", "repro.plan",
    "repro.launch.firstorder", "repro.launch.glcm", "repro.launch.pass1",
    "repro.launch.pass2a", "repro.launch.pass2b",
    "repro.fetch", "repro.window.collect", "repro.rows",
}
PROGRAMS = {
    "pass1_bound", "pass1_compact", "pass1_static",
    "pass2a_mc", "family_firstorder", "family_glcm", "pass2b_diameter",
}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@functools.lru_cache(maxsize=None)
def _case(shape, seed):
    return make_case(shape, seed=seed)


def _cases():
    # each window of two holds a 48^3 blob (pruned and compacted in pass 1)
    # and a small case; the second window spans two shape buckets
    return [_case((48, 48, 48), 1), _case((20, 18, 16), 5),
            _case((48, 48, 48), 2), _case((20, 18, 16), 6)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    compiled = []
    live = [True]

    def on_event(event, duration, **kw):
        if live[0] and event == COMPILE_EVENT:
            compiled.append(kw.get("fun_name", ""))

    out = tmp_path_factory.mktemp("trace")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_AUTOTUNE", "0")
        mp.setenv("REPRO_AUTOTUNE_CACHE", str(out / "autotune.json"))
        stream = BatchedExtractor(backend="ref", families=FAMILIES)
        static = BatchedExtractor(backend="ref", schedule="static")
        jax.clear_caches()  # every program of the runs below compiles
        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            with jax.profiler.trace(str(out)):
                rows = list(stream.extract_stream(_cases(), window=2))
                static.run(_cases()[::2])
        finally:
            live[0] = False
    path = sorted(glob.glob(str(out / "plugins" / "profile" / "*" /
                                "*.xplane.pb")))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.append((line.name, int(ev.start_ns),
                                  int(ev.end_ns), ev.name, dict(ev.stats)))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return {"spans": spans, "rows": rows, "compiled": compiled,
            "executors": [stream.executor, static.executor]}


def _named(traced, name):
    return [s for s in traced["spans"] if s[3] == name]


def test_every_span_appears(traced):
    assert {s[3] for s in traced["spans"]} == SPANS
    assert len(traced["rows"]) == 4


def test_prep_children_nest_inside_prep(traced):
    preps = _named(traced, "repro.prep")
    assert len(preps) == 4 + 2
    for child in ("repro.prep.crop", "repro.prep.stage", "repro.prep.fields"):
        found = _named(traced, child)
        assert found
        for line, start, end, _, _ in found:
            assert any(pl == line and ps <= start and end <= pe
                       for pl, ps, pe, _, _ in preps), child


def test_fetch_spans_match_transfer_log(traced):
    log = collections.Counter()
    for ex in traced["executors"]:
        log.update(ex.transfer_log)
    spans = collections.Counter(
        s[4]["stage"] for s in _named(traced, "repro.fetch"))
    assert spans == log
    assert all(s[4]["bytes"] > 0 for s in _named(traced, "repro.fetch"))


def test_stage_bytes_are_the_staged_arrays(traced):
    # the same cases prepped again, by an executor outside the trace
    ex = BatchedExtractor(backend="ref", families=FAMILIES).executor
    stages = _named(traced, "repro.prep.stage")[:4]  # the stream's cases
    for (_, _, _, _, stats), case in zip(stages, _cases()):
        p = ex.prep_case(case)
        assert stats["bytes"] == p.mask.nbytes + p.image.nbytes
    crops = _named(traced, "repro.prep.crop")[:4]
    for (_, _, _, _, stats), case in zip(crops, _cases()):
        assert stats["voxels"] == ex.prep_case(case).mask.size


def test_submit_and_collect_share_window_ids(traced):
    def ids(name):
        return [s[4]["window"] for s in _named(traced, name)]

    submits, collects = ids("repro.window.submit"), ids("repro.window.collect")
    # the stream's two windows, then the static executor's one window
    assert submits == collects == [0, 1, 0]
    stream_preps = _named(traced, "repro.prep")[:4]
    assert [(s[4]["window"], s[4]["case"]) for s in stream_preps] == [
        (0, 0), (0, 1), (1, 0), (1, 1)]
    plans = _named(traced, "repro.plan")
    assert [s[4]["schedule"] for s in plans] == [
        "counted", "counted", "static"]
    assert [s[4]["buckets"] for s in plans[:2]] == [1, 2]
    rows = _named(traced, "repro.rows")
    assert [s[4]["rows"] for s in rows] == [2, 2, 2]


def test_launch_spans_count_dispatches(traced):
    for name in ("repro.launch.pass1", "repro.launch.pass2a",
                 "repro.launch.pass2b", "repro.launch.firstorder",
                 "repro.launch.glcm"):
        assert all(s[4]["launches"] >= 1 for s in _named(traced, name)), name


def test_batched_programs_compile_under_their_names(traced):
    names = {n[len("jit("):-1] for n in traced["compiled"]
             if n.startswith("jit(")}
    assert PROGRAMS <= names
    assert "batch" not in names
    # pass 0 keeps its names
    assert {"_fields_count", "_compact_cap"} <= names
