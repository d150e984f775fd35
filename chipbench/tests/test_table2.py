"""The whole-Table-2 cell: its configuration, traffic mix, a CPU run, and
the first-order kernel's roofline reader."""
import math
from types import SimpleNamespace as NS

import numpy as np
import pytest

from chipbench import compare, harness, reference as R, work
from chipbench.tests.runs import run_cell
from chipbench.traffic import generate

CONFIG = harness.config("kits19-table2")
MIX = generate.load("table2-all")
# the three smallest tumours and the smallest kidney
SMALL = ("00004-2", "00007-2", "00009-2", "00004-1")
V5E = harness.peaks("TPU v5 lite")
FO = harness.load_module("metrics", "cohort.firstorder_roofline")


def test_the_configuration_is_the_cohort_deployment_at_window_20():
    cohort = harness.config("kits19-cohort")
    for key in ("driver", "extractor", "env", "precision", "images",
                "roi_model", "geometry_seed", "spacing"):
        assert CONFIG[key] == cohort[key], key
    # the same check table, but StdDev held to float32 rounding at kidney
    # sizes: below the one-pass formula's gap, which the cohort's limit
    # lets through
    check = dict(CONFIG["check"])
    stddev = check.pop("stddev_rel")
    assert check == {k: v for k, v in cohort["check"].items()
                     if k != "stddev_rel"}
    assert stddev == dict(cohort["check"]["stddev_rel"], limit=1e-5)
    assert CONFIG["stream"] == {"window": 20}
    assert len(CONFIG["images"]) == 20
    assert set(CONFIG["assumed"]) == set(cohort["assumed"]) | {"window"}


@pytest.mark.parametrize("config", ["kits19-table2"])
def test_the_bfloat16_control_fails_the_configured_limits(config):
    cfg = harness.config(config)
    fams = tuple(cfg["extractor"].get("families", ("shape",)))
    cases = generate.build_cases(cfg, {"cases": list(SMALL)}, 5)
    refs = [R.features(c, fams) for c in cases]
    ctl = [R.features(c, fams, dtype="bfloat16") for c in cases]
    found = compare.numbers(cfg["check"], list(enumerate(ctl)), refs)
    ok, table = compare.verdict(cfg["check"], found)
    assert not ok, table
    # StdDev included: a kidney's control misses its limit
    assert table["stddev_rel"]["value"] > table["stddev_rel"]["limit"]
    ok, _ = compare.verdict(cfg["check"], compare.numbers(
        cfg["check"], list(enumerate(refs)), refs))
    assert ok


@pytest.fixture(scope="module")
def boxes0():
    return [(c.name, c.box) for c in generate.build_cases(CONFIG, MIX, 0)]


def test_the_mix_sends_every_study_in_dataset_order(boxes0):
    assert MIX["cases"] == "all" and MIX["order"] == "dataset"
    assert [name for name, _ in boxes0] == [cid for cid, _ in
                                            CONFIG["images"]]


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_studies_are_seeded_and_keep_their_boxes(seed, boxes0):
    some = {"cases": ["00004-1", "00004-2"]}
    a = generate.build_cases(CONFIG, some, seed)
    b = generate.build_cases(CONFIG, some, seed)
    other = generate.build_cases(CONFIG, some, seed + 1)
    for x, y, z in zip(a, b, other):
        assert np.array_equal(x.image, y.image)
        assert np.array_equal(x.mask, y.mask)
        assert not np.array_equal(x.image, z.image)
    # the same box, so the same shape bucket, whatever the seed
    cases = generate.build_cases(CONFIG, MIX, seed)
    assert [(c.name, c.box) for c in cases] == boxes0


def test_a_sound_run_is_correct(monkeypatch):
    res = run_cell(monkeypatch, "cohort-table2")
    assert res["correct"] is True
    assert res["attempted"] >= 20 and res["failed"] == 0  # whole windows
    assert set(res["metrics"]) == {"cases_per_s", "setup_s"}
    assert res["check"]["stddev_rel"]["value"] <= 1e-5


def test_firstorder_work_is_per_padded_roi_voxel():
    ops, nbytes = FO.work((12, 10, 7))
    assert ops == 8 * 840
    assert nbytes == 8 * 840 + 4 * 36
    # memory-bound on a v5e, like every reading pass
    assert work.least_seconds(ops, nbytes, V5E)[1] == "memory"


def _run(kernel_s, metas, peaks=V5E):
    plan = NS(metas=metas)
    return NS(summary=NS(kernel_s=kernel_s), peaks=peaks,
              record={"plans": [plan]})


def test_firstorder_roofline_sums_least_times_over_kernel_time():
    metas = [NS(shape=(32, 32, 32), roi_shape=(12, 10, 7)),
             NS(shape=(64, 32, 32), roi_shape=(40, 30, 20)),
             NS(shape=None, roi_shape=None)]  # an empty study: no call
    run = _run({"firstorder": 2e-6}, metas)
    least = sum(work.least_seconds(*FO.work(r), V5E)[0]
                for r in ((12, 10, 7), (40, 30, 20)))
    assert FO.read(run) == pytest.approx(100 * least / 2e-6)
    # the bucket a study is padded to does not change its work
    padded = [NS(shape=(128, 128, 128), roi_shape=m.roi_shape)
              for m in metas[:2]]
    assert FO.read(_run({"firstorder": 2e-6}, padded)) == FO.read(run)


def test_firstorder_roofline_reads_nothing_without_time_or_calls():
    metas = [NS(shape=(32, 32, 32), roi_shape=(12, 10, 7))]
    assert FO.read(_run({"firstorder": 0.0}, metas)) is None
    assert FO.read(_run({}, metas)) is None
    assert FO.read(_run({"firstorder": 1.0}, [])) is None
    assert FO.read(_run({"firstorder": 1.0}, metas, peaks=None)) is None
    run = _run({"firstorder": 1.0}, metas)
    run.summary = None
    assert FO.read(run) is None
    assert math.isfinite(FO.read(_run({"firstorder": 1.0}, metas)))
