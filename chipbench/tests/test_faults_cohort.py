"""A cohort run with the timed path broken underneath reads not correct."""
import numpy as np
import pytest

from chipbench.tests.runs import run_cell


def test_a_sound_run_is_correct(monkeypatch):
    res = run_cell(monkeypatch, "cohort-tumour")
    assert res["correct"] is True
    assert res["attempted"] >= 32 and res["failed"] == 0  # whole windows
    assert set(res["metrics"]) == {"cases_per_s", "setup_s"}
    assert list(res)[-1] == "check"


def _mc_scaled(fn):
    def broken(*a, **kw):
        return fn(*a, **kw) * np.float32(1.0 + 1e-3)

    return broken


def _glcm_scaled(fn):
    def broken(*a, **kw):
        return fn(*a, **kw) * np.float32(1.0 + 1e-3)

    return broken


def _stddev_scaled(fn):
    def broken(*a, **kw):
        from repro.kernels.firstorder import FEATURES

        out = np.array(fn(*a, **kw))
        out[..., FEATURES.index("StdDev")] *= np.float32(1.0 + 1e-3)
        return out

    return broken


@pytest.mark.parametrize("owner,target,wrap,number", [
    ("repro.kernels.ops", "mc_volume_area_batch", _mc_scaled, "volume_rel"),
    ("repro.kernels.glcm", "glcm_features_from_matrix_np", _glcm_scaled,
     "glcm_rel"),
    ("repro.kernels.firstorder", "features_from_packed_np", _stddev_scaled,
     "stddev_rel"),
])
def test_an_answer_altered_where_it_is_produced_fails(monkeypatch, owner,
                                                      target, wrap, number):
    import importlib

    mod = importlib.import_module(owner)
    monkeypatch.setattr(mod, target, wrap(getattr(mod, target)))
    res = run_cell(monkeypatch, "cohort-tumour")
    assert res["correct"] is False
    row = res["check"][number]
    assert row["value"] > row["limit"]
