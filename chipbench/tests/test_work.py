"""Work counts, peaks and the roofline share built on them."""
import itertools
from types import SimpleNamespace as NS

import numpy as np
import pytest

from chipbench import harness, metrics_common, work

V5E = harness.peaks("TPU v5 lite")


def test_mc_counts_at_fixed_shapes():
    # ROI 10 x 20 x 30: staged 12 x 22 x 32 voxels, 11 x 21 x 31 cells
    ops, nbytes = work.mc((10, 20, 30), triangles=100)
    assert ops == 16 * 11 * 21 * 31 + 39 * 100
    assert nbytes == 4 * 12 * 22 * 32 + 8
    assert work.mc((1, 1, 1), 0) == (16 * 8, 4 * 27 + 8)


def test_diameter_counts_every_unordered_pair_once():
    for m in (2, 3, 10, 4097):
        pairs = sum(1 for _ in itertools.combinations(range(m), 2)) \
            if m < 100 else m * (m - 1) // 2
        assert work.diameter(m) == (14 * pairs, 16 * m + 16)
    assert work.diameter(1) == (0, 32)


@pytest.mark.parametrize("variant", ["naive", "fused", "tri", "seqacc",
                                     "tri_prefetch", "nomask", "gram"])
@pytest.mark.parametrize("block", [128, 256, 512])
def test_sweep_work_is_the_same_for_every_variant_and_block(variant, block):
    """The count follows the vertices handed to the sweep, not the bucket,
    variant or block the kernel runs at (whose own estimate differs)."""
    from repro.kernels import diameter as dk

    m = 3000
    bucket = 4096
    assert work.diameter(m) == (14 * m * (m - 1) // 2, 16 * m + 16)
    assert dk.flop_estimate(bucket, block, variant) != work.diameter(m)[0]


def test_least_time_takes_the_larger_bound_and_names_it():
    t, bound = work.least_seconds(197e12, 1.0, V5E)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = work.least_seconds(1.0, 819e9 * 2, V5E)
    assert (t, bound) == (pytest.approx(2.0), "memory")


def test_published_peaks_only_and_unknown_devices_raise():
    assert V5E["peak_flops_bf16"] == 197e12
    assert V5E["hbm_bw"] == 819e9
    assert "vpu" not in " ".join(V5E)
    with pytest.raises(KeyError, match="no published peaks"):
        harness.peaks("TPU v99")
    with pytest.raises(KeyError):
        harness.peaks("cpu")


def _run(kernel_s, mc_cases=(), sweeps=(), peaks=V5E):
    cases = [NS(roi_dims=(10, 20, 30)), NS(roi_dims=(40, 40, 40))]
    refs = [{"n_triangles": 100}, {"n_triangles": 900}]
    summary = NS(kernel_s=kernel_s, idle_share=0.25)
    return NS(cases=cases, refs=refs, summary=summary, peaks=peaks,
              record={"mc_cases": list(mc_cases), "sweeps": list(sweeps)})


def test_roofline_share_sums_least_times_over_kernel_time():
    run = _run({"mc": 1e-3, "diameter": 2e-3}, mc_cases=[0, 1, 1],
               sweeps=[(0, 1000), (1, 5000)])
    least_mc = sum(work.least_seconds(*work.mc(d, t), V5E)[0] for d, t in
                   [((10, 20, 30), 100), ((40, 40, 40), 900),
                    ((40, 40, 40), 900)])
    assert metrics_common.mc_roofline(run) == pytest.approx(
        100 * least_mc / 1e-3)
    least_d = sum(work.least_seconds(*work.diameter(m), V5E)[0]
                  for m in (1000, 5000))
    assert metrics_common.diameter_roofline(run) == pytest.approx(
        100 * least_d / 2e-3)
    assert metrics_common.idle_share(run) == pytest.approx(25.0)


def test_roofline_reads_nothing_without_trace_time_or_calls():
    assert metrics_common.mc_roofline(_run({"mc": 0.0}, mc_cases=[0])) is None
    assert metrics_common.mc_roofline(_run({"mc": 1e-3})) is None
    assert metrics_common.diameter_roofline(
        _run({"diameter": 1e-3}, sweeps=[(0, 9)], peaks=None)) is None
    run = _run({"mc": 1.0})
    run.summary = None
    assert metrics_common.idle_share(run) is None
    assert np.isfinite(metrics_common.mc_roofline(
        _run({"mc": 1.0}, mc_cases=[1])))
