"""The trace reduction on hand-built events of the profiler's format."""
from types import SimpleNamespace as NS

import pytest

from chipbench import trace

KERNELS = {"mc": ["mc_volume_area_pallas"],
           "diameter": ["max_diameters_sq_pallas"]}


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, end_ns=end, duration_ns=end - start,
              stats=list(stats.items()))


def hlo(name, op="fusion"):
    """An operation as a TPU trace names it: its HLO instruction."""
    if op == "pallas":
        return (f"%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p), "
                'custom_call_target="tpu_custom_call"')
    return f"%{name} = f32[8]{{0}} {op}(f32[8]{{0}} %p)"


MC = hlo("mc_volume_area_pallas.7", "pallas")
SWEEP = hlo("max_diameters_sq_pallas.1", "pallas")


def plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=e) for n, e in lines])


def profile(device_ops, spans, more_devices=()):
    planes = [plane("/host:CPU", [("python3", spans)]),
              plane("/device:TPU:0", [("XLA Modules", [ev("jit_x", 0, 10**9)]),
                                      ("XLA Ops", device_ops)])]
    for i, ops in enumerate(more_devices, 1):
        planes.append(plane(f"/device:TPU:{i}", [("XLA Ops", ops)]))
    return NS(planes=planes)


WINDOW = [ev("chipbench.window", 100, 1100)]


def test_busy_is_the_union_clipped_to_the_window():
    ops = [ev(hlo("fusion.1"), 50, 300), ev(hlo("fusion.2"), 200, 400),
           ev(MC, 600, 700), ev(hlo("copy.3", "copy"), 1050, 1500)]
    s = trace.summarize(profile(ops, WINDOW), KERNELS)
    assert s.window_s == pytest.approx(1000e-9)
    # [100, 400] + [600, 700] + [1050, 1100]
    assert s.busy_s == pytest.approx(450e-9)
    assert s.idle_share == pytest.approx(0.55)
    assert s.devices == 1


def test_kernel_time_is_the_named_pallas_calls():
    ops = [ev(MC, 200, 300), ev(SWEEP, 300, 450), ev(MC, 500, 520),
           ev(hlo("fusion.3"), 600, 610),
           # an XLA op inside the same jitted function is not the kernel
           ev(hlo("mc_volume_area_pallas.2", "transpose"), 700, 800)]
    s = trace.summarize(profile(ops, WINDOW), KERNELS)
    assert s.kernel_s["mc"] == pytest.approx(120e-9)
    assert s.kernel_s["diameter"] == pytest.approx(150e-9)
    assert s.kernel_calls == {"mc": 2, "diameter": 1}


def test_device_ops_merge_numbered_copies_most_time_first():
    ops = [ev(hlo("fusion.1"), 200, 260), ev(hlo("fusion.22"), 300, 360),
           ev(MC, 400, 500)]
    s = trace.summarize(profile(ops, WINDOW), KERNELS)
    assert s.device_ops[0][0] == "fusion"
    assert s.device_ops[0][1] == pytest.approx(120e-9)
    assert [n for n, _ in s.device_ops] == ["fusion", "mc_volume_area_pallas"]
    assert trace.op_name("%sort.0 = (pred[8]) sort(pred[8] %x)") == "sort"


def test_idle_gaps_go_to_the_innermost_span():
    spans = WINDOW + [ev("dropin.request", 150, 900),
                      ev("dropin.prune", 400, 600)]
    ops = [ev(hlo("a"), 100, 200), ev(hlo("b"), 700, 1100)]
    s = trace.summarize(profile(ops, spans), KERNELS)
    gaps = dict(s.idle_gaps)
    # the one gap, [200, 700), has its middle (450) inside the prune span
    assert gaps == {"dropin.prune": pytest.approx(500e-9)}


def test_gap_outside_every_span_but_the_window():
    ops = [ev(hlo("a"), 600, 1100)]
    spans = WINDOW + [ev("cohort.collect_window", 900, 1000)]
    s = trace.summarize(profile(ops, spans), KERNELS)
    assert dict(s.idle_gaps) == {"chipbench.window": pytest.approx(500e-9)}


def test_busy_and_gaps_average_over_the_devices_that_ran():
    ops0 = [ev(hlo("a"), 100, 600)]
    ops1 = [ev(hlo("a"), 100, 1100)]
    s = trace.summarize(profile(ops0, WINDOW, more_devices=[ops1]), KERNELS)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(750e-9)
    assert dict(s.idle_gaps) == {"chipbench.window": pytest.approx(250e-9)}


def test_a_trace_without_the_window_or_device_work_is_refused():
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.summarize(profile([ev(hlo("a"), 0, 10)], []), KERNELS)
    with pytest.raises(ValueError, match="no device operation"):
        trace.summarize(profile([ev(hlo("a"), 2000, 3000)], WINDOW), KERNELS)


def test_kernel_table_names_the_pallas_kernels():
    table = trace.kernel_table()
    assert {"mc", "diameter", "compact", "firstorder", "glcm"} <= set(table)
    assert all(isinstance(f, str) and f for v in table.values() for f in v)


def test_kernel_table_names_jitted_functions_that_hold_a_pallas_call():
    """Each name in the table is a jitted function of the program whose
    body calls ``pallas_call``: the name its trace events carry."""
    import inspect

    from repro.kernels import compact, diameter, firstorder, glcm
    from repro.kernels import marching_cubes

    mods = [compact, diameter, firstorder, glcm, marching_cubes]
    for names in trace.kernel_table().values():
        for name in names:
            fn = next(getattr(m, name) for m in mods if hasattr(m, name))
            src = inspect.getsource(inspect.unwrap(fn))
            assert "pallas_call" in src or "_brick_partials" in src, name
