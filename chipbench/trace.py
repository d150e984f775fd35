"""Reduction of a profiler trace to device time, idle time and kernel time.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``.  Everything is clipped to the benchmark's
own ``chipbench.window`` span on the host, which is on the same clock.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each TPU plane), averaged over the devices that
  ran any; idle is the rest of the window;
* kernel time: the summed device time of the Pallas calls that
  ``kernels.json`` maps to each kernel.  On a TPU trace an operation's name
  is its HLO instruction, and a Pallas call is a ``tpu_custom_call`` named
  after the jitted function that holds the ``pallas_call``
  (``%mc_volume_area_pallas.7 = ... custom-call(...)``);
* breakdown: the device operations that took most time, and the idle time
  by what the host was doing -- the innermost benchmark span (``chipbench.``,
  ``dropin.``, ``cohort.``) around the middle of each gap.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW_SPAN = "chipbench.window"
SPAN_PREFIXES = ("chipbench.", "dropin.", "cohort.")
DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"


def kernel_table() -> dict:
    """``{kernel: [custom-call names]}`` from ``kernels.json``."""
    with open(os.path.join(HERE, "kernels.json")) as f:
        return {k: v for k, v in json.load(f).items() if k != "about"}


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


@dataclasses.dataclass
class Op:
    name: str  # the HLO instruction's name without its numeric suffix
    start: int  # ns
    end: int  # ns
    pallas: bool  # a tpu_custom_call: a Pallas kernel


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    kernel_s: dict  # kernel -> summed device seconds
    kernel_calls: dict  # kernel -> number of device events
    device_ops: list  # [[name, seconds]] most time first
    idle_gaps: list  # [[host span, seconds]] most time first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _events(pd):
    """``(host spans, {device plane: [Op]})`` of a ProfileData."""
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((int(ev.start_ns), int(ev.end_ns),
                                      ev.name))
        elif DEVICE_PLANE.fullmatch(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(op_name(ev.name), int(ev.start_ns),
                                  int(ev.end_ns),
                                  "tpu_custom_call" in ev.name))
            devices[plane.name] = ops
    return spans, devices


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(spans_by_start, starts, t):
    """The innermost benchmark span around ``t`` (latest start covering it)."""
    i = bisect.bisect_right(starts, t)
    while i > 0:
        i -= 1
        s, e, name = spans_by_start[i]
        if e >= t:
            return name
    return "outside spans"


def op_name(text: str) -> str:
    """``sort`` of ``%sort.0 = (...) sort(...)``: the instruction's name
    without the numeric suffix XLA gives its copies."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def summarize(pd, kernels: dict, top: int = 10) -> Summary:
    spans, devices = _events(pd)
    window = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not window:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    w0, w1 = window[-1]
    spans_by_start = sorted(spans)
    starts = [s for s, _, _ in spans_by_start]
    busy, gaps = [], {}
    kernel_ns = {k: 0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    op_ns: dict = {}
    used = 0
    for ops in devices.values():
        inside = [(max(o.start, w0), min(o.end, w1), o) for o in ops
                  if o.end > w0 and o.start < w1]
        if not inside:
            continue
        used += 1
        merged = _union((s, e) for s, e, _ in inside)
        busy.append(sum(e - s for s, e in merged))
        prev = w0
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                label = _label(spans_by_start, starts, (prev + s) // 2)
                gaps[label] = gaps.get(label, 0) + (s - prev)
            prev = max(prev, e)
        for s, e, o in inside:
            op_ns[o.name] = op_ns.get(o.name, 0) + (e - s)
            if not o.pallas:
                continue
            for k, names in kernels.items():
                if o.name in names:
                    kernel_ns[k] += e - s
                    kernel_calls[k] += 1
                    break
    if not used:
        raise ValueError("no device operation ran inside the window")
    ns = 1e-9
    return Summary(
        window_s=(w1 - w0) * ns,
        busy_s=sum(busy) / used * ns,
        devices=used,
        kernel_s={k: v * ns for k, v in kernel_ns.items()},
        kernel_calls=kernel_calls,
        device_ops=[[k, v * ns] for k, v in
                    sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[k, v * ns / used] for k, v in
                   sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    )


def read(trace_dir: str, kernels: dict | None = None) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(newest_xplane(trace_dir))
    return summarize(pd, kernel_table() if kernels is None else kernels)
