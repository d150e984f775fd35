"""The program's own spans in a profiler trace.

The executor (``src/repro/core/executor.py``) wraps each step of a window
in a ``jax.profiler.TraceAnnotation`` span named ``repro.<step>``, with its
counts as stats.  They are on the profiler's clock, beside the device's
operations.  This module reads them from the ``.xplane.pb`` of a traced
run, clipped to the last ``chipbench.window`` span (in a trace without
one, to the extent of the ``repro.`` spans), and gives:

* host: each span name's count, time, self time (its time less that of
  its ``repro.`` children) and the sums of its numeric stats;
* idle: the device's idle time by the innermost ``repro.`` span around the
  middle of each gap, as ``trace.py`` labels gaps, and the idle time under
  no ``repro.`` span (:data:`NONE`);
* programs: the device time of each program (``jit_<name>``): an
  operation goes to the event of its device plane's ``XLA Modules`` line
  around it, else to its ``hlo_module`` stat.

    python3 -m chipbench.spans <trace dir>

prints the tables and the ``cohort.`` metrics read from them.  Each
metric's reading is ``None`` where the window holds no ``repro.`` span (a
program that has none), so the metric is left out, not read as 0.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import os
import re
import sys

from chipbench.trace import (DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, _union,
                             newest_xplane, op_name)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PREFIX = "repro."
MODULES_LINE = "XLA Modules"
NONE = "(no repro. span)"
NO_MODULE = "(no module)"
IDS = ("window", "case")  # stats that name a window or case: not summed
PREP = ("repro.prep", "repro.prep.crop", "repro.prep.stage",
        "repro.prep.fields")
PASS0 = ("jit__fields_count", "jit__compact_cap")


@dataclasses.dataclass
class Span:
    start: int  # ns
    end: int  # ns
    name: str
    stats: dict


@dataclasses.dataclass
class Spans:
    window_s: float
    devices: int  # devices that ran an operation inside the window
    count: dict  # span name -> spans that start inside the window
    total_s: dict  # span name -> seconds inside the window
    self_s: dict  # span name -> seconds less its repro. children
    stats: dict  # span name -> {stat: sum over the spans counted}
    idle_s: dict  # innermost repro. span (or NONE) -> idle s per device
    program_s: dict  # program -> device seconds, summed over devices
    program_ops: dict  # program -> {operation: device seconds}


def trace_dir(cell: str) -> str:
    """Where ``run.py`` writes the traced run of ``cell``."""
    return os.path.join(ROOT, ".chipbench", "trace", cell)


def program_name(text: str) -> str:
    """``jit_pass2a_mc`` of ``jit_pass2a_mc(1234)``: a module's name
    without the fingerprint the trace gives it."""
    return re.sub(r"\(\d+\)$", "", text.strip())


def _host_spans(pd):
    """``({host line: [Span]}, [(start, end)] of the window spans)``."""
    lines, windows = {}, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            found = []
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    found.append(Span(int(ev.start_ns), int(ev.end_ns),
                                      ev.name, dict(ev.stats)))
                elif ev.name == WINDOW_SPAN:
                    windows.append((int(ev.start_ns), int(ev.end_ns)))
            if found:
                lines[(plane.name, line.name)] = sorted(
                    found, key=lambda s: (s.start, -s.end))
    return lines, windows


def _innermost(spans, times):
    """The innermost span around each of ``times`` (ascending): the latest
    start that covers it.  ``spans`` are sorted by start."""
    out, stack, k = [], [], 0
    for t in times:
        while k < len(spans) and spans[k].start <= t:
            stack.append(spans[k])
            k += 1
        while stack and stack[-1].end < t:
            stack.pop()
        out.append(stack[-1].name if stack else NONE)
    return out


def summarize(pd) -> Spans | None:
    lines, windows = _host_spans(pd)
    every = sorted((s for found in lines.values() for s in found),
                   key=lambda s: (s.start, -s.end))
    if not every:
        return None
    if windows:
        w0, w1 = max(windows)
    else:
        w0, w1 = every[0].start, max(s.end for s in every)

    def inside(s, e):
        return max(0, min(e, w1) - max(s, w0))

    count, total, own = (collections.Counter() for _ in range(3))
    stats = collections.defaultdict(collections.Counter)
    for found in lines.values():
        stack = []  # the open spans of this host thread, outermost first
        for s in found:
            while stack and stack[-1].end <= s.start:
                stack.pop()
            ns = inside(s.start, s.end)
            total[s.name] += ns
            own[s.name] += ns
            if stack:
                own[stack[-1].name] -= ns
            stack.append(s)
            if w0 <= s.start < w1:
                count[s.name] += 1
                for k, v in s.stats.items():
                    if k not in IDS and isinstance(v, (int, float)):
                        stats[s.name][k] += v
    if not count:
        return None

    gaps, used = [], 0
    program_ops = collections.defaultdict(collections.Counter)
    for plane in pd.planes:
        if not DEVICE_PLANE.fullmatch(plane.name):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [ev for ev in line.events
                       if ev.end_ns > w0 and ev.start_ns < w1]
            elif line.name == MODULES_LINE:
                modules = sorted((int(ev.start_ns), int(ev.end_ns),
                                  program_name(ev.name))
                                 for ev in line.events)
        if not ops:
            continue
        used += 1
        starts = [m[0] for m in modules]
        for ev in ops:
            s, e = int(ev.start_ns), int(ev.end_ns)
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and modules[i][1] >= s:
                program = modules[i][2]
            else:
                program = program_name(
                    str(dict(ev.stats).get("hlo_module", NO_MODULE)))
            program_ops[program][op_name(ev.name)] += inside(s, e)
        prev = w0
        merged = _union((max(int(ev.start_ns), w0), min(int(ev.end_ns), w1))
                        for ev in ops)
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                gaps.append(((prev + s) // 2, s - prev))
            prev = max(prev, e)
    gaps.sort()
    idle = collections.Counter()
    for label, (_, ns) in zip(_innermost(every, [t for t, _ in gaps]), gaps):
        idle[label] += ns

    sec = 1e-9
    return Spans(
        window_s=(w1 - w0) * sec,
        devices=used,
        count=dict(count),
        total_s={k: v * sec for k, v in total.items()},
        self_s={k: v * sec for k, v in own.items()},
        stats={k: dict(v) for k, v in stats.items()},
        idle_s={k: v * sec / used for k, v in idle.items()},
        program_s={p: sum(o.values()) * sec for p, o in program_ops.items()},
        program_ops={p: {k: v * sec for k, v in o.items()}
                     for p, o in program_ops.items()},
    )


@functools.lru_cache(maxsize=1)
def _load(xplane: str) -> Spans | None:
    from jax.profiler import ProfileData

    return summarize(ProfileData.from_file(xplane))


def read(trace: str) -> Spans | None:
    """The spans of the newest trace under ``trace`` (read once per file)."""
    return _load(newest_xplane(trace))


# -- the cohort. metrics -----------------------------------------------------

def _studies(s: Spans) -> int:
    return s.count.get("repro.prep", 0)


def prep_host_ms(s: Spans):
    """Host time of pass 0 per study: the self time of ``repro.prep`` and
    its crop/stage/fields children, the fetches nested in them left out."""
    if not _studies(s):
        return None
    return 1e3 * sum(s.self_s.get(n, 0.0) for n in PREP) / _studies(s)


def fetch_wait_ms(s: Spans):
    """Host time blocked in ``repro.fetch`` per study prepped."""
    if not _studies(s):
        return None
    return 1e3 * s.total_s.get("repro.fetch", 0.0) / _studies(s)


def h2d_kib_per_case(s: Spans):
    """Bytes staged to the device by ``repro.prep.stage`` per study."""
    if not _studies(s):
        return None
    staged = s.stats.get("repro.prep.stage", {}).get("bytes", 0)
    return staged / 1024 / _studies(s)


def _idle_share(s: Spans, wanted) -> float | None:
    if not s.devices:
        return None
    return 100.0 * sum(v for k, v in s.idle_s.items()
                       if wanted(k)) / s.window_s


def idle_in_prep(s: Spans):
    """Share of the window idle with a ``repro.prep*`` span innermost."""
    return _idle_share(s, lambda k: k.startswith("repro.prep"))


def idle_in_dispatch(s: Spans):
    """Share of the window idle with ``repro.plan`` or a
    ``repro.launch.*`` span innermost."""
    return _idle_share(s, lambda k: k == "repro.plan"
                       or k.startswith("repro.launch."))


def pass0_device_ms(s: Spans):
    """Device time of the pass-0 programs (``_fields_count``,
    ``_compact_cap``) per study prepped."""
    if not set(s.program_s) - {NO_MODULE} or not _studies(s):
        return None
    return 1e3 * sum(s.program_s.get(p, 0.0) for p in PASS0) / _studies(s)


METRICS = {
    "cohort.prep_host_ms": prep_host_ms,
    "cohort.fetch_wait_ms": fetch_wait_ms,
    "cohort.h2d_kib_per_case": h2d_kib_per_case,
    "cohort.idle_in_prep": idle_in_prep,
    "cohort.idle_in_dispatch": idle_in_dispatch,
    "cohort.pass0_device_ms": pass0_device_ms,
}


def reading(run, metric):
    """``metric`` of a run's trace; ``None`` for an untraced run or a trace
    without ``repro.`` spans."""
    if not run.trace:
        return None
    s = read(trace_dir(run.cell["name"]))
    return None if s is None else metric(s)


def report(s: Spans, top: int = 6) -> str:
    out = [f"window {s.window_s:.6f} s, {s.devices} device(s)", "",
           f"{'host span':<26}{'count':>8}{'time_s':>12}{'self_s':>12}"
           "  stat sums"]
    for name in sorted(s.total_s):
        sums = ", ".join(f"{k}={v}" for k, v in
                         sorted(s.stats.get(name, {}).items()))
        out.append(f"{name:<26}{s.count.get(name, 0):>8}"
                   f"{s.total_s[name]:>12.6f}{s.self_s[name]:>12.6f}  {sums}")
    if not s.devices:
        out += ["", "no device ran an operation in the window"]
    idle = sum(s.idle_s.values())
    out += ["", f"{'device idle by innermost span':<30}{'s':>12}"
            f"{'% window':>10}{'% idle':>9}"]
    for name, v in sorted(s.idle_s.items(), key=lambda kv: -kv[1]):
        out.append(f"{name:<30}{v:>12.6f}{100 * v / s.window_s:>10.3f}"
                   f"{100 * v / idle:>9.2f}")
    out.append(f"{'all idle':<30}{idle:>12.6f}"
               f"{100 * idle / s.window_s:>10.3f}")
    out += ["", f"{'device time by program':<30}{'s':>12}  top operations"]
    for name, v in sorted(s.program_s.items(), key=lambda kv: -kv[1]):
        ops = sorted(s.program_ops[name].items(), key=lambda kv: -kv[1])
        out.append(f"{name:<30}{v:>12.6f}  " + ", ".join(
            f"{k} {t:.6f}" for k, t in ops[:top]))
    out.append("")
    for name, metric in METRICS.items():
        out.append(f"{name} = {metric(s)}")
    return "\n".join(out)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 -m chipbench.spans <trace dir>",
              file=sys.stderr)
        return 2
    s = read(argv[0])
    if s is None:
        print(f"no {PREFIX} span in the trace under {argv[0]}",
              file=sys.stderr)
        return 1
    print(report(s))
    return 0


if __name__ == "__main__":
    sys.exit(main())
