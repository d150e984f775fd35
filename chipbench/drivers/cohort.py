"""A cohort sweep: one closed-loop ``extract_stream`` over the mix's studies.

The configuration's ``extractor`` keywords go to ``BatchedExtractor`` and
its ``stream`` keywords to ``extract_stream``, unchanged: a user's call.
The stream replays the mix's order for :data:`STREAM_CASES` studies, far
more than a run reads (``extract_stream`` takes in its whole input before
the first window, so the input cannot be endless).  Rows arrive a window at
a time, so the measured window starts and ends where a window's rows have
all been returned: the rate is the rows of whole windows over the time
they took.

Two public methods of the executor are watched: ``submit_window`` (what
each window holds, when it was launched) and ``collect_window`` (when its
rows come back).  Set-up reads the stream until a window repeats the
composition of an earlier one, so every window shape the cycle makes has
compiled before the clock starts.
"""
from __future__ import annotations

import itertools
import time

import numpy as np

STREAM_CASES = 100_000


class Driver:
    def __init__(self, config, cases, order, trace: bool):
        from repro.core.pipeline import BatchedExtractor
        from repro.core import plan

        self.bx = BatchedExtractor(**config["extractor"])
        self.families = tuple(self.bx.families)
        self.names = plan.feature_names(self.families)
        self.cases = cases
        self.trace = trace
        # case position of every study in the stream, in order
        self.sent = list(itertools.islice(order, STREAM_CASES))
        self.launched = 0  # studies in every window launched so far
        self.submitted = []  # one record per window launched, in order
        self._open = {}  # id of a launched window -> its record
        self.collected = 0  # rows of every collected window
        ex = self.bx.executor
        ex.submit_window = self._watch_submit(ex.submit_window)
        ex.collect_window = self._watch_collect(ex.collect_window)
        self.stream = self.bx.extract_stream(
            ((cases[p].image, cases[p].mask, cases[p].spacing)
             for p in self.sent), **config["stream"])
        self.read = 0  # rows read from the stream

    def _watch_submit(self, submit):
        import jax

        def watched(cases, *a, **kw):
            first = self.launched
            self.launched += len(cases)
            with jax.profiler.TraceAnnotation("cohort.submit_window"):
                w = submit(cases, *a, **kw)
            # metadata only: holding the window would hold its device arrays
            rec = {"positions": tuple(self.sent[first:self.launched]),
                   "plan": w.plan, "sweeps": _sweeps(w)}
            self.submitted.append(rec)
            self._open[id(w)] = rec
            return w

        return watched

    def _watch_collect(self, collect):
        import jax

        def watched(window, *a, **kw):
            with jax.profiler.TraceAnnotation("cohort.collect_window"):
                rows, stats = collect(window, *a, **kw)
            rec = self._open.pop(id(window), None)
            if rec is not None:
                rec["sweeps"] = _sweeps(window)
            self.collected += len(rows)
            return rows, stats

        return watched

    def _next_row(self):
        row = next(self.stream)
        self.read += 1
        return row

    def _at_boundary(self) -> bool:
        return self.read == self.collected

    def warm(self):
        """Read until a submitted window repeats an earlier composition,
        then to the end of the window being returned."""
        while True:
            self._next_row()
            comps = [rec["positions"] for rec in self.submitted]
            if len(set(comps)) < len(comps) and self._at_boundary():
                return

    def _spans(self):
        from chipbench.census import spans

        ex = self.bx.executor
        return spans([
            (ex, "submit_prepped", "cohort.plan_launch"),
        ] if self.trace else [])

    def window(self, seconds: float) -> dict:
        """Whole windows of rows until ``seconds`` have passed."""
        ex = self.bx.executor
        answers, errors = [], []
        fetches0 = dict(ex.transfer_log)
        first_sub = len(self.submitted)
        with self._spans():
            t0 = time.perf_counter()
            close = t0 + seconds
            while self.read < len(self.sent):
                pos = self.sent[self.read]
                row = np.asarray(self._next_row(), np.float64)
                if np.isfinite(row).all():
                    answers.append((pos, dict(zip(self.names, row))))
                else:
                    errors.append(f"{self.cases[pos].name}: quarantined row")
                if self._at_boundary() and time.perf_counter() >= close:
                    break
            elapsed = time.perf_counter() - t0
        fetches = {k: v - fetches0.get(k, 0) for k, v in ex.transfer_log.items()}
        launched = self.submitted[first_sub:]
        return {
            "attempted": len(answers) + len(errors),
            "errors": errors,
            "answers": answers,
            "latencies_s": [],
            "cases": len(answers) + len(errors),
            "elapsed_s": elapsed,
            "host_fetches": sum(fetches.values()),
            # windows launched inside the measured window (their plans)
            "plans": [rec["plan"] for rec in launched],
            "sweeps": [(pos, n) for rec in launched
                       for pos, n in zip(rec["positions"], rec["sweeps"])
                       if n is not None],
            "mc_cases": [pos for rec in launched for pos in rec["positions"]],
        }

    def close(self):
        self.stream.close()
        self.bx = None
        self._open = {}


def _sweeps(window):
    """Vertices each case's pair sweep was given, from the program's prune
    census (``None`` until the schedule has decided)."""
    out = []
    for p in window.prepped:
        info = p.prune_info
        out.append(None if info is None else
                   info.m_kept if info.pruned else info.m_valid)
    return out
