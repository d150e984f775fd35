"""Cost model: measured autotune tables + plan censuses -> scheduling decisions.

Four PRs built the *mechanisms* of the batched pipeline -- pruned two-pass
execution, device-resident compaction, the sync-free static schedule, the
streaming front-end -- but left their *selection* to hand-chosen knobs
(``schedule=``, ``window=``, count- vs hint-sized prep).  The paper's
claim is transparent acceleration "in all scenarios", which means the
pipeline must pick its own execution strategy: this module is that
component.  It is fed by exactly two information sources, both already
persisted:

* the **v3 autotune cache** (``runtime/autotune``): measured per-bucket,
  per-batch-depth kernel timings (the ``us`` field of every
  ``diameter/<backend>/M<bucket>/B<depth>`` record) plus the new
  ``sync/<backend>`` d2h-latency probe;
* the **plan layer's census** (``core/plan``): per-case metadata --
  shape buckets, vertex caps, hint counts, pad-waste fractions -- that
  exists BEFORE any device work runs.

Decisions served (wired through ``core/executor``):

``choose_schedule(metas)``
    Counted vs static per window.  The counted schedule pays one d2h
    sync per cap group but sweeps each case at its tight M' bucket; the
    static schedule is sync-free but sweeps at the cap's aligned
    power-of-two target (``plan.static_bucket``).  The model compares
    ``n_groups * sync_us + tight-sweep cost`` against the padded-sweep
    cost; on a zero-latency local device counted wins (the measured PR 4
    trade-off), on a high-latency link (a large calibrated
    ``sync/<backend>`` entry) static wins.

``should_close(census, meta)``
    Adaptive streaming windows (``extract_stream(window='auto')``).
    Close the open window early when the incoming case introduces a new
    shape/cap bucket while every current sub-batch already sits at or
    past its break-even depth (a fresh singleton bucket would only
    fragment a healthy window); extend homogeneous runs until the
    memory-budgeted cap (``REPRO_STREAM_MEM_MB``, default 512 MiB of
    staged masks + vertex stacks, plus -- on the backends that run the
    marching-cubes brick kernel -- the device temporaries of the
    window's largest MC call, ``kernels/marching_cubes.work_bytes``) or
    the absolute case cap.

``break_even_depth(cap)``
    The smallest power-of-two sub-batch depth whose measured per-case
    cost is within :data:`BREAK_EVEN_SLACK` of the best measured depth
    for that bucket -- read straight off the v3 depth-keyed tables.
    With fewer than two measured depths (fresh cache, 'ref' backend) the
    conservative :data:`DEFAULT_BREAK_EVEN_DEPTH` applies.

``deadline_at_risk(census, slack_us)``
    The serving tier's latency-vs-throughput decision
    (``serve/service.py`` -- PR 8): every decision above optimises
    THROUGHPUT, but a persistent service also owes each request its
    deadline.  The open window's modeled collect cost
    (:meth:`CostModel.window_cost_us`: the diameter sweeps, which
    dominate per Table 2, plus one sync per cap group) is compared
    against the slack remaining before the OLDEST pending deadline; once
    the cost -- times a :data:`DEADLINE_SAFETY` margin for everything
    the model cannot see (MC, staging, drain) -- reaches the slack, the
    window must close NOW, even though throughput alone would keep
    absorbing cases.  No deadline pending means no latency pressure and
    the throughput rules above decide alone.

Roofline fallback (the estimate hierarchy): an unmeasured bucket's price
comes from the FIRST source in this ladder that can answer --

1. **measured**: a ``diameter/<backend>/M<bucket>/B<depth>`` autotune
   entry (the nearest shallower measured depth is consulted next) --
   real wall time always wins;
2. **roofline**: ``max(flops/peak_flops, bytes/mem_bw)`` from the
   structural work model (``runtime/roofline.diameter_cost``) under the
   backend's hardware profile.  The profile resolves through
   ``core/dispatcher.hw_profile`` -> ``autotune.get_hw_profile``: a
   measured ``hw/<backend>`` cache entry when one exists, a tiny
   one-time probe where probing is allowed (same policy as the
   ``sync/`` probe: pallas by default, ``REPRO_AUTOTUNE=1`` forces,
   ``=0`` disables), or the static per-backend default profile;
3. **analytic constant**: ``(cap/1024)^2 * PAIR_SWEEP_US`` -- reachable
   only when NO hardware profile exists (an unknown backend string, or
   ``REPRO_ROOFLINE=0`` explicitly disabling the roofline layer).

Determinism contract (tier-1-locked): every decision is a pure function
of (backend, cache file contents, plan metadata) -- with sweeps/probes
disabled (``REPRO_AUTOTUNE=0``) the model never measures, never writes,
and returns identical answers for identical inputs, which is what makes
an auto-configured run reproducible from its committed cache.  The
roofline layer preserves this: with probing disabled the hardware
profile is the static per-backend default, a constant.
"""
from __future__ import annotations

import os
import warnings

from repro.core import plan as planlib
from repro.kernels import marching_cubes as mck
from repro.runtime import autotune
from repro.runtime import roofline as rooflib

# analytic fallback for an unmeasured diameter bucket: the pair sweep is
# O(cap^2), anchored at ~PAIR_SWEEP_US per (1024)^2-pair launch (the order
# of the measured CPU-ref numbers in BENCH_diameter.json).  Only RATIOS
# between bucket sizes matter to the decisions, not the absolute scale.
# Reached only when no hardware profile exists -- see "Roofline fallback"
# in the module docstring.
PAIR_SWEEP_US = 200.0

# fraction of pre-prune vertices assumed to survive the exact bound when no
# count exists yet (the autotune compact probe uses the same ~25% figure)
ASSUMED_KEEP_FRACTION = 0.25

# a sub-batch depth is "past break-even" when its measured per-case cost is
# within this factor of the best measured depth for the bucket
BREAK_EVEN_SLACK = 1.25
DEFAULT_BREAK_EVEN_DEPTH = 4
MAX_PROBED_DEPTH = 64

DEFAULT_WINDOW_MEM_MB = 512.0
DEFAULT_WINDOW_MAX_CASES = 256

# safety margin on the modeled window cost when weighing it against a
# request deadline: the model only sees the diameter sweeps + syncs, not
# MC, staging, or the drain itself, so it under-estimates wall time
DEADLINE_SAFETY = 2.0

# environment variables already warned about this process (warn ONCE per
# variable: a streaming run reads the budget on every CostModel build)
_warned_env: set = set()


def _env_float(name: str, default: float) -> float:
    """Float from the environment; malformed values warn ONCE and fall back.

    An unset (or empty) variable is simply the default -- only a value
    that is present but unparseable warns: a typo'd
    ``REPRO_STREAM_MEM_MB=512MB`` silently becoming 512 MiB-the-default
    is exactly the kind of config rot a long-running service never
    notices (the satellite bugfix of PR 8).
    """
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        if name not in _warned_env:
            _warned_env.add(name)
            warnings.warn(
                f"malformed {name}={raw!r} in the environment; "
                f"falling back to the default {default!r}",
                RuntimeWarning,
                stacklevel=2,
            )
        return default


class CostModel:
    """Backend-calibrated decision layer over the autotune cache.

    One instance per executor; lookups are memoised per instance (the
    cache file is re-read at most once per distinct query), so a
    streaming run of thousands of windows costs no repeated JSON I/O.
    """

    def __init__(self, backend: str, cache: autotune.AutotuneCache | None = None,
                 *, assumed_keep: float = ASSUMED_KEEP_FRACTION,
                 break_even_default: int = DEFAULT_BREAK_EVEN_DEPTH,
                 window_mem_bytes: float | None = None,
                 window_max_cases: int | None = None):
        self.backend = backend
        self.cache = cache or autotune.AutotuneCache()
        self.assumed_keep = assumed_keep
        self.break_even_default = break_even_default
        if window_mem_bytes is None:
            window_mem_bytes = (
                _env_float("REPRO_STREAM_MEM_MB", DEFAULT_WINDOW_MEM_MB) * 2**20
            )
        self.window_mem_bytes = float(window_mem_bytes)
        if window_max_cases is None:
            window_max_cases = int(
                _env_float("REPRO_STREAM_MAX_CASES", DEFAULT_WINDOW_MAX_CASES)
            )
        self.window_max_cases = int(window_max_cases)
        self._sync_us: float | None = None
        self._hw_profile: dict | None | str = "unresolved"
        self._diam_us: dict = {}
        self._break_even: dict = {}

    # -- measured lookups ---------------------------------------------------

    def sync_cost_us(self) -> float:
        """Per-fetch d2h latency: the calibrated ``sync/<backend>`` entry."""
        if self._sync_us is None:
            from repro.core import dispatcher  # local import: avoid cycle

            self._sync_us = dispatcher.sync_cost(self.backend, cache=self.cache)
        return self._sync_us

    def hw_profile(self) -> dict | None:
        """The backend's hardware roofline profile (None: no profile).

        Resolved once per instance through ``dispatcher.hw_profile`` --
        the cached/probed/default ladder documented in the module
        docstring's "Roofline fallback" section.
        """
        if self._hw_profile == "unresolved":
            from repro.core import dispatcher  # local import: avoid cycle

            self._hw_profile = dispatcher.hw_profile(
                self.backend, cache=self.cache
            )
        return self._hw_profile

    def _measured_us(self, key: str) -> float | None:
        hit = self.cache.get(key)
        if hit is None:
            return None
        try:
            us = float(hit["us"])
        except (KeyError, TypeError, ValueError):
            return None
        return us if us > 0 else None

    def diameter_case_us(self, cap: int, depth: int = 1) -> float:
        """Modeled PER-CASE pair-sweep cost at a (bucket, depth) pair.

        The estimate hierarchy (module docstring, "Roofline fallback"):
        a measured ``diameter/<backend>/M<cap>/B<depth>`` entry wins (its
        ``us`` is the whole launch: divide by the depth bucket; the
        nearest shallower measured depth is consulted next); an
        unmeasured bucket is priced by the roofline bound under the
        backend's hardware profile; the analytic O(cap^2) constant
        applies only when no profile exists.
        """
        cap = int(cap)
        d = autotune.batch_bucket(max(1, depth))
        memo = (cap, d)
        if memo in self._diam_us:
            return self._diam_us[memo]
        out = None
        probe = d
        while probe >= 1:  # nearest shallower measured depth
            us = self._measured_us(autotune.sweep_key(cap, self.backend, probe))
            if us is not None:
                out = us / probe
                break
            probe //= 2
        if out is None:
            profile = self.hw_profile()
            if profile is not None:
                flops, nbytes = rooflib.diameter_cost(cap, 1)
                out = rooflib.roofline_us(flops, nbytes, profile)
            else:
                out = (cap / 1024.0) ** 2 * PAIR_SWEEP_US
        self._diam_us[memo] = out
        return out

    def break_even_depth(self, cap: int) -> int:
        """Smallest measured depth within BREAK_EVEN_SLACK of the best.

        Reads the depth ladder ``.../B1, .../B2, ...`` of the bucket's
        diameter entries; fewer than two measured depths mean the ladder
        cannot be ranked and the conservative default applies.
        """
        cap = int(cap)
        if cap in self._break_even:
            return self._break_even[cap]
        per_case = {}
        d = 1
        while d <= MAX_PROBED_DEPTH:
            us = self._measured_us(autotune.sweep_key(cap, self.backend, d))
            if us is not None:
                per_case[d] = us / d
            d *= 2
        if len(per_case) < 2:
            out = self.break_even_default
        else:
            best = min(per_case.values())
            out = next(
                d for d in sorted(per_case)
                if per_case[d] <= BREAK_EVEN_SLACK * best
            )
        self._break_even[cap] = out
        return out

    # -- decision: counted vs static schedule --------------------------------

    def choose_schedule(self, metas) -> str:
        """Pick the pass-2b schedule for one window of case metadata.

        counted:  one sync per cap group + tight (estimated M') sweeps;
        static:   zero syncs + padded sweeps at the aligned cap target.
        The keep fraction is estimated (``assumed_keep``) because the
        whole point of the decision is that no count has been fetched
        yet.  Ties break toward counted, the zero-latency default.
        """
        sync_us = self.sync_cost_us()
        groups: dict[int, list] = {}
        for m in metas:
            if not getattr(m, "empty", False) and m.vertex_cap:
                groups.setdefault(int(m.vertex_cap), []).append(m)
        if not groups:
            return "counted"
        counted = static = 0.0
        for cap, group in groups.items():
            depth = autotune.batch_bucket(len(group))
            counted += sync_us  # the (B, 2) count fetch, one per cap group
            target = planlib.static_bucket(cap) or cap
            for m in group:
                kept = max(2, int(m.n_vertices * self.assumed_keep))
                tight = min(planlib.vertex_bucket(kept), cap)
                counted += self.diameter_case_us(tight, depth)
                static += self.diameter_case_us(target, depth)
        return "counted" if counted <= static else "static"

    # -- decision: latency vs throughput (the serving tier) ------------------

    def window_cost_us(self, census: planlib.WindowCensus) -> float:
        """Modeled collect-side cost of the OPEN window, in microseconds.

        The diameter sweeps dominate extraction (95.7-99.9% per the
        paper's Table 2), so the model is their per-(cap, depth) cost
        off the measured tables -- the same lookups
        :meth:`choose_schedule` uses -- plus one d2h sync per cap group.
        Deliberately an under-estimate of wall time (no MC, staging, or
        drain term): callers weighing it against a deadline apply
        :data:`DEADLINE_SAFETY`.
        """
        total = 0.0
        for cap, depth in census.cap_depths.items():
            d = autotune.batch_bucket(max(1, depth))
            total += self.sync_cost_us()
            total += depth * self.diameter_case_us(cap, d)
        return total

    def deadline_at_risk(self, census: planlib.WindowCensus,
                         slack_us: float | None,
                         safety: float = DEADLINE_SAFETY) -> bool:
        """Must the open window close NOW to honour its oldest deadline?

        ``slack_us`` is the time remaining until the oldest pending
        deadline among the window's requests (``None``: no deadline, no
        latency pressure).  True once the modeled window cost, padded by
        ``safety``, reaches the slack -- the first latency-vs-throughput
        decision in the pipeline: a throughput-optimal window keeps
        absorbing cases, a deadline-safe one stops batching and ships.
        An already-expired deadline (slack <= 0) always closes.
        """
        if census.cases == 0 or slack_us is None:
            return False
        if slack_us <= 0:
            return True
        return self.window_cost_us(census) * safety >= slack_us

    # -- decision: adaptive stream windows -----------------------------------

    def window_budget_cases(self, census: planlib.WindowCensus) -> int:
        """Memory-budgeted case cap for the open window (>= 1)."""
        if census.cases and census.bytes:
            per_case = census.bytes / census.cases
            return max(1, min(self.window_max_cases,
                              int(self.window_mem_bytes // per_case)))
        return self.window_max_cases

    def mc_work_bytes(self, census: planlib.WindowCensus,
                      meta: planlib.CaseMeta) -> int:
        """Device temporaries of the largest MC call once ``meta`` joins.

        The brick kernel's corner planes are several times the staged
        mask; its batch maps cases one at a time, so only the largest
        shape counts.  Zero on ``ref``, whose marching cubes is the jnp
        z-slab scan.
        """
        if self.backend == "ref":
            return 0
        shapes = list(census.shape_depths)
        if not meta.empty:
            shapes.append(meta.shape)
        return max((mck.work_bytes(s) for s in shapes), default=0)

    def should_close(self, census: planlib.WindowCensus,
                     meta: planlib.CaseMeta) -> bool:
        """Close the open window before admitting ``meta``?

        True when the window hit its memory/case budget, or when ``meta``
        introduces a new shape/cap bucket while every current sub-batch
        already sits at or past its break-even depth -- a fresh singleton
        bucket would fragment a window whose groups are all healthy,
        whereas a still-shallow window keeps absorbing heterogeneity
        (windows must be allowed to grow past one bucket at all).
        """
        if census.cases == 0:
            return False
        if census.cases >= self.window_budget_cases(census):
            return True
        if (census.bytes + planlib.meta_bytes(meta)
                + self.mc_work_bytes(census, meta) > self.window_mem_bytes):
            return True
        if not census.fragments(meta):
            return False
        depths = list(census.shape_depths.values()) + list(
            census.cap_depths.values()
        )
        if not depths:  # only empty-mask cases so far: nothing to fragment
            return False
        break_even = max(self.break_even_depth(cap)
                         for cap in census.cap_depths) if census.cap_depths \
            else self.break_even_default
        return min(depths) >= break_even
