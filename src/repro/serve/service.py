"""Radiomics-as-a-service: a persistent extraction service (PR 8).

The batch pipeline answers "extract these 40 000 cases"; this module
answers "keep extracting, forever, for everyone" -- ROADMAP direction 3,
the millions-of-users story (Nyxus in PAPERS.md frames feature
extraction the same way: an always-on component of big-data/AI
pipelines, not a one-shot script).  The mechanism is exactly what the
sync-free pipeline was built for: because ``prep='hint'`` +
``schedule='static'`` submit windows without ever blocking on a device
sync, cases from UNRELATED clients can be fused into shared windows and
the device never waits on a straggling tenant.

Architecture (one driver thread owns all device work)::

    client threads                 driver thread (the only JAX caller)
    --------------                 ------------------------------------
    submit(cases, deadline_s=..)   loop:
      |  admission control           pull queued cases (FIFO across
      |  (bounded queue BYTES          tenants -- arrival order IS the
      |   via plan.meta_bytes;         fusion order)
      |   block / Overloaded)        expired request? -> deadline error,
      v                                NO window slot occupied
    [FIFO queue of (req, case)]      prep (executor.prep_case) + census
      ...                            close the open window when:
    future.result()  <---------        * CostModel.should_close (the
         rows + errors,                  throughput rule), or
         input order                   * CostModel.deadline_at_risk (the
                                         latency rule: modeled window
                                         cost threatens the OLDEST
                                         pending deadline), or
                                       * the queue went idle (no
                                         co-tenant traffic to fuse)
                                     submit window k+1 BEFORE draining
                                       window k (extract_stream's
                                       overlap), demux rows to futures

Contracts:

* **parity** -- served rows are bit-identical to ``extract_stream`` /
  ``run`` on the same cases (windowing never changes a feature row;
  tier-1-locked in ``tests/test_service.py`` on ref + interpret);
* **backpressure** -- admission is bounded by ESTIMATED queue bytes
  (``plan.meta_bytes`` over metadata-only ``CaseMeta``, a conservative
  over-estimate since the real prep crops first): a full queue blocks
  the submitter (or raises :class:`ServiceOverloaded` with
  ``block=False``), so a burst cannot OOM the host staging area;
* **deadlines** -- ``deadline_s`` is relative to submit.  A request
  whose deadline passes while it is still QUEUED completes with a
  :class:`DeadlineExceeded` error row per unprocessed case and never
  occupies a window slot; co-tenant cases in the same windows are
  untouched (tier-1-locked).  A request admitted to a window is always
  delivered (possibly late -- ``ServeResult.late``); the cost model's
  ``deadline_at_risk`` closes windows early to make that rare;
* **quarantine** -- a poisoned / unloadable case degrades to the
  executor's row-level error (all-NaN row + message), reported in
  ``ServeResult.errors`` by the request's own case index; the window's
  co-tenant rows are bit-identical to a run without it.

``BatchedExtractor.serve()`` is the facade entry point;
``python -m repro.launch.serve`` the CLI; ``benchmarks/serve_latency``
the gated mixed-traffic p50/p99 benchmark.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time

import numpy as np

from repro.core import plan as planlib


class ServiceError(RuntimeError):
    """Base class for service-level failures."""


class ServiceClosed(ServiceError):
    """The service is no longer accepting requests."""


class ServiceOverloaded(ServiceError):
    """Admission control rejected the request (queue byte budget full)."""


class DeadlineExceeded(ServiceError):
    """The request's deadline passed before its cases reached a window."""


class WindowFailed(ServiceError):
    """The window holding the case died (past any retry policy).

    Unlike a deadline or an input quarantine this is a failure of the
    service itself: its error rows read ``"WindowFailed: <cause>"`` and
    are counted as ``failed_cases``.
    """


DEFAULT_MAX_QUEUE_MB = 256.0
# byte charge for a lazy loader case whose shape is unknown at admission
# (callers that know their shapes pass ``shape_hints=``); sized like a
# mid-range Table-2 case so loader-heavy traffic still gets backpressure
DEFAULT_LOADER_CASE_BYTES = 8 << 20


def _peek_loader_shape(loader):
    """(shape, spacing) from a loader callable's NIfTI path, if it has one.

    Loaders that want byte-accurate admission control attach the mask
    file they will read (``loader.path`` / ``nifti_path`` / ``mask_path``
    -- a ``functools.partial`` keyword works too); the peek reads only
    the 352-byte header.  Any failure (no path, unreadable, not NIfTI)
    falls back to ``(None, None)`` -- the flat default charge -- because
    admission control must never raise on a weird loader.
    """
    for attr in ("path", "nifti_path", "mask_path"):
        path = getattr(loader, attr, None)
        if path is None:
            kw = getattr(loader, "keywords", None)  # functools.partial
            path = kw.get(attr) if isinstance(kw, dict) else None
        if path is None:
            continue
        try:
            from repro.data.nifti import read_nifti_header

            hdr = read_nifti_header(path)
        except Exception:
            continue
        shape = tuple(int(s) for s in hdr.shape3)
        return shape, np.asarray(hdr.spacing, np.float32)
    return None, None


def estimate_case_bytes(case, needs_intensity: bool = False,
                        shape_hint=None) -> int:
    """Admission-control byte estimate for one queued case.

    Metadata-only (``plan.meta_bytes`` over a :class:`plan.CaseMeta`
    built from the UNCROPPED mask shape), so the queue budget is
    enforceable before any prep work runs.  Over-estimates -- the real
    pass 0 crops to the ROI first -- which is the right direction for
    backpressure.  A loader callable exposing a NIfTI ``path`` (or
    ``nifti_path`` / ``mask_path``) attribute is sized by a 352-byte
    header peek (``data.nifti.read_nifti_header``); only a loader with
    no usable path charges the flat :data:`DEFAULT_LOADER_CASE_BYTES`.
    """
    shape = spacing = None
    if shape_hint is not None:
        shape = tuple(int(s) for s in shape_hint)
    elif callable(case):
        shape, spacing = _peek_loader_shape(case)
    else:
        try:
            _, mask, spacing = case
            shape = tuple(int(s) for s in np.shape(mask))
        except (TypeError, ValueError):
            shape = None
    if shape is None or len(shape) != 3:
        return DEFAULT_LOADER_CASE_BYTES
    hint = planlib.vertex_hint(shape, spacing)
    meta = planlib.CaseMeta(
        shape=planlib.shape_bucket(shape),
        roi_shape=shape,
        vertex_cap=planlib.vertex_bucket(hint),
        n_vertices=hint,
        intensity=needs_intensity,
    )
    return planlib.meta_bytes(meta)


@dataclasses.dataclass
class ServeResult:
    """What one request got back: rows by the request's own case order."""

    rows: list                     # one (n_features,) np row per case
    errors: dict                   # {case index: message} (quarantine,
    #                                deadline, or a window-level failure)
    latency_s: float = 0.0         # submit -> last row resolved
    late: bool = False             # delivered after the deadline passed

    @property
    def ok(self) -> bool:
        return not self.errors


class ServeFuture:
    """Handle a client polls/blocks on for one submitted request."""

    def __init__(self, request: "_Request"):
        self._req = request

    def done(self) -> bool:
        return self._req.event.is_set()

    def result(self, timeout: float | None = None) -> ServeResult:
        """Block until the request resolves; raises ``TimeoutError`` if
        ``timeout`` (seconds) elapses first."""
        if not self._req.event.wait(timeout):
            raise TimeoutError(
                f"request {self._req.rid} not resolved within {timeout}s"
            )
        r = self._req
        return ServeResult(
            rows=list(r.rows), errors=dict(r.errors),
            latency_s=r.done_t - r.submit_t,
            late=(r.deadline is not None and r.done_t > r.deadline),
        )


class _Request:
    """Driver-side state of one submitted request (single or batch)."""

    __slots__ = ("rid", "tenant", "deadline", "submit_t", "done_t",
                 "rows", "errors", "remaining", "case_bytes", "event")

    def __init__(self, rid: int, tenant: str, n_cases: int,
                 deadline: float | None, case_bytes: list):
        self.rid = rid
        self.tenant = tenant
        self.deadline = deadline          # absolute time.monotonic()
        self.submit_t = time.monotonic()
        self.done_t = 0.0
        self.rows: list = [None] * n_cases
        self.errors: dict = {}
        self.remaining = n_cases
        self.case_bytes = case_bytes
        self.event = threading.Event()


class ExtractionService:
    """Persistent multi-tenant extraction service over one executor.

    See the module docstring for the architecture and contracts.  All
    device work runs on the single internal driver thread (JAX dispatch
    is not re-entered from client threads); client threads only estimate
    bytes and enqueue.  Construct via ``BatchedExtractor.serve()`` or
    directly; the driver starts immediately and ``close()`` (or the
    context manager) drains and joins it.

    ``max_queue_bytes`` bounds ESTIMATED bytes of queued-but-unresolved
    cases (admission control); ``idle_tick_s`` is how long the driver
    waits for more co-tenant traffic before shipping a non-empty window
    (the fusion opportunity window) and also the deadline-check cadence.
    """

    def __init__(self, extractor, *,
                 max_queue_bytes: float | None = None,
                 idle_tick_s: float = 0.002,
                 loader_case_bytes: int = DEFAULT_LOADER_CASE_BYTES):
        self.ex = getattr(extractor, "executor", extractor)
        if max_queue_bytes is None:
            max_queue_bytes = DEFAULT_MAX_QUEUE_MB * 2**20
        self.max_queue_bytes = float(max_queue_bytes)
        self.idle_tick_s = float(idle_tick_s)
        self.loader_case_bytes = int(loader_case_bytes)
        self._needs_intensity = planlib.needs_intensity(self.ex.families)

        self._cond = threading.Condition()
        self._queue: collections.deque = collections.deque()
        self._queue_bytes = 0
        self._rid = itertools.count()
        self._closing = False
        self._failure: BaseException | None = None

        # census counters (snapshot via .stats())
        self._windows: list = []       # [(n_cases, n_tenants)] per window
        self._served_cases = 0
        self._expired_cases = 0
        self._quarantined_cases = 0
        self._failed_cases = 0
        self._requests = 0

        self._driver = threading.Thread(
            target=self._drive, name="repro-serve-driver", daemon=True
        )
        self._driver.start()

    # -- client surface ------------------------------------------------------

    def submit(self, cases, *, tenant: str = "default",
               deadline_s: float | None = None, shape_hints=None,
               block: bool = True, timeout: float | None = None) -> ServeFuture:
        """Enqueue a batch of cases; returns a :class:`ServeFuture`.

        Each case is an ``(image, mask, spacing)`` tuple or a zero-arg
        loader callable (the executor's contract).  ``deadline_s`` is
        relative to now; ``shape_hints`` (optional, one mask shape per
        case) tightens the byte estimate for loader cases.  A full queue
        blocks (``block=True``, up to ``timeout`` seconds) or raises
        :class:`ServiceOverloaded` -- the backpressure contract.
        """
        cases = list(cases)
        if not cases:
            raise ValueError("submit() needs at least one case")
        hints = list(shape_hints) if shape_hints is not None else [None] * len(cases)
        if len(hints) != len(cases):
            raise ValueError("shape_hints must match cases 1:1")
        case_bytes = [
            self.loader_case_bytes if (callable(c) and h is None)
            else estimate_case_bytes(c, self._needs_intensity, h)
            for c, h in zip(cases, hints)
        ]
        need = sum(case_bytes)
        deadline = None if deadline_s is None else time.monotonic() + deadline_s
        t_wait0 = time.monotonic()
        with self._cond:
            # an oversize request (need > whole budget) can never fit next
            # to other traffic: it is admitted alone, when the queue drains
            while (self._queue_bytes + need > self.max_queue_bytes
                   and self._queue_bytes > 0):
                self._raise_if_down()
                if not block:
                    raise ServiceOverloaded(
                        f"queue at {self._queue_bytes}B + {need}B would "
                        f"exceed the {int(self.max_queue_bytes)}B budget"
                    )
                remaining = (None if timeout is None
                             else timeout - (time.monotonic() - t_wait0))
                if remaining is not None and remaining <= 0:
                    raise ServiceOverloaded(
                        f"queue still over budget after {timeout}s"
                    )
                self._cond.wait(remaining if remaining is not None
                                else self.idle_tick_s * 50)
            self._raise_if_down()
            req = _Request(next(self._rid), tenant, len(cases), deadline,
                           case_bytes)
            self._requests += 1
            self._queue_bytes += need
            for ci, case in enumerate(cases):
                self._queue.append((req, ci, case))
            self._cond.notify_all()
        return ServeFuture(req)

    def submit_case(self, case, **kw) -> ServeFuture:
        """Single-case convenience wrapper around :meth:`submit`."""
        return self.submit([case], **kw)

    def stats(self) -> dict:
        """Snapshot of the service census (windows, fusion, expiries)."""
        with self._cond:
            return {
                "requests": self._requests,
                "served_cases": self._served_cases,
                "expired_cases": self._expired_cases,
                "quarantined_cases": self._quarantined_cases,
                "failed_cases": self._failed_cases,
                "windows": len(self._windows),
                "window_cases": [n for n, _ in self._windows],
                "window_tenants": [t for _, t in self._windows],
                "queue_bytes": self._queue_bytes,
            }

    def close(self, timeout: float | None = None):
        """Stop accepting requests, drain everything queued, join the driver."""
        with self._cond:
            self._closing = True
            self._cond.notify_all()
        self._driver.join(timeout)
        if self._driver.is_alive():
            raise TimeoutError("service driver did not drain in time")

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()

    # -- driver internals ----------------------------------------------------

    def _raise_if_down(self):
        if self._failure is not None:
            raise ServiceClosed(
                f"service driver failed: {self._failure!r}"
            ) from self._failure
        if self._closing:
            raise ServiceClosed("service is closed")

    def _next_item(self, timeout: float | None):
        """Pop one queued case; None on idle timeout or drained shutdown."""
        with self._cond:
            while not self._queue:
                if self._closing:
                    return None
                if timeout is not None:
                    self._cond.wait(timeout)
                    if not self._queue:
                        return None
                else:
                    self._cond.wait()
            return self._queue.popleft()

    def _nan_row(self):
        return np.full(self.ex.n_features, np.nan, np.float32)

    def _resolve(self, req: _Request, ci: int, row, error: str | None):
        """Deliver one case's outcome back to its request (driver thread)."""
        if row is None:
            row = self._nan_row()
        req.rows[ci] = np.asarray(row)
        if error is not None:
            req.errors[ci] = str(error)
        req.remaining -= 1
        done = req.remaining == 0
        if done:
            req.done_t = time.monotonic()
        with self._cond:
            self._queue_bytes -= req.case_bytes[ci]
            if error is None:
                self._served_cases += 1
            elif error.startswith("DeadlineExceeded"):
                self._expired_cases += 1
            elif error.startswith(WindowFailed.__name__):
                self._failed_cases += 1
            else:
                self._served_cases += 1
                self._quarantined_cases += 1
            self._cond.notify_all()  # bytes freed: unblock submitters
        if done:
            req.event.set()

    def _oldest_slack_us(self, buf, now: float) -> float | None:
        deadlines = [r.deadline for r, _, _ in buf if r.deadline is not None]
        if not deadlines:
            return None
        return (min(deadlines) - now) * 1e6

    def _drive(self):
        ex = self.ex
        cm = ex.cost_model
        buf: list = []                # [(req, ci, prepped)]
        census = planlib.WindowCensus()
        pending = None                # (submitted window state, recs)

        def drain(entry):
            state, recs = entry
            try:
                rows, stats = ex.collect_window(state)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:  # window died past any retry policy:
                # fail ITS requests, not the service
                for req, ci in recs:
                    self._resolve(req, ci, None,
                                  f"{WindowFailed.__name__}: "
                                  f"{type(e).__name__}: {e}")
                return
            errors = stats.get("errors", {})
            for j, (req, ci) in enumerate(recs):
                self._resolve(req, ci, rows[j], errors.get(j))

        def flush():
            nonlocal buf, census, pending
            state = ex.submit_prepped([p for _, _, p in buf])
            recs = [(r, ci) for r, ci, _ in buf]
            with self._cond:
                self._windows.append(
                    (len(buf), len({r.tenant for r, _, _ in buf}))
                )
            prev, pending = pending, (state, recs)
            buf, census = [], planlib.WindowCensus()
            if prev is not None:
                # window k+1 submitted BEFORE window k drains: the
                # extract_stream overlap, now across tenants
                drain(prev)

        try:
            while True:
                busy = bool(buf) or pending is not None
                item = self._next_item(self.idle_tick_s if busy else None)
                now = time.monotonic()
                if buf and cm.deadline_at_risk(
                        census, self._oldest_slack_us(buf, now)):
                    flush()  # the latency rule: ship before the deadline
                if item is None:
                    if buf:
                        flush()  # queue idle: no co-tenant traffic to fuse
                    elif pending is not None:
                        drain(pending)
                        pending = None
                    elif self._closing and not self._queue:
                        return
                    continue
                req, ci, case = item
                if req.deadline is not None and now >= req.deadline:
                    # expired while queued: deadline error, no window slot
                    self._resolve(
                        req, ci, None,
                        f"DeadlineExceeded: expired "
                        f"{(now - req.deadline) * 1e3:.1f}ms before reaching "
                        f"a window",
                    )
                    continue
                p = ex.prep_case(case)
                meta = ex.case_meta(p)
                if buf and cm.should_close(census, meta):
                    flush()  # the throughput rule (same as window='auto')
                buf.append((req, ci, p))
                census.add(meta)
        except BaseException as e:  # driver must never die silently
            with self._cond:
                self._failure = e
                # fail everything in flight and queued
                leftovers = list(self._queue)
                self._queue.clear()
                self._cond.notify_all()
            for req, ci, _ in buf:
                self._resolve(req, ci, None, f"ServiceFailed: {e!r}")
            if pending is not None:
                for req, ci in pending[1]:
                    self._resolve(req, ci, None, f"ServiceFailed: {e!r}")
            for req, ci, _ in leftovers:
                self._resolve(req, ci, None, f"ServiceFailed: {e!r}")
            raise
