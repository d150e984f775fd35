"""Batched, device-parallel radiomics feature pipeline: the public facade.

The paper's motivating workload is extracting features from ~40 000 CT
scans on a cluster (xLUNGS).  Single-case GPU offload (Table 2) is step
one; this layer is the throughput story -- and since PR 4 it is split in
two, with this module as the thin public surface:

* ``core/plan``     -- the PLAN layer: shape buckets, cap groups, the
  pass schedule and the static pass-2b targets, all pure functions of
  per-case metadata (never touches a device array);
* ``core/executor`` -- the EXECUTOR layer: runs a plan with a
  device-resident data plane for both passes, plus the streaming
  front-end.

Data flow of one window (``PlanExecutor.submit_window`` /
``collect_window``)::

      cases ──► pass 0: crop + bucket-pad + STAGE mask on device ──┐
                (dedup vertex fields + count; cap = M bucket)      │
                                                                   ▼
                       ┌──────────────── bucket-keyed device pools ┐
                       │  masks (per shape bucket)    verts/vmask  │
                       └───────┬───────────────────────────┬───────┘
                               │                           │
              pass 2a ◄────────┘            pass 1 ────────┘
          fused MC batch                sharded bound + segmented
        (device stacks, no       compaction per cap group
         host re-stacking)          │ 'counted': (B,2) count fetch
                               │    │   sizes ragged M' buckets
                               │    │ 'static': counts stay ON DEVICE,
                               │    │   compact into cap//2 target
                               │    ▼
                               │  pass 2b: diameter sweep per M' bucket
                               ▼    (device stacks from pass 1)
                            collect: drain rows; static schedule resolves
                            its deferred counts here and re-sweeps the
                            rare keep-originals cases at their input cap

Schedules (``schedule=``):

* ``'counted'`` (default): the PR 3 behaviour -- tightest M' buckets,
  one (B, 2) host sync per cap group between pass 1 and pass 2b;
* ``'static'``: sync-free pass 1 -> 2b dispatch chain.  The plan picks
  each cap group's target as the next power-of-two below the cap, which
  is *exactly* the counted schedule's re-bucketing win boundary
  (``plan.static_bucket``), so the two schedules are bit-identical
  (tier-1-locked) -- static trades padded pair-sweep work (cap//2 vs
  the tight bucket) for zero pass-1 syncs, the right trade for
  streaming and for high-latency links (measured numbers in ROADMAP);
* ``'auto'``: resolved per window by the cost model
  (``runtime/costmodel``) from the calibrated ``sync/<backend>`` d2h
  probe and the window's bucket census -- counted on a zero-latency
  local device, static when the modeled sync cost outweighs the
  padding (either way bit-identical, since the schedules are).

Prep (``prep=``): ``'count'`` (default) fetches each case's dedup vertex
count to size its M cap -- one ``int(n)`` host sync per case;
``'hint'`` sizes caps from ``plan.vertex_hint`` metadata alone (pass 0
becomes sync-free; the true count rides to the collector on device, and
a hint-overflow case re-runs count-sized at collect time).  Bit-identical
to ``'count'``, tier-1-locked.

Front-ends:

* ``run(cases)`` / ``extract_batch(cases)`` -- one window, results +
  stats;
* ``extract_stream(cases, window=...)`` -- dataset-level streaming:
  host prep of window k+1 overlaps device execution of window k, rows
  yielded in input order (the cluster scenario of the paper's
  conclusion; see ``examples/cluster_pipeline.py``);
* ``extract_one`` -- the single-case parity oracle: identical stages,
  no batching; batching may never change a feature value (tier-1).

Feature families (PR 7) -- the multi-family registry
(``plan.FAMILIES``): a feature row is the canonical-order concatenation
of the requested families' parts, selected with ``families=``:

* ``'shape'`` (default) -- the 7 mesh features above (MC volume/area,
  diameters, vertex count);
* ``'firstorder'`` -- 9 intensity statistics (``kernels/firstorder``):
  the case's IMAGE volume rides pass 0 to the device next to its mask,
  and one batched stats launch per shape bucket joins the submit window
  (sync-free: it drains with its own ``'firstorder'`` transfer stage,
  never adding a prep/pass-1 sync);
* ``'glcm'`` -- 4 Haralick texture features (``kernels/glcm``) off the
  same staged intensity pool (one matrix launch per bucket, its own
  ``'glcm'`` drain stage).

Each family ships a reference oracle and a Pallas kernel with a locked
parity contract (first-order: bitwise via the canonical-chunk fold;
GLCM: integer-exact count matrices), and an ``<family>/<backend>``
autotune namespace for its launch block.  Row layout is a pure function
of the requested set (``plan.family_slices`` / ``plan.feature_names``);
batched, streamed, and single-case extraction stay bit-identical per
family.  Quarantined cases degrade to full-width NaN rows.

The shape passes run one data plane: the two-pass pruned pipeline with
device compaction.  The unpruned one-pass computation and host-side
survivor compaction (``ops.prune_candidates``, which ``extract_one``
runs) are test oracles, not options.  Empty-mask cases yield all-zero
rows instead of raising: a 40k-case sweep must not die on one
degenerate segmentation.

Resilience (``runtime/resilience``) -- the layer that makes the 40k-case
cluster run *survivable*, not just fast:

* **manifest format**: ``RunManifest`` is an atomic append-only JSONL
  file, one record per case, keyed by a CONTENT hash of the mask bytes +
  spacing (``{"id", "name", "status": "done"|"error", "features"|
  "error", "window"}``).  ``resume()`` rebuilds the done-set, repairing
  a torn tail (a record cut mid-write by a kill) by truncating back to
  the last complete line; ``record`` is idempotent (an id already done
  is never written twice).
* **quarantine semantics**: every case entering ``submit_window`` /
  ``extract_stream`` may be a tuple or a lazy loader callable; a case
  that fails to load or validate (e.g. a NaN-poisoned mask) degrades to
  a row-level error -- an all-NaN feature row plus an ``errors`` entry
  in the window stats -- and the remaining cases of the window are
  bit-identical to a run without it (tier-1-locked).  Empty masks stay
  all-zero ``done`` rows.  With a ``retry`` policy, a collect-time
  fault re-submits the window from its prepped device state with
  exponential backoff (``resubmit_window``; bit-identical re-run).
* **resume guarantees**: a run preempted mid-stream (SIGTERM via
  ``PreemptionHandler``) and resumed produces a manifest record-set
  bit-identical to an uninterrupted run, with zero lost and zero
  duplicated ids, redoing at most ONE window of work (the in-flight
  window; rows already committed are skipped by the done-set).  Proved
  by ``tests/test_resilience.py`` (tier-1) and soaked at scale by
  ``benchmarks/soak.py``.

Out-of-core tiling (``core/tiled``, PR 9) -- the path for volumes that
do not fit the device (or even the host): a case may be a
``data.tiles.TiledCase`` -- a pair of z-slab SOURCES (windowed NIfTI
reads, in-memory arrays, or analytic generators) instead of materialized
volumes.  The tiled engine runs the census prepass, cuts the padded
frame into halo-exchanged z-tiles of whole marching-cubes granules, and
re-folds per-tile partials in the in-core accumulation order, so the
row is bit-identical to ``extract_one`` on any size both paths can run
(tier-1-locked; ``tile_prune='bounds'`` relaxes only the ref-backend
diameters to f32 rounding, the same contract as vertex pruning).
Hierarchical tile pruning skips empty tiles outright and skips vertex
work for tiles provably excluded from every farthest-pair combo.
Routing: a ``TiledCase`` always takes this path; with ``tiled=True``,
ordinary tuple cases whose staged frame would exceed the tile budget
(``tile_mem_mb`` / ``REPRO_TILE_MEM_MB``) are converted and routed too.
``run`` merges tiled rows back in input order; ``extract_stream`` flushes
the surrounding in-core segments around each tiled case (inter-segment
prep overlap is sacrificed -- tiled cases are assumed rare and huge;
within a tiled case, tile k+1's device work is dispatched before tile
k's partials are drained).  Surviving-tile metadata feeds the same
``plan.WindowCensus`` machinery the cost model reads.

Serving (``serve/service``, PR 8) -- the persistent multi-tenant front
door over the same windows (``serve()`` below returns the service):

* **API**: concurrent clients call ``submit(cases, tenant=...,
  deadline_s=..., block=...)`` (single or batch; tuples or loader
  callables) and get a ``ServeFuture``; ``future.result()`` returns the
  request's rows in ITS OWN input order plus a per-case ``errors`` map.
  One driver thread owns all device work and fuses queued cases across
  tenants into shared windows with the same ``plan.WindowCensus`` +
  ``CostModel.should_close`` the stream uses -- served rows are
  bit-identical to ``extract_stream`` on the same cases (tier-1).
* **deadline semantics**: ``deadline_s`` is relative to submit.  While a
  case is still QUEUED its request may expire: it then completes with a
  ``DeadlineExceeded`` error row and never occupies a window slot, and
  co-tenant cases sharing its windows are untouched.  Once a case is
  admitted to a window it is always delivered (``ServeResult.late``
  marks overruns); ``CostModel.deadline_at_risk`` -- the first
  latency-vs-throughput decision -- closes the open window early when
  its modeled cost (sync + diameter tables, x2 safety) threatens the
  oldest pending deadline, making late delivery rare.
* **backpressure**: admission is bounded by estimated queued bytes
  (``plan.meta_bytes`` over uncropped metadata, a conservative
  over-estimate); a full queue blocks the submitter or raises
  ``ServiceOverloaded`` (``block=False``), so bursts cannot OOM the
  staging host.  Quarantine semantics are the executor's, reported per
  request index.  ``benchmarks/serve_latency.py`` gates mixed-traffic
  p50/p99 + throughput; ``python -m repro.launch.serve`` is the CLI.
"""
from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np
from jax.sharding import Mesh

# re-exported planning primitives (public API since PR 1-3)
from repro.core import plan as planlib
from repro.core.executor import PlanExecutor
from repro.core.plan import (  # noqa: F401  (re-exports)
    Bucket,
    assign_bucket,
    group_indices,
)
from repro.core.tiled import TiledExtractor
from repro.data.tiles import TiledCase
from repro.kernels import marching_cubes as _mc


class BatchedExtractor:
    """Vectorised multi-case extraction, optionally sharded over a mesh.

    The public facade over ``plan.build_plan`` + ``executor.PlanExecutor``
    (see the module docstring for the architecture): the two-pass
    pruned pipeline, with pass 1's survivor compaction on device.
    ``schedule='static'`` removes the pass-1 count sync (bit-identical
    to ``'counted'``, tier-1-locked); ``schedule='auto'`` lets the cost
    model pick per window.
    ``prep='hint'`` removes the last per-case pass-0 sync (hint-sized
    caps, overflow retried at collect; bit-identical to ``'count'``).
    ``variant='auto'`` / ``mc_block='auto'`` (and the compaction block,
    always) resolve the measured-best kernel configurations per (bucket,
    batch-depth) from the autotune cache.  ``mesh`` defaults to the
    ambient ``parallel.sharding.use_mesh`` context.  ``retry`` takes a
    ``runtime/resilience.RetryPolicy`` for backed-off per-window retry;
    failed/poisoned cases quarantine as NaN rows (see the module
    docstring's Resilience section).  ``families`` selects the feature
    families (name, sequence of names, or None for shape-only; see the
    module docstring) and sets the row width ``self.n_features``;
    ``n_bins`` is the intensity discretisation the firstorder/glcm
    families share.
    """

    N_FEATURES = PlanExecutor.N_FEATURES

    def __init__(self, backend=None, variant="auto", mesh: Mesh | None = None,
                 data_axis: str = "data", mc_block="auto",
                 mc_chunk: int | None = None, schedule: str = "counted",
                 prep: str = "count", transfer_callback=None, retry=None,
                 families=None, n_bins: int = 32, tiled: bool = False,
                 tile_prune: str = "bounds",
                 tile_mem_mb: float | None = None):
        self.executor = PlanExecutor(
            backend=backend, variant=variant, mesh=mesh, data_axis=data_axis,
            mc_block=mc_block, mc_chunk=mc_chunk, schedule=schedule,
            prep=prep, transfer_callback=transfer_callback, retry=retry,
            families=families, n_bins=n_bins,
        )
        ex = self.executor
        self.tiled = bool(tiled)
        self.tile_prune = tile_prune
        self._tile_budget = (None if tile_mem_mb is None
                             else int(tile_mem_mb * 2**20))
        self._tiledx = None  # built on first tiled case (family-validated)
        self.families = ex.families
        self.n_features = ex.n_features
        self.n_bins = ex.n_bins
        self.backend = ex.backend
        self.variant = ex.variant
        self.mesh = ex.mesh
        self.data_axis = ex.data_axis
        self.schedule = ex.schedule
        self.prep = ex.prep

    @property
    def cost_model(self):
        """The executor's decision layer (``runtime/costmodel.CostModel``)."""
        return self.executor.cost_model

    @property
    def tiled_extractor(self) -> TiledExtractor:
        """The lazily-built out-of-core engine (``core/tiled``)."""
        if self._tiledx is None:
            self._tiledx = TiledExtractor(
                self.executor, budget_bytes=self._tile_budget,
                tile_prune=self.tile_prune,
            )
        return self._tiledx

    def _route_tiled(self, case) -> bool:
        """Should ``case`` take the out-of-core path?

        A ``TiledCase`` always does (constructing one is the opt-in).
        With ``tiled=True``, a materialized tuple whose staged frame
        (mask + optional intensity, f32) plus, off ``ref``, its
        marching-cubes temporaries would exceed the tile budget is
        converted too; loader callables stay in-core -- their shape is
        unknown until loaded (the serving layer's header peek handles
        byte estimation separately).
        """
        if isinstance(case, TiledCase):
            return True
        if not self.tiled:
            return False
        if not (isinstance(case, (tuple, list)) and len(case) == 3):
            return False
        mask = np.asarray(case[1])
        if mask.ndim != 3:
            return False
        ex = self.executor
        need = 4 * mask.size * (1 + int(ex._needs_intensity))
        if ex._shape_on and ex.backend != "ref":
            need += _mc.work_bytes(mask.shape)
        return need > self.tiled_extractor.budget_bytes

    def _as_tiled(self, case) -> TiledCase:
        if isinstance(case, TiledCase):
            return case
        image, mask, spacing = case
        return TiledCase(mask, image=image, spacing=spacing)

    def extract_tiled(self, case):
        """Run one case through the out-of-core tiled engine.

        Accepts a ``TiledCase`` or an ``(image, mask, spacing)`` tuple;
        returns its ``core.tiled.TiledResult`` (row + census metadata +
        tile stats).
        """
        return self.tiled_extractor.extract(self._as_tiled(case))

    def run(self, cases: Sequence, batch_size: int | None = None):
        """Extract features for (image, mask, spacing) cases (one window).

        Returns a list of ``(self.n_features,)`` arrays in input order
        plus throughput stats ((7,) for the default shape-only request).
        Cases routed out-of-core (see ``_route_tiled``) run through the
        tiled engine and merge back in input order; their surviving-tile
        metadata joins the stats as a ``plan.WindowCensus``.
        """
        cases = list(cases)
        tiled_idx = [i for i, c in enumerate(cases) if self._route_tiled(c)]
        if not tiled_idx:
            return self.executor.run(cases, batch_size)
        incore = [c for i, c in enumerate(cases) if i not in set(tiled_idx)]
        if incore:
            rows, stats = self.executor.run(incore, batch_size)
        else:
            rows, stats = [], {"cases": 0}
        rows = list(rows)
        census = planlib.WindowCensus()
        tile_stats = []
        for i in tiled_idx:
            res = self.tiled_extractor.extract(self._as_tiled(cases[i]))
            rows.insert(i, res.row)
            census.add(res.meta)
            tile_stats.append(res.stats)
        stats = dict(stats)
        stats["tiled"] = {
            "cases": len(tiled_idx),
            "census": census,
            "tiles": sum(s.get("tiles", 0) for s in tile_stats),
            "tiles_skipped": sum(s.get("tiles_skipped", 0)
                                 for s in tile_stats),
            "tiles_bounds_pruned": sum(s.get("tiles_bounds_pruned", 0)
                                       for s in tile_stats),
        }
        return rows, stats

    def extract_batch(self, cases: Sequence, batch_size: int | None = None):
        """Alias of :meth:`run`: one window of the streaming machinery."""
        return self.run(cases, batch_size)

    def extract_stream(self, cases: Iterable, window: int | str = 32,
                       batch_size: int | None = None, stats_callback=None):
        """Stream (image, mask, spacing) cases; yield rows in input order.

        Host prep (load + crop + pad + bucket) of window k+1 overlaps
        device execution of window k; ``stats_callback(i, plan_stats)``
        reports each window's plan census (buckets, pad waste) at submit
        time.  ``run`` is one window of this machinery.
        ``window='auto'`` sizes windows adaptively from the running
        bucket census and the cost model (bit-identical rows to any
        fixed window).

        Out-of-core cases (``TiledCase`` instances, or oversized tuples
        with ``tiled=True``) are handled between in-core segments: the
        preceding segment is flushed through the windowed machinery,
        then the tiled case runs (tile-level submit/collect overlap),
        then streaming resumes.  Rows still arrive in input order;
        prep overlap ACROSS a tiled boundary is sacrificed.
        """
        # validate eagerly: an all-tiled (or empty) stream would otherwise
        # never reach the executor's own check
        if window != "auto" and (not isinstance(window, int) or window < 1):
            raise ValueError(
                f"window must be a positive int or 'auto', got {window!r}"
            )

        def _segments():
            seg = []
            for case in cases:
                if self._route_tiled(case):
                    if seg:
                        yield False, seg
                        seg = []
                    yield True, case
                else:
                    seg.append(case)
            if seg:
                yield False, seg

        def _gen():
            for is_tiled, item in _segments():
                if is_tiled:
                    yield self.tiled_extractor.extract(
                        self._as_tiled(item)).row
                else:
                    yield from self.executor.extract_stream(
                        item, window=window, batch_size=batch_size,
                        stats_callback=stats_callback,
                    )

        return _gen()

    def extract_one(self, image, mask, spacing):
        """Single-case parity oracle (identical stages, no batching)."""
        return self.executor.extract_one(image, mask, spacing)

    def serve(self, *, max_queue_bytes: float | None = None,
              idle_tick_s: float = 0.002):
        """Start the persistent multi-tenant service over this extractor.

        Returns a running ``serve.service.ExtractionService`` (also a
        context manager): concurrent clients ``submit()`` cases and the
        driver fuses them across tenants into shared windows, honouring
        per-request deadlines and the queue-byte backpressure budget.
        See the module docstring's Serving section for the semantics.
        """
        from repro.serve.service import ExtractionService

        return ExtractionService(
            self, max_queue_bytes=max_queue_bytes, idle_tick_s=idle_tick_s,
        )
