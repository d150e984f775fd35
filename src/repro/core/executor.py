"""Executor layer: runs :class:`~repro.core.plan.ExtractionPlan`s with a
device-resident data plane.

The planning/execution split (see ``core/plan``) gives this module a
simple contract: ``submit_window`` turns one window of cases into device
launches without data-dependent control flow, ``collect_window`` drains
the results.  Everything between -- device pools, the sync-free static
pass-1 chain, the double-buffered feeds, the streaming overlap -- lives
here, behind the thin :class:`~repro.core.pipeline.BatchedExtractor`
facade.

One data plane runs the shape passes: the two-pass pruned pipeline with
device compaction, both passes device-resident.  Its only internal
choice is the pass-1 schedule (``'counted'`` or ``'static'``).  The
unpruned one-pass computation and host-side survivor compaction are not
executor paths: they live on as test oracles (``ops.prune_candidates``
and the per-case sweep of :meth:`PlanExecutor.extract_one`).

Data plane (both passes device-resident):

* **pass 0 (staging):** each case's cropped, bucket-padded mask goes to
  the device once during host prep (async ``device_put``-style transfer
  overlapping the next case's crop/pad); per shape bucket the staged
  masks are stacked into a bucket-keyed **device pool** that both pass 1
  (vertex fields) and pass 2a (MC) consume -- the per-chunk host
  ``np.stack`` of PR 2/3 is gone;
* **pass 1:** one (shard-able) bound + segmented-compaction chain per
  cap group.  Under ``schedule='counted'`` the survivor counts are
  fetched to size the ragged M' buckets (one small (B, 2) sync per cap
  group -- the PR 3 behaviour and the parity baseline).  Under
  ``schedule='static'`` the chain compacts straight into the plan's
  static target and the counts ride along **as a device array**: pass 1
  -> pass 2b is a single dispatch chain with ZERO host fetches (counted
  by ``transfer_log`` and locked by a tier-1 test);
* **pass 2a/2b:** grouped sub-batches sliced off the pools / pass-1
  output stacks; every launch of a window is submitted before any result
  is drained, so transfers and compute of chunk k+1 overlap chunk k.

Static-schedule collect: the deferred (B, 2) count fetch happens at
drain time, AFTER the diameter sweeps were dispatched.  Cases whose
counted-schedule decision would have been "keep the originals" (the
static target is exactly the counted win boundary -- ``core/plan``) are
then re-swept once at their original cap from the retained device
stacks; every other case's static result is already exact, because the
aligned target guarantees no survivor was dropped.

Streaming: ``extract_stream`` pipelines windows -- window k+1 is
prepped/submitted while the device still executes window k (jax dispatch
is async), then window k is drained and its rows yielded in input order.
Under ``schedule='static'`` the submit path never blocks on the device,
so the overlap is complete; under ``'counted'`` the pass-1 count fetch
re-serialises part of it (the measured trade-off is recorded in
ROADMAP.md).

Cost-model-driven knobs (PR 5, ``runtime/costmodel``): ``prep='hint'``
sizes pass-0 caps from ``plan.vertex_hint`` metadata alone -- the last
per-case host sync (``int(n)``) disappears; the true count rides to the
collector as a device future, and the rare hint-overflow case re-runs
count-sized at collect time (the same retry contract as the static
keep-originals re-sweep).  ``schedule='auto'`` resolves counted-vs-
static per window from the calibrated ``sync/<backend>`` probe and the
window's census; ``extract_stream(window='auto')`` closes windows at
census-decided boundaries.  ``prep='count'`` and fixed windows remain
the parity baselines, and every auto knob is bit-identical to them
(tier-1-locked).

Resilience (PR 6, ``runtime/resilience``): cases may be lazy loader
callables; any load/validation failure (incl. NaN-poisoned masks)
quarantines the case as an all-NaN row plus a window-stats error record
instead of killing the window (``_prep_case_safe``), and a ``retry``
policy turns a collect-time fault into a backed-off ``resubmit_window``
+ re-drain -- both pure host-side mechanisms that leave the sync-free
submit path's zero-fetch invariants untouched.

Feature families (PR 7, ``core/plan.FAMILIES``): the executor extracts
any requested subset of the registered families.  The intensity families
(first-order, GLCM) ride the same windows as the shape passes: pass 0
stages each case's cropped, bucket-padded intensity volume ONCE
alongside its mask, the per-shape-bucket intensity pools are built once
and SHARED by every intensity family, and one batched family launch per
(family, shape bucket) is submitted inside the same submit phase -- no
new host fetch happens before collect, so the sync-free invariants
(zero pass-0/pass-1 fetches under hint prep + static schedule) hold
unchanged with families enabled (tier-1-locked).  Feature rows are the
family-order concatenation ``plan.row_width(families)`` wide; quarantine
NaN rows and empty-mask zero rows derive their width from the same
registry, never from a hardcoded constant.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import math
import time
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import dispatcher
from repro.core import plan as planlib
from repro.core.shape_features import crop_padded, roi_box
from repro.kernels import ops
from repro.kernels import prune as prune_kernels
from repro.parallel import sharding as psharding
from repro.runtime import autotune


@dataclasses.dataclass
class _Prepped:
    """Pass-0 state for one case (None mask = empty-mask case).

    ``mask`` is the bucket-padded mask, staged on device (the pool
    entry).  ``verts``/``vmask`` are the pass-0 compacted vertex stacks,
    device arrays that pass 1 stacks without a host round trip; only the
    single-case oracle (``extract_one``) and the hint-overflow retry put
    the host-pruned arrays of ``ops.prune_candidates`` in their place.
    """

    mask: object | None = None  # device-staged bucket-padded mask
    image: object | None = None  # device-staged bucket-padded intensity
    # volume (same crop/pad as the mask); None unless a family needs it
    spacing: np.ndarray | None = None
    shape: tuple | None = None  # padded shape bucket (MC group key)
    roi_shape: tuple | None = None  # pre-pad cropped shape (pad stats)
    verts: object | None = None
    vmask: object | None = None
    n_vertices: int = 0  # pre-prune dedup vertex count (a feature)
    vertex_cap: int = 0  # static M' bucket the diameter kernel compiles for
    prune_info: object | None = None
    n_fut: object | None = None  # hint prep: true dedup count, ON DEVICE
    prep_cap: int = 0  # hint prep: the pass-0 compaction cap (overflow ref;
    # vertex_cap is overwritten by pass 1 with the pass-2b bucket)
    error: str | None = None  # quarantined case: the row degrades to NaNs


@dataclasses.dataclass
class _Window:
    """One submitted window: every launch issued, nothing drained yet."""

    prepped: list
    plan: planlib.ExtractionPlan
    mc_futs: list
    diam_futs: list
    static_aux: list  # [(cap, idxs, counts_fut, verts, masks)] to resolve
    seq: int  # the executor's window number: the ``window`` stat of its spans
    family_futs: dict = dataclasses.field(default_factory=dict)
    # {family: [(idxs, future)]} -- the intensity-family launches


@jax.jit
def _fields_count(mask, spacing):
    """Pass-0 compute: dedup vertex fields + active count, one compile per
    shape bucket (the eager per-op path costs ~10x on a cold sweep)."""
    fields = ops.vertex_fields(mask, 0.5, spacing)
    return fields, ops.count_vertices(fields)


@functools.partial(jax.jit, static_argnames=("cap",))
def _compact_cap(fields, cap: int):
    verts, vmask, _ = ops.compact_vertices(fields, cap)
    return verts, vmask


class PlanExecutor:
    """Plan-driven batched extraction engine (see module docstring).

    Owns the compiled-function cache, the device pools, the submit/
    collect drivers, and the ``transfer_log`` host-sync accounting.
    ``BatchedExtractor`` is the public facade.
    """

    N_FEATURES = 7  # the shape-family (default request) row width:
    # [vol, area, d3, dxy, dxz, dyz, n_vertices].  Per-instance widths
    # come from the family registry: see ``self.n_features``.

    SCHEDULES = (*planlib.SCHEDULES, "auto")
    PREPS = ("count", "hint")

    def __init__(self, backend=None, variant="auto", mesh: Mesh | None = None,
                 data_axis: str = "data", mc_block="auto",
                 mc_chunk: int | None = None, schedule: str = "counted",
                 prep: str = "count", cost_model=None,
                 transfer_callback=None, retry=None,
                 families=None, n_bins: int = 32):
        self.backend = dispatcher.resolve_backend(backend)
        self.variant = variant
        self.families = planlib.resolve_families(families)
        self.n_features = planlib.row_width(self.families)
        self.n_bins = int(n_bins)
        self._shape_on = "shape" in self.families
        self._needs_intensity = planlib.needs_intensity(self.families)
        if mesh is None:
            # adopt the ambient use_mesh mesh only when it can actually
            # shard the batch: train/serve meshes without a data axis must
            # not turn a working CPU pipeline into a KeyError
            ambient = psharding.active_mesh()
            if ambient is not None and data_axis in ambient.shape:
                mesh = ambient
        self.mesh = mesh
        self.data_axis = data_axis
        self.mc_block = mc_block
        self.mc_chunk = mc_chunk
        if schedule not in self.SCHEDULES:
            raise ValueError(
                f"schedule must be one of {self.SCHEDULES}, got {schedule!r}"
            )
        self.schedule = schedule
        if prep not in self.PREPS:
            raise ValueError(f"prep must be one of {self.PREPS}, got {prep!r}")
        self.prep = prep
        self._cost_model = cost_model
        self.transfer_log = collections.Counter()
        self._transfer_cb = transfer_callback
        self.retry = retry  # runtime/resilience.RetryPolicy (duck-typed)
        self.window_retries = 0  # collect retries performed (resilience census)
        self._next_window = 0  # number the next submitted window gets
        self._compiled = {}

    @property
    def cost_model(self):
        """Lazily-built decision layer (``runtime/costmodel.CostModel``).

        Only the auto knobs (``schedule='auto'``, ``window='auto'``) read
        it, so plain fixed-knob runs never touch the autotune cache file
        through this path.
        """
        if self._cost_model is None:
            from repro.runtime import costmodel  # local: keep import light

            self._cost_model = costmodel.CostModel(self.backend)
        return self._cost_model

    # -- host-sync accounting ----------------------------------------------

    def _fetch(self, stage: str, x) -> np.ndarray:
        """The ONLY device->host fetch point of the executor.

        Every host materialisation of a device value routes through here
        so ``transfer_log`` is a complete per-stage sync census -- the
        counter the zero-pass-1-fetch contract of ``schedule='static'``
        is asserted against (tier-1).
        """
        self.transfer_log[stage] += 1
        if self._transfer_cb is not None:
            self._transfer_cb(stage, x)
        with jax.profiler.TraceAnnotation("repro.fetch", stage=stage,
                                          bytes=x.nbytes):
            return np.asarray(x)

    # -- tuned-config resolution (outside any trace) ------------------------

    def _resolve_mc(self, shape, depth: int = 1):
        if self.backend == "ref":
            # no brick block on ref; mc_chunk doubles as the scan slab
            # depth (a memory lever the tiled engine shares)
            return None, self.mc_chunk
        return dispatcher.mc_config(
            self.backend, shape, self.mc_block, self.mc_chunk, batch=depth
        )

    def _resolve_diameter(self, cap, depth: int = 1):
        if self.backend == "ref":
            return self.variant, None
        return dispatcher.diameter_config(
            self.backend, cap, self.variant, batch=depth
        )

    def _resolve_compact(self, cap_in, depth: int = 1):
        if self.backend == "ref":
            return None
        return dispatcher.compact_config(self.backend, cap_in, batch=depth)

    def _resolve_family_block(self, family: str, shape, depth: int = 1):
        """Tuned block for an intensity-family launch (None on 'ref')."""
        if self.backend == "ref":
            return None
        resolver = (dispatcher.firstorder_config if family == "firstorder"
                    else dispatcher.glcm_config)
        return resolver(self.backend, shape, "auto", batch=depth)

    # -- compiled-function cache -------------------------------------------

    def _dp_map(self, fn, name: str, check: bool = True):
        """Shard a batched fn over the data axis (plain jit without a mesh).

        ``name`` names the program: ``jit_<name>`` in a profiler trace and
        ``jit(<name>)`` in the compile events, so device time and compiles
        are told apart by pass.  ``check=False`` for batch fns that contain
        a ``pallas_call``: jax's shard_map replication checker has no rule
        for it (the documented workaround -- results are still
        bit-identical, locked by tests/test_pipeline_multidevice.py).
        """
        fn.__name__ = fn.__qualname__ = name
        return psharding.data_parallel_map(
            fn, self.mesh, self.data_axis, check=check
        )

    def _pad_batch(self, arrays, n: int):
        return psharding.pad_batch(arrays, n, self.mesh, self.data_axis)

    def _bound_fn(self, cap: int, depth: int):
        """Pass 1 (counted): sharded vmapped pruning bound + survivor counts.

        Maps stacked ``(B, cap, 3)`` verts + ``(B, cap)`` masks to
        ``(keep, counts)``; with a mesh the batch shards over the data
        axis (``data_parallel_map`` is a plain jit without one).
        """
        key = ("prune_bound", cap, depth)
        if key in self._compiled:
            return self._compiled[key]

        def batch(verts, masks):
            keep, _ = prune_kernels.keep_mask_batch(verts, masks)
            m_valid = jnp.sum(masks.astype(jnp.int32), axis=1)
            m_kept = jnp.sum(keep.astype(jnp.int32), axis=1)
            # counts ride out pre-stacked (B, 2) so the host fetch is one
            # transfer with no eager stitching (batch dim first: shardable)
            return keep, jnp.stack([m_valid, m_kept], axis=1)

        fn = self._dp_map(batch, "pass1_bound")
        self._compiled[key] = fn
        return fn

    def _compact_fn(self, cap_in: int, cap_out: int, depth: int):
        """Pass 1 (counted): sharded batched compaction into the M' bucket."""
        key = ("compact", cap_in, cap_out, depth)
        if key in self._compiled:
            return self._compiled[key]
        backend = self.backend
        block = self._resolve_compact(cap_in, depth)

        def batch(verts, keep):
            v, m, _ = ops.compact_survivors_batch(
                verts, keep, cap_out, backend=backend, block=block
            )
            return v, m

        fn = self._dp_map(batch, "pass1_compact", check=False)
        self._compiled[key] = fn
        return fn

    def _static_fn(self, cap: int, target: int, depth: int):
        """Pass 1 (static): ONE fused bound -> compaction dispatch chain.

        Emits ``(compacted verts, compacted mask, (B, 2) counts)`` with
        the counts staying ON DEVICE -- the chain has no data-dependent
        decision, which is what makes static pass 1 sync-free.  The
        compaction target is the plan's aligned static bucket, so no
        survivor of a counted-schedule "compact" case can overflow it
        (``core/plan.static_bucket``).
        """
        key = ("static_chain", cap, target, depth)
        if key in self._compiled:
            return self._compiled[key]
        backend = self.backend
        block = self._resolve_compact(cap, depth)

        def batch(verts, masks):
            keep, _ = prune_kernels.keep_mask_batch(verts, masks)
            m_valid = jnp.sum(masks.astype(jnp.int32), axis=1)
            m_kept = jnp.sum(keep.astype(jnp.int32), axis=1)
            v, m, _ = ops.compact_survivors_batch(
                verts, keep, target, backend=backend, block=block
            )
            return v, m, jnp.stack([m_valid, m_kept], axis=1)

        fn = self._dp_map(batch, "pass1_static", check=False)
        self._compiled[key] = fn
        return fn

    def _mc_fn(self, shape, depth: int):
        """Pass 2a: staged batched fused MC for one shape bucket.

        Consumes device-pool stacks directly (``ops.mc_volume_area_batch``)
        and shards over the data axis exactly like pass 1.
        """
        key = ("mc", shape, depth)
        if key in self._compiled:
            return self._compiled[key]
        backend = self.backend
        mc_block, mc_chunk = self._resolve_mc(shape, depth)

        def batch(masks, spacings):
            return ops.mc_volume_area_batch(
                masks, 0.5, spacings, backend=backend,
                block=mc_block, chunk=mc_chunk,
            )

        fn = self._dp_map(batch, "pass2a_mc", check=False)
        self._compiled[key] = fn
        return fn

    def _family_fn(self, family: str):
        """Compile-key resolver for one intensity family's batched launch.

        Returns the ``fn_for_key`` shape :meth:`_submit` expects: per
        (padded-volume bucket, depth) one sharded jitted function mapping
        the pooled (images, masks) stacks to per-case DEVICE payloads --
        packed stats rows (firstorder) or count matrices (glcm).  Feature
        rows finalise host-side at drain time (:meth:`_family_row`); only
        the payloads need cross-backend parity.  The tuned block resolves
        OUTSIDE the trace, exactly like the shape passes' configs.
        """
        def fn_for_key(shape, depth):
            key = (family, shape, depth)
            if key in self._compiled:
                return self._compiled[key]
            backend, n_bins = self.backend, self.n_bins
            block = self._resolve_family_block(family, shape, depth)
            op = (ops.firstorder_packed_batch if family == "firstorder"
                  else ops.glcm_matrix_batch)

            def batch(images, masks):
                return op(images, masks, backend=backend, n_bins=n_bins,
                          block=block)

            fn = self._dp_map(batch, f"family_{family}", check=False)
            self._compiled[key] = fn
            return fn

        return fn_for_key

    def _diam_fn(self, cap, depth: int):
        """Pass 2b: batched diameter sweep for one (pruned) vertex bucket."""
        key = ("diam", cap, depth)
        if key in self._compiled:
            return self._compiled[key]
        backend = self.backend
        variant, block = self._resolve_diameter(cap, depth)

        def one(args):
            verts, vmask = args
            return ops.max_diameters(
                verts, vmask, backend=backend, variant=variant, block=block
            )

        def batch(verts, vmasks):
            return jax.lax.map(one, (verts, vmasks))

        fn = self._dp_map(batch, "pass2b_diameter", check=False)
        self._compiled[key] = fn
        return fn

    # -- submit/drain drivers ----------------------------------------------

    def _submit(self, stage, entries, fn_for_key, make_chunk,
                batch_size=None):
        """Submit every chunk of every entry; returns ``[(idxs, future)]``.

        ``entries`` yields ``(compile key, case indices, payload)``;
        ``make_chunk(payload, start, chunk, bs)`` materialises the stacked
        input arrays for one chunk, padded up to ``bs`` rows (a multiple
        of the mesh's data-axis size, so shard_map shapes stay uniform).
        jax dispatch is async, so every launch of the window is queued
        before any result is fetched -- the transfer/compute of chunk k+1
        overlaps chunk k, and draining is the collector's job.  The
        dispatches run inside a ``repro.launch.<stage>`` span.
        """
        n_data = psharding.axis_size(self.mesh, self.data_axis)
        futs = []
        with jax.profiler.TraceAnnotation(f"repro.launch.{stage}") as span:
            for gkey, idxs, payload in entries:
                bs = batch_size or max(n_data, len(idxs))
                bs = int(math.ceil(bs / n_data)) * n_data
                fn = fn_for_key(gkey, autotune.batch_bucket(bs))
                for s in range(0, len(idxs), bs):
                    chunk = idxs[s : s + bs]
                    futs.append(
                        (chunk, fn(*make_chunk(payload, s, chunk, bs))))
            span.set_metadata(launches=len(futs))
        return futs

    def _drain(self, futs, stage: str) -> dict:
        """Fetch submitted futures into ``{case index: np row}``."""
        out: dict[int, np.ndarray] = {}
        for idxs, fut in futs:
            o = self._fetch(stage, fut)
            for j, i in enumerate(idxs):
                out[i] = o[j]
        return out

    @staticmethod
    def _stacked_chunk(arrays, s, chunk, bs):
        """Chunk maker over PRE-STACKED device groups (pools / pass-1 out).

        Slices straight off the device stacks -- no host re-stacking;
        short trailing chunks pad with copies of their first row (mesh
        padding rows in the stacks themselves are simply never read).
        """
        sl = tuple(a[s : s + len(chunk)] for a in arrays)
        if len(chunk) < bs:
            sl = tuple(
                jnp.concatenate([a, jnp.repeat(a[:1], bs - len(chunk), axis=0)])
                for a in sl
            )
        return sl

    def _pool(self, prepped, idxs):
        """Bucket-keyed device pool for one shape group: (masks, spacings).

        ``jnp.stack`` of the staged per-case device masks runs on device;
        the (B, 3) spacing sidecar is tiny host metadata.
        """
        return (
            jnp.stack([prepped[i].mask for i in idxs]),
            jnp.asarray(np.stack([prepped[i].spacing for i in idxs])),
        )

    def _ipool(self, prepped, idxs):
        """Intensity device pool for one shape group: (images, masks).

        Built once per shape group at submit and shared by EVERY
        intensity family of the window -- the staged per-case volumes are
        stacked on device, never re-transferred per family.
        """
        return (
            jnp.stack([prepped[i].image for i in idxs]),
            jnp.stack([prepped[i].mask for i in idxs]),
        )

    def _submit_families(self, plan, prepped, batch_size=None) -> dict:
        """Submit the intensity-family launches for one planned window.

        One launch chain per (family, shape bucket), every launch queued
        before anything is drained -- the families ride the same
        submit/collect window as the shape passes and add NO host fetch
        before collect (the sync-free invariants hold unchanged;
        tier-1-locked).
        """
        families = [f for f in plan.families if f != "shape"]
        if not families:
            return {}
        pools = {
            shape: self._ipool(prepped, idxs)
            for shape, idxs in plan.shape_groups.items()
        }
        futs = {}
        for family in families:
            entries = [
                (shape, idxs, pools[shape])
                for shape, idxs in plan.shape_groups.items()
            ]
            futs[family] = self._submit(
                family, entries, self._family_fn(family), self._stacked_chunk,
                batch_size,
            )
        return futs

    # -- pass 0: prep + device staging --------------------------------------

    def _prep_case(self, image, mask, spacing,
                   prep: str | None = None) -> _Prepped:
        """Crop, bucket-pad, device-stage, and compact one case (pass 0):
        :meth:`_crop_case` on the host, then :meth:`_stage_case`."""
        return self._stage_case(self._crop_case(image, mask, spacing),
                                prep=prep)

    def _crop_case(self, image, mask, spacing) -> _Prepped:
        """Host half of pass 0: crop to the ROI and pad to its shape bucket.

        The result's ``mask``/``image`` are still host arrays (``None``
        mask: an empty-mask case, which stays an all-zero feature row).
        """
        sp = np.asarray(spacing, np.float32)
        mask = np.asarray(mask)
        box = roi_box(mask)
        if box is None:
            return _Prepped(spacing=sp)  # empty mask: all-zero feature row
        if self._needs_intensity:
            img = None if image is None else np.asarray(image)
            if img is None or img.shape != mask.shape:
                raise ValueError(
                    "intensity families requested but the case has no "
                    "matching intensity image"
                )
            if (np.issubdtype(img.dtype, np.floating)
                    and not np.isfinite(img).all()):
                raise ValueError("non-finite intensity image (poisoned case)")
        # the crop, the 1-voxel pad and the bucket pad in one copy per
        # array (shape-only requests never read the image)
        lo, hi = box
        extent = tuple(h - l for l, h in zip(lo, hi))
        bshape = planlib.shape_bucket(extent)
        return _Prepped(
            mask=crop_padded(mask, lo, hi, bshape), spacing=sp, shape=bshape,
            roi_shape=tuple(e + 2 for e in extent),
            image=(crop_padded(img, lo, hi, bshape)
                   if self._needs_intensity else None),
        )

    def _stage_case(self, p: _Prepped, prep: str | None = None) -> _Prepped:
        """Device half of pass 0: stage the padded volumes, then compact
        the case's vertex fields into its M cap on device.

        ``prep`` (default: the executor's configured prep) sizes the M
        cap: ``'count'`` fetches the measured dedup count (one ``int(n)``
        host sync per case -- the parity baseline), ``'hint'`` sizes it
        from ``plan.vertex_hint`` metadata alone and leaves the true
        count ON DEVICE (``n_fut``) for the collector -- pass 0 becomes
        sync-free, at the cost of occasional over-allocation plus the
        rare hint-overflow retry (``_resolve_hint_counts``).
        """
        if p.mask is None:
            return p
        prep = prep or self.prep
        staged = p.mask.nbytes + (0 if p.image is None else p.image.nbytes)
        with jax.profiler.TraceAnnotation("repro.prep.stage", bytes=staged):
            # staged once: the pool entries, the image shared by every
            # intensity family
            p.mask = jnp.asarray(p.mask)
            if p.image is not None:
                p.image = jnp.asarray(p.image)
        if not self._shape_on:
            # intensity-only request: no vertex stage runs at all -- the
            # shape bucket still keys the family launches
            return p
        with jax.profiler.TraceAnnotation("repro.prep.fields") as span:
            f, n = _fields_count(p.mask, jnp.asarray(p.spacing))
            if prep == "hint":
                # sync-free prep: the cap comes from metadata alone; the
                # true count stays a device future the collector drains.  A
                # larger-than-needed cap is harmless (pruning and the pair
                # sweep are padding-invariant, tier-1-locked); a SMALLER one
                # drops vertices, which the collector detects and retries
                # count-sized.
                p.n_vertices = planlib.vertex_hint(
                    tuple(s - 2 for s in p.roi_shape), p.spacing)
                p.n_fut = n
                cap = p.prep_cap = ops.vertex_bucket(p.n_vertices)
            else:
                p.n_vertices = n = int(self._fetch("prep", n))
                cap = ops.vertex_bucket(n)
            p.verts, p.vmask = _compact_cap(f, cap)
            p.vertex_cap = cap
            span.set_metadata(cap=cap)
        return p

    def _prep_case_safe(self, case, prep: str | None = None,
                        window: int = -1, pos: int = -1) -> _Prepped:
        """Quarantining wrapper around :meth:`_prep_case` (pass 0).

        ``case`` is an ``(image, mask, spacing)`` tuple or a zero-arg
        callable returning one (a lazy loader, so load failures are
        attributable to the case that raised them).  Any exception --
        loader I/O errors, non-finite (poisoned) masks or spacings, crop
        failures -- degrades to a QUARANTINED prepped case: its feature
        row is all-NaN, its error message rides the window stats, and the
        rest of the window is untouched.  A 40k-case sweep must not die
        on one poisoned segmentation (the row-level-error contract,
        tier-1-locked).  Validation and quarantine are pure host work:
        the sync-free submit path's zero-fetch invariants are untouched.

        The case runs inside a ``repro.prep`` span whose ``window`` and
        ``case`` stats are the window it is prepped for and its position
        there (-1: prepped before its window is formed).
        """
        with jax.profiler.TraceAnnotation("repro.prep", window=window,
                                          case=pos):
            try:
                with jax.profiler.TraceAnnotation("repro.prep.crop") as span:
                    if callable(case):
                        case = case()
                    image, mask, spacing = case
                    m = np.asarray(mask)
                    if (np.issubdtype(m.dtype, np.floating)
                            and not np.isfinite(m).all()):
                        raise ValueError("non-finite mask (poisoned case)")
                    sp = np.asarray(spacing, np.float64)
                    if (sp.shape != (3,) or not np.isfinite(sp).all()
                            or (sp <= 0).any()):
                        raise ValueError(f"invalid spacing {spacing!r}")
                    p = self._crop_case(image, mask, spacing)
                    span.set_metadata(
                        voxels=0 if p.mask is None else p.mask.size)
                return self._stage_case(p, prep=prep)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                return _Prepped(error=f"{type(e).__name__}: {e}")

    def _meta(self, p: _Prepped) -> planlib.CaseMeta:
        if p.mask is None:
            return planlib.CaseMeta(None, None, 0, 0)
        return planlib.CaseMeta(p.shape, p.roi_shape, p.vertex_cap,
                                p.n_vertices, intensity=p.image is not None)

    # -- public prep surface (the submit/collect reuse contract) -------------
    #
    # External drivers that window cases themselves -- the resilient
    # runner (runtime/resilience) and the serving tier (serve/service) --
    # prep each case through here, census its metadata, and hand the
    # prepped batch to submit_prepped/collect_window.  Everything they
    # need is these two names plus the window API; the underscore
    # internals stay private.

    def prep_case(self, case) -> _Prepped:
        """Pass-0 prep of one case, quarantining any load/validation
        failure (see :meth:`_prep_case_safe`); ``case`` is an
        ``(image, mask, spacing)`` tuple or a zero-arg loader callable."""
        return self._prep_case_safe(case)

    def case_meta(self, p: _Prepped) -> planlib.CaseMeta:
        """Planning metadata of a prepped case (feeds ``WindowCensus``)."""
        return self._meta(p)

    # -- pass 1 --------------------------------------------------------------

    def _pass1_counted(self, plan, prepped):
        """Pass 1 (counted device path): sharded bound + device compaction.

        Per cap group, ONE (sharded) vmapped bound launch computes every
        keep mask, one small (B, 2) count fetch sizes the ragged M'
        buckets, and one (sharded) compaction launch per target bucket
        scatters the survivors -- the vertex data itself never leaves the
        device.  Decisions (pruned or keep-originals) come from
        ``prune.plan_compaction``, the same rule the host-compaction
        oracle (``ops.prune_candidates``) composes, so the two stay
        bit-identical.  Returns the pass-2b feed:
        ``[(M' bucket, case indices, (verts, vmask) stacks)]``.
        """
        with jax.profiler.TraceAnnotation("repro.launch.pass1") as span:
            launches = 0
            entries = []
            for cap, idxs in plan.cap_groups.items():
                b = len(idxs)
                depth = autotune.batch_bucket(b)
                verts, masks = self._pad_batch(
                    (
                        jnp.stack([prepped[i].verts for i in idxs]),
                        jnp.stack([prepped[i].vmask for i in idxs]),
                    ),
                    b,
                )
                keep, counts = self._bound_fn(cap, depth)(verts, masks)
                launches += 1
                # the one host sync of counted pass 1: a small (B, 2) matrix
                counts = self._fetch("pass1", counts)
                plans = [
                    prune_kernels.plan_compaction(
                        cap, int(counts[j, 0]), int(counts[j, 1]),
                        ops.vertex_bucket,
                    )
                    for j in range(b)
                ]
                for j, i in enumerate(idxs):
                    prepped[i].prune_info = plans[j][1]
                    prepped[i].vertex_cap = plans[j][0] or cap
                # keep-originals cases feed pass 2 at their input cap
                groups = planlib.group_indices(
                    [cap_out if cap_out else ("orig", cap)
                     for cap_out, _ in plans]
                )
                for gkey, js in groups.items():
                    # whole cap group agreeing on one target reuses the stacks
                    take = (
                        None if len(js) == b
                        else jnp.asarray(np.asarray(js, np.int32))
                    )

                    def sub(*arrays):
                        if take is None:
                            return arrays
                        return self._pad_batch(
                            tuple(jnp.take(a, take, axis=0) for a in arrays),
                            len(js),
                        )

                    gidxs = [idxs[j] for j in js]
                    if isinstance(gkey, tuple):
                        # unpruned: originals, input cap
                        entries.append((cap, gidxs, sub(verts, masks)))
                        continue
                    # the launch carries the SUBGROUP's depth, not the
                    # cap group's
                    cv, cm = self._compact_fn(
                        cap, gkey, autotune.batch_bucket(len(js))
                    )(*sub(verts, keep))
                    launches += 1
                    entries.append((gkey, gidxs, (cv, cm)))
            span.set_metadata(launches=launches)
        return entries, []

    def _pass1_static(self, plan, prepped):
        """Pass 1 (static schedule): the sync-free dispatch chain.

        Per cap group ONE fused bound+compaction chain targets the plan's
        static bucket; the per-case counts stay on device and ride into
        the collector as ``static_aux`` -- no host fetch happens anywhere
        in this method (``transfer_log['pass1']`` stays 0, tier-1-locked).
        Floor-cap groups (no shrink possible -- exactly the groups the
        counted schedule always keeps at their original cap) skip the
        chain entirely and feed pass 2b their original stacks.
        """
        with jax.profiler.TraceAnnotation("repro.launch.pass1") as span:
            entries, aux = [], []
            for cap, idxs in plan.cap_groups.items():
                b = len(idxs)
                target = plan.static_targets[cap]
                verts, masks = self._pad_batch(
                    (
                        jnp.stack([prepped[i].verts for i in idxs]),
                        jnp.stack([prepped[i].vmask for i in idxs]),
                    ),
                    b,
                )
                if target is None:
                    # counted parity without the bound: a floor-cap group can
                    # never re-bucket, so its PruneInfo is metadata-only
                    for i in idxs:
                        n = prepped[i].n_vertices
                        prepped[i].prune_info = prune_kernels.PruneInfo(
                            cap, n, n, False
                        )
                        prepped[i].vertex_cap = cap
                    entries.append((cap, idxs, (verts, masks)))
                    continue
                depth = autotune.batch_bucket(b)
                cv, cm, counts = self._static_fn(cap, target, depth)(
                    verts, masks)
                entries.append((target, idxs, (cv, cm)))
                aux.append((cap, idxs, counts, verts, masks))
            span.set_metadata(launches=len(aux))
        return entries, aux

    def _resolve_static_aux(self, window, d_out):
        """Static collect: deferred count fetch + keep-originals re-sweep.

        Fetches each cap group's (B, 2) counts (the sync the static
        schedule moved out of pass 1), derives the SAME
        ``plan_compaction`` decision the counted schedule makes, and for
        the keep-originals cases re-sweeps the retained original stacks
        at their input cap -- those rows' static-target results are the
        only ones discarded.
        """
        prepped = window.prepped
        retries = []
        for cap, idxs, counts_fut, verts, masks in window.static_aux:
            counts = self._fetch("pass2b_counts", counts_fut)
            retry_js = []
            for j, i in enumerate(idxs):
                cap_out, info = prune_kernels.plan_compaction(
                    cap, int(counts[j, 0]), int(counts[j, 1]),
                    ops.vertex_bucket,
                )
                prepped[i].prune_info = info
                prepped[i].vertex_cap = cap_out or cap
                if cap_out is None:
                    retry_js.append(j)
            if retry_js:
                take = jnp.asarray(np.asarray(retry_js, np.int32))
                sub = self._pad_batch(
                    tuple(jnp.take(a, take, axis=0) for a in (verts, masks)),
                    len(retry_js),
                )
                retries.append((cap, [idxs[j] for j in retry_js], sub))
        if retries:
            futs = self._submit("pass2b", retries, self._diam_fn,
                                self._stacked_chunk)
            d_out.update(self._drain(futs, "pass2b_retry"))

    def _resolve_hint_counts(self, window, d_out):
        """Hint-prep collect: deferred count fetch + hint-overflow retry.

        ``prep='hint'`` sized each cap from metadata and left the true
        dedup count on device; it is fetched here -- AFTER every launch
        of the window was submitted, so no prep/submit ever blocked on it
        -- both because the count is itself a feature of the row and to
        detect overflow.  A case whose true count exceeds its hint cap
        had vertices dropped by ``compact_vertices``: its pass-1/2b
        results are discarded and it re-runs count-sized through the
        single-case oracle stages (same kernels, same tuned configs --
        the same retry contract as the static keep-originals re-sweep).
        """
        prepped = window.prepped
        for i, p in enumerate(prepped):
            if p.n_fut is None:
                continue
            n = int(self._fetch("collect_counts", p.n_fut))
            overflow = n > p.prep_cap
            p.n_vertices = n
            p.n_fut = None
            if not overflow:
                continue
            cap = ops.vertex_bucket(n)
            f, _ = _fields_count(p.mask, jnp.asarray(p.spacing))
            verts, vmask = _compact_cap(f, cap)
            v2, m2, info = ops.prune_candidates(verts, vmask)
            variant, block = self._resolve_diameter(len(v2))
            d = ops.max_diameters(
                v2, m2, backend=self.backend, variant=variant, block=block
            )
            d_out[i] = self._fetch("hint_retry", d)
            p.verts, p.vmask = v2, m2
            p.prune_info = info
            p.vertex_cap = len(v2)

    # -- window API ----------------------------------------------------------

    def submit_window(self, cases, batch_size=None) -> _Window:
        """Prep one window and issue EVERY device launch for it (no drains).

        Each case is an ``(image, mask, spacing)`` tuple or a zero-arg
        loader callable; a case that fails to load or validate is
        quarantined (NaN row) instead of killing the window.
        """
        prepped = [
            self._prep_case_safe(c, window=self._next_window, pos=k)
            for k, c in enumerate(cases)
        ]
        return self.submit_prepped(prepped, batch_size)

    def submit_prepped(self, prepped, batch_size=None) -> _Window:
        """Plan + submit already-prepped cases (the adaptive stream preps
        case by case, so planning must be callable on pass-0 state alone).

        ``schedule='auto'`` resolves here, per window: the cost model
        weighs the modeled sync cost of the counted schedule against the
        static schedule's padded sweeps on this window's census
        (``runtime/costmodel.CostModel.choose_schedule``).

        The window takes the executor's next window number, the ``window``
        stat of the ``repro.window.submit`` span around its planning and
        launches and of the spans that prep and collect it.
        """
        seq = self._next_window
        self._next_window += 1
        with jax.profiler.TraceAnnotation("repro.window.submit", window=seq,
                                          cases=len(prepped)):
            with jax.profiler.TraceAnnotation("repro.plan") as span:
                metas = [self._meta(p) for p in prepped]
                schedule = self.schedule
                if schedule == "auto":
                    schedule = self.cost_model.choose_schedule(metas)
                plan = planlib.build_plan(metas, schedule,
                                          families=self.families)
                span.set_metadata(schedule=plan.schedule,
                                  buckets=len(plan.shape_groups))
            family_futs = self._submit_families(plan, prepped, batch_size)

            if not self._shape_on:
                # intensity-only request: the family launches are the window
                return _Window(prepped, plan, [], [], [], seq, family_futs)

            # pass 1
            if plan.schedule == "static":
                entries, aux = self._pass1_static(plan, prepped)
            else:
                entries, aux = self._pass1_counted(plan, prepped)

            # pass 2a: staged fused MC per shape bucket, straight off the pools
            mc_entries = (
                (shape, idxs, self._pool(prepped, idxs))
                for shape, idxs in plan.shape_groups.items()
            )
            mc_futs = self._submit(
                "pass2a", mc_entries, self._mc_fn, self._stacked_chunk,
                batch_size
            )

            # pass 2b: diameter sweep per pruned vertex bucket
            diam_futs = self._submit(
                "pass2b", entries, self._diam_fn, self._stacked_chunk,
                batch_size
            )
            return _Window(prepped, plan, mc_futs, diam_futs, aux, seq,
                           family_futs)

    def resubmit_window(self, window: _Window) -> _Window:
        """Idempotently re-submit a window from its prepped device state.

        The retry path: pass 1 may have overwritten each case's
        ``vertex_cap`` with its pass-2b bucket and attached a
        ``PruneInfo``, so both are reset to the prep-time state (the cap
        is the length of the retained vertex stack) before re-planning --
        the stacks themselves were never mutated, so the re-run is
        bit-identical to a first run (padding invariance, tier-1-locked).
        Quarantined and empty cases pass through untouched.
        """
        for p in window.prepped:
            if p.mask is None or p.error is not None:
                continue
            if p.verts is not None:
                p.vertex_cap = int(p.verts.shape[0])
                p.prune_info = None
        return self.submit_prepped(window.prepped)

    def collect_window(self, window: _Window):
        """Drain one submitted window; returns ``(rows, stats)`` in order.

        With a ``retry`` policy configured (``runtime/resilience.
        RetryPolicy``), a collect failure re-submits the window from its
        prepped device state and re-drains after exponential backoff, up
        to ``max_retries`` times -- a transient device/link fault costs
        one window of recompute, not the run.  ``timeout_s`` is advisory:
        an over-deadline collect is flagged in the stats for the
        straggler census (a blocking fetch cannot be interrupted).  The
        drain runs inside a ``repro.window.collect`` span that carries the
        window's number.
        """
        with jax.profiler.TraceAnnotation("repro.window.collect",
                                          window=window.seq):
            policy = self.retry
            if policy is None:
                return self._collect_window(window)
            attempt = 0
            while True:
                t0 = time.perf_counter()
                try:
                    rows, stats = self._collect_window(window)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception:
                    if attempt >= policy.max_retries:
                        raise
                    self.window_retries += 1
                    time.sleep(policy.delay(attempt))
                    window = self.resubmit_window(window)
                    attempt += 1
                    continue
                dt = time.perf_counter() - t0
                if policy.timeout_s is not None and dt > policy.timeout_s:
                    stats["collect_timeout"] = dt
                if attempt:
                    stats["window_retries"] = attempt
                return rows, stats

    def _collect_window(self, window: _Window):
        prepped = window.prepped
        # intensity families drain first (they were submitted first);
        # stage names match the family names so transfer_log keeps a
        # per-family sync census and the shape stages' counts are
        # untouched by enabling families
        fam_out = {
            family: self._drain(futs, family)
            for family, futs in window.family_futs.items()
        }

        shape_on = self._shape_on
        mc_out = self._drain(window.mc_futs, "pass2a")
        d_out = self._drain(window.diam_futs, "pass2b")
        if window.static_aux:
            self._resolve_static_aux(window, d_out)
        if any(p.n_fut is not None for p in prepped):
            # hint prep: drain the deferred counts, retry overflow cases
            # (AFTER the static aux so a retried row wins over both)
            self._resolve_hint_counts(window, d_out)

        rows = []
        with jax.profiler.TraceAnnotation("repro.rows", rows=len(prepped)):
            for i, p in enumerate(prepped):
                if p.mask is None:
                    rows.append(self._degenerate_row(p))
                    continue
                shape_row = None
                if shape_on:
                    shape_row = np.concatenate(
                        [np.asarray(mc_out[i], np.float32),
                         np.asarray(d_out[i], np.float32),
                         np.asarray([p.n_vertices], np.float32)]
                    )
                rows.append(self._assemble_row(i, p, shape_row, fam_out))
        return rows, self._window_stats(window)

    def _family_row(self, family: str, payload) -> np.ndarray:
        """Finalise one case's fetched device payload into a feature row.

        The shared host-side derivations (numpy, deterministic): packed
        stats -> 9 first-order features, count matrix -> 4 Haralick
        features.  Kept out of the traced launches so batched and
        single-case rows stay bit-identical (see kernels/firstorder.py).
        """
        if family == "firstorder":
            from repro.kernels import firstorder as _fo

            return _fo.features_from_packed_np(payload, self.n_bins)
        from repro.kernels import glcm as _glcm

        return _glcm.glcm_features_from_matrix_np(payload, self.n_bins)

    def _assemble_row(self, i, p, shape_row, fam_out) -> np.ndarray:
        """Concatenate one case's family parts in canonical family order."""
        parts = []
        for family in self.families:
            if family == "shape":
                parts.append(shape_row)
            else:
                parts.append(self._family_row(family, fam_out[family][i]))
        if len(parts) == 1:
            return parts[0]
        return np.concatenate(parts)

    def _degenerate_row(self, p: _Prepped) -> np.ndarray:
        """Row for a case that ran no launches: zeros (empty mask, the
        degenerate-segmentation contract) or NaNs (quarantined -- the
        row-level error record; the message rides the window stats)."""
        # width derives from the RESOLVED family set, not the shape-only
        # class constant -- a quarantined case in a multi-family run must
        # produce a full-width NaN row or np.stack on the results breaks
        if p.error is not None:
            return np.full(self.n_features, np.nan, np.float32)
        return np.zeros(self.n_features, np.float32)

    def _window_stats(self, window: _Window) -> dict:
        prepped = window.prepped
        infos = [p.prune_info for p in prepped if p.prune_info is not None]
        pruned = [inf for inf in infos if inf.pruned]
        return {
            "families": list(self.families),
            "buckets": len(window.plan.shape_groups),
            "vertex_buckets": len(
                {p.vertex_cap for p in prepped if p.vertex_cap}
            ),
            "pruned_cases": len(pruned),
            "empty_cases": sum(
                1 for p in prepped if p.mask is None and p.error is None
            ),
            "quarantined_cases": sum(1 for p in prepped if p.error is not None),
            "errors": {
                i: p.error for i, p in enumerate(prepped) if p.error is not None
            },
            "mean_keep_fraction": (
                float(np.mean([inf.keep_fraction for inf in infos]))
                if infos else 1.0
            ),
            "plan": window.plan.stats(),
        }

    # -- public driving ------------------------------------------------------

    def run(self, cases: Sequence, batch_size: int | None = None):
        """Extract features for (image, mask, spacing) cases (one window).

        Returns a list of ``(row_width(families),)`` rows in input order
        plus throughput stats -- (7,) for the default shape-only request,
        wider when intensity families are enabled (``plan.family_slices``
        maps each family to its columns).
        """
        t0 = time.perf_counter()
        fetches0 = dict(self.transfer_log)
        window = self.submit_window(list(cases), batch_size)
        results, stats = self.collect_window(window)
        dt = time.perf_counter() - t0
        stats.update(
            cases=window.plan.n_cases,
            seconds=dt,
            cases_per_second=window.plan.n_cases / dt if dt > 0 else float("inf"),
            data_parallel=psharding.axis_size(self.mesh, self.data_axis),
            schedule=self.schedule,  # 'auto' here; plan.schedule = resolved
            prep=self.prep,
            host_fetches={
                k: v - fetches0.get(k, 0)
                for k, v in self.transfer_log.items()
                if v - fetches0.get(k, 0)
            },
        )
        return results, stats

    def extract_stream(self, cases: Iterable, window: int | str = 32,
                       batch_size: int | None = None, stats_callback=None):
        """Streaming front-end: overlap window k+1's prep with window k.

        Consumes an iterator of (image, mask, spacing) cases and yields
        feature rows in input order.  Window k+1 is prepped and its
        launches submitted while the device still executes window k (jax
        dispatch is async); only then is window k drained and yielded.
        ``stats_callback(window_index, plan_stats)`` fires at each
        window's submit with its plan census (buckets, pad waste).

        ``window='auto'`` sizes the windows adaptively from the running
        bucket census and the cost model (``runtime/costmodel``): a new
        shape/cap bucket closes a window early once its current
        sub-batches are all past break-even depth, and homogeneous runs
        extend up to the memory-budgeted cap -- bit-identical rows to any
        fixed window (windowing never changes a feature, tier-1-locked).
        """
        if window == "auto":
            yield from self._stream_auto(cases, batch_size, stats_callback)
            return
        if not isinstance(window, int) or window < 1:
            raise ValueError(
                f"window must be a positive int or 'auto', got {window!r}"
            )
        it = iter(cases)
        pending = None
        widx = 0
        while True:
            chunk = list(itertools.islice(it, window))
            state = None
            if chunk:
                state = self.submit_window(chunk, batch_size)
                if stats_callback is not None:
                    stats_callback(widx, state.plan.stats())
                widx += 1
            if pending is not None:
                rows, _ = self.collect_window(pending)
                yield from rows
            if state is None:
                return
            pending = state

    def _stream_auto(self, cases: Iterable, batch_size=None,
                     stats_callback=None):
        """Adaptive-window streaming: cost-model-decided window boundaries.

        Cases are prepped one by one (prep is per-case work regardless of
        windowing) into an open buffer whose bucket census
        (``plan.WindowCensus``) feeds the close-early decision
        (``CostModel.should_close``).  Submit/collect overlap is the same
        as the fixed-window path: the closed window is submitted BEFORE
        the previous one is drained.
        """
        cm = self.cost_model
        pending = None
        widx = 0
        buf: list = []
        census = planlib.WindowCensus()
        for case in cases:
            p = self._prep_case_safe(case)
            meta = self._meta(p)
            if buf and cm.should_close(census, meta):
                state = self.submit_prepped(buf, batch_size)
                if stats_callback is not None:
                    stats_callback(widx, state.plan.stats())
                widx += 1
                buf, census = [], planlib.WindowCensus()
                if pending is not None:
                    rows, _ = self.collect_window(pending)
                    yield from rows
                pending = state
            buf.append(p)
            census.add(meta)
        if buf:
            state = self.submit_prepped(buf, batch_size)
            if stats_callback is not None:
                stats_callback(widx, state.plan.stats())
            if pending is not None:
                rows, _ = self.collect_window(pending)
                yield from rows
            pending = state
        if pending is not None:
            rows, _ = self.collect_window(pending)
            yield from rows

    def extract_one(self, image, mask, spacing):
        """Single-case pruned path: the batched pipeline's parity oracle.

        Runs the identical stages (same bucket padding, pruning, tuned
        configs, kernels) without any batching, with pass 1 as the
        host-compaction oracle (``ops.prune_candidates``); returns a
        ``(row_width(families),)`` row -- (7,) for the default shape-only
        request.  Intensity families run at batch depth 1 through the
        same ``ops`` entry points as the batched pipeline (canonical-chunk
        contract: B=1 rows are bit-identical to any batched depth).  An
        empty mask yields zeros, matching the batched contract.  Always
        count-sized: the oracle is the baseline the hint prep must match.
        """
        p = self._prep_case(image, mask, spacing, prep="count")
        if p.mask is None:
            return np.zeros(self.n_features, np.float32)
        parts = []
        for family in self.families:
            if family == "shape":
                p.verts, p.vmask, p.prune_info = ops.prune_candidates(
                    p.verts, p.vmask)
                mc_block, mc_chunk = self._resolve_mc(p.shape)
                mc_kw = ({"block": mc_block, "chunk": mc_chunk}
                         if mc_block is not None
                         else {"chunk": mc_chunk}
                         if mc_chunk is not None else {})
                vol, area = ops.mc_volume_area(
                    p.mask, 0.5, p.spacing, backend=self.backend, **mc_kw
                )
                variant, block = self._resolve_diameter(len(p.verts))
                d = ops.max_diameters(
                    p.verts, p.vmask, backend=self.backend, variant=variant,
                    block=block
                )
                parts.append(np.concatenate(
                    [np.asarray([vol, area], np.float32),
                     np.asarray(d, np.float32),
                     np.asarray([p.n_vertices], np.float32)]
                ))
                continue
            blk = self._resolve_family_block(family, p.shape)
            op = (ops.firstorder_packed_batch if family == "firstorder"
                  else ops.glcm_matrix_batch)
            r = op(p.image[None], p.mask[None], backend=self.backend,
                   n_bins=self.n_bins, block=blk)
            parts.append(self._family_row(family, self._fetch(family, r)[0]))
        if len(parts) == 1:
            return parts[0]
        return np.concatenate(parts)
