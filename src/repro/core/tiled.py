"""Out-of-core tiled extraction: halo tiles, tile pruning, streamed diameter.

The layer between the slab loaders (``data/tiles.py``) and the
plan/executor: extracts the same feature row as the in-core pipeline for
a volume that never materializes on host or device.  The executor still
owns backends, tuned configs and the oracle sequence -- this engine only
re-partitions pass 0..2 into z-tiles and re-folds the partials in the
in-core order.

Data flow (one case)
--------------------
1. **Census prepass** (host, streamed): global nonzero/inside bounding
   boxes, per-plane occupancy + xy boxes, the masked intensity range
   (exact min/max -- order-invariant), and for ``tile_prune='bounds'``
   the K-direction extreme inside-voxels the tile bound needs.
2. **Frame replication**: the in-core pipeline crops to the mask bbox,
   pads by one zero plane (``crop_to_roi``) and bucket-pads to
   ``plan.shape_bucket``.  The census gives the same frame geometry
   without materializing anything: frame index = original - lo + 1.
3. **Tile sweep**: the frame is cut into z-tiles of whole MC granules
   (ref: ``chunk_z`` slabs, kernel backends: brick rows), each staged
   with a +1-plane halo so every marching-cubes cell and vertex edge on
   a tile face sees the same neighbour values as in-core.  Edge
   ownership partitions the three vertex fields exactly: a tile owns
   x/y-edges on its frame planes and z-edge slots starting there, so no
   vertex is emitted twice.  Per tile: MC partial sums
   (``ops.mc_tile_partials``), owned-vertex positions (device fields on
   an xy-subcrop, ``index_offset`` keeps coordinates in the global
   frame -- exact, see ``kernels/ref.vertex_fields``), and the
   first-order voxel gather.  Submit-(k+1)/collect-k: tile k+1's device
   work is dispatched before tile k's futures are drained.
4. **Hierarchical pruning**: ``'occupancy'`` skips all-zero tiles (their
   MC partials are exactly +0.0 and they own no vertices -- fully
   bitwise on every backend); ``'bounds'`` additionally lifts the
   ``kernels/prune`` vertex bound one level and skips the VERTEX work of
   tiles whose inflated AABB provably cannot contain a farthest-pair
   endpoint for any of the 4 diameter combos (bit-identical on the gram
   Pallas variants, ~1 ulp on the ref diameter path -- the same
   contract ``prune_candidates`` documents).  ``'none'`` stages every
   tile (the naive baseline the bench row beats).
5. **Re-fold**: MC partials are re-assembled in global slab/brick order
   (skipped tiles contribute exact +0.0) and folded with the in-core
   reduction order; owned vertices from all surviving tiles are sorted
   by their global field rank -- reproducing the in-core compacted
   buffer -- then run the UNCHANGED oracle tail: ``prune_candidates``
   -> tuned diameter kernel.  First-order stats fold the mask-touched
   canonical chunks through ``kernels/firstorder.fold_packed_chunks``.

Budget: ``REPRO_TILE_MEM_MB`` (default 256) bounds the device bytes --
two tiles' staged slabs (the submit/collect overlap holds at most two
alive), mask + intensity, plus one tile's marching-cubes temporaries on
the brick-kernel backends (``kernels/marching_cubes.work_bytes``: the
corner planes are several times the slab).  GLCM needs neighbour
pairs across tile faces and is not offered tiled (``ValueError``).
"""
from __future__ import annotations

import dataclasses
import warnings

import jax.numpy as jnp
import numpy as np

from repro.core import plan as planlib
from repro.kernels import firstorder as _fo
from repro.kernels import marching_cubes as _mc
from repro.kernels import ops

DEFAULT_TILE_MEM_MB = 256.0
TILE_PRUNE_LEVELS = ("none", "occupancy", "bounds")

_SUBCROP_STEP = 16  # xy-subcrop dims bucket (bounds fields compiles)


def tile_budget_bytes() -> int:
    """The configured staged-bytes budget (``REPRO_TILE_MEM_MB``)."""
    from repro.runtime import costmodel

    return int(costmodel._env_float("REPRO_TILE_MEM_MB",
                                    DEFAULT_TILE_MEM_MB) * 2**20)


@dataclasses.dataclass
class TiledResult:
    """One tiled case's row + the census the cost model consumes."""

    row: np.ndarray
    meta: planlib.CaseMeta
    stats: dict


@dataclasses.dataclass
class _Census:
    """Host prepass summary (see module docstring, step 1)."""

    empty: bool
    lo: np.ndarray = None          # (3,) nonzero bbox lower corner (orig)
    hi: np.ndarray = None          # (3,) nonzero bbox upper corner (orig)
    plane_any: np.ndarray = None   # (Z,) any nonzero mask on orig plane z
    plane_box: np.ndarray = None   # (Z, 4) inside-voxel xlo,xhi,ylo,yhi
    int_lo: float = 0.0            # masked intensity range (exact min/max)
    int_hi: float = 0.0
    witnesses: np.ndarray = None   # (W, 3) extreme inside-voxel coords (orig)


class TiledExtractor:
    """Drives one :class:`~repro.data.tiles.TiledCase` through the tiled
    pipeline using an executor's backend/config/oracle machinery."""

    def __init__(self, executor, budget_bytes: int | None = None,
                 tile_prune: str = "bounds"):
        if tile_prune not in TILE_PRUNE_LEVELS:
            raise ValueError(
                f"tile_prune must be one of {TILE_PRUNE_LEVELS}, got "
                f"{tile_prune!r}"
            )
        for fam in executor.families:
            if fam not in ("shape", "firstorder"):
                raise ValueError(
                    f"feature family {fam!r} is not supported in tiled mode "
                    "(GLCM needs neighbour pairs across tile faces); run it "
                    "in-core or request shape/firstorder only"
                )
        self.ex = executor
        self.budget_bytes = (tile_budget_bytes() if budget_bytes is None
                             else int(budget_bytes))
        self.tile_prune = tile_prune

    # -- census prepass -----------------------------------------------------

    def _census(self, case) -> _Census:
        X, Y, Z = case.shape
        need_int = self.ex._needs_intensity
        need_wit = self.tile_prune == "bounds" and self.ex._shape_on
        dirs = None
        if need_wit:
            from repro.kernels import prune as _prune

            dirs = _prune._directions((0, 1, 2), _prune.K_DIRS)  # (K, 3)
            pmax = np.full(len(dirs), -np.inf)
            pmin = np.full(len(dirs), np.inf)
            wmax = np.zeros((len(dirs), 3), np.int64)
            wmin = np.zeros((len(dirs), 3), np.int64)
        plane_any = np.zeros(Z, bool)
        plane_box = np.full((Z, 4), -1, np.int64)
        lo = np.array([X, Y, Z], np.int64)
        hi = np.array([-1, -1, -1], np.int64)
        int_lo, int_hi = np.inf, -np.inf
        sp64 = np.asarray(case.spacing, np.float64)

        # census chunk: a slab the budget could stage (mask only, f32)
        step = max(1, min(Z, self.budget_bytes // max(1, X * Y * 4)))
        for z0 in range(0, Z, step):
            z1 = min(z0 + step, Z)
            sl = np.asarray(case.mask_slab(z0, z1))
            nz = sl != 0
            anyz = nz.any(axis=(0, 1))
            if not anyz.any():
                continue
            plane_any[z0:z1] = anyz
            xs, ys, zs = np.nonzero(nz)
            lo = np.minimum(lo, [xs.min(), ys.min(), z0 + zs.min()])
            hi = np.maximum(hi, [xs.max(), ys.max(), z0 + zs.max()])
            ins = sl > 0.5  # iso-inside voxels: what vertices attach to
            ixs, iys, izs = np.nonzero(ins)
            for k, zz in enumerate(range(z0, z1)):
                pm = izs == k
                if pm.any():
                    px, py = ixs[pm], iys[pm]
                    plane_box[zz] = (px.min(), px.max(), py.min(), py.max())
            if need_wit and len(ixs):
                pts = np.stack([ixs, iys, izs + z0], 1).astype(np.float64)
                proj = (pts * sp64) @ dirs.T  # (V, K)
                jmax, jmin = proj.argmax(0), proj.argmin(0)
                for d in range(len(dirs)):
                    if proj[jmax[d], d] > pmax[d]:
                        pmax[d] = proj[jmax[d], d]
                        wmax[d] = pts[jmax[d]]
                    if proj[jmin[d], d] < pmin[d]:
                        pmin[d] = proj[jmin[d], d]
                        wmin[d] = pts[jmin[d]]
            if need_int and len(xs):
                pos = sl > 0  # the intensity-family mask rule (mask > 0)
                if pos.any():
                    img = np.asarray(case.image_slab(z0, z1),
                                     np.float32)[pos]
                    int_lo = min(int_lo, float(img.min()))
                    int_hi = max(int_hi, float(img.max()))
        if hi[0] < 0:
            return _Census(empty=True)
        wit = None
        if need_wit:
            wit = np.unique(np.concatenate([wmax, wmin]), axis=0)
        return _Census(
            empty=False, lo=lo, hi=hi, plane_any=plane_any,
            plane_box=plane_box,
            int_lo=0.0 if np.isinf(int_lo) else int_lo,
            int_hi=0.0 if np.isinf(int_hi) else int_hi,
            witnesses=wit,
        )

    # -- tile-level bounds pruning ------------------------------------------

    @staticmethod
    def _combo_lowers(witnesses, sp64):
        """(4,) conservative lower bounds on the combo diameters (f64).

        Max pairwise distance among the direction-extreme INSIDE-voxel
        centres, per combo projection, minus ``2*max(spacing)``: every
        inside extreme voxel has an outside axis-neighbour (otherwise a
        farther projection would exist), so a mesh vertex lies within
        ``max(spacing)`` of its centre.
        """
        combos = ((0, 1, 2), (0, 1), (0, 2), (1, 2))
        pts = witnesses * sp64  # physical centres, shift-invariant below
        slack = 2.0 * sp64.max()
        out = np.zeros(4)
        for ci, combo in enumerate(combos):
            p = pts[:, combo]
            d2 = ((p[:, None, :] - p[None, :, :]) ** 2).sum(-1)
            out[ci] = max(np.sqrt(d2.max()) - slack, 0.0)
        return out

    @staticmethod
    def _tile_upper(tbox_lo, tbox_hi, gbox_lo, gbox_hi, sp64):
        """(4,) upper bounds on any tile-vertex-to-anywhere distance.

        Boxes are inside-voxel index bboxes inflated by one voxel (a
        vertex sits on an edge of an inside voxel, within one index step
        per axis), mapped to physical space per axis.
        """
        t_lo = (tbox_lo - 1.0) * sp64
        t_hi = (tbox_hi + 1.0) * sp64
        g_lo = (gbox_lo - 1.0) * sp64
        g_hi = (gbox_hi + 1.0) * sp64
        per_axis = np.maximum(g_hi - t_lo, t_hi - g_lo)
        per_axis = np.maximum(per_axis, 0.0)
        combos = ((0, 1, 2), (0, 1), (0, 2), (1, 2))
        return np.array([
            np.sqrt((per_axis[list(c)] ** 2).sum()) for c in combos
        ])

    # -- the main sweep ------------------------------------------------------

    def extract(self, case) -> TiledResult:
        ex = self.ex
        cen = self._census(case)
        sp = np.asarray(case.spacing, np.float32)
        if cen.empty:
            meta = planlib.CaseMeta(shape=None, roi_shape=None,
                                    vertex_cap=0, n_vertices=0,
                                    intensity=ex._needs_intensity)
            return TiledResult(np.zeros(ex.n_features, np.float32), meta,
                               {"tiles": 0, "tiles_skipped": 0,
                                "tiles_bounds_pruned": 0})
        if ex._needs_intensity and case.image_source is None:
            raise ValueError(
                "intensity families requested but the TiledCase has no "
                "image source"
            )

        # frame geometry: crop_to_roi pad=1 + shape_bucket, from metadata
        lo, hi = cen.lo, cen.hi
        extent = hi - lo + 1
        roi_shape = tuple(int(e) + 2 for e in extent)
        bshape = planlib.shape_bucket(tuple(int(e) for e in extent))
        Xb, Yb, Zb = bshape
        fo = lo - 1  # frame index = original - fo
        ext_x, ext_y, ext_z = (int(e) for e in extent)

        # frame-plane census (frame plane p holds original plane p + fo[2])
        f_any = np.zeros(Zb, bool)
        f_box = np.full((Zb, 4), -1, np.int64)
        f_any[1:ext_z + 1] = cen.plane_any[lo[2]:hi[2] + 1]
        fb = cen.plane_box[lo[2]:hi[2] + 1].copy()
        has = fb[:, 1] >= 0
        fb[has, 0] -= fo[0]
        fb[has, 1] -= fo[0]
        fb[has, 2] -= fo[1]
        fb[has, 3] -= fo[1]
        f_box[1:ext_z + 1] = fb

        # MC granule + tile sizing under the staged-bytes budget
        n_cells = Zb - 1
        if ex.backend == "ref":
            cz = min(ex.mc_chunk or 32, n_cells)
            mc_block = mc_chunk = None
        else:
            mc_block, mc_chunk = ex._resolve_mc(bshape)
            cz = min(int(mc_block[2]), n_cells)
        n_slabs = -(-n_cells // cz)
        n_int = 1 + int(ex._needs_intensity)
        plane_bytes = Xb * Yb * 4 * n_int
        # per granule: two tiles' staged planes (submit k+1 / collect k
        # overlap) and, on the brick kernel, one tile's MC temporaries
        mc_granule = (_mc.work_bytes((Xb, Yb, cz + 1), mc_block)
                      if mc_block is not None and ex._shape_on else 0)
        g = max(1, (self.budget_bytes - 2 * plane_bytes)
                // (2 * plane_bytes * cz + mc_granule))
        tile_bytes = plane_bytes * (g * cz + 1)
        mc_work = g * mc_granule
        if 2 * tile_bytes + mc_work > self.budget_bytes:
            warnings.warn(
                f"tile budget {self.budget_bytes} B cannot hold two minimal "
                f"{tile_bytes} B tiles of frame {bshape} and {mc_work} B of "
                "marching-cubes temporaries; proceeding with 1-granule "
                "tiles over budget",
                RuntimeWarning, stacklevel=2,
            )
        n_tiles = -(-n_slabs // g)

        # global bounds-pruning threshold
        do_bounds = (self.tile_prune == "bounds" and ex._shape_on
                     and cen.witnesses is not None)
        sp64 = np.asarray(sp, np.float64)
        if do_bounds:
            lowers = self._combo_lowers(cen.witnesses - fo, sp64)
            g_ins_lo = np.array([
                f_box[f_box[:, 1] >= 0, 0].min(),
                f_box[f_box[:, 3] >= 0, 2].min(),
                int(np.nonzero(f_box[:, 1] >= 0)[0].min()),
            ], np.float64)
            g_ins_hi = np.array([
                f_box[:, 1].max(), f_box[:, 3].max(),
                int(np.nonzero(f_box[:, 1] >= 0)[0].max()),
            ], np.float64)

        shape_on = ex._shape_on
        needs_int = ex._needs_intensity
        iso = jnp.float32(0.5)
        sp_dev = jnp.asarray(sp)

        vol_parts = np.zeros(n_slabs, np.float32)   # ref: per-slab deltas
        area_parts = np.zeros(n_slabs, np.float32)
        brick_vol = brick_area = None               # kernel backends
        rank_list, pos_futs = [], []
        fo_chunks: dict[int, list] = {}
        n_total = 0
        skipped = bounds_pruned = 0
        pending = None  # previous tile's futures (collect-k)
        results = []

        def _drain(p):
            if p is not None:
                results.append({k: np.asarray(v) for k, v in p.items()})

        for t in range(n_tiles):
            k0, k1 = t * g, min((t + 1) * g, n_slabs)
            pz0 = k0 * cz
            pz_halo = min(k1 * cz + 1, Zb)          # planes with frame data
            own_end = k1 * cz if t < n_tiles - 1 else Zb  # x/y-edge planes
            dz = (k1 - k0) * cz + 1                 # staged depth (padded)

            if self.tile_prune != "none" and not f_any[pz0:pz_halo].any():
                skipped += 1
                continue

            # stage the frame slab (zeros frame + source window paste)
            slab = np.zeros((Xb, Yb, dz), np.float32)
            a, b = max(pz0, 1), min(pz_halo, ext_z + 1)
            if a < b:
                src = np.asarray(case.mask_slab(a + fo[2], b + fo[2]))
                slab[1:ext_x + 1, 1:ext_y + 1, a - pz0:b - pz0] = (
                    src[lo[0]:hi[0] + 1, lo[1]:hi[1] + 1].astype(np.float32)
                )
            futs = {}

            # MC partials for every staged tile
            if shape_on:
                part = ops.mc_tile_partials(
                    jnp.asarray(slab), iso, sp_dev, backend=ex.backend,
                    k0=k0, chunk_z=cz, full_shape=bshape,
                    block=mc_block, chunk=mc_chunk,
                )
                futs["mc"] = part
                futs["_mc_range"] = (k0, k1)

            # owned active edges (host): counts always, positions unless
            # the tile bound proves it holds no farthest-pair endpoint
            if shape_on:
                inside = slab > 0.5
                ax = inside[:-1, :, :] != inside[1:, :, :]
                ay = inside[:, :-1, :] != inside[:, 1:, :]
                az = inside[:, :, :-1] != inside[:, :, 1:]
                o = own_end - pz0
                if t < n_tiles - 1:
                    ax, ay = ax[:, :, :o], ay[:, :, :o]
                n_tile = int(ax.sum()) + int(ay.sum()) + int(az.sum())
                n_total += n_tile

                pruned = False
                if do_bounds and n_tile:
                    tb = f_box[pz0:pz_halo]
                    thas = tb[:, 1] >= 0
                    t_lo = np.array([
                        tb[thas, 0].min(), tb[thas, 2].min(),
                        pz0 + int(np.nonzero(thas)[0].min()),
                    ], np.float64)
                    t_hi = np.array([
                        tb[thas, 1].max(), tb[thas, 3].max(),
                        pz0 + int(np.nonzero(thas)[0].max()),
                    ], np.float64)
                    ups = self._tile_upper(t_lo, t_hi, g_ins_lo, g_ins_hi,
                                           sp64)
                    pruned = bool((ups * (1.0 + 1e-9) < lowers).all())
                if pruned:
                    bounds_pruned += 1
                elif n_tile:
                    futs.update(self._emit_vertices(
                        slab, ax, ay, az, f_box, pz0, pz_halo, sp_dev,
                        bshape, rank_list,
                    ))

            # first-order voxel gather over OWNED planes
            if needs_int:
                o1 = min(own_end, Zb) - pz0
                mm = slab[:, :, :o1] > 0
                if mm.any():
                    img = np.zeros((Xb, Yb, dz), np.float32)
                    if a < b:
                        isrc = np.asarray(
                            case.image_slab(a + fo[2], b + fo[2]))
                        img[1:ext_x + 1, 1:ext_y + 1, a - pz0:b - pz0] = (
                            isrc[lo[0]:hi[0] + 1, lo[1]:hi[1] + 1]
                            .astype(np.float32)
                        )
                    xs, ys, zs = np.nonzero(mm)
                    flat = ((xs.astype(np.int64) * Yb + ys) * Zb
                            + (zs + pz0))
                    self._scatter_chunks(fo_chunks, flat,
                                         img[xs, ys, zs])

            _drain(pending)
            pending = futs
        _drain(pending)

        # -- re-fold ---------------------------------------------------------
        parts = []
        for family in ex.families:
            if family == "shape":
                parts.append(self._finish_shape(
                    results, vol_parts, area_parts, n_slabs, bshape,
                    rank_list, n_total,
                ))
            else:
                parts.append(self._finish_firstorder(fo_chunks, cen))
        row = parts[0] if len(parts) == 1 else np.concatenate(parts)

        cap = ops.vertex_bucket(max(n_total, 1)) if shape_on else 0
        meta = planlib.CaseMeta(shape=bshape, roi_shape=roi_shape,
                                vertex_cap=cap, n_vertices=n_total,
                                intensity=needs_int)
        stats = {
            "tiles": n_tiles, "tiles_skipped": skipped,
            "tiles_bounds_pruned": bounds_pruned,
            "granule_cz": cz, "granules_per_tile": g,
            "tile_bytes": tile_bytes, "budget_bytes": self.budget_bytes,
            "staged_bytes_peak": 2 * tile_bytes,
            "mc_work_bytes": mc_work,
            "n_vertices": n_total,
            "emitted_vertices": sum(len(r) for r in rank_list),
        }
        return TiledResult(row.astype(np.float32), meta, stats)

    # -- per-tile helpers ----------------------------------------------------

    def _emit_vertices(self, slab, ax, ay, az, f_box, pz0, pz_halo, sp_dev,
                       bshape, rank_list):
        """Device vertex fields on the xy-subcrop; returns position futures.

        The subcrop spans the tile's inside-voxel xy bbox inflated by one
        (every active edge has an iso-inside endpoint, and the frame
        border is all-zero by construction), bucketed to bound the
        fields-kernel compile count; the excess is zero-extended, which
        activates nothing.  Owned active indices come from the HOST edge
        masks (the same exact comparisons the device performs), so the
        only device round trip is the gather of the active positions.
        """
        Xb, Yb, Zb = bshape
        dz = slab.shape[2]
        tb = f_box[pz0:pz_halo]
        thas = tb[:, 1] >= 0
        sx0 = max(int(tb[thas, 0].min()) - 1, 0)
        sy0 = max(int(tb[thas, 2].min()) - 1, 0)
        sx1 = min(int(tb[thas, 1].max()) + 2, Xb)
        sy1 = min(int(tb[thas, 3].max()) + 2, Yb)
        sxb = -(-(sx1 - sx0) // _SUBCROP_STEP) * _SUBCROP_STEP
        syb = -(-(sy1 - sy0) // _SUBCROP_STEP) * _SUBCROP_STEP
        sub = np.zeros((sxb, syb, dz), np.float32)
        cx, cy = min(sx0 + sxb, Xb) - sx0, min(sy0 + syb, Yb) - sy0
        sub[:cx, :cy] = slab[sx0:sx0 + cx, sy0:sy0 + cy]

        fields = ops.tile_vertex_fields(
            jnp.asarray(sub), jnp.float32(0.5), sp_dev,
            jnp.asarray([sx0, sy0, pz0], jnp.float32),
        )
        futs = {}
        off_y = (Xb - 1) * Yb * Zb
        off_z = off_y + Xb * (Yb - 1) * Zb
        specs = [
            (ax, fields.vx, (sxb - 1, syb, dz), 0, Yb, Zb),
            (ay, fields.vy, (sxb, syb - 1, dz), off_y, Yb - 1, Zb),
            (az, fields.vz, (sxb, syb, dz - 1), off_z, Yb, Zb - 1),
        ]
        for fi, (act, pos, fshape, roff, ry, rz) in enumerate(specs):
            ii, jj, ll = np.nonzero(act)
            if not len(ii):
                continue
            gx, gy, gz = ii + 0, jj + 0, ll + pz0  # global frame coords
            rank = roff + ((gx.astype(np.int64) * ry + gy) * rz + gz)
            # local indices into the subcrop field
            li, lj = ii - sx0, jj - sy0
            flat = (li.astype(np.int64) * fshape[1] + lj) * fshape[2] + ll
            rank_list.append(rank)
            futs[f"pos{fi}"] = jnp.take(
                pos.reshape(-1, 3), jnp.asarray(flat), axis=0
            )
        return futs

    @staticmethod
    def _scatter_chunks(chunks: dict, flat: np.ndarray, vals: np.ndarray):
        """Accumulate masked voxels into canonical-chunk buffers."""
        C = _fo.CANON_CHUNK
        cids = flat // C
        offs = flat % C
        uniq, starts = np.unique(cids, return_index=True)
        bounds = list(starts) + [len(flat)]
        for u, s, e in zip(uniq, bounds[:-1], bounds[1:]):
            buf = chunks.get(int(u))
            if buf is None:
                buf = chunks[int(u)] = [np.zeros(C, np.float32),
                                        np.zeros(C, np.float32)]
            buf[0][offs[s:e]] = vals[s:e]
            buf[1][offs[s:e]] = 1.0

    # -- re-fold helpers -----------------------------------------------------

    def _finish_shape(self, results, vol_parts, area_parts, n_slabs, bshape,
                      rank_list, n_total):
        ex = self.ex
        if ex.backend == "ref":
            for r in results:
                if "mc" in r:
                    k0, k1 = r["_mc_range"]
                    dvs, das = r["mc"]
                    vol_parts[k0:k1] = dvs
                    area_parts[k0:k1] = das
            vol, area = ops.mc_tile_finalize(vol_parts, area_parts,
                                             backend=ex.backend)
        else:
            # assemble the full brick grid; pruned tiles stay exact zeros
            first = next((r for r in results if "mc" in r), None)
            if first is None:
                vol = area = np.float32(0.0)
            else:
                nbx, nby = first["mc"][0].shape[:2]
                bv = np.zeros((nbx, nby, n_slabs), np.float32)
                ba = np.zeros((nbx, nby, n_slabs), np.float32)
                for r in results:
                    if "mc" in r:
                        k0, k1 = r["_mc_range"]
                        bv[:, :, k0:k1], ba[:, :, k0:k1] = r["mc"]
                vol, area = ops.mc_tile_finalize(bv, ba, backend=ex.backend)

        # streamed farthest pair: global-rank sort reproduces the in-core
        # compacted buffer; then the unchanged oracle tail
        pos = [r[k] for r in results for k in sorted(r)
               if k.startswith("pos")]
        if not pos:
            d = np.zeros(4, np.float32)
            return np.concatenate([
                np.asarray([vol, area], np.float32), d,
                np.asarray([n_total], np.float32),
            ])
        ranks = np.concatenate(rank_list)
        verts_sorted = np.concatenate(pos)[np.argsort(ranks, kind="stable")]
        n_emitted = len(verts_sorted)
        cap = ops.vertex_bucket(n_emitted)
        verts = np.zeros((cap, 3), np.float32)
        verts[:n_emitted] = verts_sorted
        vmask = np.zeros(cap, bool)
        vmask[:n_emitted] = True
        verts, vmask, _ = ops.prune_candidates(verts, vmask)
        variant, block = ex._resolve_diameter(len(verts))
        d = ops.max_diameters(verts, vmask, backend=ex.backend,
                              variant=variant, block=block)
        return np.concatenate([
            np.asarray([vol, area], np.float32),
            np.asarray(d, np.float32),
            np.asarray([n_total], np.float32),
        ])

    def _finish_firstorder(self, chunks: dict, cen: _Census):
        ex = self.ex
        if not chunks:
            return np.zeros(_fo.N_FEATURES, np.float32)
        cids = sorted(chunks)
        nt = len(cids)
        ntb = 1 << (nt - 1).bit_length()  # pad with exact-+0 chunks
        C = _fo.CANON_CHUNK
        x = np.zeros((ntb, C), np.float32)
        m = np.zeros((ntb, C), np.float32)
        for i, cid in enumerate(cids):
            x[i], m[i] = chunks[cid]
        packed = _fo.fold_packed_chunks(
            jnp.asarray(x), jnp.asarray(m),
            jnp.float32(cen.int_lo), jnp.float32(cen.int_hi),
            n_bins=ex.n_bins,
        )
        return ex._family_row("firstorder", np.asarray(packed))
