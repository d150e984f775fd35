"""Pure-jnp reference oracles for the two PyRadiomics-cuda hot spots.

These are the numerical ground truth the Pallas kernels are validated against
(``tests/test_kernels_*``) and the CPU fallback path of the dispatcher -- the
role the original C implementation plays in PyRadiomics-cuda.

Conventions
-----------
* volumes are ``(nx, ny, nz)`` float arrays; a voxel is *inside* iff
  ``value > iso`` (binary masks with ``iso=0.5``, as PyRadiomics uses).
* ``spacing``/``origin`` map index space to physical space:
  ``pos_phys = origin + index * spacing``.
* mesh vertices are deduplicated by construction: every *grid edge* owns at
  most one vertex, stored in three dense per-axis fields (VX, VY, VZ).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mc_tables as mct

_TRI_TABLE = jnp.asarray(mct.TRI_TABLE)  # (256, 15) int32, -1 padded
_NSLOTS = mct.MAX_TRIS * 3


class VertexFields(NamedTuple):
    """Dense per-axis vertex fields (the TPU-native 'triangle append')."""

    vx: jax.Array  # (nx-1, ny, nz, 3) positions on x-directed edges
    vy: jax.Array  # (nx, ny-1, nz, 3)
    vz: jax.Array  # (nx, ny, nz-1, 3)
    ax: jax.Array  # (nx-1, ny, nz) bool, edge active
    ay: jax.Array
    az: jax.Array


def _interp(v0, v1, iso):
    """Interpolation parameter of the iso crossing along an edge."""
    denom = v1 - v0
    safe = jnp.where(jnp.abs(denom) < 1e-30, 1.0, denom)
    t = (iso - v0) / safe
    return jnp.clip(t, 0.0, 1.0)


def vertex_fields(vol, iso=0.5, spacing=(1.0, 1.0, 1.0), origin=(0.0, 0.0, 0.0),
                  index_offset=None):
    """Compute the deduplicated mesh-vertex fields (pure elementwise pass).

    ``index_offset`` (default: none -- the graph is unchanged) shifts the
    per-axis grid indices before the physical mapping, so a sub-window of
    a larger volume emits positions in the FULL volume's index frame.
    The offsets are integers (< 2^24) added to integer-valued f32 iotas:
    the add is exact, so ``(local + offset) + t`` is bit-identical to the
    full volume's ``global + t`` -- the key to tiled/in-core vertex
    bit-parity (``core/tiled.py``).
    """
    vol = jnp.asarray(vol, jnp.float32)
    sp = jnp.asarray(spacing, jnp.float32)
    og = jnp.asarray(origin, jnp.float32)
    off = None if index_offset is None else jnp.asarray(index_offset, jnp.float32)
    nx, ny, nz = vol.shape
    inside = vol > iso

    def axis_field(axis, n_axis):
        sl0 = [slice(None)] * 3
        sl1 = [slice(None)] * 3
        sl0[axis] = slice(0, -1)
        sl1[axis] = slice(1, None)
        v0, v1 = vol[tuple(sl0)], vol[tuple(sl1)]
        act = inside[tuple(sl0)] != inside[tuple(sl1)]
        t = _interp(v0, v1, iso)
        shape = v0.shape
        ii, jj, kk = jnp.meshgrid(
            jnp.arange(shape[0], dtype=jnp.float32),
            jnp.arange(shape[1], dtype=jnp.float32),
            jnp.arange(shape[2], dtype=jnp.float32),
            indexing="ij",
        )
        idx = [ii, jj, kk]
        if off is not None:
            idx = [ii + off[0], jj + off[1], kk + off[2]]
        idx[axis] = idx[axis] + t
        pos = jnp.stack(idx, axis=-1) * sp + og
        return pos, act

    vx, ax = axis_field(0, nx)
    vy, ay = axis_field(1, ny)
    vz, az = axis_field(2, nz)
    return VertexFields(vx, vy, vz, ax, ay, az)


def _cell_cube_index(vol, iso):
    """(nx-1,ny-1,nz-1) int32 MC case index per cell."""
    inside = (vol > iso).astype(jnp.int32)
    idx = 0
    for c, (dx, dy, dz) in enumerate(np.asarray(mct.CORNERS)):
        sl = (
            slice(dx, dx + vol.shape[0] - 1),
            slice(dy, dy + vol.shape[1] - 1),
            slice(dz, dz + vol.shape[2] - 1),
        )
        idx = idx + (inside[sl] << c)
    return idx


def _cell_edge_positions(f: VertexFields):
    """Stack the 12 per-cell edge-vertex positions from the dense fields.

    Returns (cx, cy, cz, 12, 3).  Pure slicing -- no dynamic gather.
    """
    vx, vy, vz = f.vx, f.vy, f.vz
    e = [None] * 12
    e[0] = vx[:, :-1, :-1]
    e[2] = vx[:, 1:, :-1]
    e[4] = vx[:, :-1, 1:]
    e[6] = vx[:, 1:, 1:]
    e[3] = vy[:-1, :, :-1]
    e[1] = vy[1:, :, :-1]
    e[7] = vy[:-1, :, 1:]
    e[5] = vy[1:, :, 1:]
    e[8] = vz[:-1, :-1, :]
    e[9] = vz[1:, :-1, :]
    e[10] = vz[1:, 1:, :]
    e[11] = vz[:-1, 1:, :]
    return jnp.stack(e, axis=-2)


def _slab_volume_area(slab, iso, spacing, origin):
    """Signed mesh volume + surface area for the cells of one volume slab."""
    f = vertex_fields(slab, iso, spacing, origin)
    e = _cell_edge_positions(f)  # (cx,cy,cz,12,3)
    idx = _cell_cube_index(slab, iso)  # (cx,cy,cz)
    tids = _TRI_TABLE[idx]  # (cx,cy,cz,15) via jnp.take - oracle only
    safe = jnp.maximum(tids, 0)
    verts = jnp.take_along_axis(e, safe[..., None], axis=-2)
    # verts: (cx,cy,cz,15,3); group into triangles
    tri = verts.reshape(*verts.shape[:-2], mct.MAX_TRIS, 3, 3)
    valid = (tids.reshape(*tids.shape[:-1], mct.MAX_TRIS, 3)[..., 0] >= 0).astype(
        jnp.float32
    )
    a, b, c = tri[..., 0, :], tri[..., 1, :], tri[..., 2, :]
    cr = jnp.cross(b - a, c - a)
    area = 0.5 * jnp.linalg.norm(cr, axis=-1) * valid
    svol = jnp.einsum("...d,...d->...", a, jnp.cross(b, c)) / 6.0 * valid
    return jnp.sum(svol), jnp.sum(area)


@functools.partial(jax.jit, static_argnames=("chunk_z",))
def _mc_volume_area_jit(vol, iso, spacing, origin, chunk_z):
    nz = vol.shape[2]
    n_cells_z = nz - 1
    cz = min(chunk_z, n_cells_z)
    n_slabs = -(-n_cells_z // cz)
    pad_z = n_slabs * cz + 1 - nz
    volp = jnp.pad(vol, ((0, 0), (0, 0), (0, pad_z)), constant_values=0.0)

    def body(carry, k):
        sv, sa = carry
        slab = jax.lax.dynamic_slice_in_dim(volp, k * cz, cz + 1, axis=2)
        og = jnp.asarray(origin, jnp.float32).at[2].add(
            k * cz * jnp.asarray(spacing, jnp.float32)[2]
        )
        dv, da = _slab_volume_area(slab, iso, spacing, og)
        return (sv + dv, sa + da), None

    (sv, sa), _ = jax.lax.scan(body, (0.0, 0.0), jnp.arange(n_slabs))
    return jnp.abs(sv), sa


@functools.partial(jax.jit, static_argnames=("chunk_z",))
def _mc_slab_partials_jit(vol, iso, spacing, origin, k0, chunk_z):
    n_slabs = (vol.shape[2] - 1) // chunk_z

    def body(carry, k):
        sv, sa = carry
        slab = jax.lax.dynamic_slice_in_dim(vol, k * chunk_z, chunk_z + 1, axis=2)
        og = jnp.asarray(origin, jnp.float32).at[2].add(
            (k + k0) * chunk_z * jnp.asarray(spacing, jnp.float32)[2]
        )
        dv, da = _slab_volume_area(slab, iso, spacing, og)
        return (sv + dv, sa + da), (dv, da)

    _, (dvs, das) = jax.lax.scan(body, (0.0, 0.0), jnp.arange(n_slabs))
    return dvs, das


def mc_slab_partials(vol, iso=0.5, spacing=(1.0, 1.0, 1.0),
                     origin=(0.0, 0.0, 0.0), chunk_z=32, k0=0):
    """Per-slab (signed volume, area) partial sums for one z-window.

    The tiled-extraction building block: the scan body is the SAME
    ``_slab_volume_area`` + origin-advance as :func:`_mc_volume_area_jit`
    (slab shapes identical -- the caller pads the window to a whole
    number of ``chunk_z`` granules plus the closing plane), but the
    per-slab deltas are emitted instead of only the folded carry.  ``k0``
    is the window's first GLOBAL slab index: ``(k + k0)`` is an exact
    int add, so each slab's origin is bit-identical to the one the
    in-core scan computes for that global slab.  The host re-folds the
    collected deltas in global slab order with np.float32 adds (IEEE-754
    single, the same op the in-core carry performs) -- see
    ``core/tiled.py``.
    """
    vol = jnp.asarray(vol, jnp.float32)
    if (vol.shape[2] - 1) % chunk_z:
        raise ValueError(
            f"window depth {vol.shape[2]} is not a whole number of "
            f"chunk_z={chunk_z} slabs plus the closing plane"
        )
    return _mc_slab_partials_jit(
        vol, jnp.float32(iso), jnp.asarray(spacing, jnp.float32),
        jnp.asarray(origin, jnp.float32), jnp.int32(k0), chunk_z
    )


@jax.jit
def tile_vertex_fields(slab, iso, spacing, index_offset):
    """Jitted vertex-field pass for one halo-padded tile sub-window.

    ``index_offset`` is traced (one compile per sub-window shape bucket,
    not per tile position).  Positions land in the full volume's index
    frame -- see :func:`vertex_fields` on why this is bit-exact.
    """
    return vertex_fields(slab, iso, spacing, index_offset=index_offset)


def mc_volume_area(vol, iso=0.5, spacing=(1.0, 1.0, 1.0), origin=(0.0, 0.0, 0.0), chunk_z=32):
    """Mesh volume and surface area of the iso-surface (reference path).

    Pads nothing: callers pad masks by one voxel (as PyRadiomics does) so the
    surface closes.  Volume is the absolute signed-tetrahedron sum; with the
    outward-oriented table the sign is positive already.
    """
    vol = jnp.asarray(vol, jnp.float32)
    iso = jnp.float32(iso)
    spacing = jnp.asarray(spacing, jnp.float32)
    origin = jnp.asarray(origin, jnp.float32)
    return _mc_volume_area_jit(vol, iso, spacing, origin, chunk_z)


# ---------------------------------------------------------------------------
# Vertex compaction: dense per-edge fields -> padded (M,3) vertex list
# ---------------------------------------------------------------------------

_SCAN_BLOCK = 128


def _block_scan(x, op, identity):
    """Exact inclusive scan of a 1-D int32 array by blocks of 128.

    Each block is scanned by a masked reduction over a (128, 128) lower
    triangle, the block totals by recursion.  A ``cumsum`` or an
    ``associative_scan`` of 10^5-10^6 elements takes the TPU compiler
    several seconds; this takes well under one.
    """
    B = _SCAN_BLOCK
    n = x.shape[0]
    nb = -(-n // B)
    xb = jnp.pad(x, (0, nb * B - n), constant_values=identity).reshape(nb, B)
    lower = jnp.arange(B)[:, None] >= jnp.arange(B)[None, :]
    reduce = jnp.sum if op is jnp.add else jnp.max
    out = reduce(jnp.where(lower, xb[:, None, :], identity), axis=2)
    if nb > 1:
        before = _block_scan(out[:, -1], op, identity)
        before = jnp.concatenate([jnp.full((1,), identity, x.dtype), before[:-1]])
        out = op(out, before[:, None])
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("max_vertices",))
def compact_vertices(f: VertexFields, max_vertices: int):
    """Gather active-edge vertices into a padded (max_vertices, 3) array.

    Returns (verts, mask, n_active).  Slot j < min(n_active, max_vertices)
    holds the j-th active vertex in a deterministic order (x-field,
    y-field, z-field, row-major); the slots after them are zero with a
    False mask.  ``n_active`` is the total active count: if it exceeds
    ``max_vertices`` the excess is dropped (callers size the cap from
    ``count_vertices``).

    A stable compaction without a sort.  Every row (last axis) of the
    three active-edge fields is counted; a prefix over the row counts
    gives each row its first output slot.  A scatter of each row's index
    at its first slot, followed by a running max, names the row of every
    output slot, and the slot's column is found in the in-row prefix of
    that row, gathered and multiplied by a 0/1 triangle (exact: sums of
    at most one row of ones).  The work is O(rows) + O(max_vertices x
    row width), against the O(L log L) of an argsort over all L edges.
    """
    cap = max_vertices
    j = jnp.arange(cap, dtype=jnp.int32)
    rows = [a.reshape(-1, a.shape[-1]) for a in (f.ax, f.ay, f.az) if a.size]
    if not rows:
        return jnp.zeros((cap, 3), jnp.float32), j < 0, jnp.int32(0)
    rowc = jnp.concatenate([jnp.sum(a, axis=1, dtype=jnp.int32) for a in rows])
    rowp = _block_scan(rowc, jnp.add, 0)
    n = rowp[-1]
    start = rowp - rowc  # each row's first output slot, nondecreasing
    # a row's index and first slot at that slot: of the rows that share
    # a first slot only the last can be non-empty, and the max keeps it
    first = jnp.stack([jnp.arange(rowc.shape[0], dtype=jnp.int32), start], 1)
    first = jnp.zeros((cap, 2), jnp.int32).at[start].max(
        first, mode="drop", indices_are_sorted=True)
    r = _block_scan(first[:, 0], jnp.maximum, 0)
    k = j - _block_scan(first[:, 1], jnp.maximum, 0)  # rank inside row r
    width = max(a.shape[1] for a in rows)
    table = jnp.concatenate(
        [jnp.pad(a, ((0, 0), (0, width - a.shape[1]))) for a in rows])
    upper = jnp.arange(width)[:, None] <= jnp.arange(width)[None, :]
    inrow = jnp.dot(table[r].astype(jnp.bfloat16), upper.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    col = jnp.sum(inrow <= k[:, None].astype(jnp.float32), axis=1,
                  dtype=jnp.int32)
    order = jnp.zeros_like(j)
    row0 = slot0 = 0
    for a in rows:  # row r, column col -> index into the concatenated fields
        nr, w = a.shape
        here = (r >= row0) & (r < row0 + nr)
        order = jnp.where(here, slot0 + (r - row0) * w + col, order)
        row0 += nr
        slot0 += nr * w
    mask = j < n
    pos = jnp.concatenate([f.vx.reshape(-1, 3), f.vy.reshape(-1, 3), f.vz.reshape(-1, 3)])
    verts = jnp.where(mask[:, None], pos[jnp.where(mask, order, 0)], 0.0)
    return verts, mask, n


def count_vertices(f: VertexFields):
    return (
        jnp.sum(f.ax.astype(jnp.int32))
        + jnp.sum(f.ay.astype(jnp.int32))
        + jnp.sum(f.az.astype(jnp.int32))
    )


# ---------------------------------------------------------------------------
# Diameters: max pairwise distances (3D + three coordinate-plane projections)
# ---------------------------------------------------------------------------

NEG = jnp.float32(-1e30)


@functools.partial(jax.jit, static_argnames=("row_block",))
def max_diameters_sq(verts, mask, row_block=128):
    """Maximum squared pairwise distances over valid vertex pairs.

    Returns (4,) float32: [3D, xy-plane, xz-plane, yz-plane] squared maxima.
    Blocked over rows so memory is O(row_block * M).

    Masking trick (big CPU speedup): every *invalid* vertex is replaced by
    the first valid vertex before the pair sweep.  A duplicated point can
    never increase the maximum pairwise distance, so the sweep needs no
    per-pair mask/where at all -- the inner loop is pure sub/mul/add/max,
    SoA over axes, which XLA fuses into one vectorised pass.
    """
    verts = jnp.asarray(verts, jnp.float32)
    m = jnp.asarray(mask).astype(bool)
    M = verts.shape[0]
    R = min(row_block, M)
    nb = -(-M // R)
    pad = nb * R - M

    v0 = verts[jnp.argmax(m)]  # first valid vertex (callers reject empty)
    vfill = jnp.where(m[:, None], verts, v0[None, :])
    # centre to keep f32 magnitudes small (cancellation control)
    centre = 0.5 * (jnp.min(vfill, axis=0) + jnp.max(vfill, axis=0))
    vfill = vfill - centre
    # pad rows duplicate the last vertex -- duplicates cannot raise the max
    vp = jnp.pad(vfill, ((0, pad), (0, 0)), mode="edge") if pad else vfill
    cx, cy, cz = vp[:, 0], vp[:, 1], vp[:, 2]  # SoA (M,)

    def body(best, i):
        rows = jax.lax.dynamic_slice_in_dim(vp, i * R, R, axis=0)
        dx = rows[:, 0][:, None] - cx[None, :]
        dy = rows[:, 1][:, None] - cy[None, :]
        dz = rows[:, 2][:, None] - cz[None, :]
        qx, qy, qz = dx * dx, dy * dy, dz * dz
        qxy = qx + qy
        m3 = jnp.max(qxy + qz)
        mxy = jnp.max(qxy)
        mxz = jnp.max(qx + qz)
        myz = jnp.max(qy + qz)
        return jnp.maximum(best, jnp.stack([m3, mxy, mxz, myz])), None

    best, _ = jax.lax.scan(body, jnp.full((4,), NEG), jnp.arange(nb))
    return jnp.maximum(best, 0.0)


def max_diameters(verts, mask, row_block=128):
    """(4,) float32 diameters: [max 3D, xy(Slice), xz(Row), yz(Column)]."""
    return jnp.sqrt(max_diameters_sq(verts, mask, row_block=row_block))


# ---------------------------------------------------------------------------
# intensity-family helpers (first-order / GLCM): shared quantization contract
# ---------------------------------------------------------------------------

def intensity_range(image, mask):
    """Masked intensity ``(lo, hi)`` -- order-invariant (pure min/max).

    Min/max are exact under any reduction order, so every backend computes
    bit-identical ranges (and therefore bit-identical bin edges) without a
    canonical-order contract.  An empty mask yields ``(0, 0)``.
    """
    img = jnp.asarray(image, jnp.float32)
    m = jnp.asarray(mask) > 0
    any_ = jnp.any(m)
    lo = jnp.where(any_, jnp.min(jnp.where(m, img, jnp.inf)), 0.0)
    hi = jnp.where(any_, jnp.max(jnp.where(m, img, -jnp.inf)), 0.0)
    return lo, hi


def quantize_intensity(image, mask, lo, hi, n_bins: int):
    """Fixed-bin-count discretization: f32 bin ids in ``[0, n_bins)``.

    Returns ``(q, width)`` where ``q`` is float32 (one-hot comparisons in
    the kernels stay in the native MXU dtype) and masked-out voxels are
    forced to bin 0.  A degenerate range (constant intensity, empty mask)
    has ``width == 0`` and every voxel in bin 0.  Purely elementwise, so
    ``lo``/``hi`` may be scalars or broadcastable per-case columns.
    """
    img = jnp.asarray(image, jnp.float32)
    width = (hi - lo) / n_bins
    safe = jnp.where(width > 0, width, 1.0)
    q = jnp.clip(jnp.floor((img - lo) / safe), 0.0, float(n_bins - 1))
    return jnp.where(jnp.asarray(mask) > 0, q, 0.0), width
