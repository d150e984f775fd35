"""Pallas TPU kernel: fused Marching Cubes volume + surface area.

PyRadiomics-cuda's first kernel walks every voxel with one CUDA thread,
emitting triangles and atomically accumulating mesh volume and surface area.
The TPU adaptation:

* the volume is laid out by XLA as **brick-major corner planes**: for each
  (BX, BY, BZ) brick, an (8, BX*BY*BZ) block whose row ``c`` is corner
  ``c`` of every cell (the analogue of staging tiles in CUDA shared
  memory).  The kernel then works on lane-dense (rows, cells) tiles with
  no gather, relayout or unaligned slice -- what Mosaic can lower -- at
  the price of 8 floats of HBM traffic per cell;
* the per-voxel triangle-table *gather* (which TPUs dislike) becomes a
  **one-hot matmul on the MXU**: ``TABLE @ onehot(cube_index, 256)`` --
  data-dependent lookup expressed as dense systolic compute (0/1 and small
  integers, exact in bf16);
* CUDA atomic accumulation becomes per-brick partial sums written to their
  own output cells and reduced outside in a fixed pairwise order
  (deterministic, Megacore-safe);
* triangle *vertices* are not appended to a global list at all: the
  deduplicated vertex field is a dense per-grid-edge structure computed in a
  single fused elementwise XLA pass (see ``kernels/ref.vertex_fields``) --
  on TPU a dense masked write beats an atomic append.

Signed tetrahedron volumes are accumulated against the volume centre to keep
f32 cancellation error small; the global sum is origin-independent because
the generated MC table yields closed, consistently oriented meshes (property-
tested in tests/test_mc_tables.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mc_tables as mct

_GROUP = 8  # sublane rows per triangle-vertex group (MAX_TRIS <= 8)


def _grouped_table() -> np.ndarray:
    """(3 * 8, 256) transposed triangle table, grouped by triangle vertex.

    Row ``g * 8 + t`` holds the edge id of vertex ``g`` (a, b, c) of
    triangle ``t`` for every cube case, -1 where the case has no such
    triangle.  ``table @ onehot(case)`` then yields the a, b and c edge ids
    as three sublane-aligned (8, cells) row groups.
    """
    tri = np.asarray(mct.TRI_TABLE).reshape(256, mct.MAX_TRIS, 3)
    out = np.full((3 * _GROUP, 256), -1.0, np.float32)
    for g in range(3):
        out[g * _GROUP:g * _GROUP + mct.MAX_TRIS] = tri[:, :, g].T
    return out


_TABLE = _grouped_table()


def _corner_index(off) -> int:
    return int(np.flatnonzero((np.asarray(mct.CORNERS) == off).all(axis=1))[0])


# per edge: (axis, anchor offset, lower corner, upper corner) -- the edge
# runs from its anchor grid point one cell along ``axis``
_EDGES = tuple(
    (
        int(ax),
        tuple(int(o) for o in off),
        _corner_index(off),
        _corner_index(np.asarray(off) + np.eye(3, dtype=np.int32)[ax]),
    )
    for ax, off in zip(mct.EDGE_CELL_AXIS, mct.EDGE_CELL_OFFSET)
)


def _cell_coords(block) -> np.ndarray:
    """(8, cells) brick-local cell coordinates: rows 0-2 = (ix, iy, iz).

    Cells are numbered x-major (``ix * by * bz + iy * bz + iz``), the
    order :func:`_corner_bricks` lays them out in.
    """
    bx, by, cz = block
    g = np.indices((bx, by, cz), dtype=np.float32).reshape(3, -1)
    return np.concatenate([g, np.zeros((5, g.shape[1]), np.float32)])


def _mc_kernel(scal, table_ref, cell_ref, corner_ref, out, *, chunk,
               block, z_scal=False):
    """One brick: fused table lookup (MXU one-hot matmul) + vol/area sums.

    ``corner_ref`` holds the brick's cells lane-major: row ``c`` is corner
    ``c``'s value for every cell.  Each chunk of cells is processed as
    (rows, chunk) tiles with no gather, relayout or 3-D array: cube index
    and edge interpolation are row arithmetic, the triangle table lookup is
    one (24, 256) x (256, chunk) 0/1 matmul, and the edge-vertex select is
    a 12-way masked merge on the VPU.

    With ``z_scal`` (the tiled entry) ``scal`` carries an 8th element:
    the window's global z offset in cells, added to the brick-local z
    base.  Both are integer-valued f32 < 2^24, so the add is exact and
    the brick computes with the SAME coordinates as the in-core grid.
    The brick's (signed volume, area) pair goes to lanes 0 and 1 of row
    ``k`` of its (i, j) column's output block.
    """
    iso = scal[0]
    spacing = (scal[1], scal[2], scal[3])
    origin = (scal[4], scal[5], scal[6])
    bx, by, cz = block
    cells = bx * by * cz

    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    base0 = (
        (i * bx).astype(jnp.float32),
        (j * by).astype(jnp.float32),
        (k * cz).astype(jnp.float32),
    )
    if z_scal:
        base0 = (base0[0], base0[1], base0[2] + scal[7])
    table = table_ref[...].astype(jnp.bfloat16)  # exact: ids in [-1, 11]

    def interp(v0, v1):
        den = v1 - v0
        den = jnp.where(jnp.abs(den) < 1e-30, 1.0, den)
        return jnp.clip((iso - v0) / den, 0.0, 1.0)

    def chunk_body(c0, acc):
        sv, sa = acc
        start = pl.multiple_of(c0 * chunk, chunk)
        cv = corner_ref[0, 0, 0, :, pl.ds(start, chunk)]  # (8, chunk)
        cc = cell_ref[:, pl.ds(start, chunk)]  # (8, chunk)
        corner = [cv[c:c + 1] for c in range(8)]
        idx = jnp.zeros((1, chunk), jnp.int32)
        for c in range(8):
            idx = idx + ((corner[c] > iso).astype(jnp.int32) << c)
        # --- one-hot matmul gather (MXU): 0/1 x small ints, exact ---
        cases = jax.lax.broadcasted_iota(jnp.int32, (256, chunk), 0)
        onehot = (cases == idx).astype(jnp.bfloat16)
        ids = jax.lax.dot_general(
            table, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (24, chunk) edge ids, -1 = no triangle
        # cell origin in grid units (exact small integers)
        base = [base0[d] + cc[d:d + 1] for d in range(3)]
        vx = jnp.zeros(ids.shape, jnp.float32)
        vy = jnp.zeros(ids.shape, jnp.float32)
        vz = jnp.zeros(ids.shape, jnp.float32)
        for e, (ax, off, lo, hi) in enumerate(_EDGES):
            t = interp(corner[lo], corner[hi])
            pos = []
            for d in range(3):
                g = base[d] + float(off[d])
                if d == ax:
                    g = g + t
                pos.append(g * spacing[d] + origin[d])
            hit = ids == float(e)
            vx = jnp.where(hit, pos[0], vx)
            vy = jnp.where(hit, pos[1], vy)
            vz = jnp.where(hit, pos[2], vz)
        a = (vx[0:8], vy[0:8], vz[0:8])
        b = (vx[8:16], vy[8:16], vz[8:16])
        c = (vx[16:24], vy[16:24], vz[16:24])
        valid = (ids[0:8] >= 0.0).astype(jnp.float32)
        ab = [b[d] - a[d] for d in range(3)]
        ac = [c[d] - a[d] for d in range(3)]
        cr = (ab[1] * ac[2] - ab[2] * ac[1],
              ab[2] * ac[0] - ab[0] * ac[2],
              ab[0] * ac[1] - ab[1] * ac[0])
        bc = (b[1] * c[2] - b[2] * c[1],
              b[2] * c[0] - b[0] * c[2],
              b[0] * c[1] - b[1] * c[0])
        area = 0.5 * jnp.sqrt(cr[0] * cr[0] + cr[1] * cr[1] + cr[2] * cr[2]
                              + 1e-30) * valid
        svol = (a[0] * bc[0] + a[1] * bc[1] + a[2] * bc[2]) / 6.0 * valid
        return sv + svol, sa + area

    zero = jnp.zeros((_GROUP, chunk), jnp.float32)
    sv, sa = jax.lax.fori_loop(0, cells // chunk, chunk_body, (zero, zero))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    row = jnp.where(lane == 0, jnp.sum(sv), jnp.where(lane == 1, jnp.sum(sa),
                                                      0.0))
    out[0, 0, pl.ds(k, 1), :] = row


def normalize_chunk(block, chunk: int) -> int:
    """Clamp ``chunk`` to a valid in-kernel chunk length for ``block``.

    The kernel slices each brick's ``bx*by*bz`` cells into equal chunks, so
    a valid chunk divides the cell count; oversized chunks clamp to it.
    Shared by the kernel entry point, the autotune sweep's candidate
    enumeration and its cache-record validation (``runtime.autotune``).

    Raises ``ValueError`` when no clamp can make ``chunk`` valid.
    """
    bx, by, cz = block
    cells = bx * by * cz
    if cells % chunk:
        chunk = min(chunk, cells)
        if cells % chunk:
            raise ValueError(f"chunk {chunk} must divide cells/brick {cells}")
    return chunk


def _corner_bricks(vol, bx, by, cz):
    """Brick-major corner planes: (nbx, nby, nbz, 8, BX*BY*CZ).

    Entry ``[i, j, k, c, l]`` is corner ``c`` (``mct.CORNERS``) of cell
    ``l`` (x-major within the brick) of brick ``(i, j, k)``: the volume
    zero-padded to whole bricks plus the closing plane, read at the eight
    corner shifts.  Built by XLA once per call; the kernel then reads each
    brick as one lane-dense (8, cells) block.
    """
    nx, ny, nz = vol.shape
    nbx = max(1, -(-(nx - 1) // bx))
    nby = max(1, -(-(ny - 1) // by))
    nbz = max(1, -(-(nz - 1) // cz))
    X, Y, Z = nbx * bx, nby * by, nbz * cz
    volp = jnp.pad(
        vol, ((0, X + 1 - nx), (0, Y + 1 - ny), (0, Z + 1 - nz)),
        constant_values=0.0,
    )
    planes = []
    for dx, dy, dz in np.asarray(mct.CORNERS):
        c = volp[dx:dx + X, dy:dy + Y, dz:dz + Z]
        c = c.reshape(nbx, bx, nby, by, nbz, cz).transpose(0, 2, 4, 1, 3, 5)
        planes.append(c.reshape(nbx, nby, nbz, bx * by * cz))
    return jnp.stack(planes, axis=3), (nbx, nby, nbz)


def _brick_partials(vol, scal, block, chunk, interpret, z_scal):
    """Run the brick kernel: per-brick (signed volume, area) partials.

    Returns two (nbx, nby, nbz) arrays.  Each (i, j) brick column owns one
    (nbz, 128) output block -- a block spanning the array's last two dims,
    as Mosaic requires -- revisited along the sequential k sweep.
    """
    bx, by, cz = block
    cells = bx * by * cz
    chunk = normalize_chunk(block, chunk)
    corners, (nbx, nby, nbz) = _corner_bricks(vol, bx, by, cz)
    out = pl.pallas_call(
        functools.partial(_mc_kernel, chunk=chunk, block=tuple(block),
                          z_scal=z_scal),
        grid=(nbx, nby, nbz),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((3 * _GROUP, 256), lambda i, j, k: (0, 0)),
            pl.BlockSpec((8, cells), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1, 1, 8, cells),
                         lambda i, j, k: (i, j, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, nbz, 128),
                               lambda i, j, k: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nbx, nby, nbz, 128), jnp.float32),
        interpret=interpret,
    )(scal, jnp.asarray(_TABLE), jnp.asarray(_cell_coords(block)), corners)
    return out[..., 0], out[..., 1]


@functools.partial(
    jax.jit, static_argnames=("block", "chunk", "interpret")
)
def mc_volume_area_pallas(
    vol,
    iso=0.5,
    spacing=(1.0, 1.0, 1.0),
    *,
    block=(8, 8, 8),
    chunk=512,
    interpret=False,
):
    """Mesh volume + surface area via the fused Pallas MC kernel.

    Matches ``kernels.ref.mc_volume_area`` (same table, same interpolation).
    """
    vol = jnp.asarray(vol, jnp.float32)

    # centre the coordinate origin to minimise f32 cancellation
    nx, ny, nz = vol.shape
    sp = jnp.asarray(spacing, jnp.float32)
    origin = -0.5 * jnp.asarray([nx, ny, nz], jnp.float32) * sp
    scal = jnp.concatenate([jnp.asarray([iso], jnp.float32), sp, origin])

    vol_p, area_p = _brick_partials(vol, scal, block, chunk, interpret,
                                    z_scal=False)
    return mc_partials_finalize(vol_p, area_p)


@functools.partial(
    jax.jit, static_argnames=("full_shape", "block", "chunk", "interpret")
)
def mc_brick_partials_pallas(
    slab,
    iso=0.5,
    spacing=(1.0, 1.0, 1.0),
    *,
    full_shape,
    z_cell_offset=0.0,
    block=(8, 8, 8),
    chunk=512,
    interpret=False,
):
    """Per-brick (signed volume, area) partials for one z-window of a volume.

    The tiled-extraction entry: runs the SAME brick kernel as
    :func:`mc_volume_area_pallas` over a window of ``z_cell_offset``-shifted
    bricks, with the coordinate origin computed from ``full_shape`` (the
    whole volume's centred origin), and returns the per-brick partial
    arrays UNREDUCED.  The caller assembles the windows' partials into
    the full (nbx, nby, nbz) brick grid -- zeros for windows that were
    pruned away (a skipped empty brick contributes exactly +0.0) -- and
    reduces once via :func:`mc_partials_finalize`, reproducing the
    in-core reduction shape bit-for-bit.  ``z_cell_offset`` is traced
    (f32, exact small integer): tiles at different depths share one
    compiled kernel.

    The window must span whole bricks: ``slab.shape[2] == k*cz + 1``.
    """
    slab = jnp.asarray(slab, jnp.float32)
    sp = jnp.asarray(spacing, jnp.float32)
    origin = -0.5 * jnp.asarray(list(full_shape), jnp.float32) * sp
    scal = jnp.concatenate([
        jnp.asarray([iso], jnp.float32), sp, origin,
        jnp.asarray([z_cell_offset], jnp.float32),
    ])
    return _brick_partials(slab, scal, block, chunk, interpret, z_scal=True)


def _tree_sum(x):
    """Sum by pairwise halving over the flattened array (zero-padded).

    Each level is one elementwise add, which XLA neither re-associates nor
    vectorises differently from one program to the next -- unlike a
    ``jnp.sum``, whose accumulation order depends on what it is fused with.
    """
    x = x.reshape(-1)
    n = 1 << max(0, (x.size - 1).bit_length())
    x = jnp.pad(x, (0, n - x.size))
    while x.size > 1:
        x = x[:x.size // 2] + x[x.size // 2:]
    return x[0]


@jax.jit
def mc_partials_finalize(vol_p, area_p):
    """Reduce assembled full-grid brick partials: (|sum vol|, sum area).

    The same two reductions :func:`mc_volume_area_pallas` ends with, over
    an array of the same (nbx, nby, nbz) shape.  The summation order is
    fixed by the brick grid alone (:func:`_tree_sum`), so assembling tile
    partials into the full grid first keeps the result bit-identical to
    the in-core pass, whichever program the reduction is compiled into.
    """
    return jnp.abs(_tree_sum(vol_p)), _tree_sum(area_p)


@functools.partial(
    jax.jit, static_argnames=("block", "chunk", "interpret")
)
def mc_volume_area_batch_pallas(
    vols,
    iso=0.5,
    spacings=None,
    *,
    block=(8, 8, 8),
    chunk=512,
    interpret=False,
):
    """Device-stack MC: ``(B, nx, ny, nz)`` masks -> ``(B, 2)`` [vol, area].

    The batched entry point of the device-resident pass-2a data plane:
    the executor stages bucket-padded masks into a device pool and feeds
    stacked slices straight here -- no host re-stacking per chunk.  Cases
    are mapped sequentially per device (``lax.map``; the brick grid of a
    single case already saturates a chip) with per-case physical spacing
    ``spacings``: ``(B, 3)``.
    """
    vols = jnp.asarray(vols, jnp.float32)
    if spacings is None:
        spacings = jnp.ones((vols.shape[0], 3), jnp.float32)

    def one(args):
        vol, sp = args
        v, a = mc_volume_area_pallas(
            vol, iso, sp, block=block, chunk=chunk, interpret=interpret
        )
        return jnp.stack([v, a])

    return jax.lax.map(one, (vols, jnp.asarray(spacings, jnp.float32)))


def flop_estimate(shape, block=(8, 8, 8), chunk=512) -> float:
    """Structural FLOP count: dominated by the one-hot MXU matmul."""
    nx, ny, nz = shape
    bx, by, cz = block
    nbricks = (-(-(nx - 1) // bx)) * (-(-(ny - 1) // by)) * (-(-(nz - 1) // cz))
    cells = bx * by * cz
    rows = 3 * _GROUP  # the (24, chunk) edge-id tile
    # one-hot table matmul + 12-way compare/select of 3 coordinates +
    # the per-triangle cross products
    per_cell = 2 * 256 * rows + 12 * 4 * rows + _GROUP * 35
    return float(nbricks) * cells * per_cell


# Device temporaries one brick-kernel call holds, per padded cell: the
# (8, cells) corner planes (32 B) and the copies XLA's TPU layouts make of
# the brick transposes that build them.  A bound on the v5e compiler's
# ``memory_analysis()`` (up to 108 B at the shapes
# tests/test_chip_compile.py compiles), not a published figure.
WORK_BYTES_PER_CELL = 128
# a brick whose edges are multiples of every autotune candidate's, so
# its padding bounds theirs (runtime/autotune.DEFAULT_MC_BLOCKS)
_BOUND_BLOCK = (16, 16, 16)


def work_bytes(shape, block=_BOUND_BLOCK) -> int:
    """Device temporaries of one brick-kernel call on a ``shape`` volume.

    Scales with the cells padded to whole bricks (as in
    ``_corner_bricks``); the default block bounds every autotune
    candidate's padding.  Callers that budget device memory (the stream
    window, the tiled engine) add it to their staged bytes: a batch runs
    its cases one at a time (``lax.map``), so one call's temporaries are
    alive at once.
    """
    cells = 1
    for n, b in zip(shape, block):
        cells *= max(1, -(-(n - 1) // b)) * b
    return WORK_BYTES_PER_CELL * cells
