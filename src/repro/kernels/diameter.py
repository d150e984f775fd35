"""Pallas TPU kernel: maximum pairwise vertex distances (3D + 3 planes).

This is the PyRadiomics-cuda hot spot: 95.7%-99.9% of shape-feature time is
spent finding the farthest vertex pair (paper Table 2).  The CUDA version
assigns vertex-pair subsets to threads with per-thread max accumulators and a
final reduction; on TPU we tile the O(M^2) pair space into (B x B) VMEM
blocks walked by the Pallas grid.

Per block-pair (I, J):
    q_a[i, j] = (a_i - a_j)^2          per axis a in {x, y, z}   (VPU)
    d3  = qx + qy + qz                  max 3D diameter
    dxy = qx + qy                       'Slice'  plane (ignore z)
    dxz = qx + qz                       'Row'    plane (ignore y)
    dyz = qy + qz                       'Column' plane (ignore x)
masked by valid_i * valid_j, max-reduced into per-block partials (or an
in-kernel accumulator -- see variants).

Optimization variants (the TPU analogue of the paper's Fig. 1 study):
    'naive'  : one pass per combo (4 separate kernel launches), full grid.
    'fused'  : all 4 combos in one pass, full grid; one partial-maximum
               tile per row block, revisited across the j sweep and
               reduced outside.                                [mem-access opt]
    'tri'    : fused + predicated skip of lower-triangle blocks (j < i).
               DMA still runs; compute is skipped.            [load balance]
    'seqacc' : fused + triangular + single in-kernel accumulator block that
               is revisited across the sequential TPU grid -- the analogue of
               the paper's per-thread local accumulators (vs. the partial-
               output blocks, which are its 'block-based reduction').
    'tri_prefetch': fused + a grid over only the nb*(nb+1)/2 upper-
               triangle block pairs (:func:`tri_grid`), with (i, j) derived
               from the grid point in the index maps (:func:`tri_index`) so
               skipped blocks cost neither DMA nor compute -- the TPU-native
               version of CUDA early-exit load balancing.  (The name is
               kept from an earlier scalar-prefetched schedule table, which
               grew as nb^2 and overflowed SMEM at nb >= 512.)
    'nomask' : tri_prefetch without the mask streams: invalid slots are
               pre-filled with the first valid vertex, so the mask DMA and
               the per-pair select disappear.
    'gram'   : the triangular schedule, but the per-tile pair distances are
               computed on the MXU via the (augmented) Gram identity
                   |r_i - c_j|^2 = |r_i|^2 + |c_j|^2 - 2 <r_i, c_j>
               realised per axis as [r^2, 1, -2r] @ [1, c^2, c]^T -- the
               rank-1 cross term and both norm terms ride in one per-axis
               (B,3)x(3,B) product, batched over the 3 axes into a single
               ``dot_general``.  The per-axis products stay separate, so
               all 4 combos (3D/xy/xz/yz) are served from the same 3 MXU
               products; the VPU only does combo adds + select + max, not
               the subtract-square sweep.

Exact candidate pruning (``repro.kernels.prune``) can shrink M -> M' before
any variant runs; the result is guaranteed identical (the farthest pair per
combo always survives).  ``repro.runtime.autotune`` sweeps (variant, block)
per vertex bucket and caches the measured winner.

Coordinates are stored SoA as (3, M) (the paper's '1D arrays' layout): the
lane dimension is the vertex index, so loads are contiguous 128-lane vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG = np.float32(-1e30)
VARIANTS = ("naive", "fused", "tri", "seqacc", "tri_prefetch", "nomask", "gram")


def _pairwise_combos(rows, cols, rmask, cmask, combos):
    """(len(combos),) partial maxima for one (B, B) tile."""
    qs = []
    for a in range(3):
        d = rows[a][:, None] - cols[a][None, :]
        qs.append(d * d)
    valid = (rmask[0][:, None] > 0.0) & (cmask[0][None, :] > 0.0)
    outs = []
    for combo in combos:
        s = functools.reduce(lambda x, y: x + y, [qs[a] for a in combo])
        s = jnp.where(valid, s, NEG)
        outs.append(jnp.max(s))
    return jnp.stack(outs)


_ALL_COMBOS = ((0, 1, 2), (0, 1), (0, 2), (1, 2))  # 3D, xy, xz, yz


def _pairwise_combos_gram(rows, cols, rmask, cmask, combos):
    """(len(combos),) tile maxima via the augmented Gram identity (MXU).

    Per axis a, the whole (B, B) squared-difference matrix is ONE K=3
    matrix product: with l = [r^2, 1, -2r] (B, 3) and m = [1, c^2, c]^T
    (3, B),

        (l @ m)[i, j] = r_i^2 + c_j^2 - 2 r_i c_j = (r_i - c_j)^2,

    i.e. the norm terms of |r|^2 + |c|^2 - 2<r, c> ride in the same
    per-axis (B,3)x(3,B) ``dot_general`` as the rank-1 cross term.  The
    three axis products are batched into a single call and kept separate,
    so all 4 combos (3D/xy/xz/yz) are served from the same 3 MXU products;
    the VPU only does the per-combo adds + select + max, not the
    subtract-square sweep.  ``Precision.HIGHEST`` keeps the products in
    f32 on the MXU (its default would round the operands to bf16).
    """
    ones = jnp.ones_like(rows)
    lhs = jnp.stack([rows * rows, ones, -2.0 * rows], axis=-1)  # (3, B, 3)
    rhs = jnp.stack([ones, cols * cols, cols], axis=1)  # (3, 3, B)
    q = jax.lax.dot_general(
        lhs,
        rhs,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (3, B, B): per-axis squared differences
    valid = (rmask[0][:, None] > 0.0) & (cmask[0][None, :] > 0.0)
    outs = []
    for combo in combos:
        s = functools.reduce(lambda x, y: x + y, [q[a] for a in combo])
        s = jnp.where(valid, s, NEG)
        outs.append(jnp.max(s))
    return jnp.stack(outs)


def _row_tile(part, nc):
    """Place the (nc,) partial maxima in lanes 0..nc-1 of an (8, 128) tile.

    The per-row-block partial output of 'fused'/'tri' is one native
    (8, 128) f32 tile per row block; the other lanes hold NEG.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    tile = jnp.full((8, 128), NEG, jnp.float32)
    for c in range(nc):
        tile = jnp.where(lane == c, part[c], tile)
    return tile


def _kernel_partial(vr, mr, vc, mc, out, *, combos, triangular):
    """One running partial per row block i, revisited across the j sweep."""
    i, j = pl.program_id(0), pl.program_id(1)
    nc = len(combos)

    @pl.when(j == 0)
    def _():
        out[0] = jnp.full((8, 128), NEG, jnp.float32)

    def update():
        part = _pairwise_combos(vr[:], vc[:], mr[:], mc[:], combos)
        out[0] = jnp.maximum(out[0], _row_tile(part, nc))

    if triangular:
        pl.when(j >= i)(update)
    else:
        update()


def _kernel_seqacc(vr, mr, vc, mc, out, *, combos):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():
        out[0, :] = jnp.full((len(combos),), NEG)

    @pl.when(j >= i)
    def _():
        part = _pairwise_combos(vr[:], vc[:], mr[:], mc[:], combos)
        out[0, :] = jnp.maximum(out[0, :], part)


def tri_grid(nb: int) -> tuple[int, int]:
    """Grid of the folded upper-triangle schedule over ``nb`` blocks.

    Row block ``a`` (of the first ``ceil(nb/2)``) is paired with row block
    ``nb-1-a``: together they hold ``nb + 1`` upper-triangle tiles, so the
    ``nb*(nb+1)/2`` tiles fill a ``(ceil(nb/2), nb+1)`` rectangle.  For odd
    ``nb`` the middle row is paired with itself and its tiles run twice,
    which a max-reduction absorbs exactly.
    """
    return (nb + 1) // 2, nb + 1


def tri_index(a, c, nb: int):
    """(i, j) tile of the folded schedule at grid point ``(a, c)``.

    Integer scalar arithmetic, evaluated in the BlockSpec index maps: the
    schedule needs no prefetched table, so its SMEM footprint does not
    grow with ``nb``.
    """
    first = c < nb - a
    i = jnp.where(first, a, nb - 1 - a)
    j = jnp.where(first, a + c, c - 1)
    return i, j


def _kernel_tri(vr, mr, vc, mc, out, *, combos, tile_fn):
    @pl.when(jnp.logical_and(pl.program_id(0) == 0, pl.program_id(1) == 0))
    def _():
        out[0, :] = jnp.full((len(combos),), NEG)

    part = tile_fn(vr[:], vc[:], mr[:], mc[:], combos)
    out[0, :] = jnp.maximum(out[0, :], part)


def _combos_nomask(rows, cols, combos):
    """Mask-free tile maxima: inputs are pre-filled so every slot is valid."""
    qs = []
    for a in range(3):
        d = rows[a][:, None] - cols[a][None, :]
        qs.append(d * d)
    outs = []
    for combo in combos:
        s = functools.reduce(lambda x, y: x + y, [qs[a] for a in combo])
        outs.append(jnp.max(s))
    return jnp.stack(outs)


def _kernel_nomask(vr, vc, out, *, combos):
    """Beyond-paper variant (§Perf/3): the folded triangular schedule with
    NO mask streams.  Invalid slots were pre-filled with the first valid
    vertex (a duplicated point can never raise the max), so the mask DMA
    (2 of 8 input streams) and the per-pair select disappear."""
    @pl.when(jnp.logical_and(pl.program_id(0) == 0, pl.program_id(1) == 0))
    def _():
        out[0, :] = jnp.full((len(combos),), NEG)

    part = _combos_nomask(vr[:], vc[:], combos)
    out[0, :] = jnp.maximum(out[0, :], part)


def _pad_inputs(verts, mask, block):
    """SoA-transpose and pad to a block multiple; padding is invalid."""
    verts = jnp.asarray(verts, jnp.float32)
    mask = jnp.asarray(mask).astype(jnp.float32)
    M = verts.shape[0]
    nb = max(1, -(-M // block))
    pad = nb * block - M
    v = jnp.pad(verts, ((0, pad), (0, 0))).T  # (3, nb*B)
    m = jnp.pad(mask, (0, pad))[None, :]  # (1, nb*B)
    return v, m, nb


@functools.partial(
    jax.jit, static_argnames=("block", "variant", "interpret", "combos")
)
def max_diameters_sq_pallas(
    verts,
    mask,
    *,
    block: int = 256,
    variant: str = "fused",
    interpret: bool = False,
    combos=_ALL_COMBOS,
):
    """Maximum squared pairwise distances, Pallas TPU kernel.

    Returns (len(combos),) float32 squared maxima, default
    [3D, xy(Slice), xz(Row), yz(Column)].
    """
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    if variant == "naive":
        outs = [
            max_diameters_sq_pallas(
                verts, mask, block=block, variant="fused",
                interpret=interpret, combos=(c,),
            )
            for c in combos
        ]
        return jnp.concatenate(outs)

    v, m, nb = _pad_inputs(verts, mask, block)
    nc = len(combos)
    acc_spec = pl.BlockSpec((1, nc), lambda *_: (0, 0))
    acc_shape = jax.ShapeDtypeStruct((1, nc), jnp.float32)

    if variant in ("tri_prefetch", "nomask", "gram"):
        # folded triangular schedule: only upper-triangle tiles are visited
        def rows(a, c):
            return (0, tri_index(a, c, nb)[0])

        def cols(a, c):
            return (0, tri_index(a, c, nb)[1])

        if variant == "nomask":
            # pre-fill invalid slots with the first valid vertex; padding
            # from _pad_inputs is masked-out, so it is filled too
            first = jnp.argmax(m[0] > 0.0)
            v = jnp.where(m > 0.0, v, v[:, first][:, None])
            kernel = functools.partial(_kernel_nomask, combos=combos)
            in_specs = [pl.BlockSpec((3, block), rows),
                        pl.BlockSpec((3, block), cols)]
            args = (v, v)
        else:
            tile_fn = (_pairwise_combos_gram if variant == "gram"
                       else _pairwise_combos)
            kernel = functools.partial(_kernel_tri, combos=combos,
                                       tile_fn=tile_fn)
            in_specs = [pl.BlockSpec((3, block), rows),
                        pl.BlockSpec((1, block), rows),
                        pl.BlockSpec((3, block), cols),
                        pl.BlockSpec((1, block), cols)]
            args = (v, m, v, m)
        out = pl.pallas_call(
            kernel,
            grid=tri_grid(nb),
            in_specs=in_specs,
            out_specs=acc_spec,
            out_shape=acc_shape,
            interpret=interpret,
        )(*args)
        return jnp.maximum(out[0], 0.0)

    row_spec = pl.BlockSpec((3, block), lambda i, j: (0, i))
    col_spec = pl.BlockSpec((3, block), lambda i, j: (0, j))
    rmask_spec = pl.BlockSpec((1, block), lambda i, j: (0, i))
    cmask_spec = pl.BlockSpec((1, block), lambda i, j: (0, j))

    if variant in ("fused", "tri"):
        out = pl.pallas_call(
            functools.partial(
                _kernel_partial, combos=combos, triangular=(variant == "tri")
            ),
            grid=(nb, nb),
            in_specs=[row_spec, rmask_spec, col_spec, cmask_spec],
            out_specs=pl.BlockSpec((1, 8, 128), lambda i, j: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((nb, 8, 128), jnp.float32),
            interpret=interpret,
        )(v, m, v, m)
        best = jnp.max(out[:, 0, :nc], axis=0)
    else:  # seqacc
        out = pl.pallas_call(
            functools.partial(_kernel_seqacc, combos=combos),
            grid=(nb, nb),
            in_specs=[row_spec, rmask_spec, col_spec, cmask_spec],
            out_specs=acc_spec,
            out_shape=acc_shape,
            interpret=interpret,
        )(v, m, v, m)
        best = out[0]
    return jnp.maximum(best, 0.0)


def max_diameters_pallas(verts, mask, **kw):
    """(4,) float32 diameters [3D, Slice(xy), Row(xz), Column(yz)]."""
    return jnp.sqrt(max_diameters_sq_pallas(verts, mask, **kw))


def flop_estimate(M: int, block: int, variant: str) -> float:
    """Structural VPU cost model used by the §Perf iteration log.

    For 'gram' this counts only the vector-unit work (combo assembly, mask
    select, max-reduce); the subtract-square sweep moved to the matrix unit
    and is reported separately by :func:`mxu_flop_estimate`.
    """
    nb = -(-M // block)
    if variant in ("naive",):
        tiles = nb * nb * 4
        per_tile = block * block * (3 * 2 + 3 + 2)
    elif variant == "fused":
        tiles = nb * nb
        per_tile = block * block * (3 * 2 + 5 + 1 + 4 + 4)
    elif variant == "nomask":  # no valid-mask compare/select per combo
        tiles = nb * (nb + 1) // 2
        per_tile = block * block * (3 * 2 + 5 + 4)
    elif variant == "gram":  # per-pair: combo adds + select + max only
        tiles = nb * (nb + 1) // 2
        per_tile = block * block * (5 + 4 + 4)
    else:  # tri / seqacc / tri_prefetch
        tiles = nb * (nb + 1) // 2
        per_tile = block * block * (3 * 2 + 5 + 1 + 4 + 4)
    return float(tiles) * per_tile


def mxu_flop_estimate(M: int, block: int, variant: str) -> float:
    """Matrix-unit FLOPs: 3 axis-batched K=3 (B,3)x(3,B) products per tile
    ('gram' only): 3 * 2*3*B^2."""
    if variant != "gram":
        return 0.0
    nb = -(-M // block)
    tiles = nb * (nb + 1) // 2
    return float(tiles) * (3 * 2.0 * 3 * block * block)


def bytes_estimate(M: int, block: int, variant: str) -> float:
    nb = -(-M // block)
    if variant in ("naive", "fused", "tri"):
        tiles = nb * nb  # 'tri' skips compute but still DMAs the block
    else:
        tiles = nb * (nb + 1) // 2
    streams = 3 if variant == "nomask" else (3 + 1)  # coords (+ mask)
    scale = 4 if variant == "naive" else 1
    return float(tiles) * (2 * streams * block * 4) * scale
