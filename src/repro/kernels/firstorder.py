"""First-order intensity statistics as a batched plan-stage family.

Nine features over the masked voxels of an intensity volume: mean, std,
min, max, three histogram percentiles (P10/median/P90 over the fixed
``n_bins`` discretization), energy (sum of squares), and histogram
entropy.  Everything reduces to one accumulated statistics vector per
case -- ``[count, sum, sum_sq, m2, histogram]`` -- plus the order-invariant
intensity range, packed into one ``(B, packed_width)`` device row per
case.  ``m2`` is the centred second moment, the sum of squared
deviations from the case's mean: StdDev is ``sqrt(m2 / count)``, and
``sum_sq`` serves Energy alone.  (A one-pass ``sum_sq/n - mean**2`` in
float32 cancels away most of its digits at millions of voxels: at a
mean of 100 and a spread of 15 it misses StdDev by up to ~1e-4.)  The feature
row is derived HOST-SIDE by a single shared numpy function
(:func:`features_from_packed_np`): deriving in-graph is a trap, because
XLA fuses and contracts the derivation differently at different batch
shapes, silently breaking batched-equals-single at the last bit.  Host
derivation is one tiny deterministic code path, so backend and batch
parity only ever have to hold on the packed stats.

Bitwise parity contract (mirrors the diameter suite, but for sums):
f32 addition is not associative, so a "sum the masked voxels" spec does
not pin the result -- the ADDITION ORDER is part of the contract.  The
canonical order is a left fold over fixed :data:`CANON_CHUNK`-voxel
chunks of the flattened (zero-padded) volume.  Each chunk's partial is
computed by ``jnp.sum`` over a ``(CANON_CHUNK,)`` slice
(:func:`_chunk_stats`): its count, sum, sum of squares, histogram and
its own m2 about its own mean.  The fold merges each partial into the
running vector by Chan, Golub and LeVeque's pairwise update
(:func:`_merge`): counts, sums and bins add, and the m2s add with the
correction ``delta**2 * n_a * n_b / n`` for the gap ``delta`` between
the two means.  The reference oracle IS that fold (``lax.scan``); the
Pallas kernel performs exactly one :func:`_merge` per canonical chunk
(``for j in range(block // CANON_CHUNK)``), so its global accumulation
is the same left fold for ANY block size -- the autotuned ``block`` is a
pure performance axis, never a numerics axis, and block-sweep winners
cannot flip feature bits.

``jnp.sum`` leaves the order inside one chunk to the compiler.  On a
TPU, XLA's order for a chunk sum follows the layout it gives the
summand, and it matches the Mosaic kernel's only where the summand is
an elementwise function of the chunk slice that XLA fuses into the
reduction: there every chunk sum reads a ``(1, CANON_CHUNK)`` operand.
So the deviations are formed as ``x - mean * m``, not by a ``where`` on
the mask: the ``where`` shares the histogram's mask predicate, XLA then
summed the squares over a flat ``(CANON_CHUNK,)`` operand, and the m2
lane came out of the XLA folds a few bits from the kernel's on a v5e.
``tests/test_chip_compile.py`` holds the XLA folds to that layout.

Zero padding is exact: padded lanes have ``mask == 0``, contributing
``+0.0`` to every statistic (and bin 0 of the histogram only via the
``mask > 0`` guard, i.e. not at all).  A chunk with no masked voxel
merges as an exact no-op: its partial is all zeros and ``n_a * n_b``
in the correction is 0, so a fold over only the touched chunks
(the tiled path, :func:`fold_packed_chunks`) gives the bits of the full
scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import ref as _ref

N_BINS = 32          # default fixed-bin-count discretization
CANON_CHUNK = 1024   # canonical accumulation granule (see module docstring)
DEFAULT_BLOCK = 2048

FEATURES = ("Mean", "StdDev", "Minimum", "Maximum", "Percentile10",
            "Median", "Percentile90", "Energy", "Entropy")
N_FEATURES = len(FEATURES)


N_MOMENTS = 4  # count, sum, sum_sq, m2: the stats vector's leading lanes
M2 = 3  # lane of the centred second moment


def stats_width(n_bins: int = N_BINS) -> int:
    """Width of the accumulated stats vector: [count, sum, sum_sq, m2,
    hist]."""
    return N_MOMENTS + n_bins


def packed_width(n_bins: int = N_BINS) -> int:
    """Width of the per-case device row: stats ++ [lo, hi, bin_width]."""
    return stats_width(n_bins) + 3


def _pack(stats, lo, hi, width):
    return jnp.concatenate(
        [stats, lo[:, None], hi[:, None], width[:, None]], axis=1
    )


def _chunk_stats(x, m, q, n_bins: int):
    """``(stats_width,)`` partial statistics of ONE canonical chunk.

    THE shared numerical contract: the reference fold and the Pallas
    kernel both call this on identically-shaped ``(CANON_CHUNK,)``
    slices, so per-chunk partials lower to the same reductions and match
    bitwise across backends.  Every sum is of an elementwise function of
    the slices that XLA fuses into the reduction (module docstring).  The
    moments stay ``(1,)`` vectors: the chunk mean is a vector divide,
    which Mosaic lowers where a scalar one is not.
    """
    # integer iota, then cast: Mosaic has no f32 iota
    cols = jax.lax.broadcasted_iota(jnp.int32, (CANON_CHUNK, n_bins), 1).astype(
        jnp.float32)
    onehot = ((q[:, None] == cols) & (m[:, None] > 0)).astype(jnp.float32)
    n = jnp.sum(m, keepdims=True)
    s = jnp.sum(x, keepdims=True)
    # deviations from the chunk's mean, 0 off the mask (x is 0 there and
    # m is 0 or 1, so the product is exact)
    d = x - s / jnp.maximum(n, 1.0) * m
    return jnp.concatenate([
        n, s, jnp.sum(x * x, keepdims=True), jnp.sum(d * d, keepdims=True),
        jnp.sum(onehot, axis=0),
    ])


def _merge(acc, part):
    """The running stats vector ``acc`` with one chunk's ``part`` folded in.

    Lanes add, and the m2 lane takes Chan's correction
    ``delta**2 * n_a * n_b / n`` on top.  Works on ``(..., stats_width)``
    vectors; the leading lanes are read by a masked sum (one lane plus
    zeros: exact), which lowers alike in XLA and Mosaic.  An empty side
    makes ``n_a * n_b`` exactly 0, so merging an empty chunk changes no
    bit.  No product feeds a sum directly (the correction ends in a
    divide): a compiler that contracts ``a * b + c`` into one fused
    multiply-add in one program and not in another would otherwise
    round the two folds apart.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, acc.ndim - 1)

    def pick(v, i):
        return jnp.sum(jnp.where(lane == i, v, 0.0), axis=-1, keepdims=True)

    na, sa, nb, sb = pick(acc, 0), pick(acc, 1), pick(part, 0), pick(part, 1)
    delta = sb / jnp.maximum(nb, 1.0) - sa / jnp.maximum(na, 1.0)
    corr = delta * delta * (na * nb) / jnp.maximum(na + nb, 1.0)
    return acc + part + jnp.where(lane == M2, corr, 0.0)


def _padded_len(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _flatten_batch(images, masks, n_bins, multiple):
    """Flatten + mask + quantize a ``(B, *vol)`` stack, padded to ``multiple``.

    Returns ``(x, m, q, lo, hi, width)`` with the first three shaped
    ``(B, Lp)`` (masked values are zeroed; pads are zero) and the last
    three shaped ``(B,)``.
    """
    imgs = jnp.asarray(images, jnp.float32)
    B = imgs.shape[0]
    imgs = imgs.reshape(B, -1)
    m = (jnp.asarray(masks).reshape(B, -1) > 0).astype(jnp.float32)
    lo, hi = jax.vmap(_ref.intensity_range)(imgs, m)
    q, width = _ref.quantize_intensity(
        imgs, m, lo[:, None], hi[:, None], n_bins
    )
    x = jnp.where(m > 0, imgs, 0.0)
    pad = _padded_len(imgs.shape[1], multiple) - imgs.shape[1]
    pad2 = ((0, 0), (0, pad))
    return (jnp.pad(x, pad2), jnp.pad(m, pad2), jnp.pad(q, pad2),
            lo, hi, width[:, 0])


def features_from_packed_np(packed, n_bins: int = N_BINS) -> np.ndarray:
    """``(..., N_FEATURES)`` rows from packed stats, on the HOST in numpy.

    The single derivation shared by every backend and every batch depth:
    parity only has to hold on the packed stats vector (see module
    docstring for why this must not run in-graph).  An empty case
    (count 0) yields an all-zero row; a constant-intensity case has
    ``bin_width == 0`` so every bin centre collapses to ``lo``, entropy
    is exactly 0 and StdDev the rounding left in m2.
    """
    p = np.asarray(packed, np.float32)
    n, s1, s2, m2 = (p[..., i] for i in range(N_MOMENTS))
    w = N_MOMENTS + n_bins
    hist = p[..., N_MOMENTS:w]
    lo, hi, width = p[..., w], p[..., w + 1], p[..., w + 2]
    nsafe = np.maximum(n, 1.0)
    mean = s1 / nsafe
    var = m2 / nsafe
    prob = hist / nsafe[..., None]
    entropy = -np.sum(
        np.where(prob > 0,
                 prob * np.log2(np.where(prob > 0, prob, 1.0)), 0.0),
        axis=-1,
    )
    centers = (lo[..., None]
               + (np.arange(n_bins, dtype=np.float32) + 0.5)
               * width[..., None])
    cum = np.cumsum(hist, axis=-1)

    def pct(frac):
        # first bin whose cumulative count reaches the frac-quantile rank
        idx = np.argmax(cum >= np.float32(frac) * n[..., None], axis=-1)
        return np.take_along_axis(centers, idx[..., None], axis=-1)[..., 0]

    row = np.stack([
        mean, np.sqrt(var), lo, hi,
        pct(0.1), pct(0.5), pct(0.9), s2, entropy,
    ], axis=-1)
    return np.where(n[..., None] > 0, row, 0.0).astype(np.float32)


def firstorder_stats_ref(image, mask, n_bins: int = N_BINS):
    """Single-case oracle stats: the canonical left fold over chunks."""
    x, m, q, lo, hi, width = _flatten_batch(
        jnp.asarray(image)[None], jnp.asarray(mask)[None], n_bins, CANON_CHUNK
    )
    nc = x.shape[1] // CANON_CHUNK
    chunks = (x.reshape(nc, CANON_CHUNK), m.reshape(nc, CANON_CHUNK),
              q.reshape(nc, CANON_CHUNK))

    def body(acc, ch):
        cx, cm, cq = ch
        return _merge(acc, _chunk_stats(cx, cm, cq, n_bins)), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((stats_width(n_bins),), jnp.float32), chunks
    )
    return acc, lo[0], hi[0], width[0]


@functools.partial(jax.jit, static_argnames=("n_bins",))
def fold_packed_chunks(x, m, lo, hi, n_bins: int = N_BINS):
    """Packed stats from a stack of TOUCHED canonical chunks (tiled path).

    ``x``/``m``: (nt, CANON_CHUNK) masked values / mask lanes of the
    mask-touched chunks of the padded frame, in ascending global chunk
    order; ``lo``/``hi`` the order-invariant masked intensity range
    (exact min/max, so a streaming census computes the same bits).  An
    untouched chunk's :func:`_chunk_stats` partial is an exact +0.0
    vector (zero lanes, ``m > 0`` nowhere) and its :func:`_merge` a
    no-op, so folding ONLY the touched chunks -- same body, same
    ascending order -- accumulates bit-identically to the in-core full
    scan.  Quantization happens in-graph from the same ``lo``/``hi``
    (elementwise, shape-independent).
    """
    q, width = _ref.quantize_intensity(x, m, lo, hi, n_bins)

    def body(acc, ch):
        cx, cm, cq = ch
        return _merge(acc, _chunk_stats(cx, cm, cq, n_bins)), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((stats_width(n_bins),), jnp.float32), (x, m, q)
    )
    return jnp.concatenate([acc, jnp.stack([lo, hi, width])])


@functools.partial(jax.jit, static_argnames=("n_bins",))
def firstorder_packed_batch_ref(images, masks, n_bins: int = N_BINS):
    """``(B, packed_width)`` oracle stats via the single-case fold, mapped.

    ``lax.map`` (not vmap): each case runs the exact single-case fold, so
    batched rows are bit-identical to one-at-a-time extraction.
    """
    def one(args):
        img, m = args
        acc, lo, hi, width = firstorder_stats_ref(img, m, n_bins)
        return jnp.concatenate([acc, jnp.stack([lo, hi, width])])

    return jax.lax.map(
        one,
        (jnp.asarray(images, jnp.float32), jnp.asarray(masks, jnp.float32)),
    )


def firstorder_features_batch_ref(images, masks, n_bins: int = N_BINS):
    """``(B, N_FEATURES)`` rows: oracle stats + host derivation.

    NOT traceable (the derivation is host-side numpy by design); traced
    callers consume :func:`firstorder_packed_batch_ref` and finalise
    after the fetch.
    """
    return features_from_packed_np(
        firstorder_packed_batch_ref(images, masks, n_bins), n_bins
    )


def _fo_kernel(xref, mref, qref, out, *, block: int, n_bins: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out[...] = jnp.zeros_like(out)

    # one accumulator update PER CANONICAL CHUNK: the global add order is
    # the module-contract left fold for any block size
    for j in range(block // CANON_CHUNK):
        sl = slice(j * CANON_CHUNK, (j + 1) * CANON_CHUNK)
        vec = _chunk_stats(xref[0, 0, sl], mref[0, 0, sl], qref[0, 0, sl],
                           n_bins)
        out[0] = _merge(out[0], vec[None, :])


@functools.partial(jax.jit,
                   static_argnames=("n_bins", "block", "interpret"))
def firstorder_packed_batch_pallas(images, masks, *, n_bins: int = N_BINS,
                                   block: int = DEFAULT_BLOCK,
                                   interpret: bool = False):
    """``(B, packed_width)`` stats via the Pallas left-fold kernel."""
    if block % CANON_CHUNK:
        raise ValueError(
            f"firstorder block must be a multiple of CANON_CHUNK="
            f"{CANON_CHUNK}, got {block}"
        )
    x, m, q, lo, hi, width = _flatten_batch(images, masks, n_bins, block)
    B, Lp = x.shape
    grid = (B, Lp // block)
    spec = pl.BlockSpec((1, 1, block), lambda b, t: (b, 0, t))
    w = stats_width(n_bins)
    stats = pl.pallas_call(
        functools.partial(_fo_kernel, block=block, n_bins=n_bins),
        grid=grid,
        in_specs=[spec, spec, spec],
        # (B, 1, w): a (1, w) block spans the array's last two dims
        out_specs=pl.BlockSpec((1, 1, w), lambda b, t: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, w), jnp.float32),
        interpret=interpret,
    )(x[:, None, :], m[:, None, :], q[:, None, :])
    return _pack(stats[:, 0], lo, hi, width)


def firstorder_features_batch_pallas(images, masks, *, n_bins: int = N_BINS,
                                     block: int = DEFAULT_BLOCK,
                                     interpret: bool = False):
    """``(B, N_FEATURES)`` rows: Pallas stats kernel + host derivation.

    NOT traceable (see :func:`firstorder_features_batch_ref`)."""
    return features_from_packed_np(
        firstorder_packed_batch_pallas(
            images, masks, n_bins=n_bins, block=block, interpret=interpret
        ),
        n_bins,
    )
