"""GLCM texture family: co-occurrence accumulation as one-hot matmuls.

The gray-level co-occurrence matrix counts ordered pairs of quantized
intensities at the distance-1 axial offsets (:data:`OFFSETS`), restricted
to pairs whose BOTH voxels are inside the mask.  Accumulating it is a
scatter-add over ``(q1, q2)`` index pairs -- the exact shape of problem
``kernels/compact.py`` already solved with the one-hot-matmul trick: a
0/1 matrix product performs the scatter on the MXU.

Layout: each grid step reads ``block`` pairs as ``(1, block)`` lane rows
and builds two ``(n_bins, block)`` one-hots with the bins on sublanes
and the pairs on lanes, so every vreg is lane-dense and a pair row
broadcasts down the sublanes for free; the counts are then the NT
product ``oh1 . oh2^T`` (both factors contract their lane axis), which
the MXU takes with its weights latched transposed -- no relayout, no
transposed ``(block, n_bins)`` intermediate.  The one-hots are bfloat16
and the dot accumulates in f32 (``preferred_element_type``): 0 and 1 are
exact in bfloat16, every product is 0 or 1, and every partial sum is an
INTEGER held exactly in f32 up to 2**24.  A case's largest count is its
pair total, at most 3 per voxel: Table 2's largest ROI (3.64 M voxels)
gives at most 10.9 M pairs, under 2**24 (16.8 M).  Integer-exact
addition is associative, so the blocked Pallas accumulation equals the
reference scatter bit-for-bit and the autotuned ``block`` is a pure
performance axis.

Feature derivation (Haralick contrast / correlation / inverse difference
moment (homogeneity) / joint energy) happens OUTSIDE the kernel, on the
HOST in numpy, from the symmetrised count matrix via one shared function
(:func:`glcm_features_from_matrix_np`) -- in-graph derivation would let
XLA contract the f32 arithmetic differently per batch shape (see
``kernels/firstorder.py``), whereas the count matrix is integer-exact,
so host derivation makes the feature rows bitwise identical across
backends AND batch depths.  A case with no valid pairs (single voxel,
empty mask) yields an all-zero feature row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import ref as _ref

N_BINS = 32
DEFAULT_BLOCK = 2048
#: distance-1 axial co-occurrence offsets (symmetrised afterwards, so the
#: opposite directions are covered by the transpose)
OFFSETS = ((1, 0, 0), (0, 1, 0), (0, 0, 1))

FEATURES = ("Contrast", "Correlation", "Idm", "JointEnergy")
N_FEATURES = len(FEATURES)


def _shift(x, axis):
    """``x`` moved one voxel down ``axis``: ``y[i] = x[i + 1]``, zero-filled."""
    lo = tuple(slice(1, None) if a == axis else slice(None)
               for a in range(x.ndim))
    pad = tuple((0, 1) if a == axis else (0, 0) for a in range(x.ndim))
    return jnp.pad(x[lo], pad)


def pair_arrays(q, m):
    """Flatten one case's co-occurrence pairs: ``(q1, q2, valid)``.

    ``q`` is the f32 bin-id volume, ``m`` the f32 mask; each offset in
    :data:`OFFSETS` contributes one (voxel, neighbour) pair per voxel, the
    neighbour read from a zero-filled shifted copy of the volume, so the
    pairs that leave the volume have ``valid == 0`` and count nothing.
    Every offset keeps the volume's own shape: the flattening is a plain
    reshape of a full bucket-shaped volume (the sliced ``(n-1)``-long axes
    an exact-overlap formulation needs cost the TPU compiler minutes of
    relayout at 128^3).  The concatenated length is ``3 * q.size``, static
    given the volume shape, so the executor's shape buckets key the pair
    length too.
    """
    q1s, q2s, vs = [], [], []
    for off in OFFSETS:
        axis = off.index(1)
        q1s.append(q.reshape(-1))
        q2s.append(_shift(q, axis).reshape(-1))
        vs.append((m * _shift(m, axis)).reshape(-1))
    return jnp.concatenate(q1s), jnp.concatenate(q2s), jnp.concatenate(vs)


def _quantize_batch(images, masks, n_bins):
    imgs = jnp.asarray(images, jnp.float32)
    m = (jnp.asarray(masks) > 0).astype(jnp.float32)
    B = imgs.shape[0]
    lo, hi = jax.vmap(_ref.intensity_range)(
        imgs.reshape(B, -1), m.reshape(B, -1)
    )
    bcast = (B,) + (1,) * (imgs.ndim - 1)
    q, _ = _ref.quantize_intensity(
        imgs, m, lo.reshape(bcast), hi.reshape(bcast), n_bins
    )
    return q, m


def glcm_matrix_ref(image, mask, n_bins: int = N_BINS):
    """Single-case symmetric co-occurrence counts via ``.at[].add`` scatter."""
    q, m = _quantize_batch(jnp.asarray(image)[None], jnp.asarray(mask)[None],
                           n_bins)
    q1, q2, v = pair_arrays(q[0], m[0])
    idx = q1.astype(jnp.int32) * n_bins + q2.astype(jnp.int32)
    counts = jnp.zeros((n_bins * n_bins,), jnp.float32).at[idx].add(v)
    g = counts.reshape(n_bins, n_bins)
    return g + g.T


def glcm_features_from_matrix_np(mat, n_bins: int = N_BINS) -> np.ndarray:
    """``(..., N_FEATURES)`` Haralick rows from symmetric count matrices.

    HOST-side numpy, shared by every backend (see module docstring).
    ``correlation`` of a zero-variance (single gray level) matrix is
    defined as 1.0, matching PyRadiomics; a matrix with no pairs at all
    yields an all-zero row.
    """
    mat = np.asarray(mat, np.float32)
    total = np.sum(mat, axis=(-2, -1))
    P = mat / np.maximum(total, 1.0)[..., None, None]
    i = np.arange(n_bins, dtype=np.float32)[:, None]
    j = np.arange(n_bins, dtype=np.float32)[None, :]
    diff2 = (i - j) * (i - j)
    contrast = np.sum(diff2 * P, axis=(-2, -1))
    idm = np.sum(P / (1.0 + diff2), axis=(-2, -1))
    energy = np.sum(P * P, axis=(-2, -1))
    # marginal stats (symmetric matrix: px == py)
    px = np.sum(P, axis=-1)
    levels = np.arange(n_bins, dtype=np.float32)
    mu = np.sum(levels * px, axis=-1)
    sig2 = np.sum(
        (levels - mu[..., None]) * (levels - mu[..., None]) * px, axis=-1
    )
    corr = np.where(
        sig2 > 0,
        (np.sum(i * j * P, axis=(-2, -1)) - mu * mu)
        / np.where(sig2 > 0, sig2, 1.0),
        1.0,
    )
    row = np.stack([contrast, corr, idm, energy], axis=-1)
    return np.where(total[..., None] > 0, row, 0.0).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("n_bins",))
def glcm_matrix_batch_ref(images, masks, n_bins: int = N_BINS):
    """``(B, n_bins, n_bins)`` symmetric count matrices (scatter path)."""
    def one(args):
        img, m = args
        return glcm_matrix_ref(img, m, n_bins)

    return jax.lax.map(
        one,
        (jnp.asarray(images, jnp.float32), jnp.asarray(masks, jnp.float32)),
    )


def glcm_features_batch_ref(images, masks, n_bins: int = N_BINS):
    """``(B, N_FEATURES)`` rows: scatter matrices + host derivation.

    NOT traceable (host-side numpy derivation by design); traced callers
    consume :func:`glcm_matrix_batch_ref` and finalise after the fetch.
    """
    return glcm_features_from_matrix_np(
        glcm_matrix_batch_ref(images, masks, n_bins), n_bins
    )


def _glcm_kernel(q1ref, q2ref, vref, out, *, block: int, n_bins: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out[...] = jnp.zeros_like(out)

    # (1, block) lane rows: the pairs stay on lanes, and the compares
    # below broadcast them down the bin sublanes
    q1 = q1ref[0]
    q2 = q2ref[0]
    v = vref[0]
    # integer iota, then cast: Mosaic has no f32 iota
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_bins, block), 0).astype(
        jnp.float32)
    # invalid/padded pairs are zeroed on the LEFT factor only: one dead
    # column in oh1 kills the whole pair
    oh1 = ((q1 == rows) & (v > 0)).astype(jnp.bfloat16)
    oh2 = (q2 == rows).astype(jnp.bfloat16)
    # scatter-by-matmul, A . B^T: counts[a, b] += sum_p oh1[a, p] * oh2[b, p]
    out[0] += jax.lax.dot_general(
        oh1, oh2,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit,
                   static_argnames=("n_bins", "block", "interpret"))
def glcm_matrix_batch_pallas(images, masks, *, n_bins: int = N_BINS,
                             block: int = DEFAULT_BLOCK,
                             interpret: bool = False):
    """Batched symmetric count matrices via the one-hot-matmul kernel."""
    q, m = _quantize_batch(images, masks, n_bins)
    q1, q2, v = jax.vmap(pair_arrays)(q, m)
    B, P = q1.shape
    Pp = -(-P // block) * block
    pad = ((0, 0), (0, Pp - P))
    q1 = jnp.pad(q1, pad)[:, None, :]
    q2 = jnp.pad(q2, pad)[:, None, :]
    v = jnp.pad(v, pad)[:, None, :]  # zero validity: pads contribute nothing
    spec = pl.BlockSpec((1, 1, block), lambda b, t: (b, 0, t))
    g = pl.pallas_call(
        functools.partial(_glcm_kernel, block=block, n_bins=n_bins),
        grid=(B, Pp // block),
        in_specs=[spec, spec, spec],
        out_specs=pl.BlockSpec((1, n_bins, n_bins), lambda b, t: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_bins, n_bins), jnp.float32),
        interpret=interpret,
    )(q1, q2, v)
    return g + jnp.transpose(g, (0, 2, 1))


def glcm_features_batch_pallas(images, masks, *, n_bins: int = N_BINS,
                               block: int = DEFAULT_BLOCK,
                               interpret: bool = False):
    """``(B, N_FEATURES)`` rows: one-hot-matmul matrices + host derivation.

    NOT traceable (see :func:`glcm_features_batch_ref`)."""
    return glcm_features_from_matrix_np(
        glcm_matrix_batch_pallas(images, masks, n_bins=n_bins, block=block,
                                 interpret=interpret),
        n_bins,
    )
