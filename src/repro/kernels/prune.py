"""Exact candidate pruning for the O(M^2) diameter search.

The farthest-pair search dominates shape-feature time (paper Table 2:
95.7%-99.9%), so shrinking the candidate set M -> M' before the quadratic
pass is the biggest structural lever: pair work drops by (M/M')^2.  This
stage is O(M*K), fully vectorised, and **exact** -- the pruned search
returns bit-identical maxima for every feature combo on the Pallas
variants (see the composition note below for the ref path's ulp caveat).

Method (per combo c in {3D, xy, xz, yz}, restricted to c's axes):

1. *Lower bound* L_c: project the vertices onto K sampled unit directions
   (always including the coordinate axes), take the arg-min/arg-max vertex
   per direction, and brute-force the <= 2K extreme points.  Every extreme
   is a real valid vertex, so L_c <= D_c (the true combo diameter).
2. *Upper bound* ub_c(p) per vertex: distance from p to the farthest point
   of the candidate bounding box.  ``x -> |p - x|`` is convex, so its max
   over a box is attained at a corner -- the corner sweep is exact.  We
   additionally intersect with the triangle-inequality bound
   ``|p - centre| + max_q |q - centre|`` and keep the smaller of the two.
3. Discard p for combo c iff ub_c(p) < L_c: p can then not be an endpoint
   of any pair reaching L_c, in particular not of the farthest pair.

A vertex survives if ANY combo keeps it; the union keeps every potential
endpoint of all four maxima, which is what makes running a single 4-combo
kernel on the pruned set sound.

Exactness of the composition (prune + any Pallas kernel variant): the
achieving pair (p*, q*) of combo c has real distance D_c >= L_c and
ub_c >= D_c, so both endpoints survive; per-pair tile arithmetic is
shape-independent, so a max over a subset that contains the arg-max pair
is the same float -- **bit-identical** for every Pallas variant.  The
extreme witnesses themselves are force-kept (axis directions are always
in the sample), so the candidate bounding box is pruning-invariant.  The
pure-jnp reference path is the one exception to bit-identity: XLA fuses
its sweep shape-dependently (FMA/vectorization choices change with M),
so ref results can differ by ~1 ulp across pruning -- identical up to
f32 rounding, not bit-for-bit.

Float safety: bounds are compared with a small relative slack so f32
rounding in ub/L can never discard a borderline true endpoint.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

COMBOS = ((0, 1, 2), (0, 1), (0, 2), (1, 2))  # 3D, xy, xz, yz

# sampled projection directions per combo for the lower bound (every
# caller -- pipeline, drop-in, tiled census -- uses this one value)
K_DIRS = 16

# relative slack on the squared upper bound; >> f32 rounding, prunes
# a negligible shell of borderline candidates less aggressively
_SLACK = np.float32(1.0 + 1e-4)


def _directions(combo: tuple, k: int) -> np.ndarray:
    """(K', 3) unit directions spanning ``combo``'s axes.

    Always starts with the coordinate axes and the subspace diagonals;
    extra directions come from a deterministic golden-ratio sweep (2D:
    half-circle angles, 3D: spiral hemisphere).  Min/max projections are
    both taken per direction, so antipodes are covered for free.
    """
    dirs = []
    for a in combo:
        e = np.zeros(3)
        e[a] = 1.0
        dirs.append(e)
    if len(combo) == 2:
        a0, a1 = combo
        for s in (1.0, -1.0):
            d = np.zeros(3)
            d[a0], d[a1] = 1.0, s
            dirs.append(d)
        for i in range(max(0, k - len(dirs))):
            th = np.pi * (i + 0.5) / max(1, k - 4)
            d = np.zeros(3)
            d[a0], d[a1] = np.cos(th), np.sin(th)
            dirs.append(d)
    else:
        for sx in (1.0, -1.0):
            for sy in (1.0, -1.0):
                dirs.append(np.array([1.0, sx, sy]))
        golden = (1.0 + 5.0 ** 0.5) / 2.0
        n_extra = max(0, k - len(dirs))
        for i in range(n_extra):
            z = (i + 0.5) / n_extra
            r = (1.0 - z * z) ** 0.5
            th = 2.0 * np.pi * i / golden
            dirs.append(np.array([r * np.cos(th), r * np.sin(th), z]))
    d = np.stack(dirs)
    d /= np.linalg.norm(d, axis=1, keepdims=True)
    return d.astype(np.float32)


@functools.partial(jax.jit, static_argnames=("k_dirs",))
def candidate_keep_mask(verts, mask, k_dirs: int = K_DIRS):
    """Exact per-vertex keep mask for the 4-combo diameter search.

    Returns ``(keep, lower_sq)``: ``keep`` is a (M,) bool mask (False =
    provably not an endpoint of any of the 4 maxima, or invalid), and
    ``lower_sq`` the (4,) squared lower bounds found per combo.
    """
    verts = jnp.asarray(verts, jnp.float32)
    m = jnp.asarray(mask).astype(bool)
    v0 = verts[jnp.argmax(m)]  # first valid vertex (callers reject empty)
    vfill = jnp.where(m[:, None], verts, v0[None, :])

    keep_any = jnp.zeros(m.shape, bool)
    lower_sq = []
    for combo in COMBOS:
        axes = jnp.zeros((3,), jnp.float32).at[jnp.asarray(combo)].set(1.0)
        pc = vfill * axes[None, :]  # off-combo axes zeroed
        d = jnp.asarray(_directions(combo, k_dirs))  # (K, 3) constants
        proj = pc @ d.T  # (M, K)
        # bias invalid (duplicated-fill) slots out of the extreme search so
        # an argmax/argmin tie can never land on a slot that '& m' would
        # then drop -- the witnesses must be real valid vertices
        inf = jnp.float32(np.inf)
        pmax = jnp.where(m[:, None], proj, -inf)
        pmin = jnp.where(m[:, None], proj, inf)
        ext = jnp.concatenate([jnp.argmax(pmax, 0), jnp.argmin(pmin, 0)])
        e = pc[ext]  # (2K, 3) extreme points -- real valid vertices
        de = e[:, None, :] - e[None, :, :]
        l2 = jnp.max(jnp.sum(de * de, -1))  # squared lower bound

        lo = jnp.min(pc, axis=0)
        hi = jnp.max(pc, axis=0)
        signs = jnp.asarray(
            [[sx, sy, sz] for sx in (0, 1) for sy in (0, 1) for sz in (0, 1)],
            jnp.float32,
        )  # (8, 3); degenerate/duplicate corners are harmless
        corners = lo[None, :] + signs * (hi - lo)[None, :]
        dc = pc[:, None, :] - corners[None, :, :]
        ub_corner2 = jnp.max(jnp.sum(dc * dc, -1), axis=1)  # (M,)
        centre = 0.5 * (lo + hi)
        r = jnp.sqrt(jnp.sum((pc - centre) ** 2, -1))
        ub_centre2 = (r + jnp.max(r)) ** 2
        ub2 = jnp.minimum(ub_corner2, ub_centre2)
        keep_any = keep_any | (ub2 * _SLACK >= l2)
        # force-keep the extreme witnesses: an extreme can itself be a
        # provable non-endpoint, but dropping it would move the candidate
        # bounding box and break the pruning-invariance of the reference
        # path's centring (bit-identity).  <= 2K extra vertices.
        keep_any = keep_any.at[ext].set(True)
        lower_sq.append(l2)
    return keep_any & m, jnp.stack(lower_sq)


@dataclasses.dataclass(frozen=True)
class PruneInfo:
    """Host-side pruning statistics (fed to benchmarks / BENCH records)."""

    m_total: int  # input rows (incl. padding)
    m_valid: int  # valid vertices before pruning
    m_kept: int  # surviving candidates (M')
    pruned: bool  # False when pruning was skipped (degenerate input)

    @property
    def keep_fraction(self) -> float:
        return self.m_kept / self.m_valid if self.m_valid else 1.0


def _compact_survivors(verts_np, mask_np, keep):
    """Host-side compaction shared by the single and batched prune paths."""
    m_valid = int(mask_np.sum())
    if m_valid < 2:
        return verts_np, mask_np, PruneInfo(len(verts_np), m_valid, m_valid, False)
    keep = np.asarray(keep)
    m_kept = int(keep.sum())
    if m_kept < 2 or m_kept >= m_valid:
        return verts_np, mask_np, PruneInfo(len(verts_np), m_valid, m_valid, False)
    idx = np.nonzero(keep)[0]
    return (
        np.ascontiguousarray(verts_np[idx]),
        np.ones((m_kept,), bool),
        PruneInfo(len(verts_np), m_valid, m_kept, True),
    )


def prune_vertices(verts, mask, k_dirs: int = K_DIRS):
    """Host-side pruning: compact survivors into a dense candidate list.

    Returns ``(verts', mask', info)`` as numpy arrays with
    ``verts'.shape == (M', 3)`` and an all-true mask.  Degenerate inputs
    (fewer than 2 survivors, or nothing pruned) fall back to the originals
    so callers never lose the empty/single-vertex semantics of the kernels.
    """
    verts_np = np.asarray(verts, np.float32)
    mask_np = np.asarray(mask).astype(bool)
    if int(mask_np.sum()) < 2:  # callers reject empty; skip the kernel
        keep = np.zeros(len(verts_np), bool)
    else:
        keep, _ = candidate_keep_mask(verts_np, mask_np, k_dirs=k_dirs)
    return _compact_survivors(verts_np, mask_np, keep)


@functools.partial(jax.jit, static_argnames=("k_dirs",))
def keep_mask_batch(verts, masks, k_dirs: int = K_DIRS):
    """Vmapped :func:`candidate_keep_mask` over a (B, M, 3) stack.

    The two-pass pipeline's pass-1 bound: ONE launch computes every case's
    keep mask.  Device in/out -- both the host compaction path
    (:func:`prune_vertices_batch`) and the device compaction path
    (``kernels/compact``) consume the same masks.
    """
    keep, lower = jax.vmap(
        lambda v, m: candidate_keep_mask(v, m, k_dirs=k_dirs)
    )(verts, masks)
    return keep, lower


def plan_compaction(m_total: int, m_valid: int, m_kept: int, bucket_fn):
    """Shared pruned/kept decision for both compaction paths.

    Composes the degenerate-input rule of :func:`_compact_survivors`
    (fewer than 2 valid or surviving vertices, or nothing pruned -> keep
    the originals) with the re-bucketing rule of
    ``ops._rebucket_pruned`` (a survivor bucket no smaller than the input
    wins nothing -> keep the originals).  Returns ``(cap, info)`` where
    ``cap`` is the M' bucket to compact into, or ``None`` when the case
    keeps its original arrays.  Both the host path and the device path
    derive their ``PruneInfo`` from this single function, so the two can
    never drift.
    """
    if m_valid < 2 or m_kept < 2 or m_kept >= m_valid:
        return None, PruneInfo(m_total, m_valid, m_valid, False)
    cap = int(bucket_fn(m_kept))
    if cap >= m_total:
        return None, PruneInfo(m_total, m_valid, m_valid, False)
    return cap, PruneInfo(m_total, m_valid, m_kept, True)


def prune_vertices_batch(verts, masks, k_dirs: int = K_DIRS):
    """Batched pass-1 pruning bound for a stack of same-cap cases.

    ``verts``: (B, M, 3), ``masks``: (B, M).  One vmapped keep-mask kernel
    computes every case's bound in a single launch (the batched pipeline's
    pass 1); compaction stays host-side per case because the surviving
    counts M' are ragged.  Returns a list of B ``(verts', mask', info)``
    triples with the same degenerate-input semantics as
    :func:`prune_vertices`.  Tie-breaks in the vmapped extreme search can
    differ from the single-case path, so the surviving *sets* may differ --
    both always contain every true farthest-pair endpoint, which is the
    property the downstream diameters depend on.
    """
    verts_np = np.asarray(verts, np.float32)
    masks_np = np.asarray(masks).astype(bool)
    keep, _ = keep_mask_batch(verts_np, masks_np, k_dirs)
    keep = np.asarray(keep)
    return [
        _compact_survivors(v, m, k)
        for v, m, k in zip(verts_np, masks_np, keep)
    ]
