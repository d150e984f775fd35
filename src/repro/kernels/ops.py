"""Jitted, backend-dispatched wrappers around the shape-feature kernels.

Public entry points used by ``repro.core`` -- each takes a ``backend``
keyword resolved by ``repro.core.dispatcher`` and routes to the Pallas TPU
kernel, its interpret-mode twin, or the pure-jnp reference path.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatcher
from repro.kernels import diameter as _diam
from repro.kernels import marching_cubes as _mc
from repro.kernels import prune as _prune
from repro.kernels import ref as _ref


def mc_volume_area(vol, iso=0.5, spacing=(1.0, 1.0, 1.0), *, backend=None, **kw):
    """(mesh_volume, surface_area) of the isosurface of ``vol``.

    ``block='auto'`` (the default) resolves the measured-best MC
    (brick, chunk) for the padded-volume bucket from the autotune cache
    (see ``repro.runtime.autotune``).  Resolution may sweep, so traced
    callers must pass a concrete ``block`` AND ``chunk`` (resolved outside
    the trace via ``dispatcher.mc_config``).
    """
    b = dispatcher.resolve_backend(backend)
    if b == "ref":
        # the ref path's only configuration axis is the scan slab depth;
        # honour a kernel-style ``chunk`` too so the executor's mc_chunk
        # becomes the device-budget lever on every backend (tiled path)
        chunk_z = kw.get("chunk_z", kw.get("chunk") or 32)
        return _ref.mc_volume_area(vol, iso, spacing, chunk_z=chunk_z)
    block, chunk = kw.get("block", "auto"), kw.get("chunk")
    if block is None or block == "auto" or chunk is None:
        block, chunk = dispatcher.mc_config(b, np.shape(vol), block, chunk)
    return _mc.mc_volume_area_pallas(
        vol,
        iso,
        spacing,
        block=tuple(block),
        chunk=chunk,
        **dispatcher.kernel_kwargs(b),
    )


def mc_volume_area_batch(vols, iso=0.5, spacings=None, *, backend=None,
                         block=None, chunk=None):
    """Batched :func:`mc_volume_area` over a device stack (pass 2a).

    ``vols``: (B, nx, ny, nz) bucket-padded masks, ``spacings``: (B, 3)
    -> (B, 2) [volume, area] rows.  The device-resident MC feed: callers
    (the executor's staged pass 2a) slice stacks straight off a
    bucket-keyed device pool, so no host re-stacking happens per chunk.
    This entry point is designed to be TRACED (it sits under the
    executor's sharded jit), so ``block``/``chunk`` must already be
    concrete for kernel backends -- resolve them outside the trace via
    ``dispatcher.mc_config``; the 'ref' backend has no configuration axis.
    """
    b = dispatcher.resolve_backend(backend)
    vols = jnp.asarray(vols, jnp.float32)
    if spacings is None:
        spacings = jnp.ones((vols.shape[0], 3), jnp.float32)
    spacings = jnp.asarray(spacings, jnp.float32)
    if b == "ref":
        chunk_z = chunk if isinstance(chunk, int) else 32

        def one(args):
            vol, sp = args
            v, a = _ref.mc_volume_area(vol, iso, sp, chunk_z=chunk_z)
            return jnp.stack([v, a])

        return jax.lax.map(one, (vols, spacings))
    if block is None or block == "auto" or chunk is None:
        raise ValueError(
            "mc_volume_area_batch is traced: resolve (block, chunk) outside "
            "the trace via dispatcher.mc_config"
        )
    return _mc.mc_volume_area_batch_pallas(
        vols,
        iso,
        spacings,
        block=tuple(block),
        chunk=chunk,
        **dispatcher.kernel_kwargs(b),
    )


def mc_tile_partials(slab, iso=0.5, spacing=(1.0, 1.0, 1.0), *, backend=None,
                     k0=0, chunk_z=32, full_shape=None, block=None,
                     chunk=None):
    """Tile accumulator: MC partial sums for one halo-closed z-window.

    The tiled pipeline's per-tile reduction entry (``core/tiled.py``).
    ``slab`` spans the window's cells plus the closing plane
    (``k * chunk_z + 1`` deep for ref, ``k * block[2] + 1`` for kernel
    backends); ``k0`` is the window's first global slab/brick-row index.
    Returns per-slab ``(dvol, darea)`` 1-D arrays on the ref backend and
    per-brick ``(vol_p, area_p)`` (nbx, nby, nbz_window) arrays on the
    kernel backends.  Partials are NOT reduced here: the caller re-folds
    them in the in-core path's global order so the f32 accumulation is
    bit-identical (see :func:`repro.kernels.ref.mc_slab_partials` and
    :func:`repro.kernels.marching_cubes.mc_brick_partials_pallas`).
    """
    b = dispatcher.resolve_backend(backend)
    if b == "ref":
        return _ref.mc_slab_partials(slab, iso, spacing, chunk_z=chunk_z, k0=k0)
    if full_shape is None:
        raise ValueError("kernel backends need full_shape for the centred "
                         "origin")
    if block is None or block == "auto" or chunk is None:
        block, chunk = dispatcher.mc_config(b, tuple(full_shape), block, chunk)
    cz = int(block[2])
    return _mc.mc_brick_partials_pallas(
        slab, iso, spacing,
        full_shape=tuple(full_shape),
        z_cell_offset=np.float32(k0 * cz),
        block=tuple(block), chunk=chunk,
        **dispatcher.kernel_kwargs(b),
    )


def mc_tile_finalize(vol_partials, area_partials, *, backend=None):
    """Fold assembled tile partials into ``(volume, area)``.

    ref: a host ``np.float32`` left fold over the global-slab-order
    deltas -- IEEE-754 single adds, the same op sequence as the in-core
    scan carry.  Kernel backends: one jitted reduce over the assembled
    full brick grid (:func:`mc_partials_finalize` -- the same reduction
    shape the in-core kernel entry ends with).
    """
    b = dispatcher.resolve_backend(backend)
    if b == "ref":
        sv = np.float32(0.0)
        sa = np.float32(0.0)
        for dv, da in zip(np.asarray(vol_partials, np.float32),
                          np.asarray(area_partials, np.float32)):
            sv = np.float32(sv + dv)
            sa = np.float32(sa + da)
        return np.abs(sv), sa
    v, a = _mc.mc_partials_finalize(jnp.asarray(vol_partials, jnp.float32),
                                    jnp.asarray(area_partials, jnp.float32))
    return np.float32(v), np.float32(a)


def max_diameters(verts, mask, *, backend=None, **kw):
    """(4,) [3D, Slice(xy), Row(xz), Column(yz)] max diameters.

    ``variant='auto'`` resolves (variant, block) from the autotune cache
    for this vertex bucket (see ``repro.runtime.autotune``).
    """
    b = dispatcher.resolve_backend(backend)
    if b == "ref":
        return _ref.max_diameters(verts, mask, row_block=kw.get("row_block", 128))
    variant, block = dispatcher.diameter_config(
        b, verts.shape[0], kw.get("variant", "seqacc"), kw.get("block")
    )
    return _diam.max_diameters_pallas(
        verts,
        mask,
        block=block,
        variant=variant,
        **dispatcher.kernel_kwargs(b),
    )


def _rebucket_pruned(orig_verts, orig_mask, v2, m2, info):
    """Pad a pruned candidate list back up to its M' vertex bucket."""
    if not info.pruned:
        return v2, m2, info
    cap = vertex_bucket(info.m_kept)
    if cap >= info.m_total:
        # the survivor bucket (>= 512 floor) is no smaller than the input,
        # so re-bucketing would not shrink the padded pair sweep -- keep
        # the originals and report the stage as a no-op
        return (
            np.asarray(orig_verts, np.float32),
            np.asarray(orig_mask).astype(bool),
            dataclasses.replace(info, m_kept=info.m_valid, pruned=False),
        )
    pad = cap - len(v2)
    if pad > 0:
        v2 = np.pad(v2, ((0, pad), (0, 0)))
        m2 = np.pad(m2, (0, pad))
    return v2, m2, info


def prune_candidates(verts, mask, k_dirs: int = _prune.K_DIRS):
    """Exact host-side candidate pruning + re-bucketing for the pair sweep.

    Shrinks the vertex list to the provably-sufficient candidate set
    (identical diameters: bit-for-bit on the Pallas variants, up to f32
    rounding on the ref path -- see ``repro.kernels.prune``), then
    pads it back up to the M' vertex bucket.  Returns
    ``(verts', mask', info)``; on degenerate inputs the originals come
    back unchanged.
    """
    v2, m2, info = _prune.prune_vertices(verts, mask, k_dirs=k_dirs)
    return _rebucket_pruned(verts, mask, v2, m2, info)


def compact_survivors_batch(verts, keep, cap: int, *, backend=None,
                            block="auto"):
    """Batched device-resident segmented compaction (pass 1b).

    Scatters each case's keep-mask survivors into the first M' slots of a
    static ``cap`` bucket (stable order, zero padding -- bit-identical to
    the host ``np.nonzero`` + ``np.pad`` path it replaces).  ``verts``:
    (B, M, 3), ``keep``: (B, M) -> ``(out, mask, n)`` device arrays with
    ``out``: (B, cap, 3), ``mask``: (B, cap) bool, ``n``: (B,) int32 total
    survivor counts.  ``block='auto'`` resolves the measured-best scatter
    block for the M bucket from the autotune cache; resolution may sweep,
    so traced callers must resolve it first via ``dispatcher.compact_config``.
    """
    from repro.kernels import compact as _compact

    b = dispatcher.resolve_backend(backend)
    if b == "ref":
        return _compact.compact_batch_ref(verts, keep, cap)
    blk = dispatcher.compact_config(b, np.shape(verts)[1], block)
    return _compact.compact_batch_pallas(
        verts, keep, cap, block=blk, **dispatcher.kernel_kwargs(b)
    )


def prune_candidates_batch(verts, masks, k_dirs: int = _prune.K_DIRS):
    """Batched :func:`prune_candidates` for a (B, M, 3) stack of cases.

    The keep-mask bound runs as ONE vmapped kernel over the whole stack
    (the two-pass pipeline's pass 1); compaction + re-bucketing are per
    case HOST-side because the pruned counts M' are ragged.  Returns a
    list of B ``(verts', mask', info)`` triples.  This is the host-
    compaction oracle the batched pipeline is tested against; the
    pipeline itself pairs :func:`repro.kernels.prune.keep_mask_batch`
    with :func:`compact_survivors_batch` on device.
    """
    verts_np = np.asarray(verts, np.float32)
    masks_np = np.asarray(masks)
    return [
        _rebucket_pruned(v, m, v2, m2, info)
        for (v, m), (v2, m2, info) in zip(
            zip(verts_np, masks_np),
            _prune.prune_vertices_batch(verts_np, masks_np, k_dirs=k_dirs),
        )
    ]


def firstorder_packed_batch(images, masks, *, backend=None, n_bins=32,
                            block=None):
    """Batched packed first-order stats over bucket-padded stacks.

    ``images``/``masks``: (B, nx, ny, nz) device stacks ->
    (B, packed_width) stats rows ([count, sum, sum_sq, m2, hist, lo, hi,
    bin_width]; see ``repro.kernels.firstorder``).  Designed to be
    TRACED (it runs under the executor's sharded jit), so ``block`` must
    already be concrete for kernel backends -- resolve it outside the
    trace via ``dispatcher.firstorder_config``; the 'ref' backend has no
    configuration axis.  Batched rows are bit-identical to single-case
    extraction on every backend (canonical-chunk contract); the feature
    row derives host-side via ``firstorder.features_from_packed_np``.
    """
    from repro.kernels import firstorder as _fo

    b = dispatcher.resolve_backend(backend)
    if b == "ref":
        return _fo.firstorder_packed_batch_ref(images, masks, n_bins=n_bins)
    if block is None or block == "auto":
        raise ValueError(
            "firstorder_packed_batch is traced: resolve block outside the "
            "trace via dispatcher.firstorder_config"
        )
    return _fo.firstorder_packed_batch_pallas(
        images, masks, n_bins=n_bins, block=int(block),
        **dispatcher.kernel_kwargs(b),
    )


def firstorder_features_batch(images, masks, *, backend=None, n_bins=32,
                              block=None):
    """Batched first-order intensity rows: (B, 9) (host-finalised).

    Convenience wrapper: :func:`firstorder_packed_batch` + the shared
    host derivation.  NOT traceable -- traced callers (the executor)
    consume the packed entry and finalise after the fetch.
    """
    from repro.kernels import firstorder as _fo

    return _fo.features_from_packed_np(
        firstorder_packed_batch(images, masks, backend=backend,
                                n_bins=n_bins, block=block),
        n_bins,
    )


def glcm_matrix_batch(images, masks, *, backend=None, n_bins=32, block=None):
    """Batched symmetric GLCM count matrices: (B, n_bins, n_bins).

    Counts are integer-valued f32 and exactly equal across backends and
    block sizes (0/1 contributions; see ``repro.kernels.glcm``).  Traced
    callers must resolve ``block`` via ``dispatcher.glcm_config``.
    """
    from repro.kernels import glcm as _glcm

    b = dispatcher.resolve_backend(backend)
    if b == "ref":
        return _glcm.glcm_matrix_batch_ref(images, masks, n_bins=n_bins)
    if block is None or block == "auto":
        raise ValueError(
            "glcm_matrix_batch is traced: resolve block outside the trace "
            "via dispatcher.glcm_config"
        )
    return _glcm.glcm_matrix_batch_pallas(
        images, masks, n_bins=n_bins, block=int(block),
        **dispatcher.kernel_kwargs(b),
    )


def glcm_features_batch(images, masks, *, backend=None, n_bins=32,
                        block=None):
    """Batched Haralick GLCM rows: (B, 4) [contrast, corr, idm, energy].

    Convenience wrapper: :func:`glcm_matrix_batch` + the shared host
    derivation.  NOT traceable -- traced callers (the executor) consume
    the matrix entry and finalise after the fetch.
    """
    from repro.kernels import glcm as _glcm

    return _glcm.glcm_features_from_matrix_np(
        glcm_matrix_batch(images, masks, backend=backend, n_bins=n_bins,
                          block=block),
        n_bins,
    )


def vertex_fields(vol, iso=0.5, spacing=(1.0, 1.0, 1.0), origin=(0.0, 0.0, 0.0),
                  index_offset=None):
    """Dense dedup vertex fields (elementwise; same path on all backends)."""
    return _ref.vertex_fields(vol, iso, spacing, origin,
                              index_offset=index_offset)


def tile_vertex_fields(slab, iso, spacing, index_offset):
    """Jitted per-tile vertex fields in the full volume's index frame."""
    return _ref.tile_vertex_fields(slab, iso, spacing, index_offset)


def count_vertices(fields):
    return _ref.count_vertices(fields)


def compact_vertices(fields, max_vertices):
    return _ref.compact_vertices(fields, max_vertices)


# Single-source M-bucket ladder: defined in the (kernel-free) plan layer,
# re-exported here for the kernel-side callers that predate the split.
from repro.core.plan import vertex_bucket  # noqa: E402, F401
