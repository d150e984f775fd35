"""PyRadiomics-compatible 3D shape feature extraction.

The user-facing API mirrors the paper's usage:

    from repro.core.shape_features import ShapeFeatureExtractor
    ext = ShapeFeatureExtractor()
    res = ext.execute(image, mask, spacing=(1.0, 1.0, 1.0))
    res['MeshVolume'], res['SurfaceArea'], res['Maximum3DDiameter'], ...

Feature names and definitions follow the PyRadiomics shape(3D) class:
MeshVolume, VoxelVolume, SurfaceArea, SurfaceVolumeRatio, Sphericity,
Compactness1, Compactness2, SphericalDisproportion, Maximum3DDiameter,
Maximum2DDiameterSlice (x-y plane), Maximum2DDiameterColumn (y-z plane),
Maximum2DDiameterRow (x-z plane), MajorAxisLength, MinorAxisLength,
LeastAxisLength, Elongation, Flatness.

Axis convention: volumes are indexed (x, y, z) with ``spacing`` in the same
order.  (PyRadiomics uses (z, y, x) numpy order; the plane features map as
Slice = in-plane (x, y), Column = (y, z), Row = (x, z).)

The two expensive stages (fused marching cubes and the O(M^2) diameter
search) run on the backend chosen by ``repro.core.dispatcher`` -- this class
is the integration shim the paper implements in C: same inputs, same
outputs, accelerator decided at runtime.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatcher
from repro.kernels import ops


@dataclasses.dataclass
class StageTimes:
    """Wall-clock breakdown mirroring the paper's Table 2 columns."""

    preprocess_ms: float = 0.0  # crop/pad/mask ('File reading' analogue)
    transfer_ms: float = 0.0  # host->device ('D. tran.')
    mesh_ms: float = 0.0  # fused MC volume+area ('M.C.')
    diameter_ms: float = 0.0  # pairwise search ('Diam.')

    @property
    def total_ms(self) -> float:
        return self.preprocess_ms + self.transfer_ms + self.mesh_ms + self.diameter_ms


def roi_box(mask) -> tuple[list[int], list[int]] | None:
    """Bounding box ``(lo, hi)`` of the nonzero voxels, ``hi`` exclusive;
    ``None`` for an empty mask.

    Found from axis projections, the same box as ``np.nonzero``'s min and
    max + 1 without its index arrays: ``np.any`` over the leading axis
    (whole planes OR-ed together) projects the volume onto the trailing
    axes, whose box is found the same way, and ``np.any`` over the
    trailing axes gives the leading axis's extent.  A Fortran-ordered
    mask is read through its C-ordered transpose, so both reductions run
    in memory order.
    """
    m = np.asarray(mask)
    if m.flags.f_contiguous and not m.flags.c_contiguous:
        box = roi_box(m.T)
        return None if box is None else (box[0][::-1], box[1][::-1])
    if m.ndim == 1:
        nz = np.flatnonzero(m)
        return None if nz.size == 0 else ([int(nz[0])], [int(nz[-1]) + 1])
    box = roi_box(np.any(m, axis=0))
    if box is None:
        return None
    lead = np.flatnonzero(np.any(m, axis=tuple(range(1, m.ndim))))
    return [int(lead[0])] + box[0], [int(lead[-1]) + 1] + box[1]


def crop_padded(a, lo, hi, shape, pad: int = 1) -> np.ndarray:
    """``a[lo:hi]`` as float32, written at offset ``pad`` into a zeroed
    array of ``shape``: the crop, the cast and the zero pad in one copy."""
    src = tuple(slice(l, h) for l, h in zip(lo, hi))
    dst = tuple(slice(pad, pad + h - l) for l, h in zip(lo, hi))
    out = np.zeros(shape, np.float32)
    out[dst] = np.asarray(a)[src]
    return out


def crop_to_roi(image: np.ndarray, mask: np.ndarray, pad: int = 1):
    """Crop image/mask to the ROI bounding box and zero-pad by ``pad``.

    PyRadiomics crops to the bounding box before feature extraction; the
    1-voxel zero pad closes the isosurface at the volume boundary.
    Host-side numpy: this is part of the 'data loading' stage in the paper's
    breakdown, not the accelerated region.
    """
    box = roi_box(mask)
    if box is None:
        raise ValueError("mask is empty")
    lo, hi = box
    shape = tuple(h - l + 2 * pad for l, h in zip(lo, hi))
    m = crop_padded(mask, lo, hi, shape, pad)
    im = crop_padded(image, lo, hi, shape, pad)
    return im, m, lo


@jax.jit
def _voxel_stats(mask, spacing):
    """Voxel-count volume and PCA axis lengths (physical coordinates)."""
    n = jnp.sum(mask)
    voxel_volume = n * jnp.prod(spacing)
    nx, ny, nz = mask.shape
    ii, jj, kk = jnp.meshgrid(
        jnp.arange(nx, dtype=jnp.float32),
        jnp.arange(ny, dtype=jnp.float32),
        jnp.arange(nz, dtype=jnp.float32),
        indexing="ij",
    )
    coords = jnp.stack([ii, jj, kk], -1) * spacing  # physical
    w = mask[..., None]
    mean = jnp.sum(coords * w, axis=(0, 1, 2)) / jnp.maximum(n, 1.0)
    d = (coords - mean) * mask[..., None]
    cov = jnp.einsum("xyzi,xyzj->ij", d, d) / jnp.maximum(n, 1.0)
    eig = jnp.linalg.eigvalsh(cov)  # ascending
    eig = jnp.maximum(eig, 0.0)
    return voxel_volume, eig


class ShapeFeatureExtractor:
    """Drop-in 3D shape feature extractor with accelerator dispatch.

    ``diameter_variant='auto'`` and ``mc_block='auto'`` (the defaults) pick
    the measured-best diameter (variant, block) for the case's vertex
    bucket and the measured-best marching-cubes (brick, chunk) for the
    case's padded-volume bucket from the autotune cache
    (``repro.runtime.autotune``); pass concrete values to pin them.
    ``prune=True`` runs the exact candidate pruning stage
    (``repro.kernels.prune``) before the O(M^2) pair sweep -- identical
    diameters (bit-for-bit on the Pallas variants, up to f32 rounding on
    the ref path), usually at a fraction of the pair work.
    """

    def __init__(self, backend: str | None = None, diameter_variant: str = "auto",
                 mc_block="auto", mc_chunk: int | None = None,
                 diam_block: int | None = None, prune: bool = True):
        self.backend = dispatcher.resolve_backend(backend)
        self.diameter_variant = diameter_variant
        self.mc_block = mc_block if mc_block == "auto" else tuple(mc_block)
        self.mc_chunk = mc_chunk
        self.diam_block = diam_block
        self.prune = prune
        self.last_prune_info = None  # PruneInfo of the most recent case

    # -- staged API (used by the Table-2 benchmark harness) ----------------
    def mesh_features(self, mask_padded, spacing):
        v, a = ops.mc_volume_area(
            mask_padded, 0.5, spacing, backend=self.backend,
            block=self.mc_block, chunk=self.mc_chunk,
        )
        return v, a

    def diameter_features(self, mask_padded, spacing):
        fields = ops.vertex_fields(mask_padded, 0.5, spacing)
        n = int(ops.count_vertices(fields))
        cap = ops.vertex_bucket(n)
        verts, vmask, _ = ops.compact_vertices(fields, cap)
        self.last_prune_info = None
        if self.prune:
            verts, vmask, self.last_prune_info = ops.prune_candidates(
                np.asarray(verts), np.asarray(vmask)
            )
        d = ops.max_diameters(
            verts, vmask, backend=self.backend,
            variant=self.diameter_variant, block=self.diam_block,
        )
        return d, n

    # -- public API ---------------------------------------------------------
    def execute(
        self,
        image: np.ndarray,
        mask: np.ndarray,
        spacing=(1.0, 1.0, 1.0),
        with_times: bool = False,
    ) -> Mapping[str, float]:
        times = StageTimes()
        sp = np.asarray(spacing, np.float32)

        t0 = time.perf_counter()
        _, m, _ = crop_to_roi(image, mask)
        times.preprocess_ms = (time.perf_counter() - t0) * 1e3

        t0 = time.perf_counter()
        m_dev = jax.device_put(jnp.asarray(m))
        sp_dev = jax.device_put(jnp.asarray(sp))
        jax.block_until_ready(m_dev)
        times.transfer_ms = (time.perf_counter() - t0) * 1e3

        t0 = time.perf_counter()
        mesh_volume, surface_area = self.mesh_features(m_dev, sp_dev)
        jax.block_until_ready(surface_area)
        times.mesh_ms = (time.perf_counter() - t0) * 1e3

        t0 = time.perf_counter()
        diam, n_verts = self.diameter_features(m_dev, sp_dev)
        jax.block_until_ready(diam)
        times.diameter_ms = (time.perf_counter() - t0) * 1e3

        voxel_volume, eig = _voxel_stats(m_dev, sp_dev)

        V = float(mesh_volume)
        A = float(surface_area)
        d3, dxy, dxz, dyz = (float(x) for x in diam)
        e0, e1, e2 = (float(x) for x in eig)  # ascending: least, minor, major
        pi = float(np.pi)
        feats = {
            "MeshVolume": V,
            "VoxelVolume": float(voxel_volume),
            "SurfaceArea": A,
            "SurfaceVolumeRatio": A / V if V > 0 else float("nan"),
            "Sphericity": (36.0 * pi * V * V) ** (1.0 / 3.0) / A if A > 0 else float("nan"),
            "Compactness1": V / (pi ** 0.5 * A ** 1.5) if A > 0 else float("nan"),
            "Compactness2": 36.0 * pi * V * V / (A ** 3) if A > 0 else float("nan"),
            "SphericalDisproportion": A / (36.0 * pi * V * V) ** (1.0 / 3.0) if V > 0 else float("nan"),
            "Maximum3DDiameter": d3,
            "Maximum2DDiameterSlice": dxy,
            "Maximum2DDiameterRow": dxz,
            "Maximum2DDiameterColumn": dyz,
            "MajorAxisLength": 4.0 * e2 ** 0.5,
            "MinorAxisLength": 4.0 * e1 ** 0.5,
            "LeastAxisLength": 4.0 * e0 ** 0.5,
            "Elongation": (e1 / e2) ** 0.5 if e2 > 0 else float("nan"),
            "Flatness": (e0 / e2) ** 0.5 if e2 > 0 else float("nan"),
            "_n_mesh_vertices": float(n_verts),
        }
        if with_times:
            return feats, times
        return feats
