"""Plain numpy reference of the features the configurations promise.

Independent of the program under test: it imports nothing of it and reads
no table, weight or scale that the program made at run time.  It follows
the semantics the configurations state, for binary masks at iso 0.5 on a
study cropped to its ROI and padded by one voxel:

* mesh vertices sit at the midpoints of the grid edges whose two voxels
  differ; their count is the vertex count;
* the marching-cubes surface takes its triangles from ``mc_table.json``,
  and mesh volume and surface area are the absolute signed-tetrahedron sum
  and the summed triangle areas;
* the four maximum diameters are the largest vertex-pair distances in 3-D
  and projected onto the xy, xz and yz planes (the farthest pair lies on
  the convex hull, so only hull vertices are paired);
* first-order statistics and the GLCM use 32 fixed-count bins over the
  ROI's own intensity range; percentiles are the centre of the first bin
  whose cumulative count reaches the rank; the GLCM counts both voxels of
  each +x, +y, +z neighbour pair inside the ROI, symmetrised.

Everything is computed in float64.  ``dtype="bfloat16"`` computes the same
in bfloat16 -- each elementwise result rounded to it, sums accumulated in
float32, as an MXU-style low-precision path would -- and serves as the
comparison's control: a limit that lets it pass is too loose.
"""
from __future__ import annotations

import json
import os

import ml_dtypes
import numpy as np
from scipy.spatial import ConvexHull, QhullError

HERE = os.path.dirname(os.path.abspath(__file__))
N_BINS = 32
FIRSTORDER = ("Mean", "StdDev", "Minimum", "Maximum", "Percentile10",
              "Median", "Percentile90", "Energy", "Entropy")
GLCM = ("Contrast", "Correlation", "Idm", "JointEnergy")
AXES = ("LeastAxisLength", "MinorAxisLength", "MajorAxisLength")
DIAMETERS = ("Maximum3DDiameter", "Maximum2DDiameterSlice",
             "Maximum2DDiameterRow", "Maximum2DDiameterColumn")
_PLANES = ((0, 1, 2), (0, 1), (0, 2), (1, 2))  # the order of DIAMETERS


def _load_table():
    with open(os.path.join(HERE, "mc_table.json")) as f:
        t = json.load(f)
    corners = np.asarray(t["corners"])
    edges = np.asarray(t["edges"])
    mid = (corners[edges[:, 0]] + corners[edges[:, 1]]) / 2.0  # (12, 3)
    width = max(len(r) for r in t["triangles"])
    tri = np.full((256, width), -1, np.int64)
    for case, row in enumerate(t["triangles"]):
        tri[case, :len(row)] = row
    return corners, mid, tri.reshape(256, width // 3, 3)


CORNERS, EDGE_MID, TRIANGLES = _load_table()


class Arith:
    """The precision a reference computation runs in."""

    def __init__(self, dtype: str = "float64"):
        if dtype == "float64":
            self.t, self.acc = np.float64, np.float64
        elif dtype == "bfloat16":
            self.t, self.acc = ml_dtypes.bfloat16, np.float32
        else:
            raise ValueError(f"unknown reference dtype {dtype!r}")

    def cast(self, x):
        return np.asarray(x).astype(self.t)

    def sum(self, x, axis=None):
        return np.sum(np.asarray(x).astype(self.acc), axis=axis,
                      dtype=self.acc)


def crop(image, mask):
    """ROI crop of ``image`` and ``mask``, padded by one voxel of zeros."""
    idx = np.nonzero(mask)
    sl = tuple(slice(int(i.min()), int(i.max()) + 1) for i in idx)
    return (np.pad(np.asarray(image[sl], np.float32), 1),
            np.pad(np.asarray(mask[sl], bool), 1))


def vertices(m, spacing, ar: Arith):
    """(N, 3) mesh vertices of the padded binary ROI ``m``."""
    out = []
    for axis in range(3):
        lo = [slice(None)] * 3
        hi = [slice(None)] * 3
        lo[axis] = slice(0, -1)
        hi[axis] = slice(1, None)
        idx = np.argwhere(m[tuple(lo)] != m[tuple(hi)]).astype(np.float64)
        idx[:, axis] += 0.5
        out.append(idx)
    v = np.concatenate(out)
    return ar.cast(ar.cast(v) * ar.cast(np.asarray(spacing)))


def mesh(m, spacing, ar: Arith):
    """(volume, area, triangles) of the marching-cubes surface of ``m``."""
    nx, ny, nz = m.shape
    code = np.zeros((nx - 1, ny - 1, nz - 1), np.uint8)
    for c, (dx, dy, dz) in enumerate(CORNERS):
        code |= (m[dx:dx + nx - 1, dy:dy + ny - 1, dz:dz + nz - 1]
                 .astype(np.uint8) << c)
    cells = np.argwhere((code != 0) & (code != 255))
    codes = code[tuple(cells.T)]
    sp = np.asarray(spacing, np.float64)
    # centre the frame: the sums do not depend on it, their rounding does
    origin = cells.astype(np.float64) - (np.asarray(m.shape) - 1) / 2.0
    vol = area = 0.0
    n_tri = 0
    for t in range(TRIANGLES.shape[1]):
        ids = TRIANGLES[codes, t]  # (cells, 3) edge ids, -1 = none
        live = ids[:, 0] >= 0
        if not live.any():
            continue
        n_tri += int(live.sum())
        o, e = origin[live], ids[live]
        a, b, c = (ar.cast((o + EDGE_MID[e[:, k]]) * sp) for k in range(3))
        cross = np.cross(b - a, c - a)
        area += ar.sum(np.sqrt(ar.cast(ar.sum(cross * cross, axis=1))) /
                       ar.cast(2.0))
        vol += ar.sum(ar.cast(ar.sum(a * np.cross(b, c), axis=1)) /
                      ar.cast(6.0))
    return abs(float(vol)), float(area), n_tri


def _hull(p):
    """Rows of ``p`` that can end a farthest pair: its hull vertices."""
    p = np.unique(p, axis=0)
    if len(p) <= p.shape[1] + 1:
        return p
    try:
        return p[ConvexHull(p).vertices]
    except QhullError:  # flat or degenerate: every point stays
        return p


def _farthest(p, ar: Arith, block=1024):
    best = 0.0
    for s in range(0, len(p), block):
        d = p[s:s + block, None, :] - p[None, :, :]
        best = max(best, float(np.max(ar.sum(d * d, axis=2))))
    return float(np.sqrt(best))


def diameters(v, ar: Arith):
    """The four maximum diameters of the vertex set ``v`` (DIAMETERS)."""
    v64 = np.asarray(v, np.float64)  # exact copy of the rounded vertices
    return tuple(_farthest(ar.cast(_hull(v64[:, list(axes)])), ar)
                 for axes in _PLANES)


def axes(m, spacing, ar: Arith):
    """PCA axis lengths (AXES) of the ROI voxels' physical coordinates."""
    p = ar.cast(np.argwhere(m) * np.asarray(spacing, np.float64))
    d = ar.cast(p - ar.cast(ar.sum(p, axis=0) / len(p)))
    cov = np.array([[float(ar.sum(ar.cast(d[:, i] * d[:, j])))
                     for j in range(3)] for i in range(3)]) / len(p)
    eig = np.clip(np.linalg.eigvalsh(cov), 0.0, None)
    return tuple(4.0 * np.sqrt(eig))


def firstorder(x, ar: Arith):
    """The nine first-order features (FIRSTORDER) of ROI intensities ``x``."""
    x = ar.cast(x)
    n = x.size
    mean = ar.sum(x) / n
    var = ar.sum(ar.cast(x - ar.cast(mean)) ** 2) / n
    lo, hi = float(x.min()), float(x.max())
    q, width = bins(x, lo, hi, ar)
    hist = np.bincount(q, minlength=N_BINS).astype(np.float64)
    p = hist / n
    nz = p[p > 0]
    centres = lo + (np.arange(N_BINS) + 0.5) * width
    cum = np.cumsum(hist)

    def pct(frac):
        return float(centres[np.argmax(cum >= frac * n)])

    return (float(mean), float(np.sqrt(var)), lo, hi, pct(0.1), pct(0.5),
            pct(0.9), float(ar.sum(x * x)), float(-np.sum(nz * np.log2(nz))))


def bins(x, lo, hi, ar: Arith):
    """Fixed-count bin ids of ``x`` over ``[lo, hi]`` and the bin width."""
    width = (hi - lo) / N_BINS
    if width <= 0:
        return np.zeros(np.shape(x), np.int64), 0.0
    w = ar.cast(width)
    q = np.floor((ar.cast(x) - ar.cast(lo)) / w).astype(np.int64)
    return np.clip(q, 0, N_BINS - 1), width


def glcm(img, m, ar: Arith):
    """The four GLCM features (GLCM) of the padded ROI crop."""
    vals = img[m]
    q = np.zeros(m.shape, np.int64)
    q[m], _ = bins(vals, float(vals.min()), float(vals.max()), ar)
    counts = np.zeros(N_BINS * N_BINS, np.int64)
    for axis in range(3):
        lo = [slice(None)] * 3
        hi = [slice(None)] * 3
        lo[axis] = slice(0, -1)
        hi[axis] = slice(1, None)
        both = m[tuple(lo)] & m[tuple(hi)]
        counts += np.bincount(q[tuple(lo)][both] * N_BINS + q[tuple(hi)][both],
                              minlength=N_BINS * N_BINS)
    g = counts.reshape(N_BINS, N_BINS)
    g = (g + g.T).astype(np.float64)
    total = g.sum()
    if total == 0:
        return (0.0, 0.0, 0.0, 0.0)
    P = ar.cast(g / total)
    i = ar.cast(np.arange(N_BINS)[:, None])
    j = ar.cast(np.arange(N_BINS)[None, :])
    d2 = ar.cast((i - j) ** 2)
    px = ar.sum(P, axis=1)
    lev = np.arange(N_BINS, dtype=np.float64)
    mu = float(np.sum(lev * px))
    sig2 = float(np.sum((lev - mu) ** 2 * px))
    corr = ((float(ar.sum(i * j * P)) - mu * mu) / sig2) if sig2 > 0 else 1.0
    return (float(ar.sum(d2 * P)), corr,
            float(ar.sum(P / ar.cast(ar.cast(1.0) + d2))),
            float(ar.sum(P * P)))


def features(case, families=("shape",), dtype: str = "float64") -> dict:
    """Reference features of one ``traffic.generate.Case`` by name, plus
    ``n_vertices``, ``VoxelVolume`` and the mesh's ``n_triangles``."""
    ar = Arith(dtype)
    img, m = crop(case.image, case.mask)
    out = {}
    if "shape" in families:
        sp = np.asarray(case.spacing, np.float64)
        vol, area, n_tri = mesh(m, sp, ar)
        v = vertices(m, sp, ar)
        out.update(MeshVolume=vol, SurfaceArea=area, n_vertices=len(v),
                   n_triangles=n_tri,
                   VoxelVolume=float(m.sum()) * float(np.prod(sp)))
        out.update(zip(DIAMETERS, diameters(v, ar)))
        out.update(zip(AXES, axes(m, sp, ar)))
    if "firstorder" in families:
        out.update(zip(FIRSTORDER, firstorder(img[m], ar)))
    if "glcm" in families:
        out.update(zip(GLCM, glcm(img, m, ar)))
    return out
