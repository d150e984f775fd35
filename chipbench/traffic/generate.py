"""Synthetic KiTS19-like studies and the order they are sent in.

A configuration (``chipbench/configs/<name>.json``) lists its images -- a
case id and the image dimensions -- and the ROI model that fills them; a
traffic mix (``chipbench/traffic/<name>.json``) says which of those images
are sent and in what order.  This module is the one generator that reads
both, so a new mix is a new data file.

The ROI model is a copy of the repository's synthetic KiTS19 stand-in (a
union of 2-4 overlapping ellipsoids with a low-frequency boundary wobble,
CT-like intensities), with two changes.  The ellipsoids fill the image, as
the organ fills its Table 2 crop, which puts the vertex counts in the range
the paper reports (2,700 to 236,588).  And the outer surface comes from the
configuration's ``geometry_seed`` and the case index alone, while the run's
``--seed`` sets the intensities (box-filtered noise, so that neighbouring
voxels correlate as CT texture does) and a few small cavities deep inside
the ROI.  So every seed gives each case the same bounding box, the same shape
bucket and the same extreme vertices -- the same kernels to compile and
nearly the same work -- with different voxels.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os

import numpy as np
from scipy.ndimage import uniform_filter

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Case:
    name: str
    image: np.ndarray  # (x, y, z) float32 intensities
    mask: np.ndarray  # (x, y, z) bool ROI
    spacing: tuple  # (3,) floats, same axis order
    box: tuple  # ROI bounding box ((lo_x, lo_y, lo_z), (hi_x, hi_y, hi_z))

    @property
    def roi_dims(self) -> tuple:
        lo, hi = self.box
        return tuple(h - l for l, h in zip(lo, hi))


def load(name: str) -> dict:
    """The traffic mix ``name`` (``chipbench/traffic/<name>.json``)."""
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def _blobs(dims, rng, roi):
    lo, hi = roi["blobs"]
    n = int(rng.integers(lo, hi + 1))
    dims = np.asarray(dims, np.float64)
    c_lo, c_hi = roi["centre"]
    r_lo, r_hi = roi["radius"]
    centre0 = dims * rng.uniform(c_lo, c_hi, 3)
    out = []
    for _ in range(n):
        c = centre0 + (rng.random(3) - 0.5) * dims * (2 * roi["offset"])
        r = np.maximum(2.5, dims * rng.uniform(r_lo, r_hi, 3))
        freq = rng.uniform(0.1, 0.35, 3)
        phase = rng.random(3) * 7.0
        out.append((c, r, freq, phase))
    return out


def _window(dims, c, ext):
    """Index range ``[lo, hi)`` of the voxels within ``ext`` of ``c``."""
    lo = np.clip(np.floor(c - ext).astype(int), 0, dims)
    hi = np.clip(np.ceil(c + ext).astype(int) + 1, 0, dims)
    return lo, hi


def _grid(lo, hi):
    return [np.arange(a, b, dtype=np.float32).reshape(
        [-1 if d == i else 1 for d in range(3)]) for i, (a, b) in
        enumerate(zip(lo, hi))]


def _d2(g, c, r):
    return sum(((g[i] - np.float32(c[i])) / np.float32(r[i])) ** 2
               for i in range(3))


def _fill(mask, lo, hi, inside):
    sl = tuple(slice(a, b) for a, b in zip(lo, hi))
    mask[sl] |= inside


def make_case(name, dims, geometry_seed, content_seed, roi,
              spacing=(1.0, 1.0, 1.0)) -> Case:
    """One study: its surface from ``geometry_seed``, its cavities and
    intensities from ``content_seed`` (see the module docstring)."""
    dims = tuple(int(d) for d in dims)
    blobs = _blobs(dims, np.random.default_rng(geometry_seed), roi)
    amp = np.float32(roi["wobble"])
    mask = np.zeros(dims, bool)
    for c, r, freq, phase in blobs:
        lo, hi = _window(dims, c, r * np.sqrt(1.0 + float(amp)))
        g = _grid(lo, hi)
        wob = amp
        for i in range(3):
            wob = wob * np.sin(g[i] * np.float32(freq[i]) + np.float32(phase[i]))
        _fill(mask, lo, hi, _d2(g, c, r) + wob < 1.0)
    rng = np.random.default_rng(content_seed)
    cav = roi["cavities"]
    for k in range(int(cav["count"])):
        c, r, _, _ = blobs[k % len(blobs)]
        # a centre inside the blob's core (normalised radius sqrt(0.4)) and
        # a radius under a quarter of its smallest axis stay clear of the
        # outer surface, which no wobble moves inside d2 = 1 - wobble
        rho = min(float(cav["radius"]), 0.25 * float(np.min(r)))
        u = rng.standard_normal(3)
        u *= rng.random() ** (1.0 / 3.0) / np.linalg.norm(u)
        cc = c + u * np.sqrt(0.4) * r
        if rho < 1.0:
            continue
        lo, hi = _window(dims, cc, rho)
        g = _grid(lo, hi)
        sl = tuple(slice(a, b) for a, b in zip(lo, hi))
        mask[sl] &= ~(_d2(g, cc, (rho, rho, rho)) < 1.0)
    if not mask.any():  # degenerate geometry: the first centre alone
        mask[tuple(np.clip(np.round(blobs[0][0]).astype(int), 0,
                           np.array(dims) - 1))] = True
    idx = [np.flatnonzero(mask.any(axis=tuple(d for d in range(3) if d != i)))
           for i in range(3)]
    box = (tuple(int(i[0]) for i in idx), tuple(int(i[-1]) + 1 for i in idx))
    mu, sd = roi["background"]
    k = int(roi["texture"])
    # CT-like texture: white noise under a k-voxel box filter, rescaled to
    # the background's standard deviation
    image = uniform_filter(rng.standard_normal(dims, dtype=np.float32), k)
    image *= np.float32(sd * k ** 1.5)
    image += np.float32(mu)
    image[mask] += np.float32(roi["contrast"])
    return Case(name, image, mask, tuple(float(s) for s in spacing), box)


def select(config: dict, traffic: dict) -> list:
    """``(index, case id, dims)`` of the configuration's images the mix
    sends, in the configuration's order; ``index`` keys the geometry."""
    want = traffic.get("cases", "all")
    images = [(i, cid, tuple(d)) for i, (cid, d) in
              enumerate(config["images"])]
    if want == "all":
        return images
    by_id = {cid: (i, cid, d) for i, cid, d in images}
    return [by_id[cid] for cid in want]


def build_cases(config: dict, traffic: dict, seed: int) -> list:
    """Every study the mix sends, made from ``seed`` (held in host memory)."""
    roi = config["roi_model"]
    gseed = int(config["geometry_seed"])
    spacing = tuple(config["spacing"])
    return [make_case(cid, dims, [gseed, idx], [int(seed), idx], roi,
                      spacing) for idx, cid, dims in select(config, traffic)]


def order(traffic: dict, n: int, seed: int):
    """Endless sequence of case positions ``0..n-1`` in the mix's order.

    ``dataset``: the configuration's order, pass after pass.  ``shuffled``:
    each pass a new permutation drawn from ``seed``.
    """
    if n < 1:
        raise ValueError("the traffic mix sends no study")
    kind = traffic["order"]
    if kind == "dataset":
        return itertools.cycle(range(n))
    if kind == "shuffled":
        rng = np.random.default_rng([int(seed), 1])

        def passes():
            while True:
                yield from (int(i) for i in rng.permutation(n))

        return passes()
    raise ValueError(f"unknown traffic order {kind!r}")
