"""Roofline pricing layer: plan work items -> FLOPs/bytes -> microseconds.

The cost model (``runtime/costmodel``) needs a price for kernel launches
the autotune sweeps have never measured.  Until this layer existed that
price was a single analytic constant (``(cap/1024)^2 * PAIR_SWEEP_US``)
that knew nothing about the hardware OR about any kernel except the pair
sweep.  This module replaces it with a two-part roofline estimate:

1. **Structural work models.**  For every launch kind the executor
   dispatches (the pair-sweep diameter kernel, the prune bound, the
   segmented compaction, fused marching cubes, and the first-order/GLCM
   intensity families) a closed-form FLOPs + bytes count as a function of
   the plan metadata alone -- vertex bucket M, batch depth, padded volume
   shape.  The per-unit constants in :data:`CAL` are CALIBRATED against
   ``jax.jit(...).lower(...).compile().cost_analysis()`` on the 'ref'
   kernels (loop-corrected via ``repro.utils.roofline.jaxpr_cost``, since
   XLA counts a scan body once) at the canonical batch depth
   :data:`CAL_DEPTH`; ``tests/test_roofline.py`` and the CI ``roofline``
   stage pin the agreement to within :data:`AGREEMENT_RTOL`.

2. **A hardware profile.**  Peak FLOP/s and memory bandwidth for the
   resolved backend, from ``runtime/autotune.get_hw_profile`` -- a
   measured ``hw/<backend>`` cache entry when one exists, a tiny one-time
   probe where probing is allowed, or the static per-backend default.

The estimate is then the classic roofline bound

    time = max(flops / peak_flops, bytes / mem_bw)

which is a LOWER bound on real wall time; like the analytic constant it
replaces, only ratios between buckets feed scheduling decisions, so the
model being uniformly optimistic is harmless.  ``benchmarks/
roofline_report.py`` closes the loop by measuring each kernel and
reporting the achieved fraction of this bound as gated bench rows.

Calibration provenance: constants fitted on the jax CPU backend
(cost_analysis of the 'ref' kernels) at depth 4, k_dirs=16, n_bins=32,
MC chunk_z=32 -- the pipeline defaults.  The fit is linear per kind and
stable to ~3% across buckets/shapes; the 10% agreement gate leaves that
much headroom plus room for upstream jaxpr drift.
"""
from __future__ import annotations

import math

from repro.core import plan as planlib

# canonical batch depth the CAL constants were fitted at: the correction
# ratio (jaxpr loops-multiplied / loops-once) scales loop-EXTERNAL work
# together with the loop bodies, so the fitted per-unit constants carry a
# mild depth dependence -- agreement checks must compare at this depth
CAL_DEPTH = 4

# relative tolerance of the plan-census == cost_analysis agreement gate
AGREEMENT_RTOL = 0.10

# per-kind calibrated work models (FLOPs and bytes per structural unit):
#   diameter    per vertex pair:      depth * M^2 units
#   prune       per case, affine in M (the K-dir projections + the fixed
#               (2K)^2 extreme brute-force and 8-corner bound terms)
#   compact     per case, affine in (M, cap_out)
#   mc          per padded slab cell: nslabs * chunk_z * nx * ny units
#               (the z-scan pads the slab range, so cost follows the
#               padded slab volume, not the raw cell count)
#   firstorder  per padded voxel (n_bins=32 histogram + moment stats)
#   glcm        per padded voxel (13-direction pair accumulation)
CAL = {
    "diameter": {"flops": 22.2, "bytes": 36.9},
    "prune": {"flops_m": 2527.6, "flops_c": 36531.0,
              "bytes_m": 3390.3, "bytes_c": 9215.0},
    "compact": {"flops_m": 25.04, "flops_cap": 1.0,
                "bytes_m": 36.71, "bytes_cap": 13.0},
    "mc": {"flops": 773.0, "bytes": 2035.0},
    "firstorder": {"flops": 226.0, "bytes": 420.8},
    "glcm": {"flops": 55.0, "bytes": 117.1},
}

MC_CHUNK_Z = 32  # the ref backend's z-slab scan chunk (kernels/ops.py)


# ---------------------------------------------------------------------------
# structural work models
# ---------------------------------------------------------------------------

def diameter_cost(m: int, depth: int = 1) -> tuple[float, float]:
    """(flops, bytes) of one pair-sweep launch: ``depth`` cases at bucket M."""
    pairs = float(depth) * float(m) ** 2
    c = CAL["diameter"]
    return c["flops"] * pairs, c["bytes"] * pairs


def prune_cost(m: int, depth: int = 1) -> tuple[float, float]:
    """(flops, bytes) of one batched prune-bound launch (k_dirs=16)."""
    c = CAL["prune"]
    d = float(depth)
    return (d * (c["flops_m"] * m + c["flops_c"]),
            d * (c["bytes_m"] * m + c["bytes_c"]))


def compact_cost(m: int, cap: int, depth: int = 1) -> tuple[float, float]:
    """(flops, bytes) of one segmented-compaction launch M -> cap."""
    c = CAL["compact"]
    d = float(depth)
    return (d * (c["flops_m"] * m + c["flops_cap"] * cap),
            d * (c["bytes_m"] * m + c["bytes_cap"] * cap))


def mc_slab_cells(shape, chunk_z: int = MC_CHUNK_Z) -> float:
    """Padded slab-volume cell count the fused-MC z-scan actually visits."""
    nx, ny, nz = (int(s) for s in shape)
    nslabs = max(1, math.ceil((nz - 1) / chunk_z))
    return float(nslabs * chunk_z * nx * ny)


def mc_cost(shape, depth: int = 1) -> tuple[float, float]:
    """(flops, bytes) of one fused marching-cubes launch at a shape bucket."""
    cells = float(depth) * mc_slab_cells(shape)
    c = CAL["mc"]
    return c["flops"] * cells, c["bytes"] * cells


def family_cost(family: str, shape, depth: int = 1) -> tuple[float, float]:
    """(flops, bytes) of one intensity-family launch (n_bins=32)."""
    c = CAL[family]
    vox = float(depth) * float(math.prod(int(s) for s in shape))
    return c["flops"] * vox, c["bytes"] * vox


def work_item_cost(item: planlib.WorkItem) -> tuple[float, float]:
    """Price one plan :class:`~repro.core.plan.WorkItem` as (flops, bytes)."""
    if item.kind == "diameter":
        return diameter_cost(item.m, item.depth)
    if item.kind == "prune":
        return prune_cost(item.m, item.depth)
    if item.kind == "compact":
        return compact_cost(item.m, item.cap, item.depth)
    if item.kind == "mc":
        return mc_cost(item.shape, item.depth)
    if item.kind in ("firstorder", "glcm"):
        return family_cost(item.kind, item.shape, item.depth)
    raise ValueError(
        f"unknown work item kind {item.kind!r}; known kinds: "
        f"{planlib.WORK_KINDS}"
    )


def plan_cost(plan: planlib.ExtractionPlan) -> dict:
    """Total (flops, bytes) of every launch a plan implies, plus per-kind."""
    per_kind: dict = {}
    total_f = total_b = 0.0
    for item in plan.work_census():
        f, b = work_item_cost(item)
        kf, kb = per_kind.get(item.kind, (0.0, 0.0))
        per_kind[item.kind] = (kf + f, kb + b)
        total_f += f
        total_b += b
    return {"flops": total_f, "bytes": total_b, "per_kind": per_kind}


# ---------------------------------------------------------------------------
# roofline pricing
# ---------------------------------------------------------------------------

def roofline_us(flops: float, nbytes: float, profile: dict) -> float:
    """``max(compute, memory)`` bound in MICROSECONDS under a hw profile."""
    compute_s = flops / float(profile["peak_flops"])
    memory_s = nbytes / float(profile["mem_bw"])
    return max(compute_s, memory_s) * 1e6


def work_item_us(item: planlib.WorkItem, profile: dict) -> float:
    """Roofline bound of one planned launch, in microseconds."""
    f, b = work_item_cost(item)
    return roofline_us(f, b, profile)


# ---------------------------------------------------------------------------
# cost_analysis cross-check (the calibration the CAL table is pinned to)
# ---------------------------------------------------------------------------

def xla_kernel_cost(kind: str, *, depth: int = CAL_DEPTH, m: int | None = None,
                    cap: int | None = None,
                    shape: tuple | None = None) -> tuple[float, float]:
    """Loop-corrected ``cost_analysis()`` (flops, bytes) of one REF launch.

    Builds exactly the batched 'ref' launch the executor would dispatch
    for the given bucket, lowers and compiles it, and returns XLA's FLOP
    and bytes-accessed counts scaled by the jaxpr loop correction
    (``repro.utils.roofline``) -- the ground truth the structural models
    above are calibrated against.  Compiles a kernel, so tests and the CI
    agreement stage call it, the hot path never does.
    """
    import jax
    import jax.numpy as jnp

    from repro.utils import roofline as uro

    if kind == "diameter":
        from repro.kernels import ref as _ref

        args = (jnp.zeros((depth, m, 3), jnp.float32),
                jnp.ones((depth, m), bool))

        def fn(v, msk):
            return jax.lax.map(
                lambda a: _ref.max_diameters_sq(a[0], a[1]), (v, msk)
            )
    elif kind == "prune":
        from repro.kernels import prune as _prune

        args = (jnp.zeros((depth, m, 3), jnp.float32),
                jnp.ones((depth, m), bool))

        def fn(v, msk):
            return _prune.keep_mask_batch(v, msk, 16)
    elif kind == "compact":
        from repro.kernels import compact as _compact

        args = (jnp.zeros((depth, m, 3), jnp.float32),
                jnp.ones((depth, m), bool))

        def fn(v, keep):
            return _compact.compact_batch_ref(v, keep, cap)
    elif kind == "mc":
        from repro.kernels import ops as _ops

        args = (jnp.zeros((depth,) + tuple(shape), jnp.float32),
                jnp.ones((depth, 3), jnp.float32))

        def fn(vols, sps):
            return _ops.mc_volume_area_batch(vols, 0.5, sps, backend="ref")
    elif kind in ("firstorder", "glcm"):
        from repro.kernels import firstorder as _fo
        from repro.kernels import glcm as _glcm

        op = (_fo.firstorder_packed_batch_ref if kind == "firstorder"
              else _glcm.glcm_matrix_batch_ref)
        args = (jnp.zeros((depth,) + tuple(shape), jnp.float32),
                jnp.ones((depth,) + tuple(shape), bool))

        def fn(images, masks):
            return op(images, masks, 32)
    else:
        raise ValueError(f"unknown kernel kind {kind!r}")

    compiled = jax.jit(fn).lower(*args).compile()
    raw_f, raw_b = uro.compiled_cost(compiled)
    fc, bc, _ = uro.loop_corrections(fn, *args)
    return raw_f * fc, raw_b * bc


def model_kernel_cost(kind: str, *, depth: int = CAL_DEPTH,
                      m: int | None = None, cap: int | None = None,
                      shape: tuple | None = None) -> tuple[float, float]:
    """The structural model's (flops, bytes) for the same launch."""
    return work_item_cost(
        planlib.WorkItem(kind=kind, depth=depth, m=m, cap=cap, shape=shape)
    )


def agreement(kind: str, *, depth: int = CAL_DEPTH, m: int | None = None,
              cap: int | None = None, shape: tuple | None = None) -> dict:
    """Model-vs-XLA agreement report for one launch configuration.

    ``flops_rel_err`` / ``bytes_rel_err`` are relative to the XLA side;
    ``ok`` is both within :data:`AGREEMENT_RTOL`.
    """
    mf, mb = model_kernel_cost(kind, depth=depth, m=m, cap=cap, shape=shape)
    xf, xb = xla_kernel_cost(kind, depth=depth, m=m, cap=cap, shape=shape)
    f_err = abs(mf - xf) / xf if xf else float("inf")
    b_err = abs(mb - xb) / xb if xb else float("inf")
    return {
        "kind": kind,
        "model_flops": mf, "xla_flops": xf, "flops_rel_err": f_err,
        "model_bytes": mb, "xla_bytes": xb, "bytes_rel_err": b_err,
        "ok": f_err <= AGREEMENT_RTOL and b_err <= AGREEMENT_RTOL,
    }


#: The (kind, bucket) grid the CI roofline stage checks agreement on --
#: one small and one larger bucket per kind where the launch compiles in
#: well under a second on the CPU 'ref' backend.
AGREEMENT_GRID = (
    {"kind": "diameter", "m": 512},
    {"kind": "diameter", "m": 2048},
    {"kind": "prune", "m": 512},
    {"kind": "prune", "m": 2048},
    {"kind": "compact", "m": 1024, "cap": 512},
    {"kind": "compact", "m": 4096, "cap": 2048},
    {"kind": "mc", "shape": (34, 34, 34)},
    {"kind": "mc", "shape": (66, 66, 66)},
    {"kind": "firstorder", "shape": (34, 34, 34)},
    {"kind": "glcm", "shape": (34, 34, 34)},
)
