"""Backend dispatch: the TPU analogue of PyRadiomics-cuda's GPU probe.

The paper's C extension replaces one call site with a dispatcher that
queries for a CUDA device at runtime and falls back to the original CPU
implementation when none is found (or the driver fails).  Here:

    'pallas'    -- compiled Pallas TPU kernels (requires a TPU backend:
                   asking for it anywhere else is an error, never a
                   silent fallback)
    'interpret' -- the same kernels executed in Pallas interpret mode
                   (Python on the CPU; the test suite's kernel backend)
    'ref'       -- the pure-jnp reference path (the 'original CPU
                   implementation' role)
    'auto'      -- probe: TPU present -> 'pallas', else 'ref'

``REPRO_BACKEND`` overrides 'auto' (like CUDA_VISIBLE_DEVICES-style control).
Every backend returns identical features (tested), so switching is
transparent to callers -- the paper's key compatibility property.
"""
from __future__ import annotations

import os
from typing import Literal

import jax

Backend = Literal["auto", "pallas", "interpret", "ref"]
_VALID = ("auto", "pallas", "interpret", "ref")


def has_tpu() -> bool:
    try:
        return jax.devices()[0].platform == "tpu"
    except RuntimeError:  # pragma: no cover - no backend at all
        return False


def resolve_backend(backend: Backend | None = None) -> str:
    """Resolve 'auto' to a concrete backend, honouring REPRO_BACKEND."""
    if backend is None:
        backend = os.environ.get("REPRO_BACKEND", "auto")  # type: ignore
    if backend not in _VALID:
        raise ValueError(f"backend must be one of {_VALID}, got {backend!r}")
    if backend == "pallas" and not has_tpu():
        raise RuntimeError(
            "backend='pallas' compiles Pallas kernels for a TPU, but JAX's "
            f"default device is {jax.devices()[0].platform!r}; use "
            "'interpret' or 'ref' on this host"
        )
    if backend != "auto":
        return backend
    return "pallas" if has_tpu() else "ref"


def kernel_kwargs(backend: str) -> dict:
    """kwargs forwarded to the Pallas wrappers for a resolved backend."""
    if backend == "pallas":
        return {"interpret": False}
    if backend == "interpret":
        return {"interpret": True}
    raise ValueError(f"not a kernel backend: {backend!r}")


def diameter_config(backend: str, bucket: int, variant: str = "auto",
                    block: int | None = None, batch: int = 1):
    """Resolve the (variant, block) the diameter kernel should run with.

    ``variant='auto'`` consults the measured autotune cache for the
    (vertex bucket, batch-depth bucket) pair -- the plan-aware key: the
    executor passes the sub-batch depth a launch will actually carry
    (``repro.runtime.autotune``).  Explicit values pass through, and an
    explicitly passed ``block`` always wins over the tuned one.  For the
    'ref' backend the choice is moot and defaults are returned.
    """
    from repro.runtime import autotune  # local import: avoid cycle

    if variant != "auto":
        return variant, (block or autotune.DEFAULT_CONFIG.block)
    cfg = autotune.get_diameter_config(int(bucket), backend, batch=batch)
    return cfg.variant, (block or cfg.block)


def compact_config(backend: str, bucket: int, block="auto",
                   batch: int = 1) -> int:
    """Resolve the segmented-compaction scatter block for an M bucket.

    ``block='auto'`` consults the measured autotune cache for the (input
    vertex bucket, batch-depth bucket) pair (``repro.runtime.autotune``);
    explicit values pass through.  For the 'ref' backend the choice is
    moot and the default is returned.  Like the other config resolvers
    this may run a measuring sweep, so call it OUTSIDE any traced
    function.
    """
    from repro.runtime import autotune  # local import: avoid cycle

    if block is not None and block != "auto":
        return int(block)
    if backend == "ref":
        return autotune.DEFAULT_COMPACT_CONFIG.block
    return autotune.get_compact_config(int(bucket), backend, batch=batch).block


def firstorder_config(backend: str, shape, block="auto",
                      batch: int = 1) -> int:
    """Resolve the first-order reduction block for a padded-volume bucket.

    ``block='auto'`` consults the ``firstorder/<backend>`` autotune-cache
    namespace for the (volume bucket, batch-depth bucket) pair; explicit
    values pass through.  For the 'ref' backend the choice is moot and
    the default is returned.  May run a measuring sweep, so call it
    OUTSIDE any traced function.
    """
    from repro.runtime import autotune  # local import: avoid cycle

    if block is not None and block != "auto":
        return int(block)
    if backend == "ref":
        return autotune.DEFAULT_FIRSTORDER_CONFIG.block
    return autotune.get_family_config(
        "firstorder", autotune.mc_shape_bucket(shape), backend, batch=batch
    ).block


def glcm_config(backend: str, shape, block="auto", batch: int = 1) -> int:
    """Resolve the GLCM pair-scatter block for a padded-volume bucket.

    Same contract as :func:`firstorder_config`, against the
    ``glcm/<backend>`` autotune-cache namespace.
    """
    from repro.runtime import autotune  # local import: avoid cycle

    if block is not None and block != "auto":
        return int(block)
    if backend == "ref":
        return autotune.DEFAULT_GLCM_CONFIG.block
    return autotune.get_family_config(
        "glcm", autotune.mc_shape_bucket(shape), backend, batch=batch
    ).block


def sync_cost(backend: str, cache=None) -> float:
    """Resolve the modeled per-fetch d2h latency (microseconds).

    Consults the ``sync/<backend>`` autotune-cache entry (the one-time
    measured probe; ``repro.runtime.autotune.get_sync_cost``), falling
    back to the documented default when no calibration exists and
    probing is disallowed.  Unlike the kernel-config resolvers this is
    meaningful for EVERY backend including 'ref' -- the sync cost
    belongs to the device link, not to a kernel.  May run the measuring
    probe, so call it OUTSIDE any traced function.
    """
    from repro.runtime import autotune  # local import: avoid cycle

    return autotune.get_sync_cost(backend, cache=cache)


def hw_profile(backend: str, cache=None) -> dict | None:
    """Resolve the backend's hardware roofline profile (or ``None``).

    Consults the ``hw/<backend>`` autotune-cache entry (the one-time
    measured peak-FLOP/s + memory-bandwidth probe;
    ``repro.runtime.autotune.get_hw_profile``), falling back to the
    static per-backend default when no calibration exists and probing is
    disallowed.  ``None`` means no profile exists at all -- an unknown
    backend string, or ``REPRO_ROOFLINE=0`` -- and the cost model then
    uses its analytic constant.  Like :func:`sync_cost` this is
    meaningful for every backend, and may run the measuring probe, so
    call it OUTSIDE any traced function.
    """
    from repro.runtime import autotune  # local import: avoid cycle

    return autotune.get_hw_profile(backend, cache=cache)


def mc_config(backend: str, shape, block="auto", chunk: int | None = None,
              batch: int = 1):
    """Resolve the (brick, chunk) the marching-cubes kernel should run with.

    ``block='auto'`` consults the measured autotune cache for the
    (padded-volume bucket of ``shape``, batch-depth bucket) pair
    (``repro.runtime.autotune``); explicit values pass through, and an
    explicitly passed ``chunk`` always wins over the tuned one.  For the
    'ref' backend the choice is moot and defaults are returned.  Like
    ``diameter_config`` this may run a measuring sweep, so call it
    OUTSIDE any traced function.
    """
    from repro.runtime import autotune  # local import: avoid cycle

    if block is not None and block != "auto":
        return tuple(block), int(chunk or autotune.DEFAULT_MC_CONFIG.chunk)
    if backend == "ref":
        cfg = autotune.DEFAULT_MC_CONFIG
    else:
        cfg = autotune.get_mc_config(
            autotune.mc_shape_bucket(shape), backend, batch=batch
        )
    return cfg.block, int(chunk or cfg.chunk)
