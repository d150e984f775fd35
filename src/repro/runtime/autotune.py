"""Measured kernel-configuration selection (diameter variants + MC bricks).

The Fig.1-style variant study shows no single configuration wins at every
problem size: small vertex buckets want one big block (grid overhead), large
buckets want the triangular prefetch schedule or the MXU 'gram' path, and
the marching-cubes kernel has the same trade-off along its ``(bx, by, bz)``
brick shape and in-kernel ``chunk`` length (VMEM residency vs grid overhead).
This module turns that study into infrastructure: per static *bucket* (the
vertex padding cap from ``ops.vertex_bucket`` for the diameter kernel, the
padded volume shape for MC) it sweeps the candidate configurations once on
the resolved backend, caches the winner in a JSON file, and hands the cached
choice to every later call -- the TPU analogue of a CUDA occupancy/launch-
bound autotuner.

Cache schema (versioned): one JSON object ``{"schema": 3, "entries": {...}}``
with entries keyed ``"diameter/<backend>/M<bucket>/B<depth>"``,
``"mc/<backend>/S<nx>x<ny>x<nz>/B<depth>"``,
``"compact/<backend>/M<bucket>/B<depth>"`` (the segmented-compaction
scatter block), ``"firstorder/<backend>/S<nx>x<ny>x<nz>/B<depth>"`` /
``"glcm/<backend>/S<nx>x<ny>x<nz>/B<depth>"`` (the intensity-family
reduction/pair-scatter blocks, one namespace per registered feature
family -- see ``repro.core.plan.FamilySpec``), ``"sync/<backend>"``
(the measured device->host
fetch latency -- the quantity the counted-vs-static schedule decision
of ``runtime/costmodel`` turns on; probed once per backend, not per
bucket, since a (B, 2) count fetch is latency- not bandwidth-bound),
and ``"hw/<backend>"`` (the measured hardware roofline profile -- peak
FLOP/s + memory bandwidth -- that prices unmeasured buckets via
``runtime/roofline``; probed once per host per backend, same policy as
the sync probe).  ``B<depth>`` is the power-of-two *batch-depth bucket*
(:func:`batch_bucket`): under ``lax.map`` / the batched pipeline the best
(variant, block) / (brick, chunk) can shift with how many cases a launch
carries, so the winning configuration is cached per (bucket, depth) pair
and the sweeps measure at the requested depth.  Each record holds the
winning configuration plus the full measured table (microseconds), so the
sweep is also a persisted perf trajectory.  PR 1 wrote a *flat*
``{key: record}`` object (schema v1) and PR 2/3 a v2 envelope with
depth-less keys; loads migrate both transparently (depth-less keys gain
``/B1`` -- those sweeps measured single-case launches) and the next
``put`` rewrites the file in v3 form.  Unknown future schemas and
malformed files load as empty (worst case: re-measure) -- the cache never
crashes a run.
The path comes from ``REPRO_AUTOTUNE_CACHE`` (default
``~/.cache/repro_autotune.json``); writes are atomic (tmp + rename) so
concurrent processes at worst re-measure.

Sweeping policy: measured sweeps run by default only on the compiled
``pallas`` backend.  ``interpret`` is a correctness backend -- Python timings
there are meaningless for TPU choices -- so it uses the default config
unless ``REPRO_AUTOTUNE=1`` forces a sweep (used by tests to exercise the
round-trip) ; ``REPRO_AUTOTUNE=0`` disables sweeping everywhere.  The
``ref`` backend has no configuration axis at all.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
import time

import jax
import numpy as np

SCHEMA_VERSION = 3

DEFAULT_VARIANTS = ("seqacc", "tri_prefetch", "nomask", "gram")
DEFAULT_BLOCKS = (128, 256, 512)

DEFAULT_MC_BLOCKS = ((8, 8, 8), (16, 8, 8), (8, 8, 16), (16, 16, 8))
DEFAULT_MC_CHUNKS = (256, 512, 1024)

DEFAULT_COMPACT_BLOCKS = (128, 256, 512)

# first-order blocks MUST be multiples of the canonical accumulation chunk
# (kernels/firstorder.CANON_CHUNK) -- the sweep enforces this, so a tuned
# block can never change feature bits
DEFAULT_FIRSTORDER_BLOCKS = (1024, 2048, 4096)
DEFAULT_GLCM_BLOCKS = (512, 1024, 2048, 4096)


@dataclasses.dataclass(frozen=True)
class DiameterConfig:
    variant: str
    block: int


@dataclasses.dataclass(frozen=True)
class MCConfig:
    block: tuple[int, int, int]
    chunk: int


@dataclasses.dataclass(frozen=True)
class CompactConfig:
    block: int


@dataclasses.dataclass(frozen=True)
class FamilyConfig:
    """One intensity-family kernel configuration (block is the only axis)."""

    block: int


DEFAULT_CONFIG = DiameterConfig("seqacc", 256)
DEFAULT_MC_CONFIG = MCConfig((8, 8, 8), 512)
DEFAULT_COMPACT_CONFIG = CompactConfig(256)
DEFAULT_FIRSTORDER_CONFIG = FamilyConfig(2048)
DEFAULT_GLCM_CONFIG = FamilyConfig(2048)


def cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro_autotune.json")


def _migrate_key(key: str) -> str:
    """v1/v2 -> v3 key migration: depth-less keys gain the ``/B1`` segment.

    PR 1-3 sweeps measured single-case launches, so their records are
    exactly the depth-1 entries of the v3 key space; unknown key shapes
    pass through untouched (an unrecognised entry is merely never read).
    """
    parts = key.split("/")
    if len(parts) == 3 and parts[0] in ("diameter", "mc", "compact"):
        return key + "/B1"
    return key


class AutotuneCache:
    """Tiny versioned JSON key->record store with atomic writes.

    On disk: ``{"schema": 3, "entries": {key: record}}``.  Schema v1 (the
    PR 1 layout: a flat ``{key: record}`` object with no ``schema`` field)
    and schema v2 (the PR 2/3 envelope with depth-less keys) are migrated
    on load (see :func:`_migrate_key`); an unknown schema or a malformed
    file reads as empty so stale caches degrade to a re-sweep, never a
    crash.
    """

    def __init__(self, path: str | None = None):
        self.path = path or cache_path()

    def _read_raw(self) -> dict:
        try:
            with open(self.path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return {}
        return data if isinstance(data, dict) else {}

    def _entries(self) -> dict:
        raw = self._read_raw()
        if "schema" not in raw:
            # v1 (PR 1): flat key -> record mapping, depth-less keys
            return {
                _migrate_key(k): v
                for k, v in raw.items() if isinstance(v, dict)
            }
        if raw.get("schema") == 2:
            # v2 (PR 2/3): right envelope, depth-less keys
            ent = raw.get("entries")
            if not isinstance(ent, dict):
                return {}
            return {
                _migrate_key(k): v
                for k, v in ent.items() if isinstance(v, dict)
            }
        if raw.get("schema") != SCHEMA_VERSION:
            return {}  # future schema: don't guess, re-measure
        ent = raw.get("entries")
        return ent if isinstance(ent, dict) else {}

    def get(self, key: str):
        return self._entries().get(key)

    def put(self, key: str, record: dict) -> None:
        raw = self._read_raw()
        schema = raw.get("schema")
        if isinstance(schema, int) and schema > SCHEMA_VERSION:
            # a NEWER code version owns this file; rewriting it as v2 would
            # destroy its entries.  Skip the write -- re-measuring every run
            # is the documented worst case, losing data is not.
            return
        entries = self._entries()  # migrates v1 entries forward
        entries[key] = record
        payload = {"schema": SCHEMA_VERSION, "entries": entries}
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:  # pragma: no cover - cache is best-effort
            try:
                os.unlink(tmp)
            except OSError:
                pass


def batch_bucket(depth: int) -> int:
    """Power-of-two batch-depth bucket (limits the per-depth key space)."""
    b = 1
    while b < int(depth):
        b *= 2
    return b


def sweep_key(bucket: int, backend: str, batch: int = 1) -> str:
    return f"diameter/{backend}/M{int(bucket)}/B{batch_bucket(batch)}"


def mc_key(shape, backend: str, batch: int = 1) -> str:
    nx, ny, nz = (int(s) for s in shape)
    return f"mc/{backend}/S{nx}x{ny}x{nz}/B{batch_bucket(batch)}"


def compact_key(bucket: int, backend: str, batch: int = 1) -> str:
    return f"compact/{backend}/M{int(bucket)}/B{batch_bucket(batch)}"


def family_key(family: str, shape, backend: str, batch: int = 1) -> str:
    """Key for an intensity-family block entry: ``<ns>/<backend>/S../B..``.

    ``family`` is the autotune namespace a :class:`repro.core.plan.FamilySpec`
    registered (``firstorder`` / ``glcm``); ``shape`` the padded-volume
    bucket the launch carries.
    """
    nx, ny, nz = (int(s) for s in shape)
    return f"{family}/{backend}/S{nx}x{ny}x{nz}/B{batch_bucket(batch)}"


def mc_shape_bucket(shape, step: int = 32) -> tuple[int, int, int]:
    """Pad a volume shape up to the autotune bucket grid (limits key space)."""
    return tuple(max(step, int(math.ceil(int(s) / step)) * step) for s in shape)


# ---------------------------------------------------------------------------
# diameter kernel sweep
# ---------------------------------------------------------------------------


def measure_diameter_config(
    bucket: int,
    backend: str,
    variant: str,
    block: int,
    *,
    batch: int = 1,
    repeat: int = 2,
    warmup: int = 1,
    seed: int = 0,
) -> float:
    """Best-of-``repeat`` wall-clock seconds for one configuration.

    ``batch > 1`` measures the launch the pipeline actually issues at
    that depth -- a ``lax.map`` over a (batch, bucket, 3) stack -- since
    grid overhead amortises differently under a mapped sub-batch.
    """
    from repro.core import dispatcher
    from repro.kernels import diameter as dk

    rng = np.random.default_rng(seed)
    kw = dispatcher.kernel_kwargs(backend)

    if batch <= 1:
        verts = np.asarray(rng.normal(size=(bucket, 3)) * 10.0, np.float32)
        mask = np.ones((bucket,), np.float32)

        def call():
            return dk.max_diameters_sq_pallas(
                verts, mask, block=block, variant=variant, **kw
            )
    else:
        verts = np.asarray(
            rng.normal(size=(batch, bucket, 3)) * 10.0, np.float32
        )
        masks = np.ones((batch, bucket), np.float32)

        @jax.jit
        def mapped(v, m):
            return jax.lax.map(
                lambda a: dk.max_diameters_sq_pallas(
                    a[0], a[1], block=block, variant=variant, **kw
                ),
                (v, m),
            )

        def call():
            return mapped(verts, masks)

    for _ in range(warmup):
        jax.block_until_ready(call())
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def sweep_diameter(
    bucket: int,
    backend: str,
    *,
    variants=DEFAULT_VARIANTS,
    blocks=DEFAULT_BLOCKS,
    batch: int = 1,
    repeat: int = 2,
):
    """Measure every (variant, block) candidate; returns (best, table).

    ``table`` maps ``"variant/block"`` to measured microseconds.  Blocks
    larger than the bucket only pad the grid, so they are dropped (the
    smallest candidate block is clamped in instead when all are too big).
    """
    usable = [b for b in blocks if b <= bucket] or [min(min(blocks), bucket)]
    table: dict[str, float] = {}
    best, best_t = None, float("inf")
    for variant in variants:
        for block in usable:
            t = measure_diameter_config(
                bucket, backend, variant, block, batch=batch, repeat=repeat
            )
            table[f"{variant}/{block}"] = t * 1e6
            if t < best_t:
                best, best_t = DiameterConfig(variant, block), t
    return best, table


def _sweep_allowed(backend: str) -> bool:
    flag = os.environ.get("REPRO_AUTOTUNE")
    if flag == "0":
        return False
    if flag == "1":
        return True
    return backend == "pallas"  # interpret timings don't transfer to TPU


def get_diameter_config(
    bucket: int,
    backend: str,
    *,
    batch: int = 1,
    cache: AutotuneCache | None = None,
    variants=DEFAULT_VARIANTS,
    blocks=DEFAULT_BLOCKS,
    repeat: int = 2,
) -> DiameterConfig:
    """Cached-or-swept best (variant, block) for a (bucket, depth) pair.

    The fast path is a cache hit -- no kernel runs at all.  A miss sweeps
    (when allowed, see module docstring) at the batch-depth bucket of
    ``batch``, persists the winner + table, and returns it; when sweeping
    is disallowed the default config is returned without being cached (so
    a later TPU run can still measure).
    """
    from repro.kernels import diameter as dk

    if backend == "ref":
        return DEFAULT_CONFIG
    cache = cache or AutotuneCache()
    key = sweep_key(bucket, backend, batch)
    hit = cache.get(key)
    if hit is not None:
        # validate: the persistent cache can outlive a rename/removal of a
        # variant (or be malformed) -- treat anything unusable as a miss
        try:
            cfg = DiameterConfig(str(hit["variant"]), int(hit["block"]))
        except (KeyError, TypeError, ValueError):
            cfg = None
        if cfg is not None and cfg.variant in dk.VARIANTS and cfg.block > 0:
            return cfg
    if not _sweep_allowed(backend):
        return DEFAULT_CONFIG
    best, table = sweep_diameter(
        bucket, backend, variants=variants, blocks=blocks,
        batch=batch_bucket(batch), repeat=repeat,
    )
    cache.put(
        key,
        {
            "variant": best.variant,
            "block": best.block,
            "us": table[f"{best.variant}/{best.block}"],
            "table": table,
            "swept_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        },
    )
    return best


# ---------------------------------------------------------------------------
# marching-cubes brick sweep
# ---------------------------------------------------------------------------


def _mc_probe_volume(shape) -> np.ndarray:
    """Surface-bearing synthetic mask for MC timing: a centred ellipsoid.

    A representative occupancy matters more than the exact surface: the
    kernel's work is per-brick, and an ellipsoid at ~0.35 radius exercises
    both surface bricks (full triangle tables) and empty/interior ones.
    """
    nx, ny, nz = shape
    g = np.indices(shape, dtype=np.float32)
    c = (np.asarray(shape, np.float32) - 1.0) / 2.0
    r = np.maximum(np.asarray(shape, np.float32) * 0.35, 2.0)
    d2 = sum(((g[i] - c[i]) / r[i]) ** 2 for i in range(3))
    return (d2 < 1.0).astype(np.float32)


def measure_mc_config(
    shape,
    backend: str,
    block,
    chunk: int,
    *,
    batch: int = 1,
    repeat: int = 2,
    warmup: int = 1,
) -> float:
    """Best-of-``repeat`` wall-clock seconds for one MC (block, chunk).

    ``batch > 1`` measures the staged batched launch
    (``mc_volume_area_batch_pallas`` over a (batch, ...) stack) the
    device-pool pass-2a feed actually issues at that depth.
    """
    from repro.core import dispatcher
    from repro.kernels import marching_cubes as mck

    vol = _mc_probe_volume(tuple(int(s) for s in shape))
    kw = dispatcher.kernel_kwargs(backend)

    if batch <= 1:
        def call():
            return mck.mc_volume_area_pallas(
                vol, 0.5, (1.0, 1.0, 1.0), block=tuple(block), chunk=chunk,
                **kw
            )
    else:
        vols = np.broadcast_to(vol, (batch,) + vol.shape)
        sps = np.ones((batch, 3), np.float32)

        def call():
            return mck.mc_volume_area_batch_pallas(
                vols, 0.5, sps, block=tuple(block), chunk=chunk, **kw
            )

    for _ in range(warmup):
        jax.block_until_ready(call())
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def mc_candidates(blocks=DEFAULT_MC_BLOCKS, chunks=DEFAULT_MC_CHUNKS):
    """Valid (block, chunk) pairs: chunk must tile the brick's cell count.

    Candidates that only clamp to an already-listed chunk are dropped so
    the sweep never measures the same effective configuration twice.
    """
    from repro.kernels import marching_cubes as mck

    out = []
    for block in blocks:
        bx, by, bz = (int(b) for b in block)
        usable = []
        for c in chunks:
            try:
                eff = mck.normalize_chunk((bx, by, bz), c)
            except ValueError:
                continue
            if eff == c:  # clamped duplicates measure nothing new
                usable.append(c)
        if not usable:
            usable = [bx * by * bz]
        out.extend(((bx, by, bz), c) for c in usable)
    return out


def sweep_mc(
    shape,
    backend: str,
    *,
    blocks=DEFAULT_MC_BLOCKS,
    chunks=DEFAULT_MC_CHUNKS,
    batch: int = 1,
    repeat: int = 2,
):
    """Measure every valid MC (block, chunk) candidate; (best, table).

    ``table`` maps ``"BXxBYxBZ/chunk"`` to measured microseconds.
    """
    table: dict[str, float] = {}
    best, best_t = None, float("inf")
    for block, chunk in mc_candidates(blocks, chunks):
        t = measure_mc_config(
            shape, backend, block, chunk, batch=batch, repeat=repeat
        )
        table[f"{block[0]}x{block[1]}x{block[2]}/{chunk}"] = t * 1e6
        if t < best_t:
            best, best_t = MCConfig(block, chunk), t
    return best, table


def _valid_mc_record(hit) -> MCConfig | None:
    from repro.kernels import marching_cubes as mck

    try:
        block = tuple(int(b) for b in hit["block"])
        chunk = int(hit["chunk"])
    except (KeyError, TypeError, ValueError):
        return None
    if len(block) != 3 or any(b <= 0 for b in block) or chunk <= 0:
        return None
    try:
        if mck.normalize_chunk(block, chunk) != chunk:
            return None  # stale entry: chunk no longer tiles the brick
    except ValueError:
        return None
    return MCConfig(block, chunk)


def get_mc_config(
    shape,
    backend: str,
    *,
    batch: int = 1,
    cache: AutotuneCache | None = None,
    blocks=DEFAULT_MC_BLOCKS,
    chunks=DEFAULT_MC_CHUNKS,
    repeat: int = 2,
) -> MCConfig:
    """Cached-or-swept best MC (brick, chunk) per (volume bucket, depth).

    Same contract as :func:`get_diameter_config`: cache hit -> no kernel
    runs; miss sweeps when allowed and persists winner + table; disallowed
    sweeps return the default uncached.  ``shape`` should already be an
    autotune bucket (see :func:`mc_shape_bucket`) so the key space stays
    bounded.
    """
    if backend == "ref":
        return DEFAULT_MC_CONFIG
    shape = tuple(int(s) for s in shape)
    cache = cache or AutotuneCache()
    key = mc_key(shape, backend, batch)
    hit = cache.get(key)
    if hit is not None:
        cfg = _valid_mc_record(hit)
        if cfg is not None:
            return cfg
    if not _sweep_allowed(backend):
        return DEFAULT_MC_CONFIG
    best, table = sweep_mc(
        shape, backend, blocks=blocks, chunks=chunks,
        batch=batch_bucket(batch), repeat=repeat,
    )
    cache.put(
        key,
        {
            "block": list(best.block),
            "chunk": best.chunk,
            "us": table[f"{best.block[0]}x{best.block[1]}x{best.block[2]}/{best.chunk}"],
            "table": table,
            "swept_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        },
    )
    return best


# ---------------------------------------------------------------------------
# segmented-compaction scatter-block sweep
# ---------------------------------------------------------------------------


def compact_probe_cap(bucket: int) -> int:
    """Output cap of the compaction probe: the ~25% keep fraction's bucket."""
    return max(512, int(bucket) // 4)


def measure_compact_config(
    bucket: int,
    backend: str,
    block: int,
    *,
    batch: int = 4,
    repeat: int = 2,
    warmup: int = 1,
    seed: int = 0,
) -> float:
    """Best-of-``repeat`` wall-clock seconds for one compaction block.

    The probe keeps ~25% of a ``(batch, bucket)`` stack -- the pipeline's
    typical keep fraction -- and compacts into the ``bucket // 4`` bucket,
    so the measured trade-off (grid steps vs per-step one-hot matmul size)
    matches the production scatter.  The one-hot matmul cost scales with
    the (B, M, cap) triple, so ``batch`` tracks the cap-group depth the
    pipeline actually launches.
    """
    from repro.core import dispatcher
    from repro.kernels import compact as ck

    batch = max(1, int(batch))
    rng = np.random.default_rng(seed)
    verts = np.asarray(rng.normal(size=(batch, bucket, 3)) * 10.0, np.float32)
    keep = rng.random((batch, bucket)) < 0.25
    cap = compact_probe_cap(bucket)
    kw = dispatcher.kernel_kwargs(backend)

    def call():
        return ck.compact_batch_pallas(verts, keep, cap, block=block, **kw)

    for _ in range(warmup):
        jax.block_until_ready(call())
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def sweep_compact(
    bucket: int,
    backend: str,
    *,
    blocks=DEFAULT_COMPACT_BLOCKS,
    batch: int = 4,
    repeat: int = 2,
):
    """Measure every compaction block candidate; returns (best, table).

    ``table`` maps ``str(block)`` to measured microseconds.  Blocks larger
    than the bucket only pad the grid, so they are dropped (the smallest
    candidate is clamped in when all are too big), mirroring the diameter
    sweep's policy.  Blocks whose resident output row would not fit the
    kernel's VMEM at the probe's cap (``kernels/compact.fits``) are
    dropped before anything compiles.
    """
    from repro.kernels import compact as ck

    usable = [b for b in blocks if b <= bucket] or [min(min(blocks), bucket)]
    cap = compact_probe_cap(bucket)
    usable = [b for b in usable if ck.fits(cap, b)]
    if not usable:
        raise ValueError(f"no compaction block fits the VMEM bound at "
                         f"cap {cap}")
    table: dict[str, float] = {}
    best, best_t = None, float("inf")
    for block in usable:
        t = measure_compact_config(
            bucket, backend, block, batch=batch, repeat=repeat
        )
        table[str(block)] = t * 1e6
        if t < best_t:
            best, best_t = CompactConfig(block), t
    return best, table


def get_compact_config(
    bucket: int,
    backend: str,
    *,
    batch: int = 1,
    cache: AutotuneCache | None = None,
    blocks=DEFAULT_COMPACT_BLOCKS,
    repeat: int = 2,
) -> CompactConfig:
    """Cached-or-swept best compaction scatter block per (M bucket, depth).

    Same contract as :func:`get_diameter_config`: cache hit -> no kernel
    runs; miss sweeps when allowed and persists winner + table; disallowed
    sweeps return the default uncached.
    """
    if backend == "ref":
        return DEFAULT_COMPACT_CONFIG
    cache = cache or AutotuneCache()
    key = compact_key(bucket, backend, batch)
    hit = cache.get(key)
    if hit is not None:
        try:
            cfg = CompactConfig(int(hit["block"]))
        except (KeyError, TypeError, ValueError):
            cfg = None
        if cfg is not None and cfg.block > 0:
            return cfg
    if not _sweep_allowed(backend):
        return DEFAULT_COMPACT_CONFIG
    best, table = sweep_compact(
        bucket, backend, blocks=blocks, batch=batch_bucket(batch),
        repeat=repeat,
    )
    cache.put(
        key,
        {
            "block": best.block,
            "us": table[str(best.block)],
            "table": table,
            "swept_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        },
    )
    return best


# ---------------------------------------------------------------------------
# intensity-family (firstorder / glcm) block sweeps
# ---------------------------------------------------------------------------


def _family_blocks(family: str):
    if family == "firstorder":
        return DEFAULT_FIRSTORDER_BLOCKS
    if family == "glcm":
        return DEFAULT_GLCM_BLOCKS
    raise ValueError(f"unknown autotune family namespace {family!r}")


def _family_default(family: str) -> FamilyConfig:
    return (DEFAULT_FIRSTORDER_CONFIG if family == "firstorder"
            else DEFAULT_GLCM_CONFIG)


def _probe_intensity_case(shape, seed: int = 0):
    """Masked intensity probe: the MC ellipsoid mask + a CT-like image."""
    mask = _mc_probe_volume(shape)
    rng = np.random.default_rng(seed)
    image = np.asarray(rng.normal(40.0, 15.0, size=shape), np.float32)
    return image, mask


def measure_family_config(
    family: str,
    shape,
    backend: str,
    block: int,
    *,
    batch: int = 4,
    repeat: int = 2,
    warmup: int = 1,
) -> float:
    """Best-of-``repeat`` wall-clock seconds for one family block.

    Measures the batched launch the executor actually issues: the whole
    (batch, *shape) stack through the family's Pallas kernel.
    """
    from repro.core import dispatcher
    from repro.kernels import firstorder as fok
    from repro.kernels import glcm as gk

    image, mask = _probe_intensity_case(tuple(int(s) for s in shape))
    batch = max(1, int(batch))
    images = np.broadcast_to(image, (batch,) + image.shape)
    masks = np.broadcast_to(mask, (batch,) + mask.shape)
    kw = dispatcher.kernel_kwargs(backend)

    # measure the traced device payload (what the executor launches);
    # feature finalisation is host-side numpy and not part of the launch
    if family == "firstorder":
        def call():
            return fok.firstorder_packed_batch_pallas(
                images, masks, block=block, **kw
            )
    elif family == "glcm":
        def call():
            return gk.glcm_matrix_batch_pallas(
                images, masks, block=block, **kw
            )
    else:
        raise ValueError(f"unknown autotune family namespace {family!r}")

    for _ in range(warmup):
        jax.block_until_ready(call())
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def sweep_family(
    family: str,
    shape,
    backend: str,
    *,
    blocks=None,
    batch: int = 4,
    repeat: int = 2,
):
    """Measure every family block candidate; returns (best, table).

    ``table`` maps ``str(block)`` to measured microseconds.  For the
    first-order family, candidates that are not multiples of the
    canonical accumulation chunk are dropped (they would violate the
    bitwise left-fold contract, not just waste time).
    """
    from repro.kernels import firstorder as fok

    blocks = tuple(blocks) if blocks is not None else _family_blocks(family)
    if family == "firstorder":
        usable = [b for b in blocks if b % fok.CANON_CHUNK == 0]
        if not usable:
            usable = [fok.DEFAULT_BLOCK]
    else:
        usable = list(blocks)
    table: dict[str, float] = {}
    best, best_t = None, float("inf")
    for block in usable:
        t = measure_family_config(
            family, shape, backend, block, batch=batch, repeat=repeat
        )
        table[str(block)] = t * 1e6
        if t < best_t:
            best, best_t = FamilyConfig(block), t
    return best, table


def get_family_config(
    family: str,
    shape,
    backend: str,
    *,
    batch: int = 1,
    cache: AutotuneCache | None = None,
    blocks=None,
    repeat: int = 2,
) -> FamilyConfig:
    """Cached-or-swept best family block per (volume bucket, depth).

    Same contract as :func:`get_diameter_config`: cache hit -> no kernel
    runs; miss sweeps when allowed and persists winner + table; disallowed
    sweeps return the default uncached.  ``shape`` should already be an
    autotune bucket (see :func:`mc_shape_bucket`).
    """
    from repro.kernels import firstorder as fok

    if backend == "ref":
        return _family_default(family)
    shape = tuple(int(s) for s in shape)
    cache = cache or AutotuneCache()
    key = family_key(family, shape, backend, batch)
    hit = cache.get(key)
    if hit is not None:
        try:
            cfg = FamilyConfig(int(hit["block"]))
        except (KeyError, TypeError, ValueError):
            cfg = None
        if cfg is not None and cfg.block > 0 and not (
            family == "firstorder" and cfg.block % fok.CANON_CHUNK
        ):
            return cfg
    if not _sweep_allowed(backend):
        return _family_default(family)
    best, table = sweep_family(
        family, shape, backend, blocks=blocks, batch=batch_bucket(batch),
        repeat=repeat,
    )
    cache.put(
        key,
        {
            "block": best.block,
            "us": table[str(best.block)],
            "table": table,
            "swept_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        },
    )
    return best


# ---------------------------------------------------------------------------
# device->host sync-cost probe
# ---------------------------------------------------------------------------

# fallback per-fetch d2h latency (us) when probing is disallowed: roughly a
# local PCIe/ICI round-trip -- deliberately modest, so the auto schedule
# only abandons the counted default on a MEASURED expensive link
DEFAULT_SYNC_US = 150.0

SYNC_PROBE_SHAPE = (32, 2)  # the (B, 2) count matrix pass 1 actually fetches


def sync_key(backend: str) -> str:
    return f"sync/{backend}"


def measure_sync_cost(*, repeat: int = 64, warmup: int = 8) -> float:
    """Best-of-``repeat`` wall-clock seconds for one small d2h fetch.

    The probe materialises an already-ready (32, 2) int32 device array to
    host numpy -- the exact shape of the counted schedule's pass-1 count
    fetch -- so what is measured is the per-sync LATENCY (dispatch-queue
    flush + transfer round-trip), not bandwidth.  ``block_until_ready``
    before timing keeps device compute out of the measurement.
    """
    x = jax.block_until_ready(jax.numpy.zeros(SYNC_PROBE_SHAPE, jax.numpy.int32))
    for _ in range(warmup):
        np.asarray(x)
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        np.asarray(x)
        best = min(best, time.perf_counter() - t0)
    return best


def _sync_probe_allowed(backend: str) -> bool:
    # same policy shape as _sweep_allowed, but the d2h probe is meaningful
    # on any REAL device (it measures the link, not a kernel), so only the
    # interpret/ref-on-CI determinism concern gates it by default
    flag = os.environ.get("REPRO_AUTOTUNE")
    if flag == "0":
        return False
    if flag == "1":
        return True
    return backend == "pallas"


def get_sync_cost(
    backend: str,
    *,
    cache: AutotuneCache | None = None,
    repeat: int = 64,
) -> float:
    """Cached-or-probed per-fetch d2h latency in MICROSECONDS.

    Same contract as the config getters: cache hit -> no probe runs; a
    miss probes when allowed and persists the measurement under
    ``sync/<backend>``; disallowed probes return :data:`DEFAULT_SYNC_US`
    uncached (so a later real-hardware run can still measure).  Unlike
    the kernel sweeps this consults the cache for EVERY backend,
    including 'ref': the sync cost belongs to the device link, not to a
    kernel configuration, and the cost model must honour a calibrated
    (or operator-pinned) entry regardless of which kernels run.
    """
    cache = cache or AutotuneCache()
    hit = cache.get(sync_key(backend))
    if hit is not None:
        try:
            us = float(hit["us"])
        except (KeyError, TypeError, ValueError):
            us = None
        if us is not None and us > 0:
            return us
    if not _sync_probe_allowed(backend):
        return DEFAULT_SYNC_US
    t = measure_sync_cost(repeat=repeat)
    cache.put(
        sync_key(backend),
        {"us": t * 1e6, "probed_at": time.strftime("%Y-%m-%dT%H:%M:%S")},
    )
    return t * 1e6


# ---------------------------------------------------------------------------
# hardware roofline profile (peak FLOP/s + memory bandwidth) probe
# ---------------------------------------------------------------------------

# Static fallback profiles, used when no ``hw/<backend>`` entry exists and
# probing is disallowed.  The cost model only consumes RATIOS of these
# numbers (compute-vs-memory bound, bucket-vs-bucket cost):
#   pallas          -- the chip JAX runs on, from ``runtime/peaks``
#                      (keyed by ``device_kind``; a chip with no entry is
#                      an error): its MODELLED VPU f32 rate, because the
#                      extraction kernels are elementwise/VPU work, not
#                      MXU matmuls, and its published HBM bandwidth
#   ref / interpret -- a single CPU core driving numpy-like jnp ops
# Unknown backend strings have NO default profile: ``get_hw_profile``
# returns None and the cost model falls back to its analytic constant.
DEFAULT_HW_PROFILES = {
    "ref": {"peak_flops": 8.0e9, "mem_bw": 20.0e9, "source": "default"},
    "interpret": {"peak_flops": 8.0e9, "mem_bw": 20.0e9, "source": "default"},
}


def _default_hw_profile(backend: str) -> dict | None:
    if backend != "pallas":
        return DEFAULT_HW_PROFILES.get(backend)
    from repro.runtime import peaks

    p = peaks.device_peaks(jax.devices()[0].device_kind)
    return {"peak_flops": p["vpu_flops_f32"], "mem_bw": p["hbm_bw"],
            "source": "default"}


HW_PROBE_MATMUL_N = 512   # f32 matmul edge for the peak-FLOP/s probe
HW_PROBE_COPY_ELEMS = 1 << 22  # 16 MiB f32 stream for the bandwidth probe


def hw_key(backend: str) -> str:
    return f"hw/{backend}"


def measure_hw_profile(*, repeat: int = 8, warmup: int = 2) -> dict:
    """Measured ``{"peak_flops", "mem_bw"}`` for the local device.

    Two tiny best-of-``repeat`` probes: an (N, N) f32 matmul for peak
    FLOP/s (2*N^3 flops) and an add-scaled copy over a 16 MiB f32 stream
    for memory bandwidth (read a + read b + write out = 3 arrays).  Both
    are deliberately small -- the probe runs once per host per backend,
    cached under ``hw/<backend>``, and must never dominate a run the way
    a kernel sweep can.
    """
    n = HW_PROBE_MATMUL_N
    a = jax.block_until_ready(
        jax.numpy.ones((n, n), jax.numpy.float32) * 0.5
    )
    mm = jax.jit(lambda x: x @ x)
    for _ in range(warmup):
        jax.block_until_ready(mm(a))
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(mm(a))
        best = min(best, time.perf_counter() - t0)
    peak_flops = 2.0 * n ** 3 / best

    m = HW_PROBE_COPY_ELEMS
    x = jax.block_until_ready(jax.numpy.ones((m,), jax.numpy.float32))
    y = jax.block_until_ready(jax.numpy.full((m,), 2.0, jax.numpy.float32))
    axpy = jax.jit(lambda u, v: u + 0.5 * v)
    for _ in range(warmup):
        jax.block_until_ready(axpy(x, y))
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(axpy(x, y))
        best = min(best, time.perf_counter() - t0)
    mem_bw = 3.0 * 4.0 * m / best
    return {"peak_flops": peak_flops, "mem_bw": mem_bw}


def get_hw_profile(
    backend: str,
    *,
    cache: AutotuneCache | None = None,
    repeat: int = 8,
) -> dict | None:
    """Cached-or-probed hardware roofline profile for ``backend``.

    Contract mirrors :func:`get_sync_cost`: a valid ``hw/<backend>``
    cache entry wins without running anything; a miss probes when allowed
    (same policy as the sync probe -- pallas by default,
    ``REPRO_AUTOTUNE=1`` forces, ``=0`` disables) and persists the
    measurement; a disallowed probe returns the static default uncached
    (:data:`DEFAULT_HW_PROFILES`, or the chip's ``runtime/peaks`` entry
    for ``pallas``).  Returns ``None`` -- "no
    profile exists" -- under ``REPRO_ROOFLINE=0`` (the escape hatch back
    to the cost model's analytic constant) and for backend strings with
    no default profile when probing is disallowed.
    """
    if os.environ.get("REPRO_ROOFLINE") == "0":
        return None
    cache = cache or AutotuneCache()
    hit = cache.get(hw_key(backend))
    if hit is not None:
        try:
            peak = float(hit["peak_flops"])
            bw = float(hit["mem_bw"])
        except (KeyError, TypeError, ValueError):
            peak = bw = 0.0
        if peak > 0 and bw > 0:
            return {"peak_flops": peak, "mem_bw": bw,
                    "source": "measured"}
    if not _sync_probe_allowed(backend):
        return _default_hw_profile(backend)
    prof = measure_hw_profile(repeat=repeat)
    cache.put(
        hw_key(backend),
        {**prof, "probed_at": time.strftime("%Y-%m-%dT%H:%M:%S")},
    )
    return {**prof, "source": "measured"}
