"""Device-resident segmented survivor compaction (pass 1b of the pipeline).

PR 2's two-pass pipeline computes the exact pruning bound as one vmapped
kernel but then compacts each case's survivors HOST-side (``np.nonzero`` +
``np.pad`` per case) -- the last CPU<->device round trip between pass 1 and
pass 2, exactly the ping-pong PyRadiomics-cuda exists to eliminate.  This
module is the device-side replacement: a **segmented compaction** primitive
that scatters the survivors of a keep mask into the first M' slots of a
static M'-bucket, batched over a stack of same-cap cases, so pass 1 emits
already-bucketed ``(verts, vmask)`` device arrays that feed pass 2 directly.

Semantics (shared by both paths, and by the host path they replace):

  * survivors keep their original relative order (stable compaction);
  * slot ``j`` of the output holds the j-th survivor; slots ``>= M'`` are
    zero with a False mask -- bit-identical to the host path's
    ``verts[np.nonzero(keep)]`` + zero ``np.pad``;
  * survivors beyond the cap are dropped (callers size the cap from the
    survivor count, so this only happens under a deliberately small cap);
  * the returned count ``n`` is the TOTAL survivor count (pre-drop),
    matching ``ref.compact_vertices``.

Two implementations:

``compact_batch_ref``
    jnp reference/oracle: exclusive prefix sum over the mask gives each
    survivor its output slot; a ``mode='drop'`` scatter writes them.  Runs
    on any backend; this is also the 'ref' dispatch target.

``compact_batch_pallas``
    Pallas TPU kernel.  The grid walks ``(case, block)``; an SMEM scalar
    carries the running survivor count across a case's sequential blocks
    (the same revisited-accumulator idiom as the diameter 'seqacc'
    variant), and the per-block scatter is realised as a one-hot matmul:
    ``out += verts_block (3, B) @ onehot (B, cap)`` where
    ``onehot[i, j] = keep_i & (prefix_i == j)``.  A 0/1 matmul copies
    floats exactly (x * 1.0 + 0.0 terms), so the result is bit-identical
    to the reference path.  Scatter-by-matmul keeps the store pattern
    static -- the MXU-native way to compact on TPU, where per-element
    dynamic stores are not an option.  ``block`` is the autotuned axis
    (``runtime/autotune`` sweeps it per M bucket).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 256


def _compact_one_ref(verts, keep, cap: int):
    """Single-case jnp compaction: (M, 3), (M,) -> (cap, 3), (cap,), n."""
    k = keep.astype(bool)
    ki = k.astype(jnp.int32)
    pos = jnp.cumsum(ki) - 1  # exclusive prefix sum = output slot
    # non-survivors (and survivors past the cap) land out of bounds: dropped
    idx = jnp.where(k, pos, cap)
    out = jnp.zeros((cap, 3), jnp.float32).at[idx].set(verts, mode="drop")
    n = jnp.sum(ki)
    mask = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(n, cap)
    return out, mask, n


@functools.partial(jax.jit, static_argnames=("cap",))
def compact_batch_ref(verts, keep, cap: int):
    """Batched reference compaction.

    ``verts``: (B, M, 3), ``keep``: (B, M) -> ``(out, mask, n)`` with
    ``out``: (B, cap, 3) float32, ``mask``: (B, cap) bool, ``n``: (B,) int32.
    """
    verts = jnp.asarray(verts, jnp.float32)
    keep = jnp.asarray(keep)
    return jax.vmap(lambda v, k: _compact_one_ref(v, k, cap))(verts, keep)


_LANE = 128


def padded_cap(cap: int, block: int) -> int:
    """Lane width of the kernel's resident output row for ``cap`` slots.

    ``cap`` rounded up to whole lanes, plus room for one scatter window
    (``block + 128`` slots) opened at the last in-range aligned offset:
    windows never run off the row, and the slots past ``cap`` they touch
    are cut away afterwards (survivors beyond the cap are dropped).
    """
    return -(-cap // _LANE) * _LANE + block + _LANE


# scoped-VMEM ceiling the kernel may ask for (a v5e core has 128 MiB)
VMEM_LIMIT = 100 << 20


def vmem_bytes(cap: int, block: int) -> int:
    """Scoped VMEM the kernel needs: the double-buffered (3->8, cap_pad)
    output row, the in/out blocks, the (block, block) prefix operator and
    the (block + 128, block) one-hot window."""
    f32 = 4
    out = 2 * 8 * padded_cap(cap, block) * f32
    ins = 2 * 2 * 8 * block * f32
    work = (block * block + (block + _LANE) * block + 8 * (block + _LANE)) * f32
    return out + ins + 2 * work


def fits(cap: int, block: int) -> bool:
    """Whether ``(cap, block)`` stays under :data:`VMEM_LIMIT` -- the bound
    the autotune candidate list is filtered by."""
    return vmem_bytes(cap, block) + (8 << 20) <= VMEM_LIMIT


def _compact_kernel(kref, vref, vout, base, *, block: int, cap: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():  # new case: reset the accumulator row + running offset
        vout[...] = jnp.zeros_like(vout)
        base[0] = 0

    b0 = base[0]
    k = kref[0]  # (1, block) 0/1 keep flags

    @pl.when(b0 < cap)
    def _():
        # inclusive prefix sum as a 0/1 matmul with the upper-triangular
        # ones operator (Mosaic has no cumsum): exact small integers
        r = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        upper = (r <= c).astype(jnp.float32)
        incl = jax.lax.dot_general(
            k, upper, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (1, block)
        # this block's survivors land in slots [b0, b0 + block): open a
        # lane-aligned window of block + 128 slots over them
        w0 = pl.multiple_of((b0 // _LANE) * _LANE, _LANE)
        local = incl - 1.0 + (b0 - w0).astype(jnp.float32)  # slot - w0
        width = block + _LANE
        rows = jax.lax.broadcasted_iota(jnp.int32, (width, block), 0)
        onehot_t = ((rows.astype(jnp.float32) == local) & (k > 0.0)).astype(
            jnp.float32)  # (width, block): window slot <- survivor
        # scatter-by-matmul: each window slot receives at most one
        # survivor (slots are unique), every other term is x * 0.0 -- exact
        # in f32 at HIGHEST precision (the MXU default rounds x to bf16)
        win = jax.lax.dot_general(
            vref[0], onehot_t, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (3, width)
        vout[0, :, pl.ds(w0, width)] += win

    base[0] = b0 + jnp.sum(k).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("cap", "block", "interpret")
)
def compact_batch_pallas(
    verts, keep, cap: int, *, block: int = DEFAULT_BLOCK,
    interpret: bool = False
):
    """Batched Pallas segmented compaction; same contract as the ref path.

    ``verts``: (B, M, 3), ``keep``: (B, M) -> ``(out, mask, n)``.  The grid
    is ``(B, M/block)``; case ``b``'s blocks run sequentially, carrying the
    survivor offset in SMEM, and revisit one resident (3, cap) output row.
    Each block scatters through a one-hot window of ``block + 128`` slots
    only, so the working set is bounded by ``block``, not by ``cap``.
    """
    verts = jnp.asarray(verts, jnp.float32)
    kf = jnp.asarray(keep).astype(jnp.float32)
    B, M, _ = verts.shape
    nb = max(1, -(-M // block))
    pad = nb * block - M
    v = jnp.pad(verts, ((0, 0), (0, pad), (0, 0))).transpose(0, 2, 1)
    km = jnp.pad(kf, ((0, 0), (0, pad)))[:, None, :]  # (B, 1, nb*block)
    cap_pad = padded_cap(cap, block)

    out = pl.pallas_call(
        functools.partial(_compact_kernel, block=block, cap=cap),
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, 1, block), lambda b, t: (b, 0, t)),
            pl.BlockSpec((1, 3, block), lambda b, t: (b, 0, t)),
        ],
        out_specs=pl.BlockSpec((1, 3, cap_pad), lambda b, t: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 3, cap_pad), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=min(
            VMEM_LIMIT, max(32 << 20, vmem_bytes(cap, block) + (8 << 20)))),
        interpret=interpret,
    )(km, v)

    n = jnp.sum(kf > 0.0, axis=1).astype(jnp.int32)  # (B,)
    mask = (
        jax.lax.broadcasted_iota(jnp.int32, (B, cap), 1)
        < jnp.minimum(n, cap)[:, None]
    )
    return out[:, :, :cap].transpose(0, 2, 1), mask, n
