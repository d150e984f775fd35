"""Int8 error-feedback gradient compression for cross-pod reduction.

At multi-pod scale the data-parallel gradient all-reduce crosses the slow
pod interconnect; 4x compression (f32 -> int8) cuts that traffic
proportionally.  Implementation (1-bit-Adam-family scheme, k=8 bits):

    residual e_t carried per leaf (error feedback)
    g' = g + e_t
    q  = clip(round(g' / scale), -127, 127), scale = max|g'| / 127  per leaf
    wire format int8; reduction upcasts to int32 (no overflow for <= 2^24
    participants); dequantised mean applied, e_{t+1} = g' - q * scale

Error feedback makes the quantisation noise telescope: the *accumulated*
applied update tracks the true gradient sum, so convergence matches
uncompressed SGD/Adam up to higher-order terms (tested in
tests/test_compression.py).

``compressed_psum_tree`` works under ``shard_map`` (axis_name present) or
as a pure single-process simulation (axis_name=None) for tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _shared_scale(g32, axis_name=None):
    """One scale for ALL workers: quantising with per-worker scales and
    dequantising the wire-sum with any single scale is a biased reduction
    (q_i·(s−s_i) error terms); the scale must be agreed *before*
    quantising — one extra scalar pmax on the wire."""
    amax = jnp.max(jnp.abs(g32))
    if axis_name is not None:
        amax = jax.lax.pmax(amax, axis_name)
    return jnp.where(amax > 0, amax / 127.0, 1.0)


def compress_leaf(g, err, scale=None):
    """Returns (int8 payload, scale, new_error)."""
    g32 = g.astype(jnp.float32) + err
    if scale is None:
        scale = _shared_scale(g32)
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    deq = q.astype(jnp.float32) * scale
    return q, scale, g32 - deq


def reduce_compressed(q, scale, axis_name=None):
    """Mean-reduce quantised gradients across data parallel workers.

    ``scale`` must be identical on every worker (see ``_shared_scale``).
    """
    qi = q.astype(jnp.int32)
    if axis_name is None:
        return qi.astype(jnp.float32) * scale
    total = jax.lax.psum(qi, axis_name)  # int32 wire-sum of int8 payloads
    # the worker count is static: a psum of a constant would need an
    # ambient mesh under an explicit-axes mesh (jax.make_mesh's default)
    n = jax.lax.axis_size(axis_name)
    return total.astype(jnp.float32) * scale / jnp.float32(n)


def compressed_psum_tree(grads, err_tree, axis_name=None):
    """Error-feedback int8 psum over a gradient pytree.

    Returns (reduced_grads, new_err_tree).
    """
    leaves, tdef = jax.tree.flatten(grads)
    errs = jax.tree.leaves(err_tree)
    outs, new_errs = [], []
    for g, e in zip(leaves, errs):
        g32 = g.astype(jnp.float32) + e
        scale = _shared_scale(g32, axis_name)
        q, scale, ne = compress_leaf(g, e, scale=scale)
        outs.append(reduce_compressed(q, scale, axis_name).astype(g.dtype))
        new_errs.append(ne)
    return jax.tree.unflatten(tdef, outs), jax.tree.unflatten(tdef, new_errs)


def init_error_state(params):
    return jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
