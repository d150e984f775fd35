"""Logical-axis sharding rules (MaxText-style) for params and activations.

Every parameter dimension carries a logical axis name (see models/params.P)
and every activation constraint site names its axes.  A *rule set* maps
logical names to mesh axes; the same model code then runs on the single-pod
(16, 16) = ('data', 'model') mesh, the multi-pod (2, 16, 16) =
('pod', 'data', 'model') mesh, or CPU (no mesh: constraints become no-ops).

Default ruleset = FSDP + TP (+ DP over pods):
  * batch       -> ('pod', 'data')        data parallelism
  * heads/mlp/vocab/kv_heads -> 'model'   tensor parallelism
  * embed       -> 'data'                 weight FSDP (ZeRO-3 style; GSPMD
                                          all-gathers at use sites)
  * expert      -> 'data'                 expert parallelism (all-to-all)
  * layers/seq/head_dim -> replicated

Per-arch overrides live in the arch config files.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec


@dataclasses.dataclass(frozen=True)
class Ax:
    """Logical-axes annotation used as a *leaf* inside pytrees (e.g. the
    per-leaf axis names of a decode cache)."""

    axes: tuple

DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": "data",  # FSDP on weight embed dims
    "embed_act": None,  # activation embed dim stays replicated
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "expert": "data",
    "layers": None,
    "ssm_heads": "model",
    "ssm_state": None,
    "cache_batch": ("pod", "data"),
    "cache_seq": None,
}


class _Ctx(threading.local):
    mesh: Mesh | None = None
    rules: dict | None = None


_CTX = _Ctx()


def data_parallel_map(fn, mesh: Mesh | None = None, axis: str = "data",
                      check: bool = True):
    """Shard a batched device function over ``axis`` of a mesh.

    ``fn`` maps arrays with a leading batch dimension to arrays with the
    same leading dimension (e.g. the pipeline's vmapped pass-1 pruning
    bound, the batched segmented compaction, or the staged pass-2a
    marching-cubes batch).  With a mesh the batch axis is split over
    ``axis`` via ``jax.shard_map``, so N devices process N slices
    concurrently; with no mesh (or a mesh without the axis) this is a
    plain ``jax.jit`` -- a strict no-op fallback, which is what lets the
    same pipeline code run on CPU and on a pod.  ``mesh`` defaults to the
    ambient :func:`use_mesh` context.  Callers pad the batch to a
    multiple of the axis size (:func:`pad_batch`; shard_map shapes are
    uniform).
    """
    mesh = mesh if mesh is not None else active_mesh()
    if mesh is None or axis not in mesh.shape:
        return jax.jit(fn)
    spec = PartitionSpec(axis)
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                      check_vma=check)
    )


def axis_size(mesh: Mesh | None, axis: str = "data") -> int:
    """Size of ``axis`` on ``mesh`` (1 without a mesh or the axis)."""
    if mesh is None or axis not in mesh.shape:
        return 1
    return mesh.shape[axis]


def pad_batch(arrays, n: int, mesh: Mesh | None = None, axis: str = "data"):
    """Pad stacked leading dims to a data-axis multiple (first-row copies).

    The companion of :func:`data_parallel_map`: shard_map shapes must be
    uniform across shards, so a batch of ``n`` rows is padded up to the
    next multiple of the axis size by repeating row 0 (duplicate rows can
    never change a per-case result, and callers simply never read the
    padding rows back).  A no-op without a mesh.
    """
    n_data = axis_size(mesh, axis)
    np_ = int(math.ceil(max(n, 1) / n_data)) * n_data
    if np_ == n:
        return tuple(arrays)
    return tuple(
        jnp.concatenate([a, jnp.repeat(a[:1], np_ - n, axis=0)])
        for a in arrays
    )


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None, rules: dict | None = None):
    """Activate a mesh + ruleset for logical constraints and pspec lookup."""
    old = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, dict(DEFAULT_RULES, **(rules or {}))
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _CTX.mesh, _CTX.rules = old


def active_rules() -> dict:
    return _CTX.rules or DEFAULT_RULES


def active_mesh() -> Mesh | None:
    return _CTX.mesh


def _mesh_axes_for(logical: str, rules: dict, mesh: Mesh | None):
    ax = rules.get(logical, None)
    if ax is None:
        return None
    axes = (ax,) if isinstance(ax, str) else tuple(ax)
    if mesh is not None:
        axes = tuple(a for a in axes if a in mesh.shape)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def pspec(axes: tuple, rules: dict | None = None, mesh: Mesh | None = None,
          shape: tuple | None = None) -> PartitionSpec:
    """PartitionSpec for a tuple of logical axis names.

    Guarantees no mesh axis is used twice (later dims lose the conflict and
    stay replicated, matching GSPMD legality).  When ``shape`` is given,
    mesh axes that do not divide the dim are dropped greedily (e.g. 56
    attention heads on a 16-way 'model' axis stay replicated; a batch of 1
    drops the ('pod', 'data') sharding) -- uneven shardings are legal in
    GSPMD but pad silently, which we refuse at framework level.
    """
    rules = rules or active_rules()
    mesh = mesh or active_mesh()
    used: set = set()
    parts = []
    for i, name in enumerate(axes):
        m = None if name is None else _mesh_axes_for(name, rules, mesh)
        if m is None:
            parts.append(None)
            continue
        ms = (m,) if isinstance(m, str) else tuple(m)
        ms = tuple(a for a in ms if a not in used)
        if shape is not None and mesh is not None:
            dim = shape[i]
            kept = []
            prod = 1
            for a in ms:  # greedy prefix that divides the dim
                if dim % (prod * mesh.shape[a]) == 0:
                    kept.append(a)
                    prod *= mesh.shape[a]
                else:
                    break
            ms = tuple(kept)
        if not ms:
            parts.append(None)
            continue
        used.update(ms)
        parts.append(ms if len(ms) > 1 else ms[0])
    return PartitionSpec(*parts)


def constrain(x, *axes):
    """Sharding constraint by logical axes; no-op without an active mesh."""
    mesh = active_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, pspec(tuple(axes), shape=x.shape))
    )


def named_sharding(axes: tuple, mesh: Mesh | None = None, rules=None) -> NamedSharding:
    mesh = mesh or active_mesh()
    assert mesh is not None, "named_sharding requires a mesh"
    return NamedSharding(mesh, pspec(tuple(axes), rules=rules, mesh=mesh))


def param_shardings(spec_tree, mesh: Mesh, rules=None):
    """Tree of NamedShardings matching a params spec tree."""
    from repro.models import params as pmod

    rules = dict(DEFAULT_RULES, **(rules or {}))

    def one(leaf):
        return NamedSharding(
            mesh, pspec(leaf.axes, rules=rules, mesh=mesh, shape=leaf.shape)
        )

    flat = {path: one(leaf) for path, leaf in pmod.tree_paths(spec_tree)}
    return pmod._unflatten(flat)


def tree_shardings(abstract_tree, axes_tree, mesh: Mesh, rules=None):
    """NamedShardings for an arbitrary pytree annotated with ``Ax`` leaves.

    ``axes_tree`` mirrors ``abstract_tree`` but each array leaf is replaced
    by an ``Ax(axes)`` annotation (treated as a leaf because Ax is not a
    registered pytree).
    """
    rules = dict(DEFAULT_RULES, **(rules or {}))

    def one(sds, ax):
        assert isinstance(ax, Ax), ax
        return NamedSharding(
            mesh, pspec(ax.axes, rules=rules, mesh=mesh, shape=sds.shape)
        )

    return jax.tree.map(one, abstract_tree, axes_tree,
                        is_leaf=lambda x: isinstance(x, Ax))
