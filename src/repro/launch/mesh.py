"""Production meshes.

TPU v5e pods: single pod = 256 chips as (16, 16) = ('data', 'model');
multi-pod = 2 pods = 512 chips as (2, 16, 16) = ('pod', 'data', 'model')
with DCN/ICI over the 'pod' axis.  Functions (not module constants) so that
importing this module never touches jax device state -- the dry-run sets
``xla_force_host_platform_device_count`` before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests / local runs).

    The axes are ``Auto``: the extraction executor slices and re-stacks
    data-sharded batches between its sharded launches, which explicit
    axes (``jax.make_mesh``'s default) would require it to annotate.
    """
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))

