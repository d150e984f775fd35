"""Shared helpers for the benchmark harness.

Every benchmark emits ``name,us_per_call,derived`` CSV rows (run.py collects
them).  ``derived`` is a ';'-separated key=value list specific to each
benchmark (speedups, fractions, projections).
"""
from __future__ import annotations

import time

import jax

from repro.runtime.peaks import V5E as _PUBLISHED


def timeit(fn, *args, repeat: int = 3, warmup: int = 1, **kw):
    """Median wall-clock seconds of ``fn(*args)`` with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def row(name: str, us: float, **derived) -> str:
    d = ";".join(f"{k}={v}" for k, v in derived.items())
    return f"{name},{us:.1f},{d}"


# v5e projection constants: the peaks of runtime/peaks (the one table)
# plus two MODELLED rates, which no published figure gives
V5E = {
    **_PUBLISHED,
    "peak_flops_f32": _PUBLISHED["peak_flops_bf16"] / 4,  # modelled: f32
    # on the MXU as multi-pass bf16
    "vpu_flops": _PUBLISHED["vpu_flops_f32"],  # modelled (runtime/peaks)
    "pcie_bw": 32e9,     # modelled: host->device B/s (transfer projection)
}


def diameter_projection(M: int, block: int, variant: str) -> float:
    """Roofline seconds for one diameter-kernel configuration on a v5e.

    Unlike the generic :func:`tpu_projection`, this accounts for variants
    that split work across units: the 'gram' variant's pair sweep runs on
    the MXU while only combo-assembly stays on the VPU, so the bound is
    max(VPU term, MXU term, HBM term).
    """
    from repro.kernels import diameter as dk

    fl = dk.flop_estimate(M, block, variant)
    by = dk.bytes_estimate(M, block, variant)
    mx = dk.mxu_flop_estimate(M, block, variant)
    return max(
        fl / V5E["vpu_flops"], mx / V5E["peak_flops_f32"], by / V5E["hbm_bw"]
    )


def tpu_projection(flops: float, bytes_hbm: float, unit: str = "vpu") -> float:
    """Roofline lower-bound seconds on one v5e chip.

    ``unit``: 'mxu_f32' / 'mxu_bf16' for matmul-dominated kernels (the MC
    one-hot table gather), 'vpu' for elementwise-dominated ones (the
    pairwise diameter sweep) -- using MXU peak for elementwise work would
    overstate speedups ~25x.
    """
    peak = {"mxu_f32": V5E["peak_flops_f32"],
            "mxu_bf16": V5E["peak_flops_bf16"],
            "vpu": V5E["vpu_flops"]}[unit]
    return max(flops / peak, bytes_hbm / V5E["hbm_bw"])
