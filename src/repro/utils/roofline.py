"""Roofline accounting from compiled XLA artifacts (no hardware needed).

Three terms per (arch x shape x mesh), all in seconds:

    compute    = HLO_FLOPs_per_device / peak_FLOP/s
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / link_bw

``compiled.cost_analysis()`` provides per-device FLOPs and bytes (the
compiled module is the per-device SPMD program).  Collective bytes are NOT
in cost_analysis, so we parse the optimized HLO text and sum the result
shapes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (including their -start async forms).  Shapes in
post-SPMD HLO are already per-device, so dividing by per-link bandwidth
matches the brief's ``collective_bytes / (chips * link_bw)`` with
``collective_bytes = per_device_bytes * chips``.
"""
from __future__ import annotations

import re

from repro.runtime.peaks import V5E as HW

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# e.g.:  %ag = bf16[4,512]{1,0} all-gather(...)   or tuple results
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\s*\("
)


def shape_bytes(text: str) -> int:
    """Sum bytes of every dtype[shape] occurrence in ``text``."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved, by collective kind (result-shape sizes).

    '-done' ops are skipped so async start/done pairs count once.
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for m in _OP_RE.finditer(hlo_text):
        shape_txt, kind, startdone = m.group(1), m.group(2), m.group(3)
        if startdone == "-done":
            continue
        out[kind] += shape_bytes(shape_txt)
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


# ---------------------------------------------------------------------------
# Loop-aware cost correction.
#
# XLA's HloCostAnalysis counts a while-loop body ONCE, not times its trip
# count -- so scanned layer stacks (28-48 trips), blockwise-attention KV
# scans and chunked-CE scans are badly undercounted.  We therefore walk the
# *jaxpr* of the lowered function twice -- once multiplying scan bodies by
# their static `length`, once not -- and scale the HLO numbers by the ratio.
# This is exact for FLOPs up to sharding uniformity across iterations (all
# our scan bodies shard identically per iteration).
#
# The walk counts dot_general FLOPs exactly (2*M*N*K) AND one FLOP per
# output element of elementwise arithmetic / one per input element of
# reductions: the extraction kernels (pair sweeps, marching cubes, the
# intensity families) are elementwise-dominated with NO dots at all, so a
# dot-only count would leave their correction ratio pinned at 1.0 and the
# scan undercount uncorrected.
# ---------------------------------------------------------------------------

# elementwise primitives costed at one FLOP per OUTPUT element
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "integer_pow",
    "exp", "log", "log1p", "sqrt", "rsqrt", "abs", "neg", "floor",
    "ceil", "round", "sign", "tanh", "logistic", "erf", "expm1",
    "and", "or", "xor", "not", "eq", "ne", "lt", "le", "gt", "ge",
    "select_n", "clamp", "rem", "nextafter", "atan2",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
})

# reduction primitives costed at one FLOP per INPUT element
_REDUCTIONS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin",
})


def _nelems(aval) -> float:
    try:
        n = 1
        for d in aval.shape:
            n *= int(d)
        return float(n)
    except Exception:
        return 0.0


def _aval_bytes(aval) -> float:
    try:
        n = 1
        for d in aval.shape:
            n *= int(d)
        return float(n * aval.dtype.itemsize)
    except Exception:
        return 0.0


def _dot_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    batch = 1
    for d in lb:
        batch *= lhs.shape[d]
    contract = 1
    for d in lc:
        contract *= lhs.shape[d]
    lfree = 1
    for i, d in enumerate(lhs.shape):
        if i not in lb and i not in lc:
            lfree *= d
    rfree = 1
    for i, d in enumerate(rhs.shape):
        if i not in rb and i not in rc:
            rfree *= d
    return 2.0 * batch * lfree * rfree * contract


def jaxpr_cost(jaxpr, multiply_loops: bool = True):
    """(flops, naive_bytes) of a (closed) jaxpr, loop-aware.

    FLOPs = exact dot_general count + one per elementwise output element
    + one per reduction input element (see the section comment above).
    """
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    flops = 0.0
    byts = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            flops += _dot_flops(eqn)
            byts += sum(_aval_bytes(v.aval) for v in list(eqn.invars) + list(eqn.outvars))
            continue
        sub_mult = 1.0
        subs = []
        p = eqn.params
        if name == "scan":
            subs = [p["jaxpr"]]
            sub_mult = float(p.get("length", 1)) if multiply_loops else 1.0
        elif name == "while":
            subs = [p["body_jaxpr"]]
        elif name == "cond":
            subs = list(p["branches"])[:1]  # branches are cost-equivalent here
        elif "jaxpr" in p:
            subs = [p["jaxpr"]]
        elif "call_jaxpr" in p:
            subs = [p["call_jaxpr"]]
        elif "branches" in p:
            subs = list(p["branches"])[:1]
        if subs:
            for s in subs:
                f, b = jaxpr_cost(s, multiply_loops)
                flops += sub_mult * f
                byts += sub_mult * b
        else:
            if name in _ELEMENTWISE:
                flops += sum(_nelems(v.aval) for v in eqn.outvars)
            elif name in _REDUCTIONS:
                flops += sum(_nelems(v.aval) for v in eqn.invars)
            byts += sum(_aval_bytes(v.aval) for v in list(eqn.invars) + list(eqn.outvars))
    return flops, byts


def loop_corrections(fn, *abstract_args) -> tuple[float, float, dict]:
    """(flop_correction, byte_correction, detail) for a traced function."""
    import jax

    closed = jax.make_jaxpr(fn)(*abstract_args)
    f1, b1 = jaxpr_cost(closed, multiply_loops=True)
    f0, b0 = jaxpr_cost(closed, multiply_loops=False)
    detail = {
        "jaxpr_dot_flops_total": f1,
        "jaxpr_dot_flops_loops_once": f0,
    }
    fc = f1 / f0 if f0 > 0 else 1.0
    bc = b1 / b0 if b0 > 0 else 1.0
    return fc, bc, detail


def compiled_cost(compiled) -> tuple[float, float]:
    """Uncorrected (flops, bytes accessed) straight off ``cost_analysis()``.

    Handles the older-jax list-of-dict return form; missing fields read
    as zero.  Pair with :func:`loop_corrections` for scan-heavy programs.
    """
    ca = compiled.cost_analysis()
    if isinstance(ca, list):  # older jax returns [dict]
        ca = ca[0]
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def cost_terms(compiled, n_chips: int, model_flops: float | None = None,
               hlo_text: str | None = None, flop_correction: float = 1.0,
               byte_correction: float = 1.0,
               bytes_override: float | None = None,
               collective_total_override: float | None = None,
               structural_bytes: float | None = None,
               hw: dict | None = None) -> dict:
    """The roofline report for one compiled executable.

    ``hw`` overrides the published v5e peaks (``runtime/peaks``) with a
    measured hardware profile (``peak_flops_bf16`` / ``hbm_bw`` /
    ``ici_bw`` keys; missing keys fall back to the published ones) -- see
    ``repro.runtime.autotune.get_hw_profile``.
    """
    raw_flops, raw_bytes = compiled_cost(compiled)
    flops = raw_flops * flop_correction
    if bytes_override is not None:
        bytes_acc = bytes_override
    else:
        bytes_acc = raw_bytes * byte_correction
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    coll_total = (
        collective_total_override
        if collective_total_override is not None
        else coll["total"]
    )

    hw = {**HW, **(hw or {})}
    t_compute = flops / hw["peak_flops_bf16"]
    t_memory = bytes_acc / hw["hbm_bw"]
    t_collective = coll_total / hw["ici_bw"]
    terms = {
        "compute_s": t_compute,
        "memory_s": t_memory,
        "collective_s": t_collective,
    }
    if structural_bytes is not None:
        terms["memory_s"] = structural_bytes / hw["hbm_bw"]
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    report = {
        **terms,
        "dominant": dominant,
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "memory_s_xla": t_memory,
        "structural_hbm_bytes": structural_bytes,
        "flop_correction": flop_correction,
        "byte_correction": byte_correction,
        "collective_bytes_per_device": coll_total,
        "collective_bytes_loops_once": coll["total"],
        "collective_ops": coll["count"],
        "collective_breakdown": {k: coll[k] for k in _COLLECTIVES},
        "n_chips": n_chips,
    }
    if model_flops is not None and flops > 0:
        report["model_flops_total"] = model_flops
        report["useful_flops_ratio"] = model_flops / (flops * n_chips)
    if bound > 0:
        # roofline fraction: how much of the bound step is pure compute
        report["roofline_fraction"] = t_compute / bound
    return report


def memory_report(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = int(v)
    if "argument_size_in_bytes" in out and "temp_size_in_bytes" in out:
        out["hbm_total_bytes"] = (
            out["argument_size_in_bytes"] + out["temp_size_in_bytes"]
        )
    return out


def structural_hbm_bytes(cfg, shape, n_chips: int, tp: int = 16,
                         dp: int = 16, cache_shard: int = 1) -> float:
    """Structural per-chip HBM-traffic model for a TPU execution.

    XLA's `bytes accessed` on the CPU backend counts every op boundary --
    on a TPU the attention/SSM inner loops run fused in VMEM, so real HBM
    traffic is dominated by: weight reads (x3 for fwd/remat/bwd in
    training), optimizer state read+write, saved layer-boundary
    activations, logits, and (decode) the KV cache.  This model counts
    exactly those.  Reported alongside the XLA number; see DESIGN.md
    §Roofline-accounting.
    """
    N = cfg.n_active_params
    b_loc = max(1, shape.global_batch // dp)
    s = shape.seq_len
    d = cfg.d_model
    L = cfg.n_layers + cfg.n_encoder_layers
    vp = cfg.vocab_padded
    w_read = 2.0 * N / tp  # bf16 weight shard streamed per pass
    if shape.kind == "train":
        passes = 3.0  # fwd + remat-recompute + bwd
        opt = 10.0 * 4.0 * N / n_chips  # p,m,v,g r/w at f32, fully sharded
        acts = 2.0 * L * b_loc * s * d * 2.0  # save + reload layer inputs
        logits = 3.0 * b_loc * s * (vp / tp) * 2.0
        return passes * w_read + opt + acts + logits
    if shape.kind == "prefill":
        acts = 2.0 * L * b_loc * s * d * 2.0
        logits = b_loc * 1 * (vp / tp) * 2.0
        return w_read + acts + logits
    # decode: one token -- weights + cache traffic dominate
    cache = 0.0
    if cfg.family == "ssm":
        nh = d // 64
        cache = 2.0 * L * b_loc * (2 * d + nh * 64 * 64 * 2) * 2.0
    elif cfg.family == "hybrid":
        di = cfg.ssm_expand * d
        nh = di // cfg.head_dim
        for i in range(cfg.n_layers):
            w = cfg.attn_window if i not in cfg.global_attn_layers else 0
            slots = min(s, w) if w else s
            cache += b_loc * slots * cfg.n_kv_heads * cfg.head_dim * 2 * 2.0
            cache += b_loc * nh * cfg.ssm_state * cfg.head_dim * 4 * 2.0
    else:
        kv = max(1, cfg.n_kv_heads // 1)  # kv heads often replicated on TP
        cache = L * b_loc * s * kv * cfg.head_dim * 2 * 2.0
        if cfg.family in ("audio", "encdec"):
            cache += L * b_loc * (s // 4) * kv * cfg.head_dim * 2 * 2.0
    cache /= max(1, cache_shard)  # seq-sharded cache (flash-decode layout)
    logits = b_loc * (vp / tp) * 2.0
    return w_read + cache + logits


def model_flops_train(cfg, tokens: int) -> float:
    """6 * N_active * D (the standard training-FLOPs estimate)."""
    return 6.0 * cfg.n_active_params * tokens


def model_flops_decode(cfg, tokens: int) -> float:
    return 2.0 * cfg.n_active_params * tokens
