"""The comparison that decides ``correct``.

A configuration's ``check`` maps each number compared to the features it
covers, how a gap is measured, and its limit::

    "volume_rel": {"features": ["MeshVolume"], "gap": "rel", "limit": 1e-5}

``rel`` is ``|answer - reference| / |reference|`` and ``abs`` is
``|answer - reference|``; the number is the widest gap over every feature it
covers in every answer the window returned.  A missing or non-finite
answer reads as infinite.
"""
from __future__ import annotations

import math


def gap(got, want, kind: str) -> float:
    got, want = float(got), float(want)
    if not math.isfinite(got):
        return math.inf
    d = abs(got - want)
    if kind == "abs":
        return d
    if kind == "rel":
        return d / abs(want) if want else d
    raise ValueError(f"unknown gap {kind!r}")


def numbers(check: dict, answers, refs) -> dict:
    """``{name: widest gap}`` over ``answers``: ``(case position, {feature:
    value})`` pairs, each held against ``refs[position]``."""
    out = {}
    for name, spec in check.items():
        worst = 0.0
        for pos, ans in answers:
            for feat in spec["features"]:
                got = ans.get(feat, math.nan)
                worst = max(worst, gap(got, refs[pos][feat], spec["gap"]))
        out[name] = worst
    return out


def verdict(check: dict, found: dict) -> tuple:
    """``(correct, {name: {"value", "limit"}})``; an exact comparison has
    the limit 0 and passes only at 0."""
    table = {name: {"value": found[name], "limit": spec["limit"]}
             for name, spec in check.items()}
    ok = all(v["value"] <= v["limit"] for v in table.values())
    return ok, table
