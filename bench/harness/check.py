"""The comparison that decides ``correct``.

Per compared row, the largest deviation of a served or refreshed output
from the reference, in units of the reference's largest magnitude over the
whole graph.  ``max_err`` is the largest over the rows; ``mean_err`` their
mean, which rare one-ulp rounding flips of the program barely move and a
computation at lower precision moves on every row.  A shape mismatch or a
non-finite output reads as infinity.  Each cell's file lists the numbers it
compares with their limits; PERF.md gives the readings they were set from.
"""
from __future__ import annotations

import math

import numpy as np


def row_errors(out: np.ndarray, ref_rows: np.ndarray, scale: float):
    """Per-row largest deviation over ``scale``; None where ``out`` has the
    wrong shape or a non-finite value."""
    out = np.asarray(out, dtype=np.float64)
    ref_rows = np.asarray(ref_rows, dtype=np.float64)
    if out.shape != ref_rows.shape or not np.isfinite(out).all():
        return None
    return np.abs(out - ref_rows).reshape(len(out), -1).max(axis=1) / max(
        scale, 1e-30)


def readings(out: np.ndarray, ref_rows: np.ndarray, scale: float) -> dict:
    """``max_err`` and ``mean_err`` of the compared rows."""
    err = row_errors(out, ref_rows, scale)
    if err is None:
        return {"max_err": math.inf, "mean_err": math.inf}
    if err.size == 0:
        return {"max_err": 0.0, "mean_err": 0.0}
    return {"max_err": float(err.max()), "mean_err": float(err.mean())}


def scale_of(ref: np.ndarray) -> float:
    return float(np.abs(ref).max())


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit, and none missing."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        value = readings.get(name, math.inf)
        checks[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, checks
