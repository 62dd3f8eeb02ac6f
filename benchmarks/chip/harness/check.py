"""The numbers that decide ``correct``, and their limits.

Training compares, against the float32 reference over the same first
steps: each step's loss (largest absolute gap, in nats); the per-leaf
norm of the first gradient as the optimizer got it; and the per-leaf
norm of the weights' change over the steps. The first gradient is also
read as the norm of its difference from the reference's
(``grad_diff_gap``), scaled and taken by the worst leaf alike. A norm is compared as the
gap between the program's norm and the reference's -- not the norm of
their difference -- over the larger of the reference's norm of that
leaf and of the median leaf, and the worst leaf counts. Leaves whose
reference gradient is under a thousandth of the median leaf's move by
round-off alone and are left out of the change.
"""
from __future__ import annotations

import numpy as np

ROUNDOFF_ONLY = 1e-3


def norm_gap(prog: np.ndarray, ref: np.ndarray,
             keep: np.ndarray | None = None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    scale = np.maximum(ref, np.median(ref[keep]))
    gaps = np.abs(prog - ref) / scale
    return float(np.max(gaps[keep]))


def diff_gap(diff: np.ndarray, ref: np.ndarray) -> float:
    """The worst leaf's norm of the difference from the reference, over
    the larger of the reference's norm of that leaf and of the median
    leaf."""
    diff, ref = np.asarray(diff, np.float64), np.asarray(ref, np.float64)
    return float(np.max(diff / np.maximum(ref, np.median(ref))))


def train_readings(prog: dict, ref: dict, grad_diff=None) -> dict:
    g_ref = np.asarray(ref["grad_norms"], np.float64)
    moved = g_ref >= ROUNDOFF_ONLY * np.median(g_ref)
    out = {
        "loss_gap": float(np.max(np.abs(np.asarray(prog["losses"])
                                        - np.asarray(ref["losses"])))),
        "grad_norm_gap": norm_gap(prog["grad_norms"], g_ref),
        "change_norm_gap": norm_gap(prog["change_norms"],
                                    ref["change_norms"], moved),
    }
    if grad_diff is not None:
        out["grad_diff_gap"] = diff_gap(grad_diff, g_ref)
    return out


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, per number, ``{"value", "limit"}``. A number
    without a limit, or a limit without a number, is not correct."""
    shown = {}
    ok = bool(limits)
    for name, lim in limits.items():
        value = readings.get(name)
        shown[name] = {"value": value, "limit": lim["limit"]}
        if value is None or not np.isfinite(value) or value > lim["limit"]:
            ok = False
    return ok, shown
