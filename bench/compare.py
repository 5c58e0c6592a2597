"""The numbers that decide ``correct``.

Training cells compare the first steps of the timed path with the
reference's: each step's loss, the first gradient as the optimizer holds
it, and the parameters' change after the last checked step.  Norms are
compared leaf by leaf as the gap between the two norms, over the larger
of the reference leaf's norm and the median leaf's norm; the worst leaf
counts.  A leaf whose reference gradient is under a thousandth of the
median leaf's moves by round-off alone under Adam and is left out of the
change.
"""
from __future__ import annotations

import jax
import numpy as np


def _norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(l, np.float64)))
                     for l in jax.tree.leaves(tree)])


def loss_gap(prog: list, ref: list) -> float:
    return max(abs(p - r) / max(abs(r), 1e-30) for p, r in zip(prog, ref))


def norm_gap(prog_tree, ref_tree, keep: np.ndarray | None = None) -> float:
    p, r = _norms(prog_tree), _norms(ref_tree)
    if keep is not None:
        p, r = p[keep], r[keep]
    scale = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r) / np.maximum(scale, 1e-30)))


def diff_gap(prog_tree, ref_tree) -> float:
    """The norm of the difference, worst leaf, over the larger of the
    reference leaf's norm and the median leaf's.  Compared only where the
    gap of norms cannot separate the control from the program (PERF.md):
    rounding errors are zero-mean and nearly orthogonal to the gradient, so
    they move its norm only at second order, and their difference not."""
    d = _norms(jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                            - np.asarray(b, np.float64), prog_tree, ref_tree))
    r = _norms(ref_tree)
    return float(np.max(d / np.maximum(np.maximum(r, np.median(r)), 1e-30)))


def moving_leaves(ref_grad) -> np.ndarray:
    g = _norms(ref_grad)
    return g >= 1e-3 * np.median(g)


def delta(after, before):
    return jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                        - np.asarray(b, np.float64), after, before)


def training_checks(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` each hold ``losses``, ``grad`` (first step)
    and ``delta`` (parameters after the checked steps minus before).
    ``first_loss_gap`` is the first step's alone: a later step's loss also
    carries the round-off that Adam's first updates amplify (an element
    whose gradient is near 0 moves by the learning rate either way)."""
    keep = moving_leaves(ref["grad"])
    return dict(first_loss_gap=loss_gap(prog["losses"][:1],
                                        ref["losses"][:1]),
                loss_gap=loss_gap(prog["losses"], ref["losses"]),
                grad_gap=norm_gap(prog["grad"], ref["grad"]),
                grad_diff=diff_gap(prog["grad"], ref["grad"]),
                update_gap=norm_gap(prog["delta"], ref["delta"], keep))


def verdict(values: dict, limits: dict) -> tuple[bool, list]:
    """Every number within its limit, and none missing or not finite.
    Returns (correct, [[name, value, limit], ...])."""
    rows, ok = [], True
    for name, limit in limits.items():
        v = values.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok = ok and good
        rows.append([name, v, limit])
    return ok, rows
