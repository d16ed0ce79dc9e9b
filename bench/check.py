"""The comparison that decides ``correct``.

Each answer of the program is held against the plain reference
(:mod:`bench.reference`) of the same LP, built from the same seeded
data:

* ``finish_rel_err``: the largest gap between the program's optimal
  finish time (makespan) and the reference optimum, relative to the
  reference's;
* ``row_residual``: the largest violation of any row or bound of the
  reference LP by the program's schedule, relative to the row's size
  at the schedule's scale: ``|b| + sum |a| X``, where ``X`` is the
  schedule's largest load for a load column and its largest time for
  a time column;
* the count of lanes not certified, which must be 0.

A schedule that is feasible to the reference's rows and reaches its
optimum is an optimal schedule, whichever of several optima it is.
"""

from __future__ import annotations

import numpy as np

from . import reference as ref


def row_residual(lp: ref.LP, x: np.ndarray) -> float:
    """Largest relative violation of ``lp``'s rows and bounds by ``x``."""
    x = np.asarray(x, np.float64)
    ax = np.abs(x)
    scale = np.empty_like(ax)
    for kind in (0, 1):
        cols = lp.kinds == kind
        scale[cols] = max(float(ax[cols].max()), 1e-300)
    worst = float(np.max(np.maximum(-x, 0.0) / scale))
    for A, b, eq in ((lp.A_ub, lp.b_ub, False), (lp.A_eq, lp.b_eq, True)):
        if not A.shape[0]:
            continue
        r = A @ x - b
        r = np.abs(r) if eq else np.maximum(r, 0.0)
        size = np.abs(b) + abs(A) @ scale
        worst = max(worst, float(np.max(r / size)))
    return worst


def compare(lp: ref.LP, x: np.ndarray, x_ref: np.ndarray) -> tuple:
    """``(finish_rel_err, row_residual)`` of schedule ``x`` (last entry
    the finish time) against the reference optimum ``x_ref``."""
    t, t_ref = float(x[-1]), float(x_ref[-1])
    err = abs(t - t_ref) / abs(t_ref) if np.isfinite(t) else np.inf
    res = row_residual(lp, x) if np.all(np.isfinite(x)) else np.inf
    return err, res


def worst(pairs) -> dict:
    """Largest ``finish_rel_err`` and ``row_residual`` over ``pairs``."""
    pairs = list(pairs)
    if not pairs:
        return {"finish_rel_err": np.inf, "row_residual": np.inf}
    errs, ress = zip(*pairs)
    return {"finish_rel_err": max(errs), "row_residual": max(ress)}


def sample(rng: np.random.Generator, sizes, count: int, largest: int):
    """Indices of ``count`` answers: the ``largest`` by ``sizes`` and the
    rest drawn by ``rng`` from the others."""
    sizes = np.asarray(sizes)
    order = np.argsort(-sizes, kind="stable")
    top = order[:largest]
    rest = order[largest:]
    pick = rng.choice(rest, size=min(count - top.size, rest.size),
                      replace=False)
    return np.sort(np.concatenate([top, pick]).astype(int))
