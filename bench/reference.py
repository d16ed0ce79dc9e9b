"""Plain reference of the two scheduling LPs the cells solve.

Written from the paper's equations (Cao, Wu & Robertazzi,
arXiv:1902.01994), independent of the program: it imports nothing of
``repro`` and takes nothing the program made.  Nodes are taken in the
paper's order (sources by ascending ``G``, processors by ascending
``A``, stable), which the program's canonical order also is.

Sec 3.2, processors without front-ends,
``x = [beta (N*M), TS (N*M), TF (N*M), T]``, is ``min T`` over ``x >= 0``.  :func:`solve_highs` is the
reference (HiGHS, float64).  :func:`solve_ipm` is a plain dense
primal-dual interior point of the same LP in a chosen float type; in
float32 it is the control, the reference computed one precision below
what the configurations state.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import scipy.linalg as sla
import scipy.sparse as sp
from scipy.optimize import linprog


class LP(NamedTuple):
    """``min c.x`` s.t. ``A_ub x <= b_ub``, ``A_eq x = b_eq``, ``x >= 0``.

    ``kinds`` marks each column a load (0: a ``beta``) or a time (1)."""

    c: np.ndarray
    A_ub: sp.csr_matrix
    b_ub: np.ndarray
    A_eq: sp.csr_matrix
    b_eq: np.ndarray
    kinds: np.ndarray


def paper_order(G, R, A):
    """Sources by ascending ``G`` (``R`` follows), processors by ``A``."""
    G, R, A = (np.asarray(v, np.float64) for v in (G, R, A))
    s = np.argsort(G, kind="stable")
    return G[s], R[s], np.sort(A, kind="stable")


class _Rows:
    """Sparse rows collected as (row, col, value) triplets."""

    def __init__(self, nv: int):
        self.nv, self.r, self.c, self.v, self.b = nv, [], [], [], []

    def add(self, terms, rhs: float) -> None:
        k = len(self.b)
        for col, val in terms:
            self.r.append(k)
            self.c.append(col)
            self.v.append(val)
        self.b.append(rhs)

    def matrix(self):
        m = sp.csr_matrix((self.v, (self.r, self.c)),
                          shape=(len(self.b), self.nv))
        return m, np.asarray(self.b, np.float64)


def nofrontend_lp(G, R, A, J) -> LP:
    """Sec 3.2 (Eqs 7-14) in paper order."""
    G, R, A = paper_order(G, R, A)
    N, M = G.size, A.size
    nm = N * M
    nv = 3 * nm + 1
    T = 3 * nm
    b = lambda i, j: i * M + j            # noqa: E731
    ts = lambda i, j: nm + i * M + j      # noqa: E731
    tf = lambda i, j: 2 * nm + i * M + j  # noqa: E731
    ub, eq = _Rows(nv), _Rows(nv)
    for i in range(N):                    # Eq 7 transfer length
        for j in range(M):
            eq.add([(tf(i, j), 1.0), (ts(i, j), -1.0), (b(i, j), -G[i])], 0.0)
    for i in range(N - 1):                # Eq 8 per-processor source order
        for j in range(M):
            ub.add([(tf(i, j), 1.0), (ts(i + 1, j), -1.0)], 0.0)
    for i in range(N):                    # Eq 9 per-source processor order
        for j in range(M - 1):
            ub.add([(tf(i, j), 1.0), (ts(i, j + 1), -1.0)], 0.0)
    eq.add([(ts(0, 0), 1.0)], R[0])       # Eq 10
    for i in range(1, N):
        ub.add([(ts(i, 0), -1.0)], -R[i])      # Eq 11
        ub.add([(tf(i - 1, 0), -1.0)], -R[i])  # Eq 12
    for j in range(M):                    # Eq 13 finish time
        ub.add([(tf(N - 1, j), 1.0)] + [(b(i, j), A[j]) for i in range(N)]
               + [(T, -1.0)], 0.0)
    eq.add([(k, 1.0) for k in range(nm)], float(J))   # Eq 14
    c = np.zeros(nv)
    c[T] = 1.0
    kinds = np.r_[np.zeros(nm, int), np.ones(2 * nm + 1, int)]
    return LP(c, *ub.matrix(), *eq.matrix(), kinds)


def solve_highs(lp: LP) -> np.ndarray:
    """The reference optimum ``x`` (HiGHS, float64)."""
    res = linprog(lp.c, A_ub=lp.A_ub, b_ub=lp.b_ub, A_eq=lp.A_eq,
                  b_eq=lp.b_eq, bounds=(0, None), method="highs",
                  options={"primal_feasibility_tolerance": 1e-10,
                           "dual_feasibility_tolerance": 1e-10})
    if res.status != 0:
        raise RuntimeError(f"reference LP not solved: {res.message}")
    return res.x


def solve_ipm(lp: LP, dtype=np.float32, max_iter: int = 100,
              tol: float = 1e-9) -> np.ndarray:
    """Plain Mehrotra predictor-corrector IPM, every step in ``dtype``.

    Standard form ``[A_ub I; A_eq 0] [x; s] = b`` over ``x, s >= 0``,
    normal equations by a dense Cholesky.  It stops at ``tol`` or where
    the precision gives out (a factor that fails, iterates that diverge)
    and returns the iterate whose residuals and gap were least, so a
    lower precision always yields an answer to compare.
    """
    n_ub, nv = lp.A_ub.shape
    A = sp.vstack([sp.hstack([lp.A_ub, sp.identity(n_ub)]),
                   sp.hstack([lp.A_eq, sp.csr_matrix((lp.A_eq.shape[0], n_ub))])])
    A = sp.csr_matrix(A, dtype=dtype)
    AT = A.T.tocsr()
    b = np.concatenate([lp.b_ub, lp.b_eq]).astype(dtype)
    c = np.concatenate([lp.c, np.zeros(n_ub)]).astype(dtype)
    m, n = A.shape
    one = dtype(1)
    eps = np.finfo(dtype).eps

    def normal_solve(d, rhs):
        M = (A.multiply(d[None, :]) @ AT).toarray()
        M[np.diag_indices(m)] += eps * np.diag(M)
        L = np.linalg.cholesky(M)
        return sla.cho_solve((L, True), rhs, check_finite=False).astype(dtype)

    # Mehrotra's starting point
    ones = np.ones(n, dtype)
    y = normal_solve(ones, A @ c)
    x = AT @ normal_solve(ones, b)
    s = c - AT @ y
    x += max(-1.5 * float(x.min()), 0.0)
    s += max(-1.5 * float(s.min()), 0.0)
    shift = 0.5 * float(x @ s)
    x += shift / max(float(s.sum()), eps) + one
    s += shift / max(float(x.sum()), eps) + one

    with np.errstate(all="ignore"):
        x = _iterate(A, AT, b, c, x, y, s, normal_solve, dtype, max_iter, tol)
    return x[:nv].astype(np.float64)


def _iterate(A, AT, b, c, x, y, s, normal_solve, dtype, max_iter, tol):
    n = x.size
    one = dtype(1)

    def step(v, dv):
        neg = dv < 0
        return min(one, float(np.min(-v[neg] / dv[neg]))) if neg.any() else one

    best, best_x = np.inf, x
    for _ in range(max_iter):
        rp = b - A @ x
        rd = c - AT @ y - s
        mu = float(x @ s) / n
        merit = max(np.linalg.norm(rp) / (1 + np.linalg.norm(b)),
                    np.linalg.norm(rd) / (1 + np.linalg.norm(c)),
                    abs(float(c @ x) - float(b @ y)) / (1.0 + abs(float(c @ x))))
        if not merit < best:
            if merit > 1e3 * best:    # diverging where the precision ran out
                break
        else:
            best, best_x = merit, x
        if merit < tol:
            break
        d = x / s
        try:
            def direction(rxs):
                dy = normal_solve(d, rp - A @ (rxs / s - d * rd))
                dx = d * (AT @ dy - rd) + rxs / s
                return dx, dy, (rxs - s * dx) / x

            dxa, _, dsa = direction(-x * s)
            ap, ad = step(x, dxa), step(s, dsa)
            mu_aff = float((x + ap * dxa) @ (s + ad * dsa)) / n
            sigma = (mu_aff / mu) ** 3
            dx, dy, ds = direction(-x * s - dxa * dsa + dtype(sigma * mu))
        except np.linalg.LinAlgError:
            break
        ap = dtype(0.99) * dtype(step(x, dx))
        ad = dtype(0.99) * dtype(step(s, ds))
        if not (np.all(np.isfinite(dx)) and np.all(np.isfinite(ds))):
            break
        x, y, s = x + ap * dx, y + ad * dy, s + ad * ds
    return best_x
