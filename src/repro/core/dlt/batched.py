"""Batched, vmap-able DLT solver machinery (pure JAX).

The paper's Sec 5-6 analyses (speedup grids, cost sweeps, budget planning)
are many-scenario computations: thousands of small LPs that differ only in
their data ``(G, R, A, C, J)`` and sizes ``(N, M)``.  The scalar path solves
them one at a time through a NumPy simplex; this module holds the machinery
that solves a whole family in ONE jitted call (the session front door is
:class:`repro.core.dlt.engine.DLTEngine`; :func:`batched_solve` below is a
compatibility shim over the shared default engine):

1. :class:`BatchedSystemSpec` stacks canonically-sorted specs into padded
   ``(B, N_max)`` / ``(B, M_max)`` arrays with per-scenario size masks.
2. The LP rows come from the **formulation registry**
   (:mod:`repro.core.dlt.formulations`): Sec 3.1 front-end, Sec 3.2
   no-front-end, or the column-reduced no-front-end chain variant — the
   same row builders the scalar simplex path uses, so there is exactly one
   implementation of every constraint.  :func:`build_family_lp` embeds
   every scenario into one shared static standard form ``min c'z, Az=b,
   z>=0``; padded variables become zero columns with objective ``+1`` (the
   optimum pins them to 0), padded inequality rows read ``slack = 1`` and
   padded equality rows ``artificial = 1``.
3. **Size-bucketed batching**: ragged scenarios are grouped into a few
   ``(N, M_bucket)`` padded shapes instead of one global max, cutting the
   padding blowup for mixed source/processor counts.  Each bucket runs
   through the engine's LRU of ahead-of-time compiled family shapes
   (optionally persisted across processes via the JAX compilation cache).
4. The fixed-budget interior-point kernel (Mehrotra predictor-corrector on
   the homogeneous self-dual embedding, under ``jit(vmap(...))``) exploits
   the ``[F | I]`` structure of the standard form: slack/artificial columns
   contribute only a diagonal to the normal equations, so each iteration
   builds and factors the reduced ``F D F' + diag`` system instead of the
   full ``A D A'``.
5. :func:`batched_solve` wraps it end to end: vectorized re-checks of the
   paper constraint sets (via the formulation's verifier — the reduced
   formulation is always verified against the ORIGINAL Sec 3.2
   constraints on its reconstructed intervals), and scenarios the IPM
   could not certify fall back to the scalar simplex path, recorded in
   ``BatchedSolution.fallback_mask`` so the fallback is never silent.

The interior-point solution is an analytic-center optimum: finish times
(the LP objective) match the simplex vertex to solver tolerance, while
``beta`` may differ on degenerate optimal faces.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels.dlt_banded_chol import ops as _chol_kernels
from . import precision as _precision
from .formulations import (
    BatchFields,
    DEFAULT_NOFRONTEND_FORMULATION,
    FamilyDims,
    Formulation,
    get_formulation,
)
from .stacking import BatchedSystemSpec
from .types import InfeasibleError, Schedule

__all__ = [
    "BatchedSystemSpec",
    "BatchedSolution",
    "FamilyLP",
    "BandedFamilyLP",
    "BandedGeometry",
    "build_banded_family",
    "banded_row_transfer",
    "batched_solve",
    "solve_lp_batch",
    "build_family_lp",
    "build_standard_form_batch",
    "verify_frontend_batch",
    "verify_nofrontend_batch",
    "STATUS_OPTIMAL",
    "STATUS_MAXITER",
    "STATUS_INFEASIBLE",
    "DEFAULT_NOFRONTEND_FORMULATION",
    "DEFAULT_M_BUCKET_EDGES",
    "compile_cache_info",
]

# Status codes align with simplex.LPResult.status.
STATUS_OPTIMAL = 0
STATUS_MAXITER = 1
STATUS_INFEASIBLE = 2

#: Processor-count bucket edges for size-bucketed batching (~1.33-1.5x
#: steps: worst-case padding stays small while compiled-shape count stays
#: bounded).  Source counts are bucketed exactly — they are small and set
#: the variable layout.
DEFAULT_M_BUCKET_EDGES = (1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128)


# ---------------------------------------------------------------------------
# Standard-form family embedding (rows come from the formulation registry)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FamilyLP:
    """One padded LP family in structured standard form.

    The full constraint matrix is ``A = [F | I-ish]``: ``F`` carries the
    formulation variables, inequality slacks form an identity block, and
    equality artificials a diagonal ``art`` block (nonzero only on padded
    equality rows).  The interior-point kernel consumes this split form
    directly; :func:`build_standard_form_batch` densifies it for callers
    that want the plain ``(c, A, b)`` tensors.
    """

    c: np.ndarray      # (B, n_std) objective over z = [vars, slacks, arts]
    F: np.ndarray      # (B, n_rows, nv) variable block of A
    b: np.ndarray      # (B, n_rows) rhs
    art: np.ndarray    # (B, n_eq) artificial-diagonal (1.0 on padded eq rows)
    dims: FamilyDims


def build_family_lp(bs: BatchedSystemSpec,
                    formulation: "Formulation | str | bool") -> FamilyLP:
    """Stacked standard-form LPs ``min c'z s.t. Az=b, z>=0`` for a family.

    z = [lp_vars (nv) | ub slacks (n_ub) | eq artificials (n_eq)] per lane.
    Padded LP variables get a zero column and objective ``+1`` (optimum 0);
    padded ub rows read ``slack = 1``; padded eq rows ``artificial = 1``;
    artificials of REAL eq rows are themselves masked variables.
    """
    fm = get_formulation(formulation)
    dims = fm.batch_dims(bs)
    nv, n_ub, n_eq = dims.nv, dims.n_ub, dims.n_eq
    B = bs.batch
    rows = fm.build_batch_rows(bs)
    colmask = fm.batch_column_mask(bs)

    A_ub = rows.A_ub * colmask[:, None, :]
    A_eq = rows.A_eq * colmask[:, None, :]
    F = np.concatenate([A_ub, A_eq], axis=1)
    art = np.where(rows.eq_active, 0.0, 1.0)
    b = np.concatenate(
        [rows.b_ub, np.where(rows.eq_active, rows.b_eq, 1.0)], axis=1)

    c = np.zeros((B, dims.n_std))
    c[:, nv - 1] = 1.0                      # T_f (last LP variable)
    masked_vars = ~colmask
    masked_vars[:, nv - 1] = False
    c[:, :nv][masked_vars] = 1.0
    c[:, nv + n_ub:][rows.eq_active] = 1.0  # artificials of real eq rows
    return FamilyLP(c=c, F=F, b=b, art=art, dims=dims)


def densify_family(fam: FamilyLP) -> np.ndarray:
    """The full dense ``A (B, m, n_std)`` of a structured family."""
    nv, n_ub, n_eq = fam.dims.nv, fam.dims.n_ub, fam.dims.n_eq
    B, mrows = fam.b.shape
    A = np.zeros((B, mrows, fam.dims.n_std))
    A[:, :, :nv] = fam.F
    A[:, :n_ub, nv: nv + n_ub] = np.eye(n_ub)[None]
    r_eq = np.arange(n_eq)
    A[:, n_ub + r_eq, nv + n_ub + r_eq] = fam.art
    return A


def build_standard_form_batch(bs: BatchedSystemSpec,
                              formulation: "Formulation | str | bool"):
    """Dense ``(c (B, n), A (B, m, n), b (B, m))`` stacked standard form.

    ``formulation`` accepts a registry name, a :class:`Formulation`, or the
    legacy bool (``True`` = Sec 3.1 front-end, ``False`` = Sec 3.2).
    """
    fam = build_family_lp(bs, formulation)
    return fam.c, densify_family(fam), fam.b


# ---------------------------------------------------------------------------
# Fixed-budget interior-point LP solver (homogeneous self-dual embedding)
# ---------------------------------------------------------------------------

def _hsde_ipm_core(c, b, A_mul, AT_mul, make_normal_solver,
                   max_iter: int, tol: float, init=None,
                   make_fp32_solver=None):
    """min c'x s.t. Ax=b, x>=0 via Mehrotra predictor-corrector on the HSDE.

    The constraint matrix enters only through three hooks — ``A_mul(x)``,
    ``AT_mul(y)`` and ``make_normal_solver(dinv) -> solve`` (build AND
    factor ``A diag(dinv) A'``, returning a solver over rhs vectors) — so
    the dense, structured ``[F | I]`` and block-banded instantiations
    share this body.  Shape-static: a while_loop capped at ``max_iter``
    iterations that (under vmap) exits once every lane is decided.
    Returns (x, obj, status, iters, y, s, n_refine, handover) where x is
    the primal solution (x/tau), (y, s) the tau-scaled duals — the triple
    a warm start of a nearby program feeds back in — and the last two the
    mixed-precision telemetry (0 under the fp64 policy; ``handover`` is
    a ``HANDOVER_*`` code of :mod:`..precision`).  HSDE
    certificates make infeasibility detection residual-based: the
    embedding is always feasible and converges either to tau>0 (optimum)
    or tau->0 with kappa>0 (primal or dual infeasible).

    ``init`` (optional) is an interior ``(x0, y0, s0)`` starting triple —
    every entry of ``x0``/``s0`` must be strictly positive; the embedding
    restarts at ``tau=1`` with ``kappa`` matched to the average
    complementarity product, so a shifted previous solution of a nearby
    LP (same padded shape) enters the central path close to the optimum.

    ``make_fp32_solver`` (optional) switches on the mixed policy: it maps
    ``dinv`` to an iteratively-refined fp32-factor solver with the
    ``(w, n_refine)`` contract (:mod:`..precision`).  The kernel then
    runs two phases — the refined fp32 factor while
    ``mu > SWITCH_MU * mu0`` (where cond(M) is benign and the arithmetic
    win lives) and each step lowers mu, then the plain fp64 loop to
    certification, so the stopping test is bitwise the fp64 policy's.
    """
    n = c.shape[0]
    m = b.shape[0]
    nb = 1.0 + jnp.linalg.norm(b)
    nc = 1.0 + jnp.linalg.norm(c)
    if init is None:
        x0, y0, s0 = jnp.ones(n), jnp.zeros(m), jnp.ones(n)
        tau0, kappa0 = jnp.asarray(1.0), jnp.asarray(1.0)
    else:
        x0, y0, s0 = init
        tau0 = jnp.asarray(1.0)
        kappa0 = (x0 @ s0) / n
    mu0 = (x0 @ s0 + tau0 * kappa0) / (n + 1)

    def classify(x, y, s, tau, kappa):
        mu = (x @ s + tau * kappa) / (n + 1)
        rho_p = jnp.linalg.norm(b * tau - A_mul(x)) / nb
        rho_d = jnp.linalg.norm(c * tau - AT_mul(y) - s) / nc
        rho_g = jnp.abs(c @ x - b @ y + kappa) / (nb + nc)
        bty = b @ y
        rho_A = jnp.abs(c @ x - bty) / (tau + jnp.abs(bty))
        optimal = (rho_p < tol) & (rho_d < tol) & (rho_A < tol)
        ray = (((rho_p < tol) & (rho_d < tol) & (rho_g < tol)
                & (tau < tol * jnp.maximum(1.0, kappa)))
               | ((mu / mu0 < tol) & (tau < tol * jnp.minimum(1.0, kappa))))
        status = jnp.where(optimal, STATUS_OPTIMAL,
                           jnp.where(ray, STATUS_INFEASIBLE, STATUS_MAXITER))
        return status, optimal | ray

    def max_step(z, dz):
        return jnp.min(jnp.where(dz < 0, -z / jnp.where(dz < 0, dz, -1.0),
                                 jnp.inf))

    def cond(carry):
        done, nit = carry[6], carry[7]
        return (~done) & (nit < max_iter)

    def make_body(solver_of_dinv):
        """Body factory: one Mehrotra step with the given normal solver.

        ``solver_of_dinv(dinv)`` returns a solve with the
        ``(w, n_refine)`` contract (fp64 solvers report 0).
        """

        def body(carry):
            x, y, s, tau, kappa, status, done, nit, nref = carry
            mu = (x @ s + tau * kappa) / (n + 1)
            rP = b * tau - A_mul(x)
            rD = c * tau - AT_mul(y) - s
            rG = c @ x - b @ y + kappa

            # normal equations M = A diag(x/s) A' — built AND factored by
            # the instantiation (dense/structured: Cholesky of the full
            # matrix; banded: block-tridiagonal-arrowhead Cholesky)
            dinv = x / s
            solve_M = solver_of_dinv(dinv)

            def A_d_mul(r):  # A diag(dinv) r
                return A_mul(dinv * r)

            # tau-column system, shared by predictor and corrector
            v, nr_v = solve_M(b + A_d_mul(c))
            xv = dinv * (AT_mul(v) - c)
            denom_v = b @ v - c @ xv + kappa / tau

            def direction(eta, cc, ck):
                w = -eta * rD + cc / x
                u, nr_u = solve_M(eta * rP - A_d_mul(w))
                xu = dinv * (AT_mul(u) + w)
                dtau = (eta * rG + ck / tau - b @ u + c @ xu) / denom_v
                dy = u + dtau * v
                dx = xu + dtau * xv
                ds = (cc - s * dx) / x
                dkappa = (ck - kappa * dtau) / tau
                return dx, dy, ds, dtau, dkappa, nr_u

            def step_len(dx, ds, dtau, dkappa):
                a = jnp.minimum(max_step(x, dx), max_step(s, ds))
                a = jnp.minimum(a, jnp.where(dtau < 0, -tau / dtau, jnp.inf))
                a = jnp.minimum(
                    a, jnp.where(dkappa < 0, -kappa / dkappa, jnp.inf))
                return a

            # predictor (affine scaling)
            dxa, dya, dsa, dta, dka, nr_a = direction(
                1.0, -x * s, -tau * kappa)
            alpha_a = jnp.minimum(1.0, step_len(dxa, dsa, dta, dka))
            mu_aff = (((x + alpha_a * dxa) @ (s + alpha_a * dsa)
                       + (tau + alpha_a * dta) * (kappa + alpha_a * dka))
                      / (n + 1))
            sigma = jnp.clip((mu_aff / mu) ** 3, 0.0, 1.0)

            # corrector (combined direction, same factorization)
            cc = sigma * mu - x * s - dxa * dsa
            ck = sigma * mu - tau * kappa - dta * dka
            dx, dy, ds, dtau, dkappa, nr_c = direction(
                1.0 - sigma, cc, ck)
            alpha = jnp.minimum(1.0, 0.99995 * step_len(dx, ds, dtau, dkappa))
            finite = (jnp.all(jnp.isfinite(dx)) & jnp.all(jnp.isfinite(dy))
                      & jnp.all(jnp.isfinite(ds)) & jnp.isfinite(dtau)
                      & jnp.isfinite(dkappa) & jnp.isfinite(alpha))
            # a non-finite direction (a failed factor) leaves the iterate
            # as it was: a zero step would still give 0 * nan = nan
            take = finite & ~done

            def step(v, dv):
                return jnp.where(take, v + alpha * dv, v)

            x, y, s = step(x, dx), step(y, dy), step(s, ds)
            tau, kappa = step(tau, dtau), step(kappa, dkappa)
            status, done_now = classify(x, y, s, tau, kappa)
            return (x, y, s, tau, kappa, status, done | done_now,
                    nit + 1, nref + nr_v + nr_a + nr_c)

        return body

    status0, done0 = classify(x0, y0, s0, tau0, kappa0)
    carry0 = (x0, y0, s0, tau0, kappa0, status0, done0, jnp.asarray(0),
              jnp.asarray(0))
    handover = jnp.asarray(_precision.HANDOVER_NONE, jnp.int32)
    if make_fp32_solver is None:
        carry = jax.lax.while_loop(
            cond, make_body(lambda d: _count0(make_normal_solver(d))),
            carry0)
    else:
        # phase 1: fp32 factor + fp64-residual refinement while the
        # iterates are far from the boundary (cond(M) ~ 1/mu fits fp32)
        # and each step still lowers mu.  A step that does not (a failed
        # fp32 factor gives a non-finite direction, and the step is
        # refused) would fail the same way at the same iterate forever,
        # so it hands the lane to the fp64 loop and says why.
        def mu_of(carry):
            x, _, s, tau, kappa = carry[:5]
            return (x @ s + tau * kappa) / (n + 1)

        def cond1(state):
            carry, why = state
            done, nit = carry[6], carry[7]
            return ((~done) & (nit < max_iter)
                    & (why == _precision.HANDOVER_NONE)
                    & (mu_of(carry) > _precision.SWITCH_MU * mu0))

        body32 = make_body(make_fp32_solver)

        def body1(state):
            carry = body32(state[0])
            mu_old, mu_new = mu_of(state[0]), mu_of(carry)
            why = jnp.where(
                mu_new < mu_old, _precision.HANDOVER_NONE,
                jnp.where(mu_new == mu_old, _precision.HANDOVER_NO_STEP,
                          _precision.HANDOVER_NO_DESCENT))
            return carry, why.astype(jnp.int32)

        carry, handover = jax.lax.while_loop(
            cond1, body1, (carry0, handover))
        # phase 2: plain fp64 finish — certification is exactly fp64's
        carry = jax.lax.while_loop(
            cond, make_body(lambda d: _count0(make_normal_solver(d))),
            carry)
    x, y, s, tau, kappa, status, done, nit, nref = carry
    inv_tau = 1.0 / jnp.maximum(tau, 1e-300)
    xsol = x * inv_tau
    return (xsol, c @ xsol, status, nit, y * inv_tau, s * inv_tau,
            nref, handover)


def _count0(solve):
    """Adapt a plain fp64 solve to the (w, n_refine) contract."""
    def solve_M(rhs):
        return solve(rhs), jnp.asarray(0)
    return solve_M


def _chol_solver(Mmat):
    """Factor a dense normal matrix (+ tiny relative ridge) -> solver."""
    m = Mmat.shape[0]
    Mmat = Mmat + (1e-13 * (jnp.trace(Mmat) / m + 1.0)) * jnp.eye(m)
    L = jnp.linalg.cholesky(Mmat)

    def solve_M(rhs):  # rhs (m,) or (m, k)
        z = jax.scipy.linalg.solve_triangular(L, rhs, lower=True)
        return jax.scipy.linalg.solve_triangular(L.T, z, lower=False)

    return solve_M


def _hsde_ipm(c, A, b, max_iter: int, tol: float, init=None,
              precision: str = "fp64",
              refine_max: int = _precision.DEFAULT_REFINE_MAX,
              refine_tol: float = _precision.DEFAULT_REFINE_TOL):
    """Dense instantiation (generic ``A``) of the HSDE kernel."""

    def A_mul(z):
        return A @ z

    def AT_mul(y):
        return A.T @ y

    def make_normal_solver(dinv):
        return _chol_solver((A * dinv[None, :]) @ A.T)

    make_fp32 = None
    if precision == "mixed":
        def make_fp32(dinv):
            M64 = (A * dinv[None, :]) @ A.T
            return _precision.refined_solver(
                _precision.fp32_cholesky(M64), lambda w: M64 @ w,
                refine_max, refine_tol)

    return _hsde_ipm_core(c, b, A_mul, AT_mul, make_normal_solver,
                          max_iter, tol, init=init,
                          make_fp32_solver=make_fp32)


def _structured_ops(F, art, precision: str = "fp64",
                    refine_max: int = _precision.DEFAULT_REFINE_MAX,
                    refine_tol: float = _precision.DEFAULT_REFINE_TOL):
    """Linear maps of ``A = [[F_ub, I, 0], [F_eq, 0, diag(art)]]``.

    Slack and artificial columns touch exactly one row each, so they add
    only a diagonal to the normal equations — each iteration builds
    ``F D_v F' + diag(extra)`` (cost ``m^2 nv``) instead of the dense
    ``A D A'`` (cost ``m^2 (nv+m)``).

    Returns ``(A_mul, AT_mul, make_normal_solver, make_fp32_solver)``;
    the last is None under the fp64 policy and otherwise the refined
    fp32-factor solver factory for the core's mixed phase.
    """
    m, nv = F.shape
    n_eq = art.shape[0]
    n_ub = m - n_eq

    def split(z):
        return z[:nv], z[nv: nv + n_ub], z[nv + n_ub:]

    def A_mul(z):
        v, sl, ar = split(z)
        return F @ v + jnp.concatenate([sl, art * ar])

    def AT_mul(y):
        return jnp.concatenate([F.T @ y, y[:n_ub], art * y[n_ub:]])

    def normal_matrix(dinv):
        dv, dsl, dar = split(dinv)
        extra = jnp.concatenate([dsl, art * art * dar])
        return (F * dv[None, :]) @ F.T + jnp.diag(extra)

    def make_normal_solver(dinv):
        return _chol_solver(normal_matrix(dinv))

    make_fp32 = None
    if precision == "mixed":
        def make_fp32(dinv):
            M64 = normal_matrix(dinv)
            return _precision.refined_solver(
                _precision.fp32_cholesky(M64), lambda w: M64 @ w,
                refine_max, refine_tol)

    return A_mul, AT_mul, make_normal_solver, make_fp32


def _hsde_ipm_structured(c, F, b, art, max_iter: int, tol: float,
                         precision: str = "fp64",
                         refine_max: int = _precision.DEFAULT_REFINE_MAX,
                         refine_tol: float = _precision.DEFAULT_REFINE_TOL):
    """Structured (cold-start) instantiation of the HSDE kernel."""
    A_mul, AT_mul, make_solver, make_fp32 = _structured_ops(
        F, art, precision, refine_max, refine_tol)
    return _hsde_ipm_core(c, b, A_mul, AT_mul, make_solver, max_iter, tol,
                          make_fp32_solver=make_fp32)


def _hsde_ipm_structured_warm(c, F, b, art, x0, y0, s0,
                              max_iter: int, tol: float,
                              precision: str = "fp64",
                              refine_max: int = _precision.DEFAULT_REFINE_MAX,
                              refine_tol: float =
                              _precision.DEFAULT_REFINE_TOL):
    """Structured instantiation restarted from an interior ``(x0, y0, s0)``.

    Used by the engine's warm-started parametric sweeps: the previous
    family member's (shifted) solution triple re-enters the embedding at
    ``tau=1``, so nearby programs converge in a fraction of the cold
    iteration count.
    """
    A_mul, AT_mul, make_solver, make_fp32 = _structured_ops(
        F, art, precision, refine_max, refine_tol)
    return _hsde_ipm_core(c, b, A_mul, AT_mul, make_solver, max_iter, tol,
                          init=(x0, y0, s0), make_fp32_solver=make_fp32)


# ---------------------------------------------------------------------------
# Banded kernel: block-tridiagonal-arrowhead normal equations
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BandedGeometry:
    """Static block layout of a banded family (shape-level, no lane data).

    Derived from a :class:`~repro.core.dlt.formulations.BandedStructure`:
    positions (banded row order) are grouped into ``K`` tridiagonal
    blocks of padded size ``s`` plus ``p`` trailing border rows.  All
    arrays are position-indexed and shared by every lane of the family,
    so the jitted kernel closes over them as constants.
    """

    m: int                 # rows
    nv: int                # LP variables
    K: int                 # tridiagonal blocks
    s: int                 # padded block size
    p: int                 # border rows
    perm: np.ndarray       # (m,) original row at each banded position
    posmat: np.ndarray     # (K, s) position per (block, slot), -1 padded
    bkb: np.ndarray        # (m - p,) block of each band position
    slotb: np.ndarray      # (m - p,) slot of each band position
    dprev_c: np.ndarray    # (m,) chain-predecessor position (clipped to 0)
    has_prev: np.ndarray   # (m,) bool
    succ_c: np.ndarray     # (m,) chain-successor position (clipped to 0)
    has_succ: np.ndarray   # (m,) bool
    pair_same: np.ndarray  # (3, nd) (block, slot_t, slot_prev) same-block pairs
    pair_st: np.ndarray    # (nd,) position t of each same-block pair
    pair_cross: np.ndarray  # (3, nc) (block_prev, slot_t, slot_prev) cross pairs
    pair_ct: np.ndarray    # (nc,) position t of each cross-block pair

    @property
    def n_band(self) -> int:
        return self.m - self.p


def _banded_geometry(struct, dims: FamilyDims) -> BandedGeometry:
    """Block layout from a formulation's banded structure (validated)."""
    struct.validate(dims)
    m = dims.n_rows
    K = struct.n_blocks
    block = struct.block
    band = block < K
    n_band = int(band.sum())
    sizes = np.bincount(block[band], minlength=K)
    s = max(int(sizes.max()) if K else 1, 1)
    p = m - n_band

    slot = np.zeros(m, dtype=np.int64)
    posmat = np.full((K, s), -1, dtype=np.int64)
    fill = np.zeros(K, dtype=np.int64)
    for t in range(n_band):
        k = int(block[t])
        slot[t] = fill[k]
        posmat[k, fill[k]] = t
        fill[k] += 1
    slot[n_band:] = np.arange(p)

    has_prev = struct.dprev >= 0
    dprev_c = np.maximum(struct.dprev, 0)
    succ = struct.successor()
    has_succ = succ >= 0
    succ_c = np.maximum(succ, 0)

    same, same_t, cross, cross_t = [], [], [], []
    for t in np.flatnonzero(has_prev):
        u = int(struct.dprev[t])
        if block[t] == block[u]:
            same.append((int(block[t]), int(slot[t]), int(slot[u])))
            same_t.append(int(t))
        else:  # validated: block[t] == block[u] + 1
            cross.append((int(block[u]), int(slot[t]), int(slot[u])))
            cross_t.append(int(t))
    to3 = lambda lst: (np.asarray(lst, dtype=np.int64).reshape(-1, 3).T
                       if lst else np.zeros((3, 0), dtype=np.int64))
    return BandedGeometry(
        m=m, nv=dims.nv, K=K, s=s, p=p, perm=struct.perm, posmat=posmat,
        bkb=block[:n_band], slotb=slot[:n_band],
        dprev_c=dprev_c, has_prev=has_prev,
        succ_c=succ_c, has_succ=has_succ,
        pair_same=to3(same), pair_st=np.asarray(same_t, dtype=np.int64),
        pair_cross=to3(cross), pair_ct=np.asarray(cross_t, dtype=np.int64),
    )


@dataclasses.dataclass(frozen=True)
class BandedFamilyLP:
    """A padded family in the banded row basis (position-ordered).

    Rows are permuted into processor blocks and chained rows are
    replaced by differences with their (lane-active) chain predecessor
    — an invertible per-lane row transform, so every lane solves the
    SAME LP as its :class:`FamilyLP` counterpart.  Extra (slack /
    artificial) columns are renumbered so position ``t`` owns extra
    column ``nv + t``; the kernel variable layout is
    ``z = [lp_vars, extra (position order)]``.
    """

    c: np.ndarray       # (B, nv + m)
    F: np.ndarray       # (B, m, nv) transformed variable rows
    b: np.ndarray       # (B, m) transformed rhs
    ext: np.ndarray     # (B, m) extra-column coefficient per position
    dcoef: np.ndarray   # (B, m) predecessor coefficient (1 = differenced)
    colix: np.ndarray   # (K, w) variable-column support per block
    Fg: np.ndarray      # (B, K, s, w) block rows on their support
    Hg: np.ndarray      # (B, K, s, w) next block's rows on this support
    Ug: np.ndarray      # (B, K, p, w) border rows on this support
    Bq: np.ndarray      # (B, p, nv) border rows, dense
    geom: BandedGeometry

    @property
    def w(self) -> int:
        return int(self.colix.shape[1])


def build_banded_family(fam: FamilyLP, struct) -> BandedFamilyLP:
    """Transform a :class:`FamilyLP` into the banded row basis.

    The differencing coefficient is per-lane data: a chained row is
    differenced only when both it and its predecessor are structurally
    active in that lane, so padded trailing rows of a chain stay pure
    slack rows and the block-tridiagonal pattern holds for every lane.
    The per-block column support is computed from the union pattern of
    the transformed rows across lanes (data-driven, hence an input of
    the kernel rather than part of the static geometry).
    """
    geom = _banded_geometry(struct, fam.dims)
    perm = struct.perm
    m, nv, K, s, p = geom.m, geom.nv, geom.K, geom.s, geom.p
    B = fam.c.shape[0]
    n_ub = fam.dims.n_ub

    F0 = fam.F[:, perm, :]
    b0 = fam.b[:, perm]
    active = np.any(F0 != 0.0, axis=2)
    dcoef = np.zeros((B, m))
    hp = geom.has_prev
    dcoef[:, hp] = (active[:, hp]
                    & active[:, geom.dprev_c[hp]]).astype(float)
    Ft = F0 - dcoef[:, :, None] * F0[:, geom.dprev_c, :]
    bt = b0 - dcoef * b0[:, geom.dprev_c]

    ext = np.concatenate(
        [np.ones((B, n_ub)), fam.art], axis=1)[:, perm]
    c = np.concatenate([fam.c[:, :nv], fam.c[:, nv:][:, perm]], axis=1)

    # per-block column support: union pattern over lanes and slots
    posc = np.where(geom.posmat >= 0, geom.posmat, 0)
    real = (geom.posmat >= 0)
    Fblk = (Ft[:, posc.reshape(-1), :].reshape(B, K, s, nv)
            * real[None, :, :, None])
    pat = np.any(Fblk != 0.0, axis=(0, 2))          # (K, nv)
    w = max(int(pat.sum(axis=1).max()) if K else 1, 1)
    colix = np.zeros((K, w), dtype=np.int64)
    wmask = np.zeros((K, w))
    for k in range(K):
        cols = np.flatnonzero(pat[k])
        colix[k, :cols.size] = cols
        wmask[k, :cols.size] = 1.0

    def gather(rows):  # (B, K, r, nv) -> (B, K, r, w) on each block support
        idx = np.broadcast_to(colix[None, :, None, :],
                              rows.shape[:3] + (w,))
        return np.take_along_axis(rows, idx, axis=3) * wmask[None, :, None, :]

    Fg = gather(Fblk)
    pos_next = np.concatenate(
        [posc[1:], np.zeros((1, s), dtype=np.int64)], axis=0)
    real_next = np.concatenate(
        [real[1:], np.zeros((1, s), dtype=bool)], axis=0)
    Hblk = (Ft[:, pos_next.reshape(-1), :].reshape(B, K, s, nv)
            * real_next[None, :, :, None])
    Hg = gather(Hblk)
    Bq = Ft[:, geom.n_band:, :]                     # (B, p, nv)
    Ug = gather(np.broadcast_to(Bq[:, None], (B, K, p, nv)))
    return BandedFamilyLP(c=c, F=Ft, b=bt, ext=ext, dcoef=dcoef,
                          colix=colix, Fg=Fg, Hg=Hg, Ug=Ug, Bq=Bq, geom=geom)


def _banded_take(bfam: BandedFamilyLP, pos: np.ndarray) -> BandedFamilyLP:
    """Lanes ``pos`` of a banded family (geometry and support unchanged)."""
    return dataclasses.replace(
        bfam, c=bfam.c[pos], F=bfam.F[pos], b=bfam.b[pos],
        ext=bfam.ext[pos], dcoef=bfam.dcoef[pos], Fg=bfam.Fg[pos],
        Hg=bfam.Hg[pos], Ug=bfam.Ug[pos], Bq=bfam.Bq[pos])


def banded_warm_convert(bfam: BandedFamilyLP, x0, y0, s0):
    """Standard-layout warm triple -> the banded basis (numpy, per lane).

    Primal/dual slacks permute with the extra columns; the transformed
    dual solves ``E' y_banded = y[perm]`` by back-substitution along the
    diff chains (``E`` is unit lower triangular, so positivity of the
    primal/dual slack coordinates is preserved exactly).
    """
    g = bfam.geom
    zperm = np.concatenate([np.arange(g.nv), g.nv + g.perm])
    xb = x0[:, zperm]
    sb = s0[:, zperm]
    yb = np.ascontiguousarray(y0[:, g.perm])
    dsucc = bfam.dcoef[:, g.succ_c] * g.has_succ[None, :]
    for t in range(g.m - 1, -1, -1):
        if g.has_succ[t]:
            yb[:, t] += dsucc[:, t] * yb[:, g.succ_c[t]]
    return xb, yb, sb


def banded_dual_to_std(bfam: BandedFamilyLP, yb: np.ndarray) -> np.ndarray:
    """Banded-basis dual -> original row order (``y = P' E' y_banded``)."""
    g = bfam.geom
    dsucc = bfam.dcoef[:, g.succ_c] * g.has_succ[None, :]
    yt = yb - dsucc * yb[:, g.succ_c]
    y = np.empty_like(yt)
    y[:, g.perm] = yt
    return y


def banded_row_transfer(geom_src: BandedGeometry, geom_dst: BandedGeometry):
    """Original-row correspondence between two banded geometries.

    Two padded ``(N, M_bucket)`` buckets of the same formulation family
    share their ``(block, slot)`` coordinate system: block ``k`` is the
    k-th chain segment and the per-block row-kind order is fixed by the
    formulation's :class:`BandedStructure`, so a row present in both
    geometries sits at the same coordinate in both ``posmat``s.  Border
    (mass/arrowhead) rows are matched by index.  This is the row map
    that generalizes :func:`banded_warm_convert`'s within-bucket
    identity: it lets an anchor dual from one bucket seed a neighboring
    bucket of the same prefix family (rows only the larger bucket has
    start at zero and are interior-shifted by the warm-start machinery).

    Returns ``(src_rows, dst_rows)`` — equal-length original-row index
    arrays such that ``y_dst[:, dst_rows] = y_src[:, src_rows]``.
    """
    K = min(geom_src.K, geom_dst.K)
    s = min(geom_src.s, geom_dst.s)
    pa = geom_src.posmat[:K, :s]
    pb = geom_dst.posmat[:K, :s]
    both = (pa >= 0) & (pb >= 0)
    p = min(geom_src.p, geom_dst.p)
    src_pos = np.concatenate(
        [pa[both], geom_src.n_band + np.arange(p, dtype=np.int64)])
    dst_pos = np.concatenate(
        [pb[both], geom_dst.n_band + np.arange(p, dtype=np.int64)])
    return geom_src.perm[src_pos], geom_dst.perm[dst_pos]


def _banded_ops(geom: BandedGeometry, F, ext, dcoef, colix,
                Fg, Hg, Ug, Bq, impl: str = "scan",
                interpret: bool = False, precision: str = "fp64",
                refine_max: int = _precision.DEFAULT_REFINE_MAX,
                refine_tol: float = _precision.DEFAULT_REFINE_TOL):
    """Linear maps + block-tridiagonal-arrowhead normal solver (one lane).

    The normal matrix ``A D A'`` in the banded basis is block
    tridiagonal (diagonal blocks ``D_k``, couplings ``O_k``) with a
    dense ``p``-row border (``U_k``, ``D_b``) from the mass row.  Build
    cost is ``O(K s^2 w)`` via the per-block column supports and the
    factorization is a scan of ``s x s`` Cholesky steps — versus
    ``O(m^2 nv)`` build + ``O(m^3)`` factor on the dense paths.

    The factor/substitution passes live in
    :mod:`repro.kernels.dlt_banded_chol`.  The fp64 factor always runs
    the pure-JAX scans; ``impl`` selects what factors the mixed
    policy's Jacobi-equilibrated fp32 blocks: the same scans
    (``"scan"``) or the Pallas port (``"pallas"``, with ``interpret``
    running the kernel body uncompiled off the TPU).  The Pallas kernel
    only ever sees fp32 operands: the TPU's kernel compiler has no
    64-bit types.  The returned fp32 solver is wrapped in fp64
    iterative refinement.

    Returns ``(A_mul, AT_mul, make_normal_solver, make_fp32_solver)``
    (the last is None under the fp64 policy).
    """
    m, nv, K, s, p = geom.m, geom.nv, geom.K, geom.s, geom.p
    ext_prev = ext[geom.dprev_c]
    dsucc = dcoef[geom.succ_c] * geom.has_succ

    def A_mul(z):
        v, e = z[:nv], z[nv:]
        return F @ v + ext * e - dcoef * ext_prev * e[geom.dprev_c]

    def AT_mul(y):
        return jnp.concatenate([F.T @ y, ext * (y - dsucc * y[geom.succ_c])])

    def _blocks(dinv, dtype):
        """Build the four normal-equation blocks in ``dtype`` (no ridge)."""
        def cast(a):
            return a.astype(dtype)

        dv, dz = dinv[:nv], dinv[nv:]
        dvc = cast(dv)
        Dg = dvc[colix]                                  # (K, w)
        Fgc, Hgc, Ugc, Bqc = cast(Fg), cast(Hg), cast(Ug), cast(Bq)
        Dblk = jnp.einsum("ksw,kw,ktw->kst", Fgc, Dg, Fgc)
        Oblk = jnp.einsum("ksw,kw,ktw->kst", Hgc, Dg, Fgc)
        Ublk = jnp.einsum("kpw,kw,ksw->kps", Ugc, Dg, Fgc)
        Db = (Bqc * dvc[None, :]) @ Bqc.T

        # slack/artificial tridiagonal (position space)
        dz_p = dz[geom.dprev_c]
        diagv = cast(ext * ext * dz
                     + dcoef * dcoef * ext_prev * ext_prev * dz_p)
        offv = cast(-dcoef * ext_prev * ext_prev * dz_p)
        nb = geom.n_band
        Dblk = Dblk.at[geom.bkb, geom.slotb, geom.slotb].add(diagv[:nb])
        Db = Db + jnp.diag(diagv[nb:])
        ps, pc = geom.pair_same, geom.pair_cross
        Dblk = Dblk.at[ps[0], ps[1], ps[2]].add(offv[geom.pair_st])
        Dblk = Dblk.at[ps[0], ps[2], ps[1]].add(offv[geom.pair_st])
        Oblk = Oblk.at[pc[0], pc[1], pc[2]].add(offv[geom.pair_ct])
        return Dblk, Oblk, Ublk, Db

    posc = jnp.where(geom.posmat >= 0, geom.posmat, 0)

    def _band_solve(C, X, V, Cb, rhs, scale=None, impl="scan"):
        """Scatter rhs into band layout, run the substitutions, gather."""
        rs = rhs if scale is None else rhs * scale
        rband = (rs[posc] * (geom.posmat >= 0)).astype(C.dtype)  # (K, s)
        rb = rs[geom.n_band:].astype(C.dtype)
        wband, wb = _chol_kernels.solve(C, X, V, Cb, rband, rb,
                                        impl=impl, interpret=interpret)
        w = jnp.concatenate([wband[geom.bkb, geom.slotb], wb])
        w = w.astype(rhs.dtype)
        return w if scale is None else w * scale

    def make_normal_solver(dinv):
        rhs_dtype = F.dtype
        Dblk, Oblk, Ublk, Db = _blocks(dinv, rhs_dtype)

        # tiny relative ridge (also keeps padded slots factorizable)
        tr = (jnp.sum(jnp.diagonal(Dblk, axis1=1, axis2=2))
              + jnp.trace(Db))
        ridge = 1e-13 * (tr / m + 1.0)
        Dblk = Dblk + ridge * jnp.eye(s, dtype=rhs_dtype)[None]
        Db = Db + ridge * jnp.eye(p, dtype=rhs_dtype)

        Opad = jnp.concatenate(
            [jnp.zeros((1, s, s), dtype=rhs_dtype), Oblk[:-1]], axis=0)

        C, X, V, Cb = _chol_kernels.factor(Dblk, Opad, Ublk, Db)
        return lambda rhs: _band_solve(C, X, V, Cb, rhs)

    def _band_mul(D64, O64, U64, Db64):
        """fp64 normal-equations matvec from the assembled blocks.

        The exact refinement operator: the blocks ARE ``A D A'`` in the
        banded basis (no ridge), and a block-tridiagonal matvec is
        ``O(K s^2)`` versus the dense ``F`` matvec a generic
        ``A_mul(dinv * AT_mul(w))`` would pay twice per residual.
        """
        Opad = jnp.concatenate(
            [jnp.zeros((1, s, s), dtype=D64.dtype), O64[:-1]], axis=0)
        Onext = jnp.concatenate(
            [O64[:-1], jnp.zeros((1, s, s), dtype=D64.dtype)], axis=0)

        def M_mul(w):
            u = w[posc] * (geom.posmat >= 0)            # (K, s)
            ub = w[geom.n_band:]                        # (p,)
            u_prev = jnp.concatenate([jnp.zeros((1, s), u.dtype), u[:-1]])
            u_next = jnp.concatenate([u[1:], jnp.zeros((1, s), u.dtype)])
            band = (jnp.einsum("kst,kt->ks", D64, u)
                    + jnp.einsum("kst,kt->ks", Opad, u_prev)
                    + jnp.einsum("kts,kt->ks", Onext, u_next)
                    + jnp.einsum("kps,p->ks", U64, ub))
            border = jnp.einsum("kps,ks->p", U64, u) + Db64 @ ub
            return jnp.concatenate([band[geom.bkb, geom.slotb], border])

        return M_mul

    make_fp32 = None
    if precision == "mixed":
        def make_fp32(dinv):
            f32 = jnp.float32
            # one exact fp64 build: the refinement operator, and (cast)
            # the fp32 factor input — rebuilding in fp32 would route the
            # einsums through XLA's slow small-fp32-dot path anyway
            D64, O64, U64, Db64 = _blocks(dinv, F.dtype)
            M_mul = _band_mul(D64, O64, U64, Db64)
            with jax.named_scope(_precision.FP32_FACTOR_SCOPE):
                Dblk, Oblk, Ublk, Db = (a.astype(f32) for a in
                                        (D64, O64, U64, Db64))

                # Jacobi equilibration: unit block diagonals so the
                # relative FP32_RIDGE keeps padded/degenerate slots
                # factorizable and cond() fits fp32's range longer.
                dd = jnp.diagonal(Dblk, axis1=1, axis2=2)    # (K, s)
                sb = jnp.where(dd > 0, jax.lax.rsqrt(jnp.clip(dd, 1e-30)),
                               jnp.ones((), f32))
                db = jnp.diagonal(Db)
                scb = jnp.where(db > 0, jax.lax.rsqrt(jnp.clip(db, 1e-30)),
                                jnp.ones((), f32))
                sb_next = jnp.concatenate([sb[1:], jnp.ones((1, s), f32)])
                Dblk = sb[:, :, None] * Dblk * sb[:, None, :]
                # Oblk[k] couples block k+1 rows to block k columns
                Oblk = sb_next[:, :, None] * Oblk * sb[:, None, :]
                Ublk = scb[None, :, None] * Ublk * sb[:, None, :]
                Db = scb[:, None] * Db * scb[None, :]
                Dblk = Dblk + _precision.FP32_RIDGE * jnp.eye(s, dtype=f32)
                Db = Db + _precision.FP32_RIDGE * jnp.eye(p, dtype=f32)

                Opad = jnp.concatenate(
                    [jnp.zeros((1, s, s), dtype=f32), Oblk[:-1]], axis=0)
                C, X, V, Cb = _chol_kernels.factor(
                    Dblk, Opad, Ublk, Db, impl=impl, interpret=interpret)

                # position-space row scale S: solve M w = r via the
                # factored S M S with w = S solve(S r)
                scale = jnp.concatenate(
                    [sb[geom.bkb, geom.slotb], scb]).astype(F.dtype)

            def solve32(rhs):
                with jax.named_scope(_precision.FP32_FACTOR_SCOPE):
                    return _band_solve(C, X, V, Cb, rhs, scale=scale,
                                       impl=impl)

            return _precision.refined_solver(
                solve32, M_mul, refine_max, refine_tol)

    return A_mul, AT_mul, make_normal_solver, make_fp32


def _hsde_ipm_banded(c, F, b, ext, dcoef, colix, Fg, Hg, Ug, Bq,
                     max_iter: int, tol: float, geom=None, init=None,
                     impl: str = "scan", interpret: bool = False,
                     precision: str = "fp64",
                     refine_max: int = _precision.DEFAULT_REFINE_MAX,
                     refine_tol: float = _precision.DEFAULT_REFINE_TOL):
    """Banded instantiation of the HSDE kernel (one lane, vmapped).

    ``impl="pallas"`` swaps the mixed policy's fp32 factor/substitution
    scans for the Pallas ``dlt_banded_chol`` kernel (``interpret`` runs
    it uncompiled for backends without the native lowering).
    """
    A_mul, AT_mul, make_solver, make_fp32 = _banded_ops(
        geom, F, ext, dcoef, colix, Fg, Hg, Ug, Bq,
        impl=impl, interpret=interpret, precision=precision,
        refine_max=refine_max, refine_tol=refine_tol)
    return _hsde_ipm_core(c, b, A_mul, AT_mul, make_solver, max_iter, tol,
                          init=init, make_fp32_solver=make_fp32)


def _hsde_ipm_banded_warm(c, F, b, ext, dcoef, colix, Fg, Hg, Ug, Bq,
                          x0, y0, s0, max_iter: int, tol: float, geom=None,
                          impl: str = "scan", interpret: bool = False,
                          precision: str = "fp64",
                          refine_max: int = _precision.DEFAULT_REFINE_MAX,
                          refine_tol: float = _precision.DEFAULT_REFINE_TOL):
    """Banded instantiation restarted from a banded-basis warm triple."""
    return _hsde_ipm_banded(c, F, b, ext, dcoef, colix, Fg, Hg, Ug, Bq,
                            max_iter, tol, geom=geom, init=(x0, y0, s0),
                            impl=impl, interpret=interpret,
                            precision=precision, refine_max=refine_max,
                            refine_tol=refine_tol)


def _hsde_ipm_dense_warm(c, A, b, x0, y0, s0, max_iter: int, tol: float,
                         precision: str = "fp64",
                         refine_max: int = _precision.DEFAULT_REFINE_MAX,
                         refine_tol: float = _precision.DEFAULT_REFINE_TOL):
    """Dense instantiation restarted from an interior ``(x0, y0, s0)``."""
    return _hsde_ipm(c, A, b, max_iter, tol, init=(x0, y0, s0),
                     precision=precision, refine_max=refine_max,
                     refine_tol=refine_tol)


@functools.lru_cache(maxsize=None)
def _jitted_batch_solver(max_iter: int, tol: float):
    fn = functools.partial(_hsde_ipm, max_iter=max_iter, tol=tol)
    return jax.jit(jax.vmap(fn))


def solve_lp_batch(c, A, b, max_iter: int = 25, tol: float = 1e-8):
    """jit(vmap) fixed-budget LP solve over stacked standard-form LPs.

    Args:
      c: (B, n) objective;  A: (B, m, n) equality matrix;  b: (B, m) rhs
         (problem reads min c'z s.t. Az=b, z>=0 per batch lane).
    Returns:
      (x (B, n), obj (B,), status (B,), iters (B,)) — status per lane:
      0 optimal, 1 iteration budget exhausted, 2 infeasible/unbounded.

    This is the generic dense entry point; :func:`batched_solve` routes
    through the structured ``[F | I]`` kernel instead.  Runs in float64
    under a locally scoped ``x64_scope`` so the rest of the (float32)
    model stack is unaffected.
    """
    with _precision.x64_scope():
        c = jnp.asarray(c, jnp.float64)
        A = jnp.asarray(A, jnp.float64)
        b = jnp.asarray(b, jnp.float64)
        out = _jitted_batch_solver(int(max_iter), float(tol))(c, A, b)
        return tuple(np.asarray(t) for t in out[:4])


# ---------------------------------------------------------------------------
# Compiled-family cache (owned by the engine; module-level view for ops)
# ---------------------------------------------------------------------------

#: Default entry count of a :class:`~repro.core.dlt.engine.DLTEngine`'s
#: compiled-executable LRU.  Each entry is one ahead-of-time compiled
#: (kernel kind, batch, rows, vars, budget) family shape; eviction just
#: means recompiling on next use.  Sized for the banded/structured kernel
#: split plus the adaptive warm budgets, which roughly double the shape
#: space a mixed workload touches.  Override per engine via
#: ``EngineConfig.compile_cache_size``.
COMPILE_CACHE_SIZE = 128


def compile_cache_info() -> dict:
    """Compiled-family cache state of the shared default engine.

    Returns shape keys currently held by the LRU plus the engine's
    hit/miss counters and — when the persistent cache is on (see
    :func:`~repro.core.dlt.engine.enable_compile_cache`) — the JAX
    compilation-cache directory and its entry count.  Sessions built
    with their own :class:`DLTEngine` should call
    ``engine.compile_cache_info()`` instead.
    """
    from .engine import get_default_engine

    return get_default_engine().compile_cache_info()


# ---------------------------------------------------------------------------
# Size-bucketed batching
# ---------------------------------------------------------------------------

def _bucket_m(m: int, edges: Sequence[int]) -> int:
    for e in edges:
        if m <= e:
            return e
    return m


def _group_lanes(bs: BatchedSystemSpec, bucket: str,
                 m_edges: Sequence[int],
                 fm: "Formulation | None" = None):
    """Order-preserving lane groups keyed by padded bucket shape.

    The key is ``(n_sources, m_bucket) + formulation extra key``: a
    formulation whose LP shape depends on a declared extra axis (e.g.
    the installment count) appends that axis' bucket through
    ``Formulation.group_key``, so lanes with incompatible padded shapes
    never share a family.
    """
    if bucket not in ("none", "size"):
        raise ValueError(f"unknown bucket mode {bucket!r}: use 'size' or 'none'")
    groups: "OrderedDict[tuple, list]" = OrderedDict()
    for k in range(bs.batch):
        # even unbucketed lanes split on the formulation key: lanes from
        # different extra-axis buckets have incompatible padded LP shapes
        key = ((bs.n_max, bs.m_max) if bucket == "none"
               else (int(bs.n_sources[k]), _bucket_m(int(bs.n_procs[k]),
                                                     m_edges)))
        if fm is not None:
            key = key + tuple(fm.group_key(bs, k))
        groups.setdefault(key, []).append(k)
    return {key: np.asarray(idx) for key, idx in groups.items()}


# ---------------------------------------------------------------------------
# Vectorized paper-constraint verifiers (compat wrappers over the registry)
# ---------------------------------------------------------------------------

def verify_frontend_batch(bs: BatchedSystemSpec, beta: np.ndarray,
                          finish: np.ndarray, tol: float = 1e-6) -> np.ndarray:
    """Check every Sec 3.1 constraint per scenario; True where all hold."""
    return get_formulation("frontend").verify_batch(
        bs, BatchFields(beta=beta, finish=finish), tol)


def verify_nofrontend_batch(bs: BatchedSystemSpec, beta, TS, TF, finish,
                            tol: float = 1e-6) -> np.ndarray:
    """Check every Sec 3.2 constraint per scenario; True where all hold."""
    return get_formulation("nofrontend").verify_batch(
        bs, BatchFields(beta=beta, TS=TS, TF=TF, finish=finish), tol)


# ---------------------------------------------------------------------------
# End-to-end batched solve
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BatchedSolution:
    """Solved batch in the padded canonical layout.

    ``beta[k]`` rows/cols beyond ``(n_sources[k], n_procs[k])`` are zero.
    ``status[k]`` follows the module STATUS_* codes; infeasible scenarios
    carry NaN finish times.  ``fallback_mask[k]`` is True where the IPM
    could not certify the lane and the scalar simplex oracle was (or would
    have been) consulted; ``fallback_count`` totals them.

    ``precision`` records the engine policy that produced the batch;
    under ``"mixed"``, ``refine_iterations[k]`` counts the lane's
    iterative-refinement corrections, ``phase1_handover[k]`` says why
    the lane left its fp32 phase early (a ``HANDOVER_*`` code of
    :mod:`.precision`, 0 where it did not) and
    ``precision_fallback_mask[k]`` marks lanes the fp32-factor path
    could not certify and that were re-solved with the full-fp64
    executable.
    """

    spec: BatchedSystemSpec
    frontend: bool
    finish_time: np.ndarray       # (B,)
    beta: np.ndarray              # (B, N_max, M_max)
    status: np.ndarray            # (B,)
    iterations: np.ndarray        # (B,)
    TS: Optional[np.ndarray] = None  # (B, N_max, M_max) no-frontend only
    TF: Optional[np.ndarray] = None
    formulation: str = ""
    fallback_mask: Optional[np.ndarray] = None  # (B,) bool
    precision: str = "fp64"
    refine_iterations: Optional[np.ndarray] = None  # (B,) mixed only
    phase1_handover: Optional[np.ndarray] = None  # (B,) mixed only
    precision_fallback_mask: Optional[np.ndarray] = None  # (B,) bool

    @property
    def batch(self) -> int:
        return self.spec.batch

    @property
    def fallback_count(self) -> int:
        """Lanes the vectorized IPM could not certify on its own."""
        return 0 if self.fallback_mask is None else int(self.fallback_mask.sum())

    def monetary_cost(self) -> np.ndarray:
        """Eq 17 per scenario (NaN where unsolved or the spec had no C)."""
        if self.spec.C is None:
            return np.full(self.batch, np.nan)
        cost = np.einsum("bnm,bm->b", self.beta, self.spec.A * self.spec.C)
        cost[self.status != STATUS_OPTIMAL] = np.nan
        if self.spec.has_cost is not None:
            cost[~self.spec.has_cost] = np.nan
        return cost

    def schedule(self, k: int, strict: bool = False) -> Optional[Schedule]:
        """Scenario k as a scalar Schedule.

        Lanes without a certified solution return ``None`` by default;
        with ``strict=True`` they raise instead — an
        :class:`InfeasibleError` for lanes the solver (and, when the
        oracle fallback ran, the simplex) proved infeasible, otherwise a
        ``RuntimeError`` naming the lane's status code and whether the
        scalar oracle was consulted.  ``engine.map`` serves with
        ``strict=True`` so failed lanes can never be mistaken for
        "no schedule needed".
        """
        if self.status[k] != STATUS_OPTIMAL:
            if not strict:
                return None
            names = {STATUS_OPTIMAL: "optimal",
                     STATUS_MAXITER: "iteration budget exhausted",
                     STATUS_INFEASIBLE: "infeasible"}
            st = int(self.status[k])
            fb = (self.fallback_mask is not None
                  and bool(self.fallback_mask[k]))
            if st == STATUS_INFEASIBLE:
                how = ("infeasibility confirmed by the scalar simplex "
                       "oracle on fallback" if fb
                       else "interior-point verdict; no oracle fallback ran")
            else:
                # an uncertified lane survives only when the fallback was
                # disabled — otherwise the simplex would have settled it
                how = ("lane was flagged for oracle fallback but the "
                       "fallback was disabled (oracle_fallback=False)"
                       if fb else "no oracle fallback ran")
            msg = (f"lane {k} has no schedule: status={st} "
                   f"({names.get(st, 'unknown')}); {how}; "
                   f"precision={self.precision}")
            if self.precision == "mixed":
                # name the refinement state so mixed-path failures are
                # diagnosable without re-running the batch in fp64
                nref = (int(self.refine_iterations[k])
                        if self.refine_iterations is not None else 0)
                pfb = (self.precision_fallback_mask is not None
                       and bool(self.precision_fallback_mask[k]))
                state = ("lane failed again after the full-fp64 "
                         "re-factor fallback" if pfb
                         else "fp32+refinement path, no fp64 re-factor "
                         "fallback ran")
                msg += f" ({nref} refinement corrections; {state})"
            if st == STATUS_INFEASIBLE:
                raise InfeasibleError(msg)
            raise RuntimeError(msg)
        n, m = int(self.spec.n_sources[k]), int(self.spec.n_procs[k])
        kw = {}
        if not self.frontend and self.TS is not None:
            kw = {"TS": self.TS[k, :n, :m], "TF": self.TF[k, :n, :m]}
        return Schedule(
            spec=self.spec.scenario(k),
            beta=self.beta[k, :n, :m],
            finish_time=float(self.finish_time[k]),
            frontend=self.frontend,
            **kw,
        )

    def schedules(self, strict: bool = False) -> list:
        return [self.schedule(k, strict=strict) for k in range(self.batch)]


def batched_solve(
    specs,
    frontend: bool = True,
    formulation: "Formulation | str | None" = None,
    max_iter: int = 40,
    tol: float = 1e-8,
    verify: bool = True,
    oracle_fallback: bool = True,
    presorted: bool = False,
    chunk_size: int = 256,
    bucket: str = "size",
    m_bucket_edges: Sequence[int] = DEFAULT_M_BUCKET_EDGES,
) -> BatchedSolution:
    """Solve a whole family of DLT programs in one jitted vmapped call.

    Args:
      specs: a sequence of :class:`SystemSpec` or a ready
        :class:`BatchedSystemSpec` (ragged (N, M) welcome — scenarios are
        embedded in shared padded LP shapes).
      frontend: Sec 3.1 (True) vs Sec 3.2 (False) formulation, whole batch.
      formulation: registry name or :class:`Formulation` overriding
        ``frontend``.  Defaults to ``"frontend"`` / the column-reduced
        ``"nofrontend_reduced"`` (exactly equivalent to Sec 3.2 — pin
        ``"nofrontend"`` for the full interval program).
      max_iter / tol: iteration budget and residual tolerance of the
        interior-point solver.
      verify: re-check each solved scenario against the paper constraint
        sets (vectorized NumPy oracle; the reduced formulation is checked
        against the ORIGINAL Sec 3.2 constraints).
      oracle_fallback: every scenario the IPM could not certify optimal —
        iteration-budget misses, verification misses, AND infeasibility
        verdicts — is re-solved with the scalar simplex path, so the
        returned batch is always simplex-confirmed: status 2 means the
        oracle agreed the program is infeasible.  Fallbacks are recorded
        in ``fallback_mask`` / ``fallback_count`` either way.
      presorted: specs are already canonical (G-/A-ascending).
      chunk_size: scenarios per device batch (bounds peak memory for the
        stacked constraint tensors).
      bucket: ``"size"`` groups ragged scenarios into per-(N, M-bucket)
        padded shapes (cuts the padding blowup for mixed size families);
        ``"none"`` embeds everything in one global-max shape.
      m_bucket_edges: processor-count bucket boundaries for ``"size"``.

    This is a compatibility shim over the session API: it runs on the
    shared default :class:`~repro.core.dlt.engine.DLTEngine` (so repeat
    calls share one compiled-shape cache) with the keyword knobs applied
    as per-call config overrides.  New code should configure a
    :class:`~repro.core.dlt.engine.DLTEngine` once and call
    ``engine.solve_batch`` / ``engine.map`` instead.
    """
    from .engine import get_default_engine

    return get_default_engine().configured(
        max_iter=max_iter, tol=tol, verify=verify,
        oracle_fallback=oracle_fallback, chunk_size=chunk_size,
        bucket=bucket, m_bucket_edges=tuple(m_bucket_edges),
    ).solve_batch(specs, frontend=frontend, formulation=formulation,
                  presorted=presorted)
