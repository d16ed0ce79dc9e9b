"""Precision policy for the batched IPM: fp32 factor + fp64 refinement.

The engine solves the HSDE normal equations ``M w = rhs`` with
``M = A D A'`` once per direction (three times per IPM iteration).  In
fp64 mode the factorization runs entirely in double precision.  In
``"mixed"`` mode the matrix is *built and factored in fp32* and each
solve is polished by a bounded iterative-refinement loop whose residual
``r = rhs - M w`` is evaluated with the exact fp64 operator — the one
truncation that must never happen (dltlint DL007 checks it statically).

A single fp32 factorization cannot certify tol=1e-8 near convergence:
``cond(M)`` grows like ``1/mu`` and exceeds the fp32 range in the IPM
endgame, so refinement stalls on a large fraction of lanes (measured on
the structured path: >half the batch).  The mixed policy therefore runs
*two phases* inside one compiled kernel:

1. while ``mu > SWITCH_MU * mu0``: fp32 factor + fp64-residual
   refinement (the bulk of the iterations, where the arithmetic win
   lives and cond(M) is benign);
2. a plain fp64 while_loop finishes to tolerance, so convergence and
   certification are identical to the fp64 policy.

A lane whose phase-1 step does not lower mu leaves phase 1 early (a
``HANDOVER_*`` code per lane, counted in
``stats.phase1_handover_lanes``); a lane that still fails to certify is
re-solved with a full-fp64 executable by the engine
(``stats.precision_fallback_lanes``).

Everything fp32 is wrapped in ``jax.named_scope(FP32_FACTOR_SCOPE)`` so
dltlint's DL002 truncation rule can allowlist intentional casts, and the
fp64 residual lives under ``REFINE_RESIDUAL_SCOPE`` for DL007.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp

PRECISIONS = ("fp64", "mixed")

#: env var consulted when EngineConfig.precision is None.
PRECISION_ENV = "DLT_PRECISION"

#: named_scope wrapping every intentional fp64->fp32 truncation
#: (matrix build, factor, correction solve).  dltlint DL002 downgrades
#: truncations inside this scope to notes.
FP32_FACTOR_SCOPE = "dlt_fp32_factor"

#: named_scope wrapping the fp64 refinement residual r = rhs - M w.
#: dltlint DL007 asserts nothing inside it is computed in fp32.
REFINE_RESIDUAL_SCOPE = "dlt_refine_residual"

#: phase-1 -> phase-2 handover: once mu falls below SWITCH_MU * mu0 the
#: fp32 factor can no longer be refined reliably and the fp64 loop takes
#: over.  Relative to the lane's own initial mu so warm restarts behave.
SWITCH_MU = 1e-5

#: Why a mixed lane left phase 1 before mu reached the switch point:
#: it did not (NONE); its step was refused or had zero length, which is
#: what a non-finite fp32 direction gives (NO_STEP); or a finite step
#: did not lower mu (NO_DESCENT).
HANDOVER_NONE, HANDOVER_NO_STEP, HANDOVER_NO_DESCENT = 0, 1, 2

#: diagonal ridge added to the *equilibrated* fp32 normal matrix
#: (unit diagonal after Jacobi scaling, so this is a relative shift a
#: few times fp32 eps — keeps near-degenerate blocks factorable).
FP32_RIDGE = 2e-7

DEFAULT_REFINE_MAX = 4

#: relative residual target for each refined phase-1 solve.  Phase-1
#: directions only need a few correct digits (certification happens in
#: the fp64 phase), and every extra refinement iteration costs an fp32
#: solve + an fp64 matvec — 1e-6 keeps ~1 refinement per solve on the
#: bench family versus ~2 at 1e-9, at identical final parity.
DEFAULT_REFINE_TOL = 1e-6


def x64_scope():
    """The float64 dtype scope every batched solve runs under.

    ``jax.enable_x64(True)`` used as a context manager: the switch is
    thread-local, so concurrent sessions never leak fp64 into each
    other or into float32 code outside the scope.
    """
    return jax.enable_x64(True)


def resolve_precision(precision: Optional[str]) -> str:
    """Resolve a config value (or None) to a concrete policy name.

    None defers to $DLT_PRECISION and falls back to "fp64".
    """
    if precision is None:
        precision = os.environ.get(PRECISION_ENV, "") or "fp64"
    if precision not in PRECISIONS:
        raise ValueError(
            f"precision must be one of {PRECISIONS}, got {precision!r}"
        )
    return precision


def fp32_cholesky(M64: jnp.ndarray) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Equilibrated fp32 Cholesky factor of a dense SPD matrix.

    Jacobi-scales ``M`` to unit diagonal, casts to fp32, adds a relative
    ridge and factors once; the returned closure solves fp64 rhs ->
    fp64 solution (the inner triangular solves run in fp32).
    """
    with jax.named_scope(FP32_FACTOR_SCOPE):
        d = jnp.diagonal(M64)
        sc64 = jnp.where(d > 0, jax.lax.rsqrt(jnp.clip(d, 1e-300)), 1.0)
        Ms = (sc64[:, None] * M64) * sc64[None, :]
        M32 = Ms.astype(jnp.float32)
        M32 = M32 + FP32_RIDGE * jnp.eye(M32.shape[0], dtype=jnp.float32)
        with jax.named_scope("ipm.factor"):
            L32 = jnp.linalg.cholesky(M32)

    def solve32(r: jnp.ndarray) -> jnp.ndarray:
        with jax.named_scope(FP32_FACTOR_SCOPE):
            r32 = (r * sc64).astype(jnp.float32)
            z = jax.scipy.linalg.solve_triangular(L32, r32, lower=True)
            w32 = jax.scipy.linalg.solve_triangular(
                L32, z, lower=True, trans=1
            )
        return w32.astype(jnp.float64) * sc64

    return solve32


def refined_solver(
    solve32: Callable[[jnp.ndarray], jnp.ndarray],
    M_mul: Callable[[jnp.ndarray], jnp.ndarray],
    refine_max: int,
    refine_tol: float,
) -> Callable[[jnp.ndarray], tuple]:
    """Iterative refinement around an fp32 factor.

    ``solve32`` maps an fp64 rhs to an fp64-typed correction via the
    fp32 factor; ``M_mul`` is the *exact* fp64 normal-equations
    operator.  Returns ``solve_M(rhs) -> (w, n_refine)``: corrections
    are only accepted while they shrink the fp64 residual.  A failed
    fp32 factor (NaN) gives a non-finite ``w``, which the IPM refuses to
    step along.
    """
    refine_max = int(refine_max)
    refine_tol = float(refine_tol)

    def solve_M(rhs):
        w = solve32(rhs)
        nrm = jnp.linalg.norm(rhs) + 1e-300
        with jax.named_scope(REFINE_RESIDUAL_SCOPE):
            r = rhs - M_mul(w)
        rn = jnp.linalg.norm(r)

        def cond(carry):
            it, _, _, rn = carry
            return (it < refine_max) & (rn > refine_tol * nrm)

        def body(carry):
            it, w, r, rn = carry
            d = solve32(r)
            w2 = w + d
            with jax.named_scope(REFINE_RESIDUAL_SCOPE):
                r2 = rhs - M_mul(w2)
            rn2 = jnp.linalg.norm(r2)
            better = rn2 < rn
            return (
                it + 1,
                jnp.where(better, w2, w),
                jnp.where(better, r2, r),
                jnp.where(better, rn2, rn),
            )

        it, w, _, _ = jax.lax.while_loop(
            cond, body, (jnp.asarray(0), w, r, rn)
        )
        return w, it

    return solve_M
