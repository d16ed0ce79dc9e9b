"""Pure-JAX scan oracle for the block-tridiagonal-arrowhead Cholesky.

The batched DLT interior point reduces each iteration's normal equations
to a block-tridiagonal system (diagonal blocks ``D_k``, sub-diagonal
couplings ``O_k``) with a small dense border (``U_k`` rows, corner
``D_b``) from the mass-conservation row:

    [ D_0  O_1'              U_0' ]
    [ O_1  D_1  O_2'         U_1' ]
    [      O_2  D_2   ...    U_2' ]
    [            ...   ...    ... ]
    [ U_0  U_1  U_2   ...    D_b  ]

``banded_factor`` runs the blocked Cholesky as a :func:`jax.lax.scan` of
``s x s`` steps; ``banded_solve_fwd`` / ``banded_solve_bwd`` are the
matching substitution scans.  This is both the production path on
backends without the Pallas kernel and the parity oracle the Pallas
implementation (:mod:`.kernel`) is tested against.

Shapes (one lane — callers vmap): ``Dblk (K, s, s)``, ``Opad (K, s, s)``
(``Opad[k] = O_k``, with ``Opad[0] = 0``), ``Ublk (K, p, s)``,
``Db (p, p)``, rhs split into ``rband (K, s)`` and ``rb (p,)``.

fp64 blocks of width ``s <= SMALL_BLOCK_MAX`` (:func:`small_blocks`)
take an elementwise path: the pivot Cholesky is unrolled over its ``s``
columns and also yields the pivot's inverse factor, and every block
product is a broadcast multiply-and-sum.  A chip without an f64 unit
(the TPU) expands each f64 ``cholesky``, ``triangular_solve`` and
``dot_general`` into nested loops; straight-line elementwise code
compiles to none.  On that path the factor keeps the inverse factors
``C_k^{-1}`` (and ``Cb^{-1}``) in place of ``C_k`` (and ``Cb``), so
each substitution step is one multiply-and-sum; the solves read them
back by the same rule.  The border corner follows the rule on its own
width ``p``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "SMALL_BLOCK_MAX",
    "small_blocks",
    "chol_inv_small",
    "banded_factor",
    "banded_solve_fwd",
    "banded_solve_bwd",
    "corner_factor",
    "corner_solve",
    "factor",
    "solve",
]


#: contraction width at or below which the fp32 broadcast-sum matmul
#: beats XLA's batched dot (measured CPU crossover between s=6 and
#: s=13: at 6 the explicit form wins ~2.7x, at 13 it loses ~7x).
_MM_BSUM_MAX = 8


def _mm(a, b):
    """Tiny-block matmul, routed by dtype and block size.

    XLA CPU sends *small* batched fp32 dots (the vmapped ``(s, s)`` /
    ``(p, s)`` products here) down a path measured ~2.5x SLOWER than the
    same fp64 dot; an explicit broadcast-multiply + sum stays in the
    elementwise vectorizer and beats the fp32 dot ~2.7x — but its
    ``O(s^3)`` intermediate loses badly once blocks grow, so it only
    fires for fp32 at width <= ``_MM_BSUM_MAX``.  fp64 keeps
    ``dot_general`` (where it always wins on the CPU); only blocks above
    ``SMALL_BLOCK_MAX`` reach it in fp64.
    """
    if a.dtype == jnp.float32 and a.shape[-1] <= _MM_BSUM_MAX:
        return (a[..., :, :, None] * b[..., None, :, :]).sum(axis=-2)
    return a @ b


def _av(a, v):
    """``a @ v`` as multiply-and-sum: ``(r, s), (s,) -> (r,)``."""
    return (a * v[..., None, :]).sum(axis=-1)


def _mv(a, v):
    """Tiny matrix-vector product, same dtype routing as :func:`_mm`.

    The broadcast form is ``O(s^2)`` like the dot, so no size cutoff.
    """
    if a.dtype == jnp.float32:
        return _av(a, v)
    return a @ v


#: widest fp64 block whose factor and substitution steps run as
#: straight-line elementwise code (:func:`small_blocks`); wider blocks
#: keep the library Cholesky / triangular solve / dot.  Measured on one
#: TPU v5e (a factor and three solves, 16 lanes, K=20): the elementwise
#: steps run 19x-184x faster at every width from 4 to 31, but their
#: compile time grows with the unrolled width (5 s at s=7, 25 s at 22,
#: 60 s at 31, past 150 s at 46) while the library's stays 10-16 s, so
#: the cutoff stops where compiling costs at most 2.5x the library's.
SMALL_BLOCK_MAX = 22


def small_blocks(dtype, s: int) -> bool:
    """Do blocks of width ``s`` in ``dtype`` take the elementwise path?"""
    return jnp.dtype(dtype) == jnp.float64 and s <= SMALL_BLOCK_MAX


def _abt(a, b):
    """``a @ b.T`` as multiply-and-sum: ``(r, s), (t, s) -> (r, t)``."""
    return (a[..., :, None, :] * b[..., None, :, :]).sum(axis=-1)


def _atv(a, v):
    """``a.T @ v`` as multiply-and-sum: ``(r, s), (r,) -> (s,)``."""
    return (a * v[..., :, None]).sum(axis=-2)


def chol_inv_small(A):
    """Lower Cholesky factor ``L`` of SPD ``A (s, s)`` and ``L^{-1}``.

    Unrolled over the ``s`` columns (right-looking: sqrt, scale, rank-1
    update of the trailing block), with the forward substitution of
    ``L W = I`` riding the same steps, so both come out of elementwise
    ops alone.  A pivot that is not positive gives NaN, as
    ``jnp.linalg.cholesky`` does.
    """
    s = A.shape[-1]
    R = jnp.eye(s, dtype=A.dtype)       # rows j.. of I, less what is done
    cols, rows = [], []
    for j in range(s):
        r = 1.0 / jnp.sqrt(A[0, 0])
        lj = A[:, 0] * r                # column j of L, from row j down
        wj = R[0] * r                   # row j of L^{-1}
        A = A[1:, 1:] - lj[1:, None] * lj[None, 1:]
        R = R[1:] - lj[1:, None] * wj[None, :]
        cols.append(jnp.concatenate([jnp.zeros(j, A.dtype), lj]))
        rows.append(wj)
    return jnp.stack(cols, axis=1), jnp.stack(rows, axis=0)


def _factor_small(Dblk, Opad, Ublk):
    """:func:`banded_factor` for small fp64 blocks: ``(W, X, V, S)``.

    ``W[k] = C_k^{-1}``; ``X_k = O_k W_{k-1}'`` and
    ``V_k = (U_k - V_{k-1} X_k') W_k'`` are products with it.
    """
    K, s, _ = Dblk.shape
    p = Ublk.shape[1]
    dt = Dblk.dtype

    def factor_step(carry, inp):
        Wprev, Vprev, S = carry
        Dk, Okp, Uk = inp
        X = _abt(Okp, Wprev)
        _, Wk = chol_inv_small(Dk - _abt(X, X))
        Vk = _abt(Uk - _abt(Vprev, X), Wk)
        return (Wk, Vk, S + _abt(Vk, Vk)), (Wk, X, Vk)

    carry0 = (jnp.eye(s, dtype=dt), jnp.zeros((p, s), dt),
              jnp.zeros((p, p), dt))
    (_, _, S), (W, X, V) = jax.lax.scan(
        factor_step, carry0, (Dblk, Opad, Ublk))
    return W, X, V, S


def banded_factor(Dblk, Opad, Ublk):
    """Blocked Cholesky of the band: ``(C, X, V, S)``.

    ``C[k]`` is the Cholesky factor of the k-th pivot, ``X[k]`` the
    eliminated sub-diagonal coupling (``X[k] = O_k C_{k-1}^-T``),
    ``V[k]`` the eliminated border rows and ``S = sum_k V_k V_k'`` the
    border Schur accumulation (the caller factors ``D_b - S``).
    For small fp64 blocks ``C[k]`` holds ``C_k^{-1}`` instead.
    """
    K, s, _ = Dblk.shape
    if small_blocks(Dblk.dtype, s):
        return _factor_small(Dblk, Opad, Ublk)
    p = Ublk.shape[1]
    dt = Dblk.dtype

    def factor_step(carry, inp):
        Cprev, Vprev, S = carry
        Dk, Okp, Uk = inp
        X = jax.scipy.linalg.solve_triangular(Cprev, Okp.T, lower=True).T
        Ck = jnp.linalg.cholesky(Dk - _mm(X, X.T))
        Vk = jax.scipy.linalg.solve_triangular(
            Ck, (Uk - _mm(Vprev, X.T)).T, lower=True).T
        return (Ck, Vk, S + _mm(Vk, Vk.T)), (Ck, X, Vk)

    carry0 = (jnp.eye(s, dtype=dt), jnp.zeros((p, s), dt),
              jnp.zeros((p, p), dt))
    (_, _, S), (C, X, V) = jax.lax.scan(
        factor_step, carry0, (Dblk, Opad, Ublk))
    return C, X, V, S


def banded_solve_fwd(C, X, rband):
    """Forward substitution along the band: ``u (K, s)``."""
    s = C.shape[1]
    small = small_blocks(C.dtype, s)

    def fwd(u_prev, inp):
        Ck, Xk, rk = inp
        if small:       # Ck = C_k^{-1}
            u = _av(Ck, rk - _av(Xk, u_prev))
        else:
            u = jax.scipy.linalg.solve_triangular(
                Ck, rk - _mv(Xk, u_prev), lower=True)
        return u, u

    _, u = jax.lax.scan(fwd, jnp.zeros(s, C.dtype), (C, X, rband))
    return u


def banded_solve_bwd(C, Xnext, V, u, wb):
    """Backward substitution along the band given the border solve ``wb``.

    ``Xnext[k] = X[k+1]`` (zero-padded at the end) so each step only
    reads its own scan slice.  Returns ``wband (K, s)``.
    """
    s = C.shape[1]
    small = small_blocks(C.dtype, s)

    def bwd(w_next, inp):
        Ck, Xn, Vk, uk = inp
        if small:       # Ck = C_k^{-1}
            wk = _atv(Ck, uk - _atv(Xn, w_next) - _atv(Vk, wb))
        else:
            wk = jax.scipy.linalg.solve_triangular(
                Ck.T, uk - _mv(Xn.T, w_next) - _mv(Vk.T, wb), lower=False)
        return wk, wk

    _, wband = jax.lax.scan(bwd, jnp.zeros(s, C.dtype), (C, Xnext, V, u),
                            reverse=True)
    return wband


def corner_factor(Db, S):
    """Factor of the border corner ``D_b - S``: ``Cb``, or ``Cb^{-1}``
    where the ``p x p`` corner is small fp64 (:func:`small_blocks`)."""
    if small_blocks(Db.dtype, Db.shape[-1]):
        return chol_inv_small(Db - S)[1]
    return jnp.linalg.cholesky(Db - S)


def corner_solve(Cb, t):
    """Solve ``(D_b - S) wb = t`` with a :func:`corner_factor` result."""
    if small_blocks(Cb.dtype, Cb.shape[-1]):
        return _atv(Cb, _av(Cb, t))
    ub = jax.scipy.linalg.solve_triangular(Cb, t, lower=True)
    return jax.scipy.linalg.solve_triangular(Cb.T, ub, lower=False)


# ---------------------------------------------------------------------------
# One-shot convenience entry points (tests / standalone callers)
# ---------------------------------------------------------------------------

def factor(Dblk, Opad, Ublk, Db):
    """Full factorization ``(C, X, V, Cb)`` including the border corner."""
    C, X, V, S = banded_factor(Dblk, Opad, Ublk)
    return C, X, V, corner_factor(Db, S)


def solve(C, X, V, Cb, rband, rb):
    """Solve the full arrowhead system from a :func:`factor` result.

    Returns ``(wband (K, s), wb (p,))`` in block layout; callers gather
    the band part back to row positions.
    """
    u = banded_solve_fwd(C, X, rband)
    if V.dtype == jnp.float32 or small_blocks(V.dtype, V.shape[-1]):
        t = rb - (V * u[:, None, :]).sum(axis=(0, 2))
    else:
        t = rb - jnp.einsum("kps,ks->p", V, u)
    wb = corner_solve(Cb, t)
    Xnext = jnp.concatenate(
        [X[1:], jnp.zeros((1,) + X.shape[1:], X.dtype)], axis=0)
    wband = banded_solve_bwd(C, Xnext, V, u, wb)
    return wband, wb
