"""Pallas kernel for the block-tridiagonal-arrowhead Cholesky.

Port of the :mod:`.ref` scans: the factor pass walks the ``K`` pivot
blocks once, keeping the previous Cholesky factor, the eliminated
border rows and the border Schur accumulator in VMEM scratch, so the
whole factorization streams each ``(s, s)`` block through on-chip
memory exactly once instead of round-tripping the scan carry through
HBM.  The forward/backward substitution passes carry the ``(s, 1)``
running solution the same way (the backward pass iterates the grid in
reverse via its index maps).

Dense small-matrix primitives (``s`` is the per-processor block size,
typically < 16) are implemented in-kernel as masked ``fori_loop``
updates over full ``(s, s)`` tiles — ``lax.linalg`` is not legal inside
a Pallas body — which keeps every step a VPU-friendly broadcast:

* ``_chol``            right-looking Cholesky, one rank-1 update per column;
* ``_trisolve_lower``  forward substitution ``L Z = B``;
* ``_trisolve_lower_t`` backward substitution ``L' W = B``.

Non-SPD input (a failed interior-point step) propagates NaN exactly
like ``jnp.linalg.cholesky`` does, so the IPM's finite-step guard sees
the same signal on both implementations.

The kernels are written per lane (grid ``(K,)``) and batched by
``jax.vmap`` at the call site — Pallas prepends the batch axis to the
grid, and the ``@pl.when(program_id == 0)`` scratch resets re-arm per
lane because the block axis stays the innermost grid dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "banded_factor_pallas",
    "banded_solve_fwd_pallas",
    "banded_solve_bwd_pallas",
    "vmem_estimate",
]


def vmem_estimate(s: int, p: int, itemsize: int = 8) -> int:
    """Worst-case VMEM bytes of one grid step across the three kernels.

    The factor pass dominates: per step it holds the ``(1, s, s)`` /
    ``(1, p, s)`` input and output blocks (double-buffered by the
    Pallas pipeline, hence the x2), the ``(p, p)`` Schur output block,
    and the ``(s, s) + (p, s) + (p, p)`` carry scratch.  The estimate
    is an upper bound the dltlint DL006 rule checks against the
    per-backend VMEM budget; the authoritative per-trace number comes
    from the BlockSpecs of the traced ``pallas_call`` equations (see
    :func:`repro.analysis.dltlint.rules.pallas_call_vmem_bytes`) —
    this closed form exists for shape planning without a trace.
    """
    blocks = 2 * (s * s) + (p * s)            # factor inputs D, O, U
    blocks += 2 * (s * s) + (p * s) + (p * p)  # outputs C, X, V, S
    scratch = (s * s) + (p * s) + (p * p)
    return (2 * blocks + scratch) * itemsize


#: int32 zero for block index maps: a Python ``0`` would trace as int64
#: under ``x64_scope``, which the TPU kernel compiler refuses.
_0 = np.int32(0)


def _iota2(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _loop(s, step, init):
    """``fori_loop`` over ``0..s-1`` with an int32 counter.

    The engine traces under ``x64_scope``, where a Python-int bound
    would make the counter int64 — a type the TPU kernel compiler does
    not have.
    """
    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(s), step, init)


def _mm(a, b):
    """``a @ b`` at full fp32 precision.

    On the TPU an fp32 matrix product otherwise runs in a single
    bfloat16 pass, which the mixed policy's refinement loop could not
    make up for: it is written for an fp32 factor.
    """
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=a.dtype)


def _eye(s, dt):
    return (_iota2((s, s), 0) == _iota2((s, s), 1)).astype(dt)


def _chol(A):
    """Right-looking Cholesky of an (s, s) SPD tile (masked updates)."""
    s = A.shape[0]
    rows_c = _iota2((s, 1), 0)
    cols = _iota2((s, s), 1)

    def step(j, carry):
        A, L = carry
        colj = jnp.sum(jnp.where(cols == j, A, 0.0), axis=1,
                       keepdims=True)                       # (s, 1) = A[:, j]
        d = jnp.sqrt(jnp.sum(jnp.where(rows_c == j, colj, 0.0)))
        l = jnp.where(rows_c >= j, colj / d, 0.0)           # column j of L
        A = A - l * l.T                                     # rank-1 update
        L = jnp.where(cols == j, l, L)
        return A, L

    return _loop(s, step, (A, jnp.zeros_like(A)))[1]


def _trisolve_lower(L, B):
    """Forward substitution ``L Z = B`` (L lower with zeroed upper part)."""
    s, r = B.shape
    rows = _iota2((s, s), 0)
    cols = _iota2((s, s), 1)
    rows_b = _iota2((s, r), 0)

    def step(j, Z):
        Lrow = jnp.sum(jnp.where(rows == j, L, 0.0), axis=0,
                       keepdims=True)                       # (1, s) = L[j, :]
        ljj = jnp.sum(jnp.where((rows == j) & (cols == j), L, 0.0))
        Bj = jnp.sum(jnp.where(rows_b == j, B, 0.0), axis=0,
                     keepdims=True)                         # (1, r)
        # Z rows >= j are still zero, so Lrow @ Z covers exactly k < j
        zj = (Bj - _mm(Lrow, Z)) / ljj
        return jnp.where(rows_b == j, zj, Z)

    return _loop(s, step, jnp.zeros_like(B))


def _trisolve_lower_t(L, B):
    """Backward substitution ``L' W = B`` (same lower-storage L)."""
    s, r = B.shape
    rows = _iota2((s, s), 0)
    cols = _iota2((s, s), 1)
    rows_b = _iota2((s, r), 0)

    def step(t, W):
        j = s - 1 - t
        Lcol = jnp.sum(jnp.where(cols == j, L, 0.0), axis=1,
                       keepdims=True)                       # (s, 1) = L[:, j]
        ljj = jnp.sum(jnp.where((rows == j) & (cols == j), L, 0.0))
        Bj = jnp.sum(jnp.where(rows_b == j, B, 0.0), axis=0,
                     keepdims=True)
        # W rows <= j are still zero and L[k, j] = 0 for k < j
        wj = (Bj - _mm(Lcol.T, W)) / ljj
        return jnp.where(rows_b == j, wj, W)

    return _loop(s, step, jnp.zeros_like(B))


# ---------------------------------------------------------------------------
# factor pass
# ---------------------------------------------------------------------------

def _factor_kernel(D_ref, O_ref, U_ref, C_ref, X_ref, V_ref, S_ref,
                   c_scr, v_scr, s_scr, *, nblocks):
    k = pl.program_id(0)
    dt = D_ref.dtype

    @pl.when(k == 0)
    def _init():
        c_scr[...] = _eye(c_scr.shape[0], dt)
        v_scr[...] = jnp.zeros(v_scr.shape, dt)
        s_scr[...] = jnp.zeros(s_scr.shape, dt)

    Dk, Okp, Uk = D_ref[0], O_ref[0], U_ref[0]
    Xk = _trisolve_lower(c_scr[...], Okp.T).T
    Ck = _chol(Dk - _mm(Xk, Xk.T))
    Vk = _trisolve_lower(Ck, (Uk - _mm(v_scr[...], Xk.T)).T).T
    Sk = s_scr[...] + _mm(Vk, Vk.T)
    C_ref[0], X_ref[0], V_ref[0] = Ck, Xk, Vk
    c_scr[...], v_scr[...], s_scr[...] = Ck, Vk, Sk

    @pl.when(k == nblocks - 1)
    def _final():
        S_ref[...] = Sk


def banded_factor_pallas(Dblk, Opad, Ublk, *, interpret: bool = False):
    """Pallas counterpart of :func:`..ref.banded_factor` (one lane)."""
    K, s, _ = Dblk.shape
    p = Ublk.shape[1]
    dt = Dblk.dtype
    blk_ss = pl.BlockSpec((1, s, s), lambda k: (k, _0, _0))
    blk_ps = pl.BlockSpec((1, p, s), lambda k: (k, _0, _0))
    return pl.pallas_call(
        functools.partial(_factor_kernel, nblocks=K),
        grid=(K,),
        in_specs=[blk_ss, blk_ss, blk_ps],
        out_specs=[blk_ss, blk_ss, blk_ps,
                   pl.BlockSpec((p, p), lambda k: (_0, _0))],
        out_shape=[
            jax.ShapeDtypeStruct((K, s, s), dt),
            jax.ShapeDtypeStruct((K, s, s), dt),
            jax.ShapeDtypeStruct((K, p, s), dt),
            jax.ShapeDtypeStruct((p, p), dt),
        ],
        scratch_shapes=[pltpu.VMEM((s, s), dt), pltpu.VMEM((p, s), dt),
                        pltpu.VMEM((p, p), dt)],
        interpret=interpret,
    )(Dblk, Opad, Ublk)


# ---------------------------------------------------------------------------
# solve passes
# ---------------------------------------------------------------------------

def _fwd_kernel(C_ref, X_ref, r_ref, u_ref, u_scr):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        u_scr[...] = jnp.zeros(u_scr.shape, C_ref.dtype)

    rhs = r_ref[0].T - _mm(X_ref[0], u_scr[...])            # (s, 1)
    u = _trisolve_lower(C_ref[0], rhs)
    u_ref[0] = u.T
    u_scr[...] = u


def banded_solve_fwd_pallas(C, X, rband, *, interpret: bool = False):
    """Pallas counterpart of :func:`..ref.banded_solve_fwd` (one lane)."""
    K, s, _ = C.shape
    blk_ss = pl.BlockSpec((1, s, s), lambda k: (k, _0, _0))
    blk_s = pl.BlockSpec((1, 1, s), lambda k: (k, _0, _0))
    u = pl.pallas_call(
        _fwd_kernel,
        grid=(K,),
        in_specs=[blk_ss, blk_ss, blk_s],
        out_specs=blk_s,
        out_shape=jax.ShapeDtypeStruct((K, 1, s), C.dtype),
        scratch_shapes=[pltpu.VMEM((s, 1), C.dtype)],
        interpret=interpret,
    )(C, X, rband[:, None, :])
    return u[:, 0, :]


def _bwd_kernel(C_ref, Xn_ref, V_ref, u_ref, wb_ref, w_ref, w_scr):
    i = pl.program_id(0)                    # reversed: block K-1-i

    @pl.when(i == 0)
    def _init():
        w_scr[...] = jnp.zeros(w_scr.shape, C_ref.dtype)

    rhs = (u_ref[0].T - _mm(Xn_ref[0].T, w_scr[...])
           - _mm(V_ref[0].T, wb_ref[...]))                  # (s, 1)
    w = _trisolve_lower_t(C_ref[0], rhs)
    w_ref[0] = w.T
    w_scr[...] = w


def banded_solve_bwd_pallas(C, Xnext, V, u, wb, *, interpret: bool = False):
    """Pallas counterpart of :func:`..ref.banded_solve_bwd` (one lane).

    The grid runs the band in reverse through the index maps, so the
    scratch carry holds ``w_{k+1}`` exactly like the reference scan's
    ``reverse=True`` carry.
    """
    K, s, _ = C.shape
    p = V.shape[1]
    rev_ss = pl.BlockSpec((1, s, s), lambda i: (K - 1 - i, _0, _0))
    rev_ps = pl.BlockSpec((1, p, s), lambda i: (K - 1 - i, _0, _0))
    rev_s = pl.BlockSpec((1, 1, s), lambda i: (K - 1 - i, _0, _0))
    w = pl.pallas_call(
        _bwd_kernel,
        grid=(K,),
        in_specs=[rev_ss, rev_ss, rev_ps, rev_s,
                  pl.BlockSpec((p, 1), lambda i: (_0, _0))],
        out_specs=rev_s,
        out_shape=jax.ShapeDtypeStruct((K, 1, s), C.dtype),
        scratch_shapes=[pltpu.VMEM((s, 1), C.dtype)],
        interpret=interpret,
    )(C, Xnext, V, u[:, None, :], wb[:, None])
    return w[:, 0, :]
