"""Implementation dispatch for the block-tridiagonal-arrowhead Cholesky.

One call surface, two implementations:

* ``impl="scan"``   — the pure-JAX :mod:`.ref` scans (every backend; the
  parity oracle);
* ``impl="pallas"`` — the :mod:`.kernel` Pallas port (TPU natively,
  compiled for fp32 operands only; CPU with ``interpret=True``, which is
  how the tests check parity).

The functions are thin and **not** jitted: the batched IPM calls them
inside its own jitted, vmapped body.  :func:`pallas_supported` is the
single feasibility predicate the engine's kernel routing consults.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import kernel, ref

__all__ = [
    "IMPLS",
    "pallas_supported",
    "banded_factor",
    "banded_solve_fwd",
    "banded_solve_bwd",
    "factor",
    "solve",
]

IMPLS = ("scan", "pallas")


def pallas_supported(backend: "str | None" = None,
                     interpret: bool = False) -> bool:
    """Can the Pallas implementation run here?

    Compiled Pallas needs the TPU lowering this kernel is written
    against.  Interpret mode (the kernel body as plain jnp ops) is the
    CPU parity tool and never runs on the TPU, where it would stand in
    for the compiled kernel at a fraction of its speed.
    """
    if backend is None:
        backend = jax.default_backend()
    return (backend != "tpu") if interpret else (backend == "tpu")


def _check_impl(impl: str) -> None:
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}: use one of {IMPLS}")


def banded_factor(Dblk, Opad, Ublk, *, impl: str = "scan",
                  interpret: bool = False):
    """Blocked band Cholesky ``(C, X, V, S)`` — see :func:`.ref.banded_factor`."""
    _check_impl(impl)
    if impl == "pallas":
        return kernel.banded_factor_pallas(Dblk, Opad, Ublk,
                                           interpret=interpret)
    return ref.banded_factor(Dblk, Opad, Ublk)


def banded_solve_fwd(C, X, rband, *, impl: str = "scan",
                     interpret: bool = False):
    _check_impl(impl)
    if impl == "pallas":
        return kernel.banded_solve_fwd_pallas(C, X, rband,
                                              interpret=interpret)
    return ref.banded_solve_fwd(C, X, rband)


def banded_solve_bwd(C, Xnext, V, u, wb, *, impl: str = "scan",
                     interpret: bool = False):
    _check_impl(impl)
    if impl == "pallas":
        return kernel.banded_solve_bwd_pallas(C, Xnext, V, u, wb,
                                              interpret=interpret)
    return ref.banded_solve_bwd(C, Xnext, V, u, wb)


# ---------------------------------------------------------------------------
# One-shot factor/solve including the dense border (tests, standalone use)
# ---------------------------------------------------------------------------

def factor(Dblk, Opad, Ublk, Db, *, impl: str = "scan",
           interpret: bool = False):
    """Full factorization ``(C, X, V, Cb)`` of the arrowhead system.

    ``C`` (scan) and ``Cb`` hold inverse factors where the blocks are
    small fp64 (:func:`.ref.small_blocks`); :func:`solve` reads them so.
    """
    C, X, V, S = banded_factor(Dblk, Opad, Ublk, impl=impl,
                               interpret=interpret)
    return C, X, V, ref.corner_factor(Db, S)


def solve(C, X, V, Cb, rband, rb, *, impl: str = "scan",
          interpret: bool = False):
    """Solve from a :func:`factor` result -> ``(wband (K, s), wb (p,))``."""
    u = banded_solve_fwd(C, X, rband, impl=impl, interpret=interpret)
    if ref.small_blocks(V.dtype, V.shape[-1]):
        t = rb - (V * u[:, None, :]).sum(axis=(0, 2))
    else:
        t = rb - jnp.einsum("kps,ks->p", V, u)
    wb = ref.corner_solve(Cb, t)
    Xnext = jnp.concatenate(
        [X[1:], jnp.zeros((1,) + X.shape[1:], X.dtype)], axis=0)
    wband = banded_solve_bwd(C, Xnext, V, u, wb, impl=impl,
                             interpret=interpret)
    return wband, wb
