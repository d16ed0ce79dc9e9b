"""The banded factor's elementwise path for small fp64 blocks.

For fp64 blocks of width ``s <= SMALL_BLOCK_MAX`` the factor and
substitution scans run without a library Cholesky, triangular solve or
dot: the pivot Cholesky and its inverse are unrolled over the columns,
and the scans keep ``C_k^{-1}``.  These tests hold that path to the
library's answers at machine precision, block by block, and on whole
block-tridiagonal-arrowhead systems against a dense solve.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dlt.precision import x64_scope
from repro.kernels.dlt_banded_chol import ops, ref

#: (K, s, p) of the banded normal equations: planning without a front end
#: (s = 3N - 2 at N = 3, 4, 5), routing with one (s = N = 4, 4 x 16 and
#: 4 x 128 fleets), a wider border, and one block above the cutoff
ARROWHEADS = [(20, 7, 1), (20, 10, 1), (8, 13, 1), (16, 4, 1), (128, 4, 1),
              (3, 5, 3), (1, 1, 1), (2, ref.SMALL_BLOCK_MAX + 1, 2)]


def _spd(rng, s, lanes):
    raw = rng.normal(size=(lanes, s, s + 3))
    return raw @ np.swapaxes(raw, 1, 2) + 0.1 * np.eye(s)


@pytest.mark.parametrize("s", range(1, ref.SMALL_BLOCK_MAX + 1))
def test_small_cholesky_inverse_and_solves_match_library(s):
    rng = np.random.default_rng(s)
    with x64_scope():
        A = jnp.asarray(_spd(rng, s, 4))
        b = jnp.asarray(rng.normal(size=(4, s)))
        L, W = jax.jit(jax.vmap(ref.chol_inv_small))(A)
        L_lib = jnp.linalg.cholesky(A)
        eye = jnp.broadcast_to(jnp.eye(s), A.shape)
        W_lib = jax.scipy.linalg.solve_triangular(L_lib, eye, lower=True)
        fwd = jax.scipy.linalg.solve_triangular(L_lib, b[..., None],
                                                lower=True)[..., 0]
        bwd = jax.scipy.linalg.solve_triangular(
            jnp.swapaxes(L_lib, 1, 2), b[..., None], lower=False)[..., 0]
        L, W, L_lib, W_lib = map(np.asarray, (L, W, L_lib, W_lib))
        scale = np.abs(L_lib).max()
        np.testing.assert_allclose(L, L_lib, rtol=0, atol=1e-14 * scale)
        np.testing.assert_array_equal(np.triu(L, 1), 0.0)
        np.testing.assert_array_equal(np.triu(W, 1), 0.0)
        inv_scale = np.abs(W_lib).max()
        np.testing.assert_allclose(W, W_lib, rtol=0, atol=1e-13 * inv_scale)
        # a substitution step is one product with the inverse factor
        np.testing.assert_allclose(ref._av(W, b), fwd, rtol=0,
                                   atol=1e-13 * np.abs(fwd).max())
        np.testing.assert_allclose(ref._atv(W, b), bwd, rtol=0,
                                   atol=1e-13 * np.abs(bwd).max())


def test_small_cholesky_of_an_indefinite_block_is_nan():
    """A failed pivot must show as non-finite, as the library's does,
    so the IPM refuses the step."""
    with x64_scope():
        L, W = ref.chol_inv_small(jnp.asarray([[1.0, 2.0], [2.0, 1.0]]))
        assert not np.all(np.isfinite(np.asarray(L)))
        assert not np.all(np.isfinite(np.asarray(W)))


def test_small_blocks_rule():
    assert ref.small_blocks(jnp.float64, 1)
    assert ref.small_blocks(np.float64, ref.SMALL_BLOCK_MAX)
    assert not ref.small_blocks(jnp.float64, ref.SMALL_BLOCK_MAX + 1)
    assert not ref.small_blocks(jnp.float32, 4)      # the fp32 scans stay


def _arrowhead(rng, K, s, p):
    """A random SPD block-tridiagonal-arrowhead system in block layout."""
    n = K * s + p
    raw = rng.normal(size=(n, n + 4))
    M = raw @ raw.T
    blk = np.concatenate([np.repeat(np.arange(K), s), np.full(p, K)])
    far = ((np.abs(blk[:, None] - blk[None, :]) > 1)
           & (blk[:, None] < K) & (blk[None, :] < K))
    M[far] = 0.0
    M += n * np.eye(n)                     # SPD again after zeroing
    band = lambda k, j: M[k*s:(k+1)*s, j*s:(j+1)*s]
    Dblk = np.stack([band(k, k) for k in range(K)])
    Opad = np.stack([np.zeros((s, s))] + [band(k, k - 1)
                                          for k in range(1, K)])
    Ublk = np.stack([M[K*s:, k*s:(k+1)*s] for k in range(K)])
    rhs = rng.normal(size=n)
    return M, (Dblk, Opad, Ublk, M[K*s:, K*s:]), rhs


@pytest.mark.parametrize("K,s,p", ARROWHEADS)
def test_factor_and_solve_match_dense_solve(K, s, p):
    rng = np.random.default_rng(K * 1000 + s * 10 + p)
    M, blocks, rhs = _arrowhead(rng, K, s, p)
    with x64_scope():
        C, X, V, Cb = ops.factor(*map(jnp.asarray, blocks))
        wband, wb = ops.solve(C, X, V, Cb,
                              jnp.asarray(rhs[:K*s].reshape(K, s)),
                              jnp.asarray(rhs[K*s:]))
        small = ref.small_blocks(C.dtype, s)
        C = np.asarray(C)
        # the scans keep C_k^{-1} on the elementwise path, C_k otherwise
        pivots = np.linalg.inv(C) if small else C
        np.testing.assert_allclose(pivots @ np.swapaxes(pivots, 1, 2),
                                   blocks[0] - np.asarray(X) @ np.swapaxes(
                                       np.asarray(X), 1, 2),
                                   rtol=0, atol=1e-12 * np.abs(M).max())
    w = np.concatenate([np.asarray(wband).ravel(), np.asarray(wb)])
    want = np.linalg.solve(M, rhs)
    assert np.linalg.norm(w - want) <= 1e-13 * np.linalg.norm(want)
