"""Pallas ``dlt_banded_chol`` kernel: parity vs the scan ref, routing.

The Pallas port must reproduce the pure-JAX scan reference
(``repro.kernels.dlt_banded_chol.ref``) to well below the solver's
1e-6 certification tolerance.  CI runs these in interpret mode (the
kernel body executes as plain jnp ops), which is exactly what
``EngineConfig.pallas_interpret`` enables on CPU; routing tests cover
the ``kernel="pallas_banded"`` tier — ``auto`` takes it on the TPU for
the mixed policy's fp32 factor only, and pinning it where it cannot
run (off the TPU without interpret, under fp64, interpret on the TPU)
raises.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dlt import DLTEngine, EngineConfig, SystemSpec
from repro.core.dlt.batched import build_family_lp
from repro.core.dlt.stacking import BatchedSystemSpec
from repro.core.dlt.precision import x64_scope
from repro.kernels.dlt_banded_chol import ops, ref

REL_TOL = 1e-6
SHAPES = [(1, 1, 1), (3, 2, 1), (5, 6, 1), (4, 9, 3), (1, 4, 2)]


def _random_arrowhead(rng, K, s, p):
    """A random SPD block-tridiagonal-arrowhead system + rhs."""
    n = K * s + p
    raw = rng.normal(size=(n, n + 4))
    M = raw @ raw.T + n * np.eye(n)
    blk = np.concatenate([np.repeat(np.arange(K), s), np.full(p, K)])
    far = ((np.abs(blk[:, None] - blk[None, :]) > 1)
           & (blk[:, None] < K) & (blk[None, :] < K))
    M[far] = 0.0
    M += n * np.eye(n)                     # keep it SPD after zeroing
    Dblk = np.stack([M[k*s:(k+1)*s, k*s:(k+1)*s] for k in range(K)])
    Opad = np.stack([np.zeros((s, s))]
                    + [M[k*s:(k+1)*s, (k-1)*s:k*s] for k in range(1, K)])
    Ublk = np.stack([M[K*s:, k*s:(k+1)*s] for k in range(K)])
    Db = M[K*s:, K*s:]
    rhs = rng.normal(size=n)
    return M, Dblk, Opad, Ublk, Db, rhs[:K*s].reshape(K, s), rhs[K*s:], rhs


def _as_factor(C):
    """Pivot factors ``C_k`` from the scan ref's ``C``, which holds
    ``C_k^{-1}`` where its blocks are small fp64."""
    C = np.asarray(C)
    return np.linalg.inv(C) if ref.small_blocks(C.dtype, C.shape[-1]) else C


@pytest.mark.parametrize("K,s,p", SHAPES)
def test_pallas_factor_solve_parity(K, s, p):
    """Interpret-mode Pallas == scan ref == direct dense solve."""
    rng = np.random.default_rng(K * 100 + s * 10 + p)
    with x64_scope():
        M, Dblk, Opad, Ublk, Db, rband, rb, rhs = _random_arrowhead(
            rng, K, s, p)
        j = lambda a: jnp.asarray(a, jnp.float64)
        Cr, Xr, Vr, Cbr = ref.factor(j(Dblk), j(Opad), j(Ublk), j(Db))
        wr, wbr = ref.solve(Cr, Xr, Vr, Cbr, j(rband), j(rb))
        Cp, Xp, Vp, Cbp = ops.factor(j(Dblk), j(Opad), j(Ublk), j(Db),
                                     impl="pallas", interpret=True)
        wp, wbp = ops.solve(Cp, Xp, Vp, Cbp, j(rband), j(rb),
                            impl="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(Cp), _as_factor(Cr),
                                   atol=1e-10)
        np.testing.assert_allclose(np.asarray(Vp), np.asarray(Vr),
                                   atol=1e-10)
        np.testing.assert_allclose(np.asarray(wp), np.asarray(wr),
                                   atol=1e-9)
        np.testing.assert_allclose(np.asarray(wbp), np.asarray(wbr),
                                   atol=1e-9)
        w = np.concatenate([np.asarray(wp).ravel(), np.asarray(wbp)])
        np.testing.assert_allclose(w, np.linalg.solve(M, rhs), atol=1e-8)


def test_pallas_parity_under_vmap():
    """vmap prepends the batch grid axis; scratch carries stay per-lane."""
    rng = np.random.default_rng(0)
    with x64_scope():
        lanes = [_random_arrowhead(rng, 4, 3, 1) for _ in range(5)]
        Dv = jnp.asarray(np.stack([l[1] for l in lanes]), jnp.float64)
        Ov = jnp.asarray(np.stack([l[2] for l in lanes]), jnp.float64)
        Uv = jnp.asarray(np.stack([l[3] for l in lanes]), jnp.float64)
        fp = jax.jit(jax.vmap(
            lambda d, o, u: ops.banded_factor(
                d, o, u, impl="pallas", interpret=True)))
        fs = jax.jit(jax.vmap(ref.banded_factor))
        (Cp, *rest_p), (Cs, *rest_s) = fp(Dv, Ov, Uv), fs(Dv, Ov, Uv)
        for got, want in zip([Cp] + rest_p, [_as_factor(Cs)] + rest_s):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-10)


def test_ops_impl_validation():
    z = jnp.zeros((1, 1, 1))
    with pytest.raises(ValueError, match="unknown impl"):
        ops.banded_factor(z, z, z, impl="cuda")


def test_pallas_supported_matrix():
    assert ops.pallas_supported(backend="tpu")
    assert not ops.pallas_supported(backend="cpu")
    assert not ops.pallas_supported(backend="gpu")
    assert ops.pallas_supported(backend="cpu", interpret=True)
    # interpret mode is the CPU parity tool; it never runs on the chip
    assert not ops.pallas_supported(backend="tpu", interpret=True)


# ---------------------------------------------------------------------------
# engine tier: routing, parity, fallback recording
# ---------------------------------------------------------------------------

def _specs(seed, count, n, m):
    rng = np.random.default_rng(seed)
    return [
        SystemSpec(G=rng.uniform(0.1, 1.0, n),
                   R=np.sort(rng.uniform(0.0, 2.0, n)),
                   A=rng.uniform(0.5, 4.0, m),
                   J=float(rng.uniform(50.0, 200.0)))
        for _ in range(count)
    ]


def test_engine_pallas_tier_matches_structured():
    specs = _specs(1, 4, 2, 6)
    pal = DLTEngine(kernel="pallas_banded", pallas_interpret=True,
                    precision="mixed", verify=False, oracle_fallback=False)
    st = DLTEngine(kernel="structured", verify=False, oracle_fallback=False)
    a = pal.solve_batch(specs, frontend=False)
    b = st.solve_batch(specs, frontend=False)
    assert np.array_equal(a.status, b.status)
    ok = a.status == 0
    assert ok.sum() >= 3
    np.testing.assert_allclose(a.finish_time[ok], b.finish_time[ok],
                               rtol=REL_TOL, atol=1e-8)
    assert pal.stats.pallas_lanes == len(specs)
    assert pal.stats.banded_lanes == 0


def _auto_plan(**config):
    """The kernel plan ``auto`` resolves for a banded-capable family."""
    eng = DLTEngine(**config)
    bs = BatchedSystemSpec.from_specs(_specs(2, 3, 2, 16))  # ~50 rows
    fm = eng._formulation(False, None)
    return eng._kernel_plan(fm, bs, build_family_lp(bs, fm))


def test_auto_upgrades_to_pallas_on_supported_backend(monkeypatch):
    """On the TPU, auto gives the Pallas tier the one program the chip's
    kernel compiler accepts: the mixed policy's fp32 factor.  fp64
    factors stay on the XLA scans, and interpret mode is never routed to
    (tests/test_tpu_compile.py compiles the routed program for a v5e)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _auto_plan(precision="mixed").kind == "pallas_banded"
    assert _auto_plan(precision="fp64").kind == "banded"
    assert _auto_plan(precision="mixed",
                      pallas_interpret=True).kind == "banded"


def test_interpret_opt_in_never_changes_auto_routing():
    """pallas_interpret is a parity knob for PINNED pallas_banded — on
    CPU, auto keeps the fast scan kernels even with it set."""
    eng = DLTEngine(pallas_interpret=True, precision="mixed", verify=False,
                    oracle_fallback=False)
    specs = _specs(2, 3, 2, 16)
    eng.solve_batch(specs, frontend=False)
    assert eng.stats.pallas_lanes == 0
    assert eng.stats.banded_lanes == len(specs)
    assert eng.stats.kernel_fallbacks == 0


def test_pinned_pallas_raises_on_unsupported_backend(monkeypatch):
    specs = _specs(3, 2, 2, 6)
    eng = DLTEngine(kernel="pallas_banded", precision="mixed")  # CPU
    with pytest.raises(ValueError, match="not supported"):
        eng.solve_batch(specs, frontend=False)
    # the kernel factors fp32 blocks only: the fp64 policy cannot pin it
    with pytest.raises(ValueError, match="precision='mixed'"):
        eng.configured(precision="fp64").solve_batch(specs, frontend=False)
    # interpret mode never stands in for the compiled kernel on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="'tpu'"):
        eng.configured(pallas_interpret=True).solve_batch(
            specs, frontend=False)


def test_auto_falls_back_and_records_on_candidate_backend(monkeypatch):
    """A backend without the Pallas lowering (GPU here) runs auto's
    banded families on the XLA scans: the Pallas tier is a TPU-only
    route, not a downgrade, so no kernel fallback is recorded."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    eng = DLTEngine(verify=False, oracle_fallback=False,
                    banded_min_rows=32, precision="mixed")
    specs = _specs(4, 3, 2, 16)
    sol = eng.solve_batch(specs, frontend=False)
    assert eng.stats.kernel_fallbacks == 0
    assert eng.stats.banded_lanes == len(specs)
    assert eng.stats.pallas_lanes == 0
    ref_sol = DLTEngine(kernel="banded", precision="mixed", verify=False,
                        oracle_fallback=False).solve_batch(
                            specs, frontend=False)
    ok = (sol.status == 0) & (ref_sol.status == 0)
    np.testing.assert_allclose(sol.finish_time[ok], ref_sol.finish_time[ok],
                               rtol=REL_TOL)
    with pytest.raises(ValueError, match="'gpu'"):
        eng.configured(kernel="pallas_banded").solve_batch(
            specs, frontend=False)


def test_config_accepts_pallas_knobs():
    cfg = EngineConfig(kernel="pallas_banded", pallas_interpret=True)
    assert cfg.replace(kernel="auto").pallas_interpret
    with pytest.raises(ValueError, match="pallas_banded"):
        EngineConfig(kernel="pallas")
