"""Ahead-of-time compiles, for a TPU v5e, of the programs the chip runs.

JAX ships the TPU compiler, and it compiles for a chip that is described
rather than attached.  These tests hand it the engine's own programs at
the sizes ``chip_smoke.py`` drives, so what the v5e compiler refuses
(64-bit types in a kernel, blocks off the (8, 128) tiling, more VMEM
than a kernel may use, a program that cannot be partitioned) fails
here, with no chip:

* the banded interior-point executable of one routing window (4
  frontends x 128 replicas, one 16-lane micro-batch) under both
  precision policies, with the kernel ``kernel="auto"`` picks on a TPU;
* the planning benchmark's fp64 banded executable (3 x 20, no front
  end), whose factor and substitution scans must hold no loop: the
  chip has no f64 unit, and a library f64 op inside a scan step
  compiles to loops of its own;
* each Pallas kernel that ``auto`` can route to, with f32 operands at
  the block shapes of the router and planning families;
* the sharded executor's program over a four-chip lane mesh.

Nothing runs, so nothing here says anything about results or times.
The topology is described inside a fixture (never at import time: only
one process may load the TPU library), and the persistent compile cache
is off around the compiles, since a TPU executable written to it could
not be read back without a chip.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.analysis.hlo_parse import while_loops
from repro.core.dlt import DLTEngine, SystemSpec
from repro.core.dlt.batched import build_family_lp
from repro.core.dlt.executors import LANE_MICROBATCH
from repro.core.dlt.executors.local import LocalExecutor
from repro.core.dlt.executors.sharded import ShardedExecutor
from repro.core.dlt.precision import x64_scope
from repro.core.dlt.stacking import BatchedSystemSpec
from repro.kernels.dlt_banded_chol import kernel as pallas_kernel
from repro.serve.engine import RouterStats, _burst_specs


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Kernel routing as it resolves on a TPU backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _router_family():
    """One routing window: 16 burst lanes over 4 frontends x 128 replicas."""
    rng = np.random.default_rng(0)
    stats = RouterStats(
        frontend_seconds_per_request=rng.uniform(0.0005, 0.002, 4),
        frontend_release=np.zeros(4),
        replica_seconds_per_request=rng.uniform(0.02, 0.2, 128))
    specs, _ = _burst_specs(stats, list(range(1, LANE_MICROBATCH + 1)))
    return BatchedSystemSpec.from_specs(specs, presorted=True), True


def _planning_family(n=5, m=32, lanes=4):
    """The largest bucket of the Sec 3.2 planning family (N=5, M=32)."""
    rng = np.random.default_rng(1)
    specs = [SystemSpec(G=rng.uniform(0.1, 1.0, n),
                        R=np.sort(rng.uniform(0.0, 2.0, n)),
                        A=rng.uniform(0.5, 4.0, m), J=100.0)
             for _ in range(lanes)]
    return BatchedSystemSpec.from_specs(specs), False


def _plan(eng, family):
    bs, frontend = family
    fm = eng._formulation(frontend, None)
    return eng._kernel_plan(fm, bs, build_family_lp(bs, fm))


def _compile(executor, plan, eng, batch, sharding_of):
    fn, in_axes, args = eng._kernel_signature(plan, batch, False,
                                              eng.config.max_iter)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding_of(a))
            for a in args]
    with x64_scope():
        return jax.jit(executor.wrap(fn, in_axes, args)).lower(*args).compile()


@pytest.mark.parametrize("precision,kind", [("fp64", "banded"),
                                            ("mixed", "pallas_banded")])
def test_router_window_ipm_compiles(topo, one_chip, no_persistent_cache,
                                    on_tpu, precision, kind):
    eng = DLTEngine(precision=precision)
    plan = _plan(eng, _router_family())
    assert plan.kind == kind
    compiled = _compile(LocalExecutor(), plan, eng, LANE_MICROBATCH,
                        lambda a: one_chip)
    assert ("tpu_custom_call" in compiled.as_text()) == (kind != "banded")
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_planning_ipm_scans_hold_no_loops(topo, one_chip, no_persistent_cache,
                                          on_tpu):
    """The fp64 banded factor and substitution scans of the 3 x 20
    planning program (s = 7 blocks) are straight-line code.

    With the library Cholesky, triangular solves and f64 dots in their
    steps the program held 313 ``while`` loops (63,490 optimized
    instructions); with the elementwise small-block steps it holds 148
    (43,973 instructions), the rest being the f64 dots of the IPM body.
    """
    eng = DLTEngine(precision="fp64")
    plan = _plan(eng, _planning_family(n=3, m=20, lanes=LANE_MICROBATCH))
    assert plan.kind == "banded" and plan.bfam.geom.s == 7
    compiled = _compile(LocalExecutor(), plan, eng, LANE_MICROBATCH,
                        lambda a: one_chip)
    loops = while_loops(compiled.as_text())
    scans = [w for w in loops
             if w.op_name.endswith(("ipm.factor/while", "ipm.solve/while"))]
    assert len(scans) == 7      # one factor, three forward, three backward
    assert [w.nested for w in scans] == [0] * 7
    assert len(loops) < 313 // 2


def _geometry(family):
    g = _plan(DLTEngine(kernel="banded"), family).bfam.geom
    return g.K, g.s, g.p


@pytest.mark.parametrize("family", ["router", "planning"])
@pytest.mark.parametrize("which", ["factor", "fwd", "bwd"])
def test_pallas_kernel_compiles(topo, one_chip, no_persistent_cache,
                                family, which):
    K, s, p = _geometry(_router_family() if family == "router"
                        else _planning_family())
    B = LANE_MICROBATCH
    calls = {
        "factor": (pallas_kernel.banded_factor_pallas,
                   [(B, K, s, s), (B, K, s, s), (B, K, p, s)]),
        "fwd": (pallas_kernel.banded_solve_fwd_pallas,
                [(B, K, s, s), (B, K, s, s), (B, K, s)]),
        "bwd": (pallas_kernel.banded_solve_bwd_pallas,
                [(B, K, s, s), (B, K, s, s), (B, K, p, s), (B, K, s),
                 (B, p)]),
    }
    fn, shapes = calls[which]
    args = [jax.ShapeDtypeStruct(sh, jnp.float32, sharding=one_chip)
            for sh in shapes]
    with x64_scope():   # the engine traces its kernels in this scope
        compiled = jax.jit(jax.vmap(fn)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_executor_compiles_on_four_chips(topo, no_persistent_cache):
    eng = DLTEngine(precision="fp64", executor="sharded")
    plan = _plan(eng, _planning_family(n=2, m=8))
    executor = ShardedExecutor()
    executor._devices = list(topo.devices)      # the described 2x2 mesh
    assert len(executor._devices) == 4
    batch = 4 * LANE_MICROBATCH                 # one micro-batch per chip
    fn, in_axes, args = eng._kernel_signature(plan, batch, False,
                                              eng.config.max_iter)
    _, shardings, _ = executor._mapped(fn, in_axes, batch)
    assert all(isinstance(sh, NamedSharding) and sh.mesh.size == 4
               for sh in shardings)
    with x64_scope():
        executor.compile(fn, in_axes, args)   # what the engine calls
