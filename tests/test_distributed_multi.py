"""Multi-device behaviours (subprocess with forced host device count):
grad-compression psum, pipeline parallelism, HLO collective parsing."""

import subprocess
import sys
import textwrap
from pathlib import Path


ROOT = Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 8) -> str:
    prog = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
import sys
sys.path.insert(0, {str(ROOT / 'src')!r})
{textwrap.dedent(code)}
"""
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_compressed_psum_matches_plain():
    print(_run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.distributed.grad_compression import compressed_psum
mesh = jax.make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

def f(x, k):
    return compressed_psum(x, "data", k)

y = shard_map(f, mesh=mesh, in_specs=(P("data"), P()),
              out_specs=P("data"))(x, jax.random.PRNGKey(1))
want = jnp.broadcast_to(x.sum(0, keepdims=True), x.shape)
err = float(jnp.max(jnp.abs(y - want)))
scale = float(jnp.max(jnp.abs(x))) / 127
assert err <= 8 * scale, (err, scale)
print("OK compressed_psum err", err)
"""))


def test_pipeline_parallel_matches_sequential():
    print(_run("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline_parallel import pipeline_apply, gpipe_utilization
mesh = jax.make_mesh((4,), ("stage",))
P_stages, M, mb, D = 4, 8, 2, 16
ks = jax.random.split(jax.random.PRNGKey(0), P_stages)
params = jnp.stack([jax.random.normal(k, (D, D)) * 0.1 for k in ks])

def fn(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))
y = pipeline_apply(fn, params, x, mesh, axis="stage")
ref = x
for s in range(P_stages):
    ref = jnp.tanh(ref @ params[s])
err = float(jnp.max(jnp.abs(y - ref)))
assert err < 1e-5, err
assert abs(gpipe_utilization(8, 4) - 8/11) < 1e-9
print("OK pipeline err", err)
"""))


def test_hlo_parser_counts_collectives_and_trips():
    print(_run("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import sys
from repro.analysis.hlo_parse import analyze_hlo

mesh = jax.make_mesh((8,), ("model",))
w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
x = jax.ShapeDtypeStruct((4, 128), jnp.float32)

def step(x, w):
    # row-parallel matmul -> psum; scan body runs 5 times
    def body(c, _):
        y = c @ w                 # contraction over the sharded dim
        return y, ()
    out, _ = jax.lax.scan(body, x, None, length=5)
    return out

sh_w = NamedSharding(mesh, P("model", None))
sh_x = NamedSharding(mesh, P(None, None))
with mesh:
    compiled = jax.jit(step, in_shardings=(sh_x, sh_w)).lower(x, w).compile()
stats = analyze_hlo(compiled.as_text())
# PER-DEVICE flops: 5 iterations x 4x(128/8)x128 matmul shards
want_flops = 5 * 2 * 4 * (128 // 8) * 128
assert 0.9 * want_flops <= stats.flops <= 1.5 * want_flops, \\
    (stats.flops, want_flops)
assert sum(stats.collective_bytes.values()) > 0, stats.collective_bytes
assert 5 in stats.while_trips.values(), stats.while_trips
print("OK parser", stats.flops, stats.collective_bytes, stats.while_trips)
"""))


def test_unrolled_vs_scan_flop_parity():
    """The parser's trip-count correction: a 4-layer scanned model reports
    the same FLOPs as the unrolled equivalent (within 5%)."""
    print(_run("""
import jax, jax.numpy as jnp
from repro.analysis.hlo_parse import analyze_hlo
D, L = 64, 4
w = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
x = jax.ShapeDtypeStruct((8, D), jnp.float32)

def scanned(x, w):
    def body(c, wl):
        return jnp.tanh(c @ wl), ()
    out, _ = jax.lax.scan(body, x, w)
    return out

def unrolled(x, w):
    for i in range(L):
        x = jnp.tanh(x @ w[i])
    return x

fs = analyze_hlo(jax.jit(scanned).lower(x, w).compile().as_text()).flops
fu = analyze_hlo(jax.jit(unrolled).lower(x, w).compile().as_text()).flops
assert abs(fs - fu) / fu < 0.05, (fs, fu)
print("OK parity", fs, fu)
""", devices=1))
