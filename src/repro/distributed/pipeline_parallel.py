"""Pipeline parallelism demo: GPipe-style microbatching over a stage axis.

Not used by the 40 baseline cells (DP x TP covers them), but included as the
PP building block for >2-pod scale, where a "stage" axis amortizes weight
memory across pods.  Implementation: ``shard_map`` over a 1-D "stage" mesh
axis; each stage holds its own layer stack; activations hop stage->stage
with ``jax.lax.ppermute``.  The schedule is the classic GPipe fill-drain:
with M microbatches and P stages, utilization is M / (M + P - 1).

``pipeline_apply`` is deliberately model-agnostic: it takes a per-stage
apply function f(stage_params, x) -> x.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "gpipe_utilization"]


def gpipe_utilization(num_microbatches: int, num_stages: int) -> float:
    return num_microbatches / (num_microbatches + num_stages - 1)


def pipeline_apply(
    fn: Callable,
    stage_params,          # pytree with leading stage axis on every leaf
    x,                     # (M, mb, ...) microbatched input
    mesh: Mesh,
    axis: str = "stage",
):
    """Run ``fn`` as a P-stage pipeline over M microbatches.

    fn(params_slice, x_mb) -> y_mb must be shape-preserving (same mb shape
    in and out), e.g. a transformer block stack.
    Returns (M, mb, ...) outputs equal to the sequential composition
    fn(p[P-1], ... fn(p[0], x_mb)).
    """
    num_stages = mesh.shape[axis]
    M = x.shape[0]
    if M < num_stages:
        raise ValueError(f"need >= {num_stages} microbatches, got {M}")

    def stage_fn(params, xs):
        # params: this stage's slice (leading axis stripped by shard_map)
        # xs: (M, mb, ...) microbatches, replicated across stages
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        T = M + num_stages - 1          # fill-drain ticks
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            buf, outs = carry           # buf: (mb...) activation entering us
            # stage 0 injects microbatch t (when in range); others use buf
            inject = jnp.where(t < M, t, M - 1)
            x_in = jnp.where(stage == 0, xs[inject], buf)
            y = fn(params, x_in)
            # pass down the pipe: stage s -> s+1 (last stage's output exits)
            nxt = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(num_stages - 1)])
            # the LAST stage writes its result for microbatch (t - P + 1)
            out_idx = t - (num_stages - 1)
            valid = (out_idx >= 0) & (out_idx < M)
            idx = jnp.clip(out_idx, 0, M - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, idx, axis=0),
                lambda o: o,
                outs,
            )
            return (nxt, outs), None

        buf0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros((M,) + mb_shape, xs.dtype)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(T, dtype=jnp.int32))
        # only the last stage holds the real outputs; broadcast via a
        # masked psum (ppermute can't fan out one source to all).
        outs = jnp.where(stage == num_stages - 1, outs, 0.0)
        return jax.lax.psum(outs, axis)

    return shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)
