"""ShardedExecutor — lane batches partitioned across devices.

Lanes of a padded family are embarrassingly parallel, so the batch axis
shards cleanly: ``shard_map`` over a 1-D ``"lanes"`` mesh gives every
device its own slice of the chunk and — unlike letting GSPMD partition
the ``jit(vmap)`` — its own *program*, so each shard's IPM while_loop
exits when ITS lanes are decided instead of synchronizing the whole
chunk on the globally slowest lane.  Status flags, iteration counts and
solution vectors come back gathered along the lane axis, so everything
above the executor (verification, oracle fallback, warm seeding,
adaptive budgets) is oblivious to the sharding.

Results are bit-identical to :class:`~.local.LocalExecutor`: every
device runs the same :func:`~.base.microbatched` program over its lane
slice, so per-lane compiled arithmetic is placement-invariant (see the
:mod:`.base` module docstring).

Chunks are padded on the shared micro-batch ladder (never further), and
the mesh width adapts per compiled shape: a chunk of ``G`` micro-batches
spans the largest device count that divides ``G`` — tiny chunks simply
use fewer devices instead of padding 8x, and a 3-lane bucket runs on
one device exactly like the local path.

The lane program lowers through XLA's GSPMD partitioner rather than
Shardy, JAX's default: the TPU compiler refuses Shardy's export of a
manual computation in which an fp64 operand (an emulated f32 pair on
the TPU) reaches a Cholesky factorization ("A tuple parameter that is
being flattened shouldn't have frontend attributes"), and every IPM
kernel factors fp64 normal equations.  The switch is JAX's own
thread-local config state, scoped to this executor's compiles.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import shard_map
from jax._src.config import use_shardy_partitioner
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .base import Executor, LANE_MICROBATCH, microbatched, named

__all__ = ["ShardedExecutor"]


class ShardedExecutor(Executor):
    """``shard_map`` over a 1-D lane mesh spanning the visible devices."""

    name = "sharded"
    AXIS = "lanes"

    def __init__(self, devices: Optional[int] = None):
        visible = jax.devices()
        if devices is None:
            self._devices = list(visible)
        else:
            if devices < 1:
                raise ValueError(f"devices must be >= 1, got {devices}")
            if devices > len(visible):
                raise ValueError(
                    f"devices={devices} but only {len(visible)} JAX "
                    f"device(s) are visible ({jax.default_backend()} "
                    "backend) — on CPU, XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N adds "
                    "virtual host devices")
            self._devices = list(visible[:devices])

    def device_count(self) -> int:
        return len(self._devices)

    def cache_token(self) -> Tuple:
        return (self.name, len(self._devices), LANE_MICROBATCH)

    def _mesh_width(self, n_lanes: int) -> int:
        """Devices used for a padded chunk: the largest count that splits
        its micro-batches evenly (shard_map needs equal shards; chunks
        smaller than one micro-batch per device just use fewer devices)."""
        groups = n_lanes // LANE_MICROBATCH
        for d in range(min(len(self._devices), groups), 1, -1):
            if groups % d == 0:
                return d
        return 1

    def _mapped(self, fn: Callable, in_axes: Tuple[Optional[int], ...],
                n_lanes: int):
        """``(shard_mapped fn, in_shardings, out_sharding)`` for a chunk."""
        d_eff = self._mesh_width(n_lanes)
        mesh = Mesh(np.array(self._devices[:d_eff]), (self.AXIS,))
        specs = tuple(P(self.AXIS) if ax == 0 else P() for ax in in_axes)
        mapped = shard_map(
            microbatched(fn, in_axes),
            mesh=mesh,
            in_specs=specs,
            out_specs=P(self.AXIS),
            check_vma=False,
        )
        shardings = tuple(NamedSharding(mesh, s) for s in specs)
        return mapped, shardings, NamedSharding(mesh, P(self.AXIS))

    def wrap(self, fn: Callable, in_axes: Tuple[Optional[int], ...],
             args: Sequence[jax.ShapeDtypeStruct]) -> Callable:
        return self._mapped(fn, in_axes, args[0].shape[0])[0]

    def compile(self, fn: Callable, in_axes: Tuple[Optional[int], ...],
                args: Sequence[jax.ShapeDtypeStruct],
                name: Optional[str] = None) -> Callable:
        mapped, shardings, out_sharding = self._mapped(
            fn, in_axes, args[0].shape[0])
        with use_shardy_partitioner(False):
            exe = (jax.jit(named(mapped, name), in_shardings=shardings,
                           out_shardings=out_sharding)
                   .lower(*args).compile())

        def call(*arrays):
            # commit each operand to its lane sharding up front: batch
            # axes split across the mesh, shared operands replicated —
            # without this the executable would first gather everything
            # onto one device
            placed = [jax.device_put(a, sh)
                      for a, sh in zip(arrays, shardings)]
            return exe(*placed)

        return call
