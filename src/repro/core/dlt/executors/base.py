"""Executor protocol — how a compiled lane batch actually runs.

The batched engine separates *what* to compute (the kernel plan: which
IPM instantiation over which padded family) from *where* it runs.  An
:class:`Executor` owns the second half:

* ``pad_batch``   — the lane count a chunk is padded to before compile
  (executors pick shapes that bound the compiled-shape space AND divide
  evenly over their devices);
* ``compile``     — turn a per-lane kernel function into an
  ahead-of-time compiled callable over stacked arrays (the engine LRUs
  the result, keyed by the executor's ``cache_token``);
* ``device_count`` / ``cache_token`` — introspection for stats, bench
  topology stamps and the compile-cache key.

Two implementations ship: :class:`~.local.LocalExecutor` (the default
device — the classic path) and :class:`~.sharded.ShardedExecutor`
(``shard_map`` over a 1-D lane mesh spanning the visible devices).

**Placement invariance.**  Lanes are embarrassingly parallel, so an
executor must never change results — only placement.  XLA, however,
compiles per-lane arithmetic differently at different vmap widths
(reduction groupings shift with the batch shape), so a naive
``vmap(B)`` vs ``vmap(B / n_devices)`` split drifts in the last float
bits.  Executors therefore run lanes through :func:`microbatched`: a
``lax.map`` over fixed-width ``vmap(LANE_MICROBATCH)`` groups.  The
per-lane compiled code is then identical no matter how many devices the
batch spans — sharded results are **bit-identical** to local ones — and
as a bonus each micro-batch's IPM while_loop exits on its own, so a
straggler lane gates only its micro-batch instead of the whole chunk.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax

__all__ = [
    "LANE_MICROBATCH",
    "Executor",
    "available_executors",
    "microbatched",
    "resolve_executor",
]

#: Fixed lane width of one compiled micro-batch; every executor pads
#: chunks to a multiple of this.  Measured on the mixed + uniform bench
#: families (2-core CPU): 16 recovers the monolithic-vmap throughput on
#: small uniform LPs (8 loses ~30% to per-group overhead) while keeping
#: the while_loop exit granularity fine enough that one straggler lane
#: gates 15 neighbors, not the whole chunk (32 halves mixed-family
#: throughput for exactly that reason).
LANE_MICROBATCH = 16


def microbatched(fn: Callable, in_axes: Tuple,
                 micro: int = LANE_MICROBATCH) -> Callable:
    """``fn`` vmapped at fixed width ``micro``, looped over the batch.

    ``in_axes`` follows :func:`jax.vmap` (0 = stacked on the lane axis,
    ``None`` = shared).  The returned function takes the full stacked
    arrays (lane count divisible by ``micro``, or smaller than it) and
    runs them as a ``lax.map`` over ``vmap(micro)`` groups — the unit
    every executor compiles, making results independent of device
    placement.  A chunk below one micro-batch runs as a single narrower
    vmap: its padded width is part of the compiled shape, so it too is
    identical no matter which executor (or device) runs it, and tiny
    buckets never pay for ``micro`` lanes of padding.
    """
    vf = jax.vmap(fn, in_axes=in_axes)
    b_idx = [i for i, ax in enumerate(in_axes) if ax == 0]

    def run(*arrs):
        B = arrs[b_idx[0]].shape[0]
        if B <= micro:
            return vf(*arrs)
        nmb = B // micro
        stacked = tuple(arrs[i].reshape((nmb, micro) + arrs[i].shape[1:])
                        for i in b_idx)

        def one(mb):
            full = list(arrs)           # shared operands stay as-is
            for i, a in zip(b_idx, mb):
                full[i] = a
            return vf(*full)

        outs = jax.lax.map(one, stacked)
        return jax.tree.map(lambda o: o.reshape((B,) + o.shape[2:]), outs)

    return run


class Executor:
    """One strategy for running compiled lane batches."""

    #: registry name ("" for ad-hoc instances passed straight to a config)
    name: str = ""

    def device_count(self) -> int:
        """How many devices this executor spreads a batch over."""
        raise NotImplementedError

    def cache_token(self) -> Tuple:
        """Hashable identity mixed into the engine's compile-cache key.

        Two executors with equal tokens must produce interchangeable
        compiled callables (same placement and shape contract).
        """
        return (self.name, self.device_count())

    def wrap(self, fn: Callable, in_axes: Tuple[Optional[int], ...],
             args: Sequence[jax.ShapeDtypeStruct]) -> Callable:
        """The traceable callable :meth:`compile` would jit.

        This is the executor's whole program BEFORE XLA gets involved
        (micro-batched vmap locally, ``shard_map`` over the lane mesh
        when sharded) — the unit static analysis traces, so the linter
        sees exactly what the compiled executable will contain.
        """
        raise NotImplementedError

    def trace(self, fn: Callable, in_axes: Tuple[Optional[int], ...],
              args: Sequence[jax.ShapeDtypeStruct], *,
              lower: bool = False) -> Tuple[Any, Any]:
        """Trace the wrapped program: ``(ClosedJaxpr, Lowered | None)``.

        With ``lower`` the jaxpr is also lowered through jit (pre-
        optimization HLO, retrievable as text via
        ``lowered.compiler_ir("hlo")``).  Nothing is compiled or run.
        Callers own the dtype scope: trace inside
        :func:`repro.core.dlt.precision.x64_scope` when the runtime
        does.
        """
        wrapped = self.wrap(fn, in_axes, args)
        closed = jax.make_jaxpr(wrapped)(*args)
        lowered = jax.jit(wrapped).lower(*args) if lower else None
        return closed, lowered

    def pad_batch(self, n_lanes: int, warm: bool) -> int:
        """Padded lane count for a chunk of ``n_lanes``.

        Cold chunks pad to the next power of two (repeating lanes is
        cheap; a bounded shape set keeps the compile LRU effective);
        warm chunks pad to a multiple of 4 — a micro-batch runs to its
        slowest lane, so po2-padding a reduced-budget warm pass with
        junk lanes would waste more of it.  Ladders at or above one
        micro-batch round up to a :data:`LANE_MICROBATCH` multiple (the
        unit executors compile); smaller chunks KEEP their ladder size
        and compile as one narrower group — padding a 1-lane bucket to
        16 would multiply its normal-equations work 16x for nothing.
        """
        base = (4 * ((n_lanes + 3) // 4) if warm
                else 1 << (n_lanes - 1).bit_length())
        if base < LANE_MICROBATCH:
            return base
        return -(-base // LANE_MICROBATCH) * LANE_MICROBATCH

    def compile(self, fn: Callable, in_axes: Tuple[Optional[int], ...],
                args: Sequence[jax.ShapeDtypeStruct]) -> Callable:
        """AOT-compile the per-lane kernel ``fn`` over stacked arguments.

        ``in_axes`` follows :func:`jax.vmap` semantics (0 = stacked
        along the lane axis, ``None`` = shared by every lane) and
        ``args`` are :class:`jax.ShapeDtypeStruct` for the padded
        stacked shapes.  The returned callable takes the concrete
        stacked arrays and handles any device placement itself.
        """
        raise NotImplementedError


def available_executors() -> List[str]:
    return sorted(_REGISTRY)


def resolve_executor(which: Union[str, Executor],
                     devices: Optional[int] = None) -> Executor:
    """Executor instance from a config knob.

    ``which`` is a registry name or a ready :class:`Executor` instance
    (returned as-is — ``devices`` must then be ``None``); ``devices``
    caps how many visible devices a multi-device executor uses.
    """
    if isinstance(which, Executor):
        if devices is not None:
            raise ValueError(
                "devices= cannot be combined with an Executor instance — "
                "configure the instance itself")
        return which
    try:
        cls = _REGISTRY[which]
    except KeyError:
        raise ValueError(
            f"unknown executor {which!r}: use one of {available_executors()} "
            "or pass an Executor instance") from None
    return cls(devices=devices)


# populated at package import time (avoids base <-> impl import cycles)
_REGISTRY: Dict[str, type] = {}
