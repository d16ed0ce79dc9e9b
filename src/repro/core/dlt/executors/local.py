"""LocalExecutor — single-device execution on the default device.

The engine's pre-executor-layer behavior, extracted: compile the
per-lane kernel over the padded chunk ahead of time and run wherever
JAX's default device placement puts it.  The chunk runs as
:func:`~.base.microbatched` fixed-width vmap groups, which is the
baseline every other executor matches bit-for-bit (lanes are
independent, so placement cannot change results — see the module
docstring of :mod:`.base`).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax

from .base import Executor, LANE_MICROBATCH, microbatched, named

__all__ = ["LocalExecutor"]


class LocalExecutor(Executor):
    """Single-device execution (the classic ``jit(vmap)`` path)."""

    name = "local"

    def __init__(self, devices: Optional[int] = None):
        # the knob exists for signature parity with multi-device
        # executors; local execution always means ONE device
        if devices is not None and devices != 1:
            raise ValueError(
                f"executor='local' runs on one device, got devices={devices} "
                "— use executor='sharded' to spread lanes across devices")

    def device_count(self) -> int:
        return 1

    def cache_token(self) -> Tuple:
        return (self.name, 1, LANE_MICROBATCH)

    def wrap(self, fn: Callable, in_axes: Tuple[Optional[int], ...],
             args: Sequence[jax.ShapeDtypeStruct]) -> Callable:
        return microbatched(fn, in_axes)

    def compile(self, fn: Callable, in_axes: Tuple[Optional[int], ...],
                args: Sequence[jax.ShapeDtypeStruct],
                name: Optional[str] = None) -> Callable:
        return (jax.jit(named(self.wrap(fn, in_axes, args), name))
                .lower(*args).compile())
