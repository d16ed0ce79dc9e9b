"""Pluggable execution strategies for the batched DLT engine.

``EngineConfig(executor=..., devices=...)`` selects how compiled lane
batches run; see :mod:`.base` for the protocol.  Register additional
strategies by adding to :data:`base._REGISTRY` (name -> class taking a
``devices=`` kwarg) or by passing an :class:`Executor` instance
directly as the config knob.
"""

from .base import (
    LANE_MICROBATCH,
    Executor,
    available_executors,
    microbatch_slots,
    microbatched,
    resolve_executor,
    _REGISTRY,
)
from .local import LocalExecutor
from .sharded import ShardedExecutor

_REGISTRY.update({
    LocalExecutor.name: LocalExecutor,
    ShardedExecutor.name: ShardedExecutor,
})

__all__ = [
    "LANE_MICROBATCH",
    "Executor",
    "LocalExecutor",
    "ShardedExecutor",
    "available_executors",
    "microbatch_slots",
    "microbatched",
    "resolve_executor",
]
