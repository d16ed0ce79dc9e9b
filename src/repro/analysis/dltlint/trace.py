"""Tracing engine programs to inspectable artifacts.

The linter never executes a solve: it asks the engine for the exact
``(fn, in_axes, args)`` signature an executor would compile for a plan
(see :meth:`DLTEngine.trace_plan`), traces it to a ClosedJaxpr inside
the same ``x64_scope`` the runtime uses, and optionally lowers
it to HLO text for the :mod:`repro.analysis.hlo_parse` backend.

:func:`iter_eqns` is the shared jaxpr walker: it yields every equation
of a closed jaxpr AND of every sub-jaxpr reachable through equation
params (while cond/body, scan, pjit, pallas_call, custom derivatives),
each tagged with a provenance path like ``"pjit/while:body/scan"`` so a
finding can say where in the program it sits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterator, List, Optional, Tuple

import numpy as np

from ...core.dlt.stacking import BatchedSystemSpec
from ...core.dlt.types import SystemSpec

__all__ = [
    "TraceTarget",
    "TraceArtifact",
    "iter_eqns",
    "iter_eqns_scoped",
    "eqn_scopes",
    "demo_batch",
]


@dataclasses.dataclass(frozen=True)
class TraceTarget:
    """One formulation x kernel x executor x precision combination.

    ``precision`` pins the engine's numeric policy for the trace
    ("fp64" or "mixed") — never the env default, so lint results do not
    depend on ``$DLT_PRECISION`` of the machine running the sweep.
    """

    formulation: str
    kernel: str
    executor: str
    batch: int = 4
    warm: bool = False
    precision: str = "fp64"

    @property
    def label(self) -> str:
        ptag = f"/{self.precision}" if self.precision != "fp64" else ""
        tag = "/warm" if self.warm else ""
        return f"{self.formulation}/{self.kernel}/{self.executor}{ptag}{tag}"


@dataclasses.dataclass
class TraceArtifact:
    """Everything a rule may inspect for one traced target.

    ``jaxpr`` is the ClosedJaxpr of the executor-wrapped program;
    ``hlo_text`` is the unoptimized HLO rendering when the trace ran
    with ``with_hlo`` (rules degrade gracefully when it is ``None``).
    ``plan`` is the engine's resolved :class:`_KernelPlan` — rules use
    it for the banded geometry and the formulation name — and
    ``cache_key`` is the compile-LRU key the executable would live
    under (DL003 reports const bloat per cache key).
    """

    target: TraceTarget
    jaxpr: Any                        # jax.core.ClosedJaxpr
    cache_key: Tuple
    max_iter: int
    plan: Any = None                  # engine._KernelPlan
    config: Any = None                # EngineConfig
    hlo_text: Optional[str] = None

    @property
    def label(self) -> str:
        return self.target.label


def _sub_jaxprs(eqn) -> List[Tuple[str, Any]]:
    """(tag, jaxpr-like) pairs reachable through one equation's params."""
    subs: List[Tuple[str, Any]] = []
    for name, val in eqn.params.items():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                prim = eqn.primitive.name
                if prim == "while":
                    tag = {"cond_jaxpr": "while:cond",
                           "body_jaxpr": "while:body"}.get(name, prim)
                elif prim == "cond":
                    tag = "cond:branch"
                else:
                    tag = prim
                subs.append((tag, v))
    return subs


def iter_eqns(closed_jaxpr, _path: str = "") -> Iterator[Tuple[Any, str]]:
    """Yield ``(eqn, provenance_path)`` over a jaxpr and all sub-jaxprs."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn, _path
        for tag, sub in _sub_jaxprs(eqn):
            sub_path = f"{_path}/{tag}" if _path else tag
            yield from iter_eqns(sub, sub_path)


def eqn_scopes(eqn) -> str:
    """The ``jax.named_scope`` path recorded on one equation ("" if none).

    jax stamps the user name stack onto each equation's source info; the
    rendering is a "/"-joined path that survives into while/scan
    sub-jaxprs, so intent markers like
    :data:`~repro.core.dlt.precision.FP32_FACTOR_SCOPE` are visible to
    rules through every transform the engine applies.  One caveat: an
    internally-jitted helper (``jnp.clip`` etc.) traces its body OUTSIDE
    the caller's dynamic scope, so its sub-jaxpr equations come back
    with an empty stack even though the ``pjit`` equation itself is
    scoped — scope-sensitive rules should walk with
    :func:`iter_eqns_scoped`, which inherits the enclosing equation's
    scope across that boundary.
    """
    si = getattr(eqn, "source_info", None)
    ns = getattr(si, "name_stack", None)
    return str(ns) if ns is not None else ""


def iter_eqns_scoped(closed_jaxpr, _path: str = "", _scope: str = "",
                     ) -> Iterator[Tuple[Any, str, str]]:
    """Like :func:`iter_eqns` but yields ``(eqn, path, scopes)``.

    ``scopes`` is the equation's own named-scope stack prefixed with the
    stack of every enclosing equation — so equations inside a scoped
    ``pjit``'s sub-jaxpr (whose own stacks are empty, see
    :func:`eqn_scopes`) still report the caller's scope.
    """
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    for eqn in jaxpr.eqns:
        own = eqn_scopes(eqn)
        full = "/".join(s for s in (_scope, own) if s)
        yield eqn, _path, full
        for tag, sub in _sub_jaxprs(eqn):
            sub_path = f"{_path}/{tag}" if _path else tag
            yield from iter_eqns_scoped(sub, sub_path, full)


def _demo_specs(shapes, masked: bool) -> List[SystemSpec]:
    """Deterministic small systems spanning the requested (n, m) shapes.

    Values are fixed (no RNG): heterogeneous G/R/A so no row of the LP
    degenerates, release times strictly increasing so the Sec 3 ordering
    constraints are all active.  With ``masked`` the first shape is
    repeated at a smaller (n, m), so the stacked family contains padded
    sources, processors and rows — the masking path rules must survive.
    """
    specs = []
    for (n, m) in shapes:
        G = 0.2 + 0.1 * np.arange(n)
        R = 0.5 * np.arange(n)
        A = 1.0 + 0.25 * np.arange(m)
        specs.append(SystemSpec(G=G, R=R, A=A, J=10.0 + n + m))
    if masked and specs:
        n0, m0 = shapes[0]
        n1, m1 = max(1, n0 - 1), max(1, m0 - 1)
        specs.append(SystemSpec(G=0.3 + 0.1 * np.arange(n1),
                                R=0.25 * np.arange(n1),
                                A=1.5 + 0.5 * np.arange(m1), J=5.0))
    return specs


def demo_batch(n: int = 2, m: int = 3,
               masked: bool = True) -> BatchedSystemSpec:
    """A small stacked family at (n, m), optionally with a masked lane."""
    return BatchedSystemSpec.from_specs(_demo_specs([(n, m)], masked))
