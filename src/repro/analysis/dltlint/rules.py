"""The pluggable rule set: DL001 - DL007.

Graph-scope rules inspect one :class:`~.trace.TraceArtifact` (the
ClosedJaxpr of an executor-wrapped engine program, plus optional HLO
text); formulation-scope rules inspect a formulation's row builders
directly over a shape grid, with no tracing involved.  Register new
rules with :func:`register_rule`; the runner and the CLI pick them up
from the registry automatically (see CONTRIBUTING for the authoring
checklist).
"""

from __future__ import annotations

import numpy as np

from typing import Dict, List, Optional, Sequence, Tuple

from jax.extend.core import Literal as _Literal

from ...core.dlt.batched import build_banded_family, build_family_lp
from ...core.dlt.precision import FP32_FACTOR_SCOPE, REFINE_RESIDUAL_SCOPE
from ..hlo_parse import analyze_hlo
from .diagnostics import Finding, Severity
from .trace import (
    TraceArtifact,
    iter_eqns,
    iter_eqns_scoped,
)

__all__ = [
    "Rule",
    "register_rule",
    "get_rules",
    "all_rules",
]


class Rule:
    """One static check.

    ``scope`` picks the dispatch surface: ``"graph"`` rules get a
    :class:`TraceArtifact` through :meth:`check`; ``"formulation"``
    rules get a :class:`Formulation` through :meth:`check_formulation`.
    """

    id: str = ""
    title: str = ""
    scope: str = "graph"

    def check(self, artifact: TraceArtifact) -> List[Finding]:
        raise NotImplementedError

    def check_formulation(self, fm,
                          shapes: Optional[Sequence[Tuple[int, int]]] = None,
                          ) -> List[Finding]:
        raise NotImplementedError


_RULES: Dict[str, Rule] = {}


def register_rule(cls):
    """Class decorator adding a rule (by its ``id``) to the registry."""
    if not cls.id:
        raise ValueError(f"rule {cls.__name__} has no id")
    _RULES[cls.id] = cls()
    return cls


def all_rules() -> List[Rule]:
    return [_RULES[k] for k in sorted(_RULES)]


def get_rules(ids: Optional[Sequence[str]] = None) -> List[Rule]:
    if ids is None:
        return all_rules()
    missing = sorted(set(ids) - set(_RULES))
    if missing:
        raise ValueError(
            f"unknown rule id(s) {missing}: registered are {sorted(_RULES)}")
    return [_RULES[i] for i in sorted(set(ids))]


# ---------------------------------------------------------------------------
# DL001 — bounded loops
# ---------------------------------------------------------------------------

_INT_CMPS = ("lt", "le", "gt", "ge")


def _int_literal_bounds(cond_jaxpr) -> List[int]:
    """Integer literals compared against inside a while condition."""
    bounds = []
    for eqn, _ in iter_eqns(cond_jaxpr):
        if eqn.primitive.name not in _INT_CMPS:
            continue
        for v in eqn.invars:
            if not isinstance(v, _Literal):
                continue
            val = np.asarray(v.val)
            if np.issubdtype(val.dtype, np.integer) and val.ndim == 0:
                bounds.append(int(val))
    return bounds


@register_rule
class BoundedLoops(Rule):
    """DL001: every while-loop trip bound must derive from the IPM budget.

    A ``while`` whose condition never compares its carry against an
    integer literal has no static trip bound — under vmap one divergent
    lane would hang the whole chunk.  A literal bound LARGER than the
    engine budget means the loop's cap did not come from ``max_iter``.
    The per-loop bound map (INFO findings) is what the mixed-precision
    work consumes to pick refinement budgets.
    """

    id = "DL001"
    title = "bounded loops"

    def check(self, art: TraceArtifact) -> List[Finding]:
        out = []
        for eqn, path in iter_eqns(art.jaxpr):
            if eqn.primitive.name != "while":
                continue
            prov = f"{path}/while" if path else "while"
            bounds = _int_literal_bounds(eqn.params["cond_jaxpr"])
            if not bounds:
                out.append(Finding(
                    rule=self.id, severity=Severity.ERROR,
                    message="while-loop with no static integer trip bound "
                            "in its condition",
                    target=art.label, provenance=prov,
                    hint="cap the loop with the engine's max_iter budget "
                         "(compare the carried counter against a literal)"))
            elif max(bounds) > art.max_iter:
                out.append(Finding(
                    rule=self.id, severity=Severity.ERROR,
                    message=f"while-loop bound {max(bounds)} exceeds the "
                            f"engine budget max_iter={art.max_iter}",
                    target=art.label, provenance=prov,
                    hint="derive the trip bound from EngineConfig.max_iter "
                         "instead of an ad-hoc constant",
                    data={"bound": max(bounds), "max_iter": art.max_iter}))
            else:
                out.append(Finding(
                    rule=self.id, severity=Severity.INFO,
                    message=f"while-loop bounded at {max(bounds)} "
                            f"(budget {art.max_iter})",
                    target=art.label, provenance=prov,
                    data={"bound": max(bounds), "max_iter": art.max_iter}))
        if art.hlo_text is not None:
            stats = analyze_hlo(art.hlo_text)
            for body in stats.unbounded_whiles:
                out.append(Finding(
                    rule=self.id, severity=Severity.ERROR,
                    message=f"HLO while body {body!r} has no constant trip "
                            "bound in its condition",
                    target=art.label, provenance=f"hlo:{body}",
                    hint="the jaxpr bound did not survive lowering — check "
                         "for data-dependent loop rewrites"))
        return out


# ---------------------------------------------------------------------------
# DL002 — dtype drift
# ---------------------------------------------------------------------------

@register_rule
class DtypeDrift(Rule):
    """DL002: map implicit float truncations and weak-type promotions.

    The IPM hot path is fp64 end to end; a ``convert_element_type``
    that narrows a float (f64 -> f32) silently costs ~8 decimal digits
    exactly where the normal equations are most ill-conditioned.  The
    one sanctioned exception is the mixed-precision factor: narrowings
    under the :data:`FP32_FACTOR_SCOPE` named scope are the policy's
    intentional boundary and downgrade to INFO (DL007 separately
    asserts the refinement residual stays out of fp32).  Widening
    conversions of weakly-typed operands are reported as INFO: they are
    where a mixed-precision pass inserts its boundaries.
    """

    id = "DL002"
    title = "dtype drift"

    def check(self, art: TraceArtifact) -> List[Finding]:
        out = []
        for eqn, path, scopes in iter_eqns_scoped(art.jaxpr):
            if eqn.primitive.name != "convert_element_type":
                continue
            src = eqn.invars[0].aval
            dst = np.dtype(eqn.params["new_dtype"])
            sdt = np.dtype(src.dtype)
            if not (np.issubdtype(sdt, np.floating)
                    and np.issubdtype(dst, np.floating)):
                continue
            prov = f"{path}/convert" if path else "convert"
            if dst.itemsize < sdt.itemsize:
                if FP32_FACTOR_SCOPE in scopes:
                    out.append(Finding(
                        rule=self.id, severity=Severity.INFO,
                        message=f"intentional truncation {sdt.name} -> "
                                f"{dst.name} under the "
                                f"{FP32_FACTOR_SCOPE!r} scope "
                                "(mixed-precision factor boundary)",
                        target=art.label, provenance=prov,
                        data={"from": sdt.name, "to": dst.name,
                              "scope": FP32_FACTOR_SCOPE}))
                    continue
                out.append(Finding(
                    rule=self.id, severity=Severity.WARNING,
                    message=f"implicit float truncation {sdt.name} -> "
                            f"{dst.name} on the solve path",
                    target=art.label, provenance=prov,
                    hint="make the narrowing explicit (astype at a module "
                         "boundary, inside FP32_FACTOR_SCOPE if it is the "
                         "mixed-precision factor) or keep the hot path in "
                         "float64",
                    data={"from": sdt.name, "to": dst.name}))
            elif dst.itemsize > sdt.itemsize and getattr(
                    src, "weak_type", False):
                out.append(Finding(
                    rule=self.id, severity=Severity.INFO,
                    message=f"weak-type promotion {sdt.name} -> {dst.name}",
                    target=art.label, provenance=prov,
                    data={"from": sdt.name, "to": dst.name}))
        return out


# ---------------------------------------------------------------------------
# DL003 — const bloat
# ---------------------------------------------------------------------------

@register_rule
class ConstBloat(Rule):
    """DL003: large constants captured into a compiled executable.

    Every closed-over array is baked into the executable per compile-
    cache entry — a 10 MiB captured table times a 64-entry LRU is real
    memory, and it re-serializes into the persistent compile cache.
    Anything above ``threshold_bytes`` should arrive as an argument.
    """

    id = "DL003"
    title = "const bloat"
    threshold_bytes = 1 << 20

    def check(self, art: TraceArtifact) -> List[Finding]:
        out = []
        total = 0
        for i, c in enumerate(art.jaxpr.consts):
            try:
                nb = int(np.asarray(c).nbytes)
            except (TypeError, ValueError):
                continue
            total += nb
            if nb > self.threshold_bytes:
                arr = np.asarray(c)
                out.append(Finding(
                    rule=self.id, severity=Severity.ERROR,
                    message=f"captured constant #{i} is {nb} bytes "
                            f"(shape {tuple(arr.shape)}, {arr.dtype}) — "
                            f"baked into every executable under this key",
                    target=art.label, provenance=f"const[{i}]",
                    hint="pass the array as a traced argument (in_axes="
                         "None) instead of closing over it",
                    data={"nbytes": nb, "shape": list(arr.shape),
                          "dtype": str(arr.dtype),
                          "cache_key": repr(art.cache_key)}))
        out.append(Finding(
            rule=self.id, severity=Severity.INFO,
            message=f"{len(art.jaxpr.consts)} captured constant(s), "
                    f"{total} bytes total",
            target=art.label,
            data={"total_bytes": total, "cache_key": repr(art.cache_key)}))
        return out


# ---------------------------------------------------------------------------
# DL004 — transfer purity
# ---------------------------------------------------------------------------

_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "outside_call", "host_callback_call"}


def _explicit_placement(eqn) -> bool:
    """Does a ``device_put`` eqn pin a device or sharding?

    ``jnp.asarray`` on a numpy constant inside traced code also emits
    ``device_put`` — with ``devices=[None]`` and alias semantics, pure
    constant staging.  Only an entry with an actual device or sharding
    (or an explicit source) forces a transfer at run time.
    """
    for param in ("devices", "srcs"):
        if any(d is not None for d in eqn.params.get(param, ())):
            return True
    return False


@register_rule
class TransferPurity(Rule):
    """DL004: no placement or host round-trips inside compiled bodies.

    The executor owns placement: operands are committed to their
    shardings BEFORE the executable runs.  A ``device_put`` or host
    callback inside the traced body forces a mid-program transfer on
    every call — under ``shard_map`` it can funnel the whole sharded
    batch through one device.  Errors on the sharded executor, warnings
    elsewhere (the local path merely hides the cost).
    """

    id = "DL004"
    title = "transfer purity"

    def check(self, art: TraceArtifact) -> List[Finding]:
        out = []
        sharded = art.target.executor == "sharded"
        sev = Severity.ERROR if sharded else Severity.WARNING
        for eqn, path in iter_eqns(art.jaxpr):
            name = eqn.primitive.name
            if name == "device_put" and _explicit_placement(eqn):
                prov = f"{path}/{name}" if path else name
                out.append(Finding(
                    rule=self.id, severity=sev,
                    message="explicitly-placed device_put inside a "
                            "compiled body forces a mid-program transfer"
                            + (" (gathers the sharded batch)" if sharded
                               else ""),
                    target=art.label, provenance=prov,
                    hint="commit operands to their shardings outside the "
                         "compiled function (see ShardedExecutor.compile)"))
            elif name in _CALLBACK_PRIMS:
                prov = f"{path}/{name}" if path else name
                out.append(Finding(
                    rule=self.id, severity=sev,
                    message=f"host callback {name!r} inside a compiled body "
                            "blocks the device on the host",
                    target=art.label, provenance=prov,
                    hint="hoist host work out of the jitted region"))
        return out


# ---------------------------------------------------------------------------
# DL005 — banded-structure honesty
# ---------------------------------------------------------------------------

#: (n_sources, n_processors) grid the honesty check sweeps; every shape
#: also stacks one smaller lane so the masked-row path is covered.
HONESTY_SHAPES = ((2, 3), (3, 4), (2, 6), (4, 5))


def _band_violations(bfam) -> List[Tuple[int, int, int]]:
    """(lane, row, col) normal-equation nonzeros outside the declared band."""
    g = bfam.geom
    nv, m = g.nv, g.m
    # position -> tridiagonal block index; border rows get the sentinel K
    blockpos = np.concatenate([g.bkb, np.full(g.p, g.K, dtype=np.int64)])
    border = blockpos == g.K
    allowed = (border[:, None] | border[None, :]
               | (np.abs(blockpos[:, None] - blockpos[None, :]) <= 1))
    bad: List[Tuple[int, int, int]] = []
    B = bfam.F.shape[0]
    for b in range(B):
        # row pattern over z = [lp_vars | extra (position order)]:
        # variables from the transformed rows, own extra column nv+t,
        # and the differenced predecessor's extra column nv+dprev[t]
        P = np.zeros((m, nv + m), dtype=bool)
        P[:, :nv] = bfam.F[b] != 0.0
        P[np.arange(m), nv + np.arange(m)] = bfam.ext[b] != 0.0
        coupled = ((bfam.dcoef[b] != 0.0) & g.has_prev
                   & (bfam.ext[b][g.dprev_c] != 0.0))
        rows = np.flatnonzero(coupled)
        P[rows, nv + g.dprev_c[rows]] = True
        normal = P @ P.T            # sparsity of A D A' (pattern union)
        viol = normal & ~allowed
        for t, u in zip(*np.nonzero(viol)):
            if t <= u:
                bad.append((b, int(t), int(u)))
    return bad


@register_rule
class BandedHonesty(Rule):
    """DL005: the declared BandedStructure must match the real sparsity.

    The banded kernel only LOOKS at the block-tridiagonal band plus the
    border — a normal-equations nonzero outside it is silently dropped
    and the IPM converges to the wrong optimum (or not at all).  This
    symbolically rebuilds the normal-matrix pattern from the
    formulation's actual rows over a shape grid (masked lanes included)
    and demands zero nonzeros outside what the structure declares.
    """

    id = "DL005"
    title = "banded-structure honesty"
    scope = "formulation"

    def check_formulation(self, fm,
                          shapes: Optional[Sequence[Tuple[int, int]]] = None,
                          ) -> List[Finding]:
        out = []
        caps = fm.capabilities
        for (n, m) in (shapes or HONESTY_SHAPES):
            struct = fm.banded_structure(n, m)
            label = f"{fm.name}[n={n},m={m}]"
            if struct is None:
                if caps is not None and caps.supports_banded:
                    out.append(Finding(
                        rule=self.id, severity=Severity.ERROR,
                        message="capabilities claim supports_banded=True "
                                "but banded_structure() returned None",
                        target=label,
                        hint="either implement banded_structure() or "
                             "declare supports_banded=False"))
                else:
                    out.append(Finding(
                        rule=self.id, severity=Severity.INFO,
                        message="no banded structure declared — nothing to "
                                "verify",
                        target=label))
                continue
            bs = fm.demo_batch(n=n, m=m, masked=True)
            fam = build_family_lp(bs, fm)
            try:
                bfam = build_banded_family(
                    fam, fm.banded_structure(bs.n_max, bs.m_max))
            except ValueError as e:
                out.append(Finding(
                    rule=self.id, severity=Severity.ERROR,
                    message=f"declared structure failed validation: {e}",
                    target=label,
                    hint="fix the formulation's banded_structure() so "
                         "validate() accepts it"))
                continue
            bad = _band_violations(bfam)
            if bad:
                b, t, u = bad[0]
                out.append(Finding(
                    rule=self.id, severity=Severity.ERROR,
                    message=f"{len(bad)} normal-equation nonzero(s) outside "
                            f"the declared band (first: lane {b}, "
                            f"positions {t} x {u})",
                    target=label,
                    hint="the row chains the structure declares (dprev) do "
                         "not difference away the off-band coupling — fix "
                         "the block assignment or the chain map",
                    data={"violations": len(bad),
                          "first": [b, t, u]}))
            else:
                out.append(Finding(
                    rule=self.id, severity=Severity.INFO,
                    message="normal-equation sparsity is inside the "
                            "declared band",
                    target=label,
                    data={"K": bfam.geom.K, "s": bfam.geom.s,
                          "p": bfam.geom.p}))
        return out


# ---------------------------------------------------------------------------
# DL006 — Pallas VMEM budget
# ---------------------------------------------------------------------------

#: Conservative per-backend VMEM budgets for one grid step's working set.
VMEM_BUDGET_BYTES = {"tpu": 16 << 20}
DEFAULT_VMEM_BUDGET = 16 << 20


def _block_bytes(bm) -> int:
    """Bytes of one block window of a pallas operand (mapped dims = 1)."""
    n = 1
    for d in bm.block_shape:
        # Blocked(k) windows k elements; Squeezed / Element dims map one
        n *= int(getattr(d, "block_size", 1))
    return n * np.dtype(bm.array_aval.dtype).itemsize


def pallas_call_vmem_bytes(eqn) -> Optional[int]:
    """Estimated VMEM working set of one ``pallas_call`` equation.

    Grid-blocked operands count twice (Pallas double-buffers the block
    pipeline); scratch allocations count once.  Returns ``None`` when
    the equation's params do not carry a readable grid mapping (older
    JAX layouts) — the rule then skips rather than guessing.
    """
    gm = eqn.params.get("grid_mapping")
    if gm is None or not hasattr(gm, "block_mappings"):
        return None
    total = sum(2 * _block_bytes(bm) for bm in gm.block_mappings)
    jaxpr = eqn.params.get("jaxpr")
    nscratch = eqn.params.get("num_scratch_operands", 0)
    if jaxpr is not None and nscratch:
        inner = getattr(jaxpr, "jaxpr", jaxpr)
        for var in inner.invars[len(inner.invars) - nscratch:]:
            aval = var.aval
            n = 1
            for d in getattr(aval, "shape", ()):
                n *= int(d)
            total += n * np.dtype(aval.dtype).itemsize
    return total


@register_rule
class PallasVmem(Rule):
    """DL006: the banded-Cholesky block working set must fit in VMEM.

    The Pallas kernels stream ``(s, s)`` / ``(p, s)`` blocks through
    on-chip memory; past the budget the lowering either fails on device
    or silently spills.  The estimate comes straight from the traced
    BlockSpecs (double-buffered) plus the declared scratch shapes.
    """

    id = "DL006"
    title = "pallas VMEM budget"

    def check(self, art: TraceArtifact) -> List[Finding]:
        import jax

        budget = VMEM_BUDGET_BYTES.get(jax.default_backend(),
                                       DEFAULT_VMEM_BUDGET)
        out = []
        worst = 0
        npallas = 0
        for eqn, path in iter_eqns(art.jaxpr):
            if eqn.primitive.name != "pallas_call":
                continue
            npallas += 1
            est = pallas_call_vmem_bytes(eqn)
            if est is None:
                continue
            worst = max(worst, est)
            if est > budget:
                prov = f"{path}/pallas_call" if path else "pallas_call"
                out.append(Finding(
                    rule=self.id, severity=Severity.ERROR,
                    message=f"pallas_call working set ~{est / 2**20:.1f} "
                            f"MiB exceeds the {budget / 2**20:.0f} MiB "
                            "VMEM budget",
                    target=art.label, provenance=prov,
                    hint="shrink the block size s (split processor "
                         "blocks) or tile the border p",
                    data={"estimate_bytes": est, "budget_bytes": budget}))
        if npallas and not out:
            out.append(Finding(
                rule=self.id, severity=Severity.INFO,
                message=f"{npallas} pallas_call(s), worst working set "
                        f"~{worst / 2**20:.2f} MiB (budget "
                        f"{budget / 2**20:.0f} MiB)",
                target=art.label,
                data={"estimate_bytes": worst, "budget_bytes": budget}))
        return out


# ---------------------------------------------------------------------------
# DL007 — refinement residual precision
# ---------------------------------------------------------------------------

@register_rule
class RefineResidualPrecision(Rule):
    """DL007: the iterative-refinement residual must be exact fp64.

    Mixed precision is only honest if the residual ``r = rhs - M w``
    that drives the refinement loop is evaluated with the exact fp64
    operator — an fp32 residual caps the recoverable accuracy at fp32
    eps and the "refined" solution silently inherits the factor's
    error.  The residual lives under the
    :data:`REFINE_RESIDUAL_SCOPE` named scope (see
    :mod:`repro.core.dlt.precision`); this rule walks every equation
    inside it and errors on any sub-fp64 float output or narrowing
    convert.  A mixed-policy trace with NO residual-scope equations at
    all is a warning: the refinement loop the policy promises never
    made it into the compiled program.
    """

    id = "DL007"
    title = "refinement residual precision"

    def check(self, art: TraceArtifact) -> List[Finding]:
        if getattr(art.target, "precision", "fp64") != "mixed":
            return []
        out = []
        n_scope = 0
        for eqn, path, scopes in iter_eqns_scoped(art.jaxpr):
            if REFINE_RESIDUAL_SCOPE not in scopes:
                continue
            n_scope += 1
            name = eqn.primitive.name
            prov = f"{path}/{name}" if path else name
            if name == "convert_element_type":
                dst = np.dtype(eqn.params["new_dtype"])
                if np.issubdtype(dst, np.floating) and dst.itemsize < 8:
                    out.append(Finding(
                        rule=self.id, severity=Severity.ERROR,
                        message="refinement residual narrowed to "
                                f"{dst.name} inside the "
                                f"{REFINE_RESIDUAL_SCOPE!r} scope",
                        target=art.label, provenance=prov,
                        hint="the residual r = rhs - M w must use the "
                             "exact fp64 operator; move fp32 work into "
                             "FP32_FACTOR_SCOPE",
                        data={"to": dst.name}))
                    continue
            for v in eqn.outvars:
                dt = np.dtype(getattr(v.aval, "dtype", np.float64))
                if np.issubdtype(dt, np.floating) and dt.itemsize < 8:
                    out.append(Finding(
                        rule=self.id, severity=Severity.ERROR,
                        message=f"{name} inside the refine-residual scope "
                                f"produces {dt.name}",
                        target=art.label, provenance=prov,
                        hint="everything under REFINE_RESIDUAL_SCOPE must "
                             "stay float64",
                        data={"primitive": name, "dtype": dt.name}))
                    break
        if n_scope == 0:
            out.append(Finding(
                rule=self.id, severity=Severity.WARNING,
                message="mixed-precision trace contains no "
                        f"{REFINE_RESIDUAL_SCOPE!r} equations — the "
                        "refinement loop is missing from the compiled "
                        "program",
                target=art.label,
                hint="check that the kernel passed make_fp32_solver "
                     "through to _hsde_ipm_core and that refined_solver "
                     "wraps the residual in REFINE_RESIDUAL_SCOPE"))
        elif not out:
            out.append(Finding(
                rule=self.id, severity=Severity.INFO,
                message=f"{n_scope} refine-residual equation(s), all fp64",
                target=art.label, data={"eqns": n_scope}))
        return out
