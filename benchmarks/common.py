"""Shared helpers for the paper-reproduction benchmarks."""

from __future__ import annotations

import re
from pathlib import Path

import numpy as np

__all__ = ["table", "check", "Result", "COMPILE_CACHE_DIR"]

#: Where the benches persist compiled executables when
#: ``$JAX_COMPILATION_CACHE_DIR`` is unset: one fixed path in the
#: checkout (git-ignored), so a later process finds them again.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


class Result:
    def __init__(self, name: str):
        self.name = name
        self.checks: list[tuple[str, bool, str]] = []

    def check(self, label: str, got, want, rtol: float = 0.02):
        g = np.asarray(got, dtype=np.float64)
        w = np.asarray(want, dtype=np.float64)
        ok = bool(np.all(np.abs(g - w) <= rtol * np.maximum(np.abs(w), 1e-12)))
        self.checks.append((label, ok, f"got {got} want {want} (rtol {rtol})"))
        mark = "PASS" if ok else "FAIL"
        print(f"  [{mark}] {label}: {got} (paper: {want})")
        return ok

    def note(self, label: str, value):
        print(f"  [note] {label}: {value}")

    @property
    def passed(self) -> bool:
        return all(ok for _, ok, _ in self.checks)


def table(headers, rows, fmt="{:>12}"):
    m = re.search(r"(\d+)", fmt)
    w = int(m.group(1)) if m else 12  # truncate cells at the column width
    line = " ".join(fmt.format(str(h)[:w]) for h in headers)
    print(line)
    print("-" * len(line))
    for r in rows:
        print(" ".join(
            fmt.format(f"{v:.4g}" if isinstance(v, float) else str(v)[:w])
            for v in r))


def check(name):
    return Result(name)
