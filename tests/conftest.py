"""Test config.  NOTE: no XLA_FLAGS here on purpose — smoke tests run on
the single real CPU device; only launch/dryrun.py (its own process) forces
512 placeholder devices.  Multi-device tests spawn subprocesses."""

import os
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

# tests/ itself on the path so `from _hyp import ...` (the offline
# hypothesis fallback shim) resolves regardless of pytest's rootdir.
TESTS = Path(__file__).resolve().parent
if str(TESTS) not in sys.path:
    sys.path.insert(0, str(TESTS))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Kernel-routing tests assume the hard-coded banded_min_rows default; an
# ambient autotune table (scripts/autotune_kernels.py writes one to the
# repo root) must not leak into them.  Tests that exercise the table set
# DLT_KERNEL_AUTOTUNE themselves.
os.environ.setdefault("DLT_KERNEL_AUTOTUNE", os.devnull)

# With hypothesis installed, no example database is written into the
# checkout.
try:
    from hypothesis import settings as _hypothesis_settings
except ImportError:
    pass
else:
    _hypothesis_settings.register_profile("repro", database=None)
    _hypothesis_settings.load_profile("repro")
