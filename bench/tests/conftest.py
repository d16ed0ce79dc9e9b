"""The benchmark's own tests run on the CPU, at tiny sizes."""

import os
import sys
from pathlib import Path

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT, ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture(scope="session")
def harness(tmp_path_factory):
    """``bench/run.py`` as a module, its run-time files in a temp dir."""
    import run
    run.STATE = tmp_path_factory.mktemp("bench_state")
    return run
