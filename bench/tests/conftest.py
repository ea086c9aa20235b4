"""Tests of the benchmark's own code, on the CPU: ``python -m pytest
bench/tests``. The repository's tier-1 suite (``testpaths = tests``) does
not collect them."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
