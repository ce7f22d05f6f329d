"""The benchmark's own tests run on the CPU, at sizes a test run holds:
``python -m pytest bench/tests`` from the root of the checkout."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
