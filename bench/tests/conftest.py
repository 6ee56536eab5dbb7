"""Tests of the benchmark's own arithmetic and plumbing, on the CPU.

    python -m pytest bench/tests

JAX is held to the CPU (the Pallas kernels run in interpret mode), and no
test loads the TPU library."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
