"""The benchmark's CPU tests: ``python -m pytest bench/tests`` from the
checkout's root.  They need the program's sources on the path and run the
Pallas kernels in interpret mode."""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
