"""The benchmark's own tests run on the CPU, at sizes a test run holds:
``python -m pytest bench/tests -q`` from the root of the checkout."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
