"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
repository root, on the CPU (``JAX_PLATFORMS=cpu``)."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
