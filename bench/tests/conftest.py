"""CPU tests of the benchmark harness (run by path: ``pytest bench/tests``).

They import the harness's modules from ``bench/`` and the program from
``src/``, and keep JAX on the CPU."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
