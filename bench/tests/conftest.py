"""The benchmark's own tests import its modules by their file names, as
``bench/run.py`` does, and the program from ``src/``."""
import sys
from pathlib import Path

_BENCH = Path(__file__).resolve().parents[1]
for p in (str(_BENCH), str(_BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
