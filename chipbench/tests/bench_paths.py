"""Puts the benchmark's directory and the program's ``src`` on the
import path, for the CPU tests of the chip benchmark (its arithmetic,
its traffic, its trace reduction, and whole runs at a tiny size with
the look for the chip skipped; none needs or touches a TPU).  Each
test module imports this first.  It is not a ``conftest.py``: the
repository's own ``tests/conftest.py`` is imported by that name."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
for p in (HERE, HERE.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
