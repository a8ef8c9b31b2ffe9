"""The benchmark's one wall clock.

Every host-clock reading of the benchmark goes through this module, so
a reviewer finds all of them in one place.  ``PROCESS_START`` is read
when ``run.py`` first imports it, before JAX is imported: set-up time
counts from there.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()


def now() -> float:
    """Seconds on the monotonic high-resolution clock."""
    return time.perf_counter()


def since_start() -> float:
    return time.perf_counter() - PROCESS_START


def sleep_until(t: float) -> None:
    """Sleep until ``now() >= t`` (returns at once if it already is)."""
    left = t - time.perf_counter()
    if left > 0:
        time.sleep(left)
