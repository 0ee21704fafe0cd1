"""The measured window: a closed loop of one client.

A query starts only while the time left is at least `expected_s`, the wall
of the last warm-up query, and one query always runs.  So a run does not
overshoot `seconds` by more than a query's own variation, and no query is
cut.  `run_one()` runs one query and returns the wall it measured; what it
does around its own clock (a shuffle directory made and removed, garbage
collected) uses up window time but is in no query's wall.
"""

from __future__ import annotations

import time
from typing import Callable, List


def run_window(run_one: Callable[[], float], seconds: float,
               expected_s: float,
               clock: Callable[[], float] = time.perf_counter) -> List[float]:
    """Walls of the queries completed, in order."""
    walls: List[float] = []
    start = clock()
    while True:
        walls.append(run_one())
        if seconds - (clock() - start) < expected_s:
            return walls
