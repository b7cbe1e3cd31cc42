"""The arithmetic of a measured window: all work over all time.

A window is a list of grids, each submitted at once and answered in full
before the next is submitted (a closed loop).  ``GridRecord`` holds what
the host clock saw of one grid; the end-to-end metrics come from the list.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Sequence

import numpy as np


@dataclasses.dataclass
class GridRecord:
    t_submit: float          # host clock before the grid's first trace build
    t_done: float            # host clock when its last answer was in hand
    queries: int             # queries submitted
    failed: int              # queries whose future held an error
    accesses: int            # simulated accesses of the answered queries


def accesses(va) -> int:
    """Simulated memory accesses of one trace: its non-idle entries
    (``va >= 0``).  Idle rows that pad a trace to its bucket count none."""
    return int(np.count_nonzero(np.asarray(va) >= 0))


def percentile(values: Sequence[float], q: int) -> float:
    """The ``q``-th percentile (inclusive linear interpolation, as
    ``statistics.quantiles(method="inclusive")``) of all ``values``."""
    values = list(values)
    if not values:
        raise ValueError("no values")
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=100,
                                      method="inclusive")[q - 1])


def window_seconds(grids: Sequence[GridRecord]) -> float:
    """From the window's first submit to its last answer."""
    return grids[-1].t_done - grids[0].t_submit


def sim_accesses_per_s(grids: Sequence[GridRecord]) -> float:
    """Accesses of every answered query over the whole window."""
    return sum(g.accesses for g in grids) / window_seconds(grids)


def query_p90_s(grids: Sequence[GridRecord]) -> float:
    """90th percentile over every query of the window of the time from its
    grid's submit to its answer.  A query answers when ``drain()`` returns
    its grid, so each query of a grid carries the grid's time."""
    return percentile([g.t_done - g.t_submit for g in grids
                       for _ in range(g.queries)], 90)
