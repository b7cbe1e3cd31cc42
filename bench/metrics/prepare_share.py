"""Share of the window spent in the host schedule passes of each flush
(``fault_schedule``, ``plan_windows``, tiling and stacking of the lane
arrays): the sum of the program's ``sweep.prepare_seconds``."""
from bench.metrics._telemetry import total


def read(ctx):
    prep = total(ctx["telemetry"], "sweep.prepare_seconds")
    return 100.0 * prep / ctx["window_s"] if prep else None
