"""Device milliseconds per padded scan step of the blocked engine: the
program's ``sweep.device_seconds`` (dispatch to ``block_until_ready``)
over the scan steps of every flush of the window."""
from bench.metrics._telemetry import flushed_steps, total


def read(ctx):
    t = ctx["telemetry"]
    steps = flushed_steps(t)
    dev = total(t, "sweep.device_seconds")
    return 1000.0 * dev / steps if steps and dev else None
