"""Share of the traced window in which no operation ran on the device
(mean over the chips used), from the profiler trace.  The traced window
ends where the device's trace buffers overflow, if they do: with per-op
device events that is after the first few seconds of the window, so the
share covers those seconds only."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
