"""Share of the window spent building traces (``core/workloads.py``
through ``SimBroker.canonical_trace``), from the benchmark's own host span
around the call."""


def read(ctx):
    return 100.0 * ctx["trace_build_s"] / ctx["window_s"]
