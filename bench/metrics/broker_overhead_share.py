"""Share of the window the broker's flushes spend outside the engine
call: ``broker.flush_seconds`` less the engine's prepare, device and
readback seconds (lane bookkeeping, result cache, future resolution)."""
from bench.metrics._telemetry import total


def read(ctx):
    t = ctx["telemetry"]
    flush = total(t, "broker.flush_seconds")
    if not flush:
        return None
    inner = sum(total(t, f"sweep.{k}_seconds")
                for k in ("prepare", "device", "readback"))
    return 100.0 * (flush - inner) / ctx["window_s"]
