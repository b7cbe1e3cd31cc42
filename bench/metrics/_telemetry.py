"""Sums of the program's telemetry over the window, shared by the readers."""
import re

_STEPS = re.compile(r"/s(\d+)/")


def total(snapshot: dict, name: str) -> float:
    """Sum of histogram ``name`` (seconds) over all its labels."""
    return sum(v["sum"] for k, v in snapshot.items()
               if k == name or k.startswith(name + "{"))


def flushed_steps(snapshot: dict) -> int:
    """Scan steps of every flush: each ``broker.flushes`` count times the
    step count of its bucket (``.../s<steps>/...`` in the bucket label)."""
    out = 0
    for k, v in snapshot.items():
        if k.startswith("broker.flushes{"):
            m = _STEPS.search(k)
            if m:
                out += int(m.group(1)) * int(v)
    return out
