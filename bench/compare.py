"""The comparison that decides ``correct``: a served answer against the
plain reference (``reference.oracle``), leaf by leaf.

Two numbers come out of it, each judged against its limit in ``LIMITS``:

  mismatched_leaves  placements (data, leaf, mid, top and root page-table
                     pages), allocator state, recent-access counts, every
                     event counter and every integer timeline that is not
                     bit-for-bit equal to the reference's.  Exact: limit 0.
  cycles_gap         the widest relative gap of any float32 cycle quantity
                     (per-thread totals, walk, stall, data and fault
                     cycles, migration cycles, and their per-step
                     timelines): max |x - ref| / max(|ref|, 1).

The limit on ``cycles_gap`` was set from chip readings of the program over
a dozen seeds (lower) and of the bfloat16 control (upper); ``PERF.md``
gives both readings.
"""
from __future__ import annotations

import numpy as np

from bench.reference.oracle import COUNTER_KEYS

# The smallest gap the bfloat16 control reads is orders of magnitude above
# this, and the program's own widest gap orders of magnitude below it.
LIMITS = {"mismatched_leaves": 0, "cycles_gap": 1e-3}

# RunResult.final_state field -> reference attribute
_STATE = {"data_node": "data_node", "leaf_node": "leaf_node",
          "mid_node": "mid_node", "top_node": "top_node",
          "root_node": "root_node", "leaf_dram_children": "ldc",
          "shadow_node": "shadow", "node_free": "free",
          "node_reclaimable": "reclaimable",
          "interleave_ptr": "interleave_ptr", "oom_killed": "oom",
          "oom_step": "oom_step", "access_recent": "access",
          "written_recent": "written", "step": "step"}
_CYCLES = {"total": "cy_total", "walk": "cy_walk", "stall": "cy_stall",
           "data_mem": "cy_data", "fault": "cy_fault",
           "migration": "cy_mig"}
TIMELINE_CYCLES = ("total_cycles", "walk_cycles", "stall_cycles",
                   "migration_cycles", "data_mem_cycles", "fault_cycles")


def program_leaves(res):
    """(exact, cycles) leaves of a served ``RunResult``."""
    st = res.final_state
    exact = {f"state.{k}": getattr(st, k) for k in _STATE}
    for k in ("data_allocs", "pt_allocs", *COUNTER_KEYS):
        exact[f"counters.{k}"] = getattr(st.counters, k)
    cycles = {f"cycles.{k}": getattr(st.cycles, k) for k in _CYCLES}
    for k, v in res.timeline.items():
        (cycles if k in TIMELINE_CYCLES else exact)[f"timeline.{k}"] = v
    return exact, cycles


def reference_leaves(ref):
    """The same leaves, from a finished ``RefSim``."""
    exact = {f"state.{k}": getattr(ref, a) for k, a in _STATE.items()}
    exact["counters.data_allocs"] = ref.data_allocs
    exact["counters.pt_allocs"] = ref.pt_allocs
    for k in COUNTER_KEYS:
        exact[f"counters.{k}"] = ref.cnt[k]
    cycles = {f"cycles.{k}": getattr(ref, a) for k, a in _CYCLES.items()}
    for k, v in ref.timeline.items():
        (cycles if k in TIMELINE_CYCLES else exact)[f"timeline.{k}"] = v
    return exact, cycles


def compare(got, ref):
    """``got`` and ``ref`` are (exact, cycles) leaf pairs.  Returns the
    names of the leaves that differ (exact leaves not bit-equal, cycle
    leaves of another shape or not finite) and the widest cycle gap."""
    (ge, gc), (re_, rc) = got, ref
    mismatched = []
    for k, want in re_.items():
        have = ge.get(k)
        if have is None or not np.array_equal(
                np.asarray(have).astype(np.int64),
                np.asarray(want).astype(np.int64)):
            mismatched.append(k)
    gap = 0.0
    for k, want in rc.items():
        have = gc.get(k)
        y = np.asarray(want, np.float64)
        x = None if have is None else np.asarray(have, np.float64)
        if x is None or x.shape != y.shape or not np.isfinite(x).all():
            mismatched.append(k)
        elif y.size:
            gap = max(gap, float(np.max(np.abs(x - y)
                                        / np.maximum(np.abs(y), 1.0))))
    return mismatched, gap
