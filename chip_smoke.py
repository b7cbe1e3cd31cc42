"""Smoke run of the served simulator path on a TPU.

Drives the main path once through the entry points a user calls —
``SimQuery`` -> ``SimBroker`` -> ``sweep_lanes`` -> the blocked engine —
at the benchmark machine's full size (``benchmark_machine()``: 32
simulated CPUs, 2x49,152 DRAM and 2x204,800 NVMM pages, a 2^18-page
address space) on the fig9 grid: six workloads x Linux default / BHi /
BHi+Mig at ``footprint=1<<18``, ``run_steps=8192``::

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # lane-sharded broker over four chips

The run fails (non-zero exit, no ``ok`` line) unless:

* every query of the burst is answered, with no retry, quarantine,
  flush failure or degraded bucket;
* the repeat burst is answered from the result cache with no compile;
* a second broker with an empty result cache (the warm flush) gives
  bit-identical results without compiling;
* the blocked engine equals the per-step reference on the benchmark
  machine: counters and placements exactly (whether cycles and their
  timelines are bitwise equal too is printed, not judged);
* the broker on the small verify machine equals the pure-Python oracle:
  counters and placements exactly, cycles within ``rtol=1e-5``.

With ``--four-chips`` only the lane-sharded phase runs: the grid as one
32-lane bucket with ``lane_sharding="auto"`` over four chips, against the
same lanes on one device, bit for bit.

Every line before the last is one JSON object naming the device it ran
on; the last line is ``{"ok": true, "device": {...}}``.  Without a TPU
the script exits non-zero before any work.  All work runs in this one
process: a child could not reach the chip this process holds.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

WORKLOADS = ("memcached", "redis", "btree", "hashjoin", "xsbench", "bfs")
FOOTPRINT = 1 << 18
RUN_STEPS = 8192
MAX_LANES = 32
# Per-step reference check: one workload's three policies.  btree has the
# shortest populate phase (8192 steps); 1024 run steps put AutoNUMA scan
# ticks and fast windows after it.
REF_WORKLOAD = "btree"
REF_RUN_STEPS = 1024
# Oracle check on the small verify machine (the pure-Python oracle runs
# on the host, so the machine and traces stay small).
SMALL_FOOTPRINT = 1 << 10
SMALL_RUN_STEPS = 128

PLACEMENT = ("data_node", "leaf_node", "mid_node", "top_node", "root_node")
EXACT_KEYS = ("l1_hits", "stlb_hits", "walks", "walk_mem_reads", "faults",
              "slow_allocs", "data_migrations", "demotions",
              "l4_mig_success", "l4_mig_already_dest", "l4_mig_in_dram",
              "l4_mig_sibling_guard", "l4_mig_lock_skip",
              "data_pages_dram", "data_pages_nvmm",
              "leaf_pages_dram", "leaf_pages_nvmm", "oom_killed", "oom_step")
CYCLE_KEYS = ("total_cycles", "walk_cycles", "stall_cycles",
              "data_mem_cycles", "fault_cycles", "migration_cycles")


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def tpu_device() -> dict:
    """The device as JAX reports it; exits non-zero unless it is a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devs[0].platform} ({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def report(device: dict, phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, "device": device, **fields},
                     default=float), flush=True)


def small_machine():
    """The small verify machine: 4 threads, 1,200 DRAM pages against a
    4,096-page address space, tiny TLBs."""
    from repro.core import MachineConfig
    return MachineConfig(n_threads=4, dram_pages_per_node=600,
                         nvmm_pages_per_node=2400, va_pages=1 << 12,
                         l1_tlb_sets=4, l1_tlb_ways=2, stlb_sets=8,
                         stlb_ways=4, pde_pwc_entries=4, pdpte_pwc_entries=2)


def fig9_queries(mc, footprint, run_steps, pad_to=0):
    from repro.core import TraceSpec, bhi, bhi_mig, linux_default
    from repro.service import SimQuery
    return [SimQuery(trace=TraceSpec(workload=w, footprint=footprint,
                                     run_steps=run_steps, pad_to=pad_to),
                     policy=pc, machine=mc)
            for w in WORKLOADS for pc in (linux_default(), bhi(), bhi_mig())]


def _seconds(tel, name: str) -> float:
    snap = tel.metrics.value(name)
    return float(snap["sum"]) if snap else 0.0


def served_burst(broker, queries):
    """Submit a burst, drain, and return each future's RunResult; fails
    the run on any future that holds an error."""
    futs = broker.submit_many(queries)
    broker.drain()
    results = []
    for i, f in enumerate(futs):
        try:
            results.append(f.result())
        except Exception as exc:  # noqa: BLE001 — reported as the failure
            raise SmokeFailure(f"query {i} failed: {exc!r}") from exc
    return futs, results


def split_leaves(res):
    """(exact, cycle) views of a RunResult: every final-state leaf and
    timeline that must match exactly, and the f32 cycle ones."""
    import jax
    from repro.core.sim import TIMELINE_KEYS
    exact, cycles = {}, {}
    flat, _ = jax.tree_util.tree_flatten_with_path(res.final_state)
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        (cycles if key.startswith(".cycles") else exact)[key] = leaf
    for k in TIMELINE_KEYS:
        (cycles if k.endswith("cycles") else exact)[f"timeline/{k}"] = \
            res.timeline[k]
    return exact, cycles


def compare(a, b, label: str):
    """Exact leaves must be equal (fails the run); returns whether the
    cycle leaves are bitwise equal and their largest relative gap."""
    import numpy as np
    ea, ca = split_leaves(a)
    eb, cb = split_leaves(b)
    for k in ea:
        check(np.array_equal(np.asarray(ea[k]), np.asarray(eb[k])),
              f"{label}: {k} differs")
    bitwise, worst = True, 0.0
    for k in ca:
        x = np.asarray(ca[k], np.float64)
        y = np.asarray(cb[k], np.float64)
        if not np.array_equal(x, y):
            bitwise = False
            worst = max(worst, float(np.max(np.abs(x - y)
                                            / np.maximum(np.abs(y), 1.0))))
    return bitwise, worst


def one_chip(device: dict, mc=None, footprint: int = FOOTPRINT,
             run_steps: int = RUN_STEPS,
             ref_run_steps: int = REF_RUN_STEPS) -> None:
    import jax
    import numpy as np
    from repro.core import (CostConfig, TraceSpec, benchmark_machine, bhi,
                            bhi_mig, linux_default, sweep_lanes)
    from repro.core.ref import OracleSim
    from repro.core.sweep import compile_count
    from repro.obs import Telemetry
    from repro.service import SimBroker

    mc = mc if mc is not None else benchmark_machine()
    queries = fig9_queries(mc, footprint, run_steps)

    # -- first burst: cold, through the broker -----------------------------
    tel = Telemetry()
    broker = SimBroker(max_lanes=MAX_LANES, telemetry=tel)
    t0 = time.perf_counter()
    for q in queries:
        broker.canonical_trace(q)             # memoized: submit reuses it
    trace_s = time.perf_counter() - t0
    c0 = compile_count()
    t0 = time.perf_counter()
    futs, results = served_burst(broker, queries)
    first_s = time.perf_counter() - t0
    st = broker.stats
    check(st.retries == 0, f"{st.retries} retries")
    check(st.quarantined == 0, f"{st.quarantined} quarantined")
    fails = tel.metrics.value("broker.flush_failures") or 0
    check(fails == 0, f"{fails} flush failures")
    check(not broker.degraded_buckets(),
          f"degraded buckets {broker.degraded_buckets()}")
    report(device, "first_burst", queries=len(queries),
           flushes=st.flushes, lanes_run=st.lanes_run,
           pad_lanes=st.pad_lanes, compiles=compile_count() - c0,
           trace_build_s=trace_s, burst_s=first_s,
           flush_s=_seconds(tel, "broker.flush_seconds"),
           host_prepare_s=_seconds(tel, "sweep.prepare_seconds"),
           device_s=_seconds(tel, "sweep.device_seconds"),
           readback_s=_seconds(tel, "sweep.readback_seconds"))

    # -- repeat burst: answered from the result cache ----------------------
    broker_compiles, c1 = st.compiles, compile_count()
    futs2, results2 = served_burst(broker, queries)
    check(all(f.from_cache for f in futs2), "repeat burst missed the cache")
    check(st.compiles == broker_compiles and compile_count() == c1,
          "repeat burst compiled")
    check(all(a is b for a, b in zip(results, results2)),
          "repeat burst returned other results")
    report(device, "repeat_burst", cache_hits=st.cache_hits,
           new_compiles=compile_count() - c1)

    # -- warm flush: an empty result cache, compiled programs in hand ------
    tel_w = Telemetry()
    warm = SimBroker(max_lanes=MAX_LANES, telemetry=tel_w)
    t0 = time.perf_counter()
    _, results_w = served_burst(warm, queries)
    warm_s = time.perf_counter() - t0
    check(compile_count() == c1, "warm flush compiled")
    for i, (a, b) in enumerate(zip(results, results_w)):
        bitwise, worst = compare(a, b, f"warm flush query {i}")
        check(bitwise, f"warm flush query {i}: cycles differ by {worst}")
    first_flush = _seconds(tel, "broker.flush_seconds")
    warm_flush = _seconds(tel_w, "broker.flush_seconds")
    mem = jax.devices()[0].memory_stats() or {}
    report(device, "warm_flush", burst_s=warm_s, flush_s=warm_flush,
           compile_s=first_flush - warm_flush,
           host_prepare_s=_seconds(tel_w, "sweep.prepare_seconds"),
           device_s=_seconds(tel_w, "sweep.device_seconds"),
           readback_s=_seconds(tel_w, "sweep.readback_seconds"),
           peak_bytes_in_use=mem.get("peak_bytes_in_use"),
           bytes_limit=mem.get("bytes_limit"))

    # -- (a) blocked engine vs the per-step reference, same inputs ---------
    pcs = [linux_default(), bhi(), bhi_mig()]
    ccs = [CostConfig()] * len(pcs)
    tr = TraceSpec(workload=REF_WORKLOAD, footprint=footprint,
                   run_steps=ref_run_steps).build(mc)
    t0 = time.perf_counter()
    blocked = sweep_lanes(mc, ccs, pcs, [tr] * len(pcs))
    blocked_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    per_step = sweep_lanes(mc, ccs, pcs, [tr] * len(pcs), engine="per_step",
                           debug=True)
    per_step_s = time.perf_counter() - t0
    gaps = [compare(b, p, f"blocked vs per-step {pc.label()}")
            for b, p, pc in zip(blocked, per_step, pcs)]
    report(device, "blocked_vs_per_step", workload=REF_WORKLOAD,
           steps=tr.n_steps, lanes=len(pcs), counters_placements_equal=True,
           cycles_bitwise=all(g[0] for g in gaps),
           cycles_max_rel_diff=max(g[1] for g in gaps),
           blocked_s=blocked_s, per_step_s=per_step_s)

    # -- (b) the broker on the small verify machine vs the oracle ----------
    small = small_machine()
    sq = fig9_queries(small, SMALL_FOOTPRINT, SMALL_RUN_STEPS)
    sbroker = SimBroker(max_lanes=MAX_LANES)
    _, sres = served_burst(sbroker, sq)
    t0 = time.perf_counter()
    worst = 0.0
    for q, res in zip(sq, sres):
        label = f"oracle {q.trace.workload}/{q.policy.label()}"
        oracle = OracleSim(small, q.cost, q.policy)
        oracle.run(sbroker.canonical_trace(q))
        ref, got = oracle.summary(), res.summary()
        for k in EXACT_KEYS:
            check(got[k] == ref[k], f"{label}: {k} {got[k]} != {ref[k]}")
        for k in PLACEMENT:
            check(np.array_equal(np.asarray(getattr(res.final_state, k)),
                                 getattr(oracle, k)), f"{label}: {k} differs")
        for k in CYCLE_KEYS:
            gap = abs(got[k] - ref[k]) / max(abs(ref[k]), 1e-30)
            check(gap <= 1e-5, f"{label}: {k} {got[k]} vs {ref[k]}")
            worst = max(worst, gap)
    report(device, "broker_vs_oracle", queries=len(sq),
           counters_placements_equal=True, cycles_max_rel_diff=worst,
           oracle_s=time.perf_counter() - t0)


def four_chips(device: dict, mc=None, footprint: int = FOOTPRINT,
               run_steps: int = RUN_STEPS) -> None:
    """The grid as one 32-lane bucket, lane-sharded over four chips with
    ``lane_sharding="auto"``, against the same lanes on one device."""
    from repro.core import TraceSpec, benchmark_machine
    from repro.core.sim import pow2ceil
    from repro.core.sweep import compile_count, lane_mesh
    from repro.service import SimBroker

    check(device["count"] == 4, f"--four-chips needs 4 devices, "
          f"found {device['count']}")
    mc = mc if mc is not None else benchmark_machine()
    # pad every trace to one step count so the grid shares one bucket
    pad = pow2ceil(max(TraceSpec(workload=w, footprint=footprint,
                                 run_steps=run_steps).build(mc).n_steps
                       for w in WORKLOADS))
    queries = fig9_queries(mc, footprint, run_steps, pad_to=pad)
    check(lane_mesh(MAX_LANES).devices.size == 4,
          "the lane mesh does not span four devices")
    runs = {}
    for name, sharding in (("sharded", "auto"), ("single", None)):
        broker = SimBroker(max_lanes=MAX_LANES, lane_sharding=sharding)
        c0 = compile_count()
        t0 = time.perf_counter()
        _, runs[name] = served_burst(broker, queries)
        st = broker.stats
        check(st.lanes_run + st.pad_lanes == MAX_LANES and st.flushes == 1,
              f"{name}: {st.flushes} flushes of "
              f"{st.lanes_run + st.pad_lanes} lanes, expected one of 32")
        check(st.retries == 0 and st.quarantined == 0,
              f"{name}: {st.retries} retries, {st.quarantined} quarantined")
        report(device, f"four_chips_{name}", lanes=MAX_LANES,
               queries=len(queries), steps=pad,
               compiles=compile_count() - c0,
               burst_s=time.perf_counter() - t0)
    for i, (a, b) in enumerate(zip(runs["sharded"], runs["single"])):
        bitwise, worst = compare(a, b, f"sharded vs single query {i}")
        check(bitwise, f"sharded vs single query {i}: cycles differ by "
              f"{worst}")
    report(device, "four_chips_identity", queries=len(queries),
           bitwise=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane-sharded phase over four chips")
    args = ap.parse_args(argv)
    device = tpu_device()
    from repro.compile_cache import enable_compile_cache
    cache = Path(enable_compile_cache())
    # a warm persistent cache shortens every compile figure printed below
    report(device, "setup", compile_cache=str(cache),
           cache_entries_at_start=len(list(cache.glob("*")))
           if cache.is_dir() else 0)
    if args.four_chips:
        four_chips(device)
    else:
        one_chip(device)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
