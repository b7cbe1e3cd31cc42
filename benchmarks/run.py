"""Benchmark entry point: one module per paper table/figure + the Pillar-B
serving benchmark + the roofline table + the service-layer drivers.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig9,fig10]

Prints ``name,seconds,derived`` CSV rows (as the harness skeleton asks) and
writes JSON artifacts under artifacts/bench/.  ``--help`` lists every
registered figure; an unknown ``--only`` target is an error, not a silent
no-op.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

# name -> (module basename[:entry function], one-line description); import
# is deferred so --help and --only validation stay instant.  The entry
# function defaults to ``main`` and takes ``quick: bool``.
FIGURES = {
    "fig1": ("fig1_startup", "startup/populate-phase cost breakdown"),
    "fig5": ("fig5_ptdist", "PT-page NUMA distribution"),
    "fig6": ("fig6_walklat", "page-walk latency by PT placement"),
    "fig7": ("fig7_bind", "bind-all OOM pathology vs BHi"),
    "fig9": ("fig9_fullsystem", "full-system policy comparison"),
    "fig10": ("fig10_multitenant", "multi-tenant fill-and-free scenario"),
    "fig11": ("fig11_interleave", "interleaved data placement"),
    "fig13": ("fig13_thp", "transparent huge pages"),
    "table4": ("table4_summary", "headline geomean summary vs paper"),
    "kv_tiering": ("kv_tiering", "tiered paged-KV serving benchmark"),
    "roofline": ("roofline", "roofline over dry-run artifacts"),
    "fault_batch": ("fault_batch", "batched fault-engine micro-benchmark"),
    "steady_state": ("steady_state",
                     "time-blocked steady-state stepper micro-benchmark"),
    "cost_sweep": ("cost_sweep", "CXL what-if NVMM latency-ratio sweep"),
    "scenario_matrix": ("cost_sweep:scenario_main",
                        "policy family x tier topology x latency ratio x "
                        "workload matrix through the broker"),
    "service_throughput": ("service_throughput",
                           "query-broker throughput vs naive execution"),
    "service_chaos": ("service_throughput:chaos_main",
                      "broker under a 1% injected device-fault rate; "
                      "gates on zero stranded futures"),
}


def main() -> None:
    figure_list = "\n".join(f"  {n:<20} {d}"
                            for n, (_, d) in FIGURES.items())
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog=f"registered figures:\n{figure_list}")
    ap.add_argument("--quick", action="store_true",
                    help="2 workloads, short traces (CI-scale)")
    ap.add_argument("--only", default=None,
                    help="comma-separated figure subset, e.g. fig9,table4 "
                         "(see the registered list below)")
    ap.add_argument("--verbose", action="store_true",
                    help="print each driver's telemetry snapshot (metrics "
                         "registry + trace counts) after it finishes")
    args = ap.parse_args()

    names = list(FIGURES)
    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        unknown = [n for n in names if n not in FIGURES]
        if unknown:
            ap.error(f"unknown --only target(s) {', '.join(unknown)}; "
                     f"registered: {', '.join(FIGURES)}")

    import importlib
    import json

    from repro.compile_cache import enable_compile_cache

    from . import common

    enable_compile_cache()

    print("name,seconds,derived", flush=True)
    failures = []
    drivers: dict = {}
    suite_t0 = time.time()
    for name in names:
        target = FIGURES[name][0]
        modname, _, func = target.partition(":")
        mod = importlib.import_module(f"benchmarks.{modname}")
        # scope the shared telemetry to this driver so --verbose (and any
        # snapshot the driver embeds) reads one driver's worth of data
        common.telemetry().reset()
        common.begin_driver(name)
        t0 = time.time()
        try:
            getattr(mod, func or "main")(quick=args.quick)
            drivers[name] = {"seconds": time.time() - t0, "status": "ok"}
            print(f"{name}/done,{time.time() - t0:.1f},ok", flush=True)
        except Exception as e:  # noqa: BLE001 — report, keep going
            failures.append(name)
            traceback.print_exc()
            drivers[name] = {"seconds": time.time() - t0,
                             "status": "failed",
                             "error": f"{type(e).__name__}: {e}"}
            print(f"{name}/done,{time.time() - t0:.1f},"
                  f"FAILED:{type(e).__name__}", flush=True)
        if args.verbose:
            snap = common.telemetry().snapshot()
            print(f"# telemetry[{name}] "
                  f"{json.dumps(snap, sort_keys=True, default=float)}",
                  flush=True)
    ok = len(names) - len(failures)
    # the per-invocation run manifest: which drivers ran under which run
    # id, each one's wall clock and exit status, and the failure summary
    manifest = {
        "schema": "run-manifest/v1",
        "run_id": common.run_id(),
        "quick": bool(args.quick),
        "only": names,
        "started": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                 time.gmtime(suite_t0)),
        "wall_seconds": time.time() - suite_t0,
        "drivers": drivers,
        "failures": failures,
    }
    common.ART.mkdir(parents=True, exist_ok=True)
    (common.ART / "run_manifest.json").write_text(
        json.dumps(manifest, indent=1, default=float))
    print(f"# summary: {ok}/{len(names)} drivers ok"
          + (f"; FAILED: {', '.join(failures)}" if failures else ""),
          flush=True)
    if failures:
        print(f"benchmark drivers failed: {', '.join(failures)}",
              file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
