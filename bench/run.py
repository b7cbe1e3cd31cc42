"""One run of one benchmark cell on the served path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json`` and the files under ``bench/``.  A run:

1. checks that JAX sees a TPU with as many chips as the cell asks for, and
   exits non-zero with no result otherwise;
2. set-up: builds a ``SimBroker`` and answers one warm-up grid through it,
   which loads or compiles every program the cell's traffic uses;
3. the window: submits whole grids, each with fresh trace seeds, through
   ``SimBroker.submit_many`` and ``drain`` in a closed loop until
   ``--seconds`` have passed; the grid in flight finishes.  Any compile in
   the window fails the run;
4. reads the device's peak memory, frees the program's state and checks
   answers of one grid of the window, drawn from the seed, against the
   plain reference (``bench/compare.py``);
5. prints each compared number beside its limit as the last lines on
   standard error, and one JSON line as the last line on standard output.

With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` the broker reports into a ``repro.obs.Telemetry``, the
profiler traces the window, and the line carries the per-layer metrics,
which the readers in ``bench/metrics/`` take from the telemetry, the
benchmark's own spans and the trace, plus ``breakdown``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import compare, devtrace, generator, window  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """The benchmark cannot run here (no chip, a missing file)."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as exc:
        raise BenchError(f"cannot read {path}: {exc}") from exc


def find_cell(root: Path, name: str):
    """(benchmark spec, cell, configuration, traffic mix) of cell ``name``."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, cfg, traffic


def metric_readers(root: Path, spec: dict) -> dict:
    """{metric name: (read(ctx), unit)} of every per-layer metric, each
    loaded from ``bench/metrics/<name>.py``."""
    readers = {}
    for m in spec["per_layer"]:
        path = root / "bench" / "metrics" / f"{m['name']}.py"
        if not path.is_file():
            raise BenchError(f"no reader {path} for metric {m['name']}")
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        readers[m["name"]] = (mod.read, m["unit"])
    return readers


def chips(n: int, require_tpu: bool) -> dict:
    """The devices as JAX reports them; a BenchError unless they are at
    least ``n`` TPU chips."""
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < n):
        raise BenchError(f"the cell needs {n} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} "
                         f"({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def use_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, for every program however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


_COMPILES = []


def compiles_so_far() -> int:
    """XLA compilations in this process since the first call (persistent
    cache loads included)."""
    if not _COMPILES:
        import jax
        _COMPILES.append(0)

        def on_event(event, _secs, **_kw):
            if event == COMPILE_EVENT:
                _COMPILES[0] += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)
    return _COMPILES[0]


def error_of(fut):
    """The error an answered future holds, or None."""
    try:
        fut.result()
    except Exception as exc:  # noqa: BLE001 — any failure is the answer
        return exc
    return None


def _serve(broker, cfg, queries, annotate):
    """One grid: build its traces, submit it, drain.  Returns the record,
    the futures, each query's bucket step count and the trace-build
    seconds."""
    sq = [generator.sim_query(q, cfg) for q in queries]
    t0 = time.perf_counter()
    with annotate("bench.trace_build"):
        canon = [broker.canonical_trace(q) for q in sq]
    t_built = time.perf_counter()
    with annotate("bench.submit"):
        futs = broker.submit_many(sq)
    with annotate("bench.drain"):
        broker.drain()
    t_done = time.perf_counter()
    failed, acc = 0, 0
    for f, tr in zip(futs, canon):
        if error_of(f) is None:
            acc += window.accesses(tr.va)
        else:
            failed += 1
    rec = window.GridRecord(t_submit=t0, t_done=t_done, queries=len(sq),
                            failed=failed, accesses=acc)
    return rec, futs, [tr.n_steps for tr in canon], t_built - t0


def check_sample(steps, max_lanes: int, rng) -> list:
    """Indices of a grid's queries to check: from every flush, one lane
    drawn from its first half and one from its second half, so the
    longest bucket and both halves of each batch are always covered."""
    buckets = {}
    for i, s in enumerate(steps):
        buckets.setdefault(s, []).append(i)
    picks = []
    for lanes in buckets.values():
        for k in range(0, len(lanes), max_lanes):
            flush = lanes[k:k + max_lanes]
            half = (len(flush) + 1) // 2
            picks.append(flush[rng.integers(half)])
            if len(flush) > 1:
                picks.append(flush[half + rng.integers(len(flush) - half)])
    return sorted(picks)


def peak_bytes() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True):
    """One run of cell ``name``.  Returns the result line, the checks as
    (name, value, limit, ok), and up to 20 names of mismatched leaves."""
    spec, cell, cfg, traffic = find_cell(root, name)
    readers = metric_readers(root, spec) if trace else {}
    device = chips(int(cell["chips"]), require_tpu)

    import jax
    from repro.core.sim import DEFAULT_BLOCK
    from repro.core.sweep import compile_count
    from repro.obs import Telemetry
    from repro.service import SimBroker
    if DEFAULT_BLOCK != cfg["block"]:
        raise BenchError(f"the program's window is {DEFAULT_BLOCK} steps; "
                         f"the configuration states {cfg['block']}")
    use_compile_cache(root)
    compiles_so_far()
    annotate = jax.profiler.TraceAnnotation

    tel = Telemetry() if trace else None
    broker = SimBroker(
        max_lanes=int(cfg["max_lanes"]), telemetry=tel,
        lane_sharding="auto" if int(cell["chips"]) > 1 else None)

    # -- set-up: one warm-up grid loads or compiles every program ---------
    warm, wfuts, _, _ = _serve(broker, cfg,
                               generator.grid(cfg, traffic, seed, 0),
                               annotate)
    if warm.failed:
        err = next(e for e in map(error_of, wfuts) if e is not None)
        raise RuntimeError(f"warm-up grid failed: {err!r}") from err
    del wfuts
    setup_s = time.perf_counter() - t_start

    # -- the window --------------------------------------------------------
    if tel is not None:
        tel.reset()
    trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace \
        else None
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    c0, sweep_c0 = compiles_so_far(), compile_count()
    grids, served, trace_build_s = [], [], 0.0
    while True:
        queries = generator.grid(cfg, traffic, seed, len(grids) + 1)
        with annotate("bench.grid"):
            rec, futs, steps, tb = _serve(broker, cfg, queries, annotate)
        grids.append(rec)
        served.append((queries, futs, steps))
        trace_build_s += tb
        if rec.t_done - grids[0].t_submit >= seconds:
            break
    window_compiles = max(compiles_so_far() - c0,
                          compile_count() - sweep_c0)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    mem_peak = peak_bytes()
    window_s = window.window_seconds(grids)

    # -- per-layer metrics (traced run) -----------------------------------
    metrics, breakdown = {}, None
    if trace:
        red = None
        xplanes = sorted(trace_dir.rglob("*.xplane.pb"))
        if xplanes:
            red = devtrace.reduce_trace(**devtrace.read_xplane(xplanes[0]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"window_s": window_s, "trace_build_s": trace_build_s,
               "telemetry": tel.metrics.snapshot(), "trace": red}
        for mname, (read, unit) in readers.items():
            v = read(ctx)
            if v is not None:
                metrics[mname] = {"value": v, "unit": unit}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
    else:
        metrics = {
            "sim_accesses_per_s": {"value": window.sim_accesses_per_s(grids),
                                   "unit": "accesses/s"},
            "query_p90_s": {"value": window.query_p90_s(grids), "unit": "s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    device["memory_peak_bytes"] = mem_peak

    # -- correctness: one grid of the window against the reference --------
    rng = np.random.default_rng([seed % 2 ** 64, 1])
    queries, futs, steps = served[int(rng.integers(len(served)))]
    picks = check_sample(steps, int(cfg["max_lanes"]), rng)
    answers = [(queries[i], futs[i]) for i in picks]
    del served, broker, futs
    gc.collect()
    mismatched, gap = [], 0.0
    for q, f in answers:
        err = error_of(f)
        if err is not None:
            mismatched.append(f"{q.workload}/{q.preset}: {err!r}")
            continue
        ref = generator.reference_run(q, cfg)
        m, g = compare.compare(compare.program_leaves(f.result()),
                               compare.reference_leaves(ref))
        mismatched += [f"{q.workload}/{q.preset}: {k}" for k in m]
        gap = max(gap, g)
    attempted = sum(g.queries for g in grids)
    failed = sum(g.failed for g in grids)
    checks = [
        ("failed_queries", failed, 0, failed == 0),
        ("window_compiles", window_compiles, 0, window_compiles == 0),
        ("checked_queries", len(answers), 1, len(answers) >= 1),
        ("mismatched_leaves", len(mismatched),
         compare.LIMITS["mismatched_leaves"],
         len(mismatched) <= compare.LIMITS["mismatched_leaves"]),
        ("cycles_gap", gap, compare.LIMITS["cycles_gap"],
         gap <= compare.LIMITS["cycles_gap"]),
    ]
    result = {
        "correct": all(ok for *_, ok in checks),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim, _ in checks}
    return result, checks, mismatched[:20]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, checks, mismatched = run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            T_START)
    except BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    for line in mismatched:
        print(f"mismatch: {line}", file=sys.stderr)
    for n, v, lim, ok in checks:
        print(f"check {n} = {v!r} limit {lim!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0 if result["checks"]["window_compiles"]["value"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
