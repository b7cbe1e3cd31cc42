"""The readings that the limits in ``compare.LIMITS`` are set from.

    python3 bench/readings.py --workload <cell> --seeds 12 --control-seeds 3

In one process on the cell's chip: one warm-up grid, then for each seed
one grid served through the broker as a run serves it, checked as a run
checks it (the same sample, the same comparison).  The largest numbers
over the seeds are the program's lower readings.  Then the control: the
plain reference computed with bfloat16 cycles, one precision below the
configuration's float32, put in the program's place for the same sampled
queries.  Its smallest ``cycles_gap`` over the seeds is the upper
reading.  The benchmark's own runs never run this.  One JSON line per
reading, then a summary line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

import numpy as np  # noqa: E402

from bench import compare, generator, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 101)
    args = ap.parse_args(argv)
    import ml_dtypes
    import jax

    root = run.ROOT
    _, cell, cfg, traffic = run.find_cell(root, args.workload)
    run.chips(int(cell["chips"]), require_tpu=True)
    run.use_compile_cache(root)
    from repro.service import SimBroker
    broker = SimBroker(max_lanes=int(cfg["max_lanes"]),
                       lane_sharding="auto" if int(cell["chips"]) > 1
                       else None)
    annotate = jax.profiler.TraceAnnotation
    run._serve(broker, cfg, generator.grid(cfg, traffic, args.first_seed, 0),
               annotate)
    worst = {"mismatched_leaves": 0, "cycles_gap": 0.0}
    control_gaps = []
    for k in range(args.seeds):
        seed = args.first_seed + k
        queries = generator.grid(cfg, traffic, seed, 1)
        rec, futs, steps, _ = run._serve(broker, cfg, queries, annotate)
        picks = run.check_sample(steps, int(cfg["max_lanes"]),
                                 np.random.default_rng(seed % 2 ** 64))
        for i in picks:
            q, t0 = queries[i], time.perf_counter()
            ref = generator.reference_run(q, cfg)
            ref_s = time.perf_counter() - t0
            err = run.error_of(futs[i])
            m, g = (["error"], float("inf")) if err is not None else \
                compare.compare(compare.program_leaves(futs[i].result()),
                                compare.reference_leaves(ref))
            line = {"side": "program", "seed": seed, "query": i,
                    "workload": q.workload, "policy": q.preset,
                    "steps": steps[i], "mismatched_leaves": len(m),
                    "mismatched": m[:5], "cycles_gap": g,
                    "grid_s": rec.t_done - rec.t_submit,
                    "reference_s": ref_s}
            worst["mismatched_leaves"] = max(worst["mismatched_leaves"],
                                             len(m))
            worst["cycles_gap"] = max(worst["cycles_gap"], g)
            if k < args.control_seeds:
                ctl = generator.reference_run(
                    q, cfg, cycle_dtype=ml_dtypes.bfloat16)
                cm, cg = compare.compare(compare.reference_leaves(ctl),
                                         compare.reference_leaves(ref))
                line.update(control_mismatched_leaves=len(cm),
                            control_cycles_gap=cg)
                control_gaps.append(cg)
            print(json.dumps(line), flush=True)
    print(json.dumps({"summary": args.workload, "seeds": args.seeds,
                      "program_worst": worst,
                      "control_min_cycles_gap": min(control_gaps)
                      if control_gaps else None,
                      "limits": compare.LIMITS}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
