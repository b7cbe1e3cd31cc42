"""The one traffic generator: a configuration and a traffic mix, both data
files, become grids of queries.

A mix names its ``workloads`` (trace generators of paper Table 2), its
``policies`` (each a preset of paper Table 3 or a tiering family) and
``run_steps``.  A grid is the cross product workload x policy, in that
order, with one trace per workload.
Every grid draws fresh trace seeds from the run's seed and its own index,
so no grid repeats another's queries and the result cache never answers.
The trace shapes, and with them the work of a grid, do not depend on the
seed.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from bench.reference import oracle, traces


@dataclasses.dataclass(frozen=True)
class Query:
    """One request as the benchmark describes it to both sides."""

    workload: str
    trace_seed: int
    preset: str
    footprint: int
    run_steps: int

    def policy_fields(self, cfg) -> dict:
        return {"autonuma_period": cfg["autonuma_period"]}


def trace_seed(seed: int, grid: int, workload: int) -> int:
    """A 32-bit trace seed drawn from the run's seed (any whole number)."""
    ss = np.random.SeedSequence([seed % 2 ** 64, grid, workload, 0])
    return int(ss.generate_state(1, np.uint32)[0])


def grid(cfg: dict, traffic: dict, seed: int, index: int) -> List[Query]:
    """The queries of grid ``index`` (0 is the warm-up grid)."""
    out = []
    for w, workload in enumerate(traffic["workloads"]):
        ts = trace_seed(seed, index, w)
        for pol in traffic["policies"]:
            out.append(Query(workload=workload, trace_seed=ts,
                             preset=pol["preset"],
                             footprint=int(cfg["footprint"]),
                             run_steps=int(traffic["run_steps"])))
    return out


def sim_query(q: Query, cfg: dict):
    """The program's ``SimQuery`` for ``q``."""
    import repro.core as rc
    from repro.service import SimQuery
    machine = {f.name: cfg[f.name]
               for f in dataclasses.fields(rc.MachineConfig) if f.name in cfg}
    if "tier_pages_per_node" in machine:
        machine["tier_pages_per_node"] = tuple(machine["tier_pages_per_node"])
    policy = dataclasses.replace(getattr(rc, q.preset)(),
                                 **q.policy_fields(cfg))
    return SimQuery(
        trace=rc.TraceSpec(workload=q.workload, footprint=q.footprint,
                           run_steps=q.run_steps, seed=q.trace_seed),
        policy=policy, cost=rc.CostConfig(**cfg["cost"]),
        machine=rc.MachineConfig(**machine))


def reference_run(q: Query, cfg: dict, cycle_dtype=np.float32):
    """The plain reference's answer to ``q``.  A served query runs over its
    trace idle-padded to the next power of two of at least 64 steps, the
    bucket it shares with queries of similar length."""
    mc = oracle.Machine.from_config(cfg)
    tr = traces.build(q.workload, mc.n_threads, mc.va_pages, q.footprint,
                      q.run_steps, q.trace_seed)
    tr = traces.pad(tr, traces.pow2_steps(tr.n_steps))
    pc = oracle.policy(q.preset, **q.policy_fields(cfg))
    return oracle.RefSim(mc, cfg["cost"], pc, cycle_dtype).run(tr)
