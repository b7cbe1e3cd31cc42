"""The three-tier cell's path on the CPU: a tiny copy of ``cxl-3tier``
served with the ``family-steady`` policies comes out correct and runs
fast and hoist windows; its TPP, Nomad and AutoNUMA lanes act and differ;
and Nomad lanes answered by the TPP preset come out not correct."""
import dataclasses
import json
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from bench import generator, run

REPO = Path(__file__).resolve().parents[2]
CELL = "tiny3.steady"


@pytest.fixture(scope="module")
def tiny3_root(tmp_path_factory):
    """A checkout holding one tiny three-tier cell: 4 threads, tiers
    384 / 192 / 1,600 pages a node against a 2,048-page footprint (the
    cell's RSS of 2.67x DRAM and 1.78x DRAM + CXL), one 16-lane flush of
    1,024 steps with a scan tick every other 64-step window."""
    root = tmp_path_factory.mktemp("tiny3")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    base = next(c for c in spec["configs"] if c["name"] == "cxl-3tier")
    cell = next(w for w in spec["workloads"]
                if w["name"] == "cxl-3tier.family-steady")
    spec["configs"] = [dict(base, name="tiny3",
                            file="bench/configs/tiny3.json")]
    spec["workloads"] = [dict(cell, name=CELL, config="tiny3",
                              traffic="steady")]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    shutil.copytree(REPO / "bench" / "metrics", root / "bench" / "metrics")
    cfg = json.loads((REPO / "bench/configs/cxl-3tier.json").read_text())
    cfg.update(n_threads=4, tier_pages_per_node=[384, 192, 1600],
               va_pages=1 << 12, l1_tlb_sets=4, l1_tlb_ways=2, stlb_sets=8,
               stlb_ways=4, pde_pwc_entries=4, pdpte_pwc_entries=2,
               footprint=1 << 11, autonuma_period=128, max_lanes=16)
    (root / "bench/configs/tiny3.json").write_text(json.dumps(cfg))
    traffic = json.loads(
        (REPO / "bench/traffic/family-steady.json").read_text())
    traffic.update(run_steps=256)
    (root / "bench/traffic/steady.json").write_text(json.dumps(traffic))
    return root


def run_tiny3(root, mp, seed):
    """One run of the tiny cell, with ``run.use_compile_cache`` off (the
    test process keeps JAX's global settings).  Returns the result, the
    checks by name, the window plans of every flush and every served
    lane as (``PolicyConfig``, trace name, ``RunResult``)."""
    import importlib
    import repro.service.broker as broker_mod
    sweep_mod = importlib.import_module("repro.core.sweep")
    plans, lanes = [], []
    real_plan, real_sweep = sweep_mod.plan_windows, broker_mod.sweep_lanes

    def spy_plan(*a, **kw):
        plans.append(real_plan(*a, **kw))
        return plans[-1]

    def spy_sweep(mc, ccs, pcs, traces, **kw):
        out = real_sweep(mc, ccs, pcs, traces, **kw)
        lanes.extend(zip(pcs, [t.name for t in traces], out))
        return out

    mp.setattr(run, "use_compile_cache", lambda root: None)
    mp.setattr(sweep_mod, "plan_windows", spy_plan)
    mp.setattr(broker_mod, "sweep_lanes", spy_sweep)
    result, checks, _ = run.run_cell(root, CELL, seed, 0.0, False,
                                     time.perf_counter(), require_tpu=False)
    return result, {n: (v, ok) for n, v, _, ok in checks}, plans, lanes


SEED = 2 ** 31 + 29


@pytest.fixture(scope="module")
def served(tiny3_root):
    with pytest.MonkeyPatch.context() as mp:
        return run_tiny3(tiny3_root, mp, SEED)


def test_three_tier_cell_is_correct_on_fast_and_hoist_windows(served):
    result, checks, plans, _ = served
    assert result["correct"], checks
    assert result["attempted"] == 16 and result["failed"] == 0
    assert checks["checked_queries"][0] == 2
    # one flush of the warm-up grid and one of the window
    assert len(plans) == 2
    for plan in plans:
        n_fast, _, n_hoist, _ = plan.counts
        assert n_fast > 0 and n_hoist > 0
        assert plan.scan_ticks[0] == n_hoist


def test_tiering_families_act_and_differ(served):
    """On each trace the AutoNUMA, TPP and Nomad lanes end in different
    placements or counters, and pages move between tiers."""
    import repro.core as rc
    _, _, _, lanes = served
    fam = {rc.MIG_AUTONUMA: "autonuma", rc.MIG_TPP: "tpp",
           rc.MIG_NOMAD: "nomad"}
    by_trace = {}
    for pc, trace, res in lanes[16:]:           # the window's grid
        if not bool(pc.mig):                    # BHi+Mig is Algorithm 1
            by_trace.setdefault(trace, {})[fam[int(pc.mig_policy)]] = res
    assert len(by_trace) == 4
    moved = 0
    for trace, res in by_trace.items():
        assert set(res) == {"autonuma", "tpp", "nomad"}, trace
        for a, b in (("autonuma", "tpp"), ("autonuma", "nomad"),
                     ("tpp", "nomad")):
            sa, sb = res[a].summary(), res[b].summary()
            differ = sa != sb or not np.array_equal(
                res[a].final_state.data_node, res[b].final_state.data_node)
            assert differ, f"{trace}: {a} and {b} answer alike"
        for r in res.values():
            s = r.summary()
            moved += s["data_migrations"] + s["demotions"] \
                + s["nomad_flip_demotions"]
    assert moved > 0


def _checked_presets(cfg, traffic, seed):
    """The presets of the queries a run with ``--seconds 0`` checks: the
    window is one grid, sampled as ``run.run_cell`` samples it."""
    queries = generator.grid(cfg, traffic, seed, 1)
    rng = np.random.default_rng([seed % 2 ** 64, 1])
    rng.integers(1)                             # the one grid of the window
    steps = [1024] * len(queries)
    return [queries[i].preset
            for i in run.check_sample(steps, int(cfg["max_lanes"]), rng)]


def test_nomad_lanes_answered_as_tpp_are_incorrect(tiny3_root):
    cfg = json.loads((tiny3_root / "bench/configs/tiny3.json").read_text())
    traffic = json.loads(
        (tiny3_root / "bench/traffic/steady.json").read_text())
    # a seed whose sample checks a Nomad lane
    seed = next(s for s in range(SEED, SEED + 64)
                if "nomad" in _checked_presets(cfg, traffic, s))
    real = generator.sim_query

    def tpp_for_nomad(q, cfg):
        if q.preset == "nomad":
            q = dataclasses.replace(q, preset="tpp")
        return real(q, cfg)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(generator, "sim_query", tpp_for_nomad)
        result, checks, _, _ = run_tiny3(tiny3_root, mp, seed)
    assert checks["mismatched_leaves"][0] > 0
    assert not checks["mismatched_leaves"][1]
    assert not result["correct"]
