"""Batched policy-sweep engine vs sequential runs vs the pure-Python oracle.

The vmap refactor must not change semantics: every lane of a ``sweep()``
must be bit-identical (placement arrays, counters) to the corresponding
independent ``TieredMemSimulator`` run and to the ``core.ref`` oracle,
with cycle totals matching to float32 rounding.  The whole sweep must also
compile exactly once per trace shape.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import (CostConfig, MachineConfig, PolicyConfig,
                        TieredMemSimulator, Trace, init_state, pad_trace,
                        sweep, sweep_compile_count, sweep_lanes, FIRST_TOUCH,
                        INTERLEAVE, PT_BIND_ALL, PT_BIND_HIGH, PT_FOLLOW_DATA)
from repro.core.ref import OracleSim

EXACT_KEYS = ("l1_hits", "stlb_hits", "walks", "walk_mem_reads", "faults",
              "slow_allocs", "data_migrations", "demotions",
              "l4_mig_success", "l4_mig_already_dest", "l4_mig_in_dram",
              "l4_mig_sibling_guard", "l4_mig_lock_skip",
              "data_pages_dram", "data_pages_nvmm",
              "leaf_pages_dram", "leaf_pages_nvmm", "oom_killed", "oom_step")
CYCLE_KEYS = ("total_cycles", "walk_cycles", "stall_cycles",
              "data_mem_cycles", "fault_cycles", "migration_cycles")
PLACEMENT_ARRAYS = ("data_node", "leaf_node", "mid_node", "top_node",
                    "root_node", "leaf_dram_children", "node_free")

# The issue's sweep set: {first-touch, interleave} x {follow_data, BHi},
# plus Mig and the bind-all pathology.
POLICIES = [
    PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                 autonuma=True, autonuma_period=16, autonuma_budget=32),
    PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_BIND_HIGH, mig=True,
                 autonuma=True, autonuma_period=16, autonuma_budget=32),
    PolicyConfig(data_policy=INTERLEAVE, pt_policy=PT_FOLLOW_DATA,
                 autonuma=False),
    PolicyConfig(data_policy=INTERLEAVE, pt_policy=PT_BIND_HIGH,
                 autonuma=True, autonuma_period=16, autonuma_budget=16),
]


def tiny_machine():
    return MachineConfig(n_threads=4, dram_pages_per_node=600,
                         nvmm_pages_per_node=2400, va_pages=1 << 12,
                         l1_tlb_sets=4, l1_tlb_ways=2, stlb_sets=8,
                         stlb_ways=4, pde_pwc_entries=4, pdpte_pwc_entries=2)


def random_trace(mc, steps=160, seed=0, free_at=None, name="rand"):
    rng = np.random.default_rng(seed)
    T = mc.n_threads
    va = np.where(rng.random((steps, T)) < 0.5,
                  rng.integers(0, mc.va_pages // 2, (steps, T)),
                  rng.integers(0, mc.va_pages, (steps, T))).astype(np.int32)
    va[rng.random((steps, T)) < 0.05] = -1
    wr = rng.random((steps, T)) < 0.3
    free_seg = np.full((steps,), -1, np.int32)
    if free_at is not None:
        free_seg[free_at] = 0
    seg = np.zeros((mc.n_map,), np.int32)
    seg[mc.n_map // 2:] = 1
    llc = np.full((steps,), 0.4, np.float32)
    return Trace(va=va, is_write=wr, free_seg=free_seg, llc=llc,
                 seg_of_map=seg, name=name)


def assert_lane_matches_sequential(res, seq):
    s1, s2 = res.summary(), seq.summary()
    for k in EXACT_KEYS:
        assert s1[k] == s2[k], f"{res.policy_label}: {k}: {s1[k]} != {s2[k]}"
    for arr in PLACEMENT_ARRAYS:
        np.testing.assert_array_equal(
            np.asarray(getattr(res.final_state, arr)),
            np.asarray(getattr(seq.final_state, arr)),
            err_msg=f"{res.policy_label}: {arr}")
    for k in CYCLE_KEYS:
        np.testing.assert_allclose(s1[k], s2[k], rtol=1e-6,
                                   err_msg=f"{res.policy_label}: {k}")
    for k in res.timeline:
        np.testing.assert_allclose(res.timeline[k], seq.timeline[k],
                                   rtol=1e-6,
                                   err_msg=f"{res.policy_label}: tl/{k}")


def assert_lane_matches_oracle(res, mc, cc, pc, trace):
    oracle = OracleSim(mc, cc, pc)
    oracle.run(trace)
    ref = oracle.summary()
    s = res.summary()
    for k in EXACT_KEYS:
        assert s[k] == ref[k], \
            f"{pc.label()}: oracle {k}: {s[k]} != {ref[k]}"
    for k in CYCLE_KEYS:
        np.testing.assert_allclose(s[k], ref[k], rtol=1e-5,
                                   err_msg=f"{pc.label()}: oracle {k}")


def test_sweep_matches_sequential_and_oracle():
    """One batched sweep == 4 independent runs == 4 oracle runs."""
    mc = tiny_machine()
    cc = CostConfig()
    trace = random_trace(mc, seed=3, free_at=100)

    batch = sweep(mc, cc, POLICIES, trace)
    assert len(batch) == len(POLICIES)
    for pc, res in zip(POLICIES, batch):
        seq = TieredMemSimulator(mc=mc, cc=cc, pc=pc).run(trace)
        assert_lane_matches_sequential(res, seq)
        assert_lane_matches_oracle(res, mc, cc, pc, trace)


def test_sweep_single_compile_per_trace_shape():
    """A >=4-policy sweep costs exactly one lax.scan compilation, and
    re-sweeping the same shape (other policies, other trace data) costs
    zero more.  The time-blocked engine tiles steps into fixed windows,
    so shapes quantize at window granularity: a step count landing in the
    same window count reuses the program for free, while one that adds a
    window compiles exactly once more."""
    mc = tiny_machine()
    cc = CostConfig()
    trace = random_trace(mc, seed=11, steps=96)

    before = sweep_compile_count()
    sweep(mc, cc, POLICIES, trace)
    after_first = sweep_compile_count()
    assert after_first == before + 1

    # same shape, different policy bundles and different trace content
    reordered = list(reversed(POLICIES))
    sweep(mc, cc, reordered, random_trace(mc, seed=12, steps=96))
    assert sweep_compile_count() == after_first

    # 96 and 128 steps both tile to two 64-step windows: free reuse
    sweep(mc, cc, POLICIES, random_trace(mc, seed=13, steps=128))
    assert sweep_compile_count() == after_first

    # a window count not seen before (5 windows — 3 was compiled by an
    # earlier test in this module) is a genuinely new shape: exactly one
    # more compile
    sweep(mc, cc, POLICIES, random_trace(mc, seed=14, steps=320))
    assert sweep_compile_count() == after_first + 1


def test_sweep_multi_trace_grid():
    """Policies x padded traces in one scan, including a mid-run free."""
    mc = tiny_machine()
    cc = CostConfig()
    policies = POLICIES[:2]
    traces = [random_trace(mc, seed=21, steps=120, name="a"),
              random_trace(mc, seed=22, steps=96, free_at=60, name="b")]
    steps = max(t.n_steps for t in traces)
    traces = [pad_trace(t, steps) for t in traces]

    grid = sweep(mc, cc, policies, traces)
    assert len(grid) == len(traces) and len(grid[0]) == len(policies)
    for trace, row in zip(traces, grid):
        for pc, res in zip(policies, row):
            assert res.trace_name == trace.name
            seq = TieredMemSimulator(mc=mc, cc=cc, pc=pc).run(trace)
            assert_lane_matches_sequential(res, seq)


def sequential_trace(mc, steps, name="seq"):
    """Sequential heap growth: every step maps new pages (and, with a small
    radix, keeps demanding new PT pages long after DRAM has filled)."""
    T = mc.n_threads
    s = np.arange(steps, dtype=np.int32)[:, None]
    t = np.arange(T, dtype=np.int32)[None, :]
    va = np.minimum(s * T + t, mc.va_pages - 1).astype(np.int32)
    return Trace(va=va, is_write=np.ones((steps, T), bool),
                 free_seg=np.full((steps,), -1, np.int32),
                 llc=np.full((steps,), 0.3, np.float32),
                 seg_of_map=np.zeros((mc.n_map,), np.int32), name=name)


def test_sweep_bind_all_oom_lane():
    """An OOM-ing bind-all lane must not perturb its sweep neighbours."""
    mc = MachineConfig(n_threads=4, dram_pages_per_node=150,
                       nvmm_pages_per_node=1600, va_pages=1 << 11,
                       radix_bits=4,
                       l1_tlb_sets=4, l1_tlb_ways=2, stlb_sets=8,
                       stlb_ways=4, pde_pwc_entries=4, pdpte_pwc_entries=2)
    cc = CostConfig()
    policies = [
        PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                     autonuma=False),
        PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_BIND_ALL,
                     autonuma=False),
        PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_BIND_HIGH,
                     autonuma=False),
    ]
    trace = sequential_trace(mc, steps=256)
    batch = sweep(mc, cc, policies, trace)
    assert batch[1].summary()["oom_killed"], \
        "bind-all should OOM under memory pressure (paper fig. 7)"
    for pc, res in zip(policies, batch):
        seq = TieredMemSimulator(mc=mc, cc=cc, pc=pc).run(trace)
        assert_lane_matches_sequential(res, seq)
        assert_lane_matches_oracle(res, mc, cc, pc, trace)


def test_sweep_thp_machine():
    """fig13's setting: THP machine (3-level walks, PMD leaves)."""
    mc = MachineConfig(n_threads=4, dram_pages_per_node=600,
                       nvmm_pages_per_node=2400, va_pages=1 << 12,
                       page_order=9,
                       l1_tlb_sets=4, l1_tlb_ways=2, stlb_sets=8,
                       stlb_ways=4, pde_pwc_entries=4, pdpte_pwc_entries=2)
    cc = CostConfig()
    policies = [POLICIES[0], POLICIES[1]]
    trace = random_trace(mc, seed=51)
    for pc, res in zip(policies, sweep(mc, cc, policies, trace)):
        seq = TieredMemSimulator(mc=mc, cc=cc, pc=pc).run(trace)
        assert_lane_matches_sequential(res, seq)
        assert_lane_matches_oracle(res, mc, cc, pc, trace)


def test_sweep_rejects_mixed_periods_and_shapes():
    mc = tiny_machine()
    cc = CostConfig()
    tr = random_trace(mc, seed=41, steps=64)
    mixed = [PolicyConfig(autonuma=True, autonuma_period=16),
             PolicyConfig(autonuma=True, autonuma_period=32)]
    with pytest.raises(ValueError, match="autonuma_period"):
        sweep(mc, cc, mixed, tr)
    with pytest.raises(ValueError, match="shape"):
        sweep(mc, cc, POLICIES, [tr, random_trace(mc, seed=42, steps=65)])


def assert_runs_bitwise(a, b, what):
    """Every timeline array and every final-state leaf, bit for bit."""
    assert sorted(a.timeline) == sorted(b.timeline), what
    for k in a.timeline:
        np.testing.assert_array_equal(a.timeline[k], b.timeline[k],
                                      err_msg=f"{what}: tl/{k}")
    la, lb = jax.tree.leaves(a.final_state), jax.tree.leaves(b.final_state)
    assert len(la) == len(lb), what
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{what}: leaf {i}")


def test_solo_run_is_a_one_lane_sweep():
    """``TieredMemSimulator.run`` is a one-lane ``sweep_lanes`` call: the
    matching sweep reuses its compiled program (no new compile) and gives
    the same timeline and final state bit for bit."""
    mc = tiny_machine()
    cc = CostConfig()
    trace = random_trace(mc, steps=300, seed=61, free_at=150)
    for pc in (POLICIES[1], POLICIES[2]):
        solo = TieredMemSimulator(mc=mc, cc=cc, pc=pc).run(trace)
        before = sweep_compile_count()
        lane, = sweep_lanes(mc, [cc], [pc], [trace])
        assert sweep_compile_count() == before, pc.label()
        assert (solo.trace_name, solo.policy_label) \
            == (lane.trace_name, lane.policy_label)
        assert_runs_bitwise(solo, lane, pc.label())


def split_trace(tr, cut):
    def part(sl):
        return dataclasses.replace(tr, va=tr.va[sl], is_write=tr.is_write[sl],
                                   free_seg=tr.free_seg[sl], llc=tr.llc[sl])
    return part(slice(None, cut)), part(slice(cut, None))


@pytest.mark.parametrize("case", ["split", "clocks"])
def test_sweep_lanes_resumes_from_states(case):
    """``sweep_lanes(states=)`` resumes every lane from its own state: a
    run split at step 75 (mid-window, off the scan period) equals the
    whole run, state leaf for leaf and timeline bit for bit.  Lanes whose
    step clocks differ are refused, since the scan schedule is
    lane-shared."""
    mc = tiny_machine()
    ccs = [CostConfig(), CostConfig(nvmm_read=1500)]
    pcs = [POLICIES[0], POLICIES[3]]
    if case == "clocks":
        st = init_state(mc)
        states = [st, dataclasses.replace(st, step=st.step + 5)]
        tr = random_trace(mc, steps=32, seed=71)
        with pytest.raises(ValueError, match="step clock"):
            sweep_lanes(mc, ccs, pcs, [tr, tr], states=states)
        return
    trs = [random_trace(mc, steps=160, seed=72, free_at=100),
           random_trace(mc, steps=160, seed=73)]
    whole = sweep_lanes(mc, ccs, pcs, trs, block=16)
    halves = [split_trace(tr, 75) for tr in trs]
    first = sweep_lanes(mc, ccs, pcs, [h[0] for h in halves], block=16)
    second = sweep_lanes(mc, ccs, pcs, [h[1] for h in halves], block=16,
                         states=[r.final_state for r in first])
    for i, (w, a, b) in enumerate(zip(whole, first, second)):
        joined = dataclasses.replace(b, timeline={
            k: np.concatenate([a.timeline[k], b.timeline[k]])
            for k in b.timeline})
        assert_runs_bitwise(joined, w, f"lane {i}")


def test_policy_config_rejects_bad_codes():
    with pytest.raises(ValueError, match="data_policy"):
        PolicyConfig(data_policy=PT_FOLLOW_DATA)   # PT code in data field
    with pytest.raises(ValueError, match="pt_policy"):
        PolicyConfig(pt_policy=99)
    with pytest.raises(ValueError, match="data_policy"):
        PolicyConfig(data_policy="first-touch")    # typo'd legacy spelling
    # legacy string spellings still normalize to codes
    pc = PolicyConfig(data_policy="interleave", pt_policy="bind_high")
    assert pc.data_policy == INTERLEAVE and pc.pt_policy == PT_BIND_HIGH
