"""Conflict-aware batched phase B vs the sequential fori_loop vs the oracle.

The batched fault engine (host ``fault_schedule`` + device
``alloc.alloc_many`` + vectorized commits) must be bit-identical to the
retained sequential per-thread path — placements and counters exactly,
cycle totals to float32 rounding — on ordinary traces, on adversarial
conflict-heavy traces (all threads faulting the same leaf / the same
page in one step), and through an OOM-during-burst.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CostConfig, MachineConfig, PolicyConfig,
                        TieredMemSimulator, Trace, pad_trace, sweep,
                        FIRST_TOUCH, INTERLEAVE, PT_BIND_ALL, PT_BIND_HIGH,
                        PT_FOLLOW_DATA)
from repro.core import alloc as alloc_mod
from repro.core.ref import OracleSim
from repro.core.sim import (SCHED_DO, SCHED_NEED_LEAF, SCHED_NEED_MID,
                            SCHED_NEED_ROOT, SCHED_NEED_TOP, SCHED_WINNER,
                            fault_schedule, fault_step_mask)

EXACT_KEYS = ("l1_hits", "stlb_hits", "walks", "walk_mem_reads", "faults",
              "slow_allocs", "data_migrations", "demotions",
              "l4_mig_success", "l4_mig_already_dest", "l4_mig_in_dram",
              "l4_mig_sibling_guard", "l4_mig_lock_skip",
              "data_pages_dram", "data_pages_nvmm",
              "leaf_pages_dram", "leaf_pages_nvmm", "oom_killed", "oom_step")
CYCLE_KEYS = ("total_cycles", "walk_cycles", "stall_cycles",
              "data_mem_cycles", "fault_cycles", "migration_cycles")
PLACEMENT_ARRAYS = ("data_node", "leaf_node", "mid_node", "top_node",
                    "root_node", "leaf_dram_children", "node_free",
                    "node_reclaimable", "interleave_ptr")

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


def tiny_machine(**kw):
    kw.setdefault("n_threads", 4)
    kw.setdefault("dram_pages_per_node", 600)
    kw.setdefault("nvmm_pages_per_node", 2400)
    kw.setdefault("va_pages", 1 << 12)
    return MachineConfig(l1_tlb_sets=4, l1_tlb_ways=2, stlb_sets=8,
                         stlb_ways=4, pde_pwc_entries=4,
                         pdpte_pwc_entries=2, **kw)


def make_trace(mc, va, free_at=None):
    steps = va.shape[0]
    free_seg = np.full((steps,), -1, np.int32)
    if free_at is not None:
        free_seg[free_at] = 0
    seg = np.zeros((mc.n_map,), np.int32)
    seg[mc.n_map // 2:] = 1
    return Trace(va=va.astype(np.int32),
                 is_write=np.ones_like(va, bool),
                 free_seg=free_seg,
                 llc=np.full((steps,), 0.4, np.float32), seg_of_map=seg)


def random_trace(mc, steps=160, seed=0, free_at=None):
    rng = np.random.default_rng(seed)
    T = mc.n_threads
    va = np.where(rng.random((steps, T)) < 0.5,
                  rng.integers(0, mc.va_pages // 2, (steps, T)),
                  rng.integers(0, mc.va_pages, (steps, T))).astype(np.int32)
    va[rng.random((steps, T)) < 0.05] = -1
    return make_trace(mc, va, free_at)


def conflict_trace(mc):
    """Adversarial conflict structure, repeated past a mid-run free:

    all threads faulting the SAME page in one step (one winner, the rest
    wait), all threads faulting distinct pages under the SAME leaf PT page
    (every thread a data winner, one leaf-PT winner), a wait/fault mix and
    idle lanes.
    """
    half = mc.n_map // 2
    L = 1 << mc.radix_bits             # granules per leaf PT page
    rows = [[7, 7, 7, 7],              # same granule: 1 winner + 3 waits
            [L, L + 1, L + 2, L + 3],  # same (new) leaf PT entry, 4 pages
            [7, L, half, half],        # re-touch + conflicting new pair
            [-1, 12, -1, half + 5],    # idle threads
            [half + 5, 7, 12, L + 1]]  # all mapped: fault-free step
    va = np.array(rows * 12, np.int32)
    return make_trace(mc, va, free_at=30)


def sequential_trace(mc, steps):
    """Populate burst: every thread maps new pages every step."""
    T = mc.n_threads
    s = np.arange(steps, dtype=np.int32)[:, None]
    t = np.arange(T, dtype=np.int32)[None, :]
    va = np.minimum(s * T + t, mc.va_pages - 1).astype(np.int32)
    return make_trace(mc, va)


def assert_batched_matches_sequential(mc, pc, trace, cc=None):
    cc = cc or CostConfig()
    bat = TieredMemSimulator(mc=mc, cc=cc, pc=pc, phase_b="batched").run(trace)
    seq = TieredMemSimulator(mc=mc, cc=cc, pc=pc,
                             phase_b="sequential", debug=True).run(trace)
    s1, s2 = bat.summary(), seq.summary()
    for k in EXACT_KEYS:
        assert s1[k] == s2[k], f"{pc.label()}: {k}: {s1[k]} != {s2[k]}"
    for arr in PLACEMENT_ARRAYS:
        np.testing.assert_array_equal(
            np.asarray(getattr(bat.final_state, arr)),
            np.asarray(getattr(seq.final_state, arr)),
            err_msg=f"{pc.label()}: {arr}")
    for k in CYCLE_KEYS:
        np.testing.assert_allclose(s1[k], s2[k], rtol=1e-6,
                                   err_msg=f"{pc.label()}: {k}")
    for k in bat.timeline:
        np.testing.assert_allclose(bat.timeline[k], seq.timeline[k],
                                   rtol=1e-6, err_msg=f"{pc.label()}: tl/{k}")
    return bat


def assert_matches_oracle(res, mc, cc, pc, trace):
    oracle = OracleSim(mc, cc, pc)
    oracle.run(trace)          # also asserts the fault schedule internally
    ref = oracle.summary()
    s = res.summary()
    for k in EXACT_KEYS:
        assert s[k] == ref[k], f"{pc.label()}: oracle {k}: {s[k]} != {ref[k]}"
    for k in CYCLE_KEYS:
        np.testing.assert_allclose(s[k], ref[k], rtol=1e-5,
                                   err_msg=f"{pc.label()}: oracle {k}")


def test_batched_matches_sequential_and_oracle():
    mc = tiny_machine()
    cc = CostConfig()
    trace = random_trace(mc, seed=3, free_at=100)
    for pc in POLICIES:
        res = assert_batched_matches_sequential(mc, pc, trace, cc)
        assert_matches_oracle(res, mc, cc, pc, trace)


def test_conflict_heavy_trace():
    """All threads faulting the same leaf page / the same data page in one
    step: first-thread-wins masks must reproduce the sequential winner,
    the wait path, and the PT-entry sharing exactly."""
    mc = tiny_machine()
    cc = CostConfig()
    trace = conflict_trace(mc)
    sched = fault_schedule(trace, mc)
    # step 0: one winner, three same-page waiters; the winner allocates
    # the whole root/top/mid/leaf chain
    assert ((sched[0] & SCHED_DO) > 0).all()
    assert list((sched[0] & SCHED_WINNER) > 0) == [True, False, False, False]
    chain = (SCHED_NEED_ROOT | SCHED_NEED_TOP | SCHED_NEED_MID
             | SCHED_NEED_LEAF)
    assert sched[0, 0] & chain == chain
    # step 1: every thread is a data winner of a page under one NEW leaf
    # PT page; only thread 0 gets the leaf-allocation bit
    assert ((sched[1] & SCHED_WINNER) > 0).all()
    need_leaf = (sched[1] & SCHED_NEED_LEAF) > 0
    assert list(need_leaf) == [True, False, False, False]
    for pc in POLICIES:
        res = assert_batched_matches_sequential(mc, pc, trace, cc)
        assert_matches_oracle(res, mc, cc, pc, trace)


def test_oom_during_burst():
    """bind-all under a populate storm OOMs mid-burst (paper fig. 7); the
    batched engine must latch at the identical thread boundary."""
    mc = tiny_machine(dram_pages_per_node=150, nvmm_pages_per_node=1600,
                      va_pages=1 << 11, radix_bits=4)
    cc = CostConfig()
    trace = sequential_trace(mc, steps=256)
    for ptp in (PT_FOLLOW_DATA, PT_BIND_ALL, PT_BIND_HIGH):
        pc = PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=ptp,
                          autonuma=False)
        res = assert_batched_matches_sequential(mc, pc, trace, cc)
        assert_matches_oracle(res, mc, cc, pc, trace)
        if ptp == PT_BIND_ALL:
            assert res.summary()["oom_killed"]


def test_thp_machine():
    mc = tiny_machine(page_order=9)
    cc = CostConfig()
    trace = random_trace(mc, seed=51)
    for pc in POLICIES[:2]:
        res = assert_batched_matches_sequential(mc, pc, trace, cc)
        assert_matches_oracle(res, mc, cc, pc, trace)


def test_sweep_lanes_match_sequential_reference():
    """An 8-lane vmapped sweep of the batched engine vs 8 sequential-path
    runs: the select-penalty fix must not perturb any lane."""
    mc = tiny_machine()
    cc = CostConfig()
    trace = conflict_trace(mc)
    pols = [PolicyConfig(data_policy=d, pt_policy=p, autonuma=False)
            for d in (FIRST_TOUCH, INTERLEAVE)
            for p in (PT_FOLLOW_DATA, PT_BIND_ALL, PT_BIND_HIGH)]
    pols += [PolicyConfig(data_policy=d, pt_policy=PT_BIND_HIGH, mig=True,
                          autonuma=False) for d in (FIRST_TOUCH, INTERLEAVE)]
    batch = sweep(mc, cc, pols, trace, phase_b="batched")
    for pc, res in zip(pols, batch):
        seq = TieredMemSimulator(mc=mc, cc=cc, pc=pc,
                                 phase_b="sequential", debug=True).run(trace)
        s1, s2 = res.summary(), seq.summary()
        for k in EXACT_KEYS:
            assert s1[k] == s2[k], f"{pc.label()}: {k}: {s1[k]} != {s2[k]}"
        for arr in PLACEMENT_ARRAYS:
            np.testing.assert_array_equal(
                np.asarray(getattr(res.final_state, arr)),
                np.asarray(getattr(seq.final_state, arr)),
                err_msg=f"{pc.label()}: {arr}")
        for k in CYCLE_KEYS:
            np.testing.assert_allclose(s1[k], s2[k], rtol=1e-6,
                                       err_msg=f"{pc.label()}: {k}")


def test_fault_schedule_invariants():
    """Host-schedule structure: winners are unique per granule per step,
    NEED bits imply WINNER implies DO, fault_step_mask is the DO bits'
    step-wise any, and frees re-arm both data pages and leaf PT entries."""
    mc = tiny_machine()
    trace = random_trace(mc, seed=9, free_at=80)
    sched = fault_schedule(trace, mc)
    do = (sched & SCHED_DO) > 0
    winner = (sched & SCHED_WINNER) > 0
    needs = (sched & (SCHED_NEED_ROOT | SCHED_NEED_TOP | SCHED_NEED_MID
                      | SCHED_NEED_LEAF)) > 0
    assert not (winner & ~do).any()
    assert not (needs & ~winner).any()
    np.testing.assert_array_equal(fault_step_mask(trace, mc), do.any(axis=1))
    # winners are unique per granule within a step
    m = np.clip(trace.va >> mc.map_shift, 0, mc.n_map - 1)
    for s in range(trace.va.shape[0]):
        wm = m[s][winner[s]]
        assert len(wm) == len(set(wm.tolist()))
    # after the mid-run free, freed pages fault (DO) again
    freed = np.where(np.asarray(trace.seg_of_map) == 0)[0]
    post = slice(80, None)
    touched_freed = np.isin(m[post], freed) & (trace.va[post] >= 0)
    assert (do[post] & touched_freed).any()
    # memoization: identical trace content returns the cached array
    assert fault_schedule(trace, mc) is sched


def test_resumed_state_overapproximation():
    """Resuming from a pre-populated state: DO bits over-approximate and
    phase B must no-op on already-mapped pages (batched == sequential)."""
    mc = tiny_machine()
    pc = POLICIES[0]
    trace = random_trace(mc, seed=13, steps=96)
    full = assert_batched_matches_sequential(mc, pc, trace)
    first = Trace(va=trace.va[:48], is_write=trace.is_write[:48],
                  free_seg=trace.free_seg[:48], llc=trace.llc[:48],
                  seg_of_map=trace.seg_of_map)
    second = Trace(va=trace.va[48:], is_write=trace.is_write[48:],
                   free_seg=trace.free_seg[48:], llc=trace.llc[48:],
                   seg_of_map=trace.seg_of_map)
    sim = TieredMemSimulator(mc=mc, pc=pc, phase_b="batched")
    mid = sim.run(first)
    state = jax.tree.map(jnp.asarray, mid.final_state)
    res = sim.run(second, state=state)
    np.testing.assert_array_equal(np.asarray(res.final_state.data_node),
                                  np.asarray(full.final_state.data_node))
    assert res.summary()["faults"] == full.summary()["faults"]


def test_resume_after_cross_segment_free_reallocates_leaf():
    """A non-leaf-aligned segment free can clear a leaf PT page while a
    sibling granule's data page stays mapped.  Resuming after that free,
    the host schedule (built from an empty address space) pins its
    NEED_LEAF bit on a thread that never actually faults — the engine
    must still allocate the truly-missing leaf for the next real fault,
    exactly like the sequential path."""
    mc = tiny_machine(radix_bits=4)            # 16 granules per leaf
    T = mc.n_threads
    seg = np.zeros((mc.n_map,), np.int32)
    seg[8:] = 1                                # boundary mid-leaf-0

    def rows_to_trace(rows, free_at=None):
        va = np.array(rows, np.int32)
        free_seg = np.full((va.shape[0],), -1, np.int32)
        if free_at is not None:
            free_seg[free_at] = 0
        return Trace(va=va, is_write=np.ones_like(va, bool),
                     free_seg=free_seg,
                     llc=np.full((va.shape[0],), 0.4, np.float32),
                     seg_of_map=seg)

    # map granule 0 (seg 0) and granule 8 (seg 1) — both under leaf 0 —
    # then free seg 0: leaf 0 is cleared, granule 8 stays mapped
    first = rows_to_trace([[0, 8, 16, 24][:T] + [-1] * max(T - 4, 0),
                           [-1] * T], free_at=1)
    # resume: re-touch the surviving granule 8 (phantom host winner),
    # then genuinely fault granule 9 under the missing leaf 0
    second = rows_to_trace([[8] + [-1] * (T - 1),
                            [9] + [-1] * (T - 1)])
    pc = PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                      autonuma=False)
    finals = {}
    for mode in ("batched", "sequential"):
        sim = TieredMemSimulator(mc=mc, pc=pc, phase_b=mode,
                                 debug=(mode == "sequential"))
        st = jax.tree.map(jnp.asarray, sim.run(first).final_state)
        assert int(np.asarray(st.leaf_node)[0]) == -1      # leaf freed
        assert int(np.asarray(st.data_node)[8]) >= 0       # page survives
        finals[mode] = sim.run(second, state=st).final_state
    for arr in PLACEMENT_ARRAYS:
        np.testing.assert_array_equal(
            np.asarray(getattr(finals["batched"], arr)),
            np.asarray(getattr(finals["sequential"], arr)), err_msg=arr)
    # the real fault re-allocated the orphaned leaf
    assert int(np.asarray(finals["batched"].leaf_node)[0]) >= 0


ALLOC_OUTPUTS = ("nodes", "slow", "ok", "act", "gate", "free", "rec", "ptr",
                 "oom")
POLICY_PAIRS = [(d, p) for d in (FIRST_TOUCH, INTERLEAVE)
                for p in (PT_FOLLOW_DATA, PT_BIND_ALL, PT_BIND_HIGH)]


def near_threshold_carry(rng, wm):
    """Per-node free and reclaimable counts drawn at every class edge:
    free at the watermark +- 0..8, free at 0..8, or far above; reclaim
    at 0..8."""
    n = wm.shape[0]
    edge = rng.integers(0, 3, n)
    free = np.where(edge == 0, wm + rng.integers(-8, 9, n),
                    np.where(edge == 1, rng.integers(0, 9, n),
                             wm + rng.integers(9, 400, n)))
    rec = np.where(rng.random(n) < 0.7, rng.integers(0, 9, n), 0)
    return np.maximum(free, 0), rec


@pytest.mark.parametrize("machine", ["2tier", "3tier", "thp"])
def test_alloc_vector_matches_alloc_many(machine):
    """The one-pass row allocator against the serialized scan on random
    carries near every class threshold, every data x PT policy pair and
    OOM mid-row.  Where ``verified`` holds, every output equals the
    full-depth scan bit for bit, and the slot-compacted scan on every
    acting request; where it fails, the scan's end state really crosses a
    node's class."""
    T = 8
    kw = {"3tier": dict(tier_pages_per_node=(600, 300, 2400)),
          "thp": dict(page_order=9)}.get(machine, {})
    mc = tiny_machine(n_threads=T, **kw)
    wm = np.asarray(alloc_mod.watermark_pages(mc))
    rng = np.random.default_rng(len(machine))

    def run(fn, dpol, ppol, extra):
        return jax.jit(lambda free, rec, ptr, oom, need_pt, need_d, *sl:
                       fn(free, rec, ptr, oom, jnp.asarray(wm), dpol, ppol,
                          mc, need_pt, need_d, **extra(*sl)))

    seen = {"verified": 0, "crossing": 0, "oom_mid_row": 0,
            "bhi_fallback": 0}
    for dpol, ppol in POLICY_PAIRS:
        full = run(alloc_mod.alloc_many, dpol, ppol, lambda: {})
        slots = run(alloc_mod.alloc_many, dpol, ppol,
                    lambda sl: {"slot_thread": sl})
        vec = run(alloc_mod.alloc_vector, dpol, ppol, lambda: {})
        for trial in range(40):
            winners = rng.random(T) < rng.random()
            need_pt = winners[:, None] & (rng.random((T, 4)) < 0.5)
            need_d = winners & (rng.random(T) < 0.9)
            free, rec = near_threshold_carry(rng, wm)
            if ppol != PT_FOLLOW_DATA and trial % 4 == 0:
                # DRAM exhausted: bind-all PT requests fail, BHi's fall
                # back to the data policy
                free[:2], rec[:2] = 0, 0
            args = (jnp.asarray(free, jnp.int32), jnp.asarray(rec, jnp.int32),
                    jnp.asarray(int(rng.integers(0, 50)), jnp.int32),
                    jnp.asarray(bool(rng.random() < 0.05)),
                    jnp.asarray(need_pt), jnp.asarray(need_d))
            slot = np.cumsum(winners) - 1
            slot_thread = np.full(T, T, np.int32)
            slot_thread[slot[winners]] = np.where(winners)[0]

            ref = [np.asarray(x) for x in full(*args)]
            comp = [np.asarray(x) for x in slots(*args,
                                                  jnp.asarray(slot_thread))]
            *got, verified = [np.asarray(x) for x in vec(*args)]
            where = f"{machine} d={dpol} pt={ppol} trial {trial}"
            if not verified:
                f1, r1 = ref[5], ref[6]
                crossed = (((free > wm) != (f1 > wm)) | ((free > 0) != (f1 > 0))
                           | ((rec > 0) != (r1 > 0)))
                assert crossed.any(), where
                seen["crossing"] += 1
                continue
            seen["verified"] += 1
            gate = ref[4]
            seen["oom_mid_row"] += int(gate[0] and not gate[-1])
            seen["bhi_fallback"] += int(ppol == PT_BIND_HIGH
                                        and (ref[3][:, :3] & ref[2][:, :3]
                                             & (ref[0][:, :3] >= 2)).any())
            act = ref[3]
            for name, r, c, g in zip(ALLOC_OUTPUTS, ref, comp, got):
                np.testing.assert_array_equal(g, r, err_msg=f"{where}: {name}")
                if name in ("nodes", "slow", "ok"):
                    c, g = np.where(act, c, 0), np.where(act, g, 0)
                np.testing.assert_array_equal(g, c,
                                              err_msg=f"{where}: {name} slots")
    assert seen["verified"] > 100 and seen["crossing"] > 10, seen
    assert seen["oom_mid_row"] > 0 and seen["bhi_fallback"] > 0, seen
