"""Time-blocked engine vs the per-step reference vs the oracle.

The blocked stepper (fixed step-windows; event-free windows execute as
one scan step with only the TLB/cycle carry threaded through, event
windows replay the per-step path row by row) must be **bit-identical**
to ``engine="per_step"`` — placements, counters, per-thread f32 cycle
accumulators and the full per-step timeline, not merely within rounding
— because the fast window replays the per-step expression tree in
per-step order.  Same for the conflict-group-compacted allocator scan
(``alloc.alloc_many(slot_thread=...)``) against its full-depth scan.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CostConfig, MachineConfig, PolicyConfig,
                        TieredMemSimulator, Trace, sweep, sweep_lanes,
                        FIRST_TOUCH, INTERLEAVE, PT_BIND_ALL, PT_BIND_HIGH,
                        PT_FOLLOW_DATA)
from repro.core import alloc as alloc_mod
from repro.core.ref import OracleSim
from repro.obs import Telemetry
from repro.core.sim import (DEFAULT_BLOCK, plan_windows, pow2ceil,
                            window_tiles)
from repro.core.sweep import event_masks

EXACT_KEYS = ("l1_hits", "stlb_hits", "walks", "walk_mem_reads", "faults",
              "slow_allocs", "data_migrations", "demotions",
              "l4_mig_success", "l4_mig_already_dest", "l4_mig_in_dram",
              "l4_mig_sibling_guard", "l4_mig_lock_skip",
              "data_pages_dram", "data_pages_nvmm",
              "leaf_pages_dram", "leaf_pages_nvmm", "oom_killed", "oom_step")
CYCLE_KEYS = ("total_cycles", "walk_cycles", "stall_cycles",
              "data_mem_cycles", "fault_cycles", "migration_cycles")

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


def plan_of(mc, pc, trace, block):
    """The window plan a one-lane run of ``trace`` under ``pc`` executes:
    the sweep's own host schedule, classified by ``plan_windows``."""
    _, *masks = event_masks(mc, [trace], int(pc.autonuma_period),
                            bool(pc.autonuma))
    return plan_windows(*masks, trace.n_steps, block)


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


def steady_trace(mc, steps=200, seed=0, touched_frac=0.25, free_at=None):
    """Short populate burst, then a long fault-free re-access phase."""
    rng = np.random.default_rng(seed)
    T = mc.n_threads
    pop_rows = min(max(int(mc.n_map * touched_frac) // T, 1), steps // 3)
    pool = pop_rows * T
    s = np.arange(pop_rows, dtype=np.int64)[:, None]
    t = np.arange(T, dtype=np.int64)[None, :]
    pop = ((s * T + t) << mc.map_shift).astype(np.int64)
    run = (rng.integers(0, pool, (steps - pop_rows, T))
           << mc.map_shift).astype(np.int64)
    va = np.concatenate([pop, run]).astype(np.int32)
    va[rng.random(va.shape) < 0.05] = -1
    return make_trace(mc, va, free_at)


def fault_heavy_trace(mc, steps=160, seed=1, free_at=None):
    rng = np.random.default_rng(seed)
    T = mc.n_threads
    va = np.where(rng.random((steps, T)) < 0.5,
                  rng.integers(0, mc.va_pages // 2, (steps, T)),
                  rng.integers(0, mc.va_pages, (steps, T))).astype(np.int32)
    va[rng.random((steps, T)) < 0.05] = -1
    return make_trace(mc, va, free_at)


def assert_states_bitwise(a, b, label=""):
    flat_a, _ = jax.tree_util.tree_flatten_with_path(a)
    flat_b = jax.tree_util.tree_leaves(b)
    assert len(flat_a) == len(flat_b)
    for (path, la), lb in zip(flat_a, flat_b):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"{label}: {jax.tree_util.keystr(path)}")


def assert_blocked_matches_per_step(mc, pc, trace, cc=None, block=16):
    cc = cc or CostConfig()
    blk = TieredMemSimulator(mc=mc, cc=cc, pc=pc, engine="blocked",
                             block=block).run(trace)
    ps = TieredMemSimulator(mc=mc, cc=cc, pc=pc, engine="per_step",
                            debug=True).run(trace)
    assert_states_bitwise(blk.final_state, ps.final_state, pc.label())
    for k in blk.timeline:
        np.testing.assert_array_equal(blk.timeline[k], ps.timeline[k],
                                      err_msg=f"{pc.label()}: tl/{k}")
        assert blk.timeline[k].shape == (trace.n_steps,)
    return blk


def assert_matches_oracle(res, mc, cc, pc, trace):
    oracle = OracleSim(mc, cc, pc)
    oracle.run(trace)
    ref = oracle.summary()
    s = res.summary()
    for k in EXACT_KEYS:
        assert s[k] == ref[k], f"{pc.label()}: oracle {k}: {s[k]} != {ref[k]}"
    for k in CYCLE_KEYS:
        np.testing.assert_allclose(s[k], ref[k], rtol=1e-5,
                                   err_msg=f"{pc.label()}: oracle {k}")


def test_steady_state_trace_bitwise():
    """The target scenario: long fault-free stretches become fast windows
    (several per trace, forced by a small block) and stay bit-identical —
    cycles and timelines included, not just to rounding."""
    mc = tiny_machine()
    cc = CostConfig()
    trace = steady_trace(mc, steps=200, seed=3)
    for pc in POLICIES:
        res = assert_blocked_matches_per_step(mc, pc, trace, cc)
        assert_matches_oracle(res, mc, cc, pc, trace)


def test_fault_heavy_and_free_bitwise():
    """Faults and a mid-run segment free everywhere: nearly every window
    takes the per-step fallback; both phase-B engines agree."""
    mc = tiny_machine()
    cc = CostConfig()
    trace = fault_heavy_trace(mc, seed=5, free_at=100)
    for pc in POLICIES[:2]:
        for phase_b in ("batched", "sequential"):
            blk = TieredMemSimulator(mc=mc, cc=cc, pc=pc, engine="blocked",
                                     block=16, phase_b=phase_b,
                                     debug=True).run(trace)
            ps = TieredMemSimulator(mc=mc, cc=cc, pc=pc, engine="per_step",
                                    phase_b=phase_b, debug=True).run(trace)
            assert_states_bitwise(blk.final_state, ps.final_state,
                                  f"{pc.label()}/{phase_b}")
        assert_matches_oracle(blk, mc, cc, pc, trace)


def test_thp_machine_bitwise():
    mc = tiny_machine(page_order=9)
    cc = CostConfig()
    trace = fault_heavy_trace(mc, seed=51)
    for pc in POLICIES[:2]:
        res = assert_blocked_matches_per_step(mc, pc, trace, cc)
        assert_matches_oracle(res, mc, cc, pc, trace)


def test_oom_trace_bitwise():
    """The OOM latch freezes every lane; post-OOM fast windows must stay
    inert exactly like per-step execution (bind-all pathology)."""
    mc = tiny_machine(dram_pages_per_node=150, nvmm_pages_per_node=1600,
                      va_pages=1 << 11, radix_bits=4)
    cc = CostConfig()
    T = mc.n_threads
    s = np.arange(256, dtype=np.int32)[:, None]
    t = np.arange(T, dtype=np.int32)[None, :]
    va = np.minimum(s * T + t, mc.va_pages - 1).astype(np.int32)
    trace = make_trace(mc, va)
    for ptp in (PT_FOLLOW_DATA, PT_BIND_ALL, PT_BIND_HIGH):
        pc = PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=ptp,
                          autonuma=False)
        res = assert_blocked_matches_per_step(mc, pc, trace, cc)
        assert_matches_oracle(res, mc, cc, pc, trace)
        if ptp == PT_BIND_ALL:
            assert res.summary()["oom_killed"]


def test_resume_mid_block():
    """Splitting a trace in the middle of what the full run tiles as one
    fast window must not change anything: chained blocked runs equal the
    unsplit per-step run bit-for-bit."""
    mc = tiny_machine()
    pc = POLICIES[0]
    trace = steady_trace(mc, steps=120, seed=13)
    full = TieredMemSimulator(mc=mc, pc=pc, engine="per_step",
                              debug=True).run(trace)

    cut = 75                      # not a multiple of any pow2 block size
    first = Trace(va=trace.va[:cut], is_write=trace.is_write[:cut],
                  free_seg=trace.free_seg[:cut], llc=trace.llc[:cut],
                  seg_of_map=trace.seg_of_map)
    second = Trace(va=trace.va[cut:], is_write=trace.is_write[cut:],
                   free_seg=trace.free_seg[cut:], llc=trace.llc[cut:],
                   seg_of_map=trace.seg_of_map)
    sim = TieredMemSimulator(mc=mc, pc=pc, engine="blocked", block=16)
    mid = sim.run(first)
    state = jax.tree.map(jnp.asarray, mid.final_state)
    res = sim.run(second, state=state)
    assert_states_bitwise(res.final_state, full.final_state, "resume")
    np.testing.assert_array_equal(
        np.concatenate([mid.timeline["total_cycles"],
                        res.timeline["total_cycles"]]),
        full.timeline["total_cycles"])


def test_vmapped_sweep_bitwise():
    """Blocked vs per-step engines lane-for-lane in an 8-lane vmapped
    sweep (window events are the union across lanes), and blocked sweep
    lanes vs solo blocked runs."""
    mc = tiny_machine()
    cc = CostConfig()
    trace = fault_heavy_trace(mc, seed=7, free_at=60)
    pols = [PolicyConfig(data_policy=d, pt_policy=p, autonuma=False)
            for d in (FIRST_TOUCH, INTERLEAVE)
            for p in (PT_FOLLOW_DATA, PT_BIND_ALL, PT_BIND_HIGH)]
    pols += [PolicyConfig(data_policy=d, pt_policy=PT_BIND_HIGH, mig=True,
                          autonuma=False) for d in (FIRST_TOUCH, INTERLEAVE)]
    blk = sweep(mc, cc, pols, trace, engine="blocked", block=16)
    ps = sweep(mc, cc, pols, trace, engine="per_step", debug=True)
    for pc, a, b in zip(pols, blk, ps):
        assert_states_bitwise(a.final_state, b.final_state, pc.label())
        for k in a.timeline:
            np.testing.assert_array_equal(a.timeline[k], b.timeline[k],
                                          err_msg=f"{pc.label()}: tl/{k}")
        solo = TieredMemSimulator(mc=mc, cc=cc, pc=pc, engine="blocked",
                                  block=16).run(trace)
        assert_states_bitwise(a.final_state, solo.final_state,
                              f"solo/{pc.label()}")


def test_window_tiling_shape_independence():
    """Window count is shape-derived (ceil(S/block)) and xs shapes depend
    only on the step count plus the pow2-quantized split geometry — never
    on raw event rows (the broker-quantization property, now carrying the
    geometry in the compile key); the plan's emission mask maps emitted
    rows back to exactly S steps."""
    mc = tiny_machine()
    pc = POLICIES[0]
    tr_a = steady_trace(mc, steps=100, seed=1)
    tr_b = fault_heavy_trace(mc, steps=100, seed=2)
    plan_a, plan_b = (plan_of(mc, pc, tr, 16) for tr in (tr_a, tr_b))
    a, b = (window_tiles([tr.va], 100, 16, rows_to=plan.rows_in)
            for tr, plan in ((tr_a, plan_a), (tr_b, plan_b)))
    # window count from the shape alone, for any content
    assert a[0].shape[0] == b[0].shape[0] == 7      # ceil(100/16) windows
    assert plan_a.n_windows == plan_b.n_windows == 7
    # event rows landing in the same pow2 capacity bucket quantize to one
    # geometry (free executable reuse); xs shapes follow the geometry
    none = np.zeros(100, bool)

    def fault_at(row):
        m = none.copy()
        m[row] = True
        return m

    p1 = plan_windows(none, none, fault_at(19), 100, 16)  # window row 3
    p2 = plan_windows(none, none, fault_at(20), 100, 16)  # window row 4
    assert p1.geom == p2.geom
    assert p1.emit_valid.shape == p2.emit_valid.shape
    # every trace step is emitted exactly once, in order, for any plan
    for plan in (plan_a, plan_b):
        assert int(plan.emit_valid.sum()) == 100
    # the steady trace's dense populate windows stay per-step (full) while
    # its scan-tick windows leave the whole-window path (kind > 0)
    assert (plan_a.kind > 0).any()


def test_alloc_many_conflict_groups_match_full_scan():
    """The compacted allocator scan == the full T-deep scan on random
    winner sets, including OOM latching mid-step (committed results, the
    gates and the carried allocator state; non-acting lanes are
    don't-care by contract)."""
    rng = np.random.default_rng(0)
    T = 16
    amc = MachineConfig(n_threads=T)
    wm = jnp.asarray([5, 5, 5, 5], jnp.int32)
    for trial in range(20):
        n_winners = int(rng.integers(0, T + 1))
        winners = np.zeros(T, bool)
        winners[rng.choice(T, size=n_winners, replace=False)] = True
        need_pt = winners[:, None] & (rng.random((T, 4)) < 0.5)
        need_data = winners & (rng.random(T) < 0.9)
        free = jnp.asarray(rng.integers(0, 12, 4), jnp.int32)
        rec = jnp.asarray(rng.integers(0, 3, 4), jnp.int32)
        ptr = jnp.asarray(int(rng.integers(0, 4)), jnp.int32)
        oom0 = jnp.asarray(bool(rng.random() < 0.1))
        dpol = int(rng.choice([FIRST_TOUCH, INTERLEAVE]))
        ppol = int(rng.choice([PT_FOLLOW_DATA, PT_BIND_ALL, PT_BIND_HIGH]))

        G = pow2ceil(max(n_winners, 1))
        slot = np.cumsum(winners) - 1
        slot_thread = np.full(G, T, np.int64)
        slot_thread[slot[winners]] = np.where(winners)[0]

        args = (free, rec, ptr, oom0, wm, dpol, ppol, amc,
                jnp.asarray(need_pt), jnp.asarray(need_data))
        ref = alloc_mod.alloc_many(*args)
        got = alloc_mod.alloc_many(*args,
                                   slot_thread=jnp.asarray(slot_thread))
        names = ("nodes", "slow", "ok", "act", "gate", "free", "rec",
                 "ptr", "oom")
        act = np.asarray(ref[3])
        for name, r, g in zip(names, ref, got):
            r, g = np.asarray(r), np.asarray(g)
            if name in ("nodes", "slow", "ok"):
                np.testing.assert_array_equal(
                    np.where(act, r, 0), np.where(act, g, 0),
                    err_msg=f"trial {trial}: {name}")
            else:
                np.testing.assert_array_equal(r, g,
                                              err_msg=f"trial {trial}: {name}")


def test_block_quantization():
    assert pow2ceil(5) == 8 and pow2ceil(8) == 8 and pow2ceil(0) == 1
    assert DEFAULT_BLOCK == 64


LEAN_PERIOD = 48


def populate_mix_trace(mc, steps=112):
    """Block 16, AutoNUMA period 48: rows 0-79 fault on every row (each
    thread touches a fresh granule), rows 80+ re-access the populated
    pool.  Windows 0, 2 and 4 are lean (faults on every row, no free, no
    tick); window 1 also frees segment 0 (row 20) and window 3 holds the
    tick at row 48, so both replay through the general full body; window
    5 is fast and window 6 hoists the tick at row 96.  The first 64
    granules lie in segment 0, so the free unmaps them."""
    T = mc.n_threads
    pop_rows = 80
    base = mc.n_map // 2 - 16 * T
    va = np.empty((steps, T), np.int64)
    va[:pop_rows] = base + np.arange(pop_rows * T).reshape(pop_rows, T)
    rng = np.random.default_rng(17)
    va[pop_rows:] = base + rng.integers(16 * T, pop_rows * T,
                                        (steps - pop_rows, T))
    return make_trace(mc, (va << mc.map_shift).astype(np.int32), free_at=20)


LEAN_POLICIES = [
    PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                 autonuma=True, autonuma_period=LEAN_PERIOD,
                 autonuma_budget=32),
    PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_BIND_HIGH, mig=True,
                 autonuma=True, autonuma_period=LEAN_PERIOD,
                 autonuma_budget=32),
    PolicyConfig(data_policy=INTERLEAVE, pt_policy=PT_BIND_ALL,
                 autonuma=False, autonuma_period=LEAN_PERIOD),
]


@pytest.mark.parametrize("runner", ["solo", "lanes"])
def test_lean_windows_bitwise(runner):
    """Lean windows (populate rows through the cond-free row body) and
    general full windows (one frees, one ticks) in one trace: the blocked
    engine equals the per-step reference leaf for leaf and timeline bit
    for bit, solo and as a lane sweep, and the lean counters say three
    windows took the lean body."""
    mc = tiny_machine()
    cc = CostConfig()
    trace = populate_mix_trace(mc)
    plan = plan_of(mc, LEAN_POLICIES[0], trace, 16)
    assert plan.n_lean == 3
    assert plan.counts == (1, 5, 1, 0)
    plan = plan_of(mc, LEAN_POLICIES[2], trace, 16)
    assert plan.n_lean == 4
    tel = Telemetry()
    if runner == "solo":
        got = [TieredMemSimulator(mc=mc, cc=cc, pc=pc, block=16,
                                  telemetry=tel).run(trace)
               for pc in LEAN_POLICIES]
        ref = [TieredMemSimulator(mc=mc, cc=cc, pc=pc, engine="per_step",
                                  debug=True).run(trace)
               for pc in LEAN_POLICIES]
        # without AutoNUMA window 3 has no tick and is lean too
        assert tel.metrics.value("sweep.windows_lean") == 3 + 3 + 4
    else:
        got = sweep(mc, cc, LEAN_POLICIES, trace, block=16, telemetry=tel)
        ref = sweep(mc, cc, LEAN_POLICIES, trace, engine="per_step",
                    debug=True)
        # the lanes share the union schedule: the AutoNUMA lanes' tick
        # keeps window 3 full for every lane
        assert tel.metrics.value("sweep.windows_lean") == 3
    for pc, a, b in zip(LEAN_POLICIES, got, ref):
        assert_states_bitwise(a.final_state, b.final_state, pc.label())
        assert sorted(a.timeline) == sorted(b.timeline)
        for k in a.timeline:
            np.testing.assert_array_equal(a.timeline[k], b.timeline[k],
                                          err_msg=f"{pc.label()}: tl/{k}")
        assert (a.trace_name, a.policy_label) \
            == (b.trace_name, b.policy_label)
    assert got[0].summary()["faults"] > 0


WATERMARK_POLICIES = [
    PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                 autonuma=False),
    PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_BIND_HIGH,
                 autonuma=False),
    PolicyConfig(data_policy=INTERLEAVE, pt_policy=PT_FOLLOW_DATA,
                 autonuma=False),
    PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_BIND_ALL,
                 autonuma=False),
]


def watermark_trace(mc, delay, steps=160):
    """Idle for ``delay`` rows, then every thread touches a fresh granule
    each row: DRAM (100 pages a node, watermark 2) reaches its watermark
    50-100 populate rows in, so later the larger the delay."""
    T = mc.n_threads
    va = np.full((steps, T), -1, np.int64)
    rows = steps - delay
    va[delay:] = np.arange(rows * T).reshape(rows, T)
    return make_trace(mc, (va << mc.map_shift).astype(np.int32))


@pytest.mark.parametrize("runner", ["solo", "lanes"])
def test_allocator_arms_bitwise_across_watermarks(runner):
    """Populate lanes whose DRAM nodes cross their watermark (and run dry)
    inside lean windows, at a different row in each lane: the rows where
    a node's allocator class changes take the allocator scan, the others
    the one-pass vector arm.  The blocked engine equals the per-step
    reference leaf for leaf and timeline bit for bit, solo and over 8
    lanes; ``alloc_rows`` counts both arms, and the two counts sum to the
    faulting replayed rows."""
    mc = tiny_machine(dram_pages_per_node=100)
    cc = CostConfig()
    delays = [0, 5, 12, 17, 24, 29, 36, 41]
    traces = [watermark_trace(mc, d) for d in delays]
    pcs = [WATERMARK_POLICIES[i % 4] for i in range(len(delays))]
    if runner == "solo":
        picks = [0, 3, 6]
        got, ref = [], []
        for i in picks:
            tel, tel_ref = Telemetry(), Telemetry()
            got.append(TieredMemSimulator(mc=mc, cc=cc, pc=pcs[i], block=16,
                                          telemetry=tel).run(traces[i]))
            ref.append(TieredMemSimulator(
                mc=mc, cc=cc, pc=pcs[i], engine="per_step", debug=True,
                telemetry=tel_ref).run(traces[i]))
            arms = [tel.metrics.value("sweep.alloc_rows", path=p)
                    for p in ("batched", "scan")]
            assert arms == [tel_ref.metrics.value("sweep.alloc_rows", path=p)
                            for p in ("batched", "scan")]
            assert min(arms) > 0, (i, arms)
            assert sum(arms) == len(traces[i].va) - delays[i]
        pcs, traces = [pcs[i] for i in picks], [traces[i] for i in picks]
    else:
        tel, tel_ref = Telemetry(), Telemetry()
        got = sweep_lanes(mc, [cc] * len(pcs), pcs, traces, block=16,
                          telemetry=tel)
        ref = sweep_lanes(mc, [cc] * len(pcs), pcs, traces,
                          engine="per_step", debug=True, telemetry=tel_ref)
        arms = [tel.metrics.value("sweep.alloc_rows", path=p)
                for p in ("batched", "scan")]
        assert arms == [tel_ref.metrics.value("sweep.alloc_rows", path=p)
                        for p in ("batched", "scan")]
        assert min(arms) > 0, arms
        # every row faults in some lane, so every window is lean
        assert tel.metrics.value("sweep.windows_lean") == 10
        assert sum(arms) == tel.metrics.value("sweep.replay_rows") == 160
    for pc, a, b in zip(pcs, got, ref):
        assert_states_bitwise(a.final_state, b.final_state, pc.label())
        for k in a.timeline:
            np.testing.assert_array_equal(a.timeline[k], b.timeline[k],
                                          err_msg=f"{pc.label()}: tl/{k}")
    # DRAM went below its watermark in every lane shown
    wm = np.asarray(alloc_mod.watermark_pages(mc))
    for a in got:
        assert (np.asarray(a.final_state.node_free)[:2] <= wm[:2]).all()
