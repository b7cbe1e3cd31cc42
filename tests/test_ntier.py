"""N-tier machine model + TPP/Nomad policy families, locked to the oracle.

Four lock points:

1. **Degenerate tiers** — a 3-tier machine whose middle tier has zero
   capacity reproduces the classic 2-tier machine bit-for-bit (cycles,
   counters, timelines; placements up to the tier-major node renaming)
   for every pre-existing policy bundle.
2. **TPP / Nomad vs oracle** — the new migration families running through
   the production blocked/batched engine match the pure-Python
   ``OracleSim`` exactly on counters and placements (cycles to f32
   rounding), including the Nomad transactional counters, and blocked
   stays bit-identical to the retained ``per_step`` reference.
3. **Property fuzz** — random traces x random (tier count, capacities,
   policy family, cost model): blocked == oracle.  Runs under hypothesis
   when available, with a seeded deterministic fallback (the
   ``tests/test_memsys.py`` pattern).
4. **Fault-schedule invariants** — the host conflict model holds under
   N-tier machines: DO bits equal an independent mapped-ness replay,
   exactly one WINNER per (step, granule), every bit is monotone in the
   trace prefix (``fault_schedule(tr[:k]) == fault_schedule(tr)[:k]``),
   and the first-touch schedule equals a step-by-step replay bit for bit,
   frees included.

Plus the reference-path gate: ``engine="per_step"`` / ``phase_b=
"sequential"`` are debug-only everywhere (simulator, sweep, service).
"""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # property tests skip; the rest run
    HAVE_HYPOTHESIS = False

from repro.core import (CostConfig, MachineConfig, PolicyConfig,
                        TieredMemSimulator, Trace, fault_schedule,
                        sweep_compile_count, sweep_lanes,
                        FIRST_TOUCH, INTERLEAVE, MIG_NOMAD, MIG_TPP,
                        PT_BIND_ALL, PT_BIND_HIGH, PT_FOLLOW_DATA,
                        nomad, tpp)
from repro.core.ref import OracleSim
from repro.core.sim import (SCHED_DO, SCHED_NEED_LEAF, SCHED_NEED_MID,
                            SCHED_NEED_ROOT, SCHED_NEED_TOP, SCHED_WINNER)
from repro.service import SimBroker, SimQuery

EXACT_KEYS = ("l1_hits", "stlb_hits", "walks", "walk_mem_reads", "faults",
              "slow_allocs", "data_migrations", "demotions",
              "l4_mig_success", "l4_mig_already_dest", "l4_mig_in_dram",
              "l4_mig_sibling_guard", "l4_mig_lock_skip",
              "data_pages_dram", "data_pages_nvmm",
              "leaf_pages_dram", "leaf_pages_nvmm", "oom_killed", "oom_step",
              # N-tier / policy-family extensions
              "data_pages_per_tier", "leaf_pages_per_tier", "shadow_pages",
              "nomad_retries", "nomad_flip_demotions", "nomad_shadow_drops")
CYCLE_KEYS = ("total_cycles", "walk_cycles", "stall_cycles",
              "data_mem_cycles", "fault_cycles", "migration_cycles")
PLACEMENT_ARRAYS = ("data_node", "leaf_node", "mid_node", "top_node",
                    "root_node", "node_free", "shadow_node")

TLB_KW = dict(l1_tlb_sets=4, l1_tlb_ways=2, stlb_sets=8, stlb_ways=4,
              pde_pwc_entries=4, pdpte_pwc_entries=2)


def tiny_machine(tiers=None, **kw):
    """Small machine; ``tiers`` is pages-per-node fastest-first, default
    the classic 2-tier (600, 2400)."""
    kw.setdefault("n_threads", 4)
    kw.setdefault("va_pages", 1 << 12)
    if tiers is None:
        return MachineConfig(dram_pages_per_node=600,
                             nvmm_pages_per_node=2400, **TLB_KW, **kw)
    return MachineConfig(tier_pages_per_node=tuple(tiers), **TLB_KW, **kw)


def random_trace(mc, steps=160, seed=0, free_at=None, write_p=0.3,
                 name="rand"):
    rng = np.random.default_rng(seed)
    T = mc.n_threads
    va = np.where(rng.random((steps, T)) < 0.5,
                  rng.integers(0, mc.va_pages // 2, (steps, T)),
                  rng.integers(0, mc.va_pages, (steps, T))).astype(np.int32)
    va[rng.random((steps, T)) < 0.05] = -1       # idle slots
    wr = rng.random((steps, T)) < write_p
    free_seg = np.full((steps,), -1, np.int32)
    if free_at is not None:
        free_seg[free_at] = 0
    seg = np.zeros((mc.n_map,), np.int32)
    seg[mc.n_map // 2:] = 1
    llc = np.full((steps,), 0.4, np.float32)
    return Trace(va=va, is_write=wr, free_seg=free_seg, llc=llc,
                 seg_of_map=seg, name=name)


def assert_matches_oracle(res, mc, cc, pc, trace):
    oracle = OracleSim(mc, cc, pc)
    oracle.run(trace)
    ref = oracle.summary()
    s = res.summary()
    for k in EXACT_KEYS:
        assert s[k] == ref[k], f"{pc.label()}: {k}: jax={s[k]} oracle={ref[k]}"
    for k in CYCLE_KEYS:
        np.testing.assert_allclose(s[k], ref[k], rtol=1e-5,
                                   err_msg=f"{pc.label()}: {k}")


def assert_results_bitwise(a, b, label=""):
    for arr in PLACEMENT_ARRAYS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a.final_state, arr)),
            np.asarray(getattr(b.final_state, arr)),
            err_msg=f"{label}: {arr}")
    for k in a.timeline:
        np.testing.assert_array_equal(a.timeline[k], b.timeline[k],
                                      err_msg=f"{label}: tl/{k}")


# ---------------------------------------------------------------------------
# 1. Degenerate tiers: zero-capacity middle tier == the 2-tier machine
# ---------------------------------------------------------------------------

# Every pre-existing policy shape: data x PT x mig x autonuma(exchange)
DEGENERATE_POLICIES = [
    PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                 mig=False, autonuma=False),
    PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_BIND_HIGH, mig=True,
                 autonuma=True, autonuma_period=16, autonuma_budget=32),
    PolicyConfig(data_policy=INTERLEAVE, pt_policy=PT_BIND_HIGH, mig=True,
                 autonuma=True, autonuma_period=16, autonuma_budget=32),
    PolicyConfig(data_policy=INTERLEAVE, pt_policy=PT_BIND_ALL,
                 mig=False, autonuma=True, autonuma_period=16,
                 autonuma_budget=32, autonuma_exchange=False),
]


def remap_nodes(arr, nt):
    """2-tier node ids -> N-tier tier-major ids: the slow pair (2, 3)
    becomes the slowest tier's pair (2(nt-1), 2(nt-1)+1)."""
    arr = np.asarray(arr)
    return np.where(arr >= 2, arr + 2 * (nt - 2), arr)


@pytest.mark.parametrize("pidx", range(len(DEGENERATE_POLICIES)))
def test_zero_capacity_middle_tier_bitwise(pidx):
    """tier_pages_per_node=(600, 0, 2400) must reproduce the classic
    (600, 2400) machine bit-for-bit: same cycles, counters and timelines,
    placements equal under the tier-major node renaming, and the empty
    tier's nodes never allocated."""
    pc = DEGENERATE_POLICIES[pidx]
    mc2 = tiny_machine()
    mc3 = tiny_machine(tiers=(600, 0, 2400))
    assert mc3.alloc_nodes == (0, 1, 4, 5)
    cc = CostConfig()
    tr2 = random_trace(mc2, seed=pidx, free_at=100 if pidx == 1 else None)
    tr3 = Trace(va=tr2.va, is_write=tr2.is_write, free_seg=tr2.free_seg,
                llc=tr2.llc, seg_of_map=tr2.seg_of_map, name="rand3")
    r2 = TieredMemSimulator(mc=mc2, cc=cc, pc=pc).run(tr2)
    r3 = TieredMemSimulator(mc=mc3, cc=cc, pc=pc).run(tr3)

    s2, s3 = r2.summary(), r3.summary()
    for k in EXACT_KEYS:
        if k.endswith("per_tier"):
            continue                     # shapes differ; checked below
        assert s2[k] == s3[k], f"{pc.label()}: {k}: {s2[k]} != {s3[k]}"
    for k in CYCLE_KEYS:                 # bitwise, not rtol: same f32 ops
        assert s2[k] == s3[k], f"{pc.label()}: {k}: {s2[k]} != {s3[k]}"
    assert s3["data_pages_per_tier"] == [s2["data_pages_per_tier"][0], 0,
                                         s2["data_pages_per_tier"][1]]
    assert s3["leaf_pages_per_tier"] == [s2["leaf_pages_per_tier"][0], 0,
                                         s2["leaf_pages_per_tier"][1]]
    for k in r2.timeline:
        np.testing.assert_array_equal(r2.timeline[k], r3.timeline[k],
                                      err_msg=f"{pc.label()}: tl/{k}")
    for arr in ("data_node", "leaf_node", "mid_node", "top_node",
                "root_node", "shadow_node"):
        np.testing.assert_array_equal(
            remap_nodes(getattr(r2.final_state, arr), 3),
            np.asarray(getattr(r3.final_state, arr)),
            err_msg=f"{pc.label()}: {arr}")
    free3 = np.asarray(r3.final_state.node_free)
    np.testing.assert_array_equal(free3[[0, 1, 4, 5]],
                                  np.asarray(r2.final_state.node_free))
    np.testing.assert_array_equal(free3[[2, 3]], [0, 0])


# ---------------------------------------------------------------------------
# 2. TPP / Nomad locked to the oracle on a genuine 3-tier machine
# ---------------------------------------------------------------------------

TIER3 = (600, 1200, 2400)
FAMILY_POLICIES = [
    tpp(autonuma_period=16, autonuma_budget=32),
    tpp(data_policy=INTERLEAVE, demote_wm=0.05, autonuma_period=16,
        autonuma_budget=32),
    nomad(autonuma_period=16, autonuma_budget=32),
    PolicyConfig(data_policy=INTERLEAVE, pt_policy=PT_BIND_HIGH, mig=True,
                 autonuma=True, mig_policy=MIG_NOMAD,
                 autonuma_period=16, autonuma_budget=32),
]


@pytest.mark.parametrize("pidx", range(len(FAMILY_POLICIES)))
def test_tpp_nomad_oracle_equivalence(pidx):
    mc = tiny_machine(tiers=TIER3)
    pc = FAMILY_POLICIES[pidx]
    cc = CostConfig()
    tr = random_trace(mc, seed=30 + pidx, free_at=100 if pidx >= 2 else None)
    res = TieredMemSimulator(mc=mc, cc=cc, pc=pc).run(tr)
    assert_matches_oracle(res, mc, cc, pc, tr)
    # blocked engine stays bit-identical to the per-step reference under
    # the new families (the retained oracle path, satellite 4)
    ps = TieredMemSimulator(mc=mc, cc=cc, pc=pc, engine="per_step",
                            debug=True).run(tr)
    assert_results_bitwise(res, ps, f"{pc.label()}: blocked vs per_step")


def test_tpp_nomad_under_memory_pressure():
    """Footprint >> DRAM so the TPP demotion watermark and the Nomad
    abort/shadow machinery actually fire; counters must prove it."""
    mc = tiny_machine(tiers=(200, 400, 1600), va_pages=1 << 11)
    cc = CostConfig()
    saw_demotions = saw_nomad = False
    for i, pc in enumerate((tpp(demote_wm=0.10, autonuma_period=16,
                                autonuma_budget=32),
                            nomad(autonuma_period=16, autonuma_budget=32))):
        tr = random_trace(mc, steps=256, seed=60 + i, write_p=0.5)
        res = TieredMemSimulator(mc=mc, cc=cc, pc=pc).run(tr)
        assert_matches_oracle(res, mc, cc, pc, tr)
        s = res.summary()
        if int(pc.mig_policy) == MIG_TPP:
            saw_demotions = s["demotions"] > 0
        else:
            saw_nomad = (s["nomad_retries"] + s["nomad_flip_demotions"]
                         + s["nomad_shadow_drops"] + s["shadow_pages"]) > 0
    assert saw_demotions, "TPP never demoted under 8x-DRAM pressure"
    assert saw_nomad, "Nomad transactional machinery never engaged"


def test_nomad_abort_retry_oracle_locked():
    """A churn trace — hot set larger than DRAM, write-heavy — forces
    promotion aborts on concurrent writes; the transactional retry path
    must actually fire and stay exact against the oracle."""
    mc = tiny_machine(tiers=(150, 300, 1600), va_pages=1 << 11)
    rng = np.random.default_rng(2)
    steps, T = 256, mc.n_threads
    va = rng.integers(0, 512, (steps, T)).astype(np.int32)
    wr = rng.random((steps, T)) < 0.9
    tr = Trace(va=va, is_write=wr,
               free_seg=np.full(steps, -1, np.int32),
               llc=np.full(steps, 0.4, np.float32),
               seg_of_map=np.zeros(mc.n_map, np.int32), name="churn")
    pc = nomad(autonuma_period=16, autonuma_budget=64)
    cc = CostConfig()
    res = TieredMemSimulator(mc=mc, cc=cc, pc=pc).run(tr)
    assert_matches_oracle(res, mc, cc, pc, tr)
    s = res.summary()
    assert s["nomad_retries"] > 0, "abort/retry path not exercised"
    assert s["nomad_flip_demotions"] > 0 and s["shadow_pages"] > 0


def test_tpp_nomad_sweep_lanes_and_broker_bitwise():
    """The new policy codes flow through the batched sweep engine and the
    service broker bit-identically to solo runs (acceptance criterion)."""
    mc = tiny_machine(tiers=TIER3)
    cc = CostConfig()
    pols = [tpp(autonuma_period=16, autonuma_budget=32),
            nomad(autonuma_period=16, autonuma_budget=32),
            PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_BIND_HIGH,
                         mig=True, autonuma=True, autonuma_period=16,
                         autonuma_budget=32)]
    tr = random_trace(mc, seed=77, write_p=0.4)
    solos = [TieredMemSimulator(mc=mc, cc=cc, pc=pc).run(tr) for pc in pols]

    lanes = sweep_lanes(mc, [cc] * len(pols), pols, [tr] * len(pols))
    for pc, lane, solo in zip(pols, lanes, solos):
        assert_results_bitwise(lane, solo, f"sweep_lanes/{pc.label()}")

    broker = SimBroker(max_lanes=len(pols))
    results = broker.run([SimQuery(trace=tr, policy=pc, cost=cc, machine=mc)
                          for pc in pols])
    for pc, res, solo in zip(pols, results, solos):
        assert_results_bitwise(res, solo, f"broker/{pc.label()}")


def test_broker_compiles_once_per_tier_topology():
    """Bucket keys include the machine: a burst mixing 2-tier and 3-tier
    queries of one trace shape compiles exactly once per topology, and a
    second burst with fresh trace content compiles zero more."""
    mc2 = tiny_machine()
    mc3 = tiny_machine(tiers=TIER3)
    pols = [tpp(autonuma_period=16, autonuma_budget=32),
            nomad(autonuma_period=16, autonuma_budget=32)]
    broker = SimBroker(max_lanes=64, max_wait=0.0)

    def burst(seed):
        qs = [SimQuery(trace=random_trace(mc, seed=seed + i, name=f"b{seed}"),
                       policy=pc, machine=mc)
              for i, mc in enumerate((mc2, mc3)) for pc in pols]
        return broker.run(qs)

    before = sweep_compile_count()
    burst(500)
    assert sweep_compile_count() == before + 2, \
        "expected one compile per (tier topology, trace shape) bucket"
    burst(600)
    assert sweep_compile_count() == before + 2, \
        "same buckets, new trace content must reuse both compiled programs"


# ---------------------------------------------------------------------------
# 3. Property fuzz: random traces x random (tiers, policy family, cost)
# ---------------------------------------------------------------------------

def fuzz_case(seed):
    """Derive a full (machine, cost, policy, trace) case from one seed and
    check blocked == oracle."""
    rng = np.random.default_rng(seed)
    n_tiers = int(rng.integers(2, 5))
    mids = [int(rng.choice([0, 300, 800])) for _ in range(n_tiers - 2)]
    tiers = (int(rng.choice([200, 600])), *mids,
             int(rng.choice([1600, 2400])))
    mc = tiny_machine(tiers=tiers, va_pages=1 << 11)
    cc = CostConfig(cxl_read=int(rng.choice([300, 450, 600])),
                    cxl_write=int(rng.choice([400, 500, 700])),
                    nvmm_read=int(rng.choice([600, 750, 900])))
    family = int(rng.choice([0, MIG_TPP, MIG_NOMAD]))
    kw = dict(data_policy=int(rng.choice([FIRST_TOUCH, INTERLEAVE])),
              pt_policy=int(rng.choice([PT_FOLLOW_DATA, PT_BIND_HIGH])),
              mig=bool(rng.random() < 0.5), autonuma=True,
              autonuma_period=16, autonuma_budget=32)
    if family == MIG_TPP:
        pc = PolicyConfig(mig_policy=MIG_TPP,
                          tpp_demote_wm=float(rng.choice([0.0, 0.05])), **kw)
    elif family == MIG_NOMAD:
        pc = PolicyConfig(mig_policy=MIG_NOMAD, **kw)
    else:
        pc = PolicyConfig(**kw)
    tr = random_trace(mc, steps=96, seed=seed,
                      free_at=48 if rng.random() < 0.5 else None,
                      write_p=0.4)
    res = TieredMemSimulator(mc=mc, cc=cc, pc=pc).run(tr)
    assert_matches_oracle(res, mc, cc, pc, tr)


@pytest.mark.parametrize("seed", range(3))
def test_fuzz_blocked_vs_oracle_fixed_seeds(seed):
    """Deterministic property-style coverage (runs without hypothesis)."""
    fuzz_case(seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=6, deadline=None)
    @given(st.integers(min_value=3, max_value=10 ** 6))
    def test_property_blocked_vs_oracle(seed):
        fuzz_case(seed)


# ---------------------------------------------------------------------------
# 4. fault_schedule invariants under the N-tier model
# ---------------------------------------------------------------------------

def replay_miss_set(tr, mc):
    """Independent mapped-ness replay: bool[steps, threads] of phase-A
    misses (active access to an unmapped granule), first-winner mapping."""
    va = np.asarray(tr.va)
    seg = np.asarray(tr.seg_of_map)
    free_seg = np.asarray(tr.free_seg)
    mapped = np.zeros(mc.n_map, bool)
    miss = np.zeros(va.shape, bool)
    for s in range(va.shape[0]):
        if free_seg[s] >= 0:
            mapped[seg == free_seg[s]] = False
        for t in range(va.shape[1]):
            if va[s, t] < 0:
                continue
            m = min(int(va[s, t]) >> mc.map_shift, mc.n_map - 1)
            if not mapped[m]:
                miss[s, t] = True
        # all of this step's winners map their granules afterwards
        for t in range(va.shape[1]):
            if miss[s, t]:
                mapped[min(int(va[s, t]) >> mc.map_shift, mc.n_map - 1)] = True
    return miss


def prefix_trace(tr, k):
    return Trace(va=tr.va[:k], is_write=tr.is_write[:k],
                 free_seg=tr.free_seg[:k], llc=tr.llc[:k],
                 seg_of_map=tr.seg_of_map, name=f"{tr.name}[:{k}]")


@pytest.mark.parametrize("seed", range(3))
def test_fault_schedule_invariants(seed):
    mc = tiny_machine(tiers=TIER3, va_pages=1 << 11)
    tr = random_trace(mc, steps=128, seed=seed, free_at=64)
    sched = fault_schedule(tr, mc)
    va = np.asarray(tr.va)

    # (a) DO bits == the phase-A miss set of an independent replay
    np.testing.assert_array_equal((sched & SCHED_DO) > 0,
                                  replay_miss_set(tr, mc))

    # (b) exactly one WINNER per (step, granule); the winner is the
    #     lowest-indexed DO thread of its granule; WINNER implies DO
    do = (sched & SCHED_DO) > 0
    win = (sched & SCHED_WINNER) > 0
    assert not (win & ~do).any()
    for s in range(va.shape[0]):
        gran = {}
        for t in np.where(do[s])[0]:
            m = min(int(va[s, t]) >> mc.map_shift, mc.n_map - 1)
            gran.setdefault(m, []).append(t)
        for m, threads in gran.items():
            w = [t for t in threads if win[s, t]]
            assert w == [threads[0]], \
                f"step {s} granule {m}: winners {w}, threads {threads}"

    # (c) NEED_* bits only on winners, and each level's existence set is
    #     claimed by at most one winner per step
    for bit in (SCHED_NEED_ROOT, SCHED_NEED_TOP, SCHED_NEED_MID,
                SCHED_NEED_LEAF):
        assert not (((sched & bit) > 0) & ~win).any()

    # (d) monotone in the trace prefix: every bit of the full schedule is
    #     reproduced by scheduling the prefix alone
    for k in (1, 37, 64, 100, 128):
        np.testing.assert_array_equal(fault_schedule(prefix_trace(tr, k), mc),
                                      sched[:k], err_msg=f"prefix {k}")


def replay_schedule(tr, mc):
    """Step-by-step replay of the host conflict model: the schedule
    ``fault_schedule`` gives, one step at a time, frees applied before
    the step's accesses (they unmap the segment's granules and leaf
    entries; root, top and mid entries are never freed)."""
    rb, n_map = mc.radix_bits, mc.n_map
    va = np.asarray(tr.va)
    seg = np.asarray(tr.seg_of_map)
    free_seg = np.asarray(tr.free_seg)
    seg_of_leaf = seg[(np.arange(mc.n_leaf_pages, dtype=np.int64) << rb)
                      % max(n_map, 1)]
    mapped = np.zeros(n_map, bool)
    exists = [np.zeros(1, bool), np.zeros(mc.n_top_pages, bool),
              np.zeros(mc.n_mid_pages, bool), np.zeros(mc.n_leaf_pages, bool)]
    bits = (SCHED_NEED_ROOT, SCHED_NEED_TOP, SCHED_NEED_MID, SCHED_NEED_LEAF)
    sched = np.zeros(va.shape, np.uint8)
    for s in range(va.shape[0]):
        if free_seg[s] >= 0:
            mapped[seg == free_seg[s]] = False
            exists[3][seg_of_leaf == free_seg[s]] = False
        winners = {}
        for t in range(va.shape[1]):
            if va[s, t] < 0:
                continue
            m = min(int(va[s, t]) >> mc.map_shift, n_map - 1)
            if not mapped[m]:
                sched[s, t] |= SCHED_DO
                winners.setdefault(m, t)
        for m, t in sorted(winners.items(), key=lambda mt: mt[1]):
            sched[s, t] |= SCHED_WINNER
            for lvl, e in enumerate((0, min(m >> 3 * rb, mc.n_top_pages - 1),
                                     min(m >> 2 * rb, mc.n_mid_pages - 1),
                                     m >> rb)):
                if not exists[lvl][e]:
                    sched[s, t] |= bits[lvl]
                    exists[lvl][e] = True
        for m in winners:
            mapped[m] = True
    return sched


@pytest.mark.parametrize("case", ["3tier", "thp", "crowded", "frees",
                                  "crowded_frees"])
def test_first_touch_schedule_equals_replay(case):
    """The schedule built from first touches, one array pass per stretch
    between frees, is a step-by-step replay's bit for bit, and its DO
    bits are an independent replay's miss set.  ``crowded``: every thread
    on eight pages, so most steps hold several threads per granule;
    ``frees``: segment 0 freed at steps 0, 40, 41 and 90, so granules and
    leaf entries fault again."""
    frees = [0, 40, 41, 90] if "frees" in case else None
    if case == "3tier":
        mc = tiny_machine(tiers=TIER3, va_pages=1 << 11)
        tr = random_trace(mc, steps=128, seed=5)
    elif case == "thp":
        mc = tiny_machine(tiers=TIER3, radix_bits=6, page_order=6)
        tr = random_trace(mc, steps=128, seed=6)
    elif case == "frees":
        mc = tiny_machine(tiers=TIER3, va_pages=1 << 11)
        tr = random_trace(mc, steps=128, seed=8, free_at=frees)
    else:
        mc = tiny_machine()
        tr = random_trace(mc, steps=128, seed=7, free_at=frees)
        tr = dataclasses.replace(tr, va=np.where(tr.va >= 0, tr.va % 8, -1)
                                 .astype(np.int32))
    sched = fault_schedule(tr, mc)
    np.testing.assert_array_equal(sched, replay_schedule(tr, mc))
    np.testing.assert_array_equal((sched & SCHED_DO) > 0,
                                  replay_miss_set(tr, mc))
    assert (sched & SCHED_NEED_LEAF).any()
    if frees:       # a freed leaf entry is allocated again after the free
        assert (sched[frees[1]:] & SCHED_NEED_LEAF).any()


# ---------------------------------------------------------------------------
# 5. Reference paths are debug-only (simulator, sweep engine, service)
# ---------------------------------------------------------------------------

def test_reference_paths_require_debug_flag():
    mc = tiny_machine()
    pc = PolicyConfig(autonuma=False)
    tr = random_trace(mc, steps=16, seed=1)
    with pytest.raises(ValueError, match="debug=True"):
        TieredMemSimulator(mc=mc, pc=pc, engine="per_step")
    with pytest.raises(ValueError, match="debug=True"):
        TieredMemSimulator(mc=mc, pc=pc, phase_b="sequential")
    with pytest.raises(ValueError, match="debug=True"):
        sweep_lanes(mc, [CostConfig()], [pc], [tr], engine="per_step")
    with pytest.raises(ValueError, match="debug=True"):
        sweep_lanes(mc, [CostConfig()], [pc], [tr], phase_b="sequential")
    with pytest.raises(ValueError, match="debug=True"):
        SimQuery(trace=tr, policy=pc, machine=mc, engine="per_step")
    with pytest.raises(ValueError, match="debug=True"):
        SimQuery(trace=tr, policy=pc, machine=mc, phase_b="sequential")
    # with the flag, the oracle paths still run (and still agree)
    ref = TieredMemSimulator(mc=mc, pc=pc, engine="per_step",
                             phase_b="sequential", debug=True).run(tr)
    prod = TieredMemSimulator(mc=mc, pc=pc).run(tr)
    assert_results_bitwise(prod, ref, "debug reference")


# ---------------------------------------------------------------------------
# 6. Per-tier summary fields pinned on 3- and 4-tier machines (oracle)
# ---------------------------------------------------------------------------

def test_per_tier_summary_fields_pinned_on_3_and_4_tier():
    """``RunResult.summary``'s per-tier placement lists, pinned on
    genuinely 3- and 4-tier machines: length == tier count, tier 0
    reconciles with the scalar dram fields and tiers 1+ with the scalar
    nvmm fields, pressure actually spreads pages past the fast tier, and
    every entry equals the pure-Python oracle's."""
    cases = [
        ((300, 600, 2400),
         tpp(demote_wm=0.05, autonuma_period=16, autonuma_budget=32), 70),
        ((300, 600, 1200, 4800),
         nomad(autonuma_period=16, autonuma_budget=32), 71),
    ]
    cc = CostConfig()
    for tiers, pc, seed in cases:
        mc = tiny_machine(tiers=tiers, va_pages=1 << 11)
        tr = random_trace(mc, steps=256, seed=seed, write_p=0.5)
        res = TieredMemSimulator(mc=mc, cc=cc, pc=pc).run(tr)
        s = res.summary()
        nt = len(tiers)
        assert len(s["data_pages_per_tier"]) == nt, tiers
        assert len(s["leaf_pages_per_tier"]) == nt, tiers
        # the legacy 2-tier scalars remain the fast/slower split
        assert s["data_pages_per_tier"][0] == s["data_pages_dram"]
        assert sum(s["data_pages_per_tier"][1:]) == s["data_pages_nvmm"]
        assert s["leaf_pages_per_tier"][0] == s["leaf_pages_dram"]
        assert sum(s["leaf_pages_per_tier"][1:]) == s["leaf_pages_nvmm"]
        assert sum(s["data_pages_per_tier"][1:]) > 0, \
            f"{tiers}: pressure never engaged the slower tiers"

        oracle = OracleSim(mc, cc, pc)
        oracle.run(tr)
        ref = oracle.summary()
        assert s["data_pages_per_tier"] == ref["data_pages_per_tier"], tiers
        assert s["leaf_pages_per_tier"] == ref["leaf_pages_per_tier"], tiers
        assert_matches_oracle(res, mc, cc, pc, tr)
