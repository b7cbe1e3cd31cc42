"""Window splitting and scan-tick hoisting: plans, bitwise identity,
and executable sharing.

``plan_windows`` classifies every block-step window from the host-side
event schedule (fast / full replay / hoisted scan tick / split span)
and quantizes segment capacities to powers of two so the split geometry
lands in the compile key without fracturing executable reuse.  These
tests pin:

  * the classification rules, including the partial-tail-with-faults
    stability rule and the pow2 capacity buckets;
  * bitwise identity of hoist/split windows against per-step execution,
    property-tested over random event placements (window boundaries,
    interiors, singletons) and policy families (AutoNUMA / TPP / Nomad
    / migration off), with seeded fallbacks when hypothesis is absent;
  * that traces whose event rows differ but whose quantized geometry
    matches share one sweep executable (compile count stays flat).
"""
import numpy as np
import pytest

from repro.core import (CostConfig, PolicyConfig, FIRST_TOUCH, INTERLEAVE,
                        PT_BIND_HIGH, PT_FOLLOW_DATA, nomad, sweep,
                        sweep_compile_count, tpp)
from repro.core.sim import (WIN_FAST, WIN_FULL, WIN_HOIST, WIN_SPLIT,
                            blocked_xs, plan_windows)

from test_blocked import (assert_blocked_matches_per_step, make_trace,
                          steady_trace, tiny_machine)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def quiet_masks(steps):
    return (np.zeros(steps, bool), np.zeros(steps, bool),
            np.zeros(steps, bool))


# ---------------------------------------------------------------------------
# 1. planner classification and geometry quantization
# ---------------------------------------------------------------------------

def test_plan_classifies_fast_hoist_split_full():
    S, B = 64, 16
    df, ds, hf = quiet_masks(S)
    p = plan_windows(df, ds, hf, S, B)
    assert p.counts == (4, 0, 0, 0)
    assert p.geom is None            # all-fast: no per-step body compiled
    assert int(p.emit_valid.sum()) == S

    ds[21] = True                    # lone scan tick in window 1 -> hoist
    p = plan_windows(df, ds, hf, S, B)
    assert p.counts == (3, 0, 1, 0)

    hf[36:39] = True                 # narrow fault span in window 2 -> split
    p = plan_windows(df, ds, hf, S, B)
    assert p.counts == (2, 0, 1, 1)

    df[49] = True                    # span 49..63 wider than block // 2:
    df[63] = True                    # window 3 replays in full
    p = plan_windows(df, ds, hf, S, B)
    assert p.counts == (1, 1, 1, 1)
    assert int(p.emit_valid.sum()) == S
    assert p.counts[WIN_FAST] + p.counts[WIN_FULL] \
        + p.counts[WIN_HOIST] + p.counts[WIN_SPLIT] == p.n_windows


def test_partial_tail_with_faults_replays_full():
    """In a partial tail window the span end is the trace's last faulting
    step, so letting it pick split geometry would make the compile key a
    function of trace length modulo block: the planner must fall back to
    a full replay there."""
    S, B = 40, 16                    # windows of 16, 16, and a tail of 8
    df, ds, hf = quiet_masks(S)
    hf[38] = True
    p = plan_windows(df, ds, hf, S, B)
    assert p.counts[WIN_FULL] == 1
    assert p.counts[WIN_SPLIT] == 0
    assert int(p.emit_valid.sum()) == S


def lean_case_masks(case):
    """Window 1 (rows 16-31) of a 64-step, block-16 trace faults on every
    row; ``case`` adds the one thing that keeps it out of the lean body.
    ``tail`` instead puts the all-fault rows in the partial tail window
    of a 56-step trace."""
    S = 56 if case == "tail" else 64
    df, ds, hf = quiet_masks(S)
    if case == "tail":
        hf[48:] = True
        return df, ds, hf, S
    hf[16:32] = True
    if case == "tick":
        ds[21] = True
    elif case == "free":
        df[21] = True
    elif case == "fault-free-row":
        hf[21] = False
    return df, ds, hf, S


@pytest.mark.parametrize("case,lean", [
    ("lean", True), ("tick", False), ("free", False),
    ("fault-free-row", False), ("tail", False)])
def test_plan_marks_lean_windows(case, lean):
    """A full window with no free, no scan tick, no pad row and a fault on
    every row is lean; anything else keeps the general full body.  Lean
    moves only the branch index: ``counts`` (lean under full) and ``geom``
    are what the planner gave before lean existed, so the compile key
    never depends on it."""
    df, ds, hf, S = lean_case_masks(case)
    p = plan_windows(df, ds, hf, S, 16)
    w = 3 if case == "tail" else 1
    assert p.n_lean == int(lean)
    assert p.counts == (3, 1, 0, 0)
    assert p.geom == (True, None, None)
    assert p.rows_in == 16
    assert int(p.emit_valid.sum()) == S
    # branches [fast, full, lean]: fast 0, full 1, lean 2
    want = np.zeros(p.n_windows, np.int32)
    want[w] = 2 if lean else 1
    np.testing.assert_array_equal(p.kind, want)


def test_geometry_quantizes_to_pow2_buckets():
    S, B = 64, 16

    def one_fault(step):
        df, ds, hf = quiet_masks(S)
        hf[step] = True
        return plan_windows(df, ds, hf, S, B)

    # fault at window-1 rows 3 vs 4: both prefixes round up to capacity
    # 4 and both suffixes to 16, so the plans share geometry and shapes
    a, b = one_fault(19), one_fault(20)
    assert a.counts[WIN_SPLIT] == 1
    assert a.geom == b.geom
    assert a.emit_valid.shape == b.emit_valid.shape
    assert a.rows_in == b.rows_in
    # row 9 needs a 16-row prefix bucket: genuinely new geometry
    c = one_fault(25)
    assert c.geom != a.geom


# ---------------------------------------------------------------------------
# 2. hoisted scan ticks across migration-policy families
# ---------------------------------------------------------------------------

def test_hoist_engages_and_stays_bitwise():
    """period == block puts one scan tick at row 0 of every post-populate
    window: those windows must take the hoist branch (no per-step replay)
    and still match per-step bit for bit — AutoNUMA, TPP and Nomad all
    route their periodic work through the hoisted scan op."""
    mc = tiny_machine()
    cc = CostConfig()
    trace = steady_trace(mc, steps=192, seed=9)
    families = [
        PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                     autonuma=True, autonuma_period=16, autonuma_budget=32),
        tpp(autonuma_period=16, autonuma_budget=32),
        nomad(autonuma_period=16, autonuma_budget=32),
    ]
    for pc in families:
        _, plan = blocked_xs(trace, mc, pc, block=16)
        assert plan.counts[WIN_HOIST] > 0, pc.label()
        assert_blocked_matches_per_step(mc, pc, trace, cc, block=16)


# ---------------------------------------------------------------------------
# 3. property test: random event rows vs the per-step reference
# ---------------------------------------------------------------------------

def fuzz_case(seed):
    rng = np.random.default_rng(seed)
    mc = tiny_machine()
    cc = CostConfig()
    block = int(rng.choice([8, 16]))
    n_w = int(rng.integers(3, 6))
    S = n_w * block - int(rng.integers(0, block // 2))  # maybe partial tail
    T = mc.n_threads

    # fault-free base: a short populate burst, then re-access of the pool
    pop_rows = 4
    pool = pop_rows * T
    s = np.arange(pop_rows, dtype=np.int64)[:, None]
    t = np.arange(T, dtype=np.int64)[None, :]
    pop = s * T + t
    run = rng.integers(0, pool, (S - pop_rows, T))
    va = (np.concatenate([pop, run]) << mc.map_shift).astype(np.int32)

    # inject fresh-granule fault rows at window boundaries, interiors and
    # the final (possibly partial) row
    fresh = pool
    candidates = ([int(x) for x in rng.integers(pop_rows, S, 3)]
                  + [2 * block - 1, 2 * block, S - 1])
    picks = sorted({c for c in candidates if pop_rows <= c < S})
    rng.shuffle(picks)
    for step in picks[:int(rng.integers(1, 5))]:
        width = int(rng.integers(1, T + 1))
        va[step, :width] = (np.arange(fresh, fresh + width)
                            << mc.map_shift).astype(np.int32)
        fresh += width
    free_at = int(rng.integers(pop_rows, S)) if rng.random() < 0.5 else None
    trace = make_trace(mc, va, free_at)

    period = int(rng.choice([8, 16, 32]))
    family = int(rng.integers(0, 4))
    if family == 0:
        pc = PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                          autonuma=True, autonuma_period=period,
                          autonuma_budget=32)
    elif family == 1:
        pc = tpp(autonuma_period=period, autonuma_budget=32)
    elif family == 2:
        pc = nomad(autonuma_period=period, autonuma_budget=32)
    else:
        pc = PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                          autonuma=False)
    assert_blocked_matches_per_step(mc, pc, trace, cc, block=block)


@pytest.mark.parametrize("seed", range(4))
def test_fuzz_split_hoist_vs_per_step_fixed_seeds(seed):
    """Deterministic property-style coverage (runs without hypothesis)."""
    fuzz_case(seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=6, deadline=None)
    @given(st.integers(min_value=10, max_value=10 ** 6))
    def test_property_split_hoist_vs_per_step(seed):
        fuzz_case(seed)


# ---------------------------------------------------------------------------
# 4. executable sharing across traces with equal quantized geometry
# ---------------------------------------------------------------------------

def test_sweep_shares_executables_across_same_geometry():
    """Three traces, identical shapes, one single-row fault window each:
    fault at rows 3 and 4 of the window land in the same pow2 capacity
    bucket (prefix 4 / event 1 / suffix 16) and must reuse one compiled
    sweep; row 9 needs a wider prefix bucket and costs exactly one more."""
    mc = tiny_machine(va_pages=1 << 11)   # distinct mc: private cache keys
    cc = CostConfig()
    pcs = [PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                        autonuma=False),
           PolicyConfig(data_policy=INTERLEAVE, pt_policy=PT_BIND_HIGH,
                        autonuma=False)]
    T = mc.n_threads
    pop_rows = 16                          # window 0 faults on every row
    pool = pop_rows * T

    def tr(fault_step, seed):
        s = np.arange(pop_rows, dtype=np.int64)[:, None]
        t = np.arange(T, dtype=np.int64)[None, :]
        pop = s * T + t
        run = np.random.default_rng(seed).integers(
            0, pool, (64 - pop_rows, T))
        va = (np.concatenate([pop, run]) << mc.map_shift).astype(np.int32)
        va[fault_step] = ((np.arange(pool, pool + T)
                           << mc.map_shift).astype(np.int32))
        return make_trace(mc, va)

    before = sweep_compile_count()
    sweep(mc, cc, pcs, tr(35, 1), block=16)    # window-2 row 3
    base = sweep_compile_count()
    assert base == before + 1
    # row 4 of its window: same quantized geometry, zero new compiles
    sweep(mc, cc, pcs, tr(36, 2), block=16)
    assert sweep_compile_count() == base
    # row 9: prefix capacity bucket doubles — exactly one new executable
    sweep(mc, cc, pcs, tr(41, 3), block=16)
    assert sweep_compile_count() == base + 1
