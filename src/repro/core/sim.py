"""The vectorized tiered-memory simulator.

One ``lax.scan`` step simulates one memory access per CPU thread:

  Phase 0   process-exit events (segment frees) and the periodic AutoNUMA
            scan (+ Algorithm-1 triggers) — ``migrate.autonuma_scan``.
  Phase A   *vectorized across threads*: accesses to already-mapped pages.
            L1-TLB -> STLB -> hardware walk with PDE/PDPTE page-walk caches;
            per-level walk costs depend on the NUMA node of each PT page
            (the paper's object of study); data-access cost depends on the
            data page's node, LLC-filtered.
  Phase B   *batched over threads*: page-fault handling — PT-page and
            data-page allocation under the active policies, zeroing costs,
            PTE install, TLB fill.  Thread order remains the serialization
            order (matching zone-lock serialization in the kernel), but it
            is reproduced without a per-thread loop over the full state:

            1. Host-side, :func:`fault_schedule` extends the per-step
               fault predicate into a per-(step, thread) schedule: who
               faults, who merely waits on a page an earlier thread maps
               this step, and — via first-thread-wins masks over shared
               root/top/mid/leaf PT indices — which thread allocates each
               missing PT entry.  PT-entry conflicts are the only true
               cross-thread dependency besides the allocator counters,
               and both are trace-derivable (mapped-ness and PT-entry
               existence are policy-independent).
            2. Device-side, ``alloc.alloc_vector`` hands out the
               whole row's pages in one vector pass from the row-start
               allocator classes of each node, and says whether that is
               exact (no node crosses its watermark, runs dry or exhausts
               its reclaim reserve inside the row).  Where it is not, in
               any lane, ``alloc.alloc_many`` serializes the allocator
               counters (``node_free`` / ``node_reclaimable`` /
               ``interleave_ptr`` / the OOM latch, ~10 scalars) through a
               tiny ``lax.scan`` over threads instead — one scalar
               ``lax.cond`` per row, outside the lane vmap.  Every heavy
               update — PT placement scatters, per-thread TLB fills,
               cycle and event counters — then commits vectorized across
               all threads at once.  The result is bit-identical
               (placements, counters; cycles to f32 rounding) to the
               retained sequential ``fori_loop`` path
               (``phase_b="sequential"``) and to the pure-Python oracle;
               ``tests/test_fault_batch.py`` enforces all three pairings.

            Under a vmapped policy sweep the old per-thread ``lax.cond``
            lowered to a select that ran the fault handler for every
            thread of every lane (~1.5x/lane on fault-dominated traces);
            the batched engine has no per-thread control flow at all.

Cycle model: ``total = cpu_work + stall (+ fault/alloc/migration overheads)``
with ``stall = walk + data_stall_frac * data`` — page walks stall the
pipeline fully (the PMH serializes translations, paper section 6.7:
``walk_active/walk_pending -> stalls_mem_any``), data misses are partially
hidden by out-of-order execution.

Policy and cost knobs enter the compiled step as *traced pytree leaves*
(``PolicyConfig``/``CostConfig`` are registered dataclasses): the step is
policy-generic and runs over a leading lane axis.  ``core.sweep`` builds
one runner from it and uses it to run N policies (and M same-shape
traces) in ONE compiled ``lax.scan``; a solo run is a one-lane sweep and
shares the same compiled artifact across every policy of equal trace
shape.  This module holds the program builders and the host window
plan; it compiles nothing itself.  Step-schedule predicates that
must stay un-batched for ``lax.cond`` to survive vmap — "a segment frees
this step", "the AutoNUMA scan fires", "some thread faults" — are
precomputed host-side from the trace, as is the per-(step, thread) fault
schedule that drives batched phase B (see :func:`fault_schedule` /
:func:`fault_step_mask`).

Time-blocked execution (``engine="blocked"``, the default): the paper's
steady-state hot path — TLB lookups and page walks on long fault-free,
scan-free stretches — used to pay the full per-step scan machinery (big
placement/counter state threaded through every iteration, the three
``lax.cond`` dispatches, fifteen per-step timeline reductions).  The
blocked engine tiles the trace into fixed ``[block, T]`` step-windows
(window count ``ceil(S / block)`` depends only on the trace *shape*) and
host-classifies each window from the schedule's exact event rows
(:func:`plan_windows`): event-free windows run as ONE outer-scan step
through :func:`_build_fast_window` — only the genuinely sequential
state (the four TLB/PWC arrays, per-thread cycle accumulators, three
hit counters) threads through a tiny inner scan while placement
gathers, Bernoulli draws and cost terms are precomputed vectorized over
the whole tile; a window whose only event is a single AutoNUMA/TPP scan
tick runs as fast-prefix -> hoisted scan op -> fast-suffix with *zero*
per-step rows (so a ``period=512, block=64`` cadence no longer demotes
one window in eight to per-step replay); a window with a narrow event
span runs fast prefix/suffix around a per-step replay of just the span;
only wide spans replay the whole window per-step — through a row body
with no ``lax.cond`` at all where the window has no free, no scan tick
and a fault on every row (a populate window).  Segment capacities
are quantized to per-class pow2 maxima and folded into the compile key
(``WindowPlan.geom``) with live lengths as traced data, so compiled
programs keep quantizing across trace contents — the property the
service broker's shape buckets rely on — and an all-fast program
compiles no per-step body at all.  Every branch replays the per-step
f32 expression tree in the per-step order, so the blocked engine is
**bit-identical** to the retained per-step path (``engine="per_step"``)
— cycles included, not just to rounding — which ``tests/test_blocked.py``
asserts exactly.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import alloc as alloc_mod
from . import migrate as migrate_mod
from . import tlbs
from .config import (CostConfig, MachineConfig, PolicyConfig, INTERLEAVE,
                     PT_BIND_HIGH, PT_FOLLOW_DATA)
from .state import SimState, is_dram

I32 = jnp.int32
F32 = jnp.float32
U32 = jnp.uint32

_MIX = (np.uint32(0x9E3779B1), np.uint32(0x85EBCA77), np.uint32(0xC2B2AE3D),
        np.uint32(0x27D4EB2F))


def bern(p, site: int, *keys) -> jax.Array:
    """Deterministic Bernoulli(p) from a multiplicative hash of the keys.

    ``p`` may be a traced scalar.  Replicated bit-for-bit by ``core.ref``
    (python ints masked to 32 bits).
    """
    h = jnp.asarray(np.uint32((0x811C9DC5 + 0x1000193 * site) & 0xFFFFFFFF), U32)
    for i, k in enumerate(keys):
        h = (h ^ jnp.asarray(k).astype(U32)) * _MIX[i % 4]
    h = (h >> 8) & jnp.asarray(np.uint32(0xFFFFFF), U32)
    thr = (jnp.asarray(p, F32) * (1 << 24)).astype(U32)
    return h < thr


@dataclasses.dataclass(frozen=True)
class Trace:
    """A pregenerated access trace (host-side numpy).

    va[s, t]     4-KiB virtual page accessed by thread t at step s (-1 idle)
    is_write     same shape
    free_seg[s]  segment id whose pages are freed at the start of step s (-1)
    llc[s]       data-access LLC hit probability at step s (phase-dependent)
    seg_of_map   segment id per mapping granule (for frees)
    """

    va: np.ndarray
    is_write: np.ndarray
    free_seg: np.ndarray
    llc: np.ndarray
    seg_of_map: np.ndarray
    name: str = "trace"
    populate_steps: int = 0      # steps belonging to the populate/startup phase

    @property
    def n_steps(self) -> int:
        return self.va.shape[0]


def pad_trace(tr: Trace, n_steps: int) -> Trace:
    """Idle-pad a trace to ``n_steps`` so policy sweeps share one compile."""
    cur = tr.n_steps
    if cur >= n_steps:
        return tr
    pad = n_steps - cur
    return dataclasses.replace(
        tr,
        va=np.concatenate([tr.va, np.full((pad, tr.va.shape[1]), -1, np.int32)]),
        is_write=np.concatenate([tr.is_write,
                                 np.zeros((pad, tr.va.shape[1]), bool)]),
        free_seg=np.concatenate([tr.free_seg, np.full((pad,), -1, np.int32)]),
        llc=np.concatenate([tr.llc, np.zeros((pad,), np.float32)]))


# fault_schedule bit layout (uint8 per (step, thread)):
#   DO      thread touches a page unmapped at step start (fault or wait)
#   WINNER  first DO-thread for its mapping granule -> runs the real fault
#   NEED_*  winner is the first to touch that missing PT entry -> allocates
SCHED_DO = np.uint8(1)
SCHED_WINNER = np.uint8(2)
SCHED_NEED_ROOT = np.uint8(4)
SCHED_NEED_TOP = np.uint8(8)
SCHED_NEED_MID = np.uint8(16)
SCHED_NEED_LEAF = np.uint8(32)

# Digest-keyed, LRU-bounded: the whole benchmark suite holds well under
# the cap, while long-lived processes sweeping many generated traces
# (property tests, trace-content grids) don't accumulate schedules forever.
_SCHED_CACHE: "collections.OrderedDict[Tuple, np.ndarray]" = \
    collections.OrderedDict()
_SCHED_CACHE_MAX = 64


def fault_schedule(tr: Trace, mc: MachineConfig) -> np.ndarray:
    """uint8[steps, threads]: the per-(step, thread) fault schedule.

    Mapped-ness and PT-entry *existence* are policy-independent (placement
    differs across policies, existence does not), so the whole conflict
    structure of phase B is derivable from the trace alone: which threads
    fault, which of them wins each shared mapping granule, and which
    winner allocates each missing root/top/mid/leaf PT entry
    (first-thread-wins, the serialization order of the kernel's zone
    lock).  Like :func:`fault_step_mask` — whose per-step predicate is
    just ``(schedule & SCHED_DO).any(axis=1)`` — this stays un-batched
    under a vmapped policy sweep.

    The batched engine consumes the DO/WINNER bits (masked by phase A's
    live miss set); the NEED bits document the host model's PT-entry
    conflict resolution and anchor its tests, while the engine recomputes
    those first-winner masks from live placement state, which stays exact
    even for a resumed pre-populated state (where a cross-segment free
    may have orphaned a leaf the host model cannot see).

    The host model assumes allocations succeed; past a lane's OOM point
    the bits over-approximate, and the device gates every request on its
    per-thread OOM latch (``alloc_many``'s ``gate``), under which the
    lane is inert anyway.  Results are memoized on a digest of the trace
    contents — figures sharing padded traces pay the host pass once.  The
    schedule comes from first touches, one array pass per stretch between
    segment frees (:func:`_first_touch_schedule`).
    """
    shift, n_map, rb = mc.map_shift, mc.n_map, mc.radix_bits
    n_leaf, n_mid, n_top = mc.n_leaf_pages, mc.n_mid_pages, mc.n_top_pages
    va = np.asarray(tr.va)
    seg = np.asarray(tr.seg_of_map)
    free_seg = np.asarray(tr.free_seg)
    h = hashlib.blake2b(digest_size=16)
    for a in (va, free_seg, seg):
        h.update(np.ascontiguousarray(a))
    key = (h.digest(), va.shape, shift, n_map, rb, n_leaf, n_mid, n_top)
    hit = _SCHED_CACHE.get(key)
    if hit is not None:
        _SCHED_CACHE.move_to_end(key)
        return hit

    sched = _first_touch_schedule(va, seg, free_seg, mc)
    _SCHED_CACHE[key] = sched
    while len(_SCHED_CACHE) > _SCHED_CACHE_MAX:
        _SCHED_CACHE.popitem(last=False)
    return sched


def _first_touch_schedule(va: np.ndarray, seg: np.ndarray,
                          free_seg: np.ndarray,
                          mc: MachineConfig) -> np.ndarray:
    """:func:`fault_schedule` from first touches, one array pass per
    stretch of steps between segment frees.  Within a stretch mapped-ness
    and PT-entry existence only grow, so a thread faults exactly where a
    granule unmapped at the stretch's start is touched for the first time
    in it (in (step, thread) order); the first such touch is the granule's
    winner, and the first winner under each missing root/top/mid/leaf
    entry allocates it.  A free, applied before its step's accesses,
    unmaps its segment's granules and leaf entries."""
    shift, n_map, rb = mc.map_shift, mc.n_map, mc.radix_bits
    n_leaf, n_mid, n_top = mc.n_leaf_pages, mc.n_mid_pages, mc.n_top_pages
    leaf_first = (np.arange(n_leaf, dtype=np.int64) << rb) % max(n_map, 1)
    seg_of_leaf = seg[leaf_first]
    mapped = np.zeros(n_map, bool)
    # PT-entry existence per level, root first (only leaves are freed)
    exists = (np.zeros(1, bool), np.zeros(n_top, bool),
              np.zeros(n_mid, bool), np.zeros(n_leaf, bool))
    S, T = va.shape
    sched = np.zeros(S * T, np.uint8)
    flat = va.reshape(-1)
    starts = np.union1d([0], np.flatnonzero(free_seg >= 0))
    for a, b in zip(starts, np.append(starts[1:], S)):
        if free_seg[a] >= 0:
            mapped[seg == free_seg[a]] = False
            exists[3][seg_of_leaf == free_seg[a]] = False
        pos = a * T + np.flatnonzero(flat[a * T:b * T] >= 0)
        m = np.clip(flat[pos].astype(np.int64) >> shift, 0, n_map - 1)
        cold = ~mapped[m]
        pos, m = pos[cold], m[cold]
        if not len(pos):
            continue
        g, first, inv = np.unique(m, return_index=True, return_inverse=True)
        win = pos[first]                            # each granule's winner
        sched[pos[(win // T)[inv] == pos // T]] |= SCHED_DO
        order = np.argsort(win)
        win, g = win[order], g[order]
        sched[win] |= SCHED_WINNER
        for bit, ex, e in (
                (SCHED_NEED_ROOT, exists[0], np.zeros(len(g), np.int64)),
                (SCHED_NEED_TOP, exists[1], np.clip(g >> (3 * rb), 0,
                                                    n_top - 1)),
                (SCHED_NEED_MID, exists[2], np.clip(g >> (2 * rb), 0,
                                                    n_mid - 1)),
                (SCHED_NEED_LEAF, exists[3], g >> rb)):
            miss = ~ex[e]
            uniq, f = np.unique(e[miss], return_index=True)
            sched[win[miss][f]] |= bit
            ex[uniq] = True
        mapped[g] = True
    return sched.reshape(S, T)


def fault_step_mask(tr: Trace, mc: MachineConfig) -> np.ndarray:
    """bool[steps]: does ANY thread touch an unmapped page at step s?

    Drives the un-batched ``lax.cond`` that skips phase B entirely on
    fault-free steps even when the step is vmapped over policies.  For a
    simulation resumed from a pre-populated state this is an
    over-approximation (phase B runs and no-ops), never an
    under-approximation.
    """
    return np.asarray((fault_schedule(tr, mc) & SCHED_DO) > 0).any(axis=1)


def scan_step_mask(n_steps: int, period: int, enabled: bool = True,
                   start_step: int = 0) -> np.ndarray:
    """bool[steps]: does the periodic AutoNUMA scan fire at step s?"""
    s = np.arange(start_step, start_step + n_steps)
    return (s > 0) & (s % max(int(period), 1) == 0) & bool(enabled)


def pow2ceil(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor)."""
    p = max(int(floor), 1)
    while p < n:
        p <<= 1
    return p


# Step-window size of the time-blocked engine.  Fixed per compile; the
# window count ceil(S / block) depends only on the trace shape, never its
# content, so executables keep quantizing across trace mixes.
DEFAULT_BLOCK = 64


@dataclasses.dataclass
class RunResult:
    final_state: SimState          # host-side pytree of numpy arrays
    timeline: Dict[str, np.ndarray]
    trace_name: str
    policy_label: str

    def summary(self) -> Dict[str, float]:
        st = self.final_state
        cyc = st.cycles
        # Migration-daemon cycles were already spread into per-thread totals
        # inside the step function; ``migration_cycles`` is informational.
        total = float(np.sum(cyc.total))
        runtime = float(np.max(cyc.total))
        walk = float(np.sum(cyc.walk))
        stall = float(np.sum(cyc.stall))
        c = st.counters
        leaf_nodes = np.asarray(st.leaf_node)
        alive = leaf_nodes >= 0
        data = np.asarray(st.data_node)
        return {
            "runtime_cycles": runtime,
            "total_cycles": total,
            "walk_cycles": walk,
            "stall_cycles": stall,
            "data_mem_cycles": float(np.sum(cyc.data_mem)),
            "fault_cycles": float(np.sum(cyc.fault)),
            "migration_cycles": float(cyc.migration),
            "walk_share": walk / max(total, 1.0),
            "l1_hits": int(c.l1_hits), "stlb_hits": int(c.stlb_hits),
            "walks": int(c.walks), "walk_mem_reads": int(c.walk_mem_reads),
            "faults": int(c.faults),
            "slow_allocs": int(c.slow_allocs),
            "data_migrations": int(c.data_migrations),
            "demotions": int(c.demotions),
            "l4_mig_success": int(c.l4_mig_success),
            "l4_mig_already_dest": int(c.l4_mig_already_dest),
            "l4_mig_in_dram": int(c.l4_mig_in_dram),
            "l4_mig_sibling_guard": int(c.l4_mig_sibling_guard),
            "l4_mig_lock_skip": int(c.l4_mig_lock_skip),
            "oom_killed": bool(st.oom_killed), "oom_step": int(st.oom_step),
            "leaf_pages_dram": int(np.sum(alive & (leaf_nodes < 2))),
            "leaf_pages_nvmm": int(np.sum(alive & (leaf_nodes >= 2))),
            "data_pages_dram": int(np.sum((data >= 0) & (data < 2))),
            "data_pages_nvmm": int(np.sum(data >= 2)),
            # N-tier / policy-family extensions (tier t owns nodes 2t,
            # 2t+1; on the 2-tier machine the per-tier lists reduce to the
            # dram/nvmm pairs above).
            "data_pages_per_tier": [
                int(np.sum((data >= 2 * t) & (data < 2 * t + 2)))
                for t in range(np.asarray(st.node_free).shape[0] // 2)],
            "leaf_pages_per_tier": [
                int(np.sum(alive & (leaf_nodes >= 2 * t)
                           & (leaf_nodes < 2 * t + 2)))
                for t in range(np.asarray(st.node_free).shape[0] // 2)],
            "shadow_pages": int(np.sum(np.asarray(st.shadow_node) >= 0)),
            "nomad_retries": int(c.nomad_retries),
            "nomad_flip_demotions": int(c.nomad_flip_demotions),
            "nomad_shadow_drops": int(c.nomad_shadow_drops),
        }


TIMELINE_KEYS = ("total_cycles", "walk_cycles", "stall_cycles", "faults",
                 "dram_free", "leaf_nvmm", "leaf_dram", "walks",
                 "data_migrations", "l4_mig_success", "migration_cycles",
                 "data_mem_cycles", "fault_cycles", "l1_hits", "stlb_hits")


def _build_scan_op(mc: MachineConfig, budget: int):
    """Build the standalone migration scan-tick operator.

    One AutoNUMA/TPP/Nomad periodic scan plus its cycle accounting,
    factored out of the per-step body so the blocked engine's *hoist*
    windows can run it between two fast segments without compiling any
    per-step machinery.  ``autonuma_scan`` self-gates on
    ``pc.autonuma & ~oom_killed``, so a shared schedule can fire for
    every lane of a mixed sweep.  The tick step's access row rides along
    as Nomad's concurrent-write abort condition (a no-op input for the
    other families).  The f32 accounting order is exactly the per-step
    path's, keeping hoisted ticks bit-identical to replayed ones.
    """
    T = mc.n_threads
    wm = alloc_mod.watermark_pages(mc)

    def scan_op(st: SimState, cc: CostConfig, pc: PolicyConfig,
                va_row, w_row) -> SimState:
        s2, cost = migrate_mod.autonuma_scan(st, mc, cc, pc, wm, budget,
                                             va_row, w_row)
        cyc = dataclasses.replace(
            s2.cycles,
            total=s2.cycles.total
            + cost * jnp.asarray(cc.mig_cost_scale, F32) / T,
            migration=s2.cycles.migration + cost)
        return dataclasses.replace(s2, cycles=cyc)

    return scan_op


def _build_step(mc: MachineConfig, budget: int, phase_b: str = "batched"):
    """Build the policy-generic simulator step over a leading lane axis.

    Only MachineConfig shapes, the AutoNUMA candidate bound ``budget``
    and the ``phase_b`` engine choice are baked into the compile; every
    CostConfig/PolicyConfig value arrives per call as a traced leaf of
    the lane-stacked ``cc``/``pc`` pytrees, so one compiled step serves
    every policy bundle (``core.sweep``; a solo run is one lane).

    ``phase_b="batched"`` (default) uses the conflict-aware vectorized
    fault engine; ``"sequential"`` keeps the historical per-thread
    ``fori_loop``, retained as the differential-testing reference.
    """
    assert phase_b in ("batched", "sequential"), phase_b
    T = mc.n_threads
    shift = mc.map_shift
    n_map = mc.n_map
    rb = mc.radix_bits
    nn = mc.n_nodes
    thp = mc.page_order > 0
    wm = alloc_mod.watermark_pages(mc)
    # tier per node, indexed node+1 (node -1 -> slowest tier): one gather
    # replaces the classic is_dram() select and generalizes to N tiers
    # with identical f32 latency bits on the 2-tier machine.
    text = jnp.asarray((mc.n_tiers - 1,) + mc.tier_of_node, I32)

    def f32(v):
        return jnp.asarray(v, F32)

    def read_lat(cc, node):
        return jnp.take(migrate_mod.tier_read_lat(cc, mc),
                        jnp.take(text, node + 1))

    def write_lat(cc, node):
        return jnp.take(migrate_mod.tier_write_lat(cc, mc),
                        jnp.take(text, node + 1))

    # ------------------------------ phase A --------------------------------
    def phase_a(st: SimState, cc: CostConfig, va_row, w_row, llc_rate):
        m = jnp.clip(jnp.where(va_row >= 0, va_row >> shift, 0), 0, n_map - 1)
        tid = jnp.arange(T, dtype=I32)
        mapped = jnp.take(st.data_node, m) >= 0
        active = (va_row >= 0) & ~st.oom_killed
        vec = active & mapped
        now = st.step

        hit1, way1 = tlbs.lookup(st.l1_tlb, m)
        hit2, way2 = tlbs.lookup(st.stlb, m)
        walkn = vec & ~hit1 & ~hit2

        leaf_id, mid_id = m >> rb, m >> (2 * rb)
        top_id = m >> (3 * rb)
        pde_hit, pde_way = tlbs.lookup(st.pde_pwc, leaf_id)
        pdpte_hit, pdpte_way = tlbs.lookup(st.pdpte_pwc, mid_id)

        leaf_n = jnp.take(st.leaf_node, leaf_id)
        mid_n = jnp.take(st.mid_node, jnp.clip(mid_id, 0, st.mid_node.shape[0] - 1))
        top_n = jnp.take(st.top_node, jnp.clip(top_id, 0, st.top_node.shape[0] - 1))

        leaf_llc = bern(cc.leaf_llc_hit, 1, m, now, tid)
        up1_llc = bern(cc.upper_llc_hit, 2, mid_id, now, tid)
        up2_llc = bern(cc.upper_llc_hit, 3, top_id, now, tid)

        leaf_read = jnp.where(leaf_llc, f32(cc.llc_hit), read_lat(cc, leaf_n))
        mid_read = jnp.where(pde_hit, 0.0,
                             jnp.where(up1_llc, f32(cc.llc_hit),
                                       read_lat(cc, mid_n)))
        full = ~pde_hit & ~pdpte_hit
        if thp:
            top_read = jnp.zeros((T,), F32)
        else:
            top_read = jnp.where(full,
                                 jnp.where(up2_llc, f32(cc.llc_hit),
                                           read_lat(cc, top_n)), 0.0)
        root_read = jnp.where(full, f32(cc.llc_hit), 0.0)
        walk_cost = jnp.where(walkn, leaf_read + mid_read + top_read + root_read, 0.0)
        walk_reads = jnp.where(
            walkn,
            (~leaf_llc).astype(I32) + (~pde_hit & ~up1_llc).astype(I32)
            + ((full & ~up2_llc).astype(I32) if not thp else 0),
            0)

        data_n = jnp.take(st.data_node, m)
        data_llc = bern(llc_rate, 4, m, now, tid)
        mem_lat = jnp.where(w_row, write_lat(cc, data_n), read_lat(cc, data_n))
        data_cost = jnp.where(vec, jnp.where(data_llc, f32(cc.llc_hit),
                                             mem_lat), 0.0)

        tlb_penalty = jnp.where(vec & ~hit1, f32(cc.stlb_hit), 0.0)
        stall = walk_cost + f32(cc.data_stall_frac) * data_cost
        total = jnp.where(vec, f32(cc.cpu_work), 0.0) + tlb_penalty + stall

        l1_tlb = tlbs.update(st.l1_tlb, m, way1, now, vec)
        stlb = tlbs.update(st.stlb, m, way2, now, vec & ~hit1)
        pde = tlbs.update(st.pde_pwc, leaf_id, pde_way, now, walkn)
        pdpte = tlbs.update(st.pdpte_pwc, mid_id, pdpte_way, now, walkn)

        access_recent = st.access_recent.at[
            jnp.where(vec, m, n_map)].add(1, mode="drop")
        written_recent = st.written_recent.at[
            jnp.where(vec & w_row, m, n_map)].add(1, mode="drop")

        cyc = st.cycles
        cyc = dataclasses.replace(
            cyc, total=cyc.total + total, walk=cyc.walk + walk_cost,
            stall=cyc.stall + stall, data_mem=cyc.data_mem + data_cost)
        c = st.counters
        c = dataclasses.replace(
            c,
            l1_hits=c.l1_hits + jnp.sum((vec & hit1).astype(I32)),
            stlb_hits=c.stlb_hits + jnp.sum((vec & ~hit1 & hit2).astype(I32)),
            walks=c.walks + jnp.sum(walkn.astype(I32)),
            walk_mem_reads=c.walk_mem_reads + jnp.sum(walk_reads))
        st = dataclasses.replace(st, l1_tlb=l1_tlb, stlb=stlb, pde_pwc=pde,
                                 pdpte_pwc=pdpte, access_recent=access_recent,
                                 written_recent=written_recent,
                                 cycles=cyc, counters=c)
        return st, active & ~mapped

    # ------------------------------ phase B --------------------------------
    def _alloc_pt_level(st: SimState, cc: CostConfig, pc: PolicyConfig, t,
                        node_arr, idx, is_upper: bool, cost_acc):
        missing = node_arr[idx] < 0
        # recompute per allocation: the interleave cursor advances with
        # every page handed out (PT pages consume round-robin slots too,
        # paper section 3.2 / Fig. 5)
        data_prefs = alloc_mod.data_prefs_for(pc.data_policy, t, mc,
                                              st.interleave_ptr)
        prefs, ignore_wm = alloc_mod.pt_prefs_for(
            pc.pt_policy, is_upper, t, mc, data_prefs, thp)
        node, slow, nf, nr, ok = alloc_mod.alloc_one(
            st.node_free, st.node_reclaimable, prefs, wm, ignore_wm)
        if is_upper or thp:
            # BHi falls back to the data policy when DRAM is exhausted.
            # Both allocations are computed and the fallback selected per
            # (possibly vmapped) lane so the branch stays traced.
            node2, slow2, nf2, nr2, ok2 = alloc_mod.alloc_one(
                st.node_free, st.node_reclaimable, data_prefs, wm,
                jnp.asarray(False))
            is_bhi = jnp.asarray(pc.pt_policy) == PT_BIND_HIGH
            use_fb = is_bhi & ~ok
            node = jnp.where(use_fb, node2, node)
            slow = jnp.where(use_fb, slow2, slow)
            nf = jnp.where(use_fb, nf2, nf)
            nr = jnp.where(use_fb, nr2, nr)
            ok = ok | (is_bhi & ok2)
        oom = missing & ~ok            # bind_all pathology (section 3.5)
        do = missing & ok
        node_arr = node_arr.at[idx].set(jnp.where(do, node, node_arr[idx]))
        zero_cost = jnp.where(do, cc.zero_lines * write_lat(cc, node), 0.0)
        acost = jnp.where(do, jnp.where(slow, f32(cc.alloc_slow),
                                        f32(cc.alloc_fast)), 0.0)
        adv = do & (jnp.asarray(pc.pt_policy) == PT_FOLLOW_DATA) \
            & (jnp.asarray(pc.data_policy) == INTERLEAVE)
        st = dataclasses.replace(
            st,
            node_free=jnp.where(do, nf, st.node_free),
            node_reclaimable=jnp.where(do, nr, st.node_reclaimable),
            interleave_ptr=st.interleave_ptr + adv.astype(I32),
            oom_killed=st.oom_killed | oom,
            oom_step=jnp.where(oom & (st.oom_step < 0), st.step, st.oom_step),
            counters=dataclasses.replace(
                st.counters,
                pt_allocs=st.counters.pt_allocs.at[
                    jnp.clip(node, 0, nn - 1)].add(jnp.where(do, 1, 0)),
                slow_allocs=st.counters.slow_allocs + jnp.where(do & slow, 1, 0),
                oom_kills=st.counters.oom_kills + oom.astype(I32)))
        cost_acc = cost_acc + zero_cost + acost + jnp.where(
            oom, f32(cc.oom_scan), 0.0)
        return st, node_arr, cost_acc

    def phase_b_body(t, carry):
        st, cc, pc, va_row, w_row, fault_mask = carry
        va_t = va_row[t]
        m = jnp.clip(jnp.where(va_t >= 0, va_t >> shift, 0), 0, n_map - 1)
        do = fault_mask[t] & ~st.oom_killed
        now = st.step

        now_mapped = st.data_node[m] >= 0
        wait = do & now_mapped
        fault = do & ~now_mapped
        wait_cost = jnp.where(wait, cc.fault_base + f32(cc.llc_hit), 0.0)

        tI = jnp.asarray(t, I32)

        def run_fault(st):
            c = jnp.zeros((), F32)
            st2, root, c = _alloc_pt_level(st, cc, pc, tI, st.root_node, 0,
                                           True, c)
            st2 = dataclasses.replace(st2, root_node=root)
            st2, top, c = _alloc_pt_level(
                st2, cc, pc, tI, st2.top_node,
                jnp.clip(m >> (3 * rb), 0, st2.top_node.shape[0] - 1), True, c)
            st2 = dataclasses.replace(st2, top_node=top)
            st2, mid, c = _alloc_pt_level(
                st2, cc, pc, tI, st2.mid_node,
                jnp.clip(m >> (2 * rb), 0, st2.mid_node.shape[0] - 1), True, c)
            st2 = dataclasses.replace(st2, mid_node=mid)
            st2, leaf, c = _alloc_pt_level(st2, cc, pc, tI, st2.leaf_node,
                                           m >> rb, False, c)
            st2 = dataclasses.replace(st2, leaf_node=leaf)

            dprefs = alloc_mod.data_prefs_for(
                pc.data_policy, tI, mc, st2.interleave_ptr)
            node, slow, nf, nr, ok = alloc_mod.alloc_one(
                st2.node_free, st2.node_reclaimable, dprefs, wm,
                jnp.asarray(False))
            oom = ~ok
            data_node = st2.data_node.at[m].set(jnp.where(ok, node, -1))
            ldc = st2.leaf_dram_children.at[m >> rb].add(
                jnp.where(ok & is_dram(node), 1, 0))
            adv = (jnp.asarray(pc.data_policy) == INTERLEAVE) & ok
            c = c + jnp.where(ok, cc.zero_lines * write_lat(cc, node)
                              + jnp.where(slow, f32(cc.alloc_slow),
                                          f32(cc.alloc_fast)),
                              f32(cc.oom_scan))
            mid_n = st2.mid_node[jnp.clip(m >> (2 * rb), 0, st2.mid_node.shape[0] - 1)]
            leaf_n = st2.leaf_node[m >> rb]
            c = c + cc.fault_base + read_lat(cc, mid_n) + write_lat(cc, leaf_n)
            st2 = dataclasses.replace(
                st2, data_node=data_node, leaf_dram_children=ldc,
                node_free=jnp.where(ok, nf, st2.node_free),
                node_reclaimable=jnp.where(ok, nr, st2.node_reclaimable),
                interleave_ptr=st2.interleave_ptr + adv.astype(I32),
                oom_killed=st2.oom_killed | oom,
                oom_step=jnp.where(oom & (st2.oom_step < 0), st2.step,
                                   st2.oom_step),
                counters=dataclasses.replace(
                    st2.counters,
                    data_allocs=st2.counters.data_allocs.at[
                        jnp.clip(node, 0, nn - 1)].add(jnp.where(ok, 1, 0)),
                    faults=st2.counters.faults + 1,
                    oom_kills=st2.counters.oom_kills + oom.astype(I32)))
            return st2, c

        st, fcost = jax.lax.cond(fault, run_fault,
                                 lambda s: (s, jnp.zeros((), F32)), st)

        handled = wait | fault
        l1 = tlbs.update_one(st.l1_tlb, tI, m, now, handled)
        stlb_ = tlbs.update_one(st.stlb, tI, m, now, handled)
        pde = tlbs.update_one(st.pde_pwc, tI, m >> rb, now, handled)
        pdpte = tlbs.update_one(st.pdpte_pwc, tI, m >> (2 * rb), now, handled)
        access_recent = st.access_recent.at[m].add(jnp.where(handled, 1, 0))
        written_recent = st.written_recent.at[m].add(
            jnp.where(handled & w_row[t], 1, 0))

        all_cost = fcost + wait_cost
        cyc = st.cycles
        cyc = dataclasses.replace(
            cyc,
            total=cyc.total.at[t].add(all_cost),
            fault=cyc.fault.at[t].add(all_cost),
            data_mem=cyc.data_mem.at[t].add(jnp.where(wait, f32(cc.llc_hit),
                                                      0.0)))
        st = dataclasses.replace(st, l1_tlb=l1, stlb=stlb_, pde_pwc=pde,
                                 pdpte_pwc=pdpte, access_recent=access_recent,
                                 written_recent=written_recent, cycles=cyc)
        return st, cc, pc, va_row, w_row, fault_mask

    # ------------------------- phase B, batched ------------------------------
    def fault_requests(st: SimState, va_row, sched_row, fault_mask):
        """The per-lane allocator requests of a faulting row.

        Host-precomputed first-thread-wins masks (``sched_row``) resolve
        threads faulting the same PT entry or data page.  For a simulation
        resumed from a pre-populated state the host DO / WINNER bits
        over-approximate (the schedule starts from an empty address space)
        and are masked by phase A's actual miss set — host-mapped is always
        a subset of device-mapped, so the masked winner set is exactly the
        sequential fault set.  The per-PT-entry first-winner masks are
        *not* taken from the host NEED bits here: a resumed state can hold
        a truly-missing leaf whose host bit was latched onto a masked-off
        winner (a cross-segment free can clear a leaf while a sibling
        granule's data page stays mapped), so they are recomputed from
        live state — a scatter-min of thread ids over each (small)
        PT-level array, which is cheap next to the n_map commits and exact
        in every case.
        """
        m = jnp.clip(jnp.where(va_row >= 0, va_row >> shift, 0), 0, n_map - 1)
        do = ((sched_row & SCHED_DO) > 0) & fault_mask
        winner = ((sched_row & SCHED_WINNER) > 0) & fault_mask
        tid = jnp.arange(T, dtype=I32)

        top_idx = jnp.clip(m >> (3 * rb), 0, st.top_node.shape[0] - 1)
        mid_idx = jnp.clip(m >> (2 * rb), 0, st.mid_node.shape[0] - 1)
        leaf_idx = m >> rb
        pt_idx = (jnp.zeros((T,), I32), top_idx, mid_idx, leaf_idx)
        pt_arrs = (st.root_node, st.top_node, st.mid_node, st.leaf_node)
        need_cols = []
        for lvl in range(4):
            idx = pt_idx[lvl]
            n_e = pt_arrs[lvl].shape[0]
            cand = winner & (pt_arrs[lvl][idx] < 0)
            first = jnp.full((n_e,), T, I32).at[
                jnp.where(cand, idx, n_e)].min(tid, mode="drop")
            need_cols.append(cand & (first[idx] == tid))
        need_pt = jnp.stack(need_cols, axis=-1)                 # bool[T, 4]

        # Conflict-group compaction of the allocator scan: only host
        # WINNER threads ever touch the allocator carry (everyone else is
        # the identity and commutes), so the serialized scan runs over
        # winner slots, in thread order.  Slot ids are the host
        # schedule's winner prefix count; device-side winners (masked by
        # phase A on resume) are a subset of the host bits, so every
        # requesting thread owns one of the T slots.
        host_w = (sched_row & SCHED_WINNER) > 0
        slot = jnp.cumsum(host_w.astype(I32)) - 1
        slot_thread = jnp.full((T,), T, I32).at[
            jnp.where(host_w & (slot < T), slot, T)].set(tid, mode="drop")
        return m, do, winner, pt_idx, need_pt, slot_thread

    def phase_b_batched(st: SimState, cc: CostConfig, pc: PolicyConfig,
                        va_row, w_row, sched_row, fault_mask):
        """Conflict-aware vectorized fault engine.

        ``fault_requests`` gives each lane's allocator requests; the
        allocator hands out their pages; everything else — PT placement
        scatters, TLB fills, cycle/event accounting — commits vectorized
        (``fault_commit``).  Bit-identical to ``phase_b_body`` run over
        threads in index order (cycles to f32 rounding).

        The allocator has two arms.  ``alloc.alloc_vector`` allocates the
        row in one pass and says whether that is exact (no node's
        allocator class changes inside the row); where it is not, in any
        lane, the serialized ``alloc.alloc_many`` scan runs instead.  The
        per-lane parts are vmapped here, so the arm choice — the AND of
        every lane's bit — is one scalar ``lax.cond`` that really skips
        the scan rather than a per-lane select running both.
        Returns the state and the row's ``[batched, scan]`` arm count.
        """
        m, do, winner, pt_idx, need_pt, slot_thread = jax.vmap(fault_requests)(
            st, va_row, sched_row, fault_mask)

        def alloc_args(s, p):
            return (s.node_free, s.node_reclaimable, s.interleave_ptr,
                    s.oom_killed, wm, p.data_policy, p.pt_policy, mc)

        with jax.named_scope("fault.alloc_batched"):
            *batched, exact = jax.vmap(
                lambda s, p, n_pt, n_d: alloc_mod.alloc_vector(
                    *alloc_args(s, p), n_pt, n_d))(st, pc, need_pt, winner)
            exact = jnp.all(exact)

        def scan_arm():
            with jax.named_scope("fault.alloc_scan"):
                return jax.vmap(
                    lambda s, p, n_pt, n_d, sl: alloc_mod.alloc_many(
                        *alloc_args(s, p), n_pt, n_d, slot_thread=sl))(
                            st, pc, need_pt, winner, slot_thread)

        alloc = jax.lax.cond(exact, lambda: tuple(batched), scan_arm)
        st = jax.vmap(fault_commit)(st, cc, w_row, m, do, winner, pt_idx,
                                    alloc)
        return st, jnp.stack([exact, ~exact]).astype(I32)

    def fault_commit(st: SimState, cc: CostConfig, w_row, m, do, winner,
                     pt_idx, alloc):
        """Commit one lane's allocations: PT placements, data pages, TLB
        fills, cycles and counters."""
        mid_idx, leaf_idx = pt_idx[2], pt_idx[3]
        now = st.step
        nodes, slow, ok, act, gate, nfree, nrec, ptr, oom = alloc
        fault = winner & gate          # threads that run the fault handler
        wait = do & ~winner & gate     # an earlier thread mapped m this step
        handled = wait | fault

        # ---- commit PT placements (one first-winner per entry: no scatter
        # conflicts) and the data pages ----------------------------------
        commit = act & ok
        new_pt = []
        for lvl, arr in enumerate((st.root_node, st.top_node, st.mid_node,
                                   st.leaf_node)):
            oob = jnp.asarray(arr.shape[0], pt_idx[lvl].dtype)
            new_pt.append(arr.at[
                jnp.where(commit[:, lvl], pt_idx[lvl], oob)].set(
                    nodes[:, lvl], mode="drop"))
        root_node, top_node, mid_node, leaf_node = new_pt

        node_d, ok_d = nodes[:, 4], ok[:, 4]
        commit_d = commit[:, 4]
        data_node = st.data_node.at[
            jnp.where(commit_d, m, n_map)].set(node_d, mode="drop")
        ldc = st.leaf_dram_children.at[leaf_idx].add(
            jnp.where(commit_d & is_dram(node_d), 1, 0))

        # ---- cost model: replicate the sequential per-thread f32 chains ----
        c = jnp.zeros((T,), F32)
        for lvl in range(4):
            do_l = commit[:, lvl]
            zero_cost = jnp.where(do_l,
                                  cc.zero_lines * write_lat(cc, nodes[:, lvl]),
                                  0.0)
            acost = jnp.where(do_l, jnp.where(slow[:, lvl], f32(cc.alloc_slow),
                                              f32(cc.alloc_fast)), 0.0)
            c = c + zero_cost + acost + jnp.where(act[:, lvl] & ~ok[:, lvl],
                                                  f32(cc.oom_scan), 0.0)
        c = c + jnp.where(ok_d,
                          cc.zero_lines * write_lat(cc, node_d)
                          + jnp.where(slow[:, 4], f32(cc.alloc_slow),
                                      f32(cc.alloc_fast)),
                          f32(cc.oom_scan))
        mid_n = mid_node[mid_idx]      # post-commit == value the thread saw
        leaf_n = leaf_node[leaf_idx]
        c = c + cc.fault_base + read_lat(cc, mid_n) + write_lat(cc, leaf_n)
        fcost = jnp.where(fault, c, 0.0)
        wait_cost = jnp.where(wait, cc.fault_base + f32(cc.llc_hit), 0.0)
        all_cost = fcost + wait_cost

        # ---- TLB fills: thread-private structures, so the per-thread
        # touch-or-insert vectorizes directly -----------------------------
        _, way1 = tlbs.lookup(st.l1_tlb, m)
        l1 = tlbs.update(st.l1_tlb, m, way1, now, handled)
        _, way2 = tlbs.lookup(st.stlb, m)
        stlb_ = tlbs.update(st.stlb, m, way2, now, handled)
        _, way3 = tlbs.lookup(st.pde_pwc, m >> rb)
        pde = tlbs.update(st.pde_pwc, m >> rb, way3, now, handled)
        _, way4 = tlbs.lookup(st.pdpte_pwc, m >> (2 * rb))
        pdpte = tlbs.update(st.pdpte_pwc, m >> (2 * rb), way4, now, handled)
        access_recent = st.access_recent.at[
            jnp.where(handled, m, n_map)].add(1, mode="drop")
        written_recent = st.written_recent.at[
            jnp.where(handled & w_row, m, n_map)].add(1, mode="drop")

        # ---- counters and OOM latch -------------------------------------
        fails = act & ~ok
        any_fail = jnp.any(fails)
        pt_commit = commit[:, :4]
        cnt = st.counters
        cnt = dataclasses.replace(
            cnt,
            pt_allocs=cnt.pt_allocs.at[
                jnp.clip(nodes[:, :4], 0, nn - 1).ravel()].add(
                    pt_commit.ravel().astype(I32)),
            data_allocs=cnt.data_allocs.at[jnp.clip(node_d, 0, nn - 1)].add(
                jnp.where(commit_d, 1, 0)),
            slow_allocs=cnt.slow_allocs
            + jnp.sum((pt_commit & slow[:, :4]).astype(I32)),
            faults=cnt.faults + jnp.sum(fault.astype(I32)),
            oom_kills=cnt.oom_kills + jnp.sum(fails.astype(I32)))
        cyc = st.cycles
        cyc = dataclasses.replace(
            cyc, total=cyc.total + all_cost, fault=cyc.fault + all_cost,
            data_mem=cyc.data_mem + jnp.where(wait, f32(cc.llc_hit), 0.0))
        return dataclasses.replace(
            st, root_node=root_node, top_node=top_node, mid_node=mid_node,
            leaf_node=leaf_node, data_node=data_node,
            leaf_dram_children=ldc, node_free=nfree, node_reclaimable=nrec,
            interleave_ptr=ptr, oom_killed=oom,
            oom_step=jnp.where(any_fail & (st.oom_step < 0), st.step,
                               st.oom_step),
            l1_tlb=l1, stlb=stlb_, pde_pwc=pde, pdpte_pwc=pdpte,
            access_recent=access_recent, written_recent=written_recent,
            cycles=cyc, counters=cnt)

    # ------------------------------ frees -----------------------------------
    def free_segment(st: SimState, fid, seg_of_map, seg_of_leaf):
        mask_map = (seg_of_map == fid) & (st.data_node >= 0)
        freed_per_node = jnp.zeros((nn,), I32).at[
            jnp.clip(st.data_node, 0, nn - 1)].add(mask_map.astype(I32))
        freed_dram = mask_map & is_dram(st.data_node)
        ldc = st.leaf_dram_children.at[jnp.arange(n_map) >> rb].add(
            -freed_dram.astype(I32))
        data_node = jnp.where(mask_map, -1, st.data_node)
        # Nomad shadows of freed granules are released with the segment.
        mask_shadow = (seg_of_map == fid) & (st.shadow_node >= 0)
        freed_shadow = jnp.zeros((nn,), I32).at[
            jnp.clip(st.shadow_node, 0, nn - 1)].add(mask_shadow.astype(I32))
        shadow_node = jnp.where(mask_shadow, -1, st.shadow_node)
        mask_leaf = (seg_of_leaf == fid) & (st.leaf_node >= 0)
        freed_leaf = jnp.zeros((nn,), I32).at[
            jnp.clip(st.leaf_node, 0, nn - 1)].add(mask_leaf.astype(I32))
        leaf_node = jnp.where(mask_leaf, -1, st.leaf_node)
        l1 = tlbs.invalidate_matching(st.l1_tlb, mask_map, 0)
        stlb_ = tlbs.invalidate_matching(st.stlb, mask_map, 0)
        pde = tlbs.invalidate_matching(st.pde_pwc, mask_leaf, 0)
        return dataclasses.replace(
            st, data_node=data_node, leaf_node=leaf_node,
            shadow_node=shadow_node,
            leaf_dram_children=jnp.maximum(ldc, 0),
            node_free=st.node_free + freed_per_node + freed_leaf
            + freed_shadow,
            access_recent=jnp.where(mask_map, 0, st.access_recent),
            written_recent=jnp.where(mask_map, 0, st.written_recent),
            l1_tlb=l1, stlb=stlb_, pde_pwc=pde)

    # ------------------------------ full step --------------------------------
    # The three schedule predicates (do_free / do_scan / has_fault) arrive
    # precomputed from the trace so they stay un-batched under vmap and the
    # lax.conds keep actually skipping work in a batched policy sweep; the
    # per-thread fault schedule row (``sched_row``, fault_schedule bits)
    # rides along as ordinary masked data.
    scan_op = _build_scan_op(mc, budget)

    def timeline_row(st: SimState):
        return (jnp.sum(st.cycles.total), jnp.sum(st.cycles.walk),
                jnp.sum(st.cycles.stall), st.counters.faults,
                st.node_free[0] + st.node_free[1],
                jnp.sum((st.leaf_node >= 2).astype(I32)),
                jnp.sum(((st.leaf_node >= 0) & (st.leaf_node < 2)).astype(I32)),
                st.counters.walks, st.counters.data_migrations,
                st.counters.l4_mig_success, st.cycles.migration,
                jnp.sum(st.cycles.data_mem), jnp.sum(st.cycles.fault),
                st.counters.l1_hits, st.counters.stlb_hits)

    # ``lean=True`` is the row body of a window the host schedule shows
    # has no free, no scan tick and a fault on every row (``plan_windows``,
    # WIN_LEAN): it drops the free and scan conds (``cond(False, f, id)``
    # is the identity) and runs phase B unconditionally (``cond(True, f,
    # id)`` is ``f``), so it is bit-identical to the general row and only
    # skips the carried-state copies in and out of each ``lax.cond``.
    #
    # The step runs a lane-stacked state: every argument except the four
    # schedule predicates carries a leading lane axis, and the step vmaps
    # its per-lane parts itself so that the fault allocator's arm choice
    # (``phase_b_batched``) stays outside the vmap.  The step returns the
    # state, the timeline row and the row's allocator arm count
    # ``[batched, scan]`` (zeros where phase B did not run).
    def step(st: SimState, cc: CostConfig, pc: PolicyConfig, x,
             seg_of_map, seg_of_leaf, lean: bool = False):
        va_row, w_row, fid, llc_rate, sched_row, do_free, do_scan, \
            has_fault, valid = x

        def before_fault(st, cc, pc, va_row, w_row, fid, llc_rate,
                         seg_of_map, seg_of_leaf):
            if not lean:
                with jax.named_scope("step.free"):
                    st = jax.lax.cond(
                        do_free,
                        lambda s: free_segment(s, fid, seg_of_map,
                                               seg_of_leaf),
                        lambda s: s, st)
                with jax.named_scope("step.scan"):
                    st = jax.lax.cond(
                        do_scan, lambda s: scan_op(s, cc, pc, va_row, w_row),
                        lambda s: s, st)
            with jax.named_scope("step.access"):
                return phase_a(st, cc, va_row, w_row, llc_rate)

        st, fault_mask = jax.vmap(before_fault)(st, cc, pc, va_row, w_row,
                                                fid, llc_rate, seg_of_map,
                                                seg_of_leaf)

        if phase_b == "batched":
            def run_phase_b(st):
                return phase_b_batched(st, cc, pc, va_row, w_row, sched_row,
                                       fault_mask)
        else:
            def sequential(st, cc, pc, va_row, w_row, fault_mask):
                st2, _, _, _, _, _ = jax.lax.fori_loop(
                    0, T, phase_b_body, (st, cc, pc, va_row, w_row,
                                         fault_mask))
                return st2

            def run_phase_b(st):
                return jax.vmap(sequential)(st, cc, pc, va_row, w_row,
                                            fault_mask), no_alloc()
        # faults are bursty (populate) or rare (steady state): skip the
        # fault engine entirely on fault-free steps
        with jax.named_scope("step.fault"):
            if lean:
                st, alloc_path = run_phase_b(st)
            else:
                st, alloc_path = jax.lax.cond(
                    has_fault, run_phase_b, lambda s: (s, no_alloc()), st)
        # idle pad rows of a time-blocked window carry valid=False and
        # must not advance the step clock (it stamps TLB LRU and bern)
        st = dataclasses.replace(
            st, step=st.step + jnp.asarray(valid).astype(I32))
        return st, jax.vmap(timeline_row)(st), alloc_path

    return step


def _build_fast_window(mc: MachineConfig):
    """Build the event-free-window executor of the time-blocked engine.

    Executes a ``[block, T]`` tile of steps with no segment frees, no
    AutoNUMA ticks and no faults as one scan step.  Placement arrays are
    constant across such a tile (only phase B, frees and migrations move
    them), so every gather, Bernoulli draw and latency term is
    precomputed vectorized over the whole tile; the inner ``lax.scan``
    threads only the genuinely sequential state — the four TLB/PWC
    structures (LRU contents chain step to step), the per-thread f32
    cycle accumulators and the three hit counters the timeline reports —
    and replays the per-step cost expressions in per-step order, so the
    result is bit-identical to running ``phase_a`` row by row (cycles
    included, not just to f32 rounding).

    Mapped-ness needs no check: a window is only event-free when no
    thread touches a host-unmapped page, and host-mapped is a subset of
    device-mapped (resume masking, ``fault_schedule``), so every active
    access hits a mapped page exactly as the per-step path would see it.
    """
    T = mc.n_threads
    shift = mc.map_shift
    n_map = mc.n_map
    rb = mc.radix_bits
    thp = mc.page_order > 0
    text = jnp.asarray((mc.n_tiers - 1,) + mc.tier_of_node, I32)

    def f32(v):
        return jnp.asarray(v, F32)

    def read_lat(cc, node):
        return jnp.take(migrate_mod.tier_read_lat(cc, mc),
                        jnp.take(text, node + 1))

    def write_lat(cc, node):
        return jnp.take(migrate_mod.tier_write_lat(cc, mc),
                        jnp.take(text, node + 1))

    def fast_window(st: SimState, cc: CostConfig, va_blk, wr_blk, llc_blk,
                    valid_blk):
        B = va_blk.shape[0]
        m = jnp.clip(jnp.where(va_blk >= 0, va_blk >> shift, 0), 0,
                     n_map - 1)
        tid = jnp.arange(T, dtype=I32)
        active = (va_blk >= 0) & valid_blk[:, None] & ~st.oom_killed
        now_rows = st.step + jnp.arange(B, dtype=I32)
        nowc = now_rows[:, None]

        leaf_id, mid_id = m >> rb, m >> (2 * rb)
        top_id = m >> (3 * rb)
        leaf_n = jnp.take(st.leaf_node, leaf_id)
        mid_n = jnp.take(st.mid_node,
                         jnp.clip(mid_id, 0, st.mid_node.shape[0] - 1))
        top_n = jnp.take(st.top_node,
                         jnp.clip(top_id, 0, st.top_node.shape[0] - 1))
        data_n = jnp.take(st.data_node, m)

        leaf_llc = bern(cc.leaf_llc_hit, 1, m, nowc, tid)
        up1_llc = bern(cc.upper_llc_hit, 2, mid_id, nowc, tid)
        up2_llc = bern(cc.upper_llc_hit, 3, top_id, nowc, tid)
        data_llc = bern(llc_blk[:, None], 4, m, nowc, tid)

        # Latency terms that don't depend on the TLB outcome — selected
        # (never summed) until the inner scan, so f32 bits match phase_a.
        leaf_read = jnp.where(leaf_llc, f32(cc.llc_hit),
                              read_lat(cc, leaf_n))
        mid_read_miss = jnp.where(up1_llc, f32(cc.llc_hit),
                                  read_lat(cc, mid_n))
        top_read_miss = jnp.where(up2_llc, f32(cc.llc_hit),
                                  read_lat(cc, top_n))
        mem_lat = jnp.where(wr_blk, write_lat(cc, data_n),
                            read_lat(cc, data_n))
        data_cost = jnp.where(active, jnp.where(data_llc, f32(cc.llc_hit),
                                                mem_lat), 0.0)
        zerosT = jnp.zeros((T,), F32)

        def row(carry, xr):
            (l1, stlb_c, pde, pdpte, ct, cwk, cst, cdm,
             n_l1, n_stlb, n_walk, n_wmr) = carry
            (m_r, act_r, now_s, leaf_r, mid_r, lread_r, mread_r, tread_r,
             dcost_r, leaf_llc_r, up1_r, up2_r) = xr
            hit1, way1 = tlbs.lookup(l1, m_r)
            hit2, way2 = tlbs.lookup(stlb_c, m_r)
            walkn = act_r & ~hit1 & ~hit2
            pde_hit, pde_way = tlbs.lookup(pde, leaf_r)
            pdpte_hit, pdpte_way = tlbs.lookup(pdpte, mid_r)

            mid_read = jnp.where(pde_hit, 0.0, mread_r)
            full = ~pde_hit & ~pdpte_hit
            if thp:
                top_read = zerosT
            else:
                top_read = jnp.where(full, tread_r, 0.0)
            root_read = jnp.where(full, f32(cc.llc_hit), 0.0)
            walk_cost = jnp.where(
                walkn, lread_r + mid_read + top_read + root_read, 0.0)
            walk_reads = jnp.where(
                walkn,
                (~leaf_llc_r).astype(I32) + (~pde_hit & ~up1_r).astype(I32)
                + ((full & ~up2_r).astype(I32) if not thp else 0),
                0)
            tlb_penalty = jnp.where(act_r & ~hit1, f32(cc.stlb_hit), 0.0)
            stall = walk_cost + f32(cc.data_stall_frac) * dcost_r
            total = jnp.where(act_r, f32(cc.cpu_work), 0.0) \
                + tlb_penalty + stall

            l1 = tlbs.update(l1, m_r, way1, now_s, act_r)
            stlb_c = tlbs.update(stlb_c, m_r, way2, now_s, act_r & ~hit1)
            pde = tlbs.update(pde, leaf_r, pde_way, now_s, walkn)
            pdpte = tlbs.update(pdpte, mid_r, pdpte_way, now_s, walkn)

            ct = ct + total
            cwk = cwk + walk_cost
            cst = cst + stall
            cdm = cdm + dcost_r
            n_l1 = n_l1 + jnp.sum((act_r & hit1).astype(I32))
            n_stlb = n_stlb + jnp.sum((act_r & ~hit1 & hit2).astype(I32))
            n_walk = n_walk + jnp.sum(walkn.astype(I32))
            n_wmr = n_wmr + jnp.sum(walk_reads)
            carry = (l1, stlb_c, pde, pdpte, ct, cwk, cst, cdm,
                     n_l1, n_stlb, n_walk, n_wmr)
            out = (jnp.sum(ct), jnp.sum(cwk), jnp.sum(cst), jnp.sum(cdm),
                   n_l1, n_stlb, n_walk)
            return carry, out

        cyc, cnt = st.cycles, st.counters
        carry0 = (st.l1_tlb, st.stlb, st.pde_pwc, st.pdpte_pwc,
                  cyc.total, cyc.walk, cyc.stall, cyc.data_mem,
                  cnt.l1_hits, cnt.stlb_hits, cnt.walks, cnt.walk_mem_reads)
        xs = (m, active, now_rows, leaf_id, mid_id, leaf_read,
              mid_read_miss, top_read_miss, data_cost, leaf_llc, up1_llc,
              up2_llc)
        carry, rows = jax.lax.scan(row, carry0, xs)
        (l1, stlb_c, pde, pdpte, ct, cwk, cst, cdm,
         n_l1, n_stlb, n_walk, n_wmr) = carry
        tot_r, walk_r, stall_r, dmem_r, l1_r, stlb_r, walks_r = rows

        access_recent = st.access_recent.at[
            jnp.where(active, m, n_map)].add(1, mode="drop")
        # Per-row adds commute (integer), so one whole-tile scatter equals
        # the per-step path bit-for-bit; no scan tick can observe a
        # mid-window value (event-free windows have no scans).
        written_recent = st.written_recent.at[
            jnp.where(active & wr_blk, m, n_map)].add(1, mode="drop")
        cyc = dataclasses.replace(cyc, total=ct, walk=cwk, stall=cst,
                                  data_mem=cdm)
        cnt = dataclasses.replace(cnt, l1_hits=n_l1, stlb_hits=n_stlb,
                                  walks=n_walk, walk_mem_reads=n_wmr)
        st = dataclasses.replace(
            st, l1_tlb=l1, stlb=stlb_c, pde_pwc=pde, pdpte_pwc=pdpte,
            access_recent=access_recent, written_recent=written_recent,
            cycles=cyc, counters=cnt,
            step=st.step + jnp.sum(valid_blk.astype(I32)))

        def const(v):
            return jnp.broadcast_to(v, (B,))

        # Per-row cumulative timeline, same order as step()'s out tuple;
        # quantities phase A cannot move are window constants.
        out = (tot_r, walk_r, stall_r,
               const(st.counters.faults),
               const(st.node_free[0] + st.node_free[1]),
               const(jnp.sum((st.leaf_node >= 2).astype(I32))),
               const(jnp.sum(((st.leaf_node >= 0)
                              & (st.leaf_node < 2)).astype(I32))),
               walks_r,
               const(st.counters.data_migrations),
               const(st.counters.l4_mig_success),
               const(st.cycles.migration),
               dmem_r,
               const(jnp.sum(st.cycles.fault)),
               l1_r, stlb_r)
        return st, out

    return fast_window


def _geom_out_rows(geom, block: int) -> int:
    """Rows each compiled window emits (``R_out``): every branch of a
    geometry pads its concatenated segment outputs to one shared width so
    ``lax.switch`` arms agree on shapes."""
    r = block
    if geom is not None:
        _, hoist, split = geom
        if hoist is not None:
            r = max(r, hoist[0] + hoist[1])
        if split is not None:
            r = max(r, split[0] + split[1] + split[2])
    return r


def _geom_rows_in(geom, block: int) -> int:
    """Host row-padding of each window's input tile.  The hoist/split
    branches carve segments with ``dynamic_slice`` at traced offsets;
    slices must never clamp (clamping would misalign rows) and must never
    read the next window's rows, so each window is padded independently
    to ``2 * block`` rows whenever such a branch exists."""
    if geom is not None and (geom[1] is not None or geom[2] is not None):
        return 2 * block
    return block


def _normalize_blocked(budget: int, phase_b: str, geom):
    """Canonicalize compile-key components a blocked geometry provably
    never feeds into the compiled program, so distinct callers share one
    executable: without a full/split branch no per-step body is built
    (the phase-B engine choice is dead), and without any scan-capable
    branch the AutoNUMA candidate bound is dead too."""
    needs_step = geom is not None and (bool(geom[0]) or geom[2] is not None)
    needs_scan = needs_step or (geom is not None and geom[1] is not None)
    if not needs_step:
        phase_b = "batched"
    if not needs_scan:
        budget = 0
    return budget, phase_b


def _build_blocked_body(mc: MachineConfig, budget: int, phase_b: str,
                        block: int, geom):
    """Build the per-window body of the time-blocked engine over a
    leading lane axis (the runner is ``sweep._sweep_runner``; a solo run
    is one lane).

    ``geom`` is the host-quantized split geometry from
    :func:`plan_windows` — ``None`` (every window is fast: the compiled
    program contains no dispatch, no per-step body and no scan op at
    all) or ``(has_full, (Ph, Qh) | None, (Ps, Es, Qs) | None)``.  The
    body dispatches over at most five window kinds via ``lax.switch``
    (the kind index is host data shared by every lane, so the branch
    survives a vmapped sweep):

      fast    the whole window as one ``fast_window`` call;
      full    whole-window per-step replay (wide event spans, and
              partial tail windows with faults);
      lean    whole-window per-step replay through the cond-free row
              body (``step(..., lean=True)``); compiled wherever full
              is, so whether a window is lean never enters the key;
      hoist   fast prefix -> one hoisted scan tick -> fast suffix, with
              *zero* per-step rows — the AutoNUMA-cadence fast path;
      split   fast prefix -> per-step replay of the (narrow) event span
              -> fast suffix.

    Each kind runs under ``jax.named_scope("window.<kind>")``, and the
    per-step body's phases under ``step.free``, ``step.scan``,
    ``step.access`` and ``step.fault`` (``_build_step``; the lean body
    has only the last two; inside ``step.fault`` the allocator's vector
    pass is ``fault.alloc_batched`` and its scan arm
    ``fault.alloc_scan``), and a hoisted scan tick under ``mig.scan``
    inside ``window.hoist`` (a replayed tick stays ``step.scan``), so
    device ops carry stable names in a profiler trace; scopes change op
    metadata only.

    Segment capacities come from ``geom``; each segment's live length
    arrives as traced offsets (``a_idx``/``b_idx``) and is enforced
    in-body by masking ``valid`` (and ``va`` for the split span) — rows
    beyond a live segment are exact no-ops of the same form as the
    window pad rows, so a branch is bit-identical to replaying its
    window per-step.  Branch outputs are zero-padded to a shared
    ``R_out`` row count; :func:`plan_windows` emits the matching
    ``emit_valid`` mask that maps emitted rows back to trace steps.
    """
    fast_window = _build_fast_window(mc)
    has_full = bool(geom[0]) if geom is not None else False
    hoist = geom[1] if geom is not None else None
    split = geom[2] if geom is not None else None
    needs_step = has_full or split is not None
    step = _build_step(mc, budget, phase_b) if needs_step else None
    scan_op = _build_scan_op(mc, budget) if hoist is not None else None
    r_out = _geom_out_rows(geom, block)

    def run_fast(s, cc, va, wr, llc, vl):
        def lane(st1, cc1, va1, w1, llc1):
            return fast_window(st1, cc1, va1, w1, llc1, vl)
        st2, outs = jax.vmap(lane, in_axes=(0, 0, 1, 1, 1))(
            s, cc, va, wr, llc)
        # back to rows-major [rows, L] so the flattened timeline keeps
        # per-step semantics per lane
        return st2, jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), outs)

    def run_scan(s, cc, pc, va_row, w_row):
        with jax.named_scope("mig.scan"):
            return jax.vmap(scan_op)(s, cc, pc, va_row, w_row)

    def run_steps(s, cc, pc, arrs, seg_of_map, seg_of_leaf, lean=False):
        return scan_rows(functools.partial(step, lean=lean))(
            s, cc, pc, arrs, seg_of_map, seg_of_leaf)

    def dsl(a, start, size):
        return jax.lax.dynamic_slice_in_dim(a, start, size, axis=0)

    def pad_rows(outs, have):
        n = r_out - have
        if n == 0:
            return outs
        return jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((n,) + a.shape[1:], a.dtype)]), outs)

    def cat_rows(chunks):
        if len(chunks) == 1:
            return chunks[0]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                            *chunks)

    def scoped(name, branch):
        def run(s):
            with jax.named_scope(name):
                return branch(s)
        return run

    def window(carry, cc, pc, xw, seg_of_map, seg_of_leaf):
        (va_w, wr_w, fid_w, llc_w, sched_w, vl_w, df_w, ds_w, hf_w,
         kind, a_idx, b_idx) = xw

        def fast_whole(s):
            s, o = run_fast(s, cc, va_w[:block], wr_w[:block],
                            llc_w[:block], vl_w[:block])
            return s, pad_rows(o, block), no_alloc()

        branches = [scoped("window.fast", fast_whole)]

        if has_full:
            def replay(lean):
                def run(s):
                    arrs = (va_w[:block], wr_w[:block], fid_w[:block],
                            llc_w[:block], sched_w[:block], df_w[:block],
                            ds_w[:block], hf_w[:block], vl_w[:block])
                    s, o, n_alloc = run_steps(s, cc, pc, arrs, seg_of_map,
                                              seg_of_leaf, lean)
                    return s, pad_rows(o, block), n_alloc
                return run
            branches.append(scoped("window.full", replay(False)))
            branches.append(scoped("window.lean", replay(True)))

        if hoist is not None:
            ph, qh = hoist

            def hoist_window(s):
                chunks = []
                if ph:
                    pv = vl_w[:ph] & (jnp.arange(ph) < a_idx)
                    s, o = run_fast(s, cc, va_w[:ph], wr_w[:ph],
                                    llc_w[:ph], pv)
                    chunks.append(o)
                s = run_scan(s, cc, pc, jnp.take(va_w, a_idx, axis=0),
                             jnp.take(wr_w, a_idx, axis=0))
                if qh:
                    s, o = run_fast(s, cc, dsl(va_w, b_idx, qh),
                                    dsl(wr_w, b_idx, qh),
                                    dsl(llc_w, b_idx, qh),
                                    dsl(vl_w, b_idx, qh))
                    chunks.append(o)
                return s, pad_rows(cat_rows(chunks), ph + qh), no_alloc()
            branches.append(scoped("window.hoist", hoist_window))

        if split is not None:
            ps, es, qs = split

            def split_window(s):
                chunks = []
                if ps:
                    pv = vl_w[:ps] & (jnp.arange(ps) < a_idx)
                    s, o = run_fast(s, cc, va_w[:ps], wr_w[:ps],
                                    llc_w[:ps], pv)
                    chunks.append(o)
                # rows of the capacity slice beyond the live span are
                # real suffix rows: mask va to -1 and valid to False so
                # they replay as exact no-ops here and execute once, in
                # the fast suffix (their event masks are False already —
                # events end at the span by construction)
                span = jnp.arange(es) < (b_idx - a_idx)
                va_e = jnp.where(
                    span.reshape((es,) + (1,) * (va_w.ndim - 1)),
                    dsl(va_w, a_idx, es), -1)
                arrs = (va_e, dsl(wr_w, a_idx, es), dsl(fid_w, a_idx, es),
                        dsl(llc_w, a_idx, es), dsl(sched_w, a_idx, es),
                        dsl(df_w, a_idx, es), dsl(ds_w, a_idx, es),
                        dsl(hf_w, a_idx, es),
                        dsl(vl_w, a_idx, es) & span)
                s, o, n_alloc = run_steps(s, cc, pc, arrs, seg_of_map,
                                          seg_of_leaf)
                chunks.append(o)
                if qs:
                    s, o = run_fast(s, cc, dsl(va_w, b_idx, qs),
                                    dsl(wr_w, b_idx, qs),
                                    dsl(llc_w, b_idx, qs),
                                    dsl(vl_w, b_idx, qs))
                    chunks.append(o)
                return s, pad_rows(cat_rows(chunks), ps + es + qs), n_alloc
            branches.append(scoped("window.split", split_window))

        if len(branches) == 1:
            return branches[0](carry)
        return jax.lax.switch(kind, branches, carry)

    return window


def no_alloc() -> jax.Array:
    """A row or window that ran no fault allocator: ``[batched, scan]``."""
    return jnp.zeros((2,), I32)


def scan_rows(run_row):
    """Scan ``run_row(state, cc, pc, x, seg_of_map, seg_of_leaf) ->
    (state, outputs, alloc_path)`` over the rows (or windows) of ``xs``.

    The runner returns ``(final state, stacked outputs, alloc_rows)``:
    ``alloc_rows`` sums the allocator arm counts ``[batched, scan]`` of
    every replayed faulting row, apart from the state and the timeline.
    """
    def run_all(st, cc, pc, xs, seg_of_map, seg_of_leaf):
        def body(carry, x):
            s, n = carry
            s, out, path = run_row(s, cc, pc, x, seg_of_map, seg_of_leaf)
            return (s, n + path), out
        (final, n), outs = jax.lax.scan(body, (st, no_alloc()), xs)
        return final, outs, n
    return run_all


def seg_of_leaf_table(trace: Trace, mc: MachineConfig) -> jax.Array:
    seg_of_map = jnp.asarray(trace.seg_of_map, I32)
    n_leaf = mc.n_leaf_pages
    leaf_first = (np.arange(n_leaf, dtype=np.int64) << mc.radix_bits) \
        % max(mc.n_map, 1)
    return seg_of_map[jnp.asarray(leaf_first, I32)]


# Idle-pad fill values for the nine per-step window arrays, in xs order:
# (va, is_write, free_seg, llc, sched, valid, do_free, do_scan,
# has_fault).  Load-bearing: sched=0 carries no DO/WINNER bits, fid=-1
# frees nothing, valid=False gates the step clock.
WINDOW_PAD_FILLS = (-1, False, -1, 0.0, 0, False, False, False, False)


def window_tiles(arrays, n_steps: int, block: int,
                 fills=WINDOW_PAD_FILLS, rows_to: Optional[int] = None):
    """Idle-pad per-step host arrays to a multiple of ``block`` and tile
    them ``[n_windows, rows, ...]``.  The window count depends only on
    the step count, never the trace content — the property that keeps
    compiled blocked programs quantizing across trace mixes.  ``rows_to``
    (``WindowPlan.rows_in``) additionally idle-pads every window's row
    axis past ``block``: headroom for the hoist/split branches' dynamic
    segment slices, padded *per window* so a slice never reads the next
    window's rows."""
    n_w = -(-n_steps // block)
    pad = n_w * block - n_steps
    rpad = (rows_to or block) - block
    out = []
    for a, fill in zip(arrays, fills):
        a = np.asarray(a)
        if pad:
            a = np.concatenate(
                [a, np.full((pad,) + a.shape[1:], fill, a.dtype)])
        a = a.reshape((n_w, block) + a.shape[1:])
        if rpad:
            a = np.concatenate(
                [a, np.full((n_w, rpad) + a.shape[2:], fill, a.dtype)],
                axis=1)
        out.append(a)
    return out


# Semantic window kinds of the blocked engine's host classification.  The
# compiled dispatch table only contains the kinds a geometry needs
# ([fast] + [full, lean][hoist][split], in that order: lean is compiled
# wherever full is) and ``WindowPlan.kind`` stores the *branch index*
# under that ordering — geometry lives in the compile key, so dispatch
# table and data can never disagree.  ``WindowPlan.counts`` folds lean
# windows into full.
WIN_FAST, WIN_FULL, WIN_HOIST, WIN_SPLIT, WIN_LEAN = range(5)


@dataclasses.dataclass(frozen=True)
class WindowPlan:
    """Host-side execution plan for one blocked run.

    ``geom`` is the quantized split geometry (hashable; part of the
    compile key): ``None`` when every window is fast, else
    ``(has_full, (Ph, Qh) | None, (Ps, Es, Qs) | None)`` with pow2
    segment capacities.  ``kind``/``seg_a``/``seg_b`` are per-window
    device inputs (branch index, event/tick start row, suffix start
    row); ``emit_valid`` (``[n_windows, R_out]`` bool) maps emitted
    output rows back to trace steps in step order; ``counts`` reports
    the semantic classification (fast, full, hoist, split) for
    telemetry, lean windows counted under full, ``n_lean`` how many
    of the full windows run the cond-free lean row body, and
    ``scan_ticks`` the scan-tick rows as (hoisted, replayed): one a hoist
    window, and those inside full and split windows."""
    geom: Optional[tuple]
    kind: np.ndarray
    seg_a: np.ndarray
    seg_b: np.ndarray
    emit_valid: np.ndarray
    rows_in: int
    block: int
    counts: Tuple[int, int, int, int]
    n_lean: int
    scan_ticks: Tuple[int, int]

    @property
    def n_windows(self) -> int:
        return len(self.kind)

    @property
    def replay_rows(self) -> int:
        """Rows the per-step body runs: ``block`` for each full window and
        the split-span capacity ``Es`` for each split window (its masked
        rows cost device time too)."""
        _, n_full, _, n_split = self.counts
        es = self.geom[2][1] if n_split else 0
        return n_full * self.block + n_split * es


def _q2(n: int) -> int:
    return 0 if n <= 0 else pow2ceil(int(n))


def plan_windows(do_free, do_scan, has_fault, n_steps: int,
                 block: int) -> WindowPlan:
    """Classify each ``block``-step window of a trace and quantize the
    split geometry.

    The host schedule knows the exact event rows (segment frees, scan
    ticks, faults — for a sweep, the union over lanes), so a window
    needn't replay per-step just because it *contains* an event:

      fast    no event rows at all;
      hoist   no frees/faults and exactly one scan tick at row ``t`` —
              runs fast[0:t), the hoisted scan op, fast[t:block);
      split   a narrow event span (``<= block // 2``) — runs fast
              prefix, per-step replay of the span, fast suffix;
      full    wide spans, plus every partial tail window containing
              fault rows: there the span end *is* the trace's last
              faulting step, and letting trace content pick the split
              geometry would fracture the compile-key quantization the
              broker's shape buckets rely on;
      lean    a full window with no free, no scan tick, no pad row and
              a fault on every row (the populate phase): it replays
              through the cond-free row body.  Lean leaves ``geom``
              alone — it is compiled wherever full is — so trace
              content never picks the program.

    Segment capacities are per-class maxima rounded up to powers of two
    (``Ph``/``Qh`` hoist prefix/suffix, ``Ps``/``Es``/``Qs`` split
    prefix/event/suffix), so traces with different event rows but the
    same quantized geometry share one executable; live lengths travel as
    device data (``seg_a``/``seg_b``) and are masked in-body.
    """
    n_w = -(-n_steps // block)
    pad = n_w * block - n_steps

    def tile(m):
        m = np.asarray(m, bool)
        if pad:
            m = np.concatenate([m, np.zeros(pad, bool)])
        return m.reshape(n_w, block)

    df, ds, hf = tile(do_free), tile(do_scan), tile(has_fault)
    vl = tile(np.ones(n_steps, bool))
    ev = df | ds | hf

    kinds = np.full(n_w, WIN_FAST, np.int32)
    seg_a = np.zeros(n_w, np.int32)
    seg_b = np.zeros(n_w, np.int32)
    hoist_rows, split_rows = [], []
    for w in range(n_w):
        if not ev[w].any():
            continue
        if not (df[w] | hf[w]).any() and int(ds[w].sum()) == 1:
            t = int(np.argmax(ds[w]))
            kinds[w] = WIN_HOIST
            seg_a[w] = seg_b[w] = t
            hoist_rows.append(t)
            continue
        idx = np.flatnonzero(ev[w])
        f, l = int(idx[0]), int(idx[-1])
        if (l - f + 1) > block // 2 or (hf[w].any() and not vl[w].all()):
            lean = (vl[w].all() and hf[w].all()
                    and not (df[w] | ds[w]).any())
            kinds[w] = WIN_LEAN if lean else WIN_FULL
        else:
            kinds[w] = WIN_SPLIT
            seg_a[w], seg_b[w] = f, l + 1
            split_rows.append((f, l - f + 1, block - 1 - l))

    has_full = bool(np.isin(kinds, (WIN_FULL, WIN_LEAN)).any())
    hoist_g = (_q2(max(hoist_rows)), _q2(block - min(hoist_rows))) \
        if hoist_rows else None
    split_g = (_q2(max(r[0] for r in split_rows)),
               _q2(max(r[1] for r in split_rows)),
               _q2(max(r[2] for r in split_rows))) if split_rows else None
    geom = (has_full, hoist_g, split_g) \
        if (has_full or hoist_g or split_g) else None

    branch = {WIN_FAST: 0}
    for k, present in ((WIN_FULL, has_full), (WIN_LEAN, has_full),
                       (WIN_HOIST, hoist_g is not None),
                       (WIN_SPLIT, split_g is not None)):
        if present:
            branch[k] = len(branch)
    kind = np.array([branch[int(k)] for k in kinds], np.int32)

    r_out = _geom_out_rows(geom, block)
    rows_in = _geom_rows_in(geom, block)
    emit = np.zeros((n_w, r_out), bool)
    vlx = np.concatenate([vl, np.zeros_like(vl)], axis=1)
    for w in range(n_w):
        k = int(kinds[w])
        if k in (WIN_FAST, WIN_FULL, WIN_LEAN):
            emit[w, :block] = vl[w]
            continue
        a, b = int(seg_a[w]), int(seg_b[w])
        if k == WIN_HOIST:
            ph, qh = hoist_g
            pre = vlx[w, :ph] & (np.arange(ph) < a)
            emit[w, :ph + qh] = np.concatenate([pre, vlx[w, b:b + qh]])
        else:
            ps, es, qs = split_g
            pre = vlx[w, :ps] & (np.arange(ps) < a)
            mid = vlx[w, a:a + es] & (np.arange(es) < (b - a))
            emit[w, :ps + es + qs] = np.concatenate(
                [pre, mid, vlx[w, b:b + qs]])
    assert int(emit.sum()) == n_steps, \
        f"window plan emits {int(emit.sum())} rows for {n_steps} steps"
    sem = np.where(kinds == WIN_LEAN, WIN_FULL, kinds)
    return WindowPlan(
        geom=geom, kind=kind, seg_a=seg_a, seg_b=seg_b, emit_valid=emit,
        rows_in=rows_in, block=block,
        counts=tuple(int((sem == k).sum()) for k in range(4)),
        n_lean=int((kinds == WIN_LEAN).sum()),
        scan_ticks=(len(hoist_rows), int(ds[kinds != WIN_HOIST].sum())))
