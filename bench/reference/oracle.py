"""The plain reference: a step-by-step Python model of the simulated machine.

It is the benchmark's own copy of the tiered-memory semantics (paper
sections 2-4 and 6: first-touch/interleave placement, Linux-default,
bind-all and BHi page-table placement, Radiant's Algorithm-1 leaf
migration, the AutoNUMA, TPP and Nomad balancing scans, the TLB/PWC
hierarchy and the cycle cost model), written as plain loops over threads
and pages.  It imports nothing of the program and takes nothing the
program made: the machine, policy and cost come from the benchmark's
configuration files and the trace from ``reference.traces``.

Ordering rules that any implementation of these semantics must keep:

  * phase A (accesses to mapped pages) sees the state at step start for
    every thread; phase B (faults) runs threads in index order, so the
    first thread to touch a granule allocates it and later threads of the
    same step take the cheaper "wait" path; once an allocation fails every
    later thread is OOM-gated;
  * TLB/PWC victims: least recent stamp, lowest way on ties, empty ways
    stamped -1;
  * the balancing scan runs at the start of every ``autonuma_period``-th
    step, ranks pages by composite integer keys (recent count, then
    lowest index), demotes before it promotes, halves the recent counts;
  * Algorithm-1 trigger batches: the first trigger of each leaf judges the
    pre-batch table, winners apply, later triggers judge the post-batch
    table; try-lock conflicts go to the earliest batch position per lock
    domain.

``cycle_dtype`` is the precision of every cycle quantity (float32 as the
configuration states).  The benchmark's control runs this model with
bfloat16 cycles, to show that the comparison fails one precision down.
"""
from __future__ import annotations

import dataclasses
import types

import numpy as np

FIRST_TOUCH, INTERLEAVE = 0, 1
PT_FOLLOW_DATA, PT_BIND_ALL, PT_BIND_HIGH = 10, 11, 12
MIG_AUTONUMA, MIG_TPP, MIG_NOMAD = 20, 21, 22
_DATA_POLICY = {"first_touch": FIRST_TOUCH, "interleave": INTERLEAVE,
                FIRST_TOUCH: FIRST_TOUCH, INTERLEAVE: INTERLEAVE}

_MIX = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F)
M32 = 0xFFFFFFFF

COUNTER_KEYS = ("l1_hits", "stlb_hits", "walks", "walk_mem_reads", "faults",
                "slow_allocs", "data_migrations", "demotions",
                "l4_mig_success", "l4_mig_already_dest", "l4_mig_in_dram",
                "l4_mig_sibling_guard", "l4_mig_lock_skip", "oom_kills",
                "nomad_retries", "nomad_flip_demotions",
                "nomad_shadow_drops")


@dataclasses.dataclass(frozen=True)
class Machine:
    """The simulated machine: two NUMA nodes (one per socket) per tier,
    numbered tier-major, fastest tier first."""

    n_threads: int
    tier_pages_per_node: tuple
    va_pages: int
    radix_bits: int
    page_order: int
    l1_tlb_sets: int
    l1_tlb_ways: int
    stlb_sets: int
    stlb_ways: int
    pde_pwc_entries: int
    pdpte_pwc_entries: int
    low_watermark: float
    reclaimable_frac: float
    lock_domain_shift: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Machine":
        tiers = cfg.get("tier_pages_per_node") or (
            cfg["dram_pages_per_node"], cfg["nvmm_pages_per_node"])
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: cfg[k] for k in fields if k in cfg}
        kw["tier_pages_per_node"] = tuple(int(c) for c in tiers)
        return cls(**kw)

    @property
    def n_tiers(self):
        return len(self.tier_pages_per_node)

    @property
    def n_nodes(self):
        return 2 * self.n_tiers

    @property
    def tier_of_node(self):
        return tuple(n // 2 for n in range(self.n_nodes))

    @property
    def node_capacity(self):
        return tuple(self.tier_pages_per_node[t] for t in self.tier_of_node)

    @property
    def alloc_nodes(self):
        return tuple(n for n in range(self.n_nodes)
                     if self.tier_pages_per_node[n // 2] > 0)

    @property
    def n_map(self):
        return max(self.va_pages >> self.page_order, 1)

    @property
    def n_leaf(self):
        return max(self.n_map >> self.radix_bits, 1)

    @property
    def n_mid(self):
        return max(self.n_map >> (2 * self.radix_bits), 1)

    @property
    def n_top(self):
        return max(self.n_map >> (3 * self.radix_bits), 1)


@dataclasses.dataclass(frozen=True)
class Policy:
    data_policy: int = FIRST_TOUCH
    pt_policy: int = PT_FOLLOW_DATA
    mig: bool = False
    autonuma: bool = True
    autonuma_period: int = 512
    autonuma_budget: int = 256
    autonuma_threshold: int = 1
    autonuma_exchange: bool = True
    mig_policy: int = MIG_AUTONUMA
    tpp_demote_wm: float = 0.0


# Table 3 of the paper, plus the TPP and Nomad balancing families; a
# traffic mix may override any field of a preset.
PRESETS = {
    "linux_default": Policy(),
    "bind_all": Policy(pt_policy=PT_BIND_ALL),
    "bhi": Policy(pt_policy=PT_BIND_HIGH),
    "bhi_mig": Policy(pt_policy=PT_BIND_HIGH, mig=True),
    "tpp": Policy(mig_policy=MIG_TPP, tpp_demote_wm=0.02),
    "nomad": Policy(mig_policy=MIG_NOMAD),
}


def policy(preset: str, **overrides) -> Policy:
    if "data_policy" in overrides:
        overrides["data_policy"] = _DATA_POLICY[overrides["data_policy"]]
    return dataclasses.replace(PRESETS[preset], **overrides)


def bern(p, site, *keys) -> bool:
    """Deterministic Bernoulli draw keyed by (site, keys)."""
    h = (0x811C9DC5 + 0x1000193 * site) & M32
    for i, k in enumerate(keys):
        h = ((h ^ (int(k) & M32)) * _MIX[i % 4]) & M32
    h = (h >> 8) & 0xFFFFFF
    return h < int(np.float32(p) * np.float32(1 << 24))


class _Tlb:
    """Set-associative translation cache; per set a list of tags and of
    last-use stamps (-1 = empty)."""

    def __init__(self, sets, ways):
        self.sets = sets
        self.tags = [[-1] * ways for _ in range(sets)]
        self.lru = [[-1] * ways for _ in range(sets)]

    def lookup(self, tag):
        s = tag % self.sets
        ways = self.tags[s]
        if tag in ways:
            return True, ways.index(tag)
        lru = self.lru[s]
        return False, lru.index(min(lru))

    def update(self, tag, way, now):
        s = tag % self.sets
        self.tags[s][way] = tag
        self.lru[s][way] = now

    def invalidate(self, dead):
        for tags, lru in zip(self.tags, self.lru):
            for w, t in enumerate(tags):
                if t >= 0 and t in dead:
                    tags[w] = -1
                    lru[w] = -1

    def invalidate_leaves(self, dead, rb):
        for tags, lru in zip(self.tags, self.lru):
            for w, t in enumerate(tags):
                if t >= 0 and (t >> rb) in dead:
                    tags[w] = -1
                    lru[w] = -1


class RefSim:
    """One simulated run of one policy over one trace."""

    def __init__(self, mc: Machine, cost: dict, pc: Policy,
                 cycle_dtype=np.float32):
        self.mc, self.cc, self.pc = mc, types.SimpleNamespace(**cost), pc
        self.F = F = cycle_dtype
        T = mc.n_threads
        self.rb = mc.radix_bits
        self.n_map, self.n_leaf = mc.n_map, mc.n_leaf
        self.n_mid, self.n_top = mc.n_mid, mc.n_top
        self.thp = mc.page_order > 0
        self.nt = mc.n_tiers
        cc = self.cc
        self.rd_vals = [F(v) for v in [cc.dram_read]
                        + [cc.cxl_read] * (self.nt - 2) + [cc.nvmm_read]]
        self.wr_vals = [F(v) for v in [cc.dram_write]
                        + [cc.cxl_write] * (self.nt - 2) + [cc.nvmm_write]]

        self.data_node = np.full(self.n_map, -1, np.int64)
        self.leaf_node = np.full(self.n_leaf, -1, np.int64)
        self.mid_node = np.full(self.n_mid, -1, np.int64)
        self.top_node = np.full(self.n_top, -1, np.int64)
        self.root_node = np.full(1, -1, np.int64)
        self.ldc = np.zeros(self.n_leaf, np.int64)

        cap = np.array(mc.node_capacity, np.int64)
        self.reclaimable = (cap.astype(np.float32) * mc.reclaimable_frac
                            ).astype(np.int64)
        self.free = cap - self.reclaimable
        self.wm = (cap.astype(np.float32) * mc.low_watermark).astype(np.int64)
        self.interleave_ptr = 0
        self.oom = False
        self.oom_step = -1
        self.access = np.zeros(self.n_map, np.int64)
        self.shadow = np.full(self.n_map, -1, np.int64)
        self.written = np.zeros(self.n_map, np.int64)

        self.l1 = [_Tlb(mc.l1_tlb_sets, mc.l1_tlb_ways) for _ in range(T)]
        self.stlb = [_Tlb(mc.stlb_sets, mc.stlb_ways) for _ in range(T)]
        self.pde = [_Tlb(1, mc.pde_pwc_entries) for _ in range(T)]
        self.pdpte = [_Tlb(1, mc.pdpte_pwc_entries) for _ in range(T)]

        self.cy_total = np.zeros(T, F)
        self.cy_walk = np.zeros(T, F)
        self.cy_stall = np.zeros(T, F)
        self.cy_data = np.zeros(T, F)
        self.cy_fault = np.zeros(T, F)
        self.cy_mig = F(0)
        self.cnt = dict.fromkeys(COUNTER_KEYS, 0)
        self.data_allocs = np.zeros(len(cap), np.int64)
        self.pt_allocs = np.zeros(len(cap), np.int64)
        self.step = 0
        self.timeline = {}

    # ---------------- helpers ---------------------------------------------
    def _is_dram(self, n):
        return 0 <= n < 2

    def _tier(self, n):
        """Tier of a node; an unallocated node (-1) counts as the slowest."""
        return self.nt - 1 if n < 0 else int(n) // 2

    def _rd(self, n):
        return self.rd_vals[self._tier(n)]

    def _wr(self, n):
        return self.wr_vals[self._tier(n)]

    def _alloc_one(self, prefs, ignore_wm):
        """First node in ``prefs`` above its watermark; else any free page;
        else the reclaimable reserve; else -1.  Returns (node, slow)."""
        cand_fast = cand_slow = cand_rec = None
        for p in prefs:
            wm = 0 if ignore_wm else self.wm[p]
            if cand_fast is None and self.free[p] > wm:
                cand_fast = p
            if cand_slow is None and self.free[p] > 0:
                cand_slow = p
            if cand_rec is None and self.reclaimable[p] > 0:
                cand_rec = p
        if cand_fast is not None:
            self.free[cand_fast] -= 1
            return cand_fast, False
        if cand_slow is not None:
            self.free[cand_slow] -= 1
            return cand_slow, True
        if cand_rec is not None:
            self.reclaimable[cand_rec] -= 1
            return cand_rec, True
        return -1, True

    def _local(self, t):
        return 0 if t < self.mc.n_threads // 2 else 1

    def _data_prefs(self, t):
        if self.pc.data_policy == INTERLEAVE:
            alloc = self.mc.alloc_nodes
            a = len(alloc)
            s = self.interleave_ptr % a
            return [alloc[(s + i) % a] for i in range(a)]
        local = self._local(t)
        prefs = []
        for tt in range(self.nt):
            prefs += [2 * tt + local, 2 * tt + (1 - local)]
        return prefs

    def _oom_kill(self):
        self.oom = True
        if self.oom_step < 0:
            self.oom_step = self.step
        self.cnt["oom_kills"] += 1

    def _alloc_pt(self, t, arr, idx, is_upper):
        """Allocate a missing page-table page; returns cycles charged."""
        F = self.F
        if arr[idx] >= 0:
            return F(0)
        pc = self.pc
        data_prefs = self._data_prefs(t)
        if pc.pt_policy == PT_BIND_ALL or (
                pc.pt_policy == PT_BIND_HIGH and (is_upper or self.thp)):
            local = self._local(t)
            node, slow = self._alloc_one([local, 1 - local], True)
            if node < 0 and pc.pt_policy == PT_BIND_HIGH:
                node, slow = self._alloc_one(data_prefs, False)
        else:
            node, slow = self._alloc_one(data_prefs, False)
        if node < 0:
            self._oom_kill()
            return F(self.cc.oom_scan)
        arr[idx] = node
        self.pt_allocs[node] += 1
        if slow:
            self.cnt["slow_allocs"] += 1
        if pc.pt_policy == PT_FOLLOW_DATA and pc.data_policy == INTERLEAVE:
            self.interleave_ptr += 1
        cost = F(0)
        cost += F(self.cc.zero_lines) * self._wr(node)
        cost += F(self.cc.alloc_slow if slow else self.cc.alloc_fast)
        return cost

    # ---------------- balancing scan + Algorithm 1 ------------------------
    def _rank(self, count, idx, idx_bits):
        """Composite sort key: clamped count high, reversed index low."""
        nn = 1 << idx_bits
        return (np.clip(count, 0, 255) << idx_bits) | (nn - 1 - idx)

    def _autonuma(self, va_row, w_row):
        mc, cc, pc, F = self.mc, self.cc, self.pc, self.F
        nt = self.nt
        bt = min(int(pc.autonuma_budget), self.n_map)
        idx_bits = max(self.n_map - 1, 1).bit_length()
        en_tpp = int(pc.mig_policy) == MIG_TPP
        en_nomad = int(pc.mig_policy) == MIG_NOMAD
        idx = np.arange(self.n_map, dtype=np.int64)

        # (0) Nomad: a write since the last scan makes the shadow stale
        if en_nomad:
            stale = np.nonzero((self.shadow >= 0) & (self.written > 0))[0]
            for i in stale:
                self.free[self.shadow[i]] += 1
                self.shadow[i] = -1
                self.cnt["nomad_shadow_drops"] += 1

        # (1) hot candidates on slower tiers, hottest first
        hot_mask = ((self.data_node >= 2)
                    & (self.access >= pc.autonuma_threshold)
                    & (self.access > 0))
        hot_idx = idx[hot_mask]
        key = self._rank(self.access[hot_idx], hot_idx, idx_bits)
        hot_pages = [int(i) for i in hot_idx[np.argsort(-key, kind="stable")]]
        n_hot = min(len(hot_pages), bt)

        # (2) cold tier-0 victims, coldest first; TPP: inactive ones only
        cold_mask = (self.data_node >= 0) & (self.data_node < 2)
        if en_tpp:
            cold_mask &= self.access < pc.autonuma_threshold
        cold_idx = idx[cold_mask]
        key = self._rank(255 - np.minimum(self.access[cold_idx], 255),
                         cold_idx, idx_bits)
        cold_pages = [int(i)
                      for i in cold_idx[np.argsort(-key, kind="stable")]]
        n_victims = min(len(cold_pages), bt)

        dram_excess = max(self.free[0] - self.wm[0], 0) \
            + max(self.free[1] - self.wm[1], 0)
        n_promote_want = min(n_hot, bt)
        need_demote = max(n_promote_want - dram_excess, 0)

        # TPP demotes ahead of reclaim: watermark plus a headroom fraction
        cap0 = 2 * mc.tier_pages_per_node[0]
        tpp_extra = int(np.float32(np.float32(pc.tpp_demote_wm) * cap0))
        need_tpp = max(int(self.wm[0]) + int(self.wm[1]) + tpp_extra
                       - (int(self.free[0]) + int(self.free[1])), 0)
        need_eff = max(need_tpp, need_demote) if en_tpp else need_demote

        # demotion target: TPP the next slower non-empty tier, else slowest
        caps = mc.tier_pages_per_node
        tpp_t = next(t for t in range(1, nt) if caps[t] > 0)
        dest_a = 2 * tpp_t if en_tpp else 2 * (nt - 1)
        dest_b = dest_a + 1
        cap_a, cap_b = int(self.free[dest_a]), int(self.free[dest_b])
        room = max(cap_a, 0) + max(cap_b, 0)
        dem_en = True if en_tpp else bool(pc.autonuma_exchange)
        n_demote = min(min(need_eff, n_victims), room) if dem_en else 0
        n_promote = min(n_promote_want, dram_excess + n_demote)

        def split_two(n, ca, cb):
            if ca >= cb:
                return max(min(ca, n), 0)
            return max(n - min(cb, n), 0)

        cost = F(0)
        triggers = []
        migrated = []
        share_a = split_two(n_demote, cap_a, cap_b)
        for k in range(n_demote):
            page = cold_pages[k]
            dest = dest_a if k < share_a else dest_b
            src = self.data_node[page]
            # Nomad flip: a clean shadow becomes the page, no copy
            flip = en_nomad and self.shadow[page] >= 0
            dest_eff = int(self.shadow[page]) if flip else dest
            self.data_node[page] = dest_eff
            self.free[src] += 1
            if flip:
                self.shadow[page] = -1
                self.cnt["nomad_flip_demotions"] += 1
            else:
                self.free[dest_eff] -= 1
            self.ldc[page >> self.rb] -= 1
            add = F(cc.migrate_fixed + cc.tlb_flush)
            if not flip:
                add = add + F(cc.copy_lines) * (self._rd(src)
                                                + self._wr(dest_eff))
            cost += add
            self.cnt["demotions"] += 1
            self.cnt["data_migrations"] += 1
            triggers.append((page, dest_eff))
            migrated.append(page)

        # granules written in this step: Nomad's transactional-abort set
        conc_w = {min(int(v) >> mc.page_order, self.n_map - 1)
                  for v, w in zip(va_row, w_row) if v >= 0 and w}

        share0 = split_two(n_promote, max(self.free[0] - self.wm[0], 0),
                           max(self.free[1] - self.wm[1], 0))
        for k in range(n_promote):
            page = hot_pages[k]
            src = self.data_node[page]
            if en_nomad and page in conc_w:
                cost += F(cc.migrate_fixed) + F(cc.copy_lines) * self._rd(src)
                self.cnt["nomad_retries"] += 1
                continue
            dest = 0 if k < share0 else 1
            self.data_node[page] = dest
            if en_nomad:
                self.shadow[page] = src
            else:
                self.free[src] += 1
            self.free[dest] -= 1
            self.ldc[page >> self.rb] += 1
            cost += F(cc.migrate_fixed + cc.tlb_flush) + \
                F(cc.copy_lines) * (self._rd(src) + self._wr(dest))
            self.cnt["data_migrations"] += 1
            triggers.append((page, dest))
            migrated.append(page)

        mig_set = set(migrated)
        for tlb in self.l1 + self.stlb:
            tlb.invalidate(mig_set)
        if en_nomad:
            self.written[:] = 0
        self.access //= 2

        if pc.mig and triggers:
            cost += self._migrate_leaf_batch(triggers)
        return cost

    def _migrate_leaf_batch(self, triggers):
        """Radiant Algorithm 1 over one batch of data-page migrations."""
        cc, F = self.cc, self.F
        cost = F(0)
        pre_free = self.free.copy()
        seen_leaf = set()
        first_flags = []
        for page, _ in triggers:
            leaf = page >> self.rb
            first_flags.append(leaf not in seen_leaf)
            seen_leaf.add(leaf)

        # pass 1: the first trigger of each leaf judges the pre-batch table
        wants = []
        for pos, (page, dest) in enumerate(triggers):
            if not first_flags[pos]:
                continue
            leaf = page >> self.rb
            l4n = self.leaf_node[leaf]
            if l4n < 0:
                continue
            if l4n == dest:
                self.cnt["l4_mig_already_dest"] += 1
            elif self._tier(l4n) == self._tier(dest):
                self.cnt["l4_mig_in_dram"] += 1
            elif self._tier(dest) > 0 and self.ldc[leaf] > 0:
                self.cnt["l4_mig_sibling_guard"] += 1
            else:
                wants.append(pos)

        locked = set()
        winners = []
        for pos in wants:
            page, dest = triggers[pos]
            lock = (page >> self.rb) >> self.mc.lock_domain_shift
            if lock in locked or pre_free[dest] <= 0:
                locked.add(lock)
                self.cnt["l4_mig_lock_skip"] += 1
                continue
            locked.add(lock)
            winners.append(pos)

        flushed = set()
        for pos in winners:
            page, dest = triggers[pos]
            leaf = page >> self.rb
            src = self.leaf_node[leaf]
            self.leaf_node[leaf] = dest
            self.free[src] += 1
            self.free[dest] -= 1
            cost += F(cc.migrate_fixed + cc.tlb_flush + cc.alloc_fast) \
                + F(cc.copy_lines) * (self._rd(src) + self._wr(dest))
            self.cnt["l4_mig_success"] += 1
            flushed.add(leaf)

        # pass 2: later triggers judge the post-migration table
        for pos, (page, dest) in enumerate(triggers):
            if first_flags[pos]:
                continue
            leaf = page >> self.rb
            new_l4 = self.leaf_node[leaf]
            if new_l4 == dest:
                self.cnt["l4_mig_already_dest"] += 1
            elif self._tier(new_l4) == self._tier(dest):
                self.cnt["l4_mig_in_dram"] += 1
            elif self._tier(dest) > 0 and self.ldc[leaf] > 0:
                self.cnt["l4_mig_sibling_guard"] += 1

        for tlb in self.l1 + self.stlb:
            tlb.invalidate_leaves(flushed, self.rb)
        for tlb in self.pde:
            tlb.invalidate(flushed)
        return cost

    # ---------------- one run ---------------------------------------------
    def run(self, trace):
        mc, cc, pc, F = self.mc, self.cc, self.pc, self.F
        T = mc.n_threads
        if (np.asarray(trace.free_seg) >= 0).any():
            raise ValueError("the reference models no segment frees")
        S = trace.n_steps
        tl = {k: np.zeros(S, F) for k in ("total_cycles", "walk_cycles",
                                          "stall_cycles", "migration_cycles",
                                          "data_mem_cycles", "fault_cycles")}
        tl.update({k: np.zeros(S, np.int64) for k in (
            "faults", "dram_free", "leaf_nvmm", "leaf_dram", "walks",
            "data_migrations", "l4_mig_success", "l1_hits", "stlb_hits")})
        for s in range(S):
            va_row = [int(v) for v in trace.va[s]]
            w_row = [bool(w) for w in trace.is_write[s]]
            llc_rate = float(trace.llc[s])

            if pc.autonuma and self.step > 0 \
                    and self.step % pc.autonuma_period == 0 and not self.oom:
                c = self._autonuma(va_row, w_row)
                self.cy_total += c * F(cc.mig_cost_scale) / F(T)
                self.cy_mig += c

            # phase A: every thread sees the state at step start
            faulting = []
            for t in range(T):
                va = va_row[t]
                if va < 0 or self.oom:
                    continue
                m = min(va >> mc.page_order, self.n_map - 1)
                if self.data_node[m] < 0:
                    faulting.append(t)
                else:
                    self._mapped_access(t, m, w_row[t], llc_rate)
            # phase B: faults in thread order
            for t in faulting:
                if self.oom:
                    break
                m = min(va_row[t] >> mc.page_order, self.n_map - 1)
                self._fault(t, m, w_row[t])
            self.step += 1

            tl["total_cycles"][s] = np.sum(self.cy_total)
            tl["walk_cycles"][s] = np.sum(self.cy_walk)
            tl["stall_cycles"][s] = np.sum(self.cy_stall)
            tl["migration_cycles"][s] = self.cy_mig
            tl["data_mem_cycles"][s] = np.sum(self.cy_data)
            tl["fault_cycles"][s] = np.sum(self.cy_fault)
            tl["dram_free"][s] = self.free[0] + self.free[1]
            tl["leaf_nvmm"][s] = np.count_nonzero(self.leaf_node >= 2)
            tl["leaf_dram"][s] = np.count_nonzero(
                (self.leaf_node >= 0) & (self.leaf_node < 2))
            for k in ("faults", "walks", "data_migrations", "l4_mig_success",
                      "l1_hits", "stlb_hits"):
                tl[k][s] = self.cnt[k]
        self.timeline = tl
        return self

    def _mapped_access(self, t, m, is_write, llc_rate):
        cc, F = self.cc, self.F
        now = self.step
        hit1, way1 = self.l1[t].lookup(m)
        hit2, way2 = self.stlb[t].lookup(m)
        walkn = not hit1 and not hit2
        leaf_id, mid_id, top_id = m >> self.rb, m >> (2 * self.rb), \
            m >> (3 * self.rb)
        pde_hit, pde_way = self.pde[t].lookup(leaf_id)
        pdpte_hit, pdpte_way = self.pdpte[t].lookup(mid_id)

        walk_cost = F(0)
        if walkn:
            leaf_llc = bern(cc.leaf_llc_hit, 1, m, now, t)
            up1 = bern(cc.upper_llc_hit, 2, mid_id, now, t)
            up2 = bern(cc.upper_llc_hit, 3, top_id, now, t)
            leaf_read = F(cc.llc_hit) if leaf_llc \
                else self._rd(self.leaf_node[leaf_id])
            mid_read = F(0)
            if not pde_hit:
                mid_read = F(cc.llc_hit) if up1 \
                    else self._rd(self.mid_node[min(mid_id, self.n_mid - 1)])
            full = not pde_hit and not pdpte_hit
            top_read = F(0)
            if full and not self.thp:
                top_read = F(cc.llc_hit) if up2 \
                    else self._rd(self.top_node[min(top_id, self.n_top - 1)])
            root_read = F(cc.llc_hit) if full else F(0)
            walk_cost = leaf_read + mid_read + top_read + root_read
            walk_reads = int(not leaf_llc) + int(not pde_hit and not up1) \
                + (int(full and not up2) if not self.thp else 0)
            self.cnt["walks"] += 1
            self.cnt["walk_mem_reads"] += walk_reads
        elif hit1:
            self.cnt["l1_hits"] += 1
        else:
            self.cnt["stlb_hits"] += 1

        data_llc = bern(llc_rate, 4, m, now, t)
        node = self.data_node[m]
        mem = self._wr(node) if is_write else self._rd(node)
        data_cost = F(cc.llc_hit) if data_llc else mem

        tlb_pen = F(cc.stlb_hit) if not hit1 else F(0)
        stall = walk_cost + F(cc.data_stall_frac) * data_cost
        total = F(cc.cpu_work) + tlb_pen + stall

        self.l1[t].update(m, way1, now)
        if not hit1:
            self.stlb[t].update(m, way2, now)
        if walkn:
            self.pde[t].update(leaf_id, pde_way, now)
            self.pdpte[t].update(mid_id, pdpte_way, now)
        self.access[m] += 1
        if is_write:
            self.written[m] += 1
        self.cy_total[t] += total
        self.cy_walk[t] += walk_cost
        self.cy_stall[t] += stall
        self.cy_data[t] += data_cost

    def _fault(self, t, m, is_write):
        cc, F = self.cc, self.F
        now = self.step
        rb = self.rb
        if self.data_node[m] >= 0:      # an earlier thread of this step won
            cost = F(cc.fault_base) + F(cc.llc_hit)
            self.cy_data[t] += F(cc.llc_hit)
        else:
            cost = F(0)
            cost += self._alloc_pt(t, self.root_node, 0, True)
            cost += self._alloc_pt(t, self.top_node,
                                   min(m >> (3 * rb), self.n_top - 1), True)
            cost += self._alloc_pt(t, self.mid_node,
                                   min(m >> (2 * rb), self.n_mid - 1), True)
            cost += self._alloc_pt(t, self.leaf_node, m >> rb, False)
            node, slow = self._alloc_one(self._data_prefs(t), False)
            if node < 0:
                self._oom_kill()
                cost += F(cc.oom_scan)
            else:
                self.data_node[m] = node
                self.data_allocs[node] += 1
                if self._is_dram(node):
                    self.ldc[m >> rb] += 1
                if slow:
                    self.cnt["slow_allocs"] += 1
                if self.pc.data_policy == INTERLEAVE:
                    self.interleave_ptr += 1
                cost += F(cc.zero_lines) * self._wr(node) + \
                    F(cc.alloc_slow if slow else cc.alloc_fast)
            mid_n = self.mid_node[min(m >> (2 * rb), self.n_mid - 1)]
            leaf_n = self.leaf_node[m >> rb]
            cost += F(cc.fault_base) + self._rd(mid_n) + self._wr(leaf_n)
            self.cnt["faults"] += 1

        for tlb, tag in ((self.l1[t], m), (self.stlb[t], m),
                         (self.pde[t], m >> rb), (self.pdpte[t], m >> (2 * rb))):
            _, w = tlb.lookup(tag)
            tlb.update(tag, w, now)
        self.access[m] += 1
        if is_write:
            self.written[m] += 1
        self.cy_total[t] += cost
        self.cy_fault[t] += cost
