"""Per-node page allocator with watermarks, slow path, reclaim and OOM.

Mirrors the Linux buddy-allocator behaviors the paper measures:

  * fast path when a node is above its low watermark,
  * slow path (direct-reclaim attempt, ``alloc_slow`` cycles) below it,
  * a small "reclaimable" reserve per node standing in for clean page cache,
  * OOM when a *bound* allocation (PT bind-all) cannot be satisfied from the
    allowed nodes even after reclaim (paper section 3.5, Fig. 7).

Allocation preferences are length-``n_nodes`` node orders with -1 padding, so
the same scalar routine serves first-touch (local then remote node of each
tier, fastest tier first), interleave (rotating start over the allocatable
nodes), and DRAM-only binds.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import (MachineConfig, INTERLEAVE, PT_BIND_ALL, PT_BIND_HIGH,
                     PT_FOLLOW_DATA)

I32 = jnp.int32


def watermark_pages(mc: MachineConfig) -> jax.Array:
    cap = jnp.asarray(mc.node_capacity(), jnp.float32)
    return (cap * mc.low_watermark).astype(I32)


def first_touch_prefs(thread: jax.Array, mc: MachineConfig) -> jax.Array:
    """Zonelist order for a thread: local then remote node of each tier,
    fastest tier first (paper Fig. 2 topology; tiers beyond DRAM extend
    the classic local-DRAM, remote-DRAM, local-NVMM, remote-NVMM order)."""
    local = jnp.where(thread < mc.n_threads // 2, 0, 1).astype(I32)
    pairs = []
    for t in range(mc.n_tiers):
        pairs.append(2 * t + local)
        pairs.append(2 * t + (1 - local))
    return jnp.stack(pairs)


def interleave_prefs(ptr: jax.Array, mc: MachineConfig) -> jax.Array:
    """Round-robin start node with wrap-around fallback.  Rotates over the
    *allocatable* nodes only, so zero-capacity middle tiers never perturb
    the round-robin order (-1 pads to the machine's n_nodes)."""
    alloc = jnp.asarray(mc.alloc_nodes, I32)
    a = len(mc.alloc_nodes)
    start = (ptr % a).astype(I32)
    prefs = alloc[(start + jnp.arange(a, dtype=I32)) % a]
    if a < mc.n_nodes:
        prefs = jnp.concatenate(
            [prefs, jnp.full((mc.n_nodes - a,), -1, I32)])
    return prefs


def dram_prefs(thread: jax.Array, mc: MachineConfig) -> jax.Array:
    """DRAM-only preference (for PT binds); -1 entries are invalid."""
    local = jnp.where(thread < mc.n_threads // 2, 0, 1).astype(I32)
    pad = [jnp.asarray(-1, I32)] * (mc.n_nodes - 2)
    return jnp.stack([local, 1 - local] + pad)


def alloc_one(node_free: jax.Array, node_reclaimable: jax.Array,
              prefs: jax.Array, wm: jax.Array, ignore_wm: jax.Array
              ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Allocate a single page following ``prefs`` (i32[n_nodes], -1 = skip).

    Returns (node, slow, new_free, new_reclaimable, ok).  ``node`` is -1 on
    failure.  ``slow`` flags the watermark slow path (or a reclaim), charged
    ``alloc_slow`` cycles by the caller.  Deterministic: first acceptable
    node in preference order wins.
    """
    n = node_free.shape[0]
    valid = prefs >= 0
    safe_prefs = jnp.where(valid, prefs, 0)
    free_p = jnp.where(valid, node_free[safe_prefs], -1)
    wm_p = jnp.where(ignore_wm, 0, wm[safe_prefs])
    rec_p = jnp.where(valid, node_reclaimable[safe_prefs], 0)

    above = valid & (free_p > wm_p)
    has_page = valid & (free_p > 0)
    has_reclaim = valid & (rec_p > 0)

    fast_ok = jnp.any(above)
    slow_ok = jnp.any(has_page)
    rec_ok = jnp.any(has_reclaim)

    pick_fast = safe_prefs[jnp.argmax(above)]
    pick_slow = safe_prefs[jnp.argmax(has_page)]
    pick_rec = safe_prefs[jnp.argmax(has_reclaim)]

    node = jnp.where(fast_ok, pick_fast,
                     jnp.where(slow_ok, pick_slow,
                               jnp.where(rec_ok, pick_rec, -1)))
    ok = fast_ok | slow_ok | rec_ok
    slow = ok & ~fast_ok
    from_reclaim = ok & ~fast_ok & ~slow_ok

    dec = jnp.zeros((n,), I32).at[jnp.clip(node, 0, n - 1)].add(
        jnp.where(ok & ~from_reclaim, 1, 0))
    dec_rec = jnp.zeros((n,), I32).at[jnp.clip(node, 0, n - 1)].add(
        jnp.where(from_reclaim, 1, 0))
    return node, slow, node_free - dec, node_reclaimable - dec_rec, ok


def data_prefs_for(data_policy: jax.Array, thread: jax.Array,
                   mc: MachineConfig,
                   interleave_ptr: jax.Array) -> jax.Array:
    """Zonelist for a data-page allocation.  ``data_policy`` may be a traced
    int32 policy code (a vmap policy sweep), so both orders are computed and
    selected."""
    interleave = jnp.asarray(data_policy) == INTERLEAVE
    return jnp.where(interleave, interleave_prefs(interleave_ptr, mc),
                     first_touch_prefs(thread, mc))


def pt_prefs_for(pt_policy: jax.Array, level_is_upper: bool, thread: jax.Array,
                 mc: MachineConfig, data_prefs: jax.Array,
                 thp: bool) -> Tuple[jax.Array, jax.Array]:
    """Preference order for a PT page allocation.

    Returns (prefs, ignore_wm); ``pt_policy`` may be traced, so ``ignore_wm``
    is a traced bool.  ``level_is_upper`` marks root/top/mid pages (plus the
    leaf under THP, where the PMD *is* the leaf and BHi binds it — paper
    section 6.6); it is static because each walk level is a separate call.
    """
    pt_policy = jnp.asarray(pt_policy)
    bound = (pt_policy == PT_BIND_ALL) | \
        ((pt_policy == PT_BIND_HIGH) & (level_is_upper or thp))
    # Linux default: PT pages follow the data-page policy (paper section 3.2).
    prefs = jnp.where(bound, dram_prefs(thread, mc), data_prefs)
    return prefs, bound


# Request layout of one page fault, in allocation (= serialization) order:
# root, top and mid PT pages are "upper" levels (BHi-bound); the leaf PT
# page is upper only under THP (the PMD *is* the leaf, paper section 6.6);
# the data page comes last (request index 4).
_LEVEL_IS_UPPER = (True, True, True, False)


def alloc_many(node_free: jax.Array, node_reclaimable: jax.Array,
               interleave_ptr: jax.Array, oom_killed: jax.Array,
               wm: jax.Array, data_policy, pt_policy, mc: MachineConfig,
               need_pt: jax.Array, need_data: jax.Array,
               slot_thread=None):
    """Batched fault allocator: hand out pages to a whole thread vector.

    Reproduces the sequential thread-order semantics of
    ``sim.phase_b_body`` bit-for-bit.  The only state that genuinely
    chains through the per-thread fault loop is tiny — ``node_free[4]``,
    ``node_reclaimable[4]``, the interleave cursor and the OOM latch — so
    this runs a ``lax.scan`` over threads carrying just those ~10 scalars
    (each thread's 5 requests unrolled inside the body), while every heavy
    array update (PT placement scatters, TLB fills, counters) is left to
    the caller to commit vectorized from the returned per-request results.

    ``need_pt[T, 4]`` (root/top/mid/leaf) and ``need_data[T]`` are the
    host-precomputed first-thread-wins request masks from
    ``sim.fault_schedule``: threads faulting the same missing PT entry are
    resolved to the earliest thread, exactly as zone-lock serialization
    orders them in the sequential loop.  OOM gates at thread granularity:
    a thread whose allocation fails latches ``oom`` and every *later*
    thread goes inert, but the failing thread's own remaining requests
    still run (matching ``_alloc_pt_level``, which never re-checks the
    latch mid-fault).

    Returns ``(nodes[T,5], slow[T,5], ok[T,5], act[T,5], gate[T],
    node_free', node_reclaimable', interleave_ptr', oom')`` where ``act``
    marks requests actually attempted and ``gate`` marks threads that were
    not OOM-gated on entry.  ``ok`` is reported for *all* requests (it is
    what the sequential path's cost model reads), committed effects only
    for ``act & ok``.

    ``slot_thread`` (optional, i32[G] — ``n_threads`` marks a pad slot)
    compacts the serialized scan into *conflict groups*: a thread with no
    requests is the identity on the allocator carry and commutes with
    everything, so only the at-most-G allocating threads (the host
    schedule's WINNER bits; the engine passes ``G = n_threads``) need a
    scan slot — each group is one allocating thread plus the silent
    threads behind it.  The scan runs over the G slots in thread order and results
    scatter back to the thread axis; per-thread OOM gates are
    reconstructed from the winners' failure prefix, which is exactly the
    thread-order latch (only allocating threads can trip it).  Requests
    from threads without a slot would be dropped — callers guarantee
    every requesting thread carries a WINNER bit (device winners are a
    subset of host winners).  ``None`` keeps the full ``n_threads``-deep
    scan, the reference the tests hold the slot scan to; both are
    bit-identical.
    """
    data_policy = jnp.asarray(data_policy)
    pt_policy = jnp.asarray(pt_policy)
    thp = mc.page_order > 0
    is_follow = pt_policy == PT_FOLLOW_DATA
    is_interleave = data_policy == INTERLEAVE
    no_wm = jnp.asarray(False)

    def body(carry, x):
        free, rec, ptr, oom = carry
        needs, need_d, t = x
        gate = ~oom                     # thread-entry OOM gate
        nodes, slows, oks, acts = [], [], [], []
        for lvl in range(4):
            is_upper = _LEVEL_IS_UPPER[lvl]
            act = needs[lvl] & gate
            dprefs = data_prefs_for(data_policy, t, mc, ptr)
            prefs, ign = pt_prefs_for(pt_policy, is_upper, t, mc,
                                      dprefs, thp)
            node, slow, nf, nr, ok = alloc_one(free, rec, prefs, wm, ign)
            if is_upper or thp:
                # BHi falls back to the data policy when DRAM is exhausted
                # (mirrors sim._alloc_pt_level: both allocations computed,
                # the fallback selected per traced lane).
                node2, slow2, nf2, nr2, ok2 = alloc_one(free, rec, dprefs,
                                                        wm, no_wm)
                is_bhi = pt_policy == PT_BIND_HIGH
                use_fb = is_bhi & ~ok
                node = jnp.where(use_fb, node2, node)
                slow = jnp.where(use_fb, slow2, slow)
                nf = jnp.where(use_fb, nf2, nf)
                nr = jnp.where(use_fb, nr2, nr)
                ok = ok | (is_bhi & ok2)
            do = act & ok
            free = jnp.where(do, nf, free)
            rec = jnp.where(do, nr, rec)
            ptr = ptr + (do & is_follow & is_interleave).astype(I32)
            oom = oom | (act & ~ok)
            nodes.append(node), slows.append(slow)
            oks.append(ok), acts.append(act)

        act_d = need_d & gate
        dprefs = data_prefs_for(data_policy, t, mc, ptr)
        node, slow, nf, nr, ok = alloc_one(free, rec, dprefs, wm, no_wm)
        do = act_d & ok
        free = jnp.where(do, nf, free)
        rec = jnp.where(do, nr, rec)
        ptr = ptr + (do & is_interleave).astype(I32)
        oom = oom | (act_d & ~ok)
        nodes.append(node), slows.append(slow)
        oks.append(ok), acts.append(act_d)

        y = (jnp.stack(nodes), jnp.stack(slows), jnp.stack(oks),
             jnp.stack(acts), gate)
        return (free, rec, ptr, oom), y

    T = need_data.shape[0]
    carry0 = (node_free, node_reclaimable, interleave_ptr, oom_killed)
    if slot_thread is None:
        xs = (need_pt, need_data, jnp.arange(T, dtype=I32))
        (free, rec, ptr, oom), (nodes, slow, ok, act, gate) = \
            jax.lax.scan(body, carry0, xs)
        return nodes, slow, ok, act, gate, free, rec, ptr, oom

    # Conflict-group compaction: gather the allocating threads' requests
    # into the G slots, scan those, scatter results back.
    pad = slot_thread >= T
    safe_t = jnp.where(pad, 0, slot_thread).astype(I32)
    needs_g = jnp.where(pad[:, None], False, need_pt[safe_t])
    need_d_g = jnp.where(pad, False, need_data[safe_t])
    (free, rec, ptr, oom), (nodes_g, slow_g, ok_g, act_g, _gate_g) = \
        jax.lax.scan(body, carry0, (needs_g, need_d_g, safe_t))

    tgt = jnp.where(pad, T, slot_thread)           # route pads out of range
    nodes = jnp.full((T, 5), -1, I32).at[tgt].set(nodes_g, mode="drop")
    slow = jnp.zeros((T, 5), bool).at[tgt].set(slow_g, mode="drop")
    ok = jnp.zeros((T, 5), bool).at[tgt].set(ok_g, mode="drop")
    act = jnp.zeros((T, 5), bool).at[tgt].set(act_g, mode="drop")
    # Thread-order OOM gate: a thread is gated iff the latch was set on
    # entry or any allocating thread BEFORE it failed a request.
    fail_g = jnp.any(act_g & ~ok_g, axis=1)
    fail_t = jnp.zeros((T,), bool).at[tgt].set(fail_g, mode="drop")
    prefix = jnp.cumsum(fail_t.astype(I32)) - fail_t.astype(I32)
    gate = ~oom_killed & (prefix == 0)
    return nodes, slow, ok, act, gate, free, rec, ptr, oom


def _exclusive_cumsum(x: jax.Array) -> jax.Array:
    x = x.astype(I32)
    return jnp.cumsum(x) - x


def _pick(rank: jax.Array, above: jax.Array, has_page: jax.Array,
          has_reclaim: jax.Array):
    """``alloc_one``'s choice for many requests at once, over the static
    node axis.  ``rank[..., n]`` is node n's position in the request's
    preference order (``_UNLISTED`` where it is not listed); the three
    class masks are per node.  Returns ``(onehot[..., n], ok, slow,
    from_reclaim)``: the chosen node as a one-hot row (all False when
    nothing is acceptable) and ``alloc_one``'s flags."""
    listed = rank < _UNLISTED
    classes = (listed & above, listed & has_page, listed & has_reclaim)
    fast_ok, slow_ok, rec_ok = (jnp.any(c, axis=-1) for c in classes)
    chosen = jnp.where(fast_ok[..., None], classes[0],
                       jnp.where(slow_ok[..., None], classes[1], classes[2]))
    r = jnp.where(chosen, rank, _UNLISTED)
    onehot = chosen & (r == jnp.min(r, axis=-1, keepdims=True))
    ok = fast_ok | slow_ok | rec_ok
    return onehot, ok, ok & ~fast_ok, ok & ~fast_ok & ~slow_ok


_UNLISTED = 1 << 20


def alloc_vector(node_free: jax.Array, node_reclaimable: jax.Array,
                 interleave_ptr: jax.Array, oom_killed: jax.Array,
                 wm: jax.Array, data_policy, pt_policy, mc: MachineConfig,
                 need_pt: jax.Array, need_data: jax.Array):
    """``alloc_many`` in one vector pass, exact wherever no node's
    allocator class changes inside the row.

    Every pick of ``alloc_one`` depends on the carry only through three
    bits per node: ``free > wm``, ``free > 0`` and ``reclaimable > 0``.
    Taking them from the row-start carry, each request's outcome follows
    without the scan: whether it succeeds depends on the bits alone
    (every preference order of a request lists the same nodes, whatever
    the interleave cursor), so the thread-order OOM gate is a prefix over
    failing threads; the cursor of each request is the start cursor plus
    the advances before it; and its node is the first listed node of the
    best class.  The counts only fall inside a row, so if the bits after
    the whole row's demand equal the bits at its start they held at every
    request, and every output equals ``alloc_many``'s full-depth scan bit
    for bit.  That is the returned ``verified``; where it is False the
    caller must run the scan instead.

    Returns ``alloc_many``'s tuple followed by ``verified``.
    """
    data_policy = jnp.asarray(data_policy)
    pt_policy = jnp.asarray(pt_policy)
    T = need_data.shape[0]
    n = mc.n_nodes
    thp = mc.page_order > 0
    is_interleave = data_policy == INTERLEAVE
    is_bhi = pt_policy == PT_BIND_HIGH
    node = jnp.arange(n, dtype=I32)

    above = node_free > wm
    has_page = node_free > 0
    has_reclaim = node_reclaimable > 0

    # Static per level: BHi binds the upper levels (and the THP leaf), and
    # those fall back to the data policy when DRAM is exhausted.
    fb_level = jnp.asarray([u or thp for u in _LEVEL_IS_UPPER] + [False])
    bound = ((pt_policy == PT_BIND_ALL) & (jnp.arange(5) < 4)) | \
        (is_bhi & fb_level)                                      # [5]

    alloc_pos = np.full((n,), -1, np.int64)
    alloc_pos[list(mc.alloc_nodes)] = np.arange(len(mc.alloc_nodes))
    alloc_pos = jnp.asarray(alloc_pos, I32)
    a = len(mc.alloc_nodes)
    data_listed = jnp.where(is_interleave, alloc_pos >= 0, True)  # [n]
    dram_listed = node < 2

    # Success is cursor-free, so the OOM gate comes first.
    def any_class(listed, wm_bits):
        return jnp.any(listed & (wm_bits | has_page | has_reclaim))
    ok_data = any_class(data_listed, above)
    ok_level = jnp.where(bound, any_class(dram_listed, has_page), ok_data)
    ok_level = ok_level | (fb_level & is_bhi & ok_data)          # [5]
    need = jnp.concatenate([need_pt, need_data[:, None]], axis=1)
    fail_t = jnp.any(need & ~ok_level, axis=1)
    gate = ~oom_killed & (_exclusive_cumsum(fail_t) == 0)
    act = need & gate[:, None]
    ok = jnp.broadcast_to(ok_level, (T, 5))
    do = act & ok

    # The interleave cursor each request sees.
    pt_adv = (pt_policy == PT_FOLLOW_DATA) & is_interleave
    adv = do & jnp.where(jnp.arange(5) < 4, pt_adv, is_interleave)
    cursor = interleave_ptr + _exclusive_cumsum(adv.ravel()).reshape(T, 5)

    # Preference ranks [T, 5, n]: first touch by the thread's socket,
    # interleave rotated to the cursor, PT binds local DRAM first.
    local = jnp.where(jnp.arange(T) < mc.n_threads // 2, 0, 1)[:, None, None]
    remote = (node % 2 != local).astype(I32)
    ft_rank = 2 * (node // 2) + remote
    il_rank = jnp.where(alloc_pos >= 0,
                        (alloc_pos - (cursor % a)[..., None]) % a, _UNLISTED)
    data_rank = jnp.where(is_interleave, il_rank, ft_rank)
    dram_rank = jnp.where(dram_listed, remote, _UNLISTED)
    b3 = bound[:, None]
    rank = jnp.where(b3, dram_rank, data_rank)
    onehot, ok_p, slow, from_rec = _pick(
        rank, jnp.where(b3, has_page, above), has_page, has_reclaim)
    fb_onehot, _, fb_slow, fb_rec = _pick(data_rank, above, has_page,
                                          has_reclaim)
    use_fb = (fb_level & is_bhi) & ~ok_p
    onehot = jnp.where(use_fb[..., None], fb_onehot, onehot)
    slow = jnp.where(use_fb, fb_slow, slow)
    from_rec = jnp.where(use_fb, fb_rec, from_rec)
    nodes = jnp.where(ok, jnp.sum(jnp.where(onehot, node, 0), axis=-1), -1)

    taken = onehot & do[..., None]
    d_free = jnp.sum(taken & ~from_rec[..., None], axis=(0, 1), dtype=I32)
    d_rec = jnp.sum(taken & from_rec[..., None], axis=(0, 1), dtype=I32)
    free = node_free - d_free
    rec = node_reclaimable - d_rec
    verified = jnp.all((above == (free > wm)) & (has_page == (free > 0))
                       & (has_reclaim == (rec > 0)))
    ptr = interleave_ptr + jnp.sum(adv, dtype=I32)
    oom = oom_killed | jnp.any(act & ~ok)
    return nodes, slow, ok, act, gate, free, rec, ptr, oom, verified
