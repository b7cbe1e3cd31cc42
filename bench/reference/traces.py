"""The benchmark's own copy of the workload trace generators (paper Table 2).

The reference builds every trace it checks from the query's recipe
(workload, footprint, run steps, seed) with these generators, so a fault
in the program's trace build shows as a mismatch instead of feeding both
sides.  The semantics follow the published description of each workload:

  kv_store   Memcached/Redis: sequential heap growth during populate with
             interleaved reads of the growing table, then YCSB-zipfian
             (theta=0.99) reads over pages scattered by a permutation.
  btree      each lookup walks root -> inner -> inner -> leaf regions.
  hashjoin   build (populate) + uniform random probes.
  xsbench    uniform reads of large tables + a small hot index region.
  bfs        sequential neighbour runs with power-law jump targets.

A trace is ``va[steps, threads]`` (4 KiB page numbers, -1 idle),
``is_write``, ``free_seg`` (all -1: these workloads never free) and the
per-step LLC hit rate ``llc``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class RefTrace:
    va: np.ndarray
    is_write: np.ndarray
    free_seg: np.ndarray
    llc: np.ndarray
    populate_steps: int

    @property
    def n_steps(self) -> int:
        return int(self.va.shape[0])


def _zipf_cdf(n, theta):
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = 1.0 / ranks ** theta
    return np.cumsum(w) / np.sum(w)


def _zipf_sample(rng, cdf, size):
    return np.searchsorted(cdf, rng.random(size)).astype(np.int32)


def _populate_rows(rng, footprint, T, read_mix):
    """Thread t faults pages [t*S, (t+1)*S) in order; ``read_mix`` of its
    steps are reads of an already-touched page of its shard."""
    shard = footprint // T
    steps = shard + int(shard * read_mix)
    va = np.full((steps, T), -1, np.int32)
    wr = np.zeros((steps, T), bool)
    for t in range(T):
        base = t * shard
        seq = np.arange(shard, dtype=np.int32) + base
        n_reads = steps - shard
        read_pos = rng.choice(steps, size=n_reads, replace=False) \
            if n_reads else np.empty((0,), np.int64)
        is_read = np.zeros(steps, bool)
        is_read[read_pos] = True
        col = np.empty(steps, np.int32)
        col[~is_read] = seq
        prog = np.maximum(np.cumsum(~is_read) - 1, 0)
        col[is_read] = base + (rng.random(steps) * np.maximum(prog, 1)
                               ).astype(np.int32)[is_read] % shard
        va[:, t] = col
        wr[:, t] = ~is_read
    return va, wr


def _finish(va, wr, llc_pop, llc_run, populate_steps):
    steps = va.shape[0]
    llc = np.full((steps,), llc_run, np.float32)
    llc[:populate_steps] = llc_pop
    return RefTrace(va=va.astype(np.int32), is_write=wr,
                    free_seg=np.full((steps,), -1, np.int32), llc=llc,
                    populate_steps=populate_steps)


def _fit(footprint, va_pages, T):
    return min(footprint, va_pages) // T * T


def kv_store(T, va_pages, footprint, run_steps, seed, theta=0.99,
             write_frac=0.0):
    rng = np.random.default_rng(seed)
    footprint = _fit(footprint, va_pages, T)
    perm = rng.permutation(footprint).astype(np.int32)
    pva, pwr = _populate_rows(rng, footprint, T, read_mix=0.5)
    cdf = _zipf_cdf(footprint, theta)
    rva = perm[_zipf_sample(rng, cdf, (run_steps, T))]
    rwr = rng.random((run_steps, T)) < write_frac
    return _finish(np.concatenate([pva, rva]), np.concatenate([pwr, rwr]),
                   0.45, 0.50, pva.shape[0])


def hashjoin(T, va_pages, footprint, run_steps, seed):
    rng = np.random.default_rng(seed)
    footprint = _fit(footprint, va_pages, T)
    pva, pwr = _populate_rows(rng, footprint, T, read_mix=0.25)
    rva = rng.integers(0, footprint, (run_steps, T), dtype=np.int32)
    rwr = np.zeros((run_steps, T), bool)
    return _finish(np.concatenate([pva, rva]), np.concatenate([pwr, rwr]),
                   0.35, 0.15, pva.shape[0])


def xsbench(T, va_pages, footprint, run_steps, seed):
    rng = np.random.default_rng(seed)
    footprint = _fit(footprint, va_pages, T)
    pva, pwr = _populate_rows(rng, footprint, T, read_mix=0.1)
    hot = max(footprint // 64, 1)
    r = rng.random((run_steps, T))
    idx_hot = rng.integers(0, hot, (run_steps, T), dtype=np.int32)
    idx_cold = rng.integers(hot, footprint, (run_steps, T), dtype=np.int32)
    rva = np.where(r < 0.2, idx_hot, idx_cold).astype(np.int32)
    return _finish(np.concatenate([pva, rva]),
                   np.concatenate([pwr, np.zeros((run_steps, T), bool)]),
                   0.30, 0.10, pva.shape[0])


def btree(T, va_pages, footprint, run_steps, seed):
    rng = np.random.default_rng(seed)
    footprint = _fit(footprint, va_pages, T)
    pva, pwr = _populate_rows(rng, footprint, T, read_mix=0.0)
    r0 = max(footprint // 32768, 1)
    r1 = max(footprint // 512, 1)
    r2 = max(footprint // 16, 1)
    lookups = run_steps // 4
    lv0 = rng.integers(0, r0, (lookups, T), dtype=np.int32)
    lv1 = r0 + rng.integers(0, r1, (lookups, T), dtype=np.int32)
    lv2 = r0 + r1 + rng.integers(0, r2, (lookups, T), dtype=np.int32)
    lv3 = rng.integers(r0 + r1 + r2, footprint, (lookups, T), dtype=np.int32)
    rva = np.stack([lv0, lv1, lv2, lv3], axis=1).reshape(lookups * 4, T)
    return _finish(np.concatenate([pva, rva]),
                   np.concatenate([pwr, np.zeros((rva.shape[0], T), bool)]),
                   0.40, 0.35, pva.shape[0])


def bfs(T, va_pages, footprint, run_steps, seed, run_len=8):
    rng = np.random.default_rng(seed)
    footprint = _fit(footprint, va_pages, T)
    pva, pwr = _populate_rows(rng, footprint, T, read_mix=0.0)
    n_jumps = run_steps // run_len + 1
    cdf = _zipf_cdf(footprint, 0.6)
    starts = _zipf_sample(rng, cdf, (n_jumps, T))
    offs = np.arange(run_len, dtype=np.int32)[None, :, None]
    rva = ((starts[:, None, :] + offs) % footprint).reshape(-1, T)[:run_steps]
    return _finish(np.concatenate([pva, rva.astype(np.int32)]),
                   np.concatenate([pwr, np.zeros((rva.shape[0], T), bool)]),
                   0.35, 0.25, pva.shape[0])


GENERATORS = {
    "memcached": kv_store,
    "redis": kv_store,
    "btree": btree,
    "hashjoin": hashjoin,
    "xsbench": xsbench,
    "bfs": bfs,
}


def pow2_steps(n: int, floor: int = 64) -> int:
    """Smallest power of two >= max(n, floor): the step count a served
    query is idle-padded to."""
    p = floor
    while p < n:
        p *= 2
    return p


def build(workload: str, T: int, va_pages: int, footprint: int,
          run_steps: int, seed: int) -> RefTrace:
    """The trace of one query, at its natural length."""
    return GENERATORS[workload](T, va_pages, footprint, run_steps, seed)


def pad(tr: RefTrace, n_steps: int) -> RefTrace:
    """``tr`` followed by idle rows up to ``n_steps``."""
    extra = n_steps - tr.n_steps
    if extra <= 0:
        return tr
    T = tr.va.shape[1]
    return RefTrace(
        va=np.concatenate([tr.va, np.full((extra, T), -1, np.int32)]),
        is_write=np.concatenate([tr.is_write, np.zeros((extra, T), bool)]),
        free_seg=np.concatenate([tr.free_seg,
                                 np.full((extra,), -1, np.int32)]),
        llc=np.concatenate([tr.llc, np.zeros((extra,), np.float32)]),
        populate_steps=tr.populate_steps)
