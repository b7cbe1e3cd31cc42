"""The window arithmetic: all work over all time, the tail over all
queries, idle pad rows counted as no access."""
import numpy as np
import pytest

from bench import window
from bench.run import check_sample
from bench.reference import traces


def grid(t0, t1, queries, accesses, failed=0):
    return window.GridRecord(t_submit=t0, t_done=t1, queries=queries,
                             failed=failed, accesses=accesses)


def test_rate_is_all_work_over_all_time():
    # gaps between grids (host work after a drain) count as window time
    grids = [grid(10.0, 14.0, 24, 1000), grid(15.0, 20.0, 24, 1000)]
    assert window.window_seconds(grids) == pytest.approx(10.0)
    assert window.sim_accesses_per_s(grids) == pytest.approx(200.0)


def test_p90_is_over_every_query_not_every_grid():
    # 9 queries answered in 1 s, 1 query in 11 s: per grid the median grid
    # would read 6 s; over the ten queries the 90th percentile sits
    # between the two times
    grids = [grid(0.0, 1.0, 9, 9), grid(1.0, 12.0, 1, 1)]
    assert window.query_p90_s(grids) == pytest.approx(1.0 + 0.1 * 10.0)
    values = [1.0] * 9 + [11.0]
    assert window.percentile(values, 90) == pytest.approx(
        np.quantile(values, 0.9))


def test_pad_rows_are_not_accesses():
    tr = traces.build("btree", 4, 1 << 12, 1 << 10, 64, seed=3)
    padded = traces.pad(tr, traces.pow2_steps(tr.n_steps))
    assert padded.n_steps > tr.n_steps
    assert window.accesses(padded.va) == window.accesses(tr.va) \
        == tr.n_steps * 4


def test_failed_queries_add_no_accesses_but_count_as_attempted():
    grids = [grid(0.0, 2.0, 4, 300, failed=1)]
    assert window.sim_accesses_per_s(grids) == pytest.approx(150.0)
    assert window.query_p90_s(grids) == pytest.approx(2.0)


@pytest.mark.parametrize("steps,max_lanes,n", [
    ([4096] * 16 + [2048] * 8, 32, 4),   # two buckets, one flush each
    ([1024] * 12, 8, 4),                 # one bucket, two flushes
    ([512], 8, 1),
])
def test_check_sample_covers_both_halves_of_every_flush(steps, max_lanes, n):
    for seed in range(20):
        picks = check_sample(steps, max_lanes, np.random.default_rng(seed))
        assert len(picks) == n
        assert max(steps[i] for i in picks) == max(steps)
        for s in set(steps):
            lanes = [i for i, x in enumerate(steps) if x == s]
            for k in range(0, len(lanes), max_lanes):
                flush = lanes[k:k + max_lanes]
                half = (len(flush) + 1) // 2
                assert any(i in flush[:half] for i in picks)
                if len(flush) > 1:
                    assert any(i in flush[half:] for i in picks)
