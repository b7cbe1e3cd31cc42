"""The reduction from a profiler trace to busy time, idle share and
breakdown: on synthetic events, and on a trace recorded here."""
import pytest

from bench import devtrace


def test_union_and_gaps():
    busy = devtrace.union([(1, 3), (2, 4), (6, 7), (0, 0.5)], 1, 10)
    assert busy == [(1, 4), (6, 7)]
    assert devtrace.gaps(busy, 0, 10) == [(0, 1), (4, 6), (7, 10)]


def test_idle_share_and_labels():
    spans = [("bench.grid", 0.0, 10.0), ("bench.trace_build", 0.0, 2.0),
             ("bench.drain", 2.0, 10.0)]
    ops = {"/device:TPU:0": [("fusion.1", 2.0, 5.0), ("fusion.2", 4.0, 6.0),
                             ("while", 7.0, 9.0), ("late", 9.5, 12.0)]}
    red = devtrace.reduce_trace(spans, ops)
    # busy: [2, 6] + [7, 9] + [9.5, 10] (clipped to the window)
    assert red["window_s"] == pytest.approx(10.0)
    assert red["busy_s"] == pytest.approx(6.5)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(3.0)]
    gaps = red["idle_gaps"]
    assert gaps[0] == ["bench.trace_build", pytest.approx(2.0)]
    assert sorted(g[1] for g in gaps) == pytest.approx([0.5, 1.0, 2.0])
    assert {g[0] for g in gaps} == {"bench.trace_build", "bench.drain"}


def test_busy_is_the_mean_over_devices():
    spans = [("bench.grid", 0.0, 4.0)]
    ops = {"/device:TPU:0": [("a", 0.0, 4.0)],
           "/device:TPU:1": [("a", 0.0, 2.0)]}
    red = devtrace.reduce_trace(spans, ops)
    assert red["busy_s"] == pytest.approx(3.0)
    assert red["device_ops"] == [["a", pytest.approx(3.0)]]


def test_window_ends_where_the_profiler_dropped_events():
    spans = [("bench.grid", 0.0, 10.0)]
    ops = {"/device:TPU:0": [("a", 1.0, 3.0)]}
    red = devtrace.reduce_trace(spans, ops, dropped_at=4.0)
    assert red["window_s"] == pytest.approx(4.0)
    assert red["busy_s"] == pytest.approx(2.0)
    assert devtrace.reduce_trace(spans, ops, dropped_at=-1.0) is None


def test_nothing_to_read_gives_none():
    assert devtrace.reduce_trace([("bench.grid", 0.0, 1.0)], {}) is None
    assert devtrace.reduce_trace([], {"/device:TPU:0": [("a", 0, 1)]}) \
        is None


def test_recorded_trace(tmp_path):
    """A trace recorded on this host: the benchmark's spans come back on
    one clock and in order; the CPU has no device plane, so the reduction
    has nothing to read."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.grid"):
            with jax.profiler.TraceAnnotation("bench.drain"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    tr = devtrace.read_xplane(path)
    spans, ops = tr["spans"], tr["ops"]
    by_name = {n: (s, e) for n, s, e in spans}
    assert set(by_name) == {"bench.grid", "bench.drain"}
    g, d = by_name["bench.grid"], by_name["bench.drain"]
    assert g[0] <= d[0] < d[1] <= g[1]
    assert not any(ops.values()) and tr["dropped_at"] is None
    assert devtrace.reduce_trace(**tr) is None


@pytest.mark.parametrize("text,name", [
    ("%copy.1 = s32[16]{0:T(128)} copy(s32[16]{0:T(128)} %args_0_.1)",
     "copy.1 copy"),
    ("%cond.434 = (s32[16,4096]{1,0:T(8,128)}, pred[16]{0:T(512)(128)(4,1)})"
     " conditional(s32[]{:T(128)} %bitcast.3146, (s32[128,16,32]",
     "cond.434 conditional"),
    ("jit_convert_element_type(3470333007927857990)",
     "jit_convert_element_type(3470333007927857990)"),
])
def test_op_names_keep_name_and_opcode(text, name):
    assert devtrace.op_name(text) == name
