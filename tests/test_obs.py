"""End-to-end telemetry: metrics registry, span recorder, no-op default.

Three contracts:

1. **Primitives** — counters/gauges/histograms with fixed log buckets
   (mergeable snapshots), the span recorder's Chrome/Perfetto
   ``trace_event`` export, and the validator that gates CI traces.
2. **Bitwise identity** — every hook is host-side: the blocked engine's
   outputs (placements, counters, timelines) are bit-identical with
   telemetry+tracing on and off, for both ``sweep_lanes`` and the
   sequential facade.
3. **The acceptance burst** — a 64-query mixed burst through an
   instrumented broker yields a snapshot whose compile-count, cache-hit
   and lanes/pad-lanes figures are asserted exactly, plus a
   Perfetto-loadable trace carrying one span per query lifecycle stage
   (admit -> queue -> flush -> sweep -> resolve).
4. **Device scopes and the profiler sink** — the compiled sweep names its
   window kinds, step phases and hoisted scan ticks (``window.*``,
   ``step.*``, ``mig.scan``), and a
   ``jax.profiler`` trace holds each flush's span tree on its host plane,
   every span carrying the flush number.
"""
import json
import math
import re

import numpy as np
import pytest

from repro.core import (CostConfig, MachineConfig, PolicyConfig,
                        TieredMemSimulator, sweep_compile_count, sweep_lanes,
                        FIRST_TOUCH, INTERLEAVE, PT_BIND_HIGH, PT_FOLLOW_DATA)
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry, NULL,
                       NullTelemetry, SpanRecorder, Telemetry, or_null,
                       validate_trace_events)
from repro.obs import validate as validate_cli
from repro.service import SimBroker, SimQuery
from repro.service.broker import _bucket_label

from test_service import random_trace, tiny_machine


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("hits", tier="mem")
    c.inc()
    c.inc(3)
    assert reg.counter("hits", tier="mem") is c, "get-or-create"
    assert reg.counter("hits", tier="disk") is not c, "labels split"
    assert reg.value("hits", tier="mem") == 4
    assert reg.value("hits", tier="disk") == 0
    assert reg.value("hits") is None and reg.value("nope") is None

    g = reg.gauge("depth")
    g.set(7)
    g.inc(-2)
    assert reg.value("depth") == 5

    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("hits")

    snap = reg.snapshot()
    assert snap == {"depth": 5, "hits{tier=disk}": 0, "hits{tier=mem}": 4}
    assert list(snap) == sorted(snap), "deterministic ordering"
    reg.reset()
    assert reg.snapshot() == {}


def test_histogram_fixed_log_buckets():
    h = Histogram(lo=1e-3, base=2.0, n_buckets=8)
    # boundaries never rescale: bucket i spans (lo*2^(i-1), lo*2^i]
    assert h.bucket_of(1e-3) == 0
    assert h.bucket_of(0.0) == 0          # underflow clamps
    assert h.bucket_of(2e-3) == 1
    assert h.bucket_of(2.1e-3) == 2
    assert h.bucket_of(1e9) == 7          # overflow clamps
    assert h.bucket_le(0) == 1e-3
    assert math.isinf(h.bucket_le(7))

    for v in (0.5e-3, 1.5e-3, 1.5e-3, 3e-3):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 4 and s["min"] == 0.5e-3 and s["max"] == 3e-3
    assert s["sum"] == pytest.approx(6.5e-3)
    assert s["mean"] == pytest.approx(6.5e-3 / 4)
    # sparse buckets keyed by inclusive upper bound
    assert s["buckets"] == {"0.001": 1, "0.002": 2, "0.004": 1}

    # fixed boundaries => two snapshots merge bucket-by-bucket
    h2 = Histogram(lo=1e-3, base=2.0, n_buckets=8)
    h2.observe(1.5e-3)
    merged = dict(s["buckets"])
    for k, n in h2.snapshot()["buckets"].items():
        merged[k] = merged.get(k, 0) + n
    assert merged["0.002"] == 3

    empty = Histogram().snapshot()
    assert empty == {"count": 0, "sum": 0.0, "buckets": {}}

    with pytest.raises(ValueError):
        Histogram(lo=0)


# ---------------------------------------------------------------------------
# span recorder + trace_event export
# ---------------------------------------------------------------------------
class TickClock:
    def __init__(self, step=0.5):
        self.t, self.step = 100.0, step

    def __call__(self):
        self.t += self.step
        return self.t


def test_span_recorder_trace_event_export(tmp_path):
    rec = SpanRecorder(clock=TickClock(), process_name="unit")
    with rec.span("outer", cat="test", args={"k": 1}):
        rec.instant("tick")
    rec.add_span("explicit", rec.now(), rec.now(), tid=3)

    assert rec.span_names() == ["outer", "explicit"]
    obj = rec.to_trace_json()
    assert obj["displayTimeUnit"] == "ms"
    meta, *events = obj["traceEvents"]
    assert meta["ph"] == "M" and meta["args"]["name"] == "unit"
    inst, outer, explicit = events
    assert inst["ph"] == "i" and inst["ts"] >= 0
    assert outer["ph"] == "X" and outer["args"] == {"k": 1}
    assert outer["dur"] == pytest.approx(1.0e6)     # 2 ticks x 0.5 s, in us
    assert explicit["tid"] == 3
    assert explicit["dur"] == pytest.approx(0.5e6)
    assert validate_trace_events(obj) == []

    path = tmp_path / "t.json"
    rec.export(path)
    assert validate_trace_events(json.loads(path.read_text())) == []

    rec.reset()
    assert rec.events == [] and rec.dropped == 0


def test_span_recorder_bounded():
    rec = SpanRecorder(clock=TickClock(), max_events=2)
    for i in range(4):
        rec.instant(f"e{i}")
    assert len(rec.events) == 2 and rec.dropped == 2
    obj = rec.to_trace_json()
    assert obj["otherData"]["dropped_events"] == 2


def test_validator_catches_malformed_traces():
    assert validate_trace_events({}) == ["traceEvents missing or not a list"]
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": -1, "dur": 2, "pid": 0, "tid": 0},
        {"name": "b", "ph": "Z", "ts": 0},
        {"name": "c", "ph": "B", "ts": 0, "pid": 0, "tid": 0},
        {"ph": "E", "ts": 1, "pid": 0, "tid": 1},
    ]}
    problems = validate_trace_events(bad)
    assert any("bad ts" in p for p in problems)
    assert any("unknown ph" in p for p in problems)
    assert any("E without matching B" in p for p in problems)
    assert any("unclosed B" in p for p in problems)
    good = {"traceEvents": [
        {"name": "s", "ph": "X", "ts": 0.0, "dur": 1.0, "pid": 0, "tid": 0}]}
    assert validate_trace_events(good) == []


def test_validate_cli(tmp_path, capsys):
    rec = SpanRecorder(clock=TickClock())
    with rec.span("s"):
        pass
    ok = tmp_path / "ok.json"
    rec.export(ok)
    assert validate_cli.main([str(ok)]) == 0
    assert "ok — " in capsys.readouterr().out

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": []}))
    assert validate_cli.main([str(bad)]) == 1
    assert validate_cli.main([str(tmp_path / "missing.json")]) == 1
    assert validate_cli.main([]) == 2


# ---------------------------------------------------------------------------
# telemetry facade + the no-op default
# ---------------------------------------------------------------------------
def test_null_telemetry_is_inert_and_shared():
    assert or_null(None) is NULL
    tel = Telemetry()
    assert or_null(tel) is tel

    assert not NULL.enabled and not NULL.tracing
    # every write is absorbed; metric twins are shared singletons
    assert NULL.counter("x") is NULL.counter("y", a=1)
    NULL.counter("x").inc(5)
    NULL.gauge("g").set(3)
    NULL.histogram("h").observe(1.0)
    assert NULL.counter("x").snapshot() == 0
    with NULL.span("s", a=1):
        pass
    NULL.add_span("s", 0.0, 1.0)
    NULL.instant("i")
    assert NULL.now() is None
    assert NULL.snapshot() == {"metrics": {}}
    assert NULL.export_trace("/nonexistent/x.json") is False
    NULL.reset()
    assert isinstance(NULL, NullTelemetry)


def test_telemetry_facade_tracing_toggle(tmp_path):
    off = Telemetry()                      # metrics on, tracing off
    assert off.enabled and not off.tracing
    off.counter("c").inc()
    assert off.now() is None
    off.add_span("never", 0.0, 1.0)        # no-op without a tracer
    with off.span("timed"):                # histogram only, no event
        pass
    snap = off.snapshot()
    assert set(snap) == {"metrics"}
    assert snap["metrics"]["c"] == 1
    assert snap["metrics"]["timed_seconds"]["count"] == 1
    assert set(snap["metrics"]) == {"c", "timed_seconds"}
    assert off.export_trace(tmp_path / "no.json") is False

    on = Telemetry(tracing=True, clock=TickClock())
    assert on.tracing
    with on.span("s"):
        pass
    on.add_span("t", on.now(), on.now())
    snap = on.snapshot()
    assert snap["trace"]["events"] == 2 and snap["trace"]["dropped"] == 0
    assert on.export_trace(tmp_path / "yes.json") is True
    assert validate_trace_events(
        json.loads((tmp_path / "yes.json").read_text())) == []
    on.reset()
    assert on.snapshot() == {"metrics": {},
                             "trace": {"events": 0, "dropped": 0}}


# ---------------------------------------------------------------------------
# bitwise identity: telemetry hooks never touch the compiled engines
# ---------------------------------------------------------------------------
TELEM_POLICIES = [
    PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                 autonuma=True, autonuma_period=16, autonuma_budget=32),
    PolicyConfig(data_policy=INTERLEAVE, pt_policy=PT_BIND_HIGH, mig=True,
                 autonuma=True, autonuma_period=16, autonuma_budget=16),
]


def assert_bitwise_equal(a, b, label):
    import jax
    fa = jax.tree_util.tree_leaves(a.final_state)
    fb = jax.tree_util.tree_leaves(b.final_state)
    for x, y in zip(fa, fb, strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=label)
    for k in a.timeline:
        np.testing.assert_array_equal(a.timeline[k], b.timeline[k],
                                      err_msg=f"{label}: tl/{k}")


def test_sweep_lanes_bitwise_identical_with_telemetry():
    """The tentpole guarantee: tracing-on blocked-engine outputs are
    bit-identical to telemetry-off, and the spans/counters recorded the
    run's window classification."""
    mc = tiny_machine()
    tr_a = random_trace(mc, seed=41, free_at=30, name="a")
    tr_b = random_trace(mc, seed=42, name="b")
    ccs = [CostConfig(), CostConfig(nvmm_read=1500)]
    trs = [tr_a, tr_b]

    plain = sweep_lanes(mc, ccs, TELEM_POLICIES, trs)
    tel = Telemetry(tracing=True)
    traced = sweep_lanes(mc, ccs, TELEM_POLICIES, trs, telemetry=tel)
    for i, (p, t) in enumerate(zip(plain, traced)):
        assert_bitwise_equal(p, t, f"lane {i}")

    m = tel.metrics
    assert m.value("sweep.calls", engine="blocked") == 1
    assert m.value("sweep.lanes", engine="blocked") == 2
    n_windows = (m.value("sweep.windows_fast")
                 + m.value("sweep.windows_event"))
    assert n_windows == 1, "64-step trace, block=64 -> one window"
    assert m.value("sweep.rows") == 64
    # the populate faults and the free at step 30 span the window: the
    # per-step body replays all of it
    assert m.value("sweep.windows_event") == 1
    assert m.value("sweep.windows_split") == 0
    assert m.value("sweep.replay_rows") == 64
    names = tel.tracer.span_names()
    for name in ("sweep.prepare", "sweep.device", "sweep.readback")\
            + PREPARE_PARTS:
        assert names.count(name) == 1, name
        assert m.value(f"{name}_seconds")["count"] == 1, name
    assert not any(n.startswith("window.") for n in names)
    assert_prepare_parts_nest(tel)


PREPARE_PARTS = ("sweep.schedule", "sweep.plan", "sweep.stage")


def assert_prepare_parts_nest(tel):
    """Each ``sweep.prepare`` holds one schedule, plan and stage span, in
    that order, whose seconds sum to no more than its own."""
    ev = [e for e in tel.tracer.events if e["ph"] == "X"]
    preps = [e for e in ev if e["name"] == "sweep.prepare"]
    assert preps
    for prep in preps:
        lo, hi = prep["ts"], prep["ts"] + prep["dur"]
        parts = [e for e in ev if e["name"] in PREPARE_PARTS
                 and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]
        assert [e["name"] for e in parts] == list(PREPARE_PARTS)
        assert all(e["args"] == prep["args"] for e in parts), \
            "child spans inherit the prepare span's identifiers"
    m = tel.metrics
    parts_s = sum(m.value(f"{n}_seconds")["sum"] for n in PREPARE_PARTS)
    assert parts_s <= m.value("sweep.prepare_seconds")["sum"]
    for n in PREPARE_PARTS:
        assert m.value(f"{n}_seconds")["count"] \
            == m.value("sweep.prepare_seconds")["count"]


def test_simulator_bitwise_identical_with_telemetry():
    mc = tiny_machine()
    tr = random_trace(mc, steps=160, seed=43, free_at=100)
    pc = TELEM_POLICIES[1]
    plain = TieredMemSimulator(mc=mc, pc=pc).run(tr)
    tel = Telemetry(tracing=True)
    traced = TieredMemSimulator(mc=mc, pc=pc, telemetry=tel).run(tr)
    assert_bitwise_equal(plain, traced, "simulator")

    m = tel.metrics
    assert m.value("sim.runs", engine="blocked") == 1
    n_windows = m.value("sim.windows_fast") + m.value("sim.windows_event")
    assert n_windows == math.ceil(160 / 64)
    assert tel.tracer.span_names() == ["sim.run"]
    assert m.value("sim.run_seconds")["count"] == 1


# ---------------------------------------------------------------------------
# the acceptance burst: 64 mixed queries, exact snapshot, loadable trace
# ---------------------------------------------------------------------------
def burst_machine():
    """Distinct shape/config from every other test so the XLA compile
    count measured here is this burst's own, not a jit-cache hit from a
    sibling test in the same process."""
    return MachineConfig(n_threads=4, dram_pages_per_node=280,
                         nvmm_pages_per_node=1120, va_pages=1 << 10,
                         l1_tlb_sets=4, l1_tlb_ways=2, stlb_sets=8,
                         stlb_ways=4, pde_pwc_entries=4, pdpte_pwc_entries=2)


def test_64_query_burst_snapshot_and_trace(tmp_path):
    mc = burst_machine()
    policies = [PolicyConfig(data_policy=d, pt_policy=p, autonuma=False)
                for d in (FIRST_TOUCH, INTERLEAVE)
                for p in (PT_FOLLOW_DATA, PT_BIND_HIGH)]
    traces = [random_trace(mc, steps=96, seed=300 + i, name=f"b{i}")
              for i in range(16)]
    queries = [SimQuery(trace=tr, policy=pc, machine=mc)
               for tr in traces for pc in policies]
    assert len(queries) == 64

    tel = Telemetry(tracing=True)
    broker = SimBroker(max_lanes=64, telemetry=tel)
    before = sweep_compile_count()
    broker.run(queries)
    assert sweep_compile_count() == before + 1

    bkey = broker._bucket_key(queries[0],
                              broker.canonical_trace(queries[0]))
    blabel = _bucket_label(bkey)
    m = tel.metrics

    # exact figures: one bucket, one flush, one compile, 64 distinct
    # lanes, zero padding (64 is already a power of two), zero hits yet
    assert m.value("broker.queries") == 64
    assert m.value("broker.compiles", bucket=blabel) == 1
    assert m.value("broker.flushes", bucket=blabel) == 1
    assert m.value("broker.lanes_run", bucket=blabel) == 64
    assert m.value("broker.pad_lanes", bucket=blabel) == 0
    assert m.value("broker.cache_hits") is None
    assert m.value("cache.mem.misses") == 64
    assert m.value("broker.queue_wait_seconds")["count"] == 64
    assert m.value("broker.flush_seconds")["count"] == 1
    assert m.value("sweep.lanes", engine="blocked") == 64
    # summary lifts: one per-family counter line for the whole burst
    assert m.value("sim.promotions", family="autonuma") == 0
    assert m.value("sim.data_pages", tier=0) is not None

    # replay: answered entirely from cache — no new flush/lanes/compiles
    broker.run(queries)
    assert m.value("broker.queries") == 128
    assert m.value("broker.cache_hits") == 64
    assert m.value("cache.mem.hits") == 64
    assert m.value("broker.lanes_run", bucket=blabel) == 64
    assert m.value("broker.compiles", bucket=blabel) == 1

    # broker.snapshot() is the blessed artifact payload and agrees
    snap = broker.snapshot()
    assert snap["broker"]["queries"] == 128
    assert snap["broker"]["compiles"] == 1
    assert snap["broker"]["lanes_run"] == 64
    assert snap["broker"]["pad_lanes"] == 0
    assert snap["broker"]["pad_ratio"] == 0.0
    assert snap["broker"]["cache_hits"] == 64
    assert snap["cache"]["hits"] == 64 and snap["cache"]["misses"] == 64
    assert snap["pending_lanes"] == 0
    assert snap["telemetry"]["metrics"][f"broker.compiles{{bucket={blabel}}}"] == 1

    # one span per lifecycle stage: every query admits (both passes),
    # every distinct lane queues, the bucket flushes/sweeps/resolves once
    names = tel.tracer.span_names()
    assert names.count("query.admit") == 128
    assert names.count("query.queue") == 64
    assert names.count("broker.flush") == 1
    assert names.count("sweep.device") == 1
    assert names.count("broker.resolve") == 1
    assert not any(n.startswith("window.") for n in names)
    assert_prepare_parts_nest(tel)
    for n in PREPARE_PARTS + ("broker.resolve",):
        assert m.value(f"{n}_seconds")["count"] == 1, n
    admits = [e for e in tel.tracer.events
              if e.get("name") == "query.admit" and e["ph"] == "X"]
    assert sum(e["args"]["cache_hit"] for e in admits) == 64
    digests = {broker.query_digest(q) for q in queries}
    assert {e["args"]["lane"] for e in admits} == digests

    # the exported trace is well-formed, balanced, Perfetto-loadable JSON
    path = tmp_path / "burst_trace.json"
    assert tel.export_trace(path)
    obj = json.loads(path.read_text())
    assert validate_trace_events(obj) == []
    assert validate_cli.main([str(path)]) == 0


def test_burst_pad_lanes_ratio_counted():
    """A 3-lane flush pads to 4: the pad shows up in both the raw counter
    and the ratio, in stats and registry alike."""
    mc = burst_machine()
    tr = random_trace(mc, steps=96, seed=400)
    tel = Telemetry()
    broker = SimBroker(max_lanes=64, max_wait=1e9, telemetry=tel)
    futs = [broker.submit(SimQuery(trace=tr, policy=pc, machine=mc))
            for pc in [PolicyConfig(data_policy=d, autonuma=False)
                       for d in (FIRST_TOUCH, INTERLEAVE)]
            + [PolicyConfig(pt_policy=PT_BIND_HIGH, autonuma=False)]]
    futs[0].result()
    assert broker.stats.pad_lanes == 1 and broker.stats.pad_ratio == 0.25
    bkey = broker._bucket_key(futs[0].query,
                              broker.canonical_trace(futs[0].query))
    assert tel.metrics.value("broker.pad_lanes",
                             bucket=_bucket_label(bkey)) == 1


# ---------------------------------------------------------------------------
# device scopes and the profiler sink
# ---------------------------------------------------------------------------
def scoped_trace(mc, case):
    """64 steps for block 16 and scan period 16.  ``mixed``: window 0
    holds a narrow populate span (split), windows 1 and 3 a lone scan tick
    (hoist), window 2 a tick plus faults 13 rows apart (full); the other
    rows re-access the populated pool.  ``lean``: window 0 faults on every
    row (lean), windows 1-3 re-access the pool around a lone tick each
    (hoist).  Otherwise the threads stay idle and, with AutoNUMA off,
    every window is fast."""
    from test_blocked import make_trace
    T = mc.n_threads
    if case == "all-fast":
        return make_trace(mc, np.full((64, T), -1, np.int32))
    rng = np.random.default_rng(7)
    if case == "lean":
        va = rng.integers(0, 16 * T, (64, T)).astype(np.int64)
        va[:16] = np.arange(16 * T).reshape(16, T)
    else:
        va = rng.integers(0, 4 * T, (64, T)).astype(np.int64)
        va[:4] = np.arange(4 * T).reshape(4, T)
        va[33, 0], va[45, 0] = 4 * T, 4 * T + 1
    return make_trace(mc, (va << mc.map_shift).astype(np.int32))


@pytest.mark.parametrize("case", ["all-fast", "mixed", "lean"])
def test_sweep_runner_hlo_carries_window_and_step_scopes(monkeypatch, case):
    """The compiled lane sweep names each window kind its geometry
    compiles (``window.*``: lean wherever full) and, where it compiles
    the per-step body, each step phase (``step.*``) in its ops' metadata;
    the lean row body holds only ``step.access`` and ``step.fault``, and
    the hoisted scan tick runs under ``mig.scan`` inside ``window.hoist``
    wherever that window compiles (a replayed tick stays ``step.scan``)."""
    import importlib
    import re
    from test_blocked import tiny_machine as blocked_machine
    sweep_mod = importlib.import_module("repro.core.sweep")

    mc = blocked_machine()
    pc = PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                      autonuma=case != "all-fast", autonuma_period=16,
                      autonuma_budget=32)
    texts = []
    real = sweep_mod._sweep_runner

    def spy(*a, **kw):
        fn = real(*a, **kw)

        def run(*args):
            texts.append(fn.lower(*args).compile().as_text())
            return fn(*args)
        return run

    monkeypatch.setattr(sweep_mod, "_sweep_runner", spy)
    tel = Telemetry()
    sweep_lanes(mc, [CostConfig()], [pc], [scoped_trace(mc, case)],
                block=16, telemetry=tel)
    m = tel.metrics
    kinds = {"window.fast": m.value("sweep.windows_fast"),
             "window.full": (m.value("sweep.windows_event")
                             - m.value("sweep.windows_hoist")
                             - m.value("sweep.windows_split")
                             - m.value("sweep.windows_lean")),
             "window.lean": m.value("sweep.windows_lean"),
             "window.hoist": m.value("sweep.windows_hoist"),
             "window.split": m.value("sweep.windows_split")}
    if case == "mixed":
        assert kinds == {"window.fast": 0, "window.full": 1,
                         "window.lean": 0, "window.hoist": 2,
                         "window.split": 1}
        assert m.value("sweep.replay_rows") == 16 + 4
    elif case == "lean":
        assert kinds == {"window.fast": 0, "window.full": 0,
                         "window.lean": 1, "window.hoist": 3,
                         "window.split": 0}
        assert m.value("sweep.replay_rows") == 16
    else:
        assert kinds == {"window.fast": 4, "window.full": 0,
                         "window.lean": 0, "window.hoist": 0,
                         "window.split": 0}
        assert m.value("sweep.replay_rows") == 0
    assert m.value("sweep.rows") == 64
    (text,) = texts
    # under vmap a scope reads "vmap(step.access)"
    scopes = set(re.findall(r"[/(]((?:window|step)\.[a-z]+)[/)]", text))
    # fast is always compiled: it is branch 0 of every geometry
    want = {"window.fast"} | {k for k, n in kinds.items() if n}
    if kinds["window.full"] or kinds["window.lean"]:
        want |= {"window.full", "window.lean"}
    if want & {"window.full", "window.split"}:
        want |= {"step.free", "step.scan", "step.access", "step.fault"}
    assert scopes == want
    # the step phases each window kind's ops are named under
    paths = re.findall(r'op_name="([^"]*)"', text)
    lean_steps = {st for p in paths if "window.lean" in p
                  for st in re.findall(r"step\.[a-z]+", p)}
    assert lean_steps == ({"step.access", "step.fault"}
                          if "window.lean" in want else set())
    mig = [p for p in paths if "mig.scan" in p]
    assert bool(mig) == ("window.hoist" in want)
    assert all("window.hoist" in p and "step." not in p for p in mig)
    # the fault allocator's arms, both under step.fault: the one-pass
    # vector arm, then in every replaying window kind one conditional on
    # a scalar (the AND over lanes, reduced outside the lane vmap) with
    # the slot scan in one branch only: a per-lane predicate would have
    # become a select running both arms
    replay_kinds = want & {"window.full", "window.lean", "window.split"}
    alloc = [p for p in paths if "fault.alloc_" in p]
    assert {a for p in alloc for a in re.findall(r"fault\.alloc_[a-z]+", p)} \
        == ({"fault.alloc_batched", "fault.alloc_scan"}
            if replay_kinds else set())
    assert all("step.fault/" in p for p in alloc)
    comps = hlo_computations(text)
    types = dict(re.findall(r"^\s*(%[\w.\-]+) = (\S+)", text, re.M))
    conds = re.findall(r" conditional\((%[\w.\-]+),.*branch_computations="
                       r"\{([^}]*)\}.*op_name=\"([^\"]*)\"", text)
    for kind in replay_kinds:
        mine = [(pred, branches.split(", "))
                for pred, branches, path in conds if kind in path]
        # the allocator's conditional: the scan in a branch, the vector
        # pass outside it (the fault cond around both holds the two)
        arm_conds = [
            (pred, [hlo_reaches(comps, b, "fault.alloc_scan")
                    for b in branches]) for pred, branches in mine
            if not any(hlo_reaches(comps, b, "fault.alloc_batched")
                       for b in branches)
            and any(hlo_reaches(comps, b, "fault.alloc_scan")
                    for b in branches)]
        assert len(arm_conds) == 1, kind
        (pred, hits), = arm_conds
        assert types[pred] in ("pred[]", "s32[]"), (kind, types[pred])
        assert hits.count(True) == 1, kind
        if kind == "window.lean":
            assert len(mine) == 1       # the lean body's only conditional


def hlo_computations(text):
    """Compiled HLO text -> {computation name: its lines}."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return comps


def hlo_reaches(comps, root, needle):
    """Does computation ``root``, or one it calls, hold an op whose
    metadata names ``needle``?"""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            if needle in line:
                return True
            todo += [c for c in re.findall(r"%[\w.\-]+", line)
                     if c in comps]
    return False


@pytest.mark.parametrize("entry", ["sweep", "sim"])
def test_scan_tick_counters_match_the_plan(entry):
    """``sweep.scan_ticks`` (``sim.scan_ticks`` for a solo run) counts
    the window plan's scan ticks by arm: in the ``mixed`` trace windows 1
    and 3 hoist theirs and window 2 replays its tick inside a full
    window.  Outputs stay bit-identical to telemetry off."""
    from repro.core.sim import blocked_xs, scan_step_mask
    from test_blocked import tiny_machine as blocked_machine
    mc = blocked_machine()
    pc = PolicyConfig(data_policy=FIRST_TOUCH, pt_policy=PT_FOLLOW_DATA,
                      autonuma=True, autonuma_period=16, autonuma_budget=32)
    tr = scoped_trace(mc, "mixed")
    _, plan = blocked_xs(tr, mc, pc, block=16)
    assert plan.scan_ticks == (2, 1)
    assert sum(plan.scan_ticks) == int(scan_step_mask(64, 16).sum())

    tel = Telemetry(tracing=True)
    if entry == "sweep":
        plain, = sweep_lanes(mc, [CostConfig()], [pc], [tr], block=16)
        traced, = sweep_lanes(mc, [CostConfig()], [pc], [tr], block=16,
                              telemetry=tel)
    else:
        plain = TieredMemSimulator(mc=mc, pc=pc, block=16).run(tr)
        traced = TieredMemSimulator(mc=mc, pc=pc, block=16,
                                    telemetry=tel).run(tr)
    assert_bitwise_equal(plain, traced, entry)
    m = tel.metrics
    assert (m.value(f"{entry}.scan_ticks", arm="hoist"),
            m.value(f"{entry}.scan_ticks", arm="replay")) == plan.scan_ticks
    assert m.value(f"{entry}.windows_hoist") == plan.scan_ticks[0]


def read_host_spans(trace_dir):
    """(name, start_s, end_s, stats) of every ``broker.*`` and ``sweep.*``
    host event in the one ``.xplane.pb`` under ``trace_dir``, read as
    ``bench/devtrace.py`` reads the profiler's output."""
    import warnings
    from pathlib import Path
    from jax.profiler import ProfileData
    (path,) = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    out = []
    with warnings.catch_warnings():
        # the stats view's type warns that it names no module
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(str(path)).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    out += [(ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9,
                             dict(ev.stats)) for ev in line.events
                            if ev.name.startswith(("broker.", "sweep."))]
    return out


def test_profiler_trace_holds_the_flush_span_tree(tmp_path):
    """With metrics-only telemetry under ``jax.profiler``, each flush
    lands on the host plane as ``broker.flush`` holding ``sweep.prepare``
    (holding schedule, plan and stage), then ``sweep.device``,
    ``sweep.readback`` and ``broker.resolve``, each carrying the flush
    number and bucket label."""
    import jax
    mc = burst_machine()
    pc = PolicyConfig(data_policy=FIRST_TOUCH, autonuma=False)
    queries = [SimQuery(trace=random_trace(mc, steps=96, seed=500 + i),
                        policy=pc, machine=mc) for i in range(4)]
    broker = SimBroker(max_lanes=2, telemetry=Telemetry())
    broker.run(queries[:2])                     # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        broker.run(queries[2:])
    finally:
        jax.profiler.stop_trace()
    spans = read_host_spans(tmp_path)
    flushes = [sp for sp in spans if sp[0] == "broker.flush"]
    assert [sp[3]["flush"] for sp in flushes] == [2]
    (_, lo, hi, ids) = flushes[0]
    assert ids["bucket"] == _bucket_label(
        broker._bucket_key(queries[2], queries[2].trace))

    def inside(name, a, b):
        found = [sp for sp in spans if sp[0] == name
                 and a <= sp[1] and sp[2] <= b]
        assert len(found) == 1, name
        assert found[0][3]["flush"] == 2 and \
            found[0][3]["bucket"] == ids["bucket"], name
        return found[0]

    prep = inside("sweep.prepare", lo, hi)
    parts = [inside(n, prep[1], prep[2]) for n in PREPARE_PARTS]
    assert [p[1] for p in parts] == sorted(p[1] for p in parts)
    dev = inside("sweep.device", prep[2], hi)
    back = inside("sweep.readback", dev[2], hi)
    inside("broker.resolve", back[2], hi)


def test_queue_span_names_the_flush_that_resolved_it():
    """Each lane's ``query.queue`` carries the number of the flush whose
    ``broker.resolve`` settled it, and its lane digest."""
    from repro.service.query import lane_digest
    mc = burst_machine()
    pc = PolicyConfig(data_policy=FIRST_TOUCH, autonuma=False)
    queries = [SimQuery(trace=random_trace(mc, steps=96, seed=600 + i),
                        policy=pc, machine=mc) for i in range(6)]
    tel = Telemetry(tracing=True)
    broker = SimBroker(max_lanes=2, telemetry=tel)
    settled = {}                                # lane digest -> clock
    real_put = broker.cache.put

    def put(key, res):
        settled[lane_digest(key)] = tel.tracer._ts(tel.clock())
        real_put(key, res)

    broker.cache.put = put
    broker.run(queries)

    ev = [e for e in tel.tracer.events if e["ph"] == "X"]
    resolves = [e for e in ev if e["name"] == "broker.resolve"]
    assert sorted(e["args"]["flush"] for e in resolves) == [1, 2, 3]
    queued = [e for e in ev if e["name"] == "query.queue"]
    assert {e["args"]["lane"] for e in queued} \
        == {broker.query_digest(q) for q in queries} == set(settled)
    for e in queued:
        t = settled[e["args"]["lane"]]
        (res,) = [r for r in resolves
                  if r["ts"] <= t <= r["ts"] + r["dur"]]
        assert e["args"]["flush"] == res["args"]["flush"]
