"""The perf observatory: BenchRecord schema, regression gate, flight
recorder, run manifest, and the extended artifact validator.

Five contracts:

1. **BenchRecord** — ``make_record`` emits schema-valid records with a
   machine fingerprint and dotted-path metrics; history.jsonl
   round-trips; run ids stay monotonic.
2. **The gate** — ``report --check`` passes on the repo's committed
   history/baselines (green path) and fails non-zero, naming the
   metric, on a seeded 30% synthetic regression; min/max/best entry
   kinds implement exactly the documented semantics.
3. **Quantiles** — ``Histogram.quantile`` + snapshot ``merge``:
   merge-then-quantile equals observe-all-then-quantile exactly, and
   both land within one bucket of the same-rank empirical quantile.
4. **Flight recorder** — a persistent poison through a real broker
   dumps a schema-valid postmortem carrying spans, a metrics delta and
   the quarantined digest — and the dump path never perturbs results.
5. **Validator** — partial same-track span overlap and non-monotonic
   B/E tracks are rejected; nesting/containment passes; the CLI is
   schema-aware across traces, history logs and postmortems.
"""
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

from repro import compile_cache
from repro.obs import bench as obsbench
from repro.obs import (FlightRecorder, Histogram, Telemetry, make_record,
                       append_record, flatten_metrics, load_history, merge,
                       next_run_id, quantile_from_snapshot, validate_record,
                       validate_postmortem, validate_trace_events)
from repro.obs import validate as validate_cli
from repro.obs import report as report_mod
from repro.obs.bench import namespace_of
from repro.obs.inject import FaultInjector, fail_lane
from repro.service import SimBroker, SimQuery
from repro.service.resilience import PoisonedQueryError

from test_service import MIXED_POLICIES, random_trace, tiny_machine

REPO = Path(__file__).resolve().parent.parent
COMMITTED_HISTORY = REPO / "artifacts" / "bench" / "history.jsonl"
COMMITTED_BASELINES = REPO / "artifacts" / "bench" / "baselines.json"


# ---------------------------------------------------------------------------
# BenchRecord schema + history
# ---------------------------------------------------------------------------
def test_make_record_is_schema_valid():
    rec = make_record(
        driver="demo", quick=True, run_id=3, wall_seconds=1.5,
        payload={"a": {"b": 2.0, "ok": True, "name": "skipme",
                       "pair": [1, 2]},
                 "snapshot": {"not": "a metric"}},
        figures=[("demo/x", 0.25, "speedup=2x")],
        clock=lambda: 1700000000.0)
    assert validate_record(rec) == []
    assert rec["metrics"] == {"a.b": 2.0, "a.ok": 1.0,
                              "a.pair.0": 1.0, "a.pair.1": 2.0}
    assert rec["figures"] == [["demo/x", 0.25, "speedup=2x"]]
    fp = rec["fingerprint"]
    assert fp["device_platform"] and fp["jax"] and fp["python"]
    assert rec["namespace"] == namespace_of(fp)


def test_fingerprint_of_failed_device_is_not_filed_under_cpu(monkeypatch):
    """A device that fails to initialise must not yield a record filed
    under ``cpu``: the fingerprint raises, and a fingerprint without a
    known platform gets a namespace of its own."""
    import jax

    def no_device():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(obsbench, "_FINGERPRINT", None)
    monkeypatch.setattr(jax, "devices", no_device)
    with pytest.raises(RuntimeError, match="initialize backend"):
        obsbench.fingerprint()
    assert obsbench._FINGERPRINT is None
    assert namespace_of({"device_platform": "unknown",
                         "device_kind": "unknown"}) != "cpu"
    assert namespace_of({}) != "cpu"
    assert namespace_of({"device_platform": "cpu"}) == "cpu"
    assert namespace_of({"device_platform": "tpu",
                         "device_kind": "TPU v5 lite"}) == "tpu:tpu-v5-lite"


def test_validate_record_rejects():
    assert validate_record([]) == ["record is not an object"]
    rec = make_record(driver="demo", run_id=0)
    bad = dict(rec, schema="nope", run_id=-1)
    problems = "\n".join(validate_record(bad))
    assert "schema" in problems and "negative" in problems
    bad = dict(rec, metrics={"x": "not-a-number"})
    assert any("numeric" in p for p in validate_record(bad))


def test_flatten_metrics_skips_non_scalars():
    flat = flatten_metrics({
        "inf": float("inf"), "nan": float("nan"), "s": "str",
        "long": list(range(100)), "deep": {"v": 4},
        "telemetry": {"hidden": 1}, "n": 7})
    assert flat == {"deep.v": 4.0, "n": 7.0}


def test_history_roundtrip_and_monotonic_run_id(tmp_path):
    hist = tmp_path / "history.jsonl"
    assert next_run_id(hist) == 0
    for i in range(3):
        append_record(make_record(driver="d", run_id=i,
                                  payload={"m": i}), hist)
    records, problems = load_history(hist)
    assert problems == [] and len(records) == 3
    assert [r["metrics"]["m"] for r in records] == [0.0, 1.0, 2.0]
    assert next_run_id(hist) == 3
    # a corrupt line is reported, not silently swallowed
    with open(hist, "a") as fh:
        fh.write("{broken\n")
    _, problems = load_history(hist)
    assert any("unparseable" in p for p in problems)


def test_validator_rejects_duplicate_run_id_per_driver(tmp_path, capsys):
    """One run id is shared by every driver of a ``benchmarks.run``
    invocation, but a (run_id, driver) pair appearing twice in one
    manifest is a double-append and must fail validation."""
    hist = tmp_path / "history.jsonl"
    rec_a = make_record(driver="steady_state", run_id=0, payload={"m": 1})
    rec_b = make_record(driver="fault_batch", run_id=0, payload={"m": 2})
    append_record(rec_a, hist)
    append_record(rec_b, hist)    # same run id, different driver: fine
    assert validate_cli.main([str(hist)]) == 0
    capsys.readouterr()
    append_record(rec_a, hist)    # the exact double-append
    assert validate_cli.main([str(hist)]) == 1
    err = capsys.readouterr().err
    assert "duplicate record for run_id=0 driver='steady_state'" in err
    assert "first at line 1" in err


# ---------------------------------------------------------------------------
# the regression gate
# ---------------------------------------------------------------------------
def _history_of(tmp_path, values, driver="steady_state",
                payload_of=lambda v: {"steady": {"8lane": {"speedup": v}}}):
    hist = tmp_path / "history.jsonl"
    for i, v in enumerate(values):
        append_record(make_record(driver=driver, payload=payload_of(v),
                                  run_id=i, clock=lambda t=i: 1000.0 + t),
                      hist)
    return hist


def _baselines_of(tmp_path, entries):
    path = tmp_path / "baselines.json"
    path.write_text(json.dumps({
        "schema": "bench-baselines/v1",
        "namespaces": {"cpu": {"entries": entries}}}))
    return path


def test_seeded_30pct_regression_fails_and_names_metric(tmp_path, capsys):
    # best-known 6.0; the last three runs degraded 30% -> candidate 4.2
    # misses the 15% tolerance band and the gate must say which metric
    hist = _history_of(tmp_path, [6.0, 6.1, 4.2, 4.2, 4.2])
    base = _baselines_of(tmp_path, [
        {"driver": "steady_state", "metric": "steady.8lane.speedup",
         "kind": "best", "value": 6.0, "rel_tol": 0.15, "min_of_n": 3}])
    rc = report_mod.main(["--check", "--history", str(hist),
                          "--baselines", str(base)])
    assert rc == 1
    err = capsys.readouterr().err
    assert "REGRESSION" in err and "steady.8lane.speedup" in err


def test_min_of_n_damps_single_noisy_dip(tmp_path):
    # one bad run inside the window is tolerated (best-of-3) ...
    hist = _history_of(tmp_path, [6.0, 3.0, 5.9])
    base = _baselines_of(tmp_path, [
        {"driver": "steady_state", "metric": "steady.8lane.speedup",
         "kind": "best", "value": 6.0, "rel_tol": 0.15, "min_of_n": 3}])
    assert report_mod.main(["--check", "--history", str(hist),
                            "--baselines", str(base)]) == 0


def test_min_max_kinds_judge_latest_sample(tmp_path, capsys):
    hist = _history_of(tmp_path, [6.0, 1.1])     # newest violates a floor
    base = _baselines_of(tmp_path, [
        {"driver": "steady_state", "metric": "steady.8lane.speedup",
         "kind": "min", "value": 2.0}])
    assert report_mod.main(["--check", "--history", str(hist),
                            "--baselines", str(base)]) == 1
    capsys.readouterr()
    # a max bar: metric must stay at/below the ceiling
    hist2 = _history_of(tmp_path / "h2" if False else tmp_path,
                        [0.0, 0.0], driver="chaos",
                        payload_of=lambda v: {"gates": {"stranded": v}})
    base2 = _baselines_of(tmp_path, [
        {"driver": "chaos", "metric": "gates.stranded",
         "kind": "max", "value": 0}])
    assert report_mod.main(["--check", "--history", str(hist2),
                            "--baselines", str(base2)]) == 0


def test_missing_history_sample_is_a_failure(tmp_path, capsys):
    hist = _history_of(tmp_path, [6.0])
    base = _baselines_of(tmp_path, [
        {"driver": "steady_state", "metric": "no.such.metric",
         "kind": "min", "value": 1.0}])
    assert report_mod.main(["--check", "--history", str(hist),
                            "--baselines", str(base)]) == 1
    assert "no history sample" in capsys.readouterr().err


def test_update_baselines_ratchets_best_entries(tmp_path):
    hist = _history_of(tmp_path, [6.0, 7.5, 7.0])
    base = _baselines_of(tmp_path, [
        {"driver": "steady_state", "metric": "steady.8lane.speedup",
         "kind": "best", "value": 6.0, "rel_tol": 0.2, "min_of_n": 3},
        {"driver": "steady_state", "metric": "steady.8lane.speedup",
         "kind": "min", "value": 2.0}])
    assert report_mod.main(["--history", str(hist), "--baselines",
                            str(base), "--update-baselines"]) == 0
    obj = json.loads(base.read_text())
    entries = obj["namespaces"]["cpu"]["entries"]
    best = [e for e in entries if e["kind"] == "best"][0]
    assert best["value"] == 7.5                   # ratcheted to candidate
    assert [e for e in entries if e["kind"] == "min"][0]["value"] == 2.0


def test_green_path_on_committed_history():
    """The repo's own committed history + baselines pass the gate (the
    exact command CI runs), and the report renders with gate + driver
    trajectory sections."""
    assert COMMITTED_HISTORY.exists(), "committed history.jsonl missing"
    records, problems = load_history(COMMITTED_HISTORY)
    assert problems == [], problems
    assert records, "committed history is empty"
    baselines = report_mod.load_baselines(COMMITTED_BASELINES)
    checks = report_mod.check(records, baselines)
    bad = [c for c in checks if not c["ok"]]
    assert not bad, f"committed baselines violated: {bad}"
    report = report_mod.render_report(records, baselines, checks)
    assert "## Regression gate" in report
    assert "## Driver trajectory" in report
    assert "FAIL" not in report


# ---------------------------------------------------------------------------
# Histogram.quantile + merge (satellite property test)
# ---------------------------------------------------------------------------
def test_quantile_empty_and_bounds():
    h = Histogram()
    assert h.quantile(0.5) is None
    h.observe(0.003)
    assert h.quantile(0.0) == pytest.approx(0.003)
    assert h.quantile(1.0) == pytest.approx(0.003)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_merge_rejects_mismatched_geometry():
    a, b = Histogram(lo=1e-6), Histogram(lo=1e-3)
    a.observe(0.5)
    b.observe(0.5)
    with pytest.raises(ValueError, match="lo"):
        merge(a.snapshot(), b.snapshot())


def test_merge_then_quantile_equals_observe_all_then_quantile():
    """The satellite property: fixed bucket boundaries make merge exact,
    so quantiles over the merged snapshot equal quantiles over one
    histogram fed everything — and both sit within one (log) bucket of
    the same-rank empirical quantile."""
    rng = np.random.default_rng(7)
    for trial in range(12):
        n = int(rng.integers(2, 400))
        vals = np.exp(rng.normal(loc=-5.0, scale=2.5, size=n))
        split = int(rng.integers(0, n + 1))
        h_all, h_a, h_b = Histogram(), Histogram(), Histogram()
        for i, v in enumerate(vals):
            h_all.observe(v)
            (h_a if i < split else h_b).observe(v)
        merged = merge(h_a.snapshot(), h_b.snapshot())
        assert merged["count"] == h_all.count
        assert merged["buckets"] == h_all.snapshot()["buckets"]
        for q in (0.0, 0.1, 0.5, 0.9, 0.99, 1.0):
            qm = quantile_from_snapshot(merged, q)
            qa = h_all.quantile(q)
            assert qm == pytest.approx(qa, rel=1e-12), (trial, q)
            # one-bucket-width accuracy vs the same-rank order statistic
            rank = min(max(int(np.ceil(q * n)), 1), n)
            emp = float(np.sort(vals)[rank - 1])
            assert abs(h_all.bucket_of(qa) - h_all.bucket_of(emp)) <= 1, \
                (trial, q, qa, emp)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_recorder_dump_contents(tmp_path):
    tel = Telemetry(tracing=True)
    fl = FlightRecorder(tel, tmp_path / "pm", clock=lambda: 1700000000.0)
    tel.counter("work.done").inc(5)
    with tel.span("step.one"):
        pass
    err = PoisonedQueryError("d3adb33f")
    path = fl.dump("unit.site", error=err, state={"extra": 1})
    obj = json.loads(path.read_text())
    assert validate_postmortem(obj) == []
    assert obj["site"] == "unit.site"
    assert obj["error"]["type"] == "PoisonedQueryError"
    assert obj["error"]["digest"] == "d3adb33f"
    assert [e["name"] for e in obj["spans"]] == ["step.one"]
    assert obj["metrics_delta"]["work.done"] == 5
    assert obj["state"] == {"extra": 1}
    # the dump marks a new baseline: an immediate re-dump has no delta,
    # and the same-second filename collision gets a suffix
    path2 = fl.dump("unit.site")
    assert path2 != path
    assert json.loads(path2.read_text())["metrics_delta"] == {}


def test_broker_poison_produces_postmortem(tmp_path):
    """A persistently poisoned lane through a real (tiny) broker dumps a
    schema-valid postmortem carrying spans, a metrics delta and the
    quarantined digest; the innocent lane still resolves."""
    mc = tiny_machine()
    tel = Telemetry(tracing=True)
    q_bad = SimQuery(trace=random_trace(mc, seed=1),
                     policy=MIXED_POLICIES[0], machine=mc)
    q_ok = SimQuery(trace=random_trace(mc, seed=2, name="ok"),
                    policy=MIXED_POLICIES[0], machine=mc)
    probe = SimBroker(pad_steps_floor=1)
    digest = probe.query_digest(q_bad)
    injector = FaultInjector(
        [fail_lane("sweep.device", digest, transient=False)])
    flight = FlightRecorder(tel, tmp_path / "pm")
    broker = SimBroker(max_lanes=2, telemetry=tel, injector=injector,
                       flight=flight, pad_steps_floor=1, sleep=lambda s: None)
    f_bad, f_ok = broker.submit_many([q_bad, q_ok])
    broker.drain()
    with pytest.raises(PoisonedQueryError):
        f_bad.result()
    assert f_ok.result().summary()["faults"] >= 0
    assert len(flight.dumps) == 1
    obj = json.loads(flight.dumps[0].read_text())
    assert validate_postmortem(obj) == []
    assert obj["site"] == "broker.poison"
    assert obj["error"]["digest"] == digest
    assert len(obj["spans"]) >= 1
    assert obj["metrics_delta"]
    assert digest in obj["state"]["quarantine"]
    assert obj["state"]["stats"]["quarantined"] == 1


def test_flight_dump_failure_never_breaks_settlement(tmp_path):
    mc = tiny_machine()
    q = SimQuery(trace=random_trace(mc, seed=3),
                 policy=MIXED_POLICIES[0], machine=mc)
    probe = SimBroker(pad_steps_floor=1)
    injector = FaultInjector([fail_lane(
        "sweep.device", probe.query_digest(q), transient=False)])

    class Exploding:
        def dump(self, *a, **kw):
            raise OSError("disk full")

    tel = Telemetry()
    broker = SimBroker(max_lanes=2, telemetry=tel, injector=injector,
                       flight=Exploding(), pad_steps_floor=1,
                       sleep=lambda s: None)
    fut = broker.submit(q)
    broker.drain()
    with pytest.raises(PoisonedQueryError):
        fut.result()
    assert tel.metrics.value("broker.flight_errors") == 1


# ---------------------------------------------------------------------------
# run manifest
# ---------------------------------------------------------------------------
def test_run_manifest_records_drivers_and_failures(tmp_path, monkeypatch):
    import benchmarks.run as runmod
    from benchmarks import common

    seen = {}
    ok_mod = types.ModuleType("benchmarks.fake_ok")
    ok_mod.main = lambda quick=False: seen.setdefault("quick", quick)
    bad_mod = types.ModuleType("benchmarks.fake_bad")

    def _boom(quick=False):
        raise RuntimeError("boom")
    bad_mod.main = _boom
    monkeypatch.setitem(sys.modules, "benchmarks.fake_ok", ok_mod)
    monkeypatch.setitem(sys.modules, "benchmarks.fake_bad", bad_mod)
    monkeypatch.setattr(runmod, "FIGURES", {
        "ok": ("fake_ok", "fake passing driver"),
        "bad": ("fake_bad", "fake failing driver")})
    # keep the worker's jax config off the on-disk compile cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(common, "ART", tmp_path)
    monkeypatch.setattr(common, "HISTORY", tmp_path / "history.jsonl")
    monkeypatch.setitem(common._RUN_STATE, "run_id", None)
    monkeypatch.setattr(sys, "argv", ["run", "--quick"])
    with pytest.raises(SystemExit):
        runmod.main()
    manifest = json.loads((tmp_path / "run_manifest.json").read_text())
    assert manifest["schema"] == "run-manifest/v1"
    assert manifest["quick"] is True and seen["quick"] is True
    assert isinstance(manifest["run_id"], int)
    assert manifest["drivers"]["ok"]["status"] == "ok"
    assert manifest["drivers"]["ok"]["seconds"] >= 0
    assert manifest["drivers"]["bad"]["status"] == "failed"
    assert "boom" in manifest["drivers"]["bad"]["error"]
    assert manifest["failures"] == ["bad"]


# ---------------------------------------------------------------------------
# validator extensions (satellite: overlap + monotonicity rejects)
# ---------------------------------------------------------------------------
def _span(name, ts, dur, tid=0, pid=0):
    return {"name": name, "cat": "t", "ph": "X", "ts": ts, "dur": dur,
            "pid": pid, "tid": tid}


def test_validator_rejects_partial_overlap_same_track():
    obj = {"traceEvents": [_span("a", 0, 10), _span("b", 5, 10)]}
    problems = validate_trace_events(obj)
    assert any("partially overlaps" in p for p in problems), problems


def test_validator_allows_nesting_and_cross_track_overlap():
    obj = {"traceEvents": [
        _span("outer", 0, 100),
        _span("inner", 10, 20),
        _span("inner2", 30, 70),
        _span("tail-aligned", 60, 40),      # exact containment to the edge
        _span("other-track", 5, 200, tid=1),
        _span("next", 101, 10),
    ]}
    assert validate_trace_events(obj) == []


def test_validator_rejects_non_monotonic_be_track():
    obj = {"traceEvents": [
        _span("x", 0, 1),
        {"name": "a", "cat": "t", "ph": "B", "ts": 10, "pid": 0, "tid": 0},
        {"ph": "E", "ts": 5, "pid": 0, "tid": 0},
    ]}
    problems = validate_trace_events(obj)
    assert any("non-monotonic" in p for p in problems), problems


def test_validate_cli_is_schema_aware(tmp_path, capsys):
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({"traceEvents": [_span("a", 0, 1)]}))
    hist = tmp_path / "history.jsonl"
    append_record(make_record(driver="d", run_id=0), hist)
    tel = Telemetry(tracing=True)
    with tel.span("s"):
        pass
    pm = FlightRecorder(tel, tmp_path).dump("cli.site")
    assert validate_cli.main([str(trace), str(hist), str(pm)]) == 0
    out = capsys.readouterr().out
    assert "1 bench records" in out and "postmortem at cli.site" in out
    # a bad history line flips the exit code and names the line
    with open(hist, "a") as fh:
        fh.write(json.dumps({"schema": "bench-record/v1"}) + "\n")
    assert validate_cli.main([str(hist)]) == 1
    assert "line 2" in capsys.readouterr().err
    assert validate_cli.main([str(tmp_path / "nope.json")]) == 1
