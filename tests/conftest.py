"""Shared fixtures.

Tests that exercise benchmark drivers directly (e.g. the
``service_throughput`` quick smoke) must not append BenchRecords to the
*committed* ``artifacts/bench/history.jsonl`` — that log is the
regression gate's input and only real ``benchmarks.run`` invocations
belong in it — nor rewrite the committed artifacts beside it.  Redirect
the artifact directory and the history sink to per-test temp paths for
every test; tests that want the real committed history (the green-path
gate test) read it by explicit path.
"""
import pytest


@pytest.fixture(autouse=True)
def _isolated_bench_history(tmp_path, monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(common, "ART", tmp_path / "bench")
    monkeypatch.setattr(common, "HISTORY", tmp_path / "history.jsonl")
    monkeypatch.setitem(common._RUN_STATE, "run_id", None)
