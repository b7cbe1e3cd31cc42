"""Shared fixtures.

Tests that exercise benchmark drivers directly (e.g. the
``service_throughput`` quick smoke) must not append BenchRecords to the
*committed* ``artifacts/bench/history.jsonl`` — that log is the
regression gate's input and only real ``benchmarks.run`` invocations
belong in it — nor rewrite the committed artifacts beside it.  Redirect
the artifact directory and the history sink to per-test temp paths for
every test; tests that want the real committed history (the green-path
gate test) read it by explicit path.

The program keeps every compiled runner for the life of the process
(``sim._RUN_CACHE``, ``sweep._SWEEP_CACHE`` and JAX's own caches), and
on the CPU each compiled kernel holds its own memory mappings.  A test
worker that runs several files would pile up the mappings of every
file's programs and reach the kernel's per-process mapping limit, which
kills the worker inside a compile; so the compiled programs are released
after each test file.
"""
import gc
import importlib

import jax
import pytest


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    yield
    importlib.import_module("repro.core.sim")._RUN_CACHE.clear()
    importlib.import_module("repro.core.sweep")._SWEEP_CACHE.clear()
    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _isolated_bench_history(tmp_path, monkeypatch):
    from benchmarks import common
    monkeypatch.setattr(common, "ART", tmp_path / "bench")
    monkeypatch.setattr(common, "HISTORY", tmp_path / "history.jsonl")
    monkeypatch.setitem(common._RUN_STATE, "run_id", None)
