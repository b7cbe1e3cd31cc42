"""Whole runs of a tiny cell on the CPU, past the harness's look for a
chip: a sound run is correct; a compile inside the window, and each fault
the served path can have, make it incorrect; the bfloat16 control fails
the comparison; without a chip the command prints nothing and fails."""
import dataclasses
import json
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from bench import compare, generator, run
from bench.reference import oracle

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout holding one tiny cell: 4 threads, 1,200 DRAM pages
    against a 2,048-page footprint, two 8-lane flushes of 1,024 steps."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [dict(spec["configs"][0], name="tiny",
                            file="bench/configs/tiny.json")]
    spec["workloads"] = [dict(spec["workloads"][0], name="tiny.grid",
                              config="tiny", traffic="grid")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    shutil.copytree(REPO / "bench" / "metrics", tmp_path / "bench" / "metrics")
    cfg = json.loads((REPO / "bench/configs/optane-2tier.json").read_text())
    cfg.update(n_threads=4, dram_pages_per_node=600, nvmm_pages_per_node=2400,
               va_pages=1 << 12, l1_tlb_sets=4, l1_tlb_ways=2, stlb_sets=8,
               stlb_ways=4, pde_pwc_entries=4, pdpte_pwc_entries=2,
               radix_bits=9, footprint=1 << 11, max_lanes=8,
               autonuma_period=32)
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    traffic = json.loads((REPO / "bench/traffic/fig9-grid.json").read_text())
    traffic.update(workloads=["memcached", "btree", "xsbench"], run_steps=128)
    (tmp_path / "bench/traffic/grid.json").write_text(json.dumps(traffic))
    # the test process keeps JAX's global compile-cache settings
    monkeypatch.setattr(run, "use_compile_cache", lambda root: None)
    return tmp_path


def run_tiny(root, seed=2 ** 31 + 11, trace=False):
    result, checks, _ = run.run_cell(root, "tiny.grid", seed, 0.0, trace,
                                     time.perf_counter(), require_tpu=False)
    return result, {n: (v, ok) for n, v, _, ok in checks}


def test_sound_run_is_correct_and_reports_every_metric(tiny_root):
    result, checks = run_tiny(tiny_root)
    assert result["correct"], checks
    assert list(result)[-1] == "checks"
    assert result["attempted"] == 12 and result["failed"] == 0
    assert checks["checked_queries"][0] == 4
    assert set(result["metrics"]) == {"sim_accesses_per_s", "query_p90_s",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_traced_run_reports_the_per_layer_metrics(tiny_root):
    result, _ = run_tiny(tiny_root, seed=5, trace=True)
    assert result["correct"]
    # the CPU has no device plane: the idle share has nothing to read
    assert set(result["metrics"]) == {"trace_build_share", "prepare_share",
                                      "broker_overhead_share",
                                      "device_ms_per_step"}


def test_compile_in_window_fails_the_run(tiny_root, monkeypatch):
    real = generator.grid

    def longer_after_warmup(cfg, traffic, seed, index):
        if index:
            traffic = dict(traffic, run_steps=traffic["run_steps"] + 512)
        return real(cfg, traffic, seed, index)

    monkeypatch.setattr(generator, "grid", longer_after_warmup)
    result, checks = run_tiny(tiny_root)
    assert checks["window_compiles"][0] > 0
    assert not checks["window_compiles"][1]
    assert not result["correct"]


def _state_unchanged(results, mc):
    from repro.core.state import init_state
    import jax
    st0 = jax.device_get(init_state(mc))
    return [dataclasses.replace(r, final_state=st0) for r in results]


def _half_left_out(results, mc):
    half = (len(results) + 1) // 2
    return results[:half] + [results[i - half]
                             for i in range(half, len(results))]


def _answer_altered(results, mc):
    out = []
    for r in results:
        data = np.array(r.final_state.data_node)
        data[0] = (data[0] + 1) % 4
        out.append(dataclasses.replace(
            r, final_state=dataclasses.replace(r.final_state,
                                               data_node=data)))
    return out


@pytest.mark.parametrize("fault", [_state_unchanged, _half_left_out,
                                   _answer_altered])
def test_fault_in_the_served_path_is_incorrect(tiny_root, monkeypatch,
                                               fault):
    import repro.service.broker as broker_mod
    real = broker_mod.sweep_lanes

    def faulty(mc, *args, **kw):
        return fault(real(mc, *args, **kw), mc)

    monkeypatch.setattr(broker_mod, "sweep_lanes", faulty)
    result, checks = run_tiny(tiny_root)
    assert not checks["mismatched_leaves"][1]
    assert not result["correct"]


def test_bfloat16_control_fails_the_comparison():
    """The reference computed one precision below the configuration's
    float32 cycles, put in the program's place, fails ``cycles_gap``."""
    import ml_dtypes
    cfg = json.loads((REPO / "bench/configs/optane-2tier.json").read_text())
    cfg.update(n_threads=4, dram_pages_per_node=600, nvmm_pages_per_node=2400,
               va_pages=1 << 12, radix_bits=9, footprint=1 << 11)
    traffic = json.loads((REPO / "bench/traffic/fig9-grid.json").read_text())
    traffic.update(workloads=["memcached"], run_steps=128)
    for q in generator.grid(cfg, traffic, 7, 1):
        ref = generator.reference_run(q, cfg)
        ctl = generator.reference_run(q, cfg, cycle_dtype=ml_dtypes.bfloat16)
        mism, gap = compare.compare(compare.reference_leaves(ctl),
                                    compare.reference_leaves(ref))
        assert mism == []
        assert gap > 10 * compare.LIMITS["cycles_gap"]


def test_reference_policies_match_the_programs_presets():
    import repro.core as rc
    for name in oracle.PRESETS:
        mine = dataclasses.asdict(oracle.policy(name))
        theirs = dataclasses.asdict(getattr(rc, name)())
        assert mine == {k: theirs[k] for k in mine}, name


def test_without_a_chip_the_command_prints_nothing(capsys):
    # the test suite runs JAX on the CPU
    assert run.main(["--workload", "optane-2tier.fig9-grid", "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""
