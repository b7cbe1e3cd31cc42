"""Everything a run needs is found by name from BENCHMARK.json, and a new
cell, machine, mix or metric is new files and entries only."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import run

REPO = Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_every_cell_resolves(cell):
    spec, c, cfg, traffic = run.find_cell(REPO, cell)
    assert cfg["name"] == c["config"]
    assert traffic["workloads"] and traffic["policies"]
    readers = run.metric_readers(REPO, spec)
    assert set(readers) == {m["name"] for m in spec["per_layer"]}
    assert all(callable(read) for read, _ in readers.values())


def test_spec_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["moves"] in e2e for m in SPEC["per_layer"])
    for c in SPEC["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


def test_a_new_cell_is_new_files_only(tmp_path):
    """A copy of the checkout gains a machine, a mix, a metric and a cell
    by adding files and entries; no existing file changes."""
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / spec["configs"][0]["file"]).read_text())
    cfg.update(name="thp-box", page_order=6)
    (tmp_path / "bench/configs/thp-box.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/kv-only.json").write_text(json.dumps(
        {"workloads": ["memcached"], "policies": [{"preset": "bhi"}],
         "run_steps": 256}))
    (tmp_path / "bench/metrics/window_seconds.py").write_text(
        "def read(ctx):\n    return ctx['window_s']\n")
    spec["configs"].append(dict(spec["configs"][0], name="thp-box",
                                file="bench/configs/thp-box.json"))
    spec["workloads"].append({"name": "thp-box.kv-only", "config": "thp-box",
                              "traffic": "kv-only", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "window_seconds", "unit": "s",
                              "better": "lower", "source": "host_clock",
                              "layer": "device", "moves": "setup_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    spec2, cell, cfg2, traffic = run.find_cell(tmp_path, "thp-box.kv-only")
    assert cfg2["page_order"] == 6 and traffic["run_steps"] == 256
    assert cell["config"] == "thp-box"
    readers = run.metric_readers(tmp_path, spec2)
    assert readers["window_seconds"][0]({"window_s": 3.0}) == 3.0
    assert all(p.read_bytes() == b for p, b in before.items())


def test_unknown_cell_is_refused():
    with pytest.raises(run.BenchError):
        run.find_cell(REPO, "no-such.cell")
