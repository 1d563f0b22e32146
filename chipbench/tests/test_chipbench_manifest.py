"""BENCHMARK.json against the benchmark's contract, and every file it names."""
from __future__ import annotations

import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = MANIFEST["workloads"]


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["paths"] == ["chipbench"]
    for word in MANIFEST["command"]:
        assert not word.startswith("/") and ".." not in word


@pytest.mark.parametrize("entry", MANIFEST["configs"] + CELLS + METRICS,
                         ids=lambda e: e["name"])
def test_names_and_units(entry):
    assert NAME.match(entry["name"]), entry["name"]
    if "unit" in entry:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key]), entry[key]
    for key in entry.get("reduced", []):
        assert NAME.match(key), key
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]


def test_names_are_unique():
    for group in (MANIFEST["configs"], CELLS, METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(c["config"], c["traffic"]) for c in CELLS]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_bounds():
    names = [m["name"] for m in MANIFEST["end_to_end"]]
    assert "setup_s" in names
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_files_resolve(cell):
    from chipbench import harness

    c = harness.load_cell(cell["name"], MANIFEST)
    assert (BENCH / "drivers" / f"{c.traffic['driver']}.py").exists()
    for key in ("setup", "window", "check", "control"):
        assert callable(getattr(harness.driver_module(c), key))
    assert c.limits, "every cell states the limits of its compared numbers"
    assert (BENCH / "reference" / f"{c.config['reference']}.py").exists()
    for m in c.per_layer:
        assert callable(harness.metric_module(m["name"]).read)
    assert cell["chips"] in (1, 4)


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_reports_what_its_metrics_move(cell):
    from chipbench import harness

    c = harness.load_cell(cell["name"], MANIFEST)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_configs_are_used_and_files_are_their_own():
    used = {c["config"] for c in CELLS}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert used == {c["name"] for c in MANIFEST["configs"]}
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert c["file"].startswith("chipbench/")
        data = json.loads((ROOT / c["file"]).read_text())
        assert sorted(data["reduced"]) == sorted(c["reduced"])


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    cell = CELLS[0]["name"]
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
