"""Every cell of BENCHMARK.json resolves its files by name, the file keeps
to the benchmark's contract, and a new cell, configuration, traffic mix
and per-layer metric can be added as new files and entries alone."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, lm  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = harness.Cell(name)
    drv = cell.driver()
    for attr in ("main", "setup", "reference", "control", "compare",
                 "FAULTS"):
        assert hasattr(drv, attr), attr
    for reader in cell.readers().values():
        assert callable(reader.read)
    model = lm.Model(cell.config)         # layout == the program's tree
    assert model.n_params > 0
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    assert all(m["moves"] in reported for m in cell.per_layer)
    assert cell.limits and all(v > 0 for v in cell.limits.values())


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(BENCH["command"]) <= 32
    assert all((ROOT / p).is_dir() for p in BENCH["paths"])
    configs = {c["name"]: c for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(configs)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert c["reduced"] == json.loads(
            (ROOT / c["file"]).read_text())["reduced"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(pairs) // 2)
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + list(configs)
    assert all(NAME.match(n) for n in names)
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    # a full check with 24 cells fits its time
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_new_cell_config_mix_and_metric_are_files_alone(tmp_path):
    """Copy the benchmark, add a configuration, a traffic mix, a metric and
    a cell as new files plus entries, and resolve the new cell."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = tmp_path / "chipbench"
    conf = json.loads((b / "configs" / "xlstm-125m.json").read_text())
    conf.update(name="xlstm-125m-d6", reduced=["n_layers"])
    conf["model"]["n_layers"] = 6
    (b / "configs" / "xlstm-125m-d6.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "sync-secure8.json").read_text())
    mix["batch"] = 4
    (b / "traffic" / "sync-secure8-b4.json").write_text(json.dumps(mix))
    (b / "metrics" / "rounds_run.py").write_text(
        "def read(x):\n    return x.get('rounds')\n")
    name = "xlstm-125m-d6.sync-secure8-b4"
    (b / "cells" / f"{name}.json").write_text(json.dumps({"limits": {
        "loss_gap": 1.0, "delta1_gap": 1.0, "change_gap": 1.0}}))
    bench["configs"].append({"name": "xlstm-125m-d6", "source": "x",
                             "file": "chipbench/configs/xlstm-125m-d6.json",
                             "reduced": ["n_layers"], "why": "x"})
    bench["workloads"].append({"name": name, "config": "xlstm-125m-d6",
                               "traffic": "sync-secure8-b4", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tokens_per_s" in (m["name"], m.get("moves")):
            m["workloads"].append(name)
    bench["per_layer"].append({"name": "rounds_run", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "tokens_per_s",
                               "workloads": [name]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell(name, root=tmp_path)
    assert cell.config["model"]["n_layers"] == 6
    assert cell.traffic["batch"] == 4
    assert cell.readers()["rounds_run"].read({"rounds": 3}) == 3
    assert {m["name"] for m in cell.per_layer} >= {"rounds_run",
                                                   "train_mfu"}
    assert lm.Model(cell.config).n_params > 0
    old = harness.Cell(CELLS[0], root=tmp_path)      # the old cells still
    assert old.config == harness.Cell(CELLS[0]).config   # resolve as before


def test_no_tpu_exits_before_measuring():
    """On a machine without a TPU the command fails and prints no result;
    it never falls back to the CPU."""
    import os
    import subprocess
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr
