"""BENCHMARK.json and the files it names: everything a cell needs is found
by name, and the harness itself names no cell, configuration, traffic mix
or metric."""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import run

SPEC = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _metrics():
    return SPEC["end_to_end"] + SPEC["per_layer"]


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_found_by_name(cell):
    w = run.named(SPEC["workloads"], cell, "workload")
    cfg = run.named(SPEC["configs"], w["config"], "config")
    config = run.load_json(os.path.join(run.ROOT, cfg["file"]))
    assert config["name"] == cfg["name"]
    assert config["chips"] == w["chips"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    assert set(config["check"]) == {"residual", "unconverged"}
    traffic = run.load_json(
        os.path.join(run.BENCH, "traffic", f"{w['traffic']}.json"))
    assert traffic["rhs_count"] >= 1
    loop = importlib.import_module(f"benchmark.loops.{traffic['loop']}")
    assert callable(loop.drive)
    rhs = importlib.import_module(f"benchmark.rhs.{traffic['rhs']}")
    assert callable(rhs.field)
    op = importlib.import_module(f"benchmark.operators.{config['operator']}")
    assert callable(op.build)
    entry = importlib.import_module(f"benchmark.entries.{config['entry']}")
    assert hasattr(entry, "Program")
    for traced in (False, True):
        for m in run.cell_metrics(SPEC, cell, traced):
            reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
            assert callable(reader.read)
    e2e = {m["name"] for m in run.cell_metrics(SPEC, cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.cell_metrics(SPEC, cell, True)


def test_contract_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in _metrics()])
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in SPEC[group]}) == len(SPEC[group])
    assert len({m["name"] for m in _metrics()}) == len(_metrics())
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) \
        == len(SPEC["workloads"])
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/") and len(c["why"]) <= 200
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in _metrics():
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert set(m.get("workloads", [])) <= {w["name"]
                                                for w in SPEC["workloads"]}
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("harness", ["run.py", "generate.py", "trace.py"])
def test_harness_names_no_cell_config_traffic_or_metric(harness):
    with open(os.path.join(run.BENCH, harness)) as f:
        text = f.read()
    names = ({w["name"] for w in SPEC["workloads"]}
             | {w["traffic"] for w in SPEC["workloads"]}
             | {c["name"] for c in SPEC["configs"]}
             | {m["name"] for m in _metrics()})
    for name in names:
        assert not re.search(r"""["'/.]""" + re.escape(name) + r"""["'.]""",
                             text), name
        if "." in name or "_" in name:
            assert name not in text, name


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = SPEC["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr
