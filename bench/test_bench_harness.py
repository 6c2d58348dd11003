"""The harness finds every cell's parts by name, keeps to its contract, and
runs a toy cell end to end on the CPU (the chip check is skipped)."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

from bench import registry, testing, work

REPO = testing.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CHECKS = {"lm_serve": "mean_logit_gap", "cholesky": "factor_rel_error"}


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"] and BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", [cell]), (m["name"], cell)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    # every end-to-end metric that a roofline moves has an mfu beside it
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in x["name"] and x["moves"] == m["moves"]
                       for x in BENCH["per_layer"]), m["name"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] == 1 for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = registry.resolve(REPO, cell)
    assert callable(registry.driver(c).run)
    assert "setup_s" in [m["name"] for m in c.end_to_end]
    assert len(c.end_to_end) >= 2 and c.per_layer
    limits = testing.limits(REPO, cell)
    assert CHECKS[c.kind] in limits
    assert all(float(lim["limit"]) > 0 for lim in limits.values())
    for m in c.per_layer:
        assert callable(registry.metric_reader(REPO, m["name"]))


def test_a_cell_added_as_files_only_is_found(tmp_path):
    root = testing.toy_root(tmp_path)
    for cell, (config, traffic, e2e, layer) in testing.TOY_CELLS.items():
        c = registry.resolve(root, cell)
        assert (c.config_name, c.traffic_name) == (config, traffic)
        assert {m["name"] for m in c.end_to_end} == set(e2e) | {"setup_s"}
        assert {m["name"] for m in c.per_layer} == set(layer)
        for m in c.per_layer:
            assert callable(registry.metric_reader(root, m["name"]))
    with pytest.raises(registry.CellError):
        registry.resolve(root, "toy.missing")


def _run(args, cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu_and_prints_no_result():
    p = _run(["--workload", "qwen3-14b.conv-batch", "--seed", "1", "--seconds", "1",
              "--trace", "0"], REPO, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert testing.last_json(p.stdout) is None
    assert "no TPU" in p.stderr


def test_run_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text((REPO / "BENCHMARK.json").read_text())
    testing.shutil.copytree(REPO / "bench", tmp_path / "bench",
                            ignore=testing.shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "qwen3-14b.conv-batch", "--seed", "1", "--seconds", "1",
              "--trace", "0"], tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert testing.last_json(p.stdout) is None


@pytest.fixture
def cpu_peak(monkeypatch):
    """Readers need a peak table entry; the CPU has none, and a test's
    numbers are never reported."""
    monkeypatch.setitem(work.PEAKS, "cpu", dict(work.PEAKS["TPU v5 lite"]))


@pytest.mark.parametrize("cell", ["toy.batch", "toy.poisson", "toy.chol"])
def test_toy_cell_runs_end_to_end(tmp_path, capsys, cpu_peak, cell):
    root = testing.toy_root(tmp_path)
    c = registry.resolve(root, cell)
    line = testing.toy_run(root, cell, 2**31 + 11, 0, capsys)
    assert line["correct"] is True, line
    assert set(line["metrics"]) == {m["name"] for m in c.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    traced = testing.toy_run(root, cell, 7, 1, capsys)
    assert traced["correct"] is True
    assert set(traced["metrics"]) <= {m["name"] for m in c.per_layer}
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert "breakdown" in traced
