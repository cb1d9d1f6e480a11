"""BENCHMARK.json against the contract the harness is written to, and
the harness's refusal to run without a TPU."""
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


def test_configs_and_workloads(bench):
    cfgs = {c["name"]: c for c in bench["configs"]}
    files = [c["file"] for c in cfgs.values()]
    assert len(files) == len(set(files))
    for c in cfgs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and \
            os.path.exists(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert not re.search(r"(_dim|_rank|size|width|expand|head)",
                                 k) or k == "vocab_size", k
    pairs = set()
    used = set()
    fours = 0
    for w in bench["workloads"]:
        assert w["config"] in cfgs
        used.add(w["config"])
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(ROOT, "bench", "limits",
                                           w["name"] + ".json"))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
        fours += w["chips"] == 4
    assert used == set(cfgs)
    assert fours <= max(1, len(bench["workloads"]) // 2)


def _reports(bench, cell, metric):
    for e in bench["end_to_end"]:
        if e["name"] == metric:
            return cell in e.get("workloads", [cell])
    return False


def test_metrics(bench):
    e2e = {e["name"]: e for e in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for e in e2e.values():
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bench["workloads"]}
    layers = {}
    for p in bench["per_layer"]:
        assert p["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert p["moves"] in e2e
        for cell in p.get("workloads", cells):
            assert cell in cells and _reports(bench, cell, p["moves"])
        mod = importlib.import_module(f"bench.metrics.{p['name']}")
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == \
            (p["layer"], p["unit"], p["source"], p["moves"])
        assert 1 <= len(p["layer"]) <= 200
        layers.setdefault(p["layer"], set()).add(p["name"])
    for cell in cells:
        assert _reports(bench, cell, "setup_s")
        assert any(_reports(bench, cell, m) for m in e2e if m != "setup_s")
        assert any(cell in p.get("workloads", cells)
                   for p in bench["per_layer"])


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "deepseek-7b-stage.recomp-1chip", "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    r = _run(ROOT, {})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path), {})
    assert r.returncode != 0 and r.stdout.strip() == ""
