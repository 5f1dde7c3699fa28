"""BENCHMARK.json against the benchmark's contract, name resolution, and
the refusal to run without the accelerator."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run as bench_run

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


@pytest.fixture(scope="module")
def spec():
    return bench_run.load_spec(ROOT)


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    assert len(spec["command"]) <= 32 and all(map(_line, spec["command"]))
    assert 1 <= spec["run_seconds"] <= 51
    assert isinstance(spec["run_seconds"], int)
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    # a full check of 24 cells fits its 43200 s
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_names_units_and_entries(spec):
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"])
        assert _line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))
        names.add(c["name"])
    assert len(names) == len(spec["configs"])
    cells = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _line(w["why"])
        cells.add(w["name"])
    assert len(cells) == len(spec["workloads"])
    assert len({(w["config"], w["traffic"])
                for w in spec["workloads"]}) == len(cells)
    e2e = {}
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == E2E_KEYS
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert "setup_s" in e2e
    for cell in cells:
        assert any(cell in ws for n, ws in e2e.items() if n != "setup_s")
    layers = set()
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == LAYER_KEYS
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= e2e[m["moves"]]
        assert m["name"] not in e2e
        layers.add(m["name"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert len(layers) == len(spec["per_layer"])
    for cell in cells:
        assert any(cell in m["workloads"] for m in spec["per_layer"])


def test_every_name_resolves_to_a_file(spec):
    for w in spec["workloads"]:
        r = bench_run.resolve(spec, w["name"], ROOT)
        assert r["config"]["name"] == w["config"]
        assert r["traffic"]["sessions"] >= 1
        for m in r["per_layer"]:
            assert callable(bench_run.metric_reader(m["name"], ROOT))
    produced = {"update_ops_s", "update_p95_ms", "read_p95_ms",
                "peak_hbm_mb", "setup_s"}
    assert {m["name"] for m in spec["end_to_end"]} <= produced


def test_new_files_alone_are_found(tmp_path, spec):
    """A later cell adds a configuration, a mix and a metric as files,
    and entries in BENCHMARK.json; no existing file changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    cfg = json.loads((ROOT / "bench/configs/web_google.json").read_text())
    cfg["name"] = "dummy_graph"
    (tmp_path / "bench/configs/dummy_graph.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/dummy_mix.json").write_text(json.dumps(
        {"sessions": 3, "pool": 4, "requests": []}))
    (tmp_path / "bench/metrics/dummy_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    new = json.loads(json.dumps(spec))
    new["configs"].append({"name": "dummy_graph", "source": "x",
                           "file": "bench/configs/dummy_graph.json",
                           "reduced": [], "why": "x"})
    new["workloads"].append({"name": "dummy_graph.dummy_mix",
                             "config": "dummy_graph",
                             "traffic": "dummy_mix", "chips": 1,
                             "why": "x"})
    new["per_layer"].append({"name": "dummy_metric", "unit": "%",
                             "better": "higher", "source": "device_trace",
                             "layer": "device", "moves": "update_ops_s",
                             "workloads": ["dummy_graph.dummy_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    r = bench_run.resolve(bench_run.load_spec(tmp_path),
                          "dummy_graph.dummy_mix", tmp_path)
    assert r["config"]["name"] == "dummy_graph"
    assert r["traffic"]["sessions"] == 3
    assert [m["name"] for m in r["per_layer"]] == ["dummy_metric"]
    assert bench_run.metric_reader("dummy_metric", tmp_path)(None) == 42.0
    # the new cell takes every end-to-end metric that lists no cells
    assert "setup_s" in [m["name"] for m in r["end_to_end"]]


def _run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "web_google.churn", "--seed", str(2 ** 40 + 1), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_accelerator_no_result():
    out = _run_bench(ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_bare_checkout_no_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run_bench(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout == ""
