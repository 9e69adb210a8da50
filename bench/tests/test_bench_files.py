"""BENCHMARK.json and the files it names: allowed characters, metrics
tied to the cells that report them, every driver and reader present;
and a new cell or per-layer metric added by files alone."""

from __future__ import annotations

import json
import re
import shutil

import pytest

from _bench_tiny import BENCH, ROOT, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_and_units(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e and section != "end_to_end" or key == "why" and key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_configs(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    f = ROOT / cfg["file"]
    assert f.is_file() and f.relative_to(BENCH)
    body = json.loads(f.read_text())
    assert body["name"] == cfg["name"] and body["reduced"] == cfg["reduced"]
    assert len(cfg["reduced"]) <= 16
    for k in cfg["reduced"]:
        assert NAME.match(k) and k in body, k
        assert not k.endswith(("_dim", "_rank", "_size")), f"{k} is a width"
    assert (BENCH / "reference" / f"{body['reference']}.py").is_file()


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_workloads(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    wl = json.loads((BENCH / "workloads" / f"{cell['name']}.json").read_text())
    assert (BENCH / "drivers" / f"{wl['driver']}.py").is_file()
    assert int(__import__("math").prod(wl.get("mesh", {}).get("shape", [1]))) <= cell["chips"]
    cfg = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert mix.get("max_bytes", 0) <= cfg.get("max_payload_bytes", float("inf"))
    loaded = harness().load_cell(cell["name"])
    reported = [m["name"] for m in loaded.end_to_end]
    assert "setup_s" in reported and len(reported) >= 2
    assert loaded.per_layer, "every cell reports a per-layer metric"


def test_four_chip_cells_at_most_half():
    four = sum(c["chips"] == 4 for c in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_end_to_end_bounds():
    names = {m["name"] for m in SPEC["end_to_end"]}
    assert names == {"allreduce_us", "train_tokens_per_s", "serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    moved = e2e[metric["moves"]]
    for cell in metric["workloads"]:
        assert cell in moved.get("workloads", [cell]), f"{cell} does not report {metric['moves']}"
    assert (BENCH / "metrics" / f"{metric['name']}.py").is_file()
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_layer_names_are_consistent():
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_a_cell_and_a_metric_come_from_files_alone(tmp_path):
    """A new cell of an existing driver is one workload file; a new
    per-layer metric is one reader file (plus their entries)."""
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "allreduce-2x2.tiny", "config": "allreduce-2x2",
                              "traffic": "allreduce-small", "chips": 4, "why": "a test cell"})
    spec["end_to_end"][0]["workloads"].append("allreduce-2x2.tiny")
    spec["per_layer"].append({"name": "calls_seen", "unit": "calls", "better": "higher",
                              "source": "host_clock", "layer": "collectives", "moves": "allreduce_us",
                              "workloads": ["allreduce-2x2.tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench" / "workloads" / "allreduce-2x2.tiny.json").write_text(json.dumps(
        {"driver": "allreduce", "mesh": {"shape": [2, 2], "axes": ["pod", "data"]}, "why": "test"}))
    (tmp_path / "bench" / "metrics" / "calls_seen.py").write_text(
        "def read(ctx):\n    return ctx.traced.get('calls')\n")
    run = harness()
    cell = run.load_cell("allreduce-2x2.tiny", bench=tmp_path / "bench")
    assert cell.workload["driver"] == "allreduce"
    assert [m["name"] for m in cell.per_layer] == ["calls_seen"]
    assert {m["name"] for m in cell.end_to_end} == {"allreduce_us", "setup_s"}
    reader = cell.module("metrics", "calls_seen")
    ctx = run.MetricContext(cell=cell, trace=None, traced={"calls": 9}, measured={},
                            trace_mod=None, bench=cell.bench)
    assert reader.read(ctx) == 9
    assert cell.module("drivers", "allreduce").setup


@pytest.mark.parametrize("path", sorted((BENCH / "workloads").glob("*.json")), ids=lambda p: p.name)
def test_every_workload_file_is_a_cell(path):
    wl = json.loads(path.read_text())
    assert path.stem in {c["name"] for c in SPEC["workloads"]}
    assert (BENCH / "drivers" / f"{wl['driver']}.py").is_file()
    assert wl["limits"] and all(v > 0 for v in wl["limits"].values())


@pytest.mark.parametrize("path", sorted((BENCH / "configs").glob("*.json")), ids=lambda p: p.name)
def test_every_config_file_is_a_configuration(path):
    cfg = json.loads(path.read_text())
    assert NAME.match(cfg["name"])
    assert cfg["name"] in {c["name"] for c in SPEC["configs"]}
    assert "source" in cfg and "reference" in cfg
