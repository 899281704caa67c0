"""BENCHMARK.json against the benchmark's contract, and every file it names."""

import re

import benchutil
import pytest

from bench import core

SPEC = core.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in SPEC["end_to_end"]}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (benchutil.ROOT / p).is_dir() and ".." not in p


def test_names_units_and_keys():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    for kind in ("end_to_end", "per_layer"):
        assert len({m["name"] for m in SPEC[kind]}) == len(SPEC[kind])
        for m in SPEC[kind]:
            assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (benchutil.ROOT / c["file"]).is_file()
        assert core.load_json(c["file"])["name"] == c["name"]
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in E2E


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_each_cell_has_its_files_and_metrics(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    assert any(c["name"] == cell["config"] for c in SPEC["configs"])
    mix = core.traffic_file(cell["traffic"])
    assert (core.BENCH / "drivers" / f"{mix['driver']}.py").is_file()
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in SPEC["per_layer"] if cell["name"] in m.get("workloads", [])]
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        assert (core.BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_each_config_file_states_its_source_and_cuts(config):
    cfg = core.load_json(config["file"])
    assert cfg["source"] == config["source"]
    assert cfg["reduced"] == config["reduced"]


def test_peaks_table_is_keyed_by_device_kind():
    assert core.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        core.peaks("cpu")
