"""The result line's schema, and runs that must refuse to start."""

import json
import os
import shutil
import subprocess
import sys

import benchutil
import pytest

from bench import core

RUN = ["bench/run.py", "--workload", "rnn124m-longgen", "--seed", "5", "--seconds", "1",
       "--trace", "0"]


def test_refuses_to_run_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable] + RUN, cwd=benchutil.ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(benchutil.ROOT / "BENCHMARK.json", tmp_path)
    for rel in core.load_spec()["paths"]:
        shutil.copytree(benchutil.ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable] + RUN, cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_schema(trace, capsys):
    line, checks = benchutil.run.run_cell("rnn124m-longgen", 2 ** 32 + 9, 2.0, trace,
                                          require_chip=False, overrides=benchutil.SERVE_SMOKE)
    core.emit(line, checks)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    assert set(last["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    spec = core.load_spec()
    if trace:
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
        allowed = {m["name"]: m["unit"] for m in spec["per_layer"]}
    else:
        allowed = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        assert set(last["metrics"]) == {"setup_s", "ttft_p90_ms", "tpot_p90_ms"}
    for name, m in last["metrics"].items():
        assert m["unit"] == allowed[name] and m["value"] > 0
    for name, c in last["checks"].items():
        assert set(c) == {"value", "limit"}
    tail = err.strip().splitlines()[-len(last["checks"]):]
    assert all(t.startswith("check ") for t in tail)


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_memory_peak_counts_what_the_chip_reserved_for_programs():
    devs = [_Device({"peak_bytes_in_use": 100, "peak_bytes_reserved": 800}),
            _Device({"peak_bytes_in_use": 500}), _Device(None)]
    assert core.memory_peak_bytes(devs) == 900


def test_token_gaps_read_the_longest_wait():
    from bench.drivers.serve_stream import token_gaps

    recs = [{"events": [[0.0, 1], [0.5, 8], [1.0, 8], [3.0, 8]]},
            {"events": [[0.2, 1], [0.7, 8]]}, {"events": []}]
    assert token_gaps(recs) == {"token_gap_max_ms": 2000.0, "token_gap_at_s": 3.0}
    assert token_gaps([{"events": [[0.0, 1]]}]) == {}
