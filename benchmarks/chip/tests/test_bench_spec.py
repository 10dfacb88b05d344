"""Everything BENCHMARK.json names resolves by name, within the contract's
characters; the command refuses to run without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

import bench_tiny
from chipbench import spec

BENCH = spec.load_benchmark(bench_tiny.ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert os.path.exists(os.path.join(bench_tiny.ROOT, BENCH["command"][1]))


def test_names_and_units_use_the_allowed_characters():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.cell(cell, BENCH)
    names = [m.name for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m.moves in names
        assert callable(spec.load_reader(m.name))
    assert c.config["name"] == next(
        w for w in BENCH["workloads"] if w["name"] == cell)["config"]
    assert c.traffic["loop"] in ("open", "closed")


def test_every_config_file_is_under_paths():
    for c in BENCH["configs"]:
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert json.load(open(os.path.join(bench_tiny.ROOT, c["file"])))[
            "name"] == c["name"]
        assert c["reduced"] == []


def test_readers_leave_out_what_they_cannot_read():
    """Without a trace the device readers return nothing (never 0)."""
    ctx = {"trace": None, "trace_steps": [], "host_steps": [],
           "host_window_s": 0.0}
    for m in BENCH["per_layer"]:
        assert spec.load_reader(m["name"])(ctx) is None


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(bench_tiny.ROOT, BENCH["command"][1]),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
