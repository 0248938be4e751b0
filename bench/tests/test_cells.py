"""Each cell's driver end to end on the CPU at a tiny size, the command's
refusal without a TPU, and a new cell added by files alone."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests import tiny

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("name", CELLS)
def test_driver_ends_with_the_contract_keys(name):
    c = tiny.cell(name)
    out = tiny.measure(c)
    assert list(out) == KEYS                  # checks come last
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in c.end_to_end}
    assert "setup_s" in out["metrics"]
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert all(v["limit"] == 0 and v["value"] == 0
               for v in out["checks"].values())


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ycsb.load-a",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ycsb.scan-a",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


def test_nothing_describes_a_topology_at_import(monkeypatch):
    import importlib
    from jax.experimental import topologies
    calls = []
    monkeypatch.setattr(topologies, "get_topology_desc",
                        lambda *a, **k: calls.append(a))
    mods = ["bench.harness", "bench.trace", "bench.work", "bench.peaks",
            "bench.reference", "bench.sut", "bench.workload",
            "bench.drivers.load", "bench.drivers.scan", "bench.control",
            "bench.sweep", "bench.data.ycsb", "bench.data.winlog"]
    for m in mods:
        importlib.reload(importlib.import_module(m))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        harness.reader(m["name"])
    assert calls == []


def test_a_new_mix_and_metric_need_only_files(tmp_path):
    """A traffic mix of an existing kind and a per-layer metric, added as
    files plus BENCHMARK.json entries in a copy of the checkout."""
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench")
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((harness.ROOT / "bench/traffic/load-a.json").read_text())
    mix["workload"] = dict(mix["workload"], zipf_a=2.0)    # workload B
    (tmp_path / "bench/traffic/load-b.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/chunks_per_s.py").write_text(
        "def read(r):\n"
        "    c = r.counters\n"
        "    return c['rows'] / c['chunk_records'] / c['window_s']\n")
    spec["workloads"].append({"name": "ycsb.load-b", "config": "ycsb-fakeit",
                              "traffic": "load-b", "chips": 1, "why": "B"})
    spec["end_to_end"][0]["workloads"].append("ycsb.load-b")
    spec["per_layer"].append({
        "name": "chunks_per_s", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "serve front end",
        "moves": "ingest_rows_per_s", "workloads": ["ycsb.load-b"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    c = tiny.cell("ycsb.load-b", root=tmp_path)
    assert c.traffic["workload"]["zipf_a"] == 2.0
    assert [m["name"] for m in c.per_layer] == ["chunks_per_s"]
    out = tiny.measure(c, trace=True)
    assert out["correct"] is True
    assert out["metrics"]["chunks_per_s"]["value"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
