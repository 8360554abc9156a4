"""The entry point: no chip, no run; and the result line's keys."""

import json
import os
import subprocess
import sys

import pytest

import harness

ROOT = harness.ROOT


def test_run_exits_nonzero_and_prints_no_result_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "lm_train_4k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
    # nothing was compiled: the cache directory was never written
    assert not os.path.exists(tmp_path / "cache")


def test_benchmark_json_names_files_that_exist_and_readers_for_every_metric():
    listed = harness.load_benchmark()
    bench = harness.load_benchmark(left_out=True)
    out = {w["name"] for w in bench["workloads"]} - {
        w["name"] for w in listed["workloads"]}
    # a cell that is out is still found by name, with its entries whole
    assert all(harness.find_cell(name)["per_layer"] for name in out)
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"], bench)
        assert cell["mix"]["kind"] in ("train", "serve")
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
        for m in cell["end_to_end"]:
            harness.load_reader("end_to_end", m["name"])
        for m in cell["per_layer"]:
            harness.load_reader("layer_metrics", m["name"])
            assert m["moves"] in {e["name"] for e in cell["end_to_end"]}
    four = [w for w in listed["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(listed["workloads"]) // 4)


def test_emit_prints_checks_on_stderr_then_one_line_with_the_contracts_keys(capsys):
    result = {"correct": True, "attempted": 3, "failed": 0,
              "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
              "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                         "memory_peak_bytes": 7}}
    harness.emit(result, {"loss_gap": {"value": 1e-5, "limit": 1e-4,
                                       "ok": True}})
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["checks"]["loss_gap"] == {"value": 1e-5, "limit": 1e-4}
    assert "loss_gap = 1e-05 limit 0.0001 ok" in err.strip().splitlines()[-1]
