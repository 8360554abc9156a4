"""Every cell of ``BENCHMARK.json``, and every cell left out of it
(``benchmarks/left_out/``), rehearsed without the chip: the whole
run at a tiny size on the CPU (the four-chip cell on four virtual devices),
traced, and its kernels compiled at the real shapes for a described v5e.
A later PR that adds a cell gets both for nothing: the cells are read from
``BENCHMARK.json``.
"""

import json

import pytest

import harness
import rehearse

WORKLOADS = [w["name"] for w in
             harness.load_benchmark(left_out=True)["workloads"]]


@pytest.fixture(scope="module")
def topo():
    import jax
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        return rehearse.describe_v5e()
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_kernels_compile_for_the_chip_at_the_cells_real_shapes(workload, topo):
    took = rehearse.compile_cell_kernels(harness.find_cell(workload), topo)
    assert took and all(s > 0 for s in took.values())


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traced_tiny_run_prints_the_contracts_line(workload, capsys):
    done = rehearse.rehearse(workload, seed=(1 << 31) + 99, seconds=3.0,
                             trace=True)
    harness.emit(done["result"], done["checks"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device",
            "breakdown", "checks"} <= set(line)
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
            "window_s"} <= set(line["device"])
    assert line["attempted"] > 0 and line["failed"] == 0
    cell = harness.find_cell(workload)
    assert line["device"]["count"] == cell["chips"]
    known = {m["name"] for m in cell["per_layer"]}
    assert set(line["metrics"]) <= known
    # host-clock and counter metrics need no device trace: they are there
    assert "entry.compile_s" in line["metrics"]
    assert all(isinstance(v["value"], float) for v in line["metrics"].values())
