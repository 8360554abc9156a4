"""What every kind of run shares: finding a cell's files by the names in
``BENCHMARK.json``, the look for the chip, the compile cache and its clock,
the readers of single metrics, and the one result line.

Nothing here names a cell, a configuration, a family, a traffic mix or a
metric.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import sys

import families
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*a) -> None:
    print("[bench]", *a, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark(left_out: bool = False) -> dict:
    """``BENCHMARK.json``.  With ``left_out`` also the cells that are out of
    it: ``benchmarks/left_out/<cell>.json`` keeps the entries that bring a
    cell back and says why it is out, so calibration, rehearsal and the
    tests of its path still reach it by name.  The driver runs only what
    ``BENCHMARK.json`` lists."""
    bench = load_json(ROOT, "BENCHMARK.json")
    pattern = os.path.join(HERE, "left_out", "*.json")
    for path in sorted(glob.glob(pattern)) if left_out else ():
        out = load_json(path)
        bench["workloads"] += out["workloads"]
        for kind in ("end_to_end", "per_layer"):
            have = {m["name"]: m for m in bench[kind]}
            for m in out[kind]:
                if m["name"] in have:
                    have[m["name"]]["workloads"] += m["workloads"]
                else:
                    have[m["name"]] = m
                    bench[kind].append(m)
    return bench


def find_cell(name: str, benchmark: dict | None = None) -> dict:
    """The cell ``name`` with its configuration's and its traffic's files
    read, its configuration's family loaded, and the metrics it reports:
    everything by name."""
    bench = benchmark or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells and benchmark is None:
        bench = load_benchmark(left_out=True)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name in cells:
            log(f"{name} is left out of BENCHMARK.json: "
                f"benchmarks/left_out/{name}.json says why")
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json or "
                         f"benchmarks/left_out; known: {sorted(cells)}")
    cell = dict(cells[name])
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["config_file"] = load_json(ROOT, cfg_entry["file"])
    cell["family"] = families.of_config(cell["config_file"], cell["config"])
    cell["mix"] = traffic.load(cell["traffic"])
    if "deployment" in cell["mix"]:
        cell["deployment"] = load_json(HERE, "deployments",
                                       cell["mix"]["deployment"] + ".json")
    limits = os.path.join(HERE, "limits", name + ".json")
    cell["limits"] = load_json(limits) if os.path.exists(limits) else {}

    def reported(metric: dict, e2e_names: set) -> bool:
        if "workloads" in metric:
            return name in metric["workloads"]
        return "moves" not in metric or metric["moves"] in e2e_names

    cell["end_to_end"] = [m for m in bench["end_to_end"] if reported(m, set())]
    names = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in bench["per_layer"] if reported(m, names)]
    return cell


def require_devices(chips: int):
    """TPUs, exactly as many as the cell asks for; otherwise the run ends
    non-zero with no result (never a CPU fallback)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU; JAX found {len(devices)} "
                         f"{devices[0].platform} device(s).  Nothing was run.")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), JAX "
                         f"found {len(devices)}.  Nothing was run.")
    return devices[:chips]


def enable_compile_cache() -> str:
    """The persistent cache at ``JAX_COMPILATION_CACHE_DIR`` or at the
    program's one fixed path in the checkout, keeping every program
    however quickly it compiled (serving has hundreds of small ones)."""
    import jax
    from distributed_pytorch_tpu.utils import compile_cache

    where = compile_cache.enable(min_compile_secs=0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # and however many there are: a size limit on the cache evicts what the
    # next run of the cell needs (a 192 MiB limit made warm serving
    # set-ups compile again)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return where


class CompileClock:
    """Seconds spent obtaining executables (compiling or loading them from
    the cache) and how many were obtained, by JAX's own monitoring."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.secs += secs
            self.count += 1

    def snapshot(self) -> tuple[float, int]:
        return self.secs, self.count


class Phases:
    """The run's own phase lines on standard error: what each part of
    set-up cost, and how much of it was obtaining executables."""

    def __init__(self, clock: CompileClock, t_start: float):
        import time

        self.clock, self.now = clock, time.perf_counter
        self.t, self.c = t_start, clock.snapshot()

    def __call__(self, name: str) -> None:
        t, c = self.now(), self.clock.snapshot()
        log(f"phase {name}: {t - self.t:.1f} s, of which {c[0] - self.c[0]:.1f}"
            f" s obtaining {c[1] - self.c[1]} executables")
        self.t, self.c = t, c


def memory_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            if d.platform == "cpu":   # a rehearsal: the CPU keeps no count
                stats = {"peak_bytes_in_use": 0}
            else:
                raise RuntimeError(f"{d} reports no peak_bytes_in_use")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def load_reader(kind: str, metric: str):
    """``read(ctx)`` of ``benchmarks/<kind>/<metric>.py``."""
    path = os.path.join(HERE, kind, metric + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"metric {metric!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(kind: str, metrics: list[dict], ctx: dict) -> dict:
    """name -> {"value", "unit"} for every metric whose reader found
    something to read; one that returns None is left out of the line."""
    out = {}
    for m in metrics:
        value = load_reader(kind, m["name"])(ctx)
        if value is None:
            continue
        value = float(value)
        if value != value:
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_block(devices, memory_peak: int, trace: dict | None) -> dict:
    d = devices[0]
    block = {"platform": d.platform, "kind": d.device_kind,
             "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    if trace is not None:
        block["busy_s"] = trace["busy_s"]
        block["window_s"] = trace["window_s"]
    return block


def emit(result: dict, checks: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error, then the one result line, ``checks`` last in it."""
    for name, c in checks.items():
        print(f"[check] {name} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    print(json.dumps(line), flush=True)
