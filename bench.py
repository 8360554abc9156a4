"""Headline benchmark: CIFAR-10 training samples/sec/chip.

Measures the framework's full compiled training step (augment + forward +
loss + backward + gradient sync + SGD update) at the reference's workload
shape — VGG-11, batch 256 per replica (reference main.py:18,103-104) — over
all available devices, and reports throughput per chip.

``vs_baseline`` is the ratio to the reference implementation's semantics run
with torch on CPU (the reference is CPU-only: main.py:15-16, 4 threads) —
measured live on this machine when torch is available, else a fallback
constant measured on the dev box.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "samples/sec/chip", "vs_baseline": N}
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


def bench_meta() -> dict:
    """Provenance block stamped into every bench JSON: git sha,
    jax/jaxlib versions, platform/device, host, UTC timestamp.  The
    BENCH_r*.json trajectory spans hosts and runtimes — without this a
    round-over-round comparison (scripts/bench_compare.py) cannot tell
    a code regression from a host change, so the comparator refuses to
    gate across mismatched platforms unless told otherwise."""
    import socket
    import subprocess

    import jax
    import jaxlib

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        sha = "unknown"
    dev = jax.devices()[0]
    return {
        "git_sha": sha,
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "device_count": jax.device_count(),
        "hostname": socket.gethostname(),
        "python": sys.version.split()[0],
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                       time.gmtime()),
    }


def vgg11_train_flops_per_sample() -> float:
    """Analytic training FLOPs/sample for VGG-11 on 32x32 (reference
    model.py:3-8 cfg): conv MACs = H*W*Cin*Cout*9 at each stage's
    resolution, x2 FLOPs/MAC, x3 for fwd + input-grad + weight-grad
    (the standard training estimate; BN/ReLU/pool are O(activations),
    <1% of conv FLOPs, excluded — this slightly UNDERSTATES work, so the
    MFU it yields is conservative)."""
    cfg = [(32, 3, 64), (16, 64, 128), (8, 128, 256), (8, 256, 256),
           (4, 256, 512), (4, 512, 512), (2, 512, 512), (2, 512, 512)]
    macs = sum(h * h * cin * cout * 9 for h, cin, cout in cfg)
    macs += 512 * 10  # fc head
    return 2 * 3 * macs


# bf16 peak TFLOP/s per chip by device kind (Google Cloud documentation,
# "TPU v5e" / "TPU v4" / "TPU v5p" / "TPU v6e" system architecture pages).
_PEAK_BF16_TFLOPS = {
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5e": 197.0,
    "TPU v4": 275.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,   # v6e
}


def peak_bf16_flops(device) -> float:
    """The device's bf16 peak FLOP/s.  A device kind that is not in the
    table is an error, not a default: a utilization over a guessed peak
    is not a measurement."""
    kind = getattr(device, "device_kind", "")
    for name, tf in _PEAK_BF16_TFLOPS.items():
        if kind.startswith(name):
            return tf * 1e12
    raise ValueError(
        f"no bf16 peak for device kind {kind!r} (platform "
        f"{getattr(device, 'platform', '?')!r}); known: "
        f"{sorted(_PEAK_BF16_TFLOPS)}")


def calibrate_matmul_tflops(iters: int = 400, n: int = 4096) -> float:
    """Session device control: achieved bf16 TFLOP/s on a dependency-chained
    n^3 matmul, measured exactly like the bench (one scan dispatch, one
    value fetch, min-of-2).  Historically the headline samples/s appeared
    to carry ~±10% session noise; this calibration's ±0.3% stability
    exposed that as fetch-RTT inside a too-short timed window (now
    hardened — BASELINE.md session-drift section).  It remains in the
    JSON as the cross-session control: a genuine device/toolchain change
    moves it, measurement noise does not."""
    import jax
    import jax.numpy as jnp

    # value-stable chain: x = ones, b = 1/n everywhere -> x @ b == ones
    # exactly, every iteration (no overflow/decay, nothing to constant-fold
    # since b is a runtime operand)
    a = jnp.ones((n, n), jnp.bfloat16)
    b = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def loop(a, b):
        def body(x, _):
            return x @ b, ()
        x, _ = jax.lax.scan(body, a, None, length=iters)
        return jnp.sum(x.astype(jnp.float32))

    float(loop(a, b))  # compile + warm
    best = float("inf")
    for _ in range(2):  # min-of-2: the one end-of-chain fetch RTT is noise
        t0 = time.perf_counter()
        v = float(loop(a, b))
        best = min(best, time.perf_counter() - t0)
    tflops = 2 * n**3 * iters / best / 1e12
    _log(f"[bench] calibration: {n}^3 bf16 matmul x{iters} -> "
         f"{tflops:.1f} TF/s achieved (checksum {v:.3e})")
    return tflops


def bench_tpu(batch_per_replica: int, warmup: int,
              iters: int) -> tuple[float, float]:
    """(samples/sec/chip, MFU) of the compiled train step on real devices;
    a device kind with no peak-FLOPs entry is an error."""
    import jax

    from distributed_pytorch_tpu.parallel.mesh import make_mesh
    from distributed_pytorch_tpu.train import TrainConfig, Trainer

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    # bfloat16 compute: the MXU-native dtype (params stay float32).  The
    # whole measured window runs as ONE lax.scan dispatch (steps_per_loop),
    # the TPU-native training-loop shape: host dispatch/transfer latency is
    # off the hot path, exactly as a prefetching input pipeline provides.
    cfg = TrainConfig(strategy="ddp" if n_dev > 1 else "none",
                      batch_size=batch_per_replica,
                      steps_per_loop=iters,
                      compute_dtype="bfloat16")
    mesh = make_mesh(n_dev) if n_dev > 1 else None
    trainer = Trainer(cfg, mesh=mesh)

    global_batch = batch_per_replica * n_dev
    rng = np.random.default_rng(0)
    images = rng.integers(
        0, 256, (iters, global_batch, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, (iters, global_batch)).astype(np.int32)
    if mesh is None:  # pre-stage on device (the mesh path stages internally)
        images, labels = jax.device_put((images, labels))

    _log(f"[bench] platform={platform} devices={n_dev} "
         f"global_batch={global_batch} strategy={cfg.strategy}")
    # Warm-up (in steps): at least one full window so the scan is compiled
    # and the caches are hot before the timed window.
    for _ in range(max(round(warmup / iters), 1)):
        losses = trainer.train_steps(images, labels)
    float(losses[-1])

    # min-of-2 timed windows: each window ends with ONE value fetch, which
    # forces the whole chain of donated-buffer steps.  (On the v5e
    # machine block_until_ready waits for the device just as well, and a
    # one-element fetch of a ready value costs ~1.6 ms: chip_smoke.py's
    # clock phase, PR 21.)
    dt = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        losses = trainer.train_steps(images, labels)
        final_loss = float(losses[-1])
        dt = min(dt, time.perf_counter() - t0)

    sps_total = global_batch * iters / dt
    _log(f"[bench] {iters} steps in {dt:.3f}s -> {sps_total:.1f} samples/s "
         f"total, {sps_total / n_dev:.1f}/chip, loss={final_loss:.3f}")
    sps_chip = sps_total / n_dev
    # MFU: analytic model FLOPs vs the chip's bf16 peak — the regression-
    # visible efficiency number (samples/s alone hides chip generation and
    # session drift; MFU does not).
    peak = peak_bf16_flops(jax.devices()[0])
    mfu = sps_chip * vgg11_train_flops_per_sample() / peak
    _log(f"[bench] {global_batch / n_dev / sps_chip * 1000:.3f} ms/step/chip"
         f", MFU {mfu:.1%} of {peak / 1e12:.0f} TF bf16 peak")
    return sps_chip, mfu


def _canon_bool_env(name: str, value: str | None, *, default: bool,
                    guess: str) -> bool:
    """The ONE '0'/'1' env-knob validation (the BENCH_KV_DTYPE
    fail-loudly contract): a typo must raise HERE, before any
    measurement — inside the benches it would be swallowed by their
    catch-alls while the JSON silently omitted (or silently ran) the
    gate.  Unset/'' takes the knob's ``default``."""
    if value is None or value == "":
        return default
    if value == "1":
        return True
    if value == "0":
        return False
    raise ValueError(
        f"{name} must be '0' or '1', got {value!r} — refusing to guess "
        f"{guess}")


def canon_overlap_env(value: str | None) -> bool:
    """Validate the BENCH_OVERLAP knob ('1' = run the overlap A/B, the
    default; '0' = skip it)."""
    return _canon_bool_env("BENCH_OVERLAP", value, default=True,
                           guess="which A/B you meant")


def bench_train_overlap(batch_per_replica: int = 64, iters: int = 30,
                        reps: int = 5) -> dict | None:
    """In-session A/B of backward-overlapped gradient sync (round 8):
    the SAME bucketed strategy (torch DDP's engine semantics) with the
    bucket collectives emitted inside the backward graph (overlap=True)
    vs after it (the historical post-backward path), VGG-11 bf16 on all
    devices, >= ``reps`` alternating timed windows per mode with
    median-of-reps (the hardened-window discipline of the serving
    gates).  Needs >= 2 devices (there is no collective to overlap on
    one chip) — returns None there, and the JSON carries nulls.

    The two programs are bitwise-identical in results (test-pinned), so
    the delta is pure schedule: on CPU meshes expect ~1.0x (XLA's CPU
    backend runs thunks serially — the schedule proof lives in the
    utils/debug.py inspector instead); on real ICI/DCN the collective
    time hides under backward compute.
    """
    import jax

    from distributed_pytorch_tpu.parallel.mesh import make_mesh
    from distributed_pytorch_tpu.train import TrainConfig, Trainer

    n_dev = len(jax.devices())
    if n_dev < 2:
        _log("[bench] train-overlap A/B needs >= 2 devices "
             f"(have {n_dev}); omitting")
        return None
    mesh = make_mesh(n_dev)

    def build(overlap: bool) -> Trainer:
        cfg = TrainConfig(strategy="bucketed", batch_size=batch_per_replica,
                          steps_per_loop=iters, compute_dtype="bfloat16",
                          overlap=overlap)
        return Trainer(cfg, mesh=mesh)

    trainers = {False: build(False), True: build(True)}
    rng = np.random.default_rng(0)
    global_batch = batch_per_replica * n_dev
    images = rng.integers(
        0, 256, (iters, global_batch, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, (iters, global_batch)).astype(np.int32)

    for tr in trainers.values():  # compile + warm outside the timed reps
        tr.precompile_steps(images, labels)
        float(tr.train_steps(images, labels)[-1])

    times: dict[bool, list[float]] = {False: [], True: []}
    for _ in range(reps):
        for mode, tr in trainers.items():  # alternate: drift hits both
            t0 = time.perf_counter()
            losses = tr.train_steps(images, labels)
            float(losses[-1])  # fetch forces the whole donated chain
            times[mode].append((time.perf_counter() - t0) / iters * 1e3)
    med = {m: sorted(ts)[len(ts) // 2] for m, ts in times.items()}
    speedup = med[False] / max(med[True], 1e-9)
    _log(f"[bench] train-overlap A/B (bucketed, VGG-11, {n_dev} dev): "
         f"{med[True]:.2f} ms/step overlapped vs {med[False]:.2f} "
         f"post-backward -> {speedup:.3f}x ({reps} reps median)")
    return {"speedup": speedup, "ms_overlap": med[True],
            "ms_post_backward": med[False]}


def canon_dcn_size_env(value: str | None) -> int:
    """Validate the BENCH_DCN_SIZE knob: unset/''/'0' skips the factored-
    mesh DCN A/B (the default — it needs >= 2 devices to mean anything);
    an integer >= 2 is the number of slices for the virtual two-level
    mesh.  A typo must fail HERE, before any measurement (the
    BENCH_KV_DTYPE contract): inside the bench it would be swallowed by
    the catch-all while the JSON silently omitted the A/B."""
    if value is None or value in ("", "0"):
        return 0
    try:
        n = int(value)
    except ValueError:
        raise ValueError(
            f"BENCH_DCN_SIZE must be an integer >= 2 (or ''/0 to skip), "
            f"got {value!r}") from None
    if n < 2:
        raise ValueError(
            f"BENCH_DCN_SIZE must be >= 2 (a {n}-slice 'factored' mesh "
            f"has no cross-slice hop); unset it or use 0 to skip")
    return n


def canon_dcn_compress_env(value: str | None) -> str | None:
    """Validate BENCH_DCN_COMPRESS (the slow-hop compression the DCN A/B
    runs with): unset/''/'none' = exact full-precision psum, 'int8' /
    'int4' = the quantized ring exchange at that width (round 16 adds
    the nibble-packed int4 rung).  Fails loudly pre-bench like
    BENCH_KV_DTYPE."""
    if value is None or value in ("", "none"):
        return None
    if value in ("int8", "int4"):
        return value
    raise ValueError(
        f"BENCH_DCN_COMPRESS must be ''/'none', 'int8', or 'int4', "
        f"got {value!r}")


def bench_train_dcn(dcn_size: int, compress: str | None,
                    batch_per_replica: int = 64, iters: int = 30,
                    reps: int = 5) -> dict | None:
    """Factored-mesh (two-level DCN) training A/B (round 9): the
    'hierarchical' strategy over a Mesh(('dcn', 'ici')) built from all
    devices, streaming per-bucket overlap=True vs the post-backward
    path, with the same hardened-window discipline as the round-8
    overlap A/B (>= ``reps`` alternating reps, median, value-fetch
    barrier).  ``compress`` additionally runs the int8 DCN hop on BOTH
    sides of the A/B.  Also reports the per-axis wire accounting from
    the schedule inspector — ``dcn_bytes_per_step`` is the measured
    cross-slice payload (|grads|/ici exact, ~1/4 of that again under
    int8).  Needs >= 2 devices divisible by dcn_size; returns None (JSON
    nulls) otherwise.  On CPU meshes expect ~1.0x speedup (no
    latency-hiding scheduler — the schedule/byte numbers are the CPU
    content); on real DCN the slow hop hides under backward compute."""
    import jax

    from distributed_pytorch_tpu.train import (TrainConfig, Trainer,
                                               make_multi_step)
    from distributed_pytorch_tpu.utils import debug as dbg

    n_dev = len(jax.devices())
    if n_dev < 2 or n_dev % dcn_size or n_dev // dcn_size < 1:
        _log(f"[bench] train-dcn A/B needs >= 2 devices divisible by "
             f"dcn_size={dcn_size} (have {n_dev}); omitting")
        return None

    def build(overlap: bool) -> Trainer:
        cfg = TrainConfig(strategy="hierarchical", dcn_size=dcn_size,
                          dcn_compress=compress,
                          batch_size=batch_per_replica,
                          steps_per_loop=iters, compute_dtype="bfloat16",
                          overlap=overlap)
        return Trainer(cfg)  # builds the ('dcn', 'ici') mesh itself

    trainers = {False: build(False), True: build(True)}
    rng = np.random.default_rng(0)
    global_batch = batch_per_replica * n_dev
    images = rng.integers(
        0, 256, (iters, global_batch, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, (iters, global_batch)).astype(np.int32)

    for tr in trainers.values():  # compile + warm outside the timed reps
        tr.precompile_steps(images, labels)
        float(tr.train_steps(images, labels)[-1])

    times: dict[bool, list[float]] = {False: [], True: []}
    for _ in range(reps):
        for mode, tr in trainers.items():  # alternate: drift hits both
            t0 = time.perf_counter()
            losses = tr.train_steps(images, labels)
            float(losses[-1])  # fetch forces the whole donated chain
            times[mode].append((time.perf_counter() - t0) / iters * 1e3)
    med = {m: sorted(ts)[len(ts) // 2] for m, ts in times.items()}
    speedup = med[False] / max(med[True], 1e-9)

    # per-axis wire accounting of the overlapped program (one trace; the
    # executable is already compiled) — the dcn row is the slow-hop cost
    tr = trainers[True]
    img, lbl = tr._stage(images[:1], labels[:1])
    args = tr._args(img, lbl)
    if tr._multi_fn is None:
        tr._multi_fn = make_multi_step(tr.cfg, tr.strategy, tr.mesh,
                                       fault_sig=tr._fault_sig)
    per_axis = dbg.per_axis_collective_stats(
        dbg.op_schedule(tr._multi_fn, *args))
    dcn_bytes = per_axis.get("dcn", {}).get("bytes_executed", 0)
    ici_bytes = per_axis.get("ici", {}).get("bytes_executed", 0)
    _log(f"[bench] train-dcn A/B (hierarchical, dcn_size={dcn_size}, "
         f"compress={compress or 'none'}, {n_dev} dev): "
         f"{med[True]:.2f} ms/step overlapped vs {med[False]:.2f} "
         f"post-backward -> {speedup:.3f}x; "
         f"{dcn_bytes / 1e6:.2f} MB dcn / {ici_bytes / 1e6:.2f} MB ici "
         f"per step ({reps} reps median)")
    return {"speedup": speedup, "ms_overlap": med[True],
            "ms_post_backward": med[False], "dcn_bytes_per_step": dcn_bytes,
            "ici_bytes_per_step": ici_bytes}


def canon_sync_every_env(value: str | None) -> int:
    """Validate the BENCH_SYNC_EVERY knob (round 18): unset/''/'0'/'1'
    skips the local-SGD window A/B (per-step sync IS the baseline, so
    H=1 vs H=1 measures nothing); an integer >= 2 is the window length
    H the A/B runs against per-step sync.  A typo must fail HERE,
    before any measurement (the BENCH_KV_DTYPE contract): inside the
    bench it would be swallowed by the catch-all while the JSON
    silently omitted the A/B."""
    if value is None or value in ("", "0", "1"):
        return 1
    try:
        h = int(value)
    except ValueError:
        raise ValueError(
            f"BENCH_SYNC_EVERY must be an integer >= 2 (or ''/0/1 to "
            f"skip), got {value!r}") from None
    if h < 2:
        raise ValueError(
            f"BENCH_SYNC_EVERY must be >= 2 (H=1 is the per-step "
            f"baseline — there is no window to A/B); unset it or use "
            f"0/1 to skip")
    return h


def bench_train_localsgd(sync_every: int, batch_per_replica: int = 64,
                         iters: int = 32, reps: int = 5) -> dict | None:
    """Local-SGD window A/B (round 18, BENCH_SYNC_EVERY=H): the
    hierarchical two-level strategy on a dcn_size=2 factored mesh with
    ``sync_every=H`` local steps per DCN exchange vs the per-step H=1
    path, same hardened-window discipline as the round-9 DCN A/B
    (>= ``reps`` alternating reps, median, value-fetch barrier).  Both
    sides run the same model/batch/mesh; ``iters`` rounds up to a
    multiple of H because windowed dispatches must end on a boundary
    (train_steps refuses unaligned windows).  Also reports the
    inspector's AMORTIZED cross-slice payload:
    ``dcn_bytes_per_step_windowed`` is dcn bytes per step at interval H
    (~1/H of the per-step payload, ici unchanged — the round-18
    schedule claim, test-pinned in tests/test_localsgd.py).  Needs an
    even device count >= 2; returns None (JSON nulls) otherwise.  On
    CPU meshes expect ~1.0x speedup (no real slow hop to remove); the
    byte accounting is the CPU content."""
    import jax

    from distributed_pytorch_tpu.train import (TrainConfig, Trainer,
                                               make_multi_step)
    from distributed_pytorch_tpu.utils import debug as dbg

    n_dev = len(jax.devices())
    if n_dev < 2 or n_dev % 2:
        _log(f"[bench] train-localsgd A/B needs an even device count "
             f">= 2 (have {n_dev}); omitting")
        return None
    h = sync_every
    iters = -(-iters // h) * h  # window-aligned dispatches

    def build(sync: int) -> Trainer:
        cfg = TrainConfig(strategy="hierarchical", dcn_size=2,
                          batch_size=batch_per_replica,
                          steps_per_loop=iters, compute_dtype="bfloat16",
                          sync_every=sync, max_sync_every=sync)
        return Trainer(cfg)  # builds the ('dcn', 'ici') mesh itself

    trainers = {1: build(1), h: build(h)}
    rng = np.random.default_rng(0)
    global_batch = batch_per_replica * n_dev
    images = rng.integers(
        0, 256, (iters, global_batch, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, (iters, global_batch)).astype(np.int32)

    for tr in trainers.values():  # compile + warm outside the timed reps
        tr.precompile_steps(images, labels)
        float(tr.train_steps(images, labels)[-1])

    times: dict[int, list[float]] = {1: [], h: []}
    for _ in range(reps):
        for mode, tr in trainers.items():  # alternate: drift hits both
            t0 = time.perf_counter()
            losses = tr.train_steps(images, labels)
            float(losses[-1])  # fetch forces the whole donated chain
            times[mode].append((time.perf_counter() - t0) / iters * 1e3)
    med = {m: sorted(ts)[len(ts) // 2] for m, ts in times.items()}
    speedup = med[1] / max(med[h], 1e-9)

    # amortized per-axis wire accounting: one trace per side over the
    # full window-multiple dispatch, divided by its step count — the
    # windowed program holds H local steps + one exchange per window
    def axis_bytes(tr: Trainer) -> dict[str, float]:
        img, lbl = tr._stage(images, labels)
        args = tr._args(img, lbl)
        if tr._multi_fn is None:
            tr._multi_fn = make_multi_step(tr.cfg, tr.strategy, tr.mesh,
                                           fault_sig=tr._fault_sig)
        return dbg.amortized_axis_bytes(
            [(dbg.op_schedule(tr._multi_fn, *args), 1)], iters)

    per_step, windowed = axis_bytes(trainers[1]), axis_bytes(trainers[h])
    dcn_w = windowed.get("dcn", 0.0)
    dcn_1 = per_step.get("dcn", 0.0)
    _log(f"[bench] train-localsgd A/B (hierarchical, dcn_size=2, "
         f"sync_every={h}, {n_dev} dev): {med[h]:.2f} ms/step windowed "
         f"vs {med[1]:.2f} per-step-sync -> {speedup:.3f}x; dcn "
         f"{dcn_w / 1e6:.2f} MB/step amortized vs {dcn_1 / 1e6:.2f} "
         f"per-step ({reps} reps median)")
    return {"speedup": speedup, "ms_windowed": med[h],
            "ms_per_step_sync": med[1],
            "dcn_bytes_per_step_windowed": dcn_w,
            "dcn_bytes_per_step_h1": dcn_1, "sync_every": h}


def canon_fsdp_gather_env(value: str | None) -> str | None:
    """Validate BENCH_FSDP_GATHER (round 16): unset/''/'none' skips the
    quantized ZeRO-3 gather A/B; 'int8' runs it (fsdp weight all-gathers
    quantized per-row, dequant at the consumer).  Fails loudly pre-bench
    like BENCH_DCN_COMPRESS."""
    if value is None or value in ("", "none"):
        return None
    if value == "int8":
        return "int8"
    raise ValueError(
        f"BENCH_FSDP_GATHER must be ''/'none' or 'int8', got {value!r}")


def bench_lm_q8_gather(iters: int = 20, batch_per_dev: int = 1,
                       seq: int = 256, reps: int = 5) -> dict | None:
    """Quantized ZeRO-3 gather A/B (round 16, BENCH_FSDP_GATHER=int8):
    the LM fsdp step with ``fsdp_gather_dtype="int8"`` vs the f32 weight
    all-gathers, same model/batch/mesh, hardened-window discipline
    (alternating reps, median, value-fetch barrier).  ``speedup`` is
    ms_f32 / ms_int8 — >1 when the quartered gather wire wins, ~1.0 on
    CPU meshes (no real interconnect; the wire accounting in
    scripts/bench_strategies.py's lm_fsdp_q8gather row is the CPU
    content).  Needs >= 2 devices; returns None (JSON null) otherwise."""
    import jax

    from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer
    from distributed_pytorch_tpu.models import transformer as tfm

    n_dev = len(jax.devices())
    if n_dev < 2:
        _log(f"[bench] lm-q8gather A/B needs >= 2 devices (have {n_dev}); "
             f"omitting")
        return None
    model = tfm.TransformerConfig(vocab_size=256, d_model=256, n_layers=4,
                                  n_heads=4, head_dim=64, d_ff=512)

    def build(gather_dtype: str | None) -> LMTrainer:
        return LMTrainer(LMTrainConfig(
            model=model, dp=n_dev, fsdp=True,
            fsdp_gather_dtype=gather_dtype))

    trainers = {None: build(None), "int8": build("int8")}
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, (batch_per_dev * n_dev,
                                 seq)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)
    for tr in trainers.values():  # compile + warm outside the timed reps
        float(tr.train_step(toks, tgts))

    times: dict[str | None, list[float]] = {None: [], "int8": []}
    for _ in range(reps):
        for mode, tr in trainers.items():  # alternate: drift hits both
            t0 = time.perf_counter()
            for _ in range(iters):
                loss = tr.train_step(toks, tgts)
            float(loss)  # value fetch: the honest end-of-window barrier
            times[mode].append((time.perf_counter() - t0) / iters * 1e3)
    med = {m: sorted(ts)[len(ts) // 2] for m, ts in times.items()}
    speedup = med[None] / max(med["int8"], 1e-9)
    _log(f"[bench] lm-q8gather A/B (fsdp, {n_dev} dev): "
         f"{med['int8']:.2f} ms/step int8 vs {med[None]:.2f} f32 -> "
         f"{speedup:.3f}x ({reps} reps median)")
    return {"speedup": speedup, "ms_int8": med["int8"],
            "ms_f32": med[None]}


def canon_matmul_dtype_env(value: str | None) -> str | None:
    """Validate BENCH_MATMUL_DTYPE (round 16): unset/''/'none' skips the
    int8-matmul flip-rate gate; 'int8' runs it (transformer dense
    projections through the quantized matmul forward).  Fails loudly
    pre-bench like BENCH_KV_DTYPE."""
    if value is None or value in ("", "none"):
        return None
    if value == "int8":
        return "int8"
    raise ValueError(
        f"BENCH_MATMUL_DTYPE must be ''/'none' or 'int8', got {value!r}")


def bench_lm_int8_matmul(train_steps: int = 30, batch: int = 8,
                         seq: int = 256) -> dict | None:
    """int8-matmul flip-rate gate (round 16, BENCH_MATMUL_DTYPE=int8):
    the measure_fliprate methodology applied to the compute path —
    briefly train the small byte-LM on the synthetic corpus (so logits
    are a language model's, not random init's), then TEACHER-FORCE one
    held-out corpus batch through the bf16 forward and the
    ``matmul_dtype="int8"`` forward (identical context at every
    position) and report per-position argmax flips / positions.  The
    BASELINE round-7 kernel-vs-XLA bf16 near-tie baseline is 0.0024;
    the int8-vs-bf16 rate is a few x that (the quantization
    perturbation is wider than bf16 accumulation noise, flips still
    concentrate at |top1-top2| < 0.05 near-ties) — BASELINE.md's
    round-16 flip-rate table records the measured numbers, and
    tests/test_lowbit.py pins the ceiling."""
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu.data import lm_corpus
    from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=256, d_model=256, n_layers=4,
                                  n_heads=4, head_dim=64, d_ff=512)
    tr = LMTrainer(LMTrainConfig(model=model))
    data = lm_corpus.encode(lm_corpus.synthetic_corpus(1 << 18, seed=3))
    rng = np.random.default_rng(0)
    for _ in range(train_steps):
        idx = rng.integers(0, len(data) - seq - 1, batch)
        toks = np.stack([data[i:i + seq] for i in idx]).astype(np.int32)
        tgts = np.stack([data[i + 1:i + seq + 1]
                         for i in idx]).astype(np.int32)
        tr.train_step(toks, tgts)
    idx = rng.integers(0, len(data) - seq, batch)
    held = jnp.asarray(np.stack([data[i:i + seq]
                                 for i in idx]).astype(np.int32))

    def argmax_with(md: str | None) -> np.ndarray:
        f = jax.jit(lambda p, t: tfm.apply(p, t, cfg=model,
                                           dtype=jnp.bfloat16,
                                           matmul_dtype=md))
        return np.asarray(jnp.argmax(f(tr.params, held), axis=-1))

    ref = argmax_with(None)
    q = argmax_with("int8")
    flips = int((ref != q).sum())
    total = int(ref.size)
    _log(f"[bench] lm-int8matmul flip rate: {flips}/{total} = "
         f"{flips / total:.5f} (bf16 vs matmul_dtype=int8, "
         f"teacher-forced)")
    return {"fliprate": flips / total, "flips": flips, "positions": total}


def canon_autotune_env(value: str | None) -> bool:
    """Validate the BENCH_AUTOTUNE knob: '1' runs the round-11
    calibrate->choose->A/B leg, unset/''/'0' skips it (the default —
    calibration takes real device time)."""
    return _canon_bool_env(
        "BENCH_AUTOTUNE", value, default=False,
        guess="whether to run the calibrate->choose->A/B leg")


def bench_train_autotune(batch_per_replica: int = 64, iters: int = 30,
                         reps: int = 5) -> dict | None:
    """Topology-aware sync autotuner A/B (round 11, BENCH_AUTOTUNE=1):
    CALIBRATE the real mesh's per-axis links (alpha-beta fit over a
    psum / reduce-scatter+all-gather / ring ladder, cached repo-locally
    like the XLA compile cache), CHOOSE the sync plan for the VGG-11
    grad census (parallel/autotune.py), then A/B the resolved
    ``strategy="auto"`` trainer against the hand-picked default (the
    fixed-25 MB-bucket ``ddp`` baseline every round before this one
    used) with the hardened-window discipline (>= ``reps`` alternating
    timed windows, median, value-fetch barrier, precompile outside the
    window).  Returns the measured speedup plus the explainable plan
    (strategy / bucket / compression / predicted ms) so the JSON
    records WHY the chooser picked what it picked.  Needs >= 2 devices
    (one chip has no sync to tune) — returns None there, JSON nulls.
    On CPU meshes expect ~1.0x (no latency-hiding scheduler; the
    calibration/choice plumbing is the content)."""
    import jax

    from distributed_pytorch_tpu.parallel import autotune
    from distributed_pytorch_tpu.train import TrainConfig, Trainer

    n_dev = len(jax.devices())
    if n_dev < 2:
        _log(f"[bench] train-autotune A/B needs >= 2 devices (have "
             f"{n_dev}); omitting")
        return None
    # calibrate (or reuse the cached profile) on the topology the config
    # describes: factored when the fleet splits into 2 slices, flat
    # otherwise — the same recipe Trainer(strategy="auto") applies.
    dcn_size = 2 if n_dev % 2 == 0 and n_dev > 2 else 1
    axes = autotune.train_topology_axes(dcn_size, n_dev)
    profile = autotune.get_profile(None, axes)
    _log(f"[bench] autotune profile ({profile.source}): " + "; ".join(
        f"{a}: alpha {l.alpha_s * 1e6:.1f}us beta "
        f"{1.0 / max(l.beta_s_per_byte, 1e-30) / 1e9:.2f}GB/s"
        for a, l in profile.links.items()))

    def build(auto: bool) -> Trainer:
        cfg = TrainConfig(
            strategy="auto" if auto else "ddp",
            batch_size=batch_per_replica, dcn_size=dcn_size,
            steps_per_loop=iters, compute_dtype="bfloat16",
            autotune_profile=profile if auto else None)
        return Trainer(cfg)

    trainers = {False: build(False), True: build(True)}
    plan = trainers[True].sync_plan
    _log("[bench] " + plan.table().replace("\n", "\n[bench] "))
    rng = np.random.default_rng(0)
    global_batch = batch_per_replica * n_dev
    images = rng.integers(
        0, 256, (iters, global_batch, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, (iters, global_batch)).astype(np.int32)

    for tr in trainers.values():  # compile + warm outside the timed reps
        tr.precompile_steps(images, labels)
        float(tr.train_steps(images, labels)[-1])

    times: dict[bool, list[float]] = {False: [], True: []}
    for _ in range(reps):
        for mode, tr in trainers.items():  # alternate: drift hits both
            t0 = time.perf_counter()
            losses = tr.train_steps(images, labels)
            float(losses[-1])  # fetch forces the whole donated chain
            times[mode].append((time.perf_counter() - t0) / iters * 1e3)
    med = {m: sorted(ts)[len(ts) // 2] for m, ts in times.items()}
    speedup = med[False] / max(med[True], 1e-9)
    _log(f"[bench] train-autotune A/B (auto={plan.strategy}, {n_dev} "
         f"dev): {med[True]:.2f} ms/step auto vs {med[False]:.2f} "
         f"default-ddp -> {speedup:.3f}x ({reps} reps median)")
    return {"speedup": speedup, "ms_auto": med[True],
            "ms_default": med[False], "plan": plan.summary()}


def canon_route_env(value: str | None) -> bool:
    """Validate the BENCH_ROUTE knob (round 20): '1' runs the routed
    hop-graph leg (choose a route on the synthetic wan_dcn profile, run
    the RoutedSync trainer, report per-hop wire bytes), unset/''/'0'
    skips it."""
    return _canon_bool_env(
        "BENCH_ROUTE", value, default=False,
        guess="whether to run the routed hop-graph sync leg")


def bench_train_routed(batch_per_replica: int = 64, iters: int = 30,
                       reps: int = 5) -> dict | None:
    """Routed hop-graph sync leg (round 20, BENCH_ROUTE=1): run the
    route-searching chooser (parallel/autotune.choose_sync_plan) over
    the VGG-11 grad census on the synthetic ``wan_dcn`` profile shaped
    to this fleet's ('dcn', 'ici') factorization, execute the winning
    route with the RoutedSync trainer (strategy="routed" +
    ``sync_route``), and A/B it against the hand-built
    hierarchical+int4 path it generalizes — plus the schedule
    inspector's PER-HOP wire accounting (``amortized_axis_bytes(...,
    by_hop=True)``), the deterministic numbers bench_compare gates.
    Needs >= 4 devices divisible by 2 (a 2-slice factored mesh);
    returns None (JSON nulls) otherwise.  On CPU meshes expect ~1.0x
    (no latency-hiding scheduler; the route choice + per-hop byte
    accounting are the content)."""
    import jax

    from distributed_pytorch_tpu.parallel import autotune
    from distributed_pytorch_tpu.train import (TrainConfig, Trainer,
                                               make_multi_step)
    from distributed_pytorch_tpu.utils import debug as dbg

    n_dev = len(jax.devices())
    if n_dev < 4 or n_dev % 2:
        _log(f"[bench] train-routed A/B needs >= 4 devices divisible "
             f"by 2 (have {n_dev}); omitting")
        return None
    dcn_size = 2
    axes = autotune.train_topology_axes(dcn_size, n_dev)
    profile = autotune.synthetic_profile("wan_dcn", axes)
    from distributed_pytorch_tpu.models import vgg
    census = autotune.grad_census(jax.eval_shape(
        lambda k: vgg.init(k, "VGG11")[0], jax.random.key(0)))
    plan = autotune.choose_sync_plan(census, profile)
    _log("[bench] " + plan.table().replace("\n", "\n[bench] "))
    route = plan.route

    def build(routed: bool) -> Trainer:
        cfg = TrainConfig(
            strategy="routed" if routed else "hierarchical",
            sync_route=route if routed else None,
            dcn_compress=None if routed else "int4",
            batch_size=batch_per_replica, dcn_size=dcn_size,
            steps_per_loop=iters, compute_dtype="bfloat16")
        return Trainer(cfg)

    trainers = {False: build(False), True: build(True)}
    rng = np.random.default_rng(0)
    global_batch = batch_per_replica * n_dev
    images = rng.integers(
        0, 256, (iters, global_batch, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, (iters, global_batch)).astype(np.int32)

    for tr in trainers.values():  # compile + warm outside the timed reps
        tr.precompile_steps(images, labels)
        float(tr.train_steps(images, labels)[-1])

    times: dict[bool, list[float]] = {False: [], True: []}
    for _ in range(reps):
        for mode, tr in trainers.items():  # alternate: drift hits both
            t0 = time.perf_counter()
            losses = tr.train_steps(images, labels)
            float(losses[-1])  # fetch forces the whole donated chain
            times[mode].append((time.perf_counter() - t0) / iters * 1e3)
    med = {m: sorted(ts)[len(ts) // 2] for m, ts in times.items()}
    speedup = med[False] / max(med[True], 1e-9)

    # per-hop wire accounting of the routed program (one trace; the
    # executable is already compiled) — the rows bench_compare gates
    tr = trainers[True]
    img, lbl = tr._stage(images[:1], labels[:1])
    args = tr._args(img, lbl)
    if tr._multi_fn is None:
        tr._multi_fn = make_multi_step(tr.cfg, tr.strategy, tr.mesh,
                                       fault_sig=tr._fault_sig)
    sched = dbg.op_schedule(tr._multi_fn, *args)
    # the [:1] slice traced a K=1 scan, so the schedule is already
    # per-step — no /iters here (the timed program is K=iters, but the
    # per-step collective content is identical)
    by_hop = {k: int(v) for k, v in dbg.amortized_axis_bytes(
        [(sched, 1)], 1, by_hop=True).items()}
    bytes_per_step = sum(by_hop.values())
    _log(f"[bench] train-routed A/B (route={route!r}, {n_dev} dev): "
         f"{med[True]:.2f} ms/step routed vs {med[False]:.2f} "
         f"hierarchical_int4 -> {speedup:.3f}x; "
         f"{bytes_per_step / 1e6:.2f} MB/step by hop "
         f"{ {k: round(v / 1e6, 3) for k, v in by_hop.items()} } "
         f"({reps} reps median)")
    return {"speedup": speedup, "ms_routed": med[True],
            "ms_hierarchical_int4": med[False], "plan": plan.summary(),
            "bytes_by_hop": by_hop, "bytes_per_step": bytes_per_step}


def canon_moe_a2a_env(value: str | None) -> bool:
    """Validate the BENCH_MOE_A2A knob (round 21): '1' runs the
    quantized MoE dispatch A/B (f32 vs int8 expert all_to_all wire),
    unset/''/'0' skips it."""
    return _canon_bool_env(
        "BENCH_MOE_A2A", value, default=False,
        guess="whether to run the quantized MoE dispatch A/B")


def bench_moe_a2a(train_steps: int = 30, batch: int = 8,
                  seq: int = 256) -> dict | None:
    """Quantized expert-dispatch A/B (round 21, BENCH_MOE_A2A=1): train
    the small byte-LM as a Switch MoE over a dedicated ep=2 expert axis
    TWICE from identical init — ``moe_dispatch_bits="f32"`` vs
    ``"int8"`` (the routed ``expert:a2a@int8`` wire) — then report the
    deterministic numbers bench_compare gates:

    - ``bytes_per_step``: the int8 step program's all_to_all wire bytes
      (utils/debug.py op_schedule; quantized payload + bitcast f32
      scale rows ride ONE exchange per direction);
    - ``dispatch_ratio``: int8/f32 all_to_all bytes — rowwise (d+4)/4d,
      0.2539 at d_model=256, the <= 0.30 contract tests/test_a2a.py
      pins;
    - ``fliprate``: the round-16 flip-rate methodology applied to
      DISPATCH quantization — teacher-force one held-out corpus batch
      through the trained int8 model's sharded forward with f32 vs
      int8 dispatch (identical params, identical routing inputs at the
      first MoE layer) and count per-position argmax flips; routing
      disagreement anywhere downstream of the first MoE layer
      surfaces here.

    Needs an even device count >= 2 (the ep=2 expert axis); returns
    None (JSON nulls) otherwise."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributed_pytorch_tpu import lm as lm_mod
    from distributed_pytorch_tpu.data import lm_corpus
    from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.utils import debug as dbg
    from distributed_pytorch_tpu.utils.compat import shard_map

    n_dev = len(jax.devices())
    if n_dev < 2 or n_dev % 2:
        _log(f"[bench] moe-a2a A/B needs an even device count >= 2 "
             f"(have {n_dev}); omitting")
        return None
    batch = max(batch, n_dev)
    batch -= batch % n_dev  # shards over (data, expert)

    def build(bits: str) -> LMTrainer:
        model = tfm.TransformerConfig(
            vocab_size=256, d_model=256, n_layers=4, n_heads=4,
            head_dim=64, d_ff=512, n_experts=4,
            moe_dispatch_bits=bits)
        return LMTrainer(LMTrainConfig(model=model, dp=n_dev // 2,
                                       ep=2, compute_dtype=None))

    trainers = {"f32": build("f32"), "int8": build("int8")}
    data = lm_corpus.encode(lm_corpus.synthetic_corpus(1 << 18, seed=3))
    rng = np.random.default_rng(0)
    losses: dict[str, list[float]] = {k: [] for k in trainers}
    for _ in range(train_steps):
        idx = rng.integers(0, len(data) - seq - 1, batch)
        toks = np.stack([data[i:i + seq] for i in idx]).astype(np.int32)
        tgts = np.stack([data[i + 1:i + seq + 1]
                         for i in idx]).astype(np.int32)
        for k, tr in trainers.items():  # identical batches both sides
            losses[k].append(float(tr.train_step(toks, tgts)))

    def a2a_bytes(tr: LMTrainer) -> int:
        sched = dbg.op_schedule(tr.step_fn, tr.params, tr.opt_state,
                                jnp.asarray(toks), jnp.asarray(tgts))
        return int(sum(r["bytes"] for r in sched
                       if r["kind"] == "collective"
                       and r["prim"] == "all_to_all"))

    bytes_f32 = a2a_bytes(trainers["f32"])
    bytes_int8 = a2a_bytes(trainers["int8"])
    ratio = bytes_int8 / max(bytes_f32, 1)

    idx = rng.integers(0, len(data) - seq, batch)
    held = jnp.asarray(np.stack([data[i:i + seq]
                                 for i in idx]).astype(np.int32))
    tr8 = trainers["int8"]
    specs = lm_mod.param_specs(tr8.cfg)
    bspec = lm_mod._lm_batch_spec(tr8.cfg)

    def argmax_with(bits: str) -> np.ndarray:
        mcfg = dataclasses.replace(tr8.cfg.model, moe_dispatch_bits=bits)

        def local_fwd(params, tokens):
            return tfm.apply(params, tokens, cfg=mcfg,
                             tp_axis=lm_mod.MODEL, ep_axis=lm_mod.EXPERT)

        sm = shard_map(local_fwd, mesh=tr8.mesh,
                       in_specs=(specs, bspec), out_specs=P(*bspec, None))
        return np.asarray(jnp.argmax(jax.jit(sm)(tr8.params, held),
                                     axis=-1))

    ref = argmax_with("f32")
    q = argmax_with("int8")
    flips = int((ref != q).sum())
    total = int(ref.size)
    _log(f"[bench] moe-a2a A/B (ep=2, {n_dev} dev): "
         f"{bytes_int8} B/step int8 vs {bytes_f32} f32 -> "
         f"ratio {ratio:.4f}; flip rate {flips}/{total} = "
         f"{flips / total:.5f}; final loss f32 {losses['f32'][-1]:.4f} "
         f"vs int8 {losses['int8'][-1]:.4f}")
    return {"bytes_per_step": bytes_int8, "bytes_f32": bytes_f32,
            "dispatch_ratio": ratio, "fliprate": flips / total,
            "flips": flips, "positions": total,
            "loss_f32": losses["f32"][-1],
            "loss_int8": losses["int8"][-1]}


def canon_wan_env(value: str | None) -> bool:
    """Validate the BENCH_WAN knob (round 22): '1' runs the DiLoCo WAN
    leg (plain-mean vs outer-optimizer window boundaries at matched H,
    plus the chooser's predicted WAN bytes/optimizer-step vs the
    inspector's measured figure), unset/''/'0' skips it."""
    return _canon_bool_env(
        "BENCH_WAN", value, default=False,
        guess="whether to run the DiLoCo WAN outer-optimizer A/B")


def bench_wan_diloco(sync_every: int = 8, iters: int = 16,
                     reps: int = 5) -> dict | None:
    """DiLoCo WAN leg (round 22, BENCH_WAN=1): train the small byte-LM
    on a 2-slice factored ('dcn', 'data') mesh at window length
    ``sync_every`` TWICE from identical init — plain window-mean anchor
    update vs the Nesterov outer optimizer over the same averaged
    window delta — and report:

    - ``speedup``: plain/outer ms-per-step ratio at matched H (the
      outer step is one O(params) momentum update per WINDOW, so the
      expected figure is ~1.0x — the claim is "outer costs nothing on
      the wire", not "outer is faster");
    - ``bytes_per_opt_step``: the boundary exchange program's dcn-axis
      wire bytes amortized over the H optimizer steps it serves
      (schedule-inspector measured — outer momentum rides the anchor
      update, NOT the exchange, so this must equal the plain windowed
      figure);
    - ``bytes_per_opt_step_predicted``: the route chooser's amortized
      WAN-hop bytes/optimizer-step for the SAME parameter census on
      the synthetic ``ici_dcn_wan`` profile at ``max_sync_every=H``
      (the round-22 per-hop interval search — deterministic, gated
      ±2% by bench_compare like the measured figure);
    - ``plan``: the chooser's full routed plan summary (route,
      ``interval_by_hop``, ``outer_opt``) for the JSON record.

    Needs an even device count >= 2 (the 2-slice dcn axis); returns
    None (JSON nulls) otherwise.  On CPU meshes expect ~1.0x; the byte
    accounting and the plan are the content."""
    import jax

    from distributed_pytorch_tpu.data import lm_corpus
    from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.parallel import autotune
    from distributed_pytorch_tpu.utils import debug as dbg

    n_dev = len(jax.devices())
    if n_dev < 2 or n_dev % 2:
        _log(f"[bench] wan-diloco A/B needs an even device count >= 2 "
             f"(have {n_dev}); omitting")
        return None
    h = sync_every
    iters = -(-iters // h) * h  # whole windows only
    batch = max(8, n_dev)
    batch -= batch % n_dev

    def build(outer: bool) -> LMTrainer:
        model = tfm.TransformerConfig(
            vocab_size=256, d_model=128, n_layers=2, n_heads=4,
            head_dim=32, d_ff=256)
        return LMTrainer(LMTrainConfig(
            model=model, compute_dtype=None, dp=n_dev, dcn_size=2,
            sync_every=h, max_sync_every=h,
            outer_opt="nesterov" if outer else None,
            outer_momentum=0.9, outer_lr=1.0))

    trainers = {"plain": build(False), "outer": build(True)}
    data = lm_corpus.encode(lm_corpus.synthetic_corpus(1 << 16, seed=7))
    seq = 64
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(iters):
        idx = rng.integers(0, len(data) - seq - 1, batch)
        toks = np.stack([data[i:i + seq] for i in idx]).astype(np.int32)
        tgts = np.stack([data[i + 1:i + seq + 1]
                         for i in idx]).astype(np.int32)
        batches.append((toks, tgts))

    losses: dict[str, float] = {}
    for k, tr in trainers.items():  # warm: compile step + exchange
        for toks, tgts in batches:
            losses[k] = float(tr.train_step(toks, tgts))

    times: dict[str, list[float]] = {k: [] for k in trainers}
    for _ in range(reps):
        for k, tr in trainers.items():  # alternate: drift hits both
            t0 = time.perf_counter()
            for toks, tgts in batches:
                last = tr.train_step(toks, tgts)
            float(last)  # fetch forces the chain
            times[k].append((time.perf_counter() - t0) / iters * 1e3)
    med = {k: sorted(ts)[len(ts) // 2] for k, ts in times.items()}
    speedup = med["plain"] / max(med["outer"], 1e-9)

    # measured: the outer trainer's boundary exchange program, dcn wire
    # bytes amortized over the H optimizer steps each exchange serves
    tr = trainers["outer"]
    sched = dbg.op_schedule(tr._exchange_fn, tr.params, tr._delta,
                            tr._outer_m)
    measured = dbg.amortized_axis_bytes([(sched, 1)], h).get("dcn", 0.0)

    # predicted: the round-22 per-hop interval search over the same
    # census on the synthetic 3-tier WAN profile — its wan-hop row is
    # already amortized per optimizer step (price_route intervals)
    axes = {"wan": 2, "dcn": 2, "data": 2}
    profile = autotune.synthetic_profile("ici_dcn_wan", axes)
    census = autotune.grad_census(tr.params)
    plan = autotune.choose_sync_plan(census, profile, max_sync_every=h)
    predicted = sum(hp.predicted_bytes for hp in plan.per_hop
                    if hp.axis.startswith("wan:"))
    _log("[bench] " + plan.table().replace("\n", "\n[bench] "))
    _log(f"[bench] wan-diloco A/B (dcn_size=2, sync_every={h}, {n_dev} "
         f"dev): {med['outer']:.2f} ms/step outer vs {med['plain']:.2f} "
         f"plain-mean -> {speedup:.3f}x; dcn "
         f"{measured / 1e6:.3f} MB/opt-step measured, wan "
         f"{predicted / 1e6:.3f} MB/opt-step predicted "
         f"(plan outer_opt={plan.outer_opt}, intervals="
         f"{dict(plan.interval_by_hop)}); final loss plain "
         f"{losses['plain']:.4f} vs outer {losses['outer']:.4f} "
         f"({reps} reps median)")
    return {"speedup": speedup, "ms_outer": med["outer"],
            "ms_plain": med["plain"], "sync_every": h,
            "bytes_per_opt_step": measured,
            "bytes_per_opt_step_predicted": int(predicted),
            "plan": plan.summary(),
            "loss_plain": losses["plain"], "loss_outer": losses["outer"]}


def canon_telemetry_env(value: str | None) -> bool:
    """Validate the BENCH_TELEMETRY knob: '1' runs the round-13
    telemetry on/off A/B (CPU overhead of the unified event stream),
    unset/''/'0' skips it."""
    return _canon_bool_env(
        "BENCH_TELEMETRY", value, default=False,
        guess="whether to run the telemetry-overhead A/B")


def bench_train_telemetry(batch_per_replica: int = 64, iters: int = 30,
                          reps: int = 5) -> dict:
    """Telemetry-overhead gate (round 13, BENCH_TELEMETRY=1): the SAME
    trainer measured with the unified telemetry registry off (the
    default) and on (streaming JSONL to a throwaway run dir), >=
    ``reps`` alternating timed windows per mode with median-of-reps —
    the hardened-window discipline of the other gates.  The compiled
    program is IDENTICAL in both modes (the per-step scalars ride the
    in-scan health-flag output; test-pinned), so the delta is pure
    host-side cost: the registry reads, the JSONL appends, and the
    per-dispatch metric fetch.  The acceptance bound is <= 2% CPU step
    overhead (``telemetry_overhead_pct`` in the JSON)."""
    import tempfile

    import jax

    from distributed_pytorch_tpu.parallel.mesh import make_mesh
    from distributed_pytorch_tpu.train import TrainConfig, Trainer
    from distributed_pytorch_tpu.utils import telemetry

    n_dev = len(jax.devices())
    cfg = TrainConfig(strategy="ddp" if n_dev > 1 else "none",
                      batch_size=batch_per_replica,
                      steps_per_loop=iters, compute_dtype="bfloat16")
    tr = Trainer(cfg, mesh=make_mesh(n_dev) if n_dev > 1 else None)
    rng = np.random.default_rng(0)
    global_batch = batch_per_replica * n_dev
    images = rng.integers(
        0, 256, (iters, global_batch, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, (iters, global_batch)).astype(np.int32)
    if tr.mesh is None:
        images, labels = jax.device_put((images, labels))

    tr.precompile_steps(images, labels)
    float(tr.train_steps(images, labels)[-1])  # warm outside timed reps

    run_dir = tempfile.mkdtemp(prefix="bench_telemetry_")
    times: dict[bool, list[float]] = {False: [], True: []}
    try:
        for _ in range(reps):
            for on in (False, True):  # alternate: drift hits both modes
                if on:
                    telemetry.enable(run_dir)
                t0 = time.perf_counter()
                losses = tr.train_steps(images, labels)
                float(losses[-1])  # fetch forces the whole donated chain
                times[on].append((time.perf_counter() - t0) / iters * 1e3)
                if on:
                    telemetry.disable()
    finally:
        telemetry.disable()
    med = {m: sorted(ts)[len(ts) // 2] for m, ts in times.items()}
    overhead_pct = (med[True] / max(med[False], 1e-9) - 1.0) * 100.0
    n_records = sum(
        1 for _, recs in telemetry.read_run(run_dir) for _ in recs)
    _log(f"[bench] telemetry A/B ({cfg.strategy}, VGG-11, {n_dev} dev): "
         f"{med[True]:.2f} ms/step on vs {med[False]:.2f} off -> "
         f"{overhead_pct:+.2f}% ({n_records} records, {reps} reps "
         f"median)")
    return {"overhead_pct": overhead_pct, "ms_on": med[True],
            "ms_off": med[False], "records": n_records}


def canon_elastic_env(value: str | None) -> bool:
    """Validate the BENCH_ELASTIC knob: '1' runs the round-12 elastic
    shrink->reshard->grow recovery gate, unset/''/'0' skips it."""
    return _canon_bool_env(
        "BENCH_ELASTIC", value, default=False,
        guess="whether to run the elastic-recovery gate")


def bench_elastic(steps: int = 2, seq: int = 128, batch: int = 8) -> dict:
    """Elastic-resize recovery gate (round 12, BENCH_ELASTIC=1): measure
    the detect->resume gap a gang pays when it loses a member — the
    in-process leg (mesh rebuild + cross-topology ``load_resharded`` +
    one proving step at the smaller size), which is everything except
    the re-rendezvous the launcher layer adds on top.

    Shrink-and-grow on the bench LM config: train ``steps`` at the full
    fleet (ZeRO-3 so the reshard is real — params/Adam state change
    layout with the world size), checkpoint SHARDED, then time
    ``rebuild(dp=half)`` + ``load_resharded`` + one step; then grow back
    to the full fleet the same way.  Returns the recovery wall ms and
    the resize-event count (shrink + grow = 2) for the JSON keys
    ``elastic_recovery_ms`` / ``elastic_resize_events``."""
    import tempfile

    import jax

    from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer
    from distributed_pytorch_tpu.parallel import elastic as el
    from distributed_pytorch_tpu.utils.checkpoint import ShardedCheckpointer

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            f"elastic gate needs >= 2 devices (have {n_dev}): a 1-chip "
            f"fleet has no smaller world size to reshard onto")
    dp = n_dev if n_dev % 2 == 0 else n_dev - 1
    half = dp // 2
    cfg = LMTrainConfig(model=_lm_cfg(), dp=dp, fsdp=True,
                        compute_dtype="bfloat16")
    tr = LMTrainer(cfg)
    rng = np.random.default_rng(0)

    def lm_batch():
        t = rng.integers(0, 256, (batch, seq)).astype(np.int32)
        return t, np.roll(t, -1, 1)

    for _ in range(steps):
        float(tr.train_step(*lm_batch()))
    ckpt_dir = tempfile.mkdtemp(prefix="bench_elastic_")
    ck = ShardedCheckpointer(ckpt_dir)
    ck.save({"params": tr.params, "opt": tr.opt_state}, tr._step)
    events = 0
    # SHRINK: rebuild at half the fleet + reshard-restore + prove a step
    t0 = time.perf_counter()
    start = el.reshard_from_checkpoint(tr, ckpt_dir, dp=half,
                                       fsdp=half > 1)
    loss = float(tr.train_step(*lm_batch()))
    recovery_ms = (time.perf_counter() - t0) * 1e3
    events += 1
    assert start == steps and np.isfinite(loss), (start, loss)
    # GROW back to the full fleet through the same machinery
    ck.save({"params": tr.params, "opt": tr.opt_state}, tr._step)
    el.reshard_from_checkpoint(tr, ckpt_dir, dp=dp, fsdp=True)
    float(tr.train_step(*lm_batch()))
    events += 1
    _log(f"[bench] elastic gate: {dp}->{half}->{dp} devices, recovery "
         f"(rebuild + load_resharded + 1 step) {recovery_ms:.0f} ms, "
         f"{events} resize events, reshard stats "
         f"{getattr(tr._ckptr, 'last_reshard_stats', None)}")
    return {"recovery_ms": recovery_ms, "resize_events": events}


def _lm_cfg():
    """The BASELINE.md LM measurement config: byte-vocab d512/4L
    transformer, flash attention, bf16."""
    from distributed_pytorch_tpu.models import transformer as tfm
    return tfm.TransformerConfig(vocab_size=256, d_model=512, n_layers=4,
                                 n_heads=4, head_dim=128)


def lm_train_flops_per_token(cfg, n_params: int, seq: int) -> float:
    """Conservative analytic train FLOPs/token: the standard 6*P plus the
    causal attention matmuls (2 matmuls x 2 FLOPs x 3 for fwd+bwd x S/2
    visible positions = 6*S*H*Dh per layer); flash's backward recompute
    is NOT counted, so the MFU reported is a lower bound."""
    return 6.0 * n_params + 6.0 * seq * cfg.n_layers * cfg.n_heads * cfg.head_dim


def _bench_lm_at(model_cfg, label: str, iters: int, batch: int,
                 seq: int) -> tuple[float, float]:
    """Shared LM train-step measurement (ONE methodology for every LM
    gate): per-step dispatch (async dispatch hides the host), one value
    fetch at the end, min-of-2 windows.  Un-synced dispatches of multi-GB
    donated state queue without growing host memory or stalling on the
    v5e machine (PR 21: 24 steps of the 535M config enqueue in 0.06 s
    with the host's RSS flat), so no gate fetches the loss per step."""
    import jax

    from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer

    cfg = LMTrainConfig(model=model_cfg)
    tr = LMTrainer(cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, (batch, seq)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)

    float(tr.train_step(toks, tgts))  # compile + warm
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = tr.train_step(toks, tgts)
        float(loss)
        best = min(best, time.perf_counter() - t0)
    tps = batch * seq * iters / best
    n_params = sum(x.size for x in jax.tree.leaves(tr.params))
    peak = peak_bf16_flops(jax.devices()[0])
    mfu = tps * lm_train_flops_per_token(cfg.model, n_params, seq) / peak
    _log(f"[bench] {label} ({n_params / 1e6:.0f}M): "
         f"{best / iters * 1e3:.2f} ms/step -> {tps:,.0f} tok/s/chip"
         f", MFU>={mfu:.1%}")
    return tps, mfu


def bench_lm(iters: int = 40, batch: int = 8,
             seq: int = 2048) -> tuple[float, float | None]:
    """(tokens/sec/chip, MFU lower bound) of the LM train step — the
    transformer half of the framework, regression-gated since round 4
    (VERDICT round-3 #3)."""
    return _bench_lm_at(_lm_cfg(), "lm", iters, batch, seq)


def _lm_large_cfg():
    """The ~535M config (d2048/8L) the round-4 speculation study used —
    the weight-bandwidth-bound regime where MXU utilization is the
    honest question (the d512/4L gate is partly overhead-bound)."""
    from distributed_pytorch_tpu.models import transformer as tfm
    return tfm.TransformerConfig(vocab_size=256, d_model=2048, n_layers=8,
                                 n_heads=16, head_dim=128)


def bench_lm_large(iters: int = 12, batch: int = 4,
                   seq: int = 2048) -> tuple[float, float | None]:
    """(tokens/sec/chip, MFU lower bound) of the LM train step at the
    535M d2048/8L config (round-4 VERDICT #6: gate MFU where the model
    is large enough for the question to be about the MXU, not per-op
    overhead).  Same methodology as bench_lm (shared _bench_lm_at)."""
    return _bench_lm_at(_lm_large_cfg(), "lm-large", iters, batch, seq)


def canon_loss_impl_env(value: str | None) -> str | None:
    """Validate BENCH_LOSS_IMPL (round 17): unset/'' skips the
    activation-memory gate's loss leg (the default); 'dense' / 'chunked'
    selects which head the gate measures.  Fails loudly pre-bench like
    BENCH_KV_DTYPE."""
    if value is None or value == "":
        return None
    if value in ("dense", "chunked"):
        return value
    raise ValueError(
        f"BENCH_LOSS_IMPL must be ''/'dense'/'chunked', got {value!r}")


def canon_remat_env(value: str | None) -> str | None:
    """Validate BENCH_REMAT (round 17): unset/'' skips the gate's remat
    leg; 'none' / 'full' / 'selective' selects the layer-stack
    checkpointing the gate measures.  Fails loudly pre-bench like
    BENCH_KV_DTYPE."""
    if value is None or value == "":
        return None
    if value in ("none", "full", "selective"):
        return value
    raise ValueError(
        f"BENCH_REMAT must be ''/'none'/'full'/'selective', got {value!r}")


def bench_lm_memory(loss_impl: str | None, remat: str | None,
                    iters: int = 10, batch: int = 4,
                    seq: int = 512, reps: int = 3) -> dict | None:
    """Activation-memory gate (round 17, BENCH_LOSS_IMPL /
    BENCH_REMAT): A/B the requested (loss_impl, remat) LM step against
    the stock (dense, none) step — same model, same data, alternating
    timed windows, median-of-reps — and put the accountant's numbers
    next to the measured ones:

    - ``peak_activation_bytes``: utils.memacct's census-verified
      prediction of the variant's saved-residual footprint;
    - ``remat_saved_bytes``: bytes the remat knob shaves off the
      no-remat footprint at the same head (0 when remat is 'none');
    - ``step_overhead_pct``: the measured recompute price, (variant -
      baseline)/baseline ms/step — what the memory chooser's
      ``recompute_s_per_byte`` term is supposed to predict.

    Both steps train the SAME losses to ~1e-6 (chunked) or bitwise
    (remat; test-pinned), so the overhead is pure schedule + recompute.
    """
    from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer
    from distributed_pytorch_tpu.utils import memacct

    li = loss_impl or "dense"
    rm = remat or "none"
    model = _lm_cfg()

    def build(li_: str, rm_: str) -> LMTrainer:
        return LMTrainer(LMTrainConfig(model=model, loss_impl=li_,
                                       remat=rm_))

    trainers = {"base": build("dense", "none"), "var": build(li, rm)}
    rng = np.random.default_rng(0)
    toks = rng.integers(0, model.vocab_size, (batch, seq)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)
    for tr in trainers.values():
        float(tr.train_step(toks, tgts))  # compile + warm
    times: dict[str, list[float]] = {"base": [], "var": []}
    for _ in range(reps):
        for mode, tr in trainers.items():  # alternate: drift hits both
            t0 = time.perf_counter()
            for _ in range(iters):
                loss = tr.train_step(toks, tgts)
            float(loss)
            times[mode].append((time.perf_counter() - t0) / iters * 1e3)
    med = {m: sorted(ts)[len(ts) // 2] for m, ts in times.items()}
    overhead = (med["var"] - med["base"]) / max(med["base"], 1e-9) * 100.0
    # dp defaults to 1 here, so the whole batch is the per-device batch
    peak = memacct.predict_activation_bytes(
        model, batch=batch, seq=seq, remat=rm, loss_impl=li)
    saved = memacct.predict_activation_bytes(
        model, batch=batch, seq=seq, remat="none", loss_impl=li) - peak
    _log(f"[bench] lm-memory gate (loss_impl={li}, remat={rm}): "
         f"{med['var']:.2f} ms/step vs {med['base']:.2f} dense/none "
         f"({overhead:+.1f}%), predicted peak {peak / 1e6:.2f} MB, "
         f"remat saves {saved / 1e6:.2f} MB")
    return {"loss_impl": li, "remat": rm,
            "peak_activation_bytes": int(peak),
            "remat_saved_bytes": int(saved),
            "step_overhead_pct": overhead,
            "ms_variant": med["var"], "ms_base": med["base"]}


def bench_decode(max_new: int = 4096, base: int = 256,
                 reps: int = 5,
                 kv_dtype: str | None = None
                 ) -> tuple[float, float, int]:
    """(p50, p95, est. KV bytes/step) ms per decode step (B=2, prompt 64,
    bf16, Pallas decode kernel) — the BASELINE.md warm-decode config,
    HARDENED (round 6, VERDICT r5 #1).  ``kv_dtype="int8"`` runs the
    quantized KV cache (per-row scales, in-kernel dequant) — decode is
    HBM-bound on cache reads, so the third return value is the analytic
    per-step cache-read estimate (B x kv_bytes_per_token x mean attended
    length over the differenced window) the JSON carries: the knob's
    predicted effect, next to its measured one.  The old window divided
    ONE wall-clock (prefill scan included), ended by a full-output fetch,
    by ``max_new``.  Now:

    - PAIRED WINDOWS: each rep times ``generate`` at ``max_new`` and at a
      short ``base`` window; ms/token = (T_long - T_base)/(max_new -
      base).  The difference cancels the prefill scan (the old
      denominator bug: prefill time was divided across max_new) and the
      mean fetch RTT common to both windows;
    - each window ends on a ONE-ELEMENT device fetch of the final token
      (``gen.force_fetch_last``), not a full-output host transfer —
      constant fetch payload;
    - >=5 reps, median-of-reps headline, p95 alongside so drift can
      never hide a move again (gate: p95 within 15% of p50).
    """
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu import generate as gen
    from distributed_pytorch_tpu.models import transformer as tfm

    cfg = _lm_cfg()
    params = tfm.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 256, (2, 64)).astype(np.int32))

    def run(n):
        out = gen.generate(params, prompt, jax.random.key(1), cfg=cfg,
                           max_new=n, temperature=0.0,
                           dtype=jnp.bfloat16, decode_kernel=True,
                           kv_dtype=kv_dtype)
        return gen.force_fetch_last(out)

    run(base)
    run(max_new)  # compile + warm both windows
    ds = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run(base)
        t_base = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(max_new)
        t_long = time.perf_counter() - t0
        ds.append((t_long - t_base) / (max_new - base) * 1e3)
    ds.sort()
    p50 = ds[len(ds) // 2]
    p95 = ds[min(len(ds) - 1, int(len(ds) * 0.95))]
    # per-step cache-read estimate over the differenced (base, max_new]
    # steps: the mean attended length times bytes per cached token
    mean_len = prompt.shape[1] + (base + max_new) // 2
    kv_bytes = int(prompt.shape[0] * mean_len * gen.kv_bytes_per_token(
        cfg, dtype=jnp.bfloat16, kv_dtype=kv_dtype))
    _log(f"[bench] decode: {p50:.4f} ms/token p50, {p95:.4f} p95 "
         f"({reps} paired reps of {max_new}-vs-{base} new, B=2, "
         f"kv={kv_dtype or 'bf16'}, ~{kv_bytes / 1e6:.1f} MB KV/step; "
         f"spread {(ds[-1] - ds[0]) / max(p50, 1e-9):.1%})")
    return p50, p95, kv_bytes


def bench_serving(reps: int = 5, kv_dtype: str | None = None) -> dict:
    """Serving throughput on the BASELINE.md workload (16 ragged requests
    over 4 slots, K=32, chunked prefill, in-block refill, longest_first),
    HARDENED (round 6): >=``reps`` warm timed passes per variant with
    median-of-reps and p50/p95 — the wall clock is host-bound and a
    one-chip machine shares its host's cores, so one-shot numbers are
    unreadable.  Measures overlap ON (the headline) and overlap OFF
    in the same session, sharing one set of compiled fns, so the
    overlapped-dispatch win is an A/B under identical conditions rather
    than a cross-round comparison.  Utilization is deterministic and
    overlap-invariant (totals are unchanged; emissions just arrive one
    step later)."""
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), "scripts"))
    import bench_serving as bs
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.serve import ContinuousBatcher

    cfg = tfm.TransformerConfig(vocab_size=4096, d_model=512, n_layers=4,
                                n_heads=8, head_dim=64, d_ff=2048)
    params = tfm.init(jax.random.key(0), cfg)
    prompts, budgets = bs.build_workload(16, 0)
    on_tpu = jax.default_backend() != "cpu"

    def make(overlap=True):
        return ContinuousBatcher(
            params, cfg, slots=4, max_len=1024, temperature=0.0,
            dtype=jnp.bfloat16 if on_tpu else None,
            prompt_buckets=(32, 128),
            steps_per_sync=32, prefill_chunk=32,
            schedule="longest_first", overlap=overlap,
            kv_dtype=kv_dtype)

    cold = make()
    bs.run(cold, prompts, budgets)

    def timed(overlap):
        mk = lambda: make(overlap)  # noqa: E731
        return [bs.run(bs.warm_clone(cold, mk), prompts, budgets)
                for _ in range(reps)]

    on = timed(True)
    off = timed(False)

    def stats(rs):
        ts = sorted(float(r["tok_per_s"]) for r in rs)
        n = len(ts)
        return (ts[n // 2], ts[min(n - 1, int(n * 0.95))], ts[0], ts[-1])

    p50_on, p95_on, lo_on, hi_on = stats(on)
    p50_off, _, _, _ = stats(off)
    util = float(on[0]["utilization"])
    eps = float(on[0]["emitted_per_slot_step"])
    _log(f"[bench] serving: {p50_on:.1f} tok/s p50 overlap on "
         f"(range {lo_on:.1f}-{hi_on:.1f}, {reps} reps), "
         f"{p50_off:.1f} off -> {p50_on / max(p50_off, 1e-9):.2f}x; "
         f"util {util:.1%}, emitted/slot-step {eps:.1%} "
         f"(16 req / 4 slots, LPT, kv={kv_dtype or 'default'})")
    return {"tok_per_s": p50_on, "tok_per_s_p95": p95_on,
            "tok_per_s_no_overlap": p50_off,
            "overlap_speedup": p50_on / max(p50_off, 1e-9),
            "utilization": util, "emitted_per_slot_step": eps}


def canon_fleet_env(value: str | None) -> bool:
    """Validate the BENCH_FLEET knob: '1' runs the round-14 serving-
    fleet gate (prefix-aware router over 2 replicas + a disaggregated
    prefill->decode handoff pass), unset/''/'0' skips it."""
    return _canon_bool_env(
        "BENCH_FLEET", value, default=False,
        guess="whether to run the serving-fleet gate")


def bench_serve_fleet(reps: int = 3, kv_dtype: str | None = None) -> dict:
    """Serving-fleet gate (round 14, BENCH_FLEET=1), two passes over the
    same compiled model (fns shared via ``warm_clone`` per replica):

    1. **routed throughput** — a 2-replica unified fleet serves a mixed
       workload (6 prompts sharing one full 512-token page + distinct
       tails, 6 short prompts) after a seed request registers the shared
       page on one replica, so the shared-prefix requests route
       prefix-aware while the short ones fall back to LPT.  Median
       tok/s over ``reps`` fresh fleets (hardened-window discipline) ->
       ``fleet_tokens_per_sec``; the measuring run's placement split ->
       ``fleet_prefix_hit_rate`` (routed_prefix / routed, seed
       included).
    2. **handoff cost** — a disaggregated fleet (replica 0 prefill,
       replica 1 decode) serves short requests, so EVERY request crosses
       pools as a paged-KV handoff; mean wall ms per handoff (export
       gather + admit) -> ``fleet_handoff_ms``."""
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), "scripts"))
    import bench_serving as bs
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu.fleet import make_fleet
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.serve import ContinuousBatcher

    cfg = tfm.TransformerConfig(vocab_size=4096, d_model=512, n_layers=4,
                                n_heads=8, head_dim=64, d_ff=2048)
    params = tfm.init(jax.random.key(0), cfg)
    on_tpu = jax.default_backend() != "cpu"

    def make():
        # no prefill_chunk: prefix_cache refuses to compose with chunked
        # admission (serve.py) — shared-prefix admits are already one
        # suffix-sized dispatch
        return ContinuousBatcher(
            params, cfg, slots=4, max_len=1024, temperature=0.0,
            dtype=jnp.bfloat16 if on_tpu else None,
            prompt_buckets=(32, 544), steps_per_sync=8,
            schedule="longest_first", paged=True, prefix_cache=True,
            kv_dtype=kv_dtype)

    rng = np.random.default_rng(0)
    shared = rng.integers(0, 4096, 512).astype(np.int32)  # one full page

    def tail(n):
        return np.concatenate(
            [shared, rng.integers(0, 4096, n).astype(np.int32)])

    prompts = ([tail(16 + 2 * i) for i in range(6)]
               + [rng.integers(0, 4096, 16 + 2 * i).astype(np.int32)
                  for i in range(6)])
    budgets = [24] * len(prompts)

    cold = make()
    bs.run(cold, [tail(16), prompts[6]], [8, 8])  # compile both buckets
    factory = lambda: bs.warm_clone(cold, make)  # noqa: E731

    runs = []
    for _ in range(reps):
        fleet = make_fleet(factory, 2)
        try:
            fleet.run([tail(8)], 8)  # seed: register the shared page
            runs.append(bs.run_fleet(fleet, prompts, budgets))
        finally:
            fleet.close()
    ts = sorted(r["tok_per_s"] for r in runs)
    p50 = ts[len(ts) // 2]
    hit_rate = runs[0]["prefix_hit_rate"]  # deterministic placement

    fleet = make_fleet(factory, 2, disaggregate=True)
    try:
        hand = bs.run_fleet(fleet, prompts[6:], budgets[6:])
    finally:
        fleet.close()
    _log(f"[bench] serving fleet: {p50:.1f} tok/s p50 routed over 2 "
         f"replicas ({reps} reps, range {ts[0]:.1f}-{ts[-1]:.1f}), "
         f"prefix hit rate {hit_rate:.1%}, disaggregated handoff "
         f"{hand['handoff_ms']:.1f} ms mean over {hand['handoffs']} "
         f"handoffs (kv={kv_dtype or 'default'})")
    return {"tok_per_s": p50, "prefix_hit_rate": hit_rate,
            "handoff_ms": hand["handoff_ms"],
            "handoffs": hand["handoffs"]}


def canon_fleet_transport_env(value: str | None) -> bool:
    """Validate the BENCH_FLEET_TRANSPORT knob: '1' runs the round-19
    multi-process transport gate (2 unix-socket daemons probed for RPC
    overhead + an in-process autoscaler pressure->spawn / idle->drain
    cycle), unset/''/'0' skips it."""
    return _canon_bool_env(
        "BENCH_FLEET_TRANSPORT", value, default=False,
        guess="whether to run the multi-process transport gate")


def bench_fleet_transport(probes: int = 50) -> dict:
    """Multi-process transport gate (round 19, BENCH_FLEET_TRANSPORT=1).

    1. **RPC overhead** — spawn a 2-daemon unix-socket fleet (small
       model; the daemons are forced to CPU since two processes cannot
       share one TPU) and serve a short workload through the crc-framed
       RPC, then probe ``heartbeat`` round-trips ->
       ``fleet_rpc_overhead_ms`` (median of ``probes``): the per-call
       socket+framing tax scripts/bench_compare.py gates.
    2. **autoscale reaction** — an in-process single-replica fleet under
       queue pressure: the ``FleetAutoscaler`` must spawn a second
       replica, then drain it back once idle ->
       ``fleet_autoscale_events`` (event count; the spawn->drain pair
       proves both directions) plus the measured reaction ticks for
       BASELINE.md."""
    import sys as _sys

    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), "scripts"))
    import bench_serving as bs
    import jax

    from distributed_pytorch_tpu.fleet import (BatcherReplica,
                                               FleetAutoscaler, FleetRouter,
                                               make_socket_fleet)
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.serve import ContinuousBatcher
    from distributed_pytorch_tpu.utils import compile_cache

    cfg_kw = dict(vocab_size=256, d_model=128, n_layers=2, n_heads=4,
                  head_dim=32, n_kv_heads=2, d_ff=256)
    batcher = dict(slots=2, max_len=512, temperature=0.0,
                   prompt_buckets=[32], steps_per_sync=4, paged=True)
    spec = {"cfg": cfg_kw, "seed": 0, "batcher": batcher}
    # the daemons run on the CPU whatever the parent holds (a chip
    # belongs to one process), and the result says so: daemon_platform.
    # Fresh processes do not see a code-set compile cache — hand it over
    daemon_platform = "cpu"
    env = {"JAX_PLATFORMS": daemon_platform,
           **compile_cache.child_env(min_compile_secs=0.5)}

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 255, size=int(s)).astype(np.int32)
               for s in rng.integers(5, 17, size=6)]
    budgets = [8] * len(prompts)

    fleet = make_socket_fleet(spec, 2, transport="unix", env=env)
    try:
        served = bs.run_fleet(fleet, prompts, budgets)
        overhead = bs.rpc_overhead_ms(fleet, probes=probes)
        reps = list(fleet.replicas.values())
        calls = sum(r.client.stats["calls"] for r in reps)
        retries = sum(r.client.stats["retries"] for r in reps)
    finally:
        fleet.close()

    # autoscale leg: in-process (reaction logic is transport-agnostic
    # and the socket leg above already priced the RPC edge)
    cfg = tfm.TransformerConfig(**cfg_kw)
    params = tfm.init(jax.random.key(0), cfg)

    def make():
        return ContinuousBatcher(params, cfg,
                                 **{**batcher, "prompt_buckets": (32,)})

    router = FleetRouter([BatcherReplica(0, make)])
    sc = FleetAutoscaler(router, lambda: BatcherReplica(1, make),
                         min_replicas=1, max_replicas=2, grow_after=2,
                         shrink_after=3, queue_high=1)
    try:
        for p in prompts + prompts:
            router.submit(p, 8)
        for _ in range(600):
            router.step()
            sc.tick()
            if not router.pending() and sc.stats["drained"]:
                break
        while router.pending():
            router.step()
    finally:
        router.close()
    actions = [e["action"] for e in sc.events]
    if actions[:1] != ["spawn"] or "drain" not in actions:
        raise RuntimeError(
            f"autoscaler failed to complete a spawn->drain cycle under "
            f"queue pressure (events: {actions})")
    _log(f"[bench] fleet transport: rpc overhead {overhead:.3f} ms "
         f"median over {probes} probes ({calls} calls, {retries} "
         f"retries, {served['tok_per_s']:.1f} tok/s served by "
         f"{daemon_platform} daemons over unix sockets); autoscaler "
         f"{actions} in "
         f"{sc.stats['reaction_ticks']} reaction ticks")
    return {"rpc_overhead_ms": overhead, "rpc_calls": calls,
            "daemon_platform": daemon_platform,
            "rpc_retries": retries, "tok_per_s": served["tok_per_s"],
            "autoscale_events": len(sc.events),
            "autoscale_actions": actions,
            "autoscale_reaction_ticks": sc.stats["reaction_ticks"]}


# Reference-semantics torch-CPU throughput: fallback constant for when torch
# is unavailable, measured with the windowed metric below (BASELINE.md
# records the methodology and the live-host measurement).
FALLBACK_BASELINE_SPS = 89.4


def bench_torch_cpu(batch: int, window: int = 39) -> float:
    """Reference-equivalent torch CPU samples/sec, measured with the
    reference's OWN metric: per-iteration wall time, iteration 0 excluded as
    warm-up, averaged over a ``window``-iteration window.  The default 39
    reproduces the reference's first window exactly: iters 1..39 summed and
    divided by 39 (main.py:43-48 — 40 iterations with iter 0 excluded).

    The hot loop is the reference's single-process path rebuilt from its
    semantics (main.py:30-48): batch 256, VGG-11 with BN, CrossEntropyLoss,
    SGD(0.1, momentum 0.9, wd 1e-4), 4 CPU threads (main.py:16,18,103-104).
    """
    import torch
    import torch.nn as nn

    torch.manual_seed(1)
    torch.set_num_threads(4)  # reference main.py:16

    cfg = [64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"]
    layers: list[nn.Module] = []
    in_ch = 3
    for c in cfg:
        if c == "M":
            layers.append(nn.MaxPool2d(2, 2))
        else:
            layers += [nn.Conv2d(in_ch, c, 3, padding=1),
                       nn.BatchNorm2d(c), nn.ReLU(inplace=True)]
            in_ch = c
    model = nn.Sequential(*layers, nn.Flatten(), nn.Linear(512, 10))
    opt = torch.optim.SGD(model.parameters(), lr=0.1, momentum=0.9,
                          weight_decay=1e-4)
    criterion = nn.CrossEntropyLoss()
    x = torch.randn(batch, 3, 32, 32)
    y = torch.randint(0, 10, (batch,))

    def step():
        opt.zero_grad()
        loss = criterion(model(x), y)
        loss.backward()
        opt.step()

    step()  # iteration 0: excluded as warm-up (main.py:43-48)
    times = []
    for _ in range(window):
        t0 = time.perf_counter()
        step()
        times.append(time.perf_counter() - t0)
    mean_t = sum(times) / len(times)
    sps = batch / mean_t
    _log(f"[bench] torch-cpu baseline: {len(times)}-iter window "
         f"(iter 0 excluded) mean {mean_t:.3f}s/iter -> {sps:.1f} samples/s "
         f"(min {batch / max(times):.1f}, max {batch / min(times):.1f})")
    return sps


def main() -> None:
    from distributed_pytorch_tpu.utils import compile_cache
    compile_cache.enable()
    # KV-cache storage knob for the inference gates: unset = the
    # historical bf16 cache; BENCH_KV_DTYPE=int8 measures the quantized
    # cache (same hardened windows, so the win is a clean A/B).  A typo
    # must fail HERE, before any measurement — inside the benches it
    # would be swallowed by their catch-alls while the JSON stamps the
    # bogus value as the measured format.
    kv_dtype = os.environ.get("BENCH_KV_DTYPE") or None
    if kv_dtype is not None:
        from distributed_pytorch_tpu import generate as _gen
        _gen.canon_kv_dtype(kv_dtype)
    # Overlap A/B knob: validated pre-bench for the same reason (a typo'd
    # BENCH_OVERLAP must not silently skip or force the A/B).
    run_overlap = canon_overlap_env(os.environ.get("BENCH_OVERLAP"))
    # Factored-mesh DCN A/B knobs (round 9), validated loudly pre-bench:
    # BENCH_DCN_SIZE >= 2 runs the two-level hierarchical A/B on a
    # dcn_size-sliced mesh; BENCH_DCN_COMPRESS selects the slow-hop
    # format it measures.
    dcn_size = canon_dcn_size_env(os.environ.get("BENCH_DCN_SIZE"))
    dcn_compress = canon_dcn_compress_env(
        os.environ.get("BENCH_DCN_COMPRESS"))
    # Local-SGD window knob (round 18), validated loudly pre-bench:
    # BENCH_SYNC_EVERY=H >= 2 A/Bs sync_every=H windows against
    # per-step sync on the dcn_size=2 factored mesh.
    sync_every = canon_sync_every_env(os.environ.get("BENCH_SYNC_EVERY"))
    # Low-bit knobs (round 16), validated loudly pre-bench:
    # BENCH_FSDP_GATHER=int8 A/Bs the quantized ZeRO-3 weight gathers;
    # BENCH_MATMUL_DTYPE=int8 measures the int8-projection flip rate.
    fsdp_gather = canon_fsdp_gather_env(os.environ.get("BENCH_FSDP_GATHER"))
    matmul_dtype = canon_matmul_dtype_env(
        os.environ.get("BENCH_MATMUL_DTYPE"))
    # Activation-memory knobs (round 17), validated loudly pre-bench:
    # BENCH_LOSS_IMPL=chunked / BENCH_REMAT=full|selective A/B the
    # memory-thrifty LM step against the stock dense/no-remat one.
    mem_loss_impl = canon_loss_impl_env(os.environ.get("BENCH_LOSS_IMPL"))
    mem_remat = canon_remat_env(os.environ.get("BENCH_REMAT"))
    # Autotuner A/B knob (round 11), validated loudly pre-bench:
    # BENCH_AUTOTUNE=1 runs calibrate->choose->A/B vs the hand-picked
    # default and stamps the chosen plan into the JSON.
    run_autotune = canon_autotune_env(os.environ.get("BENCH_AUTOTUNE"))
    # Routed hop-graph knob (round 20), validated loudly pre-bench:
    # BENCH_ROUTE=1 runs choose-route -> RoutedSync trainer -> per-hop
    # byte accounting vs the hand-built hierarchical_int4 path.
    run_route = canon_route_env(os.environ.get("BENCH_ROUTE"))
    # Quantized MoE dispatch knob (round 21), validated loudly
    # pre-bench: BENCH_MOE_A2A=1 A/Bs f32 vs int8 expert all_to_all
    # dispatch (wire bytes + the round-16 flip-rate gate).
    run_moe_a2a = canon_moe_a2a_env(os.environ.get("BENCH_MOE_A2A"))
    # DiLoCo WAN knob (round 22), validated loudly pre-bench:
    # BENCH_WAN=1 A/Bs plain-mean vs outer-optimizer window boundaries
    # at matched H + predicted-vs-measured WAN bytes/optimizer-step.
    run_wan = canon_wan_env(os.environ.get("BENCH_WAN"))
    # Elastic-recovery knob (round 12), validated loudly pre-bench:
    # BENCH_ELASTIC=1 measures the shrink->reshard->grow recovery gap.
    run_elastic = canon_elastic_env(os.environ.get("BENCH_ELASTIC"))
    # Telemetry-overhead knob (round 13), validated loudly pre-bench:
    # BENCH_TELEMETRY=1 A/Bs the unified event stream on vs off.
    run_telemetry = canon_telemetry_env(os.environ.get("BENCH_TELEMETRY"))
    # Serving-fleet knob (round 14), validated loudly pre-bench:
    # BENCH_FLEET=1 runs the routed-throughput + disaggregated-handoff
    # passes over a 2-replica fleet.
    run_fleet = canon_fleet_env(os.environ.get("BENCH_FLEET"))
    # Multi-process transport knob (round 19), validated loudly
    # pre-bench: BENCH_FLEET_TRANSPORT=1 prices the socket RPC edge and
    # proves an autoscaler spawn->drain cycle.
    run_fleet_transport = canon_fleet_transport_env(
        os.environ.get("BENCH_FLEET_TRANSPORT"))
    batch = int(os.environ.get("BENCH_BATCH", "256"))
    # iters=300 keeps the single end-of-window fetch (~1.6 ms on the v5e
    # machine, PR 21) far under 1% of the window; warmup (steps) rounds to
    # whole windows, minimum one.
    warmup = int(os.environ.get("BENCH_WARMUP", "300"))
    iters = int(os.environ.get("BENCH_ITERS", "300"))

    sps_chip, mfu = bench_tpu(batch, warmup, iters)
    try:
        calib = calibrate_matmul_tflops()
    except Exception as e:  # tiny-memory devices etc. — control is optional
        _log(f"[bench] calibration failed ({e}); omitting")
        calib = None

    # Backward-overlap A/B (round 8): same strategy, collectives inside vs
    # after the backward; optional like the other gates (the VGG headline
    # must survive it failing).
    overlap_ab = None
    if run_overlap:
        try:
            overlap_ab = bench_train_overlap()
        except Exception as e:
            _log(f"[bench] train-overlap A/B failed ({e}); omitting")

    # Factored-mesh DCN A/B (round 9): streaming two-level sync on the
    # dcn_size-sliced mesh; optional like the other gates.
    dcn_ab = None
    if dcn_size:
        try:
            dcn_ab = bench_train_dcn(dcn_size, dcn_compress)
        except Exception as e:
            _log(f"[bench] train-dcn A/B failed ({e}); omitting")

    # Local-SGD window A/B (round 18): H local steps per DCN exchange
    # vs per-step sync on the factored mesh; optional like the other
    # gates.
    localsgd_ab = None
    if sync_every > 1:
        try:
            localsgd_ab = bench_train_localsgd(sync_every)
        except Exception as e:
            _log(f"[bench] train-localsgd A/B failed ({e}); omitting")

    # Quantized ZeRO-3 gather A/B (round 16): fsdp weight all-gathers
    # at int8 vs f32; optional like the other gates.
    q8gather_ab = None
    if fsdp_gather == "int8":
        try:
            q8gather_ab = bench_lm_q8_gather()
        except Exception as e:
            _log(f"[bench] lm-q8gather A/B failed ({e}); omitting")

    # int8-matmul flip-rate gate (round 16): quantized dense projections
    # vs the bf16 forward; optional like the other gates.
    int8mm = None
    if matmul_dtype == "int8":
        try:
            int8mm = bench_lm_int8_matmul()
        except Exception as e:
            _log(f"[bench] lm-int8matmul gate failed ({e}); omitting")

    # Activation-memory gate (round 17): the chunked-CE/remat LM step
    # vs dense/no-remat, with the accountant's predicted footprint next
    # to the measured overhead; optional like the other gates.
    mem_ab = None
    if mem_loss_impl is not None or mem_remat is not None:
        try:
            mem_ab = bench_lm_memory(mem_loss_impl, mem_remat)
        except Exception as e:
            _log(f"[bench] lm-memory gate failed ({e}); omitting")

    # Topology-aware autotuner A/B (round 11): calibrate the real
    # links, choose a plan, measure it against the hand-picked default;
    # optional like the other gates.
    autotune_ab = None
    if run_autotune:
        try:
            autotune_ab = bench_train_autotune()
        except Exception as e:
            _log(f"[bench] train-autotune A/B failed ({e}); omitting")

    # Routed hop-graph gate (round 20): chooser-picked route executed
    # by the RoutedSync trainer, per-hop wire bytes from the schedule
    # inspector; optional like the other gates.
    route_ab = None
    if run_route:
        try:
            route_ab = bench_train_routed()
        except Exception as e:
            _log(f"[bench] train-routed A/B failed ({e}); omitting")

    # Quantized MoE dispatch gate (round 21): f32 vs int8 expert
    # all_to_all wire bytes + the dispatch flip-rate; optional like
    # the other gates.
    moe_a2a_ab = None
    if run_moe_a2a:
        try:
            moe_a2a_ab = bench_moe_a2a()
        except Exception as e:
            _log(f"[bench] moe-a2a A/B failed ({e}); omitting")

    # DiLoCo WAN gate (round 22): outer-optimizer vs plain-mean window
    # boundaries + the chooser's predicted WAN bytes/optimizer-step vs
    # the inspector's measured figure; optional like the other gates.
    wan_ab = None
    if run_wan:
        try:
            wan_ab = bench_wan_diloco()
        except Exception as e:
            _log(f"[bench] wan-diloco A/B failed ({e}); omitting")

    # Elastic-recovery gate (round 12): shrink -> load_resharded -> grow
    # on the LM trainer; optional like the other gates.
    elastic_ab = None
    if run_elastic:
        try:
            elastic_ab = bench_elastic()
        except Exception as e:
            _log(f"[bench] elastic gate failed ({e}); omitting")

    # Telemetry-overhead gate (round 13): the unified event stream's
    # measured CPU step cost (same compiled program both sides);
    # optional like the other gates.
    telemetry_ab = None
    if run_telemetry:
        try:
            telemetry_ab = bench_train_telemetry()
        except Exception as e:
            _log(f"[bench] telemetry A/B failed ({e}); omitting")

    # Serving-fleet gate (round 14): routed throughput + prefix hit
    # rate + disaggregated handoff cost; optional like the other gates.
    fleet_ab = None
    if run_fleet:
        try:
            fleet_ab = bench_serve_fleet(kv_dtype=kv_dtype)
        except Exception as e:
            _log(f"[bench] serving-fleet gate failed ({e}); omitting")

    # Multi-process transport gate (round 19): socket-fleet RPC
    # overhead + autoscaler reaction; optional like the other gates.
    transport_ab = None
    if run_fleet_transport:
        try:
            transport_ab = bench_fleet_transport()
        except Exception as e:
            _log(f"[bench] fleet-transport gate failed ({e}); omitting")

    # Transformer-stack gates (VERDICT round-3 #3): the LM train step,
    # warm decode, and continuous-batching serving were previously only
    # recorded in BASELINE.md prose — a regression would have been
    # invisible to the driver.  Each is optional (the VGG headline must
    # survive any of them failing) and skippable for quick runs.
    lm_tps = lm_mfu = decode_ms = decode_p95 = serve = None
    lml_tps = lml_mfu = decode_kv_bytes = None
    if not os.environ.get("BENCH_SKIP_LM"):
        try:
            lm_tps, lm_mfu = bench_lm()
        except Exception as e:
            _log(f"[bench] lm bench failed ({e}); omitting")
        try:
            lml_tps, lml_mfu = bench_lm_large()
        except Exception as e:
            _log(f"[bench] lm-large bench failed ({e}); omitting")
        try:
            decode_ms, decode_p95, decode_kv_bytes = bench_decode(
                kv_dtype=kv_dtype)
        except Exception as e:
            _log(f"[bench] decode bench failed ({e}); omitting")
        try:
            serve = bench_serving(kv_dtype=kv_dtype)
        except Exception as e:
            _log(f"[bench] serving bench failed ({e}); omitting")

    if os.environ.get("BENCH_SKIP_TORCH"):
        baseline = FALLBACK_BASELINE_SPS
    else:
        try:
            baseline = bench_torch_cpu(
                batch, window=int(os.environ.get("BENCH_BASELINE_WINDOW",
                                                 "39")))
        except Exception as e:  # torch missing/broken: use recorded constant
            _log(f"[bench] torch baseline failed ({e}); using fallback")
            baseline = FALLBACK_BASELINE_SPS

    print(json.dumps({
        "metric": "cifar10_vgg11_train_samples_per_sec_per_chip",
        # provenance (round 15): who/what/when produced these numbers —
        # bench_compare.py gates regressions only within one platform
        "meta": bench_meta(),
        "value": round(sps_chip, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sps_chip / baseline, 3),
        "mfu": round(mfu, 4) if mfu is not None else None,
        # in-session device control: achieved TF/s on a fixed 4096^3 bf16
        # matmul chain — stable ±0.3%, so a genuine device/toolchain
        # change moves it while measurement noise does not (BASELINE.md)
        "calib_tflops": round(calib, 1) if calib is not None else None,
        # backward-overlapped gradient sync A/B (round 8): median ms/step
        # with the bucket collectives emitted inside vs after the backward
        # (bitwise-identical programs otherwise); null on 1-device hosts
        # or with BENCH_OVERLAP=0
        "train_overlap_speedup": (round(overlap_ab["speedup"], 3)
                                  if overlap_ab is not None else None),
        "train_step_ms_overlap": (round(overlap_ab["ms_overlap"], 3)
                                  if overlap_ab is not None else None),
        "train_step_ms_post_backward": (
            round(overlap_ab["ms_post_backward"], 3)
            if overlap_ab is not None else None),
        # factored-mesh DCN A/B (round 9, BENCH_DCN_SIZE): streaming
        # per-bucket two-level sync vs post-backward on the
        # Mesh(('dcn','ici')) virtual topology; dcn bytes are the
        # measured cross-slice payload (inspector, per-axis), and
        # train_dcn_compress records which slow-hop format ran
        # (BENCH_DCN_COMPRESS).  All null when the A/B is skipped.
        "train_dcn_overlap_speedup": (round(dcn_ab["speedup"], 3)
                                      if dcn_ab is not None else None),
        "train_dcn_bytes_per_step": (dcn_ab["dcn_bytes_per_step"]
                                     if dcn_ab is not None else None),
        "train_dcn_compress": ((dcn_compress or "none")
                               if dcn_ab is not None else None),
        # low-bit wire/compute gates (round 16): the int4 DCN payload
        # when BENCH_DCN_COMPRESS=int4 ran (~0.51x the int8 bytes:
        # nibble-packed chunks, full-width scale rows), the quantized
        # ZeRO-3 gather A/B (BENCH_FSDP_GATHER=int8), and the int8
        # dense-projection argmax flip rate vs the bf16 forward
        # (BENCH_MATMUL_DTYPE=int8).  All null when skipped.
        "train_dcn_int4_bytes_per_step": (
            dcn_ab["dcn_bytes_per_step"]
            if dcn_ab is not None and dcn_compress == "int4" else None),
        # local-SGD window A/B (round 18, BENCH_SYNC_EVERY=H): median
        # ms/step at sync_every=H vs the per-step path on the same
        # factored mesh, plus the inspector's amortized cross-slice
        # payload per step at interval H (~1/H of the per-step dcn
        # bytes, ici unchanged) and which H ran.  All null when the
        # A/B is skipped.
        "train_localsgd_speedup": (round(localsgd_ab["speedup"], 3)
                                   if localsgd_ab is not None else None),
        "train_dcn_bytes_per_step_windowed": (
            localsgd_ab["dcn_bytes_per_step_windowed"]
            if localsgd_ab is not None else None),
        "train_localsgd_sync_every": (localsgd_ab["sync_every"]
                                      if localsgd_ab is not None
                                      else None),
        "lm_q8_gather_speedup": (round(q8gather_ab["speedup"], 3)
                                 if q8gather_ab is not None else None),
        "lm_int8_matmul_fliprate": (round(int8mm["fliprate"], 5)
                                    if int8mm is not None else None),
        # activation-memory gate (round 17, BENCH_LOSS_IMPL/BENCH_REMAT):
        # the accountant's census-verified predicted peak for the
        # measured (loss_impl, remat) step, the bytes the remat knob
        # saves vs no-remat at the same head, and the measured recompute
        # price as a ms/step overhead vs the stock dense/none step.
        # All null when the gate is skipped.
        "lm_ce_peak_activation_bytes": (
            mem_ab["peak_activation_bytes"]
            if mem_ab is not None else None),
        "lm_remat_saved_bytes": (mem_ab["remat_saved_bytes"]
                                 if mem_ab is not None else None),
        "lm_remat_step_overhead_pct": (
            round(mem_ab["step_overhead_pct"], 3)
            if mem_ab is not None else None),
        # topology-aware autotuner A/B (round 11, BENCH_AUTOTUNE=1):
        # calibrated-link plan (strategy/bucket/compression + predicted
        # ms — the explainable decision) and its measured ms/step ratio
        # vs the hand-picked ddp default.  Null when skipped.
        "train_autotune_speedup": (round(autotune_ab["speedup"], 3)
                                   if autotune_ab is not None else None),
        "train_autotune_plan": (autotune_ab["plan"]
                                if autotune_ab is not None else None),
        # routed hop-graph leg (round 20, BENCH_ROUTE=1): the chooser's
        # routed plan (route string + per-hop cost rows), the measured
        # per-hop wire bytes of the executed program, their sum (the
        # deterministic number bench_compare gates), and the ms ratio
        # vs the hand-built hierarchical_int4 path.  Null when skipped.
        "train_routed_plan": (route_ab["plan"]
                              if route_ab is not None else None),
        "train_routed_bytes_by_hop": (route_ab["bytes_by_hop"]
                                      if route_ab is not None else None),
        "train_routed_bytes_per_step": (route_ab["bytes_per_step"]
                                        if route_ab is not None else None),
        "train_routed_speedup": (round(route_ab["speedup"], 3)
                                 if route_ab is not None else None),
        # quantized MoE dispatch leg (round 21, BENCH_MOE_A2A=1): the
        # int8-dispatch step program's per-step all_to_all wire bytes,
        # the int8/f32 wire ratio ((d+4)/4d rowwise incl. bitcast
        # scale rows — the <= 0.30 contract), and the round-16
        # flip-rate gate applied to dispatch quantization.  All null
        # when the A/B is skipped.
        "moe_a2a_bytes_per_step": (moe_a2a_ab["bytes_per_step"]
                                   if moe_a2a_ab is not None else None),
        "moe_a2a_dispatch_ratio": (round(moe_a2a_ab["dispatch_ratio"], 4)
                                   if moe_a2a_ab is not None else None),
        "moe_router_flip_rate": (round(moe_a2a_ab["fliprate"], 5)
                                 if moe_a2a_ab is not None else None),
        # DiLoCo WAN leg (round 22, BENCH_WAN=1): plain-mean vs outer-
        # optimizer window boundaries at matched H (~1.0x expected —
        # the outer step is off the wire), the boundary exchange's
        # measured dcn bytes amortized per optimizer step, the route
        # chooser's predicted WAN-hop bytes/optimizer-step on the
        # synthetic 3-tier profile (both deterministic accounting,
        # tight-banded in bench_compare), and the chooser's routed
        # plan.  All null when the A/B is skipped.
        "wan_diloco_speedup": (round(wan_ab["speedup"], 3)
                               if wan_ab is not None else None),
        "wan_diloco_bytes_per_opt_step": (wan_ab["bytes_per_opt_step"]
                                          if wan_ab is not None else None),
        "wan_bytes_per_opt_step_predicted": (
            wan_ab["bytes_per_opt_step_predicted"]
            if wan_ab is not None else None),
        "wan_diloco_plan": (wan_ab["plan"]
                            if wan_ab is not None else None),
        "wan_diloco_sync_every": (wan_ab["sync_every"]
                                  if wan_ab is not None else None),
        # elastic-recovery gate (round 12, BENCH_ELASTIC=1): wall-clock
        # of the in-process shrink recovery (mesh rebuild + cross-
        # topology load_resharded + one proving step at the smaller
        # world size — everything except the launcher's re-rendezvous)
        # and the resize events exercised (shrink + grow back = 2).
        # Null when the gate is skipped.
        "elastic_recovery_ms": (round(elastic_ab["recovery_ms"], 1)
                                if elastic_ab is not None else None),
        "elastic_resize_events": (elastic_ab["resize_events"]
                                  if elastic_ab is not None else None),
        # telemetry-overhead gate (round 13, BENCH_TELEMETRY=1): median
        # ms/step with the unified event stream on vs off (identical
        # compiled programs — the delta is host-side registry + JSONL
        # cost; acceptance bound <= 2%).  Null when the gate is skipped.
        "telemetry_overhead_pct": (round(telemetry_ab["overhead_pct"], 3)
                                   if telemetry_ab is not None else None),
        "train_step_ms_telemetry_on": (round(telemetry_ab["ms_on"], 3)
                                       if telemetry_ab is not None
                                       else None),
        "train_step_ms_telemetry_off": (round(telemetry_ab["ms_off"], 3)
                                        if telemetry_ab is not None
                                        else None),
        # transformer-stack gates (BASELINE.md is the prose companion;
        # these keys are the regression source of truth since round 4)
        "lm_tokens_per_sec_per_chip": (round(lm_tps, 1)
                                       if lm_tps is not None else None),
        "lm_mfu": round(lm_mfu, 4) if lm_mfu is not None else None,
        "lm_large_tokens_per_sec_per_chip": (round(lml_tps, 1)
                                             if lml_tps is not None
                                             else None),
        "lm_large_mfu": (round(lml_mfu, 4)
                         if lml_mfu is not None else None),
        # hardened decode gate (round 6): median of >=5 paired windows
        # ending on a 1-element fetch, prefill + RTT differenced out;
        # p95 alongside so drift is visible in the JSON itself
        "decode_ms_per_token": (round(decode_ms, 4)
                                if decode_ms is not None else None),
        "decode_ms_per_token_p95": (round(decode_p95, 4)
                                    if decode_p95 is not None else None),
        # KV-cache storage knob (BENCH_KV_DTYPE): which cache format the
        # inference gates above measured, plus the analytic cache-read
        # bytes one decode step costs at the bench shape — int8 should
        # roughly halve it vs bf16 (gen.kv_bytes_per_token)
        "kv_dtype": kv_dtype or "bf16",
        "decode_kv_bytes_per_step": decode_kv_bytes,
        # hardened serving gate (round 6): median-of-reps, overlap A/B
        # in-session (serving_overlap_speedup is the tentpole's win)
        "serving_tokens_per_sec": (round(serve["tok_per_s"], 1)
                                   if serve is not None else None),
        "serving_tokens_per_sec_p95": (round(serve["tok_per_s_p95"], 1)
                                       if serve is not None else None),
        "serving_tokens_per_sec_no_overlap": (
            round(serve["tok_per_s_no_overlap"], 1)
            if serve is not None else None),
        "serving_overlap_speedup": (round(serve["overlap_speedup"], 3)
                                    if serve is not None else None),
        "serving_slot_step_utilization": (round(serve["utilization"], 4)
                                          if serve is not None
                                          else None),
        # acceptance-adjusted utilization (VERDICT r5 weak #4): emitted
        # tokens per dispatched slot-step — the number that stays
        # meaningful under speculation, where raw utilization counts
        # rejected verify positions as dispatched work
        "serving_emitted_per_slot_step": (
            round(serve["emitted_per_slot_step"], 4)
            if serve is not None else None),
        # serving-fleet gate (round 14, BENCH_FLEET=1): median routed
        # tok/s over a 2-replica fleet, the measuring run's
        # prefix-aware placement rate (routed_prefix / routed), and the
        # mean wall ms one paged-KV handoff costs on the disaggregated
        # prefill->decode pass.  All null when the gate is skipped.
        "fleet_tokens_per_sec": (round(fleet_ab["tok_per_s"], 1)
                                 if fleet_ab is not None else None),
        "fleet_prefix_hit_rate": (round(fleet_ab["prefix_hit_rate"], 4)
                                  if fleet_ab is not None else None),
        "fleet_handoff_ms": (round(fleet_ab["handoff_ms"], 3)
                             if fleet_ab is not None else None),
        # multi-process transport gate (round 19,
        # BENCH_FLEET_TRANSPORT=1): median heartbeat round-trip over
        # the crc-framed unix-socket RPC (the per-call tax
        # bench_compare gates) and the autoscaler's completed event
        # count (a spawn->drain cycle = 2).  Null when skipped.
        "fleet_rpc_overhead_ms": (round(transport_ab["rpc_overhead_ms"], 4)
                                  if transport_ab is not None else None),
        "fleet_autoscale_events": (transport_ab["autoscale_events"]
                                   if transport_ab is not None else None),
        # where those daemons ran: not the device of "meta" above
        "fleet_daemon_platform": (transport_ab["daemon_platform"]
                                  if transport_ab is not None else None),
    }), flush=True)


if __name__ == "__main__":
    main()
