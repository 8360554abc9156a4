#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py             # one TPU chip: clock, vgg, lm, serve
    python3 chip_smoke.py --chips 4   # four chips: the sharded paths only

One process, which holds the chip for the whole run.  It reads
``jax.devices()`` first and exits non-zero unless they are TPUs (it never
sets a platform itself), then drives the main paths through the entry
points a user would call, at the full width of the models the repo trains
and serves, with data and weights made from ``--seed``:

- ``clock``  whether ``block_until_ready`` waits for the device, and what a
  one-element fetch costs: every later timing rests on it;
- ``vgg``    ``cli.main``: VGG-11, batch 256, bf16, ``ddp`` on the one-device
  mesh, one epoch of the synthetic CIFAR set plus the evaluation pass;
- ``lm``     ``lm_cli.main``: d2048 / 8 layers / 16 heads x 128 at sequence
  2048, batch 4, bf16, a few optimizer steps, then ``--generate``;
- ``serve``  ``serve.ContinuousBatcher`` at the same width, paged, bf16 KV
  and then int8 KV: every ragged request's stream against static greedy
  ``generate`` (equal, or parting at a near tie of the oracle's logits),
  and the decode kernels against the XLA path.

With ``--chips 4`` it runs VGG-11 ``ddp`` (against ``all_reduce``) over the
four-device mesh and the LM at ``--dp 2 --tp 2`` (against the one-device
run of the same seed and batch), shows that arrays, memory and collectives
are spread over four devices, and runs no other phase.

Any phase that raises or fails its check ends the run non-zero: nothing is
caught and skipped.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; every other
result (per-phase compile and run seconds, losses, ``peak_bytes_in_use``,
tokens emitted) is printed on earlier lines, each a ``[chip_smoke]`` JSON
object.
"""

from __future__ import annotations

import argparse
import gc
import io
import json
import math
import os
import re
import sys
import tempfile
import time

# The widths are the models' own and are never trimmed; step and request
# counts are what fits the smoke's time limit.
CLOCK = {"n": 4096, "iters": 64}   # 8.8 TFLOP: tens of ms on one chip
VGG = {"model": "VGG11", "batch": 256, "dtype": "bfloat16"}
LM = {"d_model": 2048, "n_layers": 8, "n_heads": 16, "head_dim": 128,
      "seq_len": 2048, "batch": 4, "steps": 12, "warmup": 4, "max_new": 32}
# (prompt length, token budget) per request: ragged, on four slots
SERVE = {"slots": 4, "max_len": 1024, "buckets": (32, 64),
         "requests": ((5, 12), (17, 8), (40, 10), (9, 16),
                      (17, 12), (5, 16), (40, 6), (9, 9))}
# bf16 carries 8 bits of mantissa (eps 2**-8 = 3.9e-3).  One attention
# output is an average of O(1) values through two bf16 dots: 2e-2 is ~5 eps.
# Logits sit behind 8 layers of that (sigma ~0.9 for a seeded random model),
# so they get 5x the room.  tests/test_attention.py's 2e-5 is the same
# comparison in float32 on the CPU.
ATTN_TOL = 2e-2
LOGIT_TOL = 1e-1
# bf16 losses of the same seed and global batch on 1 and on 2x2 devices:
# the first step is one forward pass (half an eps), the rest compound
# through the optimizer
MESH_FIRST_LOSS_RTOL = 2e-3
MESH_LOSS_RTOL = 5e-2


def report(**fields) -> None:
    print("[chip_smoke] " + json.dumps(fields), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class Tee(io.TextIOBase):
    """Standard output that also keeps what was written (the CLIs print
    their evaluation result and generated text; they do not return them)."""

    def __init__(self, out):
        self.out = out
        self.text: list[str] = []

    def write(self, s: str) -> int:
        self.text.append(s)
        return self.out.write(s)

    def flush(self) -> None:
        self.out.flush()

    def since(self, mark: int) -> str:
        return "".join(self.text[mark:])


def require_devices(chips: int):
    """The device check: TPUs, exactly as many as asked for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {len(devices)} "
            f"{devices[0].platform} device(s).  Nothing was run.")
    if len(devices) != chips:
        raise SystemExit(
            f"chip_smoke: asked for {chips} chip(s), JAX found "
            f"{len(devices)}.  Nothing was run.")
    return devices


def peak_bytes(device) -> int:
    """``peak_bytes_in_use`` as the runtime reports it; a runtime that does
    not report it fails the run (no summing of pytrees in its place)."""
    stats = device.memory_stats()
    check(bool(stats) and "peak_bytes_in_use" in stats,
          f"{device} reports no peak_bytes_in_use: {stats!r}")
    return int(stats["peak_bytes_in_use"])


def kernel_calls(compiled_text: str) -> int:
    """Pallas kernels in a compiled program: compiled ones are
    ``tpu_custom_call``s; interpreted ones and the XLA reference are not."""
    return compiled_text.count("tpu_custom_call")


class CompileClock:
    """Seconds this process spent obtaining executables (compiling, or
    loading from the persistent cache) and how many came from the cache."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        return self.secs, self.requests, self.hits


def run_phase(name: str, fn, clock: CompileClock, devices) -> None:
    secs0, req0, hit0 = clock.snapshot()
    t0 = time.perf_counter()
    details = fn()
    wall = time.perf_counter() - t0
    secs1, req1, hit1 = clock.snapshot()
    gc.collect()
    report(phase=name, wall_s=round(wall, 2),
           compile_s=round(secs1 - secs0, 2),
           run_s=round(wall - (secs1 - secs0), 2),
           cache_requests=req1 - req0, cache_hits=hit1 - hit0,
           peak_bytes_in_use=[peak_bytes(d) for d in devices], **details)


def step_losses(telemetry_dir: str) -> list[float]:
    """Per-step training losses of a CLI run, from its telemetry stream."""
    from distributed_pytorch_tpu.utils import telemetry

    telemetry.disable()  # the CLIs leave the registry on: flush and close
    by_step = {}
    for _, records in telemetry.read_run(telemetry_dir):
        for rec in records:
            if rec.get("type") == "gauge" and rec.get("name") == "loss":
                by_step[int(rec["args"]["step"])] = float(rec["value"])
    check(bool(by_step), f"no loss gauges under {telemetry_dir}")
    check(sorted(by_step) == list(range(len(by_step))),
          f"loss gauges skip steps: {sorted(by_step)}")
    return [by_step[s] for s in range(len(by_step))]


def all_finite(values) -> bool:
    return all(math.isfinite(v) for v in values)


class Recorded:
    """Keep the trainer a CLI builds: ``main`` returns an exit code, and the
    checks need the compiled step and the arrays' placement."""

    def __init__(self, module, attr: str):
        self.module, self.attr = module, attr
        self.made: list = []

    def __enter__(self):
        made, base = self.made, getattr(self.module, self.attr)
        self.base = base

        class Recording(base):
            def __init__(self, *args, **kwargs):
                super().__init__(*args, **kwargs)
                made.append(self)

        setattr(self.module, self.attr, Recording)
        return self

    def __exit__(self, *exc):
        setattr(self.module, self.attr, self.base)

    def one(self):
        check(len(self.made) == 1,
              f"expected one {self.attr}, the CLI built {len(self.made)}")
        return self.made[0]


def shard_devices(tree) -> set:
    import jax

    return {shard.device for leaf in jax.tree.leaves(tree)
            if isinstance(leaf, jax.Array)
            for shard in leaf.addressable_shards}


def as_struct(tree):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.Array) else x, tree)


# -- clock ----------------------------------------------------------------

def phase_clock() -> dict:
    """Does ``block_until_ready`` wait for the device?  A chain of matmuls
    is timed three ways: enqueue only, to ``block_until_ready``, and to a
    one-element value fetch.  If blocking returned before the work was
    done, it would read well under the fetch."""
    import jax
    import jax.numpy as jnp

    n, iters = CLOCK["n"], CLOCK["iters"]

    @jax.jit
    def chain(x):
        def body(c, _):
            return (c @ x) * (1.0 / n), None
        return jax.lax.scan(body, x, None, length=iters)[0]

    x = jnp.ones((n, n), jnp.bfloat16)
    jax.block_until_ready(chain(x))          # compile + warm

    def timed(end):
        best_enq = best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            y = chain(x)
            t1 = time.perf_counter()
            end(y)
            t2 = time.perf_counter()
            best_enq, best = min(best_enq, t1 - t0), min(best, t2 - t0)
        return best_enq, best

    enq, block = timed(jax.block_until_ready)
    _, fetch = timed(lambda y: float(y[0, 0]))
    ready = jax.block_until_ready(chain(x))
    t0 = time.perf_counter()
    for _ in range(20):
        float(ready[0, 0])
    fetch_ready = (time.perf_counter() - t0) / 20
    check(block >= 0.9 * (fetch - fetch_ready),
          f"block_until_ready returned in {block * 1e3:.2f} ms but the "
          f"value took {fetch * 1e3:.2f} ms: it does not wait for the device")
    return {"matmul": f"{iters} x {n}^3 bf16", "enqueue_ms": enq * 1e3,
            "block_until_ready_ms": block * 1e3, "value_fetch_ms": fetch * 1e3,
            "fetch_of_ready_value_ms": fetch_ready * 1e3}


# -- vgg ------------------------------------------------------------------

def run_vgg_cli(strategy: str, seed: int, scratch: str, *extra: str):
    """One epoch + evaluation through ``cli.main``; returns the per-step
    losses, what the run printed, and the trainer it built."""
    from distributed_pytorch_tpu import cli

    tel = os.path.join(scratch, f"tel_vgg_{strategy}")
    # an empty --data-dir: the loader looks nowhere else, finds no CIFAR
    # files and makes its synthetic set (the machine has no network)
    data = os.path.join(scratch, "no_cifar_here")
    os.makedirs(data, exist_ok=True)
    mark = len(sys.stdout.text)
    with Recorded(cli, "Trainer") as rec:
        rc = cli.main([
            "--model", VGG["model"], "--batch-size", str(VGG["batch"]),
            "--compute-dtype", VGG["dtype"], "--strategy", strategy,
            "--epochs", "1", "--seed", str(seed), "--data-dir", data,
            "--telemetry-dir", tel, *extra])
    check(rc == 0, f"cli.main returned {rc}")
    return step_losses(tel), sys.stdout.since(mark), rec.one()


def check_vgg_run(losses: list[float], printed: str) -> dict:
    check(all_finite(losses), f"non-finite VGG loss: {losses}")
    window = min(20, len(losses) // 2)
    first = sum(losses[:window]) / window
    last = sum(losses[-window:]) / window
    check(last < first, f"VGG loss did not fall: first window {first:.4f}, "
                        f"last window {last:.4f}")
    m = re.search(r"Test set: Average loss: ([0-9.naninf-]+), "
                  r"Accuracy: (\d+)/(\d+)", printed)
    check(m is not None, "the evaluation pass printed no 'Test set:' line")
    eval_loss = float(m.group(1))
    check(all_finite([eval_loss]), f"evaluation loss {eval_loss}")
    return {"steps": len(losses), "first_window_loss": first,
            "last_window_loss": last, "first_step_loss": losses[0],
            "eval_loss": eval_loss,
            "eval_accuracy": int(m.group(2)) / int(m.group(3))}


def phase_vgg(seed: int, scratch: str) -> dict:
    losses, printed, trainer = run_vgg_cli("ddp", seed, scratch)
    out = check_vgg_run(losses, printed)
    check(trainer.n_replicas == 1, "expected the one-device mesh")
    return {**VGG, **out}


# -- lm -------------------------------------------------------------------

def lm_argv(seed: int, tel: str, *extra: str) -> list[str]:
    return ["--d-model", str(LM["d_model"]), "--n-layers",
            str(LM["n_layers"]), "--n-heads", str(LM["n_heads"]),
            "--head-dim", str(LM["head_dim"]), "--seq-len",
            str(LM["seq_len"]), "--batch-size", str(LM["batch"]),
            "--compute-dtype", "bfloat16", "--steps", str(LM["steps"]),
            # without a warm-up Adam's first steps at 3e-4 throw this
            # width's loss from 5.9 to 9.3 before it comes back
            "--warmup-steps", str(LM["warmup"]),
            "--log-every", "1", "--seed", str(seed),
            "--telemetry-dir", tel, *extra]


def run_lm_cli(seed: int, scratch: str, tag: str, *extra: str):
    from distributed_pytorch_tpu import lm_cli

    tel = os.path.join(scratch, f"tel_lm_{tag}")
    mark = len(sys.stdout.text)
    with Recorded(lm_cli, "LMTrainer") as rec:
        rc = lm_cli.main(lm_argv(seed, tel, *extra))
    check(rc == 0, f"lm_cli.main returned {rc}")
    losses = step_losses(tel)
    check(len(losses) == LM["steps"] and all_finite(losses),
          f"LM losses: {losses}")
    return losses, sys.stdout.since(mark), rec.one()


def lm_step_text(trainer) -> str:
    """The compiled text of the trainer's own step program, lowered from
    shapes (nothing runs, no donated buffer is consumed)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    tokens = jax.ShapeDtypeStruct(
        (LM["batch"], LM["seq_len"]), jnp.int32,
        sharding=NamedSharding(trainer.mesh, trainer._batch_spec))
    return trainer.step_fn.lower(
        as_struct(trainer.params), as_struct(trainer.opt_state),
        tokens, tokens).compile().as_text()


def phase_lm(seed: int, scratch: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu import generate as gen
    from distributed_pytorch_tpu.data import lm_corpus

    prompt = "the "
    losses, printed, trainer = run_lm_cli(
        seed, scratch, "one", "--generate", prompt,
        "--max-new", str(LM["max_new"]), "--temperature", "0")
    check(losses[-1] < losses[0],
          f"LM loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")

    # the flash kernels are in the step program, compiled
    flash = kernel_calls(lm_step_text(trainer))
    # the forward and the fused backward kernel in each layer
    check(flash >= 2 * LM["n_layers"],
          f"{flash} tpu_custom_call in the LM step, expected "
          f">= {2 * LM['n_layers']}: the flash kernels are not compiled in")

    # --generate printed prompt + max_new bytes, decoded by the program
    # that holds the Pallas decode kernel (lowered here as the CLI calls it)
    n_prompt = len(lm_corpus.encode(prompt))
    n_bytes = n_prompt + LM["max_new"]
    tail = printed.rstrip("\n").encode("utf-8", "replace")
    check(prompt.encode() in tail and len(tail) >= n_bytes,
          "--generate did not print the prompt and its continuation")
    cfg = trainer.cfg
    decode = kernel_calls(gen.generate.lower(
        as_struct(trainer.params),
        jax.ShapeDtypeStruct((1, n_prompt), np.int32),
        jax.random.key(seed), cfg=cfg.model, max_new=LM["max_new"],
        temperature=0.0, top_k=None, top_p=None, dtype=cfg.dtype,
        kv_dtype=None).compile().as_text())
    check(decode >= LM["n_layers"],
          f"{decode} tpu_custom_call in generate, expected one decode "
          f"kernel per layer")
    del trainer
    return {**LM, "losses": losses, "flash_kernel_calls": flash,
            "decode_kernel_calls": decode, "generated_bytes": n_bytes,
            "compute_dtype": str(jnp.dtype(cfg.dtype))}


# -- serve ----------------------------------------------------------------

def check_decode_kernels(params, cfg, seed: int) -> dict:
    """The decode kernels against the XLA path, at the model's widths:
    the attention op alone (dense and paged, bf16 and int8 KV) on seeded
    random tensors, then one whole decode step's logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu import generate as gen
    from distributed_pytorch_tpu.ops import attention as att

    b, h, hkv, d = SERVE["slots"], cfg.n_heads, cfg.kv_heads, cfg.head_dim
    s, page = SERVE["max_len"], 512
    keys = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(keys[0], (b, h, 1, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, hkv, s, d), jnp.bfloat16)
    pos = jnp.asarray([3, 300, 511, s - 1], jnp.int32)[:b]

    def reference(kk, vv):
        rep = h // hkv
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, s), 3)
        bias = jnp.where(slot <= pos[:, None, None, None], 0.0, att.NEG_INF)
        with jax.default_matmul_precision("highest"):
            return att.attention_reference(
                q.astype(jnp.float32),
                jnp.repeat(kk.astype(jnp.float32), rep, axis=1),
                jnp.repeat(vv.astype(jnp.float32), rep, axis=1), bias=bias)

    def pool(x):  # dense (b, hkv, s, w) -> pages, page 0 the scratch page
        w = x.shape[-1]
        pages = (x.reshape(b, hkv, s // page, page, w)
                 .transpose(0, 2, 1, 3, 4).reshape(-1, hkv, page, w))
        return jnp.concatenate([jnp.zeros_like(pages[:1]), pages])

    table = jnp.arange(1, b * (s // page) + 1,
                       dtype=jnp.int32).reshape(b, s // page)
    kq, ks = gen.quantize_kv(k)
    vq, vs = gen.quantize_kv(v)
    errs = {}
    for name, got, want in (
        ("dense_bf16", att.decode_attention(q, k, v, pos), reference(k, v)),
        ("paged_bf16",
         att.decode_attention_paged(q, pool(k), pool(v), table, pos),
         reference(k, v)),
        ("dense_int8",
         att.decode_attention(q, kq, vq, pos, k_scale=ks, v_scale=vs),
         reference(gen.dequantize_kv(kq, ks), gen.dequantize_kv(vq, vs))),
        ("paged_int8",
         att.decode_attention_paged(q, pool(kq), pool(vq), table, pos,
                                    k_scale=pool(ks), v_scale=pool(vs)),
         reference(gen.dequantize_kv(kq, ks), gen.dequantize_kv(vq, vs))),
    ):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        check(err <= ATTN_TOL, f"{name} decode kernel is {err:.4f} from the "
                               f"XLA reference (tolerance {ATTN_TOL})")
        errs[f"attn_{name}_max_err"] = err

    # one decode step of the whole model after a short prefill, kernel
    # against the XLA path, for both cache formats
    prompt = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (b, 24)), jnp.int32)
    for kv in (None, "int8"):
        cache = gen.init_cache(cfg, b, s, dtype=jnp.bfloat16,
                               kv_dtype=kv)
        _, cache = gen._forward_cached(
            params, cache, prompt, jnp.arange(24), 0, cfg=cfg,
            dtype=jnp.bfloat16, unembed_last_only=True, k_len=24)
        token = prompt[:, -1]
        logits = {}
        for kernel in (True, False):
            step = jax.jit(lambda p, c, t, kernel=kernel: gen.decode_step(
                p, c, t, 24, cfg=cfg, dtype=jnp.bfloat16,
                use_decode_kernel=kernel)[0])
            if kernel:
                check(kernel_calls(step.lower(params, cache, token)
                                   .compile().as_text()) >= cfg.n_layers,
                      "the decode step holds no compiled decode kernel")
            logits[kernel] = step(params, cache, token)
        err = float(jnp.max(jnp.abs(logits[True] - logits[False])))
        check(err <= LOGIT_TOL,
              f"decode-step logits (kv {kv or 'bf16'}): kernel is {err:.4f} "
              f"from the XLA path (tolerance {LOGIT_TOL})")
        errs[f"logits_{kv or 'bf16'}_max_err"] = err
    return errs


def next_token_gap(params, cfg, context, a: int, b: int, kv_dtype) -> float:
    """|logit[a] - logit[b]| for the token after ``context``, on the
    oracle's path (a batched prefill through the cache)."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu import generate as gen

    n = len(context)
    cache = gen.init_cache(cfg, 1, gen.pad_cache_len(n), dtype=jnp.bfloat16,
                           kv_dtype=kv_dtype)
    logits, _ = gen._forward_cached(
        params, cache, jnp.asarray(context)[None], jnp.arange(n), 0, cfg=cfg,
        dtype=jnp.bfloat16, unembed_last_only=True, k_len=n)
    return float(jnp.abs(logits[0, 0, a] - logits[0, 0, b]))


def check_serving(params, cfg, seed: int, kv_dtype) -> dict:
    """Every ragged request's stream against static greedy ``generate`` on
    the same decode path (the kernel), token for token.

    The two runs are the same arithmetic in different shapes (four slots
    in lockstep and prompts teacher-forced inside the decode block, against
    one sequence and a batched prefill), which in bf16 can round a logit
    differently, and with int8 KV re-quantize a cache row.  So where a
    stream leaves the oracle's, the first differing token must be a near
    tie: the oracle's own logits for the two tokens within LOGIT_TOL, the
    room the kernel and the XLA path get above.  Anything else fails."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_pytorch_tpu import generate as gen
    from distributed_pytorch_tpu.serve import ContinuousBatcher

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n, _ in SERVE["requests"]]
    budgets = [m for _, m in SERVE["requests"]]
    batcher = ContinuousBatcher(
        params, cfg, slots=SERVE["slots"], max_len=SERVE["max_len"],
        temperature=0.0, dtype=jnp.bfloat16,
        prompt_buckets=SERVE["buckets"], paged=True, kv_dtype=kv_dtype,
        seed=seed)
    check(batcher.use_kernel, "the batcher is not on the decode kernel")
    rids = [batcher.submit(p, m) for p, m in zip(prompts, budgets)]
    while batcher.pending():
        batcher.step()
    # one oracle program per prompt length: greedy is causal, so the
    # longest budget's stream holds every shorter one as a prefix
    longest = max(budgets)
    near_ties = []
    for rid, prompt, budget in zip(rids, prompts, budgets):
        want = np.asarray(gen.generate(
            params, jnp.asarray(prompt)[None], jax.random.key(seed),
            cfg=cfg, max_new=longest, temperature=0.0, dtype=jnp.bfloat16,
            kv_dtype=kv_dtype))[0][:len(prompt) + budget]
        got = batcher.result(rid)
        check(len(got) == len(want) and
              np.array_equal(got[:len(prompt)], prompt),
              f"request {rid}: served {len(got)} tokens, wanted {len(want)}")
        if np.array_equal(got, want):
            continue
        at = int(np.argmax(got != want))
        gap = next_token_gap(params, cfg, want[:at], int(got[at]),
                             int(want[at]), kv_dtype)
        check(gap <= LOGIT_TOL,
              f"request {rid} (kv {kv_dtype or 'bf16'}, prompt "
              f"{len(prompt)}, budget {budget}) leaves static generate at "
              f"token {at - len(prompt)} where the oracle's logits are "
              f"{gap:.4f} apart (not a near tie):\n served "
              f"{got[len(prompt):].tolist()}\n oracle "
              f"{want[len(prompt):].tolist()}")
        near_ties.append({"request": rid, "token": at - len(prompt),
                          "logit_gap": gap})
    emitted = int(batcher.stats["emitted_tokens"])
    check(emitted == sum(budgets),
          f"emitted {emitted} tokens, budgets sum to {sum(budgets)}")
    return {"requests": len(rids),
            "exact_streams": len(rids) - len(near_ties),
            "near_tie_flips": near_ties, "tokens_emitted": emitted,
            "decode_dispatches": int(batcher.stats["decode_dispatches"])}


def phase_serve(seed: int) -> dict:
    import jax

    from distributed_pytorch_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=LM["d_model"], n_layers=LM["n_layers"],
        n_heads=LM["n_heads"], head_dim=LM["head_dim"])
    params = tfm.init(jax.random.key(seed), cfg)
    out = check_decode_kernels(params, cfg, seed)
    for kv in (None, "int8"):
        for key, val in check_serving(params, cfg, seed, kv).items():
            out[f"{kv or 'bf16'}_{key}"] = val
    return {"slots": SERVE["slots"], "paged": True, **out}


# -- four chips -----------------------------------------------------------

def check_spread(name: str, devices, params, batch, compiled_text: str,
                 collectives: tuple[str, ...]) -> dict:
    """The work is really on four devices: shards, memory, collectives."""
    n = len(devices)
    check(shard_devices(params) == set(devices),
          f"{name}: parameters have shards on "
          f"{len(shard_devices(params))} of {n} devices")
    check(shard_devices(batch) == set(devices),
          f"{name}: the batch has shards on "
          f"{len(shard_devices(batch))} of {n} devices")
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in devices]
    check(all(b > 0 for b in in_use),
          f"{name}: bytes_in_use per device {in_use}")
    found = {c: compiled_text.count(c) for c in collectives}
    check(all(found.values()), f"{name}: collectives in the compiled "
                               f"step: {found}")
    return {f"{name}_shard_devices": n, f"{name}_bytes_in_use": in_use,
            f"{name}_collectives": found}


def phase_multichip_vgg(seed: int, scratch: str, devices) -> dict:
    import numpy as np

    ddp, printed, trainer = run_vgg_cli("ddp", seed, scratch,
                                        "--debug-checks")
    out = check_vgg_run(ddp, printed)
    check("replica-consistency checks passed" in printed,
          "--debug-checks did not report passing")
    check(trainer.n_replicas == len(devices),
          f"ddp ran on {trainer.n_replicas} replicas")
    batch = trainer._stage(
        np.zeros((1, VGG["batch"] * len(devices), 32, 32, 3), np.uint8),
        np.zeros((1, VGG["batch"] * len(devices)), np.int32))
    text = "\n".join(exe.as_text() for exe in trainer._compiled.values())
    out.update(check_spread("vgg", devices, trainer.params, batch, text,
                            ("all-reduce",)))
    del trainer, batch
    gc.collect()
    # the comparison: the reference's per-tensor all_reduce, same mesh,
    # same seed — the first step's loss must agree to 0.1%
    ar, _, _ = run_vgg_cli("all_reduce", seed, scratch)
    rel = abs(ddp[0] - ar[0]) / abs(ar[0])
    check(rel <= 1e-3, f"first-step loss ddp {ddp[0]:.6f} vs all_reduce "
                       f"{ar[0]:.6f}: {rel:.2e} apart (limit 1e-3)")
    return {**VGG, "replicas": len(devices), **out,
            "all_reduce_first_step_loss": ar[0],
            "first_step_rel_diff": rel}


def phase_multichip_lm(seed: int, scratch: str, devices) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    sharded, _, trainer = run_lm_cli(seed, scratch, "dp2tp2",
                                     "--dp", "2", "--tp", "2")
    batch = jax.device_put(
        jnp.zeros((LM["batch"], LM["seq_len"]), jnp.int32),
        NamedSharding(trainer.mesh, trainer._batch_spec))
    text = lm_step_text(trainer)
    check(kernel_calls(text) >= 2 * LM["n_layers"],
          "the dp2 x tp2 step lost its flash kernels")
    out = check_spread("lm", devices, trainer.params, batch, text,
                       ("all-reduce",))
    del trainer, batch
    gc.collect()
    # the comparison: one device, same seed, same global batch
    single, _, _ = run_lm_cli(seed, scratch, "one")
    rel = [abs(a - b) / abs(b) for a, b in zip(sharded, single)]
    check(rel[0] <= MESH_FIRST_LOSS_RTOL and max(rel) <= MESH_LOSS_RTOL,
          f"loss trajectory dp2 x tp2 {sharded} vs one device {single}: "
          f"{rel[0]:.2e} apart at the first step (limit "
          f"{MESH_FIRST_LOSS_RTOL}), {max(rel):.2e} at the worst (limit "
          f"{MESH_LOSS_RTOL})")
    check(sharded[-1] < sharded[0], "the dp2 x tp2 loss did not fall")
    return {**LM, "mesh": "dp2 x tp2", **out, "losses": sharded,
            "one_device_losses": single, "first_step_rel_diff": rel[0],
            "max_rel_diff": max(rel)}


# -- main -----------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): the one-chip phases.  4: the "
                         "sharded paths over all four chips, and nothing "
                         "else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_devices(args.chips)

    from distributed_pytorch_tpu.utils import compile_cache
    cache = compile_cache.enable()
    clock = CompileClock()
    real_stdout = sys.stdout
    sys.stdout = Tee(real_stdout)
    try:
        report(device=str(devices[0]), kind=devices[0].device_kind,
               count=len(devices), compile_cache=cache,
               cache_entries_at_start=(len(os.listdir(cache))
                                       if os.path.isdir(cache) else 0))
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as scratch:
            if args.chips == 1:
                phases = (
                    ("clock", phase_clock),
                    ("vgg", lambda: phase_vgg(args.seed, scratch)),
                    ("lm", lambda: phase_lm(args.seed, scratch)),
                    ("serve", lambda: phase_serve(args.seed)),
                )
            else:
                phases = (
                    ("multichip_vgg", lambda: phase_multichip_vgg(
                        args.seed, scratch, devices)),
                    ("multichip_lm", lambda: phase_multichip_lm(
                        args.seed, scratch, devices)),
                )
            for name, fn in phases:
                run_phase(name, fn, clock, devices)
    finally:
        sys.stdout = real_stdout
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
