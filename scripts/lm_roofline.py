"""LM train-step roofline decomposition (round-4 VERDICT #6).

Gives the LM step the VGG-grade treatment (ROADMAP.md MFU accounting):
measure the full step, then its pieces — forward, forward+backward,
optimizer — and microbench the four matmul families (attention,
QKV/O projections, SwiGLU FFN, embed/unembed+CE) at the exact training
shapes, each as fwd+bwd.  The gap between the summed matmul time and
the measured fwd+bwd is the elementwise/HBM remainder (norms,
residual adds, rotary, remat traffic); opt is the f32 optimizer HBM
pass.  Achieved TF/s per family vs the chip's bf16 peak says which op
(if any) is a lever.

All timings per-step-dispatch loops with ONE value fetch at the end and
min-of-2 windows (the bench.py methodology).

Run (TPU):  PYTHONPATH=. python scripts/lm_roofline.py [--model large]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import peak_bf16_flops
from distributed_pytorch_tpu.lm import (
    LMTrainConfig, LMTrainer, make_optimizer)
from distributed_pytorch_tpu.models import transformer as tfm
from distributed_pytorch_tpu.ops.attention import flash_attention
from distributed_pytorch_tpu.ops.nn import masked_ce

MODELS = {
    "small": dict(d_model=512, n_layers=4, n_heads=4, head_dim=128,
                  batch=8),
    "large": dict(d_model=2048, n_layers=8, n_heads=16, head_dim=128,
                  batch=4),
}


def timed(run, fetch, iters: int) -> float:
    """ms per call: ``run`` dispatches once (async), ``fetch(out)``
    forces the final value; min-of-2 windows of ``iters`` calls."""
    fetch(run())  # compile + warm
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = run()
        fetch(out)
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3


def timed_scan(body, carry, inner: int, fetch, carry_fn=None,
               target_ms: float = 2500.0) -> float:
    """ms per INNER iteration of a dependency-chained ``lax.scan``.
    A synchronized window pays a fixed dispatch + fetch (~0.2 ms + ~1.6
    ms on the v5e machine: chip_smoke.py's clock phase, PR 21), so the
    timed window CHAINS repeated calls of one fixed-length compiled loop
    — carry out feeds carry in, all async, ONE fetch at the end — until
    it spans ``target_ms`` of device time, a thousand times that
    overhead; min-of-2 windows on top.  No per-repetition compiles.

    ``carry_fn`` (optional) rebuilds a fresh carry per window and the
    loop DONATES it — for carries the size of optimizer state, where
    keeping input and output trees alive would not fit HBM; the rebuild
    runs outside the timed region (donation makes chaining free)."""
    def scan_body(c):
        return jax.lax.scan(lambda c, _: (body(c), None), c, None,
                            length=inner)[0]

    loop = (jax.jit(scan_body, donate_argnums=(0,)) if carry_fn
            else jax.jit(scan_body))
    get = carry_fn if carry_fn is not None else lambda: carry

    def window(reps):
        c0 = get()
        jax.block_until_ready(jax.tree.leaves(c0)[0])
        t0 = time.perf_counter()
        c = loop(c0)
        for _ in range(reps - 1):
            c = loop(c)
        fetch(c)
        return time.perf_counter() - t0

    fetch(loop(get()))      # compile + warm
    w1 = window(1)
    reps = max(int(target_ms / max(w1 * 1e3, 1e-6)), 1)
    best = min(window(reps), window(reps))
    return best / (reps * inner) * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("small", "large"), default="small")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--inner", type=int, default=60,
                    help="chained iterations per scan dispatch")
    ap.add_argument("--skip-step", action="store_true",
                    help="skip the full-step phase (use the bench.py "
                         "lm gate number instead)")
    args = ap.parse_args()
    spec = MODELS[args.model]
    batch, seq = spec["batch"], args.seq
    model = tfm.TransformerConfig(vocab_size=256, d_model=spec["d_model"],
                                  n_layers=spec["n_layers"],
                                  n_heads=spec["n_heads"],
                                  head_dim=spec["head_dim"])
    print("[roofline] building trainer", file=sys.stderr, flush=True)
    cfg = LMTrainConfig(model=model)
    tr = LMTrainer(cfg)
    print("[roofline] measuring step", file=sys.stderr, flush=True)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 256, (batch, seq)).astype(np.int32))
    tgts = jnp.asarray(np.roll(np.asarray(toks), -1, 1).astype(np.int32))
    dtype = jnp.bfloat16
    d, ff = model.d_model, model.ff
    h, dh, nl = model.n_heads, model.head_dim, model.n_layers
    vocab = model.vocab_size
    n_tok = batch * seq
    res = {"model": args.model, "batch": batch, "seq": seq}

    # 1. the full train step FIRST, donating the trainer's own state
    # through the loop (copies would not fit HBM at 535M: params 2.1GB
    # + Adam 4.2GB doubled).  The evolved params then serve the other
    # measurements; the optimizer tree is dropped to free its 4.2GB.
    toks_np, tgts_np = np.asarray(toks), np.asarray(tgts)

    def full_step():
        # the trainer's own entry point (device_put per call), with the
        # loss fetched every step, as a training loop that logs it pays
        # (on the v5e machine that sync is ~1.4% of a 535M step, and
        # leaving it out neither grows host memory nor stalls: PR 21)
        return float(tr.train_step(toks_np, tgts_np))

    if args.skip_step:
        res["step_ms"] = None  # bench.py's lm gate measures it
    else:
        res["step_ms"] = timed(full_step, lambda x: x, args.iters)
    params = tr.params
    tr.opt_state = None

    # 2. forward only and forward+backward of the same loss, each a
    # dependency-chained scan (ONE dispatch per window)
    def loss_fn(params):
        logits, aux = tfm.apply(params, toks, cfg=model, dtype=dtype,
                                return_aux=True)
        ce, n = masked_ce(logits, tgts)
        return ce / jnp.maximum(n, 1) + 0.01 * aux

    inner = args.inner

    def fwd_body(c):
        # params ride the CARRY: closing over them would bake 2.1GB of
        # weights into the program as constants — measured minutes of
        # extra lowering at 535M; the loss dependency is a tiny embed
        # perturbation
        p, lo = c
        return (p, loss_fn(dict(p, embed=p["embed"] + lo * 1e-30)))

    print("[roofline] measuring fwd", file=sys.stderr, flush=True)
    res["fwd_ms"] = timed_scan(fwd_body, (params, jnp.float32(0.0)),
                               inner, lambda c: float(c[1]))

    print("[roofline] measuring fwd_bwd", file=sys.stderr,
          flush=True)
    vg = jax.value_and_grad(loss_fn)

    def fwd_bwd_body(p):
        _, g = vg(p)
        return jax.tree.map(
            lambda a, gg: (a - 1e-12 * gg).astype(a.dtype), p, g)

    res["fwd_bwd_ms"] = timed_scan(
        fwd_bwd_body, None, inner,
        lambda p: float(jax.tree.leaves(p)[0].ravel()[0]),
        carry_fn=lambda: jax.tree.map(jnp.array, params))

    # 3. optimizer alone (clip + AdamW + weight decay, f32 state HBM)
    import optax
    tx = make_optimizer(cfg)
    grads = jax.tree.map(jnp.ones_like, params)

    def opt_body(c):
        # grads ride the carry too (same closed-over-constants hazard)
        p, o, g = c
        u, o = tx.update(g, o, p)
        return (optax.apply_updates(p, u), o, g)

    res["opt_ms"] = timed_scan(
        opt_body, None, inner,
        lambda c: float(jax.tree.leaves(c[0])[0].ravel()[0]),
        carry_fn=lambda: (jax.tree.map(jnp.array, params),
                          jax.jit(tx.init)(params), grads))

    # 4. matmul-family microbenches at training shapes, each fwd+bwd
    # (grads w.r.t. EVERY operand so the backward runs the same matmul
    # set training does), chained by a vanishing SGD step
    def micro(f, *xs):
        # squared-sum loss: the incoming cotangent is 2*out (runtime
        # data) — a plain .sum() feeds a LITERAL ones cotangent that
        # XLA constant-folds parts of the backward away (measured >100%
        # "MXU" on the matmul micros before this fix)
        g = jax.grad(
            lambda *a: (lambda o: (o * o).sum())(
                f(*a).astype(jnp.float32)),
            argnums=tuple(range(len(xs))))

        def body(c):
            gs = g(*c)
            return tuple((a - 1e-12 * gg).astype(a.dtype)
                         for a, gg in zip(c, gs))

        return timed_scan(body, xs, inner,
                          lambda c: float(c[0].ravel()[0]))

    q = jnp.asarray(rng.normal(size=(batch, h, seq, dh)), dtype)
    res["attn_ms"] = nl * micro(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, q, q)
    attn_flops = nl * 3 * 2 * 2 * batch * h * seq * seq * dh / 2  # causal

    x2 = jnp.asarray(rng.normal(size=(n_tok, d)), dtype)
    wq = jnp.asarray(rng.normal(size=(d, h * dh)) / np.sqrt(d), dtype)

    def qkvo(x, w):
        return ((x @ w) @ w.T) @ w @ w.T  # 4 projections' worth

    res["qkvo_ms"] = nl * micro(qkvo, x2, wq)
    qkvo_flops = nl * 3 * 4 * 2 * n_tok * d * h * dh

    wg = jnp.asarray(rng.normal(size=(d, ff)) / np.sqrt(d), dtype)
    wd = jnp.asarray(rng.normal(size=(ff, d)) / np.sqrt(ff), dtype)

    def ffn(x, wg_, wu_, wd_):
        return (jax.nn.silu(x @ wg_) * (x @ wu_)) @ wd_

    res["ffn_ms"] = nl * micro(ffn, x2, wg, wg, wd)
    ffn_flops = nl * 3 * 3 * 2 * n_tok * d * ff

    emb = jnp.asarray(rng.normal(size=(vocab, d)) / np.sqrt(d), dtype)

    def unembed(x, e):
        logits = x.astype(jnp.float32) @ e.T.astype(jnp.float32)
        ce, n = masked_ce(logits[None], tgts.reshape(1, -1))
        return ce / jnp.maximum(n, 1)

    res["embed_ce_ms"] = micro(unembed, x2, emb)
    emb_flops = 3 * 2 * n_tok * d * vocab

    # control: a bare fwd (n_tok, d) @ (d, d) matmul chain at the same
    # tile shapes — the achieved-TF/s ceiling the model's K=d tiles
    # allow, independent of autodiff (compare with calibrate 4096^3)
    wsq = jnp.asarray(rng.normal(size=(d, d)) / np.sqrt(d), dtype)
    res["ctl_matmul_ms"] = timed_scan(
        lambda x: ((x @ wsq) / jnp.float32(1.0)).astype(dtype), x2,
        inner, lambda x: float(x.ravel()[0]))
    res["ctl_matmul_tflops"] = round(
        2 * n_tok * d * d / (res["ctl_matmul_ms"] / 1e3) / 1e12, 1)

    # 5. the accounting
    matmul_ms = (res["attn_ms"] + res["qkvo_ms"] + res["ffn_ms"]
                 + res["embed_ce_ms"])
    res["matmul_sum_ms"] = round(matmul_ms, 3)
    res["elementwise_remainder_ms"] = round(
        res["fwd_bwd_ms"] - matmul_ms, 3)
    res["step_minus_parts_ms"] = (round(
        res["step_ms"] - res["fwd_bwd_ms"] - res["opt_ms"], 3)
        if res["step_ms"] is not None else None)
    peak = peak_bf16_flops(jax.devices()[0])
    for k, fl in (("attn", attn_flops), ("qkvo", qkvo_flops),
                  ("ffn", ffn_flops), ("embed_ce", emb_flops)):
        key = f"{k}_ms" if f"{k}_ms" in res else "embed_ce_ms"
        res[f"{k}_mxu"] = round(fl / (res[key] / 1e3) / peak, 3)
    for k in list(res):
        if k.endswith("_ms") and res[k] is not None:
            res[k] = round(res[k], 3)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
