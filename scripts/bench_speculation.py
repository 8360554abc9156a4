"""Speculation measurement harness (BASELINE.md speculation tables).

Trains a byte-LM target (and optionally a draft) briefly on a corpus,
then measures speculative decoding against the plain path on HELD-OUT
text from the same corpus:

- ``--mode static``: the round-4 methodology — `generate` vs
  `generate_speculative` / `generate_lookup` (greedy, B=2, 1024 new
  tokens, bf16, kernel decode), reporting acceptance, target passes, and
  wall-clock ratio.
- ``--mode serving``: the round-5 flagship — `ContinuousBatcher` with
  ``speculate=0`` vs ``speculate=N`` on a ragged multi-request workload
  whose prompts are corpus windows, reporting tok/s, acceptance, and
  tokens per verify round.

``--corpus synthetic`` is the word-salad generator (repetitive — the
lookup-friendliest case); ``--corpus pysrc`` concatenates Python stdlib
sources (code text — the less friendly workload VERDICT round-4 weak #3
asks for).  Prompts/eval text come from the corpus TAIL, never trained
on.

Run (TPU):  PYTHONPATH=. python scripts/bench_speculation.py \
    --mode serving --corpus synthetic --model large --train-steps 300
"""
from __future__ import annotations

import argparse
import glob
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_pytorch_tpu import generate as gen
from distributed_pytorch_tpu.data import lm_corpus
from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer
from distributed_pytorch_tpu.models import transformer as tfm
from distributed_pytorch_tpu.serve import ContinuousBatcher

from bench_serving import warm_clone  # single source of the warm-fn list

MODELS = {
    "small": dict(d_model=512, n_layers=4, n_heads=4, head_dim=128),
    "large": dict(d_model=2048, n_layers=8, n_heads=16, head_dim=128),
    "draft": dict(d_model=256, n_layers=2, n_heads=2, head_dim=128),
}


def build_corpus(kind: str, n_bytes: int) -> np.ndarray:
    if kind == "synthetic":
        return lm_corpus.encode(lm_corpus.synthetic_corpus(n_bytes, seed=0))
    # pysrc: concatenated Python stdlib sources — byte text that is NOT
    # the repetitive word salad (code repeats structurally, not verbatim
    # at the window scale; acceptance shows whatever it shows)
    chunks, total = [], 0
    for path in sorted(glob.glob("/usr/lib/python3.*/[a-z]*.py")):
        try:
            b = open(path, "rb").read()
        except OSError:
            continue
        chunks.append(b)
        total += len(b)
        if total >= n_bytes:
            break
    blob = b"".join(chunks)[:n_bytes]
    assert len(blob) >= n_bytes // 2, "not enough stdlib source found"
    return lm_corpus.encode(blob)


def train_model(name: str, tokens: np.ndarray, steps: int, batch: int,
                seq: int, cache_dir: str | None = None):
    cfg = LMTrainConfig(model=tfm.TransformerConfig(vocab_size=256,
                                                    **MODELS[name]))
    if cache_dir:
        import os
        path = os.path.join(cache_dir, f"{name}_{steps}.npz")
        if os.path.exists(path):
            import jax
            z = np.load(path, allow_pickle=True)
            flat = [z[f"a{i}"] for i in range(len(z.files) - 1)]
            import pickle
            td = pickle.loads(z["treedef"].tobytes())
            params = jax.tree.unflatten(td, [jax.numpy.asarray(a)
                                             for a in flat])
            print(f"[spec-bench] {name}: loaded cached params ({path})",
                  flush=True)
            return params, cfg.model, float("nan")
    tr = LMTrainer(cfg)
    dl = lm_corpus.LMDataLoader(lm_corpus.LMCorpus(tokens),
                                batch_size=batch, seq_len=seq, seed=0)
    it, done, loss = iter(dl), 0, float("nan")
    t0 = time.perf_counter()
    while done < steps:
        try:
            tk, tg = next(it)
        except StopIteration:
            it = iter(dl)
            continue
        loss = tr.train_step(tk, tg)
        done += 1
    loss = float(loss)
    print(f"[spec-bench] {name}: {steps} steps in "
          f"{time.perf_counter() - t0:.0f}s, final loss {loss:.3f}",
          flush=True)
    if cache_dir:
        import os, pickle, jax
        leaves, td = jax.tree.flatten(tr.params)
        np.savez(os.path.join(cache_dir, f"{name}_{steps}.npz"),
                 treedef=np.frombuffer(pickle.dumps(td), np.uint8),
                 **{f"a{i}": np.asarray(x) for i, x in enumerate(leaves)})
    return tr.params, tr.cfg.model, loss


def held_out_windows(tokens: np.ndarray, n: int, width: int, seed: int):
    """Prompt windows from the corpus TAIL (beyond any trained window)."""
    tail = tokens[int(len(tokens) * 0.9):]
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, len(tail) - width, n)
    return [tail[s:s + width].astype(np.int32) for s in starts]


def bench_static(params, cfg, draft, draft_cfg, prompts, max_new, n_spec,
                 ngram):
    prompt = jnp.asarray(np.stack(prompts[:2]))

    def timed(fn):
        fn()  # compile + warm
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return best, out

    t_plain, _ = timed(lambda: np.asarray(gen.generate(
        params, prompt, jax.random.key(1), cfg=cfg, max_new=max_new,
        temperature=0.0, dtype=jnp.bfloat16, decode_kernel=True)))
    rows = {"plain_wall_s": round(t_plain, 2)}

    def stats_of(out):
        _, st = out
        return {k: int(v) for k, v in st.items()}

    def _fetched(out):
        # a real value FETCH, matching the plain path
        return (np.asarray(out[0]), out[1])

    t_lk, out = timed(lambda: _fetched(gen.generate_lookup(
        params, prompt, cfg=cfg, max_new=max_new, n_spec=n_spec,
        ngram=ngram, dtype=jnp.bfloat16)))
    st = stats_of(out)
    rows["lookup"] = dict(wall_s=round(t_lk, 2),
                          speedup=round(t_plain / t_lk, 2),
                          acceptance=round(st["accepted"]
                                           / max(st["drafted"], 1), 3),
                          rounds=st["rounds"])
    if draft is not None:
        t_sp, out = timed(lambda: _fetched(
            gen.generate_speculative(
                params, draft, prompt, cfg=cfg, draft_cfg=draft_cfg,
                max_new=max_new, n_spec=max(n_spec // 2, 3),
                dtype=jnp.bfloat16, decode_kernel=True)))
        st = stats_of(out)
        rows["draft_spec"] = dict(wall_s=round(t_sp, 2),
                                  speedup=round(t_plain / t_sp, 2),
                                  acceptance=round(st["accepted"]
                                                   / max(st["drafted"], 1),
                                                   3),
                                  rounds=st["rounds"])
    return rows


def bench_serving(params, cfg, prompts, budgets, n_spec, ngram, slots,
                  steps_per_sync, paged):
    def make(spec):
        return ContinuousBatcher(
            params, cfg, slots=slots, max_len=1024, temperature=0.0,
            dtype=jnp.bfloat16, prompt_buckets=(32, 128),
            steps_per_sync=steps_per_sync, paged=paged,
            speculate=spec, spec_ngram=ngram)

    def run(spec):
        # cold pass compiles; timed pass runs warm with clean stats
        cold = make(spec)
        for p, b in zip(prompts, budgets):
            cold.submit(p, max_new=b)
        while cold.pending():
            cold.step()
        cb = warm_clone(cold, lambda: make(spec))
        rids = [cb.submit(p, max_new=b)
                for p, b in zip(prompts, budgets)]
        t0 = time.perf_counter()
        while cb.pending():
            cb.step()
        wall = time.perf_counter() - t0
        print(f"[spec-bench] spec={spec}: warm wall {wall:.1f}s, "
              f"{cb.stats['decode_dispatches']} decode dispatches, "
              f"{cb.stats['prefill_dispatches']} prefills", flush=True)
        total = sum(len(cb.result(r)) - len(p)
                    for r, p in zip(rids, prompts))
        s = cb.stats
        out = dict(wall_s=round(wall, 2),
                   tok_per_s=round(total / wall, 1),
                   utilization=round(cb.utilization(), 3))
        if spec:
            out.update(
                acceptance=round(s["spec_accepted"]
                                 / max(s["spec_proposed"], 1), 3),
                tokens_per_round=round(
                    s["emitted_tokens"]
                    / max(s["spec_rounds"] * slots, 1), 2),
                rounds=s["spec_rounds"])
        return out

    plain = run(0)
    spec = run(n_spec)
    spec["speedup"] = round(plain["wall_s"] / spec["wall_s"], 2)
    return {"plain": plain, f"speculate_{n_spec}": spec}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("static", "serving"),
                    default="serving")
    ap.add_argument("--corpus", choices=("synthetic", "pysrc"),
                    default="synthetic")
    ap.add_argument("--model", choices=("small", "large"), default="large")
    ap.add_argument("--with-draft", action="store_true")
    ap.add_argument("--train-steps", type=int, default=300)
    ap.add_argument("--train-batch", type=int, default=8)
    ap.add_argument("--train-seq", type=int, default=1024)
    ap.add_argument("--n-spec", type=int, default=8)
    ap.add_argument("--ngram", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=1024)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--steps-per-sync", type=int, default=8)
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--corpus-bytes", type=int, default=1 << 21)
    ap.add_argument("--params-cache", default=None,
                    help="dir to cache trained params (skips retraining)")
    args = ap.parse_args()

    tokens = build_corpus(args.corpus, args.corpus_bytes)
    cache = (f"{args.params_cache}/{args.corpus}"
             if args.params_cache else None)
    if cache:
        import os
        os.makedirs(cache, exist_ok=True)
    params, cfg, loss = train_model(args.model, tokens, args.train_steps,
                                    args.train_batch, args.train_seq,
                                    cache_dir=cache)
    draft = draft_cfg = None
    if args.with_draft:
        draft, draft_cfg, _ = train_model("draft", tokens,
                                          args.train_steps,
                                          args.train_batch, args.train_seq,
                                          cache_dir=cache)
    out = {"mode": args.mode, "corpus": args.corpus, "model": args.model,
           "train_steps": args.train_steps, "target_loss": round(loss, 3),
           "n_spec": args.n_spec, "ngram": args.ngram}
    if args.mode == "static":
        prompts = held_out_windows(tokens, 2, 64, seed=1)
        out.update(bench_static(params, cfg, draft, draft_cfg, prompts,
                                args.max_new, args.n_spec, args.ngram))
    else:
        rng = np.random.default_rng(1)
        widths = rng.integers(16, 97, args.requests)
        prompts = [held_out_windows(tokens, 1, int(w), seed=2 + i)[0]
                   for i, w in enumerate(widths)]
        budgets = [int(b) for b in rng.integers(64, 513, args.requests)]
        out.update(bench_serving(params, cfg, prompts, budgets,
                                 args.n_spec, args.ngram, args.slots,
                                 args.steps_per_sync, args.paged))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
