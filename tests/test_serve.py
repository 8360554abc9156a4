"""Continuous batching tests (serve.py).

Oracle: static `generate()` with temperature 0 — greedy decoding is
key-independent, so every request's tokens must match regardless of how
requests were batched, bucketed, or which recycled slot served them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import generate as gen
from distributed_pytorch_tpu.models import transformer as tfm
from distributed_pytorch_tpu.serve import ContinuousBatcher

CFG = tfm.TransformerConfig(vocab_size=256, d_model=128, n_layers=2,
                            n_heads=4, head_dim=32, n_kv_heads=2, d_ff=256)


@pytest.fixture(scope="module")
def params():
    return tfm.init(jax.random.key(0), CFG)


def _greedy_oracle(params, prompt, max_new, decode_kernel=False):
    return np.asarray(gen.generate(
        params, jnp.asarray(prompt)[None], jax.random.key(1), cfg=CFG,
        max_new=max_new, temperature=0.0, decode_kernel=decode_kernel))[0]


def test_matches_generate_oracle_with_slot_recycling(params):
    """5 ragged requests through 2 slots: every sequence decodes exactly as
    in static generation — per-sequence read bounds hold and recycled
    slots' stale K/V never leaks."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (5, 17, 40, 9, 23)]
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32, 64))
    results = cb.run(prompts, max_new=10)
    for rid, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            results[rid], _greedy_oracle(params, prompt, 10))


def test_eos_retires_slot_early(params):
    """A sequence that samples eos_id retires immediately and its slot
    serves the next request; others continue unaffected."""
    rng = np.random.default_rng(1)
    p1 = rng.integers(0, 256, (8,)).astype(np.int32)
    # find what p1 greedily emits first, use it as the "eos"
    first = int(_greedy_oracle(params, p1, 1)[-1])
    p2 = rng.integers(0, 256, (12,)).astype(np.int32)
    cb = ContinuousBatcher(params, CFG, slots=1, max_len=512,
                           temperature=0.0, eos_id=first,
                           prompt_buckets=(32,))
    r1 = cb.submit(p1, max_new=10)
    r2 = cb.submit(p2, max_new=4)
    while cb.pending():
        cb.step()
    out1, out2 = cb.result(r1), cb.result(r2)
    assert len(out1) == len(p1) + 1 and out1[-1] == first  # stopped at eos
    assert len(out2) == len(p2) + 4  # full budget after taking the slot
    # p2's tokens unaffected by sharing the slot (unless it hit the eos)
    want2 = _greedy_oracle(params, p2, 4)
    cut = len(p2) + 4
    for t in range(len(p2), cut):
        assert out2[t] == want2[t]
        if out2[t] == first:
            break


def test_submission_validation(params):
    cb = ContinuousBatcher(params, CFG, slots=1, max_len=512,
                           prompt_buckets=(32,))
    with pytest.raises(ValueError, match="empty"):
        cb.submit(np.zeros((0,), np.int32))
    with pytest.raises(ValueError, match="bucket"):
        cb.submit(np.zeros((100,), np.int32))
    with pytest.raises(ValueError, match="max_len"):
        cb.submit(np.zeros((8,), np.int32), max_new=512)


def test_interleaved_submission_mid_stream(params):
    """Requests submitted while others decode still come out oracle-exact."""
    rng = np.random.default_rng(2)
    pa = rng.integers(0, 256, (6,)).astype(np.int32)
    pb = rng.integers(0, 256, (14,)).astype(np.int32)
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32,))
    ra = cb.submit(pa, max_new=8)
    cb.step()
    cb.step()
    rb = cb.submit(pb, max_new=6)  # lands mid-decode of ra
    while cb.pending():
        cb.step()
    np.testing.assert_array_equal(cb.result(ra), _greedy_oracle(params, pa, 8))
    np.testing.assert_array_equal(cb.result(rb), _greedy_oracle(params, pb, 6))


def test_tensor_parallel_continuous_batching(params):
    """TP serving: the batcher runs on a 'model' mesh with Megatron-sharded
    params and a head-sharded slot pool (prefill + ragged decode inside
    shard_map) — tokens match the single-device oracle exactly."""
    from jax.sharding import Mesh, NamedSharding

    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    specs = tfm.shard_specs(CFG, tp_axis="model")
    sharded = jax.device_put(params, jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (6, 19, 33)]
    cb = ContinuousBatcher(sharded, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32, 64),
                           mesh=mesh)
    results = cb.run(prompts, max_new=8)
    for rid, p in enumerate(prompts):
        np.testing.assert_array_equal(results[rid],
                                      _greedy_oracle(params, p, 8))


def test_chunked_prefill_matches_oracle(params):
    """Chunked prefill (VERDICT round-2 #4): admissions prefill 16 tokens
    per step() interleaved with decode — every request stays oracle-exact
    (the chunk rows attend causally to earlier chunks via k_len=bucket)."""
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (5, 17, 40, 9, 23)]
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32, 64),
                           prefill_chunk=16)
    results = cb.run(prompts, max_new=10)
    for rid, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            results[rid], _greedy_oracle(params, prompt, 10))


def test_chunked_prefill_keeps_slots_emitting(params):
    """The latency property: while a long prompt admits chunk by chunk,
    already-running slots keep emitting every step — no multi-step stall."""
    rng = np.random.default_rng(5)
    pa = rng.integers(0, 256, (4,)).astype(np.int32)
    pb = rng.integers(0, 256, (60,)).astype(np.int32)   # 4 chunks of 16
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(16, 64),
                           prefill_chunk=16, steps_per_sync=2)
    ra = cb.submit(pa, max_new=40)
    cb.step()                      # admit + start decoding ra
    rb = cb.submit(pb, max_new=4)  # long prompt starts chunked admission
    steps_until_rb, ra_tokens_during = 0, 0
    while not cb.requests[rb].emitted:
        got = cb.step()
        steps_until_rb += 1
        ra_tokens_during += sum(1 for rid, _ in got if rid == ra)
    # admission spanned multiple steps (60 tokens / 16-chunk = 4 steps)...
    assert steps_until_rb >= 4, steps_until_rb
    # ...and ra kept emitting its 2-token blocks during EVERY one of them
    assert ra_tokens_during >= 2 * (steps_until_rb - 1), (
        steps_until_rb, ra_tokens_during)
    while cb.pending():
        cb.step()
    np.testing.assert_array_equal(cb.result(ra),
                                  _greedy_oracle(params, pa, 40))
    np.testing.assert_array_equal(cb.result(rb),
                                  _greedy_oracle(params, pb, 4))


def test_per_request_sampling_params(params):
    """Per-request temperature/top_k/top_p/eos (VERDICT round-2 #4): a
    greedy request stays oracle-exact while sharing the pool with hot
    stochastic requests; top_k=1 and tiny top_p degenerate to greedy."""
    rng = np.random.default_rng(6)
    pa = rng.integers(0, 256, (7,)).astype(np.int32)
    pb = rng.integers(0, 256, (11,)).astype(np.int32)
    pc = rng.integers(0, 256, (9,)).astype(np.int32)
    pd = rng.integers(0, 256, (13,)).astype(np.int32)
    cb = ContinuousBatcher(params, CFG, slots=4, max_len=512,
                           temperature=1.5, prompt_buckets=(32,))
    ra = cb.submit(pa, max_new=8, temperature=0.0)  # greedy in a hot pool
    rb = cb.submit(pb, max_new=8)                   # batcher default 1.5
    rc = cb.submit(pc, max_new=8, temperature=1.0, top_k=1)
    rd = cb.submit(pd, max_new=8, temperature=1.0, top_p=1e-6)
    while cb.pending():
        cb.step()
    np.testing.assert_array_equal(cb.result(ra),
                                  _greedy_oracle(params, pa, 8))
    # top_k=1 keeps only the argmax -> greedy regardless of temperature
    np.testing.assert_array_equal(cb.result(rc),
                                  _greedy_oracle(params, pc, 8))
    # nucleus with p -> 0 keeps only the top token -> greedy
    np.testing.assert_array_equal(cb.result(rd),
                                  _greedy_oracle(params, pd, 8))
    assert len(cb.result(rb)) == len(pb) + 8  # sampled request completed


def test_per_request_eos(params):
    """eos_id is per-request: the same token retires one request and is an
    ordinary token for its pool-mate."""
    rng = np.random.default_rng(7)
    p1 = rng.integers(0, 256, (8,)).astype(np.int32)
    first = int(_greedy_oracle(params, p1, 1)[-1])
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32,))
    r_stop = cb.submit(p1, max_new=10, eos_id=first)
    r_free = cb.submit(p1, max_new=3)   # same prompt, no eos
    while cb.pending():
        cb.step()
    assert len(cb.result(r_stop)) == len(p1) + 1   # stopped at its eos
    assert len(cb.result(r_free)) == len(p1) + 3   # ran its full budget


def test_sample_per_seq_matches_scalar_sample(params):
    """gen.sample_per_seq with uniform row params reproduces gen._sample
    bit-for-bit (same key): same thresholds, same categorical draw."""
    rng = np.random.default_rng(8)
    logits = jnp.asarray(rng.standard_normal((4, 256)).astype(np.float32))
    key = jax.random.key(9)
    want = gen._sample(key, logits, 0.8, 50)
    got = gen.sample_per_seq(
        key, logits, jnp.full((4,), 0.8, jnp.float32),
        jnp.full((4,), 50, jnp.int32), jnp.ones((4,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    # greedy rows
    want0 = gen._sample(key, logits, 0.0, None)
    got0 = gen.sample_per_seq(
        key, logits, jnp.zeros((4,), jnp.float32),
        jnp.zeros((4,), jnp.int32), jnp.ones((4,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(want0), np.asarray(got0))


def test_serving_stats_account_for_every_slot_step(params):
    """Accounting identity: slot_steps == emitted decode tokens +
    in-block prefill steps + wasted (idle or discarded) slot-steps.
    Batch-prefilled admissions emit their first token from the prefill
    dispatch (one per bucketed prefill); in-block admitted/refilled
    requests emit everything from decode dispatches."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (5, 9, 14)]
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32,),
                           steps_per_sync=4)
    results = cb.run(prompts, max_new=6)
    s = cb.stats
    # one first-token emit per batch-prefilled admission; the rest
    # entered in-block
    decode_emitted = s["emitted_tokens"] - s["batch_admissions"]
    assert s["slot_steps"] == (decode_emitted
                               + s["inblock_prefill_steps"]
                               + s["wasted_slot_steps"]), s
    # the initial wave batch-prefills (idle pool); the third request
    # enters through the in-block path (admission or retire handoff)
    assert s["decode_dispatches"] > 0 and s["batch_admissions"] == 2
    assert s["inblock_prefill_steps"] > 0
    assert all(len(results[r]) == len(prompts[r]) + 6 for r in results)

    # the round-3 behavior is preserved under inblock_refill=False:
    # every admission batch-prefills and the old identity holds
    cb2 = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                            temperature=0.0, prompt_buckets=(32,),
                            steps_per_sync=4, inblock_refill=False)
    results2 = cb2.run(prompts, max_new=6)
    s2 = cb2.stats
    assert s2["prefill_dispatches"] == 3 and s2["batch_admissions"] == 3
    assert s2["inblock_prefill_steps"] == 0 and s2["inblock_refills"] == 0
    assert s2["slot_steps"] == (s2["emitted_tokens"] - 3
                                + s2["wasted_slot_steps"]), s2
    assert all(len(results2[r]) == len(prompts[r]) + 6 for r in results2)


def test_tensor_parallel_chunked_prefill(params):
    """TP serving x chunked prefill: the scratch cache is created inside
    shard_map with the LOCAL kv-head count — tokens stay oracle-exact."""
    from jax.sharding import Mesh, NamedSharding

    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    specs = tfm.shard_specs(CFG, tp_axis="model")
    sharded = jax.device_put(params, jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs))
    rng = np.random.default_rng(10)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (6, 45, 19)]
    cb = ContinuousBatcher(sharded, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32, 64),
                           prefill_chunk=16, mesh=mesh)
    results = cb.run(prompts, max_new=8)
    for rid, p in enumerate(prompts):
        np.testing.assert_array_equal(results[rid],
                                      _greedy_oracle(params, p, 8))


def test_eos_none_disables_inherited_default(params):
    """submit(eos_id=None) opts OUT of the batcher's default eos; omitting
    the argument inherits it."""
    rng = np.random.default_rng(11)
    p1 = rng.integers(0, 256, (8,)).astype(np.int32)
    first = int(_greedy_oracle(params, p1, 1)[-1])
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, eos_id=first,
                           prompt_buckets=(32,))
    r_inherit = cb.submit(p1, max_new=5)
    r_nostop = cb.submit(p1, max_new=5, eos_id=None)
    while cb.pending():
        cb.step()
    assert len(cb.result(r_inherit)) == len(p1) + 1  # stopped at default eos
    assert len(cb.result(r_nostop)) == len(p1) + 5   # eos disabled


def test_early_exit_cuts_short_tail_waste(params):
    """Short-tail waste: the device-side early exit ends the block once
    every budget is exhausted — a 5-token request costs ~its own tokens,
    not a full steps_per_sync block; tokens stay oracle-exact."""
    rng = np.random.default_rng(12)
    p = rng.integers(0, 256, (9,)).astype(np.int32)
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32,),
                           steps_per_sync=32)
    r = cb.submit(p, max_new=5)
    while cb.pending():
        cb.step()
    np.testing.assert_array_equal(cb.result(r), _greedy_oracle(params, p, 5))
    # 5 tokens: 1 at admission + one 4-step dispatch covers the rest.
    # Without the clamp this costs 32 steps x 2 slots = 64 slot-steps.
    assert cb.stats["slot_steps"] <= 8, cb.stats


def test_scalar_and_per_seq_samplers_agree_on_combined_filters(params):
    """top_k AND top_p combined: _sample (generate path) and
    sample_per_seq (serving path) must keep the SAME token set — both
    thresholds from one sort of the full scaled distribution."""
    rng = np.random.default_rng(13)
    logits = jnp.asarray(rng.standard_normal((4, 256)).astype(np.float32))
    key = jax.random.key(3)
    for temp, k, p in ((0.8, 50, 0.9), (1.3, 5, 0.5), (1.0, 200, 0.99)):
        want = gen._sample(key, logits, temp, k, p)
        got = gen.sample_per_seq(
            key, logits, jnp.full((4,), temp, jnp.float32),
            jnp.full((4,), k, jnp.int32), jnp.full((4,), p, jnp.float32))
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got),
                                      err_msg=f"{temp},{k},{p}")


def test_early_exit_on_eos_cuts_block_short(params):
    """Device-side early exit: a request that samples its eos ends the
    decode block AT the eos (steps_executed == tokens needed), not at the
    steps_per_sync boundary — without any host round-trip."""
    rng = np.random.default_rng(14)
    p = rng.integers(0, 256, (8,)).astype(np.int32)
    oracle = _greedy_oracle(params, p, 32)
    # pick the 3rd generated token as "eos": the request should emit
    # exactly 3 tokens and the block should stop right there
    eos = int(oracle[len(p) + 2])
    # ensure it doesn't appear earlier (else adjust expectations)
    first_hit = next(i for i in range(32) if int(oracle[len(p) + i]) == eos)
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32,),
                           steps_per_sync=32)
    r = cb.submit(p, max_new=32, eos_id=eos)
    while cb.pending():
        cb.step()
    out = cb.result(r)
    assert out[-1] == eos and len(out) == len(p) + first_hit + 1
    # block ended at the eos: slot-steps ~= tokens needed, not 32 x slots
    assert cb.stats["slot_steps"] <= 2 * (first_hit + 2), cb.stats


def test_paged_kv_pool_matches_oracle(params):
    """Paged KV pool (vLLM-style block tables over the decode kernel's
    scalar-prefetch index maps): ragged requests through a page pool with
    recycling stay oracle-exact, and pages actually recycle."""
    rng = np.random.default_rng(15)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (5, 17, 40, 9, 23)]

    cb = ContinuousBatcher(params, CFG, slots=2, max_len=1024,
                           temperature=0.0, prompt_buckets=(32, 64),
                           paged=True, decode_kernel=True)
    assert cb.pool_pages == 2 * (1024 // 512) + 1  # + scratch
    results = cb.run(prompts, max_new=10)
    for rid, prompt in enumerate(prompts):
        np.testing.assert_array_equal(results[rid],
                                      _greedy_oracle(params, prompt, 10, decode_kernel=True))
    # all usable pages returned to the free list after every request
    # retired (page 0 is the reserved scratch page)
    assert len(cb.free_pages) == cb.pool_pages - 1
    assert all(not p for p in cb.slot_pages)


def test_paged_pool_oversubscription(params):
    """A pool SMALLER than slots x max_len serves fine while sequences
    stay short (the memory win); when live sequences outgrow it, the
    youngest is PREEMPTED (host-swap) and resumed later — every request
    still completes oracle-exact (round 4; exhaustion used to raise)."""
    rng = np.random.default_rng(16)
    p = rng.integers(0, 256, (8,)).astype(np.int32)
    # 2 slots x 1024 max_len = 4 usable pages dense-equivalent; give the
    # pool only 2 usable (+1 scratch)
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=1024,
                           temperature=0.0, prompt_buckets=(32,),
                           paged=True, pool_pages=3, decode_kernel=True)
    r1 = cb.submit(p, max_new=8)
    r2 = cb.submit(p, max_new=8)
    while cb.pending():
        cb.step()
    assert len(cb.result(r1)) == len(p) + 8
    assert len(cb.result(r2)) == len(p) + 8
    assert cb.stats["evictions"] == 0  # short sequences: no pressure

    # two sequences that must BOTH cross page 0's boundary cannot share
    # the 2-page pool: one is evicted mid-stream, swapped to host, and
    # resumed after the other finishes — both land oracle-exact
    p1 = rng.integers(0, 256, (500,)).astype(np.int32)
    p2 = rng.integers(0, 256, (500,)).astype(np.int32)
    cb2 = ContinuousBatcher(params, CFG, slots=2, max_len=1024,
                            temperature=0.0, prompt_buckets=(512,),
                            paged=True, pool_pages=3, decode_kernel=True)
    q1 = cb2.submit(p1, max_new=80)
    q2 = cb2.submit(p2, max_new=80)
    while cb2.pending():
        cb2.step()
    np.testing.assert_array_equal(
        cb2.result(q1), _greedy_oracle(params, p1, 80, decode_kernel=True))
    np.testing.assert_array_equal(
        cb2.result(q2), _greedy_oracle(params, p2, 80, decode_kernel=True))
    assert cb2.stats["evictions"] >= 1, cb2.stats
    assert cb2.stats["swap_ins"] == cb2.stats["evictions"], cb2.stats
    # the pool drained clean: every usable page back on the free list
    assert len(cb2.free_pages) == cb2.pool_pages - 1
    assert not cb2.swapped


def test_preemption_resumes_past_prompt_buckets(params):
    """The reason preemption host-swaps instead of re-prefilling: a
    victim whose prompt + generated prefix exceeds every compiled
    prompt bucket must still resume exactly.  Three long-budget
    requests through 2 slots on a tight pool force mid-generation
    evictions at positions far past the 64-token bucket."""
    rng = np.random.default_rng(24)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (40, 60, 30)]
    budgets = [700, 650, 600]    # all cross the 512-page boundary
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=1024,
                           temperature=0.0, prompt_buckets=(64,),
                           paged=True, pool_pages=4, decode_kernel=True,
                           steps_per_sync=64)
    rids = [cb.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    while cb.pending():
        cb.step()
    for rid, p, b in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(
            cb.result(rid), _greedy_oracle(params, p, b,
                                           decode_kernel=True))
    assert cb.stats["evictions"] >= 1, cb.stats
    assert len(cb.free_pages) == cb.pool_pages - 1


def test_inblock_refill_handoff_exact_and_utilized(params):
    """In-block refill (round 4): slots retiring mid-block hand over to
    the next queued request inside the same compiled block (teacher-
    forced prefill through the ragged decode step), so ragged budgets
    stop wasting slot-steps.  Exactness through multiple handoffs is
    oracle-pinned, refills actually trigger, and the accounting shows
    the waste collapsing vs the same workload with refill disabled."""
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (5, 17, 40, 9, 23, 12, 31, 7)]
    budgets = [3, 25, 7, 18, 4, 30, 9, 5]   # ragged: retirements mid-block

    def serve(**kw):
        cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                               temperature=0.0, prompt_buckets=(32, 64),
                               steps_per_sync=16, **kw)
        rids = [cb.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
        while cb.pending():
            cb.step()
        return cb, rids

    cb, rids = serve()
    for rid, p, b in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(cb.result(rid),
                                      _greedy_oracle(params, p, b))
    assert cb.stats["inblock_refills"] >= 3, cb.stats
    util = cb.utilization()

    off, _ = serve(inblock_refill=False)
    util_off = off.utilization()
    assert util > util_off, (util, util_off)
    # the remaining waste on this tiny workload is the drained-queue
    # tail (the last long request finishing alone); the >=90% target on
    # the BASELINE workloads is measured by scripts/bench_serving.py
    assert util >= 0.85, (util, cb.stats)


def test_latency_stats_structure(params):
    """latency_stats: completed-request percentiles are present, finite,
    and ordered (ttft <= total per construction; p50 <= p95); an empty
    batcher reports zero completed."""
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32,))
    assert cb.latency_stats() == {"completed": 0}
    rng = np.random.default_rng(28)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (5, 17, 9)]
    cb.run(prompts, max_new=8)
    ls = cb.latency_stats()
    assert ls["completed"] == 3
    for k in ("ttft_p50", "ttft_p95", "total_p50", "total_p95"):
        assert np.isfinite(ls[k]) and ls[k] >= 0, (k, ls)
    assert ls["ttft_p50"] <= ls["ttft_p95"]
    assert ls["total_p50"] <= ls["total_p95"]
    assert ls["ttft_p50"] <= ls["total_p50"]
    assert 0 < cb.utilization() <= 1.0


def test_drained_tail_batch_compaction(params):
    """Round-4 tail lever: once the queue drains, paged serving
    dispatches NARROWER blocks over just the live slots (the page
    tables carry the indirection) — the end-of-stream empty-slot
    lockstep steps that neither refill nor LPT can reclaim stop being
    dispatched.  Exactness and page hygiene preserved; compact
    dispatches visible in stats; utilization beats the uncompacted
    run of the same workload."""
    rng = np.random.default_rng(27)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (5, 17, 9, 23)]
    budgets = [4, 6, 8, 40]   # one long request left alone at the tail

    cb = ContinuousBatcher(params, CFG, slots=4, max_len=1024,
                           temperature=0.0, prompt_buckets=(32,),
                           paged=True, decode_kernel=True,
                           steps_per_sync=8)
    rids = [cb.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    while cb.pending():
        cb.step()
    for rid, p, b in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(
            cb.result(rid), _greedy_oracle(params, p, b,
                                           decode_kernel=True))
    assert cb.stats["compact_dispatches"] >= 2, cb.stats
    assert len(cb.free_pages) == cb.pool_pages - 1

    # dense caches are physically slot-indexed: no compaction there
    cb_d = ContinuousBatcher(params, CFG, slots=4, max_len=1024,
                             temperature=0.0, prompt_buckets=(32,),
                             steps_per_sync=8)
    rids_d = [cb_d.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    while cb_d.pending():
        cb_d.step()
    assert cb_d.stats["compact_dispatches"] == 0
    assert cb.utilization() > cb_d.utilization(), (
        cb.utilization(), cb_d.utilization())

    # the shape-stability opt-out: paged but never compacted
    cb_o = ContinuousBatcher(params, CFG, slots=4, max_len=1024,
                             temperature=0.0, prompt_buckets=(32,),
                             paged=True, decode_kernel=True,
                             steps_per_sync=8, compact_tail=False)
    rids_o = [cb_o.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    while cb_o.pending():
        cb_o.step()
    assert cb_o.stats["compact_dispatches"] == 0
    for rid, p, b in zip(rids_o, prompts, budgets):
        np.testing.assert_array_equal(
            cb_o.result(rid), _greedy_oracle(params, p, b,
                                             decode_kernel=True))


def test_longest_first_schedule_exact_and_validated(params):
    """LPT queue discipline: every request still lands oracle-exact
    (admission order cannot change a greedy request's tokens — KV slots
    are isolated), long budgets are served first, and unknown schedule
    names raise."""
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (5, 17, 40, 9)]
    budgets = [3, 30, 8, 21]
    cb = ContinuousBatcher(params, CFG, slots=1, max_len=512,
                           temperature=0.0, prompt_buckets=(32, 64),
                           steps_per_sync=8, schedule="longest_first")
    rids = [cb.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    first_done = None
    while cb.pending():
        for rid, _ in cb.step():
            if first_done is None and cb.requests[rid].done:
                first_done = rid
    for rid, p, b in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(cb.result(rid),
                                      _greedy_oracle(params, p, b))
    # with one slot, the largest budget (request 1) must finish first
    assert first_done == rids[1], first_done
    with pytest.raises(ValueError, match="schedule"):
        ContinuousBatcher(params, CFG, schedule="shortest_first")


def test_inblock_refill_paged_handoff_exact(params):
    """The paged twin: the handoff switches the slot's block-table row to
    the refill's reserved pages inside the block — oracle-exact, and
    every page recycles."""
    rng = np.random.default_rng(22)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (5, 17, 40, 9, 23, 12)]
    budgets = [3, 25, 7, 18, 4, 30]
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=1024,
                           temperature=0.0, prompt_buckets=(32, 64),
                           steps_per_sync=16, paged=True,
                           decode_kernel=True)
    rids = [cb.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    while cb.pending():
        cb.step()
    for rid, p, b in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(
            cb.result(rid), _greedy_oracle(params, p, b,
                                           decode_kernel=True))
    assert cb.stats["inblock_refills"] >= 2, cb.stats
    assert len(cb.free_pages) == cb.pool_pages - 1
    assert all(not p for p in cb.slot_pages)
    assert all(not p for p in cb.refill_pages)


def test_preemption_with_non_power_of_two_pages_per_slot(params):
    """Review regression (round 4): the swap gather/scatter compile
    width is _pow2(n) CLAMPED to pages_per_slot — with max_len=1536
    (3 pages/slot) a victim owning all 3 pages must evict and resume
    without a shape mismatch, oracle-exact."""
    rng = np.random.default_rng(26)
    p1 = rng.integers(0, 256, (20,)).astype(np.int32)
    p2 = rng.integers(0, 256, (25,)).astype(np.int32)
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=1536,
                           temperature=0.0, prompt_buckets=(32,),
                           paged=True, pool_pages=4, decode_kernel=True,
                           steps_per_sync=64)
    r1 = cb.submit(p1, max_new=1100)  # needs 3 pages by the end
    r2 = cb.submit(p2, max_new=1100)
    while cb.pending():
        cb.step()
    np.testing.assert_array_equal(
        cb.result(r1), _greedy_oracle(params, p1, 1100,
                                      decode_kernel=True))
    np.testing.assert_array_equal(
        cb.result(r2), _greedy_oracle(params, p2, 1100,
                                      decode_kernel=True))
    assert cb.stats["evictions"] >= 1, cb.stats
    assert len(cb.free_pages) == cb.pool_pages - 1


def test_preempted_request_not_starved_by_refill_handoffs(params):
    """Review regression (round 4): a swapped-out victim must get the
    next free slot even under a sustained stream of young short
    requests — while the resume queue is non-empty, retiring slots are
    NOT handed over in-block (the handoff cannot restore pages), so the
    victim resumes at the next step boundary instead of waiting behind
    every later arrival."""
    rng = np.random.default_rng(25)
    p_long = rng.integers(0, 256, (30,)).astype(np.int32)
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=1024,
                           temperature=0.0, prompt_buckets=(32,),
                           paged=True, pool_pages=3, decode_kernel=True,
                           steps_per_sync=32)
    r_long = cb.submit(p_long, max_new=600)   # will cross a page: evicted
    other = cb.submit(rng.integers(0, 256, (20,)).astype(np.int32),
                      max_new=600)            # the other long occupant
    shorts = []
    steps_to_long = None
    for i in range(200):
        if not cb.pending():
            break
        # sustained arrivals: one young short request per step
        if i < 40:
            shorts.append(cb.submit(
                rng.integers(0, 256, (8,)).astype(np.int32), max_new=4))
        cb.step()
        if steps_to_long is None and cb.requests[r_long].done:
            steps_to_long = i
    assert not cb.pending()
    assert cb.stats["evictions"] >= 1, cb.stats
    np.testing.assert_array_equal(
        cb.result(r_long),
        _greedy_oracle(params, p_long, 600, decode_kernel=True))
    # the victim finished well before the arrival stream drained: it was
    # resumed at the first free slot, not queued behind 40 young shorts
    assert steps_to_long is not None and steps_to_long < 150, steps_to_long


def test_paged_prealloc_respects_budget(params):
    """Advisor regression (round 3): pre-allocation must cover only
    pos + min(steps_per_sync, budget) — the early exit never writes past
    the budget (lockstep writes clamp at write_cap), so a short-budget
    request on an oversubscribed pool must NOT demand pages for the full
    K-step block it will never fill."""
    rng = np.random.default_rng(20)
    # two ~505-token prompts: 1 page each (pos 504 + budget 4 = 508 < 512)
    # but pos + K = 536 would cross into a second page per slot — the old
    # full-K pre-allocation needed 4 usable pages, the pool has 2
    prompts = [rng.integers(0, 256, (505,)).astype(np.int32)
               for _ in range(2)]
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=1024,
                           temperature=0.0, prompt_buckets=(512,),
                           paged=True, pool_pages=3, decode_kernel=True,
                           steps_per_sync=32)
    r1 = cb.submit(prompts[0], max_new=4)
    r2 = cb.submit(prompts[1], max_new=4)
    while cb.pending():
        cb.step()
    for r, p in ((r1, prompts[0]), (r2, prompts[1])):
        np.testing.assert_array_equal(
            cb.result(r), _greedy_oracle(params, p, 4, decode_kernel=True))
    assert len(cb.free_pages) == 2


def test_paged_validation(params):
    with pytest.raises(ValueError, match="decode-kernel"):
        ContinuousBatcher(params, CFG, paged=True, decode_kernel=False)
    with pytest.raises(ValueError, match="cannot hold"):
        ContinuousBatcher(params, CFG, max_len=1024, paged=True,
                          pool_pages=2, decode_kernel=True)


def test_paged_freed_slot_writes_cannot_corrupt_recycled_pages(params):
    """Corruption regression (round-3 review): a retired slot keeps
    lockstep-writing until the block exits and across later dispatches —
    its table row must repoint at the reserved scratch page when its
    pages are recycled to another slot, or it would overwrite the new
    owner's K/V.  Scenario: slot 0 retires; the pool is so tight that
    slot 1's page-boundary crossing acquires slot 0's freed page; slot
    1's continuation must stay oracle-exact."""
    rng = np.random.default_rng(17)
    p_short = rng.integers(0, 256, (6,)).astype(np.int32)
    p_long = rng.integers(0, 256, (480,)).astype(np.int32)

    # usable pages = 2 (+1 scratch): long takes page A; short takes page
    # B and retires; long crosses 512 and must acquire B
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=1024,
                           temperature=0.0, prompt_buckets=(32, 512),
                           paged=True, pool_pages=3, decode_kernel=True,
                           steps_per_sync=8)
    r_long = cb.submit(p_long, max_new=80)   # crosses 512 mid-run
    r_short = cb.submit(p_short, max_new=4)  # retires early, frees B
    while cb.pending():
        cb.step()
    np.testing.assert_array_equal(cb.result(r_short),
                                  _greedy_oracle(params, p_short, 4, decode_kernel=True))
    np.testing.assert_array_equal(cb.result(r_long),
                                  _greedy_oracle(params, p_long, 80, decode_kernel=True))
    assert len(cb.free_pages) == 2  # both usable pages recycled


def test_paged_allocates_by_prompt_length_not_bucket(params):
    """A short prompt in a wide bucket holds only ceil(L/page) pages —
    the padding tax must not erode oversubscription headroom."""
    rng = np.random.default_rng(18)
    cb = ContinuousBatcher(params, CFG, slots=1, max_len=1024,
                           temperature=0.0, prompt_buckets=(1024,),
                           paged=True, decode_kernel=True)
    r = cb.submit(rng.integers(0, 256, (5,)).astype(np.int32), max_new=20)
    cb.step()
    assert len(cb.slot_pages[0]) == 1, cb.slot_pages  # not ceil(1024/512)
    while cb.pending():
        cb.step()
    assert len(cb.result(r)) == 25


def test_tensor_parallel_paged_serving(params):
    """Paged pool x TP: the head-sharded page pool serves through
    shard_map (paged decode kernel on local head shards) — oracle-exact,
    pages recycle."""
    from jax.sharding import Mesh, NamedSharding

    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    specs = tfm.shard_specs(CFG, tp_axis="model")
    sharded = jax.device_put(params, jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs))
    rng = np.random.default_rng(19)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (6, 45, 19)]

    cb = ContinuousBatcher(sharded, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32, 64),
                           paged=True, decode_kernel=True, mesh=mesh)
    results = cb.run(prompts, max_new=8)
    for rid, p in enumerate(prompts):
        np.testing.assert_array_equal(results[rid], _greedy_oracle(params, p, 8, decode_kernel=True))
    assert len(cb.free_pages) == cb.pool_pages - 1


# -- in-batcher speculation ---------------------------------------------------

SPEC_CFG = tfm.TransformerConfig(vocab_size=64, d_model=64, n_layers=2,
                                 n_heads=2, head_dim=32, d_ff=128)


@pytest.fixture(scope="module")
def spec_params():
    return tfm.init(jax.random.key(0), SPEC_CFG)


def _spec_workload():
    rng = np.random.default_rng(0)
    prompts = [np.tile(np.asarray([5, 9, 23, 7], np.int32), 6),
               rng.integers(0, 64, (9,)).astype(np.int32),
               np.tile(np.asarray([3, 11], np.int32), 8),
               rng.integers(0, 64, (15,)).astype(np.int32),
               np.tile(np.asarray([40, 2, 19], np.int32), 5)]
    budgets = [18, 7, 25, 12, 21]
    return prompts, budgets


def _spec_oracle(spec_params, prompts, budgets):
    return [np.asarray(gen.generate(
        spec_params, jnp.asarray(p)[None], jax.random.key(0), cfg=SPEC_CFG,
        max_new=b, temperature=0.0))[0] for p, b in zip(prompts, budgets)]


@pytest.mark.parametrize("kw", [dict(), dict(paged=True),
                                dict(paged=True, pool_pages=3)])
def test_spec_serving_oracle_exact(spec_params, kw):
    """In-batcher speculation (round-4 VERDICT #1): greedy serving with
    per-slot prompt-lookup proposals + one multi-token ragged verify per
    round is EXACTLY the non-speculative greedy stream for every request
    (f32), across slot recycling, in-block refill handoff, mixed
    lookup-friendly/hostile prompts, dense and paged pools — and the
    lookup-friendly workload actually accepts proposals."""
    prompts, budgets = _spec_workload()
    want = _spec_oracle(spec_params, prompts, budgets)
    cb = ContinuousBatcher(spec_params, SPEC_CFG, slots=2, max_len=512,
                           temperature=0.0, steps_per_sync=4,
                           prompt_buckets=(32,), speculate=4, **kw)
    rids = [cb.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    while cb.pending():
        cb.step()
    for r in rids:
        np.testing.assert_array_equal(cb.result(r), want[r])
    s = cb.stats
    assert s["spec_rounds"] > 0 and s["spec_proposed"] > 0
    assert 0 < s["spec_accepted"] <= s["spec_proposed"]
    # the speedup identity: tokens per weight pass > 1 requires accepted
    # proposals; on this half-repetitive workload acceptance is real
    assert s["spec_accepted"] / s["spec_proposed"] > 0.1, s


def test_spec_serving_eos_exact(spec_params):
    prompts, budgets = _spec_workload()
    p = prompts[0]
    ref = _spec_oracle(spec_params, [p], [18])[0]
    eos = int(ref[len(p) + 3])
    weos = np.asarray(gen.generate(
        spec_params, jnp.asarray(p)[None], jax.random.key(0), cfg=SPEC_CFG,
        max_new=18, temperature=0.0, eos_id=eos))[0]
    cut = int(np.where(weos[len(p):] == eos)[0][0]) + 1
    cb = ContinuousBatcher(spec_params, SPEC_CFG, slots=2, max_len=512,
                           temperature=0.0, steps_per_sync=4,
                           prompt_buckets=(32,), speculate=4)
    rid = cb.submit(p, max_new=18, eos_id=eos)
    while cb.pending():
        cb.step()
    np.testing.assert_array_equal(cb.result(rid), weos[:len(p) + cut])


def test_spec_serving_preemption_exact(spec_params):
    """Speculation x host-swap preemption: an oversubscribed page pool
    that actually evicts mid-generation still produces the exact greedy
    streams (the swapped pages restore bitwise; spec windows clamp at
    the restored frontier)."""
    rng = np.random.default_rng(3)
    # IDENTICAL requests progress in lockstep (same greedy stream, same
    # acceptance), so both cross the 512-token page boundary in the SAME
    # block — with only 3 usable pages for 2x2 needed, the second
    # crosser must preempt deterministically (no timing luck)
    p = np.tile(rng.integers(0, 64, (4,)).astype(np.int32), 8)
    prompts = [p, p]
    budgets = [610, 610]
    want = _spec_oracle(spec_params, prompts, budgets)
    cb = ContinuousBatcher(spec_params, SPEC_CFG, slots=2, max_len=1024,
                           temperature=0.0, steps_per_sync=4,
                           prompt_buckets=(32,), speculate=4,
                           paged=True, pool_pages=4)
    rids = [cb.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    while cb.pending():
        cb.step()
    for r in rids:
        np.testing.assert_array_equal(cb.result(r), want[r])
    assert cb.stats["evictions"] > 0 and cb.stats["swap_ins"] > 0, cb.stats


def test_spec_serving_tp_exact(spec_params):
    """Speculation through tensor-parallel serving: the verify forward
    runs inside shard_map on Megatron shards with a head-sharded pool."""
    from jax.sharding import Mesh, NamedSharding
    prompts, budgets = _spec_workload()
    want = _spec_oracle(spec_params, prompts, budgets)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    specs = tfm.shard_specs(SPEC_CFG, tp_axis="model")
    sharded = jax.device_put(spec_params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs))
    cb = ContinuousBatcher(sharded, SPEC_CFG, slots=2, max_len=512,
                           temperature=0.0, steps_per_sync=4,
                           prompt_buckets=(32,), speculate=4, mesh=mesh)
    rids = [cb.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    while cb.pending():
        cb.step()
    for r in rids:
        np.testing.assert_array_equal(cb.result(r), want[r])


def test_spec_serving_sampled_distribution(spec_params):
    """Sampled in-batcher speculation preserves the warped target
    distribution: the serve block's OWN point-mass rejection sampler
    (independent of generate.py's) is pinned against the analytic
    marginal of generated position 1, with plain (speculate=0) sampled
    serving as the calibration at the same sample count.

    768 samples (4 reps x 192 queued requests through 8 slots — the
    refill paths reuse the compiled block, so extra requests are cheap)
    put the TV sampling noise near 0.085 over vocab 64, making the 0.13
    absolute tolerance comparable to the generate.py pin rather than the
    old 72-sample ~0.45-noise gross-bias check (ADVICE r5 #4)."""
    from tests.test_lm_data_gen import _marginal_pos1
    prompt = np.asarray([3, 17, 5, 9], np.int32)
    temperature = 1.0
    want = _marginal_pos1(spec_params, SPEC_CFG,
                          jnp.asarray(prompt)[None], temperature, None,
                          None)

    def harvest(speculate, reps=4, slots=8, requests=192):
        toks = []
        for rep in range(reps):
            cb = ContinuousBatcher(spec_params, SPEC_CFG, slots=slots,
                                   max_len=512, temperature=temperature,
                                   steps_per_sync=2,
                                   prompt_buckets=(32,),
                                   speculate=speculate, seed=100 + rep)
            rids = [cb.submit(prompt, max_new=2)
                    for _ in range(requests)]
            while cb.pending():
                cb.step()
            toks += [cb.result(r)[len(prompt) + 1] for r in rids]
        emp = np.bincount(np.asarray(toks), minlength=SPEC_CFG.vocab_size)
        return 0.5 * np.abs(emp / len(toks) - want).sum()

    tv_spec = harvest(speculate=3)
    tv_plain = harvest(speculate=0)  # calibrates the harness itself
    assert tv_plain < 0.13, tv_plain
    assert tv_spec < 0.13, (tv_spec, tv_plain)


def test_spec_serving_stats_identity(spec_params):
    """Speculation accounting: dispatched verify positions bound useful
    work, and utilization() stays the single coherent source."""
    prompts, budgets = _spec_workload()
    cb = ContinuousBatcher(spec_params, SPEC_CFG, slots=2, max_len=512,
                           temperature=0.0, steps_per_sync=4,
                           prompt_buckets=(32,), speculate=4)
    cb.run(prompts, max_new=8)
    s = cb.stats
    useful = (s["emitted_tokens"] - s["batch_admissions"]
              + s["inblock_prefill_steps"])
    assert 0 < useful <= s["slot_steps"] + s["spec_rounds"] * cb.slots, s
    assert s["slot_steps"] == s["spec_rounds"] * cb.slots * (cb.n_spec + 1)
    assert abs(cb.utilization()
               - useful / s["slot_steps"]) < 1e-9


def test_spec_acceptance_adjusted_utilization_pinned(spec_params):
    """Acceptance-adjusted utilization under speculation (VERDICT r5
    weak #4): the batcher reports BOTH raw dispatch utilization (verify
    positions in the denominator — reads low by design when proposals
    are rejected) and emitted-tokens-per-slot-step, and for a greedy
    ``speculate>0`` workload both are deterministic — two identical runs
    pin identical values satisfying the accounting identities."""
    prompts, budgets = _spec_workload()

    def make():
        return ContinuousBatcher(spec_params, SPEC_CFG, slots=2,
                                 max_len=512, temperature=0.0,
                                 steps_per_sync=4, prompt_buckets=(32,),
                                 speculate=4)

    def run():
        cb = make()
        cb.run(prompts, max_new=8)
        return cb

    a, b = run(), run()
    assert a.stats == b.stats  # greedy: fully deterministic
    assert a.utilization() == b.utilization()
    assert a.emitted_per_slot_step() == b.emitted_per_slot_step()
    s = a.stats
    assert a.emitted_per_slot_step() == (
        (s["emitted_tokens"] - s["batch_admissions"])
        / s["slot_steps"])
    assert abs(a.utilization() - a.emitted_per_slot_step()
               - s["inblock_prefill_steps"] / s["slot_steps"]) < 1e-12
    # both live in (0, 1]; the adjusted metric never exceeds the raw one
    assert 0.0 < a.emitted_per_slot_step() <= a.utilization() <= 1.0
    # and the bench_serving JSON carries both keys
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    import bench_serving as bs
    rep = bs.run(make(), prompts, [8] * len(prompts))
    assert rep["utilization"] == round(a.utilization(), 4)
    assert rep["emitted_per_slot_step"] == round(
        a.emitted_per_slot_step(), 4)


# -- prefix caching -----------------------------------------------------------

def _prefix_oracle(spec_params, p, b):
    return np.asarray(gen.generate(
        spec_params, jnp.asarray(p)[None], jax.random.key(0), cfg=SPEC_CFG,
        max_new=b, temperature=0.0))[0]


def test_prefix_cache_shared_prompt_workload(spec_params):
    """Prefix caching (round-4 VERDICT #3): N requests sharing a >1-page
    system prompt admit over the SAME cached pages — prefill work drops
    to one full prefill + per-request suffix dispatches, pages in use
    drop ~Nx, outputs stay oracle-exact, and the registry persists
    across retirements (a later wave is all hits)."""
    rng = np.random.default_rng(0)
    sysp = rng.integers(0, 64, (520,)).astype(np.int32)
    prompts = [np.concatenate([sysp,
                               rng.integers(0, 64, (6,)).astype(np.int32)])
               for _ in range(4)]
    cb = ContinuousBatcher(spec_params, SPEC_CFG, slots=2, max_len=1024,
                           temperature=0.0, steps_per_sync=4,
                           prompt_buckets=(32, 1024), paged=True,
                           prefix_cache=True)
    rids = [cb.submit(p, max_new=6) for p in prompts]
    while cb.pending():
        cb.step()
    for r, p in zip(rids, prompts):
        np.testing.assert_array_equal(cb.result(r),
                                      _prefix_oracle(spec_params, p, 6))
    s = cb.stats
    # one full prefill registered the page; the other three shared it
    assert s["prefix_hits"] == 3 and s["prefix_pages_shared"] == 3, s
    # page economy: at any point a sharing slot owns 1 shared + 1 fresh
    # page instead of 2 private ones; across the run the single shared
    # page replaced 3 private prefix pages
    assert len(cb.registry) == 1
    pid = next(iter(cb.registry.values()))
    assert cb.page_refs[pid] == 0  # all retired; cached for the future

    # second wave: every admission hits the persistent registry
    rids = [cb.submit(p, max_new=6) for p in prompts]
    while cb.pending():
        cb.step()
    for r, p in zip(rids, prompts):
        np.testing.assert_array_equal(cb.result(r),
                                      _prefix_oracle(spec_params, p, 6))
    assert cb.stats["prefix_hits"] == 7, cb.stats


def test_prefix_cache_reclaim_under_pressure(spec_params):
    """Registry pages yield to live work: distinct cached prefixes are
    reclaimed FIFO when the free list runs dry, instead of preempting
    occupants or failing admissions — and reuse stays exact afterward."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, (513,)).astype(np.int32)
               for _ in range(4)]  # 4 DISTINCT 1-full-page prefixes
    cb = ContinuousBatcher(spec_params, SPEC_CFG, slots=2, max_len=1024,
                           temperature=0.0, steps_per_sync=4,
                           prompt_buckets=(32, 1024), paged=True,
                           prefix_cache=True, pool_pages=6)
    rids = [cb.submit(p, max_new=4) for p in prompts]
    while cb.pending():
        cb.step()
    for r, p in zip(rids, prompts):
        np.testing.assert_array_equal(cb.result(r),
                                      _prefix_oracle(spec_params, p, 4))
    s = cb.stats
    # 5 usable pages cannot hold 4 registered prefixes + 2x2 live pages:
    # old registrations were reclaimed to keep admissions flowing
    assert s["prefix_reclaimed"] > 0, s
    assert len(cb.registry) + len(cb.free_pages) == cb.pool_pages - 1


def test_prefix_cache_composes_with_speculation(spec_params):
    """prefix_cache x speculate: shared-prefix admission then
    speculative decode — exact streams, hits recorded, and the spec
    window's clamped writes never corrupt the shared pages (a second
    shared-prefix wave decodes identically)."""
    rng = np.random.default_rng(2)
    sysp = np.tile(rng.integers(0, 64, (8,)).astype(np.int32), 65)[:516]
    prompts = [np.concatenate([sysp,
                               rng.integers(0, 64, (5,)).astype(np.int32)])
               for _ in range(3)]
    cb = ContinuousBatcher(spec_params, SPEC_CFG, slots=2, max_len=1024,
                           temperature=0.0, steps_per_sync=4,
                           prompt_buckets=(32, 1024), paged=True,
                           prefix_cache=True, speculate=4)
    for wave in range(2):
        rids = [cb.submit(p, max_new=12) for p in prompts]
        while cb.pending():
            cb.step()
        for r, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                cb.result(r), _prefix_oracle(spec_params, p, 12))
    assert cb.stats["prefix_hits"] >= 5, cb.stats
    assert cb.stats["spec_rounds"] > 0


# -- scheduling fairness ------------------------------------------------------

def test_lpt_delays_short_requests(spec_params):
    """The fairness cost of longest_first (round-4 VERDICT #10): LPT
    admits the largest budgets first, so a SHORT request submitted first
    gets its first token strictly LATER (in step() calls — the
    deterministic clock behind the wall-clock TTFT percentiles) than
    under fifo, which serves it immediately.  This pins the trade the
    latency_stats exist to expose."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, (6,)).astype(np.int32)
               for _ in range(4)]
    budgets = [4, 60, 50, 40]  # the short request arrives FIRST

    def first_emit_step(schedule):
        cb = ContinuousBatcher(spec_params, SPEC_CFG, slots=2,
                               max_len=512, temperature=0.0,
                               steps_per_sync=4, prompt_buckets=(32,),
                               schedule=schedule)
        rids = [cb.submit(p, max_new=b)
                for p, b in zip(prompts, budgets)]
        first, step_i = {}, 0
        while cb.pending():
            step_i += 1
            for rid, _ in cb.step():
                first.setdefault(rid, step_i)
        return {r: first[r] for r in rids}, rids[0]

    fifo, short = first_emit_step("fifo")
    lpt, _ = first_emit_step("longest_first")
    assert fifo[short] == 1, fifo       # fifo serves the head immediately
    assert lpt[short] > fifo[short], (lpt, fifo)


# -- overlapped dispatch (round 6) --------------------------------------------

def _ragged_workload(seed, n, lens=(5, 17, 40, 9, 23)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (lens[i % len(lens)],)).astype(np.int32)
            for i in range(n)]


@pytest.mark.parametrize("kw", [dict(), dict(paged=True),
                                dict(schedule="longest_first")])
def test_overlap_oracle_exact(params, kw):
    """The tentpole's oracle: overlapped dispatch (device-carried block
    chaining, deferred fetch/parse) emits EXACTLY the serial greedy
    streams across slot recycling, in-block refill handoffs riding
    chained blocks, dense and paged pools — and the pipeline actually
    chained (the stats prove the fetch RTT had something to hide
    under)."""
    prompts = _ragged_workload(30, 5)
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32, 64),
                           steps_per_sync=8, overlap=True, **kw)
    results = cb.run(prompts, max_new=24)
    assert cb.stats["chained_dispatches"] > 0, cb.stats
    for rid, prompt in enumerate(prompts):
        np.testing.assert_array_equal(
            results[rid], _greedy_oracle(params, prompt, 24))


def test_overlap_interleaved_submission_exact(params):
    """Submissions landing while a chained block is in flight still come
    out oracle-exact: the chain breaks for admission at the next
    eligible step, never mid-request."""
    rng = np.random.default_rng(31)
    pa = rng.integers(0, 256, (6,)).astype(np.int32)
    pb = rng.integers(0, 256, (14,)).astype(np.int32)
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32,),
                           steps_per_sync=4, overlap=True)
    ra = cb.submit(pa, max_new=20)
    cb.step()
    cb.step()
    rb = cb.submit(pb, max_new=10)  # lands mid-pipeline
    while cb.pending():
        cb.step()
    np.testing.assert_array_equal(cb.result(ra),
                                  _greedy_oracle(params, pa, 20))
    np.testing.assert_array_equal(cb.result(rb),
                                  _greedy_oracle(params, pb, 10))


def test_overlap_eos_mid_chain_exact(params):
    """An armed EOS firing inside a chained block retires the request
    exactly (the slot idles out the chain; the parsed retirement then
    breaks it) — stream identical to the serial run."""
    rng = np.random.default_rng(32)
    p = rng.integers(0, 256, (8,)).astype(np.int32)
    oracle = _greedy_oracle(params, p, 40)
    eos = int(oracle[len(p) + 9])  # fires a few blocks in
    first_hit = next(i for i in range(40)
                     if int(oracle[len(p) + i]) == eos)

    def run(overlap):
        cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                               temperature=0.0, prompt_buckets=(32,),
                               steps_per_sync=4, overlap=overlap)
        r = cb.submit(p, max_new=40, eos_id=eos)
        while cb.pending():
            cb.step()
        return cb, cb.result(r)

    cb_on, out_on = run(True)
    cb_off, out_off = run(False)
    np.testing.assert_array_equal(out_on, out_off)
    assert out_on[-1] == eos and len(out_on) == len(p) + first_hit + 1


def test_overlap_accounting_matches_serial(params):
    """Satellite pin: on a pure-decode workload (budgets >> K, no
    retirement boundary mid-chain) the overlapped pipeline dispatches
    the IDENTICAL block sequence — decode_dispatches, slot_steps, and
    the whole accounting identity equal the serial run, with
    chained_dispatches > 0 proving the pipeline engaged (and == 0
    serial)."""
    prompts = _ragged_workload(33, 2, lens=(7, 11))

    def run(overlap):
        cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                               temperature=0.0, prompt_buckets=(32,),
                               steps_per_sync=4, overlap=overlap)
        res = cb.run(prompts, max_new=30)
        return cb, res

    cb_on, r_on = run(True)
    cb_off, r_off = run(False)
    for rid in r_off:
        np.testing.assert_array_equal(r_on[rid], r_off[rid])
    for key in ("decode_dispatches", "slot_steps", "emitted_tokens",
                "inblock_prefill_steps", "wasted_slot_steps",
                "batch_admissions", "prefill_dispatches"):
        assert cb_on.stats[key] == cb_off.stats[key], (
            key, cb_on.stats, cb_off.stats)
    assert cb_on.stats["chained_dispatches"] > 0
    assert cb_off.stats["chained_dispatches"] == 0
    s = cb_on.stats
    assert s["slot_steps"] == (s["emitted_tokens"] - s["batch_admissions"]
                               + s["inblock_prefill_steps"]
                               + s["wasted_slot_steps"]), s


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_overlap_zero_recompiles(params, kv_dtype):
    """Compile-counter pin: chaining reuses the ONE compiled block
    program (the carry is an ordinary input — serial staging and
    device-fed chaining share shapes/dtypes), so an overlapped run adds
    zero executable cache entries beyond the serial run's — on the int8
    KV path too (quantize/dequantize live INSIDE the block program;
    the scale leaves are ordinary donated cache inputs)."""
    prompts = _ragged_workload(34, 4)

    def make(overlap):
        return ContinuousBatcher(params, CFG, slots=2, max_len=512,
                                 temperature=0.0, prompt_buckets=(32, 64),
                                 steps_per_sync=8, overlap=overlap,
                                 kv_dtype=kv_dtype)

    cb_off = make(False)
    cb_off.run(prompts, max_new=20)

    def sizes(cb):
        return {k: f._cache_size() for k, f in cb._decode_fns.items()}

    before = sizes(cb_off)
    cb_on = make(True)
    # share every compiled fn (scripts/bench_serving.warm_clone's list)
    for attr in ("_prefill_fns", "_chunk_fns", "_decode_fns", "_spec_fns",
                 "_suffix_fns", "_insert_fn", "_insert_paged_fn"):
        if hasattr(cb_off, attr):
            setattr(cb_on, attr, getattr(cb_off, attr))
    cb_on.run(prompts, max_new=20)
    assert cb_on.stats["chained_dispatches"] > 0
    assert sizes(cb_on) == before, (sizes(cb_on), before)


def test_overlap_donation_on_off_bitwise(params, monkeypatch):
    """Satellite pin: the clean (greedy f32) serving path is bitwise
    identical with buffer donation forced ON vs OFF — donation is a
    memory optimization, never a numerics change."""
    from distributed_pytorch_tpu.utils import compat

    prompts = _ragged_workload(35, 3)

    def run():
        cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                               temperature=0.0, prompt_buckets=(32, 64),
                               steps_per_sync=4, paged=True, overlap=True)
        return cb.run(prompts, max_new=10)

    monkeypatch.setattr(compat, "DONATION_SAFE", False)
    off = run()
    monkeypatch.setattr(compat, "DONATION_SAFE", True)
    on = run()
    assert set(on) == set(off)
    for rid in off:
        np.testing.assert_array_equal(on[rid], off[rid])


def test_timing_stats_phases(params):
    """The per-phase timer layer: a serving run attributes wall clock to
    host_plan / dispatch / fetch / host_parse (+ prefill), every block
    lands one fetch segment, and the summary carries p50/p95."""
    prompts = _ragged_workload(36, 3)
    cb = ContinuousBatcher(params, CFG, slots=2, max_len=512,
                           temperature=0.0, prompt_buckets=(32, 64),
                           steps_per_sync=8, overlap=True)
    cb.run(prompts, max_new=12)
    ts = cb.timing_stats()
    for phase in ("host_plan", "dispatch", "fetch", "host_parse"):
        assert phase in ts, (phase, ts.keys())
        assert ts[phase]["segments"] > 0
        assert ts[phase]["total_s"] >= 0
        assert {"p50_s", "p95_s", "max_s"} <= set(ts[phase])
    assert ts["fetch"]["segments"] == cb.stats["decode_dispatches"]
    assert ts["_total_s"] > 0
