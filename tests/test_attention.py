"""Flash-attention kernel tests (ops/attention.py).

The Pallas kernels run in interpret mode on CPU — the identical kernel code
path that compiles on TPU (tests/conftest.py pins the cpu backend).  The
oracle is ``attention_reference``, plain XLA attention.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.ops import attention as attn

B, H, S, D = 2, 2, 256, 64


pytestmark = pytest.mark.quick  # sub-2-min tier (tests/conftest.py)

def _qkv(dtype=jnp.float32, s=S):
    key = jax.random.key(0)
    return tuple(
        jax.random.normal(jax.random.fold_in(key, i), (B, H, s, D), dtype)
        for i in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference_fwd(causal):
    q, k, v = _qkv()
    ref = attn.attention_reference(q, k, v, causal=causal)
    out = attn.flash_attention(q, k, v, causal=causal, block_q=128,
                               block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference_grads(causal):
    q, k, v = _qkv()

    def loss(f):
        def inner(q, k, v):
            return jnp.sum(jnp.sin(f(q, k, v)))
        return inner

    ref_fn = loss(lambda q, k, v: attn.attention_reference(
        q, k, v, causal=causal))
    fl_fn = loss(lambda q, k, v: attn.flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128))
    g_ref = jax.grad(ref_fn, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(fl_fn, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4)


def test_uneven_blocks_and_rect():
    """q/k block sizes that differ and tile the sequence unevenly."""
    q, k, v = _qkv(s=384)
    ref = attn.attention_reference(q, k, v, causal=True)
    out = attn.flash_attention(q, k, v, causal=True, block_q=128, block_k=192)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_cross_attention_shapes():
    """sq != sk (non-causal cross attention)."""
    key = jax.random.key(1)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, H, 128, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, 384, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, 384, D))
    ref = attn.attention_reference(q, k, v)
    out = attn.flash_attention(q, k, v, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_jit_and_vmap_compose():
    q, k, v = _qkv()
    f = jax.jit(lambda q, k, v: attn.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128))
    out = f(q, k, v)
    assert out.shape == q.shape


def test_input_validation():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="B, H, S, D"):
        attn.flash_attention(q[0], k[0], v[0])
    with pytest.raises(ValueError, match="divide"):
        attn.flash_attention(q, k, v, block_q=96)
    with pytest.raises(ValueError, match="causal"):
        attn.flash_attention(
            q[:, :, :128], k, v, causal=True, block_q=128, block_k=128)


def test_reference_lse():
    """with_lse returns the softmax normalizer ring attention merges on."""
    q, k, v = _qkv()
    o, lse = attn.attention_reference(q, k, v, with_lse=True)
    assert lse.shape == (B, H, S)
    # exp(lse) must equal the softmax partition function
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(s, -1)),
        atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_with_lse_matches_reference(causal):
    q, k, v = _qkv()
    o_ref, lse_ref = attn.attention_reference(q, k, v, causal=causal,
                                              with_lse=True)
    o, lse = attn.flash_attention(q, k, v, causal=causal, block_q=128,
                                  block_k=128, with_lse=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_lse_cotangent_matches_reference(causal):
    """Loss uses BOTH outputs, so the backward must handle the lse cotangent
    — the exact contract of ring attention's online-softmax merge."""
    q, k, v = _qkv()

    def loss(f):
        def inner(q, k, v):
            o, lse = f(q, k, v)
            return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))
        return inner

    ref_fn = loss(lambda q, k, v: attn.attention_reference(
        q, k, v, causal=causal, with_lse=True))
    fl_fn = loss(lambda q, k, v: attn.flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128, with_lse=True))
    g_ref = jax.grad(ref_fn, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(fl_fn, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4)


def test_default_blocks_fit_any_8_aligned_seq():
    """Defaults auto-shrink to divide the sequence (e.g. 1536 is a multiple
    of 256/512 but not of the 512/1024 defaults)."""
    key = jax.random.key(5)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 1, 192, 64))
               for i in range(3))
    ref = attn.attention_reference(q, k, v, causal=True)
    out = attn.flash_attention(q, k, v, causal=True)  # default blocks
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="8-aligned"):
        attn.flash_attention(q[:, :, :100], k[:, :, :100], v[:, :, :100])


# ---------------------------------------------------------------------------
# Decode kernel (single-token query over a KV cache, exact pos+1 bounds)
# ---------------------------------------------------------------------------

def _decode_oracle(q, kc, vc, pos):
    """attention_reference over the repeated-head cache with the cache-
    validity bias — the XLA decode path of generate._forward_cached."""
    rep = q.shape[1] // kc.shape[1]
    ka = jnp.repeat(kc, rep, axis=1)
    va = jnp.repeat(vc, rep, axis=1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, kc.shape[2]), 1)
    bias = jnp.where(slot <= pos, 0.0, attn.NEG_INF)[None, None]
    return attn.attention_reference(q, ka, va, bias=bias)


@pytest.mark.parametrize("hkv", [1, 2, 4])
def test_decode_attention_matches_reference(hkv):
    """GQA group sizes 4/2/1 (hkv=4 is MHA), positions spanning first
    block / mid-buffer / last slot."""
    key = jax.random.key(3)
    b, h, s, d = 2, 4, 256, 64
    q = jax.random.normal(jax.random.fold_in(key, 0), (b, h, 1, d))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, s, d))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, s, d))
    for pos in (0, 5, 130, s - 1):
        out = attn.decode_attention(q, kc, vc, jnp.int32(pos), block_k=128)
        ref = _decode_oracle(q, kc, vc, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_decode_attention_ignores_garbage_past_pos():
    """Slots beyond pos must not leak: fill the dead tail with huge values
    and check the output is untouched (the exact-read-bound property)."""
    key = jax.random.key(4)
    b, h, s, d = 1, 2, 256, 64
    pos = 100
    q = jax.random.normal(jax.random.fold_in(key, 0), (b, h, 1, d))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (b, h, s, d))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (b, h, s, d))
    out_clean = attn.decode_attention(q, kc, vc, jnp.int32(pos), block_k=64)
    kc_dirty = kc.at[:, :, pos + 1:].set(1e4)
    vc_dirty = vc.at[:, :, pos + 1:].set(-1e4)
    out_dirty = attn.decode_attention(q, kc_dirty, vc_dirty, jnp.int32(pos),
                                      block_k=64)
    np.testing.assert_array_equal(np.asarray(out_clean),
                                  np.asarray(out_dirty))


def test_decode_attention_validates_shapes():
    q = jnp.zeros((1, 4, 2, 64))  # sq=2: not a single-token query
    kc = vc = jnp.zeros((1, 4, 256, 64))
    with pytest.raises(ValueError, match="single-token"):
        attn.decode_attention(q, kc, vc, jnp.int32(0))
    q3 = jnp.zeros((1, 3, 1, 64))  # 3 q heads over 4 kv heads
    with pytest.raises(ValueError, match="group"):
        attn.decode_attention(q3, kc, vc, jnp.int32(0))


def test_decode_attention_per_sequence_positions():
    """Ragged batches: pos as a (B,) vector gives each sequence its own
    exact read bound (the continuous-batching primitive)."""
    key = jax.random.key(9)
    b, h, hkv, s, d = 3, 4, 2, 256, 64
    q = jax.random.normal(jax.random.fold_in(key, 0), (b, h, 1, d))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, s, d))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, s, d))
    pos = jnp.array([7, 130, 255], jnp.int32)
    out = attn.decode_attention(q, kc, vc, pos, block_k=64)
    for i in range(b):
        ref = _decode_oracle(q[i:i + 1], kc[i:i + 1], vc[i:i + 1],
                             int(pos[i]))
        np.testing.assert_allclose(np.asarray(out[i:i + 1]),
                                   np.asarray(ref), atol=2e-5, rtol=2e-5)
    # garbage beyond each sequence's own bound must not leak
    kc_dirty = kc.at[0, :, 8:].set(1e4)
    out_dirty = attn.decode_attention(q, kc_dirty, vc, pos, block_k=64)
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.asarray(out_dirty[0]))


def test_paged_decode_matches_dense():
    """The paged decode kernel == the dense kernel when the dense cache's
    blocks are scattered into a shuffled pool and the table maps them
    back — per-sequence exact pos bounds included, garbage table tails
    never dereferenced."""
    rng = np.random.default_rng(0)
    b, h, hkv, d, s, page = 3, 4, 2, 32, 1024, 512
    n_pages = s // page
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    pos = jnp.asarray([37, 700, 1023], jnp.int32)

    want = attn.decode_attention(q, k, v, pos, block_k=page)

    # scatter dense blocks into a shuffled pool (plus spare garbage pages)
    p_total = b * n_pages + 3
    perm = rng.permutation(b * n_pages)
    k_pool = jnp.asarray(rng.standard_normal((p_total, hkv, page, d)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((p_total, hkv, page, d)),
                         jnp.float32)
    table = np.full((b, n_pages), 999_999, np.int32)  # poison the tails
    for bb in range(b):
        for j in range(n_pages):
            pid = int(perm[bb * n_pages + j]) + 3  # skip the garbage pages
            k_pool = k_pool.at[pid].set(k[bb, :, j * page:(j + 1) * page])
            v_pool = v_pool.at[pid].set(v[bb, :, j * page:(j + 1) * page])
            table[bb, j] = pid
    # poison entries past each sequence's live pages: must never be read
    for bb in range(b):
        live = int(pos[bb]) // page
        table[bb, live + 1:] = 0  # points at garbage page 0

    got = attn.decode_attention_paged(q, k_pool, v_pool,
                                      jnp.asarray(table), pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# The backward's plan: one fused kernel, or dQ and dK/dV apart
# ---------------------------------------------------------------------------

def _rectangular_qkv(sq, sk, dtype):
    key = jax.random.key(7)
    return tuple(jax.random.normal(jax.random.fold_in(key, i),
                                   (B, H, s, D), dtype)
                 for i, s in enumerate((sq, sk, sk)))


def _backward(case, dtype):
    """Gradients of a loss over ``flash_attention``'s outputs, ``case``
    naming the keyword arguments and the sequence lengths."""
    kw, sq, sk = case
    q, k, v = _rectangular_qkv(sq, sk, dtype)

    def loss(q, k, v):
        out = attn.flash_attention(q, k, v, block_q=128, block_k=128, **kw)
        if kw.get("with_lse"):   # a non-zero lse cotangent
            return (jnp.sum(jnp.sin(out[0].astype(jnp.float32)))
                    + jnp.sum(jnp.cos(out[1])))
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


BACKWARD_CASES = {
    "noncausal": ({}, 512, 512),
    "causal": ({"causal": True}, 512, 512),
    # the band's far edge falls inside a block: keys 200 behind, blocks of 128
    "window_across_blocks": ({"causal": True, "window": 200}, 512, 512),
    "rectangular": ({}, 256, 640),
    "with_lse": ({"causal": True, "with_lse": True}, 512, 512),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(BACKWARD_CASES))
def test_fused_backward_equals_the_two_kernels_bit_for_bit(
        monkeypatch, case, dtype):
    """One recomputation of a tile's scores feeds dQ, dK and dV; the sums
    run in the order the dQ and the dK/dV kernels run them, so nothing
    differs, not by a rounding."""
    shapes = BACKWARD_CASES[case]
    assert attn._bwd_fuses(shapes[1], shapes[2], D, dtype)
    fused = _backward(shapes, dtype)
    monkeypatch.setattr(attn, "_FUSED_BWD_VMEM_BUDGET", 0)
    assert not attn._bwd_fuses(shapes[1], shapes[2], D, dtype)
    apart = _backward(shapes, dtype)
    for a, b in zip(fused, apart):
        assert a.dtype == b.dtype == dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_backward_plan_follows_the_shape(monkeypatch):
    """Fused at both training cells' attention, the two kernels once the
    whole-sequence dQ buffers pass the budget (ring attention's long local
    chunks); and past the budget the gradients are still the reference's."""
    assert attn._bwd_fuses(4096, 4096, 128, jnp.bfloat16)
    assert attn._bwd_fuses(8192, 8192, 128, jnp.bfloat16)
    assert not attn._bwd_fuses(32768, 32768, 128, jnp.bfloat16)
    assert not attn._bwd_fuses(16384, 16384, 128, jnp.float32)

    q, k, v = _qkv()

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v, causal=True)))

    def flash():    # a new function a call: a trace is kept by its function
        return jax.grad(loss(functools.partial(
            attn.flash_attention, block_q=128, block_k=128)),
            argnums=(0, 1, 2))

    def kernels():
        return str(jax.make_jaxpr(flash())(q, k, v)).count("pallas_call[")

    assert kernels() == 2           # forward, fused backward
    monkeypatch.setattr(attn, "_FUSED_BWD_VMEM_BUDGET", 0)
    assert kernels() == 3           # forward, dQ, dK/dV
    g_ref = jax.grad(loss(attn.attention_reference),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = flash()(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# A causal call's dead tiles: no compute, and no copy either
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("keys,window,counts", [
    (4096, None, (6, 10)),          # lm_train_4k / lm_train_dp4
    (8192, None, (28, 36)),         # the global layers at 8,192 keys
    (8192, 4096, (34, 30)),         # SmallThinker's windowed layers
    (8192, 512, (49, 15)),          # Laguna's
    (8192, 2500, (38, 26)),         # no multiple of the block: 1 + 2 + 3 + 5 x 4
    (4096, 4096, (6, 10)),          # a window over everything is none
])
def test_tile_census_at_the_cells_shapes(keys, window, counts):
    census = attn.tile_census(keys, keys, 1024, 1024, True, window)
    assert census == counts and sum(census) == (keys // 1024) ** 2
    assert attn.tile_census(keys, keys, 1024, 1024, False) == (
        0, (keys // 1024) ** 2)


@pytest.mark.parametrize("blocks", [(128, 128), (128, 192), (192, 128)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("window", [None, 1, 128, 200, 384, 767])
def test_the_index_maps_name_the_nearest_live_tile(blocks, window):
    """``_live_k_block`` and ``_live_q_block`` are ``_tile_live`` solved for
    one index: a live tile names itself, a dead one the nearest live tile of
    its row (column) of the grid, at every tile."""
    s, (bq, bk) = 768, blocks
    nq, nk = s // bq, s // bk
    tiles = attn._Tiling(True, bq, bk, window)
    live = np.array([[attn._tile_live(i, j, tiles) for j in range(nk)]
                     for i in range(nq)])
    assert live.any(axis=0).all() and live.any(axis=1).all()
    for i in range(nq):
        for j in range(nk):
            nearest_k = min(np.flatnonzero(live[i]), key=lambda t: abs(t - j))
            nearest_q = min(np.flatnonzero(live[:, j]),
                            key=lambda t: abs(t - i))
            assert int(attn._live_k_block(i, j, tiles)) == nearest_k
            assert int(attn._live_q_block(j, i, tiles, nq)) == nearest_q


DEAD_TILE_CASES = [
    (plan, window, with_lse)
    for plan in ("forward", "fused", "two_kernels")
    for window in (None, 128, 200, 384)
    for with_lse in (False, True)]


@pytest.mark.parametrize(
    "plan,window,with_lse", DEAD_TILE_CASES,
    ids=[f"{p}-w{w}-{'lse' if l else 'o'}" for p, w, l in DEAD_TILE_CASES])
def test_a_dead_step_reads_nothing_of_the_tile_it_names(
        monkeypatch, plan, window, with_lse):
    """512 keys in blocks of 128: 6 dead tiles of 16 without a window, 9 / 7
    / 6 under 128 / 200 / 384.  Outputs, lse and the three gradients equal,
    bit for bit, those of the same call with every step naming its own tile
    (the identity index maps, the program before dead steps were held): a
    live step is handed its own tile and a dead one reads none."""
    kw = {"causal": True, "window": window, "with_lse": with_lse}
    dtype = jnp.bfloat16
    assert attn.tile_census(512, 512, 128, 128, True, window).dead == {
        None: 6, 128: 9, 200: 7, 384: 6}[window]
    if plan == "two_kernels":
        monkeypatch.setattr(attn, "_FUSED_BWD_VMEM_BUDGET", 0)

    def run():      # a new function a call: a trace is kept by its function
        if plan != "forward":
            return _backward((kw, 512, 512), dtype)
        out = attn.flash_attention(*_rectangular_qkv(512, 512, dtype),
                                   block_q=128, block_k=128, **kw)
        return out if with_lse else (out,)

    got = run()
    monkeypatch.setattr(attn, "_live_k_block", lambda i, j, tiles: j)
    monkeypatch.setattr(attn, "_live_q_block", lambda j, i, tiles, nq: i)
    want = run()
    assert len(got) == len(want) == (
        3 if plan != "forward" else 1 + with_lse)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
