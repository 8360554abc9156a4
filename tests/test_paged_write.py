"""The paged pool's token write (``generate._paged_put``) against the
indexing it replaced, ``leaf.at[pids, :, offs].set(u)``, bit for bit.

The new form scatters along the two leading dimensions of the
(P * hkv, page, W) view so that the TPU compiler keeps the pool in the
layout the decode kernel reads (tests/test_chip_compile.py holds that
half); here: the same values land in the same rows, whatever the leaf's
dtype and width, where slots collide, and where an index is out of range.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import generate as gen

P, HKV, PAGE = 5, 2, 16
# a pool leaf's dtype and width: K/V in bf16 and int8, and a scale leaf
KINDS = {"bf16": (jnp.bfloat16, 8), "int8": (jnp.int8, 8),
         "f32_scale": (jnp.float32, 1)}


def _leaf(rng, dtype, w):
    x = rng.integers(-100, 100, (P, HKV, PAGE, w))
    return jnp.asarray(x, dtype)


def _rows(rng, shape, dtype, w):
    return jnp.asarray(rng.integers(-100, 100, shape + (HKV, w)), dtype)


def _old(leaf, pids, offs, u):
    return leaf.at[pids, :, offs].set(u)


# (page ids, row offsets): one entry per slot
CASES = {
    "distinct_pages": ([1, 2, 3, 4], [3, 0, 7, 9]),
    "first_and_last_row": ([1, 1, 4, 4], [0, PAGE - 1, 0, PAGE - 1]),
    "last_page_last_row": ([P - 1], [PAGE - 1]),
    "page_id_past_the_pool": ([1, P, 2, P + 7], [3, 3, 5, 0]),
    "page_id_far_below_zero": ([1, -P - 1, 2], [3, 3, 5]),
    "negative_wraps_as_numpy": ([-1, 1], [2, 2]),
    "offset_past_the_page": ([1, 2], [PAGE, 4]),
}


@pytest.mark.parametrize("leaf_kind", sorted(KINDS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_paged_put_matches_old_indexing(case, leaf_kind):
    dtype, w = KINDS[leaf_kind]
    rng = np.random.default_rng(sorted(CASES).index(case))
    pids, offs = (jnp.asarray(a, jnp.int32) for a in CASES[case])
    leaf = _leaf(rng, dtype, w)
    u = _rows(rng, pids.shape, dtype, w)
    new = jax.jit(gen._paged_put)(leaf, pids, offs, u)
    old = jax.jit(_old)(leaf, pids, offs, u)
    assert new.shape == leaf.shape and new.dtype == leaf.dtype
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
    # the dropped writes really are dropped: only in-range rows changed
    changed = np.argwhere((np.asarray(new) != np.asarray(leaf)).any(-1))
    legal = {(int(p) % P, int(o)) for p, o in zip(*CASES[case])
             if -P <= p < P and o < PAGE}
    assert {(int(p), int(o)) for p, _, o in changed} <= legal


@pytest.mark.parametrize("leaf_kind", ["bf16", "f32_scale"])
def test_paged_put_colliding_slots_on_scratch_page(leaf_kind):
    """Idle and finished slots all write row (0, :, 0) of scratch page 0:
    duplicates are legal and land in any order; every other row is exact."""
    dtype, w = KINDS[leaf_kind]
    rng = np.random.default_rng(7)
    pids = jnp.asarray([0, 3, 0, 0, 2, 0], jnp.int32)
    offs = jnp.asarray([0, 5, 0, 0, 15, 0], jnp.int32)
    leaf = _leaf(rng, dtype, w)
    u = _rows(rng, pids.shape, dtype, w)
    new = np.asarray(jax.jit(gen._paged_put)(leaf, pids, offs, u))
    old = np.asarray(jax.jit(_old)(leaf, pids, offs, u))
    np.testing.assert_array_equal(new[1:], old[1:])
    np.testing.assert_array_equal(new[0, :, 1:], np.asarray(leaf)[0, :, 1:])
    candidates = np.asarray(u)[np.asarray(pids) == 0]   # (4, hkv, w)
    for h in range(HKV):
        assert any((new[0, h, 0] == c[h]).all() for c in candidates)


@pytest.mark.parametrize("leaf_kind", sorted(KINDS))
def test_paged_put_token_window(leaf_kind):
    """The speculative verify window's write: (B, S) page ids and offsets,
    rows (B, S, hkv, W)."""
    dtype, w = KINDS[leaf_kind]
    rng = np.random.default_rng(11)
    pids = jnp.asarray([[1, 1, 2], [3, 3, 3], [4, P, 4]], jnp.int32)
    offs = jnp.asarray([[14, 15, 0], [0, 1, 2], [7, 8, 9]], jnp.int32)
    leaf = _leaf(rng, dtype, w)
    u = _rows(rng, pids.shape, dtype, w)
    new = jax.jit(gen._paged_put)(leaf, pids, offs, u)
    old = jax.jit(_old)(leaf, pids, offs, u)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_paged_decode_step_writes_quantized_rows_and_scales():
    """Through ``decode_step_ragged`` on an int8 pool: each slot's K/V row
    and its scale row land at (table[b, pos // page], :, pos % page), the
    out-of-pool slot's are dropped, and nothing else moves."""
    from distributed_pytorch_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=1,
                                n_heads=2, head_dim=16, n_kv_heads=2,
                                d_ff=64)
    params = tfm.init(jax.random.key(0), cfg)
    page = 128
    cache = gen.init_paged_cache(cfg, 4, page, dtype=jnp.float32,
                                 kv_heads=2, kv_dtype="int8")
    table = jnp.asarray([[1, 2], [3, 9]], jnp.int32)   # 9: past the pool
    pos = jnp.asarray([page - 1, page], jnp.int32)     # slot 1 -> page 9
    old = jax.tree.map(np.asarray, cache)   # the step rebinds cache's layers
    _, new = gen.decode_step_ragged(
        params, cache, jnp.asarray([5, 6], jnp.int32), pos, cfg=cfg,
        use_decode_kernel=True, page_table=table)
    for name in ("k", "v", "ks", "vs"):
        before = old["layer0"][name]
        after = np.asarray(new["layer0"][name])
        moved = np.argwhere((before != after).any(-1))
        assert {(int(p), int(o)) for p, _, o in moved} == {(1, page - 1)}, name
