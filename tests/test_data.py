"""Data pipeline tests: sampler semantics, loader sharding, augmentation.

Parity targets: torch DistributedSampler(num_replicas, rank, shuffle=True,
seed=0, drop_last=False) as used at reference main_all_reduce.py:112
(SURVEY.md section 2.3), and the transform stack at reference main.py:71-82.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_tpu.data import (
    DataLoader, Dataset, DistributedSampler, augment, cifar10,
)


pytestmark = pytest.mark.quick  # sub-2-min tier (tests/conftest.py)

def _ds(n=100, seed=0):
    rng = np.random.default_rng(seed)
    return Dataset(
        images=rng.integers(0, 256, (n, 32, 32, 3)).astype(np.uint8),
        labels=rng.integers(0, 10, n).astype(np.int32),
    )


class TestDistributedSampler:
    def test_partition_covers_dataset_with_padding(self):
        # 100 samples, 3 replicas -> ceil(100/3)=34 each, total 102 (2 padded).
        shards = [DistributedSampler(100, 3, r).indices() for r in range(3)]
        assert all(len(s) == 34 for s in shards)
        union = np.concatenate(shards)
        assert len(union) == 102
        counts = np.bincount(union, minlength=100)
        assert (counts >= 1).all() and counts.sum() == 102

    def test_even_split_is_disjoint(self):
        shards = [DistributedSampler(100, 4, r).indices() for r in range(4)]
        union = np.concatenate(shards)
        assert len(np.unique(union)) == 100

    def test_same_global_permutation_across_ranks(self):
        # All ranks must derive from one shared permutation (no comm needed).
        s0 = DistributedSampler(40, 2, 0, seed=0)
        s1 = DistributedSampler(40, 2, 1, seed=0)
        merged = np.empty(40, dtype=np.int64)
        merged[0::2] = s0.indices()
        merged[1::2] = s1.indices()
        assert sorted(merged) == list(range(40))

    def test_epoch_reshuffles_deterministically(self):
        s = DistributedSampler(50, 1, 0, seed=0)
        e0 = s.indices().copy()
        s.set_epoch(1)
        e1 = s.indices().copy()
        s.set_epoch(0)
        assert not np.array_equal(e0, e1)
        np.testing.assert_array_equal(s.indices(), e0)

    def test_no_shuffle_is_identity_order(self):
        s = DistributedSampler(10, 2, 1, shuffle=False)
        np.testing.assert_array_equal(s.indices(), [1, 3, 5, 7, 9])

    def test_drop_last(self):
        s = DistributedSampler(10, 3, 0, shuffle=False, drop_last=True)
        assert s.num_samples == 3

    def test_matches_torch_distributed_sampler_arithmetic(self):
        """Padding + striding arithmetic identical to torch's (shuffle off)."""
        torch = pytest.importorskip("torch")
        from torch.utils.data import DistributedSampler as TorchDS

        class _FakeDataset:
            def __len__(self):
                return 100

        for n_rep, rank in [(3, 0), (3, 2), (4, 1)]:
            t = TorchDS(_FakeDataset(), num_replicas=n_rep, rank=rank,
                        shuffle=False, drop_last=False)
            ours = DistributedSampler(100, n_rep, rank, shuffle=False)
            np.testing.assert_array_equal(ours.indices(), list(iter(t)))


class TestDataLoader:
    def test_batching_and_shapes(self):
        dl = DataLoader(_ds(100), batch_size=32)
        batches = list(dl)
        assert [len(b[1]) for b in batches] == [32, 32, 32, 4]
        assert batches[0][0].shape == (32, 32, 32, 3)
        assert batches[0][0].dtype == np.uint8

    def test_sharded_loaders_cover_global_batch(self):
        ds = _ds(64)
        shards = []
        for r in range(4):
            dl = DataLoader(ds, 8, sampler=DistributedSampler(64, 4, r, seed=0))
            shards.append(next(iter(dl))[1])
        # 4 ranks x 8 = 32 distinct samples in the first global batch
        all_labels_idx = np.concatenate(
            [DistributedSampler(64, 4, r, seed=0).indices()[:8] for r in range(4)])
        assert len(np.unique(all_labels_idx)) == 32

    def test_shuffle_no_sampler_reproducible(self):
        ds = _ds(50)
        dl = DataLoader(ds, 10, shuffle=True, seed=0)
        a = [b[1] for b in dl]
        b = [b[1] for b in dl]
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


class TestAugment:
    def test_normalize_constants(self):
        x = np.full((2, 32, 32, 3), 128, np.uint8)
        y = np.asarray(augment.normalize(jnp.asarray(x)))
        expected = (128 / 255.0 - cifar10.MEAN) / cifar10.STD
        np.testing.assert_allclose(y[0, 0, 0], expected, rtol=1e-5)

    def test_augment_shapes_and_determinism(self):
        x = jnp.asarray(_ds(8).images)
        a = augment.augment(jax.random.key(0), x)
        b = augment.augment(jax.random.key(0), x)
        c = augment.augment(jax.random.key(1), x)
        assert a.shape == (8, 32, 32, 3) and a.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_augment_is_crop_of_padded_image(self):
        # Every augmented pixel either comes from the source or the zero pad.
        x = jnp.asarray(np.full((4, 32, 32, 3), 255, np.uint8))
        y = np.asarray(augment.augment(jax.random.key(3), x))
        norm_255 = ((1.0 - cifar10.MEAN) / cifar10.STD).astype(np.float32)
        norm_0 = ((0.0 - cifar10.MEAN) / cifar10.STD).astype(np.float32)
        for ch in range(3):
            vals = y[:, :, :, ch]
            near = (np.abs(vals - norm_255[ch]) < 1e-4) | (np.abs(vals - norm_0[ch]) < 1e-4)
            assert near.all()

    def test_augment_jits(self):
        f = jax.jit(augment.augment)
        x = jnp.asarray(_ds(4).images)
        assert f(jax.random.key(0), x).shape == (4, 32, 32, 3)


class TestCifar10Load:
    def test_synthetic_fallback_deterministic(self):
        a = cifar10.load("train", data_dir="/nonexistent")
        b = cifar10.load("train", data_dir="/nonexistent")
        assert a.synthetic and len(a) == 50_000
        np.testing.assert_array_equal(a.images[:10], b.images[:10])
        t = cifar10.load("test", data_dir="/nonexistent")
        assert len(t) == 10_000
        # train and test draws differ
        assert not np.array_equal(a.images[:10], t.images[:10])

    def test_synthetic_learnable_structure(self):
        ds = cifar10.load("train", data_dir="/nonexistent")
        # same-class images are correlated, cross-class are not
        i0 = np.where(ds.labels == 0)[0][:2]
        i1 = np.where(ds.labels == 1)[0][0]
        a, b, c = (ds.images[j].astype(np.float32).ravel() for j in (*i0, i1))
        same = np.corrcoef(a, b)[0, 1]
        diff = np.corrcoef(a, c)[0, 1]
        assert same > 0.5 > diff


class TestPrefetch:
    def test_keeps_order(self):
        from distributed_pytorch_tpu.data.pipeline import prefetch
        assert list(prefetch(iter(range(50)), depth=2)) == list(range(50))

    def test_producer_exception_reraises_in_consumer(self):
        from distributed_pytorch_tpu.data.pipeline import prefetch

        def gen():
            yield 0
            raise KeyError("boom")

        it = prefetch(gen())
        assert next(it) == 0
        with pytest.raises(KeyError, match="boom"):
            next(it)

    def test_passes_a_pair_of_arrays_through(self):
        # (images, labels) chunks are 2-tuples of arrays: the error
        # sentinel is told by identity, never by comparing to an array
        from distributed_pytorch_tpu.data.pipeline import prefetch
        pairs = [(np.full((2, 3), i), np.arange(i + 2)) for i in range(3)]
        for got, want in zip(prefetch(iter(pairs)), pairs, strict=True):
            assert got[0] is want[0] and got[1] is want[1]
