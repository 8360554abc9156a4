"""Every seed offers the same load, in another order."""

import numpy as np
import pytest

import traffic

SEEDS = (1, 77, (1 << 31) + 12345)
MIXES = ("chat_open_loop", "decode_backlog")


def lengths(segment):
    return (sorted(len(r.prompt) for r in segment),
            sorted(r.max_new for r in segment),
            sum(r.greedy for r in segment))


@pytest.mark.parametrize("name", MIXES)
def test_same_multisets_and_offered_tokens_from_every_seed(name):
    mix = traffic.load(name)
    # past the staggered first requests of a backlog (set-up, cut short)
    k = 2
    segs = [traffic.Requests(mix, s, 103_424).segment(k) for s in SEEDS]
    first = lengths(segs[0])
    for seg in segs[1:]:
        assert lengths(seg) == first
    offered = traffic.Requests(mix, 0, 103_424).offered(k)
    assert offered["prompt_tokens"] == sum(first[0])
    assert offered["output_tokens"] == sum(first[1])


@pytest.mark.parametrize("name", MIXES)
def test_order_and_token_ids_differ_between_seeds(name):
    mix = traffic.load(name)
    a, b = (traffic.Requests(mix, s, 103_424).segment(2) for s in SEEDS[:2])
    assert [r.max_new for r in a] != [r.max_new for r in b]
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])
    again = traffic.Requests(mix, SEEDS[0], 103_424).segment(2)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               for x, y in zip(a, again))


def test_due_times_span_the_same_seconds_at_the_files_rate():
    mix = traffic.load("chat_open_loop")
    arr = mix["arrivals"]
    for seed in SEEDS:
        reqs = traffic.Requests(mix, seed, 103_424)
        gaps = []
        for k in range(3):
            seg = reqs.segment(k)
            assert len(seg) == round(arr["rate_per_s"] * arr["segment_seconds"])
            assert seg[0].due == pytest.approx(k * arr["segment_seconds"])
            assert all(k * arr["segment_seconds"] <= r.due
                       < (k + 1) * arr["segment_seconds"] for r in seg)
            ends = [r.due for r in seg] + [(k + 1) * arr["segment_seconds"]]
            gaps.append(sorted(np.diff(ends).round(6)))
        assert gaps[0] == gaps[1] == gaps[2]   # same gaps, another order


def test_lengths_keep_to_the_files_clips_and_ids_to_the_vocabulary():
    mix = traffic.load("chat_open_loop")
    seg = traffic.Requests(mix, 5, 1000).segment(0)
    p, o = mix["prompt_len"], mix["output_len"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in seg)
    assert all(o["min"] <= r.max_new <= o["max"] for r in seg)
    assert all(r.prompt.max() < 1000 and r.prompt.min() >= 0 for r in seg)
    mean_out = np.mean([r.max_new for r in seg])
    assert 140 < mean_out < 190     # lognormal 128, sigma 0.8, clipped


def test_backlog_staggers_only_its_first_slots_full():
    mix = traffic.load("decode_backlog")
    reqs = traffic.Requests(mix, 3, 103_424)
    first, later = reqs.segment(0), reqs.segment(1)
    lo = mix["output_len"]["min"]
    assert min(r.max_new for r in first) < lo <= min(r.max_new for r in later)
    assert all(r.due == 0.0 for r in first + later)


def test_shared_prefix_groups_share_their_first_tokens():
    mix = dict(traffic.load("chat_open_loop"),
               shared_prefix={"groups": 1, "length": 24})
    seg = traffic.Requests(mix, 9, 103_424).segment(0)
    assert all(np.array_equal(r.prompt[:24], seg[0].prompt[:24])
               for r in seg if len(r.prompt) > 24)


def test_gamma_gaps_are_burstier_than_exponential_ones():
    exp = traffic.stratified({"dist": "exponential", "mean": 1.0}, 200, 0.5)
    gam = traffic.stratified({"dist": "gamma", "mean": 1.0, "shape": 0.5},
                             200, 0.5)
    assert gam.std() / gam.mean() > exp.std() / exp.mean()


def test_a_mix_with_a_base_is_that_mix_with_its_own_keys_laid_over():
    base, over = traffic.load("packed_4k"), traffic.load("packed_4k_dp4")
    assert {k for k in base if base[k] != over.get(k)} == {"why"}
    assert "base" not in over and "dp" not in over
