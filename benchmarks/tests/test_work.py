"""The operation and byte functions of the dense family, reached through
the family as a run reaches them, against hand-worked values."""

import pytest

import families
import harness
import work as chip   # the chip's side: peaks and roofline_seconds

work = families.load("dense_gqa").work


@pytest.fixture(scope="module")
def ernie():
    return harness.load_json(harness.HERE, "configs", "ernie-4.5-0.3b.json")


def test_param_count_is_361_million(ernie):
    embed = 103_424 * 1024
    layer = (2 * 1024 + 1024 * 16 * 128 + 2 * 1024 * 2 * 128
             + 16 * 128 * 1024 + 3 * 1024 * 3072)
    assert work.param_count(ernie) == embed + 1024 + 18 * layer == 360_748_032


def test_train_flops_per_token_at_4096(ernie):
    want = 6 * 360_748_032 + 6 * 4096 * 18 * 16 * 128
    assert work.train_flops_per_token(ernie, 4096) == want
    assert round(want / 1e9, 2) == 3.07


def test_kv_bytes_per_token(ernie):
    assert work.kv_bytes_per_token(ernie) == 2 * 18 * 2 * 128 * 2 == 18_432
    need = work.kernels["decode_attn"](ernie, context_tokens=400 * 64)
    assert need["bytes"] == 18_432 * 400 * 64
    assert chip.roofline_seconds(need["flops"], need["bytes"],
                                 chip.peaks("TPU v5 lite"))["bound"] == "memory"


def test_prompt_flops_is_decode_flops_summed(ernie):
    by_token = sum(work.decode_flops(ernie, 1, c) for c in range(1, 33))
    assert work.prompt_flops(ernie, 32) == pytest.approx(by_token)


def test_flash_attention_is_compute_bound_at_4k(ernie):
    w = work.kernels["flash_attn"](ernie, rows=2, seq=4096)
    assert w["flops"] == 6 * 4096 * 4096 * 128 * 16 * 2 * 18
    least = chip.roofline_seconds(w["flops"], w["bytes"],
                                  chip.peaks("TPU v5 lite"))
    assert least["bound"] == "compute"
    assert work.train_flops_per_token(ernie, 4096) * 2 * 4096 > w["flops"]


def test_peaks_are_the_v5e_and_an_unknown_device_is_an_error():
    p = chip.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        chip.peaks("cpu")
