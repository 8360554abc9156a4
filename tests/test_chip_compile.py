"""Chip-less compiles: the Pallas kernels of the main path, at the widths
the chip smoke runs them, compiled by the TPU's own compiler for a v5e
2x2 topology that is described and not attached (``interpret=False``).

Interpret mode cannot see what these catch: a kernel that passed every
interpret-mode test since it was written was refused here for wanting more
fast memory than a kernel may use (int8 paged decode, the ``paged_int8``
case below).  Nothing runs, so these say nothing about results or times; a
compile that passes is not a chip run.

The persistent compile cache is off around them: a chip-less compile is
written to it but cannot be read back without a chip, and the next run
would warn about every entry.  Skipped where the topology cannot be
described (no TPU compiler installed).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp
# no chip is opened here, so several test processes (xdist workers) may
# load the TPU compiler at once; without this all but one are refused
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from distributed_pytorch_tpu.ops import attention as att
from distributed_pytorch_tpu.ops import quantized

# the d2048 LM's attention: 16 heads x 128, batch 4 at sequence 2048
B, H, D, SEQ = 4, 16, 128, 2048
CACHE, PAGE, POOL_PAGES = 4096, 512, 65


@pytest.fixture(scope="module")
def chip():
    """Sharding on the first chip of the described topology."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compile(chip, fn, *shapes):
    """Compile ``fn`` for the chip from (shape, dtype) pairs."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def test_flash_forward_backward_compiles(chip):
    qkv = ((B, H, SEQ, D), jnp.bfloat16)

    def loss(q, k, v):
        return att.flash_attention(q, k, v, causal=True,
                                   interpret=False).astype(jnp.float32).sum()

    compiled = _compile(chip, jax.grad(loss, argnums=(0, 1, 2)),
                        qkv, qkv, qkv)
    # forward, dQ and dK/dV kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("case", ["dense_bf16", "dense_int8_block512",
                                  "paged_bf16", "paged_int8"])
def test_decode_attention_compiles(chip, case):
    """Single-token decode at 16 kv heads x 128 over a 4096-slot cache or
    a pool of 512-slot pages.  int8 KV is the regression for the scale
    tiles: lane-dense they fit the 16 MiB scoped VMEM limit (as (page, 1)
    columns they wanted 18 MiB), and XLA hands the scale arrays over
    without a padded copy (the program needs no temporary at all)."""
    kv = jnp.int8 if "int8" in case else jnp.bfloat16
    q = ((B, H, 1, D), jnp.bfloat16)
    pos = ((B,), jnp.int32)
    if case.startswith("dense"):
        cache = ((B, H, CACHE, D), kv)
        scale = ((B, H, CACHE, 1), jnp.float32)
        block = 512 if case.endswith("block512") else None
        if "int8" in case:
            compiled = _compile(
                chip, lambda q, k, v, ks, vs, p: att.decode_attention(
                    q, k, v, p, k_scale=ks, v_scale=vs, block_k=block,
                    interpret=False),
                q, cache, cache, scale, scale, pos)
        else:
            compiled = _compile(
                chip, lambda q, k, v, p: att.decode_attention(
                    q, k, v, p, interpret=False), q, cache, cache, pos)
    else:
        pool = ((POOL_PAGES, H, PAGE, D), kv)
        scale = ((POOL_PAGES, H, PAGE, 1), jnp.float32)
        table = ((B, CACHE // PAGE), jnp.int32)
        if "int8" in case:
            compiled = _compile(
                chip, lambda q, k, v, ks, vs, t, p:
                att.decode_attention_paged(q, k, v, t, p, k_scale=ks,
                                           v_scale=vs, interpret=False),
                q, pool, pool, scale, scale, table, pos)
        else:
            compiled = _compile(
                chip, lambda q, k, v, t, p: att.decode_attention_paged(
                    q, k, v, t, p, interpret=False),
                q, pool, pool, table, pos)
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_int8_matmul_compiles(chip):
    compiled = _compile(
        chip, lambda x, w: quantized.int8_matmul(x, w, interpret=False),
        ((8192, 2048), jnp.bfloat16), ((2048, 8192), jnp.bfloat16))
    assert compiled.as_text().count("tpu_custom_call") == 1
