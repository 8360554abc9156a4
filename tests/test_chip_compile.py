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
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp
# no chip is opened here, so several test processes (xdist workers) may
# load the TPU compiler at once; without this all but one are refused
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from distributed_pytorch_tpu.models import transformer as tfm
from distributed_pytorch_tpu.ops import attention as att
from distributed_pytorch_tpu.ops import quantized
from distributed_pytorch_tpu.serve import ContinuousBatcher

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


def _kernels(text):
    """The compiled Pallas kernels' instruction names without their serial
    numbers: each kernel's own name (``pallas_call(..., name=...)``) as the
    name stack leaves it, wrapped in the transformations the kernel was
    traced under (``jvp(flash_fwd)`` compiles to ``%jvp_flash_fwd_.1``)."""
    return sorted(
        re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ", line).group(1)
        for line in text.split("\n") if "tpu_custom_call" in line)


def _under_grad(window, *backward):
    """``_kernels`` of one flash call's forward and ``backward`` kernels
    under ``jax.grad``."""
    return sorted([f"jvp_{att._flash_name('fwd', window)}_"]
                  + [f"transpose_jvp_{att._flash_name(b, window)}__"
                     for b in backward])


def test_flash_forward_backward_compiles(chip):
    qkv = ((B, H, SEQ, D), jnp.bfloat16)

    def loss(q, k, v):
        return att.flash_attention(q, k, v, causal=True,
                                   interpret=False).astype(jnp.float32).sum()

    compiled = _compile(chip, jax.grad(loss, argnums=(0, 1, 2)),
                        qkv, qkv, qkv)
    # the forward kernel and the fused backward, each under its own name
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert _kernels(compiled.as_text()) == [
        "jvp_flash_fwd_", "transpose_jvp_flash_bwd__"]


@pytest.mark.parametrize("window", [None, 4096])
@pytest.mark.parametrize("shape", [(1, 28, 8192, D), (2, 16, 4096, D)],
                         ids=["routed_8k", "dense_4k"])
def test_flash_at_the_training_cells_rows_compiles(chip, shape, window):
    """The training cells' attention: ``smallthinker_train_8k``'s one
    8,192-token row of 28 heads x 128, the NoPE-global layers without a
    window and the others over 4,096 keys, and the dense cells' two
    4,096-token rows of 16 heads (where a 4,096-key window is no window).
    The windowed kernels' index maps hold a dead step on the tile the
    pipeline has (``jnp.clip`` of a block index), and the fused backward
    keeps one sequence's dQ in VMEM and asks for what that takes: VMEM and
    tiling as the chip's compiler sees them, two kernels a layer."""
    _forward_backward_compiles(chip, shape, window)


def _forward_backward_compiles(chip, shape, window, kernels=2):
    assert att._bwd_fuses(shape[2], shape[2], D, jnp.bfloat16) == (kernels == 2)
    qkv = (shape, jnp.bfloat16)

    def loss(q, k, v):
        return att.flash_attention(q, k, v, causal=True, window=window,
                                   interpret=False).astype(jnp.float32).sum()

    compiled = _compile(chip, jax.grad(loss, argnums=(0, 1, 2)),
                        qkv, qkv, qkv)
    assert compiled.as_text().count("tpu_custom_call") == kernels
    # a window that covers the sequence is none
    named = None if window is None or window >= shape[2] else window
    assert _kernels(compiled.as_text()) == _under_grad(
        named, *(["bwd"] if kernels == 2 else ["bwd_dq", "bwd_dkv"]))


@pytest.mark.parametrize("window", [None, 4096, 512])
def test_the_two_kernel_backward_compiles_with_dead_steps_held(
        monkeypatch, chip, window):
    """Past the fused plan's budget (here: the budget at nought) the dQ and
    the dK/dV kernel run, the one with K/V and the other with q, dO, lse and
    delta held on the nearest live tile through a dead step, with a window
    and without: three kernels a layer."""
    monkeypatch.setattr(att, "_FUSED_BWD_VMEM_BUDGET", 0)
    _forward_backward_compiles(chip, (1, 28, 8192, D), window, kernels=3)


@pytest.mark.parametrize("heads,window", [(48, None), (64, 512)],
                         ids=["global_48_heads", "window_64_heads"])
def test_flash_at_two_head_counts_in_one_model_compiles(chip, heads, window):
    """``laguna_train_8k``'s attention: one 8,192-token row at 48 heads
    without a window (the global layers) and at 64 heads over a 512-key
    window, half a block of 1,024 (the windowed layers): the fused backward
    at both, two kernels a layer."""
    _forward_backward_compiles(chip, (1, heads, 8192, D), window)


def test_flash_at_head_256_compiles_with_the_fused_backward(chip):
    """``qwen3next_train_8k``'s full-attention layer: one 8,192-token row of
    16 heads x 256 (K/V repeated from 2).  One sequence's dQ at 256 wide in
    bfloat16 is 8,192 x 256 x (4 + 2 x 2) B = 16 MiB, the fused plan's
    budget exactly, so the fused backward runs (asking the compiler for
    ~33 MiB of VMEM): two kernels a layer."""
    shape = (1, 16, 8192, 256)
    assert att._bwd_fuses(8192, 8192, 256, jnp.bfloat16)
    qkv = (shape, jnp.bfloat16)

    def loss(q, k, v):
        return att.flash_attention(q, k, v, causal=True,
                                   interpret=False).astype(jnp.float32).sum()

    compiled = _compile(chip, jax.grad(loss, argnums=(0, 1, 2)),
                        qkv, qkv, qkv)
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert _kernels(compiled.as_text()) == _under_grad(None, "bwd")


def test_the_chunked_delta_rule_compiles_at_the_cells_shapes(chip):
    """``qwen3next_train_8k``'s linear layer: the chunked gated delta rule
    over one 8,192-token row of 32 value heads x 128 (bfloat16 q, k, v;
    float32 g and beta), forward and backward: the triangular solve and the
    scan over 128 chunks as the chip's compiler lowers them, no kernel of
    the program's own."""
    from distributed_pytorch_tpu.ops import gated_delta as gd

    qkv = ((1, 8192, 32, 128), jnp.bfloat16)
    gb = ((1, 8192, 32), jnp.float32)

    def loss(q, k, v, g, beta):
        o, _, norm_max = gd.gated_delta_chunked(q, k, v, g, beta)
        return jnp.sum(o * o) + norm_max

    text = _compile(chip, jax.grad(loss, argnums=range(5)),
                    qkv, qkv, qkv, gb, gb).as_text()
    assert "tpu_custom_call" not in text and " while(" in text


def _entry_results(text, shape):
    """Opcodes of the entry computation's operations whose result (or an
    element of it) has ``shape``: what runs once a call, outside every loop
    body, conditional and fusion."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    found = []
    for line in entry.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", line)
        if m and shape in re.sub(r"\{[^{}]*\}", "", m.group(1)):
            found.append(m.group(2))
    return found


def test_grouped_products_compile_to_the_chips_own_kernel(chip):
    """The dropless routed layer at the routed cell's shapes (49,152 picks
    of 2,560 over 16 held experts of 768), forward and backward: XLA lowers
    ``lax.ragged_dot`` on the TPU to Mosaic kernels of its own (so the step
    holds two families of ``tpu_custom_call`` and the benchmark's readers
    tell them apart by name, ``benchmarks/routed_ops.py``), with no dense
    (rows, experts, ...) expansion.  And the buffer is allocated for the
    worst case but worked over its live part: beside the products, nothing
    with a result of the buffer's length, or of one row a pick, runs once a
    call; the row work is in loops whose trip counts are read on the
    device."""
    from distributed_pytorch_tpu.ops import moe

    def loss(params, x):
        out, _ = moe.moe_dropless_apply(params, x, top_k=6, act="relu")
        return out.astype(jnp.float32).sum()

    shapes = {"router": (2560, 64), "w_gate": (16, 2560, 768),
              "w_up": (16, 2560, 768), "w_down": (16, 768, 2560)}
    x = jax.ShapeDtypeStruct((8192, 2560), jnp.bfloat16, sharding=chip)
    params = {k: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=chip)
              for k, dims in shapes.items()}
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    text = compiled.as_text()
    assert text.count("ragged-dot") >= 9     # 3 products x (fwd, dx, dw)
    # both sides of the products move rows by gathers, forward and backward
    # (a scatter-add of rows is serialised on this chip: fifty times slower,
    # PERF.md PR 29): the scatters that remain are of indices
    assert not [line for line in text.split("\n") if " scatter(" in line
                and "2560" in line.split(" scatter(")[0]]
    # 1.27 GiB here (1.13 GiB before the loops; the whole step's
    # temporaries fell, PERF.md section 4)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2**30
    rows = -(-(8192 * 6 + 16 * moe.ROW_TILE) // moe.CHUNK) * moe.CHUNK
    passing = {"custom-call", "while", "get-tuple-element", "tuple",
               "parameter", "bitcast"}
    for shape in (f"[{rows},2560]", f"[{rows},768]", "[49152,2560]",
                  "[8192,6,2560]", "[57856,2560]"):
        assert set(_entry_results(text, shape)) <= passing, shape
    # rows in and the gate, forward; cotangents in, the gate's transpose
    # and the rows summed back per token, backward
    assert len(re.findall(r" while\(", text)) >= 5


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
    assert _kernels(compiled.as_text()) == [att.KERNEL_NAMES[
        "decode" if case.startswith("dense") else "decode_paged"]]
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_int8_matmul_compiles(chip):
    compiled = _compile(
        chip, lambda x, w: quantized.int8_matmul(x, w, interpret=False),
        ((8192, 2048), jnp.bfloat16), ((2048, 8192), jnp.bfloat16))
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert _kernels(compiled.as_text()) == ["int8_matmul"]


# the chat cell's server (benchmarks/deployments/paged_64x4096.json) at
# ERNIE-4.5-0.3B's widths, two layers of it
POOL = (513, 2, 512, 128)


class _Captured(Exception):
    pass


def _decode_block_hlo(chip, monkeypatch, width, kv_dtype):
    """Optimized HLO of the batcher's own decode block at ``width`` slots
    over the cell's pool, compiled for the chip.  The block's arguments
    are taken from a dispatch of a small-pool batcher (one request,
    admitted inside the block, so nothing else is compiled or run)."""
    # the kernel asks the backend, which is the CPU here
    monkeypatch.setattr(att, "_interpret_default", lambda: False)
    cfg = tfm.TransformerConfig(vocab_size=103424, d_model=1024, n_layers=2,
                                n_heads=16, head_dim=POOL[3],
                                n_kv_heads=POOL[1], d_ff=3072)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: tfm.init(jax.random.key(0), cfg)))
    cb = ContinuousBatcher(params, cfg, slots=width, max_len=4096, paged=True,
                           pool_pages=9, prompt_buckets=(64, 2048),
                           dtype=jnp.bfloat16, kv_dtype=kv_dtype, eos_id=None,
                           compact_tail=False)
    block = cb._decode_for(width)

    def capture(*args):
        raise _Captured(args)

    cb._decode_fns[width] = capture
    cb.submit(np.arange(4, dtype=np.int32), max_new=4)
    assert cb._occupy_prefilling(0, cb.queue.popleft())
    with pytest.raises(_Captured) as caught:
        cb.step()
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        list(caught.value.args[0]))
    args[1] = jax.tree.map(     # the pool at the cell's size
        lambda a: jax.ShapeDtypeStruct(POOL[:1] + a.shape[1:], a.dtype,
                                       sharding=chip), args[1])
    return block.lower(*args).compile().as_text()


def _pool_values(hlo, width):
    """(opcode, layout) of every value in ``hlo`` shaped like a K/V pool
    leaf or like its (P * hkv, page, width) view."""
    p, hkv, page, _ = POOL
    shapes = (f"{p},{hkv},{page},{width}", f"{p * hkv},{page},{width}")
    found = re.findall(
        r"= \w+\[([\d,]+)\]\{([^}]*)\} ([\w-]+)\(", hlo)
    return [(op, layout) for shape, layout, op in found if shape in shapes]


@pytest.mark.parametrize("width,kv_dtype", [(64, None), (32, None),
                                            (64, "int8")])
def test_decode_block_keeps_the_pool_in_the_kernels_layout(
        chip, monkeypatch, width, kv_dtype):
    """The new token's K/V row is scattered into the pool in place, in the
    row-major layout ``decode_attention_paged`` reads: no K or V pool leaf
    is copied at the block's entry, in its loop or at its exit, and the
    ``while`` carries every one as ``{3,2,1,0``.  Indexing dimensions 0 and
    2 of the leaf made the compiler keep it pages, rows, heads, and the
    chat cell spent half its window on 350 copies of 134 MB a block
    (PERF.md, PR 25).

    Under int8 the (P, hkv, page, 1) scale leaves are still relaid (2 MB
    each): the compiler tiles that shape (2, 128) over heads and rows
    however it is written to, the runtime hands it over and the kernel
    reads it (1, 128) (PERF.md Open questions)."""
    hlo = _decode_block_hlo(chip, monkeypatch, width, kv_dtype)
    assert hlo.count("tpu_custom_call") == 2      # one a layer
    values = _pool_values(hlo, POOL[3])
    assert values
    copies = [v for v in values if v[0] in ("copy", "copy-start")
              and "S(" not in v[1]]     # S(n): a prefetch, same layout
    assert not copies, copies
    wrong = sorted({lay for _, lay in values
                    if not lay.startswith(("3,2,1,0:", "2,1,0:"))})
    assert not wrong, wrong
    # the while's operands: 2 layers x K and V, row-major
    carried = [line for line in hlo.splitlines() if " while(" in line]
    assert len(carried) == 1
    dt = "s8" if kv_dtype else "bf16"
    leaf = f"{dt}[{','.join(map(str, POOL))}]{{"
    result_type = carried[0].split(" while(")[0]
    assert result_type.count(leaf) == 4
    assert result_type.count(leaf + "3,2,1,0:") == 4
    if kv_dtype:
        scales = [v for v in _pool_values(hlo, 1) if v[0] == "copy"]
        assert len(scales) <= 8, scales    # 4 leaves, entry and exit
