"""Every Pallas kernel of the package passes its own name to ``pallas_call``.

The compiled program keeps the name in the kernel's instruction name
(``%jvp_flash_fwd_.1`` under ``jax.grad``), so a device trace tells the
kernels apart (unnamed, a kernel is called after the transformation it was
traced under alone: ``jvp__``, ``transpose_jvp___``).  What
is pinned, on the source and on the traced programs of every kernel at small
shapes: each call passes ``name=``; the names are lower-case identifiers,
the forward flash kernels' start ``flash_fwd`` and the backward ones'
``flash_bwd``; none holds ``ragged-dot`` (the name of XLA's own grouped
product kernels); and no two kernels share a name.  Nothing is compiled.
"""

import ast
import pathlib
import re

import jax
import jax.extend
import jax.numpy as jnp
import pytest

import distributed_pytorch_tpu
from distributed_pytorch_tpu.ops import attention as att
from distributed_pytorch_tpu.ops import fused_bn, gated_delta, quantized

PACKAGE = pathlib.Path(distributed_pytorch_tpu.__file__).parent

# kernel -> its name: the call sites of ops/attention.py (with and without a
# window), ops/fused_bn.py, ops/quantized.py and ops/gated_delta.py
NAMES = {
    "flash forward": "flash_fwd",
    "flash forward, window": "flash_fwd_window",
    "flash backward, fused": "flash_bwd",
    "flash backward, fused, window": "flash_bwd_window",
    "flash backward, dQ": "flash_bwd_dq",
    "flash backward, dQ, window": "flash_bwd_dq_window",
    "flash backward, dK/dV": "flash_bwd_dkv",
    "flash backward, dK/dV, window": "flash_bwd_dkv_window",
    "decode": "decode_attn",
    "paged decode": "decode_attn_paged",
    "batch norm backward, sums": "bn_bwd_sums",
    "batch norm backward, dx": "bn_bwd_dx",
    "int8 matmul": "int8_matmul",
    "gated delta forward": "gated_delta_fwd",
    "gated delta backward": "gated_delta_bwd",
}


def pallas_calls():
    """(file, line, keyword names) of every ``pallas_call(...)`` in the
    package's source."""
    found = []
    for path in sorted(PACKAGE.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                found.append((path.relative_to(PACKAGE).as_posix(),
                              node.lineno, {k.arg for k in node.keywords}))
    return found


def test_every_pallas_call_in_the_source_passes_a_name():
    calls = pallas_calls()
    assert {f for f, _, _ in calls} == {
        "ops/attention.py", "ops/fused_bn.py", "ops/quantized.py",
        "ops/gated_delta.py"}
    assert len(calls) == 11   # 6 attention, 2 batch norm, 1 int8, 2 delta
    unnamed = [(f, line) for f, line, kw in calls if "name" not in kw]
    assert not unnamed


def kernel_names(fn, *args) -> list:
    """The names the ``pallas_call``s of ``fn``'s traced program pass, in
    order, through every nested program (custom VJPs, jits)."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
                continue
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else [v]:
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def _flash(window):
    x = jnp.ones((1, 2, 256, 128), jnp.float32)

    def loss(q, k, v):
        return att.flash_attention(q, k, v, causal=True, window=window,
                                   interpret=True).sum()

    return kernel_names(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)


def traced_names(monkeypatch) -> dict:
    """kernel -> the names its traced program passes."""
    got = {}
    for window, tag in ((None, ""), (128, ", window")):
        fwd, bwd = _flash(window)
        got["flash forward" + tag] = [fwd]
        got["flash backward, fused" + tag] = [bwd]
    with monkeypatch.context() as m:   # past the fused plan's budget
        m.setattr(att, "_FUSED_BWD_VMEM_BUDGET", 0)
        for window, tag in ((None, ""), (128, ", window")):
            _, dq, dkv = _flash(window)
            got["flash backward, dQ" + tag] = [dq]
            got["flash backward, dK/dV" + tag] = [dkv]
    q = jnp.ones((2, 4, 1, 128), jnp.bfloat16)
    cache = jnp.ones((2, 2, 256, 128), jnp.bfloat16)
    pos = jnp.array([3, 200], jnp.int32)
    got["decode"] = kernel_names(
        lambda q, k, v, p: att.decode_attention(q, k, v, p, interpret=True),
        q, cache, cache, pos)
    scale = jnp.ones((2, 2, 256, 1), jnp.float32)
    assert kernel_names(       # the int8 cache's kernel takes the same name
        lambda q, k, v, ks, vs, p: att.decode_attention(
            q, k, v, p, k_scale=ks, v_scale=vs, interpret=True),
        q, cache, cache, scale, scale, pos) == got["decode"]
    pool = jnp.ones((5, 2, 128, 128), jnp.bfloat16)
    table = jnp.array([[1, 2], [3, 4]], jnp.int32)
    got["paged decode"] = kernel_names(
        lambda q, k, v, t, p: att.decode_attention_paged(
            q, k, v, t, p, interpret=True), q, pool, pool, table, pos)
    a = jnp.ones((256, 128), jnp.float32)
    c = jnp.ones((128,), jnp.float32)
    sums, dx = kernel_names(
        lambda dr, a: fused_bn._bwd_pallas(dr, a, c, c, c, c, interpret=True),
        a, a)
    got["batch norm backward, sums"] = [sums]
    got["batch norm backward, dx"] = [dx]
    got["int8 matmul"] = kernel_names(
        lambda x, w: quantized.int8_matmul(x, w, interpret=True),
        jnp.ones((64, 256), jnp.float32), jnp.ones((256, 256), jnp.float32))
    qkv = jnp.ones((1, 64, 2, 128), jnp.float32)
    gb = jnp.ones((1, 64, 2), jnp.float32)
    fwd, bwd = kernel_names(jax.grad(
        lambda *a: gated_delta.gated_delta(*a, interpret=True)[0].sum(),
        argnums=range(5)), qkv, qkv, qkv, gb, gb)
    got["gated delta forward"], got["gated delta backward"] = [fwd], [bwd]
    return got


def test_each_kernel_passes_its_own_name(monkeypatch):
    got = traced_names(monkeypatch)
    assert got == {kernel: [name] for kernel, name in NAMES.items()}
    # the flash names are built in one place, from the kernel and its window
    assert att._flash_name("bwd_dkv", 512) == "flash_bwd_dkv_window"
    assert att._flash_name("fwd", None) == att.KERNEL_NAMES["fwd"]


@pytest.mark.parametrize("kernel,name", list(NAMES.items()))
def test_the_names_keep_the_rules(kernel, name):
    assert re.fullmatch(r"[a-z][a-z0-9_]*", name)
    assert "ragged-dot" not in name
    if kernel.startswith("flash forward"):
        assert name.startswith("flash_fwd")
    elif kernel.startswith("flash backward"):
        assert name.startswith("flash_bwd")
    else:
        assert not name.startswith("flash")
    # no other kernel shares it
    assert [k for k, n in NAMES.items() if n == name] == [kernel]
