"""The readers of the linear layers' delta rule see its kernels.

``ops/gated_delta.gated_delta`` runs the rule as two Pallas kernels,
``gated_delta_fwd`` and ``gated_delta_bwd``.  ``kernel.gated_delta_roofline``
and ``model.gated_delta_share_pct`` tell the rule's operations by their
result shapes (``work.gated_delta_operands``), so the kernels' results keep
those shapes: checked on the kernels' pallas_calls as the training step
traces them at the cell's shapes (nothing compiled).
``model.gated_delta_kernel_pct`` reads the kernels by their own names: on a
copy of the chip's recorded trace of ``qwen3next_train_8k`` (recorded
before the kernels existed) whose triangular solves are renamed as the
backward kernel is named under ``jax.grad``, it reads those calls' share of
the rule's time, and None on the trace as recorded.
"""

import copy
import re

import jax
import jax.numpy as jnp
import pytest

import harness
import routed_ops
import tracing
from test_kernel_names import load

CELL = "qwen3next_train_8k"
SOLVE = "custom-call f32[128,1,32,1,64,64]"


def kernel_results(fn, *args):
    """{name: [result shape as the trace writes it]} of the pallas_calls of
    ``fn``'s traced program, through the custom VJP's nested programs."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = [
                    f"{v.aval.dtype}[{','.join(map(str, v.aval.shape))}]"
                    .replace("float32", "f32").replace("bfloat16", "bf16")
                    for v in eqn.outvars]
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else [v]:
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_every_result_of_the_kernels_carries_a_run_of_the_rule():
    """At the cell's shapes (one 8,192-token row, q and k at 16 key heads
    and v at 32 value heads of 128 in bfloat16, g and beta in float32),
    each result of both kernels has one of ``gated_delta_operands``' runs,
    but the forward's one-tile counter (the largest state norm), which
    rides in the same result tuple."""
    from distributed_pytorch_tpu.ops import gated_delta as gd

    found = harness.find_cell(CELL)
    runs = found["family"].work.gated_delta_operands(
        found["config_file"], 1, 8192)["delta_rule"]
    qk = jax.ShapeDtypeStruct((1, 8192, 16, 128), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    gb = jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32)

    def loss(q, k, v, g, beta):
        o, _, norm = gd.gated_delta(q, k, v, g, beta, interpret=False)
        return jnp.sum(o) + norm

    results = kernel_results(jax.grad(loss, argnums=range(5)),
                             qk, qk, v, gb, gb)
    assert set(results) == {"gated_delta_fwd", "gated_delta_bwd"}
    assert results["gated_delta_fwd"][2] == "f32[1,8,128]"    # the counter
    del results["gated_delta_fwd"][2]
    for name, shapes in results.items():
        for shape in shapes:
            assert any(r in shape for r in runs), (name, shape)
    assert results["gated_delta_bwd"] == (
        ["bf16[1,8192,16,128]"] * 2 + ["bf16[1,8192,32,128]"]
        + ["f32[1,8192,32]"] * 2)


def renamed_solves(ctx):
    """A copy of ``ctx`` whose triangular solves are Pallas kernels named
    as the backward kernel is under ``jax.grad``; and their seconds inside
    the window (each is a leaf: its self time is its duration)."""
    trace = copy.deepcopy(ctx["trace"])
    t0, t1 = ctx["reduced"]["t0"], ctx["reduced"]["t1"]
    seconds = 0.0
    for plane in tracing.device_planes(trace):
        for event in tracing.line_events(plane, tracing.OPS_LINE):
            if tracing.op_key(event[0]) != SOLVE:
                continue
            event[0] = re.sub(r"^%custom-call", "%transpose_jvp_gated_delta_bwd__",
                              event[0]) + " pallas"
            if plane is tracing.device_planes(trace)[0] and (
                    t0 <= event[1] and event[1] + event[2] <= t1):
                seconds += event[2] / 1e9
    return {**ctx, "trace": trace}, seconds


def test_the_kernel_share_reads_the_named_kernels_over_the_rule():
    ctx = load(CELL)
    read = harness.load_reader("layer_metrics", "model.gated_delta_kernel_pct")
    assert read(ctx) is None          # no kernel carries the name
    named, seconds = renamed_solves(ctx)
    runs = ctx["work"].gated_delta_operands(ctx["config"], 1, 8192)[
        "delta_rule"]
    rule = routed_ops.seconds(ctx, lambda n: any(
        r in n.partition(" = ")[2] for r in runs))
    # two steps of 6 solves, 16.4 ms a step, of the rule's 104.5 ms a step
    assert seconds == pytest.approx(0.0328, rel=0.01)
    assert rule == pytest.approx(0.2091, rel=0.01)
    assert read(named) == pytest.approx(100 * seconds / rule)
    assert 15 < read(named) < 16.5


def test_the_kernel_share_finds_nothing_in_another_family():
    ctx = load(CELL)
    named, _ = renamed_solves(ctx)
    other = harness.find_cell("laguna_train_8k")
    read = harness.load_reader("layer_metrics", "model.gated_delta_kernel_pct")
    assert read({**named, "work": other["family"].work,
                 "config": other["config_file"]}) is None
