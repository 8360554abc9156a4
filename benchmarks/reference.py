"""The plain reference of the dense tied-head decoder, and its control.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
kernels, no cache, no batching, no sharding.  It imports nothing of the
program and takes nothing the program has made; it is handed the benchmark's
own weights (``weights.make_params``) and the tokens the timed path saw.

Layer equations (ERNIE-4.5's published block; every dense config the
benchmark holds shares them):

    h   = x + Wo . softmax(causal(rope(Wq n1(x)) rope(Wk n1(x))^T / sqrt(dh))) Wv n1(x)
    out = h + Wdown (silu(Wgate n2(h)) * Wup n2(h))
    logits = n3(out_L) E^T          (tied table E, no biases anywhere)

with RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * scale``, rotary over
interleaved pairs at base ``rope_theta``, and K/V heads repeated to the query
heads' count.  Training: mean cross-entropy over every position, global-norm
clipping, then AdamW as optax defines it (bias-corrected moments, eps 1e-8
outside the root, decoupled decay on every leaf).

The control is the same code with the operands of every matrix product of
the dense layers and the head rounded to an int8 grid (absmax scale per row
of activations and of cotangents, per column of weights), forward and
backward: the nearest precision below the bfloat16 the configuration
states, and the step that would tempt a later PR.

Memory and compile time: rows are taken one at a time and *layer by layer*:
one small program runs a layer forward, one runs its backward (recomputing
its forward), one the head and loss over a block of positions.  Each
compiles once, in seconds, and is small enough for the persistent cache
(the whole 18-layer gradient as one program was 267 MB and took minutes).
Attention goes in blocks of query rows, so a 4,096-token row at a 103k
vocabulary fits beside the float32 parameters, gradients and moments on one
16 GB chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 1024      # query rows per attention block
HEAD_BLOCK = 1024   # positions per block of the head
LENGTH_PAD = 1024   # a served sequence is padded to a multiple of this
ROWS_PAD = 512      # and its served tokens' logit rows to one of this


def _q8(x, axis):
    """Round to the int8 grid along ``axis`` (absmax scale); the value is
    kept in float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def _mm_int8(x, w):
    """(n, k) @ (k, m) as an int8 step would compute it: all three matrix
    products of the layer -- forward, and both of the backward -- take
    their operands on the int8 grid (rows of x, dy; columns of w)."""
    return _q8(x, -1) @ _q8(w, 0)


def _mm_int8_fwd(x, w):
    xq, wq = _q8(x, -1), _q8(w, 0)
    return xq @ wq, (xq, wq)


def _mm_int8_bwd(res, dy):
    xq, wq = res
    dyq = _q8(dy, -1)
    return dyq @ wq.T, xq.T @ dyq


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def _mm(x, w, quant):
    """(n, k) @ (k, m), in the control's precision if asked."""
    if quant is None:
        return x @ w
    if quant == "int8":
        return _mm_int8(x, w)
    raise ValueError(f"unknown control precision {quant!r}")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (S, H, D) at positions pos (S,), interleaved pairs."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def attention(q, k, v):
    """Causal softmax attention, q (S, H, D), k/v (S, H, D), in blocks of
    query rows so the (H, S, S) scores never exist whole."""
    s, _, d = q.shape
    outs = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(lo + Q_BLOCK, s)
        sc = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) / math.sqrt(d)
        mask = (jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :])
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:hi]))
    return jnp.concatenate(outs, 0)


def layer(lp, x, pos, cfg, quant):
    d = x.shape[-1]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n = rms_norm(x, lp["attn_norm"], cfg["rms_norm_eps"])
    q = _mm(n, lp["wq"].reshape(d, h * dh), quant).reshape(-1, h, dh)
    k = _mm(n, lp["wk"].reshape(d, kv * dh), quant).reshape(-1, kv, dh)
    v = _mm(n, lp["wv"].reshape(d, kv * dh), quant).reshape(-1, kv, dh)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    o = attention(q, k, v).reshape(-1, h * dh)
    x = x + _mm(o, lp["wo"].reshape(h * dh, d), quant)
    n = rms_norm(x, lp["mlp_norm"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(_mm(n, lp["w_gate"], quant))
    return x + _mm(gate * _mm(n, lp["w_up"], quant), lp["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer_fwd(lp, x, cfg_items, quant):
    with jax.default_matmul_precision("highest"):
        return layer(lp, x, jnp.arange(x.shape[0]), dict(cfg_items), quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer_bwd(lp, x, dy, cfg_items, quant):
    """Cotangents (d lp, d x) of one layer at its input ``x``."""
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(lambda p, h: layer(p, h, jnp.arange(h.shape[0]),
                                            dict(cfg_items), quant), lp, x)
        return vjp(dy)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(scale, x, eps):
    return rms_norm(x, scale, eps)


def hidden(params, tokens, cfg, quant=None, keep=False):
    """Final-norm hidden states (S, d) of one sequence of token ids; with
    ``keep`` also every layer's input (what its backward starts from)."""
    items = cfg_items(cfg)
    x = params["embed"][tokens]
    inputs = []
    for i in range(cfg["num_hidden_layers"]):
        if keep:
            inputs.append(x)
        x = _layer_fwd(params[f"layer{i}"], x, items, quant)
    hs = _final_norm(params["final_norm"], x, cfg["rms_norm_eps"])
    return (hs, inputs, x) if keep else hs


# -- training -------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("quant", "eps"))
def _head_block(scale, table, x, targets, quant, eps):
    """Summed cross-entropy of a block of positions from the last layer's
    output ``x``, and its cotangents on (scale, table, x)."""
    def ce(scale, table, x):
        lg = _mm(rms_norm(x, scale, eps), table.T, quant)
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, targets[:, None], -1)[:, 0])

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(ce, argnums=(0, 1, 2))(scale, table, x)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(table_grad, tokens, dx):
    """The embedding lookup's backward: rows of ``dx`` added at ``tokens``."""
    return table_grad.at[tokens].add(dx)


def _row_grad(params, tokens, targets, cfg, quant):
    """Summed cross-entropy of one row and its gradient, layer by layer."""
    items = cfg_items(cfg)
    n_layers, eps = cfg["num_hidden_layers"], cfg["rms_norm_eps"]
    _, inputs, last = hidden(params, tokens, cfg, quant, keep=True)
    total, d_scale, d_table, d_last = 0.0, 0.0, 0.0, []
    for lo in range(0, tokens.shape[0], HEAD_BLOCK):
        ls, (gs, gt, gx) = _head_block(
            params["final_norm"], params["embed"], last[lo:lo + HEAD_BLOCK],
            targets[lo:lo + HEAD_BLOCK], quant, eps)
        total, d_scale, d_table = total + ls, d_scale + gs, d_table + gt
        d_last.append(gx)
    grads = {"final_norm": d_scale}
    dx = jnp.concatenate(d_last, 0)
    for i in reversed(range(n_layers)):
        grads[f"layer{i}"], dx = _layer_bwd(params[f"layer{i}"], inputs[i],
                                            dx, items, quant)
        inputs[i] = None
    grads["embed"] = _scatter_rows(d_table, tokens, dx)
    return total, grads


def cfg_items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))
                        and not isinstance(v, bool)))


def loss_and_grads(params, tokens, targets, cfg, quant=None):
    """Mean cross-entropy over every position of (rows, S) tokens and its
    gradient, one row at a time."""
    total, grads = 0.0, None
    for r in range(tokens.shape[0]):
        ls, g = _row_grad(params, jnp.asarray(tokens[r]),
                          jnp.asarray(targets[r]), cfg, quant)
        total = total + ls
        grads = g if grads is None else _add(grads, g)
    n = float(tokens.shape[0] * tokens.shape[1])
    return total / n, _scale(grads, 1.0 / n)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(a, s):
    return jax.tree.map(lambda x: x * s, a)


@jax.jit
def leaf_norms(tree):
    """Flat list of each leaf's L2 norm, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def diff_norms(a, b):
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


@functools.partial(jax.jit, static_argnames=("hp_items",), donate_argnums=(0, 1, 2, 3))
def _adamw(params, grads, mu, nu, t, hp_items):
    hp = dict(hp_items)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-30))
    grads = jax.tree.map(lambda g: g * clip, grads)
    mu = jax.tree.map(lambda m, g: hp["b1"] * m + (1 - hp["b1"]) * g,
                      mu, grads)
    nu = jax.tree.map(lambda v, g: hp["b2"] * v + (1 - hp["b2"]) * g * g,
                      nu, grads)
    c1, c2 = 1 - hp["b1"] ** t, 1 - hp["b2"] ** t

    def upd(p, m, v):
        step = (m / c1) / (jnp.sqrt(v / c2) + 1e-8)
        return p - hp["lr"] * (step + hp["weight_decay"] * p)

    return jax.tree.map(upd, params, mu, nu), mu, nu, grads


def train_steps(params, batches, cfg, hp, quant=None, grad_fault=None):
    """Follow ``len(batches)`` optimizer steps from ``params`` (float32;
    they are used up: the caller makes them anew from the seed where it
    needs the start again, so only one copy is alive beside the moments).

    ``batches`` is a list of (tokens, targets) host arrays, (rows, S) each.
    ``grad_fault(tokens, targets) -> (tokens, targets)`` plants a fault in
    what the gradient is taken over (the tests' and the calibration's).
    Returns the losses, the per-leaf norms of the first clipped gradient,
    and the parameters after the last step."""
    hp_items = tuple(sorted(hp.items()))
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for t, (tok, tgt) in enumerate(batches, 1):
        if grad_fault is not None:
            tok, tgt = grad_fault(tok, tgt)
        loss, grads = loss_and_grads(params, tok, tgt, cfg, quant)
        params, mu, nu, clipped = _adamw(params, grads, mu, nu,
                                         jnp.float32(t), hp_items)
        if first is None:
            first = jax.device_get(leaf_norms(clipped))
        del grads, clipped
        losses.append(float(loss))
    return {"losses": losses, "grad_norms": first, "params": params}


def with_delta_norms(result: dict, start) -> dict:
    """``result`` of ``train_steps`` with the per-leaf norms of the
    parameters' change from ``start`` in place of the parameters."""
    out = {k: v for k, v in result.items() if k != "params"}
    out["delta_norms"] = jax.device_get(diff_norms(result["params"], start))
    return out


# -- serving --------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("quant", "n_rows"))
def _logit_rows(table, hs, start, quant, n_rows):
    """Logits of rows [start, start + n_rows) of the hidden states."""
    with jax.default_matmul_precision("highest"):
        rows = jax.lax.dynamic_slice_in_dim(hs, start, n_rows, 0)
        return _mm(rows, table.T, quant)


@jax.jit
def _as_f32(params):
    return jax.tree.map(lambda p: p.astype(jnp.float32), params)


def _served_logits(params, tokens, start, cfg, quant, n_rows):
    """Row i of the result predicts the token at position start + i + 1."""
    hs = hidden(params, jnp.asarray(tokens), cfg, quant)
    return _logit_rows(params["embed"], hs, start, quant, n_rows)


def served_gaps(params, prompt, served, cfg, with_control=False):
    """For one request: by how much each served token's logit lies below
    the reference's best at its position (0 where it is the best).

    With ``with_control`` also the same gap for the token the int8 control
    puts first at each position (the reading a lower precision would give)
    and for an altered token (the served id plus one).
    Lengths are padded behind (causal, so the rows read are untouched) to
    multiples of ``LENGTH_PAD`` and ``ROWS_PAD``, so that a handful of
    programs serve every request and all are in the cache after a few runs
    (compiling, not computing, is what the reference costs)."""
    import numpy as np

    prompt, served = np.asarray(prompt), np.asarray(served)
    n_out, start = len(served), len(prompt) - 1
    n_rows = n_out + (-n_out) % ROWS_PAD
    need = max(len(prompt) + n_out - 1, start + n_rows)
    tokens = np.zeros(need + (-need) % LENGTH_PAD, np.int32)
    tokens[:len(prompt)] = prompt
    tokens[len(prompt):len(prompt) + n_out - 1] = served[:-1]
    # served weights are bfloat16: the same values, computed in float32
    params = _as_f32(params)

    def logits(quant):
        return _served_logits(params, tokens, start, cfg, quant,
                              n_rows)[:n_out]

    lg = logits(None)
    best = jnp.max(lg, -1)

    def gap_of(tok):
        return np.asarray(
            best - jnp.take_along_axis(lg, tok[:, None], -1)[:, 0])

    out = {"gaps": gap_of(jnp.asarray(served))}
    if with_control:
        out["control_gaps"] = gap_of(jnp.argmax(logits("int8"), -1))
        # what one altered token would read: the next id in the vocabulary
        out["altered_gaps"] = gap_of(
            (jnp.asarray(served) + 1) % cfg["vocab_size"])
    return out
