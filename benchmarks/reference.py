"""The plain reference's driver and its control: what every family shares.

A family's ``reference.py`` (``benchmarks/families/<family>/``) holds the
equations: a layer, the lookup, the head.  This module drives them, in
float32 at matmul precision ``highest``, with no kernels, no cache, no
batching and no sharding.  It imports nothing of the program and takes
nothing the program has made; it is handed the benchmark's own weights
(``weights.make_params``) and the tokens the timed path saw.  ``model`` below
is a family's reference module (``cell["family"].reference``).

Training: mean cross-entropy over every position, global-norm clipping, then
AdamW as optax defines it (bias-corrected moments, eps 1e-8 outside the
root, decoupled decay on every leaf).

The control is the same code with the operands of every matrix product that
goes through ``mm`` -- a family routes the products of its dense layers and
its head through it -- rounded to an int8 grid (absmax scale per row of
activations and of cotangents, per column of weights), forward and
backward: the nearest precision below the bfloat16 the configurations
state, and the step that would tempt a later PR.

Memory and compile time: rows are taken one at a time and *layer by layer*:
one small program runs a layer forward, one runs its backward (recomputing
its forward), one the head and loss over a block of positions.  Each
compiles once, in seconds, and is small enough for the persistent cache
(a whole 18-layer gradient as one program was 267 MB and took minutes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


def mm(x, w, quant):
    """(n, k) @ (k, m), in the control's precision if asked."""
    if quant is None:
        return x @ w
    if quant == "int8":
        return _mm_int8(x, w)
    raise ValueError(f"unknown control precision {quant!r}")


@functools.partial(jax.jit, static_argnames=("layer", "cfg_items", "quant"))
def _layer_fwd(lp, x, layer, cfg_items, quant):
    with jax.default_matmul_precision("highest"):
        return layer(lp, x, jnp.arange(x.shape[0]), dict(cfg_items), quant)


@functools.partial(jax.jit, static_argnames=("layer", "cfg_items", "quant"))
def _layer_bwd(lp, x, dy, layer, cfg_items, quant):
    """Cotangents (d lp, d x) of one layer at its input ``x``."""
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(lambda p, h: layer(p, h, jnp.arange(h.shape[0]),
                                            dict(cfg_items), quant), lp, x)
        return vjp(dy)


@functools.partial(jax.jit, static_argnames=("final", "cfg_items"))
def _final_norm(hp, x, final, cfg_items):
    return final(hp, x, dict(cfg_items))


def hidden(model, params, tokens, cfg, quant=None, keep=False):
    """Final-norm hidden states (S, d) of one sequence of token ids; with
    ``keep`` also every layer's input (what its backward starts from)."""
    items = cfg_items(cfg)
    x = model.embed(params, tokens)
    inputs = []
    for key in model.layer_keys(cfg):
        if keep:
            inputs.append(x)
        x = _layer_fwd(params[key], x, model.layer, items, quant)
    hs = _final_norm(model.head_params(params), x, model.final, items)
    return (hs, inputs, x) if keep else hs


# -- training -------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("final", "project", "cfg_items", "quant"))
def _head_block(hp, x, targets, final, project, cfg_items, quant):
    """Summed cross-entropy of a block of positions from the last layer's
    output ``x``, and its cotangents on (the head's leaves, x)."""
    cfg = dict(cfg_items)

    def ce(hp, x):
        lg = project(hp, final(hp, x, cfg), cfg, quant)
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, targets[:, None], -1)[:, 0])

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(ce, argnums=(0, 1))(hp, x)


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_rows(table_grad, tokens, dx):
    """A lookup's backward: rows of ``dx`` added at ``tokens``."""
    return table_grad.at[tokens].add(dx)


def _row_grad(model, params, tokens, targets, cfg, quant):
    """Summed cross-entropy of one row and its gradient, layer by layer."""
    items = cfg_items(cfg)
    keys = model.layer_keys(cfg)
    _, inputs, last = hidden(model, params, tokens, cfg, quant, keep=True)
    hp = model.head_params(params)
    total, d_head, d_last = 0.0, None, []
    for lo in range(0, tokens.shape[0], HEAD_BLOCK):
        ls, (gh, gx) = _head_block(
            hp, last[lo:lo + HEAD_BLOCK], targets[lo:lo + HEAD_BLOCK],
            model.final, model.project, items, quant)
        total = total + ls
        d_head = gh if d_head is None else jax.tree.map(jnp.add, d_head, gh)
        d_last.append(gx)
    grads = dict(d_head)
    dx = jnp.concatenate(d_last, 0)
    for i in reversed(range(len(keys))):
        grads[keys[i]], dx = _layer_bwd(params[keys[i]], inputs[i], dx,
                                        model.layer, items, quant)
        inputs[i] = None
    model.embed_backward(grads, params, tokens, dx)
    return total, grads


def cfg_items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))
                        and not isinstance(v, bool)))


def loss_and_grads(model, params, tokens, targets, cfg, quant=None):
    """Mean cross-entropy over every position of (rows, S) tokens and its
    gradient, one row at a time."""
    total, grads = 0.0, None
    for r in range(tokens.shape[0]):
        ls, g = _row_grad(model, params, jnp.asarray(tokens[r]),
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


def train_steps(model, params, batches, cfg, hp, quant=None,
                grad_fault=None):
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
        loss, grads = loss_and_grads(model, params, tok, tgt, cfg, quant)
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

@functools.partial(jax.jit,
                   static_argnames=("project", "cfg_items", "quant", "n_rows"))
def _logit_rows(hp, hs, start, project, cfg_items, quant, n_rows):
    """Logits of rows [start, start + n_rows) of the hidden states."""
    with jax.default_matmul_precision("highest"):
        rows = jax.lax.dynamic_slice_in_dim(hs, start, n_rows, 0)
        return project(hp, rows, dict(cfg_items), quant)


@jax.jit
def _as_f32(params):
    return jax.tree.map(lambda p: p.astype(jnp.float32), params)


def _served_logits(model, params, tokens, start, cfg, quant, n_rows):
    """Row i of the result predicts the token at position start + i + 1."""
    hs = hidden(model, params, jnp.asarray(tokens), cfg, quant)
    return _logit_rows(model.head_params(params), hs, start, model.project,
                       cfg_items(cfg), quant, n_rows)


def served_gaps(model, params, prompt, served, cfg, with_control=False):
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
        return _served_logits(model, params, tokens, start, cfg, quant,
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
