"""A configuration's model files, found by the configuration's ``family``.

A configuration file (``benchmarks/configs/<name>.json``) names its
``"family"``; the harness finds ``benchmarks/families/<family>/`` by that
name, as it finds a metric's reader, and reaches the model only through it.
A new architecture therefore arrives as new files: one directory here, a
configuration file, limits, and entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import os
import types

CONTRACT = """\
A family is a directory benchmarks/families/<family>/ of four files, each
with every name below.  ``cfg`` is the configuration file's dict, ``quant``
the control's precision (None or "int8"), handed on to ``reference.mm``.

reference.py -- the plain equations: float32 jax.numpy, no kernels, no
    cache, no batching.  May import jax, numpy, the shared ``reference``
    (``mm`` for every matrix product of a dense layer or the head, so that
    the int8 control reaches it; ``scatter_rows``) and another family's
    reference; nothing of the program, and not ``program.py``.
  layer_keys(cfg) -> [key, ...]      the layers' keys in the parameter tree, in order
  layer(lp, x, pos, cfg, quant) -> x one layer on (S, d) rows at positions pos (S,); traced under jit, differentiated by the shared driver
  embed(params, tokens) -> (S, d)    the lookup
  embed_backward(grads, params, tokens, dx)
                                     adds the lookup's cotangent dx (S, d) into the gradient tree, which already holds the head's part
  head_params(params) -> dict        the top-level leaves the head reads (final norm, table), under their own keys
  final(hp, x, cfg) -> (n, d)        the last layer's output rows, normed
  project(hp, hs, cfg, quant) -> (n, V)   logits of normed rows
weights.py -- shapes only; the values are the benchmark's (weights._make).
  leaf_shapes(cfg) -> tree           the parameter tree the program's model takes: name -> (shape, fan), fan = None for a norm scale, "embed" for a table, else the fan-in
  tiny                               dict of configuration keys at a size the CPU rehearses in seconds (every code path stays)
work.py -- operations and bytes the mathematics requires, from shapes alone; imports nothing but the standard library.
  param_count(cfg); train_flops_per_token(cfg, seq); kv_bytes_per_token(cfg, itemsize=2)
  decode_flops(cfg, tokens, context_tokens); prompt_flops(cfg, length)
  kernels                            {kernel: fn(cfg, **numbers) -> {"flops", "bytes"}}: what each kernel of the family must do, read by the reader kernel.<kernel>_roofline
program.py -- the family's only file that imports the program under test.
  model_config(cfg)                  the program's model configuration
  trainer_keywords, server_keywords  dicts: what the family adds to LMTrainConfig / ContinuousBatcher
  kernel_compiles(cell) -> {name: (fn, [(dims, dtype), ...])}
                                     the family's kernels at the cell's real shapes, for the chip-less compile (rehearse.py --compile)
Shared and never copied into a family: the int8 control, AdamW and
clipping, the norms of leaves, the layer-by-layer gradient driver and the
padding and gap arithmetic of served_gaps (reference.py); seed -> values
(weights.py); peaks and roofline_seconds (work.py); building and resetting
the trainer and the server (program.py); checks.py, traffic.py, tracing.py.
"""

PARTS = {
    "reference": ("layer_keys", "layer", "embed", "embed_backward",
                  "head_params", "final", "project"),
    "weights": ("leaf_shapes", "tiny"),
    "work": ("param_count", "train_flops_per_token", "kv_bytes_per_token",
             "decode_flops", "prompt_flops", "kernels"),
    "program": ("model_config", "trainer_keywords", "server_keywords",
                "kernel_compiles"),
}

# where families live; the tests add a directory of their own
ROOTS = [os.path.dirname(os.path.abspath(__file__))]
_loaded: dict = {}


class ContractError(SystemExit):
    """A family that is not there or lacks a name: the run ends with the
    contract's text."""


def _fail(family: str, what: str):
    raise ContractError(f"family {family!r}: {what}\n\n{CONTRACT}")


def load(family: str) -> types.SimpleNamespace:
    """The family's four modules, ``.reference``, ``.weights``, ``.work``,
    ``.program``, loaded once a process (the reference's functions are
    static arguments of jitted programs: one object, one compile)."""
    where = next((d for d in (os.path.join(r, family) for r in ROOTS)
                  if os.path.isdir(d)), None)
    if where is None:
        _fail(family, f"no such directory under {ROOTS}")
    if where in _loaded:
        return _loaded[where]
    fam = types.SimpleNamespace(name=family, dir=where)
    for part, names in PARTS.items():
        path = os.path.join(where, part + ".py")
        if not os.path.exists(path):
            _fail(family, f"no {part}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_family_{family}_{part}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        missing = [n for n in names if not hasattr(mod, n)]
        if missing:
            _fail(family, f"{part}.py lacks {', '.join(missing)}")
        setattr(fam, part, mod)
    _loaded[where] = fam
    return fam


def of_config(cfg: dict, config_name: str = "?") -> types.SimpleNamespace:
    if "family" not in cfg:
        _fail("?", f"configuration {config_name!r} names no \"family\"")
    return load(cfg["family"])
