"""The only module of the benchmark that touches the program under test.

It builds the program's own objects (``LMTrainer``, ``ContinuousBatcher``)
from a cell -- its configuration file, its traffic mix and what its family's
``program.py`` says of the model (``model_config`` and the keywords the family
adds) -- hands them the benchmark's weights, and reads the program's counters.
The yardstick (``reference``, ``work``, ``traffic``, ``tracing``, ``checks``)
imports none of this.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def place_like(tree, like):
    """``tree`` laid out as ``like`` is (a fresh buffer for every leaf: the
    program donates what it is handed)."""
    return jax.tree.map(lambda x, o: jax.device_put(x, o.sharding), tree,
                        like)


def build_trainer(cell: dict, devices, seed: int):
    """``LMTrainer`` over ``devices`` for the cell's job."""
    from distributed_pytorch_tpu.lm import (LMTrainConfig, LMTrainer,
                                            make_lm_mesh)

    fam = cell["family"].program
    dp = len(devices)   # the cell's chips: every training cell is dp today
    tcfg = LMTrainConfig(model=fam.model_config(cell["config_file"]), dp=dp,
                         seed=int(seed) % (1 << 31),
                         **{**cell["mix"]["trainer"], **fam.trainer_keywords})
    trainer = LMTrainer(tcfg, make_lm_mesh(tcfg, devices=list(devices)))
    # where each leaf of the state lives, kept for ``reset_trainer``
    trainer.bench_layout = {
        "params": jax.tree.map(_sharding, trainer.params),
        "opt": jax.tree.map(_sharding, trainer.opt_state)}
    return trainer


def _sharding(x):
    return x.sharding if isinstance(x, jax.Array) else None


def reset_trainer(trainer, params) -> None:
    """Hand the trainer the benchmark's weights and a fresh optimizer
    state: the state a run of this seed starts from."""
    from distributed_pytorch_tpu.lm import make_optimizer

    layout = trainer.bench_layout
    trainer.params = trainer.opt_state = None   # free the old state first
    placed = jax.tree.map(jax.device_put, params, layout["params"])
    fresh = jax.jit(make_optimizer(trainer.cfg).init)(placed)
    trainer.opt_state = jax.tree.map(
        lambda new, where: (jax.device_put(new, where)
                            if where is not None else new),
        fresh, layout["opt"])
    trainer.params = placed
    trainer._step = 0


def release_trainer(trainer) -> None:
    """Drop the trainer's state from the device (the compiled step stays)."""
    trainer.params = trainer.opt_state = None
    trainer.last_ok = trainer.last_metrics = None


def adam_first_moment(opt_state):
    """The ``mu`` tree of the optimizer's Adam state."""
    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def train_loader(mix: dict, corpus, global_rows: int, seed: int):
    """The program's host input pipeline over the benchmark's corpus."""
    from distributed_pytorch_tpu.data import lm_corpus
    from distributed_pytorch_tpu.data.pipeline import prefetch

    loader = lm_corpus.LMDataLoader(
        lm_corpus.LMCorpus(corpus), global_rows, int(mix["seq_len"]),
        shuffle=True, seed=int(seed) % (1 << 31))

    def batches():
        # one list per batch: ``prefetch`` compares a 2-tuple's first item
        # with a string, which an array of tokens cannot answer
        epoch = 0
        while True:
            loader.set_epoch(epoch)
            for tokens, targets in loader:
                yield [tokens, targets]
            epoch += 1

    return prefetch(batches(), depth=2), len(loader)


def build_server(cell: dict, params, seed: int, **options):
    """``ContinuousBatcher`` as the cell's deployment file sets it;
    everything not named there or by the family is the server's default
    (sampling, steps per sync, refill, chaining), EOS off.  ``options`` are
    for a witness only (``calibrate.py --witness``): no run of a cell
    passes any."""
    from distributed_pytorch_tpu.serve import ContinuousBatcher

    fam, deployment = cell["family"].program, cell["deployment"]
    options = {**fam.server_keywords, **options}
    return ContinuousBatcher(
        params, fam.model_config(cell["config_file"]),
        slots=deployment["slots"],
        max_len=deployment["max_len"], paged=deployment["paged"],
        pool_pages=deployment["pool_pages"],
        prompt_buckets=tuple(deployment["prompt_buckets"]),
        dtype=jnp.dtype(deployment["dtype"]),
        kv_dtype=deployment.get("kv_dtype"), eos_id=None,
        seed=int(seed) % (1 << 31), **options)


def server_counters(cb) -> dict:
    """The program's counters and host-clock spans, as plain numbers."""
    out = {k: float(v) for k, v in cb.stats.items()}
    for phase, s in cb.timing_stats().items():
        if isinstance(s, dict):
            out[f"span.{phase}.s"] = float(s["total_s"])
            out[f"span.{phase}.n"] = float(s["segments"])
    return out


def server_programs(cb) -> dict:
    """Which shapes the server holds compiled programs for."""
    return {"decode_widths": sorted(cb._decode_fns),
            "prefill_buckets": sorted(cb._prefill_fns)}
