#!/usr/bin/env python3
"""``calibrate.py`` for a training cell whose step is one row a chip.

    python3 benchmarks/calibrate_one_row.py --workload <name> --seeds 12 \
        --controls 3 --out chiprun_out/<name>.calib.json

``calibrate.py`` leaves half the batch out by dropping half the rows, which
of one row leaves none.  Here the fault keeps the first half of every row
(half the step's tokens left out, the mean taken over the rest); everything
else is ``calibrate.py``'s own code and options.  For a family with a routed
layer two more readings are taken on the first ``--controls`` seeds:

- ``expert_zeroed``: the program run with one held expert's down projection
  zeroed in its copy of the weights, against the sound reference (a fault
  in the program's place, as the tests plant it);
- ``pick_flips``: of the picks the float32 reference's routers make over
  the first batch, layer by layer, how many the program's routers (its
  compute type, its own layer inputs) make differently.
"""

from __future__ import annotations

import sys

import calibrate

HERE = calibrate.HERE


def half_row(tokens, targets):
    half = tokens.shape[1] // 2
    return tokens[:, :half], targets[:, :half]


def fault_readings(cell, seed, batches, hp, ref) -> dict:
    import checks
    import run_train

    out = {name: checks.train_numbers(
        run_train.follow(cell, seed, batches, hp, **kw), ref)
        for name, kw in (("control_int8", dict(quant="int8")),
                         ("half_batch", dict(grad_fault=half_row)))}
    if hasattr(cell["family"].work, "routed_rows"):
        out["expert_zeroed"] = expert_zeroed(cell, seed, hp, ref)
        out["pick_flips"] = pick_flips(cell, seed, batches[0][0])
    return out


def expert_zeroed(cell, seed, hp, ref) -> dict:
    """The program's first steps with expert 0 of layer 1 zeroed in its
    copy, against the sound reference."""
    import jax

    import checks
    import program
    import run_train

    reset = program.reset_trainer

    def reset_broken(trainer, params):
        moe = dict(params["layer1"]["moe"])
        moe["w_down"] = moe["w_down"].at[0].set(0.0)
        reset(trainer, {**params, "layer1": {**params["layer1"],
                                             "moe": moe}})

    n_ref = int(cell["mix"]["reference_steps"])
    program.reset_trainer = reset_broken
    try:
        state = run_train.prepare(cell, jax.devices()[:int(cell["chips"])],
                                  seed, 1.0, trainer=TRAINER[0])
        firsts = run_train.first_steps(state, cell, seed, n_ref, n_ref)
    finally:
        program.reset_trainer = reset
        program.release_trainer(state["trainer"])
    return checks.train_numbers(firsts["prog"], ref)


def pick_flips(cell, seed, tokens) -> dict:
    """Picks of the reference's routers (float32, its own layer inputs)
    that the program's routers (the mix's compute type, its own layer
    inputs) do not make, over the first row of the first batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference
    import weights
    from distributed_pytorch_tpu.models import transformer as tfm

    cfg, fam = cell["config_file"], cell["family"]
    model = fam.program.model_config(cfg)
    k = model.moe_top_k
    params = weights.make_params(fam, seed, cfg)
    row = jnp.asarray(tokens[0])

    def picks(x, lp):
        h = tfm.rms_norm(x, lp["attn_norm"], model.norm_eps)
        logits = jnp.dot(h.astype(jnp.float32), lp["moe"]["router"],
                         precision=jax.lax.Precision.HIGHEST)
        return np.sort(np.asarray(jax.lax.top_k(logits, k)[1]), -1)

    _, inputs, _ = reference.hidden(fam.reference, params, row, cfg,
                                    keep=True)
    x = params["embed"][row][None].astype(
        jnp.dtype(cell["mix"]["trainer"]["compute_dtype"]))
    pos = jnp.arange(row.shape[0])
    flips = []
    for i, x_ref in enumerate(inputs):
        lp = params[f"layer{i}"]
        mine, theirs = picks(x[0], lp), picks(x_ref, lp)
        flips.append(int(sum(len(set(a) - set(b))
                             for a, b in zip(mine, theirs))))
        x = jax.jit(lambda lp, x, i=i: tfm.block(
            lp, x, cfg=model, is_moe=True, pos=pos,
            kind=model.attn_kind(i))[0])(lp, x)
    return {"picks_per_layer": int(row.shape[0]) * k, "flipped": flips}


TRAINER = [None]     # the one compiled trainer, shared with expert_zeroed


def main(argv=None) -> int:
    import run_train

    prepare = run_train.prepare

    def keep_trainer(*a, **kw):
        state = prepare(*a, **kw)
        TRAINER[0] = state["trainer"]
        return state

    run_train.prepare = keep_trainer
    calibrate.fault_readings = fault_readings
    calibrate.UPPER_KEYS += ("expert_zeroed",)
    return calibrate.main(argv)


if __name__ == "__main__":
    sys.exit(main())
