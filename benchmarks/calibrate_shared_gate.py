#!/usr/bin/env python3
"""``calibrate_one_row.py`` for a cell whose model has a shared expert and a
gate on the attention output.

    python3 benchmarks/calibrate_shared_gate.py --workload <name> --seeds 12 \
        --controls 3 --out chiprun_out/<name>.calib.json

Everything is ``calibrate.py``'s and ``calibrate_one_row.py``'s own code and
options (the int8 control, half of the one row left out).  Three faults are
planted in the program's copy of the weights on the first ``--controls``
seeds, against the sound reference:

- ``expert_zeroed``: one held expert of layer 1 adds nothing (its down
  projection zeroed), as ``calibrate_one_row.py`` plants it;
- ``shared_zeroed``: the shared expert of layer 1 adds nothing (its down
  projection zeroed);
- ``gate_stuck``: the attention gate of layer 1 no longer reads its input
  (``wg`` zeroed: every head's gate is a half).

``calibrate_one_row.pick_flips`` is left out: it reads every layer's router
off the attention's normed input, which is another family's.

A third fork of one job, because a ``model_config`` PR may edit no file of the
benchmark: it rebinds ``run_train.prepare``, ``calibrate.fault_readings``,
``calibrate.UPPER_KEYS`` and ``program.reset_trainer``, all of it inside
``main()`` and ``planted()`` and nothing at import.  A ``benchmark`` PR folds
the three scripts into one, the faults as data (ROADMAP W16).
"""

from __future__ import annotations

import sys

import calibrate
import calibrate_one_row as one_row

HERE = calibrate.HERE


def _edited(tree: dict, path: tuple, edit) -> dict:
    """``tree`` with ``edit`` applied to the leaf at ``path``, nothing else
    copied."""
    key, rest = path[0], path[1:]
    return {**tree, key: _edited(tree[key], rest, edit) if rest
            else edit(tree[key])}


def _attention_of(layer: dict) -> str:
    return next(k for k in layer if k.startswith("attn_") and k != "attn_norm")


def _zero(leaf):
    return 0.0 * leaf


# name -> the program's copy of the weights, altered
FAULTS = {
    "expert_zeroed": lambda p: _edited(
        p, ("layer1", "moe", "w_down"), lambda w: w.at[0].set(0.0)),
    "shared_zeroed": lambda p: _edited(p, ("layer1", "shared", "w_down"),
                                       _zero),
    "gate_stuck": lambda p: _edited(
        p, ("layer1", _attention_of(p["layer1"]), "wg"), _zero),
}


def planted(cell, seed, ref, edit) -> dict:
    """The program's first steps from weights that ``edit`` has altered in
    its copy, against the sound reference's numbers ``ref``."""
    import jax

    import checks
    import program
    import run_train

    reset = program.reset_trainer
    n_ref = int(cell["mix"]["reference_steps"])
    program.reset_trainer = lambda trainer, params: reset(trainer,
                                                          edit(params))
    try:
        state = run_train.prepare(cell, jax.devices()[:int(cell["chips"])],
                                  seed, 1.0, trainer=one_row.TRAINER[0])
        firsts = run_train.first_steps(state, cell, seed, n_ref, n_ref)
    finally:
        program.reset_trainer = reset
        program.release_trainer(one_row.TRAINER[0])
    return checks.train_numbers(firsts["prog"], ref)


def fault_readings(cell, seed, batches, hp, ref) -> dict:
    import checks
    import run_train

    out = {name: checks.train_numbers(
        run_train.follow(cell, seed, batches, hp, **kw), ref)
        for name, kw in (("control_int8", dict(quant="int8")),
                         ("half_batch", dict(grad_fault=one_row.half_row)))}
    for name, edit in FAULTS.items():
        out[name] = planted(cell, seed, ref, edit)
    return out


def main(argv=None) -> int:
    import run_train

    prepare = run_train.prepare

    def keep_trainer(*a, **kw):
        state = prepare(*a, **kw)
        one_row.TRAINER[0] = state["trainer"]
        return state

    run_train.prepare = keep_trainer
    calibrate.fault_readings = fault_readings
    calibrate.UPPER_KEYS += tuple(FAULTS)
    return calibrate.main(argv)


if __name__ == "__main__":
    sys.exit(main())
