#!/usr/bin/env python3
"""``calibrate_one_row.py`` for a cell whose model has linear-attention
layers (the gated delta rule) and a gated shared expert.

    python3 benchmarks/calibrate_gated_delta.py --workload <name> --seeds 12 \
        --controls 3 --out chiprun_out/<name>.calib.json

Everything is ``calibrate.py``'s and ``calibrate_one_row.py``'s own code and
options (the int8 control, half of the one row left out).  Five faults are
planted in the program on the first ``--controls`` seeds, against the sound
reference:

- ``expert_zeroed``: one held expert of layer 1 adds nothing (its down
  projection zeroed), as ``calibrate_one_row.py`` plants it;
- ``carry_zeroed``: the state carried into each chunk of the delta rule is
  zeroed in every linear layer (``ops/gated_delta._carry`` replaced for a
  trainer of its own, compiled once);
- ``beta_half``: layer 1's beta no longer reads its input (its columns of
  ``w_ba`` zeroed: beta is a half everywhere);
- ``conv_past_zeroed``: layer 1's causal convolution reads the current
  position only (the taps on earlier positions zeroed);
- ``shared_gate_stuck``: layer 1's shared-expert gate no longer reads its
  input (``w_sg`` zeroed: a half).

``calibrate_one_row.pick_flips`` is left out: it reads every layer's router
off a plain-scale norm, which this family's zero-centred norms are not.

A fourth fork of one job, because a ``model_config`` PR may edit no file of
the benchmark: it rebinds ``run_train.prepare``, ``calibrate.fault_readings``,
``calibrate.UPPER_KEYS``, ``program.reset_trainer``,
``ops/gated_delta._carry`` and ``transformer._gated_delta_mixer``, all of it
inside ``main()``, ``planted()`` and ``carry_zeroed()`` and nothing at
import.  ROADMAP W16 folds the scripts into one, the faults
as data.
"""

from __future__ import annotations

import sys

import calibrate
import calibrate_one_row as one_row
from calibrate_shared_gate import _edited

HERE = calibrate.HERE
CARRY_TRAINER = [None]    # the trainer compiled with the carry zeroed


def _linear_1(p, leaf, edit):
    return _edited(p, ("layer1", "attn_linear", leaf), edit)


# name -> the program's copy of the weights, altered
FAULTS = {
    "expert_zeroed": lambda p: _edited(
        p, ("layer1", "moe", "w_down"), lambda w: w.at[0].set(0.0)),
    "beta_half": lambda p: _linear_1(
        p, "w_ba", lambda w: w.at[:, :w.shape[1] // 2].set(0.0)),
    "conv_past_zeroed": lambda p: _linear_1(
        p, "conv", lambda w: w.at[:-1].set(0.0)),
    "shared_gate_stuck": lambda p: _edited(
        p, ("layer1", "shared", "w_sg"), lambda w: 0.0 * w),
}


def planted(cell, seed, ref, edit=None, trainer=None) -> dict:
    """The program's first steps from weights that ``edit`` has altered in
    its copy (or through ``trainer``, built with a fault of its own),
    against the sound reference's numbers ``ref``."""
    import jax

    import checks
    import program
    import run_train

    reset = program.reset_trainer
    n_ref = int(cell["mix"]["reference_steps"])
    if edit is not None:
        program.reset_trainer = lambda tr, params: reset(tr, edit(params))
    trainer = trainer or one_row.TRAINER[0]
    try:
        state = run_train.prepare(cell, jax.devices()[:int(cell["chips"])],
                                  seed, 1.0, trainer=trainer)
        firsts = run_train.first_steps(state, cell, seed, n_ref, n_ref)
    finally:
        program.reset_trainer = reset
        program.release_trainer(trainer)
    return checks.train_numbers(firsts["prog"], ref)


def carry_zeroed(cell, seed, ref) -> dict:
    """``planted`` through a trainer whose step was traced with the state
    carried into each chunk zeroed (built once, on the first seed).  The
    mixer is ``jax.checkpoint``-ed, which keeps the jaxpr it traced by the
    function: a new function in its place is traced anew."""
    import jax

    import program
    from distributed_pytorch_tpu.models import transformer
    from distributed_pytorch_tpu.ops import gated_delta

    carry, mixer = gated_delta._carry, transformer._gated_delta_mixer
    gated_delta._carry = lambda s, x: carry(0.0 * s, x)
    transformer._gated_delta_mixer = lambda *a: mixer(*a)
    try:
        if CARRY_TRAINER[0] is None:
            CARRY_TRAINER[0] = program.build_trainer(
                cell, jax.devices()[:int(cell["chips"])], seed)
            program.release_trainer(CARRY_TRAINER[0])
        return planted(cell, seed, ref, trainer=CARRY_TRAINER[0])
    finally:
        gated_delta._carry, transformer._gated_delta_mixer = carry, mixer


def fault_readings(cell, seed, batches, hp, ref) -> dict:
    import checks
    import run_train

    out = {name: checks.train_numbers(
        run_train.follow(cell, seed, batches, hp, **kw), ref)
        for name, kw in (("control_int8", dict(quant="int8")),
                         ("half_batch", dict(grad_fault=one_row.half_row)))}
    for name, edit in FAULTS.items():
        out[name] = planted(cell, seed, ref, edit)
    out["carry_zeroed"] = carry_zeroed(cell, seed, ref)
    return out


def main(argv=None) -> int:
    import run_train

    prepare = run_train.prepare

    def keep_trainer(*a, **kw):
        state = prepare(*a, **kw)
        if kw.get("trainer") is None:
            one_row.TRAINER[0] = state["trainer"]
        return state

    run_train.prepare = keep_trainer
    calibrate.fault_readings = fault_readings
    calibrate.UPPER_KEYS += tuple(FAULTS) + ("carry_zeroed",)
    return calibrate.main(argv)


if __name__ == "__main__":
    sys.exit(main())
