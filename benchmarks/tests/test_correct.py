"""``correct`` has to come out false when it should.

At a size a test run can hold (``rehearse.shrink``), on the CPU, with limits
set for that size the way the chip's were (between the sound runs' readings
and the control's):

- the control -- the reference in int8, put in the program's place -- fails;
- the rest of a run (``run.execute``, entered past the look for a chip),
  with the timed path broken underneath, sees ``correct`` come out false,
  once for each fault a cell can have: a step that returns its state
  unchanged; half of the batch left out, the mean taken over the rest; the
  exchange between chips left out; a token altered where it is produced.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import checks
import harness
import program
import reference
import rehearse
import run
import run_train
import weights
import work

# sound tiny runs on the CPU read loss 1e-5 to 3e-5, grad 2e-3, delta 1e-3 to
# 3e-3 (bfloat16 against float32); the int8 control reads loss 1e-4 to 2e-4,
# grad 5e-2 to 9e-2 and delta 2e-1 (a gap of norms is second order in noise,
# so it takes the backward products on the int8 grid too to move it)
TRAIN_LIMITS = {"limits": {"loss_gap": {"limit": 4e-5},
                           "grad_gap": {"limit": 1.5e-2},
                           "delta_gap": {"limit": 2e-2}}}
# float32 serving on the CPU puts the reference's own first token first
SERVE_LIMITS = {"limits": {"max_gap": {"limit": 1e-3},
                           "p90_gap": {"limit": 1e-4},
                           "wrong_length": {"limit": 0},
                           "prompt_altered": {"limit": 0}}}
SEED = (1 << 31) + 4242


@pytest.fixture(autouse=True)
def v5e_peaks(monkeypatch):
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: v5e)


def tiny(workload, limits):
    cell = rehearse.shrink(harness.find_cell(workload))
    cell["limits"] = limits
    return cell


def execute(cell, seconds=1.0):
    devices = jax.devices("cpu")[:int(cell["chips"])]
    return run.execute(cell, devices, SEED, seconds, False,
                       time.perf_counter(), harness.CompileClock())


def broken_trainer(monkeypatch, wrap):
    """Every trainer the run builds gets its step replaced by ``wrap``."""
    build = program.build_trainer

    def build_broken(*a, **kw):
        tr = build(*a, **kw)
        tr.train_step = wrap(tr, tr.train_step)
        return tr

    monkeypatch.setattr(program, "build_trainer", build_broken)


def test_a_sound_training_run_is_correct():
    done = execute(tiny("lm_train_4k", TRAIN_LIMITS))
    assert done["result"]["correct"], done["checks"]
    assert set(done["checks"]) == {"loss_gap", "grad_gap", "delta_gap"}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    def wrap(tr, step):
        def unchanged(tok, tgt):
            keep = jax.tree.map(jnp.copy, (tr.params, tr.opt_state))
            loss = step(tok, tgt)
            if tr._step > 1:    # the first step stands: Adam's state exists
                tr.params, tr.opt_state = keep
            return loss
        return unchanged

    broken_trainer(monkeypatch, wrap)
    done = execute(tiny("lm_train_4k", TRAIN_LIMITS))
    assert not done["result"]["correct"]
    assert not done["checks"]["delta_gap"]["ok"]


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    def wrap(tr, step):
        return lambda tok, tgt: step(
            np.concatenate([tok[:len(tok) // 2]] * 2),
            np.concatenate([tgt[:len(tgt) // 2]] * 2))

    broken_trainer(monkeypatch, wrap)
    done = execute(tiny("lm_train_4k", TRAIN_LIMITS))
    assert not done["result"]["correct"], done["checks"]


def test_the_exchange_between_chips_left_out_is_not_correct(monkeypatch):
    cell = tiny("lm_train_dp4", TRAIN_LIMITS)
    per_chip = int(cell["mix"]["rows_per_chip"])

    def wrap(tr, step):
        # every chip is fed the first chip's rows: the reduced gradient is
        # then what that chip would hold with the exchange left out
        return lambda tok, tgt: step(np.tile(tok[:per_chip], (4, 1)),
                                     np.tile(tgt[:per_chip], (4, 1)))

    sound = execute(cell)
    assert sound["result"]["correct"], sound["checks"]
    broken_trainer(monkeypatch, wrap)
    done = execute(cell)
    assert not done["result"]["correct"], done["checks"]


def test_the_int8_control_in_the_trainers_place_is_not_correct():
    cell = tiny("lm_train_4k", TRAIN_LIMITS)
    cfg, mix = cell["config_file"], cell["mix"]
    rng = np.random.default_rng(3)
    tok = rng.integers(0, cfg["vocab_size"], (2, 129)).astype(np.int32)
    batches = [(tok[:, :-1], tok[:, 1:])] * 3
    hp = run_train.hyperparams(mix)
    ref = run_train.follow(cell, SEED, batches, hp)
    control = run_train.follow(cell, SEED, batches, hp, quant="int8")
    ok, judged = checks.judge(checks.train_numbers(control, ref),
                              TRAIN_LIMITS)
    assert not ok, judged
    same, _ = checks.judge(checks.train_numbers(ref, ref), TRAIN_LIMITS)
    assert same


def test_a_sound_serving_run_is_correct_and_an_altered_token_is_not(monkeypatch):
    from distributed_pytorch_tpu.serve import ContinuousBatcher

    cell = tiny("lm_serve_chat", SERVE_LIMITS)
    done = execute(cell, seconds=3.0)
    assert done["result"]["correct"], done["checks"]
    emit, calls = ContinuousBatcher._emit, [0]

    def altered(self, slot, tok, out):
        calls[0] += 1
        if calls[0] % 7 == 0:
            tok = (tok + 1) % cell["config_file"]["vocab_size"]
        return emit(self, slot, tok, out)

    monkeypatch.setattr(ContinuousBatcher, "_emit", altered)
    done = execute(cell, seconds=3.0)
    assert not done["result"]["correct"]
    assert not done["checks"]["max_gap"]["ok"]


def test_the_int8_control_in_the_servers_place_is_not_correct():
    cell = tiny("lm_serve_chat", SERVE_LIMITS)
    cfg = cell["config_file"]
    model = cell["family"].reference
    params = weights.make_params(cell["family"], SEED, cfg)
    rng = np.random.default_rng(5)
    worst = 0.0
    for _ in range(3):
        prompt = rng.integers(0, cfg["vocab_size"], 40).astype(np.int32)
        # greedy tokens of the reference itself, so the sound gap is 0
        seq = list(prompt)
        for _ in range(24):
            lg = reference._served_logits(
                model, params, np.pad(seq, (0, (-len(seq)) % 256)),
                len(seq) - 1, cfg, None, 128)[0]
            seq.append(int(jnp.argmax(lg)))
        served = np.asarray(seq[len(prompt):], np.int32)
        got = reference.served_gaps(model, params, prompt, served, cfg,
                                    with_control=True)
        assert got["gaps"].max() == 0.0
        worst = max(worst, float(got["control_gaps"].max()))
    assert worst > SERVE_LIMITS["limits"]["max_gap"]["limit"]


def test_the_chips_readings_of_control_and_faults_are_judged_not_correct():
    """``calibrate.judge_upper`` on the smallest readings the chip gave
    (PERF.md section 6), against the limits as committed."""
    import calibrate

    chat = harness.find_cell("lm_serve_chat")["limits"]
    row = {"program": {"max_gap": 0.0469, "p90_gap": 0.0, "wrong_length": 0.0,
                       "prompt_altered": 0.0},
           "control_int8": {"max_gap": 0.169, "p90_gap": 0.0172},
           "altered_token": {"min_gap": 0.582}}
    assert checks.judge(row["program"], chat)[0]
    judged = calibrate.judge_upper(row, chat)
    assert judged["control_int8"] == {"correct": False,
                                      "failed": ["max_gap", "p90_gap"]}
    assert judged["altered_token"] == {"correct": False,
                                       "failed": ["max_gap"]}
    train = harness.find_cell("lm_train_4k")["limits"]
    row = {"control_int8": {"loss_gap": 3.01e-4, "grad_gap": 0.185,
                            "delta_gap": 0.618},
           "half_batch": {"loss_gap": 4.8e-4, "grad_gap": 0.0734,
                          "delta_gap": 0.0111}}
    assert not any(j["correct"]
                   for j in calibrate.judge_upper(row, train).values())
