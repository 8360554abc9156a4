"""Where the compile cache lives (utils/compile_cache.py), and the chip
smoke's refusal to run anywhere but on a TPU."""

import json
import os
import subprocess
import sys

import jax
import pytest

from distributed_pytorch_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_set_means_code_sets_no_directory(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, ``enable`` leaves the directory
    to JAX (which reads the variable itself) and hands that same directory
    to child processes."""
    outside = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv(compile_cache.ENV_VAR, outside)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append(name))
    assert compile_cache.enable() == outside
    assert "jax_compilation_cache_dir" not in updates
    assert compile_cache.enable(min_compile_secs=0.5) == outside
    assert updates == ["jax_persistent_cache_min_compile_time_secs"]
    assert compile_cache.child_env()[compile_cache.ENV_VAR] == outside


def test_unset_is_one_fixed_path_in_the_checkout(tmp_path):
    """Without the variable, two processes started from different
    directories both land on the same fixed path inside the checkout — the
    path is part of JAX's cache key, so one that moved would never hit."""
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    prog = ("import json, jax\n"
            "from distributed_pytorch_tpu.utils import compile_cache\n"
            "print(json.dumps([compile_cache.enable(), "
            "jax.config.jax_compilation_cache_dir]))")
    seen = []
    for cwd in (REPO, str(tmp_path)):
        out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=cwd,
                             capture_output=True, text=True, timeout=120,
                             check=True).stdout
        seen.append(json.loads(out.strip().splitlines()[-1]))
    want = os.path.join(REPO, ".jax_cache")
    assert seen == [[want, want], [want, want]]
    assert compile_cache.DEFAULT_DIR == want


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_checkout", "script_alone"])
def test_chip_smoke_refuses_without_a_tpu(tmp_path, alone):
    """Under JAX_PLATFORMS=cpu the smoke exits non-zero at the device
    check, before compiling anything, and prints no result — from the
    checkout, and from a directory that holds the script and nothing else
    of the repo."""
    script = os.path.join(REPO, "chip_smoke.py")
    cwd = REPO
    if alone:
        cwd = str(tmp_path)
        with open(script) as src, open(tmp_path / "chip_smoke.py", "w") as dst:
            dst.write(src.read())
        script = str(tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    # a cache directory of the test's own: nothing may be written to it
    env[compile_cache.ENV_VAR] = str(tmp_path / "cache")
    proc = subprocess.run([sys.executable, script], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "Nothing was run" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert not os.path.exists(tmp_path / "cache")
