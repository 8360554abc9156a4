"""Shared compile-cache setup for worker subprocesses.

Workers are fresh processes: without the suite's persistent XLA
compilation cache, every integration-test run recompiles from scratch.
Mirrors tests/conftest.py's settings.
"""

from distributed_pytorch_tpu.utils import compile_cache


def enable_compile_cache() -> None:
    compile_cache.enable(min_compile_secs=0.5)
