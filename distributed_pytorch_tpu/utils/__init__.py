"""utils subpackage.

Submodules resolve LAZILY (PEP 562): the launcher agent — a
deliberately jax-free process (see launch.py's module docstring) —
imports ``utils.telemetry`` and ``utils.logging`` for gang lifecycle
events and structured logs, and an eager ``from . import checkpoint``
here would drag jax into it.  ``from .utils import <submodule>`` keeps
working everywhere (the import system loads submodules regardless);
only attribute-style access routes through ``__getattr__``.
"""

import importlib

_SUBMODULES = ("checkpoint", "compat", "compile_cache", "debug", "faults",
               "logging", "metrics", "sentry", "telemetry", "tracing")

__all__ = list(_SUBMODULES)


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
