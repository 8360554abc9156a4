"""The chip's side of every roofline and share of the peak: the table of
peaks, keyed by ``device_kind`` (a device that is not in it is an error), and
the least time the chip could take for given operations and bytes.

The model's side -- the operations and bytes its algorithm needs, from shapes
alone -- is the family's (``benchmarks/families/<family>/work.py``), reached
through ``cell["family"].work`` and, by a metric's reader, ``ctx["work"]``.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """{"bf16_flops_per_s", "hbm_bytes_per_s", "hbm_bytes"} of one chip."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> dict:
    """The least time the chip could take, and which bound sets it."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "memory"}
