"""The chip's peaks from ``peaks.json``, keyed by ``device_kind``, and the
least time of a piece of work on them."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def for_kind(device_kind: str) -> dict:
    """Peaks of one device kind: FLOP/s per precision class (``bf16``,
    ``f32_highest``, ``int8``) and ``hbm_bytes_per_s``.  A kind that is
    not in the table is an error."""
    with open(PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json knows {sorted(table)}")
    return table[device_kind]


def least_time(flops: dict, nbytes: float, peaks: dict) -> float:
    """Seconds the chip needs at least: the larger of the operations at
    their classes' peaks (``{class: flops}``) and the bytes at HBM
    bandwidth."""
    compute = sum(f / peaks[cls] for cls, f in flops.items())
    return max(compute, nbytes / peaks["hbm_bytes_per_s"])
