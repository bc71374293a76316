"""Published per-chip peaks, keyed by ``device_kind`` as jax reports it.

The table is ``peaks.json`` beside this module: the bf16 column copied
from ``scripts/perf_north_star.py``, the memory columns added, each row
with its source.  A device that is not in the table is an error, not a
default.
"""

from __future__ import annotations

import json
import os


def peak(device_kind: str, column: str) -> float:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(table)}")
    return table[device_kind][column]
