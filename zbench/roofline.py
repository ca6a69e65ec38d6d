"""The least bytes the chip has to move for the records a log holds: rows
read and written per record kind times the row bytes of the tables in
``zeebe_tpu/tpu/state.py``'s layout. Counted from the log, not from the
kernel, so it reads the same work whatever implements the step. The work
is integer gathers and scatters: bytes bound it, operations do not.

A model, not a measurement: ``row_model.json`` holds the widths and which
rows a record of each value type has to touch at the least."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def row_bytes(num_vars: int) -> tuple:
    with open(os.path.join(HERE, "row_model.json")) as f:
        model = json.load(f)
    return {
        table: w["fixed_bytes"] + w["bytes_per_var"] * num_vars
        for table, w in model["tables"].items()
    }, model["touches"]


def least_bytes(value_types: list, num_vars: int) -> int:
    """``value_types``: the value type number of every record stepped."""
    widths, touches = row_bytes(num_vars)
    total = 0
    for vt in value_types:
        for table, (reads, writes) in touches.get(str(vt), {}).items():
            total += (reads + writes) * widths[table]
    return total


def share_pct(value_types: list, num_vars: int, program_seconds: float,
              hbm_bytes_per_s: float) -> float | None:
    if program_seconds <= 0 or not value_types:
        return None
    return 100.0 * (least_bytes(value_types, num_vars) / hbm_bytes_per_s) / program_seconds
