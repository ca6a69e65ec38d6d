"""The least time the chip could take for the records stepped in the traced
seconds (their bytes by ``zbench/roofline.py``, counted from the re-read
log, at the chip's HBM peak) over the device time of the programs whose
name contains the reader's ``match``, in %."""

from zbench import roofline, trace


def read(ctx: dict):
    traced, peak = ctx.get("trace"), ctx.get("peak")
    if not traced or not peak:
        return None
    durs = trace.program_launches(traced["doc"], traced["window_ns"], ctx["reader"]["match"])
    lo, hi = traced["wall_ns"]
    stepped = [
        r.vtype for rows in ctx["rows"].values() for r in rows
        if lo <= r.timestamp * 1_000_000 < hi
    ]
    return roofline.share_pct(
        stepped, ctx["num_vars"], sum(durs) / 1e9, peak["hbm_bytes_per_s"]
    )
