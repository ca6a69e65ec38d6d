"""Device time per launch (ms, mean over the traced seconds) of the
programs whose name contains the reader's ``match``."""

from zbench import trace


def read(ctx: dict):
    traced = ctx.get("trace")
    if not traced:
        return None
    durs = trace.program_launches(traced["doc"], traced["window_ns"], ctx["reader"]["match"])
    return sum(durs) / len(durs) / 1e6 if durs else None
