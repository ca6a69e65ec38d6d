"""1 - union of the device's operation intervals over the traced seconds."""


def read(ctx: dict):
    red = (ctx.get("trace") or {}).get("reduction")
    if not red or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
