"""The device's idle time inside the traced seconds, by what the host was
doing in it: the share (%) that the program's phase ``phase`` covers, or,
with ``phase`` null, the share that no phase of any track covers.

The phases are those the program stamps itself (``zeebe_tpu/tracing/
phases.py``): every selected wave's ``phases`` on the tracer's wave
timeline, and the drains', ticks' and raft group commits' on its cycle
ring, as ``[name, t0_us, t1_us]`` on the span clock. ``tracing.wall_ns``
puts them on the wall clock, and the traced window, known on both clocks
(``wall_ns``, ``window_ns``), puts them on the trace's. A program that
stamps no phases, or a trace without its clock tie, gives nothing."""

from zbench import trace


def phase_slices(tracer) -> list:
    """Every phase slice the tracer's rings still hold."""
    events = tracer.waves.snapshot()
    cycles = getattr(tracer, "cycles", None)
    if cycles is not None:
        events = events + cycles.snapshot()
    return [s for e in events for s in e.get("phases", ())]


def idle_intervals(doc: dict, window_ns: tuple) -> list:
    """The first device's gaps between operations, inside the window."""
    lo, hi = window_ns
    for plane in trace.device_planes(doc):
        lines = {line["name"]: line for line in plane["lines"]}
        ops = lines.get("XLA Ops") or lines.get("XLA Modules")
        if ops and ops["events"]:
            busy = trace.clip(
                trace.merged([[s, s + d] for _, s, d in ops["events"]]), lo, hi
            )
            return trace.subtract([[lo, hi]], busy)
    return []


def read(ctx: dict):
    from zeebe_tpu import tracing

    traced = ctx.get("trace")
    to_wall = getattr(tracing, "wall_ns", None)
    if not traced or traced["doc"].get("sync_ns") is None or to_wall is None:
        return None
    if tracing.TRACER is None:
        return None
    slices = phase_slices(tracing.TRACER)
    idle = idle_intervals(traced["doc"], traced["window_ns"])
    if not slices or not idle:
        return None
    offset = traced["wall_ns"][0] - traced["window_ns"][0]
    phase = ctx["reader"].get("phase")
    cover = trace.merged([
        [to_wall(t0) - offset, to_wall(t1) - offset]
        for name, t0, t1 in slices if phase is None or name == phase
    ])
    hit = trace.subtract(idle, cover) if phase is None else trace.intersect(idle, cover)
    return 100.0 * trace.total(hit) / trace.total(idle)
