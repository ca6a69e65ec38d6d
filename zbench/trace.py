"""From a profiler trace to numbers: device busy union, a program's time
per launch, the operations that took most time, and the idle gaps labelled
by what the host was doing.

``events_of`` is the one function that reads an ``.xplane.pb`` (with
``jax.profiler.ProfileData``); everything else works on its plain output
``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}``, which is also the form of the small recorded trace
in ``fixtures/`` that ``selfcheck`` reduces."""

from __future__ import annotations

import bisect
import glob
import os
import re

GAP_FLOOR_NS = 50_000  # shorter gaps lie between operations of one program
SYNC_NAME = "zbench_clock_sync"  # a host annotation stamped with the wall clock


def events_of(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes, sync_ns = [], None
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device:
                events = [
                    [e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events
                ]
                lines.append({"name": line.name, "events": events})
            elif sync_ns is None:
                for e in line.events:
                    if e.name == SYNC_NAME:
                        sync_ns = int(e.start_ns)
                        break
        if device:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "sync_ns": sync_ns}


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_op(name: str) -> str:
    """An HLO operation's text cut to what names it: its result, opcode,
    first shapes and custom-call target, without layouts."""
    target = re.search(r'custom_call_target="([^"]+)"', name)
    head = _LAYOUT.sub("", name.split("), ")[0])[:110]
    return head + (f" [{target.group(1)}]" if target else "")


def module_name(name: str) -> str:
    return "program " + name.split("(")[0]


def device_planes(doc: dict) -> list:
    return [p for p in doc["planes"] if p["name"].startswith("/device:")
            and "CUSTOM" not in p["name"].upper()]


def _line(plane: dict, *names: str):
    for want in names:
        for line in plane["lines"]:
            if line["name"] == want:
                return line
    return None


def merged(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def total(intervals: list) -> int:
    return sum(b - a for a, b in intervals)


def clip(intervals: list, lo: int, hi: int) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals if b > lo and a < hi]


def subtract(intervals: list, holes: list) -> list:
    """``intervals`` minus ``holes``; both merged and sorted."""
    out = []
    starts = [h[0] for h in holes]
    for a, b in intervals:
        i = max(0, bisect.bisect_right(starts, a) - 1)
        cur = a
        while i < len(holes) and holes[i][0] < b:
            ha, hb = holes[i]
            if hb > cur:
                if ha > cur:
                    out.append([cur, min(ha, b)])
                cur = max(cur, hb)
            i += 1
        if cur < b:
            out.append([cur, b])
    return out


def intersect(intervals: list, others: list) -> list:
    return subtract(intervals, subtract(intervals, others))


def program_launches(doc: dict, window_ns: tuple | None, match: str) -> list:
    """Device time (ns) of every launch, inside the window, of the
    programs whose name contains ``match`` (the ``XLA Modules`` line)."""
    durs = []
    for plane in device_planes(doc):
        modules = _line(plane, "XLA Modules")
        for name, s, d in (modules["events"] if modules else []):
            if match in name and (window_ns is None or (s >= window_ns[0] and s + d <= window_ns[1])):
                durs.append(d)
    return durs


def reduce(doc: dict, window_ns: tuple | None = None,
           host_intervals: list | None = None) -> dict | None:
    """``host_intervals``: [(label, [[start_ns, end_ns], ...]), ...] in
    priority order, on the trace's clock. Returns None when no operation
    ran on a device."""
    planes = device_planes(doc)
    per_device = []
    for plane in planes:
        ops = _line(plane, "XLA Ops") or _line(plane, "XLA Modules")
        if ops is None or not ops["events"]:
            continue
        per_device.append((plane, ops))
    if not per_device:
        return None
    if window_ns is None:
        lo = min(e[1] for _, ops in per_device for e in ops["events"])
        hi = max(e[1] + e[2] for _, ops in per_device for e in ops["events"])
        window_ns = (lo, hi)
    lo, hi = window_ns
    busy_ns = []
    op_time: dict = {}
    module_time: dict = {}
    gaps_all = []
    for plane, ops in per_device:
        busy = clip(merged([[s, s + d] for _, s, d in ops["events"]]), lo, hi)
        busy_ns.append(total(busy))
        for name, s, d in ops["events"]:
            if s + d > lo and s < hi:
                key = short_op(name)
                op_time[key] = op_time.get(key, 0) + d
        modules = _line(plane, "XLA Modules")
        if modules is not None:
            for name, s, d in modules["events"]:
                if s >= lo and s + d <= hi:
                    key = module_name(name)
                    module_time[key] = module_time.get(key, 0) + d
        gaps_all.append(subtract([[lo, hi]], busy))
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    gaps = gaps_all[0]
    long_gaps = [g for g in gaps if g[1] - g[0] >= GAP_FLOOR_NS]
    labelled = {"within_programs_lt_50us": (total(gaps) - total(long_gaps)) / 1e9}
    rest = long_gaps
    for label, intervals in host_intervals or []:
        cover = merged(intervals)
        hit = intersect(rest, cover)
        if hit:
            labelled[label] = labelled.get(label, 0.0) + total(hit) / 1e9
        rest = subtract(rest, cover)
    if rest:
        labelled["unattributed"] = total(rest) / 1e9
    def top(d: dict, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]

    return {
        "window_ns": [lo, hi],
        "window_s": window_s,
        "busy_s": busy_s,
        "device_ops": top({k: v / 1e9 for k, v in module_time.items()}, 4)
        + top({k: v / 1e9 for k, v in op_time.items()}, 6),
        "idle_gaps": top({k: v for k, v in labelled.items() if v > 0}),
        "longest_gap_s": max((b - a for a, b in gaps), default=0) / 1e9,
    }
