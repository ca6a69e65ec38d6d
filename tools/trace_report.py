#!/usr/bin/env python
"""Convert a tracer dump into Chrome-trace/Perfetto JSON.

Input: the JSON document ``RecordTracer.dump`` writes (format
``zeebe-tpu-trace-v1``: record-lifecycle spans, per-wave device
timelines with their host phases, the drains', ticks' and raft group
commits' phases, the measured actors' jobs, and the flight-recorder event
ring).

Output: Chrome trace-event JSON (load in ``chrome://tracing`` or
https://ui.perfetto.dev):

- one track per traced record (``pid="records"``, ``tid=trace-<id>``)
  with an ``X`` slice per stage interval plus instant events at each
  stamp — the per-stage attribution view;
- one row per host track (``pid="host"``: ``actor``, the broker actor's
  jobs and idle stretches, above the ``wave``, ``drain`` and ``tick``
  cycles that run inside them; ``actor:raft`` above ``raft``, the raft
  actor's), sorted above the devices, with an ``X`` slice per phase
  (docs/operations/tracing.md, "Wave phases");
- one track per mesh device (``pid="devices"``) with an ``X`` slice per
  wave segment (dispatch → collect), labeled with fill and the
  host/device time split;
- flight-recorder events as instants on ``pid="flight"`` per category.

Usage:
    python tools/trace_report.py DUMP.json [-o OUT.json]
    python tools/trace_report.py --selftest
"""

from __future__ import annotations

import argparse
import json
import sys


def span_events(span: dict) -> list:
    tid = f"trace-{span.get('trace_id', 0)}"
    out = []
    stages = span.get("stages", [])
    for i, stage in enumerate(stages):
        ts = int(stage["t_us"])
        args = {
            k: v for k, v in stage.items() if k not in ("stage", "t_us")
        }
        args.update(
            partition=span.get("partition"), position=span.get("position")
        )
        out.append({
            "name": stage["stage"], "cat": "record", "ph": "i", "s": "t",
            "ts": ts, "pid": "records", "tid": tid, "args": args,
        })
        if i + 1 < len(stages):
            dur = max(0, int(stages[i + 1]["t_us"]) - ts)
            out.append({
                "name": f"{stage['stage']}→{stages[i + 1]['stage']}",
                "cat": "record", "ph": "X", "ts": ts, "dur": dur,
                "pid": "records", "tid": tid, "args": args,
            })
    return out


def wave_events(wave: dict) -> list:
    out = []
    for seg in wave.get("segments", []):
        t0 = int(seg["t_dispatch_us"])
        t1 = int(seg.get("t_collect_us", -1))
        if t1 < t0:
            t1 = int(wave.get("t_collect_us", t0))
        device = seg.get("device", -1)
        tid = f"device-{device}" if device >= 0 else "host"
        out.append({
            "name": (
                f"wave {wave.get('wave_id')} p{seg.get('partition')} "
                f"({seg.get('records')} rec)"
            ),
            "cat": "wave", "ph": "X", "ts": t0, "dur": max(0, t1 - t0),
            "pid": "devices", "tid": tid,
            "args": {
                "wave_id": wave.get("wave_id"),
                "partition": seg.get("partition"),
                "records": seg.get("records"),
                "host_s": seg.get("host_s"),
                "device_s": seg.get("device_s"),
                "wave_records": wave.get("records"),
                "wave_capacity": wave.get("capacity"),
            },
        })
    return out


# the host rows from the top: an actor's jobs above the cycles inside them
HOST_ROWS = ("actor", "wave", "drain", "tick", "actor:raft", "raft")


def phase_events(cycle: dict) -> list:
    """One slice per host phase of a wave (``wave_id``), of a drain, tick
    or raft group commit, or of an actor's job (``track``, ``cycle_id``);
    each measured role's jobs on a row of their own."""
    track = cycle.get("track", "wave")
    if track == "actor" and cycle.get("role", "broker") != "broker":
        track = f"actor:{cycle['role']}"
    ident = {
        k: cycle[k] for k in ("wave_id", "cycle_id", "partition", "role", "kind")
        if k in cycle
    }
    return [
        {
            "name": name, "cat": "phase", "ph": "X", "ts": int(t0),
            "dur": max(0, int(t1) - int(t0)), "pid": "host", "tid": track,
            "args": ident,
        }
        for name, t0, t1 in cycle.get("phases", [])
    ]


def flight_events(events: list, span_t0_wall=None) -> list:
    if not events:
        return []
    # flight timestamps are wall-clock seconds. When the dump carries the
    # wall-clock instant of the span timebase's zero, align the flight
    # track onto the span/wave timeline (both clocks derive from
    # perf_counter, so the offset is a constant); otherwise fall back to
    # rebasing on the ring's first event.
    t0 = (
        float(span_t0_wall) if span_t0_wall is not None
        else min(e.get("t", 0) for e in events)
    )
    out = []
    for e in events:
        out.append({
            "name": e.get("msg", ""), "cat": e.get("cat", "flight"),
            "ph": "i", "s": "g",
            "ts": int((e.get("t", t0) - t0) * 1_000_000),
            "pid": "flight", "tid": e.get("cat", "flight"),
            "args": e.get("fields") or {},
        })
    return out


def convert(doc: dict) -> dict:
    if doc.get("format") != "zeebe-tpu-trace-v1":
        raise ValueError(
            f"unsupported input format {doc.get('format')!r} "
            "(expected zeebe-tpu-trace-v1)"
        )
    events = []
    for span in doc.get("spans", []):
        events.extend(span_events(span))
    for wave in doc.get("waves", []):
        events.extend(wave_events(wave))
        events.extend(phase_events(wave))
    for cycle in doc.get("cycles", []):
        events.extend(phase_events(cycle))
    # the host rows sit above the devices they keep waiting
    events.extend(
        {"name": "process_sort_index", "ph": "M", "pid": pid,
         "args": {"sort_index": i}}
        for i, pid in enumerate(("host", "devices"))
    )
    events.extend(
        {"name": "thread_sort_index", "ph": "M", "pid": "host", "tid": row,
         "args": {"sort_index": i}}
        for i, row in enumerate(HOST_ROWS)
    )
    events.extend(
        flight_events(doc.get("events", []), doc.get("span_t0_wall"))
    )
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"source": "zeebe-tpu trace_report", **doc.get("stats", {})},
    }


def selftest() -> int:
    """Round-trip a synthetic dump: convert → serialize → parse → sanity
    checks (the ci smoke's validity gate)."""
    doc = {
        "format": "zeebe-tpu-trace-v1",
        "span_t0_wall": 99.9999,
        "stats": {"sampled": 1},
        "spans": [{
            "trace_id": 0, "partition": 0, "position": 7, "request_id": 3,
            "stages": [
                {"stage": "gateway_recv", "t_us": 10},
                {"stage": "commit", "t_us": 30},
                {"stage": "apply", "t_us": 40, "device": 0},
            ],
        }],
        "waves": [{
            "wave_id": 0, "t_dispatch_us": 20, "t_collect_us": 45,
            "capacity": 512, "records": 3,
            "segments": [{
                "partition": 0, "device": 0, "records": 3,
                "t_dispatch_us": 20, "t_collect_us": 44,
                "host_s": 0.001, "device_s": 0.002,
            }],
            "phases": [["pack", 18, 20], ["route", 20, 22], ["stage", 22, 30]],
        }],
        "cycles": [{
            "track": "raft", "cycle_id": 0, "partition": 0,
            "phases": [["log_append", 24, 27], ["fsync", 27, 29]],
        }, {
            "track": "actor", "cycle_id": 1, "role": "broker", "kind": "drain",
            "phases": [["actor_idle", 12, 17], ["job:drain", 17, 46]],
        }],
        "events": [
            {"seq": 0, "t": 100.0, "cat": "raft", "msg": "state -> leader"},
        ],
    }
    out = json.loads(json.dumps(convert(doc)))
    events = out["traceEvents"]
    assert any(e["ph"] == "X" and e["pid"] == "records" for e in events)
    assert any(e["ph"] == "X" and e["pid"] == "devices" for e in events)
    host = [e for e in events if e["pid"] == "host" and e["ph"] == "X"]
    assert [(e["tid"], e["name"]) for e in host] == [
        ("wave", "pack"), ("wave", "route"), ("wave", "stage"),
        ("raft", "log_append"), ("raft", "fsync"),
        ("actor", "actor_idle"), ("actor", "job:drain"),
    ]
    flight = [e for e in events if e["pid"] == "flight"]
    assert flight
    # flight events align onto the span timebase via span_t0_wall
    assert flight[0]["ts"] == int((100.0 - 99.9999) * 1_000_000)
    names = {e["name"] for e in events}
    assert "gateway_recv" in names and "commit" in names
    durs = [e["dur"] for e in events if e["ph"] == "X"]
    assert all(d >= 0 for d in durs)
    print("trace_report selftest OK "
          f"({len(events)} events, {len(durs)} slices)")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("dump", nargs="?", help="tracer dump JSON file")
    parser.add_argument("-o", "--out", default=None,
                        help="output path (default: <dump>.chrome.json)")
    parser.add_argument("--selftest", action="store_true",
                        help="synthetic round-trip check, no input needed")
    args = parser.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.dump:
        parser.error("dump file required (or --selftest)")
    with open(args.dump) as f:
        doc = json.load(f)
    trace = convert(doc)
    out_path = args.out or (args.dump + ".chrome.json")
    with open(out_path, "w") as f:
        json.dump(trace, f)
    print(
        f"wrote {out_path}: {len(trace['traceEvents'])} events from "
        f"{len(doc.get('spans', []))} spans / {len(doc.get('waves', []))} "
        f"waves / {len(doc.get('events', []))} flight events"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
