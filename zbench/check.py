"""The comparison that decides ``correct``: every number compared, beside
its limit. Works on plain rows (``normalize`` is the one function that
touches the program's record objects), so the controls can hand it a log
with a fault planted."""

from __future__ import annotations

import collections

from zbench import reference as ref
from zbench import traffic

# ``correlation``: a message's correlation key (its ``element`` is its name)
Row = collections.namedtuple(
    "Row", "position rtype vtype intent key timestamp instance element payload jtype correlation",
    defaults=(None,),
)
MESSAGE, MESSAGE_PUBLISHED = 11, 1

# value types whose records make up an instance's lifecycle: none of them
# may be processed by the device engine's embedded host engine
LIFECYCLE_VALUE_TYPES = (0, 5, 6, 11, 12, 13, 14)


def normalize(record) -> Row:
    md, v = record.metadata, record.value
    vtype = int(md.value_type)
    instance = element = payload = jtype = correlation = None
    if vtype == ref.WORKFLOW_INSTANCE:
        instance, element, payload = v.workflow_instance_key, v.activity_id, dict(v.payload)
    elif vtype == ref.JOB:
        instance, jtype, payload = v.headers.workflow_instance_key, v.type, dict(v.payload)
    elif vtype == MESSAGE:
        element, correlation, payload = v.name, v.correlation_key, dict(v.payload)
    return Row(
        record.position, int(md.record_type), vtype, int(md.intent), record.key,
        record.timestamp, instance, element, payload, jtype, correlation,
    )


def per_instance(rows: list) -> dict:
    """instance key -> its events and jobs, in log order."""
    out: dict = collections.defaultdict(lambda: {"events": [], "jobs": []})
    for r in rows:
        if r.rtype != ref.EVENT or r.instance is None or r.instance < 0:
            continue
        if r.vtype == ref.WORKFLOW_INSTANCE:
            out[r.instance]["events"].append((r.intent, r.element, r.payload, r.timestamp))
        elif r.vtype == ref.JOB:
            out[r.instance]["jobs"].append((r.intent, r.key, r.jtype, r.payload))
    return out


def compare(logs: dict, gen: dict, graphs: dict, host_lifecycle: int,
            oracle_live: int, compiled_in_window: int, children_with_jax: int) -> tuple:
    """``logs``: partition -> rows of the committed log re-read from disk.
    ``gen``: the generator's report. Returns (name -> [value, limit], the
    first mismatching instance with why and its records); the run is
    correct when no value is above its limit. Every comparison is exact, so
    every limit is 0. Where the traffic publishes messages (``gen``'s
    ``messages``), each publish is held to what a create is: answered, and
    its ``PUBLISHED`` event in the committed log."""
    by_partition = {p: per_instance(rows) for p, rows in logs.items()}
    rows = gen["rows"]
    acked = [r for r in rows if "key" in r]
    acked_not_in_log = completed_not_once = jobs_not_once = mismatches = 0
    seen_vs_log = gen.get("unmatched_completions", 0)
    first_mismatch = None
    for r in acked:
        got = by_partition.get(r["partition"], {}).get(r["key"])
        if got is None or not any(e[0] == ref.CREATED for e in got["events"]):
            acked_not_in_log += 1
            continue
        graph = graphs[r["process"]]
        n_done = sum(
            1 for e in got["events"]
            if e[0] == ref.ELEMENT_COMPLETED and e[1] == graph["id"]
        )
        seen = ("done" in r) + r.get("extra_completions", 0)
        if n_done != 1:
            completed_not_once += 1
        if seen != n_done:
            seen_vs_log += 1
        if n_done == 0:
            continue
        jobs = collections.Counter(j[1] for j in got["jobs"] if j[0] == ref.JOB_CREATED)
        done = collections.Counter(j[1] for j in got["jobs"] if j[0] == ref.JOB_COMPLETED)
        if any(c != 1 for c in jobs.values()) or jobs != done:
            jobs_not_once += 1
        want = ref.expected(graph, r["payload"], traffic.worker_result)
        why = ref.instance_mismatch(want, got["events"], got["jobs"])
        if why is not None:
            mismatches += 1
            if first_mismatch is None:
                first_mismatch = {
                    "instance": r["key"], "process": r["process"], "why": why,
                    # (position, record type, value type, intent, key, element)
                    "records": [
                        [x.position, x.rtype, x.vtype, x.intent, x.key, x.element]
                        for x in logs[r["partition"]] if x.instance == r["key"]
                    ][:80],
                }
    out = {
        "create_errors": [sum(1 for r in rows if "error" in r), 0],
        "acked_not_in_log": [acked_not_in_log, 0],
        **publishes(logs, gen, acked),
        "never_completed": [sum(1 for r in acked if "done" not in r), 0],
        "completed_not_once": [completed_not_once, 0],
        "seen_vs_log": [seen_vs_log, 0],
        "jobs_not_once": [jobs_not_once, 0],
        "reference_mismatches": [mismatches, 0],
        "host_lifecycle_records": [host_lifecycle, 0],
        "oracle_live_instances": [oracle_live, 0],
        "compiled_in_window": [compiled_in_window, 0],
        "children_with_jax": [children_with_jax, 0],
    }
    return out, first_mismatch


def publishes(logs: dict, gen: dict, acked: list) -> dict:
    """``publish_errors``: acknowledged creates of a process that waits for
    a message whose publish was refused, timed out or never went out;
    ``acked_publish_not_in_log``: acknowledged publishes whose ``PUBLISHED``
    event (key and correlation key) no partition's committed log holds.
    Nothing where the traffic publishes no message."""
    messages = gen.get("messages")
    if not messages:
        return {}
    published = {
        (r.key, r.correlation) for rows in logs.values() for r in rows
        if r.vtype == MESSAGE and r.rtype == ref.EVENT and r.intent == MESSAGE_PUBLISHED
    }
    sent = [r.get("publish", {}) for r in acked if r["process"] in messages]
    answered = [(p["key"], p["correlation_key"]) for p in sent if "key" in p]
    return {
        "publish_errors": [len(sent) - len(answered), 0],
        "acked_publish_not_in_log": [sum(1 for k in answered if k not in published), 0],
    }


def is_correct(compared: dict) -> bool:
    return all(value <= limit for value, limit in compared.values())
