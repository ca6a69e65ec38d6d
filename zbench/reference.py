"""The plain reference: what one workflow instance's records have to be.

A straightforward token walk over a process ``GRAPH`` (plain data under
``zbench/processes/``). It shares no code with the program: not the
interpreter, not the kernel, not the model classes. Its inputs are what
the generator sent (the create payload) and what the traffic answered
(``traffic.Inputs``: the worker's result, the published message), both
defined in ``zbench/traffic.py``. The numbers below are the wire
protocol's (reference ``protocol.xml``).

Four element kinds are walked here: ``start``, ``exclusive_gateway``,
``service_task`` and ``end``. Any other kind is a file found by its name,
``zbench/rules/<kind>.py``, whose ``walk(node_id, node, payload, inputs)``
returns the element's events, the jobs it adds, the payload after it and
the node whose outgoing flow the token takes next.
"""

from __future__ import annotations

import importlib
import re

from zbench import traffic

# value types
JOB, WORKFLOW_INSTANCE = 0, 5
# record types
EVENT, COMMAND = 0, 1
# workflow instance intents
CREATED, START_EVENT_OCCURRED, END_EVENT_OCCURRED = 1, 2, 3
SEQUENCE_FLOW_TAKEN, GATEWAY_ACTIVATED = 4, 5
ELEMENT_READY, ELEMENT_ACTIVATED, ELEMENT_COMPLETING, ELEMENT_COMPLETED = 6, 7, 8, 9
# job intents
JOB_CREATED, JOB_ACTIVATED, JOB_COMPLETED = 1, 3, 5

_OPS = {
    ">=": lambda a, b: a >= b, ">": lambda a, b: a > b, "<=": lambda a, b: a <= b,
    "<": lambda a, b: a < b, "==": lambda a, b: a == b, "!=": lambda a, b: a != b,
}


def _take(graph: dict, node_id: str, payload: dict) -> dict:
    """The sequence flow a token leaves ``node_id`` by."""
    out = [f for f in graph["flows"] if f["from"] == node_id]
    node = graph["nodes"][node_id]
    if node["kind"] != "exclusive_gateway":
        (flow,) = out
        return flow
    for flow in out:
        when = flow.get("when")
        if when and when["var"] in payload and _OPS[when["op"]](
            payload[when["var"]], when["value"]
        ):
            return flow
    return next(f for f in out if f["id"] == node["default"])


_KIND = re.compile(r"^[a-z_][a-z0-9_]*$")


def rule(kind: str):
    """The rule file of an element kind: ``zbench.rules.<kind>``."""
    name = f"zbench.rules.{kind}"
    if _KIND.match(kind):
        try:
            return importlib.import_module(name)
        except ModuleNotFoundError as e:
            if e.name != name:
                raise
    raise ValueError(f"the reference has no rule for element kind {kind!r}")


def expected(graph: dict, create_payload: dict, worker_result) -> dict:
    """The instance's workflow-instance events as (intent, element id,
    payload) in order, and its jobs as (type, payload at creation, result).
    A rule file gets the traffic's inputs: ``worker_result``, the traffic's
    ``message_for``, and this instance's process id and create payload."""
    inputs = traffic.Inputs(worker_result, traffic.message_for, graph["id"], dict(create_payload))
    gid = graph["id"]
    p = dict(create_payload)
    events = [
        (CREATED, gid, dict(p)), (ELEMENT_READY, gid, dict(p)),
        (ELEMENT_ACTIVATED, gid, dict(p)),
        (START_EVENT_OCCURRED, graph["start"], dict(p)),
    ]
    jobs = []
    node_id = graph["start"]
    for _ in range(1000):
        flow = _take(graph, node_id, p)
        events.append((SEQUENCE_FLOW_TAKEN, flow["id"], dict(p)))
        node_id = flow["to"]
        node = graph["nodes"][node_id]
        kind = node["kind"]
        while kind == "exclusive_gateway":
            events.append((GATEWAY_ACTIVATED, node_id, dict(p)))
            flow = _take(graph, node_id, p)
            events.append((SEQUENCE_FLOW_TAKEN, flow["id"], dict(p)))
            node_id = flow["to"]
            node = graph["nodes"][node_id]
            kind = node["kind"]
        if kind == "end":
            events += [
                (END_EVENT_OCCURRED, node_id, dict(p)),
                (ELEMENT_COMPLETING, gid, dict(p)), (ELEMENT_COMPLETED, gid, dict(p)),
            ]
            return {"events": events, "jobs": jobs}
        if kind != "service_task":
            added, new_jobs, p, node_id = rule(kind).walk(node_id, node, dict(p), inputs)
            events += added
            jobs += new_jobs
            continue
        events += [(ELEMENT_READY, node_id, dict(p)), (ELEMENT_ACTIVATED, node_id, dict(p))]
        brought = worker_result(p)
        jobs.append((node["job_type"], dict(p), brought))
        events.append((ELEMENT_COMPLETING, node_id, dict(brought)))
        p = {**p, **brought}
        events.append((ELEMENT_COMPLETED, node_id, dict(p)))
    raise ValueError("the token did not reach an end event")


def instance_mismatch(want: dict, got_events: list, got_jobs: list):
    """None when the instance's records say what the reference says, else
    a short description of the first difference. ``got_events`` are
    (intent, element, payload, record timestamp) in log order; ``got_jobs``
    are (intent, job key, type, payload)."""
    if len(got_events) != len(want["events"]):
        return f"{len(got_events)} events, the reference has {len(want['events'])}"
    for i, (w, g) in enumerate(zip(want["events"], got_events)):
        if tuple(w[:2]) != tuple(g[:2]) or w[2] != g[2]:
            return f"event {i}: got {g[:3]}, the reference has {w}"
    created = [j for j in got_jobs if j[0] == JOB_CREATED]
    completed = [j for j in got_jobs if j[0] == JOB_COMPLETED]
    if len(created) != len(want["jobs"]) or len(completed) != len(want["jobs"]):
        return (f"jobs created {len(created)}, completed {len(completed)}, "
                f"the reference has {len(want['jobs'])}")
    for (jtype, at_creation, result), c, d in zip(want["jobs"], created, completed):
        if c[2] != jtype or c[3] != at_creation:
            return f"job created as {c[2:]}, the reference has {(jtype, at_creation)}"
        if d[1] != c[1] or d[3] != result:
            return f"job completed as {d[1:]}, the reference has {(c[1], result)}"
    return None
