"""``route-order``'s exclusive gateway under the id ``route-doc``: the
condition reads ``orderValue``, one scalar column beside the order document
of ``order_doc.py``. The variants alternate ``orderValue`` 250 and 40, so
that both branches are taken (and warmed up)."""

from zbench.processes.order_doc import document

GRAPH = {
    "id": "route-doc",
    "start": "start",
    "nodes": {
        "start": {"kind": "start"},
        "split": {"kind": "exclusive_gateway", "default": "flow-split-end-normal-2"},
        "end-priority": {"kind": "end"},
        "end-normal": {"kind": "end"},
    },
    "flows": [
        {"id": "flow-start-split-0", "from": "start", "to": "split"},
        {"id": "flow-split-end-priority-1", "from": "split", "to": "end-priority",
         "when": {"var": "orderValue", "op": ">=", "value": 100}},
        {"id": "flow-split-end-normal-2", "from": "split", "to": "end-normal"},
    ],
    "payload_variants": [
        {**document(k), "orderValue": 250 if k % 2 == 0 else 40} for k in range(16)
    ],
}


def build():
    from zeebe_tpu.models.bpmn.builder import Bpmn

    b = Bpmn.create_process("route-doc").start_event("start").exclusive_gateway("split")
    b.branch("$.orderValue >= 100").end_event("end-priority")
    b.branch(default=True).end_event("end-normal")
    return b.done()
