GRAPH = {
    "id": "route-order",
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
    "payload_variants": [{"orderValue": 250}, {"orderValue": 40}],
}


def build():
    from zeebe_tpu.models.bpmn.builder import Bpmn

    b = Bpmn.create_process("route-order").start_event("start").exclusive_gateway("split")
    b.branch("$.orderValue >= 100").end_event("end-priority")
    b.branch(default=True).end_event("end-normal")
    return b.done()
