GRAPH = {
    "id": "order-process",
    "start": "start",
    "nodes": {
        "start": {"kind": "start"},
        "collect-money": {"kind": "service_task", "job_type": "payment-service"},
        "end": {"kind": "end"},
    },
    "flows": [
        {"id": "flow-start-collect-money-0", "from": "start", "to": "collect-money"},
        {"id": "flow-collect-money-end-1", "from": "collect-money", "to": "end"},
    ],
    "payload_variants": [{}],
}


def build():
    from zeebe_tpu.models.bpmn.builder import Bpmn

    return (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )
