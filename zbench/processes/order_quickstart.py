"""The quickstart's order process as shipped: three service tasks in
sequence, each with a job type of its own (``order-1p``'s ``order-process``
is the same file cut to the first task)."""
GRAPH = {
    "id": "order-quickstart",
    "start": "start",
    "nodes": {
        "start": {"kind": "start"},
        "collect-money": {"kind": "service_task", "job_type": "payment-service"},
        "fetch-items": {"kind": "service_task", "job_type": "inventory-service"},
        "ship-parcel": {"kind": "service_task", "job_type": "shipment-service"},
        "end": {"kind": "end"},
    },
    "flows": [
        {"id": "flow-start-collect-money-0", "from": "start", "to": "collect-money"},
        {"id": "flow-collect-money-fetch-items-1", "from": "collect-money", "to": "fetch-items"},
        {"id": "flow-fetch-items-ship-parcel-2", "from": "fetch-items", "to": "ship-parcel"},
        {"id": "flow-ship-parcel-end-3", "from": "ship-parcel", "to": "end"},
    ],
    "payload_variants": [{}],
}


def build():
    from zeebe_tpu.models.bpmn.builder import Bpmn

    return (
        Bpmn.create_process("order-quickstart")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .service_task("fetch-items", type="inventory-service")
        .service_task("ship-parcel", type="shipment-service")
        .end_event("end")
        .done()
    )
