"""The upstream getting-started tutorial's order process: a payment is
initiated by a job, the process waits for the `payment-received` message on
the order's id, then an exclusive gateway on the order's value picks one of
two shipping jobs and a second exclusive gateway joins the two paths before
the end event. The process id is ``order-payment`` because ``order-process``
is ``order-1p``'s (``zbench/processes/order_process.py``)."""
GRAPH = {
    "id": "order-payment",
    "start": "order-placed",
    "nodes": {
        "order-placed": {"kind": "start"},
        "initiate-payment": {"kind": "service_task", "job_type": "initiate-payment"},
        "payment-received": {"kind": "message_catch", "message": "payment-received",
                             "correlation_var": "orderId"},
        "order-value?": {"kind": "exclusive_gateway"},
        "ship-with-insurance": {"kind": "service_task", "job_type": "ship-with-insurance"},
        "ship-without-insurance": {"kind": "service_task",
                                   "job_type": "ship-without-insurance"},
        "order-shipped": {"kind": "exclusive_gateway",
                          "default": "flow-order-shipped-order-fulfilled-6"},
        "order-fulfilled": {"kind": "end"},
    },
    "flows": [
        {"id": "flow-order-placed-initiate-payment-0", "from": "order-placed",
         "to": "initiate-payment"},
        {"id": "flow-initiate-payment-payment-received-1", "from": "initiate-payment",
         "to": "payment-received"},
        {"id": "flow-payment-received-order-value?-2", "from": "payment-received",
         "to": "order-value?"},
        {"id": "flow-order-value?-ship-with-insurance-3", "from": "order-value?",
         "to": "ship-with-insurance", "when": {"var": "orderValue", "op": ">=", "value": 100}},
        {"id": "flow-order-value?-ship-without-insurance-4", "from": "order-value?",
         "to": "ship-without-insurance", "when": {"var": "orderValue", "op": "<", "value": 100}},
        {"id": "flow-ship-with-insurance-order-shipped-5", "from": "ship-with-insurance",
         "to": "order-shipped"},
        {"id": "flow-order-shipped-order-fulfilled-6", "from": "order-shipped",
         "to": "order-fulfilled"},
        {"id": "flow-ship-without-insurance-order-shipped-7", "from": "ship-without-insurance",
         "to": "order-shipped"},
    ],
    "payload_variants": [{}],
}


def build():
    from zeebe_tpu.models.bpmn.builder import Bpmn

    b = (
        Bpmn.create_process("order-payment")
        .start_event("order-placed")
        .service_task("initiate-payment", type="initiate-payment")
        .message_catch_event("payment-received", message_name="payment-received",
                             correlation_key="$.orderId")
        .exclusive_gateway("order-value?")
    )
    insured = b.branch("$.orderValue >= 100").service_task(
        "ship-with-insurance", type="ship-with-insurance"
    )
    uninsured = b.branch("$.orderValue < 100").service_task(
        "ship-without-insurance", type="ship-without-insurance"
    )
    insured.exclusive_gateway("order-shipped").end_event("order-fulfilled")
    uninsured.connect_to("order-shipped")
    return b.done()
