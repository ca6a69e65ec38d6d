"""``order-process``'s one service task under the id ``order-doc``, each
create carrying an order document of about 2 KB: the quickstart's
``orderItems`` and the upstream benchmark starter's JSON payload, as
remembered (``zbench/configs/order-doc-1p.json``, ``assumed``). The
documents are plain data made by a fixed formula of the variant's index,
so the generator and the reference read the same ones."""

_TIERS = ("gold", "silver", "bronze", "platinum")
_CITIES = ("München", "Berlin", "Kraków", "São Paulo")
_TAGS = (
    "priority-handling-requested", "gift-wrap-with-message-card",
    "fragile-contents-handle-with-care", "express-next-day-delivery",
    "insured-shipment-signature-required", "business-customer-invoice-net-30",
)
_CREATED_AT = 1_530_000_000_000  # epoch ms, mid 2018


def _f32_exact(value) -> bool:
    import struct

    return struct.unpack("<f", struct.pack("<f", value))[0] == value


def document(k: int) -> dict:
    """Variant ``k``'s seven variables: none of them has a device column
    form (maps, lists, an id at or above 2^40, prices and a total that f32
    does not hold, an epoch-millisecond time) but ``note``, a string."""
    cents = [1999 + 250 * j + 110 * k for j in range(8)]
    qty = [1 + (k + j) % 5 for j in range(8)]
    while _f32_exact(sum(q * c for q, c in zip(qty, cents)) / 100):
        cents[-1] += 10
    lines = [
        {"sku": f"SKU-{k:02d}-{j:05d}", "qty": qty[j], "unitPrice": cents[j] / 100}
        for j in range(8)
    ]
    total = sum(q * c for q, c in zip(qty, cents)) / 100
    created = _CREATED_AT + 86_400_013 * k + 777
    assert not _f32_exact(float(created)) and not _f32_exact(total)
    note = "".join(
        f"line {i}: deliver order variant {k} to the back door, ring twice. "
        for i in range(20)
    )[:1024]
    return {
        "orderItems": [1000 + 3 * k, 1001 + 3 * k, 1002 + 3 * k],
        "customerProfile": {
            "id": (1 << 40) + 7919 * k,
            "name": f"Customer Number {k:02d} Anna-Katharina Müller-Łukasiewicz",
            "email": f"anna-katharina.customer.{k:02d}@webshop.example.com",
            "tier": _TIERS[k % len(_TIERS)],
            "address": {
                "street": f"{100 + k} Long Distribution Center Street, Building C, Gate 4",
                "city": _CITIES[k % len(_CITIES)],
                "zip": f"{10000 + 37 * k}",
                "country": "DE",
            },
        },
        "lines": lines,
        "total": total,
        "createdAt": created,
        "tags": [_TAGS[(k + i) % len(_TAGS)] for i in range(4)],
        "note": note,
    }


GRAPH = {
    "id": "order-doc",
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
    "payload_variants": [document(k) for k in range(16)],
}


def build():
    from zeebe_tpu.models.bpmn.builder import Bpmn

    return (
        Bpmn.create_process("order-doc")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )
