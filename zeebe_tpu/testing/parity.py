"""Log-signature comparison for engine parity checks.

The correctness contract between the device engine and the host oracle is
the committed record stream (SURVEY.md §5 — "the event log IS the trace"):
the same commands must give the same records. ``record_signature`` reduces
a log to the fields that contract covers, so two logs compare with ``==``.
Used by ``tests/test_tpu_parity.py`` and by ``chip_smoke.py`` (which must
not import ``tests/``: its conftest forces the CPU).
"""

from zeebe_tpu.protocol.enums import ValueType

SIG_TYPES = {
    int(ValueType.WORKFLOW_INSTANCE),
    int(ValueType.JOB),
    int(ValueType.INCIDENT),
    int(ValueType.TIMER),
    int(ValueType.MESSAGE),
    int(ValueType.MESSAGE_SUBSCRIPTION),
    int(ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION),
}


def record_signature(records):
    out = []
    for r in records:
        if int(r.metadata.value_type) not in SIG_TYPES:
            continue
        out.append(
            (
                r.position,
                int(r.metadata.record_type),
                int(r.metadata.value_type),
                int(r.metadata.intent),
                r.key,
                r.source_record_position,
                int(r.metadata.rejection_type),
                r.metadata.rejection_reason,
                getattr(r.value, "activity_id", None) or None,
                dict(getattr(r.value, "payload", {}) or {}),
                getattr(r.value, "scope_instance_key", None),
                getattr(r.value, "workflow_instance_key", None),
                getattr(r.value, "retries", None),
                getattr(r.value, "worker", None),
                getattr(r.value, "error_type", None),
                getattr(r.value, "error_message", None),
                getattr(
                    getattr(r.value, "headers", None), "activity_instance_key", None
                ),
            )
        )
    return out
