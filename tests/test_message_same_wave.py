"""A message PUBLISH and a subscription OPEN on one (message name,
correlation value) composite in ONE wave, in both log orders, beside a
publish on another key: the device engine must correlate them as the host
engine does. Each command of the message family reads the device's message
tables as they stood before its step, so in one device segment neither
would see the other (``TpuPartitionEngine._route_wave`` starts a new
segment at the second). The waves are driven one at a time so that the two
commands meet in the same one; the logs are compared with the parity
harness of ``tests/test_tpu_parity.py``."""

import pytest

from tests.test_tpu_parity import DualRig
from zeebe_tpu.gateway import ZeebeClient
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.protocol.enums import ValueType
from zeebe_tpu.protocol.intents import MessageIntent
from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI
from zeebe_tpu.protocol.records import MessageRecord, WorkflowInstanceRecord

MSUB = int(ValueType.MESSAGE_SUBSCRIPTION)


def catch_process():
    return (
        Bpmn.create_process("pay")
        .start_event("start")
        .message_catch_event("payment-received", message_name="payment-received",
                             correlation_key="$.orderId")
        .end_event("end")
        .done()
    )


def _step(broker, before_apply=None) -> list:
    """One wave of partition 0's committed tail: processed, then (after
    ``before_apply``) its results applied in log order."""
    partition = broker.partitions[0]
    records = partition.log.reader(partition.next_read_position).read_committed()
    results = partition.engine.process_wave(records) if records else []
    if before_apply is not None:
        before_apply(results)
    for record, result in zip(records, results):
        broker._apply_result(partition, record, result)
    return records


def _publish(broker, key: str) -> None:
    broker.write_command(0, MessageRecord(
        name="payment-received", correlation_key=key, time_to_live=60_000,
        payload={"paid": True, "key": key},
    ), MessageIntent.PUBLISH)


def _scenario(broker, publish_first: bool) -> tuple:
    """Deploy, create an order waiting for its payment, and publish the
    payment (and one for another order) so that it meets the order's OPEN
    in one wave: before it in the log, or after it. Returns that wave and
    the device segments the wave's second command on the composite cut
    (None on the host engine)."""
    ZeebeClient(broker).deploy_model(catch_process())
    broker.write_command(
        0, WorkflowInstanceRecord(bpmn_process_id="pay", payload={"orderId": 7}), WI.CREATE
    )
    for _ in range(20):
        if publish_first:
            # the PUBLISH lands while the wave that sends the OPEN is being
            # applied, so it precedes the OPEN in the log
            def publish_before_open(results):
                if any(int(send.metadata.value_type) == MSUB
                       for result in results for _, send in result.sends):
                    _publish(broker, "7")
                    _publish(broker, "8")

            _step(broker, publish_before_open)
        else:
            partition = broker.partitions[0]
            tail = partition.log.reader(partition.next_read_position).read_committed()
            if any(int(r.metadata.value_type) == MSUB for r in tail):
                _publish(broker, "7")
                _publish(broker, "8")
                break
            _step(broker)
        partition = broker.partitions[0]
        tail = partition.log.reader(partition.next_read_position).read_committed()
        kinds = [int(r.metadata.value_type) for r in tail]
        if publish_first and int(ValueType.MESSAGE) in kinds and MSUB in kinds:
            break
    wave = _step(broker)
    phases = getattr(broker.partitions[0].engine, "last_wave_phases", None)
    cuts = phases.counts.get("message_cuts", 0) if phases is not None else None
    broker.run_until_idle()
    return wave, cuts


@pytest.mark.parametrize("publish_first", [False, True], ids=["open_first", "publish_first"])
def test_publish_and_open_in_one_wave_correlate(publish_first):
    rig = DualRig()
    try:
        (host_wave, _), (device_wave, cuts) = [
            _scenario(broker, publish_first) for broker in rig.brokers
        ]
        for wave in (host_wave, device_wave):
            kinds = [(int(r.metadata.value_type), int(r.metadata.intent)) for r in wave]
            opened = kinds.index((MSUB, 0))
            published = [i for i, k in enumerate(kinds) if k == (int(ValueType.MESSAGE), 0)]
            assert len(published) == 2, kinds
            # the two commands on one composite are in the wave, in the order asked
            assert (published[0] < opened) is publish_first, kinds
        rig.assert_parity()
        # the order completed: its message correlated exactly once
        records = rig.brokers[1].records(0)
        completed = [
            r for r in records
            if int(r.metadata.value_type) == int(ValueType.WORKFLOW_INSTANCE)
            and int(r.metadata.intent) == int(WI.ELEMENT_COMPLETED)
            and r.value.activity_id == "pay"
        ]
        assert len(completed) == 1
        assert completed[0].value.payload == {"orderId": 7, "paid": True, "key": "7"}
        # the second command on the composite began a segment of its own
        assert cuts == 1
    finally:
        rig.close()
