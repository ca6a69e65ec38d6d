"""The device's message store at its capacity. A deployment keeps every
published message with a time to live until the time runs out (one hour by
the client's default), so the store holds about rate x TTL messages; a 2^20
engine gives it 2^18 rows (``state.init_state``: a quarter of the
capacity). Here at the smallest size: every acknowledged publish is in the
log and stored, and the publish that finds the store full stops the
partition with the overflow error; it is never acknowledged, and no stored
message is dropped to make room."""

import pytest

from zeebe_tpu.gateway import ZeebeClient
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import MessageIntent
from zeebe_tpu.protocol.records import MessageRecord
from zeebe_tpu.tpu import state as state_mod

CAPACITY = 1024  # the engine's rows; the message store gets 256 of them


def _broker():
    from zeebe_tpu.engine.interpreter import WorkflowRepository
    from zeebe_tpu.runtime import Broker, ControlledClock
    from zeebe_tpu.tpu import TpuPartitionEngine

    clock = ControlledClock(start_ms=1_000_000)
    repo = WorkflowRepository()
    return Broker(
        num_partitions=1, clock=clock,
        engine_factory=lambda pid: TpuPartitionEngine(
            pid, 1, repository=repo, clock=clock, capacity=CAPACITY
        ),
    )


def _publish(broker, key: str) -> int:
    return broker.write_command(0, MessageRecord(
        name="payment-received", correlation_key=key, time_to_live=3_600_000,
        payload={"paid": True},
    ), MessageIntent.PUBLISH)


def test_a_publish_into_a_full_store_raises_and_drops_nothing():
    broker = _broker()
    try:
        ZeebeClient(broker).deploy_model(
            Bpmn.create_process("pay").start_event("start")
            .message_catch_event("payment-received", message_name="payment-received",
                                 correlation_key="$.orderId")
            .end_event("end").done()
        )
        engine = broker.partitions[0].engine
        rows = engine.state.msg_key.shape[0]
        assert rows == CAPACITY // 4
        requests = [_publish(broker, str(i)) for i in range(rows)]
        broker.run_until_idle()
        acked = [broker.take_response(r) for r in requests]
        assert all(
            r is not None and int(r.metadata.record_type) == int(RecordType.EVENT)
            for r in acked
        )
        published = {
            r.key for r in broker.records(0)
            if int(r.metadata.value_type) == int(ValueType.MESSAGE)
            and int(r.metadata.record_type) == int(RecordType.EVENT)
            and int(r.metadata.intent) == int(MessageIntent.PUBLISHED)
        }
        # every acknowledged publish is in the log, and in the store
        assert {r.key for r in acked} == published and len(published) == rows
        stored = state_mod.host_i64(engine.state.msg_key, 0)
        assert int((stored >= 0).sum()) == rows
        last = _publish(broker, str(rows))
        with pytest.raises(RuntimeError, match="device table overflow"):
            broker.run_until_idle()
        assert broker.take_response(last) is None
    finally:
        broker.close()
