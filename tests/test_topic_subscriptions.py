"""Topic subscription tests: durable server-push of a partition's records.

Reference parity: ``broker-core/.../event/processor/
TopicSubscriptionManagementProcessor`` (SUBSCRIBE/SUBSCRIBED lifecycle),
``TopicSubscriptionPushProcessor:36`` (per-subscriber push with credit flow
control), and ack records persisting consumer progress in the log
(``TopicSubscriberState``). Tests mirror broker-core's
TopicSubscriptionTest: open, receive all records, ack, reopen resumes,
force-start rewinds.
"""

import tempfile

import pytest

from zeebe_tpu.gateway import JobWorker, TopicSubscriber, ZeebeClient
from zeebe_tpu.protocol import msgpack
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.protocol.enums import ValueType
from zeebe_tpu.protocol.intents import WorkflowInstanceIntent
from zeebe_tpu.runtime import Broker, ControlledClock


def order_process():
    return (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )


@pytest.fixture
def broker(tmp_path):
    b = Broker(num_partitions=1, data_dir=str(tmp_path / "data"),
               clock=ControlledClock())
    yield b
    b.close()


class TestTopicSubscription:
    def test_receives_all_records_of_the_partition(self, broker):
        client = ZeebeClient(broker)
        sub = TopicSubscriber(broker, "all-records")
        client.deploy_model(order_process())
        JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
        client.create_instance("order-process", {"orderId": 1})
        broker.run_until_idle()

        value_types = {r.metadata.value_type for r in sub.records}
        assert ValueType.DEPLOYMENT in value_types
        assert ValueType.WORKFLOW_INSTANCE in value_types
        assert ValueType.JOB in value_types
        # matches the log (minus subscription-admin records)
        log_records = [
            r for r in broker.records(0)
            if r.metadata.value_type not in (ValueType.SUBSCRIBER, ValueType.SUBSCRIPTION)
        ]
        assert [r.position for r in sub.records] == [r.position for r in log_records]
        sub.close()

    def test_credit_flow_control_pauses_delivery(self, broker):
        client = ZeebeClient(broker)
        received = []
        # no auto-ack: delivery must stall at the credit limit
        handle = broker.open_topic_subscription(
            "limited", lambda pid, r: received.append(r), credits=4
        )
        client.deploy_model(order_process())
        client.create_instance("order-process")
        broker.run_until_idle()
        assert len(received) == 4, "delivery must stop at the credit limit"
        # acking frees credits and delivery resumes
        handle.ack(received[-1].position)
        broker.run_until_idle()
        assert len(received) > 4
        handle.close()

    def test_reopen_resumes_after_last_ack(self, tmp_path):
        clock = ControlledClock()
        data = str(tmp_path / "data")
        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        client = ZeebeClient(broker)
        client.deploy_model(order_process())
        client.create_instance("order-process")
        sub = TopicSubscriber(broker, "resume-me", ack_batch=1)
        broker.run_until_idle()
        seen = len(sub.records)
        assert seen > 0
        last = sub.records[-1].position
        sub.ack_all()
        broker.run_until_idle()
        sub.close()

        # restart the broker: the ack survives in the log; a reopened
        # subscription with the same name resumes AFTER the acked position
        broker.close()
        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        client = ZeebeClient(broker)
        sub2 = TopicSubscriber(broker, "resume-me")
        client.create_instance("order-process")
        broker.run_until_idle()
        assert sub2.records, "new records must still arrive"
        assert all(r.position > last for r in sub2.records), (
            "resumed subscription must not re-deliver acked records"
        )
        sub2.close()
        broker.close()

    def test_force_start_rewinds_to_the_beginning(self, broker):
        client = ZeebeClient(broker)
        client.deploy_model(order_process())
        sub = TopicSubscriber(broker, "rewind", ack_batch=1)
        broker.run_until_idle()
        sub.ack_all()
        broker.run_until_idle()
        sub.close()

        sub2 = TopicSubscriber(broker, "rewind", force_start=True)
        broker.run_until_idle()
        assert sub2.records and sub2.records[0].position == 0
        sub2.close()

    def test_start_position_skips_history(self, broker):
        client = ZeebeClient(broker)
        client.deploy_model(order_process())
        broker.run_until_idle()
        cut = broker.partitions[0].log.next_position
        sub = TopicSubscriber(broker, "tail-only", start_position=cut)
        client.create_instance("order-process")
        broker.run_until_idle()
        assert sub.records
        assert all(r.position >= cut for r in sub.records)
        intents = [
            WorkflowInstanceIntent(r.metadata.intent)
            for r in sub.records
            if r.metadata.value_type == ValueType.WORKFLOW_INSTANCE
        ]
        assert WorkflowInstanceIntent.CREATED in intents
        sub.close()


# -- the topic push over the wire: frames from the log's append blocks -------

ROUTE = Bpmn.create_process("route").start_event("start").end_event("end").done()


def _device_broker(data_dir: str):
    """One ClusterBroker on the device engine (small tables), leading
    partition 0."""
    import time

    from zeebe_tpu.runtime.cluster_broker import ClusterBroker
    from zeebe_tpu.runtime.config import BrokerCfg
    from zeebe_tpu.runtime.engines import engine_factory_from_config

    cfg = BrokerCfg()
    cfg.network.client_port = 0
    cfg.network.management_port = 0
    cfg.network.subscription_port = 0
    cfg.metrics.enabled = False
    cfg.engine.type = "tpu"
    cfg.engine.capacity = 1024
    broker = ClusterBroker(
        cfg, data_dir, engine_factory=engine_factory_from_config(cfg)
    )
    broker.open_partition(0).join(120)
    broker.bootstrap_partition(0, {})
    deadline = time.time() + 120
    while time.time() < deadline and not broker.partitions[0].is_leader:
        time.sleep(0.01)
    assert broker.partitions[0].is_leader
    return broker


def _on_actor(broker, fn):
    import threading

    done = threading.Event()
    out = []
    broker.actor_control.run(lambda: (out.append(fn()), done.set()))
    assert done.wait(120), "the broker actor is stuck"
    return out[0]


def _push_counts() -> dict:
    from zeebe_tpu.runtime.metrics import event_count

    return {
        name: event_count(name)
        for name in (
            "serving_topic_push_frames_total",
            "serving_topic_push_encoded_total",
            "log_tail_frozen_total",
            "serving_waves_total",
        )
    }


def _disk_frames(log) -> dict:
    """position -> the frame bytes storage holds (``_iter_disk_frames``)."""
    import struct

    from zeebe_tpu.log.logstream import _iter_disk_frames

    out = {}
    for record, address in _iter_disk_frames(log, 0):
        (length,) = struct.unpack("<i", log.storage.read(address, 4))
        out[record.position] = log.storage.read(address, length)
    return out


def _capturing_client(broker):
    """A ClusterClient that keeps every topic push it receives, raw."""
    from zeebe_tpu.gateway.cluster_client import ClusterClient

    class Client(ClusterClient):
        payloads = []

        def _on_push(self, payload):
            self.payloads.append(bytes(payload))
            super()._on_push(payload)

    return Client(
        [broker.client_address], num_partitions=1, request_timeout_ms=120_000
    )


def _subscribe(client, name):
    return client.open_topic_subscription(
        name, lambda _pid, _rec: None, credits=8192, ack_batch=4096
    )


def _pushed(client) -> list:
    """``(position, frame)`` of every topic push ``client`` received, each
    message checked to be the map the broker always sent."""
    import struct

    out = []
    for payload in client.payloads:
        msg = msgpack.unpack(payload)
        assert list(msg) == ["t", "partition", "subscriber_key", "epoch", "frame"]
        assert msgpack.pack(msg) == payload
        frame = bytes(msg["frame"])
        out.append((struct.unpack_from("<q", frame, 8)[0], frame))
    return out


def _settle(broker) -> None:
    import time

    server = broker.partitions[0]

    def settled():
        return (
            server.next_read_position > server.log.commit_position
            and all(
                p["cursor"] > server.log.commit_position
                for p in server.topic_pushers.values()
            )
            and not broker._drain_scheduled
        )

    deadline = time.time() + 120
    while time.time() < deadline:
        if _on_actor(broker, settled):
            time.sleep(0.2)
            if _on_actor(broker, settled):
                return
        time.sleep(0.02)
    raise AssertionError("the served run did not settle")


@pytest.fixture(scope="module")
def pushed(tmp_path_factory):
    """20 instances of a straight-through process served by the device
    engine with a wire subscription open: what the pusher sent, what the
    pump and the orchestration check materialized, and which emission
    batches were alive after each pump."""
    import gc
    import weakref

    from zeebe_tpu.protocol import columnar

    data_dir = str(tmp_path_factory.mktemp("pushed"))
    broker = _device_broker(data_dir)
    batches = []
    init = columnar.ColumnarBatch.__init__

    def tracked(self, *args, **kwargs):
        init(self, *args, **kwargs)
        batches.append(weakref.ref(self))

    def emissions_alive() -> int:
        return sum(
            1 for ref in batches
            if ref() is not None and ref().device_source is not None
        )

    server = broker.partitions[0]
    pump, orchestrate = server.pump_topic_subscriptions, server._orchestrate_topics
    materialized = {"pump": 0.0, "orchestrate": 0.0}
    alive_after_pump = []

    def counted(name, fn):
        def run(*args, **kwargs):
            before = columnar.rows_materialized_total()
            try:
                return fn(*args, **kwargs)
            finally:
                materialized[name] += columnar.rows_materialized_total() - before
                if name == "pump":
                    alive_after_pump.append(emissions_alive())

        return run

    mp = pytest.MonkeyPatch()
    mp.setattr(columnar.ColumnarBatch, "__init__", tracked)
    try:
        client = _capturing_client(broker)
        try:
            before = _push_counts()
            sub = _subscribe(client, "frames")
            server.pump_topic_subscriptions = counted("pump", pump)
            server._orchestrate_topics = counted("orchestrate", orchestrate)
            client.deploy_model(ROUTE)
            for i in range(20):
                client.create_instance("route", {"orderId": i})
            _settle(broker)
            after = _push_counts()
            gc.collect()
            alive_at_end = emissions_alive()
            sub.close()
            sent = _pushed(client)
        finally:
            client.close()
        disk = _disk_frames(server.log)
        log = server.log
        frozen = [
            p for p, e in enumerate(log._records, log.base_position)
            if type(e) is tuple and isinstance(e[0], columnar.FrameBlock)
        ]
        frozen = {p: log.record_at(p) for p in frozen}
    finally:
        mp.undo()
        broker.close()
    yield {
        "data_dir": data_dir,
        "sent": sent,
        "disk": disk,
        "frozen": frozen,
        "delta": {k: after[k] - before[k] for k in after},
        "materialized": materialized,
        "alive_after_pump": alive_after_pump,
        "alive_at_end": alive_at_end,
    }


class TestPushedFrames:
    def test_frames_are_the_frames_on_disk(self, pushed):
        from zeebe_tpu.protocol import codec
        from zeebe_tpu.protocol.enums import RecordType

        sent, disk = pushed["sent"], pushed["disk"]
        assert sent
        for position, frame in sent:
            assert frame == disk[position], position
        kinds = {
            (codec.decode_record(frame)[0].metadata.record_type,
             codec.decode_record(frame)[0].metadata.value_type)
            for _p, frame in sent
        }
        # list-path client commands and the device engine's emissions
        assert (RecordType.COMMAND, ValueType.WORKFLOW_INSTANCE) in kinds
        assert (RecordType.COMMAND, ValueType.DEPLOYMENT) in kinds
        assert (RecordType.EVENT, ValueType.WORKFLOW_INSTANCE) in kinds
        # every record but the subscription's admin records, each once,
        # in log order
        expected = [
            p for p, frame in sorted(disk.items())
            if frame[69] not in (ValueType.SUBSCRIBER, ValueType.SUBSCRIPTION)
        ]
        assert [p for p, _f in sent] == expected

    def test_every_frame_came_from_a_block(self, pushed):
        delta = pushed["delta"]
        assert delta["serving_topic_push_frames_total"] >= len(pushed["sent"])
        assert delta["serving_topic_push_encoded_total"] == 0
        assert delta["log_tail_frozen_total"] > 0

    def test_pump_and_orchestration_check_build_no_row(self, pushed):
        assert pushed["delta"]["serving_waves_total"] >= 50
        assert pushed["materialized"] == {"pump": 0.0, "orchestrate": 0.0}

    def test_readbacks_let_go_behind_the_pump(self, pushed):
        # over 50 waves at most the wave in flight (collected, its
        # follow-ups not yet taken by the feed) keeps its emission batch
        alive = pushed["alive_after_pump"]
        assert len(alive) >= 50
        assert max(alive) <= 2, alive
        assert pushed["alive_at_end"] == 0

    def test_frozen_entries_read_as_the_records_on_disk(self, pushed):
        from zeebe_tpu.protocol import codec

        frozen = pushed["frozen"]
        assert len(frozen) > len(pushed["disk"]) // 2
        for position, record in frozen.items():
            assert codec.encode_record(record) == pushed["disk"][position]

    def test_after_a_restart_the_push_encodes_the_recovered_records(self, pushed):
        broker = _device_broker(pushed["data_dir"])
        try:
            client = _capturing_client(broker)
            try:
                before = _push_counts()
                sub = _subscribe(client, "after-restart")
                client.create_instance("route", {"orderId": 99})
                _settle(broker)
                delta = {k: v - before[k] for k, v in _push_counts().items()}
                sub.close()
                sent = _pushed(client)
            finally:
                client.close()
            disk = _disk_frames(broker.partitions[0].log)
        finally:
            broker.close()
        recovered = max(pushed["disk"]) + 1
        assert delta["serving_topic_push_encoded_total"] > 0
        assert delta["serving_topic_push_frames_total"] > 0
        assert any(p < recovered for p, _f in sent)
        assert any(p >= recovered for p, _f in sent)
        for position, frame in sent:
            assert frame == disk[position], position
