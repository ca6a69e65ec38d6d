"""Event-replay parity: TPU device engine vs host oracle engine.

The correctness contract from BASELINE.json: the device kernel must produce
the same committed record stream as the reference-semantics oracle for the
same commands (SURVEY.md §5 — "the event log IS the trace"). Every scenario
drives both engines through the broker runtime with identical inputs and
compares the full log signature: position, record type, value type, intent,
key, source position, rejection, activity, payload, scope, headers.

Scenarios mirror BASELINE.json's benchmark configs: service-task sequence,
exclusive-gateway split with json-el conditions, parallel fork/join, timer
catch events, plus incident/rejection paths.
"""

import pytest

from zeebe_tpu.engine.interpreter import WorkflowRepository
from zeebe_tpu.gateway import ClientException, JobWorker, ZeebeClient
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI
from zeebe_tpu.runtime import Broker, ControlledClock
from zeebe_tpu.testing.parity import record_signature
from zeebe_tpu.tpu import TpuPartitionEngine

class DualRig:
    """Runs the same scenario against oracle and TPU brokers."""

    def __init__(self):
        self.brokers = []
        for tpu in (False, True):
            clock = ControlledClock(start_ms=1_000_000)
            if tpu:
                repo = WorkflowRepository()
                broker = Broker(
                    num_partitions=1,
                    clock=clock,
                    engine_factory=lambda pid: TpuPartitionEngine(
                        pid, 1, repository=repo, clock=clock
                    ),
                )
            else:
                broker = Broker(num_partitions=1, clock=clock)
            broker._test_clock = clock
            self.brokers.append(broker)

    def run(self, scenario):
        outcomes = []
        for broker in self.brokers:
            client = ZeebeClient(broker)
            outcomes.append(scenario(broker, client, broker._test_clock))
            broker.run_until_idle()
        return outcomes

    def assert_parity(self):
        oracle = record_signature(self.brokers[0].records(0))
        tpu = record_signature(self.brokers[1].records(0))
        for i, (a, b) in enumerate(zip(oracle, tpu)):
            assert a == b, f"record {i} mismatch:\n  oracle: {a}\n  tpu:    {b}"
        assert len(oracle) == len(tpu), (
            f"record count mismatch: oracle={len(oracle)} tpu={len(tpu)}\n"
            f"oracle tail: {oracle[-4:]}\ntpu tail: {tpu[-4:]}"
        )

    def close(self):
        for broker in self.brokers:
            broker.close()


@pytest.fixture
def rig():
    r = DualRig()
    yield r
    r.close()


def order_process():
    return (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )


def gateway_process():
    b = Bpmn.create_process("decision").start_event("start").exclusive_gateway("split")
    b.branch("$.orderValue >= 100").service_task(
        "high", type="priority-service"
    ).end_event("end-high")
    b.branch(default=True).service_task("low", type="normal-service").end_event(
        "end-low"
    )
    return b.done()


def fork_join_process():
    b = Bpmn.create_process("fork-join").start_event("start").parallel_gateway("fork")
    branch1 = b.branch().service_task("task-a", type="svc-a")
    branch2 = b.branch().service_task("task-b", type="svc-b")
    branch1.parallel_gateway("join")
    branch2.connect_to("join")
    b.move_to("join").end_event("end")
    return b.done()


class TestServiceTaskParity:
    def test_happy_path(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(order_process())
            JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
            client.create_instance(
                "order-process", payload={"orderId": 31243, "orderValue": 99}
            )

        rig.run(scenario)
        rig.assert_parity()

    def test_multiple_instances(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(order_process())
            JobWorker(
                broker, "payment-service", lambda ctx: {"paid": True}, credits=64
            )
            for i in range(10):
                client.create_instance("order-process", payload={"orderId": i})

        rig.run(scenario)
        rig.assert_parity()

    def test_job_fail_and_retry(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(order_process())
            attempts = []

            def handler(ctx):
                attempts.append(1)
                if len(attempts) == 1:
                    ctx.fail(retries=ctx.job.retries - 1)
                    return None
                return {"paid": True}

            JobWorker(broker, "payment-service", handler)
            client.create_instance("order-process", payload={"orderId": 1})

        rig.run(scenario)
        rig.assert_parity()

    def test_job_no_retries_incident(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(order_process())

            def handler(ctx):
                ctx.fail(retries=0)

            JobWorker(broker, "payment-service", handler)
            client.create_instance("order-process", payload={"orderId": 1})

        rig.run(scenario)
        rig.assert_parity()

    def test_job_timeout_reactivation(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(order_process())
            seen = []

            def handler(ctx):
                seen.append(ctx.key)
                if len(seen) == 1:
                    ctx.finished = True  # crashed worker: never completes
                    return None
                return {"paid": True}

            JobWorker(broker, "payment-service", handler, timeout_ms=5_000)
            client.create_instance("order-process", payload={"orderId": 1})
            broker.run_until_idle()
            clock.advance(10_000)
            broker.tick()

        rig.run(scenario)
        rig.assert_parity()

    def test_complete_unknown_job_rejected(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(order_process())
            try:
                client.complete_job(999999)
            except ClientException:
                pass

        rig.run(scenario)
        rig.assert_parity()

    def test_create_unknown_workflow_rejected(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(order_process())
            try:
                client.create_instance("no-such-process")
            except ClientException:
                pass

        rig.run(scenario)
        rig.assert_parity()


class TestExclusiveGatewayParity:
    def test_condition_routes_high(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(gateway_process())
            JobWorker(broker, "priority-service", lambda ctx: None)
            JobWorker(broker, "normal-service", lambda ctx: None)
            client.create_instance("decision", payload={"orderValue": 250})

        rig.run(scenario)
        rig.assert_parity()

    def test_condition_routes_default(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(gateway_process())
            JobWorker(broker, "priority-service", lambda ctx: None)
            JobWorker(broker, "normal-service", lambda ctx: None)
            client.create_instance("decision", payload={"orderValue": 42})

        rig.run(scenario)
        rig.assert_parity()

    def test_condition_error_incident(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(gateway_process())
            client.create_instance("decision", payload={"unrelated": 1})

        rig.run(scenario)
        rig.assert_parity()

    def test_string_and_mixed_conditions(self, rig):
        def scenario(broker, client, clock):
            b = (
                Bpmn.create_process("strings")
                .start_event("start")
                .exclusive_gateway("split")
            )
            b.branch('$.kind == "express" && $.weight < 10').service_task(
                "a", type="svc-a"
            ).end_event("end-a")
            b.branch(default=True).service_task("b", type="svc-b").end_event("end-b")
            client.deploy_model(b.done())
            JobWorker(broker, "svc-a", lambda ctx: None)
            JobWorker(broker, "svc-b", lambda ctx: None)
            client.create_instance("strings", payload={"kind": "express", "weight": 5})
            client.create_instance("strings", payload={"kind": "bulk", "weight": 5})

        rig.run(scenario)
        rig.assert_parity()


class TestParallelGatewayParity:
    def test_fork_join(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(fork_join_process())
            JobWorker(broker, "svc-a", lambda ctx: {"a": 1})
            JobWorker(broker, "svc-b", lambda ctx: {"b": 2})
            client.create_instance("fork-join", payload={"seed": 7})

        rig.run(scenario)
        rig.assert_parity()

    def test_fork_join_many(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(fork_join_process())
            JobWorker(broker, "svc-a", lambda ctx: {"a": 1}, credits=64)
            JobWorker(broker, "svc-b", lambda ctx: {"b": 2}, credits=64)
            for i in range(5):
                client.create_instance("fork-join", payload={"seed": i})

        rig.run(scenario)
        rig.assert_parity()


class TestTimerParity:
    def test_timer_catch_event(self, rig):
        def scenario(broker, client, clock):
            model = (
                Bpmn.create_process("timed")
                .start_event("start")
                .timer_catch_event("wait", duration_ms=60_000)
                .end_event("end")
                .done()
            )
            client.deploy_model(model)
            client.create_instance("timed", payload={"x": 1})
            broker.run_until_idle()
            clock.advance(120_000)
            broker.tick()

        rig.run(scenario)
        rig.assert_parity()


class TestMappingParity:
    def test_io_mappings(self, rig):
        def scenario(broker, client, clock):
            model = (
                Bpmn.create_process("mapped")
                .start_event("start")
                .service_task(
                    "work",
                    type="svc",
                    inputs=[("$.total", "$.amount")],
                    outputs=[("$.result", "$.outcome")],
                )
                .end_event("end")
                .done()
            )
            client.deploy_model(model)
            JobWorker(broker, "svc", lambda ctx: {"result": 41})
            client.create_instance("mapped", payload={"total": 99, "noise": 1})

        rig.run(scenario)
        rig.assert_parity()

    def test_input_mapping_error_incident(self, rig):
        def scenario(broker, client, clock):
            model = (
                Bpmn.create_process("mapped-err")
                .start_event("start")
                .service_task("work", type="svc", inputs=[("$.missing", "$.amount")])
                .end_event("end")
                .done()
            )
            client.deploy_model(model)
            client.create_instance("mapped-err", payload={"total": 99})

        rig.run(scenario)
        rig.assert_parity()


class TestInstanceCounts:
    def test_completion_events_present(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(order_process())
            JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
            client.create_instance("order-process", payload={"v": 1})

        rig.run(scenario)
        for broker in rig.brokers:
            completed = [
                r
                for r in broker.records(0)
                if int(r.metadata.value_type) == int(ValueType.WORKFLOW_INSTANCE)
                and int(r.metadata.record_type) == int(RecordType.EVENT)
                and int(r.metadata.intent) == int(WI.ELEMENT_COMPLETED)
                and r.value.activity_id == "order-process"
            ]
            assert len(completed) == 1


class TestPayloadContract:
    """TPU partitions hold payload numbers that f32 does not hold exactly
    on the host (``tpu/values.py``) and give them back exactly, as the host
    engine does: the device stores payload numerics as f32
    (state.pack_payload), so such a number rides as a handle, and a
    workflow that compares it runs on the host engine."""

    def _tpu_broker(self):
        from tests.conftest import make_tpu_broker

        return make_tpu_broker()

    def test_inexact_float_create_is_rejected(self, rig):
        """(Named for the rejection that went:) the create of an inexact
        float is accepted, and its records are the host engine's."""

        def scenario(broker, client, clock):
            client.deploy_model(order_process())
            JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
            inst = client.create_instance(
                "order-process", {"x": 0.1, "big": (1 << 53) + 1}
            )
            assert inst.workflow_instance_key > 0

        rig.run(scenario)
        rig.assert_parity()
        tpu = rig.brokers[1]
        assert not any(
            int(r.metadata.record_type) == int(RecordType.COMMAND_REJECTION)
            for r in tpu.records(0)
        )
        payloads = [
            r.value.payload for r in tpu.records(0)
            if int(r.metadata.value_type) == int(ValueType.WORKFLOW_INSTANCE)
            and "x" in r.value.payload
        ]
        assert len(payloads) > 5 and all(
            p["x"] == 0.1 and p["big"] == (1 << 53) + 1 and type(p["big"]) is int
            for p in payloads
        )

    def test_exact_float_passes(self):
        broker = self._tpu_broker()
        try:
            client = ZeebeClient(broker)
            client.deploy_model(order_process())
            client.create_instance("order-process", {"x": 0.25, "n": 1 << 20})
            broker.run_until_idle()
            assert not any(
                int(r.metadata.record_type) == int(RecordType.COMMAND_REJECTION)
                for r in broker.records(0)
            )
        finally:
            broker.close()


class TestHostOnlyFallback:
    """Device-incompatible workflows (nested correlation-key paths here —
    message catches with FLAT keys compile to the device since round 4) run
    on the embedded host oracle of a TPU-backed partition — every deployed
    workflow keeps executing (reference bar: the stream processor serves
    the whole deployed set; `graph.check_device_compatible` decides WHERE
    each one runs)."""

    def _tpu_broker(self):
        from tests.conftest import make_tpu_broker

        return make_tpu_broker()

    def test_mixed_deployment_both_complete(self):
        broker = self._tpu_broker()
        try:
            client = ZeebeClient(broker)
            client.deploy_model(order_process())
            msg_model = (
                Bpmn.create_process("wait-for-msg")
                .start_event("s")
                .message_catch_event(
                    # nested path: no device column form → host-only
                    "wait", message_name="go", correlation_key="$.meta.orderId"
                )
                .end_event("e")
                .done()
            )
            client.deploy_model(msg_model)
            engine = broker.partitions[0].engine
            assert engine._host_only_keys, "nested-path workflow should be host-only"
            assert engine.graph is not None, "device workflow should compile"

            # device workflow completes on the kernel
            worker = JobWorker(broker, "payment-service", lambda ctx: {"ok": True})
            client.create_instance("order-process", {"orderId": 1})
            broker.run_until_idle()
            assert len(worker.handled) == 1

            # host-only workflow completes via message correlation
            client.create_instance("wait-for-msg", {"meta": {"orderId": 7}})
            broker.run_until_idle()
            client.publish_message("go", correlation_key="7")
            broker.run_until_idle()
            completed = [
                r for r in broker.records(0)
                if int(r.metadata.value_type) == int(ValueType.WORKFLOW_INSTANCE)
                and int(r.metadata.record_type) == int(RecordType.EVENT)
                and int(r.metadata.intent) == int(WI.ELEMENT_COMPLETED)
                and getattr(r.value, "activity_id", "") in ("order-process", "wait-for-msg")
            ]
            assert {r.value.activity_id for r in completed} == {
                "order-process", "wait-for-msg"
            }
        finally:
            broker.close()

    def test_host_only_workflow_with_service_task(self):
        """Jobs of host-only workflows are served through the embedded host
        oracle's subscriptions (the device sub table only covers device
        jobs) — a worker completes them like on a host partition."""
        broker = self._tpu_broker()
        try:
            client = ZeebeClient(broker)
            model = (
                Bpmn.create_process("msg-then-work")
                .start_event("s")
                .message_catch_event(
                    # nested path keeps this workflow host-only
                    "wait", message_name="go2", correlation_key="$.meta.k"
                )
                .service_task("work", type="late-service")
                .end_event("e")
                .done()
            )
            client.deploy_model(model)
            assert broker.partitions[0].engine._host_only_keys
            worker = JobWorker(broker, "late-service", lambda ctx: {"done": 1})
            client.create_instance("msg-then-work", {"meta": {"k": 5}})
            broker.run_until_idle()
            client.publish_message("go2", correlation_key="5")
            broker.run_until_idle()
            assert len(worker.handled) == 1
            events = [
                (int(r.metadata.intent), getattr(r.value, "activity_id", ""))
                for r in broker.records(0)
                if int(r.metadata.value_type) == int(ValueType.WORKFLOW_INSTANCE)
                and int(r.metadata.record_type) == int(RecordType.EVENT)
            ]
            assert (int(WI.ELEMENT_COMPLETED), "msg-then-work") in events
        finally:
            broker.close()

    def test_mixed_deployment_survives_snapshot_restore(self, tmp_path):
        """Snapshot + restart of a mixed (device + host-only) deployment
        preserves the host-only split and workflow slot numbering — the
        regression where restore compiled EVERYTHING into the device graph
        wedged host-only instances at their catch events."""
        from tests.conftest import make_tpu_broker

        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")

        def make_broker():
            return make_tpu_broker(data_dir=data, clock=clock)

        broker = make_broker()
        client = ZeebeClient(broker)
        client.deploy_model(order_process())
        msg_model = (
            Bpmn.create_process("wait-for-msg")
            .start_event("s")
            .message_catch_event(
                # nested path keeps this workflow host-only
                "wait", message_name="go3", correlation_key="$.meta.k")
            .end_event("e")
            .done()
        )
        client.deploy_model(msg_model)
        host_only_before = set(broker.partitions[0].engine._host_only_keys)
        compiled_before = broker.partitions[0].engine._compiled_count
        client.create_instance("wait-for-msg", {"meta": {"k": 9}})
        broker.run_until_idle()
        broker.snapshot()
        broker.close()

        broker = make_broker()
        engine = broker.partitions[0].engine
        assert set(engine._host_only_keys) == host_only_before
        assert engine._compiled_count == compiled_before
        client = ZeebeClient(broker)
        client.publish_message("go3", correlation_key="9")
        broker.run_until_idle()
        completed = [
            r for r in broker.records(0)
            if int(r.metadata.value_type) == int(ValueType.WORKFLOW_INSTANCE)
            and int(r.metadata.record_type) == int(RecordType.EVENT)
            and int(r.metadata.intent) == int(WI.ELEMENT_COMPLETED)
            and getattr(r.value, "activity_id", "") == "wait-for-msg"
        ]
        assert completed, "host-only instance must complete after restore"
        # a device workflow still runs on the kernel after restore
        worker = JobWorker(broker, "payment-service", lambda ctx: {"ok": 1})
        client.create_instance("order-process", {"orderId": 3})
        broker.run_until_idle()
        assert len(worker.handled) == 1
        broker.close()

    def test_cancel_host_only_instance(self):
        """CANCEL carries no workflow key — routing must recognize the
        host-side instance by key (regression: it went to the device
        kernel and vanished without a response)."""
        broker = self._tpu_broker()
        try:
            client = ZeebeClient(broker)
            client.deploy_model(order_process())  # device workflow too
            msg_model = (
                Bpmn.create_process("cancellable")
                .start_event("s")
                .message_catch_event(
                    # nested path keeps this workflow host-only
                    "w", message_name="m9", correlation_key="$.meta.k")
                .end_event("e")
                .done()
            )
            client.deploy_model(msg_model)
            inst = client.create_instance("cancellable", {"meta": {"k": 1}})
            broker.run_until_idle()
            client.cancel_instance(inst.workflow_instance_key)
            broker.run_until_idle()
            canceled = [
                r for r in broker.records(0)
                if int(r.metadata.value_type) == int(ValueType.WORKFLOW_INSTANCE)
                and int(r.metadata.intent) == int(WI.ELEMENT_TERMINATED)
            ]
            assert canceled
        finally:
            broker.close()


def receive_task_process():
    return (
        Bpmn.create_process("msgflow")
        .start_event("start")
        .receive_task("wait", message_name="paid", correlation_key="$.oid")
        .end_event("done")
        .done()
    )


def catch_event_process():
    return (
        Bpmn.create_process("catchflow")
        .start_event("start")
        .message_catch_event("gate", message_name="go", correlation_key="$.key")
        .service_task("after", type="post-service")
        .end_event("end")
        .done()
    )


class TestMessageCorrelationParity:
    """Round 4: message catch/receive compile to the device — subscription
    open, publish correlate, stored-message TTL, close — and the full log
    must stay bit-identical to the oracle (reference
    SubscriptionCommandSender.java:96-108,
    WorkflowInstanceStreamProcessor.java:455-509)."""

    def test_open_then_publish(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(receive_task_process())
            client.create_instance("msgflow", {"oid": "o-7"})
            broker.run_until_idle()
            client.publish_message("paid", "o-7", {"paid": True})

        rig.run(scenario)
        rig.assert_parity()

    def test_publish_before_open_with_ttl(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(receive_task_process())
            client.publish_message(
                "paid", "o-1", {"amount": 5}, time_to_live_ms=60_000
            )
            broker.run_until_idle()
            client.create_instance("msgflow", {"oid": "o-1"})

        rig.run(scenario)
        rig.assert_parity()

    def test_publish_without_ttl_does_not_store(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(receive_task_process())
            client.publish_message("paid", "o-2", {"x": 1})  # no subscriber
            broker.run_until_idle()
            client.create_instance("msgflow", {"oid": "o-2"})
            broker.run_until_idle()
            # instance still waiting: publish again, now correlates
            client.publish_message("paid", "o-2", {"x": 2})

        rig.run(scenario)
        rig.assert_parity()

    def test_ttl_expiry_deletes_stored_message(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(receive_task_process())
            client.publish_message("paid", "late", {"v": 1}, time_to_live_ms=5_000)
            broker.run_until_idle()
            clock.advance(6_000)
            broker.tick()
            broker.run_until_idle()
            # a subscriber arriving after expiry waits (no stored message)
            client.create_instance("msgflow", {"oid": "late"})

        rig.run(scenario)
        rig.assert_parity()

    def test_message_catch_event_with_downstream_task(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(catch_event_process())
            JobWorker(broker, "post-service", lambda ctx: {"done": 1})
            client.create_instance("catchflow", {"key": "k-1"})
            broker.run_until_idle()
            client.publish_message("go", "k-1", {"approved": True})

        rig.run(scenario)
        rig.assert_parity()

    def test_numeric_correlation_key(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(receive_task_process())
            client.create_instance("msgflow", {"oid": 42})
            broker.run_until_idle()
            client.publish_message("paid", "42", {"ok": True})

        rig.run(scenario)
        rig.assert_parity()

    def test_duplicate_message_id_rejected(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(receive_task_process())
            client.publish_message(
                "paid", "dup", {"n": 1}, time_to_live_ms=60_000, message_id="m-1"
            )
            broker.run_until_idle()
            try:
                client.publish_message(
                    "paid", "dup", {"n": 2}, time_to_live_ms=60_000, message_id="m-1"
                )
            except ClientException:
                pass

        rig.run(scenario)
        rig.assert_parity()

    def test_cancel_closes_subscription(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(receive_task_process())
            inst = client.create_instance("msgflow", {"oid": "c-1"})
            broker.run_until_idle()
            client.cancel_instance(inst.workflow_instance_key)
            broker.run_until_idle()
            # late publish: subscription is closed, message stores (TTL)
            client.publish_message("paid", "c-1", {"late": 1}, time_to_live_ms=9_000)

        rig.run(scenario)
        rig.assert_parity()

    def test_two_instances_distinct_keys(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(receive_task_process())
            client.create_instance("msgflow", {"oid": "a"})
            client.create_instance("msgflow", {"oid": "b"})
            broker.run_until_idle()
            client.publish_message("paid", "b", {"who": "b"})
            broker.run_until_idle()
            client.publish_message("paid", "a", {"who": "a"})

        rig.run(scenario)
        rig.assert_parity()

    def test_correlation_key_missing_raises_incident(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(receive_task_process())
            client.create_instance("msgflow", {"other": 1})  # no oid var

        rig.run(scenario)
        rig.assert_parity()

    def test_float_correlation_key_raises_incident(self, rig):
        # oracle accepts (str, int) only; floats incident on both engines
        def scenario(broker, client, clock):
            client.deploy_model(receive_task_process())
            client.create_instance("msgflow", {"oid": 1.5})

        rig.run(scenario)
        rig.assert_parity()

    def test_bool_correlation_key_subscribes(self, rig):
        # bool IS an int to the oracle — both engines subscribe with "True"
        def scenario(broker, client, clock):
            client.deploy_model(receive_task_process())
            client.create_instance("msgflow", {"oid": True})
            broker.run_until_idle()
            client.publish_message("paid", "True", {"ok": 1})

        rig.run(scenario)
        rig.assert_parity()


class TestMessageStoreLimits:
    """The device message store keys ONE live slot per (name, correlation)
    composite. Workloads exceeding that (two instances waiting on the same
    key, two buffered messages with the same key) REJECT the extra record
    with an explicit reason — a documented capability divergence from the
    oracle that degrades per-record instead of crashing the partition."""

    def _tpu_broker(self):
        from tests.conftest import make_tpu_broker

        return make_tpu_broker()

    def test_second_subscription_same_key_rejected_partition_survives(self):
        broker = self._tpu_broker()
        try:
            client = ZeebeClient(broker)
            client.deploy_model(receive_task_process())
            client.create_instance("msgflow", {"oid": "same"})
            client.create_instance("msgflow", {"oid": "same"})
            broker.run_until_idle()
            rejections = [
                r for r in broker.records(0)
                if int(r.metadata.record_type) == int(RecordType.COMMAND_REJECTION)
                and "already open" in (r.metadata.rejection_reason or "")
            ]
            assert rejections, "second OPEN must reject with a reason"
            # the partition keeps serving: first instance still correlates
            client.publish_message("paid", "same", {"ok": 1})
            broker.run_until_idle()
            completed = [
                r for r in broker.records(0)
                if int(r.metadata.value_type) == int(ValueType.WORKFLOW_INSTANCE)
                and int(r.metadata.intent) == int(WI.ELEMENT_COMPLETED)
                and getattr(r.value, "activity_id", "") == "msgflow"
            ]
            assert len(completed) == 1
        finally:
            broker.close()

    def test_second_stored_message_same_key_rejected(self):
        broker = self._tpu_broker()
        try:
            client = ZeebeClient(broker)
            client.deploy_model(receive_task_process())
            client.publish_message("paid", "k", {"n": 1}, time_to_live_ms=60_000)
            try:
                client.publish_message(
                    "paid", "k", {"n": 2}, time_to_live_ms=60_000
                )
                raise AssertionError("second TTL store should reject")
            except ClientException as e:
                assert "already stored" in str(e)
            # the stored first message still correlates a late subscriber
            client.create_instance("msgflow", {"oid": "k"})
            broker.run_until_idle()
            completed = [
                r for r in broker.records(0)
                if int(r.metadata.value_type) == int(ValueType.WORKFLOW_INSTANCE)
                and int(r.metadata.intent) == int(WI.ELEMENT_COMPLETED)
                and getattr(r.value, "activity_id", "") == "msgflow"
            ]
            assert len(completed) == 1
        finally:
            broker.close()


def boundary_timer_process(interrupting=True):
    return (
        Bpmn.create_process("bdflow")
        .start_event("start")
        .service_task("slow", type="slow-service")
        .boundary_event("deadline", duration_ms=30_000, interrupting=interrupting)
        .service_task("escalate", type="esc-service")
        .end_event("late-end")
        .move_to("slow")
        .end_event("end")
        .done()
    )


def boundary_message_process(interrupting=True):
    return (
        Bpmn.create_process("bdmsg")
        .start_event("start")
        .service_task("work", type="work-service")
        .boundary_event(
            "stop", message_name="halt", correlation_key="$.wid",
            interrupting=interrupting,
        )
        .end_event("halted")
        .move_to("work")
        .end_event("end")
        .done()
    )


def mi_cardinality_process(cardinality=3):
    builder = Bpmn.create_process("miflow")
    sub = builder.start_event("start").sub_process(
        "each", multi_instance={"cardinality": cardinality}
    )
    sub.start_event("s").service_task("work", type="mi-service").end_event("e")
    return sub.embedded_done().end_event("done").done()


class TestBoundaryEventParity:
    """Round 4: timer and message boundary events on tasks compile to the
    device — arming, disarming, interrupting termination (job cancel +
    continuation at the boundary), non-interrupting token fan-out — with
    logs bit-identical to the oracle (reference model BoundaryEvent.java;
    the reference engine never executes it)."""

    def test_interrupting_timer_fires(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(boundary_timer_process())
            done = []
            JobWorker(broker, "esc-service", lambda ctx: done.append(1) or {})
            # no slow-service worker: the job stays out; the timer wins
            client.create_instance("bdflow", {"orderId": 1})
            broker.run_until_idle()
            clock.advance(31_000)
            broker.tick()
            broker.run_until_idle()

        rig.run(scenario)
        rig.assert_parity()

    def test_interrupting_timer_beaten_by_completion(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(boundary_timer_process())
            JobWorker(broker, "slow-service", lambda ctx: {"done": True})
            client.create_instance("bdflow", {"orderId": 2})
            broker.run_until_idle()
            clock.advance(31_000)
            broker.tick()
            broker.run_until_idle()

        rig.run(scenario)
        rig.assert_parity()

    def test_non_interrupting_timer_fires_host_continues(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(boundary_timer_process(interrupting=False))
            done = []
            JobWorker(broker, "esc-service", lambda ctx: done.append(1) or {})
            client.create_instance("bdflow", {"orderId": 3})
            broker.run_until_idle()
            clock.advance(31_000)
            broker.tick()
            broker.run_until_idle()
            # the host task is still live after the boundary fired —
            # completing it now finishes the instance
            JobWorker(broker, "slow-service", lambda ctx: {"late": True})
            broker.run_until_idle()

        rig.run(scenario)
        rig.assert_parity()

    def test_interrupting_message_boundary(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(boundary_message_process())
            client.create_instance("bdmsg", {"wid": "w-1"})
            broker.run_until_idle()
            client.publish_message("halt", "w-1", {"reason": "stop"})
            broker.run_until_idle()

        rig.run(scenario)
        rig.assert_parity()

    def test_message_boundary_disarms_on_completion(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(boundary_message_process())
            JobWorker(broker, "work-service", lambda ctx: {"ok": 1})
            client.create_instance("bdmsg", {"wid": "w-2"})
            broker.run_until_idle()
            # late publish: the subscription is closed, message buffers
            client.publish_message("halt", "w-2", {"late": 1}, time_to_live_ms=5_000)
            broker.run_until_idle()

        rig.run(scenario)
        rig.assert_parity()

    def test_receive_task_with_timer_boundary_config4(self, rig):
        """The BASELINE config-4 shape: message catch + interrupting timer
        deadline — half the instances correlate, half expire."""
        def scenario(broker, client, clock):
            model = (
                Bpmn.create_process("c4")
                .start_event("start")
                .receive_task("wait-pay", message_name="paid",
                              correlation_key="$.oid")
                .boundary_event("deadline", duration_ms=30_000)
                .end_event("expired")
                .move_to("wait-pay")
                .end_event("done")
                .done()
            )
            client.deploy_model(model)
            for i in range(6):
                client.create_instance("c4", {"oid": f"o-{i}"})
            broker.run_until_idle()
            for i in range(0, 6, 2):
                client.publish_message("paid", f"o-{i}", {"paid": True})
            broker.run_until_idle()
            clock.advance(31_000)
            broker.tick()
            broker.run_until_idle()

        rig.run(scenario)
        rig.assert_parity()


class TestMultiInstanceParity:
    """Round 4: cardinality-based multi-instance sub-processes fan out on
    the device (collection-driven MI keeps the host path — collections
    have no columnar form)."""

    def test_cardinality_fanout_completes(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(mi_cardinality_process(3))
            seen = []
            JobWorker(
                broker, "mi-service",
                lambda ctx: seen.append(ctx.job.payload.get("loopCounter")) or {},
                credits=16,
            )
            client.create_instance("miflow", {"batch": 7})

        rig.run(scenario)
        rig.assert_parity()

    def test_two_instances_interleaved(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(mi_cardinality_process(2))
            JobWorker(broker, "mi-service", lambda ctx: {}, credits=16)
            client.create_instance("miflow", {"a": 1})
            client.create_instance("miflow", {"a": 2})

        rig.run(scenario)
        rig.assert_parity()

    def test_collection_mi_stays_host_side(self):
        from tests.conftest import make_tpu_broker

        broker = make_tpu_broker()
        try:
            client = ZeebeClient(broker)
            builder = Bpmn.create_process("coll")
            sub = builder.start_event("s").sub_process(
                "each", multi_instance={"input_collection": "$.items",
                                        "input_element": "item"}
            )
            sub.start_event("ss").service_task("w", type="c-svc").end_event("se")
            client.deploy_model(sub.embedded_done().end_event("e").done())
            assert broker.partitions[0].engine._host_only_keys
            seen = []
            JobWorker(
                broker, "c-svc",
                lambda ctx: seen.append(ctx.job.payload["item"]) or {},
            )
            client.create_instance("coll", {"items": ["x", "y"]})
            broker.run_until_idle()
            assert sorted(seen) == ["x", "y"]
        finally:
            broker.close()


def dual_boundary_process():
    """Receive task with BOTH an interrupting message boundary and a timer
    boundary — the terminate-catch path must re-scan timers exactly like
    the oracle (two CANCEL commands for the armed timer: disarm + the
    terminate-catch scan)."""
    return (
        Bpmn.create_process("dual")
        .start_event("start")
        .receive_task("wait", message_name="main", correlation_key="$.cid")
        .boundary_event(
            "abort", message_name="abort", correlation_key="$.cid",
            interrupting=True,
        )
        .end_event("aborted")
        .move_to("wait")
        .boundary_event("late", duration_ms=60_000)
        .end_event("timed-out")
        .move_to("wait")
        .end_event("done")
        .done()
    )


class TestDualBoundaryParity:
    def test_message_boundary_fires_while_timer_armed(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(dual_boundary_process())
            client.create_instance("dual", {"cid": "c-1"})
            broker.run_until_idle()
            client.publish_message("abort", "c-1", {"why": "stop"})
            broker.run_until_idle()

        rig.run(scenario)
        rig.assert_parity()

    def test_timer_fires_while_message_boundary_armed(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(dual_boundary_process())
            client.create_instance("dual", {"cid": "c-2"})
            broker.run_until_idle()
            clock.advance(61_000)
            broker.tick()
            broker.run_until_idle()

        rig.run(scenario)
        rig.assert_parity()

    def test_main_message_wins_disarms_both(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(dual_boundary_process())
            client.create_instance("dual", {"cid": "c-3"})
            broker.run_until_idle()
            client.publish_message("main", "c-3", {"ok": 1})
            broker.run_until_idle()
            clock.advance(61_000)
            broker.tick()
            broker.run_until_idle()

        rig.run(scenario)
        rig.assert_parity()
