"""Payload documents on the device path: a top-level variable whose value
is a map, a list, or a number f32 does not hold exactly (``VT_HELD``) is
staged as a handle into the engine's host-side value store
(``zeebe_tpu/tpu/values.py``); an instance goes to the host engine only
where a variable its workflow reads holds one.

Every scenario runs on the host engine and on the device engine with the
same commands, and the two logs must agree record for record, payloads
compared type for type (the canonical msgpack of each document, so that an
int that came back a float, or a list a tuple, is a difference)."""

import random

import numpy as np
import pytest

from zeebe_tpu.gateway import ClientException, JobWorker, ZeebeClient
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.models.bpmn.model import OutputBehavior
from zeebe_tpu.protocol import msgpack
from zeebe_tpu.protocol.enums import ValueType
from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI
from zeebe_tpu.runtime import ControlledClock
from zeebe_tpu.runtime.metrics import event_count

from tests.test_tpu_parity import DualRig, gateway_process, order_process


def random_document(rng: random.Random, depth: int = 0):
    """A JSON-shaped value: nested maps and lists of 64-bit integers,
    doubles, unicode strings, None and bools."""
    kinds = ["int", "i64", "f64", "str", "none", "bool"]
    if depth < 3:
        kinds += ["map", "list"] * 2
    kind = rng.choice(kinds)
    if kind == "int":
        return rng.randint(-1000, 1000)
    if kind == "i64":
        return rng.choice([1, -1]) * rng.randint(1 << 40, (1 << 62))
    if kind == "f64":
        return round(rng.uniform(-1e6, 1e6), rng.randint(1, 6))
    if kind == "str":
        return "".join(rng.choice("aZ9 ßéπ漢字🙂-") for _ in range(rng.randint(0, 12)))
    if kind == "none":
        return None
    if kind == "bool":
        return rng.random() < 0.5
    if kind == "map":
        return {
            f"k{i}{rng.choice('xyzé')}": random_document(rng, depth + 1)
            for i in range(rng.randint(0, 4))
        }
    return [random_document(rng, depth + 1) for _ in range(rng.randint(0, 4))]


def document_payload(seed: int, **scalars) -> dict:
    """A create payload: the given scalars beside documents, held numbers
    (an id above 2^40, a price f32 does not hold) and a nested customer."""
    rng = random.Random(seed)
    doc = {
        "customer": {"id": (1 << 40) + seed, "tier": "gold",
                     "address": {"city": "Zürich", "lines": ["a", "b"]}},
        "lines": [{"sku": f"S-{i}", "qty": i + 1, "unitPrice": 19.99 + i}
                  for i in range(3)],
        "total": 59.97 + seed,
        "createdAt": 1_530_000_000_123 + seed,
        "doc": random_document(rng),
        "extra": [random_document(rng) for _ in range(3)],
    }
    doc.update(scalars)
    return doc


def canonical_log(broker):
    from zeebe_tpu.testing.parity import SIG_TYPES

    return [
        (r.position, int(r.metadata.record_type), int(r.metadata.value_type),
         int(r.metadata.intent), r.key,
         msgpack.canonical(dict(getattr(r.value, "payload", {}) or {})))
        for r in broker.records(0)
        if int(r.metadata.value_type) in SIG_TYPES
    ]


def tpu_engine(rig):
    return rig.brokers[1].partitions[0].engine


def instance_records_on_host(engine) -> int:
    """Workflow-instance and job records the device engine handed to its
    embedded host engine."""
    vts = (int(ValueType.WORKFLOW_INSTANCE), int(ValueType.JOB))
    return sum(n for (vt, _wf), n in engine.host_records_by_kind.items() if vt in vts)


@pytest.fixture
def rig():
    r = DualRig()
    yield r
    r.close()


def assert_same_logs(rig):
    rig.assert_parity()
    host, tpu = (canonical_log(b) for b in rig.brokers)
    assert host == tpu


def service_process(**task):
    return (
        Bpmn.create_process("svc-doc")
        .start_event("start")
        .service_task("work", type="svc", **task)
        .end_event("end")
        .done()
    )


class TestDocumentParity:
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_no_mapping(self, rig, seed):
        def scenario(broker, client, clock):
            client.deploy_model(order_process())
            JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
            for i in range(3):
                client.create_instance(
                    "order-process", document_payload(seed * 10 + i, orderId=i)
                )

        rig.run(scenario)
        assert_same_logs(rig)
        engine = tpu_engine(rig)
        assert instance_records_on_host(engine) == 0
        assert len(engine._values) == 0

    def test_input_mappings(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(service_process(
                inputs=[("$.orderValue", "$.amount"), ("$.orderId", "$.id")],
            ))
            JobWorker(broker, "svc", lambda ctx: {"seen": sorted(ctx.payload)})
            client.create_instance(
                "svc-doc", document_payload(7, orderValue=120, orderId=1)
            )

        rig.run(scenario)
        assert_same_logs(rig)
        assert instance_records_on_host(tpu_engine(rig)) == 0

    @pytest.mark.parametrize("behavior", list(OutputBehavior), ids=lambda b: b.name)
    def test_output_behavior(self, rig, behavior):
        def scenario(broker, client, clock):
            client.deploy_model(service_process(
                outputs=[("$.result", "$.outcome")], output_behavior=behavior,
            ))
            # the mapped variable is a scalar (a mapping source is a read);
            # the result adds a document and overrides held variables beside
            JobWorker(broker, "svc", lambda ctx: {
                "result": 41, "lines": [], "total": 0.1,
                "receipt": {"n": (1 << 50) + 3, "ok": [1, 2.5, "ü"]},
            })
            for i in range(2):
                client.create_instance("svc-doc", document_payload(20 + i, orderId=i))

        rig.run(scenario)
        assert_same_logs(rig)
        engine = tpu_engine(rig)
        assert instance_records_on_host(engine) == 0
        assert len(engine._values) == 0

    def test_worker_adds_and_overrides_documents(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(order_process())
            JobWorker(broker, "payment-service", lambda ctx: {
                "customer": {"id": 5, "tier": "silver"}, "fresh": [[1], {"a": None}],
                "createdAt": 1_600_000_000_001, "paid": True,
            })
            client.create_instance("order-process", document_payload(31, orderId=3))

        rig.run(scenario)
        assert_same_logs(rig)
        assert instance_records_on_host(tpu_engine(rig)) == 0

    def test_gateway_reads_a_scalar_beside_documents(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(gateway_process())
            JobWorker(broker, "priority-service", lambda ctx: {"fast": [1]})
            JobWorker(broker, "normal-service", lambda ctx: {"slow": {"x": 0.3}})
            for i, value in enumerate((250, 40, 100, 99)):
                client.create_instance(
                    "decision", document_payload(40 + i, orderValue=value)
                )

        rig.run(scenario)
        assert_same_logs(rig)
        engine = tpu_engine(rig)
        assert instance_records_on_host(engine) == 0
        assert len(engine._values) == 0

    @pytest.mark.parametrize("value", [99.99, 100.01, [250], {"v": 250}],
                             ids=["inexact-below", "inexact-above", "list", "map"])
    def test_condition_reading_a_held_value_runs_on_the_host(self, rig, value):
        def scenario(broker, client, clock):
            client.deploy_model(gateway_process())
            JobWorker(broker, "priority-service", lambda ctx: {"fast": True})
            JobWorker(broker, "normal-service", lambda ctx: {"slow": True})
            client.create_instance("decision", document_payload(50, orderValue=250))
            client.create_instance("decision", document_payload(51, orderValue=value))

        before = event_count("serving_payload_host_routed_total")
        rig.run(scenario)
        assert_same_logs(rig)
        assert event_count("serving_payload_host_routed_total") - before == 1
        engine = tpu_engine(rig)
        # the host engine ran the second instance: its create, its events
        # and its job
        assert instance_records_on_host(engine) > 3
        assert len(engine._values) == 0

    def test_mapping_source_holding_a_document_runs_on_the_host(self, rig):
        def scenario(broker, client, clock):
            client.deploy_model(service_process(
                inputs=[("$.customer", "$.who")],
            ))
            JobWorker(broker, "svc", lambda ctx: {"done": True})
            client.create_instance("svc-doc", document_payload(55, orderId=5))

        before = event_count("serving_payload_host_routed_total")
        rig.run(scenario)
        assert_same_logs(rig)
        assert event_count("serving_payload_host_routed_total") - before == 1
        assert instance_records_on_host(tpu_engine(rig)) > 3

    def test_worker_result_under_a_read_variable_moves_the_instance(self, rig):
        def scenario(broker, client, clock):
            b = Bpmn.create_process("then-decide").start_event("s")
            b = b.service_task("price", type="pricing").exclusive_gateway("split")
            b.branch("$.orderValue >= 100").end_event("high")
            b.branch(default=True).end_event("low")
            client.deploy_model(b.done())
            JobWorker(broker, "pricing", lambda ctx: {"orderValue": 149.99})
            client.create_instance("then-decide", document_payload(60, orderValue=1))

        before = event_count("serving_payload_host_routed_total")
        rig.run(scenario)
        assert_same_logs(rig)
        assert event_count("serving_payload_host_routed_total") - before == 1
        assert len(tpu_engine(rig)._values) == 0

    @pytest.mark.parametrize("ttl", [0, 60_000], ids=["at-publish", "stored"])
    def test_message_document_correlates(self, rig, ttl):
        def scenario(broker, client, clock):
            client.deploy_model(
                Bpmn.create_process("msg-doc").start_event("s")
                .receive_task("wait", message_name="paid", correlation_key="$.oid")
                .end_event("e").done()
            )
            message = {"receipt": {"lines": [1, 2, 3], "total": 19.99},
                       "paidAt": 1_530_000_000_999}
            if ttl:
                client.publish_message("paid", "o-1", message, time_to_live_ms=ttl)
                broker.run_until_idle()
            client.create_instance("msg-doc", document_payload(70, oid="o-1"))
            broker.run_until_idle()
            if not ttl:
                client.publish_message("paid", "o-1", message)
            broker.run_until_idle()
            clock.advance(ttl + 1_000)
            broker.tick()

        rig.run(scenario)
        assert_same_logs(rig)
        completed = [
            r for r in rig.brokers[1].records(0)
            if int(r.metadata.value_type) == int(ValueType.WORKFLOW_INSTANCE)
            and int(r.metadata.intent) == int(WI.ELEMENT_COMPLETED)
            and r.value.activity_id == "msg-doc"
        ]
        assert completed and completed[0].value.payload["receipt"]["total"] == 19.99
        engine = tpu_engine(rig)
        assert instance_records_on_host(engine) == 0
        assert len(engine._values) == 0


class TestValueStore:
    def test_rejected_commands_free_their_values(self):
        from tests.conftest import make_tpu_broker

        broker = make_tpu_broker()
        try:
            client = ZeebeClient(broker)
            client.deploy_model(order_process())
            with pytest.raises(ClientException):
                client.complete_job(999_999, {"doc": {"a": [1, 2]}, "n": 0.1})
            with pytest.raises(ClientException):
                client.create_instance("no-such-process", {"doc": [1]})
            broker.run_until_idle()
            assert len(broker.partitions[0].engine._values) == 0
        finally:
            broker.close()

    def test_inexact_numbers_nothing_reads_are_held_exactly(self):
        from tests.conftest import make_tpu_broker

        broker = make_tpu_broker()
        try:
            client = ZeebeClient(broker)
            client.deploy_model(order_process())
            held = event_count("serving_payload_values_held_total")
            client.create_instance("order-process", {"x": 0.1, "id": (1 << 53) + 1})
            broker.run_until_idle()
            engine = broker.partitions[0].engine
            assert event_count("serving_payload_values_held_total") - held == 2
            assert len(engine._values) == 2  # the job is open
            ready = [
                r for r in broker.records(0)
                if int(r.metadata.intent) == int(WI.ELEMENT_ACTIVATED)
                and r.value.activity_id == "collect-money"
            ]
            assert ready[0].value.payload == {"x": 0.1, "id": (1 << 53) + 1}
            assert type(ready[0].value.payload["id"]) is int
        finally:
            broker.close()

    def test_store_round_trips_its_snapshot(self):
        from zeebe_tpu.tpu.values import ValueStore

        store = ValueStore()
        a, b, c = store.hold({"x": [1]}), store.hold([2.5]), store.hold(0.1)
        store.own(7, a)
        store.own(7, b)
        store.own(("m", 9), b)
        snap = msgpack.unpack(msgpack.pack(store.snapshot()))
        again = ValueStore()
        again.restore(snap)
        assert len(again) == 2 and again[a] == {"x": [1]} and again[b] == [2.5]
        assert c not in again._values  # no owner: dropped
        again.end(7)
        assert len(again) == 1  # the message still holds b
        again.end(("m", 9))
        assert len(again) == 0
        assert again.hold("new") in (a, b, c)  # freed handles are given out again

    @pytest.mark.parametrize("restart", ["snapshot", "replay"])
    def test_restart_with_documents_in_flight_gives_the_same_records(
        self, tmp_path, restart
    ):
        from tests.conftest import make_tpu_broker
        from zeebe_tpu.runtime import Broker

        def scenario(make):
            broker = make()
            client = ZeebeClient(broker)
            client.deploy_model(gateway_process())
            # one job type: which open job a worker that arrives after a
            # replay gets first is the backlog's order, not the documents'
            for i, value in enumerate((250, 120, 300)):
                client.create_instance(
                    "decision", document_payload(80 + i, orderValue=value)
                )
            broker.run_until_idle()
            if make is not host:
                engine = broker.partitions[0].engine
                assert len(engine._values) > 0  # jobs open, documents held
                if restart == "snapshot":
                    broker.snapshot()
                broker.close()
                broker = make()
                client = ZeebeClient(broker)
            JobWorker(broker, "priority-service", lambda ctx: {"fast": [1]})
            JobWorker(broker, "normal-service", lambda ctx: {"slow": 0.3})
            broker.run_until_idle()
            return broker

        clock_t = ControlledClock(start_ms=1_000_000)
        clock_h = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "tpu")

        def tpu():
            return make_tpu_broker(data_dir=data, clock=clock_t)

        def host():
            return Broker(num_partitions=1, clock=clock_h)

        restarted, reference = scenario(tpu), scenario(host)
        try:
            assert canonical_log(restarted) == canonical_log(reference)
            assert len(restarted.partitions[0].engine._values) == 0
        finally:
            restarted.close()
            reference.close()


class TestHeldValuesNeverCompared:
    def test_a_handle_never_reaches_compare(self, monkeypatch):
        """Trace the step anew with ``conditions._compare`` wrapped: every
        operand type it is handed in a run whose payloads hold documents
        beside the gateway's variable is recorded, and none is a held
        value's."""
        import jax

        from tests.conftest import make_tpu_broker
        from zeebe_tpu.tpu import conditions, kernel

        seen = []
        compare = conditions._compare

        def recording_compare(op, lvt, lnum, lsid, rvt, rnum, rsid):
            jax.debug.callback(
                lambda a, b: seen.append((np.asarray(a), np.asarray(b))), lvt, rvt
            )
            return compare(op, lvt, lnum, lsid, rvt, rnum, rsid)

        def step(graph, state, batch, now, synthetic_workers=False, partition_id=0):
            # a function of its own: jax keeps the traces of step_kernel
            return kernel.step_kernel(
                graph, state, batch, now, synthetic_workers, partition_id
            )

        monkeypatch.setattr(conditions, "_compare", recording_compare)
        monkeypatch.setattr(kernel, "step_jit", jax.jit(
            step, static_argnames=("synthetic_workers",),
        ))
        broker = make_tpu_broker()
        try:
            client = ZeebeClient(broker)
            client.deploy_model(gateway_process())
            JobWorker(broker, "priority-service", lambda ctx: None)
            JobWorker(broker, "normal-service", lambda ctx: None)
            held = event_count("serving_payload_values_held_total")
            # the third holds the gateway's own variable: it runs on the
            # host engine, so the kernel never sees its handle either
            for i, value in enumerate((250, 40, 99.99)):
                client.create_instance(
                    "decision", document_payload(90 + i, orderValue=value)
                )
            broker.run_until_idle()
            assert event_count("serving_payload_values_held_total") - held >= 10
        finally:
            broker.close()
        assert seen
        types = np.unique(np.concatenate([np.ravel(t) for pair in seen for t in pair]))
        assert conditions.VT_NUM in types
        assert conditions.VT_HELD not in types


class TestColumns:
    def test_unknown_type_tag_raises(self):
        from zeebe_tpu.tpu import batch as rb
        from zeebe_tpu.tpu.intern import InternTable

        vt = np.array([3, 9], np.int8)
        num = np.array([1.0, 0.0], np.float32)
        sid = np.zeros(2, np.int32)
        with pytest.raises(ValueError, match="unknown type tag 9"):
            rb.columns_to_payload(vt, num, sid, ["a", "b"], InternTable())

    def test_held_values_round_trip_through_columns(self):
        from zeebe_tpu.tpu import batch as rb
        from zeebe_tpu.tpu.intern import InternTable
        from zeebe_tpu.tpu.values import ValueStore

        store, interns = ValueStore(), InternTable()
        names = []

        def column_of(name):
            if name not in names:
                names.append(name)
            return names.index(name)

        doc = {"a": 1, "b": 0.1, "c": [1, {"d": None}], "e": "s", "f": (1 << 40) + 1,
               "g": None, "h": True, "i": 0.5}
        vt, num, sid = rb.payload_to_columns(doc, column_of, interns, 16, store.hold)
        assert len(store) == 3
        back = rb.columns_to_payload(vt, num, sid, names, interns, store)
        assert msgpack.canonical(back) == msgpack.canonical(doc)
        with pytest.raises(rb.PayloadError):
            rb.payload_to_columns(doc, column_of, interns, 16)


class TestReferenceOnDocuments:
    """``zbench/reference.py`` derives what an ``order-doc-1p`` instance's
    records have to be; both engines give exactly that on the cell's own
    processes and documents."""

    @pytest.mark.parametrize("engine", ["host", "tpu"])
    def test_both_processes_match_the_reference(self, engine):
        from tests.conftest import make_tpu_broker
        from zbench import check, reference, traffic
        from zbench.processes import order_doc, route_doc
        from zeebe_tpu.runtime import Broker

        graphs = {m.GRAPH["id"]: m.GRAPH for m in (order_doc, route_doc)}
        plan = traffic.Plan(
            {"order-doc": 0.75, "route-doc": 0.25},
            {pid: g["payload_variants"] for pid, g in graphs.items()},
            seed=(1 << 31) + 7,
        )
        broker = make_tpu_broker() if engine == "tpu" else Broker(num_partitions=1)
        try:
            client = ZeebeClient(broker)
            client.deploy_model(order_doc.build())
            client.deploy_model(route_doc.build())
            JobWorker(broker, "payment-service",
                      lambda ctx: traffic.worker_result(ctx.payload), credits=64)
            creates = []
            for _ in range(40):
                _seq, pid, payload = plan.next()
                inst = client.create_instance(pid, payload)
                creates.append((inst.workflow_instance_key, pid, payload))
            broker.run_until_idle()
            got = check.per_instance([check.normalize(r) for r in broker.records(0)])
            for key, pid, payload in creates:
                want = reference.expected(graphs[pid], payload, traffic.worker_result)
                why = reference.instance_mismatch(
                    want, got[key]["events"], got[key]["jobs"]
                )
                assert why is None, (pid, why)
            if engine == "tpu":
                tpu = broker.partitions[0].engine
                assert instance_records_on_host(tpu) == 0
                assert len(tpu._values) == 0
        finally:
            broker.close()
