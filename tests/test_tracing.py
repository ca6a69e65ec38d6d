"""Trace plane tests: span completeness on both engines, deterministic
sampling, ring wraparound, dump-on-invariant-failure, and the
tracing-disabled fast path (ISSUE 10 coverage satellite)."""

import json
import os

import pytest

from zeebe_tpu import tracing
from zeebe_tpu.gateway import JobWorker, ZeebeClient
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.runtime import Broker
from zeebe_tpu.runtime.config import ExporterCfg
from zeebe_tpu.tracing.recorder import (
    FlightRecorder,
    read_flight_dump,
)

# the single-writer (in-process) lifecycle; the cluster adds the raft hops
HOST_LIFECYCLE = [
    tracing.GATEWAY_RECV,
    tracing.COMMIT,
    tracing.FEED_TAKE,
    tracing.WAVE_DISPATCH,
    tracing.APPLY,
    tracing.RESPONSE,
    tracing.EXPORT_DISPATCH,
    tracing.EXPORT_ACK,
]


@pytest.fixture
def tracer():
    """A rate-1.0 tracer installed for the test, uninstalled after."""
    t = tracing.install(tracing.RecordTracer(sample_rate=1.0, seed=42))
    yield t
    tracing.install(None)


def order_model():
    return (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("work", type="payment-service")
        .end_event("end")
        .done()
    )


def _run_traced_workload(data_dir, engine_factory=None, exporters=True):
    broker = Broker(
        num_partitions=1,
        data_dir=data_dir,
        engine_factory=engine_factory,
        exporters=(
            [ExporterCfg(id="trace-mem", type="memory")] if exporters else None
        ),
    )
    try:
        client = ZeebeClient(broker)
        client.deploy_model(order_model())
        JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
        for i in range(4):
            client.create_instance("order-process", {"orderId": i})
        broker.run_until_idle()
    finally:
        broker.close()


def _complete_spans(tracer):
    return [
        span for span in tracer.spans()
        if tracing.RESPONSE in span.stage_names()
    ]


class TestSpanCompleteness:
    def test_host_engine_full_lifecycle(self, tracer, tmp_path):
        from zeebe_tpu.exporter import InMemoryExporter

        InMemoryExporter.reset()
        _run_traced_workload(str(tmp_path / "host"))
        spans = _complete_spans(tracer)
        assert len(spans) >= 4  # the four CREATE commands at minimum
        for span in spans:
            names = span.stage_names()
            missing = [s for s in HOST_LIFECYCLE if s not in names]
            assert not missing, (span.trace_id, names, missing)
            ts = [t for _n, t, _f in span.stages]
            assert ts == sorted(ts), list(zip(names, ts))
            assert span.position >= 0

    def test_device_engine_full_lifecycle(self, tracer, tmp_path):
        from zeebe_tpu.engine.interpreter import WorkflowRepository
        from zeebe_tpu.exporter import InMemoryExporter
        from zeebe_tpu.tpu import TpuPartitionEngine

        InMemoryExporter.reset()
        repo = WorkflowRepository()
        _run_traced_workload(
            str(tmp_path / "device"),
            engine_factory=lambda pid: TpuPartitionEngine(
                pid, 1, repository=repo
            ),
        )
        spans = _complete_spans(tracer)
        assert len(spans) >= 4
        for span in spans:
            names = span.stage_names()
            missing = [s for s in HOST_LIFECYCLE if s not in names]
            assert not missing, (span.trace_id, names, missing)
            ts = [t for _n, t, _f in span.stages]
            assert ts == sorted(ts), list(zip(names, ts))

    def test_cluster_lifecycle_includes_raft_hops(self, tmp_path):
        """One-broker cluster: the sampled span additionally carries
        admission, actor-enqueue and the raft queue/fsync/commit hops."""
        from zeebe_tpu.testing.chaos import ChaosHarness

        tracer = tracing.install(
            tracing.RecordTracer(sample_rate=1.0, seed=3)
        )
        harness = ChaosHarness(str(tmp_path / "cluster"), n_brokers=1)
        client = None
        try:
            harness.await_leaders()
            client = harness.client()
            client.deploy_model(order_model())
            worker = client.open_job_worker(
                "payment-service", lambda pid, rec: {"paid": True}
            )
            client.create_instance(
                "order-process", {"orderId": 1}, partition_id=0
            )
            import time

            deadline = time.monotonic() + 20
            want = {
                tracing.GATEWAY_RECV, tracing.ADMISSION,
                tracing.ACTOR_ENQUEUE, tracing.RAFT_QUEUE,
                tracing.RAFT_FSYNC, tracing.COMMIT, tracing.FEED_TAKE,
                tracing.WAVE_DISPATCH, tracing.APPLY, tracing.RESPONSE,
            }
            full = None
            while time.monotonic() < deadline and full is None:
                for span in tracer.spans():
                    if want.issubset(set(span.stage_names())):
                        full = span
                        break
                time.sleep(0.1)
            assert full is not None, [
                (s.trace_id, s.stage_names()) for s in tracer.spans()
            ]
            ts = [t for _n, t, _f in full.stages]
            assert ts == sorted(ts)
            worker.close()
        finally:
            if client is not None:
                client.close()
            harness.close()
            tracing.install(None)


    def test_scheduler_collect_stamps_device_collect_before_apply(
        self, tracer
    ):
        """The pipelined scheduler feed must order DEVICE_COLLECT before
        APPLY — a span's apply->device_collect gap would otherwise
        contain the apply work."""
        from types import SimpleNamespace

        from zeebe_tpu.runtime.cluster_broker import PartitionServer

        span = tracer.maybe_sample(0)
        tracer.bind_position(span, 0, 7, committed=True)

        stub = SimpleNamespace(partition_id=0, device_index=3)
        stub.engine = SimpleNamespace(collect_wave=lambda pending: [])

        def apply_chunk(records, merged, clock=None):
            # the real _apply_chunk stamps APPLY at its top
            tracer.stamp_positions(
                0, tracing.positions_of(records), tracing.APPLY
            )

        stub._apply_chunk = apply_chunk
        pending = SimpleNamespace(
            records=[SimpleNamespace(position=7)],
            host_seconds=0.0, device_seconds=0.0,
        )
        host_s, device_s = PartitionServer.collect(stub, pending)
        assert (host_s, device_s) == (0.0, 0.0)
        names = span.stage_names()
        assert tracing.DEVICE_COLLECT in names and tracing.APPLY in names
        assert names.index(tracing.DEVICE_COLLECT) < names.index(
            tracing.APPLY
        )
        fields = {n: f for n, _t, f in span.stages}
        assert fields[tracing.DEVICE_COLLECT]["device"] == 3


class TestDeterministicSampling:
    def test_same_seed_same_schedule(self):
        a = tracing.RecordTracer(sample_rate=0.31, seed=9)
        b = tracing.RecordTracer(sample_rate=0.31, seed=9)
        picks_a = [a.maybe_sample(0) is not None for _ in range(500)]
        picks_b = [b.maybe_sample(0) is not None for _ in range(500)]
        assert picks_a == picks_b
        assert abs(sum(picks_a) - 155) <= 2  # accumulator tracks the rate

    def test_different_seed_different_phase(self):
        picks = {}
        for seed in (1, 2, 3, 4, 5, 6):
            t = tracing.RecordTracer(sample_rate=0.5, seed=seed)
            picks[seed] = tuple(
                t.maybe_sample(0) is not None for _ in range(40)
            )
        assert len(set(picks.values())) > 1  # the seed shifts the phase

    def test_rate_one_samples_everything_rate_zero_nothing(self):
        t1 = tracing.RecordTracer(sample_rate=1.0)
        assert all(t1.maybe_sample(0) is not None for _ in range(50))
        t0 = tracing.RecordTracer(sample_rate=0.0)
        assert all(t0.maybe_sample(0) is None for _ in range(50))

    def test_partitions_sample_independently(self):
        t = tracing.RecordTracer(sample_rate=0.25, seed=7)
        for _ in range(100):
            t.maybe_sample(0)
        before = [t.maybe_sample(1) is not None for _ in range(100)]
        fresh = tracing.RecordTracer(sample_rate=0.25, seed=7)
        alone = [fresh.maybe_sample(1) is not None for _ in range(100)]
        assert before == alone  # partition 0 traffic cannot shift p1


class TestSpanBudget:
    def test_overflow_evicts_oldest_to_finished(self):
        t = tracing.RecordTracer(sample_rate=1.0, per_partition_budget=8)
        spans = [t.maybe_sample(0) for _ in range(20)]
        stats = t.stats()
        assert stats["live"] == 8
        assert stats["dropped"] == 12
        # the oldest spans were evicted (finished), newest are live
        live_ids = {
            s.trace_id for s in t.spans() if not s.finished
        }
        assert live_ids == {s.trace_id for s in spans[-8:]}
        # eviction drops the position index entries too
        t2 = tracing.RecordTracer(sample_rate=1.0, per_partition_budget=2)
        s1 = t2.maybe_sample(0)
        t2.bind_position(s1, 0, 10, committed=True)
        assert (0, 10) in t2.by_position
        t2.maybe_sample(0)
        t2.maybe_sample(0)  # budget 2: s1 evicts here
        assert s1.finished
        assert (0, 10) not in t2.by_position

    def test_leadership_uninstall_orphans_live_spans(self):
        """A step-down strands the partition's live spans on this node
        (drain/apply/response/export are leader-side): the uninstall
        sweep must finish them, or they pin every per-record stamp path
        hot until budget eviction."""
        t = tracing.RecordTracer(sample_rate=1.0)
        s1 = t.maybe_sample(0)
        t.bind_position(s1, 0, 4, committed=True)
        other = t.maybe_sample(1)
        t.bind_position(other, 1, 4, committed=True)
        t.finish_partition_spans(0, "leader uninstalled")
        assert s1.finished
        assert "orphaned" in s1.stage_names()
        assert not other.finished  # other partitions untouched
        assert (0, 4) not in t.by_position

    def test_truncation_finishes_bound_spans(self):
        """A new leader truncating the log from P must finish every span
        bound at >= P: those positions get REUSED, and a later commit
        covering them must not stamp COMMIT onto a command that failed."""
        t = tracing.RecordTracer(sample_rate=1.0)
        spans = []
        for pos in (5, 6, 7):
            s = t.maybe_sample(0)
            t.bind_position(s, 0, pos)  # awaiting commit
            spans.append(s)
        t.truncate_positions_from(0, 6)
        assert not spans[0].finished
        assert spans[1].finished and spans[2].finished
        assert "truncated" in spans[1].stage_names()
        t.on_commit(0, 10)  # covers the reused positions
        assert tracing.COMMIT in spans[0].stage_names()
        assert tracing.COMMIT not in spans[1].stage_names()
        assert tracing.COMMIT not in spans[2].stage_names()


class TestFlightRecorder:
    def test_ring_overflow_wraparound(self):
        ring = FlightRecorder(capacity=64)
        for i in range(200):
            ring.record("test", f"event-{i}", i=i)
        events = ring.snapshot()
        assert len(events) == 64
        # oldest dropped, newest kept, order preserved
        seqs = [e["seq"] for e in events]
        assert seqs == sorted(seqs)
        assert seqs[0] == 200 - 64 and seqs[-1] == 199
        assert events[-1]["msg"] == "event-199"

    def test_dump_and_read_back(self, tmp_path):
        ring = FlightRecorder(capacity=32)
        for i in range(10):
            ring.record("raft", "state -> leader", term=i)
        path = ring.dump(
            path=str(tmp_path / "flight.jsonl"), reason="unit-test"
        )
        events = read_flight_dump(path)
        assert len(events) == 10
        assert events[0]["cat"] == "raft"
        assert events[3]["fields"]["term"] == 3

    def test_invariant_failure_dumps_to_disk(self, tmp_path, monkeypatch):
        from zeebe_tpu.testing import chaos
        from zeebe_tpu.tracing.recorder import FLIGHT

        monkeypatch.setenv("ZB_FLIGHT_DIR", str(tmp_path))
        FLIGHT.record("test", "before the failure", marker=1)
        chaos.invariant(True, "fine")  # no dump on success
        assert not [p for p in os.listdir(tmp_path) if "flight" in p]
        with pytest.raises(AssertionError) as err:
            chaos.invariant(False, "injected invariant failure")
        msg = str(err.value)
        assert "injected invariant failure" in msg
        assert "flight recorder dump:" in msg
        dump_path = msg.split("flight recorder dump: ")[1].split("]")[0]
        events = read_flight_dump(dump_path)
        assert any(e["msg"] == "before the failure" for e in events)

    def test_slice_formatting(self):
        ring = FlightRecorder(capacity=32)
        ring.record("scheduler", "backpressure skip", partition=2)
        text = ring.format_slice(last=5)
        assert "backpressure skip" in text and "'partition': 2" in text

    def test_rate_limited_events_cannot_wrap_the_ring(self):
        """Per-record-rate events (admission sheds, mesh fallbacks) must
        not evict the control-plane history: within the window only ONE
        ring entry lands, and the next one carries the suppressed count."""
        from zeebe_tpu.tracing import recorder
        from zeebe_tpu.tracing.recorder import RateLimitedEvent

        before = next(recorder.FLIGHT._seq)
        ev = RateLimitedEvent("admission", "command shed", interval_s=60.0)
        for _ in range(1000):
            ev.record(reason="queue_depth", depth=9)
        ev._last_t = 0.0  # window elapsed
        ev.record(reason="queue_depth", depth=9)
        after = next(recorder.FLIGHT._seq)
        assert after - before - 1 == 2  # one per window, not 1001
        shed = [
            e for e in recorder.FLIGHT.snapshot()
            if e["msg"] == "command shed" and e["seq"] > before
        ]
        assert shed[-1]["fields"]["suppressed_in_window"] == 999


class TestDisabledFastPath:
    def test_no_tracer_no_spans_no_allocation(self, tmp_path):
        """With the tracer explicitly uninstalled the hot paths must not
        allocate spans, wave timelines, or sampling state — and a broker
        boot must NOT silently re-install a default tracer (the sticky
        uninstall the ≤2% overhead gate's OFF leg rests on)."""
        tracing.install(None)
        probe = tracing.RecordTracer(sample_rate=1.0)
        # a probe tracer NOT installed must stay untouched by a workload
        _run_traced_workload(str(tmp_path / "off"), exporters=False)
        assert tracing.TRACER is None  # Broker boot respected the off
        assert probe.stats() == {
            "sampled": 0, "dropped": 0, "live": 0, "finished": 0,
        }
        assert not probe.waves.snapshot()
        assert not probe._acc  # sampling state never consulted

    def test_disabled_config_uninstalls(self):
        from zeebe_tpu.runtime.config import TracingCfg

        tracing.install(tracing.RecordTracer())
        cfg = TracingCfg(enabled=False)
        assert tracing.ensure_tracer(cfg) is None
        assert tracing.TRACER is None

    def test_stamp_sites_guard_on_empty_index(self):
        """stamp_positions with no live spans is one truthiness check."""
        t = tracing.RecordTracer(sample_rate=0.0)
        assert not t.tracking()
        t.stamp_positions(0, range(512), tracing.APPLY)  # no-op, no error
        assert t.stats()["sampled"] == 0


class TestDumpAndReport:
    def test_dump_converts_to_chrome_trace(self, tracer, tmp_path):
        import importlib
        import sys

        sys.path.insert(
            0,
            os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools"),
        )
        try:
            trace_report = importlib.import_module("trace_report")
        finally:
            sys.path.pop(0)
        _run_traced_workload(str(tmp_path / "dump"))
        dump_path = str(tmp_path / "dump.json")
        tracer.dump(dump_path)
        with open(dump_path) as f:
            doc = json.load(f)
        assert doc["format"] == "zeebe-tpu-trace-v1"
        assert doc["spans"] and doc["waves"]
        chrome = trace_report.convert(doc)
        events = chrome["traceEvents"]
        assert any(e["pid"] == "records" and e["ph"] == "X" for e in events)
        assert any(e["pid"] == "devices" for e in events)
        assert all(e["dur"] >= 0 for e in events if e["ph"] == "X")
