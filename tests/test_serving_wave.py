"""Pipelined/batched serving plane: parity + group-commit recovery.

The wave drain (runtime/broker.run_until_idle, cluster drain chunks) and
the raft group commit are PERF changes — the log is the contract, so each
is pinned against the unbatched baseline:

- the wave-drained broker produces a BIT-IDENTICAL log to record-at-a-time
  processing (wave_size=1), for both the host oracle and the device
  engine (CPU backend), and the committed log replays deterministically
  through the chaos plane's ``replay_oracle``;
- a crash mid-batch-append (group commit writes many frames in one block)
  recovers to a whole-record boundary and loses nothing that was flushed
  before the torn batch;
- concurrent ``raft.append`` calls coalesce into one log append + one
  fsync, in call order, with every future observing its own records.
"""

import threading
import time

import pytest

from zeebe_tpu.gateway import JobWorker, ZeebeClient
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.protocol import codec
from zeebe_tpu.protocol.records import Record, WorkflowInstanceRecord
from zeebe_tpu.runtime import Broker, ControlledClock
from zeebe_tpu.testing.chaos import (
    DiskFaults,
    oracle_state_bytes,
    replay_oracle,
)


def order_model():
    return (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )


def xor_model():
    builder = (
        Bpmn.create_process("xor-process")
        .start_event("start")
        .exclusive_gateway("split")
    )
    builder.branch("$.orderValue > 50").service_task(
        "big", type="payment-service"
    ).end_event("end-big")
    builder.branch(default=True).service_task(
        "small", type="payment-service"
    ).end_event("end-small")
    return builder.done()


def _run_workload(data_dir, wave_size, engine_factory=None):
    """One deterministic serving workload; returns the committed records
    and the encoded frame bytes (the bit-identity witness)."""
    import itertools

    from zeebe_tpu.gateway import workers as workers_mod

    # process-global subscriber-key counter: reset so both runs of a
    # comparison see identical subscriber keys in their logs
    workers_mod._subscriber_keys = itertools.count(1)
    clock = ControlledClock(start_ms=1_000_000)
    if engine_factory is not None:
        broker = Broker(
            num_partitions=1, data_dir=data_dir, clock=clock,
            engine_factory=engine_factory(clock),
        )
    else:
        broker = Broker(num_partitions=1, data_dir=data_dir, clock=clock)
    broker.wave_size = wave_size
    try:
        client = ZeebeClient(broker)
        client.deploy_model(order_model())
        client.deploy_model(xor_model())
        JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
        for i in range(20):
            client.create_instance("order-process", {"orderId": i})
        for i in range(10):
            client.create_instance(
                "xor-process", {"orderValue": 10 + 10 * i}
            )
        # exercise the timer/deadline path inside the same log
        clock.advance(1_000)
        broker.tick()
        broker.run_until_idle()
        records = broker.records(0)
        frames = [codec.encode_record(r) for r in records]
        return records, frames
    finally:
        broker.close()


class TestWaveDrainParity:
    def test_host_engine_log_bit_identical_to_record_at_a_time(self, tmp_path):
        records_wave, frames_wave = _run_workload(str(tmp_path / "wave"), 256)
        records_one, frames_one = _run_workload(str(tmp_path / "one"), 1)
        assert len(frames_wave) > 100
        assert frames_wave == frames_one
        # and the committed sequence replays deterministically: two
        # independent oracle replays agree bit-for-bit, and the wave log
        # replays to the same state as the unbatched log
        assert oracle_state_bytes(replay_oracle(records_wave)) == (
            oracle_state_bytes(replay_oracle(records_one))
        )

    def test_device_engine_log_bit_identical_to_record_at_a_time(self, tmp_path):
        from zeebe_tpu.engine.interpreter import WorkflowRepository
        from zeebe_tpu.tpu import TpuPartitionEngine

        def factory(clock):
            repo = WorkflowRepository()
            return lambda pid: TpuPartitionEngine(
                pid, 1, repository=repo, clock=clock
            )

        _, frames_wave = _run_workload(
            str(tmp_path / "wave"), 256, engine_factory=factory
        )
        _, frames_one = _run_workload(
            str(tmp_path / "one"), 1, engine_factory=factory
        )
        assert len(frames_wave) > 100
        assert frames_wave == frames_one

    def test_pure_wave_drain_materializes_zero_rows(self, tmp_path):
        """The columnar-plane proof metric: a pure host wave drain —
        client commands → codec → append → interpreter wave → exporter
        egress → responses — materializes ZERO lazy rows from columnar
        views (``serving_rows_materialized_total``). Rows on this path
        are engine-built ``Record`` objects; only a columnar batch whose
        rows were never Records (device readback) may count."""
        import os

        from zeebe_tpu.exporter import InMemoryExporter
        from zeebe_tpu.gateway import workers as workers_mod
        from zeebe_tpu.protocol.columnar import rows_materialized_total
        from zeebe_tpu.runtime.config import ExporterCfg
        import itertools

        InMemoryExporter.reset()
        workers_mod._subscriber_keys = itertools.count(1)
        clock = ControlledClock(start_ms=1_000_000)
        audit_dir = os.path.join(str(tmp_path), "audit")
        broker = Broker(
            num_partitions=1, data_dir=str(tmp_path / "d"), clock=clock,
            exporters=[
                ExporterCfg(id="audit", type="jsonl",
                            args={"path": audit_dir}),
                ExporterCfg(id="metrics", type="metrics", args={}),
            ],
        )
        broker.wave_size = 256
        before = rows_materialized_total()
        try:
            client = ZeebeClient(broker)
            client.deploy_model(order_model())
            JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
            for i in range(16):
                client.create_instance("order-process", {"orderId": i})
            clock.advance(1_000)
            broker.tick()
            broker.run_until_idle()
        finally:
            broker.close()
        assert rows_materialized_total() - before == 0
        InMemoryExporter.reset()

    def test_wave_metrics_observed(self, tmp_path):
        from zeebe_tpu.runtime.metrics import GLOBAL_REGISTRY

        waves = GLOBAL_REGISTRY.counter("serving_waves_total")
        recs = GLOBAL_REGISTRY.counter("serving_wave_records_total")
        w0, r0 = waves.value, recs.value
        _, frames = _run_workload(str(tmp_path / "m"), 256)
        assert waves.value > w0
        assert recs.value - r0 >= len(frames)
        # the gauges render on the global registry (the /metrics surface)
        text = GLOBAL_REGISTRY.dump()
        assert "zb_serving_wave_fill" in text
        assert "zb_serving_wave_records_total" in text
        assert "zb_serving_host_seconds_total" in text


class TestGroupCommit:
    def _single_raft(self, tmp_path):
        from zeebe_tpu.cluster.raft import Raft, RaftConfig, RaftState
        from zeebe_tpu.log import LogStream, SegmentedLogStorage
        from zeebe_tpu.runtime.actors import ActorScheduler

        scheduler = ActorScheduler(cpu_threads=2, io_threads=2).start()
        storage = SegmentedLogStorage(str(tmp_path / "log"))
        log = LogStream(storage, recover_commit=False)
        raft = Raft(
            "n0", log, scheduler,
            config=RaftConfig(
                heartbeat_interval_ms=50, election_timeout_ms=100,
                election_jitter_ms=50,
            ),
            storage_path=str(tmp_path / "raft.meta"),
        )
        raft.bootstrap({"n0": raft.address})
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and raft.state != RaftState.LEADER:
            time.sleep(0.01)
        assert raft.state == RaftState.LEADER
        return raft, log, storage, scheduler

    @staticmethod
    def _command(i):
        from zeebe_tpu.protocol.enums import RecordType, ValueType
        from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI
        from zeebe_tpu.protocol.metadata import RecordMetadata

        return Record(
            key=i,
            metadata=RecordMetadata(
                record_type=RecordType.COMMAND,
                value_type=ValueType.WORKFLOW_INSTANCE,
                intent=int(WI.CREATE),
            ),
            value=WorkflowInstanceRecord(
                bpmn_process_id="p", payload={"i": i}
            ),
        )

    def test_concurrent_appends_coalesce_in_order(self, tmp_path):
        from zeebe_tpu.runtime.metrics import event_count

        raft, log, storage, scheduler = self._single_raft(tmp_path)
        try:
            fsyncs_before = event_count("log_fsyncs")
            coalesced_before = event_count("log_group_commit_coalesced")
            # wedge the raft actor so every append queues behind one drain
            gate = threading.Event()
            raft.actor.run(lambda: gate.wait(5))
            futures = [raft.append([self._command(i)]) for i in range(16)]
            gate.set()
            positions = [f.join(10) for f in futures]
            # call order == log order, and every future saw its own record
            assert positions == sorted(positions)
            got = [log.record_at(p).key for p in positions]
            assert got == list(range(16))
            # the burst shared fsyncs: strictly fewer syncs than appends
            assert event_count("log_group_commit_coalesced") > coalesced_before
            assert (
                event_count("log_fsyncs") - fsyncs_before
                < len(futures)
            )
        finally:
            raft.close()
            storage.close()
            scheduler.stop()

    def test_torn_mid_batch_append_recovers_to_record_boundary(self, tmp_path):
        """Group commit writes many frames in one storage block; a crash
        mid-write must recover every whole record and lose only the torn
        frame — acked (flushed) batches survive untouched."""
        from zeebe_tpu.log import LogStream, SegmentedLogStorage

        d = str(tmp_path / "log")
        storage = SegmentedLogStorage(d)
        log = LogStream(storage)
        acked = [self._command(i) for i in range(8)]
        log.append(acked)
        log.flush()  # the acked group
        tail = [self._command(100 + i) for i in range(8)]
        log.append(tail)  # crash before this batch's flush
        storage.close()
        # tear into the LAST frame of the unflushed batch (partial write)
        DiskFaults.tear_log_tail(d, nbytes=5)

        storage2 = SegmentedLogStorage(d)
        log2 = LogStream(storage2)
        recovered = list(log2.reader(0))
        # every surviving record is whole; the acked batch is intact
        assert [r.key for r in recovered[:8]] == list(range(8))
        assert len(recovered) == 15  # 16 written, exactly the torn one lost
        assert [r.key for r in recovered[8:]] == [100 + i for i in range(7)]
        # appends resume cleanly at the recovered boundary
        log2.append([self._command(999)])
        assert list(log2.reader(0))[-1].key == 999
        storage2.close()
