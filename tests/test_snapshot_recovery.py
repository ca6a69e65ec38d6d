"""Snapshot storage + broker restart/recovery tests.

Reference parity: ``qa/integration-tests/.../BrokerReprocessingTest`` (restart
the broker, state is rebuilt by replay, workflows continue), plus
``FsSnapshotStorage``/``StateSnapshotController`` unit behavior (checksums,
commit-rename, stale-snapshot validation against the log).
"""

import os
import pickle

import pytest

from zeebe_tpu.gateway import JobWorker, ZeebeClient
from zeebe_tpu.log.snapshot import (
    SnapshotController,
    SnapshotMetadata,
    SnapshotStorage,
)
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import (
    JobIntent,
    MessageIntent,
    WorkflowInstanceIntent as WI,
)
from zeebe_tpu.runtime import Broker, ControlledClock


def order_process_model():
    return (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )


def wi_events(broker, partition=0):
    return [
        (WI(r.metadata.intent).name, r.value.activity_id)
        for r in broker.records(partition)
        if r.metadata.value_type == ValueType.WORKFLOW_INSTANCE
        and r.metadata.record_type == RecordType.EVENT
    ]


# ---------------------------------------------------------------------------
# snapshot storage unit tests
# ---------------------------------------------------------------------------


class TestSnapshotStorage:
    def test_write_read_roundtrip(self, tmp_path):
        storage = SnapshotStorage(str(tmp_path))
        meta = SnapshotMetadata(10, 12, 1)
        storage.write(meta, b"hello-state")
        assert storage.list() == [meta]
        assert storage.read(meta) == b"hello-state"

    def test_newest_first_ordering(self, tmp_path):
        storage = SnapshotStorage(str(tmp_path))
        for pos in (5, 20, 10):
            storage.write(SnapshotMetadata(pos, pos + 1, 0), b"x")
        assert [m.last_processed_position for m in storage.list()] == [20, 10, 5]

    def test_corrupt_payload_rejected(self, tmp_path):
        storage = SnapshotStorage(str(tmp_path))
        meta = SnapshotMetadata(3, 4, 0)
        storage.write(meta, b"good")
        with open(os.path.join(str(tmp_path), meta.dirname, "state.bin"), "wb") as f:
            f.write(b"evil")
        assert storage.read(meta) is None

    def test_torn_tmp_dir_swept_on_open(self, tmp_path):
        os.makedirs(tmp_path / "snapshot_1_2_0.tmp")
        storage = SnapshotStorage(str(tmp_path))
        assert storage.list() == []
        assert not (tmp_path / "snapshot_1_2_0.tmp").exists()

    def test_purge_older(self, tmp_path):
        storage = SnapshotStorage(str(tmp_path))
        old = SnapshotMetadata(5, 6, 0)
        new = SnapshotMetadata(9, 11, 0)
        storage.write(old, b"a")
        storage.write(new, b"b")
        storage.purge_older_than(new)
        assert storage.list() == [new]

    def test_controller_skips_snapshot_ahead_of_log(self, tmp_path):
        """A snapshot whose written position exceeds the log end is stale
        (log truncated/diverged) — recovery falls back to an older one."""
        controller = SnapshotController(SnapshotStorage(str(tmp_path)))
        controller.take({"v": 1}, SnapshotMetadata(5, 6, 0))
        # take() purges older snapshots, so write the newer one directly
        controller.storage.write(
            SnapshotMetadata(50, 60, 0), pickle.dumps({"v": 2})
        )
        state, meta = controller.recover(log_last_position=10)
        assert state == {"v": 1}
        assert meta.last_processed_position == 5

    def test_controller_skips_corrupt_falls_back(self, tmp_path):
        controller = SnapshotController(SnapshotStorage(str(tmp_path)))
        controller.take({"v": 1}, SnapshotMetadata(5, 6, 0))
        bad = SnapshotMetadata(9, 9, 0)
        controller.storage.write(bad, b"not-a-pickle")
        with open(
            os.path.join(str(tmp_path), bad.dirname, "checksum.crc32"), "w"
        ) as f:
            f.write("0")
        state, meta = controller.recover(log_last_position=100)
        assert state == {"v": 1}

    def test_recover_empty(self, tmp_path):
        controller = SnapshotController(SnapshotStorage(str(tmp_path)))
        assert controller.recover(100) == (None, None)


# ---------------------------------------------------------------------------
# incremental checkpoints (content-addressed segment store)
# ---------------------------------------------------------------------------


def _device_like_state(**overrides):
    """A device-engine-shaped snapshot state (SoA tables as arrays)."""
    import numpy as np

    from zeebe_tpu.log import stateser

    arrays = {
        "instances.state": np.zeros((4096,), np.int32),
        "instances.elem": np.full((4096,), -1, np.int32),
        "payload": np.zeros((4096, 64), np.float32),
        "jobs.keys": np.full((1024,), -1, np.int64),
    }
    arrays.update(overrides)
    return {
        "fmt": stateser.FORMAT_DEVICE_V1,
        "arrays": arrays,
        "meta": {"last_processed_position": 7},
        "host": None,
    }


class TestIncrementalCheckpoints:
    """VERDICT round-3 #6: checkpoints keyed by (processed, written, term)
    whose write cost tracks CHANGED state, not total state size (reference
    StateSnapshotController: RocksDB checkpoints share unchanged SSTs)."""

    def test_unchanged_tables_are_not_rewritten(self, tmp_path):
        import numpy as np

        controller = SnapshotController(SnapshotStorage(str(tmp_path)))
        state = _device_like_state()
        controller.take(state, SnapshotMetadata(10, 12, 1))
        first = dict(controller.last_take_stats)
        assert first["new_bytes"] == first["total_bytes"]

        # mutate ONE small table; the big payload matrix is untouched
        state2 = _device_like_state(
            **{"instances.state": np.ones((4096,), np.int32)}
        )
        controller.take(state2, SnapshotMetadata(20, 22, 1))
        second = dict(controller.last_take_stats)
        assert second["total_bytes"] == first["total_bytes"]
        # incremental cost ≈ the changed table + the small root part
        assert second["new_bytes"] < first["total_bytes"] // 4
        assert second["new_segments"] < second["parts"]

        state_r, meta = controller.recover(log_last_position=100)
        assert meta == SnapshotMetadata(20, 22, 1)
        assert (state_r["arrays"]["instances.state"] == 1).all()
        assert (state_r["arrays"]["payload"] == 0).all()

    def test_identical_checkpoint_costs_near_zero(self, tmp_path):
        controller = SnapshotController(SnapshotStorage(str(tmp_path)))
        controller.take(_device_like_state(), SnapshotMetadata(10, 12, 1))
        controller.take(_device_like_state(), SnapshotMetadata(20, 22, 1))
        assert controller.last_take_stats["new_bytes"] == 0
        assert controller.last_take_stats["new_segments"] == 0

    def test_missing_segment_falls_back_to_older(self, tmp_path):
        from zeebe_tpu.log import snapshot as snapmod
        from zeebe_tpu.log import stateser

        storage = SnapshotStorage(str(tmp_path))
        controller = SnapshotController(storage)
        # write directly (take() would purge the older snapshot)
        storage.write_parts(
            SnapshotMetadata(5, 6, 0),
            stateser.encode_state_parts({"v": 1}),
        )
        storage.write_parts(
            SnapshotMetadata(9, 11, 0),
            stateser.encode_state_parts({"v": 2}),
        )
        # corrupt the NEWER snapshot by deleting a segment unique to it
        newer = storage.manifest(SnapshotMetadata(9, 11, 0))
        older = {e["h"] for e in storage.manifest(SnapshotMetadata(5, 6, 0))}
        unique = [e for e in newer if e["h"] not in older]
        assert unique, "distinct states must produce distinct segments"
        os.unlink(os.path.join(
            str(tmp_path), snapmod._SEGMENTS_DIR, unique[0]["h"] + ".seg"
        ))
        state, meta = controller.recover(log_last_position=100)
        assert state == {"v": 1}
        assert meta == SnapshotMetadata(5, 6, 0)

    def test_purge_gcs_unreferenced_segments(self, tmp_path, monkeypatch):
        from zeebe_tpu.log import snapshot as snapmod

        monkeypatch.setattr(snapmod, "_SEGMENT_GC_GRACE_SEC", 0.0)
        controller = SnapshotController(SnapshotStorage(str(tmp_path)))
        controller.take({"v": 1}, SnapshotMetadata(5, 6, 0))
        controller.take({"v": 2}, SnapshotMetadata(9, 11, 0))
        seg_dir = os.path.join(str(tmp_path), snapmod._SEGMENTS_DIR)
        live = {e["h"] + ".seg"
                for e in controller.storage.manifest(SnapshotMetadata(9, 11, 0))}
        assert set(os.listdir(seg_dir)) == live
        state, _ = controller.recover(log_last_position=100)
        assert state == {"v": 2}

    def test_legacy_single_blob_snapshot_still_recovers(self, tmp_path):
        from zeebe_tpu.log import stateser

        storage = SnapshotStorage(str(tmp_path))
        meta = SnapshotMetadata(10, 12, 1)
        storage.write(meta, stateser.encode_state({"v": 42}))
        controller = SnapshotController(storage)
        state, got = controller.recover(log_last_position=100)
        assert state == {"v": 42}
        assert got == meta


# ---------------------------------------------------------------------------
# broker restart / replay tests
# ---------------------------------------------------------------------------


class TestBrokerRecovery:
    def _restart(self, broker, data_dir, clock):
        broker.close()
        return Broker(
            num_partitions=len(broker.partitions), data_dir=data_dir, clock=clock
        )

    def test_restart_resumes_mid_workflow(self, tmp_path):
        """Create an instance, restart before the job completes, then complete
        it on the restarted broker — the instance finishes (replay rebuilt
        element-instance + job state)."""
        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")
        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        client = ZeebeClient(broker)
        client.deploy_model(order_process_model())
        client.create_instance("order-process", payload={"orderId": 1})
        broker.run_until_idle()
        assert ("ELEMENT_ACTIVATED", "collect-money") in wi_events(broker)

        broker = self._restart(broker, data, clock)
        client = ZeebeClient(broker)
        worker = JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
        broker.run_until_idle()
        assert ("ELEMENT_COMPLETED", "order-process") in wi_events(broker)
        assert len(worker.handled) == 1
        broker.close()

    def test_restart_preserves_deployments_and_versions(self, tmp_path):
        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")
        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        client = ZeebeClient(broker)
        client.deploy_model(order_process_model())
        client.deploy_model(order_process_model())  # version 2

        broker = self._restart(broker, data, clock)
        client = ZeebeClient(broker)
        JobWorker(broker, "payment-service", lambda ctx: None)
        result = client.create_instance("order-process")
        assert result.version == 2
        broker.run_until_idle()
        assert ("ELEMENT_COMPLETED", "order-process") in wi_events(broker)
        broker.close()

    def test_replay_rebuilds_identical_state(self, tmp_path):
        """Replay parity: restarting from the log alone reproduces the exact
        engine state of the live run (the correctness contract of SURVEY.md
        §5 — deterministic processing is what makes snapshots optional)."""
        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")
        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        client = ZeebeClient(broker)
        client.deploy_model(order_process_model())
        client.create_instance("order-process", payload={"orderId": 7})
        client.create_instance("order-process", payload={"orderId": 8})
        broker.run_until_idle()
        live = broker.partitions[0].engine.snapshot_state()

        broker = self._restart(broker, data, clock)
        # replay stops at the last source event position; the tail records
        # (no follow-ups of their own) are handled by the normal loop — run
        # to quiescence before comparing, and require that doing so appends
        # nothing new (no duplicated side effects)
        n_records = len(broker.records(0))
        broker.run_until_idle()
        assert len(broker.records(0)) == n_records
        replayed = broker.partitions[0].engine.snapshot_state()
        assert sorted(replayed["jobs"]) == sorted(live["jobs"])
        assert sorted(replayed["element_instances"].instances) == sorted(
            live["element_instances"].instances
        )
        assert replayed["wf_keys"].peek == live["wf_keys"].peek
        assert replayed["job_keys"].peek == live["job_keys"].peek
        assert replayed["last_processed_position"] == live["last_processed_position"]
        for key, job in live["jobs"].items():
            assert replayed["jobs"][key].state == job.state
            assert replayed["jobs"][key].deadline == job.deadline
        broker.close()

    def test_crash_between_append_and_process_still_executes_command(self, tmp_path):
        """A command appended to the log but never processed (crash right
        after append) must be processed after restart — replay only covers
        records whose follow-ups are already in the log, the tail runs
        through the normal loop with effects."""
        from zeebe_tpu.protocol.intents import WorkflowInstanceIntent
        from zeebe_tpu.protocol.records import WorkflowInstanceRecord

        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")
        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        client = ZeebeClient(broker)
        client.deploy_model(order_process_model())
        broker.run_until_idle()
        # append the CREATE command without giving the loop a chance to run
        broker.write_command(
            0,
            WorkflowInstanceRecord(bpmn_process_id="order-process", payload={}),
            WorkflowInstanceIntent.CREATE,
            with_response=False,
        )
        broker = self._restart(broker, data, clock)
        worker = JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
        broker.run_until_idle()
        intents = [
            int(r.metadata.intent)
            for r in broker.records(0)
            if r.metadata.value_type == ValueType.WORKFLOW_INSTANCE
        ]
        assert int(WorkflowInstanceIntent.ELEMENT_COMPLETED) in intents
        broker.close()

    def test_snapshot_shortens_replay(self, tmp_path):
        """With a snapshot, recovery replays only the records after the
        snapshot position (reference: reprocessing starts at the snapshot's
        last-processed position)."""
        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")
        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        client = ZeebeClient(broker)
        client.deploy_model(order_process_model())
        client.create_instance("order-process")
        broker.run_until_idle()
        broker.snapshot()
        snap_position = broker.partitions[0].next_read_position - 1
        client.create_instance("order-process")
        broker.run_until_idle()
        broker.close()

        processed = []
        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        # the restored engine replayed only positions after the snapshot
        assert broker.partitions[0].engine.last_processed_position > snap_position
        # and the state includes BOTH instances (snapshot + replayed)
        keys = [
            i.value.workflow_instance_key
            for i in broker.partitions[0].engine.element_instances.instances.values()
        ]
        assert len(set(keys)) == 2
        broker.close()

    def test_restart_after_snapshot_only_no_tail(self, tmp_path):
        """Snapshot taken at the log end: recovery restores and replays
        nothing; processing continues seamlessly."""
        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")
        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        client = ZeebeClient(broker)
        client.deploy_model(order_process_model())
        client.create_instance("order-process")
        broker.run_until_idle()
        broker.snapshot()
        broker.close()

        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        client = ZeebeClient(broker)
        JobWorker(broker, "payment-service", lambda ctx: None)
        broker.run_until_idle()
        assert ("ELEMENT_COMPLETED", "order-process") in wi_events(broker)
        broker.close()

    def test_message_ttl_survives_restart_deterministically(self, tmp_path):
        """A published message's TTL deadline derives from the record
        timestamp, so a restarted broker expires it at the same absolute
        time the live broker would have."""
        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")
        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        client = ZeebeClient(broker)
        client.publish_message("order-shipped", "order-1", time_to_live_ms=5_000)
        broker.run_until_idle()
        live_deadline = next(
            iter(broker.partitions[0].engine.messages.values())
        ).deadline

        broker = self._restart(broker, data, clock)
        msg = next(iter(broker.partitions[0].engine.messages.values()))
        assert msg.deadline == live_deadline

        clock.advance(6_000)
        broker.tick()
        broker.run_until_idle()
        deleted = [
            r
            for r in broker.records(0)
            if r.metadata.value_type == ValueType.MESSAGE
            and r.metadata.record_type == RecordType.EVENT
            and r.metadata.intent == int(MessageIntent.DELETED)
        ]
        assert deleted
        assert not broker.partitions[0].engine.messages
        broker.close()

    def test_multi_partition_restart(self, tmp_path):
        """Cross-partition message correlation state survives restart on
        both the message partition and the workflow partition."""
        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")
        broker = Broker(num_partitions=3, data_dir=data, clock=clock)
        client = ZeebeClient(broker)
        model = (
            Bpmn.create_process("msg-process")
            .start_event("start")
            .message_catch_event(
                "wait", message_name="order-shipped", correlation_key="$.orderId"
            )
            .end_event("end")
            .done()
        )
        client.deploy_model(model)
        client.create_instance(
            "msg-process", payload={"orderId": "order-77"}, partition_id=1
        )
        broker.run_until_idle()

        broker = self._restart(broker, data, clock)
        client = ZeebeClient(broker)
        client.publish_message("order-shipped", "order-77", payload={"ok": 1})
        broker.run_until_idle()
        assert ("ELEMENT_COMPLETED", "msg-process") in wi_events(broker, 1)
        broker.close()


# ---------------------------------------------------------------------------
# device-engine (TPU) snapshot + replay recovery
# ---------------------------------------------------------------------------


class TestTpuEngineRecovery:
    """The device engine checkpoints its SoA tables (device_get -> the
    data-only device envelope, log/stateser.py) keyed by last-processed
    position, and recovers by restore + suppressed-side-effect replay —
    the same contract the reference's StateSnapshotController +
    StreamProcessorController recovery give RocksDB-backed processors."""

    def _tpu_broker(self, data, clock):
        from tests.conftest import make_tpu_broker

        return make_tpu_broker(data_dir=data, clock=clock)

    def test_restart_resumes_mid_workflow_with_snapshot(self, tmp_path):
        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")
        broker = self._tpu_broker(data, clock)
        client = ZeebeClient(broker)
        client.deploy_model(order_process_model())
        client.create_instance("order-process", payload={"orderId": 1})
        broker.run_until_idle()
        assert ("ELEMENT_ACTIVATED", "collect-money") in wi_events(broker)
        broker.snapshot()
        n_records = len(list(broker.records(0)))
        broker.close()

        broker = self._tpu_broker(data, clock)
        # replay must not duplicate side effects
        assert len(list(broker.records(0))) == n_records
        client = ZeebeClient(broker)
        worker = JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
        broker.run_until_idle()
        assert ("ELEMENT_COMPLETED", "order-process") in wi_events(broker)
        assert len(worker.handled) == 1
        broker.close()

    def test_kill_between_snapshots_replays_tail(self, tmp_path):
        """Snapshot early, keep processing, crash: recovery restores the
        snapshot then replays the committed tail to catch up."""
        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")
        broker = self._tpu_broker(data, clock)
        client = ZeebeClient(broker)
        client.deploy_model(order_process_model())
        client.create_instance("order-process", payload={"orderId": 1})
        broker.run_until_idle()
        broker.snapshot()
        # post-snapshot tail: a second instance + first job completes
        worker = JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
        client.create_instance("order-process", payload={"orderId": 2})
        broker.run_until_idle()
        assert len(worker.handled) == 2
        completed = [
            e for e in wi_events(broker) if e == ("ELEMENT_COMPLETED", "order-process")
        ]
        assert len(completed) == 2
        n_records = len(list(broker.records(0)))
        broker.close()  # "crash": snapshot is stale, tail must replay

        broker = self._tpu_broker(data, clock)
        assert len(list(broker.records(0))) == n_records
        client = ZeebeClient(broker)
        # a third instance runs end-to-end on the recovered engine
        worker = JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
        client.create_instance("order-process", payload={"orderId": 3})
        broker.run_until_idle()
        completed = [
            e for e in wi_events(broker) if e == ("ELEMENT_COMPLETED", "order-process")
        ]
        assert len(completed) == 3
        broker.close()

    def test_replay_only_restart_without_snapshot(self, tmp_path):
        clock = ControlledClock(start_ms=1_000_000)
        data = str(tmp_path / "data")
        broker = self._tpu_broker(data, clock)
        client = ZeebeClient(broker)
        client.deploy_model(order_process_model())
        client.create_instance("order-process", payload={"orderId": 7})
        broker.run_until_idle()
        broker.close()

        broker = self._tpu_broker(data, clock)
        client = ZeebeClient(broker)
        worker = JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
        broker.run_until_idle()
        assert len(worker.handled) == 1
        assert ("ELEMENT_COMPLETED", "order-process") in wi_events(broker)
        broker.close()

    def test_device_state_round_trips_exactly(self, tmp_path):
        """snapshot_state -> codec -> restore_state reproduces the SoA
        tables bit-for-bit (keys, payload matrices, hash maps, counters)."""
        import numpy as np

        from zeebe_tpu.log import stateser

        clock = ControlledClock(start_ms=1_000_000)
        broker = self._tpu_broker(str(tmp_path / "a"), clock)
        client = ZeebeClient(broker)
        client.deploy_model(order_process_model())
        client.create_instance("order-process", payload={"orderId": 1, "tag": "x"})
        broker.run_until_idle()
        engine = broker.partitions[0].engine
        snap = stateser.decode_state(
            stateser.encode_state(engine.snapshot_state())
        )

        restored = self._tpu_broker(str(tmp_path / "b"), clock)
        engine2 = restored.partitions[0].engine
        engine2.restore_state(snap)
        import dataclasses as dc

        import jax

        from zeebe_tpu.tpu import state as state_mod

        # ei/job lookup structures are DERIVED state (re-built from live
        # rows at restore — rebuild_lookup_state), so compare them after
        # normalizing both sides through the same derivation; everything
        # else must round-trip bit-for-bit
        norm_a = state_mod.rebuild_lookup_state(engine.state)
        norm_b = state_mod.rebuild_lookup_state(engine2.state)
        derived = {
            "ei_map", "ei_index", "job_map", "job_index",
            "free_ei", "free_ei_pop", "free_ei_push",
            "free_job", "free_job_pop", "free_job_push",
        }
        for f in dc.fields(engine.state):
            if f.name in derived:
                a, b = getattr(norm_a, f.name), getattr(norm_b, f.name)
            else:
                a, b = getattr(engine.state, f.name), getattr(engine2.state, f.name)
            if f.name.startswith("sub_"):
                continue  # transient worker subscriptions drop on restore
            # a leaf, or a hash map's three
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_array_equal(
                    np.asarray(la), np.asarray(lb), err_msg=f.name
                )
        assert engine2.interns._by_id == engine.interns._by_id
        assert engine2.meta.varspace.names == engine.meta.varspace.names
        broker.close()
        restored.close()
