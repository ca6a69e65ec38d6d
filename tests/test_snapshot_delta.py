"""Dirty-delta snapshots: family tracking, capture/commit fence, delta-vs-
full bit-identity, GC safety, and crash-mid-delta-commit invariants
(zeebe_tpu/log/{stateser,snapshot}.py, engine dirty tracking).

The two invariants the tentpole adds to the chaos contract:
5. a delta-chain snapshot restores BIT-IDENTICALLY to a from-scratch full
   take of the same state, and
6. a crash mid-delta-commit never orphans the previous snapshot's
   referenced segments (the previous snapshot stays fully restorable,
   even across the GC sweep).
"""

import dataclasses
import os
import time

import numpy as np
import pytest

from zeebe_tpu.gateway import JobWorker, ZeebeClient
from zeebe_tpu.log import stateser
from zeebe_tpu.log.snapshot import (
    SnapshotController,
    SnapshotMetadata,
    SnapshotStorage,
    _SEGMENTS_DIR,
    part_hash,
)
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.runtime import Broker, ControlledClock
from zeebe_tpu.runtime.metrics import event_count
from zeebe_tpu.testing.chaos import DiskFaults
from zeebe_tpu.tpu import state as state_mod


def order_process_model():
    return (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )


def _broker_with_traffic(tmp_path, n_instances=4):
    clock = ControlledClock(start_ms=1_000_000)
    data = str(tmp_path / "data")
    broker = Broker(num_partitions=1, data_dir=data, clock=clock)
    client = ZeebeClient(broker)
    client.deploy_model(order_process_model())
    JobWorker(broker, "payment-service", lambda ctx: {"paid": True})
    for i in range(n_instances):
        client.create_instance("order-process", payload={"orderId": i})
    broker.run_until_idle()
    return broker, client, clock


def _age_segments(root, by_sec=3600.0):
    """Backdate every segment file past the GC grace window."""
    seg_dir = os.path.join(root, _SEGMENTS_DIR)
    past = time.time() - by_sec
    for name in os.listdir(seg_dir):
        os.utime(os.path.join(seg_dir, name), (past, past))


# ---------------------------------------------------------------------------
# host-engine dirty tracking
# ---------------------------------------------------------------------------


class TestHostDirtyTracking:
    def test_second_take_with_no_traffic_is_free(self, tmp_path):
        """Acceptance pin: unchanged state between two takes → the second
        take re-encodes nothing but the tiny root and reports
        new_bytes == 0."""
        broker, _, _ = _broker_with_traffic(tmp_path)
        try:
            broker.snapshot()
            first = dict(broker.partitions[0].snapshots.last_take_stats)
            assert first["new_bytes"] > 0  # cold take is full
            assert first["reused_parts"] == 0

            broker.snapshot()
            second = dict(broker.partitions[0].snapshots.last_take_stats)
            assert second["new_bytes"] == 0
            assert second["new_segments"] == 0
            assert second["total_bytes"] == first["total_bytes"]
            # every family part was reused from the previous manifest —
            # only _root was re-encoded
            assert second["reused_parts"] == second["parts"] - 1
        finally:
            broker.close()

    def test_take_cost_scales_with_the_delta(self, tmp_path):
        """Under traffic between takes, new bytes track the CHANGED
        families, not total state."""
        broker, client, _ = _broker_with_traffic(tmp_path, n_instances=16)
        try:
            broker.snapshot()
            total = broker.partitions[0].snapshots.last_take_stats["total_bytes"]

            # small delta: one more instance through the same workflow
            client.create_instance("order-process", payload={"orderId": 99})
            broker.run_until_idle()
            broker.snapshot()
            stats = dict(broker.partitions[0].snapshots.last_take_stats)
            assert stats["reused_parts"] >= 1  # e.g. clean workflows family
            assert 0 < stats["new_bytes"] < stats["total_bytes"]
            assert stats["new_bytes"] < total
        finally:
            broker.close()

    def test_family_marking_is_selective(self, tmp_path):
        """A message publish dirties the messages family but not the (much
        larger) instance family."""
        broker, client, _ = _broker_with_traffic(tmp_path)
        try:
            engine = broker.partitions[0].engine
            engine.snapshot_mark_clean()
            assert engine.snapshot_dirty_families() == frozenset()
            client.publish_message(
                "some-event", "corr-1", {"x": 1}, time_to_live_ms=60_000
            )
            broker.run_until_idle()
            dirty = engine.snapshot_dirty_families()
            assert "h/messages" in dirty
            assert "h/control" in dirty
            assert "h/instances" not in dirty
            assert "h/workflows" not in dirty
        finally:
            broker.close()

    def test_unknown_value_type_marks_everything(self, tmp_path):
        broker, _, _ = _broker_with_traffic(tmp_path, n_instances=1)
        try:
            engine = broker.partitions[0].engine
            engine.snapshot_mark_clean()
            engine._mark_dirty_for_record(9999)
            assert engine.snapshot_dirty_families() is None
        finally:
            broker.close()

    def test_delta_chain_bit_identical_to_full_take(self, tmp_path):
        """Invariant 5 (unit form): after a chain of delta takes, the
        on-disk parts equal a freshly encoded FULL snapshot of the live
        engine, byte for byte."""
        broker, client, _ = _broker_with_traffic(tmp_path)
        try:
            broker.snapshot()  # full base
            for i in range(3):  # delta chain with varied traffic
                client.create_instance("order-process", payload={"orderId": 100 + i})
                if i == 1:
                    client.publish_message("evt", f"k{i}", {}, time_to_live_ms=5_000)
                broker.run_until_idle()
                broker.snapshot()
            assert broker.partitions[0].snapshots.last_take_stats["reused_parts"] > 0

            partition = broker.partitions[0]
            newest = partition.snapshots.storage.list()[0]
            on_disk = partition.snapshots.storage.read_parts(newest)
            fresh = dict(stateser.encode_state_parts(partition.engine.snapshot_state()))
            assert on_disk == fresh
        finally:
            broker.close()

    def test_incident_resolve_delta_equals_full(self, tmp_path):
        """Regression (review finding): incident RESOLVE re-writes the
        failure event through _write_wi_followup, mutating the element
        instance index — the INCIDENT value type must dirty h/instances or
        the delta take reuses a stale instances segment."""
        clock = ControlledClock(start_ms=1_000_000)
        broker = Broker(num_partitions=1, data_dir=str(tmp_path / "d"), clock=clock)
        try:
            client = ZeebeClient(broker)
            # IO_MAPPING_ERROR on a SERVICE TASK: the failure event is the
            # task's ELEMENT_READY, a LIVE element instance whose value the
            # resolve rewrite mutates in place
            model = (
                Bpmn.create_process("flow")
                .start_event("s")
                .service_task("work", type="t", inputs=[("$.missing", "$.x")])
                .end_event("e")
                .done()
            )
            client.deploy_model(model)
            inst = client.create_instance("flow", {})  # missing variable
            broker.run_until_idle()
            broker.snapshot()  # base take under the OPEN incident

            from zeebe_tpu.protocol.enums import ValueType
            from zeebe_tpu.protocol.intents import IncidentIntent

            incident = [
                r for r in broker.records(0)
                if r.metadata.value_type == ValueType.INCIDENT
                and r.metadata.intent == int(IncidentIntent.CREATED)
            ][0]
            # process ONLY the RESOLVE command — its _write_wi_followup
            # mutates the element instance, and the take fence can land
            # BEFORE the re-written WI follow-up (which would also mark
            # h/instances) gets processed: exactly the uncovered window
            from zeebe_tpu.protocol.records import IncidentRecord

            partition = broker.partitions[0]
            engine = partition.engine
            broker.write_command(
                0,
                IncidentRecord(
                    workflow_instance_key=inst.workflow_instance_key,
                    activity_instance_key=incident.value.activity_instance_key,
                    payload={"missing": 500},
                ),
                IncidentIntent.RESOLVE,
                key=incident.key,
                with_response=False,
            )
            resolve = partition.log.reader(partition.next_read_position)
            record = resolve.read_committed()[0]
            engine.process(record)  # follow-ups deliberately NOT applied
            instance = engine.element_instances.get(
                incident.value.activity_instance_key
            )
            assert instance is not None and instance.value.payload.get(
                "missing") == 500, "fixture must mutate the instance"

            meta = SnapshotMetadata(record.position, record.position, 0)
            partition.snapshots.take_engine(engine, meta)  # delta take
            assert partition.snapshots.last_take_stats["reused_parts"] > 0
            on_disk = partition.snapshots.storage.read_parts(meta)
            fresh = dict(stateser.encode_state_parts(engine.snapshot_state()))
            assert on_disk == fresh  # bit-identical incl. h/instances
        finally:
            broker.close()

    def test_restored_broker_resumes_after_delta_chain(self, tmp_path):
        broker, client, clock = _broker_with_traffic(tmp_path)
        data = broker.data_dir
        try:
            broker.snapshot()
            client.create_instance("order-process", payload={"orderId": 50})
            broker.run_until_idle()
            broker.snapshot()  # delta take; compaction runs below it
            live = stateser.encode_host_state(
                broker.partitions[0].engine.snapshot_state()
            )
        finally:
            broker.close()
        broker = Broker(num_partitions=1, data_dir=data, clock=clock)
        try:
            broker.run_until_idle()
            restored = stateser.encode_host_state(
                broker.partitions[0].engine.snapshot_state()
            )
            assert restored == live
            # and the restored engine keeps serving
            client = ZeebeClient(broker)
            JobWorker(broker, "payment-service", lambda ctx: None)
            client.create_instance("order-process")
            broker.run_until_idle()
        finally:
            broker.close()

    def test_commit_failure_remarks_dirty_and_next_take_is_full(
        self, tmp_path, monkeypatch
    ):
        """The capture fence resets tracking; a failed commit must merge
        the captured families back so nothing is lost, and the delta base
        is dropped (unknown on-disk state ⇒ full take next)."""
        broker, client, _ = _broker_with_traffic(tmp_path)
        try:
            broker.snapshot()
            client.create_instance("order-process", payload={"orderId": 7})
            broker.run_until_idle()
            controller = broker.partitions[0].snapshots
            engine = broker.partitions[0].engine

            def boom(*a, **k):
                raise OSError("injected fsync failure")

            monkeypatch.setattr(controller.storage, "_write_segment", boom)
            with pytest.raises(OSError):
                broker.snapshot()
            monkeypatch.undo()
            dirty = engine.snapshot_dirty_families()
            assert dirty is None or "h/instances" in dirty

            broker.snapshot()  # full again (delta base dropped), succeeds
            stats = controller.last_take_stats
            assert stats["reused_parts"] == 0
            newest = controller.storage.list()[0]
            on_disk = controller.storage.read_parts(newest)
            fresh = dict(stateser.encode_state_parts(engine.snapshot_state()))
            assert on_disk == fresh
        finally:
            broker.close()


# ---------------------------------------------------------------------------
# device-engine dirty tracking
# ---------------------------------------------------------------------------


def _device_engine(n_jobs=4, capacity=256):
    """Device engine with synthetic device-table jobs + one credited
    subscription (no kernel dispatch needed)."""
    import jax.numpy as jnp

    from zeebe_tpu.protocol.intents import JobIntent as JI
    from zeebe_tpu.tpu.engine import TpuPartitionEngine

    eng = TpuPartitionEngine(capacity=capacity, sub_capacity=8)
    s = eng.state
    tid = eng.interns.intern("work")
    job_i32 = np.asarray(s.job_i32).copy()
    job_i64 = state_mod.host_i64(s.job_i64).copy()
    for i in range(n_jobs):
        job_i32[i] = (int(JI.CREATED), 0, 0, tid, 3, 0)
        job_i64[i] = (100 + 5 * i, -1, -1, -1)
    sub_key = np.asarray(s.sub_key).copy()
    sub_type = np.asarray(s.sub_type).copy()
    sub_worker = np.asarray(s.sub_worker).copy()
    sub_credits = np.asarray(s.sub_credits).copy()
    sub_timeout = np.asarray(s.sub_timeout).copy()
    sub_valid = np.asarray(s.sub_valid).copy()
    sub_key[0], sub_type[0] = 1, tid
    sub_worker[0] = eng.interns.intern("w-1")
    sub_credits[0], sub_timeout[0], sub_valid[0] = 10, 1000, True
    eng.state = dataclasses.replace(
        s,
        job_i32=jnp.asarray(job_i32),
        job_i64=jnp.asarray(state_mod.host_planes(job_i64)),
        sub_key=jnp.asarray(sub_key), sub_type=jnp.asarray(sub_type),
        sub_worker=jnp.asarray(sub_worker),
        sub_credits=jnp.asarray(sub_credits),
        sub_timeout=jnp.asarray(sub_timeout),
        sub_valid=jnp.asarray(sub_valid),
    )
    return eng


class TestDeviceDirtyTracking:
    def test_second_take_does_zero_device_readback(self, tmp_path):
        """Acceptance pin: with unchanged state, the second take performs
        ZERO device→host readback (no np.asarray of any table) and
        new_bytes == 0."""
        eng = _device_engine()
        controller = SnapshotController(SnapshotStorage(str(tmp_path)))
        controller.take_engine(eng, SnapshotMetadata(10, 12, 1))
        assert len(eng.last_snapshot_readback) > 0  # cold take read all

        controller.take_engine(eng, SnapshotMetadata(20, 22, 1))
        assert eng.last_snapshot_readback == []
        stats = controller.last_take_stats
        assert stats["new_bytes"] == 0
        assert stats["new_segments"] == 0
        assert stats["reused_parts"] > 0

    def test_tick_mutation_reads_back_only_its_family(self, tmp_path):
        eng = _device_engine()
        controller = SnapshotController(SnapshotStorage(str(tmp_path)))
        controller.take_engine(eng, SnapshotMetadata(10, 12, 1))

        out = eng.device_backlog_activations()  # mutates sub credits/cursor
        assert out, "fixture must assign at least one backlog job"
        assert eng.snapshot_dirty_families() == frozenset({"d/sub"})
        controller.take_engine(eng, SnapshotMetadata(20, 22, 1))
        read = set(eng.last_snapshot_readback)
        assert read, "the dirty sub family must be re-read"
        assert all(name.startswith("sub_") for name in read), read
        # the big ei/job/payload tables were NOT transferred
        assert not any(name.startswith(("ei_", "job_", "msg_")) for name in read)

    def test_kernel_dispatch_marks_all_device_families_not_cold(self):
        """A wave dispatch dirties every DEVICE family but must keep the
        HOST family tracking live — else every serving wave degrades the
        next take to fully-full (clean host bulk like workflows would be
        re-encoded every period)."""
        from zeebe_tpu.tpu.engine import TpuPartitionEngine

        assert set(TpuPartitionEngine._ALL_DEVICE_FAMILIES) == set(
            stateser.DEVICE_ARRAY_FAMILIES
        )
        eng = _device_engine()
        eng.snapshot_mark_clean()
        eng._mark_device_dirty()  # what _dispatch_device does per wave
        dirty = eng.snapshot_dirty_families()
        assert dirty is not None, "dispatch must not collapse tracking to cold"
        assert {"d/" + f for f in stateser.DEVICE_ARRAY_FAMILIES} <= set(dirty)
        assert "h/workflows" not in dirty

    def test_device_delta_restores_bit_identically(self, tmp_path):
        eng = _device_engine()
        controller = SnapshotController(SnapshotStorage(str(tmp_path)))
        controller.take_engine(eng, SnapshotMetadata(10, 12, 1))
        eng.device_backlog_activations()
        eng.increase_job_credits(1, 5)
        controller.take_engine(eng, SnapshotMetadata(20, 22, 1))
        assert controller.last_take_stats["reused_parts"] > 0

        newest = controller.storage.list()[0]
        on_disk = controller.storage.read_parts(newest)
        fresh = dict(stateser.encode_state_parts(eng.snapshot_state()))
        assert on_disk == fresh
        # and the streamed restore reassembles the exact bytes
        state, meta = controller.recover(log_last_position=100)
        assert meta == SnapshotMetadata(20, 22, 1)
        assert dict(stateser.encode_state_parts(state)) == on_disk


# ---------------------------------------------------------------------------
# gc_segments edge cases (satellite)
# ---------------------------------------------------------------------------


class TestSegmentGc:
    def _controller(self, tmp_path):
        return SnapshotController(SnapshotStorage(str(tmp_path)))

    def test_young_unreferenced_segment_survives_grace(self, tmp_path):
        """An unreferenced segment younger than the grace window may belong
        to an install whose manifest has not committed yet — kept."""
        storage = SnapshotStorage(str(tmp_path))
        storage.write_parts(
            SnapshotMetadata(5, 6, 0), stateser.encode_state_parts({"v": 1})
        )
        # an in-flight install's segment: present, referenced by nothing
        orphan = part_hash(b"in-flight-part")
        storage._write_segment(orphan, b"x" * 8)
        assert storage.gc_segments() == 0
        assert storage.has_segment(orphan)

    def test_old_unreferenced_segment_is_reaped(self, tmp_path):
        storage = SnapshotStorage(str(tmp_path))
        storage.write_parts(
            SnapshotMetadata(5, 6, 0), stateser.encode_state_parts({"v": 1})
        )
        orphan = part_hash(b"dead-part")
        storage._write_segment(orphan, b"x" * 8)
        _age_segments(str(tmp_path))
        assert storage.gc_segments() >= 1
        assert not storage.has_segment(orphan)
        # referenced segments of the committed snapshot survived the sweep
        state, _ = SnapshotController(storage).recover(log_last_position=100)
        assert state == {"v": 1}

    def test_segment_referenced_only_by_newest_manifest_survives(self, tmp_path):
        """Mid-delta-chain safety: a segment first referenced by the NEWEST
        manifest (a delta's fresh family) is never collected, however old
        the file is."""
        controller = self._controller(tmp_path)
        controller.take({"v": 1}, SnapshotMetadata(5, 6, 0))
        controller.take({"v": 2}, SnapshotMetadata(9, 11, 0))
        _age_segments(str(tmp_path))
        controller.storage.gc_segments()
        state, meta = controller.recover(log_last_position=100)
        assert state == {"v": 2}
        assert meta == SnapshotMetadata(9, 11, 0)


# ---------------------------------------------------------------------------
# crash mid-delta-commit (invariant 6) + recovery skip accounting
# ---------------------------------------------------------------------------


class TestCrashMidDeltaCommit:
    @pytest.mark.parametrize("point", [
        DiskFaults.CRASH_SEGMENTS_WRITTEN,
        DiskFaults.CRASH_TMP_WRITTEN,
        DiskFaults.CRASH_OLD_ASIDE,
        DiskFaults.CRASH_SWAPPED,
    ])
    def test_previous_snapshot_survives_crash_and_gc(self, tmp_path, point):
        """Whatever instant a delta commit dies at, the PREVIOUS snapshot's
        referenced segments survive the restart sweep + GC and it restores
        bit-identically."""
        storage = SnapshotStorage(str(tmp_path))
        controller = SnapshotController(storage)
        base_state = {"v": 1, "bulk": "x" * 4096}
        controller.take(base_state, SnapshotMetadata(5, 6, 0))
        base_parts = storage.read_parts(SnapshotMetadata(5, 6, 0))

        delta_parts = stateser.encode_state_parts({"v": 2, "bulk": "y" * 4096})
        DiskFaults.crash_manifest_commit(
            storage, SnapshotMetadata(9, 11, 0), delta_parts, [], point
        )

        # restart: open sweeps orphans, then GC past the grace window
        reopened = SnapshotStorage(str(tmp_path))
        _age_segments(str(tmp_path))
        reopened.gc_segments()
        state, meta = SnapshotController(reopened).recover(log_last_position=100)
        if point in (DiskFaults.CRASH_SEGMENTS_WRITTEN, DiskFaults.CRASH_TMP_WRITTEN):
            # the delta never committed: the base must be fully intact
            assert meta == SnapshotMetadata(5, 6, 0)
            assert state == base_state
            assert reopened.read_parts(SnapshotMetadata(5, 6, 0)) == base_parts
        else:
            # CRASH_OLD_ASIDE restores the set-aside base; CRASH_SWAPPED
            # committed the delta — either way recovery converges on a
            # complete snapshot with no missing segments
            assert state in (base_state, {"v": 2, "bulk": "y" * 4096})
            assert meta in (SnapshotMetadata(5, 6, 0), SnapshotMetadata(9, 11, 0))


class TestRecoverSkipAccounting:
    def test_skipped_snapshot_warns_and_counts(self, tmp_path, caplog):
        controller = SnapshotController(SnapshotStorage(str(tmp_path)))
        controller.take({"v": 1}, SnapshotMetadata(5, 6, 0))
        # corrupt a NEWER manifest snapshot: delete one of its segments
        newer = SnapshotMetadata(9, 11, 0)
        controller.storage.write_parts(
            newer, stateser.encode_state_parts({"v": 2})
        )
        seg_dir = os.path.join(str(tmp_path), _SEGMENTS_DIR)
        older_hashes = {
            e["h"] for e in controller.storage.manifest(SnapshotMetadata(5, 6, 0))
        }
        unique = [
            e for e in controller.storage.manifest(newer)
            if e["h"] not in older_hashes
        ]
        os.unlink(os.path.join(seg_dir, unique[0]["h"] + ".seg"))

        before = event_count("snapshot_recover_skipped")
        import logging

        with caplog.at_level(logging.WARNING, logger="zeebe_tpu.log.snapshot"):
            state, meta = controller.recover(log_last_position=100)
        assert state == {"v": 1}
        assert event_count("snapshot_recover_skipped") == before + 1
        assert any(
            newer.dirname in rec.getMessage() for rec in caplog.records
        ), "the warn log must NAME the skipped snapshot"


# ---------------------------------------------------------------------------
# snapshot-while-serving (cluster path)
# ---------------------------------------------------------------------------


class TestSnapshotWhileServing:
    def _boot(self, tmp_path):
        from zeebe_tpu.testing.chaos import ChaosHarness

        harness = ChaosHarness(str(tmp_path), n_brokers=1)
        harness.await_leaders()
        client = harness.client()
        client.deploy_model(order_process_model())
        done = []
        worker = client.open_job_worker(
            "payment-service", lambda pid, rec: done.append(rec.key) or {"ok": 1}
        )
        return harness, client, worker, done

    def test_wave_drain_completes_while_take_in_flight(self, tmp_path):
        """Acceptance pin: serving continues during encode/commit — a
        workflow completes end-to-end while a snapshot commit is wedged on
        its worker thread; the capture pause stays bounded; at most one
        take is in flight (the overlapping one is skipped + counted)."""
        import threading

        from tests.test_raft import wait_until
        from zeebe_tpu.runtime.metrics import GLOBAL_REGISTRY

        harness, client, worker, done = self._boot(tmp_path)
        try:
            broker = harness.brokers["b0"]
            server = broker.partitions[0]
            client.create_instance("order-process")
            assert wait_until(lambda: len(done) >= 1, timeout=30)
            broker.snapshot_all()  # full base (synchronous, commits joined)

            # dirty some families, then wedge the next commit's segment
            # write so the take stays in flight
            client.create_instance("order-process")
            assert wait_until(lambda: len(done) >= 2, timeout=30)
            gate = threading.Event()
            entered = threading.Event()
            storage = server.snapshots.storage
            real_write = storage._write_segment

            def slow_write(h, compressed):
                entered.set()
                assert gate.wait(30), "test gate never released"
                real_write(h, compressed)

            storage._write_segment = slow_write
            try:
                thread = broker.actor.call(server.snapshot).join(10)
                assert thread is not None
                assert entered.wait(10), "commit never reached the storage"
                assert server._snapshot_inflight

                # serving continues while the take is in flight: a fresh
                # workflow must complete end-to-end
                client.create_instance("order-process")
                assert wait_until(lambda: len(done) >= 3, timeout=30)

                # the guard: a second take while one is in flight is
                # skipped and counted
                before = event_count("snapshot_skipped_inflight")
                assert broker.actor.call(server.snapshot).join(10) is None
                assert event_count("snapshot_skipped_inflight") == before + 1
            finally:
                gate.set()
            thread.join(20)
            assert not thread.is_alive()
            storage._write_segment = real_write
            assert not server._snapshot_inflight

            # the in-flight take committed; capture pause was reported and
            # bounded (the wedged 30s gate was commit-side, not capture)
            pause = GLOBAL_REGISTRY.gauge("snapshot_capture_pause_seconds").value
            assert 0 < pause < 5.0
            stats = server.snapshots.last_take_stats
            assert stats["reused_parts"] > 0  # it was a delta take
        finally:
            worker.close()
            client.close()
            harness.close()

    def test_partition_take_failure_is_isolated(self, tmp_path):
        """Satellite: a raising take on one partition must not abort
        _snapshot_all_on_actor for the rest (break_fsync-style storage
        failure on partition 0; partition 1 still checkpoints)."""
        from tests.test_raft import wait_until
        from zeebe_tpu.testing.chaos import ChaosHarness

        harness = ChaosHarness(str(tmp_path), n_brokers=1, partitions=2)
        client = None
        try:
            harness.await_leaders()
            client = harness.client()
            client.deploy_model(order_process_model())
            done = []
            worker = client.open_job_worker(
                "payment-service", lambda pid, rec: done.append(rec.key) or {}
            )
            client.create_instance("order-process")
            assert wait_until(lambda: len(done) >= 1, timeout=30)
            worker.close()

            broker = harness.brokers["b0"]
            p0 = broker.partitions[0]

            def boom(*a, **k):
                raise OSError("injected fsync failure")

            p0.snapshots.storage._write_segment = boom
            failures_before = event_count("snapshot_take_failures")
            broker.snapshot_all()  # must not raise
            assert wait_until(
                lambda: event_count("snapshot_take_failures") > failures_before,
                timeout=10,
            )
            # the OTHER partition still checkpointed
            assert broker.partitions[1].snapshots.storage.list()
        finally:
            if client is not None:
                client.close()
            harness.close()

    def test_delta_chain_crash_restore_parity(self, tmp_path):
        """Chaos invariant 5 (cluster form): crash-stop after a chain of
        delta takes; the restarted broker restores from the delta-chain
        snapshot and its state matches the replay oracle exactly."""
        from tests.test_chaos import _assert_oracle_parity
        from tests.test_raft import wait_until

        harness, client, worker, done = self._boot(tmp_path)
        try:
            broker = harness.brokers["b0"]
            client.create_instance("order-process")
            assert wait_until(lambda: len(done) >= 1, timeout=30)
            broker.snapshot_all()  # full base

            client.create_instance("order-process")
            assert wait_until(lambda: len(done) >= 2, timeout=30)
            broker.snapshot_all()  # delta take
            server = broker.partitions[0]
            assert server.snapshots.last_take_stats["reused_parts"] > 0

            client.close()
            client = None
            worker.close()
            worker = None
            harness.crash("b0")
            harness.restart("b0")
            harness.await_leaders()

            # recovered broker serves new traffic on the restored state
            client = harness.client()
            done2 = []
            worker = client.open_job_worker(
                "payment-service", lambda pid, rec: done2.append(rec.key) or {}
            )
            client.create_instance("order-process")
            assert wait_until(lambda: len(done2) >= 1, timeout=30)
            _assert_oracle_parity(harness)
        finally:
            if worker is not None:
                worker.close()
            if client is not None:
                client.close()
            harness.close()


# ---------------------------------------------------------------------------
# million-instance-scale lifecycle sweep (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestLargeResidentStateSweep:
    """ROADMAP item 5 at scale: snapshot/restore + crash sweeps under LARGE
    resident device state (≥100k instances). Slow tier; the same paths run
    tier-1 at small scale above."""

    N = 1 << 17  # 131072 rows ≥ 100k instances

    def _big_engine(self):
        import jax.numpy as jnp

        from zeebe_tpu.protocol.intents import JobIntent as JI
        from zeebe_tpu.tpu.engine import TpuPartitionEngine

        eng = TpuPartitionEngine(capacity=self.N, sub_capacity=8)
        s = eng.state
        n = self.N - 8  # a few free slots so backlog ticks stay cheap
        rows = np.arange(n)
        ei_i32 = np.asarray(s.ei_i32).copy()
        ei_i64 = state_mod.host_i64(s.ei_i64).copy()
        ei_i32[:n, 0] = 3            # elem
        ei_i32[:n, 1] = 2            # lifecycle state
        ei_i64[:n, 0] = 100 + 5 * rows   # key
        ei_i64[:n, 1] = 100 + 5 * rows   # workflowInstanceKey
        tid = eng.interns.intern("work")
        job_i32 = np.asarray(s.job_i32).copy()
        job_i64 = state_mod.host_i64(s.job_i64).copy()
        job_i32[:n, 0] = int(JI.CREATED)
        job_i32[:n, 3] = tid
        job_i32[:n, 4] = 3
        job_i64[:n, 0] = 102 + 5 * rows
        job_i64[:n, 1] = 100 + 5 * rows
        sub_key = np.asarray(s.sub_key).copy()
        sub_type = np.asarray(s.sub_type).copy()
        sub_credits = np.asarray(s.sub_credits).copy()
        sub_timeout = np.asarray(s.sub_timeout).copy()
        sub_valid = np.asarray(s.sub_valid).copy()
        sub_key[0], sub_type[0] = 1, tid
        sub_credits[0], sub_timeout[0], sub_valid[0] = 64, 1000, True
        eng.state = dataclasses.replace(
            s,
            ei_i32=jnp.asarray(ei_i32),
            ei_i64=jnp.asarray(state_mod.host_planes(ei_i64)),
            job_i32=jnp.asarray(job_i32),
            job_i64=jnp.asarray(state_mod.host_planes(job_i64)),
            sub_key=jnp.asarray(sub_key), sub_type=jnp.asarray(sub_type),
            sub_credits=jnp.asarray(sub_credits),
            sub_timeout=jnp.asarray(sub_timeout),
            sub_valid=jnp.asarray(sub_valid),
        )
        return eng

    def test_delta_take_and_bounded_restore_at_scale(self, tmp_path):
        import time as _time

        eng = self._big_engine()
        controller = SnapshotController(SnapshotStorage(str(tmp_path)))
        t0 = _time.perf_counter()
        controller.take_engine(eng, SnapshotMetadata(10, 12, 1))
        full_seconds = _time.perf_counter() - t0
        full = dict(controller.last_take_stats)
        assert full["total_bytes"] > 10 * self.N  # the state is actually big

        # a tick-sized mutation, then the delta take: cost tracks the
        # DELTA, not the ~100k-instance resident state
        out = eng.device_backlog_activations()
        assert out
        t0 = _time.perf_counter()
        controller.take_engine(eng, SnapshotMetadata(20, 22, 1))
        delta_seconds = _time.perf_counter() - t0
        delta = dict(controller.last_take_stats)
        assert delta["total_bytes"] == full["total_bytes"]
        assert delta["new_bytes"] < full["total_bytes"] // 50
        assert set(eng.last_snapshot_readback) <= {
            "sub_key", "sub_type", "sub_worker", "sub_credits",
            "sub_timeout", "sub_valid", "sub_rr",
        }
        # delta takes must not be slower than full ones at scale
        assert delta_seconds < max(full_seconds, 1.0)

        # bounded restore: streamed decode reassembles the exact bytes
        t0 = _time.perf_counter()
        state, meta = controller.recover(log_last_position=100)
        restore_seconds = _time.perf_counter() - t0
        assert meta == SnapshotMetadata(20, 22, 1)
        on_disk = controller.storage.read_parts(meta)
        assert dict(stateser.encode_state_parts(state)) == on_disk
        assert restore_seconds < 120  # bounded, reported via the gauge

    @pytest.mark.parametrize("point", [
        DiskFaults.CRASH_SEGMENTS_WRITTEN,
        DiskFaults.CRASH_SWAPPED,
    ])
    def test_crash_mid_delta_commit_at_scale(self, tmp_path, point):
        eng = self._big_engine()
        storage = SnapshotStorage(str(tmp_path))
        controller = SnapshotController(storage)
        controller.take_engine(eng, SnapshotMetadata(10, 12, 1))
        base_parts = storage.read_parts(SnapshotMetadata(10, 12, 1))

        eng.device_backlog_activations()
        pending = controller.capture(eng, SnapshotMetadata(20, 22, 1))
        DiskFaults.crash_manifest_commit(
            storage, pending.metadata, pending.parts, pending.reused, point
        )
        reopened = SnapshotStorage(str(tmp_path))
        _age_segments(str(tmp_path))
        reopened.gc_segments()
        state, meta = SnapshotController(reopened).recover(log_last_position=100)
        assert state is not None
        if point == DiskFaults.CRASH_SEGMENTS_WRITTEN:
            assert meta == SnapshotMetadata(10, 12, 1)
            assert reopened.read_parts(meta) == base_parts
        else:
            assert meta == SnapshotMetadata(20, 22, 1)
        # whichever snapshot won, every referenced segment survived GC
        assert dict(stateser.encode_state_parts(state)) == reopened.read_parts(meta)


# ---------------------------------------------------------------------------
# scenario storms (ROADMAP item 5): message-TTL + incident create/resolve
# chaos sweeps — tier-1 at small scale, slow tier larger
# ---------------------------------------------------------------------------


def _ttl_storm(broker_harness_client, n_messages, ttl_ms=400):
    harness, client = broker_harness_client
    for i in range(n_messages):
        client.publish_message(
            "storm-evt", f"corr-{i}", {"i": i}, time_to_live_ms=ttl_ms
        )
    return harness.leader_of(0)


class TestScenarioStorms:
    def _messages_alive(self, harness):
        leader = harness.leader_of(0)
        if leader is None:
            return -1
        server = leader.partitions[0]
        if server.engine is None:
            return -1
        return len(server.engine.messages)

    def _run_ttl_storm(self, tmp_path, n_messages):
        """Publish a burst of short-TTL messages with no subscriptions,
        snapshot mid-storm, crash-stop the broker, and require: the TTL
        sweep drains the store to empty on the restarted broker, and replay
        parity holds (expiry DELETEs are ordinary committed records)."""
        from tests.test_chaos import _assert_oracle_parity
        from tests.test_raft import wait_until
        from zeebe_tpu.testing.chaos import ChaosHarness

        harness = ChaosHarness(str(tmp_path), n_brokers=1)
        client = None
        try:
            harness.await_leaders()
            client = harness.client()
            client.deploy_model(order_process_model())
            _ttl_storm((harness, client), n_messages)
            broker = harness.brokers["b0"]
            broker.snapshot_all()  # mid-storm take (messages family dirty)
            stats = broker.partitions[0].snapshots.last_take_stats
            assert stats["new_bytes"] > 0

            client.close()
            client = None
            harness.crash("b0")
            harness.restart("b0")
            harness.await_leaders()
            # the restored broker's TTL sweep must expire the storm fully
            assert wait_until(
                lambda: self._messages_alive(harness) == 0, timeout=60
            ), f"{self._messages_alive(harness)} messages never expired"
            _assert_oracle_parity(harness)
        finally:
            if client is not None:
                client.close()
            harness.close()

    def _run_incident_storm(self, tmp_path, n_instances):
        """Create a wave of instances that all raise CONDITION_ERROR
        incidents (missing variable), snapshot under open incidents, crash,
        restart, then resolve every incident via payload update — every
        instance must complete, and replay parity holds."""
        from tests.test_chaos import _assert_oracle_parity
        from tests.test_raft import wait_until
        from zeebe_tpu.models.bpmn.builder import Bpmn
        from zeebe_tpu.protocol.enums import RecordType, ValueType
        from zeebe_tpu.protocol.intents import IncidentIntent
        from zeebe_tpu.testing.chaos import ChaosHarness

        b = (
            Bpmn.create_process("storm-flow")
            .start_event("s")
            .exclusive_gateway("split")
        )
        b.branch("$.orderValue >= 100").service_task(
            "insured", type="insured-t"
        ).end_event("e1")
        b.branch(default=True).service_task(
            "plain", type="plain-t"
        ).end_event("e2")
        model = b.done()

        harness = ChaosHarness(str(tmp_path), n_brokers=1)
        client = None
        workers = []
        try:
            harness.await_leaders()
            client = harness.client()
            client.deploy_model(model)
            done = []
            for jt in ("insured-t", "plain-t"):
                workers.append(client.open_job_worker(
                    jt, lambda pid, rec: done.append(rec.key) or {}
                ))
            instances = [
                client.create_instance("storm-flow", {})  # missing variable
                for _ in range(n_instances)
            ]

            def created_incidents():
                leader = harness.leader_of(0)
                if leader is None or leader.partitions[0].engine is None:
                    return []
                return [
                    r for r in leader.partitions[0].log.reader(0).read_committed()
                    if r.metadata.value_type == ValueType.INCIDENT
                    and r.metadata.record_type == RecordType.EVENT
                    and r.metadata.intent == int(IncidentIntent.CREATED)
                ]

            assert wait_until(
                lambda: len(created_incidents()) >= n_instances, timeout=60
            )
            broker = harness.brokers["b0"]
            broker.snapshot_all()  # take under open incidents

            client.close()
            client = None
            for w in workers:
                w.close()
            workers = []
            harness.crash("b0")
            harness.restart("b0")
            harness.await_leaders()

            client = harness.client()
            for jt in ("insured-t", "plain-t"):
                workers.append(client.open_job_worker(
                    jt, lambda pid, rec: done.append(rec.key) or {}
                ))
            # resolve the storm: payload update at each failed token
            for inc in created_incidents():
                client.update_payload(
                    0, inc.value.workflow_instance_key,
                    {"orderValue": 500},
                    activity_instance_key=inc.value.activity_instance_key,
                )
            assert wait_until(
                lambda: len(done) >= n_instances, timeout=90
            ), f"only {len(done)}/{n_instances} storm instances completed"
            _assert_oracle_parity(harness)
        finally:
            for w in workers:
                w.close()
            if client is not None:
                client.close()
            harness.close()

    def test_message_ttl_storm_small(self, tmp_path):
        self._run_ttl_storm(tmp_path, n_messages=24)

    def test_incident_storm_small(self, tmp_path):
        self._run_incident_storm(tmp_path, n_instances=8)

    @pytest.mark.slow
    def test_message_ttl_storm_large(self, tmp_path):
        self._run_ttl_storm(tmp_path, n_messages=512)

    @pytest.mark.slow
    def test_incident_storm_large(self, tmp_path):
        self._run_incident_storm(tmp_path, n_instances=128)
