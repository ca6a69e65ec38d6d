"""Device job-backlog serving path: typed probe, persisted round-robin
cursor, the in-process broker's gated device pull, and the engine's own
account of parked jobs, held against a scan of the job table (ISSUE 28)
(zeebe_tpu/tpu/engine.py, zeebe_tpu/runtime/broker.py).
"""

import dataclasses
import random

import numpy as np
import pytest

import jax.numpy as jnp

from zeebe_tpu import tpu as _tpu  # noqa: F401  (enables x64)
from zeebe_tpu import tracing
from zeebe_tpu.engine.interpreter import JobSubscription, WorkflowRepository
from zeebe_tpu.gateway import ZeebeClient
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import JobIntent as JI
from zeebe_tpu.protocol.metadata import RecordMetadata
from zeebe_tpu.protocol.records import JobRecord, Record
from zeebe_tpu.runtime import Broker, ControlledClock
from zeebe_tpu.runtime.metrics import event_count
from zeebe_tpu.tpu import state as state_mod
from zeebe_tpu.tpu.engine import (
    PROBE_DEADLINES,
    PROBE_JOB_BACKLOG,
    TpuPartitionEngine,
    _due_probe_jit,
)


def _engine(n_jobs, sub_specs, job_type="work"):
    """TpuPartitionEngine with ``n_jobs`` CREATED device-table jobs of
    ``job_type`` and subscriptions per (key, type, credits) specs."""
    eng = TpuPartitionEngine(capacity=256, sub_capacity=8)
    s = eng.state
    tid = eng.interns.intern(job_type)
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
    for slot, (key, stype, credits) in enumerate(sub_specs):
        sub_key[slot] = key
        sub_type[slot] = eng.interns.intern(stype)
        sub_worker[slot] = eng.interns.intern(f"worker-{key}")
        sub_credits[slot] = credits
        sub_timeout[slot] = 1000
        sub_valid[slot] = True
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


class TestTypedBacklogProbe:
    def test_backlog_bit_set_on_type_match(self):
        eng = _engine(2, [(1, "work", 5)])
        # the probe donates state (aliased pass-through): rebind
        eng.state, mask = _due_probe_jit(eng.state, jnp.asarray(0, jnp.int64))
        mask = int(mask)
        assert mask & PROBE_JOB_BACKLOG
        assert not mask & PROBE_DEADLINES

    def test_orphan_job_with_unmatched_credits_keeps_bit_clear(self):
        """The round-5 failure mode: ONE orphan job of an unserved type +
        any credited subscription kept the bit set, paying a full
        device→host backlog pull every tick for nothing."""
        eng = _engine(1, [(1, "other-type", 5)])
        eng.state, mask = _due_probe_jit(eng.state, jnp.asarray(0, jnp.int64))
        mask = int(mask)
        assert not mask & PROBE_JOB_BACKLOG
        # and the pull it gates would indeed have found nothing
        assert eng.device_backlog_activations() == []

    def test_exhausted_credits_keep_bit_clear(self):
        eng = _engine(2, [(1, "work", 0)])
        eng.state, mask = _due_probe_jit(eng.state, jnp.asarray(0, jnp.int64))
        mask = int(mask)
        assert not mask & PROBE_JOB_BACKLOG


class TestRoundRobinCursor:
    def test_assignments_alternate_within_a_call(self):
        eng = _engine(4, [(1, "work", 10), (2, "work", 10)])
        out = eng.device_backlog_activations()
        streams = [r.metadata.request_stream_id for r in out]
        assert streams == [1, 2, 1, 2]

    def test_cursor_persists_across_calls(self):
        """A fresh ``rr = 0`` every call handed every drain's first job to
        the first credited subscription; the cursor now lives in
        state.sub_rr, so consecutive drains continue the rotation."""
        eng = _engine(1, [(1, "work", 10), (2, "work", 10)])
        first = eng.device_backlog_activations()
        # the job's ACTIVATE is on its way: the next sweeps leave it alone
        assert eng.device_backlog_activations() == []
        # as after a restore: nothing known of what is in flight or parked
        eng._assigning.clear()
        eng._parked = None
        second = eng.device_backlog_activations()
        assert first[0].metadata.request_stream_id == 1
        assert second[0].metadata.request_stream_id == 2
        assert int(np.asarray(eng.state.sub_rr)) == 0  # wrapped around

    def test_cursor_survives_snapshot_restore(self):
        eng = _engine(1, [(1, "work", 10), (2, "work", 10)])
        eng.device_backlog_activations()  # advances the cursor to 1
        assert int(np.asarray(eng.state.sub_rr)) == 1
        snap = eng.snapshot_state()
        restored = TpuPartitionEngine(capacity=256, sub_capacity=8)
        restored.restore_state(snap)
        assert int(np.asarray(restored.state.sub_rr)) == 1


class TestBrokerTickGating:
    def test_device_pull_gated_by_probe_bit(self, tmp_path):
        """Broker.tick must consult the fused probe before paying the
        device→host backlog pull (the cluster broker's existing
        protocol); a clear bit skips the pull entirely."""
        broker = Broker(num_partitions=1, data_dir=str(tmp_path / "d"))
        partition = broker.partitions[0]
        calls = {"pull": 0}

        class GatedEngine:
            def __init__(self, inner, mask):
                self._inner = inner
                self._mask = mask

            def __getattr__(self, name):
                return getattr(self._inner, name)

            def deadlines_due_probe(self):
                return self._mask

            def device_backlog_activations(self):
                calls["pull"] += 1
                return []

        partition.engine = GatedEngine(partition.engine, 0)
        broker.tick()
        assert calls["pull"] == 0
        partition.engine = GatedEngine(
            partition.engine._inner, PROBE_JOB_BACKLOG
        )
        broker.tick()
        assert calls["pull"] == 1
        broker.close()


# -- the engine's own account of parked jobs (ISSUE 28) ----------------------
PAY_A, PAY_B, SHIP = 7, 8, 9  # subscriber keys: two of one type, one of another


def _device_broker(capacity=1 << 10):
    clock = ControlledClock(start_ms=1_000_000)
    repo = WorkflowRepository()
    broker = Broker(
        num_partitions=1, clock=clock,
        engine_factory=lambda pid: TpuPartitionEngine(
            pid, 1, repository=repo, clock=clock, capacity=capacity
        ),
    )
    client = ZeebeClient(broker)
    client.deploy_model(
        Bpmn.create_process("order-process").start_event("start")
        .service_task(
            "collect-money", type="payment-service", headers={"tier": "gold"}
        )
        .end_event("end").done()
    )
    client.deploy_model(
        Bpmn.create_process("ship").start_event("start")
        .service_task("ship-it", type="shipping-service")
        .end_event("end").done()
    )
    return broker, client, clock, broker.partitions[0].engine


def _subscribe(broker, engine, key, job_type, credits):
    backlog = engine.add_job_subscription(
        JobSubscription(
            subscriber_key=key, job_type=job_type, worker=f"w{key}",
            timeout=300_000, credits=credits,
        )
    )
    if backlog:
        broker.partitions[0].log.append(backlog)
    broker.run_until_idle()


def _job_events(broker, intent):
    return [
        r for r in broker.records(0)
        if r.metadata.value_type == ValueType.JOB
        and r.metadata.record_type == RecordType.EVENT
        and r.metadata.intent == int(intent)
    ]


def _job_command(intent, key):
    return Record(
        key=key, value=JobRecord(),
        metadata=RecordMetadata(
            record_type=RecordType.COMMAND, value_type=ValueType.JOB,
            intent=int(intent),
        ),
    )


def _sub_state(engine):
    s = engine.state
    return (
        np.asarray(s.sub_credits).tolist(), int(np.asarray(s.sub_rr)),
    )


def _counts(engine, fn):
    """``fn()`` with the engine's phases and counts on a clock of its own."""
    clock = tracing.PhaseClock()
    with engine.on_clock(clock):
        out = fn()
    return out, clock.counts


def _sweep_both_ways(engine):
    """The sweep from a scan of the job table (as after a restore), then,
    on the same state, from the engine's parked set: (records, credits and
    cursor left) of each. The engine is left as the parked set left it."""
    before = engine.state
    assigning, parked = set(engine._assigning), dict(engine._parked)
    engine._parked = None
    from_scan, counts = _counts(engine, engine.device_backlog_activations)
    assert counts.get("backlog_table_scans") == 1
    assert counts.get("job_row_reads", 0) == len(from_scan)
    left_by_scan = _sub_state(engine)
    engine.state = before
    engine._assigning, engine._parked = assigning, parked
    from_set, counts = _counts(engine, engine.device_backlog_activations)
    assert "backlog_table_scans" not in counts
    assert "job_row_reads" not in counts  # the events carried the values
    return (from_set, _sub_state(engine)), (from_scan, left_by_scan)


def _population(seed):
    """Jobs of two types created with no credit, five of them activated
    and then failed with retries left, failed without and given retries
    again, or timed out; one failed job given other retries while parked;
    one parked job cancelled by a command, one by its instance."""
    rng = random.Random(seed)
    broker, client, clock, engine = _device_broker()
    _subscribe(broker, engine, PAY_A, "payment-service", 0)
    _subscribe(broker, engine, PAY_B, "payment-service", 0)
    _subscribe(broker, engine, SHIP, "shipping-service", 0)
    kinds = ["order-process"] * 10 + ["ship"] * 4
    rng.shuffle(kinds)
    instances = [
        client.create_instance(kind, payload={"orderId": i, "v": rng.random() < 0.5})
        for i, kind in enumerate(kinds)
    ]
    broker.run_until_idle()
    assert len(engine._parked) == 14 and not engine._assigning
    engine.increase_job_credits(PAY_A, 3)
    engine.increase_job_credits(PAY_B, 2)
    broker.tick()
    broker.run_until_idle()
    active = [r.key for r in _job_events(broker, JI.ACTIVATED)]
    assert len(active) == 5 and len(engine._parked) == 9
    rng.shuffle(active)
    for key in active[:2]:
        client.fail_job(key, retries=2)
    client.fail_job(active[2], retries=0)
    client.update_job_retries(active[2], 4)
    clock.advance(300_001)  # the other two time out
    broker.tick()
    broker.run_until_idle()
    client.update_job_retries(active[0], 7)  # while parked
    broker.run_until_idle()
    assert len(engine._parked) == 14 and not engine._assigning
    waiting = sorted(
        k for k, (_t, held) in engine._parked.items()
        if held.type == "payment-service" and k not in active
    )
    broker.partitions[0].log.append([_job_command(JI.CANCEL, waiting[1])])
    owner = engine._parked[waiting[3]][1].headers.workflow_instance_key
    assert owner in {i.workflow_instance_key for i in instances}
    client.cancel_instance(owner)
    broker.run_until_idle()
    assert len(engine._parked) == 12
    assert not {waiting[1], waiting[3]} & set(engine._parked)
    return broker, client, clock, engine, active


class TestParkedSet:
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_sweep_from_parked_set_equals_table_scan(self, seed):
        broker, _client, _clock, engine, active = _population(seed)
        try:
            handed = []
            for credits in ((2, 3, 1), (0, 1, 9), (9, 9, 0)):
                for key, n in zip((PAY_A, PAY_B, SHIP), credits):
                    engine.increase_job_credits(key, n)
                (a, left_a), (b, left_b) = _sweep_both_ways(engine)
                assert a == b and left_a == left_b
                assert [r.key for r in a] == sorted(r.key for r in a)
                handed += a
                broker.partitions[0].log.append(a)
                broker.run_until_idle()
                assert not engine._assigning
            assert len(handed) == 12 == len({r.key for r in handed})
            assert not engine._parked
            by_key = {r.key: r.value for r in handed}
            assert by_key[active[0]].retries == 7
            assert by_key[active[2]].retries == 4
            assert all(
                v.custom_headers == {"tier": "gold"}
                for v in by_key.values() if v.type == "payment-service"
            )
            activated = [r.key for r in _job_events(broker, JI.ACTIVATED)]
            # the five of the first round, then each of the twelve once
            assert sorted(activated) == sorted(active + list(by_key))
            rejected = [
                r for r in broker.records(0)
                if r.metadata.record_type == RecordType.COMMAND_REJECTION
                and r.metadata.value_type == ValueType.JOB
            ]
            assert not rejected
        finally:
            broker.close()

    def test_tick_with_every_open_job_in_flight_touches_nothing(self):
        """(b) the listed cell's case: every activatable row is the
        pool's. The probe's bit is set, and the sweep returns before it
        reads a leaf of the device's state."""
        eng = _engine(3, [(1, "work", 5)])
        eng._parked = {}
        eng._assigning = {100, 105, 110}
        eng.state, mask = _due_probe_jit(eng.state, jnp.asarray(0, jnp.int64))
        assert int(mask) & PROBE_JOB_BACKLOG
        state, eng.state = eng.state, None  # any read of it would raise
        out, counts = _counts(eng, eng.device_backlog_activations)
        eng.state = state
        assert out == [] and counts == {}

    def test_restore_scans_once_and_strands_no_job(self):
        """(c) the parked set is not in a snapshot: the first sweep after
        a restore scans the table, the second does not."""
        broker, _client, _clock, engine, _active = _population(4)
        try:
            parked = set(engine._parked)
            snap = engine.snapshot_state()
            restored = TpuPartitionEngine(
                0, 1, repository=WorkflowRepository(),
                clock=ControlledClock(start_ms=2_000_000), capacity=1 << 10,
            )
            restored.restore_state(snap)
            assert restored._parked is None and not restored._assigning
            # a subscription without credits: nothing to hand out, but it
            # asks for the backlog of its type, which scans
            scans = event_count("serving_backlog_table_scans_total")
            restored.add_job_subscription(
                JobSubscription(
                    subscriber_key=PAY_A, job_type="payment-service",
                    worker="w", timeout=1000, credits=0,
                )
            )
            assert event_count("serving_backlog_table_scans_total") == scans + 1
            assert set(restored._parked) == parked
            restored._parked = None  # and a sweep that comes first
            restored.increase_job_credits(PAY_A, 4)
            clock = tracing.PhaseClock(slices=[])
            with restored.on_clock(clock):
                first = restored.device_backlog_activations()
            assert clock.counts["backlog_table_scans"] == 1 and len(first) == 4
            # what the scan found holds its row's slot: read when handed
            # out, phase ``job_read`` cut out of ``backlog``
            assert clock.counts["job_row_reads"] == 4
            names = [name for name, _t0, _t1 in clock.slices]
            assert names[0] == "backlog" == names[-1]
            assert names.count("job_read") == 4
            for prev, cur in zip(clock.slices, clock.slices[1:]):
                assert cur[1] == prev[2], (prev, cur)  # self times
            restored.increase_job_credits(PAY_A, 100)
            restored.add_job_subscription(
                JobSubscription(
                    subscriber_key=SHIP, job_type="shipping-service",
                    worker="w", timeout=1000, credits=0,
                )
            )
            restored.increase_job_credits(SHIP, 100)
            second, counts = _counts(restored, restored.device_backlog_activations)
            assert "backlog_table_scans" not in counts
            assert counts.get("backlog_sweeps") == 1
            keys = [r.key for r in first + second]
            assert sorted(keys) == sorted(parked) and not restored._parked
            _out, counts = _counts(restored, restored.device_backlog_activations)
            assert counts == {}  # nothing parked: the gate
        finally:
            broker.close()

    def test_cancelled_while_parked_is_not_activated(self):
        """(d) by a job CANCEL on the device path and by the instance's
        CANCEL (demotion to the host engine)."""
        broker, client, _clock, engine = _device_broker()
        try:
            _subscribe(broker, engine, PAY_A, "payment-service", 0)
            instances = [
                client.create_instance("order-process", payload={"orderId": i})
                for i in range(4)
            ]
            broker.run_until_idle()
            keys = sorted(engine._parked)
            assert len(keys) == 4
            broker.partitions[0].log.append([_job_command(JI.CANCEL, keys[0])])
            client.cancel_instance(
                engine._parked[keys[2]][1].headers.workflow_instance_key
            )
            broker.run_until_idle()
            assert sorted(engine._parked) == [keys[1], keys[3]]
            assert not engine._ended
            engine.increase_job_credits(PAY_A, 10)
            broker.tick()
            broker.run_until_idle()
            assert sorted(
                r.key for r in _job_events(broker, JI.ACTIVATED)
            ) == [keys[1], keys[3]]
            assert len(instances) == 4 and not engine._parked
        finally:
            broker.close()

    def test_job_that_ended_before_its_pool_event_is_not_parked(self):
        """A job times out and its worker completes it all the same: the
        COMPLETE is stepped between the TIME_OUT and the TIMED_OUT event,
        which then finds no credit free. The job is gone from the table
        and must not wait for one (``_ended``)."""
        broker, client, clock, engine = _device_broker()
        try:
            _subscribe(broker, engine, PAY_A, "payment-service", 1)
            client.create_instance("order-process", payload={"orderId": 1})
            broker.run_until_idle()
            (job,) = _job_events(broker, JI.ACTIVATED)
            clock.advance(300_001)
            broker.tick()  # appends the TIME_OUT
            broker.partitions[0].log.append(
                [_job_command(JI.COMPLETE, job.key)]
            )
            broker.wave_size = 1  # TIME_OUT, COMPLETE, TIMED_OUT: a wave each
            broker.run_until_idle()
            (timed_out,) = _job_events(broker, JI.TIMED_OUT)
            (completed,) = _job_events(broker, JI.COMPLETED)
            # the COMPLETE was stepped before the TIMED_OUT event
            assert completed.source_record_position < timed_out.position
            assert timed_out.value.retries > 0
            assert engine._parked == {} and not engine._ended
            assert not engine._assigning
            # and the table agrees: nothing for a scan to find
            engine._parked = None
            engine.increase_job_credits(PAY_A, 5)
            assert engine.device_backlog_activations() == []
        finally:
            broker.close()

    def test_parked_job_of_a_demoted_instance_waits_in_the_host_engine(self):
        """UPDATE_PAYLOAD moves the instance, and its job, to the embedded
        host engine: the job leaves the device's parked set and waits in
        the host's, and is activated once a credit is free."""
        broker, client, _clock, engine = _device_broker()
        try:
            _subscribe(broker, engine, PAY_A, "payment-service", 0)
            instance = client.create_instance(
                "order-process", payload={"orderId": 1}
            )
            broker.run_until_idle()
            (key,) = engine._parked
            client.update_payload(
                instance.workflow_instance_key, {"orderId": 2}
            )
            broker.run_until_idle()
            assert not engine._parked and not engine._ended
            assert key in engine._host._awaiting_jobs["payment-service"]
            engine.increase_job_credits(PAY_A, 1)
            broker.tick()
            broker.run_until_idle()
            assert [r.key for r in _job_events(broker, JI.ACTIVATED)] == [key]
        finally:
            broker.close()
