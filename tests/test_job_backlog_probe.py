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
        k for k, (_t, held, _since) in engine._parked.items()
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


PAY, INV, SHP = 21, 22, 23  # subscriber keys of the quickstart's three workers
QUICKSTART_TYPES = {
    PAY: "payment-service", INV: "inventory-service", SHP: "shipment-service",
}


def _quickstart_population(seed):
    """The quickstart's order process (three service tasks in sequence,
    ``zbench/processes/order_quickstart.py``) with one subscription a job
    type at the client's default of 32 credits, driven until jobs of all
    three types wait parked with their keys interleaved: payment jobs of
    the first burst, inventory and shipment jobs made while their
    subscriptions were dry, then payment jobs of a later burst. The
    workers' credits are returned by the caller, never here."""
    import importlib

    rng = random.Random(seed)
    broker, client, clock, engine = _device_broker(capacity=1 << 11)
    client.deploy_model(
        importlib.import_module("zbench.processes.order_quickstart").build()
    )
    for key, job_type in QUICKSTART_TYPES.items():
        _subscribe(broker, engine, key, job_type, 32)

    def start(n, first):
        for i in range(first, first + n):
            client.create_instance("order-quickstart", payload={"orderId": i})
        broker.run_until_idle()

    def active(job_type):
        done = {r.key for r in _job_events(broker, JI.COMPLETED)}
        return [
            r.key for r in _job_events(broker, JI.ACTIVATED)
            if r.value.type == job_type and r.key not in done
        ]

    def complete(job_type, n):
        keys = active(job_type)
        rng.shuffle(keys)
        for key in keys[:n]:
            client.complete_job(key, {"done": True})
        broker.run_until_idle()

    start(70, 0)  # 32 payments activated by the pool, 38 parked
    assert len(engine._parked) == 38 and not engine._assigning
    complete("payment-service", 32)  # 32 inventory jobs take every credit
    clock.advance(40)
    engine.increase_job_credits(PAY, 24)
    broker.tick()
    broker.run_until_idle()  # 24 parked payments leave with the sweep
    assert len(engine._parked) == 14
    complete("payment-service", 24)  # their inventory jobs find no credit
    assert len(engine._parked) == 14 + 24
    complete("inventory-service", 32)  # 32 shipments take every credit
    clock.advance(40)
    engine.increase_job_credits(INV, 20)
    broker.tick()
    broker.run_until_idle()
    complete("inventory-service", 20)  # their shipments find no credit
    clock.advance(40)
    start(9, 70)  # later payments: their keys follow the others'
    parked_types = [
        held.type for _k, (_t, held, _since) in sorted(engine._parked.items())
    ]
    assert sorted(set(parked_types)) == sorted(QUICKSTART_TYPES.values())
    # interleaved: sorted by key, the types change more often than twice
    assert sum(a != b for a, b in zip(parked_types, parked_types[1:])) > 2
    assert not engine._assigning
    return broker, client, clock, engine, complete


class TestParkedSet:
    @pytest.mark.parametrize("seed", [11, 12, 13])
    def test_three_types_at_32_credits_sweep_equals_table_scan(self, seed):
        """The quickstart's deployment (ISSUE 33): three job types, 32
        credits a subscription, parked keys interleaved across the types.
        Every sweep hands out what a scan of the table would, in key order,
        each type from its own credits; every job is activated once."""
        broker, _client, clock, engine, complete = _quickstart_population(seed)
        try:
            parked = dict(engine._parked)
            handed = []
            for credits in ((5, 0, 3), (0, 7, 32), (32, 32, 32), (32, 32, 32)):
                for key, n in zip((PAY, INV, SHP), credits):
                    if n:
                        engine.increase_job_credits(key, n)
                clock.advance(100)
                (a, left_a), (b, left_b) = _sweep_both_ways(engine)
                assert a == b and left_a == left_b
                assert [r.key for r in a] == sorted(r.key for r in a)
                by_type = {t: 0 for t in QUICKSTART_TYPES.values()}
                for r in a:
                    assert r.value.type == parked[r.key][1].type
                    assert r.metadata.request_stream_id == next(
                        k for k, t in QUICKSTART_TYPES.items() if t == r.value.type
                    )
                    by_type[r.value.type] += 1
                gone = {h.key for h in handed}
                waiting = {t: 0 for t in QUICKSTART_TYPES.values()}
                for k, (_t, held, _since) in parked.items():
                    waiting[held.type] += k not in gone
                assert by_type == {
                    t: min(n, waiting[t])
                    for t, n in zip(QUICKSTART_TYPES.values(), credits)
                }
                handed += a
                broker.partitions[0].log.append(a)
                broker.run_until_idle()
                assert not engine._assigning
                # the workers answer: what this makes next finds no credit
                # (every one is out) and parks behind the others
                for job_type in QUICKSTART_TYPES.values():
                    complete(job_type, 1 << 10)
                parked.update(engine._parked)
            assert len(handed) == len({r.key for r in handed})
            activated = [r.key for r in _job_events(broker, JI.ACTIVATED)]
            assert len(activated) == len(set(activated))
            rejected = [
                r for r in broker.records(0)
                if r.metadata.record_type == RecordType.COMMAND_REJECTION
                and r.metadata.value_type == ValueType.JOB
            ]
            assert not rejected
        finally:
            broker.close()

    def test_park_wait_counts_only_jobs_with_a_known_entry_time(self):
        """``serving_backlog_park_wait_seconds_total``: the engine's clock
        at the hand-out minus the clock at the job's entry into the parked
        set; a job that the scan after a restore found has no entry time
        and adds nothing. ``serving_backlog_parked_walked_total``: the keys
        a sweep looked at, which ends where the free credits do."""
        broker, client, clock, engine = _device_broker()
        try:
            _subscribe(broker, engine, PAY_A, "payment-service", 0)
            for i in range(3):
                client.create_instance("order-process", payload={"orderId": i})
            broker.run_until_idle()
            clock.advance(250)
            for i in range(3, 5):
                client.create_instance("order-process", payload={"orderId": i})
            broker.run_until_idle()
            keys = sorted(engine._parked)
            assert len(keys) == 5
            assert [engine._parked[k][2] for k in keys] == (
                [1_000_000] * 3 + [1_000_250] * 2
            )
            clock.advance(100)
            engine.increase_job_credits(PAY_A, 4)
            waited = event_count("serving_backlog_park_wait_seconds_total")
            out, counts = _counts(engine, engine.device_backlog_activations)
            assert [r.key for r in out] == keys[:4]
            # 3 x 350 ms + 1 x 100 ms
            assert counts["backlog_park_wait"] == pytest.approx(1.15)
            assert counts["backlog_parked_walked"] == 4  # the fifth: no credit left
            assert counts["backlog_activations"] == 4
            # a clock of the caller's is flushed by the caller (the tick's);
            # nothing reached the global counter behind its back
            assert event_count("serving_backlog_park_wait_seconds_total") == waited
            broker.partitions[0].log.append(out)
            broker.run_until_idle()
            # the last one is found again by a scan (as after a restore):
            # since when it waits is not known
            assert sorted(engine._parked) == keys[4:]
            engine._parked = None
            clock.advance(5_000)
            engine.increase_job_credits(PAY_A, 1)
            out, counts = _counts(engine, engine.device_backlog_activations)
            assert [r.key for r in out] == keys[4:]
            assert counts["backlog_table_scans"] == 1
            assert counts["backlog_parked_walked"] == 1
            assert "backlog_park_wait" not in counts
        finally:
            broker.close()

    def test_tick_flushes_the_park_wait_and_the_walk(self):
        """Flushed as the cluster broker's tick flushes its clock
        (``PartitionServer.tick``), the two counts reach the registry by
        the names the benchmark's readers use."""
        from zeebe_tpu.runtime.metrics import observe_phases

        broker, client, clock, engine = _device_broker()
        try:
            _subscribe(broker, engine, PAY_A, "payment-service", 0)
            for i in range(6):
                client.create_instance("order-process", payload={"orderId": i})
            broker.run_until_idle()
            clock.advance(300)
            engine.increase_job_credits(PAY_A, 2)
            before = {
                n: event_count(n) for n in (
                    "serving_backlog_park_wait_seconds_total",
                    "serving_backlog_parked_walked_total",
                    "serving_backlog_activations_total",
                    "serving_backlog_sweeps_total",
                )
            }
            clock_of_tick = tracing.PhaseClock()
            with clock_of_tick.phase("tick"), engine.on_clock(clock_of_tick):
                out = engine.device_backlog_activations()
            observe_phases(clock_of_tick, "ticks")
            assert len(out) == 2
            grew = {n: event_count(n) - v for n, v in before.items()}
            assert grew["serving_backlog_activations_total"] == 2
            assert grew["serving_backlog_sweeps_total"] == 1
            assert grew["serving_backlog_parked_walked_total"] == 2
            assert grew["serving_backlog_park_wait_seconds_total"] == pytest.approx(0.6)
        finally:
            broker.close()

    @pytest.mark.parametrize("calls", [[(PAY_A, 1)], [(PAY_A, 3), (SHIP, 2), (PAY_A, 1)]])
    def test_credit_returns_are_counted_once_a_call(self, calls):
        """``serving_job_credit_returns_total`` counts calls, not credits,
        and ``serving_job_credit_return_seconds_total`` their seconds; the
        credits land on the subscription that returned them."""
        broker, _client, _clock, engine = _device_broker()
        try:
            _subscribe(broker, engine, PAY_A, "payment-service", 0)
            _subscribe(broker, engine, SHIP, "shipping-service", 0)
            returns = event_count("serving_job_credit_returns_total")
            seconds = event_count("serving_job_credit_return_seconds_total")
            for key, n in calls:
                engine.increase_job_credits(key, n)
            assert event_count("serving_job_credit_returns_total") == returns + len(calls)
            assert event_count("serving_job_credit_return_seconds_total") > seconds
            s = engine.state
            by_key = dict(zip(
                np.asarray(s.sub_key).tolist(), np.asarray(s.sub_credits).tolist()
            ))
            for key in (PAY_A, SHIP):
                assert by_key[key] == sum(n for k, n in calls if k == key)
        finally:
            broker.close()

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
