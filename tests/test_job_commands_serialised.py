"""Commands on ONE job (or timer) in one wave serialise in log order
(ISSUE 27): the device engine steps waves that hold duplicate and mixed
commands on shared keys and must write, record for record, what the oracle
(``engine/interpreter.py``, one record after another) writes, and leave the
same job rows; and the backlog sweep leaves a job alone while an activation
of it is on its way, without losing a credit.

``kernel._first_per_key`` has two forms (comparison triangle up to 2,048
rows, stable sort above): the wide cases step one segment of more than
2,048 rows.
"""

import dataclasses
import random

import numpy as np
import pytest

from zeebe_tpu.engine.interpreter import JobSubscription, WorkflowRepository
from zeebe_tpu.gateway import ZeebeClient
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import JobIntent as JI, TimerIntent as TI
from zeebe_tpu.protocol.metadata import RecordMetadata
from zeebe_tpu.protocol.records import JobRecord, Record
from zeebe_tpu.runtime import Broker, ControlledClock
from zeebe_tpu.testing.parity import record_signature
from zeebe_tpu.tpu import TpuPartitionEngine
from zeebe_tpu.tpu import state as state_mod

SUB = 7  # the subscriber key of the one job subscription
JOBS = 6


def order_process():
    return (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )


def timer_process():
    return (
        Bpmn.create_process("wait")
        .start_event("start")
        .timer_catch_event("timer", duration_ms=60_000)
        .end_event("end")
        .done()
    )


class Pair:
    """The oracle's broker and the device engine's, fed the same records:
    the oracle steps them one by one, the device engine in waves of
    ``wave_size``."""

    def __init__(self, wave_size: int, capacity: int = 1 << 12):
        self.brokers = []
        for tpu in (False, True):
            clock = ControlledClock(start_ms=1_000_000)
            if tpu:
                repo = WorkflowRepository()
                broker = Broker(
                    num_partitions=1, clock=clock,
                    engine_factory=lambda pid: TpuPartitionEngine(
                        pid, 1, repository=repo, clock=clock,
                        capacity=capacity,
                    ),
                )
                broker.wave_size = wave_size
            else:
                broker = Broker(num_partitions=1, clock=clock)
            self.brokers.append(broker)
        self.pushes = [[], []]

    def each(self, fn):
        for i, broker in enumerate(self.brokers):
            fn(broker, i)
            broker.run_until_idle()

    def subscribe(self, credits: int):
        def go(broker, i):
            broker.on_push(SUB, lambda pid, rec, i=i: self.pushes[i].append(rec.key))
            backlog = broker.partitions[0].engine.add_job_subscription(
                JobSubscription(
                    subscriber_key=SUB, job_type="payment-service",
                    worker="w", timeout=300_000, credits=credits,
                )
            )
            if backlog:
                broker.partitions[0].log.append(backlog)

        self.each(go)

    def append(self, commands):
        """One append of ``commands`` ((intent, key, retries) each): they
        are committed together, so the device engine meets them in one
        wave (as far as ``wave_size`` reaches)."""

        def go(broker, _i):
            created = {
                r.key: r.value for r in broker.records(0)
                if r.metadata.value_type == ValueType.JOB
                and r.metadata.record_type == RecordType.EVENT
                and r.metadata.intent == int(JI.CREATED)
            }
            records = []
            for intent, key, retries in commands:
                value = (
                    created[key].copy() if key in created
                    else JobRecord(type="payment-service")
                )
                md = RecordMetadata(
                    record_type=RecordType.COMMAND, value_type=ValueType.JOB,
                    intent=int(intent),
                )
                if intent == JI.ACTIVATE:
                    value.worker = "w"
                    value.deadline = 1_300_000
                    md.request_stream_id = SUB
                elif intent == JI.COMPLETE:
                    value.payload = {"paid": 1}
                elif intent in (JI.FAIL, JI.UPDATE_RETRIES):
                    value.retries = retries
                records.append(Record(key=key, metadata=md, value=value))
            broker.partitions[0].log.append(records)

        self.each(go)

    def job_keys(self):
        keys = [
            sorted(
                r.key for r in broker.records(0)
                if r.metadata.value_type == ValueType.JOB
                and r.metadata.intent == int(JI.CREATED)
                and r.metadata.record_type == RecordType.EVENT
            )
            for broker in self.brokers
        ]
        assert keys[0] == keys[1]
        return keys[0]

    def assert_same(self):
        oracle = record_signature(self.brokers[0].records(0))
        device = record_signature(self.brokers[1].records(0))
        for i, (a, b) in enumerate(zip(oracle, device)):
            assert a == b, f"record {i}:\n  oracle: {a}\n  device: {b}"
        assert len(oracle) == len(device)
        assert self.pushes[0] == self.pushes[1]
        # the job rows that are left, and the credits
        host = self.brokers[0].partitions[0].engine
        engine = self.brokers[1].partitions[0].engine
        assert engine.host_records_by_kind.get((int(ValueType.JOB), -1), 0) == 0
        s = engine.state
        i32 = np.asarray(s.job_i32)
        i64 = state_mod.host_i64(s.job_i64)
        rows = {
            int(i64[slot, state_mod.JBL_KEY]): (
                int(i32[slot, state_mod.JB_STATE]),
                int(i32[slot, state_mod.JB_RETRIES]),
            )
            for slot in np.nonzero(i32[:, state_mod.JB_STATE] != -1)[0]
        }
        assert rows == {
            key: (job.state, job.record.retries)
            for key, job in host.jobs.items()
        }
        valid = np.asarray(s.sub_valid)
        assert [int(c) for c in np.asarray(s.sub_credits)[valid]] == [
            sub.credits for sub in host.job_subscriptions
        ]
        assert not engine._assigning, engine._assigning

    def close(self):
        for broker in self.brokers:
            broker.close()


def started(pair: Pair, credits: int = 0):
    pair.each(lambda b, _i: ZeebeClient(b).deploy_model(order_process()))
    pair.subscribe(credits)

    def create(broker, _i):
        client = ZeebeClient(broker)
        for i in range(JOBS):
            client.create_instance("order-process", payload={"orderId": i})

    pair.each(create)
    return pair.job_keys()


A, C, F, T, U, X = (
    JI.ACTIVATE, JI.COMPLETE, JI.FAIL, JI.TIME_OUT, JI.UPDATE_RETRIES,
    JI.CANCEL,
)

# (name, waves): a wave is a list of (intent, job index, retries); job
# index -1 is a key no job has
CASES = [
    ("activate_x3", [[(A, 0, 0)] * 3]),
    ("activate_across_two", [[(A, 0, 0)], [(A, 0, 0), (A, 0, 0)]]),
    ("complete_x3", [[(A, 0, 0)], [(C, 0, 0)] * 3]),
    ("complete_unactivated_x2", [[(C, 0, 0), (C, 0, 0)]]),
    ("activate_complete", [[(A, 0, 0), (C, 0, 0)]]),
    ("activate_complete_activate", [[(A, 0, 0), (A, 0, 0), (C, 0, 0), (A, 0, 0), (C, 0, 0)]]),
    ("fail_x2", [[(A, 0, 0)], [(F, 0, 2), (F, 0, 1)]]),
    ("fail_then_retries", [[(A, 0, 0), (F, 0, 0), (U, 0, 3), (U, 0, 0), (U, 0, 2), (A, 0, 0)]]),
    ("time_out_x2", [[(A, 1, 0)], [(T, 1, 0), (T, 1, 0), (C, 1, 0)]]),
    ("time_out_activate", [[(A, 1, 0), (T, 1, 0), (A, 1, 0), (T, 1, 0)]]),
    ("cancel_x2", [[(X, 2, 0), (X, 2, 0), (A, 2, 0)]]),
    ("cancel_activated", [[(A, 2, 0), (X, 2, 0), (C, 2, 0), (X, 2, 0)]]),
    ("unknown_key", [[(A, -1, 0), (A, -1, 0), (C, -1, 0), (X, -1, 0), (X, -1, 0)]]),
    ("interleaved_keys", [[(A, 0, 0), (A, 1, 0), (A, 0, 0), (C, 1, 0), (A, 2, 0), (C, 0, 0), (C, 1, 0), (A, 1, 0)]]),
]


def seeded_waves(seed: int):
    """Two waves of 40 commands over four jobs and a key that is no job's,
    weighted towards what a broker really meets: repeated ACTIVATEs and
    COMPLETEs."""
    rng = random.Random(seed)
    intents = [A] * 6 + [C] * 4 + [F] * 2 + [T] * 2 + [U] * 2 + [X]
    return [
        [
            (rng.choice(intents), rng.choice([0, 0, 1, 1, 2, 3, -1]), rng.choice([0, 1, 3]))
            for _ in range(40)
        ]
        for _wave in range(2)
    ]


def run_case(waves, wave_size: int, widen_with: int = 0, credits: int = 0):
    pair = Pair(wave_size)
    try:
        keys = started(pair, credits)
        key_of = lambda j: keys[j] if j >= 0 else 999_999  # noqa: E731
        engine = pair.brokers[1].partitions[0].engine
        staged = []
        stage = engine._stage

        def spy_stage(records, **kwargs):
            staged.append(len(records))
            return stage(records, **kwargs)

        engine._stage = spy_stage
        for n, wave in enumerate(waves):
            commands = [(i, key_of(j), r) for i, j, r in wave]
            if widen_with and n == 0:
                # one segment of more than 2,048 rows: ACTIVATEs of one
                # job ahead of the case's own rows (same intent, so the
                # engine keeps them in one segment)
                commands = [(A, keys[JOBS - 1], 0)] * widen_with + commands
            pair.append(commands)
        pair.assert_same()
        # the form of _first_per_key the case was meant for
        assert (max(staged) > 2048) == bool(widen_with), staged
    finally:
        pair.close()


@pytest.mark.parametrize("name,waves", CASES, ids=[c[0] for c in CASES])
def test_shared_job_keys_in_a_wave_step_as_the_oracle(name, waves):
    run_case(waves, wave_size=64)


@pytest.mark.parametrize("seed", [27, 77, 80, 4511])
def test_seeded_job_command_waves_step_as_the_oracle(seed):
    run_case(seeded_waves(seed), wave_size=64, credits=seed % 3)


@pytest.mark.parametrize(
    "name,waves",
    [CASES[5], ("seeded_27", seeded_waves(27))],
    ids=["activate_complete_activate", "seeded_27"],
)
def test_sort_form_of_first_per_key(name, waves):
    """The same above 2,048 rows, where ``_first_per_key`` sorts."""
    run_case(waves, wave_size=4096, widen_with=2100)


def test_timer_fires_once_per_wave():
    """TRIGGER x 3 and a CANCEL on one due timer in one wave: it fires
    once, the later TRIGGERs are rejected, the CANCEL is the oracle's
    silent no-op."""
    pair = Pair(64)
    try:
        pair.each(lambda b, _i: ZeebeClient(b).deploy_model(timer_process()))
        pair.each(
            lambda b, _i: ZeebeClient(b).create_instance("wait", payload={"a": 1})
        )

        def trigger(broker, _i):
            created = [
                r for r in broker.records(0)
                if r.metadata.value_type == ValueType.TIMER
                and r.metadata.record_type == RecordType.EVENT
                and r.metadata.intent == int(TI.CREATED)
            ]
            assert len(created) == 1
            records = [
                Record(
                    key=created[0].key, value=created[0].value.copy(),
                    metadata=RecordMetadata(
                        record_type=RecordType.COMMAND,
                        value_type=ValueType.TIMER, intent=int(intent),
                    ),
                )
                for intent in (TI.TRIGGER, TI.TRIGGER, TI.CANCEL, TI.TRIGGER)
            ]
            broker.partitions[0].log.append(records)

        pair.each(trigger)
        oracle = record_signature(pair.brokers[0].records(0))
        device = record_signature(pair.brokers[1].records(0))
        assert oracle == device
        triggered = [
            r for r in pair.brokers[1].records(0)
            if r.metadata.value_type == ValueType.TIMER
            and r.metadata.intent == int(TI.TRIGGERED)
        ]
        assert len(triggered) == 1
    finally:
        pair.close()


class TestSweepKnowsWhatIsInFlight:
    """(b) the tick's sweep while an ACTIVATE is appended and not stepped."""

    def _parked(self, credits_later: int):
        """A device broker with JOBS jobs CREATED while the subscription
        had no credit (so the pool let them pass), then given credits."""
        clock = ControlledClock(start_ms=1_000_000)
        repo = WorkflowRepository()
        broker = Broker(
            num_partitions=1, clock=clock,
            engine_factory=lambda pid: TpuPartitionEngine(
                pid, 1, repository=repo, clock=clock, capacity=1 << 10
            ),
        )
        client = ZeebeClient(broker)
        client.deploy_model(order_process())
        engine = broker.partitions[0].engine
        engine.add_job_subscription(
            JobSubscription(
                subscriber_key=SUB, job_type="payment-service", worker="w",
                timeout=300_000, credits=0,
            )
        )
        for i in range(JOBS):
            client.create_instance("order-process", payload={"orderId": i})
        broker.run_until_idle()
        assert not engine._assigning  # every CREATED event was stepped
        engine.increase_job_credits(SUB, credits_later)
        return broker, engine

    @staticmethod
    def _credits(engine) -> int:
        s = engine.state
        return int(np.asarray(s.sub_credits)[np.asarray(s.sub_valid)].sum())

    def test_successive_ticks_append_one_activate_a_job(self):
        broker, engine = self._parked(credits_later=4)
        try:
            first = engine.device_backlog_activations()
            assert len(first) == 4 and self._credits(engine) == 0
            engine.increase_job_credits(SUB, 10)
            # the first four are appended, not stepped: two more ticks
            second = engine.device_backlog_activations()
            third = engine.device_backlog_activations()
            keys = [r.key for r in first + second + third]
            assert len(keys) == JOBS == len(set(keys))
            assert third == []
            assert self._credits(engine) == 10 - 2  # none taken for a skip
            broker.partitions[0].log.append(first + second)
            broker.run_until_idle()
            assert not engine._assigning
            activated = [
                r.key for r in broker.records(0)
                if r.metadata.value_type == ValueType.JOB
                and r.metadata.intent == int(JI.ACTIVATED)
            ]
            assert sorted(activated) == sorted(keys)
            assert self._credits(engine) == 8
            assert engine.device_backlog_activations() == []
        finally:
            broker.close()

    def test_credits_conserved_across_accept_reject_and_restore(self):
        broker, engine = self._parked(credits_later=3)
        try:
            handed = engine.device_backlog_activations()
            assert len(handed) == 3 and self._credits(engine) == 0
            # a snapshot between the append and the wave. Subscriptions are
            # not in a snapshot (workers subscribe again after a failover)
            # and neither is what is in flight: the restored engine hands
            # the same jobs out again to the returning worker, and when the
            # late duplicates step they are rejected and return the credits
            # its backlog scan took
            snap = engine.snapshot_state()
            clock = ControlledClock(start_ms=1_000_000)
            restored = TpuPartitionEngine(
                0, 1, repository=WorkflowRepository(), clock=clock,
                capacity=1 << 10,
            )
            restored.restore_state(snap)
            assert not restored._assigning
            again = restored.add_job_subscription(
                JobSubscription(
                    subscriber_key=SUB, job_type="payment-service",
                    worker="w", timeout=300_000, credits=3,
                )
            )
            assert sorted(r.key for r in again) == sorted(r.key for r in handed)
            assert self._credits(restored) == 0
            # a tick with two credits more: those three are on their way,
            # so the sweep takes the next two jobs and no credit for a skip
            restored.increase_job_credits(SUB, 2)
            swept = restored.device_backlog_activations()
            assert len(swept) == 2 and self._credits(restored) == 0
            assert not {r.key for r in swept} & {r.key for r in handed}
            wave = [
                dataclasses.replace(r, position=10_000 + i)
                for i, r in enumerate(handed + again)
            ]
            results = restored.process_wave(wave)
            written = [w for res in results for w in res.written]
            kinds = sorted(
                (int(_md(w).record_type), int(_md(w).intent)) for w in written
            )
            assert kinds == sorted(
                [(int(RecordType.EVENT), int(JI.ACTIVATED))] * 3
                + [(int(RecordType.COMMAND_REJECTION), int(JI.ACTIVATE))] * 3
            )
            # three accepted (their credits stay out until the worker
            # returns them), three rejected (theirs are back)
            assert self._credits(restored) == 3
            assert restored._assigning == {r.key for r in swept}
        finally:
            broker.close()


def _md(written):
    if type(written) is tuple:
        written = written[0].row(written[1])
    return written.metadata
