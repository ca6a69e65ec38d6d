"""A worker's credit return stops at the host (ISSUE 35): the engine keeps
a host side of its job-subscription table (``_HostSubscriptions``), a
return is host arithmetic on it (``_credit_delta``), and the device's
credit column is brought up to date by one launch of ``engine.credit_flush``
before its next reader: a step, the due probe, the sweep, a subscription
method, a snapshot, a read of ``engine.state`` (zeebe_tpu/tpu/engine.py).
"""

import dataclasses
import random

import numpy as np
import pytest

import jax

from zeebe_tpu import tpu as _tpu  # noqa: F401  (enables x64)
from zeebe_tpu.engine.interpreter import JobSubscription, WorkflowRepository
from zeebe_tpu.gateway import ZeebeClient
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import JobIntent as JI
from zeebe_tpu.runtime import Broker, ControlledClock
from zeebe_tpu.runtime.metrics import event_count
from zeebe_tpu.tpu import engine as engine_mod
from zeebe_tpu.tpu import kernel
from zeebe_tpu.tpu.engine import PROBE_JOB_BACKLOG, TpuPartitionEngine

PAY_A, PAY_B, SHIP = 7, 8, 9  # subscriber keys: two of one type, one of another
UNKNOWN = 4242                # a key no slot holds
TYPES = {PAY_A: "payment-service", PAY_B: "payment-service", SHIP: "shipping-service"}
FLUSHES = "serving_job_credit_flushes_total"
FLUSH_SECONDS = "serving_job_credit_flush_seconds_total"


def _device_broker(partitions=1, **placement):
    clock = ControlledClock(start_ms=1_000_000)
    repo = WorkflowRepository()
    broker = Broker(
        num_partitions=partitions, clock=clock,
        engine_factory=lambda pid: TpuPartitionEngine(
            pid, partitions, repository=repo, clock=clock, capacity=256,
            sub_capacity=8, **placement,
        ),
    )
    client = ZeebeClient(broker)
    client.deploy_model(
        Bpmn.create_process("order-process").start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end").done()
    )
    client.deploy_model(
        Bpmn.create_process("ship").start_event("start")
        .service_task("ship-it", type="shipping-service")
        .end_event("end").done()
    )
    engines = [broker.partitions[p].engine for p in range(partitions)]
    return broker, client, engines


def _subscribe(broker, engine, key, credits=0):
    backlog = engine.add_job_subscription(
        JobSubscription(
            subscriber_key=key, job_type=TYPES[key], worker=f"w{key}",
            timeout=300_000, credits=credits,
        )
    )
    if backlog:
        broker.partitions[engine.partition_id].log.append(backlog)
    broker.run_until_idle()


def _credits_by_key(engine):
    s = engine.state
    valid = np.asarray(s.sub_valid)
    return dict(zip(
        np.asarray(s.sub_key)[valid].tolist(),
        np.asarray(s.sub_credits)[valid].tolist(),
    ))


def _job_log(broker, partition=0):
    return [
        (r.metadata.record_type, r.metadata.intent, r.key,
         r.metadata.request_stream_id)
        for r in broker.records(partition)
        if r.metadata.value_type == ValueType.JOB
    ]


def _activated(broker, partition=0):
    return [
        r for r in broker.records(partition)
        if r.metadata.value_type == ValueType.JOB
        and r.metadata.record_type == RecordType.EVENT
        and r.metadata.intent == int(JI.ACTIVATED)
    ]


def _empty_step(engine):
    """One launch of the step over a wave of no valid rows: it reads the
    state as a wave's launch does and changes nothing in it."""
    if engine.graph is None:  # the deployment went through another partition
        engine._recompile()
    batch = engine._stage([], pad_to=64)
    engine._run_step(batch, np.int64(engine.clock()))


class _Crossings:
    """Counts the calls that cross the host-device boundary in the
    subscription methods and the sweep: ``jax.device_put``,
    ``jax.device_get`` and the flush program's launches. (A bare
    ``np.asarray`` of a device array is not seen here: where a test must
    show that NO leaf is read, it puts ``_Unreadable`` in the state's
    place.)"""

    def __init__(self, monkeypatch):
        self.put = self.get = self.arrays_got = self.flush = 0
        real_put, real_get = jax.device_put, jax.device_get
        real_flush = engine_mod._credit_flush_jit

        def put(*a, **k):
            self.put += 1
            return real_put(*a, **k)

        def get(*a, **k):
            self.get += 1
            self.arrays_got += len(jax.tree.leaves(a[0]))
            return real_get(*a, **k)

        def flush(*a, **k):
            self.flush += 1
            return real_flush(*a, **k)

        monkeypatch.setattr(jax, "device_put", put)
        monkeypatch.setattr(jax, "device_get", get)
        monkeypatch.setattr(engine_mod, "_credit_flush_jit", flush)

    def total(self):
        return self.put + self.get + self.flush


@pytest.mark.parametrize("partitions", [1, 4])
@pytest.mark.parametrize("returns", [1, 11, 300])
def test_returns_then_a_step_equal_each_return_written_at_once(returns, partitions):
    """N returns to three subscribers (and to a key nobody holds), then a
    step: every leaf of the state is bit for bit what it was, but for
    ``sub_credits``, which is the parent's rule a return, ``sub_credits +
    where(sub_key == key, n, 0)``, in numpy on a fetched copy. One flush
    an engine, whatever N."""
    broker, _client, engines = _device_broker(partitions)
    try:
        rng = random.Random(1000 * returns + partitions)
        for engine in engines:
            for key in (PAY_A, PAY_B, SHIP):
                _subscribe(broker, engine, key, credits=rng.randrange(3))
            _empty_step(engine)
        before = [jax.device_get(e.state) for e in engines]
        expected = [np.array(s.sub_credits) for s in before]
        flushes = event_count(FLUSHES)
        touched = set()
        for _ in range(returns):
            p = rng.randrange(partitions)
            key = rng.choice((PAY_A, PAY_B, SHIP, SHIP, UNKNOWN))
            n = rng.randrange(1, 4)
            engines[p].increase_job_credits(key, n)
            expected[p] = expected[p] + np.where(
                np.asarray(before[p].sub_key) == key, n, 0
            ).astype(np.int32)
            if key != UNKNOWN:
                touched.add(p)
        assert event_count(FLUSHES) == flushes  # no reader yet
        for engine in engines:
            _empty_step(engine)
        assert event_count(FLUSHES) == flushes + len(touched)
        for p, engine in enumerate(engines):
            after = jax.device_get(engine.state)
            paths_before = jax.tree_util.tree_leaves_with_path(before[p])
            for (path, was), now in zip(paths_before, jax.tree_util.tree_leaves(after)):
                name = jax.tree_util.keystr(path)
                want = expected[p] if name == ".sub_credits" else was
                assert now.dtype == want.dtype, name
                np.testing.assert_array_equal(now, want, err_msg=name)
        assert event_count(FLUSHES) == flushes + len(touched)  # reads flush nothing
    finally:
        broker.close()


class _Unreadable:
    """In the place of the engine's state: any read of a leaf raises."""

    def __getattr__(self, name):
        raise AssertionError(f"the return read state.{name}")


@pytest.mark.parametrize("returns", [1, 11])
def test_a_return_is_host_work_only(monkeypatch, returns):
    """A return crosses nothing, launches nothing and reads no leaf of the
    state, under a guard that refuses every transfer; the flush comes with
    the next reader, once, and is a phase of that reader's cycle."""
    broker, _client, (engine,) = _device_broker()
    try:
        _subscribe(broker, engine, PAY_A)
        _subscribe(broker, engine, SHIP)
        flushes, seconds = event_count(FLUSHES), event_count(FLUSH_SECONDS)
        crossings = _Crossings(monkeypatch)
        state, engine._state = engine._state, _Unreadable()
        with jax.transfer_guard("disallow_explicit"):
            for i in range(returns):
                engine.increase_job_credits((PAY_A, SHIP)[i % 2], 2)
        engine._state = state
        assert crossings.total() == 0
        assert event_count(FLUSHES) == flushes
        assert engine._credit_delta is not None
        engine.deadlines_due_probe()  # a reader, outside every cycle
        assert crossings.flush == 1 and crossings.get == 0
        assert event_count(FLUSHES) == flushes + 1
        assert event_count(FLUSH_SECONDS) > seconds
        assert engine._credit_delta is None
        engine.deadlines_due_probe()  # nothing pending: no second flush
        assert crossings.flush == 1
        assert _credits_by_key(engine) == {
            PAY_A: 2 * ((returns + 1) // 2), SHIP: 2 * (returns // 2),
        }
    finally:
        broker.close()


@pytest.mark.parametrize("reader", ["wave", "tick"])
def test_the_flush_is_a_phase_of_the_cycle_that_reads_the_column(reader):
    """Inside a wave or a tick the flush is cut out of the phase it runs
    in, on that cycle's clock: one ``credit_flush`` slice, one count."""
    from zeebe_tpu import tracing

    broker, _client, (engine,) = _device_broker()
    try:
        _subscribe(broker, engine, PAY_A)
        engine.increase_job_credits(PAY_A, 3)
        clock = tracing.PhaseClock(slices=[])
        flushes = event_count(FLUSHES)
        with engine.on_clock(clock), clock.phase("tick" if reader == "tick" else "route"):
            if reader == "tick":
                engine.deadlines_due_probe()
            else:
                _empty_step(engine)
        assert clock.counts.get("credit_flushes") == 1
        assert clock.us.get("credit_flush", 0) > 0
        names = [s[0] for s in clock.slices]
        assert names.count("credit_flush") == 1
        outer = "tick" if reader == "tick" else "route"
        at = names.index("credit_flush")
        assert outer in names[:at]  # cut out of the phase that was open
        for prev, cur in zip(clock.slices, clock.slices[1:]):
            assert cur[1] >= prev[2], (prev, cur)  # self times: no overlap
        # the cycle's clock is flushed by its owner, not by the engine
        assert event_count(FLUSHES) == flushes
        assert _credits_by_key(engine) == {PAY_A: 3}
    finally:
        broker.close()


@pytest.mark.parametrize("late_return", [False, True])
def test_a_freed_slot_carries_no_credit_to_its_next_owner(late_return):
    """Return, remove, add of another key into the freed slot: the new
    subscriber holds exactly its own credits, also where a return of the
    removed subscriber arrives after its removal."""
    broker, _client, (engine,) = _device_broker()
    try:
        _subscribe(broker, engine, PAY_A, credits=1)
        _subscribe(broker, engine, SHIP, credits=2)
        slot = int(np.nonzero(engine._subs.key == PAY_A)[0][0])
        engine.increase_job_credits(PAY_A, 5)
        engine.remove_job_subscription(PAY_A)
        assert engine._credit_delta is None  # the removal read the state
        if late_return:
            engine.increase_job_credits(PAY_A, 7)
        _subscribe(broker, engine, PAY_B, credits=3)
        assert int(np.nonzero(engine._subs.key == PAY_B)[0][0]) == slot
        engine.increase_job_credits(PAY_B, 1)
        assert _credits_by_key(engine) == {PAY_B: 4, SHIP: 2}
        assert engine._credit_delta is None
    finally:
        broker.close()


@pytest.mark.parametrize("subscribed", [False, True])
def test_a_return_for_an_unknown_key_changes_nothing_on_the_device(subscribed):
    broker, _client, (engine,) = _device_broker()
    try:
        if subscribed:
            _subscribe(broker, engine, PAY_A, credits=2)
        before = np.array(engine.state.sub_credits)
        flushes = event_count(FLUSHES)
        returns = event_count("serving_job_credit_returns_total")
        engine.increase_job_credits(UNKNOWN, 3)
        assert engine._credit_delta is None
        _empty_step(engine)
        engine.deadlines_due_probe()
        assert event_count(FLUSHES) == flushes
        assert event_count("serving_job_credit_returns_total") == returns + 1
        np.testing.assert_array_equal(np.asarray(engine.state.sub_credits), before)
    finally:
        broker.close()


@pytest.mark.parametrize("families", [None, "dirty"])
def test_a_snapshot_right_after_a_return_carries_the_credit(families):
    """The snapshot reads the state like any reader (a delta take too:
    the return marked its family dirty); a restore leaves no subscription
    on either side and no return pending."""
    broker, _client, (engine,) = _device_broker()
    try:
        _subscribe(broker, engine, PAY_A, credits=1)
        _subscribe(broker, engine, SHIP, credits=0)
        engine.snapshot_mark_clean()
        engine.increase_job_credits(SHIP, 4)
        if families == "dirty":
            families = engine.snapshot_dirty_families()
            assert "d/sub" in families
        snap = engine.snapshot_state(families)
        by_key = dict(zip(
            snap["arrays"]["sub_key"].tolist(), snap["arrays"]["sub_credits"].tolist()
        ))
        assert by_key[PAY_A] == 1 and by_key[SHIP] == 4
        engine.increase_job_credits(PAY_A, 2)  # pending when the restore comes
        full = engine.snapshot_state() if families is not None else snap
        restored = TpuPartitionEngine(capacity=256, sub_capacity=8)
        for target in (restored, engine):
            target.restore_state(full)
            assert target._credit_delta is None
            s = target.state
            assert not np.asarray(s.sub_valid).any()
            assert not np.asarray(s.sub_credits).any()
            assert (np.asarray(s.sub_key) == -1).all()
            subs = target._subscriptions()
            assert not subs.valid.any() and (subs.key == -1).all()
            # a return that arrives for the dropped subscription is nobody's
            target.increase_job_credits(PAY_A, 1)
            assert target._credit_delta is None
    finally:
        broker.close()


@pytest.mark.parametrize("returned", [1, 2, 3])
def test_the_sweep_hands_out_a_returned_credit_in_the_same_tick(monkeypatch, returned):
    """Jobs park while no credit is free; a return, then the tick's probe
    and sweep: the probe sees the credit, the sweep hands out that many
    jobs, lowest keys first, and fetches ONE array (the credit column)."""
    broker, client, (engine,) = _device_broker()
    try:
        _subscribe(broker, engine, PAY_A)
        for i in range(4):
            client.create_instance("order-process", payload={"orderId": i})
        broker.run_until_idle()
        parked = sorted(engine._parked)
        assert len(parked) == 4 and not _activated(broker)
        engine.increase_job_credits(PAY_A, returned)
        assert int(engine.deadlines_due_probe()) & PROBE_JOB_BACKLOG
        crossings = _Crossings(monkeypatch)
        out = engine.device_backlog_activations()
        assert crossings.get == 1 and crossings.arrays_got == 1
        assert crossings.flush == 0  # the probe was the first reader
        assert crossings.put == 1    # the credits and the cursor, together
        assert [r.key for r in out] == parked[:returned]
        assert {r.metadata.request_stream_id for r in out} == {PAY_A}
        assert _credits_by_key(engine) == {PAY_A: 0}
        broker.partitions[0].log.append(out)
        broker.run_until_idle()
        assert [r.key for r in _activated(broker)] == parked[:returned]
    finally:
        broker.close()


def test_a_sweep_with_no_subscription_touches_nothing(monkeypatch):
    """The subscriptions are known on the host: a sweep that finds none
    valid returns before it reads a leaf of the device's state."""
    broker, client, (engine,) = _device_broker()
    try:
        _subscribe(broker, engine, PAY_A)
        client.create_instance("order-process", payload={"orderId": 1})
        broker.run_until_idle()
        assert engine._parked
        engine.remove_job_subscription(PAY_A)
        crossings = _Crossings(monkeypatch)
        state, engine._state = engine._state, _Unreadable()
        assert engine.device_backlog_activations() == []
        engine._state = state
        assert crossings.total() == 0
    finally:
        broker.close()


def test_a_state_from_outside_is_read_once(monkeypatch):
    """An engine handed a state it did not write learns that table's
    subscriptions by one fetch at their first use, and the returns that
    were pending for the table it held before are dropped with it."""
    broker, _client, (engine,) = _device_broker()
    try:
        _subscribe(broker, engine, PAY_A, credits=1)
        engine.increase_job_credits(PAY_A, 2)
        other = TpuPartitionEngine(capacity=256, sub_capacity=8)
        _subscribe_direct(other, SHIP, credits=0)
        other.increase_job_credits(SHIP, 9)  # pending for the table it holds
        other.state = engine.state
        assert other._subs is None and other._credit_delta is None
        crossings = _Crossings(monkeypatch)
        other.increase_job_credits(PAY_A, 4)
        assert crossings.get == 1 and crossings.arrays_got == 6
        learned = other._subs
        assert learned.key.tolist().count(PAY_A) == 1 and learned.valid.sum() == 1
        other.increase_job_credits(PAY_A, 1)
        assert other._subs is learned and crossings.get == 1
        assert _credits_by_key(other) == {PAY_A: 8}
    finally:
        broker.close()


def _subscribe_direct(engine, key, credits):
    engine.add_job_subscription(
        JobSubscription(
            subscriber_key=key, job_type=TYPES[key], worker=f"w{key}",
            timeout=300_000, credits=credits,
        )
    )


# -- any interleaving equals the parent's rule ---------------------------------

OPS = ("return", "return", "return", "return", "wave", "wave", "tick", "tick",
       "tick", "add", "remove", "assign", "snapshot_restore", "read")


def _apply(op, arg, broker, client, engine, eager):
    """One operation of a drawn sequence. ``eager`` reads the state after
    every return: the credit is on the device at once, which is the
    parent's rule (a return wrote the column itself)."""
    if op == "return":
        key, n = arg
        engine.increase_job_credits(key, n)
        if eager:
            engine.state  # noqa: B018 - the read is the flush
    elif op == "wave":
        kind, n = arg
        for i in range(n):
            client.create_instance(kind, payload={"orderId": i})
        broker.run_until_idle()
    elif op == "tick":
        broker.tick()
        broker.run_until_idle()
    elif op == "add":
        key, credits = arg
        _subscribe(broker, engine, key, credits)
    elif op == "remove":
        engine.remove_job_subscription(arg)
    elif op == "assign":
        # a state the engine did not write, as a test's fixture assigns it
        engine.state = dataclasses.replace(engine.state)
    elif op == "snapshot_restore":
        engine.restore_state(engine.snapshot_state())
        broker.run_until_idle()
    elif op == "read":
        _credits_by_key(engine)


def _draw(rng):
    op = rng.choice(OPS)
    if op == "return":
        return op, (rng.choice((PAY_A, PAY_B, SHIP, UNKNOWN)), rng.randrange(1, 3))
    if op == "wave":
        return op, (rng.choice(("order-process", "ship")), rng.randrange(1, 4))
    if op == "add":
        return op, (rng.choice((PAY_A, PAY_B, SHIP)), rng.randrange(0, 3))
    if op == "remove":
        return op, rng.choice((PAY_A, PAY_B, SHIP))
    return op, None


@pytest.mark.parametrize("seed", range(6))
def test_any_interleaving_equals_each_return_written_at_once(seed):
    """Returns, waves, ticks (probe and sweep), subscriptions added and
    removed, a state assigned from outside, snapshot -> restore and plain
    reads, in a drawn order, on two brokers: one leaves a return on the
    host until a reader comes, the other writes each return at once. The
    subscription table as a reader sees it and the jobs' records are the
    same at the end, and each return was applied once."""
    rng = random.Random(35_000 + seed)
    # jobs of both types park first (one credit between them), so that a
    # returned credit has someone waiting for it
    ops = [("wave", ("order-process", 3)), ("wave", ("ship", 3))]
    ops += [_draw(rng) for _ in range(48)]
    twins = []
    for eager in (False, True):
        broker, client, (engine,) = _device_broker()
        _subscribe(broker, engine, PAY_A, credits=1)
        _subscribe(broker, engine, SHIP, credits=0)
        twins.append((broker, client, engine, eager))
    try:
        for op, arg in ops:
            for broker, client, engine, eager in twins:
                _apply(op, arg, broker, client, engine, eager)
        (lazy_broker, _c, lazy, _e), (eager_broker, _c2, eager_engine, _e2) = twins
        was_pending = lazy._credit_delta is not None
        table = [
            {
                name: np.asarray(getattr(e.state, name)).tolist()
                for name in ("sub_key", "sub_type", "sub_worker", "sub_credits",
                             "sub_timeout", "sub_valid", "sub_rr")
            }
            for e in (lazy, eager_engine)
        ]
        assert table[0] == table[1]
        assert lazy._credit_delta is None or not was_pending
        assert _job_log(lazy_broker) == _job_log(eager_broker)
        # the host side is the device's, column for column
        subs, s = lazy._subscriptions(), lazy.state
        for name in ("key", "type", "worker", "timeout", "valid"):
            np.testing.assert_array_equal(
                getattr(subs, name), np.asarray(getattr(s, "sub_" + name)), name
            )
        assert subs.rr == int(s.sub_rr)
    finally:
        for broker, *_ in twins:
            broker.close()


# -- one compiled signature ----------------------------------------------------


def _cache_sizes(engine):
    programs = {
        "step": kernel.step_jit,
        "sharded step": engine._state_step,
        "routed step": engine._state_step_routed,
        "fallback step": engine._state_step_fallback,
        "due_probe": engine_mod._due_probe_jit,
        "credit_flush": engine_mod._credit_flush_jit,
    }
    return {n: f._cache_size() for n, f in programs.items() if f is not None}


PLACEMENTS = {
    "default": lambda: {},
    "committed": lambda: {"device": jax.devices()[1]},
    "sharded": lambda: {"state_shards": 2},
    "resident": lambda: {"state_shards": 2, "routing": "resident"},
}


@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
def test_one_compiled_signature_across_add_return_sweep_remove(placement):
    """The step, the due probe and the flush each compile once for an
    engine, wherever its state lives (one device by default or committed,
    or a mesh span, gathered or resident): what the subscription methods,
    the flush and the sweep write back is placed like the leaf it
    replaces, and ``warm()`` compiled the flush before the first return."""
    broker, client, (engine,) = _device_broker(**PLACEMENTS[placement]())
    try:
        def cycle(key, other):
            _subscribe(broker, engine, key, credits=1)
            for i in range(3):
                client.create_instance("order-process", payload={"orderId": i})
            broker.run_until_idle()  # steps: one job assigned, two parked
            engine.increase_job_credits(key, 1)
            broker.tick()            # flush, probe, sweep
            broker.run_until_idle()  # the ACTIVATE's step
            engine.increase_job_credits(key, 1)
            engine.increase_job_credits(other, 1)
            broker.run_until_idle()
            broker.tick()
            broker.run_until_idle()
            engine.remove_job_subscription(key)

        flushes = event_count(FLUSHES)
        engine.warm(sizes=())
        assert event_count(FLUSHES) == flushes  # warming is no flush
        flush_warmed = engine_mod._credit_flush_jit._cache_size()
        assert flush_warmed >= 1
        cycle(PAY_A, PAY_B)
        sizes = _cache_sizes(engine)
        assert sizes["credit_flush"] == flush_warmed  # warm() compiled it
        cycle(PAY_B, PAY_A)
        cycle(PAY_A, PAY_B)
        assert _cache_sizes(engine) == sizes
        assert len(_activated(broker)) == 9
    finally:
        broker.close()


def test_warm_compiles_the_flush_with_no_workflow_deployed():
    """A broker on a fresh data directory warms before any deployment:
    the step cannot compile yet, the flush does."""
    engine = TpuPartitionEngine(capacity=128, sub_capacity=4)
    assert engine.graph is None
    before = engine_mod._credit_flush_jit._cache_size()
    engine.warm()
    assert engine.graph is None
    warmed = engine_mod._credit_flush_jit._cache_size()
    assert warmed == before + 1  # sub_capacity 4: a shape of its own
    _subscribe_direct(engine, PAY_A, credits=0)
    engine.increase_job_credits(PAY_A, 2)
    assert _credits_by_key(engine) == {PAY_A: 2}
    assert engine_mod._credit_flush_jit._cache_size() == warmed
