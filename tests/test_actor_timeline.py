"""The broker actor's whole timeline (ISSUE 36): every mailbox job of a
measured actor is timed in the loop that runs it
(``runtime/actors.ActorScheduler._run_job``): kind, self time, thread CPU,
mailbox wait and the idle stretch it ended, read as always-on counters, as
track ``actor`` of the cycle ring and as zbench's per-layer metrics."""

import importlib
import json
import math
import os
import sys
import threading
import time

import pytest

from tests import test_wave_phases as wave_phases
from zeebe_tpu import tracing
from zeebe_tpu.runtime.actors import Actor, ActorScheduler, ControlledActorScheduler
from zeebe_tpu.runtime.metrics import event_count
from zeebe_tpu.tracing import phases as phases_mod

REPO = wave_phases.REPO
KINDS = phases_mod.JOB_KINDS
ROLE_TOTALS = ("busy", "cpu", "offcpu", "idle")
CPU_CLOCK_JOBS = ["broker_actor_cpu_clock_jobs_total", "raft_actor_cpu_clock_jobs_total"]
PER_KIND = (
    "broker_actor_{}_seconds_total", "broker_actor_{}_jobs_total",
    "broker_actor_{}_mailbox_wait_seconds_total",
    "broker_actor_idle_before_{}_seconds_total",
)
COUNTERS = [
    f"{role}_actor_{total}_seconds_total"
    for role in ("broker", "raft") for total in ROLE_TOTALS
] + CPU_CLOCK_JOBS + [name.format(kind) for kind in KINDS for name in PER_KIND]
# every phase a clock can cut on the broker actor's thread (``drain_wait``
# is a ``waited`` interval: it begins on the thread that saw the commit)
ACTOR_PHASE_COUNTERS = sorted(
    {
        f"serving_{phase}_seconds_total"
        for track in ("wave", "tick") for phase in phases_mod.TRACKS[track]
    } - {"serving_credit_flush_seconds_total"}
) + [
    "serving_pump_seconds_total", "serving_job_credit_flush_seconds_total",
    "serving_job_credit_return_seconds_total",
]


def counters() -> dict:
    return {name: event_count(name) for name in COUNTERS + ACTOR_PHASE_COUNTERS}


def delta(before: dict) -> dict:
    after = counters()
    return {name: after[name] - before[name] for name in before}


def spin(ms: float) -> None:
    """Burn ``ms`` of this thread's CPU (by the thread's own clock: on a
    machine shared with other test workers the wall time is longer)."""
    end = time.thread_time() + ms / 1e3
    while time.thread_time() < end:
        pass


class Measured(Actor):
    role = "broker"


@pytest.fixture
def tracer():
    """Every job on the timeline; restores whatever tracer was installed."""
    installed = tracing.TRACER
    probe = tracing.install(tracing.RecordTracer(sample_rate=1.0, seed=36))
    yield probe
    tracing.install(installed)


def _started(cls=Measured):
    scheduler = ControlledActorScheduler().start()
    actor = cls()
    scheduler.submit_actor(actor)
    scheduler.work_until_done()
    return scheduler, actor


def _actor_events(probe, role="broker"):
    return [
        c for c in probe.cycles.snapshot()
        if c.get("track") == "actor" and c["role"] == role
    ]


class TestJobStopwatch:
    def test_kinds_waits_and_idle_on_a_controlled_scheduler(self, tracer):
        """(a) three kinds with known busy-waits: a command after an idle
        stretch, then a drain and a tick enqueued together, so the tick
        waits in the mailbox for the drain and ends no idle stretch."""
        scheduler, actor = _started()
        before = counters()
        t_start = time.perf_counter()
        actor.actor.run(lambda: spin(3), kind="command")
        scheduler.work_until_done()
        time.sleep(0.02)  # the mailbox is empty: idle, ended by the drain
        actor.actor.run(lambda: spin(2), kind="drain")
        actor.actor.run_on_completion(
            actor.actor.call(lambda: spin(1), kind="tick"), lambda _f: None
        )
        scheduler.work_until_done()
        took = time.perf_counter() - t_start
        d = delta(before)
        jobs = {kind: d[f"broker_actor_{kind}_jobs_total"] for kind in KINDS}
        assert jobs == {
            "command": 1, "drain": 1, "tick": 1, "other": 1,  # the continuation
            "job_subscription": 0, "topic_subscription": 0,
        }
        self_s = {kind: d[f"broker_actor_{kind}_seconds_total"] for kind in KINDS}
        for kind, ms in (("command", 3), ("drain", 2), ("tick", 1)):
            assert self_s[kind] >= ms / 1e3, (kind, self_s[kind])
        # no phase ran, so the self times are the busy time, inside the test's
        assert sum(self_s.values()) == pytest.approx(
            d["broker_actor_busy_seconds_total"], abs=1e-9
        )
        assert d["broker_actor_busy_seconds_total"] <= took - 0.02
        # the tick stood behind the drain's 2 ms; the drain, enqueued on an
        # idle actor and run at once, behind nothing
        assert d["broker_actor_tick_mailbox_wait_seconds_total"] >= 0.002
        assert (
            d["broker_actor_drain_mailbox_wait_seconds_total"]
            < d["broker_actor_tick_mailbox_wait_seconds_total"] - 0.002
        )
        assert d["broker_actor_idle_before_drain_seconds_total"] >= 0.02
        assert d["broker_actor_idle_before_tick_seconds_total"] == 0
        assert d["broker_actor_idle_seconds_total"] == pytest.approx(
            sum(d[f"broker_actor_idle_before_{kind}_seconds_total"] for kind in KINDS)
        )
        # the drain is on the CPU clock (the kind that can wait for the
        # device; the actor's first is sampled), and a spinning job is on
        # the CPU for all of its wall time
        assert d["broker_actor_busy_seconds_total"] >= 0.006
        assert d["broker_actor_cpu_clock_jobs_total"] == 1
        assert 0.002 <= d["broker_actor_cpu_seconds_total"] < 0.004
        assert d["broker_actor_offcpu_seconds_total"] == pytest.approx(
            self_s["drain"] - d["broker_actor_cpu_seconds_total"], abs=2e-5
        )
        # busy + idle is the wall clock from the first job's start (the idle
        # stretch it ended began at the actor's boot job) to the last job's
        # end, but for the loop between two jobs that follow at once (some
        # microseconds on a quiet machine; the timeline shows them as the
        # gaps between such jobs' slices)
        events = _actor_events(tracer)[-4:]
        assert [e["kind"] for e in events] == ["command", "drain", "tick", "other"]
        jobs = [e["phases"][-1] for e in events]
        elapsed = (jobs[-1][2] - jobs[0][1]) / 1e6
        loop = sum(cur[1] - prev[2] for prev, cur in zip(jobs[1:], jobs[2:])) / 1e6
        total = (
            d["broker_actor_busy_seconds_total"] + d["broker_actor_idle_seconds_total"]
            - d["broker_actor_idle_before_command_seconds_total"]
        )
        assert total + loop == pytest.approx(elapsed, abs=1e-5)
        # the same stamps on the timeline: an idle slice where one ended
        assert [[s[0] for s in e["phases"]] for e in events] == [
            ["actor_idle", "job:command"], ["actor_idle", "job:drain"],
            ["job:tick"], ["job:other"],
        ]

    def test_phase_time_is_not_self_time_a_waited_interval_is(self):
        """(b) a phase opened on the job's thread is cut out of its self
        time; ``waited`` crosses threads and is not."""
        scheduler, actor = _started()
        before = counters()
        clock = tracing.PhaseClock()

        def job():
            clock.waited("drain_wait", tracing.now_us() - 50_000)
            spin(2)
            with clock.phase("pump"):
                spin(4)

        actor.actor.run(job, kind="drain")
        scheduler.work_until_done()
        d = delta(before)
        assert clock.us["pump"] >= 4000 and clock.us["drain_wait"] >= 50_000
        busy = d["broker_actor_busy_seconds_total"]
        self_time = d["broker_actor_drain_seconds_total"]
        assert busy >= 0.006
        assert self_time == pytest.approx(busy - clock.us["pump"] / 1e6, abs=1e-9)
        assert 0.002 <= self_time < busy - 0.004 + 1e-6

    def test_off_cpu_time_of_the_sampled_drains(self):
        """A drain that sleeps was in a job and not running: wall less
        thread CPU. The clock is a system call: it is read around one drain
        in ``CPU_CLOCK_STRIDE`` and around no command."""
        scheduler, actor = _started()
        before = counters()
        actor.actor.run(lambda: (spin(2), time.sleep(0.01)), kind="drain")
        actor.actor.run(lambda: time.sleep(0.005), kind="command")
        scheduler.work_until_done()
        d = delta(before)
        assert d["broker_actor_busy_seconds_total"] >= 0.017
        assert d["broker_actor_cpu_clock_jobs_total"] == 1
        assert 0.002 <= d["broker_actor_cpu_seconds_total"] < 0.004
        assert d["broker_actor_offcpu_seconds_total"] >= 0.01
        # the drain's wall time alone, the command's sleep is in neither
        assert d["broker_actor_cpu_seconds_total"] + d[
            "broker_actor_offcpu_seconds_total"
        ] == pytest.approx(d["broker_actor_drain_seconds_total"], abs=2e-5)
        # the next seven drains are off the clock, the eighth after is on it
        stride = phases_mod.CPU_CLOCK_STRIDE
        for _ in range(stride):
            actor.actor.run(lambda: None, kind="drain")
        scheduler.work_until_done()
        assert delta(before)["broker_actor_cpu_clock_jobs_total"] == 2

    def test_a_failing_job_is_timed_and_reported(self):
        scheduler, actor = _started()
        before = counters()

        def boom():
            spin(1)
            raise RuntimeError("job failed")

        actor.actor.run(boom, kind="command")
        scheduler.work_until_done()
        assert scheduler.actor_failures == 1
        d = delta(before)
        assert d["broker_actor_command_jobs_total"] == 1
        assert d["broker_actor_command_seconds_total"] >= 0.001

    def test_disabled_tracing_counts_and_makes_no_event(self, monkeypatch):
        """(d) ``[tracing] enabled = false``: the counters count; no
        ``actor`` event and no annotation object is made."""
        import jax

        made = []
        annotation = jax.profiler.TraceAnnotation
        monkeypatch.setattr(
            jax.profiler, "TraceAnnotation",
            lambda *a, **k: made.append(a) or annotation(*a, **k),
        )
        installed = tracing.TRACER
        tracing.install(None)
        probe = tracing.RecordTracer(sample_rate=1.0)
        try:
            scheduler, actor = _started()
            before = counters()
            actor.actor.run(lambda: spin(1), kind="command")
            scheduler.work_until_done()
            d = delta(before)
        finally:
            tracing.install(installed)
        assert d["broker_actor_command_jobs_total"] == 1
        assert d["broker_actor_busy_seconds_total"] >= 0.001
        assert not probe.cycles.snapshot() and not made
        # with a tracer every selected job holds one
        tracing.install(probe)
        try:
            actor.actor.run(lambda: None, kind="tick")
            scheduler.work_until_done()
        finally:
            tracing.install(installed)
        assert made == [("zb:job:tick",)]
        assert [e["kind"] for e in _actor_events(probe)] == ["tick"]

    def test_stride_selects_jobs_like_cycles(self):
        """Unselected jobs take no slice: at rate 0.1 one job in ten."""
        installed = tracing.TRACER
        probe = tracing.install(tracing.RecordTracer(sample_rate=0.1))
        try:
            scheduler, actor = _started()
            for _ in range(40):
                actor.actor.run(lambda: None, kind="command")
            scheduler.work_until_done()
        finally:
            tracing.install(installed)
        assert 4 <= len(_actor_events(probe)) <= 5

    def test_an_actor_without_a_role_bumps_nothing(self, tracer):
        """(e) the loop pays one attribute test; nothing is counted."""
        scheduler, actor = _started(Actor)
        assert actor.role is None
        before = counters()
        actor.actor.run(lambda: spin(1), kind="command")
        scheduler.work_until_done()
        assert not any(delta(before).values())
        assert not tracer.cycles.snapshot()

    def test_a_role_without_counters_is_refused(self):
        class Stranger(Actor):
            role = "gateway"

        with pytest.raises(ValueError, match="unknown role"):
            ControlledActorScheduler().start().submit_actor(Stranger())

    def test_another_role_has_totals_only(self, tracer):
        class Replica(Actor):
            role = "raft"

        scheduler, actor = _started(Replica)
        before = counters()
        actor.actor.run(lambda: spin(1))
        scheduler.work_until_done()
        d = delta(before)
        assert d["raft_actor_busy_seconds_total"] >= 0.001
        assert not any(v for name, v in d.items() if name.startswith("broker_"))
        (event,) = _actor_events(tracer, "raft")[-1:]
        assert [s[0] for s in event["phases"]] == ["raft_idle", "raft_job:other"]

    def test_threaded_scheduler_flushes_once_a_mailbox_run(self, monkeypatch):
        """The threaded loop runs the same ``_run_job``; a mailbox run of
        many jobs takes each counter's lock once."""
        from zeebe_tpu.runtime import metrics

        flushes = []
        observe = metrics.observe_phases
        monkeypatch.setattr(
            metrics, "observe_phases",
            lambda clock, cycle=None: flushes.append(dict(clock.counts))
            or observe(clock, cycle),
        )
        scheduler = ActorScheduler(cpu_threads=1, io_threads=0).start()
        try:
            actor = Measured()
            scheduler.submit_actor(actor).join(5)
            before = counters()
            gate = threading.Event()
            actor.actor.run(lambda: gate.wait(5), kind="other")  # holds the run
            for _ in range(20):
                actor.actor.run(lambda: None, kind="command")
            gate.set()
            assert actor.actor.call(lambda: True, kind="tick").join(5)
        finally:
            scheduler.stop()  # joins the worker: its last run has flushed
        d = delta(before)
        assert d["broker_actor_command_jobs_total"] == 20
        assert d["broker_actor_tick_jobs_total"] == 1
        with_commands = [f for f in flushes if f.get("broker_actor_command_jobs")]
        assert [f["broker_actor_command_jobs"] for f in with_commands] == [20]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One ClusterBroker leading one partition on the device engine (the
    fixture style of tests/test_wave_phases.py), every job on the timeline:
    a deployment, a topic subscription, a job worker and six instances with
    a job each."""
    from zeebe_tpu.protocol.enums import ValueType
    from zeebe_tpu.protocol.intents import JobIntent

    installed = tracing.TRACER
    probe = tracing.install(tracing.RecordTracer(sample_rate=1.0, seed=36))
    broker = wave_phases._broker(str(tmp_path_factory.mktemp("actor_timeline")))
    jobs = 6
    try:
        server = wave_phases._lead(broker)
        before = counters()
        client = wave_phases._client(broker)
        try:
            client.deploy_model(wave_phases.ORDER_MODEL)
            seen = []
            subscription = client.open_topic_subscription(
                "timeline", lambda _pid, record: seen.append(record)
            )
            worker = client.open_job_worker(
                "payment-service", lambda _pid, _rec: {"paid": True}, credits=2
            )
            for i in range(jobs):
                client.create_instance("order", {"orderId": i})

            def completed() -> int:
                return sum(
                    1 for r in server.log.reader(0).read_committed()
                    if r.metadata.value_type == ValueType.JOB
                    and r.metadata.intent == int(JobIntent.COMPLETED)
                )

            deadline = time.time() + 180
            while time.time() < deadline and completed() < jobs:
                time.sleep(0.05)
            assert completed() == jobs
            time.sleep(0.5)  # the last wave's clock and a tick's flush
            worker.close()
            subscription.close()
        finally:
            client.close()
    finally:
        broker.close()
    yield {"tracer": probe, "delta": delta(before), "seen": len(seen)}
    tracing.install(installed)


class TestServedBroker:
    def test_every_kind_has_jobs(self, served):
        """(c) a deployment, creates, a worker's round trips and a topic
        subscription leave jobs under every kind."""
        d = served["delta"]
        assert served["seen"] > 0
        for kind in KINDS:
            assert d[f"broker_actor_{kind}_jobs_total"] >= 1, kind
            assert d[f"broker_actor_{kind}_seconds_total"] > 0, kind
        # 1 deployment + 6 creates + 6 COMPLETEs, at least
        assert d["broker_actor_command_jobs_total"] >= 13
        # the worker's open and a credit return a job
        assert d["broker_actor_job_subscription_jobs_total"] >= 7
        assert d["raft_actor_busy_seconds_total"] > 0
        assert d["raft_actor_idle_seconds_total"] > 0

    def test_self_times_and_phases_sum_to_busy(self, served):
        """The identity of the counters: the kinds' self seconds + the
        phase seconds recorded on the broker actor's thread = its busy
        seconds (a clock that was never flushed, a wave in flight at the
        close, is what the 2 % are for)."""
        d = served["delta"]
        self_s = sum(d[f"broker_actor_{kind}_seconds_total"] for kind in KINDS)
        phase_s = sum(d[name] for name in ACTOR_PHASE_COUNTERS)
        busy = d["broker_actor_busy_seconds_total"]
        assert phase_s > 0 and self_s > 0
        assert self_s + phase_s == pytest.approx(busy, rel=0.02)
        assert d["broker_actor_offcpu_seconds_total"] <= busy
        assert d["broker_actor_cpu_seconds_total"] > 0
        assert d["broker_actor_idle_seconds_total"] == pytest.approx(
            sum(d[f"broker_actor_idle_before_{kind}_seconds_total"] for kind in KINDS)
        )

    def test_actor_slices_do_not_overlap_on_one_actor(self, served):
        events = _actor_events(served["tracer"])
        assert {e["kind"] for e in events} == set(KINDS)
        names = {s[0] for e in events for s in e["phases"]}
        assert names == set(phases_mod.TRACKS["actor"])
        for role in ("broker", "raft"):
            slices = sorted(
                (s for e in _actor_events(served["tracer"], role) for s in e["phases"]),
                key=lambda s: s[1],
            )
            assert slices, role
            for prev, cur in zip(slices, slices[1:]):
                assert cur[1] >= prev[2], (role, prev, cur)
        # no job's phases outlast it: self time is never negative
        cycles = served["tracer"].cycles.snapshot()
        jobs = [e["phases"][-1] for e in events if e["phases"]]
        for cycle in cycles:
            if cycle["track"] not in ("drain", "tick"):
                continue
            own = [s for s in cycle["phases"] if s[0] != "drain_wait"]
            if not own:
                continue
            t0, t1 = own[0][1], own[-1][2]
            assert any(j[1] <= t0 and t1 <= j[2] for j in jobs), cycle

    def test_trace_report_draws_the_actor_row(self, served, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            trace_report = importlib.import_module("trace_report")
        finally:
            sys.path.pop(0)
        path = served["tracer"].dump(str(tmp_path / "dump.json"))
        with open(path) as f:
            doc = json.load(f)
        events = trace_report.convert(doc)["traceEvents"]
        host = [e for e in events if e["pid"] == "host" and e["ph"] == "X"]
        rows = {e["tid"] for e in host}
        assert {"actor", "actor:raft", "wave", "drain", "tick", "raft"} <= rows
        on_actor = {e["name"] for e in host if e["tid"] == "actor"}
        assert on_actor == set(phases_mod.TRACKS["actor"])
        order = {
            e["tid"]: e["args"]["sort_index"] for e in events
            if e.get("name") == "thread_sort_index" and e["pid"] == "host"
        }
        assert order["actor"] < order["wave"] < order["drain"] < order["tick"]
        assert order["tick"] < order["actor:raft"] < order["raft"]


NEW_METRICS = (
    "actor_busy_share", "actor_command_ms", "actor_drain_self_ms",
    "actor_rest_ms", "actor_idle_ms", "actor_idle_before_drain_share",
    "actor_offcpu_ms", "actor_offcpu_ms.steady", "mailbox_wait_ms",
    "raft_actor_busy_share",
)


class TestReaders:
    @pytest.mark.parametrize("metric", NEW_METRICS)
    def test_layer_metric_file_reads_a_finite_value(self, metric):
        """(f) each new data file loads, is listed, names counters the
        program has, and reads a finite value from a synthetic ``ctx``;
        on a program without the counters (all 0) nothing raises."""
        from zbench import layers
        from zeebe_tpu.runtime.metrics import _phase_handles

        path = os.path.join(REPO, "zbench", "layer_metrics", metric + ".json")
        with open(path) as f:
            reader = json.load(f)
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == metric]
        assert entry["source"] == "program_counter" and entry["workloads"]
        have = {h.name for h in _phase_handles().values()} | {"serving_waves_total"}
        names = sorted(layers.counters_needed([reader]))
        assert set(names) <= have, set(names) - have
        ctx = {"counters": {name: 2.0 + i for i, name in enumerate(names)}}
        value = layers.read(metric, reader, ctx)
        assert value is not None and math.isfinite(value) and value > 0
        zeros = {name: 0.0 for name in names}
        zeros["serving_waves_total"] = 100.0
        assert layers.read(metric, reader, {"counters": zeros}) in (None, 0.0)

    def test_idle_gap_share_reads_the_actor_slices(self):
        """``idle_gap_share`` with ``phase: "actor_idle"`` over the recorded
        v5e trace of zbench's fixtures and hand-made ``actor`` slices."""
        from zbench import trace
        from zbench.layer_metrics import idle_gap_share

        fixtures = os.path.join(REPO, "zbench", "fixtures")
        with open(os.path.join(fixtures, "trace_small.json")) as f:
            doc = json.load(f)
        with open(os.path.join(fixtures, "trace_small.expected.json")) as f:
            expected = json.load(f)
        lo, hi = expected["window_ns"]
        doc = {**doc, "sync_ns": lo}
        wall0 = tracing.wall_ns(1_000_000)

        def span_us(trace_ns: int) -> int:
            return 1_000_000 + (trace_ns - lo) // 1000

        # the actor idle over the window's first half, then one command
        # job over its last 100 ms
        half = lo + (hi - lo) // 2
        tail = hi - 100_000_000
        probe = tracing.RecordTracer(sample_rate=1.0)
        probe.cycles.cycle("actor", role="broker", kind="command").extend([
            ["actor_idle", span_us(lo), span_us(half)],
            ["job:command", span_us(tail), span_us(hi)],
        ])
        ops = next(
            line for line in doc["planes"][0]["lines"] if line["name"] == "XLA Ops"
        )
        busy = trace.clip(
            trace.merged([[s, s + d] for _, s, d in ops["events"]]), lo, hi
        )
        idle = trace.subtract([[lo, hi]], busy)
        ctx = {"trace": {
            "doc": doc, "window_ns": (lo, hi), "wall_ns": (wall0, wall0 + hi - lo),
        }}
        installed = tracing.TRACER
        tracing.install(probe)
        try:
            shares = {
                phase: idle_gap_share.read({**ctx, "reader": {"phase": phase}})
                for phase in ("actor_idle", "job:command", None)
            }
        finally:
            tracing.install(installed)
        want = {
            "actor_idle": trace.total(trace.intersect(idle, [[lo, half]])),
            "job:command": trace.total(trace.intersect(idle, [[tail, hi]])),
        }
        for phase, ns in want.items():
            assert ns > 0
            assert shares[phase] == pytest.approx(
                100.0 * ns / trace.total(idle), abs=0.01
            ), phase
        assert shares[None] == pytest.approx(
            100.0 - shares["actor_idle"] - shares["job:command"], abs=0.01
        )
