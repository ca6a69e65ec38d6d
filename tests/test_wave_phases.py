"""The wave cycle phase by phase (ISSUE 25): contiguous host phases stamped
where the work happens (``zeebe_tpu/tracing/phases.py``), read as always-on
counters, as the timelines' ``phases`` and as zbench's per-layer metrics."""

import importlib
import json
import os
import sys
import threading
import time

import jax
import pytest

from zeebe_tpu import tracing
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.runtime.metrics import event_count
from zeebe_tpu.tracing import phases as phases_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODEL = (
    Bpmn.create_process("route")
    .start_event("start")
    .end_event("end")
    .done()
)

PHASE_COUNTERS = {
    phase: f"serving_{phase}_seconds_total" for phase in phases_mod.TRACKS["wave"]
}
# the job path's own (ISSUE 27): phases ``push`` and ``job_read`` are in
# PHASE_COUNTERS, ``backlog`` is the tick's
JOB_COUNTERS = [
    "serving_job_read_seconds_total", "serving_push_seconds_total",
    "serving_backlog_seconds_total", "serving_job_row_reads_total",
    "serving_job_pushes_total", "serving_backlog_activations_total",
    "serving_backlog_skipped_in_flight_total",
    "serving_job_commands_serialised_total",
    "serving_backlog_sweeps_total", "serving_backlog_table_scans_total",
    "serving_backlog_parked_total",
]
COUNTERS = sorted(set(PHASE_COUNTERS.values()) | set(JOB_COUNTERS)) + [
    "serving_host_seconds_total", "serving_device_seconds_total",
    "serving_waves_total", "serving_h2d_bytes_total", "serving_d2h_bytes_total",
    "serving_drains_total", "serving_drain_wait_seconds_total",
    "serving_pump_seconds_total", "serving_ticks_total",
    "serving_tick_seconds_total", "raft_group_commits_total",
    "raft_log_append_seconds_total", "raft_fsync_seconds_total",
    "raft_commit_seconds_total",
]


def counters() -> dict:
    return {name: event_count(name) for name in COUNTERS}


def _broker(data_dir: str):
    """One ClusterBroker on the device engine, every cycle on the timeline
    (rate 1.0); ``_lead`` makes it the leader of partition 0."""
    from zeebe_tpu.runtime.cluster_broker import ClusterBroker
    from zeebe_tpu.runtime.config import BrokerCfg
    from zeebe_tpu.runtime.engines import engine_factory_from_config

    cfg = BrokerCfg()
    cfg.network.client_port = 0
    cfg.network.management_port = 0
    cfg.network.subscription_port = 0
    cfg.metrics.enabled = False
    cfg.engine.type = "tpu"
    cfg.engine.capacity = 1024
    cfg.tracing.sample_rate = 1.0
    return ClusterBroker(
        cfg, data_dir, engine_factory=engine_factory_from_config(cfg)
    )


def _lead(broker):
    broker.open_partition(0).join(120)
    broker.bootstrap_partition(0, {})
    deadline = time.time() + 120
    while time.time() < deadline and not broker.partitions[0].is_leader:
        time.sleep(0.01)
    server = broker.partitions[0]
    assert server.is_leader
    return server


def _client(broker):
    from zeebe_tpu.gateway.cluster_client import ClusterClient

    # the first wave compiles ``kernel.step`` inside its dispatch: over the
    # client's default 10 s when several test workers share the machine
    return ClusterClient(
        [broker.client_address], num_partitions=1, request_timeout_ms=120_000
    )


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A small served run: one ClusterBroker leading one partition on the
    device engine, 24 instances over the client socket, every wave, drain,
    tick and group commit on the timeline (rate 1.0). Yields what the run
    left behind."""
    tracer = tracing.install(tracing.RecordTracer(sample_rate=1.0, seed=25))
    broker = _broker(str(tmp_path_factory.mktemp("phases")))
    staged = []
    flushes = []
    try:
        server = _lead(broker)
        assert tracing.TRACER is tracer  # the boot kept the installed tracer

        stage = server.engine._stage
        flush = server.log.flush

        def spy_stage(*args, **kwargs):
            batch = stage(*args, **kwargs)
            staged.append(sum(a.nbytes for a in jax.tree_util.tree_leaves(batch)))
            return batch

        def spy_flush():
            flushes.append(1)
            return flush()

        server.engine._stage = spy_stage
        server.log.flush = spy_flush
        before = counters()
        client = _client(broker)
        try:
            client.deploy_model(MODEL)
            for i in range(24):
                client.create_instance("route", {"orderId": i})
        finally:
            client.close()
        def settled() -> bool:
            # a job behind whatever the broker actor is running: when it
            # ran, no drain is in the middle of a wave (a first wave of a
            # new batch shape compiles for seconds inside its dispatch)
            ran = threading.Event()
            broker.actor_control.run(ran.set)
            assert ran.wait(120), "the broker actor is stuck"
            return (
                server.next_read_position > server.log.commit_position
                and broker.wave_scheduler.backlog() == 0
                and not broker._drain_scheduled
            )

        # every follow-up drained and every wave collected, and still so a
        # few ticks later (a wave in flight at close is never collected,
        # and its clock never flushed)
        deadline = time.time() + 120
        while time.time() < deadline:
            if settled():
                time.sleep(0.3)
                if settled():
                    break
            time.sleep(0.02)
        else:
            raise AssertionError("the served run did not settle")
    finally:
        broker.close()
    after = counters()
    yield {
        "tracer": tracer,
        "delta": {name: after[name] - before[name] for name in COUNTERS},
        "staged_bytes": staged,
        "flushes": len(flushes),
    }
    tracing.install(None)


def _runs(slices):
    """Split a time-ordered slice list where a slice does not start at its
    predecessor's end."""
    runs = [[slices[0]]]
    for prev, cur in zip(slices, slices[1:]):
        if cur[1] == prev[2]:
            runs[-1].append(cur)
        else:
            runs.append([cur])
    return runs


ORDER_MODEL = (
    Bpmn.create_process("order")
    .start_event("start")
    .service_task("pay", type="payment-service")
    .end_event("end")
    .done()
)


@pytest.fixture(scope="module")
def served_jobs(tmp_path_factory):
    """The same broker serving a process with a job to the client's own
    worker, which has ONE credit for six jobs: the kernel's pool assigns
    while the credit is free, and the jobs that found none wait for the
    tick's sweep (``backlog``; their values are their events', so no
    ``job_read``) after the credit's return."""
    from zeebe_tpu.protocol.enums import ValueType
    from zeebe_tpu.protocol.intents import JobIntent

    installed = tracing.TRACER
    tracer = tracing.install(tracing.RecordTracer(sample_rate=1.0, seed=27))
    broker = _broker(str(tmp_path_factory.mktemp("job_phases")))
    jobs = 6
    try:
        server = _lead(broker)
        before = counters()
        client = _client(broker)
        try:
            client.deploy_model(ORDER_MODEL)
            worker = client.open_job_worker(
                "payment-service", lambda _pid, _rec: {"paid": True}, credits=1
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
        finally:
            client.close()
        records = list(server.log.reader(0).read_committed())
    finally:
        broker.close()
    after = counters()
    yield {
        "tracer": tracer,
        "delta": {name: after[name] - before[name] for name in COUNTERS},
        "jobs": jobs,
        "activated": sum(
            1 for r in records
            if r.metadata.value_type == ValueType.JOB
            and r.metadata.intent == int(JobIntent.ACTIVATED)
        ),
    }
    tracing.install(installed)


class TestJobPathPhases:
    def test_zero_on_waves_without_jobs(self, served):
        """(g) a process with no job leaves every counter of the job path
        where it was."""
        for name in JOB_COUNTERS:
            assert served["delta"][name] == 0, name

    def test_flush_into_their_counters(self, served_jobs):
        d = served_jobs["delta"]
        assert served_jobs["activated"] == served_jobs["jobs"]  # each once
        assert d["serving_job_pushes_total"] == served_jobs["activated"]
        assert d["serving_push_seconds_total"] > 0
        # one credit for six jobs: the sweep handed most of them out, from
        # the engine's own account of them (each entered it once, with the
        # value its event carried: no row is read, the table is scanned
        # once, by the subscription, which found nothing known), and
        # nothing was handed out twice
        swept = d["serving_backlog_activations_total"]
        assert 1 <= swept <= served_jobs["jobs"]
        assert d["serving_backlog_parked_total"] == swept
        assert 1 <= d["serving_backlog_sweeps_total"] <= d["serving_ticks_total"]
        assert d["serving_backlog_table_scans_total"] == 1
        assert d["serving_job_row_reads_total"] == 0
        assert d["serving_job_read_seconds_total"] == 0
        assert d["serving_backlog_seconds_total"] > 0
        assert d["serving_job_commands_serialised_total"] == 0

    def test_self_times_on_their_tracks(self, served_jobs):
        """``push`` is cut out of ``apply`` and ``backlog`` out of ``tick``
        (``job_read`` out of ``backlog`` only after a restore:
        tests/test_job_backlog_probe.py): no slice overlaps another, and a
        phase that was cut resumes where the inner one ended."""
        tracer = served_jobs["tracer"]
        waves = [w for w in tracer.waves.snapshot() if w["segments"]]
        pushed = [w for w in waves if any(s[0] == "push" for s in w["phases"])]
        assert pushed
        for wave in pushed:
            slices = wave["phases"]
            for prev, cur in zip(slices, slices[1:]):
                assert cur[1] >= prev[2], (prev, cur)
            names = [s[0] for s in slices]
            assert names[names.index("push") - 1] == "apply", names
            assert set(names) <= set(phases_mod.TRACKS["wave"]), names
        ticks = [c for c in tracer.cycles.snapshot() if c["track"] == "tick"]
        swept = [
            t for t in ticks if any(s[0] == "backlog" for s in t["phases"])
        ]
        assert swept
        for tick in ticks:
            slices = tick["phases"]
            assert {s[0] for s in slices} <= set(phases_mod.TRACKS["tick"])
            for prev, cur in zip(slices, slices[1:]):
                assert cur[1] == prev[2], (prev, cur)  # contiguous: self times
        for tick in swept:
            names = [s[0] for s in tick["phases"]]
            assert names[0] == "tick" and names[-1] == "tick", names
            # the parked jobs' values are the events': no row is read back
            assert "job_read" not in names, names
        # the counters are the slices' sums
        d = served_jobs["delta"]
        for phase, counter in (
            ("backlog", "serving_backlog_seconds_total"),
            ("job_read", "serving_job_read_seconds_total"),
        ):
            total = sum(
                s[2] - s[1] for t in ticks for s in t["phases"] if s[0] == phase
            ) / 1e6
            assert total == pytest.approx(d[counter], rel=0.01, abs=1e-5), phase


class TestWavePhases:
    def test_phases_contiguous_ordered_inside_the_wave(self, served):
        """(a) every sampled wave: slices in time order, none overlapping,
        all inside dispatch..collect extended by pack and apply; the
        engine's dispatch half (route..launch) and its collect half
        (blocked, readback, decode) are each stamped by one clock, so each
        is contiguous to the microsecond."""
        waves = [w for w in served["tracer"].waves.snapshot() if w["segments"]]
        assert waves
        device_waves = 0
        for wave in waves:
            slices = wave["phases"]
            assert slices and slices[0][0] == "pack", wave
            names = {name for name, _t0, _t1 in slices}
            assert names <= set(phases_mod.TRACKS["wave"]), names
            for name, t0, t1 in slices:
                assert t1 > t0, slices
            for prev, cur in zip(slices, slices[1:]):
                assert cur[1] >= prev[2], (prev, cur)
            inner = [s for s in slices if s[0] not in ("pack", "apply")]
            for _name, t0, t1 in inner:
                assert wave["t_dispatch_us"] <= t0 and t1 <= wave["t_collect_us"]
            for name, t0, t1 in slices:
                if name == "pack":
                    assert t1 <= wave["t_dispatch_us"] + 1
                if name == "apply":
                    assert t0 >= wave["t_dispatch_us"]
                    assert t1 <= wave["t_collect_us"] + 1
            if "launch" not in names:
                continue  # a wave of host-routed records (the deployment)
            device_waves += 1
            halves = _runs(inner)
            assert len(halves) == 2, halves
            dispatch, collect = ([s[0] for s in half] for half in halves)
            assert dispatch[0] == "route"  # (its tail may be under a us)
            assert {"stage", "h2d", "launch"} <= set(dispatch)
            assert set(dispatch) <= {"route", "stage", "h2d", "launch"}
            assert collect[:2] == ["decode", "blocked"] or collect[0] == "blocked"
            assert {"blocked", "readback", "decode"} == set(collect)
        assert device_waves >= 2

    def test_counter_identity(self, served):
        """(b) the wave's seconds are the sums of its phases, and the bytes
        handed to the device are the staged batches' bytes."""
        d = served["delta"]
        assert d["serving_waves_total"] >= 2
        host = sum(
            d[PHASE_COUNTERS[p]] for p in phases_mod.WAVE_HOST_PHASES
        )
        blocked = sum(
            d[PHASE_COUNTERS[p]] for p in phases_mod.WAVE_BLOCKED_PHASES
        )
        assert host > 0 and blocked > 0
        assert host == pytest.approx(d["serving_host_seconds_total"], rel=0.01)
        assert blocked == pytest.approx(
            d["serving_device_seconds_total"], rel=0.01
        )
        assert served["staged_bytes"]
        assert d["serving_h2d_bytes_total"] == sum(served["staged_bytes"])
        assert d["serving_d2h_bytes_total"] > 0
        for phase in ("pack", "apply"):
            assert d[PHASE_COUNTERS[phase]] > 0, phase
        assert d["serving_drains_total"] >= 1
        assert d["serving_ticks_total"] >= 1 and d["serving_tick_seconds_total"] > 0

    def test_raft_group_commit_phases(self, served):
        """(c) a group commit leaves log_append, fsync, commit in order
        and counts once per log.flush."""
        groups = [
            c for c in served["tracer"].cycles.snapshot() if c["track"] == "raft"
        ]
        assert groups
        for group in groups:
            assert [s[0] for s in group["phases"]] == list(
                phases_mod.TRACKS["raft"]
            ), group
            for prev, cur in zip(group["phases"], group["phases"][1:]):
                assert cur[1] >= prev[2], group  # siblings: a stamp apart
            assert group["partition"] == 0
        d = served["delta"]
        assert d["raft_group_commits_total"] == served["flushes"] >= 1
        assert d["raft_fsync_seconds_total"] > 0
        assert d["raft_log_append_seconds_total"] > 0

    def test_drains_and_ticks_on_the_cycle_ring(self, served):
        cycles = served["tracer"].cycles.snapshot()
        drains = [c for c in cycles if c["track"] == "drain"]
        ticks = [c for c in cycles if c["track"] == "tick"]
        assert drains and ticks
        for drain in drains:
            assert {s[0] for s in drain["phases"]} <= set(
                phases_mod.TRACKS["drain"]
            )
        assert any(s[0] == "drain_wait" for d in drains for s in d["phases"])
        assert all([s[0] for s in t["phases"]] == ["tick"] for t in ticks)

    def test_wave_dispatch_stamp_names_its_wave(self, served):
        """(d) a traced record's span names the wave that carried it."""
        tracer = served["tracer"]
        wave_ids = {w["wave_id"] for w in tracer.waves.snapshot()}
        stamped = [
            fields for span in tracer.spans()
            for stage, _t, fields in span.stages
            if stage == tracing.WAVE_DISPATCH
        ]
        assert stamped
        for fields in stamped:
            assert fields["wave_id"] in wave_ids, fields

    def test_disabled_tracing_counts_and_allocates_nothing(self, served):
        """(e) with no tracer the phase counters still advance and no
        timeline entry, slice list or annotation is made."""
        from tests.conftest import make_tpu_broker  # as test_snapshot_recovery
        from zeebe_tpu.gateway import ZeebeClient

        installed = tracing.TRACER
        tracing.install(None)
        probe = tracing.RecordTracer(sample_rate=1.0)
        try:
            before = counters()
            broker = make_tpu_broker()
            try:
                client = ZeebeClient(broker)
                client.deploy_model(MODEL)
                for i in range(3):
                    client.create_instance("route", {"orderId": i})
                broker.run_until_idle()
                clock = broker.partitions[0].engine.last_wave_phases
            finally:
                broker.close()
            after = counters()
        finally:
            tracing.install(installed)
        assert tracing.TRACER is installed
        assert clock.slices is None and not clock._annotate
        assert not probe.waves.snapshot() and not probe.cycles.snapshot()
        for phase in ("pack", "stage", "h2d", "launch", "blocked", "decode"):
            name = PHASE_COUNTERS[phase]
            assert after[name] > before[name], name
        assert after["serving_h2d_bytes_total"] > before["serving_h2d_bytes_total"]


class TestReaders:
    def test_trace_report_one_slice_per_phase(self, served, tmp_path):
        """(f) the dump carries both rings and the report draws one slice
        per phase on a host row per track."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            trace_report = importlib.import_module("trace_report")
        finally:
            sys.path.pop(0)
        path = served["tracer"].dump(str(tmp_path / "dump.json"))
        with open(path) as f:
            doc = json.load(f)
        assert doc["cycles"]
        want = sum(len(e["phases"]) for e in doc["waves"] + doc["cycles"])
        events = trace_report.convert(doc)["traceEvents"]
        host = [e for e in events if e["pid"] == "host" and e["ph"] == "X"]
        assert len(host) == want > 0
        # the actors' own rows (ISSUE 36) above the cycles that run inside them
        assert {e["tid"] for e in host} == {
            "actor", "wave", "drain", "tick", "actor:raft", "raft",
        }
        assert all(e["dur"] > 0 for e in host)

    @pytest.fixture
    def gap_ctx(self):
        """The recorded v5e trace of zbench's fixtures with hand-made phase
        slices: ``host_a`` of the fixture's expected numbers as a raft
        ``fsync``, and a wave ``stage`` over the window's last 100 ms."""
        from zbench import trace

        fixtures = os.path.join(REPO, "zbench", "fixtures")
        with open(os.path.join(fixtures, "trace_small.json")) as f:
            doc = json.load(f)
        with open(os.path.join(fixtures, "trace_small.expected.json")) as f:
            expected = json.load(f)
        lo, hi = expected["window_ns"]
        # the fixture was cut without its clock tie; any stamp stands for it
        # (the reader takes the window on both clocks from ``trace``)
        assert doc["sync_ns"] is None
        doc = {**doc, "sync_ns": lo}
        # the traced window starts at span-clock second 1
        wall0 = tracing.wall_ns(1_000_000)

        def span_us(trace_ns: int) -> int:
            return 1_000_000 + (trace_ns - lo) // 1000

        tracer = tracing.RecordTracer(sample_rate=1.0)
        (a, b), = expected["host_a"]
        tracer.cycles.cycle("raft").append(["fsync", span_us(a), span_us(b)])
        tail = [hi - 100_000_000, hi]
        tracer.waves.begin(0, 512)["phases"].append(
            ["stage", span_us(tail[0]), span_us(tail[1])]
        )
        installed = tracing.TRACER
        tracing.install(tracer)
        ops = next(
            line for line in doc["planes"][0]["lines"] if line["name"] == "XLA Ops"
        )
        busy = trace.clip(
            trace.merged([[s, s + d] for _, s, d in ops["events"]]), lo, hi
        )
        idle = trace.subtract([[lo, hi]], busy)
        yield {
            "ctx": {"trace": {
                "doc": doc, "window_ns": (lo, hi),
                "wall_ns": (wall0, wall0 + hi - lo),
            }},
            "idle_s": expected["window_s"] - expected["busy_s"],
            # gaps under 50 us included, which ``host_a_s`` leaves out
            "fsync_s": trace.total(trace.intersect(idle, [[a, b]])) / 1e9,
            "stage_s": trace.total(trace.intersect(idle, [tail])) / 1e9,
        }
        tracing.install(installed)

    def test_idle_gap_share_by_phase(self, gap_ctx):
        """(g) hand-computed shares: a named phase, and the idle time no
        phase covers (wall_ns has a quarter microsecond of float grain)."""
        from zbench.layer_metrics import idle_gap_share

        def read(phase):
            return idle_gap_share.read(
                {**gap_ctx["ctx"], "reader": {"phase": phase}}
            )

        idle_s = gap_ctx["idle_s"]
        assert gap_ctx["stage_s"] > 0.05 and gap_ctx["fsync_s"] > 0.04
        assert read("fsync") == pytest.approx(
            100 * gap_ctx["fsync_s"] / idle_s, rel=1e-4
        )
        assert read("stage") == pytest.approx(
            100 * gap_ctx["stage_s"] / idle_s, rel=1e-4
        )
        assert read("launch") == 0.0
        covered = gap_ctx["fsync_s"] + gap_ctx["stage_s"]
        assert read(None) == pytest.approx(
            100 * (idle_s - covered) / idle_s, rel=1e-4
        )

    def test_idle_gap_share_gives_nothing_without_its_sources(self, gap_ctx):
        from zbench.layer_metrics import idle_gap_share

        ctx = {**gap_ctx["ctx"], "reader": {"phase": None}}
        untied = {**ctx["trace"], "doc": {**ctx["trace"]["doc"], "sync_ns": None}}
        assert idle_gap_share.read(ctx) is not None
        assert idle_gap_share.read({**ctx, "trace": untied}) is None
        assert idle_gap_share.read({**ctx, "trace": None}) is None
        tracing.install(tracing.RecordTracer(sample_rate=1.0))  # no phases
        assert idle_gap_share.read(ctx) is None
        tracing.install(None)
        assert idle_gap_share.read(ctx) is None

    @pytest.mark.parametrize("cell,suffix,metrics", [
        ("route-1p.saturated", "", [
            "wave_pack_ms", "wave_route_ms", "wave_stage_ms", "wave_h2d_ms",
            "wave_launch_ms", "wave_blocked_ms", "wave_readback_ms",
            "wave_decode_ms", "wave_apply_ms", "drain_wait_ms",
            "drain_pump_ms", "tick_ms", "log_append_ms", "fsync_ms",
            "h2d_bytes_per_wave", "d2h_bytes_per_wave",
            "idle_unattributed_share",
        ]),
        ("route-1p.steady", ".steady", [
            "wave_route_ms", "wave_stage_ms", "wave_h2d_ms", "wave_launch_ms",
            "wave_blocked_ms", "wave_readback_ms", "wave_decode_ms",
            "wave_apply_ms", "idle_unattributed_share",
        ]),
    ])
    def test_cells_load_the_new_metrics(self, cell, suffix, metrics):
        """(h) each cell lists every new metric with its reader file, and
        every counter a reader names is one the program counts."""
        from zbench import spec
        from zeebe_tpu.runtime.metrics import _phase_handles

        loaded = {m["name"]: m for m in spec.Cell(cell).per_layer}
        counted = {metric.name for metric in _phase_handles().values()}
        counted.add("serving_waves_total")
        for name in metrics:
            metric = loaded[name + suffix]
            reader = metric["reader"]
            if reader["kind"] == "counter_ratio":
                assert metric["source"] == "program_counter"
                assert set(reader["num"] + reader["den"]) <= counted, reader
            else:
                assert metric["source"] == "device_trace"
                assert reader["module"] == "idle_gap_share"
                assert reader["phase"] is None
