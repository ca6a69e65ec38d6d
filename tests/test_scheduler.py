"""Continuous-batching wave scheduler + gateway admission control.

The scheduler is a PACKING change, not a semantics change — so the pins
are structural (DRR fairness, backpressure bounds, shared fill) plus the
hard contract: every partition's log stays BIT-IDENTICAL to the
per-partition baseline drain, for both engines. Admission is pinned at
the unit level (bounds, release, close cleanup) and end-to-end (a shed
command is retryable and eventually lands).
"""

import itertools
import threading
import time

import pytest

from zeebe_tpu.protocol import codec
from zeebe_tpu.runtime import Broker, ControlledClock
from zeebe_tpu.runtime.metrics import GLOBAL_REGISTRY, event_count
from zeebe_tpu.scheduler import (
    AdmissionConfig,
    AdmissionController,
    PartitionFeed,
    WaveScheduler,
)


# ---------------------------------------------------------------------------
# unit level: DRR packing, backpressure, rewind
# ---------------------------------------------------------------------------


class _Rec:
    __slots__ = ("position", "pid")

    def __init__(self, position, pid):
        self.position = position
        self.pid = pid


class FakeFeed(PartitionFeed):
    """A queue-backed feed; dispatch collects per-wave history so the
    packing itself is assertable."""

    def __init__(self, pid, n, pipelined=False, fail_dispatch=False):
        self.partition_id = pid
        self.cursor = 0
        self.limit_n = n
        self.pipelined = pipelined
        self.fail_dispatch = fail_dispatch
        self.dispatched = []  # list of lists (per segment)
        self.collected = []
        self.rewound_to = None

    def backlog(self):
        return self.limit_n - self.cursor

    def take(self, limit):
        take = min(limit, self.limit_n - self.cursor)
        if take <= 0:
            return []
        out = [_Rec(self.cursor + i, self.partition_id) for i in range(take)]
        self.cursor += take
        return out

    def dispatch(self, records):
        if self.fail_dispatch:
            raise RuntimeError("engine exploded")
        self.dispatched.append(list(records))
        if self.pipelined:
            return list(records), 0.0, 0.0
        return None, 0.0, 0.0

    def collect(self, pending):
        self.collected.append(list(pending))
        return 0.0, 0.0

    def rewind(self, position):
        self.rewound_to = position
        self.cursor = min(self.cursor, position)


class TestWavePacking:
    def test_shared_wave_packs_all_sparse_partitions(self):
        """Four sparse partitions → ONE shared wave, not four tiny ones
        (the whole point: fill at any traffic mix)."""
        ws = WaveScheduler(wave_size=512)
        feeds = [FakeFeed(pid, 16) for pid in range(4)]
        for f in feeds:
            ws.register(f)
        shared_before = GLOBAL_REGISTRY.counter(
            "scheduler_shared_waves_total"
        ).value
        total = ws.drain()
        assert total == 64
        for f in feeds:
            assert len(f.dispatched) == 1  # one segment per feed
            assert len(f.dispatched[0]) == 16
        assert (
            GLOBAL_REGISTRY.counter("scheduler_shared_waves_total").value
            - shared_before
            == 1
        )
        # the traffic-mix gauge saw all four sources
        assert GLOBAL_REGISTRY.gauge("serving_wave_sources").value == 4

    def test_drr_fairness_deep_backlog_cannot_starve_sparse_feeds(self):
        """A 10k-record partition shares every wave with the 10-record
        ones: the sparse feeds fully drain within the first wave."""
        ws = WaveScheduler(wave_size=256, quantum=32)
        big = FakeFeed(0, 10_000)
        smalls = [FakeFeed(pid, 10) for pid in (1, 2, 3)]
        ws.register(big)
        for f in smalls:
            ws.register(f)
        ws.drain(max_records=256)
        for f in smalls:
            assert f.cursor == 10, "sparse feed starved by the deep backlog"
        # and the big feed got the remaining room, not the whole wave
        assert 0 < big.cursor < 256

    def test_per_partition_order_is_cursor_order(self):
        ws = WaveScheduler(wave_size=64, quantum=8)
        feeds = [FakeFeed(pid, 100) for pid in range(3)]
        for f in feeds:
            ws.register(f)
        ws.drain()
        for f in feeds:
            seen = [r.position for seg in f.dispatched for r in seg]
            assert seen == sorted(seen) == list(range(100))

    def test_backpressure_skips_and_resumes(self):
        """A pipelined feed at its in-flight cap is skipped (counted) but
        drains fully once collects catch up."""
        ws = WaveScheduler(wave_size=16, quantum=16, backpressure_limit=16)
        feed = FakeFeed(0, 100, pipelined=True)
        ws.register(feed)
        skips_before = event_count("scheduler_backpressure_skips")
        ws.drain()
        assert feed.cursor == 100
        assert sum(len(c) for c in feed.collected) == 100
        assert event_count("scheduler_backpressure_skips") > skips_before

    def test_backpressure_bounds_records_within_one_wave(self):
        """Records packed into the wave BEING BUILT count against the
        in-flight cap: DRR revisits across rounds must not assemble a
        segment larger than the configured apply-side bound."""
        ws = WaveScheduler(wave_size=512, quantum=64, backpressure_limit=64)
        feed = FakeFeed(0, 10_000, pipelined=True)
        ws.register(feed)
        ws.drain(max_records=64)
        assert feed.dispatched, "nothing dispatched"
        assert max(len(seg) for seg in feed.dispatched) <= 64

    @pytest.mark.parametrize(
        "sizes, pipelined, wave_size, waves, segments, largest, ahead",
        [
            # four feeds share one wave: four launches before the first
            # collect, each finds the earlier ones queued: 0 + 1 + 2 + 3
            ((16, 8, 24, 12), True, 512, 1, 4, 24, 6),
            # one feed, one wave: its own records, nothing ahead of it
            ((16,), True, 512, 1, 1, 16, 0),
            # one feed, two waves: the second is dispatched before the
            # first is collected (the drain's double buffer): 0 + 1
            ((1024,), True, 512, 2, 2, 1024, 1),
            # a synchronous engine applies inline: nothing is ever queued
            ((16, 8, 24, 12), False, 512, 1, 4, 24, 0),
        ],
        ids=["four-feeds", "one-feed", "two-waves-in-flight", "synchronous"],
    )
    def test_segment_counters(
        self, sizes, pipelined, wave_size, waves, segments, largest, ahead
    ):
        """``serving_segments_total``, ``serving_segment_records_max_total``
        and ``serving_launch_ahead_total`` by name, as ``zbench``'s
        ``counter_ratio`` reads them, beside the counters that were there
        (``scheduler_wave_sources_total`` / ``scheduler_shared_waves_total``
        is the segments a wave)."""
        names = (
            "scheduler_shared_waves_total", "scheduler_wave_sources_total",
            "serving_segments_total", "serving_segment_records_max_total",
            "serving_launch_ahead_total", "serving_wave_records_total",
        )
        ws = WaveScheduler(wave_size=wave_size)
        for pid, n in enumerate(sizes):
            ws.register(FakeFeed(pid, n, pipelined=pipelined))
        before = {n: event_count(n) for n in names}
        assert ws.drain() == sum(sizes)
        got = {n: event_count(n) - before[n] for n in names}
        assert got == {
            "scheduler_shared_waves_total": waves,
            "scheduler_wave_sources_total": segments,
            "serving_segments_total": segments,
            "serving_segment_records_max_total": largest,
            "serving_launch_ahead_total": ahead,
            "serving_wave_records_total": sum(sizes),
        }
        assert ws._uncollected == 0

    def test_failed_dispatch_counts_no_segment_and_leaves_nothing_queued(self):
        """A segment whose dispatch raised was never launched: it is no
        segment dispatched and nothing of its wave stays counted as queued."""
        ws = WaveScheduler(wave_size=64)
        ok = FakeFeed(0, 8, pipelined=True)
        bad = FakeFeed(1, 8, fail_dispatch=True)
        ws.register(ok)
        ws.register(bad)
        before = event_count("serving_segments_total")
        with pytest.raises(RuntimeError, match="engine exploded"):
            ws.drain()
        assert event_count("serving_segments_total") - before == 1
        assert ws._uncollected == 0

    def test_timeline_shows_every_segment_of_a_shared_wave(self):
        """The per-wave timeline of a four-partition wave: four segments,
        each with its partition, its device, its records and its own
        seconds blocked on the device."""
        from zeebe_tpu import tracing

        class PlacedFeed(FakeFeed):
            device_index = 0

            def collect(self, pending):
                super().collect(pending)
                return 0.001, (10 + self.partition_id) / 1000

        tracer = tracing.install(tracing.RecordTracer(sample_rate=1.0, seed=3))
        try:
            ws = WaveScheduler(wave_size=512)
            for pid, n in enumerate((16, 8, 24, 12)):
                ws.register(PlacedFeed(pid, n, pipelined=True))
            ws.drain()
            waves = [w for w in tracer.waves.snapshot() if "segments" in w]
        finally:
            tracing.install(None)
        assert len(waves) == 1 and waves[0]["records"] == 60
        segs = sorted(waves[0]["segments"], key=lambda seg: seg["partition"])
        assert [
            (seg["partition"], seg["device"], seg["records"], seg["device_s"])
            for seg in segs
        ] == [(0, 0, 16, 0.010), (1, 0, 8, 0.011), (2, 0, 24, 0.012),
              (3, 0, 12, 0.013)]
        # all four were dispatched before the first was collected
        assert max(seg["t_dispatch_us"] for seg in segs) <= min(
            seg["t_collect_us"] for seg in segs
        )

    def test_dispatch_failure_rewinds_and_collects_inflight(self):
        """A raising dispatch rewinds that segment's cursor (records
        re-drain) and still collects the previously dispatched wave."""
        ws = WaveScheduler(wave_size=8, quantum=8)
        ok = FakeFeed(0, 8, pipelined=True)
        bad = FakeFeed(1, 8)
        bad.fail_dispatch = True
        ws.register(ok)
        ws.register(bad)
        with pytest.raises(RuntimeError, match="engine exploded"):
            ws.drain()
        assert bad.rewound_to == 0
        assert bad.cursor == 0  # records not lost: they re-drain
        # the ok feed's dispatched wave was still collected (finally path)
        assert sum(len(c) for c in ok.collected) == len(
            [r for seg in ok.dispatched for r in seg]
        )

    def test_unregister_mid_stream(self):
        ws = WaveScheduler(wave_size=32)
        a, b = FakeFeed(0, 40), FakeFeed(1, 40)
        ws.register(a)
        ws.register(b)
        ws.drain(max_records=32)
        ws.unregister(0)
        ws.drain()
        assert b.cursor == 40
        assert a.cursor < 40  # stopped feeding after unregister


# ---------------------------------------------------------------------------
# in-process broker: shared waves vs per-partition baseline, bit-identical
# ---------------------------------------------------------------------------


def _skewed_workload(data_dir, use_scheduler, partitions=4):
    """Deterministic multi-partition workload (Zipf-ish skew via explicit
    partition targeting); returns per-partition frame bytes."""
    from zeebe_tpu.gateway import JobWorker, ZeebeClient
    from zeebe_tpu.gateway import workers as workers_mod
    from zeebe_tpu.models.bpmn.builder import Bpmn

    workers_mod._subscriber_keys = itertools.count(1)
    clock = ControlledClock(start_ms=1_000_000)
    broker = Broker(num_partitions=partitions, data_dir=data_dir, clock=clock)
    broker.use_scheduler = use_scheduler
    broker.wave_size = 256
    try:
        client = ZeebeClient(broker)
        model = (
            Bpmn.create_process("mt-process")
            .start_event("start")
            .service_task("work", type="mt-service")
            .end_event("end")
            .done()
        )
        client.deploy_model(model)
        JobWorker(broker, "mt-service", lambda ctx: {"ok": True})
        # skewed mix: partition 0 heavy, the rest sparse (the regime where
        # per-partition waves collapse)
        mix = [0] * 24 + [1] * 6 + [2] * 3 + [3] * 2
        for i, pid in enumerate(mix):
            broker.write_command(
                pid,
                _create_value("mt-process", {"i": i}),
                _create_intent(),
            )
        broker.run_until_idle()
        return [
            [codec.encode_record(r) for r in broker.records(pid)]
            for pid in range(partitions)
        ]
    finally:
        broker.close()


def _create_value(process_id, payload):
    from zeebe_tpu.protocol.records import WorkflowInstanceRecord

    return WorkflowInstanceRecord(bpmn_process_id=process_id, payload=payload)


def _create_intent():
    from zeebe_tpu.protocol.intents import WorkflowInstanceIntent

    return WorkflowInstanceIntent.CREATE


class TestSharedWaveParity:
    def test_per_partition_logs_bit_identical_to_baseline(self, tmp_path):
        frames_shared = _skewed_workload(str(tmp_path / "s"), True)
        frames_base = _skewed_workload(str(tmp_path / "b"), False)
        assert sum(len(f) for f in frames_shared) > 100
        for pid, (a, b) in enumerate(zip(frames_shared, frames_base)):
            assert a == b, f"partition {pid} log diverged under scheduling"

    def test_shared_fill_beats_per_partition_baseline(self, tmp_path):
        """The acceptance metric at test scale: identical skewed offered
        load, mean wave fill of the shared drain ≥ 2× the per-partition
        baseline's."""
        c_waves = GLOBAL_REGISTRY.counter("serving_waves_total")
        c_recs = GLOBAL_REGISTRY.counter("serving_wave_records_total")

        def fill(run):
            w0, r0 = c_waves.value, c_recs.value
            run()
            dw = c_waves.value - w0
            dr = c_recs.value - r0
            assert dw > 0
            return dr / dw

        # trickle mode: several small drains (each run_until_idle is one
        # arrival burst) — the baseline pays one wave per partition per
        # burst, the scheduler packs them
        fill_shared = fill(
            lambda: _skewed_workload(str(tmp_path / "s"), True)
        )
        fill_base = fill(
            lambda: _skewed_workload(str(tmp_path / "b"), False)
        )
        assert fill_shared >= 2 * fill_base, (
            f"shared fill {fill_shared:.1f} vs baseline {fill_base:.1f}"
        )


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_per_connection_inflight_bound(self):
        ctl = AdmissionController(
            AdmissionConfig(max_inflight_per_connection=2)
        )
        assert ctl.try_admit(1) is None
        assert ctl.try_admit(1) is None
        assert ctl.try_admit(1) == "CONNECTION_INFLIGHT"
        assert ctl.try_admit(2) is None  # other connections unaffected
        ctl.release(1)
        assert ctl.try_admit(1) is None
        assert ctl.inflight(1) == 2

    def test_queue_depth_watermark_sheds(self):
        depth = [0]
        ctl = AdmissionController(
            AdmissionConfig(queue_depth_high=10),
            queue_depth_probe=lambda: depth[0],
        )
        assert ctl.try_admit(1) is None
        depth[0] = 10
        assert ctl.try_admit(1) == "QUEUE_DEPTH"
        depth[0] = 9
        assert ctl.try_admit(1) is None
        assert GLOBAL_REGISTRY.gauge("gateway_queue_depth").value == 9

    def test_forget_connection_drops_accounting(self):
        ctl = AdmissionController(
            AdmissionConfig(max_inflight_per_connection=2)
        )
        ctl.try_admit(7)
        ctl.try_admit(7)
        ctl.forget_connection(7)
        assert ctl.inflight(7) == 0
        assert ctl.try_admit(7) is None

    def test_release_unknown_connection_is_noop(self):
        ctl = AdmissionController(AdmissionConfig())
        ctl.release(42)  # never admitted: must not go negative
        assert ctl.inflight(42) == 0

    def test_disabled_admits_everything(self):
        ctl = AdmissionController(
            AdmissionConfig(enabled=False, max_inflight_per_connection=1)
        )
        for _ in range(10):
            assert ctl.try_admit(1) is None

    def test_rejection_body_is_retryable(self):
        ctl = AdmissionController(AdmissionConfig(retry_after_ms=25))
        body = ctl.rejection_body("QUEUE_DEPTH")
        assert body["code"] == "RESOURCE_EXHAUSTED"
        assert body["retry_ms"] == 25


# ---------------------------------------------------------------------------
# cluster end-to-end: shared waves serve multiple partitions; shed+retry
# ---------------------------------------------------------------------------


def _boot_cluster_broker(tmp_path, partitions=2, cfg_tweak=None):
    import os

    from zeebe_tpu.runtime.cluster_broker import ClusterBroker
    from zeebe_tpu.runtime.config import BrokerCfg

    cfg = BrokerCfg()
    cfg.network.client_port = 0
    cfg.network.management_port = 0
    cfg.network.subscription_port = 0
    cfg.metrics.port = 0
    cfg.metrics.enabled = False
    cfg.cluster.partitions = partitions
    if cfg_tweak is not None:
        cfg_tweak(cfg)
    broker = ClusterBroker(cfg, os.path.join(str(tmp_path), "b0"))
    for pid in range(partitions):
        broker.open_partition(pid).join(10)
        broker.bootstrap_partition(pid, {})
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not all(
        broker.partitions[pid].is_leader for pid in range(partitions)
    ):
        time.sleep(0.02)
    assert all(broker.partitions[pid].is_leader for pid in range(partitions))
    return broker


class TestClusterScheduler:
    def test_shared_waves_serve_all_partitions(self, tmp_path):
        from zeebe_tpu.gateway.cluster_client import ClusterClient
        from zeebe_tpu.models.bpmn.builder import Bpmn

        broker = _boot_cluster_broker(tmp_path, partitions=2)
        client = None
        try:
            assert broker.wave_scheduler is not None
            client = ClusterClient(
                [broker.client_address], num_partitions=2,
                request_timeout_ms=30_000,
            )
            model = (
                Bpmn.create_process("sched-process")
                .start_event("s")
                .service_task("work", type="sched-service")
                .end_event("e")
                .done()
            )
            client.deploy_model(model)
            done = []
            lock = threading.Lock()

            def on_job(pid, rec):
                with lock:
                    done.append(pid)
                return {}

            worker = client.open_job_worker("sched-service", on_job)
            for i in range(6):
                client.create_instance("sched-process", partition_id=i % 2)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline and len(done) < 6:
                time.sleep(0.02)
            worker.close()
            assert len(done) >= 6
            assert set(done) == {0, 1}  # both partitions served
            assert (
                GLOBAL_REGISTRY.counter(
                    "scheduler_shared_waves_total"
                ).value > 0
            )
        finally:
            if client is not None:
                client.close()
            broker.close()

    def test_parked_partition_does_not_stall_the_other(self, tmp_path):
        """A partition waiting on a workflow fetch (CREATE for an unknown
        process parks its feed) must not stop the OTHER partition's waves
        — the backpressure/park isolation contract."""
        from zeebe_tpu.gateway.client import ClientException
        from zeebe_tpu.gateway.cluster_client import ClusterClient
        from zeebe_tpu.models.bpmn.builder import Bpmn

        broker = _boot_cluster_broker(tmp_path, partitions=2)
        client = None
        try:
            client = ClusterClient(
                [broker.client_address], num_partitions=2,
                request_timeout_ms=30_000,
            )
            model = (
                Bpmn.create_process("real-process")
                .start_event("s")
                .end_event("e")
                .done()
            )
            client.deploy_model(model)

            # ghost CREATE on partition 1: parks the feed, fetch finds
            # nothing, the engine rejects — asynchronously
            ghost_error = []

            def ghost():
                try:
                    client.create_instance("ghost-process", partition_id=1)
                except ClientException as e:
                    ghost_error.append(e)

            t = threading.Thread(target=ghost, daemon=True)
            t.start()
            # meanwhile partition 0 keeps serving
            for _ in range(3):
                rsp = client.create_instance(
                    "real-process", partition_id=0
                )
                assert rsp.value.workflow_instance_key > 0
            t.join(30)
            assert not t.is_alive()
            assert ghost_error, "ghost create should be rejected"
        finally:
            if client is not None:
                client.close()
            broker.close()

    def test_a_drain_job_is_one_wave_and_leaves_nothing_behind(self, tmp_path):
        """Waves of 8 records and bursts of creates on two partitions: far
        more is committed than one wave holds. Each drain job runs ONE
        wave and hands the actor back to its mailbox (the commands that
        arrived meanwhile), and what the wave had no room for is drained
        by the job it schedules: every instance completes, and no drain
        job ever held more than one wave."""
        from zeebe_tpu.gateway.cluster_client import ClusterClient
        from zeebe_tpu.models.bpmn.builder import Bpmn
        from zeebe_tpu.protocol.enums import RecordType, ValueType
        from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI

        def tweak(cfg):
            cfg.scheduler.wave_size = 8

        broker = _boot_cluster_broker(tmp_path, partitions=2, cfg_tweak=tweak)
        client = None
        try:
            client = ClusterClient(
                [broker.client_address], num_partitions=2,
                request_timeout_ms=60_000,
            )
            client.deploy_model(
                Bpmn.create_process("burst-process")
                .start_event("s").end_event("e").done()
            )
            names = ("serving_waves_total", "serving_drains_total")
            before = {n: event_count(n) for n in names}
            errors = []

            def burst(pid):
                try:
                    for _ in range(12):
                        client.create_instance("burst-process", partition_id=pid)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [
                threading.Thread(target=burst, args=(i % 2,), daemon=True)
                for i in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not errors, errors

            def completed(pid):
                return sum(
                    1 for r in broker.partitions[pid].log.reader(0).read_committed()
                    if r.metadata.value_type == ValueType.WORKFLOW_INSTANCE
                    and r.metadata.record_type == RecordType.EVENT
                    and int(r.metadata.intent) == int(WI.ELEMENT_COMPLETED)
                    and r.key == r.value.workflow_instance_key
                )

            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and (
                completed(0) < 48 or completed(1) < 48
            ):
                time.sleep(0.05)
            assert (completed(0), completed(1)) == (48, 48)
            got = {n: event_count(n) - before[n] for n in names}
            # 96 instances of several records each, 8 a wave
            assert got["serving_waves_total"] >= 48
            assert got["serving_drains_total"] >= got["serving_waves_total"]
        finally:
            if client is not None:
                client.close()
            broker.close()

    @pytest.mark.parametrize("leaders", [1, 2])
    def test_next_drain_job_stands_behind_the_commands_of_its_wave(
        self, tmp_path, leaders
    ):
        """A commit seen in the middle of a wave asks for the next drain
        job; a command that arrives after it, still during the wave, must
        not wait for that job's wave too: the job is enqueued when the
        running one ends, whatever the number of leader partitions."""
        broker = _boot_cluster_broker(tmp_path, partitions=0)
        try:
            jobs = []

            class Mailbox:
                def run(self, fn, kind="other"):
                    jobs.append(fn)

            class CommittingFeed(FakeFeed):
                def dispatch(self, records):
                    # in the middle of the wave: a raft thread sees a
                    # commit, then a client command reaches the actor
                    broker._schedule_drain()
                    broker.actor_control.run("command")
                    return super().dispatch(records)

            broker.actor_control = Mailbox()
            broker.wave_scheduler.register(CommittingFeed(0, 4))
            for pid in range(1, leaders):
                broker.wave_scheduler.register(FakeFeed(pid, 4))
            broker._schedule_drain()
            assert jobs == [broker._drain_committed]
            jobs.pop()()
            assert jobs == ["command", broker._drain_committed]
            assert broker._drain_scheduled
            # the job it asked for finds nothing, and asks for no other
            jobs.pop()()
            assert jobs == ["command"] and not broker._drain_scheduled
        finally:
            broker.close()

    def test_overload_sheds_retryably(self, tmp_path):
        """Synthetic overload against a 1-command in-flight bound: sheds
        fire (counted) but every command eventually lands via the
        client's retry — shed-before-collapse, not reject-forever."""
        from zeebe_tpu.gateway.cluster_client import ClusterClient
        from zeebe_tpu.models.bpmn.builder import Bpmn

        def tweak(cfg):
            cfg.admission.max_inflight_per_connection = 1

        broker = _boot_cluster_broker(tmp_path, partitions=1, cfg_tweak=tweak)
        client = None
        try:
            client = ClusterClient(
                [broker.client_address], num_partitions=1,
                request_timeout_ms=60_000,
            )
            model = (
                Bpmn.create_process("ovl-process")
                .start_event("s")
                .end_event("e")
                .done()
            )
            client.deploy_model(model)
            shed = GLOBAL_REGISTRY.counter(
                "gateway_commands_shed", reason="CONNECTION_INFLIGHT"
            )
            shed_before = shed.value
            errors = []
            keys = []
            lock = threading.Lock()

            def pump():
                try:
                    rsp = client.create_instance("ovl-process")
                    with lock:
                        keys.append(rsp.value.workflow_instance_key)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [
                threading.Thread(target=pump, daemon=True)
                for _ in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not errors, errors
            assert len(keys) == 8
            assert len(set(keys)) == 8
            assert shed.value > shed_before, "overload never shed"
        finally:
            if client is not None:
                client.close()
            broker.close()


# ---------------------------------------------------------------------------
# lazy columnar emissions (device wave path)
# ---------------------------------------------------------------------------


def _device_workload(data_dir, lazy):
    """Device-engine serving workload; returns (frames, materialized
    delta, column-staged delta). The counter deltas cover the RUN only —
    reading the frames at the end deliberately materializes every lazy
    tail entry and must not pollute the measurement."""
    from zeebe_tpu.engine.interpreter import WorkflowRepository
    from zeebe_tpu.gateway import JobWorker, ZeebeClient
    from zeebe_tpu.gateway import workers as workers_mod
    from zeebe_tpu.models.bpmn.builder import Bpmn
    from zeebe_tpu.protocol.columnar import rows_materialized_total
    from zeebe_tpu.tpu import TpuPartitionEngine

    workers_mod._subscriber_keys = itertools.count(1)
    clock = ControlledClock(start_ms=1_000_000)
    repo = WorkflowRepository()

    def factory(pid):
        engine = TpuPartitionEngine(pid, 1, repository=repo, clock=clock)
        engine.lazy_emissions = lazy
        return engine

    broker = Broker(
        num_partitions=1, data_dir=data_dir, clock=clock,
        engine_factory=factory,
    )
    broker.wave_size = 256
    staged = GLOBAL_REGISTRY.counter("serving_rows_staged_columnar_total")
    m0, s0 = rows_materialized_total(), staged.value
    try:
        client = ZeebeClient(broker)
        model = (
            Bpmn.create_process("lazy-process")
            .start_event("start")
            .service_task("work", type="lazy-service")
            .end_event("end")
            .done()
        )
        client.deploy_model(model)
        JobWorker(broker, "lazy-service", lambda ctx: {"done": True})
        for i in range(12):
            client.create_instance("lazy-process", {"n": i})
        clock.advance(1_000)
        broker.tick()
        broker.run_until_idle()
        mat, stg = rows_materialized_total() - m0, staged.value - s0
        frames = [codec.encode_record(r) for r in broker.records(0)]
        return frames, mat, stg
    finally:
        broker.close()


def _raw_log_bytes(data_dir):
    import os

    pdir = os.path.join(data_dir, "partition-0")
    out = []
    for name in sorted(os.listdir(pdir)):
        if name.endswith(".data") or name.startswith("segment"):
            with open(os.path.join(pdir, name), "rb") as f:
                out.append(f.read())
    return out


class TestLazyEmissions:
    def test_lazy_log_bit_identical_to_eager(self, tmp_path):
        """The columns-encode + column-staging path produces EXACTLY the
        log the materialized-row path produces (frames AND downstream
        state transitions — a staging divergence would change follow-up
        records, not just bytes). Pinned on the in-memory frames AND the
        raw on-disk segment bytes."""
        frames_lazy, _, _ = _device_workload(str(tmp_path / "l"), True)
        frames_eager, _, _ = _device_workload(str(tmp_path / "e"), False)
        assert len(frames_lazy) > 100
        assert frames_lazy == frames_eager
        raw_lazy = _raw_log_bytes(str(tmp_path / "l"))
        raw_eager = _raw_log_bytes(str(tmp_path / "e"))
        assert raw_lazy and raw_lazy == raw_eager

    def test_lazy_path_materializes_fewer_rows_and_stages_columnar(
        self, tmp_path
    ):
        """The satellite pin: lazy emissions materialize strictly FEWER
        Record objects during the drain than the eager path, and a
        healthy share of device rows re-stage straight from columns."""
        _, eager_mat, eager_staged = _device_workload(
            str(tmp_path / "e"), False
        )
        assert eager_staged == 0, "eager mode must not column-stage"
        _, lazy_mat, lazy_staged = _device_workload(
            str(tmp_path / "l"), True
        )
        assert lazy_staged > 0, "no rows staged straight from columns"
        assert lazy_mat < eager_mat, (
            f"lazy path should materialize fewer rows "
            f"({lazy_mat} vs {eager_mat})"
        )
