"""Mesh-sharded serving plane (ISSUE 9): partition→device placement,
rebalance on leadership change, dead-device fallback, the all_to_all
frame exchange, and the hard contract — per-partition logs BIT-IDENTICAL
(frames and raw segment bytes) whether the engines are spread across the
mesh or pinned to one device. Placement is a WHERE change, never a WHAT
change."""

import itertools
import os
import tempfile
import time

import jax
import pytest

from zeebe_tpu.runtime.metrics import GLOBAL_REGISTRY, event_count
from zeebe_tpu.scheduler import PartitionFeed, WaveScheduler
from zeebe_tpu.scheduler.placement import DevicePlan, MeshExchange


# ---------------------------------------------------------------------------
# DevicePlan
# ---------------------------------------------------------------------------


class TestDevicePlan:
    def test_round_robin_assignment(self):
        plan = DevicePlan(devices=list("abcd"))
        assert [plan.assign(p) for p in range(8)] == [0, 1, 2, 3, 0, 1, 2, 3]

    def test_assignment_is_sticky(self):
        plan = DevicePlan(devices=list("abcd"))
        idx = plan.assign(7)
        for _ in range(3):
            assert plan.assign(7) == idx
        assert plan.device_for(7) == "abcd"[idx]

    def test_release_rebalances_next_install(self):
        """A leadership flap (release + assign) lands the next install on
        the emptiest device — the freed one."""
        plan = DevicePlan(devices=list("abcd"))
        for p in range(4):
            plan.assign(p)
        plan.release(2)
        assert plan.assign(99) == 2  # the freed slot is the emptiest
        # and the flapped partition itself re-places onto a least-loaded
        plan.release(0)
        assert plan.assign(0) == 0

    def test_least_loaded_wins(self):
        plan = DevicePlan(devices=list("ab"))
        assert plan.assign(0) == 0
        assert plan.assign(1) == 1
        assert plan.assign(2) == 0
        plan.release(0)
        plan.release(2)  # device 0 now empty, device 1 holds partition 1
        assert plan.assign(3) == 0

    def test_exclude_moves_partitions_to_remaining(self):
        plan = DevicePlan(devices=list("abcd"))
        for p in range(8):
            plan.assign(p)
        moves = plan.exclude(1)
        assert set(moves) == {1, 5}  # partitions that lived on device 1
        assert all(idx != 1 for idx in moves.values())
        assert all(idx != 1 for idx in plan.assignments().values())
        # new placements stay balanced over the healthy devices
        load = plan.load()
        assert load[1] == 0
        assert max(load[i] for i in (0, 2, 3)) <= 3

    def test_excluded_device_not_assigned_and_readmit(self):
        plan = DevicePlan(devices=list("ab"))
        plan.exclude(0)
        assert all(plan.assign(p) == 1 for p in range(3))
        plan.readmit(0)
        assert plan.assign(100) == 0  # emptiest again

    def test_all_excluded_raises(self):
        plan = DevicePlan(devices=list("ab"))
        plan.exclude(0)
        plan.exclude(1)
        with pytest.raises(RuntimeError, match="every device is excluded"):
            plan.assign(0)

    @pytest.mark.parametrize("devices, used", [(1, 1), (0, 4)])
    def test_mesh_devices_caps_a_brokers_plan(self, tmp_path, devices, used):
        """``[mesh] devices = 1`` (zbench/configs/mixed-4p.json) places a
        broker's four leaders on ONE device although more are visible;
        the default (0) spreads them over four."""
        from zeebe_tpu.runtime.cluster_broker import ClusterBroker
        from zeebe_tpu.runtime.config import load_config

        assert len(jax.devices()) >= 4
        cfg = load_config(toml_text=(
            "[network]\nclientPort = 0\nmanagementPort = 0\n"
            "subscriptionPort = 0\n[metrics]\nport = 0\nenabled = false\n"
            f"[cluster]\npartitions = 4\n[mesh]\ndevices = {devices}\n"
        ))
        broker = ClusterBroker(cfg, str(tmp_path / "b0"))
        try:
            placed = [broker.planned_device(pid) for pid in range(4)]
        finally:
            broker.close()
        assert len({device for device, _ in placed}) == used
        assert sorted({idx for _, idx in placed}) == list(range(used))
        if used == 1:
            assert placed[0][0] == jax.devices()[0]

    def test_load_gauges_published(self):
        plan = DevicePlan(devices=list("ab"))
        plan.assign(0)
        plan.assign(1)
        plan.assign(2)
        g = GLOBAL_REGISTRY.gauge("mesh_device_partitions", device="0")
        assert g.value == 2
        assert GLOBAL_REGISTRY.gauge("mesh_devices_healthy").value >= 2


# ---------------------------------------------------------------------------
# MeshExchange (the all_to_all frame hop)
# ---------------------------------------------------------------------------


class TestMeshExchange:
    def test_frames_round_trip_in_order(self):
        ex = MeshExchange(jax.devices()[:4], slots=4, frame_bytes=64)
        assert ex.queue(0, 2, 7, b"one")
        assert ex.queue(0, 2, 7, b"two")
        assert ex.queue(3, 2, 9, b"three")
        assert ex.queue(2, 0, 1, b"home")
        got = []
        delivered = ex.flush(lambda pid, frame: got.append((pid, frame)))
        assert delivered == 4
        # per destination: source-device order, then slot (queue) order
        assert got == [
            (1, b"home"),            # → device 0
            (7, b"one"), (7, b"two"),  # → device 2 from device 0
            (9, b"three"),           # → device 2 from device 3
        ]
        assert ex.pending() == 0

    def test_oversize_frame_refused_and_counted(self):
        ex = MeshExchange(jax.devices()[:2], slots=2, frame_bytes=16)
        before = event_count("mesh_exchange_fallbacks")
        assert not ex.queue(0, 1, 0, b"x" * 17)
        assert event_count("mesh_exchange_fallbacks") == before + 1

    def test_slot_overflow_refused(self):
        ex = MeshExchange(jax.devices()[:2], slots=2, frame_bytes=16)
        assert ex.queue(0, 1, 0, b"a")
        assert ex.queue(0, 1, 0, b"b")
        assert not ex.queue(0, 1, 0, b"c")  # pair budget exhausted
        assert ex.queue(1, 0, 0, b"d")  # other pairs unaffected

    def test_flush_with_nothing_queued_is_noop(self):
        ex = MeshExchange(jax.devices()[:2], slots=2, frame_bytes=16)
        assert ex.flush(lambda *_: pytest.fail("nothing to deliver")) == 0

    def test_failing_collective_still_delivers_frames(self):
        """The mesh hop is an optimization, never a durability boundary:
        when the collective raises, the round's frames (still in host
        memory) deliver directly — a dropped subscription OPEN would
        wedge its instance forever."""
        ex = MeshExchange(jax.devices()[:2], slots=4, frame_bytes=32)
        assert ex.queue(0, 1, 3, b"alpha")
        assert ex.queue(0, 1, 3, b"beta")
        assert ex.queue(1, 0, 0, b"gamma")

        def boom(*_a, **_k):
            raise RuntimeError("device lost mid-collective")

        ex._step = boom
        before = event_count("mesh_exchange_flush_failures")
        got = []
        delivered = ex.flush(lambda pid, frame: got.append((pid, frame)))
        assert delivered == 3
        # per-(src,dst) order preserved in the fallback
        assert got == [(3, b"alpha"), (3, b"beta"), (0, b"gamma")]
        assert event_count("mesh_exchange_flush_failures") > before
        assert ex.pending() == 0


# ---------------------------------------------------------------------------
# scheduler-level: shared waves span devices; flap keeps in-flight waves
# ---------------------------------------------------------------------------


class _Rec:
    __slots__ = ("position",)

    def __init__(self, position):
        self.position = position


class PlacedFeed(PartitionFeed):
    """Queue-backed pipelined feed tagged with a plan device (the shape
    PartitionServer presents to the scheduler in mesh mode)."""

    def __init__(self, pid, n, device_index, fail_dispatch=False):
        self.partition_id = pid
        self.device_index = device_index
        self.cursor = 0
        self.limit_n = n
        self.fail_dispatch = fail_dispatch
        self.dispatched = []
        self.collected = []

    def backlog(self):
        return self.limit_n - self.cursor

    def take(self, limit):
        take = min(limit, self.limit_n - self.cursor)
        if take <= 0:
            return []
        out = [_Rec(self.cursor + i) for i in range(take)]
        self.cursor += take
        return out

    def dispatch(self, records):
        if self.fail_dispatch:
            raise RuntimeError("device lost")
        self.dispatched.append(list(records))
        return list(records), 0.0, 0.0

    def collect(self, pending):
        self.collected.append(list(pending))
        return 0.0, 0.0

    def rewind(self, position):
        self.cursor = min(self.cursor, position)


class TestMeshWaves:
    def test_shared_wave_spans_devices(self):
        """One scheduling round's wave carries segments for SEVERAL
        devices — the '>1 device active per round' acceptance metric."""
        ws = WaveScheduler(wave_size=512)
        plan = DevicePlan(devices=list("abcd"))
        feeds = [PlacedFeed(p, 16, plan.assign(p)) for p in range(4)]
        for f in feeds:
            ws.register(f)
        devs_total0 = GLOBAL_REGISTRY.counter(
            "scheduler_wave_devices_total"
        ).value
        shared0 = GLOBAL_REGISTRY.counter(
            "scheduler_shared_waves_total"
        ).value
        ws.drain()
        d_shared = (
            GLOBAL_REGISTRY.counter("scheduler_shared_waves_total").value
            - shared0
        )
        mean_devices = (
            GLOBAL_REGISTRY.counter("scheduler_wave_devices_total").value
            - devs_total0
        ) / max(d_shared, 1)
        assert mean_devices > 1.0
        assert GLOBAL_REGISTRY.gauge("serving_wave_devices").value == 4
        for f in feeds:
            waves = GLOBAL_REGISTRY.counter(
                "serving_device_waves_total", device=str(f.device_index)
            )
            assert waves.value > 0

    def test_flap_rebalance_keeps_inflight_waves(self):
        """A dispatch failure mid-shared-wave (the device/leadership
        flap): the failing partition's segment REWINDS (records re-drain,
        nothing lost), every other device's in-flight segment still
        collects, and the flapped partition re-places onto the emptiest
        device."""
        ws = WaveScheduler(wave_size=64, quantum=16)
        plan = DevicePlan(devices=list("abc"))
        ok_a = PlacedFeed(0, 32, plan.assign(0))
        flappy = PlacedFeed(1, 32, plan.assign(1))
        ok_b = PlacedFeed(2, 32, plan.assign(2))
        flappy.fail_dispatch = True
        for f in (ok_a, flappy, ok_b):
            ws.register(f)
        with pytest.raises(RuntimeError, match="device lost"):
            ws.drain()
        # nothing lost: the flapped feed's cursor rewound to its segment
        # start, the other feeds' dispatched records were all collected
        assert flappy.cursor == 0
        for f in (ok_a, ok_b):
            assert sum(len(c) for c in f.collected) == sum(
                len(d) for d in f.dispatched
            )
        # leadership flap: release + re-assign lands on the emptiest
        # device (its own freed slot here)
        old = flappy.device_index
        plan.release(1)
        assert plan.assign(1) == old
        # after the flap the feed drains to completion
        flappy.fail_dispatch = False
        ws.drain()
        assert flappy.cursor == 32
        assert sum(len(c) for c in flappy.collected) == 32


# ---------------------------------------------------------------------------
# engine placement: committed state, migration, serving parity
# ---------------------------------------------------------------------------


def _mesh_workload(data_dir, devices, partitions=4, exchange=None):
    """Deterministic multi-partition device-engine workload; returns
    (per-partition frames, per-partition raw segment bytes). ``devices``
    is a list of per-partition jax devices (None = default placement)."""
    from zeebe_tpu.engine.interpreter import WorkflowRepository
    from zeebe_tpu.gateway import JobWorker, ZeebeClient
    from zeebe_tpu.gateway import workers as workers_mod
    from zeebe_tpu.models.bpmn.builder import Bpmn
    from zeebe_tpu.protocol import codec
    from zeebe_tpu.protocol.intents import WorkflowInstanceIntent
    from zeebe_tpu.protocol.records import WorkflowInstanceRecord
    from zeebe_tpu.runtime import Broker, ControlledClock
    from zeebe_tpu.tpu import TpuPartitionEngine

    workers_mod._subscriber_keys = itertools.count(1)
    clock = ControlledClock(start_ms=1_000_000)
    repo = WorkflowRepository()

    def factory(pid):
        dev = devices[pid] if devices is not None else None
        return TpuPartitionEngine(
            pid, partitions, repository=repo, clock=clock,
            device=dev, device_index=pid if dev is not None else -1,
        )

    broker = Broker(
        num_partitions=partitions, data_dir=data_dir, clock=clock,
        engine_factory=factory,
    )
    broker.wave_size = 256
    if exchange is not None:
        broker.mesh_exchange = exchange
    try:
        client = ZeebeClient(broker)
        client.deploy_model(
            Bpmn.create_process("mesh-par")
            .start_event("s")
            .service_task("w", type="mesh-par-svc")
            .end_event("e")
            .done()
        )
        JobWorker(broker, "mesh-par-svc", lambda ctx: {"ok": True})
        for burst in range(2):
            for i in range(4 * partitions):
                broker.write_command(
                    i % partitions,
                    WorkflowInstanceRecord(
                        bpmn_process_id="mesh-par",
                        payload={"b": burst, "i": i},
                    ),
                    WorkflowInstanceIntent.CREATE,
                )
            broker.run_until_idle()
        frames = [
            [codec.encode_record(r) for r in broker.records(pid)]
            for pid in range(partitions)
        ]
    finally:
        broker.close()
    raw = []
    for pid in range(partitions):
        pdir = os.path.join(data_dir, f"partition-{pid}")
        blobs = []
        for name in sorted(os.listdir(pdir)):
            if name.startswith("segment-") and name.endswith(".log"):
                with open(os.path.join(pdir, name), "rb") as f:
                    blobs.append(f.read())
        raw.append(blobs)
    return frames, raw


class TestEnginePlacement:
    def test_state_commits_to_the_assigned_device(self):
        from zeebe_tpu.tpu import TpuPartitionEngine

        dev = jax.devices()[3]
        engine = TpuPartitionEngine(0, 1, device=dev, device_index=3)
        assert engine.state.ei_i32.devices() == {dev}
        assert engine.device_index == 3

    def test_place_on_migrates_live_state(self):
        """Dead-device fallback at the engine level: a served engine moves
        to another device mid-life and keeps serving with its state
        intact."""
        from zeebe_tpu.engine.interpreter import WorkflowRepository
        from zeebe_tpu.gateway import JobWorker, ZeebeClient
        from zeebe_tpu.gateway import workers as workers_mod
        from zeebe_tpu.models.bpmn.builder import Bpmn
        from zeebe_tpu.runtime import Broker, ControlledClock
        from zeebe_tpu.tpu import TpuPartitionEngine

        workers_mod._subscriber_keys = itertools.count(1)
        clock = ControlledClock(start_ms=1_000_000)
        repo = WorkflowRepository()
        devs = jax.devices()
        engine_box = []

        def factory(pid):
            engine = TpuPartitionEngine(
                pid, 1, repository=repo, clock=clock,
                device=devs[1], device_index=1,
            )
            engine_box.append(engine)
            return engine

        with tempfile.TemporaryDirectory() as data_dir:
            broker = Broker(
                num_partitions=1, data_dir=data_dir, clock=clock,
                engine_factory=factory,
            )
            try:
                client = ZeebeClient(broker)
                client.deploy_model(
                    Bpmn.create_process("mig")
                    .start_event("s")
                    .service_task("w", type="mig-svc")
                    .end_event("e")
                    .done()
                )
                done = []
                JobWorker(broker, "mig-svc", lambda ctx: done.append(1) or {})
                client.create_instance("mig", {"i": 0})
                broker.run_until_idle()
                assert len(done) == 1
                # device 1 died: fall back to device 2 with live state
                engine = engine_box[0]
                engine.place_on(devs[2], 2)
                assert engine.state.ei_i32.devices() == {devs[2]}
                client.create_instance("mig", {"i": 1})
                broker.run_until_idle()
                assert len(done) == 2
            finally:
                broker.close()

    def test_mesh_vs_single_device_logs_bit_identical(self, tmp_path):
        """THE parity pin: frames AND raw on-disk segment bytes are
        identical whether partitions spread over the mesh or share the
        default device."""
        devs = jax.devices()[:4]
        frames_mesh, raw_mesh = _mesh_workload(
            str(tmp_path / "m"), list(devs)
        )
        frames_single, raw_single = _mesh_workload(str(tmp_path / "s"), None)
        assert sum(len(f) for f in frames_mesh) > 100
        for pid, (a, b) in enumerate(zip(frames_mesh, frames_single)):
            assert a == b, f"partition {pid} frames diverged under mesh"
        for pid, (a, b) in enumerate(zip(raw_mesh, raw_single)):
            assert a and a == b, f"partition {pid} raw bytes diverged"


# ---------------------------------------------------------------------------
# exchange-routed correlation: same log bytes as the direct hop
# ---------------------------------------------------------------------------


def _correlation_workload(data_dir, exchange):
    from zeebe_tpu.engine.interpreter import WorkflowRepository
    from zeebe_tpu.gateway import ZeebeClient
    from zeebe_tpu.gateway import workers as workers_mod
    from zeebe_tpu.models.bpmn.builder import Bpmn
    from zeebe_tpu.protocol import codec
    from zeebe_tpu.runtime import Broker, ControlledClock
    from zeebe_tpu.tpu import TpuPartitionEngine

    workers_mod._subscriber_keys = itertools.count(1)
    clock = ControlledClock(start_ms=1_000_000)
    repo = WorkflowRepository()
    devs = jax.devices()

    def factory(pid):
        return TpuPartitionEngine(
            pid, 2, repository=repo, clock=clock,
            device=devs[pid], device_index=pid,
        )

    broker = Broker(
        num_partitions=2, data_dir=data_dir, clock=clock,
        engine_factory=factory,
    )
    if exchange:
        broker.mesh_exchange = MeshExchange(
            devs[:2], slots=8, frame_bytes=2048
        )
    try:
        client = ZeebeClient(broker)
        client.deploy_model(
            Bpmn.create_process("xcorr")
            .start_event("s")
            .receive_task("wait", message_name="paid",
                          correlation_key="$.oid")
            .end_event("e")
            .done()
        )
        for i in range(6):
            # the key "k-i" hashes to partition i % 2 — creating the
            # instance on the OTHER partition forces every subscription
            # OPEN/CORRELATE across partitions (and across devices)
            client.create_instance(
                "xcorr", {"oid": f"k-{i}"}, partition_id=(i + 1) % 2
            )
        broker.run_until_idle()
        for i in range(6):
            client.publish_message("paid", f"k-{i}")
        broker.run_until_idle()
        return [
            [codec.encode_record(r) for r in broker.records(pid)]
            for pid in range(2)
        ]
    finally:
        broker.close()


class TestExchangeRouting:
    def test_exchange_routed_correlation_bit_identical(self, tmp_path):
        """Cross-partition subscription commands riding the all_to_all
        frame exchange produce EXACTLY the logs the direct (transport-
        analog) hop produces — the frames ARE the wire bytes — and the
        mesh counter proves they actually rode the mesh."""
        before = event_count("mesh_exchange_frames")
        frames_x = _correlation_workload(str(tmp_path / "x"), True)
        rode_mesh = event_count("mesh_exchange_frames") - before
        frames_d = _correlation_workload(str(tmp_path / "d"), False)
        assert rode_mesh > 0, "no frames rode the mesh exchange"
        for pid, (a, b) in enumerate(zip(frames_x, frames_d)):
            assert a == b, f"partition {pid} diverged (exchange vs direct)"


# ---------------------------------------------------------------------------
# cluster broker: plan wiring, leadership flap, dead-device fallback
# ---------------------------------------------------------------------------


def _boot_mesh_cluster(tmp_path, partitions=2):
    from zeebe_tpu.runtime.cluster_broker import ClusterBroker
    from zeebe_tpu.runtime.config import BrokerCfg
    from zeebe_tpu.runtime.engines import engine_factory_from_config

    cfg = BrokerCfg()
    cfg.network.client_port = 0
    cfg.network.management_port = 0
    cfg.network.subscription_port = 0
    cfg.metrics.port = 0
    cfg.metrics.enabled = False
    cfg.cluster.partitions = partitions
    cfg.engine.type = "tpu"
    cfg.engine.capacity = 1 << 10
    broker = ClusterBroker(
        cfg, os.path.join(str(tmp_path), "b0"),
        engine_factory=engine_factory_from_config(cfg),
    )
    for pid in range(partitions):
        broker.open_partition(pid).join(60)
        broker.bootstrap_partition(pid, {})
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and not all(
        broker.partitions[pid].is_leader for pid in range(partitions)
    ):
        time.sleep(0.02)
    assert all(
        broker.partitions[pid].is_leader for pid in range(partitions)
    )
    return broker


@pytest.mark.slow
class TestClusterMesh:
    """Device-engine cluster legs (slow tier with the other TPU cluster
    suites: per-device kernel compiles dominate on the CPU container)."""

    def test_partitions_placed_across_devices_and_flap_rebalances(
        self, tmp_path
    ):
        from zeebe_tpu.gateway.cluster_client import ClusterClient
        from zeebe_tpu.models.bpmn.builder import Bpmn

        broker = _boot_mesh_cluster(tmp_path, partitions=2)
        client = None
        try:
            plan = broker.device_plan
            assert plan is not None
            placed = plan.assignments()
            assert len(placed) == 2
            assert placed[0] != placed[1], "partitions share a device"

            def device_waves():
                return [
                    GLOBAL_REGISTRY.counter(
                        "serving_device_waves_total", device=str(placed[pid])
                    ).value
                    for pid in (0, 1)
                ]

            def shed():
                return sum(
                    GLOBAL_REGISTRY.counter(
                        "gateway_commands_shed", reason=reason
                    ).value
                    for reason in ("CONNECTION_INFLIGHT", "QUEUE_DEPTH")
                )

            waves0, shed0 = device_waves(), shed()
            client = ClusterClient(
                [broker.client_address], num_partitions=2,
                request_timeout_ms=120_000,
            )
            client.deploy_model(
                Bpmn.create_process("cm").start_event("s").end_event("e")
                .done()
            )
            for pid in (0, 1):
                rsp = client.create_instance("cm", partition_id=pid)
                assert rsp.value.workflow_instance_key > 0
            # served over the sockets: every placed device received waves
            # and nominal load shed nothing
            assert all(b > a for a, b in zip(waves0, device_waves()))
            assert shed() == shed0

            # leadership flap on partition 1: uninstall + reinstall (raft
            # stays leader; the serving install re-runs) — the plan frees
            # the slot and re-places, and serving continues with no
            # records lost
            server = broker.partitions[1]
            term = server.raft.term
            broker.actor.call(server._uninstall_leader).join(10)
            assert plan.device_index(1) == -1
            broker.actor.call(lambda: server._install_leader(term)).join(60)
            assert plan.device_index(1) >= 0
            rsp = client.create_instance("cm", partition_id=1)
            assert rsp.value.workflow_instance_key > 0
        finally:
            if client is not None:
                client.close()
            broker.close()

    def test_excluded_device_falls_back_to_remaining(self, tmp_path):
        from zeebe_tpu.gateway.cluster_client import ClusterClient
        from zeebe_tpu.models.bpmn.builder import Bpmn

        broker = _boot_mesh_cluster(tmp_path, partitions=2)
        client = None
        try:
            plan = broker.device_plan
            victim = plan.device_index(0)
            client = ClusterClient(
                [broker.client_address], num_partitions=2,
                request_timeout_ms=120_000,
            )
            client.deploy_model(
                Bpmn.create_process("cx").start_event("s").end_event("e")
                .done()
            )
            client.create_instance("cx", partition_id=0)
            moves = broker.exclude_device(victim).join(60)
            assert moves.get(0, victim) != victim
            new_idx = plan.device_index(0)
            assert new_idx >= 0 and new_idx != victim
            engine = broker.partitions[0].engine
            assert engine.state.ei_i32.devices() == {
                plan.devices[new_idx]
            }
            # the partition keeps serving from the fallback device
            rsp = client.create_instance("cx", partition_id=0)
            assert rsp.value.workflow_instance_key > 0
        finally:
            if client is not None:
                client.close()
            broker.close()
