"""Mesh-sharded partition state (ISSUE 19): one partition's
instance/job/timer/message tables block-shard over a mesh span, the step
gathers them per wave and keeps local row blocks on write — and the hard
contract is the same as mesh placement (test_mesh.py): sharding is a
WHERE change, never a WHAT change. Logs (frames AND raw segment bytes)
are bit-identical to the single-device engine, key-hash routing is
deterministic and host/device-agreed, snapshots round-trip across shard
counts, and a fixed-seed crash-stop replays to the identical log."""

import dataclasses
import itertools
import os
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI
from zeebe_tpu.runtime.metrics import GLOBAL_REGISTRY, event_count
from zeebe_tpu.scheduler import PartitionFeed, WaveScheduler
from zeebe_tpu.scheduler.placement import DevicePlan
from zeebe_tpu.tpu import shard
from zeebe_tpu.tpu import hashmap
from zeebe_tpu.tpu import state as state_mod

SEED = 0x5A4DED


# ---------------------------------------------------------------------------
# key-hash routing: deterministic, host == device
# ---------------------------------------------------------------------------


def _key_corpus():
    rng = np.random.default_rng(SEED)
    keys = np.concatenate([
        np.arange(0, 256, dtype=np.int64),
        rng.integers(1, 1 << 62, size=256, dtype=np.int64),
        np.array([0, 1, (1 << 62) - 1, np.iinfo(np.int64).max], np.int64),
    ])
    return keys


class TestKeyHashRouting:
    def test_host_and_device_hash_agree(self):
        """shard_of_key (device) and shard_of_key_host (wave staging) are
        the same function — the routing plane has ONE hash."""
        keys = _key_corpus()
        for ns in (2, 3, 4, 8):
            dev = np.asarray(shard.shard_of_key(jnp.asarray(keys), ns))
            host = shard.shard_of_key_host(keys, ns)
            np.testing.assert_array_equal(dev, host)
            assert host.min() >= 0 and host.max() < ns

    def test_routing_is_deterministic_and_key_only(self):
        """Same key → same shard, independent of position in the wave or
        of any other key in it."""
        keys = _key_corpus()
        a = shard.shard_of_key_host(keys, 8)
        b = shard.shard_of_key_host(keys, 8)
        np.testing.assert_array_equal(a, b)
        perm = np.random.default_rng(SEED + 1).permutation(len(keys))
        np.testing.assert_array_equal(
            shard.shard_of_key_host(keys[perm], 8), a[perm]
        )

    def test_row_counts_match_host_and_respect_valid(self):
        keys = _key_corpus()
        valid = np.random.default_rng(SEED + 2).random(len(keys)) < 0.7
        for ns in (2, 8):
            dev = np.asarray(
                shard.shard_row_counts(jnp.asarray(keys), jnp.asarray(valid), ns)
            )
            host = shard.shard_row_counts_host(keys, valid, ns)
            np.testing.assert_array_equal(dev, host)
            assert host.sum() == valid.sum()

    def test_hash_spreads_sequential_keys(self):
        """Entity keys are near-sequential (per-partition counters); the
        Fibonacci hash must still spread them instead of striping."""
        counts = shard.shard_row_counts_host(
            np.arange(1, 4097, dtype=np.int64), np.ones(4096, bool), 8
        )
        assert counts.min() > 0
        assert counts.max() < 2 * counts.mean()


# ---------------------------------------------------------------------------
# spec tree + exchange model
# ---------------------------------------------------------------------------


class TestStateShardingSpecs:
    def _state(self):
        return state_mod.make_state(
            capacity=256, num_vars=8, job_capacity=256, sub_capacity=8
        )

    def _zipped(self, state, ns):
        specs = shard.state_partition_specs(state, ns)
        leaves = jax.tree_util.tree_flatten_with_path(state)[0]
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P)
        )
        assert len(leaves) == len(spec_leaves)
        return [
            (jax.tree_util.keystr(path), leaf, s)
            for (path, leaf), s in zip(leaves, spec_leaves)
        ]

    def test_row_tables_shard_and_scalars_replicate(self):
        state = self._state()
        sharded = {
            name for name, _, s in self._zipped(state, 8)
            if tuple(s) == (shard.STATE_AXIS,)
        }
        # the big row-table families are sharded...
        for fam in ("ei_i32", "job_i32", "timer_key", "ei_pay"):
            assert any(fam in n for n in sharded), f"{fam} not sharded"
        # ...and every scalar/rank-0 leaf stays replicated
        for name, leaf, s in self._zipped(state, 8):
            if np.ndim(leaf) == 0:
                assert tuple(s) == (), f"scalar {name} got spec {s}"

    def test_sharded_leaves_divide_evenly(self):
        state = self._state()
        for name, leaf, s in self._zipped(state, 8):
            if tuple(s) == (shard.STATE_AXIS,):
                assert leaf.shape[0] % 8 == 0, name

    def test_non_divisible_tables_fall_back_replicated(self):
        """num_shards that doesn't divide a table's rows must NOT shard it
        (correctness never depends on which leaves shard)."""
        state = self._state()
        for name, leaf, s in self._zipped(state, 7):
            if tuple(s) == (shard.STATE_AXIS,):
                assert leaf.shape[0] % 7 == 0, name

    def test_exchange_bytes_scale_with_span(self):
        """One wave's gather volume is sharded_bytes * (D-1): zero on a
        single device, linear in the span beyond it."""
        state = self._state()
        assert shard.state_exchange_bytes(state, 1) == 0
        eb2 = shard.state_exchange_bytes(state, 2)
        eb8 = shard.state_exchange_bytes(state, 8)
        assert eb2 > 0
        assert eb8 == 7 * eb2


# ---------------------------------------------------------------------------
# DevicePlan spans
# ---------------------------------------------------------------------------


class TestDevicePlanSpans:
    def test_span_assignment_sticky_and_sorted(self):
        plan = DevicePlan(devices=list("abcdefgh"))
        got = plan.assign_span(0, 4)
        assert got == sorted(got) and len(got) == 4
        assert plan.assign_span(0, 4) == got  # sticky
        assert plan.device_indices(0) == got
        assert plan.devices_for(0) == [plan.devices[i] for i in got]
        assert plan.device_index(0) == got[0]  # primary

    def test_spans_balance_across_the_mesh(self):
        plan = DevicePlan(devices=list("abcdefgh"))
        s0 = plan.assign_span(0, 4)
        s1 = plan.assign_span(1, 4)
        assert not set(s0) & set(s1), "second span landed on loaded devices"
        load = plan.load()
        assert all(load[i] == 1 for i in range(8))

    def test_span_of_one_degenerates_to_assign(self):
        plan = DevicePlan(devices=list("ab"))
        assert plan.assign_span(3, 1) == [plan.device_index(3)]
        assert plan.device_indices(3) == [plan.device_index(3)]

    def test_release_frees_the_whole_span(self):
        plan = DevicePlan(devices=list("abcd"))
        plan.assign_span(0, 4)
        plan.release(0)
        assert plan.device_indices(0) == []
        assert all(v == 0 for v in plan.load().values())

    def test_exclude_respans_sharded_victims(self):
        plan = DevicePlan(devices=list("abcdefgh"))
        span = plan.assign_span(0, 4)
        victim = span[1]
        moves = plan.exclude(victim)
        assert 0 in moves
        new_span = plan.device_indices(0)
        assert len(new_span) == 4
        assert victim not in new_span
        assert moves[0] == new_span[0]

    def test_span_larger_than_healthy_mesh_raises(self):
        plan = DevicePlan(devices=list("ab"))
        plan.exclude(0)
        with pytest.raises(RuntimeError, match="exceeds the 1 healthy"):
            plan.assign_span(0, 2)


# ---------------------------------------------------------------------------
# scheduler: a sharded segment's wave counts its WHOLE span active
# ---------------------------------------------------------------------------


class _Rec:
    __slots__ = ("position",)

    def __init__(self, position):
        self.position = position


class _SpanFeed(PartitionFeed):
    def __init__(self, pid, n, span):
        self.partition_id = pid
        self.device_index = span[0]
        self.device_indices = tuple(span)
        self.cursor = 0
        self.limit_n = n

    def backlog(self):
        return self.limit_n - self.cursor

    def take(self, limit):
        take = min(limit, self.limit_n - self.cursor)
        out = [_Rec(self.cursor + i) for i in range(take)]
        self.cursor += take
        return out

    def dispatch(self, records):
        return list(records), 0.0, 0.0

    def collect(self, pending):
        return 0.0, 0.0

    def rewind(self, position):
        self.cursor = min(self.cursor, position)


class TestSchedulerSpanAccounting:
    def test_wave_devices_gauge_counts_the_span(self):
        ws = WaveScheduler(wave_size=64)
        ws.register(_SpanFeed(0, 16, (0, 2, 5)))
        ws.drain()
        assert GLOBAL_REGISTRY.gauge("serving_wave_devices").value == 3


# ---------------------------------------------------------------------------
# engine guards
# ---------------------------------------------------------------------------


class TestShardedEngineGuards:
    def test_pinned_device_conflicts_with_sharding(self):
        from zeebe_tpu.tpu import TpuPartitionEngine

        with pytest.raises(ValueError, match="cannot also be pinned"):
            TpuPartitionEngine(
                0, 1, state_shards=2, device=jax.devices()[0], device_index=0
            )

    def test_span_larger_than_devices_raises(self):
        from zeebe_tpu.tpu import TpuPartitionEngine

        with pytest.raises(ValueError, match="needs that many devices"):
            TpuPartitionEngine(0, 1, state_shards=64)

    def test_sharded_engine_refuses_live_migration(self):
        """place_on is the single-device fallback path; a sharded engine
        is pinned to its span and rebuilds via snapshot → restore."""
        from zeebe_tpu.tpu import TpuPartitionEngine

        engine = TpuPartitionEngine(0, 1, capacity=256, state_shards=2)
        assert engine.device_indices == [0, 1]
        assert engine._shard_exchange_bytes > 0
        with pytest.raises(RuntimeError, match="pinned to its mesh span"):
            engine.place_on(jax.devices()[0], 0)


# ---------------------------------------------------------------------------
# serving parity: sharded tables, identical logs
# ---------------------------------------------------------------------------


def _sharded_workload(data_dir, state_shards, engine_box=None, **engine_kw):
    """Single-partition device-engine workload (service task + timer —
    instance, job AND timer tables all see traffic); returns
    (frames, raw segment bytes). ``engine_kw`` forwards to the engine
    ctor (``routing="resident"``, ``routed_lane_slots=...``)."""
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
        engine = TpuPartitionEngine(
            pid, 1, repository=repo, clock=clock, capacity=1 << 10,
            state_shards=state_shards, **engine_kw,
        )
        if engine_box is not None:
            engine_box.append(engine)
        return engine

    broker = Broker(
        num_partitions=1, data_dir=data_dir, clock=clock,
        engine_factory=factory,
    )
    broker.wave_size = 128
    try:
        client = ZeebeClient(broker)
        client.deploy_model(
            Bpmn.create_process("shst")
            .start_event("s")
            .service_task("w", type="shst-svc")
            .timer_catch_event("cool", duration_ms=5_000)
            .end_event("e")
            .done()
        )
        JobWorker(broker, "shst-svc", lambda ctx: {"ok": True})
        for burst in range(2):
            for i in range(16):
                broker.write_command(
                    0,
                    WorkflowInstanceRecord(
                        bpmn_process_id="shst", payload={"b": burst, "i": i}
                    ),
                    WorkflowInstanceIntent.CREATE,
                )
            broker.run_until_idle()
            clock.advance(10_000)
            broker.tick()
            broker.run_until_idle()
        frames = [codec.encode_record(r) for r in broker.records(0)]
    finally:
        broker.close()
    blobs = []
    pdir = os.path.join(data_dir, "partition-0")
    for name in sorted(os.listdir(pdir)):
        if name.startswith("segment-") and name.endswith(".log"):
            with open(os.path.join(pdir, name), "rb") as f:
                blobs.append(f.read())
    return frames, blobs


@pytest.fixture(scope="module")
def single_device_baseline(tmp_path_factory):
    """The single-device drain of THE workload, run once per module: the
    deterministic oracle every parity test compares against (same seeds,
    same clock schedule — bit-identical across runs by construction, so
    sharing it is sound and saves three full drains of tier-1 wall)."""
    return _sharded_workload(str(tmp_path_factory.mktemp("un")), 1)


class TestShardedServingParity:
    def test_sharded_vs_single_device_logs_bit_identical(
        self, tmp_path, single_device_baseline
    ):
        """THE parity pin (acceptance): frames AND raw on-disk segment
        bytes identical with the tables sharded over all 8 devices — and
        the waves actually rode the sharded step (metrics prove it)."""
        waves0 = GLOBAL_REGISTRY.counter("serving_sharded_waves_total").value
        bytes0 = GLOBAL_REGISTRY.counter("mesh_shard_exchange_bytes_total").value
        box = []
        frames_sh, raw_sh = _sharded_workload(
            str(tmp_path / "sh"), 8, engine_box=box
        )
        d_waves = (
            GLOBAL_REGISTRY.counter("serving_sharded_waves_total").value - waves0
        )
        d_bytes = (
            GLOBAL_REGISTRY.counter("mesh_shard_exchange_bytes_total").value
            - bytes0
        )
        frames_un, raw_un = single_device_baseline
        assert len(frames_sh) > 100
        assert frames_sh == frames_un, "frames diverged under sharding"
        assert raw_sh and raw_sh == raw_un, "raw segment bytes diverged"
        # the sharded run really ran sharded
        engine = box[0]
        assert engine.device_indices == list(range(8))
        assert engine.sharded_waves > 0
        assert d_waves >= engine.sharded_waves
        assert d_bytes >= engine.sharded_waves * engine._shard_exchange_bytes
        # per-shard routing gauges populated for the whole span
        for d in range(8):
            assert (
                GLOBAL_REGISTRY.gauge("mesh_shard_rows", device=str(d)).value
                >= 0
            )


# ---------------------------------------------------------------------------
# sharded-state v2 (ISSUE 20): residency-routed staging
# ---------------------------------------------------------------------------


class TestRoutedServingParity:
    """Resident routing is a HOW change, never a WHAT change: the routed
    lane program, the overflow fallback, and the v1 gathered step must
    all drain the same workload to bit-identical logs."""

    def _routed_run(self, data_dir, shards, **kw):
        box = []
        frames, raw = _sharded_workload(
            data_dir, shards, engine_box=box, routing="resident", **kw
        )
        return frames, raw, box[0]

    def test_routed_vs_single_device_logs_bit_identical(
        self, tmp_path, monkeypatch, single_device_baseline
    ):
        """THE v2 parity pin (acceptance): 8-shard resident routing vs
        the single-device engine, frames AND raw segment bytes — the
        routed lane program actually carried waves, every routed wave's
        staged split landed on ONE lane (flagged single-lane for the
        skew gauge), and every residency entry sits on the
        host/device-agreed hash shard of its instance key (shard_of_key
        parity ON the routed staging plane). A routed wave moves strictly
        fewer collective bytes than a gathered wave of the same span."""
        from zeebe_tpu.runtime import metrics as metrics_mod

        observed = []
        real = metrics_mod.observe_sharded_wave

        def spy(split, xb, single_lane=False):
            observed.append((list(int(x) for x in split), single_lane, xb))
            real(split, xb, single_lane=single_lane)

        monkeypatch.setattr(metrics_mod, "observe_sharded_wave", spy)
        frames_rt, raw_rt, engine = self._routed_run(
            str(tmp_path / "rt"), 8
        )
        resident = dict(engine._resident)
        frames_un, raw_un = single_device_baseline
        assert len(frames_rt) > 100
        assert frames_rt == frames_un, "frames diverged under routing"
        assert raw_rt and raw_rt == raw_un, "raw segment bytes diverged"
        assert engine.routing == "resident"
        assert engine.routed_waves > 0, "no wave took the routed program"
        assert engine.routed_overflows == 0, (
            "default lanes overflowed on a 32-instance workload"
        )
        # completed instances demote; re-learned entries may remain from
        # in-flight timers — either way the invariant holds for all
        for ik, owner in resident.items():
            assert owner == shard.shard_of_key_host(ik, 8), ik
        if resident:
            keys = np.fromiter(resident, dtype=np.int64)
            np.testing.assert_array_equal(
                np.asarray(shard.shard_of_key(jnp.asarray(keys), 8)),
                np.asarray([resident[int(k)] for k in keys]),
            )
        routed = [(s, xb) for s, single, xb in observed if single and sum(s)]
        assert len(routed) == engine.routed_waves > 0
        for fill, xb in routed:
            assert len(fill) == 8
            assert sum(1 for v in fill if v) == 1, fill
            assert 0 < xb < engine._shard_exchange_bytes, xb

    @pytest.mark.slow
    def test_routed_vs_gathered_bit_identity_small_spans(self, tmp_path):
        """Routed-vs-gathered across the remaining shard counts (8 is
        pinned above against single-device, which gathered parity
        already equals; slow tier with the other heavy parity legs)."""
        for shards in (2, 4):
            frames_rt, raw_rt, engine = self._routed_run(
                str(tmp_path / f"rt{shards}"), shards
            )
            frames_g, raw_g = _sharded_workload(
                str(tmp_path / f"g{shards}"), shards
            )
            assert engine.routed_waves > 0
            assert frames_rt == frames_g, f"{shards}-shard logs diverged"
            assert raw_rt == raw_g, f"{shards}-shard raw bytes diverged"

    def test_undersized_lanes_overflow_to_fallback_losslessly(
        self, tmp_path, monkeypatch, single_device_baseline
    ):
        """Overflow-fallback parity: 2-slot lanes force every multi-row
        wave through the gathered fallback — counted, demoted from
        residency, and STILL bit-identical. Any wave that DOES route
        lands on exactly one lane; fallback waves keep the advisory
        key-hash split (never flagged single-lane)."""
        from zeebe_tpu.runtime import metrics as metrics_mod

        observed = []
        real = metrics_mod.observe_sharded_wave

        def spy(split, xb, single_lane=False):
            observed.append((list(int(x) for x in split), single_lane))
            real(split, xb, single_lane=single_lane)

        monkeypatch.setattr(metrics_mod, "observe_sharded_wave", spy)
        frames_rt, raw_rt, engine = self._routed_run(
            str(tmp_path / "rt"), 4, routed_lane_slots=2
        )
        frames_un, raw_un = single_device_baseline
        assert frames_rt == frames_un, "overflow fallback diverged"
        assert raw_rt == raw_un
        assert engine.routed_overflows > 0, "lanes never overflowed"
        assert engine.fallback_waves > 0, "overflow never took fallback"
        routed = [s for s, single in observed if single and sum(s)]
        assert len(routed) == engine.routed_waves
        for fill in routed:
            assert sum(1 for v in fill if v) == 1, fill
        fallbacks = [s for s, single in observed if not single and sum(s)]
        assert len(fallbacks) == engine.fallback_waves > 0

    def test_message_graphs_refuse_routing(self, tmp_path):
        """Message-correlation state is cross-instance by nature; a
        resident engine serving a message graph routes NOTHING (all
        waves fall back) and stays bit-identical — pinned by the slow
        correlation suite; here we pin the guard itself."""
        from zeebe_tpu.tpu import TpuPartitionEngine

        engine = TpuPartitionEngine(
            0, 1, capacity=256, state_shards=2, routing="resident"
        )
        assert engine._routing_active() is False  # no graph yet

    def test_unknown_routing_mode_raises(self):
        from zeebe_tpu.tpu import TpuPartitionEngine

        with pytest.raises(ValueError, match="routing"):
            TpuPartitionEngine(
                0, 1, capacity=256, state_shards=2, routing="telepathic"
            )


def _emission_stub(instance_keys, vtypes=None, intents=None, keys=None):
    """Minimal emission-batch stand-in for residency bookkeeping tests:
    just the columns _note_residency / _pop_residency_fallback read."""
    import types

    n = len(instance_keys)
    return types.SimpleNamespace(
        valid=np.ones(n, bool),
        instance_key=np.asarray(instance_keys, np.int64),
        vtype=np.asarray(vtypes if vtypes is not None else [0] * n, np.int32),
        intent=np.asarray(
            intents if intents is not None else [0] * n, np.int32
        ),
        key=np.asarray(keys if keys is not None else [-1] * n, np.int64),
    )


class TestResidencyInvalidation:
    """The residency map must never trust stale knowledge. A gathered
    fallback allocates at GLOBAL free slots, so (a) its collect retires
    every instance its EMISSIONS name — including the ones whose key the
    host could not prove at dispatch, exactly the rows that forced the
    fallback — (b) a routed segment dispatched BEFORE the pop cannot
    note such a key back in when its pipelined collect runs later, and
    (c) while a fallback with host-unprovable rows is in flight, routing
    holds off entirely (any entry might be stale until the emissions
    resolve the keys)."""

    def _engine(self):
        import types

        from zeebe_tpu.tpu import TpuPartitionEngine

        engine = TpuPartitionEngine(
            0, 1, capacity=256, state_shards=2, routing="resident"
        )
        engine.graph = types.SimpleNamespace(has_messages=False)
        assert engine._routing_active()
        return engine

    def test_fallback_collect_retires_emission_instances(self):
        engine = self._engine()
        engine._resident = {11: 1, 22: 0}
        engine._pop_residency_fallback(_emission_stub([11, 11, -1]), seq=7)
        assert engine._resident == {22: 0}
        assert engine._residency_invalid[11] == 7

    def test_stale_note_cannot_reinstate_popped_residency(self):
        engine = self._engine()
        o = _emission_stub([33], vtypes=[int(ValueType.JOB)], keys=[99])
        engine._residency_invalid = {33: 5}
        # dispatched before the fallback that invalidated at seq 5:
        # its collect arrives late (pipelining) and must be ignored
        engine._note_residency(o, owner=1, seq=4)
        assert 33 not in engine._resident
        # a segment dispatched AFTER the invalidation carries newer
        # knowledge and may note again
        engine._note_residency(o, owner=1, seq=6)
        assert engine._resident[33] == 1

    def test_blind_fallback_inflight_gates_routing(self):
        import types

        engine = self._engine()
        engine._resident = {44: 1}
        entry = types.SimpleNamespace(
            value=types.SimpleNamespace(
                headers=types.SimpleNamespace(workflow_instance_key=44)
            )
        )
        args = (entry, False, int(ValueType.JOB), int(RecordType.COMMAND), 0)
        assert engine._wave_route_class(*args) == ("ik", 1)
        engine._blind_fb_inflight = 1
        assert engine._wave_route_class(*args) == ("fb",)
        # CREATEs stay routable through the gate: their root key is
        # freshly allocated, so no residency entry can be stale for them
        create = (
            None, False, int(ValueType.WORKFLOW_INSTANCE),
            int(RecordType.COMMAND), int(WI.CREATE),
        )
        assert engine._wave_route_class(*create) == ("create",)
        engine._blind_fb_inflight = 0
        assert engine._wave_route_class(*args) == ("ik", 1)


class TestRoutedLoweringCensus:
    def test_routed_lowers_without_all_gather_fallback_keeps_it(self):
        """THE op-census acceptance pin: the routed program's lowering
        contains ZERO all_gathers — its only collectives are the
        boundary psums (all_reduce) — while the fallback's lowering
        keeps the row-table gathers (also proving the census string
        actually detects the prim)."""
        import dataclasses as dc

        from zeebe_tpu.testing import graphs
        from jax.sharding import Mesh
        from zeebe_tpu.tpu import batch as rb
        from zeebe_tpu.tpu import state as state_mod

        graph, _meta = graphs.build_graph()
        nv = max(graph.num_vars, 8)
        graph = dc.replace(graph, num_vars=nv)
        mesh = Mesh(np.asarray(jax.devices()), (shard.STATE_AXIS,))
        state_sds = jax.eval_shape(
            lambda: state_mod.make_state(
                capacity=256, num_vars=nv, job_capacity=256, sub_capacity=8
            )
        )
        now = jax.ShapeDtypeStruct((), jnp.int64)
        pid = jax.ShapeDtypeStruct((), jnp.int32)
        batch_sds = jax.eval_shape(lambda: rb.empty(16, nv))
        lanes_sds = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((8,) + tuple(a.shape), a.dtype),
            batch_sds,
        )
        routed = shard.build_state_step_routed(mesh, state_sds)
        text = routed.lower(graph, state_sds, lanes_sds, now, pid).as_text()
        assert "all_gather" not in text, "routed lowering gained a gather"
        assert "all_reduce" in text, "boundary psums missing"
        fallback = shard.build_state_step_fallback(mesh, state_sds)
        ftext = fallback.lower(
            graph, state_sds, batch_sds, now, pid
        ).as_text()
        assert "all_gather" in ftext, "census string detects nothing"


class TestShardSkewGauge:
    def test_skew_ratio_and_warn_counter(self):
        from zeebe_tpu.runtime import metrics as metrics_mod

        g = GLOBAL_REGISTRY.gauge("mesh_shard_skew_ratio")
        skewed0 = GLOBAL_REGISTRY.counter(
            "mesh_shard_skewed_waves_total"
        ).value
        # balanced wave: ratio 1.0, no warn
        metrics_mod.observe_sharded_wave(np.array([8, 8, 8, 8]), 0)
        assert g.value == pytest.approx(1.0)
        # one shard takes everything at meaningful fill: ratio = nshards
        metrics_mod.observe_sharded_wave(np.array([32, 0, 0, 0]), 0)
        assert g.value == pytest.approx(4.0)
        # 4x is the warn threshold boundary (strictly-above fires)
        metrics_mod.observe_sharded_wave(np.array([33, 0, 0, 0, 0]), 0)
        assert g.value > 4.0
        assert GLOBAL_REGISTRY.counter(
            "mesh_shard_skewed_waves_total"
        ).value > skewed0
        # empty waves leave the gauge untouched
        before = g.value
        metrics_mod.observe_sharded_wave(np.array([0, 0, 0, 0]), 0)
        assert g.value == before
        # resident-ROUTED waves are one-lane BY DESIGN: no skew score
        skewed1 = GLOBAL_REGISTRY.counter(
            "mesh_shard_skewed_waves_total"
        ).value
        metrics_mod.observe_sharded_wave(
            np.array([0, 40, 0, 0, 0]), 0, single_lane=True
        )
        assert g.value == before
        assert GLOBAL_REGISTRY.counter(
            "mesh_shard_skewed_waves_total"
        ).value == skewed1


# ---------------------------------------------------------------------------
# cross-shard correlation: sharded partition, same wire bytes
# ---------------------------------------------------------------------------


def _correlation_workload(data_dir, sharded):
    """Two partitions, every subscription OPEN/CORRELATE forced across
    them; partition 0 optionally shards its tables over 4 devices."""
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

    def factory(pid):
        if sharded and pid == 0:
            return TpuPartitionEngine(
                pid, 2, repository=repo, clock=clock, capacity=1 << 10,
                state_shards=4, shard_devices=jax.devices()[:4],
            )
        return TpuPartitionEngine(
            pid, 2, repository=repo, clock=clock, capacity=1 << 10
        )

    broker = Broker(
        num_partitions=2, data_dir=data_dir, clock=clock,
        engine_factory=factory,
    )
    try:
        client = ZeebeClient(broker)
        client.deploy_model(
            Bpmn.create_process("xshard")
            .start_event("s")
            .receive_task("wait", message_name="paid",
                          correlation_key="$.oid")
            .end_event("e")
            .done()
        )
        for i in range(6):
            # "k-i" hashes to partition i % 2; creating on the OTHER
            # partition forces the subscription hop across partitions —
            # for even i the subscription lands IN the sharded tables
            client.create_instance(
                "xshard", {"oid": f"k-{i}"}, partition_id=(i + 1) % 2
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


@pytest.mark.slow
class TestCrossShardCorrelation:
    def test_correlation_parity_with_sharded_partition(self, tmp_path):
        """Cross-partition message correlation with one side's tables
        mesh-sharded produces EXACTLY the transport path's logs — the
        budgeted cross-shard gathers never change a correlation."""
        frames_sh = _correlation_workload(str(tmp_path / "sh"), True)
        frames_un = _correlation_workload(str(tmp_path / "un"), False)
        assert sum(len(f) for f in frames_sh) > 50
        for pid, (a, b) in enumerate(zip(frames_sh, frames_un)):
            assert a == b, f"partition {pid} diverged (sharded vs plain)"


# ---------------------------------------------------------------------------
# snapshot / restore across shard counts
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestShardedSnapshotRestore:
    # lookup structures re-derive from live rows at restore
    DERIVED = {
        "ei_map", "ei_index", "job_map", "job_index",
        "free_ei", "free_ei_pop", "free_ei_push",
        "free_job", "free_job_pop", "free_job_push",
    }

    def _assert_states_equal(self, ea, eb):
        norm_a = state_mod.rebuild_lookup_state(ea.state)
        norm_b = state_mod.rebuild_lookup_state(eb.state)
        for f in dataclasses.fields(ea.state):
            if f.name.startswith("sub_"):
                continue  # transient worker subscriptions drop on restore
            src_a = norm_a if f.name in self.DERIVED else ea.state
            src_b = norm_b if f.name in self.DERIVED else eb.state
            a, b = getattr(src_a, f.name), getattr(src_b, f.name)
            if isinstance(a, hashmap.HashTable):
                for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                    np.testing.assert_array_equal(
                        np.asarray(la), np.asarray(lb), err_msg=f.name
                    )
            else:
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b), err_msg=f.name
                )

    def test_round_trip_across_shard_counts(self, tmp_path):
        """A snapshot taken from an 8-way sharded engine restores
        bit-exactly into a 4-way sharded engine AND into a plain
        single-device engine: the snapshot is shard-layout-free."""
        from zeebe_tpu.engine.interpreter import WorkflowRepository
        from zeebe_tpu.runtime import ControlledClock
        from zeebe_tpu.tpu import TpuPartitionEngine

        box = []
        _sharded_workload(str(tmp_path / "w"), 8, engine_box=box)
        engine = box[0]
        snap = engine.snapshot_state()

        clock = ControlledClock(start_ms=1_000_000)
        for shards in (4, 1):
            restored = TpuPartitionEngine(
                0, 1, repository=WorkflowRepository(), clock=clock,
                capacity=1 << 10, state_shards=shards,
            )
            restored.restore_state(snap)
            self._assert_states_equal(engine, restored)
            if shards > 1:
                # the restored engine is still sharded end to end
                assert restored._mesh is not None
                assert restored._state_step is not None
                assert restored._shard_exchange_bytes > 0
                assert len(restored.state.ei_i32.devices()) == shards


# ---------------------------------------------------------------------------
# fixed-seed chaos: crash-stop replay + (slow) leader flap on a span
# ---------------------------------------------------------------------------


def _chaos_run(data_dir, state_shards, crash, routing="gathered"):
    """Seeded two-burst workload with an optional crash-stop between the
    bursts (close + reopen from the same log dir: replay rebuilds the
    sharded tables). Returns the final frame list."""
    from zeebe_tpu.engine.interpreter import WorkflowRepository
    from zeebe_tpu.gateway import JobWorker, ZeebeClient
    from zeebe_tpu.gateway import workers as workers_mod
    from zeebe_tpu.models.bpmn.builder import Bpmn
    from zeebe_tpu.protocol import codec
    from zeebe_tpu.protocol.intents import WorkflowInstanceIntent
    from zeebe_tpu.protocol.records import WorkflowInstanceRecord
    from zeebe_tpu.runtime import Broker, ControlledClock
    from zeebe_tpu.tpu import TpuPartitionEngine

    rnd = random.Random(SEED)
    clock = ControlledClock(start_ms=1_000_000)

    def boot():
        workers_mod._subscriber_keys = itertools.count(1)
        repo = WorkflowRepository()
        broker = Broker(
            num_partitions=1, data_dir=data_dir, clock=clock,
            engine_factory=lambda pid: TpuPartitionEngine(
                pid, 1, repository=repo, clock=clock, capacity=1 << 10,
                state_shards=state_shards,
                routing=routing if state_shards > 1 else "gathered",
            ),
        )
        broker.wave_size = 128
        JobWorker(broker, "chaos-svc", lambda ctx: {"ok": True})
        return broker

    def burst(broker, b):
        for i in range(12):
            broker.write_command(
                0,
                WorkflowInstanceRecord(
                    bpmn_process_id="chaos",
                    payload={"b": b, "i": i, "r": rnd.randrange(1_000_000)},
                ),
                WorkflowInstanceIntent.CREATE,
            )
        broker.run_until_idle()

    broker = boot()
    try:
        ZeebeClient(broker).deploy_model(
            Bpmn.create_process("chaos")
            .start_event("s")
            .service_task("w", type="chaos-svc")
            .end_event("e")
            .done()
        )
        burst(broker, 0)
        if crash:
            broker.close()
            broker = boot()
            # replay alone must rebuild the state: running to quiescence
            # appends NOTHING new (no duplicated side effects)
            n_records = len(broker.records(0))
            broker.run_until_idle()
            assert len(broker.records(0)) == n_records
        burst(broker, 1)
        return [codec.encode_record(r) for r in broker.records(0)]
    finally:
        broker.close()


@pytest.mark.slow
class TestShardedChaos:
    def test_fixed_seed_crash_stop_replays_identically(self, tmp_path):
        """Acceptance chaos leg: a crash-stop mid-run on a 4-way sharded
        partition replays from the log and finishes with EXACTLY the
        frames of a single-device run under the SAME seeded fault
        schedule (same-schedule control isolates the sharding variable;
        transient gateway request ids reset on ANY restart, sharded or
        not, so a no-crash oracle can never be byte-identical)."""
        frames_sharded = _chaos_run(str(tmp_path / "c"), 4, crash=True)
        frames_single = _chaos_run(str(tmp_path / "u"), 1, crash=True)
        assert len(frames_sharded) > 100
        assert frames_sharded == frames_single

    def test_fixed_seed_crash_stop_replays_identically_routed(
        self, tmp_path
    ):
        """Same chaos leg under resident routing: the crash drops the
        host residency dict with everything else; replay re-learns it
        (or falls back) and the frames stay byte-identical to the
        single-device run under the same seeded schedule."""
        frames_routed = _chaos_run(
            str(tmp_path / "r"), 4, crash=True, routing="resident"
        )
        frames_single = _chaos_run(str(tmp_path / "u"), 1, crash=True)
        assert len(frames_routed) > 100
        assert frames_routed == frames_single


@pytest.mark.slow
class TestShardedClusterFlap:
    """Cluster-level leader flap with a sharded span (slow tier with the
    other device-engine cluster suites)."""

    def test_leader_flap_releases_and_respans(self, tmp_path):
        import time

        from zeebe_tpu.gateway.cluster_client import ClusterClient
        from zeebe_tpu.models.bpmn.builder import Bpmn
        from zeebe_tpu.runtime.cluster_broker import ClusterBroker
        from zeebe_tpu.runtime.config import BrokerCfg
        from zeebe_tpu.runtime.engines import engine_factory_from_config

        cfg = BrokerCfg()
        cfg.network.client_port = 0
        cfg.network.management_port = 0
        cfg.network.subscription_port = 0
        cfg.metrics.port = 0
        cfg.metrics.enabled = False
        cfg.cluster.partitions = 1
        cfg.engine.type = "tpu"
        cfg.engine.capacity = 1 << 10
        cfg.mesh.sharded_partitions = 4
        broker = ClusterBroker(
            cfg, os.path.join(str(tmp_path), "b0"),
            engine_factory=engine_factory_from_config(cfg),
        )
        client = None
        try:
            broker.open_partition(0).join(60)
            broker.bootstrap_partition(0, {})
            deadline = time.monotonic() + 60
            while (
                time.monotonic() < deadline
                and not broker.partitions[0].is_leader
            ):
                time.sleep(0.02)
            assert broker.partitions[0].is_leader

            plan = broker.device_plan
            span = plan.device_indices(0)
            assert len(span) == 4
            engine = broker.partitions[0].engine
            assert engine.device_indices == span
            assert engine._mesh is not None

            client = ClusterClient(
                [broker.client_address], num_partitions=1,
                request_timeout_ms=120_000,
            )
            client.deploy_model(
                Bpmn.create_process("flap").start_event("s").end_event("e")
                .done()
            )
            assert client.create_instance(
                "flap", partition_id=0
            ).value.workflow_instance_key > 0
            # served over the sockets, the wave took the sharded program
            assert engine.sharded_waves > 0

            # leader flap: uninstall frees the WHOLE span, reinstall
            # re-spans and serving continues on the sharded engine
            server = broker.partitions[0]
            term = server.raft.term
            broker.actor.call(server._uninstall_leader).join(10)
            assert plan.device_indices(0) == []
            broker.actor.call(lambda: server._install_leader(term)).join(60)
            new_span = plan.device_indices(0)
            assert len(new_span) == 4
            assert broker.partitions[0].engine.device_indices == new_span
            assert client.create_instance(
                "flap", partition_id=0
            ).value.workflow_instance_key > 0
        finally:
            if client is not None:
                client.close()
            broker.close()
