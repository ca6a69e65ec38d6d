"""Multi-device sharded engine tests (virtual 8-device CPU mesh).

Reference parity: partitions are the reference's horizontal shards — each
an independent ordered log + state machine, with hash-routed
cross-partition commands over the subscription transport
(``docs/src/basics/clustering.md``, ``SubscriptionCommandSender.java:96-108``,
``qa/integration-tests/.../clustering/ClusteringRule.java``). Here
partitions are mesh shards: the step kernel runs under ``shard_map``, the
subscription-transport hop is an ``all_to_all`` over the mesh axis, and
global control aggregates (quiescence, processed counts) are ``psum``s.

conftest.py forces JAX_PLATFORMS=cpu with 8 virtual devices.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from zeebe_tpu.engine import keyspace
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.models.transform.transformer import transform_model
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI
from zeebe_tpu.tpu import batch as rb
from zeebe_tpu.tpu import (
    drive, graph as graph_mod, hashmap, shard, state as state_mod,
)
from zeebe_tpu.tpu.conditions import VT_NUM

N_DEV = 8
CAP = 256
NUM_VARS = 8
BATCH = 64


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    if len(devices) < N_DEV:
        pytest.skip(f"need {N_DEV} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:N_DEV]), ("partitions",))


@pytest.fixture(scope="module")
def compiled():
    model = (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )
    workflows = transform_model(model)
    for wf in workflows:
        wf.key = 9
        wf.version = 1
    graph, meta = graph_mod.compile_graph(workflows)
    num_vars = max(graph.num_vars, NUM_VARS)
    graph = dataclasses.replace(graph, num_vars=num_vars)
    return graph, meta, num_vars


def _subscribed_state(num_partitions, meta, num_vars):
    """Partitioned state with one synthetic worker subscription per shard
    (the bench's instant worker, so instances run to completion)."""
    st = shard.make_partitioned_state(
        num_partitions, capacity=CAP, num_vars=num_vars, sub_capacity=8
    )
    type_id = meta.interns.intern("payment-service")
    worker_id = meta.interns.intern("w")
    return dataclasses.replace(
        st,
        sub_key=st.sub_key.at[:, 0].set(1),
        sub_type=st.sub_type.at[:, 0].set(type_id),
        sub_worker=st.sub_worker.at[:, 0].set(worker_id),
        sub_credits=st.sub_credits.at[:, 0].set(np.int32(2**30)),
        sub_timeout=st.sub_timeout.at[:, 0].set(300_000),
        sub_valid=st.sub_valid.at[:, 0].set(True),
    )


def _creates(meta, size, count, num_vars, value=99.0):
    b = rb.empty(size, num_vars)
    col = meta.varspace.column("orderValue")
    v_vt = np.zeros((size, num_vars), np.int8)
    v_num = np.zeros((size, num_vars), np.float32)
    v_vt[:count, col] = VT_NUM
    v_num[:count, col] = value
    return dataclasses.replace(
        b,
        valid=jnp.asarray(np.arange(size) < count),
        rtype=jnp.full((size,), int(RecordType.COMMAND), jnp.int32),
        vtype=jnp.full((size,), int(ValueType.WORKFLOW_INSTANCE), jnp.int32),
        intent=jnp.full((size,), int(WI.CREATE), jnp.int32),
        wf=jnp.zeros((size,), jnp.int32),
        v_vt=jnp.asarray(v_vt),
        v_num=jnp.asarray(v_num),
    )


def _stack(batches):
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *batches)


class TestPartitionedKeyspace:
    def test_key_bases_partition_disjoint(self, compiled):
        graph, meta, num_vars = compiled
        st = shard.make_partitioned_state(N_DEV, capacity=64, num_vars=num_vars)
        bases = np.asarray(st.next_wf_key)
        assert len(set(int(b) >> shard.PARTITION_KEY_SHIFT for b in bases)) == N_DEV
        job_bases = np.asarray(st.next_job_key)
        for p, base in enumerate(bases):
            assert int(base) >> shard.PARTITION_KEY_SHIFT == p
            # families stay stride-disjoint WITHIN a partition (keys are
            # partition-scoped — reference KeyGenerator.java:23)
            assert int(job_bases[p]) - int(base) == (
                keyspace.JOB_OFFSET - keyspace.WF_OFFSET
            )

    def test_allocated_keys_stay_disjoint_after_processing(self, mesh, compiled):
        graph, meta, num_vars = compiled
        state = _subscribed_state(N_DEV, meta, num_vars)
        queue = shard.make_partitioned_queue(N_DEV, 8 * BATCH, num_vars)
        creates = _stack([_creates(meta, BATCH, 16, num_vars) for _ in range(N_DEV)])
        enq = jax.jit(jax.vmap(drive.enqueue))
        queue = enq(queue, creates)
        run = shard.build_sharded_drive(mesh, BATCH, synthetic_workers=True)
        state, queue, totals = run(graph, state, queue, jnp.asarray(0, jnp.int64))
        keys = state_mod.host_i64(state.ei_i64, 0)  # [P, cap] allocated keys
        for p in range(N_DEV):
            used = keys[p][keys[p] >= 0]
            # every key this shard ever allocated carries its partition id
            nk = int(np.asarray(state.next_wf_key)[p])
            assert nk >> shard.PARTITION_KEY_SHIFT == p
            assert all(int(k) >> shard.PARTITION_KEY_SHIFT == p for k in used)


class TestExchange:
    def test_all_to_all_delivers_rows_with_payload(self, mesh, compiled):
        graph, meta, num_vars = compiled
        slots = 8
        sends = shard.make_exchange(N_DEV, slots=slots, num_vars=num_vars)
        # source p addresses destination q with a recognizable key p*100+q
        key_mat = np.full((N_DEV, N_DEV, slots), -1, np.int64)
        valid = np.zeros((N_DEV, N_DEV, slots), bool)
        num = np.zeros((N_DEV, N_DEV, slots, num_vars), np.float32)
        for p in range(N_DEV):
            for q in range(N_DEV):
                key_mat[p, q, 0] = p * 100 + q
                valid[p, q, 0] = True
                num[p, q, 0, 0] = float(p * 1000 + q)
        sends = dataclasses.replace(
            sends,
            key=jnp.asarray(key_mat),
            valid=jnp.asarray(valid),
            v_num=jnp.asarray(num),
        )
        state = _subscribed_state(N_DEV, meta, num_vars)
        batch = _stack([rb.empty(BATCH, num_vars) for _ in range(N_DEV)])
        step_fn, _ = shard.build_sharded_step(mesh)
        _, _, sends_in, _, _ = step_fn(
            graph, state, batch, sends, jnp.asarray(0, jnp.int64)
        )
        got = np.asarray(sends_in.key)  # [P(dest), P(src), slots]
        gnum = np.asarray(sends_in.v_num)
        for q in range(N_DEV):
            for p in range(N_DEV):
                assert got[q, p, 0] == p * 100 + q, (q, p, got[q, p, 0])
                assert gnum[q, p, 0, 0] == float(p * 1000 + q)

    def test_exchange_output_compacts_for_enqueue(self, compiled):
        graph, meta, num_vars = compiled
        # interleaved valid rows (what all_to_all delivers, grouped by
        # source shard) must compact into a contiguous prefix, preserving
        # relative order — drive.enqueue's precondition
        b = rb.empty(16, num_vars)
        valid = np.zeros(16, bool)
        valid[[1, 5, 6, 11]] = True
        keys = np.full(16, -1, np.int64)
        keys[[1, 5, 6, 11]] = [10, 20, 30, 40]
        b = dataclasses.replace(
            b, valid=jnp.asarray(valid), key=jnp.asarray(keys)
        )
        c = rb.compact(b)
        assert np.asarray(c.valid)[:4].all() and not np.asarray(c.valid)[4:].any()
        assert list(np.asarray(c.key)[:4]) == [10, 20, 30, 40]


class TestShardedDrive:
    def test_all_partitions_drive_to_quiescence(self, mesh, compiled):
        graph, meta, num_vars = compiled
        state = _subscribed_state(N_DEV, meta, num_vars)
        queue = shard.make_partitioned_queue(N_DEV, 8 * BATCH, num_vars)
        per_part = [4, 8, 12, 16, 2, 6, 10, 14]  # uneven load per shard
        creates = _stack(
            [_creates(meta, BATCH, n, num_vars) for n in per_part]
        )
        queue = jax.jit(jax.vmap(drive.enqueue))(queue, creates)
        run = shard.build_sharded_drive(mesh, BATCH, synthetic_workers=True)
        state, queue, totals = run(graph, state, queue, jnp.asarray(0, jnp.int64))
        t = jax.device_get(totals)
        assert not t["overflow"].any()
        assert list(t["completed_roots"]) == per_part
        assert np.asarray(queue.count).sum() == 0
        # uneven shards quiesce together (lockstep rounds)
        assert len(set(int(r) for r in t["rounds"])) == 1

    def test_multi_wave_sharded_drive(self, mesh, compiled):
        graph, meta, num_vars = compiled
        state = _subscribed_state(N_DEV, meta, num_vars)
        queue = shard.make_partitioned_queue(N_DEV, 8 * BATCH, num_vars)
        run = shard.build_sharded_drive(mesh, BATCH, synthetic_workers=True)
        enq = jax.jit(jax.vmap(drive.enqueue))
        waves = 3
        completed = np.zeros(N_DEV, np.int64)
        for _ in range(waves):
            creates = _stack(
                [_creates(meta, BATCH, 8, num_vars) for _ in range(N_DEV)]
            )
            queue = enq(queue, creates)
            state, queue, totals = run(
                graph, state, queue, jnp.asarray(0, jnp.int64)
            )
            t = jax.device_get(totals)
            assert not t["overflow"].any()
            completed += np.asarray(t["completed_roots"])
        assert list(completed) == [8 * waves] * N_DEV
        # instances completed → element-instance tables fully freed
        assert (np.asarray(state.ei_i32[:, :, 1]) == -1).all()

    def test_sharded_matches_independent_partitions(self, mesh, compiled):
        """Record-level parity: the 8-partition sharded drive leaves every
        shard in EXACTLY the state an independent single-partition run with
        the same commands produces (partitions are independent ordered
        logs — the sharding must be semantically invisible)."""
        graph, meta, num_vars = compiled
        state = _subscribed_state(N_DEV, meta, num_vars)
        queue = shard.make_partitioned_queue(N_DEV, 8 * BATCH, num_vars)
        per_part = [3, 7, 1, 9, 5, 0, 8, 4]
        creates_list = [
            _creates(meta, BATCH, n, num_vars, value=float(10 + n))
            for n in per_part
        ]
        queue = jax.jit(jax.vmap(drive.enqueue))(queue, _stack(creates_list))
        run = shard.build_sharded_drive(mesh, BATCH, synthetic_workers=True)
        state, queue, totals = run(graph, state, queue, jnp.asarray(0, jnp.int64))

        for p in range(N_DEV):
            # independent single-partition reference run, same key base
            ref = state_mod.make_state(
                capacity=CAP, num_vars=num_vars, sub_capacity=8
            )
            base = jnp.int64(p) << shard.PARTITION_KEY_SHIFT
            ref = dataclasses.replace(
                ref,
                next_wf_key=base + keyspace.WF_OFFSET,
                next_job_key=base + keyspace.JOB_OFFSET,
                sub_key=ref.sub_key.at[0].set(1),
                sub_type=ref.sub_type.at[0].set(
                    meta.interns.intern("payment-service")
                ),
                sub_worker=ref.sub_worker.at[0].set(meta.interns.intern("w")),
                sub_credits=ref.sub_credits.at[0].set(np.int32(2**30)),
                sub_timeout=ref.sub_timeout.at[0].set(300_000),
                sub_valid=ref.sub_valid.at[0].set(True),
            )
            rqueue = drive.make_queue(8 * BATCH, num_vars)
            rqueue = drive.enqueue(rqueue, creates_list[p])
            ref, rqueue, rtot = drive.run_to_quiescence(
                graph, ref, rqueue, 0, BATCH, synthetic_workers=True
            )
            assert rtot["completed_roots"] == per_part[p]
            sharded_shard = jax.tree.map(lambda a: a[p], state)
            for f in dataclasses.fields(ref):
                a = getattr(ref, f.name)
                b = getattr(sharded_shard, f.name)
                if isinstance(a, hashmap.HashTable):
                    for w in ("keys_lo", "keys_hi"):
                        np.testing.assert_array_equal(
                            np.asarray(getattr(a, w)),
                            np.asarray(getattr(b, w)),
                            err_msg=f"{f.name}.{w} partition {p}",
                        )
                else:
                    np.testing.assert_array_equal(
                        np.asarray(a), np.asarray(b),
                        err_msg=f"{f.name} partition {p}",
                    )

    def test_cross_partition_commands_via_exchange(self, mesh, compiled):
        """Hash-routed command distribution: partition 0 addresses CREATE
        commands to every partition through the all_to_all exchange (the
        SubscriptionCommandSender hop over ICI); each destination then
        drives its inbound commands to completion."""
        graph, meta, num_vars = compiled
        slots = 8
        sends = shard.make_exchange(N_DEV, slots=slots, num_vars=num_vars)
        # partition 0 sends 2 CREATEs to every destination
        v = jax.tree.map(lambda a: np.asarray(a).copy(), sends)
        col = meta.varspace.column("orderValue")
        for q in range(N_DEV):
            for s in (0, 1):
                v.valid[0, q, s] = True
                v.rtype[0, q, s] = int(RecordType.COMMAND)
                v.vtype[0, q, s] = int(ValueType.WORKFLOW_INSTANCE)
                v.intent[0, q, s] = int(WI.CREATE)
                v.wf[0, q, s] = 0
                v.v_vt[0, q, s, col] = VT_NUM
                v.v_num[0, q, s, col] = 50.0
        sends = jax.tree.map(jnp.asarray, v)
        state = _subscribed_state(N_DEV, meta, num_vars)
        batch = _stack([rb.empty(BATCH, num_vars) for _ in range(N_DEV)])
        step_fn, _ = shard.build_sharded_step(mesh)
        state, _out, sends_in, _, _ = step_fn(
            graph, state, batch, sends, jnp.asarray(0, jnp.int64)
        )
        # deliver each shard its inbound rows: flatten [P(src), S] → rows,
        # compact to a prefix, enqueue, drive
        queue = shard.make_partitioned_queue(N_DEV, 8 * BATCH, num_vars)
        inbound = jax.tree.map(
            lambda a: a.reshape((N_DEV, -1) + a.shape[3:]), sends_in
        )
        inbound = jax.jit(jax.vmap(rb.compact))(inbound)
        queue = jax.jit(jax.vmap(drive.enqueue))(queue, inbound)
        run = shard.build_sharded_drive(mesh, BATCH, synthetic_workers=True)
        state, queue, totals = run(graph, state, queue, jnp.asarray(0, jnp.int64))
        t = jax.device_get(totals)
        assert list(t["completed_roots"]) == [2] * N_DEV

    def test_overflow_anywhere_aborts_everywhere(self, mesh, compiled):
        graph, meta, num_vars = compiled
        state = _subscribed_state(N_DEV, meta, num_vars)
        # partition 3 gets more instances than its element-instance table
        # can hold → its overflow must stop the whole mesh (lockstep abort)
        tiny = shard.make_partitioned_state(
            N_DEV, capacity=16, num_vars=num_vars, sub_capacity=8
        )
        tiny = dataclasses.replace(
            tiny,
            sub_key=state.sub_key, sub_type=state.sub_type,
            sub_worker=state.sub_worker, sub_credits=state.sub_credits,
            sub_timeout=state.sub_timeout, sub_valid=state.sub_valid,
        )
        queue = shard.make_partitioned_queue(N_DEV, 8 * BATCH, num_vars)
        counts = [1, 1, 1, 60, 1, 1, 1, 1]  # 60 > capacity 16
        creates = _stack([_creates(meta, BATCH, n, num_vars) for n in counts])
        queue = jax.jit(jax.vmap(drive.enqueue))(queue, creates)
        run = shard.build_sharded_drive(mesh, BATCH, synthetic_workers=True)
        _, _, totals = run(graph, tiny, queue, jnp.asarray(0, jnp.int64))
        t = jax.device_get(totals)
        assert t["overflow"].all(), "overflow must propagate to all shards"


class TestShardedMessageCorrelation:
    """Round 4: cross-partition message correlation rides the drive loop's
    all_to_all exchange — OPEN routes to the correlation-key's hash
    partition, CORRELATE back to the instance's partition, CLOSE to the
    message partition (reference SubscriptionCommandSender.java:96-108 as
    a mesh collective). Single-partition bit-for-bit parity with the
    oracle is pinned in test_tpu_parity; here the MESH semantics are
    validated: every instance completes, stores drain, no overflow."""

    @pytest.fixture(scope="class")
    def msg_compiled(self):
        model = (
            Bpmn.create_process("msgflow")
            .start_event("start")
            .receive_task("wait", message_name="paid", correlation_key="$.oid")
            .end_event("done")
            .done()
        )
        workflows = transform_model(model)
        for wf in workflows:
            wf.key = 9
            wf.version = 1
        graph, meta = graph_mod.compile_graph(workflows)
        num_vars = max(graph.num_vars, NUM_VARS)
        graph = dataclasses.replace(graph, num_vars=num_vars)
        return graph, meta, num_vars

    def _route_of(self, meta, corr: str) -> int:
        """Host mirror of shard.correlation_route's hash for staging
        publishes at their owner partition."""
        from zeebe_tpu.tpu.conditions import VT_STR

        name_id = meta.interns.intern("paid")
        sid = meta.interns.intern(corr)
        ckey = (name_id << 35) | (int(VT_STR) << 32) | (sid & 0xFFFFFFFF)
        h = ((ckey * -7046029254386353131) & (2**64 - 1)) % 2**64
        h = ((h >> 33) & 0x7FFFFFFF)
        return int(h % N_DEV)

    def _creates_msg(self, meta, size, oids, num_vars):
        from zeebe_tpu.tpu.conditions import VT_STR

        b = rb.empty(size, num_vars)
        col = meta.varspace.column("oid")
        v_vt = np.zeros((size, num_vars), np.int8)
        v_str = np.zeros((size, num_vars), np.int32)
        for i, oid in enumerate(oids):
            v_vt[i, col] = VT_STR
            v_str[i, col] = meta.interns.intern(oid)
        return dataclasses.replace(
            b,
            valid=jnp.asarray(np.arange(size) < len(oids)),
            rtype=jnp.full((size,), int(RecordType.COMMAND), jnp.int32),
            vtype=jnp.full((size,), int(ValueType.WORKFLOW_INSTANCE), jnp.int32),
            intent=jnp.full((size,), int(WI.CREATE), jnp.int32),
            wf=jnp.zeros((size,), jnp.int32),
            v_vt=jnp.asarray(v_vt),
            v_str=jnp.asarray(v_str),
        )

    def _publishes(self, meta, size, corrs, num_vars):
        from zeebe_tpu.protocol.intents import MessageIntent as MI
        from zeebe_tpu.tpu.conditions import VT_BOOL, VT_STR

        b = rb.empty(size, num_vars)
        paid_col = meta.varspace.column("paid")
        v_vt = np.zeros((size, num_vars), np.int8)
        v_num = np.zeros((size, num_vars), np.float32)
        type_id = np.zeros((size,), np.int32)
        retries = np.zeros((size,), np.int32)
        worker = np.zeros((size,), np.int32)
        for i, corr in enumerate(corrs):
            v_vt[i, paid_col] = VT_BOOL
            v_num[i, paid_col] = 1.0
            type_id[i] = meta.interns.intern("paid")
            retries[i] = int(VT_STR)
            worker[i] = meta.interns.intern(corr)
        return dataclasses.replace(
            b,
            valid=jnp.asarray(np.arange(size) < len(corrs)),
            rtype=jnp.full((size,), int(RecordType.COMMAND), jnp.int32),
            vtype=jnp.full((size,), int(ValueType.MESSAGE), jnp.int32),
            intent=jnp.full((size,), int(MI.PUBLISH), jnp.int32),
            v_vt=jnp.asarray(v_vt),
            v_num=jnp.asarray(v_num),
            type_id=jnp.asarray(type_id),
            retries=jnp.asarray(retries),
            worker=jnp.asarray(worker),
        )

    def test_cross_partition_correlation_completes_all(self, mesh, msg_compiled):
        graph, meta, num_vars = msg_compiled
        assert graph.has_messages
        st = shard.make_partitioned_state(
            N_DEV, capacity=CAP, num_vars=num_vars
        )
        # headroom: batch*emit_width local + nparts*exchange_slots arrivals
        # per round (see build_sharded_drive queue-sizing note)
        queue = shard.make_partitioned_queue(N_DEV, 32 * BATCH, num_vars)
        run = shard.build_sharded_drive(mesh, BATCH, exchange_slots=BATCH)

        # 3 instances per partition, each with a distinct correlation key
        n_per = 3
        oid_by_part = {
            p: [f"o-{p}-{i}" for i in range(n_per)] for p in range(N_DEV)
        }
        create_batches = [
            self._creates_msg(meta, BATCH, oid_by_part[p], num_vars)
            for p in range(N_DEV)
        ]
        queue = jax.jit(
            lambda q, b: jax.vmap(drive.enqueue)(q, b)
        )(queue, _stack(create_batches))
        st, queue, totals = run(graph, st, queue, jnp.int64(1_000))
        assert not bool(np.asarray(totals["overflow"]).any())
        # every instance waits at its receive task; subs live on their
        # hash partitions
        assert int(np.asarray(totals["completed_roots"]).sum()) == 0
        live_subs = int((state_mod.host_i64(st.msub_ckey, 0) >= 0).sum())
        assert live_subs == N_DEV * n_per

        # publish each key AT its owner partition (hash-consistent staging,
        # exactly how the gateway routes publishes by correlation key)
        pubs_by_part = {p: [] for p in range(N_DEV)}
        for p in range(N_DEV):
            for oid in oid_by_part[p]:
                pubs_by_part[self._route_of(meta, oid)].append(oid)
        assert len({p for p, v in pubs_by_part.items() if v}) > 1, (
            "test needs keys hashing to multiple partitions"
        )
        pub_batches = [
            self._publishes(meta, BATCH, pubs_by_part[p], num_vars)
            for p in range(N_DEV)
        ]
        queue = jax.jit(
            lambda q, b: jax.vmap(drive.enqueue)(q, b)
        )(queue, _stack(pub_batches))
        st, queue, totals = run(graph, st, queue, jnp.int64(2_000))
        assert not bool(np.asarray(totals["overflow"]).any())
        # every instance correlated and completed; stores drained
        assert int(np.asarray(totals["completed_roots"]).sum()) == N_DEV * n_per
        assert int((state_mod.host_i64(st.msub_ckey, 0) >= 0).sum()) == 0
        assert int((state_mod.host_i64(st.msg_key, 0) >= 0).sum()) == 0
