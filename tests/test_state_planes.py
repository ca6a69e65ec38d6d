"""The state's 64-bit columns as 32-bit planes (ISSUE 30).

A TPU has no 64-bit integers, so no table-sized leaf of ``EngineState`` is
``int64``: tables of 64-bit columns are ``[rows, 2C] int32`` (low word,
high word per column), hash-map keys two ``[T] int32`` leaves. These
tests hold the layout itself: the hash map on planes against a plain
dict, the host helpers and the wave-sized conversions as inverses, the
32-bit table scans against their int64 expressions, and the snapshot's
format on disk, which the layout must not change.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from zeebe_tpu import tpu as _tpu  # noqa: F401  (enables x64)
from zeebe_tpu.gateway import JobWorker, ZeebeClient
from zeebe_tpu.log import stateser
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI
from zeebe_tpu.runtime import ControlledClock
from zeebe_tpu.tpu import hashmap, pallas_ops as pops, state as state_mod

# keys that try the planes: small, above 2^32, and with a low word that
# reads as a sentinel's (-1 = EMPTY's, -2 = TOMBSTONE's) under a real,
# non-negative high word
LOW_MINUS_1 = 0x0000_0000_FFFF_FFFF
LOW_MINUS_2 = 0x0000_0000_FFFF_FFFE
HARD_KEYS = [
    0, 1, 6, 2**31 - 1, 2**31, LOW_MINUS_2, LOW_MINUS_1, 2**32, 2**32 + 1,
    (5 << 32) | 0xFFFF_FFFF, (5 << 32) | 0xFFFF_FFFE, (7 << 32) | 6,
    2**40 + 11, 2**62 - 1, 2**63 - 1,
]


def _words(tb):
    return np.asarray(tb.keys_lo), np.asarray(tb.keys_hi)


def _stored(tb):
    """{key: val} of a table's live buckets, by plain arithmetic."""
    lo, hi = _words(tb)
    live = hi >= 0
    keys = (hi[live].astype(np.int64) << 32) | (lo[live].astype(np.int64) & 0xFFFF_FFFF)
    return dict(zip(keys.tolist(), np.asarray(tb.vals)[live].tolist()))


def _as_query(keys, form):
    keys = np.asarray(keys, np.int64)
    if form == "int64":
        return jnp.asarray(keys)
    return jnp.asarray(state_mod.host_planes(keys, column=True))  # [B, 2]


FORMS = ["int64", "planes"]
ARMS = {"xla": hashmap, "dispatch": pops}  # off-TPU pops falls back to XLA


class TestHashMapOnPlanes:
    def test_empty_table_is_all_sentinels(self):
        tb = hashmap.make(64)
        lo, hi = _words(tb)
        assert lo.dtype == hi.dtype == np.int32
        assert (lo == -1).all() and (hi == -1).all()  # EMPTY = (-1, -1)
        assert [int(x) for x in hashmap.fill_counts(tb)] == [0, 0]

    @pytest.mark.parametrize("form", FORMS)
    @pytest.mark.parametrize("arm", sorted(ARMS))
    def test_insert_then_lookup_hard_keys(self, arm, form):
        ops = ARMS[arm]
        n = len(HARD_KEYS)
        q = _as_query(HARD_KEYS, form)
        tb, ok = ops.insert(
            hashmap.make(64), q, jnp.arange(n, dtype=jnp.int32) + 100,
            jnp.ones((n,), bool),
        )
        assert np.asarray(ok).all()
        assert _stored(tb) == {k: 100 + i for i, k in enumerate(HARD_KEYS)}
        found, vals = ops.lookup(tb, q, jnp.ones((n,), bool))
        assert np.asarray(found).all()
        assert np.asarray(vals).tolist() == [100 + i for i in range(n)]
        # neighbours that share a word with a stored key are not found
        near = [k ^ (1 << 32) for k in HARD_KEYS if k ^ (1 << 32) not in HARD_KEYS]
        near = [k for k in near if 0 <= k < 2**63]
        f2, v2 = ops.lookup(
            tb, _as_query(near, form), jnp.ones((len(near),), bool)
        )
        assert not np.asarray(f2).any() and (np.asarray(v2) == -1).all()

    @pytest.mark.parametrize("key", [LOW_MINUS_1, LOW_MINUS_2])
    def test_a_key_whose_low_word_is_a_sentinels_is_a_key(self, key):
        """Stored, it neither ends a probe chain (EMPTY) nor offers its
        bucket to the next insert (TOMBSTONE / EMPTY)."""
        one = jnp.ones((1,), bool)
        tb, _ = hashmap.insert(
            hashmap.make(8), jnp.asarray([key], jnp.int64),
            jnp.asarray([7], jnp.int32), one,
        )
        assert [int(x) for x in hashmap.fill_counts(tb)] == [1, 0]
        # fill every other bucket: nothing may claim the stored key's
        others = [key + 8 * (i + 1) for i in range(7)]
        tb, ok = hashmap.insert(
            tb, jnp.asarray(others, jnp.int64),
            jnp.arange(7, dtype=jnp.int32), jnp.ones((7,), bool),
        )
        assert np.asarray(ok).all()
        assert _stored(tb)[key] == 7 and len(_stored(tb)) == 8
        found, vals = hashmap.lookup(tb, jnp.asarray([key], jnp.int64), one)
        assert bool(found[0]) and int(vals[0]) == 7

    @pytest.mark.parametrize("arm", sorted(ARMS))
    def test_delete_leaves_a_tombstone_the_chain_survives(self, arm):
        ops = ARMS[arm]
        # 4 keys with one hash chain: same words but for the bucket bits
        size = 16
        keys, h0 = [], None
        k = 2**33 + 5
        while len(keys) < 4:
            lo, hi = hashmap.split_keys(jnp.asarray([k], jnp.int64))
            h = int(hashmap._hash(lo, hi, size)[0])
            h0 = h if h0 is None else h0
            if h == h0:
                keys.append(k)
            k += 5
        q = jnp.asarray(keys, jnp.int64)
        four = jnp.ones((4,), bool)
        tb, _ = ops.insert(
            hashmap.make(size), q, jnp.arange(4, dtype=jnp.int32), four
        )
        tb = ops.delete(tb, q, jnp.asarray([False, True, False, False]))
        lo, hi = _words(tb)
        dead = (lo == -2) & (hi == -1)  # TOMBSTONE = (-2, -1)
        assert dead.sum() == 1
        assert [int(x) for x in hashmap.fill_counts(tb)] == [3, 1]
        found, vals = ops.lookup(tb, q, four)
        assert np.asarray(found).tolist() == [True, False, True, True]
        assert np.asarray(vals)[[0, 2, 3]].tolist() == [0, 2, 3]
        # the next insert on that chain takes the tombstone's bucket
        tb2, ok = ops.insert(
            tb, jnp.asarray([keys[1]], jnp.int64),
            jnp.asarray([9], jnp.int32), jnp.ones((1,), bool),
        )
        assert bool(ok[0])
        assert [int(x) for x in hashmap.fill_counts(tb2)] == [4, 0]
        assert _stored(tb2)[keys[1]] == 9

    def test_random_churn_matches_a_dict(self):
        rng = np.random.default_rng(30)
        tb, ref = hashmap.make(256), {}
        pool = rng.choice(
            np.concatenate([
                np.arange(1, 400, 5, dtype=np.int64),
                (np.arange(1, 400, 5, dtype=np.int64) << 32) | 0xFFFF_FFFE,
                (np.arange(1, 400, 5, dtype=np.int64) << 31),
            ]),
            96, replace=False,
        )
        for step in range(6):
            ins = rng.choice(pool, 24, replace=False)
            ins = np.asarray([k for k in ins if int(k) not in ref], np.int64)
            vals = rng.integers(0, 1000, len(ins)).astype(np.int32)
            tb, ok = hashmap.insert(
                tb, jnp.asarray(ins), jnp.asarray(vals),
                jnp.ones((len(ins),), bool),
            )
            assert np.asarray(ok).all()
            ref.update(zip(ins.tolist(), vals.tolist()))
            gone = np.asarray(
                rng.choice(sorted(ref), min(10, len(ref)), replace=False)
            )
            tb = hashmap.delete(
                tb, jnp.asarray(gone), jnp.ones((len(gone),), bool)
            )
            for k in gone.tolist():
                del ref[k]
            assert _stored(tb) == ref
            found, vals = hashmap.lookup(
                tb, jnp.asarray(pool), jnp.ones((len(pool),), bool)
            )
            for k, f, v in zip(pool.tolist(), np.asarray(found), np.asarray(vals)):
                assert (bool(f), int(v)) == ((True, ref[k]) if k in ref else (False, -1))

    def test_rebuild_takes_a_tables_own_key_planes(self):
        keys = np.asarray(HARD_KEYS[:12], np.int64)
        planes = jnp.asarray(state_mod.host_planes(keys, column=True))
        valid = jnp.asarray([True] * 10 + [False] * 2)
        tb, all_ok = hashmap.rebuild_from(
            64, planes, jnp.arange(12, dtype=jnp.int32), valid
        )
        assert bool(all_ok)
        assert _stored(tb) == {int(k): i for i, k in enumerate(keys[:10])}


class TestPlaneHelpers:
    @pytest.mark.parametrize("cols", [1, 3, 4])
    def test_planes_to_i64_to_planes_of_a_wave_sized_gather(self, cols):
        """The step's read: plane rows gathered, int64 made of the [B, C]
        result; its write: int64 rows back to planes. Identity both ways."""
        rng = np.random.default_rng(cols)
        table64 = rng.integers(-(2**62), 2**62, (64, cols), dtype=np.int64)
        table64[:3] = np.asarray(HARD_KEYS[-3 * cols :], np.int64).reshape(3, cols)
        planes = jnp.asarray(state_mod.host_planes(table64))
        assert planes.shape == (64, 2 * cols) and planes.dtype == jnp.int32
        slots = jnp.asarray(rng.integers(0, 64, 16), jnp.int32)
        (rows,) = pops.fused_gather_rows([planes], [pops.GatherOp(0, slots)])
        got64 = pops.planes_to_i64(rows)
        assert got64.dtype == jnp.int64
        np.testing.assert_array_equal(got64, table64[np.asarray(slots)])
        np.testing.assert_array_equal(pops.i64_to_planes(got64), rows)

    def test_host_views_are_inverses_and_match_the_device_bitcast(self):
        vals = np.asarray(HARD_KEYS + [-1, -2], np.int64)
        col = state_mod.host_planes(vals, column=True)
        assert col.shape == (len(vals), 2) and col.dtype == np.int32
        np.testing.assert_array_equal(state_mod.host_i64(col, 0), vals)
        np.testing.assert_array_equal(
            col, np.asarray(pops.vec64_to_planes(jnp.asarray(vals)))
        )
        table = vals[:16].reshape(4, 4)
        planes = state_mod.host_planes(table)
        np.testing.assert_array_equal(state_mod.host_i64(planes), table)
        np.testing.assert_array_equal(state_mod.host_i64(planes, 2), table[:, 2])
        tb = hashmap.from_host(vals, np.arange(len(vals), dtype=np.int32))
        np.testing.assert_array_equal(hashmap.host_keys(tb), vals)
        lo, hi = _words(tb)
        assert (lo[-2], hi[-2]) == (-1, -1) and (lo[-1], hi[-1]) == (-2, -1)

    def test_table_scans_on_words_match_their_int64_expressions(self):
        rng = np.random.default_rng(5)
        vals = np.concatenate([
            np.asarray(HARD_KEYS + [-1], np.int64),
            rng.integers(0, 2**62, 48, dtype=np.int64),
        ])
        planes = jnp.asarray(state_mod.host_planes(vals, column=True))
        np.testing.assert_array_equal(state_mod.col_neg(planes), vals < 0)
        for x in [0, 5, 2**31, LOW_MINUS_1, 2**32, 2**40, 2**62]:
            np.testing.assert_array_equal(
                state_mod.col_le(planes, 0, jnp.asarray(x, jnp.int64)),
                vals <= x, err_msg=str(x),
            )
        probe = jnp.asarray(vals[:8])
        np.testing.assert_array_equal(
            state_mod.col_eq(planes, 0, probe),
            vals[None, :] == vals[:8, None],
        )
        live = vals[vals >= 0]
        for icap in (8, 1 << 13, 1 << 23):
            np.testing.assert_array_equal(
                state_mod.index_bucket(
                    jnp.asarray(state_mod.host_planes(live, column=True)), 0, icap
                ),
                (live // 5) & (icap - 1), err_msg=str(icap),
            )

    def test_no_table_leaf_of_the_state_is_int64(self):
        state = jax.eval_shape(lambda: state_mod.make_state(capacity=256))
        wide = {
            jax.tree_util.keystr(path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]
            if leaf.dtype in (jnp.int64, jnp.uint64)
        }
        # the scalar counters and the worker-subscription table: nothing
        # that grows with the capacity
        assert set(wide) == {
            ".free_ei_pop", ".free_ei_push", ".free_job_pop", ".free_job_push",
            ".next_wf_key", ".next_job_key", ".sub_key", ".sub_timeout",
        }, wide
        assert all(int(np.prod(s)) <= 64 for s in wide.values())
        for name in state_mod.I64_TABLES + state_mod.I64_COLUMNS:
            leaf = getattr(state, name)
            assert leaf.dtype == jnp.int32 and leaf.ndim == 2
            assert leaf.shape[1] % 2 == 0


# ---------------------------------------------------------------------------
# the snapshot on disk holds int64, as before the planes
# ---------------------------------------------------------------------------

# what the parent commit's snapshot_state() wrote for the 64-bit leaves:
# name -> columns (None = a [rows] column)
PARENT_I64 = {
    "ei_i64": 3, "job_i64": 4, "msub_i64": 2,
    "join_key": None, "timer_key": None, "timer_due": None,
    "timer_aik": None, "timer_instance_key": None, "msub_ckey": None,
    "msg_key": None, "msg_ckey": None, "msg_deadline": None,
}
PARENT_MAPS = ("ei_map", "job_map", "join_map", "timer_map", "msub_map", "msg_map")


def _join(lo, hi):
    return (np.asarray(hi).astype(np.int64) << 32) | (
        np.asarray(lo).astype(np.int64) & 0xFFFF_FFFF
    )


def _parent_arrays(state):
    """The ``arrays`` of a device snapshot as the parent commit built them:
    one ``np.asarray(leaf)`` per field, its 64-bit leaves int64 — made
    here from the plane leaves by plain arithmetic, not by the engine."""
    arrays = {}
    for f in dataclasses.fields(state):
        v = getattr(state, f.name)
        if f.name in PARENT_MAPS:
            arrays[f.name + ".keys"] = _join(v.keys_lo, v.keys_hi)
            arrays[f.name + ".vals"] = np.asarray(v.vals)
        elif f.name in PARENT_I64:
            p = np.asarray(v)
            a = _join(p[:, 0::2], p[:, 1::2])
            arrays[f.name] = a[:, 0] if PARENT_I64[f.name] is None else a
        else:
            arrays[f.name] = np.asarray(v)
    return arrays


def _model():
    return (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )


def _wi_events(broker):
    return [
        (WI(r.metadata.intent).name, r.value.activity_id, r.key)
        for r in broker.records(0)
        if r.metadata.value_type == ValueType.WORKFLOW_INSTANCE
        and r.metadata.record_type == RecordType.EVENT
    ]


class TestSnapshotFormatUnchanged:
    def _waiting_broker(self, data):
        from tests.conftest import make_tpu_broker

        broker = make_tpu_broker(
            data_dir=data, clock=ControlledClock(start_ms=1_000_000)
        )
        client = ZeebeClient(broker)
        client.deploy_model(_model())
        for i in range(3):
            client.create_instance("order-process", payload={"orderId": i})
        broker.run_until_idle()  # three jobs created, no worker yet
        return broker

    def test_snapshot_writes_the_parents_int64_arrays(self, tmp_path):
        broker = self._waiting_broker(str(tmp_path / "a"))
        engine = broker.partitions[0].engine
        arrays = engine.snapshot_state()["arrays"]
        want = _parent_arrays(engine.state)
        assert sorted(arrays) == sorted(want)
        n = engine.state.capacity
        for name, cols in PARENT_I64.items():
            assert arrays[name].dtype == np.int64, name
            assert arrays[name].ndim == (1 if cols is None else 2), name
            if cols is not None:
                assert arrays[name].shape[1] == cols, name
        assert arrays["ei_i64"].shape == (n, 3)
        for m in PARENT_MAPS:
            assert arrays[m + ".keys"].dtype == np.int64
            assert arrays[m + ".keys"].ndim == 1
        for name in want:
            assert arrays[name].dtype == want[name].dtype, name
            np.testing.assert_array_equal(arrays[name], want[name], err_msg=name)
        assert (arrays["ei_i64"][:, 0] >= 0).sum() >= 3  # live rows went in
        broker.close()

    def test_a_parent_snapshot_restores_into_planes_and_serves(self, tmp_path):
        from tests.conftest import make_tpu_broker

        # the run that never restarts: what the log must read
        straight = self._waiting_broker(str(tmp_path / "straight"))
        JobWorker(straight, "payment-service", lambda ctx: {"paid": True})
        straight.run_until_idle()
        want_events = _wi_events(straight)
        assert sum(e[:2] == ("ELEMENT_COMPLETED", "order-process") for e in want_events) == 3
        straight.close()

        broker = self._waiting_broker(str(tmp_path / "a"))
        engine = broker.partitions[0].engine
        snap = engine.snapshot_state()
        # the bytes a parent broker wrote: its int64 arrays through the codec
        parent_doc = dict(snap, arrays=_parent_arrays(engine.state))
        payload = stateser.encode_state(parent_doc)
        assert payload == stateser.encode_state(snap)  # and back: byte-identical
        n_records = len(list(broker.records(0)))
        broker.close()

        restored = make_tpu_broker(
            data_dir=str(tmp_path / "a"), clock=ControlledClock(start_ms=1_000_000)
        )
        engine2 = restored.partitions[0].engine
        engine2.restore_state(stateser.decode_state(payload))
        for name in state_mod.I64_TABLES + state_mod.I64_COLUMNS:
            assert getattr(engine2.state, name).dtype == jnp.int32, name
        assert engine2.state.ei_i64.shape[1] == 6
        assert engine2.state.ei_map.keys_hi.dtype == jnp.int32
        assert len(list(restored.records(0))) == n_records
        worker = JobWorker(restored, "payment-service", lambda ctx: {"paid": True})
        restored.run_until_idle()
        assert len(worker.handled) == 3
        assert _wi_events(restored) == want_events
        restored.close()
