"""Device job-backlog serving path: typed probe, persisted round-robin
cursor, and the in-process broker's gated device pull
(zeebe_tpu/tpu/engine.py, zeebe_tpu/runtime/broker.py).
"""

import dataclasses

import numpy as np

import jax.numpy as jnp

from zeebe_tpu import tpu as _tpu  # noqa: F401  (enables x64)
from zeebe_tpu.protocol.intents import JobIntent as JI
from zeebe_tpu.runtime import Broker
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
    job_i64 = np.asarray(s.job_i64).copy()
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
        job_i32=jnp.asarray(job_i32), job_i64=jnp.asarray(job_i64),
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
        eng._assigning.clear()  # as if its wave were collected, rejected
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
