"""Long-running delete-churn regression tests for the device engine.

A parallel fork-join workflow inserts AND deletes a join-map entry per
instance; sustained waves once filled the map with tombstones until
inserts silently failed (hashmap.insert claimed only EMPTY buckets),
arrivals were lost, and stuck instances eventually overflowed the table
— observed as a ~4% completion loss in bench config 3 at wave 11+.
Inserts now claim tombstones (standard open addressing) and the wave
rebuild compacts every map; this pins both.
"""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zeebe_tpu.testing import graphs
from zeebe_tpu.tpu import drive, hashmap, state as state_mod


class TestHashmapTombstoneReuse:
    def test_insert_claims_tombstones(self):
        t = hashmap.make(64)
        keys = jnp.arange(1, 33, dtype=jnp.int64)
        vals = jnp.arange(32, dtype=jnp.int32)
        ones = jnp.ones((32,), bool)
        # churn the same table far past its capacity in EMPTY buckets
        for gen in range(8):
            t, ok = hashmap.insert(t, keys + 100 * gen, vals, ones)
            assert bool(ok.all()), f"insert failed at generation {gen}"
            found, _ = hashmap.lookup(t, keys + 100 * gen, ones)
            assert bool(found.all())
            t = hashmap.delete(t, keys + 100 * gen, ones)

    def test_fill_counts_reflect_churn(self):
        t = hashmap.make(64)
        keys = jnp.arange(1, 17, dtype=jnp.int64)
        ones = jnp.ones((16,), bool)
        t, _ = hashmap.insert(t, keys, jnp.arange(16, dtype=jnp.int32), ones)
        t = hashmap.delete(t, keys[:8], ones[:8])
        live, dead = hashmap.fill_counts(t)
        assert int(live) == 8


class TestForkJoinChurn:
    @pytest.mark.slow
    def test_sustained_fork_join_waves_complete_exactly(self):
        """12 waves of parallel fork-join instances through the drive
        loop: every root must complete (bench config-3 regression)."""
        graph, meta = graphs.build_graph_forkjoin()
        num_vars = max(graph.num_vars, 8)
        graph = dc.replace(graph, num_vars=num_vars)
        wave = 1 << 7
        state = state_mod.make_state(
            capacity=4 * wave, num_vars=num_vars, job_capacity=4 * wave,
            join_capacity=wave, max_join_in=max(graph.max_join_in, 2),
            sub_capacity=8,
        )
        state = dc.replace(
            state,
            sub_key=state.sub_key.at[0].set(1),
            sub_type=state.sub_type.at[0].set(
                meta.interns.intern("payment-service")
            ),
            sub_worker=state.sub_worker.at[0].set(
                meta.interns.intern("bench-worker")
            ),
            sub_credits=state.sub_credits.at[0].set(np.int32(2**31 - 1)),
            sub_timeout=state.sub_timeout.at[0].set(300_000),
            sub_valid=state.sub_valid.at[0].set(True),
        )
        queue = drive.make_queue(4 * wave * max(2, graph.emit_width), num_vars)
        creates = graphs.stage_creates(meta, wave, num_vars, meta.interns)
        enqueue_jit = jax.jit(drive.enqueue, donate_argnums=(0,))
        rebuild_jit = jax.jit(
            state_mod.rebuild_lookup_state, donate_argnums=(0,)
        )
        completed = 0
        waves = 12
        for i in range(waves):
            queue = enqueue_jit(queue, creates)
            state, queue, tot = drive.run_to_quiescence(
                graph, state, queue, 0, wave, synthetic_workers=True,
                sync=True,
            )
            completed += tot["completed_roots"]
            if (i + 1) % 3 == 0:
                state = rebuild_jit(state)
            assert completed == (i + 1) * wave, (
                f"wave {i}: {completed} != {(i + 1) * wave} — "
                "fork-join instances lost to table churn"
            )
        assert int((np.asarray(state.ei_state) >= 0).sum()) == 0
