"""Compile the served programs for the chip, without the chip.

The TPU's compiler is installed beside the CPU backend and compiles for a
device that is described, not attached (``get_topology_desc``). These
cases compile what the broker serves with at deployment size — capacity
2^20, wave 512, 16 payload variables — for one described v5e, and the
pallas kernels at the largest tables the size rule admits. Nothing runs:
a compile that passes says the program fits and lowers, not that it is
right or fast (``chip_smoke.py`` runs it on a chip).

This is the only file that describes the chip. The description happens in
a module-scoped fixture, never at import: only one process may load the
TPU's library, and under pytest-xdist every worker imports every file.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from zeebe_tpu import tpu as _tpu  # noqa: F401  (enables x64)
from zeebe_tpu.tpu import (
    batch as rb,
    engine as engine_mod,
    hashmap,
    kernel,
    pallas_ops as pops,
    shard,
    state as state_mod,
)

CAPACITY = 1 << 20
WAVE = 512
NUM_VARS = 16
# kernel.step's arguments at this size, from the v5e compiler (ISSUE 22)
STEP_ARGUMENT_BYTES = 986_920_960
HBM_BYTES = 16 * 1000**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip writes cache entries no run here can
    # read back; conftest.py keeps the cache off, make sure of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _served_shapes():
    from zeebe_tpu.testing import graphs

    graph, _meta = graphs.build_graph()
    graph = dataclasses.replace(graph, num_vars=NUM_VARS)
    state = jax.eval_shape(
        lambda: state_mod.make_state(
            capacity=CAPACITY, num_vars=NUM_VARS, sub_capacity=16
        )
    )
    # the wave as the engine ships it: the packed pair, whose column views
    # the step program takes itself (``rb.column_views``)
    batch = rb.pair_shapes(WAVE, NUM_VARS)
    return graph, state, batch


def _scalar(dtype, sharding):
    return jax.ShapeDtypeStruct((), dtype, sharding=sharding)


# -- the programs the broker serves with --------------------------------------


def _lower_step(one_chip):
    graph, state, batch = _served_shapes()
    return kernel.step_jit.lower(
        _on(one_chip, graph), _on(one_chip, state), _on(one_chip, batch),
        _scalar(jnp.int64, one_chip),
        partition_id=_scalar(jnp.int32, one_chip),
    )


def _lower_tick(one_chip):
    _graph, state, _batch = _served_shapes()
    return kernel.tick_jit.lower(
        _on(one_chip, state), _scalar(jnp.int64, one_chip)
    )


def _lower_due_probe(one_chip):
    _graph, state, _batch = _served_shapes()
    return engine_mod._due_probe_jit.lower(
        _on(one_chip, state), _scalar(jnp.int64, one_chip)
    )


@pytest.mark.parametrize(
    "lower",
    [_lower_step, _lower_tick, _lower_due_probe],
    ids=["kernel.step", "kernel.tick", "engine.due_probe"],
)
def test_served_program_compiles_at_deployment_size(one_chip, lower):
    """XLA dispatch (off-TPU ``use_pallas`` is False), state donated: the
    tables alias input to output, and the program fits one 16 GB chip."""
    compiled = lower(one_chip).compile()
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" not in compiled.as_text()
    assert mem.argument_size_in_bytes == pytest.approx(
        STEP_ARGUMENT_BYTES, rel=0.01
    )
    # donation holds: all but the batch and scalars alias
    assert mem.alias_size_in_bytes >= 0.99 * mem.argument_size_in_bytes
    resident = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
        + mem.generated_code_size_in_bytes
    )
    assert resident < HBM_BYTES


def test_credit_flush_compiles_with_its_column_aliased(one_chip):
    """``engine.credit_flush`` at the served subscription capacity: the
    donated credit column aliases the result, and nothing table-sized is
    an argument of it (a launch of it costs by its two small leaves)."""
    column = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    compiled = engine_mod._credit_flush_jit.lower(column, column).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes <= 2 * 512  # two padded small vectors
    assert mem.alias_size_in_bytes >= 16 * 4


def test_sharded_state_step_divides_the_tables_by_the_span(topo):
    """``shard.state_step`` (gathered, the default routing) on the
    described 2x2 mesh: each device holds about a quarter of what one
    device holds alone, and the step gathers over the mesh."""
    graph, state, batch = _served_shapes()
    mesh = Mesh(np.asarray(topo.devices), (shard.STATE_AXIS,))
    repl = NamedSharding(mesh, PartitionSpec())
    step = shard.build_state_step(mesh, state)
    sharded_state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        state, shard.state_shardings(mesh, state),
    )
    compiled = step.lower(
        _on(repl, graph), sharded_state, _on(repl, batch),
        _scalar(jnp.int64, repl), _scalar(jnp.int32, repl),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(
        STEP_ARGUMENT_BYTES / 4, rel=0.01
    )
    assert "all-gather" in compiled.as_text()
    assert (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    ) < HBM_BYTES


# -- the pallas families and the table-size rule --------------------------------

B = WAVE
K = 6  # the row tables' width ([T, 6] i32: 512 B per row once padded)


def _family_call(family, rows, sharding):
    """(function, abstract args) calling one family on ``rows``-row tables."""

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    slots, active = S((B,), jnp.int32), S((B,), jnp.bool_)
    table, lane_table = S((rows, K), jnp.int32), S((rows,), jnp.int32)
    vals, lane_vals = S((B, K), jnp.int32), S((B,), jnp.int32)
    keys = S((B,), jnp.int64)
    hash_table = hashmap.HashTable(
        S((rows,), jnp.int32), S((rows,), jnp.int32), S((rows,), jnp.int32)
    )
    calls = {
        "row_update": (pops.masked_row_update, (table, slots, active, vals)),
        "row_max": (pops.masked_row_max, (table, slots, active, vals)),
        "row_add": (pops.masked_row_add, (table, slots, active, vals)),
        "lane": (pops.masked_lane_update, (lane_table, slots, active, lane_vals)),
        "vec64": (
            pops.masked_vec64_update,
            (S((rows, 2), jnp.int32), slots, active, S((B,), jnp.int64)),
        ),
        "lookup": (pops.lookup, (hash_table, keys, active)),
        "insert": (pops.insert, (hash_table, keys, lane_vals, active)),
        "delete": (pops.delete, (hash_table, keys, active)),
        "fused": (
            lambda t, r, s, a, v, lv: pops.fused_table_commit(
                [t, r],
                [pops.TableOp(0, "set", s, a, v), pops.TableOp(1, "set", s, a, lv)],
            ),
            (table, lane_table, slots, active, vals, lane_vals),
        ),
        "gather": (
            lambda t, r, s: pops.fused_gather_rows(
                [t, r], [pops.GatherOp(0, s), pops.GatherOp(1, s)]
            ),
            (table, lane_table, slots),
        ),
        "emit": (
            lambda t, s: pops.fused_gather_rows(
                [t], [pops.GatherOp(0, s)], family="emit"
            ),
            (table, slots),
        ),
    }
    fn, args = calls[family]
    # a fresh function object per call: jit caches traces by function, and
    # the trace bakes in what use_pallas and the size rule said
    return (lambda *a: fn(*a)), args


def _rule_admits(family, rows, sharding) -> bool:
    pops._SIZE_RULINGS.clear()
    fn, args = _family_call(family, rows, sharding)
    jax.eval_shape(fn, *args)
    rulings = pops.size_rulings()
    assert rulings, f"{family}: the size rule was never asked"
    return all(r["admitted"] for r in rulings)


def _largest_admitted_rows(family, sharding) -> int:
    lo, hi = 1, 1 << 16  # in units of 1024 rows
    assert _rule_admits(family, lo * 1024, sharding)
    assert not _rule_admits(family, hi * 1024, sharding)
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if _rule_admits(family, mid * 1024, sharding):
            lo = mid
        else:
            hi = mid
    return lo * 1024


@pytest.fixture
def pallas_on(monkeypatch):
    """Take the pallas pass wherever the size rule admits it (off-TPU
    ``use_pallas`` says False: steered here, not by a switch)."""
    monkeypatch.setattr(pops, "use_pallas", lambda family="row_update": True)
    yield
    pops._SIZE_RULINGS.clear()


@pytest.mark.parametrize("family", pops.FAMILIES)
def test_pallas_family_compiles_at_the_largest_admitted_table(
    one_chip, pallas_on, family
):
    """What the rule admits, the chip's compiler accepts — with the
    kernel really in the program."""
    rows = _largest_admitted_rows(family, one_chip)
    fn, args = _family_call(family, rows, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (family, rows)


def test_size_rule_sends_a_served_row_table_to_xla(one_chip, pallas_on):
    """A [2^20, 6] i32 table pads to 512 MiB of VMEM per window; Mosaic
    refuses it (RESOURCE_EXHAUSTED), so the rule must never offer it."""
    fn, args = _family_call("row_update", CAPACITY, one_chip)
    pops._SIZE_RULINGS.clear()
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    (ruling,) = pops.size_rulings()
    assert ruling["family"] == "row_update" and not ruling["admitted"]
    assert ruling["tables"] == [[CAPACITY, K], [CAPACITY, K]]
    assert ruling["vmem_bytes"] > 2 * CAPACITY * 512 > pops.VMEM_LIMIT_BYTES


def test_whole_step_compiles_with_pallas_where_admitted(one_chip, pallas_on):
    """The served step with every family on pallas: the 2D row tables go
    to XLA by the size rule, the 1D tables keep their kernels, and the
    whole program still compiles for the chip."""
    graph, state, batch = _served_shapes()
    compiled = (
        jax.jit(
            lambda g, s, b, now, pid: kernel.step_kernel(
                g, s, b, now, partition_id=pid
            ),
            donate_argnums=(1,),
        )
        .lower(
            _on(one_chip, graph), _on(one_chip, state), _on(one_chip, batch),
            _scalar(jnp.int64, one_chip), _scalar(jnp.int32, one_chip),
        )
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    rulings = pops.size_rulings()
    refused = {r["family"] for r in rulings if not r["admitted"]}
    admitted = {r["family"] for r in rulings if r["admitted"]}
    assert {"row_update", "fused"} <= refused
    assert {"lane", "lookup"} <= admitted
