"""The wave's transfer format: ONE packed pair each way (``rb.StagedBatch``:
an i32 matrix and an i8 matrix) and one stats vector out, where six arrays
went in and twenty-five came back.

- host views and device ``column_views`` of one pair are the same 24
  columns bit for bit, 64-bit edge values, ``-0.0``, a NaN's payload bits
  and every ``VT_*`` included, flat, laned, and for a fetched matrix that
  arrives column-major (as a TPU hands it over);
- ``step_kernel``'s packed emission is, column for column, what the
  per-column exit produced on the served processes: the golden
  (``tests/data/wave_io_golden.npz``) was taken from the parent's program
  by ``_rounds`` below before the exit was changed;
- a served wave counts two arrays in and three out a segment;
- an overflowing step raises before a record is decoded;
- the routed program's reduced emission is the gathered program's.
"""

import dataclasses
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from zeebe_tpu.engine.interpreter import WorkflowRepository
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.models.transform.transformer import transform_model
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import JobIntent as JI
from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI
from zeebe_tpu.protocol.records import Record, RecordMetadata, WorkflowInstanceRecord
from zeebe_tpu.tpu import TpuPartitionEngine
from zeebe_tpu.tpu import batch as rb
from zeebe_tpu.tpu import conditions, kernel, shard
from zeebe_tpu.tpu import graph as graph_mod
from zeebe_tpu.tpu import state as state_mod

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "wave_io_golden.npz")
FIELDS = [f.name for f in dataclasses.fields(rb.RecordBatch)]
WAVE, NUM_VARS, INSTANCES = 64, 16, 8


def _bits(a: np.ndarray) -> np.ndarray:
    """Floats compare by their bits (a NaN equals itself, -0.0 is not 0.0)."""
    return a.view(np.int32) if a.dtype == np.float32 else a


def _assert_same_columns(got: rb.RecordBatch, want: dict, rows=slice(None)):
    for name in FIELDS:
        a = np.asarray(getattr(got, name))[rows]
        assert a.dtype == want[name].dtype, name
        np.testing.assert_array_equal(_bits(a), _bits(want[name]), err_msg=name)


# -- (a) one pair, two sides ------------------------------------------------


def _edge_columns(lead: tuple) -> dict:
    """24 columns of 16 rows whose first rows hold the values a packing
    could lose: 64-bit extremes and words that differ, a negative zero, a
    NaN with payload bits, every payload type."""
    rng = np.random.default_rng([0x10, len(lead)])
    shape = lead + (16,)
    edges = np.array(
        [-1, 0, 1, -(1 << 32), (1 << 32) + 5, (1 << 40) - 1,
         np.iinfo(np.int64).min, np.iinfo(np.int64).max], np.int64,
    )
    cols = {}
    for k, n in enumerate(rb.I64_COLS):
        col = rng.integers(-(1 << 62), 1 << 62, shape, dtype=np.int64)
        col[..., : len(edges)] = np.roll(edges, k)
        cols[n] = col
    for n in rb.I32_COLS:
        col = rng.integers(-(1 << 31), (1 << 31) - 1, shape, dtype=np.int32)
        col[..., :2] = (np.iinfo(np.int32).min, np.iinfo(np.int32).max)
        cols[n] = col
    for n in rb.BOOL_COLS:
        cols[n] = rng.random(shape) < 0.5
    v_num = rng.standard_normal(shape + (NUM_VARS,)).astype(np.float32)
    v_num[..., 0, 0] = -0.0
    v_num[..., 1, 0] = np.inf
    v_num[..., 2, :2] = np.array([0x7FC00001, 0xFFC12345], np.uint32).view(np.float32)
    vts = [conditions.VT_ABSENT, conditions.VT_NIL, conditions.VT_BOOL,
           conditions.VT_NUM, conditions.VT_FLOAT, conditions.VT_STR]
    v_vt = np.resize(np.array(vts, np.int8), shape + (NUM_VARS,))
    cols.update(
        v_vt=v_vt, v_num=v_num,
        v_str=rng.integers(0, 1 << 30, shape + (NUM_VARS,), dtype=np.int32),
    )
    return cols


def _filled_pair(cols: dict, lead: tuple) -> rb.StagedBatch:
    pair = rb.host_pair(16, NUM_VARS, lead)
    views = rb.column_views(pair)
    for name, column in cols.items():
        getattr(views, name)[...] = column
    return pair


@pytest.mark.parametrize(
    "lead,order", [((), "C"), ((3,), "C"), ((), "F")],
    ids=["flat", "laned", "flat-column-major"],
)
def test_host_and_device_views_of_one_pair_are_the_same_columns(lead, order):
    cols = _edge_columns(lead)
    pair = _filled_pair(cols, lead)
    assert pair.size == 16 and pair.num_vars == NUM_VARS
    on_device = jax.device_put(pair)
    if order == "F":  # a fetched matrix as the TPU's runtime lays it out
        pair = jax.tree.map(np.asfortranarray, pair)
        assert not pair.i32.flags.c_contiguous
    host = rb.column_views(pair)
    _assert_same_columns(host, cols)
    _assert_same_columns(jax.jit(rb.column_views)(on_device), cols)
    _assert_same_columns(rb.column_views(on_device), cols)
    assert host.key.shape == lead + (16,)
    assert host.v_num.shape == lead + (16, NUM_VARS)
    # the device's packing is the host's: plane for plane
    packed = jax.jit(rb.pack)(rb.column_views(on_device))
    np.testing.assert_array_equal(np.asarray(packed.i32), np.asarray(pair.i32))
    np.testing.assert_array_equal(np.asarray(packed.i8), np.asarray(pair.i8))
    # and a row take of the columns is a row take of the pair
    idx = jnp.asarray([5, 0, 7, 2], jnp.int32)
    if not lead:
        taken = rb.take_rows(rb.column_views(on_device), idx)
        _assert_same_columns(taken, {n: c[np.asarray(idx)] for n, c in cols.items()})


# -- (b) the packed exit against the per-column exit's golden -----------------


def _served_graph(process: str):
    model = importlib.import_module("zbench.processes." + process).build()
    workflows = transform_model(model)
    for wf in workflows:
        wf.key, wf.version = 9, 1
    graph, meta = graph_mod.compile_graph(workflows)
    return dataclasses.replace(graph, num_vars=NUM_VARS), meta


def _empty_wave() -> dict:
    return {
        n: np.array(a) for n, a in zip(
            FIELDS, jax.tree_util.tree_leaves(rb.empty(WAVE, NUM_VARS))
        )
    }


def _creates(meta) -> dict:
    """INSTANCES CREATE commands as host columns: both branches of the
    gateway, a string variable, one request id each."""
    b, n = _empty_wave(), INSTANCES
    oid, oval, cust = (
        meta.varspace.column(v) for v in ("orderId", "orderValue", "customer")
    )
    b["valid"][:n] = True
    b["rtype"][:n] = int(RecordType.COMMAND)
    b["vtype"][:n] = int(ValueType.WORKFLOW_INSTANCE)
    b["intent"][:n] = int(WI.CREATE)
    b["wf"][:n] = 0
    b["req"][:n] = (1 << 33) + np.arange(n)
    b["req_stream"][:n] = 3
    b["v_vt"][:n, oid] = conditions.VT_NUM
    b["v_num"][:n, oid] = np.arange(n)
    b["v_vt"][:n, oval] = conditions.VT_NUM
    b["v_num"][:n, oval] = np.where(np.arange(n) % 2, 250.0, 40.0)
    b["v_vt"][:n, cust] = conditions.VT_STR
    b["v_str"][:n, cust] = [meta.interns.intern(f"c{i}") for i in range(n)]
    return b


def _subscribed_state(meta):
    state = state_mod.make_state(
        capacity=256, num_vars=NUM_VARS, job_capacity=256, sub_capacity=8
    )
    return dataclasses.replace(
        state,
        sub_key=state.sub_key.at[0].set(1),
        sub_type=state.sub_type.at[0].set(meta.interns.intern("payment-service")),
        sub_worker=state.sub_worker.at[0].set(meta.interns.intern("w")),
        sub_credits=state.sub_credits.at[0].set(np.int32(64)),
        sub_timeout=state.sub_timeout.at[0].set(300_000),
        sub_valid=state.sub_valid.at[0].set(True),
    )


def _next_wave(em: dict, count: int) -> dict:
    """The emission's rows as the next wave, as the log would feed them:
    every record once, and behind them the worker's COMPLETE for each
    ACTIVATED job."""
    activated = [
        r for r in range(count)
        if em["vtype"][r] == int(ValueType.JOB)
        and em["rtype"][r] == int(RecordType.EVENT)
        and em["intent"][r] == int(JI.ACTIVATED)
    ]
    rows = list(range(count)) + activated
    assert len(rows) <= WAVE
    b = _empty_wave()
    for n in FIELDS:
        b[n][: len(rows)] = em[n][rows]
    b["valid"][: len(rows)] = True
    done = slice(count, len(rows))
    b["rtype"][done] = int(RecordType.COMMAND)
    b["intent"][done] = int(JI.COMPLETE)
    b["req"][done] = (1 << 34) + np.arange(len(activated))
    return b


def _rounds(process: str) -> list:
    """Step the served process's creates to quiescence through
    ``kernel.step_jit``; every round's emission as host columns, its valid
    rows only. (Run on the parent's program, whose step returned the 24
    columns, this wrote the golden: ``column_views`` passes them through.)"""
    graph, meta = _served_graph(process)
    state = _subscribed_state(meta)
    wave = _creates(meta)
    out = []
    for _ in range(40):
        batch = rb.RecordBatch(**{n: jnp.asarray(a) for n, a in wave.items()})
        state, emission, stats = kernel.step_jit(
            graph, state, batch, np.int64(1_000_000)
        )
        em = {
            n: np.asarray(getattr(rb.column_views(emission), n)) for n in FIELDS
        }
        count = int(em["valid"].sum())
        assert em["valid"][:count].all()
        if not count:
            return out
        out.append(({n: a[:count] for n, a in em.items()}, emission, stats))
        wave = _next_wave(em, count)
    raise AssertionError("did not quiesce")


@pytest.mark.parametrize("process", ["route_order", "order_process"])
def test_packed_emission_is_the_per_column_exits(process):
    golden = np.load(GOLDEN)
    counts = golden[f"{process}.counts"]
    rounds = _rounds(process)
    assert [len(r[0]["valid"]) for r in rounds] == counts.tolist()
    assert len(rounds) >= 9 and counts.sum() >= 80
    at = 0
    for (em, emission, stats), count in zip(rounds, counts.tolist()):
        for name in FIELDS:
            want = golden[f"{process}.{name}"][at : at + count]
            assert em[name].dtype == want.dtype, name
            np.testing.assert_array_equal(
                _bits(em[name]), _bits(want), err_msg=f"{name} at row {at}"
            )
        at += count
        # what crosses: the pair and one vector, nothing 64 bits wide
        assert isinstance(emission, rb.StagedBatch)
        assert emission.i32.dtype == jnp.int32 and emission.i8.dtype == jnp.int8
        assert emission.i32.shape == (emission.size, rb.packed_widths(NUM_VARS)[0])
        named = kernel.stats_of(np.asarray(stats))
        assert stats.dtype == jnp.int32 and stats.shape == (len(kernel.STATS),)
        assert named["emitted"] == count and named["overflow"] == 0
        assert np.asarray(rb.column_views(emission).valid).sum() == count
    if process == "order_process":  # the job's columns crossed too
        jobs = golden[f"{process}.vtype"] == int(ValueType.JOB)
        assert jobs.sum() >= 40 and golden[f"{process}.push"].any()


# -- (c), (d) the served engine ------------------------------------------------


def _engine(capacity: int) -> TpuPartitionEngine:
    repo = WorkflowRepository()
    workflows = transform_model(
        Bpmn.create_process("io")
        .start_event("start")
        .service_task("work", type="io-service")
        .end_event("end")
        .done()
    )
    for wf in workflows:
        wf.key, wf.version = 1, 1
    repo.merge(workflows)
    engine = TpuPartitionEngine(
        0, 1, repository=repo, clock=lambda: 1_000_000, capacity=capacity,
        num_vars=8,
    )
    engine._recompile()
    return engine


def _create_command(i: int) -> Record:
    return Record(
        key=-1, position=100 + i, timestamp=0,
        metadata=RecordMetadata(
            record_type=RecordType.COMMAND,
            value_type=ValueType.WORKFLOW_INSTANCE,
            intent=int(WI.CREATE), request_id=7 + i, request_stream_id=3,
        ),
        value=WorkflowInstanceRecord(
            bpmn_process_id="io", workflow_key=1, payload={"n": i},
        ),
    )


def test_a_served_segment_counts_two_arrays_in_and_three_out(tmp_path):
    from zeebe_tpu.gateway import JobWorker, ZeebeClient
    from zeebe_tpu.runtime import Broker, ControlledClock
    from zeebe_tpu.runtime.metrics import event_count

    names = ("serving_h2d_transfers_total", "serving_d2h_transfers_total",
             "serving_h2d_bytes_total", "serving_d2h_bytes_total")
    before = {n: event_count(n) for n in names}
    launches = []
    inner = kernel.step_jit

    def step_jit(*args, **kw):
        launches.append(args[2])
        return inner(*args, **kw)

    clock = ControlledClock(start_ms=1_000_000)
    repo = WorkflowRepository()
    broker = Broker(
        num_partitions=1, data_dir=str(tmp_path), clock=clock,
        engine_factory=lambda pid: TpuPartitionEngine(
            pid, 1, repository=repo, clock=clock
        ),
    )
    kernel.step_jit = step_jit
    try:
        client = ZeebeClient(broker)
        client.deploy_model(
            Bpmn.create_process("io").start_event("start")
            .service_task("work", type="io-service").end_event("end").done()
        )
        JobWorker(broker, "io-service", lambda ctx: {"done": True})
        for i in range(5):
            client.create_instance("io", {"n": i})
        broker.run_until_idle()
    finally:
        kernel.step_jit = inner
        broker.close()
    moved = {n: event_count(n) - before[n] for n in names}
    segments = len(launches)
    assert segments >= 4
    assert moved["serving_h2d_transfers_total"] == 2 * segments
    assert 2 * segments <= moved["serving_d2h_transfers_total"] <= 3 * segments
    assert moved["serving_h2d_bytes_total"] == sum(
        a.nbytes for b in launches for a in jax.tree_util.tree_leaves(b)
    )
    assert moved["serving_d2h_bytes_total"] > moved["serving_h2d_bytes_total"]


def test_an_overflowing_step_raises_before_a_record_is_decoded(monkeypatch):
    engine = _engine(capacity=16)
    decoded = []
    monkeypatch.setattr(
        engine, "_emit_records", lambda *a, **kw: decoded.append(a)
    )
    wave = engine.dispatch_wave([_create_command(i) for i in range(48)])
    with pytest.raises(RuntimeError, match="device table overflow"):
        engine.collect_wave(wave)
    assert not decoded
    # a wave that fits the same tables is collected and decoded
    roomy = _engine(capacity=256)
    monkeypatch.setattr(
        roomy, "_emit_records", lambda *a, **kw: decoded.append(a)
    )
    roomy.collect_wave(roomy.dispatch_wave([_create_command(0)]))
    assert len(decoded) == 1


# -- (e) the routed program's reduction of the pair ---------------------------


def test_routed_reduction_of_the_pair_equals_the_gathered_emission():
    """The routed step reduces the emission over the mesh axis with
    ``psum`` AFTER packing: every plane is an integer and only the owner
    lane's term is not zero, so the reduced pair is bit for bit the pair
    the gathered program (and the single-device one) emits, negative keys'
    high words and float bits included."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    graph, meta = _served_graph("order_process")
    devices = np.asarray(jax.devices()[:4])
    mesh = Mesh(devices, (shard.STATE_AXIS,))
    nshards = len(devices)
    state = _subscribed_state(meta)
    now, pid = np.int64(1_000_000), np.int32(0)
    wave = _creates(meta)
    wave["v_num"][:INSTANCES, 3] = -0.0  # a float whose sum with 0.0 is not itself
    wave["v_vt"][:INSTANCES, 3] = conditions.VT_FLOAT
    w32, w8 = rb.packed_widths(NUM_VARS)
    owner = 2

    def pair_of(columns, lead=()):
        pair = rb.host_pair(WAVE, NUM_VARS, lead)
        lane = pair if not lead else jax.tree.map(lambda a: a[owner], pair)
        views = rb.column_views(lane)
        for name, column in columns.items():
            getattr(views, name)[...] = column
        return pair

    _s, single, single_stats = kernel.step_jit(
        graph, state, pair_of(wave), now, partition_id=pid
    )
    shardings = shard.state_shardings(mesh, state)
    repl = NamedSharding(mesh, PartitionSpec())

    def placed():
        return jax.tree.map(jax.device_put, _subscribed_state(meta), shardings)

    gathered = shard.build_state_step(mesh, state)
    _s, g_out, g_stats = gathered(
        graph, placed(), jax.device_put(pair_of(wave), repl), now, pid
    )
    routed = shard.build_state_step_routed(mesh, state)
    lanes = jax.device_put(
        pair_of(wave, (nshards,)),
        NamedSharding(mesh, PartitionSpec(shard.STATE_AXIS)),
    )
    _s, r_out, r_stats = routed(graph, placed(), lanes, now, pid)
    count = int(kernel.stats_of(np.asarray(single_stats))["emitted"])
    assert count == 2 * INSTANCES
    for other, other_stats in ((g_out, g_stats), (r_out, r_stats)):
        np.testing.assert_array_equal(
            np.asarray(other_stats), np.asarray(single_stats)
        )
        for a, b in zip(jax.tree_util.tree_leaves(other),
                        jax.tree_util.tree_leaves(single)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(
                np.asarray(a)[:count], np.asarray(b)[:count]
            )
    neg = np.asarray(rb.column_views(jax.device_get(r_out)).v_num)[:count, 3]
    assert np.signbit(neg).all()
    assert shard.routed_exchange_bytes(r_out, nshards) == (
        (nshards - 1) * 4 * r_out.size * (w32 + w8)
    )
