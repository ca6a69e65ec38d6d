"""Wave staging by columns (``TpuPartitionEngine._stage``): the host fills
the packed pair's two matrices with array operations, ships them as one
``rb.StagedBatch`` and the step program takes the column views itself.

Pinned here against the row-by-row rule the column fill replaced (kept
below as the reference, one Python write per column and row):

- the staged matrices hold exactly the values the row rule produces, for
  lazy emission refs of several source batches and value types, rows
  without a workflow slot, junk in unset payload lanes, materialized
  Records between the refs, an empty (warm) wave and the routed laned
  layout;
- ``rb.column_views`` of the staged pair gives the per-column arrays the
  engine used to slice eagerly, flat and laned, inside and outside ``jit``;
- a served wave hands ``kernel.step_jit`` two array leaves, both put by
  ``_put_staged``, and two numpy scalars — nothing the host would have to
  launch a device op for.
"""

import dataclasses

import numpy as np
import pytest

import jax

from zeebe_tpu.engine.interpreter import WorkflowRepository
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.models.transform.transformer import transform_model
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import JobIntent as JI
from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI
from zeebe_tpu.protocol.records import (
    JobHeaders,
    JobRecord,
    Record,
    RecordMetadata,
    WorkflowInstanceRecord,
)
from zeebe_tpu.tpu import TpuPartitionEngine
from zeebe_tpu.tpu import batch as rb
from zeebe_tpu.tpu import engine as engine_mod
from zeebe_tpu.tpu import kernel

SEED = 0x57A6ED
NUM_VARS = 8
LANE_SLOTS = 128


def _engine(**kw) -> TpuPartitionEngine:
    repo = WorkflowRepository()
    workflows = transform_model(
        Bpmn.create_process("staged")
        .start_event("start")
        .service_task("work", type="stage-service")
        .end_event("end")
        .done()
    )
    for wf in workflows:
        wf.key, wf.version = 1, 1
    repo.merge(workflows)
    engine = TpuPartitionEngine(
        0, 1, repository=repo, clock=lambda: 1_000_000, capacity=256,
        num_vars=NUM_VARS, **kw,
    )
    engine._recompile()
    assert engine.meta is not None
    return engine


class _Source:
    """A collected emission batch as ``_emit_records`` leaves it behind:
    ``device_source = (numpy columns, list columns, meta epoch)``."""

    def __init__(self, o):
        lists = {k: a.tolist() for k, a in o.items() if a.ndim == 1}
        self.device_source = (o, lists, 0)

    def device_ref(self, i):
        return (self, i)


def _source(rng, rows: int, vtypes, wfs=(0,), set_share=0.5) -> _Source:
    """``rows`` emission rows with EVERY column random — also the columns
    staging must not carry over (src, resp, push, rej, aux2_key) and the
    payload lanes whose type says "unset"."""
    def i64():
        return rng.integers(1, 1 << 40, rows, dtype=np.int64)

    def i32(lo=0, hi=1 << 20):
        return rng.integers(lo, hi, rows, dtype=np.int32)

    v_vt = rng.integers(1, 6, (rows, NUM_VARS), dtype=np.int8)
    v_vt[rng.random((rows, NUM_VARS)) >= set_share] = 0
    return _Source({
        "valid": np.ones(rows, bool),
        "rtype": np.full(rows, int(RecordType.EVENT), np.int32),
        "vtype": rng.choice(np.asarray(vtypes, np.int32), rows),
        "intent": i32(0, 12),
        "key": i64(),
        "elem": i32(0, 3),
        "wf": rng.choice(np.asarray(wfs, np.int32), rows),
        "instance_key": i64(),
        "scope_key": i64(),
        "v_vt": v_vt,
        "v_num": rng.random((rows, NUM_VARS)).astype(np.float32) + 1,
        "v_str": rng.integers(1, 99, (rows, NUM_VARS), dtype=np.int32),
        "req": i64(),
        "req_stream": i32(),
        "aux_key": i64(),
        "aux2_key": i64(),
        "type_id": i32(),
        "retries": i32(0, 5),
        "deadline": i64(),
        "worker": i32(),
        "src": i32(0, rows),
        "resp": rng.random(rows) < 0.5,
        "push": rng.random(rows) < 0.5,
        "rej": i32(1, 9),
    })


def _create_command(i: int) -> Record:
    return Record(
        key=-1, position=100 + i, timestamp=0,
        metadata=RecordMetadata(
            record_type=RecordType.COMMAND,
            value_type=ValueType.WORKFLOW_INSTANCE,
            intent=int(WI.CREATE), request_id=7 + i, request_stream_id=3,
        ),
        value=WorkflowInstanceRecord(
            bpmn_process_id="staged", workflow_key=1,
            payload={"orderValue": 100 + i, "customer": f"c{i}", "vip": True},
        ),
    )


def _complete_command(i: int) -> Record:
    return Record(
        key=4000 + i, position=200 + i, timestamp=0,
        metadata=RecordMetadata(
            record_type=RecordType.COMMAND, value_type=ValueType.JOB,
            intent=int(JI.COMPLETE), request_id=70 + i, request_stream_id=4,
        ),
        value=JobRecord(
            type="stage-service", worker="w", retries=3, deadline=2_000_000,
            headers=JobHeaders(
                workflow_instance_key=900 + i, workflow_key=1,
                activity_id="work", activity_instance_key=950 + i,
            ),
            payload={"paid": 1.5, "note": None},
        ),
    )


WI_, JOB_ = int(ValueType.WORKFLOW_INSTANCE), int(ValueType.JOB)


def _refs(source: _Source, rng, count: int) -> list:
    rows = len(source.device_source[0]["valid"])
    return [(source, int(j)) for j in rng.permutation(rows)[:count]]


def _wave(case: str, rng) -> list:
    """The staged entries of one wave: ``(batch, row)`` lazy refs and
    materialized Records, in log order."""
    if case == "zero_rows":
        return []
    if case == "wi_and_job_from_two_sources":
        a = _refs(_source(rng, 24, (WI_, JOB_), wfs=(0,)), rng, 9)
        b = _refs(_source(rng, 16, (WI_, JOB_), wfs=(0,)), rng, 11)
        return [ref for pair in zip(a, b) for ref in pair] + b[len(a):]
    if case == "rows_without_workflow_slot":
        return _refs(_source(rng, 24, (WI_, JOB_), wfs=(-1, 0)), rng, 20)
    if case == "junk_in_unset_payload_lanes":
        return _refs(_source(rng, 16, (WI_,), set_share=0.1), rng, 16)
    if case == "other_value_types":
        vts = (WI_, JOB_, int(ValueType.TIMER), int(ValueType.INCIDENT))
        return _refs(_source(rng, 32, vts, wfs=(-1, 0)), rng, 27)
    assert case == "records_between_refs"
    a = _refs(_source(rng, 16, (WI_, JOB_), wfs=(-1, 0)), rng, 7)
    b = _refs(_source(rng, 16, (WI_, JOB_)), rng, 6)
    return (
        [_create_command(0)] + a[:4] + [_complete_command(0)] + b
        + [_create_command(1), _complete_command(1)] + a[4:]
    )


CASES = (
    "wi_and_job_from_two_sources", "rows_without_workflow_slot",
    "junk_in_unset_payload_lanes", "other_value_types",
    "records_between_refs", "zero_rows",
)


# -- the reference: one write per column and row -------------------------------


def _row_from_emission(cols, i, src, j) -> None:
    o, s, _epoch = src.device_source
    vt = s["vtype"][j]
    cols["valid"][i] = True
    for name in ("rtype", "vtype", "intent", "key", "req", "req_stream"):
        cols[name][i] = s[name][j]
    wf = s["wf"][j]
    if vt == WI_:
        cols["wf"][i] = wf
        cols["elem"][i] = s["elem"][j] if wf >= 0 else -1
        cols["instance_key"][i] = s["instance_key"][j]
        cols["scope_key"][i] = s["scope_key"][j]
    elif vt == JOB_:
        for name in ("type_id", "retries", "deadline", "worker", "aux_key",
                     "instance_key"):
            cols[name][i] = s[name][j]
        cols["wf"][i] = wf
        cols["elem"][i] = s["elem"][j] if wf >= 0 else -1
    vt_row = o["v_vt"][j]
    cols["v_vt"][i] = vt_row
    cols["v_num"][i] = np.where(vt_row != 0, o["v_num"][j], 0)
    cols["v_str"][i] = np.where(vt_row != 0, o["v_str"][j], 0)


def _reference_columns(engine, records, size: int) -> dict:
    """The row-by-row staging rule: Python lists of defaults, one setitem
    per column and row, one array per column at the end — what the engine
    built, and sliced out of the device matrices again, before the column
    fill."""
    cols = {
        name: [default] * size
        for name, default in engine_mod._COL_DEFAULTS.items()
    }
    cols["v_vt"] = np.zeros((size, NUM_VARS), np.int8)
    cols["v_num"] = np.zeros((size, NUM_VARS), np.float32)
    cols["v_str"] = np.zeros((size, NUM_VARS), np.int32)
    for i, record in enumerate(records):
        if type(record) is tuple:
            _row_from_emission(cols, i, *record[0].device_ref(record[1]))
        else:
            engine._stage_row(cols, i, record)
    dtypes = {n: np.int64 for n in rb.I64_COLS}
    dtypes.update({n: np.int32 for n in rb.I32_COLS})
    dtypes.update({n: bool for n in rb.BOOL_COLS})
    return {n: np.asarray(c, dtypes.get(n)) for n, c in cols.items()}


def _assert_columns_equal(views: rb.RecordBatch, want: dict, lane=None):
    for f in dataclasses.fields(views):
        got = np.asarray(getattr(views, f.name))
        got = got if lane is None else got[lane]
        assert got.dtype == want[f.name].dtype, f.name
        np.testing.assert_array_equal(got, want[f.name], err_msg=f.name)


@pytest.fixture(scope="module")
def flat_engine():
    return _engine()


@pytest.fixture(scope="module")
def laned_engine():
    return _engine(
        state_shards=2, routing="resident", routed_lane_slots=LANE_SLOTS
    )


class TestColumnFillParity:
    @pytest.mark.parametrize("case", CASES)
    def test_flat_matrices_hold_the_row_rules_values(self, flat_engine, case):
        rng = np.random.default_rng([SEED, CASES.index(case)])
        records = _wave(case, rng)
        pad_to = 128 if case == "zero_rows" else 0  # warm() stages [] padded
        staged = flat_engine._stage(records, pad_to=pad_to)
        assert isinstance(staged, rb.StagedBatch)
        assert len(jax.tree_util.tree_leaves(staged)) == 2
        size = staged.size
        assert size == max(64, pad_to) and size >= len(records)
        want = _reference_columns(flat_engine, records, size)
        _assert_columns_equal(rb.column_views(jax.device_get(staged)), want)

    @pytest.mark.parametrize("case", ("records_between_refs", "zero_rows"))
    def test_laned_owner_lane_holds_them_and_the_rest_defaults(
        self, laned_engine, case
    ):
        rng = np.random.default_rng([SEED, 100 + CASES.index(case)])
        records = _wave(case, rng)
        staged = laned_engine._stage(records, lane_owner=1)
        w32, w8 = rb.packed_widths(NUM_VARS)
        assert staged.i32.shape == (2, LANE_SLOTS, w32)
        assert staged.i8.shape == (2, LANE_SLOTS, w8)
        views = rb.column_views(jax.device_get(staged))
        _assert_columns_equal(
            views, _reference_columns(laned_engine, records, LANE_SLOTS),
            lane=1,
        )
        _assert_columns_equal(
            views, _reference_columns(laned_engine, [], LANE_SLOTS), lane=0
        )
        # the routing accounting reads the owner lane's columns
        assert laned_engine._last_stage_valid == len(records)
        assert int(laned_engine._last_stage_split.sum()) == len(records)

    def test_columnar_counter_counts_the_refs_not_the_records(
        self, flat_engine
    ):
        from zeebe_tpu.runtime.metrics import GLOBAL_REGISTRY

        counter = GLOBAL_REGISTRY.counter("serving_rows_staged_columnar_total")
        records = _wave(
            "records_between_refs", np.random.default_rng([SEED, 200])
        )
        before = counter.value
        flat_engine._stage(records)
        assert counter.value - before == sum(
            type(r) is tuple for r in records
        ) == len(records) - 4


class TestColumnViews:
    """``rb.column_views`` is the old per-column slicing, moved: the same
    arrays whether the program takes them (under ``jit``) or the host."""

    def _columns(self, lead=()):
        rng = np.random.default_rng([SEED, 300 + len(lead)])
        shape = lead + (16,)
        want = {
            n: rng.integers(-5, 1 << 40, shape, dtype=np.int64)
            for n in rb.I64_COLS
        }
        want.update({
            n: rng.integers(-5, 1 << 20, shape, dtype=np.int32)
            for n in rb.I32_COLS
        })
        want.update({n: rng.random(shape) < 0.5 for n in rb.BOOL_COLS})
        want.update(
            v_vt=rng.integers(0, 6, shape + (NUM_VARS,), dtype=np.int8),
            v_num=rng.random(shape + (NUM_VARS,)).astype(np.float32),
            v_str=rng.integers(0, 99, shape + (NUM_VARS,), dtype=np.int32),
        )
        return want

    @pytest.mark.parametrize("lead", ((), (4,)), ids=("flat", "laned"))
    def test_views_equal_the_per_column_slices(self, lead):
        want = self._columns(lead)
        assert set(want) == {f.name for f in dataclasses.fields(rb.RecordBatch)}
        # the pair, filled the way staging fills it: through its host views
        staged = rb.host_pair(16, NUM_VARS, lead)
        views = rb.column_views(staged)
        for name, column in want.items():
            getattr(views, name)[...] = column
        on_device = jax.device_put(staged)
        assert len(jax.tree_util.tree_leaves(on_device)) == 2
        _assert_columns_equal(jax.jit(rb.column_views)(on_device), want)
        _assert_columns_equal(rb.column_views(on_device), want)
        _assert_columns_equal(rb.column_views(staged), want)
        _assert_columns_equal(rb.column_views(jax.device_get(on_device)), want)
        assert rb.column_views(staged).valid.shape == lead + (16,)
        # and back: packing the device columns gives the staged matrices
        packed = jax.jit(rb.pack)(rb.column_views(on_device))
        np.testing.assert_array_equal(np.asarray(packed.i32), staged.i32)
        np.testing.assert_array_equal(np.asarray(packed.i8), staged.i8)

    def test_a_record_batch_passes_through(self):
        batch = rb.empty(8, NUM_VARS)
        assert rb.column_views(batch) is batch


class TestLaunchArguments:
    def test_served_waves_hand_the_step_the_pair_and_numpy_scalars(
        self, tmp_path, monkeypatch
    ):
        """The engine calls ``kernel.step_jit`` BY ATTRIBUTE (the hook
        ``zbench/faults.py``'s ``state_unchanged`` control wraps) with the
        staged pair and host scalars: every device array of the call was
        put by ``_put_staged``, two of them, and nothing is left for an
        eager op. What comes back is the emission's pair and one stats
        vector, and no 64-bit array crosses either way."""
        from zeebe_tpu.gateway import JobWorker, ZeebeClient
        from zeebe_tpu.runtime import Broker, ControlledClock
        inner = kernel.step_jit
        calls = []
        put = []

        def step_jit(graph, state, batch, now, **kw):
            result = inner(graph, state, batch, now, **kw)
            calls.append((batch, now, kw, result[1:]))
            return result

        inner_put = TpuPartitionEngine._put_staged

        def put_staged(self, staged, target):
            placed = inner_put(self, staged, target)
            put.extend(jax.tree_util.tree_leaves(placed))
            return placed

        monkeypatch.setattr(kernel, "step_jit", step_jit)
        monkeypatch.setattr(TpuPartitionEngine, "_put_staged", put_staged)
        clock = ControlledClock(start_ms=1_000_000)
        repo = WorkflowRepository()
        broker = Broker(
            num_partitions=1, data_dir=str(tmp_path), clock=clock,
            engine_factory=lambda pid: TpuPartitionEngine(
                pid, 1, repository=repo, clock=clock
            ),
        )
        try:
            client = ZeebeClient(broker)
            client.deploy_model(
                Bpmn.create_process("served")
                .start_event("start")
                .service_task("work", type="served-service")
                .end_event("end")
                .done()
            )
            JobWorker(broker, "served-service", lambda ctx: {"done": True})
            for i in range(6):
                client.create_instance("served", {"n": i})
            broker.run_until_idle()
        finally:
            broker.close()
        assert len(calls) >= 4
        assert len(put) == 2 * len(calls)
        for batch, now, kw, (emission, stats) in calls:
            assert isinstance(batch, rb.StagedBatch)
            leaves = jax.tree_util.tree_leaves(batch)
            assert len(leaves) == 2
            assert all(isinstance(a, jax.Array) for a in leaves)
            assert all(any(a is p for p in put) for a in leaves)
            assert type(now) is np.int64
            assert set(kw) == {"partition_id"}
            assert type(kw["partition_id"]) is np.int32
            assert isinstance(emission, rb.StagedBatch)
            assert stats.shape == (len(kernel.STATS),)
            crossing = leaves + jax.tree_util.tree_leaves((emission, stats))
            assert {str(a.dtype) for a in crossing} == {"int32", "int8"}
