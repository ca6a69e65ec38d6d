"""SoA record batches: the device form of log records.

A batch is the columnar image of a contiguous log range (the unit the kernel
processes per invocation), mirroring the logical record layout of the
reference protocol (``protocol/src/main/resources/protocol.xml`` metadata +
value fields): record type / value type / intent / key plus the value
columns the kernel needs. Payloads are columnarized over the graph's
variable space; strings are interned ids.

Emissions reuse the same layout — the kernel's output batch IS the next
input batch (plus host bookkeeping columns: source row, response/push
flags, rejection codes).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from zeebe_tpu.tpu.conditions import (
    VT_ABSENT,
    VT_BOOL,
    VT_FLOAT,
    VT_HELD,
    VT_NIL,
    VT_NUM,
    VT_STR,
    f32_exact,
)
from zeebe_tpu.tpu.intern import InternTable

# ---------------------------------------------------------------------------
# rejection / incident codes (device → host reason strings)
# ---------------------------------------------------------------------------

REJ_NONE = 0
REJ_JOB_NOT_ACTIVATABLE = 1
REJ_JOB_NOT_COMPLETABLE = 2
REJ_JOB_NOT_ACTIVATED = 3
REJ_JOB_NOT_FAILED = 4
REJ_RETRIES_NOT_POSITIVE = 5
REJ_JOB_NOT_EXIST = 6
REJ_TIMER_NOT_EXIST = 7
REJ_SUB_NOT_ACTIVE = 8   # correlate arrival for a gone activity instance
REJ_MSG_DUP = 9          # duplicate (name, correlation, message id) publish
# the device message store keys ONE live slot per (name, correlation)
# composite — a second open subscription / stored message on an occupied
# composite rejects per-record instead of crashing the partition
REJ_SUB_OCCUPIED = 10
REJ_MSG_STORE_OCCUPIED = 11

# incident error codes (emitted on INCIDENT CREATE commands)
ERR_CONDITION_NO_FLOW = 101
ERR_CONDITION_EVAL = 102
ERR_IO_MAPPING_IN = 103
ERR_IO_MAPPING_OUT = 104
ERR_CORRELATION_KEY = 106  # 105 = job-no-retries (engine.py)

# reason strings match the oracle engine exactly (interpreter.py)
REJECTION_REASONS = {
    REJ_JOB_NOT_ACTIVATABLE: "Job is not in one of these states: CREATED, FAILED, TIMED_OUT",
    REJ_JOB_NOT_COMPLETABLE: "Job is not in state: ACTIVATED, TIMED_OUT",
    REJ_JOB_NOT_ACTIVATED: "Job is not in state ACTIVATED",
    REJ_JOB_NOT_FAILED: "Job is not in state FAILED",
    REJ_RETRIES_NOT_POSITIVE: "Retries must be greater than 0",
    REJ_JOB_NOT_EXIST: "Job does not exist",
    REJ_TIMER_NOT_EXIST: "timer does not exist",
    REJ_SUB_NOT_ACTIVE: "activity is not active anymore",
    # REJ_MSG_DUP's reason embeds the message id — formatted in
    # engine._materialize from the interned id
    REJ_SUB_OCCUPIED: (
        "a subscription for this (message name, correlation key) is already "
        "open on this TPU-backed partition (one live subscription per key)"
    ),
    REJ_MSG_STORE_OCCUPIED: (
        "a message with this (name, correlation key) is already stored on "
        "this TPU-backed partition (one buffered message per key)"
    ),
}

_FIELDS = [
    "valid", "rtype", "vtype", "intent", "key", "elem", "wf",
    "instance_key", "scope_key", "v_vt", "v_num", "v_str",
    "req", "req_stream", "aux_key", "aux2_key", "type_id", "retries",
    "deadline", "worker", "src", "resp", "push", "rej",
]


@partial(jax.tree_util.register_dataclass, data_fields=_FIELDS, meta_fields=[])
@dataclasses.dataclass
class RecordBatch:
    valid: jax.Array        # [B] bool
    rtype: jax.Array        # [B] i32 RecordType
    vtype: jax.Array        # [B] i32 ValueType
    intent: jax.Array       # [B] i32
    key: jax.Array          # [B] i64
    elem: jax.Array         # [B] i32 element index (-1 n/a)
    wf: jax.Array           # [B] i32 workflow slot (-1 n/a)
    instance_key: jax.Array # [B] i64 workflowInstanceKey
    scope_key: jax.Array    # [B] i64 scopeInstanceKey
    v_vt: jax.Array         # [B, V] i8 payload types
    v_num: jax.Array        # [B, V] f32 (f32-exact by construction; see
                            # payload_to_columns — an inexact number is
                            # held on the host like a map or a list)
    v_str: jax.Array        # [B, V] i32 intern id, or held-value handle
    req: jax.Array          # [B] i64 request id (-1 none)
    req_stream: jax.Array   # [B] i32 request stream / subscriber key
    aux_key: jax.Array      # [B] i64 job activityInstanceKey / incident aik / timer aik
    aux2_key: jax.Array     # [B] i64 incident jobKey / timer dueDate
    type_id: jax.Array      # [B] i32 job type (interned)
    retries: jax.Array      # [B] i32
    deadline: jax.Array     # [B] i64
    worker: jax.Array       # [B] i32 interned worker name
    src: jax.Array          # [B] i32 source row in the previous batch (-1 host)
    resp: jax.Array         # [B] bool respond to req at append
    push: jax.Array         # [B] bool push to req_stream subscriber
    rej: jax.Array          # [B] i32 rejection / incident code

    @property
    def size(self) -> int:
        return self.valid.shape[0]

    @property
    def num_vars(self) -> int:
        return self.v_vt.shape[1]


def empty(size: int, num_vars: int) -> RecordBatch:
    i64, i32, i8, f32 = jnp.int64, jnp.int32, jnp.int8, jnp.float32
    z64 = lambda: jnp.full((size,), -1, i64)  # noqa: E731
    z32 = lambda: jnp.full((size,), -1, i32)  # noqa: E731
    return RecordBatch(
        valid=jnp.zeros((size,), bool),
        rtype=jnp.zeros((size,), i32),
        vtype=jnp.zeros((size,), i32),
        intent=jnp.zeros((size,), i32),
        key=z64(),
        elem=z32(),
        wf=z32(),
        instance_key=z64(),
        scope_key=z64(),
        v_vt=jnp.zeros((size, num_vars), i8),
        v_num=jnp.zeros((size, num_vars), f32),
        v_str=jnp.zeros((size, num_vars), i32),
        req=z64(),
        req_stream=z32(),
        aux_key=z64(),
        aux2_key=z64(),
        type_id=jnp.zeros((size,), i32),
        retries=jnp.zeros((size,), i32),
        deadline=z64(),
        worker=jnp.zeros((size,), i32),
        src=z32(),
        resp=jnp.zeros((size,), bool),
        push=jnp.zeros((size,), bool),
        rej=jnp.zeros((size,), i32),
    )


# ---------------------------------------------------------------------------
# host payload conversion
# ---------------------------------------------------------------------------


class PayloadError(ValueError):
    """Payload not columnarizable (variable space overflow, or a value the
    caller gave no store to hold) — the caller must fall back to the host
    oracle engine."""


def scalar_form(value) -> bool:
    """True when ``value`` has a column form of its own: nil, a bool, a
    string, or a number that f32 holds exactly. Anything else is held on
    the host (``VT_HELD``)."""
    if value is None or isinstance(value, (bool, str)):
        return True
    if isinstance(value, (int, float)):
        return f32_exact(value)
    return False


def payload_to_columns(
    doc: Dict[str, Any],
    column_of,          # name -> column (VarSpace.column, growable)
    interns: InternTable,
    num_vars: int,
    hold=None,          # value -> handle (ValueStore.hold), or None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    vt = np.zeros((num_vars,), np.int8)
    num = np.zeros((num_vars,), np.float32)
    sid = np.zeros((num_vars,), np.int32)
    for name, value in doc.items():
        col = column_of(name)
        if col >= num_vars:
            raise PayloadError(f"variable space overflow: {name}")
        if value is None:
            vt[col] = VT_NIL
        elif isinstance(value, bool):
            vt[col] = VT_BOOL
            num[col] = 1.0 if value else 0.0
        elif isinstance(value, str):
            vt[col] = VT_STR
            sid[col] = interns.intern(value)
        elif isinstance(value, (int, float)) and f32_exact(value):
            vt[col] = VT_NUM if isinstance(value, int) else VT_FLOAT
            num[col] = value
        elif hold is not None:
            vt[col] = VT_HELD
            sid[col] = hold(value)
        else:
            raise PayloadError(f"no column form for {name!r}: {value!r}")
    return vt, num, sid


def columns_to_payload(
    vt: np.ndarray, num: np.ndarray, sid: np.ndarray, names,
    interns: InternTable, values=None,
) -> Dict[str, Any]:
    """The document of one row's payload columns; ``values`` resolves a
    held value's handle (a ``ValueStore`` or a dict a decode kept)."""
    doc: Dict[str, Any] = {}
    for col, name in enumerate(names):
        t = int(vt[col])
        if t == VT_ABSENT:
            continue
        if t == VT_NIL:
            doc[name] = None
        elif t == VT_BOOL:
            doc[name] = bool(num[col])
        elif t == VT_NUM:
            doc[name] = int(num[col])
        elif t == VT_FLOAT:
            doc[name] = float(num[col])
        elif t == VT_STR:
            doc[name] = interns.string(int(sid[col]))
        elif t == VT_HELD:
            doc[name] = values[int(sid[col])]
        else:
            raise ValueError(f"payload column {name!r} has unknown type tag {t}")
    return doc


# the scalar columns' dtype families, schema-derived so a new field fails
# loudly here instead of silently dropping: the ONE layout shared by the
# packed row takes below, the kernel's emission and a wave as it crosses the
# host-device boundary (``StagedBatch``)
I32_COLS = ("rtype", "vtype", "intent", "elem", "wf", "req_stream",
            "type_id", "retries", "worker", "src", "rej")
I64_COLS = ("key", "instance_key", "scope_key", "req", "aux_key",
            "aux2_key", "deadline")
BOOL_COLS = ("valid", "resp", "push")
assert set(I32_COLS + I64_COLS + BOOL_COLS
           + ("v_vt", "v_num", "v_str")) == set(_FIELDS)


def packed_widths(num_vars: int) -> Tuple[int, int]:
    """Last-axis widths of a packed wave's ``(i32, i8)`` matrices."""
    return (
        len(I32_COLS) + 2 * num_vars + 2 * len(I64_COLS),
        len(BOOL_COLS) + num_vars,
    )


@partial(
    jax.tree_util.register_dataclass, data_fields=["i32", "i8"],
    meta_fields=[],
)
@dataclasses.dataclass
class StagedBatch:
    """A wave as it crosses the host-device boundary, either way: ONE
    packed pair (two leaves, one transfer each) instead of one array per
    column. No 64-bit array crosses: a 64-bit column is a little-endian
    lo/hi pair of i32 planes, ``v_num`` its own bits. Any leading dims
    (the routed ``[num_shards]`` lane dim) ride along.

    ``i32``: ``I32_COLS``, then ``v_str`` [V], ``v_num`` bit for bit [V],
    then ``I64_COLS`` as plane pairs; ``i8``: ``BOOL_COLS``, then ``v_vt``
    [V]. ``column_views`` gives the columns by name, on either side."""

    i32: jax.Array  # [.., B, len(I32_COLS) + 2V + 2 len(I64_COLS)] i32
    i8: jax.Array   # [.., B, len(BOOL_COLS) + V] i8

    @property
    def size(self) -> int:
        return self.i32.shape[-2]

    @property
    def num_vars(self) -> int:
        return self.i8.shape[-1] - len(BOOL_COLS)


def host_pair(size: int, num_vars: int, lead: tuple = ()) -> StagedBatch:
    """An all-zero packed wave of numpy matrices, to fill through
    ``column_views``."""
    w32, w8 = packed_widths(num_vars)
    return StagedBatch(
        i32=np.zeros(lead + (size, w32), np.int32),
        i8=np.zeros(lead + (size, w8), np.int8),
    )


def pair_shapes(size: int, num_vars: int, lead: tuple = ()) -> StagedBatch:
    """A packed wave's abstract form (``jax.ShapeDtypeStruct`` leaves):
    what the compile checks and the IR audit lower the step programs with."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        host_pair(size, num_vars, lead),
    )


def column_views(batch) -> RecordBatch:
    """The ``RecordBatch`` of a packed wave's column views (last-axis
    slices of its two matrices, the 64-bit columns and ``v_num`` as the
    same bits under their own dtype); a ``RecordBatch`` passes through.

    Of numpy matrices the views are numpy VIEWS: what is written through
    them lands in the matrices (staging fills a wave this way), and a
    fetched emission is decoded without a copy of its columns. One
    exception: a TPU hands a fetched ``[B, W]`` matrix over column-major,
    where a 64-bit column's two planes are not adjacent in memory, so of
    a matrix that is not row-major the 64-bit columns are made from a
    row-major copy of the plane block (``14 x B`` words; read-only use).
    Of device arrays, or under a trace, the views are slices and bitcasts
    of the program: the step program calls this at trace time, so the host
    launches none."""
    if isinstance(batch, RecordBatch):
        return batch
    m32, m8 = batch.i32, batch.i8
    v = batch.num_vars
    n32, n64, nb = len(I32_COLS), len(I64_COLS), len(BOOL_COLS)
    str_at, num_at, i64_at = n32, n32 + v, n32 + 2 * v
    num_bits = m32[..., num_at:i64_at]
    planes = m32[..., i64_at : i64_at + 2 * n64]
    if isinstance(m32, np.ndarray):
        v_num = num_bits.view(np.float32)
        if planes.strides[-1] != planes.itemsize:
            planes = np.ascontiguousarray(planes)
        i64 = planes.view(np.int64)
        flags = m8[..., :nb].view(np.bool_)
    else:
        v_num = jax.lax.bitcast_convert_type(num_bits, jnp.float32)
        i64 = jax.lax.bitcast_convert_type(
            planes.reshape(planes.shape[:-1] + (n64, 2)), jnp.int64
        )
        flags = m8[..., :nb] != 0
    kw = {n: m32[..., j] for j, n in enumerate(I32_COLS)}
    kw.update({n: i64[..., j] for j, n in enumerate(I64_COLS)})
    kw.update({n: flags[..., j] for j, n in enumerate(BOOL_COLS)})
    return RecordBatch(
        v_vt=m8[..., nb:], v_num=v_num, v_str=m32[..., str_at:num_at], **kw
    )


def pack(batch: RecordBatch) -> StagedBatch:
    """The packed pair of a device ``RecordBatch`` (``column_views``'
    inverse, exact: bitcasts and a bool widening)."""
    i64 = jnp.stack([getattr(batch, n) for n in I64_COLS], axis=-1)
    return StagedBatch(
        i32=jnp.concatenate(
            [jnp.stack([getattr(batch, n) for n in I32_COLS], axis=-1),
             batch.v_str,
             jax.lax.bitcast_convert_type(batch.v_num, jnp.int32),
             jax.lax.bitcast_convert_type(i64, jnp.int32).reshape(
                 i64.shape[:-1] + (2 * len(I64_COLS),)
             )],
            axis=-1,
        ),
        i8=jnp.concatenate(
            [jnp.stack(
                [getattr(batch, n).astype(jnp.int8) for n in BOOL_COLS],
                axis=-1,
            ),
             batch.v_vt],
            axis=-1,
        ),
    )


def take_rows(batch: RecordBatch, idx: jax.Array) -> RecordBatch:
    """``batch[idx]`` (row take along axis 0) as TWO packed row gathers
    instead of one per field: the packed pair's i32 matrix (i32 scalars +
    v_str + bitcast v_num + i64 lo/hi planes) and its i8 matrix (bool
    flags + v_vt).
    A gather costs per-index issue, not bytes (PERF_NOTES round-4 cost
    model), so the naive per-field tree.map paid ~24 serial gathers where
    2 suffice. Bitcast/widen round-trips are exact — the result is
    bit-identical to ``jax.tree.map(lambda a: a[idx], batch)`` — and the
    takes route through the "emit" fused-gather family so the pallas
    mega-pass picks them up on TPU."""
    from zeebe_tpu.tpu import pallas_ops as pops

    packed = pack(batch)
    t32, t8 = pops.fused_gather_rows(
        [packed.i32, packed.i8],
        [pops.GatherOp(0, idx), pops.GatherOp(1, idx)],
        family="emit",
    )
    return column_views(StagedBatch(i32=t32, i8=t8))


def compact(batch: RecordBatch) -> RecordBatch:
    """Stable-reorder a batch so valid rows form a contiguous prefix
    (drive.enqueue's precondition). Used for batches whose valid rows are
    interleaved — e.g. the all_to_all exchange output, which groups rows by
    source shard. The reorder is ``take_rows``' two packed gathers."""
    order = jnp.argsort(~batch.valid, stable=True)
    return take_rows(batch, order)
