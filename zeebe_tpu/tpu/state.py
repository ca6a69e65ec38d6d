"""Engine state: struct-of-arrays tables in HBM.

The reference keeps per-partition state in heap object maps / RocksDB
(``broker-core/.../workflow/index/ElementInstanceIndex.java:25``,
``broker-core/.../job/state/JobInstanceStateController.java:28``); here
each state family is a fixed-capacity SoA table plus an HBM hash index
(``zeebe_tpu.tpu.hashmap``) mapping entity key → slot:

- element instances: lifecycle state, element, scope linkage, token counts,
  columnar payload (the ElementInstanceIndex analogue)
- jobs: the short job state machine + stored job record
- joins: in-flight parallel-gateway joins keyed by (scope, gateway), with
  flow-position-stamped payload merge (matches the oracle's flow-order merge)
- timers: due-date table scanned by the tick kernel
- job subscriptions: small table mutated host-side (credits, workers)
- key counters (reference KeyGenerator strides: workflow ≡1, job ≡2 mod 5)

Capacities are static (jit shapes); the host engine grows tables by
re-padding when occupancy crosses a threshold.

64-bit columns are 32-bit planes. A TPU has no 64-bit integers: XLA holds
an ``s64`` array as two ``u32`` arrays, splits it whole where it enters a
program and combines it whole where it leaves, whether the program touched
one row or none. So no leaf of table size is ``int64``. A table of C 64-bit
columns is ONE ``[rows, 2C] int32`` array, column c's low word at ``[:,
2c]`` and its high word at ``[:, 2c + 1]`` (``ei_i64``, ``job_i64``,
``msub_i64``); a single 64-bit column is the same with C = 1 (``[rows, 2]``:
``timer_key``, ``msg_deadline``, ...); a hash map's keys are two ``[T]
int32`` leaves (``hashmap.HashTable``). The chip lays a narrow 2D array
out with its ROWS minor (``s32[N, 6]{0,1:T(8,128)}``), so on the device
these are planes, at the bytes the int64 form took. Keys stay full 64-bit
values: the step gathers plane rows and makes ``int64`` of the ``[wave,
C]`` result (``pallas_ops.planes_to_i64``), and writes plane rows back.
Host code reads and builds them with ``host_i64`` / ``host_planes`` below,
and the snapshot on disk holds ``int64`` arrays as it always did
(``I64_TABLES`` / ``I64_COLUMNS`` name what is converted at that boundary;
``hashmap.host_keys`` / ``from_host`` convert a map's keys).
Only the scalar counters (``next_*_key``, ``free_*_pop`` / ``_push``) and
the 16-row worker-subscription table are ``int64``.

Write-path note: the step kernel commits each table GROUP (ei_i32 +
ei_i64 + ei_pay + free ring + index; likewise jobs and timers)
through ONE fused pallas mega-pass (``pallas_ops.fused_table_commit``) on
builds where the boot autotune picked fusion — the packed same-dtype
layout below is what makes those groups commit as whole-row writes.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from zeebe_tpu.engine import keyspace
from zeebe_tpu.tpu import hashmap

# packed column layouts: same-dtype scalar fields of a table live in one
# [cap, K] matrix so inserts/updates touching many fields are ONE row
# scatter instead of one scatter fusion per field
EI_ELEM, EI_STATE, EI_WF, EI_SCOPE, EI_TOKENS = 0, 1, 2, 3, 4
# pending interrupting-boundary continuation: the boundary element whose
# BOUNDARY_EVENT_OCCURRED fires when this instance's ELEMENT_TERMINATED
# processes (-1 none) — the oracle's _pending_boundary dict as a column
EI_PENDING_BD = 5
# 64-bit column numbers: column c of a plane table is words [:, 2c] (low)
# and [:, 2c + 1] (high)
EIL_KEY, EIL_IKEY, EIL_JOB_KEY = 0, 1, 2
JB_STATE, JB_ELEM, JB_WF, JB_TYPE, JB_RETRIES, JB_WORKER = 0, 1, 2, 3, 4, 5
JBL_KEY, JBL_IKEY, JBL_AIK, JBL_DEADLINE = 0, 1, 2, 3
# message subscriptions (message-partition role): i32 cols = (name id,
# correlation vt, correlation bits, workflow-instance partition);
# i64 cols = (workflowInstanceKey, activityInstanceKey)
MS_NAME, MS_CVT, MS_CBITS, MS_PART = 0, 1, 2, 3
MSL_WIKEY, MSL_AIK = 0, 1
# stored messages (TTL > 0): i32 cols = (name id, correlation vt,
# correlation bits, interned message id)
MG_NAME, MG_CVT, MG_CBITS, MG_MSGID = 0, 1, 2, 3


def corr_composite(name_id, corr_vt, corr_bits):
    """Injective i64 composite of (message name, correlation value) — the
    hashmap key for subscription and stored-message lookups. The oracle
    keys correlation on ``(message name, str(correlation key))``
    (interpreter ``StoredSubscription``); on device the value is an
    interned-string id or the f32 bit pattern, tagged by its value type so
    numeric and string keys can never alias. Non-negative by construction
    (intern ids are ≥ 0), so it never collides with the hashmap's
    EMPTY/TOMBSTONE sentinels."""
    import jax.numpy as _jnp

    return (
        (name_id.astype(_jnp.int64) << 35)
        | (corr_vt.astype(_jnp.int64) << 32)
        | corr_bits.astype(_jnp.uint32).astype(_jnp.int64)
    )

# the 64-bit leaves, by what the snapshot on disk holds for each: a [rows, C]
# int64 table, or a [rows] int64 column (their plane form is [rows, 2C] /
# [rows, 2] int32 — see the module docstring)
I64_TABLES = ("ei_i64", "job_i64", "msub_i64")
I64_COLUMNS = (
    "join_key", "timer_key", "timer_due", "timer_aik", "timer_instance_key",
    "msub_ckey", "msg_key", "msg_ckey", "msg_deadline",
)


# ---------------------------------------------------------------------------
# 64-bit values as 32-bit planes
# ---------------------------------------------------------------------------
# Host side (numpy; off the wave's path): the ONE way host code reads and
# builds 64-bit state.


def host_i64(planes, col=None) -> np.ndarray:
    """A pulled plane array as int64: ``[..., 2C] int32`` -> ``[..., C]``,
    or with ``col`` the one column ``[...]`` (only its two words cross the
    wire when ``planes`` is still on the device). A view, no arithmetic."""
    if col is not None:
        planes = planes[..., 2 * col : 2 * col + 2]
    out = np.ascontiguousarray(np.asarray(planes), np.int32).view(np.int64)
    return out[..., 0] if col is not None else out


def host_planes(vals64, column: bool = False) -> np.ndarray:
    """The inverse: ``[..., C] int64`` -> ``[..., 2C] int32``; with
    ``column`` a ``[...]`` int64 column -> its ``[..., 2]`` planes."""
    a = np.ascontiguousarray(np.asarray(vals64), np.int64)
    if column:
        a = a[..., None]
    return a.view(np.int32)


# Device side, table-sized and 32-bit throughout (the scans of the tick, the
# due probe and the rebuild; the step's few whole-column predicates).


def col_lo(planes, col=0):
    return planes[:, 2 * col]


def col_hi(planes, col=0):
    return planes[:, 2 * col + 1]


def col_planes(planes, col=0):
    """[rows, 2] planes of one column of a plane table."""
    return planes[:, 2 * col : 2 * col + 2]


def col_neg(planes, col=0):
    """``value < 0`` per row (free rows hold -1; a key is never negative)."""
    return col_hi(planes, col) < 0


def col_le(planes, col, x):
    """``value <= x`` per row, ``x`` an int64 scalar: signed on the high
    words, unsigned on the low."""
    xw = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.int64), jnp.int32)
    hi, lo = col_hi(planes, col), col_lo(planes, col).astype(jnp.uint32)
    return (hi < xw[1]) | ((hi == xw[1]) & (lo <= xw[0].astype(jnp.uint32)))


def col_eq(planes, col, vals64):
    """``[B, rows]``: row r's value == ``vals64[b]`` (``[B]`` int64)."""
    vw = jax.lax.bitcast_convert_type(vals64.astype(jnp.int64), jnp.int32)
    return (col_lo(planes, col)[None, :] == vw[:, 0][:, None]) & (
        col_hi(planes, col)[None, :] == vw[:, 1][:, None]
    )


def index_bucket(planes, col, icap: int):
    """``(key // 5) & (icap - 1)`` per row — the direct-mapped index's
    bucket (see ``ei_index``) — of a table's own non-negative key column,
    by long division on 16-bit digits: no 64-bit arithmetic at table size.
    Equal to the int64 expression the step uses on a wave's keys."""
    assert icap & (icap - 1) == 0 and icap <= 1 << 32
    u = jnp.uint32
    lo = col_lo(planes, col).astype(u)
    r = col_hi(planes, col).astype(u) % u(5)
    t1 = (r << u(16)) | (lo >> u(16))
    t2 = ((t1 % u(5)) << u(16)) | (lo & u(0xFFFF))
    low32 = ((t1 // u(5)) << u(16)) | (t2 // u(5))
    return (low32 & u(icap - 1)).astype(jnp.int32)


_STATE_FIELDS = [
    "ei_i32", "ei_i64", "ei_pay", "ei_map", "ei_index",
    "free_ei", "free_ei_pop", "free_ei_push",
    "job_i32", "job_i64", "job_pay", "job_map", "job_index",
    "free_job", "free_job_pop", "free_job_push",
    "join_key", "join_nin", "join_arrived", "join_pay",
    "join_pos_stamp", "join_map",
    "timer_key", "timer_due", "timer_aik", "timer_instance_key", "timer_elem",
    "timer_wf", "timer_map",
    "msub_ckey", "msub_i32", "msub_i64", "msub_map",
    "msg_key", "msg_ckey", "msg_i32", "msg_deadline", "msg_pay", "msg_map",
    "sub_key", "sub_type", "sub_worker", "sub_credits", "sub_timeout", "sub_valid",
    "sub_rr",
    "next_wf_key", "next_job_key",
]


# ---------------------------------------------------------------------------
# packed payload columns
# ---------------------------------------------------------------------------
# A table's payload (per-variable value type, interned string id, numeric
# value) is ONE [cap, 3V] i32 matrix: cols [0,V) = value types, [V,2V) =
# string ids, [2V,3V) = float32 numbers bitcast to i32. XLA lowers general
# scatters to SERIAL per-index loops on TPU, so a payload write must be one
# scatter, not three — and float32 (not 64) halves the emulated-64-bit op
# cost throughout the kernel. A number that f32 does not hold exactly never
# reaches the device as a number: ``batch.payload_to_columns`` holds it on
# the host (type ``VT_HELD``, a handle in the string-id column), like a map
# or a list.


def pack_payload(vt, sid, num):
    """[..., V] (vt int, sid i32, num f32) → [..., 3V] i32."""
    return jnp.concatenate(
        [
            vt.astype(jnp.int32),
            sid.astype(jnp.int32),
            jax.lax.bitcast_convert_type(num.astype(jnp.float32), jnp.int32),
        ],
        axis=-1,
    )


def unpack_payload(pay):
    """[..., 3V] i32 → (vt i32, sid i32, num f32), each [..., V]."""
    v = pay.shape[-1] // 3
    vt = pay[..., :v]
    sid = pay[..., v : 2 * v]
    num = jax.lax.bitcast_convert_type(pay[..., 2 * v : 3 * v], jnp.float32)
    return vt, sid, num


@partial(jax.tree_util.register_dataclass, data_fields=_STATE_FIELDS, meta_fields=[])
@dataclasses.dataclass
class EngineState:
    # element instances [N] (ElementInstanceIndex analogue), packed:
    # ei_i32 cols = (elem, lifecycle state[-1 free], wf slot, scope slot,
    # token count, pending boundary elem[-1 none]);
    # ei_i64 64-bit cols = (key[-1 free], workflowInstanceKey, jobKey)
    ei_i32: jax.Array          # [N, 6] i32
    ei_i64: jax.Array          # [N, 2*3] i32 planes
    ei_pay: jax.Array          # [N, 3V] i32 packed payload (vt | sid | f32 bits)
    ei_map: hashmap.HashTable  # key → slot (FALLBACK; see ei_index)
    # Direct-mapped key → slot accelerator: keys are allocated
    # sequentially with stride 5 by this engine (keyspace residue
    # classes), so ``index[(key // 5) & (cap-1)]`` is collision-free
    # within any window of ``5 * cap`` consecutive keys. A hit
    # is verified against the row's own key column; misses (an old live
    # instance whose congruent-mod-cap successor overwrote the entry)
    # fall back to the hashmap probe, which is rebuilt from live rows at
    # wave boundaries rather than maintained per round — the per-round
    # probe/insert/delete machinery was the largest profiled cost class.
    ei_index: jax.Array        # [8N] i32 slot, -1 empty
    # free-slot ring (replaces the per-round full-table free scan): pop
    # cursor hands out ring[(pop+rank) % N], frees append at push; both
    # cursors are monotonic i64, free count = push - pop. Rebuilt with the
    # lookup state (host-side frees — demotions — re-enter the ring then).
    free_ei: jax.Array         # [N] i32 ring of free slots
    free_ei_pop: jax.Array     # i64 scalar
    free_ei_push: jax.Array    # i64 scalar

    # jobs [M], packed: job_i32 cols = (state[-1 free], elem, wf, type,
    # retries, worker); job_i64 cols = (key[-1 free], instanceKey, aik,
    # deadline)
    job_i32: jax.Array         # [M, 6] i32
    job_i64: jax.Array         # [M, 2*4] i32 planes
    job_pay: jax.Array         # [M, 3V] i32 packed payload
    job_map: hashmap.HashTable  # fallback (see ei_index)
    job_index: jax.Array       # [8M] i32 slot, -1 empty
    free_job: jax.Array        # [M] i32
    free_job_pop: jax.Array    # i64
    free_job_push: jax.Array   # i64

    # parallel joins [J]
    join_key: jax.Array        # [J, 2] planes: composite (scope_key<<10 | gateway), -1 free
    join_nin: jax.Array        # i32
    join_arrived: jax.Array    # [J, F_in] bool
    join_pay: jax.Array        # [J, 3V] i32 packed merged payload
    join_pos_stamp: jax.Array  # [J, V] i32: flow position that wrote each var
    join_map: hashmap.HashTable

    # timers [TM]
    timer_key: jax.Array       # [TM, 2] planes, -1 free
    timer_due: jax.Array       # [TM, 2] planes
    timer_aik: jax.Array       # [TM, 2] planes
    timer_instance_key: jax.Array  # [TM, 2] planes
    timer_elem: jax.Array      # i32 handler element
    timer_wf: jax.Array        # i32
    timer_map: hashmap.HashTable

    # message subscriptions [MS] (this partition as MESSAGE partition —
    # reference broker-core message correlation state; device redesign of
    # the oracle's StoredSubscription list). One open subscription per
    # (name, correlation) composite; a second OPEN on a live composite is
    # a loud overflow (kernel stat), not silent data loss.
    msub_ckey: jax.Array       # [MS, 2] planes: corr_composite, -1 free
    msub_i32: jax.Array        # [MS, 4] (name, cvt, cbits, wi partition)
    msub_i64: jax.Array        # [MS, 2*2] planes (workflowInstanceKey, activityInstanceKey)
    msub_map: hashmap.HashTable  # composite → slot

    # stored messages with TTL [MG] (oracle StoredMessage dict)
    msg_key: jax.Array         # [MG, 2] planes: message key, -1 free
    msg_ckey: jax.Array        # [MG, 2] planes: corr_composite
    msg_i32: jax.Array         # [MG, 4] (name, cvt, cbits, interned msg id)
    msg_deadline: jax.Array    # [MG, 2] planes: expiry timestamp
    msg_pay: jax.Array         # [MG, 3V] packed payload
    msg_map: hashmap.HashTable  # composite → slot

    # job worker subscriptions [S] (host-managed)
    sub_key: jax.Array         # i64 subscriber key
    sub_type: jax.Array        # i32 interned job type
    sub_worker: jax.Array      # i32 interned worker name
    sub_credits: jax.Array     # i32
    sub_timeout: jax.Array     # i64
    sub_valid: jax.Array       # bool
    # i32 round-robin cursor (global, like the oracle's _job_rr_cursor);
    # persisted by engine.device_backlog_activations across calls and
    # across snapshot/restore so drain fairness survives ticks and leaders
    sub_rr: jax.Array

    # key counters (i64 scalars)
    next_wf_key: jax.Array
    next_job_key: jax.Array

    # unpacked read views (lazy column slices — free inside jit; host code
    # and the kernel's read paths keep the original field names)
    @property
    def ei_elem(self): return self.ei_i32[:, EI_ELEM]
    @property
    def ei_state(self): return self.ei_i32[:, EI_STATE]
    @property
    def ei_wf(self): return self.ei_i32[:, EI_WF]
    @property
    def ei_scope_slot(self): return self.ei_i32[:, EI_SCOPE]
    @property
    def ei_tokens(self): return self.ei_i32[:, EI_TOKENS]
    @property
    def job_state(self): return self.job_i32[:, JB_STATE]
    @property
    def job_elem(self): return self.job_i32[:, JB_ELEM]
    @property
    def job_wf(self): return self.job_i32[:, JB_WF]
    @property
    def job_type(self): return self.job_i32[:, JB_TYPE]
    @property
    def job_retries(self): return self.job_i32[:, JB_RETRIES]
    @property
    def job_worker(self): return self.job_i32[:, JB_WORKER]

    @property
    def capacity(self) -> int:
        return self.ei_i32.shape[0]

    @property
    def num_vars(self) -> int:
        return self.ei_pay.shape[1] // 3


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def make_state(
    capacity: int = 1 << 12,
    num_vars: int = 8,
    job_capacity: int = 0,
    join_capacity: int = 0,
    timer_capacity: int = 0,
    sub_capacity: int = 64,
    max_join_in: int = 4,
    msub_capacity: int = 0,
    msg_capacity: int = 0,
) -> EngineState:
    n = capacity
    m = job_capacity or capacity
    j = join_capacity or max(capacity // 8, 256)
    tm = timer_capacity or max(capacity // 8, 256)
    ms = msub_capacity or max(capacity // 2, 256)
    mg = msg_capacity or max(capacity // 4, 256)
    v = num_vars
    i64, i32 = jnp.int64, jnp.int32

    def free64(rows, cols=1):
        # -1 in every 64-bit column: both words -1
        return jnp.full((rows, 2 * cols), -1, i32)

    return EngineState(
        # ei_i32: elem=0, state=-1, wf=0, scope=-1, tokens=0, pending_bd=-1
        ei_i32=jnp.tile(jnp.array([[0, -1, 0, -1, 0, -1]], i32), (n, 1)),
        ei_i64=free64(n, 3),
        ei_pay=jnp.zeros((n, 3 * v), i32),
        ei_map=hashmap.make(_pow2(8 * n)),
        ei_index=jnp.full((_pow2(8 * n),), -1, i32),
        free_ei=jnp.arange(n, dtype=i32),
        free_ei_pop=jnp.zeros((), i64),
        free_ei_push=jnp.asarray(n, i64),
        # job_i32: state=-1, elem/wf/type/retries/worker=0
        job_i32=jnp.tile(jnp.array([[-1, 0, 0, 0, 0, 0]], i32), (m, 1)),
        job_i64=free64(m, 4),
        job_pay=jnp.zeros((m, 3 * v), i32),
        job_map=hashmap.make(_pow2(8 * m)),
        job_index=jnp.full((_pow2(8 * m),), -1, i32),
        free_job=jnp.arange(m, dtype=i32),
        free_job_pop=jnp.zeros((), i64),
        free_job_push=jnp.asarray(m, i64),
        join_key=free64(j),
        join_nin=jnp.zeros((j,), i32),
        join_arrived=jnp.zeros((j, max_join_in), bool),
        join_pay=jnp.zeros((j, 3 * v), i32),
        join_pos_stamp=jnp.full((j, v), -1, i32),
        join_map=hashmap.make(_pow2(4 * j)),
        timer_key=free64(tm),
        timer_due=free64(tm),
        timer_aik=free64(tm),
        timer_instance_key=free64(tm),
        timer_elem=jnp.zeros((tm,), i32),
        timer_wf=jnp.zeros((tm,), i32),
        timer_map=hashmap.make(_pow2(4 * tm)),
        msub_ckey=free64(ms),
        msub_i32=jnp.zeros((ms, 4), i32),
        msub_i64=free64(ms, 2),
        msub_map=hashmap.make(_pow2(4 * ms)),
        msg_key=free64(mg),
        msg_ckey=free64(mg),
        msg_i32=jnp.zeros((mg, 4), i32),
        msg_deadline=free64(mg),
        msg_pay=jnp.zeros((mg, 3 * v), i32),
        msg_map=hashmap.make(_pow2(4 * mg)),
        sub_key=jnp.full((sub_capacity,), -1, i64),
        sub_type=jnp.zeros((sub_capacity,), i32),
        sub_worker=jnp.zeros((sub_capacity,), i32),
        sub_credits=jnp.zeros((sub_capacity,), i32),
        sub_timeout=jnp.zeros((sub_capacity,), i64),
        sub_valid=jnp.zeros((sub_capacity,), bool),
        sub_rr=jnp.zeros((), i32),
        next_wf_key=jnp.array(keyspace.WF_OFFSET, i64),
        next_job_key=jnp.array(keyspace.JOB_OFFSET, i64),
    )


def rebuild_lookup_state(state: EngineState) -> EngineState:
    """Recompute the key→slot indexes and fallback hashmaps from live
    table rows.

    Run at wave boundaries (drive entry), at snapshot restore, and on the
    engine's key-advance cadence — NOT per round: in-round lookups resolve
    through the direct-mapped index (rows created this wave are always
    index-hits, the index is collision-free within a window of 8N
    consecutive keys), and stale map/index entries are harmless because
    every lookup verifies the row's own key column. The invariant this
    maintains: the fallback map covers every instance live at the last
    rebuild."""
    import dataclasses as _dc

    import jax.numpy as _jnp

    n = state.ei_i32.shape[0]
    m = state.job_i32.shape[0]
    icap = state.ei_index.shape[0]
    jcap = state.job_index.shape[0]
    ei_live = state.ei_state >= 0
    job_live = state.job_state >= 0
    ei_idx = (
        _jnp.full((icap,), -1, _jnp.int32)
        .at[_jnp.where(ei_live, index_bucket(state.ei_i64, EIL_KEY, icap), icap)]
        .set(_jnp.arange(n, dtype=_jnp.int32), mode="drop")
    )
    job_idx = (
        _jnp.full((jcap,), -1, _jnp.int32)
        .at[_jnp.where(job_live, index_bucket(state.job_i64, JBL_KEY, jcap), jcap)]
        .set(_jnp.arange(m, dtype=_jnp.int32), mode="drop")
    )
    # the maps take a table's own key column as plane rows
    ei_map, _ = hashmap.rebuild_from(
        state.ei_map.size, col_planes(state.ei_i64, EIL_KEY),
        _jnp.arange(n, dtype=_jnp.int32), ei_live,
    )
    job_map, _ = hashmap.rebuild_from(
        state.job_map.size, col_planes(state.job_i64, JBL_KEY),
        _jnp.arange(m, dtype=_jnp.int32), job_live,
    )
    ei_free_mask = ~ei_live
    job_free_mask = ~job_live
    ei_rank = _jnp.cumsum(ei_free_mask.astype(_jnp.int32)) - ei_free_mask
    job_rank = _jnp.cumsum(job_free_mask.astype(_jnp.int32)) - job_free_mask
    free_ei = (
        _jnp.full((n,), n, _jnp.int32)
        .at[_jnp.where(ei_free_mask, ei_rank, n)]
        .set(_jnp.arange(n, dtype=_jnp.int32), mode="drop")
    )
    free_job = (
        _jnp.full((m,), m, _jnp.int32)
        .at[_jnp.where(job_free_mask, job_rank, m)]
        .set(_jnp.arange(m, dtype=_jnp.int32), mode="drop")
    )
    # the remaining maps are maintained in-round (tombstone churn);
    # rebuilding them here compacts the churn away on the same cadence
    def _iota(a):
        return _jnp.arange(a.shape[0], dtype=_jnp.int32)

    join_map, _ = hashmap.rebuild_from(
        state.join_map.size, state.join_key,
        _iota(state.join_key), ~col_neg(state.join_key),
    )
    timer_map, _ = hashmap.rebuild_from(
        state.timer_map.size, state.timer_key,
        _iota(state.timer_key), ~col_neg(state.timer_key),
    )
    msub_map, _ = hashmap.rebuild_from(
        state.msub_map.size, state.msub_ckey,
        _iota(state.msub_ckey), ~col_neg(state.msub_ckey),
    )
    msg_map, _ = hashmap.rebuild_from(
        state.msg_map.size, state.msg_ckey,
        _iota(state.msg_ckey), ~col_neg(state.msg_key),
    )
    return _dc.replace(
        state, ei_index=ei_idx, job_index=job_idx,
        ei_map=ei_map, job_map=job_map,
        join_map=join_map, timer_map=timer_map,
        msub_map=msub_map, msg_map=msg_map,
        free_ei=free_ei,
        # zeros OF the old cursors, not fresh ones: a leaf made from
        # nothing is uncommitted where the rest of the state is committed,
        # which is another signature of the step program — the first wave
        # after a rebuild on the serving path would compile it again
        free_ei_pop=_jnp.zeros_like(state.free_ei_pop),
        free_ei_push=_jnp.sum(ei_free_mask, dtype=_jnp.int64),
        free_job=free_job,
        free_job_pop=_jnp.zeros_like(state.free_job_pop),
        free_job_push=_jnp.sum(job_free_mask, dtype=_jnp.int64),
    )

