"""THE step kernel: one jit'd application of all stream processors to a
record batch.

This replaces the reference's per-record hot loop
(``logstreams/.../processor/StreamProcessorController.java:296-399`` driving
``BpmnStepProcessor.processRecord`` and the job/incident processors) with a
single SIMD pass: every record in the batch is routed, guarded, and stepped
in parallel; follow-up records are produced into fixed emission slots and
compacted; state lands via deterministic scatters (conflicts resolved by
batch rank or flow position, never by scheduling). Feeding emissions back
as the next batch reproduces the oracle's serial log exactly — a batch is a
contiguous log range, and slot order (record-major, then emission slot)
equals the oracle's append order.

Kernel phases:
  A. hash lookups (record key / scope key / job aik → table slots)
  B. routing + step guards (BpmnStepProcessor.java:127-151 semantics)
  C. masked per-step compute: payload mappings, condition programs,
     parallel-join arrival merge, job state machine, timers
  D. key assignment (strided counters + prefix sums — KeyGenerator parity)
  E. emissions → compaction; state scatters; table insert/delete
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from zeebe_tpu.engine import keyspace
from zeebe_tpu.models.transform.steps import BpmnStep as BS
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import (
    JobIntent as JI,
    MessageIntent as MI,
    MessageSubscriptionIntent as MS,
    TimerIntent as TI,
    WorkflowInstanceIntent as WI,
    WorkflowInstanceSubscriptionIntent as WS,
)
from zeebe_tpu.tpu import batch as rb
from zeebe_tpu.tpu import graph as graph_mod
from zeebe_tpu.tpu import hashmap
from zeebe_tpu.tpu import jit_registry
from zeebe_tpu.tpu import pallas_ops as pops
from zeebe_tpu.tpu.batch import RecordBatch
from zeebe_tpu.tpu.conditions import ERROR as TRI_ERROR
from zeebe_tpu.tpu.conditions import TRUE as TRI_TRUE
from zeebe_tpu.tpu.conditions import (
    VT_ABSENT,
    VT_BOOL as COND_VT_BOOL,
    VT_NUM as COND_VT_NUM,
    VT_STR as COND_VT_STR,
    eval_programs,
)
from zeebe_tpu.tpu.graph import DeviceGraph
from zeebe_tpu.tpu.state import (
    EngineState,
    col_eq, col_hi, col_le, col_lo, col_neg,
    corr_composite,
    pack_payload, unpack_payload,
    EI_ELEM, EI_STATE, EI_WF, EI_SCOPE, EI_TOKENS, EI_PENDING_BD,
    EIL_KEY, EIL_IKEY, EIL_JOB_KEY,
    JB_STATE, JB_ELEM, JB_WF, JB_TYPE, JB_RETRIES, JB_WORKER,
    JBL_KEY, JBL_IKEY, JBL_AIK, JBL_DEADLINE,
    MS_NAME, MS_CVT, MS_CBITS, MS_PART, MSL_WIKEY, MSL_AIK,
    MG_NAME, MG_CVT, MG_CBITS, MG_MSGID,
)

RT_EVENT = int(RecordType.EVENT)
RT_CMD = int(RecordType.COMMAND)
RT_REJ = int(RecordType.COMMAND_REJECTION)
VT_WI = int(ValueType.WORKFLOW_INSTANCE)
VT_JOB = int(ValueType.JOB)
VT_INCIDENT = int(ValueType.INCIDENT)
VT_TIMER = int(ValueType.TIMER)
VT_MSG = int(ValueType.MESSAGE)
VT_MSUB = int(ValueType.MESSAGE_SUBSCRIPTION)
VT_WISUB = int(ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION)

_KEY_STEP = keyspace.STEP_SIZE


def _later(n):
    """[n, n] bool: column j comes after row i (the strict upper triangle;
    its transpose is "comes before"). From an i32 iota: ``jnp.triu`` builds
    its own from an int64 one under x64, wave x wave elements of it."""
    i = jnp.arange(n, dtype=jnp.int32)
    return i[:, None] < i[None, :]


def _mxu_cumsum_i32(x):
    """Inclusive scan of small-int vectors via triangular matmuls on the
    MXU. XLA's TPU cumsum lowering (reduce-window) serializes badly at
    these lengths; two tiny matmuls are ~free. Exact while the running sum
    stays below 2^24 (batch sizes here are ≤ 2^20 of 0/1 counts) — which
    requires full f32 accumulation: the TPU matmul default feeds the MXU
    bf16 inputs (8 mantissa bits), so Precision.HIGHEST is load-bearing,
    not a nicety (row totals above 256 would round)."""
    n = x.shape[0]
    tile = 128
    if n % tile != 0:  # fall back off the fast path for odd sizes
        return jnp.cumsum(x)
    rows = n // tile
    hi = jax.lax.Precision.HIGHEST
    xf = x.astype(jnp.float32).reshape(rows, tile)
    upper = (~_later(tile).T).astype(jnp.float32)  # j >= i
    lower_strict = _later(rows).T.astype(jnp.float32)  # j < i
    within = jnp.matmul(xf, upper, precision=hi)  # [rows, tile] row-wise scan
    row_tot = within[:, -1]                       # [rows]
    row_off = jnp.matmul(lower_strict, row_tot, precision=hi)
    return (within + row_off[:, None]).reshape(n).astype(x.dtype)


def _excl_cumsum(x):
    c = _mxu_cumsum_i32(x)
    return c - x


def _first_true_indices(avail, k):
    """Indices of the first ``k`` True entries of ``avail`` (padded with
    ``len(avail)``) — the free-slot scan. ``jnp.nonzero`` lowers to a slow
    serialized cumsum+scatter on TPU; this uses the MXU scan + one bounded
    scatter."""
    cap = avail.shape[0]
    rank = _excl_cumsum(avail.astype(jnp.int32))
    tgt = jnp.where(avail & (rank < k), rank, k)
    return (
        jnp.full((k,), cap, jnp.int32)
        .at[tgt]
        .set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
    )


def _last_writer(slots, mask, size):
    """True for the highest-batch-rank writer per target slot (deterministic
    conflict resolution for duplicate scatters). Small batches (the serving
    wave) use an O(B²) comparison triangle — no gather/scatter pair per
    call site; large drive-loop batches keep the scatter-max + read-back
    form (same split as ``_first_per_key``)."""
    n = slots.shape[0]
    if n <= 2048:
        later_same = (
            (slots[:, None] == slots[None, :])
            & mask[None, :]
            & _later(n)
        )
        return mask & ~jnp.any(later_same, axis=1)
    rank = jnp.arange(n, dtype=jnp.int32)
    tgt = jnp.where(mask, slots, size)
    best = jnp.full((size + 1,), -1, jnp.int32).at[tgt].max(
        jnp.where(mask, rank, -1), mode="drop"
    )
    return mask & (best[jnp.clip(tgt, 0, size)] == rank)


def _first_per_key(keys, mask):
    """[B] bool: row i is the FIRST masked row carrying its key (row order
    = log order; intra-batch duplicate commands on one entity serialize
    to first-wins, matching the oracle's sequential pop-then-no-op).
    Small batches (the serving wave) use an O(B²) comparison triangle —
    cheap, no extra gathers/scatters; large drive-loop batches switch to
    a stable two-key sort to avoid the B² blowup."""
    b = keys.shape[0]
    if b <= 2048:
        earlier_same = (
            (keys[:, None] == keys[None, :])
            & mask[None, :]
            & _later(b).T
        )
        return ~jnp.any(earlier_same, axis=1)
    idx = jnp.arange(b, dtype=jnp.int64)
    # unmasked rows get unique sentinels so they never collide
    k = jnp.where(mask, keys, jnp.int64(-1) - idx)
    k_sorted, idx_sorted = jax.lax.sort((k, idx), num_keys=2)
    first_sorted = jnp.concatenate(
        [jnp.ones((1,), bool), k_sorted[1:] != k_sorted[:-1]]
    )
    return jnp.zeros((b,), bool).at[idx_sorted].set(first_sorted)


def _col64(rows, col=0):
    """One 64-bit column of gathered plane rows: ``[B, 2C]`` i32 → ``[B]``
    i64. The state's tables hold 64-bit columns as 32-bit planes
    (``tpu/state.py``); int64 is made here, of a wave's rows only."""
    return pops.planes_to_i64(rows[:, 2 * col : 2 * col + 2])[:, 0]


def _indexed_lookup_multi(lookups):
    """N parallel key → (found, slot) resolutions via the direct-mapped
    indexes with hashmap fallback; both paths verify against the table's
    own key column, so stale index/map entries (deleted rows, reused
    slots) resolve to not-found without any per-round index maintenance.

    Each lookup is ``(index, key_col, fallback_map, keys, want, cap)``,
    ``key_col`` a ``(plane table, 64-bit column)`` pair; returns
    ``[(found, slot), ...]`` in input order. The index probes and
    the two key-column verifies run through ``pops.fused_gather_rows``,
    so the N lookups share one gather per stage instead of issuing 3
    gathers apiece."""
    # keys are stride-5 (keyspace: one residue class per entity family),
    # so indexing on key // 5 packs them densely — the collision-free
    # window is icap * 5 consecutive keys, not icap (a parallel-split /
    # multi-instance wave can allocate hundreds of thousands of keys;
    # indexing on the raw key wrapped the window within ONE wave and
    # silently dropped ~4% of fork-join completions at bench scale)
    cands = pops.fused_gather_rows(
        [index for index, *_ in lookups],
        [
            pops.GatherOp(
                i, ((keys // 5) & (index.shape[0] - 1)).astype(jnp.int32)
            )
            for i, (index, _kc, _fb, keys, _w, _c) in enumerate(lookups)
        ],
    )
    cand_clips = [
        jnp.clip(cand, 0, lk[5] - 1) for cand, lk in zip(cands, lookups)
    ]
    key_tables = [kc[0] for _i, kc, *_ in lookups]

    def key_reads(slots):
        rows = pops.fused_gather_rows(
            key_tables, [pops.GatherOp(i, sl) for i, sl in enumerate(slots)]
        )
        return [_col64(r, lk[1][1]) for r, lk in zip(rows, lookups)]

    kc_hit = key_reads(cand_clips)
    hits = [
        lk[4] & (cand >= 0) & (kc == lk[3])
        for lk, cand, kc in zip(lookups, cands, kc_hit)
    ]
    misses = [lk[4] & ~hit for lk, hit in zip(lookups, hits)]
    # fallback probe for clobbered index entries and genuinely absent
    # keys; with no misses the probe's while_loop exits after its first
    # condition check (cheaper than a lax.cond, whose operand copies cost
    # more than the empty loop — measured)
    fbs = [
        pops.lookup(lk[2], lk[3], miss) for lk, miss in zip(lookups, misses)
    ]
    fb_clips = [
        jnp.clip(fb_slot, 0, lk[5] - 1)
        for (_f, fb_slot), lk in zip(fbs, lookups)
    ]
    kc_fb = key_reads(fb_clips)
    out = []
    for lk, hit, miss, (fb_found, _s), fb_clip, kc, cand_clip in zip(
        lookups, hits, misses, fbs, fb_clips, kc_fb, cand_clips
    ):
        fb_ok = miss & fb_found & (kc == lk[3])
        out.append((hit | fb_ok, jnp.where(hit, cand_clip, fb_clip)))
    return out


def _indexed_lookup(index, key_col, fallback_map, keys, want, cap):
    """Single-lookup form of ``_indexed_lookup_multi`` (tests, tools)."""
    return _indexed_lookup_multi(
        [(index, key_col, fallback_map, keys, want, cap)]
    )[0]


def _apply_mappings(graph, wf, elem, src_vt, src_num, src_sid, is_input):
    """Vectorized MappingProcessor.extract (input) source selection.

    Returns (dst_from [B, V] source column per target column or -1,
    has_mappings [B], root [B], err [B] — any listed source absent).
    """
    b = wf.shape[0]
    v = src_vt.shape[1]
    if is_input:
        m_src, m_dst, m_n, m_root = (
            graph.in_map_src, graph.in_map_dst, graph.in_map_n, graph.in_root
        )
    else:
        m_src, m_dst, m_n, m_root = (
            graph.out_map_src, graph.out_map_dst, graph.out_map_n, graph.out_root
        )
    k_max = m_src.shape[2]
    rows = jnp.arange(b, dtype=jnp.int32)
    dst_from = jnp.full((b, v), -1, jnp.int32)
    err = jnp.zeros((b,), bool)
    for k in range(k_max):
        src = m_src[wf, elem, k]
        dst = m_dst[wf, elem, k]
        active = src >= 0
        src_c = jnp.clip(src, 0, v - 1)
        err = err | (active & (src_vt[rows, src_c] == VT_ABSENT))
        dst_c = jnp.where(active, dst, v)
        dst_from = dst_from.at[rows, dst_c].set(src, mode="drop")
    has = m_n[wf, elem] > 0
    root = m_root[wf, elem]
    return dst_from, has, root, err


def _select_by_map(dst_from, vt, num, sid):
    """payload'[v] = payload[dst_from[v]] (absent where dst_from = -1)."""
    c = jnp.clip(dst_from, 0, vt.shape[1] - 1)
    got = dst_from >= 0
    take = lambda a, fill: jnp.where(got, jnp.take_along_axis(a, c, axis=1), fill)  # noqa: E731
    return (
        take(vt, jnp.int8(VT_ABSENT)),
        take(num, 0.0),
        take(sid, 0),
    )


def scope_to_local(ei_i32, shard_index, local_rows):
    """Translate the EI_SCOPE parent-slot column from the GLOBAL row space
    into one shard's LOCAL row space (residency-routed sharded state).

    At rest the sharded tables store parent slots globally so host readers
    (``_demote_instance``'s scope-tree walk, snapshots) see one coherent
    space. The routed step runs the kernel on a local row block, whose
    slot arithmetic is local — so in-block parents shift down by the block
    base, sentinels (< 0) pass through, and out-of-block parents become the
    POISON slot ``local_rows`` (one past the last local row: never equal
    to any real slot, so parent-slot comparisons can't alias). A gather
    through the POISON slot clamps to the LAST local row — JAX clamps
    out-of-range indices to the valid edge, not to row 0 — so the read
    itself returns real (wrong-parent) data; it is harmless only because
    the routing policy routes exclusively instances wholly resident in
    the block: poisoned parents belong to instances the wave does not
    step, their lanes stay masked, and :func:`scope_to_global` restores
    the original global slot afterwards."""
    base = shard_index * local_rows
    g = ei_i32[:, EI_SCOPE]
    local = jnp.where(
        g < 0,
        g,
        jnp.where(
            (g >= base) & (g < base + local_rows), g - base, local_rows
        ),
    )
    return ei_i32.at[:, EI_SCOPE].set(local.astype(ei_i32.dtype))


def scope_to_global(ei_i32, prev_global_scope, shard_index, local_rows):
    """Inverse of :func:`scope_to_local` after the kernel ran on the local
    block: local slots shift up by the block base, sentinels pass through,
    and rows still carrying the POISON slot were untouched by the wave —
    their original global parent (``prev_global_scope``) is restored."""
    base = shard_index * local_rows
    loc = ei_i32[:, EI_SCOPE]
    back = jnp.where(
        loc < 0,
        loc,
        jnp.where(loc == local_rows, prev_global_scope, loc + base),
    )
    return ei_i32.at[:, EI_SCOPE].set(back.astype(ei_i32.dtype))


# the entries of a step's stats vector (i32), in order
STATS = ("processed", "stepped", "emitted", "completed_roots", "overflow")
# appended to the vector where the graph has messages: the message tables'
# outcomes of the step, for the engine's counters
MESSAGE_STATS = ("message_publishes", "messages_stored", "messages_deleted",
                 "message_correlated_at_open", "message_correlated_at_publish")


def stats_of(stats) -> dict:
    """A step's stats vector by name (device scalars or, of a fetched
    vector, numpy ones); ``overflow`` is 0 or 1."""
    return {name: stats[..., i] for i, name in enumerate(STATS)}


def step_kernel(
    graph: DeviceGraph, state: EngineState, batch, now,
    synthetic_workers: bool = False, partition_id=0,
) -> Tuple[EngineState, rb.StagedBatch, jax.Array]:
    """Process one committed-record batch; returns (state', emissions, stats).

    Emissions are compacted in oracle append order and leave as ONE packed
    pair (``rb.StagedBatch``; ``rb.column_views`` gives the columns):
    ``src`` links each emission to its source row (host assigns
    positions/responses), the first ``emitted`` rows are valid. ``stats``
    is one i32 vector, ``STATS`` its entries.

    ``synthetic_workers`` (static, bench-only): every ACTIVATED push also
    emits an instant COMPLETE command — the worker round-trip of
    ``gateway/.../impl/subscription/job/JobSubscriber.java:51`` without
    leaving the device.

    ``batch`` is a ``RecordBatch`` or a packed wave (``rb.StagedBatch``,
    what the serving engine transfers, and what a step emits): the column
    views of the latter are taken here, inside the program.
    """
    batch = rb.column_views(batch)
    b = batch.size
    v = state.num_vars
    e_w = graph.emit_width
    n_cap = state.capacity
    m_cap = state.job_i32.shape[0]
    j_cap = state.join_key.shape[0]
    t_cap = state.timer_key.shape[0]
    s_cap = state.sub_key.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)

    valid = batch.valid
    rt, vt_, it = batch.rtype, batch.vtype, batch.intent
    wf_c = jnp.clip(batch.wf, 0, graph.elem_type.shape[0] - 1)
    el_c = jnp.clip(batch.elem, 0, graph.elem_type.shape[1] - 1)
    # hot-path per-element graph reads (round 9a): the meta scalar row,
    # the step table, the conditioned/parallel flow fans and the timer
    # duration all index by the same (workflow, element) pair — flattened
    # to one [W*E, K] i32 table (timer_dur rides as two bitcast planes)
    # they collapse into ONE row gather instead of five
    n_elems = graph.elem_type.shape[1]
    n_intents = graph.step_table.shape[2]
    cond_fan = graph.cond_flows.shape[2]
    fork_fan = graph.out_flows.shape[2]
    em_cols = graph.elem_meta.shape[2]
    with jax.named_scope("zb_gather"):
        g_flat = jnp.concatenate(
            [
                graph.elem_meta.reshape(-1, em_cols),
                graph.step_table.reshape(-1, n_intents),
                graph.cond_flows.reshape(-1, cond_fan),
                graph.cond_prog.reshape(-1, cond_fan),
                graph.out_flows.reshape(-1, fork_fan),
                pops.vec64_to_planes(graph.timer_dur.reshape(-1)),
            ],
            axis=1,
        )
        (g_row,) = pops.fused_gather_rows(
            [g_flat], [pops.GatherOp(0, wf_c * n_elems + el_c)]
        )
    _go = 0
    emeta = g_row[:, _go : _go + em_cols]; _go += em_cols
    step_row = g_row[:, _go : _go + n_intents]; _go += n_intents
    cflow = g_row[:, _go : _go + cond_fan]; _go += cond_fan
    cprog = g_row[:, _go : _go + cond_fan]; _go += cond_fan
    fork_flows = g_row[:, _go : _go + fork_fan]; _go += fork_fan
    timer_dur_rec = pops.planes_to_i64(g_row[:, _go : _go + 2])[:, 0]

    # ---------------- A. lookups ----------------
    is_wi = valid & (vt_ == VT_WI)
    wi_ev = is_wi & (rt == RT_EVENT)
    wi_cmd = is_wi & (rt == RT_CMD)
    is_job = valid & (vt_ == VT_JOB)
    job_cmd = is_job & (rt == RT_CMD)
    job_ev = is_job & (rt == RT_EVENT)
    timer_cmd = valid & (vt_ == VT_TIMER) & (rt == RT_CMD)
    # message family (reference broker-core message correlation — the
    # MESSAGE/MESSAGE_SUBSCRIPTION processors on the message partition and
    # CorrelateWorkflowInstanceSubscription on the workflow partition;
    # correlation columns ride type_id=name, retries=corr vt, worker=corr
    # bits — fields the message rows never use for their job meanings)
    msg_pub = valid & (vt_ == VT_MSG) & (rt == RT_CMD) & (it == int(MI.PUBLISH))
    msg_del = valid & (vt_ == VT_MSG) & (rt == RT_CMD) & (it == int(MI.DELETE))
    ms_open = valid & (vt_ == VT_MSUB) & (rt == RT_CMD) & (it == int(MS.OPEN))
    ms_close = valid & (vt_ == VT_MSUB) & (rt == RT_CMD) & (it == int(MS.CLOSE))
    wisub_corr = (
        valid & (vt_ == VT_WISUB) & (rt == RT_CMD) & (it == int(WS.CORRELATE))
    )

    # the three element-instance lookups (record key / scope key / job
    # activity key) resolve through the direct-mapped index: keys are
    # engine-allocated and sequential, so index[key & (cap-1)] hits for
    # everything created within the last 8N keys; a hit is verified
    # against the row's own key column, and the rare miss (congruent-key
    # clobber) falls back to the per-wave-rebuilt hashmap. No per-record
    # probe loop on the hot path (reference: ElementInstanceIndex is a
    # Long2ObjectHashMap — this is its O(1) vectorized analogue).
    keys3 = jnp.concatenate([batch.key, batch.scope_key, batch.aux_key])
    want3 = jnp.concatenate(
        [wi_ev, wi_ev & (batch.scope_key >= 0),
         job_ev | timer_cmd | wisub_corr]
    )
    with jax.named_scope("zb_lookups"):
        (ei3_found, ei3_slot), (jb_found, jb_slot) = _indexed_lookup_multi([
            (state.ei_index, (state.ei_i64, EIL_KEY), state.ei_map,
             keys3, want3, n_cap),
            (state.job_index, (state.job_i64, JBL_KEY), state.job_map,
             batch.key, job_cmd & (batch.key >= 0), m_cap),
        ])
    ei_found, ei_slot = ei3_found[:b], ei3_slot[:b]
    sc_found, sc_slot = ei3_found[b : 2 * b], ei3_slot[b : 2 * b]
    aik_found, aik_slot = ei3_found[2 * b :], ei3_slot[2 * b :]
    if graph.has_timers:
        tm_found, tm_slot = pops.lookup(
            state.timer_map, batch.key, timer_cmd & (batch.key >= 0)
        )
    else:
        tm_found = jnp.zeros((b,), bool)
        tm_slot = jnp.zeros((b,), jnp.int32)
    ms_cap = state.msub_ckey.shape[0]
    mg_cap = state.msg_key.shape[0]
    if graph.has_messages:
        # composite (message name, correlation value) — the store key for
        # both subscription and stored-message probes
        ckey = corr_composite(batch.type_id, batch.retries, batch.worker)
        msub_probe = msg_pub | ms_open | ms_close
        msub_found, msub_slot = pops.lookup(state.msub_map, ckey, msub_probe)
        mmsg_probe = msg_pub | ms_open | msg_del
        mmsg_found, mmsg_slot = pops.lookup(state.msg_map, ckey, mmsg_probe)
    else:
        ckey = jnp.full((b,), -1, jnp.int64)
        msub_found = jnp.zeros((b,), bool)
        msub_slot = jnp.zeros((b,), jnp.int32)
        mmsg_found = jnp.zeros((b,), bool)
        mmsg_slot = jnp.zeros((b,), jnp.int32)
    msub_clip = jnp.clip(msub_slot, 0, ms_cap - 1)
    mmsg_clip = jnp.clip(mmsg_slot, 0, mg_cap - 1)
    ei_clip = jnp.clip(ei_slot, 0, n_cap - 1)
    sc_clip = jnp.clip(sc_slot, 0, n_cap - 1)
    aik_clip = jnp.clip(aik_slot, 0, n_cap - 1)
    jb_clip = jnp.clip(jb_slot, 0, m_cap - 1)
    tm_clip = jnp.clip(tm_slot, 0, t_cap - 1)

    # ONE fused gather pass feeds every phase-B/C read: each role's rows
    # (element-instance i32/i64, payload, job, timer columns, message
    # store) are pulled once per wave through pops.fused_gather_rows — on
    # the pallas path a single serial launch with the tables VMEM-resident,
    # on the XLA path one concatenated gather per table (a [B, 6] row
    # gather costs the same as a [B] column gather: the cost is per-index
    # issue, not bytes). Every per-role read below slices these gathered
    # rows instead of issuing its own gather.
    with jax.named_scope("zb_gather"):
        g_tables = [
            state.ei_i32, state.ei_i64, state.ei_pay,
            state.job_i32, state.job_i64, state.job_pay,
            state.timer_elem, state.timer_wf,
        ]
        g_ops = [
            pops.GatherOp(0, ei_clip), pops.GatherOp(0, sc_clip),
            pops.GatherOp(0, aik_clip),
            pops.GatherOp(1, aik_clip), pops.GatherOp(1, ei_clip),
            pops.GatherOp(2, sc_clip), pops.GatherOp(2, aik_clip),
            pops.GatherOp(2, ei_clip),
            pops.GatherOp(3, jb_clip), pops.GatherOp(4, jb_clip),
            pops.GatherOp(5, jb_clip),
            pops.GatherOp(6, tm_clip), pops.GatherOp(7, tm_clip),
        ]
        if graph.has_messages:
            gm = len(g_tables)
            g_tables += [
                state.msg_i32, state.msg_key, state.msg_pay,
                state.msub_i32, state.msub_i64,
            ]
            g_ops += [
                pops.GatherOp(gm, mmsg_clip),
                pops.GatherOp(gm + 1, mmsg_clip),
                pops.GatherOp(gm + 2, mmsg_clip),
                pops.GatherOp(gm + 3, msub_clip),
                pops.GatherOp(gm + 4, msub_clip),
            ]
        g = pops.fused_gather_rows(g_tables, g_ops)
    (ei_rows, sc_rows, aik_rows, aik_i64_rows, ei_i64_rows,
     sc_pay_rows, aik_pay_rows, ei_pay_rows,
     jb_i32_rows, jb_i64_rows, jb_pay_rows,
     tm_elem_rows, tm_wf_rows) = g[:13]
    # the 64-bit tables gave plane rows: int64 of the wave's rows only
    aik_i64_rows = pops.planes_to_i64(aik_i64_rows)
    ei_i64_rows = pops.planes_to_i64(ei_i64_rows)
    jb_i64_rows = pops.planes_to_i64(jb_i64_rows)
    if graph.has_messages:
        (mmsg_i32_rows, mmsg_key_rows, mmsg_pay_rows,
         msub_i32_rows, msub_i64_rows) = g[13:]
        mmsg_key_rows = _col64(mmsg_key_rows)
        msub_i64_rows = pops.planes_to_i64(msub_i64_rows)
    inst_state = jnp.where(ei_found, ei_rows[:, EI_STATE], -1)
    scope_state = jnp.where(sc_found, sc_rows[:, EI_STATE], -1)

    # second-level reads: scope-of-scope keys resolve through slots that
    # only exist after the first gather pass lands (a row's parent slot is
    # a COLUMN of its gathered row) — one more fused pass, one gather
    scope_parent = jnp.where(sc_found, sc_rows[:, EI_SCOPE], -1)
    inst_scope_slot = aik_rows[:, EI_SCOPE]
    with jax.named_scope("zb_gather"):
        sp_key_g, is_key_g = pops.fused_gather_rows(
            [state.ei_i64],
            [pops.GatherOp(0, jnp.clip(scope_parent, 0, n_cap - 1)),
             pops.GatherOp(0, jnp.clip(inst_scope_slot, 0, n_cap - 1))],
        )
    scope_parent_key = jnp.where(
        scope_parent >= 0, _col64(sp_key_g, EIL_KEY), -1
    )
    inst_scope_key = jnp.where(
        inst_scope_slot >= 0, _col64(is_key_g, EIL_KEY), -1
    )

    # ---------------- B. routing + guards ----------------
    m_create = wi_cmd & (it == int(WI.CREATE)) & (batch.wf >= 0)
    m_created_ev = wi_ev & (it == int(WI.CREATED))

    g_own = (
        (it == int(WI.ELEMENT_READY))
        | (it == int(WI.ELEMENT_ACTIVATED))
        | (it == int(WI.ELEMENT_COMPLETING))
    )
    g_flow = (
        (it == int(WI.END_EVENT_OCCURRED))
        | (it == int(WI.GATEWAY_ACTIVATED))
        | (it == int(WI.START_EVENT_OCCURRED))
        | (it == int(WI.SEQUENCE_FLOW_TAKEN))
        | (it == int(WI.BOUNDARY_EVENT_OCCURRED))
    )
    # pending interrupting-boundary continuation (the oracle's
    # _pending_boundary dict as the instance column EI_PENDING_BD):
    # ELEMENT_TERMINATED with a pending boundary processes while the scope
    # stays ACTIVATED (the token moves to the boundary event)
    pending_bd = jnp.where(
        ei_found, ei_rows[:, EI_PENDING_BD], -1
    )
    guard = jnp.where(
        g_own,
        ei_found & (inst_state == it),
        jnp.where(
            it == int(WI.ELEMENT_COMPLETED),
            sc_found & (scope_state == int(WI.ELEMENT_ACTIVATED)),
            jnp.where(
                it == int(WI.ELEMENT_TERMINATED),
                sc_found & jnp.where(
                    pending_bd >= 0,
                    (scope_state == int(WI.ELEMENT_ACTIVATED))
                    | (scope_state == int(WI.ELEMENT_TERMINATING)),
                    scope_state == int(WI.ELEMENT_TERMINATING),
                ),
                jnp.where(
                    g_flow, sc_found & (scope_state == int(WI.ELEMENT_ACTIVATED)), True
                ),
            ),
        ),
    )
    shall = ei_found | sc_found
    stepped = wi_ev & ~m_created_ev & shall & guard & (batch.wf >= 0) & (batch.elem >= 0)
    # per-row intent select from the gathered step row: a one-hot
    # multiply-sum over the (small, static) intent axis — no second gather
    step_id = jnp.where(
        stepped,
        jnp.sum(
            jnp.where(
                jnp.arange(n_intents, dtype=jnp.int32)[None, :]
                == jnp.clip(it, 0, n_intents - 1)[:, None],
                step_row,
                0,
            ),
            axis=1,
        ),
        int(BS.NONE),
    )

    def m_step(s):
        return stepped & (step_id == int(s))

    m_take = m_step(BS.TAKE_SEQUENCE_FLOW)
    m_consume = m_step(BS.CONSUME_TOKEN)
    m_xsplit = m_step(BS.EXCLUSIVE_SPLIT)
    m_createjob = m_step(BS.CREATE_JOB)
    m_inmap = m_step(BS.APPLY_INPUT_MAPPING)
    m_outmap = m_step(BS.APPLY_OUTPUT_MAPPING)
    m_actgw = m_step(BS.ACTIVATE_GATEWAY)
    m_startst = m_step(BS.START_STATEFUL_ELEMENT)
    m_trigend = m_step(BS.TRIGGER_END_EVENT)
    m_trigstart = m_step(BS.TRIGGER_START_EVENT)
    m_complete_proc = m_step(BS.COMPLETE_PROCESS)
    m_psplit = m_step(BS.PARALLEL_SPLIT)
    m_pmerge = m_step(BS.PARALLEL_MERGE)
    m_timer_step = m_step(BS.CREATE_TIMER)
    m_subscribe = m_step(BS.SUBSCRIBE_TO_INTERMEDIATE_MESSAGE)
    m_term_job = m_step(BS.TERMINATE_JOB_TASK)
    m_term_catch = m_step(BS.TERMINATE_CATCH_EVENT)
    m_term_elem = m_step(BS.TERMINATE_ELEMENT)
    m_mi = m_step(BS.MULTI_INSTANCE_SPLIT)

    # job commands
    job_state = jnp.where(jb_found, jb_i32_rows[:, JB_STATE], -1)
    m_jcreate = job_cmd & (it == int(JI.CREATE))
    m_jactivate = job_cmd & (it == int(JI.ACTIVATE))
    m_jcomplete = job_cmd & (it == int(JI.COMPLETE))
    m_jfail = job_cmd & (it == int(JI.FAIL))
    m_jtimeout = job_cmd & (it == int(JI.TIME_OUT))
    m_jretries = job_cmd & (it == int(JI.UPDATE_RETRIES))
    m_jcancel = job_cmd & (it == int(JI.CANCEL))

    activatable = (
        (job_state == int(JI.CREATED))
        | (job_state == int(JI.FAILED))
        | (job_state == int(JI.TIMED_OUT))
    )
    completable = (job_state == int(JI.ACTIVATED)) | (job_state == int(JI.TIMED_OUT))
    # Commands on ONE row in one wave serialise in log order. The lookups
    # above read the tables as they stood BEFORE the wave, so of several
    # command rows on one key only the FIRST is judged against them
    # (``first_cmd``, one comparison for jobs and timers):
    # - jobs: rows with the same key AND the same intent. Whatever the
    #   first comes to (accepted: the job leaves the state the intent
    #   needs, or the table; rejected: the state stays one the intent
    #   cannot take), the oracle rejects every later one with the reason
    #   of a job that is not in that state (CANCEL: that does not exist),
    #   and a rejected ACTIVATE returns its credit below. UPDATE_RETRIES is
    #   not in the rule (a second one is accepted again), and a later row
    #   of ANOTHER intent is judged against the first one's outcome: the
    #   engine keeps both out of the wave (TpuPartitionEngine._route_wave
    #   starts a new device segment at such a row), so they never meet
    #   here.
    # - timers: TRIGGER and CANCEL both pop the timer, so rows with the
    #   same key whatever their intent. A later TRIGGER is rejected (the
    #   timer fired once, not once per TRIGGER the tick appended); a later
    #   CANCEL is the oracle's silent no-op (the engine emits a disarm
    #   cancel AND a terminate-catch-scan cancel for one armed timer, and
    #   under the wave drain both land in one step).
    m_jonce = m_jactivate | m_jcomplete | m_jfail | m_jtimeout | m_jcancel
    m_ttrigger = timer_cmd & (it == int(TI.TRIGGER))
    m_tcancel = timer_cmd & (it == int(TI.CANCEL))
    m_tpop = m_ttrigger | m_tcancel
    first_cmd = _first_per_key(
        batch.key * 16 + jnp.where(m_tpop, 15, it).astype(jnp.int64),
        m_jonce | m_tpop,
    )
    jb_first = jb_found & first_cmd
    jact_ok = m_jactivate & jb_first & activatable
    jact_rej = m_jactivate & ~(jb_first & activatable)
    jcomp_ok = m_jcomplete & jb_first & completable
    jcomp_rej = m_jcomplete & ~(jb_first & completable)
    jfail_ok = m_jfail & jb_first & (job_state == int(JI.ACTIVATED))
    jfail_rej = m_jfail & ~(jb_first & (job_state == int(JI.ACTIVATED)))
    jtime_ok = m_jtimeout & jb_first & (job_state == int(JI.ACTIVATED))
    jtime_rej = m_jtimeout & ~(jb_first & (job_state == int(JI.ACTIVATED)))
    jret_ok = m_jretries & jb_found & (job_state == int(JI.FAILED)) & (batch.retries > 0)
    jret_badv = m_jretries & jb_found & (job_state == int(JI.FAILED)) & (batch.retries <= 0)
    jret_rej = m_jretries & ~(jb_found & (job_state == int(JI.FAILED)))
    jcan_ok = m_jcancel & jb_first
    jcan_rej = m_jcancel & ~jb_first

    # job events (workflow-side processors + activation pool + incidents)
    jev_created = job_ev & (it == int(JI.CREATED))
    jev_completed = job_ev & (it == int(JI.COMPLETED)) & aik_found
    m_actpool = job_ev & (
        (it == int(JI.CREATED))
        | (it == int(JI.TIMED_OUT))
        | (it == int(JI.FAILED))
        | (it == int(JI.RETRIES_UPDATED))
    ) & (batch.retries > 0)
    jev_fail_noretry = job_ev & (it == int(JI.FAILED)) & (batch.retries <= 0)

    # timer commands
    m_tcreate = timer_cmd & (it == int(TI.CREATE))
    tm_first = tm_found & first_cmd
    ttrig_ok = m_ttrigger & tm_first
    ttrig_rej = m_ttrigger & ~tm_first
    tcan_ok = m_tcancel & tm_first
    # timer trigger resumes the catch event when still active
    ttrig_inst = ttrig_ok & aik_found & (
        jnp.where(aik_found, aik_rows[:, EI_STATE], -1) == int(WI.ELEMENT_ACTIVATED)
    )
    # boundary-event triggers: the timer's handler element is a BOUNDARY
    # event attached to the instance's element (oracle _boundary_for +
    # _fire_boundary_event); interrupting boundaries terminate the host
    # and continue at the boundary when ELEMENT_TERMINATED processes
    # the trigger's handler element comes from the TIMER TABLE (a
    # host-staged TRIGGER command does not carry element columns)
    trig_elem = jnp.where(tm_found, tm_elem_rows, batch.elem)
    trig_wf = jnp.where(tm_found, tm_wf_rows, 0)
    if graph.has_boundaries:
        trig_elem_c = jnp.clip(trig_elem, 0, graph.elem_type.shape[1] - 1)
        trig_wf_c = jnp.clip(trig_wf, 0, graph.elem_type.shape[0] - 1)
        trig_is_bd = graph.bd_is_boundary[trig_wf_c, trig_elem_c]
        ttrig_catch = ttrig_inst & ~trig_is_bd
        ttrig_bd = ttrig_inst & trig_is_bd
        ttrig_bd_int = ttrig_bd & graph.bd_host_interrupt[trig_wf_c, trig_elem_c]
        ttrig_bd_non = ttrig_bd & ~graph.bd_host_interrupt[trig_wf_c, trig_elem_c]
        # arming/disarming rides the host element's lifecycle events
        # (oracle _arm_boundary_events / _disarm_boundary_events)
        lifecycle_ok = (
            wi_ev & ~m_created_ev & shall & guard
            & (batch.wf >= 0) & (batch.elem >= 0)
        )
        bd_n = emeta[:, graph_mod.EM_BD_COUNT]
        m_arm = lifecycle_ok & (it == int(WI.ELEMENT_ACTIVATED)) & (bd_n > 0)
        m_disarm_bd = lifecycle_ok & (
            (it == int(WI.ELEMENT_COMPLETING))
            | (it == int(WI.ELEMENT_TERMINATING))
        ) & (bd_n > 0)
        # TERMINATE_CATCH_EVENT re-scans timers by aik (the oracle's
        # _h_terminate_catch_event scan — a SECOND cancel for timers the
        # disarm already canceled, since state only mutates when the
        # commands process)
        m_cancel_timers = m_term_catch
        # TERMINATED with a pending boundary: continue the token at the
        # boundary element with the stored trigger payload
        m_bd_continue = (
            lifecycle_ok & (it == int(WI.ELEMENT_TERMINATED)) & (pending_bd >= 0)
        )
    else:
        zbb = jnp.zeros((b,), bool)
        ttrig_catch = ttrig_inst
        ttrig_bd = ttrig_bd_int = ttrig_bd_non = zbb
        m_arm = m_disarm_bd = m_bd_continue = zbb
        m_cancel_timers = m_term_catch
        bd_n = jnp.zeros((b,), jnp.int32)
    # rows on boundary-carrying elements re-slot their own step output
    # AFTER the arm/disarm records (the oracle writes arms/cancels first)
    has_bd = bd_n > 0

    # message correlation guards (oracle: _process_message_command /
    # _process_message_subscription / _process_wi_subscription)
    if graph.has_messages:
        msgid = batch.aux2_key.astype(jnp.int32)  # interned message id, 0 none
        pub_dup = (
            msg_pub & mmsg_found & (msgid > 0)
            & (mmsg_i32_rows[:, MG_MSGID] == msgid)
        )
        # one live slot per composite (the device store is hashmap-keyed):
        # a second TTL-store or OPEN on an occupied composite REJECTS that
        # record with an explicit reason — a legal-but-unsupported workload
        # degrades per-record, never crashes the partition
        pub_chain = msg_pub & ~pub_dup & (batch.deadline > 0) & mmsg_found
        pub_ok = msg_pub & ~pub_dup & ~pub_chain
        pub_store = pub_ok & (batch.deadline > 0)   # TTL rides the deadline col
        pub_nostore = pub_ok & ~(batch.deadline > 0)
        pub_corr = pub_ok & msub_found
        open_dup = ms_open & msub_found
        open_ok = ms_open & ~msub_found
        open_corr = open_ok & mmsg_found
        close_ok = (
            ms_close & msub_found
            & (msub_i64_rows[:, MSL_AIK] == batch.aux_key)
            & (msub_i64_rows[:, MSL_WIKEY] == batch.instance_key)
        )
        del_ok = msg_del & mmsg_found & (mmsg_key_rows == batch.key)
        corr_live = wisub_corr & aik_found & (
            jnp.where(aik_found, aik_rows[:, EI_STATE], -1)
            == int(WI.ELEMENT_ACTIVATED)
        )
        corr_rej = wisub_corr & ~corr_live
        # boundary-message correlate: the message name matches one of the
        # instance element's attached boundary events (oracle
        # _process_wi_subscription -> _boundary_for by message name)
        ci_elem = jnp.where(aik_found, aik_rows[:, EI_ELEM], 0)
        ci_wf = jnp.where(aik_found, aik_rows[:, EI_WF], 0)
        ci_elem_c = jnp.clip(ci_elem, 0, graph.elem_type.shape[1] - 1)
        ci_wf_c = jnp.clip(ci_wf, 0, graph.elem_type.shape[0] - 1)
        if graph.has_boundaries:
            bd_cnt_i = graph.bd_count[ci_wf_c, ci_elem_c]
            corr_bd_elem = jnp.full((b,), -1, jnp.int32)
            corr_bd_interrupt = jnp.zeros((b,), bool)
            for bslot in range(graph.bd_elem.shape[2]):
                match_b = (
                    (bslot < bd_cnt_i)
                    & (graph.bd_msg[ci_wf_c, ci_elem_c, bslot] == batch.type_id)
                    & (graph.bd_msg[ci_wf_c, ci_elem_c, bslot] > 0)
                    & (corr_bd_elem < 0)
                )
                corr_bd_elem = jnp.where(
                    match_b, graph.bd_elem[ci_wf_c, ci_elem_c, bslot],
                    corr_bd_elem,
                )
                corr_bd_interrupt = jnp.where(
                    match_b,
                    graph.bd_interrupt[ci_wf_c, ci_elem_c, bslot],
                    corr_bd_interrupt,
                )
            corr_is_bd = corr_live & (corr_bd_elem >= 0)
        else:
            corr_bd_elem = jnp.full((b,), -1, jnp.int32)
            corr_bd_interrupt = jnp.zeros((b,), bool)
            corr_is_bd = jnp.zeros((b,), bool)
        corr_inst_ok = corr_live & ~corr_is_bd
        corr_bd_int = corr_is_bd & corr_bd_interrupt
        corr_bd_non = corr_is_bd & ~corr_bd_interrupt
        # subscribe step: correlation key extracted from the payload column.
        # Accepted types mirror the oracle's isinstance(corr, (str, int)):
        # strings, ints, and bools (a Python bool IS an int); floats raise
        # the same IO_MAPPING incident the oracle does
        cvar = emeta[:, graph_mod.EM_CORR_VAR]
        cvar_c = jnp.clip(cvar, 0, v - 1)
        corr_vt_ext = batch.v_vt[rows, cvar_c].astype(jnp.int32)
        corr_bits_ext = jnp.where(
            corr_vt_ext == int(COND_VT_STR),
            batch.v_str[rows, cvar_c],
            jax.lax.bitcast_convert_type(batch.v_num[rows, cvar_c], jnp.int32),
        )
        corr_extractable = (
            (cvar >= 0)
            & (
                (corr_vt_ext == int(COND_VT_STR))
                | (corr_vt_ext == int(COND_VT_NUM))
                | (corr_vt_ext == int(COND_VT_BOOL))
            )
        )
        sub_ok = m_subscribe & corr_extractable
        sub_err = m_subscribe & ~corr_extractable
    else:
        zb = jnp.zeros((b,), bool)
        pub_dup = pub_chain = pub_ok = pub_store = pub_nostore = pub_corr = zb
        open_dup = open_ok = open_corr = close_ok = del_ok = zb
        corr_inst_ok = corr_rej = sub_ok = sub_err = zb
        corr_bd_int = corr_bd_non = corr_is_bd = zb
        corr_bd_elem = jnp.full((b,), -1, jnp.int32)
        corr_vt_ext = jnp.zeros((b,), jnp.int32)
        corr_bits_ext = jnp.zeros((b,), jnp.int32)

    # ---------------- C. per-step compute ----------------
    # exclusive split: evaluate conditioned flows in order
    fan = cond_fan
    # cflow / cprog [B, F] rows ride the phase-A fused graph gather
    has_cond = cprog >= 0
    if graph.has_conditions:
        tri = eval_programs(
            graph.progs,
            graph.lit_nums,
            cprog,
            jnp.broadcast_to(batch.v_vt[:, None, :], (b, fan, v)),
            jnp.broadcast_to(batch.v_num[:, None, :], (b, fan, v)),
            jnp.broadcast_to(batch.v_str[:, None, :], (b, fan, v)),
        )
        tri = jnp.where(has_cond, tri, -1)
    else:
        # deploy-time specialization: no conditioned flow in the whole
        # deployed set — the predicate machine is compiled out
        tri = jnp.full((b, fan), -1, jnp.int32)
    is_true = tri == TRI_TRUE
    is_err = tri == TRI_ERROR
    fidx = jnp.arange(fan, dtype=jnp.int32)
    first_true = jnp.min(jnp.where(is_true, fidx, fan), axis=1)
    first_err = jnp.min(jnp.where(is_err, fidx, fan), axis=1)
    cond_errored = first_err < first_true
    default_f = emeta[:, graph_mod.EM_DEFAULT_FLOW]
    # select the first-true flow by one-hot multiply-sum over the (small,
    # static) fan axis instead of a per-row gather
    taken_flow = jnp.where(
        first_true < fan,
        jnp.sum(
            jnp.where(
                fidx[None, :] == jnp.clip(first_true, 0, fan - 1)[:, None],
                cflow,
                0,
            ),
            axis=1,
        ),
        default_f,
    )
    xs_ok = m_xsplit & ~cond_errored & (taken_flow >= 0)
    xs_nofl = m_xsplit & ~cond_errored & (taken_flow < 0)
    xs_err = m_xsplit & cond_errored

    # input mapping (compiled out when the deployed set has no mappings:
    # identity pass-through is the default behavior)
    if graph.has_mappings:
        in_from, in_has, in_root, in_err = _apply_mappings(
            graph, wf_c, el_c, batch.v_vt, batch.v_num, batch.v_str, True
        )
        im_vt, im_num, im_sid = _select_by_map(
            in_from, batch.v_vt, batch.v_num, batch.v_str
        )
        sel_in = (in_has & ~in_root)[:, None]
        in_vt = jnp.where(sel_in, im_vt, batch.v_vt)
        in_num = jnp.where(sel_in, im_num, batch.v_num)
        in_sid = jnp.where(sel_in, im_sid, batch.v_str)
        inmap_ok = m_inmap & ~(in_has & in_err)
        inmap_err = m_inmap & in_has & in_err
    else:
        in_vt, in_num, in_sid = batch.v_vt, batch.v_num, batch.v_str
        inmap_ok = m_inmap
        inmap_err = jnp.zeros((b,), bool)

    # output mapping: merge(record payload → scope payload)
    scope_vt, scope_sid, scope_num = unpack_payload(sc_pay_rows)
    scope_vt = scope_vt.astype(jnp.int8)
    no_scope = ~sc_found
    scope_vt = jnp.where(no_scope[:, None], VT_ABSENT, scope_vt)
    if graph.has_mappings:
        out_from, out_has, out_root, out_err = _apply_mappings(
            graph, wf_c, el_c, batch.v_vt, batch.v_num, batch.v_str, False
        )
        om_vt, om_num, om_sid = _select_by_map(
            out_from, batch.v_vt, batch.v_num, batch.v_str
        )
    else:
        out_from = jnp.full((b, v), -1, jnp.int32)
        out_has = jnp.zeros((b,), bool)
        out_root = jnp.zeros((b,), bool)
        out_err = jnp.zeros((b,), bool)
        om_vt, om_num, om_sid = batch.v_vt, batch.v_num, batch.v_str
    behavior = emeta[:, graph_mod.EM_OUT_BEHAVIOR]
    B_MERGE, B_OVERWRITE, B_NONE = 0, 1, 2
    src_present = batch.v_vt != VT_ABSENT

    def _merge_one(scope_a, src_a, mapped_a, fill):
        base = jnp.where((behavior == B_OVERWRITE)[:, None], fill, scope_a)
        with_maps = jnp.where(out_from >= 0, mapped_a, base)
        without = jnp.where(
            (behavior == B_OVERWRITE)[:, None],
            src_a,
            jnp.where(src_present, src_a, scope_a),
        )
        merged = jnp.where((out_has & ~out_root)[:, None], with_maps, jnp.where(
            out_root[:, None], src_a, without))
        return jnp.where((behavior == B_NONE)[:, None], scope_a, merged)

    out_vt = _merge_one(scope_vt, batch.v_vt, om_vt, jnp.int8(VT_ABSENT))
    out_num = _merge_one(scope_num, batch.v_num, om_num, 0.0)
    out_sid = _merge_one(scope_sid, batch.v_str, om_sid, 0)
    outmap_ok = m_outmap & ~(out_has & out_err)
    outmap_err = m_outmap & out_has & out_err

    # parallel join: composite key (scope_key, gateway element). Compiled
    # out for deployed sets without a joining parallel gateway.
    gw_elem = emeta[:, graph_mod.EM_FLOW_TGT]
    gw_clip = jnp.clip(gw_elem, 0, graph.elem_type.shape[1] - 1)
    if graph.has_parallel_joins:
        join_key = jnp.where(
            m_pmerge, (batch.scope_key << jnp.int64(10)) | gw_clip.astype(jnp.int64), -1
        )
        jn_found, jn_slot = pops.lookup(state.join_map, join_key, m_pmerge)
        # leaders: first batch occurrence of each missing join key (sort-dedup)
        missing = m_pmerge & ~jn_found
        sort_k = jnp.where(missing, join_key, jnp.int64(2**62))
        order = jnp.argsort(sort_k, stable=True)
        sorted_k = sort_k[order]
        first_occ = jnp.concatenate(
            [jnp.ones((1,), bool), sorted_k[1:] != sorted_k[:-1]]
        )
        leader = jnp.zeros((b,), bool).at[order].set(first_occ) & missing
        # allocate join slots for leaders
        join_free = _first_true_indices(col_neg(state.join_key), b)
        l_rank = _excl_cumsum(leader.astype(jnp.int32))
        l_slot = join_free[jnp.clip(l_rank, 0, b - 1)]
        join_overflow = jnp.any(leader & (l_slot >= j_cap))
        join_key_arr = pops.masked_vec64_update(
            state.join_key, l_slot, leader, join_key
        )
        nin_here = graph.join_nin[wf_c, gw_clip]
        join_nin_arr = pops.masked_lane_update(
            state.join_nin, l_slot, leader, nin_here
        )
        jmap, jins = pops.insert(state.join_map, join_key, l_slot, leader)
        # re-lookup so every arrival sees its slot
        jn_found2, jn_slot2 = pops.lookup(jmap, join_key, m_pmerge)
        arr_slot = jnp.clip(jn_slot2, 0, j_cap - 1)
        my_pos = emeta[:, graph_mod.EM_JOIN_POS]
        arrival = m_pmerge & jn_found2
        aw = jnp.where(arrival, arr_slot, j_cap)
        # dynamic column one-hot; arrivals are monotonic so a row MAX
        # composes concurrent arrivals at the same join slot
        fcols = jnp.arange(state.join_arrived.shape[1], dtype=jnp.int32)
        pos_hot = fcols[None, :] == jnp.clip(
            my_pos, 0, state.join_arrived.shape[1] - 1
        )[:, None]
        arrived = pops.masked_row_max(
            state.join_arrived.astype(jnp.int32), arr_slot, arrival,
            pos_hot.astype(jnp.int32),
        ).astype(bool)
        # flow-position-stamped payload merge: higher flow pos wins per variable
        stamp = pops.masked_row_max(
            state.join_pos_stamp, arr_slot, arrival,
            jnp.where(src_present, my_pos[:, None], -1),
        )
        win_var = m_pmerge[:, None] & src_present & (
            stamp[jnp.clip(aw, 0, j_cap - 1)] == my_pos[:, None]
        )
        win3 = jnp.concatenate([win_var, win_var, win_var], axis=1)
        b_pay_join = pack_payload(batch.v_vt, batch.v_str, batch.v_num)
        join_pay = pops.masked_row_update(
            state.join_pay, arr_slot, arrival, b_pay_join, win3
        )
        # completion: all incoming arrived; completer = last arrival in batch
        arr_count = jnp.sum(arrived, axis=1, dtype=jnp.int32)
        complete_slot = (join_nin_arr > 0) & (arr_count >= join_nin_arr)
        my_complete = m_pmerge & jn_found2 & complete_slot[arr_slot]
        completer = _last_writer(arr_slot, my_complete, j_cap)
        # merged payload for the completer
        mg_vt, mg_sid, mg_num = unpack_payload(join_pay[arr_slot])
        mg_vt = mg_vt.astype(jnp.int8)
    else:
        join_key = jnp.full((b,), -1, jnp.int64)
        arr_slot = jnp.zeros((b,), jnp.int32)
        my_complete = jnp.zeros((b,), bool)
        completer = jnp.zeros((b,), bool)
        join_overflow = jnp.zeros((), bool)
        join_key_arr = state.join_key
        join_nin_arr = state.join_nin
        arrived = state.join_arrived
        stamp = state.join_pos_stamp
        join_pay = state.join_pay
        jmap = state.join_map
        mg_vt, mg_num, mg_sid = batch.v_vt, batch.v_num, batch.v_str

    # ---------------- D. key assignment ----------------
    out_count = emeta[:, graph_mod.EM_OUT_COUNT]
    single_key = (
        m_create | m_take | xs_ok | m_actgw | m_startst | m_trigend
        | m_trigstart | completer | m_tcreate | pub_ok | open_ok
        | ttrig_bd_non | m_bd_continue | corr_bd_non
    )
    n_wf = jnp.where(
        single_key, 1,
        jnp.where(
            m_psplit, out_count,
            jnp.where(m_mi, emeta[:, graph_mod.EM_MI_CARD], 0),
        ),
    )
    wf_base = state.next_wf_key + _KEY_STEP * _excl_cumsum(n_wf).astype(jnp.int64)
    key0 = wf_base  # key for single-allocation steps
    n_job = m_jcreate.astype(jnp.int32)
    job_base = state.next_job_key + _KEY_STEP * _excl_cumsum(n_job).astype(jnp.int64)
    next_wf_key = state.next_wf_key + _KEY_STEP * jnp.sum(n_wf, dtype=jnp.int64)
    next_job_key = state.next_job_key + _KEY_STEP * jnp.sum(n_job, dtype=jnp.int64)

    # ---------------- job activation pool ----------------
    # candidate subscription: first valid sub of the job's type (oracle
    # round-robin degenerates to this for one subscription per type)
    sub_match = (
        state.sub_valid[None, :]
        & (state.sub_type[None, :] == batch.type_id[:, None])
        & (state.sub_credits[None, :] > 0)
    )  # [B, S]
    cand = jnp.argmax(sub_match, axis=1).astype(jnp.int32)
    has_sub = jnp.any(sub_match, axis=1)
    pool = m_actpool & has_sub
    sub_credits = state.sub_credits
    activated = jnp.zeros((b,), bool)
    for s in range(s_cap):
        mask_s = pool & (cand == s)
        rank_s = _excl_cumsum(mask_s.astype(jnp.int32))
        act_s = mask_s & (rank_s < sub_credits[s])
        activated = activated | act_s
        sub_credits = sub_credits.at[s].add(-jnp.sum(act_s, dtype=jnp.int32))
    cand_c = jnp.clip(cand, 0, s_cap - 1)
    # the sub tables are tiny ([S] with S = sub_capacity): read the
    # candidate's columns by one-hot multiply-sum instead of three gathers
    cand_oh = jnp.arange(s_cap, dtype=jnp.int32)[None, :] == cand_c[:, None]
    act_deadline = now + jnp.sum(
        jnp.where(cand_oh, state.sub_timeout[None, :], 0), axis=1
    )
    act_worker = jnp.sum(
        jnp.where(cand_oh, state.sub_worker[None, :], 0), axis=1
    )
    act_stream = jnp.sum(
        jnp.where(cand_oh, state.sub_key[None, :], 0), axis=1
    ).astype(jnp.int32)
    # credit return on activate rejection
    ret_idx = jnp.argmax(
        state.sub_key[None, :] == batch.req_stream[:, None].astype(jnp.int64), axis=1
    ).astype(jnp.int32)
    ret_has = jnp.any(
        state.sub_key[None, :] == batch.req_stream[:, None].astype(jnp.int64), axis=1
    )
    ret_w = jnp.where(jact_rej & ret_has, ret_idx, s_cap)
    sub_credits = sub_credits.at[ret_w].add(1, mode="drop")

    # ---------------- E. emissions ----------------
    zero_vt = jnp.zeros((b, v), jnp.int8)
    zero_num = jnp.zeros((b, v), jnp.float32)
    zero_sid = jnp.zeros((b, v), jnp.int32)

    def blank():
        return {
            "valid": jnp.zeros((b,), bool),
            "rtype": jnp.zeros((b,), jnp.int32),
            "vtype": jnp.zeros((b,), jnp.int32),
            "intent": jnp.zeros((b,), jnp.int32),
            "key": jnp.full((b,), -1, jnp.int64),
            "elem": jnp.full((b,), -1, jnp.int32),
            "wf": batch.wf,
            "instance_key": batch.instance_key,
            "scope_key": batch.scope_key,
            "v_vt": batch.v_vt,
            "v_num": batch.v_num,
            "v_str": batch.v_str,
            "req": jnp.full((b,), -1, jnp.int64),
            "req_stream": jnp.full((b,), -1, jnp.int32),
            "aux_key": jnp.full((b,), -1, jnp.int64),
            "aux2_key": jnp.full((b,), -1, jnp.int64),
            "type_id": jnp.zeros((b,), jnp.int32),
            "retries": jnp.zeros((b,), jnp.int32),
            "deadline": jnp.full((b,), -1, jnp.int64),
            "worker": jnp.zeros((b,), jnp.int32),
            "src": rows,
            "resp": jnp.zeros((b,), bool),
            "push": jnp.zeros((b,), bool),
            "rej": jnp.zeros((b,), jnp.int32),
        }

    def put(em, mask, **kw):
        for name, val in kw.items():
            em[name] = jnp.where(mask, val, em[name])
        return em

    e0 = blank()
    e1 = blank()
    # emission slots ≥ 2 materialize lazily (messages, boundary arm/disarm
    # fan-out); rows claiming the same slot index always have disjoint
    # masks — compaction keeps slot order = the oracle's append order
    extra_slots: Dict[int, dict] = {}

    def eslot(i: int) -> dict:
        if i == 0:
            return e0
        if i == 1:
            return e1
        if i not in extra_slots:
            extra_slots[i] = blank()
        return extra_slots[i]

    pid_col = jnp.broadcast_to(jnp.asarray(partition_id, jnp.int32), (b,))

    # --- slot 0: workflow-instance emissions
    # (scope_parent / scope_parent_key resolved in the phase-A fused pass)
    scope_elem = jnp.where(sc_found, sc_rows[:, EI_ELEM], -1)

    e0 = put(
        e0, m_create,
        valid=True, rtype=RT_EVENT, vtype=VT_WI, intent=int(WI.CREATED),
        key=key0, elem=0, instance_key=key0, scope_key=jnp.int64(-1),
        req=batch.req, req_stream=batch.req_stream, resp=batch.req >= 0,
    )
    e1 = put(
        e1, m_create,
        valid=True, rtype=RT_EVENT, vtype=VT_WI, intent=int(WI.ELEMENT_READY),
        key=key0, elem=0, instance_key=key0, scope_key=jnp.int64(-1),
    )

    first_out = emeta[:, graph_mod.EM_FIRST_OUT]
    e0 = put(
        e0, m_take,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.SEQUENCE_FLOW_TAKEN), key=key0, elem=first_out,
    )
    # consume token: the last consumed token completes the scope
    tokens_after = jnp.zeros((n_cap,), jnp.int32).at[
        jnp.where(m_consume, sc_clip, n_cap)
    ].add(-1, mode="drop") + state.ei_tokens
    # round-9a fused read pass: the remaining 1D i32 state reads — the
    # post-consume token count per scope, the parallel-join fan-in, and
    # the two free-slot ring pops (whose index math is pure, so the
    # phase-E pops hoist here) — share ONE gather
    ins_replay = m_created_ev & ~ei_found
    ins = m_create | m_startst | ins_replay
    ins_rank = _excl_cumsum(ins.astype(jnp.int32))
    ei_pop_idx = state.free_ei_pop + ins_rank.astype(jnp.int64)
    ei_ring_ok = ei_pop_idx < state.free_ei_push
    job_ins = m_jcreate
    j_rank = _excl_cumsum(job_ins.astype(jnp.int32))
    job_pop_idx = state.free_job_pop + j_rank.astype(jnp.int64)
    job_ring_ok = job_pop_idx < state.free_job_push
    with jax.named_scope("zb_gather"):
        tok_after_sc, nin_rec, ei_pop_slot, job_pop_slot = (
            pops.fused_gather_rows(
                [tokens_after, join_nin_arr, state.free_ei, state.free_job],
                [
                    pops.GatherOp(0, sc_clip),
                    pops.GatherOp(1, arr_slot),
                    pops.GatherOp(2, (ei_pop_idx % n_cap).astype(jnp.int32)),
                    pops.GatherOp(3, (job_pop_idx % m_cap).astype(jnp.int32)),
                ],
            )
        )
    consume_done = m_consume & (tok_after_sc <= 0)
    consume_completer = _last_writer(sc_clip, consume_done, n_cap)
    e0 = put(
        e0, consume_completer,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.ELEMENT_COMPLETING), key=batch.scope_key, elem=scope_elem,
        scope_key=scope_parent_key,
    )
    if graph.has_multi_instance:
        # a completing multi-instance container keeps ITS OWN payload (the
        # oracle never copies iteration payloads into an MI scope)
        sc_elem_c = jnp.clip(scope_elem, 0, graph.elem_type.shape[1] - 1)
        sc_wf_c = jnp.clip(
            jnp.where(sc_found, sc_rows[:, EI_WF], 0),
            0, graph.elem_type.shape[0] - 1,
        )
        mi_completer = (
            consume_completer
            & (graph.mi_cardinality[sc_wf_c, sc_elem_c] > 0)
        )
        sc_vt, sc_sid, sc_num = unpack_payload(sc_pay_rows)
        e0["v_vt"] = jnp.where(
            mi_completer[:, None], sc_vt.astype(jnp.int8), e0["v_vt"]
        )
        e0["v_num"] = jnp.where(mi_completer[:, None], sc_num, e0["v_num"])
        e0["v_str"] = jnp.where(mi_completer[:, None], sc_sid, e0["v_str"])
    e0 = put(
        e0, xs_ok,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.SEQUENCE_FLOW_TAKEN), key=key0, elem=taken_flow,
    )
    e0 = put(
        e0, xs_nofl | xs_err,
        valid=True, rtype=RT_CMD, vtype=VT_INCIDENT, intent=0,  # IncidentIntent.CREATE
        key=jnp.int64(-1), elem=batch.elem, aux_key=batch.key,
        rej=jnp.where(xs_nofl, rb.ERR_CONDITION_NO_FLOW, rb.ERR_CONDITION_EVAL),
    )
    e0 = put(
        e0, m_createjob & ~has_bd,
        valid=True, rtype=RT_CMD, vtype=VT_JOB, intent=int(JI.CREATE),
        key=jnp.int64(-1), elem=batch.elem, aux_key=batch.key,
        type_id=emeta[:, graph_mod.EM_JOB_TYPE], retries=emeta[:, graph_mod.EM_JOB_RETRIES],
    )
    e0 = put(
        e0, inmap_ok,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.ELEMENT_ACTIVATED), key=batch.key, elem=batch.elem,
    )
    e0["v_vt"] = jnp.where(inmap_ok[:, None], in_vt, e0["v_vt"])
    e0["v_num"] = jnp.where(inmap_ok[:, None], in_num, e0["v_num"])
    e0["v_str"] = jnp.where(inmap_ok[:, None], in_sid, e0["v_str"])
    e0 = put(
        e0, outmap_ok & ~has_bd,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.ELEMENT_COMPLETED), key=batch.key, elem=batch.elem,
    )
    e0["v_vt"] = jnp.where((outmap_ok & ~has_bd)[:, None], out_vt, e0["v_vt"])
    e0["v_num"] = jnp.where((outmap_ok & ~has_bd)[:, None], out_num, e0["v_num"])
    e0["v_str"] = jnp.where((outmap_ok & ~has_bd)[:, None], out_sid, e0["v_str"])
    e0 = put(
        e0, inmap_err | outmap_err,
        valid=True, rtype=RT_CMD, vtype=VT_INCIDENT, intent=0,
        key=jnp.int64(-1), elem=batch.elem, aux_key=batch.key,
        rej=jnp.where(inmap_err, rb.ERR_IO_MAPPING_IN, rb.ERR_IO_MAPPING_OUT),
    )
    ftarget = emeta[:, graph_mod.EM_FLOW_TGT]
    e0 = put(
        e0, m_actgw,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.GATEWAY_ACTIVATED), key=key0, elem=ftarget,
    )
    e0 = put(
        e0, m_startst,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.ELEMENT_READY), key=key0, elem=ftarget,
    )
    e0 = put(
        e0, m_trigend,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.END_EVENT_OCCURRED), key=key0, elem=ftarget,
    )
    start_ev = emeta[:, graph_mod.EM_START_EV]
    e0 = put(
        e0, m_trigstart,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.START_EVENT_OCCURRED), key=key0, elem=start_ev,
        scope_key=batch.key,
    )
    e0 = put(
        e0, m_complete_proc,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.ELEMENT_COMPLETED), key=batch.key, elem=batch.elem,
    )
    e0 = put(
        e0, completer,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.GATEWAY_ACTIVATED), key=key0, elem=gw_elem,
    )
    e0["v_vt"] = jnp.where(completer[:, None], mg_vt, e0["v_vt"])
    e0["v_num"] = jnp.where(completer[:, None], mg_num, e0["v_num"])
    e0["v_str"] = jnp.where(completer[:, None], mg_sid, e0["v_str"])
    e0 = put(
        e0, m_timer_step,
        valid=True, rtype=RT_CMD, vtype=VT_TIMER, intent=int(TI.CREATE),
        key=jnp.int64(-1), elem=batch.elem, aux_key=batch.key,
        deadline=now + timer_dur_rec,
    )

    # --- slot 0: job command results
    jrej = jact_rej | jcomp_rej | jfail_rej | jtime_rej | jret_rej | jret_badv | jcan_rej
    e0 = put(
        e0, m_jcreate,
        valid=True, rtype=RT_EVENT, vtype=VT_JOB, intent=int(JI.CREATED),
        key=job_base, elem=batch.elem, aux_key=batch.aux_key,
        type_id=batch.type_id, retries=batch.retries,
        req=batch.req, req_stream=batch.req_stream, resp=batch.req >= 0,
    )
    e0 = put(
        e0, jact_ok,
        valid=True, rtype=RT_EVENT, vtype=VT_JOB, intent=int(JI.ACTIVATED),
        key=batch.key, elem=batch.elem, aux_key=batch.aux_key,
        type_id=batch.type_id, retries=batch.retries, deadline=batch.deadline,
        worker=batch.worker, push=True, req_stream=batch.req_stream,
    )
    if synthetic_workers:
        # bench-only instant worker: the COMPLETE lands in the slot right
        # after its ACTIVATED, riding the normal emission compaction (e1 is
        # never used by job-command rows, so the slot is free here)
        e1 = put(
            e1, jact_ok,
            valid=True, rtype=RT_CMD, vtype=VT_JOB, intent=int(JI.COMPLETE),
            key=batch.key, elem=batch.elem, aux_key=batch.aux_key,
            type_id=batch.type_id, retries=batch.retries,
            worker=batch.worker, src=jnp.full((b,), -1, jnp.int32),
        )
    # completed value = stored job record + command payload (columns of
    # the phase-A jb row gathers — no per-column gathers here)
    st_elem = jb_i32_rows[:, JB_ELEM]
    st_wf = jb_i32_rows[:, JB_WF]
    st_ik = jb_i64_rows[:, JBL_IKEY]
    st_aik = jb_i64_rows[:, JBL_AIK]
    st_type = jb_i32_rows[:, JB_TYPE]
    st_retries = jb_i32_rows[:, JB_RETRIES]
    st_worker = jb_i32_rows[:, JB_WORKER]
    st_deadline = jb_i64_rows[:, JBL_DEADLINE]
    e0 = put(
        e0, jcomp_ok,
        valid=True, rtype=RT_EVENT, vtype=VT_JOB, intent=int(JI.COMPLETED),
        key=batch.key, elem=st_elem, wf=st_wf, instance_key=st_ik,
        aux_key=st_aik, type_id=st_type, retries=st_retries,
        worker=st_worker, deadline=st_deadline,
        req=batch.req, req_stream=batch.req_stream, resp=batch.req >= 0,
    )
    payload_nonempty = jnp.any(batch.v_vt != VT_ABSENT, axis=1)
    jb_vt, jb_sid, jb_num = unpack_payload(jb_pay_rows)
    jb_vt = jb_vt.astype(jnp.int8)
    fail_vt = jnp.where(payload_nonempty[:, None], batch.v_vt, jb_vt)
    fail_num = jnp.where(payload_nonempty[:, None], batch.v_num, jb_num)
    fail_sid = jnp.where(payload_nonempty[:, None], batch.v_str, jb_sid)
    e0 = put(
        e0, jfail_ok,
        valid=True, rtype=RT_EVENT, vtype=VT_JOB, intent=int(JI.FAILED),
        key=batch.key, elem=st_elem, wf=st_wf, instance_key=st_ik,
        aux_key=st_aik, type_id=st_type, retries=batch.retries,
        worker=st_worker, deadline=st_deadline,
        req=batch.req, req_stream=batch.req_stream, resp=batch.req >= 0,
    )
    e0["v_vt"] = jnp.where(jfail_ok[:, None], fail_vt, e0["v_vt"])
    e0["v_num"] = jnp.where(jfail_ok[:, None], fail_num, e0["v_num"])
    e0["v_str"] = jnp.where(jfail_ok[:, None], fail_sid, e0["v_str"])
    e0 = put(
        e0, jtime_ok,
        valid=True, rtype=RT_EVENT, vtype=VT_JOB, intent=int(JI.TIMED_OUT),
        key=batch.key, elem=batch.elem, aux_key=batch.aux_key,
        type_id=batch.type_id, retries=batch.retries,
        deadline=batch.deadline, worker=batch.worker,
        req=batch.req, req_stream=batch.req_stream, resp=batch.req >= 0,
    )
    ret_vt = jb_vt
    ret_num = jb_num
    ret_sid = jb_sid
    e0 = put(
        e0, jret_ok,
        valid=True, rtype=RT_EVENT, vtype=VT_JOB, intent=int(JI.RETRIES_UPDATED),
        key=batch.key, elem=st_elem, wf=st_wf, instance_key=st_ik,
        aux_key=st_aik, type_id=st_type, retries=batch.retries,
        worker=st_worker, deadline=st_deadline,
        req=batch.req, req_stream=batch.req_stream, resp=batch.req >= 0,
    )
    e0["v_vt"] = jnp.where(jret_ok[:, None], ret_vt, e0["v_vt"])
    e0["v_num"] = jnp.where(jret_ok[:, None], ret_num, e0["v_num"])
    e0["v_str"] = jnp.where(jret_ok[:, None], ret_sid, e0["v_str"])
    e0 = put(
        e0, jcan_ok,
        valid=True, rtype=RT_EVENT, vtype=VT_JOB, intent=int(JI.CANCELED),
        key=batch.key, elem=batch.elem, aux_key=batch.aux_key,
        type_id=batch.type_id, retries=batch.retries,
        deadline=batch.deadline, worker=batch.worker,
        req=batch.req, req_stream=batch.req_stream, resp=batch.req >= 0,
    )
    rej_code = jnp.select(
        [jact_rej, jcomp_rej, jfail_rej, jtime_rej, jret_badv, jret_rej, jcan_rej],
        [
            rb.REJ_JOB_NOT_ACTIVATABLE, rb.REJ_JOB_NOT_COMPLETABLE,
            rb.REJ_JOB_NOT_ACTIVATED, rb.REJ_JOB_NOT_ACTIVATED,
            rb.REJ_RETRIES_NOT_POSITIVE, rb.REJ_JOB_NOT_FAILED,
            rb.REJ_JOB_NOT_EXIST,
        ],
        0,
    )
    e0 = put(
        e0, jrej,
        valid=True, rtype=RT_REJ, vtype=vt_, intent=it, key=batch.key,
        elem=batch.elem, aux_key=batch.aux_key, type_id=batch.type_id,
        retries=batch.retries, deadline=batch.deadline, worker=batch.worker,
        rej=rej_code, req=batch.req, req_stream=batch.req_stream,
        resp=batch.req >= 0,
    )

    # --- slot 0: job events → workflow / activation / incident
    wi_of_inst_vt, wi_of_inst_sid, wi_of_inst_num = unpack_payload(
        aik_pay_rows
    )
    wi_of_inst_vt = wi_of_inst_vt.astype(jnp.int8)
    inst_elem = aik_rows[:, EI_ELEM]
    inst_wf = aik_rows[:, EI_WF]
    # (inst_scope_slot / inst_scope_key resolved in the phase-A fused pass)
    e0 = put(
        e0, jev_completed,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.ELEMENT_COMPLETING), key=batch.aux_key,
        elem=inst_elem, wf=inst_wf, scope_key=inst_scope_key,
    )
    act_pool_win = activated
    e0 = put(
        e0, act_pool_win,
        valid=True, rtype=RT_CMD, vtype=VT_JOB, intent=int(JI.ACTIVATE),
        key=batch.key, elem=batch.elem, aux_key=batch.aux_key,
        type_id=batch.type_id, retries=batch.retries,
        deadline=act_deadline, worker=act_worker, req_stream=act_stream,
    )
    e0 = put(
        e0, jev_fail_noretry,
        valid=True, rtype=RT_CMD, vtype=VT_INCIDENT, intent=0,
        key=jnp.int64(-1), elem=batch.elem, aux_key=batch.aux_key,
        aux2_key=batch.key, rej=0,  # JOB_NO_RETRIES handled host-side by code 0? no:
    )
    # job-no-retries uses a dedicated code so the host maps the error type
    e0["rej"] = jnp.where(jev_fail_noretry, 105, e0["rej"])

    # --- slot 0/1: timer commands
    e0 = put(
        e0, m_tcreate,
        valid=True, rtype=RT_EVENT, vtype=VT_TIMER, intent=int(TI.CREATED),
        key=key0, elem=batch.elem, aux_key=batch.aux_key, deadline=batch.deadline,
    )
    e0 = put(
        e0, ttrig_ok,
        valid=True, rtype=RT_EVENT, vtype=VT_TIMER, intent=int(TI.TRIGGERED),
        key=batch.key, elem=trig_elem, wf=trig_wf, aux_key=batch.aux_key,
        deadline=batch.deadline,
    )
    e1 = put(
        e1, ttrig_catch,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.ELEMENT_COMPLETING), key=batch.aux_key,
        elem=inst_elem, wf=inst_wf, scope_key=inst_scope_key,
    )
    # interrupting boundary: terminate the host (the continuation fires at
    # ELEMENT_TERMINATED); non-interrupting: the token appears at the
    # boundary event, the host keeps running (oracle _fire_boundary_event)
    e1 = put(
        e1, ttrig_bd_int,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.ELEMENT_TERMINATING), key=batch.aux_key,
        elem=inst_elem, wf=inst_wf, scope_key=inst_scope_key,
    )
    e1 = put(
        e1, ttrig_bd_non,
        valid=True, rtype=RT_EVENT, vtype=VT_WI,
        intent=int(WI.BOUNDARY_EVENT_OCCURRED), key=key0,
        elem=trig_elem, wf=inst_wf, scope_key=inst_scope_key,
    )
    ttrig_any_inst = ttrig_catch | ttrig_bd_int | ttrig_bd_non
    e1["v_vt"] = jnp.where(ttrig_any_inst[:, None], wi_of_inst_vt, e1["v_vt"])
    e1["v_num"] = jnp.where(ttrig_any_inst[:, None], wi_of_inst_num, e1["v_num"])
    e1["v_str"] = jnp.where(ttrig_any_inst[:, None], wi_of_inst_sid, e1["v_str"])
    e1["instance_key"] = jnp.where(
        ttrig_any_inst, aik_i64_rows[:, EIL_IKEY], e1["instance_key"]
    )
    e0 = put(
        e0, ttrig_rej,
        valid=True, rtype=RT_REJ, vtype=vt_, intent=it, key=batch.key,
        rej=rb.REJ_TIMER_NOT_EXIST, req=batch.req, req_stream=batch.req_stream,
        resp=batch.req >= 0,
    )
    e0 = put(
        e0, tcan_ok,
        valid=True, rtype=RT_EVENT, vtype=VT_TIMER, intent=int(TI.CANCELED),
        key=batch.key, elem=batch.elem, aux_key=batch.aux_key,
        deadline=batch.deadline,
    )

    # --- message correlation emissions
    if graph.has_messages:
        e2 = eslot(2)
        # subscribe step → OPEN sent to the message partition (oracle
        # _h_subscribe_to_message); correlation-key failure → incident
        e0 = put(
            e0, sub_ok & ~has_bd,
            valid=True, rtype=RT_CMD, vtype=VT_MSUB, intent=int(MS.OPEN),
            key=jnp.int64(-1), elem=batch.elem,
            type_id=emeta[:, graph_mod.EM_MSG_NAME],
            retries=corr_vt_ext, worker=corr_bits_ext,
            instance_key=batch.instance_key, aux_key=batch.key,
            wf=pid_col,
        )
        e0 = put(
            e0, sub_err & ~has_bd,
            valid=True, rtype=RT_CMD, vtype=VT_INCIDENT, intent=0,
            key=jnp.int64(-1), elem=batch.elem, aux_key=batch.key,
            rej=rb.ERR_CORRELATION_KEY,
        )
        # message partition: PUBLISH
        e0 = put(
            e0, pub_dup | pub_chain | open_dup,
            valid=True, rtype=RT_REJ, vtype=vt_, intent=it, key=batch.key,
            type_id=batch.type_id, retries=batch.retries, worker=batch.worker,
            instance_key=batch.instance_key, aux_key=batch.aux_key,
            aux2_key=batch.aux2_key,
            rej=jnp.where(
                pub_dup, rb.REJ_MSG_DUP,
                jnp.where(pub_chain, rb.REJ_MSG_STORE_OCCUPIED,
                          rb.REJ_SUB_OCCUPIED),
            ),
            req=batch.req, req_stream=batch.req_stream, resp=batch.req >= 0,
        )
        e0 = put(
            e0, pub_ok,
            valid=True, rtype=RT_EVENT, vtype=VT_MSG, intent=int(MI.PUBLISHED),
            key=key0, type_id=batch.type_id, retries=batch.retries,
            worker=batch.worker, deadline=batch.deadline,
            aux2_key=batch.aux2_key,
            req=batch.req, req_stream=batch.req_stream, resp=batch.req >= 0,
        )
        e1 = put(
            e1, pub_nostore,
            valid=True, rtype=RT_EVENT, vtype=VT_MSG, intent=int(MI.DELETED),
            key=key0, type_id=batch.type_id, retries=batch.retries,
            worker=batch.worker, aux2_key=batch.aux2_key,
        )
        e2 = put(
            e2, pub_corr,
            valid=True, rtype=RT_CMD, vtype=VT_WISUB, intent=int(WS.CORRELATE),
            key=jnp.int64(-1),
            wf=msub_i32_rows[:, MS_PART],
            instance_key=msub_i64_rows[:, MSL_WIKEY],
            aux_key=msub_i64_rows[:, MSL_AIK],
            type_id=batch.type_id, retries=batch.retries, worker=batch.worker,
            aux2_key=pid_col.astype(jnp.int64),  # message partition id
        )
        # message partition: OPEN / CLOSE
        e0 = put(
            e0, open_ok,
            valid=True, rtype=RT_EVENT, vtype=VT_MSUB, intent=int(MS.OPENED),
            key=key0, type_id=batch.type_id, retries=batch.retries,
            worker=batch.worker, instance_key=batch.instance_key,
            aux_key=batch.aux_key,
        )
        stored_vt, stored_sid, stored_num = unpack_payload(mmsg_pay_rows)
        e1 = put(
            e1, open_corr,
            valid=True, rtype=RT_CMD, vtype=VT_WISUB, intent=int(WS.CORRELATE),
            key=jnp.int64(-1), wf=batch.wf,
            instance_key=batch.instance_key, aux_key=batch.aux_key,
            type_id=batch.type_id, retries=batch.retries, worker=batch.worker,
            aux2_key=pid_col.astype(jnp.int64),
        )
        e1["v_vt"] = jnp.where(
            open_corr[:, None], stored_vt.astype(jnp.int8), e1["v_vt"]
        )
        e1["v_num"] = jnp.where(open_corr[:, None], stored_num, e1["v_num"])
        e1["v_str"] = jnp.where(open_corr[:, None], stored_sid, e1["v_str"])
        e0 = put(
            e0, close_ok,
            valid=True, rtype=RT_EVENT, vtype=VT_MSUB, intent=int(MS.CLOSED),
            key=batch.key, type_id=batch.type_id, retries=batch.retries,
            worker=batch.worker, instance_key=batch.instance_key,
            aux_key=batch.aux_key,
        )
        e0 = put(
            e0, del_ok,
            valid=True, rtype=RT_EVENT, vtype=VT_MSG, intent=int(MI.DELETED),
            key=batch.key, type_id=batch.type_id, retries=batch.retries,
            worker=batch.worker, aux2_key=batch.aux2_key,
        )
        # workflow partition: CORRELATE arrival (oracle
        # _process_wi_subscription) — CORRELATED, then either the element
        # completes with the message payload (own catch), a boundary event
        # fires (non-interrupting keeps the subscription open), or the
        # host terminates (interrupting); CLOSE goes back to the message
        # partition except for non-interrupting boundaries
        e0 = put(
            e0, corr_live,
            valid=True, rtype=RT_EVENT, vtype=VT_WISUB,
            intent=int(WS.CORRELATED), key=batch.key,
            type_id=batch.type_id, retries=batch.retries, worker=batch.worker,
            instance_key=batch.instance_key, aux_key=batch.aux_key,
        )
        e1 = put(
            e1, corr_inst_ok,
            valid=True, rtype=RT_EVENT, vtype=VT_WI,
            intent=int(WI.ELEMENT_COMPLETING), key=batch.aux_key,
            elem=inst_elem, wf=inst_wf, scope_key=inst_scope_key,
        )
        e1 = put(
            e1, corr_bd_non,
            valid=True, rtype=RT_EVENT, vtype=VT_WI,
            intent=int(WI.BOUNDARY_EVENT_OCCURRED), key=key0,
            elem=corr_bd_elem, wf=inst_wf, scope_key=inst_scope_key,
        )
        e1 = put(
            e1, corr_bd_int,
            valid=True, rtype=RT_EVENT, vtype=VT_WI,
            intent=int(WI.ELEMENT_TERMINATING), key=batch.aux_key,
            elem=inst_elem, wf=inst_wf, scope_key=inst_scope_key,
        )
        # interrupting-boundary TERMINATING carries the INSTANCE payload
        # (oracle terminates with host_value); completion and boundary
        # firing carry the MESSAGE payload (batch defaults)
        e1["v_vt"] = jnp.where(corr_bd_int[:, None], wi_of_inst_vt, e1["v_vt"])
        e1["v_num"] = jnp.where(corr_bd_int[:, None], wi_of_inst_num, e1["v_num"])
        e1["v_str"] = jnp.where(corr_bd_int[:, None], wi_of_inst_sid, e1["v_str"])
        corr_any_inst = corr_inst_ok | corr_bd_non | corr_bd_int
        e1["instance_key"] = jnp.where(
            corr_any_inst, aik_i64_rows[:, EIL_IKEY], e1["instance_key"]
        )
        e2 = put(
            e2, corr_inst_ok | corr_bd_int,
            valid=True, rtype=RT_CMD, vtype=VT_MSUB, intent=int(MS.CLOSE),
            key=jnp.int64(-1), wf=pid_col,
            type_id=batch.type_id, retries=batch.retries, worker=batch.worker,
            instance_key=batch.instance_key, aux_key=batch.aux_key,
        )
        e0 = put(
            e0, corr_rej,
            valid=True, rtype=RT_REJ, vtype=vt_, intent=it, key=batch.key,
            type_id=batch.type_id, retries=batch.retries, worker=batch.worker,
            instance_key=batch.instance_key, aux_key=batch.aux_key,
            rej=rb.REJ_SUB_NOT_ACTIVE,
            req=batch.req, req_stream=batch.req_stream, resp=batch.req >= 0,
        )
    # --- boundary events: arm / disarm / terminate / continue.
    # Slot plan for rows on boundary-carrying elements (written order
    # mirrors the oracle: arms/cancels BEFORE the row's own step output):
    #   slots 0..BD-1   arm records (ACTIVATED) / timer cancels (disarm)
    #   slots BD..2BD-1 subscription closes (disarm; sends)
    #   slot 2BD        the row's own step output (job CREATE / OPEN /
    #                   COMPLETED / job CANCEL / own CLOSE)
    #   slot 2BD+1      ELEMENT_TERMINATED (terminating rows)
    if graph.has_boundaries:
        bdw = graph.bd_elem.shape[2]
        step_slot = eslot(2 * bdw)
        t_iota = jnp.arange(t_cap, dtype=jnp.int32)
        # disarm scan: this instance's armed timers by activityInstanceKey
        # (oracle _disarm_boundary_events' self.timers scan)
        timer_armed_on = ~col_neg(state.timer_key)[None, :] & col_eq(
            state.timer_aik, 0, batch.key
        )  # [B, TM]: the armed timers of each row's activity instance
        cancel_mask = m_disarm_bd[:, None] & timer_armed_on
        for bslot in range(bdw):
            arm_b = m_arm & (bslot < bd_n)
            b_elem = graph.bd_elem[wf_c, el_c, bslot]
            b_tdur = graph.bd_timer[wf_c, el_c, bslot]
            b_mname = graph.bd_msg[wf_c, el_c, bslot]
            b_cvar = graph.bd_corr[wf_c, el_c, bslot]
            es = eslot(bslot)
            # timer boundary arm (oracle writes TimerIntent.CREATE)
            es = put(
                es, arm_b & (b_tdur >= 0),
                valid=True, rtype=RT_CMD, vtype=VT_TIMER, intent=int(TI.CREATE),
                key=jnp.int64(-1), elem=b_elem, aux_key=batch.key,
                deadline=now + jnp.maximum(b_tdur, 0),
            )
            # message boundary arm: correlation key from this row's payload
            b_cvar_c = jnp.clip(b_cvar, 0, v - 1)
            b_cvt = batch.v_vt[rows, b_cvar_c].astype(jnp.int32)
            b_cbits = jnp.where(
                b_cvt == int(COND_VT_STR),
                batch.v_str[rows, b_cvar_c],
                jax.lax.bitcast_convert_type(
                    batch.v_num[rows, b_cvar_c], jnp.int32
                ),
            )
            b_extractable = (b_cvar >= 0) & (
                (b_cvt == int(COND_VT_STR))
                | (b_cvt == int(COND_VT_NUM))
                | (b_cvt == int(COND_VT_BOOL))
            )
            es = put(
                es, arm_b & (b_mname > 0) & b_extractable,
                valid=True, rtype=RT_CMD, vtype=VT_MSUB, intent=int(MS.OPEN),
                key=jnp.int64(-1), elem=b_elem, type_id=b_mname,
                retries=b_cvt, worker=b_cbits,
                instance_key=batch.instance_key, aux_key=batch.key,
                wf=pid_col,
            )
            es = put(
                es, arm_b & (b_mname > 0) & ~b_extractable,
                valid=True, rtype=RT_CMD, vtype=VT_INCIDENT, intent=0,
                key=jnp.int64(-1), elem=b_elem, aux_key=batch.key,
                rej=rb.ERR_CORRELATION_KEY,
            )
            # disarm: bslot-th armed timer cancel
            c_idx = jnp.min(
                jnp.where(cancel_mask, t_iota[None, :], t_cap), axis=1
            ).astype(jnp.int32)
            c_found = c_idx < t_cap
            c_clipd = jnp.clip(c_idx, 0, t_cap - 1)
            with jax.named_scope("zb_gather"):
                c_key, c_due, c_ik, c_elem = pops.fused_gather_rows(
                    [state.timer_key, state.timer_due,
                     state.timer_instance_key, state.timer_elem],
                    [pops.GatherOp(0, c_clipd), pops.GatherOp(1, c_clipd),
                     pops.GatherOp(2, c_clipd), pops.GatherOp(3, c_clipd)],
                )
            es = put(
                es, c_found,
                valid=True, rtype=RT_CMD, vtype=VT_TIMER, intent=int(TI.CANCEL),
                key=_col64(c_key), elem=c_elem,
                aux_key=batch.key, deadline=_col64(c_due),
                instance_key=_col64(c_ik),
            )
            cancel_mask = cancel_mask & (t_iota[None, :] != c_clipd[:, None])
            # disarm: message-boundary subscription closes (sends)
            es2 = eslot(bdw + bslot)
            es2 = put(
                es2, m_disarm_bd & (bslot < bd_n) & (b_mname > 0) & b_extractable,
                valid=True, rtype=RT_CMD, vtype=VT_MSUB, intent=int(MS.CLOSE),
                key=jnp.int64(-1), type_id=b_mname,
                retries=b_cvt, worker=b_cbits,
                instance_key=batch.instance_key, aux_key=batch.key,
                wf=pid_col,
            )

        # re-slotted step outputs for boundary-carrying rows
        step_slot = put(
            step_slot, m_createjob & has_bd,
            valid=True, rtype=RT_CMD, vtype=VT_JOB, intent=int(JI.CREATE),
            key=jnp.int64(-1), elem=batch.elem, aux_key=batch.key,
            type_id=emeta[:, graph_mod.EM_JOB_TYPE],
            retries=emeta[:, graph_mod.EM_JOB_RETRIES],
        )
        step_slot = put(
            step_slot, outmap_ok & has_bd,
            valid=True, rtype=RT_EVENT, vtype=VT_WI,
            intent=int(WI.ELEMENT_COMPLETED), key=batch.key, elem=batch.elem,
        )
        step_slot["v_vt"] = jnp.where(
            (outmap_ok & has_bd)[:, None], out_vt, step_slot["v_vt"]
        )
        step_slot["v_num"] = jnp.where(
            (outmap_ok & has_bd)[:, None], out_num, step_slot["v_num"]
        )
        step_slot["v_str"] = jnp.where(
            (outmap_ok & has_bd)[:, None], out_sid, step_slot["v_str"]
        )
        if graph.has_messages:
            step_slot = put(
                step_slot, sub_ok & has_bd,
                valid=True, rtype=RT_CMD, vtype=VT_MSUB, intent=int(MS.OPEN),
                key=jnp.int64(-1), elem=batch.elem,
                type_id=emeta[:, graph_mod.EM_MSG_NAME],
                retries=corr_vt_ext, worker=corr_bits_ext,
                instance_key=batch.instance_key, aux_key=batch.key,
                wf=pid_col,
            )
            step_slot = put(
                step_slot, sub_err & has_bd,
                valid=True, rtype=RT_CMD, vtype=VT_INCIDENT, intent=0,
                key=jnp.int64(-1), elem=batch.elem, aux_key=batch.key,
                rej=rb.ERR_CORRELATION_KEY,
            )
            # TERMINATE_CATCH_EVENT: close the element's own subscription
            step_slot = put(
                step_slot,
                m_term_catch & (emeta[:, graph_mod.EM_MSG_NAME] > 0)
                & corr_extractable,
                valid=True, rtype=RT_CMD, vtype=VT_MSUB, intent=int(MS.CLOSE),
                key=jnp.int64(-1), type_id=emeta[:, graph_mod.EM_MSG_NAME],
                retries=corr_vt_ext, worker=corr_bits_ext,
                instance_key=batch.instance_key, aux_key=batch.key,
                wf=pid_col,
            )
        # TERMINATE_JOB_TASK: cancel the instance's job, then TERMINATED
        job_key_inst = jnp.where(ei_found, ei_i64_rows[:, EIL_JOB_KEY], -1)
        tj_found, tj_slot = pops.lookup(
            state.job_map, job_key_inst, m_term_job & (job_key_inst > 0)
        )
        tj_clip = jnp.clip(tj_slot, 0, m_cap - 1)
        mask_jcancel = m_term_job & (job_key_inst > 0)
        step_slot = put(
            step_slot, mask_jcancel,
            valid=True, rtype=RT_CMD, vtype=VT_JOB, intent=int(JI.CANCEL),
            key=job_key_inst, elem=batch.elem, aux_key=batch.key,
            type_id=jnp.where(tj_found, state.job_type[tj_clip], 0),
            retries=jnp.int32(-1),  # JobRecord default — oracle sends a
            # bare record: type + headers only, no payload
        )
        step_slot["v_vt"] = jnp.where(
            mask_jcancel[:, None], jnp.int8(0), step_slot["v_vt"]
        )
        step_slot["v_num"] = jnp.where(
            mask_jcancel[:, None], jnp.float32(0), step_slot["v_num"]
        )
        step_slot["v_str"] = jnp.where(
            mask_jcancel[:, None], jnp.int32(0), step_slot["v_str"]
        )
        # TERMINATE_CATCH_EVENT's own timer scan (slots 2BD+1..3BD): the
        # oracle writes these cancels between the step output and
        # TERMINATED; a timer both disarmed and terminate-scanned cancels
        # TWICE, exactly like the oracle's two passes over self.timers
        tc_mask = m_cancel_timers[:, None] & timer_armed_on
        for t in range(bdw):
            tc_idx = jnp.min(
                jnp.where(tc_mask, t_iota[None, :], t_cap), axis=1
            ).astype(jnp.int32)
            tc_found = tc_idx < t_cap
            tc_clipd = jnp.clip(tc_idx, 0, t_cap - 1)
            with jax.named_scope("zb_gather"):
                tc_key, tc_due, tc_ik, tc_elem = pops.fused_gather_rows(
                    [state.timer_key, state.timer_due,
                     state.timer_instance_key, state.timer_elem],
                    [pops.GatherOp(0, tc_clipd), pops.GatherOp(1, tc_clipd),
                     pops.GatherOp(2, tc_clipd), pops.GatherOp(3, tc_clipd)],
                )
            es3 = eslot(2 * bdw + 1 + t)
            es3 = put(
                es3, tc_found,
                valid=True, rtype=RT_CMD, vtype=VT_TIMER, intent=int(TI.CANCEL),
                key=_col64(tc_key), elem=tc_elem,
                aux_key=batch.key, deadline=_col64(tc_due),
                instance_key=_col64(tc_ik),
            )
            tc_mask = tc_mask & (t_iota[None, :] != tc_clipd[:, None])

        term_tail = eslot(3 * bdw + 1)
        term_tail = put(
            term_tail, m_term_job | m_term_catch,
            valid=True, rtype=RT_EVENT, vtype=VT_WI,
            intent=int(WI.ELEMENT_TERMINATED), key=batch.key, elem=batch.elem,
        )
        e0 = put(
            e0, m_term_elem,
            valid=True, rtype=RT_EVENT, vtype=VT_WI,
            intent=int(WI.ELEMENT_TERMINATED), key=batch.key, elem=batch.elem,
        )
        # ELEMENT_TERMINATED with a pending boundary: the token continues
        # at the boundary event with the stored trigger payload
        cont_vt, cont_sid, cont_num = unpack_payload(ei_pay_rows)
        e0 = put(
            e0, m_bd_continue,
            valid=True, rtype=RT_EVENT, vtype=VT_WI,
            intent=int(WI.BOUNDARY_EVENT_OCCURRED), key=key0,
            elem=pending_bd,
        )
        e0["v_vt"] = jnp.where(
            m_bd_continue[:, None], cont_vt.astype(jnp.int8), e0["v_vt"]
        )
        e0["v_num"] = jnp.where(m_bd_continue[:, None], cont_num, e0["v_num"])
        e0["v_str"] = jnp.where(m_bd_continue[:, None], cont_sid, e0["v_str"])

    # jev_completed payload = job payload (record payload already in columns)
    # (value defaults carry batch payload, which is the job's — correct)

    # --- fork slots (parallel split + multi-instance) + assemble [B, E]
    em = {}
    for name in e0:
        parts = [e0[name], e1[name]] + [
            extra_slots[i][name] if i in extra_slots
            else jnp.zeros_like(e0[name])
            for i in range(2, e_w)
        ]
        em[name] = jnp.stack(parts, axis=1)  # [B, E] or [B, E, V]

    # fork_flows [B, F<=E] rows rode the phase-A fused graph gather
    fan_out = fork_flows.shape[1]
    for f in range(min(fan_out, e_w)):
        mask_f = m_psplit & (f < out_count)
        em["valid"] = em["valid"].at[:, f].set(
            jnp.where(mask_f, True, em["valid"][:, f])
        )
        for name, val in (
            ("rtype", RT_EVENT), ("vtype", VT_WI),
            ("intent", int(WI.SEQUENCE_FLOW_TAKEN)),
        ):
            em[name] = em[name].at[:, f].set(
                jnp.where(mask_f, val, em[name][:, f])
            )
        em["key"] = em["key"].at[:, f].set(
            jnp.where(mask_f, wf_base + _KEY_STEP * f, em["key"][:, f])
        )
        em["elem"] = em["elem"].at[:, f].set(
            jnp.where(mask_f, fork_flows[:, f], em["elem"][:, f])
        )
        for name in ("wf", "instance_key", "scope_key"):
            em[name] = em[name].at[:, f].set(
                jnp.where(mask_f, getattr(batch, name), em[name][:, f])
            )
        for name in ("v_vt", "v_num", "v_str"):
            em[name] = em[name].at[:, f].set(
                jnp.where(mask_f[:, None], getattr(batch, name), em[name][:, f])
            )
        em["src"] = em["src"].at[:, f].set(rows)

    if graph.has_multi_instance:
        # multi-instance fan-out (oracle _h_multi_instance_split,
        # cardinality form): one body token per iteration, each carrying
        # loopCounter = i+1; the container completes when the last body
        # token is consumed (token counting, same as the parallel join)
        mi_card = emeta[:, graph_mod.EM_MI_CARD]
        lv = graph.mi_loop_var
        for f in range(e_w):  # emit_width covers the max cardinality
            mask_f = m_mi & (f < mi_card)
            em["valid"] = em["valid"].at[:, f].set(
                jnp.where(mask_f, True, em["valid"][:, f])
            )
            for name, val in (
                ("rtype", RT_EVENT), ("vtype", VT_WI),
                ("intent", int(WI.START_EVENT_OCCURRED)),
            ):
                em[name] = em[name].at[:, f].set(
                    jnp.where(mask_f, val, em[name][:, f])
                )
            em["key"] = em["key"].at[:, f].set(
                jnp.where(mask_f, wf_base + _KEY_STEP * f, em["key"][:, f])
            )
            em["elem"] = em["elem"].at[:, f].set(
                jnp.where(mask_f, start_ev, em["elem"][:, f])
            )
            em["wf"] = em["wf"].at[:, f].set(
                jnp.where(mask_f, batch.wf, em["wf"][:, f])
            )
            em["instance_key"] = em["instance_key"].at[:, f].set(
                jnp.where(mask_f, batch.instance_key, em["instance_key"][:, f])
            )
            em["scope_key"] = em["scope_key"].at[:, f].set(
                jnp.where(mask_f, batch.key, em["scope_key"][:, f])
            )
            mi_vt = batch.v_vt.at[:, lv].set(jnp.int8(COND_VT_NUM))
            mi_num = batch.v_num.at[:, lv].set(jnp.float32(f + 1))
            em["v_vt"] = em["v_vt"].at[:, f].set(
                jnp.where(mask_f[:, None], mi_vt, em["v_vt"][:, f])
            )
            em["v_num"] = em["v_num"].at[:, f].set(
                jnp.where(mask_f[:, None], mi_num, em["v_num"][:, f])
            )
            em["v_str"] = em["v_str"].at[:, f].set(
                jnp.where(mask_f[:, None], batch.v_str, em["v_str"][:, f])
            )
            em["src"] = em["src"].at[:, f].set(rows)

    # -------- state scatters: fused phase-E commits --------
    # Every table write below is expressed as a pops.TableOp and committed
    # through pops.fused_table_commit: ONE pallas mega-pass per table group
    # (element instances, jobs, timers) that keeps the tables VMEM-resident
    # and applies the whole ~20-op write tail in a single serial pass — the
    # per-record cost is a handful of VPU instructions instead of ~20ns of
    # per-index DMA issue PER OP (PERF_NOTES round-4 cost model). Where the
    # engine-boot autotune picked the unfused path (or off-TPU), the commit
    # degrades to the exact previous op chain, so the CPU parity suites pin
    # the semantics bit-for-bit. Op order matches the old op-major chain;
    # the only cross-op row sharing between records is through commutative
    # "add" ops (token counters), so the mega-pass's chunk-major execution
    # is observationally identical. The 64-bit tables are planes at rest
    # (tpu/state.py): they enter the commit as they are, and what a wave
    # writes into them is converted at wave size (TableOp vals).
    ei_k32 = state.ei_i32.shape[1]
    T_EI32, T_EI64, T_EIPAY, T_EIFREE, T_EIIDX = range(5)
    ei_ops = []

    def _col_op(k, col, val):
        """([B, k] vals, [B, k] mask) pair writing ``val`` into one column."""
        if jnp.ndim(val) == 0:
            val = jnp.full((b,), val, jnp.int32)
        vals = jnp.zeros((b, k), jnp.int32).at[:, col].set(
            val.astype(jnp.int32)
        )
        mask = jnp.zeros((b, k), bool).at[:, col].set(True)
        return vals, mask

    # token counters: one select-by-kind accumulate on the scope row (a
    # record is exactly one of consume / parallel-split / join-complete,
    # so the old per-kind accumulate chain merges into one commutative op)
    # nin_rec (join fan-in per record) rode the round-9a fused read pass
    tok_m = m_consume | m_psplit | completer
    tok_v = jnp.where(
        m_consume, jnp.int32(-1),
        jnp.where(m_psplit, out_count - 1, -(nin_rec - 1)),
    )
    tok_vals, tok_mask = _col_op(ei_k32, EI_TOKENS, tok_v)
    ei_ops.append(pops.TableOp(T_EI32, "add", sc_clip, tok_m, tok_vals, tok_mask))
    if graph.has_boundaries:
        # non-interrupting boundary fire: the host's scope gains a token
        # for the boundary path (oracle: scope.active_tokens += 1)
        bd_vals, bd_mask = _col_op(ei_k32, EI_TOKENS, jnp.ones((b,), jnp.int32))
        ei_ops.append(pops.TableOp(
            T_EI32, "add", jnp.clip(inst_scope_slot, 0, n_cap - 1),
            ttrig_bd_non | corr_bd_non, bd_vals, bd_mask,
        ))
    # start-trigger / multi-instance container token counts (own row; the
    # container holds one token per body iteration — disjoint step kinds)
    tokset_m = m_trigstart
    tokset_v = jnp.ones((b,), jnp.int32)
    if graph.has_multi_instance:
        tokset_m = tokset_m | m_mi
        tokset_v = jnp.where(m_mi, emeta[:, graph_mod.EM_MI_CARD], 1)
    ts_vals, ts_mask = _col_op(ei_k32, EI_TOKENS, tokset_v)
    ei_ops.append(pops.TableOp(T_EI32, "set", ei_clip, tokset_m, ts_vals, ts_mask))

    # scope payload on consume (oracle: scope value.payload = record
    # payload) and on an element's output mapping (the merged payload, so
    # the next element merges into what this one added) — EXCEPT
    # multi-instance containers, whose iteration-local variables must not
    # leak into the container payload. Two such rows of one scope never
    # share a segment (TpuPartitionEngine._route_wave cuts between them
    # where a scope can hold several tokens), so each merge above read the
    # scope's payload as the oracle's sequential loop leaves it
    b_pay = pack_payload(batch.v_vt, batch.v_str, batch.v_num)
    outmap_pay_m = outmap_ok & sc_found
    if graph.has_multi_instance:
        scope_elem_c = jnp.clip(
            jnp.where(sc_found, sc_rows[:, EI_ELEM], 0),
            0, graph.elem_type.shape[1] - 1,
        )
        scope_wf_c = jnp.clip(
            jnp.where(sc_found, sc_rows[:, EI_WF], 0),
            0, graph.elem_type.shape[0] - 1,
        )
        mi_scope = graph.mi_cardinality[scope_wf_c, scope_elem_c] > 0
        consume_pay_m = m_consume & ~mi_scope
        outmap_pay_m = outmap_pay_m & ~mi_scope
    else:
        consume_pay_m = m_consume
    scope_pay = jnp.where(
        outmap_pay_m[:, None], pack_payload(out_vt, out_sid, out_num), b_pay
    )
    ei_ops.append(pops.TableOp(
        T_EIPAY, "set", sc_clip,
        _last_writer(sc_clip, consume_pay_m | outmap_pay_m, n_cap), scope_pay,
    ))
    # scope state transition by consume completer
    cc_vals, cc_mask = _col_op(
        ei_k32, EI_STATE, jnp.int32(int(WI.ELEMENT_COMPLETING))
    )
    ei_ops.append(pops.TableOp(
        T_EI32, "set", sc_clip, consume_completer, cc_vals, cc_mask
    ))
    # -- own-row transitions, ONE composed write per dtype family ---------
    # Every record is exactly one step kind (the guard predicates are
    # mutually exclusive per record, and the no-concurrent-transition
    # guards exclude two records transitioning the same instance row in
    # one round), so the per-kind column writes compose into a single
    # select-by-kind row write instead of one write per kind.
    if graph.has_boundaries:
        bd_int_any = ttrig_bd_int | corr_bd_int
        term_all = m_term_job | m_term_catch | m_term_elem
    else:
        bd_int_any = jnp.zeros((b,), bool)
        term_all = jnp.zeros((b,), bool)
    ei_remove = outmap_ok | m_complete_proc | m_bd_continue

    own_is_aik = jev_completed | ttrig_catch | bd_int_any
    own_slot = jnp.where(own_is_aik, aik_clip, ei_clip)
    completing = jev_completed | ttrig_catch
    own_state_m = inmap_ok | completing | bd_int_any | term_all | ei_remove
    own_state_v = jnp.where(
        ei_remove, jnp.int32(-1),                      # removal wins last
        jnp.where(
            term_all, jnp.int32(int(WI.ELEMENT_TERMINATED)),
            jnp.where(
                bd_int_any, jnp.int32(int(WI.ELEMENT_TERMINATING)),
                jnp.where(
                    completing, jnp.int32(int(WI.ELEMENT_COMPLETING)),
                    jnp.int32(int(WI.ELEMENT_ACTIVATED)),
                ),
            ),
        ),
    )
    own_vals = jnp.zeros((b, ei_k32), jnp.int32)
    own_mask = jnp.zeros((b, ei_k32), bool)
    own_vals = own_vals.at[:, EI_STATE].set(own_state_v)
    own_mask = own_mask.at[:, EI_STATE].set(own_state_m)
    if graph.has_boundaries:
        # pending boundary element recorded with the TERMINATING write
        own_vals = own_vals.at[:, EI_PENDING_BD].set(
            jnp.where(ttrig_bd_int, trig_elem, corr_bd_elem)
        )
        own_mask = own_mask.at[:, EI_PENDING_BD].set(bd_int_any)
    own_active = own_state_m
    ei_ops.append(pops.TableOp(
        T_EI32, "set", own_slot, own_active, own_vals, own_mask
    ))

    # own-row payloads: input mapping writes the mapped document, job
    # completion / message-boundary interruption write the record payload
    own_pay_m = inmap_ok | jev_completed | (corr_bd_int if graph.has_boundaries
                                            else jnp.zeros((b,), bool))
    inmap_pay = pack_payload(in_vt, in_sid, in_num)
    own_pay = jnp.where(inmap_ok[:, None], inmap_pay, b_pay)
    ei_ops.append(pops.TableOp(
        T_EIPAY, "set", own_slot,
        _last_writer(own_slot, own_pay_m, n_cap), own_pay,
    ))

    # own-row i64 columns (job-key attach/detach, removal key clear)
    jobkey_m = jev_completed | (jev_created & aik_found)
    jobkey_v = jnp.where(jev_completed, jnp.int64(-1), batch.key)
    ei64_slot = jnp.where(jobkey_m, aik_clip, ei_clip)
    v2 = pops.vec64_to_planes(jobkey_v)
    neg2 = pops.vec64_to_planes(jnp.full((b,), -1, jnp.int64))
    ei64_vals = jnp.zeros((b, state.ei_i64.shape[1]), jnp.int32)
    ei64_mask = jnp.zeros((b, state.ei_i64.shape[1]), bool)
    ei64_vals = ei64_vals.at[:, 2 * EIL_JOB_KEY].set(v2[:, 0])
    ei64_vals = ei64_vals.at[:, 2 * EIL_JOB_KEY + 1].set(v2[:, 1])
    ei64_mask = ei64_mask.at[:, 2 * EIL_JOB_KEY].set(jobkey_m)
    ei64_mask = ei64_mask.at[:, 2 * EIL_JOB_KEY + 1].set(jobkey_m)
    ei64_vals = jnp.where(
        (ei_remove & ~jobkey_m)[:, None],
        jnp.zeros_like(ei64_vals).at[:, 2 * EIL_KEY].set(neg2[:, 0])
        .at[:, 2 * EIL_KEY + 1].set(neg2[:, 1]),
        ei64_vals,
    )
    ei64_mask = jnp.where(
        (ei_remove & ~jobkey_m)[:, None],
        jnp.zeros_like(ei64_mask).at[:, 2 * EIL_KEY].set(True)
        .at[:, 2 * EIL_KEY + 1].set(True),
        ei64_mask,
    )
    ei_ops.append(pops.TableOp(
        T_EI64, "set", ei64_slot, jobkey_m | ei_remove, ei64_vals, ei64_mask
    ))
    # no map delete: the removed row's key column is cleared above, and
    # every lookup verifies against it — stale index/map entries are inert
    ei_map = state.ei_map

    # inserts: CREATE command roots + START_STATEFUL children (+ replayed
    # CREATED events whose instance is missing)
    ins_root = m_create
    ins_child = m_startst
    ins = ins_root | ins_child | ins_replay
    ins_key = jnp.where(ins_root, key0, jnp.where(ins_child, key0, batch.key))
    ins_elem = jnp.where(ins_root, 0, jnp.where(ins_child, ftarget, batch.elem))
    ins_parent = jnp.where(ins_child, sc_slot, -1)
    ins_ikey = jnp.where(ins_root, key0, batch.instance_key)
    # free-slot ring pop (replaces the full-table free scan): slots freed
    # this round enter at push and are never re-allocated in the same
    # round (matches the old scan, which read round-start state). The
    # ring read itself rode the round-9a fused read pass (ei_pop_slot).
    ins_slot = jnp.where(
        ins & ei_ring_ok, ei_pop_slot, n_cap
    ).astype(jnp.int32)
    ei_overflow = jnp.any(ins & ~ei_ring_ok)
    free_ei_pop_new = state.free_ei_pop + jnp.sum(ins, dtype=jnp.int64)
    # dedup pushes per slot: two removal records for the same row in one
    # batch (e.g. a client-retried command) must free the slot ONCE, or
    # the ring later hands the row to two inserts
    ei_push_m = _last_writer(ei_clip, ei_remove, n_cap)
    ei_rm_rank = _excl_cumsum(ei_push_m.astype(jnp.int32))
    ei_push_idx = state.free_ei_push + ei_rm_rank.astype(jnp.int64)
    ei_ops.append(pops.TableOp(
        T_EIFREE, "set", (ei_push_idx % n_cap).astype(jnp.int32),
        ei_push_m, ei_clip,
    ))
    free_ei_push_new = state.free_ei_push + jnp.sum(ei_push_m, dtype=jnp.int64)
    # one row write per dtype group (the point of the packed layout)
    ei_i32_rows = jnp.stack(
        [ins_elem,
         jnp.full((b,), int(WI.ELEMENT_READY), jnp.int32),
         batch.wf, ins_parent, jnp.zeros((b,), jnp.int32),
         jnp.full((b,), -1, jnp.int32)], axis=-1,  # no pending boundary
    )
    ei_ops.append(pops.TableOp(T_EI32, "set", ins_slot, ins, ei_i32_rows))
    ei_i64_rows = jnp.stack(
        [ins_key, ins_ikey, jnp.full((b,), -1, jnp.int64)], axis=-1
    )
    ei_ops.append(pops.TableOp(
        T_EI64, "set", ins_slot, ins, pops.i64_to_planes(ei_i64_rows)
    ))
    ei_ops.append(pops.TableOp(T_EIPAY, "set", ins_slot, ins, b_pay))
    ei_icap = state.ei_index.shape[0]
    ei_ops.append(pops.TableOp(
        T_EIIDX, "set", ((ins_key // 5) & (ei_icap - 1)).astype(jnp.int32),
        ins, ins_slot,
    ))
    if graph.has_messages:
        # correlate arrival → instance completes with the message payload
        corr_vals, corr_mask = _col_op(
            ei_k32, EI_STATE, jnp.int32(int(WI.ELEMENT_COMPLETING))
        )
        ei_ops.append(pops.TableOp(
            T_EI32, "set", aik_clip, corr_inst_ok, corr_vals, corr_mask
        ))
        ei_ops.append(pops.TableOp(
            T_EIPAY, "set", aik_clip,
            _last_writer(aik_clip, corr_inst_ok, n_cap), b_pay,
        ))

    ei_i32_arr, ei_i64_arr, ei_pay, free_ei_arr, ei_index_arr = (
        pops.fused_table_commit(
            [state.ei_i32, state.ei_i64, state.ei_pay, state.free_ei,
             state.ei_index],
            ei_ops,
        )
    )

    # ---------------- job table (fused commit) ----------------
    T_J32, T_J64, T_JPAY, T_JFREE, T_JIDX = range(5)
    job_k32 = state.job_i32.shape[1]
    job_ops = []
    # job ring pop indices + the ring read hoisted into the round-9a
    # fused read pass (job_pop_slot)
    j_slot = jnp.where(
        job_ins & job_ring_ok, job_pop_slot, m_cap
    ).astype(jnp.int32)
    job_overflow = jnp.any(job_ins & ~job_ring_ok)
    free_job_pop_new = state.free_job_pop + jnp.sum(job_ins, dtype=jnp.int64)
    job_i32_rows = jnp.stack(
        [jnp.full((b,), int(JI.CREATED), jnp.int32),
         batch.elem, batch.wf, batch.type_id, batch.retries,
         jnp.zeros((b,), jnp.int32)], axis=-1,
    )
    job_ops.append(pops.TableOp(T_J32, "set", j_slot, job_ins, job_i32_rows))
    job_i64_rows = jnp.stack(
        [job_base, batch.instance_key, batch.aux_key,
         jnp.full((b,), -1, jnp.int64)], axis=-1,
    )
    job_ops.append(pops.TableOp(
        T_J64, "set", j_slot, job_ins, pops.i64_to_planes(job_i64_rows)
    ))
    job_ops.append(pops.TableOp(T_JPAY, "set", j_slot, job_ins, b_pay))
    job_icap = state.job_index.shape[0]
    job_ops.append(pops.TableOp(
        T_JIDX, "set", ((job_base // 5) & (job_icap - 1)).astype(jnp.int32),
        job_ins, j_slot,
    ))
    job_map = state.job_map

    # transitions: every record is one job step kind and all kinds target
    # jb_clip, so the per-kind column writes compose into ONE row write
    # per dtype family (select-by-kind values)
    job_rm = jcomp_ok | jcan_ok
    jstate_m = jact_ok | jfail_ok | jtime_ok | job_rm
    jstate_v = jnp.where(
        job_rm, jnp.int32(-1),
        jnp.where(
            jtime_ok, jnp.int32(int(JI.TIMED_OUT)),
            jnp.where(
                jfail_ok, jnp.int32(int(JI.FAILED)),
                jnp.int32(int(JI.ACTIVATED)),
            ),
        ),
    )
    jretries_m = jact_ok | jfail_ok | jret_ok
    jb_vals = jnp.zeros((b, job_k32), jnp.int32)
    jb_mask = jnp.zeros((b, job_k32), bool)
    jb_vals = jb_vals.at[:, JB_STATE].set(jstate_v)
    jb_mask = jb_mask.at[:, JB_STATE].set(jstate_m)
    jb_vals = jb_vals.at[:, JB_RETRIES].set(batch.retries)
    jb_mask = jb_mask.at[:, JB_RETRIES].set(jretries_m)
    jb_vals = jb_vals.at[:, JB_WORKER].set(batch.worker)
    jb_mask = jb_mask.at[:, JB_WORKER].set(jact_ok)
    job_ops.append(pops.TableOp(
        T_J32, "set", jb_clip, jstate_m | jret_ok, jb_vals, jb_mask
    ))

    jd2 = pops.vec64_to_planes(batch.deadline)
    jneg2 = pops.vec64_to_planes(jnp.full((b,), -1, jnp.int64))
    j64_vals = jnp.zeros((b, state.job_i64.shape[1]), jnp.int32)
    j64_mask = jnp.zeros((b, state.job_i64.shape[1]), bool)
    j64_vals = j64_vals.at[:, 2 * JBL_DEADLINE].set(jd2[:, 0])
    j64_vals = j64_vals.at[:, 2 * JBL_DEADLINE + 1].set(jd2[:, 1])
    j64_mask = j64_mask.at[:, 2 * JBL_DEADLINE].set(jact_ok)
    j64_mask = j64_mask.at[:, 2 * JBL_DEADLINE + 1].set(jact_ok)
    j64_vals = jnp.where(
        job_rm[:, None],
        jnp.zeros_like(j64_vals).at[:, 2 * JBL_KEY].set(jneg2[:, 0])
        .at[:, 2 * JBL_KEY + 1].set(jneg2[:, 1]),
        j64_vals,
    )
    j64_mask = jnp.where(
        job_rm[:, None],
        jnp.zeros_like(j64_mask).at[:, 2 * JBL_KEY].set(True)
        .at[:, 2 * JBL_KEY + 1].set(True),
        j64_mask,
    )
    job_ops.append(pops.TableOp(
        T_J64, "set", jb_clip, jact_ok | job_rm, j64_vals, j64_mask
    ))

    jpay_m = jact_ok | jfail_ok
    jpay = jnp.where(
        jfail_ok[:, None], pack_payload(fail_vt, fail_sid, fail_num), b_pay
    )
    job_ops.append(pops.TableOp(T_JPAY, "set", jb_clip, jpay_m, jpay))
    # dedup per slot (see the ei ring push)
    job_push_m = _last_writer(jb_clip, job_rm, m_cap)
    job_rm_rank = _excl_cumsum(job_push_m.astype(jnp.int32))
    job_push_idx = state.free_job_push + job_rm_rank.astype(jnp.int64)
    job_ops.append(pops.TableOp(
        T_JFREE, "set", (job_push_idx % m_cap).astype(jnp.int32),
        job_push_m, jb_clip,
    ))
    free_job_push_new = state.free_job_push + jnp.sum(job_push_m, dtype=jnp.int64)

    job_i32_arr, job_i64_arr, job_pay_arr, free_job_arr, job_index_arr = (
        pops.fused_table_commit(
            [state.job_i32, state.job_i64, state.job_pay, state.free_job,
             state.job_index],
            job_ops,
        )
    )

    # ---------------- join cleanup ----------------
    if graph.has_parallel_joins:
        join_key_arr = pops.masked_vec64_update(
            join_key_arr, arr_slot, completer,
            jnp.full((b,), -1, jnp.int64),
        )
        join_nin_arr = pops.masked_lane_update(
            join_nin_arr, arr_slot, completer, jnp.zeros((b,), jnp.int32)
        )
        arrived = pops.masked_row_update(
            arrived.astype(jnp.int32), arr_slot, completer,
            jnp.zeros((b, arrived.shape[1]), jnp.int32),
        ).astype(bool)
        stamp = pops.masked_row_update(
            stamp, arr_slot, completer,
            jnp.full((b, stamp.shape[1]), -1, jnp.int32),
        )
        join_map = pops.delete(jmap, join_key, completer)
    else:
        join_map = jmap

    # ---------------- timer table ----------------
    if graph.has_timers:
        # fused commit over the timer bookkeeping columns (the 64-bit
        # columns are [TM, 2] i32 planes, elem/wf 1D lane tables): the 8
        # insert / remove writes ride one mega-pass; the hashmap
        # insert/delete stay their own probe kernels
        t_ins = m_tcreate
        tfree = _first_true_indices(col_neg(state.timer_key), b)
        t_rank = _excl_cumsum(t_ins.astype(jnp.int32))
        t_slot = tfree[jnp.clip(t_rank, 0, b - 1)]
        timer_overflow = jnp.any(t_ins & (t_slot >= t_cap))
        t_rm = ttrig_ok | tcan_ok
        tneg_pl = pops.vec64_to_planes(jnp.full((b,), -1, jnp.int64))
        T_TK, T_TD, T_TA, T_TIK, T_TE, T_TW = range(6)
        timer_ops = [
            pops.TableOp(T_TK, "set", t_slot, t_ins, pops.vec64_to_planes(key0)),
            pops.TableOp(
                T_TD, "set", t_slot, t_ins, pops.vec64_to_planes(batch.deadline)
            ),
            pops.TableOp(
                T_TA, "set", t_slot, t_ins, pops.vec64_to_planes(batch.aux_key)
            ),
            pops.TableOp(
                T_TIK, "set", t_slot, t_ins,
                pops.vec64_to_planes(batch.instance_key),
            ),
            pops.TableOp(T_TE, "set", t_slot, t_ins, batch.elem),
            pops.TableOp(T_TW, "set", t_slot, t_ins, batch.wf),
            pops.TableOp(T_TK, "set", tm_clip, t_rm, tneg_pl),
            pops.TableOp(T_TD, "set", tm_clip, t_rm, tneg_pl),
        ]
        (timer_key_arr, timer_due_arr, timer_aik_arr, timer_ik_arr,
         timer_elem_arr, timer_wf_arr) = pops.fused_table_commit(
            [state.timer_key, state.timer_due, state.timer_aik,
             state.timer_instance_key, state.timer_elem, state.timer_wf],
            timer_ops,
        )
        timer_map, _t_ok = pops.insert(state.timer_map, key0, t_slot, t_ins)
        timer_map = pops.delete(timer_map, batch.key, t_rm)
    else:
        timer_overflow = jnp.zeros((), bool)
        timer_key_arr = state.timer_key
        timer_due_arr = state.timer_due
        timer_aik_arr = state.timer_aik
        timer_ik_arr = state.timer_instance_key
        timer_elem_arr = state.timer_elem
        timer_wf_arr = state.timer_wf
        timer_map = state.timer_map

    # ---------------- message tables ----------------
    if graph.has_messages:
        neg64 = jnp.full((b,), -1, jnp.int64)
        # subscription inserts (OPEN) / removals (CLOSE)
        msfree = _first_true_indices(col_neg(state.msub_ckey), b)
        ms_rank = _excl_cumsum(open_ok.astype(jnp.int32))
        ms_slot_new = msfree[jnp.clip(ms_rank, 0, b - 1)]
        msub_overflow = jnp.any(open_ok & (ms_slot_new >= ms_cap))
        msub_ckey_arr = pops.masked_vec64_update(
            state.msub_ckey, ms_slot_new, open_ok, ckey
        )
        msub_i32_arr = pops.masked_row_update(
            state.msub_i32, ms_slot_new, open_ok,
            jnp.stack(
                [batch.type_id, batch.retries, batch.worker, batch.wf], axis=-1
            ),
        )
        msub_i64_arr = pops.masked_row_update(
            state.msub_i64, ms_slot_new, open_ok,
            pops.i64_to_planes(
                jnp.stack([batch.instance_key, batch.aux_key], axis=-1)
            ),
        )
        msub_map_arr, msub_ins_ok = pops.insert(
            state.msub_map, ckey, ms_slot_new, open_ok
        )
        msub_ckey_arr = pops.masked_vec64_update(
            msub_ckey_arr, msub_clip, close_ok, neg64
        )
        msub_map_arr = pops.delete(msub_map_arr, ckey, close_ok)

        # stored messages (PUBLISH with TTL) / deletions
        mgfree = _first_true_indices(col_neg(state.msg_key), b)
        mg_rank = _excl_cumsum(pub_store.astype(jnp.int32))
        mg_slot_new = mgfree[jnp.clip(mg_rank, 0, b - 1)]
        msg_overflow = jnp.any(pub_store & (mg_slot_new >= mg_cap))
        msg_key_arr = pops.masked_vec64_update(
            state.msg_key, mg_slot_new, pub_store, key0
        )
        msg_ckey_arr = pops.masked_vec64_update(
            state.msg_ckey, mg_slot_new, pub_store, ckey
        )
        msg_i32_arr = pops.masked_row_update(
            state.msg_i32, mg_slot_new, pub_store,
            jnp.stack(
                [batch.type_id, batch.retries, batch.worker,
                 batch.aux2_key.astype(jnp.int32)], axis=-1,
            ),
        )
        msg_deadline_arr = pops.masked_vec64_update(
            state.msg_deadline, mg_slot_new, pub_store, now + batch.deadline
        )
        msg_pay_arr = pops.masked_row_update(
            state.msg_pay, mg_slot_new, pub_store, b_pay
        )
        msg_map_arr, msg_ins_ok = pops.insert(
            state.msg_map, ckey, mg_slot_new, pub_store
        )
        msg_key_arr = pops.masked_vec64_update(
            msg_key_arr, mmsg_clip, del_ok, neg64
        )
        msg_deadline_arr = pops.masked_vec64_update(
            msg_deadline_arr, mmsg_clip, del_ok, neg64
        )
        msg_map_arr = pops.delete(msg_map_arr, ckey, del_ok)

        message_overflow = (
            msub_overflow | msg_overflow
            | ~jnp.all(msub_ins_ok == open_ok)
            | ~jnp.all(msg_ins_ok == pub_store)
        )
    else:
        msub_ckey_arr = state.msub_ckey
        msub_i32_arr = state.msub_i32
        msub_i64_arr = state.msub_i64
        msub_map_arr = state.msub_map
        msg_key_arr = state.msg_key
        msg_ckey_arr = state.msg_ckey
        msg_i32_arr = state.msg_i32
        msg_deadline_arr = state.msg_deadline
        msg_pay_arr = state.msg_pay
        msg_map_arr = state.msg_map
        message_overflow = jnp.zeros((), bool)

    # ---------------- output compaction ----------------
    flat_valid = em["valid"].reshape(-1)
    be = b * e_w
    take_idx = _first_true_indices(flat_valid, be)
    count = jnp.sum(flat_valid, dtype=jnp.int32)

    idx = jnp.clip(take_idx, 0, be - 1)

    # the compaction packs the whole emission record into TWO row gathers
    # (an i32 mega-matrix: scalars + v_str + bitcast v_num + i64 planes;
    # an i8 matrix: flags + v_vt) routed through the "emit" fused-gather
    # family — the per-dtype-group takes before this dominated the
    # emission tail at ~20ns/record of per-index issue apiece. The
    # bitcast/widen round-trips are exact, so the packed take is
    # bit-identical to per-field takes. The gathered pair IS the emission
    # as it leaves the program (``rb.StagedBatch``'s layout): nothing is
    # cut into columns again on the way out.

    def _flat(n):
        return em[n].reshape((be,) + em[n].shape[2:])

    with jax.named_scope("zb_emit"):
        i32_mat = jnp.concatenate(
            [jnp.stack([_flat(n).astype(jnp.int32) for n in rb.I32_COLS],
                       axis=-1),
             _flat("v_str"),
             jax.lax.bitcast_convert_type(_flat("v_num"), jnp.int32),
             pops.i64_to_planes(
                 jnp.stack([_flat(n) for n in rb.I64_COLS], axis=-1)
             )],
            axis=1,
        )
        i8_mat = jnp.concatenate(
            [jnp.stack([_flat("resp").astype(jnp.int8),
                        _flat("push").astype(jnp.int8)], axis=-1),
             _flat("v_vt")],
            axis=1,
        )
        taken_i32, taken_i8 = pops.fused_gather_rows(
            [i32_mat, i8_mat],
            [pops.GatherOp(0, idx), pops.GatherOp(1, idx)],
            family="emit",
        )
    # ``valid`` is the compacted prefix: the flag column written from the count
    out = rb.StagedBatch(
        i32=taken_i32,
        i8=jnp.concatenate(
            [(jnp.arange(be, dtype=jnp.int32) < count)
             .astype(jnp.int8)[:, None],
             taken_i8],
            axis=1,
        ),
    )

    new_state = EngineState(
        ei_i32=ei_i32_arr, ei_i64=ei_i64_arr,
        ei_pay=ei_pay, ei_map=ei_map, ei_index=ei_index_arr,
        free_ei=free_ei_arr, free_ei_pop=free_ei_pop_new,
        free_ei_push=free_ei_push_new,
        job_i32=job_i32_arr, job_i64=job_i64_arr,
        job_pay=job_pay_arr, job_map=job_map, job_index=job_index_arr,
        free_job=free_job_arr, free_job_pop=free_job_pop_new,
        free_job_push=free_job_push_new,
        join_key=join_key_arr, join_nin=join_nin_arr, join_arrived=arrived,
        join_pay=join_pay, join_pos_stamp=stamp, join_map=join_map,
        timer_key=timer_key_arr, timer_due=timer_due_arr,
        timer_aik=timer_aik_arr, timer_instance_key=timer_ik_arr,
        timer_elem=timer_elem_arr, timer_wf=timer_wf_arr, timer_map=timer_map,
        msub_ckey=msub_ckey_arr, msub_i32=msub_i32_arr,
        msub_i64=msub_i64_arr, msub_map=msub_map_arr,
        msg_key=msg_key_arr, msg_ckey=msg_ckey_arr, msg_i32=msg_i32_arr,
        msg_deadline=msg_deadline_arr, msg_pay=msg_pay_arr,
        msg_map=msg_map_arr,
        sub_key=state.sub_key, sub_type=state.sub_type,
        sub_worker=state.sub_worker, sub_credits=sub_credits,
        sub_timeout=state.sub_timeout, sub_valid=state.sub_valid,
        sub_rr=state.sub_rr,
        next_wf_key=next_wf_key, next_job_key=next_job_key,
    )
    # one small vector (``STATS`` names its entries, then ``MESSAGE_STATS``
    # where the graph has messages), so a collect fetches the wave's counts
    # and its overflow flag as one array
    message_stats = [
        jnp.sum(m, dtype=jnp.int32)
        for m in (msg_pub, pub_store, del_ok, open_corr, pub_corr)
    ] if graph.has_messages else []
    stats = jnp.stack([
        jnp.sum(valid, dtype=jnp.int32),
        jnp.sum(stepped, dtype=jnp.int32)
        + jnp.sum(job_cmd | job_ev | timer_cmd | m_create | m_created_ev
                  | msg_pub | msg_del | ms_open | ms_close | wisub_corr,
                  dtype=jnp.int32),
        count,
        jnp.sum(m_complete_proc & (batch.elem == 0), dtype=jnp.int32),
        (
            ei_overflow | job_overflow | join_overflow | timer_overflow
            | message_overflow
        ).astype(jnp.int32),
        *message_stats,
    ])
    return new_state, out, stats


step_jit = jit_registry.register_jit(
    "kernel.step",
    step_kernel,
    state_args=(1,),
    donate_argnums=(1,),
    static_argnames=("synthetic_workers",),
    max_signatures=4,
    notes="one signature per (synthetic_workers, wave shape) pair a "
    "serving process uses; the scheduler packs fixed-size waves",
)


# rows of one tick's command batch: the due rows beyond it stay due and
# ride the next tick (``count`` says how many were due in all)
TICK_LIMIT = 4096


def tick_kernel(state: EngineState, now) -> Tuple[RecordBatch, jax.Array]:
    """Due-timer and job-deadline scan → TIME_OUT / TRIGGER command batch
    (reference JobTimeOutStreamProcessor + the oracle's check_*_deadlines;
    ordered by key like the oracle's sorted iteration). The scan and the
    sort run on the tables' 32-bit planes; int64 is made of the batch's
    rows only, at most ``TICK_LIMIT`` of them."""
    t_cap = state.timer_key.shape[0]
    m_cap = state.job_i32.shape[0]
    size = min(t_cap + m_cap, TICK_LIMIT)

    timer_due = ~col_neg(state.timer_key) & col_le(state.timer_due, 0, now)
    job_due = (
        (state.job_state == int(JI.ACTIVATED))
        & ~col_neg(state.job_i64, JBL_DEADLINE)
        & col_le(state.job_i64, JBL_DEADLINE, now)
    )
    due = jnp.concatenate([timer_due, job_due])
    # ascending by key = by (high word, low word unsigned); rows not due
    # sort behind every key, in table order
    key_hi = jnp.concatenate(
        [col_hi(state.timer_key), col_hi(state.job_i64, JBL_KEY)]
    )
    key_lo = jnp.concatenate(
        [col_lo(state.timer_key), col_lo(state.job_i64, JBL_KEY)]
    ).astype(jnp.uint32)
    _, _, order = jax.lax.sort(
        (
            jnp.where(due, key_hi, jnp.iinfo(jnp.int32).max),
            jnp.where(due, key_lo, 0),
            jnp.arange(t_cap + m_cap, dtype=jnp.int32),
        ),
        num_keys=3,
    )
    order = order[:size]
    count = jnp.sum(due, dtype=jnp.int32)

    is_timer = order < t_cap
    tidx = jnp.clip(order, 0, t_cap - 1)
    jidx = jnp.clip(order - t_cap, 0, m_cap - 1)
    job_i64_rows = pops.planes_to_i64(state.job_i64[jidx])

    def timer_col(planes):
        return _col64(planes[tidx])

    sel = jnp.arange(size, dtype=jnp.int32) < count
    tick_jb_vt, tick_jb_sid, tick_jb_num = unpack_payload(state.job_pay[jidx])
    out = RecordBatch(
        valid=sel,
        rtype=jnp.full((size,), RT_CMD, jnp.int32),
        vtype=jnp.where(
            is_timer, jnp.int32(VT_TIMER), jnp.int32(VT_JOB)
        ),
        intent=jnp.where(
            is_timer, jnp.int32(int(TI.TRIGGER)), jnp.int32(int(JI.TIME_OUT))
        ),
        key=jnp.where(
            is_timer, timer_col(state.timer_key), job_i64_rows[:, JBL_KEY]
        ),
        elem=jnp.where(is_timer, state.timer_elem[tidx], state.job_elem[jidx]),
        wf=jnp.where(is_timer, state.timer_wf[tidx], state.job_wf[jidx]),
        instance_key=jnp.where(
            is_timer, timer_col(state.timer_instance_key),
            job_i64_rows[:, JBL_IKEY],
        ),
        scope_key=jnp.full((size,), -1, jnp.int64),
        v_vt=jnp.where(is_timer[:, None], 0, tick_jb_vt).astype(jnp.int8),
        v_num=jnp.where(is_timer[:, None], jnp.float32(0.0), tick_jb_num),
        v_str=jnp.where(is_timer[:, None], 0, tick_jb_sid),
        req=jnp.full((size,), -1, jnp.int64),
        req_stream=jnp.full((size,), -1, jnp.int32),
        aux_key=jnp.where(
            is_timer, timer_col(state.timer_aik), job_i64_rows[:, JBL_AIK]
        ),
        aux2_key=jnp.full((size,), -1, jnp.int64),
        type_id=jnp.where(is_timer, 0, state.job_type[jidx]),
        retries=jnp.where(is_timer, 0, state.job_retries[jidx]),
        deadline=jnp.where(
            is_timer, timer_col(state.timer_due), job_i64_rows[:, JBL_DEADLINE]
        ),
        worker=jnp.where(is_timer, 0, state.job_worker[jidx]),
        src=jnp.full((size,), -1, jnp.int32),
        resp=jnp.zeros((size,), bool),
        push=jnp.zeros((size,), bool),
        rej=jnp.zeros((size,), jnp.int32),
    )
    return out, count


def _tick_entry(
    state: EngineState, now
) -> Tuple[EngineState, RecordBatch, jax.Array]:
    """Donating wrapper for ``tick_kernel``: the scan only READS state, so
    the entry passes it through unchanged and declares the input donated —
    XLA aliases the ~50 state tables input→output instead of keeping a
    second resident copy live across the tick (zbaudit boundary pass).
    Callers must rebind: ``state, out, count = tick_jit(state, now)``."""
    out, count = tick_kernel(state, now)
    return state, out, count


tick_jit = jit_registry.register_jit(
    "kernel.tick",
    _tick_entry,
    state_args=(0,),
    donate_argnums=(0,),
    max_signatures=2,
    notes="state shape is fixed per engine; one extra signature allowed "
    "for a capacity-resized engine in the same process",
)
