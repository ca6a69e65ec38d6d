"""Host wrapper: a batched partition stream processor over the step kernel.

``TpuPartitionEngine`` is the device-backed drop-in for the host oracle
``PartitionEngine`` (``zeebe_tpu/engine/interpreter.py``): the broker feeds
it committed records (in log order) and gets back written follow-ups,
responses, cross-partition sends, and worker pushes — but processing runs
as SIMD batches on the accelerator.

Routing: WORKFLOW_INSTANCE / JOB / TIMER records run on device; DEPLOYMENT,
MESSAGE, MESSAGE_SUBSCRIPTION and INCIDENT records are delegated to an
embedded host oracle engine (they are rare control-plane work — the
reference likewise runs deployments on the system partition only,
``DeploymentCreateEventProcessor``). Emissions are merged back in source
order, which preserves the oracle's append order (each record's follow-ups
appended after the whole committed batch, record-major).

Device-incompatible workflows (``graph.check_device_compatible``) fall
back per-workflow: their instance records route to the embedded host
oracle, so a TPU-backed partition serves every deployed workflow — the
device graph simply covers the compatible subset.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from zeebe_tpu.engine.interpreter import (
    JobSubscription,
    PartitionEngine,
    ProcessingResult,
    WorkflowRepository,
)
from zeebe_tpu import tracing
from zeebe_tpu.engine.mappings import MappingError, extract, merge
from zeebe_tpu.models.bpmn.model import ElementType
from zeebe_tpu.models.el.interpreter import ConditionEvalError, evaluate_condition
from zeebe_tpu.protocol.enums import ErrorType, RecordType, RejectionType, ValueType
from zeebe_tpu.protocol.intents import (
    JobIntent as JI,
    WorkflowInstanceIntent as WI,
)
from zeebe_tpu.protocol.metadata import RecordMetadata
from zeebe_tpu.protocol.records import (
    IncidentRecord,
    JobHeaders,
    JobRecord,
    Record,
    TimerRecord,
    WorkflowInstanceRecord,
)
from zeebe_tpu.tpu import batch as rb
from zeebe_tpu.tpu import graph as graph_mod
from zeebe_tpu.tpu import jit_registry
from zeebe_tpu.tpu import kernel, state as state_mod
from zeebe_tpu.tpu.batch import PayloadError, RecordBatch
from zeebe_tpu.tpu.conditions import VT_HELD, DeviceIneligible
from zeebe_tpu.tpu.intern import InternTable
from zeebe_tpu.tpu.values import ValueStore

_DEVICE_VALUE_TYPES = {
    int(ValueType.WORKFLOW_INSTANCE),
    int(ValueType.JOB),
    int(ValueType.TIMER),
}

# device-served when the compiled graph has message elements (round 4):
# the message store side is chosen per deployment set — see
# TpuPartitionEngine._recompile
_MESSAGE_VALUE_TYPES = {
    int(ValueType.MESSAGE),
    int(ValueType.MESSAGE_SUBSCRIPTION),
    int(ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION),
}
# workflow-instance events whose step reads or writes the payload of the
# element's flow scope (kernel.step: an output mapping merges into it and
# writes it back, a consumed token writes it)
_SCOPE_PAYLOAD_INTENTS = frozenset(
    int(i) for i in (
        WI.ELEMENT_COMPLETING, WI.ELEMENT_COMPLETED, WI.END_EVENT_OCCURRED,
    )
)
# the value types whose commands a device segment serialises
# (TpuPartitionEngine._route_wave): a job's commands by job key, and where
# the message store is on the device, the commands on the message tables
# by their (message name, correlation value) composite
_CUT_JOB = frozenset({int(ValueType.JOB)})
_CUT_JOB_AND_MESSAGES = frozenset({
    int(ValueType.JOB),
    int(ValueType.MESSAGE),
    int(ValueType.MESSAGE_SUBSCRIPTION),
})

_ERR_NO_RETRIES = 105  # kernel's JOB_NO_RETRIES incident code


# job commands that change a job's row (kernel.step: "Commands on ONE row")
_JOB_ROW_COMMANDS = frozenset(
    int(i) for i in (
        JI.ACTIVATE, JI.COMPLETE, JI.FAIL, JI.TIME_OUT, JI.UPDATE_RETRIES,
        JI.CANCEL,
    )
)
_JI_ACTIVATE = int(JI.ACTIVATE)
_JI_UPDATE_RETRIES = int(JI.UPDATE_RETRIES)
# job events the kernel's activation pool judges (kernel.step: m_actpool)
_JOB_POOL_EVENTS = frozenset(
    int(i) for i in (
        JI.CREATED, JI.TIMED_OUT, JI.FAILED, JI.RETRIES_UPDATED,
    )
)

# the row states a job can be activated from (JB_STATE only ever holds these
# and ACTIVATED: the kernel keeps state FAILED on UPDATE_RETRIES and bumps
# only the retries column, so FAILED with retries left covers
# retries-updated jobs)
_JOB_ACTIVATABLE_STATES = (
    int(JI.CREATED), int(JI.TIMED_OUT), int(JI.FAILED),
)
# job events after which a job is no longer parked: it is activated, or it
# left the table (TpuPartitionEngine._job_left)
_JI_ACTIVATED = int(JI.ACTIVATED)
_JOB_LEFT_EVENTS = frozenset(
    int(i) for i in (JI.ACTIVATED, JI.COMPLETED, JI.CANCELED)
)

PROBE_DEADLINES = 1  # bit0: some job/timer/message deadline is due
PROBE_JOB_BACKLOG = 2  # bit1: assignable jobs exist AND credits are free


def _due_probe_kernel(
    state: "state_mod.EngineState", now: jax.Array
) -> jax.Array:
    """i32 bitmask scalar (PROBE_*): is ANY device-side deadline due at
    ``now``, and is there job backlog a free credit could assign? One
    fused reduction over the relevant columns — launched asynchronously
    by the broker tick and polled with ``is_ready()`` so the tick never
    blocks on a device→host sync. The deadline predicates mirror the
    host sweeps below exactly (check_job_deadlines /
    check_timer_deadlines / check_message_ttls). The backlog predicate
    TYPE-MATCHES jobs against credited subscriptions: the earlier
    over-approximation (any assignable job AND any credited sub) kept
    the bit set whenever one orphan job of an unserved type coexisted
    with any credited subscription, paying the device→host backlog pull
    (the whole job table) every tick for nothing. [M, S] broadcast over
    the small subscription table — still one fused reduction, no host
    round trip."""
    # 64-bit columns are 32-bit planes (tpu/state.py): compared as words
    sm = state_mod
    job_due = jnp.any(
        (state.job_state == int(JI.ACTIVATED))
        & ~sm.col_neg(state.job_i64, sm.JBL_DEADLINE)
        & sm.col_le(state.job_i64, sm.JBL_DEADLINE, now)
    )
    timer_due = jnp.any(
        ~sm.col_neg(state.timer_key) & sm.col_le(state.timer_due, 0, now)
    )
    msg_due = jnp.any(
        ~sm.col_neg(state.msg_key) & sm.col_le(state.msg_deadline, 0, now)
    )
    assignable = (
        (state.job_state == int(JI.CREATED))
        | (state.job_state == int(JI.TIMED_OUT))
        | (state.job_state == int(JI.FAILED))
    ) & (state.job_i32[:, state_mod.JB_RETRIES] > 0)
    credited = state.sub_valid & (state.sub_credits > 0)
    backlog = jnp.any(
        assignable[:, None]
        & credited[None, :]
        & (state.job_i32[:, state_mod.JB_TYPE, None] == state.sub_type[None, :])
    )
    return (
        (job_due | timer_due | msg_due).astype(jnp.int32) * PROBE_DEADLINES
        + backlog.astype(jnp.int32) * PROBE_JOB_BACKLOG
    )


def _due_probe_entry(
    state: "state_mod.EngineState", now: jax.Array
) -> Tuple["state_mod.EngineState", jax.Array]:
    """Donating entry for the probe: the reduction only READS state, so it
    passes the tables through and declares the input donated — without the
    alias, every async probe launch kept a full second copy of the ~50
    state tables resident until the poll completed (zbaudit boundary
    pass). Callers must rebind: ``state, mask = _due_probe_jit(state, now)``."""
    return state, _due_probe_kernel(state, now)


_due_probe_jit = jit_registry.register_jit(
    "engine.due_probe",
    _due_probe_entry,
    state_args=(0,),
    donate_argnums=(0,),
    max_signatures=2,
    notes="state shape is fixed per engine; one extra signature allowed "
    "for a capacity-resized engine in the same process",
)


def _credit_flush_entry(sub_credits: jax.Array, delta: jax.Array) -> jax.Array:
    """Donating entry that adds the workers' returned credits (``delta``,
    i32 per subscription slot, summed on the host since the last flush)
    to the device's credit column. Over the column and not over the
    state: a launch costs by its leaves (PERF.md, PR 32), and no other
    table has a part in it."""
    return sub_credits + delta


_credit_flush_jit = jit_registry.register_jit(
    "engine.credit_flush",
    _credit_flush_entry,
    state_args=(0,),
    donate_argnums=(0,),
    max_signatures=2,
    notes="the subscription table's shape is fixed per engine; one extra "
    "signature allowed for an engine of another sub_capacity in the same "
    "process",
)


@dataclasses.dataclass(frozen=True)
class _HostSubscriptions:
    """The host side of the job-subscription table: the columns that only
    the host writes, as the device holds them. The arrays are never
    written in place (a change makes new ones), so one may be handed to
    the device as it is."""

    key: np.ndarray      # i64 [S] subscriber key
    type: np.ndarray     # i32 [S] interned job type
    worker: np.ndarray   # i32 [S] interned worker name
    timeout: np.ndarray  # i64 [S]
    valid: np.ndarray    # bool [S]
    rr: int              # the sweep's round-robin cursor (state.sub_rr)


def _host_unpack_payload(pay: np.ndarray):
    """Host-side view of one packed payload row ([3V] i32 — see
    state.pack_payload): returns (vt, num, sid) for columns_to_payload."""
    v = pay.shape[-1] // 3
    vt = pay[..., :v]
    sid = pay[..., v : 2 * v]
    num = np.ascontiguousarray(pay[..., 2 * v : 3 * v]).view(np.float32)
    return vt, num, sid


def _pow2(n: int) -> int:
    p = 64
    while p < n:
        p *= 2
    return p


def _as_record(entry) -> Record:
    """Materialize a tail entry (real ``Record`` or lazy ``(batch, idx)``
    ref) — the slow-path escape hatch for host-side inspection."""
    if type(entry) is tuple:
        return entry[0].row(entry[1])
    return entry


def _message_composite(vt: int, value) -> tuple:
    """The (message name, correlation value) composite a MESSAGE or
    MESSAGE_SUBSCRIPTION command touches in the device's message tables,
    equal where ``state.corr_composite`` of its staged columns
    (``_stage_corr``) is equal: a string as itself, a number by its f32
    bits, an empty or other key as none."""
    name = value.name if vt == int(ValueType.MESSAGE) else value.message_name
    corr = value.correlation_key
    if isinstance(corr, str):
        return name, corr or None
    if isinstance(corr, (int, float)):
        return name, None, int(np.float32(float(corr)).view(np.int32))
    return name, None


# frame-field defaults of a fresh Record/metadata (producer_id,
# incident_key, rejection_type) — the lazy emission batch pre-fills its
# frame columns with these so encode-from-columns matches what a
# materialized row would encode
_FRAME_DEFAULTS = None


def _frame_defaults():
    global _FRAME_DEFAULTS
    if _FRAME_DEFAULTS is None:
        md = RecordMetadata()
        probe = Record(metadata=md)
        _FRAME_DEFAULTS = (
            probe.producer_id, md.incident_key, int(md.rejection_type),
        )
    return _FRAME_DEFAULTS


# rows staged for the device STRAIGHT from readback columns (no Record
# build; index gathers in _stage_from_emission) — the counterpart of
# serving_rows_materialized_total; cached handle, bumped once per wave
_staged_columnar_counter = None


def _count_staged_columnar(n: int = 1) -> None:
    global _staged_columnar_counter
    if _staged_columnar_counter is None:
        from zeebe_tpu.runtime.metrics import GLOBAL_REGISTRY

        _staged_columnar_counter = GLOBAL_REGISTRY.counter(
            "serving_rows_staged_columnar_total",
            "Device rows staged by index gathers straight from their "
            "emission batch's columns (no Record object ever materialized "
            "for them)",
        )
    _staged_columnar_counter.inc(n)


# staging defaults of the scalar columns (an all-default row is an invalid
# row; the payload columns default to zeros)
_COL_DEFAULTS = {
    "valid": False, "rtype": 0, "vtype": 0, "intent": 0, "key": -1,
    "elem": -1, "wf": -1, "instance_key": -1, "scope_key": -1,
    "req": -1, "req_stream": -1, "aux_key": -1, "aux2_key": -1,
    "type_id": 0, "retries": 0, "deadline": -1, "worker": 0,
    "src": -1, "resp": False, "push": False, "rej": 0,
}


@functools.lru_cache(maxsize=None)
def _default_row(num_vars: int) -> rb.StagedBatch:
    """One all-default row of a packed wave, written through the column
    views: what a wave's two host matrices are filled with, one broadcast
    each, before its rows are staged."""
    row = rb.host_pair(1, num_vars)
    views = rb.column_views(row)
    for name, default in _COL_DEFAULTS.items():
        getattr(views, name)[...] = default
    return row


@dataclasses.dataclass
class _PendingSegment:
    """One dispatched (not yet collected) device segment of a wave."""

    results: List[ProcessingResult]
    positions: List[int]
    live: List[int]           # indices (into the segment's records) staged
    suppress: set             # segment-record indices with host-emitted
                              # job-incident follow-ups (kernel copy drops)
    rows: List[int] = dataclasses.field(default_factory=list)
    out: Optional[rb.StagedBatch] = None  # device emission pair (unfetched)
    stats: Optional[jax.Array] = None   # device stats vector (unfetched)
    route_owner: Optional[int] = None   # routed wave's owner shard (v2)
    seq: int = -1                       # dispatch order (residency ordering)
    fb_pop: bool = False                # gathered fallback under routing:
                                        # collect pops residency from emissions
    blind: bool = False                 # fallback carried rows whose instance
                                        # key the host could not prove
    # (handle, owner or None) of the values its staging held anew: owned
    # or freed when the segment is decoded (TpuPartitionEngine._own_held)
    held: List[tuple] = dataclasses.field(default_factory=list)
    # per staged row, whether it is a command (None: the value store was
    # empty, so no emitted row can carry a handle; _held_values)
    src_commands: Optional[np.ndarray] = None


@dataclasses.dataclass
class PendingWave:
    """A wave in flight: dispatched to the device, results not yet
    materialized. The serving loop double-buffers on this — stage/dispatch
    wave N+1 and materialize wave N−1 while the device computes wave N
    (JAX async dispatch carries the state dependency device-side).

    ``records`` may be a plain list or a lazy columnar view; ``positions``
    carries every record's log position so collection never materializes
    a row just to read it. ``partition_id`` tags the wave's owner — the
    cross-partition scheduler packs SHARED waves whose per-partition
    segments each arrive here tagged."""

    records: List[Record]
    per_record: List[Optional[ProcessingResult]]
    segments: List[_PendingSegment] = dataclasses.field(default_factory=list)
    positions: List[int] = dataclasses.field(default_factory=list)
    partition_id: int = -1
    # the wave's phases (tracing/phases.py), stamped by dispatch_wave,
    # collect_wave and the broker's apply; its totals are what the wave
    # reports, so nothing is timed twice
    phases: tracing.PhaseClock = dataclasses.field(
        default_factory=tracing.PhaseClock
    )
    collected: Optional[List[ProcessingResult]] = None  # one-shot cache
    # keys of the job ACTIVATE commands and pool events this wave steps
    # (TpuPartitionEngine._assigning forgets them at collect)
    assigning_stepped: List[int] = dataclasses.field(default_factory=list)
    # (key, entry) of the job pool events its device segments step: judged
    # at collect (TpuPartitionEngine._park_unassigned)
    pool_events: List[tuple] = dataclasses.field(default_factory=list)
    # owners of held payload values that end with this wave (an instance
    # whose last record it steps or that it demoted, a message deleted):
    # their values are freed once the wave is collected
    ending: List = dataclasses.field(default_factory=list)

    @property
    def host_seconds(self) -> float:
        """Host work of the wave path: routing, staging, transfer, launch
        and decode."""
        return self.phases.seconds(*tracing.phases.WAVE_HOST_PHASES)

    @property
    def device_seconds(self) -> float:
        """HOST seconds blocked on device outputs at collect (first sync
        and readback) — not the device's busy time."""
        return self.phases.seconds(*tracing.phases.WAVE_BLOCKED_PHASES)


class TpuPartitionEngine:
    """Batched device stream processor for one partition."""

    def __init__(
        self,
        partition_id: int = 0,
        num_partitions: int = 1,
        repository: Optional[WorkflowRepository] = None,
        clock: Optional[Callable[[], int]] = None,
        capacity: int = 1 << 12,
        num_vars: int = 16,
        sub_capacity: int = 16,
        device=None,
        device_index: int = -1,
        state_shards: int = 1,
        shard_devices=None,
        device_indices=None,
        routing: str = "gathered",
        routed_lane_slots: int = 512,
    ):
        self.partition_id = partition_id
        # the PhaseClock of the wave being dispatched; between waves (warm,
        # selfcheck) staging stamps a clock that nothing reads
        self._idle_clock = tracing.PhaseClock()
        self._clock = self._idle_clock
        # WHO ASSIGNS A JOB. The kernel's activation pool (kernel.step,
        # m_actpool) owns a job while one of its pool events (CREATED,
        # TIMED_OUT, FAILED, RETRIES_UPDATED with retries left) is on its
        # way to a wave: it assigns the job when it steps that event, if a
        # credit is free. The sweep (device_backlog_activations, and the
        # backlog scan of add_job_subscription) owns a job only once that
        # event was stepped WITHOUT an assignment (no credit then): a row
        # in state CREATED / FAILED / TIMED_OUT with retries left and
        # nothing on its way. The engine keeps that account itself, on the
        # host, in three places and no other:
        # - ``_assigning`` (in flight): the keys of device jobs with a pool
        #   event or an ACTIVATE command handed out (emitted by a collected
        #   wave, or returned by a sweep) and not yet seen stepped. A key
        #   leaves when the wave that carries its record is collected (as
        #   ACTIVATED, as a rejection, or as an event the pool let pass),
        #   before that wave's own emissions enter.
        # - ``_parked`` (the sweep's): key -> (type id, job value as the
        #   stepped event carried it, ``self.clock()`` at its entry: what
        #   ``serving_backlog_park_wait_seconds_total`` counts from when
        #   a sweep hands it out). A job enters when a collected wave
        #   stepped its pool event and neither holds an ACTIVATE of that
        #   key nor ended the job (collect_wave, _park_unassigned: what the
        #   host engine keeps as _awaiting_jobs). It leaves when a sweep
        #   hands out its ACTIVATE, when a collected wave emits its
        #   ACTIVATED, COMPLETED or CANCELED, when another pool event of
        #   it is stepped (judged anew), and when its instance is demoted.
        #   The sweep walks this set and never the device's job table.
        #   None = not known: after a restore, and on an engine over a
        #   table it did not step, the first sweep the due probe asks for
        #   scans the table once (_scan_parked_jobs; such an entry holds
        #   the row's slot and is read back when handed out, and None for
        #   its entry time, which nobody knows: its wait is not counted)
        #   and from then on the set is kept. Not in a snapshot.
        # - ``_ended``: jobs that left the table (COMPLETED, CANCELED,
        #   demoted) while a record of theirs was still in flight; the pool
        #   event that arrives late must not park them. Pruned to the keys
        #   in ``_assigning`` at every collect.
        # All three are empty or unknown after a restore: exactly-once
        # does not rest on them (the kernel rejects a late duplicate and
        # returns its credit), they spare the tick a pull of the job table
        # and the pipeline an ACTIVATE, a rejection and a credit round
        # trip per tick and job.
        self._assigning: set = set()
        self._parked: Optional[Dict[int, tuple]] = None
        self._ended: set = set()
        # THE JOB-SUBSCRIPTION TABLE HAS A HOST SIDE. Of its columns only
        # ``sub_credits`` is written by the device (the pool's assignments,
        # a rejected ACTIVATE's return); the others and ``sub_rr`` are
        # written by this engine's subscription methods and its sweep, and
        # every step passes them through. So:
        # - ``_subs`` is the host's copy of those columns; who needs a
        #   subscriber's slot, a free slot or a worker's name reads it
        #   there and fetches nothing. None = not known: whoever assigns
        #   ``state`` from outside (tests, the restore) may have written
        #   the table, and its next reader fetches it once
        #   (``_subscriptions``).
        # - ``_credit_delta`` holds the credits the workers returned and
        #   the device has not been told of, per slot (None = none): a
        #   return is host arithmetic (``increase_job_credits``). It is
        #   added to the device's column in one program (``_flush_credits``)
        #   before anything reads that column: a step, the due probe, the
        #   sweep, a subscription method, a snapshot, a read of ``state``.
        self._subs: Optional[_HostSubscriptions] = None
        self._credit_delta: Optional[np.ndarray] = None
        self.num_partitions = num_partitions
        # mesh placement (scheduler/placement.DevicePlan): this engine's
        # state lives COMMITTED on `device`, batches stage onto it, and the
        # step program executes there — so several partitions' waves
        # compute concurrently across the mesh. None = default device (the
        # single-device baseline). `device_index` is the plan's index,
        # used only as the per-device metrics label.
        self.device = device
        self.device_index = device_index
        # sharded state mode (ROADMAP item 2, mesh-sharded partition
        # state): with state_shards > 1 this ONE partition's row tables
        # live block-sharded on dim 0 over a `shards` mesh axis spanning
        # `shard_devices` (DevicePlan hands the span; defaults to the
        # first N local devices). The step runs through
        # shard.build_state_step — gather-for-compute, keep-local-on-write
        # — and replays bit-identical to the single-device program by
        # construction. Mutually exclusive with single-device placement.
        self._state_shards = max(int(state_shards), 1)
        self._mesh = None
        self._state_step = None
        self._shard_exchange_bytes = 0
        self.sharded_waves = 0
        # sharded-state v2 (ROADMAP item 2, second half): routing mode.
        # "gathered" = v1 gather-for-compute every wave; "resident" =
        # residency-routed staging — single-owner waves stage into the
        # owner shard's batch lane and step ONLY local rows (no table
        # gather), everything else takes the gathered fallback program.
        # Both modes replay bit-identical to the single-device engine.
        if routing not in ("gathered", "resident"):
            raise ValueError(f"unknown mesh routing mode: {routing!r}")
        self.routing = routing if self._state_shards > 1 else "gathered"
        self._routed_lane_slots = max(int(routed_lane_slots), 1)
        self._state_step_routed = None
        self._state_step_fallback = None
        self._fallback_exchange_bytes = 0
        # residency map: workflow_instance_key → shard whose row block
        # holds the ENTIRE instance (learned from routed-segment
        # emissions; popped on fallback dispatch/collect, demotion,
        # completion)
        self._resident: Dict[int, int] = {}
        # instance_key → dispatch seq whose fallback/demotion broke the
        # single-owner proof. Collects run after LATER dispatches
        # (pipelining), so an earlier-dispatched routed segment's
        # _note_residency must not re-add a key a later fallback popped —
        # the seq ordering decides which knowledge is newer.
        self._residency_invalid: Dict[int, int] = {}
        self._dispatch_seq = 0
        # dispatched-but-uncollected fallback segments that stepped rows
        # whose instance key the host could not prove: until their
        # emissions name those instances (collect), ANY residency entry
        # may be stale, so routing holds off
        self._blind_fb_inflight = 0
        self.routed_waves = 0
        self.fallback_waves = 0
        self.routed_overflows = 0
        # per-shard staged-row counts of the last dispatched wave (owner
        # lane fill in resident mode, advisory hash split otherwise) —
        # read by the broker feed for scheduler/wave fill accounting
        self.last_shard_fill: tuple = ()
        self._last_stage_split = None
        self._last_stage_valid = 0
        self.device_indices = (
            list(device_indices) if device_indices is not None else []
        )
        if self._state_shards > 1:
            if device is not None:
                raise ValueError(
                    "state_shards > 1 shards over a mesh span; a single "
                    "`device` placement cannot also be pinned"
                )
            from zeebe_tpu.tpu import shard as shard_mod

            devs = (
                list(shard_devices)
                if shard_devices is not None
                else list(jax.devices())[: self._state_shards]
            )
            if len(devs) < self._state_shards:
                raise ValueError(
                    f"state_shards={self._state_shards} needs that many "
                    f"devices; have {len(devs)}"
                )
            self._mesh = shard_mod.Mesh(
                np.asarray(devs[: self._state_shards]),
                (shard_mod.STATE_AXIS,),
            )
            if not self.device_indices:
                self.device_indices = list(range(self._state_shards))
        self.repository = repository if repository is not None else WorkflowRepository()
        self.clock = clock or (lambda: 0)
        # pallas-vs-XLA dispatch is BUILD-dependent (PERF_NOTES round 4):
        # measure once per process on the actual libtpu build (disk-cached
        # per build fingerprint) instead of trusting a static env default.
        # No-op off-TPU; ZB_PALLAS stays the manual override.
        from zeebe_tpu.tpu import autotune

        autotune.ensure_autotuned()
        self.capacity = capacity
        self.num_vars = num_vars
        self.interns = InternTable()
        # payload values with no column form, held on the host under the
        # handles their columns carry (tpu/values.py); the variables each
        # device workflow reads (workflow key -> names, and their union):
        # where one holds such a value the instance runs on the host engine
        self._values = ValueStore()
        self._reads: Dict[int, frozenset] = {}
        self._reads_any: frozenset = frozenset()
        # the held values of the segment being staged (_stage_and_launch),
        # the row whose payload is being staged (_stage_payload), and the
        # owners that end with the wave being routed
        self._staged_held: List[tuple] = []
        self._staging_row: Optional[tuple] = None
        self._ending: List = []

        # host oracle engine for control-plane records (deployment, messages,
        # incidents); shares the repository and the workflow keyspace via
        # explicit counter sync after each batch
        self._host = PartitionEngine(
            partition_id=partition_id,
            num_partitions=num_partitions,
            repository=self.repository,
            clock=self.clock,
        )

        self.graph: Optional[graph_mod.DeviceGraph] = None
        self.meta: Optional[graph_mod.GraphMeta] = None
        self.state = self._place(
            state_mod.make_state(
                capacity=capacity, num_vars=num_vars, sub_capacity=sub_capacity
            )
        )
        if self._mesh is not None:
            from zeebe_tpu.tpu import shard as shard_mod

            if self.routing == "resident":
                bad = shard_mod.unshardable_state_leaves(
                    self.state, self._state_shards
                )
                if bad:
                    raise ValueError(
                        "resident routing needs every shardable table "
                        "divisible by the span; replicated-fallback "
                        f"leaves: {bad} (use routing='gathered' or a "
                        "divisible capacity)"
                    )
                self._state_step_routed = shard_mod.build_state_step_routed(
                    self._mesh, self.state
                )
                self._state_step_fallback = (
                    shard_mod.build_state_step_fallback(self._mesh, self.state)
                )
                self._fallback_exchange_bytes = (
                    shard_mod.state_exchange_bytes(
                        self.state, self._state_shards, include_lookup=False
                    )
                )
            else:
                self._state_step = shard_mod.build_state_step(
                    self._mesh, self.state
                )
            self._shard_exchange_bytes = shard_mod.state_exchange_bytes(
                self.state, self._state_shards
            )
        # key advance since the last rebuild_lookup_state run: the direct-
        # mapped indexes are collision-free only within a window of index-
        # capacity consecutive keys, so the serving path re-derives the
        # fallback maps before the window can wrap (_stage_and_launch)
        self._note_lookup_rebuilt()
        self._compiled_count = 0
        # repository size at the last _recompile (see dispatch_wave)
        self._repo_compiled = 0
        self._host_only_keys: set = set()
        # device-residency observability (fuzzers/tests assert the routing
        # split instead of trusting eligibility rules not to drift)
        self.device_records_processed = 0
        self.host_records_processed = 0
        # the host share by (value type, workflow key; -1 where the value
        # names none): a workflow outside kernel coverage runs on the
        # embedded oracle without a word, and this is where it shows
        self.host_records_by_kind: Dict[tuple, int] = {}
        self._device_keys_dirty = False
        # message store side (see _recompile): True = device tables serve
        # this partition's MESSAGE-partition role
        self._messages_on_device = False
        self._cut_value_types = _CUT_JOB
        # a deployed workflow lets one scope hold several tokens (a
        # parallel fork, a boundary event): _route_wave then keeps the
        # scope-payload steps of one scope in separate segments
        self._scope_cuts = False
        self._restoring = False
        # ONE position→record cache shared with the embedded host oracle:
        # the broker fills it during recovery, host-side incident
        # resolution reads it (reference TypedStreamReader by position)
        self.records_by_position: Dict[int, Record] = self._host.records_by_position
        self.last_processed_position = -1
        # delta-snapshot dirty tracking over the device table families
        # (log/stateser.DEVICE_ARRAY_FAMILIES); None = cold (everything
        # dirty). Marking is conservative at wave granularity: one kernel
        # step may write any table (a job COMPLETE activates follow-on
        # elements), so a dispatched device segment dirties every family —
        # the win is that an idle partition's takes skip ALL device→host
        # readback, and host-side control traffic (subscriptions, acks,
        # ticks) dirties only the families it touches.
        self._dirty_device: Optional[set] = None
        # array part names materialized (device→host) by the last
        # snapshot_state call — the zero-readback proof for tests
        self.last_snapshot_readback: List[str] = []
        # lazy columnar emissions (ROADMAP item 4, device-path slice):
        # plain follow-up rows flow to the log as lazy refs into the
        # readback batch and re-STAGE from its columns — no Record builds
        # on the hot path. False gives eager rows: the reference the lazy
        # log is compared with (tests/test_scheduler.py::TestLazyEmissions)
        self.lazy_emissions = True
        # bumped by _recompile: workflow SLOTS in older emission batches
        # are stale after a redeploy — the staging fast path checks this
        self._meta_epoch = 0

    # -- mesh placement ----------------------------------------------------
    def _place(self, tree):
        """Commit a pytree's arrays to this engine's mesh device (no-op for
        the default single-device engine). Committed placement is what
        makes the jit programs EXECUTE there; uncommitted companions
        (clock scalars, migration rows) follow the committed operands."""
        if self._mesh is not None:
            # sharded mode: state tables commit block-sharded over the
            # mesh span (dim 0), everything else replicated across it —
            # both are NamedShardings, so the step program executes on
            # the whole span without per-call resharding
            from jax.sharding import NamedSharding, PartitionSpec
            from zeebe_tpu.tpu import shard as shard_mod

            if isinstance(tree, state_mod.EngineState):
                return jax.device_put(
                    tree, shard_mod.state_shardings(self._mesh, tree)
                )
            return jax.device_put(
                tree, NamedSharding(self._mesh, PartitionSpec())
            )
        if self.device is None:
            return tree
        return jax.device_put(tree, self.device)

    # -- the device state, as every reader must see it ----------------------
    @property
    def state(self) -> "state_mod.EngineState":
        """The device tables, with every returned credit applied."""
        if self._credit_delta is not None:
            self._flush_credits()
        return self._state

    @state.setter
    def state(self, value) -> None:
        # assigned from outside the subscription methods: the table may be
        # another one, so the host's copy of it is not known, and returns
        # not yet applied were the replaced table's
        self._state = value
        self._subs = None
        self._credit_delta = None

    def _flush_credits(self) -> None:
        """Add the returned credits the device has not been told of to
        its credit column: one donating program for however many returns,
        as phase ``credit_flush`` of the cycle that needs the column (a
        wave's or a tick's; outside every cycle, of a clock of its own)."""
        delta = self._credit_delta
        if delta is None:
            return
        self._credit_delta = None
        own = self._clock is self._idle_clock
        clock = tracing.PhaseClock() if own else self._clock
        with clock.phase("credit_flush"):
            self._mark_device_dirty("sub")
            self._add_credits(delta)
        clock.count("credit_flushes", 1)
        if own:
            from zeebe_tpu.runtime.metrics import observe_phases

            observe_phases(clock)

    def _add_credits(self, delta: np.ndarray) -> None:
        """One launch of the flush program. ``delta`` rides in it as the
        numpy array it is; the sum is placed like the leaf it replaces
        (``_put_leaves``)."""
        s = self._state
        self._state = dataclasses.replace(
            s,
            sub_credits=self._place(_credit_flush_jit(s.sub_credits, delta)),
        )

    def _subscriptions(self) -> _HostSubscriptions:
        """The host side of the subscription table; fetched from the
        device (one ``device_get``) only where it is not known."""
        subs = self._subs
        if subs is None:
            s = self._state
            *columns, rr = jax.device_get(
                (s.sub_key, s.sub_type, s.sub_worker, s.sub_timeout,
                 s.sub_valid, s.sub_rr)
            )
            # copies: a fetched array may be a view of the device's buffer,
            # which the next step is given to write on
            subs = self._subs = _HostSubscriptions(
                *(np.array(c) for c in columns), rr=rr.item()
            )
        return subs

    def place_on(self, device, device_index: int = -1) -> None:
        """Migrate this engine's live device state onto another mesh device
        (DevicePlan rebalance after a device exclusion or leadership
        change). Content is unchanged — snapshot dirty-tracking is
        untouched — and the next dispatched wave compiles/executes on the
        new device. Call between waves (the brokers do: placement changes
        happen on the broker actor, serialized with the drain)."""
        if self._mesh is not None:
            raise RuntimeError(
                "sharded-state engine is pinned to its mesh span; rebuild "
                "the engine (snapshot → restore) to move it"
            )
        self.device = device
        self.device_index = device_index
        if device is not None:
            self._state = jax.device_put(self.state, device)
            if self.graph is not None:
                self.graph = jax.device_put(self.graph, device)

    # -- routing ----------------------------------------------------------
    def partition_for_correlation_key(self, correlation_key: str) -> int:
        return self._host.partition_for_correlation_key(correlation_key)

    # topic orchestration + subscription-ack state live on the embedded
    # host oracle (system-partition control plane); the cluster broker
    # reads them through the engine interface
    @property
    def topics(self):
        return self._host.topics

    @property
    def topic_sub_acks(self):
        return self._host.topic_sub_acks

    @property
    def exporter_positions(self):
        return self._host.exporter_positions

    # -- deployment → graph recompile -------------------------------------
    def _recompile(self, extra_variables=None) -> None:
        """Split the deployed set: device-compatible workflows compile into
        the graph; incompatible ones (exotic conditions, non-flat JSONPath,
        …) run their instances on the embedded host oracle instead — the
        per-workflow fallback that makes a TPU-backed partition a drop-in
        for the host engine (reference bar: every deployed workflow keeps
        executing; where is an implementation detail)."""
        workflows = []
        host_only = set()
        self._repo_compiled = len(self.repository.by_key)
        for key in sorted(self.repository.by_key):
            wf = self.repository.by_key[key]
            if graph_mod.check_device_compatible(wf) is not None:
                host_only.add(key)
            else:
                workflows.append(wf)
        self._host_only_keys = host_only
        self._meta_epoch += 1  # older emission batches' wf slots are stale
        self._reads = {wf.key: graph_mod.read_variables(wf) for wf in workflows}
        self._reads_any = frozenset().union(*self._reads.values())
        if not workflows:
            self.graph = None
            self._compiled_count = 0
            self._set_message_store_side(False)
            return
        if extra_variables is not None:
            var_names = list(extra_variables)
        else:
            var_names = list(self.meta.varspace.names) if self.meta else []
        self.graph, self.meta = graph_mod.compile_graph(
            workflows, interns=self.interns, extra_variables=var_names
        )
        # the graph is replicated per engine: committed next to the state
        # so a step never re-transfers it from the default device per call
        self.graph = self._place(self.graph)
        if self.graph.num_vars > self.num_vars:
            raise PayloadError(
                f"workflow variables ({self.graph.num_vars}) exceed engine "
                f"num_vars={self.num_vars}; raise num_vars"
            )
        self._compiled_count = len(workflows)
        self._scope_cuts = self.graph.has_boundaries or any(
            el.element_type == ElementType.PARALLEL_GATEWAY
            and len(el.outgoing) > 1
            for wf in workflows for el in wf.elements
        )
        # The message store (this partition's MESSAGE-partition role: stored
        # messages + open subscriptions) lives on EXACTLY one side. Device
        # iff the deployed set compiles with message elements and has no
        # host-only workflows — a mixed store would let a publish see only
        # half the subscriptions. Flipping sides migrates the store.
        self._set_message_store_side(
            self.graph.has_messages and not host_only
        )

    def _set_message_store_side(self, on_device: bool) -> None:
        prev = self._messages_on_device
        self._messages_on_device = on_device
        self._cut_value_types = (
            _CUT_JOB_AND_MESSAGES if on_device else _CUT_JOB
        )
        if self._restoring:
            return
        if on_device and not prev:
            self._migrate_message_store_to_device()
        elif prev and not on_device:
            self._migrate_message_store_to_host()

    def _migrate_message_store_to_device(self) -> None:
        """Host oracle message store → device tables (a deployment flipped
        the store side; rare control-plane event, plain host loop)."""
        from zeebe_tpu.tpu import hashmap as hm
        from zeebe_tpu.tpu.conditions import VT_NUM, VT_STR

        host = self._host
        if not host.messages and not host.message_subscriptions:
            return
        self._mark_device_dirty("msg", "msub")
        host.snapshot_mark_dirty(("h/messages",))
        s = self.state

        def corr_cols(value) -> tuple:
            if isinstance(value, str):
                return int(VT_STR), self.interns.intern(value)
            return (
                int(VT_NUM),
                int(np.float32(float(value)).view(np.int32)),
            )

        def composite(name: str, cvt: int, cbits: int) -> int:
            nid = self.interns.intern(name)
            return (nid << 35) | (cvt << 32) | (cbits & 0xFFFFFFFF)

        # 64-bit columns: pulled planes viewed as int64, filled, and put
        # back as planes (state_mod.host_i64 / host_planes)
        h64, planes = state_mod.host_i64, state_mod.host_planes
        msub_ckey = h64(s.msub_ckey, 0).copy()
        msub_i32 = np.asarray(s.msub_i32).copy()
        msub_i64 = h64(s.msub_i64).copy()
        mkeys, mslots = [], []
        free = list(np.nonzero(msub_ckey < 0)[0])
        if len(host.message_subscriptions) > len(free):
            raise RuntimeError(
                f"message-store migration needs "
                f"{len(host.message_subscriptions)} subscription slots but "
                f"the device table has {len(free)} free — raise the "
                "engine's msub capacity"
            )
        for sub in host.message_subscriptions:
            cvt, cbits = corr_cols(sub.correlation_key)
            ck = composite(sub.message_name, cvt, cbits)
            slot = int(free.pop(0))
            msub_ckey[slot] = ck
            msub_i32[slot] = (
                self.interns.intern(sub.message_name), cvt, cbits,
                sub.workflow_instance_partition_id,
            )
            msub_i64[slot] = (sub.workflow_instance_key, sub.activity_instance_key)
            mkeys.append(ck)
            mslots.append(slot)
        host.message_subscriptions = []

        msg_key = h64(s.msg_key, 0).copy()
        msg_ckey = h64(s.msg_ckey, 0).copy()
        msg_i32 = np.asarray(s.msg_i32).copy()
        msg_deadline = h64(s.msg_deadline, 0).copy()
        msg_pay = np.asarray(s.msg_pay).copy()
        gkeys, gslots = [], []
        gfree = list(np.nonzero(msg_key < 0)[0])
        if len(host.messages) > len(gfree):
            raise RuntimeError(
                f"message-store migration needs {len(host.messages)} stored-"
                f"message slots but the device table has {len(gfree)} free "
                "— raise the engine's msg capacity"
            )
        for key, message in sorted(host.messages.items()):
            cvt, cbits = corr_cols(message.correlation_key)
            ck = composite(message.name, cvt, cbits)
            slot = int(gfree.pop(0))
            msg_key[slot] = key
            msg_ckey[slot] = ck
            msg_i32[slot] = (
                self.interns.intern(message.name), cvt, cbits,
                self.interns.intern(message.message_id)
                if message.message_id else 0,
            )
            msg_deadline[slot] = message.deadline
            vt, num, sid = rb.payload_to_columns(
                message.payload, self._var_column, self.interns, self.num_vars,
                hold=self._values.hold,
            )
            for handle in sid[vt == VT_HELD].tolist():
                self._values.own(("m", key), handle)
            msg_pay[slot] = np.concatenate(
                [vt.astype(np.int32), sid,
                 np.ascontiguousarray(num).view(np.int32)]
            )
            gkeys.append(ck)
            gslots.append(slot)
        host.messages = {}

        state = dataclasses.replace(
            self.state,
            msub_ckey=jnp.asarray(planes(msub_ckey, column=True)),
            msub_i32=jnp.asarray(msub_i32),
            msub_i64=jnp.asarray(planes(msub_i64)),
            msg_key=jnp.asarray(planes(msg_key, column=True)),
            msg_ckey=jnp.asarray(planes(msg_ckey, column=True)),
            msg_i32=jnp.asarray(msg_i32),
            msg_deadline=jnp.asarray(planes(msg_deadline, column=True)),
            msg_pay=jnp.asarray(msg_pay),
        )
        if mkeys:
            m, _ = hm.insert(
                state.msub_map, jnp.asarray(mkeys, jnp.int64),
                jnp.asarray(mslots, jnp.int32),
                jnp.ones((len(mkeys),), bool),
            )
            state = dataclasses.replace(state, msub_map=m)
        if gkeys:
            g, _ = hm.insert(
                state.msg_map, jnp.asarray(gkeys, jnp.int64),
                jnp.asarray(gslots, jnp.int32),
                jnp.ones((len(gkeys),), bool),
            )
            state = dataclasses.replace(state, msg_map=g)
        # host-built leaves are uncommitted arrays on the default device:
        # re-place, or the next step sees a new signature and recompiles
        self._state = self._place(state)

    def _migrate_message_store_to_host(self) -> None:
        """Device message tables → host oracle store (a host-only workflow
        arrived; the store moves so every subscription sees every publish)."""
        from zeebe_tpu.engine.interpreter import StoredMessage, StoredSubscription
        from zeebe_tpu.tpu import hashmap as hm

        self._mark_device_dirty("msg", "msub")
        self._host.snapshot_mark_dirty(("h/messages",))
        s = self.state
        names = self.meta.varspace.names if self.meta else []
        corr_value = self._corr_string

        h64 = state_mod.host_i64
        msub_ckey = h64(s.msub_ckey, 0)
        msub_i32 = np.asarray(s.msub_i32)
        msub_i64 = h64(s.msub_i64)
        for slot in np.nonzero(msub_ckey >= 0)[0]:
            slot = int(slot)
            self._host.message_subscriptions.append(
                StoredSubscription(
                    message_name=self.interns.string(int(msub_i32[slot, 0])) or "",
                    correlation_key=corr_value(
                        int(msub_i32[slot, 1]), int(msub_i32[slot, 2])
                    ),
                    workflow_instance_partition_id=int(msub_i32[slot, 3]),
                    workflow_instance_key=int(msub_i64[slot, 0]),
                    activity_instance_key=int(msub_i64[slot, 1]),
                )
            )
        msg_key = h64(s.msg_key, 0)
        msg_i32 = np.asarray(s.msg_i32)
        msg_deadline = h64(s.msg_deadline, 0)
        msg_pay = np.asarray(s.msg_pay)
        for slot in np.nonzero(msg_key >= 0)[0]:
            slot = int(slot)
            key = int(msg_key[slot])
            self._host.messages[key] = StoredMessage(
                key=key,
                name=self.interns.string(int(msg_i32[slot, 0])) or "",
                correlation_key=corr_value(
                    int(msg_i32[slot, 1]), int(msg_i32[slot, 2])
                ),
                time_to_live=0,
                payload=rb.columns_to_payload(
                    *_host_unpack_payload(msg_pay[slot]), names, self.interns,
                    self._values,
                ),
                message_id=self.interns.string(int(msg_i32[slot, 3])) or "",
                deadline=int(msg_deadline[slot]),
            )
            self._ending.append(("m", key))
        v = self.num_vars
        self._state = self._place(dataclasses.replace(
            s,
            msub_ckey=jnp.full_like(s.msub_ckey, -1),
            msub_i64=jnp.full_like(s.msub_i64, -1),
            msub_map=hm.make(s.msub_map.size),
            msg_key=jnp.full_like(s.msg_key, -1),
            msg_ckey=jnp.full_like(s.msg_ckey, -1),
            msg_deadline=jnp.full_like(s.msg_deadline, -1),
            msg_map=hm.make(s.msg_map.size),
        ))

    # -- instance demotion: rare imperative ops take the host path ---------
    def _live_device_instance_slot(self, key: int) -> int:
        """Slot of a live root element instance in the device table, -1
        when absent (completed, unknown, or host-side)."""
        if key < 0:
            return -1
        keys = state_mod.host_i64(self.state.ei_i64, state_mod.EIL_KEY)
        states = np.asarray(self.state.ei_i32[:, state_mod.EI_STATE])
        hits = np.nonzero((keys == key) & (states != -1))[0]
        return int(hits[0]) if len(hits) else -1

    def _demote_instance(self, root_key: int) -> bool:
        """Migrate a live instance's scope tree (+ its jobs and timers)
        from the device SoA tables into the embedded host oracle.

        CANCEL and UPDATE_PAYLOAD are rare imperative control operations;
        running them host-side preserves the oracle's exact record cascade
        (CancelWorkflowInstanceProcessor's termination order, child-by-key
        sorting, job CANCEL commands) without teaching the SIMD kernel a
        cold path. The device keeps the hot lifecycle; a demoted instance
        finishes on the oracle — semantically invisible, since the oracle
        IS the semantics. True when a live device instance was demoted."""
        from zeebe_tpu.tpu import hashmap

        # demotion rewrites device tables AND inserts instances/jobs/timers
        # straight into the oracle's maps (outside any record dispatch)
        self._mark_device_dirty()
        self._host.snapshot_mark_dirty(None)
        # a demoted instance leaves the device tables — it is no longer
        # block-resident anywhere (resident routing, sharded-state v2).
        # The invalidation also blocks in-flight collects (all dispatched
        # before this point) from noting the key back in.
        self._resident.pop(int(root_key), None)
        self._residency_invalid[int(root_key)] = self._dispatch_seq
        s = self.state
        h64 = state_mod.host_i64  # 64-bit columns: planes viewed as int64
        ei_i32 = np.asarray(s.ei_i32)
        ei_i64 = h64(s.ei_i64)
        ei_pay = np.asarray(s.ei_pay)
        states = ei_i32[:, state_mod.EI_STATE]
        live = states != -1

        root_slot = self._live_device_instance_slot(root_key)
        if root_slot < 0:
            return False
        # its values now live in the host engine's documents: the device's
        # handles are freed once the waves in flight are decoded
        self._ending.append(int(root_key))
        # collect the scope tree (parent-slot pointers, bounded depth)
        tree = {root_slot}
        changed = True
        while changed:
            changed = False
            for slot in np.nonzero(live)[0]:
                parent = int(ei_i32[slot, state_mod.EI_SCOPE])
                if parent in tree and int(slot) not in tree:
                    tree.add(int(slot))
                    changed = True
        slots_sorted = sorted(tree, key=lambda sl: int(ei_i64[sl, 0]))

        names = self.meta.varspace.names if self.meta else []
        by_slot: Dict[int, object] = {}
        for slot in slots_sorted:
            key = int(ei_i64[slot, 0])
            parent_slot = int(ei_i32[slot, state_mod.EI_SCOPE])
            parent = by_slot.get(parent_slot)
            wf_slot = int(ei_i32[slot, state_mod.EI_WF])
            workflow = (
                self.meta.workflows[wf_slot]
                if self.meta and 0 <= wf_slot < len(self.meta.workflows)
                else None
            )
            value = WorkflowInstanceRecord(
                bpmn_process_id=workflow.id if workflow else "",
                version=workflow.version if workflow else -1,
                workflow_key=workflow.key if workflow else -1,
                workflow_instance_key=int(ei_i64[slot, 1]),
                activity_id=(
                    self.meta.element_id(
                        wf_slot, int(ei_i32[slot, state_mod.EI_ELEM])
                    )
                    if self.meta else ""
                ),
                payload=rb.columns_to_payload(
                    *_host_unpack_payload(ei_pay[slot]), names, self.interns,
                    self._values,
                ),
                scope_instance_key=(
                    int(ei_i64[parent_slot, 0]) if parent_slot in tree else -1
                ),
            )
            inst = self._host.element_instances.new_instance(
                key, value, WI(int(states[slot])), parent=parent
            )
            inst.job_key = int(ei_i64[slot, 2])
            inst.active_tokens = int(ei_i32[slot, state_mod.EI_TOKENS])
            pending_elem = int(ei_i32[slot, state_mod.EI_PENDING_BD])
            if pending_elem >= 0 and self.meta:
                # in-flight interrupting-boundary continuation migrates to
                # the oracle's _pending_boundary (ei_pay holds the trigger
                # payload by construction)
                self._host._pending_boundary[key] = (
                    self.meta.element_id(wf_slot, pending_elem),
                    dict(value.payload),
                )
            by_slot[slot] = inst

        tree_keys = {int(ei_i64[sl, 0]) for sl in tree}

        # migrate this tree's jobs
        job_i64 = h64(s.job_i64)
        job_i32 = np.asarray(s.job_i32)
        job_slots = [
            int(sl)
            for sl in np.nonzero(job_i32[:, state_mod.JB_STATE] != -1)[0]
            if int(job_i64[sl, state_mod.JBL_AIK]) in tree_keys
        ]
        from zeebe_tpu.engine.interpreter import JobState

        for sl in job_slots:
            jkey = int(job_i64[sl, state_mod.JBL_KEY])
            self._job_left(jkey, True)
            job = self._host.jobs[jkey] = JobState(
                state=int(job_i32[sl, state_mod.JB_STATE]),
                record=self._job_value_from_slot(sl),
                deadline=int(job_i64[sl, state_mod.JBL_DEADLINE]),
            )
            if (
                job.state in _JOB_ACTIVATABLE_STATES
                and job.record.retries > 0
                and jkey not in self._assigning
            ):
                # it waited for a credit here: it goes on waiting there
                self._host._awaiting_jobs.setdefault(
                    job.record.type, {}
                )[jkey] = None

        # migrate this tree's timers
        from zeebe_tpu.engine.interpreter import TimerState

        timer_keys = h64(s.timer_key, 0)
        timer_aik = h64(s.timer_aik, 0)
        timer_due = h64(s.timer_due, 0)
        timer_slots = [
            int(sl)
            for sl in np.nonzero(timer_keys >= 0)[0]
            if int(timer_aik[sl]) in tree_keys
        ]
        for sl in timer_slots:
            tkey = int(timer_keys[sl])
            wf_slot = int(np.asarray(s.timer_wf)[sl])
            self._host.timers[tkey] = TimerState(
                due_date=int(timer_due[sl]),
                activity_instance_key=int(timer_aik[sl]),
                record=TimerRecord(
                    activity_instance_key=int(timer_aik[sl]),
                    workflow_instance_key=int(
                        h64(s.timer_instance_key[sl], 0)
                    ),
                    due_date=int(timer_due[sl]),
                    handler_element_id=self.meta.element_id(
                        wf_slot, int(np.asarray(s.timer_elem)[sl])
                    ) if self.meta else "",
                ),
            )

        # migrate in-flight parallel joins: device join rows are keyed by
        # (scope_key << 10 | gateway element). The device merges arrival
        # payloads eagerly (flow-position-stamped), so the reconstructed
        # per-flow arrival map carries the merged payload for every arrived
        # position — exact for termination (which discards it) and for
        # joins that complete after demotion with the merged document.
        join_keys = h64(s.join_key, 0)
        join_arr = np.asarray(s.join_arrived)
        join_pay_np = np.asarray(s.join_pay)
        join_slots = [
            int(sl)
            for sl in np.nonzero(join_keys >= 0)[0]
            if int(join_keys[sl]) >> 10 in tree_keys
        ]
        for sl in join_slots:
            scope_key = int(join_keys[sl]) >> 10
            gw_elem = int(join_keys[sl]) & ((1 << 10) - 1)
            scope = self._host.element_instances.get(scope_key)
            if scope is None:
                continue
            merged = rb.columns_to_payload(
                *_host_unpack_payload(join_pay_np[sl]), names, self.interns,
                self._values,
            )
            arrivals = {
                int(pos): dict(merged)
                for pos in np.nonzero(join_arr[sl])[0]
            }
            if arrivals:
                scope.join_arrivals[gw_elem] = arrivals

        # clear the migrated rows from the device tables + hash maps
        ei_idx = jnp.asarray(sorted(tree), jnp.int32)
        ei_del_keys = jnp.asarray(
            [int(ei_i64[sl, 0]) for sl in sorted(tree)], jnp.int64
        )
        new_state = dataclasses.replace(
            s,
            ei_i32=s.ei_i32.at[ei_idx, state_mod.EI_STATE].set(-1),
            # key column cleared: both its words
            ei_i64=s.ei_i64.at[ei_idx, 0:2].set(-1),
            ei_map=hashmap.delete(
                s.ei_map, ei_del_keys, jnp.ones(ei_del_keys.shape, bool)
            ),
        )
        if job_slots:
            j_idx = jnp.asarray(job_slots, jnp.int32)
            j_keys = jnp.asarray(
                [int(job_i64[sl, state_mod.JBL_KEY]) for sl in job_slots],
                jnp.int64,
            )
            new_state = dataclasses.replace(
                new_state,
                job_i32=new_state.job_i32.at[j_idx, state_mod.JB_STATE].set(-1),
                job_i64=new_state.job_i64.at[
                    j_idx, 2 * state_mod.JBL_KEY : 2 * state_mod.JBL_KEY + 2
                ].set(-1),
                job_map=hashmap.delete(
                    new_state.job_map, j_keys, jnp.ones(j_keys.shape, bool)
                ),
            )
        if timer_slots:
            t_idx = jnp.asarray(timer_slots, jnp.int32)
            t_keys = jnp.asarray(
                [int(timer_keys[sl]) for sl in timer_slots], jnp.int64
            )
            new_state = dataclasses.replace(
                new_state,
                timer_key=new_state.timer_key.at[t_idx].set(-1),
                timer_due=new_state.timer_due.at[t_idx].set(-1),
                timer_map=hashmap.delete(
                    new_state.timer_map, t_keys, jnp.ones(t_keys.shape, bool)
                ),
            )
        if join_slots:
            jo_idx = jnp.asarray(join_slots, jnp.int32)
            jo_keys = jnp.asarray(
                [int(join_keys[sl]) for sl in join_slots], jnp.int64
            )
            new_state = dataclasses.replace(
                new_state,
                join_key=new_state.join_key.at[jo_idx].set(-1),
                join_nin=new_state.join_nin.at[jo_idx].set(0),
                join_arrived=new_state.join_arrived.at[jo_idx].set(False),
                join_pos_stamp=new_state.join_pos_stamp.at[jo_idx].set(-1),
                join_map=hashmap.delete(
                    new_state.join_map, jo_keys, jnp.ones(jo_keys.shape, bool)
                ),
            )
        # the host-side frees above bypass the kernel's free-slot ring —
        # re-derive it (and the lookup structures) NOW, or near capacity
        # the ring runs dry and inserts report spurious table overflow
        # while the freed rows sit unused until the next cadence rebuild
        self._state = state_mod.rebuild_lookup_state(new_state)
        self._note_lookup_rebuilt()
        return True

    def _routes_to_host(self, record: Record) -> bool:
        """True when a device-value-type record belongs to a host-only
        workflow or a host-side (possibly demoted) instance and must run on
        the oracle. Pure — no side effects: process_batch performs the
        demotion for CANCEL / UPDATE_PAYLOAD after flushing the pending
        device segment, so demotion always sees up-to-date state."""
        vt = int(record.metadata.value_type)
        value = record.value
        if vt == int(ValueType.WORKFLOW_INSTANCE):
            wf_key = value.workflow_key
            intent = int(record.metadata.intent)
            if wf_key <= 0 and intent == int(WI.CREATE):
                wf = self._resolve_workflow(value)
                wf_key = wf.key if wf is not None else -1
            if wf_key in self._host_only_keys:
                return True
            if self._unscalar_read(record, self._reads_of(wf_key)):
                # a variable the workflow reads (a condition, a correlation
                # key, a mapping source) holds a value with no column form:
                # the instance is born (and lives) host-side, where the
                # oracle walks documents and compares in f64. Device-born
                # records never hold one there (the kernel only moves what
                # staging let in), so this also finds the CREATED event of
                # an instance whose CREATE it sent to the host
                return True
            if int(record.metadata.record_type) == int(RecordType.COMMAND) and (
                intent in (int(WI.CANCEL), int(WI.UPDATE_PAYLOAD))
            ):
                # rare imperative ops always take the host path (with
                # demotion of their live device instance, done by
                # process_batch at the segment boundary)
                return True
            # EVENTS of host-side (host-only or demoted) instances route
            # by instance ownership in the oracle's element-instance index
            instances = self._host.element_instances.instances
            return (
                record.key in instances
                or value.workflow_instance_key in instances
            )
        if vt == int(ValueType.JOB):
            if self._unscalar_read(
                record, self._reads_of(value.headers.workflow_key)
            ):
                # e.g. a worker completing with a map under a variable a
                # condition reads: process_batch demotes the owning
                # instance first (for commands; job events with such
                # payloads are host-born)
                return True
            return (
                value.headers.workflow_key in self._host_only_keys
                or record.key in self._host.jobs
                or value.headers.workflow_instance_key
                in self._host.element_instances.instances
            )
        if vt == int(ValueType.TIMER):
            # host-side instances own their timers
            return (
                record.key in self._host.timers
                or value.activity_instance_key
                in self._host.element_instances.instances
            )
        if vt in (
            int(ValueType.MESSAGE), int(ValueType.MESSAGE_SUBSCRIPTION)
        ):
            # the message store lives on exactly one side (see _recompile)
            return not self._messages_on_device
        if vt == int(ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION):
            if int(record.metadata.record_type) == int(
                RecordType.COMMAND
            ) and self._unscalar_read(record, self._reads_any):
                # the message brings a value with no column form under a
                # variable a workflow reads: process_batch demotes the
                # target instance, and it correlates on the oracle
                return True
            # CORRELATE routes by where the TARGET INSTANCE lives: demoted
            # and host-only instances correlate on the oracle, device
            # instances in the kernel
            return (
                value.activity_instance_key
                in self._host.element_instances.instances
            )
        return False

    def _var_column(self, name: str) -> int:
        if self.meta is None:
            raise PayloadError("no workflows deployed")
        col = self.meta.varspace.column(name)
        if col >= self.num_vars:
            raise PayloadError(f"variable space overflow at {name!r}")
        return col

    # -- worker subscriptions (host-managed device table) ------------------
    def add_job_subscription(self, sub: JobSubscription) -> List[Record]:
        """Idempotent per subscriber key (same contract as the interpreter
        engine): a re-subscribe replaces the previous slot rather than
        double-registering it.

        Returns ACTIVATE commands for the backlog of already-created
        matching jobs (reference: ActivateJobStreamProcessor reads the log
        from the start, so pre-existing CREATED / failed-with-retries /
        timed-out jobs get assigned too — this is what lets workers find
        their jobs again after a failover/restart). The caller appends the
        returned commands to the partition log, exactly like the host
        oracle's add_job_subscription.

        The subscription registers in BOTH engines: the device table serves
        device-workflow jobs, the embedded host oracle serves jobs of
        host-only workflows. Each side draws on its own credit counter, so
        the per-subscription in-flight bound is per-engine."""
        self.remove_job_subscription(sub.subscriber_key)
        host_backlog = self._host.add_job_subscription(dataclasses.replace(sub))
        subs = self._subscriptions()
        if subs.valid.all():
            raise RuntimeError("subscription table full")
        free = int(np.argmin(subs.valid))

        # the backlog of this type, from the engine's account of parked
        # jobs (the table is scanned only where that is not known). A
        # subscription arrives outside every cycle: what it counts (a table
        # scan, row reads) is flushed from a clock of its own
        from zeebe_tpu.runtime.metrics import observe_phases

        type_id = self.interns.intern(sub.job_type)
        backlog: List[Record] = []
        credits = sub.credits
        now = self.clock()
        clock = tracing.PhaseClock()
        with self.on_clock(clock):
            for key in self._parked_keys({type_id}):
                if credits <= 0:
                    break
                if key in self._assigning:
                    continue  # the pool's, or already on its way (__init__)
                backlog.append(
                    self._activate_parked(
                        key, now + sub.timeout, sub.worker, sub.subscriber_key
                    )
                )
                credits -= 1
        observe_phases(clock)

        def with_slot(column: np.ndarray, value) -> np.ndarray:
            column = column.copy()
            column[free] = value
            return column

        self._mark_device_dirty("sub")
        s = self.state  # with every returned credit applied
        subs = dataclasses.replace(
            subs,
            key=with_slot(subs.key, sub.subscriber_key),
            type=with_slot(subs.type, type_id),
            worker=with_slot(subs.worker, self.interns.intern(sub.worker)),
            timeout=with_slot(subs.timeout, sub.timeout),
            valid=with_slot(subs.valid, True),
        )
        # backlog activations consumed credits up front; the kernel
        # returns them on ACTIVATE rejection like pool assignments
        sub_credits = with_slot(jax.device_get(s.sub_credits), credits)
        key, type_, worker, timeout, valid, sub_credits = self._put_leaves(
            subs.key, subs.type, subs.worker, subs.timeout, subs.valid,
            sub_credits,
        )
        self._state = dataclasses.replace(
            s, sub_key=key, sub_type=type_, sub_worker=worker,
            sub_timeout=timeout, sub_valid=valid, sub_credits=sub_credits,
        )
        self._subs = subs
        return host_backlog + backlog

    def remove_job_subscription(self, subscriber_key: int) -> None:
        self._host.remove_job_subscription(subscriber_key)
        self._mark_device_dirty("sub")
        subs = self._subscriptions()
        match = subs.key == subscriber_key
        if not (match & subs.valid).any():
            return  # the device's column holds no such subscription
        subs = dataclasses.replace(subs, valid=subs.valid & ~match)
        (valid,) = self._put_leaves(subs.valid)
        self._state = dataclasses.replace(self.state, sub_valid=valid)
        self._subs = subs

    def _put_leaves(self, *columns: np.ndarray) -> tuple:
        """Host arrays as leaves of the state, in one ``device_put``,
        placed like the leaves they replace: an uncommitted leaf on the
        default device gives the step, the due probe and the credit flush
        a second signature, compiled on the broker actor mid-traffic."""
        return self._place(jax.device_put(columns))

    def increase_job_credits(self, subscriber_key: int, credits: int) -> None:
        """A worker's credit return: host arithmetic. The subscriber's
        slot is found in the host side of the table and the credits wait
        in ``_credit_delta`` for the column's next reader
        (``_flush_credits``); nothing is launched and nothing fetched
        (but the table where it is not known). Like a subscription it
        arrives outside every cycle, so its seconds and its count are
        flushed from a clock of its own."""
        from zeebe_tpu.runtime.metrics import observe_phases

        clock = tracing.PhaseClock()
        with clock.phase("credit_return"):
            self._host.increase_job_credits(subscriber_key, credits)
            # as the device's column would take it: every slot that holds
            # the key, valid or not
            match = self._subscriptions().key == subscriber_key
            if match.any():
                self._mark_device_dirty("sub")
                if self._credit_delta is None:
                    self._credit_delta = np.zeros(match.shape, np.int32)
                self._credit_delta[match] += credits
        clock.count("credit_returns", 1)
        observe_phases(clock)

    # -- deadline scans (broker tick) --------------------------------------
    def deadlines_due_probe(self):
        """Device i32 bitmask scalar (PROBE_DEADLINES | PROBE_JOB_BACKLOG):
        is any device-side job/timer/message deadline due now, and is
        there unassigned job backlog a free credit could serve? The
        broker launches this and polls ``is_ready()`` without blocking —
        the full column sweeps below each pull whole table columns
        device→host (8 MB apiece at 2^20 rows) behind a sync and would
        starve the broker actor at the tick rate. Host-oracle deadlines
        are NOT covered: the broker sweeps those (cheap dict scans) every
        tick via ``host_deadline_commands``."""
        now = jnp.asarray(self.clock(), jnp.int64)
        self._state, mask = _due_probe_jit(self.state, now)
        return mask

    def backlog_activations(self) -> List[Record]:
        """Host-oracle side only (cheap dict scans — call freely). The
        DEVICE job backlog is served by ``device_backlog_activations``,
        gated behind the async probe's PROBE_JOB_BACKLOG bit so the tick
        only pays the device→host pull when something is assignable."""
        return self._host.backlog_activations()

    def device_backlog_activations(self) -> List[Record]:
        """ACTIVATE commands for device-table jobs that became activatable
        while every subscription was out of credits (same stranding class
        as the host engine's backlog_activations; the kernel only assigns
        jobs when it processes a job event with credits available), found
        in the engine's own account of them (``_parked``, see
        ``__init__``), in key order. With nothing parked this returns
        before it touches the device. Credits are consumed up front,
        exactly like add_job_subscription's backlog scan — the kernel
        returns them on ACTIVATE rejection. A job with a pool event or an
        ACTIVATE on its way is not this sweep's (``_assigning``): it is
        left alone and no credit is taken for it. The caller appends what
        this returns; phase ``backlog`` of the cycle it runs in (the
        tick's)."""
        with self._clock.phase("backlog"):
            if self._parked is not None and not self._parked:
                return []
            return self._sweep_job_backlog()

    def _sweep_job_backlog(self) -> List[Record]:
        self._clock.count("backlog_sweeps", 1)
        subs = self._subscriptions()
        valid = subs.valid
        if not valid.any():
            return []
        sub_keys, sub_types = subs.key, subs.type
        sub_timeouts, sub_workers = subs.timeout, subs.worker
        # the one column the device writes too: the sweep's one fetch,
        # with every returned credit applied
        s = self.state
        sub_credits = np.array(jax.device_get(s.sub_credits))
        if not (sub_credits[valid] > 0).any():
            return []
        assigning = self._assigning
        skipped = 0
        out: List[Record] = []
        now = self.clock()
        sub_slots = [int(i) for i in np.nonzero(valid)[0]]
        credited = {
            int(sub_types[i]) for i in sub_slots if sub_credits[i] > 0
        }
        # the round-robin cursor persists in state.sub_rr across calls
        # (and across snapshot/restore): a fresh `rr = 0` every tick made
        # the first credited subscription win every drain, starving the
        # rest — the oracle's _job_rr_cursor is global, so this is also
        # host-oracle parity
        rr = subs.rr % len(sub_slots)
        left = int(sub_credits[valid].clip(min=0).sum())
        walked = 0
        for key in self._parked_keys(credited):
            if not left:
                break
            walked += 1
            if key in assigning:
                skipped += 1
                continue
            type_id = self._parked[key][0]
            target = None
            for j in range(len(sub_slots)):
                cand = sub_slots[(rr + j) % len(sub_slots)]
                if sub_credits[cand] > 0 and int(sub_types[cand]) == type_id:
                    target = cand
                    rr = (rr + j + 1) % len(sub_slots)
                    break
            if target is None:
                continue  # no credits for this type; try other jobs' types
            sub_credits[target] -= 1
            left -= 1
            out.append(
                self._activate_parked(
                    key,
                    now + int(sub_timeouts[target]),
                    self.interns.string(int(sub_workers[target])) or "",
                    int(sub_keys[target]),
                )
            )
        if walked:
            self._clock.count("backlog_parked_walked", walked)
        if skipped:
            self._clock.count("backlog_skipped_in_flight", skipped)
        if out:  # rr only advances on an assignment, which also appends
            self._clock.count("backlog_activations", len(out))
            self._mark_device_dirty("sub")
            sub_credits, sub_rr = self._put_leaves(sub_credits, np.int32(rr))
            self._state = dataclasses.replace(
                s, sub_credits=sub_credits, sub_rr=sub_rr
            )
            self._subs = dataclasses.replace(subs, rr=rr)
        return out

    def _parked_keys(self, type_ids) -> List[int]:
        """The parked jobs of the types ``type_ids``, in key order. Where
        ``_parked`` is not known (``__init__``) it is scanned from the
        device's job table first: the one place the table's columns cross
        to the host for the backlog, once per restore."""
        if self._parked is None:
            self._parked = self._scan_parked_jobs()
        return sorted(
            k for k, held in self._parked.items() if held[0] in type_ids
        )

    def _scan_parked_jobs(self) -> Dict[int, tuple]:
        """Every activatable row with retries left and nothing on its way,
        as key -> (type id, slot, None: since when it waits is not known)."""
        self._clock.count("backlog_table_scans", 1)
        s = self.state
        job_i32 = np.asarray(s.job_i32)
        job_keys = state_mod.host_i64(s.job_i64, state_mod.JBL_KEY)
        slots = np.nonzero(
            np.isin(job_i32[:, state_mod.JB_STATE], _JOB_ACTIVATABLE_STATES)
            & (job_i32[:, state_mod.JB_RETRIES] > 0)
        )[0]
        assigning = self._assigning
        return {
            key: (type_id, slot, None)
            for key, type_id, slot in zip(
                job_keys[slots].tolist(),
                job_i32[slots, state_mod.JB_TYPE].tolist(),
                slots.tolist(),
            )
            if key not in assigning
        }

    def _activate_parked(
        self, key: int, deadline: int, worker: str, subscriber_key: int
    ) -> Record:
        """The ACTIVATE command that hands parked job ``key`` out: it
        leaves ``_parked`` and is on its way (``_assigning``)."""
        _type_id, held, since = self._parked.pop(key)
        self._assigning.add(key)
        if since is not None:
            # a sum of seconds that is no phase: the wait spans cycles
            self._clock.count(
                "backlog_park_wait", max(0, self.clock() - since) / 1e3
            )
        activated = (
            held.copy() if isinstance(held, JobRecord)
            else self._job_value_from_slot(held)
        )
        activated.deadline = deadline
        activated.worker = worker
        return Record(
            key=key,
            value=activated,
            metadata=RecordMetadata(
                record_type=RecordType.COMMAND,
                value_type=ValueType.JOB,
                intent=int(JI.ACTIVATE),
                request_stream_id=subscriber_key,
            ),
        )

    def _job_left(self, key: int, gone: bool) -> None:
        """A job is no longer parked: it was activated, or (``gone``) it
        left the device table, and then a pool event of it that is still
        in flight must not park it either (``_ended``)."""
        if self._parked:
            self._parked.pop(key, None)
        if gone:
            self._ended.add(key)

    def host_deadline_commands(self) -> List[Record]:
        """The embedded oracle's due commands only (same per-family key
        order the merged sweeps produce when the device side is empty).
        The broker tick calls this UNCONDITIONALLY every tick — host
        sweeps are cheap dict scans — and pairs it with
        ``device_deadline_commands`` gated by the async probe."""
        return (
            sorted(self._host.check_job_deadlines(), key=lambda r: r.key)
            + sorted(self._host.check_timer_deadlines(), key=lambda r: r.key)
            + sorted(self._host.check_message_ttls(), key=lambda r: r.key)
        )

    def device_deadline_commands(self) -> List[Record]:
        """Device-side due commands only (jobs, timers, message TTLs — each
        family key-sorted, same per-family order as host_deadline_commands).
        Callers that already swept the host oracle this tick use this to
        avoid double-emitting host commands (which would append duplicate
        TIME_OUT/TRIGGER/DELETE commands and surface as rejections)."""
        return (
            self._device_job_deadlines()
            + self._device_timer_deadlines()
            + self._device_message_ttls()
        )

    def check_job_deadlines(self) -> List[Record]:
        # jobs of host-only/demoted workflows live in the embedded oracle;
        # merge key-sorted so mixed device+host populations emit the same
        # global order the pure oracle would (log order IS the contract)
        return sorted(
            self._device_job_deadlines() + self._host.check_job_deadlines(),
            key=lambda r: r.key,
        )

    def _device_job_deadlines(self) -> List[Record]:
        now = self.clock()
        s = self.state
        job_i64 = state_mod.host_i64(s.job_i64)
        keys = job_i64[:, state_mod.JBL_KEY]
        states = np.asarray(s.job_state)
        deadlines = job_i64[:, state_mod.JBL_DEADLINE]
        due = (states == int(JI.ACTIVATED)) & (deadlines >= 0) & (deadlines <= now)
        out = []
        for slot in np.nonzero(due)[0][np.argsort(keys[np.nonzero(due)[0]])]:
            out.append(
                Record(
                    key=int(keys[slot]),
                    metadata=RecordMetadata(
                        record_type=RecordType.COMMAND,
                        value_type=ValueType.JOB,
                        intent=int(JI.TIME_OUT),
                    ),
                    value=self._job_value_from_slot(int(slot)),
                )
            )
        return out

    def check_timer_deadlines(self) -> List[Record]:
        # timers of host-only/demoted workflows (incl. boundary-event
        # timers) live in the embedded oracle and must be swept too;
        # key-sorted merge = the pure oracle's global order
        return sorted(
            self._device_timer_deadlines() + self._host.check_timer_deadlines(),
            key=lambda r: r.key,
        )

    def _device_timer_deadlines(self) -> List[Record]:
        now = self.clock()
        s = self.state
        h64 = state_mod.host_i64
        keys = h64(s.timer_key, 0)
        dues = h64(s.timer_due, 0)
        due = (keys >= 0) & (dues <= now)
        slots = np.nonzero(due)[0]
        if not len(slots):
            return []
        # one pull per column, not one per due timer
        instance_keys = h64(s.timer_instance_key, 0)
        aiks = h64(s.timer_aik, 0)
        wfs = np.asarray(s.timer_wf)
        elems = np.asarray(s.timer_elem)
        out = []
        for slot in slots[np.argsort(keys[slots])]:
            slot = int(slot)
            out.append(
                Record(
                    key=int(keys[slot]),
                    metadata=RecordMetadata(
                        record_type=RecordType.COMMAND,
                        value_type=ValueType.TIMER,
                        intent=2,  # TimerIntent.TRIGGER
                    ),
                    value=TimerRecord(
                        workflow_instance_key=int(instance_keys[slot]),
                        activity_instance_key=int(aiks[slot]),
                        due_date=int(dues[slot]),
                        handler_element_id=self.meta.element_id(
                            int(wfs[slot]), int(elems[slot])
                        ),
                    ),
                )
            )
        return out

    def check_message_ttls(self) -> List[Record]:
        return sorted(
            self._device_message_ttls() + self._host.check_message_ttls(),
            key=lambda r: r.key,
        )

    def _device_message_ttls(self) -> List[Record]:
        from zeebe_tpu.protocol.intents import MessageIntent as MI
        from zeebe_tpu.protocol.records import MessageRecord

        now = self.clock()
        s = self.state
        keys = state_mod.host_i64(s.msg_key, 0)
        due = (keys >= 0) & (state_mod.host_i64(s.msg_deadline, 0) <= now)
        slots = np.nonzero(due)[0]
        names = self.meta.varspace.names if self.meta else []
        msg_i32 = np.asarray(s.msg_i32)
        msg_pay = np.asarray(s.msg_pay)
        out = []
        for slot in slots[np.argsort(keys[slots])]:
            slot = int(slot)
            out.append(
                Record(
                    key=int(keys[slot]),
                    metadata=RecordMetadata(
                        record_type=RecordType.COMMAND,
                        value_type=ValueType.MESSAGE,
                        intent=int(MI.DELETE),
                    ),
                    value=MessageRecord(
                        name=self.interns.string(int(msg_i32[slot, 0])) or "",
                        correlation_key=self._corr_string(
                            int(msg_i32[slot, 1]), int(msg_i32[slot, 2])
                        ),
                        payload=rb.columns_to_payload(
                            *_host_unpack_payload(msg_pay[slot]),
                            names, self.interns, self._values,
                        ),
                        message_id=(
                            self.interns.string(int(msg_i32[slot, 3])) or ""
                        ),
                    ),
                )
            )
        return out

    def compaction_floor(self) -> int:
        """See PartitionEngine.compaction_floor — incident state lives on
        the embedded host oracle."""
        return min(
            self.last_processed_position + 1, self._host.compaction_floor()
        )

    # -- snapshot / restore (reference StateSnapshotController: RocksDB
    # checkpoint keyed by last-processed position; here the SoA tables are
    # device_get into the data-only device envelope of log/stateser.py,
    # alongside the intern/varspace sidecars and the embedded host oracle's
    # state. Restore + replay is the same contract as the host engine:
    # the broker replays committed records after last_processed_position
    # with side effects suppressed.) --------------------------------------
    # every device table family (kept in sync with
    # stateser.DEVICE_ARRAY_FAMILIES; pinned by a test) — module-local so
    # the per-wave mark pays no import lookup
    _ALL_DEVICE_FAMILIES = (
        "ei", "job", "join", "keys", "msg", "msub", "sub", "timer",
    )

    def _mark_device_dirty(self, *families: str) -> None:
        """Record device-table mutations for delta snapshots; no args =
        every device family (a kernel step may write any table) — host
        family tracking stays live, so clean host parts (e.g. workflows)
        still reuse their previous segments on the next take."""
        if self._dirty_device is None:
            return
        self._dirty_device.update(families or self._ALL_DEVICE_FAMILIES)

    def snapshot_dirty_families(self):
        """Union of device ("d/<family>") and embedded-oracle ("h/...")
        dirty families since the last mark_clean; None when either side's
        tracking is cold (forces a full take)."""
        host = self._host.snapshot_dirty_families()
        if self._dirty_device is None or host is None:
            return None
        return frozenset({"d/" + f for f in self._dirty_device} | set(host))

    def snapshot_mark_clean(self) -> None:
        self._dirty_device = set()
        self._host.snapshot_mark_clean()

    def snapshot_mark_dirty(self, families=None) -> None:
        if families is None:
            self._dirty_device = None
            self._host.snapshot_mark_dirty(None)
            return
        dev = [f[2:] for f in families if f.startswith("d/")]
        if dev:  # empty would mean mark-ALL in _mark_device_dirty's varargs
            self._mark_device_dirty(*dev)
        host = [f for f in families if f.startswith("h/")]
        if host:
            self._host.snapshot_mark_dirty(host)

    def snapshot_state(self, families=None) -> dict:
        from zeebe_tpu.log import stateser
        from zeebe_tpu.tpu import hashmap

        dirty_dev = None
        if families is not None:
            dirty_dev = {f[2:] for f in families if f.startswith("d/")}
        arrays: Dict[str, Optional[np.ndarray]] = {}
        read: List[str] = []

        def put(name: str, value, skip: bool, to_host=np.asarray) -> None:
            if skip:
                # clean family: the caller reuses the previous manifest's
                # segment — NO device→host transfer, no encode, no hash
                arrays[name] = None
            else:
                arrays[name] = to_host(value)
                read.append(name)

        # the format on disk is older than the plane layout and does not
        # change with it: 64-bit tables, columns and hash-map keys are
        # written as the int64 arrays they always were (host views of the
        # pulled planes; restore_state converts back)
        state = self.state  # with every returned credit applied
        for f in dataclasses.fields(state):
            skip = (
                dirty_dev is not None
                and stateser.device_array_family(f.name) not in dirty_dev
            )
            v = getattr(state, f.name)
            if isinstance(v, hashmap.HashTable):
                put(f.name + ".keys", v, skip, hashmap.host_keys)
                put(f.name + ".vals", v.vals, skip)
            elif f.name in state_mod.I64_TABLES:
                put(f.name, v, skip, state_mod.host_i64)
            elif f.name in state_mod.I64_COLUMNS:
                put(f.name, v, skip, lambda p: state_mod.host_i64(p, 0))
            else:
                put(f.name, v, skip)
        self.last_snapshot_readback = read
        return {
            "fmt": stateser.FORMAT_DEVICE_V1,
            "arrays": arrays,
            "meta": {
                # interned strings in id order (id 0 is reserved NIL);
                # restoring in order reproduces identical ids, which the
                # table columns (job types, workers, string payloads) hold
                "interns": [s or "" for s in self.interns._by_id[1:]],
                # held payload values under the handles the tables carry,
                # and who owns each (tpu/values.py)
                "values": self._values.snapshot(),
                "variables": (
                    list(self.meta.varspace.names) if self.meta else []
                ),
                "last_processed_position": self.last_processed_position,
            },
            "host": self._host.snapshot_state(),
        }

    def restore_state(self, snap: dict) -> None:
        from zeebe_tpu.log import stateser
        from zeebe_tpu.tpu import hashmap

        if snap.get("fmt") != stateser.FORMAT_DEVICE_V1:
            raise ValueError("not a device-engine snapshot")
        self._dirty_device = None  # restored engine: next take is full
        # what is on its way is not in a snapshot, nor who waits for a
        # credit: the first sweep scans the restored table (__init__)
        self._assigning.clear()
        self._ended.clear()
        self._parked = None
        # host oracle first: restores the shared repository (workflows) and
        # the control-plane state families
        self._host.restore_state(snap["host"])
        meta = snap.get("meta", {})
        self.interns = InternTable()
        for s in meta.get("interns", []):
            self.interns.intern(s)
        self._values.restore(meta.get("values"))
        # recompile through the SAME path as deployments (_recompile):
        # it re-derives the host-only split and compiles only the
        # device-compatible subset, so workflow slot numbering matches the
        # run that wrote the snapshot; the snapshot's variable-column order
        # is forced (column ids live in the payload matrices, so order is
        # part of the state)
        self.meta = None
        self.graph = None
        if self.repository.by_key:
            # no store migration during restore: the snapshot arrays below
            # already carry the message store on whichever side the gate
            # computes (the gate is a pure function of the restored repo)
            self._restoring = True
            try:
                self._recompile(extra_variables=list(meta.get("variables", [])))
            finally:
                self._restoring = False
        arrays = snap["arrays"]
        kwargs = {}
        pre_round4_arrays = False
        for f in dataclasses.fields(self.state):
            if f.name + ".keys" in arrays:
                kwargs[f.name] = hashmap.from_host(
                    arrays[f.name + ".keys"], arrays[f.name + ".vals"]
                )
            elif f.name in arrays and f.name in state_mod.I64_TABLES:
                # int64 on disk, planes on the device
                kwargs[f.name] = jnp.asarray(
                    state_mod.host_planes(arrays[f.name])
                )
            elif f.name in arrays and f.name in state_mod.I64_COLUMNS:
                kwargs[f.name] = jnp.asarray(
                    state_mod.host_planes(arrays[f.name], column=True)
                )
            elif f.name == "ei_i32" and arrays[f.name].shape[1] == 5:
                # pre-round-4 snapshot: pad the pending-boundary column
                kwargs[f.name] = jnp.concatenate(
                    [jnp.asarray(arrays[f.name]),
                     jnp.full((arrays[f.name].shape[0], 1), -1, jnp.int32)],
                    axis=1,
                )
                pre_round4_arrays = True
            elif f.name in arrays:
                kwargs[f.name] = jnp.asarray(arrays[f.name])
            else:
                # snapshot written before this state family existed (e.g.
                # message tables added in round 4): keep the fresh empty
                # table; any live state of that family sits on the host
                # side of the snapshot and migrates below
                kwargs[f.name] = getattr(self.state, f.name)
                pre_round4_arrays = True
        st = state_mod.EngineState(**kwargs)
        # job-worker subscriptions are transient client-session state: the
        # reference drops them across failover (workers re-subscribe); the
        # snapshot carries the columns but a restored partition starts with
        # an empty subscription table
        st = dataclasses.replace(
            st,
            sub_key=jnp.full_like(st.sub_key, -1),
            sub_credits=jnp.zeros_like(st.sub_credits),
            sub_valid=jnp.zeros_like(st.sub_valid),
        )
        # derive the lookup structures from the restored rows: an old
        # snapshot has no index arrays, a cross-backend snapshot may carry
        # a bucket layout the local builder would not produce, and the
        # fallback maps must cover every restored live instance
        st = state_mod.rebuild_lookup_state(st)
        self.state = self._place(st)
        if self._mesh is not None:
            # the restored capacity may differ from the ctor template's,
            # which changes the spec tree (divisibility) and the program's
            # traced shapes — rebuild both (register_jit: latest wins)
            from zeebe_tpu.tpu import shard as shard_mod

            self._state_step = shard_mod.build_state_step(self._mesh, st)
            self._shard_exchange_bytes = shard_mod.state_exchange_bytes(
                st, self._state_shards
            )
        self._note_lookup_rebuilt()
        self.capacity = st.capacity
        self.num_vars = st.num_vars
        self.last_processed_position = int(
            meta.get("last_processed_position", -1)
        )
        if pre_round4_arrays and self._messages_on_device:
            # the old snapshot's message store lives host-side (flat-key
            # message workflows were host-only before round 4) but the
            # restored deployment now computes a device store — migrate so
            # publishes see the restored subscriptions
            self._migrate_message_store_to_device()

    def _job_value_from_slot(self, slot: int) -> JobRecord:
        """A device job's record, from three ROW reads (sliced on the
        device, a few hundred bytes over the wire: whole-column pulls here
        cost ~230 MB per job at 2^20 rows), once per timed-out job and
        per backlog activation of a job that a table scan found (a job a
        wave parked carries its value). Phase ``job_read`` of whichever
        cycle asks (a tick's sweeps, a subscription's backlog): the read
        and the record built on it."""
        with self._clock.phase("job_read"):
            self._clock.count("job_row_reads", 1)
            s = self.state
            i32, i64, pay = jax.device_get(
                (s.job_i32[slot], s.job_i64[slot], s.job_pay[slot])
            )
            i64 = state_mod.host_i64(i64)
            wf_slot = int(i32[state_mod.JB_WF])
            elem = int(i32[state_mod.JB_ELEM])
            workflow = (
                self.meta.workflows[wf_slot]
                if self.meta and 0 <= wf_slot < len(self.meta.workflows)
                else None
            )
            element = (
                workflow.elements[elem]
                if workflow and 0 <= elem < len(workflow.elements) else None
            )
            return JobRecord(
                type=self.interns.string(int(i32[state_mod.JB_TYPE])) or "",
                retries=int(i32[state_mod.JB_RETRIES]),
                deadline=int(i64[state_mod.JBL_DEADLINE]),
                worker=self.interns.string(int(i32[state_mod.JB_WORKER])) or "",
                payload=rb.columns_to_payload(
                    *_host_unpack_payload(pay),
                    self.meta.varspace.names if self.meta else [],
                    self.interns, self._values,
                ),
                # as the job's events carry them (_materialize_value)
                custom_headers=dict(element.job_headers) if element else {},
                headers=JobHeaders(
                    workflow_instance_key=int(i64[state_mod.JBL_IKEY]),
                    bpmn_process_id=workflow.id if workflow else "",
                    workflow_definition_version=workflow.version if workflow else -1,
                    workflow_key=workflow.key if workflow else -1,
                    activity_id=self.meta.element_id(wf_slot, elem) if self.meta else "",
                    activity_instance_key=int(i64[state_mod.JBL_AIK]),
                ),
            )

    # ------------------------------------------------------------------
    # batch processing
    # ------------------------------------------------------------------
    def process(self, record: Record) -> ProcessingResult:
        """Single-record convenience (tests); real throughput uses
        process_batch / the dispatch_wave+collect_wave pipeline."""
        return self.process_batch([record])

    def process_batch(self, records: List[Record]) -> ProcessingResult:
        """Synchronous wave: dispatch + collect, merged record-major (the
        cluster drain's non-pipelined entry)."""
        return ProcessingResult.merged(self.process_wave(records))

    def process_wave(self, records: List[Record]) -> List[ProcessingResult]:
        """Per-record results of one wave (same contract as the host
        oracle's process_wave; one device dispatch per contiguous device
        segment)."""
        return self.collect_wave(self.dispatch_wave(records))

    def dispatch_wave(self, records) -> PendingWave:
        """Stage + launch a wave WITHOUT reading device outputs back.
        Host-routed records process inline (they mutate host state in
        strict log order); device segments dispatch through the kernel and
        stay pending until ``collect_wave``. The caller may dispatch the
        next wave before collecting this one — the state dependency chains
        on device, so host staging of wave N+1 overlaps device compute of
        wave N.

        ``records`` may be a plain list of ``Record`` objects or a lazy
        columnar view (``RecordsView`` — the drains' ``committed_view``
        spans). Routing reads the COLUMNS; a lazy entry that is a device
        EVENT of a device-resident instance enters its segment as a ref
        and later stages straight from the emission batch's columns — no
        ``Record`` ever materializes for it (the columnar plane's
        device-path slice)."""
        clock = tracing.phase_clock(tracing.selected_slices())
        self._values.annotate = tracing.TRACER is not None
        with self.on_clock(clock), clock.phase("route"):
            return self._route_wave(records, clock)

    @contextlib.contextmanager
    def on_clock(self, clock):
        """The engine's phases and counts land on ``clock`` meanwhile: a
        wave's in ``dispatch_wave``, a tick's around its sweeps."""
        self._clock = clock
        try:
            yield
        finally:
            self._clock = self._idle_clock

    def _route_wave(self, records, clock) -> PendingWave:
        # The repository is shared by a broker's partitions, and only the
        # partition that processes a DEPLOYMENT record recompiles on it: a
        # workflow deployed through (or fetched from) another partition
        # reaches this engine's repository without a record. Compile it in
        # before routing — with no graph for it, its instances would run on
        # the embedded host engine without a word.
        if len(self.repository.by_key) != self._repo_compiled:
            self._recompile()
        view = records if hasattr(records, "entries") else None
        entries = list(view.entries()) if view is not None else records
        n = len(entries)
        if view is not None:
            col_vts = view.value_types()
            col_rts = view.record_types()
            col_its = view.intents()
            col_pos = view.positions()
            col_keys = view.keys()
        else:
            col_vts = None
            col_rts = col_its = col_pos = col_keys = None

        per_record: List[Optional[ProcessingResult]] = [None] * n
        wave = PendingWave(
            records=records, per_record=per_record,
            partition_id=self.partition_id, phases=clock,
        )
        positions = wave.positions
        # segment processing: device rows batch up, but whenever a
        # host-routed record appears the pending device segment DISPATCHES
        # through the kernel first — state mutates in strict log order,
        # exactly like the oracle's per-record loop (a host record may
        # depend on state a preceding device record writes, e.g. a job
        # COMPLETE followed by the instance's CANCEL)
        pending: List[int] = []
        # resident routing: the pending segment carries ONE route class —
        # ("create",) all-CREATE, ("ik", shard) proven-resident, ("fb",)
        # unknown/mixed — and a record of a different class flushes first
        # (single-owner waves are what make the routed program exact).
        # None everywhere when routing is inactive: no split, no change.
        pending_route: List = [None]
        # the two engines allocate from ONE keyspace; their counters sync
        # at segment boundaries so keys never collide across the
        # host/device split. Device→host pulls cost a device read and only
        # happen when a device segment has run since the last pull — the
        # flag lives on SELF because the boundary usually falls BETWEEN
        # process_batch calls; host→device pushes are device-side maxima
        # (no read).
        host_allocated = [False]

        def push_host_keys() -> None:
            if not host_allocated[0]:
                return
            self._mark_device_dirty("keys")
            # device-side maxima: no host↔device round trip
            self._state = dataclasses.replace(
                self.state,
                next_wf_key=jnp.maximum(
                    self.state.next_wf_key,
                    jnp.asarray(self._host.wf_keys.peek, jnp.int64),
                ),
                next_job_key=jnp.maximum(
                    self.state.next_job_key,
                    jnp.asarray(self._host.job_keys.peek, jnp.int64),
                ),
            )
            host_allocated[0] = False

        def seg_meta(i: int):
            if col_vts is not None:
                return col_vts[i], col_rts[i], col_its[i]
            md = entries[i].metadata
            return (
                int(md.value_type), int(md.record_type), int(md.intent),
            )

        # commands on ONE job row in one device segment (kernel.step,
        # "Commands on ONE row"): the kernel turns a later row of the same
        # intent away by itself; a later row of another intent, or a second
        # UPDATE_RETRIES, is judged against the first one's outcome, so it
        # starts a new segment (the next step program of this wave, in log
        # order, on the table the first one left)
        seg_job_cmds: Dict[int, int] = {}
        # the same for the message tables (kernel.step, "message tables"):
        # a PUBLISH and an OPEN (or any two commands) on one composite in
        # one segment would each read the tables as they were before it,
        # so neither would see the other; the second starts a new segment
        seg_msg_composites: set = set()
        cut_vts = self._cut_value_types
        # the scopes whose payload a step of the pending segment reads or
        # writes (kernel.step, "scope payload"): where a scope can hold
        # several tokens, a second such step of one scope starts a new
        # segment, so it merges into what the first left
        seg_scopes: set = set()
        scope_cuts = self._scope_cuts
        vt_wi = int(ValueType.WORKFLOW_INSTANCE)
        # job keys whose activation record this wave steps: they leave
        # ``_assigning`` when the wave is collected
        assigning_stepped = wave.assigning_stepped
        # the pool events among them that a device segment steps
        pool_events = wave.pool_events
        vt_job = int(ValueType.JOB)
        rt_command = int(RecordType.COMMAND)
        rt_event = int(RecordType.EVENT)

        def flush() -> None:
            seg_job_cmds.clear()
            seg_msg_composites.clear()
            seg_scopes.clear()
            if not pending:
                return
            push_host_keys()  # device allocations continue after the host's
            seg = self._dispatch_device(
                [entries[i] for i in pending],
                [positions[i] for i in pending],
                [seg_meta(i) for i in pending],
                route=pending_route[0],
            )
            seg.rows = list(pending)
            wave.segments.append(seg)
            self.device_records_processed += len(pending)
            pending.clear()
            self._device_keys_dirty = True

        for i in range(n):
            entry = entries[i]
            lazy = type(entry) is tuple
            if col_vts is not None:
                vt, rt, intent = col_vts[i], col_rts[i], col_its[i]
                pos, key = col_pos[i], col_keys[i]
            else:
                md = entry.metadata
                vt = int(md.value_type)
                rt = int(md.record_type)
                intent = int(md.intent)
                pos, key = entry.position, entry.key
            positions.append(pos)
            pool_event = (
                vt == vt_job and rt == rt_event and intent in _JOB_POOL_EVENTS
            )
            if pool_event or (
                vt == vt_job and rt == rt_command and intent == _JI_ACTIVATE
            ):
                assigning_stepped.append(key)
            device_vt = vt in _DEVICE_VALUE_TYPES or (
                vt in _MESSAGE_VALUE_TYPES
                and self.graph is not None
                and self.graph.has_messages
            )
            eligible = (
                device_vt and self.meta is not None and self.graph is not None
            )
            if lazy and eligible and self._lazy_device_row(
                entry, vt, rt, intent, key
            ):
                # device EVENT of a device-resident instance, born from a
                # readback batch with current workflow slots: the row
                # stages from columns; no Record materializes, and the
                # log-backed position cache covers any later re-read
                rc = self._wave_route_class(entry, True, vt, rt, intent)
                if pending and rc != pending_route[0]:
                    flush()
                if scope_cuts and vt == vt_wi and (
                    intent in _SCOPE_PAYLOAD_INTENTS
                ):
                    src, j = entry[0].device_ref(entry[1])
                    scope_key = int(src.device_source[1]["scope_key"][j])
                    if scope_key >= 0:
                        if scope_key in seg_scopes:
                            flush()
                        seg_scopes.add(scope_key)
                pending_route[0] = rc
                pending.append(i)
                if pool_event:
                    pool_events.append((key, entry))
                continue
            if lazy:
                record = entry[0].row(entry[1])
                entries[i] = record
            else:
                record = entry
            # records_by_position aliases the host oracle's cache (one
            # shared dict) — a single write covers both readers
            self.records_by_position[pos] = record
            md = record.metadata
            if eligible and not self._routes_to_host(record):
                # a payload value with no column form (a map, a list, a
                # number f32 does not hold exactly) under a variable no
                # workflow reads is staged as a handle into the value
                # store (_stage_payload): held exactly, never rounded
                rc = self._wave_route_class(record, False, vt, rt, intent)
                if pending and rc != pending_route[0]:
                    flush()
                if scope_cuts and vt == vt_wi and rt == rt_event and (
                    intent in _SCOPE_PAYLOAD_INTENTS
                ):
                    scope_key = record.value.scope_instance_key
                    if scope_key >= 0:
                        if scope_key in seg_scopes:
                            flush()
                        seg_scopes.add(scope_key)
                elif rt == rt_command and vt in cut_vts:
                    if vt != vt_job:
                        with clock.phase("message_cut"):
                            composite = _message_composite(vt, record.value)
                            met = composite in seg_msg_composites
                        if met:
                            clock.count("message_cuts", 1)
                            flush()
                        seg_msg_composites.add(composite)
                    elif intent in _JOB_ROW_COMMANDS:
                        first = seg_job_cmds.get(key)
                        if first is not None:
                            clock.count("job_commands_serialised", 1)
                            if first != intent or intent == _JI_UPDATE_RETRIES:
                                flush()
                        seg_job_cmds[key] = intent
                pending_route[0] = rc
                pending.append(i)
                if pool_event:
                    pool_events.append((key, record))
            else:
                flush()  # earlier device rows execute BEFORE this record
                if self._device_keys_dirty:
                    self._pull_device_keys_into_host()
                    self._device_keys_dirty = False
                wf_peek = self._host.wf_keys.peek
                job_peek = self._host.job_keys.peek
                if (
                    vt == int(ValueType.WORKFLOW_INSTANCE)
                    and int(md.record_type) == int(RecordType.COMMAND)
                ):
                    # rare imperative ops demote their live device instance
                    # to the host oracle, which then runs the exact
                    # reference cascade (see _demote_instance)
                    if int(md.intent) == int(WI.CANCEL):
                        self._demote_instance(record.key)
                    elif int(md.intent) == int(WI.UPDATE_PAYLOAD):
                        self._demote_instance(
                            record.value.workflow_instance_key
                        )
                    elif int(md.intent) == int(WI.CREATE):
                        workflow = self._resolve_workflow(record.value)
                        if workflow is not None and self._unscalar_read(
                            record, self._reads.get(workflow.key, frozenset())
                        ):
                            clock.count("payload_host_routed", 1)
                elif (
                    vt in (
                        int(ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION),
                        int(ValueType.INCIDENT),
                    )
                    and int(md.record_type) == int(RecordType.COMMAND)
                    and self._unscalar_read(record, self._reads_any)
                ):
                    # a correlated message, or an incident resolved with a
                    # payload, brings a value with no column form under a
                    # variable a workflow reads: the instance goes to the
                    # host engine before the command applies there
                    if self._demote_instance(
                        record.value.workflow_instance_key
                    ):
                        clock.count("payload_host_routed", 1)
                elif (
                    vt == int(ValueType.JOB)
                    and int(md.record_type) == int(RecordType.COMMAND)
                    and self._unscalar_read(
                        record,
                        self._reads_of(record.value.headers.workflow_key),
                    )
                ):
                    # a job result with a value with no column form under a
                    # variable a workflow reads drags the owning instance
                    # to the host path before the command applies. Client
                    # commands may omit headers — resolve the owner from
                    # the device job table by job key then.
                    owner = record.value.headers.workflow_instance_key
                    if owner < 0 and record.key >= 0:
                        job_i64 = state_mod.host_i64(self.state.job_i64)
                        slots = np.nonzero(
                            job_i64[:, state_mod.JBL_KEY] == record.key
                        )[0]
                        if len(slots):
                            owner = int(
                                job_i64[int(slots[0]), state_mod.JBL_IKEY]
                            )
                    if self._demote_instance(owner):
                        clock.count("payload_host_routed", 1)
                deployed_before = len(self.repository.by_key)
                self.host_records_processed += 1
                wf_of = getattr(
                    getattr(record.value, "headers", record.value),
                    "workflow_key", -1,
                )
                kinds = self.host_records_by_kind
                kinds[(vt, wf_of)] = kinds.get((vt, wf_of), 0) + 1
                try:
                    per_record[i] = self._host.process(record)
                except Exception as e:  # noqa: BLE001 - poison isolation,
                    # same contract as the oracle's process_batch: skip and
                    # record, never wedge the drain loop
                    self._host.processing_failures.append(
                        (record.position, repr(e)[:300])
                    )
                if len(self.repository.by_key) != deployed_before:
                    self._recompile()
                    cut_vts = self._cut_value_types
                    scope_cuts = self._scope_cuts
                # key-sync check runs even for a poisoned record: a handler
                # may allocate keys before raising
                if (
                    self._host.wf_keys.peek != wf_peek
                    or self._host.job_keys.peek != job_peek
                ):
                    host_allocated[0] = True
        flush()
        push_host_keys()
        if positions:
            self.last_processed_position = positions[-1]
        wave.ending, self._ending = self._ending, []
        return wave

    def _lazy_device_row(self, entry, vt, rt, intent, key) -> bool:
        """True when a LAZY tail ref (``(batch, idx)``) can enter a device
        segment straight from its backing readback columns. Conservative:
        anything this cannot prove device-resident from columns alone
        materializes and takes the exact per-record path.

        Mirrors ``_routes_to_host`` for the EVENT cases it admits —
        device-born events hold no value without a column form under a
        variable their workflow reads, by induction, so the payload checks
        are vacuous for them."""
        if rt != int(RecordType.EVENT):
            return False
        ref = entry[0].device_ref(entry[1])
        if ref is None:
            return False
        src, j = ref
        _o, scols, epoch = src.device_source
        if epoch != self._meta_epoch or self.meta is None:
            # a redeploy recompiled the graph: workflow SLOTS in this
            # batch are stale — rebuild through the record path
            return False
        if vt == int(ValueType.JOB):
            if intent in (
                int(JI.FAILED), int(JI.RETRIES_UPDATED), int(JI.CANCELED)
            ):
                # host-side job-incident bookkeeping reads these records
                return False
        elif vt != int(ValueType.WORKFLOW_INSTANCE):
            return False
        wf_slot = scols["wf"][j]
        workflow = (
            self.meta.workflows[wf_slot]
            if 0 <= wf_slot < len(self.meta.workflows) else None
        )
        if workflow is not None and workflow.key in self._host_only_keys:
            return False
        instances = self._host.element_instances.instances
        if scols["instance_key"][j] in instances:
            return False
        if vt == int(ValueType.JOB):
            return key not in self._host.jobs
        return key not in instances

    # -- resident routing policy (sharded-state v2) ------------------------
    @property
    def _resident_mode(self) -> bool:
        return self.routing == "resident" and self._mesh is not None

    def _routing_active(self) -> bool:
        """Resident routing applies per wave: message-correlation graphs
        probe subscription tables across the whole keyspace, which the
        single-owner contract cannot cover — such partitions run every
        wave through the gathered fallback (still correct, still
        bit-identical; the routed win simply does not apply)."""
        return (
            self._resident_mode
            and self.graph is not None
            and not self.graph.has_messages
        )

    def _instance_key_of(self, entry, lazy: bool, vt: int):
        """The workflow_instance_key a device record belongs to — the
        residency-map key (the ROOT instance key, shared by every row of
        the instance's scope tree). None = not provable from the entry."""
        if lazy:
            ref = entry[0].device_ref(entry[1])
            if ref is None:
                return None
            src, j = ref
            _o, scols, _epoch = src.device_source
            return int(scols["instance_key"][j])
        value = getattr(entry, "value", None)
        if value is None:
            return None
        if vt == int(ValueType.JOB):
            headers = getattr(value, "headers", None)
            ik = getattr(headers, "workflow_instance_key", None)
        else:
            ik = getattr(value, "workflow_instance_key", None)
        return int(ik) if ik is not None else None

    def _wave_route_class(self, entry, lazy: bool, vt, rt, intent):
        """Route class of one device-eligible record: ``("create",)``
        (WI CREATE commands — the root key is the NEXT counter value, so
        the whole instance births in one predictable block),
        ``("ik", shard)`` (instance proven block-resident), ``("fb",)``
        (unknown residency → gathered fallback). None = routing inactive."""
        if not self._routing_active():
            return None
        if (
            vt == int(ValueType.WORKFLOW_INSTANCE)
            and rt == int(RecordType.COMMAND)
            and intent == int(WI.CREATE)
        ):
            return ("create",)
        ik = self._instance_key_of(entry, lazy, vt)
        if ik is None or ik < 0:
            return ("fb",)
        if self._blind_fb_inflight:
            # an uncollected fallback segment stepped rows whose instance
            # the host could not identify — possibly THIS one, and the
            # gathered kernel may have allocated its rows outside the
            # home block. Until that segment's emissions resolve the
            # keys, no residency entry is trustworthy. (CREATEs above
            # stay routable: their keys are freshly allocated.)
            return ("fb",)
        s = self._resident.get(int(ik))
        return ("ik", s) if s is not None else ("fb",)

    def _routed_lane_cap(self) -> int:
        """Max rows a routed wave may carry. Beyond the lane size, the
        binding constraint is the shard-local direct-mapped index window:
        rows born in one wave resolve through ei_index/job_index until the
        next rebuild (wave start), and the direct maps are collision-free
        only across a window of local-capacity consecutive keys — the
        same invariant `_keys_at_rebuild` maintains globally, here per
        wave with the v1 safety factor (4) because local capacity is
        1/D of the global one."""
        fanout = max(
            1, self.graph.emit_width if self.graph is not None else 1
        )
        window = (
            self._state.ei_index.shape[0] // self._state_shards
        ) // (4 * fanout)
        return max(1, min(self._routed_lane_slots, window))

    def _pop_residency_fallback(self, o, seq: int) -> None:
        """Retire residency for every instance a collected FALLBACK
        segment's emissions name: the gathered step allocates at GLOBAL
        free slots, so each touched instance may now own rows outside
        its home block. This is the collect-time complement of the
        dispatch-time pop — it covers the rows whose instance key the
        host could not prove (the kernel's emissions resolve them)."""
        valid = np.asarray(o.valid)
        ik = np.asarray(o.instance_key)
        for k in np.unique(ik[valid & (ik >= 0)]).tolist():
            self._resident.pop(int(k), None)
            self._residency_invalid[int(k)] = seq

    def _note_residency(self, o, owner: int, seq: int) -> None:
        """Learn residency from a collected ROUTED segment's emissions:
        every instance the wave touched has all its rows in ``owner``'s
        block (single-owner staging + local allocation), and instances
        whose root completed/terminated leave the map (their rows are
        freed; a later reuse of the key would be a different instance).

        ``seq`` is the segment's dispatch order: a key invalidated by a
        LATER-dispatched fallback (or a demotion) is skipped — this
        collect reflects older device state and must not reinstate an
        entry that newer knowledge already retired."""
        valid = np.asarray(o.valid)
        ik = np.asarray(o.instance_key)
        live = valid & (ik >= 0)
        inv = self._residency_invalid
        for k in np.unique(ik[live]).tolist():
            if inv.get(int(k), -1) >= seq:
                continue
            self._resident[int(k)] = owner
        vt = np.asarray(o.vtype)
        it = np.asarray(o.intent)
        key = np.asarray(o.key)
        done = (
            live
            & (vt == int(ValueType.WORKFLOW_INSTANCE))
            & (key == ik)
            & (
                (it == int(WI.ELEMENT_COMPLETED))
                | (it == int(WI.ELEMENT_TERMINATED))
            )
        )
        for k in np.unique(ik[done]).tolist():
            self._resident.pop(int(k), None)

    def collect_wave(self, wave: PendingWave) -> List[ProcessingResult]:
        """Materialize a dispatched wave: one bulk device fetch per
        segment, columnar emission decode, per-record source stamping.
        Returns per-record results in log order (a record with no output
        yields an empty result)."""
        from zeebe_tpu.protocol.records import stamp_source_positions

        if wave.collected is not None:  # collection is one-shot
            return wave.collected
        clock = wave.phases
        with clock.phase("decode"):
            self._assigning.difference_update(wave.assigning_stepped)
            for seg in wave.segments:
                self._collect_device(seg, clock)
                for i, res in zip(seg.rows, seg.results):
                    wave.per_record[i] = res
            self._park_unassigned(wave.pool_events, clock)
            self._end_owners(wave.ending)
            for name, n in self._values.take_counts().items():
                clock.count(name, n)
            results: List[ProcessingResult] = []
            for pos, res in zip(wave.positions, wave.per_record):
                if res is None:  # poisoned host record: contained, no output
                    res = ProcessingResult()
                stamp_source_positions(res.written, pos)
                results.append(res)
        # (host, device) seconds and phases of the last collected wave —
        # read by the in-process broker's wave metrics (the seconds are the
        # same attribute as the host oracle's)
        self.last_wave_seconds = (wave.host_seconds, wave.device_seconds)
        self.last_wave_phases = clock
        wave.collected = results
        return results

    def _end_owners(self, ending: List) -> None:
        """Free the held values of owners that ended with a collected
        wave (``_note_ending``, ``_demote_instance``)."""
        if ending:
            with self._values.timed():
                for owner in ending:
                    self._values.end(owner)

    def _park_unassigned(self, pool_events: List[tuple], clock) -> None:
        """The pool events a collected wave stepped, judged on the host
        (``__init__``, "WHO ASSIGNS A JOB"): the wave's emissions are in,
        so a job whose ACTIVATE they hold is in ``_assigning`` and one they
        ended is in ``_ended``; every other one with retries left was let
        pass for want of a credit and is the sweep's from now on."""
        ended = self._ended
        parked = self._parked
        if pool_events and parked is not None:
            assigning = self._assigning
            entered = 0
            now = self.clock()
            for key, entry in pool_events:
                parked.pop(key, None)  # judged anew
                if key in assigning or key in ended:
                    continue
                value = _as_record(entry).value
                if value.retries > 0:
                    parked[key] = (self.interns.intern(value.type), value, now)
                    entered += 1
            if entered:
                clock.count("backlog_parked", entered)
        if ended:
            ended.intersection_update(self._assigning)

    def _device_key_counters(self) -> Tuple[int, int]:
        """The device's next workflow and job keys (a blocking device→host
        read of two scalars)."""
        # .item() extracts the scalar for any size-1 array; plain int() on a
        # ndim>0 array is deprecated NumPy behavior that will hard-error
        return (
            int(np.asarray(self.state.next_wf_key).item()),
            int(np.asarray(self.state.next_job_key).item()),
        )

    def _note_lookup_rebuilt(self) -> None:
        """The lookup structures were just derived from the rows: the key
        window (see ``_stage_and_launch``) opens at the counters of now."""
        self._keys_at_rebuild = 0
        self._keys_rebuilt = self._device_key_counters()

    def _pull_device_keys_into_host(self) -> None:
        """Advance the embedded oracle's key generators past the device
        counters (one device→host scalar read; called only at
        device-segment → host-record boundaries)."""
        from zeebe_tpu.engine import keyspace

        dev_wf, dev_job = self._device_key_counters()
        if self._host.wf_keys.peek < dev_wf or self._host.job_keys.peek < dev_job:
            self._host.snapshot_mark_dirty(("h/control",))
        if self._host.wf_keys.peek < dev_wf:
            self._host.wf_keys.set_key(dev_wf - keyspace.STEP_SIZE)
        if self._host.job_keys.peek < dev_job:
            self._host.job_keys.set_key(dev_job - keyspace.STEP_SIZE)

    def _reads_of(self, workflow_key: int) -> frozenset:
        """The variables a device workflow reads; for a key the engine did
        not compile (a client's job command may carry no headers), those
        any device workflow reads."""
        reads = self._reads.get(workflow_key)
        return self._reads_any if reads is None else reads

    @staticmethod
    def _unscalar_read(record: Record, reads: frozenset) -> bool:
        """True when the record's payload holds, under one of ``reads``, a
        value with no column form (a map, a list, a number f32 does not
        hold exactly): such a value is never handed to the kernel where it
        compares or extracts."""
        if not reads:
            return False
        payload = getattr(record.value, "payload", None)
        if not payload:
            return False
        return any(
            name in payload and not rb.scalar_form(payload[name])
            for name in reads
        )

    # -- host record → batch row -------------------------------------------
    _TPU_BATCH = 512  # one canonical staged shape on TPU (= drain chunk)

    def _stage(
        self, records: List[Record], pad_to: int = 0, lane_owner=None
    ) -> rb.StagedBatch:
        n = len(records)
        # on TPU every batch pads to ONE canonical shape: invalid rows are
        # SIMD-masked and near-free, while each distinct pow2 bucket would
        # be its own cold compile of the step program (~25 s at 2^20 rows
        # with the v5e's compiler), serialized on the broker actor. CPU
        # (tests) keeps tight pow2 buckets — small batches there are
        # latency-bound.
        if lane_owner is not None:
            # routed lanes stage at ONE fixed shape ([D, lane_slots] per
            # column) — one compiled routed program regardless of fill
            pad_to = max(pad_to, self._routed_lane_slots)
        if jax.default_backend() == "tpu":
            pad_to = max(pad_to, self._TPU_BATCH)
        size = max(_pow2(n), pad_to)
        v = self.num_vars
        # the wave is filled where it ships from: the packed pair's two host
        # matrices, their defaults written with one broadcast each. A routed
        # wave (``lane_owner``, sharded-state v2) gets a leading [num_shards]
        # lane dim: the owner's lane takes the rows, every other lane
        # keeps the all-invalid defaults.
        lead = () if lane_owner is None else (self._state_shards,)
        row = _default_row(v)
        staged = rb.StagedBatch(
            i32=np.empty(lead + (size, row.i32.shape[-1]), np.int32),
            i8=np.empty(lead + (size, row.i8.shape[-1]), np.int8),
        )
        staged.i32[...] = row.i32
        staged.i8[...] = row.i8
        lane = (
            staged if lane_owner is None
            else jax.tree.map(lambda a: a[lane_owner], staged)
        )
        # the columns by name, as numpy VIEWS of the matrices (the same
        # slicing the step program does on the device; a 64-bit column is
        # an int64 view of its two planes): the row and column writers
        # below fill the matrices through them
        views = rb.column_views(lane)
        cols = {
            f.name: getattr(views, f.name) for f in dataclasses.fields(views)
        }
        # lazy emission refs (_lazy_device_row admitted them) copy the
        # device columns straight from their readback batch — payloads
        # skip the columns→payload→columns round trip — one index gather
        # per column and source batch; materialized Records (client
        # commands) fill row by row
        by_source: Dict[int, tuple] = {}
        for i, record in enumerate(records):
            if type(record) is tuple:
                src, j = record[0].device_ref(record[1])
                group = by_source.get(id(src))
                if group is None:
                    group = by_source[id(src)] = (src, [], [])
                group[1].append(i)
                group[2].append(j)
            else:
                self._stage_row(cols, i, record)
        for src, rows, js in by_source.values():
            self._stage_from_emission(cols, src, np.array(rows), np.array(js))
        if by_source:
            _count_staged_columnar(
                sum(len(rows) for _src, rows, _js in by_source.values())
            )
        if len(self._values):
            self._note_ending(cols)
        return self._pack_batch(staged, cols, lane_owner=lane_owner)

    def _note_ending(self, cols) -> None:
        """Owners of held values whose last record this segment steps: an
        instance's own ELEMENT_COMPLETED or ELEMENT_TERMINATED event, a
        message's DELETED event or its PUBLISHED event where it was not
        stored (no time to live). Every earlier record of theirs is before
        it in the log, so in this wave or an earlier one: their values are
        freed once this wave is collected."""
        from zeebe_tpu.protocol.intents import MessageIntent as MI

        ev = cols["valid"] & (cols["rtype"] == int(RecordType.EVENT))
        vt, intent, key = cols["vtype"], cols["intent"], cols["key"]
        done = (
            ev & (vt == int(ValueType.WORKFLOW_INSTANCE))
            & (key == cols["instance_key"]) & (key >= 0)
            & (
                (intent == int(WI.ELEMENT_COMPLETED))
                | (intent == int(WI.ELEMENT_TERMINATED))
            )
        )
        gone = (
            ev & (vt == int(ValueType.MESSAGE)) & (key >= 0)
            & (
                (intent == int(MI.DELETED))
                | ((intent == int(MI.PUBLISHED)) & (cols["deadline"] <= 0))
            )
        )
        self._ending.extend(key[done].tolist())
        self._ending.extend(("m", k) for k in key[gone].tolist())

    def _stage_from_emission(self, cols, src, rows, js) -> None:
        """Stage ``rows`` by COPYING rows ``js`` of the backing emission
        batch's columns (the kernel emitted them; re-deriving via a
        materialized Record is the identity — pinned by the lazy-vs-eager
        log bit-identity test). Only the columns ``_stage_row`` would set
        for each row's value type are copied; everything else keeps the
        staging defaults (``src``, ``resp``, ``push`` are per-staging
        flags, never carried over)."""
        o = src.device_source[0]
        cols["valid"][rows] = True
        for name in ("rtype", "vtype", "intent", "key", "req", "req_stream"):
            cols[name][rows] = o[name][js]
        vt = o["vtype"][js]
        is_wi = vt == int(ValueType.WORKFLOW_INSTANCE)
        is_job = vt == int(ValueType.JOB)
        for names, mask in (
            (("wf", "elem", "instance_key"), is_wi | is_job),
            (("scope_key",), is_wi),
            (("type_id", "retries", "deadline", "worker", "aux_key"), is_job),
        ):
            r, j = rows[mask], js[mask]
            for name in names:
                cols[name][r] = o[name][j]
        # an element index means something only under a workflow slot
        cols["elem"][rows[o["wf"][js] < 0]] = -1
        # payload columns copy MASKED by the type column: zeros where no
        # variable is set — exactly what payload_to_columns(
        # columns_to_payload(...)) would produce (unset lanes must not
        # carry junk)
        v_vt = o["v_vt"][js]
        cols["v_vt"][rows] = v_vt
        cols["v_num"][rows] = np.where(v_vt != 0, o["v_num"][js], 0)
        cols["v_str"][rows] = np.where(v_vt != 0, o["v_str"][js], 0)

    def _pack_batch(
        self, staged: rb.StagedBatch, cols: Dict[str, np.ndarray],
        lane_owner=None,
    ) -> rb.StagedBatch:
        """The filled host matrices → the wave on the device: the packed
        pair's two arrays and nothing else — the step program takes the
        column views itself (``rb.column_views``), so no device op runs
        between the fill and the launch.

        A routed wave (``lane_owner``) is put lane-sharded over the mesh
        axis, so each device receives ONLY its own lane while the
        transfer count stays two."""
        # sharded-state routing accounting: record the staged row split
        # (residency basis: instance_key in resident mode, advisory key
        # hash otherwise) and the valid count — _run_step observes them
        # together with the wave's ACTUAL exchange volume, so idle waves
        # that dispatch zero records no longer inflate the exchange
        # counter (they still count as sharded waves).
        if self._mesh is not None:
            from zeebe_tpu.tpu import shard as shard_mod

            basis = (
                cols["instance_key"] if self._resident_mode else cols["key"]
            )
            self._last_stage_split = shard_mod.shard_row_counts_host(
                basis, cols["valid"], self._state_shards
            )
            self._last_stage_valid = int(np.count_nonzero(cols["valid"]))
        # the matrices commit to THIS engine's mesh device (placement is
        # what routes the step program to it); sharded mode replicates
        # them over the span via _place-style NamedSharding (lane-sharded
        # in routed staging); default device otherwise
        target = self.device
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from zeebe_tpu.tpu import shard as shard_mod

            target = NamedSharding(
                self._mesh,
                PartitionSpec() if lane_owner is None
                else PartitionSpec(shard_mod.STATE_AXIS),
            )
        return self._put_staged(staged, target)

    def _put_staged(self, staged: rb.StagedBatch, target) -> rb.StagedBatch:
        """The wave's host->device transfers: the packed pair's two arrays
        in one ``device_put``, as phase ``h2d`` of the wave being
        dispatched."""
        clock = self._clock
        with clock.phase("h2d"):
            placed = jax.device_put(staged, target)
        arrays = jax.tree_util.tree_leaves(staged)
        clock.count("h2d_bytes", sum(a.nbytes for a in arrays))
        clock.count("h2d_transfers", len(arrays))
        return placed

    def warm(self, sizes=(512,)) -> None:
        """Pre-compile the step program for the hot batch shapes BEFORE the
        partition serves: a cold kernel compile on the first drained batch
        otherwise blocks the broker actor for the whole compile (~25 s at
        2^20 rows), and every client request meanwhile waits or times
        out. Compiles nothing while no workflow is deployed — there is no
        graph to step — so a broker on a fresh data directory still pays
        the compile on its first instance; after a restart the replayed
        deployments come after this call too. The credit flush needs no
        graph and compiles here in any case, on a delta of zeros placed
        as a return's is, so that the first worker's first return finds
        it compiled (no flush is counted for it)."""
        self._flush_credits()
        self._add_credits(np.zeros(self._state.sub_credits.shape, np.int32))
        if self.graph is None:
            self._recompile()
        if self.graph is None:
            return
        now = np.int64(self.clock())
        for n in sizes:
            batch = self._stage([], pad_to=n)
            # zero valid rows: a semantic no-op step that only compiles
            _out, _stats = self._run_step(batch, now)
        if self._resident_mode:
            # resident mode serves through TWO programs: the fallback just
            # warmed above (it takes the same flat batch shapes); warm the
            # routed program at its one laned shape too
            batch = self._stage([], lane_owner=0)
            _out, _stats = self._run_step(batch, now, lane_owner=0)
        jax.block_until_ready(self.state.ei_i32)

    def _run_step(self, batch: rb.StagedBatch, now, lane_owner=None) -> tuple:
        """Launch ONE wave through the active step program — routed or
        fallback in resident mode (``lane_owner`` picks; the choice is
        host-side so the routed lowering never contains the fallback's
        gather), the v1 gathered program in sharded mode, kernel.step_jit
        otherwise — rebinding ``self.state`` and returning ``(out,
        stats)``. All programs are bit-identical by construction, so
        callers never branch on the mode."""
        # ``now`` and the partition id ride in the launch as numpy scalars
        # (an eager ``jnp.asarray`` would be a device dispatch of its own)
        pid = np.int32(self.partition_id)
        # the pool reads the credit column: what the workers returned since
        # the last reader goes in first, as a phase of its own
        self._flush_credits()
        with self._clock.phase("launch"):
            if self._resident_mode:
                program = (
                    self._state_step_routed
                    if lane_owner is not None
                    else self._state_step_fallback
                )
                self._state, out, stats = program(
                    self.graph, self._state, batch, now, pid
                )
            elif self._state_step is not None:
                self._state, out, stats = self._state_step(
                    self.graph, self._state, batch, now, pid
                )
            else:
                self._state, out, stats = kernel.step_jit(
                    self.graph, self._state, batch, now, partition_id=pid
                )
        if self._mesh is not None:
            from zeebe_tpu.runtime import metrics as metrics_mod
            from zeebe_tpu.tpu import shard as shard_mod

            n_valid = self._last_stage_valid
            # exchange model per wave KIND — and zero when the wave
            # dispatched zero records: an idle/warm step moves no table
            # or boundary data worth accounting (satellite fix; the
            # gathered program still lowers its gathers, but capacity
            # planning reads demand, not compilation artifacts)
            if not n_valid:
                xb = 0
            elif self._resident_mode and lane_owner is not None:
                xb = shard_mod.routed_exchange_bytes(out, self._state_shards)
            elif self._resident_mode:
                xb = self._fallback_exchange_bytes
            else:
                xb = self._shard_exchange_bytes
            split = self._last_stage_split
            single_lane = self._resident_mode and lane_owner is not None
            if single_lane:
                split = np.zeros(self._state_shards, np.int64)
                split[int(lane_owner)] = n_valid
            metrics_mod.observe_sharded_wave(
                split, xb, single_lane=single_lane
            )
            self.sharded_waves += 1
            self.last_shard_fill = tuple(int(x) for x in split)
            if self._resident_mode and n_valid:
                if lane_owner is not None:
                    self.routed_waves += 1
                else:
                    self.fallback_waves += 1
        return out, stats

    def _stage_row(self, cols, i, record: Record) -> None:
        md = record.metadata
        vt = int(md.value_type)
        cols["valid"][i] = True
        cols["rtype"][i] = int(md.record_type)
        cols["vtype"][i] = vt
        cols["intent"][i] = int(md.intent)
        cols["key"][i] = record.key
        cols["req"][i] = md.request_id
        cols["req_stream"][i] = md.request_stream_id
        value = record.value
        if vt == int(ValueType.WORKFLOW_INSTANCE):
            wf_slot = self.meta.slot(value.workflow_key)
            if (
                int(md.record_type) == int(RecordType.COMMAND)
                and int(md.intent) == int(WI.CREATE)
            ):
                workflow = self._resolve_workflow(value)
                wf_slot = self.meta.slot(workflow.key) if workflow else -1
            cols["wf"][i] = wf_slot
            if wf_slot >= 0 and value.activity_id:
                cols["elem"][i] = self.meta.elem_idx[wf_slot].get(
                    value.activity_id, -1
                )
            cols["instance_key"][i] = value.workflow_instance_key
            cols["scope_key"][i] = value.scope_instance_key
            self._stage_payload(cols, i, value.payload)
        elif vt == int(ValueType.JOB):
            cols["type_id"][i] = self.interns.intern(value.type) if value.type else 0
            cols["retries"][i] = value.retries
            cols["deadline"][i] = value.deadline
            cols["worker"][i] = (
                self.interns.intern(value.worker) if value.worker else 0
            )
            headers = value.headers
            cols["aux_key"][i] = headers.activity_instance_key
            cols["instance_key"][i] = headers.workflow_instance_key
            wf_slot = self.meta.slot(headers.workflow_key)
            cols["wf"][i] = wf_slot
            if wf_slot >= 0 and headers.activity_id:
                cols["elem"][i] = self.meta.elem_idx[wf_slot].get(
                    headers.activity_id, -1
                )
            self._stage_payload(cols, i, value.payload)
        elif vt == int(ValueType.TIMER):
            cols["aux_key"][i] = value.activity_instance_key
            cols["instance_key"][i] = value.workflow_instance_key
            cols["deadline"][i] = value.due_date
            # the handler element (a boundary event or the catch element
            # itself) re-resolves from the owning instance's workflow —
            # TimerRecord carries no workflow reference
            if value.handler_element_id and self.meta is not None:
                wf_slot = self._wf_slot_of_instance(
                    value.activity_instance_key
                )
                if wf_slot >= 0:
                    cols["wf"][i] = wf_slot
                    cols["elem"][i] = self.meta.elem_idx[wf_slot].get(
                        value.handler_element_id, -1
                    )
        elif vt == int(ValueType.MESSAGE):
            self._stage_corr(cols, i, value.name, value.correlation_key)
            cols["deadline"][i] = value.time_to_live
            cols["aux2_key"][i] = (
                self.interns.intern(value.message_id) if value.message_id else 0
            )
            self._stage_payload(cols, i, value.payload)
        elif vt == int(ValueType.MESSAGE_SUBSCRIPTION):
            self._stage_corr(cols, i, value.message_name, value.correlation_key)
            cols["wf"][i] = value.workflow_instance_partition_id
            cols["instance_key"][i] = value.workflow_instance_key
            cols["aux_key"][i] = value.activity_instance_key
        elif vt == int(ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION):
            self._stage_corr(
                cols, i, value.message_name, value.correlation_key
            )
            cols["wf"][i] = value.message_partition_id
            cols["aux2_key"][i] = value.message_partition_id
            cols["instance_key"][i] = value.workflow_instance_key
            cols["aux_key"][i] = value.activity_instance_key
            self._stage_payload(cols, i, value.payload)

    def _wf_slot_of_instance(self, key: int) -> int:
        """Workflow slot of a live device element instance (host-side scan;
        timer creates are rare control records)."""
        if key < 0:
            return -1
        keys = state_mod.host_i64(self.state.ei_i64, state_mod.EIL_KEY)
        hits = np.nonzero(keys == key)[0]
        if not len(hits):
            return -1
        return int(np.asarray(self.state.ei_i32)[int(hits[0]), state_mod.EI_WF])

    def _stage_corr(self, cols, i, name: str, correlation_key) -> None:
        """Message-family correlation columns: type_id = interned name,
        retries = correlation value type, worker = correlation bits."""
        from zeebe_tpu.tpu.conditions import VT_NUM, VT_STR

        cols["type_id"][i] = self.interns.intern(name) if name else 0
        if isinstance(correlation_key, str) and correlation_key:
            cols["retries"][i] = int(VT_STR)
            cols["worker"][i] = self.interns.intern(correlation_key)
        elif isinstance(correlation_key, (int, float)):
            cols["retries"][i] = int(VT_NUM)
            cols["worker"][i] = int(
                np.float32(float(correlation_key)).view(np.int32)
            )

    def _stage_payload(self, cols, i, payload) -> None:
        if not payload:
            return
        rejection = int(cols["rtype"][i]) == int(RecordType.COMMAND_REJECTION)
        self._staging_row = (cols, i)
        try:
            # a rejection is terminal for the kernel: a value with no
            # column form is not held for it (it stages with an empty
            # payload: PayloadError below)
            vt, num, sid = rb.payload_to_columns(
                payload, self._var_column, self.interns, self.num_vars,
                None if rejection else self._hold_staged,
            )
        except rb.PayloadError:
            if rejection:
                return
            raise
        cols["v_vt"][i] = vt
        cols["v_num"][i] = num
        cols["v_str"][i] = sid

    def _hold_staged(self, value) -> int:
        """A value with no column form of the row being staged
        (``_staging_row``), held for the segment (``_staged_held``). An
        event's values belong to its instance (a message's own, to the
        message) from the start; a command's wait for the rows its step
        emits (``_own_held``)."""
        handle = self._values.handle_of(value)
        if handle is not None:
            # a record the engine built from its handles: the value is
            # held, and owned, already
            return handle
        cols, i = self._staging_row
        owner = None
        if int(cols["rtype"][i]) == int(RecordType.EVENT):
            if int(cols["vtype"][i]) == int(ValueType.MESSAGE):
                if cols["key"][i] >= 0:
                    owner = ("m", int(cols["key"][i]))
            elif cols["instance_key"][i] >= 0:
                owner = int(cols["instance_key"][i])
        with self._values.timed():
            handle = self._values.hold(value)
        self._staged_held.append((handle, owner))
        return handle

    def _resolve_workflow(self, value: WorkflowInstanceRecord):
        if value.workflow_key > 0:
            return self.repository.by_key.get(value.workflow_key)
        if value.version > 0:
            return self.repository.by_id_and_version(
                value.bpmn_process_id, value.version
            )
        return self.repository.latest(value.bpmn_process_id)

    # -- device round -------------------------------------------------------
    def _dispatch_device(
        self, records: List, positions: List[int],
        metas: "Optional[List[tuple]]" = None,
        route=None,
    ) -> _PendingSegment:
        """One device segment of the wave being dispatched, as its phase
        ``stage`` (the transfers and the launch inside it are phases of
        their own: ``_put_staged``, ``_run_step``)."""
        with self._clock.phase("stage"):
            return self._stage_and_launch(records, positions, metas, route)

    def _stage_and_launch(
        self, records: List, positions: List[int],
        metas: "Optional[List[tuple]]", route,
    ) -> _PendingSegment:
        """Host pre-work + staging + kernel launch for one device segment;
        returns the pending segment WITHOUT synchronizing on the device
        (overflow check and emission fetch happen in ``_collect_device``).

        ``records`` entries may be lazy ``(batch, idx)`` refs (admitted by
        ``_lazy_device_row``); ``metas`` carries each entry's
        ``(value_type, record_type, intent)`` so the host-side scans below
        never materialize a row just to filter on it."""
        if metas is None:
            metas = []
            for record in records:
                md = record.metadata
                metas.append(
                    (int(md.value_type), int(md.record_type), int(md.intent))
                )
        results = [ProcessingResult() for _ in records]
        # Job-incident bookkeeping lives in the host engine (incident records
        # are host-processed); run the oracle's _incident_on_job_event for
        # the corresponding JOB events flowing through the device. For
        # FAILED-with-no-retries the HOST emits the follow-up — either the
        # incident CREATE (stamped with the failure event's position) or,
        # when the failure event was re-written by an incident RESOLVE
        # (metadata.incident_key set), the RESOLVE_FAILED event. The
        # kernel's own unconditional incident-CREATE emission for these
        # rows is suppressed below (it cannot see the incident_key).
        # (Lazy refs never match: _lazy_device_row excludes these intents.)
        suppress_incident_create: set = set()
        for i, (vt, rt, intent) in enumerate(metas):
            if vt != int(ValueType.JOB) or rt != int(RecordType.EVENT):
                continue
            if intent == int(JI.FAILED):
                record = _as_record(records[i])
                if record.value.retries <= 0:
                    # mutates the oracle's incident maps outside
                    # host.process
                    self._host.snapshot_mark_dirty(
                        ("h/incidents", "h/control")
                    )
                    self._host._incident_on_job_event(record, results[i])
                    suppress_incident_create.add(i)
            elif intent in (int(JI.RETRIES_UPDATED), int(JI.CANCELED)):
                record = _as_record(records[i])
                self._host.snapshot_mark_dirty(("h/incidents", "h/control"))
                self._host._incident_on_job_event(record, results[i])
        # CREATE commands with unknown workflows are rejected host-side,
        # mirroring CreateWorkflowInstanceEventProcessor's rejection
        rejected = set()
        for i, (vt, rt, intent) in enumerate(metas):
            if (
                vt == int(ValueType.WORKFLOW_INSTANCE)
                and rt == int(RecordType.COMMAND)
                and intent == int(WI.CREATE)
            ):
                record = _as_record(records[i])
                if self._resolve_workflow(record.value) is None:
                    md = record.metadata
                    value = record.value.copy()
                    value.workflow_instance_key = self._next_wf_key_host()
                    rejection = Record(
                        key=record.key,
                        source_record_position=record.position,
                        metadata=RecordMetadata(
                            record_type=RecordType.COMMAND_REJECTION,
                            value_type=ValueType.WORKFLOW_INSTANCE,
                            intent=int(WI.CREATE),
                            rejection_type=RejectionType.BAD_VALUE,
                            rejection_reason="Workflow is not deployed",
                            request_id=md.request_id,
                            request_stream_id=md.request_stream_id,
                        ),
                        value=value,
                    )
                    results[i].written.append(rejection)
                    results[i].responses.append(rejection)
                    rejected.add(i)

        seg = _PendingSegment(
            results=results,
            positions=positions,
            live=[i for i in range(len(records)) if i not in rejected],
            suppress=suppress_incident_create,
        )
        live = seg.live
        if not live:
            return seg
        seg.seq = self._dispatch_seq
        self._dispatch_seq += 1
        lane_owner = None
        if self._routing_active():
            if route is not None and route[0] == "ik":
                lane_owner = route[1]
            elif route is not None and route[0] == "create":
                # all-CREATE segment: the first live CREATE allocates the
                # NEXT counter value as its root key (the rejection scan
                # above already advanced the counter for rejected rows),
                # and every follow-on allocation of the segment lands in
                # the same owner's block. One blocking scalar read — the
                # cost of making CREATE waves routable without a mirror
                # of the kernel's allocation arithmetic.
                from zeebe_tpu.tpu import shard as shard_mod

                key0 = int(np.asarray(self.state.next_wf_key))
                lane_owner = int(
                    shard_mod.shard_of_key_host(key0, self._state_shards)
                )
            if lane_owner is not None and len(live) > self._routed_lane_cap():
                lane_owner = None
                self.routed_overflows += 1
            if lane_owner is None:
                # gathered fallback allocates follow-up rows at GLOBAL
                # free slots — the instances it steps can no longer be
                # proven block-resident. Host-provable keys pop at
                # dispatch so later segments never route on them; rows
                # whose key the host CANNOT prove (e.g. client job
                # commands with default headers — exactly what forced
                # the fallback) resolve at collect, when the kernel's
                # emissions name them (seg.fb_pop), and routing holds
                # off until then (seg.blind). CREATE rows are exempt
                # from blindness: their keys are freshly allocated, so
                # no pre-existing residency entry can go stale.
                seg.fb_pop = True
                for i in live:
                    vt_i, rt_i, it_i = metas[i]
                    ik = self._instance_key_of(
                        records[i], type(records[i]) is tuple, vt_i
                    )
                    if ik is not None and ik >= 0:
                        self._resident.pop(int(ik), None)
                        self._residency_invalid[int(ik)] = seg.seq
                    elif not (
                        vt_i == int(ValueType.WORKFLOW_INSTANCE)
                        and rt_i == int(RecordType.COMMAND)
                        and it_i == int(WI.CREATE)
                    ):
                        seg.blind = True
                if seg.blind:
                    self._blind_fb_inflight += 1
        seg.route_owner = lane_owner
        self._staged_held = seg.held
        try:
            batch = self._stage(
                [records[i] for i in live], lane_owner=lane_owner
            )
        finally:
            self._staged_held = []
        if len(self._values):
            rt_command = int(RecordType.COMMAND)
            seg.src_commands = np.fromiter(
                (metas[i][1] == rt_command for i in live), bool, len(live)
            )
        now = np.int64(self.clock())
        # re-derive the fallback maps before the key window can wrap past
        # the direct-mapped index capacity (see rebuild_lookup_state).
        # Wave by wave the advance is a conservative host-side bound — one
        # record can allocate up to emit_width keys (parallel split /
        # multi-instance fan-out), each advancing the counter by the
        # stride (5) — so the serving path pays no device sync; where the
        # bound crosses the window, the device's own counters say what was
        # really allocated (one blocking read of two scalars in some
        # hundreds of waves), and the rebuild — whole-column passes, and
        # their compiles the first time — runs only when that is due.
        # Resident mode skips the cadence entirely: BOTH its step programs
        # rebuild the lookup structures in-program every wave, so no
        # at-rest window can go stale.
        if not self._resident_mode:
            fanout = max(
                1, self.graph.emit_width if self.graph is not None else 1
            )
            window = self._state.ei_index.shape[0] // 4
            this_wave = 5 * fanout * len(live)
            self._keys_at_rebuild += this_wave
            if self._keys_at_rebuild > window:
                self._keys_at_rebuild = this_wave + max(
                    now_key - then_key for now_key, then_key
                    in zip(self._device_key_counters(), self._keys_rebuilt)
                )
                if self._keys_at_rebuild > window:
                    self._state = state_mod.rebuild_lookup_state(self.state)
                    self._note_lookup_rebuilt()
        self._mark_device_dirty()  # a kernel step may write any table
        out, stats = self._run_step(batch, now, lane_owner=lane_owner)
        seg.out = out
        seg.stats = stats
        return seg

    def _collect_device(self, seg: _PendingSegment, clock) -> None:
        """Synchronize on one dispatched segment: wait for its step (phase
        ``blocked``: host time waiting for the device, no transfer), then
        ONE device→host fetch of the emission's packed pair and the stats
        vector (phase ``readback``), the overflow check on the fetched
        vector, and the columnar decode of the pair's host column views
        into the segment's per-record results, in the caller's phase."""
        if seg.out is None:
            return
        with clock.phase("blocked"):
            jax.block_until_ready(seg.stats)
        with clock.phase("readback"):
            fetched = jax.device_get((seg.out, seg.stats))
        arrays = jax.tree_util.tree_leaves(fetched)
        clock.count("d2h_bytes", sum(a.nbytes for a in arrays))
        clock.count("d2h_transfers", len(arrays))
        packed, stats = fetched
        if kernel.stats_of(stats)["overflow"]:
            raise RuntimeError(
                "device table overflow — raise TpuPartitionEngine capacity"
            )
        if len(stats) > len(kernel.STATS):
            for name, n in zip(kernel.MESSAGE_STATS, stats[len(kernel.STATS):]):
                clock.count(name, int(n))
        o = rb.column_views(packed)
        # collection is one-shot: clear the device refs BEFORE decoding so
        # a re-collect of this wave (the drain's finally path after an
        # exception elsewhere) can never append duplicate emissions into
        # seg.results
        seg.out = None
        seg.stats = None
        if seg.route_owner is not None:
            self._note_residency(o, seg.route_owner, seg.seq)
        elif seg.fb_pop:
            self._pop_residency_fallback(o, seg.seq)
            if seg.blind:
                self._blind_fb_inflight -= 1
        if self._residency_invalid:
            # collects run in dispatch order: an invalidation at/before
            # this seq can no longer suppress any future note
            self._residency_invalid = {
                k: s
                for k, s in self._residency_invalid.items()
                if s > seg.seq
            }
        self._emit_records(
            o, [seg.positions[i] for i in seg.live], seg.results, seg.live,
            seg.suppress, seg.src_commands,
        )
        if seg.held:
            self._own_held(seg.held)

    def _own_held(self, held: List[tuple]) -> None:
        """The values a decoded segment's staging held anew: an event's go
        to its owner; a command's that no emitted row took (the command
        was rejected) are freed."""
        values = self._values
        with values.timed():
            for handle, owner in held:
                if owner is not None:
                    values.own(owner, handle)
            values.release_unowned([handle for handle, _owner in held])

    def _held_values(self, o, count: int, src_commands):
        """handle -> value for the held values one decoded emission
        carries (None where it carries none), each handle a COMMAND brought
        added to the set of the row's owner: its instance, or the message
        of a message's own row. (The rows an event emits carry its own
        instance's values, owned already: an event's fresh values are owned
        where it is staged.) A rejection's rows own nothing (its values go
        with the command), nor do the subscription commands sent to an
        instance's partition (that partition holds them anew). The dict is
        what the batch's rows read, however late they are built."""
        if src_commands is None:
            return None
        held = o["v_vt"][:count] == VT_HELD
        if not held.any():
            return None
        values = self._values
        with values.timed():
            rows, cols = np.nonzero(held)
            handles = o["v_str"][:count][rows, cols]
            resolved = values.resolve(np.unique(handles).tolist())
            vt = o["vtype"][:count][rows]
            src = o["src"][:count][rows]
            inside = (src >= 0) & (src < len(src_commands))
            keep = (
                (~inside | src_commands[np.where(inside, src, 0)])
                & (o["rtype"][:count][rows] != int(RecordType.COMMAND_REJECTION))
                & (vt != int(ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION))
            )
            msg = keep & (vt == int(ValueType.MESSAGE))
            owner = np.where(msg, o["key"][:count][rows], o["instance_key"][:count][rows])
            keep &= owner >= 0
            for handle, key, is_msg in set(zip(
                handles[keep].tolist(), owner[keep].tolist(), msg[keep].tolist()
            )):
                values.own(("m", key) if is_msg else key, handle)
        return resolved

    def _next_wf_key_host(self) -> int:
        """Allocate a workflow key host-side, keeping the device counter in
        sync (rejections consume a key in the oracle too)."""
        key = int(np.asarray(self.state.next_wf_key))
        self._mark_device_dirty("keys")
        self._state = dataclasses.replace(
            self.state,
            next_wf_key=self.state.next_wf_key + 5,
        )
        return key

    # -- emission → host records -------------------------------------------
    def _emit_records(
        self,
        out: RecordBatch,
        src_positions: List[int],
        results: List[ProcessingResult],
        live_rows: List[int],
        suppress_incident_create: "set | None" = None,
        src_commands: Optional[np.ndarray] = None,
    ) -> None:
        """Decode one emission batch (``out``: the host column views of the
        caller's single bulk ``device_get``) into Record objects. Columnar:
        scalar columns convert to Python lists ONCE (`.tolist()`); rows
        materialize lazily from those lists only up to the valid count."""
        from zeebe_tpu.protocol.intents import (
            IncidentIntent,
            MessageSubscriptionIntent as MS,
            WorkflowInstanceSubscriptionIntent as WS,
        )

        from zeebe_tpu.protocol.columnar import ColumnarBatch

        o = {f.name: np.asarray(getattr(out, f.name)) for f in dataclasses.fields(out)}
        count = int(o["valid"].sum())
        if not count:
            return
        values = self._held_values(o, count, src_commands)
        # per-row int(np_scalar) dominated readback CPU at serving wave
        # sizes; one C-level tolist per column replaces them all
        cols = {
            k: v[:count].tolist() for k, v in o.items() if v.ndim == 1
        }
        # bind THIS compile's meta into the lazy closures: a later
        # redeploy replaces self.meta, but the slots in these columns
        # index the graph that emitted them
        meta = self.meta
        names = meta.varspace.names
        srcs = cols["src"]
        sources = [
            src_positions[s] if 0 <= s < len(src_positions) else -1
            for s in srcs
        ]
        producer_d, incident_d, rejtype_d = _frame_defaults()
        # the readback decodes into a COLUMNAR batch carrying the FULL
        # frame-column set plus a value-only builder: plain follow-up rows
        # flow to LogStream.append as lazy refs and encode from columns +
        # built values — no Record/metadata objects on the append edge —
        # and later re-STAGE from these very columns (_stage_from_
        # emission). Only rows that need objects now (sends, responses,
        # pushes, rejections, incident fixups) materialize here.
        emission = ColumnarBatch(
            count,
            {
                "key": cols["key"],
                "record_type": cols["rtype"],
                "value_type": cols["vtype"],
                "intent": cols["intent"],
                "request_id": cols["req"],
                "request_stream_id": cols["req_stream"],
                "source_record_position": sources,
                "producer_id": [producer_d] * count,
                "incident_key": [incident_d] * count,
                "rejection_type": [rejtype_d] * count,
                "rejection_reason": [""] * count,
                "raft_term": [0] * count,
            },
            materializer=lambda r: self._materialize(
                o, cols, r, names, sources, meta, values
            ),
            value_builder=lambda r: self._materialize_value(
                o, cols, r, names, meta, values
            ),
        )
        emission.device_source = (o, cols, self._meta_epoch)
        lazy_ok = self.lazy_emissions
        rt_cmd = int(RecordType.COMMAND)
        rt_rej = int(RecordType.COMMAND_REJECTION)
        rt_event = int(RecordType.EVENT)
        vt_job = int(ValueType.JOB)
        for r in range(count):
            src = srcs[r]
            res = results[live_rows[src]] if 0 <= src < len(live_rows) else results[0]
            vt = cols["vtype"][r]
            rt = cols["rtype"][r]
            intent = cols["intent"][r]
            if vt == vt_job:
                if rt == rt_event and intent in _JOB_LEFT_EVENTS:
                    # activated, or gone from the table: not parked
                    self._job_left(cols["key"][r], intent != _JI_ACTIVATED)
                elif (rt == rt_cmd and intent == _JI_ACTIVATE) or (
                    rt == rt_event and intent in _JOB_POOL_EVENTS
                    and cols["retries"][r] > 0
                ):
                    # the pool's assignment, or the event it will judge, is
                    # on its way: not the sweep's until it was stepped
                    # (__init__)
                    self._assigning.add(cols["key"][r])
            # cross-partition subscription commands are SENDS, not appended
            # records — exactly the oracle's out.sends channel
            # (SubscriptionCommandSender.java:96-108)
            if rt == rt_cmd and vt == int(
                ValueType.MESSAGE_SUBSCRIPTION
            ) and intent in (int(MS.OPEN), int(MS.CLOSE)):
                record = emission.row(r)
                target = self.partition_for_correlation_key(
                    record.value.correlation_key
                )
                record.source_record_position = -1  # sends are unstamped
                res.sends.append((target, record))
                continue
            if rt == rt_cmd and vt == int(
                ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION
            ) and intent == int(WS.CORRELATE):
                record = emission.row(r)
                record.source_record_position = -1
                res.sends.append((cols["wf"][r], record))
                continue
            if (
                rt == rt_cmd
                and vt == int(ValueType.INCIDENT)
                and intent == int(IncidentIntent.CREATE)
            ):
                if (
                    suppress_incident_create
                    and 0 <= src < len(live_rows)
                    and live_rows[src] in suppress_incident_create
                ):
                    # job incidents are host-emitted (see _process_device:
                    # the host branches on metadata.incident_key, which
                    # the kernel cannot see) — drop the kernel's copy
                    continue
                record = emission.row(r)
                if (
                    record.value is not None
                    and record.value.failure_event_position < 0
                ):
                    # the oracle stamps the failing event's position into
                    # the CREATE command (it re-reads that record on
                    # RESOLVE and compaction pins it); the kernel only
                    # ships an error code, but the failing event IS this
                    # emission's source record
                    record.value.failure_event_position = (
                        record.source_record_position
                    )
                res.written.append(record)
                if cols["resp"][r] and cols["req"][r] >= 0:
                    res.responses.append(record)
                if cols["push"][r]:
                    res.pushes.append((cols["req_stream"][r], record))
                continue
            resp = cols["resp"][r] and cols["req"][r] >= 0
            push = cols["push"][r]
            if lazy_ok and not resp and not push and rt != rt_rej:
                # plain append: the row stays COLUMNS all the way into
                # the log tail (a (batch, idx) ref) — materialized only
                # if something later reads it as an object
                res.written.append((emission, r))
                continue
            record = emission.row(r)
            res.written.append(record)
            if resp:
                res.responses.append(record)
            if push:
                res.pushes.append((cols["req_stream"][r], record))

    def _materialize(self, o, cols, r, names, sources, meta, values) -> Record:
        """One emission row → Record. ``cols`` holds the scalar columns as
        Python lists (see _emit_records); ``o`` the 2D payload matrices;
        ``meta`` is the graph meta bound AT EMIT (slots in these columns
        index it, not whatever self.meta later becomes), ``values`` the
        held values the batch carries (``_held_values``)."""
        vt = cols["vtype"][r]
        rt = cols["rtype"][r]
        rej = cols["rej"][r]
        value = self._materialize_value(o, cols, r, names, meta, values)

        md = RecordMetadata(
            record_type=RecordType(rt),
            value_type=ValueType(vt),
            intent=cols["intent"][r],
            request_id=cols["req"][r],
            request_stream_id=cols["req_stream"][r],
        )
        if rt == int(RecordType.COMMAND_REJECTION):
            md.rejection_type = (
                RejectionType.BAD_VALUE
                if rej == rb.REJ_RETRIES_NOT_POSITIVE
                else RejectionType.NOT_APPLICABLE
            )
            md.rejection_reason = rb.REJECTION_REASONS.get(rej, "")
            if vt == int(ValueType.MESSAGE) and rej == rb.REJ_MSG_DUP:
                md.rejection_type = RejectionType.BAD_VALUE
                md.rejection_reason = (
                    f"message with id '{value.message_id}' is already "
                    "published"
                )
        record = Record(key=cols["key"][r], metadata=md, value=value)
        record.source_record_position = sources[r]
        return record

    def _materialize_value(self, o, cols, r, names, meta, values):
        """One emission row → its typed ``RecordValue`` only (no
        Record/metadata wrapper) — the append-edge encode path for lazy
        rows builds exactly this and nothing more."""
        vt = cols["vtype"][r]
        rej = cols["rej"][r]
        wf_slot = cols["wf"][r]
        elem = cols["elem"][r]
        payload = rb.columns_to_payload(
            o["v_vt"][r], o["v_num"][r], o["v_str"][r], names, self.interns,
            values,
        )
        workflow = (
            meta.workflows[wf_slot]
            if 0 <= wf_slot < len(meta.workflows)
            else None
        )
        elem_id = meta.element_id(wf_slot, elem)
        element = (
            workflow.elements[elem] if workflow and 0 <= elem < len(workflow.elements)
            else None
        )

        if vt == int(ValueType.WORKFLOW_INSTANCE):
            value = WorkflowInstanceRecord(
                bpmn_process_id=workflow.id if workflow else "",
                version=workflow.version if workflow else -1,
                workflow_key=workflow.key if workflow else -1,
                workflow_instance_key=cols["instance_key"][r],
                activity_id=elem_id,
                payload=payload,
                scope_instance_key=cols["scope_key"][r],
            )
        elif vt == int(ValueType.JOB):
            value = JobRecord(
                type=self.interns.string(cols["type_id"][r]) or "",
                retries=cols["retries"][r],
                deadline=cols["deadline"][r],
                worker=self.interns.string(cols["worker"][r]) or "",
                payload=payload,
                custom_headers=dict(element.job_headers) if element else {},
                headers=JobHeaders(
                    workflow_instance_key=cols["instance_key"][r],
                    bpmn_process_id=workflow.id if workflow else "",
                    workflow_definition_version=workflow.version if workflow else -1,
                    workflow_key=workflow.key if workflow else -1,
                    activity_id=elem_id,
                    activity_instance_key=cols["aux_key"][r],
                ),
            )
        elif vt == int(ValueType.INCIDENT):
            error_type, message = self._incident_error(o, r, element, payload, rej)
            value = IncidentRecord(
                error_type=int(error_type),
                error_message=message,
                bpmn_process_id=workflow.id if workflow else "",
                workflow_instance_key=cols["instance_key"][r],
                activity_id=elem_id,
                activity_instance_key=cols["aux_key"][r],
                job_key=cols["aux2_key"][r],
                payload=payload,
            )
        elif vt == int(ValueType.TIMER):
            value = TimerRecord(
                workflow_instance_key=cols["instance_key"][r],
                activity_instance_key=cols["aux_key"][r],
                due_date=cols["deadline"][r],
                handler_element_id=elem_id,
            )
        elif vt == int(ValueType.MESSAGE):
            from zeebe_tpu.protocol.records import MessageRecord

            value = MessageRecord(
                name=self.interns.string(cols["type_id"][r]) or "",
                correlation_key=self._corr_string(
                    cols["retries"][r], cols["worker"][r]
                ),
                time_to_live=max(cols["deadline"][r], 0),
                payload=payload,
                message_id=self.interns.string(cols["aux2_key"][r]) or "",
            )
        elif vt == int(ValueType.MESSAGE_SUBSCRIPTION):
            from zeebe_tpu.protocol.records import MessageSubscriptionRecord

            value = MessageSubscriptionRecord(
                workflow_instance_partition_id=cols["wf"][r],
                workflow_instance_key=cols["instance_key"][r],
                activity_instance_key=cols["aux_key"][r],
                message_name=self.interns.string(cols["type_id"][r]) or "",
                correlation_key=self._corr_string(
                    cols["retries"][r], cols["worker"][r]
                ),
            )
        elif vt == int(ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION):
            from zeebe_tpu.protocol.records import (
                WorkflowInstanceSubscriptionRecord,
            )

            value = WorkflowInstanceSubscriptionRecord(
                workflow_instance_key=cols["instance_key"][r],
                activity_instance_key=cols["aux_key"][r],
                message_name=self.interns.string(cols["type_id"][r]) or "",
                payload=payload,
                message_partition_id=cols["aux2_key"][r],
                correlation_key=self._corr_string(
                    cols["retries"][r], cols["worker"][r]
                ),
            )
        else:
            value = None
        return value

    def _corr_string(self, cvt: int, cbits: int) -> str:
        """Correlation columns → the oracle's string form (numeric keys
        normalize to ``str(int(...))`` exactly like the oracle's
        ``str(corr_value)`` on an int payload value; bools to
        ``str(True/False)``)."""
        from zeebe_tpu.tpu.conditions import VT_BOOL, VT_STR

        if cvt == int(VT_STR):
            return self.interns.string(cbits) or ""
        if cvt == int(VT_BOOL):
            return str(bool(np.int32(cbits).view(np.float32)))
        if cvt == 0:
            return ""
        f = float(np.int32(cbits).view(np.float32))
        return str(int(f)) if f == int(f) else str(f)

    def _incident_error(self, o, r, element, payload, rej):
        """Reconstruct the oracle's exact incident error message by
        re-running the failing host evaluation (incidents are rare; the
        device only ships an error code)."""
        if rej == rb.ERR_CONDITION_NO_FLOW:
            return (
                ErrorType.CONDITION_ERROR,
                "All conditions evaluated to false and no default flow is set.",
            )
        if rej == rb.ERR_CONDITION_EVAL and element is not None:
            try:
                for flow in element.outgoing_with_condition:
                    evaluate_condition(flow.condition, payload)
            except ConditionEvalError as e:
                return ErrorType.CONDITION_ERROR, str(e)
            return ErrorType.CONDITION_ERROR, "condition evaluation failed"
        if rej in (rb.ERR_IO_MAPPING_IN, rb.ERR_IO_MAPPING_OUT) and element is not None:
            mappings = (
                element.input_mappings
                if rej == rb.ERR_IO_MAPPING_IN
                else element.output_mappings
            )
            try:
                if rej == rb.ERR_IO_MAPPING_IN:
                    extract(payload, mappings)
                else:
                    merge(payload, {}, mappings)
            except MappingError as e:
                return ErrorType.IO_MAPPING_ERROR, str(e)
            return ErrorType.IO_MAPPING_ERROR, "io mapping failed"
        if rej == _ERR_NO_RETRIES:
            return ErrorType.JOB_NO_RETRIES, "No more retries left."
        if rej == rb.ERR_CORRELATION_KEY:
            path = getattr(element, "correlation_key_path", "") if element else ""
            return (
                ErrorType.IO_MAPPING_ERROR,
                f"Failed to extract the correlation-key by '{path}'",
            )
        return ErrorType.UNKNOWN, ""
