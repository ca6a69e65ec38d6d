"""Host reference engine: exact per-record stream-processing semantics.

This is the correctness oracle for the TPU engine (event-replay parity) and
the recovery fallback. It mirrors, processor by processor, the reference
broker's per-partition stream processors:

- workflow instance processor + BpmnStepProcessor
  (``broker-core/.../workflow/processor/WorkflowInstanceStreamProcessor.java``,
  ``BpmnStepProcessor.java`` + the 16 step handlers),
- job processor + activate-job push processor
  (``broker-core/.../job/processor/JobInstanceStreamProcessor.java``,
  ``ActivateJobStreamProcessor.java``),
- incident processor (``broker-core/.../incident/processor/IncidentStreamProcessor.java``),
- message processors (``broker-core/.../subscription/message/processor/``),
- deployment processor (``broker-core/.../system/workflow/repository/processor/``).

Determinism contract (deviation by design, documented): the reference runs
these processors as independent actors whose interleaving is scheduler
dependent; here each committed record is routed through the sub-processors
in one fixed registration order, which yields a canonical serializable
interleaving. Cross-processor per-entity record order is preserved.

TPU-native extensions beyond the reference engine (per BASELINE.json):
parallel-gateway fork/join with scope token accounting, timer catch events,
receive tasks, and message-subscription close on termination.
"""

from __future__ import annotations

import dataclasses
import logging
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

from zeebe_tpu.engine import keyspace
from zeebe_tpu.engine.mappings import MappingError, extract, merge
from zeebe_tpu.models.bpmn.model import ElementType, OutputBehavior
from zeebe_tpu.models.el.ast import query_json_path
from zeebe_tpu.models.el.interpreter import ConditionEvalError, evaluate_condition
from zeebe_tpu.models.transform.executable import (
    ExecutableFlowElement,
    ExecutableWorkflow,
)
from zeebe_tpu.models.transform.steps import BpmnStep
from zeebe_tpu.protocol.enums import ErrorType, RecordType, RejectionType, ValueType
from zeebe_tpu.protocol.intents import (
    IncidentIntent,
    JobIntent,
    MessageIntent,
    MessageSubscriptionIntent,
    SubscriberIntent,
    SubscriptionIntent,
    TimerIntent,
    WorkflowInstanceIntent as WI,
    WorkflowInstanceSubscriptionIntent,
    is_final_state,
    is_initial_state,
    can_terminate,
)
from zeebe_tpu.protocol.metadata import RecordMetadata
from zeebe_tpu.protocol.records import (
    IncidentRecord,
    JobHeaders,
    JobRecord,
    MessageRecord,
    MessageSubscriptionRecord,
    Record,
    TimerRecord,
    WorkflowInstanceRecord,
    WorkflowInstanceSubscriptionRecord,
)

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------


class ElementInstance:
    """Reference: broker-core/.../workflow/index/ElementInstance.java, plus
    ``active_tokens`` (TPU-native scope token counter for parallel flows)."""

    __slots__ = (
        "key", "parent", "state", "value", "children", "job_key",
        "active_tokens", "join_arrivals", "mi_outputs",
    )

    def __init__(self, key: int, parent: Optional["ElementInstance"]):
        self.key = key
        self.parent = parent
        self.state: Optional[WI] = None
        self.value: Optional[WorkflowInstanceRecord] = None
        self.children: List["ElementInstance"] = []
        self.job_key = -1
        self.active_tokens = 0
        # parallel-join arrival payloads: gateway element idx → {flow idx → payload}
        self.join_arrivals: Dict[int, Dict[int, dict]] = {}
        # multi-instance containers: loopCounter → extracted output value
        self.mi_outputs: Dict[int, Any] = {}
        if parent is not None:
            parent.children.append(self)

    def destroy(self):
        if self.parent is not None and self in self.parent.children:
            self.parent.children.remove(self)

    def can_terminate(self) -> bool:
        return can_terminate(self.state)


class ElementInstanceIndex:
    """Reference: broker-core/.../workflow/index/ElementInstanceIndex.java."""

    def __init__(self):
        self.instances: Dict[int, ElementInstance] = {}

    def new_instance(
        self,
        key: int,
        value: WorkflowInstanceRecord,
        state: WI,
        parent: Optional[ElementInstance] = None,
    ) -> ElementInstance:
        instance = ElementInstance(key, parent)
        instance.state = state
        instance.value = value.copy()
        self.instances[key] = instance
        return instance

    def get(self, key: int) -> Optional[ElementInstance]:
        return self.instances.get(key)

    def remove(self, key: int) -> None:
        instance = self.instances.pop(key, None)
        if instance is not None:
            instance.destroy()


@dataclasses.dataclass
class JobState:
    """Reference: JobInstanceStateController short states in RocksDB."""

    state: int  # JobIntent value of the last state event
    record: JobRecord
    deadline: int = -1


@dataclasses.dataclass
class JobSubscription:
    """Reference: broker-core/.../job/processor/JobSubscription.java."""

    subscriber_key: int
    job_type: str
    worker: str
    timeout: int
    credits: int


@dataclasses.dataclass
class StoredMessage:
    key: int
    name: str
    correlation_key: str
    time_to_live: int
    payload: Dict[str, Any]
    message_id: str
    deadline: int


@dataclasses.dataclass
class StoredSubscription:
    message_name: str
    correlation_key: str
    workflow_instance_partition_id: int
    workflow_instance_key: int
    activity_instance_key: int


@dataclasses.dataclass
class IncidentState:
    state: int  # CREATED / RESOLVING / DELETING (int of IncidentIntent-ish)
    incident_event_position: int
    failure_event_position: int


INCIDENT_CREATED = 1
INCIDENT_RESOLVING = 2
INCIDENT_DELETING = 3


@dataclasses.dataclass
class TimerState:
    due_date: int
    activity_instance_key: int
    record: TimerRecord


class WorkflowRepository:
    """Deployed workflow store (reference: WorkflowRepositoryIndex on the
    system partition + per-partition WorkflowCache; here fetches are
    synchronous in-process, so one shared repository serves all partitions)."""

    def __init__(self):
        self.by_key: Dict[int, ExecutableWorkflow] = {}
        self.versions: Dict[str, List[ExecutableWorkflow]] = {}
        # monotonic mutation counter: the repository is SHARED across
        # partitions (and mutated by workflow fetches outside any record),
        # so snapshot dirty tracking compares this instead of guessing
        # from processed value types
        self.version = 0

    def put(self, workflow: ExecutableWorkflow) -> None:
        self.by_key[workflow.key] = workflow
        self.versions.setdefault(workflow.id, []).append(workflow)
        self.version += 1

    def next_version(self, process_id: str) -> int:
        return len(self.versions.get(process_id, [])) + 1

    def latest(self, process_id: str) -> Optional[ExecutableWorkflow]:
        versions = self.versions.get(process_id)
        return versions[-1] if versions else None

    def by_id_and_version(self, process_id: str, version: int) -> Optional[ExecutableWorkflow]:
        for wf in self.versions.get(process_id, []):
            if wf.version == version:
                return wf
        return None

    def merge(self, workflows: List[ExecutableWorkflow]) -> None:
        """Idempotent restore-merge (snapshot recovery): register unknown
        workflows, keeping version lists sorted so ``latest`` stays correct."""
        for wf in workflows:
            if wf.key not in self.by_key:
                self.put(wf)
        for versions in self.versions.values():
            versions.sort(key=lambda w: w.version)


# ---------------------------------------------------------------------------
# processing result plumbing
# ---------------------------------------------------------------------------


class RecordCache:
    """Position → record cache with a bounded in-heap hot window and a
    native keyed cold store behind it.

    The reference keeps keyed processor state in RocksDB
    (``logstreams/.../state/StateController.java:24-50``); this is that
    role for the oracle's position-based reads (incident resolution
    re-reads its failure event by position, reference TypedStreamReader):
    the newest ``hot_capacity`` records stay as Python objects, older ones
    spill to ``native/kvstore.cc`` as encoded frames. Without the native
    toolchain the cache degrades to a plain unbounded dict (the
    round-2 behavior)."""

    def __init__(self, hot_capacity: int = 8192):
        self._hot: "OrderedDict[int, Record]" = OrderedDict()
        self._hot_capacity = hot_capacity
        # position-addressed fallback (the partition's LOG, installed by
        # the brokers): every cached record IS a log record, and the
        # engine's compaction floor pins exactly the positions incident
        # resolution re-reads — so with a log behind the cache, eviction
        # needs NO spill copy at all. The KV spill (encoded frame per
        # evicted record) was ~a third of the serving drain's host CPU.
        self._log_lookup = None
        self._kv = None
        try:
            from zeebe_tpu import native as _native

            if _native.available():
                self._kv = _native.KvStore()
        except Exception:  # noqa: BLE001 - cold store is an optimization
            self._kv = None

    def set_log_lookup(self, lookup) -> None:
        """Install ``lookup(position) -> Optional[Record]`` (the log's
        ``record_at``); eviction stops paying the encode+KV spill."""
        self._log_lookup = lookup

    def __setitem__(self, position: int, record: Record) -> None:
        self._hot[position] = record
        self._hot.move_to_end(position)
        if len(self._hot) <= self._hot_capacity:
            return
        if self._log_lookup is not None:
            self._hot.popitem(last=False)  # the log serves old positions
            return
        if self._kv is not None:
            old_pos, old_rec = self._hot.popitem(last=False)
            try:
                from zeebe_tpu.protocol import codec as _codec

                self._kv.put(
                    old_pos.to_bytes(8, "little", signed=True),
                    _codec.encode_record(old_rec),
                )
            except Exception:  # noqa: BLE001 - keep it hot on encode failure
                self._hot[old_pos] = old_rec
                self._hot.move_to_end(old_pos, last=False)

    def get(self, position: int, default=None):
        record = self._hot.get(position)
        if record is not None:
            return record
        if self._log_lookup is not None:
            record = self._log_lookup(position)
            if record is not None:
                return record
        if self._kv is not None:
            blob = self._kv.get(position.to_bytes(8, "little", signed=True))
            if blob is not None:
                from zeebe_tpu.protocol import codec as _codec

                record, _ = _codec.decode_record(blob, 0)
                return record
        return default

    def __contains__(self, position: int) -> bool:
        return self.get(position) is not None

    def __len__(self) -> int:
        return len(self._hot) + (len(self._kv) if self._kv is not None else 0)


@dataclasses.dataclass
class ProcessingResult:
    """Output of processing one committed record."""

    written: List[Record] = dataclasses.field(default_factory=list)
    responses: List[Record] = dataclasses.field(default_factory=list)
    # cross-partition sends (reference: subscription transport messages):
    # (target_partition_id, record-to-write-as-command)
    sends: List[Tuple[int, Record]] = dataclasses.field(default_factory=list)
    # job pushes to subscribers: (subscriber_key, record)
    pushes: List[Tuple[int, Record]] = dataclasses.field(default_factory=list)

    @classmethod
    def merged(cls, results) -> "ProcessingResult":
        """Record-major merge of per-record results (every output channel;
        the ONE place to extend when ProcessingResult grows a field)."""
        out = cls()
        for res in results:
            out.written.extend(res.written)
            out.responses.extend(res.responses)
            out.sends.extend(res.sends)
            out.pushes.extend(res.pushes)
        return out


def _record(
    record_type: RecordType,
    value,
    intent: int,
    key: int = -1,
    source_position: int = -1,
    metadata_extra: Optional[dict] = None,
) -> Record:
    md = RecordMetadata(
        record_type=record_type,
        value_type=value.VALUE_TYPE,
        intent=int(intent),
    )
    if metadata_extra:
        for k, v in metadata_extra.items():
            setattr(md, k, v)
    return Record(
        key=key,
        source_record_position=source_position,
        metadata=md,
        value=value,
    )


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class PartitionEngine:
    """Reference-semantics stream processor for one partition."""

    def __init__(
        self,
        partition_id: int = 0,
        num_partitions: int = 1,
        repository: Optional[WorkflowRepository] = None,
        clock: Optional[Callable[[], int]] = None,
    ):
        self.partition_id = partition_id
        self.num_partitions = num_partitions
        self.repository = repository if repository is not None else WorkflowRepository()
        self.clock = clock or (lambda: 0)

        # key generators (reference KeyGenerator.create*KeyGenerator)
        self.wf_keys = keyspace.workflow_instance_keys()
        self.job_keys = keyspace.job_keys()
        self.incident_keys = keyspace.incident_keys()
        self.deployment_keys = keyspace.deployment_keys()

        # workflow state
        self.element_instances = ElementInstanceIndex()

        # job state
        self.jobs: Dict[int, JobState] = {}
        self.job_subscriptions: List[JobSubscription] = []
        self._job_rr_cursor = 0
        # jobs that became activatable while every matching subscription
        # was out of credits: type → insertion-ordered key set. The
        # reference never strands these — ActivateJobStreamProcessor
        # pauses its log reader on credit exhaustion and RESUMES where it
        # stopped when credits return; this index is the bounded-memory
        # equivalent (backlog_activations drains it on credit return /
        # broker tick). Entries are verified against live job state on
        # pop, so stale keys (completed/canceled meanwhile) just drop.
        self._awaiting_jobs: Dict[str, Dict[int, None]] = {}

        # incident state (reference IncidentStreamProcessor maps)
        self.incidents: Dict[int, IncidentState] = {}
        self.incident_by_activity: Dict[int, int] = {}
        self.incident_by_failed_job: Dict[int, int] = {}
        self.resolving_events: Dict[int, int] = {}  # failure-event position → incident key
        self.incident_records: Dict[int, IncidentRecord] = {}

        # message state (this partition acting as message partition)
        self.messages: Dict[int, StoredMessage] = {}
        self.message_subscriptions: List[StoredSubscription] = []

        # timers (TPU-native)
        self.timers: Dict[int, TimerState] = {}

        # interrupting-boundary continuations: host instance key →
        # (boundary element id, trigger payload); set when the trigger
        # terminates the host, consumed when ELEMENT_TERMINATED processes
        self._pending_boundary: Dict[int, tuple] = {}

        # topic subscription ack state (reference TopicSubscriberState:
        # per-subscription last acked position, persisted in the log)
        self.topic_sub_acks: Dict[str, int] = {}
        self.topic_sub_keys = keyspace.topic_subscriber_keys()

        # exporter export progress (reference ExportersState: per exporter
        # the last log position it durably exported; replicated through
        # EXPORTER ACKNOWLEDGE records so a new leader resumes without
        # gaps, and pins the compaction floor until exported)
        self.exporter_positions: Dict[str, int] = {}

        # poison-record isolation (reference StreamProcessor onError):
        # (position, error) for records whose handler raised; they are
        # skipped by process_batch, never retried
        self.processing_failures: List[tuple] = []

        # topic orchestration state, system partition only (reference
        # KnownTopics + the IdGenerator stream processor: partition ids are
        # assigned deterministically from replicated state)
        self.topics: Dict[str, dict] = {}
        self.topic_keys = keyspace.topic_keys()
        self.next_partition_id = 1  # 0 is the system partition

        # log access for position-based reads (reference TypedStreamReader,
        # backed by the keyed cold-state store when the native layer is
        # present — the RocksDB StateController analogue; in-heap otherwise)
        self.records_by_position = RecordCache()

        self.last_processed_position = -1

        # delta-snapshot dirty tracking: families ("h/<family>") mutated
        # since the last snapshot_mark_clean(); None = tracking cold
        # (everything assumed dirty — fresh or restored engine)
        self._dirty_families: Optional[set] = None
        self._repo_version_at_clean: Optional[int] = None

    # -- partition routing (reference SubscriptionCommandSender:96-108) ----
    def partition_for_correlation_key(self, correlation_key: str) -> int:
        return _correlation_hash(correlation_key) % self.num_partitions

    # -- snapshot support (reference: ComposedSnapshot of the processor's
    # state resources — ElementInstanceIndex SerializableWrapper, job RocksDB
    # checkpoint, incident/message maps; SURVEY.md §5 checkpoint/resume) ----

    # Dirty-family tracking for delta snapshots: which state families
    # (log/stateser.py HOST_FAMILIES, "h/" namespace) a record of a given
    # value type may mutate. CONSERVATIVE supersets derived from the
    # handler dispatch — over-marking merely re-encodes a clean family;
    # under-marking would silently corrupt delta takes (the chaos
    # delta-vs-full invariant is the regression net). "h/control" appears
    # everywhere because every processed record advances
    # last_processed_position; "h/workflows" is tracked separately via
    # WorkflowRepository.version (the repository is shared and mutated by
    # fetches outside record processing).
    _VT_DIRTY_FAMILIES = {
        int(ValueType.DEPLOYMENT): frozenset({"h/control"}),
        int(ValueType.WORKFLOW_INSTANCE): frozenset(
            {"h/instances", "h/incidents", "h/control"}),
        int(ValueType.JOB): frozenset(
            {"h/jobs", "h/instances", "h/incidents", "h/control"}),
        # RESOLVE re-writes the failure event via _write_wi_followup,
        # which mutates the element-instance index directly
        int(ValueType.INCIDENT): frozenset(
            {"h/incidents", "h/instances", "h/control"}),
        int(ValueType.MESSAGE): frozenset({"h/messages", "h/control"}),
        int(ValueType.MESSAGE_SUBSCRIPTION): frozenset(
            {"h/messages", "h/control"}),
        int(ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION): frozenset(
            {"h/instances", "h/messages", "h/control"}),
        int(ValueType.TIMER): frozenset(
            {"h/timers", "h/instances", "h/control"}),
        int(ValueType.SUBSCRIBER): frozenset({"h/control"}),
        int(ValueType.SUBSCRIPTION): frozenset({"h/control"}),
        int(ValueType.EXPORTER): frozenset({"h/control"}),
        int(ValueType.TOPIC): frozenset({"h/control"}),
        int(ValueType.NOOP): frozenset({"h/control"}),
        int(ValueType.RAFT): frozenset({"h/control"}),
    }

    def snapshot_dirty_families(self):
        """Families mutated since the last ``snapshot_mark_clean`` (the
        ``"h/<family>"`` names of log/stateser.HOST_FAMILIES), or None when
        tracking is cold (fresh/restored engine) — the controller takes a
        full snapshot then."""
        if self._dirty_families is None:
            return None
        dirty = set(self._dirty_families)
        if (
            self._repo_version_at_clean is None
            or self.repository.version != self._repo_version_at_clean
        ):
            dirty.add("h/workflows")
        return frozenset(dirty)

    def snapshot_mark_clean(self) -> None:
        """Reset tracking at a capture fence: mutations from now on belong
        to the NEXT snapshot."""
        self._dirty_families = set()
        self._repo_version_at_clean = self.repository.version

    def snapshot_mark_dirty(self, families=None) -> None:
        """Re-mark families dirty (None = everything) — used when a take
        fails after its capture fence already reset the tracking."""
        if families is None:
            self._dirty_families = None
            self._repo_version_at_clean = None
            return
        if "h/workflows" in families:
            self._repo_version_at_clean = None
        if self._dirty_families is not None:
            self._dirty_families.update(families)

    def _mark_dirty_for_record(self, value_type) -> None:
        if self._dirty_families is None:
            return
        families = self._VT_DIRTY_FAMILIES.get(int(value_type))
        if families is None:
            # unknown value type: assume everything mutated (safety over
            # delta efficiency)
            self._dirty_families = None
            self._repo_version_at_clean = None
            return
        self._dirty_families.update(families)

    def compaction_floor(self) -> int:
        """Highest log position below which records may be compacted away
        (exclusive). Open incidents re-read their failure event from the
        log on resolution (reference TypedStreamReader by position), so
        those positions must survive until the incident is deleted."""
        floor = self.last_processed_position + 1
        for incident in self.incidents.values():
            if incident.failure_event_position >= 0:
                floor = min(floor, incident.failure_event_position)
            if incident.incident_event_position >= 0:
                floor = min(floor, incident.incident_event_position)
        # durable topic subscriptions resume from their logged acks — the
        # records past a subscriber's ack must survive compaction or the
        # subscriber silently loses them (reference: segment deletion is
        # bounded by exporter/subscriber positions)
        for acked in self.topic_sub_acks.values():
            floor = min(floor, acked + 1)
        # exporters bound segment deletion the same way (reference: "the
        # broker deletes segments only up to the lowest exporter
        # position"): a registered exporter with no progress yet (-1)
        # pins the floor at 0
        for acked in self.exporter_positions.values():
            floor = min(floor, acked + 1)
        return floor

    def snapshot_state(self, families=None) -> dict:
        """All log-derived state. Excludes transient client-session state
        (job subscriptions re-register after failover, as in the reference)
        and the position→record cache (rebuilt from the log on recovery).

        ``families`` (a dirty-family set from ``snapshot_dirty_families``)
        is accepted for interface parity with the device engine, where a
        partial capture skips device→host readback; host state is plain
        references, so the dict is cheap either way — the delta filtering
        happens at encode time (``stateser.encode_state_parts_delta``)."""
        return {
            "wf_keys": self.wf_keys,
            "job_keys": self.job_keys,
            "incident_keys": self.incident_keys,
            "deployment_keys": self.deployment_keys,
            "element_instances": self.element_instances,
            "jobs": self.jobs,
            "incidents": self.incidents,
            "incident_by_activity": self.incident_by_activity,
            "incident_by_failed_job": self.incident_by_failed_job,
            "resolving_events": self.resolving_events,
            "incident_records": self.incident_records,
            "messages": self.messages,
            "message_subscriptions": self.message_subscriptions,
            "timers": self.timers,
            "pending_boundary": self._pending_boundary,
            "awaiting_jobs": self._awaiting_jobs,
            "topic_sub_acks": self.topic_sub_acks,
            "exporter_positions": self.exporter_positions,
            "topics": self.topics,
            "next_partition_id": self.next_partition_id,
            "last_processed_position": self.last_processed_position,
            # deployed workflows ride along so a restored partition does not
            # depend on replaying the deployment partition (reference:
            # WorkflowCache refetches; here the repository is restored)
            "workflows": list(self.repository.by_key.values()),
        }

    def restore_state(self, state: dict) -> None:
        # a restored engine's tracking is cold: the next take is full
        self.snapshot_mark_dirty(None)
        self.wf_keys = state["wf_keys"]
        self.job_keys = state["job_keys"]
        self.incident_keys = state["incident_keys"]
        self.deployment_keys = state["deployment_keys"]
        self.element_instances = state["element_instances"]
        self.jobs = state["jobs"]
        self.incidents = state["incidents"]
        self.incident_by_activity = state["incident_by_activity"]
        self.incident_by_failed_job = state["incident_by_failed_job"]
        self.resolving_events = state["resolving_events"]
        self.incident_records = state["incident_records"]
        self.messages = state["messages"]
        self.message_subscriptions = state["message_subscriptions"]
        self.timers = state["timers"]
        self._pending_boundary = state.get("pending_boundary", {})
        self._awaiting_jobs = state.get("awaiting_jobs", {})
        self.topic_sub_acks = state.get("topic_sub_acks", {})
        self.exporter_positions = state.get("exporter_positions", {})
        self.topics = state.get("topics", {})
        self.next_partition_id = state.get("next_partition_id", 1)
        self.last_processed_position = state["last_processed_position"]
        self.repository.merge(state["workflows"])

    # ------------------------------------------------------------------
    # main entry: process one committed record
    # ------------------------------------------------------------------
    def process_batch(self, records: List[Record]) -> ProcessingResult:
        """Batch drain: per-record processing with per-record source
        stamping, merged in log order (the device engine overrides this
        with real SIMD batching).

        Failure containment (reference StreamProcessorController onError →
        skip/blacklist, ``StreamProcessorController.java:296-399``): a record
        whose handler raises is logged, recorded in ``processing_failures``,
        answered with a PROCESSING_ERROR rejection when it was a client
        command, and SKIPPED — a poison record cannot wedge the partition by
        re-raising on every drain (round-3 advisor finding). Determinism
        note: handlers fail deterministically (pure functions of record +
        state), so replay re-raises at the same point and reconverges on the
        same partial mutations; the skip is replay-stable."""
        return ProcessingResult.merged(self.process_wave(records))

    # value types the wave fold handles WITHOUT the full per-record
    # dispatch: pure state-fold records that never produce follow-ups and
    # are never re-read by position (the plane's own admin traffic — on an
    # exporter-heavy partition every dispatched batch appends an ack that
    # flows back through here)
    _FOLD_VTS = frozenset(
        {int(ValueType.NOOP), int(ValueType.RAFT), int(ValueType.EXPORTER)}
    )

    def process_wave(self, records) -> List[ProcessingResult]:
        """One drained wave → PER-RECORD results (source-stamped). The
        in-process broker applies each record's sends/appends in record
        order, so a wave-drained log stays byte-identical to
        record-at-a-time processing even when sends target the local
        partition; the device engine overrides this with one SIMD dispatch
        per wave. Failure containment is per record (see process_batch).

        ``records`` may be a plain list or a columnar view
        (``RecordsView``/``ColumnarBatch``); the wave FOLDS over the
        value-type column for the plane's own admin records (NOOP / RAFT
        no-ops, EXPORTER position acks) — no position-cache insert, no
        handler dispatch — and runs the full per-record path for
        everything else."""
        import time as _time

        from zeebe_tpu.protocol.records import stamp_source_positions

        t0 = _time.perf_counter()
        results: List[ProcessingResult] = []
        fold_vts = self._FOLD_VTS
        command = RecordType.COMMAND
        for record in records:
            md = record.metadata
            if int(md.value_type) in fold_vts:
                # column fold: state-only admin record. EXPORTER acks fold
                # into exporter_positions; NOOP/RAFT only advance the
                # processed position. Both dirty h/control exactly like
                # the dispatched path (_VT_DIRTY_FAMILIES) and emit no
                # follow-ups, so skipping dispatch is byte-invisible.
                out = ProcessingResult()
                if self._dirty_families is not None:
                    self._dirty_families.add("h/control")
                if (
                    int(md.value_type) == int(ValueType.EXPORTER)
                    and md.record_type == command
                ):
                    try:
                        self._process_exporter_ack(record, out)
                    except Exception as e:  # noqa: BLE001 - poison isolation
                        self._contain_processing_failure(record, e, out)
                self.last_processed_position = record.position
                results.append(out)
                continue
            try:
                res = self.process(record)
            except Exception as e:  # noqa: BLE001 - poison-record isolation
                res = ProcessingResult()
                self._contain_processing_failure(record, e, res)
            else:
                stamp_source_positions(res.written, record.position)
            results.append(res)
        # (host_seconds, device_seconds) of the last wave — the serving
        # metrics' time-split source; pure host engine ⇒ device share 0
        self.last_wave_seconds = (_time.perf_counter() - t0, 0.0)
        return results

    def _contain_processing_failure(
        self, record: Record, exc: Exception, merged: ProcessingResult
    ) -> None:
        """Record, log, and (for client commands) answer a record whose
        handler raised, so the client sees a rejection instead of hanging
        to its request timeout."""
        self.processing_failures.append((record.position, repr(exc)[:300]))
        logger.error(
            "record at position %d (valueType=%s intent=%s) poisoned the "
            "engine and was skipped: %r",
            record.position, record.metadata.value_type,
            record.metadata.intent, exc,
        )
        if (
            record.metadata.record_type == RecordType.COMMAND
            and record.metadata.request_id >= 0
        ):
            try:
                rejection = _record(
                    RecordType.COMMAND_REJECTION, record.value.copy(),
                    record.metadata.intent, record.key, record.position,
                    {
                        "rejection_type": RejectionType.PROCESSING_ERROR,
                        "rejection_reason": f"processing failed: {exc!r}"[:200],
                        "request_id": record.metadata.request_id,
                        "request_stream_id": record.metadata.request_stream_id,
                    },
                )
            except Exception:  # noqa: BLE001 - the value itself may be broken
                return
            merged.responses.append(rejection)

    def process(self, record: Record) -> ProcessingResult:
        self.records_by_position[record.position] = record
        self._mark_dirty_for_record(record.metadata.value_type)
        out = ProcessingResult()
        vt = record.metadata.value_type
        rt = record.metadata.record_type
        intent = record.metadata.intent

        if vt == ValueType.DEPLOYMENT and rt == RecordType.COMMAND:
            self._process_deployment(record, out)
        elif vt == ValueType.WORKFLOW_INSTANCE:
            self._process_workflow_instance(record, out)
            self._incident_on_workflow_record(record, out)
        elif vt == ValueType.JOB:
            if rt == RecordType.COMMAND:
                self._process_job_command(record, out)
            else:
                self._workflow_on_job_event(record, out)
                self._activate_jobs_on_event(record, out)
                self._incident_on_job_event(record, out)
        elif vt == ValueType.INCIDENT:
            self._process_incident(record, out)
        elif vt == ValueType.MESSAGE and rt == RecordType.COMMAND:
            self._process_message_command(record, out)
        elif vt == ValueType.MESSAGE_SUBSCRIPTION and rt == RecordType.COMMAND:
            self._process_message_subscription(record, out)
        elif vt == ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION and rt == RecordType.COMMAND:
            self._process_wi_subscription(record, out)
        elif vt == ValueType.TIMER and rt == RecordType.COMMAND:
            self._process_timer(record, out)
        elif vt == ValueType.SUBSCRIBER and rt == RecordType.COMMAND:
            self._process_topic_subscriber(record, out)
        elif vt == ValueType.SUBSCRIPTION and rt == RecordType.COMMAND:
            self._process_topic_subscription_ack(record, out)
        elif vt == ValueType.EXPORTER and rt == RecordType.COMMAND:
            self._process_exporter_ack(record, out)
        elif vt == ValueType.TOPIC and rt == RecordType.COMMAND:
            self._process_topic(record, out)

        self.last_processed_position = record.position
        return out

    # -- topic orchestration, system partition (reference
    # TopicCreateProcessor / TopicCreatedProcessor + IdGenerator) ----------
    def _process_topic(self, record: Record, out: ProcessingResult) -> None:
        from zeebe_tpu.protocol.intents import TopicIntent

        intent = TopicIntent(record.metadata.intent)
        value = record.value
        request_meta = {
            "request_id": record.metadata.request_id,
            "request_stream_id": record.metadata.request_stream_id,
        }
        if intent == TopicIntent.CREATE:
            if not value.name:
                self._topic_rejection(record, "topic name must not be empty", out)
                return
            if value.partitions <= 0:
                self._topic_rejection(record, "partition count must be positive", out)
                return
            if value.name in self.topics:
                self._topic_rejection(record, f"topic '{value.name}' already exists", out)
                return
            created = value.copy()
            # deterministic id assignment from replicated state (reference
            # IdGenerator: ids survive failover because they come from the
            # replicated log, never from local counters)
            created.partition_ids = [
                self.next_partition_id + i for i in range(value.partitions)
            ]
            self.next_partition_id += value.partitions
            key = self.topic_keys.next_key()
            self.topics[created.name] = {"record": created, "state": "CREATING"}
            # CREATING carries the client request metadata: the response is
            # deferred until CREATE_COMPLETE confirms leaders exist
            out.written.append(
                _record(RecordType.EVENT, created.copy(), TopicIntent.CREATING,
                        key, record.position, request_meta)
            )
        elif intent == TopicIntent.CREATE_COMPLETE:
            topic = self.topics.get(value.name)
            if topic is None or topic["state"] == "CREATED":
                return
            topic["state"] = "CREATED"
            done = _record(
                RecordType.EVENT, topic["record"].copy(), TopicIntent.CREATED,
                record.key, record.position, request_meta,
            )
            out.written.append(done)
            out.responses.append(done)

    def _topic_rejection(self, record: Record, reason: str, out: ProcessingResult) -> None:
        rejection = _record(
            RecordType.COMMAND_REJECTION, record.value.copy(),
            record.metadata.intent, record.key, record.position,
            {
                "rejection_type": RejectionType.BAD_VALUE,
                "rejection_reason": reason,
                "request_id": record.metadata.request_id,
                "request_stream_id": record.metadata.request_stream_id,
            },
        )
        out.written.append(rejection)
        out.responses.append(rejection)

    # -- topic subscriptions (reference TopicSubscriptionManagementProcessor)
    def _process_topic_subscriber(self, record: Record, out: ProcessingResult) -> None:
        intent = SubscriberIntent(record.metadata.intent)
        if intent != SubscriberIntent.SUBSCRIBE:
            return
        value = record.value
        key = self.topic_sub_keys.next_key()
        if value.force_start:
            # reference: forceStart resets persisted progress
            self.topic_sub_acks.pop(value.name, None)
        subscribed = _record(
            RecordType.EVENT, value.copy(), SubscriberIntent.SUBSCRIBED, key,
            record.position,
            {
                "request_id": record.metadata.request_id,
                "request_stream_id": record.metadata.request_stream_id,
            },
        )
        out.written.append(subscribed)
        out.responses.append(subscribed)

    def _process_topic_subscription_ack(self, record: Record, out: ProcessingResult) -> None:
        intent = SubscriptionIntent(record.metadata.intent)
        if intent != SubscriptionIntent.ACKNOWLEDGE:
            return
        value = record.value
        prior = self.topic_sub_acks.get(value.name, -1)
        if value.ack_position > prior:
            self.topic_sub_acks[value.name] = value.ack_position
        out.written.append(
            _record(RecordType.EVENT, value.copy(), SubscriptionIntent.ACKNOWLEDGED,
                    record.key, record.position)
        )

    # -- exporter position acks (reference: exporter positions column in
    # broker state, ExporterDirector#updateLastExportedPosition) -----------
    def _process_exporter_ack(self, record: Record, out: ProcessingResult) -> None:
        from zeebe_tpu.protocol.intents import ExporterIntent

        intent = ExporterIntent(record.metadata.intent)
        value = record.value
        if not value.exporter_id:
            return
        if intent == ExporterIntent.REMOVE:
            # deconfigured exporter: drop its entry so a stale position
            # (possibly the -1 registration) stops pinning the compaction
            # floor forever (the director appends REMOVE on open for
            # recovered ids no longer in its configured set)
            self.exporter_positions.pop(value.exporter_id, None)
            return
        if intent != ExporterIntent.ACKNOWLEDGE:
            return
        # monotonic: a late/duplicate ack (director retry after failover)
        # never rewinds export progress. position -1 REGISTERS an exporter
        # before its first ack so compaction is pinned from the start.
        prior = self.exporter_positions.get(value.exporter_id)
        if prior is None or value.position > prior:
            self.exporter_positions[value.exporter_id] = value.position
        # no follow-up event: the ack command itself is the durable,
        # replicated artifact (state-only update, nothing re-processable)

    # ------------------------------------------------------------------
    # writers (reference TypedStreamWriter / ElementInstanceWriter)
    # ------------------------------------------------------------------
    def _write_new_wi_event(
        self, out: ProcessingResult, source: Record, state: WI, value: WorkflowInstanceRecord
    ) -> int:
        """Reference ElementInstanceWriter.writeNewEvent."""
        key = self.wf_keys.next_key()
        out.written.append(
            _record(RecordType.EVENT, value.copy(), state, key, source.position)
        )
        if is_initial_state(state):
            scope_key = value.scope_instance_key
            parent = self.element_instances.get(scope_key) if scope_key >= 0 else None
            self.element_instances.new_instance(key, value, state, parent)
        return key

    def _write_wi_followup(
        self, out: ProcessingResult, source: Record, key: int, state: WI,
        value: WorkflowInstanceRecord, metadata_extra: Optional[dict] = None,
    ) -> None:
        """Reference ElementInstanceWriter.writeFollowUpEvent."""
        out.written.append(
            _record(RecordType.EVENT, value.copy(), state, key, source.position, metadata_extra)
        )
        if is_final_state(state):
            self.element_instances.remove(key)
        else:
            instance = self.element_instances.get(key)
            if instance is not None:
                instance.state = state
                instance.value = value.copy()

    # ------------------------------------------------------------------
    # deployment (reference DeploymentCreateEventProcessor)
    # ------------------------------------------------------------------
    def _process_deployment(self, record: Record, out: ProcessingResult) -> None:
        from zeebe_tpu.models.bpmn.validation import validate_model
        from zeebe_tpu.models.bpmn.xml import read_model
        from zeebe_tpu.models.bpmn.yaml_front import read_yaml_workflow
        from zeebe_tpu.models.transform.transformer import transform_model
        from zeebe_tpu.protocol.intents import DeploymentIntent
        from zeebe_tpu.protocol.records import DeployedWorkflowMeta

        # the CREATED event carries the deployed workflows: fill a copy, so
        # the command stays the record its log frame holds
        deployment = record.value.copy()
        deployed: List[ExecutableWorkflow] = []
        try:
            for resource in deployment.resources:
                data = resource.resource
                if isinstance(data, str):
                    data = data.encode("utf-8")
                if resource.resource_type == "YAML_WORKFLOW":
                    model = read_yaml_workflow(data.decode("utf-8"))
                else:
                    model = read_model(data)
                errors = validate_model(model)
                if errors:
                    raise ValueError("; ".join(str(e) for e in errors))
                for wf in transform_model(model):
                    wf.source_resource = data
                    wf.source_type = resource.resource_type
                    deployed.append(wf)
        except Exception as e:  # malformed resource → rejection
            out.written.append(
                _record(
                    RecordType.COMMAND_REJECTION,
                    deployment,
                    DeploymentIntent.CREATE,
                    record.key,
                    record.position,
                    {
                        "rejection_type": RejectionType.BAD_VALUE,
                        "rejection_reason": str(e),
                        "request_id": record.metadata.request_id,
                        "request_stream_id": record.metadata.request_stream_id,
                    },
                )
            )
            out.responses.append(out.written[-1])
            return

        key = self.deployment_keys.next_key()
        deployment.deployed_workflows = []
        for wf in deployed:
            wf.version = self.repository.next_version(wf.id)
            wf.key = self.deployment_keys.next_key()
            self.repository.put(wf)
            deployment.deployed_workflows.append(
                DeployedWorkflowMeta(
                    bpmn_process_id=wf.id, version=wf.version, key=wf.key
                )
            )
        created = _record(
            RecordType.EVENT,
            deployment,
            DeploymentIntent.CREATED,
            key,
            record.position,
            {
                "request_id": record.metadata.request_id,
                "request_stream_id": record.metadata.request_stream_id,
            },
        )
        out.written.append(created)
        out.responses.append(created)

    # ------------------------------------------------------------------
    # workflow instance records
    # ------------------------------------------------------------------
    def _process_workflow_instance(self, record: Record, out: ProcessingResult) -> None:
        intent = WI(record.metadata.intent)
        rt = record.metadata.record_type
        if rt == RecordType.COMMAND:
            if intent == WI.CREATE:
                self._create_workflow_instance(record, out)
            elif intent == WI.CANCEL:
                self._cancel_workflow_instance(record, out)
            elif intent == WI.UPDATE_PAYLOAD:
                self._update_payload(record, out)
            return
        if rt != RecordType.EVENT:
            return
        if intent == WI.CREATED:
            # reference WorkflowInstanceCreatedEventProcessor
            self.element_instances.new_instance(record.key, record.value, WI.ELEMENT_READY)
            out.responses.append(record)
            return
        if intent in (
            WI.SEQUENCE_FLOW_TAKEN,
            WI.ELEMENT_READY,
            WI.ELEMENT_ACTIVATED,
            WI.ELEMENT_COMPLETING,
            WI.ELEMENT_COMPLETED,
            WI.ELEMENT_TERMINATING,
            WI.ELEMENT_TERMINATED,
            WI.START_EVENT_OCCURRED,
            WI.END_EVENT_OCCURRED,
            WI.GATEWAY_ACTIVATED,
            WI.BOUNDARY_EVENT_OCCURRED,
        ):
            self._bpmn_step(record, intent, out)

    def _create_workflow_instance(self, command: Record, out: ProcessingResult) -> None:
        """Reference CreateWorkflowInstanceEventProcessor (fetches are
        synchronous here; key generated before lookup for replay parity)."""
        value: WorkflowInstanceRecord = command.value.copy()
        wf_instance_key = self.wf_keys.next_key()
        value.workflow_instance_key = wf_instance_key

        workflow = None
        if value.workflow_key > 0:
            workflow = self.repository.by_key.get(value.workflow_key)
        elif value.version > 0:
            workflow = self.repository.by_id_and_version(value.bpmn_process_id, value.version)
        else:
            workflow = self.repository.latest(value.bpmn_process_id)

        md_extra = {
            "request_id": command.metadata.request_id,
            "request_stream_id": command.metadata.request_stream_id,
        }
        if workflow is None:
            out.written.append(
                _record(
                    RecordType.COMMAND_REJECTION,
                    value,
                    WI.CREATE,
                    command.key,
                    command.position,
                    {
                        "rejection_type": RejectionType.BAD_VALUE,
                        "rejection_reason": "Workflow is not deployed",
                        **md_extra,
                    },
                )
            )
            out.responses.append(out.written[-1])
            return

        value.workflow_key = workflow.key
        value.version = workflow.version
        value.bpmn_process_id = workflow.id
        value.activity_id = workflow.id
        # batch: CREATED (with request metadata) + ELEMENT_READY
        out.written.append(
            _record(RecordType.EVENT, value.copy(), WI.CREATED, wf_instance_key,
                    command.position, md_extra)
        )
        out.written.append(
            _record(RecordType.EVENT, value.copy(), WI.ELEMENT_READY, wf_instance_key,
                    command.position)
        )
        # index entry is created when the CREATED event is processed

    def _cancel_workflow_instance(self, command: Record, out: ProcessingResult) -> None:
        """Reference CancelWorkflowInstanceProcessor."""
        instance = self.element_instances.get(command.key)
        if instance is None or not instance.can_terminate():
            rejection = _record(
                RecordType.COMMAND_REJECTION,
                command.value,
                WI.CANCEL,
                command.key,
                command.position,
                {
                    "rejection_type": RejectionType.NOT_APPLICABLE,
                    "rejection_reason": "Workflow instance is not running",
                    "request_id": command.metadata.request_id,
                    "request_stream_id": command.metadata.request_stream_id,
                },
            )
            out.written.append(rejection)
            out.responses.append(rejection)
            return
        value = instance.value.copy()
        value.payload = {}
        out.written.append(
            _record(RecordType.EVENT, value.copy(), WI.CANCELING, command.key,
                    command.position,
                    {
                        "request_id": command.metadata.request_id,
                        "request_stream_id": command.metadata.request_stream_id,
                    })
        )
        out.responses.append(out.written[-1])
        out.written.append(
            _record(RecordType.EVENT, value.copy(), WI.ELEMENT_TERMINATING, command.key,
                    command.position)
        )
        instance.state = WI.ELEMENT_TERMINATING

    def _update_payload(self, command: Record, out: ProcessingResult) -> None:
        """Reference UpdatePayloadProcessor."""
        value: WorkflowInstanceRecord = command.value
        instance = self.element_instances.get(value.workflow_instance_key)
        md_extra = {
            "request_id": command.metadata.request_id,
            "request_stream_id": command.metadata.request_stream_id,
        }
        if instance is None:
            rejection = _record(
                RecordType.COMMAND_REJECTION, value, WI.UPDATE_PAYLOAD,
                command.key, command.position,
                {
                    "rejection_type": RejectionType.NOT_APPLICABLE,
                    "rejection_reason": "Workflow instance is not running",
                    **md_extra,
                },
            )
            out.written.append(rejection)
            out.responses.append(rejection)
            return
        instance.value.payload = dict(value.payload)
        event = _record(
            RecordType.EVENT, instance.value.copy(), WI.PAYLOAD_UPDATED,
            command.key, command.position, md_extra,
        )
        out.written.append(event)
        out.responses.append(event)

    # ------------------------------------------------------------------
    # BPMN step dispatch (reference BpmnStepProcessor)
    # ------------------------------------------------------------------
    def _bpmn_step(self, record: Record, intent: WI, out: ProcessingResult) -> None:
        value: WorkflowInstanceRecord = record.value
        workflow = self.repository.by_key.get(value.workflow_key)
        if workflow is None:
            return

        element = workflow.element_by_id(value.activity_id)
        if element is None:
            return

        instance = self.element_instances.get(record.key)
        scope_instance = self.element_instances.get(value.scope_instance_key)

        # reference shallProcessRecord: skip finished instances
        if instance is None and scope_instance is None:
            return
        if not self._step_guard(intent, record, instance, scope_instance):
            return

        # boundary-event arming/disarming rides the host activity's
        # lifecycle events, independent of its bound step (the reference
        # model defines BoundaryEvent but its engine never executes it;
        # the continuation intent is BOUNDARY_EVENT_OCCURRED)
        if element.boundary_events:
            if intent == WI.ELEMENT_ACTIVATED:
                self._arm_boundary_events(record, element, out)
            elif intent in (WI.ELEMENT_COMPLETING, WI.ELEMENT_TERMINATING):
                self._disarm_boundary_events(record, element, out)

        if intent == WI.ELEMENT_TERMINATED and record.key in self._pending_boundary:
            # interrupting boundary: the host terminated on behalf of the
            # trigger — continue the token at the boundary event instead of
            # propagating the termination. If the SCOPE started terminating
            # in between (a cancel raced the boundary), drop the
            # continuation and let normal termination propagation run.
            boundary_id, payload = self._pending_boundary.pop(record.key)
            if scope_instance is not None and scope_instance.state == WI.ELEMENT_ACTIVATED:
                boundary_el = workflow.element_by_id(boundary_id)
                if boundary_el is not None:
                    new_value = value.copy()
                    new_value.activity_id = boundary_el.id
                    new_value.payload = dict(payload)
                    self._write_new_wi_event(
                        out, record, WI.BOUNDARY_EVENT_OCCURRED, new_value
                    )
                return

        step = element.get_step(intent)
        if step == BpmnStep.NONE:
            return

        handler = self._STEP_HANDLERS[step]
        handler(self, record, element, workflow, instance, scope_instance, out)

    def _step_guard(
        self,
        intent: WI,
        record: Record,
        instance: Optional[ElementInstance],
        scope: Optional[ElementInstance],
    ) -> bool:
        """Reference BpmnStepProcessor stepGuards (BpmnStepProcessor.java:127-151)."""
        if intent in (WI.ELEMENT_READY, WI.ELEMENT_ACTIVATED, WI.ELEMENT_COMPLETING):
            return instance is not None and instance.state == intent
        if intent == WI.ELEMENT_COMPLETED:
            return scope is not None and scope.state == WI.ELEMENT_ACTIVATED
        if intent == WI.ELEMENT_TERMINATING:
            return True
        if intent == WI.ELEMENT_TERMINATED:
            # pending interrupting-boundary continuations are processed
            # while the scope stays ACTIVATED (the token moves to the
            # boundary event, the scope does not terminate); when the
            # scope is itself TERMINATING (boundary raced a cancel) the
            # guard passes so normal termination propagation runs —
            # _bpmn_step discards the stale pending entry
            if record.key in self._pending_boundary:
                return scope is not None and scope.state in (
                    WI.ELEMENT_ACTIVATED,
                    WI.ELEMENT_TERMINATING,
                )
            return scope is not None and scope.state == WI.ELEMENT_TERMINATING
        if intent in (
            WI.END_EVENT_OCCURRED,
            WI.GATEWAY_ACTIVATED,
            WI.START_EVENT_OCCURRED,
            WI.SEQUENCE_FLOW_TAKEN,
            WI.BOUNDARY_EVENT_OCCURRED,
        ):
            return scope is not None and scope.state == WI.ELEMENT_ACTIVATED
        return True

    # -- step handlers ----------------------------------------------------
    def _raise_incident(
        self, record: Record, error_type: ErrorType, message: str, out: ProcessingResult
    ) -> None:
        """Reference BpmnStepContext.raiseIncident."""
        value: WorkflowInstanceRecord = record.value
        incident = IncidentRecord(
            error_type=int(error_type),
            error_message=message,
            failure_event_position=record.position,
            bpmn_process_id=value.bpmn_process_id,
            workflow_instance_key=value.workflow_instance_key,
            activity_id=value.activity_id,
            activity_instance_key=record.key,
            payload=dict(value.payload),
        )
        if record.metadata.incident_key < 0:
            out.written.append(
                _record(RecordType.COMMAND, incident, IncidentIntent.CREATE, -1, record.position)
            )
        else:
            out.written.append(
                _record(
                    RecordType.EVENT, incident, IncidentIntent.RESOLVE_FAILED,
                    record.metadata.incident_key, record.position,
                )
            )

    def _h_take_sequence_flow(self, record, element, workflow, instance, scope, out):
        # reference TakeSequenceFlowHandler: exactly one outgoing flow
        flow = element.outgoing[0]
        value = record.value.copy()
        value.activity_id = flow.id
        self._write_new_wi_event(out, record, WI.SEQUENCE_FLOW_TAKEN, value)

    def _h_consume_token(self, record, element, workflow, instance, scope, out):
        # reference ConsumeTokenHandler, extended with token counting for
        # parallel flows: the scope completes when its last token is consumed
        value: WorkflowInstanceRecord = record.value
        scope_value = scope.value
        scope_el = workflow.element_by_id(scope_value.activity_id)
        is_mi = scope_el is not None and scope_el.is_multi_instance
        if is_mi:
            # multi-instance container: iteration-local variables
            # (loopCounter, the input element) must NOT leak into the
            # container payload; per-iteration outputs are collected in
            # loopCounter order instead
            if scope_el.mi_output_collection:
                # keyed by COMPLETION order (log order — deterministic and
                # replay-stable). loopCounter cannot key the collection: a
                # job result replaces the iteration payload (reference
                # semantics), dropping it for some iterations, and a
                # mixed keyspace would let a surviving loopCounter collide
                # with an order-assigned key and silently drop an output
                try:
                    found, extracted = query_json_path(
                        value.payload, scope_el.mi_output_element
                    )
                except ValueError:
                    # a bad output-element path collects null rather than
                    # escaping the engine loop mid-token-consume
                    found, extracted = False, None
                scope.mi_outputs[len(scope.mi_outputs) + 1] = (
                    extracted if found else None
                )
        else:
            scope_value.payload = dict(value.payload)
        scope.active_tokens -= 1
        if scope.active_tokens <= 0:
            if is_mi and scope_el.mi_output_collection:
                payload = dict(scope_value.payload)
                payload[scope_el.mi_output_collection] = [
                    scope.mi_outputs[c] for c in sorted(scope.mi_outputs)
                ]
                scope_value.payload = payload
                scope.mi_outputs = {}
            self._write_wi_followup(out, record, scope.key, WI.ELEMENT_COMPLETING, scope_value)

    def _h_exclusive_split(self, record, element, workflow, instance, scope, out):
        # reference ExclusiveSplitHandler
        value: WorkflowInstanceRecord = record.value
        try:
            taken = None
            for flow in element.outgoing_with_condition:
                if evaluate_condition(flow.condition, value.payload):
                    taken = flow
                    break
            if taken is None:
                taken = element.default_flow
            if taken is not None:
                new_value = value.copy()
                new_value.activity_id = taken.id
                self._write_new_wi_event(out, record, WI.SEQUENCE_FLOW_TAKEN, new_value)
            else:
                self._raise_incident(
                    record,
                    ErrorType.CONDITION_ERROR,
                    "All conditions evaluated to false and no default flow is set.",
                    out,
                )
        except ConditionEvalError as e:
            self._raise_incident(record, ErrorType.CONDITION_ERROR, str(e), out)

    def _h_create_job(self, record, element, workflow, instance, scope, out):
        # reference CreateJobHandler
        value: WorkflowInstanceRecord = record.value
        job = JobRecord(
            type=element.job_type,
            retries=element.job_retries,
            payload=dict(value.payload),
            custom_headers=dict(element.job_headers),
            headers=JobHeaders(
                bpmn_process_id=value.bpmn_process_id,
                workflow_definition_version=value.version,
                workflow_key=value.workflow_key,
                workflow_instance_key=value.workflow_instance_key,
                activity_id=element.id,
                activity_instance_key=record.key,
            ),
        )
        out.written.append(
            _record(RecordType.COMMAND, job, JobIntent.CREATE, -1, record.position)
        )

    def _h_apply_input_mapping(self, record, element, workflow, instance, scope, out):
        # reference InputMappingHandler
        value = record.value.copy()
        try:
            if element.input_mappings:
                value.payload = extract(value.payload, element.input_mappings)
            self._write_wi_followup(out, record, record.key, WI.ELEMENT_ACTIVATED, value)
        except MappingError as e:
            self._raise_incident(record, ErrorType.IO_MAPPING_ERROR, str(e), out)

    def _h_apply_output_mapping(self, record, element, workflow, instance, scope, out):
        # reference OutputMappingHandler
        value = record.value.copy()
        scope_payload = dict(scope.value.payload) if scope is not None else {}
        try:
            if element.output_behavior == OutputBehavior.NONE:
                value.payload = scope_payload
            else:
                if element.output_behavior == OutputBehavior.OVERWRITE:
                    scope_payload = {}
                value.payload = merge(value.payload, scope_payload, element.output_mappings)
            self._write_wi_followup(out, record, record.key, WI.ELEMENT_COMPLETED, value)
        except MappingError as e:
            self._raise_incident(record, ErrorType.IO_MAPPING_ERROR, str(e), out)
            return
        if scope is not None:
            # the scope's payload takes the merged one, so the next
            # element's output merges into what this one added (the
            # payload flows from element to element); a multi-instance
            # container keeps its own, as at a consumed token
            scope_el = workflow.element_by_id(scope.value.activity_id)
            if scope_el is None or not scope_el.is_multi_instance:
                scope.value.payload = dict(value.payload)

    def _h_activate_gateway(self, record, element, workflow, instance, scope, out):
        # reference ActivateGatewayHandler
        value = record.value.copy()
        value.activity_id = element.target.id
        self._write_new_wi_event(out, record, WI.GATEWAY_ACTIVATED, value)

    def _h_start_stateful_element(self, record, element, workflow, instance, scope, out):
        # reference StartStatefulElementHandler
        value = record.value.copy()
        value.activity_id = element.target.id
        self._write_new_wi_event(out, record, WI.ELEMENT_READY, value)

    def _h_trigger_end_event(self, record, element, workflow, instance, scope, out):
        # reference TriggerEndEventHandler
        value = record.value.copy()
        value.activity_id = element.target.id
        self._write_new_wi_event(out, record, WI.END_EVENT_OCCURRED, value)

    def _h_trigger_start_event(self, record, element, workflow, instance, scope, out):
        # reference TriggerStartEventHandler (+ token accounting)
        start_event = element.start_event
        value = record.value.copy()
        value.activity_id = start_event.id
        value.scope_instance_key = record.key
        container = self.element_instances.get(record.key)
        if container is not None:
            container.active_tokens = 1
        self._write_new_wi_event(out, record, WI.START_EVENT_OCCURRED, value)

    def _h_complete_process(self, record, element, workflow, instance, scope, out):
        # reference CompleteProcessHandler
        self._write_wi_followup(out, record, record.key, WI.ELEMENT_COMPLETED, record.value.copy())

    def _h_terminate_contained(self, record, element, workflow, instance, scope, out):
        # reference TerminateContainedElementsHandler (extended: terminate all
        # children, not just the first — multi-token scopes)
        container = instance
        if container is None:
            return
        if not container.children:
            self._write_wi_followup(out, record, record.key, WI.ELEMENT_TERMINATED, record.value.copy())
        else:
            for child in sorted(container.children, key=lambda c: c.key):
                if child.can_terminate():
                    self._write_wi_followup(
                        out, record, child.key, WI.ELEMENT_TERMINATING, child.value.copy()
                    )

    def _h_terminate_job_task(self, record, element, workflow, instance, scope, out):
        # reference TerminateServiceTaskHandler
        if instance is not None and instance.job_key > 0:
            job_state = self.jobs.get(instance.job_key)
            value: WorkflowInstanceRecord = record.value
            job = JobRecord(
                type=job_state.record.type if job_state else "",
                headers=JobHeaders(
                    bpmn_process_id=value.bpmn_process_id,
                    workflow_definition_version=value.version,
                    workflow_instance_key=value.workflow_instance_key,
                    activity_id=value.activity_id,
                    activity_instance_key=instance.key,
                ),
            )
            out.written.append(
                _record(RecordType.COMMAND, job, JobIntent.CANCEL, instance.job_key, record.position)
            )
        self._write_wi_followup(out, record, record.key, WI.ELEMENT_TERMINATED, record.value.copy())

    def _h_terminate_element(self, record, element, workflow, instance, scope, out):
        # reference TerminateElementHandler
        self._write_wi_followup(out, record, record.key, WI.ELEMENT_TERMINATED, record.value.copy())

    def _h_terminate_catch_event(self, record, element, workflow, instance, scope, out):
        # TPU-native: close message subscription / cancel timer, then terminate
        if element.message_name:
            value: WorkflowInstanceRecord = record.value
            try:
                found, corr_value = query_json_path(
                    value.payload, element.correlation_key_path
                )
            except ValueError:
                found, corr_value = False, None
            if found:
                target = self.partition_for_correlation_key(str(corr_value))
                close = MessageSubscriptionRecord(
                    workflow_instance_partition_id=self.partition_id,
                    workflow_instance_key=value.workflow_instance_key,
                    activity_instance_key=record.key,
                    message_name=element.message_name,
                    correlation_key=str(corr_value),
                )
                out.sends.append(
                    (target, _record(RecordType.COMMAND, close, MessageSubscriptionIntent.CLOSE))
                )
        for timer_key, timer in list(self.timers.items()):
            if timer.activity_instance_key == record.key:
                out.written.append(
                    _record(RecordType.COMMAND, timer.record, TimerIntent.CANCEL,
                            timer_key, record.position)
                )
        self._write_wi_followup(out, record, record.key, WI.ELEMENT_TERMINATED, record.value.copy())

    def _h_propagate_termination(self, record, element, workflow, instance, scope, out):
        # reference PropagateTerminationHandler
        if scope is None:
            return
        if not scope.children:
            self._write_wi_followup(out, record, scope.key, WI.ELEMENT_TERMINATED, scope.value.copy())

    def _h_subscribe_to_message(self, record, element, workflow, instance, scope, out):
        # reference SubscribeMessageHandler: extract correlation key, send
        # OpenMessageSubscription to the message partition
        value: WorkflowInstanceRecord = record.value
        try:
            found, corr_value = query_json_path(
                value.payload, element.correlation_key_path
            )
        except ValueError:
            found, corr_value = False, None
        if not found or not isinstance(corr_value, (str, int)):
            self._raise_incident(
                record,
                ErrorType.IO_MAPPING_ERROR,
                f"Failed to extract the correlation-key by '{element.correlation_key_path}'",
                out,
            )
            return
        correlation_key = str(corr_value)
        target = self.partition_for_correlation_key(correlation_key)
        sub = MessageSubscriptionRecord(
            workflow_instance_partition_id=self.partition_id,
            workflow_instance_key=value.workflow_instance_key,
            activity_instance_key=record.key,
            message_name=element.message_name,
            correlation_key=correlation_key,
        )
        out.sends.append(
            (target, _record(RecordType.COMMAND, sub, MessageSubscriptionIntent.OPEN))
        )

    def _h_parallel_split(self, record, element, workflow, instance, scope, out):
        # TPU-native: fork — one SEQUENCE_FLOW_TAKEN per outgoing flow, scope
        # gains (n-1) tokens
        if scope is not None:
            scope.active_tokens += len(element.outgoing) - 1
        for flow in element.outgoing:
            value = record.value.copy()
            value.activity_id = flow.id
            self._write_new_wi_event(out, record, WI.SEQUENCE_FLOW_TAKEN, value)

    def _h_parallel_merge(self, record, element, workflow, instance, scope, out):
        # TPU-native: join — count arrivals per (scope, gateway); activate
        # when all incoming flows have arrived; payloads merge in flow order
        gateway = element.target
        if scope is None:
            return
        arrivals = scope.join_arrivals.setdefault(gateway.index, {})
        flow_order = [f.index for f in gateway.incoming]
        arrivals[element.index] = dict(record.value.payload)
        if len(arrivals) == len(gateway.incoming):
            merged: Dict[str, Any] = {}
            for flow_idx in flow_order:
                merged.update(arrivals[flow_idx])
            scope.active_tokens -= len(gateway.incoming) - 1
            scope.join_arrivals.pop(gateway.index, None)
            value = record.value.copy()
            value.activity_id = gateway.id
            value.payload = merged
            self._write_new_wi_event(out, record, WI.GATEWAY_ACTIVATED, value)

    def _h_multi_instance_split(self, record, element, workflow, instance, scope, out):
        """Parallel multi-instance activation (reference model
        MultiInstanceLoopCharacteristics.java — the reference engine never
        executes it): spawn one body token per item; the container
        completes when the last body token is consumed (token counting,
        the same mechanism as the parallel join). Each iteration's payload
        carries ``loopCounter`` (1-based) and, with an input collection,
        ``input_element`` = collection[i]."""
        value: WorkflowInstanceRecord = record.value
        container = instance
        items = None
        if element.mi_input_collection:
            try:
                found, coll = query_json_path(
                    value.payload, element.mi_input_collection
                )
            except ValueError:
                # malformed path that slipped past deploy validation must
                # become an incident, not wedge the partition drain loop
                found, coll = False, None
            if not found or not isinstance(coll, list):
                self._raise_incident(
                    record,
                    ErrorType.IO_MAPPING_ERROR,
                    "Multi-instance input collection "
                    f"'{element.mi_input_collection}' is not an array in the payload",
                    out,
                )
                return
            items = coll
            n = len(items)
        else:
            n = int(element.mi_cardinality or 0)
        if n <= 0:
            # empty collection: the multi-instance body never runs and the
            # container completes immediately — with an EMPTY output
            # collection, so downstream readers of the variable see []
            done_value = value
            if element.mi_output_collection:
                done_value = value.copy()
                payload = dict(done_value.payload)
                payload[element.mi_output_collection] = []
                done_value.payload = payload
            self._write_wi_followup(
                out, record, record.key, WI.ELEMENT_COMPLETING, done_value
            )
            return
        if container is not None:
            container.active_tokens = n
        start_event = element.start_event
        for i in range(n):
            child_value = value.copy()
            child_value.activity_id = start_event.id
            child_value.scope_instance_key = record.key
            payload = dict(value.payload)
            payload["loopCounter"] = i + 1
            if items is not None and element.mi_input_element:
                payload[element.mi_input_element] = items[i]
            child_value.payload = payload
            self._write_new_wi_event(out, record, WI.START_EVENT_OCCURRED, child_value)

    def _h_create_timer(self, record, element, workflow, instance, scope, out):
        # TPU-native: timer catch event
        # record.timestamp, not clock(): replay must rebuild identical state
        # (reference reprocessing re-reads due dates from logged records)
        due = record.timestamp + int(element.timer_duration_ms or 0)
        timer = TimerRecord(
            workflow_instance_key=record.value.workflow_instance_key,
            activity_instance_key=record.key,
            due_date=due,
            handler_element_id=element.id,
        )
        out.written.append(
            _record(RecordType.COMMAND, timer, TimerIntent.CREATE, -1, record.position)
        )

    def _h_cancel_process(self, record, element, workflow, instance, scope, out):
        pass  # reference BpmnStep.CANCEL_PROCESS is unused in this version

    # -- boundary events (reference model BoundaryEvent.java +
    # cancelActivity; the continuation intent BOUNDARY_EVENT_OCCURRED is a
    # TPU-native extension — the reference engine never executes boundary
    # events) ----------------------------------------------------------------
    def _arm_boundary_events(self, record: Record, element, out: ProcessingResult) -> None:
        """On host ELEMENT_ACTIVATED: start a timer / open a message
        subscription per attached boundary event."""
        value: WorkflowInstanceRecord = record.value
        for boundary in element.boundary_events:
            if boundary.timer_duration_ms is not None:
                due = record.timestamp + int(boundary.timer_duration_ms)
                timer = TimerRecord(
                    workflow_instance_key=value.workflow_instance_key,
                    activity_instance_key=record.key,
                    due_date=due,
                    handler_element_id=boundary.id,
                )
                out.written.append(
                    _record(RecordType.COMMAND, timer, TimerIntent.CREATE, -1, record.position)
                )
            elif boundary.message_name:
                try:
                    found, corr_value = query_json_path(
                        value.payload, boundary.correlation_key_path
                    )
                except ValueError:
                    found, corr_value = False, None
                if not found or not isinstance(corr_value, (str, int)):
                    self._raise_incident(
                        record,
                        ErrorType.IO_MAPPING_ERROR,
                        "Failed to extract the correlation-key by "
                        f"'{boundary.correlation_key_path}'",
                        out,
                    )
                    continue
                correlation_key = str(corr_value)
                target = self.partition_for_correlation_key(correlation_key)
                sub = MessageSubscriptionRecord(
                    workflow_instance_partition_id=self.partition_id,
                    workflow_instance_key=value.workflow_instance_key,
                    activity_instance_key=record.key,
                    message_name=boundary.message_name,
                    correlation_key=correlation_key,
                )
                out.sends.append(
                    (target, _record(RecordType.COMMAND, sub, MessageSubscriptionIntent.OPEN))
                )

    def _disarm_boundary_events(self, record: Record, element, out: ProcessingResult) -> None:
        """On host COMPLETING/TERMINATING: cancel boundary timers and close
        boundary message subscriptions that did not fire."""
        value: WorkflowInstanceRecord = record.value
        for timer_key, timer in list(self.timers.items()):
            if timer.activity_instance_key == record.key:
                out.written.append(
                    _record(RecordType.COMMAND, timer.record, TimerIntent.CANCEL,
                            timer_key, record.position)
                )
        for boundary in element.boundary_events:
            if not boundary.message_name:
                continue
            try:
                found, corr_value = query_json_path(
                    value.payload, boundary.correlation_key_path
                )
            except ValueError:
                continue
            if not found:
                continue
            target = self.partition_for_correlation_key(str(corr_value))
            close = MessageSubscriptionRecord(
                workflow_instance_partition_id=self.partition_id,
                workflow_instance_key=value.workflow_instance_key,
                activity_instance_key=record.key,
                message_name=boundary.message_name,
                correlation_key=str(corr_value),
            )
            out.sends.append(
                (target, _record(RecordType.COMMAND, close, MessageSubscriptionIntent.CLOSE))
            )

    def _fire_boundary_event(
        self,
        record: Record,
        boundary,
        host: ElementInstance,
        payload: Dict,
        out: ProcessingResult,
    ) -> None:
        """A boundary trigger fired while the host activity is active."""
        host_value = host.value
        new_value = host_value.copy()
        new_value.activity_id = boundary.id
        new_value.payload = dict(payload)
        if boundary.cancel_activity:
            # interrupting: terminate the host; the token continues at the
            # boundary event when ELEMENT_TERMINATED processes
            self._pending_boundary[host.key] = (boundary.id, dict(payload))
            self._write_wi_followup(
                out, record, host.key, WI.ELEMENT_TERMINATING, host_value
            )
        else:
            scope = host.parent
            if scope is not None:
                scope.active_tokens += 1
            self._write_new_wi_event(out, record, WI.BOUNDARY_EVENT_OCCURRED, new_value)

    def _boundary_for(self, instance: ElementInstance, message_name: str = "",
                      handler_element_id: str = ""):
        """Resolve a trigger to the host element's attached boundary event
        (by handler element id for timers, by message name for messages).
        Returns (element, boundary) or (None, None)."""
        if instance is None or instance.value is None:
            return None, None
        workflow = self.repository.by_key.get(instance.value.workflow_key)
        if workflow is None:
            return None, None
        element = workflow.element_by_id(instance.value.activity_id)
        if element is None:
            return None, None
        for boundary in element.boundary_events:
            if handler_element_id and boundary.id == handler_element_id:
                return element, boundary
            if message_name and boundary.message_name == message_name:
                return element, boundary
        return element, None

    _STEP_HANDLERS = {
        BpmnStep.TAKE_SEQUENCE_FLOW: _h_take_sequence_flow,
        BpmnStep.CONSUME_TOKEN: _h_consume_token,
        BpmnStep.EXCLUSIVE_SPLIT: _h_exclusive_split,
        BpmnStep.CREATE_JOB: _h_create_job,
        BpmnStep.APPLY_INPUT_MAPPING: _h_apply_input_mapping,
        BpmnStep.APPLY_OUTPUT_MAPPING: _h_apply_output_mapping,
        BpmnStep.ACTIVATE_GATEWAY: _h_activate_gateway,
        BpmnStep.START_STATEFUL_ELEMENT: _h_start_stateful_element,
        BpmnStep.TRIGGER_END_EVENT: _h_trigger_end_event,
        BpmnStep.SUBSCRIBE_TO_INTERMEDIATE_MESSAGE: _h_subscribe_to_message,
        BpmnStep.TRIGGER_START_EVENT: _h_trigger_start_event,
        BpmnStep.COMPLETE_PROCESS: _h_complete_process,
        BpmnStep.TERMINATE_CONTAINED_INSTANCES: _h_terminate_contained,
        BpmnStep.TERMINATE_JOB_TASK: _h_terminate_job_task,
        BpmnStep.TERMINATE_ELEMENT: _h_terminate_element,
        BpmnStep.PROPAGATE_TERMINATION: _h_propagate_termination,
        BpmnStep.CANCEL_PROCESS: _h_cancel_process,
        BpmnStep.PARALLEL_SPLIT: _h_parallel_split,
        BpmnStep.PARALLEL_MERGE: _h_parallel_merge,
        BpmnStep.CREATE_TIMER: _h_create_timer,
        BpmnStep.TERMINATE_CATCH_EVENT: _h_terminate_catch_event,
        BpmnStep.MULTI_INSTANCE_SPLIT: _h_multi_instance_split,
    }

    # ------------------------------------------------------------------
    # job subsystem (reference JobInstanceStreamProcessor)
    # ------------------------------------------------------------------
    def _job_response(self, command: Record, intent: JobIntent, value: JobRecord,
                      out: ProcessingResult, key: int) -> Record:
        event = _record(
            RecordType.EVENT, value.copy(), intent, key, command.position,
            {
                "request_id": command.metadata.request_id,
                "request_stream_id": command.metadata.request_stream_id,
            },
        )
        out.written.append(event)
        if command.metadata.request_id >= 0:
            out.responses.append(event)
        return event

    def _job_rejection(self, command: Record, reason: str, out: ProcessingResult,
                       rejection_type: RejectionType = RejectionType.NOT_APPLICABLE) -> None:
        rejection = _record(
            RecordType.COMMAND_REJECTION, command.value, command.metadata.intent,
            command.key, command.position,
            {
                "rejection_type": rejection_type,
                "rejection_reason": reason,
                "request_id": command.metadata.request_id,
                "request_stream_id": command.metadata.request_stream_id,
            },
        )
        out.written.append(rejection)
        if command.metadata.request_id >= 0:
            out.responses.append(rejection)

    def _process_job_command(self, command: Record, out: ProcessingResult) -> None:
        intent = JobIntent(command.metadata.intent)
        value: JobRecord = command.value
        job = self.jobs.get(command.key)

        if intent == JobIntent.CREATE:
            key = self.job_keys.next_key()
            self.jobs[key] = JobState(state=int(JobIntent.CREATED), record=value.copy())
            self._job_response(command, JobIntent.CREATED, value, out, key)
        elif intent == JobIntent.ACTIVATE:
            # reference ActivateJobProcessor
            if job is not None and job.state in (
                int(JobIntent.CREATED), int(JobIntent.FAILED), int(JobIntent.TIMED_OUT)
            ):
                job.state = int(JobIntent.ACTIVATED)
                job.record = value.copy()
                job.deadline = value.deadline
                event = _record(RecordType.EVENT, value.copy(), JobIntent.ACTIVATED,
                                command.key, command.position)
                out.written.append(event)
                subscriber_key = command.metadata.request_stream_id
                out.pushes.append((subscriber_key, event))
            else:
                self._job_rejection(
                    command, "Job is not in one of these states: CREATED, FAILED, TIMED_OUT", out
                )
                self._return_job_credit(command.metadata.request_stream_id)
        elif intent == JobIntent.COMPLETE:
            if job is not None and job.state in (int(JobIntent.ACTIVATED), int(JobIntent.TIMED_OUT)):
                # merge the (possibly thin) command value onto the stored job
                # record so the COMPLETED event carries full headers — the
                # workflow processor resolves the activity instance from them
                completed = job.record.copy()
                completed.payload = dict(value.payload)
                del self.jobs[command.key]
                self._job_response(command, JobIntent.COMPLETED, completed, out, command.key)
            else:
                self._job_rejection(command, "Job is not in state: ACTIVATED, TIMED_OUT", out)
        elif intent == JobIntent.FAIL:
            if job is not None and job.state == int(JobIntent.ACTIVATED):
                failed = job.record.copy()
                failed.retries = value.retries
                if value.payload:
                    failed.payload = dict(value.payload)
                job.state = int(JobIntent.FAILED)
                job.record = failed.copy()
                self._job_response(command, JobIntent.FAILED, failed, out, command.key)
            else:
                self._job_rejection(command, "Job is not in state ACTIVATED", out)
        elif intent == JobIntent.TIME_OUT:
            if job is not None and job.state == int(JobIntent.ACTIVATED):
                job.state = int(JobIntent.TIMED_OUT)
                self._job_response(command, JobIntent.TIMED_OUT, value, out, command.key)
            else:
                self._job_rejection(command, "Job is not in state ACTIVATED", out)
        elif intent == JobIntent.UPDATE_RETRIES:
            if job is not None and job.state == int(JobIntent.FAILED):
                if value.retries > 0:
                    # respond with the stored job record (the reference client
                    # echoes the full job record in the command; a thin client
                    # may send only retries)
                    job.record.retries = value.retries
                    self._job_response(
                        command, JobIntent.RETRIES_UPDATED, job.record, out, command.key
                    )
                else:
                    self._job_rejection(
                        command, "Retries must be greater than 0", out, RejectionType.BAD_VALUE
                    )
            else:
                self._job_rejection(command, "Job is not in state FAILED", out)
        elif intent == JobIntent.CANCEL:
            if job is not None:
                del self.jobs[command.key]
                self._job_response(command, JobIntent.CANCELED, value, out, command.key)
            else:
                self._job_rejection(command, "Job does not exist", out)

    def _workflow_on_job_event(self, record: Record, out: ProcessingResult) -> None:
        """Reference JobCreatedProcessor / JobCompletedEventProcessor in the
        workflow instance stream processor."""
        intent = JobIntent(record.metadata.intent)
        value: JobRecord = record.value
        activity_instance_key = value.headers.activity_instance_key
        if intent == JobIntent.CREATED:
            if activity_instance_key > 0:
                instance = self.element_instances.get(activity_instance_key)
                if instance is not None:
                    instance.job_key = record.key
        elif intent == JobIntent.COMPLETED:
            instance = self.element_instances.get(activity_instance_key)
            if instance is not None:
                wi_value = instance.value
                wi_value.payload = dict(value.payload)
                self._write_wi_followup(
                    out, record, activity_instance_key, WI.ELEMENT_COMPLETING, wi_value
                )
                instance.job_key = -1

    def _activate_jobs_on_event(self, record: Record, out: ProcessingResult) -> None:
        """Reference ActivateJobStreamProcessor (push with credits)."""
        intent = JobIntent(record.metadata.intent)
        if intent not in (
            JobIntent.CREATED, JobIntent.TIMED_OUT, JobIntent.FAILED, JobIntent.RETRIES_UPDATED
        ):
            return
        value: JobRecord = record.value
        if value.retries <= 0:
            return
        subscription = self._next_job_subscription(value.type)
        if subscription is None:
            # no credits right now: remember the job so a later credit
            # return can assign it (reference: the paused job stream
            # processor resumes from this position)
            self._awaiting_jobs.setdefault(value.type, {})[record.key] = None
            return
        self._awaiting_jobs.get(value.type, {}).pop(record.key, None)
        activated = value.copy()
        activated.deadline = record.timestamp + subscription.timeout
        activated.worker = subscription.worker
        out.written.append(
            _record(
                RecordType.COMMAND, activated, JobIntent.ACTIVATE, record.key, record.position,
                {"request_stream_id": subscription.subscriber_key},
            )
        )
        subscription.credits -= 1

    def _next_job_subscription(self, job_type: str) -> Optional[JobSubscription]:
        """Round-robin over subscriptions with credits (reference
        getNextAvailableSubscription)."""
        matching = [s for s in self.job_subscriptions if s.job_type == job_type]
        if not matching or sum(s.credits for s in matching) <= 0:
            return None
        for i in range(len(matching)):
            sub = matching[(self._job_rr_cursor + i) % len(matching)]
            if sub.credits > 0:
                self._job_rr_cursor = (self._job_rr_cursor + i + 1) % len(matching)
                return sub
        return None

    def _return_job_credit(self, subscriber_key: int) -> None:
        for sub in self.job_subscriptions:
            if sub.subscriber_key == subscriber_key:
                sub.credits += 1
                return

    def backlog_activations(self) -> List[Record]:
        """ACTIVATE commands pairing available credits with jobs that
        became activatable during a credit drought (``_awaiting_jobs``).
        The broker calls this on credit return and from the periodic
        tick, appending the returned commands to the partition log —
        without it, any job created while all matching subscriptions were
        out of credits is stranded forever (round-5 serving-path finding:
        a 10k-instance run converged at ~34% because returned credits
        never revisited the backlog)."""
        out: List[Record] = []
        if self._dirty_families is not None and self._awaiting_jobs:
            # drains the awaiting index and stamps activation deadlines
            self._dirty_families.add("h/jobs")
        activatable = (
            int(JobIntent.CREATED), int(JobIntent.TIMED_OUT),
            int(JobIntent.FAILED), int(JobIntent.RETRIES_UPDATED),
        )
        for job_type in list(self._awaiting_jobs):
            keys = self._awaiting_jobs.get(job_type) or {}
            while keys:
                key = next(iter(keys))
                job = self.jobs.get(key)
                if (
                    job is None
                    or job.state not in activatable
                    or job.record.retries <= 0
                ):
                    keys.pop(key, None)  # stale: finished/failed meanwhile
                    continue
                subscription = self._next_job_subscription(job_type)
                if subscription is None:
                    break  # credits exhausted; keep the rest queued
                keys.pop(key, None)
                activated = job.record.copy()
                activated.deadline = self.clock() + subscription.timeout
                activated.worker = subscription.worker
                subscription.credits -= 1
                out.append(
                    _record(
                        RecordType.COMMAND, activated, JobIntent.ACTIVATE,
                        key, -1,
                        {"request_stream_id": subscription.subscriber_key},
                    )
                )
            if not keys:
                self._awaiting_jobs.pop(job_type, None)
        return out

    # -- host API: subscriptions + deadline checks ------------------------
    def add_job_subscription(self, subscription: JobSubscription) -> List[Record]:
        """Register a worker subscription and return ACTIVATE commands for the
        backlog of already-created matching jobs.

        Reference: ActivateJobStreamProcessor is installed on first
        subscription and reads the log from the start, so pre-existing
        CREATED (or failed-with-retries / timed-out) jobs get assigned too.
        The returned commands must be appended to the partition log.
        Idempotent per subscriber key: a re-subscribe (client recovering
        from a leader change) replaces the previous registration."""
        self.remove_job_subscription(subscription.subscriber_key)
        self.job_subscriptions.append(subscription)
        backlog = []
        activatable = (
            int(JobIntent.CREATED),
            int(JobIntent.TIMED_OUT),
            int(JobIntent.FAILED),
            int(JobIntent.RETRIES_UPDATED),
        )
        for key, job in sorted(self.jobs.items()):
            if subscription.credits <= 0:
                break
            if job.state not in activatable:
                continue
            if job.record.type != subscription.job_type or job.record.retries <= 0:
                continue
            activated = job.record.copy()
            activated.deadline = self.clock() + subscription.timeout
            activated.worker = subscription.worker
            backlog.append(
                _record(
                    RecordType.COMMAND, activated, JobIntent.ACTIVATE, key, -1,
                    {"request_stream_id": subscription.subscriber_key},
                )
            )
            subscription.credits -= 1
        return backlog

    def remove_job_subscription(self, subscriber_key: int) -> None:
        self.job_subscriptions = [
            s for s in self.job_subscriptions if s.subscriber_key != subscriber_key
        ]

    def increase_job_credits(self, subscriber_key: int, credits: int) -> None:
        for sub in self.job_subscriptions:
            if sub.subscriber_key == subscriber_key:
                sub.credits += credits

    def check_job_deadlines(self) -> List[Record]:
        """Reference JobTimeOutStreamProcessor: TIME_OUT commands for expired
        activated jobs; returned commands must be appended to the log."""
        now = self.clock()
        # filter THEN sort: the sweep runs every broker tick over the
        # whole table — sorting only the due entries keeps the idle tick
        # O(n) with no allocation instead of an O(n log n) sort of
        # thousands of in-flight jobs (output order unchanged: due keys
        # ascending)
        activated = int(JobIntent.ACTIVATED)
        due = [
            (key, job) for key, job in self.jobs.items()
            if job.state == activated and 0 <= job.deadline <= now
        ]
        return [
            _record(RecordType.COMMAND, job.record.copy(), JobIntent.TIME_OUT, key)
            for key, job in sorted(due)
        ]

    def check_timer_deadlines(self) -> List[Record]:
        """TPU-native timer firing: TRIGGER commands for due timers."""
        now = self.clock()
        due = [
            (key, timer) for key, timer in self.timers.items()
            if timer.due_date <= now
        ]
        return [
            _record(RecordType.COMMAND, timer.record.copy(), TimerIntent.TRIGGER, key)
            for key, timer in sorted(due)
        ]

    def check_message_ttls(self) -> List[Record]:
        """Reference MessageTimeToLiveChecker: DELETE commands for expired
        messages."""
        now = self.clock()
        due = [
            (key, message) for key, message in self.messages.items()
            if message.deadline <= now
        ]
        return [
            _record(
                RecordType.COMMAND,
                MessageRecord(
                    name=message.name,
                    correlation_key=message.correlation_key,
                    time_to_live=message.time_to_live,
                    payload=dict(message.payload),
                    message_id=message.message_id,
                ),
                MessageIntent.DELETE,
                key,
            )
            for key, message in sorted(due)
        ]

    # ------------------------------------------------------------------
    # incident subsystem (reference IncidentStreamProcessor)
    # ------------------------------------------------------------------
    def _process_incident(self, record: Record, out: ProcessingResult) -> None:
        intent = IncidentIntent(record.metadata.intent)
        rt = record.metadata.record_type
        value: IncidentRecord = record.value

        if rt == RecordType.COMMAND and intent == IncidentIntent.CREATE:
            is_job_incident = value.job_key > 0
            if is_job_incident and self.incident_by_failed_job.get(value.job_key, -1) != -2:
                self._job_rejection(record, "Job is not failed", out)
                return
            key = self.incident_keys.next_key()
            created = _record(RecordType.EVENT, value.copy(), IncidentIntent.CREATED,
                              key, record.position)
            out.written.append(created)
            if is_job_incident:
                self.incident_by_failed_job[value.job_key] = key
            else:
                self.incident_by_activity[value.activity_instance_key] = key
            self.incidents[key] = IncidentState(
                state=INCIDENT_CREATED,
                incident_event_position=record.position,
                failure_event_position=value.failure_event_position,
            )
            self.incident_records[key] = value.copy()
        elif rt == RecordType.COMMAND and intent == IncidentIntent.RESOLVE:
            incident = self.incidents.get(record.key)
            if incident is not None and incident.state == INCIDENT_CREATED:
                failure = self.records_by_position.get(incident.failure_event_position)
                if failure is not None:
                    new_value = failure.value.copy()
                    new_value.payload = dict(value.payload)
                    self._write_wi_followup(
                        out, record, failure.key, WI(failure.metadata.intent), new_value,
                        {"incident_key": record.key},
                    )
                    incident.state = INCIDENT_RESOLVING
            else:
                self._job_rejection(record, "Incident is not in state CREATED", out)
        elif rt == RecordType.EVENT and intent == IncidentIntent.RESOLVE_FAILED:
            incident = self.incidents.get(record.key)
            if incident is not None and incident.state == INCIDENT_RESOLVING:
                incident.state = INCIDENT_CREATED
        elif rt == RecordType.COMMAND and intent == IncidentIntent.DELETE:
            incident = self.incidents.pop(record.key, None)
            if incident is not None:
                prior = self.incident_records.pop(record.key, None)
                out.written.append(
                    _record(RecordType.EVENT, prior or value, IncidentIntent.DELETED,
                            record.key, record.position)
                )
            else:
                self._job_rejection(record, "Incident does not exist", out)

    def _incident_on_workflow_record(self, record: Record, out: ProcessingResult) -> None:
        if record.metadata.record_type != RecordType.EVENT:
            return
        intent = WI(record.metadata.intent)
        # ActivityRewrittenProcessor: remember re-written failure events
        if intent in (WI.ELEMENT_READY, WI.GATEWAY_ACTIVATED, WI.ELEMENT_COMPLETING):
            if record.metadata.incident_key > 0:
                self.resolving_events[record.position] = record.metadata.incident_key
        # PayloadUpdatedProcessor: trigger RESOLVE
        if intent == WI.PAYLOAD_UPDATED:
            incident_key = self.incident_by_activity.get(record.key, -1)
            if incident_key > 0 and self.incidents.get(incident_key, None) is not None \
                    and self.incidents[incident_key].state == INCIDENT_CREATED:
                resolve_value = IncidentRecord(
                    workflow_instance_key=record.value.workflow_instance_key,
                    activity_instance_key=record.key,
                    payload=dict(record.value.payload),
                )
                out.written.append(
                    _record(RecordType.COMMAND, resolve_value, IncidentIntent.RESOLVE,
                            incident_key, record.position)
                )
        # ActivityIncidentResolvedProcessor: resolution completes on the next
        # lifecycle event produced from the re-written failure event
        if intent in (
            WI.ELEMENT_ACTIVATED, WI.SEQUENCE_FLOW_TAKEN, WI.ELEMENT_COMPLETED,
        ):
            incident_key = self.resolving_events.get(record.source_record_position, -1)
            if incident_key > 0:
                incident = self.incidents.get(incident_key)
                if incident is not None and incident.state == INCIDENT_RESOLVING:
                    prior = self.incident_records.get(incident_key)
                    out.written.append(
                        _record(RecordType.EVENT, prior, IncidentIntent.RESOLVED,
                                incident_key, record.position)
                    )
                    self.incidents.pop(incident_key, None)
                    if prior is not None:
                        self.incident_by_activity.pop(prior.activity_instance_key, None)
                    self.resolving_events.pop(record.source_record_position, None)
        # ActivityTerminatedProcessor: delete incidents of terminated elements
        if intent == WI.ELEMENT_TERMINATED:
            incident_key = self.incident_by_activity.pop(record.key, -1)
            if incident_key > 0:
                incident = self.incidents.get(incident_key)
                if incident is not None and incident.state in (
                    INCIDENT_CREATED, INCIDENT_RESOLVING
                ):
                    incident.state = INCIDENT_DELETING
                    out.written.append(
                        _record(RecordType.COMMAND, IncidentRecord(), IncidentIntent.DELETE,
                                incident_key, record.position)
                    )

    def _incident_on_job_event(self, record: Record, out: ProcessingResult) -> None:
        intent = JobIntent(record.metadata.intent)
        value: JobRecord = record.value
        if intent == JobIntent.FAILED and value.retries <= 0:
            # reference JobFailedProcessor
            headers = value.headers
            incident = IncidentRecord(
                error_type=int(ErrorType.JOB_NO_RETRIES),
                error_message="No more retries left.",
                failure_event_position=record.position,
                bpmn_process_id=headers.bpmn_process_id,
                workflow_instance_key=headers.workflow_instance_key,
                activity_id=headers.activity_id,
                activity_instance_key=headers.activity_instance_key,
                job_key=record.key,
                payload=dict(value.payload),
            )
            self.incident_by_failed_job[record.key] = -2  # NON_PERSISTENT_INCIDENT
            if record.metadata.incident_key < 0:
                out.written.append(
                    _record(RecordType.COMMAND, incident, IncidentIntent.CREATE, -1, record.position)
                )
            else:
                out.written.append(
                    _record(RecordType.EVENT, incident, IncidentIntent.RESOLVE_FAILED,
                            record.metadata.incident_key, record.position)
                )
        elif intent in (JobIntent.RETRIES_UPDATED, JobIntent.CANCELED):
            # reference JobIncidentResolvedProcessor
            incident_key = self.incident_by_failed_job.pop(record.key, -1)
            if incident_key > 0:
                incident = self.incidents.get(incident_key)
                if incident is not None and incident.state == INCIDENT_CREATED:
                    if intent == JobIntent.RETRIES_UPDATED:
                        # re-activate by re-writing the failure event: the job
                        # goes back to the activation pool
                        prior = self.incident_records.get(incident_key)
                        out.written.append(
                            _record(RecordType.EVENT, prior, IncidentIntent.RESOLVED,
                                    incident_key, record.position)
                        )
                    else:
                        prior = self.incident_records.get(incident_key)
                        out.written.append(
                            _record(RecordType.COMMAND, prior or IncidentRecord(),
                                    IncidentIntent.DELETE, incident_key, record.position)
                        )
                    self.incidents.pop(incident_key, None)
                    self.incident_records.pop(incident_key, None)

    # ------------------------------------------------------------------
    # message subsystem (reference subscription/message/processor/*)
    # ------------------------------------------------------------------
    def _process_message_command(self, record: Record, out: ProcessingResult) -> None:
        intent = MessageIntent(record.metadata.intent)
        value: MessageRecord = record.value
        if intent == MessageIntent.PUBLISH:
            if value.message_id and any(
                m.name == value.name
                and m.correlation_key == value.correlation_key
                and m.message_id == value.message_id
                for m in self.messages.values()
            ):
                reason = f"message with id '{value.message_id}' is already published"
                self._job_rejection(record, reason, out, RejectionType.BAD_VALUE)
                return
            key = self.wf_keys.next_key()
            published = _record(
                RecordType.EVENT, value.copy(), MessageIntent.PUBLISHED, key, record.position,
                {
                    "request_id": record.metadata.request_id,
                    "request_stream_id": record.metadata.request_stream_id,
                },
            )
            out.written.append(published)
            if record.metadata.request_id >= 0:
                out.responses.append(published)
            # correlate to open subscriptions
            for sub in self.message_subscriptions:
                if sub.message_name == value.name and sub.correlation_key == value.correlation_key:
                    out.sends.append(
                        (
                            sub.workflow_instance_partition_id,
                            _record(
                                RecordType.COMMAND,
                                WorkflowInstanceSubscriptionRecord(
                                    workflow_instance_key=sub.workflow_instance_key,
                                    activity_instance_key=sub.activity_instance_key,
                                    message_name=value.name,
                                    payload=dict(value.payload),
                                    message_partition_id=self.partition_id,
                                    correlation_key=sub.correlation_key,
                                ),
                                WorkflowInstanceSubscriptionIntent.CORRELATE,
                            ),
                        )
                    )
            if value.time_to_live > 0:
                self.messages[key] = StoredMessage(
                    key=key,
                    name=value.name,
                    correlation_key=value.correlation_key,
                    time_to_live=value.time_to_live,
                    payload=dict(value.payload),
                    message_id=value.message_id,
                    deadline=record.timestamp + value.time_to_live,
                )
            else:
                out.written.append(
                    _record(RecordType.EVENT, value.copy(), MessageIntent.DELETED,
                            key, record.position)
                )
        elif intent == MessageIntent.DELETE:
            if record.key in self.messages:
                del self.messages[record.key]
                out.written.append(
                    _record(RecordType.EVENT, value.copy(), MessageIntent.DELETED,
                            record.key, record.position)
                )

    def _process_message_subscription(self, record: Record, out: ProcessingResult) -> None:
        intent = MessageSubscriptionIntent(record.metadata.intent)
        value: MessageSubscriptionRecord = record.value
        if intent == MessageSubscriptionIntent.OPEN:
            # reference OpenMessageSubscriptionProcessor
            key = self.wf_keys.next_key()
            out.written.append(
                _record(RecordType.EVENT, value.copy(), MessageSubscriptionIntent.OPENED,
                        key, record.position)
            )
            self.message_subscriptions.append(
                StoredSubscription(
                    message_name=value.message_name,
                    correlation_key=value.correlation_key,
                    workflow_instance_partition_id=value.workflow_instance_partition_id,
                    workflow_instance_key=value.workflow_instance_key,
                    activity_instance_key=value.activity_instance_key,
                )
            )
            for message in sorted(self.messages.values(), key=lambda m: m.key):
                if message.name == value.message_name and message.correlation_key == value.correlation_key:
                    out.sends.append(
                        (
                            value.workflow_instance_partition_id,
                            _record(
                                RecordType.COMMAND,
                                WorkflowInstanceSubscriptionRecord(
                                    workflow_instance_key=value.workflow_instance_key,
                                    activity_instance_key=value.activity_instance_key,
                                    message_name=value.message_name,
                                    payload=dict(message.payload),
                                    message_partition_id=self.partition_id,
                                    correlation_key=value.correlation_key,
                                ),
                                WorkflowInstanceSubscriptionIntent.CORRELATE,
                            ),
                        )
                    )
                    break
        elif intent == MessageSubscriptionIntent.CLOSE:
            before = len(self.message_subscriptions)
            self.message_subscriptions = [
                s
                for s in self.message_subscriptions
                if not (
                    s.activity_instance_key == value.activity_instance_key
                    and s.workflow_instance_key == value.workflow_instance_key
                    # name-scoped: an activity instance holds one
                    # subscription per message (own catch + message
                    # boundaries); each CLOSE names the one it consumes
                    and (
                        not value.message_name
                        or s.message_name == value.message_name
                    )
                )
            ]
            if len(self.message_subscriptions) != before:
                out.written.append(
                    _record(RecordType.EVENT, value.copy(), MessageSubscriptionIntent.CLOSED,
                            record.key, record.position)
                )

    def _process_wi_subscription(self, record: Record, out: ProcessingResult) -> None:
        """Reference CorrelateWorkflowInstanceSubscription."""
        value: WorkflowInstanceSubscriptionRecord = record.value
        instance = self.element_instances.get(value.activity_instance_key)
        if instance is None:
            self._job_rejection(record, "activity is not active anymore", out)
            return
        out.written.append(
            _record(RecordType.EVENT, value.copy(),
                    WorkflowInstanceSubscriptionIntent.CORRELATED,
                    record.key, record.position)
        )
        _, boundary = self._boundary_for(instance, message_name=value.message_name)
        if boundary is not None:
            self._fire_boundary_event(
                record, boundary, instance, dict(value.payload), out
            )
            if not boundary.cancel_activity:
                # non-interrupting: the subscription stays open so the
                # boundary can fire again for further messages
                return
        else:
            wi_value = instance.value
            wi_value.payload = dict(value.payload)
            self._write_wi_followup(
                out, record, value.activity_instance_key, WI.ELEMENT_COMPLETING, wi_value
            )
        # close the now-consumed subscription on the message partition (the
        # reference leaks it in this version; see MessageSubscriptionIntent)
        if value.message_partition_id >= 0:
            close = MessageSubscriptionRecord(
                workflow_instance_partition_id=self.partition_id,
                workflow_instance_key=value.workflow_instance_key,
                activity_instance_key=value.activity_instance_key,
                message_name=value.message_name,
                correlation_key=value.correlation_key,
            )
            out.sends.append(
                (
                    value.message_partition_id,
                    _record(RecordType.COMMAND, close, MessageSubscriptionIntent.CLOSE),
                )
            )

    # ------------------------------------------------------------------
    # timers (TPU-native)
    # ------------------------------------------------------------------
    def _process_timer(self, record: Record, out: ProcessingResult) -> None:
        intent = TimerIntent(record.metadata.intent)
        value: TimerRecord = record.value
        if intent == TimerIntent.CREATE:
            key = self.wf_keys.next_key()
            self.timers[key] = TimerState(
                due_date=value.due_date,
                activity_instance_key=value.activity_instance_key,
                record=value.copy(),
            )
            out.written.append(
                _record(RecordType.EVENT, value.copy(), TimerIntent.CREATED, key, record.position)
            )
        elif intent == TimerIntent.TRIGGER:
            timer = self.timers.pop(record.key, None)
            if timer is None:
                self._job_rejection(record, "timer does not exist", out)
                return
            out.written.append(
                _record(RecordType.EVENT, value.copy(), TimerIntent.TRIGGERED,
                        record.key, record.position)
            )
            instance = self.element_instances.get(value.activity_instance_key)
            if instance is not None and instance.state == WI.ELEMENT_ACTIVATED:
                _, boundary = self._boundary_for(
                    instance, handler_element_id=value.handler_element_id
                )
                if boundary is not None:
                    self._fire_boundary_event(
                        record, boundary, instance,
                        dict(instance.value.payload), out,
                    )
                else:
                    self._write_wi_followup(
                        out, record, instance.key, WI.ELEMENT_COMPLETING, instance.value
                    )
        elif intent == TimerIntent.CANCEL:
            timer = self.timers.pop(record.key, None)
            if timer is not None:
                out.written.append(
                    _record(RecordType.EVENT, value.copy(), TimerIntent.CANCELED,
                            record.key, record.position)
                )


def _correlation_hash(key: str) -> int:
    """Deterministic correlation-key hash (reference uses String.hashCode-style
    routing in SubscriptionCommandSender; any stable hash works as long as
    every node agrees)."""
    h = 0
    for ch in key:
        h = (h * 31 + ord(ch)) & 0x7FFFFFFF
    return h


