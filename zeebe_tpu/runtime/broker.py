"""Single-process broker: partitions + processing loop + routing.

Reference parity: the broker assembles per-partition log streams and stream
processors (``PartitionInstallService``), commands enter via the client API
handler (``ClientApiMessageHandler``: validate + write COMMAND with request
metadata), processors run the StreamProcessorController loop
(read committed → process → write follow-ups → side effects), and
cross-partition subscription commands travel over the subscription transport
(``SubscriptionApiCommandMessageHandler``).

Here the loop is explicit (`run_until_idle`) and single-threaded —
determinism is the point: the same committed log always replays to the same
state. The TPU engine plugs in as an alternative partition processor.
"""

from __future__ import annotations

import os
import tempfile
from typing import Callable, Dict, List, Optional, Tuple

from zeebe_tpu.engine.interpreter import PartitionEngine, WorkflowRepository
from zeebe_tpu.log import LogStream, SegmentedLogStorage
from zeebe_tpu.protocol.columnar import as_log_batch
from zeebe_tpu.log.snapshot import SnapshotController, SnapshotMetadata, SnapshotStorage
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.protocol.intents import SubscriberIntent, SubscriptionIntent
from zeebe_tpu.protocol.records import Record, stamp_source_positions
from zeebe_tpu.runtime.clock import SystemClock
from zeebe_tpu import tracing


class Partition:
    """A partition: log stream + stream processor + reader position."""

    def __init__(
        self,
        partition_id: int,
        log: LogStream,
        engine: PartitionEngine,
        snapshots: Optional[SnapshotController] = None,
    ):
        self.partition_id = partition_id
        self.log = log
        self.engine = engine
        self.snapshots = snapshots
        self.next_read_position = 0
        self.term = 0  # raft term once replicated; 0 in single-writer mode
        self.exporter_director = None  # set when exporters are configured

    def has_backlog(self) -> bool:
        return self.next_read_position <= self.log.commit_position


class TopicSubscriptionHandle:
    """Per-subscriber push stream (reference TopicSubscriptionPushProcessor):
    a read-only cursor over the partition's committed records with
    credit-bound delivery; acks persist progress as records in the log."""

    def __init__(self, broker, partition_id, name, handler, subscriber_key, cursor, credits):
        self.broker = broker
        self.partition_id = partition_id
        self.name = name
        self.handler = handler
        self.subscriber_key = subscriber_key
        self.cursor = cursor
        self.capacity = credits
        self._unacked: List[int] = []
        self.closed = False

    def pump(self) -> bool:
        """Push committed records up to the credit limit. Returns True if
        anything was delivered."""
        if self.closed:
            return False
        partition = self.broker.partitions[self.partition_id]
        pushed = False
        while len(self._unacked) < self.capacity:
            reader = partition.log.reader(self.cursor)
            batch = reader.read_committed()
            if not batch:
                break
            advanced = False
            for record in batch:
                if len(self._unacked) >= self.capacity:
                    break
                self.cursor = record.position + 1
                advanced = True
                # subscription/exporter-admin records are not re-delivered:
                # pushing them would make every ack generate further pushes
                if record.metadata.value_type in (
                    ValueType.SUBSCRIBER, ValueType.SUBSCRIPTION,
                    ValueType.EXPORTER,
                ):
                    continue
                self._unacked.append(record.position)
                self.handler(self.partition_id, record)
                pushed = True
            if not advanced:
                break
        return pushed

    def ack(self, position: int) -> None:
        """Acknowledge progress up to ``position`` (persisted in the log;
        restart/reopen resumes after it) and free credits."""
        from zeebe_tpu.protocol.records import TopicSubscriptionRecord

        self.broker.write_command(
            self.partition_id,
            TopicSubscriptionRecord(name=self.name, ack_position=position),
            SubscriptionIntent.ACKNOWLEDGE,
            key=self.subscriber_key,
            with_response=False,
        )
        self._unacked = [p for p in self._unacked if p > position]

    def close(self) -> None:
        self.closed = True
        if self in self.broker._topic_subscriptions:
            self.broker._topic_subscriptions.remove(self)


def _entry_position(entry) -> int:
    """Log position of a tail entry without materializing a lazy ref."""
    if type(entry) is tuple:
        return entry[0].col("position")[entry[1]]
    return entry.position


def _entry_record(entry):
    """The entry as a real ``Record`` (materializes lazy refs — only the
    record-listener tap pays this)."""
    if type(entry) is tuple:
        return entry[0].row(entry[1])
    return entry


class _BrokerFeed:
    """In-process partition → scheduler feed. Dispatch is synchronous
    (``engine.process_wave``) and applies PER RECORD in cursor order, so
    each partition's log bytes are independent of how the shared waves
    were packed — bit-identical to the per-partition drain."""

    def __init__(self, broker: "Broker", partition: Partition):
        self.broker = broker
        self.partition = partition
        self.partition_id = partition.partition_id
        self.wave_phases = None

    @property
    def device_index(self) -> int:
        """Mesh device of this partition's engine (per-device wave
        metrics; -1 = unplaced/host engine)."""
        return getattr(self.partition.engine, "device_index", -1)

    @property
    def device_indices(self):
        """Span of a sharded-state engine (every plan index its wave
        computes on); empty for single-device engines."""
        return tuple(
            getattr(self.partition.engine, "device_indices", ()) or ()
        )

    @property
    def shard_fill(self):
        """Per-shard staged-row counts of the engine's last dispatched
        wave (sharded-state v2 fill accounting); empty otherwise."""
        return tuple(
            getattr(self.partition.engine, "last_shard_fill", ()) or ()
        )

    def backlog(self) -> int:
        p = self.partition
        return max(0, p.log.commit_position - p.next_read_position + 1)

    def take(self, limit: int):
        p = self.partition
        view = p.log.committed_view(p.next_read_position, limit)
        if not len(view):
            return []
        positions = view.positions()
        p.next_read_position = positions[-1] + 1
        tracer = tracing.TRACER
        if tracer is not None and tracer.by_position:
            tracer.stamp_positions(
                self.partition_id, positions, tracing.FEED_TAKE
            )
        return view

    def dispatch(self, records):
        import time as _time

        t0 = _time.perf_counter()
        p = self.partition
        results = p.engine.process_wave(records)
        entries = (
            records.entries() if hasattr(records, "entries") else records
        )
        for entry, result in zip(entries, results):
            self.broker._apply_result(p, entry, result)
        host_s, device_s = getattr(p.engine, "last_wave_seconds", (None, 0.0))
        if host_s is None:
            host_s, device_s = _time.perf_counter() - t0, 0.0
        # the device engine's phases of this wave; the scheduler reads them
        # right after this call, as it does shard_fill
        self.wave_phases = getattr(p.engine, "last_wave_phases", None)
        return None, host_s, device_s

    def collect(self, pending):  # synchronous dispatch: nothing pending
        return 0.0, 0.0

    def rewind(self, position: int) -> None:
        if position >= 0:
            p = self.partition
            p.next_read_position = min(p.next_read_position, position)

    def tick(self) -> None:  # Broker.tick drives sweeps explicitly
        pass


class Broker:
    """In-process broker (reference: EmbeddedBrokerRule-style single JVM)."""

    def __init__(
        self,
        num_partitions: int = 1,
        data_dir: Optional[str] = None,
        clock: Optional[Callable[[], int]] = None,
        engine_factory=None,
        exporters=None,
    ):
        """``exporters``: optional list of ``ExporterCfg`` entries and/or
        ``(id, Exporter)`` pairs; each partition gets its own director
        (cfg entries build a fresh instance per partition, instance pairs
        are shared — fine for the default single partition)."""
        self.clock = clock or SystemClock()
        self.data_dir = data_dir or tempfile.mkdtemp(prefix="zeebe-tpu-")
        self.repository = WorkflowRepository()
        self.partitions: List[Partition] = []
        import random

        # request ids stay sequential from 0: they are LOG-VISIBLE
        # metadata, and the wave/mesh parity suites pin two Brokers'
        # logs byte-identical. The process-global tracer, however,
        # indexes live spans by request id — several in-process Brokers
        # would collide in by_request and stamp or finish each other's
        # spans — so tracer keys get a per-incarnation random namespace
        # added on top (the log bytes never see it)
        self._next_request_id = 0
        self._trace_request_ns = random.getrandbits(47) << 20
        self._responses: Dict[int, Record] = {}
        self._push_listeners: Dict[int, Callable[[Record], None]] = {}
        self._record_listeners: List[Callable[[int, Record], None]] = []
        self._topic_subscriptions: List[TopicSubscriptionHandle] = []
        self._rr_partition = 0
        self._exporter_specs = list(exporters or [])
        # mesh frame exchange (scheduler/placement.MeshExchange): when the
        # engine factory placed partitions on devices, cross-partition
        # sends between device-resident partitions ride the all_to_all
        # exchange. The single-writer broker flushes IMMEDIATELY per send,
        # so the destination log is byte-identical to the direct append
        # (tests pin it). None = direct append (the default).
        self.mesh_exchange = None
        # shared-wave drain (zeebe_tpu/scheduler): the SAME scheduler the
        # cluster broker runs, so tier-1 covers its packing/dispatch path;
        # False restores the per-partition baseline the A/B compares to
        self.use_scheduler = True
        self._scheduler = None
        # record-lifecycle tracing: reuse (or install) the process-wide
        # span tracer — stamp sites read the tracing.TRACER global, and
        # tests drive sampling via tracing.install()
        tracing.ensure_tracer()
        from zeebe_tpu.tracing.recorder import record_event

        # a boot marker anchors every flight-recorder dump: restarts are
        # the first thing a post-mortem looks for
        record_event(
            "broker", "in-process broker started",
            partitions=num_partitions, data_dir=self.data_dir,
        )

        factory = engine_factory or (
            lambda pid: PartitionEngine(
                partition_id=pid,
                num_partitions=num_partitions,
                repository=self.repository,
                clock=self.clock,
            )
        )
        for pid in range(num_partitions):
            pdir = os.path.join(self.data_dir, f"partition-{pid}")
            storage = SegmentedLogStorage(pdir)
            log = LogStream(storage, partition_id=pid, clock=self.clock)
            snapshots = SnapshotController(
                SnapshotStorage(os.path.join(pdir, "snapshots"))
            )
            self.partitions.append(Partition(pid, log, factory(pid), snapshots))
        self._recover_partitions()
        self._open_exporters()

    # -- recovery: snapshot + replay (reference StreamProcessorController
    # recovery :156-211 then reprocessing :213-279) -------------------------
    def _recover_partitions(self) -> None:  # noqa: D401
        """Restore each partition's newest valid snapshot, then replay the
        committed records after it to rebuild state — without re-executing
        side effects (no appends, responses, sends, or pushes).

        Partitions replay in id order: deployments commit on their partition
        before instance commands causally follow on others (the reference's
        system-partition-first ordering)."""
        boundaries = {}
        for partition in self.partitions:
            # position-based re-reads (incident resolution, reference
            # TypedStreamReader) serve from the LOG behind the engine's
            # hot cache window — no spill copies, no cache pre-fill
            cache = getattr(partition.engine, "records_by_position", None)
            log_backed = hasattr(cache, "set_log_lookup")
            if log_backed:
                cache.set_log_lookup(partition.log.record_at)
            state, meta = partition.snapshots.recover(partition.log.next_position - 1)
            if state is not None:
                partition.engine.restore_state(state)
                partition.next_read_position = meta.last_processed_position + 1
            # single pass over the log to find the replay boundary
            last_source = -1
            for record in partition.log.reader(0):
                if not log_backed:
                    partition.engine.records_by_position[record.position] = record
                if record.source_record_position > last_source:
                    last_source = record.source_record_position
            boundaries[partition.partition_id] = last_source
        for partition in self.partitions:
            self._replay(partition, boundaries[partition.partition_id])

    def _open_exporters(self) -> None:
        """One director per partition, resumed at the engine state's
        recovered acked positions (reference ExporterDirectorService:
        installed next to the stream processor). Synchronous mode: the
        ``run_until_idle`` loop pumps directors to quiescence."""
        from zeebe_tpu.exporter.director import (
            fold_tail_acks,
            remove_stale_positions,
        )

        if not self._exporter_specs:
            # even with NO exporters configured the recovered positions of
            # previously configured ones must be swept (REMOVE), or the
            # last-removed exporter's stale entry pins the compaction
            # floor forever
            for partition in self.partitions:
                stale = remove_stale_positions(
                    fold_tail_acks(
                        partition.engine.exporter_positions,
                        partition.log,
                        partition.next_read_position,
                    ),
                    (),
                )
                if stale:
                    partition.log.append(stale)
            return
        from zeebe_tpu.exporter import ExporterDirector, build_exporter

        ids = [
            spec[0] if isinstance(spec, tuple) else spec.id
            for spec in self._exporter_specs
        ]
        if len(set(ids)) != len(ids):
            # two exporters on one id share one replicated position entry:
            # the faster one's ack overwrites the slower one's progress
            # and a restart silently skips the difference
            raise ValueError(f"duplicate exporter ids in {ids}")
        if len(self.partitions) > 1 and any(
            isinstance(spec, tuple) for spec in self._exporter_specs
        ):
            # a shared instance would interleave partitions into one sink
            # (and the JSONL dedup tail would silently DROP the lower
            # partition's records); cfg entries build one instance per
            # partition and are the only safe multi-partition shape
            raise ValueError(
                "exporter instance pairs cannot be shared across "
                "multiple partitions — pass ExporterCfg entries instead"
            )
        for partition in self.partitions:
            pairs = []
            for spec in self._exporter_specs:
                if isinstance(spec, tuple):
                    pairs.append(spec)
                else:
                    pairs.append(build_exporter(spec))
            director = ExporterDirector(
                partition.partition_id,
                partition.log,
                pairs,
                append_fn=lambda recs, p=partition: p.log.append(recs),
                clock=self.clock,
            )
            director.open(fold_tail_acks(
                partition.engine.exporter_positions,
                partition.log,
                partition.next_read_position,
            ))
            partition.exporter_director = director

    def _pump_exporters(self) -> bool:
        progress = False
        for partition in self.partitions:
            director = getattr(partition, "exporter_director", None)
            if director is not None:
                progress = director.pump() or progress
        return progress

    def _replay(self, partition: Partition, last_source: int) -> None:
        # Reprocess only up to the last source event position — the highest
        # position whose follow-ups are already in the log. Records after it
        # were appended but never processed (crash between append and
        # process); they are processed normally, WITH side effects, by the
        # regular loop (reference StreamProcessorController:189-279:
        # lastSourceEventPosition bounds reprocessing).
        reader = partition.log.reader(partition.next_read_position)
        for record in reader.read_committed():
            if record.position > last_source:
                break
            partition.engine.process(record)  # state updates only
            partition.next_read_position = record.position + 1

    def snapshot(self) -> None:
        """Checkpoint every partition (reference: periodic
        ``actor.runAtFixedRate(snapshotPeriod, createSnapshot)``; here the
        runtime decides when — tests and the broker's timer loop call it)."""
        for partition in self.partitions:
            metadata = SnapshotMetadata(
                last_processed_position=partition.next_read_position - 1,
                last_written_position=partition.log.next_position - 1,
                term=partition.term,
            )
            # dirty-delta path: clean families reuse the previous take's
            # manifest entries (no re-encode/re-hash; on the device engine
            # no device→host readback either)
            partition.snapshots.take_engine(partition.engine, metadata)
            # compaction: the snapshot covers everything below its
            # last-processed position — drop those records (bounded by the
            # engine's floor: open incidents still re-read their failure
            # events by position). Reference: segments below the snapshot
            # are deleted; the log stops pinning every record in RAM.
            floor = min(
                metadata.last_processed_position + 1,
                partition.engine.compaction_floor(),
            )
            partition.log.compact(floor)

    # -- client API (reference ClientApiMessageHandler) --------------------
    def write_command(
        self,
        partition_id: int,
        value,
        intent: int,
        key: int = -1,
        with_response: bool = True,
    ) -> Optional[int]:
        """Write a COMMAND record to a partition's log; returns request id."""
        from zeebe_tpu.protocol.metadata import RecordMetadata

        request_id = None
        md = RecordMetadata(
            record_type=RecordType.COMMAND,
            value_type=value.VALUE_TYPE,
            intent=int(intent),
        )
        if with_response:
            request_id = self._next_request_id
            self._next_request_id += 1
            md.request_id = request_id
            md.request_stream_id = 0
        record = Record(key=key, metadata=md, value=value)
        tracer = tracing.TRACER
        span = tracer.maybe_sample(partition_id) if tracer is not None else None
        partition = self.partitions[partition_id]
        if span is not None and request_id is not None:
            # bind by request id BEFORE the append: a concurrent drain
            # thread can apply the record the instant it lands, and the
            # RESPONSE stamp looks the span up by request id
            tracer.bind_request(
                span, self._trace_request_ns + request_id, partition_id
            )
        partition.log.append([record])
        if span is not None:
            # single-writer broker: the append IS the commit (no raft
            # queue/fsync hops); the span is position-keyed from here
            tracer.bind_position(
                span, partition_id, record.position, committed=True
            )
            if (
                not span.finished
                and partition.next_read_position > record.position
            ):
                # a drain on another thread applied the record between
                # the append and the bind: the position-keyed stamps
                # (APPLY, finish_positions) already missed this span.
                # With no ack plane nothing later can finish it; with a
                # working plane it survives ONLY if some exporter has
                # not yet dispatched past the position (the coming
                # dispatch stamps it and the ack then finishes it) —
                # otherwise close it instead of leaking it in the live
                # budget with every stamp path hot.
                director = partition.exporter_director
                if (
                    director is None
                    or not director.can_ack()
                    or director.dispatch_passed(record.position)
                ):
                    tracer.finish_positions(
                        partition_id, (record.position,)
                    )
        return request_id

    def next_partition(self) -> int:
        """Round-robin partition selection (reference client routing)."""
        pid = self._rr_partition
        self._rr_partition = (self._rr_partition + 1) % len(self.partitions)
        return pid

    def partition_for_correlation_key(self, correlation_key: str) -> int:
        return self.partitions[0].engine.partition_for_correlation_key(correlation_key)

    def take_response(self, request_id: int) -> Optional[Record]:
        return self._responses.pop(request_id, None)

    def on_push(
        self, subscriber_key: int, listener: Callable[[int, Record], None]
    ) -> None:
        """Register a push listener; called with (partition_id, record)."""
        self._push_listeners[subscriber_key] = listener

    def on_record(self, listener: Callable[[int, Record], None]) -> None:
        """In-process record tap (tests/debug; the durable, credit-controlled
        variant is ``open_topic_subscription``)."""
        self._record_listeners.append(listener)

    # -- topic subscriptions (reference TopicSubscriptionManagementProcessor
    # + per-subscriber TopicSubscriptionPushProcessor:36) -------------------
    def open_topic_subscription(
        self,
        name: str,
        handler: Callable[[int, Record], None],
        partition_id: int = 0,
        start_position: Optional[int] = None,
        credits: int = 32,
        force_start: bool = False,
    ) -> "TopicSubscriptionHandle":
        """Open a durable push subscription over a partition's record stream.
        Resumes from the last ACKNOWLEDGEd position persisted in the log
        unless ``force_start``; otherwise starts at ``start_position`` (or
        0). Push pace is credit-bound; ``handle.ack(position)`` persists
        progress and replenishes credits."""
        from zeebe_tpu.protocol.records import TopicSubscriberRecord

        request_id = self.write_command(
            partition_id,
            TopicSubscriberRecord(
                name=name,
                start_position=-1 if start_position is None else start_position,
                buffer_size=credits,
                force_start=force_start,
            ),
            SubscriberIntent.SUBSCRIBE,
        )
        self.run_until_idle()
        response = self.take_response(request_id)
        engine = self.partitions[partition_id].engine
        acked = engine.topic_sub_acks.get(name)
        if acked is not None and not force_start:
            cursor = acked + 1  # resume after the last acknowledged record
        elif start_position is not None:
            cursor = start_position
        else:
            cursor = 0
        handle = TopicSubscriptionHandle(
            broker=self,
            partition_id=partition_id,
            name=name,
            handler=handler,
            subscriber_key=response.key if response is not None else -1,
            cursor=cursor,
            credits=credits,
        )
        self._topic_subscriptions.append(handle)
        self._pump_topic_subscriptions()
        return handle

    def _pump_topic_subscriptions(self) -> bool:
        pushed = False
        for handle in list(self._topic_subscriptions):
            pushed = handle.pump() or pushed
        return pushed

    # -- processing loop ----------------------------------------------------
    # committed records drain in WAVES: one engine dispatch per wave (the
    # device engine's SIMD unit — per-record process() calls round-trip
    # the device once per record), but results apply PER RECORD in log
    # order, so the appended log is byte-identical to record-at-a-time
    # processing (tests/test_serving_wave.py pins this). Set to 1 to force
    # the record-at-a-time baseline.
    wave_size = 256

    def _wave_scheduler(self):
        """The broker's shared-wave scheduler, feeds registered once and
        sizing resynced per drain (tests retune ``wave_size`` after
        construction)."""
        from zeebe_tpu.scheduler import WaveScheduler

        size = max(1, self.wave_size)
        scheduler = self._scheduler
        if scheduler is None:
            scheduler = WaveScheduler(wave_size=size)
            for partition in self.partitions:
                scheduler.register(_BrokerFeed(self, partition))
            self._scheduler = scheduler
        if scheduler.wave_size != size:
            scheduler.wave_size = size
            scheduler.quantum = max(1, size // 8)
            scheduler.backpressure_limit = 4 * size
        return scheduler

    def run_until_idle(self, max_iterations: int = 100_000) -> int:
        """Process all partitions until no backlog remains. Returns the number
        of records processed (the StreamProcessorController hot loop,
        StreamProcessorController.java:296-399, run to quiescence).

        Default mode drains through the shared-wave scheduler — one wave
        may pack several partitions' committed tails (continuous
        batching); per-partition apply order is cursor order either way,
        so each partition's log is bit-identical across modes
        (``use_scheduler = False`` forces the per-partition baseline)."""
        if not self.use_scheduler:
            return self._run_until_idle_unscheduled(max_iterations)
        scheduler = self._wave_scheduler()
        processed = 0
        progress = True
        while progress:
            progress = False
            drained = scheduler.drain(
                max_records=max_iterations + 1 - processed
            )
            processed += drained
            if processed > max_iterations:
                raise RuntimeError("broker did not reach quiescence")
            if drained:
                progress = True
            # deliver to topic subscriptions; their handlers may write acks
            # or commands, which the next pass processes
            if self._pump_topic_subscriptions():
                progress = True
            # exporters tail the freshly committed records; their position
            # acks are records too and process on the next pass
            if self._pump_exporters():
                progress = True
        return processed

    def _run_until_idle_unscheduled(self, max_iterations: int) -> int:
        """Per-partition baseline drain (the reference the shared-wave log
        is compared with, ``tests/test_scheduler.py::TestSharedWaveParity``):
        each partition's backlog drains to empty in its own waves before
        the next partition runs."""
        from zeebe_tpu.runtime.metrics import observe_wave

        processed = 0
        progress = True
        wave_cap = max(1, self.wave_size)
        while progress:
            progress = False
            for partition in self.partitions:
                while partition.has_backlog():
                    reader = partition.log.reader(partition.next_read_position)
                    records = reader.read_committed()
                    if not records:
                        break
                    for start in range(0, len(records), wave_cap):
                        wave = records[start : start + wave_cap]
                        results = partition.engine.process_wave(wave)
                        for record, result in zip(wave, results):
                            self._apply_result(partition, record, result)
                        processed += len(wave)
                        host_s, device_s = getattr(
                            partition.engine, "last_wave_seconds", (0.0, 0.0)
                        )
                        observe_wave(
                            len(wave), wave_cap, host_s, device_s,
                            getattr(
                                partition.engine, "last_wave_phases", None
                            ),
                        )
                        if processed > max_iterations:
                            raise RuntimeError("broker did not reach quiescence")
                    progress = True
            if self._pump_topic_subscriptions():
                progress = True
            if self._pump_exporters():
                progress = True
        return processed

    def _apply_result(self, partition: Partition, record, result) -> None:
        """Apply one processed record's outputs — sends, follow-up appends,
        responses, pushes — exactly as the per-record loop did (the engine
        already processed the whole wave; application stays record-major
        so the log bytes don't depend on the wave size). ``record`` may be
        a real ``Record`` or a lazy ``(batch, idx)`` tail entry; only the
        record-listener tap materializes it."""
        position = _entry_position(record)
        # monotone: the scheduler feed already advanced the cursor at
        # take(); the baseline path advances here
        if position + 1 > partition.next_read_position:
            partition.next_read_position = position + 1
        tracer = tracing.TRACER
        # "no ack will ever arrive" probe (scans exporter handles):
        # computed lazily, at most once per record, only on traced paths
        no_ack_plane = None
        if tracer is not None and tracer.by_position:
            tracer.stamp_positions(
                partition.partition_id, (position,), tracing.APPLY
            )
        for target_pid, send in result.sends:
            # reference: subscription transport → command on the target log.
            # Sends go BEFORE the local follow-up append: once the follow-ups
            # are durable this record is inside the replay boundary and its
            # side effects never re-run, so a crash in between must lose the
            # (reprocessable) follow-ups, not the send. Duplicate sends after
            # a crash are fine — subscription open/correlate are idempotent
            # (dead activity ⇒ rejection; CLOSE removes all matches).
            self._route_send(partition, target_pid, send)
        if result.written:
            stamp_source_positions(result.written, position)
            partition.log.append(as_log_batch(result.written))
            cache = partition.engine.records_by_position
            for written in result.written:
                if type(written) is tuple:
                    # lazy columnar follow-up: the log-backed cache serves
                    # position re-reads without materializing it here
                    continue
                cache[written.position] = written
        for response in result.responses:
            if response.metadata.request_id >= 0:
                self._responses[response.metadata.request_id] = response
                if tracer is not None and tracer.tracking_requests():
                    # without an exporter plane — or with one whose every
                    # exporter broke at open — no ack will ever finish
                    # the span: the response is its last stage
                    if no_ack_plane is None:
                        no_ack_plane = tracing.no_ack_plane(partition)
                    tracer.stamp_request(
                        self._trace_request_ns + response.metadata.request_id,
                        tracing.RESPONSE, final=no_ack_plane,
                    )
        for subscriber_key, push in result.pushes:
            listener = self._push_listeners.get(subscriber_key)
            if listener is not None:
                listener(partition.partition_id, push)
        if tracer is not None and tracer.by_position:
            if no_ack_plane is None:
                no_ack_plane = tracing.no_ack_plane(partition)
            if no_ack_plane:
                # no exporter plane (or one that can never ack again):
                # this apply is the last stage a span at this position can
                # reach (response-less internal commands never hit the
                # stamp_request(final=True) path above)
                tracer.finish_positions(partition.partition_id, (position,))
        for listener in self._record_listeners:
            listener(partition.partition_id, _entry_record(record))

    def _route_send(self, partition: Partition, target_pid: int, send) -> None:
        """Cross-partition send: over the mesh all_to_all frame exchange
        when both partitions are device-resident and an exchange is
        installed, direct append otherwise. Immediate flush keeps the
        single-writer broker deterministic: the arrival appends at exactly
        the point the direct append would have."""
        exchange = self.mesh_exchange
        if exchange is not None:
            src = getattr(partition.engine, "device_index", -1)
            dst = getattr(
                self.partitions[target_pid].engine, "device_index", -1
            )
            if src >= 0 and dst >= 0 and src != dst:
                from zeebe_tpu.protocol import codec

                if exchange.queue(
                    src, dst, target_pid, codec.encode_record(send)
                ):
                    exchange.flush(self._deliver_mesh_frame)
                    return
        self.partitions[target_pid].log.append([send])

    def _deliver_mesh_frame(self, partition_id: int, frame: bytes) -> None:
        from zeebe_tpu.protocol import codec

        record, _ = codec.decode_record(bytes(frame))
        record.position = -1  # assigned at append, like transport arrivals
        record.timestamp = -1
        self.partitions[partition_id].log.append([record])

    # -- time-driven side processors ---------------------------------------
    def tick(self) -> None:
        """Fire due timers / job timeouts / message TTLs (reference: periodic
        actor jobs — JobTimeOutStreamProcessor, MessageTimeToLiveChecker)."""
        for partition in self.partitions:
            for command in partition.engine.check_job_deadlines():
                partition.log.append([command])
            for command in partition.engine.check_timer_deadlines():
                partition.log.append([command])
            for command in partition.engine.check_message_ttls():
                partition.log.append([command])
            # jobs stranded by credit droughts (see backlog_activations).
            # The DEVICE job backlog is gated behind the same cheap fused
            # probe the cluster broker uses (PROBE_JOB_BACKLOG): the
            # unconditional device_backlog_activations() here pulled the
            # whole job table device→host every tick (32 MB at 2^20 rows)
            # even when nothing was assignable. Unlike the
            # cluster broker's launch-and-poll pattern, the probe here is
            # read SYNCHRONOUSLY (one fused scalar): this embedded broker
            # is the oracle-parity surface — a one-tick-deferred probe
            # would assign backlog a tick later than the host oracle and
            # break the DualRig log comparisons tick-for-tick
            backlog = partition.engine.backlog_activations()
            probe = getattr(partition.engine, "deadlines_due_probe", None)
            if hasattr(partition.engine, "device_backlog_activations"):
                from zeebe_tpu.tpu.engine import PROBE_JOB_BACKLOG

                mask = int(probe()) if probe is not None else PROBE_JOB_BACKLOG
                if mask & PROBE_JOB_BACKLOG:
                    backlog = backlog + (
                        partition.engine.device_backlog_activations()
                    )
            for command in backlog:
                partition.log.append([command])

    def records(self, partition_id: int = 0) -> List[Record]:
        """All committed records of a partition (test/debug; reference
        LogStreamPrinter / RecordStream asserts)."""
        return list(self.partitions[partition_id].log.reader(0))

    def close(self) -> None:
        from zeebe_tpu.tracing.recorder import record_event

        record_event("broker", "in-process broker closed",
                     data_dir=self.data_dir)
        for partition in self.partitions:
            if partition.exporter_director is not None:
                partition.exporter_director.close()
            partition.log.storage.close()
