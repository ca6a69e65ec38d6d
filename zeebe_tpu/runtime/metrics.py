"""Metrics: registry + prometheus text rendering + periodic file writer.

Reference parity: ``util/.../metrics/MetricsManager.java`` (allocate
counters with name + labels, ``dump`` renders Prometheus text format) and
``broker-core/.../system/metrics/MetricsFileWriter.java:34-90`` (an actor
flushes the registry to ``metrics/zeebe.prom`` every 5s; scraped via node
exporter). Counters are used throughout the broker: records processed /
skipped / written per stream processor (``StreamProcessorMetrics``),
workflow-instance counts (``WorkflowInstanceMetrics``), transport and
scheduler internals.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

from zeebe_tpu.runtime.actors import Actor, ActorScheduler


class Metric:
    """A counter/gauge with fixed labels. Increment-only use makes it a
    counter; ``set`` makes it a gauge — prometheus typing is emitted from
    ``kind``."""

    __slots__ = ("name", "labels", "kind", "_value", "_lock")

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...], kind: str):
        self.name = name
        self.labels = labels
        self.kind = kind
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, delta: float = 1.0) -> None:
        with self._lock:
            self._value += delta

    def set(self, value: float) -> None:
        with self._lock:
            self._value = value

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """A cumulative-bucket histogram with fixed labels (prometheus
    `histogram` type: `_bucket{le=...}`, `_sum`, `_count` series). Used by
    the metrics exporter for per-ValueType/intent export latencies."""

    DEFAULT_BUCKETS = (1, 5, 10, 25, 50, 100, 250, 500, 1000, 5000, 30000)

    __slots__ = ("name", "labels", "buckets", "_counts", "_sum", "_count", "_lock")

    def __init__(
        self,
        name: str,
        labels: Tuple[Tuple[str, str], ...],
        buckets: Tuple[float, ...] = DEFAULT_BUCKETS,
    ):
        self.name = name
        self.labels = labels
        self.buckets = tuple(sorted(buckets))
        self._counts = [0] * len(self.buckets)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        with self._lock:
            self._sum += value
            self._count += 1
            for i, le in enumerate(self.buckets):
                if value <= le:
                    self._counts[i] += 1

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def render(self, prefix: str, ts: int) -> List[str]:
        base = ",".join(f'{k}="{v}"' for k, v in self.labels)
        sep = "," if base else ""
        lines = []
        with self._lock:
            # _counts are cumulative already (observe bumps every bucket
            # whose bound covers the value) — prometheus `le` semantics
            for le, n in zip(self.buckets, self._counts):
                lines.append(
                    f'{prefix}{self.name}_bucket{{{base}{sep}le="{le:g}"}} '
                    f"{n} {ts}"
                )
            lines.append(
                f'{prefix}{self.name}_bucket{{{base}{sep}le="+Inf"}} '
                f"{self._count} {ts}"
            )
            suffix = f"{{{base}}}" if base else ""
            lines.append(f"{prefix}{self.name}_sum{suffix} {self._sum:g} {ts}")
            lines.append(f"{prefix}{self.name}_count{suffix} {self._count} {ts}")
        return lines


class MetricsRegistry:
    """Reference MetricsManager: allocate once, render many."""

    def __init__(self, prefix: str = "zb_"):
        self.prefix = prefix
        self._metrics: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], Metric] = {}
        self._histograms: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], Histogram] = {}
        self._help: Dict[str, str] = {}
        self._lock = threading.Lock()

    def counter(self, name: str, help_text: str = "", **labels: str) -> Metric:
        return self._allocate(name, "counter", help_text, labels)

    def gauge(self, name: str, help_text: str = "", **labels: str) -> Metric:
        return self._allocate(name, "gauge", help_text, labels)

    def histogram(
        self,
        name: str,
        help_text: str = "",
        buckets: Tuple[float, ...] = Histogram.DEFAULT_BUCKETS,
        **labels: str,
    ) -> Histogram:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            h = self._histograms.get(key)
            if h is None:
                h = Histogram(name, key[1], buckets)
                self._histograms[key] = h
            elif h.buckets != tuple(sorted(buckets)):
                # allocate-once semantics: the first caller's buckets win
                # for the process lifetime (observations already landed in
                # them) — silently dropping a DIFFERENT buckets arg would
                # let an operator believe a changed latency_buckets config
                # took effect when it did not
                logging.getLogger(__name__).warning(
                    "histogram %r already allocated with buckets %s; "
                    "ignoring different buckets %s (restart the process "
                    "to change histogram buckets)",
                    name, h.buckets, tuple(sorted(buckets)),
                )
            if help_text:
                self._help[name] = help_text
            return h

    def _allocate(self, name: str, kind: str, help_text: str, labels: Dict[str, str]) -> Metric:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            metric = self._metrics.get(key)
            if metric is None:
                metric = Metric(name, key[1], kind)
                self._metrics[key] = metric
            if help_text:
                self._help[name] = help_text
            return metric

    def dump(self, now_ms: Optional[int] = None) -> str:
        """Prometheus text format (reference MetricsManager.dump renders
        `name{label="v",...} value timestamp`)."""
        ts = now_ms if now_ms is not None else int(time.time() * 1000)
        by_name: Dict[str, List[Metric]] = {}
        hists_by_name: Dict[str, List[Histogram]] = {}
        with self._lock:
            for metric in self._metrics.values():
                by_name.setdefault(metric.name, []).append(metric)
            for hist in self._histograms.values():
                hists_by_name.setdefault(hist.name, []).append(hist)
        lines: List[str] = []
        for name in sorted(by_name):
            full = self.prefix + name
            if name in self._help:
                lines.append(f"# HELP {full} {self._help[name]}")
            lines.append(f"# TYPE {full} {by_name[name][0].kind}")
            for metric in by_name[name]:
                if metric.labels:
                    label_str = ",".join(f'{k}="{v}"' for k, v in metric.labels)
                    lines.append(f"{full}{{{label_str}}} {metric.value:g} {ts}")
                else:
                    lines.append(f"{full} {metric.value:g} {ts}")
        for name in sorted(hists_by_name):
            full = self.prefix + name
            if name in self._help:
                lines.append(f"# HELP {full} {self._help[name]}")
            lines.append(f"# TYPE {full} histogram")
            for hist in hists_by_name[name]:
                lines.extend(hist.render(self.prefix, ts))
        return "\n".join(lines) + "\n"


# -- process-global event counters ------------------------------------------
# Low-level components (transport, log storage, snapshot storage, raft) have
# no broker registry in reach — they are constructed in many places, some
# (raft's own ClientTransport) several layers away from the broker. Chaos-
# relevant events from those layers count into one process-global registry
# instead, merged into every /metrics dump and metrics-file flush via
# ``render_with_global``. Names used today: raft_elections_started,
# raft_elections_won, transport_reconnects, transport_pending_expired,
# log_torn_tail_truncations, snapshot_salvage_events; exporter plane:
# exporter_lag (gauge, per exporter/partition), exporter_records_exported,
# exporter_export_failures, exporter_floor_stalls, exporter_open_failures,
# exporter_skipped_compacted; snapshot lifecycle (docs/STATE.md):
# snapshot_last_new_bytes / snapshot_last_total_bytes /
# snapshot_take_seconds / snapshot_capture_pause_seconds /
# snapshot_restore_seconds (gauges), snapshot_full_takes,
# snapshot_delta_takes, snapshot_take_failures, snapshot_skipped_inflight,
# snapshot_recover_skipped; columnar record plane (docs/SERVING.md):
# serving_rows_materialized_total — Record objects lazily materialized from
# columnar batch views (protocol/columnar.py); 0 on the pure host wave
# path, where every row is an engine-built Record already; tracing plane
# (docs/operations/tracing.md): raft_commit_stalls,
# raft_appends_truncated, serving_commit_stalls, serving_slow_waves,
# flight_recorder_dumps.
GLOBAL_REGISTRY = MetricsRegistry()


def global_counter(name: str, help_text: str = "", **labels: str) -> Metric:
    return GLOBAL_REGISTRY.counter(name, help_text, **labels)


def global_gauge(name: str, help_text: str = "", **labels: str) -> Metric:
    """Labeled process-global gauge (exporter lag per exporter/partition,
    etc.) — merged into every /metrics dump via ``render_with_global``."""
    return GLOBAL_REGISTRY.gauge(name, help_text, **labels)


def count_event(name: str, help_text: str = "", delta: float = 1.0) -> None:
    """Bump a process-global event counter (allocate-on-first-use)."""
    GLOBAL_REGISTRY.counter(name, help_text).inc(delta)


def event_count(name: str) -> float:
    """Current value of a global event counter (0 if never bumped)."""
    return GLOBAL_REGISTRY.counter(name).value


# -- serving-plane wave instrumentation --------------------------------------
# The pipelined batched drain (runtime/broker.run_until_idle waves, the
# wave scheduler's shared waves in the cluster broker) reports each
# dispatched wave here: the fill gauge and records over waves localize "the
# pipeline is running empty" vs "the device is the bottleneck" without a
# profiler (occupancy = serving_wave_records_total / (serving_waves_total x
# the wave size)), and
# the host/device second counters give the time split ``wave_host_share``
# reads. Handles are cached — this sits on the drain hot loop.
_WAVE_HANDLES: dict = {}


def _wave_handles() -> dict:
    if not _WAVE_HANDLES:
        g = GLOBAL_REGISTRY
        _WAVE_HANDLES.update(
            waves=g.counter(
                "serving_waves_total",
                "Committed-record drain waves dispatched to the engine",
            ),
            records=g.counter(
                "serving_wave_records_total",
                "Committed records drained through waves",
            ),
            fill=g.gauge(
                "serving_wave_fill", "Records in the most recent drain wave"
            ),
            host_s=g.counter(
                "serving_host_seconds_total",
                "Serving-path host seconds (staging, host-routed records, "
                "emission materialization)",
            ),
            device_s=g.counter(
                "serving_device_seconds_total",
                "Serving-path seconds blocked on device outputs",
            ),
        )
    return _WAVE_HANDLES


# -- wave-cycle phases ---------------------------------------------------------
# The serving cycle phase by phase (tracing/phases.py; the operator's table
# is docs/operations/tracing.md "Wave phases"): one flat counter per phase
# and per byte count, always on, flushed once per wave / drain / tick / raft
# group commit from that cycle's PhaseClock. Flat names, no labels: readers
# take a counter by event_count(name).
_PHASE_HANDLES: dict = {}


def _phase_handles() -> dict:
    if not _PHASE_HANDLES:
        g = GLOBAL_REGISTRY
        # keyed by phase / count name; built whole, then ONE update: the
        # broker actor and the raft actor both flush here, and neither may
        # see the table half filled
        _PHASE_HANDLES.update(
            pack=g.counter(
                "serving_pack_seconds_total",
                "Scheduler seconds packing shared waves (feed.take, DRR)",
            ),
            route=g.counter(
                "serving_route_seconds_total",
                "Engine dispatch seconds outside device segments: "
                "recompile check, per-record routing, host-routed "
                "records, key sync",
            ),
            stage=g.counter(
                "serving_stage_seconds_total",
                "Seconds staging device segments: pre-work scans, column "
                "fill, family matrices",
            ),
            h2d=g.counter(
                "serving_h2d_seconds_total",
                "Seconds in the device_put calls of the staged batch",
            ),
            launch=g.counter(
                "serving_launch_seconds_total",
                "Seconds in the call into the step program until it "
                "returns",
            ),
            blocked=g.counter(
                "serving_blocked_seconds_total",
                "HOST seconds waiting for the device at a wave's first "
                "sync",
            ),
            readback=g.counter(
                "serving_readback_seconds_total",
                "Seconds in device_get of a wave's emission batch",
            ),
            decode=g.counter(
                "serving_decode_seconds_total",
                "Seconds decoding emissions into records (residency "
                "notes, columnar decode, source stamping)",
            ),
            apply=g.counter(
                "serving_apply_seconds_total",
                "Broker seconds applying a collected wave: follow-ups to "
                "raft.append, responses, sends, pushes",
            ),
            job_read=g.counter(
                "serving_job_read_seconds_total",
                "Seconds reading a device job's row back and building its "
                "record (one device_get of three row slices a job), for a "
                "sweep's ACTIVATE or TIME_OUT or a subscription's backlog",
            ),
            push=g.counter(
                "serving_push_seconds_total",
                "Broker seconds marshalling and sending a collected wave's "
                "ACTIVATED records to their job subscribers",
            ),
            backlog=g.counter(
                "serving_backlog_seconds_total",
                "Seconds in the tick's sweep of the engine's parked jobs, "
                "those no credit was free for (device_backlog_activations)",
            ),
            drain_wait=g.counter(
                "serving_drain_wait_seconds_total",
                "Seconds from a drain being scheduled to its start on the "
                "broker actor",
            ),
            pump=g.counter(
                "serving_pump_seconds_total",
                "Seconds after a drain's waves: mesh exchange flush, "
                "parked fetches, topic-subscription pushes",
            ),
            tick=g.counter(
                "serving_tick_seconds_total",
                "Seconds in the partitions' deadline ticks (host sweeps "
                "and the due probe's launch)",
            ),
            log_append=g.counter(
                "raft_log_append_seconds_total",
                "Raft group-commit seconds up to the end of log.append: "
                "term stamping, merge, codec encode, write",
            ),
            fsync=g.counter(
                "raft_fsync_seconds_total",
                "Raft group-commit seconds in log.flush",
            ),
            commit=g.counter(
                "raft_commit_seconds_total",
                "Raft group-commit seconds after the fsync: traced binds, "
                "commit advance, replication fan-out",
            ),
            h2d_bytes=g.counter(
                "serving_h2d_bytes_total",
                "Bytes handed to device_put by wave staging",
            ),
            d2h_bytes=g.counter(
                "serving_d2h_bytes_total",
                "Bytes fetched by device_get at wave collect",
            ),
            h2d_transfers=g.counter(
                "serving_h2d_transfers_total",
                "Arrays handed to device_put by wave staging (two a "
                "segment: the packed pair)",
            ),
            d2h_transfers=g.counter(
                "serving_d2h_transfers_total",
                "Arrays fetched by device_get at wave collect (three a "
                "segment: the emission's packed pair and the stats vector)",
            ),
            job_row_reads=g.counter(
                "serving_job_row_reads_total",
                "Device job rows read back to build a record",
            ),
            job_pushes=g.counter(
                "serving_job_pushes_total",
                "ACTIVATED records pushed to job subscribers by collected "
                "waves",
            ),
            backlog_activations=g.counter(
                "serving_backlog_activations_total",
                "ACTIVATE commands the tick's backlog sweep appended",
            ),
            backlog_sweeps=g.counter(
                "serving_backlog_sweeps_total",
                "Backlog sweeps that got past the gate: a job was parked, "
                "or the parked set was not known (after a restore)",
            ),
            backlog_table_scans=g.counter(
                "serving_backlog_table_scans_total",
                "Whole scans of the device job table for parked jobs: one "
                "per restore, by the first sweep or subscription after it",
            ),
            backlog_parked=g.counter(
                "serving_backlog_parked_total",
                "Jobs that entered the engine's parked set: a collected "
                "wave stepped their pool event and no credit was free",
            ),
            backlog_parked_walked=g.counter(
                "serving_backlog_parked_walked_total",
                "Parked jobs the backlog sweeps looked at: those of the "
                "credited types, in key order, until the free credits ran "
                "out",
            ),
            backlog_park_wait=g.counter(
                "serving_backlog_park_wait_seconds_total",
                "Seconds the jobs a sweep handed out had waited in the "
                "engine's parked set (its clock, milliseconds; a job the "
                "scan after a restore found has no entry time and adds "
                "nothing)",
            ),
            credit_return=g.counter(
                "serving_job_credit_return_seconds_total",
                "Seconds in the device engine's increase_job_credits: host "
                "arithmetic on the host side of the subscription table (the "
                "credits wait there for the credit column's next reader; "
                "the table is fetched only after a state assigned from "
                "outside)",
            ),
            credit_returns=g.counter(
                "serving_job_credit_returns_total",
                "Credit returns the device engine took "
                "(increase_job_credits calls)",
            ),
            credit_flush=g.counter(
                "serving_job_credit_flush_seconds_total",
                "Seconds adding the returned credits to the device's credit "
                "column before its next reader (a step, the due probe, the "
                "sweep, a subscription, a snapshot): one launch a flush",
            ),
            credit_flushes=g.counter(
                "serving_job_credit_flushes_total",
                "Flushes of returned credits to the device (over "
                "serving_job_credit_returns_total: the returns one launch "
                "carried)",
            ),
            # the device engine's host-held payload values (tpu/values.py)
            payload_values_held=g.counter(
                "serving_payload_values_held_total",
                "Payload values with no column form (a map, a list, a number "
                "f32 does not hold exactly) the device engine staged into its "
                "value store under a new handle",
            ),
            payload_value_bytes=g.counter(
                "serving_payload_value_bytes_total",
                "Msgpack bytes of the payload values staged into the value "
                "store",
            ),
            payload_values_freed=g.counter(
                "serving_payload_values_freed_total",
                "Held payload values freed: their last owner (an instance, a "
                "message) ended, or the command that staged them was rejected",
            ),
            payload_values_live=g.gauge(
                "serving_payload_values_live",
                "Held payload values now live (held less freed, set at each "
                "flush)",
            ),
            payload_value_seconds=g.counter(
                "serving_payload_value_seconds_total",
                "Seconds holding, resolving, owning and freeing payload "
                "values, on a clock of its own: inside the phases stage, "
                "decode and push, which keep the time",
            ),
            # the topic push (PartitionServer.pump_topic_subscriptions) and
            # the log tail it reads (LogStream.committed_frames,
            # freeze_below); hit share = frames / (frames + encoded)
            topic_push_frames=g.counter(
                "serving_topic_push_frames_total",
                "Records pushed to topic subscribers as the frame their log "
                "append wrote (nothing built or encoded)",
            ),
            topic_push_encoded=g.counter(
                "serving_topic_push_encoded_total",
                "Records pushed to topic subscribers encoded anew, because "
                "the log holds no frame block for them (recovered from "
                "disk, or appended by a follower)",
            ),
            log_tail_frozen=g.counter(
                "log_tail_frozen_total",
                "Log-tail entries turned from a ref into a wave's readback "
                "into a frame ref, once the feed and every topic pusher "
                "were past them",
            ),
            payload_host_routed=g.counter(
                "serving_payload_host_routed_total",
                "Instances sent to the host engine because a variable their "
                "workflow reads held a value with no column form",
            ),
            backlog_skipped_in_flight=g.counter(
                "serving_backlog_skipped_in_flight_total",
                "Activatable jobs a backlog sweep left alone because a pool "
                "event or an ACTIVATE of theirs was on its way to a wave",
            ),
            # the device engine's message tables (kernel.step's stats vector
            # where the message store is on the device; tpu/engine.py)
            message_cut=g.counter(
                "serving_message_cut_seconds_total",
                "Seconds checking the commands on the device's message tables "
                "against the composites (message name, correlation value) "
                "their pending segment holds: phase message_cut, cut out of "
                "route",
            ),
            message_cuts=g.counter(
                "serving_message_cuts_total",
                "Commands on the device's message tables that met an earlier "
                "one on the same composite in their segment and started a "
                "segment of their own (a PUBLISH and an OPEN that would not "
                "see each other)",
            ),
            message_publishes=g.counter(
                "serving_message_publishes_total",
                "Message PUBLISH commands the device stepped",
            ),
            messages_stored=g.counter(
                "serving_messages_stored_total",
                "Published messages with a time to live the device stored",
            ),
            messages_deleted=g.counter(
                "serving_messages_deleted_total",
                "Stored messages the device deleted (DELETE commands: the "
                "time to live ran out)",
            ),
            message_correlated_at_open=g.counter(
                "serving_message_correlated_at_open_total",
                "Subscriptions whose OPEN found a stored message and "
                "correlated it",
            ),
            message_correlated_at_publish=g.counter(
                "serving_message_correlated_at_publish_total",
                "Published messages that found an open subscription and "
                "correlated to it",
            ),
            messages_live=g.gauge(
                "serving_messages_live",
                "Messages now in the device's store (stored less deleted, "
                "set at each flush)",
            ),
            job_commands_serialised=g.counter(
                "serving_job_commands_serialised_total",
                "Job commands that met an earlier command on the same job "
                "in their wave: turned away by the kernel's first-per-key "
                "rule (same intent) or moved to a segment of their own "
                "(another intent)",
            ),
            drains=g.counter(
                "serving_drains_total",
                "Shared-wave drains run",
            ),
            ticks=g.counter(
                "serving_ticks_total",
                "Partition deadline ticks run",
            ),
            groups=g.counter(
                "raft_group_commits_total",
                "Raft group commits (one log.flush each)",
            ),
            # the actors' own timeline (runtime/actors.ActorScheduler._run_job),
            # under the keys tracing.phases.JobNames gives; flushed once per
            # mailbox run
            broker_actor_busy=g.counter(
                "broker_actor_busy_seconds_total",
                "Seconds the broker actor spent running jobs (wall time, "
                "the phases inside them included)",
            ),
            broker_actor_cpu=g.counter(
                "broker_actor_cpu_seconds_total",
                "Thread-CPU seconds of the broker actor's jobs on the CPU "
                "clock (one in eight of its drain jobs, the kind that runs "
                "the waves)",
            ),
            broker_actor_offcpu=g.counter(
                "broker_actor_offcpu_seconds_total",
                "Seconds those jobs were off the CPU: waiting for the "
                "device, a lock, the interpreter lock, a system call (their "
                "wall time less their CPU time)",
            ),
            broker_actor_cpu_clock_jobs=g.counter(
                "broker_actor_cpu_clock_jobs_total",
                "Jobs of the broker actor put on the CPU clock (a system "
                "call a reading: a sample, not every job)",
            ),
            broker_actor_idle=g.counter(
                "broker_actor_idle_seconds_total",
                "Seconds the broker actor had an empty mailbox, from the "
                "end of a job to the start of the next",
            ),
            raft_actor_busy=g.counter(
                "raft_actor_busy_seconds_total",
                "Seconds the raft actors spent running jobs (wall time, the "
                "phases inside them included)",
            ),
            raft_actor_cpu=g.counter(
                "raft_actor_cpu_seconds_total",
                "Thread-CPU seconds of the raft actors' jobs on the CPU "
                "clock (one in eight of their jobs)",
            ),
            raft_actor_offcpu=g.counter(
                "raft_actor_offcpu_seconds_total",
                "Seconds those jobs were off the CPU: waiting for the "
                "device, a lock, the interpreter lock, a system call (their "
                "wall time less their CPU time)",
            ),
            raft_actor_cpu_clock_jobs=g.counter(
                "raft_actor_cpu_clock_jobs_total",
                "Jobs of the raft actors put on the CPU clock (a system "
                "call a reading: a sample, not every job)",
            ),
            raft_actor_idle=g.counter(
                "raft_actor_idle_seconds_total",
                "Seconds the raft actors had an empty mailbox, from the end "
                "of a job to the start of the next",
            ),
            broker_actor_command=g.counter(
                "broker_actor_command_seconds_total",
                "Self seconds of the broker actor's client commands "
                "(_handle_command): wall time less the phases recorded on "
                "its thread meanwhile",
            ),
            broker_actor_command_jobs=g.counter(
                "broker_actor_command_jobs_total",
                "The broker actor's command jobs run",
            ),
            broker_actor_command_mailbox_wait=g.counter(
                "broker_actor_command_mailbox_wait_seconds_total",
                "Seconds the broker actor's command jobs waited in its "
                "mailbox, enqueue to start",
            ),
            broker_actor_idle_before_command=g.counter(
                "broker_actor_idle_before_command_seconds_total",
                "Idle seconds of the broker actor that a command job ended",
            ),
            broker_actor_job_subscription=g.counter(
                "broker_actor_job_subscription_seconds_total",
                "Self seconds of the broker actor's job-subscription "
                "requests (a worker's open, close and credit returns): wall "
                "time less the phases recorded on its thread meanwhile",
            ),
            broker_actor_job_subscription_jobs=g.counter(
                "broker_actor_job_subscription_jobs_total",
                "The broker actor's job_subscription jobs run",
            ),
            broker_actor_job_subscription_mailbox_wait=g.counter(
                "broker_actor_job_subscription_mailbox_wait_seconds_total",
                "Seconds the broker actor's job_subscription jobs waited in "
                "its mailbox, enqueue to start",
            ),
            broker_actor_idle_before_job_subscription=g.counter(
                "broker_actor_idle_before_job_subscription_seconds_total",
                "Idle seconds of the broker actor that a job_subscription "
                "job ended",
            ),
            broker_actor_topic_subscription=g.counter(
                "broker_actor_topic_subscription_seconds_total",
                "Self seconds of the broker actor's topic-subscription "
                "requests (open, close, check, acknowledgements): wall time "
                "less the phases recorded on its thread meanwhile",
            ),
            broker_actor_topic_subscription_jobs=g.counter(
                "broker_actor_topic_subscription_jobs_total",
                "The broker actor's topic_subscription jobs run",
            ),
            broker_actor_topic_subscription_mailbox_wait=g.counter(
                "broker_actor_topic_subscription_mailbox_wait_seconds_total",
                "Seconds the broker actor's topic_subscription jobs waited "
                "in its mailbox, enqueue to start",
            ),
            broker_actor_idle_before_topic_subscription=g.counter(
                "broker_actor_idle_before_topic_subscription_seconds_total",
                "Idle seconds of the broker actor that a topic_subscription "
                "job ended",
            ),
            broker_actor_drain=g.counter(
                "broker_actor_drain_seconds_total",
                "Self seconds of the broker actor's drain jobs "
                "(_drain_committed: one shared wave and the drain's pump): "
                "wall time less the phases recorded on its thread meanwhile",
            ),
            broker_actor_drain_jobs=g.counter(
                "broker_actor_drain_jobs_total",
                "The broker actor's drain jobs run",
            ),
            broker_actor_drain_mailbox_wait=g.counter(
                "broker_actor_drain_mailbox_wait_seconds_total",
                "Seconds the broker actor's drain jobs waited in its "
                "mailbox, enqueue to start",
            ),
            broker_actor_idle_before_drain=g.counter(
                "broker_actor_idle_before_drain_seconds_total",
                "Idle seconds of the broker actor that a drain job ended",
            ),
            broker_actor_tick=g.counter(
                "broker_actor_tick_seconds_total",
                "Self seconds of the broker actor's tick jobs "
                "(_tick_engines, every 100 ms): wall time less the phases "
                "recorded on its thread meanwhile",
            ),
            broker_actor_tick_jobs=g.counter(
                "broker_actor_tick_jobs_total",
                "The broker actor's tick jobs run",
            ),
            broker_actor_tick_mailbox_wait=g.counter(
                "broker_actor_tick_mailbox_wait_seconds_total",
                "Seconds the broker actor's tick jobs waited in its "
                "mailbox, enqueue to start",
            ),
            broker_actor_idle_before_tick=g.counter(
                "broker_actor_idle_before_tick_seconds_total",
                "Idle seconds of the broker actor that a tick job ended",
            ),
            broker_actor_other=g.counter(
                "broker_actor_other_seconds_total",
                "Self seconds of the broker actor's other jobs (leader "
                "install, snapshots, gossip, fetches, continuations): wall "
                "time less the phases recorded on its thread meanwhile",
            ),
            broker_actor_other_jobs=g.counter(
                "broker_actor_other_jobs_total",
                "The broker actor's other jobs run",
            ),
            broker_actor_other_mailbox_wait=g.counter(
                "broker_actor_other_mailbox_wait_seconds_total",
                "Seconds the broker actor's other jobs waited in its "
                "mailbox, enqueue to start",
            ),
            broker_actor_idle_before_other=g.counter(
                "broker_actor_idle_before_other_seconds_total",
                "Idle seconds of the broker actor that a other job ended",
            ),
        )
    return _PHASE_HANDLES


def observe_phases(clock, cycle: Optional[str] = None) -> None:
    """Flush one cycle's PhaseClock into the phase counters. ``cycle``
    names the count to bump for a cycle that is no wave (``drains``,
    ``ticks``, ``groups``); waves count in ``observe_wave``."""
    h = _phase_handles()
    for name, us in clock.us.items():
        h[name].inc(us / 1e6)
    for name, n in clock.counts.items():
        h[name].inc(n)
    if "payload_values_held" in clock.counts or "payload_values_freed" in clock.counts:
        h["payload_values_live"].set(
            h["payload_values_held"].value - h["payload_values_freed"].value
        )
    if "messages_stored" in clock.counts:
        h["messages_live"].set(
            h["messages_stored"].value - h["messages_deleted"].value
        )
    if cycle is not None:
        h[cycle].inc()


def observe_wave(
    records: int,
    capacity: int,
    host_seconds: float = 0.0,
    device_seconds: float = 0.0,
    phases=None,
) -> None:
    """Record one committed-record drain wave (process-global; shows up on
    every /metrics dump and metrics file via ``render_with_global``).
    ``phases`` is the wave's PhaseClock where the engine keeps one."""
    if phases is not None:
        observe_phases(phases)
    h = _wave_handles()
    h["waves"].inc()
    h["records"].inc(records)
    h["fill"].set(records)
    if host_seconds > 0:
        h["host_s"].inc(host_seconds)
    if device_seconds > 0:
        h["device_s"].inc(device_seconds)


# -- shared-wave scheduler instrumentation -----------------------------------
# The cross-partition wave scheduler (zeebe_tpu/scheduler/) reports each
# SHARED wave here on top of the plain wave series: how many partitions
# contributed (the fill-by-traffic-mix view — high fill with many sources
# is the scheduler doing its job; high fill from one source is just a
# firehose), plus its own backpressure/shed counters (allocated on first
# use via count_event / the admission controller).
_SCHED_HANDLES: dict = {}


def _sched_handles() -> dict:
    if not _SCHED_HANDLES:
        g = GLOBAL_REGISTRY
        _SCHED_HANDLES.update(
            shared_waves=g.counter(
                "scheduler_shared_waves_total",
                "Shared waves packed across partitions by the wave scheduler",
            ),
            sources=g.gauge(
                "serving_wave_sources",
                "Partitions contributing records to the most recent shared "
                "wave",
            ),
            sources_total=g.counter(
                "scheduler_wave_sources_total",
                "Sum of contributing partitions over all shared waves "
                "(mean = this / scheduler_shared_waves_total)",
            ),
            segments=g.counter(
                "serving_segments_total",
                "Wave segments dispatched (one per partition and shared "
                "wave)",
            ),
            segment_max=g.counter(
                "serving_segment_records_max_total",
                "Per shared wave the records of its largest segment, summed "
                "(over serving_wave_records_total: 1/partitions when they "
                "share every wave evenly, 1.0 when one owns each wave)",
            ),
            launch_ahead=g.counter(
                "serving_launch_ahead_total",
                "At each segment's launch, the earlier launched segments "
                "not yet collected, over all waves in flight, summed (over "
                "serving_segments_total: the depth of the device's queue)",
            ),
        )
    return _SCHED_HANDLES


def observe_shared_wave(
    records: int,
    capacity: int,
    sources: int,
    host_seconds: float = 0.0,
    device_seconds: float = 0.0,
    phases=None,
    segments: int = 0,
    segment_max: int = 0,
    launch_ahead: int = 0,
) -> None:
    """Record one SHARED drain wave (scheduler path): the plain wave
    series (fill/time split) plus the traffic-mix gauges.
    ``segments`` counts the segments whose dispatch returned,
    ``segment_max`` is the record count of the wave's largest segment,
    ``launch_ahead`` the sum over its segments of the launched segments
    that were not yet collected when each was launched."""
    observe_wave(records, capacity, host_seconds, device_seconds, phases)
    h = _sched_handles()
    h["shared_waves"].inc()
    h["sources"].set(sources)
    h["sources_total"].inc(sources)
    h["segments"].inc(segments)
    h["segment_max"].inc(segment_max)
    h["launch_ahead"].inc(launch_ahead)


# -- mesh serving instrumentation --------------------------------------------
# The mesh-sharded serving plane (scheduler/placement.DevicePlan) places
# leader partitions across devices; these series prove the spread is real:
# per-device wave/record/occupancy/time-split (labeled by plan device
# index) and the per-shared-wave distinct-device count — ">1 device active
# per scheduling round" is scheduler_wave_devices_total /
# scheduler_shared_waves_total > 1.
_DEVICE_WAVE_HANDLES: dict = {}
_MESH_WAVE_HANDLES: dict = {}


def _device_wave_handles(device: str) -> dict:
    h = _DEVICE_WAVE_HANDLES.get(device)
    if h is None:
        g = GLOBAL_REGISTRY
        h = dict(
            waves=g.counter(
                "serving_device_waves_total",
                "Wave segments dispatched to each mesh device",
                device=device,
            ),
            records=g.counter(
                "serving_device_records_total",
                "Records processed per mesh device",
                device=device,
            ),
            share=g.gauge(
                "serving_device_wave_share",
                "Share of the most recent shared wave's records that "
                "landed on each mesh device (balance view; ~1/active "
                "devices under uniform load)",
                device=device,
            ),
            host_s=g.counter(
                "serving_device_host_seconds_total",
                "Host seconds spent staging/collecting per mesh device",
                device=device,
            ),
            device_s=g.counter(
                "serving_device_device_seconds_total",
                "Seconds blocked on each mesh device's outputs",
                device=device,
            ),
        )
        _DEVICE_WAVE_HANDLES[device] = h
    return h


def observe_device_wave(
    device_index: int,
    records: int,
    wave_total: int,
    host_seconds: float = 0.0,
    device_seconds: float = 0.0,
) -> None:
    """Record one wave segment landing on a mesh device (labeled by the
    DevicePlan index). ``wave_total`` is the WHOLE shared wave's record
    count — the share gauge reads balance across devices, not fill.
    Called by the wave scheduler per dispatched segment; engines without
    a plan placement (index < 0) are skipped."""
    if device_index < 0:
        return
    h = _device_wave_handles(str(device_index))
    h["waves"].inc()
    h["records"].inc(records)
    if wave_total > 0:
        h["share"].set(records / wave_total)
    if host_seconds > 0:
        h["host_s"].inc(host_seconds)
    if device_seconds > 0:
        h["device_s"].inc(device_seconds)


def observe_mesh_wave(devices_active: int) -> None:
    """Distinct mesh devices that received segments of one shared wave."""
    h = _MESH_WAVE_HANDLES
    if not h:
        g = GLOBAL_REGISTRY
        h.update(
            devices=g.gauge(
                "serving_wave_devices",
                "Mesh devices active in the most recent shared wave",
            ),
            devices_total=g.counter(
                "scheduler_wave_devices_total",
                "Sum of active mesh devices over all shared waves "
                "(mean = this / scheduler_shared_waves_total)",
            ),
        )
    h["devices"].set(devices_active)
    h["devices_total"].inc(devices_active)


_SHARDED_WAVE_HANDLES: Dict[str, Metric] = {}
_SHARD_ROW_HANDLES: Dict[str, Metric] = {}
_SHARD_FILL_HANDLES: Dict[str, Metric] = {}
# edge-trigger for the skew warn log: one line per skew EPISODE, re-armed
# by the next balanced wave (flooding the log at wave rate would bury the
# signal the warn exists to surface)
_SKEW_WARNED = [False]

# waves skewed beyond this (max/mean routed rows) warn: one shard is
# doing >4x its fair share — the residency router's load-balance signal
SHARD_SKEW_WARN_RATIO = 4.0


def observe_sharded_wave(
    shard_rows, exchange_bytes: int, single_lane: bool = False
) -> None:
    """Record one wave dispatched through a SHARDED-state partition:
    ``shard_rows`` is the per-shard row count of the staged batch (owner
    lane fill under resident routing, advisory key-hash split under
    gathered — the balance signal operators watch for hot shards),
    ``exchange_bytes`` the wave's ACTUAL cross-shard volume (0 for waves
    that dispatched no records — idle/warm steps move nothing worth
    accounting). ``single_lane`` marks a RESIDENT-ROUTED wave: one lane
    holds everything BY DESIGN, so the skew gauge/warn skip it (the
    ratio would read num_shards on every healthy routed wave — skew is a
    key-hash-split signal, scored on gathered and fallback waves)."""
    h = _SHARDED_WAVE_HANDLES
    if not h:
        g = GLOBAL_REGISTRY
        h.update(
            waves=g.counter(
                "serving_sharded_waves_total",
                "Waves dispatched through the mesh-sharded step program",
            ),
            exchange=g.counter(
                "mesh_shard_exchange_bytes_total",
                "Cross-shard collective bytes moved by sharded-state waves "
                "(table gathers over the mesh axis, or boundary psum "
                "volume under resident routing)",
            ),
            skew=g.gauge(
                "mesh_shard_skew_ratio",
                "max/mean routed rows across the shard span for the most "
                "recent non-empty sharded wave (1.0 = perfectly balanced, "
                "num_shards = one shard takes everything)",
            ),
            skew_waves=g.counter(
                "mesh_shard_skewed_waves_total",
                "Sharded waves whose routed-row skew exceeded the 4x "
                "warn threshold (at meaningful fill)",
            ),
        )
    h["waves"].inc()
    if exchange_bytes > 0:
        h["exchange"].inc(exchange_bytes)
    total = 0
    peak = 0
    for i, rows in enumerate(shard_rows):
        rows = int(rows)
        total += rows
        peak = max(peak, rows)
        key = str(i)
        m = _SHARD_ROW_HANDLES.get(key)
        if m is None:
            m = GLOBAL_REGISTRY.gauge(
                "mesh_shard_rows",
                "Rows of the most recent sharded wave routed to each "
                "shard by key hash",
                device=key,
            )
            _SHARD_ROW_HANDLES[key] = m
        m.set(rows)
    nshards = max(len(shard_rows), 1)
    if total > 0 and not single_lane:
        ratio = peak * nshards / total  # max over mean
        h["skew"].set(ratio)
        # the warn gates on meaningful fill (>= 4 rows/shard on average):
        # a 3-record wave on 8 shards is ALWAYS "skewed" and means nothing
        if ratio > SHARD_SKEW_WARN_RATIO and total >= 4 * nshards:
            h["skew_waves"].inc()
            if not _SKEW_WARNED[0]:
                _SKEW_WARNED[0] = True
                logging.getLogger(__name__).warning(
                    "sharded wave skew %.1fx across %d shards (%d rows, "
                    "peak %d): one shard is doing >%gx its fair share — "
                    "resident routing is only as parallel as the "
                    "instance spread",
                    ratio, nshards, total, peak, SHARD_SKEW_WARN_RATIO,
                )
        else:
            _SKEW_WARNED[0] = False


def observe_shard_fill(plan_indices, fill) -> None:
    """Per-shard staged-row fill of one collected sharded-state segment,
    keyed by the PLAN device index each shard occupies (the scheduler's
    view — ``mesh_shard_rows`` above is keyed by shard ordinal, which
    every sharded partition shares)."""
    for d, rows in zip(plan_indices, fill):
        key = str(int(d))
        m = _SHARD_FILL_HANDLES.get(key)
        if m is None:
            m = GLOBAL_REGISTRY.gauge(
                "mesh_shard_wave_fill",
                "Staged rows the most recent collected sharded segment "
                "routed to this plan device",
                device=key,
            )
            _SHARD_FILL_HANDLES[key] = m
        m.set(int(rows))


def render_with_global(registry: MetricsRegistry, now_ms: Optional[int] = None) -> str:
    """A registry's Prometheus dump with the global event counters appended
    (skipped when the registry IS the global one — no duplicate series)."""
    text = registry.dump(now_ms)
    if registry is not GLOBAL_REGISTRY:
        text += GLOBAL_REGISTRY.dump(now_ms)
    return text


class MetricsHttpServer:
    """Serves ``GET /metrics`` with the registry's Prometheus text dump.

    The reference exposes the metrics file through a node exporter
    (prometheus/prometheus.yml + MetricsFileWriter); here the broker
    serves the same text directly so the compose stack needs no exporter
    sidecar."""

    def __init__(self, registry: MetricsRegistry, host: str = "0.0.0.0", port: int = 9600):
        import http.server

        registry_ref = registry

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 - stdlib API name
                if self.path.rstrip("/") not in ("", "/metrics", "/healthz"):
                    self.send_response(404)
                    self.end_headers()
                    return
                body = render_with_global(registry_ref).encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type", "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # quiet
                pass

        self._server = http.server.ThreadingHTTPServer((host, port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="zb-metrics-http", daemon=True
        )
        self._thread.start()

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()


class MetricsFileWriter(Actor):
    """Periodically dumps the registry to a file (reference
    MetricsFileWriter: temp-write then rename so scrapers never see a torn
    file)."""

    def __init__(
        self,
        registry: MetricsRegistry,
        path: str,
        scheduler: ActorScheduler,
        flush_period_ms: int = 5_000,
    ):
        super().__init__("metrics-file-writer")
        self.registry = registry
        self.path = path
        self.flush_period_ms = flush_period_ms
        scheduler.submit_actor(self, io_bound=True)  # zblint: disable=unobserved-actor-future (boot submit; start failures land in the scheduler failure ring)

    def on_actor_started(self) -> None:
        self.actor.run_at_fixed_rate(self.flush_period_ms, self.flush)

    def flush(self) -> None:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            f.write(render_with_global(self.registry))
        os.replace(tmp, self.path)
