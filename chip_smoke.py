#!/usr/bin/env python3
"""chip_smoke.py — does the served path still start, and answer right, on the chip?

One process, no child that needs JAX. With no arguments it needs ONE TPU
chip and drives the main path once at deployment table size (2^20 rows):

    client → ClusterBroker → raft commit → wave scheduler →
    TpuPartitionEngine.dispatch_wave → kernel.step_jit → collect_wave →
    log append → worker push → job completion → response

Phases, each printing one JSON line; any failure raises, so the run ends
non-zero and prints no ``ok`` line:

  env      platform, device kind and count, versions (refuses a non-TPU)
  native   zeebe_tpu/native/libzbtpu.so builds from native/*.cc
  boot     the launcher's own config path (load_config +
           engine_factory_from_config): autotune, selfcheck, warm()
  parity   a scripted scenario on a host-engine Broker and a device-engine
           Broker; the two logs' record_signature must be equal
  serving  >= 2,048 instances of three processes through the socket
           client and a job worker; every one completes exactly once, none
           of their records ran on the embedded host engine, the oracle
           replays the committed log, no compile during the traffic
  restart  snapshot, a little more traffic, close, reopen on the same data
           directory: same log, and one more instance completes

``--chips 4`` runs only the four-chip paths and what they are compared
with: four partitions placed on four devices with one cross-partition
message correlation over the mesh exchange, and one partition's tables
sharded over the four devices against the same scenario on one device.

``--rehearse`` admits whatever platform JAX has (the sandbox CPU) at a
small size; the device reported is the one that really ran. Nothing this
prints is a benchmark: ``smoke_instances_per_s`` is a smoke reading.

The last line of standard output is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import tempfile
import threading
import time

DEADLINE_S = 1150  # the driver allows 1200 s; a hang must not outlive it


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def check(cond, message: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {message}")


def wait_for(predicate, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while not predicate():
        check(time.monotonic() < deadline, f"timed out after {timeout_s}s: {what}")
        time.sleep(0.02)


# -- the processes ------------------------------------------------------------


def order_process():
    from zeebe_tpu.models.bpmn.builder import Bpmn

    return (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )


def gateway_process():
    from zeebe_tpu.models.bpmn.builder import Bpmn

    b = (
        Bpmn.create_process("decision")
        .start_event("start")
        .exclusive_gateway("split")
    )
    b.branch("$.orderValue >= 100").service_task(
        "high", type="priority-service"
    ).end_event("end-high")
    b.branch(default=True).service_task(
        "low", type="normal-service"
    ).end_event("end-low")
    return b.done()


def timer_process():
    from zeebe_tpu.models.bpmn.builder import Bpmn

    return (
        Bpmn.create_process("wait-a-second")
        .start_event("start")
        .timer_catch_event("wait", duration_ms=1_000)
        .end_event("end")
        .done()
    )


def correlation_process():
    from zeebe_tpu.models.bpmn.builder import Bpmn

    return (
        Bpmn.create_process("xcorr")
        .start_event("s")
        .receive_task("wait", message_name="paid", correlation_key="$.oid")
        .end_event("e")
        .done()
    )


JOB_TYPES = ("payment-service", "priority-service", "normal-service")
PROCESS_IDS = ("order-process", "decision", "wait-a-second", "xcorr")


def payload_for(process_id: str, i: int) -> dict:
    if process_id == "decision":
        return {"orderId": i, "orderValue": 250 if i % 2 else 40, "tier": "t1"}
    return {"orderId": i, "orderValue": 99, "customer": f"c-{i % 7}"}


# -- shared checks -------------------------------------------------------------


def completions(records) -> collections.Counter:
    """workflow instance key → number of process-level ELEMENT_COMPLETED
    events in ``records`` (a completed instance has exactly one)."""
    from zeebe_tpu.protocol.enums import RecordType, ValueType
    from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI

    done: collections.Counter = collections.Counter()
    for r in records:
        md = r.metadata
        if (
            int(md.value_type) == int(ValueType.WORKFLOW_INSTANCE)
            and int(md.record_type) == int(RecordType.EVENT)
            and int(md.intent) == int(WI.ELEMENT_COMPLETED)
            and r.value.activity_id in PROCESS_IDS
        ):
            done[r.value.workflow_instance_key] += 1
    return done


class CompletionWatch:
    """Follows one partition's committed log from a cursor and counts
    instance completions as they commit."""

    def __init__(self, server):
        self.server = server
        self.cursor = 0
        self.done: collections.Counter = collections.Counter()

    def poll(self) -> collections.Counter:
        records = self.server.log.reader(self.cursor).read_committed()
        if records:
            self.cursor = records[-1].position + 1
            self.done.update(completions(records))
        return self.done

    def completed(self, keys, exactly_once: bool = True) -> bool:
        """One read of the log's new tail, then: is every key done?"""
        done = self.poll()
        if exactly_once:
            return all(done[k] == 1 for k in keys)
        return all(done[k] >= 1 for k in keys)


def check_oracle_accepts(
    records, partition_id: int, num_partitions: int, repository=None
):
    """The plain reference: replay the committed log through a fresh host
    oracle. It must raise nothing and end with no live instance. Returns
    (summary, the oracle's repository — partition 0's holds the deployed
    workflows the other partitions' replays need)."""
    from zeebe_tpu.testing.chaos import replay_oracle

    oracle = replay_oracle(records, partition_id, num_partitions, repository)
    live = len(oracle.element_instances.instances)
    check(live == 0, f"oracle replay of partition {partition_id} ends with "
          f"{live} live element instances")
    return (
        {"records_replayed": len(records), "live_instances": live},
        oracle.repository,
    )


def lifecycle_on_host(engine) -> dict:
    """Instance-lifecycle records this device engine ran on its embedded
    host oracle, by (value type, workflow key) — a workflow outside kernel
    coverage would show here and nowhere else."""
    from zeebe_tpu.protocol.enums import ValueType
    from zeebe_tpu.testing.parity import SIG_TYPES

    return {
        f"{ValueType(vt).name}/wf={wf}": n
        for (vt, wf), n in sorted(engine.host_records_by_kind.items())
        if vt in SIG_TYPES
    }


def jit_cache_sizes() -> dict:
    from zeebe_tpu.tpu import jit_registry

    return {
        name: row["cache_size"]
        for name, row in jit_registry.signature_report().items()
    }


def dispatch_table() -> dict:
    """Per family: what the boot A/B chose, why, and — where pallas won —
    which served table shapes the size rule admitted or sent to XLA."""
    from zeebe_tpu.tpu import autotune, pallas_ops

    decisions = pallas_ops.get_dispatch()
    timings = autotune.dispatch_timings()
    rulings = pallas_ops.size_rulings()
    table = {}
    for family in pallas_ops.FAMILIES:
        ruled = [r for r in rulings if r["family"] == family]
        row = {
            "boot_choice": (
                "pallas" if pallas_ops.use_pallas(family) else "xla"
            ),
            "why": (
                f"boot A/B at 2^12 rows: {timings[family]}"
                if family in timings
                else f"no A/B ran (source: {autotune.dispatch_source()})"
            ),
            "pallas_at": [r["tables"] for r in ruled if r["admitted"]],
            "xla_by_size_rule": [
                {"tables": r["tables"], "vmem_bytes": r["vmem_bytes"]}
                for r in ruled if not r["admitted"]
            ],
        }
        if family in decisions:
            row["ab_winner"] = "pallas" if decisions[family] else "xla"
        table[family] = row
    return {
        "source": autotune.dispatch_source(),
        "vmem_limit_bytes": pallas_ops.VMEM_LIMIT_BYTES,
        "families": table,
    }


def state_bytes(engine) -> int:
    import jax

    return int(sum(leaf.nbytes for leaf in jax.tree.leaves(engine.state)))


def peak_device_bytes(devices) -> dict:
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        out[str(d)] = stats.get("peak_bytes_in_use")
    return out


# -- the launcher's config path -----------------------------------------------


def launcher_config(work: str, capacity: int, partitions: int = 1):
    """The config ``python -m zeebe_tpu --config FILE`` would load."""
    from zeebe_tpu.runtime.config import load_config

    path = os.path.join(work, "zeebe.cfg.toml")
    with open(path, "w") as f:
        f.write(
            "[network]\n"
            'host = "127.0.0.1"\n'
            "clientPort = 0\nmanagementPort = 0\nsubscriptionPort = 0\n"
            "[cluster]\n"
            'nodeId = "smoke-0"\n'
            f"partitions = {partitions}\n"
            "replicationFactor = 1\n"
            "[engine]\n"
            'type = "tpu"\n'
            f"capacity = {capacity}\n"
            "numVars = 16\n"
            "[metrics]\n"
            "enabled = false\nport = 0\n"
        )
    return load_config(path)


def start_broker(cfg, data_dir: str):
    """Bring-up sequence of tests/test_cluster.py::ClusterUnderTest for
    one node: open every partition, bootstrap it alone, await leadership
    (the leader install builds the engine: autotune, selfcheck, warm)."""
    from zeebe_tpu.runtime.cluster_broker import ClusterBroker
    from zeebe_tpu.runtime.engines import engine_factory_from_config

    broker = ClusterBroker(
        cfg, data_dir, engine_factory=engine_factory_from_config(cfg)
    )
    pids = range(cfg.cluster.partitions)
    for pid in pids:
        broker.open_partition(pid).join(60)
        broker.bootstrap_partition(pid, {})
    wait_for(
        lambda: all(
            pid in broker.partitions and broker.partitions[pid].is_leader
            for pid in pids
        ),
        600, "partition leaders installed",
    )
    return broker


def connect(broker, partitions: int = 1):
    from zeebe_tpu.gateway.cluster_client import ClusterClient

    # the first instance of a process compiles the step program on the
    # broker actor; its response waits behind that compile
    return ClusterClient(
        [broker.client_address], num_partitions=partitions,
        request_timeout_ms=300_000,
    )


def open_workers(client, credits: int = 256):
    return [
        client.open_job_worker(t, lambda pid, rec: {"done": True}, credits=credits)
        for t in JOB_TYPES
    ]


def pump_creates(client, plan, threads: int):
    """``plan`` = list of (process id, payload, partition or None); sent
    from ``threads`` client threads. Returns the created instance keys in
    plan order; a create without a response fails the run."""
    keys = [None] * len(plan)
    errors: list = []

    def run(k: int) -> None:
        for i in range(k, len(plan), threads):
            process_id, payload, partition = plan[i]
            try:
                rsp = client.create_instance(process_id, payload, partition)
                keys[i] = rsp.value.workflow_instance_key
            except BaseException as e:  # noqa: BLE001 - reported, then fatal
                errors.append(f"create #{i} ({process_id}): {e!r}")
                return

    ts = [threading.Thread(target=run, args=(k,)) for k in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    check(not errors, f"{len(errors)} creates failed; first: {errors[:1]}")
    check(all(k is not None and k > 0 for k in keys), "a create got no key")
    # keys are unique within a partition, not across partitions
    check(
        len({(p[2], k) for p, k in zip(plan, keys)}) == len(keys),
        "duplicate workflow instance keys",
    )
    return keys


# -- one chip -------------------------------------------------------------------


def scripted_scenario(broker, instances_each: int, what: str, clock=None):
    """The deterministic scenario of the parity comparisons, on an
    in-process Broker: deploy the order and the gateway process, one
    worker per job type, ``instances_each`` instances of each, each create
    drained single-threaded before the next (jobs complete in key order);
    given the controlled ``clock``, ten seconds then pass and the deadline
    sweep runs. Returns the log."""
    from zeebe_tpu.gateway import JobWorker, ZeebeClient

    client = ZeebeClient(broker)
    client.deploy_model(order_process())
    client.deploy_model(gateway_process())
    for job_type in JOB_TYPES:
        JobWorker(broker, job_type, lambda ctx: {"paid": True}, credits=64)
    for i in range(instances_each):
        client.create_instance("order-process", payload_for("order-process", i))
        client.create_instance("decision", payload_for("decision", i))
    broker.run_until_idle()
    if clock is not None:
        clock.advance(10_000)
        broker.tick()
        broker.run_until_idle()
    records = broker.records(0)
    done = completions(records)
    check(
        len(done) == 2 * instances_each and set(done.values()) == {1},
        f"{what}: {len(done)} of {2 * instances_each} instances completed",
    )
    return records


def fresh_subscriber_keys() -> None:
    """Subscriber keys come from one process-wide counter; two brokers
    whose logs are compared must hand out the same ones."""
    import itertools

    from zeebe_tpu.gateway import workers as workers_mod

    workers_mod._subscriber_keys = itertools.count(1)


def parity_phase(capacity: int, instances_each: int) -> None:
    """DualRig form of tests/test_tpu_parity.py: the same scripted
    scenario, single-threaded under a controlled clock, on a host-engine
    Broker and on a device-engine Broker at the served capacity."""
    from zeebe_tpu.engine.interpreter import WorkflowRepository
    from zeebe_tpu.runtime import Broker, ControlledClock
    from zeebe_tpu.testing.parity import record_signature
    from zeebe_tpu.tpu import TpuPartitionEngine

    t0 = time.perf_counter()
    signatures = []
    device_engine = None
    for on_device in (False, True):
        fresh_subscriber_keys()
        clock = ControlledClock(start_ms=1_000_000)
        if on_device:
            repo = WorkflowRepository()
            broker = Broker(
                num_partitions=1, clock=clock,
                engine_factory=lambda pid: TpuPartitionEngine(
                    pid, 1, repository=repo, clock=clock,
                    capacity=capacity, num_vars=16,
                ),
            )
            device_engine = broker.partitions[0].engine
        else:
            broker = Broker(num_partitions=1, clock=clock)
        records = scripted_scenario(
            broker, instances_each,
            f"parity scenario on the {'device' if on_device else 'host'} engine",
            clock=clock,
        )
        signatures.append(record_signature(records))
        broker.close()
    host_sig, dev_sig = signatures
    for i, (a, b) in enumerate(zip(host_sig, dev_sig)):
        check(a == b, f"parity: record {i} differs\n  host:   {a}\n  device: {b}")
    check(
        len(host_sig) == len(dev_sig),
        f"parity: {len(host_sig)} host records vs {len(dev_sig)} device records",
    )
    on_host = lifecycle_on_host(device_engine)
    check(not on_host, f"parity: lifecycle records ran on the host engine: {on_host}")
    emit(
        "parity",
        instances=2 * instances_each,
        records_compared=len(host_sig),
        signatures_equal=True,
        device_records=device_engine.device_records_processed,
        host_records=device_engine.host_records_processed,
        seconds=round(time.perf_counter() - t0, 3),
    )


def serving_phase(broker, n_instances: int, threads: int) -> None:
    import jax

    from zeebe_tpu.protocol.enums import RecordType, ValueType
    from zeebe_tpu.protocol.intents import JobIntent
    from zeebe_tpu.runtime.metrics import GLOBAL_REGISTRY

    server = broker.partitions[0]
    engine = server.engine
    client = connect(broker)
    watch = CompletionWatch(server)
    for model in (order_process(), gateway_process(), timer_process()):
        client.deploy_model(model)
    workers = open_workers(client)
    # one warm-up instance per process: whatever compiles, compiles here
    t0 = time.perf_counter()
    warm_plan = [
        ("order-process", payload_for("order-process", 0), None),
        ("decision", payload_for("decision", 1), None),
        ("decision", payload_for("decision", 2), None),
        ("wait-a-second", payload_for("wait-a-second", 0), None),
    ]
    warm_keys = pump_creates(client, warm_plan, threads=1)
    wait_for(
        lambda: watch.completed(warm_keys), 600, "warm-up instances completed"
    )
    warm_s = time.perf_counter() - t0

    counter = GLOBAL_REGISTRY.counter
    caches_before = jit_cache_sizes()
    waves0 = counter("serving_waves_total").value
    recs0 = counter("serving_wave_records_total").value
    dev0, host0 = engine.device_records_processed, engine.host_records_processed

    n_timer = max(n_instances // 32, 1)
    plan = []
    for i in range(n_instances):
        pid = (
            "wait-a-second" if i < n_timer
            else ("order-process", "decision")[i % 2]
        )
        plan.append((pid, payload_for(pid, i), None))
    t0 = time.perf_counter()
    keys = pump_creates(client, plan, threads)
    created_s = time.perf_counter() - t0
    wait_for(
        lambda: watch.completed(keys, exactly_once=False),
        600, f"all {n_instances} instances completed",
    )
    wall_s = time.perf_counter() - t0
    caches_after = jit_cache_sizes()

    done = watch.poll()
    wrong = {k: done[k] for k in keys if done[k] != 1}
    check(not wrong, f"instances not completed exactly once: {list(wrong.items())[:5]}")
    # on the TPU every wave pads to one batch shape, so nothing may
    # compile after the warm-up; off it (rehearsal) the engine keeps
    # tight power-of-two buckets and each new one compiles
    check(
        caches_before == caches_after or jax.default_backend() != "tpu",
        "a jit entry compiled during the traffic: "
        f"{caches_before} -> {caches_after}",
    )
    on_host = lifecycle_on_host(engine)
    check(not on_host, f"lifecycle records ran on the host engine: {on_host}")
    devices = engine.state.ei_i32.devices()
    check(
        devices == {jax.devices()[0]},
        f"engine state is on {devices}, not on {jax.devices()[0]}",
    )
    for w in workers:
        w.close()
    client.close()
    committed = server.log.reader(0).read_committed()
    oracle, _ = check_oracle_accepts(committed, 0, 1)
    # job activations per job: the tick's backlog sweep can activate a
    # job again while an earlier ACTIVATE is still in the pipeline
    job_events = collections.Counter(
        int(r.metadata.intent) for r in committed
        if int(r.metadata.value_type) == int(ValueType.JOB)
        and int(r.metadata.record_type) == int(RecordType.EVENT)
    )

    waves = counter("serving_waves_total").value - waves0
    recs = counter("serving_wave_records_total").value - recs0
    sbytes = state_bytes(engine)
    peak = peak_device_bytes([jax.devices()[0]])
    if jax.devices()[0].platform == "tpu":
        p = next(iter(peak.values()))
        check(
            p is not None and p >= sbytes,
            f"peak HBM {p} B is below the state pytree's {sbytes} B",
        )
    emit(
        "serving",
        instances_completed=len(keys),
        warmup_instances=len(warm_keys),
        warmup_seconds=round(warm_s, 3),
        create_seconds=round(created_s, 3),
        wall_seconds=round(wall_s, 3),
        smoke_instances_per_s=round(len(keys) / wall_s, 2),
        note="smoke, not a benchmark: one run, closed loop, "
             f"{threads} client threads",
        waves_dispatched=int(waves),
        mean_wave_fill=round(recs / waves, 2) if waves else 0.0,
        device_records=engine.device_records_processed - dev0,
        host_records=engine.host_records_processed - host0,
        host_records_by_kind={
            f"{ValueType(vt).name}/wf={wf}": n
            for (vt, wf), n in sorted(engine.host_records_by_kind.items())
        },
        lifecycle_records_on_host=0,
        committed_records=len(committed),
        jobs_created=job_events[int(JobIntent.CREATED)],
        jobs_activated=job_events[int(JobIntent.ACTIVATED)],
        jobs_completed=job_events[int(JobIntent.COMPLETED)],
        oracle=oracle,
        state_devices=[str(d) for d in devices],
        state_pytree_bytes=sbytes,
        peak_device_bytes=peak,
        jit_cache_sizes_before_traffic=caches_before,
        jit_cache_sizes_after_traffic=caches_after,
    )
    emit("dispatch", **dispatch_table())


def restart_phase(broker, cfg, data_dir: str) -> None:
    """Snapshot, a little more traffic so that a tail follows the
    snapshot, close, reopen on the same directory: the log is the same
    and one more instance completes (snapshot restore + replay onto the
    device at the served capacity)."""
    from zeebe_tpu.testing.parity import record_signature

    t0 = time.perf_counter()
    broker.snapshot_all()
    snapshot_s = time.perf_counter() - t0
    server = broker.partitions[0]
    watch = CompletionWatch(server)
    client = connect(broker)
    workers = open_workers(client)
    plan = [
        ("order-process", payload_for("order-process", i), None)
        for i in range(8)
    ]
    keys = pump_creates(client, plan, threads=2)
    wait_for(lambda: watch.completed(keys), 300,
             "post-snapshot instances completed")
    for w in workers:
        w.close()
    client.close()
    # let the worker's last completions' follow-ups commit and apply
    wait_for(
        lambda: server.next_read_position > server.log.commit_position,
        60, "partition drained before close",
    )
    before = record_signature(server.log.reader(0).read_committed())
    broker.close()

    t0 = time.perf_counter()
    broker = start_broker(cfg, data_dir)
    reopen_s = time.perf_counter() - t0
    server = broker.partitions[0]
    after = record_signature(server.log.reader(0).read_committed())
    check(
        after == before,
        f"log changed across restart: {len(before)} -> {len(after)} records",
    )
    watch = CompletionWatch(server)
    client = connect(broker)
    workers = open_workers(client)
    (key,) = pump_creates(
        client, [("order-process", payload_for("order-process", 1), None)], 1
    )
    wait_for(lambda: watch.completed([key]), 600,
             "instance completed after restart")
    for w in workers:
        w.close()
    client.close()
    on_host = lifecycle_on_host(server.engine)
    check(not on_host, f"after restart, lifecycle records on host: {on_host}")
    emit(
        "restart",
        snapshot_seconds=round(snapshot_s, 3),
        reopen_to_leader_seconds=round(reopen_s, 3),
        records_before=len(before),
        records_after=len(after),
        log_unchanged=True,
        instance_completed_after_restart=True,
    )
    broker.close()


def one_chip(args) -> None:
    from zeebe_tpu.runtime import engines

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    cfg = launcher_config(work, args.capacity)
    data_dir = os.path.join(work, "data", cfg.cluster.node_id)

    t0 = time.perf_counter()
    broker = start_broker(cfg, data_dir)
    emit(
        "boot",
        capacity=cfg.engine.capacity,
        num_vars=cfg.engine.num_vars,
        wave_size=cfg.scheduler.wave_size,
        seconds_to_leader=round(time.perf_counter() - t0, 3),
        seconds_by_part={
            k: round(v, 3) for k, v in engines.LAST_BOOT_SECONDS.items()
        },
        note="warm() compiles nothing on a fresh data directory: no "
             "workflow is deployed yet, so there is no graph to step",
    )
    parity_phase(args.capacity, args.parity_instances)
    serving_phase(broker, args.instances, args.threads)
    restart_phase(broker, cfg, data_dir)


# -- four chips ------------------------------------------------------------------


def four_partitions(args) -> None:
    """One broker, four partitions, ``[mesh]`` at its defaults: the plan
    places the four leaders on four devices; traffic to all four, and one
    message correlation that crosses partitions over the mesh exchange."""
    import jax

    from zeebe_tpu.gateway.cluster_client import _correlation_hash
    from zeebe_tpu.protocol.enums import RecordType, ValueType
    from zeebe_tpu.runtime.metrics import event_count

    work = tempfile.mkdtemp(prefix="chip_smoke4_")
    cfg = launcher_config(work, args.capacity, partitions=4)
    check(cfg.mesh.enabled and cfg.mesh.exchange, "[mesh] defaults changed")
    broker = start_broker(cfg, os.path.join(work, "data", cfg.cluster.node_id))
    servers = [broker.partitions[pid] for pid in range(4)]
    placed = {}
    seen = []  # (partition, batch devices, output devices) per wave

    def watch_steps(engine) -> None:
        inner = engine._run_step

        def run(batch, now, lane_owner=None):
            out, stats = inner(batch, now, lane_owner=lane_owner)
            seen.append((
                engine.partition_id,
                batch.key.devices(),
                jax.tree.leaves(out)[0].devices(),
            ))
            return out, stats

        engine._run_step = run

    for s in servers:
        devs = s.engine.state.ei_i32.devices()
        check(len(devs) == 1, f"partition {s.partition_id} state on {devs}")
        placed[s.partition_id] = next(iter(devs))
        watch_steps(s.engine)
    check(
        len(set(placed.values())) == 4,
        f"four partitions sit on {len(set(placed.values()))} devices: {placed}",
    )

    client = connect(broker, partitions=4)
    watches = [CompletionWatch(s) for s in servers]
    for model in (order_process(), gateway_process(), correlation_process()):
        client.deploy_model(model)
    workers = open_workers(client)
    plan = []
    for i in range(args.instances):
        pid = ("order-process", "decision")[i % 2]
        plan.append((pid, payload_for(pid, i), i % 4))
    # the correlation key's hash names the MESSAGE partition; the instance
    # goes to another one, so OPEN and CORRELATE cross partitions
    oid = "k-smoke"
    msg_partition = _correlation_hash(oid) % 4
    wf_partition = (msg_partition + 1) % 4
    plan.append(("xcorr", {"oid": oid}, wf_partition))
    frames0 = event_count("mesh_exchange_frames")
    fails0 = (
        event_count("mesh_exchange_flush_failures")
        + event_count("mesh_exchange_fallbacks")
    )
    t0 = time.perf_counter()
    keys = pump_creates(client, plan, args.threads)

    # the subscription must be open on the message partition before the
    # publish, or the message (time to live 0) is dropped
    def subscription_opened() -> bool:
        return any(
            int(r.metadata.value_type) == int(ValueType.MESSAGE_SUBSCRIPTION)
            and int(r.metadata.record_type) == int(RecordType.EVENT)
            for r in servers[msg_partition].log.reader(0).read_committed()
        )

    wait_for(subscription_opened, 600, "subscription opened across partitions")
    client.publish_message("paid", oid, {"paid": True})

    def all_done() -> bool:
        done = [w.poll() for w in watches]
        return all(done[p[2]][k] == 1 for p, k in zip(plan, keys))

    wait_for(all_done, 600, "all instances on four partitions completed")
    wall_s = time.perf_counter() - t0
    for w in workers:
        w.close()
    client.close()

    rode = event_count("mesh_exchange_frames") - frames0
    failed = (
        event_count("mesh_exchange_flush_failures")
        + event_count("mesh_exchange_fallbacks") - fails0
    )
    check(rode > 0, "no frame rode the mesh exchange")
    check(failed == 0, f"{failed} mesh exchange failures or fallbacks")
    stepped = collections.Counter()
    for pid, batch_devs, out_devs in seen:
        check(
            batch_devs == {placed[pid]} and out_devs == {placed[pid]},
            f"partition {pid} wave: batch on {batch_devs}, outputs on "
            f"{out_devs}, state on {placed[pid]}",
        )
        stepped[pid] += 1
    check(set(stepped) == {0, 1, 2, 3}, f"waves ran on partitions {set(stepped)}")
    oracles = {}
    repository = None
    for s in servers:  # partition 0 first: its log holds the deployments
        on_host = lifecycle_on_host(s.engine)
        check(not on_host, f"partition {s.partition_id}: lifecycle records "
              f"on the host engine: {on_host}")
        oracles[s.partition_id], repository = check_oracle_accepts(
            s.log.reader(0).read_committed(), s.partition_id, 4, repository
        )
    emit(
        "four_partitions",
        placement={pid: str(d) for pid, d in placed.items()},
        instances_completed=len(keys),
        cross_partition_correlation={
            "workflow_partition": wf_partition,
            "message_partition": msg_partition,
            "instance_key": keys[-1],
            "mesh_exchange_frames": int(rode),
        },
        waves_by_partition=dict(stepped),
        waves_on_their_partitions_device=True,
        oracle=oracles,
        wall_seconds=round(wall_s, 3),
        peak_device_bytes=peak_device_bytes(jax.devices()),
    )
    broker.close()


def sharded_parity(args) -> None:
    """The parity scenario on two device-engine Brokers: one partition's
    tables block-sharded over four devices (gathered routing, the default)
    against the same partition on one device — compared as
    tests/test_sharded_state.py compares them: frames and raw segment bytes."""
    import jax

    from zeebe_tpu.engine.interpreter import WorkflowRepository
    from zeebe_tpu.protocol import codec
    from zeebe_tpu.runtime import Broker, ControlledClock
    from zeebe_tpu.tpu import TpuPartitionEngine

    work = tempfile.mkdtemp(prefix="chip_smoke_sharded_")
    t0 = time.perf_counter()
    logs = []
    shard_devices = {}
    for shards in (4, 1):
        fresh_subscriber_keys()
        clock = ControlledClock(start_ms=1_000_000)
        repo = WorkflowRepository()
        kw = (
            dict(state_shards=4, shard_devices=jax.devices()[:4])
            if shards > 1 else {}
        )
        data_dir = os.path.join(work, f"shards-{shards}")
        broker = Broker(
            num_partitions=1, data_dir=data_dir, clock=clock,
            engine_factory=lambda pid: TpuPartitionEngine(
                pid, 1, repository=repo, clock=clock,
                capacity=args.capacity, num_vars=16, **kw,
            ),
        )
        engine = broker.partitions[0].engine
        records = scripted_scenario(
            broker, args.parity_instances, f"sharded parity ({shards} shards)"
        )
        if shards > 1:
            for name in ("ei_i32", "job_i32", "ei_pay"):
                table = getattr(engine.state, name)
                devs = {s.device for s in table.addressable_shards}
                check(len(devs) == 4, f"{name} shards sit on {len(devs)} devices")
                rows = {s.data.shape[0] for s in table.addressable_shards}
                check(
                    rows == {table.shape[0] // 4},
                    f"{name} shard rows {rows} of {table.shape[0]}",
                )
                shard_devices[name] = sorted(str(d) for d in devs)
            check(engine.sharded_waves > 0, "no wave ran the sharded step")
        frames = [codec.encode_record(r) for r in records]
        broker.close()
        pdir = os.path.join(data_dir, "partition-0")
        segments = b""
        for name in sorted(os.listdir(pdir)):
            if name.startswith("segment-") and name.endswith(".log"):
                with open(os.path.join(pdir, name), "rb") as f:
                    segments += f.read()
        logs.append((frames, segments))
    (f4, s4), (f1, s1) = logs
    check(f4 == f1, "sharded vs single-device: record frames differ")
    check(len(s1) > 0 and s4 == s1, "sharded vs single-device: segment bytes differ")
    emit(
        "sharded_parity",
        shards=4,
        routing="gathered",
        instances=2 * args.parity_instances,
        frames_equal=True,
        records=len(f1),
        segment_bytes_equal=True,
        segment_bytes=len(s1),
        shard_devices=shard_devices,
        seconds=round(time.perf_counter() - t0, 3),
    )


# -- entry ---------------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument(
        "--rehearse", action="store_true",
        help="admit a non-TPU platform (sandbox rehearsal at a small size)",
    )
    parser.add_argument("--capacity", type=int, default=None)
    parser.add_argument("--instances", type=int, default=None)
    parser.add_argument("--parity-instances", type=int, default=None)
    parser.add_argument("--threads", type=int, default=16)
    args = parser.parse_args()
    sized = (args.capacity, args.instances, args.parity_instances)
    if not args.rehearse and any(v is not None for v in sized):
        parser.error("sizes are fixed unless --rehearse is given")
    small = args.rehearse
    if args.capacity is None:
        args.capacity = 1 << 12 if small else 1 << 20
    if args.instances is None:
        args.instances = (64 if small else 2048) if args.chips == 1 else (
            32 if small else 512
        )
    if args.parity_instances is None:
        args.parity_instances = 8 if small else 64

    def too_long() -> None:
        print(f"chip_smoke FAILED: still running after {DEADLINE_S}s",
              file=sys.stderr, flush=True)
        os._exit(3)

    watchdog = threading.Timer(DEADLINE_S, too_long)
    watchdog.daemon = True
    watchdog.start()

    import jax
    import jaxlib

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" and not args.rehearse:
        raise SystemExit(
            f"chip_smoke FAILED: needs a TPU, JAX found {device} "
            "(--rehearse admits other platforms for a sandbox rehearsal)"
        )
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs that many devices, found {len(devices)}")
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "?")
    except ImportError:
        libtpu_version = None

    from zeebe_tpu import compile_cache

    import jax.extend.backend

    emit(
        "env",
        device=device,
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=libtpu_version,
        platform_version=jax.extend.backend.get_backend().platform_version,
        compile_cache=compile_cache.enable(),
        rehearsal=args.rehearse,
        chips=args.chips,
    )

    from zeebe_tpu import native

    lib = os.path.join(os.path.dirname(native.__file__), "libzbtpu.so")
    had_lib = os.path.exists(lib)
    t0 = time.perf_counter()
    check(native.available(), f"native library unavailable: {native.build_error()}")
    emit("native", available=True, built_in_this_run=not had_lib,
         seconds=round(time.perf_counter() - t0, 3))

    if args.chips == 4:
        four_partitions(args)
        sharded_parity(args)
    else:
        one_chip(args)

    watchdog.cancel()
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    # A failed phase leaves brokers, clients and their threads running,
    # and an orderly close of a wedged broker can outlast the time limit:
    # report, then leave at once. Nothing here ends in 0 but main's return.
    try:
        code = main()
    except BaseException as e:  # noqa: BLE001 - reported, exit non-zero
        import traceback

        if isinstance(e, SystemExit) and isinstance(e.code, str):
            print(e.code, file=sys.stderr)
        else:
            traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
    sys.exit(code)
