"""The parent process of a run: holds the chip, runs the broker, starts the
load generator and the worker pool as child processes, measures one window
and prints one result line. See ``zbench/README.md``."""

from __future__ import annotations

import argparse
import gc
import json
import os
import queue
import shutil
import subprocess
import sys
import tempfile
import threading
import time

from zbench import check, faults, layers, spec, stats
from zbench import trace as trace_mod

T_START = time.monotonic()
CHILD_TIMEOUT_S = 1100  # a first run compiles; the driver allows it 1200 s
TURNOVER_TIMEOUT_S = 240  # go -> window: pre-roll, or one turnover of the in-flight set


def note(kind: str, **fields) -> None:
    """An earlier line of the output (never the last one)."""
    print(json.dumps({"zbench": kind, **fields}, default=str), flush=True)


class Child:
    """A child process that speaks JSON lines (``zbench/client_proc.py``)."""

    def __init__(self, role: str, child_spec: dict, workdir: str):
        self.role = role
        self.out_path = os.path.join(workdir, f"{role}.out.json")
        spec_path = os.path.join(workdir, f"{role}.spec.json")
        with open(spec_path, "w") as f:
            json.dump({**child_spec, "out": self.out_path}, f)
        self.stderr_path = os.path.join(workdir, f"{role}.stderr")
        self.stderr = open(self.stderr_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "zbench.client_proc", role, spec_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=self.stderr,
            cwd=spec.CHECKOUT, text=True,
        )
        self.events: queue.Queue = queue.Queue()
        self.on_event = None
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            ev["received"] = time.monotonic()
            if self.on_event is not None:
                self.on_event(ev)
            self.events.put(ev)
        self.events.put({"ev": "eof"})

    def tell(self, command: str) -> None:
        self.proc.stdin.write(command + "\n")
        self.proc.stdin.flush()

    def wait_for(self, name: str, timeout_s: float) -> dict:
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                ev = self.events.get(timeout=max(0.01, deadline - time.monotonic()))
            except queue.Empty:
                raise RuntimeError(f"{self.role}: no {name!r} within {timeout_s}s") from None
            if ev["ev"] == name:
                return ev
            if ev["ev"] == "eof":
                raise RuntimeError(
                    f"{self.role} ended before {name!r}: {self.stderr_tail()}"
                )

    def stderr_tail(self) -> str:
        self.stderr.flush()
        with open(self.stderr_path) as f:
            return f.read()[-1500:]

    def finish(self, timeout_s: float = 30) -> dict:
        """Wait for the child to end; its report, with its exit code."""
        try:
            code = self.proc.wait(timeout_s)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            code = self.proc.wait()
        self.stderr.close()
        report = {}
        if os.path.exists(self.out_path):
            with open(self.out_path) as f:
                report = json.load(f)
        report["exit_code"] = code
        return report

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def toml_of(sections: dict) -> str:
    def lit(v):
        if isinstance(v, bool):
            return "true" if v else "false"
        return json.dumps(v)

    return "".join(
        f"[{name}]\n" + "".join(f"{k} = {lit(v)}\n" for k, v in table.items())
        for name, table in sections.items()
    )


def section_override(item: str) -> dict:
    """``SECTION.KEY=VALUE`` (the value as JSON) as a one-key section."""
    path, value = item.split("=", 1)
    section, key = path.split(".", 1)
    return {section: {key: json.loads(value)}}


def merged_sections(base: dict, *overrides: dict) -> dict:
    out = {k: dict(v) for k, v in base.items()}
    for o in overrides:
        for name, table in o.items():
            out.setdefault(name, {}).update(table)
    return out


def start_broker(cfg, data_dir: str):
    """Bring-up as ``python -m zeebe_tpu`` does for one node: open every
    partition, bootstrap it alone, await leadership (the leader install
    builds the engine: autotune, selfcheck, warm)."""
    from zeebe_tpu.runtime.cluster_broker import ClusterBroker
    from zeebe_tpu.runtime.engines import engine_factory_from_config

    broker = ClusterBroker(cfg, data_dir, engine_factory=engine_factory_from_config(cfg))
    pids = range(cfg.cluster.partitions)
    for pid in pids:
        broker.open_partition(pid).join(60)
        broker.bootstrap_partition(pid, {})
    deadline = time.monotonic() + 600
    while not all(
        pid in broker.partitions and broker.partitions[pid].is_leader for pid in pids
    ):
        if time.monotonic() > deadline:
            raise RuntimeError("partition leaders were not installed within 600 s")
        time.sleep(0.02)
    return broker


def state_devices_of(servers: list) -> list:
    """The devices that hold the partitions' tables. The broker's actor
    donates the state to every step (a tick steps it even with no traffic),
    so a read from this thread can find the array deleted: read again. A
    witness run on the host engine has no device state."""
    for _ in range(50):
        try:
            return sorted({
                str(d) for s in servers if hasattr(s.engine, "state")
                for d in s.engine.state.ei_i32.devices()
            })
        except RuntimeError:
            time.sleep(0.01)
    raise RuntimeError("the engine state could not be read between two steps")


def dispatch_table() -> dict:
    from zeebe_tpu.tpu import autotune, pallas_ops

    return {
        "source": autotune.dispatch_source(),
        "families": {
            family: "pallas" if pallas_ops.use_pallas(family) else "xla"
            for family in pallas_ops.FAMILIES
        },
        "timings": autotune.dispatch_timings(),
    }


def jit_cache_sizes() -> dict:
    from zeebe_tpu.tpu import jit_registry

    return {n: row["cache_size"] for n, row in jit_registry.signature_report().items()}


def read_logs(data_dir: str, cfg) -> dict:
    """The committed log of every partition, re-read from disk."""
    from zeebe_tpu.log.logstream import LogStream
    from zeebe_tpu.log.storage import SegmentedLogStorage

    logs = {}
    for pid in range(cfg.cluster.partitions):
        storage = SegmentedLogStorage(
            os.path.join(data_dir, f"partition-{pid}"),
            segment_size=cfg.data.segment_size_bytes,
            native=cfg.data.native_storage,
        )
        log = LogStream(storage, partition_id=pid, recover_commit=True)
        logs[pid] = log.reader(0).read_committed()
        storage.close()
    return logs


def oracle_live_instances(logs: dict) -> int:
    """Second witness: the program's host interpreter replays each
    committed log; it must raise nothing and end with no live instance."""
    from zeebe_tpu.testing.chaos import replay_oracle

    live, repository = 0, None
    for pid in sorted(logs):
        oracle = replay_oracle(logs[pid], pid, len(logs), repository)
        repository = oracle.repository
        live += len(oracle.element_instances.instances)
    return live


def host_intervals(tracer, spans: list) -> list:
    """What the host was doing, as labelled intervals in wall-clock ns."""
    from zeebe_tpu.tracing import spans as spans_mod

    def wall_ns(t_us: int) -> int:
        return int((spans_mod._T0_WALL + t_us / 1e6) * 1e9)

    waves = [
        [wall_ns(w["t_dispatch_us"]), wall_ns(w["t_collect_us"])]
        for w in tracer.waves.snapshot() if w["t_collect_us"] > 0
    ]

    def between(a: str, b: str) -> list:
        out = []
        for s in spans:
            at = {x["stage"]: x["t_us"] for x in reversed(s["stages"])}
            if a in at and b in at and at[b] > at[a]:
                out.append([wall_ns(at[a]), wall_ns(at[b])])
        return out

    return [
        ("wave_dispatch_to_collect", waves),
        ("raft_queue_to_commit", between("raft_queue", "commit")),
        ("commit_to_wave_dispatch", between("commit", "wave_dispatch")),
        ("collect_to_response", between("device_collect", "response")),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m zbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="admit a platform that is no TPU, at the configuration's rehearsal size")
    ap.add_argument("--rate", type=float, default=None,
                    help="open loop only: another rate than the cell's (the sweep that found it)")
    ap.add_argument("--fault", default=None,
                    help="plant a fault of zbench/faults.py under the timed path (controls)")
    ap.add_argument("--set", action="append", default=[], metavar="SECTION.KEY=VALUE",
                    help="override a broker config key (a witness run on the host engine: "
                         "engine.type=\"host\")")
    args = ap.parse_args(argv)

    cell = spec.Cell(args.workload)
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" and not args.rehearsal:
        print(f"zbench: needs a TPU, JAX found {device}; --rehearsal admits it "
              "for a rehearsal, whose numbers are no device numbers", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"zbench: {cell.name} needs {cell.chips} chips, JAX found {device}",
              file=sys.stderr)
        return 2
    peak = spec.peak_for(device["kind"]) if device["platform"] == "tpu" else None

    import jaxlib

    from zeebe_tpu import compile_cache, native, tracing
    from zeebe_tpu.runtime import engines
    from zeebe_tpu.runtime.config import load_config
    from zeebe_tpu.runtime.metrics import event_count

    note("env", device=device, jax=jax.__version__, jaxlib=jaxlib.__version__,
         python=sys.version.split()[0], compile_cache=compile_cache.enable(),
         rehearsal=args.rehearsal, workload=cell.name, seed=args.seed,
         seconds=args.seconds, trace=args.trace, fault=args.fault)

    parts = {"imports_and_device": time.monotonic() - T_START}
    t = time.monotonic()
    if not native.available():
        print(f"zbench: native library unavailable: {native.build_error()}", file=sys.stderr)
        return 2
    parts["native"] = time.monotonic() - t

    workdir = tempfile.mkdtemp(prefix="zbench_")
    children: list = []
    broker = None
    try:
        sections = merged_sections(
            cell.config["broker"],
            cell.config.get("rehearsal", {}) if args.rehearsal else {},
            {"tracing": {"sampleRate": 1.0}} if args.trace else {},
            *(section_override(item) for item in args.set),
        )
        cfg_path = os.path.join(workdir, "zeebe.cfg.toml")
        with open(cfg_path, "w") as f:
            f.write(toml_of(sections))
        cfg = load_config(cfg_path)
        data_dir = os.path.join(workdir, "data", cfg.cluster.node_id)

        t = time.monotonic()
        broker = start_broker(cfg, data_dir)
        parts["boot"] = time.monotonic() - t
        parts.update({f"boot.{k}": v for k, v in engines.LAST_BOOT_SECONDS.items()})
        servers = [broker.partitions[p] for p in range(cfg.cluster.partitions)]
        state_devices = state_devices_of(servers)

        from zeebe_tpu.gateway.cluster_client import ClusterClient

        assumed = cell.config["assumed"]
        processes = cell.processes()
        graphs = {pid: mod.GRAPH for pid, mod in processes.items()}
        t = time.monotonic()
        client = ClusterClient(
            [broker.client_address], num_partitions=cfg.cluster.partitions,
            request_timeout_ms=300_000,
        )
        for pid in sorted(processes):
            model = processes[pid].build()
            flows = {f["id"] for f in graphs[pid]["flows"]}
            ids = set(model.elements)
            missing = (flows | set(graphs[pid]["nodes"])) - ids
            if missing:
                raise RuntimeError(f"process {pid}: GRAPH names {missing}, the model has {ids}")
            client.deploy_model(model)
        client.close()
        parts["deploy"] = time.monotonic() - t

        host, port = broker.client_address.host, broker.client_address.port
        common = {
            "host": host, "port": port, "partitions": cfg.cluster.partitions,
            "request_timeout_ms": assumed["request_timeout_ms"], "seed": args.seed,
        }
        job_types = sorted({
            n["job_type"] for g in graphs.values() for n in g["nodes"].values()
            if n["kind"] == "service_task"
        })
        t = time.monotonic()
        workers = Child("workers", {
            **common, "job_types": job_types, "job_credits": assumed["job_credits"],
        }, workdir)
        children.append(workers)
        traffic_spec = {k: v for k, v in cell.traffic.items() if k != "rehearsal"}
        if args.rehearsal:
            traffic_spec.update(cell.traffic.get("rehearsal", {}))
        if args.rate is not None:
            traffic_spec["rate_per_s"] = args.rate
        generator = Child("generator", {
            **common, **traffic_spec, "graphs": graphs, "seconds": args.seconds,
            "topic_subscription_credits": assumed["topic_subscription_credits"],
            "topic_subscription_ack_batch": assumed["topic_subscription_ack_batch"],
            "warm_timeout_s": CHILD_TIMEOUT_S, "turnover_timeout_s": TURNOVER_TIMEOUT_S - 20,
        }, workdir)
        children.append(generator)
        workers.wait_for("ready", 120)
        generator.wait_for("ready", 120)
        parts["children"] = time.monotonic() - t

        t = time.monotonic()
        generator.tell("warm")
        generator.wait_for("warm_done", CHILD_TIMEOUT_S)
        parts["warm_up"] = time.monotonic() - t

        readers = [m["reader"] for m in cell.per_layer]
        counter_names = sorted(layers.counters_needed(readers))
        snaps: dict = {}

        def snapshot(ev: dict) -> None:
            if ev["ev"] in ("window_start", "window_end"):
                snaps[ev["ev"]] = {
                    "counters": {n: event_count(n) for n in counter_names},
                    "device": [getattr(s.engine, "device_records_processed", 0) for s in servers],
                    "host": [getattr(s.engine, "host_records_processed", 0) for s in servers],
                    "at": ev["at"], "wall": time.time(), "mono": time.monotonic(),
                }

        generator.on_event = snapshot
        caches_before = jit_cache_sizes()
        t = time.monotonic()
        generator.tell("go")
        start_ev = generator.wait_for("window_start", TURNOVER_TIMEOUT_S)
        window_start = start_ev["at"]
        parts["turnover"] = time.monotonic() - t
        setup_s = window_start - T_START
        if args.fault:
            # a control: the set-up was sound, the window runs on a broken path
            faults.plant(args.fault, servers)

        traced = None
        if args.trace:
            trace_s = min(4.0, args.seconds / 3)
            trace_dir = os.path.join(workdir, "trace")
            time.sleep(max(0.0, window_start + (args.seconds - trace_s) / 2 - time.monotonic()))
            t_trace = time.monotonic()
            jax.profiler.start_trace(trace_dir)
            started_s = time.monotonic() - t_trace
            # the trace's clock starts with the session: one host
            # annotation stamped with the wall clock ties the two
            wall0 = time.time_ns()
            with jax.profiler.TraceAnnotation(trace_mod.SYNC_NAME):
                pass
            wall0 = (wall0 + time.time_ns()) // 2
            time.sleep(trace_s)
            wall1 = time.time_ns()
            t_stop = time.monotonic()
            jax.profiler.stop_trace()
            traced = {"dir": trace_dir, "wall_ns": (wall0, wall1), "start_s": started_s,
                      "stop_s": time.monotonic() - t_stop}

        generator.wait_for("window_end", args.seconds + 120)
        done_ev = generator.wait_for("done", cell.traffic["grace_s"] + 120)
        caches_after = jit_cache_sizes()
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[: cell.chips]
        ]
        memory_peak = int(max(peaks))
        generator.tell("stop")
        workers.tell("stop")
        gen = generator.finish()
        work = workers.finish()
        children.clear()

        tracer = tracing.TRACER
        spans = [s.to_dict() for s in tracer.spans()] if tracer is not None else []
        hosts = host_intervals(tracer, spans) if (args.trace and tracer is not None) else []
        host_lifecycle = sum(
            n for s in servers
            for (vt, _wf), n in getattr(s.engine, "host_records_by_kind", {}).items()
            if int(vt) in check.LIFECYCLE_VALUE_TYPES
        )
        engine_counts = {
            "device_records": sum(snaps["window_end"]["device"]) - sum(snaps["window_start"]["device"]),
            "host_records": sum(snaps["window_end"]["host"]) - sum(snaps["window_start"]["host"]),
            "host_records_by_kind": {
                f"vt{int(vt)}/wf{wf}": n for s in servers
                for (vt, wf), n in sorted(getattr(s.engine, "host_records_by_kind", {}).items())
            },
        }
        num_vars = cfg.engine.num_vars
        deadline = time.monotonic() + 10
        while any(s.next_read_position <= s.log.commit_position for s in servers):
            if time.monotonic() > deadline:
                break
            time.sleep(0.02)
        t = time.monotonic()
        broker.close()
        broker = None
        del servers
        gc.collect()
        close_s = time.monotonic() - t

        # -- the check: after the window, the peak read, the state freed --
        t = time.monotonic()
        if args.fault:
            faults.after_close(args.fault, data_dir)
        logs = read_logs(data_dir, cfg)
        rows_by_partition = {p: [check.normalize(r) for r in recs] for p, recs in logs.items()}
        try:
            oracle_live = oracle_live_instances(logs)
        except Exception as e:  # noqa: BLE001 - a replay that raises has failed
            note("oracle_raised", error=repr(e)[:500])
            oracle_live = 1
        compiled = sum(
            max(0, caches_after.get(n, 0) - caches_before.get(n, 0)) for n in caches_after
        ) if device["platform"] == "tpu" else 0
        children_with_jax = int(bool(gen.get("jax_imported", True))) + int(
            bool(work.get("jax_imported", True))
        ) + int(gen["exit_code"] != 0) + int(work["exit_code"] != 0)
        compared, first_mismatch = check.compare(
            rows_by_partition, gen, graphs, host_lifecycle, oracle_live, compiled,
            children_with_jax,
        )
        correct = check.is_correct(compared)
        check_s = time.monotonic() - t

        e2e = stats.end_to_end(gen)
        e2e["setup_s"] = setup_s
        w0, w1 = snaps["window_start"], snaps["window_end"]
        in_window = [
            r for rows in rows_by_partition.values() for r in rows
            if w0["wall"] * 1000 <= r.timestamp < w1["wall"] * 1000
        ]
        log_counts: dict = {}
        for r in in_window:
            kind = f"@log.{r.vtype}.{r.rtype}.{r.intent}"
            log_counts[kind] = log_counts.get(kind, 0) + 1
        jobs_created = log_counts.get("@log.0.0.1", 0)
        traced_ctx = None
        breakdown = None
        device_extra = {}
        if traced is not None:
            doc = trace_mod.events_of(traced["dir"])
            lo, hi = traced["wall_ns"]
            # wall clock = trace clock + offset (None: no annotation found,
            # then the whole trace is the window and no gap is labelled)
            offset = lo - doc["sync_ns"] if doc["sync_ns"] is not None else None
            red = trace_mod.reduce(
                doc,
                window_ns=(lo - offset, hi - offset) if offset is not None else None,
                host_intervals=[
                    (label, [[a - offset, b - offset] for a, b in spans_])
                    for label, spans_ in hosts
                ] if offset is not None else None,
            )
            note("trace", start_s=traced["start_s"], stop_s=traced["stop_s"],
                 clock_offset_ns=offset, reduction=red)
            if red is not None:
                device_extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
                breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
                traced_ctx = {"doc": doc, "window_ns": tuple(red["window_ns"]),
                              "wall_ns": (lo, hi), "reduction": red}
        # what a per-layer reader can read: zbench/layers.py
        ctx = {
            "spans": [
                s for s in spans if s["stages"] and
                _span_in_window(s, w0["mono"], w1["mono"])
            ],
            "counters": {n: w1["counters"][n] - w0["counters"][n] for n in counter_names},
            "derived": {
                "@committed_records": len(in_window),
                "@instances_completed": round(e2e["instances_per_s"] * (w1["at"] - w0["at"])),
                **log_counts,
            },
            "values": e2e,
            "gen": gen,
            "rows": rows_by_partition,
            "window_wall_ms": (w0["wall"] * 1000, w1["wall"] * 1000),
            "trace": traced_ctx,
            "device": device,
            "peak": peak,
            "num_vars": num_vars,
        }

        note("setup", setup_s=setup_s, parts=parts)
        note("dispatch", **dispatch_table())
        note("run", state_devices=state_devices, memory_peak_bytes=memory_peak,
             jit_cache_sizes_before=caches_before, jit_cache_sizes_after=caches_after,
             generator={k: v for k, v in gen.items() if k != "rows"},
             creates=len(gen["rows"]), workers=work, engine=engine_counts,
             activations_per_job=(
                 log_counts.get("@log.0.0.3", 0) / jobs_created if jobs_created else None
             ),
             gen_late_p95_ms=e2e.get("gen_late_p95_ms"), drained=done_ev.get("drained"),
             counters=ctx["counters"], derived=ctx["derived"], spans=len(ctx["spans"]),
             close_s=close_s, check_s=check_s, first_mismatch=first_mismatch,
             e2e=e2e,
             # the same readings over the window's first seconds: how the
             # spread falls with the length, from one set of runs
             e2e_by_seconds={
                 str(n): stats.end_to_end({**gen, "window_end": gen["window_start"] + n})
                 for n in (10, 20, 30, 40) if n < args.seconds
             })

        metrics = {}
        if args.trace:
            for m in cell.per_layer:
                if m["source"] == "device_trace" and device["platform"] != "tpu":
                    continue  # a rehearsal prints no number under a device metric's name
                value = layers.read(m["name"], m["reader"], ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        result = {
            "correct": correct, "attempted": e2e["attempted"], "failed": e2e["failed"],
            "metrics": metrics,
            "device": {**device, "memory_peak_bytes": memory_peak, **device_extra},
        }
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["compared"] = compared
        sys.stdout.flush()
        for name, (value, limit) in compared.items():
            print(f"zbench compared {name}: {value} (limit {limit})", file=sys.stderr)
        print(f"zbench correct: {correct}", file=sys.stderr, flush=True)
        print(json.dumps(result), flush=True)
        return 0
    finally:
        for c in children:
            c.kill()
        if broker is not None:
            try:
                broker.close()
            except Exception:  # noqa: BLE001 - already failing; report the first error
                pass
        shutil.rmtree(workdir, ignore_errors=True)


def _span_in_window(span: dict, mono0: float, mono1: float) -> bool:
    from zeebe_tpu.tracing import spans as spans_mod

    t_us = span["stages"][0]["t_us"]
    mono = (spans_mod._T0_NS + t_us * 1000) / 1e9
    return mono0 <= mono < mono1
