"""The child processes of a run: one load generator, one worker pool. They
talk to the broker's client socket through ``ClusterClient`` and never
import JAX (asserted after import and at exit), so the parent alone holds
the chip and the broker does not share its interpreter lock with the load.

    python3 -m zbench.client_proc generator|workers <spec.json>

Events go to standard output as JSON lines; commands (``warm``, ``go``,
``stop``) come on standard input.
"""

from __future__ import annotations

import importlib
import json
import sys
import threading
import time

from zbench import spec as spec_mod
from zbench import traffic

WI_VALUE_TYPE = 5  # protocol: ValueType.WORKFLOW_INSTANCE
EVENT = 0  # protocol: RecordType.EVENT
ELEMENT_COMPLETED = 9  # protocol: WorkflowInstanceIntent.ELEMENT_COMPLETED


def emit(ev: str, **fields) -> None:
    print(json.dumps({"ev": ev, "t": time.monotonic(), **fields}), flush=True)


def expect(command: str) -> None:
    line = sys.stdin.readline().strip()
    if line != command:
        raise SystemExit(f"expected command {command!r}, got {line!r}")


def connect(spec: dict):
    from zeebe_tpu.gateway.cluster_client import ClusterClient
    from zeebe_tpu.transport import RemoteAddress

    if "jax" in sys.modules:
        raise SystemExit("the client import loaded jax")
    return ClusterClient(
        [RemoteAddress(spec["host"], spec["port"])],
        num_partitions=spec["partitions"],
        request_timeout_ms=spec["request_timeout_ms"],
    )


class Generator:
    def __init__(self, spec: dict):
        self.spec = spec
        self.client = connect(spec)
        self.partitions = spec["partitions"]
        self.graphs = spec["graphs"]
        self.lock = threading.Lock()
        self.rows: list = []
        self.by_key: dict = {}
        self.early: dict = {}
        self.completed = 0
        self.sub_records = 0
        self.stopping = False
        self.slots = threading.Semaphore(0)
        self.plan = traffic.Plan(
            spec["mix"],
            {pid: g["payload_variants"] for pid, g in self.graphs.items()},
            spec["seed"],
        )
        self.subs = [
            self.client.open_topic_subscription(
                f"zbench-{p}", self.on_record, partition_id=p,
                credits=spec["topic_subscription_credits"],
                ack_batch=spec["topic_subscription_ack_batch"],
            )
            for p in range(self.partitions)
        ]

    # the subscription: how a client of this era learns an instance ended
    def on_record(self, partition: int, record) -> None:
        self.sub_records += 1
        md = record.metadata
        if (
            int(md.value_type) != WI_VALUE_TYPE
            or int(md.record_type) != EVENT
            or int(md.intent) != ELEMENT_COMPLETED
            or record.key != record.value.workflow_instance_key
        ):
            return
        now = time.monotonic()
        with self.lock:
            row = self.by_key.get((partition, record.key))
            if row is None:
                self.early.setdefault((partition, record.key), []).append(now)
                return
            self._mark_done(row, now)

    def _mark_done(self, row: dict, now: float) -> None:
        if "done" in row:
            row["extra_completions"] = row.get("extra_completions", 0) + 1
            return
        row["done"] = now
        self.completed += 1
        self.slots.release()

    def create(self, due: float | None = None, pid: str | None = None) -> dict:
        with self.lock:
            seq, pid, payload = self.plan.next(pid)
        partition = seq % self.partitions
        row = {"seq": seq, "process": pid, "partition": partition, "payload": payload}
        if due is not None:
            row["due"] = due
        row["sent"] = time.monotonic()
        try:
            rsp = self.client.create_instance(pid, payload, partition)
            row["acked"] = time.monotonic()
            row["key"] = rsp.value.workflow_instance_key
        except Exception as e:  # noqa: BLE001 - a failed create is a counted result
            row["error"] = repr(e)[:300]
        with self.lock:
            self.rows.append(row)
            if "key" in row:
                self.by_key[(partition, row["key"])] = row
                for t in self.early.pop((partition, row["key"]), []):
                    self._mark_done(row, t)
        if "key" not in row:
            self.slots.release()  # a refused create frees its slot
        return row

    def wait_done(self, rows: list, timeout_s: float) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if all("done" in r or "key" not in r for r in rows):
                return True
            time.sleep(0.01)
        return False

    def warm(self) -> None:
        """One instance per process and payload variant: whatever compiles,
        compiles here."""
        rows = [
            self.create(pid=pid)
            for pid in sorted(self.spec["mix"])
            for _ in self.graphs[pid]["payload_variants"]
        ]
        if not self.wait_done(rows, self.spec["warm_timeout_s"]):
            raise SystemExit("warm-up instances did not complete")
        if any("error" in r for r in rows):
            raise SystemExit(f"warm-up create failed: {rows}")

    def main(self) -> None:
        emit("ready")
        expect("warm")
        self.warm()
        emit("warm_done")
        expect("go")
        # the generator kind is a file found by its name, as a process is
        kind = importlib.import_module(
            "zbench.generators." + spec_mod.check_name(self.spec["generator"], "generator")
        )
        start, end = kind.run(self, emit)
        with self.lock:
            rows = list(self.rows)
        drained = self.wait_done(rows, self.spec["grace_s"])
        grace_end = time.monotonic()
        with self.lock:
            rows = list(self.rows)
        emit("done", drained=drained)
        expect("stop")
        for s in self.subs:
            s.close()
        self.client.close()
        with open(self.spec["out"], "w") as f:
            json.dump(
                {
                    "window_start": start, "window_end": end, "grace_end": grace_end,
                    "drained": drained, "sub_records": self.sub_records,
                    "unmatched_completions": len(self.early),
                    "jax_imported": "jax" in sys.modules, "rows": rows,
                },
                f,
            )


def workers_main(spec: dict) -> None:
    """The worker pool: one ``open_job_worker`` per job type, the client's
    own worker (``RemoteJobWorker``: every push is handled and answered with
    a COMPLETE, credits returned). Activation is at-least-once, so a job key
    can be pushed again; the pool answers that push too, as any client
    does, and only counts it."""
    client = connect(spec)
    lock = threading.Lock()
    pushes: dict = {}
    by_type: dict = {}

    def handler(partition: int, record) -> dict:
        with lock:
            key = (partition, record.key)
            pushes[key] = pushes.get(key, 0) + 1
            by_type[record.value.type] = by_type.get(record.value.type, 0) + 1
        return traffic.worker_result(record.value.payload)

    workers = [
        client.open_job_worker(t, handler, worker_name="zbench", credits=spec["job_credits"])
        for t in spec["job_types"]
    ]
    emit("ready")
    expect("stop")
    for w in workers:
        w.close()
    client.close()
    with open(spec["out"], "w") as f:
        json.dump(
            {
                "pushes": by_type, "jobs": len(pushes),
                "duplicate_pushes": sum(n - 1 for n in pushes.values()),
                "jax_imported": "jax" in sys.modules,
            },
            f,
        )


def main() -> int:
    role, path = sys.argv[1], sys.argv[2]
    with open(path) as f:
        spec = json.load(f)
    if role == "generator":
        Generator(spec).main()
    elif role == "workers":
        workers_main(spec)
    else:
        raise SystemExit(f"unknown role {role!r}")
    if "jax" in sys.modules:
        raise SystemExit("a child process imported jax")
    emit("exit")
    return 0


if __name__ == "__main__":
    sys.exit(main())
