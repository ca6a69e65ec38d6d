"""``python3 -m zbench.selfcheck``: the harness checks itself, here on the
CPU, in under two minutes. Arithmetic on made-up samples, the trace
reduction on the small recorded trace, the roofline's byte count on a
hand-made log, the loader's refusals, the controls of the check on
hand-made logs, the reference against its golden digests, and one
rehearsal run end to end."""

from __future__ import annotations

import hashlib
import importlib
import json
import math
import os
import subprocess
import sys

from zbench import check, faults, layers, reference as ref, roofline, spec, stats, traffic
from zbench import trace as trace_mod

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "fixtures", "reference_golden.json")


def expect(cond, what: str) -> None:
    if not cond:
        raise SystemExit(f"selfcheck FAILED: {what}")
    print(f"ok  {what}")


def arithmetic() -> None:
    expect(stats.percentile([1, 2, 3, 4, 5], 50) == 3, "median of 1..5 is 3")
    expect(abs(stats.percentile(range(1, 101), 95) - 95.05) < 1e-9, "p95 of 1..100 interpolates")
    expect(math.isinf(stats.percentile([1] * 10 + [math.inf], 95)),
           "an instance that never completed sits in the tail")

    def report(stall: bool) -> dict:
        # 10 s window, one create due every 0.1 s, each done 0.5 s later;
        # with a stall the broker answers nothing between 4 s and 6 s
        rows = []
        for i in range(100):
            due = 100.0 + i * 0.1
            done = due + 0.5
            if stall and 104.0 <= done < 106.0:
                done = 106.0 + (done - 104.0) * 0.1
            rows.append({"due": due, "sent": due, "acked": due + 0.1, "done": done, "key": i})
        return {"window_start": 100.0, "window_end": 110.0, "rows": rows}

    calm, stalled = stats.end_to_end(report(False)), stats.end_to_end(report(True))
    expect(abs(calm["instances_per_s"] - 9.5) < 1e-9, "rate counts completions inside the window")
    expect(abs(calm["complete_p95_ms"] - 500) < 1e-6, "latency is taken from the due time")
    expect(stalled["complete_p95_ms"] > 2 * calm["complete_p95_ms"],
           "a stall in the window moves complete_p95_ms")
    late = report(False)
    for r in late["rows"][50:]:
        del r["done"]
    late = stats.end_to_end(late)
    expect(late["instances_per_s"] < calm["instances_per_s"] and late["failed"] == 50
           and math.isinf(late["complete_p95_ms"]),
           "work that never completes lowers the rate, counts as failed and as missing")
    expect(abs(stats.spread([10, 10, 10.4, 10.6, 11, 11]) - 0.09523809) < 1e-6,
           "spread is the driver's: quartiles of statistics.quantiles over the median")
    gaps = traffic.arrival_offsets(20.0, 30.0, 1)
    other = traffic.arrival_offsets(20.0, 30.0, 2**31 + 7)
    diffs = lambda xs: sorted(round(b - a, 9) for a, b in zip(xs, xs[1:]))  # noqa: E731
    expect(len(gaps) == len(other) == 600 and gaps != other,
           "every seed gets the same number of arrivals, in another order")
    plan_a = traffic.Plan({"a": 0.5, "b": 0.5}, {}, 1)
    plan_b = traffic.Plan({"a": 0.5, "b": 0.5}, {}, 2**31 + 7)
    pa = [plan_a.next()[1] for _ in range(40)]
    pb = [plan_b.next()[1] for _ in range(40)]
    expect(sorted(pa) == sorted(pb) and pa.count("a") == 20, "the mix's shares are exact per block")


def trace_reduction() -> None:
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        doc = json.load(f)
    with open(os.path.join(HERE, "fixtures", "trace_small.expected.json")) as f:
        want = json.load(f)
    lo, hi = want["window_ns"]
    red = trace_mod.reduce(doc, window_ns=(lo, hi), host_intervals=[("host_a", want["host_a"])])

    def readers(doc: dict, red: dict, window: tuple) -> dict:
        # the per-layer readers that reduce a trace, as a traced run calls them
        ctx = {"trace": {"doc": doc, "window_ns": window, "wall_ns": window, "reduction": red}}
        return {
            "device_idle_share": layers.read(
                "device_idle_share", {"kind": "module", "module": "device_idle_share"}, ctx),
            "step_ms": layers.read(
                "step_ms", {"kind": "module", "module": "program_ms", "match": want["step_match"]}, ctx),
            "step_launches": len(trace_mod.program_launches(doc, window, want["step_match"])),
        }

    got = {**red, **readers(doc, red, (lo, hi))}
    for key in ("busy_s", "window_s", "device_idle_share", "step_ms", "step_launches"):
        expect(abs(got[key] - want[key]) <= 1e-9 * max(1.0, abs(want[key])),
               f"recorded trace: {key} = {want[key]}")
    expect(red["device_ops"][0][0] == want["top_op"], f"recorded trace: top op {want['top_op']}")
    labels = dict(red["idle_gaps"])
    expect(abs(labels.get("host_a", 0) - want["host_a_s"]) < 1e-9
           and abs(sum(labels.values()) - (red["window_s"] - red["busy_s"])) < 1e-6,
           "idle gaps are labelled by what overlaps them and add up to the idle time")
    # made-up trace: two ops of 2 ms in a 10 ms window, one gap labelled
    toy = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(1)", 1_000_000, 2_000_000], ["jit_step(1)", 6_000_000, 2_000_000]]},
        {"name": "XLA Ops", "events": [["gather.1", 1_000_000, 1_500_000], ["scatter.2", 2_000_000, 1_000_000], ["gather.1", 6_000_000, 2_000_000]]},
    ]}]}
    red = trace_mod.reduce(toy, window_ns=(0, 10_000_000),
                           host_intervals=[("wave", [[3_000_000, 5_000_000]])])
    want = {"step_match": "step"}
    got = readers(toy, red, (0, 10_000_000))
    expect(abs(red["busy_s"] - 0.004) < 1e-12 and abs(got["device_idle_share"] - 60.0) < 1e-9,
           "busy is the union of overlapping ops: 4 ms of 10, idle 60 %")
    expect(got["step_ms"] == 2.0 and got["step_launches"] == 2, "step time per launch from the modules line")
    expect(dict(red["idle_gaps"]) == {"wave": 0.002, "unattributed": 0.004},
           "a gap is split between the label that overlaps it and unattributed")
    expect(trace_mod.reduce({"planes": [{"name": "/host:CPU", "lines": []}]}) is None,
           "a trace with no device operation reduces to nothing, not to 0")


def roofline_bytes() -> None:
    widths, _ = roofline.row_bytes(16)
    expect(widths["element_instance"] == 240 and widths["job"] == 248 and widths["lane"] == 256,
           "row bytes at numVars 16: element instance 240, job 248, lane 256")
    # a hand-made log: 3 workflow-instance records, 2 job records, 1 deployment
    log = [5, 5, 5, 0, 0, 4]
    want = 3 * (2 * 256 + 3 * 240) + 2 * (2 * 256 + 2 * 248 + 240)
    expect(roofline.least_bytes(log, 16) == want, f"hand-made log moves {want} B at the least")
    share = roofline.share_pct(log, 16, 1e-3, 819e9)
    expect(abs(share - 100 * want / 819e9 / 1e-3) < 1e-12 and share > 0,
           "the share is a float in %, never rounded to 0")
    expect(roofline.share_pct(log, 16, 0.0, 819e9) is None, "no program time, no share")
    rows = {0: [check.Row(i, 0, vt, 0, i, 5, None, None, None, None) for i, vt in enumerate(log)]}
    toy = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(1)", 1_000_000, 1_000_000]]}]}]}
    ctx = {"trace": {"doc": toy, "window_ns": (0, 10_000_000), "wall_ns": (0, 10_000_000)},
           "rows": rows, "num_vars": 16, "peak": {"hbm_bytes_per_s": 819e9}}
    reader = {"kind": "module", "module": "program_roofline", "match": "step"}
    expect(abs(layers.read("step_roofline", reader, ctx) - share) < 1e-12,
           "the roofline's reader counts the records stepped in the traced seconds from the log")
    expect(layers.read("step_roofline", reader, {**ctx, "trace": None}) is None,
           "without a trace the roofline's reader returns nothing, not 0")
    ratio = {"kind": "counter_ratio", "num": ["@log.0.0.3"], "den": ["@log.0.0.1"]}
    expect(layers.read("a", ratio, {"derived": {"@log.0.0.3": 5, "@log.0.0.1": 4}}) == 1.25
           and layers.read("a", ratio, {"derived": {"@log.5.0.1": 4}}) is None,
           "activations per job from the log's counts; a log with no job gives nothing")
    bursts = traffic.arrival_offsets(20.0, 50.0, 7, {"factor": 10, "burst_s": 1, "period_s": 5})
    calm = traffic.arrival_offsets(20.0, 50.0, 7)
    share_in = sum(1 for t in bursts if t % 5 < 1) / len(bursts)
    expect(len(bursts) == len(calm) and bursts == sorted(bursts) and abs(share_in - 10 / 14) < 0.05,
           "bursts warp the same arrivals in time: 10 of 14 fall into the bursting fifth")


def refusals() -> None:
    def refused(fn, *a) -> bool:
        try:
            fn(*a)
        except spec.SpecError:
            return True
        return False

    expect(refused(spec.peak_for, "TPU v9 imaginary"), "an unknown device_kind is refused")
    expect(spec.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9, "v5e: 819 GB/s")
    expect(refused(spec.check_name, "two words"), "a name with a space is refused")
    expect(refused(spec.check_unit, "instances per second"), "a unit over 16 characters is refused")
    expect(refused(spec.Cell, "no-such.cell"), "an unknown workload is refused")
    for w in (w for d in spec.documents() for w in d["workloads"]):
        cell = spec.Cell(w["name"])
        expect(cell.per_layer and len(cell.end_to_end) >= 2,
               f"{w['name']}: every file it names loads")
        for m in cell.per_layer:
            expect(layers.read(m["name"], m["reader"], {}) is None,
                   f"{m['name']}: a reader with nothing to read returns nothing")


def controls_of_the_check() -> None:
    """Hand-made logs: a sound one passes; one instance completed twice,
    one acknowledged create missing, one record on the host engine each
    turn ``correct`` false."""
    graph = spec.Cell("order-1p.saturated").processes()["order-process"].GRAPH
    graphs = {"order-process": graph}

    def log_of(key: int, payload: dict, pos0: int) -> list:
        want = ref.expected(graph, payload, traffic.worker_result)
        rows, pos = [], pos0
        for intent, element, p in want["events"]:
            rows.append(check.Row(pos, 0, 5, intent, key, 1000 + pos, key, element, p, None))
            pos += 1
            if intent == ref.ELEMENT_ACTIVATED and element == "collect-money":
                jtype, at_creation, result = want["jobs"][0]
                for ji, jp in ((1, at_creation), (3, at_creation), (5, result)):
                    rows.append(check.Row(pos, 0, 0, ji, key + 1, 1000 + pos, key, None, jp, jtype))
                    pos += 1
        return rows

    def sound():
        rows, gen_rows = [], []
        for i in range(3):
            payload = {"orderId": i, "orderValue": 99, "customer": "c"}
            key = 10 * (i + 1)
            rows += log_of(key, payload, len(rows))
            gen_rows.append({"seq": i, "process": "order-process", "partition": 0,
                             "payload": payload, "key": key, "sent": 1.0, "acked": 1.1, "done": 1.5})
        return {0: rows}, {"rows": gen_rows, "unmatched_completions": 0}

    def verdict(logs, gen, host=0):
        compared, _ = check.compare(logs, gen, graphs, host, 0, 0, 0)
        return check.is_correct(compared), compared

    ok, _ = verdict(*sound())
    expect(ok, "a sound hand-made log is correct")
    logs, gen = sound()
    last = [r for r in logs[0] if r.instance == 20][-1]
    logs[0].append(last._replace(position=len(logs[0])))
    ok, c = verdict(logs, gen)
    expect(not ok and c["completed_not_once"][0] == 1, "an instance completed twice is not correct")
    logs, gen = sound()
    logs[0] = [r for r in logs[0] if r.instance != 30]
    ok, c = verdict(logs, gen)
    expect(not ok and c["acked_not_in_log"][0] == 1,
           "an acknowledged create missing from the re-read log is not correct")
    ok, c = verdict(*sound(), host=1)
    expect(not ok and c["host_lifecycle_records"][0] == 1,
           "a lifecycle record on the host engine is not correct")
    logs, gen = sound()
    i = next(i for i, r in enumerate(logs[0]) if r.intent == ref.ELEMENT_COMPLETED and r.element == "collect-money")
    logs[0][i] = logs[0][i]._replace(payload={**logs[0][i].payload, "receipt": -1})
    ok, c = verdict(logs, gen)
    expect(not ok and c["reference_mismatches"][0] == 1, "an altered answer is not correct")
    # the gateway's rule: the branch taken is the one the condition selects
    route = spec.Cell("route-1p.saturated").processes()["route-order"].GRAPH
    for value, end in ((250, "end-priority"), (100, "end-priority"), (99, "end-normal")):
        want = ref.expected(route, {"orderValue": value}, traffic.worker_result)
        expect(want["events"][-3][:2] == (ref.END_EVENT_OCCURRED, end) and not want["jobs"],
               f"route-order with orderValue {value} ends at {end}, with no job")
    payload = {"orderId": 1, "orderValue": 250, "customer": "c"}
    wrong = ref.expected(route, {**payload, "orderValue": 40}, traffic.worker_result)
    rows = [check.Row(i, 0, 5, intent, 10, 1000 + i, 10, element, {**p, "orderValue": 250}, None)
            for i, (intent, element, p) in enumerate(wrong["events"])]
    gen = {"rows": [{"seq": 0, "process": "route-order", "partition": 0, "payload": payload,
                     "key": 10, "sent": 1.0, "acked": 1.1, "done": 1.5}], "unmatched_completions": 0}
    compared, first = check.compare({0: rows}, gen, {"route-order": route}, 0, 0, 0, 0)
    expect(not check.is_correct(compared) and compared["reference_mismatches"][0] == 1
           and first["instance"] == 10 and first["records"],
           "a branch taken against its condition is not correct, and the instance's records are named")


def process_graphs() -> dict:
    """process id -> ``GRAPH`` of every process module under ``processes/``."""
    graphs = {}
    for name in sorted(os.listdir(os.path.join(HERE, "processes"))):
        if name.endswith(".py") and not name.startswith("_"):
            graph = importlib.import_module("zbench.processes." + name[:-3]).GRAPH
            graphs[graph["id"]] = graph
    return graphs


def reference_digest(graph: dict, variant: int) -> str:
    """sha256 of the JSON of ``reference.expected`` over eight create
    payloads of one payload variant."""
    got = []
    for i in range(8):
        payload = {"orderId": i * 37 + variant, "orderValue": traffic.ORDER_VALUES[i],
                   "customer": f"c-{i % 7}"}
        payload.update(graph["payload_variants"][variant])
        got.append(ref.expected(graph, payload, traffic.worker_result))
    return hashlib.sha256(json.dumps(got, sort_keys=True).encode()).hexdigest()


def reference_is_the_golden() -> None:
    with open(GOLDEN) as f:
        golden = json.load(f)["digests"]
    got = {
        f"{pid}/{v}": reference_digest(graph, v)
        for pid, graph in process_graphs().items() for v in range(len(graph["payload_variants"]))
    }
    expect(got == golden, f"reference.expected gives to the byte what fixtures/reference_golden.json "
                          f"holds, for all {len(golden)} process x payload variant cases")


# start -> an intermediate message catch on orderId -> end, as a process
# module would give it; the traffic publishes its message
WAITING = {
    "id": "payment-wait",
    "start": "start",
    "nodes": {
        "start": {"kind": "start"},
        "paid": {"kind": "message_catch", "message": "payment-received",
                 "correlation_var": "orderId"},
        "end": {"kind": "end"},
    },
    "flows": [
        {"id": "flow-start-paid-0", "from": "start", "to": "paid"},
        {"id": "flow-paid-end-1", "from": "paid", "to": "end"},
    ],
    "payload_variants": [{}],
}
MESSAGES = {"payment-wait": {"name": "payment-received", "correlation_var": "orderId",
                             "delay_ms": 0, "ttl_ms": 60000}}


def publishing_log(n: int = 8) -> tuple:
    """A sound hand-made log of ``n`` instances of ``WAITING``, one message
    published for each, and the generator's report of it."""
    rows, gen_rows = [], []
    for i in range(n):
        payload = {"orderId": i, "orderValue": 99, "customer": "c"}
        key, message_key = 10 * (i + 1), 1000 + i
        message = traffic.message_for("payment-wait", payload)
        rows.append(check.Row(len(rows), 0, check.MESSAGE, check.MESSAGE_PUBLISHED, message_key,
                              1000, None, "payment-received", message, None, str(i)))
        want = ref.expected(WAITING, payload, traffic.worker_result)
        for intent, element, p in want["events"]:
            rows.append(check.Row(len(rows), 0, 5, intent, key, 1000, key, element, p, None))
        gen_rows.append({
            "seq": i, "process": "payment-wait", "partition": 0, "payload": payload, "key": key,
            "sent": 1.0, "acked": 1.1, "done": 1.5,
            "publish": {"due": 1.1, "correlation_key": str(i), "sent": 1.1, "acked": 1.2,
                        "key": message_key},
        })
    return {0: rows}, {"rows": gen_rows, "unmatched_completions": 0, "messages": MESSAGES}


def lost_publish_on_disk(messages: list, gen: dict) -> list:
    """``messages`` (``check.Row`` of MESSAGE records) written by the
    program's log to a data directory, ``lost_publish`` planted there as a
    run plants it after close, and the log re-read from disk."""
    import tempfile

    from zeebe_tpu.log.logstream import LogStream
    from zeebe_tpu.log.storage import SegmentedLogStorage
    from zeebe_tpu.protocol.enums import RecordType, ValueType
    from zeebe_tpu.protocol.metadata import RecordMetadata
    from zeebe_tpu.protocol.records import MessageRecord, Record

    with tempfile.TemporaryDirectory() as data_dir:
        path = os.path.join(data_dir, "partition-0")
        storage = SegmentedLogStorage(path)
        LogStream(storage, partition_id=0).append([
            Record(key=r.key, metadata=RecordMetadata(record_type=RecordType(r.rtype),
                                                      value_type=ValueType.MESSAGE, intent=r.intent),
                   value=MessageRecord(name=r.element, correlation_key=r.correlation,
                                       payload=r.payload))
            for r in messages
        ])
        storage.close()
        faults.after_close("lost_publish", data_dir, gen)
        storage = SegmentedLogStorage(path)
        back = LogStream(storage, partition_id=0).reader(0).read_committed()
        storage.close()
    return [check.normalize(r) for r in back]


def controls_of_the_publisher() -> None:
    """Hand-made logs of a process that waits for a message: a sound one
    passes with both publish counts at 0; a refused publish, an
    acknowledged publish missing from the log and the ``lost_publish``
    fault, planted in a log on disk, each turn ``correct`` false; without
    messages neither count is there."""
    graphs = {"payment-wait": WAITING}

    def verdict(logs, gen):
        compared, _ = check.compare(logs, gen, graphs, 0, 0, 0, 0)
        return check.is_correct(compared), compared

    ok, c = verdict(*publishing_log())
    expect(ok and c["publish_errors"] == [0, 0] and c["acked_publish_not_in_log"] == [0, 0],
           "a sound hand-made log of published messages is correct")
    logs, gen = publishing_log()
    del gen["rows"][3]["publish"]["key"]
    gen["rows"][3]["publish"]["error"] = "TimeoutError()"
    ok, c = verdict(logs, gen)
    expect(not ok and c["publish_errors"][0] == 1, "a publish that timed out is not correct")
    logs, gen = publishing_log()
    del gen["rows"][5]["publish"]
    ok, c = verdict(logs, gen)
    expect(not ok and c["publish_errors"][0] == 1, "a publish that never went out is not correct")
    logs, gen = publishing_log()
    logs[0] = [r for r in logs[0] if not (r.vtype == check.MESSAGE and r.key == 1002)]
    ok, c = verdict(logs, gen)
    expect(not ok and c["acked_publish_not_in_log"][0] == 1,
           "an acknowledged publish missing from the re-read log is not correct")
    logs, gen = publishing_log()
    messages = [r for r in logs[0] if r.vtype == check.MESSAGE]
    back = lost_publish_on_disk(messages, gen)
    expect([(r.position, r.key) for r in back] == [(i, r.key) for i, r in enumerate(messages)]
           and [r.vtype for r in back].count(check.MESSAGE) == 6,
           "lost_publish leaves the log whole on disk, at the same positions, "
           "and the messages of the first and the eighth of 8 publishes without a value")
    logs[0] = [r for r in logs[0] if r.vtype != check.MESSAGE] + back
    ok, c = verdict(logs, gen)
    expect(not ok and c["acked_publish_not_in_log"][0] == 2 and c["reference_mismatches"][0] == 0,
           "lost_publish on disk is caught by acked_publish_not_in_log")
    logs, gen = publishing_log()
    del gen["messages"]
    _, c = verdict(logs, gen)
    expect("publish_errors" not in c and "acked_publish_not_in_log" not in c,
           "a traffic without messages adds no publish count")
    expect(faults.applies("lost_publish", {"messages": MESSAGES})
           and not faults.applies("lost_publish", {}),
           "lost_publish applies only to a cell whose traffic publishes messages")


def rehearsal() -> None:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    docs = spec.documents()
    listed = next(w["name"] for w in docs[0]["workloads"] if w["traffic"] == "saturated")

    def cmd(cell: str) -> list:
        return [sys.executable, "-m", "zbench", "--workload", cell,
                "--seed", str(2**31 + 11), "--seconds", "3", "--trace", "0"]

    refused = subprocess.run(cmd(listed), cwd=spec.CHECKOUT, env=env, capture_output=True, text=True)
    expect(refused.returncode != 0 and not refused.stdout.strip(),
           "without a chip and without --rehearsal the run fails and prints no result")
    run = subprocess.run(cmd(listed) + ["--rehearsal"], cwd=spec.CHECKOUT, env=env,
                         capture_output=True, text=True, timeout=600)
    expect(run.returncode == 0,
           f"the rehearsal run of {listed} exits 0" + (run.stderr[-400:] if run.returncode else ""))
    result = json.loads(run.stdout.strip().splitlines()[-1])
    expect(result["correct"] is True and result["failed"] == 0, "the rehearsal run is correct")
    expect(result["device"]["platform"] == "cpu", "the reported device is the one that ran")
    expect(set(result["metrics"]) == {"instances_per_s", "setup_s"},
           "a --trace 0 line carries the cell's end-to-end metrics")
    expect(result["compared"]["children_with_jax"] == [0, 0],
           "generator and workers never imported jax")
    expect(list(result)[-1] == "compared", "the numbers compared come last in the line")
    # a pending cell waits for a repair of the program: its files must still
    # run to a result line; the verdict is the program's and is only printed
    for w in (docs[1]["workloads"] if len(docs) > 1 else [])[:1]:
        run = subprocess.run(cmd(w["name"]) + ["--rehearsal"], cwd=spec.CHECKOUT, env=env,
                             capture_output=True, text=True, timeout=600)
        expect(run.returncode == 0,
               f"pending cell {w['name']} still runs" + (run.stderr[-400:] if run.returncode else ""))
        result = json.loads(run.stdout.strip().splitlines()[-1])
        over = {k: v for k, v in result["compared"].items() if v[0] > v[1]}
        print(f"    pending {w['name']}: correct {result['correct']} {over}")


def main() -> int:
    arithmetic()
    trace_reduction()
    roofline_bytes()
    refusals()
    controls_of_the_check()
    reference_is_the_golden()
    controls_of_the_publisher()
    if "--no-run" not in sys.argv:
        rehearsal()
    print("selfcheck passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
