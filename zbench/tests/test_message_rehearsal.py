"""A process that waits for a message, through the served path at the
rehearsal size, in a copy of the harness to which only NEW files and new
``BENCHMARK.json`` entries were added: what a later PR that adds such a
deployment has to do, and all it may do.

The process is start -> an intermediate message catch on ``orderId`` ->
end; the workload's ``messages`` block has the generator publish one
message a create, ``delay_ms`` after its acknowledgement. The runs use the
host engine (``engine.type = "host"``) as the harness's witness: on the
device engine a message published in the same wave as its subscription's
OPEN is never correlated (``PERF.md``, Open questions), a fault of the
program that the message deployment has to repair first."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(HERE)
CELL = "msg-1p.saturated"

PROCESS = '''GRAPH = {
    "id": "payment-wait",
    "start": "start",
    "nodes": {
        "start": {"kind": "start"},
        "payment-received": {"kind": "message_catch", "message": "payment-received",
                             "correlation_var": "orderId"},
        "end": {"kind": "end"},
    },
    "flows": [
        {"id": "flow-start-payment-received-0", "from": "start", "to": "payment-received"},
        {"id": "flow-payment-received-end-1", "from": "payment-received", "to": "end"},
    ],
    "payload_variants": [{}],
}


def build():
    from zeebe_tpu.models.bpmn.builder import Bpmn

    return (
        Bpmn.create_process("payment-wait")
        .start_event("start")
        .message_catch_event("payment-received", message_name="payment-received",
                             correlation_key="$.orderId")
        .end_event("end")
        .done()
    )
'''


def _copy_with_new_files(root, delay_ms: int) -> str:
    """``zbench/`` and ``BENCHMARK.json`` copied, the program linked, and
    the new deployment's files added to the copy alone."""
    copy = os.path.join(str(root), f"checkout-{delay_ms}")
    shutil.copytree(os.path.join(CHECKOUT, "zbench"), os.path.join(copy, "zbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), copy)
    os.symlink(os.path.join(CHECKOUT, "zeebe_tpu"), os.path.join(copy, "zeebe_tpu"))
    before = {
        os.path.join(d, f)
        for d, _, fs in os.walk(os.path.join(copy, "zbench")) for f in fs
    }

    def add(rel: str, text: str) -> None:
        path = os.path.join(copy, rel)
        assert not os.path.exists(path), f"{rel} is there already: a new file only"
        with open(path, "w") as f:
            f.write(text)

    add("zbench/processes/payment_wait.py", PROCESS)
    with open(os.path.join(copy, "zbench", "configs", "route-1p.json")) as f:
        config = json.load(f)
    config.pop("stands_in_for", None)
    config.update(
        name="msg-1p",
        reduced={"resident_instances": config["reduced"]["resident_instances"]},
    )
    add("zbench/configs/msg-1p.json", json.dumps(config, indent=1))
    add(f"zbench/workloads/{CELL}.json", json.dumps({
        "generator": "closed", "in_flight": 256, "mix": {"payment-wait": 1.0},
        "sender_threads": 48, "grace_s": 45,
        "messages": {"payment-wait": {"name": "payment-received", "correlation_var": "orderId",
                                      "delay_ms": delay_ms, "ttl_ms": 60000}},
        "rehearsal": {"in_flight": 16},
    }, indent=1))
    after = {
        os.path.join(d, f)
        for d, _, fs in os.walk(os.path.join(copy, "zbench")) for f in fs
    }
    assert before < after and len(after - before) == 3

    bench_path = os.path.join(copy, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "msg-1p", "source": "test", "file": "zbench/configs/msg-1p.json",
        "reduced": ["resident_instances"], "why": "a process that waits for a message",
    })
    bench["workloads"].append({
        "name": CELL, "config": "msg-1p", "traffic": "saturated", "chips": 1,
        "why": "closed loop, a message published for every create",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("instances_per_s", "records_per_instance"):
            m["workloads"].append(CELL)
    with open(bench_path, "w") as f:
        json.dump(bench, f, indent=1)
    return copy


def _run(copy: str, seed: int, *extra: str) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    run = subprocess.run(
        [sys.executable, "-m", "zbench", "--workload", CELL, "--seed", str(seed),
         "--seconds", "4", "--trace", "0", "--rehearsal",
         "--set", 'engine.type="host"', *extra],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600,
    )
    assert run.returncode == 0, run.stderr[-2000:]
    return json.loads(run.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("delay_ms", [0, 50])
def test_a_message_process_runs_correct_from_new_files_alone(tmp_path, delay_ms):
    copy = _copy_with_new_files(tmp_path, delay_ms)
    result = _run(copy, 2**31 + 41 + delay_ms)
    assert result["correct"] is True, result["compared"]
    assert all(v == [0, 0] for v in result["compared"].values()), result["compared"]
    assert {"publish_errors", "acked_publish_not_in_log"} <= set(result["compared"])
    assert result["failed"] == 0 and result["attempted"] > 0
    if delay_ms == 0:
        lost = _run(copy, 2**31 + 41, "--fault", "lost_publish")
        assert lost["correct"] is False
        assert lost["compared"]["acked_publish_not_in_log"][0] > 0, lost["compared"]
