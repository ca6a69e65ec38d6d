"""``order-doc-1p`` through the served path at the rehearsal size (capacity
4,096, 16 in flight, the client's own job worker): every create carries an
order document of about 2 KB, and the benchmark's own check on the timed
path must find every record the reference's with every instance on the
device path. The run is made in a process of its own that also reports the
value store's counters after it."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RUN = """
import json, sys
from zbench import run
from zeebe_tpu.runtime.metrics import event_count
rc = run.main(sys.argv[1:])
names = ("serving_payload_values_held_total", "serving_payload_values_freed_total",
         "serving_payload_host_routed_total")
print(json.dumps({"payload_counters": {n: event_count(n) for n in names}}))
sys.exit(rc)
"""


@pytest.mark.parametrize("seed", [5, 77, 2**31 + 11])
def test_order_doc_rehearsal_is_correct(seed):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    env.pop("XLA_FLAGS", None)  # one CPU device, as the benchmark runs
    run = subprocess.run(
        [sys.executable, "-c", RUN, "--workload", "order-doc-1p.saturated",
         "--seed", str(seed), "--seconds", "4", "--trace", "0", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    assert run.returncode == 0, run.stderr[-2000:]
    lines = run.stdout.strip().splitlines()
    counters = json.loads(lines[-1])["payload_counters"]
    result = json.loads(lines[-2])
    over = {k: v for k, v in result["compared"].items() if v[0] > v[1]}
    assert result["correct"] is True and not over, over
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["compared"]["host_lifecycle_records"] == [0, 0]
    report = next(
        json.loads(line) for line in lines if line.startswith('{"zbench": "run"')
    )
    # six of a create's variables have no column form (order_doc.document):
    # held once each, on the device path (no record the engine built from
    # its handles is held again), and freed as the instances end
    held = counters["serving_payload_values_held_total"]
    assert held == 6 * report["creates"] > 0
    assert 0 < counters["serving_payload_values_freed_total"] <= held
    assert counters["serving_payload_host_routed_total"] == 0
