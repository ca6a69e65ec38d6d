"""``order-1p`` through the served path at the rehearsal size (capacity
4,096, 16 in flight, the client's own job worker answering every push, a
repeated one too), on the seeds on which a job was activated, completed and
its task completed more than once before ISSUE 27 (``PERF.md``, Open
questions 1): the benchmark's own check on the timed path must find every
instance and every job exactly once and every record the reference's."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("seed", [77, 80, 2**31 + 11])
def test_order_rehearsal_is_correct(seed):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # one CPU device, as the benchmark runs
    run = subprocess.run(
        [sys.executable, "-m", "zbench", "--workload", "order-1p.saturated",
         "--seed", str(seed), "--seconds", "4", "--trace", "0", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    assert run.returncode == 0, run.stderr[-2000:]
    lines = run.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    over = {k: v for k, v in result["compared"].items() if v[0] > v[1]}
    assert result["correct"] is True and not over, over
    assert result["failed"] == 0 and result["attempted"] > 16
    for name in ("completed_not_once", "jobs_not_once", "reference_mismatches",
                 "host_lifecycle_records", "compiled_in_window"):
        assert result["compared"][name] == [0, 0], name
    report = next(
        json.loads(line) for line in lines
        if '"activations_per_job"' in line and line.startswith('{"zbench"')
    )
    assert 1.0 <= report["activations_per_job"] <= 1.01, report["activations_per_job"]
    assert report["workers"]["duplicate_pushes"] == 0
