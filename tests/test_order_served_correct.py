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
IN_FLIGHT = 16  # zbench/workloads/order-1p.saturated.json, "rehearsal"


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
    # no bound here hangs on the host's speed: beside five busy test
    # workers the 4 s window holds few creates, and its log is cut at both
    # ends through the jobs that are open there
    assert result["failed"] == 0 and result["attempted"] > 0
    for name in ("completed_not_once", "jobs_not_once", "reference_mismatches",
                 "host_lifecycle_records", "compiled_in_window"):
        assert result["compared"][name] == [0, 0], name
    report = next(
        json.loads(line) for line in lines
        if '"activations_per_job"' in line and line.startswith('{"zbench"')
    )
    # over the whole run every job reached the worker once ...
    assert report["workers"]["duplicate_pushes"] == 0
    assert report["workers"]["jobs"] > IN_FLIGHT
    # ... and in the window's log (``@log.<value>.<record>.<intent>``: job 0;
    # event 0, rejection 2; CREATED 1, ACTIVATE 2, ACTIVATED 3, TIMED_OUT 7)
    # no ACTIVATE was turned away and no job timed out, so a job counts
    # under CREATED and not under ACTIVATED, or the other way round, only
    # where an edge of the window falls between the two: at most the
    # in-flight set at either edge
    log = report["derived"]
    assert log.get("@log.0.2.2", 0) == 0 and log.get("@log.0.0.7", 0) == 0
    assert abs(log["@log.0.0.3"] - log["@log.0.0.1"]) <= IN_FLIGHT, log
