"""``quickstart-1p`` through the served path at the rehearsal size (capacity
4,096, 320 in flight, the quickstart's three service tasks, the client's
own job worker a job type at its default of 32 credits): the benchmark's
own check on the timed path must find every instance and each of its three
jobs exactly once and every record the reference's, and the traffic must do
what the cell is for: jobs that find no credit wait parked and leave with a
tick's sweep (ISSUE 33)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JOB_TYPES = ("inventory-service", "payment-service", "shipment-service")


@pytest.mark.parametrize("seed", [33, 9091, 2**31 + 33])
def test_quickstart_rehearsal_is_correct(seed):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # one CPU device, as the benchmark runs
    run = subprocess.run(
        [sys.executable, "-m", "zbench", "--workload", "quickstart-1p.saturated",
         "--seed", str(seed), "--seconds", "4", "--trace", "0", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    assert run.returncode == 0, run.stderr[-2000:]
    lines = run.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True, result["compared"]
    # every comparison AT its limit, not only under it: all eleven are exact
    assert all(v == [0, 0] for v in result["compared"].values()), result["compared"]
    assert len(result["compared"]) == 11
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"instances_per_s", "setup_s"}
    report = next(
        json.loads(line) for line in lines if line.startswith('{"zbench": "run"')
    )
    # three workers, one a job type, and every instance that went through
    # (the warm-up's too) made one job of each: over the whole run each job
    # reached its worker once
    work = report["workers"]
    assert sorted(work["pushes"]) == list(JOB_TYPES)
    assert len(set(work["pushes"].values())) == 1
    assert work["duplicate_pushes"] == 0
    # jobs = 3 x instances: every create of the run (``creates`` counts the
    # warm-up's too) completed, so each made one job of each type
    assert work["jobs"] == 3 * report["creates"]
    assert report["drained"] is True
    # the cell's mechanism: with 320 in flight against 32 credits a type
    # (128 no longer park once five other test workers share the CPU) jobs
    # parked and a tick's sweep activated them, inside the window
    n = report["counters"]
    assert n["serving_backlog_activations_total"] > 0
    assert n["serving_backlog_sweeps_total"] > 0
    assert n["serving_backlog_parked_walked_total"] >= n["serving_backlog_activations_total"]
    assert n["serving_backlog_park_wait_seconds_total"] > 0
    assert n["serving_job_credit_returns_total"] > 0
    assert n["serving_job_credit_return_seconds_total"] > 0
    # and reached the device in flushes, each before a reader of the state
    assert 0 < n["serving_job_credit_flushes_total"] <= n["serving_job_credit_returns_total"]
    assert n["serving_job_credit_flush_seconds_total"] > 0
    # a parked job carries its value: no row is read back for it
    assert n["serving_job_row_reads_total"] == 0
    log = report["derived"]
    # no ACTIVATE was turned away and no job timed out
    assert log.get("@log.0.2.2", 0) == 0 and log.get("@log.0.0.7", 0) == 0
    assert log["@log.0.0.1"] > 0


def test_the_cell_is_the_issues():
    """The deployment and its traffic as ISSUE 33 names them, and every
    per-layer metric of the cell with a reader file whose counters the
    program counts."""
    from zbench import spec
    from zbench.run import toml_of
    from zeebe_tpu.runtime.config import load_config
    from zeebe_tpu.runtime.metrics import _phase_handles

    cell = spec.Cell("quickstart-1p.saturated")
    assert cell.listed and cell.chips == 1
    assert {k: v for k, v in cell.traffic.items() if k not in ("who", "rehearsal")} == {
        "generator": "closed", "in_flight": 512,
        "mix": {"order-quickstart": 1.0}, "sender_threads": 48, "grace_s": 45,
    }
    assert cell.config["assumed"]["job_credits"] == 32
    assert sorted(cell.config["reduced"]) == ["rehearsal", "resident_instances"]
    order = spec.Cell("order-1p.saturated").config
    assert cell.config["broker"] == order["broker"]
    assert cell.config["guarantees"].keys() == order["guarantees"].keys()
    cfg = load_config(toml_text=toml_of(cell.config["broker"]))
    assert (cfg.cluster.partitions, cfg.cluster.replication_factor) == (1, 1)
    assert (cfg.engine.type, cfg.engine.capacity, cfg.engine.num_vars) == ("tpu", 1 << 20, 16)
    graph = cell.processes()["order-quickstart"].GRAPH
    tasks = [n["job_type"] for n in graph["nodes"].values() if n["kind"] == "service_task"]
    assert tasks == ["payment-service", "inventory-service", "shipment-service"]
    assert [m["name"] for m in cell.end_to_end] == ["instances_per_s", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert len(names) == 35 and {  # ISSUE 35: the flush's two; 36: the actor's nine
        "actor_busy_share", "actor_idle_before_drain_share", "actor_offcpu_ms",
        "job_park_wait_ms", "parked_walked_per_sweep", "backlog_skipped_per_sweep",
        "credit_return_ms", "credit_returns_per_job", "backlog_activations_per_job.quick",
        "credit_flush_ms", "credit_returns_per_flush",
        "step_ms.quick", "step_roofline.quick", "device_idle_share.quick",
    } <= names
    assert all(m["moves"] == "instances_per_s" for m in cell.per_layer)
    counted = {metric.name for metric in _phase_handles().values()}
    counted |= {"serving_waves_total", "serving_wave_records_total",
                "serving_host_seconds_total", "serving_device_seconds_total"}
    for m in cell.per_layer:
        reader = m["reader"]
        if reader["kind"] == "counter_ratio":
            named = {n for n in reader["num"] + reader["den"] if not n.startswith("@")}
            assert named <= counted, (m["name"], named - counted)
