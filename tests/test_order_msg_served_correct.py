"""``order-msg-1p`` through the served path at the rehearsal size (capacity
4,096, 16 in flight) on the DEVICE engine: the getting-started order
process (a payment job, the ``payment-received`` message correlated on the
order's id, a split and a join gateway, one of two shipping jobs), one
message published for every create as soon as it is acknowledged. The
benchmark's own check on the timed path must find every create and every
publish in the log, every instance and each of its two jobs exactly once,
and every record the reference's; no message-family record may reach the
host engine."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "order-msg-1p.saturated"
IN_FLIGHT = 16  # zbench/workloads/order-msg-1p.saturated.json, "rehearsal"
JOB_TYPES = ("initiate-payment", "ship-with-insurance", "ship-without-insurance")


def _run(seed: int, *extra: str) -> tuple:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # one CPU device, as the benchmark runs
    run = subprocess.run(
        [sys.executable, "-m", "zbench", "--workload", CELL, "--seed", str(seed),
         "--seconds", "4", "--trace", "0", "--rehearsal", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    assert run.returncode == 0, run.stderr[-2000:]
    lines = run.stdout.strip().splitlines()
    report = next(
        json.loads(line) for line in lines if line.startswith('{"zbench": "run"')
    )
    return json.loads(lines[-1]), report


@pytest.mark.parametrize("seed", [42, 2147483900, 2**31 + 4242])
def test_order_msg_rehearsal_is_correct(seed):
    result, report = _run(seed)
    assert result["correct"] is True, result["compared"]
    # every comparison AT its limit, the two of the publisher among them
    assert all(v == [0, 0] for v in result["compared"].values()), result["compared"]
    assert len(result["compared"]) == 13
    assert {"publish_errors", "acked_publish_not_in_log"} <= set(result["compared"])
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"instances_per_s", "setup_s"}
    # three workers, one a job type; every instance made the payment job
    # and one of the two shipping jobs, and each reached its worker once
    work = report["workers"]
    assert sorted(work["pushes"]) == list(JOB_TYPES)
    assert work["duplicate_pushes"] == 0
    assert work["jobs"] == 2 * report["creates"]
    assert report["drained"] is True
    # the device path: the message store is on the device, and no message,
    # subscription or instance record went to the host engine
    engine = report["engine"]
    assert not any(
        kind.startswith(("vt5/", "vt11/", "vt12/", "vt13/"))
        for kind in engine["host_records_by_kind"]
    ), engine
    # the device correlated every instance's message once: in the window,
    # as many correlations as completions, but for the instances open at
    # either edge of it
    n = report["counters"]
    at_open = n["serving_message_correlated_at_open_total"]
    at_publish = n["serving_message_correlated_at_publish_total"]
    assert at_open > 0
    completed = report["derived"]["@instances_completed"]
    assert completed > 0
    assert abs(at_open + at_publish - completed) <= 2 * IN_FLIGHT, (at_open, at_publish, completed)
    # and the window's log: one CORRELATED workflow-instance subscription
    # event (value type 13, event, intent 1) an instance, one OPENED and one
    # CLOSED message subscription (value type 12, intents 1 and 5)
    log = report["derived"]
    for kind in ("@log.13.0.1", "@log.12.0.1", "@log.12.0.5"):
        assert abs(log.get(kind, 0) - completed) <= 2 * IN_FLIGHT, (kind, log)
    # nothing was rejected: no second message or subscription on a composite
    assert not any(k.startswith(("@log.11.2.", "@log.12.2.", "@log.13.2.")) for k in log), log


def test_a_lost_publish_is_caught():
    """The control of the publisher's check: every seventh acknowledged
    publish's MESSAGE records are taken out of the log on disk."""
    result, _ = _run(2147483937, "--fault", "lost_publish")
    assert result["correct"] is False
    assert result["compared"]["acked_publish_not_in_log"][0] > 0, result["compared"]


def test_the_cell_is_the_issues():
    """The deployment and its traffic as the cell's table in ``PERF.md``
    (section 4) gives them, and every per-layer metric of the cell with a
    reader whose counters the program counts."""
    from zbench import spec
    from zbench.run import toml_of
    from zeebe_tpu.runtime.config import load_config
    from zeebe_tpu.runtime.metrics import _phase_handles

    cell = spec.Cell(CELL)
    assert cell.listed and cell.chips == 1
    assert {k: v for k, v in cell.traffic.items() if k not in ("who", "rehearsal")} == {
        "generator": "closed", "in_flight": 256, "mix": {"order-payment": 1.0},
        "sender_threads": 48, "grace_s": 45,
        "messages": {"order-payment": {
            "name": "payment-received", "correlation_var": "orderId",
            "delay_ms": 0, "ttl_ms": 3_600_000,
        }},
    }
    assert cell.traffic["rehearsal"] == {"in_flight": IN_FLIGHT}
    assert cell.config["assumed"]["job_credits"] == 256
    assert sorted(cell.config["reduced"]) == ["message_store", "rehearsal", "resident_instances"]
    order = spec.Cell("order-1p.saturated").config
    assert cell.config["broker"] == order["broker"]
    quick = spec.Cell("quickstart-1p.saturated").config
    assert set(quick["guarantees"]) < set(cell.config["guarantees"])
    cfg = load_config(toml_text=toml_of(cell.config["broker"]))
    assert (cfg.cluster.partitions, cfg.cluster.replication_factor) == (1, 1)
    assert (cfg.engine.type, cfg.engine.capacity, cfg.engine.num_vars) == ("tpu", 1 << 20, 16)
    assert cfg.scheduler.wave_size == 512
    graph = cell.processes()["order-payment"].GRAPH
    nodes = graph["nodes"]
    tasks = [n["job_type"] for n in nodes.values() if n["kind"] == "service_task"]
    assert tasks == list(JOB_TYPES)
    assert nodes["payment-received"] == {
        "kind": "message_catch", "message": "payment-received", "correlation_var": "orderId",
    }
    split = [f["when"] for f in graph["flows"] if f["from"] == "order-value?"]
    assert split == [{"var": "orderValue", "op": ">=", "value": 100},
                     {"var": "orderValue", "op": "<", "value": 100}]
    joins = [n for n in nodes.values() if n["kind"] == "exclusive_gateway" and "default" in n]
    assert len(joins) == 1
    assert [m["name"] for m in cell.end_to_end] == ["instances_per_s", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert names == {
        "records_per_instance", "wave_fill", "wave_host_share", "wave_stage_ms",
        "wave_decode_ms", "wave_readback_ms", "wave_apply_ms", "wave_push_ms", "step_ms",
        "step_roofline", "device_idle_share", "stage_columnar_share", "actor_busy_share",
        "actor_command_ms", "message_cut_ms", "message_cuts_per_wave",
        "message_records_per_instance", "message_correlated_at_open_share",
    }
    assert all(m["moves"] == "instances_per_s" for m in cell.per_layer)
    counted = {metric.name for metric in _phase_handles().values()}
    counted |= {"serving_waves_total", "serving_wave_records_total",
                "serving_host_seconds_total", "serving_device_seconds_total",
                "serving_rows_staged_columnar_total"}
    for m in cell.per_layer:
        reader = m["reader"]
        if reader["kind"] == "counter_ratio":
            named = {n for n in reader["num"] + reader["den"] if not n.startswith("@")}
            assert named <= counted, (m["name"], named - counted)
