"""``mixed-4p`` through the served path at the rehearsal size (one broker
leading four partitions of 4,096 rows on one device, 64 in flight
round-robin, a quarter orders answered by the client's own job worker
subscribed at all four leaders, three quarters decisions): the
benchmark's own check on the timed path must find every instance and
every job exactly once, on the partition its create was sent to, and every
record the reference's; four engines share ONE compiled step."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTITIONS = 4  # zbench/configs/mixed-4p.json, "cluster.partitions"

# ``python3 -m zbench`` with one more earlier line: completions per
# partition, as the generator saw them and as the re-read logs hold them
# (``check.compare`` gets both; its result is untouched)
_WITH_PARTITIONS = """
import json, sys
from zbench import check, run

compare = check.compare

def noting(logs, gen, graphs, *rest):
    seen, in_log = {}, {}
    for row in gen["rows"]:
        if "done" in row:
            seen[row["partition"]] = seen.get(row["partition"], 0) + 1
    ids = {g["id"] for g in graphs.values()}
    for pid, rows in logs.items():
        in_log[pid] = sum(
            1 for r in rows
            if r.vtype == 5 and r.rtype == 0 and r.intent == 9
            and r.key == r.instance and r.element in ids
        )
    print(json.dumps({"zbench": "per_partition", "seen": seen, "log": in_log}),
          flush=True)
    return compare(logs, gen, graphs, *rest)

check.compare = noting
sys.exit(run.main())
"""


@pytest.mark.parametrize("seed", [31, 4242, 2**31 + 31])
def test_mixed_4p_rehearsal_is_correct(seed):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # one CPU device, as the benchmark runs
    run = subprocess.run(
        [sys.executable, "-c", _WITH_PARTITIONS, "--workload",
         "mixed-4p.saturated", "--seed", str(seed), "--seconds", "4",
         "--trace", "0", "--rehearsal"],
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

    def note(kind):
        return next(
            json.loads(line) for line in lines
            if line.startswith('{"zbench": "%s"' % kind)
        )

    report = note("run")
    assert report["workers"]["duplicate_pushes"] == 0
    assert report["workers"]["jobs"] > 0
    # four engines, one program: the step compiled once, in set-up
    assert report["jit_cache_sizes_before"]["kernel.step"] == 1
    assert report["jit_cache_sizes_after"]["kernel.step"] == 1
    assert len(report["state_devices"]) == 1
    # both processes ran, on every partition: an instance completes where
    # it was created, and the generator saw exactly what the logs hold
    parts = note("per_partition")
    assert sorted(parts["log"]) == [str(p) for p in range(PARTITIONS)]
    assert parts["seen"] == parts["log"]
    assert min(parts["log"].values()) > 0
    # creates go round-robin, so no partition is ahead of another by more
    # than the in-flight set (plus the warm-up's three)
    assert max(parts["log"].values()) - min(parts["log"].values()) <= 64 + 3
    # the shared waves' counters, by the names the cell's readers use
    n = report["counters"]
    assert n["serving_segments_total"] == n["scheduler_wave_sources_total"]
    assert n["scheduler_wave_sources_total"] > n["scheduler_shared_waves_total"] > 0
    assert (n["serving_wave_records_total"] / PARTITIONS
            <= n["serving_segment_records_max_total"]
            <= n["serving_wave_records_total"])
    assert n["serving_launch_ahead_total"] > 0
    assert n["scheduler_backpressure_skips"] == 0
    log = report["derived"]
    assert log["@log.0.0.1"] > 0, "no job was created in the window"
    assert log.get("@log.0.2.2", 0) == 0 and log.get("@log.0.0.7", 0) == 0


def test_the_cell_is_the_issues():
    """The deployment and its traffic as ISSUE 31 names them, and every
    per-layer metric of the cell with a reader file."""
    from zbench import spec
    from zeebe_tpu.runtime.config import load_config
    from zbench.run import toml_of

    cell = spec.Cell("mixed-4p.saturated")
    assert cell.listed and cell.chips == 1
    assert {k: v for k, v in cell.traffic.items() if k not in ("who", "rehearsal")} == {
        "generator": "closed", "in_flight": 512,
        "mix": {"order-process": 0.25, "route-order": 0.75},
        "sender_threads": 128, "grace_s": 45,
    }
    assert cell.config["assumed"]["job_credits"] == 256
    cfg = load_config(toml_text=toml_of(cell.config["broker"]))
    assert (cfg.cluster.partitions, cfg.cluster.replication_factor) == (4, 1)
    assert (cfg.engine.type, cfg.engine.capacity, cfg.engine.num_vars) == ("tpu", 1 << 20, 16)
    assert (cfg.mesh.enabled, cfg.mesh.devices) == (True, 1)
    assert cfg.scheduler.wave_size == 512
    assert [m["name"] for m in cell.end_to_end] == ["instances_per_s", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert len(names) == 42 and {  # ISSUE 36 added the actor timeline's nine
        "actor_busy_share", "actor_idle_ms", "mailbox_wait_ms",
        "h2d_transfers_per_wave", "d2h_transfers_per_wave",  # ISSUE 32
        "segments_per_wave", "segment_max_share", "launch_ahead_depth",
        "backpressure_skips_per_wave", "step_roofline.4p", "device_idle_share.4p",
        "drain_wait_ms.4p", "drain_pump_ms.4p", "mailbox_wait_ms.4p",
        "wave_route_ms.4p", "wave_h2d_ms.4p", "fsyncs_per_record.4p",
        "job_commands_serialised_per_job.4p",
    } <= names
    assert all(m["moves"] == "instances_per_s" for m in cell.per_layer)
