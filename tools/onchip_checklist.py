"""One entry point for the pending ON-CHIP validations (PERF_NOTES
rounds 6-11): the per-build autotune A/B, the pallas-vs-XLA parity gate,
the serving-path bench, the shared-wave scheduler bench, the mesh
serving A/B, and the round-8 mega-gather config-5 sweep — each queued
across PRs 1/4/8/9/10 for "the next chip session".
Running them through one command that WRITES A REPORT is what keeps the
checklist from rotting: ci.sh invokes this on every gate, it skips
cleanly off-TPU, and on a chip session the JSON lands in
``onchip_report.json`` for the PERF_NOTES update.

Run: ``python tools/onchip_checklist.py [--out report.json] [--quick]``
  --quick swaps the full benches for their --smoke legs (sanity only).
"""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "onchip_report.json")


def probe_backend(timeout_sec: int = 180) -> str:
    """The backend jax would initialize, probed in a SUBPROCESS: this
    parent never touches jax, so each step's child gets the chip (one
    process per chip) — and the probe's own child has let go of it."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.default_backend())"],
            capture_output=True, text=True, timeout=timeout_sec, cwd=ROOT,
        )
        if proc.returncode == 0:
            return proc.stdout.strip().splitlines()[-1]
    except (subprocess.TimeoutExpired, OSError):
        pass
    return "unavailable"


def run_step(name, argv, timeout_sec, env=None):
    start = time.time()
    step = {"name": name, "cmd": " ".join(argv)}
    try:
        proc = subprocess.run(
            argv, capture_output=True, text=True, timeout=timeout_sec,
            cwd=ROOT, env={**os.environ, **(env or {})},
        )
        step["rc"] = proc.returncode
        tail = (proc.stdout + proc.stderr)[-4000:]
        step["tail"] = tail
    except subprocess.TimeoutExpired:
        step["rc"] = -1
        step["tail"] = f"TIMEOUT after {timeout_sec}s"
    step["seconds"] = round(time.time() - start, 1)
    print(
        f"onchip_checklist: {name}: rc={step['rc']} "
        f"({step['seconds']}s)", flush=True,
    )
    return step


def _audit_summary(doc):
    """The model numbers worth diffing across backends from one zbaudit
    --json report: finding count, per-entry modeled HBM peaks, per-entry
    collective bytes/round, and the step-program op census."""
    rep = doc.get("report", {})
    return {
        "findings": len(doc.get("findings", [])),
        "hbm_peak_bytes": {
            k: v.get("peak_bytes")
            for k, v in (rep.get("hbm", {}).get("entries") or {}).items()
        },
        "collective_bytes_per_round": {
            k: v.get("total_bytes_per_round")
            for k, v in (rep.get("collective") or {}).items()
        },
        "census_counts": (rep.get("op-census") or {}).get("counts"),
    }


def zbaudit_reaudit(report, py, timeout_sec=1800):
    """The PR-14 TPU re-audit leg: run the IR audit against the REAL
    lowering (``--backend tpu``) and against the CPU reference, then diff
    the model numbers into the report — the off-chip audit gates CI, so
    what matters on a chip session is exactly where the tpu lowering
    diverges from the numbers the budget was ratcheted on."""
    docs = {}
    steps = []
    for backend in ("tpu", "cpu"):
        out = os.path.join(ROOT, f"zbaudit_{backend}_report.json")
        step = run_step(
            f"zbaudit_{backend}",
            [py, "-m", "tools.zbaudit", "--backend", backend,
             "--json", "--out", out],
            timeout_sec,
        )
        steps.append(step)
        if step["rc"] == 0:
            try:
                with open(out, encoding="utf-8") as f:
                    docs[backend] = _audit_summary(json.load(f))
            except (OSError, ValueError) as e:
                step["rc"] = -2
                step["tail"] += f"\nreport unreadable: {e}"
    diff = {}
    if "tpu" in docs and "cpu" in docs:
        for section in ("hbm_peak_bytes", "collective_bytes_per_round"):
            t, c = docs["tpu"][section], docs["cpu"][section]
            diff[section] = {
                k: {"tpu": t.get(k), "cpu": c.get(k)}
                for k in sorted(set(t) | set(c)) if t.get(k) != c.get(k)
            }
        t, c = docs["tpu"]["census_counts"], docs["cpu"]["census_counts"]
        if t != c:
            diff["census_counts"] = {"tpu": t, "cpu": c}
    report["zbaudit"] = {**docs, "tpu_vs_cpu_diff": diff}
    return steps


def main() -> int:
    out_path = DEFAULT_OUT
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]
    quick = "--quick" in sys.argv

    backend = probe_backend()
    report = {
        "backend": backend,
        "started": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "quick": quick,
        "steps": [],
    }
    if backend != "tpu":
        # the checklist is ON-CHIP validation: without a TPU it fails
        # (ci.sh calls it only where one is attached)
        print(
            f"onchip_checklist: backend={backend!r}, no TPU — nothing was "
            "validated", file=sys.stderr,
        )
        return 1

    py = sys.executable
    smoke = ["--smoke"] if quick else []
    steps = [
        # PR 1: per-build pallas/XLA dispatch decisions on THIS libtpu
        ("autotune", [py, "-m", "zeebe_tpu.tpu.autotune"], 3600),
        # PR 1: pallas table ops + mega-pass parity on the real lowering
        ("pallas_ops_check",
         [py, os.path.join("benchmarks", "pallas_ops_check.py")], 3600),
        # PR 4: the pipelined serving path (BENCH_r05 recorded 11.5 t/s
        # before the per-column transfers were packed; not re-measured)
        ("serving_bench", [py, "bench.py"], 7200),
        # PR 8: shared-wave fill -> throughput win on chip
        ("shared_wave_bench",
         [py, "bench.py", "--multi-tenant"] + smoke, 7200,
         {"ZB_BENCH_ENGINE": "tpu"}),
        # PR 9: mesh serving A/B across the real chips
        ("mesh_bench", [py, "bench.py", "--mesh"] + smoke, 7200),
        # ISSUE 19: mesh-SHARDED partition state — tables block-shard
        # over a span of real chips, bit-identity + A/B vs single-device
        # placement at equal offered load (the gathers ride real ICI
        # here; the CPU run only models them)
        ("sharded_state_bench",
         [py, "bench.py", "--sharded-state"] + smoke, 7200),
        # ISSUE 20: sharded-state v2 — residency-routed staging; the
        # routed leg must stay bit-identical on real chips AND move
        # strictly fewer collective bytes per wave than the gathered leg
        # (on chip the psum boundary traffic rides real ICI links)
        ("sharded_state_routed_bench",
         [py, "bench.py", "--sharded-state", "--routed"] + smoke, 7200),
        # PR 10 (kernel round 8): the mega-gather/emit families — the
        # autotune step above already tables their A/B and the
        # pallas_ops_check step pins their parity; these two legs run the
        # config-5 acid test fused vs. forced-XLA for the PERF_NOTES row
        ("config5_sweep_fused",
         [py, "bench.py", "--config5-sweep"] + smoke, 7200),
        ("config5_sweep_xla",
         [py, "bench.py", "--config5-sweep"] + smoke, 7200,
         {"ZB_PALLAS": "0"}),
    ]
    failed = []
    for entry in steps:
        name, argv, timeout_sec = entry[0], entry[1], entry[2]
        env = entry[3] if len(entry) > 3 else None
        step = run_step(name, argv, timeout_sec, env)
        report["steps"].append(step)
        if step["rc"] != 0:
            failed.append(name)
    # PR 14: re-run the IR audit against the real tpu lowering and diff
    # its model numbers (HBM peaks, collective volumes, op census)
    # against the CPU reference the budgets were ratcheted on
    for step in zbaudit_reaudit(report, py):
        report["steps"].append(step)
        if step["rc"] != 0:
            failed.append(step["name"])
    report["status"] = "failed" if failed else "ok"
    report["failed"] = failed
    report["completed"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"onchip_checklist: {report['status']} -> {out_path}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
