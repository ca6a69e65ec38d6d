"""``python3 -m zbench.controls [--workload CELL] [--seeds a,b,c] [--seconds S]
[--rehearsal]``: the controls of ``correct``, as a test.

For each fault of ``zbench/faults.py`` it drives a whole run of the cell
with the fault planted under the timed path and sees ``correct`` come out
false; a sound run of the same seed has to come out true. On the chip it
runs at the cell's own size (that is how PERF.md's readings were taken);
with ``--rehearsal`` it skips the look for a chip and runs at the
rehearsal size. The benchmark's own runs never call it."""

from __future__ import annotations

import argparse
import json
import subprocess
import sys

from zbench import faults, spec


def one(workload: str, seed: int, seconds: float, rehearsal: bool, fault: str | None) -> dict:
    cmd = [sys.executable, "-m", "zbench", "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", "0"]
    if rehearsal:
        cmd.append("--rehearsal")
    if fault:
        cmd += ["--fault", fault]
    run = subprocess.run(cmd, cwd=spec.CHECKOUT, capture_output=True, text=True, timeout=900)
    if run.returncode != 0:
        # a control that crashes or gives no number has failed
        return {"correct": False, "crashed": run.stderr[-300:]}
    return json.loads(run.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="route-1p.saturated")
    ap.add_argument("--seeds", default="11,12,13")
    ap.add_argument("--seconds", type=float, default=8)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--faults", default=",".join(faults.NAMES))
    ap.add_argument("--skip-sound", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    bad = 0
    traffic = spec.Cell(args.workload).traffic
    for fault in [None] * (not args.skip_sound) + args.faults.split(","):
        if fault and not faults.applies(fault, traffic):
            print(json.dumps({"workload": args.workload, "fault": fault,
                              "skipped": "the cell cannot have this fault"}), flush=True)
            continue
        for seed in seeds if fault else seeds[:1]:
            result = one(args.workload, seed, args.seconds, args.rehearsal, fault)
            failing = {k: v for k, v in result.get("compared", {}).items() if v[0] > v[1]}
            want = fault is None
            ok = result["correct"] is want
            bad += not ok
            print(json.dumps({
                "workload": args.workload, "fault": fault, "seed": seed,
                "correct": result["correct"], "as_expected": ok, "over_limit": failing,
                "crashed": result.get("crashed"),
            }), flush=True)
    print("controls passed" if not bad else f"controls FAILED: {bad} runs")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
