#!/usr/bin/env python
"""Profile the drive loop: per-op time breakdown of one quiescence wave.

Runs the bench setup (order-process, wave 2^14), captures a trace of a few
timed waves, and prints the top ops by total self time. Maps fusion names
back to source lines where the trace metadata has them.

Usage: python benchmarks/profile_round.py [--wave 14] [--trace-dir DIR]
"""

import argparse
import dataclasses
import glob
import gzip
import json
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def op_census(wave_pow: int = 10) -> dict:
    """Static gather/scatter/pallas census of ONE lowered step program on
    the current backend — the ops/record number the mega-pass collapses.
    Runs anywhere (CPU too: the fallback chain shows the unfused count, a
    TPU lowering shows the fused pallas passes as tpu_custom_call)."""
    import dataclasses as _dc
    import re

    import jax
    import jax.numpy as jnp

    from zeebe_tpu.tpu import batch as rb, kernel, state as state_mod
    from zeebe_tpu.testing import graphs

    wave = 1 << wave_pow
    graph, meta = graphs.build_graph()
    num_vars = max(graph.num_vars, 8)
    graph = _dc.replace(graph, num_vars=num_vars)
    state = state_mod.make_state(
        capacity=2 * wave, num_vars=num_vars, job_capacity=2 * wave,
        sub_capacity=8,
    )
    batch = rb.empty(wave, num_vars)
    lowered = jax.jit(
        kernel.step_kernel, static_argnames=("synthetic_workers",)
    ).lower(
        graph, state, batch, jnp.asarray(0, jnp.int64),
        synthetic_workers=True,
    )
    return census_counts(lowered)


def census_counts(lowered) -> dict:
    """The census numbers for an already-lowered step program — shared
    with zbaudit's ``op-census`` pass so the audit and this profiler gate
    the SAME lowering rather than paying two traces."""
    import re

    text = lowered.as_text()
    counts = {
        "gather": len(re.findall(r"\bgather\b", text)),
        "scatter": len(re.findall(r"\bscatter\b", text)),
        "pallas_passes": len(re.findall(r"tpu_custom_call", text)),
        "while_loops": len(re.findall(r"\bwhile\b", text)),
    }
    counts["gather_scatter_total"] = counts["gather"] + counts["scatter"]
    counts["per_pass"] = _per_pass_attribution(lowered)
    return counts


def _per_pass_attribution(lowered) -> dict:
    """Attribute each lowered gather/scatter OP (not the headline regex
    count, which also matches gather dimension_numbers attrs) to the
    kernel's named passes via stablehlo location metadata. The step kernel
    wraps its fused passes in ``jax.named_scope``: ``zb_lookups`` (indexed
    lookup probes/verifies), ``zb_gather`` (phase-B mega-gather + boundary
    scans), ``zb_emit`` (output-queue compaction); everything else lands in
    ``other``. This makes the census diff in PERF_NOTES mechanical — a
    regression names the pass that reintroduced the op."""
    import re
    from collections import defaultdict

    try:
        asm = lowered.compiler_ir().operation.get_asm(
            enable_debug_info=True
        )
    except Exception as e:  # noqa: BLE001 - loc metadata is best-effort
        # (jax API drift, e.g. as_text(debug_info=...) went away in
        # 0.4.x); headline counts still gate — surface why the split is
        # missing instead of silently dropping it
        return {"error": repr(e)[:200]}
    # #loc14 = loc("jit(f)/jit(main)/zb_gather/gather"(#loc8))
    loc_paths = dict(
        re.findall(r'(#loc\d+) = loc\("([^"]*)"', asm)
    )
    scopes = ("zb_lookups", "zb_gather", "zb_emit")
    per = {"gather": defaultdict(int), "scatter": defaultdict(int)}

    def _attr(op: str, locref: str) -> None:
        path = loc_paths.get(locref, "")
        scope = next((s for s in scopes if f"/{s}/" in path or
                      path.endswith(s)), "other")
        per[op][scope] += 1

    # gathers print on one line ending loc(#locN); scatters carry a region,
    # so their loc rides the closing "}) : ... loc(#locN)" line
    pending = None
    for line in asm.splitlines():
        m = re.search(
            r'"stablehlo\.(gather|scatter)".*?(?:loc\((#loc\d+)\))?$', line
        )
        if m and m.group(1):
            if m.group(2):
                _attr(m.group(1), m.group(2))
            else:
                pending = m.group(1)
            continue
        if pending:
            c = re.match(r"\s*\}\).*loc\((#loc\d+)\)", line)
            if c:
                _attr(pending, c.group(1))
                pending = None
    return {op: dict(d) for op, d in per.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--wave", type=int, default=14)
    ap.add_argument("--waves", type=int, default=3)
    ap.add_argument("--trace-dir", default="/tmp/zbtpu-trace")
    ap.add_argument(
        "--census", action="store_true",
        help="static gather/scatter/pallas op census of one lowered step "
        "program (no device run; works on CPU)",
    )
    args = ap.parse_args()

    if args.census:
        from zeebe_tpu import tpu as _tpu2  # noqa: F401  (enables x64)
        print(json.dumps(op_census(min(args.wave, 10))))
        return

    from zeebe_tpu import tpu as _tpu  # noqa: F401
    import jax
    import jax.numpy as jnp

    from zeebe_tpu.tpu import drive, hashmap, state as state_mod
    from zeebe_tpu.testing import graphs

    wave = 1 << args.wave
    capacity = 4 * wave
    graph, meta = graphs.build_graph()
    meta.varspace.column("orderId")
    meta.varspace.column("orderValue")
    meta.varspace.column("paid")
    num_vars = max(graph.num_vars, 8)
    graph = dataclasses.replace(graph, num_vars=num_vars)

    state = state_mod.make_state(
        capacity=capacity, num_vars=num_vars, job_capacity=capacity,
        sub_capacity=8,
    )
    import numpy as np
    state = dataclasses.replace(
        state,
        sub_key=state.sub_key.at[0].set(1),
        sub_type=state.sub_type.at[0].set(meta.interns.intern("payment-service")),
        sub_worker=state.sub_worker.at[0].set(meta.interns.intern("bench-worker")),
        sub_credits=state.sub_credits.at[0].set(np.int32(2**31 - 1)),
        sub_timeout=state.sub_timeout.at[0].set(300_000),
        sub_valid=state.sub_valid.at[0].set(True),
    )
    queue = drive.make_queue(8 * wave, num_vars)
    creates = graphs.stage_creates(meta, wave, num_vars, meta.interns)
    enqueue_jit = jax.jit(drive.enqueue, donate_argnums=(0,))
    rebuild_jit = jax.jit(state_mod.rebuild_lookup_state, donate_argnums=(0,))

    def run_wave(state, queue, sync=True):
        queue = enqueue_jit(queue, creates)
        return drive.run_to_quiescence(
            graph, state, queue, 0, wave, synthetic_workers=True, sync=sync)

    print("warmup/compile...", file=sys.stderr)
    t0 = time.perf_counter()
    state, queue, warm = run_wave(state, queue)
    print(f"warmup {time.perf_counter()-t0:.1f}s totals={warm}", file=sys.stderr)
    state = rebuild_jit(state)
    jax.block_until_ready(state.ei_state)

    # timed, untraced: ground-truth wave time
    t0 = time.perf_counter()
    for _ in range(args.waves):
        state, queue, tot = run_wave(state, queue, sync=False)
        state = rebuild_jit(state)
    jax.block_until_ready(state.ei_state)
    per_wave = (time.perf_counter() - t0) / args.waves
    rounds = warm["rounds"]
    print(f"per-wave {per_wave*1e3:.1f}ms  (warm rounds={rounds}, "
          f"per-round {per_wave/rounds*1e3:.2f}ms)", file=sys.stderr)

    # traced wave
    os.system(f"rm -rf {args.trace_dir}")
    with jax.profiler.trace(args.trace_dir):
        state, queue, tot = run_wave(state, queue, sync=False)
        state = rebuild_jit(state)
        jax.block_until_ready(state.ei_state)

    # parse trace: sum durations per op name on the device track
    paths = glob.glob(f"{args.trace_dir}/**/*.trace.json.gz", recursive=True)
    if not paths:
        print("no trace found", file=sys.stderr)
        return
    with gzip.open(paths[0], "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    # find device pids (TPU core tracks)
    dev_pids = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            nm = e.get("args", {}).get("name", "")
            if "TPU" in nm or "/device:" in nm or "Chip" in nm:
                dev_pids.add(e["pid"])
    agg = defaultdict(lambda: [0.0, 0])
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in dev_pids:
            nm = e.get("name", "")
            agg[nm][0] += e.get("dur", 0)
            agg[nm][1] += 1
    total = sum(v[0] for v in agg.values())
    print(f"\ndevice total {total/1e3:.1f}ms over {len(agg)} distinct ops")
    for nm, (dur, n) in sorted(agg.items(), key=lambda kv: -kv[1][0])[:45]:
        print(f"{dur/1e3:9.2f}ms  x{n:5d}  {nm[:110]}")


if __name__ == "__main__":
    main()
