"""The zbaudit passes: IR-level models and gates over audited entries.

Each pass takes ``(audited, budget, report)`` — the list of
:class:`~tools.zbaudit.core.AuditedEntry`, the parsed
``tools/zbaudit_budget.json``, and a mutable report dict it records its
model numbers into (surfaced via ``--json`` and the onchip diff) — and
returns zblint ``Finding`` objects. Findings carry STABLE messages (no
line numbers, no timings) so the ratchet baseline survives churn.

Pass families and their sub-rule ids:

- ``hbm-budget``     — HBM footprint model + per-device budget gate
- ``dtype-flow``     — ``dtype-f64`` / ``dtype-i64`` creep lints
- ``boundary``       — ``boundary-callback`` / ``boundary-transfer`` /
                       ``boundary-donation`` / ``boundary-alias``
- ``collective-volume`` — per-round collective bytes model +
                       ``collective-unexpected``
- ``signature-guard``   — ``signature-coverage`` / ``signature-cache`` /
                       ``signature-stale-driver``
- ``op-census``      — the old census_gate, same ratchet semantics over
                       ``benchmarks/census_budget.json``
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, List

from tools.zbaudit.core import (
    CENSUS_BUDGET_PATH,
    REPO_ROOT,
    AuditedEntry,
    Finding,
    aval_bytes,
    fmt_bytes,
    iter_eqns,
    tree_bytes,
)

# -- hbm-budget --------------------------------------------------------------

# %argN: tensor<2048x6xi32> {..., tf.aliasing_output = 3 : i32}
_ALIAS_ARG_RE = re.compile(
    r"tensor<([0-9x]*?)x?([a-z][a-z0-9]*)>\s*\{[^{}]*tf\.aliasing_output"
)
_DTYPE_BYTES = {
    "i1": 1, "i8": 1, "ui8": 1, "i16": 2, "ui16": 2, "bf16": 2, "f16": 2,
    "i32": 4, "ui32": 4, "f32": 4, "i64": 8, "ui64": 8, "f64": 8,
}


def _aliased_bytes(text: str) -> int:
    total = 0
    for dims, dtype in _ALIAS_ARG_RE.findall(text):
        size = 1
        for d in dims.split("x"):
            if d:
                size *= int(d)
        total += size * _DTYPE_BYTES.get(dtype, 4)
    return total


def pass_hbm(audited: List[AuditedEntry], budget: dict, report: dict):
    """Peak-HBM model: per entry, resident bytes = args + outputs minus
    donated (aliased) buffers; plus the closed-form state-size model in
    ``[engine] capacity`` evaluated at the default serving config (feeds
    ROADMAP item 5's tiering — the numbers say when a resident-instance
    target stops fitting one device)."""
    import jax

    from zeebe_tpu.tpu import batch as rb, drive, state as state_mod

    findings: List[Finding] = []
    hb = budget.get("hbm", {})
    device_budget = int(hb.get("device_budget_bytes", 0))
    dc = budget.get("default_config", {})
    cap = int(dc.get("capacity", 4096))
    nv = int(dc.get("num_vars", 16))
    sub = int(dc.get("sub_capacity", 16))
    wave = int(dc.get("wave", 512))

    def state_bytes(capacity: int) -> int:
        sds = jax.eval_shape(
            lambda: state_mod.make_state(
                capacity=capacity, num_vars=nv, job_capacity=capacity,
                sub_capacity=sub,
            )
        )
        return tree_bytes(sds)

    # closed form: the tables are (piecewise) linear in capacity — two
    # samples give the slope; the table below carries exact values
    b1, b2 = state_bytes(cap), state_bytes(2 * cap)
    slope = (b2 - b1) / cap
    intercept = b1 - slope * cap
    table = {
        int(c): state_bytes(int(c))
        for c in hb.get("capacity_table", (4096, 65536, 1 << 20))
    }
    wave_bytes = tree_bytes(jax.eval_shape(lambda: rb.empty(wave, nv)))
    queue_bytes = tree_bytes(
        jax.eval_shape(lambda: drive.make_queue(4 * wave, nv))
    )
    # serving residency at the default config: one donated state copy,
    # the drive queue, and an in-flight wave batch each way
    serving_peak = state_bytes(cap) + queue_bytes + 2 * wave_bytes
    model = {
        "default_config": dict(dc),
        "state_bytes_at_default_capacity": b1,
        "bytes_per_capacity_row": round(slope, 2),
        "fixed_bytes": int(intercept),
        "capacity_table": table,
        "wave_batch_bytes": wave_bytes,
        "queue_bytes": queue_bytes,
        "serving_peak_bytes": serving_peak,
        "device_budget_bytes": device_budget,
        "entries": {},
    }
    report["hbm"] = model

    for a in audited:
        if a.jaxpr is None:
            continue
        jx = a.jaxpr.jaxpr
        in_b = sum(aval_bytes(v.aval) for v in jx.invars)
        out_b = sum(aval_bytes(v.aval) for v in jx.outvars)
        aliased = _aliased_bytes(a.text)
        peak = in_b + out_b - aliased
        entry_model = {
            "arg_bytes": in_b, "out_bytes": out_b,
            "aliased_bytes": aliased, "peak_bytes": peak,
            "config": a.config,
        }
        # mesh-SHARDED state entries (engine state_shards): the global
        # args/outputs spread over D devices, so the RESIDENT footprint
        # per device is total/D; the transient gather-for-compute view
        # (one full state copy during the step) is priced separately so
        # the gate still sees the true per-device high-water mark
        shards = int(a.config.get("state_shards", 0) or 0)
        if shards > 1:
            resident = peak // shards
            gathered = in_b  # the gathered full-table view, freed per wave
            peak = resident + gathered
            entry_model.update({
                "state_shards": shards,
                "resident_bytes_per_device": resident,
                "gathered_bytes": gathered,
                "peak_bytes_per_device": peak,
            })
        model["entries"][a.name] = entry_model
        if device_budget and peak > device_budget and not a.suppresses(
            "hbm-budget"
        ):
            findings.append(a.finding(
                "hbm-budget",
                f"modeled peak {fmt_bytes(peak)} exceeds the per-device "
                f"budget {fmt_bytes(device_budget)} at the audit config",
            ))
    if device_budget and serving_peak > device_budget:
        findings.append(Finding(
            "hbm-budget", "zeebe_tpu/tpu/state.py", 1,
            f"default-config serving residency {fmt_bytes(serving_peak)} "
            f"exceeds the per-device budget {fmt_bytes(device_budget)}",
        ))
    return findings


# -- dtype-flow --------------------------------------------------------------

def pass_dtype(audited: List[AuditedEntry], budget: dict, report: dict):
    """f64/i64 creep: the engine deliberately runs i64 key planes (x64 is
    on), so i64 is RATCHETED per entry rather than banned; f64 has no
    deliberate use anywhere in the device plane and is banned outright
    (whitelist via budget ``dtype.allow_f64`` with a reason)."""
    cfg = budget.get("dtype", {})
    i64_budget: Dict[str, int] = cfg.get("i64_budget", {})
    allow_f64 = set(cfg.get("allow_f64", ()))
    findings: List[Finding] = []
    per: Dict[str, dict] = {}
    hints: List[str] = []
    for a in audited:
        if a.jaxpr is None:
            continue
        f64 = i64 = weak64 = 0
        for eqn in iter_eqns(a.jaxpr):
            for v in eqn.outvars:
                dt = str(getattr(getattr(v, "aval", None), "dtype", ""))
                if dt == "float64":
                    f64 += 1
                elif dt == "int64":
                    i64 += 1
            if eqn.primitive.name == "convert_element_type":
                nd = str(eqn.params.get("new_dtype", ""))
                if nd in ("float64", "int64") and all(
                    getattr(getattr(v, "aval", None), "weak_type", False)
                    for v in eqn.invars
                ):
                    weak64 += 1
        per[a.name] = {"f64": f64, "i64": i64, "weak_64bit_promotions": weak64}
        if f64 and a.name not in allow_f64 and not a.suppresses("dtype-f64"):
            findings.append(a.finding(
                "dtype-f64",
                f"{f64} float64-producing eqns in the traced program "
                "(f64 creep; whitelist via budget dtype.allow_f64 only "
                "with a reason)",
            ))
        limit = i64_budget.get(a.name)
        if limit is not None and not a.suppresses("dtype-i64"):
            if i64 > limit:
                findings.append(a.finding(
                    "dtype-i64",
                    f"{i64} int64-producing eqns > budget {limit} (i64 "
                    "creep beyond the deliberate key planes; ratchet "
                    "tools/zbaudit_budget.json only with a reason)",
                ))
            elif i64 < limit:
                hints.append(
                    f"{a.name}: i64 eqns {i64} < budget {limit} — ratchet "
                    "dtype.i64_budget down"
                )
    report["dtype"] = {"entries": per, "ratchet_hints": hints}
    return findings


# -- table-i64 ----------------------------------------------------------------

_INT64 = ("int64", "uint64")


def oversized_i64(jaxpr, floor: int) -> List[str]:
    """Every 64-bit integer array of at least ``floor`` elements among a
    traced program's inputs, outputs and equation results (nested
    programs included), as ``"<where>: <dtype>[shape]"``."""
    def big(v):
        aval = getattr(v, "aval", None)
        dt = str(getattr(aval, "dtype", ""))
        if dt not in _INT64:
            return None
        size = 1
        for d in aval.shape:
            size *= int(d)
        return f"{dt}{list(aval.shape)}" if size >= floor else None

    inner = getattr(jaxpr, "jaxpr", jaxpr)
    hits = []
    for where, vs in (("input", inner.invars), ("output", inner.outvars)):
        hits += [f"{where}: {b}" for b in map(big, vs) if b]
    for eqn in iter_eqns(jaxpr):
        hits += [
            f"{eqn.primitive.name}: {b}" for b in map(big, eqn.outvars) if b
        ]
    return hits


def pass_table_i64(audited: List[AuditedEntry], budget: dict, report: dict):
    """No table-sized 64-bit array in the served programs. A TPU has no
    64-bit integers: an ``s64`` array is two ``u32`` arrays, split whole
    where it enters a program and combined whole where it leaves, so one
    table-sized ``int64`` leaf or intermediate costs passes over the whole
    table for a wave of a few hundred records (PERF.md, PR 30). The state
    holds its 64-bit columns as 32-bit planes (``tpu/state.py``); this pass
    traces ``kernel.step``, ``kernel.tick``, ``engine.due_probe`` and
    ``engine.credit_flush`` at the served shape (budget
    ``dtype.table_i64``: the tables dwarf the wave there, which the census
    config's do not) and fails on any 64-bit integer array with at least
    as many elements as the smallest table that holds a 64-bit column."""
    cfg = budget.get("dtype", {}).get("table_i64")
    if not cfg:
        return []
    import dataclasses

    import jax
    import jax.numpy as jnp

    from zeebe_tpu import tpu as _tpu  # noqa: F401  (enables x64)
    from zeebe_tpu.testing import graphs
    from zeebe_tpu.tpu import (
        batch as rb, engine as engine_mod, kernel, state as state_mod,
    )

    num_vars = int(cfg.get("num_vars", 16))
    # every specialisation of the step switched on, so that no branch of it
    # goes untraced: timers, messages, boundaries, joins, multi-instance
    graph, _meta = graphs.build_graph_c4()
    graph = dataclasses.replace(
        graph, num_vars=max(graph.num_vars, num_vars),
        has_conditions=True, has_parallel_joins=True, has_timers=True,
        has_mappings=True, has_messages=True, has_boundaries=True,
        has_multi_instance=True,
    )
    num_vars = graph.num_vars
    state = jax.eval_shape(
        lambda: state_mod.make_state(
            capacity=int(cfg["capacity"]), num_vars=num_vars, sub_capacity=16
        )
    )
    # the wave as served: the packed pair
    batch = rb.pair_shapes(int(cfg["wave"]), num_vars)
    now = jax.ShapeDtypeStruct((), jnp.int64)
    floor = min(
        getattr(state, name).shape[0]
        for name in state_mod.I64_TABLES + state_mod.I64_COLUMNS
    )
    programs = {
        "kernel.step": lambda: kernel.step_jit.trace(graph, state, batch, now),
        "kernel.tick": lambda: kernel.tick_jit.trace(state, now),
        "engine.due_probe": lambda: engine_mod._due_probe_jit.trace(state, now),
        "engine.credit_flush": lambda: engine_mod._credit_flush_jit.trace(
            state.sub_credits, state.sub_credits
        ),
    }
    by_name = {a.name: a for a in audited}
    findings: List[Finding] = []
    per: Dict[str, object] = {"floor_elements": floor, "config": dict(cfg)}
    for name, trace in programs.items():
        hits = oversized_i64(trace().jaxpr, floor)
        per[name] = len(hits)
        if not hits:
            continue
        a = by_name.get(name)
        message = (
            f"{len(hits)} table-sized 64-bit integer array(s) (>= {floor} "
            f"elements) in the program at capacity {cfg['capacity']}, wave "
            f"{cfg['wave']}: {sorted(set(hits))[:6]} — hold the column as "
            "32-bit planes (tpu/state.py) and make int64 of the wave's rows"
        )
        findings.append(
            a.finding("table-i64", message) if a is not None else Finding(
                "table-i64", "zeebe_tpu/tpu/kernel.py", 1, f"{name}: {message}"
            )
        )
    report["table-i64"] = per
    return findings


# -- boundary ----------------------------------------------------------------

_TRANSFER_PRIMS = ("device_put", "copy")


def wave_io(a: AuditedEntry, resident_args=()) -> dict:
    """What crosses the host-device boundary on every call of a step
    program besides its state: the array parameters (rank >= 1) that are
    neither state (``state_args``) nor resident (``resident_args``: the
    graph), the scalar ones, and the results beyond the state's own
    leaves; ``wide`` names the 64-bit arrays among the arrays counted."""
    import jax

    args, _kwargs = a.lowered.args_info
    kept = set(a.entry.state_args) | set(resident_args)
    state_leaves = sum(
        len(jax.tree.leaves(args[i])) for i in a.entry.state_args
    )
    params = [
        leaf for i, arg in enumerate(args) if i not in kept
        for leaf in jax.tree.leaves(arg)
    ]
    arrays_in = [p for p in params if len(p.shape)]
    results = jax.tree.leaves(a.lowered.out_info)[state_leaves:]
    return {
        "arrays_in": len(arrays_in),
        "scalars_in": len(params) - len(arrays_in),
        "arrays_out": len(results),
        "wide": sorted({
            f"{v.dtype}{list(v.shape)}" for v in arrays_in + results
            if v.dtype.itemsize == 8 and len(v.shape)
        }),
    }


def pass_boundary(audited: List[AuditedEntry], budget: dict, report: dict):
    """The host boundary of each device program: no callbacks, no
    implicit transfers, every state-carrying argument donated with the
    aliasing actually materialized in the lowering, and, for the programs
    budgeted under ``boundary.wave_io``, no more arrays a call than the
    budget's: for a step the wave's packed pair in and the pair and one
    stats vector out, for the credit flush its addends in and nothing out
    (a transfer costs by the array, not by the byte: PERF.md, PR 32), none
    of them 64 bits wide."""
    findings: List[Finding] = []
    per: Dict[str, dict] = {}
    io_budget = budget.get("boundary", {}).get("wave_io", {})
    for a in audited:
        callbacks = set()
        transfers = set()
        if a.jaxpr is not None:
            for eqn in iter_eqns(a.jaxpr):
                nm = eqn.primitive.name
                if "callback" in nm:
                    callbacks.add(nm)
                elif nm in _TRANSFER_PRIMS:
                    transfers.add(nm)
        if a.lowered is not None and "cpu_callback" in a.text:
            callbacks.add("custom_call(cpu_callback)")
        missing = sorted(
            i for i in a.entry.state_args if i not in a.entry.donate_argnums
        )
        aliased = bool(a.lowered is not None
                       and "tf.aliasing_output" in a.text)
        per[a.name] = {
            "callbacks": sorted(callbacks), "transfers": sorted(transfers),
            "state_args": list(a.entry.state_args),
            "donate_argnums": list(a.entry.donate_argnums),
            "alias_materialized": aliased,
        }
        if callbacks and not a.suppresses("boundary-callback"):
            findings.append(a.finding(
                "boundary-callback",
                f"host callback in the device program: {sorted(callbacks)}"
                " (a device->host sync per call; move it out of the jit)",
            ))
        if transfers and not a.suppresses("boundary-transfer"):
            findings.append(a.finding(
                "boundary-transfer",
                f"explicit transfer primitives inside the program: "
                f"{sorted(transfers)}",
            ))
        if missing and not a.suppresses("boundary-donation"):
            findings.append(a.finding(
                "boundary-donation",
                f"state-carrying arg(s) {missing} not donated — a second "
                "copy of the state tables stays resident for the call "
                "(register with donate_argnums and rebind at callers)",
            ))
        if (a.entry.donate_argnums and not missing and a.lowered is not None
                and not aliased and not a.suppresses("boundary-alias")):
            findings.append(a.finding(
                "boundary-alias",
                "donation declared but no tf.aliasing_output materialized "
                "in the lowering (outputs do not reuse the donated "
                "buffers — shape/dtype mismatch?)",
            ))
        io_cfg = io_budget.get(a.name)
        if io_cfg and a.lowered is not None:
            got = wave_io(a, io_cfg.get("resident_args", ()))
            per[a.name]["wave_io"] = got
            over = [
                f"{k} {got[k]} > {io_cfg[k]}"
                for k in ("arrays_in", "arrays_out") if got[k] > io_cfg[k]
            ]
            if got["wide"]:
                over.append(f"64-bit arrays cross: {got['wide']}")
            if over and not a.suppresses("boundary-wave-io"):
                findings.append(a.finding(
                    "boundary-wave-io",
                    "a call moves more across the host boundary than the "
                    f"wave's packed pair and a stats vector: {'; '.join(over)}"
                    " (pack the new column into rb.StagedBatch's matrices, "
                    "or the new count into kernel.STATS)",
                ))
    report["boundary"] = per
    return findings


# -- collective-volume -------------------------------------------------------

# jax 0.9.0's names for the primitives that move bytes between devices.
# Under shard_map's default check_vma=True, ``lax.psum`` / ``all_gather``
# trace to the ``*_invariant`` forms; the registered programs pass
# check_vma=False and keep the plain names. Both are listed: a program
# audited under either setting counts the same bytes.
_COLLECTIVES = {
    "all_to_all", "ragged_all_to_all", "psum", "psum_invariant",
    "all_gather", "all_gather_invariant", "all_gather_reduced", "ppermute",
    "pmin", "pmax", "reduce_scatter", "unreduced_psum",
    "unreduced_reduce_scatter", "pgather",
}


def pass_collective(audited: List[AuditedEntry], budget: dict, report: dict):
    """Bytes moved by collectives per scheduling round, per device (the
    GNN-accelerator communication cost model: each ``all_to_all`` /
    ``psum`` in the program body executes once per round). Budget-gated
    for collective entries; non-collective entries must be
    collective-free."""
    ccfg = budget.get("collective", {})
    limit = ccfg.get("per_round_budget_bytes")
    # per-entry overrides: the sharded-STATE step gathers whole tables by
    # design, orders of magnitude above the frame-exchange budget — each
    # such entry carries its own ratcheted ceiling instead of inflating
    # the global one
    per_entry: Dict[str, int] = ccfg.get("per_entry_budget_bytes", {})
    findings: List[Finding] = []
    per: Dict[str, dict] = {}
    for a in audited:
        if a.jaxpr is None:
            continue
        vol: Dict[str, dict] = {}
        total = 0
        for eqn in iter_eqns(a.jaxpr):
            nm = eqn.primitive.name
            if nm not in _COLLECTIVES:
                continue
            b = sum(aval_bytes(v.aval) for v in eqn.outvars)
            d = vol.setdefault(nm, {"count": 0, "bytes": 0})
            d["count"] += 1
            d["bytes"] += b
            total += b
        per[a.name] = {"per_prim": vol, "total_bytes_per_round": total}
        if a.entry.collective:
            entry_limit = per_entry.get(a.name, limit)
            if (entry_limit is not None and total > int(entry_limit)
                    and not a.suppresses("collective-volume")):
                findings.append(a.finding(
                    "collective-volume",
                    f"{fmt_bytes(total)} per round over ICI exceeds the "
                    f"budget {fmt_bytes(int(entry_limit))} (shrink exchange "
                    "slots/frames or ratchet the budget with a reason)",
                ))
        elif vol and not a.suppresses("collective-unexpected"):
            findings.append(a.finding(
                "collective-unexpected",
                f"collective primitives in a non-collective entry: "
                f"{sorted(vol)} (register with collective=True if "
                "deliberate)",
            ))
    report["collective"] = per
    return findings


# -- signature-guard ---------------------------------------------------------

def pass_signature(audited: List[AuditedEntry], budget: dict, report: dict):
    """Registry <-> driver coverage plus the recompile guard: an entry
    whose live compile cache exceeds its declared ``max_signatures`` is
    recompiling on unkeyed shape variation (the silent serving-latency
    cliff). The runtime leg — stepping waves of varying record counts and
    pinning a zero cache delta — lives in tests/test_zbaudit.py."""
    from zeebe_tpu.tpu import jit_registry

    from tools.zbaudit import entries as entries_mod

    findings: List[Finding] = []
    reg = jit_registry.entries()
    audited_names = {a.name for a in audited}
    if report.get("complete"):
        for name, e in sorted(reg.items()):
            if name in audited_names:
                continue
            if name.startswith(entries_mod.AUTOTUNE_PREFIX) and (
                name.endswith(".xla") or name.endswith(".pallas")
            ):
                continue  # timing arms of the audited autotune.<family>
            if any(s in ("signature-coverage", "signature") for s in e.suppress):
                continue
            from tools.zbaudit.core import rel_src

            path, line = rel_src(e.wrapped)
            findings.append(Finding(
                "signature-coverage", path, line,
                f"{name}: registered jit entry has no zbaudit driver (add "
                "one to tools/zbaudit/entries.py or suppress with a note)",
            ))
        for name in entries_mod.DRIVER_NAMES:
            if name not in reg and not name.startswith("shard."):
                findings.append(Finding(
                    "signature-stale-driver", "tools/zbaudit/entries.py", 1,
                    f"{name}: driver names an entry the registry never "
                    "registered",
                ))
    for a in audited:
        cs = a.entry.cache_size()
        if (cs is not None and cs > a.entry.max_signatures
                and not a.suppresses("signature-cache")):
            findings.append(a.finding(
                "signature-cache",
                f"live compile cache holds {cs} signatures > declared max "
                f"{a.entry.max_signatures} (unkeyed shape-driven "
                "recompile)",
            ))
    report["registry"] = jit_registry.signature_report()
    return findings


# -- op-census ---------------------------------------------------------------

def pass_census(audited: List[AuditedEntry], budget: dict, report: dict):
    """The old tools/census_gate.py, folded in: gather/scatter counts of
    the lowered step program vs benchmarks/census_budget.json, with the
    same ratchet-down hints. Gates only on the backend the budget was
    measured on."""
    import jax

    from benchmarks.profile_round import census_counts

    step = next((a for a in audited if a.name == "kernel.step"), None)
    if step is None or step.lowered is None:
        return []
    with open(os.path.join(REPO_ROOT, CENSUS_BUDGET_PATH),
              encoding="utf-8") as f:
        cb = json.load(f)
    counts = census_counts(step.lowered)
    backend = jax.default_backend()
    info = {"counts": counts, "budget": cb, "backend": backend,
            "ratchet_hints": []}
    report["op-census"] = info
    if cb.get("backend") and cb["backend"] != backend:
        info["skipped"] = (
            f"budget measured on {cb['backend']}, running on {backend}"
        )
        return []
    findings: List[Finding] = []
    for key in ("gather", "scatter", "gather_scatter_total"):
        limit = cb.get(key)
        if limit is None:
            continue
        got = int(counts[key])
        if got > int(limit):
            findings.append(step.finding(
                "op-census",
                f"{key} count {got} > budget {limit} (a kernel change "
                "reintroduced per-record ops; see the census history in "
                "PERF_NOTES)",
            ))
        elif got < int(limit):
            info["ratchet_hints"].append(
                f"{key}: {got} < budget {limit} — ratchet "
                "benchmarks/census_budget.json down"
            )
    return findings


PASSES = {
    "hbm-budget": pass_hbm,
    "dtype-flow": pass_dtype,
    "table-i64": pass_table_i64,
    "boundary": pass_boundary,
    "collective-volume": pass_collective,
    "signature-guard": pass_signature,
    "op-census": pass_census,
}

# minimal entry set per pass (None = needs every entry); lets the
# census_gate shim run the op-census family without paying the full build
PASS_ENTRIES = {
    "op-census": {"kernel.step"},
    "table-i64": {
        "kernel.step", "kernel.tick", "engine.due_probe",
        "engine.credit_flush",
    },
}
