"""Engine-boot autotune: pick pallas vs XLA per table-op family on the
RUNNING libtpu build.

PERF_NOTES round 4: the fast path for the step kernel's table ops is
BUILD-dependent — libtpu builds that lower general scatters to serial
per-index programs need the pallas serial passes, builds with the
DMA-pipelined scatter/gather lowering are faster through plain XLA, and
the winner has flipped between builds. A static env default (the old
``ZB_PALLAS`` switch) is therefore wrong half the time; this module A/Bs
both paths per op family with a dependent-chain microbench ONCE at engine
boot and installs the winners in ``pallas_ops``' dispatch table.

What the A/B decides, and what it does not: it runs at ``_T`` = 2^12 table
rows, so a family's winner holds only among tables that a pallas pass can
hold in VMEM at all. The table-size rule (``pallas_ops._fits_vmem``, at
every call site) decides the rest: a family that won here still takes the
XLA form for a table whose padded windows do not fit — at the served 2^20
rows, every 2D row table. The cost of a pallas pass also grows with the
table it copies through VMEM, which a 2^12-row A/B does not see; whether
the winner changes with size below the VMEM bound is not measured.

Rules that shape the measurement (all empirical, see PERF_NOTES):
- chains must be DEPENDENT (each op consumes the previous op's output) —
  isolated op timing is pipelined and lies ~20x;
- decisions cache on disk keyed by a build fingerprint (jax/jaxlib
  versions + device kind + platform version) under the checkout's cache
  root (``zeebe_tpu.compile_cache``), so a fleet restart pays the
  microbench once per build, not once per boot;
- an arm that fails to compile or run fails the boot: a Mosaic error is
  never turned into a choice (a table too large for VMEM is not an error
  — the size rule sends it to XLA before anything compiles);
- ``ZB_PALLAS=0/1`` remains the manual override (checked inside
  ``pallas_ops.use_pallas``, so a tuned table never shadows it), and
  ``ZB_AUTOTUNE=0`` skips tuning entirely (keeps the defaults);
- off-TPU this is a no-op: Mosaic is TPU-only and ``use_pallas`` already
  pins every family to the XLA fallbacks there.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from zeebe_tpu import compile_cache
from zeebe_tpu.tpu import hashmap, jit_registry, pallas_ops as pops

_CHAIN = 8   # dependent ops per timed call (amortizes dispatch overhead)
_REPS = 5    # timed repetitions; min is the reported cost
_MARGIN = 1.05  # pallas must beat XLA by >5% to win (ties keep XLA: one
# fewer Mosaic program to trust on an unproven build)

_T = 1 << 12  # table rows for the probes
_B = 1 << 11  # batch per op
_K = 16       # row width

_state: Dict[str, object] = {
    "done": False, "source": "default", "timings_us": {},
}


def dispatch_source() -> str:
    """Where the current dispatch came from: ``default`` (never tuned),
    ``env`` (ZB_PALLAS override), ``cache`` (fingerprint hit), or
    ``measured`` (microbench ran this boot)."""
    return str(_state["source"])


def dispatch_timings() -> dict:
    """Per-family microbench timings (µs) behind the current dispatch —
    measured this boot or read back from the cache; empty otherwise."""
    return dict(_state["timings_us"])


def build_fingerprint() -> str:
    """Identity of the (jax, jaxlib, libtpu/device) combination a cached
    decision table is valid for."""
    import jax.extend.backend
    import jaxlib

    dev = jax.devices()[0]
    return (
        f"{jax.__version__}|{jaxlib.__version__}|"
        f"{dev.platform}:{dev.device_kind}|"
        f"{jax.extend.backend.get_backend().platform_version}"
    )


def _cache_path() -> str:
    digest = hashlib.sha256(build_fingerprint().encode()).hexdigest()[:16]
    return os.path.join(
        compile_cache.cache_root(), f"autotune-{digest}.json"
    )


def _load_cache() -> Optional[dict]:
    try:
        with open(_cache_path()) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if doc.get("fingerprint") != build_fingerprint():
        return None
    decisions = doc.get("decisions")
    if not isinstance(decisions, dict):
        return None
    return doc


def _save_cache(decisions: dict, timings: dict) -> None:
    path = _cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(
                {
                    "fingerprint": build_fingerprint(),
                    "decisions": decisions,
                    "timings_us": timings,
                },
                f,
                indent=2,
            )
    except OSError:
        pass  # cache is an optimization, never fatal


def _time(fn: Callable[[], object]) -> float:
    """Best-of-N wall time of ``fn`` (compiles on the first call)."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _operands(rng: np.random.Generator):
    tbl = jnp.asarray(rng.integers(0, 100, (_T, _K)), jnp.int32)
    t1 = jnp.asarray(rng.integers(0, 100, (_T,)), jnp.int32)
    # a 64-bit column as the state holds it: [T, 2] i32 (lo, hi) planes
    t64 = jnp.asarray(
        rng.integers(0, 1 << 40, (_T, 1), dtype=np.int64).view(np.int32)
    )
    slots = jnp.asarray(rng.integers(0, _T, (_B,)), jnp.int32)
    active = jnp.asarray(rng.random(_B) < 0.7)
    vals = jnp.asarray(rng.integers(0, 1000, (_B, _K)), jnp.int32)
    mask = jnp.asarray(rng.random((_B, _K)) < 0.3)
    keys = jnp.asarray(
        rng.choice(np.arange(1, 10 * _T, 5, dtype=np.int64), _B, replace=False)
    )
    return tbl, t1, t64, slots, active, vals, mask, keys


def _benches() -> Dict[str, Callable[[], object]]:
    """family -> jit-able dependent-chain workload. Each chain feeds the
    previous op's output table into the next op, so per-op cost cannot
    hide behind pipelining."""
    rng = np.random.default_rng(23)
    tbl, t1, t64, slots, active, vals, mask, keys = _operands(rng)
    lvals = jnp.asarray(rng.integers(0, 9, (_B,)), jnp.int32)
    v64 = keys + 7

    def row_update(t=tbl):
        for i in range(_CHAIN):
            t = pops.masked_row_update(t, slots, active, vals + i, mask)
        return t

    def row_max(t=tbl):
        for i in range(_CHAIN):
            t = pops.masked_row_max(t, slots, active, vals + i)
        return t

    def row_add(t=tbl):
        for i in range(_CHAIN):
            t = pops.masked_row_add(t, slots, active, vals + i, mask)
        return t

    def lane(t=t1):
        for i in range(_CHAIN):
            t = pops.masked_lane_update(t, slots, active, lvals + i)
        return t

    def vec64(t=t64):
        for i in range(_CHAIN):
            t = pops.masked_vec64_update(t, slots, active, v64 + i)
        return t

    def lookup():
        table, _ = hashmap.insert(
            hashmap.make(_T * 2), keys, jnp.arange(_B, dtype=jnp.int32),
            jnp.ones((_B,), bool),
        )
        probe = keys
        acc = jnp.int32(0)
        for _ in range(_CHAIN):
            found, slot = pops.lookup(table, probe + acc, active)
            acc = jnp.max(jnp.where(found, slot, 0))
        return acc

    def insert():
        table = hashmap.make(_T * 4)
        for i in range(_CHAIN):
            table, ok = pops.insert(
                table, keys + i, jnp.arange(_B, dtype=jnp.int32), active
            )
        return table.keys_lo, table.keys_hi

    def delete():
        table, _ = hashmap.insert(
            hashmap.make(_T * 4), keys, jnp.arange(_B, dtype=jnp.int32),
            jnp.ones((_B,), bool),
        )
        for i in range(_CHAIN):
            table = pops.delete(table, keys + i, active)
        return table.keys_lo, table.keys_hi

    def gather(t=tbl, r=t1):
        # representative phase-B shape: several row reads off 2D tables
        # plus lane reads off a 1D table, chained through the gathered rows
        s = slots
        for _ in range(_CHAIN // 2):
            rows_a, rows_b, lanes = pops.fused_gather_rows(
                [t, r],
                [pops.GatherOp(0, s),
                 pops.GatherOp(0, (s + 1) % _T),
                 pops.GatherOp(1, s)],
            )
            s = (jnp.max(rows_a, axis=1) + jnp.max(rows_b, axis=1)
                 + lanes) % _T
        return s

    def emit(t=tbl):
        # representative phase-C shape: queue compaction — one packed row
        # take at a data-dependent permutation, chained through the output
        for _ in range(_CHAIN):
            order = jnp.argsort(t[:_B, 0], stable=True).astype(jnp.int32)
            (taken,) = pops.fused_gather_rows(
                [t], [pops.GatherOp(0, order)], family="emit"
            )
            t = t.at[:_B].set(taken + 1)
        return t

    def fused(t=tbl, r=t1):
        # representative phase-E shape: mixed set/add/max rows + a lane
        # write, chained through the output tables
        for i in range(_CHAIN // 2):
            ops = [
                pops.TableOp(0, "add", slots, active, vals + i, mask),
                pops.TableOp(0, "set", slots, active, vals + i, mask),
                pops.TableOp(0, "max", slots, active, vals + i),
                pops.TableOp(1, "set", slots, active, lvals + i),
            ]
            t, r = pops.fused_table_commit([t, r], ops)
        return t

    return {
        "row_update": row_update,
        "row_max": row_max,
        "row_add": row_add,
        "lane": lane,
        "vec64": vec64,
        "lookup": lookup,
        "insert": insert,
        "delete": delete,
        "fused": fused,
        "gather": gather,
        "emit": emit,
    }


def audit_candidates() -> Dict[str, Callable]:
    """Register and return one jitted program per microbench family, for
    ``tools/zbaudit`` to lower and audit. ``measure()`` registers the
    ``.xla``/``.pallas`` timing arms only when it actually runs; this
    enumerates the same workloads without timing anything."""
    return {
        family: jit_registry.register_jit(
            f"autotune.{family}",
            fn,
            max_signatures=1,
            notes="boot microbench candidate; carries no engine state",
        )
        for family, fn in _benches().items()
    }


def measure(progress: Optional[Callable[[str], None]] = None):
    """Run the per-family A/B microbench on the current backend. Returns
    (decisions, timings_us) — decisions maps family -> use pallas."""
    decisions: Dict[str, bool] = {}
    timings: Dict[str, dict] = {}
    benches = _benches()
    for family, fn in benches.items():
        # two jit instances so each dispatch arm traces (and caches) its
        # own program — a shared cache would reuse the first arm's trace
        jitted_x = jit_registry.register_jit(
            f"autotune.{family}.xla", fn, max_signatures=1,
            notes="boot microbench candidate (XLA arm); no state args",
        )
        jitted_p = jit_registry.register_jit(
            f"autotune.{family}.pallas", fn, max_signatures=1,
            notes="boot microbench candidate (pallas arm); no state args",
        )
        if family == "fused":
            # the fused baseline is the UNFUSED chain under the already-
            # tuned per-family winners — with the fused family pinned OFF
            # explicitly: a missing "fused" key defaults to pallas, which
            # would time the mega-pass against itself and silently lose
            # every A/B
            prev = pops.get_dispatch()
            pops.set_dispatch({**decisions, "fused": False})
            try:
                t_xla = _time(jitted_x)
            finally:
                pops.set_dispatch(prev)
        else:
            with pops.forced("xla"):
                t_xla = _time(jitted_x)
        # a pallas arm that fails to compile or run raises out of the boot:
        # at these shapes every table fits VMEM, so an error here is a
        # broken kernel, not a size refusal
        with pops.forced("pallas"):
            t_pal = _time(jitted_p)
        win = t_pal * _MARGIN < t_xla
        decisions[family] = bool(win)
        timings[family] = {
            "xla_us": round(t_xla * 1e6, 1),
            "pallas_us": round(t_pal * 1e6, 1),
        }
        if progress:
            progress(
                f"autotune {family}: xla {t_xla*1e6:.0f}us "
                f"pallas {t_pal*1e6:.0f}us -> "
                f"{'pallas' if win else 'xla'}"
            )
    return decisions, timings


def ensure_autotuned(
    progress: Optional[Callable[[str], None]] = None, force: bool = False
) -> dict:
    """Idempotent boot hook: install per-family dispatch decisions for the
    running build (cache hit or fresh measurement). Called from
    ``TpuPartitionEngine.__init__`` and the engine factory's boot; cheap
    no-op off-TPU and on every call after the first."""
    if _state["done"] and not force:
        return pops.get_dispatch()
    if pops.env_override() is not None:
        # manual override active: the dispatch table is shadowed anyway
        _state.update(done=True, source="env")
        return pops.get_dispatch()
    if os.environ.get("ZB_AUTOTUNE", "").strip() in ("0", "false", "off"):
        _state.update(done=True, source="disabled")
        return pops.get_dispatch()
    if jax.default_backend() != "tpu":
        _state.update(done=True, source="off-tpu")
        return pops.get_dispatch()
    cached = None if force else _load_cache()
    if cached is not None:
        pops.set_dispatch(cached["decisions"])
        _state.update(
            done=True, source="cache",
            timings_us=cached.get("timings_us") or {},
        )
        if progress:
            progress(f"autotune: cached decisions {cached['decisions']}")
        return pops.get_dispatch()
    decisions, timings = measure(progress)
    pops.set_dispatch(decisions)
    _save_cache(decisions, timings)
    _state.update(done=True, source="measured", timings_us=timings)
    return pops.get_dispatch()


def get_decisions_json() -> str:
    """Current per-family dispatch as a JSON string (logging helper)."""
    return json.dumps(pops.get_dispatch(), sort_keys=True)


def main() -> None:
    """Self-check CLI: run the microbench (ignoring the cache), print the
    per-family table, and verify the chosen dispatch still passes the
    pallas selfcheck. Needs a TPU and exits non-zero without one (ci.sh
    calls it only where one is attached)."""
    import sys

    if jax.default_backend() != "tpu":
        raise SystemExit("autotune self-check needs a TPU backend; found none")
    decisions = ensure_autotuned(progress=lambda m: print(m, flush=True),
                                 force=True)
    print(f"dispatch ({dispatch_source()}): {json.dumps(decisions)}")
    pops.selfcheck()
    print("autotune self-check OK")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
