"""Pallas TPU primitives for the step kernel's table operations.

XLA lowers general scatters/gathers and the hashmap probe loops to SERIAL
per-index programs on TPU (~70ns-1.4ms per op at wave 2^14 — see
PERF_NOTES.md); the whole round is a dependent chain of ~70 such ops, so
op count × batch dominates. These kernels replace each op family with one
serial pallas pass whose per-record cost is a handful of VPU/scalar-core
instructions (~1.5-5ns/record measured, benchmarks/pallas_probe.py):

- ``masked_row_update`` / ``masked_row_accum``: ``tbl[slot[i]] =
  where(lane_mask[i], vals[i], old)`` for active records, serial in batch
  order (= the XLA chain's last-writer-wins rank order).
- ``masked_lane_update`` / ``masked_lane_accum``: the 1D-table variant;
  the table is viewed as [T/128, 128] and the dynamic lane is modified by
  vector select (TPU has no scalar VMEM stores).
- ``lookup`` / ``insert`` / ``delete``: the hashmap ops
  (zeebe_tpu.tpu.hashmap semantics). Bucket LAYOUT may differ from the
  XLA path when colliding keys race (XLA claims are round-synchronous,
  this path is serial) — the key→slot mapping and probe invariants are
  identical, so tables from either path are interchangeable.

Addressing rules (load-bearing, measured):
- per-record control scalars (slots, flags, hashes, key halves) MUST live
  in SMEM — extracting a scalar from a VMEM vector costs ~300x;
- the batch is grid-chunked so each chunk's scalars fit SMEM;
- int64 never enters a kernel, and no table is int64 anywhere: the
  state holds its 64-bit columns as (lo, hi) i32 plane pairs
  (``tpu/state.py``), and only wave-sized values are ever made int64.

Every kernel holds its WHOLE table in VMEM for the pass, so a family takes
the pallas form only for tables whose windows fit (``_fits_vmem``, a static
shape rule applied at every call site); larger tables take the XLA form
and the refusal is recorded (``size_rulings``). At the served 2^20-row
capacity that sends every 2D row table to XLA — a ``[2^20, 6]`` i32 table
pads to 512 MiB of VMEM — while the 1D tables still fit.

Everything falls back to the XLA implementations off-TPU (tests run on
the CPU mesh; tests/test_chip_compile.py compiles the kernels for a
described v5e, chip_smoke.py runs them on one).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from zeebe_tpu.tpu import hashmap
from zeebe_tpu.tpu.hashmap import EMPTY, HashTable, MAX_PROBES, TOMBSTONE

LANES = 128
# lane extraction = max(where(sel, row, INT32_MIN)): exact for every value
# (jnp.sum's 1D reduce does not lower under x64; max does), and the weak
# python literal adopts i32 from the row instead of promoting
_CHUNK = 2048  # records per grid step; scalars per chunk must fit SMEM

# ---------------------------------------------------------------------------
# per-family dispatch: pallas vs XLA is BUILD-dependent (PERF_NOTES round 4:
# libtpu builds with the serial per-index scatter lowering need the pallas
# passes, builds with the DMA-pipelined lowering are faster through plain
# XLA — and the winner flipped between builds). The engine-boot autotune
# (zeebe_tpu.tpu.autotune) measures both paths per op family on the actual
# build and writes the winners here; ZB_PALLAS=0/1 remains the manual
# override for A/B benchmarking.
# ---------------------------------------------------------------------------

FAMILIES = (
    "row_update", "row_max", "row_add", "lane", "vec64",
    "lookup", "insert", "delete", "fused", "gather", "emit",
)

# family -> use pallas?  Written once by autotune.set_dispatch; until then
# every family defaults to pallas-on-TPU (the pre-autotune behavior).
_DECISIONS: dict = {}
_FORCED: Optional[str] = None  # "pallas" | "xla" | None (autotune probes)


def set_dispatch(decisions: dict) -> None:
    """Install autotuned per-family decisions ({family: bool})."""
    _DECISIONS.clear()
    _DECISIONS.update({k: bool(v) for k, v in decisions.items()})


def get_dispatch() -> dict:
    return dict(_DECISIONS)


@contextlib.contextmanager
def forced(mode: Optional[str]):
    """Force every op onto one path regardless of env/autotune decisions
    (``"pallas"`` / ``"xla"``). Used by the autotune microbenches and the
    parity checks; traces taken inside the context bake the forced path
    into the compiled program."""
    global _FORCED
    prev = _FORCED
    _FORCED = mode
    try:
        yield
    finally:
        _FORCED = prev


def env_override() -> Optional[bool]:
    """The ``ZB_PALLAS`` manual override, or None when unset/unrecognized
    (one parser shared with the autotune, so an unrecognized value can
    never disable tuning while also failing to force a path)."""
    import os

    env = os.environ.get("ZB_PALLAS", "").strip().lower()
    if env in ("0", "false", "off", "no"):
        return False
    if env in ("1", "true", "on", "yes"):
        return True
    return None


def use_pallas(family: str = "row_update") -> bool:
    """Pallas path for this op family? Priority: forced() context >
    ZB_PALLAS env override > autotuned per-family decision > default
    (pallas on TPU). Always False off-TPU (Mosaic is TPU-only)."""
    if jax.default_backend() != "tpu":
        return False
    if _FORCED == "pallas":
        return True
    if _FORCED == "xla":
        return False
    env = env_override()
    if env is not None:
        return env
    return _DECISIONS.get(family, True)


def _use_pallas(family: str = "row_update") -> bool:
    return use_pallas(family)


def _chunk(b: int) -> int:
    c = min(b, _CHUNK)
    while b % c:
        c //= 2
    return max(c, 1)


# ---------------------------------------------------------------------------
# table-size rule: which shapes a pallas pass can hold
# ---------------------------------------------------------------------------
#
# What every kernel asks Mosaic for (``vmem_limit_bytes``). A v5e core has
# 128 MiB of VMEM; asked through the chip's compiler, it grants a call's
# scoped allocation up to the limit the call names and refuses the call
# beyond it (row_update, two [T, 6] windows: T = 112,640 compiles at this
# limit, 113,664 is refused; at a limit of 128 MiB and above the ceiling is
# 130,048 rows, the operand blocks taking the rest). The same number
# bounds the rule below, so what the rule admits the compiler accepts.
VMEM_LIMIT_BYTES = 110 * 1024 * 1024
_SUBLANES = 8  # an i32 VMEM window is tiled (8, 128)

_log = logging.getLogger(__name__)
# (family, table windows) -> {"admitted", "vmem_bytes"}; filled at trace
# time, only for calls that would otherwise take the pallas form
_SIZE_RULINGS: Dict[tuple, dict] = {}


def _window_bytes(shape: Sequence[int]) -> int:
    """VMEM bytes of one i32 window: rows pad to 8 sublanes, columns to 128
    lanes — a [T, 6] table costs 512 B per row, not 24."""
    rows, cols = (1, shape[0]) if len(shape) == 1 else shape
    return (
        -(-rows // _SUBLANES) * _SUBLANES * -(-cols // LANES) * LANES * 4
    )


def _fits_vmem(
    family: str,
    tables: Sequence[Sequence[int]],
    blocks: Sequence[Sequence[int]] = (),
) -> bool:
    """The static shape rule: do one call's VMEM windows fit under
    ``VMEM_LIMIT_BYTES``? ``tables`` are the whole-table windows held for
    the pass (an aliased output is its own window, so a read-modify-write
    table counts twice); ``blocks`` are the per-chunk operand blocks, which
    the grid pipeline double-buffers. SMEM operands are not VMEM. A refusal
    sends the call to its XLA form and is recorded, never raised."""
    need = sum(_window_bytes(t) for t in tables) + 2 * sum(
        _window_bytes(b) for b in blocks
    )
    admitted = need <= VMEM_LIMIT_BYTES
    key = (family, tuple(tuple(t) for t in tables))
    if key not in _SIZE_RULINGS:
        _SIZE_RULINGS[key] = {"admitted": admitted, "vmem_bytes": need}
        if not admitted:
            _log.info(
                "pallas %s refused for tables %s: %d B of VMEM windows > "
                "%d B; XLA form", family, list(key[1]), need,
                VMEM_LIMIT_BYTES,
            )
    return admitted


def size_rulings() -> List[dict]:
    """Every (family, table windows) the size rule has ruled on so far in
    this process, admitted or refused, with the padded VMEM bytes."""
    return [
        {"family": fam, "tables": [list(t) for t in tables], **ruling}
        for (fam, tables), ruling in _SIZE_RULINGS.items()
    ]


def _pallas_call(kernel, grid, in_specs, out_specs, out_shape, aliases):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",),
        ),
    )


def _smem_spec(c):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec((c,), lambda g: (g,), memory_space=pltpu.SMEM)


def _vmem_rows_spec(c, k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec((c, k), lambda g: (g, jnp.int32(0)), memory_space=pltpu.VMEM)


def _vmem_full_spec(shape):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(
        shape, lambda g: tuple(jnp.int32(0) for _ in shape),
        memory_space=pltpu.VMEM,
    )


# ---------------------------------------------------------------------------
# 2D-table row updates
# ---------------------------------------------------------------------------


def masked_row_update(
    table: jax.Array,  # [T, K] i32
    slots: jax.Array,  # [B] i32 (any value; inactive rows ignored)
    active: jax.Array,  # [B] bool
    vals: jax.Array,  # [B, K] i32
    lane_mask: Optional[jax.Array] = None,  # [B, K] bool; None = full row
) -> jax.Array:
    """Serial batch-order row writes: for i in range(B): if active[i]:
    row = table[slots[i]]; table[slots[i]] = where(lane_mask[i], vals[i], row).

    Equivalent to the XLA ``table.at[where(active, slots, T)].set(vals,
    mode="drop")`` chain (last writer in batch order wins)."""
    b = slots.shape[0]
    t, k = table.shape
    c = _chunk(b)
    blind = lane_mask is None
    if not (
        _use_pallas("row_update")
        and _fits_vmem(
            "row_update", [(t, k)] * 2, [(c, k)] * (1 if blind else 2)
        )
    ):
        idx = jnp.where(active, slots, table.shape[0])
        if blind:
            return table.at[idx].set(vals, mode="drop")
        # element-wise scatter: two active records may target DISJOINT
        # lanes of the same row (parallel-join arrivals) — a row-level
        # read-merge-write would drop one of them
        k = table.shape[1]
        rows = jnp.where(
            active[:, None] & lane_mask, slots[:, None], table.shape[0]
        )
        cols = jnp.broadcast_to(
            jnp.arange(k, dtype=jnp.int32)[None, :], lane_mask.shape
        )
        return table.at[rows, cols].set(vals, mode="drop")

    if blind:
        lane_mask = jnp.ones((1, 1), jnp.int32)  # placeholder operand

    def kernel(slots_ref, active_ref, vals_ref, mask_ref, tbl_ref, out_ref):
        _init_out(out_ref, tbl_ref)

        def body(i, _):
            @functools.partial(_when, active_ref[i] != 0)
            def _():
                s = slots_ref[i]
                if blind:
                    out_ref[s, :] = vals_ref[i, :]
                else:
                    row = out_ref[s, :]
                    out_ref[s, :] = jnp.where(
                        mask_ref[i, :] != 0, vals_ref[i, :], row
                    )
            return jnp.int32(0)

        lax.fori_loop(jnp.int32(0), jnp.int32(c), body, jnp.int32(0))

    mask_spec = (
        _vmem_full_spec((1, 1)) if blind else _vmem_rows_spec(c, k)
    )
    return _pallas_call(
        kernel,
        grid=(b // c,),
        in_specs=[
            _smem_spec(c),
            _smem_spec(c),
            _vmem_rows_spec(c, k),
            mask_spec,
            _vmem_full_spec((t, k)),
        ],
        out_specs=_vmem_full_spec((t, k)),
        out_shape=jax.ShapeDtypeStruct((t, k), table.dtype),
        aliases={4: 0},
    )(
        slots.astype(jnp.int32),
        active.astype(jnp.int32),
        vals.astype(table.dtype),
        (lane_mask if blind else lane_mask.astype(jnp.int32)),
        table,
    )


def _when(cond, fn):
    from jax.experimental import pallas as pl

    return pl.when(cond)(fn)


def _init_out(out_ref, in_ref):
    """Copy the aliased input block into the output block on grid step 0.

    ``input_output_aliases`` donates the HBM buffer but does NOT guarantee
    the output VMEM window starts with the input's contents (observed on
    this jax/libtpu build: it reads back zeros). Every RMW kernel must
    seed its output window explicitly; the window then persists across
    grid steps (constant index_map + arbitrary semantics)."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = in_ref[...]


def masked_row_max(
    table: jax.Array,  # [T, K] i32
    slots: jax.Array,  # [B] i32
    active: jax.Array,  # [B] bool
    vals: jax.Array,  # [B, K] i32
) -> jax.Array:
    """Serial ``table[slot[i]] = maximum(old, vals[i])`` for active records
    (the ``.at[slots].max(vals, mode="drop")`` analogue; max commutes, so
    batch order does not matter)."""
    b = slots.shape[0]
    t, k = table.shape
    c = _chunk(b)
    if not (
        _use_pallas("row_max")
        and _fits_vmem("row_max", [(t, k)] * 2, [(c, k)])
    ):
        idx = jnp.where(active, slots, table.shape[0])
        return table.at[idx].max(vals.astype(table.dtype), mode="drop")

    def kernel(slots_ref, active_ref, vals_ref, tbl_ref, out_ref):
        _init_out(out_ref, tbl_ref)

        def body(i, _):
            @functools.partial(_when, active_ref[i] != 0)
            def _():
                s = slots_ref[i]
                row = out_ref[s, :]
                out_ref[s, :] = jnp.maximum(row, vals_ref[i, :])
            return jnp.int32(0)

        lax.fori_loop(jnp.int32(0), jnp.int32(c), body, jnp.int32(0))

    return _pallas_call(
        kernel,
        grid=(b // c,),
        in_specs=[
            _smem_spec(c),
            _smem_spec(c),
            _vmem_rows_spec(c, k),
            _vmem_full_spec((t, k)),
        ],
        out_specs=_vmem_full_spec((t, k)),
        out_shape=jax.ShapeDtypeStruct((t, k), table.dtype),
        aliases={3: 0},
    )(
        slots.astype(jnp.int32),
        active.astype(jnp.int32),
        vals.astype(table.dtype),
        table,
    )


def masked_row_add(
    table: jax.Array,  # [T, K] i32
    slots: jax.Array,  # [B] i32
    active: jax.Array,  # [B] bool
    vals: jax.Array,  # [B, K] i32
    lane_mask: Optional[jax.Array] = None,  # [B, K] bool; None = full row
) -> jax.Array:
    """Serial ``table[slot[i], lane] += vals[i, lane]`` for active records
    and masked lanes (integer addition commutes, so batch order does not
    matter; duplicates accumulate like ``.at[].add(..., mode="drop")``)."""
    b = slots.shape[0]
    t, k = table.shape
    c = _chunk(b)
    blind = lane_mask is None
    if not (
        _use_pallas("row_add")
        and _fits_vmem(
            "row_add", [(t, k)] * 2, [(c, k)] * (1 if blind else 2)
        )
    ):
        idx = jnp.where(active, slots, table.shape[0])
        add = vals if lane_mask is None else jnp.where(lane_mask, vals, 0)
        return table.at[idx].add(add.astype(table.dtype), mode="drop")

    if blind:
        lane_mask = jnp.ones((1, 1), jnp.int32)  # placeholder operand

    def kernel(slots_ref, active_ref, vals_ref, mask_ref, tbl_ref, out_ref):
        _init_out(out_ref, tbl_ref)

        def body(i, _):
            @functools.partial(_when, active_ref[i] != 0)
            def _():
                s = slots_ref[i]
                row = out_ref[s, :]
                if blind:
                    out_ref[s, :] = row + vals_ref[i, :]
                else:
                    out_ref[s, :] = jnp.where(
                        mask_ref[i, :] != 0, row + vals_ref[i, :], row
                    )
            return jnp.int32(0)

        lax.fori_loop(jnp.int32(0), jnp.int32(c), body, jnp.int32(0))

    mask_spec = _vmem_full_spec((1, 1)) if blind else _vmem_rows_spec(c, k)
    return _pallas_call(
        kernel,
        grid=(b // c,),
        in_specs=[
            _smem_spec(c),
            _smem_spec(c),
            _vmem_rows_spec(c, k),
            mask_spec,
            _vmem_full_spec((t, k)),
        ],
        out_specs=_vmem_full_spec((t, k)),
        out_shape=jax.ShapeDtypeStruct((t, k), table.dtype),
        aliases={4: 0},
    )(
        slots.astype(jnp.int32),
        active.astype(jnp.int32),
        vals.astype(table.dtype),
        (lane_mask if blind else lane_mask.astype(jnp.int32)),
        table,
    )


# ---------------------------------------------------------------------------
# 1D-table lane updates (table viewed as [T/128, 128])
# ---------------------------------------------------------------------------


def _lane_kernel(accumulate: bool):
    def kernel(slots_ref, active_ref, vals_ref, tbl_ref, out_ref):
        _init_out(out_ref, tbl_ref)
        lane_iota = lax.broadcasted_iota(jnp.int32, (LANES,), 0)

        def body(i, _):
            @functools.partial(_when, active_ref[i] != 0)
            def _():
                s = slots_ref[i]
                r = s >> 7
                lane = s & (LANES - 1)
                row = out_ref[r, :]
                v = vals_ref[i]
                hit = lane_iota == lane
                if accumulate:
                    out_ref[r, :] = jnp.where(hit, row + v, row)
                else:
                    out_ref[r, :] = jnp.where(hit, v, row)
            return jnp.int32(0)

        c = slots_ref.shape[0]
        lax.fori_loop(jnp.int32(0), jnp.int32(c), body, jnp.int32(0))

    return kernel


def _lane_op(table1d, slots, active, vals, accumulate):
    t = table1d.shape[0]
    b = slots.shape[0]
    if not (
        t % LANES == 0
        and _use_pallas("lane")
        and _fits_vmem("lane", [(t // LANES, LANES)] * 2)
    ):
        idx = jnp.where(active, slots, t)
        if accumulate:
            return table1d.at[idx].add(vals.astype(table1d.dtype), mode="drop")
        return table1d.at[idx].set(vals.astype(table1d.dtype), mode="drop")
    c = _chunk(b)
    folded = table1d.reshape(t // LANES, LANES)
    out = _pallas_call(
        _lane_kernel(accumulate),
        grid=(b // c,),
        in_specs=[
            _smem_spec(c),
            _smem_spec(c),
            _smem_spec(c),
            _vmem_full_spec((t // LANES, LANES)),
        ],
        out_specs=_vmem_full_spec((t // LANES, LANES)),
        out_shape=jax.ShapeDtypeStruct((t // LANES, LANES), table1d.dtype),
        aliases={3: 0},
    )(
        slots.astype(jnp.int32),
        active.astype(jnp.int32),
        vals.astype(table1d.dtype),
        folded,
    )
    return out.reshape(t)


def masked_lane_update(table1d, slots, active, vals):
    """1D analogue of masked_row_update (i32 tables only)."""
    return _lane_op(table1d, slots, active, vals, accumulate=False)


def masked_lane_accum(table1d, slots, active, deltas):
    """Serial ``table[slot] += delta`` (i32), batch order."""
    return _lane_op(table1d, slots, active, deltas, accumulate=True)


# ---------------------------------------------------------------------------
# int64 plane helpers, for WAVE-sized values only. A TPU has no 64-bit
# integers: XLA holds an s64 array as two separate u32 arrays, so these
# bitcasts are an interleave / de-interleave of the whole operand (and an
# s64 program parameter or result is split / combined whole on top). At wave
# size that is nothing; at table size it was 4 ms of a 9 ms step (PERF.md,
# PR 30) — so the state's tables ARE planes, [rows, 2C] i32, and nothing
# table-sized passes through here.
# ---------------------------------------------------------------------------


def i64_to_planes(x: jax.Array) -> jax.Array:
    """[N, C] i64 → [N, 2C] i32 (little-endian lo/hi pairs per column)."""
    n, cdim = x.shape
    return lax.bitcast_convert_type(x, jnp.int32).reshape(n, 2 * cdim)


def planes_to_i64(p: jax.Array) -> jax.Array:
    """[N, 2C] i32 → [N, C] i64."""
    n, c2 = p.shape
    return lax.bitcast_convert_type(
        p.reshape(n, c2 // 2, 2), jnp.int64
    )


def vec64_to_planes(x: jax.Array) -> jax.Array:
    """[B] i64 → [B, 2] i32."""
    return lax.bitcast_convert_type(x, jnp.int32)


def masked_vec64_update(planes, slots, active, vals64):
    """One 64-bit column's scatter: ``column[slot[i]] = vals64[i]``, the
    column a ``[T, 2]`` i32 plane table (lo, hi), the values ``[B]`` i64."""
    t = planes.shape[0]
    vals = vec64_to_planes(vals64)
    if not (
        _use_pallas("vec64")
        and _fits_vmem(
            "vec64", [(t, 2)] * 2, [(_chunk(slots.shape[0]), 2)]
        )
    ):
        idx = jnp.where(active, slots, t)
        return planes.at[idx].set(vals, mode="drop")
    # force the inner row update onto the pallas path: this call must be
    # exactly what the autotune's "vec64" pallas arm measured — letting it
    # re-consult the independent "row_update" decision could install a
    # hybrid neither A/B arm ever timed
    with forced("pallas"):
        return masked_row_update(planes, slots, active, vals)


# ---------------------------------------------------------------------------
# fused phase-E mega-pass
# ---------------------------------------------------------------------------
#
# The step kernel's phase-E tail is a dependent chain of ~20 masked table
# writes (element-instance rows, job rows, timer bookkeeping, free-slot
# rings, direct-mapped indexes). Profiled on-chip, EVERY one of those ops
# costs ~20ns/record in per-index DMA issue — the chain, not the math, is
# the round's floor (PERF_NOTES round-4 cost model). ``fused_table_commit``
# collapses the whole tail into ONE pallas launch: the tables live in VMEM
# for the duration, each op is a serial register-resident RMW loop, and the
# per-record cost of the entire tail is a handful of VPU instructions.
#
# Ordering contract: ops apply in list order per batch chunk (chunk-major,
# op-minor). This equals the XLA chain's global op-major order whenever
# cross-record conflicts between DIFFERENT ops are confined to commutative
# kinds ("add"/"max") — which the step kernel guarantees: its guards make
# record kinds disjoint per row, so two records never hit the same (row,
# lane) through different non-commutative ops in one round. Within one op,
# serial batch order = the XLA chain's last-writer-wins rank order.


@dataclasses.dataclass
class TableOp:
    """One masked table write inside a fused commit.

    ``table`` indexes into the commit's table list. 2D [T, K] tables take
    ``vals`` [B, K] (+ optional ``mask`` [B, K]); 1D [T] tables (free
    rings, direct-mapped indexes) take scalar ``vals`` [B] and no mask.
    ``kind``: "set" (masked row write, serial last-writer-wins), "add"
    (commutative accumulate), "max" (commutative monotonic merge).
    """

    table: int
    kind: str
    slots: jax.Array
    active: jax.Array
    vals: jax.Array
    mask: Optional[jax.Array] = None


def _apply_op_unfused(tbl: jax.Array, op: TableOp) -> jax.Array:
    """One TableOp through the standalone per-family ops (exact XLA-chain
    semantics off-TPU; per-family autotuned dispatch on-TPU)."""
    if tbl.ndim == 1:
        if op.kind == "add":
            return masked_lane_accum(tbl, op.slots, op.active, op.vals)
        return masked_lane_update(tbl, op.slots, op.active, op.vals)
    if op.kind == "max":
        return masked_row_max(tbl, op.slots, op.active, op.vals)
    if op.kind == "add":
        return masked_row_add(tbl, op.slots, op.active, op.vals, op.mask)
    return masked_row_update(tbl, op.slots, op.active, op.vals, op.mask)


def fused_table_commit(
    tables: Sequence[jax.Array], ops: Sequence[TableOp]
) -> List[jax.Array]:
    """Apply ``ops`` to ``tables`` (all i32; 64-bit state is planes) as
    ONE pallas serial pass — or, when the fused family lost the autotune
    A/B, the tables together do not fit VMEM, or off-TPU, as the
    equivalent unfused op chain (each op then decides on its own table).
    Returns the new tables in input order.
    """
    ops = list(ops)
    if not ops:
        return list(tables)
    b = ops[0].slots.shape[0]
    c = _chunk(b)
    fusable = (
        all(t.ndim == 1 or t.ndim == 2 for t in tables)
        and all(t.shape[0] % LANES == 0 for t in tables if t.ndim == 1)
        and all(op.slots.shape[0] == b for op in ops)
        and use_pallas("fused")
    )
    is1d = [t.ndim == 1 for t in tables]
    if fusable:
        folded_shapes = [
            (t.shape[0] // LANES, LANES) if t.ndim == 1 else tuple(t.shape)
            for t in tables
        ]
        blocks = []
        for op in ops:
            if not is1d[op.table]:
                k = tables[op.table].shape[1]
                blocks += [(c, k)] * (1 if op.mask is None else 2)
        fusable = _fits_vmem("fused", folded_shapes * 2, blocks)
    if not fusable:
        out = list(tables)
        for op in ops:
            out[op.table] = _apply_op_unfused(out[op.table], op)
        return out

    ntab = len(tables)
    folded = [
        t.reshape(shape) for t, shape in zip(tables, folded_shapes)
    ]

    # static operand layout: per op (slots, active, vals[, mask]) then the
    # tables; refs arrive in the same flat order, outputs one per table
    operands: List[jax.Array] = []
    in_specs = []
    meta = []  # (kind, table, one_d, masked, base ref index)
    for op in ops:
        one_d = is1d[op.table]
        base = len(operands)
        operands.append(op.slots.astype(jnp.int32))
        in_specs.append(_smem_spec(c))
        operands.append(op.active.astype(jnp.int32))
        in_specs.append(_smem_spec(c))
        if one_d:
            operands.append(op.vals.astype(tables[op.table].dtype))
            in_specs.append(_smem_spec(c))
        else:
            k = tables[op.table].shape[1]
            operands.append(op.vals.astype(tables[op.table].dtype))
            in_specs.append(_vmem_rows_spec(c, k))
        masked = (not one_d) and op.mask is not None
        if masked:
            operands.append(op.mask.astype(jnp.int32))
            in_specs.append(_vmem_rows_spec(c, k))
        meta.append((op.kind, op.table, one_d, masked, base))
    n_operands = len(operands)
    for f in folded:
        in_specs.append(_vmem_full_spec(f.shape))

    def kernel(*refs):
        in_tab = refs[n_operands : n_operands + ntab]
        out_tab = refs[n_operands + ntab :]
        for j in range(ntab):
            _init_out(out_tab[j], in_tab[j])
        lane_iota = lax.broadcasted_iota(jnp.int32, (LANES,), 0)

        for kind, tab, one_d, masked, base in meta:
            s_ref = refs[base]
            a_ref = refs[base + 1]
            v_ref = refs[base + 2]
            m_ref = refs[base + 3] if masked else None
            o_ref = out_tab[tab]

            def body(i, _, s_ref=s_ref, a_ref=a_ref, v_ref=v_ref,
                     m_ref=m_ref, o_ref=o_ref, kind=kind, one_d=one_d,
                     masked=masked):
                @functools.partial(_when, a_ref[i] != 0)
                def _():
                    s = s_ref[i]
                    if one_d:
                        r = s >> 7
                        hit = lane_iota == (s & (LANES - 1))
                        row = o_ref[r, :]
                        v = v_ref[i]
                        if kind == "add":
                            o_ref[r, :] = jnp.where(hit, row + v, row)
                        else:
                            o_ref[r, :] = jnp.where(hit, v, row)
                    else:
                        row = o_ref[s, :]
                        v = v_ref[i, :]
                        if kind == "max":
                            o_ref[s, :] = jnp.maximum(row, v)
                        elif kind == "add":
                            if masked:
                                o_ref[s, :] = jnp.where(
                                    m_ref[i, :] != 0, row + v, row
                                )
                            else:
                                o_ref[s, :] = row + v
                        else:
                            if masked:
                                o_ref[s, :] = jnp.where(
                                    m_ref[i, :] != 0, v, row
                                )
                            else:
                                o_ref[s, :] = v
                return jnp.int32(0)

            lax.fori_loop(jnp.int32(0), jnp.int32(c), body, jnp.int32(0))

    out = _pallas_call(
        kernel,
        grid=(b // c,),
        in_specs=in_specs,
        out_specs=tuple(_vmem_full_spec(f.shape) for f in folded),
        out_shape=tuple(
            jax.ShapeDtypeStruct(f.shape, f.dtype) for f in folded
        ),
        aliases={n_operands + j: j for j in range(ntab)},
    )(*operands, *folded)
    return [
        o.reshape(tables[j].shape) if is1d[j] else o
        for j, o in enumerate(out)
    ]


# ---------------------------------------------------------------------------
# fused phase-B/C mega-gather
# ---------------------------------------------------------------------------
#
# The read side of the round mirrors the write side: phases B/C open with
# one row gather per (role, table) pair — element-instance rows for the
# record/scope/activity keys, job rows, timer columns, payload rows — and
# each XLA gather costs the same ~20ns/record per-index DMA issue as the
# scatters fused_table_commit absorbed. ``fused_gather_rows`` collapses
# every read of a wave into ONE pallas launch: the tables sit in VMEM, a
# serial loop copies each requested row into a register-composed output
# block, and the per-record cost of the whole read tail is one row copy.
#
# The XLA fallback is where the op-census win lives: reads commute, so
# gathers against the SAME table concatenate their index vectors (one
# gather + static splits replaces N gathers, elementwise-identical), and
# 1D tables of one dtype concatenate along axis 0 with per-table index
# offsets. The fallback is pure data movement — no masking, no RMW — so
# fused-vs-unfused results are bit-identical by construction.


@dataclasses.dataclass
class GatherOp:
    """One row (2D table) or lane (1D table) read inside a fused gather.

    ``table`` indexes into the pass's table list; ``slots`` [B] i32 must
    already be clipped into range (the step kernel clips every slot
    vector once, right after the lookups).
    """

    table: int
    slots: jax.Array


def _gather_unfused(
    tables: Sequence[jax.Array], ops: Sequence[GatherOp]
) -> List[jax.Array]:
    """XLA gather chain with per-table index concatenation: one gather per
    2D table touched, one per 1D-table dtype group."""
    results: List[Optional[jax.Array]] = [None] * len(ops)
    by_table: dict = {}
    for i, op in enumerate(ops):
        by_table.setdefault(op.table, []).append(i)
    oned: List[int] = []
    for t_idx, op_ids in by_table.items():
        tbl = tables[t_idx]
        if tbl.ndim == 1:
            oned.extend(op_ids)
            continue
        if len(op_ids) == 1:
            i = op_ids[0]
            results[i] = tbl[ops[i].slots]
            continue
        cat = jnp.concatenate([ops[i].slots for i in op_ids])
        rows = tbl[cat]
        off = 0
        for i in op_ids:
            n = ops[i].slots.shape[0]
            results[i] = rows[off : off + n]
            off += n
    by_dtype: dict = {}
    for i in oned:
        by_dtype.setdefault(tables[ops[i].table].dtype, []).append(i)
    for op_ids in by_dtype.values():
        if len(op_ids) == 1:
            i = op_ids[0]
            results[i] = tables[ops[i].table][ops[i].slots]
            continue
        tbl_ids: List[int] = []
        for i in op_ids:
            if ops[i].table not in tbl_ids:
                tbl_ids.append(ops[i].table)
        offs = {}
        off = 0
        for t in tbl_ids:
            offs[t] = off
            off += tables[t].shape[0]
        cat_tbl = (
            tables[tbl_ids[0]] if len(tbl_ids) == 1
            else jnp.concatenate([tables[t] for t in tbl_ids])
        )
        cat_idx = jnp.concatenate(
            [ops[i].slots + offs[ops[i].table] for i in op_ids]
        )
        vals = cat_tbl[cat_idx]
        off = 0
        for i in op_ids:
            n = ops[i].slots.shape[0]
            results[i] = vals[off : off + n]
            off += n
    return results  # type: ignore[return-value]


def _gather_norm_shape(t: jax.Array) -> Tuple[int, int]:
    """Shape of a table's i32 normal form inside ``fused_gather_rows``:
    2D as it is, 1D folded to [T/128, 128] and read by lane."""
    if t.ndim == 2:
        return tuple(t.shape)
    return (t.shape[0] // LANES, LANES)


def fused_gather_rows(
    tables: Sequence[jax.Array],
    ops: Sequence[GatherOp],
    family: str = "gather",
) -> List[jax.Array]:
    """``[tables[op.table][op.slots] for op in ops]`` as ONE pallas serial
    pass — or, off the pallas path, as one concatenated XLA gather per
    table group. Tables may be i32/f32/i8/bool, 1D or 2D; f32 crosses the
    pallas boundary as a bitcast, i8/bool widened to i32 — exact
    round-trips. (The state has no int64 table: a 64-bit column is i32
    planes, ``tpu/state.py``. One passed here all the same is read by the
    XLA form, not converted whole for a wave's rows.) Every result is
    elementwise equal to direct indexing on both paths.

    ``family`` selects the dispatch row ("gather" for the phase-B/C state
    reads, "emit" for the output-queue compaction takes) so the autotuner
    can pick per-shape winners.
    """
    ops = list(ops)
    if not ops:
        return []
    b = ops[0].slots.shape[0]
    c = _chunk(b)
    fusable = (
        all(op.slots.shape[0] == b for op in ops)
        and all(t.ndim in (1, 2) for t in tables)
        and all(t.shape[0] % LANES == 0 for t in tables if t.ndim == 1)
        and all(t.dtype.itemsize <= 4 for t in tables)
        and use_pallas(family)
    )
    if fusable:
        # every table is VMEM-resident for the whole pass, in the i32
        # normal form built below
        norm_shapes = [_gather_norm_shape(t) for t in tables]
        blocks = [
            (c, norm_shapes[op.table][1])
            for op in ops
            if tables[op.table].ndim == 2
        ]
        fusable = _fits_vmem(family, norm_shapes, blocks)
    if not fusable:
        return _gather_unfused(tables, ops)

    ntab = len(tables)
    n_ops = len(ops)

    # normalize every table to i32 — 2D stays [T, K] (f32 → bitcast, i8 →
    # widened), 1D folds to [T/128, 128] for lane extraction
    norm: List[jax.Array] = []
    decode: List[Tuple[str, object]] = []  # per-table (mode, dtype)
    for t in tables:
        if t.ndim == 2:
            if t.dtype == jnp.float32:
                norm.append(lax.bitcast_convert_type(t, jnp.int32))
                decode.append(("bitcast", t.dtype))
            elif t.dtype == jnp.int32:
                norm.append(t)
                decode.append(("rows", t.dtype))
            else:
                norm.append(t.astype(jnp.int32))
                decode.append(("widen", t.dtype))
        else:
            if t.dtype == jnp.float32:
                norm.append(
                    lax.bitcast_convert_type(t, jnp.int32).reshape(
                        t.shape[0] // LANES, LANES
                    )
                )
                decode.append(("lane_bitcast", t.dtype))
            else:
                norm.append(
                    t.astype(jnp.int32).reshape(t.shape[0] // LANES, LANES)
                )
                decode.append(("lane", t.dtype))

    # the size rule ruled on these shapes before anything was built
    assert [tuple(nt.shape) for nt in norm] == norm_shapes
    lane_modes = ("lane", "lane_bitcast")
    in_specs = [_smem_spec(c) for _ in ops]
    in_specs += [_vmem_full_spec(nt.shape) for nt in norm]
    out_specs = []
    out_shape = []
    for op in ops:
        mode = decode[op.table][0]
        if mode in lane_modes:
            out_specs.append(_smem_spec(c))
            out_shape.append(jax.ShapeDtypeStruct((b,), jnp.int32))
        else:
            k = norm[op.table].shape[1]
            out_specs.append(_vmem_rows_spec(c, k))
            out_shape.append(jax.ShapeDtypeStruct((b, k), jnp.int32))

    meta = [(op.table, decode[op.table][0] in lane_modes) for op in ops]

    def kernel(*refs):
        t_refs = refs[n_ops : n_ops + ntab]
        o_refs = refs[n_ops + ntab :]
        lane_iota = lax.broadcasted_iota(jnp.int32, (LANES,), 0)
        for j, (tab, is_lane) in enumerate(meta):
            s_ref = refs[j]
            t_ref = t_refs[tab]
            o_ref = o_refs[j]

            def body(i, _, s_ref=s_ref, t_ref=t_ref, o_ref=o_ref,
                     is_lane=is_lane):
                s = s_ref[i]
                if is_lane:
                    r = s >> 7
                    sel = lane_iota == (s & (LANES - 1))
                    o_ref[i] = jnp.max(
                        jnp.where(sel, t_ref[r, :], jnp.int32(-(2**31)))
                    )
                else:
                    o_ref[i, :] = t_ref[s, :]
                return jnp.int32(0)

            lax.fori_loop(jnp.int32(0), jnp.int32(c), body, jnp.int32(0))

    out = _pallas_call(
        kernel,
        grid=(b // c,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        aliases={},
    )(*[op.slots.astype(jnp.int32) for op in ops], *norm)

    results: List[jax.Array] = []
    for j, op in enumerate(ops):
        mode, dt = decode[op.table]
        o = out[j]
        if mode == "bitcast":
            results.append(lax.bitcast_convert_type(o, dt))
        elif mode == "widen":
            results.append(o.astype(dt))
        elif mode == "lane_bitcast":
            results.append(lax.bitcast_convert_type(o, dt))
        elif mode == "lane":
            results.append(o.astype(dt))
        else:
            results.append(o)
    return results


# ---------------------------------------------------------------------------
# hashmap ops (int64 keys as (lo, hi) i32 planes)
# ---------------------------------------------------------------------------


_split_keys = hashmap.split_keys  # [B] i64 or [B, 2] planes -> (lo, hi)


def _hash_i32(lo, hi, table_size):
    # must match hashmap._hash exactly (tables move between backends)
    c1 = jnp.uint32(0x9E3779B1).astype(jnp.int32)
    c2 = jnp.uint32(0x85EBCA77).astype(jnp.int32)
    h = (lo * c1) ^ (hi * c2)
    h = h ^ lax.shift_right_logical(h, jnp.int32(15))
    return h & jnp.int32(table_size - 1)


# sentinel planes: EMPTY = -1 → (lo, hi) = (-1, -1); TOMBSTONE = -2 →
# (-2, -1). Real keys are non-negative, so neither collides.


def _fold_table(table: HashTable):
    t = table.size
    return (
        table.keys_lo.reshape(t // LANES, LANES),
        table.keys_hi.reshape(t // LANES, LANES),
        table.vals.reshape(t // LANES, LANES),
    )


def lookup(table: HashTable, keys: jax.Array, valid: jax.Array):
    """Batched probe; identical results to hashmap.lookup."""
    t = table.size
    b = keys.shape[0]
    if not (
        t % LANES == 0
        and _use_pallas("lookup")
        and _fits_vmem("lookup", [(t // LANES, LANES)] * 3)
    ):
        return hashmap.lookup(table, keys, valid)
    c = _chunk(b)
    lo, hi = _split_keys(keys)
    h0 = _hash_i32(lo, hi, t)
    tlo, thi, tvals = _fold_table(table)

    def kernel(h0_ref, lo_ref, hi_ref, valid_ref, tlo_ref, thi_ref, tv_ref,
               found_ref, slot_ref):
        lane_iota = lax.broadcasted_iota(jnp.int32, (LANES,), 0)

        def body(i, _):
            # validity folds into the loop condition (done starts True for
            # invalid records): one less conditional nesting level — the
            # cond→while→masked-op tower otherwise exceeds the tracer's
            # Python recursion budget
            klo = lo_ref[i]
            khi = hi_ref[i]
            h = h0_ref[i]
            invalid = jnp.where(valid_ref[i] == 0, jnp.int32(1), jnp.int32(0))

            # all carries are i32: mosaic's scalar bool conversions recurse
            def probe(carry):
                j, found, slot, done = carry
                idx = (h + j) & (t - 1)
                r = idx >> 7
                lane = idx & (LANES - 1)
                sel = lane_iota == lane
                blo = jnp.max(jnp.where(sel, tlo_ref[r, :], jnp.int32(-(2**31))))
                bhi = jnp.max(jnp.where(sel, thi_ref[r, :], jnp.int32(-(2**31))))
                bval = jnp.max(jnp.where(sel, tv_ref[r, :], jnp.int32(-(2**31))))
                hit = (blo == klo) & (bhi == khi)
                empty = (blo == -1) & (bhi == -1)
                return (
                    j + 1,
                    jnp.where(hit, jnp.int32(1), found),
                    jnp.where(hit, bval, slot),
                    jnp.where(hit | empty, jnp.int32(1), done),
                )

            _, found, slot, _ = lax.while_loop(
                lambda cy: (cy[0] < MAX_PROBES) & (cy[3] == 0),
                probe,
                (jnp.int32(0), jnp.int32(0), jnp.int32(-1), invalid),
            )
            found_ref[i] = found
            slot_ref[i] = slot
            return jnp.int32(0)

        lax.fori_loop(jnp.int32(0), jnp.int32(c), body, jnp.int32(0))

    found, slot = _pallas_call(
        kernel,
        grid=(b // c,),
        in_specs=[_smem_spec(c)] * 4
        + [_vmem_full_spec((t // LANES, LANES))] * 3,
        out_specs=(_smem_spec(c), _smem_spec(c)),
        out_shape=(
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
        ),
        aliases={},
    )(h0, lo, hi, valid.astype(jnp.int32), tlo, thi, tvals)
    return found.astype(bool), slot


def insert(table: HashTable, keys: jax.Array, vals: jax.Array, valid: jax.Array):
    """Batched insert of unique keys (hashmap.insert semantics; bucket
    layout may differ on collisions — see module docstring)."""
    t = table.size
    b = keys.shape[0]
    if not (
        t % LANES == 0
        and _use_pallas("insert")
        and _fits_vmem("insert", [(t // LANES, LANES)] * 6)
    ):
        return hashmap.insert(table, keys, vals, valid)
    c = _chunk(b)
    lo, hi = _split_keys(keys)
    h0 = _hash_i32(lo, hi, t)
    tlo, thi, tvals = _fold_table(table)

    def kernel(h0_ref, lo_ref, hi_ref, vals_ref, valid_ref,
               tlo_in, thi_in, tv_in,
               tlo_ref, thi_ref, tv_ref, ok_ref):
        _init_out(tlo_ref, tlo_in)
        _init_out(thi_ref, thi_in)
        _init_out(tv_ref, tv_in)
        lane_iota = lax.broadcasted_iota(jnp.int32, (LANES,), 0)

        def body(i, _):
            klo = lo_ref[i]
            khi = hi_ref[i]
            h = h0_ref[i]
            v = vals_ref[i]
            invalid = jnp.where(valid_ref[i] == 0, jnp.int32(1), jnp.int32(0))

            # find the first EMPTY bucket; no ref writes inside the loop,
            # validity folded into the condition, i32 carries only
            def probe(carry):
                j, target, placed = carry
                idx = (h + j) & (t - 1)
                r = idx >> 7
                lane = idx & (LANES - 1)
                sel = lane_iota == lane
                blo = jnp.max(jnp.where(sel, tlo_ref[r, :], jnp.int32(-(2**31))))
                bhi = jnp.max(jnp.where(sel, thi_ref[r, :], jnp.int32(-(2**31))))
                # claim EMPTY (-1) or TOMBSTONE (-2) buckets, mirroring
                # the XLA insert (delete-heavy tables fill with
                # tombstones otherwise): hi plane is -1 for both
                free = ((blo == -1) | (blo == -2)) & (bhi == -1)
                return (
                    j + 1,
                    jnp.where(free, idx, target),
                    jnp.where(free, jnp.int32(1), placed),
                )

            _, target, placed = lax.while_loop(
                lambda cy: (cy[0] < MAX_PROBES) & (cy[2] == 0) & (invalid == 0),
                probe,
                (jnp.int32(0), jnp.int32(-1), jnp.int32(0)),
            )

            @functools.partial(_when, placed != 0)
            def _():
                r = target >> 7
                sel = lane_iota == (target & (LANES - 1))
                tlo_ref[r, :] = jnp.where(sel, klo, tlo_ref[r, :])
                thi_ref[r, :] = jnp.where(sel, khi, thi_ref[r, :])
                tv_ref[r, :] = jnp.where(sel, v, tv_ref[r, :])

            ok_ref[i] = placed
            return jnp.int32(0)

        lax.fori_loop(jnp.int32(0), jnp.int32(c), body, jnp.int32(0))

    shape2d = jax.ShapeDtypeStruct((t // LANES, LANES), jnp.int32)
    tlo2, thi2, tv2, ok = _pallas_call(
        kernel,
        grid=(b // c,),
        in_specs=[_smem_spec(c)] * 5
        + [_vmem_full_spec((t // LANES, LANES))] * 3,
        out_specs=(
            _vmem_full_spec((t // LANES, LANES)),
            _vmem_full_spec((t // LANES, LANES)),
            _vmem_full_spec((t // LANES, LANES)),
            _smem_spec(c),
        ),
        out_shape=(shape2d, shape2d, shape2d,
                   jax.ShapeDtypeStruct((b,), jnp.int32)),
        aliases={5: 0, 6: 1, 7: 2},
    )(h0, lo, hi, vals.astype(jnp.int32), valid.astype(jnp.int32),
      tlo, thi, tvals)
    return (
        HashTable(tlo2.reshape(t), thi2.reshape(t), tv2.reshape(t)),
        ok.astype(bool),
    )


def delete(table: HashTable, keys: jax.Array, valid: jax.Array) -> HashTable:
    """Batched delete (tombstones); identical to hashmap.delete."""
    t = table.size
    b = keys.shape[0]
    if not (
        t % LANES == 0
        and _use_pallas("delete")
        and _fits_vmem("delete", [(t // LANES, LANES)] * 4)
    ):
        return hashmap.delete(table, keys, valid)
    c = _chunk(b)
    lo, hi = _split_keys(keys)
    h0 = _hash_i32(lo, hi, t)
    tlo, thi, _ = _fold_table(table)

    def kernel(h0_ref, lo_ref, hi_ref, valid_ref, tlo_in, thi_in,
               tlo_ref, thi_ref):
        _init_out(tlo_ref, tlo_in)
        _init_out(thi_ref, thi_in)
        lane_iota = lax.broadcasted_iota(jnp.int32, (LANES,), 0)

        def body(i, _):
            klo = lo_ref[i]
            khi = hi_ref[i]
            h = h0_ref[i]
            invalid = jnp.where(valid_ref[i] == 0, jnp.int32(1), jnp.int32(0))

            def probe(carry):
                j, target, done = carry
                idx = (h + j) & (t - 1)
                r = idx >> 7
                lane = idx & (LANES - 1)
                sel = lane_iota == lane
                blo = jnp.max(jnp.where(sel, tlo_ref[r, :], jnp.int32(-(2**31))))
                bhi = jnp.max(jnp.where(sel, thi_ref[r, :], jnp.int32(-(2**31))))
                hit = (blo == klo) & (bhi == khi)
                empty = (blo == -1) & (bhi == -1)
                return (
                    j + 1,
                    jnp.where(hit, idx, target),
                    jnp.where(hit | empty, jnp.int32(1), done),
                )

            _, target, _ = lax.while_loop(
                lambda cy: (cy[0] < MAX_PROBES) & (cy[2] == 0) & (invalid == 0),
                probe,
                (jnp.int32(0), jnp.int32(-1), jnp.int32(0)),
            )

            @functools.partial(_when, target >= 0)
            def _():
                # TOMBSTONE = -2 → planes (-2, -1)
                r = target >> 7
                sel = lane_iota == (target & (LANES - 1))
                tlo_ref[r, :] = jnp.where(sel, jnp.int32(-2), tlo_ref[r, :])
                thi_ref[r, :] = jnp.where(sel, jnp.int32(-1), thi_ref[r, :])

            return jnp.int32(0)

        lax.fori_loop(jnp.int32(0), jnp.int32(c), body, jnp.int32(0))

    shape2d = jax.ShapeDtypeStruct((t // LANES, LANES), jnp.int32)
    tlo2, thi2 = _pallas_call(
        kernel,
        grid=(b // c,),
        in_specs=[_smem_spec(c)] * 4
        + [_vmem_full_spec((t // LANES, LANES))] * 2,
        out_specs=(
            _vmem_full_spec((t // LANES, LANES)),
            _vmem_full_spec((t // LANES, LANES)),
        ),
        out_shape=(shape2d, shape2d),
        aliases={4: 0, 5: 1},
    )(h0, lo, hi, valid.astype(jnp.int32), tlo, thi)
    return HashTable(tlo2.reshape(t), thi2.reshape(t), table.vals)


# ----------------------------------------------------------------------
# startup self-check
# ----------------------------------------------------------------------
_SELFCHECK_PASSED = False


def selfcheck() -> None:
    """On-chip pallas-vs-XLA parity smoke, run ONCE before a TPU-backed
    broker serves traffic (round-3 advisor: the full parity gate in
    ``benchmarks/pallas_ops_check.py`` had never completed on hardware,
    yet ``_use_pallas()`` enabled these kernels unconditionally for
    production serving). Small shapes keep the extra boot cost to a few
    compiles; raises ``RuntimeError`` on any divergence so a broken
    Mosaic lowering refuses to serve instead of corrupting state.

    No-op off-TPU (the CPU suite pins semantics through the XLA
    fallbacks, which are the same code path).
    """
    global _SELFCHECK_PASSED
    if _SELFCHECK_PASSED or not _use_pallas():
        return

    import numpy as np

    rng = np.random.default_rng(11)
    t, b, k = 1 << 10, 1 << 8, 16

    def _fail(name, a, b_):
        raise RuntimeError(
            f"pallas selfcheck MISMATCH [{name}]: refusing to serve "
            f"({np.asarray(a).ravel()[:4]} vs {np.asarray(b_).ravel()[:4]})"
        )

    def _eq(name, a, b_):
        if not (np.asarray(a) == np.asarray(b_)).all():
            _fail(name, a, b_)

    table = hashmap.make(t)
    keys = jnp.asarray(
        rng.choice(np.arange(1, 8 * t, 3, dtype=np.int64), b, replace=False)
    )
    vals = jnp.arange(b, dtype=jnp.int32)
    valid = jnp.asarray(rng.random(b) < 0.8)
    t_x, ok_x = hashmap.insert(table, keys, vals, valid)
    t_p, ok_p = insert(table, keys, vals, valid)
    def _keyset(tb):
        return np.sort(hashmap.host_keys(tb))

    _eq("insert keyset", _keyset(t_x), _keyset(t_p))
    _eq("insert ok", ok_x, ok_p)
    fx, sx = hashmap.lookup(t_p, keys, valid)
    fp, sp = lookup(t_p, keys, valid)
    _eq("lookup found", fx, fp)
    _eq("lookup slots", np.where(np.asarray(fx), np.asarray(sx), -1),
        np.where(np.asarray(fp), np.asarray(sp), -1))
    d_x = hashmap.delete(t_x, keys, valid)
    d_p = delete(t_p, keys, valid)
    _eq("delete keyset", _keyset(d_x), _keyset(d_p))

    tbl = jnp.asarray(rng.integers(0, 100, (t, k)), jnp.int32)
    slots = jnp.asarray(rng.choice(t, b, replace=False), jnp.int32)
    active = jnp.asarray(rng.random(b) < 0.7)
    rows = jnp.asarray(rng.integers(0, 1000, (b, k)), jnp.int32)
    x = tbl.at[jnp.where(active, slots, t)].set(rows, mode="drop")
    p = masked_row_update(tbl, slots, active, rows)
    _eq("row update", x, p)

    t1 = jnp.asarray(rng.integers(0, 100, (t,)), jnp.int32)
    lvals = jnp.asarray(rng.integers(0, 9, (b,)), jnp.int32)
    _eq("lane update",
        t1.at[jnp.where(active, slots, t)].set(lvals, mode="drop"),
        masked_lane_update(t1, slots, active, lvals))
    _eq("lane accum",
        t1.at[jnp.where(active, slots, t)].add(lvals, mode="drop"),
        masked_lane_accum(t1, slots, active, lvals))

    _SELFCHECK_PASSED = True
