"""Device-resident record queue + drive loop.

The broker's hot loop (``StreamProcessorController.java:296-399``) reads
committed records and feeds follow-ups back into the log. On device, that
feedback must not cross the host boundary: emissions are appended to an
HBM FIFO (the dispatcher/"write buffer" analogue,
``dispatcher/.../Dispatcher.java:222``) and dequeued as the next fixed-size
input batch. One host sync per wave (the totals dict) drives the loop;
everything else stays on device.

Queue design (TPU-specific): XLA lowers general scatters/gathers to
SERIAL per-index loops on TPU (~10ns/row), so a classic ring buffer —
one scatter per record field per enqueue — dominated the whole round
(~50 serial ops x 32k rows). This queue instead keeps the FIFO front at
index 0:

- dequeue  = static slice ``rows[:B]`` + one contiguous shift-down copy
  per field (vectorized copies, no per-index work),
- enqueue  = one ``dynamic_update_slice`` per field at the tail
  (requires the incoming batch to be PREFIX-COMPACTED: valid rows form a
  contiguous prefix, which the kernel's emission compaction guarantees).

Rows at index >= count are always invalid (valid=False padding), so block
writes past the tail never clobber live records. FIFO order — the replay
determinism contract — is bit-identical to the ring design.

The bench and the batched broker path both run on this driver; the
durability path drains the same emissions to the host log asynchronously.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from zeebe_tpu.tpu import batch as rb
from zeebe_tpu.tpu import jit_registry
from zeebe_tpu.tpu.batch import RecordBatch
from zeebe_tpu.tpu.graph import DeviceGraph
from zeebe_tpu.tpu.kernel import stats_of, step_kernel
from zeebe_tpu.tpu.state import EngineState


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["rows", "count", "overflow"],
    meta_fields=[],
)
@dataclasses.dataclass
class RecordQueue:
    rows: RecordBatch   # [Q] storage; live rows are exactly [0, count)
    count: jax.Array    # i32 scalar
    overflow: jax.Array  # bool scalar, sticky: an enqueue didn't fit

    @property
    def capacity(self) -> int:
        return self.rows.size


def make_queue(capacity: int, num_vars: int) -> RecordQueue:
    """``capacity`` must budget for block writes: an enqueue needs the whole
    (padded) incoming block to fit, so the usable record count is
    ``capacity - largest_enqueued_block`` (the kernel's emission block is
    ``batch_size * graph.emit_width`` rows). Size generously — storage is
    cheap, the shift copy is bandwidth-bound, and overflow is a hard abort."""
    return RecordQueue(
        rows=rb.empty(capacity, num_vars),
        count=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), bool),
    )


def enqueue(queue: RecordQueue, batch: RecordBatch) -> RecordQueue:
    """Append a prefix-compacted batch in row order (FIFO).

    ``batch`` must have its valid rows as a contiguous prefix (the kernel's
    output compaction and host staging both guarantee this); the whole
    block lands at the tail with one dynamic_update_slice per field — the
    invalid padding rows fall beyond the new count where they are inert.
    Sets the sticky overflow flag (and leaves the queue corrupt) if the
    block doesn't fit; callers abort the drive loop on overflow.
    """
    qcap = queue.capacity
    ob = batch.size
    add = jnp.sum(batch.valid, dtype=jnp.int32)
    tail = queue.count
    # dynamic_update_slice clamps the start index; past qcap-ob the block
    # would land over live rows, so that is the (sticky) overflow line
    overflow = queue.overflow | (tail > qcap - ob)
    start = jnp.minimum(tail, qcap - ob)
    rows = jax.tree.map(
        lambda store, b: lax.dynamic_update_slice_in_dim(store, b, start, axis=0),
        queue.rows,
        batch,
    )
    return RecordQueue(rows=rows, count=tail + add, overflow=overflow)


def dequeue(queue: RecordQueue, batch_size: int) -> Tuple[RecordQueue, RecordBatch]:
    """Take the first ``batch_size`` rows (static slice) and shift the
    remainder down (contiguous per-field copies). Valid flags in storage
    already mask the sub-batch tail when fewer than ``batch_size`` rows
    are pending."""
    take = jnp.minimum(queue.count, batch_size)
    batch = jax.tree.map(lambda a: a[:batch_size], queue.rows)
    blanks = rb.empty(batch_size, queue.rows.num_vars)
    rows = jax.tree.map(
        lambda a, z: jnp.concatenate([a[batch_size:], z], axis=0),
        queue.rows,
        blanks,
    )
    return (
        RecordQueue(rows=rows, count=queue.count - take, overflow=queue.overflow),
        batch,
    )


def drive_round(
    graph: DeviceGraph,
    state: EngineState,
    queue: RecordQueue,
    now,
    batch_size: int,
    synthetic_workers: bool = False,
):
    """Dequeue one batch, step the kernel, enqueue the emissions.

    Returns (state, queue, stats). jit-compiled per (batch_size, shapes).
    ``synthetic_workers`` makes the kernel emit an instant COMPLETE after
    every ACTIVATED push (bench-only; see kernel.step_kernel).
    """
    queue, batch = dequeue(queue, batch_size)
    state, out, stats = step_kernel(
        graph, state, batch, now, synthetic_workers=synthetic_workers
    )
    queue = enqueue(queue, rb.column_views(out))
    stats = stats_of(stats)
    stats["overflow"] = (stats["overflow"] != 0) | queue.overflow
    return state, queue, stats


drive_jit = jit_registry.register_jit(
    "drive.round",
    drive_round,
    state_args=(1,),
    static_argnames=("batch_size", "synthetic_workers"),
    donate_argnums=(1, 2),
    max_signatures=4,
    notes="one signature per (batch_size, synthetic_workers) a process "
    "drives; batch_size is fixed per bench/serving config",
)


def _quiesce_device_fn(graph, state, queue, now, batch_size, synthetic_workers, max_rounds):
    """The whole drive-to-quiescence loop as ONE device program
    (``lax.while_loop``): no host round-trips between rounds — each
    per-round scalar sync would stall the host on the device, and the
    per-dispatch latency is paid once, not once per round (neither cost
    is measured on the locally attached v5e yet)."""
    totals0 = {
        "processed": jnp.zeros((), jnp.int64),
        "emitted": jnp.zeros((), jnp.int64),
        "completed_roots": jnp.zeros((), jnp.int64),
        "rounds": jnp.zeros((), jnp.int32),
        "overflow": jnp.zeros((), bool),
    }

    def cond(carry):
        _, q, t = carry
        return (q.count > 0) & (t["rounds"] < max_rounds) & (~t["overflow"])

    def body(carry):
        s, q, t = carry
        q, batch = dequeue(q, batch_size)
        s, out, stats = step_kernel(
            graph, s, batch, now, synthetic_workers=synthetic_workers
        )
        q = enqueue(q, rb.column_views(out))
        stats = stats_of(stats)
        t = {
            "processed": t["processed"] + stats["processed"].astype(jnp.int64),
            "emitted": t["emitted"] + stats["emitted"].astype(jnp.int64),
            "completed_roots": t["completed_roots"]
            + stats["completed_roots"].astype(jnp.int64),
            "rounds": t["rounds"] + 1,
            "overflow": t["overflow"]
            | stats["overflow"].astype(bool)
            | q.overflow,
        }
        return s, q, t

    return jax.lax.while_loop(cond, body, (state, queue, totals0))


_quiesce_device = jit_registry.register_jit(
    "drive.quiesce",
    _quiesce_device_fn,
    state_args=(1,),
    static_argnames=("batch_size", "synthetic_workers", "max_rounds"),
    donate_argnums=(1, 2),
    max_signatures=4,
    notes="one signature per (batch_size, synthetic_workers, max_rounds) "
    "combination a process drives",
)


# NOTE: an earlier revision compiled this program with
# ``xla_tpu_scoped_vmem_limit_kib=65536`` to get XLA's reduce-window cumsum
# lowering past a scoped-vmem allocation failure. The MXU-matmul prefix sums
# (kernel._mxu_cumsum_i32) removed those programs, and plain compilation is
# both sufficient and faster.
_quiesce_cache: dict = {}


def _quiesce_executable(graph, state, queue, now, batch_size, synthetic_workers, max_rounds):
    shapes = tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        for leaf in jax.tree.leaves((graph, state, queue, now))
    )
    # the treedef must be part of the key: graphs with optional tables
    # absent (None) can have the same leaf list as graphs with a different
    # structure, and an AOT executable rejects a mismatched pytree
    treedef = jax.tree.structure((graph, state, queue, now))
    key = (treedef, shapes, batch_size, synthetic_workers, max_rounds)
    compiled = _quiesce_cache.get(key)
    if compiled is None:
        lowered = _quiesce_device.lower(
            graph, state, queue, now, batch_size, synthetic_workers, max_rounds
        )
        compiled = lowered.compile()
        _quiesce_cache[key] = compiled
    return compiled


def run_to_quiescence(
    graph: DeviceGraph,
    state: EngineState,
    queue: RecordQueue,
    now,
    batch_size: int,
    synthetic_workers: bool = False,
    max_rounds: int = 10_000,
    sync: bool = True,
):
    """Drive rounds until the queue drains — one device dispatch, one host
    sync for the totals. Returns (state, queue, totals dict).

    ``sync=False`` returns the totals as device scalars without any host
    round trip (callers accumulating across many waves fetch once at the
    end; overflow/quiescence checking is then the caller's job)."""
    now = jnp.asarray(now, jnp.int64)
    if jax.default_backend() == "tpu":
        compiled = _quiesce_executable(
            graph, state, queue, now, batch_size, synthetic_workers, max_rounds
        )
        state, queue, dev_totals = compiled(graph, state, queue, now)
    else:
        state, queue, dev_totals = _quiesce_device(
            graph, state, queue, now, batch_size, synthetic_workers, max_rounds
        )
    if not sync:
        return state, queue, dev_totals
    # ONE host transfer for all scalars — per-scalar syncs each cost a full
    # round trip to the device
    host_totals = jax.device_get(dev_totals)
    if bool(host_totals.pop("overflow")):
        raise RuntimeError("device table or queue overflow during drive loop")
    totals = {k: int(v) for k, v in host_totals.items()}
    if totals["rounds"] >= max_rounds and int(queue.count) > 0:
        raise RuntimeError("drive loop did not quiesce")
    return state, queue, totals
