"""Multi-partition sharding over a device mesh.

The reference scales by splitting topics into partitions, each an
independent ordered log + state machine, with hash-routed cross-partition
messaging over the subscription transport
(``docs/src/basics/clustering.md``, ``SubscriptionCommandSender.java:96-108``).
Here partitions ARE mesh shards: each device owns one partition's engine
state and record queue; the step kernel runs under ``shard_map`` with

- partition-disjoint keyspaces (partition id in the key's high bits, the
  Protocol.java partition-key encoding),
- an ``all_to_all`` exchange slot for hash-routed cross-partition commands
  (message correlation — the subscription-transport data plane moved onto
  ICI),
- ``psum`` for global control-plane aggregates (processed counts,
  quiescence detection).
"""

from __future__ import annotations

import dataclasses
import re
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from zeebe_tpu.engine import keyspace
from zeebe_tpu.protocol.enums import RecordType, ValueType
from zeebe_tpu.tpu import batch as rb
from zeebe_tpu.tpu import jit_registry
from zeebe_tpu.tpu import state as state_mod
from zeebe_tpu.tpu.batch import RecordBatch
from zeebe_tpu.tpu.graph import DeviceGraph
from zeebe_tpu.tpu.kernel import stats_of, step_kernel
from zeebe_tpu.tpu.state import EngineState, corr_composite

# partition id lives in the key's high bits (reference Protocol.java keeps
# partition-local key spaces; 13 bits of partition, 51 bits of counter)
PARTITION_KEY_SHIFT = 51


def correlation_route(out: RecordBatch, nparts: int, my_pid):
    """Destination partition per emission row.

    Message-subscription commands (OPEN/CLOSE) hash their correlation
    composite — the device mesh's analogue of the oracle's
    ``partition_for_correlation_key`` (``SubscriptionCommandSender.java:
    96-108``; the hash FUNCTION differs from the host's string hash, which
    only matters when comparing partition assignments across engine kinds
    — the mesh is self-consistent). CORRELATE commands carry their
    destination (the subscribing instance's partition) in the ``wf``
    column. Everything else stays local."""
    rt_cmd = out.rtype == int(RecordType.COMMAND)
    is_msub = out.valid & rt_cmd & (
        out.vtype == int(ValueType.MESSAGE_SUBSCRIPTION)
    )
    is_corr = out.valid & rt_cmd & (
        out.vtype == int(ValueType.WORKFLOW_INSTANCE_SUBSCRIPTION)
    )
    ckey = corr_composite(out.type_id, out.retries, out.worker)
    # Fibonacci multiplicative hash on the composite (wraps mod 2^64)
    h = ((ckey * jnp.int64(-7046029254386353131)) >> 33) & jnp.int64(
        0x7FFFFFFF
    )
    hash_target = (h % nparts).astype(jnp.int32)
    return jnp.where(
        is_msub, hash_target,
        jnp.where(is_corr, jnp.clip(out.wf, 0, nparts - 1), my_pid),
    )


def _first_true_indices_local(mask, k):
    """Indices of the first ``k`` True entries (kernel._first_true_indices
    without the MXU scan — exchange blocks are small and this runs inside
    shard_map where odd lengths are common)."""
    n = mask.shape[0]
    rank = jnp.cumsum(mask.astype(jnp.int32)) - mask.astype(jnp.int32)
    tgt = jnp.where(mask & (rank < k), rank, k)
    return (
        jnp.full((k,), n, jnp.int32)
        .at[tgt]
        .set(jnp.arange(n, dtype=jnp.int32), mode="drop")
    )


def make_partitioned_state(
    num_partitions: int, capacity: int, num_vars: int, **kw
) -> EngineState:
    """Stacked per-partition state: every leaf gains a leading partition
    axis; key counters start at partition-disjoint bases."""
    shards = []
    for pid in range(num_partitions):
        st = state_mod.make_state(capacity=capacity, num_vars=num_vars, **kw)
        base = jnp.int64(pid) << PARTITION_KEY_SHIFT
        st = dataclasses.replace(
            st,
            next_wf_key=base + keyspace.WF_OFFSET,
            next_job_key=base + keyspace.JOB_OFFSET,
        )
        shards.append(st)
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *shards)


def make_partitioned_batch(num_partitions: int, size: int, num_vars: int) -> RecordBatch:
    shards = [rb.empty(size, num_vars) for _ in range(num_partitions)]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *shards)


def _squeeze(tree):
    return jax.tree.map(lambda a: jnp.squeeze(a, axis=0), tree)


def _unsqueeze(tree):
    return jax.tree.map(lambda a: a[None], tree)


def build_sharded_step(mesh: Mesh, exchange_slots: int = 128):
    """A jit-compiled multi-partition step:

      (graph, state[P,...], batch[P,B,...], sends[P,P,S,...], now)
        → (state', emissions[P,...], sends_in[P,...], global_processed)

    ``sends`` carries hash-routed cross-partition command rows (row p,q =
    rows partition p addresses to partition q); the all_to_all delivers
    ``sends_in`` (rows arriving at each partition), which the caller
    enqueues into the destination partition's queue next round (after
    prefix-compaction: drive.enqueue requires valid rows contiguous at the
    front, and all_to_all output interleaves them by source shard) — exactly
    the reference's subscription-transport hop, but over ICI.
    """
    axis = mesh.axis_names[0]
    nparts = mesh.devices.shape[0]

    def shard_fn(graph, state, batch, sends, now):
        state = _squeeze(state)
        batch = _squeeze(batch)
        sends = _squeeze(sends)  # [P, S, ...] rows addressed per destination
        state, out, stats = step_kernel(graph, state, batch, now)
        out = rb.column_views(out)
        # subscription-transport hop: deliver each partition its inbound rows
        sends_in = jax.tree.map(
            lambda a: jax.lax.all_to_all(a, axis, 0, 0), sends
        )
        total = jax.lax.psum(stats_of(stats)["processed"], axis)
        pending = jax.lax.psum(
            jnp.sum(out.valid, dtype=jnp.int32)
            + jnp.sum(sends_in.valid, dtype=jnp.int32),
            axis,
        )
        return (
            _unsqueeze(state),
            _unsqueeze(out),
            _unsqueeze(sends_in),
            total[None],
            pending[None],
        )

    spec_sharded = P(axis)
    spec_repl = P()

    def specs(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def sharded_step(graph, state, batch, sends, now):
        fn = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(
                specs(graph, spec_repl),
                specs(state, spec_sharded),
                specs(batch, spec_sharded),
                specs(sends, spec_sharded),
                spec_repl,
            ),
            out_specs=(
                specs(state, spec_sharded),
                specs(batch, spec_sharded),
                specs(sends, spec_sharded),
                spec_sharded,
                spec_sharded,
            ),
            check_vma=False,
        )
        return fn(graph, state, batch, sends, now)

    return (
        jit_registry.register_jit(
            "shard.sharded_step",
            sharded_step,
            state_args=(1,),
            collective=True,
            max_signatures=2,
            suppress=("boundary-donation",),
            notes="state donation deferred: mesh A/B harnesses reuse the "
            "pre-step state for parity runs (ROADMAP item 3 picks this up "
            "when tables carry sharding specs natively)",
        ),
        nparts,
    )


def build_frame_exchange(mesh: Mesh, slots: int, frame_bytes: int):
    """The subscription-transport hop for the SERVING plane, as a mesh
    collective: encoded record frames ride the same per-destination
    ``all_to_all`` exchange-slot pattern ``build_sharded_step`` uses for
    staged record rows — but as raw wire bytes, so the destination decodes
    EXACTLY what the host transport would have carried (bit-identical
    appends by construction; see scheduler/placement.MeshExchange).

    Returns ``exchange(buf[D,D,S,B] u8, lens[D,D,S] i32, pids[D,D,S] i32)
    → (buf', lens', pids')`` where row ``d`` of each output carries the
    frames addressed TO device ``d``, indexed [source device, slot].
    """
    axis = mesh.axis_names[0]

    def shard_fn(buf, lens, pids):
        buf = jnp.squeeze(buf, axis=0)    # [D, S, B] rows per destination
        lens = jnp.squeeze(lens, axis=0)  # [D, S]
        pids = jnp.squeeze(pids, axis=0)
        out_buf = jax.lax.all_to_all(buf, axis, 0, 0)
        out_lens = jax.lax.all_to_all(lens, axis, 0, 0)
        out_pids = jax.lax.all_to_all(pids, axis, 0, 0)
        return out_buf[None], out_lens[None], out_pids[None]

    spec = P(axis)
    fn = jit_registry.register_jit(
        "shard.frame_exchange",
        jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=(spec, spec, spec),
            check_vma=False,
        ),
        collective=True,
        max_signatures=2,
        notes="pure permutation of wire frames; carries no engine state",
    )
    n = mesh.devices.shape[0]

    def exchange(buf, lens, pids):
        # the builder's geometry IS the contract: a mismatched caller
        # would otherwise shard garbage silently
        if buf.shape != (n, n, slots, frame_bytes):
            raise ValueError(
                f"frame exchange built for buf shape "
                f"{(n, n, slots, frame_bytes)}, got {buf.shape}"
            )
        if lens.shape != (n, n, slots) or pids.shape != (n, n, slots):
            raise ValueError(
                f"frame exchange built for lane shape {(n, n, slots)}, "
                f"got {lens.shape} / {pids.shape}"
            )
        return fn(buf, lens, pids)

    return exchange


def make_exchange(num_partitions: int, slots: int, num_vars: int) -> RecordBatch:
    """The cross-partition send buffer: [P, P, S] record rows (source,
    destination, slot)."""
    shards = [
        jax.tree.map(
            lambda a: jnp.stack([a] * num_partitions, axis=0),
            rb.empty(slots, num_vars),
        )
        for _ in range(num_partitions)
    ]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *shards)


def build_sharded_drive(
    mesh: Mesh, batch_size: int, synthetic_workers: bool = False,
    max_rounds: int = 10_000, exchange_slots: int = 0,
):
    """The multi-partition drive-to-quiescence loop as ONE device program:
    per-partition record queues feed the step kernel under ``shard_map``,
    with a ``psum`` of pending counts deciding GLOBAL quiescence (all
    shards iterate in lockstep; a partition with an empty queue simply
    processes empty batches until every partition drains — the sharded
    analogue of ``drive.run_to_quiescence``).

    Cross-partition message correlation rides the ICI every round: emission
    rows whose route (``correlation_route``) is another partition are
    bucketed into per-destination blocks of ``exchange_slots`` rows and
    delivered by ``all_to_all`` — the reference's subscription transport
    (``SubscriptionCommandSender``) as a mesh collective. Arrivals enqueue
    after local emissions; a block overflow aborts the drive loudly.

    Queue sizing: ``drive.enqueue`` needs the whole PADDED incoming block
    to fit, so with messages each per-partition queue must hold at least
    ``batch_size * graph.emit_width + nparts * exchange_slots`` rows of
    headroom above its backlog.

    Staging contract: the mesh never materializes rows to host records, so
    correlation VALUE-TYPE TAGS must agree between what the subscribe step
    extracts from instance payloads and what staged publishes carry — a
    publish staged with a VT_STR intern of "42" will NOT match a
    subscription whose payload variable was numeric 42 (the serving path
    normalizes through record materialization; the mesh path by staging
    discipline).

    Returns ``drive(graph, state[P], queue[P], now) →
    (state', queue', totals[P])`` where totals carries per-shard processed/
    emitted/completed counts plus the shared overflow flag.
    """
    from zeebe_tpu.tpu import drive as drive_mod

    axis = mesh.axis_names[0]
    nparts = mesh.devices.shape[0]
    exchange_slots = exchange_slots or batch_size

    def shard_fn(graph, state, queue, now):
        state = _squeeze(state)
        queue = _squeeze(queue)
        my_pid = jax.lax.axis_index(axis).astype(jnp.int32)

        totals0 = {
            "processed": jnp.zeros((), jnp.int64),
            "emitted": jnp.zeros((), jnp.int64),
            "completed_roots": jnp.zeros((), jnp.int64),
            "rounds": jnp.zeros((), jnp.int32),
            "overflow": jnp.zeros((), bool),
        }
        pending0 = jax.lax.psum(queue.count, axis)

        def cond(carry):
            _s, _q, t, pending = carry
            return (
                (pending > 0)
                & (t["rounds"] < max_rounds)
                & (~t["overflow"])
            )

        def body(carry):
            s, q, t, _pending = carry
            q, batch = drive_mod.dequeue(q, batch_size)
            s, out, stats = step_kernel(
                graph, s, batch, now, synthetic_workers=synthetic_workers,
                partition_id=my_pid,
            )
            out = rb.column_views(out)
            stats = stats_of(stats)
            xover = jnp.zeros((), bool)
            if graph.has_messages and nparts > 1:
                target = correlation_route(out, nparts, my_pid)
                stay = out.valid & (target == my_pid)
                # per-destination blocks (own-destination block is empty by
                # construction: target == my_pid rows are 'stay')
                be = out.size
                blocks = []
                for p in range(nparts):
                    m = out.valid & (target == p) & (target != my_pid)
                    xover = xover | (
                        jnp.sum(m, dtype=jnp.int32) > exchange_slots
                    )
                    idx = jnp.clip(
                        _first_true_indices_local(m, exchange_slots),
                        0, be - 1,
                    )
                    n_p = jnp.sum(m, dtype=jnp.int32)
                    # two packed row gathers instead of a per-field tree.map
                    # (batch.take_rows, PERF_NOTES round-4 cost model)
                    block = rb.take_rows(out, idx)
                    block = dataclasses.replace(
                        block,
                        valid=jnp.arange(exchange_slots, dtype=jnp.int32)
                        < n_p,
                        # arrivals are fresh log entries at the destination
                        src=jnp.full((exchange_slots,), -1, jnp.int32),
                    )
                    blocks.append(block)
                sends = jax.tree.map(
                    lambda *xs: jnp.stack(xs, axis=0), *blocks
                )  # [P, S, ...]
                arrivals = jax.tree.map(
                    lambda a: jax.lax.all_to_all(a, axis, 0, 0), sends
                )
                flat = jax.tree.map(
                    lambda a: a.reshape((nparts * exchange_slots,)
                                        + a.shape[2:]),
                    arrivals,
                )
                # local rows keep their emission order; exchanged arrivals
                # append after (both prefix-compacted for enqueue)
                local = rb.compact(dataclasses.replace(out, valid=stay))
                q = drive_mod.enqueue(q, local)
                q = drive_mod.enqueue(q, rb.compact(flat))
            else:
                q = drive_mod.enqueue(q, out)
            t = {
                "processed": t["processed"] + stats["processed"].astype(jnp.int64),
                "emitted": t["emitted"] + stats["emitted"].astype(jnp.int64),
                "completed_roots": t["completed_roots"]
                + stats["completed_roots"].astype(jnp.int64),
                "rounds": t["rounds"] + 1,
                # overflow anywhere aborts everywhere (lockstep)
                "overflow": t["overflow"]
                | (jax.lax.psum(
                    ((stats["overflow"] != 0) | q.overflow | xover)
                    .astype(jnp.int32),
                    axis,
                ) > 0),
            }
            pending = jax.lax.psum(q.count, axis)
            return s, q, t, pending

        state, queue, totals, _ = jax.lax.while_loop(
            cond, body, (state, queue, totals0, pending0)
        )
        return _unsqueeze(state), _unsqueeze(queue), _unsqueeze(totals)

    spec_sharded = P(axis)
    spec_repl = P()

    def specs(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def drive(graph, state, queue, now):
        fn = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(
                specs(graph, spec_repl),
                specs(state, spec_sharded),
                specs(queue, spec_sharded),
                spec_repl,
            ),
            out_specs=(
                specs(state, spec_sharded),
                specs(queue, spec_sharded),
                {k: spec_sharded for k in (
                    "processed", "emitted", "completed_roots", "rounds",
                    "overflow",
                )},
            ),
            check_vma=False,
        )
        return fn(graph, state, queue, now)

    return jit_registry.register_jit(
        "shard.sharded_drive",
        drive,
        state_args=(1,),
        collective=True,
        max_signatures=2,
        suppress=("boundary-donation",),
        notes="state donation deferred with shard.sharded_step (parity "
        "A/B harnesses reuse the pre-drive state)",
    )


def make_partitioned_queue(num_partitions: int, capacity: int, num_vars: int):
    from zeebe_tpu.tpu import drive as drive_mod

    shards = [drive_mod.make_queue(capacity, num_vars) for _ in range(num_partitions)]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *shards)


# ---------------------------------------------------------------------------
# mesh-sharded SINGLE-partition state (ROADMAP item 2)
# ---------------------------------------------------------------------------
# Everything above shards ACROSS partitions (each device owns one whole
# partition). This section shards ONE partition's state tables over the
# mesh axis, so a single hot tenant's resident rows scale with the mesh
# instead of being capped at one chip's HBM: the row tables carry
# ``match_partition_rules``-style sharding specs (the pjit shard/gather
# pattern), live sharded at rest between waves, and are gathered over ICI
# for each step — the cross-shard reads (message correlation, scope-parent
# resolution, key sync) are ONE budgeted ``all_gather`` per table family
# per wave, modeled by zbaudit's collective-volume pass. The write side is
# collective-free: every device computes the identical full-table update
# (the batch is replicated), then keeps only its own row block. Running
# the UNMODIFIED step kernel on the gathered view is what makes the
# sharded engine replay bit-identical to the single-device one by
# construction.

# default mesh axis name for sharded-state programs
STATE_AXIS = "shards"

# (regex over the state leaf's dotted key-path, shard?) — first match
# wins, like SNIPPETS' match_partition_rules over a parameter pytree.
# Row tables (leading dim = a table capacity) shard on dim 0; host-managed
# worker-subscription tables, ring cursors, and key counters replicate
# (tiny, scalar, or mutated host-side between waves).
STATE_PARTITION_RULES = (
    (r"ei_(i32|i64|pay|index)$", True),
    (r"ei_map\.", True),
    (r"free_ei$", True),
    (r"job_(i32|i64|pay|index)$", True),
    (r"job_map\.", True),
    (r"free_job$", True),
    (r"join_(key|nin|arrived|pay|pos_stamp)$", True),
    (r"join_map\.", True),
    (r"timer_(key|due|aik|instance_key|elem|wf)$", True),
    (r"timer_map\.", True),
    (r"msub_(ckey|i32|i64)$", True),
    (r"msub_map\.", True),
    (r"msg_(key|ckey|i32|deadline|pay)$", True),
    (r"msg_map\.", True),
    (r".*", False),
)


def _path_str(path) -> str:
    parts = []
    for k in path:
        name = getattr(k, "name", None)
        if name is None:
            name = getattr(k, "key", None)
        if name is None:
            name = getattr(k, "idx", None)
        parts.append(str(name))
    return ".".join(parts)


def match_partition_rules(
    rules, tree, num_shards: int, axis: str = STATE_AXIS
):
    """PartitionSpec pytree for ``tree``: each leaf's dotted key-path is
    matched against ``rules`` (first match wins); a shard rule puts
    ``P(axis)`` on dim 0 when the leaf has rows divisible by
    ``num_shards``, else the leaf stays replicated (``P()``) — a
    non-divisible table silently falling back is safe (correctness never
    depends on WHICH leaves shard), and the HBM model reads the spec tree
    rather than assuming."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    spec_leaves = []
    for path, leaf in leaves:
        name = _path_str(path)
        spec = P()
        for pat, want in rules:
            if re.search(pat, name):
                shape = getattr(leaf, "shape", ())
                if (
                    want
                    and len(shape) >= 1
                    and shape[0] > 0
                    and shape[0] % num_shards == 0
                ):
                    spec = P(axis)
                break
        spec_leaves.append(spec)
    return jax.tree_util.tree_unflatten(treedef, spec_leaves)


def state_partition_specs(
    state: EngineState, num_shards: int, axis: str = STATE_AXIS
):
    """The sharded-state spec tree for an :class:`EngineState`."""
    return match_partition_rules(STATE_PARTITION_RULES, state, num_shards, axis)


def state_shardings(mesh: Mesh, state: EngineState):
    """NamedSharding pytree for committing a state to a sharded mesh
    (``jax.device_put(state, state_shardings(mesh, state))``)."""
    from jax.sharding import NamedSharding

    specs = state_partition_specs(
        state, int(mesh.devices.size), mesh.axis_names[0]
    )
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_of_key(key, num_shards: int):
    """Owning shard of an entity key — the same Fibonacci multiplicative
    hash ``correlation_route`` uses for message routing, so one routing
    function covers both planes. Deterministic in the key alone; the wave
    stager (engine ``_pack_batch``) and the routing tests both call this."""
    k = jnp.asarray(key, jnp.int64)
    h = ((k * jnp.int64(-7046029254386353131)) >> 33) & jnp.int64(0x7FFFFFFF)
    return (h % num_shards).astype(jnp.int32)


def shard_row_counts(keys, valid, num_shards: int):
    """Rows per owning shard for one staged wave ([num_shards] i32) — the
    ``mesh_shard_rows{device}`` gauge feed."""
    tgt = jnp.where(
        jnp.asarray(valid, bool), shard_of_key(keys, num_shards), num_shards
    )
    return (
        jnp.zeros((num_shards,), jnp.int32)
        .at[tgt]
        .add(1, mode="drop")
    )


def shard_of_key_host(keys, num_shards: int) -> np.ndarray:
    """numpy twin of :func:`shard_of_key` for host-side wave staging —
    the engine accounts routing per wave without a device round-trip.
    Tests pin the two implementations equal (routing determinism)."""
    k = np.asarray(keys, np.int64)
    with np.errstate(over="ignore"):
        h = (
            (k * np.int64(-7046029254386353131)) >> np.int64(33)
        ) & np.int64(0x7FFFFFFF)
    return (h % num_shards).astype(np.int32)


def shard_row_counts_host(keys, valid, num_shards: int) -> np.ndarray:
    """Host twin of :func:`shard_row_counts` ([num_shards] counts)."""
    tgt = shard_of_key_host(keys, num_shards)
    v = np.asarray(valid, bool)
    return np.bincount(tgt[v], minlength=num_shards).astype(np.int64)


def state_exchange_bytes(
    state: EngineState,
    num_shards: int,
    axis: str = STATE_AXIS,
    include_lookup: bool = True,
) -> int:
    """Aggregate cross-shard bytes ONE wave's table gathers move: each of
    the D devices receives the (D-1)/D fraction of every sharded table it
    does not hold, so the interconnect carries ``sharded_bytes * (D-1)``
    per wave. Pure shape arithmetic (no tracing) — the engine stamps it
    on the ``mesh_shard_exchange_bytes_total`` counter per wave, and the
    zbaudit collective pass independently measures the same gathers at
    the jaxpr level. ``include_lookup=False`` models resident mode's
    fallback leg, which rebuilds the lookup structures in-program instead
    of gathering them (only the row tables cross the interconnect)."""
    specs = state_partition_specs(state, num_shards, axis)
    leaves, _ = jax.tree_util.tree_flatten_with_path(state)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    total = 0
    for (path, a), s in zip(leaves, spec_leaves):
        if tuple(s) != (axis,):
            continue
        if not include_lookup and is_lookup_leaf(_path_str(path)):
            continue
        total += int(np.dtype(a.dtype).itemsize) * int(np.prod(a.shape))
    return total * (num_shards - 1)


def _zip_specs(fn, tree, specs):
    """Map ``fn(leaf, spec)`` over aligned (tree, spec-tree) leaves."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P)
    )
    return jax.tree_util.tree_unflatten(
        treedef, [fn(a, s) for a, s in zip(leaves, spec_leaves)]
    )


def build_state_step(mesh: Mesh, state_template: EngineState):
    """The sharded-state step program:

      (graph, state, batch, now, partition_id) → (state', out, stats)

    ``state`` row tables arrive sharded per ``state_partition_specs``
    (dim 0 over the mesh axis); the batch, graph, and scalars are
    replicated. Each wave all_gathers the sharded tables (the budgeted
    cross-shard read), runs the UNMODIFIED ``step_kernel`` on the gathered
    view — identical on every device, so emissions and stats are
    replicated and bit-identical to the single-device program — and keeps
    only the local row block of the updated tables (the write side is a
    local slice, no collective). Registered as ``shard.state_step`` so
    zbaudit traces, lowers, and gates it like the other entries.
    """
    axis = mesh.axis_names[0]
    nshards = int(mesh.devices.size)
    specs_tree = state_partition_specs(state_template, nshards, axis)

    def _sharded(spec) -> bool:
        return tuple(spec) == (axis,)

    def shard_fn(graph, state, batch, now, partition_id):
        idx = jax.lax.axis_index(axis)

        def gather(a, s):
            if not _sharded(s):
                return a
            return jax.lax.all_gather(a, axis, axis=0, tiled=True)

        def keep(a, s):
            if not _sharded(s):
                return a
            rows = a.shape[0] // nshards
            return jax.lax.dynamic_slice_in_dim(a, idx * rows, rows, axis=0)

        full = _zip_specs(gather, state, specs_tree)
        new_state, out, stats = step_kernel(
            graph, full, batch, now, partition_id=partition_id
        )
        return _zip_specs(keep, new_state, specs_tree), out, stats

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), specs_tree, P(), P(), P()),
        out_specs=(specs_tree, P(), P()),
        check_vma=False,
    )
    return jit_registry.register_jit(
        "shard.state_step",
        fn,
        state_args=(1,),
        donate_argnums=(1,),
        collective=True,
        max_signatures=2,
        suppress=("boundary-alias",),
        notes="one partition's tables sharded over the mesh axis "
        "(gather-for-compute / keep-local-on-write); aliasing of the "
        "donated sharded blocks is layout-dependent under shard_map, so "
        "the alias materialization check is waived — donation itself "
        "stays asserted",
    )


# ---------------------------------------------------------------------------
# sharded-state v2: residency-routed staging (ROADMAP item 2, second half)
# ---------------------------------------------------------------------------
# ``build_state_step`` above is gather-for-compute: resident HBM divides by
# the span but every wave gathers every sharded table, so neither the
# compute term nor the per-wave collective volume divides. The routed
# programs below make the key-hash routing plane PHYSICAL: the engine
# stages each wave into per-shard batch lanes (``_pack_batch``'s laned
# path), every shard rebuilds its lookup structures from its OWN row block
# in-program (``rebuild_lookup_state`` — pow2 capacities stay pow2 under
# the block split) and steps the unmodified kernel on local rows + its
# routed batch lane. No per-wave table ``all_gather`` exists in the routed
# lowering; the only collectives are ``psum`` reductions of the (single-
# owner, hence exact) emissions, stats, and replicated-leaf deltas — the
# boundary traffic, scaling with the BATCH, not the tables.
#
# Residency contract (enforced by the engine's routing policy, not here):
# a routed wave is SINGLE-OWNER — all rows belong to instances wholly
# resident in one shard's row block — so key allocation from the
# replicated counters happens on exactly one lane (no cross-lane key
# collisions) and parent-slot references never leave the block. Waves the
# policy cannot prove single-owner (unknown residency, lane overflow,
# message-correlation graphs) run ``build_state_step_fallback``: the v1
# gathered shape but with the lookup structures rebuilt GLOBALLY in-program
# from the gathered rows — in resident mode the lookup leaves are per-wave
# derived scratch in BOTH legs, which is what lets the two interleave
# freely on the same sharded tables. Both legs replay bit-identical to the
# single-device engine: emissions depend on keys and batch-row order, never
# on which table slot a row occupies.

# state leaves DERIVED from live rows (direct-mapped indexes, fallback
# hashmaps, free-slot rings + their cursors): in resident mode these are
# per-wave scratch — rebuilt inside the step programs — never gathered,
# never trusted across waves.
LOOKUP_LEAF_PATTERNS = (
    r"ei_map\.", r"job_map\.", r"join_map\.", r"timer_map\.",
    r"msub_map\.", r"msg_map\.",
    r"ei_index$", r"job_index$",
    r"free_(ei|job)$", r"free_(ei|job)_(pop|push)$",
)

_CURSOR_RE = re.compile(r"free_(ei|job)_(pop|push)$")


def is_lookup_leaf(name: str) -> bool:
    """True when a dotted state-leaf path names a row-derived lookup
    structure (rebuilt per wave by the resident-mode step programs)."""
    return any(re.search(p, name) for p in LOOKUP_LEAF_PATTERNS)


def unshardable_state_leaves(state: EngineState, num_shards: int) -> list:
    """Leaf paths the partition rules WANT sharded but whose leading dim
    is not divisible by ``num_shards`` (they silently replicate in v1).
    Resident mode refuses such a configuration outright: a replicated row
    table would put its slots in the global space while sharded tables use
    block-local spaces, and the owner lane's writes to it would diverge
    from the other lanes' no-ops."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(state)
    bad = []
    for path, leaf in leaves:
        name = _path_str(path)
        for pat, want in STATE_PARTITION_RULES:
            if re.search(pat, name):
                if want:
                    shape = getattr(leaf, "shape", ())
                    if not (
                        len(shape) >= 1
                        and shape[0] > 0
                        and shape[0] % num_shards == 0
                    ):
                        bad.append(name)
                break
    return bad


def routed_exchange_bytes(out_tree, num_shards: int) -> int:
    """Cross-shard bytes ONE routed wave moves: the emission batch (and
    stats/replicated-leaf deltas, which it dominates) reduces over the mesh
    axis via ``psum``, so the interconnect carries ``reduced_bytes *
    (D-1)`` — the same receive-volume convention as
    :func:`state_exchange_bytes`, now a function of the BATCH instead of
    the tables. bool/int8 leaves reduce in i32 (4 B/element)."""
    total = 0
    for a in jax.tree_util.tree_leaves(out_tree):
        dt = np.dtype(a.dtype)
        item = 4 if dt in (np.dtype(bool), np.dtype(np.int8)) else dt.itemsize
        total += item * int(np.prod(a.shape))
    return total * (num_shards - 1)


def _psum_masked(leaf, mine, axis):
    """Exact single-owner reduction of a per-lane value: non-owner lanes
    contribute zeros, so the sum IS the owner's value (f32 included — one
    nonzero term). bool/int8 reduce in i32."""
    if leaf.dtype == jnp.bool_:
        z = jnp.where(mine, leaf, False).astype(jnp.int32)
        return jax.lax.psum(z, axis) != 0
    if leaf.dtype == jnp.int8:
        z = jnp.where(mine, leaf, jnp.zeros_like(leaf)).astype(jnp.int32)
        return jax.lax.psum(z, axis).astype(jnp.int8)
    z = jnp.where(mine, leaf, jnp.zeros_like(leaf))
    return jax.lax.psum(z, axis)


def _delta_psum(new, old, mine, axis):
    """Replicated-leaf reconciliation: every lane holds the same ``old``;
    only the owner lane's kernel produced a real ``new`` — apply exactly
    its delta on all lanes (bools via i32 space)."""
    if new.dtype == jnp.bool_:
        o = old.astype(jnp.int32)
        d = jnp.where(mine, new.astype(jnp.int32) - o, 0)
        return (o + jax.lax.psum(d, axis)) != 0
    d = jnp.where(mine, new - old, jnp.zeros_like(new))
    return old + jax.lax.psum(d, axis)


def build_state_step_routed(mesh: Mesh, state_template: EngineState):
    """The residency-routed sharded-state step program:

      (graph, state, lanes, now, partition_id) → (state', out, stats)

    ``state`` arrives sharded per ``state_partition_specs`` exactly like
    ``shard.state_step``; ``lanes`` is a packed wave (or a RecordBatch)
    with a leading ``[num_shards]`` lane dim, sharded over the mesh axis,
    so each device receives ONLY its own routed rows (the host→device put
    of the pair's two matrices covers all lanes). Each shard translates the
    parent-slot column into its local row space, rebuilds the lookup
    structures from its own block, and steps the UNMODIFIED kernel on
    local rows + local lane — no table gather anywhere in the lowering.
    Emissions, stats, and the
    deltas of replicated leaves (key counters, worker-subscription
    tables) reduce with ``psum``; single-owner waves make every reduction
    exact, so outputs are replicated and bit-identical to the
    single-device program. Registered as ``shard.state_step_routed`` with
    its own zbaudit collective budget (boundary traffic only)."""
    axis = mesh.axis_names[0]
    nshards = int(mesh.devices.size)
    specs_tree = state_partition_specs(state_template, nshards, axis)
    spec_leaves = jax.tree_util.tree_leaves(
        specs_tree, is_leaf=lambda x: isinstance(x, P)
    )

    def _sharded(spec) -> bool:
        return tuple(spec) == (axis,)

    def shard_fn(graph, state, lanes, now, partition_id):
        from zeebe_tpu.tpu.kernel import scope_to_global, scope_to_local

        idx = jax.lax.axis_index(axis)
        batch = rb.column_views(_squeeze(lanes))
        mine = jnp.any(batch.valid)
        lrows = state.ei_i32.shape[0]
        prev_scope = state.ei_i32[:, state_mod.EI_SCOPE]
        local = dataclasses.replace(
            state, ei_i32=scope_to_local(state.ei_i32, idx, lrows)
        )
        # lookup structures are per-wave derived scratch: rebuild them
        # from THIS block's rows (local capacities — pow2/D stays pow2)
        local = state_mod.rebuild_lookup_state(local)
        new_state, out, stats = step_kernel(
            graph, local, batch, now, partition_id=partition_id
        )
        new_state = dataclasses.replace(
            new_state,
            ei_i32=scope_to_global(
                new_state.ei_i32, prev_scope, idx, lrows
            ),
        )
        new_leaves, treedef = jax.tree_util.tree_flatten_with_path(new_state)
        old_leaves = jax.tree_util.tree_leaves(state)
        rec = []
        for (path, nl), ol, sp in zip(new_leaves, old_leaves, spec_leaves):
            if _sharded(sp):
                rec.append(nl)  # local block stays local
            elif _CURSOR_RE.search(_path_str(path)):
                # free-ring cursors are lane-local rebuild scratch: pass
                # the replicated input through (next rebuild resets them)
                rec.append(ol)
            else:
                rec.append(_delta_psum(nl, ol, mine, axis))
        new_state = jax.tree_util.tree_unflatten(treedef, rec)
        # the packed pair reduces exactly: every plane is an integer, and
        # only the owner lane's term is not zero
        out = jax.tree.map(lambda a: _psum_masked(a, mine, axis), out)
        return new_state, out, _psum_masked(stats, mine, axis)

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), specs_tree, P(axis), P(), P()),
        out_specs=(specs_tree, P(), P()),
        check_vma=False,
    )
    return jit_registry.register_jit(
        "shard.state_step_routed",
        fn,
        state_args=(1,),
        donate_argnums=(1,),
        collective=True,
        max_signatures=2,
        suppress=("boundary-alias",),
        notes="residency-routed sharded state: local rows + routed batch "
        "lane per shard, lookup structures rebuilt in-program, psum-only "
        "boundary exchange (no table all_gather in the lowering); alias "
        "materialization waived as for shard.state_step",
    )


def build_state_step_fallback(mesh: Mesh, state_template: EngineState):
    """Resident mode's gathered fallback step (same signature as
    ``shard.state_step``): waves the routing policy cannot prove
    single-owner (unknown residency, lane overflow, message graphs) gather
    the ROW tables and step the replicated global view like v1 — but the
    lookup structures are NOT gathered: they are per-wave scratch in
    resident mode, so this leg substitutes global-shaped placeholders and
    rebuilds them in-program from the gathered rows (strictly fresher than
    v1's cadence invariant, and it sheds the map/index/ring gather volume
    from the wave). Sharded lookup leaves return the local slice of the
    rebuilt global scratch so at-rest shapes stay identical to v1."""
    axis = mesh.axis_names[0]
    nshards = int(mesh.devices.size)
    specs_tree = state_partition_specs(state_template, nshards, axis)
    spec_leaves = jax.tree_util.tree_leaves(
        specs_tree, is_leaf=lambda x: isinstance(x, P)
    )
    template_leaves = [
        leaf
        for _, leaf in jax.tree_util.tree_flatten_with_path(state_template)[0]
    ]

    def _sharded(spec) -> bool:
        return tuple(spec) == (axis,)

    def shard_fn(graph, state, batch, now, partition_id):
        idx = jax.lax.axis_index(axis)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
        full_leaves = []
        for (path, a), t, sp in zip(leaves, template_leaves, spec_leaves):
            name = _path_str(path)
            if is_lookup_leaf(name):
                if _sharded(sp):
                    # global-shaped scratch; rebuild overwrites it below
                    full_leaves.append(
                        jnp.zeros(tuple(t.shape), dtype=t.dtype)
                    )
                else:
                    full_leaves.append(a)
            elif _sharded(sp):
                full_leaves.append(
                    jax.lax.all_gather(a, axis, axis=0, tiled=True)
                )
            else:
                full_leaves.append(a)
        full = jax.tree_util.tree_unflatten(treedef, full_leaves)
        full = state_mod.rebuild_lookup_state(full)
        new_state, out, stats = step_kernel(
            graph, full, batch, now, partition_id=partition_id
        )

        def keep(a, s):
            if not _sharded(s):
                return a
            rows = a.shape[0] // nshards
            return jax.lax.dynamic_slice_in_dim(a, idx * rows, rows, axis=0)

        return _zip_specs(keep, new_state, specs_tree), out, stats

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), specs_tree, P(), P(), P()),
        out_specs=(specs_tree, P(), P()),
        check_vma=False,
    )
    return jit_registry.register_jit(
        "shard.state_step_fallback",
        fn,
        state_args=(1,),
        donate_argnums=(1,),
        collective=True,
        max_signatures=4,
        suppress=("boundary-alias",),
        notes="resident mode's gathered fallback: row tables gather, "
        "lookup structures rebuild in-program (sheds the map/index/ring "
        "gather volume vs shard.state_step); overflow waves add pow2 "
        "batch buckets, hence the wider signature allowance",
    )
