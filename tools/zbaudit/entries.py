"""Drivers: trace + lower every registered jit entry point for audit.

One representative argument configuration per entry, all abstract where
possible (``jax.eval_shape`` ShapeDtypeStructs — no device arrays, no
compile): the only concrete inputs are the small host-built device graph
tables. ``kernel.step`` is lowered at the census configuration
(wave 2^10, capacity 2*wave — benchmarks/census_budget.json's geometry)
so the ``op-census`` pass gates the SAME program the old census_gate
did; the HBM pass evaluates its closed-form model at the DEFAULT serving
config separately, which needs no lowering at all.

Import discipline: jax is imported inside :func:`build_entries` so
``tools.zbaudit.__main__`` can pin JAX_PLATFORMS / XLA_FLAGS (8 virtual
CPU devices for the mesh entries) before jax initializes.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Set

from tools.zbaudit.core import AuditedEntry, rel_src

# every driver below, keyed by the registry name it audits; the
# signature-guard pass checks this set against the live registry
DRIVER_NAMES = (
    "kernel.step",
    "kernel.tick",
    "engine.due_probe",
    "engine.credit_flush",
    "drive.round",
    "drive.quiesce",
    "shard.sharded_step",
    "shard.frame_exchange",
    "shard.sharded_drive",
    "shard.state_step",
    "shard.state_step_routed",
    "shard.state_step_fallback",
)
AUTOTUNE_PREFIX = "autotune."


def _trace_lower(fn, *args, **kw):
    """(traced, lowered) — trace once, lower from the trace; falls back
    to a plain .lower() on jax builds without the Traced stage."""
    try:
        traced = fn.trace(*args, **kw)
        return traced, traced.lower()
    except AttributeError:
        return None, fn.lower(*args, **kw)


def build_entries(
    budget: dict, names: Optional[Set[str]] = None
) -> List[AuditedEntry]:
    """Build AuditedEntry objects (optionally restricted to ``names``;
    an ``autotune.*`` wildcard member selects all microbench families)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from zeebe_tpu import tpu as _tpu  # noqa: F401  (enables x64)
    from zeebe_tpu.tpu import (
        autotune,
        batch as rb,
        drive,
        engine as engine_mod,
        jit_registry,
        kernel,
        shard,
        state as state_mod,
    )
    from zeebe_tpu.testing import graphs

    cfg = budget.get("audit_config", {})
    wave = 1 << int(cfg.get("wave_pow", 10))
    shard_wave = int(cfg.get("shard_wave", 256))
    exchange_slots = int(cfg.get("exchange_slots", 32))
    frame_slots = int(cfg.get("frame_slots", 32))
    frame_bytes = int(cfg.get("frame_bytes", 1024))

    def wanted(name: str) -> bool:
        if names is None:
            return True
        if name.startswith(AUTOTUNE_PREFIX):
            return name in names or AUTOTUNE_PREFIX + "*" in names
        return name in names

    graph, _meta = graphs.build_graph()
    num_vars = max(graph.num_vars, 8)
    graph = dataclasses.replace(graph, num_vars=num_vars)
    state_sds = jax.eval_shape(
        lambda: state_mod.make_state(
            capacity=2 * wave, num_vars=num_vars, job_capacity=2 * wave,
            sub_capacity=8,
        )
    )
    # a wave as the engine ships it: the packed pair
    batch_sds = rb.pair_shapes(wave, num_vars)
    now_sds = jax.ShapeDtypeStruct((), jnp.int64)
    census_cfg = {
        "capacity": 2 * wave, "wave": wave, "num_vars": num_vars,
        "sub_capacity": 8,
    }

    out: List[AuditedEntry] = []

    def add(name: str, fn, *args, config=None, **kw):
        entry = jit_registry.get(name)
        if entry is None:
            return  # the signature-guard pass reports the stale driver
        traced, lowered = _trace_lower(fn, *args, **kw)
        path, line = rel_src(entry.wrapped)
        out.append(AuditedEntry(
            name=name, entry=entry, traced=traced, lowered=lowered,
            config=dict(config or census_cfg), path=path, line=line,
        ))

    if wanted("kernel.step"):
        add(
            "kernel.step", kernel.step_jit,
            graph, state_sds, batch_sds, now_sds, synthetic_workers=True,
        )
    if wanted("kernel.tick"):
        add("kernel.tick", kernel.tick_jit, state_sds, now_sds)
    if wanted("engine.due_probe"):
        add(
            "engine.due_probe", engine_mod._due_probe_jit,
            state_sds, now_sds,
        )
    if wanted("engine.credit_flush"):
        # the two small leaves it is launched with: the credit column and
        # the returned credits it has not been told of, one i32 a slot
        add(
            "engine.credit_flush", engine_mod._credit_flush_jit,
            state_sds.sub_credits, state_sds.sub_credits,
        )

    if wanted("drive.round") or wanted("drive.quiesce"):
        queue_sds = jax.eval_shape(
            lambda: drive.make_queue(4 * wave, num_vars)
        )
        if wanted("drive.round"):
            add(
                "drive.round", drive.drive_jit,
                graph, state_sds, queue_sds, now_sds,
                batch_size=wave, synthetic_workers=True,
            )
        if wanted("drive.quiesce"):
            add(
                "drive.quiesce", drive._quiesce_device,
                graph, state_sds, queue_sds, now_sds,
                batch_size=wave, synthetic_workers=True, max_rounds=10_000,
            )

    shard_names = ("shard.sharded_step", "shard.frame_exchange",
                   "shard.sharded_drive", "shard.state_step",
                   "shard.state_step_routed", "shard.state_step_fallback")
    if any(wanted(n) for n in shard_names) and len(jax.devices()) >= 2:
        mesh = Mesh(np.asarray(jax.devices()), ("partitions",))
        nparts = mesh.devices.shape[0]

        def stack(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    (nparts,) + tuple(a.shape), a.dtype
                ),
                tree,
            )

        shard_cfg = {
            "nparts": nparts, "capacity": 2 * wave, "wave": shard_wave,
            "num_vars": num_vars, "exchange_slots": exchange_slots,
        }
        if wanted("shard.sharded_step"):
            step_fn, _n = shard.build_sharded_step(
                mesh, exchange_slots=exchange_slots
            )
            sbatch = jax.eval_shape(
                lambda: rb.empty(shard_wave, num_vars)
            )
            sends = jax.eval_shape(
                lambda: shard.make_exchange(nparts, exchange_slots, num_vars)
            )
            add(
                "shard.sharded_step", step_fn,
                graph, stack(state_sds), stack(sbatch), sends, now_sds,
                config=shard_cfg,
            )
        if wanted("shard.frame_exchange"):
            shard.build_frame_exchange(mesh, frame_slots, frame_bytes)
            fx = jit_registry.get("shard.frame_exchange")
            if fx is not None:
                buf = jax.ShapeDtypeStruct(
                    (nparts, nparts, frame_slots, frame_bytes), jnp.uint8
                )
                lane = jax.ShapeDtypeStruct(
                    (nparts, nparts, frame_slots), jnp.int32
                )
                add(
                    "shard.frame_exchange", fx.fn, buf, lane, lane,
                    config={
                        "nparts": nparts, "slots": frame_slots,
                        "frame_bytes": frame_bytes,
                    },
                )
        if wanted("shard.sharded_drive"):
            # the message-correlation graph (config 4): it has messages,
            # so the cross-partition all_to_all exchange branch traces in
            # and the collective-volume pass models the real ICI hop
            mgraph, _mmeta = graphs.build_graph_c4()
            mnv = max(mgraph.num_vars, 8)
            mgraph = dataclasses.replace(mgraph, num_vars=mnv)
            mstate = jax.eval_shape(
                lambda: state_mod.make_state(
                    capacity=2 * wave, num_vars=mnv, job_capacity=2 * wave,
                    sub_capacity=8,
                )
            )
            drive_fn = shard.build_sharded_drive(
                mesh, batch_size=shard_wave, synthetic_workers=True,
                exchange_slots=exchange_slots,
            )
            squeue = jax.eval_shape(
                lambda: drive.make_queue(4 * shard_wave * max(
                    mgraph.emit_width, 1), mnv)
            )
            add(
                "shard.sharded_drive", drive_fn,
                mgraph, stack(mstate), stack(squeue), now_sds,
                config={**shard_cfg, "num_vars": mnv, "graph": "config4"},
            )
        if wanted("shard.state_step"):
            # mesh-SHARDED single-partition state (engine state_shards):
            # ONE partition's tables block-shard over every device; the
            # step gathers them per wave (the budgeted cross-shard read)
            # and keeps local row blocks on write. Audited at the census
            # geometry so the collective pass prices the real gathers;
            # `state_shards` in the config switches the HBM pass to the
            # per-device residency model (total / D for sharded leaves).
            smesh = Mesh(np.asarray(jax.devices()), (shard.STATE_AXIS,))
            sstep = shard.build_state_step(smesh, state_sds)
            pid_sds = jax.ShapeDtypeStruct((), jnp.int32)
            add(
                "shard.state_step", sstep,
                graph, state_sds, batch_sds, now_sds, pid_sds,
                config={**census_cfg, "state_shards": nparts},
            )
        routed_names = ("shard.state_step_routed",
                        "shard.state_step_fallback")
        if any(wanted(n) for n in routed_names):
            # sharded-state v2 (resident routing): the routed program
            # steps each shard on its own rows + its routed batch lane
            # ([nparts, shard_wave] lanes sharded over the mesh axis) —
            # its collective budget is the acceptance gate proving the
            # per-wave volume is boundary traffic (psum of emissions),
            # not table gathers; the op census proves NO all_gather in
            # the lowering. The fallback keeps v1's gathered shape but
            # rebuilds the lookup structures in-program, shedding their
            # gather volume — budgeted separately.
            smesh = Mesh(np.asarray(jax.devices()), (shard.STATE_AXIS,))
            pid_sds = jax.ShapeDtypeStruct((), jnp.int32)
            routed_cfg = {
                **census_cfg, "state_shards": nparts, "wave": shard_wave,
                "routing": "resident",
            }
            if wanted("shard.state_step_routed"):
                rstep = shard.build_state_step_routed(smesh, state_sds)
                add(
                    "shard.state_step_routed", rstep,
                    graph, state_sds,
                    rb.pair_shapes(shard_wave, num_vars, (nparts,)),
                    now_sds, pid_sds,
                    config=routed_cfg,
                )
            if wanted("shard.state_step_fallback"):
                fstep = shard.build_state_step_fallback(smesh, state_sds)
                add(
                    "shard.state_step_fallback", fstep,
                    graph, state_sds, rb.pair_shapes(shard_wave, num_vars),
                    now_sds, pid_sds,
                    config=routed_cfg,
                )

    if names is None or any(n.startswith(AUTOTUNE_PREFIX) for n in names):
        for family, fn in autotune.audit_candidates().items():
            name = AUTOTUNE_PREFIX + family
            if wanted(name):
                add(name, fn, config={"family": family})

    return out
