"""Engine selection: config → partition engine factory.

Reference parity: the reference has a single stream-processor engine,
installed unconditionally per leader partition
(broker-core/.../clustering/base/partitions/PartitionInstallService.java:106-291).
Here the broker chooses between the batched TPU device kernel (the
flagship) and the host oracle interpreter per the ``[engine]`` config
section; both serve the same record contract.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

from zeebe_tpu.runtime.config import BrokerCfg

# seconds the last device-engine install spent in each boot step (the
# autotune and the selfcheck are memoized: later installs read ~0)
LAST_BOOT_SECONDS: Dict[str, float] = {}


def engine_factory_from_config(
    cfg: BrokerCfg,
) -> Optional[Callable]:
    """Build the ``engine_factory`` for :class:`ClusterBroker` /
    :class:`Broker` from ``cfg.engine``. Returns ``None`` for the host
    oracle (the brokers' built-in default)."""
    etype = cfg.engine.type.lower()
    if etype == "host":
        return None
    if etype == "tpu":
        capacity = int(cfg.engine.capacity)
        num_vars = int(cfg.engine.num_vars)
        sub_capacity = int(cfg.engine.sub_capacity)

        def factory(partition_id: int, broker):
            from zeebe_tpu.tpu import TpuPartitionEngine

            if getattr(cfg.engine, "pallas_selfcheck", True):
                # autotune FIRST so the selfcheck validates the dispatch
                # the partition will actually serve with (per-build
                # pallas/XLA winners; cache-hit after the first boot on a
                # given build), then the on-chip parity smoke: a broken
                # Mosaic lowering must refuse to serve, not corrupt
                # partition state (round-3 advisor). Memoized; no-op
                # off-TPU.
                from zeebe_tpu.tpu import autotune, pallas_ops

                t0 = time.perf_counter()
                autotune.ensure_autotuned()
                t1 = time.perf_counter()
                pallas_ops.selfcheck()
                LAST_BOOT_SECONDS["autotune"] = t1 - t0
                LAST_BOOT_SECONDS["selfcheck"] = time.perf_counter() - t1
            # mesh placement: the broker's DevicePlan assigned this leader
            # partition a device at install time — the engine's state
            # commits there and its waves compute there, concurrently with
            # other partitions' waves on other devices
            device = None
            device_index = -1
            shard_devices = None
            device_indices = None
            state_shards = 1
            # sharded-state span first ([mesh] shardedPartitions > 1):
            # the partition's tables block-shard over the span instead of
            # committing to one device
            spanned = getattr(broker, "planned_span", None)
            if spanned is not None:
                shard_devs, shard_idx = spanned(partition_id)
                if shard_devs:
                    shard_devices = shard_devs
                    device_indices = shard_idx
                    device_index = shard_idx[0]
                    state_shards = len(shard_devs)
            if state_shards == 1:
                planned = getattr(broker, "planned_device", None)
                if planned is not None:
                    device, device_index = planned(partition_id)
            engine = TpuPartitionEngine(
                partition_id,
                broker.cfg.cluster.partitions,
                repository=broker.repository,
                clock=broker.clock,
                capacity=capacity,
                num_vars=num_vars,
                sub_capacity=sub_capacity,
                device=device,
                device_index=device_index,
                state_shards=state_shards,
                shard_devices=shard_devices,
                device_indices=device_indices,
                routing=getattr(cfg.mesh, "routing", "gathered"),
            )
            import jax as _jax

            if _jax.default_backend() == "tpu":
                # pay the kernel compiles at install time, not on the
                # first served batch (which blocks the broker actor and
                # times out every client request) — off-TPU compiles are
                # fast and tests deploy immediately, so skip there
                t0 = time.perf_counter()
                engine.warm()
                LAST_BOOT_SECONDS["warm"] = time.perf_counter() - t0
            return engine

        return factory
    raise ValueError(
        f"unknown engine type {cfg.engine.type!r} (expected 'host' or 'tpu')"
    )
