"""The serving path's rebuild of the lookup structures
(``state.rebuild_lookup_state`` from ``_stage_and_launch``): whole-column
passes that stall the broker actor, at the served size for over a second
and the first time for as long as their compiles take.

- It runs when the DEVICE's key counters have advanced past the index
  window since the last rebuild. The host's wave-by-wave figure (every
  record allocating ``emit_width`` keys) only bounds that advance from
  above: crossing the window with it costs a read of the counters, not a
  rebuild.
- The rebuilt state keeps the step program's signature: a leaf made from
  nothing is uncommitted beside a committed state, and the first wave after
  the rebuild would compile the step again.
"""

import numpy as np

import jax

from zeebe_tpu.engine.interpreter import WorkflowRepository
from zeebe_tpu.gateway import JobWorker, ZeebeClient
from zeebe_tpu.models.bpmn.builder import Bpmn
from zeebe_tpu.models.transform.transformer import transform_model
from zeebe_tpu.runtime import Broker, ControlledClock
from zeebe_tpu.tpu import TpuPartitionEngine, kernel
from zeebe_tpu.tpu import state as state_mod


def _model():
    return (
        Bpmn.create_process("served")
        .start_event("start")
        .service_task("work", type="served-service")
        .end_event("end")
        .done()
    )


def test_rebuild_waits_for_the_devices_key_counters(tmp_path, monkeypatch):
    clock = ControlledClock(start_ms=1_000_000)
    repo = WorkflowRepository()
    engines = []

    def factory(pid):
        engines.append(
            TpuPartitionEngine(pid, 1, repository=repo, clock=clock, capacity=256)
        )
        return engines[-1]

    broker = Broker(
        num_partitions=1, data_dir=str(tmp_path), clock=clock,
        engine_factory=factory,
    )
    (engine,) = engines
    window = engine.state.ei_index.shape[0] // 4
    inner_rebuild = state_mod.rebuild_lookup_state
    inner_read = engine._device_key_counters
    rebuilds, reads = [], []

    def advance():
        return max(
            now - then for now, then in zip(inner_read(), engine._keys_rebuilt)
        )

    def rebuild(state):
        rebuilds.append((engine._keys_at_rebuild, advance()))
        return inner_rebuild(state)

    def read():
        reads.append(advance())
        return inner_read()

    monkeypatch.setattr(state_mod, "rebuild_lookup_state", rebuild)
    monkeypatch.setattr(engine, "_device_key_counters", read)
    try:
        client = ZeebeClient(broker)
        client.deploy_model(_model())
        JobWorker(broker, "served-service", lambda ctx: {"done": True})
        highest = 0
        for i in range(36):
            client.create_instance("served", {"n": i})
            broker.run_until_idle()
            # between waves the host's figure bounds the true advance
            assert advance() <= engine._keys_at_rebuild <= window
            highest = max(highest, advance())
    finally:
        broker.close()
    # the true advance came close to the window and never past it
    assert window * 3 // 4 < highest <= window
    assert len(rebuilds) == 2
    for figure, true_advance in rebuilds:
        # rebuilt on the measured advance plus one wave's bound, no earlier
        assert window < figure <= true_advance + 5 * 2 * 8
    # the counters were read where the bound crossed the window, which is
    # more often than the rebuilds and far less often than every wave
    assert len(rebuilds) < len(reads) < engine._dispatch_seq // 4


def test_the_rebuild_keeps_the_step_signature():
    repo = WorkflowRepository()
    workflows = transform_model(_model())
    for wf in workflows:
        wf.key, wf.version = 1, 1
    repo.merge(workflows)
    # a placed engine, as a broker's is (DevicePlan): its state is
    # COMMITTED to the device, so a leaf made from nothing stands out
    engine = TpuPartitionEngine(
        0, 1, repository=repo, clock=lambda: 1_000_000, capacity=256,
        device=jax.devices()[0],
    )
    engine._recompile()

    def step(now):
        engine._run_step(engine._stage([], pad_to=64), np.int64(now))

    step(1)
    step(2)  # the state is the step's own output from here on
    before = kernel.step_jit._cache_size()
    engine.state = state_mod.rebuild_lookup_state(engine.state)
    step(3)
    assert kernel.step_jit._cache_size() == before
