"""The five ``BASELINE.json`` process shapes as compiled device graphs, and
staged command batches for them: what the compile checks, the IR audit
(``tools/zbaudit``), the churn and sharded-state tests and
``benchmarks/profile_round.py`` step through ``kernel.step`` directly."""

import dataclasses

import numpy as np


def _compile(model):
    from zeebe_tpu.models.transform.transformer import transform_model
    from zeebe_tpu.tpu import graph as graph_mod

    workflows = transform_model(model)
    for wf in workflows:
        wf.key = 9
        wf.version = 1
    return graph_mod.compile_graph(workflows)


def build_graph():
    """Config 1: single service-task sequence (order-process)."""
    from zeebe_tpu.models.bpmn.builder import Bpmn

    model = (
        Bpmn.create_process("order-process")
        .start_event("start")
        .service_task("collect-money", type="payment-service")
        .end_event("end")
        .done()
    )
    return _compile(model)


def build_graph_xor():
    """Config 2: exclusive-gateway 2-way split/merge with json-el
    conditions (BASELINE.json configs[1])."""
    from zeebe_tpu.models.bpmn.builder import Bpmn

    builder = (
        Bpmn.create_process("xor-process")
        .start_event("start")
        .exclusive_gateway("split")
    )
    builder.branch('$.orderValue > 50').service_task(
        "big", type="payment-service"
    ).end_event("end-big")
    builder.branch(default=True).service_task(
        "small", type="payment-service"
    ).end_event("end-small")
    return _compile(builder.done())


def build_graph_forkjoin():
    """Config 3: parallel-gateway fork/join (BASELINE.json configs[2])."""
    from zeebe_tpu.models.bpmn.builder import Bpmn
    from zeebe_tpu.models.bpmn.model import ParallelGateway

    builder = (
        Bpmn.create_process("fork-process")
        .start_event("start")
        .parallel_gateway("fork")
    )
    join = ParallelGateway(id="join")
    join.scope_id = "fork-process"
    builder.model.add(join)
    builder.branch().service_task("task-a", type="payment-service").connect_to("join")
    builder.branch().service_task("task-b", type="payment-service").connect_to("join")
    builder.move_to("join").end_event("end")
    return _compile(builder.done())


def stage_creates(meta, wave, num_vars, interns):
    """Columnar CREATE commands (payload {orderId, orderValue}) — the
    ClientApiMessageHandler write path, batched."""
    import jax.numpy as jnp

    from zeebe_tpu.protocol.enums import RecordType, ValueType
    from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI
    from zeebe_tpu.tpu import batch as rb
    from zeebe_tpu.tpu.conditions import VT_NUM

    b = rb.empty(wave, num_vars)
    oid = meta.varspace.column("orderId")
    oval = meta.varspace.column("orderValue")
    v_vt = np.zeros((wave, num_vars), np.int8)
    v_num = np.zeros((wave, num_vars), np.float32)
    v_vt[:, oid] = VT_NUM
    v_vt[:, oval] = VT_NUM
    v_num[:, oid] = np.arange(wave)
    v_num[:, oval] = 99.0
    return dataclasses.replace(
        b,
        valid=jnp.ones((wave,), bool),
        rtype=jnp.full((wave,), int(RecordType.COMMAND), jnp.int32),
        vtype=jnp.full((wave,), int(ValueType.WORKFLOW_INSTANCE), jnp.int32),
        intent=jnp.full((wave,), int(WI.CREATE), jnp.int32),
        wf=jnp.zeros((wave,), jnp.int32),
        v_vt=jnp.asarray(v_vt),
        v_num=jnp.asarray(v_num),
    )


def build_graph_c4():
    """Config 4: message catch + interrupting timer boundary — device-
    compiled since round 4 (BASELINE.json configs[3])."""
    return _compile(_config4_model())


def build_graph_c5():
    """Config 5: multi-instance sub-process, cardinality 4 (BASELINE.json
    configs[4]) — device-compiled since round 4."""
    return _compile(_config5_model())


def stage_c4_creates(meta, wave, num_vars, base):
    """CREATE commands with numeric correlation keys oid = base+i."""
    import jax.numpy as jnp

    from zeebe_tpu.protocol.enums import RecordType, ValueType
    from zeebe_tpu.protocol.intents import WorkflowInstanceIntent as WI
    from zeebe_tpu.tpu import batch as rb
    from zeebe_tpu.tpu.conditions import VT_NUM

    b = rb.empty(wave, num_vars)
    oid = meta.varspace.column("oid")
    v_vt = np.zeros((wave, num_vars), np.int8)
    v_num = np.zeros((wave, num_vars), np.float32)
    v_vt[:, oid] = VT_NUM
    v_num[:, oid] = base + np.arange(wave)
    return dataclasses.replace(
        b,
        valid=jnp.ones((wave,), bool),
        rtype=jnp.full((wave,), int(RecordType.COMMAND), jnp.int32),
        vtype=jnp.full((wave,), int(ValueType.WORKFLOW_INSTANCE), jnp.int32),
        intent=jnp.full((wave,), int(WI.CREATE), jnp.int32),
        wf=jnp.zeros((wave,), jnp.int32),
        v_vt=jnp.asarray(v_vt),
        v_num=jnp.asarray(v_num),
    )


def stage_c4_publishes(meta, wave, num_vars, base):
    """PUBLISH commands correlating every EVEN oid of the wave (the odd
    half expires through the interrupting timer boundary)."""
    import jax.numpy as jnp

    from zeebe_tpu.protocol.enums import RecordType, ValueType
    from zeebe_tpu.protocol.intents import MessageIntent as MI
    from zeebe_tpu.tpu import batch as rb
    from zeebe_tpu.tpu.conditions import VT_BOOL, VT_NUM

    half = wave // 2
    b = rb.empty(wave, num_vars)
    paid = meta.varspace.column("paid")
    v_vt = np.zeros((wave, num_vars), np.int8)
    v_num = np.zeros((wave, num_vars), np.float32)
    v_vt[:half, paid] = VT_BOOL
    v_num[:half, paid] = 1.0
    name_id = meta.interns.intern("paid")
    worker = np.zeros((wave,), np.int32)
    worker[:half] = (
        (base + 2 * np.arange(half)).astype(np.float32).view(np.int32)
    )
    return dataclasses.replace(
        b,
        valid=jnp.asarray(np.arange(wave) < half),
        rtype=jnp.full((wave,), int(RecordType.COMMAND), jnp.int32),
        vtype=jnp.full((wave,), int(ValueType.MESSAGE), jnp.int32),
        intent=jnp.full((wave,), int(MI.PUBLISH), jnp.int32),
        type_id=jnp.full((wave,), name_id, jnp.int32),
        retries=jnp.full((wave,), int(VT_NUM), jnp.int32),
        worker=jnp.asarray(worker),
        v_vt=jnp.asarray(v_vt),
        v_num=jnp.asarray(v_num),
    )



def _config4_model():
    """Message catch + interrupting timer boundary (BASELINE configs[3])."""
    from zeebe_tpu.models.bpmn.builder import Bpmn

    return (
        Bpmn.create_process("c4")
        .start_event("start")
        .receive_task("wait-pay", message_name="paid", correlation_key="$.oid")
        .boundary_event("deadline", duration_ms=30_000)
        .end_event("expired")
        .move_to("wait-pay")
        .end_event("done")
        .done()
    )


def _config5_model():
    """Multi-instance subprocess (BASELINE configs[4])."""
    from zeebe_tpu.models.bpmn.builder import Bpmn

    builder = Bpmn.create_process("c5")
    sub = builder.start_event("start").sub_process(
        "each", multi_instance={"cardinality": 4}
    )
    sub.start_event("s").service_task(
        "work", type="payment-service"  # the job type of every shape here
    ).end_event("e")
    return sub.embedded_done().end_event("done").done()
