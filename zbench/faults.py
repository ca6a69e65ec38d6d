"""Faults planted under the timed path, for the controls (``--fault``).
Each breaks one thing the comparison has to catch; a run with one planted
must come out not correct. The benchmark's own runs never plant one.

  altered_answer        every 7th SEQUENCE_FLOW_TAKEN event carries an altered
                        payload, altered where the engine produces it
  half_batch            half of a wave's records are left out of the step
  state_unchanged       every fifth step returns its state unchanged: the
                        wave's records are written, its table updates lost
  duplicate_completion  an instance's completion is written twice
                        (control: the exactly-once guarantee)
  lost_tail             the end of the log is lost after the broker closed
                        (control: the durability guarantee)
  lost_publish          the message records of every seventh acknowledged
                        publish, the first among them, are gone from the log
                        on disk after the broker closed: each frame keeps its
                        position and length but holds no value (control: the
                        durability guarantee of a publish; only where the
                        traffic publishes messages)
"""

from __future__ import annotations

import glob
import os
import struct
import zlib

from zbench.check import MESSAGE

WORKFLOW_INSTANCE, EVENT, SEQUENCE_FLOW_TAKEN, ELEMENT_COMPLETED = 5, 0, 4, 9
NULL_VAL = 255
# a record frame's header up to its value-type byte: length, crc32 over
# bytes [8:length), position .. incident key, record type
VALUE_TYPE_AT = struct.calcsize("<iIqqqqiiqiqB")
NAMES = ("altered_answer", "half_batch", "state_unchanged", "duplicate_completion", "lost_tail",
         "lost_publish")


def applies(name: str, traffic: dict) -> bool:
    """Whether a cell with this traffic can have the fault."""
    return name != "lost_publish" or bool(traffic.get("messages"))


def _materialized(entry):
    return entry[0].row(entry[1]) if type(entry) is tuple else entry


def _wrap_collect(engine, change) -> None:
    inner = engine.collect_wave

    def collect_wave(wave):
        fresh = wave.collected is None
        results = inner(wave)
        if fresh:
            for res in results:
                change(res.written)
        return results

    engine.collect_wave = collect_wave


def plant(name: str, servers: list) -> None:
    if name not in NAMES:
        raise SystemExit(f"unknown fault {name!r}; zbench/faults.py has {NAMES}")
    count = [0]

    def event(entry, intent: int):
        rec = _materialized(entry)
        md = rec.metadata
        if (
            int(md.value_type) == WORKFLOW_INSTANCE and int(md.record_type) == EVENT
            and int(md.intent) == intent
        ):
            return rec
        return None

    def alter(written: list) -> None:
        # every process takes sequence flows: the fault fits any mix
        for i, entry in enumerate(written):
            rec = event(entry, SEQUENCE_FLOW_TAKEN)
            if rec is None:
                continue
            count[0] += 1
            if count[0] % 7 == 0:
                changed = rec.copy()
                changed.value.payload = {
                    **changed.value.payload,
                    "orderValue": changed.value.payload.get("orderValue", 0) + 1,
                }
                written[i] = changed

    def duplicate(written: list) -> None:
        for i, entry in enumerate(list(written)):
            rec = event(entry, ELEMENT_COMPLETED)
            if rec is None or rec.key != rec.value.workflow_instance_key:
                continue
            count[0] += 1
            if count[0] % 7 == 0:
                written.insert(i + 1, rec.copy())
                return

    if name == "state_unchanged":
        import jax
        import jax.numpy as jnp

        from zeebe_tpu.tpu import kernel

        inner_step = kernel.step_jit

        def step_jit(graph, state, batch, now, **kw):
            count[0] += 1
            if count[0] % 5:
                return inner_step(graph, state, batch, now, **kw)
            kept = jax.tree.map(jnp.copy, state)  # the step donates its state
            _, out, stats = inner_step(graph, state, batch, now, **kw)
            return kept, out, stats

        kernel.step_jit = step_jit

    for server in servers:
        engine = server.engine
        if name == "altered_answer":
            _wrap_collect(engine, alter)
        elif name == "duplicate_completion":
            _wrap_collect(engine, duplicate)
        elif name == "half_batch":
            inner = engine.dispatch_wave

            def dispatch_wave(records, _inner=inner):
                n = len(records)
                count[0] += 1
                if n >= 8 and count[0] % 5 == 0:
                    keep = list(range(n // 2))
                    select = getattr(records, "select", None)
                    records = select(keep) if select else [records[i] for i in keep]
                return _inner(records)

            engine.dispatch_wave = dispatch_wave


def after_close(name: str, data_dir: str, gen: dict) -> None:
    if name == "lost_tail":
        segments = sorted(glob.glob(os.path.join(data_dir, "partition-0", "segment-*.log")))
        with open(segments[-1], "r+b") as f:
            f.truncate(max(0, os.path.getsize(segments[-1]) - 16384))
    elif name == "lost_publish":
        _lose_publishes(data_dir, gen)


def _lose_publishes(data_dir: str, gen: dict) -> None:
    """Every frame of a MESSAGE record whose correlation key is a chosen
    publish's, in every partition's segments, made a frame with no value
    (value type NULL_VAL, its crc32 made good again): the log re-reads
    whole, at the same positions, without those records."""
    from zeebe_tpu.log.storage import SEGMENT_HEADER_SIZE
    from zeebe_tpu.protocol import codec

    acked = sorted(
        (r for r in gen["rows"] if "key" in r.get("publish", {})), key=lambda r: r["seq"]
    )
    lost = {r["publish"]["correlation_key"] for r in acked[::7]}
    for path in sorted(glob.glob(os.path.join(data_dir, "partition-*", "segment-*.log"))):
        with open(path, "rb") as f:
            data = bytearray(f.read())
        offset, changed = SEGMENT_HEADER_SIZE, False
        while offset < len(data):
            record, end = codec.decode_record(data, offset)
            if int(record.metadata.value_type) == MESSAGE and record.value.correlation_key in lost:
                data[offset + VALUE_TYPE_AT] = NULL_VAL
                struct.pack_into("<I", data, offset + 4, zlib.crc32(bytes(data[offset + 8:end])))
                changed = True
            offset = end
        if changed:
            with open(path, "r+b") as f:
                f.write(data)
