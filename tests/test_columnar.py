"""Columnar record plane: batch codec bit-identity, lazy row views, and
the zero-materialization proof metric.

The wave is the currency from readback to log/exporter/gateway (ROADMAP
item 4); the log is the contract — so the batch codec is pinned
bit-identical to per-record encoding, and the pure host wave path is
pinned to ZERO lazy row materializations
(``serving_rows_materialized_total``)."""

import pytest

from zeebe_tpu.protocol import codec, msgpack
from zeebe_tpu.protocol.columnar import (
    ColumnarBatch,
    FrameBlock,
    RecordsView,
    rows_materialized_total,
)
from zeebe_tpu.protocol.enums import ErrorType, RecordType, RejectionType, ValueType
from zeebe_tpu.protocol.metadata import RecordMetadata
from zeebe_tpu.protocol.records import (
    DeployedWorkflowMeta,
    DeploymentRecord,
    DeploymentResource,
    IncidentRecord,
    JobHeaders,
    JobRecord,
    MessageRecord,
    Record,
    TimerRecord,
    WorkflowInstanceRecord,
)


def _assorted_records():
    """One record per interesting shape: every value class family, unicode
    rejection reasons, binary resources, nested headers, empty values."""
    return [
        Record(
            position=5, key=7, timestamp=123, raft_term=2, producer_id=3,
            source_record_position=4,
            metadata=RecordMetadata(
                record_type=RecordType.COMMAND,
                value_type=ValueType.WORKFLOW_INSTANCE,
                intent=0, request_id=9, request_stream_id=1, incident_key=11,
            ),
            value=WorkflowInstanceRecord(
                bpmn_process_id="p", payload={
                    "a": 1, "s": "héllo", "n": -1.5, "b": True, "z": None,
                    "big": 2 ** 40, "neg": -77, "lst": [1, "x"],
                },
            ),
        ),
        Record(
            position=6,
            metadata=RecordMetadata(
                record_type=RecordType.COMMAND_REJECTION,
                value_type=ValueType.JOB, intent=3,
                rejection_type=RejectionType.BAD_VALUE,
                rejection_reason="bad ünicode reason " + "x" * 100,
            ),
            value=JobRecord(
                type="t" * 40, retries=-3,
                headers=JobHeaders(workflow_instance_key=5),
                custom_headers={"h": "v"},
            ),
        ),
        Record(
            position=7,
            metadata=RecordMetadata(value_type=ValueType.DEPLOYMENT),
            value=DeploymentRecord(
                topic_name="x",
                resources=[DeploymentResource(resource=b"\x00\xffbin" * 100)],
                deployed_workflows=[
                    DeployedWorkflowMeta(bpmn_process_id="p", version=1, key=2)
                ],
            ),
        ),
        Record(
            position=8,
            metadata=RecordMetadata(value_type=ValueType.INCIDENT),
            value=IncidentRecord(
                error_type=int(ErrorType.UNKNOWN), error_message="m" * 300
            ),
        ),
        Record(
            position=9,
            metadata=RecordMetadata(value_type=ValueType.MESSAGE),
            value=MessageRecord(name="n", correlation_key="ck"),
        ),
        Record(
            position=10,
            metadata=RecordMetadata(value_type=ValueType.TIMER),
            value=TimerRecord(due_date=-5),
        ),
        Record(position=11),  # no value → EMPTY_DOCUMENT
    ]


class TestBatchCodec:
    def test_encode_records_bit_identical_to_per_record(self):
        records = _assorted_records()
        buf, offsets = codec.encode_records(records)
        reference = b"".join(codec.encode_record(r) for r in records)
        assert bytes(buf) == reference
        # offsets point exactly at each frame start
        for record, off in zip(records, offsets):
            decoded, _ = codec.decode_record(bytes(buf), off)
            assert codec.encode_record(decoded) == codec.encode_record(record)

    def test_encode_columnar_bit_identical(self):
        records = _assorted_records()
        reference = b"".join(codec.encode_record(r) for r in records)
        batch = ColumnarBatch.from_records(records)
        assert bytes(codec.encode_columnar(batch)[0]) == reference
        view = RecordsView(list(records))
        assert bytes(codec.encode_columnar(view)[0]) == reference

    def test_fused_value_encode_matches_document_pack(self):
        for record in _assorted_records():
            if record.value is None:
                continue
            assert record.value.encode() == msgpack.pack(
                record.value.to_document()
            )

    def test_value_copy_is_deep(self):
        value = WorkflowInstanceRecord(
            bpmn_process_id="p", payload={"a": [1, {"b": 2}], "c": "x"}
        )
        clone = value.copy()
        clone.payload["a"][1]["b"] = 99
        clone.payload["c"] = "y"
        assert value.payload == {"a": [1, {"b": 2}], "c": "x"}


class TestLazyRows:
    def test_from_records_rows_precached_no_materializations(self):
        before = rows_materialized_total()
        records = _assorted_records()
        batch = ColumnarBatch.from_records(records)
        # column reads AND row reads: everything is pre-cached
        assert batch.positions() == [r.position for r in records]
        assert batch.value_types() == [
            int(r.metadata.value_type) for r in records
        ]
        assert list(batch) == records
        assert batch[0] is records[0]
        assert rows_materialized_total() == before

    def test_lazy_batch_materializes_on_row_access_and_counts(self):
        records = _assorted_records()
        built = []

        def materializer(i):
            built.append(i)
            return records[i].copy()

        batch = ColumnarBatch(
            len(records),
            {
                "position": [r.position for r in records],
                "value_type": [int(r.metadata.value_type) for r in records],
            },
            materializer=materializer,
        )
        before = rows_materialized_total()
        # column access never materializes
        assert batch.value_types() == [
            int(r.metadata.value_type) for r in records
        ]
        assert rows_materialized_total() == before
        assert built == []
        # row access materializes ONCE per row (cached) and counts
        row = batch.row(2)
        assert batch.row(2) is row
        assert built == [2]
        assert rows_materialized_total() == before + 1

    def test_records_view_columns_from_lazy_entries(self):
        records = _assorted_records()
        batch = ColumnarBatch(
            len(records),
            {
                "position": [r.position for r in records],
                "value_type": [int(r.metadata.value_type) for r in records],
            },
            materializer=lambda i: records[i].copy(),
        )
        view = RecordsView(batch.log_entries())
        before = rows_materialized_total()
        assert view.positions() == [r.position for r in records]
        assert view.value_types() == [
            int(r.metadata.value_type) for r in records
        ]
        sub = view.select([0, 2])
        assert sub.positions() == [records[0].position, records[2].position]
        assert rows_materialized_total() == before  # columns stayed lazy
        # iteration materializes (and shares row identity with the batch)
        rows = list(sub)
        assert rows[0] is batch.row(0)
        assert rows_materialized_total() > before


class TestColumnarLogAppend:
    def test_columnar_append_bit_identical_and_lazy(self, tmp_path):
        from zeebe_tpu.log import LogStream, SegmentedLogStorage

        def command(i):
            return Record(
                key=i,
                metadata=RecordMetadata(
                    record_type=RecordType.COMMAND,
                    value_type=ValueType.WORKFLOW_INSTANCE, intent=0,
                ),
                value=WorkflowInstanceRecord(
                    bpmn_process_id="p", payload={"i": i}
                ),
            )

        # reference log: plain record appends
        s1 = SegmentedLogStorage(str(tmp_path / "a"))
        log1 = LogStream(s1, clock=lambda: 42)
        log1.append([command(i) for i in range(10)])

        # columnar log: lazy batch (rows built only through the batch)
        template = [command(i) for i in range(10)]
        batch = ColumnarBatch(
            10,
            {
                "key": [r.key for r in template],
                "record_type": [int(r.metadata.record_type) for r in template],
                "value_type": [int(r.metadata.value_type) for r in template],
                "intent": [0] * 10,
            },
            materializer=lambda i: template[i],
        )
        s2 = SegmentedLogStorage(str(tmp_path / "b"))
        log2 = LogStream(s2, clock=lambda: 42)
        before = rows_materialized_total()
        log2.append(batch)
        # the append itself had to encode values (template rows), counted
        # as materializations only for rows the batch had to build
        a = [codec.encode_record(r) for r in log1.reader(0).read_committed()]
        b = [codec.encode_record(r) for r in log2.reader(0).read_committed()]
        assert a == b
        # reopen: recovery decodes the same bytes
        s2.close()
        s3 = SegmentedLogStorage(str(tmp_path / "b"))
        log3 = LogStream(s3, clock=lambda: 42)
        assert [
            codec.encode_record(r) for r in log3.reader(0).read_committed()
        ] == a
        s1.close()
        s3.close()
        assert rows_materialized_total() >= before

    def test_committed_view_reads_columns_without_lock_per_record(self, tmp_path):
        from zeebe_tpu.log import LogStream, SegmentedLogStorage

        storage = SegmentedLogStorage(str(tmp_path))
        log = LogStream(storage, clock=lambda: 1)
        records = _assorted_records()
        for r in records:
            r.position = -1
        log.append(records)
        view = log.committed_view(0)
        assert len(view) == len(records)
        assert view.positions() == list(range(len(records)))
        assert view.value_types() == [
            int(r.metadata.value_type) for r in records
        ]
        # bounded reads
        assert len(log.committed_view(2, 3)) == 3
        assert log.committed_view(2, 3).positions() == [2, 3, 4]
        storage.close()


def _lazy_batch(records):
    """A columnar batch over copies of ``records`` that builds no row to
    be appended (every frame column given, values from a builder)."""
    from zeebe_tpu.protocol.columnar import FRAME_COLUMNS

    meta = {"record_type", "value_type", "intent", "rejection_type"}
    cols = {}
    for name in FRAME_COLUMNS:
        if name == "position":
            continue
        if name in meta:
            cols[name] = [int(getattr(r.metadata, name)) for r in records]
        elif hasattr(records[0].metadata, name):
            cols[name] = [getattr(r.metadata, name) for r in records]
        else:
            cols[name] = [getattr(r, name) for r in records]
    return ColumnarBatch(
        len(records), cols,
        materializer=lambda i: records[i].copy(),
        value_builder=lambda i: records[i].value,
    )


def _appended_twice(tmp_path, segment_size=1 << 20):
    """Two logs holding the same lazy appends (a columnar batch, then a
    mixed one: real rows between refs into a second batch); the second
    log's entries are frozen into frame refs, the first's stay lazy."""
    from zeebe_tpu.log import LogStream, SegmentedLogStorage
    from zeebe_tpu.protocol.columnar import MixedBatch

    logs = []
    for name in ("lazy", "frozen"):
        records = _assorted_records()
        for r in records:
            r.position = -1
        log = LogStream(
            SegmentedLogStorage(str(tmp_path / name), segment_size=segment_size),
            clock=lambda: 7,
        )
        log.append(_lazy_batch(records))
        more = _assorted_records()
        backing = _lazy_batch(more)
        log.append(MixedBatch([more[0], (backing, 1), more[2], (backing, 3), (backing, 4)]))
        logs.append(log)
    return logs


class TestFrameRefs:
    def test_frozen_entries_read_as_the_rows_the_batch_builds(self, tmp_path):
        from zeebe_tpu.protocol.columnar import FRAME_COLUMNS

        lazy, frozen = _appended_twice(tmp_path)
        n = frozen.next_position
        lazy_refs = sum(1 for e in frozen._records if type(e) is tuple)
        assert frozen.freeze_below(n) == lazy_refs > 0
        assert all(
            type(e) is not tuple or type(e[0]) is FrameBlock
            for e in frozen._records
        )
        assert frozen.freeze_below(n) == 0  # once
        # columns and value bytes straight from the frames: nothing built
        before = rows_materialized_total()
        a, b = lazy.committed_view(0), frozen.committed_view(0)
        for name in FRAME_COLUMNS:
            assert b.col(name) == a.col(name), name
        for i in range(n):
            assert b.value_bytes(i) == a.value_bytes(i)
            assert frozen.term_at(i) == lazy.term_at(i)
        assert rows_materialized_total() == before
        # rows decode once from the frame, equal to the batch's own
        for p in range(n):
            row = frozen.record_at(p)
            assert row == lazy.record_at(p), p
            assert frozen.record_at(p) is row
        assert list(frozen.committed_view(0)) == list(lazy.committed_view(0))

    def test_committed_frames_are_the_frames_on_disk(self, tmp_path):
        import struct

        from zeebe_tpu.log import LogStream, SegmentedLogStorage
        from zeebe_tpu.log.logstream import _iter_disk_frames

        lazy, frozen = _appended_twice(tmp_path)
        frozen.freeze_below(3)
        for log in (lazy, frozen):
            disk = {}
            for record, address in _iter_disk_frames(log, 0):
                (length,) = struct.unpack("<i", log.storage.read(address, 4))
                disk[record.position] = log.storage.read(address, length)
            got = log.committed_frames(0)
            assert [p for p, _vt, _f in got] == sorted(disk)
            for p, vt, frame in got:
                assert frame == disk[p]
                assert vt == frame[69]
            assert log.committed_frames(2, limit=3) == got[2:5]
        # reopened, the log holds no block: the recovered Record instead
        frozen.storage.close()
        reopened = LogStream(
            SegmentedLogStorage(str(tmp_path / "frozen")), clock=lambda: 7
        )
        for p, vt, record in reopened.committed_frames(0):
            assert codec.encode_record(record) == disk[p]
            assert vt == int(record.metadata.value_type)

    def test_truncation_and_compaction_keep_frames_and_refs(self, tmp_path):
        # a segment an append: compaction can drop whole appends
        lazy, frozen = _appended_twice(tmp_path, segment_size=512)
        n = frozen.next_position
        frozen.freeze_below(n)
        kept = {p: frozen.record_at(p) for p in range(4)}
        frozen.truncate(4)  # inside the first append's block
        records = _assorted_records()[:3]
        for r in records:
            r.position = -1
        frozen.append(_lazy_batch(records))
        got = frozen.committed_frames(0)
        assert [p for p, _vt, _f in got] == list(range(7))
        assert [codec.decode_record(f)[0] for _p, _vt, f in got[:4]] == [
            kept[p] for p in range(4)
        ]
        assert codec.decode_record(got[4][2])[0] == frozen.record_at(4)
        frozen.append(_lazy_batch(_assorted_records()[:2]))
        got = frozen.committed_frames(0)
        base = frozen.compact(7)
        assert base == 7
        assert frozen.committed_frames(0) == got[7:]
        assert [b.first for b in frozen._blocks] == [7]

    def test_appends_race_the_pump_reads(self, tmp_path):
        """One thread appends (the raft actor), another reads frames and
        freezes behind the commit (the broker actor's pump)."""
        import struct
        import sys
        import threading

        from zeebe_tpu.log import LogStream, SegmentedLogStorage

        log = LogStream(SegmentedLogStorage(str(tmp_path)), clock=lambda: 7)
        rounds = 200
        errors = []

        def append():
            for _ in range(rounds):
                records = _assorted_records()
                for r in records:
                    r.position = -1
                log.append(_lazy_batch(records))

        def pump():
            cursor = 0
            while cursor < rounds * 7:
                for position, _vt, frame in log.committed_frames(cursor):
                    if struct.unpack_from("<q", frame, 8)[0] != position:
                        errors.append(position)
                    cursor = position + 1
                log.freeze_below(cursor)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=f) for f in (append, pump)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(interval)
        assert errors == []
        assert all(
            type(e) is tuple and isinstance(e[0], FrameBlock) or type(e) is not tuple
            for e in log._records
        )
        assert sum(1 for e in log._records if type(e) is tuple) == rounds * 7
