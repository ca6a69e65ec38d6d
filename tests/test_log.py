"""Log storage + log stream tests (reference: logstreams module tests)."""

from zeebe_tpu.log import LogStream, LogStreamReader, SegmentedLogStorage
from zeebe_tpu.protocol import RecordType, ValueType, WorkflowInstanceIntent
from zeebe_tpu.protocol.metadata import RecordMetadata
from zeebe_tpu.protocol.records import Record, WorkflowInstanceRecord
from zeebe_tpu.testing import DiskFaults


def wi_record(key=1, activity="start", intent=WorkflowInstanceIntent.ELEMENT_READY):
    return Record(
        key=key,
        metadata=RecordMetadata(
            record_type=RecordType.EVENT,
            value_type=ValueType.WORKFLOW_INSTANCE,
            intent=int(intent),
        ),
        value=WorkflowInstanceRecord(activity_id=activity, workflow_instance_key=key),
    )


def test_append_assigns_dense_positions(tmp_log_dir):
    log = LogStream(SegmentedLogStorage(tmp_log_dir))
    log.append([wi_record(), wi_record()])
    last = log.append([wi_record()])
    assert last == 2
    assert log.next_position == 3
    assert log.commit_position == 2


def test_reader_iterates_in_order(tmp_log_dir):
    log = LogStream(SegmentedLogStorage(tmp_log_dir))
    for i in range(10):
        log.append([wi_record(key=i, activity=f"a{i}")])
    records = list(log.reader(0))
    assert [r.position for r in records] == list(range(10))
    assert [r.value.activity_id for r in records] == [f"a{i}" for i in range(10)]


def test_reader_seek(tmp_log_dir):
    log = LogStream(SegmentedLogStorage(tmp_log_dir))
    for i in range(10):
        log.append([wi_record(key=i)])
    reader = log.reader(7)
    assert [r.position for r in reader] == [7, 8, 9]


def test_recovery_after_reopen(tmp_log_dir):
    log = LogStream(SegmentedLogStorage(tmp_log_dir))
    for i in range(5):
        log.append([wi_record(key=i)])
    log.flush()
    log.storage.close()

    reopened = LogStream(SegmentedLogStorage(tmp_log_dir))
    assert reopened.next_position == 5
    assert reopened.commit_position == 4
    assert [r.position for r in reopened.reader(0)] == list(range(5))
    # appends continue from the recovered position
    assert reopened.append([wi_record(key=99)]) == 5


def test_append_after_close_reopens_current_segment(tmp_log_dir):
    """Regression: an append arriving after close() —
    broker shutdown racing a late drain — crashed with ``AttributeError:
    'NoneType' object has no attribute 'seek'``. The storage must reopen
    the current segment and keep the address sequence intact."""
    storage = SegmentedLogStorage(tmp_log_dir)
    a0 = storage.append(b"block-0")
    storage.close()
    a1 = storage.append(b"block-1")  # must reopen, not crash
    assert storage.segment_of(a1) == storage.segment_of(a0)
    assert storage.offset_of(a1) == storage.offset_of(a0) + len(b"block-0")
    assert storage.read(a0, 7) == b"block-0"
    assert storage.read(a1, 7) == b"block-1"
    # close/reset interplay: reset on a closed storage must not crash
    storage.close()
    storage.reset()
    assert storage.append(b"fresh") > 0


def test_log_append_after_storage_close(tmp_log_dir):
    log = LogStream(SegmentedLogStorage(tmp_log_dir))
    log.append([wi_record(key=1)])
    log.storage.close()
    # the stream keeps accepting appends after its storage was closed
    assert log.append([wi_record(key=2)]) == 1
    assert [r.key for r in log.reader(0)] == [1, 2]


def test_segment_rolling(tmp_log_dir):
    log = LogStream(SegmentedLogStorage(tmp_log_dir, segment_size=1024))
    for i in range(50):
        log.append([wi_record(key=i, activity="activity-with-a-longer-name")])
    assert len(log.storage._segments) > 1
    assert [r.position for r in log.reader(0)] == list(range(50))


def test_truncate(tmp_log_dir):
    log = LogStream(SegmentedLogStorage(tmp_log_dir))
    for i in range(10):
        log.append([wi_record(key=i)])
    log.truncate(6)
    assert [r.position for r in log.reader(0)] == list(range(6))
    assert log.next_position == 6
    # positions are re-assigned after truncation
    assert log.append([wi_record(key=100)]) == 6


def test_commit_listener(tmp_log_dir):
    log = LogStream(SegmentedLogStorage(tmp_log_dir))
    seen = []
    log.on_commit(seen.append)
    log.append([wi_record()], commit=False)
    assert seen == []
    log.set_commit_position(0)
    assert seen == [0]


def test_read_committed_stops_at_commit_position(tmp_log_dir):
    log = LogStream(SegmentedLogStorage(tmp_log_dir))
    log.append([wi_record(key=1)], commit=True)
    log.append([wi_record(key=2)], commit=False)
    reader = LogStreamReader(log, 0)
    records = reader.read_committed()
    assert [r.position for r in records] == [0]


def test_torn_tail_truncated_on_reopen_and_appends_resume(tmp_log_dir):
    """Acceptance regression: a segment truncated mid-record is detected
    via CRC on reopen, cut back to the last whole record, and appends
    RESUME from there — before this, the torn bytes stayed in the file and
    every post-restart append landed after them, unreachable to replay."""
    from zeebe_tpu.runtime.metrics import event_count

    log = LogStream(SegmentedLogStorage(tmp_log_dir))
    for i in range(5):
        log.append([wi_record(key=i)])
    log.flush()
    log.storage.close()
    DiskFaults.tear_log_tail(tmp_log_dir, nbytes=13)

    t0 = event_count("log_torn_tail_truncations")
    reopened = LogStream(SegmentedLogStorage(tmp_log_dir))
    assert event_count("log_torn_tail_truncations") - t0 == 1
    assert reopened.next_position == 4  # last record discarded
    assert reopened.append([wi_record(key=99)]) == 4
    reopened.flush()
    reopened.storage.close()

    # the resumed append is durable and replay sees a contiguous log
    final = LogStream(SegmentedLogStorage(tmp_log_dir))
    assert [r.position for r in final.reader(0)] == [0, 1, 2, 3, 4]
    assert final.record_at(4).key == 99
    final.storage.close()


def test_torn_first_record_recovers_to_empty_log(tmp_log_dir):
    log = LogStream(SegmentedLogStorage(tmp_log_dir))
    log.append([wi_record(key=1)])
    log.flush()
    log.storage.close()
    DiskFaults.tear_log_tail(tmp_log_dir, nbytes=5)

    reopened = LogStream(SegmentedLogStorage(tmp_log_dir))
    assert reopened.next_position == 0
    assert reopened.append([wi_record(key=7)]) == 0
    reopened.flush()
    reopened.storage.close()
    final = LogStream(SegmentedLogStorage(tmp_log_dir))
    assert [r.key for r in final.reader(0)] == [7]
    final.storage.close()


def test_midfile_corruption_flagged_distinctly(tmp_log_dir):
    """A CRC failure with intact frames AFTER it is bitrot, not a torn
    append (a crash leaves at most one partial frame, at the tail). The
    suffix is still discarded — records are positionally sequential, so
    replay cannot skip past the bad one, and raft re-replicates it — but
    the distinct counter + error log tell the operator intact acked data
    was dropped, unlike the benign torn-tail path."""
    import os
    import struct

    from zeebe_tpu.runtime.metrics import event_count

    log = LogStream(SegmentedLogStorage(tmp_log_dir))
    for i in range(5):
        log.append([wi_record(key=i)])
    log.flush()
    log.storage.close()
    segments = sorted(
        n for n in os.listdir(tmp_log_dir)
        if n.startswith("segment-") and n.endswith(".log")
    )
    path = os.path.join(tmp_log_dir, segments[-1])
    with open(path, "r+b") as f:
        data = f.read()
        first_len = struct.unpack_from("<i", data, 16)[0]
        pos = 16 + first_len + 8 + 2  # inside the SECOND record's body
        f.seek(pos)
        f.write(bytes([data[pos] ^ 0xFF]))

    m0 = event_count("log_midfile_corruption")
    t0 = event_count("log_torn_tail_truncations")
    reopened = LogStream(SegmentedLogStorage(tmp_log_dir))
    assert event_count("log_midfile_corruption") - m0 == 1
    assert event_count("log_torn_tail_truncations") - t0 == 1
    # everything from the corrupt record on is discarded; appends resume
    assert reopened.next_position == 1
    assert reopened.append([wi_record(key=99)]) == 1
    reopened.storage.close()


def test_opaque_blocks_survive_reopen_unvalidated(tmp_log_dir):
    """The crc tail scan must never truncate content it cannot parse:
    raw-block users (native-format compat tests write arbitrary bytes)
    reopen with their data intact."""
    storage = SegmentedLogStorage(tmp_log_dir)
    a = storage.append(b"opaque-not-a-frame")
    storage.close()
    reopened = SegmentedLogStorage(tmp_log_dir)
    assert reopened.read(a, 18) == b"opaque-not-a-frame"
    reopened.close()
