"""WAL tests: round trips, segment rollover, truncation, corruption
detection, and the torn-write property test (truncate the tail segment at
every byte offset, repair, and confirm a valid prefix survives).

Parity model: reference pkg/wal/writeaheadlog_test.go (temp-dir file I/O,
CRC corruption injection, torn-write repair, segment rollover).
"""

import os

import pytest

from consensus_tpu.wal import (
    QUARANTINE_DIRNAME,
    CorruptLogError,
    WALError,
    WalScrubber,
    WriteAheadLog,
    initialize_and_read_all,
    quarantine,
    repair,
)


def entries_of(n, size=24):
    return [bytes([i % 256]) * size for i in range(1, n + 1)]


def test_create_append_read_round_trip(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d)
    data = entries_of(10)
    for e in data:
        wal.append(e)
    assert wal.read_all() == data
    wal.close()
    # Reopen and continue appending.
    wal2 = WriteAheadLog.open_(d)
    wal2.append(b"after-reopen")
    assert wal2.read_all() == data + [b"after-reopen"]
    wal2.close()


def test_create_refuses_existing_log(tmp_path):
    d = str(tmp_path / "wal")
    WriteAheadLog.create(d).close()
    with pytest.raises(WALError):
        WriteAheadLog.create(d)


def test_segment_rollover_preserves_entries(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d, segment_max_bytes=256)
    data = entries_of(40)
    for e in data:
        wal.append(e)
    segments = [f for f in os.listdir(d) if f.endswith(".wal")]
    assert len(segments) > 3, "expected multiple segments"
    assert wal.read_all() == data
    wal.close()
    assert WriteAheadLog.open_(d).read_all() == data


def test_truncate_to_drops_older_segments(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d, segment_max_bytes=256)
    for e in entries_of(30):
        wal.append(e)
    before = len([f for f in os.listdir(d) if f.endswith(".wal")])
    wal.append(b"stable-point", truncate_to=True)
    after = len([f for f in os.listdir(d) if f.endswith(".wal")])
    assert after < before
    # A restore point retires everything before it — even records that share
    # its segment (reference pkg/wal/writeaheadlog.go:549-551).
    assert wal.read_all() == [b"stable-point"]
    wal.append(b"next")
    assert wal.read_all() == [b"stable-point", b"next"]
    wal.close()
    # Reopened log reads the same surviving suffix.
    assert WriteAheadLog.open_(d).read_all() == [b"stable-point", b"next"]


def test_bit_flip_detected(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d)
    for e in entries_of(5):
        wal.append(e)
    wal.close()
    seg = sorted(f for f in os.listdir(d) if f.endswith(".wal"))[0]
    path = os.path.join(d, seg)
    buf = bytearray(open(path, "rb").read())
    buf[len(buf) // 2] ^= 0xFF
    open(path, "wb").write(bytes(buf))
    with pytest.raises(CorruptLogError):
        WriteAheadLog(d).read_all()


def test_torn_write_repair_at_every_offset(tmp_path):
    # Property test: crash mid-write at any byte boundary must leave a log
    # that repairs to an intact prefix of what was appended.
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d)
    data = entries_of(6, size=10)
    for e in data:
        wal.append(e)
    wal.close()
    seg = sorted(f for f in os.listdir(d) if f.endswith(".wal"))[-1]
    path = os.path.join(d, seg)
    full = open(path, "rb").read()

    for cut in range(len(full)):
        open(path, "wb").write(full[:cut])
        wal2, entries = initialize_and_read_all(d)
        wal2.close()
        assert entries == data[: len(entries)], f"not a prefix at cut={cut}"
        # The repaired log must accept new appends.
        wal3 = WriteAheadLog.open_(d)
        wal3.append(b"post-repair")
        assert wal3.read_all() == entries + [b"post-repair"]
        wal3.close()
        # Restore for the next iteration.
        for f in os.listdir(d):
            if f.endswith(".bak"):
                os.unlink(os.path.join(d, f))
        open(path, "wb").write(full)


def test_torn_write_across_segments(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d, segment_max_bytes=200)
    data = entries_of(12, size=16)
    for e in data:
        wal.append(e)
    wal.close()
    segs = sorted(f for f in os.listdir(d) if f.endswith(".wal"))
    assert len(segs) >= 2
    # Tear the last segment down to one byte.
    last = os.path.join(d, segs[-1])
    open(last, "r+b").truncate(1)
    wal2, entries = initialize_and_read_all(d)
    assert entries == data[: len(entries)]
    assert len(entries) > 0
    wal2.close()


def test_repair_noop_on_healthy_log(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d)
    for e in entries_of(3):
        wal.append(e)
    wal.close()
    repair(d)
    assert WriteAheadLog.open_(d).read_all() == entries_of(3)


def test_initialize_creates_fresh_log(tmp_path):
    d = str(tmp_path / "wal")
    wal, entries = initialize_and_read_all(d)
    assert entries == []
    wal.append(b"x")
    assert wal.read_all() == [b"x"]
    wal.close()
    wal2, entries2 = initialize_and_read_all(d)
    assert entries2 == [b"x"]
    wal2.close()


def test_append_after_close_fails(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d)
    wal.close()
    with pytest.raises(WALError):
        wal.append(b"x")


def test_corrupt_anchor_length_detected_not_crash(tmp_path):
    # A bit-flip in an anchor's length field must surface as CorruptLogError
    # (repairable), not a raw struct.error.
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d)
    wal.append(b"x" * 8)
    wal.close()
    seg = sorted(f for f in os.listdir(d) if f.endswith(".wal"))[0]
    path = os.path.join(d, seg)
    buf = bytearray(open(path, "rb").read())
    buf[0] = 2  # anchor payload length 6 -> 2
    open(path, "wb").write(bytes(buf))
    with pytest.raises(CorruptLogError):
        WriteAheadLog(d).read_all()


def test_non_tail_corruption_refuses_auto_repair(tmp_path):
    # Damage in a fully-fsynced earlier segment is data loss, not a torn
    # tail: repair must refuse rather than silently discard durable records.
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d, segment_max_bytes=200)
    for e in entries_of(12, size=16):
        wal.append(e)
    wal.close()
    segs = sorted(f for f in os.listdir(d) if f.endswith(".wal"))
    assert len(segs) >= 3
    mid = os.path.join(d, segs[1])
    buf = bytearray(open(mid, "rb").read())
    buf[len(buf) // 2] ^= 0xFF
    open(mid, "wb").write(bytes(buf))
    with pytest.raises(WALError):
        repair(d)


def test_group_commit_batches_fsyncs_and_fires_callbacks(tmp_path):
    from unittest import mock

    from consensus_tpu.runtime import SimScheduler

    s = SimScheduler()
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d, group_commit_window=0.002, scheduler=s)
    durable = []
    with mock.patch("os.fsync") as fsync:
        fsync.reset_mock()
        for i in range(10):
            wal.append(b"e%d" % i, on_durable=lambda i=i: durable.append(i))
        assert durable == []  # nothing durable before the window closes
        group_syncs_before = fsync.call_count
        s.advance(0.002)
        # One fsync covered all ten appends.
        assert fsync.call_count == group_syncs_before + 1
    assert durable == list(range(10))
    # Records are intact and readable.
    assert wal.read_all() == [b"e%d" % i for i in range(10)]
    wal.close()


def test_group_commit_close_flushes_pending(tmp_path):
    from consensus_tpu.runtime import SimScheduler

    s = SimScheduler()
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d, group_commit_window=1.0, scheduler=s)
    durable = []
    wal.append(b"x", on_durable=lambda: durable.append("x"))
    wal.close()  # window never elapsed: close must make it durable
    assert durable == ["x"]
    assert WriteAheadLog.open_(d).read_all() == [b"x"]


def test_default_mode_callback_fires_synchronously(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d)
    durable = []
    wal.append(b"x", on_durable=lambda: durable.append("x"))
    assert durable == ["x"]
    wal.close()


def test_group_commit_truncate_flushes_before_dropping_history(tmp_path):
    from unittest import mock

    from consensus_tpu.runtime import SimScheduler

    s = SimScheduler()
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d, segment_max_bytes=200,
                               group_commit_window=1.0, scheduler=s)
    for e in entries_of(12, size=16):
        wal.append(e)
    calls = []
    real_fsync = os.fsync
    with mock.patch("os.fsync", side_effect=lambda fd: (calls.append("fsync"), real_fsync(fd))):
        with mock.patch("os.unlink", side_effect=lambda p: calls.append("unlink")):
            wal.append(b"restore-point", truncate_to=True)
    assert "fsync" in calls and "unlink" in calls
    assert calls.index("fsync") < calls.index("unlink"), (
        "history deleted before the restore point was durable"
    )
    wal.close()


def test_group_commit_config_validation(tmp_path):
    import pytest as _pytest

    with _pytest.raises(ValueError):
        WriteAheadLog(str(tmp_path / "a"), group_commit_window=0.1)
    with _pytest.raises(ValueError):
        WriteAheadLog(str(tmp_path / "b"), group_commit_window=0.1,
                      scheduler=object(), sync=False)
    d = str(tmp_path / "c")
    wal = WriteAheadLog.create(d, sync=False)
    with _pytest.raises(WALError):
        wal.append(b"x", on_durable=lambda: None)


def test_group_commit_waiter_exception_does_not_strand_others(tmp_path):
    from consensus_tpu.runtime import SimScheduler

    s = SimScheduler()
    wal = WriteAheadLog.create(str(tmp_path / "wal"),
                               group_commit_window=0.01, scheduler=s)
    fired = []
    wal.append(b"a", on_durable=lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    wal.append(b"b", on_durable=lambda: fired.append("b"))
    wal.append(b"c", on_durable=lambda: fired.append("c"))
    s.advance(0.01)
    assert fired == ["b", "c"]
    wal.close()


def test_group_commit_truncate_cancels_stale_timer(tmp_path):
    from unittest import mock

    from consensus_tpu.runtime import SimScheduler

    s = SimScheduler()
    wal = WriteAheadLog.create(str(tmp_path / "wal"),
                               group_commit_window=0.01, scheduler=s)
    wal.append(b"x")
    wal.append(b"checkpoint", truncate_to=True)  # eager flush cancels timer
    real_fsync = os.fsync
    with mock.patch("os.fsync", side_effect=real_fsync) as fsync:
        s.advance(0.05)  # the stale timer must NOT fire an extra fsync
        assert fsync.call_count == 0
    wal.close()


def test_group_commit_fsync_failure_retries_without_false_durability(tmp_path):
    from unittest import mock

    from consensus_tpu.runtime import SimScheduler

    s = SimScheduler()
    wal = WriteAheadLog.create(str(tmp_path / "wal"),
                               group_commit_window=0.01, scheduler=s)
    durable = []
    wal.append(b"x", on_durable=lambda: durable.append("x"))
    real_fsync = os.fsync
    calls = {"n": 0}

    def flaky(fd):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError(28, "No space left on device")
        return real_fsync(fd)

    with mock.patch("os.fsync", side_effect=flaky):
        s.advance(0.01)
        assert durable == []  # failed fsync must not report durability
        s.advance(0.02)  # retry window
    assert durable == ["x"]
    wal.close()


def test_group_commit_cluster_defers_broadcasts_until_durable(tmp_path):
    # End to end: replicas on REAL group-commit WALs still order correctly —
    # the protocol's sends ride on_durable, so nothing is ever said that is
    # not remembered (persist-before-broadcast under batched fsyncs).
    from consensus_tpu.consensus import Consensus
    from consensus_tpu.testing import Cluster
    from consensus_tpu.testing.app import make_request

    cluster = Cluster(4)
    # Swap every node's WAL for a real group-commit log on disk.
    for node_id, node in cluster.nodes.items():
        wal_dir = str(tmp_path / f"wal-{node_id}")

        def start_with_real_wal(node=node, wal_dir=wal_dir):
            comm = cluster.network.register(node.node_id, node._on_message)
            wal = WriteAheadLog.create(
                wal_dir, group_commit_window=0.002, scheduler=cluster.scheduler
            )
            node.consensus = Consensus(
                config=node.config,
                scheduler=cluster.scheduler,
                comm=comm,
                application=node.app,
                assembler=node.app,
                wal=wal,
                signer=node.app,
                verifier=node.app,
                request_inspector=node.app.inspector,
                synchronizer=node.app,
            )
            node.consensus.start()
            node.running = True

        node.start = start_with_real_wal
    cluster.start()
    for i in range(3):
        cluster.submit_to_all(make_request("gc", i))
        assert cluster.run_until_ledger(i + 1, max_time=300.0), f"block {i} stalled"
    cluster.assert_ledgers_consistent()


# --- explicit open contract, repair idempotence -----------------------------


def test_open_default_raises_on_torn_tail(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d)
    for e in entries_of(4):
        wal.append(e)
    wal.close()
    seg = sorted(f for f in os.listdir(d) if f.endswith(".wal"))[-1]
    path = os.path.join(d, seg)
    full = open(path, "rb").read()
    open(path, "wb").write(full[:-5])
    # repair=False (the default) surfaces the tear to the caller.
    with pytest.raises(CorruptLogError):
        WriteAheadLog.open_(d)
    # repair=True chops the tail and opens the intact prefix.
    wal2 = WriteAheadLog.open_(d, repair=True)
    entries = wal2.read_all()
    assert entries == entries_of(4)[: len(entries)]
    wal2.append(b"post-repair")
    assert wal2.read_all()[-1] == b"post-repair"
    wal2.close()


def test_open_repair_still_refuses_non_tail_corruption(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d, segment_max_bytes=200)
    for e in entries_of(12, size=16):
        wal.append(e)
    wal.close()
    segs = sorted(f for f in os.listdir(d) if f.endswith(".wal"))
    mid = os.path.join(d, segs[1])
    buf = bytearray(open(mid, "rb").read())
    buf[len(buf) // 2] ^= 0xFF
    open(mid, "wb").write(bytes(buf))
    # Durable records damaged at rest: repair=True must NOT silently chop.
    with pytest.raises(WALError):
        WriteAheadLog.open_(d, repair=True)


def test_repair_idempotent_with_two_consecutive_torn_frames(tmp_path):
    # Regression: a crash can leave MORE than one partial frame at the tail
    # (a torn group write).  One repair pass must remove the whole damaged
    # suffix, and a second pass must be a no-op — not find fresh damage.
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d)
    for e in entries_of(4):
        wal.append(e)
    wal.close()
    seg = sorted(f for f in os.listdir(d) if f.endswith(".wal"))[-1]
    path = os.path.join(d, seg)
    full = open(path, "rb").read()
    # Fabricate two torn frames: a header claiming more payload than exists,
    # followed by a second truncated header fragment.
    import struct as _struct

    torn_a = _struct.pack("<II", 64, 0xDEAD) + b"\x01\x00partial"
    torn_b = _struct.pack("<I", 99)[:3]
    with open(path, "ab") as f:
        f.write(torn_a + torn_b)
    repair(d)
    assert WriteAheadLog.open_(d).read_all() == entries_of(4)
    before = open(path, "rb").read()
    repair(d)  # idempotent: second pass finds a healthy log
    assert open(path, "rb").read() == before
    assert WriteAheadLog.open_(d).read_all() == entries_of(4)
    # The pre-repair bytes were preserved for forensics.
    assert any(f.endswith(".bak") for f in os.listdir(d))


def test_initialize_and_read_all_repairs_double_tear(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d)
    for e in entries_of(3):
        wal.append(e)
    wal.close()
    seg = sorted(f for f in os.listdir(d) if f.endswith(".wal"))[-1]
    path = os.path.join(d, seg)
    import struct as _struct

    with open(path, "ab") as f:
        f.write(_struct.pack("<II", 1 << 20, 0) + b"\x01\x00x")
        f.write(b"\x07\x00")
    wal2, entries = initialize_and_read_all(d)
    assert entries == entries_of(3)
    wal2.append(b"alive")
    assert wal2.read_all() == entries_of(3) + [b"alive"]
    wal2.close()


# --- quarantine -------------------------------------------------------------


def test_quarantine_preserves_mid_segment_intact_prefix(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d)
    for e in entries_of(6):
        wal.append(e)
    wal.close()
    seg = sorted(f for f in os.listdir(d) if f.endswith(".wal"))[0]
    path = os.path.join(d, seg)
    buf = bytearray(open(path, "rb").read())
    # Flip a byte inside the LAST record's payload (entries are 24-byte
    # frames padded to 8: the final 6 bytes are CRC-exempt padding, so
    # target 10 bytes back from the end) — a whole-record prefix precedes
    # the damage.
    buf[len(buf) - 10] ^= 0x10
    open(path, "wb").write(bytes(buf))
    probe = WriteAheadLog(d)
    with pytest.raises(CorruptLogError) as exc:
        probe.read_all()
    moved = quarantine(d, exc.value)
    assert moved, "damaged suffix should have been set aside"
    qdir = os.path.join(d, QUARANTINE_DIRNAME)
    assert sorted(os.listdir(qdir)) == sorted(moved)
    # The intact prefix survived in place and the log reopens cleanly.
    reopened = WriteAheadLog.open_(d)
    entries = reopened.read_all()
    assert entries == entries_of(6)[: len(entries)]
    assert len(entries) >= 1
    reopened.close()


def test_boot_quarantine_books_metrics_exactly_once(tmp_path):
    from consensus_tpu.metrics import InMemoryProvider, Metrics

    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d, segment_max_bytes=200)
    for e in entries_of(12, size=16):
        wal.append(e)
    wal.close()
    segs = sorted(f for f in os.listdir(d) if f.endswith(".wal"))
    mid = os.path.join(d, segs[1])
    buf = bytearray(open(mid, "rb").read())
    buf[len(buf) // 2] ^= 0xFF
    open(mid, "wb").write(bytes(buf))
    # Non-tail corruption + quarantine_corrupt: boot survives with amnesia
    # recorded instead of raising.
    wal2, entries = initialize_and_read_all(d, quarantine_corrupt=True)
    assert wal2.recovery is not None
    assert wal2.recovery.intact_entries == len(entries)
    # It comes back on a non-empty STRICT prefix: the damaged suffix is
    # set aside, what stood before it is kept.
    assert 0 < len(entries) < 12
    assert entries == entries_of(12, size=16)[: len(entries)]
    # Metrics attach AFTER boot (the facade wires them later): the pinned
    # quarantine counter books once, and only once, on attach.
    metrics = Metrics(InMemoryProvider())
    wal2.attach_metrics(metrics.wal)
    assert metrics.wal.quarantines.value == 1
    wal2.attach_metrics(metrics.wal)
    assert metrics.wal.quarantines.value == 1
    wal2.close()


def test_boot_without_quarantine_flag_still_raises(tmp_path):
    d = str(tmp_path / "wal")
    wal = WriteAheadLog.create(d, segment_max_bytes=200)
    for e in entries_of(12, size=16):
        wal.append(e)
    wal.close()
    segs = sorted(f for f in os.listdir(d) if f.endswith(".wal"))
    mid = os.path.join(d, segs[1])
    buf = bytearray(open(mid, "rb").read())
    buf[len(buf) // 2] ^= 0xFF
    open(mid, "wb").write(bytes(buf))
    with pytest.raises(WALError):
        initialize_and_read_all(d)


# --- the scrubber -----------------------------------------------------------


def test_scrubber_clean_passes_book_runs_and_records(tmp_path):
    from consensus_tpu.metrics import InMemoryProvider, Metrics
    from consensus_tpu.runtime import SimScheduler

    s = SimScheduler()
    d = str(tmp_path / "wal")
    wal, _ = initialize_and_read_all(d)
    for e in entries_of(5):
        wal.append(e)
    metrics = Metrics(InMemoryProvider())
    scrubber = WalScrubber(wal, s, interval=10.0, metrics=metrics.wal)
    scrubber.start()
    s.advance(35.0)
    assert scrubber.runs == 3  # one pass per elapsed interval
    assert metrics.wal.scrub_runs.value == 3
    assert metrics.wal.scrub_records.value == 15
    assert metrics.wal.scrub_corruptions.value == 0
    scrubber.stop()
    s.advance(50.0)
    assert scrubber.runs == 3  # stopped: no further passes
    wal.close()


def test_scrubber_detection_invokes_callback_once_per_pass(tmp_path):
    from consensus_tpu.runtime import SimScheduler

    s = SimScheduler()
    d = str(tmp_path / "wal")
    wal, _ = initialize_and_read_all(d)
    for e in entries_of(5):
        wal.append(e)
    seg = sorted(f for f in os.listdir(d) if f.endswith(".wal"))[0]
    path = os.path.join(d, seg)
    buf = bytearray(open(path, "rb").read())
    buf[len(buf) // 2] ^= 0x01
    open(path, "wb").write(bytes(buf))
    detections = []
    scrubber = WalScrubber(wal, s, interval=1.0,
                           on_corruption=detections.append)
    err = scrubber.scrub_now()
    assert err is not None and detections == [err]
    # The callback is expected to quarantine; doing so makes later passes
    # clean again.
    wal.quarantine_corrupt(err)
    assert scrubber.scrub_now() is None
    assert len(detections) == 1
    wal.close()


def test_scrubber_rejects_nonpositive_interval(tmp_path):
    from consensus_tpu.runtime import SimScheduler

    d = str(tmp_path / "wal")
    wal, _ = initialize_and_read_all(d)
    with pytest.raises(ValueError):
        WalScrubber(wal, SimScheduler(), interval=0.0)
    wal.close()
