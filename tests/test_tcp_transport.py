"""Live TCP transport tests: framing round trips, and a real 4-replica
cluster over localhost sockets with realtime schedulers ordering blocks in
wall-clock time (the production deployment shape, minus TLS).
"""

import socket
import threading
import time

import pytest

from consensus_tpu.config import Configuration
from consensus_tpu.consensus import Consensus
from consensus_tpu.net import TcpComm
from consensus_tpu.runtime import RealtimeScheduler
from consensus_tpu.testing.app import MemWAL, make_request
from consensus_tpu.testing.app import TestApp as PortsApp
from consensus_tpu.types import Decision, Reconfig
from consensus_tpu.wire import HeartBeat, Prepare


def free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def test_tcp_comm_frames_consensus_and_requests():
    ports = free_ports(2)
    addrs = {1: ("127.0.0.1", ports[0]), 2: ("127.0.0.1", ports[1])}
    received = []
    got = threading.Event()

    def on_message_2(sender, payload, is_request):
        received.append((sender, payload, is_request))
        if len(received) >= 2:
            got.set()

    comm1 = TcpComm(1, addrs, lambda *a: None)
    comm2 = TcpComm(2, addrs, on_message_2)
    comm1.start()
    comm2.start()
    try:
        comm1.send_consensus(2, Prepare(view=1, seq=2, digest="abcd"))
        comm1.send_transaction(2, b"raw-request-bytes")
        assert got.wait(timeout=10.0), f"only received {received}"
        kinds = {(s, type(p).__name__, r) for s, p, r in received}
        assert (1, "Prepare", False) in kinds
        assert (1, "bytes", True) in kinds
        assert comm1.nodes() == [1, 2]
    finally:
        comm1.stop()
        comm2.stop()


def test_tcp_send_to_dead_peer_drops_silently():
    ports = free_ports(2)
    addrs = {1: ("127.0.0.1", ports[0]), 2: ("127.0.0.1", ports[1])}
    comm1 = TcpComm(1, addrs, lambda *a: None, reconnect_backoff=0.05)
    comm1.start()
    try:
        # Peer 2 never starts: sends must not raise or block.
        for _ in range(50):
            comm1.send_consensus(2, HeartBeat(view=0, seq=0))
        time.sleep(0.2)
    finally:
        comm1.stop()


class _RealCluster:
    """Shared ledger registry for TestApp.sync across real replicas."""

    def __init__(self):
        self.nodes = {}

    def longest_ledger(self, *, exclude):
        best = []
        for node_id, holder in self.nodes.items():
            if node_id == exclude or not holder.running:
                continue
            ledger = holder.app.ledger
            if len(ledger) > len(best):
                best = ledger
        return list(best)

    def reconfig_of(self, proposal):
        return Reconfig()


class _Holder:
    def __init__(self, app):
        self.app = app
        self.running = True


def test_four_replicas_over_real_tcp_sockets():
    n = 4
    ports = free_ports(n)
    addrs = {i + 1: ("127.0.0.1", ports[i]) for i in range(n)}
    cluster = _RealCluster()
    replicas = {}
    comms = {}
    schedulers = {}

    try:
        for node_id in addrs:
            app = PortsApp(node_id, cluster)
            cluster.nodes[node_id] = _Holder(app)
            rt = RealtimeScheduler()
            rt.start(thread_name=f"replica-{node_id}")
            schedulers[node_id] = rt

            def make_router(nid):
                def route(sender, payload, is_request):
                    consensus = replicas.get(nid)
                    if consensus is None:
                        return
                    if is_request:
                        consensus.handle_request(sender, payload)
                    else:
                        consensus.handle_message(sender, payload)
                return route

            comm = TcpComm(node_id, addrs, make_router(node_id),
                           reconnect_backoff=0.05)
            comm.start()
            comms[node_id] = comm

            consensus = Consensus(
                config=Configuration(
                    self_id=node_id,
                    leader_rotation=False,
                    decisions_per_leader=0,
                    request_batch_max_interval=0.02,
                ),
                scheduler=rt,
                comm=comm,
                application=app,
                assembler=app,
                wal=MemWAL([]),
                signer=app,
                verifier=app,
                request_inspector=app.inspector,
                synchronizer=app,
            )
            consensus.start()
            replicas[node_id] = consensus

        # Order 5 blocks through real sockets, in real time.
        for i in range(5):
            raw = make_request("cli", i)
            for consensus in replicas.values():
                consensus.submit_request(raw)
            deadline = time.time() + 30.0
            while time.time() < deadline:
                if all(
                    len(cluster.nodes[nid].app.ledger) >= i + 1 for nid in replicas
                ):
                    break
                time.sleep(0.02)
            else:
                raise AssertionError(f"block {i} not ordered over TCP")

        ledgers = {
            nid: [d.proposal.digest() for d in cluster.nodes[nid].app.ledger]
            for nid in replicas
        }
        reference = next(iter(ledgers.values()))
        assert all(l == reference for l in ledgers.values()), "ledger divergence"
        for nid in replicas:
            for decision in cluster.nodes[nid].app.ledger:
                assert len(decision.signatures) >= 3
    finally:
        for consensus in replicas.values():
            consensus.stop()
        for comm in comms.values():
            comm.stop()
        for rt in schedulers.values():
            try:
                rt.stop(timeout=2.0)
            except RuntimeError:
                pass


def test_hello_pins_sender_and_rejects_impersonation():
    import struct

    from consensus_tpu.net.transport import _HEADER, _KIND_HELLO

    ports = free_ports(2)
    addrs = {1: ("127.0.0.1", ports[0]), 2: ("127.0.0.1", ports[1])}
    received = []
    comm2 = TcpComm(2, addrs, lambda s, m, r: received.append((s, m)))
    comm2.start()
    try:
        # A raw client claiming sender 1 in HELLO, then forging sender 3 in
        # a later frame: the link must be dropped, nothing dispatched.
        sock = socket.create_connection(("127.0.0.1", ports[1]), timeout=5)
        sock.sendall(_HEADER.pack(0, 1, _KIND_HELLO))
        from consensus_tpu.wire import encode_message

        forged = encode_message(HeartBeat(view=0, seq=0))
        sock.sendall(_HEADER.pack(len(forged), 3, 0) + forged)
        time.sleep(0.3)
        assert received == [], "forged-sender frame was dispatched"
        # And a frame before HELLO is also rejected.
        sock2 = socket.create_connection(("127.0.0.1", ports[1]), timeout=5)
        sock2.sendall(_HEADER.pack(len(forged), 1, 0) + forged)
        time.sleep(0.3)
        assert received == []
        sock.close()
        sock2.close()
    finally:
        comm2.stop()


def test_auth_secret_rejects_wrong_key():
    ports = free_ports(2)
    addrs = {1: ("127.0.0.1", ports[0]), 2: ("127.0.0.1", ports[1])}
    received = []
    got = threading.Event()
    comm2 = TcpComm(2, addrs, lambda s, m, r: (received.append(m), got.set()),
                    auth_secret=b"cluster-secret")
    comm2.start()
    bad = TcpComm(1, addrs, lambda *a: None, auth_secret=b"wrong-secret",
                  reconnect_backoff=0.05)
    bad.start()
    try:
        bad.send_consensus(2, HeartBeat(view=1, seq=1))
        time.sleep(0.4)
        assert received == [], "wrong-secret peer got through"
        bad.stop()

        # Fresh listen port for node 1 (the old listener may still be in
        # teardown); only node 2's address matters for this direction.
        addrs_good = {1: ("127.0.0.1", free_ports(1)[0]), 2: addrs[2]}
        good = TcpComm(1, addrs_good, lambda *a: None, auth_secret=b"cluster-secret")
        good.start()
        try:
            good.send_consensus(2, HeartBeat(view=2, seq=2))
            assert got.wait(5.0), "right-secret peer was rejected"
            assert received[0].view == 2
        finally:
            good.stop()
    finally:
        comm2.stop()


# --------------------------------------------------------------------------
# Deploy-rig hardening regressions: abrupt peer death on both channels.


def test_sync_listener_survives_partial_frames_and_rst():
    """A peer killed mid-frame (kill -9 shape: EOF after a partial header,
    a truncated payload, or a hard RST) must not hang the SyncListener or
    half-apply a chunk — and the listener must keep serving afterwards."""
    import struct as _struct

    from consensus_tpu.sync import LedgerDecisionStore, SyncListener, SyncServer
    from consensus_tpu.sync.transport import TcpSyncTransport
    from consensus_tpu.types import Proposal

    ledger = [
        Decision(proposal=Proposal(payload=f"block-{i}".encode()))
        for i in range(1, 4)
    ]
    listener = SyncListener(SyncServer(LedgerDecisionStore(ledger)))
    try:
        # 1) EOF after a partial u32 header.
        c = socket.create_connection(listener.address, timeout=5)
        c.sendall(b"\x00\x00")
        c.close()
        # 2) Header promises 100 bytes, connection dies after 10 (RST).
        c = socket.create_connection(listener.address, timeout=5)
        c.sendall(_struct.pack(">I", 100) + b"x" * 10)
        c.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     _struct.pack("ii", 1, 0))  # RST on close
        c.close()
        time.sleep(0.1)
        # 3) The listener still answers a well-formed fetch.
        transport = TcpSyncTransport(9, {1: listener.address}, timeout=5.0)
        from consensus_tpu.wire import SyncRequest

        reply = transport.fetch(1, SyncRequest(from_seq=1, to_seq=3))
        assert reply is not None and len(reply.decisions) == 3
    finally:
        listener.close()


def test_sync_fetch_fails_clean_when_server_dies_mid_reply():
    """The client half of the same contract: a server that accepts and then
    closes without a full reply yields None (no hang, no partial chunk)."""
    from consensus_tpu.sync.transport import TcpSyncTransport
    from consensus_tpu.wire import SyncRequest

    server = socket.create_server(("127.0.0.1", 0))
    address = server.getsockname()
    done = threading.Event()

    def half_reply():
        conn, _ = server.accept()
        conn.recv(65536)          # swallow the request
        conn.sendall(b"\x00\x00\x00\x40" + b"y" * 5)  # promise 64, send 5
        conn.close()
        done.set()

    t = threading.Thread(target=half_reply, daemon=True)
    t.start()
    try:
        transport = TcpSyncTransport(9, {1: address}, timeout=2.0)
        t0 = time.monotonic()
        reply = transport.fetch(1, SyncRequest(from_seq=1, to_seq=1))
        assert reply is None
        assert time.monotonic() - t0 < 5.0, "fetch hung instead of failing"
        assert done.wait(2.0)
    finally:
        server.close()


def test_tcp_comm_reconnect_retry_metrics_and_recovery():
    """Satellite-1 hardening: connection-refused gets bounded retries with
    the pinned reconnect counters booked, and frames flow once the peer
    comes up (a supervisor-restarted process reuses its spec'd port)."""
    from consensus_tpu.metrics import InMemoryProvider, Metrics

    ports = free_ports(2)
    addrs = {1: ("127.0.0.1", ports[0]), 2: ("127.0.0.1", ports[1])}
    provider = Metrics(InMemoryProvider())
    comm1 = TcpComm(
        1, addrs, lambda *a: None,
        reconnect_backoff=0.02, connect_attempts=2, send_retries=1,
        metrics=provider.network,
    )
    comm1.start()
    received = []
    got = threading.Event()
    try:
        # Peer 2 is down: the frame rides the bounded retry path and is
        # dropped, with attempts and the drop booked.
        comm1.send_consensus(2, HeartBeat(view=1, seq=1))
        deadline = time.monotonic() + 5.0
        p = provider.provider
        while time.monotonic() < deadline:
            if p.value("net_send_dropped") >= 1:
                break
            time.sleep(0.02)
        assert p.value("net_send_dropped") >= 1
        assert p.value("net_reconnect_attempts") >= 2  # both budgeted tries
        assert p.value("net_reconnect_success") == 0

        # Peer restarts on the SAME port (the deploy restart contract):
        # the next frame reconnects and is delivered.
        comm2 = TcpComm(2, addrs, lambda s, m, r: (received.append(m), got.set()))
        comm2.start()
        try:
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not got.is_set():
                comm1.send_consensus(2, HeartBeat(view=7, seq=7))
                got.wait(0.2)
            assert got.is_set(), "no frame delivered after peer came back"
            assert received[0].view == 7
            assert p.value("net_reconnect_success") >= 1
        finally:
            comm2.stop()
    finally:
        comm1.stop()


def test_tcp_comm_resends_frame_after_midframe_abrupt_close():
    """A peer killed while we were writing (OSError from sendall) must not
    lose the frame: the writer reconnects and re-sends it, booking the
    pinned retry counter — the fire-and-forget drop fires only after the
    retry budget."""
    from consensus_tpu.metrics import InMemoryProvider, Metrics
    from consensus_tpu.testing.faults import FaultPlan

    ports = free_ports(2)
    addrs = {1: ("127.0.0.1", ports[0]), 2: ("127.0.0.1", ports[1])}
    received = []
    got = threading.Event()
    comm2 = TcpComm(2, addrs, lambda s, m, r: (received.append(m), got.set()))
    comm2.start()
    provider = Metrics(InMemoryProvider())
    # net.send.io_error armed for hit 1: the FIRST write dies exactly as if
    # the peer vanished mid-frame; the retry path must deliver it anyway.
    comm1 = TcpComm(
        1, addrs, lambda *a: None,
        reconnect_backoff=0.02, send_retries=2,
        metrics=provider.network,
        fault_plan=FaultPlan("net.send.io_error", on_hit=1),
    )
    comm1.start()
    try:
        comm1.send_consensus(2, HeartBeat(view=3, seq=9))
        assert got.wait(10.0), "frame lost to a mid-frame abrupt close"
        assert received[0].seq == 9
        assert provider.provider.value("net_send_retried") >= 1
        assert provider.provider.value("net_send_dropped") == 0
    finally:
        comm1.stop()
        comm2.stop()


def test_tcp_comm_listener_pause_resume():
    """The deploy chaos verb: pause_listener drops the listen port (inbound
    peers see refused + severed links), resume_listener rebinds the same
    address and frames flow again."""
    ports = free_ports(2)
    addrs = {1: ("127.0.0.1", ports[0]), 2: ("127.0.0.1", ports[1])}
    received = []
    comm2 = TcpComm(2, addrs, lambda s, m, r: received.append(m))
    comm2.start()
    comm1 = TcpComm(1, addrs, lambda *a: None, reconnect_backoff=0.02,
                    connect_attempts=1)
    comm1.start()
    try:
        comm1.send_consensus(2, HeartBeat(view=1, seq=1))
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not received:
            time.sleep(0.02)
        assert received, "baseline frame not delivered"

        comm2.pause_listener()
        time.sleep(0.1)
        n = len(received)
        comm1.send_consensus(2, HeartBeat(view=2, seq=2))
        time.sleep(0.5)
        assert len(received) == n, "frame delivered through a dropped listener"

        comm2.resume_listener()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and len(received) == n:
            comm1.send_consensus(2, HeartBeat(view=3, seq=3))
            time.sleep(0.2)
        assert len(received) > n, "no frames after listener resume"
        assert received[-1].view == 3
    finally:
        comm1.stop()
        comm2.stop()


def test_tcp_comm_resume_listener_failure_stays_healable():
    """A failed resume (port stolen during the pause window) must NOT
    clear the paused flag: the next resume_listener retries the rebind
    instead of silently no-opping into a permanent inbound partition."""
    ports = free_ports(2)
    addrs = {1: ("127.0.0.1", ports[0]), 2: ("127.0.0.1", ports[1])}
    received = []
    comm2 = TcpComm(2, addrs, lambda s, m, r: received.append(m))
    comm2.start()
    comm1 = TcpComm(1, addrs, lambda *a: None, reconnect_backoff=0.02,
                    connect_attempts=1)
    comm1.start()
    try:
        comm2.pause_listener()
        comm2._rebind_attempts = 3  # keep the failing resume fast
        comm2._rebind_delay = 0.01

        def stolen_port():
            raise OSError("port stolen during the pause window")

        real_bind = comm2._bind_listener
        comm2._bind_listener = stolen_port
        try:
            with pytest.raises(OSError):
                comm2.resume_listener()
        finally:
            comm2._bind_listener = real_bind
        # The paused flag survived the failure, so this retry (the chaos
        # heal re-issuing net_resume) actually rebinds and heals.
        comm2.resume_listener()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not received:
            comm1.send_consensus(2, HeartBeat(view=7, seq=7))
            time.sleep(0.2)
        assert received, "listener never healed after a failed resume"
        assert received[-1].view == 7
    finally:
        comm1.stop()
        comm2.stop()


# --- a backlog leaves in a few writes, and a drop is still one frame --------
#
# Found on the chip (PERF.md section 6, PR 33): a replica whose listener had
# been away got the client's backlog as thousands of 150-byte segments, its
# fresh connection crawled at 10 requests a second for 11 s and more, and
# what arrived then was stale past the pool's dedup horizon.


class _RecordingSock:
    def __init__(self, fail_first=0):
        self.writes, self._fail = [], fail_first

    def sendall(self, data):
        if self._fail:
            self._fail -= 1
            raise OSError("peer went away mid-write")
        self.writes.append(bytes(data))

    def close(self):
        pass


def _peer(socks, **comm_kw):
    """A writer whose connections are the given fakes, in turn; ``None`` is
    a connect budget that ran out."""
    from consensus_tpu.net import transport

    comm = TcpComm(1, {1: ("127.0.0.1", 1), 2: ("127.0.0.1", 2)},
                   lambda *a: None, send_queue_depth=10_000, **comm_kw)
    peer = transport._Peer(comm, 2, ("127.0.0.1", 2))
    socks = list(socks)

    def ensure_connected():
        if peer._sock is None:
            peer._sock = socks.pop(0)
        return peer._sock

    peer._ensure_connected = ensure_connected
    return comm, peer


def _run_writer(comm, peer, until, timeout=5.0):
    thread = threading.Thread(target=peer._writer_loop, daemon=True)
    thread.start()
    deadline = time.monotonic() + timeout
    while not until() and time.monotonic() < deadline:
        time.sleep(0.005)
    comm._stopped.set()
    thread.join(timeout=2.0)
    assert until()


def test_writer_sends_a_backlog_in_few_writes_in_order():
    from consensus_tpu.net import transport

    sock = _RecordingSock()
    comm, peer = _peer([sock])
    frames = [b"%05d" % i + bytes(145) for i in range(1600)]  # a 0.6 s backlog
    for frame in frames:
        peer.enqueue(frame)
    _run_writer(comm, peer, lambda: sum(map(len, sock.writes)) == 1600 * 150)
    assert b"".join(sock.writes) == b"".join(frames)
    # whole frames only, each write as full as the cap allows
    assert all(len(w) % 150 == 0 for w in sock.writes)
    assert len(sock.writes) <= -(-1600 * 150 // transport._COALESCE_BYTES) + 1
    assert max(map(len, sock.writes)) < transport._COALESCE_BYTES + 150


def test_writer_sends_a_lone_frame_at_once_and_a_huge_one_alone():
    from consensus_tpu.net import transport

    sock = _RecordingSock()
    comm, peer = _peer([sock])
    huge = bytes(transport._COALESCE_BYTES + 1)
    peer.enqueue(b"lone")
    thread = threading.Thread(target=peer._writer_loop, daemon=True)
    thread.start()
    _wait(lambda: len(sock.writes) >= 1)
    assert sock.writes == [b"lone"]  # nothing waits for company
    peer.enqueue(huge)
    _wait(lambda: len(sock.writes) >= 2)
    peer.enqueue(b"after")
    _wait(lambda: len(sock.writes) >= 3)
    comm._stopped.set()
    thread.join(timeout=2.0)
    assert sock.writes == [b"lone", huge, b"after"]


def _wait(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert cond()


def test_write_cut_midway_is_sent_again_whole_over_a_new_connection():
    broken, fresh = _RecordingSock(fail_first=1), _RecordingSock()
    comm, peer = _peer([broken, fresh])
    frames = [b"%03d" % i for i in range(40)]
    for frame in frames:
        peer.enqueue(frame)
    _run_writer(comm, peer, lambda: b"".join(fresh.writes) == b"".join(frames))
    assert broken.writes == []


def test_exhausted_budget_drops_one_frame_and_keeps_the_rest():
    """A peer that is away costs one frame a connect budget, as before the
    writes were joined: what was queued behind it is not thrown away with
    it."""
    sock = _RecordingSock()
    comm, peer = _peer([None, None, sock])
    frames = [b"%03d" % i for i in range(40)]
    for frame in frames:
        peer.enqueue(frame)
    _run_writer(comm, peer, lambda: b"".join(sock.writes) == b"".join(frames[2:]))
