"""Listener hardening against byzantine peers (ISSUE 20).

Three layers:

* **ListenerGuard / recv_exact units** — quotas, strikes, temporary bans
  with expiry forgiveness, handshake-timeout-is-not-a-strike, and the
  cap-check-before-allocate regression: a peer claiming a 2^31-byte frame
  costs memory proportional to bytes actually SENT, never to the claim.
* **Four-family adversarial batteries** — the raw-TCP
  :class:`~consensus_tpu.testing.adversary.AdversarialPeer` drives its
  full vocabulary against real comm / sync / control / sidecar listeners;
  each defense books its pinned metric EXACTLY once per provoked event
  and honest traffic keeps flowing before, during, and after.
* **HELLO-pinning reconnection races** — a banned peer reconnecting
  mid-ban is refused at accept; an honest successor on the recycled
  address gets service after expiry with strikes forgiven.

The ``wire_abuse`` detector and sim-chaos ``net_abuse`` arm are pinned
here too (edge-trigger unit + end-to-end sim run + RNG-neutral off-arm).
"""

import socket
import struct
import threading
import time
import tracemalloc

import numpy as np
import pytest

from consensus_tpu.config import ObsConfig
from consensus_tpu.deploy.control import ControlServer
from consensus_tpu.metrics import (
    NET_CONN_REJECTED_KEY,
    NET_HANDSHAKE_TIMEOUT_KEY,
    NET_MALFORMED_KEY,
    NET_PEER_BANNED_KEY,
    InMemoryProvider,
    MetricsNetwork,
)
from consensus_tpu.net import TcpComm
from consensus_tpu.net.framing import (
    MALFORMED_KINDS,
    FrameReader,
    FrameStall,
    ListenerGuard,
    recv_exact,
)
from consensus_tpu.net.sidecar import SidecarVerifierClient, VerifySidecarServer
from consensus_tpu.sync import (
    LedgerDecisionStore,
    SyncListener,
    SyncServer,
    TcpSyncTransport,
)
from consensus_tpu.testing.adversary import (
    HUGE_LENGTH,
    STYLE_BATTERIES,
    AdversarialPeer,
    control_probe_reply,
)
from consensus_tpu.testing.chaos import (
    ADVERSARIAL_NET_KINDS,
    ChaosAction,
    ChaosEngine,
    ChaosSchedule,
)
from consensus_tpu.wire import HeartBeat, SyncRequest, SyncSnapshotMeta
from test_sync_subsystem import build_chain

SECRET = b"hardening-secret"


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _metered_guard(**kw):
    provider = InMemoryProvider()
    guard = ListenerGuard(metrics=MetricsNetwork(provider), **kw)
    return guard, provider


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# --- ListenerGuard units -----------------------------------------------------


def test_guard_quotas_per_peer_and_global():
    guard = ListenerGuard(max_conns_per_peer=2, max_conns_total=3)
    assert guard.admit("a") and guard.admit("a")
    assert not guard.admit("a")  # peer quota
    assert guard.admit("b")
    assert not guard.admit("c")  # global quota
    assert guard.stats.rejected == 2
    guard.release("a")
    assert guard.admit("c")  # slot returned


def test_guard_strikes_ban_and_expiry_forgives():
    clock = _Clock()
    guard, provider = _metered_guard(
        strike_limit=2, ban_seconds=5.0, clock=clock
    )
    bans = []
    guard.on_ban = lambda addr, kind: bans.append((addr, kind))
    assert guard.strike("p", "oversized") is False
    assert guard.strike("p", "stall") is True  # limit crossed
    assert guard.is_banned("p")
    assert bans == [("p", "stall")]
    assert not guard.admit("p")  # mid-ban reconnect refused
    assert (guard.stats.malformed, guard.stats.bans, guard.stats.rejected) \
        == (2, 1, 1)
    # Expiry forgives: the next admit succeeds AND strikes are cleared,
    # so one later strike does not instantly re-ban.
    clock.t = 6.0
    assert not guard.is_banned("p")
    assert guard.admit("p")
    assert guard.strike("p", "garbage") is False
    # Triple booking went through the pinned metrics exactly once each.
    dump = provider.dump()
    assert dump[f"{NET_MALFORMED_KEY}{{oversized}}"]["value"] == 1
    assert dump[f"{NET_MALFORMED_KEY}{{stall}}"]["value"] == 1
    assert dump[NET_PEER_BANNED_KEY]["value"] == 1
    assert dump[NET_CONN_REJECTED_KEY]["value"] == 1


def test_guard_handshake_timeout_is_not_a_strike():
    guard, provider = _metered_guard(strike_limit=1)
    for _ in range(5):
        guard.handshake_timed_out("p")
    assert guard.stats.handshake_timeouts == 5
    assert guard.stats.malformed == 0 and guard.stats.bans == 0
    assert not guard.is_banned("p")  # connect-and-idle never escalates
    assert provider.dump()[NET_HANDSHAKE_TIMEOUT_KEY]["value"] == 5


def test_guard_rejects_unknown_strike_kind():
    guard = ListenerGuard()
    with pytest.raises(ValueError):
        guard.strike("p", "not_a_kind")
    assert set(MALFORMED_KINDS) >= {"oversized", "bad_hello", "stall", "garbage"}


def test_guard_on_ban_hook_failure_is_swallowed():
    def boom(addr, kind):
        raise RuntimeError("flight recorder down")

    guard = ListenerGuard(strike_limit=1, on_ban=boom)
    assert guard.strike("p", "garbage") is True  # ban still lands
    assert guard.is_banned("p")


# --- recv_exact: cap-check-before-allocate + slow-loris ----------------------


def test_recv_exact_huge_claim_allocates_only_received_bytes():
    """The satellite-2 regression: a 2^31-byte claimed header.  The old
    per-listener copies called ``conn.recv(claimed)``, which CPython turns
    into a 2 GiB buffer allocation for 4 attacker bytes.  The shared
    reader's allocation must track bytes RECEIVED."""
    a, b = socket.socketpair()
    try:
        a.sendall(b"x" * 100)
        a.close()
        tracemalloc.start()
        out = recv_exact(b, HUGE_LENGTH)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert out is None  # EOF long before 2 GiB
        assert peak < 8 * 1024 * 1024, f"allocated {peak} bytes for a claim"
    finally:
        b.close()


def test_recv_exact_midframe_stall_raises_framestall():
    a, b = socket.socketpair()
    try:
        a.sendall(b"\x00\x01")
        with pytest.raises(FrameStall) as exc:
            recv_exact(b, 10, progress_timeout=0.2)
        assert exc.value.received == 2  # provably mid-frame
    finally:
        a.close()
        b.close()


def test_recv_exact_eof_returns_none():
    a, b = socket.socketpair()
    a.close()
    try:
        assert recv_exact(b, 4) is None
    finally:
        b.close()


# --- FrameReader: recv_exact's rules through one receive buffer --------------


class _CountingConn:
    """A blocking socket whose ``recv`` calls are counted."""

    def __init__(self, sock):
        self.sock, self.recvs = sock, 0

    def recv(self, n):
        self.recvs += 1
        return self.sock.recv(n)

    def settimeout(self, t):
        self.sock.settimeout(t)


def test_frame_reader_takes_back_to_back_frames_with_one_recv():
    frames = [struct.pack(">I", len(p)) + p for p in (b"a" * 140, b"", b"b" * 7)]
    a, b = socket.socketpair()
    try:
        a.sendall(b"".join(frames))
        conn = _CountingConn(b)
        reader = FrameReader(conn)
        got = []
        for _ in frames:
            (length,) = struct.unpack(">I", reader.read(4))
            got.append(reader.read(length))
        assert got == [b"a" * 140, b"", b"b" * 7]
        assert conn.recvs == 1  # recv_exact: two a frame
    finally:
        a.close()
        b.close()


def test_frame_reader_huge_claim_allocates_only_received_bytes():
    a, b = socket.socketpair()
    try:
        a.sendall(b"x" * 100)
        a.close()
        tracemalloc.start()
        out = FrameReader(b).read(HUGE_LENGTH)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert out is None  # EOF long before 2 GiB
        assert peak < 8 * 1024 * 1024, f"allocated {peak} bytes for a claim"
    finally:
        b.close()


def test_frame_reader_midframe_stall_counts_the_bytes_of_its_read():
    a, b = socket.socketpair()
    try:
        a.sendall(b"\x00\x01\x02\x03\x04\x05")
        reader = FrameReader(b)
        assert reader.read(4, progress_timeout=0.2) == b"\x00\x01\x02\x03"
        with pytest.raises(FrameStall) as exc:
            reader.read(10, progress_timeout=0.2)
        assert exc.value.received == 2  # the buffered rest of this read
    finally:
        a.close()
        b.close()


def test_frame_reader_patient_first_byte_then_eof_returns_none():
    a, b = socket.socketpair()
    try:
        b.setblocking(False)  # the pinned connection's non-blocking lane
        reader = FrameReader(b)
        threading.Timer(0.3, lambda: (a.sendall(b"\x07" * 3), a.close())).start()
        assert reader.read(
            3, progress_timeout=0.1, patient_first=True, preset=True
        ) == b"\x07" * 3  # the first byte waited past the deadline
        assert reader.read(1, progress_timeout=0.1, preset=True) is None
    finally:
        b.close()


# --- hardening is default-on, opt-out via guard=False ------------------------


def test_all_four_listener_families_are_hardened_by_default():
    port = _free_port()
    comm = TcpComm(1, {1: ("127.0.0.1", port)}, lambda *a: None)
    assert isinstance(comm.guard, ListenerGuard)
    comm_off = TcpComm(1, {1: ("127.0.0.1", port)}, lambda *a: None, guard=False)
    assert comm_off.guard is None

    listener = SyncListener(SyncServer(LedgerDecisionStore([])))
    try:
        assert isinstance(listener.guard, ListenerGuard)
    finally:
        listener.close()

    control = ControlServer({})
    try:
        assert isinstance(control.guard, ListenerGuard)
    finally:
        control.close()

    sidecar = VerifySidecarServer(("127.0.0.1", 0), object(), auth_secret=SECRET)
    assert isinstance(sidecar.guard, ListenerGuard)
    sidecar_off = VerifySidecarServer(
        ("127.0.0.1", 0), object(), auth_secret=SECRET, guard=False
    )
    assert sidecar_off.guard is None


# --- comm listener under the full battery ------------------------------------


def _start_comm_pair(guard2, *, secret=SECRET):
    ports = []
    for _ in range(2):
        ports.append(_free_port())
    addrs = {1: ("127.0.0.1", ports[0]), 2: ("127.0.0.1", ports[1])}
    received = []
    got = threading.Event()
    comm1 = TcpComm(1, addrs, lambda *a: None, auth_secret=secret)
    comm2 = TcpComm(
        2, addrs,
        lambda s, m, r: (received.append((s, m)), got.set()),
        auth_secret=secret, guard=guard2,
    )
    comm1.start()
    comm2.start()
    return addrs, comm1, comm2, received, got


def test_comm_listener_survives_full_battery_with_honest_traffic():
    guard, provider = _metered_guard(
        name="comm-2", handshake_timeout=0.4, progress_timeout=0.4,
        strike_limit=100,  # localhost: honest peers share 127.0.0.1
    )
    addrs, comm1, comm2, received, got = _start_comm_pair(guard)
    try:
        comm1.send_consensus(2, HeartBeat(view=1, seq=1))
        assert got.wait(timeout=10.0)  # honest baseline

        adv = AdversarialPeer(addrs[2], "comm", secret=SECRET, close_wait=10.0)
        assert adv.never_hello(1) == {"handshake_timeout": 1}
        assert adv.midframe_stall(2) == {"stall": 2}
        assert adv.oversized_length(2) == {"oversized": 2}
        assert adv.wrong_hmac_flood(2) == {"bad_hello": 2}
        assert adv.handshake_replay(2) == {"bad_hello": 2}

        # Exactly-once booking: stats and the pinned per-kind metrics
        # match the provoked counts with nothing extra.
        assert guard.stats.handshake_timeouts == 1
        assert guard.stats.malformed == 8
        assert guard.stats.bans == 0
        dump = provider.dump()
        assert dump[f"{NET_MALFORMED_KEY}{{stall}}"]["value"] == 2
        assert dump[f"{NET_MALFORMED_KEY}{{oversized}}"]["value"] == 2
        assert dump[f"{NET_MALFORMED_KEY}{{bad_hello}}"]["value"] == 4
        assert dump[NET_HANDSHAKE_TIMEOUT_KEY]["value"] == 1

        # Honest traffic still commits after the battery.
        got.clear()
        comm1.send_consensus(2, HeartBeat(view=2, seq=2))
        assert got.wait(timeout=10.0), "battery starved the honest peer"
    finally:
        comm1.stop()
        comm2.stop()


def test_comm_connect_flood_is_shed_at_the_quota():
    guard, provider = _metered_guard(
        name="comm-2", handshake_timeout=2.0, max_conns_per_peer=3,
    )
    port = _free_port()
    comm = TcpComm(
        2, {2: ("127.0.0.1", port)}, lambda *a: None,
        auth_secret=SECRET, guard=guard,
    )
    comm.start()
    try:
        adv = AdversarialPeer(("127.0.0.1", port), "comm", close_wait=5.0)
        out = adv.connect_flood(count=6, probe_timeout=0.5)
        assert out["admitted"] == 3 and out["conn_rejected"] == 3
        assert guard.stats.rejected == 3
        assert provider.dump()[NET_CONN_REJECTED_KEY]["value"] == 3
        # The flood booked ONLY rejections: admitted conns were closed
        # before the handshake deadline.
        assert guard.stats.malformed == 0
    finally:
        comm.stop()


def test_banned_peer_refused_mid_ban_then_honest_successor_served():
    """The reconnection races: (a) a peer banned for malformed frames
    reconnects immediately — refused at accept before any read; (b) after
    the ban expires, an HONEST peer on the same (recycled) address gets
    full service with strikes forgiven."""
    guard, _ = _metered_guard(
        name="comm-2", handshake_timeout=1.0, progress_timeout=1.0,
        strike_limit=1, ban_seconds=1.0,
    )
    addrs, comm1, comm2, received, got = _start_comm_pair(guard)
    try:
        comm1.stop()  # keep the honest peer off the wire during the ban
        adv = AdversarialPeer(addrs[2], "comm", close_wait=5.0)
        assert adv.oversized_length(1) == {"oversized": 1}
        assert guard.stats.bans == 1 and guard.is_banned("127.0.0.1")
        # (a) mid-ban reconnect: the accept gate closes it immediately.
        out = adv.connect_flood(count=1, probe_timeout=0.5)
        assert out == {"conn_rejected": 1, "admitted": 0}
        # (b) ban expiry: an honest successor on the recycled address.
        deadline = time.monotonic() + 10.0
        while guard.is_banned("127.0.0.1"):
            assert time.monotonic() < deadline, "ban never expired"
            time.sleep(0.05)
        comm1b = TcpComm(1, addrs, lambda *a: None, auth_secret=SECRET)
        comm1b.start()
        try:
            comm1b.send_consensus(2, HeartBeat(view=3, seq=3))
            assert got.wait(timeout=10.0), "honest successor starved post-ban"
            assert guard.stats.bans == 1  # honest traffic drew no second ban
        finally:
            comm1b.stop()
    finally:
        comm1.stop()
        comm2.stop()


# --- sync listener under battery ---------------------------------------------


def test_sync_listener_battery_and_honest_catchup():
    guard, provider = _metered_guard(
        name="sync", handshake_timeout=0.4, progress_timeout=0.4,
        strike_limit=100,
    )
    chain = build_chain(5)
    listener = SyncListener(
        SyncServer(LedgerDecisionStore(list(chain))), guard=guard
    )
    try:
        adv = AdversarialPeer(listener.address, "sync", close_wait=10.0)
        assert adv.oversized_length(2) == {"oversized": 2}
        assert adv.midframe_stall(1) == {"stall": 1}
        assert adv.wrong_hmac_flood(2) == {"garbage": 2}
        assert adv.never_hello(1) == {"handshake_timeout": 1}

        assert guard.stats.malformed == 5
        assert guard.stats.handshake_timeouts == 1
        dump = provider.dump()
        assert dump[f"{NET_MALFORMED_KEY}{{oversized}}"]["value"] == 2
        assert dump[f"{NET_MALFORMED_KEY}{{stall}}"]["value"] == 1
        assert dump[f"{NET_MALFORMED_KEY}{{garbage}}"]["value"] == 2

        # Honest catch-up still answers.
        transport = TcpSyncTransport(2, {1: listener.address}, timeout=5.0)
        reply = transport.fetch(1, SyncRequest(from_seq=1, to_seq=0))
        assert isinstance(reply, SyncSnapshotMeta) and reply.height == 5
    finally:
        listener.close()


# --- control server under battery --------------------------------------------


def test_control_server_battery_keeps_answering_honest_probes():
    guard, provider = _metered_guard(
        name="control", handshake_timeout=0.4, progress_timeout=0.4,
        strike_limit=100,
    )
    server = ControlServer(
        {"ping": lambda req: {"ok": True}}, guard=guard, max_line=4096
    )
    try:
        assert control_probe_reply(server.address) == {"ok": True}

        # Honest probes run CONCURRENTLY with the battery: the threaded
        # accept path means a stalled byzantine prober cannot block the
        # supervisor's health probe behind it.
        stop = threading.Event()
        probe_failures = []

        def prober():
            while not stop.is_set():
                try:
                    if control_probe_reply(server.address) != {"ok": True}:
                        probe_failures.append("bad reply")
                except Exception as exc:  # noqa: BLE001
                    probe_failures.append(repr(exc))
                time.sleep(0.05)

        t = threading.Thread(target=prober, daemon=True)
        t.start()
        try:
            adv = AdversarialPeer(server.address, "control", close_wait=10.0)
            assert adv.never_hello(1) == {"handshake_timeout": 1}
            assert adv.midframe_stall(1) == {"stall": 1}
            # Garbage still gets the structured error reply — the battery
            # itself raises if the control plane goes silent.
            assert adv.wrong_hmac_flood(2) == {"garbage": 2}
            assert adv.oversized_length(1) == {"oversized": 1}
        finally:
            stop.set()
            t.join(timeout=5.0)
        assert not probe_failures, probe_failures

        assert guard.stats.handshake_timeouts == 1
        assert guard.stats.malformed == 4
        dump = provider.dump()
        assert dump[f"{NET_MALFORMED_KEY}{{garbage}}"]["value"] == 2
        assert dump[f"{NET_MALFORMED_KEY}{{oversized}}"]["value"] == 1
        assert dump[f"{NET_MALFORMED_KEY}{{stall}}"]["value"] == 1
    finally:
        server.close()


# --- sidecar under battery ---------------------------------------------------


class _YesEngine:
    def verify_batch(self, msgs, sigs, keys):
        return np.ones(len(msgs), dtype=bool)

    def verify_host(self, msgs, sigs, keys):
        return self.verify_batch(msgs, sigs, keys)


def test_sidecar_battery_including_insider_replay():
    guard, provider = _metered_guard(
        name="sidecar", handshake_timeout=0.4, progress_timeout=0.4,
        strike_limit=100,
    )
    server = VerifySidecarServer(
        ("127.0.0.1", 0), _YesEngine(), auth_secret=SECRET, guard=guard
    )
    server.start()
    try:
        adv = AdversarialPeer(
            server.address, "sidecar", secret=SECRET, close_wait=10.0
        )
        assert adv.never_hello(1) == {"handshake_timeout": 1}
        assert adv.wrong_hmac_flood(2) == {"bad_hello": 2}
        # Insider batteries: the adversary HOLDS the secret and must still
        # be bounded — a replayed transcript fails against fresh nonces,
        # and an oversized claim strikes before any allocation.
        assert adv.handshake_replay(2) == {"bad_hello": 2}
        assert adv.oversized_length(1) == {"oversized": 1}

        assert guard.stats.handshake_timeouts == 1
        assert guard.stats.malformed == 5
        dump = provider.dump()
        assert dump[f"{NET_MALFORMED_KEY}{{bad_hello}}"]["value"] == 4
        assert dump[f"{NET_MALFORMED_KEY}{{oversized}}"]["value"] == 1

        # Honest client unharmed after the battery.
        client = SidecarVerifierClient(server.address, auth_secret=SECRET)
        assert list(client.verify_batch([b"m"], [b"s"], [b"k"])) == [True]
        client.close()
    finally:
        server.stop()


def test_style_batteries_cover_every_style():
    assert set(STYLE_BATTERIES) == {"comm", "sync", "control", "sidecar"}
    for batteries in STYLE_BATTERIES.values():
        assert batteries  # nobody ships an empty vocabulary


# --- wire_abuse detector -----------------------------------------------------


def test_wire_abuse_detector_edge_triggers_on_guard_deltas():
    from consensus_tpu.obs.detectors import DetectorBank

    bank = DetectorBank()

    def sample(t, malformed=None, timeouts=0, bans=0, rejected=0):
        h = {"running": True, "ledger": 1, "pool": 0}
        if malformed is not None:
            h["net_malformed"] = malformed
            h["net_handshake_timeouts"] = timeouts
            h["net_peer_bans"] = bans
            h["net_conn_rejected"] = rejected
        return [a.kind for a in bank.evaluate(t, {2: h})]

    # No wire_guard on the node (fields absent): silent forever.
    assert sample(0.0) == []
    # Guard appears with zero events: still silent.
    assert sample(1.0, malformed=0) == []
    # New defense events fire once per sample-with-delta...
    assert sample(2.0, malformed=3) == ["wire_abuse"]
    # ...and the base ratchets: no NEW events, no firing.
    assert sample(3.0, malformed=3) == []
    assert sample(4.0, malformed=3, bans=1) == ["wire_abuse"]
    # Fields vanish (restart without hardened listeners): latch discarded.
    assert sample(5.0) == []
    assert sample(6.0, malformed=4, bans=1) == ["wire_abuse"]


def test_sim_chaos_net_abuse_arm_fires_detector_and_flight_trail():
    schedule = ChaosSchedule(
        seed=5,
        n=4,
        actions=(
            ChaosAction(
                at=30.0, kind="net_abuse",
                args={"node": 2, "battery": "garbage_flood", "events": 5},
            ),
            ChaosAction(
                at=50.0, kind="net_abuse",
                args={"node": 2, "battery": "connect_flood", "events": 3},
            ),
        ),
    )
    obs = ObsConfig(enabled=True, sample_interval=2.0)
    engine = ChaosEngine(schedule, obs=obs)
    result = engine.run()
    assert result.ok, result.violation
    counts = engine.cluster.sampler.anomaly_counts()
    assert "wire_abuse" in counts
    assert {a.node for a in result.anomalies if a.kind == "wire_abuse"} == {2}
    # events=5 at strike_limit 3 crossed a ban: the event log carries the
    # wire-ban line the flight recorder keys on.
    assert b"wire-ban node=2" in result.event_log
    # The same seed replays byte-identically, batteries included.
    result2 = ChaosEngine(schedule, obs=obs).run()
    assert result2.event_log == result.event_log


def test_clean_sim_soak_never_fires_wire_abuse():
    obs = ObsConfig(enabled=True, sample_interval=2.0)
    engine = ChaosEngine(ChaosSchedule(seed=7, n=4, actions=()), obs=obs)
    result = engine.run()
    assert result.ok
    assert "wire_abuse" not in engine.cluster.sampler.anomaly_counts()


# --- schedule generation: the off-arm is RNG-neutral -------------------------


def test_generate_adversarial_net_arm_and_rng_neutral_off_arm():
    on = ChaosSchedule.generate(21, steps=60, adversarial_net=True)
    assert on.adversarial_net is True
    abuse = [a for a in on.actions if a.kind in ADVERSARIAL_NET_KINDS]
    assert abuse, "60 steps with the arm on must draw at least one net_abuse"
    for action in abuse:
        assert action.args["battery"] in (
            "stall_flood", "garbage_flood", "connect_flood"
        )
        assert 3 <= action.args["events"] < 8
    # Off-arm (default False) consumes ZERO extra RNG: explicit False is
    # byte-identical to the pre-hardening default draw, so every pinned
    # chaos/soak seed in the repo replays unchanged.
    base = ChaosSchedule.generate(21, steps=60)
    off = ChaosSchedule.generate(21, steps=60, adversarial_net=False)
    assert off == base
    assert not any(a.kind in ADVERSARIAL_NET_KINDS for a in base.actions)
    # And the arm itself is deterministic.
    assert ChaosSchedule.generate(21, steps=60, adversarial_net=True) == on
