"""Verification sidecar: n replica processes sharing one device through a
socket front (SURVEY §7 step 9; VERDICT r3 #2 deployment shape).

These tests run server + clients in one process (threads stand in for the
replica processes — the socket boundary is identical); the cross-process
path is exercised by tests/test_zz_deploy_rig.py.
"""

import threading

import numpy as np
import pytest

from consensus_tpu.net.sidecar import (
    SidecarVerifierClient,
    VerifySidecarServer,
    decode_request,
    encode_request,
)

SECRET = b"test-shared-secret"


class FakeEngine:
    """Valid iff sig == b"good"; counts launches."""

    def __init__(self):
        self.calls = []
        self.lock = threading.Lock()

    def verify_batch(self, msgs, sigs, keys):
        with self.lock:
            self.calls.append(len(msgs))
        return np.array([s == b"good" for s in sigs], dtype=bool)

    def verify_host(self, msgs, sigs, keys):
        return self.verify_batch(msgs, sigs, keys)


def test_request_codec_round_trip():
    msgs = [b"alpha", b"", b"x" * 300]
    sigs = [b"s1", b"good", b"s3"]
    keys = [b"k" * 32, b"", b"q" * 65]
    out = decode_request(encode_request(msgs, sigs, keys))
    assert out == (msgs, sigs, keys)


def test_request_codec_rejects_trailing_bytes():
    buf = encode_request([b"m"], [b"s"], [b"k"]) + b"JUNK"
    with pytest.raises(ValueError):
        decode_request(buf)


@pytest.fixture(params=["tcp", "unix"])
def server_address(request, tmp_path):
    if request.param == "tcp":
        return ("127.0.0.1", 0)
    return str(tmp_path / "sidecar.sock")


def test_round_trip_over_socket(server_address):
    engine = FakeEngine()
    server = VerifySidecarServer(server_address, engine, auth_secret=SECRET)
    server.start()
    try:
        client = SidecarVerifierClient(server.address, auth_secret=SECRET)
        out = client.verify_batch(
            [b"m1", b"m2", b"m3"], [b"good", b"bad", b"good"], [b"k"] * 3
        )
        assert list(out) == [True, False, True]
        # Second request rides the same connection.
        out2 = client.verify_batch([b"m"], [b"good"], [b"k"])
        assert list(out2) == [True]
        client.close()
    finally:
        server.stop()


def test_concurrent_clients_all_get_correct_slices(server_address):
    """Many client processes (threads here; the socket boundary is the same)
    with interleaved requests — every caller gets exactly its own results."""
    engine = FakeEngine()
    server = VerifySidecarServer(server_address, engine, auth_secret=SECRET)
    server.start()
    results = {}
    try:
        def worker(i):
            client = SidecarVerifierClient(server.address, auth_secret=SECRET)
            pattern = [b"good" if (i + j) % 2 == 0 else b"bad" for j in range(20)]
            out = client.verify_batch([b"m"] * 20, pattern, [b"k"] * 20)
            results[i] = (pattern, list(out))
            client.close()

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
        assert len(results) == 6
        for pattern, out in results.values():
            assert out == [s == b"good" for s in pattern]
    finally:
        server.stop()


def test_sidecar_coalesces_processes_into_one_launch():
    """The deployment thesis: wrap the engine in a ThreadCoalescingVerifier
    and concurrent requests from different connections merge into ONE
    engine launch."""
    from consensus_tpu.models import ThreadCoalescingVerifier

    engine = FakeEngine()
    coalescer = ThreadCoalescingVerifier(engine, window=0.05, max_batch=40)
    server = VerifySidecarServer(("127.0.0.1", 0), coalescer, auth_secret=SECRET)
    server.start()
    results = {}
    try:
        def worker(i):
            client = SidecarVerifierClient(server.address, auth_secret=SECRET)
            out = client.verify_batch([b"m"] * 10, [b"good"] * 10, [b"k"] * 10)
            results[i] = out.all()
            client.close()

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
        assert all(results.values())
        # 4 x 10 sigs hit max_batch=40: one merged launch.
        assert engine.calls == [40]
    finally:
        coalescer.close()
        server.stop()


def test_engine_error_is_served_as_error_not_disconnect():
    class Boom:
        def verify_batch(self, m, s, k):
            raise RuntimeError("kernel exploded")

    server = VerifySidecarServer(("127.0.0.1", 0), Boom(), auth_secret=SECRET)
    server.start()
    try:
        client = SidecarVerifierClient(server.address, auth_secret=SECRET)
        with pytest.raises(RuntimeError, match="kernel exploded"):
            client.verify_batch([b"m"], [b"s"], [b"k"])
        # The connection survives an engine error (next request still works
        # at the framing level — it errors again, but over the same link).
        with pytest.raises(RuntimeError):
            client.verify_batch([b"m"], [b"s"], [b"k"])
        client.close()
    finally:
        server.stop()


def test_dead_sidecar_falls_back_to_local_engine():
    """VERDICT r3 #3 applied to the process boundary: an unreachable
    sidecar must not wedge the replica — with a local_engine the client
    fails over to host verification."""
    local = FakeEngine()
    client = SidecarVerifierClient(
        ("127.0.0.1", 1), local_engine=local, connect_timeout=0.2
    )
    out = client.verify_batch([b"m", b"m"], [b"good", b"bad"], [b"k"] * 2)
    assert list(out) == [True, False]
    assert local.calls == [2]


def test_client_books_every_signature_by_verdict_source():
    """``counts()`` is what lets a run prove the device did the work: sent /
    served by the sidecar, bypassed by size, FALLEN BACK to the host — a
    fallback is a count, not only a log line."""
    served_by = FakeEngine()
    server = VerifySidecarServer(("127.0.0.1", 0), served_by, auth_secret=SECRET)
    server.start()
    local = FakeEngine()
    client = SidecarVerifierClient(
        server.address, local_engine=local, bypass_below=2,
        auth_secret=SECRET, connect_timeout=0.5,
    )
    try:
        assert client.counts() == {
            "sent": 0, "served": 0, "bypassed": 0, "fallen_back": 0,
            "suspect": False,
        }
        client.verify_batch([b"m"], [b"good"], [b"k"])             # by size
        client.verify_batch([b"m"] * 3, [b"good"] * 3, [b"k"] * 3)  # served
        assert client.counts() == {
            "sent": 3, "served": 3, "bypassed": 1, "fallen_back": 0,
            "suspect": False,
        }
        assert served_by.calls == [3] and local.calls == [1]
        server.stop()
        client.close()
        # The sidecar is gone: the batch is still answered, and it SHOWS.
        dead = SidecarVerifierClient(
            ("127.0.0.1", 1), local_engine=local, connect_timeout=0.2
        )
        out = dead.verify_batch([b"m"] * 2, [b"good", b"bad"], [b"k"] * 2)
        assert list(out) == [True, False]
        assert dead.counts() == {
            "sent": 2, "served": 0, "bypassed": 0, "fallen_back": 2,
            "suspect": False,
        }
    finally:
        server.stop()


def test_dead_sidecar_without_local_engine_raises():
    client = SidecarVerifierClient(("127.0.0.1", 1), connect_timeout=0.2)
    with pytest.raises(OSError):
        client.verify_batch([b"m"], [b"s"], [b"k"])


def test_server_death_mid_flight_fails_over():
    """Kill the server while requests are pending: waiters get a connection
    error and (with a local engine) the batch is still answered."""
    import time

    class Slow:
        def verify_batch(self, m, s, k):
            time.sleep(5.0)
            return np.ones(len(m), dtype=bool)

    local = FakeEngine()
    server = VerifySidecarServer(("127.0.0.1", 0), Slow(), auth_secret=SECRET)
    server.start()
    client = SidecarVerifierClient(
        server.address, local_engine=local, request_timeout=30.0,
        auth_secret=SECRET,
    )
    out = {}

    def worker():
        out["r"] = client.verify_batch([b"m"], [b"good"], [b"k"])

    t = threading.Thread(target=worker)
    t.start()
    time.sleep(0.3)  # request in flight on the server's slow engine
    client.close()  # simulates the link dying
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert list(out["r"]) == [True]  # answered by the local fallback
    server.stop()


def test_send_failure_falls_back_without_deadlock(monkeypatch):
    """A failed SEND (sidecar died; EPIPE) must drop the socket and fall
    back locally — regression: _drop_socket used to be called while holding
    the client lock it re-acquires, wedging every later verify call."""
    import consensus_tpu.net.sidecar as sc

    local = FakeEngine()
    server = VerifySidecarServer(("127.0.0.1", 0), FakeEngine(), auth_secret=SECRET)
    server.start()
    client = SidecarVerifierClient(server.address, local_engine=local, auth_secret=SECRET)
    try:
        assert list(client.verify_batch([b"m"], [b"good"], [b"k"])) == [True]

        orig = sc._write_frame

        def boom(sock, req_id, payload):
            raise OSError("broken pipe")

        monkeypatch.setattr(sc, "_write_frame", boom)
        out = {}

        def worker(key):
            out[key] = list(client.verify_batch([b"m"], [b"bad"], [b"k"]))

        t1 = threading.Thread(target=worker, args=("a",))
        t1.start()
        t1.join(timeout=5.0)
        assert not t1.is_alive(), "client deadlocked on send failure"
        assert out["a"] == [False]

        # A second call must not block on a held lock either, and once
        # sends work again the client reconnects to the sidecar.
        t2 = threading.Thread(target=worker, args=("b",))
        t2.start()
        t2.join(timeout=5.0)
        assert not t2.is_alive(), "client deadlocked after socket drop"
        monkeypatch.setattr(sc, "_write_frame", orig)
        assert list(client.verify_batch([b"m"], [b"good"], [b"k"])) == [True]
    finally:
        client.close()
        server.stop()


def test_wedged_sidecar_marks_suspect_and_probes_back():
    """A TIMED-OUT request (wedged sidecar, hung device call) must not cost
    every later call the full request_timeout: the client marks the sidecar
    suspect, answers from the local engine immediately, and a background
    probe restores sidecar mode once it answers again."""
    import time

    gate = threading.Event()

    class Gated:
        """Blocks until the gate opens (wedged), then serves normally."""

        def verify_batch(self, m, s, k):
            if not gate.wait(timeout=30.0):
                raise RuntimeError("gate never opened")
            return np.array([x == b"good" for x in s], dtype=bool)

    local = FakeEngine()
    server = VerifySidecarServer(("127.0.0.1", 0), Gated(), auth_secret=SECRET)
    server.start()
    client = SidecarVerifierClient(
        server.address, local_engine=local, request_timeout=0.3,
        probe_interval=0.05, auth_secret=SECRET,
    )
    try:
        # First call: stalls request_timeout, falls back, marks suspect.
        out = client.verify_batch([b"m"], [b"good"], [b"k"])
        assert list(out) == [True]
        assert client._suspect

        # Later calls answer locally with NO timeout stall.
        start = time.monotonic()
        out = client.verify_batch([b"m"], [b"bad"], [b"k"])
        assert time.monotonic() - start < 0.2
        assert list(out) == [False]

        # Unwedge the server: the probe clears the flag and sidecar mode
        # resumes.
        gate.set()
        deadline = time.monotonic() + 5.0
        while client._suspect and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not client._suspect, "probe never cleared the suspect flag"
        out = client.verify_batch([b"m"], [b"good"], [b"k"])
        assert list(out) == [True]
    finally:
        client.close()
        server.stop()


# -- hardening (ADVICE r4 / VERDICT r4 #6) ---------------------------------


def test_tcp_server_without_secret_refuses_to_start():
    """Unauthenticated TCP ingress is a free-verification + DoS surface:
    the server refuses the configuration outright."""
    server = VerifySidecarServer(("127.0.0.1", 0), FakeEngine())
    with pytest.raises(ValueError, match="auth_secret"):
        server.start()


def test_wrong_secret_client_is_rejected():
    """A peer that cannot HMAC the nonce is dropped before any frame is
    read; with a local engine the replica still gets its answer."""
    local = FakeEngine()
    remote = FakeEngine()
    server = VerifySidecarServer(("127.0.0.1", 0), remote, auth_secret=SECRET)
    server.start()
    try:
        client = SidecarVerifierClient(
            server.address, local_engine=local, auth_secret=b"not-the-secret",
            request_timeout=2.0,
        )
        out = client.verify_batch([b"m"], [b"good"], [b"k"])
        assert list(out) == [True]
        assert local.calls == [1]       # served by the fallback
        assert remote.calls == []       # never reached the engine
        client.close()
    finally:
        server.stop()


def test_secretless_client_cannot_use_authed_server():
    """A client that skips the handshake entirely never gets service (its
    first frame header is consumed as a bad HMAC answer and the connection
    is closed)."""
    local = FakeEngine()
    remote = FakeEngine()
    server = VerifySidecarServer(("127.0.0.1", 0), remote, auth_secret=SECRET)
    server.start()
    try:
        client = SidecarVerifierClient(
            server.address, local_engine=local, request_timeout=2.0,
        )
        out = client.verify_batch([b"m"], [b"good"], [b"k"])
        assert list(out) == [True]
        assert remote.calls == []
        client.close()
    finally:
        server.stop()


def test_flood_is_bounded_per_connection():
    """max_inflight bounds concurrent worker threads for one connection:
    a flood of pipelined requests backpressures into the socket instead of
    spawning unbounded threads — and every request is still answered."""
    import time

    class Gauge:
        """Tracks peak concurrent verify calls."""

        def __init__(self):
            self.lock = threading.Lock()
            self.live = 0
            self.peak = 0

        def verify_batch(self, m, s, k):
            with self.lock:
                self.live += 1
                self.peak = max(self.peak, self.live)
            time.sleep(0.02)  # hold the slot so concurrency is observable
            with self.lock:
                self.live -= 1
            return np.ones(len(m), dtype=bool)

    gauge = Gauge()
    server = VerifySidecarServer(
        ("127.0.0.1", 0), gauge, auth_secret=SECRET, max_inflight=4
    )
    server.start()
    try:
        client = SidecarVerifierClient(server.address, auth_secret=SECRET)
        outs = {}

        def worker(i):
            outs[i] = client.verify_batch([b"m"], [b"good"], [b"k"]).all()

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20.0)
        assert len(outs) == 24 and all(outs.values())
        assert gauge.peak <= 4, f"flood exceeded max_inflight: {gauge.peak}"
        client.close()
    finally:
        server.stop()


def test_oversized_frame_drops_connection_not_server():
    """A frame above max_frame closes that connection; the server keeps
    serving well-behaved peers."""
    import socket as socket_mod
    import struct as struct_mod

    engine = FakeEngine()
    server = VerifySidecarServer(
        ("127.0.0.1", 0), engine, auth_secret=SECRET, max_frame=1024
    )
    server.start()
    try:
        import os as os_mod

        from consensus_tpu.net.sidecar import (
            _CLIENT_PROOF,
            _SERVER_PROOF,
            _hmac256,
            _recv_exact,
        )

        raw = socket_mod.create_connection(tuple(server.address), timeout=5.0)
        raw.settimeout(5.0)
        server_nonce = _recv_exact(raw, 32)
        client_nonce = os_mod.urandom(32)
        raw.sendall(
            client_nonce
            + _hmac256(SECRET, _CLIENT_PROOF, server_nonce, client_nonce)
        )
        proof = _recv_exact(raw, 32)
        assert proof == _hmac256(SECRET, _SERVER_PROOF, server_nonce, client_nonce)
        raw.sendall(struct_mod.pack(">IQ", 1 << 20, 7))  # oversized header
        assert raw.recv(1) == b""  # server hung up (max_frame guard)
        raw.close()

        client = SidecarVerifierClient(server.address, auth_secret=SECRET)
        assert list(client.verify_batch([b"m"], [b"good"], [b"k"])) == [True]
        client.close()
    finally:
        server.stop()


def test_drop_socket_spares_waiters_on_newer_socket():
    """Regression (ADVICE r4): a stale reader thread's _drop_socket must
    only fail waiters registered on ITS socket, not fresh requests on the
    reconnected one."""
    client = SidecarVerifierClient(("127.0.0.1", 1))
    old_sock, new_sock = object(), object()
    old_waiter = {"event": threading.Event(), "body": None, "sock": old_sock}
    new_waiter = {"event": threading.Event(), "body": None, "sock": new_sock}
    client._pending = {1: old_waiter, 2: new_waiter}
    client._sock = new_sock

    class _Closeable:
        def close(self):
            pass

    old = _Closeable()
    old_waiter["sock"] = old
    client._drop_socket(old)
    assert old_waiter["event"].is_set()          # stale waiter failed
    assert not new_waiter["event"].is_set()      # fresh waiter untouched
    assert client._pending == {2: new_waiter}
    assert client._sock is new_sock              # current socket kept


def test_blocked_send_times_out_and_fails_over():
    """Regression (ADVICE r4 medium): a sidecar that accepts but never
    READS must not wedge the sender forever — the socket send timeout
    surfaces, the client marks the sidecar suspect, and the local engine
    answers.  Other verify calls must not be blocked behind the stalled
    send (the send happens outside the client lock)."""
    import socket as socket_mod
    import time

    listener = socket_mod.socket(socket_mod.AF_INET, socket_mod.SOCK_STREAM)
    listener.bind(("127.0.0.1", 0))
    listener.listen(1)
    addr = listener.getsockname()
    local = FakeEngine()
    # No auth (server never reads, so the handshake would stall): use a
    # secretless client against a raw listener.
    client = SidecarVerifierClient(
        addr, local_engine=local, request_timeout=1.0, probe_interval=60.0,
    )
    try:
        big = b"x" * (4 * 1024 * 1024)
        out = {}

        def stalled():
            out["a"] = client.verify_batch([big] * 8, [b"good"] * 8, [b"k"] * 8)

        t = threading.Thread(target=stalled)
        start = time.monotonic()
        t.start()
        t.join(timeout=15.0)
        assert not t.is_alive(), "blocked send never surfaced"
        assert list(out["a"]) == [True] * 8  # answered by the fallback
        # Suspect mode: the next call answers locally without re-stalling.
        start = time.monotonic()
        assert list(client.verify_batch([b"m"], [b"bad"], [b"k"])) == [False]
        assert time.monotonic() - start < 0.5
    finally:
        client.close()
        listener.close()


def test_in_path_forger_cannot_mint_verdicts():
    """A relay that passes the handshake through (it cannot compute the
    session key) and then forges an 'all valid' response must NOT be
    believed: the frame MAC fails, the connection drops, and the replica
    falls back to local verification — forged input never becomes a
    consensus verdict."""
    import socket as socket_mod
    import struct as struct_mod

    engine = FakeEngine()
    server = VerifySidecarServer(("127.0.0.1", 0), engine, auth_secret=SECRET)
    server.start()

    relay = socket_mod.socket(socket_mod.AF_INET, socket_mod.SOCK_STREAM)
    relay.bind(("127.0.0.1", 0))
    relay.listen(1)

    stop = threading.Event()

    def mitm():
        victim, _ = relay.accept()
        upstream = socket_mod.create_connection(tuple(server.address), timeout=5.0)
        victim.settimeout(5.0)
        upstream.settimeout(5.0)
        try:
            # Relay the handshake verbatim: server nonce down, client
            # nonce+proof up, server proof down.  The relay learns nothing
            # usable — the session key needs the shared secret.
            victim.sendall(upstream.recv(32))
            up = b""
            while len(up) < 64:
                up += victim.recv(64 - len(up))
            upstream.sendall(up)
            victim.sendall(upstream.recv(32))
            # Swallow the victim's first request, then FORGE "1 valid".
            victim.recv(65536)
            forged = b"\x00" + b"\x01"
            victim.sendall(struct_mod.pack(">IQ", len(forged), 0) + forged
                           + b"\x00" * 16)  # garbage MAC
            stop.wait(5.0)
        except OSError:
            pass
        finally:
            victim.close()
            upstream.close()

    t = threading.Thread(target=mitm, daemon=True)
    t.start()
    local = FakeEngine()
    client = SidecarVerifierClient(
        relay.getsockname(), local_engine=local, auth_secret=SECRET,
        request_timeout=3.0,
    )
    try:
        out = client.verify_batch([b"m"], [b"bad"], [b"k"])
        # The honest answer (invalid) from the LOCAL engine — never the
        # forged "valid" verdict.
        assert list(out) == [False]
        assert local.calls == [1]
    finally:
        stop.set()
        client.close()
        relay.close()
        server.stop()


def test_idle_connection_survives_io_timeout():
    """The server's per-connection io_timeout bounds SENDS to a non-reading
    peer; an idle (but healthy) connection must NOT be dropped by it — the
    read loop treats frame-boundary timeouts as idle and keeps waiting."""
    import time

    engine = FakeEngine()
    server = VerifySidecarServer(
        ("127.0.0.1", 0), engine, auth_secret=SECRET, io_timeout=0.2
    )
    server.start()
    try:
        client = SidecarVerifierClient(server.address, auth_secret=SECRET)
        assert list(client.verify_batch([b"m"], [b"good"], [b"k"])) == [True]
        time.sleep(1.0)  # several io_timeout periods of silence
        assert list(client.verify_batch([b"m"], [b"bad"], [b"k"])) == [False]
        client.close()
    finally:
        server.stop()


# -- multi-tenant verification service --------------------------------------


TENANTS = {"alpha": b"alpha-secret", "beta": b"beta-secret",
           "gamma": b"gamma-secret", "delta": b"delta-secret"}


def _tenant_client(address, tenant, **kw):
    return SidecarVerifierClient(
        address, auth_secret=TENANTS[tenant], tenant=tenant, **kw
    )


def test_tenant_handshake_round_trip_and_wrong_secret_rejected():
    """Each connection authenticates AS a tenant; a wrong per-tenant secret
    never gets service, and the legacy shared-secret client still works on
    a server configured with both."""
    engine = FakeEngine()
    server = VerifySidecarServer(
        ("127.0.0.1", 0), engine, auth_secret=SECRET, tenants=TENANTS,
        wave_window=0.002,
    )
    server.start()
    try:
        for tenant in ("alpha", "beta"):
            client = _tenant_client(server.address, tenant)
            out = client.verify_batch([b"m", b"m"], [b"good", b"bad"], [b"k"] * 2)
            assert list(out) == [True, False]
            client.close()
        legacy = SidecarVerifierClient(server.address, auth_secret=SECRET)
        assert list(legacy.verify_batch([b"m"], [b"good"], [b"k"])) == [True]
        legacy.close()

        local = FakeEngine()
        impostor = SidecarVerifierClient(
            server.address, auth_secret=b"beta-secret", tenant="alpha",
            local_engine=local, request_timeout=2.0,
        )
        assert list(impostor.verify_batch([b"m"], [b"good"], [b"k"])) == [True]
        assert local.calls == [1], "impostor must be served by its fallback only"
        impostor.close()
    finally:
        server.stop()


def test_four_tenants_share_one_wave_vs_four_private_sidecars():
    """The multi-tenant thesis (pinned metric + test): four tenants'
    concurrent quorum-sized sweeps on ONE shared server coalesce into fewer
    engine launches than four private sidecars serving the same load."""
    from consensus_tpu.metrics import (
        SIDECAR_WAVE_LAUNCHES_KEY,
        SIDECAR_WAVE_SIGNATURES_KEY,
        SIDECAR_WAVE_TENANTS_KEY,
        InMemoryProvider,
        Metrics,
    )
    from consensus_tpu.obs.kernels import TenantAccounting

    def drive(clients):
        """Submit one 10-signature sweep per client, concurrently."""
        outs = {}

        def worker(i, c):
            outs[i] = c.verify_batch([b"m"] * 10, [b"good"] * 10, [b"k"] * 10)

        threads = [
            threading.Thread(target=worker, args=(i, c))
            for i, c in enumerate(clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
        assert len(outs) == len(clients)
        for out in outs.values():
            assert out.all()

    # Shared multi-tenant server: one wave former, one engine.
    provider = InMemoryProvider()
    metrics = Metrics(provider, label_names=("tenant",))
    accounting = TenantAccounting()
    shared_engine = FakeEngine()
    server = VerifySidecarServer(
        ("127.0.0.1", 0), shared_engine, tenants=TENANTS,
        wave_window=0.05, metrics=metrics.sidecar, tenant_accounting=accounting,
    )
    server.start()
    clients = [_tenant_client(server.address, t) for t in sorted(TENANTS)]
    try:
        drive(clients)
    finally:
        for c in clients:
            c.close()
        server.stop()
    shared_launches = len(shared_engine.calls)

    # Four private sidecars: one engine each, same concurrent load.
    private_engines = [FakeEngine() for _ in range(4)]
    servers = [
        VerifySidecarServer(("127.0.0.1", 0), e, auth_secret=SECRET)
        for e in private_engines
    ]
    for s in servers:
        s.start()
    clients = [
        SidecarVerifierClient(s.address, auth_secret=SECRET) for s in servers
    ]
    try:
        drive(clients)
    finally:
        for c in clients:
            c.close()
        for s in servers:
            s.stop()
    private_launches = sum(len(e.calls) for e in private_engines)

    assert private_launches == 4
    assert shared_launches < private_launches, (
        f"shared server did not coalesce: {shared_launches} launches"
    )
    # The pinned metrics agree with the engine's own count.
    dump = provider.dump()
    assert dump[SIDECAR_WAVE_LAUNCHES_KEY]["value"] == shared_launches
    assert dump[SIDECAR_WAVE_SIGNATURES_KEY]["value"] == 40
    assert dump[SIDECAR_WAVE_TENANTS_KEY]["value"] >= 4
    # Per-tenant kernel attribution: every tenant rode its 10 signatures.
    snap = accounting.snapshot()
    assert set(snap) == set(TENANTS)
    for stats in snap.values():
        assert stats["signatures"] == 10 and stats["waves"] >= 1


def test_admission_reject_is_structured_and_never_stalls_other_tenants():
    """A tenant over its queue limit gets an IMMEDIATE structured status-2
    reject (tenant id, queue depth, limit); a concurrent honest tenant's
    wave still launches and completes.  With a local engine the rejected
    tenant falls back locally WITHOUT marking the sidecar suspect."""
    import time

    from consensus_tpu.metrics import (
        SIDECAR_ADMISSION_REJECTS_KEY,
        InMemoryProvider,
        Metrics,
    )
    from consensus_tpu.net.sidecar import TenantAdmissionReject

    provider = InMemoryProvider()
    metrics = Metrics(provider, label_names=("tenant",))
    engine = FakeEngine()
    server = VerifySidecarServer(
        ("127.0.0.1", 0), engine, tenants=TENANTS,
        wave_window=0.02, tenant_queue_limit=16, metrics=metrics.sidecar,
    )
    server.start()
    flooder = _tenant_client(server.address, "alpha")
    honest = _tenant_client(server.address, "beta")
    try:
        outs = {}

        def honest_worker():
            outs["beta"] = honest.verify_batch(
                [b"m"] * 8, [b"good"] * 8, [b"k"] * 8
            )

        t = threading.Thread(target=honest_worker)
        t.start()
        start = time.monotonic()
        with pytest.raises(TenantAdmissionReject) as exc:
            flooder.verify_batch([b"m"] * 20, [b"good"] * 20, [b"k"] * 20)
        reject_latency = time.monotonic() - start
        t.join(timeout=10.0)
        assert outs["beta"].all(), "honest tenant stalled behind the reject"
        assert exc.value.tenant == "alpha"
        assert exc.value.limit == 16
        assert reject_latency < 5.0, "reject must not wait out a stall budget"
        assert not flooder._suspect, "admission reject must not mark suspect"
        assert provider.dump()[SIDECAR_ADMISSION_REJECTS_KEY]["value"] >= 1

        # With a local engine the over-quota tenant degrades gracefully.
        local = FakeEngine()
        fallback = _tenant_client(
            server.address, "alpha", local_engine=local,
        )
        out = fallback.verify_batch([b"m"] * 20, [b"good"] * 20, [b"k"] * 20)
        assert out.all() and local.calls == [20]
        assert not fallback._suspect
        fallback.close()
    finally:
        flooder.close()
        honest.close()
        server.stop()


def test_give_up_queued_raises_structured_sidecar_stall():
    """The client give-up path (budget spent behind a stalled sender,
    wire never touched) must raise the STRUCTURED SidecarQueueStall —
    tenant id, local queue depth, expired budget — and still satisfy the
    legacy QueueStallTimeout isinstance contract."""
    from consensus_tpu.net.sidecar import QueueStallTimeout, SidecarQueueStall

    engine = FakeEngine()
    server = VerifySidecarServer(
        ("127.0.0.1", 0), engine, tenants=TENANTS, wave_window=0.002,
    )
    server.start()
    client = _tenant_client(server.address, "gamma", request_timeout=0.3)
    try:
        # Prime the connection, then hold the write lock so the next call
        # burns its whole budget queued behind a "stalled sender".
        assert client.verify_batch([b"m"], [b"good"], [b"k"]).all()
        client._wlock.acquire()
        try:
            with pytest.raises(QueueStallTimeout) as exc:
                client.verify_batch([b"m"], [b"good"], [b"k"])
        finally:
            client._wlock.release()
        stall = exc.value
        assert isinstance(stall, SidecarQueueStall)
        assert stall.tenant == "gamma"
        assert stall.deadline == pytest.approx(0.3)
        assert stall.queue_depth == 0  # nothing else was in flight
        assert not client._suspect, "queue stall must not mark suspect"
    finally:
        client.close()
        server.stop()


def test_tenant_mode_requires_secret():
    with pytest.raises(ValueError, match="tenant mode requires"):
        SidecarVerifierClient(("127.0.0.1", 1), tenant="alpha")


def test_tenant_isolation_under_chaos_flood():
    """Satellite of the multi-tenant PR: a flooding tenant hammering the
    shared verification service with over-quota sweeps is admission-rejected
    (status 2, bounded queue) while an honest tenant's REAL-crypto consensus
    cluster — running a lossy, delayed, byzantine chaos schedule THROUGH the
    shared sidecar — keeps committing, and the obs ``verify_collapse``
    detector stays silent for every honest node: the flood never starves
    their verify launches."""
    from consensus_tpu.config import ObsConfig
    from consensus_tpu.models import Ed25519BatchVerifier
    from consensus_tpu.net.sidecar import TenantAdmissionReject
    from consensus_tpu.testing.chaos import (
        ChaosAction,
        ChaosEngine,
        ChaosSchedule,
    )

    server = VerifySidecarServer(
        ("127.0.0.1", 0),
        Ed25519BatchVerifier(min_device_batch=10**9),
        tenants={"honest": b"honest-secret", "flood": b"flood-secret"},
        wave_window=0.001,
        tenant_queue_limit=64,
    )
    server.start()

    stop = threading.Event()
    rejects = [0]

    def flood():
        client = SidecarVerifierClient(
            server.address, auth_secret=b"flood-secret", tenant="flood",
            request_timeout=5.0,
        )
        try:
            while not stop.is_set():
                try:
                    client.verify_batch(
                        [b"junk"] * 100, [bytes(64)] * 100, [bytes(32)] * 100
                    )
                except TenantAdmissionReject:
                    rejects[0] += 1
                except Exception:
                    pass
        finally:
            client.close()

    flooder = threading.Thread(target=flood, daemon=True)
    flooder.start()
    try:
        def honest_engine():
            return SidecarVerifierClient(
                server.address, auth_secret=b"honest-secret", tenant="honest",
                local_engine=Ed25519BatchVerifier(min_device_batch=10**9),
            )

        # Loss, delay, and a signature-corrupting byzantine node — but no
        # partition/crash, so any verify_collapse firing could only come
        # from the flood starving honest verify launches.
        schedule = ChaosSchedule(
            seed=23,
            n=4,
            actions=(
                ChaosAction(at=20.0, kind="loss",
                            args={"a": 1, "b": 3, "p": 0.1}),
                ChaosAction(at=30.0, kind="byzantine",
                            args={"node": 4, "rate": 0.5}),
                ChaosAction(at=60.0, kind="delay",
                            args={"a": 2, "b": 4, "d": 0.5}),
                ChaosAction(at=90.0, kind="heal"),
            ),
        )
        result = ChaosEngine(
            schedule, crypto="ed25519", engine_factory=honest_engine,
            obs=ObsConfig(enabled=True, sample_interval=5.0),
        ).run()
    finally:
        stop.set()
        flooder.join(timeout=10.0)
        server.stop()

    assert result.ok, result.violation
    assert rejects[0] > 0, "the flooding tenant was never admission-rejected"
    collapse = [a for a in result.anomalies if a.kind == "verify_collapse"]
    assert not collapse, f"flood starved honest verify launches: {collapse}"


def test_wrong_secret_handshake_flood_never_starves_honest_tenants():
    """ISSUE 20 companion to the admission flood above: this flood never
    AUTHENTICATES — every connection fails the handshake proof outright
    (an outsider guessing secrets, not a tenant over quota).  The hardened
    listener guard strikes each failure as ``bad_hello`` and the honest
    tenant's verifies keep succeeding throughout."""
    from consensus_tpu.net.framing import ListenerGuard
    from consensus_tpu.testing.adversary import AdversarialPeer

    # Honest clients share 127.0.0.1 with the flood, so keep the strike
    # limit above the flood volume: the defense under test here is the
    # strike accounting + per-connection shedding, not the ban.
    guard = ListenerGuard(
        name="sidecar", handshake_timeout=0.5, strike_limit=10_000
    )
    server = VerifySidecarServer(
        ("127.0.0.1", 0), FakeEngine(), auth_secret=SECRET, tenants=TENANTS,
        wave_window=0.002, guard=guard,
    )
    server.start()
    stop = threading.Event()
    flood_events = [0]

    def flood():
        adv = AdversarialPeer(server.address, "sidecar", close_wait=5.0)
        while not stop.is_set():
            try:
                adv.wrong_hmac_flood(1)
                flood_events[0] += 1
            except OSError:
                pass

    flooder = threading.Thread(target=flood, daemon=True)
    flooder.start()
    try:
        client = _tenant_client(server.address, "alpha")
        try:
            for i in range(25):
                pattern = [b"good" if j % 2 else b"bad" for j in range(8)]
                out = client.verify_batch([b"m"] * 8, pattern, [b"k"] * 8)
                assert list(out) == [s == b"good" for s in pattern], (
                    f"honest verify {i} corrupted under handshake flood"
                )
        finally:
            client.close()
    finally:
        stop.set()
        flooder.join(timeout=10.0)
        server.stop()

    assert flood_events[0] > 0, "the flood never ran"
    # Every failed proof was booked as a bad_hello strike, exactly once.
    assert guard.stats.malformed >= flood_events[0]
    assert guard.stats.bans == 0  # under the limit by construction
