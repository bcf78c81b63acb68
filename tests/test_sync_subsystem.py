"""The catch-up subsystem (consensus_tpu/sync/): store, server, transports,
and the verifying client — including the acceptance scenarios: a
50-decision wire-only catch-up with one batched verifier call per chunk,
and a byzantine sync server that is detected, scored down, and routed
around."""

import struct
from dataclasses import replace

from consensus_tpu.sync import (
    InProcessSyncTransport,
    LedgerDecisionStore,
    LedgerSynchronizer,
    SyncListener,
    SyncServer,
    TcpSyncTransport,
    honest_endorsement_threshold,
)
from consensus_tpu.testing import TestApp, make_request, pack_batch
from consensus_tpu.types import Decision, Proposal
from consensus_tpu.wire import (
    SyncChunk,
    SyncRequest,
    SyncSnapshotMeta,
    ViewMetadata,
    encode_view_metadata,
)

NODES = (1, 2, 3, 4)


def build_chain(length, *, quorum_ids=(1, 3, 4)):
    """A decision chain signed with the harness's toy (content-binding)
    scheme: position i carries ViewMetadata.latest_sequence == i and a
    3-of-4 commit cert."""
    signers = {i: TestApp(i, None) for i in quorum_ids}
    chain = []
    for seq in range(1, length + 1):
        proposal = Proposal(
            payload=pack_batch([make_request("chain", seq)]),
            header=struct.pack(">Q", seq - 1),
            metadata=encode_view_metadata(
                ViewMetadata(view_id=0, latest_sequence=seq, decisions_in_view=seq)
            ),
        )
        sigs = tuple(signers[i].sign_proposal(proposal) for i in quorum_ids)
        chain.append(Decision(proposal=proposal, signatures=sigs))
    return chain


class _OpenNetwork:
    """Reachability stub: everyone can talk to everyone."""

    def __init__(self, ids=NODES):
        self._ids = list(ids)

    def node_ids(self):
        return list(self._ids)

    def reachable(self, a, b):
        return True


class _CountingVerifier:
    """Wraps the toy verifier, counting batched multi-proposal calls — the
    acceptance criterion is ONE call per chunk."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = 0
        self.group_sizes = []

    def verify_consenter_sigs_multi_batch(self, groups):
        self.calls += 1
        self.group_sizes.append(len(groups))
        return self.inner.verify_consenter_sigs_multi_batch(groups)


def _client(store, transport, *, verifier=None, **kw):
    return LedgerSynchronizer(
        node_id=2,
        store=store,
        transport=transport,
        verifier=verifier if verifier is not None else TestApp(2, None),
        nodes=NODES,
        **kw,
    )


# --- store ------------------------------------------------------------------


def test_ledger_store_ranged_reads_and_clamping():
    chain = build_chain(5)
    store = LedgerDecisionStore(list(chain))
    assert store.height() == 5
    assert store.read(1, 5) == chain
    assert store.read(2, 3) == chain[1:3]
    assert store.read(4, 99) == chain[3:]  # clamped to height
    assert store.read(6, 9) == []
    assert store.read(3, 2) == []
    assert store.last() == chain[-1]
    store.append(build_chain(6)[-1])
    assert store.height() == 6


def test_empty_store():
    store = LedgerDecisionStore([])
    assert store.height() == 0
    assert store.last() is None
    assert store.read(1, 10) == []


# --- server -----------------------------------------------------------------


def test_server_meta_probe_and_out_of_range():
    chain = build_chain(3)
    server = SyncServer(LedgerDecisionStore(list(chain)))
    meta = server.handle(SyncRequest(from_seq=1, to_seq=0))
    assert isinstance(meta, SyncSnapshotMeta)
    assert meta.height == 3
    assert meta.last_digest == chain[-1].proposal.digest()
    # A range starting above the height is a probe too.
    assert isinstance(server.handle(SyncRequest(from_seq=4, to_seq=9)), SyncSnapshotMeta)
    empty = SyncServer(LedgerDecisionStore([]))
    meta = empty.handle(SyncRequest(from_seq=1, to_seq=0))
    assert meta.height == 0 and meta.last_digest == ""


def test_server_chunk_count_cap():
    chain = build_chain(10)
    server = SyncServer(LedgerDecisionStore(list(chain)), max_chunk_decisions=4)
    chunk = server.handle(SyncRequest(from_seq=1, to_seq=10))
    assert isinstance(chunk, SyncChunk)
    assert chunk.from_seq == 1
    assert chunk.height == 10
    assert len(chunk.decisions) == 4
    assert [d.digest() for d in chunk.decisions] == [
        d.proposal.digest() for d in chain[:4]
    ]
    assert chunk.quorum_certs == tuple(d.signatures for d in chain[:4])


def test_server_chunk_byte_cap_serves_at_least_one():
    chain = build_chain(6)
    # A byte budget far below one decision: flow control must still make
    # progress one decision at a time, never an empty chunk.
    server = SyncServer(LedgerDecisionStore(list(chain)), max_chunk_bytes=8)
    chunk = server.handle(SyncRequest(from_seq=3, to_seq=6))
    assert len(chunk.decisions) == 1
    assert chunk.from_seq == 3
    assert chunk.decisions[0].digest() == chain[2].proposal.digest()


# --- client: the 50-decision wire catch-up (acceptance) ---------------------


def _wire_setup(chain, *, server_cls=SyncServer, byzantine_peer=None):
    """Three peers serving ``chain`` over the in-process wire transport;
    ``byzantine_peer`` (if given) gets ``server_cls`` instead of the honest
    one."""
    servers = {}
    for peer in (1, 3, 4):
        cls = server_cls if peer == byzantine_peer else SyncServer
        servers[peer] = cls(LedgerDecisionStore(list(chain)))
    transport = InProcessSyncTransport(2, _OpenNetwork(), servers)
    return servers, transport


def test_empty_replica_catches_up_50_decisions_over_wire():
    """A lagging replica with an EMPTY ledger reaches a 50-decision chain
    purely over the wire transport (every byte crosses encode->decode; the
    client never touches peer memory), with every chunk's certs verified in
    ONE batched verifier call."""
    chain = build_chain(50)
    servers, transport = _wire_setup(chain)
    ledger = []
    counting = _CountingVerifier(TestApp(2, None))
    from consensus_tpu.metrics import InMemoryProvider, Metrics

    provider = InMemoryProvider()
    client = _client(
        LedgerDecisionStore(ledger), transport,
        verifier=counting, metrics=Metrics(provider).sync,
    )
    response = client.sync()

    assert len(ledger) == 50
    assert [d.proposal.digest() for d in ledger] == [
        d.proposal.digest() for d in chain
    ]
    assert [d.signatures for d in ledger] == [d.signatures for d in chain]
    assert response.latest is not None
    assert response.latest.proposal.digest() == chain[-1].proposal.digest()

    # One multi-batch verifier call per chunk: 50 decisions / 32-window
    # server caps = 2 chunks, 3 sigs per decision.
    assert counting.calls == 2
    assert counting.group_sizes == [32, 18]
    assert provider.value("sync_count_chunks_fetched") == 2
    assert provider.value("sync_count_decisions_fetched") == 50
    assert provider.value("sync_count_sig_verifications") == 150
    assert provider.observations("sync_sigs_per_chunk") == [96, 54]
    assert len(provider.observations("sync_latency_catchup")) == 1


def test_partial_replica_fetches_only_the_tail():
    chain = build_chain(20)
    servers, transport = _wire_setup(chain)
    ledger = list(chain[:12])
    client = _client(LedgerDecisionStore(ledger), transport)
    client.sync()
    assert len(ledger) == 20
    assert [d.proposal.digest() for d in ledger] == [
        d.proposal.digest() for d in chain
    ]


def test_already_current_replica_is_a_noop():
    chain = build_chain(7)
    servers, transport = _wire_setup(chain)
    ledger = list(chain)
    client = _client(LedgerDecisionStore(ledger), transport)
    response = client.sync()
    assert len(ledger) == 7
    assert response.latest.proposal.digest() == chain[-1].proposal.digest()
    assert all(s.chunks_served == 0 for s in servers.values())


# --- client vs byzantine servers --------------------------------------------


class ForgingServer(SyncServer):
    """Serves chunks with the FIRST decision's payload tampered — the
    commit cert no longer matches the content."""

    def handle(self, request):
        reply = super().handle(request)
        if isinstance(reply, SyncChunk) and reply.decisions:
            forged = replace(
                reply.decisions[0], payload=reply.decisions[0].payload + b"|evil"
            )
            return replace(reply, decisions=(forged,) + reply.decisions[1:])
        return reply


class OmittingServer(SyncServer):
    """Serves chunks with the first decision dropped but still labeled
    ``from_seq`` — an offset/truncation attack on position addressing."""

    def handle(self, request):
        reply = super().handle(request)
        if isinstance(reply, SyncChunk) and len(reply.decisions) > 1:
            return replace(
                reply,
                decisions=reply.decisions[1:],
                quorum_certs=reply.quorum_certs[1:],
            )
        return reply


class UndersignedServer(SyncServer):
    """Strips certs down to a single signature — below every acceptance
    threshold (f + 1 == 2 at n == 4)."""

    def handle(self, request):
        reply = super().handle(request)
        if isinstance(reply, SyncChunk):
            return replace(
                reply, quorum_certs=tuple(c[:1] for c in reply.quorum_certs)
            )
        return reply


def _byzantine_case(server_cls):
    """Peer 1 (the client's FIRST choice: equal scores, lowest id) is
    byzantine; the sync must reject its data, demote it, and complete from
    the honest peers 3 and 4."""
    chain = build_chain(50)
    servers, transport = _wire_setup(chain, server_cls=server_cls, byzantine_peer=1)
    ledger = []
    client = _client(LedgerDecisionStore(ledger), transport)
    response = client.sync()

    assert len(ledger) == 50, "sync did not complete from the honest peers"
    assert [d.proposal.digest() for d in ledger] == [
        d.proposal.digest() for d in chain
    ], "byzantine data leaked into the chain"
    assert response.latest.proposal.digest() == chain[-1].proposal.digest()
    # The byzantine peer was scored down hard, below any fetch-failure
    # demotion an honest peer could ever accumulate in one call.
    assert client.scores.get(1, 0.0) <= -100.0
    assert servers[1].chunks_served >= 1, "the byzantine peer was never even tried"


def test_forged_decision_rejected_and_routed_around():
    _byzantine_case(ForgingServer)


def test_omitted_decision_rejected_and_routed_around():
    _byzantine_case(OmittingServer)


def test_undersigned_cert_rejected_and_routed_around():
    _byzantine_case(UndersignedServer)


def test_all_peers_byzantine_sync_stops_without_applying():
    chain = build_chain(10)
    servers = {p: ForgingServer(LedgerDecisionStore(list(chain))) for p in (1, 3, 4)}
    transport = InProcessSyncTransport(2, _OpenNetwork(), servers)
    ledger = []
    client = _client(LedgerDecisionStore(ledger), transport)
    response = client.sync()
    assert ledger == [], "forged decisions were applied"
    assert response.latest is None


def test_threshold_default_is_f_plus_one():
    assert honest_endorsement_threshold(4) == 2
    assert honest_endorsement_threshold(7) == 3
    # A stricter policy can be injected (full commit quorum).
    chain = build_chain(10, quorum_ids=(1,))  # 1-signature certs
    servers, transport = _wire_setup(chain)
    ledger = []
    client = _client(LedgerDecisionStore(ledger), transport)
    client.sync()
    assert ledger == []  # 1 < f+1: rejected by default policy too


def test_down_peer_is_skipped():
    chain = build_chain(8)
    servers, transport = _wire_setup(chain)
    del servers[1]  # peer 1 crashed: no server registered
    ledger = []
    client = _client(LedgerDecisionStore(ledger), transport)
    client.sync()
    assert len(ledger) == 8
    assert client.scores.get(1, 0.0) < 0  # probe failure demoted it


# --- TCP transport ----------------------------------------------------------


def test_tcp_sync_transport_end_to_end():
    """The same 50-decision catch-up over REAL sockets: SyncListener per
    peer, TcpSyncTransport on the client, ephemeral ports."""
    chain = build_chain(50)
    listeners = {
        peer: SyncListener(SyncServer(LedgerDecisionStore(list(chain))))
        for peer in (1, 3, 4)
    }
    try:
        addresses = {p: lst.address for p, lst in listeners.items()}
        transport = TcpSyncTransport(2, addresses, timeout=5.0)
        assert transport.peers() == [1, 3, 4]
        ledger = []
        client = _client(LedgerDecisionStore(ledger), transport)
        response = client.sync()
        assert len(ledger) == 50
        assert [d.proposal.digest() for d in ledger] == [
            d.proposal.digest() for d in chain
        ]
        assert response.latest.proposal.digest() == chain[-1].proposal.digest()
        # An unreachable peer is a scored-down fetch failure, not an error.
        transport.addresses[9] = ("127.0.0.1", 1)  # nothing listens there
        assert transport.fetch(9, SyncRequest(from_seq=1, to_seq=0)) is None
    finally:
        for lst in listeners.values():
            lst.close()


def test_tcp_listener_rejects_garbage_and_keeps_serving():
    import socket as socket_mod

    chain = build_chain(3)
    listener = SyncListener(SyncServer(LedgerDecisionStore(list(chain))))
    try:
        # Garbage frame: the listener must drop the conn and keep serving.
        with socket_mod.create_connection(listener.address, timeout=2.0) as conn:
            conn.sendall(struct.pack(">I", 4) + b"junk")
            conn.settimeout(1.0)
            try:
                assert conn.recv(64) == b""
            except OSError:
                pass  # reset is as good as close
        transport = TcpSyncTransport(2, {1: listener.address})
        reply = transport.fetch(1, SyncRequest(from_seq=1, to_seq=0))
        assert isinstance(reply, SyncSnapshotMeta) and reply.height == 3
    finally:
        listener.close()


# --- what a sync appended is reported, and leaves the controller's pool ------
#
# SyncResponse.synced carries every decision this call applied through
# _verify_and_apply, in chain order; Controller._forget_synced removes their
# requests from the pool (tests/test_sync_then_lead.py: why).

import pytest


@pytest.mark.parametrize(
    "have,total", [(0, 50), (12, 20), (7, 7), (0, 1)],
    ids=["empty-50", "tail-8", "current", "one"])
def test_sync_reports_exactly_the_decisions_it_appended(have, total):
    chain = build_chain(total)
    _, transport = _wire_setup(chain)
    ledger = list(chain[:have])
    response = _client(LedgerDecisionStore(ledger), transport).sync()
    assert [d.proposal.digest() for d in response.synced] == [
        d.proposal.digest() for d in chain[have:]
    ]
    assert list(response.synced) == ledger[have:]
    # a second call has nothing left to report
    assert _client(LedgerDecisionStore(ledger), transport).sync().synced == ()


@pytest.mark.parametrize(
    "server_cls", [ForgingServer, OmittingServer, UndersignedServer],
    ids=["forged", "omitted", "undersigned"])
def test_rejected_chunks_are_not_reported_as_synced(server_cls):
    chain = build_chain(40)
    _, transport = _wire_setup(chain, server_cls=server_cls, byzantine_peer=1)
    ledger = []
    response = _client(LedgerDecisionStore(ledger), transport).sync()
    assert list(response.synced) == ledger and len(ledger) == 40
    assert [d.proposal.digest() for d in response.synced] == [
        d.proposal.digest() for d in chain
    ]
    # every peer byzantine: nothing applied, nothing reported
    servers = {p: server_cls(LedgerDecisionStore(list(chain))) for p in (1, 3, 4)}
    only_bad = InProcessSyncTransport(2, _OpenNetwork(), servers)
    assert _client(LedgerDecisionStore([]), only_bad).sync().synced == ()


@pytest.mark.parametrize("k", [0, 1, 40])
@pytest.mark.parametrize("entry", ["do_sync", "deliver_checked"])
def test_controller_pool_forgets_what_the_ledger_synchronizer_fetched(entry, k):
    from test_controller_sync import Harness

    have = 3
    chain = build_chain(have + k)
    _, transport = _wire_setup(chain)
    h = Harness()
    h.app.ledger.extend(chain[:have])
    h.checkpoint.set(chain[have - 1].proposal, chain[have - 1].signatures)
    h.controller._synchronizer = _client(LedgerDecisionStore(h.app.ledger), transport)
    h.start(view=0, seq=have + 1, dec=have + 1)
    fetched = [make_request("chain", seq) for seq in range(have + 1, have + k + 1)]
    waiting = [make_request("later", 1), make_request("later", 2)]
    for raw in fetched + waiting:
        h.controller.pool.submit(raw)

    if entry == "do_sync":
        h.controller.sync()
        h.sched.advance(0.05)
    else:
        h.controller.deliver(chain[have - 1].proposal, chain[have - 1].signatures)

    assert len(h.app.ledger) == have + k
    assert h.controller.latest_seq() == have + k
    assert h.controller.pool.next_requests(100, 10**6) == waiting
    refused = []
    for raw in fetched:
        h.controller.pool.submit(raw, refused.append)
    assert refused == ["request already exists"] * k
    health = h.controller.health()
    assert (health["syncs"], health["synced_decisions"],
            health["sync_pool_removed"]) == (1, k, k)
