"""End-to-end: a consensus cluster running REAL Ed25519 crypto through the
batch-verification engine — the full TPU seam exercised inside the protocol
(commit quorums and prev-commit signatures verified as device batches).

One shared engine serves all replicas (compile once); on the CPU test
backend this is slow-ish but proves the integration the bench measures.
"""

import numpy as np

from consensus_tpu.models import Ed25519BatchVerifier, Ed25519Signer, Ed25519VerifierMixin
from consensus_tpu.testing import Cluster, make_request
from consensus_tpu.testing.crypto_app import CryptoApp


class CountingEngine(Ed25519BatchVerifier):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.calls = 0
        self.items = 0

    def verify_batch(self, messages, signatures, public_keys) -> np.ndarray:
        self.calls += 1
        self.items += len(messages)
        return super().verify_batch(messages, signatures, public_keys)



class _SigVerifier(Ed25519VerifierMixin):
    def verify_proposal(self, proposal):
        raise NotImplementedError  # app half lives in CryptoApp

    def verify_request(self, raw):
        raise NotImplementedError

    def verification_sequence(self):
        return 0

    def requests_from_proposal(self, proposal):
        return []


def test_cluster_orders_with_real_ed25519_signatures():
    cluster = Cluster(4)
    engine = CountingEngine()
    signers = {i: Ed25519Signer(i) for i in cluster.nodes}
    keys = {i: s.public_bytes for i, s in signers.items()}
    for node_id, node in cluster.nodes.items():
        node.app = CryptoApp(
            node_id, cluster, signers[node_id], _SigVerifier(keys, engine=engine)
        )
    cluster.start()

    for i in range(3):
        cluster.submit_to_all(make_request("c", i))
        assert cluster.run_until_ledger(i + 1, max_time=300.0), f"block {i} stalled"
    cluster.assert_ledgers_consistent()

    # Every decision carries a quorum of REAL signatures that verify under
    # the registered public keys.
    from consensus_tpu.models.verifier import commit_message

    for node in cluster.nodes.values():
        for decision in node.app.ledger:
            assert len(decision.signatures) >= 3
            msgs = [commit_message(decision.proposal, s.msg) for s in decision.signatures]
            ok = Ed25519BatchVerifier(min_device_batch=10**9).verify_batch(
                msgs,
                [s.value for s in decision.signatures],
                [keys[s.id] for s in decision.signatures],
            )
            assert ok.all(), "ledger carries an invalid signature"

    # The protocol actually drained signatures through the batch engine.
    assert engine.calls > 0
    assert engine.items >= 3 * 4 * 2  # >= quorum-1 commits per decision per node


def test_forged_commit_rejected_by_real_crypto():
    cluster = Cluster(4)
    engine = CountingEngine()
    signers = {i: Ed25519Signer(i) for i in cluster.nodes}
    keys = {i: s.public_bytes for i, s in signers.items()}
    # Node 4 uses a key nobody registered: its commits must be rejected,
    # but the other three still form a quorum.
    rogue = Ed25519Signer(4)
    signers[4] = rogue
    for node_id, node in cluster.nodes.items():
        node.app = CryptoApp(
            node_id, cluster, signers[node_id], _SigVerifier(keys, engine=engine)
        )
    cluster.start()
    cluster.submit_to_all(make_request("c", 0))
    assert cluster.run_until_ledger(1, node_ids=[1, 2, 3], max_time=300.0)
    for node_id in (1, 2, 3):
        decision = cluster.nodes[node_id].app.ledger[0]
        assert 4 not in {s.id for s in decision.signatures}, (
            "forged signature entered the quorum"
        )


def test_signed_requests_batch_verified_per_proposal():
    """SignedRequestApp: client-request signatures are verified as ONE
    engine batch per proposal, and tampered requests are rejected."""
    import pytest

    from consensus_tpu.models import Ed25519Signer
    from consensus_tpu.testing import ClientKeyring, Cluster, SignedRequestApp

    cluster = Cluster(4)
    engine = CountingEngine(min_device_batch=10**9)  # host path: fast, exact
    signers = {i: Ed25519Signer(i) for i in cluster.nodes}
    keys = {i: s.public_bytes for i, s in signers.items()}
    clients = ClientKeyring([Ed25519Signer(100 + i) for i in range(3)])
    for node_id, node in cluster.nodes.items():
        node.app = SignedRequestApp(
            node_id, cluster, signers[node_id], _SigVerifier(keys, engine=engine),
            client_keys=clients.public_keys, engine=engine,
        )
    cluster.start()

    for i in range(2):
        for c in range(3):
            cluster.submit_to_all(clients.make_request(c, i))
        assert cluster.run_until_ledger(i + 1, max_time=300.0)
    cluster.assert_ledgers_consistent()
    total_reqs = sum(
        int.from_bytes(d.proposal.payload[:4], "big")
        for d in cluster.nodes[1].app.ledger
    )
    assert total_reqs == 6, f"requests lost: only {total_reqs}/6 ordered"
    assert engine.items >= 6  # request sigs actually drained through batches

    # A tampered request never clears ingress.
    app = cluster.nodes[1].app
    bad = bytearray(clients.make_request(0, 99))
    bad[-1] ^= 0xFF
    with pytest.raises(ValueError):
        app.verify_request(bytes(bad))


def test_verify_requests_batch_remaps_around_unparseable_entries():
    """The batch request-verify path must return results aligned with the
    INPUT list even when unparseable entries are interleaved (the pruning
    burst sees arbitrary pool contents)."""
    from consensus_tpu.models import Ed25519Signer
    from consensus_tpu.testing import ClientKeyring, Cluster, SignedRequestApp

    cluster = Cluster(4)
    engine = CountingEngine(min_device_batch=10**9)
    signer = Ed25519Signer(1)
    clients = ClientKeyring([Ed25519Signer(100 + i) for i in range(2)])
    keys = {1: signer.public_bytes}
    app = SignedRequestApp(
        1, cluster, signer, _SigVerifier(keys, engine=engine),
        client_keys=clients.public_keys, engine=engine,
    )

    good0 = clients.make_request(0, 7)
    good1 = clients.make_request(1, 8)
    bad_sig = bytearray(clients.make_request(0, 9))
    bad_sig[-1] ^= 0xFF
    raws = [b"short", good0, b"\x00" * 200, bytes(bad_sig), good1]
    out = app.verify_requests_batch(raws)
    assert out[0] is None            # too short to parse
    assert out[1] is not None and out[1].request_id == "7"
    assert out[2] is None            # unknown client index
    assert out[3] is None            # parseable but invalid signature
    assert out[4] is not None and out[4].request_id == "8"
    assert engine.calls == 1, "one engine batch for the whole list"


def test_wedged_device_cluster_completes_via_host_fallback():
    """VERDICT r3 #3: a hung device call must not wedge the
    replicas.  Every replica's verifier rides a ThreadCoalescingVerifier
    whose device path NEVER returns; the escape hatch (host fallback after
    ``wait_timeout``) must let the cluster keep deciding within protocol
    timeouts."""
    import threading

    from consensus_tpu.models import ThreadCoalescingVerifier

    class HungEngine(Ed25519BatchVerifier):
        """Device path hangs forever; host path (verify_host) inherited."""

        def __init__(self):
            super().__init__()
            self.never = threading.Event()

        def verify_batch(self, messages, signatures, public_keys):
            self.never.wait()  # simulates a hung device call: no return, no error

    hung = HungEngine()
    coalescer = ThreadCoalescingVerifier(hung, window=0.002, wait_timeout=0.2)
    cluster = Cluster(4)
    signers = {i: Ed25519Signer(i) for i in cluster.nodes}
    keys = {i: s.public_bytes for i, s in signers.items()}
    for node_id, node in cluster.nodes.items():
        node.app = CryptoApp(
            node_id, cluster, signers[node_id], _SigVerifier(keys, engine=coalescer)
        )
    cluster.start()

    for i in range(2):
        cluster.submit_to_all(make_request("c", i))
        assert cluster.run_until_ledger(i + 1, max_time=300.0), (
            f"block {i} stalled behind the wedged device"
        )
    cluster.assert_ledgers_consistent()
    assert coalescer.device_suspect, "escape hatch should have tripped"
    hung.never.set()  # let the stuck flusher thread exit


def test_fused_request_and_cert_waves_halve_launches_per_decision():
    """Satellite of the mesh/multi-tenant PR (ROADMAP item 3a tail):
    client-request waves coalesce with the consenter-cert sweep — when the
    app and the verifier mixin share ONE engine, each proposal verification
    drains request signatures AND prev-commit certs in a single
    ``verify_batch`` launch.  Launch-histogram regression: the fused wiring
    must launch strictly fewer (and larger) batches than split engines on
    the identical workload, with identical ledgers."""
    from consensus_tpu.models import Ed25519Signer
    from consensus_tpu.testing import ClientKeyring, Cluster, SignedRequestApp

    class SizedEngine(CountingEngine):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.sizes = []

        def verify_batch(self, messages, signatures, public_keys):
            self.sizes.append(len(messages))
            return super().verify_batch(messages, signatures, public_keys)

    def run(fused: bool):
        cluster = Cluster(4, seed=77)
        app_engine = SizedEngine(min_device_batch=10**9)
        sig_engine = app_engine if fused else SizedEngine(min_device_batch=10**9)
        signers = {i: Ed25519Signer(i, bytes([i + 1] * 32)) for i in cluster.nodes}
        keys = {i: s.public_bytes for i, s in signers.items()}
        clients = ClientKeyring(
            [Ed25519Signer(100 + i, bytes([100 + i] * 32)) for i in range(3)]
        )
        for node_id, node in cluster.nodes.items():
            node.app = SignedRequestApp(
                node_id, cluster, signers[node_id],
                _SigVerifier(keys, engine=sig_engine),
                client_keys=clients.public_keys, engine=app_engine,
            )
        cluster.start()
        for i in range(3):
            for c in range(3):
                cluster.submit_to_all(clients.make_request(c, i))
            assert cluster.run_until_ledger(i + 1, max_time=300.0)
        cluster.assert_ledgers_consistent()
        ledger = [d.proposal.payload for d in cluster.nodes[1].app.ledger]
        launches = app_engine.calls + (0 if fused else sig_engine.calls)
        sizes = sorted(app_engine.sizes + ([] if fused else sig_engine.sizes))
        return ledger, launches, sizes

    fused_ledger, fused_launches, fused_sizes = run(fused=True)
    split_ledger, split_launches, split_sizes = run(fused=False)
    assert fused_ledger == split_ledger, "fusing changed what was ordered"
    assert fused_launches < split_launches, (
        f"fused wiring did not reduce launches: {fused_launches} vs "
        f"{split_launches}"
    )
    # The histogram shifted to fewer, larger batches: the fused run's
    # biggest wave carries requests + certs together.
    assert max(fused_sizes) > max(split_sizes)
    assert sum(fused_sizes) == sum(split_sizes), "fusing changed total work"
