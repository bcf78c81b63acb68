"""Fabric-BFT-orderer-shaped embedder demo (BASELINE config 5).

The reference's canonical embedder is the Hyperledger Fabric BFT orderer:
Fabric implements the ~10 dependency ports around ``pkg/consensus`` —
envelopes in, hash-chained blocks out, per-consenter block signatures
(reference pkg/api/dependencies.go:14-99; README.md names Fabric as the
consumer).  A REAL Fabric integration is out of scope in this environment
(no Fabric tree, no Go toolchain); this
example is the Fabric-SHAPED embedding: every port implemented the way the
orderer implements it, against this framework's API, so an embedder can
see the whole integration surface in ~200 lines.

Shape parity with the orderer:

* **Envelope ingress** — opaque 256-byte client envelopes; RequestID =
  (channel, txid) parsed from the envelope header.
* **Block cutting** — the Assembler cuts a Fabric-style block: header
  ``(number, prev_hash, data_hash)``, data = the envelope batch; the hash
  chain binds block n to block n-1 (orderer blockcutter + block factory).
* **Delivery** — Deliver appends the block to the channel ledger after
  checking the chain linkage; consenter signatures ride the block metadata
  the way Fabric stores BlockSignature.
* **Identity** — each orderer node signs blocks with its Ed25519 key
  (Fabric: MSP identities); commit signatures are batch-verified through
  the TPU engine seam.

Run (in-process cluster over real localhost TCP, realtime schedulers):

    python examples/fabric_orderer.py [--n 10] [--seconds 5] [--rate 50000]

Prints one JSON line with the achieved ordering throughput vs the 50k
tx/s config-5 target.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import struct
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from examples._cluster import start_feeder, start_replicas, teardown
from consensus_tpu.config import Configuration
from consensus_tpu.models import Ed25519Signer, Ed25519VerifierMixin
from consensus_tpu.models.ed25519 import Ed25519BatchVerifier
from consensus_tpu.testing.app import TestApp, pack_batch, unpack_batch
from consensus_tpu.types import Proposal, RequestInfo

ENVELOPE_BYTES = 256
_HEADER = struct.Struct(">QQ32s32s")  # block number | tx count | prev | data


def make_envelope(channel: str, txid: int) -> bytes:
    """A Fabric-ish envelope: channel header (channel, txid) + payload,
    padded to exactly ENVELOPE_BYTES."""
    head = struct.pack(">16sQ", channel.encode()[:16].ljust(16, b"\0"), txid)
    body = head + b"tx-payload"
    return body.ljust(ENVELOPE_BYTES, b"\xee")


def parse_envelope(raw: bytes) -> RequestInfo:
    if len(raw) != ENVELOPE_BYTES:
        raise ValueError(f"envelope must be {ENVELOPE_BYTES} bytes")
    channel, txid = struct.unpack_from(">16sQ", raw, 0)
    return RequestInfo(
        client_id=channel.rstrip(b"\0").decode(), request_id=str(txid)
    )


class _OrdererVerifier(Ed25519VerifierMixin):
    """Consenter-signature half of the Verifier port (the app half lives in
    FabricShapedOrderer)."""

    def verify_proposal(self, proposal):
        raise NotImplementedError

    def verify_request(self, raw):
        raise NotImplementedError

    def verification_sequence(self):
        return 0

    def requests_from_proposal(self, proposal):
        return []


class FabricShapedOrderer(TestApp):
    """All ten ports, implemented the way the Fabric BFT orderer shapes
    them: envelope inspector, block-cutting assembler, hash-chain-checked
    delivery, Ed25519 consenter signatures over block digests."""

    def __init__(self, node_id, cluster, signer, verifier):
        super().__init__(node_id, cluster)
        self._signer = signer
        self._verifier = verifier

    # --- RequestInspector (envelope header -> (channel, txid)) -----------
    class _Inspector:
        def request_id(self, raw: bytes) -> RequestInfo:
            return parse_envelope(raw)

    @property
    def inspector(self):
        return self._Inspector()

    @inspector.setter
    def inspector(self, value):  # TestApp.__init__ assigns; ignore
        pass

    # --- Assembler: cut a Fabric-style block -----------------------------
    def assemble_proposal(self, metadata: bytes, requests) -> Proposal:
        data = pack_batch(requests)
        prev = (
            hashlib.sha256(self.ledger[-1].proposal.header).digest()
            if self.ledger
            else b"\0" * 32
        )
        header = _HEADER.pack(
            len(self.ledger), len(requests), prev, hashlib.sha256(data).digest()
        )
        return Proposal(
            payload=data, header=header, metadata=metadata,
            verification_sequence=0,
        )

    # --- Verifier: block structure + envelope well-formedness ------------
    def verify_proposal(self, proposal: Proposal):
        number, count, prev, data_hash = _HEADER.unpack(proposal.header)
        if hashlib.sha256(proposal.payload).digest() != data_hash:
            raise ValueError("block data hash mismatch")
        # Depth-1 pipelining means a proposal for block n+1 can be verified
        # before block n is delivered; its prev-hash is only checkable at
        # delivery time.  Everything else is rejected outright.
        if number == len(self.ledger):
            expected_prev = (
                hashlib.sha256(self.ledger[-1].proposal.header).digest()
                if self.ledger
                else b"\0" * 32
            )
            if prev != expected_prev:
                raise ValueError("block hash chain broken")
        elif number != len(self.ledger) + 1:
            raise ValueError(
                f"unexpected block number {number} (ledger at {len(self.ledger)})"
            )
        envelopes = unpack_batch(proposal.payload)
        if len(envelopes) != count:
            raise ValueError("tx count mismatch")
        return [parse_envelope(e) for e in envelopes]

    def verify_request(self, raw: bytes) -> RequestInfo:
        return parse_envelope(raw)

    def requests_from_proposal(self, proposal: Proposal):
        return [parse_envelope(e) for e in unpack_batch(proposal.payload)]

    # --- Signer / consenter-signature verification (Ed25519, batched) ----
    def sign(self, data: bytes) -> bytes:
        return self._signer.sign(data)

    def sign_proposal(self, proposal: Proposal, aux: bytes = b""):
        return self._signer.sign_proposal(proposal, aux)

    def verify_consenter_sig(self, signature, proposal):
        return self._verifier.verify_consenter_sig(signature, proposal)

    def verify_consenter_sigs_batch(self, signatures, proposal):
        return self._verifier.verify_consenter_sigs_batch(signatures, proposal)

    def verify_consenter_sigs_multi_batch(self, groups):
        # Catch-up path: drain a whole sync chunk's certs through the
        # engine in one batch instead of the ABC's per-proposal loop.
        return self._verifier.verify_consenter_sigs_multi_batch(groups)

    def verify_signature(self, signature) -> None:
        self._verifier.verify_signature(signature)

    def auxiliary_data(self, msg: bytes) -> bytes:
        return msg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--warmup", type=float, default=3.0)
    ap.add_argument("--rate", type=int, default=50_000,
                    help="config-5 target tx/s (reported against)")
    ap.add_argument("--batch", type=int, default=500)
    ap.add_argument("--envelopes", type=int, default=60_000)
    args = ap.parse_args()

    node_ids = list(range(1, args.n + 1))
    engine = Ed25519BatchVerifier(min_device_batch=10**9)  # host path
    signers = {i: Ed25519Signer(i) for i in node_ids}
    keys = {i: s.public_bytes for i, s in signers.items()}

    def make_app(node_id, cluster):
        return FabricShapedOrderer(
            node_id, cluster, signers[node_id], _OrdererVerifier(keys, engine=engine)
        )

    def make_config(node_id):
        return Configuration(
            self_id=node_id,
            request_batch_max_count=args.batch,
            request_batch_max_bytes=args.batch * ENVELOPE_BYTES * 2,
            request_batch_max_interval=0.05,
            request_pool_size=max(2000, 3 * args.batch),
            request_max_bytes=ENVELOPE_BYTES,
        )

    cluster, replicas, comms, schedulers = start_replicas(
        args.n, make_app, make_config
    )
    envelopes = [make_envelope("demo", i) for i in range(args.envelopes)]
    stop, exhausted = start_feeder(
        replicas[1], envelopes, inflight=max(1500, 2 * args.batch)
    )

    ledger = cluster.nodes[1].app.ledger
    time.sleep(args.warmup)
    t0, start_blocks = time.time(), len(ledger)
    start_tx = sum(
        _HEADER.unpack(d.proposal.header)[1] for d in ledger
    )
    time.sleep(args.seconds)
    elapsed = time.time() - t0
    end_tx = sum(_HEADER.unpack(d.proposal.header)[1] for d in ledger)
    tx_per_sec = (end_tx - start_tx) / elapsed
    stop.set()

    # The hash chain held on every replica (the delivery-side check ran on
    # the hot path; re-assert here end-to-end).
    for holder in cluster.nodes.values():
        prev = b"\0" * 32
        for d in holder.app.ledger:
            number, count, prev_hash, data_hash = _HEADER.unpack(d.proposal.header)
            assert prev_hash == prev, "hash chain broken"
            assert hashlib.sha256(d.proposal.payload).digest() == data_hash
            prev = hashlib.sha256(d.proposal.header).digest()

    print(
        json.dumps(
            {
                "metric": "fabric_shaped_orderer_tx_per_sec",
                "value": round(tx_per_sec, 1),
                "unit": "tx/sec",
                "n": args.n,
                "envelope_bytes": ENVELOPE_BYTES,
                "target_tx_per_sec": args.rate,
                "target_attained": round(tx_per_sec / args.rate, 4),
                "blocks": len(ledger) - start_blocks,
                "hash_chain_verified": True,
            }
        )
    )
    teardown(replicas, comms, schedulers, cluster)


if __name__ == "__main__":
    main()
