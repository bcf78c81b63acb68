"""Traffic: requests made from the seed, and when each one is due.

One general generator.  A traffic mix is a data file under ``traffic/``:

* ``{"mode": "closed", "outstanding_batches": 2, "chunks_per_batch": 10,
  "warm_batches": 4, ...}`` — at most that many proposals' worth outstanding
  (sent and not yet committed by the slowest replica), sent in chunks; the
  window opens at the commit instant that ends the warm batches.
* ``{"mode": "open", "rate_per_s": R, "tick_s": 0.01, "warm_s": 3, ...}`` —
  request ``i`` is due ``i / R`` seconds after the first; the window opens
  ``warm_s`` after the first.

Requests are signed here with OpenSSL (the ``cryptography`` package), in the
deployment's wire format (``>IQ`` client index and sequence, the body, a raw
Ed25519 signature over ``b"ctpu/request" + head``), under the client keys every
process of the rig derives from the spec's ``key_namespace``.  Nothing of the
program is imported: a replica that accepts these bytes agrees with this file
on the format and on the key derivation.
"""

from __future__ import annotations

import hashlib
import json
import os
import struct

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

HERE = os.path.dirname(os.path.abspath(__file__))
REQUEST_TAG = b"ctpu/request"
#: ``--seed`` may be a little over 2**31; everything derived from it goes
#: through SHA-256 of its decimal form, so any whole number works.


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json"), encoding="utf-8") as fh:
        mix = json.load(fh)
    if mix.get("mode") not in ("closed", "open"):
        raise ValueError(f"traffic {name}: mode must be 'closed' or 'open'")
    return mix


def seeded_namespace(seed: int) -> str:
    """The rig's ``key_namespace`` for this seed: every node and client key of
    the run follows from it."""
    return hashlib.sha256(b"served-bench-namespace:%d" % seed).hexdigest()[:16]


def client_seed32(namespace: str, i: int) -> bytes:
    """The deployment's key distribution rule (cluster.json carries only the
    namespace): client ``i`` signs under SHA-256 of this tag."""
    return hashlib.sha256(
        b"ctpu-deploy:%s:%s:%d" % (namespace.encode(), b"client", i)
    ).digest()


class RequestFactory:
    """Signed requests, all of one size, numbered in the order they are sent."""

    def __init__(self, seed: int, clients: int, body_bytes: int) -> None:
        self.namespace = seeded_namespace(seed)
        self.clients = clients
        self.body_bytes = body_bytes
        self._seed = seed
        self._keys = [
            Ed25519PrivateKey.from_private_bytes(client_seed32(self.namespace, i))
            for i in range(clients)
        ]

    def body(self, i: int) -> bytes:
        out = b""
        block = 0
        while len(out) < self.body_bytes:
            out += hashlib.sha256(
                b"served-bench-body:%d:%d:%d" % (self._seed, i, block)
            ).digest()
            block += 1
        return out[: self.body_bytes]

    def make(self, i: int) -> bytes:
        client = i % self.clients
        head = struct.pack(">IQ", client, (client << 32) | i) + self.body(i)
        return head + self._keys[client].sign(REQUEST_TAG + head)

    def make_many(self, count: int) -> list:
        return [self.make(i) for i in range(count)]
