"""The plain reference: what a correct run must have produced.

Independent of the program: OpenSSL's Ed25519 (the ``cryptography`` package)
and ``hashlib`` only.  It states, from the requests the run sent,

* the set every replica must have delivered, exactly once
  (:func:`ids_digest`, the order-free digest of the ``(client, seq)`` heads),
* the digest of the send order (:func:`ordered_digest`) — equal to a
  replica's ordered digest iff requests committed in the order they were
  sent, which is what lets the k-th commit be read as the k-th request, and
* the verdict of every lane of a wave that carries one lane of each
  rejection class (:func:`verdict_wave`, :func:`verify_one`).
"""

from __future__ import annotations

import hashlib
import random

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
    Ed25519PublicKey,
)

#: Curve25519's field prime and the group order of Ed25519 (RFC 8032).
P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
_D = (-121665 * pow(121666, P - 2, P)) % P


def ids_digest(raw_requests) -> str:
    h = hashlib.sha256()
    for head in sorted({bytes(raw[:12]) for raw in raw_requests}):
        h.update(head)
    return h.hexdigest()


def ordered_digest(raw_requests) -> str:
    h = hashlib.sha256()
    for raw in raw_requests:
        h.update(raw)
    return h.hexdigest()


def verify_one(message: bytes, signature: bytes, public_key: bytes) -> bool:
    """RFC 8032 verification by OpenSSL; anything malformed is a rejection."""
    try:
        Ed25519PublicKey.from_public_bytes(public_key).verify(signature, message)
    except (InvalidSignature, ValueError):
        return False
    return True


def _has_x(y: int) -> bool:
    """Whether ``y`` is the y-coordinate of a curve point."""
    u = (y * y - 1) % P
    v = (_D * y * y + 1) % P
    x2 = (u * pow(v, P - 2, P)) % P
    return x2 == 0 or pow(x2, (P - 1) // 2, P) == 1


def verdict_wave(lanes: int, seed: int, index: int = 0):
    """``lanes`` (message, signature, key) triples — honest signatures of 16
    seeded signers with one lane of every rejection class planted at seeded
    positions — and ``{position: class}``.  ``index`` tells the waves of one
    run apart: other messages, other positions, the same signers."""

    def seed32(tag: bytes, i: int) -> bytes:
        return hashlib.sha256(b"served-bench-wave:%d:%s:%d" % (seed, tag, i)).digest()

    signers = [Ed25519PrivateKey.from_private_bytes(seed32(b"signer", i))
               for i in range(16)]
    publics = [s.public_key().public_bytes_raw() for s in signers]
    msgs, sigs, keys = [], [], []
    for i in range(lanes):
        m = b"ctpu/served-bench/%d/%d/%d" % (seed, index, i)
        msgs.append(m)
        sigs.append(signers[i % 16].sign(m))
        keys.append(publics[i % 16])

    def forged(i):  # well-formed, canonical, signed by nobody
        s = int.from_bytes(seed32(b"forge:%d" % index, i), "little") % L
        sigs[i] = sigs[i][:32] + s.to_bytes(32, "little")

    def tampered(i):
        msgs[i] = msgs[i] + b"!"

    def wrong_key(i):
        keys[i] = publics[(i + 1) % 16]

    def s_ge_l(i):  # S + L: the malleable twin of a valid signature
        s = int.from_bytes(sigs[i][32:], "little") + L
        sigs[i] = sigs[i][:32] + s.to_bytes(32, "little")

    def noncanonical_r(i):  # y >= p
        sigs[i] = (P + 3).to_bytes(32, "little") + sigs[i][32:]

    def noncanonical_a(i):
        keys[i] = (P + 5).to_bytes(32, "little")

    def short_sig(i):
        sigs[i] = sigs[i][:63]

    def undecodable_r(i):  # canonical y with no x on the curve
        y = 2
        while _has_x(y):
            y += 1
        sigs[i] = y.to_bytes(32, "little") + sigs[i][32:]

    classes = [forged, tampered, wrong_key, s_ge_l, noncanonical_r,
               noncanonical_a, short_sig, undecodable_r]
    rng = random.Random(
        hashlib.sha256(b"served-bench-plant:%d:%d" % (seed, index)).digest())
    positions = rng.sample(range(lanes), min(len(classes), lanes // 2))
    planted = {}
    for pos, plant in zip(positions, classes):
        plant(pos)
        planted[pos] = plant.__name__
    return (msgs, sigs, keys), planted


def wave_verdicts(wave) -> list:
    return [verify_one(m, s, k) for m, s, k in zip(*wave)]
