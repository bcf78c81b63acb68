"""Batched ECDSA-P256 signature verification — the second crypto model
family (BASELINE.json config 1 pairs naive_chain with ECDSA-P256).

Same architecture as :mod:`consensus_tpu.models.ed25519`: the host parses,
range-checks, hashes (SHA-256) and computes the scalar pair u1 = e/s,
u2 = r/s (mod n, Python big-int — modular inversion of the *scalar* field
is irregular host work); the device runs the regular 99%: an on-curve check
for the public key and the double-scalar multiplication R' = u1*G + u2*Q
over complete P-256 formulas — [u2]Q as a 64-step 4-bit-window scan,
[u1]G as an 8-bit fixed-base comb (zero doubles; G is a compile-time
constant) — then the projective acceptance test X == r * Z (with the
r + n second candidate when it exists).

Native formats: signature = 64 bytes big-endian r || s; public key =
65 bytes SEC1 uncompressed (0x04 || X || Y).  DER/cryptography interop
helpers are provided for tests and embedders.
"""

from __future__ import annotations

import functools
import hashlib
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from consensus_tpu.obs.kernels import instrumented_jit, kernel_lane_suffix

from consensus_tpu.models.ed25519 import _next_pow2
from consensus_tpu.ops import field_p256 as fp
from consensus_tpu.ops import p256

N = p256.N

_WINDOW_BITS = 4
_WINDOWS = 256 // _WINDOW_BITS
#: Signed digits: |d| <= 8 needs multiples 0..8 of Q (9 entries, 7 adds to
#: build) instead of 0..15 (15 adds), and shrinks every step's one-hot
#: lookup contraction from 16 rows to 9.  Negation is one field sub.
_TABLE_SIGNED = 9


def _be_bytes_to_limb_rows(rows_be: np.ndarray) -> np.ndarray:
    """(n, 32) big-endian byte rows -> (n, 32) little-endian limb rows
    (uint8 — the wire width; the kernel widens on device)."""
    return rows_be[:, ::-1]


def _scalars_to_signed_window_digits(values: list[int]) -> np.ndarray:
    """Scalars -> (65, n) SIGNED 4-bit digits in [-8, 7], wire-encoded as
    d+8 (uint8), MSB window first.

    Unlike Ed25519's k < L < 2^253 (top window can never overflow), u2 can
    occupy all 256 bits (u2 < n ~ 2^256), so the LSB-to-MSB recoding carry
    CAN escape the top window.  The carry c in {0, 1} is prepended as a
    65th most-significant window: the Horner scan just consumes it first
    (its 4 doubles act on the identity, and 64 subsequent x16 rounds give
    it weight 2^256 exactly)."""
    n = len(values)
    rows = np.zeros((n, 32), dtype=np.uint8)
    for i, v in enumerate(values):
        rows[i] = np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)
    bits = np.unpackbits(rows, axis=-1, bitorder="little")  # (n, 256) LSB first
    weights = np.array([1, 2, 4, 8], dtype=np.int32)
    u = bits.reshape(n, _WINDOWS, _WINDOW_BITS) @ weights  # (n, 64) LSB first
    d = np.zeros_like(u)
    carry = np.zeros(n, dtype=u.dtype)
    for j in range(_WINDOWS):
        t = u[:, j] + carry
        over = t >= 8
        d[:, j] = np.where(over, t - 16, t)
        carry = over.astype(u.dtype)
    full = np.concatenate([carry[:, None], d[:, ::-1]], axis=1)  # (n, 65) MSB first
    return np.ascontiguousarray(full.T + 8).astype(np.uint8)


def _scalars_to_comb_digits8(values: list[int]) -> np.ndarray:
    """Scalars -> (32, n) 8-bit digits, LSB window first: with byte-sized
    windows the little-endian bytes ARE the digits (the comb sums windows,
    order-free)."""
    n = len(values)
    rows = np.zeros((n, 32), dtype=np.uint8)
    for i, v in enumerate(values):
        rows[i] = np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)
    return np.ascontiguousarray(rows.T)


def verify_impl(
    qx: jnp.ndarray,        # (32, batch) public key X limbs
    qy: jnp.ndarray,        # (32, batch) public key Y limbs
    u1_digits: jnp.ndarray, # (32, batch) 8-bit comb digits of u1 = e/s, LSB first
    u2_digits: jnp.ndarray, # (65, batch) signed 4-bit windows of u2 = r/s
                            # (encoded d+8), MSB first incl. recoding carry
    r1: jnp.ndarray,        # (32, batch) r as field limbs
    r2: jnp.ndarray,        # (32, batch) r + n as field limbs (when valid)
    has_r2: jnp.ndarray,    # (batch,) whether r + n < p
    host_ok: jnp.ndarray,   # (batch,) host-side pre-checks passed
) -> jnp.ndarray:
    """Un-jitted kernel body; shards over the trailing batch axis.

    R' = u1*G + u2*Q split by operand class: the variable half [u2]Q runs
    the signed-4-bit Horner scan (65 windows incl. the recoding carry, each
    4 doubles + 1 table add; the 9-entry |d|*Q table built per batch, sign
    applied by a mul-free negate); the fixed-base half [u1]G — G is a
    compile-time constant — uses the 8-bit comb
    (:func:`consensus_tpu.ops.p256.fixed_base_mul_comb`):
    32 constant lookups + adds, zero doubles, no per-batch table."""
    # Inputs ship as uint8 (limbs/digits all fit) — 4x less transfer;
    # widen to the compute dtypes on device.
    qx = qx.astype(jnp.float32)
    qy = qy.astype(jnp.float32)
    u1_digits = u1_digits.astype(jnp.int32)
    u2_digits = u2_digits.astype(jnp.int32)
    r1 = r1.astype(jnp.float32)
    r2 = r2.astype(jnp.float32)
    q = p256.affine_like(qx, qy)
    q_ok = p256.on_curve(qx, qy)
    q_table = p256.multiples_table(q, _TABLE_SIGNED)
    lanes = jnp.arange(_TABLE_SIGNED, dtype=jnp.int32)[:, None]

    def step(acc: p256.Point, w):
        d = w - 8  # signed digit in [-8, 7] ({0, 1} for the carry window)
        oh2 = (jnp.abs(d)[None] == lanes).astype(jnp.float32)
        # 4 doubles as an inner scan: one double body in the graph instead
        # of four (trace/compile-size economy, identical runtime schedule).
        acc, _ = jax.lax.scan(
            lambda a, _: (p256.double(a), None), acc, None, length=4
        )
        t = p256.table_lookup(q_table, oh2)
        t = p256.select(d < 0, p256.negate(t), t)
        acc = p256.add(acc, t)
        return acc, None

    acc, _ = jax.lax.scan(step, p256.identity_like(qx), u2_digits)
    acc = p256.add(acc, p256.fixed_base_mul_comb(u1_digits))

    # Accept iff R' is not the identity and x(R') ≡ r (mod n):
    # X == r * Z or (r + n < p and X == (r + n) * Z), projectively.
    nonzero = ~fp.is_zero(acc.z)
    match1 = fp.eq(acc.x, fp.mul(r1, acc.z))
    match2 = has_r2 & fp.eq(acc.x, fp.mul(r2, acc.z))
    return host_ok & q_ok & nonzero & (match1 | match2)


_verify_kernel = instrumented_jit(
    verify_impl, "ecdsa_p256.verify" + kernel_lane_suffix()
)


def pad_prepared(prepped, padded: int):
    """Pad the 8 host-side arrays to ``padded`` batch elements."""
    qx, qy, u1d, u2d, r1, r2, has_r2, host_ok = prepped
    pad = padded - len(host_ok)
    if pad:
        qx = np.pad(qx, ((0, pad), (0, 0)))
        qy = np.pad(qy, ((0, pad), (0, 0)))
        u1d = np.pad(u1d, ((0, 0), (0, pad)))
        u2d = np.pad(u2d, ((0, 0), (0, pad)))
        r1 = np.pad(r1, ((0, pad), (0, 0)))
        r2 = np.pad(r2, ((0, pad), (0, 0)))
        has_r2 = np.pad(has_r2, (0, pad))
        host_ok = np.pad(host_ok, (0, pad))
    return qx, qy, u1d, u2d, r1, r2, has_r2, host_ok


def to_kernel_layout(qx, qy, u1d, u2d, r1, r2, has_r2, host_ok):
    """Host row-major arrays -> device layout (vector axis leading),
    shipped as the narrowest dtype (uint8/bool); the kernel widens on
    device."""
    return (
        jnp.asarray(np.ascontiguousarray(qx.T)),
        jnp.asarray(np.ascontiguousarray(qy.T)),
        jnp.asarray(u1d),
        jnp.asarray(u2d),
        jnp.asarray(np.ascontiguousarray(r1.T)),
        jnp.asarray(np.ascontiguousarray(r2.T)),
        jnp.asarray(has_r2),
        jnp.asarray(host_ok),
    )


class EcdsaP256BatchVerifier:
    """Verify many (message, signature, public key) triples at once."""

    def __init__(
        self,
        *,
        min_device_batch: int = 1,
        pad_to: int = 0,
    ) -> None:
        """``pad_to`` > 0 pads every device batch to that fixed size (one
        compiled kernel shape for the whole deployment — no mid-run compiles
        on underfull batches); batches larger than ``pad_to`` fall back to
        the pow-2 ladder."""
        self._min_device_batch = min_device_batch
        self._pad_to = pad_to

    @property
    def preferred_wave_size(self) -> int:
        """The smallest padded batch that saturates this engine (see the
        Ed25519 twin) — coalescers read it to size cross-tenant waves."""
        from consensus_tpu.parallel.topology import engine_padded_size

        return engine_padded_size(
            max(1, self._min_device_batch),
            1,
            pad_to=self._pad_to,
        )

    @staticmethod
    def _batch_invert_mod_n(values: list[int]) -> list[int]:
        """Montgomery batch inversion mod the group order: ONE modular
        exponentiation + 3 multiplications per element, vs one ~25 µs
        ``pow(s, n-2, n)`` per signature — the dominant host-prep cost at
        proposal-sized batches.  Zeros pass through as zero (callers have
        already marked them invalid)."""
        prefix: list[int] = []
        acc = 1
        for v in values:
            prefix.append(acc)
            if v:
                acc = (acc * v) % N
        inv = pow(acc, N - 2, N)
        out = [0] * len(values)
        for i in range(len(values) - 1, -1, -1):
            if values[i]:
                out[i] = (inv * prefix[i]) % N
                inv = (inv * values[i]) % N
        return out

    def _prepare(self, messages, signatures, public_keys):
        n = len(messages)
        host_ok = np.ones(n, dtype=bool)
        qx_rows = np.zeros((n, 32), dtype=np.uint8)
        qy_rows = np.zeros((n, 32), dtype=np.uint8)
        u1s = [0] * n
        u2s = [0] * n
        r1_rows = np.zeros((n, 32), dtype=np.uint8)
        r2_rows = np.zeros((n, 32), dtype=np.uint8)
        has_r2 = np.zeros(n, dtype=bool)
        rs = [0] * n
        ss = [0] * n
        es = [0] * n
        for i in range(n):
            sig = signatures[i]
            key = public_keys[i]
            if len(sig) != 64 or len(key) != 65 or key[0] != 0x04:
                host_ok[i] = False
                continue
            r = int.from_bytes(sig[:32], "big")
            s = int.from_bytes(sig[32:], "big")
            if not (1 <= r < N and 1 <= s < N):
                host_ok[i] = False
                continue
            qx = int.from_bytes(key[1:33], "big")
            qy = int.from_bytes(key[33:], "big")
            if qx >= fp.P or qy >= fp.P:
                host_ok[i] = False
                continue
            rs[i], ss[i] = r, s
            es[i] = int.from_bytes(hashlib.sha256(messages[i]).digest(), "big")
            qx_rows[i] = np.frombuffer(key[1:33], dtype=np.uint8)
            qy_rows[i] = np.frombuffer(key[33:], dtype=np.uint8)
            r1_rows[i] = np.frombuffer(r.to_bytes(32, "big"), dtype=np.uint8)
            if r + N < fp.P:
                has_r2[i] = True
                r2_rows[i] = np.frombuffer((r + N).to_bytes(32, "big"), dtype=np.uint8)
        ws = self._batch_invert_mod_n(ss)
        for i in range(n):
            if not ss[i]:
                continue
            u1s[i] = (es[i] * ws[i]) % N
            u2s[i] = (rs[i] * ws[i]) % N
        return (
            _be_bytes_to_limb_rows(qx_rows),
            _be_bytes_to_limb_rows(qy_rows),
            _scalars_to_comb_digits8(u1s),
            _scalars_to_signed_window_digits(u2s),
            _be_bytes_to_limb_rows(r1_rows),
            _be_bytes_to_limb_rows(r2_rows),
            has_r2,
            host_ok,
        )

    def verify_batch(self, messages, signatures, public_keys) -> np.ndarray:
        n = len(messages)
        if not (n == len(signatures) == len(public_keys)):
            raise ValueError("batch length mismatch")
        if n == 0:
            return np.zeros(0, dtype=bool)
        if n < self._min_device_batch:
            return self._verify_host(messages, signatures, public_keys)
        prepped = self._prepare(messages, signatures, public_keys)
        if self._pad_to >= n:
            padded = self._pad_to
        else:
            padded = _next_pow2(n)
        result = _verify_kernel(*to_kernel_layout(*pad_prepared(prepped, padded)))
        return np.asarray(result)[:n]

    @staticmethod
    def _verify_host(messages, signatures, public_keys) -> np.ndarray:
        """Sequential fallback via the ``cryptography`` package."""
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives import hashes
        from cryptography.hazmat.primitives.asymmetric import ec
        from cryptography.hazmat.primitives.asymmetric.utils import (
            encode_dss_signature,
        )

        out = np.zeros(len(messages), dtype=bool)
        for i, (msg, sig, key) in enumerate(zip(messages, signatures, public_keys)):
            try:
                pub = ec.EllipticCurvePublicKey.from_encoded_point(
                    ec.SECP256R1(), bytes(key)
                )
                der = encode_dss_signature(
                    int.from_bytes(sig[:32], "big"), int.from_bytes(sig[32:], "big")
                )
                pub.verify(der, bytes(msg), ec.ECDSA(hashes.SHA256()))
                out[i] = True
            except (InvalidSignature, ValueError):
                out[i] = False
        return out

    def verify_host(self, messages, signatures, public_keys) -> np.ndarray:
        """Public seam for the coalescer's wedged-device escape hatch:
        verify on the host regardless of batch size, same semantics as the
        device path.  (A forwarding method, not a class-level alias, so
        subclass overrides of ``_verify_host`` take effect here too.)"""
        return self._verify_host(messages, signatures, public_keys)


def raw_signature_from_der(der: bytes) -> bytes:
    """DER ECDSA signature -> 64-byte big-endian r || s."""
    from cryptography.hazmat.primitives.asymmetric.utils import decode_dss_signature

    r, s = decode_dss_signature(der)
    return r.to_bytes(32, "big") + s.to_bytes(32, "big")


__all__ = [
    "EcdsaP256BatchVerifier",
    "raw_signature_from_der",
    "pad_prepared",
    "to_kernel_layout",
    "N",
]
