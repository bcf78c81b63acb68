"""On-device scalar arithmetic mod L (the edwards25519 group order).

The last host big-int holdout of the strict verification pipeline:
reducing the 512-bit challenge hash mod L ran as Python integers between
the SHA-512 stage and the kernel.  This module does it in the same batched
8-bit-limb discipline as
:mod:`consensus_tpu.ops.field25519` — bytes on the trailing-batch lanes,
products held exactly in f32's 24-bit integer window, sequential int32
carries only at stage boundaries.

Reduction is Barrett-shaped but exploits L's sparse form
``L = 2^252 + δ`` (δ < 2^125):

1. **Byte fold** — a value given as little-endian bytes ``x = Σ bᵢ·2^8i``
   collapses to 32 limbs through one constant matmul with the
   ``(2^8i mod L)`` table: congruent mod L, every column sum < 2^23
   (f32-exact), and the contraction is MXU-shaped.
2. **Carry** to canonical bytes over two spare top limbs (the folded
   value is < 64·255·L < 2^267).
3. **Sparse fold** — split at bit 252: ``x = hi·2^252 + lo ≡ lo − hi·δ``.
   ``hi`` < 2^15 splits into two bytes against exact ``δ·2^8j`` tables,
   so the signed result lies in ``(−2^142, 2^252)`` — already below L
   (= 2^252 + δ) — and one borrow-driven conditional ``+L`` lands in
   ``[0, L)``.  No quotient estimation, no correction loop.

Every entry point is traceable (no host sync) and reports its work to the
field-op counting shim via :func:`consensus_tpu.ops.limbs.note_byte_muls`
so ``measure_field_ops`` covers the fused front-end too.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from consensus_tpu.ops import limbs

#: Group order of edwards25519 (RFC 8032) and its sparse-form tail.
L = 2**252 + 27742317777372353535851937790883648493
_DELTA = L - 2**252

#: L as little-endian bytes (canonical-range checks: S < L).
L_BYTES_LE = np.frombuffer(L.to_bytes(32, "little"), dtype=np.uint8)


def _int_to_bytes_row(value: int, width: int) -> np.ndarray:
    return np.frombuffer(value.to_bytes(width, "little"), dtype=np.uint8)


#: Row i = little-endian bytes of (2^8i mod L): the byte-fold matmul table.
_POW_TABLE = np.stack(
    [_int_to_bytes_row(pow(256, i, L), 32) for i in range(64)]
).astype(np.float32)  # (64, 32)

#: Row j = exact little-endian bytes of (δ << 8j) — NOT reduced: the sparse
#: fold subtracts hi·δ exactly (δ·2^16 < 2^141 fits 18 bytes).
_DELTA_SHIFT = np.stack(
    [_int_to_bytes_row(_DELTA << (8 * j), 32) for j in range(2)]
).astype(np.int32)  # (2, 32)

_L_LIMBS = _int_to_bytes_row(L, 32).astype(np.int32)

_WINDOW_BITS = 4


def reduce_bytes_mod_l(x_bytes: jnp.ndarray) -> jnp.ndarray:
    """Little-endian byte rows ``(n_bytes, batch)`` (n_bytes <= 64, each
    byte in [0, 255]) -> canonical bytes ``(32, batch)`` int32 of the value
    mod L.  Handles the full 512-bit SHA-512 digest range."""
    n_bytes, batch = x_bytes.shape
    if n_bytes > 64:
        raise ValueError("byte fold table covers 64 input bytes")
    table = jnp.asarray(_POW_TABLE[:n_bytes])  # (n_bytes, 32)
    limbs.note_byte_muls(n_bytes * 32, batch)
    folded = jnp.einsum(
        "ij,ib->jb", table, x_bytes.astype(jnp.float32)
    )  # (32, batch), columns < 64*255*255 < 2^23: f32-exact
    # Two spare limbs hold the fold's overflow (< 2^267 < 2^272).
    ext = jnp.concatenate(
        [folded.astype(jnp.int32), jnp.zeros((2, batch), jnp.int32)], axis=0
    )
    canon, top = limbs.carry_i32(ext)  # top carry provably 0

    # Sparse fold at bit 252: hi < 2^15 after the carry above.
    hi = (canon[31] >> 4) + (canon[32] << 4) + (canon[33] << 12) + (top << 20)
    lo = jnp.concatenate([canon[:31], (canon[31] & 0xF)[None]], axis=0)
    h_bytes = jnp.stack([hi & 0xFF, hi >> 8])  # (2, batch)
    limbs.note_byte_muls(2 * 32, batch)
    sub = jnp.einsum("jk,jb->kb", jnp.asarray(_DELTA_SHIFT), h_bytes)
    signed, borrow = limbs.carry_i32(lo - sub)
    # Value in (-2^142, 2^252): negative iff borrow < 0; one +L lands
    # canonical (2^252 < L, so the non-negative branch is already there).
    fixup = jnp.where(borrow < 0, jnp.asarray(_L_LIMBS)[:, None], 0)
    out, _ = limbs.carry_i32(signed + fixup)
    return out


def lt_l(s_bytes: jnp.ndarray) -> jnp.ndarray:
    """On-device malleability check ``S < L`` (RFC 8032 §5.1.7) over
    ``(32, batch)`` little-endian byte rows."""
    return limbs.lt_bytes(
        s_bytes.astype(jnp.int32), jnp.asarray(L_BYTES_LE, dtype=jnp.int32)
    )


def signed_window_digits(
    k_bytes: jnp.ndarray, windows: int = 64
) -> jnp.ndarray:
    """Canonical little-endian byte rows -> signed 4-bit window digits in
    [-8, 7], encoded ``d+8``, MSB window first — the device twin of
    ``models.ed25519._signed_digits_int``, and what every strict launch
    recodes k with.  Signed digits halve the Horner scan's per-batch
    table: |d| <= 8 needs 9 multiples of (-A) instead of 16 (negation is
    two mul-free field subs).  ``windows`` must leave carry headroom as
    the host version requires (64 for k < L < 2^253: the top window is at
    most 1 before the carry, so no 65th is ever needed; 33 for 128-bit
    coefficients)."""
    k = k_bytes.astype(jnp.int32)
    nibbles = jnp.stack([k & 0xF, k >> 4], axis=1).reshape(
        2 * k.shape[0], k.shape[-1]
    )  # LSB-first 4-bit windows
    if nibbles.shape[0] > windows:
        nibbles = nibbles[:windows]
    elif nibbles.shape[0] < windows:
        nibbles = jnp.concatenate(
            [
                nibbles,
                jnp.zeros((windows - nibbles.shape[0], k.shape[-1]), jnp.int32),
            ],
            axis=0,
        )

    def step(carry, u):
        t = u + carry
        over = (t >= 8).astype(jnp.int32)
        return over, t - 16 * over

    _, digits = limbs.counted_scan(step, jnp.zeros_like(nibbles[0]), nibbles)
    return digits[::-1] + 8


__all__ = [
    "L",
    "L_BYTES_LE",
    "lt_l",
    "reduce_bytes_mod_l",
    "signed_window_digits",
]
