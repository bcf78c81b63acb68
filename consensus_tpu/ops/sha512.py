"""Vectorized SHA-512 over lanes of padded blocks (FIPS 180-4).

The missing piece of the bytes-in → verdict-out pipeline: the Ed25519
challenge ``k = SHA-512(R ‖ A ‖ M) mod L`` ran through ``hashlib`` on the
host, serializing a Python loop in front of every launch.  This module
hashes a whole wave per launch:
the batch rides the trailing axis (the vector lanes), the 80-round
compression runs as one ``lax.scan`` body, and multi-block messages scan
over a leading block axis with a per-lane active-block count so one fixed
shape serves every message length up to the padded maximum.

SHA-512 is 64-bit word arithmetic and the deployment runs without x64, so
a word is a ``(hi, lo)`` pair of uint32 lanes: adds propagate one carry
(``lo' < lo`` detects uint32 wraparound), rotates are static cross-half
shift pairs.  Bit-exact against ``hashlib.sha512`` including every padding
edge case (tests/test_sha512.py).

Layouts:

* host packing: :func:`pad_messages` → ``(blocks, n_blocks)`` with
  ``blocks`` uint32 of shape ``(B, 16, 2, batch)`` (block, word, hi/lo,
  lane) and ``n_blocks`` int32 ``(batch,)``.
* device: :func:`sha512_blocks` → state ``(8, 2, batch)`` uint32;
  :func:`digest_bytes` → ``(64, batch)`` int32 digest bytes in stream
  order (byte 0 first — little-endian weight ``2^(8i)`` for the scalar
  stack).
"""

from __future__ import annotations

import math
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

BLOCK_BYTES = 128

# --- constants (FIPS 180-4 §4.2.3 / §5.3.5) --------------------------------
# Derived, not transcribed: IV words are the fractional parts of sqrt(p) and
# the round constants of cbrt(p) over the first 8 / 80 primes, computed with
# exact integer roots — a typo here cannot survive the hashlib parity suite.


def _primes(count: int) -> list[int]:
    out: list[int] = []
    candidate = 2
    while len(out) < count:
        if all(candidate % p for p in out):
            out.append(candidate)
        candidate += 1
    return out


def _icbrt(n: int) -> int:
    x = 1 << ((n.bit_length() + 2) // 3)
    while True:
        y = (2 * x + n // (x * x)) // 3
        if y >= x:
            break
        x = y
    return x


_MASK64 = (1 << 64) - 1
_IV_INT = [math.isqrt(p << 128) & _MASK64 for p in _primes(8)]
_K_INT = [_icbrt(p << 192) & _MASK64 for p in _primes(80)]


def _split_words(values: Sequence[int]) -> np.ndarray:
    """64-bit ints -> (n, 2) uint32 rows of (hi, lo) halves."""
    return np.array(
        [[v >> 32, v & 0xFFFFFFFF] for v in values], dtype=np.uint32
    )


_IV = _split_words(_IV_INT)      # (8, 2)
_K = _split_words(_K_INT)        # (80, 2)


# --- 64-bit ops on (hi, lo) uint32 pairs -----------------------------------


def _add64(a, b):
    lo = a[1] + b[1]
    carry = (lo < b[1]).astype(jnp.uint32)
    return a[0] + b[0] + carry, lo


def _ror64(x, r: int):
    hi, lo = x
    if r >= 32:
        hi, lo = lo, hi
        r -= 32
    if r == 0:
        return hi, lo
    t = 32 - r
    return (hi >> r) | (lo << t), (lo >> r) | (hi << t)


def _shr64(x, r: int):
    hi, lo = x
    if r >= 32:
        return jnp.zeros_like(hi), hi >> (r - 32)
    return hi >> r, (lo >> r) | (hi << (32 - r))


def _xor64(a, b):
    return a[0] ^ b[0], a[1] ^ b[1]


def _big_sigma0(a):
    return _xor64(_xor64(_ror64(a, 28), _ror64(a, 34)), _ror64(a, 39))


def _big_sigma1(e):
    return _xor64(_xor64(_ror64(e, 14), _ror64(e, 18)), _ror64(e, 41))


def _small_sigma0(x):
    return _xor64(_xor64(_ror64(x, 1), _ror64(x, 8)), _shr64(x, 7))


def _small_sigma1(x):
    return _xor64(_xor64(_ror64(x, 19), _ror64(x, 61)), _shr64(x, 6))


def _ch(e, f, g):
    return (e[0] & f[0]) ^ (~e[0] & g[0]), (e[1] & f[1]) ^ (~e[1] & g[1])


def _maj(a, b, c):
    return (
        (a[0] & b[0]) ^ (a[0] & c[0]) ^ (b[0] & c[0]),
        (a[1] & b[1]) ^ (a[1] & c[1]) ^ (b[1] & c[1]),
    )


def _pair(stacked: jnp.ndarray):
    return stacked[0], stacked[1]


def _compress_block(state: jnp.ndarray, block: jnp.ndarray) -> jnp.ndarray:
    """One SHA-512 compression: state (8, 2, batch) + block (16, 2, batch).

    The 80 rounds run as a single scanned body carrying the working
    variables and a rolling 16-word schedule window — the on-the-fly
    schedule (W[t+16] from the window) keeps the carry at 16 words instead
    of materializing all 80.
    """

    def round_step(carry, k):
        vars8, w = carry
        a, b, c, d = _pair(vars8[0]), _pair(vars8[1]), _pair(vars8[2]), _pair(vars8[3])
        e, f, g, h = _pair(vars8[4]), _pair(vars8[5]), _pair(vars8[6]), _pair(vars8[7])
        wt = _pair(w[0])
        k_pair = (k[0], k[1])
        t1 = _add64(
            _add64(h, _big_sigma1(e)),
            _add64(_ch(e, f, g), _add64(k_pair, wt)),
        )
        t2 = _add64(_big_sigma0(a), _maj(a, b, c))
        new_e = _add64(d, t1)
        new_a = _add64(t1, t2)
        nxt = _add64(
            _add64(_small_sigma1(_pair(w[14])), _pair(w[9])),
            _add64(_small_sigma0(_pair(w[1])), _pair(w[0])),
        )
        vars8 = jnp.stack(
            [
                jnp.stack(new_a), jnp.stack(a), jnp.stack(b), jnp.stack(c),
                jnp.stack(new_e), jnp.stack(e), jnp.stack(f), jnp.stack(g),
            ]
        )
        w = jnp.concatenate([w[1:], jnp.stack(nxt)[None]], axis=0)
        return (vars8, w), None

    (vars8, _), _ = jax.lax.scan(
        round_step, (state, block), jnp.asarray(_K, dtype=jnp.uint32)
    )
    lo = state[:, 1] + vars8[:, 1]
    carry = (lo < state[:, 1]).astype(jnp.uint32)
    hi = state[:, 0] + vars8[:, 0] + carry
    return jnp.stack([hi, lo], axis=1)


def sha512_blocks(blocks: jnp.ndarray, n_blocks: jnp.ndarray) -> jnp.ndarray:
    """SHA-512 state for a batch of pre-padded messages.

    ``blocks``: uint32 ``(B, 16, 2, batch)``; ``n_blocks``: int32
    ``(batch,)`` active blocks per lane.  Lanes whose message ends before
    block ``B`` simply stop absorbing — the select keeps their state
    frozen, so one compiled shape serves every length mix.  Returns the
    final state ``(8, 2, batch)`` uint32.
    """
    blocks = blocks.astype(jnp.uint32)
    n_blocks = n_blocks.astype(jnp.int32)
    batch = blocks.shape[-1]
    state0 = jnp.broadcast_to(
        jnp.asarray(_IV, dtype=jnp.uint32)[:, :, None], (8, 2, batch)
    )

    def block_step(state, xs):
        block, index = xs
        new_state = _compress_block(state, block)
        keep = index < n_blocks  # (batch,)
        return jnp.where(keep[None, None, :], new_state, state), None

    state, _ = jax.lax.scan(
        block_step,
        state0,
        (blocks, jnp.arange(blocks.shape[0], dtype=jnp.int32)),
    )
    return state


def digest_bytes(state: jnp.ndarray) -> jnp.ndarray:
    """State ``(8, 2, batch)`` -> digest bytes ``(64, batch)`` int32 in
    stream order (the order ``hashlib.sha512(...).digest()`` emits): each
    word big-endian, hi half first."""
    shifts = jnp.asarray([24, 16, 8, 0], dtype=jnp.uint32)
    # (8, 2, 4, batch): word, half, byte-within-half, lane.
    expanded = (state[:, :, None, :] >> shifts[None, None, :, None]) & jnp.uint32(0xFF)
    return expanded.reshape(64, state.shape[-1]).astype(jnp.int32)


# --- host packing ----------------------------------------------------------


def padded_blocks_for(length: int) -> int:
    """Blocks occupied by a ``length``-byte message after FIPS 180-4
    padding (0x80, zeros, 128-bit bit length)."""
    return (length + 17 + BLOCK_BYTES - 1) // BLOCK_BYTES


def pad_trailer(length: int) -> bytes:
    """The padding suffix for a ``length``-byte message: everything after
    the message bytes up to its final block boundary."""
    blocks = padded_blocks_for(length)
    zeros = blocks * BLOCK_BYTES - length - 1 - 16
    return b"\x80" + b"\x00" * zeros + (8 * length).to_bytes(16, "big")


def pad_messages(
    messages: Sequence[bytes], *, min_blocks: int = 1
) -> tuple[np.ndarray, np.ndarray]:
    """Pack variable-length messages into the fixed kernel block layout.

    Pure byte movement — no hashing, no big-int — this is the host cost
    that remains in fused mode.  Returns ``(blocks, n_blocks)``:
    ``blocks`` uint32 ``(B, 16, 2, n)`` with ``B`` the max padded block
    count (at least ``min_blocks``, so callers can pin a shape), and
    ``n_blocks`` int32 ``(n,)``.
    """
    n = len(messages)
    lengths = [len(m) for m in messages]
    n_blocks = np.array(
        [padded_blocks_for(length) for length in lengths], dtype=np.int32
    )
    total = max(int(n_blocks.max()) if n else 0, min_blocks)
    buf = np.zeros((n, total * BLOCK_BYTES), dtype=np.uint8)
    for i, message in enumerate(messages):
        length = lengths[i]
        end = int(n_blocks[i]) * BLOCK_BYTES
        buf[i, :length] = np.frombuffer(bytes(message), dtype=np.uint8)
        buf[i, length:end] = np.frombuffer(pad_trailer(length), dtype=np.uint8)
    words = buf.view(">u4").astype(np.uint32).reshape(n, total, 16, 2)
    return np.ascontiguousarray(words.transpose(1, 2, 3, 0)), n_blocks


__all__ = [
    "BLOCK_BYTES",
    "digest_bytes",
    "pad_messages",
    "pad_trailer",
    "padded_blocks_for",
    "sha512_blocks",
]
