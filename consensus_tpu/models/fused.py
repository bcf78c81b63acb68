"""Fused bytes-in → verdict-out strict Ed25519 engine
(``Configuration.device_prep``).

The host-prep engine splits a wave into host prep (SHA-512 challenge
hashing, mod-L reduction, canonical-range checks, digit recoding — a Python
loop per signature) and a device launch.  This engine moves that prep into
the launch: the host does byte movement only (slice ``R ‖ A ‖ M`` into
padded SHA-512 block layout — :func:`consensus_tpu.ops.sha512
.pad_messages`), and one jitted graph per wave does everything else on
device:

    SHA-512 → reduce mod L → digit recode → canonical checks →
    decompress → double-scalar multiplication → verdict

Parity contract (SAFETY.md §10): with ``device_prep`` on, accept/reject is
bit-identical to the host-prep strict engine on every rejection class —
forged and tampered lanes reject by math, ``S ≥ L`` / non-canonical ``y``
reject by the same range checks, now computed on device.  ``device_prep``
off is bit-for-bit the previous protocol: the class is additive.

The graph is shape-polymorphic over (block count × batch) like the
host-prep kernel ladder.  There is no fused randomized or half-agg lane:
those graphs baked the LIVE subset size into the compiled transcript (a
new compile for every distinct wave size and every bisection level), which
on the chip meant minutes of compilation per wave — removed in PR 22 (see
PERF.md); ``device_prep`` with ``batch_verify_mode`` is refused by the
engine registry, and half-aggregated certs always use the host-derived
transcript in front of the device MSM.

Input buffers are donated to the runtime on accelerator backends (the
block arrays are the dominant transfer; donation lets XLA alias them into
scratch instead of holding both copies) — donation is skipped on CPU,
which would only warn.
"""

from __future__ import annotations

import functools
from typing import Iterable, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from consensus_tpu.obs.kernels import instrumented_jit, kernel_lane_suffix
from consensus_tpu.ops import field25519 as fe
from consensus_tpu.ops import scalar25519 as sc
from consensus_tpu.ops import sha512 as sh

from consensus_tpu.models.ed25519 import (
    _WINDOWS,
    _next_pow2,
    Ed25519BatchVerifier,
    L,
    verify_impl,
)

_L_BYTES_BE = np.frombuffer(L.to_bytes(32, "big"), dtype=np.uint8)
_P_BYTES_BE = np.frombuffer(fe.P.to_bytes(32, "big"), dtype=np.uint8)


# --- host-side helpers (byte movement + vectorized range checks) -----------


def _rows_lt_be(rows_be: np.ndarray, bound_be: np.ndarray) -> np.ndarray:
    """Vectorized big-endian lexicographic ``row < bound`` (row == bound
    compares False, matching the exclusive canonical ranges)."""
    n = rows_be.shape[0]
    diff = rows_be != bound_be
    first = np.argmax(diff, axis=1)
    lt = rows_be[np.arange(n), first] < bound_be[first]
    return np.where(diff.any(axis=1), lt, False)


def canonical_ok_fast(signatures, public_keys) -> np.ndarray:
    """Vectorized twin of ``Ed25519BatchVerifier._canonical_ok`` — same
    classes (sig/key length, S < L, canonical y for R and A), no per-lane
    big-int loop.  The randomized fused engine pre-filters its subset with
    this so transcript membership matches the legacy path exactly."""
    n = len(signatures)
    ok = np.ones(n, dtype=bool)
    sig_chunks: list[bytes] = []
    key_chunks: list[bytes] = []
    for i in range(n):
        sig, key = bytes(signatures[i]), bytes(public_keys[i])
        if len(sig) != 64:
            ok[i] = False
            sig = b"\x00" * 64
        if len(key) != 32:
            ok[i] = False
            key = b"\x00" * 32
        sig_chunks.append(sig)
        key_chunks.append(key)
    if n == 0:
        return ok
    sig_rows = np.frombuffer(b"".join(sig_chunks), dtype=np.uint8).reshape(n, 64)
    key_rows = np.frombuffer(b"".join(key_chunks), dtype=np.uint8).reshape(n, 32)
    ok &= _rows_lt_be(sig_rows[:, :31:-1], _L_BYTES_BE)  # S < L
    y_r = sig_rows[:, 31::-1].copy()
    y_r[:, 0] &= 0x7F
    ok &= _rows_lt_be(y_r, _P_BYTES_BE)
    y_a = key_rows[:, ::-1].copy()
    y_a[:, 0] &= 0x7F
    ok &= _rows_lt_be(y_a, _P_BYTES_BE)
    return ok


def _byte_rows(chunks: Sequence[bytes], width: int) -> np.ndarray:
    return np.frombuffer(b"".join(chunks), dtype=np.uint8).reshape(
        len(chunks), width
    )


def _pad_wave(arrays: Sequence[np.ndarray], n: int, padded: int):
    """Zero-pad the trailing batch dim of row-major host arrays."""
    if padded == n:
        return list(arrays)
    out = []
    for a in arrays:
        pad = [(0, 0)] * a.ndim
        pad[0] = (0, padded - n)
        out.append(np.pad(a, pad))
    return out


def _pack_blocks(messages: Sequence[bytes], *, min_blocks: int = 1):
    """Pad+pack messages, quantizing the block axis to a power of two so
    the compiled-shape set stays a short ladder."""
    longest = max((len(m) for m in messages), default=0)
    want = _next_pow2(sh.padded_blocks_for(longest), minimum=min_blocks)
    return sh.pad_messages(messages, min_blocks=want)


# --- the fused strict kernel -----------------------------------------------


def fused_verify_impl(
    sig_rows: jnp.ndarray,   # (64, batch) signature bytes R ‖ S
    key_rows: jnp.ndarray,   # (32, batch) public-key bytes
    blocks: jnp.ndarray,     # (B, 16, 2, batch) padded SHA-512(R‖A‖M) blocks
    n_blocks: jnp.ndarray,   # (batch,) active block counts
    host_ok: jnp.ndarray,    # (batch,) host length checks passed
) -> jnp.ndarray:
    """Un-jitted fused strict body: the whole front-end on device, then the
    legacy MSM body (:func:`consensus_tpu.models.ed25519.verify_impl`).
    Shards over the batch axis unchanged — every stage keeps batch
    trailing."""
    sig = sig_rows.astype(jnp.int32)
    key = key_rows.astype(jnp.int32)

    digest = sh.digest_bytes(sh.sha512_blocks(blocks, n_blocks))
    k_bytes = sc.reduce_bytes_mod_l(digest)
    k_digits = sc.signed_window_digits(k_bytes, _WINDOWS)

    s_bytes = sig[32:]
    y_r = jnp.concatenate([sig[:31], (sig[31] & 0x7F)[None]], axis=0)
    sign_r = sig[31] >> 7
    y_a = jnp.concatenate([key[:31], (key[31] & 0x7F)[None]], axis=0)
    sign_a = key[31] >> 7

    ok = (
        host_ok
        & sc.lt_l(s_bytes)        # RFC 8032 §5.1.7 malleability
        & fe.bytes_lt_p(y_r)      # canonical encodings
        & fe.bytes_lt_p(y_a)
    )
    return verify_impl(y_r, sign_r, y_a, sign_a, s_bytes, k_digits, ok)


@functools.lru_cache(maxsize=None)
def _fused_verify_kernel():
    donate = (2,) if jax.default_backend() != "cpu" else ()
    return instrumented_jit(
        fused_verify_impl,
        "ed25519.fused_verify" + kernel_lane_suffix(),
        donate_argnums=donate,
    )


class FusedEd25519BatchVerifier(Ed25519BatchVerifier):
    """Strict verifier with the on-device front-end.

    Same contract and bit-identical verdicts as
    :class:`~consensus_tpu.models.ed25519.Ed25519BatchVerifier`; the host
    work per wave is one pass of byte slicing into the block layout.
    """

    fused = True

    def _prepare_fused(self, messages, signatures, public_keys):
        n = len(messages)
        host_ok = np.ones(n, dtype=bool)
        sig_chunks: list[bytes] = []
        key_chunks: list[bytes] = []
        prehash: list[bytes] = []
        for i in range(n):
            sig, key = bytes(signatures[i]), bytes(public_keys[i])
            if len(sig) != 64:
                host_ok[i] = False
                sig = b"\x00" * 64
            if len(key) != 32:
                host_ok[i] = False
                key = b"\x00" * 32
            sig_chunks.append(sig)
            key_chunks.append(key)
            prehash.append(sig[:32] + key + bytes(messages[i]))
        sig_rows = _byte_rows(sig_chunks, 64)
        key_rows = _byte_rows(key_chunks, 32)
        blocks, n_blocks = _pack_blocks(prehash)
        return sig_rows, key_rows, blocks, n_blocks, host_ok

    def _device_args(self, messages, signatures, public_keys):
        """Pack one wave into padded device arrays (dispatchable args)."""
        n = len(messages)
        sig_rows, key_rows, blocks, n_blocks, host_ok = self._prepare_fused(
            messages, signatures, public_keys
        )
        if self._pad_to >= n:
            padded = self._pad_to
        else:
            padded = _next_pow2(n)
        sig_rows, key_rows, n_blocks, host_ok = _pad_wave(
            [sig_rows, key_rows, n_blocks, host_ok], n, padded
        )
        if padded != n:
            blocks = np.pad(blocks, ((0, 0),) * 3 + ((0, padded - n),))
        return (
            jnp.asarray(np.ascontiguousarray(sig_rows.T)),
            jnp.asarray(np.ascontiguousarray(key_rows.T)),
            jnp.asarray(blocks),
            jnp.asarray(n_blocks),
            jnp.asarray(host_ok),
        )

    def verify_batch(self, messages, signatures, public_keys) -> np.ndarray:
        n = len(messages)
        if not (n == len(signatures) == len(public_keys)):
            raise ValueError("batch length mismatch")
        if n == 0:
            return np.zeros(0, dtype=bool)
        if n < self._min_device_batch:
            return self._verify_host(messages, signatures, public_keys)
        result = _fused_verify_kernel()(
            *self._device_args(messages, signatures, public_keys)
        )
        return np.asarray(result)[:n]

    def verify_stream(
        self, waves: Iterable[Tuple[Sequence, Sequence, Sequence]]
    ) -> Iterable[np.ndarray]:
        """Double-buffered streaming: pack + dispatch wave ``i+1`` before
        blocking on wave ``i``'s verdict, so host byte packing and the
        host→device transfer overlap device compute (JAX dispatch is
        async — the blocking point is the ``np.asarray`` fetch)."""
        kernel = _fused_verify_kernel()
        pending: Optional[tuple[int, object]] = None
        for messages, signatures, public_keys in waves:
            n = len(messages)
            out = kernel(*self._device_args(messages, signatures, public_keys))
            if pending is not None:
                prev_n, prev_out = pending
                yield np.asarray(prev_out)[:prev_n]
            pending = (n, out)
        if pending is not None:
            yield np.asarray(pending[1])[: pending[0]]


__all__ = [
    "FusedEd25519BatchVerifier",
    "canonical_ok_fast",
    "fused_verify_impl",
]
