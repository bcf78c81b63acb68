"""Batched Ed25519 signature verification: the TPU replacement for the
reference's per-vote goroutine + sequential CPU ECDSA
(reference internal/bft/view.go:537-541).

Split of labor:

* **Host** (cheap, irregular): parse signatures, range-check ``S < L`` and
  ``y < p``, hash ``k = SHA-512(R || A || M) mod L`` (hashing is
  variable-length and byte-oriented — the wrong shape for the MXU/VPU), and
  write the bytes of R, A, S and k into ONE fixed-shape uint8 array: a
  wave reaches the device as the bytes it came in, in one copy.
* **Device** (the 99%: elliptic-curve math): take the bytes apart (sign
  bits, S's 8-bit comb digits, k's signed 4-bit digits), decompress R and A, then the
  double-scalar multiplication ``[S]B + [k](-A)`` — the variable half as a
  64-step 4-bit-window ``lax.scan``, the fixed-base half as an 8-bit comb
  over constant tables — and a projective comparison against R.  Everything
  is f32 8-bit-limb arithmetic (:mod:`consensus_tpu.ops.field25519`)
  batched on the trailing axes — one compiled program per padded batch size
  verifies the whole quorum.  On the TPU a width of 1,024 lanes or more is
  held limb-major, ``(32, width // 128, 128)``, and runs every field
  multiply and point operation as one Mosaic kernel
  (:mod:`consensus_tpu.ops.mosaic25519`); other widths keep ``(32, width)``
  and XLA.

Batches are padded to the next power of two
so XLA compiles a handful of shapes once and reuses them forever.
"""

from __future__ import annotations

import hashlib
from typing import Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from consensus_tpu.obs.kernels import instrumented_jit, kernel_lane_suffix, phase

from consensus_tpu.ops import ed25519 as ed
from consensus_tpu.ops import field25519 as fe
from consensus_tpu.ops import limbs
from consensus_tpu.ops import mosaic25519 as mosaic
from consensus_tpu.ops import scalar25519 as sc

#: Group order of edwards25519 (RFC 8032).
L = 2**252 + 27742317777372353535851937790883648493

_WINDOW_BITS = 4
_WINDOWS = 256 // _WINDOW_BITS  # 64
_TABLE = 9  # signed digits: |d| <= 8 -> multiples 0..8 of (-A)


def verify_impl(
    y_r: jnp.ndarray,       # (32, batch) R.y limbs, uint8 on the wire
    sign_r: jnp.ndarray,    # (batch,)    R.x sign bits
    y_a: jnp.ndarray,       # (32, batch) A.y limbs, uint8 on the wire
    sign_a: jnp.ndarray,    # (batch,)    A.x sign bits
    s_digits8: jnp.ndarray, # (32, batch) S 8-bit window digits, LSB window first
    k_digits: jnp.ndarray,  # (64, batch) k signed 4-bit digits + 8, MSB window first
    host_ok: jnp.ndarray,   # (batch,)    host-side pre-checks passed
) -> jnp.ndarray:
    """Un-jitted kernel body — every op is independent per batch element
    (batch is the trailing axis, riding the vector lanes), so this function
    shards over the batch axis unchanged (see :mod:`consensus_tpu.parallel`).

    acc = [S]B + [k](-A) is split by operand class: the variable half
    [k](-A) runs a signed-4-bit-windowed Horner scan (64 steps of 4
    doubles + 1 table add; j*(-A) for j <= 8 built per batch with 7
    additions, sign applied by a mul-free conditional negate), while the
    fixed-base half [S]B — B is a compile-time constant — uses an 8-bit
    comb over precomputed tables (:func:`consensus_tpu.ops.ed25519
    .fixed_base_mul_comb`): 32 constant lookups + mixed adds, zero doubles,
    with the lookups riding the MXU.  Lookups are one-hot contractions (no
    gathers), and digit 0 adds the identity — the complete addition
    formulas make that branch-free."""
    # Limb-major from here to the verdict where the width takes the Mosaic
    # field and point kernels (ops/mosaic25519.py: the TPU, 1,024 lanes or
    # more): (..., width) becomes (..., width // 128, 128), so a field
    # element is (32, rows, 128) and its every limb whole vregs.  Every op
    # below is batch-shape-generic; other widths keep (32, width).
    width = y_r.shape[-1]
    if mosaic.launch_path(width) == "mosaic":
        y_r, sign_r, y_a, sign_a, s_digits8, k_digits, host_ok = (
            mosaic.limb_major(x)
            for x in (y_r, sign_r, y_a, sign_a, s_digits8, k_digits, host_ok)
        )
    # Inputs arrive in the narrowest dtype that holds them (uint8 limbs and
    # digits) — 4x less host->device transfer.  Widen to the compute dtypes
    # on device.
    y_r = y_r.astype(jnp.float32)
    y_a = y_a.astype(jnp.float32)
    sign_r = sign_r.astype(jnp.int32)
    sign_a = sign_a.astype(jnp.int32)
    s_digits8 = s_digits8.astype(jnp.int32)
    k_digits = k_digits.astype(jnp.int32)
    # Decompress R and A in ONE instance of the (large) decompression graph
    # by stacking them on the first batch axis (the rows when limb-major) —
    # same total runtime work, half the traced/compiled graph.
    rows = y_r.shape[1]
    pt, pt_ok = ed.decompress(
        jnp.concatenate([y_r, y_a], axis=1),
        jnp.concatenate([sign_r, sign_a], axis=0),
    )
    r_point = ed.Point(*(c[:, :rows] for c in pt))
    a_point = ed.Point(*(c[:, rows:] for c in pt))
    r_ok, a_ok = pt_ok[:rows], pt_ok[rows:]
    neg_a = ed.negate(a_point)
    # The table coords inherit the inputs' sharding variance so the scan
    # carry type-checks under shard_map.
    a_table = ed.multiples_table(neg_a, _TABLE)

    # (9, 1, ...): the table's entries against every lane's digit.
    lanes = jnp.arange(_TABLE, dtype=jnp.int32)[
        (slice(None),) + (None,) * (k_digits.ndim - 1)
    ]

    def step(acc: ed.Point, k_w):
        d = k_w - 8             # signed digit in [-8, 7]
        k_oh = (jnp.abs(d)[None] == lanes).astype(jnp.float32)  # (9, *batch)
        # 3 T-free doubles as an inner scan (one body in the graph) + the
        # final T-producing double — graph size, not runtime, economy.
        acc, _ = limbs.counted_scan(
            lambda a, _: (ed.double(a, need_t=False), None), acc, None, length=3
        )
        acc = ed.double(acc)
        q = ed.table_lookup(a_table, k_oh)
        q = ed.select(d < 0, ed.negate(q), q)  # two field subs, no muls
        acc = ed.add(acc, q)
        return acc, None

    acc, _ = limbs.counted_scan(step, ed.identity_like(y_r), k_digits)
    acc = ed.add(acc, ed.fixed_base_mul_comb(s_digits8))

    return (host_ok & r_ok & a_ok & ed.equal(acc, r_point)).reshape(width)


#: Rows of a packed wave (:func:`pack_wave`): the 32 bytes each of R, A, S
#: and k, little-endian as on the wire, then ``host_ok``.
_PACKED_ROWS = 4 * 32 + 1
#: Clears bit 255 (the sign of x) of a compressed point's 32 byte rows.
_Y_MASK = np.array([0xFF] * 31 + [0x7F], dtype=np.int32)[:, None]


def packed_verify_impl(wave: jnp.ndarray) -> jnp.ndarray:
    """The program a launch runs: one ``(129, batch)`` uint8 array
    (:func:`pack_wave`) in, the verdicts out.  It takes the bytes apart on
    the device — bit 255 of R and A is the sign of x, S's bytes ARE the
    comb's 8-bit digits, k's bytes recode into signed 4-bit digits — and
    hands them to :func:`verify_impl`.  Every op keeps batch trailing, so it
    shards over the batch axis like the body."""
    rows = wave.astype(jnp.int32)
    r, a, s, k = (rows[i:i + 32] for i in range(0, 128, 32))
    return verify_impl(
        r & _Y_MASK, r[31] >> 7,
        a & _Y_MASK, a[31] >> 7,
        s,
        sc.signed_window_digits(k, _WINDOWS),
        wave[128] != 0,
    )


_verify_kernel = instrumented_jit(
    packed_verify_impl, "ed25519.verify" + kernel_lane_suffix()
)


_P_BYTES_BE = np.frombuffer(fe.P.to_bytes(32, "big"), dtype=np.uint8)


def _y_lt_p(rows: np.ndarray) -> np.ndarray:
    """(n, 32) little-endian y rows (sign bit cleared) -> y < p, the
    canonical-range check: a lexicographic compare of the big-endian byte
    rows against p's bytes."""
    rows_be = rows[:, ::-1]
    diff = rows_be != _P_BYTES_BE
    first = np.argmax(diff, axis=1)
    lt = rows_be[np.arange(len(rows)), first] < _P_BYTES_BE[first]
    return np.where(diff.any(axis=1), lt, False)  # y == p is out of range too


def _prep_compressed(points: Sequence[bytes]) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Compressed point bytes -> (y limbs, sign bits, y<p validity), for the
    lanes whose kernels take limbs and signs apart on the host."""
    n = len(points)
    ok = np.ones(n, dtype=bool)
    chunks: list[bytes] = []
    for i, raw in enumerate(points):
        if len(raw) == 32:
            chunks.append(raw)
        else:
            ok[i] = False
            chunks.append(b"\x00" * 32)
    # One bulk copy instead of n tiny frombuffer calls.
    rows = np.frombuffer(b"".join(chunks), dtype=np.uint8).reshape(n, 32)
    signs = (rows[:, 31] >> 7)  # uint8
    rows = rows.copy()
    rows[:, 31] &= 0x7F
    ok &= _y_lt_p(rows)
    return rows, signs, ok  # byte-sized limbs: the bytes ARE the limbs


def pack_wave(rows: np.ndarray, host_ok: np.ndarray, width: int) -> np.ndarray:
    """What :meth:`Ed25519BatchVerifier._prepare` made of ``n`` signatures
    -> the ONE ``(129, width)`` uint8 array a launch of ``width`` lanes
    takes (:func:`packed_verify_impl`): bytes leading (on the sublanes),
    batch trailing (on the lanes).  The lanes past ``n`` are zero, so their
    ``host_ok`` is false."""
    n = len(host_ok)
    wave = np.zeros((_PACKED_ROWS, width), dtype=np.uint8)
    wave[:128, :n] = rows.T
    wave[128, :n] = host_ok
    return wave


def _next_pow2(n: int, minimum: int = 8) -> int:
    size = minimum
    while size < n:
        size *= 2
    return size


class Ed25519BatchVerifier:
    """Verify many (message, signature, public key) triples at once.

    ``verify_batch`` returns a boolean numpy array.  Padding to a power of
    two keeps the set of compiled kernel shapes small; ``min_device_batch``
    routes tiny batches to the host path (kernel launch overhead dominates
    below it).
    ``pad_to`` names the launch widths a deployment compiles before it
    serves: one (every wave pads to it) or a ladder of them (a wave pads
    to the narrowest that holds it, :meth:`launch_width`) — the device's
    time follows the PADDED width, so a half-empty wave in a half-width
    launch costs about half.
    """

    def __init__(
        self,
        *,
        min_device_batch: int = 1,
        pad_to: Union[int, Sequence[int]] = 0,
    ) -> None:
        """``pad_to`` > 0 pads every device batch to that fixed size (one
        compiled kernel shape for the whole deployment — no mid-run compiles
        on underfull batches); a sequence of sizes pads each batch to the
        smallest of them that holds it (one compiled shape per size);
        batches larger than all of them fall back to the pow-2 ladder."""
        self._min_device_batch = min_device_batch
        widths = (pad_to,) if isinstance(pad_to, int) else tuple(pad_to)
        #: The launch widths, ascending; ``_pad_to`` is the widest (what
        #: the one-width subclasses and the wave sizing read).
        self._widths = tuple(sorted({int(w) for w in widths if w > 0}))
        self._pad_to = self._widths[-1] if self._widths else 0

    def launch_width(self, n: int) -> int:
        """The padded width a device wave of ``n`` signatures launches at:
        the narrowest ``pad_to`` width that holds it, else the next power
        of two."""
        for width in self._widths:
            if width >= n:
                return width
        return _next_pow2(n)

    @property
    def preferred_wave_size(self) -> int:
        """The smallest padded batch that saturates this engine — the
        device-batch floor rounded through the padding knobs.  Coalescers
        (models/engine.py) read it to size cross-tenant waves; the mesh
        engines override it with the whole-slice shard multiple."""
        from consensus_tpu.parallel.topology import engine_padded_size

        return engine_padded_size(
            max(1, self._min_device_batch),
            1,
            pad_to=self._pad_to,
        )

    def _prepare(
        self,
        messages: Sequence[bytes],
        signatures: Sequence[bytes],
        public_keys: Sequence[bytes],
    ) -> tuple[np.ndarray, np.ndarray]:
        """Host-side parse/hash/check: ``(rows, host_ok)`` for
        :func:`pack_wave`, unpadded.  ``rows`` is ``(n, 128)`` uint8, a lane
        a row: R ‖ A ‖ S ‖ k as their 32 little-endian bytes each, with
        k = SHA-512(R ‖ A ‖ M) mod L; a lane the checks here reject (length,
        S < L, y < p for R and A) has ``host_ok`` false and whatever of its
        bytes parsed."""
        n = len(messages)
        host_ok = np.ones(n, dtype=bool)
        zeros = b"\x00" * 128
        chunks: list[bytes] = []
        sha512 = hashlib.sha512
        from_bytes = int.from_bytes
        for i in range(n):
            sig, key = signatures[i], public_keys[i]
            if len(sig) != 64 or len(key) != 32:
                host_ok[i] = False
                chunks.append(zeros)
                continue
            r_raw, s_raw = sig[:32], sig[32:]
            if from_bytes(s_raw, "little") >= L:  # malleability, RFC 8032 §5.1.7
                host_ok[i] = False
                chunks.append(zeros)
                continue
            k = from_bytes(sha512(r_raw + key + messages[i]).digest(), "little") % L
            chunks.append(r_raw + key + s_raw + k.to_bytes(32, "little"))
        # One bulk copy (no per-row numpy calls).
        rows = np.frombuffer(b"".join(chunks), dtype=np.uint8).reshape(n, 128)
        y = rows[:, :64].copy().reshape(2 * n, 32)  # R and A, lane by lane
        y[:, 31] &= 0x7F
        host_ok &= _y_lt_p(y).reshape(n, 2).all(axis=1)
        return rows, host_ok

    #: The lane the field arithmetic of a ``width``-lane launch takes on this
    #: process's backend, ``"mosaic"`` or ``"xla"`` (the sidecar's health).
    field_path = staticmethod(mosaic.launch_path)

    def compile_ahead(self, sizes: Sequence[int]) -> None:
        """Compile, one after the other on the calling thread, the width
        each wave of ``sizes`` signatures launches at — before any such wave
        is there.  jax keeps trace, lowered module and executable, so the
        first launch of a width then starts at the launch.

        Why a server that warms several widths calls this instead of
        sending one wave after the other: tracing and lowering are seconds
        of Python a shape, while a compile — with a warm persistent cache
        the load of an executable, the longest item of a start — holds no
        Python lock.  So while this thread compiles the first width a
        helper thread traces and lowers every LATER one (it never compiles:
        no two threads of a process compile at once)."""
        import threading

        jitted = _verify_kernel.__wrapped__

        def lower(n: int):
            return jitted.lower(
                jax.ShapeDtypeStruct((_PACKED_ROWS, self.launch_width(n)), np.uint8)
            )

        first, *later = sizes
        lowered = lower(first)
        ahead = threading.Thread(
            target=lambda: [lower(n) for n in later], name="lower-ahead",
            daemon=True,
        )
        ahead.start()
        lowered.compile()
        ahead.join()
        for n in later:
            lower(n).compile()  # jax's own caches answer the lowering

    def verify_batch(
        self,
        messages: Sequence[bytes],
        signatures: Sequence[bytes],
        public_keys: Sequence[bytes],
    ) -> np.ndarray:
        n = len(messages)
        if not (n == len(signatures) == len(public_keys)):
            raise ValueError("batch length mismatch")
        if n == 0:
            return np.zeros(0, dtype=bool)
        if n < self._min_device_batch:
            return self._verify_host(messages, signatures, public_keys)

        # Four phases of the calling thread, in the sidecar its flusher
        # (obs/kernels.py FLUSHER_PHASES).
        with phase("verify.prepare", cpu=True):
            rows, host_ok = self._prepare(messages, signatures, public_keys)
        with phase("verify.layout"):
            wave = jnp.asarray(pack_wave(rows, host_ok, self.launch_width(n)))
        with phase("verify.dispatch"):
            result = _verify_kernel(wave)
        with phase("verify.await"):
            verdicts = np.asarray(result)
        return verdicts[:n]

    @staticmethod
    def _canonical_ok(signatures, public_keys) -> np.ndarray:
        """The device kernel's host-side pre-checks, standalone: sig length,
        S < L (RFC 8032 §5.1.7 malleability), and canonical compressed
        encodings (y < p) for both R and A."""
        n = len(signatures)
        ok = np.ones(n, dtype=bool)
        for i in range(n):
            sig, key = signatures[i], public_keys[i]
            if len(sig) != 64 or len(key) != 32:
                ok[i] = False
                continue
            if int.from_bytes(sig[32:], "little") >= L:
                ok[i] = False
                continue
            y_r = int.from_bytes(sig[:32], "little") & ((1 << 255) - 1)
            y_a = int.from_bytes(key, "little") & ((1 << 255) - 1)
            if y_r >= fe.P or y_a >= fe.P:
                ok[i] = False
        return ok

    @classmethod
    def _verify_host(cls, messages, signatures, public_keys) -> np.ndarray:
        """Sequential host fallback through the ``cryptography`` package
        (OpenSSL).

        Ed25519 verifiers disagree on adversarial edge cases (non-canonical
        encodings, S >= L), and in BFT a vote's validity must not depend on
        which replica (or batch size) checked it — so the device kernel's
        strict pre-checks run here too, and all replicas must use identical
        verifier config (min_device_batch included in quorum-relevant
        paths only via config parity)."""
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PublicKey,
        )

        out = cls._canonical_ok(signatures, public_keys)
        for i, (msg, sig, key) in enumerate(zip(messages, signatures, public_keys)):
            if not out[i]:
                continue
            try:
                Ed25519PublicKey.from_public_bytes(bytes(key)).verify(
                    bytes(sig), bytes(msg)
                )
            except (InvalidSignature, ValueError):
                out[i] = False
        return out

    def verify_host(self, messages, signatures, public_keys) -> np.ndarray:
        """Public seam for the coalescer's wedged-device escape hatch:
        verify on the host regardless of batch size, same strict semantics
        as the device path.  (A forwarding method, not a class-level alias,
        so subclass overrides of ``_verify_host`` take effect here too.)"""
        return self._verify_host(messages, signatures, public_keys)


# --- randomized batch verification ------------------------------------------
# One aggregate check for the whole batch: Σ zᵢ(SᵢB − kᵢAᵢ − Rᵢ) = 0 with
# independent 128-bit coefficients zᵢ.  A batch containing any forgery
# passes with probability <= 2^-128 over the choice of z (see SAFETY.md §7);
# the win is that the 256-bit variable-base doubling chain — ~2,000 of the
# strict kernel's ~2,800 M/sig — is paid once per BATCH, not per signature.

_Z_BITS = 128
#: Signed-4-bit windows for a 128-bit coefficient: 32 value windows plus one
#: for the recoding carry.
_Z_WINDOWS = _Z_BITS // _WINDOW_BITS + 1  # 33
_Z_TAG = b"ctpu/batchz/v1"


def _transcript_coefficients(
    messages: Sequence[bytes],
    signatures: Sequence[bytes],
    public_keys: Sequence[bytes],
) -> list[int]:
    """Deterministic per-batch coefficients zᵢ ∈ [1, 2^128).

    Fiat–Shamir over the whole batch: every byte of every (message,
    signature, key) triple — length-framed so no two transcripts collide —
    feeds a root hash, and zᵢ = H(root || i).  An adversary must commit to
    the batch contents before learning any zᵢ, which is exactly the game
    the 2^-128 soundness bound is proved in; and there is no wallclock or
    ambient RNG, so same-seed runs stay byte-identical (repo determinism
    rule)."""
    sha512 = hashlib.sha512

    def frame(raw: bytes) -> bytes:
        return len(raw).to_bytes(8, "little") + bytes(raw)

    leaves = [
        sha512(frame(m) + frame(s) + frame(a)).digest()
        for m, s, a in zip(messages, signatures, public_keys)
    ]
    root = sha512(
        _Z_TAG + len(leaves).to_bytes(8, "little") + b"".join(leaves)
    ).digest()
    return [
        int.from_bytes(
            sha512(root + i.to_bytes(8, "little")).digest()[:_Z_BITS // 8],
            "little",
        )
        or 1
        for i in range(len(leaves))
    ]


def _signed_digits_int(value: int, windows: int) -> list[int]:
    """Host-integer twin of
    :func:`consensus_tpu.ops.scalar25519.signed_window_digits`: signed
    4-bit digits in [-8, 7], MSB window first.  ``windows`` must leave one
    window of headroom for the recoding carry."""
    digits = [0] * windows
    carry = 0
    for j in range(windows):
        t = (value & 15) + carry
        value >>= 4
        if t >= 8:
            digits[j] = t - 16
            carry = 1
        else:
            digits[j] = t
            carry = 0
    if carry or value:
        raise ValueError("scalar too wide for signed-digit recoding")
    return digits[::-1]


def batch_verify_impl(
    y_r: jnp.ndarray,        # (32, batch) R.y limbs
    sign_r: jnp.ndarray,     # (batch,)    R.x sign bits
    y_a: jnp.ndarray,        # (32, batch) A.y limbs
    sign_a: jnp.ndarray,     # (batch,)    A.x sign bits
    zs_digits8: jnp.ndarray, # (32, 1)     Σ zᵢsᵢ mod L, 8-bit comb digits
    zk_digits: jnp.ndarray,  # (64, batch) zᵢkᵢ mod L signed 4-bit + 8, MSB first
    z_digits: jnp.ndarray,   # (33, batch) zᵢ signed 4-bit + 8, MSB first
    host_ok: jnp.ndarray,    # (batch,)    host pre-checks passed
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Un-jitted randomized-batch kernel body.

    Computes [Σzᵢsᵢ mod L]B + Σ[zᵢkᵢ mod L](−Aᵢ) + Σ[zᵢ](−Rᵢ) as one
    shared-doubling Straus MSM (:func:`consensus_tpu.ops.ed25519
    .straus_shared_msm`) plus a batch-1 fixed-base comb, and tests the
    accumulator against the identity.  Returns ``(eq_ok, valid)``:
    ``eq_ok`` is the scalar aggregate verdict, ``valid`` flags entries that
    decompressed (host pre-checks included).  Entries with ``valid`` false
    have their digits masked to zero so they contribute the identity —
    padding lanes ride the same mechanism — and the driver re-checks the
    surviving subset, so an undecompressable R/A can never poison the
    aggregate verdict of its batchmates."""
    y_r = y_r.astype(jnp.float32)
    y_a = y_a.astype(jnp.float32)
    sign_r = sign_r.astype(jnp.int32)
    sign_a = sign_a.astype(jnp.int32)
    zs_digits8 = zs_digits8.astype(jnp.int32)
    zk_digits = zk_digits.astype(jnp.int32)
    z_digits = z_digits.astype(jnp.int32)

    batch = y_r.shape[-1]
    pt, pt_ok = ed.decompress(
        jnp.concatenate([y_r, y_a], axis=-1),
        jnp.concatenate([sign_r, sign_a], axis=-1),
    )
    r_point = ed.Point(
        x=pt.x[..., :batch], y=pt.y[..., :batch],
        z=pt.z[..., :batch], t=pt.t[..., :batch],
    )
    a_point = ed.Point(
        x=pt.x[..., batch:], y=pt.y[..., batch:],
        z=pt.z[..., batch:], t=pt.t[..., batch:],
    )
    valid = host_ok & pt_ok[..., :batch] & pt_ok[..., batch:]

    # Digit 0 is encoded as 8; masking an invalid lane's digits to 8 makes
    # every one of its window contributions the identity point.
    zk_digits = jnp.where(valid[None], zk_digits, 8)
    z_digits = jnp.where(valid[None], z_digits, 8)

    a_table = ed.multiples_table9(ed.negate(a_point))
    r_table = ed.multiples_table9(ed.negate(r_point))
    acc = ed.straus_shared_msm(a_table, r_table, zk_digits, z_digits)
    acc = ed.add(acc, ed.fixed_base_mul_comb(zs_digits8))
    return ed.is_identity(acc)[0], valid


_batch_verify_kernel = instrumented_jit(
    batch_verify_impl, "ed25519.batch_verify" + kernel_lane_suffix()
)


def _ref_negate(p):
    x, y, z, t = p
    return ((fe.P - x) % fe.P, y, z, (fe.P - t) % fe.P)


class Ed25519RandomizedBatchVerifier(Ed25519BatchVerifier):
    """Randomized batch verification with bisection fallback.

    Same ``verify_batch`` contract (and, for honest inputs, the same result
    vector) as :class:`Ed25519BatchVerifier`, at an amortized per-signature
    cost that approaches the add-dominated floor as batches grow: one
    aggregate check replaces n independent double chains.  When the
    aggregate fails, the batch is split in half and each half re-checked
    with FRESH transcript coefficients — forgeries are localized in
    O(f · log n) aggregate checks, and every subset below
    ``min_randomized`` is decided by the strict verifier, so the final
    boolean vector for any input the strict kernel rejects-by-math is
    bit-identical to the strict path's (see SAFETY.md §7 for the one
    caveat class: small-order torsion components, which honest signers
    never produce).

    ``min_device_batch`` picks between the shared-doubling device kernel
    and a host big-int Straus with the identical two-phase window schedule.
    """

    randomized = True

    def __init__(
        self,
        *,
        min_device_batch: int = 1,
        pad_to: int = 0,
        min_randomized: int = 2,
    ) -> None:
        super().__init__(
            min_device_batch=min_device_batch,
            pad_to=pad_to,
        )
        self._min_randomized = max(2, int(min_randomized))

    def verify_batch(
        self,
        messages: Sequence[bytes],
        signatures: Sequence[bytes],
        public_keys: Sequence[bytes],
    ) -> np.ndarray:
        n = len(messages)
        if not (n == len(signatures) == len(public_keys)):
            raise ValueError("batch length mismatch")
        results = np.zeros(n, dtype=bool)
        if n == 0:
            return results
        host_ok = self._canonical_ok(signatures, public_keys)
        scalars: dict[int, tuple[int, int]] = {}
        for i in range(n):
            if not host_ok[i]:
                continue  # stays False, exactly like the strict kernel
            sig = bytes(signatures[i])
            key = bytes(public_keys[i])
            k = int.from_bytes(
                hashlib.sha512(sig[:32] + key + bytes(messages[i])).digest(),
                "little",
            ) % L
            scalars[i] = (int.from_bytes(sig[32:], "little"), k)
        self._check(
            [i for i in range(n) if host_ok[i]],
            messages, signatures, public_keys, scalars, results,
        )
        return results

    def _check(self, idx, messages, signatures, public_keys, scalars, results):
        """Recursive bisection: decide every index in ``idx``."""
        if not idx:
            return
        if len(idx) < self._min_randomized:
            sub = super().verify_batch(
                [messages[i] for i in idx],
                [signatures[i] for i in idx],
                [public_keys[i] for i in idx],
            )
            for j, i in enumerate(idx):
                results[i] = bool(sub[j])
            return
        zs = _transcript_coefficients(
            [messages[i] for i in idx],
            [signatures[i] for i in idx],
            [public_keys[i] for i in idx],
        )
        if len(idx) >= self._min_device_batch:
            eq_ok, valid = self._aggregate_device(idx, signatures, public_keys, scalars, zs)
        else:
            eq_ok, valid = self._aggregate_host(idx, signatures, public_keys, scalars, zs)
        if not all(valid):
            # Decompression failures are definitively invalid (strict
            # parity: the strict kernel rejects them the same way); their
            # digits were masked out of the aggregate, but re-check the
            # survivors under a fresh transcript rather than trusting a
            # verdict whose membership changed.
            survivors = [i for i, ok in zip(idx, valid) if ok]
            self._check(survivors, messages, signatures, public_keys, scalars, results)
            return
        if eq_ok:
            for i in idx:
                results[i] = True
            return
        mid = len(idx) // 2
        self._check(idx[:mid], messages, signatures, public_keys, scalars, results)
        self._check(idx[mid:], messages, signatures, public_keys, scalars, results)

    def _aggregate_inputs(self, idx, signatures, scalars, zs):
        """Shared host math for both backends: per-entry scalars
        (zk mod L, z) and the aggregate base-point scalar Σzᵢsᵢ mod L."""
        zk = [(z * scalars[i][1]) % L for z, i in zip(zs, idx)]
        u = 0
        for z, i in zip(zs, idx):
            u += z * scalars[i][0]
        return zk, u % L

    def _aggregate_device(self, idx, signatures, public_keys, scalars, zs):
        """One shared-doubling kernel launch over the subset."""
        m = len(idx)
        zk, u = self._aggregate_inputs(idx, signatures, scalars, zs)
        y_r, sign_r, _ = _prep_compressed([bytes(signatures[i])[:32] for i in idx])
        y_a, sign_a, _ = _prep_compressed([bytes(public_keys[i]) for i in idx])
        zk_digits = np.array(
            [_signed_digits_int(v, _WINDOWS) for v in zk], dtype=np.int16
        ).T
        z_digits = np.array(
            [_signed_digits_int(z, _Z_WINDOWS) for z in zs], dtype=np.int16
        ).T
        zk_digits = (zk_digits + 8).astype(np.uint8)
        z_digits = (z_digits + 8).astype(np.uint8)
        u_row = np.frombuffer(u.to_bytes(32, "little"), dtype=np.uint8).reshape(1, 32)
        zs_digits8 = np.ascontiguousarray(u_row.T)  # u's bytes ARE the comb's digits
        host_ok = np.ones(m, dtype=bool)

        if self._pad_to >= m:
            padded = self._pad_to
        else:
            padded = _next_pow2(m)
        if padded != m:
            pad = padded - m
            y_r = np.pad(y_r, ((0, pad), (0, 0)))
            y_a = np.pad(y_a, ((0, pad), (0, 0)))
            sign_r = np.pad(sign_r, (0, pad))
            sign_a = np.pad(sign_a, (0, pad))
            # Padding lanes: host_ok=False masks their digits to identity
            # contributions inside the kernel; the pad value just keeps the
            # encoding in range.
            zk_digits = np.pad(zk_digits, ((0, 0), (0, pad)), constant_values=8)
            z_digits = np.pad(z_digits, ((0, 0), (0, pad)), constant_values=8)
            host_ok = np.pad(host_ok, (0, pad))

        eq_ok, valid = _batch_verify_kernel(
            jnp.asarray(np.ascontiguousarray(y_r.T)),
            jnp.asarray(sign_r),
            jnp.asarray(np.ascontiguousarray(y_a.T)),
            jnp.asarray(sign_a),
            jnp.asarray(zs_digits8),
            jnp.asarray(zk_digits),
            jnp.asarray(z_digits),
            jnp.asarray(host_ok),
        )
        return bool(np.asarray(eq_ok)), list(np.asarray(valid)[:m])

    def _aggregate_host(self, idx, signatures, public_keys, scalars, zs):
        """Host big-int twin of the kernel: the SAME two-phase shared-window
        schedule in plain integers (~113 point adds per signature vs ~380
        for per-signature double-and-add — the host path needs the
        amortization too, it backs every CPU deployment and test)."""
        m = len(idx)
        a_pts = [_ref_decompress(bytes(public_keys[i])) for i in idx]
        r_pts = [_ref_decompress(bytes(signatures[i])[:32]) for i in idx]
        valid = [a is not None and r is not None for a, r in zip(a_pts, r_pts)]
        if not all(valid):
            return False, valid
        zk, u = self._aggregate_inputs(idx, signatures, scalars, zs)

        def table(p):
            neg = _ref_negate(p)
            tbl = [_REF_IDENTITY, neg]
            for _ in range(_TABLE - 2):
                tbl.append(_ref_add(tbl[-1], neg))
            return tbl

        a_tbl = [table(p) for p in a_pts]
        r_tbl = [table(p) for p in r_pts]
        zk_digits = [_signed_digits_int(v, _WINDOWS) for v in zk]
        z_digits = [_signed_digits_int(z, _Z_WINDOWS) for z in zs]

        acc = _REF_IDENTITY
        low_start = _WINDOWS - _Z_WINDOWS
        for w in range(_WINDOWS):
            for _ in range(4):
                acc = _ref_add(acc, acc)
            for j in range(m):
                d = zk_digits[j][w]
                if d:
                    acc = _ref_add(
                        acc, a_tbl[j][d] if d > 0 else _ref_negate(a_tbl[j][-d])
                    )
                if w >= low_start:
                    d = z_digits[j][w - low_start]
                    if d:
                        acc = _ref_add(
                            acc, r_tbl[j][d] if d > 0 else _ref_negate(r_tbl[j][-d])
                        )
        acc = _ref_add(acc, _ref_mul(u, _BASE_POINT))
        eq_ok = acc[0] % fe.P == 0 and (acc[1] - acc[2]) % fe.P == 0
        return eq_ok, valid


# --- pure-Python RFC 8032 reference (host) ---------------------------------
# Plain-integer edwards25519: keygen, sign, verify — the reference the
# tests and the host big-int twins compare against (interoperable with any
# conformant implementation, Python-speed).  Verification keeps the strict
# semantics of the device kernel: S < L, canonical (y < p) encodings.

_D_REF = (-121665 * pow(121666, fe.P - 2, fe.P)) % fe.P
_BASE_Y = (4 * pow(5, fe.P - 2, fe.P)) % fe.P


def _ref_recover_x(y: int, sign: int) -> Optional[int]:
    x2 = (y * y - 1) * pow(_D_REF * y * y + 1, fe.P - 2, fe.P) % fe.P
    x = pow(x2, (fe.P + 3) // 8, fe.P)
    if (x * x - x2) % fe.P:
        x = x * pow(2, (fe.P - 1) // 4, fe.P) % fe.P
    if (x * x - x2) % fe.P:
        return None
    if x == 0 and sign:
        return None  # RFC 8032 §5.1.3 step 4
    if x & 1 != sign:
        x = fe.P - x
    return x


_REF_IDENTITY = (0, 1, 1, 0)


def _ref_add(p, q):
    # Extended homogeneous coordinates, RFC 8032 §5.1.4.
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % fe.P
    b = (y1 + x1) * (y2 + x2) % fe.P
    c = 2 * t1 * t2 * _D_REF % fe.P
    d = 2 * z1 * z2 % fe.P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % fe.P, g * h % fe.P, f * g % fe.P, e * h % fe.P)


def _ref_mul(s: int, p):
    q = _REF_IDENTITY
    while s:
        if s & 1:
            q = _ref_add(q, p)
        p = _ref_add(p, p)
        s >>= 1
    return q


_BASE_POINT = (
    _ref_recover_x(_BASE_Y, 0),
    _BASE_Y,
    1,
    _ref_recover_x(_BASE_Y, 0) * _BASE_Y % fe.P,
)


def _ref_compress(p) -> bytes:
    x, y, z, _ = p
    zinv = pow(z, fe.P - 2, fe.P)
    x, y = x * zinv % fe.P, y * zinv % fe.P
    return (y | ((x & 1) << 255)).to_bytes(32, "little")


def _ref_decompress(raw: bytes):
    if len(raw) != 32:
        return None
    y = int.from_bytes(raw, "little")
    sign, y = y >> 255, y & ((1 << 255) - 1)
    if y >= fe.P:
        return None
    x = _ref_recover_x(y, sign)
    if x is None:
        return None
    return (x, y, 1, x * y % fe.P)


def _ref_scalars(seed: bytes) -> tuple[int, bytes]:
    if len(seed) != 32:
        raise ValueError("Ed25519 seed must be 32 bytes")
    h = hashlib.sha512(seed).digest()
    a = int.from_bytes(h[:32], "little")
    a &= (1 << 254) - 8
    a |= 1 << 254
    return a, h[32:]


def ref_public_key(seed: bytes) -> bytes:
    """RFC 8032 §5.1.5: the 32-byte public key for a 32-byte seed."""
    a, _ = _ref_scalars(seed)
    return _ref_compress(_ref_mul(a, _BASE_POINT))


def ref_sign(seed: bytes, message: bytes) -> bytes:
    """RFC 8032 §5.1.6: the 64-byte signature R || S."""
    a, prefix = _ref_scalars(seed)
    a_enc = _ref_compress(_ref_mul(a, _BASE_POINT))
    r = int.from_bytes(hashlib.sha512(prefix + message).digest(), "little") % L
    r_enc = _ref_compress(_ref_mul(r, _BASE_POINT))
    k = int.from_bytes(
        hashlib.sha512(r_enc + a_enc + message).digest(), "little"
    ) % L
    s = (r + k * a) % L
    return r_enc + s.to_bytes(32, "little")


def ref_verify(public_key: bytes, signature: bytes, message: bytes) -> bool:
    """RFC 8032 §5.1.7 with the device kernel's strict pre-checks."""
    if len(signature) != 64 or len(public_key) != 32:
        return False
    r_enc, s_raw = signature[:32], signature[32:]
    s = int.from_bytes(s_raw, "little")
    if s >= L:
        return False
    a_pt = _ref_decompress(public_key)
    r_pt = _ref_decompress(r_enc)
    if a_pt is None or r_pt is None:
        return False
    k = int.from_bytes(
        hashlib.sha512(r_enc + public_key + message).digest(), "little"
    ) % L
    lhs = _ref_mul(s, _BASE_POINT)
    rhs = _ref_add(r_pt, _ref_mul(k, a_pt))
    return (
        (lhs[0] * rhs[2] - rhs[0] * lhs[2]) % fe.P == 0
        and (lhs[1] * rhs[2] - rhs[1] * lhs[2]) % fe.P == 0
    )


__all__ = [
    "Ed25519BatchVerifier",
    "Ed25519RandomizedBatchVerifier",
    "L",
    "ref_public_key",
    "ref_sign",
    "ref_verify",
]
