"""Bit-exactness of the on-device SHA-512 kernel and mod-L scalar stage.

The fused verification pipeline (models/fused.py) is only sound if its
device hash/reduce stages agree with ``hashlib`` / big-int arithmetic on
EVERY input — a single differing byte desynchronizes the Fiat–Shamir
transcript across replicas.  These tests pin the kernels against their
host twins on the classic SHA-512 padding boundaries (55/56, 63/64,
111/112, 127/128 — where the length field does or doesn't fit the last
block) and the mod-L boundary scalars (0, L−1, L, L+1, 2²⁵⁶−1, full
512-bit range).

Everything here runs eagerly on tiny batches — no big jitted graphs, so
the suite stays cheap on cold caches (the fused end-to-end engines are
covered by tests/test_fused.py).
"""

import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from consensus_tpu.ops import scalar25519 as sc  # noqa: E402
from consensus_tpu.ops import sha512 as sh  # noqa: E402
from consensus_tpu.ops.scalar25519 import L  # noqa: E402

#: Lengths covering every padding regime: empty; 55/56 straddles the
#: "length field fits the first block" boundary; 63/64 the block edge;
#: 111/112 and 127/128 the same two boundaries in the second block.
_BOUNDARY_LENGTHS = [0, 1, 55, 56, 63, 64, 111, 112, 127, 128]


def _device_digests(messages):
    blocks, n_blocks = sh.pad_messages(messages)
    out = np.asarray(sh.digest_bytes(sh.sha512_blocks(blocks, n_blocks)))
    return [bytes(out[:, i].astype(np.uint8)) for i in range(len(messages))]


def test_sha512_matches_hashlib_on_padding_boundaries():
    rng = np.random.default_rng(0xED)
    messages = [
        rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        for n in _BOUNDARY_LENGTHS
    ]
    got = _device_digests(messages)
    want = [hashlib.sha512(m).digest() for m in messages]
    for n, g, w in zip(_BOUNDARY_LENGTHS, got, want):
        assert g == w, f"digest mismatch at message length {n}"


def test_sha512_multiblock_and_ragged_batch():
    """A ragged batch (1..5 blocks in one padded launch) must hash each
    lane over exactly its own active block count."""
    rng = np.random.default_rng(7)
    messages = [
        rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        for n in [3, 200, 256, 400, 511, 512]
    ]
    assert _device_digests(messages) == [
        hashlib.sha512(m).digest() for m in messages
    ]


def test_sha512_chained_hash_of_hash():
    """Digest-of-digest round trip — the exact shape the transcript root
    computation uses (root = H(prefix ‖ leaf digests ‖ ...))."""
    inner = hashlib.sha512(b"ctpu fused pipeline").digest()
    (got,) = _device_digests([inner * 3])
    assert got == hashlib.sha512(inner * 3).digest()


@pytest.mark.parametrize(
    "value",
    [0, 1, L - 1, L, L + 1, 2 * L, 2**252, 2**255 - 19, 2**256 - 1],
    ids=["0", "1", "L-1", "L", "L+1", "2L", "2^252", "p", "2^256-1"],
)
def test_reduce_bytes_mod_l_boundary_scalars(value):
    rows = np.frombuffer(
        value.to_bytes(32, "little"), dtype=np.uint8
    ).reshape(32, 1)
    out = np.asarray(sc.reduce_bytes_mod_l(rows.astype(np.int32)))
    assert int.from_bytes(bytes(out[:, 0].astype(np.uint8)), "little") == (
        value % L
    )


def test_reduce_bytes_mod_l_full_512bit_range():
    """Random 64-byte inputs — the SHA-512 digest range the challenge
    reduction actually sees."""
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 256, size=(64, 9), dtype=np.uint8)
    out = np.asarray(sc.reduce_bytes_mod_l(rows.astype(np.int32)))
    for i in range(rows.shape[1]):
        want = int.from_bytes(bytes(rows[:, i]), "little") % L
        got = int.from_bytes(bytes(out[:, i].astype(np.uint8)), "little")
        assert got == want


def test_lt_l_on_the_boundary():
    rows = np.stack(
        [
            np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)
            for v in [0, L - 1, L, L + 1, 2**256 - 1]
        ],
        axis=1,
    ).astype(np.int32)
    assert list(np.asarray(sc.lt_l(rows))) == [True, True, False, False, False]


def test_signed_window_digits_match_host_recoding():
    from consensus_tpu.models.ed25519 import _signed_digits_int, _WINDOWS

    rng = np.random.default_rng(9)
    vals = [0, 1, L - 1, int(rng.integers(1, 2**63)) << 190]
    rows = np.stack(
        [
            np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)
            for v in vals
        ],
        axis=1,
    ).astype(np.int32)
    got = np.asarray(sc.signed_window_digits(rows, _WINDOWS))
    want = np.array(
        [_signed_digits_int(v, _WINDOWS) for v in vals], dtype=np.int64
    ).T + 8
    assert (got == want).all()
