"""Half-aggregated Ed25519 quorum certificates (models/aggregate.py): the
aggregate/verify unit surface, the adversarial rejection-class parity
matrix (device kernel and host big-int twin agreeing with STRICT
verification on every class), bisection localization, and the
one-MSM-launch-per-cert accounting gate.

Everything here runs on the in-repo reference implementation
(``ref_sign`` / ``ref_public_key``) so the file needs neither the
``cryptography`` package nor a TPU — the "device" path is the
shared-doubling kernel jitted on whatever backend JAX has.
"""

import numpy as np
import pytest

from consensus_tpu.models.aggregate import HalfAggregator, halfagg_coefficients
from consensus_tpu.models.ed25519 import (
    Ed25519BatchVerifier,
    L,
    _ref_decompress,
    ref_public_key,
    ref_sign,
)
from consensus_tpu.obs.kernels import KERNELS
from consensus_tpu.ops import field25519 as fe

N = 4  # quorum-sized; padded device batch stays tiny on the CPU backend


def make_quorum(n=N, tag=b"halfagg"):
    msgs, sigs, keys = [], [], []
    for i in range(n):
        seed = bytes([i + 1]) * 32
        m = b"ctpu/%s/%d" % (tag, i)
        msgs.append(m)
        sigs.append(ref_sign(seed, m))
        keys.append(ref_public_key(seed))
    return msgs, sigs, keys


def strict_verdicts(msgs, sigs, keys):
    return np.asarray(
        Ed25519BatchVerifier(min_device_batch=10**9).verify_batch(
            msgs, sigs, keys
        )
    )


DEVICE = HalfAggregator(min_device_batch=1)
HOST = HalfAggregator(min_device_batch=10**9)


def aggregate_parts(msgs, sigs, keys):
    agg, bad = HOST.aggregate(msgs, sigs, keys)
    assert agg is not None and bad == ()
    rs, s_agg = agg
    return list(rs), s_agg


def test_aggregate_verifies_on_both_backends():
    msgs, sigs, keys = make_quorum()
    rs, s_agg = aggregate_parts(msgs, sigs, keys)
    assert rs == [s[:32] for s in sigs]
    assert HOST.verify(msgs, rs, s_agg, keys)
    assert DEVICE.verify(msgs, rs, s_agg, keys)


def test_coefficients_deterministic_and_committing():
    msgs, sigs, keys = make_quorum()
    rs = [s[:32] for s in sigs]
    zs = halfagg_coefficients(msgs, rs, keys)
    assert zs == halfagg_coefficients(msgs, rs, keys)  # no ambient RNG
    assert zs[0] == 1 and all(z != 0 for z in zs)
    # The transcript commits to every (message, R, key) triple: perturbing
    # any one changes the downstream coefficients.
    other = halfagg_coefficients([b"x"] + msgs[1:], rs, keys)
    assert other[1:] != zs[1:]


# --- the adversarial rejection-class matrix --------------------------------
#
# Each case mutates one honest cert dimension; BOTH backends must reject.

def _tamper_s_agg(msgs, rs, s_agg, keys):
    bad = bytearray(s_agg)
    bad[0] ^= 0x01
    return msgs, rs, bytes(bad), keys


def _s_agg_above_l(msgs, rs, s_agg, keys):
    return msgs, rs, L.to_bytes(32, "little"), keys


def _s_agg_bad_length(msgs, rs, s_agg, keys):
    return msgs, rs, s_agg[:31], keys


def _forge_component_r(msgs, rs, s_agg, keys):
    bad = bytearray(rs[1])
    bad[3] ^= 0xFF
    return msgs, [rs[0], bytes(bad)] + rs[2:], s_agg, keys


def _wrong_key(msgs, rs, s_agg, keys):
    return msgs, rs, s_agg, [keys[1], keys[0]] + keys[2:]


def _wrong_message(msgs, rs, s_agg, keys):
    return [b"swapped"] + msgs[1:], rs, s_agg, keys


def _non_decodable_r_high_y(msgs, rs, s_agg, keys):
    # y-coordinate >= p: rejected by the canonical-encoding precheck.
    return msgs, [b"\xff" * 32] + rs[1:], s_agg, keys


def _non_decodable_r_off_curve(msgs, rs, s_agg, keys):
    # Smallest y < p whose decompression has no square root: exercises the
    # kernel's valid-mask (identity-masked inside the MSM) rather than the
    # host precheck.
    y = next(
        c for c in range(2, 64)
        if _ref_decompress(c.to_bytes(32, "little")) is None
    )
    assert (y & ((1 << 255) - 1)) < fe.P
    return msgs, [y.to_bytes(32, "little")] + rs[1:], s_agg, keys


REJECTION_CLASSES = {
    "tampered_s_agg": _tamper_s_agg,
    "s_agg_above_L": _s_agg_above_l,
    "s_agg_bad_length": _s_agg_bad_length,
    "forged_component_R": _forge_component_r,
    "wrong_key": _wrong_key,
    "wrong_message": _wrong_message,
    "non_decodable_R_high_y": _non_decodable_r_high_y,
    "non_decodable_R_off_curve": _non_decodable_r_off_curve,
}


@pytest.mark.parametrize("cls", sorted(REJECTION_CLASSES))
def test_rejection_class_parity_device_and_host(cls):
    msgs, sigs, keys = make_quorum()
    rs, s_agg = aggregate_parts(msgs, sigs, keys)
    m2, r2, s2, k2 = REJECTION_CLASSES[cls](msgs, list(rs), s_agg, list(keys))
    host = HOST.verify(m2, r2, s2, k2)
    device = DEVICE.verify(m2, r2, s2, k2)
    assert host is False and device is False, (
        f"{cls}: host={host} device={device} — backends must both reject"
    )
    # Control: the honest cert still passes on both backends.
    assert HOST.verify(msgs, rs, s_agg, keys)
    assert DEVICE.verify(msgs, rs, s_agg, keys)


def test_empty_cert_rejected():
    assert HOST.verify([], [], b"\x00" * 32, []) is False
    assert DEVICE.verify([], [], b"\x00" * 32, []) is False


# --- aggregation fallback: strict parity of the localized bad set ----------


@pytest.mark.parametrize("bad_indices", [(1,), (0, 3), (2,)])
def test_bisection_localizes_exactly_the_strict_invalid_set(bad_indices):
    msgs, sigs, keys = make_quorum(8)
    for i in bad_indices:
        flipped = bytearray(sigs[i])
        flipped[7] ^= 0xFF
        sigs[i] = bytes(flipped)
    agg = HalfAggregator(min_device_batch=10**9)
    cert, bad = agg.aggregate(msgs, sigs, keys)
    assert cert is None
    assert agg.fallback_bisections == 1
    strict = strict_verdicts(msgs, sigs, keys)
    assert set(bad) == {i for i in range(8) if not strict[i]} == set(bad_indices)


def test_component_scalar_above_l_localized_like_strict():
    msgs, sigs, keys = make_quorum(4)
    sigs[2] = sigs[2][:32] + L.to_bytes(32, "little")  # S >= L: non-canonical
    agg = HalfAggregator(min_device_batch=10**9)
    cert, bad = agg.aggregate(msgs, sigs, keys)
    assert cert is None
    strict = strict_verdicts(msgs, sigs, keys)
    assert set(bad) == {i for i in range(4) if not strict[i]} == {2}


def test_aggregate_counts_checks_and_rejects_length_mismatch():
    msgs, sigs, keys = make_quorum()
    agg = HalfAggregator(min_device_batch=10**9)
    before = agg.aggregate_checks
    assert agg.aggregate(msgs, sigs, keys)[0] is not None
    assert agg.aggregate_checks == before + 1  # ONE self-check per aggregate
    with pytest.raises(ValueError):
        agg.aggregate(msgs, sigs[:-1], keys)
    with pytest.raises(ValueError):
        agg.verify(msgs, [s[:32] for s in sigs][:-1], b"\x00" * 32, keys)


# --- launch accounting: exactly ONE MSM launch per aggregate cert ----------


def _halfagg_launches() -> int:
    return KERNELS.snapshot().get("ed25519.halfagg_verify", {}).get(
        "launches", 0
    )


def test_one_msm_launch_per_cert_verify():
    msgs, sigs, keys = make_quorum()
    rs, s_agg = aggregate_parts(msgs, sigs, keys)
    DEVICE.verify(msgs, rs, s_agg, keys)  # warmup: compile outside the count
    before = _halfagg_launches()
    for _ in range(5):
        assert DEVICE.verify(msgs, rs, s_agg, keys)
    assert _halfagg_launches() - before == 5, (
        "an aggregate cert verify must cost exactly one MSM launch"
    )
    # The host twin never touches the kernel.
    before = _halfagg_launches()
    assert HOST.verify(msgs, rs, s_agg, keys)
    assert _halfagg_launches() == before


def test_engine_knobs_inherited():
    engine = Ed25519BatchVerifier(min_device_batch=10**9)
    agg = HalfAggregator(engine=engine)
    assert agg._min_device_batch == 10**9  # rides the host twin like the engine
