"""Fused bytes-in → verdict-out strict engine (``Configuration.device_prep``).

Parity contract under test (SAFETY.md §10): with device_prep on, every
accept/reject verdict is bit-identical to the host-prep strict engine —
across forged/tampered lanes, ``S ≥ L``, non-canonical/non-decodable
encodings, wrong keys, and malformed lengths.  Plus the launch-count gate:
one fused kernel launch per wave.  The fused randomized and half-agg lanes
were removed in PR 22 (one compile per live wave size); the registry
refuses the combination and that refusal is pinned here.

Shape discipline: every device test pins one compiled-shape set (n = 8
lanes, pad_to = 8, ~100-byte messages → a 2-block SHA ladder) so the
whole file compiles a handful of graphs once — warmed by the persistent
compile cache thereafter.  End-to-end engine tests are marked slow (XLA CPU
compiles the big fused graph in minutes cold); the pre-check parity and
routing tests stay tier-1.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from consensus_tpu.models.aggregate import HalfAggregator  # noqa: E402
from consensus_tpu.models.ed25519 import (  # noqa: E402
    Ed25519BatchVerifier,
    Ed25519RandomizedBatchVerifier,
    L,
    ref_public_key,
    ref_sign,
)
from consensus_tpu.models.fused import (  # noqa: E402
    FusedEd25519BatchVerifier,
    canonical_ok_fast,
)
from consensus_tpu.ops import field25519 as fe  # noqa: E402


def _batch(n, seed=0, msg_len=100):
    rng = np.random.default_rng(seed)
    seeds = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes() for _ in range(n)]
    keys = [ref_public_key(s) for s in seeds]
    msgs = [
        rng.integers(0, 256, msg_len, dtype=np.uint8).tobytes() for _ in range(n)
    ]
    sigs = [ref_sign(s, m) for s, m in zip(seeds, msgs)]
    return msgs, sigs, keys


def _flip(raw, i):
    raw = bytes(raw)
    return raw[:i] + bytes([raw[i] ^ 1]) + raw[i + 1 :]


def _adversarial_waves():
    """Two 8-lane waves (one compiled shape) covering every rejection
    class next to honest lanes, including honest empty/long messages that
    share the wave's block ladder."""
    msgs, sigs, keys = _batch(16, seed=42)
    msgs, sigs, keys = list(msgs), list(sigs), list(keys)
    sigs[1] = _flip(sigs[1], 2)                  # tampered R: forged
    msgs[2] = _flip(msgs[2], 50)                 # tampered message
    keys[3] = keys[0]                            # wrong key
    sigs[4] = sigs[4][:32] + (
        int.from_bytes(sigs[4][32:], "little") + L
    ).to_bytes(32, "little")                     # S >= L (malleability)
    sigs[5] = sigs[5][:32] + (2**256 - 1).to_bytes(32, "little")  # S max
    keys[6] = fe.P.to_bytes(32, "little")        # non-canonical A (y = p)
    sigs[7] = (fe.P + 1).to_bytes(32, "little") + sigs[7][32:]  # y_r > p
    sigs[9] = sigs[9][:40]                       # bad signature length
    keys[10] = keys[10][:16]                     # bad key length
    sigs[11] = (2).to_bytes(32, "little") + sigs[11][32:]  # non-square y
    seeds_extra = np.random.default_rng(1).integers(0, 256, 32, dtype=np.uint8)
    msgs[12] = b""                               # honest empty message
    sigs[12] = ref_sign(seeds_extra.tobytes(), msgs[12])
    keys[12] = ref_public_key(seeds_extra.tobytes())
    return [
        (msgs[:8], sigs[:8], keys[:8]),
        (msgs[8:], sigs[8:], keys[8:]),
    ]


# --- tier-1: host pre-checks + routing (cheap) -------------------------------


def test_canonical_ok_fast_matches_loop_twin():
    for msgs, sigs, keys in _adversarial_waves():
        fast = canonical_ok_fast(sigs, keys)
        loop = Ed25519BatchVerifier._canonical_ok(sigs, keys)
        assert list(fast) == list(loop)


def test_engine_for_config_device_prep_routing():
    from consensus_tpu.models.verifier import engine_for_config
    from consensus_tpu.parallel import ShardedFusedEd25519Verifier

    class Cfg:
        crypto_tpu_min_batch = 4
        batch_verify_mode = False
        device_prep = True
        mesh_shards = 1

    eng = engine_for_config(Cfg())
    assert isinstance(eng, FusedEd25519BatchVerifier)
    assert eng._min_device_batch == 4
    Cfg.mesh_shards = 2
    assert isinstance(engine_for_config(Cfg()), ShardedFusedEd25519Verifier)
    with pytest.raises(ValueError, match="Ed25519-only"):
        engine_for_config(Cfg(), curve="p256")
    # device_prep x randomized was removed (one compile per live wave size):
    # the registry refuses it on either topology, with the reason.
    Cfg.batch_verify_mode = True
    for shards in (1, 2):
        Cfg.mesh_shards = shards
        with pytest.raises(ValueError, match="device_prep is strict-only"):
            engine_for_config(Cfg())
    # device_prep off: bit-for-bit the previous engine classes.
    Cfg.device_prep = False
    Cfg.mesh_shards = 1
    assert type(engine_for_config(Cfg())) is Ed25519RandomizedBatchVerifier
    Cfg.batch_verify_mode = False
    assert type(engine_for_config(Cfg())) is Ed25519BatchVerifier


def test_halfagg_over_a_fused_engine_keeps_the_host_transcript():
    """A device_prep deployment's cert verifies inherit the engine's padding
    and routing knobs, and run the host-derived transcript in front of the
    device MSM — there is no fused half-agg graph."""
    fused_engine = FusedEd25519BatchVerifier(min_device_batch=10**9, pad_to=8)
    agg = HalfAggregator(engine=fused_engine)
    assert agg._min_device_batch == 10**9 and agg._pad_to == 8
    msgs, sigs, keys = _batch(4, seed=8)
    cert, bad = agg.aggregate(msgs, sigs, keys)  # host twin (10**9 floor)
    assert cert is not None and bad == ()
    rs, s_agg = cert
    assert agg.verify(msgs, list(rs), s_agg, keys)
    assert not agg.verify(msgs, list(rs), _flip(s_agg, 1), keys)


def test_config_knob_validates():
    from consensus_tpu.config import default_config

    cfg = default_config(1).with_(device_prep=True)
    cfg.validate()
    assert cfg.device_prep


# --- slow: end-to-end fused engine parity + launch gate ---------------------


_KW = dict(min_device_batch=1, pad_to=8)


def _launches():
    from consensus_tpu.obs.kernels import KERNELS

    return {k: v["launches"] for k, v in KERNELS.snapshot().items()}


def _delta(before, after):
    return {
        k: after.get(k, 0) - before.get(k, 0)
        for k in set(before) | set(after)
        if after.get(k, 0) != before.get(k, 0)
    }


@pytest.mark.slow
def test_fused_strict_rejection_matrix_bit_identical():
    host = Ed25519BatchVerifier(**_KW)
    fused = FusedEd25519BatchVerifier(**_KW)
    for msgs, sigs, keys in _adversarial_waves():
        want = host.verify_batch(msgs, sigs, keys)
        before = _launches()
        got = fused.verify_batch(msgs, sigs, keys)
        delta = _delta(before, _launches())
        assert list(got) == list(want)
        # Launch-count gate: the whole wave is ONE fused launch — no
        # legacy prep kernel, no secondary launches.
        assert delta == {"ed25519.fused_verify": 1}


@pytest.mark.slow
def test_sharded_fused_parity():
    from consensus_tpu.parallel import ShardedFusedEd25519Verifier, mesh_for_shards

    mesh = mesh_for_shards(2)
    host = Ed25519BatchVerifier(**_KW)
    shard = ShardedFusedEd25519Verifier(mesh, **_KW)
    for msgs, sigs, keys in _adversarial_waves():
        assert list(shard.verify_batch(msgs, sigs, keys)) == list(
            host.verify_batch(msgs, sigs, keys)
        )


@pytest.mark.slow
def test_fused_verify_stream_double_buffering():
    fused = FusedEd25519BatchVerifier(**_KW)
    host = Ed25519BatchVerifier(**_KW)
    waves = _adversarial_waves()
    got = list(fused.verify_stream(waves))
    assert len(got) == len(waves)
    for out, (msgs, sigs, keys) in zip(got, waves):
        assert list(out) == list(host.verify_batch(msgs, sigs, keys))
