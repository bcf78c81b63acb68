"""Randomized Ed25519 batch verification (models/ed25519.py:
Ed25519RandomizedBatchVerifier) — the shared-doubling aggregate check, its
bisection fallback, and the wiring that rides it.

The load-bearing contract is EXACT boolean-vector parity with the strict
verifier: for every input the strict path rejects-by-math (forged S, wrong
message, wrong key, undecodable R/A, non-canonical encodings), the
randomized verifier must return the bit-identical result vector — the
aggregate check only amortizes cost, it never changes verdicts.  The
adversarial cases below hide forgeries at every awkward position (single,
clustered, all, bisection boundaries) and assert that parity.

Also covered: the deps.py multi-batch coalescing seam (one engine launch
for many quorum groups when batch_verify_mode is on), the chaos-engine
crypto parity gate (strict vs randomized engines on the SAME schedule must
produce identical ledgers), and the field-op counting shim behind
the amortization counts in PERF.md.
"""

import hashlib
import os
import subprocess
import sys

import numpy as np
import pytest

from consensus_tpu.api.deps import Verifier
from consensus_tpu.models.ed25519 import (
    Ed25519BatchVerifier,
    Ed25519RandomizedBatchVerifier,
    _transcript_coefficients,
    ref_public_key,
    ref_sign,
)
from consensus_tpu.types import Proposal

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N = 512


def _host_strict():
    return Ed25519BatchVerifier(min_device_batch=10**9)


def _host_randomized(**kw):
    kw.setdefault("min_device_batch", 10**9)
    return Ed25519RandomizedBatchVerifier(**kw)


@pytest.fixture(scope="module")
def corpus():
    """512 honest (message, signature, key) triples from 8 signers, pure
    deterministic ref crypto (no ambient RNG)."""
    seeds = [
        hashlib.sha512(b"ctpu/test-bv/%d" % i).digest()[:32] for i in range(8)
    ]
    pubs = [ref_public_key(s) for s in seeds]
    msgs, sigs, keys = [], [], []
    for i in range(N):
        m = b"batch-verify-%d" % i
        msgs.append(m)
        sigs.append(ref_sign(seeds[i % 8], m))
        keys.append(pubs[i % 8])
    return msgs, sigs, keys


@pytest.fixture(scope="module")
def strict_honest(corpus):
    """The strict host verifier's vector over the honest corpus — the
    ground truth every randomized run is compared against."""
    msgs, sigs, keys = corpus
    vec = _host_strict().verify_batch(msgs, sigs, keys)
    assert vec.all(), "honest corpus must verify strictly"
    return vec


def _forge(sig: bytes) -> bytes:
    # Flip a low byte of S: stays canonical (S < L), fails by math — the
    # case that MUST go through the aggregate-check + bisection machinery
    # rather than being shed by host pre-checks.
    f = bytearray(sig)
    f[33] ^= 0xFF
    return bytes(f)


def _strict_expected(strict_honest, corpus, forged_idx, sigs):
    """Strict vector for the corpus with ``sigs`` substituted — computed by
    running the strict verifier on exactly the substituted entries and
    splicing (strict verification is per-signature independent, so this IS
    the full strict vector, at a fraction of the cost)."""
    msgs, _, keys = corpus
    expected = strict_honest.copy()
    sub = _host_strict().verify_batch(
        [msgs[i] for i in forged_idx],
        [sigs[i] for i in forged_idx],
        [keys[i] for i in forged_idx],
    )
    for j, i in enumerate(forged_idx):
        expected[i] = sub[j]
    return expected


# --- adversarial bisection: exact parity with strict ------------------------


def test_honest_batch_matches_strict(corpus, strict_honest):
    msgs, sigs, keys = corpus
    got = _host_randomized().verify_batch(msgs, sigs, keys)
    assert got.dtype == np.bool_
    assert np.array_equal(got, strict_honest)


@pytest.mark.parametrize(
    "forged",
    [
        [137],                      # one forged hidden in 512
        [3, 77, 200, 201, 350, 508],  # several, incl. an adjacent pair
        [0, 255, 256, 511],         # bisection boundaries: ends + midpoint
    ],
    ids=["one-in-512", "multiple", "boundaries"],
)
def test_forged_signatures_localized_exactly(corpus, strict_honest, forged):
    msgs, sigs, keys = corpus
    sigs = list(sigs)
    for i in forged:
        sigs[i] = _forge(sigs[i])
    expected = _strict_expected(strict_honest, corpus, forged, sigs)
    assert not expected[forged].any(), "forgeries must fail strictly"
    got = _host_randomized().verify_batch(msgs, sigs, keys)
    assert np.array_equal(got, expected)


def test_all_forged(corpus):
    msgs, sigs, keys = corpus
    m, s, k = msgs[:64], [_forge(x) for x in sigs[:64]], keys[:64]
    expected = _host_strict().verify_batch(m, s, k)
    got = _host_randomized().verify_batch(m, s, k)
    assert not got.any()
    assert np.array_equal(got, expected)


def test_mixed_failure_classes_match_strict(corpus):
    """Every rejection class in one batch: math forgery, tampered message,
    wrong key, non-canonical S (host pre-check), undecodable A (non-QR y),
    undecodable R — the valid-mask re-check path and the host_ok path must
    both land exactly where strict lands."""
    msgs, sigs, keys = [list(x[:16]) for x in corpus]
    sigs[1] = _forge(sigs[1])
    msgs[3] = b"tampered"
    keys[5] = keys[6]                       # valid point, wrong signer
    sigs[7] = b"\xff" * 64                  # S >= L: non-canonical
    keys[9] = b"\x02" + b"\x00" * 31        # y=2 is not on the curve
    sigs[11] = b"\x02" + b"\x00" * 31 + sigs[11][32:]  # undecodable R
    expected = _host_strict().verify_batch(msgs, sigs, keys)
    got = _host_randomized().verify_batch(msgs, sigs, keys)
    assert np.array_equal(got, expected)
    assert not expected[[1, 3, 5, 7, 9, 11]].any()
    assert expected[[0, 2, 4, 6, 8, 10, 12, 13, 14, 15]].all()


def test_tiny_batches_delegate_to_strict(corpus):
    msgs, sigs, keys = corpus
    v = _host_randomized()
    assert v.verify_batch([], [], []).shape == (0,)
    one = v.verify_batch(msgs[:1], sigs[:1], keys[:1])
    assert one.tolist() == [True]
    bad = v.verify_batch(msgs[:1], [_forge(sigs[0])], keys[:1])
    assert bad.tolist() == [False]


def test_device_kernel_parity(corpus):
    """The shared-doubling device kernel (batch_verify_impl) agrees with
    the host big-int backend and with strict, through bisection.  pad_to
    pins every subset launch to one compiled shape."""
    msgs, sigs, keys = [list(x[:16]) for x in corpus]
    sigs[4] = _forge(sigs[4])
    keys[9] = b"\x02" + b"\x00" * 31
    expected = _host_strict().verify_batch(msgs, sigs, keys)
    v = Ed25519RandomizedBatchVerifier(min_device_batch=1, pad_to=16)
    got = v.verify_batch(msgs, sigs, keys)
    assert np.array_equal(np.asarray(got), expected)


def test_same_inputs_same_verdicts(corpus):
    """Determinism rule: no wallclock, no ambient RNG — two fresh verifier
    instances on the same bytes produce identical vectors (and the
    transcript coefficients behind them are pure functions of the batch)."""
    msgs, sigs, keys = [list(x[:32]) for x in corpus]
    sigs[10] = _forge(sigs[10])
    a = _host_randomized().verify_batch(msgs, sigs, keys)
    b = _host_randomized().verify_batch(msgs, sigs, keys)
    assert np.array_equal(a, b)

    z1 = _transcript_coefficients(msgs, sigs, keys)
    z2 = _transcript_coefficients(msgs, sigs, keys)
    assert z1 == z2
    assert all(1 <= z < 2**128 for z in z1)
    # The transcript binds content AND position: permuting the batch
    # changes every coefficient.
    z3 = _transcript_coefficients(msgs[::-1], sigs[::-1], keys[::-1])
    assert z3 != z1


@pytest.mark.slow
def test_batch_1024_parity(corpus):
    # Batch sizes beyond the 512 acceptance point ride the slow lane.
    msgs, sigs, keys = corpus
    m, s, k = msgs + msgs, list(sigs + sigs), keys + keys
    s[700] = _forge(s[700])
    expected = _host_strict().verify_batch(m, s, k)
    got = _host_randomized().verify_batch(m, s, k)
    assert np.array_equal(got, expected)


# --- field-op counting shim + the measured amortization claim ---------------


def test_counting_shim_weighs_lanes_and_scan_trips():
    import jax.numpy as jnp

    from consensus_tpu.ops import field25519 as fe
    from consensus_tpu.ops import limbs

    a = jnp.zeros((32, 4), jnp.float32)  # 4 batch lanes
    assert not limbs.counting()
    count = limbs.measure_field_ops(fe.mul, a, a)
    assert (count.muls, count.squares) == (4, 0)
    count = limbs.measure_field_ops(fe.square, a)
    assert (count.muls, count.squares) == (0, 4)
    assert count.m_equiv == pytest.approx(4 * limbs.SQUARE_M_RATIO)

    def scanned(x):
        def body(c, _):
            return fe.mul(c, x), None

        c, _ = limbs.counted_scan(body, x, None, length=5)
        return c

    # One traced mul body, weighted by 5 trips x 4 lanes.
    count = limbs.measure_field_ops(scanned, a)
    assert (count.muls, count.squares) == (20, 0)
    assert not limbs.counting()


@pytest.mark.slow
def test_amortized_field_muls_at_512_below_half_of_strict():
    """THE acceptance count (PERF.md §5 records the numbers): at
    batch 512 the randomized aggregate path costs <= 50% of the strict
    kernel's field multiplications per signature.  Abstract tracing only
    (jax.eval_shape) — but tracing two batch-512 graphs still takes
    minutes, hence the slow marker; the committed PERF.md table is the
    tier-1-visible artifact of this claim."""
    import jax
    import jax.numpy as jnp

    from consensus_tpu.models import ed25519 as model
    from consensus_tpu.ops import limbs

    b = 512
    strict = limbs.measure_field_ops(
        model.verify_impl,
        jnp.zeros((32, b), jnp.uint8),
        jnp.zeros((b,), jnp.uint8),
        jnp.zeros((32, b), jnp.uint8),
        jnp.zeros((b,), jnp.uint8),
        jnp.zeros((32, b), jnp.uint8),
        jnp.zeros((64, b), jnp.uint8),
        jnp.zeros((b,), jnp.bool_),
    )
    batched = limbs.measure_field_ops(
        model.batch_verify_impl,
        jnp.zeros((32, b), jnp.uint8),
        jnp.zeros((b,), jnp.uint8),
        jnp.zeros((32, b), jnp.uint8),
        jnp.zeros((b,), jnp.uint8),
        jnp.zeros((32, 1), jnp.uint8),
        jnp.zeros((64, b), jnp.uint8),
        jnp.zeros((33, b), jnp.uint8),
        jnp.zeros((b,), jnp.bool_),
    )
    assert batched.muls / strict.muls <= 0.50
    assert batched.m_equiv / strict.m_equiv <= 0.50


# --- the multi-batch coalescing seam (api/deps.py) --------------------------


class _SpyMixin:
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.launches = 0

    def verify_batch(self, msgs, sigs, keys):
        self.launches += 1
        return super().verify_batch(msgs, sigs, keys)


class _SpyRandomized(_SpyMixin, Ed25519RandomizedBatchVerifier):
    pass


class _SpyStrict(_SpyMixin, Ed25519BatchVerifier):
    pass


class _Facade(Verifier):
    """Minimal api.deps facade over an inner signature verifier — the shape
    of CryptoApp: implements only the per-group batch call and wires the
    delegate, leaving multi-batch to the Verifier ABC default."""

    def __init__(self, inner):
        self._inner = inner
        self.multi_batch_delegate = inner
        self.batch_verify_enabled = inner.batch_verify_enabled

    def verify_proposal(self, proposal):
        raise NotImplementedError

    def verify_request(self, raw):
        raise NotImplementedError

    def verify_consenter_sig(self, signature, proposal):
        return self._inner.verify_consenter_sig(signature, proposal)

    def verify_signature(self, signature):
        raise NotImplementedError

    def verification_sequence(self):
        return 0

    def requests_from_proposal(self, proposal):
        return []

    def verify_consenter_sigs_batch(self, signatures, proposal):
        return self._inner.verify_consenter_sigs_batch(signatures, proposal)


def _quorum_groups(n_groups=3):
    from consensus_tpu.models import Ed25519Signer
    from consensus_tpu.testing.crypto_app import SigOnlyVerifier

    signers = {
        i: Ed25519Signer(
            i, hashlib.sha512(b"ctpu/test-mb/%d" % i).digest()[:32]
        )
        for i in (1, 2, 3, 4)
    }
    keys = {i: s.public_bytes for i, s in signers.items()}
    groups = []
    for g in range(n_groups):
        proposal = Proposal(payload=b"blk-%d" % g, metadata=b"md")
        cert = [signers[i].sign_proposal(proposal, b"aux") for i in (1, 2, 3)]
        groups.append((proposal, cert))
    return keys, groups, SigOnlyVerifier


def test_multi_batch_default_coalesces_to_one_launch():
    """With batch_verify_mode's engine behind the verifier, the Verifier
    ABC's multi-batch default forwards the whole group list to the delegate
    — ONE engine launch for 3 quorum certs.  A strict engine keeps the
    bit-exact per-group loop."""
    keys, groups, SigOnlyVerifier = _quorum_groups()

    spy = _SpyRandomized(min_device_batch=10**9)
    facade = _Facade(SigOnlyVerifier(keys, engine=spy))
    out = facade.verify_consenter_sigs_multi_batch(groups)
    assert spy.launches == 1
    assert out == [[b"aux"] * 3] * 3

    strict_spy = _SpyStrict(min_device_batch=10**9)
    strict_facade = _Facade(SigOnlyVerifier(keys, engine=strict_spy))
    assert strict_facade.verify_consenter_sigs_multi_batch(groups) == out
    assert strict_spy.launches == 3


def test_multi_batch_coalesced_rejections_localized():
    keys, groups, SigOnlyVerifier = _quorum_groups()
    # Corrupt one signature inside the middle group.
    bad = groups[1][1][2]
    groups[1][1][2] = type(bad)(id=bad.id, value=_forge(bad.value), msg=bad.msg)
    spy = _SpyRandomized(min_device_batch=10**9)
    facade = _Facade(SigOnlyVerifier(keys, engine=spy))
    out = facade.verify_consenter_sigs_multi_batch(groups)
    assert spy.launches == 1
    assert out[0] == [b"aux"] * 3 and out[2] == [b"aux"] * 3
    assert out[1] == [b"aux", b"aux", None]


def test_engine_for_config_and_mixin_contradiction():
    from consensus_tpu.config import Configuration
    from consensus_tpu.models.verifier import (
        Ed25519VerifierMixin,
        engine_for_config,
    )

    assert Configuration().batch_verify_mode is False
    strict = engine_for_config(Configuration())
    assert type(strict) is Ed25519BatchVerifier
    randomized = engine_for_config(Configuration(batch_verify_mode=True))
    assert isinstance(randomized, Ed25519RandomizedBatchVerifier)

    from consensus_tpu.testing.crypto_app import SigOnlyVerifier

    v = SigOnlyVerifier({}, engine=randomized)
    assert v.batch_verify_enabled
    assert not SigOnlyVerifier({}, engine=strict).batch_verify_enabled
    assert SigOnlyVerifier({}, batch_verify_mode=True).batch_verify_enabled
    with pytest.raises(ValueError, match="randomized"):
        SigOnlyVerifier({}, engine=strict, batch_verify_mode=True)


# --- cluster integration: coalesced launches stay single-launch -------------


def test_cluster_verify_launch_histogram_with_batch_mode():
    """A live cluster running batch_verify_mode: the cross-slot verify
    instrumentation still records exactly one histogram observation per
    launch, decisions commit, and every decided quorum re-verifies
    strictly (randomized accept == strict accept on honest traffic)."""
    from consensus_tpu.metrics import InMemoryProvider, Metrics
    from consensus_tpu.models import Ed25519Signer
    from consensus_tpu.models.verifier import commit_message
    from consensus_tpu.testing import Cluster, make_request
    from consensus_tpu.testing.crypto_app import CryptoApp, SigOnlyVerifier

    provider = InMemoryProvider()
    cluster = Cluster(4, seed=913)
    engine = Ed25519RandomizedBatchVerifier(min_device_batch=10**9)
    signers = {
        i: Ed25519Signer(
            i, hashlib.sha512(b"ctpu/test-cl/%d" % i).digest()[:32]
        )
        for i in cluster.nodes
    }
    keys = {i: s.public_bytes for i, s in signers.items()}
    for node_id, node in cluster.nodes.items():
        node.app = CryptoApp(
            node_id, cluster, signers[node_id],
            SigOnlyVerifier(keys, engine=engine),
        )
    assert cluster.nodes[2].app.batch_verify_enabled
    cluster.nodes[2].metrics = Metrics(provider)
    cluster.start()
    for i in range(3):
        cluster.submit_to_all(make_request("bv", i))
        assert cluster.run_until_ledger(i + 1, max_time=600.0)
    cluster.assert_ledgers_consistent()

    launches = provider.value("consensus_verify_launches")
    batches = provider.observations("consensus_cross_slot_verify_batch")
    assert launches >= 3  # at least one coalesced launch per decision
    assert len(batches) == launches  # exactly one observation per launch
    assert all(b >= 1 for b in batches)

    checker = _host_strict()
    for decision in cluster.nodes[2].app.ledger:
        assert len(decision.signatures) >= 3
        ok = checker.verify_batch(
            [commit_message(decision.proposal, s.msg) for s in decision.signatures],
            [s.value for s in decision.signatures],
            [keys[s.id] for s in decision.signatures],
        )
        assert ok.all()


# --- chaos parity gate (strict vs randomized engine, same schedule) ---------


def test_chaos_byzantine_mutation_parity_strict_vs_batch():
    """One tier-1 byzantine-mutation schedule run twice — strict engine vs
    randomized batch engine — must produce identical ledgers AND identical
    event logs: flipping batch_verify_mode may never change a verdict, so
    the whole deterministic execution replays byte-for-byte."""
    from consensus_tpu.testing.chaos import ChaosAction, ChaosEngine, ChaosSchedule

    schedule = ChaosSchedule(
        seed=4117,
        n=4,
        actions=(
            ChaosAction(at=35.0, kind="byzantine", args={"node": 4, "rate": 0.6}),
            ChaosAction(at=70.0, kind="loss", args={"a": 2, "b": 3, "p": 0.2}),
            ChaosAction(at=95.0, kind="byzantine_stop", args={}),
            ChaosAction(at=110.0, kind="heal", args={}),
        ),
    )
    strict = ChaosEngine(schedule, crypto="ed25519").run()
    assert strict.ok, strict.violation
    batch = ChaosEngine(schedule, crypto="ed25519-batch").run()
    assert batch.ok, batch.violation
    assert strict.ledgers == batch.ledgers
    assert strict.event_log == batch.event_log
    assert max(len(d) for d in strict.ledgers.values()) >= 1


def test_wallclock_lint_covers_batch_verify_modules():
    """scripts/check_no_wallclock.py walks the trees the randomized
    verifier lives in — the determinism rule (transcript-derived z, no
    wallclock) is enforced by lint, not convention."""
    script = os.path.join(_REPO, "scripts", "check_no_wallclock.py")
    proc = subprocess.run(
        [
            sys.executable,
            script,
            os.path.join(_REPO, "consensus_tpu", "models"),
            os.path.join(_REPO, "consensus_tpu", "ops"),
        ],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
