"""TPU crypto engine tests (run on the CPU JAX backend): field arithmetic
against Python big-int, RFC 8032 vectors, batch verification against the
``cryptography`` package, the Verifier-port adapter, and the coalescer.
"""

import hashlib
import random

import numpy as np
import pytest

import jax.numpy as jnp

from consensus_tpu.models import (
    Ed25519BatchVerifier,
    Ed25519Signer,
    Ed25519VerifierMixin,
)
from consensus_tpu.ops import ed25519 as ed
from consensus_tpu.ops import field25519 as fe
from consensus_tpu.types import Proposal, Signature


def limbs_of(values):
    # Device layout: limbs leading, batch trailing.
    return jnp.asarray(np.stack([fe.int_to_limbs(v) for v in values], axis=1))


def ints_of(arr):
    frozen = np.asarray(fe.freeze(arr))
    return [fe.limbs_to_int(frozen[:, i]) for i in range(frozen.shape[1])]


class TestField:
    def test_mul_add_sub_match_bigint(self):
        rng = random.Random(7)
        a_vals = [rng.randrange(fe.P) for _ in range(16)] + [0, 1, fe.P - 1, fe.P - 19]
        b_vals = [rng.randrange(fe.P) for _ in range(16)] + [fe.P - 1, 0, fe.P - 1, 2]
        a, b = limbs_of(a_vals), limbs_of(b_vals)
        assert ints_of(fe.mul(a, b)) == [(x * y) % fe.P for x, y in zip(a_vals, b_vals)]
        assert ints_of(fe.add(a, b)) == [(x + y) % fe.P for x, y in zip(a_vals, b_vals)]
        assert ints_of(fe.sub(a, b)) == [(x - y) % fe.P for x, y in zip(a_vals, b_vals)]

    def test_deep_mul_chain_stays_exact(self):
        # Repeated squaring: any normalization bug compounds and is caught.
        rng = random.Random(9)
        vals = [rng.randrange(fe.P) for _ in range(4)]
        x = limbs_of(vals)
        want = vals
        for _ in range(50):
            x = fe.mul(x, x)
            want = [w * w % fe.P for w in want]
        assert ints_of(x) == want

    def test_mixed_op_chains_with_borrows(self):
        # Long random add/sub/mul chains: exercises the negative-limb
        # (borrow) representations the parallel relaxed carries produce.
        rng = random.Random(11)
        vals = [rng.randrange(fe.P) for _ in range(8)]
        other = [rng.randrange(fe.P) for _ in range(8)]
        x, y = limbs_of(vals), limbs_of(other)
        wx, wy = list(vals), list(other)
        for step in range(60):
            op = step % 3
            if op == 0:
                x = fe.sub(x, y)
                wx = [(a - b) % fe.P for a, b in zip(wx, wy)]
            elif op == 1:
                x = fe.mul(x, y)
                wx = [(a * b) % fe.P for a, b in zip(wx, wy)]
            else:
                y = fe.sub(y, x)
                wy = [(b - a) % fe.P for a, b in zip(wx, wy)]
        assert ints_of(x) == wx and ints_of(y) == wy

    def test_freeze_handles_borrowed_negatives(self):
        # sub(0, small) yields a weakly-reduced value with negative limbs;
        # freeze must still canonicalize it.
        zero = limbs_of([0, 0, 0])
        small = limbs_of([1, 19, fe.P - 1])
        d = fe.sub(zero, small)
        assert ints_of(d) == [(fe.P - 1), (fe.P - 19), 1]


    def test_raw_ops_stay_exact_at_bound(self):
        # One raw add/sub level feeding mul must stay bit-exact: drive the
        # worst-case limb magnitudes the curve formulas produce.
        rng = random.Random(21)
        vals = [rng.randrange(fe.P) for _ in range(8)]
        others = [rng.randrange(fe.P) for _ in range(8)]
        x, y = limbs_of(vals), limbs_of(others)
        for _ in range(10):
            s = fe.add_raw(x, y)        # <= 680 per limb
            d = fe.sub_raw(x, y)        # in [-345, 600]
            prod = fe.mul(s, d)         # raw x raw multiply
            want = [((a + b) * (a - b)) % fe.P for a, b in zip(vals, others)]
            assert ints_of(prod) == want
            x, vals = prod, want
            y = fe.mul(y, y)
            others = [b * b % fe.P for b in others]

    def test_square_matches_mul(self):
        rng = random.Random(23)
        vals = [rng.randrange(fe.P) for _ in range(8)] + [0, 1, fe.P - 1]
        x = limbs_of(vals)
        assert ints_of(fe.square(x)) == ints_of(fe.mul(x, x)) == [
            v * v % fe.P for v in vals
        ]


    def test_exactness_at_synthetic_limb_extremes(self):
        # Drive mul/square at the DOCUMENTED limb bounds directly (random
        # canonical inputs never reach them): raw-level operands at +-680 /
        # -345..600 per limb, squaring at its 500 bound.
        def arr(limb_values):
            a = np.tile(np.array(limb_values, dtype=np.float32)[:, None], (1, 2))
            return jnp.asarray(a)

        def as_int(a):
            col = np.asarray(a, dtype=np.int64)[:, 0]
            return sum(int(col[i]) << (8 * i) for i in range(32))

        hi = arr([680] * 32)                      # max add_raw output
        lo = arr([-345, 600] * 16)                # extreme sub_raw output
        want = (as_int(hi) * as_int(lo)) % fe.P
        assert ints_of(fe.mul(hi, lo))[0] == want

        sq_in = arr([500, -500] * 16)             # square() bound
        want_sq = (as_int(sq_in) ** 2) % fe.P
        assert ints_of(fe.square(sq_in))[0] == want_sq

        # Reduction domain: _weak_reduce must handle the worst fold output.
        big = arr([2**21] * 32)
        assert ints_of(fe.add(big, big * 0))[0] == as_int(big) % fe.P

    def test_invert(self):
        vals = [3, 12345, fe.P - 2, 2**200 + 7]
        inv = fe.invert(limbs_of(vals))
        assert ints_of(inv) == [pow(v, fe.P - 2, fe.P) for v in vals]

    def test_freeze_canonicalizes(self):
        # p and 2p-1 etc. must freeze to their canonical residues.
        raw = [fe.P, fe.P + 5, 2 * fe.P - 1, 0, 1]
        arr = jnp.asarray(np.stack([fe.int_to_limbs(v) for v in raw], axis=1))
        assert ints_of(arr) == [v % fe.P for v in raw]


class TestPoints:
    def test_base_point_on_curve_and_order(self):
        # 2B computed by add(B, B) and double(B) must agree.
        b = ed.base_point(())
        d1 = ed.double(b)
        d2 = ed.add(b, b)
        assert bool(ed.equal(d1, d2))

    def test_identity_is_neutral(self):
        b = ed.base_point(())
        assert bool(ed.equal(ed.add(b, ed.identity(())), b))

    def test_negation_cancels(self):
        b = ed.base_point(())
        assert bool(ed.equal(ed.add(b, ed.negate(b)), ed.identity(())))

    def test_decompress_base_point(self):
        # Compressed base point: y with sign bit of x (x_B is even -> 0).
        y = ed._BY
        point, valid = ed.decompress(limbs_of([y]), jnp.asarray([0]))
        assert bool(valid[0])
        assert ints_of(point.x)[0] == ed._BX

    def test_decompress_rejects_non_square(self):
        # y = 2 gives u/v that is not a QR for edwards25519.
        point, valid = ed.decompress(limbs_of([2]), jnp.asarray([0]))
        assert not bool(valid[0])


def make_sigs(n, msg_prefix=b"m"):
    pytest.importorskip("cryptography", reason="reference signer unavailable")
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    msgs, sigs, keys = [], [], []
    for i in range(n):
        sk = Ed25519PrivateKey.generate()
        pk = sk.public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw
        )
        m = msg_prefix + b"-%d" % i
        msgs.append(m)
        sigs.append(sk.sign(m))
        keys.append(pk)
    return msgs, sigs, keys


class TestBatchVerifier:
    def test_rfc8032_vectors(self):
        # RFC 8032 §7.1 test vectors 1-3.
        vectors = [
            (  # TEST 1: empty message
                "9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
                "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a",
                "",
                "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
                "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b",
            ),
            (  # TEST 2: one byte
                "4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
                "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c",
                "72",
                "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da"
                "085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00",
            ),
            (  # TEST 3: two bytes
                "c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7",
                "fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025",
                "af82",
                "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac"
                "18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a",
            ),
        ]
        msgs = [bytes.fromhex(m) for _, _, m, _ in vectors]
        keys = [bytes.fromhex(pk) for _, pk, _, _ in vectors]
        sigs = [bytes.fromhex(s) for _, _, _, s in vectors]
        ok = Ed25519BatchVerifier().verify_batch(msgs, sigs, keys)
        assert ok.all()

    def test_valid_batch_and_each_corruption_mode(self):
        msgs, sigs, keys = make_sigs(8)
        v = Ed25519BatchVerifier()
        assert v.verify_batch(msgs, sigs, keys).all()

        bad = list(sigs)
        bad[0] = bytes([sigs[0][0] ^ 1]) + sigs[0][1:]      # flipped R byte
        bad[1] = sigs[1][:32] + bytes(32)                   # S = 0
        bad[2] = b"short"                                   # malformed
        bad[3] = sigs[3][:63] + bytes([sigs[3][63] ^ 0x40])  # flipped S bit
        ok = v.verify_batch(msgs, bad, keys)
        assert not ok[:4].any() and ok[4:].all()

        wrong_msg = [b"x" + m for m in msgs]
        assert not v.verify_batch(wrong_msg, sigs, keys).any()

        swapped = keys[1:] + keys[:1]
        assert not v.verify_batch(msgs, sigs, swapped).any()

    def test_the_four_verify_phases_fire_once_per_device_launch_only(self, monkeypatch):
        """The device path books ``verify.prepare`` / ``.layout`` /
        ``.dispatch`` / ``.await`` in the flusher ledger, once each and in
        that order per ``verify_batch``; the host path books none.  Same
        8-lane shape as the test above: no compile of its own."""
        from consensus_tpu.models import ed25519 as model
        from consensus_tpu.obs.kernels import FLUSHER, phase

        opened = []

        class spy(phase):
            def __init__(self, name, **kw):
                opened.append((name, kw))
                super().__init__(name, **kw)

        monkeypatch.setattr(model, "phase", spy)
        names = ("verify.prepare", "verify.layout", "verify.dispatch", "verify.await")
        msgs, sigs, keys = make_sigs(8)
        before = FLUSHER.snapshot()
        assert Ed25519BatchVerifier().verify_batch(msgs, sigs, keys).all()
        assert opened == [(names[0], {"cpu": True})] + [(n, {}) for n in names[1:]]
        after = FLUSHER.snapshot()
        moved = {k for k in after if after[k] != before[k]}
        assert moved == set(names) | {"verify.prepare_cpu"}
        # prepare is Python and numpy on this thread: it held a core
        assert 0 < after["verify.prepare_cpu"] - before["verify.prepare_cpu"]
        del opened[:]
        host = Ed25519BatchVerifier(min_device_batch=100)
        assert host.verify_batch(msgs, sigs, keys).all()
        assert Ed25519BatchVerifier().verify_host(msgs, sigs, keys).all()
        assert opened == [] and FLUSHER.snapshot() == after

    def test_high_s_rejected(self):
        # S >= L must be rejected even if the curve equation would hold.
        from consensus_tpu.models.ed25519 import L

        msgs, sigs, keys = make_sigs(1)
        s = int.from_bytes(sigs[0][32:], "little")
        high_s = s + L
        forged = sigs[0][:32] + high_s.to_bytes(32, "little")
        ok = Ed25519BatchVerifier().verify_batch(msgs, [forged], keys)
        assert not ok[0]

    def test_pow2_padding_returns_exact_length(self):
        msgs, sigs, keys = make_sigs(5)
        ok = Ed25519BatchVerifier().verify_batch(msgs, sigs, keys)
        assert ok.shape == (5,) and ok.all()

    def test_host_fallback_matches_device(self):
        msgs, sigs, keys = make_sigs(4)
        bad = list(sigs)
        bad[2] = bytes(64)
        device = Ed25519BatchVerifier(min_device_batch=1).verify_batch(msgs, bad, keys)
        host = Ed25519BatchVerifier(min_device_batch=100).verify_batch(msgs, bad, keys)
        assert (device == host).all()

    def test_host_and_device_agree_on_edge_case_vectors(self):
        """Known adversarial classes where Ed25519 verifiers diverge
        (non-canonical encodings, S >= L, small-order components): in BFT a
        vote's validity must not depend on which path checked it, so the
        host fallback applies the device kernel's strict pre-checks
        (ADVICE r2: models/ed25519.py:246)."""
        from consensus_tpu.models.ed25519 import L
        from consensus_tpu.ops.field25519 import P

        msgs, sigs, keys = make_sigs(8)
        # 0: non-canonical R (y >= p): p + 1 little-endian, sign bit clear.
        sigs[0] = (P + 1).to_bytes(32, "little") + sigs[0][32:]
        # 1: non-canonical A (y >= p).
        keys[1] = (P + 2).to_bytes(32, "little")
        # 2: S = L exactly (malleability boundary).
        sigs[2] = sigs[2][:32] + L.to_bytes(32, "little")
        # 3: S = L - 1 but otherwise-wrong signature (range-valid, invalid).
        sigs[3] = sigs[3][:32] + (L - 1).to_bytes(32, "little")
        # 4: small-order A: identity point (y=1, x=0).
        keys[4] = (1).to_bytes(32, "little")
        # 5: small-order R: identity encoding as R.
        sigs[5] = (1).to_bytes(32, "little") + sigs[5][32:]
        # 6: A with y = p - 1 but sign bit set (may be a non-square x^2).
        keys[6] = bytes(31) + b"\x80"  # y=0, sign=1
        # 7: left valid as a control.
        device = Ed25519BatchVerifier(min_device_batch=1).verify_batch(msgs, sigs, keys)
        host = Ed25519BatchVerifier(min_device_batch=100).verify_batch(msgs, sigs, keys)
        assert (device == host).all(), (device, host)
        assert device[7] and not device[:3].any()


class _Ed25519OnlyVerifier(Ed25519VerifierMixin):
    """Concrete mixin instance for the signature-path tests."""

    def verify_proposal(self, proposal):
        return []

    def verify_request(self, raw_request):
        raise NotImplementedError

    def verification_sequence(self):
        return 0

    def requests_from_proposal(self, proposal):
        return []


class TestVerifierPort:
    def test_sign_proposal_then_batch_verify_quorum(self):
        signers = {i: Ed25519Signer(i) for i in (1, 2, 3, 4)}
        verifier = _Ed25519OnlyVerifier(
            {i: s.public_bytes for i, s in signers.items()}
        )
        proposal = Proposal(payload=b"batch", metadata=b"md")
        sigs = [signers[i].sign_proposal(proposal, b"aux-%d" % i) for i in (2, 3, 4)]
        results = verifier.verify_consenter_sigs_batch(sigs, proposal)
        assert results == [b"aux-2", b"aux-3", b"aux-4"]

        # Tampered aux breaks the binding (the signature covers it).
        tampered = Signature(id=2, value=sigs[0].value, msg=b"aux-x")
        assert verifier.verify_consenter_sigs_batch([tampered], proposal) == [None]
        # Signature over one proposal does not verify another.
        other = Proposal(payload=b"other")
        assert verifier.verify_consenter_sigs_batch(sigs, other) == [None] * 3

    def test_unknown_signer_rejected(self):
        signer = Ed25519Signer(9)
        verifier = _Ed25519OnlyVerifier({1: Ed25519Signer(1).public_bytes})
        proposal = Proposal(payload=b"p")
        sig = signer.sign_proposal(proposal)
        assert verifier.verify_consenter_sigs_batch([sig], proposal) == [None]

    def test_verify_signature_raw_path(self):
        signer = Ed25519Signer(3)
        verifier = _Ed25519OnlyVerifier({3: signer.public_bytes})
        data = b"view-data-bytes"
        sig = Signature(id=3, value=signer.sign(data), msg=data)
        verifier.verify_signature(sig)  # must not raise
        with pytest.raises(ValueError):
            verifier.verify_signature(Signature(id=3, value=bytes(64), msg=data))


class TestPowChain:
    def test_addition_chain_matches_binary_ladder_and_bigint(self):
        """pow_2_252_m3 (11-mul chain) == pow_const == python pow, incl.
        edge cases 0, 1, p-1, sqrt(-1)."""
        import jax
        import numpy as np

        from consensus_tpu.ops import field25519 as fe

        rng = np.random.default_rng(7)
        vals = [int.from_bytes(rng.bytes(32), "little") % fe.P for _ in range(4)]
        vals += [0, 1, fe.P - 1, fe.SQRT_M1]
        arr = np.stack([fe.int_to_limbs(v) for v in vals]).T.astype(np.float32)
        x = jax.numpy.asarray(arr)
        got = np.asarray(fe.freeze(jax.jit(fe.pow_2_252_m3)(x)))
        for i, v in enumerate(vals):
            assert fe.limbs_to_int(got[:, i]) == pow(v, (fe.P - 5) // 8, fe.P)


class TestThreadCoalescer:
    """Cross-thread coalescer (shared-device deployments): merges concurrent
    verify_batch calls from replica threads into single engine launches."""

    class _Fake:
        def __init__(self):
            self.calls = []

        def verify_batch(self, msgs, sigs, keys):
            import numpy as np

            self.calls.append(len(msgs))
            # valid iff sig == b"good"
            return np.array([s == b"good" for s in sigs], dtype=bool)

    def _make(self, **kw):
        from consensus_tpu.models import ThreadCoalescingVerifier

        fake = self._Fake()
        return fake, ThreadCoalescingVerifier(fake, **kw)

    def test_concurrent_callers_merge_and_get_their_slices(self):
        import threading

        fake, v = self._make(window=0.05, max_batch=30)
        results = {}

        def worker(i, sigs):
            results[i] = list(
                v.verify_batch([b"m"] * len(sigs), sigs, [b"k"] * len(sigs))
            )

        patterns = {
            0: [b"good"] * 10,
            1: [b"bad"] * 10,
            2: [b"good", b"bad"] * 5,
        }
        threads = [
            threading.Thread(target=worker, args=(i, p))
            for i, p in patterns.items()
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=5.0)
        # One merged launch (max_batch reached), per-caller slices correct.
        assert fake.calls == [30]
        assert results[0] == [True] * 10
        assert results[1] == [False] * 10
        assert results[2] == [True, False] * 5
        v.close()

    def test_hard_cap_splits_whole_submissions(self):
        import threading

        fake, v = self._make(window=0.01, max_batch=10, hard_cap=15)
        done = []

        def worker():
            done.append(v.verify_batch([b"m"] * 10, [b"good"] * 10, [b"k"] * 10).all())

        threads = [threading.Thread(target=worker) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=5.0)
        # 10 + 10 > hard_cap 15: two launches, submissions never split.
        assert fake.calls == [10, 10]
        assert done == [True, True]
        v.close()

    def test_engine_error_propagates_to_every_waiter(self):
        import threading

        from consensus_tpu.models import ThreadCoalescingVerifier

        class _Boom:
            def verify_batch(self, m, s, k):
                raise RuntimeError("device fell over")

        v = ThreadCoalescingVerifier(_Boom(), window=0.01, max_batch=4)
        errors = []

        def worker():
            try:
                v.verify_batch([b"m"], [b"s"], [b"k"])
            except RuntimeError as e:
                errors.append(f"{e} / cause: {e.__cause__}")

        threads = [threading.Thread(target=worker) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=5.0)
        # Each waiter gets its OWN wrapper exception (a shared instance
        # raised from N threads would interleave tracebacks), chaining the
        # original engine failure as __cause__.
        assert len(errors) == 2
        assert all("device fell over" in e for e in errors)
        v.close()

    def test_oversized_submission_is_chunked_not_overlaunched(self):
        fake, v = self._make(window=0.005, max_batch=8, hard_cap=8)
        out = v.verify_batch([b"m"] * 20, [b"good"] * 19 + [b"bad"], [b"k"] * 20)
        assert len(out) == 20
        assert out[:19].all() and not out[19]
        assert max(fake.calls) <= 8  # never beyond the compiled shape
        v.close()

    def test_short_engine_result_errors_instead_of_validating(self):
        import numpy as np
        import pytest

        from consensus_tpu.models import ThreadCoalescingVerifier

        class _Short:
            def verify_batch(self, m, s, k):
                return np.ones(len(m) - 1, dtype=bool)

        v = ThreadCoalescingVerifier(_Short(), window=0.005, max_batch=4)
        with pytest.raises(RuntimeError) as exc_info:
            v.verify_batch([b"m"] * 2, [b"s"] * 2, [b"k"] * 2)
        assert isinstance(exc_info.value.__cause__, ValueError)
        v.close()

    def test_closed_coalescer_rejects_submissions(self):
        import pytest

        fake, v = self._make(window=0.01)
        v.close()
        with pytest.raises(RuntimeError):
            v.verify_batch([b"m"], [b"s"], [b"k"])


class TestWedgedDeviceEscapeHatch:
    """A hung device call must not block the replica loop:
    waiters fall back to the engine's host path within ``wait_timeout`` and
    subsequent submissions skip the device queue entirely (VERDICT r3 #3)."""

    class _Hung:
        """Engine whose device path never returns (hung device call) but whose
        host path works."""

        def __init__(self):
            import threading

            self.never = threading.Event()
            self.host_calls = 0

        def verify_batch(self, msgs, sigs, keys):
            self.never.wait()  # wedged forever

        def verify_host(self, msgs, sigs, keys):
            import numpy as np

            self.host_calls += 1
            return np.array([s == b"good" for s in sigs], dtype=bool)

    def test_hung_engine_falls_back_to_host_and_marks_suspect(self):
        import time

        from consensus_tpu.models import ThreadCoalescingVerifier

        fake = self._Hung()
        v = ThreadCoalescingVerifier(fake, window=0.005, wait_timeout=0.15)
        start = time.monotonic()
        out = v.verify_batch([b"m"] * 3, [b"good", b"bad", b"good"], [b"k"] * 3)
        first = time.monotonic() - start
        assert list(out) == [True, False, True]
        assert first < 5.0  # escaped the hang, did not wait forever
        assert v.device_suspect
        # Second call: straight to host, no wait_timeout stall.
        start = time.monotonic()
        out2 = v.verify_batch([b"m"], [b"good"], [b"k"])
        assert time.monotonic() - start < 0.1
        assert out2[0]
        assert fake.host_calls >= 2
        v.close()

    def test_warm_rides_the_flusher_with_its_own_deadline(self):
        """``warm`` — the cold first compile before traffic — runs on the
        flusher thread (every compile of the process on ONE thread), is not
        bound by the steady-state ``wait_timeout``, and on its own timeout
        raises instead of quietly serving from the host."""
        import threading
        import time

        import numpy as np

        from consensus_tpu.models import ThreadCoalescingVerifier

        class _SlowFirst:
            def __init__(self):
                self.threads = []

            def verify_batch(self, msgs, sigs, keys):
                self.threads.append(threading.current_thread().name)
                if len(self.threads) == 1:
                    time.sleep(0.4)  # the "compile": longer than wait_timeout
                return np.array([s == b"good" for s in sigs], dtype=bool)

            def verify_host(self, msgs, sigs, keys):
                raise AssertionError("warm-up must never be served by the host")

        fake = _SlowFirst()
        v = ThreadCoalescingVerifier(
            fake, window=0.005, wait_timeout=0.1, name="one-flusher"
        )
        out = v.warm([b"m"] * 2, [b"good", b"bad"], [b"k"] * 2, timeout=5.0)
        assert list(out) == [True, False] and not v.device_suspect
        assert list(v.verify_batch([b"m"], [b"good"], [b"k"])) == [True]
        assert set(fake.threads) == {"one-flusher"}
        v.close()

        hung = self._Hung()
        w = ThreadCoalescingVerifier(hung, window=0.005, wait_timeout=60.0)
        with pytest.raises(TimeoutError, match="warm-up flush"):
            w.warm([b"m"], [b"good"], [b"k"], timeout=0.15)
        assert hung.host_calls == 0

    def test_fast_device_error_is_served_by_host_fallback(self):
        from consensus_tpu.models import ThreadCoalescingVerifier

        class _Flaky(self._Hung):
            def verify_batch(self, msgs, sigs, keys):
                raise RuntimeError("device fell over")

        fake = _Flaky()
        v = ThreadCoalescingVerifier(fake, window=0.005, wait_timeout=5.0)
        # No exception: the flusher serves the flush from the host path.
        out = v.verify_batch([b"m"] * 2, [b"good", b"bad"], [b"k"] * 2)
        assert list(out) == [True, False]
        assert v.device_suspect
        v.close()

    def test_probe_recovers_device_after_transient_failure(self):
        import time

        import numpy as np

        from consensus_tpu.models import ThreadCoalescingVerifier

        class _Transient:
            def __init__(self):
                self.fail = True
                self.device_calls = 0

            def verify_batch(self, msgs, sigs, keys):
                self.device_calls += 1
                if self.fail:
                    raise RuntimeError("transient device error")
                return np.array([s == b"good" for s in sigs], dtype=bool)

            def verify_host(self, msgs, sigs, keys):
                return np.array([s == b"good" for s in sigs], dtype=bool)

        fake = _Transient()
        v = ThreadCoalescingVerifier(fake, window=0.005, wait_timeout=5.0)
        v._probe_interval = 0.0  # probe on every suspect-mode call
        assert list(v.verify_batch([b"m"], [b"good"], [b"k"])) == [True]
        assert v.device_suspect
        fake.fail = False
        # Suspect-mode call host-verifies AND enqueues a no-wait probe; the
        # flusher's successful probe flush clears the flag.
        assert list(v.verify_batch([b"m"], [b"good"], [b"k"])) == [True]
        deadline = time.monotonic() + 5.0
        while v.device_suspect and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not v.device_suspect, "successful probe flush should clear suspect"
        before = fake.device_calls
        assert list(v.verify_batch([b"m"], [b"good"], [b"k"])) == [True]
        assert fake.device_calls > before  # back on the device path
        v.close()

    def test_flush_error_reaching_a_waiter_is_retried_on_host_not_raised(self):
        """Regression: when the device flush fails AND the flusher's own
        host attempt hits a transient, the error lands on the waiter —
        which used to raise it out of ``verify_batch``.  With a host twin
        available that is a degrade, not a decision-killer: the waiter
        retries host-side on its own thread and the call completes."""
        import numpy as np

        from consensus_tpu.models import ThreadCoalescingVerifier

        class _DoubleFault:
            def __init__(self):
                self.host_calls = 0

            def verify_batch(self, msgs, sigs, keys):
                raise RuntimeError("device fell over")

            def verify_host(self, msgs, sigs, keys):
                self.host_calls += 1
                if self.host_calls == 1:
                    raise RuntimeError("host transient")
                return np.array([s == b"good" for s in sigs], dtype=bool)

        fake = _DoubleFault()
        v = ThreadCoalescingVerifier(fake, window=0.005, wait_timeout=5.0)
        out = v.verify_batch([b"m"] * 2, [b"good", b"bad"], [b"k"] * 2)
        assert list(out) == [True, False]
        assert fake.host_calls == 2  # flusher's failed try, waiter's retry
        assert v.device_suspect
        v.close()

    def test_coalescers_share_suspect_state_per_engine(self):
        """Two coalescers over the SAME engine share one EngineHealth entry
        (the process-wide registry): a wedge seen by one routes the other
        host-side immediately, without its own wait_timeout stall."""
        from consensus_tpu.models import ThreadCoalescingVerifier

        fake = self._Hung()
        a = ThreadCoalescingVerifier(fake, window=0.005, wait_timeout=0.15)
        b = ThreadCoalescingVerifier(fake, window=0.005, wait_timeout=60.0)
        assert a.health is b.health
        out = a.verify_batch([b"m"], [b"good"], [b"k"])  # wedges, abandons
        assert list(out) == [True]
        assert a.device_suspect and b.device_suspect
        # b answers from host instantly — no 60s flush wait.
        import time

        start = time.monotonic()
        assert list(b.verify_batch([b"m"], [b"bad"], [b"k"])) == [False]
        assert time.monotonic() - start < 5.0
        fake.never.set()  # unwedge so close() doesn't ride out wait_timeout
        a.close()
        b.close()

    def test_probe_pacing_uses_injected_scheduler_clock(self):
        """Suspect re-probes ride the protocol clock when the embedder
        hands one over; only the schedulerless sidecar path reads the
        audited wall clock."""
        from consensus_tpu.models import ThreadCoalescingVerifier
        from consensus_tpu.runtime.scheduler import SimScheduler

        sched = SimScheduler()
        fake = self._Hung()
        v = ThreadCoalescingVerifier(
            fake, window=0.005, wait_timeout=0.15, scheduler=sched
        )
        assert v._probe_clock == sched.now
        v.close()


class TestSharding:
    def test_sharded_matches_single_device(self):
        import jax

        from consensus_tpu.parallel import ShardedEd25519Verifier, make_mesh

        msgs, sigs, keys = make_sigs(12)
        bad = list(sigs)
        bad[5] = bytes(64)
        mesh = make_mesh()
        assert mesh.devices.size == 8  # conftest forces the virtual mesh
        sharded = ShardedEd25519Verifier(mesh).verify_batch(msgs, bad, keys)
        single = Ed25519BatchVerifier().verify_batch(msgs, bad, keys)
        assert (sharded == single).all()
        assert sharded.sum() == 11 and not sharded[5]

    def test_graft_entry_contract(self):
        import importlib
        import sys

        sys.path.insert(0, "/root/repo")
        g = importlib.import_module("__graft_entry__")
        import jax

        fn, args = g.entry()
        out = jax.jit(fn)(*args)
        assert out.shape == (8,) and bool(out[0]) and not bool(out[1])
        g.dryrun_multichip(8)
