"""Parity gate for the MXU field-arithmetic lane (``CTPU_MXU_LIMBS=1``).

The lane (ISSUE 18) re-expresses limb-product field multiplication as two
integer ``dot_general`` contractions (ops/mxu_limbs.py).  The rewrite is
not allowed to move a single bit:

* ``mul``/``square`` outputs are bit-exact against the VPU lane across the
  full relaxed-limb operand ranges the curve kernels actually feed them;
* engine verdicts — strict, randomized-batch, half-aggregated — are
  byte-identical flag-on vs flag-off across every rejection class, on a
  single device AND on the 8-way virtual host mesh (conftest forces
  ``xla_force_host_platform_device_count=8``);
* the counting shim records ``dot_general`` work (dense MACs — the MXU
  does not skip structural zeros) instead of VPU muls, never both, so the
  counted denominators in PERF.md stay honest.

(The VMEM-resident Straus/MSM Pallas kernel that used to ride this flag was
removed in PR 22: Mosaic refused its ``(32, 1)`` output block on the chip.
Flag-on, the randomized MSM is the XLA scan with MXU field contractions.)

Lane selection happens at TRACE time, so every A/B below jits (or traces)
fresh under an explicit ``force_mxu_limbs``/``suppress_mxu_limbs`` context
— reusing one jit cache across lanes would silently replay the first
lane's graph and turn the gate into a tautology.

The chip verdict for the lane is ``chip_smoke.py``'s census (strict and
randomized under the flag).  Every engine-level A/B here (single-device
strict/randomized, both mesh variants) compiles its full verify graph twice
— fresh trace per lane, no kernel memo — which does not fit the tier-1
wall-clock budget; those gates ride the slow lane with the batch-512 pins.
Tier-1 keeps the operand-range field parity, the jitted mul chain, the
anti-tautology distinct-graph pin, lane-selection precedence, and the
counting semantics.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from consensus_tpu.models import aggregate as agg
from consensus_tpu.models import ed25519 as model
from consensus_tpu.models.verifier import Ed25519Signer
from consensus_tpu.ops import ed25519 as ed
from consensus_tpu.ops import field25519 as fe
from consensus_tpu.ops import field_p256 as fp
from consensus_tpu.ops import limbs, mxu_limbs

_LANES = (
    ("vpu", mxu_limbs.suppress_mxu_limbs),
    ("mxu", mxu_limbs.force_mxu_limbs),
)


def _fresh_jit(fn):
    """``jax.jit`` keyed on a NEW function object.

    jax's trace cache is keyed on (function identity, avals) — jitting the
    bare module-level function under the second lane would replay the first
    lane's jaxpr and turn the A/B into a tautology.  A fresh lambda per
    lane forces a fresh trace, so the lane flag is actually consulted.
    (test_lane_ab_traces_distinct_graphs pins that this works.)
    """
    return jax.jit(lambda *a: fn(*a))


# --- operand-range bit-exactness --------------------------------------------

def _rand_limbs(rng, batch, lo, hi):
    return jnp.asarray(
        rng.integers(lo, hi, size=(32, batch)).astype(np.float32)
    )


def _ab_lanes(fn, *args):
    """Run ``fn(*args)`` eagerly under each lane; return {lane: ndarray}."""
    out = {}
    for lane, ctx in _LANES:
        with ctx():
            out[lane] = np.asarray(fn(*args))
    return out


#: Relaxed-limb operand ranges the 25519 kernel actually feeds mul/square:
#: canonical bytes, post-(add/sub) mixed-sign limbs, and the symmetric
#: range the subtraction bias produces.  (-345, 681) is the widest range
#: _schoolbook_columns' int16 products must survive.
_ED_RANGES = [(0, 256), (-345, 681), (-340, 341)]
_P256_RANGES = [(0, 256), (-600, 601)]


@pytest.mark.parametrize("lo,hi", _ED_RANGES)
def test_mul25519_bit_exact_across_operand_ranges(lo, hi):
    rng = np.random.default_rng(1000 + hi - lo)
    a = _rand_limbs(rng, 16, lo, hi)
    b = _rand_limbs(rng, 16, lo, hi)
    got = _ab_lanes(fe.mul, a, b)
    assert got["mxu"].dtype == got["vpu"].dtype == np.float32
    assert np.array_equal(got["vpu"], got["mxu"]), (
        f"fe.mul diverged on range ({lo}, {hi})"
    )


@pytest.mark.parametrize("lo,hi", _ED_RANGES)
def test_square25519_bit_exact_across_operand_ranges(lo, hi):
    rng = np.random.default_rng(2000 + hi - lo)
    a = _rand_limbs(rng, 16, lo, hi)
    got = _ab_lanes(fe.square, a)
    assert np.array_equal(got["vpu"], got["mxu"]), (
        f"fe.square diverged on range ({lo}, {hi})"
    )


@pytest.mark.parametrize("lo,hi", _P256_RANGES)
def test_p256_mul_square_bit_exact_across_operand_ranges(lo, hi):
    rng = np.random.default_rng(3000 + hi - lo)
    a = _rand_limbs(rng, 16, lo, hi)
    b = _rand_limbs(rng, 16, lo, hi)
    got = _ab_lanes(fp.mul, a, b)
    assert np.array_equal(got["vpu"], got["mxu"]), (
        f"fp.mul diverged on range ({lo}, {hi})"
    )
    got = _ab_lanes(fp.square, a)
    assert np.array_equal(got["vpu"], got["mxu"]), (
        f"fp.square diverged on range ({lo}, {hi})"
    )


def test_jitted_mul_chain_bit_exact():
    """The bench's A/B shape: a scan of dependent muls, traced FRESH per
    lane — pins that the contraction survives jit + scan composition, not
    just eager single calls."""
    rng = np.random.default_rng(7)
    a = _rand_limbs(rng, 8, 0, 256)
    b = _rand_limbs(rng, 8, 0, 256)

    out = {}
    for lane, ctx in _LANES:
        # The chain is DEFINED inside the lane loop: a shared def would be
        # one function object, and jit's trace cache would replay the first
        # lane's graph for the second (see _fresh_jit).
        def chain(x, y):
            def body(c, _):
                return fe.mul(c, y), None

            c, _ = jax.lax.scan(body, x, None, length=8)
            return c

        with ctx():
            out[lane] = np.asarray(jax.jit(chain)(a, b))
    assert np.array_equal(out["vpu"], out["mxu"])


def test_lane_ab_traces_distinct_graphs():
    """Anti-tautology pin: a fresh-per-lane jit must lower DIFFERENT graphs
    (the MXU lane's dot_general contraction has a very different flop
    profile), while producing bit-identical values.  If the lane flag ever
    stops reaching jitted traces — e.g. a trace-cache key collision — the
    flop counts collapse to equal and this fails before any parity test
    can silently pass by replaying one lane's graph twice."""
    rng = np.random.default_rng(11)
    a = _rand_limbs(rng, 4, 0, 256)
    b = _rand_limbs(rng, 4, 0, 256)
    flops, vals = {}, {}
    for lane, ctx in _LANES:
        with ctx():
            compiled = _fresh_jit(fe.mul).lower(a, b).compile()
            ca = compiled.cost_analysis()
            flops[lane] = (ca[0] if isinstance(ca, list) else ca)["flops"]
            vals[lane] = np.asarray(compiled(a, b))
    assert flops["mxu"] != flops["vpu"], (
        "both lanes lowered the same graph — the A/B is a tautology"
    )
    assert np.array_equal(vals["vpu"], vals["mxu"])


# --- lane selection ----------------------------------------------------------

def test_lane_selection_precedence(monkeypatch):
    monkeypatch.delenv("CTPU_MXU_LIMBS", raising=False)
    assert not mxu_limbs.lane_active()
    monkeypatch.setenv("CTPU_MXU_LIMBS", "1")
    assert mxu_limbs.lane_active()
    # Suppression wins over both the env flag and an explicit force (the
    # bench A/B's control arm relies on it).
    with mxu_limbs.suppress_mxu_limbs():
        assert not mxu_limbs.lane_active()
        with mxu_limbs.force_mxu_limbs():
            assert not mxu_limbs.lane_active()
    monkeypatch.delenv("CTPU_MXU_LIMBS")
    with mxu_limbs.force_mxu_limbs():
        assert mxu_limbs.lane_active()
    assert not mxu_limbs.lane_active()


# --- end-to-end verdict parity ----------------------------------------------

def _flip(raw, i):
    raw = bytearray(raw)
    raw[i] ^= 0x40
    return bytes(raw)


def _signers(n=4):
    return [Ed25519Signer(i, bytes([i + 1] * 32)) for i in range(n)]


def _corpus(n=8):
    """Valid signatures plus one of each rejection class the engines
    distinguish: forged, tampered, wrong-key, non-canonical S (= L), and
    an undecodable public key."""
    signers = _signers()
    msgs, sigs, keys = [], [], []
    for i in range(n):
        s = signers[i % len(signers)]
        m = b"mxu-parity-%d" % i
        msgs.append(m)
        sigs.append(s.sign_raw(m))
        keys.append(s.public_bytes)
    sigs[1] = bytes(64)                                    # forged
    sigs[2] = _flip(sigs[2], 3)                            # tampered R
    keys[3] = signers[0].public_bytes                      # wrong key
    sigs[4] = sigs[4][:32] + model.L.to_bytes(32, "little")  # S = L
    keys[5] = b"\xff" * 32                                 # non-canonical A
    return msgs, sigs, keys


_EXPECTED = [True, False, False, False, False, False, True, True]


@pytest.mark.slow
def test_strict_verdict_parity_single_device(monkeypatch):
    msgs, sigs, keys = _corpus()
    out = {}
    for lane, ctx in _LANES:
        with ctx():
            monkeypatch.setattr(
                model, "_verify_kernel", _fresh_jit(model.packed_verify_impl)
            )
            v = model.Ed25519BatchVerifier(min_device_batch=1)
            out[lane] = np.asarray(v.verify_batch(msgs, sigs, keys))
    assert out["vpu"].tolist() == _EXPECTED
    assert np.array_equal(out["vpu"], out["mxu"])


@pytest.mark.slow
def test_randomized_verdict_parity_single_device(monkeypatch):
    """Flag-on the randomized verifier's MSM runs its field arithmetic on
    the MXU lane and its reject-bisection localizes every bad lane —
    verdicts must still match the flag-off run bit for bit.  min_device_batch=5 keeps the bisection's sub-batches on
    the strict kernel compiled once per lane (a 2-lane A/B that also
    compiled 4- and 2-lane aggregate kernels would double tier-1's bill
    for no extra coverage — the slow mesh test exercises those tiles)."""
    msgs, sigs, keys = _corpus()
    out = {}
    for lane, ctx in _LANES:
        with ctx():
            monkeypatch.setattr(
                model, "_batch_verify_kernel", _fresh_jit(model.batch_verify_impl)
            )
            monkeypatch.setattr(
                model, "_verify_kernel", _fresh_jit(model.packed_verify_impl)
            )
            v = model.Ed25519RandomizedBatchVerifier(min_device_batch=5)
            out[lane] = np.asarray(v.verify_batch(msgs, sigs, keys))
    assert out["vpu"].tolist() == _EXPECTED
    assert np.array_equal(out["vpu"], out["mxu"])


@pytest.mark.slow
def test_halfagg_verdict_parity(monkeypatch):
    """All-or-nothing aggregate certs: accept/reject parity across the
    valid cert, a tampered aggregate scalar, a swapped key, and a
    non-canonical R component.  Slow lane: each lane compiles the full
    half-agg verify graph fresh (~20 s apiece on the CI host)."""
    signers = _signers()
    msgs = [b"halfagg-%d" % i for i in range(4)]
    sigs = [s.sign_raw(m) for s, m in zip(signers, msgs)]
    keys = [s.public_bytes for s in signers]
    cert, bad = agg.HalfAggregator(min_device_batch=1).aggregate(
        msgs, sigs, keys
    )
    assert cert is not None and bad == ()
    rs, s_agg = cert
    rs = list(rs)
    cases = {
        "valid": (msgs, rs, s_agg, keys),
        "tampered_s_agg": (msgs, rs, _flip(s_agg, 0), keys),
        "swapped_key": (msgs, rs, s_agg, [keys[1], keys[0]] + keys[2:]),
        "noncanonical_r": (msgs, [b"\xff" * 32] + rs[1:], s_agg, keys),
    }
    out = {}
    for lane, ctx in _LANES:
        with ctx():
            monkeypatch.setattr(
                agg, "_halfagg_verify_kernel", _fresh_jit(agg.batch_verify_impl)
            )
            ver = agg.HalfAggregator(min_device_batch=1)
            out[lane] = {
                name: ver.verify(*case) for name, case in cases.items()
            }
    assert out["vpu"] == out["mxu"]
    assert out["vpu"] == {
        "valid": True,
        "tampered_s_agg": False,
        "swapped_key": False,
        "noncanonical_r": False,
    }


def _mesh_or_skip():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-way virtual host mesh (conftest XLA flag)")


@pytest.mark.slow
def test_strict_verdict_parity_8way_mesh():
    """SAFETY.md §7 with the MXU lane on: topology never changes verdicts.
    ``compile_cache=False`` keeps each lane's shard_map trace out of the
    process-wide kernel memo — a shared memo entry would replay the first
    lane's graph for both."""
    _mesh_or_skip()
    from consensus_tpu.parallel.sharding import ShardedEd25519Verifier

    msgs, sigs, keys = _corpus()
    out = {}
    for lane, ctx in _LANES:
        with ctx():
            eng = ShardedEd25519Verifier(
                min_device_batch=1, compile_cache=False
            )
            assert eng.shard_count == 8
            out[lane] = np.asarray(eng.verify_batch(msgs, sigs, keys))
    assert out["vpu"].tolist() == _EXPECTED
    assert np.array_equal(out["vpu"], out["mxu"])


@pytest.mark.slow
def test_randomized_verdict_parity_8way_mesh():
    """Flag-on, the sharded randomized engine runs the XLA MSM with MXU
    field contractions.  Verdicts must not move."""
    _mesh_or_skip()
    from consensus_tpu.parallel.sharding import ShardedEd25519RandomizedVerifier

    msgs, sigs, keys = _corpus()
    out = {}
    for lane, ctx in _LANES:
        with ctx():
            eng = ShardedEd25519RandomizedVerifier(
                min_device_batch=2, compile_cache=False
            )
            out[lane] = np.asarray(eng.verify_batch(msgs, sigs, keys))
    assert out["vpu"].tolist() == _EXPECTED
    assert np.array_equal(out["vpu"], out["mxu"])


# --- counting-shim semantics -------------------------------------------------

def test_counting_records_dots_not_muls():
    """The MXU dispatch happens BEFORE the shim notes a mul, so a counted
    trace records muls OR dot_general MACs per site, never both.  Pinned
    per-site weights (batch 4): 25519 mul = outer-product (32x1x32) +
    column assembly (63x1x1024) = 65536 dense MACs/lane = 64 m-equiv;
    P-256 adds the Solinas contraction (32x1x64) on top."""
    a = jnp.zeros((32, 4), jnp.float32)
    with mxu_limbs.force_mxu_limbs():
        for fn, args in ((fe.mul, (a, a)), (fe.square, (a,))):
            d = limbs.measure_field_ops(fn, *args).as_dict()
            assert (d["muls"], d["squares"], d["adds"]) == (0, 0, 0)
            assert d["dots"] == 8          # 2 contractions x 4 lanes
            assert d["dot_macs"] == 4 * 65536
            assert d["m_equiv"] == pytest.approx(4 * 64.0)
        d = limbs.measure_field_ops(fp.mul, a, a).as_dict()
        assert (d["muls"], d["dots"]) == (0, 12)
        assert d["dot_macs"] == 4 * 67584
        assert d["m_equiv"] == pytest.approx(4 * 66.0)
    # Flag off: the classic VPU ledger, no dot traffic.
    d = limbs.measure_field_ops(fe.mul, a, a).as_dict()
    assert (d["muls"], d["dots"], d["dot_macs"]) == (4, 0, 0)


@pytest.mark.slow
def test_batch512_op_counts_pinned_both_lanes():
    """The measured PERF.md denominators at the batch-512 acceptance
    point, pinned exactly for BOTH lanes (abstract tracing only — big
    graphs, hence slow).  The MXU column is honest dense-MAC accounting:
    ~77x the VPU m-equiv, the bet being that MXU throughput covers it.
    Any drift here means the arithmetic (and thus PERF.md §5) changed."""
    b = 512
    strict_args = (
        jnp.zeros((32, b), jnp.uint8), jnp.zeros((b,), jnp.uint8),
        jnp.zeros((32, b), jnp.uint8), jnp.zeros((b,), jnp.uint8),
        jnp.zeros((32, b), jnp.uint8), jnp.zeros((64, b), jnp.uint8),
        jnp.zeros((b,), jnp.bool_),
    )
    rand_args = (
        jnp.zeros((32, b), jnp.uint8), jnp.zeros((b,), jnp.uint8),
        jnp.zeros((32, b), jnp.uint8), jnp.zeros((b,), jnp.uint8),
        jnp.zeros((32, 1), jnp.uint8), jnp.zeros((64, b), jnp.uint8),
        jnp.zeros((33, b), jnp.uint8), jnp.zeros((b,), jnp.bool_),
    )
    with mxu_limbs.suppress_mxu_limbs():
        strict = limbs.measure_field_ops(model.verify_impl, *strict_args)
        rand = limbs.measure_field_ops(model.batch_verify_impl, *rand_args)
    assert (strict.muls, strict.squares, strict.adds) == (
        1042432, 654336, 332800
    )
    assert strict.m_equiv == pytest.approx(1402316.8)
    assert (rand.muls, rand.squares, rand.adds) == (516937, 274176, 114176)
    assert rand.m_equiv == pytest.approx(667733.8)

    with mxu_limbs.force_mxu_limbs():
        strict = limbs.measure_field_ops(model.verify_impl, *strict_args)
        rand = limbs.measure_field_ops(model.batch_verify_impl, *rand_args)
    assert (strict.muls, strict.squares) == (0, 0)
    assert (strict.dots, strict.dot_macs) == (3393536, 111199387648)
    assert strict.m_equiv == pytest.approx(108593152.0)
    # The counted randomized trace: the XLA MSM — MXU contractions, XLA
    # scheduling.
    assert (rand.muls, rand.squares) == (0, 0)
    assert (rand.dots, rand.dot_macs) == (1582226, 51846381568)
    assert rand.m_equiv == pytest.approx(50631232.0)
