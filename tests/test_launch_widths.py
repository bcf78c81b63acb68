"""The launch is as wide as its wave: ``Ed25519BatchVerifier`` with a ladder
of compiled widths (``pad_to=(16, 32)`` and ``(8, 16, 32)`` here; the rig
sidecar's are ``lanes // 2`` and ``lanes``, and ``lanes // 4`` where
``sidecar_main.launch_widths`` takes it) pads a wave to the narrowest width
that holds it, and gives the same strict verdicts at each; ``instrumented_jit``
lowers each shape once and still reads the executable's cost estimates; the
comb table a verify kernel bakes in is built with one modular inversion.

The wave goes to the device as the bytes it came in: a launch is
``_prepare`` -> ``pack_wave`` -> ONE host->device copy -> ONE program
(``packed_verify_impl``), whose verdicts equal the host references' on every
rejection class at every fill of either width; the device's signed-digit
recoding equals the host integers' on the carry's edge scalars; what the
lanes past a wave's end hold never reaches a verdict.

Compiles three small strict kernels (8, 16 and 32 lanes) on the CPU backend.
"""

import hashlib

import numpy as np
import pytest

from consensus_tpu.models import ed25519 as model
from consensus_tpu.models.ed25519 import (
    Ed25519BatchVerifier,
    L,
    ref_public_key,
    ref_sign,
)
from consensus_tpu.obs.kernels import (
    KERNELS,
    KernelRegistry,
    instrumented_jit,
    kernel_lane_suffix,
)

QUARTER, HALF, TOP = 8, 16, 32
#: The two ladders a sidecar builds: the n4 shape's, and the n7 shape's.
TWO, THREE = (HALF, TOP), (QUARTER, HALF, TOP)
KERNEL = "ed25519.verify" + kernel_lane_suffix()


@pytest.fixture(scope="module")
def corpus():
    """``TOP`` honest triples under 4 signers (deterministic ref crypto)."""
    seeds = [hashlib.sha512(b"ctpu/test-lw/%d" % i).digest()[:32] for i in range(4)]
    pubs = [ref_public_key(s) for s in seeds]
    msgs = [b"launch-width-%d" % i for i in range(TOP)]
    sigs = [ref_sign(seeds[i % 4], m) for i, m in enumerate(msgs)]
    return msgs, sigs, [pubs[i % 4] for i in range(TOP)]


@pytest.fixture(scope="module")
def planted(corpus):
    """The first ``HALF`` triples with one lane of each rejection class."""
    msgs, sigs, keys = (list(x[:HALF]) for x in corpus)
    sigs[1] = sigs[1][:63]  # bad length
    sigs[3] = sigs[3][:32] + (
        int.from_bytes(sigs[3][32:], "little") + L
    ).to_bytes(32, "little")  # S >= L: the malleable twin
    sigs[5] = (2**255 - 19 + 1).to_bytes(32, "little") + sigs[5][32:]  # R.y >= p
    keys[7] = keys[8]  # wrong key
    sigs[9] = sigs[9][:10] + bytes([sigs[9][10] ^ 1]) + sigs[9][11:]  # forged R
    msgs[11] = msgs[11] + b"!"  # wrong message
    return msgs, sigs, keys


@pytest.fixture(scope="module")
def ladders():
    return {rungs: Ed25519BatchVerifier(min_device_batch=1, pad_to=rungs)
            for rungs in (TWO, THREE)}


@pytest.fixture(scope="module")
def ladder(ladders):
    return ladders[TWO]


@pytest.mark.parametrize("rungs, sizes", [
    (TWO, [HALF + 1, 1]), (THREE, [HALF + 1, QUARTER + 1, 1])])
def test_compile_ahead_compiles_on_its_thread_and_lowers_the_next_beside_it(
    ladders, corpus, rungs, sizes
):
    """``compile_ahead(sizes)`` compiles the width each size rides on the
    thread that calls it, one after the other, each once; the helper thread
    beside it only traces and lowers (jax's own events, by thread).  The
    launches that follow neither trace, lower nor compile, and the ledger
    books each width's compile at its first launch, as for a wave that
    compiled it.  (The three-rung ladder finds two of its widths compiled
    by the two-rung one: one kernel a width in the process.)"""
    import threading

    from jax import monitoring

    events = []

    def listener(event, secs, **_kw):
        event = event.rsplit("/", 1)[-1]
        # A trace answered from jax's cache is an event too, of no length.
        if event != "jaxpr_trace_duration" or secs > 0.25:
            events.append((threading.current_thread().name, event))

    msgs, sigs, keys = corpus
    ladder = ladders[rungs]
    stats = KERNELS.stats(KERNEL)
    launches, compiles = stats.launches, stats.compiles
    monitoring.register_event_duration_secs_listener(listener)
    try:
        flusher = threading.Thread(
            target=ladder.compile_ahead, args=(sizes,), name="flusher")
        flusher.start()
        flusher.join()
        ahead = {e for who, e in events if who == "lower-ahead"}
        assert ahead <= {"jaxpr_trace_duration", "jaxpr_to_mlir_module_duration"}
        assert {who for who, _ in events} <= {"flusher", "lower-ahead"}
        # In a process that had compiled none of the widths: a compile each,
        # all on the calling thread, and the later widths' lowering beside
        # the first one's.
        compiled = [who for who, e in events if e == "backend_compile_duration"]
        assert compiled == ["flusher"] * len(compiled) and len(compiled) <= len(sizes)
        if len(compiled) == len(sizes):
            lowered = [e for e in events
                       if e == ("lower-ahead", "jaxpr_to_mlir_module_duration")]
            assert len(lowered) == len(sizes) - 1
        assert (stats.launches, stats.compiles) == (launches, compiles)
        del events[:]
        for n in sizes:
            assert ladder.verify_batch(msgs[:n], sigs[:n], keys[:n]).all()
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert not events, events
    assert stats.launches == launches + len(sizes)
    assert stats.compiles - compiles in (0, len(compiled))


@pytest.mark.parametrize(
    "rungs, n, width",
    [(TWO, 1, HALF), (TWO, HALF, HALF), (TWO, HALF + 1, TOP), (TWO, TOP, TOP),
     (TWO, TOP + 1, 2 * TOP), (TWO, 3 * TOP, 4 * TOP),
     (THREE, 1, QUARTER), (THREE, QUARTER, QUARTER), (THREE, QUARTER + 1, HALF),
     (THREE, HALF, HALF), (THREE, HALF + 1, TOP), (THREE, TOP, TOP),
     (THREE, TOP + 1, 2 * TOP)],
)
def test_a_wave_rides_the_narrowest_width_that_holds_it(ladders, rungs, n, width):
    """At every rung's boundary (rung, rung + 1), on the two-rung ladder and
    the three-rung one; over the top the power-of-two fallback."""
    assert ladders[rungs].launch_width(n) == width
    assert ladders[rungs]._pad_to == TOP  # what wave sizing and subclasses read


@pytest.mark.parametrize("lanes, widths", [
    (512, (256, 512)),  # the n4 shape keeps two
    (256, (128, 256)), (8, (4, 8)),
    (1024, (256, 512, 1024)), (2048, (512, 1024, 2048)),
    (8192, (2048, 4096, 8192)),  # the n7 shape gains the quarter
    (16384, (4096, 8192, 16384)),
])
def test_a_sidecar_takes_the_quarter_only_where_it_is_a_launch_worth_compiling(
    lanes, widths
):
    """``sidecar_main.launch_widths``: arithmetic on the full width alone;
    a ladder gets the quarter from 256 lanes up, the half always."""
    from consensus_tpu.deploy.sidecar_main import launch_widths

    assert launch_widths(lanes) == widths
    engine = Ed25519BatchVerifier(pad_to=launch_widths(lanes))
    assert engine._widths == widths and engine._pad_to == lanes


@pytest.mark.parametrize("pad_to", [0, 8, TOP])
@pytest.mark.parametrize("n", [1, 8, 9, TOP, TOP + 1, 5 * TOP])
def test_a_single_width_engine_pads_as_before(pad_to, n):
    """One width (or none) is the rule it was: ``pad_to`` if the wave fits,
    else the next power of two, 8-lane floor."""
    was = pad_to if pad_to >= n else model._next_pow2(n)
    assert Ed25519BatchVerifier(pad_to=pad_to).launch_width(n) == was
    assert Ed25519BatchVerifier(pad_to=(pad_to,)).launch_width(n) == was


@pytest.mark.parametrize("width", [HALF, TOP])
def test_planted_rejections_get_the_same_verdicts_at_either_width(
    ladder, corpus, planted, width
):
    """The same wave of ``HALF`` lanes, every rejection class planted, through
    the ladder (it rides ``HALF``) and through a one-width engine of
    ``width``: identical verdicts, equal to ``verify_host``'s, and exactly
    the planted lanes rejected."""
    msgs, sigs, keys = planted
    expected = ladder.verify_host(msgs, sigs, keys)
    assert np.flatnonzero(~expected).tolist() == [1, 3, 5, 7, 9, 11]
    before = KERNELS.stats(KERNEL).launches
    got = ladder.verify_batch(msgs, sigs, keys)
    one_width = Ed25519BatchVerifier(min_device_batch=1, pad_to=width)
    assert np.array_equal(got, expected)
    assert np.array_equal(one_width.verify_batch(msgs, sigs, keys), expected)
    assert KERNELS.stats(KERNEL).launches == before + 2


def test_a_wave_over_the_half_rides_the_top_with_the_same_verdicts(
    ladder, corpus, planted
):
    """The planted lanes plus honest ones past the half: the wave rides
    ``TOP`` and the shared lanes read as they did at ``HALF``."""
    msgs, sigs, keys = (list(p) + list(c[HALF:]) for p, c in zip(planted, corpus))
    assert ladder.launch_width(len(msgs)) == TOP
    got = ladder.verify_batch(msgs, sigs, keys)
    assert np.array_equal(got, ladder.verify_host(msgs, sigs, keys))
    assert np.array_equal(
        got[:HALF], ladder.verify_batch(*(x[:HALF] for x in (msgs, sigs, keys)))
    )
    assert got[HALF:].all()


@pytest.mark.parametrize("rungs", [TWO, THREE])
def test_the_ladder_compiled_one_shape_a_width_and_no_more(ladders, corpus, rungs):
    """After a wave at every rung and one past every rung the jit cache of
    the strict kernel holds the ladder's widths (other tests of this file
    compiled them already: nothing new compiles here)."""
    msgs, sigs, keys = corpus
    ladder = ladders[rungs]
    stats = KERNELS.stats(KERNEL)
    for n in rungs:  # make sure all are there, whatever ran before
        ladder.verify_batch(msgs[:n], sigs[:n], keys[:n])
    compiles = stats.compiles
    for n in sorted({1, *rungs, *(r + 1 for r in rungs[:-1])}):
        assert ladder.verify_batch(msgs[:n], sigs[:n], keys[:n]).all()
    assert stats.compiles == compiles
    assert stats.flops is None or stats.flops > 0


# --- the packed launch path -------------------------------------------------

P = 2**255 - 19


def _flip(raw: bytes, at: int, bit: int = 1) -> bytes:
    return raw[:at] + bytes([raw[at] ^ bit]) + raw[at + 1:]


def _no_x_on_the_curve() -> bytes:
    """A canonical y that is no point's: decompression fails on the device."""
    y = 2
    while model._ref_recover_x(y, 0) is not None:
        y += 1
    return y.to_bytes(32, "little")


#: One lane of each class the judge plants (served_bench/reference.py) and
#: the two beside them: ``plant(msgs, sigs, keys, i)`` spoils lane ``i``.
_CLASSES = {
    "valid": lambda m, s, k, i: None,
    "short_signature": lambda m, s, k, i: s.__setitem__(i, s[i][:63]),
    "short_key": lambda m, s, k, i: k.__setitem__(i, k[i][:31]),
    "s_ge_l": lambda m, s, k, i: s.__setitem__(i, s[i][:32] + (
        int.from_bytes(s[i][32:], "little") + L).to_bytes(32, "little")),
    "noncanonical_r": lambda m, s, k, i: s.__setitem__(
        i, (P + 3).to_bytes(32, "little") + s[i][32:]),
    "noncanonical_a": lambda m, s, k, i: k.__setitem__(
        i, (P + 5).to_bytes(32, "little")),
    "undecodable_r": lambda m, s, k, i: s.__setitem__(
        i, _no_x_on_the_curve() + s[i][32:]),
    "sign_bit_of_r": lambda m, s, k, i: s.__setitem__(i, _flip(s[i], 31, 0x80)),
    "sign_bit_of_a": lambda m, s, k, i: k.__setitem__(i, _flip(k[i], 31, 0x80)),
    "altered_message": lambda m, s, k, i: m.__setitem__(i, m[i] + b"!"),
    "altered_r": lambda m, s, k, i: s.__setitem__(i, _flip(s[i], 10)),
    "altered_s": lambda m, s, k, i: s.__setitem__(i, _flip(s[i], 40)),
    "altered_key": lambda m, s, k, i: k.__setitem__(
        i, ref_public_key(b"nobody's signer".ljust(32))),
}


@pytest.mark.parametrize("rungs, n", [
    (TWO, 1), (TWO, HALF - 3), (TWO, HALF), (TWO, HALF + 5), (TWO, TOP),
    (THREE, 1), (THREE, QUARTER - 3), (THREE, QUARTER)])
@pytest.mark.parametrize("spoiled", list(_CLASSES))
def test_the_packed_program_gives_the_references_verdicts(
    ladders, corpus, spoiled, rungs, n
):
    """One lane (the last but one, or the only one) of the class, in a wave
    that fills its width, falls short of it, or is a single lane — on the
    three-rung ladder the waves that ride the new, narrowest rung: the
    device's verdicts are OpenSSL's under the strict pre-checks
    (``verify_host``) lane by lane, and the spoiled lane's is the plain
    integers' (``ref_verify``)."""
    ladder = ladders[rungs]
    assert ladder.launch_width(n) == min(w for w in rungs if w >= n)
    msgs, sigs, keys = (list(x[:n]) for x in corpus)
    at = max(0, n - 2)
    _CLASSES[spoiled](msgs, sigs, keys, at)
    got = ladder.verify_batch(msgs, sigs, keys)
    assert got.shape == (n,)
    assert np.array_equal(got, ladder.verify_host(msgs, sigs, keys))
    assert bool(got[at]) == model.ref_verify(keys[at], sigs[at], msgs[at])
    assert got.sum() == n - (spoiled != "valid")


def _lone_eight(window: int) -> int:
    return 8 << (4 * window)


#: The carry's edge scalars, all under 2^253 as k < L is: a lone 8 makes a
#: carry, a 7 above it passes it on.
_EDGE_SCALARS = {
    "zero": 0,
    "one": 1,
    "l_minus_1": L - 1,
    "sevens_pass_a_carry_all_the_way": int("7" * 62 + "8", 16),
    "sevens_make_none": int("7" * 63, 16),
    "eights_under_l": int("8" * 64, 16) % L,
    "eights_below_the_top_window": int("8" * 63, 16),
    **{"lone_eight_in_window_%02d" % w: _lone_eight(w) for w in range(63)},
}


@pytest.fixture(scope="module")
def edge_digits():
    """``sc.signed_window_digits`` over all the edge scalars at once, a
    scalar a lane: name -> its 64 digits, MSB window first."""
    from consensus_tpu.ops import scalar25519 as sc

    rows = np.stack([
        np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)
        for v in _EDGE_SCALARS.values()
    ])
    digits = np.asarray(sc.signed_window_digits(rows.T, model._WINDOWS))
    assert digits.shape == (model._WINDOWS, len(_EDGE_SCALARS))
    return dict(zip(_EDGE_SCALARS, digits.T))


@pytest.mark.parametrize("name", list(_EDGE_SCALARS))
def test_the_device_recodes_k_as_the_host_integers_do(edge_digits, name):
    value = _EDGE_SCALARS[name]
    want = model._signed_digits_int(value, model._WINDOWS)
    assert (edge_digits[name] - 8).tolist() == want
    assert sum(d << (4 * (63 - j)) for j, d in enumerate(want)) == value


def test_a_lone_eight_in_the_top_window_is_no_scalar_under_l():
    """What the 64 windows cannot hold the host refuses; k < L never is it."""
    assert _lone_eight(63) > L
    with pytest.raises(ValueError):
        model._signed_digits_int(_lone_eight(63), model._WINDOWS)


@pytest.mark.parametrize("width", [HALF, QUARTER])
@pytest.mark.parametrize("tail_ok", [0, 1])
def test_what_the_lanes_past_the_wave_hold_never_flips_a_verdict(
    ladder, corpus, planted, tail_ok, width
):
    """A short wave written over a full one (as a buffer kept between
    launches would hold it; none is kept): the short wave's lanes read as
    they do from a fresh array, whatever the tail holds, and the tail, with
    ``host_ok`` cleared, rejects.  With the tail's ``host_ok`` LEFT it reads
    as the full wave did: the lanes are independent, and ``verify_batch``
    cuts them off."""
    import jax.numpy as jnp

    n = 5
    full = model.pack_wave(*ladder._prepare(*(x[:width] for x in planted)), width)
    rows, host_ok = ladder._prepare(*(x[8:8 + n] for x in corpus))
    fresh = model.pack_wave(rows, host_ok, width)
    assert not fresh[:, n:].any()
    kept = full.copy()
    kept[:, :n] = fresh[:, :n]
    if not tail_ok:
        kept[128, n:] = 0
    assert kept[:128, n:].any()
    want_full = np.asarray(model._verify_kernel(jnp.asarray(full)))
    want = np.asarray(model._verify_kernel(jnp.asarray(fresh)))
    got = np.asarray(model._verify_kernel(jnp.asarray(kept)))
    assert want[:n].all() and not want[n:].any()
    assert np.array_equal(got[:n], want[:n])
    assert np.array_equal(got[n:], want_full[n:] if tail_ok else want[n:])


def test_a_launch_is_one_copy_and_one_program(ladder, corpus, monkeypatch):
    """One device launch: the four ``verify.*`` phases, ONE host->device
    copy (``jnp.asarray`` of the one ``(129, width)`` uint8 array, no
    ``device_put``), ONE call of one jitted program with that one argument;
    no other kernel of the ledger launches."""
    import jax

    from consensus_tpu.obs.kernels import FLUSHER

    copies, puts, calls = [], [], []

    class JnpSpy:  # stands in for the module's ``jnp``
        def __getattr__(self, name):
            return getattr(jax.numpy, name)

        @staticmethod
        def asarray(a, *args, **kw):
            copies.append((type(a), a.shape, a.dtype))
            return jax.numpy.asarray(a, *args, **kw)

    kernel, device_put = model._verify_kernel, jax.device_put

    def spy_kernel(*args, **kw):
        calls.append([(type(a).__name__, a.shape, a.dtype) for a in args] + list(kw))
        return kernel(*args, **kw)

    monkeypatch.setattr(model, "jnp", JnpSpy())
    monkeypatch.setattr(model, "_verify_kernel", spy_kernel)
    monkeypatch.setattr(
        jax, "device_put", lambda *a, **kw: puts.append(a) or device_put(*a, **kw))
    msgs, sigs, keys = (x[:HALF + 1] for x in corpus)
    before, launched = FLUSHER.snapshot(), KERNELS.snapshot()
    assert ladder.verify_batch(msgs, sigs, keys).all()
    after = FLUSHER.snapshot()
    assert {k for k in after if after[k] != before[k]} == {
        "verify.prepare", "verify.prepare_cpu", "verify.layout",
        "verify.dispatch", "verify.await"}
    assert copies == [(np.ndarray, (129, TOP), np.dtype(np.uint8))]
    assert puts == []
    assert calls == [[("ArrayImpl", (129, TOP), np.dtype(np.uint8))]]
    now = KERNELS.snapshot()
    moved = {name for name in now
             if now[name]["launches"] != launched.get(name, {}).get("launches", 0)}
    assert moved == {KERNEL}
    assert now[KERNEL]["launches"] == launched[KERNEL]["launches"] + 1


@pytest.mark.parametrize("rungs, sizes", [
    (TWO, (HALF + 1, 1)), (THREE, (HALF + 1, QUARTER + 1, 1))])
def test_compile_ahead_lowers_the_widths_from_shapes_alone(
    ladders, monkeypatch, rungs, sizes
):
    """``compile_ahead`` of the sidecar's warm-up sizes prepares and packs
    no wave and launches nothing: a width's shape is enough."""

    def never(*_a, **_kw):
        raise AssertionError("compile_ahead built a wave")

    monkeypatch.setattr(Ed25519BatchVerifier, "_prepare", never)
    monkeypatch.setattr(model, "pack_wave", never)
    launches = KERNELS.stats(KERNEL).launches
    lowered = []
    jitted = model._verify_kernel.__wrapped__

    class Spy:
        def lower(self, *args):
            lowered.append([(a.shape, a.dtype) for a in args])
            return jitted.lower(*args)

    kernel = model._verify_kernel
    monkeypatch.setattr(kernel, "__wrapped__", Spy())
    ladders[rungs].compile_ahead(sizes)
    assert KERNELS.stats(KERNEL).launches == launches
    shapes = {tuple(x) for x in lowered}
    assert shapes == {(((129, width), np.dtype(np.uint8)),) for width in rungs}


@pytest.mark.parametrize("fused, randomized", [(False, True), (True, False)])
def test_engines_that_launch_at_one_width_take_the_widest(fused, randomized):
    """``engine_for_config`` hands a ladder to the strict single-device
    engine only; the others keep one width, the ladder's widest, bit for bit
    what ``pad_to=TOP`` built."""
    from dataclasses import replace

    from consensus_tpu.config import Configuration
    from consensus_tpu.models import engine_for_config

    config = replace(Configuration(), batch_verify_mode=randomized,
                     device_prep=fused)
    engine = engine_for_config(config, pad_to=(HALF, TOP))
    assert engine._pad_to == TOP and engine._widths == (TOP,)
    assert engine.launch_width(1) == TOP
    strict = engine_for_config(Configuration(), pad_to=(HALF, TOP))
    assert type(strict) is Ed25519BatchVerifier
    assert strict._widths == (HALF, TOP)
    assert engine_for_config(Configuration(), pad_to=TOP)._widths == (TOP,)


def test_instrumented_jit_lowers_each_shape_once_and_reads_its_cost(monkeypatch):
    """By jax's own count a NEW shape is lowered (jaxpr -> MLIR) once and
    compiled once, a shape seen before not at all; the one ``lower()`` the
    wrapper calls, at the kernel's first compile, is answered from jax's
    caches, and so is its ``compile()``: ``flops`` / ``bytes_accessed`` are
    that executable's, and the lowered module is never converted a second
    time for ``Lowered.cost_analysis()``."""
    import jax
    from jax import monitoring

    events, calls = [], []

    def listener(event, _secs, **_kw):
        events.append(event.rsplit("/", 1)[-1])

    class LoweredSpy:
        def __init__(self, lowered):
            self._lowered = lowered

        def compile(self):
            calls.append("compile")
            return self._lowered.compile()

        def cost_analysis(self):
            calls.append("lowered.cost_analysis")
            return self._lowered.cost_analysis()

    class JitSpy:
        def __init__(self, jitted):
            self._jitted = jitted

        def __call__(self, *args, **kwargs):
            return self._jitted(*args, **kwargs)

        def __getattr__(self, name):
            return getattr(self._jitted, name)

        def lower(self, *args, **kwargs):
            calls.append(("lower", args[0].shape))
            return LoweredSpy(self._jitted.lower(*args, **kwargs))

    real_jit = jax.jit
    monkeypatch.setattr(jax, "jit", lambda fn, **kw: JitSpy(real_jit(fn, **kw)))
    registry = KernelRegistry()
    fn = instrumented_jit(
        lambda x: jax.numpy.cumsum(x * x) @ x, "unit.lower_once", registry=registry
    )
    monkeypatch.undo()
    monitoring.register_event_duration_secs_listener(listener)
    try:
        for lanes, new_shape in (16, 1), (16, 0), (24, 1), (16, 0):
            x = np.arange(lanes, dtype=np.float32)
            del events[:]
            got = fn(x)
            assert events.count("jaxpr_to_mlir_module_duration") == new_shape
            assert events.count("backend_compile_duration") == new_shape
            assert float(got) == float(np.cumsum(x * x) @ x)
    finally:
        monitoring.unregister_event_duration_listener(listener)
    stats = registry.stats("unit.lower_once")
    assert (stats.launches, stats.compiles, stats.retraces) == (4, 2, 1)
    assert calls == [("lower", (16,)), "compile"]
    assert stats.flops is not None and stats.flops > 0
    assert stats.bytes_accessed is not None and stats.bytes_accessed > 0
    assert set(registry.snapshot()["unit.lower_once"]) == {
        "launches", "compiles", "retraces", "flops", "bytes_accessed"}


def test_the_comb_table_is_the_multiples_of_the_base_point():
    """``T[j][d] = d * 2^(8j) * B`` in affine limbs, whatever way it was
    built: spot entries against the reference scalar multiplication, and
    the batched inversion against plain ones."""
    from consensus_tpu.ops import ed25519 as ops
    from consensus_tpu.ops import field25519 as fe

    xs, ys, ts = ops._comb_table_np()
    assert xs.shape == ys.shape == ts.shape == (32, 256, fe.LIMBS)
    base = (ops._BX, ops._BY, 1, ops._BX * ops._BY % fe.P)
    for j, d in [(0, 0), (0, 1), (0, 2), (0, 255), (1, 1), (7, 130), (31, 255)]:
        x, y, z, _ = model._ref_mul(d << (8 * j), base)
        z_inv = pow(z, fe.P - 2, fe.P)
        x, y = x * z_inv % fe.P, y * z_inv % fe.P
        assert np.array_equal(xs[j, d], fe.int_to_limbs(x)), (j, d)
        assert np.array_equal(ys[j, d], fe.int_to_limbs(y)), (j, d)
        assert np.array_equal(ts[j, d], fe.int_to_limbs(x * y % fe.P)), (j, d)
    values = [3, fe.P - 1, 2**200 + 7, 1]
    assert ops._batch_inverse_int(values) == [pow(v, fe.P - 2, fe.P) for v in values]
    # The extended addition agrees with the affine one the 16-entry table uses.
    two_b = ops._edwards_add_int((ops._BX, ops._BY), (ops._BX, ops._BY))
    x, y, z, _ = ops._extended_add_int(base, base)
    z_inv = pow(z, fe.P - 2, fe.P)
    assert (x * z_inv % fe.P, y * z_inv % fe.P) == two_b
