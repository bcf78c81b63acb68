"""The launch is as wide as its wave: ``Ed25519BatchVerifier`` with a ladder
of compiled widths (``pad_to=(16, 32)`` here; the rig sidecar's are
``lanes // 2`` and ``lanes``) pads a wave to the narrowest width that holds
it, and gives the same strict verdicts at either; ``instrumented_jit``
lowers each shape once and still reads the executable's cost estimates; the
comb table a verify kernel bakes in is built with one modular inversion.

Compiles two small strict kernels (16 and 32 lanes) on the CPU backend.
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

HALF, TOP = 16, 32
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
def ladder():
    return Ed25519BatchVerifier(min_device_batch=1, pad_to=(HALF, TOP))


def test_compile_ahead_compiles_on_its_thread_and_lowers_the_next_beside_it(
    ladder, corpus
):
    """``compile_ahead(sizes)`` compiles the width each size rides on the
    thread that calls it, one after the other; the helper thread beside it
    only traces and lowers (jax's own events, by thread).  The launches
    that follow neither trace, lower nor compile, and the ledger books each
    width's compile at its first launch, as for a wave that compiled it."""
    import threading

    from jax import monitoring

    events = []

    def listener(event, secs, **_kw):
        event = event.rsplit("/", 1)[-1]
        # A trace answered from jax's cache is an event too, of no length.
        if event != "jaxpr_trace_duration" or secs > 0.25:
            events.append((threading.current_thread().name, event))

    msgs, sigs, keys = corpus
    stats = KERNELS.stats(KERNEL)
    launches, compiles = stats.launches, stats.compiles
    monitoring.register_event_duration_secs_listener(listener)
    try:
        flusher = threading.Thread(
            target=ladder.compile_ahead, args=([HALF + 1, 1],), name="flusher")
        flusher.start()
        flusher.join()
        ahead = {e for who, e in events if who == "lower-ahead"}
        assert ahead <= {"jaxpr_trace_duration", "jaxpr_to_mlir_module_duration"}
        assert {who for who, _ in events} <= {"flusher", "lower-ahead"}
        # In a process that had compiled neither width: two compiles, both
        # on the calling thread, and the second width's lowering beside it.
        compiled = [who for who, e in events if e == "backend_compile_duration"]
        assert compiled in ([], ["flusher"], ["flusher"] * 2)
        if len(compiled) == 2:
            assert ("lower-ahead", "jaxpr_to_mlir_module_duration") in events
        assert (stats.launches, stats.compiles) == (launches, compiles)
        del events[:]
        for n in (HALF + 1, 1):
            assert ladder.verify_batch(msgs[:n], sigs[:n], keys[:n]).all()
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert not events, events
    assert stats.launches == launches + 2
    assert stats.compiles - compiles in (0, len(compiled))


@pytest.mark.parametrize(
    "n, width",
    [(1, HALF), (HALF, HALF), (HALF + 1, TOP), (TOP, TOP),
     (TOP + 1, 2 * TOP), (3 * TOP, 4 * TOP)],
)
def test_a_wave_rides_the_narrowest_width_that_holds_it(ladder, n, width):
    """n = 1, half, half + 1, top; over the top the power-of-two fallback."""
    assert ladder.launch_width(n) == width
    assert ladder._pad_to == TOP  # what wave sizing and subclasses read


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


def test_the_ladder_compiled_one_shape_a_width_and_no_more(ladder, corpus):
    """After waves of 1, ``HALF``, ``HALF + 1`` and ``TOP`` signatures the jit
    cache of the strict kernel holds the two widths (other tests of this
    file compiled them already: nothing new compiles here)."""
    msgs, sigs, keys = corpus
    stats = KERNELS.stats(KERNEL)
    for n in (HALF, TOP):  # make sure both are there, whatever ran before
        ladder.verify_batch(msgs[:n], sigs[:n], keys[:n])
    compiles = stats.compiles
    for n in (1, HALF, HALF + 1, TOP):
        assert ladder.verify_batch(msgs[:n], sigs[:n], keys[:n]).all()
    assert stats.compiles == compiles
    assert stats.flops is None or stats.flops > 0


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
