"""The Mosaic lane of the field and point arithmetic (ops/mosaic25519.py).

Bit for bit the XLA lane's results: ``mul`` / ``square`` as kernels (Pallas
interpret mode on the CPU) at 1,024 and 2,048 lanes over random weakly
reduced limbs, one raw ``add_raw`` / ``sub_raw`` level and the edge values
0, p - 1, p, p + 1; the point kernels' bodies (``double``, ``add``,
``add_affine``) on vreg limbs, evaluated op by op.  The dispatch rule: the
CPU and 256 / 512 lanes take XLA, the TPU with whole-vreg rows takes
Mosaic.  The strict program in the limb-major layout gives the references'
verdicts on every rejection class at 256 lanes, and the counted cost model
reads what it read before the layout.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from consensus_tpu.models import ed25519 as model
from consensus_tpu.ops import ed25519 as ed
from consensus_tpu.ops import field25519 as fe
from consensus_tpu.ops import limbs
from consensus_tpu.ops import mosaic25519 as mosaic

P = fe.P


def _edge_limbs() -> np.ndarray:
    """(32, 4): 0, p - 1, p and p + 1 as 8-bit limbs."""
    return np.stack([fe.int_to_limbs(v) for v in (0, P - 1, P, P + 1)], axis=1)


def _operand(rng, lanes: int, lo: int, hi: int) -> jnp.ndarray:
    """A limb-major ``(32, lanes // 128, 128)`` element: random limbs in
    ``[lo, hi)`` with the four edge values in its first lanes."""
    x = rng.integers(lo, hi, size=(32, lanes)).astype(np.float32)
    x[:, :4] = _edge_limbs()
    return jnp.asarray(mosaic.limb_major(x))


def _raw(rng, lanes: int) -> tuple:
    """One raw level of weakly reduced operands: an ``add_raw`` (|limb| up
    to 680) and a ``sub_raw`` (negative limbs down to -345)."""
    a, b, c, d = (_operand(rng, lanes, -340, 341) for _ in range(4))
    return fe.add_raw(a, b), fe.sub_raw(c, d)


def _bits(x) -> np.ndarray:
    return np.asarray(x).view(np.int32)


@pytest.mark.parametrize("lanes", [1024, 2048])
@pytest.mark.parametrize("operands", ["weak", "raw", "bytes"])
def test_the_mosaic_mul_is_bit_identical_to_xla(lanes, operands):
    rng = np.random.default_rng(lanes + len(operands))
    if operands == "raw":
        a, b = _raw(rng, lanes)
        assert float(jnp.max(a)) > 340 and float(jnp.min(b)) < 0
    else:
        lo, hi = (-340, 341) if operands == "weak" else (0, 256)
        a, b = _operand(rng, lanes, lo, hi), _operand(rng, lanes, lo, hi)
    got = mosaic.mul(a, b)
    assert got.shape == (32, lanes // 128, 128)
    assert np.array_equal(_bits(got), _bits(fe.mul(a, b)))  # the CPU: XLA


@pytest.mark.parametrize("lanes", [1024, 2048])
@pytest.mark.parametrize("operands", ["weak", "bytes", "square_bound"])
def test_the_mosaic_square_is_bit_identical_to_xla(lanes, operands):
    rng = np.random.default_rng(7 * lanes + len(operands))
    lo, hi = {"weak": (-340, 341), "bytes": (0, 256),
              "square_bound": (-500, 501)}[operands]
    a = _operand(rng, lanes, lo, hi)
    assert np.array_equal(_bits(mosaic.square(a)), _bits(fe.square(a)))


def _limb_lists(point):
    """A limb-major element as the 32 vreg-shaped limbs a kernel loads."""
    return [[c[i] for i in range(fe.LIMBS)] for c in point]


_POINT_BODIES = {
    # name: (kernel body, its arguments from p and q, the XLA formula)
    "double": (ed._double_body, lambda p, q: p[:3],
               lambda p, q: ed._double(fe, p, True)),
    "double_xyz": (ed._double_xyz_body, lambda p, q: p[:3],
                   lambda p, q: ed._double(fe, p, False)[:3]),
    "add": (ed._add_body, lambda p, q: (*p, *q), lambda p, q: ed._add(fe, p, q)),
    "add_affine": (ed._add_affine_body, lambda p, q: (*p, q.x, q.y, q.t),
                   lambda p, q: ed._add_affine(fe, p, q.x, q.y, q.t)),
}


@pytest.mark.parametrize("name", list(_POINT_BODIES))
def test_the_point_kernel_bodies_are_bit_identical_to_xla(name):
    """Each point kernel's body on vreg limbs (one ``(8, 128)`` block,
    evaluated op by op: compiling it for the interpreter takes minutes on
    the CPU), against the XLA formula on the limb-major element."""
    body, args, formula = _POINT_BODIES[name]
    rng = np.random.default_rng(len(name))
    p = ed.Point(*(_operand(rng, 1024, -340, 341) for _ in range(4)))
    q = ed.Point(*(_operand(rng, 1024, -340, 341) for _ in range(4)))
    want = formula(p, q)
    got = body(fe.VregField, *_limb_lists(args(p, q)))
    assert len(got) == len(want)
    for coord, limbs_ in zip(want, got):
        assert np.array_equal(_bits(coord), _bits(jnp.stack(limbs_)))


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch as the TPU backend sees it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("lanes, path", [
    (256, "xla"), (512, "xla"),  # the n4 widths: 2 and 4 of a vreg's 8 rows
    (1024, "mosaic"), (2048, "mosaic"), (4096, "mosaic"), (16384, "mosaic")])
def test_the_dispatch_rule_on_the_tpu(on_tpu, monkeypatch, lanes, path):
    a = jnp.zeros((32, lanes // 128, 128), jnp.float32)
    assert mosaic.path((lanes // 128, 128)) == path
    assert mosaic.launch_path(lanes) == path  # the layout verify_impl takes
    assert model.Ed25519BatchVerifier.field_path(lanes) == path  # the sidecar's
    assert mosaic.active(a, a) == (path == "mosaic")
    calls = []
    monkeypatch.setattr(mosaic, "mul", lambda x, y: calls.append("mul") or x)
    monkeypatch.setattr(mosaic, "square", lambda x: calls.append("square") or x)
    monkeypatch.setattr(
        mosaic, "run", lambda body, n, *e: calls.append(body.__name__) or e[:n])
    jax.eval_shape(fe.mul, a, a)
    jax.eval_shape(fe.square, a)
    point = ed.Point(a, a, a, a)
    jax.eval_shape(ed.add, point, point)
    jax.eval_shape(lambda p: ed.double(p, need_t=False), point)
    jax.eval_shape(lambda p: ed.add_affine(p, a, a, a), point)
    if path == "mosaic":
        assert calls == ["mul", "square", "_add_body", "_double_xyz_body",
                         "_add_affine_body"]
    else:
        assert calls == []


def test_the_dispatch_rule_keeps_xla_off_the_tpu_and_off_whole_vregs(
    on_tpu, monkeypatch
):
    full = jnp.zeros((32, 16, 128), jnp.float32)
    assert not mosaic.active(jnp.zeros((32, 2048), jnp.float32))  # lane-major
    assert not mosaic.active(full, jnp.zeros((32, 1, 1), jnp.float32))
    assert not mosaic.active(jnp.zeros((32, 12, 128), jnp.float32))
    assert mosaic.path((2048,)) == "xla"
    assert mosaic.launch_path(2048 + 64) == "xla"  # not whole 128-lane rows
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not mosaic.active(full, full)
    assert mosaic.path((16, 128)) == "xla"
    assert [mosaic.launch_path(w) for w in (256, 2048, 16384)] == ["xla"] * 3


def test_the_mxu_lane_and_the_counting_shim_keep_the_xla_point_formulas(on_tpu):
    from consensus_tpu.ops import mxu_limbs

    a = jnp.zeros((32, 8, 128), jnp.float32)
    assert ed._on_mosaic(a, a)
    with mxu_limbs.force_mxu_limbs():
        assert not ed._on_mosaic(a, a)
    with limbs.count_field_ops():
        assert not ed._on_mosaic(a, a)


#: Lanes of one class each in a 256-lane wave (the rest honest).
_PLANTS = {
    "short_signature": lambda m, s, k, i: s.__setitem__(i, s[i][:63]),
    "short_key": lambda m, s, k, i: k.__setitem__(i, k[i][:31]),
    "s_ge_l": lambda m, s, k, i: s.__setitem__(i, s[i][:32] + (
        int.from_bytes(s[i][32:], "little") + model.L).to_bytes(32, "little")),
    "noncanonical_r": lambda m, s, k, i: s.__setitem__(
        i, (P + 3).to_bytes(32, "little") + s[i][32:]),
    "noncanonical_a": lambda m, s, k, i: k.__setitem__(i, (P + 5).to_bytes(32, "little")),
    "undecodable_r": lambda m, s, k, i: s.__setitem__(i, _no_x() + s[i][32:]),
    "sign_bit_of_r": lambda m, s, k, i: s.__setitem__(i, _flip(s[i], 31, 0x80)),
    "sign_bit_of_a": lambda m, s, k, i: k.__setitem__(i, _flip(k[i], 31, 0x80)),
    "altered_message": lambda m, s, k, i: m.__setitem__(i, m[i] + b"!"),
    "altered_r": lambda m, s, k, i: s.__setitem__(i, _flip(s[i], 10)),
    "altered_s": lambda m, s, k, i: s.__setitem__(i, _flip(s[i], 40)),
    "wrong_key": lambda m, s, k, i: k.__setitem__(i, k[(i + 1) % len(k)]),
}


def _flip(raw: bytes, at: int, bit: int = 1) -> bytes:
    return raw[:at] + bytes([raw[at] ^ bit]) + raw[at + 1:]


def _no_x() -> bytes:
    y = 2
    while model._ref_recover_x(y, 0) is not None:
        y += 1
    return y.to_bytes(32, "little")


def test_the_limb_major_program_gives_the_references_verdicts_at_256_lanes(
    monkeypatch,
):
    """One wave of 256 lanes with a lane of every rejection class among 8
    signers' honest ones, through the strict program in the limb-major
    layout the Mosaic widths take (forced here: 256 lanes on the CPU keep
    the lane-major one, and its field ops the XLA lane either way): its
    verdicts are the lane-major program's, ``verify_host``'s (OpenSSL under
    the strict pre-checks) and the plain integers' (``ref_verify``), lane
    by lane."""
    import hashlib

    width = 256
    seeds = [hashlib.sha512(b"ctpu/test-mosaic/%d" % i).digest()[:32] for i in range(8)]
    pubs = [model.ref_public_key(s) for s in seeds]
    msgs = [b"limb-major-%d" % i for i in range(width - 3)]
    sigs = [model.ref_sign(seeds[i % 8], m) for i, m in enumerate(msgs)]
    keys = [pubs[i % 8] for i in range(len(msgs))]
    at = {}
    for j, (name, plant) in enumerate(_PLANTS.items()):
        at[name] = 17 * j + 5
        plant(msgs, sigs, keys, at[name])
    engine = model.Ed25519BatchVerifier(min_device_batch=1, pad_to=width)
    assert engine.launch_width(len(msgs)) == width
    assert engine.field_path(width) == "xla"
    rows, ok = engine._prepare(msgs, sigs, keys)
    wave = jnp.asarray(model.pack_wave(rows, ok, width))

    held = []
    to_limb_major = mosaic.limb_major
    monkeypatch.setattr(mosaic, "launch_path", lambda w: "mosaic")
    monkeypatch.setattr(
        mosaic, "limb_major", lambda x: held.append(x.shape) or to_limb_major(x))
    program = lambda w: model.packed_verify_impl(w)  # a fresh trace
    got = np.asarray(jax.jit(program)(wave))[: len(msgs)]
    assert held == [(32, width), (width,)] * 2 + [(32, width), (64, width), (width,)]

    assert np.array_equal(got, engine.verify_host(msgs, sigs, keys))
    assert np.flatnonzero(~got).tolist() == sorted(at.values())
    for i in at.values():
        assert not model.ref_verify(keys[i], sigs[i], msgs[i])


def test_the_counted_cost_model_reads_as_before_the_layout(on_tpu, monkeypatch):
    """``measure_field_ops`` of the strict body: 2,738.9 field-multiply
    equivalents a signature (served_bench/peaks.py), lane-major or
    limb-major, with the Mosaic dispatch live (the TPU's view at 1,024
    lanes) or not."""
    lanes = 1024
    args = (
        jnp.zeros((32, lanes), jnp.uint8), jnp.zeros((lanes,), jnp.uint8),
        jnp.zeros((32, lanes), jnp.uint8), jnp.zeros((lanes,), jnp.uint8),
        jnp.zeros((32, lanes), jnp.uint8), jnp.zeros((64, lanes), jnp.uint8),
        jnp.zeros((lanes,), jnp.bool_),
    )
    on_mosaic = limbs.measure_field_ops(model.verify_impl, *args)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    on_xla = limbs.measure_field_ops(model.verify_impl, *args)
    for counted in (on_mosaic, on_xla):
        assert (counted.muls, counted.squares, counted.adds) == (
            1042432 * 2, 654336 * 2, 332800 * 2)
        assert counted.m_equiv / lanes == pytest.approx(2738.9)
