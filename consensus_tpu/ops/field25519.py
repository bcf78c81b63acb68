"""GF(2^255-19) arithmetic as batched JAX float32 limb vectors.

The building block of the TPU Ed25519 batch verifier
(:mod:`consensus_tpu.models.ed25519`), which replaces the reference's
goroutine-per-signature CPU verification (reference
internal/bft/view.go:537-541) with one data-parallel kernel.

Representation: a field element is **32 limbs x 8 bits** stored as
``float32`` of shape ``(32, *batch)`` — limbs leading, batch trailing, so
the batch axis rides the TPU's 128-wide vector lanes.  Why float32 with
tiny limbs: the VPU has no native 32-bit integer multiply (int32 muls are
emulated and ~10x slower), while f32 FMAs are native — and with 8-bit limbs
every product is <= (255+85)^2 < 2^17 and every 32-term schoolbook column
sums below 2^22, comfortably inside f32's 24-bit exact-integer window.  All
arithmetic is therefore **bit-exact**; floats are used as fast small
integers, never rounded.

Multiplication is 32 broadcast-multiplies + shifted column adds (schoolbook
convolution) followed by *parallel* carry-save passes (split with
``floor(x/256)``, which is exact and floor-semantics for negatives, so
borrows propagate like arithmetic shifts).  There are no sequential carry
chains on the hot path.

Two lanes run this arithmetic, chosen per op at trace time.  On the TPU,
an element held **limb-major** as ``(32, rows, 128)`` with ``rows`` a
multiple of 8 (1,024 lanes or more: every limb whole vregs) goes to ONE
Mosaic kernel per ``mul`` / ``square``
(:mod:`consensus_tpu.ops.mosaic25519`; the point ops of
:mod:`consensus_tpu.ops.ed25519` likewise run one kernel each).  What the
chip showed (PERF.md sections 5 and 6): XLA does NOT fuse this code into
large VPU kernels there — the pads of the 32 product rows and the
concatenated one-limb slices of the carry passes split one ``mul`` at
2,048 lanes into 15 fusions that write 12 MB through VMEM, and one step of
the verify kernel's Horner scan into 1,015; a kernel keeps the product
columns in vector registers and writes only the 32 result limbs.  Every
other shape (the CPU, 256 / 512 lanes, a broadcast operand) runs the XLA
code below, bit for bit the same result.
One headroom item remains behind ``CTPU_MXU_LIMBS=1``:
:mod:`consensus_tpu.ops.mxu_limbs` re-expresses the schoolbook convolution
as integer ``dot_general`` tiles for the MXU (``mul``/``square`` below
dispatch there first, bit-identical output).  Counted denominators for the
A/B live in PERF.md §5.

Normalization contract: public ops take and return *weakly reduced*
elements — |limb| <= 340 with value within (-2^250, 2^255 + 2^13), exact
mod p.  ``freeze`` (rare path: comparisons/parity) converts to int32 and
produces the canonical representative in [0, p).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

from consensus_tpu.ops import limbs
from consensus_tpu.ops import mosaic25519 as mosaic
from consensus_tpu.ops.limbs import carry_i32


def _note_lanes(a, b=None) -> int:
    """Independent field elements an op touches: product of the broadcast
    batch dims (everything after the leading limb axis)."""
    shape = a.shape if b is None else jnp.broadcast_shapes(a.shape, b.shape)
    lanes = 1
    for dim in shape[1:]:
        lanes *= int(dim)
    return lanes

LIMBS = 32
LIMB_BITS = 8
BASE = 256.0
INV_BASE = 1.0 / 256.0

P = 2**255 - 19
#: 2^256 mod p — the weight of limb index 32 (used to fold product columns).
FOLD = (2**256) % P  # == 38
#: 2^255 mod p — the weight of bit 255 (used to fold limb 31's top bit).
TOP_FOLD = 19
#: d of edwards25519: -121665/121666 mod p.
D = (-121665 * pow(121666, P - 2, P)) % P
D2 = (2 * D) % P
#: sqrt(-1) mod p (for decompression's second root candidate).
SQRT_M1 = pow(2, (P - 1) // 4, P)


def int_to_limbs(value: int) -> np.ndarray:
    """Python int -> one limb vector (numpy, for constants and host prep)."""
    if not 0 <= value < 2**256:
        raise ValueError("value out of limb range")
    return np.array(
        [(value >> (LIMB_BITS * i)) & 0xFF for i in range(LIMBS)], dtype=np.float32
    )


def limbs_to_int(limbs) -> int:
    """Limb vector (limbs axis first) -> Python int (host-side)."""
    arr = np.asarray(limbs, dtype=np.int64)
    return sum(int(arr[i]) << (LIMB_BITS * i) for i in range(LIMBS))


def constant(value: int) -> jnp.ndarray:
    return jnp.asarray(int_to_limbs(value % P))


def _cexpand(const_limbs, like: jnp.ndarray) -> jnp.ndarray:
    """Reshape a (32,) constant so it broadcasts against (32, *batch)."""
    return jnp.reshape(jnp.asarray(const_limbs), (LIMBS,) + (1,) * (like.ndim - 1))


def constant_like(value: int, like: jnp.ndarray) -> jnp.ndarray:
    """A constant broadcast to ``like``'s shape, inheriting its sharding
    variance (``like * 0 + c`` keeps shard_map's varying-axis typing)."""
    return like * 0 + _cexpand(int_to_limbs(value % P), like)


def from_int_broadcast(value: int, batch_shape) -> jnp.ndarray:
    c = jnp.asarray(int_to_limbs(value % P)).reshape(
        (LIMBS,) + (1,) * len(tuple(batch_shape))
    )
    return jnp.broadcast_to(c, (LIMBS, *batch_shape)).astype(jnp.float32)


def zeros_like_batch(batch_shape) -> jnp.ndarray:
    return jnp.zeros((LIMBS, *batch_shape), dtype=jnp.float32)


# --- reduction ------------------------------------------------------------


def _split(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x -> (x mod 256, floor(x / 256)); exact for |x| < 2^24, floor
    semantics so negative limbs borrow correctly.  Both lanes split with
    it: an array of limbs here, one limb at a time in :class:`VregField`."""
    hi = lax.floor(lax.mul(x, INV_BASE))
    return lax.sub(x, lax.mul(hi, BASE)), hi


def _relax(x: jnp.ndarray) -> jnp.ndarray:
    """One parallel carry-save pass over 32 limbs: 13-bit-free split into an
    8-bit residue plus a high part shifted one limb up; the top limb's high
    part folds back at weight 2^256 ≡ 38.  No sequential dependency."""
    lo, hi = _split(x)
    rolled = jnp.concatenate([hi[31:] * FOLD, hi[:31]], axis=0)
    return lo + rolled


def _top_fold(x: jnp.ndarray) -> jnp.ndarray:
    """Fold bit 255 (limb 31's bit >= 7) back at weight 19, bounding the
    value below 2^255 + epsilon so subtraction biases stay in range."""
    high = jnp.floor(x[31] * (1.0 / 128.0))
    return jnp.concatenate(
        [(x[0] + high * TOP_FOLD)[None], x[1:31], (x[31] - high * 128.0)[None]],
        axis=0,
    )


def _weak_reduce(x: jnp.ndarray) -> jnp.ndarray:
    """Parallel weak reduction for inputs with |limb| < 2^22: three relax
    passes plus a top fold land limbs within |limb| <= 340."""
    x = _relax(x)
    x = _relax(x)
    x = _relax(x)
    return _top_fold(x)


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    if limbs.counting():
        limbs.note_add(_note_lanes(a, b))
    return _weak_reduce(a + b)


# --- lazy (unreduced) ops -------------------------------------------------
# Exactness budget: mul/square require |a_limb| * |b_limb| * 32 < 2^24,
# i.e. the product of the two operands' limb bounds must stay under 2^19
# (724^2).  Weakly reduced values have |limb| <= 340, so ONE level of
# unreduced add/sub (|limb| <= 680 / 600) can feed a multiplication
# directly — the curve formulas exploit this to skip ~half their carry
# passes.  Never stack two raw levels into a multiply.


def add_raw(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a + b without reduction: |limb| grows to |a| + |b| (<= 680 for two
    weakly reduced inputs — still multiplication-safe)."""
    return a + b


def sub_raw(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b (bias 2p) without reduction: for weakly reduced inputs the
    limbs stay within [-345, 600] — multiplication-safe."""
    return a + _cexpand(_TWO_P, a) - b


#: 2p = 2^256 - 38 fits exactly in 32 limbs (top limb 255).
_TWO_P = np.array(
    [((2 * P) >> (LIMB_BITS * i)) & 0xFF for i in range(LIMBS)], dtype=np.float32
)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    # a + 2p - b stays positive for any weakly reduced a, b (< 2p each).
    if limbs.counting():
        limbs.note_add(_note_lanes(a, b))
    return _weak_reduce(a + _cexpand(_TWO_P, a) - b)


def _reduce_cols(cols: jnp.ndarray) -> jnp.ndarray:
    """(63, *batch) schoolbook columns (|col| < 2^24) -> weakly reduced."""
    lo, hi = _split(cols)
    c = jnp.concatenate([lo[:1], lo[1:] + hi[:-1], hi[-1:]], axis=0)  # width 64
    # |r| <= ~2^21.2 with one-raw-level operands (columns up to ~1.48e7,
    # hi < 2^15.9, fold x38) — inside _weak_reduce's 2^22 domain with ~1.8x
    # margin.  Do NOT widen the lazy budget without redoing this analysis.
    r = c[:LIMBS] + c[LIMBS:] * FOLD
    return _weak_reduce(r)


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Batched field multiplication: schoolbook convolution as 32 broadcast
    multiplies + shifted adds (full-lane VPU work), then parallel folds.

    Exact while |a_limb| * |b_limb| <= 2^19 (columns sum 32 products under
    the f32 24-bit integer window) — weakly reduced inputs and one raw
    add/sub level both qualify.

    With ``CTPU_MXU_LIMBS=1`` (trace-time) this dispatches to the
    bit-identical MXU lane, which records its work as ``note_dot`` MACs —
    the dispatch sits BEFORE the ``note_mul`` so a counted trace reports
    muls or dots per site, never both.  Then, after the note, whole-vreg
    limb-major operands on the TPU take the one-kernel Mosaic lane."""
    from consensus_tpu.ops import mxu_limbs

    if mxu_limbs.lane_active():
        return mxu_limbs.mul25519(a, b)
    if limbs.counting():
        limbs.note_mul(_note_lanes(a, b))
    if mosaic.active(a, b):
        return mosaic.mul(a, b)
    batch_pad = [(0, 0)] * (a.ndim - 1)
    terms = [
        jnp.pad(a[i] * b, [(i, LIMBS - 1 - i)] + batch_pad) for i in range(LIMBS)
    ]
    return _reduce_cols(sum(terms))


def square(a: jnp.ndarray) -> jnp.ndarray:
    """Specialized squaring: the product matrix is symmetric, so only the
    upper triangle is computed (cross terms doubled) — ~half the multiplies
    of :func:`mul`.

    Exactness requires |limb| <= 500 (2 * 500^2 * 32 < 2^24); callers with
    one-raw-level inputs (bound 680) must use ``mul(x, x)`` instead.

    The MXU lane squares via ``mul(a, a)`` — the full product columns
    equal these doubled-triangle columns as integers, so the output stays
    bit-identical.  Whole-vreg limb-major operands on the TPU take the
    Mosaic lane, as :func:`mul` does."""
    from consensus_tpu.ops import mxu_limbs

    if mxu_limbs.lane_active():
        return mxu_limbs.square25519(a)
    if limbs.counting():
        limbs.note_square(_note_lanes(a))
    if mosaic.active(a):
        return mosaic.square(a)
    batch_pad = [(0, 0)] * (a.ndim - 1)
    doubled = a + a
    terms = []
    for i in range(LIMBS):
        # Diagonal a_i^2 at column 2i, doubled cross terms a_i*a_j (j > i)
        # at columns i+j — one row per i, padded to the full 63 columns so
        # the terms sum as a parallel reduction tree (a chained scatter-add
        # would serialize all 32 updates).
        row = jnp.concatenate([a[i : i + 1] * a[i], doubled[i + 1 :] * a[i]], axis=0)
        terms.append(jnp.pad(row, [(2 * i, LIMBS - 1 - i)] + batch_pad))
    return _reduce_cols(sum(terms))


# --- the same field on a list of limbs --------------------------------------


def _sum(terms):
    acc = terms[0]
    for t in terms[1:]:
        acc = lax.add(acc, t)
    return acc


class VregField:
    """The field above on an element held as a LIST of its 32 limbs: inside
    a Mosaic kernel (:mod:`~consensus_tpu.ops.mosaic25519`) each limb is one
    ``(8, 128)`` vreg, and the point formulas of
    :mod:`~consensus_tpu.ops.ed25519` run on this class as on this module.

    The array code above, op for op, with its shifts written on the list:
    the same :func:`_split`, column sums, fold at 38, three relax passes,
    top fold at 19 and 2p bias.  Every value is an integer under 2^24, so
    the order of a column's additions cannot change a bit and the results
    are the array code's bit for bit (``tests/test_mosaic25519.py``).
    Written with ``lax`` primitives: a point kernel's body is some 20,000
    of them, traced about four times faster than ``jnp`` operators."""

    _TWO_P = [float(v) for v in _TWO_P]

    @staticmethod
    def _relax(x):
        lo, hi = zip(*map(_split, x))
        return [lax.add(lo[0], lax.mul(hi[LIMBS - 1], float(FOLD)))] + [
            lax.add(lo[i], hi[i - 1]) for i in range(1, LIMBS)
        ]

    @staticmethod
    def _weak_reduce(x):
        x = VregField._relax(VregField._relax(VregField._relax(x)))
        high = lax.floor(lax.mul(x[LIMBS - 1], 1.0 / 128.0))
        return (
            [lax.add(x[0], lax.mul(high, float(TOP_FOLD)))]
            + list(x[1 : LIMBS - 1])
            + [lax.sub(x[LIMBS - 1], lax.mul(high, 128.0))]
        )

    @staticmethod
    def _reduce_cols(cols):
        lo, hi = zip(*map(_split, cols))
        c = (
            [lo[0]]
            + [lax.add(lo[k], hi[k - 1]) for k in range(1, 2 * LIMBS - 1)]
            + [hi[-1]]
        )
        return VregField._weak_reduce(
            [lax.add(c[i], lax.mul(c[i + LIMBS], float(FOLD))) for i in range(LIMBS)]
        )

    @staticmethod
    def mul(a, b):
        return VregField._reduce_cols([
            _sum([lax.mul(a[i], b[k - i])
                  for i in range(max(0, k - LIMBS + 1), min(k, LIMBS - 1) + 1)])
            for k in range(2 * LIMBS - 1)
        ])

    @staticmethod
    def square(a):
        doubled = [lax.add(x, x) for x in a]
        cols = []
        for k in range(2 * LIMBS - 1):
            terms = [lax.mul(a[i], doubled[k - i])
                     for i in range(max(0, k - LIMBS + 1), (k + 1) // 2)]
            if k % 2 == 0:
                terms.append(lax.mul(a[k // 2], a[k // 2]))
            cols.append(_sum(terms))
        return VregField._reduce_cols(cols)

    @staticmethod
    def add_raw(a, b):
        return [lax.add(x, y) for x, y in zip(a, b)]

    @staticmethod
    def sub_raw(a, b):
        return [lax.sub(lax.add(x, t), y) for x, t, y in zip(a, VregField._TWO_P, b)]

    @staticmethod
    def add(a, b):
        return VregField._weak_reduce(VregField.add_raw(a, b))

    @staticmethod
    def sub(a, b):
        return VregField._weak_reduce(VregField.sub_raw(a, b))

    @staticmethod
    def constant_like(value, like):
        """A constant's limbs as Python floats: a limb of a product with it
        is a vreg times a scalar."""
        return [float(v) for v in int_to_limbs(value % P)]


_P_LIMBS_I32 = np.array(
    [(P >> (LIMB_BITS * i)) & 0xFF for i in range(LIMBS)], dtype=np.int32
)
_TWO_P_I32 = _TWO_P.astype(np.int32)


def _carry_i32(x):
    """Exact sequential int32 carry pass (freeze-only path)."""
    return carry_i32(x, LIMB_BITS)


def freeze(a: jnp.ndarray) -> jnp.ndarray:
    """Canonical int32 representative in [0, p).

    Weakly reduced values may be slightly negative (borrow limbs), so bias
    by 2p first, normalize exactly, fold the top bit, then subtract p while
    the value still exceeds it.  Rare path (comparisons/parity only)."""
    x = jnp.asarray(jnp.rint(a), dtype=jnp.int32)
    x = x + jnp.reshape(
        jnp.asarray(_TWO_P_I32), (LIMBS,) + (1,) * (a.ndim - 1)
    )
    x, top = _carry_i32(x)  # value in (0, 2^256 + 2^255); top in {0, 1}
    # Fold the carry-out (weight 2^256 ≡ 38) and bit 255 back.
    x = x.at[0].add(top * FOLD)
    high = x[31] >> 7
    x = x.at[31].set(x[31] & 0x7F)
    x = x.at[0].add(high * TOP_FOLD)
    x, _ = _carry_i32(x)
    p_e = jnp.reshape(jnp.asarray(_P_LIMBS_I32), (LIMBS,) + (1,) * (a.ndim - 1))
    for _ in range(2):
        d, borrow = _carry_i32(x - p_e)
        ge_p = borrow == 0  # no negative carry out => x >= p
        x = jnp.where(ge_p[None], d, x)
    return x


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field equality (boolean per batch element)."""
    return jnp.all(freeze(a) == freeze(b), axis=0)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(freeze(a) == 0, axis=0)


def select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Per-batch-element select between limb vectors (cond shape = batch)."""
    return jnp.where(cond[None], a, b)


def pow_const(x: jnp.ndarray, exponent: int) -> jnp.ndarray:
    """x ** exponent for a fixed public exponent, via an MSB-first
    square-and-multiply ``lax.scan`` (compiles to a rolled loop — the graph
    stays small regardless of exponent length)."""
    bits = [(exponent >> i) & 1 for i in range(exponent.bit_length())][::-1]
    bits_arr = jnp.asarray(np.array(bits, dtype=np.int32))

    def step(acc, bit):
        acc = square(acc)
        acc = select(bit == 1, mul(acc, x), acc)
        return acc, None

    # First bit is always 1: start from x to save one square+mul.
    acc, _ = limbs.counted_scan(step, x, bits_arr[1:])
    return acc


def invert(x: jnp.ndarray) -> jnp.ndarray:
    """Field inverse via Fermat (x^(p-2)); x=0 maps to 0."""
    return pow_const(x, P - 2)


def _square_n(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """n successive squarings as a rolled scan (one body in the graph)."""
    if n == 1:
        return square(x)
    acc, _ = limbs.counted_scan(lambda a, _: (square(a), None), x, None, length=n)
    return acc


def pow_2_252_m3(x: jnp.ndarray) -> jnp.ndarray:
    """x^(2^252 - 3) — the RFC 8032 decompression square-root exponent
    ((p-5)/8) — via the standard 2^k-1 addition-chain ladder: 251 squarings
    + 11 multiplies.  The generic binary ladder (:func:`pow_const`) pays a
    multiply per *step* (the select evaluates both branches), ~251 of them
    for this exponent — this chain is the decompression hot-path's ~14%
    saving per signature."""
    t0 = square(x)            # x^2
    t1 = _square_n(t0, 2)     # x^8
    t1 = mul(x, t1)           # x^9
    t0 = mul(t0, t1)          # x^11
    t0 = square(t0)           # x^22
    t0 = mul(t1, t0)          # x^31   = x^(2^5 - 1)
    t1 = _square_n(t0, 5)
    t0 = mul(t1, t0)          # 2^10 - 1
    t1 = _square_n(t0, 10)
    t1 = mul(t1, t0)          # 2^20 - 1
    t2 = _square_n(t1, 20)
    t1 = mul(t2, t1)          # 2^40 - 1
    t1 = _square_n(t1, 10)
    t0 = mul(t1, t0)          # 2^50 - 1
    t1 = _square_n(t0, 50)
    t1 = mul(t1, t0)          # 2^100 - 1
    t2 = _square_n(t1, 100)
    t1 = mul(t2, t1)          # 2^200 - 1
    t1 = _square_n(t1, 50)
    t0 = mul(t1, t0)          # 2^250 - 1
    t0 = _square_n(t0, 2)     # 2^252 - 4
    return mul(x, t0)         # 2^252 - 3


#: p as little-endian bytes, for the on-device canonical-encoding check.
P_BYTES_LE = np.frombuffer(P.to_bytes(32, "little"), dtype=np.uint8)


def bytes_lt_p(y_bytes: jnp.ndarray) -> jnp.ndarray:
    """On-device canonical-range check ``y < p`` over ``(32, batch)``
    little-endian byte rows — the fused engine's twin of the host-side
    lexicographic compare in ``models.ed25519._prep_compressed``."""
    return limbs.lt_bytes(
        y_bytes.astype(jnp.int32), jnp.asarray(P_BYTES_LE, dtype=jnp.int32)
    )


__all__ = [
    "LIMBS",
    "LIMB_BITS",
    "P",
    "P_BYTES_LE",
    "bytes_lt_p",
    "D",
    "D2",
    "SQRT_M1",
    "FOLD",
    "int_to_limbs",
    "limbs_to_int",
    "constant",
    "constant_like",
    "from_int_broadcast",
    "zeros_like_batch",
    "add",
    "add_raw",
    "sub",
    "sub_raw",
    "mul",
    "square",
    "freeze",
    "eq",
    "is_zero",
    "select",
    "pow_const",
    "pow_2_252_m3",
    "invert",
]
