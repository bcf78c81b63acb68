"""edwards25519 group operations on batched limb vectors.

Extended homogeneous coordinates (X : Y : Z : T) with x = X/Z, y = Y/Z,
T = XY/Z on the a = -1 twisted Edwards curve -x^2 + y^2 = 1 + d x^2 y^2.
Formulas: add-2008-hwcd-3 (8M) and dbl-2008-hwcd (4M + 4S) — complete for
this curve, so a single code path covers identity/doubling/negatives and
the double-scalar-mult scan needs no data-dependent branches (every step is
double + two selected adds of constant shape, exactly what XLA wants).

Point decompression (RFC 8032 §5.1.3) runs on-device too: the square root
is a fixed-exponent ``pow_const`` chain, so a batch of compressed keys and
R points decompresses in two scans — no per-element host math.

Each formula is written once over a field namespace ``F``: the XLA lane
(:mod:`~consensus_tpu.ops.field25519`) or, inside a Mosaic kernel,
:class:`~consensus_tpu.ops.field25519.VregField`.  Where the field ops
would run as kernels (the TPU, limb-major whole-vreg elements), ``add`` /
``double`` / ``add_affine`` run as ONE kernel each instead: the raw sums
between the multiplies stay in vector registers (PERF.md section 6: on a
TPU v5e a 2,048-lane verify launch took 6.48 ms by the host clock, against
7.24 with a kernel a field op and 16.75 in XLA).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax.numpy as jnp

from consensus_tpu.ops import field25519 as fe
from consensus_tpu.ops import limbs
from consensus_tpu.ops import mosaic25519 as mosaic

# Base point of edwards25519 (RFC 8032).
_BY = (4 * pow(5, fe.P - 2, fe.P)) % fe.P
_BX = 15112221349535400772501151409588531511454012693041857206046113283949847762202


class Point(NamedTuple):
    """Batched point in extended coordinates; each field is a (32, *batch)
    float32 limb vector."""

    x: jnp.ndarray
    y: jnp.ndarray
    z: jnp.ndarray
    t: jnp.ndarray


def identity(batch_shape) -> Point:
    return Point(
        x=fe.zeros_like_batch(batch_shape),
        y=fe.from_int_broadcast(1, batch_shape),
        z=fe.from_int_broadcast(1, batch_shape),
        t=fe.zeros_like_batch(batch_shape),
    )


def base_point(batch_shape) -> Point:
    return Point(
        x=fe.from_int_broadcast(_BX, batch_shape),
        y=fe.from_int_broadcast(_BY, batch_shape),
        z=fe.from_int_broadcast(1, batch_shape),
        t=fe.from_int_broadcast(_BX * _BY % fe.P, batch_shape),
    )


def identity_like(ref: jnp.ndarray) -> Point:
    """Identity point inheriting ``ref``'s (20, *batch) shape *and* sharding
    variance — required as a scan carry under ``shard_map`` (a broadcast
    constant would be 'unvarying' and fail the carry type check)."""
    return Point(
        x=ref * 0,
        y=fe.constant_like(1, ref),
        z=fe.constant_like(1, ref),
        t=ref * 0,
    )


def base_point_like(ref: jnp.ndarray) -> Point:
    return Point(
        x=fe.constant_like(_BX, ref),
        y=fe.constant_like(_BY, ref),
        z=fe.constant_like(1, ref),
        t=fe.constant_like(_BX * _BY % fe.P, ref),
    )


def negate(p: Point) -> Point:
    zero = p.x * 0
    return Point(x=fe.sub(zero, p.x), y=p.y, z=p.z, t=fe.sub(zero, p.t))


_D2 = fe.D2


def _add(F, p: Point, q: Point) -> Point:
    """add-2008-hwcd-3: 8M + 1 constant mul, over the field namespace ``F``
    (:mod:`~consensus_tpu.ops.field25519`, or
    :class:`~consensus_tpu.ops.field25519.VregField` inside a kernel).

    Every intermediate add/sub stays *unreduced* (one raw level, limb bound
    600/680) and feeds straight into a multiplication — all operand-bound
    products stay under the 2^19 exactness budget, so the formula needs no
    carry passes outside the multiplies themselves."""
    a = F.mul(F.sub_raw(p.y, p.x), F.sub_raw(q.y, q.x))
    b = F.mul(F.add_raw(p.y, p.x), F.add_raw(q.y, q.x))
    c = F.mul(F.mul(p.t, F.constant_like(_D2, p.t)), q.t)
    d = F.mul(F.add_raw(p.z, p.z), q.z)
    e = F.sub_raw(b, a)
    f = F.sub_raw(d, c)
    g = F.add_raw(d, c)
    h = F.add_raw(b, a)
    return Point(x=F.mul(e, f), y=F.mul(g, h), z=F.mul(f, g), t=F.mul(e, h))


def _double(F, p: Point, need_t: bool) -> Point:
    """dbl-2008-hwcd: 4M + 4S (3M + 4S without T) over ``F``.

    Lazy-reduction layout: A/B/ZZ use the half-cost specialized squaring
    (inputs weakly reduced), C/H/G/XY stay raw; only E and F — whose raw
    bounds would overflow the multiply budget — get reduced."""
    a = F.square(p.x)
    b = F.square(p.y)
    zz = F.square(p.z)
    c = F.add_raw(zz, zz)           # <= 680
    h = F.add_raw(a, b)             # <= 680
    xy = F.add_raw(p.x, p.y)        # <= 680: square() bound is 500 -> mul
    e = F.sub(h, F.mul(xy, xy))     # reduced: raw h - weak square
    g = F.sub_raw(a, b)             # <= 600
    f = F.add(c, g)                 # reduced: 680 + 600 would exceed 724
    t = F.mul(e, h) if need_t else p.t
    return Point(x=F.mul(e, f), y=F.mul(g, h), z=F.mul(f, g), t=t)


def _add_affine(F, p: Point, q_x, q_y, q_t) -> Point:
    """madd-2008-hwcd-3 over ``F``: 7M + 1 constant mul (the D = 2 Z1 Z2
    multiply degenerates to a raw doubling of p.z)."""
    a = F.mul(F.sub_raw(p.y, p.x), F.sub_raw(q_y, q_x))
    b = F.mul(F.add_raw(p.y, p.x), F.add_raw(q_y, q_x))
    c = F.mul(F.mul(p.t, F.constant_like(_D2, p.t)), q_t)
    d = F.add_raw(p.z, p.z)
    e = F.sub_raw(b, a)
    f = F.sub_raw(d, c)
    g = F.add_raw(d, c)
    h = F.add_raw(b, a)
    return Point(x=F.mul(e, f), y=F.mul(g, h), z=F.mul(f, g), t=F.mul(e, h))


# Kernel bodies: the formulas above on the coordinates a kernel was handed.
def _add_body(F, x1, y1, z1, t1, x2, y2, z2, t2):
    return _add(F, Point(x1, y1, z1, t1), Point(x2, y2, z2, t2))


def _double_body(F, x, y, z):
    return _double(F, Point(x, y, z, None), True)


def _double_xyz_body(F, x, y, z):
    return _double(F, Point(x, y, z, None), False)[:3]


def _add_affine_body(F, x, y, z, t, q_x, q_y, q_t):
    return _add_affine(F, Point(x, y, z, t), q_x, q_y, q_t)


def _on_mosaic(*coords) -> bool:
    """A point op runs as ONE kernel where its field ops would each run as
    one (:func:`consensus_tpu.ops.mosaic25519.active`), after the MXU lane
    (which keeps the XLA formula) and never while the counting shim traces
    (the XLA formula notes each field op it makes)."""
    from consensus_tpu.ops import mxu_limbs

    return (
        not limbs.counting()
        and not mxu_limbs.lane_active()
        and mosaic.active(*coords)
    )


def add(p: Point, q: Point) -> Point:
    """p + q, complete (:func:`_add`); one kernel on the Mosaic path."""
    if _on_mosaic(*p, *q):
        return Point(*mosaic.run(_add_body, 4, *p, *q))
    return _add(fe, p, q)


def double(p: Point, *, need_t: bool = True) -> Point:
    """2p (:func:`_double`); ``need_t=False`` skips T (doubling never reads
    it, so runs of doubles only need it at the end).  One kernel on the
    Mosaic path."""
    if _on_mosaic(p.x, p.y, p.z):
        if need_t:
            return Point(*mosaic.run(_double_body, 4, p.x, p.y, p.z))
        x, y, z = mosaic.run(_double_xyz_body, 3, p.x, p.y, p.z)
        return Point(x, y, z, p.t)
    return _double(fe, p, need_t)


def select(cond: jnp.ndarray, p: Point, q: Point) -> Point:
    """Per-element point select (cond shape = batch)."""
    return Point(
        x=fe.select(cond, p.x, q.x),
        y=fe.select(cond, p.y, q.y),
        z=fe.select(cond, p.z, q.z),
        t=fe.select(cond, p.t, q.t),
    )


def conditional_add(p: Point, q: Point, bit: jnp.ndarray) -> Point:
    """p + q where bit is set, else p — constant work either way."""
    return select(bit == 1, add(p, q), p)


def decompress(y_limbs: jnp.ndarray, sign: jnp.ndarray) -> tuple[Point, jnp.ndarray]:
    """Recover (x, y) from a compressed point's y limbs + x sign bit.

    Returns (point with Z=1, valid mask).  RFC 8032 §5.1.3: x^2 = (y^2-1) /
    (d y^2 + 1); candidate root x = u v^3 (u v^7)^((p-5)/8), fixed up by
    sqrt(-1) when v x^2 == -u, rejected when neither matches.
    """
    one = fe.constant_like(1, y_limbs)
    y2 = fe.square(y_limbs)
    u = fe.sub(y2, one)
    v = fe.add(fe.mul(fe.constant_like(fe.D, y_limbs), y2), one)

    v3 = fe.mul(fe.square(v), v)
    v7 = fe.mul(fe.square(v3), v)
    x = fe.mul(fe.mul(u, v3), fe.pow_2_252_m3(fe.mul(u, v7)))

    vx2 = fe.mul(v, fe.square(x))
    root_ok = fe.eq(vx2, u)
    neg_u = fe.sub(u * 0, u)
    root_neg = fe.eq(vx2, neg_u)
    x_fixed = fe.mul(x, fe.constant_like(fe.SQRT_M1, y_limbs))
    x = fe.select(root_neg, x_fixed, x)
    valid = root_ok | root_neg

    x_frozen = fe.freeze(x)
    x_is_zero = jnp.all(x_frozen == 0, axis=0)
    # x = 0 with sign bit set is invalid; u = 0 with x = 0 is the valid y=±1.
    valid = valid & ~(x_is_zero & (sign == 1))
    # Match the requested sign: x and p - x have opposite parities.
    parity = x_frozen[0] & 1
    x = fe.select((parity != sign) & ~x_is_zero, fe.sub(x * 0, x), x)

    return Point(x=x, y=y_limbs, z=one, t=fe.mul(x, y_limbs)), valid


def equal(p: Point, q: Point) -> jnp.ndarray:
    """Projective equality: X1 Z2 == X2 Z1 and Y1 Z2 == Y2 Z1."""
    return fe.eq(fe.mul(p.x, q.z), fe.mul(q.x, p.z)) & fe.eq(
        fe.mul(p.y, q.z), fe.mul(q.y, p.z)
    )


def is_identity(p: Point) -> jnp.ndarray:
    """True where p is the neutral element: X = 0 and Y = Z.

    Complete for curve points — the only points with X = 0 are (0, 1)
    (identity) and the order-2 torsion point (0, -1), and Y = Z rejects the
    latter.  No multiplies, so cheaper than :func:`equal` against identity."""
    return fe.is_zero(p.x) & fe.eq(p.y, p.z)


# --- windowed scalar-mult support -----------------------------------------


def _edwards_add_int(p1, p2):
    """Host-side integer point addition (affine) for constant-table gen."""
    x1, y1 = p1
    x2, y2 = p2
    P_, D_ = fe.P, fe.D
    denom_x = (1 + D_ * x1 * x2 * y1 * y2) % P_
    denom_y = (1 - D_ * x1 * x2 * y1 * y2) % P_
    x3 = (x1 * y2 + x2 * y1) * pow(denom_x, P_ - 2, P_) % P_
    y3 = (y1 * y2 + x1 * x2) * pow(denom_y, P_ - 2, P_) % P_
    return x3, y3


def base_point_table_ints(size: int = 16) -> list[tuple[int, int]]:
    """Affine (x, y) for j*B, j = 0..size-1 (identity first)."""
    table = [(0, 1)]
    for _ in range(size - 1):
        table.append(_edwards_add_int(table[-1], (_BX, _BY)))
    return table


_COMB_WINDOWS = 32
_COMB_BITS = 8


def _extended_add_int(p1, p2):
    """Host-side integer point addition in extended coordinates
    (X, Y, Z, T), add-2008-hwcd-3: complete on this curve (identity and
    doubling included) and free of inversions."""
    x1, y1, z1, t1 = p1
    x2, y2, z2, t2 = p2
    P_ = fe.P
    a = (y1 - x1) * (y2 - x2) % P_
    b = (y1 + x1) * (y2 + x2) % P_
    c = t1 * _D2 % P_ * t2 % P_
    d = 2 * z1 * z2 % P_
    e, f, g, h = b - a, d - c, d + c, b + a
    return e * f % P_, g * h % P_, f * g % P_, e * h % P_


def _batch_inverse_int(values: list[int]) -> list[int]:
    """Modular inverses of ``values`` (none zero) for ONE exponentiation:
    Montgomery's trick, prefix products up and back down."""
    P_ = fe.P
    prefix = [1]
    for v in values:
        prefix.append(prefix[-1] * v % P_)
    inv = pow(prefix[-1], P_ - 2, P_)
    out = [0] * len(values)
    for i in range(len(values) - 1, -1, -1):
        out[i] = inv * prefix[i] % P_
        inv = inv * values[i] % P_
    return out


@functools.lru_cache(maxsize=1)
def _comb_table_np() -> tuple["np.ndarray", "np.ndarray", "np.ndarray"]:
    """Fixed-base comb: affine (x, y, t=xy) limb arrays of shape
    (32 windows, 256 entries, 32 limbs) with ``T[j][d] = d * 2^(8j) * B``.

    B is a compile-time constant, so [S]B needs NO doubles and NO per-batch
    table build: 32 constant-table lookups + 31 adds, vs riding the shared
    Horner scan (64 table adds).  Host-side integer precompute, cached for
    the process (the arrays are baked into the jitted graph as constants):
    the 8,192 entries are chained in extended coordinates and brought to
    affine by one batched inversion — a modular inversion an entry, as
    :func:`_edwards_add_int` would pay, is seconds of every process start
    that traces a verify kernel."""
    import numpy as np

    entries = []
    window_base = (_BX, _BY, 1, _BX * _BY % fe.P)  # 2^(8j) * B
    for _ in range(_COMB_WINDOWS):
        entry = (0, 1, 1, 0)  # identity
        for _ in range(1 << _COMB_BITS):
            entries.append(entry)
            entry = _extended_add_int(entry, window_base)
        for _ in range(_COMB_BITS):
            window_base = _extended_add_int(window_base, window_base)
    xs = np.zeros((len(entries), fe.LIMBS), dtype=np.float32)
    ys = np.zeros_like(xs)
    ts = np.zeros_like(xs)
    z_inverses = _batch_inverse_int([z for _, _, z, _ in entries])
    for i, ((x, y, _, _), z_inv) in enumerate(zip(entries, z_inverses)):
        x, y = x * z_inv % fe.P, y * z_inv % fe.P
        xs[i] = fe.int_to_limbs(x)
        ys[i] = fe.int_to_limbs(y)
        ts[i] = fe.int_to_limbs(x * y % fe.P)
    shape = (_COMB_WINDOWS, 1 << _COMB_BITS, fe.LIMBS)
    return xs.reshape(shape), ys.reshape(shape), ts.reshape(shape)


def add_affine(p: Point, q_x: jnp.ndarray, q_y: jnp.ndarray, q_t: jnp.ndarray) -> Point:
    """Mixed addition p + q with q affine (Z=1, T=XY given):
    :func:`_add_affine`, one kernel on the Mosaic path.  Same lazy-reduction
    discipline as :func:`add`."""
    if _on_mosaic(*p, q_x, q_y, q_t):
        return Point(*mosaic.run(_add_affine_body, 4, *p, q_x, q_y, q_t))
    return _add_affine(fe, p, q_x, q_y, q_t)


def fixed_base_mul_comb(s_digits8: jnp.ndarray) -> Point:
    """[S]B from 8-bit window digits ``s_digits8`` of shape (32, *batch),
    LSB window first: one constant-table lookup + one mixed add per window,
    zero doubles.  The lookups are one-hot contractions against broadcast
    constants — they lower to (256 x 128) x batch matmuls (MXU work), while
    the adds stay on the VPU."""
    xs, ys, ts = _comb_table_np()
    unit = (None,) * (s_digits8.ndim - 1)  # one per batch axis
    lanes = jnp.arange(1 << _COMB_BITS, dtype=jnp.int32)[(slice(None),) + unit]

    # Stack the per-window tables as scan inputs, limbs trailing the entry
    # axis: (32, 256, 32limbs, 1, ...) broadcasting against (256, *batch)
    # one-hots.
    def coords(arr) -> jnp.ndarray:
        return jnp.asarray(arr)[(Ellipsis,) + unit]

    def step(acc: Point, inputs):
        digits, tx, ty, tt = inputs  # (*batch), (256, 32, 1, ...) x3
        oh = (digits[None] == lanes).astype(jnp.float32)  # (256, *batch)

        def pick(tbl: jnp.ndarray) -> jnp.ndarray:
            return jnp.sum(tbl * oh[:, None], axis=0)  # (32, *batch)

        return add_affine(acc, pick(tx), pick(ty), pick(tt)), None

    # The (32, batch)-shaped digit array doubles as the identity's shape /
    # sharding-variance reference (it IS (LIMBS, batch)).
    ref = s_digits8.astype(jnp.float32)
    acc, _ = limbs.counted_scan(
        step, identity_like(ref), (s_digits8, coords(xs), coords(ys), coords(ts))
    )
    return acc


def table_lookup(table: Point, one_hot: jnp.ndarray) -> Point:
    """Select table[digit] per batch element via a one-hot contraction —
    pure VPU multiply-adds, no gather (TPU gathers serialize).

    ``table`` coords are (W, 32, *batch) or (W, 32, 1); ``one_hot`` is
    (W, *batch) float32."""
    oh = one_hot[:, None]  # (W, 1, *batch)

    def pick(coord: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(coord * oh, axis=0)

    return Point(x=pick(table.x), y=pick(table.y), z=pick(table.z), t=pick(table.t))


def multiples_table(p: Point, size: int = 16) -> Point:
    """j*p for j = 0..size-1, coords stacked on a leading axis (identity
    first, so digit 0 adds the neutral element — the unified formulas make
    that a plain add, no branch).

    Built with a ``lax.scan`` so the add formula appears ONCE in the graph
    regardless of table size — inlining size-2 point adds was a measured
    chunk of the kernel's trace+compile time."""

    def step(prev: Point, _):
        nxt = add(prev, p)
        return nxt, nxt

    _, rest = limbs.counted_scan(step, p, None, length=size - 2)
    ident = identity_like(p.x)
    return Point(
        x=jnp.concatenate([ident.x[None], p.x[None], rest.x]),
        y=jnp.concatenate([ident.y[None], p.y[None], rest.y]),
        z=jnp.concatenate([ident.z[None], p.z[None], rest.z]),
        t=jnp.concatenate([ident.t[None], p.t[None], rest.t]),
    )


def multiples_table9(p: Point) -> Point:
    """j*p for j = 0..8 (the signed-4-bit window table), laid out exactly
    like ``multiples_table(p, 9)`` but built cheaper: even multiples come
    from doublings (4M+4S each, one of them vectorized over a trailing
    entry axis) instead of riding the sequential add chain — 3 adds + 4
    doubled lanes (43M + 16S) vs 7 adds (63M).  Worth the extra graph
    bodies in the randomized batch kernel, which builds TWO tables (A and
    R) per launch."""
    p2 = double(p)

    def step(prev: Point, _):
        nxt = add(prev, p2)
        return nxt, nxt

    # Odd chain 3p, 5p, 7p: one add body in the graph.
    _, odd = limbs.counted_scan(step, p, None, length=3)
    p3 = Point(*(c[0] for c in odd))
    p5 = Point(*(c[1] for c in odd))
    p7 = Point(*(c[2] for c in odd))
    # 4p, 6p = one double of (2p, 3p) stacked on a trailing entry axis.
    pair = double(Point(*(jnp.stack([a, b], axis=-1) for a, b in zip(p2, p3))))
    p4 = Point(*(c[..., 0] for c in pair))
    p6 = Point(*(c[..., 1] for c in pair))
    p8 = double(p4)
    entries = [identity_like(p.x), p, p2, p3, p4, p5, p6, p7, p8]
    return Point(
        *(
            jnp.concatenate([getattr(q, coord)[None] for q in entries])
            for coord in ("x", "y", "z", "t")
        )
    )


# --- shared-doubling batch multi-scalar multiplication --------------------


def batch_sum(p: Point) -> Point:
    """Sum a point batch down to batch 1 over the trailing axis.

    A binary halving tree: every level is ONE vectorized add over half the
    remaining lanes (odd widths carry their last lane to the next level), so
    n lanes cost n-1 adds in log2(n) full-width ops — the reduction shape
    the VPU wants, vs a sequential fold's n dependent adds."""
    n = p.x.shape[-1]

    def half_slice(coord: jnp.ndarray, lo: int, hi: int) -> jnp.ndarray:
        return coord[..., lo:hi]

    while n > 1:
        half = n // 2
        head = add(
            Point(*(half_slice(c, 0, half) for c in p)),
            Point(*(half_slice(c, half, 2 * half) for c in p)),
        )
        if n % 2:
            p = Point(
                *(
                    jnp.concatenate([hc, c[..., 2 * half :]], axis=-1)
                    for hc, c in zip(head, p)
                )
            )
        else:
            p = head
        n = half + (n % 2)
    return p


def _signed_window_contribution(table: Point, digits_row: jnp.ndarray) -> Point:
    """Per-lane table[|d|] with sign applied, from one row of encoded
    signed-4-bit digits (stored as d + 8, so 8 means digit 0 -> identity)."""
    size = table.x.shape[0]
    lanes = jnp.arange(size, dtype=jnp.int32)[:, None]
    d = digits_row.astype(jnp.int32) - 8
    oh = (jnp.abs(d)[None] == lanes).astype(jnp.float32)
    picked = table_lookup(table, oh)
    return select(d < 0, negate(picked), picked)


def straus_shared_msm(
    a_table: Point,
    r_table: Point,
    zk_digits: jnp.ndarray,
    z_digits: jnp.ndarray,
) -> Point:
    """Σᵢ [zkᵢ]Aᵢ' + Σᵢ [zᵢ]Rᵢ' with ONE doubling chain for the whole batch.

    ``a_table``/``r_table`` are per-signature multiples tables (9, 32limbs,
    batch) of the (already negated) points; ``zk_digits`` is (64, batch) and
    ``z_digits`` (Wz, batch), both signed-4-bit recodings stored as d + 8,
    MSB window first.  The accumulator has batch shape (1,): each window
    costs 4 doubles of that single lane, then every signature's looked-up
    contribution is folded in via :func:`batch_sum` — so the 256-bit
    double chain (the ~2,000 M/sig wall for independent verification) is
    paid once per batch, not once per signature.

    Because z < 2^128 its high windows are all zero, the scan runs in two
    phases — ``64 - Wz`` A-only windows, then ``Wz`` combined windows —
    instead of padding z to 64 rows of dead lookups/adds."""
    n_low = z_digits.shape[0]
    n_high = zk_digits.shape[0] - n_low
    acc0 = identity_like(a_table.x[0][..., :1])  # (32limbs, 1)

    def quad_double(acc: Point) -> Point:
        acc, _ = limbs.counted_scan(
            lambda a, _: (double(a, need_t=False), None), acc, None, length=3
        )
        return double(acc)  # final double materializes T for the next add

    def step_high(acc: Point, zk_row):
        acc = quad_double(acc)
        contrib = _signed_window_contribution(a_table, zk_row)
        return add(acc, batch_sum(contrib)), None

    def step_low(acc: Point, rows):
        zk_row, z_row = rows
        acc = quad_double(acc)
        contrib = add(
            _signed_window_contribution(a_table, zk_row),
            _signed_window_contribution(r_table, z_row),
        )
        return add(acc, batch_sum(contrib)), None

    acc, _ = limbs.counted_scan(step_high, acc0, zk_digits[:n_high])
    acc, _ = limbs.counted_scan(step_low, acc, (zk_digits[n_high:], z_digits))
    return acc


__all__ = [
    "Point",
    "identity",
    "identity_like",
    "base_point",
    "base_point_like",
    "negate",
    "add",
    "double",
    "select",
    "conditional_add",
    "decompress",
    "equal",
    "is_identity",
    "base_point_table_ints",
    "table_lookup",
    "multiples_table",
    "multiples_table9",
    "add_affine",
    "fixed_base_mul_comb",
    "batch_sum",
    "straus_shared_msm",
]
