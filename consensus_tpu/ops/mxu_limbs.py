"""MXU-lane field multiplication: limb products as integer ``dot_general``
tiles (ROADMAP item 3; the ``CTPU_MXU_LIMBS=1`` backend).

The VPU lane (:mod:`consensus_tpu.ops.field25519`,
:mod:`consensus_tpu.ops.field_p256`) lowers schoolbook limb multiplication
to 32 broadcast multiplies + shifted column adds — elementwise work the
MXU never sees.  This module expresses the SAME arithmetic as two integer
contractions the MXU can tile:

1. a batched outer product ``P[n, i, j] = a_i(n) * b_j(n)`` via
   ``lax.dot_general`` over ``int16`` limb tiles with
   ``preferred_element_type=int32`` (operands are weakly reduced or one
   raw level, |limb| <= 680, so products stay <= 680^2 = 462,400 — exact
   in int32, and int16 holds the operands with 48x headroom);
2. a contraction of the flattened products against a constant (63, 1024)
   0/1 **column-assembly matrix** ``C[c, 32i+j] = [i + j == c]`` — the
   schoolbook convolution as one (63 x 1024) x (1024 x batch) integer
   matmul with a shared constant operand (the shape the MXU needs to
   win: reuse across the batch, not per-lane elementwise work).  Column
   sums are <= 32 * 462,400 < 2^24 — the same bound the f32 lane proves.

Reduction mod p is fused into the same tile as an **int32-domain mirror**
of the f32 lane's carry-save passes: arithmetic ``>> 8`` is exactly
``floor(x / 256)`` for negatives, so every intermediate integer equals the
f32 lane's value and the final weakly-reduced limbs are **bit-identical**
to the VPU lane after the f32 cast (|limb| <= 340 / ~300 — exact in f32).
Squaring dispatches through ``mul(a, a)``: the full product columns equal
the VPU square's diagonal-plus-doubled-cross columns as integers, so the
reduced output is bit-identical to the specialized VPU square as well.

Deliberately NOT done: folding the mod-p reduction into the assembly
matrix (e.g. columns 32..62 re-entering at weight 38).  That would change
the intermediate limb representation and void every bounds analysis the
curve formulas' lazy-reduction budget rests on; the mirror keeps the two
lanes byte-identical at every step instead.

Lane selection is **trace-time**: the field stacks consult
:func:`lane_active` inside ``mul``/``square``, so a process opts in with
``CTPU_MXU_LIMBS=1`` (read per trace — already-compiled shapes keep their
lane) and bench A/Bs flip lanes in-process with :func:`force_mxu_limbs` /
:func:`suppress_mxu_limbs` around fresh jits.

Counting: the shim (:mod:`consensus_tpu.ops.limbs`) records this lane's
work through :func:`~consensus_tpu.ops.limbs.note_dot` as dense MACs —
the outer product is 1024 MACs/lane and the column assembly 63 * 1024 =
64,512 MACs/lane, ~64x the VPU lane's useful multiplies.  That ratio is
the honest price of dense tiling (the MXU does not skip the zeros in C);
PERF.md §5 records it as the counted denominator a device A/B must beat
with systolic-array throughput.
"""

from __future__ import annotations

import contextlib
import functools
import os

import numpy as np

import jax
import jax.numpy as jnp

from consensus_tpu.ops import limbs

LIMBS = 32
_COLS = 2 * LIMBS - 1  # 63 schoolbook columns

#: curve25519 fold weights (mirrors field25519.FOLD / TOP_FOLD).
_FOLD = 38
_TOP_FOLD = 19

#: Trace-time lane overrides (module globals, mutated only under the
#: context managers below).
_FORCED = False
_SUPPRESSED = False


def lane_active() -> bool:
    """True when field ``mul``/``square`` should trace the MXU lane.

    Checked per trace by the field stacks; already-compiled shapes keep
    whichever lane they were traced under.  Suppression wins over forcing.
    """
    if _SUPPRESSED:
        return False
    if _FORCED:
        return True
    return os.environ.get("CTPU_MXU_LIMBS", "") == "1"


@contextlib.contextmanager
def force_mxu_limbs():
    """Trace the MXU lane inside this block regardless of the environment
    (bench in-process A/B: an env flip cannot retrace already-cached
    shapes, a fresh jit under this context can)."""
    global _FORCED
    prev = _FORCED
    _FORCED = True
    try:
        yield
    finally:
        _FORCED = prev


@contextlib.contextmanager
def suppress_mxu_limbs():
    """Trace the VPU lane inside this block regardless of the environment
    (the bench A/B's control arm)."""
    global _SUPPRESSED
    prev = _SUPPRESSED
    _SUPPRESSED = True
    try:
        yield
    finally:
        _SUPPRESSED = prev


@functools.lru_cache(maxsize=1)
def _conv_matrix() -> np.ndarray:
    """(63, 1024) 0/1 column-assembly matrix: C @ flatten(outer(a, b))
    yields the schoolbook convolution columns.  int8 at rest (the MXU's
    native integer operand width); cast to int32 at the contraction."""
    c = np.zeros((_COLS, LIMBS * LIMBS), dtype=np.int8)
    for i in range(LIMBS):
        for j in range(LIMBS):
            c[i + j, LIMBS * i + j] = 1
    return c


def _schoolbook_columns(a: jnp.ndarray, b: jnp.ndarray):
    """Exact int32 schoolbook columns of a * b as two MXU contractions.

    Returns ``(cols, batch_shape)`` with ``cols`` of shape
    ``(63, *batch)`` — integer-identical to the f32 lane's
    ``sum(padded terms)``.  Operands must satisfy the field stacks' lazy
    budget (|a_limb| * |b_limb| <= 2^19), which also bounds them inside
    int16.
    """
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)
    batch_shape = shape[1:]
    lanes = 1
    for dim in batch_shape:
        lanes *= int(dim)

    a16 = jnp.reshape(a, (LIMBS, lanes)).T.astype(jnp.int16)  # (B, 32)
    b16 = jnp.reshape(b, (LIMBS, lanes)).T.astype(jnp.int16)
    # Batched outer product: one rank-1 matmul per lane, int32 accumulation
    # (the products themselves overflow int16).
    outer = jax.lax.dot_general(
        a16[:, :, None],
        b16[:, None, :],
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )  # (B, 32, 32)
    # Column assembly: (63, 1024) x (1024, B) — the constant operand is
    # shared across the whole batch, the reuse shape the MXU wants.
    cols = jax.lax.dot_general(
        jnp.asarray(_conv_matrix(), dtype=jnp.int32),
        jnp.reshape(outer, (lanes, LIMBS * LIMBS)),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (63, B)
    if limbs.counting():
        limbs.note_dot(LIMBS, LIMBS, 1, lanes)          # outer products
        limbs.note_dot(_COLS, 1, LIMBS * LIMBS, lanes)  # column assembly
    return jnp.reshape(cols, (_COLS,) + batch_shape), batch_shape


# --- int32 mirrors of the f32 reductions (bit-identical by construction) ---


def _split_i32(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """int32 twin of the f32 ``_split``: arithmetic >> 8 IS floor(x/256)."""
    hi = x >> 8
    return x - (hi << 8), hi


def _relax_i32(x: jnp.ndarray) -> jnp.ndarray:
    lo, hi = _split_i32(x)
    rolled = jnp.concatenate([hi[LIMBS - 1 :] * _FOLD, hi[: LIMBS - 1]], axis=0)
    return lo + rolled


def _top_fold_i32(x: jnp.ndarray) -> jnp.ndarray:
    high = x[LIMBS - 1] >> 7
    return jnp.concatenate(
        [
            (x[0] + high * _TOP_FOLD)[None],
            x[1 : LIMBS - 1],
            (x[LIMBS - 1] - high * 128)[None],
        ],
        axis=0,
    )


def _weak_reduce_i32(x: jnp.ndarray) -> jnp.ndarray:
    x = _relax_i32(x)
    x = _relax_i32(x)
    x = _relax_i32(x)
    return _top_fold_i32(x)


def _reduce_cols_i32(cols: jnp.ndarray) -> jnp.ndarray:
    """int32 mirror of field25519._reduce_cols: same integers every step."""
    lo, hi = _split_i32(cols)
    c = jnp.concatenate([lo[:1], lo[1:] + hi[:-1], hi[-1:]], axis=0)  # width 64
    r = c[:LIMBS] + c[LIMBS:] * _FOLD
    return _weak_reduce_i32(r)


def mul25519(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """GF(2^255-19) multiplication on the MXU lane — bit-identical output
    to :func:`consensus_tpu.ops.field25519.mul` (weakly reduced,
    |limb| <= 340, exact in the f32 cast)."""
    cols, _ = _schoolbook_columns(a, b)
    return _reduce_cols_i32(cols).astype(jnp.float32)


def square25519(a: jnp.ndarray) -> jnp.ndarray:
    """MXU squaring = ``mul25519(a, a)``: the full product columns equal
    the VPU square's diagonal + doubled-cross columns as integers, so the
    output is bit-identical to the specialized square (and valid over its
    whole |limb| <= 500 domain, with margin to 680)."""
    return mul25519(a, a)


# --- P-256 (Solinas) reduction mirror --------------------------------------


@functools.lru_cache(maxsize=1)
def _solinas_i32() -> np.ndarray:
    """field_p256's (32, 64) Solinas matrix as exact int32 (entries are
    integers with |m| <= 4, so the f32 -> int32 cast is lossless).  Built
    from ``fp._solinas_matrix()``, not read from the ``fp._SOLINAS_M``
    global."""
    from consensus_tpu.ops import field_p256 as fp

    return np.asarray(fp._solinas_matrix(), dtype=np.int32)


def _reduce_wide_i32(x: jnp.ndarray) -> jnp.ndarray:
    """int32 mirror of field_p256._reduce_wide: carry-save, Solinas matrix
    contraction (integer dot — no Precision knob needed, unlike the f32
    lane's HIGHEST-precision tensordot), two light fold rounds."""
    from consensus_tpu.ops import field_p256 as fp

    batch_pad = [(0, 0)] * (x.ndim - 1)
    if x.shape[0] > _COLS:
        raise ValueError(f"input too wide: {x.shape[0]}")
    if x.shape[0] < _COLS:
        x = jnp.pad(x, [(0, _COLS - x.shape[0])] + batch_pad)
    lo, hi = _split_i32(x)
    x = jnp.pad(lo, [(0, 1)] + batch_pad) + jnp.pad(hi, [(1, 0)] + batch_pad)

    lanes = 1
    for dim in x.shape[1:]:
        lanes *= int(dim)
    r = jnp.tensordot(jnp.asarray(_solinas_i32()), x, axes=([1], [0]))
    if limbs.counting():
        limbs.note_dot(LIMBS, 1, 2 * LIMBS, lanes)

    for _ in range(2):
        lo, hi = _split_i32(r)
        carried = (
            jnp.pad(lo, [(0, 1)] + batch_pad) + jnp.pad(hi, [(1, 0)] + batch_pad)
        )
        r = carried[:LIMBS]
        top = carried[LIMBS]
        for pos, sign in fp._FOLD_PATTERN:
            r = r.at[pos].add(sign * top)
    return r


def mul_p256(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """GF(p256) multiplication on the MXU lane — bit-identical output to
    :func:`consensus_tpu.ops.field_p256.mul`."""
    cols, _ = _schoolbook_columns(a, b)
    return _reduce_wide_i32(cols).astype(jnp.float32)


def square_p256(a: jnp.ndarray) -> jnp.ndarray:
    """MXU P-256 squaring via ``mul_p256(a, a)`` (same column-integer
    argument as :func:`square25519`)."""
    return mul_p256(a, a)


__all__ = [
    "lane_active",
    "force_mxu_limbs",
    "suppress_mxu_limbs",
    "mul25519",
    "square25519",
    "mul_p256",
    "square_p256",
]
