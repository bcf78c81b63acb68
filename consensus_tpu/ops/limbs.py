"""Shared limb-vector helpers for the batched field stacks.

Both GF(2^255-19) (:mod:`consensus_tpu.ops.field25519`) and the P-256 field
(:mod:`consensus_tpu.ops.field_p256`) represent elements as 32x8-bit limb
vectors; the exact sequential int32 carry normalization is identical and
lives here so a carry-semantics fix can never diverge between curves.

This module also hosts the **field-multiplication counting shim** that makes
kernel cost models *counted* instead of estimated (PERF.md §5).  The field
stacks report every ``mul``/``square`` through :func:`note_mul` /
:func:`note_square`, weighted by how many independent field elements the op
touches (the batch lanes) and by the length of every enclosing ``lax.scan``
(:func:`counted_scan` — JAX traces a scan body once regardless of trip
count, so the weight stack is what turns a trace into an operation count).
:func:`measure_field_ops` runs a kernel under ``jax.eval_shape`` — abstract
tracing only, no compilation, no device — so a batch-512 A/B costs seconds
on CPU.  When no counter is active every hook is a cheap no-op and
``counted_scan`` degrades to ``jax.lax.scan`` exactly.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


def carry_i32(x: jnp.ndarray, limb_bits: int = 8) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact sequential int32 carry pass over the leading (limb) axis.

    A ``lax.scan`` so the body appears once in the graph instead of one
    unrolled step per limb (freeze shows up ~10x in a verify graph via
    eq/parity checks, so unrolling was a measured compile-time cost).
    Returns ``(normalized limbs, final carry)``; negative inputs borrow
    correctly through the arithmetic right shift.
    """
    mask = (1 << limb_bits) - 1

    def step(carry, limb):
        v = limb + carry
        return v >> limb_bits, v & mask

    carry, out = jax.lax.scan(step, jnp.zeros_like(x[0]), x)
    return out, carry


def lt_bytes(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Little-endian lexicographic ``a < b`` over byte rows.

    ``a`` is ``(n_bytes, batch)``; ``b`` is a ``(n_bytes,)`` constant (a
    modulus bound: ``S < L``, ``y < p``).  Branch-free: locate the most
    significant differing byte with an argmax over the reversed
    difference mask and read both operands there through a one-hot
    contraction (same no-gather idiom as the point-table lookups).
    Equal inputs compare False — the canonical-range checks all exclude
    the bound itself.
    """
    n = a.shape[0]
    b_col = b.astype(a.dtype)[:, None]
    diff = a != b_col  # (n, batch)
    first = jnp.argmax(diff[::-1], axis=0)  # offset of MS difference
    one_hot = (
        jnp.arange(n, dtype=jnp.int32)[:, None] == (n - 1 - first)[None]
    ).astype(a.dtype)
    a_at = (a * one_hot).sum(axis=0)
    b_at = (b_col * one_hot).sum(axis=0)
    return jnp.where(diff.any(axis=0), a_at < b_at, False)


# --------------------------------------------------------------------------
# Field-operation counting shim
# --------------------------------------------------------------------------

#: Active counters (a stack so measurements may nest) and the stack of
#: enclosing-scan trip counts.  Trace-time state only — nothing here is ever
#: captured into a compiled graph.
_COUNTERS: list["FieldOpCount"] = []
_SCAN_WEIGHTS: list[int] = []

#: One squaring costs roughly this many generic multiplications in the
#: schoolbook limb stack (the symmetric half of the product terms).
SQUARE_M_RATIO = 0.55

#: One VPU field multiplication is 32x32 = 1024 byte-level MACs; dense
#: ``dot_general`` MACs convert to mul-equivalents at this rate so the
#: MXU-vs-VPU denominator compares like with like (note_byte_muls already
#: uses the same 1024-MAC yardstick).
DOT_MACS_PER_MUL = 1024


class FieldOpCount:
    """Tally of field operations observed during one traced region.

    ``muls``/``squares`` count semantic field ops on the VPU lane;
    ``dots``/``dot_macs`` count ``dot_general`` contractions (the MXU lane
    dispatches *before* noting, so a trace records muls OR dots per mul
    site, never both); ``adds`` counts field additions/subtractions —
    cheap, but the per-kernel breakdown (satellite of ISSUE 18) wants the
    full shape of the work, not just the expensive tail.
    """

    def __init__(self) -> None:
        self.muls = 0
        self.squares = 0
        self.adds = 0
        self.dots = 0
        self.dot_macs = 0

    @property
    def m_equiv(self) -> float:
        """Generic-multiplication equivalents (1 S ~ 0.55 M; 1024 dense
        dot MACs ~ 1 M — adds are deliberately excluded, matching the
        pinned round-7 baseline semantics)."""
        return (
            self.muls
            + SQUARE_M_RATIO * self.squares
            + self.dot_macs / DOT_MACS_PER_MUL
        )

    def as_dict(self) -> dict:
        """Per-kernel breakdown for bench JSON (muls vs dot-equivalents
        vs adds), so engine PRs inherit the richer denominator for free."""
        return {
            "muls": self.muls,
            "squares": self.squares,
            "adds": self.adds,
            "dots": self.dots,
            "dot_macs": self.dot_macs,
            "dot_m_equiv": round(self.dot_macs / DOT_MACS_PER_MUL, 3),
            "m_equiv": round(self.m_equiv, 3),
        }

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (
            f"FieldOpCount(muls={self.muls}, squares={self.squares}, "
            f"adds={self.adds}, dots={self.dots}, dot_macs={self.dot_macs})"
        )


def counting() -> bool:
    """True while at least one :func:`count_field_ops` region is active."""
    return bool(_COUNTERS)


def _note(attr: str, lanes: int) -> None:
    weight = lanes
    for trip in _SCAN_WEIGHTS:
        weight *= trip
    for counter in _COUNTERS:
        setattr(counter, attr, getattr(counter, attr) + weight)


def note_mul(lanes: int = 1) -> None:
    """Record a field multiplication over ``lanes`` independent elements."""
    if _COUNTERS:
        _note("muls", lanes)


def note_square(lanes: int = 1) -> None:
    """Record a field squaring over ``lanes`` independent elements."""
    if _COUNTERS:
        _note("squares", lanes)


def note_add(lanes: int = 1) -> None:
    """Record a field addition/subtraction over ``lanes`` elements."""
    if _COUNTERS:
        _note("adds", lanes)


def note_dot(m: int, n: int, k: int, lanes: int = 1) -> None:
    """Record a ``dot_general`` contraction of an (m, k) by (k, n) tile
    per lane.  Counted as dense MACs — the MXU does not skip structural
    zeros in a constant operand, so m*n*k is the honest per-lane cost the
    device A/B has to amortize, not the nonzero count."""
    if _COUNTERS:
        _note("dots", lanes)
        _note("dot_macs", m * n * k * lanes)


def note_byte_muls(byte_muls: int, lanes: int = 1) -> None:
    """Record byte-level multiply work in field-mul equivalents.

    The scalar stack (mod-L reduction, coefficient products) multiplies
    byte limbs outside the 32x32 schoolbook shape; 1024 byte products is
    one field mul's worth, rounded up so small stages stay visible in the
    measured cost model."""
    if _COUNTERS:
        _note("muls", max(1, (byte_muls + 1023) // 1024) * lanes)


@contextlib.contextmanager
def count_field_ops():
    """Collect field-op notes emitted while tracing inside this block."""
    counter = FieldOpCount()
    _COUNTERS.append(counter)
    try:
        yield counter
    finally:
        _COUNTERS.remove(counter)


def counted_scan(f, init, xs=None, length=None, **kwargs):
    """``jax.lax.scan`` that weights the body's field-op notes by trip count.

    JAX traces a scan body exactly once, so a naive trace-time tally would
    count a 64-iteration Horner loop as one step.  While a counter is
    active the body runs under a weight equal to the scan length; otherwise
    this is ``jax.lax.scan`` verbatim.
    """
    if not _COUNTERS:
        return jax.lax.scan(f, init, xs, length=length, **kwargs)
    if length is not None:
        trips = int(length)
    else:
        leaves = jax.tree_util.tree_leaves(xs)
        trips = int(leaves[0].shape[0])

    def weighted(carry, x):
        _SCAN_WEIGHTS.append(trips)
        try:
            return f(carry, x)
        finally:
            _SCAN_WEIGHTS.pop()

    return jax.lax.scan(weighted, init, xs, length=length, **kwargs)


def measure_field_ops(fn, *args, **kwargs) -> FieldOpCount:
    """Exact field-op count for one abstract trace of ``fn(*args)``.

    Uses ``jax.eval_shape`` — no compilation, no execution, no device — so
    counting a batch-512 verify kernel takes seconds on any host.  ``fn``
    must be the *unjitted* implementation (a cached jit would skip tracing
    and report zero).  A fresh wrapper busts eval_shape's own trace cache
    each call — without it, measuring the same fn + shapes twice (the
    MXU-vs-VPU A/B does exactly that) silently reports zeros the second
    time.
    """
    with count_field_ops() as counter:
        jax.eval_shape(lambda *a, **k: fn(*a, **k), *args, **kwargs)
    return counter


__all__ = [
    "carry_i32",
    "DOT_MACS_PER_MUL",
    "FieldOpCount",
    "SQUARE_M_RATIO",
    "count_field_ops",
    "counted_scan",
    "counting",
    "lt_bytes",
    "measure_field_ops",
    "note_add",
    "note_byte_muls",
    "note_dot",
    "note_mul",
    "note_square",
]
