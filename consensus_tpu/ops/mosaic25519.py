"""GF(2^255-19) and edwards25519 arithmetic as Mosaic (Pallas TPU) kernels.

The XLA lane of :mod:`consensus_tpu.ops.field25519` writes a field
multiply as 32 padded broadcast products and four carry passes built from
``concatenate``\\ s of shifted one-limb slices; XLA on the TPU does not
fuse the pads and slices, so one ``mul`` at 2,048 lanes compiled to 15
fusions writing 12 MB through VMEM, and one step of the verify kernel's
Horner scan to 1,015 fusions (PERF.md section 5).  Here the same
arithmetic runs inside ONE kernel per call: every limb is read once into
vector registers, the 63 product columns, the fold and the carry passes
are straight-line vector code, and only the 32 result limbs go back.

Layout: an element is **limb-major**, ``(32, rows, 128)`` float32, so one
limb of 1,024 lanes is one whole ``(8, 128)`` vreg and every limb index in
a kernel is static.  A kernel walks ``rows`` in blocks of 8 (a grid of
``rows // 8`` steps).  :func:`launch_path` is the rule that gives a verify
launch that layout (the TPU, 1,024 lanes or more); :func:`active` is the
dispatch the field and point ops ask at trace time: the TPU backend, and
elements of that shape.

Inside a kernel an element is a list of 32 ``(8, 128)`` arrays and the
field is :class:`consensus_tpu.ops.field25519.VregField`, the XLA lane's
operations on that list under the same names, so the point formulas of
:mod:`consensus_tpu.ops.ed25519` run unchanged on either.  Every value is an
integer under 2^24 in magnitude, so every f32 operation is exact and the
order of a column's additions cannot change a bit: the kernels' output is
**bit-identical** to the XLA lane's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LIMBS = 32
LANES = 128
#: Rows of a kernel block: one limb of a block is one (8, 128) vreg.
BLOCK_ROWS = 8


def path(batch_shape) -> str:
    """Which lane a field element of ``batch_shape`` takes on this process's
    backend: ``"mosaic"`` where the backend is the TPU and the element is
    limb-major whole vregs, ``(rows, 128)`` with ``rows`` a multiple of 8
    (1,024 lanes or more), else ``"xla"``."""
    whole_vregs = (
        len(batch_shape) == 2
        and batch_shape[1] == LANES
        and batch_shape[0] % BLOCK_ROWS == 0
    )
    return "mosaic" if whole_vregs and jax.default_backend() == "tpu" else "xla"


def launch_path(width: int) -> str:
    """The layout rule of a ``width``-lane verify launch, in one place:
    ``"mosaic"`` where its elements held limb-major, ``(32, width // 128,
    128)``, would take the kernels (:func:`path`), and then the program
    holds them so; else ``"xla"``, and the program keeps the lane-major
    ``(32, width)`` layout it had before the kernels (the n4 widths, 256 /
    512 lanes, and every width off the TPU).  The sidecar reports it per
    width."""
    if width % LANES:
        return "xla"
    return path((width // LANES, LANES))


def active(*elems) -> bool:
    """The trace-time dispatch rule: the kernels run where every operand is
    the same shape and :func:`path` says ``"mosaic"`` for it; anything else
    (the CPU, a lane-major element, a broadcast operand) runs the XLA lane."""
    shape = elems[0].shape
    return path(shape[1:]) == "mosaic" and all(e.shape == shape for e in elems[1:])


# --- kernels ------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _block_body(body, n_in: int):
    """``body(VregField, *elements)`` on one block's limbs, flat in and out,
    jitted: a kernel's block is ``(8, 128)`` a limb at every width, so jax
    traces a body once a process and every width's kernel replays it (traced
    inline, a point body cost every width seconds of Python)."""
    # field25519 asks this module for its dispatch, so it is imported here.
    from consensus_tpu.ops.field25519 import VregField

    def block(*limbs):
        elements = [list(limbs[LIMBS * k : LIMBS * (k + 1)]) for k in range(n_in)]
        return tuple(x for element in body(VregField, *elements) for x in element)

    block.__name__ = body.__name__
    return jax.jit(block)


@functools.lru_cache(maxsize=None)
def _kernel_call(body, n_in: int, n_out: int, rows: int, interpret: bool, vma):
    """One jitted ``pallas_call`` running ``body(VregField, *elements)`` over
    ``n_in`` limb-major elements of ``rows`` rows into ``n_out``; jitted so a
    graph that calls it at many sites traces the kernel once.  ``vma``: the
    mesh axes the elements vary over inside a ``shard_map`` (none outside)."""
    spec = pl.BlockSpec((LIMBS, BLOCK_ROWS, LANES), lambda i: (0, i, 0))
    out_shape = jax.ShapeDtypeStruct((LIMBS, rows, LANES), jnp.float32, vma=vma)
    block = _block_body(body, n_in)

    def kernel(*refs):
        ins, outs = refs[:n_in], refs[n_in:]
        results = block(*(ref[i] for ref in ins for i in range(LIMBS)))
        for k, ref in enumerate(outs):
            for i in range(LIMBS):
                ref[i] = results[LIMBS * k + i]

    # A stable name in traces: "mosaic25519_mul", "mosaic25519_double", ...
    name = "mosaic25519_" + body.__name__.strip("_").removesuffix("_body")
    kernel.__name__ = name
    call = pl.pallas_call(
        kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=[spec] * n_in,
        out_specs=[spec] * n_out,
        out_shape=[out_shape] * n_out,
        interpret=interpret,
        name=name,
    )

    def launch(*elems):
        return tuple(call(*elems))

    launch.__name__ = name
    return jax.jit(launch)


def run(body, n_out: int, *elems) -> tuple:
    """``body(field, *elems)`` as one kernel: ``body`` is a module-level
    function of a field namespace and ``len(elems)`` elements that returns
    ``n_out`` elements.  Every element is ``(32, rows, 128)`` float32 with
    ``rows`` a multiple of 8; off the TPU the kernel runs interpreted."""
    rows = elems[0].shape[1]
    interpret = jax.default_backend() != "tpu"
    vma = frozenset().union(*(jax.typeof(e).vma for e in elems))
    return _kernel_call(body, len(elems), n_out, rows, interpret, vma)(*elems)


def _mul_body(field, a, b):
    return (field.mul(a, b),)


def _square_body(field, a):
    return (field.square(a),)


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """field25519.mul as one kernel."""
    return run(_mul_body, 1, a, b)[0]


def square(a: jnp.ndarray) -> jnp.ndarray:
    """field25519.square as one kernel."""
    return run(_square_body, 1, a)[0]


def limb_major(x):
    """``(..., n)`` -> ``(..., n // 128, 128)``: a width of whole 128-lane
    rows as the layout the kernels take (a field element ``(32, n)``
    becomes ``(32, n // 128, 128)``, a per-lane vector ``(n // 128, 128)``)."""
    return x.reshape(*x.shape[:-1], -1, LANES)


__all__ = [
    "BLOCK_ROWS",
    "LANES",
    "active",
    "launch_path",
    "limb_major",
    "mul",
    "path",
    "run",
    "square",
]
