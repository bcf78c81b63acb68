"""MXU lowering experiment for the batched field multiplication
(VERDICT r3 #4): can the 32-limb schoolbook convolution — the ~2,800
per-signature field muls that dominate the Ed25519 kernel — ride the MXU
(systolic array) instead of the VPU?

Three lowerings of c = a * b over GF(2^255-19) limbs, all bit-exact:

  vpu       the production path (consensus_tpu/ops/field25519.py::mul):
            32 broadcast multiplies + shifted column adds, pure VPU.
  toeplitz  per-element banded matvec on the MXU: build T[n] with
            T[n, k, i] = b[n, k-i] and contract dot_general(T, a) over the
            limb axis (batch dim = signatures).  The matrices are NOT
            constant (b varies per element), so the Toeplitz tensor is
            materialized per call — 63x32 f32 per element of HBM traffic.
  outer     the "one big matmul" diagonal trick: C = A^T B computes ALL
            cross-element products (N x N blocks) and keeps the diagonal —
            included to quantify why it cannot win (N-fold FLOP waste).
            Runs at a reduced batch to keep the waste affordable.

The analysis this script exists to confirm or refute (PERF.md §5 cost
model): a matmul computes sum_i A[m,i] * B[i,n] — a SHARED contraction
operand.  Batched elementwise bignum products share nothing across
elements, so the MXU can only be fed by (a) replicating per-element
operands into per-element small matrices (toeplitz: 63x32 matvec, far
below the 128x128 systolic tile, plus the materialization traffic), or
(b) computing cross-element garbage (outer).  Constant-operand
multiplications (the fixed-base comb tables) are the exception and
already ride the MXU.

Run: python benchmarks/mxu_fieldmul.py [--batch 8192] [--iters 50]
Prints one JSON line per lowering with ns/fieldmul, plus correctness
cross-checks against the integer reference.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rand_elements(rng, batch):
    """Weakly-reduced random field elements as (32, batch) f32 limbs."""
    vals = [rng.randrange(0, 2**255 - 19) for _ in range(batch)]
    limbs = np.zeros((32, batch), dtype=np.float32)
    for n, v in enumerate(vals):
        for i in range(32):
            limbs[i, n] = (v >> (8 * i)) & 0xFF
    return limbs, vals


def _to_int(limbs):
    """(32, batch) limb array -> python ints (exact, handles negatives)."""
    arr = np.asarray(limbs, dtype=np.float64)
    out = []
    for n in range(arr.shape[1]):
        out.append(sum(int(arr[i, n]) << (8 * i) for i in range(32)))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--chain", type=int, default=16,
                    help="muls chained per jit call (amortizes dispatch)")
    ap.add_argument("--platform", default=None)
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from consensus_tpu.parallel.topology import apply_compile_cache

    apply_compile_cache()

    import random

    import jax.numpy as jnp
    from jax import lax

    from consensus_tpu.ops import field25519 as fe

    P = fe.P
    rng = random.Random(7)
    a_np, a_int = _rand_elements(rng, args.batch)
    b_np, b_int = _rand_elements(rng, args.batch)

    # ---- lowerings -------------------------------------------------------

    def mul_vpu(a, b):
        return fe.mul(a, b)

    _band_rows = np.arange(63)[:, None] - np.arange(32)[None, :]  # k - i
    _band_mask = ((_band_rows >= 0) & (_band_rows < 32)).astype(np.float32)
    _band_idx = np.clip(_band_rows, 0, 31)

    def mul_toeplitz(a, b):
        # T[n, k, i] = b[n, k-i] (banded); c[n, k] = sum_i T[n,k,i] a[n,i].
        bt = jnp.transpose(b)                      # (N, 32)
        at = jnp.transpose(a)                      # (N, 32)
        T = bt[:, _band_idx] * _band_mask          # (N, 63, 32)
        cols = lax.dot_general(
            T, at,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                          # (N, 63)
        return fe._reduce_cols(jnp.transpose(cols))

    def mul_outer(a, b):
        # All-pairs products per limb pair, diagonal extracted: quantifies
        # the N-fold waste of feeding the MXU a shared-operand contraction.
        # c_cols[k, n] = sum_{i+j=k} a[i, n] b[j, n]
        #             = sum_{i+j=k} diag(outer(a[i], b[j]))[n]
        cols = []
        for k in range(63):
            acc = None
            for i in range(max(0, k - 31), min(32, k + 1)):
                j = k - i
                # (N, N) matmul, keep the diagonal only.
                prod = lax.dot_general(
                    a[i][:, None], b[j][None, :],
                    dimension_numbers=((((1,), (0,))), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                d = jnp.diagonal(prod)
                acc = d if acc is None else acc + d
            cols.append(acc)
        return fe._reduce_cols(jnp.stack(cols))

    def chain(mul_fn):
        def run(a, b):
            # a <- a*b repeated: keeps values weakly reduced (mul's output
            # contract) and data-dependent so XLA cannot elide iterations.
            def body(carry, _):
                return mul_fn(carry, b), None

            out, _ = lax.scan(body, a, None, length=args.chain)
            return out

        return jax.jit(run)

    # ---- correctness -----------------------------------------------------
    results = {}
    expected1 = [(x * y) % P for x, y in zip(a_int, b_int)]
    for name, fn in (
        ("vpu", mul_vpu),
        ("toeplitz", mul_toeplitz),
    ):
        got = _to_int(fe.freeze(jax.jit(fn)(a_np, b_np)))
        assert [g % P for g in got] == expected1, f"{name} lowering is WRONG"
    small = 256  # outer is O(N^2); keep the check affordable
    got = _to_int(
        fe.freeze(jax.jit(mul_outer)(a_np[:, :small], b_np[:, :small]))
    )
    assert [g % P for g in got] == expected1[:small], "outer lowering is WRONG"

    # ---- timing ----------------------------------------------------------
    backend = jax.default_backend()

    def time_one(name, fn, a, b):
        jitted = chain(fn)
        out = jitted(a, b)
        np.asarray(out)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = jitted(a, b)
        np.asarray(out)  # host materialization fences the device queue
        elapsed = time.perf_counter() - t0
        per_mul_ns = elapsed / (args.iters * args.chain * a.shape[1]) * 1e9
        results[name] = round(per_mul_ns, 2)
        print(
            json.dumps(
                {
                    "metric": "fieldmul_ns_per_element",
                    "lowering": name,
                    "value": round(per_mul_ns, 2),
                    "unit": "ns",
                    "batch": int(a.shape[1]),
                    "backend": backend,
                }
            )
        )

    time_one("vpu", mul_vpu, a_np, b_np)
    time_one("toeplitz", mul_toeplitz, a_np, b_np)
    time_one("outer_n256", mul_outer, a_np[:, :256], b_np[:, :256])

    if "vpu" in results and "toeplitz" in results:
        print(
            f"# toeplitz/vpu ratio: {results['toeplitz'] / results['vpu']:.2f}x "
            f"(<1 would mean the MXU lowering wins)",
            file=sys.stderr,
        )


if __name__ == "__main__":
    main()
