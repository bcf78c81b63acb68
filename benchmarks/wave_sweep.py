"""Wave-size sweep: where does the device engine beat one host core?

ROADMAP S6 asks for the measured boundary behind the scoped claim "P-256
breaks even at wave >= N".  The integrated configurations 2/4 feed the
engine waves of n*batch signatures (1-2k); this sweep measures the
end-to-end pipelined rate at each wave size so PERF.md can state N from a
chip run instead of extrapolation.  A device family: it raises unless jax
finds a TPU.

    python benchmarks/wave_sweep.py [--family p256|ed25519] \
        [--sizes 256,512,...] [--iters 4]

Prints one JSON line per wave size:
    {"metric": "<family>_wave_rate", "wave": W, "value": sigs/sec,
     "host_core_rate": R, "x_core": value/R}
and a final summary line:
    {"metric": "<family>_breakeven_wave", "value": N_1x,
     "wave_1_2x": N_12x, ...}

The per-wave kernel shapes are powers of two, so each size compiles once
and lands in the persistent compile cache; re-runs are cheap.  Host rate
is the sequential OpenSSL loop (the reference's per-signature path,
reference internal/bft/view.go:537-541) on this box's single core.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=["p256", "ed25519"], default="p256")
    ap.add_argument(
        "--sizes", default="256,512,1024,2048,4096,8192,16384",
        help="comma-separated wave sizes (powers of two >= 8)",
    )
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--host-sample", type=int, default=256)
    args = ap.parse_args()
    # Ascending order is load-bearing: the breakeven report takes the FIRST
    # wave that clears each threshold.
    sizes = sorted(int(s) for s in args.sizes.split(","))

    from consensus_tpu.parallel.topology import apply_compile_cache

    apply_compile_cache()

    import bench

    bench.DEVICE_ITERS = args.iters
    bench.HOST_SAMPLE = args.host_sample
    device = bench.require_tpu()

    if args.family == "p256":
        make = bench.make_p256_signatures
    else:
        make = bench.make_signatures

    # One signature pool at the largest size; each wave is a prefix (the
    # signers repeat every 16, so every prefix is a representative mix).
    msgs, sigs, keys = make(max(sizes))

    # The host rate comes from the first wave's measurement (bench_p256
    # times both paths anyway; ed25519 measures it once up front) — no
    # separate warm-up device run just to read the host number.
    host_rate = None
    if args.family == "ed25519":
        host_rate = bench.bench_host(msgs, sigs, keys)

    rows = []
    for w in sizes:
        mw, sw, kw = msgs[:w], sigs[:w], keys[:w]
        if args.family == "p256":
            rate, host_now = bench.bench_p256(mw, sw, kw)
            if host_rate is None:
                host_rate = host_now
        else:
            rate = bench.bench_device(mw, sw, kw)
        row = {
            "metric": f"{args.family}_wave_rate",
            "wave": w,
            "value": round(rate, 1),
            "unit": "sigs/sec",
            "host_core_rate": round(host_rate, 1),
            "x_core": round(rate / host_rate, 3),
            "device": device,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)

    def first_wave(threshold: float):
        for row in rows:
            if row["x_core"] >= threshold:
                return row["wave"]
        return None

    print(
        json.dumps(
            {
                "metric": f"{args.family}_breakeven_wave",
                "value": first_wave(1.0),
                "wave_1_2x": first_wave(1.2),
                "unit": "signatures",
                "host_core_rate": round(host_rate, 1),
                "peak_x_core": max(r["x_core"] for r in rows),
                "device": device,
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
