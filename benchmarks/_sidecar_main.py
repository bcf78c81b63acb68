"""Sidecar process entry: owns the device, serves verification over a unix
socket to the n replica processes (benchmarks/chain_crypto_mp.py starts
one of these in device mode).

Prints ``READY`` on stdout once the kernel shape is warm and the socket is
listening; replicas must not start their measurement before that.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=["ed25519", "p256"], required=True)
    ap.add_argument("--socket", required=True, help="unix socket path")
    ap.add_argument("--wave", type=int, required=True,
                    help="steady-state merged wave size (n * batch)")
    ap.add_argument("--pad-to", type=int, required=True,
                    help="the ONE compiled kernel shape")
    ap.add_argument("--window", type=float, default=0.010)
    ap.add_argument("--min-device-batch", type=int, default=512)
    ap.add_argument("--platform", default=None)
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from consensus_tpu.parallel.topology import apply_compile_cache

    apply_compile_cache()

    from benchmarks.mp_common import make_client_keyring, make_raw_engine
    from consensus_tpu.models import ThreadCoalescingVerifier
    from consensus_tpu.net.sidecar import VerifySidecarServer

    raw = make_raw_engine(
        args.family, min_device_batch=args.min_device_batch, pad_to=args.pad_to
    )

    # Warm the one kernel shape BEFORE accepting traffic: a first-compile
    # stall inside the serving path would blow every replica's timeouts.
    clients = make_client_keyring(args.family, 4)
    warm_n = max(args.min_device_batch, 512)
    reqs = [clients.make_request(i % 4, i) for i in range(warm_n)]
    msgs = [b"ctpu/request" + r[:-64] for r in reqs]
    sigs = [r[-64:] for r in reqs]
    keys = [clients.public_keys[i % 4] for i in range(warm_n)]
    t0 = time.time()
    ok = raw.verify_batch(msgs, sigs, keys)
    assert ok.all(), "sidecar warmup failed to verify"
    print(f"# sidecar warm ({warm_n} sigs -> shape {args.pad_to}) "
          f"in {time.time()-t0:.1f}s on {jax.default_backend()}",
          file=sys.stderr)

    coalescer = ThreadCoalescingVerifier(
        raw,
        window=args.window,
        max_batch=args.wave,
        hard_cap=args.pad_to,
        bypass_below=64,
    )
    server = VerifySidecarServer(args.socket, coalescer)
    server.start()
    print("READY", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
        coalescer.close()


if __name__ == "__main__":
    main()
