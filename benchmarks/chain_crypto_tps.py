"""Integrated north-star benchmark: consensus chain throughput with REAL
signature crypto, TPU-batched vs sequential-host verification.

This measures the thesis end-to-end (BASELINE.json configurations 1-3): n replicas
over real TCP with realtime schedulers, client requests carrying real
signatures, commit quorums carrying real consenter signatures.  The
``--verify host`` mode verifies exactly like the reference — sequentially
on CPU per signature (reference internal/bft/view.go:537-541 per-vote and
view.go:602-647 per-proposal loops, modulo goroutines) — while
``--verify device`` drains the same checks into the batch engine.

Run:
    python benchmarks/chain_crypto_tps.py --family ed25519 --n 7 \
        --batch 1000 --verify device --seconds 10 [--platform cpu]

Prints ONE JSON line:
    {"metric": "chain_crypto_tx_per_sec", "value": ..., "unit": "tx/sec",
     "p50_commit_latency_ms": ..., "p90_commit_latency_ms": ..., ...}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks._harness import start_feeder, start_replicas, teardown

_REQ_TAG = b"ctpu/request"

#: Coalesced flushes below this ride OpenSSL faster than a padded
#: device launch would run (host ~7-35k sigs/s vs the fixed launch+pad
#: cost).  Coalescing can only ever reach the device when the full
#: n-replica wave clears it.
MIN_DEVICE_COALESCED = 512


def build_family(family: str, node_ids, n_clients: int, verify_mode: str,
                 wave: int, pad_to: int, coalesce: bool, window: float):
    """Returns (replica signers, verifier factory, engine, raw engine,
    min_device_batch, client keyring).  ``engine`` is what the replicas
    use; when coalescing is on it is a :class:`ThreadCoalescingVerifier`
    wrapper that merges the n replicas' concurrent verify waves into single
    device launches (``raw_engine`` stays available for shape warm-up)."""
    from consensus_tpu.models import (
        EcdsaP256Signer,
        EcdsaP256VerifierMixin,
        Ed25519Signer,
        Ed25519VerifierMixin,
        ThreadCoalescingVerifier,
    )
    from consensus_tpu.models.ecdsa_p256 import EcdsaP256BatchVerifier
    from consensus_tpu.models.ed25519 import Ed25519BatchVerifier
    from consensus_tpu.testing.crypto_app import ClientKeyring

    # Host mode = the reference's sequential CPU loop (OpenSSL per sig).
    # Device mode routes small batches (quorum checks, a handful of sigs)
    # to the host too — kernel launch overhead dominates below
    # min_device_batch — and pads every device batch to ONE fixed shape
    # (pad_to) so no mid-run XLA compile can stall a replica thread.
    if verify_mode == "host":
        min_dev = 10**9
    elif coalesce:
        min_dev = MIN_DEVICE_COALESCED
    else:
        min_dev = 32
    kw = dict(min_device_batch=min_dev, pad_to=pad_to)
    if family == "ed25519":
        raw_engine = Ed25519BatchVerifier(**kw)
        signers = {i: Ed25519Signer(i) for i in node_ids}
        clients = ClientKeyring([Ed25519Signer(1000 + i) for i in range(n_clients)])
        mixin_cls = Ed25519VerifierMixin
    elif family == "p256":
        raw_engine = EcdsaP256BatchVerifier(**kw)
        signers = {i: EcdsaP256Signer(i) for i in node_ids}
        clients = ClientKeyring([EcdsaP256Signer(1000 + i) for i in range(n_clients)])
        mixin_cls = EcdsaP256VerifierMixin
    else:
        raise ValueError(family)

    engine = raw_engine
    if verify_mode == "device" and coalesce:
        # Flush as soon as the full n-replica wave has arrived (max_batch =
        # wave), never launch beyond the one compiled shape (hard_cap), and
        # let genuinely tiny checks (heartbeats, quorum votes) skip the
        # window.  bypass_below must stay SMALL: per-replica proposal
        # batches below min_device_batch still belong in the coalescer —
        # merging n of them is exactly what lifts the flush over the
        # device threshold.
        engine = ThreadCoalescingVerifier(
            raw_engine,
            window=window,
            max_batch=wave,
            hard_cap=pad_to,
            bypass_below=64,
        )

    keys = {i: s.public_bytes for i, s in signers.items()}

    class _SigVerifier(mixin_cls):
        def verify_proposal(self, proposal):
            raise NotImplementedError  # app half lives in SignedRequestApp

        def verify_request(self, raw):
            raise NotImplementedError

        def verification_sequence(self):
            return 0

        def requests_from_proposal(self, proposal):
            return []

    def make_verifier():
        return _SigVerifier(keys, engine=engine)

    return signers, make_verifier, engine, raw_engine, min_dev, clients


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=["ed25519", "p256"], default="ed25519")
    ap.add_argument("--n", type=int, default=7)
    ap.add_argument("--batch", type=int, default=1000)
    ap.add_argument("--verify", choices=["device", "host"], default="device")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument(
        "--rotate",
        type=int,
        default=0,
        metavar="DECISIONS",
        help="leader rotation every N decisions (BASELINE config 4: "
        "n=10, --rotate 100); 0 = rotation off",
    )
    ap.add_argument("--presign", type=int, default=100000)
    ap.add_argument(
        "--coalesce",
        choices=["on", "off"],
        default="on",
        help="merge the n replicas' concurrent device verify calls into "
        "single launches (device mode only; 'off' = one launch per replica "
        "per proposal, each paying full dispatch overhead)",
    )
    ap.add_argument(
        "--window",
        type=float,
        default=0.010,
        help="coalescing window in seconds (must stay well under the "
        "heartbeat/view-change timeouts; SURVEY §7 hard part 3)",
    )
    ap.add_argument(
        "--platform",
        default=None,
        help="jax platform pin (e.g. cpu); default leaves the real device",
    )
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from consensus_tpu.parallel.topology import apply_compile_cache

    apply_compile_cache()

    from consensus_tpu.config import Configuration
    from consensus_tpu.metrics import InMemoryProvider, Metrics
    from consensus_tpu.testing.crypto_app import SignedRequestApp

    from consensus_tpu.models.ed25519 import _next_pow2

    node_ids = list(range(1, args.n + 1))
    coalesce = args.coalesce == "on" and args.verify == "device"
    if coalesce and args.n * args.batch < MIN_DEVICE_COALESCED:
        # Even the full merged wave would ride the host path — coalescing
        # could only add window latency.  Fall back honestly (reported in
        # the output JSON as coalesce=false).
        coalesce = False
    # With coalescing the steady-state device launch is the n replicas'
    # proposal wave (n * batch signatures); without it, one replica's batch.
    wave = args.n * args.batch if coalesce else args.batch
    pad_to = _next_pow2(wave)
    signers, make_verifier, engine, raw_engine, min_dev, clients = build_family(
        args.family, node_ids, args.clients, args.verify, wave, pad_to,
        coalesce, args.window,
    )
    sig_len = 64

    # Pre-sign the request stream so feeder-side signing can't bottleneck
    # the measurement (clients in production sign concurrently).
    presigned = [
        clients.make_request(i % args.clients, i) for i in range(args.presign)
    ]

    warm_n = min(pad_to, len(presigned))
    if args.verify == "device" and wave >= min_dev and warm_n < min_dev:
        ap.error(
            f"--presign {args.presign} is too small to warm the device "
            f"shape (need >= {min_dev}); raise --presign"
        )
    if args.verify == "device" and wave >= min_dev:
        # Warm the ONE kernel shape (pad_to) BEFORE consensus starts: a
        # first-compile stall inside a replica thread trips heartbeat
        # timeouts and the cluster spends the benchmark in view changes.
        # (When even the full wave rides the host path, nothing to warm.)
        warm = presigned[:warm_n]
        t0 = time.time()
        raws = [r[:-sig_len] for r in warm]
        sigs = [r[-sig_len:] for r in warm]
        keys = [clients.public_keys[i % args.clients] for i in range(len(warm))]
        ok = raw_engine.verify_batch([_REQ_TAG + r for r in raws], sigs, keys)
        assert ok.all(), "warmup requests failed to verify"
        print(
            f"# kernel warm ({len(warm)} sigs -> shape {pad_to}) "
            f"in {time.time()-t0:.1f}s",
            file=sys.stderr,
        )

    leader_provider = InMemoryProvider()

    def make_app(node_id, cluster):
        return SignedRequestApp(
            node_id,
            cluster,
            signers[node_id],
            make_verifier(),
            client_keys=clients.public_keys,
            engine=engine,
            sig_len=sig_len,
        )

    def make_config(node_id):
        return Configuration(
            self_id=node_id,
            leader_rotation=args.rotate > 0,
            decisions_per_leader=args.rotate,
            request_batch_max_count=args.batch,
            request_batch_max_interval=0.02,
            request_pool_size=max(2000, 3 * args.batch),
        )

    cluster, replicas, comms, schedulers = start_replicas(
        args.n, make_app, make_config, leader_metrics=Metrics(leader_provider)
    )

    leader = replicas[1]
    ledger = cluster.nodes[1].app.ledger
    # Under rotation the leader moves between proposals; submitting to a
    # fixed replica still works (stage-1 forwarding), which is exactly what
    # the reference's clients do.
    stop, exhausted = start_feeder(
        leader, presigned, inflight=max(1500, 2 * args.batch)
    )

    # Warmup, then measure.
    time.sleep(4.0)
    lat = leader_provider.observations("view_latency_batch_processing")
    start_blocks, start_lat = len(ledger), len(lat)
    start_tx = sum(int.from_bytes(d.proposal.payload[:4], "big") for d in ledger)
    t0 = time.time()
    time.sleep(args.seconds)
    elapsed = time.time() - t0
    end_blocks = len(ledger)
    end_tx = sum(int.from_bytes(d.proposal.payload[:4], "big") for d in ledger)
    window_lat = sorted(lat[start_lat:])
    ran_dry = exhausted[0]
    stop.set()
    if ran_dry:
        print(
            "# WARNING: presigned request stream ran dry during the window; "
            "tx/sec under-measures — raise --presign",
            file=sys.stderr,
        )

    tx_per_sec = (end_tx - start_tx) / elapsed

    def pct(p):
        if not window_lat:
            return None
        return round(
            1000 * window_lat[min(len(window_lat) - 1, int(p * len(window_lat)))], 2
        )

    print(
        json.dumps(
            {
                "metric": "chain_crypto_tx_per_sec",
                "value": round(tx_per_sec, 1),
                "unit": "tx/sec",
                "family": args.family,
                "verify": args.verify,
                "n": args.n,
                "f": (args.n - 1) // 3,
                "batch": args.batch,
                "rotate_every": args.rotate,
                "coalesce": coalesce,
                "blocks_per_sec": round((end_blocks - start_blocks) / elapsed, 1),
                "p50_commit_latency_ms": pct(0.50),
                "p90_commit_latency_ms": pct(0.90),
                "backend": jax.default_backend(),
                "presign_exhausted": ran_dry,
            }
        )
    )

    teardown(replicas, comms, schedulers, cluster)


if __name__ == "__main__":
    main()
