"""DEPLOYMENT-SHAPED north-star benchmark: n replica OS PROCESSES over real
TCP, one shared TPU behind a verification sidecar.

The in-process benchmark (benchmarks/chain_crypto_tps.py) runs all n
replicas under one Python GIL, which caps the integrated multiple at ~2x
regardless of crypto speed.  The reference
never carries that handicap: its replicas are separate Go processes wired
by Comm (reference pkg/api/dependencies.go:22-30).  This benchmark removes
it the same way — every replica is its own interpreter/process:

    orchestrator
      ├─ sidecar process (device mode): owns the TPU + one compiled shape,
      │    coalesces all replicas' waves into single launches
      │    (benchmarks/_sidecar_main.py -> consensus_tpu/net/sidecar.py)
      └─ n replica processes (benchmarks/_replica_main.py), each:
           TcpComm over localhost, SignedRequestApp with real signatures,
           host mode: its own sequential OpenSSL loop (the reference
           equivalent, internal/bft/view.go:537-541) on its own core
           device mode: SidecarVerifierClient -> shared TPU

Run:
    python benchmarks/chain_crypto_mp.py --family ed25519 --n 10 \
        --batch 1000 --rotate 100 --verify device --seconds 15

Prints ONE JSON line (same schema as chain_crypto_tps.py plus mode=mp).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks._harness import free_ports


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=["ed25519", "p256"], default="ed25519")
    ap.add_argument("--n", type=int, default=10)
    ap.add_argument("--batch", type=int, default=1000)
    ap.add_argument("--verify", choices=["device", "host"], default="device")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--warmup", type=float, default=5.0)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--rotate", type=int, default=0)
    ap.add_argument("--presign", type=int, default=60000)
    ap.add_argument("--window", type=float, default=0.010)
    ap.add_argument(
        "--wal",
        choices=["mem", "disk", "disk-group"],
        default="mem",
        help="replica WAL mode (disk = fsync per append, the reference's "
        "2-fsyncs-per-decision shape; disk-group = 2ms group commit)",
    )
    ap.add_argument(
        "--platform",
        default=None,
        help="jax platform pin for the SIDECAR (e.g. cpu for a smoke run); "
        "replicas never touch the device",
    )
    args = ap.parse_args()

    here = os.path.dirname(os.path.abspath(__file__))
    ports = free_ports(args.n)
    procs: list[subprocess.Popen] = []
    sidecar_proc = None
    sidecar_path = ""
    wal_base = ""
    if args.wal != "mem":
        wal_base = tempfile.mkdtemp(prefix="ctpu-mp-wal-")

    # Replica processes must never touch the TPU (the sidecar owns it) —
    # pin them to the CPU platform so even an accidental jax op is local.
    replica_env = dict(os.environ, JAX_PLATFORMS="cpu")

    try:
        if args.verify == "device":
            from consensus_tpu.models.ed25519 import _next_pow2

            wave = args.n * args.batch
            pad_to = _next_pow2(wave)
            sidecar_path = os.path.join(
                tempfile.mkdtemp(prefix="ctpu-sidecar-"), "verify.sock"
            )
            cmd = [
                sys.executable, os.path.join(here, "_sidecar_main.py"),
                "--family", args.family,
                "--socket", sidecar_path,
                "--wave", str(wave),
                "--pad-to", str(pad_to),
                "--window", str(args.window),
            ]
            if args.platform:
                cmd += ["--platform", args.platform]
            sidecar_proc = subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=sys.stderr, text=True
            )
            line = sidecar_proc.stdout.readline()
            if line.strip() != "READY":
                raise RuntimeError(
                    f"sidecar failed to start (got {line!r}); see stderr"
                )
            print("# sidecar ready", file=sys.stderr)

        port_list = ",".join(str(p) for p in ports)
        for node_id in range(args.n, 0, -1):  # leader (1) last: peers ready
            cmd = [
                sys.executable, os.path.join(here, "_replica_main.py"),
                "--node-id", str(node_id),
                "--n", str(args.n),
                "--ports", port_list,
                "--family", args.family,
                "--verify", args.verify,
                "--sidecar", sidecar_path,
                "--batch", str(args.batch),
                "--rotate", str(args.rotate),
                "--clients", str(args.clients),
                "--seconds", str(args.seconds),
                "--warmup", str(args.warmup),
                "--presign", str(args.presign),
                "--wal", args.wal,
                "--wal-base", wal_base,
            ]
            proc = subprocess.Popen(
                cmd,
                stdout=subprocess.PIPE if node_id == 1 else subprocess.DEVNULL,
                stderr=sys.stderr,
                text=True,
                env=replica_env,
            )
            procs.append(proc)

        leader = procs[-1]  # node 1, started last
        deadline = time.time() + args.warmup + args.seconds + 600
        result = None
        while time.time() < deadline:
            line = leader.stdout.readline()
            if not line:
                break
            line = line.strip()
            if line.startswith("{"):
                result = json.loads(line)
                break
        if result is None:
            raise RuntimeError("leader process produced no measurement")

        print(
            json.dumps(
                {
                    "metric": "chain_crypto_tx_per_sec",
                    "value": result["tx_per_sec"],
                    "unit": "tx/sec",
                    "mode": "multiprocess",
                    "family": args.family,
                    "verify": args.verify,
                    "n": args.n,
                    "f": (args.n - 1) // 3,
                    "batch": args.batch,
                    "rotate_every": args.rotate,
                    "blocks_per_sec": result["blocks_per_sec"],
                    "p50_commit_latency_ms": result["p50_commit_latency_ms"],
                    "p90_commit_latency_ms": result["p90_commit_latency_ms"],
                    "presign_exhausted": result["presign_exhausted"],
                }
            )
        )
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
        if sidecar_proc is not None and sidecar_proc.poll() is None:
            sidecar_proc.send_signal(signal.SIGKILL)
        for proc in procs:
            proc.wait()
        if sidecar_proc is not None:
            sidecar_proc.wait()
        if wal_base:
            import shutil

            shutil.rmtree(wal_base, ignore_errors=True)


if __name__ == "__main__":
    main()
