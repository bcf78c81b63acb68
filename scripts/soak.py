#!/usr/bin/env python
"""Multi-hour soak driver for the process-per-replica deployment rig.

Stands up a real cluster (N replica processes + a sidecar verifier fleet
+ the ingress driver, each its own OS process over real sockets and real
disk), then loops for ``--minutes``:

* the driver process replays the deterministic client trace against the
  cluster (restarted with a fresh seed each time it drains),
* the process-chaos schedule fires one seeded action per period
  (``kill -9`` leader/follower/sidecar, SIGSTOP freeze, listener-port
  drop, WAL storage faults) unless ``--no-chaos``,
* every period the obs plane scrapes each replica's Prometheus text over
  its control socket and the invariant monitor re-collects every ledger
  (prefix agreement + durable-before-visible across restarts),
* the autoscaler evaluates the sidecar fleet's offered/rejected window.

Exit code 0 requires: the invariant monitor is clean, the cluster made
forward progress, and teardown found zero orphaned processes and zero
leaked listen ports.  The last stdout line is a JSON summary.

CI-scale: ``python scripts/soak.py --minutes 2``.  The multi-hour run is
the same command with ``--minutes 360`` (documented in README — run it
manually, it is deliberately not a test).

A soak is wall-time by definition: this script lives outside the lint's
no-wallclock domain (scripts/ drive, they don't implement consensus).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--minutes", type=float, default=2.0)
    ap.add_argument("--replicas", type=int, default=5)
    ap.add_argument("--sidecars", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--period", type=float, default=10.0,
                    help="seconds between chaos/scrape/invariant rounds")
    ap.add_argument("--no-chaos", action="store_true")
    ap.add_argument("--base-dir", default=None,
                    help="cluster directory (default: a fresh tempdir)")
    ap.add_argument("--driver-rate", type=float, default=30.0)
    return ap.parse_args(argv)


def start_driver(launcher, seconds: float, seed: int, rate: float):
    """The ingress plane as its own OS process (PR-12 driver), pinned to
    the CPU like every rig child but the sidecar."""
    spec = launcher.spec
    return subprocess.Popen(
        [
            sys.executable, "-m", "consensus_tpu.deploy.driver_main",
            "--config", spec.config_path,
            "--seconds", str(seconds),
            "--seed", str(seed),
            "--rate", str(rate),
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        env=launcher.cpu_env,
        text=True,
    )


def main(argv=None) -> int:
    args = parse_args(argv)
    from consensus_tpu.deploy import (
        ClusterLauncher,
        ClusterSpec,
        FleetAutoscaler,
        ProcessChaosSchedule,
    )

    base = args.base_dir or tempfile.mkdtemp(prefix="ctpu-soak-")
    spec = ClusterSpec.generate(
        args.replicas, args.sidecars, base,
        config_overrides={
            "view_change_timeout": 4.0,
            "view_change_resend_interval": 1.0,
            "leader_heartbeat_timeout": 3.0,
            "leader_heartbeat_count": 10,
            # A process-chaos soak, not a device run: its 20-request
            # proposals never reach the sidecars' device threshold anyway,
            # so say so — host-only sidecars open no backend, and a fleet of
            # them fits a host with one chip or none.
            "crypto_tpu_min_batch": 10**9,
        },
    )
    launcher = ClusterLauncher(spec, backoff_initial=1.0)
    chaos = ProcessChaosSchedule(launcher, seed=args.seed)
    autoscaler = FleetAutoscaler(
        min_sidecars=1, max_sidecars=max(args.sidecars + 1, 2)
    )

    summary = {
        "minutes": args.minutes,
        "replicas": args.replicas,
        "sidecars": args.sidecars,
        "seed": args.seed,
        "chaos": [],
        "scrapes": 0,
        "scrape_bytes": 0,
        "driver_runs": [],
        "autoscale": [],
        "ok": False,
    }
    driver = None
    driver_seed = args.seed
    rc = 1
    try:
        launcher.start(timeout=180)
        start = time.monotonic()
        deadline = start + args.minutes * 60.0
        start_height = max(launcher.heights().values() or [0])
        rounds = 0
        while time.monotonic() < deadline:
            # Keep exactly one driver process replaying the trace.
            if driver is None or driver.poll() is not None:
                if driver is not None:
                    out = (driver.stdout.read() or "").strip().splitlines()
                    if out:
                        try:
                            summary["driver_runs"].append(json.loads(out[-1]))
                        except ValueError:
                            pass
                driver_seed += 1
                driver = start_driver(
                    launcher,
                    seconds=max(args.period * 3, 30.0),
                    seed=driver_seed,
                    rate=args.driver_rate,
                )
            time.sleep(min(args.period, max(0.0, deadline - time.monotonic())))
            rounds += 1
            # Obs plane: scrape every replica's Prometheus endpoint.
            bodies = launcher.scrape()
            summary["scrapes"] += len(bodies)
            summary["scrape_bytes"] += sum(len(b) for b in bodies.values())
            # Invariants across every live ledger.
            launcher.observe_invariants()
            if not launcher.monitor.clean:
                print(json.dumps(
                    {"fatal": "invariant violation",
                     "detail": launcher.monitor.summary()}), flush=True)
                break
            # Fleet sizing on the offered/rejected window.
            decision = autoscaler.run_once(launcher)
            if decision.action:
                summary["autoscale"].append(
                    {"action": decision.action, "target": decision.target,
                     "reason": decision.reason})
            # One seeded chaos action per period.
            if not args.no_chaos:
                summary["chaos"].append(chaos.step())
        chaos.quiesce()
        # Let in-flight restarts land before the final accounting.
        heal_deadline = time.monotonic() + 30.0
        while time.monotonic() < heal_deadline:
            if all(s.alive for s in launcher.replicas.values()):
                break
            time.sleep(1.0)
        launcher.observe_invariants()
        end_height = max(launcher.heights().values() or [0])
        summary["rounds"] = rounds
        summary["start_height"] = start_height
        summary["end_height"] = end_height
        summary["invariants"] = launcher.monitor.summary()
        progressed = end_height > start_height
        summary["ok"] = bool(launcher.monitor.clean and progressed)
    finally:
        if driver is not None and driver.poll() is None:
            driver.kill()
            driver.wait()
        try:
            teardown = launcher.stop()
            summary["teardown"] = {
                "orphans": teardown["orphans"],
                "leaked_ports": teardown["leaked_ports"],
                "restarts": teardown["restarts"],
            }
        except AssertionError as e:
            summary["teardown"] = {"error": str(e)}
            summary["ok"] = False
    rc = 0 if summary["ok"] else 1
    print(json.dumps(summary, sort_keys=True), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
