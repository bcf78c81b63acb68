#!/usr/bin/env python
"""chip_smoke.py — prove the served path on the chip, once.

``python chip_smoke.py`` drives the system's main path through the entry
points an embedder calls — ``ClusterSpec.generate`` -> ``ClusterLauncher``
-> replica processes over real sockets and file WALs -> ONE sidecar process
holding the chip — and fails unless the chip did the work and got it right.
It refuses anything but a TPU.  ``--dry-run`` rehearses the same code at a
tiny size on the CPU backend; that mode labels everything it prints
``dry_run`` / ``platform: cpu`` and none of it is a chip result.

Phases, one JSON line each on stdout (a time-out shows how far it got),
then one summary line (ending ``"claim": null``), then — last — the contract
line ``{"ok": ..., "device": {"platform", "kind", "count"}}`` and nothing else:

1. *rig*      BASELINE.json configuration 3 (n=7, f=2, Ed25519, 1,000
              requests per proposal) as 7 replica processes + 1 sidecar.
2. *traffic*  12,000 pre-signed requests broadcast to every replica; pass =
              every replica delivered all of them exactly once, identically.
3. *device*   from the processes' own reports: the sidecar ran on a TPU,
              compiled nothing after ready, launched every signature the
              replicas sent it; no fallback, suspect, degrade or restart.
4. *verdicts* one full-width wave seeded with every rejection class through
              the same sidecar socket, bit-identical to ``verify_host``.
5. *census*   after the rig is down, one child process per engine lane (each
              owns the chip in turn, env-read flags honoured): compiled on
              the device, verdicts equal to the host twin on a wave with bad
              lanes.  A lane that cannot pass here does not stay in the tree.

This process never initialises a JAX backend: the sidecar (phases 1-4) and
each census child (phase 5) is the one process holding the chip.  It writes
no number under the name of a throughput or latency metric — counts, and
set-up seconds, only.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: What phases 1-2 deploy, where it comes from, and every value this script
#: had to pick itself.
SOURCE = (
    "BASELINE.json configs[2]: '7-replica (f=2) Ed25519, batch=1000 + "
    "Checkpoint sig-sets'; every other value is the shipped Configuration "
    "default (rotation on, 3 decisions per leader, 50 ms batch interval, "
    "pipeline depth 1, file WAL, fsync per append)"
)
ASSUMED = {
    "request_pool_size": "4x the batch; the injector keeps at most 2 batches "
                         "outstanding",
    "clients": "1024 distinct Ed25519 client keys, round-robin",
    "request_body_bytes": "64 (ClientKeyring.make_request default): 140-byte "
                          "signed requests",
    "requests": "12 full proposals",
    "sidecars": "1 (one chip, one process)",
}

#: Real size: configuration 3 at full width.  Dry run: the same code, tiny.
REAL = dict(n=7, batch=1000, pool=4000, clients=1024, requests=12_000,
            census_lanes=1024, start_timeout=900.0, traffic_timeout=600.0,
            lane_timeout=420.0, request_timeout=10.0)
DRY = dict(n=4, batch=64, pool=256, clients=16, requests=768,
           census_lanes=8, start_timeout=900.0, traffic_timeout=600.0,
           lane_timeout=900.0, request_timeout=60.0)

#: Transport id of this script's request injector (outside the replica ids).
INJECTOR_ID = 900

#: Engine lanes of the census: name -> (environment flags, curve, engine
#: knobs, check).  Default lane first.  Every key the engine registry builds
#: on one device (strict / randomized x host prep, strict x device prep,
#: P-256), half-aggregated certs on the device MSM, then the one
#: environment-flag lane left in the tree (CTPU_MXU_LIMBS=1) on both modes.
LANES = {
    "ed25519.strict": ({}, "ed25519", {}, "verdicts"),
    "ed25519.randomized": ({}, "ed25519", {"batch_verify_mode": True}, "verdicts"),
    "ed25519.halfagg": ({}, "ed25519", {}, "halfagg"),
    "ed25519.device_prep": ({}, "ed25519", {"device_prep": True}, "verdicts"),
    "p256.strict": ({}, "p256", {}, "verdicts"),
    "ed25519.strict+mxu_limbs": (
        {"CTPU_MXU_LIMBS": "1"}, "ed25519", {}, "verdicts"),
    "ed25519.randomized+mxu_limbs": (
        {"CTPU_MXU_LIMBS": "1"}, "ed25519", {"batch_verify_mode": True},
        "verdicts"),
}


# --------------------------------------------------------------- waves


def _plant(n: int, seed: int, classes: list) -> dict:
    """Apply one rejection class each (as many as fit in ``n // 2`` lanes)
    at seeded positions; returns ``{position: class name}``."""
    import random

    positions = random.Random(seed).sample(range(n), min(len(classes), n // 2))
    planted = {}
    for pos, plant in zip(positions, classes):
        plant(pos)
        planted[pos] = plant.__name__
    return planted


def _ed25519_wave(n: int, seed: int):
    """``n`` (message, signature, key) triples: honest lanes under 16 seeded
    signers, with one lane of every rejection class planted at seeded
    positions (as many classes as fit in ``n // 2`` lanes).  Returns the
    triples and ``{position: class}``."""
    from consensus_tpu.models import Ed25519Signer
    from consensus_tpu.models.ed25519 import L
    from consensus_tpu.ops.field25519 import P

    def seed32(tag: str, i: int) -> bytes:
        return hashlib.sha256(b"chip-smoke:%d:%s:%d" % (seed, tag.encode(), i)).digest()

    signers = [Ed25519Signer(i, private_key_bytes=seed32("signer", i))
               for i in range(16)]
    msgs, sigs, keys = [], [], []
    for i in range(n):
        s = signers[i % len(signers)]
        m = b"ctpu/chip-smoke/%d/%d" % (seed, i)
        msgs.append(m)
        sigs.append(s.sign_raw(m))
        keys.append(s.public_bytes)

    def forged(i):  # well-formed, canonical, signed by nobody
        sigs[i] = sigs[i][:32] + (
            int.from_bytes(seed32("forge", i), "little") % L
        ).to_bytes(32, "little")

    def tampered(i):
        msgs[i] = msgs[i] + b"!"

    def wrong_key(i):
        keys[i] = signers[(i + 1) % len(signers)].public_bytes

    def s_ge_l(i):  # S + L: the malleable twin of a valid signature
        s = int.from_bytes(sigs[i][32:], "little") + L
        sigs[i] = sigs[i][:32] + s.to_bytes(32, "little")

    def noncanonical_r(i):  # y >= p
        sigs[i] = (P + 3).to_bytes(32, "little") + sigs[i][32:]

    def noncanonical_a(i):
        keys[i] = (P + 5).to_bytes(32, "little")

    def short_sig(i):
        sigs[i] = sigs[i][:63]

    def undecodable_r(i):  # canonical y with no x on the curve
        y = 2
        from consensus_tpu.models.ed25519 import _ref_decompress

        while _ref_decompress(y.to_bytes(32, "little")) is not None:
            y += 1
        sigs[i] = y.to_bytes(32, "little") + sigs[i][32:]

    planted = _plant(n, seed, [forged, tampered, wrong_key, s_ge_l,
                               noncanonical_r, noncanonical_a, short_sig,
                               undecodable_r])
    return (msgs, sigs, keys), planted


def _p256_wave(n: int, seed: int):
    """The P-256 twin of :func:`_ed25519_wave` (raw r||s signatures,
    uncompressed keys)."""
    from cryptography.hazmat.primitives.asymmetric import ec

    from consensus_tpu.models import EcdsaP256Signer
    from consensus_tpu.models.ecdsa_p256 import N

    def scalar(tag: str, i: int) -> int:
        h = hashlib.sha256(b"chip-smoke-p256:%d:%s:%d" % (seed, tag.encode(), i))
        return 1 + int.from_bytes(h.digest(), "big") % (N - 1)

    signers = [
        EcdsaP256Signer(
            i, private_key=ec.derive_private_key(scalar("signer", i), ec.SECP256R1())
        )
        for i in range(8)
    ]
    msgs, sigs, keys = [], [], []
    for i in range(n):
        s = signers[i % len(signers)]
        m = b"ctpu/chip-smoke-p256/%d/%d" % (seed, i)
        msgs.append(m)
        sigs.append(s.sign_raw(m))
        keys.append(s.public_bytes)

    def forged(i):
        sigs[i] = scalar("r", i).to_bytes(32, "big") + scalar("s", i).to_bytes(32, "big")

    def tampered(i):
        msgs[i] = msgs[i] + b"!"

    def wrong_key(i):
        keys[i] = signers[(i + 1) % len(signers)].public_bytes

    def s_zero(i):
        sigs[i] = sigs[i][:32] + bytes(32)

    def r_ge_n(i):
        sigs[i] = N.to_bytes(32, "big") + sigs[i][32:]

    def off_curve_key(i):
        keys[i] = keys[i][:33] + bytes(a ^ 1 for a in keys[i][33:])

    def short_sig(i):
        sigs[i] = sigs[i][:63]

    def bad_key_prefix(i):
        keys[i] = b"\x02" + keys[i][1:]

    planted = _plant(n, seed, [forged, tampered, wrong_key, s_zero, r_ge_n,
                               off_curve_key, short_sig, bad_key_prefix])
    return (msgs, sigs, keys), planted


def _verdict_report(got, want, planted) -> dict:
    """Bit-for-bit comparison of a verdict vector with the host twin's."""
    got = [bool(v) for v in got]
    want = [bool(v) for v in want]
    mismatches = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    # The host twin itself must reject exactly the planted lanes, or the
    # wave proves nothing.
    twin_rejects = sorted(i for i, w in enumerate(want) if not w)
    return {
        "ok": (
            len(got) == len(want)
            and not mismatches
            and twin_rejects == sorted(planted)
        ),
        "signatures": len(want),
        "rejected": len(twin_rejects),
        "planted": {str(k): v for k, v in sorted(planted.items())},
        "mismatches": mismatches[:16],
    }


# ------------------------------------------------------- phases 1 to 4


def _seeded_namespace(seed: int) -> str:
    return hashlib.sha256(b"chip-smoke-namespace:%d" % seed).hexdigest()[:16]


def run_served_path(size: dict, out_dir: str, seed: int, *, dry_run: bool,
                    emit, before_traffic=None) -> dict:
    """Phases 1-4 plus teardown.  ``emit(record)`` is called once per phase;
    returns ``{"ok", "device", "phases"}``.  ``before_traffic(launcher)`` is
    a test hook (e.g. kill the sidecar and watch the run fail)."""
    from consensus_tpu.deploy import ClusterLauncher, ClusterSpec
    from consensus_tpu.deploy.control import ControlClient
    from consensus_tpu.deploy.identity import make_client_keyring
    from consensus_tpu.deploy.sidecar_main import EXIT_NO_DEVICE
    from consensus_tpu.deploy.spec import free_ports
    from consensus_tpu.net import SidecarVerifierClient, TcpComm
    from consensus_tpu.testing.crypto_app import request_ids_digest

    phases: dict = {}
    device = None

    def done(name: str, t0: float, record: dict) -> dict:
        record = {"phase": name, "dry_run": dry_run,
                  "wall_secs": round(time.monotonic() - t0, 3), **record}
        phases[name] = record
        emit(record)
        return record

    cluster_dir = os.path.join(out_dir, "cluster")
    shutil.rmtree(cluster_dir, ignore_errors=True)
    overrides = {
        "request_batch_max_count": size["batch"],
        "request_pool_size": size["pool"],
        # The rig's own defaults switch rotation off; configuration 3 runs
        # the shipped Configuration defaults.
        "leader_rotation": True,
        "decisions_per_leader": 3,
    }
    if dry_run:
        # The CPU backend stands in for the chip ~50x slower, so a decision
        # takes seconds.  Keep the request-forward timer (2 s shipped) out
        # of that range: a forwarded copy that reaches the leader more than
        # 5 s after the request was delivered is past the pool's dedup
        # horizon and would be committed a second time.
        overrides.update(request_forward_timeout=60.0,
                         request_complain_timeout=120.0)
    spec = ClusterSpec.generate(
        size["n"], 1, cluster_dir, clients=size["clients"],
        config_overrides=overrides, hold_ports=True,
    )
    spec.key_namespace = _seeded_namespace(seed)
    spec.sidecar_request_timeout = size["request_timeout"]
    launcher = ClusterLauncher(spec)
    f = (size["n"] - 1) // 3
    comm = None
    try:
        # ---- 1. rig ------------------------------------------------------
        t0 = time.monotonic()
        try:
            launcher.start(timeout=size["start_timeout"])
        except (RuntimeError, TimeoutError) as exc:
            sup = launcher.sidecars.get("sc-0")
            if (sup is not None and sup.exit_code == EXIT_NO_DEVICE
                    and not dry_run):
                # No accelerator: say so on stderr and print NO result.
                print(f"chip_smoke: {exc}", file=sys.stderr)
                return {"ok": False, "device": None, "phases": phases,
                        "no_device": True}
            done("rig", t0, {"ok": False, "error": str(exc)})
            return {"ok": False, "device": None, "phases": phases}
        sc = launcher.sidecars["sc-0"].probe() or {}
        device = {"platform": sc.get("platform"),
                  "kind": sc.get("device_kind"),
                  "count": sc.get("device_count")}
        want_platform = "cpu" if dry_run else "tpu"
        rig = done("rig", t0, {
            "ok": device["platform"] == want_platform,
            "replicas": size["n"], "f": f, "sidecars": 1,
            "requests_per_proposal": size["batch"],
            "device": device,
            "sidecar_lanes": sc.get("lanes"),
            "cache_dir": sc.get("cache_dir"),
            "backend_secs": sc.get("backend_secs"),
            "cold_compile_secs": {str(sc.get("lanes")): sc.get("warm_compile_secs")},
            "compiles_at_ready": sc.get("compiles"),
        })
        if not rig["ok"]:
            return {"ok": False, "device": device, "phases": phases}

        if before_traffic is not None:
            before_traffic(launcher)

        # ---- 2. traffic --------------------------------------------------
        t0 = time.monotonic()
        keyring = make_client_keyring(spec.key_namespace, spec.clients)
        requests = []
        for i in range(size["requests"]):
            client = i % spec.clients
            requests.append(keyring.make_request(client, (client << 32) | i))
        presign_secs = time.monotonic() - t0
        addresses = dict(spec.comm_addresses())
        addresses[INJECTOR_ID] = ("127.0.0.1", free_ports(1)[0])
        # Paced so batches fill and nothing overflows: with two proposals'
        # worth outstanding, a full batch is always waiting while the
        # previous one is in flight, and neither a replica's pool (parks,
        # then drops after submit_timeout) nor this sender's queue (drops on
        # overflow) is ever full.  A small backlog also keeps every copy of
        # a request arriving long before the pool forgets it was delivered
        # (5 s) — a straggler after that would be committed twice.
        window = 2 * size["batch"]
        comm = TcpComm(
            INJECTOR_ID, addresses, lambda *a: None,
            reconnect_backoff=0.05, auth_secret=spec.auth_secret,
            send_queue_depth=window + size["batch"],
        )
        comm.start()
        controls = {
            r.node_id: ControlClient((r.host, r.control_port), timeout=5.0)
            for r in spec.replicas
        }

        def committed() -> tuple:
            counts, leader = [], None
            for node_id, control in controls.items():
                h = control.try_call("health") or {}
                counts.append(int(h.get("requests", 0)))
                if leader is None:
                    leader = h.get("leader")
            return min(counts), leader

        leaders: list = []
        sent = 0
        chunk = max(1, size["batch"] // 10)
        deadline = time.monotonic() + size["traffic_timeout"]
        low = 0
        while time.monotonic() < deadline:
            low, leader = committed()
            if leader is not None and (not leaders or leaders[-1] != leader):
                leaders.append(leader)
            if low >= len(requests):
                break
            while sent < len(requests) and sent - low < window:
                for raw in requests[sent:sent + chunk]:
                    for node_id in spec.node_ids():
                        comm.send_transaction(node_id, raw)
                sent += chunk
            time.sleep(0.05)
        audits = {
            node_id: control.try_call("delivered") or {}
            for node_id, control in controls.items()
        }
        launcher.observe_invariants()
        invariants_clean = launcher.monitor.clean
        first = audits[spec.node_ids()[0]]
        n_req = len(requests)
        sent_ids = request_ids_digest(requests)
        exactly_once = all(
            a.get("requests") == n_req and a.get("distinct") == n_req
            and a.get("ids_digest") == sent_ids
            for a in audits.values()
        )
        identical = all(
            a.get("digest") == first.get("digest") for a in audits.values()
        )
        decisions = int(first.get("decisions", 0))
        traffic = done("traffic", t0, {
            "ok": bool(
                exactly_once and identical and invariants_clean
                and decisions * size["batch"] >= n_req
                and len(leaders) - 1 >= 3
            ),
            "requests_sent": sent,
            "requests_committed_min": low,
            "replicas_delivered_all_exactly_once": exactly_once,
            "ledgers_identical": identical,
            "decisions": decisions,
            "leader_rotations_seen": len(leaders) - 1,
            "invariants": launcher.monitor.summary(),
            "presign_secs": round(presign_secs, 3),
        })

        # ---- 3. the device did it ---------------------------------------
        t0 = time.monotonic()
        # Let the last decision's trailing verifies land before reading.
        settled, seen = 0, None
        while settled < 2 and time.monotonic() - t0 < 30.0:
            now = (launcher.sidecars["sc-0"].probe() or {}).get("offered")
            settled = settled + 1 if now == seen else 0
            seen = now
            time.sleep(0.5)
        health = launcher.health()
        sc = health.get("sc-0") or {}
        clients = {
            name: (h or {}).get("sidecar") or {}
            for name, h in health.items() if name.startswith("replica-")
        }
        sent_sigs = sum(c.get("sent", 0) for c in clients.values())
        served_sigs = sum(c.get("served", 0) for c in clients.values())
        fallen_back = sum(c.get("fallen_back", 1) for c in clients.values())
        suspects = [name for name, c in clients.items() if c.get("suspect", True)]
        launches = sc.get("launches_after_ready", 0)
        dev_sigs = sc.get("device_signatures", -1)
        dev_lanes = sc.get("device_lanes", 0)
        restarts = launcher.sidecars["sc-0"].restarts
        did_it = done("device", t0, {
            "ok": bool(
                sc.get("platform") == want_platform
                and sc.get("compiles_after_ready") == 0
                and dev_sigs == sent_sigs == served_sigs
                and dev_sigs >= n_req * 2 * f
                and fallen_back == 0 and not suspects
                and sc.get("device_suspect") is False
                and sc.get("degrade_count") == 0
                and sc.get("host_signatures") == 0
                and restarts == 0
            ),
            "platform": sc.get("platform"),
            "compiles_after_ready": sc.get("compiles_after_ready"),
            "device_signatures": dev_sigs,
            "replica_signatures_sent": sent_sigs,
            "replica_signatures_served": served_sigs,
            "replica_signatures_bypassed": sum(
                c.get("bypassed", 0) for c in clients.values()),
            "floor_committed_x_2f": n_req * 2 * f,
            "client_fallbacks": fallen_back,
            "suspect_clients": suspects,
            "device_suspect": sc.get("device_suspect"),
            "degrade_count": sc.get("degrade_count"),
            "sidecar_host_signatures": sc.get("host_signatures"),
            "sidecar_restarts": restarts,
            # Counts, not rates.
            "launches": launches,
            "signatures_per_launch_mean": (
                round(dev_sigs / launches, 1) if launches else None),
            "padded_lane_share": (
                round(1.0 - dev_sigs / dev_lanes, 4) if dev_lanes else None),
        })

        # ---- 4. verdicts -------------------------------------------------
        t0 = time.monotonic()
        lanes = int(sc.get("lanes") or 0)
        if not lanes:
            raise RuntimeError("sidecar unreachable: no launch shape reported")
        wave, planted = _ed25519_wave(lanes, seed)
        from consensus_tpu.models import Ed25519BatchVerifier

        want = Ed25519BatchVerifier().verify_host(*wave)
        client = SidecarVerifierClient(
            spec.sidecar_addresses()["sc-0"], auth_secret=spec.auth_secret,
            request_timeout=max(120.0, size["request_timeout"]),
        )
        try:
            got = client.verify_batch(*wave)
        finally:
            client.close()
        after = launcher.sidecars["sc-0"].probe() or {}
        report = _verdict_report(got, want, planted)
        report["ok"] = bool(
            report["ok"]
            and after.get("compiles_after_ready") == 0
            and after.get("device_signatures") == dev_sigs + lanes
            and after.get("device_suspect") is False
        )
        report["launches"] = after.get("launches_after_ready", 0) - launches
        report["compiles_after_ready"] = after.get("compiles_after_ready")
        verdicts = done("verdicts", t0, report)
        ok = bool(traffic["ok"] and did_it["ok"] and verdicts["ok"])
    except Exception as exc:  # a phase crashed: report it, still tear down
        import traceback

        traceback.print_exc()
        done("error", time.monotonic(), {"ok": False, "error": repr(exc)})
        ok = False
    finally:
        t0 = time.monotonic()
        if comm is not None:
            comm.stop()
        try:
            summary = launcher.stop()
            teardown = {"ok": True, "orphans": summary["orphans"],
                        "leaked_ports": summary["leaked_ports"],
                        "restarts": summary["restarts"]}
        except AssertionError as exc:
            teardown = {"ok": False, "error": str(exc)}
        # Keep cluster.json and the flight records; drop the WALs.
        for r in spec.replicas:
            shutil.rmtree(os.path.dirname(r.wal_dir), ignore_errors=True)
        done("teardown", t0, teardown)
    return {"ok": bool(ok and teardown["ok"]), "device": device,
            "phases": phases}


# ------------------------------------------------------- phase 5: census


def census_child(lane: str, n: int, seed: int, dry_run: bool) -> int:
    """Body of one census child: this process owns the chip.  Prints one
    JSON line."""
    env_flags, curve, knobs, check = LANES[lane]
    for key, value in env_flags.items():
        if os.environ.get(key) != value:
            raise SystemExit(f"lane {lane} needs {key}={value} in the environment")
    import jax

    from consensus_tpu.parallel.topology import apply_compile_cache

    cache_dir = apply_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != ("cpu" if dry_run else "tpu"):
        print(f"chip_smoke census: lane {lane} found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2

    from consensus_tpu.config import Configuration
    from consensus_tpu.models import engine_for_config
    from consensus_tpu.obs.kernels import KERNELS

    config = Configuration(self_id=1, crypto_tpu_min_batch=1, **knobs)
    engine = engine_for_config(config, curve, pad_to=n)
    record = {"lane": lane, "dry_run": dry_run, "platform": dev.platform,
              "device_kind": dev.device_kind,
              "device_count": len(jax.devices()),
              "engine": type(engine).__name__,
              "lanes": n, "env": env_flags}
    t0 = time.monotonic()
    if check == "verdicts":
        wave, planted = (_p256_wave if curve == "p256" else _ed25519_wave)(n, seed)
        got = engine.verify_batch(*wave)
        record["first_call_secs"] = round(time.monotonic() - t0, 3)
        # The same wave again, compiled: first minus repeat is the cold
        # compile (set-up accounting, not a rate — the wave is 1% forgeries).
        t1 = time.monotonic()
        again = engine.verify_batch(*wave)
        record["warm_repeat_secs"] = round(time.monotonic() - t1, 3)
        record.update(_verdict_report(got, engine.verify_host(*wave), planted))
        record["ok"] = bool(record["ok"] and list(again) == list(got))
    else:
        record.update(_halfagg_check(engine, n, seed, t0))
    ledger = KERNELS.snapshot()
    record["kernels"] = {
        name: {"launches": s["launches"], "compiles": s["compiles"]}
        for name, s in ledger.items()
    }
    record["ok"] = bool(record["ok"] and ledger)  # a device kernel did run
    record["cache_dir"] = cache_dir
    print(json.dumps(record, sort_keys=True), flush=True)
    return 0 if record["ok"] else 1


def _halfagg_check(engine, n: int, seed: int, t0: float) -> dict:
    """Half-aggregated certs on the engine's device MSM: (a) aggregating a
    wave with bad lanes localizes exactly the lanes strict host
    verification rejects; (b) the honest remainder aggregates into ONE cert
    the device and the big-int host twin both accept; (c) both reject it
    once the aggregate scalar is tampered."""
    from consensus_tpu.models.aggregate import HalfAggregator

    wave, planted = _ed25519_wave(n, seed)
    msgs, sigs, keys = wave
    device = HalfAggregator(engine=engine)
    host = HalfAggregator(min_device_batch=10**9)
    agg, bad = device.aggregate(msgs, sigs, keys)
    first_call_secs = round(time.monotonic() - t0, 3)
    strict_bad = [
        i for i, ok in enumerate(engine.verify_host(msgs, sigs, keys)) if not ok
    ]
    good = [i for i in range(n) if i not in set(strict_bad)]
    g = ([msgs[i] for i in good], [sigs[i] for i in good], [keys[i] for i in good])
    cert, cert_bad = device.aggregate(*g)
    checks = {"bad_lanes_localized": agg is None and list(bad) == strict_bad
              and strict_bad == sorted(planted)}
    if cert is not None:
        rs, s_agg = cert
        tampered = bytes([s_agg[0] ^ 1]) + s_agg[1:]
        checks["honest_cert_device"] = device.verify(g[0], list(rs), s_agg, g[2])
        checks["honest_cert_host_twin"] = host.verify(g[0], list(rs), s_agg, g[2])
        checks["tampered_cert_device"] = not device.verify(g[0], list(rs), tampered, g[2])
        checks["tampered_cert_host_twin"] = not host.verify(g[0], list(rs), tampered, g[2])
    else:
        checks["honest_cert_aggregated"] = False
    return {
        "ok": all(checks.values()) and not cert_bad,
        "first_call_secs": first_call_secs,
        "signatures": n, "rejected": len(strict_bad),
        "planted": {str(k): v for k, v in sorted(planted.items())},
        "checks": checks,
        "aggregate_checks": device.aggregate_checks,
    }


def _last_exception(stderr: str) -> str:
    """The last ``SomeError: message`` line of a child's traceback."""
    import re

    hits = re.findall(r"^[\w.]*(?:Error|Exception)\b.*$", stderr, flags=re.M)
    lines = stderr.strip().splitlines()
    return (hits or lines or ["no output"])[-1][:600]


def run_census(size: dict, seed: int, *, dry_run: bool, emit) -> dict:
    """One child process per lane, in order, each owning the chip in turn."""
    lanes: dict = {}
    for lane, (env_flags, _curve, _knobs, _check) in LANES.items():
        env = dict(os.environ, **env_flags)
        if dry_run:
            env["JAX_PLATFORMS"] = "cpu"
        argv = [sys.executable, os.path.abspath(__file__), "--census-child",
                lane, "--seed", str(seed)] + (["--dry-run"] if dry_run else [])
        t0 = time.monotonic()
        try:
            proc = subprocess.run(
                argv, env=env, cwd=REPO, capture_output=True, text=True,
                timeout=size["lane_timeout"],
            )
            lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
            record = json.loads(lines[-1]) if lines else {
                "lane": lane, "ok": False, "error": _last_exception(proc.stderr),
            }
            record["exit_code"] = proc.returncode
            record["ok"] = bool(record.get("ok") and proc.returncode == 0)
            if not record["ok"]:
                sys.stderr.write(proc.stderr[-4000:])
        except subprocess.TimeoutExpired:
            record = {"lane": lane, "ok": False,
                      "error": f"timed out after {size['lane_timeout']}s"}
        record.update(phase="census", dry_run=dry_run,
                      wall_secs=round(time.monotonic() - t0, 3))
        lanes[lane] = record
        emit(record)
    return {"ok": all(r["ok"] for r in lanes.values()), "lanes": lanes}


# ------------------------------------------------------------------ main


def _parent_backends() -> list:
    """JAX backends THIS process initialised (must stay empty)."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    return sorted(getattr(bridge, "_backends", {})) if bridge else []


def contract_line(ok: bool, device) -> str | None:
    """The last line of stdout: exactly ``ok`` and ``device`` (``platform``,
    ``kind``, ``count``), the device as the process that held it reported it.
    None — print nothing — when no process reported a device."""
    if not device or not device.get("platform"):
        return None
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]),
        "kind": str(device["kind"]),
        "count": int(device["count"]),
    }})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU rehearsal at a tiny size; not a chip result")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "chip_smoke"))
    ap.add_argument("--phases", default="served,census",
                    help="comma list of: served (phases 1-4), census")
    ap.add_argument("--census-child", metavar="LANE", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    size = DRY if args.dry_run else REAL
    sys.path.insert(0, REPO)

    if args.census_child is not None:
        return census_child(
            args.census_child, size["census_lanes"], args.seed, args.dry_run
        )

    pinned_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if not args.dry_run and pinned_cpu:
        print("chip_smoke: JAX_PLATFORMS=cpu — this check needs the TPU "
              "(use --dry-run for the CPU rehearsal)", file=sys.stderr)
        return 2
    if args.dry_run and not pinned_cpu:
        print("chip_smoke: --dry-run rehearses on the CPU backend; run it "
              "with JAX_PLATFORMS=cpu so it cannot take the chip",
              file=sys.stderr)
        return 2

    os.makedirs(args.out, exist_ok=True)
    t_start = time.monotonic()
    records: list = []

    def emit(record: dict) -> None:
        records.append(record)
        slim = {k: v for k, v in record.items() if k != "kernels"}
        print(json.dumps(slim, sort_keys=True), flush=True)

    phases = [p for p in args.phases.split(",") if p]
    ok = True
    device = None
    served = census = None
    if "served" in phases:
        served = run_served_path(size, args.out, args.seed,
                                 dry_run=args.dry_run, emit=emit)
        if served.get("no_device"):
            return 2
        ok = ok and served["ok"]
        device = served["device"]
    if "census" in phases:
        census = run_census(size, args.seed, dry_run=args.dry_run, emit=emit)
        ok = ok and census["ok"]
        if device is None:
            for r in census["lanes"].values():
                if r.get("platform"):
                    device = {"platform": r["platform"],
                              "kind": r.get("device_kind"),
                              "count": r.get("device_count")}
                    break
    backends = _parent_backends()
    ok = bool(ok and not backends and device is not None)
    lanes = (census or {}).get("lanes") or {}
    summary = {
        "ok": ok,
        "device": device,
        "dry_run": args.dry_run,
        "seed": args.seed,
        "source": SOURCE,
        "assumed": ASSUMED,
        "size": {k: size[k] for k in
                 ("n", "batch", "pool", "clients", "requests", "census_lanes")},
        "parent_backends": backends,
        "wall_secs": round(time.monotonic() - t_start, 3),
        "phase_wall_secs": {
            r.get("lane") or r["phase"]: r.get("wall_secs") for r in records
        },
        # Set-up seconds, not rates.  Sidecar: its warm-up waves (both launch
        # widths), keyed by the full width.  Lanes: first call minus the same wave repeated
        # (half-agg lanes report their first call only).
        "cold_compile_secs": {
            **((served or {}).get("phases", {}).get("rig", {})
               .get("cold_compile_secs") or {}),
            **{lane: round(r["first_call_secs"] - r["warm_repeat_secs"], 3)
               for lane, r in lanes.items()
               if r.get("first_call_secs") is not None
               and r.get("warm_repeat_secs") is not None},
        },
        "first_call_secs": {
            lane: r.get("first_call_secs") for lane, r in lanes.items()
        },
        "phases": {r.get("lane") or r["phase"]: bool(r.get("ok")) for r in records},
        "claim": None,
    }
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as fh:
        json.dump({"summary": summary, "records": records}, fh, indent=1,
                  sort_keys=True)
    print(json.dumps(summary), flush=True)
    line = contract_line(ok, device)
    if line is not None:
        print(line, flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
