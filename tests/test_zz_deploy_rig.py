"""Real-cluster tests for the process-per-replica deployment rig.

Every test here boots actual OS processes (``python -m
consensus_tpu.deploy.replica_main`` et al.) over real TCP sockets and
file-backed WALs.  The file sorts alphabetically LAST on purpose: the
tier-1 suite is time-budget-bound, and these subprocess tests must not
displace the faster suite's coverage inside that budget.

* ``test_cluster_smoke_orders_decisions`` — tier-1: 3 replicas + 1
  sidecar as subprocesses, ~20 decisions through real sockets, clean
  shutdown with zero orphaned processes.
* ``test_acceptance_kill9_leader_sidecar_and_rejoin`` (@slow) — the
  5-replica (f=1) acceptance run: kill -9 the leader (view change
  completes, ordering resumes), kill -9 a sidecar (verification reroutes
  through the fleet), supervisor restart of the killed replica (rejoins
  via verified sync off its intact WAL) — invariant monitor clean, no
  orphans or leaked ports at teardown.
* ``test_soak_ci_scale`` (@slow) — ``scripts/soak.py --minutes 2``:
  trace-driven load + the seeded process-chaos loop end to end, rc 0
  with a JSON summary line.  The multi-hour soak is the same entry point
  run manually (README's deployment runbook).
"""

import json
import os
import subprocess
import sys
import time

import pytest

from consensus_tpu.deploy import ClusterLauncher, ClusterSpec
from consensus_tpu.deploy.identity import make_client_keyring
from consensus_tpu.deploy.spec import free_ports
from consensus_tpu.net import TcpComm

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: These rigs prove process lifecycles, not the device path: their sidecars
#: are declared host-only (no backend opened, nothing compiled).  The
#: device-path rig is tests/test_zz_chip_smoke.py.
_HOST_ONLY = {"crypto_tpu_min_batch": 10**9}

#: The ingress driver's transport id (outside the replica id range).
_CLIENT_ID = 900


class _Injector:
    """Driver-side request source: signs with the cluster's derived client
    keys and broadcasts over an authenticated TcpComm, like driver_main."""

    def __init__(self, spec):
        self.spec = spec
        self.keyring = make_client_keyring(spec.key_namespace, spec.clients)
        addresses = dict(spec.comm_addresses())
        addresses[_CLIENT_ID] = ("127.0.0.1", free_ports(1)[0])
        self.comm = TcpComm(
            _CLIENT_ID, addresses, lambda *a: None,
            reconnect_backoff=0.05, auth_secret=spec.auth_secret,
        )
        self.comm.start()
        self._seq = 0

    def submit(self, n, pace=0.02):
        for _ in range(n):
            s = self._seq
            self._seq += 1
            client = s % self.spec.clients
            raw = self.keyring.make_request(client, (client << 32) | s)
            for node_id in self.spec.node_ids():
                self.comm.send_transaction(node_id, raw)
            time.sleep(pace)

    def stop(self):
        self.comm.stop()


def test_cluster_smoke_orders_decisions(tmp_path):
    """3 replicas + 1 sidecar as real subprocesses order ~20 decisions
    through real sockets; teardown leaves zero orphans / leaked ports."""
    spec = ClusterSpec.generate(
        3, 1, str(tmp_path),
        # 1 request = 1 decision
        config_overrides={"request_batch_max_count": 1, **_HOST_ONLY},
    )
    launcher = ClusterLauncher(spec)
    injector = None
    try:
        launcher.start(timeout=120)
        health = launcher.health()
        assert health["sc-0"]["role"] == "sidecar"
        assert all(
            health[f"replica-{i}"]["ok"] for i in spec.node_ids()
        )
        injector = _Injector(spec)
        injector.submit(20)
        assert launcher.wait_height(20, timeout=60), (
            f"cluster never reached height 20: {launcher.heights()}"
        )
        # Prefix agreement across every process's reported ledger.
        launcher.observe_invariants()
        launcher.monitor.assert_clean()
        assert len(launcher.monitor.agreed) >= 20
        # The sync and hand-over counters ride every replica's health
        # (cumulative; a static leader that nobody fell behind reads 0).
        for i in spec.node_ids():
            h = launcher.replicas[i].probe()
            assert {"syncs", "synced_decisions", "sync_pool_removed",
                    "leader_handovers"} <= set(h), sorted(h)
            assert h["synced_decisions"] >= 0 and h["sync_pool_removed"] >= 0
            assert h["leader_handovers"] == 0
        # The obs plane scrapes every replica over its control socket.
        bodies = launcher.scrape()
        assert set(bodies) == {f"replica-{i}" for i in spec.node_ids()}
        assert all("obs_sample_time" in b for b in bodies.values())
    finally:
        if injector is not None:
            injector.stop()
        summary = launcher.stop()  # raises on orphans / leaked ports
    assert summary["orphans"] == [] and summary["leaked_ports"] == []


@pytest.mark.slow
def test_acceptance_kill9_leader_sidecar_and_rejoin(tmp_path):
    """The ISSUE-16 acceptance run on a 5-replica (f=1) cluster."""
    spec = ClusterSpec.generate(
        5, 2, str(tmp_path),
        config_overrides={
            "view_change_timeout": 3.0,
            "view_change_resend_interval": 1.0,
            "leader_heartbeat_timeout": 2.0,
            "leader_heartbeat_count": 8,
            **_HOST_ONLY,
        },
    )
    # Supervisor backoff well past the view-change window: the killed
    # leader must come back AFTER the survivors elected a successor, so
    # the run proves the view change rather than a fast restart.
    launcher = ClusterLauncher(spec, backoff_initial=8.0)
    injector = None
    try:
        launcher.start(timeout=180)
        injector = _Injector(spec)
        injector.submit(5)
        assert launcher.wait_height(1, timeout=30)
        old_leader = launcher.leader_id()
        assert old_leader is not None

        # --- leg 1: kill -9 the current leader -> view change completes,
        # ordering resumes among the surviving 4 (quorum with f=1).
        launcher.kill_replica(old_leader)
        view_advanced = False
        deadline = time.monotonic() + 40.0
        while time.monotonic() < deadline:
            views = [
                h["view"]
                for i, sup in launcher.replicas.items()
                if i != old_leader and (h := sup.probe()) is not None
            ]
            if views and max(views) >= 1:
                view_advanced = True
                break
            time.sleep(0.2)
        assert view_advanced, "view change never completed after leader kill"
        h0 = max(launcher.heights().values())
        resumed = False
        deadline = time.monotonic() + 40.0
        while time.monotonic() < deadline:
            injector.submit(2)
            reached = sum(
                1 for v in launcher.heights().values() if v >= h0 + 1
            )
            if reached >= 4:
                resumed = True
                break
            time.sleep(0.5)
        assert resumed, f"ordering did not resume: {launcher.heights()}"
        new_leader = launcher.leader_id()
        assert new_leader != old_leader

        # --- leg 2: kill -9 one sidecar -> replicas reroute verification
        # through the surviving fleet member; ordering continues.
        launcher.kill_sidecar("sc-0")
        h1 = max(launcher.heights().values())
        ok = False
        deadline = time.monotonic() + 40.0
        while time.monotonic() < deadline:
            injector.submit(2)
            if sum(1 for v in launcher.heights().values() if v >= h1 + 1) >= 4:
                ok = True
                break
            time.sleep(0.5)
        assert ok, f"ordering stalled after sidecar kill: {launcher.heights()}"

        # --- leg 3: the supervisor restarts the killed replica; it rejoins
        # through verified sync off its intact WAL and catches up.
        target = max(launcher.heights().values())
        rejoined = False
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            h = launcher.replicas[old_leader].probe()
            if (h is not None and h.get("restarted")
                    and h.get("ledger", 0) >= target):
                rejoined = True
                break
            time.sleep(0.5)
        assert rejoined, (
            f"killed replica never rejoined: "
            f"{launcher.replicas[old_leader].probe()}"
        )
        assert launcher.replicas[old_leader].restarts >= 1

        launcher.observe_invariants()
        launcher.monitor.assert_clean()
    finally:
        if injector is not None:
            injector.stop()
        summary = launcher.stop()  # raises on orphans / leaked ports
    assert summary["orphans"] == [] and summary["leaked_ports"] == []


@pytest.mark.slow
def test_soak_ci_scale(tmp_path):
    """scripts/soak.py --minutes 2: trace-driven load + process chaos,
    obs scraping, invariant gating — rc 0 and a JSON summary line."""
    env = os.environ.copy()
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable, os.path.join(_REPO, "scripts", "soak.py"),
            "--minutes", "2", "--replicas", "3", "--sidecars", "1",
            "--period", "8", "--seed", "7",
            "--base-dir", str(tmp_path / "soak"),
        ],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["ok"] is True
    assert summary["invariants"]["violations"] == []
    assert summary["end_height"] > summary["start_height"]
    assert summary["chaos"], "chaos loop never fired"
    assert summary["scrapes"] > 0
    assert summary["teardown"]["orphans"] == []
    assert summary["teardown"]["leaked_ports"] == []
