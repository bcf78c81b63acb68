"""Unit tests for the process-per-replica deployment rig's host-side
pieces: cluster spec round-trip, the JSON control channel, supervisor
restart/backoff/flight-record behavior (against a trivial child — no jax
import, so these stay fast), the cross-process invariant monitor, the
fleet autoscaler's pure decision function, and the seeded chaos schedule.

The real-cluster smoke and chaos acceptance runs live in
tests/test_zz_deploy_rig.py (subprocess-heavy; alphabetically last so
they never displace the rest of the tier-1 suite inside its time budget).
"""

import json
import os
import signal
import sys
import time

import pytest

from consensus_tpu.deploy import (
    AutoscaleDecision,
    ClusterSpec,
    ControlClient,
    ControlServer,
    DeployInvariantMonitor,
    FleetAutoscaler,
    NodeSupervisor,
    ProcessChaosSchedule,
)


# --------------------------------------------------------------- spec


def test_cluster_spec_roundtrip(tmp_path):
    spec = ClusterSpec.generate(
        3, 2, str(tmp_path), clients=5,
        config_overrides={"view_change_timeout": 2.5},
    )
    assert len(spec.replicas) == 3 and len(spec.sidecars) == 2
    # 3 ports per replica + 2 per sidecar, all distinct.
    ports = [p for r in spec.replicas
             for p in (r.port, r.sync_port, r.control_port)]
    ports += [p for s in spec.sidecars for p in (s.port, s.control_port)]
    assert len(set(ports)) == len(ports)
    path = spec.write()
    assert os.path.basename(path) == "cluster.json"
    loaded = ClusterSpec.load(path)
    assert loaded.node_ids() == [1, 2, 3]
    assert loaded.auth_secret == spec.auth_secret
    assert loaded.comm_addresses() == spec.comm_addresses()
    assert loaded.sidecar_addresses() == spec.sidecar_addresses()
    assert loaded.config_overrides == {"view_change_timeout": 2.5}
    config = loaded.make_configuration(2)
    assert config.self_id == 2
    assert config.view_change_timeout == 2.5
    # Boot-time extras land without mutating the frozen dataclass.
    assert loaded.make_configuration(2, sync_on_start=True).sync_on_start


def test_cluster_spec_add_sidecar_mints_fresh_id(tmp_path):
    spec = ClusterSpec.generate(1, 1, str(tmp_path))
    sc = spec.add_sidecar()
    assert sc.sidecar_id == "sc-1"
    assert len(spec.sidecars) == 2
    spec.write()
    assert len(ClusterSpec.load(spec.config_path).sidecars) == 2


# ------------------------------------------------------------ control


def test_control_roundtrip_unknown_op_and_handler_crash():
    calls = []

    def echo(request):
        calls.append(request)
        return {"ok": True, "x": request.get("x")}

    server = ControlServer({
        "ping": lambda r: {"ok": True},
        "echo": echo,
        "boom": lambda r: 1 / 0,
    })
    try:
        client = ControlClient(server.address, timeout=2.0)
        assert client.wait_ready(5.0)
        assert client.call("echo", x=41) == {"ok": True, "x": 41}
        assert calls[-1]["x"] == 41
        # Unknown op and handler crash both answer, never kill the server.
        assert "error" in client.call("nope")
        assert "ZeroDivisionError" in client.call("boom")["error"]
        assert client.call("echo", x=1)["x"] == 1
    finally:
        server.close()
    # Closed server: try_call fails clean, no hang.
    assert ControlClient(server.address, timeout=0.5).try_call("ping") is None


# --------------------------------------------------------- supervisor


def _sleeper_argv():
    # A trivial child: no consensus imports, boots in milliseconds.
    return [sys.executable, "-c", "import time; time.sleep(600)"]


def test_supervisor_restarts_after_kill9_and_writes_flight_record(tmp_path):
    sup = NodeSupervisor(
        "unit-child",
        _sleeper_argv(),
        ("127.0.0.1", 1),  # no control socket; probes just answer None
        flight_dir=str(tmp_path / "flight"),
        backoff_initial=0.05,
        backoff_max=0.2,
        max_restarts=3,
        probe_timeout=0.2,
    )
    sup.start()
    first_pid = sup.pid
    assert sup.alive
    sup.kill(signal.SIGKILL)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if sup.restarts == 1 and sup.alive and sup.pid != first_pid:
            break
        time.sleep(0.05)
    assert sup.restarts == 1 and sup.alive and sup.pid != first_pid
    # Flight record captured the death forensics.
    assert sup.flight_records[0]["signal"] == "SIGKILL"
    assert sup.flight_records[0]["cause"] == "signal SIGKILL"
    records = os.listdir(tmp_path / "flight")
    assert any(r.startswith("unit-child-") for r in records)
    with open(tmp_path / "flight" / sorted(records)[0]) as fh:
        assert json.load(fh)["name"] == "unit-child"
    sup.stop()
    sup.assert_reaped()


def test_supervisor_stops_restart_budget_exhausted(tmp_path):
    # A child that dies instantly: the supervisor must give up after
    # max_restarts, not spin forever.
    sup = NodeSupervisor(
        "dying-child",
        [sys.executable, "-c", "raise SystemExit(3)"],
        ("127.0.0.1", 1),
        flight_dir=str(tmp_path / "flight"),
        backoff_initial=0.01,
        backoff_max=0.02,
        max_restarts=2,
        probe_timeout=0.2,
    )
    sup.start()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if sup.restarts == 2 and not sup.alive:
            time.sleep(0.2)  # would-be extra restart window
            break
        time.sleep(0.05)
    assert sup.restarts == 2 and not sup.alive
    assert len(sup.flight_records) == 3  # initial death + 2 restart deaths
    assert all(r["exit_code"] == 3 for r in sup.flight_records)
    sup.stop()
    sup.assert_reaped()


def test_supervisor_healthy_uptime_resets_restart_budget(tmp_path):
    # max_restarts caps CONSECUTIVE failures, not lifetime restarts: a
    # child that survives past healthy_uptime resets the budget, so a
    # soak can kill the same replica more times than max_restarts and
    # the supervisor keeps bringing it back.
    sup = NodeSupervisor(
        "soak-child",
        _sleeper_argv(),
        ("127.0.0.1", 1),
        flight_dir=str(tmp_path / "flight"),
        backoff_initial=0.01,
        backoff_max=0.05,
        max_restarts=1,
        healthy_uptime=0.3,
        probe_timeout=0.2,
    )
    sup.start()
    try:
        for kill_round in range(1, 4):  # 3 kills > max_restarts=1
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if sup.alive and (
                    time.monotonic() - sup._spawned_at
                ) >= 0.35:
                    break
                time.sleep(0.05)
            assert sup.alive, f"child not back before kill {kill_round}"
            sup.kill(signal.SIGKILL)
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if sup.restarts == kill_round and sup.alive:
                    break
                time.sleep(0.05)
            assert sup.restarts == kill_round and sup.alive, (
                f"supervisor gave up after kill {kill_round} "
                "(lifetime cap instead of consecutive-failure cap)"
            )
            # Every healthy death reset the budget.
            assert sup.consecutive_failures == 1
    finally:
        sup.stop()
    sup.assert_reaped()


def test_supervisor_suspend_is_not_a_death(tmp_path):
    sup = NodeSupervisor(
        "frozen-child",
        _sleeper_argv(),
        ("127.0.0.1", 1),
        flight_dir=str(tmp_path / "flight"),
        backoff_initial=0.05,
        probe_timeout=0.2,
    )
    sup.start()
    pid = sup.pid
    sup.suspend()
    time.sleep(0.3)
    # SIGSTOP: alive to the kernel, no restart fired, same pid.
    assert sup.alive and sup.pid == pid and sup.restarts == 0
    assert sup.flight_records == []
    sup.resume()
    assert sup.alive and sup.pid == pid
    sup.stop()
    sup.assert_reaped()


# --------------------------------------------------------- invariants


def test_invariant_monitor_prefix_agreement():
    mon = DeployInvariantMonitor()
    mon.observe(1, ["a", "b", "c"])
    mon.observe(2, ["a", "b"])          # shorter prefix: fine
    mon.observe(3, ["a", "b", "c", "d"])  # extends the chain: fine
    assert mon.clean
    assert len(mon.agreed) == 4
    mon.assert_clean()
    summary = mon.summary()
    assert summary["agreed_height"] == 4
    assert summary["reported_height"] == {"1": 3, "2": 2, "3": 4}


def test_invariant_monitor_flags_divergence_and_amnesia():
    mon = DeployInvariantMonitor()
    mon.observe(1, ["a", "b"])
    mon.observe(2, ["a", "x"])  # disagrees at height 1
    assert not mon.clean
    with pytest.raises(AssertionError, match="height 1"):
        mon.assert_clean()
    # Amnesia shape: a restarted node re-orders a different digest over an
    # already-visible height.
    mon2 = DeployInvariantMonitor()
    mon2.observe(1, ["a", "b", "c"])
    mon2.observe(1, ["a"])       # shorter after restart: legal
    assert mon2.clean
    mon2.observe(1, ["a", "z"])  # re-extends a DIFFERENT chain: violation
    assert not mon2.clean


# --------------------------------------------------------- autoscaler


def _signals(*triples):
    return [
        {"sidecar_id": sid, "offered": off, "rejected": rej,
         "engine_degraded": deg}
        for sid, off, rej, deg in triples
    ]


def test_autoscaler_scales_up_on_admission_overload():
    a = FleetAutoscaler(min_sidecars=1, max_sidecars=3, cooldown_evals=1)
    d = a.decide(_signals(("sc-0", 100, 60, False)))
    assert d.action == "scale_up" and "admission_overload" in d.reason
    # Cooldown right after an action.
    assert a.decide(_signals(("sc-0", 100, 60, False))).action is None


def test_autoscaler_drains_degraded_and_protects_min_fleet():
    a = FleetAutoscaler(min_sidecars=1, max_sidecars=3, cooldown_evals=0)
    d = a.decide(_signals(("sc-0", 10, 0, False), ("sc-1", 10, 0, True)))
    assert d.action == "drain" and d.target == "sc-1"
    # Degraded at min fleet: add a replacement instead of draining to zero.
    d2 = a.decide(_signals(("sc-0", 10, 0, True)))
    assert d2.action == "scale_up"


def test_autoscaler_drains_calm_fleet_and_holds_steady():
    a = FleetAutoscaler(min_sidecars=1, max_sidecars=3, cooldown_evals=0,
                        min_offered=20)
    d = a.decide(_signals(("sc-0", 100, 1, False), ("sc-1", 100, 0, False)))
    assert d.action == "drain" and d.target == "sc-1"
    # Moderate rejects below the overload bar, above calm: hold.
    d2 = a.decide(_signals(("sc-0", 100, 20, False)))
    assert d2.action is None and d2.reason == "steady"
    assert isinstance(d2, AutoscaleDecision)


def test_autoscaler_run_once_applies_decision():
    class FakeLauncher:
        def __init__(self):
            self.added = 0
            self.drained = []

        def sidecar_signals(self):
            return _signals(("sc-0", 50, 40, False))

        def add_sidecar(self):
            self.added += 1

        def drain_sidecar(self, sid):
            self.drained.append(sid)

    launcher = FakeLauncher()
    a = FleetAutoscaler(min_sidecars=1, max_sidecars=2, cooldown_evals=0)
    d = a.run_once(launcher)
    assert d.action == "scale_up" and launcher.added == 1
    assert a.history[-1] is d


# -------------------------------------------------------------- chaos


class _FakeRigLauncher:
    """Launcher double recording chaos verbs (no processes)."""

    def __init__(self, replica_ids=(1, 2, 3, 4, 5), sidecar_ids=("sc-0",)):
        self.replicas = {i: None for i in replica_ids}
        self.sidecars = {s: None for s in sidecar_ids}
        self.calls = []

    def leader_id(self):
        return min(self.replicas)

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)

        def verb(*args, **kw):
            self.calls.append((name,) + args)
        return verb


def test_chaos_schedule_is_seed_deterministic():
    runs = []
    for _ in range(2):
        launcher = _FakeRigLauncher()
        sched = ProcessChaosSchedule(launcher, seed=42)
        for _ in range(8):
            sched.step()
        runs.append([(r["action"], r["target"]) for r in sched.history])
    assert runs[0] == runs[1]
    assert len({a for a, _ in runs[0]}) >= 3  # a real mix of verbs


def test_chaos_schedule_heals_transients_next_step():
    launcher = _FakeRigLauncher()
    sched = ProcessChaosSchedule(
        launcher, seed=0,
        weights={"freeze": 1},  # force the transient verb
    )
    sched.step()
    assert launcher.calls[-1][0] == "freeze_replica"
    frozen = launcher.calls[-1][1]
    sched.step()  # heals before acting again
    assert ("thaw_replica", frozen) in launcher.calls
    sched.quiesce()
    thaws = [c for c in launcher.calls if c[0] == "thaw_replica"]
    freezes = [c for c in launcher.calls if c[0] == "freeze_replica"]
    assert len(thaws) == len(freezes)


def test_chaos_schedule_skips_sidecar_verb_without_fleet():
    launcher = _FakeRigLauncher(sidecar_ids=())
    sched = ProcessChaosSchedule(
        launcher, seed=1, weights={"kill9_sidecar": 1, "kill9_follower": 1},
    )
    for _ in range(6):
        sched.step()
    assert all(r["action"] != "kill9_sidecar" for r in sched.history)


# ------------------------------------------------- launcher: one chip owner


#: A stand-in rig child: answers ping/health/exit on the given control port
#: and reports the JAX platform pin it was started with.
_ENV_CHILD = """
import os, sys, threading
from consensus_tpu.deploy.control import ControlServer
done = threading.Event()
ControlServer(
    {"ping": lambda r: {"ok": True},
     "health": lambda r: {"ok": True,
                          "jax_platforms": os.environ.get("JAX_PLATFORMS")},
     "exit": lambda r: (done.set(), {"ok": True})[1]},
    port=int(sys.argv[1]),
)
done.wait()
"""


def test_launcher_pins_everything_but_the_sidecar_to_the_cpu(
    tmp_path, monkeypatch
):
    """Replica (and driver) children carry JAX_PLATFORMS=cpu; the sidecar's
    platform is left to the environment — it is the one process of a rig
    that may hold the chip."""
    from consensus_tpu.deploy import ClusterLauncher

    monkeypatch.setenv("JAX_PLATFORMS", "as-the-operator-set-it")
    spec = ClusterSpec.generate(2, 1, str(tmp_path))
    launcher = ClusterLauncher(spec, restart=False)
    monkeypatch.setattr(
        launcher, "_replica_argv",
        lambda node_id: [sys.executable, "-c", _ENV_CHILD,
                         str(spec.replica(node_id).control_port)],
    )
    monkeypatch.setattr(
        launcher, "_sidecar_argv",
        lambda sid: [sys.executable, "-c", _ENV_CHILD,
                     str(spec.sidecar(sid).control_port)],
    )
    try:
        launcher.start(timeout=60)
        health = launcher.health()
        assert health["sc-0"]["jax_platforms"] == "as-the-operator-set-it"
        assert health["replica-1"]["jax_platforms"] == "cpu"
        assert health["replica-2"]["jax_platforms"] == "cpu"
        assert launcher.cpu_env["JAX_PLATFORMS"] == "cpu"  # the driver's env
    finally:
        launcher.stop()


def test_sidecar_that_exits_at_boot_fails_start_without_restart(
    tmp_path, monkeypatch
):
    """A sidecar that cannot get its device exits non-zero with one line;
    ``start`` fails on it at once — no restart loop behind replicas that
    would verify on the host, and no replica is ever spawned."""
    from consensus_tpu.deploy import ClusterLauncher

    spec = ClusterSpec.generate(2, 1, str(tmp_path))
    launcher = ClusterLauncher(spec, backoff_initial=0.05)
    monkeypatch.setattr(
        launcher, "_sidecar_argv",
        lambda sid: [sys.executable, "-c",
                     "import sys; print('sc-0: no TPU for this process', "
                     "file=sys.stderr); sys.exit(3)"],
    )
    started = time.monotonic()
    try:
        with pytest.raises(RuntimeError, match="exited with code 3 at boot"):
            launcher.start(timeout=60)
        assert time.monotonic() - started < 30.0
        time.sleep(0.5)  # a restart, were one armed, would have fired by now
        sup = launcher.sidecars["sc-0"]
        assert sup.restarts == 0 and not sup.alive
        assert "no TPU" in sup.boot_failure()
        assert launcher.replicas == {}
    finally:
        launcher.stop()


def test_device_sidecar_refuses_to_serve_without_a_tpu(tmp_path):
    """The real sidecar main, platform NOT pinned to the CPU, on a host
    where jax finds no TPU: one clear line, exit code EXIT_NO_DEVICE —
    never a silent host-serving sidecar.  (A host-only spec opens no
    backend at all and is unaffected.)"""
    import subprocess

    from consensus_tpu.deploy.sidecar_main import EXIT_NO_DEVICE

    spec = ClusterSpec.generate(
        4, 1, str(tmp_path), config_overrides={"request_batch_max_count": 64}
    )
    spec.write()
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "consensus_tpu.deploy.sidecar_main",
         "--config", spec.config_path, "--sidecar-id", "sc-0"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    if "platform='tpu'" in proc.stderr or proc.returncode == 0:
        pytest.skip("this host has a TPU: the refusal path cannot be shown")
    assert proc.returncode == EXIT_NO_DEVICE, proc.stderr[-2000:]
    last = proc.stderr.strip().splitlines()[-1]
    assert "no TPU for this process" in last and "refusing" in last
    assert proc.stdout.strip() == ""  # never printed ready


def test_orchestrator_side_opens_no_jax_backend(tmp_path):
    """What chip_smoke.py and scripts/soak.py do in their own
    process — import the rig, mint a spec, build a launcher, sign
    requests, verify on the host — initialises no jax backend: the chip
    stays free for the sidecar."""
    import subprocess

    code = """
import sys, tempfile
sys.path.insert(0, %r)
import chip_smoke
import scripts.soak
from consensus_tpu.deploy import ClusterLauncher, ClusterSpec
from consensus_tpu.deploy.identity import make_client_keyring
from consensus_tpu.models import Ed25519BatchVerifier
spec = ClusterSpec.generate(4, 1, tempfile.mkdtemp(dir=%r))
ClusterLauncher(spec)
ring = make_client_keyring(spec.key_namespace, 2)
ring.make_request(0, 1)
wave, planted = chip_smoke._ed25519_wave(16, 3)
assert not Ed25519BatchVerifier().verify_host(*wave).all()
print(chip_smoke._parent_backends())
""" % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))), str(tmp_path))
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "[]"
