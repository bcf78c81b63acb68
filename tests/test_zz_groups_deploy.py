"""Sharded deployment rig tests: the free_ports TOCTOU fix and N consensus
groups as real OS-process clusters over one shared sidecar fleet.

Sorts alphabetically last (after test_zz_deploy_rig) on purpose: the
subprocess tests must not displace the fast suite inside the tier-1 time
budget.

* ``test_port_reservations_never_collide_concurrently`` — tier-1, no
  processes: the bind-and-hold regression gate for the generate-to-spawn
  port race.
* ``test_two_groups_share_one_fleet_as_processes`` — tier-1: 2 groups x 3
  replicas + one shared sidecar boot as 7 real processes, each group
  orders its own decisions through the SHARED verifier fleet, teardown
  leaves zero orphans and zero leaked ports.
"""

import threading

from consensus_tpu.deploy.identity import make_client_keyring
from consensus_tpu.deploy.spec import ClusterSpec, PortReservation, free_ports
from consensus_tpu.groups.deploy import ShardedClusterLauncher, ShardedDeploySpec
from consensus_tpu.net import TcpComm

#: Driver-side transport ids (outside the replica id range), one per group.
_CLIENT_ID = 900


# --- satellite: the free_ports TOCTOU fix -----------------------------------


def test_port_reservation_holds_until_release():
    r = PortReservation(6)
    assert r.held and len(set(r.ports)) == 6
    # While held, nobody else can be handed these ports.
    for _ in range(5):
        assert not (set(free_ports(16)) & set(r.ports))
    other = PortReservation(16)
    assert not (set(other.ports) & set(r.ports))
    other.release()
    r.release()
    r.release()  # idempotent
    assert not r.held


def test_port_reservations_never_collide_concurrently(tmp_path):
    """The regression gate: many launchers generating specs CONCURRENTLY
    (hold_ports=True) must draw pairwise-disjoint port sets — under the
    old bind-then-close free_ports, overlaps were routine."""
    specs = []
    lock = threading.Lock()

    def generate(i):
        spec = ClusterSpec.generate(
            3, 1, str(tmp_path / f"c{i}"), hold_ports=True
        )
        with lock:
            specs.append(spec)

    threads = [
        threading.Thread(target=generate, args=(i,)) for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert len(specs) == 8
        port_sets = []
        for spec in specs:
            assert spec.ports_held
            ports = {r.port for r in spec.replicas}
            ports |= {r.sync_port for r in spec.replicas}
            ports |= {r.control_port for r in spec.replicas}
            ports |= {s.port for s in spec.sidecars}
            ports |= {s.control_port for s in spec.sidecars}
            port_sets.append(ports)
        for i in range(len(port_sets)):
            for j in range(i + 1, len(port_sets)):
                assert not (port_sets[i] & port_sets[j]), (i, j)
    finally:
        for spec in specs:
            spec.release_ports()
    assert not specs[0].ports_held


def test_spec_without_hold_releases_immediately(tmp_path):
    spec = ClusterSpec.generate(2, 1, str(tmp_path))
    assert not spec.ports_held
    spec.release_ports()  # no-op, never raises


# --- the sharded rig --------------------------------------------------------


class _GroupInjector:
    """Driver-side request source for ONE group's spec (signs with that
    group's derived client keys, broadcasts over authenticated TcpComm)."""

    def __init__(self, spec, client_id):
        self.spec = spec
        self.keyring = make_client_keyring(spec.key_namespace, spec.clients)
        addresses = dict(spec.comm_addresses())
        addresses[client_id] = ("127.0.0.1", free_ports(1)[0])
        self.comm = TcpComm(
            client_id, addresses, lambda *a: None,
            reconnect_backoff=0.05, auth_secret=spec.auth_secret,
        )
        self.comm.start()
        self._seq = 0

    def submit(self, n):
        for _ in range(n):
            s = self._seq
            self._seq += 1
            client = s % self.spec.clients
            raw = self.keyring.make_request(client, (client << 32) | s)
            for node_id in self.spec.node_ids():
                self.comm.send_transaction(node_id, raw)

    def stop(self):
        self.comm.stop()


def test_two_groups_share_one_fleet_as_processes(tmp_path):
    """2 groups x 3 replicas + ONE shared sidecar boot as 7 real OS
    processes; both groups order decisions, only the fleet-owning
    launcher runs sidecar processes, and teardown leaves zero orphans
    and zero leaked ports in EVERY group."""
    sharded = ShardedDeploySpec.generate(
        2, 3, 1, str(tmp_path),
        # Host-only shared sidecar: this rig proves process sharing, not
        # the device path (no backend opened, nothing compiled).
        config_overrides={
            "request_batch_max_count": 1, "crypto_tpu_min_batch": 10**9,
        },
    )
    # Shared fleet, disjoint identities: same sidecar addresses + auth
    # secret everywhere, per-group key namespaces.
    s0, s1 = (sharded.specs[g] for g in sharded.group_ids())
    assert s0.sidecar_addresses() == s1.sidecar_addresses()
    assert s0.auth_secret_hex == s1.auth_secret_hex
    assert s0.key_namespace != s1.key_namespace
    assert s0.ports_held and s1.ports_held

    launcher = ShardedClusterLauncher(sharded)
    injectors = []
    try:
        launcher.start(timeout=120)
        assert not s0.ports_held  # released just before spawn
        # Exactly one launcher owns sidecar processes.
        owners = [
            gid for gid, sub in launcher.launchers.items() if sub.sidecars
        ]
        assert owners == [sharded.group_ids()[0]]
        for i, gid in enumerate(sharded.group_ids()):
            injector = _GroupInjector(sharded.specs[gid], _CLIENT_ID + i)
            injectors.append(injector)
            injector.submit(8)
        assert launcher.wait_heights(8, timeout=90), launcher.heights()
        launcher.observe_invariants()
        for sub in launcher.launchers.values():
            sub.monitor.assert_clean()
    finally:
        for injector in injectors:
            injector.stop()
        summaries = launcher.stop()  # raises on orphans / leaked ports
    for gid, summary in summaries.items():
        assert summary["orphans"] == [], gid
        assert summary["leaked_ports"] == [], gid
