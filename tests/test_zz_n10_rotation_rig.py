"""The n=10 deployment on the normal path, at a CPU size: ten replica
processes (f=3) with the leader handed on every 3 decisions, 256-byte signed
requests, and one DEVICE-PATH sidecar on the CPU backend whose ladder comes
from the same ``launch_widths`` rule the chip's does (batch 80: 10 x 90
signatures -> 1,024 lanes -> 256 / 512 / 1,024; at batch 1,000 the same rule
gives 4,096 / 8,192 / 16,384).  Every replica delivers every request exactly
once in one order, equal to the plain reference's digests; each rung gives
the host twin's verdicts; and the sidecar books the signatures each width
carried (``signatures_by_lanes``).

Nothing here is a chip result.  Subprocess-heavy and compiles (or loads) the
sidecar's three launch widths on the CPU: named to sort last, like the other
rig rehearsals.
"""

import time

import numpy as np
import pytest

from served_bench import reference
from served_bench.traffic import RequestFactory

N, BATCH, BODY = 10, 80, 256
INJECTOR_ID = 900
START_TIMEOUT = 900.0
TRAFFIC_TIMEOUT = 600.0


@pytest.fixture(scope="module")
def rig(tmp_path_factory):
    """ONE run: boot, 10 batches sent one at a time (each committed by every
    replica before the next is sent), the audits, then one planted wave per
    rung through the sidecar's socket; the readings for the tests below."""
    from consensus_tpu.deploy import ClusterLauncher, ClusterSpec
    from consensus_tpu.deploy.control import ControlClient
    from consensus_tpu.deploy.spec import free_ports
    from consensus_tpu.net import SidecarVerifierClient, TcpComm

    factory = RequestFactory(3_700_000_011, 16, BODY)
    spec = ClusterSpec.generate(
        N, 1, str(tmp_path_factory.mktemp("n10") / "cluster"), clients=16,
        config_overrides={
            "request_batch_max_count": BATCH,
            "request_pool_size": 4 * BATCH,
            "leader_rotation": True,
            "decisions_per_leader": 3,
            # a decision takes seconds on the CPU backend: keep the forward
            # timer (2 s shipped) clear of the pool's 5 s dedup horizon
            "request_forward_timeout": 60.0,
            "request_complain_timeout": 120.0,
        },
        hold_ports=True)
    spec.key_namespace = factory.namespace
    requests = factory.make_many(10 * BATCH)
    launcher = ClusterLauncher(spec)
    comm = client = None
    out = {"spec": spec, "requests": requests}
    try:
        launcher.start(timeout=START_TIMEOUT)
        out["ready"] = launcher.sidecars["sc-0"].probe()
        addresses = dict(spec.comm_addresses())
        addresses[INJECTOR_ID] = ("127.0.0.1", free_ports(1)[0])
        comm = TcpComm(INJECTOR_ID, addresses, lambda *a: None,
                       reconnect_backoff=0.05, auth_secret=spec.auth_secret,
                       send_queue_depth=2 * BATCH)
        comm.start()
        controls = {r.node_id: ControlClient((r.host, r.control_port), timeout=5.0)
                    for r in spec.replicas}
        deadline = time.monotonic() + TRAFFIC_TIMEOUT
        for lo in range(0, len(requests), BATCH):
            for raw in requests[lo:lo + BATCH]:
                for node_id in spec.node_ids():
                    comm.send_transaction(node_id, raw)
            while time.monotonic() < deadline:
                counts = [int((c.try_call("health") or {}).get("requests", 0))
                          for c in controls.values()]
                if min(counts) >= lo + BATCH:
                    break
                time.sleep(0.05)
        out["health"] = {node_id: c.try_call("health") or {}
                         for node_id, c in controls.items()}
        out["audits"] = {node_id: c.try_call("delivered") or {}
                         for node_id, c in controls.items()}
        launcher.observe_invariants()
        out["clean"] = launcher.monitor.clean
        out["after_traffic"] = launcher.sidecars["sc-0"].probe()
        client = SidecarVerifierClient(
            spec.sidecar_addresses()["sc-0"], auth_secret=spec.auth_secret,
            request_timeout=120.0)
        out["rungs"] = []
        # the smallest and the largest wave each rung takes
        for k, n in enumerate((64, 256, 257, 512, 513, 1024)):
            wave, planted = reference.verdict_wave(n, 37, k)
            got = [bool(v) for v in client.verify_batch(*wave)]
            out["rungs"].append((n, wave, planted, got))
        out["last"] = launcher.sidecars["sc-0"].probe()
    finally:
        if client is not None:
            client.close()
        if comm is not None:
            comm.stop()
        launcher.stop()
    return out


def test_the_ladder_is_the_launch_widths_rule_on_the_spec(rig):
    from consensus_tpu.deploy.sidecar_main import launch_widths

    spec, ready = rig["spec"], rig["ready"]
    assert spec.sidecar_wave_lanes() == 1024
    assert launch_widths(1024) == (256, 512, 1024)
    assert ready["platform"] == "cpu" and ready["lanes"] == 1024
    assert ready["compiles"] == 3 and ready["compiles_after_ready"] == 0
    assert ready["launches_by_lanes"] == {"256": 0, "512": 0, "1024": 0}
    assert ready["signatures_by_lanes"] == {"256": 0, "512": 0, "1024": 0}
    # The CPU backend: every width's field arithmetic is XLA code.
    assert ready["field_path_by_lanes"] == {"256": "xla", "512": "xla", "1024": "xla"}


def test_every_replica_delivers_every_request_once_in_the_references_order(rig):
    requests, audits = rig["requests"], rig["audits"]
    assert sorted(audits) == list(range(1, N + 1))
    for node_id, audit in audits.items():
        assert audit["requests"] == audit["distinct"] == len(requests), node_id
        assert audit["ids_digest"] == reference.ids_digest(requests), node_id
        assert audit["digest"] == reference.ordered_digest(requests), node_id
    assert rig["clean"]


def test_the_leader_was_handed_on_every_three_decisions(rig):
    for node_id, health in rig["health"].items():
        decisions = int(health["ledger"])
        assert decisions >= 10, node_id
        # 10 batches or more a replica: three hand-overs or more it saw
        assert int(health["leader_handovers"]) >= decisions // 3 - 1 >= 2, node_id


def test_each_rung_gives_the_host_twins_verdicts(rig):
    from consensus_tpu.models import Ed25519BatchVerifier

    host = Ed25519BatchVerifier()
    for n, wave, planted, got in rig["rungs"]:
        want = host.verify_host(*wave)
        assert got == want.tolist(), n
        assert got == reference.wave_verdicts(wave), n
        assert sorted(planted) == [i for i, ok in enumerate(want) if not ok], n
        assert len(planted) == 8


def test_the_sidecar_books_the_signatures_each_width_carried(rig):
    before, last = rig["after_traffic"], rig["last"]
    # the replicas' own waves rode the device: 2f signatures at least for
    # every request committed (the judge's device floor)
    assert before["device_signatures"] >= len(rig["requests"]) * 2 * 3
    for health in (before, last):
        by_lanes = health["signatures_by_lanes"]
        assert set(by_lanes) == set(health["launches_by_lanes"]) == {"256", "512", "1024"}
        assert sum(by_lanes.values()) == health["device_signatures"]
        for width, launches in health["launches_by_lanes"].items():
            assert by_lanes[width] <= launches * int(width)
            assert by_lanes[width] >= launches * 16  # min_device_batch
        assert health["host_signatures"] == 0
    # the planted waves: two a rung, each booked at the rung it selects
    grew = {w: last["signatures_by_lanes"][w] - before["signatures_by_lanes"][w]
            for w in ("256", "512", "1024")}
    assert grew == {"256": 64 + 256, "512": 257 + 512, "1024": 513 + 1024}
    assert last["compiles"] == 3 and last["compiles_after_ready"] == 0


class _Inner:
    """An engine with a three-rung ladder, as far as the wrapper asks."""

    def launch_width(self, n):
        return next(w for w in (8, 16, 32) if w >= n)

    def verify_batch(self, messages, signatures, public_keys):
        return np.ones(len(messages), dtype=bool)

    def verify_host(self, messages, signatures, public_keys):
        return np.ones(len(messages), dtype=bool)


@pytest.mark.parametrize("sizes", [
    (1, 3, 4, 5, 8, 9, 16, 17, 32), (32, 32, 4), (2, 3), ()])
def test_counting_engine_books_signatures_at_the_width_each_wave_rides(sizes):
    from consensus_tpu.deploy.sidecar_main import _CountingEngine

    engine = _CountingEngine(_Inner(), min_device_batch=4, lanes=32)
    for n in sizes:
        engine.verify_batch([b"m"] * n, [b"s"] * n, [b"k"] * n)
    engine.verify_host([b"m"] * 5, [b"s"] * 5, [b"k"] * 5)
    counts = engine.counts()
    device = [n for n in sizes if n >= 4]
    want_sigs, want_launches = {}, {}
    for n in device:
        width = engine.launch_width(n)
        want_sigs[width] = want_sigs.get(width, 0) + n
        want_launches[width] = want_launches.get(width, 0) + 1
    assert counts["signatures_by_lanes"] == want_sigs
    assert counts["launches_by_lanes"] == want_launches
    assert sum(counts["signatures_by_lanes"].values()) == counts["device_signatures"]
    assert counts["device_signatures"] == sum(device)
    assert counts["host_signatures"] == sum(sizes) - sum(device) + 5
    assert counts["offered"] == sum(sizes) + 5
