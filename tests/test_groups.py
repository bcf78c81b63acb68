"""Horizontal consensus sharding (consensus_tpu/groups/): placement
directory, admit-then-route, cross-group wave coalescing over one shared
verifier fleet, and the sharding acceptance gate — a 4-group
ShardedCluster must book strictly fewer, larger verify launches than four
private fleets on IDENTICAL total work, while every group's ledger stays
byte-identical to a standalone cluster run with the same derived seed.
"""

import threading

import pytest

from consensus_tpu.groups.cluster import ShardedCluster, group_seed
from consensus_tpu.groups.directory import (
    GROUPS_PLACEMENT_DOMAIN,
    GroupDirectory,
    group_ids,
)
from consensus_tpu.groups.router import GroupRouter
from consensus_tpu.groups.twopc import parse_twopc_payload, twopc_payload
from consensus_tpu.metrics import (
    GROUPS_COUNT_KEY,
    GROUPS_ROUTED_KEY,
    GROUPS_WAVE_MULTI_KEY,
    InMemoryProvider,
    Metrics,
)
from consensus_tpu.models import Ed25519Signer
from consensus_tpu.models.ed25519 import Ed25519BatchVerifier
from consensus_tpu.models.engine import FairShareWaveFormer
from consensus_tpu.testing.app import Cluster, make_request
from consensus_tpu.wire import SavedTwoPC, decode_saved, encode_saved

# --- placement directory ----------------------------------------------------


def test_directory_assignment_is_deterministic_and_total():
    d = GroupDirectory.of_size(4)
    assert d.groups() == ("group-0", "group-1", "group-2", "group-3")
    assert len(d) == 4
    tenants = [f"tenant-{i}" for i in range(200)]
    first = d.assignment_map(tenants)
    again = GroupDirectory.of_size(4).assignment_map(tenants)
    assert first == again
    assert set(first.values()) <= set(d.groups())
    # Rendezvous hashing spreads tenants: no group owns everything.
    owners = set(first.values())
    assert len(owners) >= 3


def test_directory_growth_remaps_boundedly():
    """Adding one group moves only tenants won by the newcomer — the
    rendezvous bound carried over from the ingress placement domain."""
    tenants = [f"t{i}" for i in range(400)]
    before = GroupDirectory.of_size(4).assignment_map(tenants)
    after = GroupDirectory.of_size(5).assignment_map(tenants)
    moved = [t for t in tenants if before[t] != after[t]]
    # Every move lands on the new group; nothing reshuffles among old ones.
    assert all(after[t] == "group-4" for t in moved)
    assert len(moved) < len(tenants) / 2


def test_directory_domain_is_distinct_from_ingress_placement():
    assert GROUPS_PLACEMENT_DOMAIN == b"ctpu/groups/placement/v1"
    # The ingress placement ring separates its scores with its own domain;
    # the two planes must never share one (same tenant, different answer).
    assert GROUPS_PLACEMENT_DOMAIN != b"ctpu/ingress/placement/v1"
    d = GroupDirectory.of_size(4)
    from consensus_tpu.ingress.placement import PlacementRing

    ring = PlacementRing(tuple(f"group-{i}" for i in range(4)))
    picks = {f"t{i}": (d.assign(f"t{i}"), ring.candidates(f"t{i}")[0])
             for i in range(64)}
    assert any(a != b for a, b in picks.values())


def test_group_ids_shape():
    assert group_ids(1) == ("group-0",)
    assert group_ids(3) == ("group-0", "group-1", "group-2")


# --- admit-then-route -------------------------------------------------------


def test_router_counts_and_metrics():
    metrics = Metrics(InMemoryProvider())
    router = GroupRouter(GroupDirectory.of_size(3), metrics=metrics.groups)
    for i in range(30):
        router.route(f"tenant-{i}")
    counts = router.counts()
    assert sum(counts.values()) == 30
    assert set(counts) <= {"group-0", "group-1", "group-2"}
    dump = metrics.provider.dump()
    assert dump[GROUPS_ROUTED_KEY]["value"] == 30.0
    assert dump[GROUPS_COUNT_KEY]["value"] == 3.0


def test_router_routing_matches_directory():
    d = GroupDirectory.of_size(4)
    router = GroupRouter(d)
    for t in ("alpha", "beta", "gamma"):
        assert router.route(t) == d.assign(t)


def test_ingress_driver_groups_mode_is_additive():
    """groups=N adds routing to the open-loop driver without perturbing a
    single existing summary key (byte-identity of non-sharded runs)."""
    from consensus_tpu.ingress.driver import IngressDriver
    from consensus_tpu.ingress.workload import WorkloadSpec, generate_trace

    spec = WorkloadSpec(clients=16, duration=3.0)
    plain = IngressDriver(generate_trace(11, spec), spec, seed=11).run()
    sharded = IngressDriver(
        generate_trace(11, spec), spec, seed=11, groups=3
    ).run()
    assert "groups" not in plain and "group_routed" not in plain
    assert sharded["groups"] == 3
    assert sum(sharded["group_routed"].values()) == sharded["admitted"]
    assert {
        k: v for k, v in sharded.items() if k not in ("groups", "group_routed")
    } == plain


# --- 2PC payload codec ------------------------------------------------------


def test_twopc_payload_round_trip():
    payload = twopc_payload(
        "prepare", "tx-9", ("group-0", "group-2"), "coord-7"
    )
    rec = parse_twopc_payload(payload)
    assert rec == {
        "kind": "prepare",
        "txid": "tx-9",
        "groups": ("group-0", "group-2"),
        "coordinator": "coord-7",
    }


def test_twopc_payload_rejects_bad_input():
    assert parse_twopc_payload(b"ordinary app bytes") is None
    with pytest.raises(ValueError):
        twopc_payload("promise", "tx", ("g",))
    with pytest.raises(ValueError):
        twopc_payload("prepare", "tx|evil", ("g",))
    with pytest.raises(ValueError):
        twopc_payload("prepare", "tx", ("g,rouped",))
    with pytest.raises(ValueError):
        parse_twopc_payload(b"2pc|commit|only-three|fields")


def test_saved_twopc_wire_round_trip_rides_v4():
    """SavedTwoPC is the v4 saved record; pre-sharding records keep their
    old envelope versions (lowest-lossless rule)."""
    from consensus_tpu.wire import SavedCommit

    rec = SavedTwoPC(
        txid="tx-1",
        phase="committed",
        groups=("group-0", "group-1"),
        coordinator="coord-0",
    )
    blob = encode_saved(rec)
    back = decode_saved(blob)
    assert back == rec
    assert blob[0] == 4  # the envelope leads with its version byte
    from consensus_tpu.types import Signature
    from consensus_tpu.wire import Commit

    old = encode_saved(
        SavedCommit(
            commit=Commit(view=0, seq=1, digest="d",
                          signature=Signature(id=1, value=b"s", msg=b""))
        )
    )
    assert old[0] < 4


# --- cross-group wave coalescing -------------------------------------------


def _signed(signer, tag: bytes, count: int):
    messages = [tag + b"/%d" % i for i in range(count)]
    return (
        messages,
        [signer.sign_raw(m) for m in messages],
        [signer.public_bytes for m in messages],
    )


def test_shared_former_coalesces_across_groups():
    """Two groups submitting concurrently share one fused launch, and the
    wave NEVER splits a submission (SAFETY §7): per-group signature runs
    stay contiguous and complete."""
    metrics = Metrics(InMemoryProvider())
    engine = Ed25519BatchVerifier(min_device_batch=10**9)
    waves = []
    former = FairShareWaveFormer(
        engine,
        window=0.2,
        groups_metrics=metrics.groups,
        on_group_wave=lambda counts, total: waves.append(dict(counts)),
        name="test-groups-former",
    )
    signer = Ed25519Signer(1, b"\x11" * 32)
    barrier = threading.Barrier(2)
    results = {}

    def submit(gid):
        barrier.wait()
        msgs, sigs, keys = _signed(signer, gid.encode(), 3)
        results[gid] = former.submit(
            f"{gid}/certs", msgs, sigs, keys, group=gid
        )

    threads = [
        threading.Thread(target=submit, args=(g,))
        for g in ("group-0", "group-1")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    former.close()
    assert all(results["group-0"]) and all(results["group-1"])
    assert any(len(w) == 2 for w in waves), waves
    multi = [w for w in waves if len(w) == 2]
    # Whole submissions: the coalesced wave carries all 3 sigs per group.
    assert multi[0] == {"group-0": 3, "group-1": 3}
    assert metrics.provider.dump()[GROUPS_WAVE_MULTI_KEY]["value"] >= 1.0


# --- the sharding acceptance gate ------------------------------------------


def _run_workload(shard: ShardedCluster, tenants, per_tenant: int, height: int):
    shard.start()
    for r in range(per_tenant):
        for t in tenants:
            shard.submit(t, b"w%d" % r)
    assert shard.run_until_heights(height, max_time=600.0)
    shard.assert_clean()


def test_sharded_groups_match_standalone_clusters_byte_for_byte():
    """Group i inside a shard replays a standalone Cluster with the same
    derived seed byte-for-byte — the shared scheduler interleaves groups
    but never reorders one group's own events."""
    tenants = [f"tenant-{i}" for i in range(8)]
    shard = ShardedCluster(2, n=4, seed=5)
    groups_of = {t: shard.router.directory.assign(t) for t in tenants}
    _run_workload(shard, tenants, per_tenant=2, height=1)
    sharded_digests = shard.ledger_digests()

    for gi, gid in enumerate(shard.group_ids()):
        solo = Cluster(4, seed=group_seed(5, gi))
        solo.start()
        rids: dict = {}
        # Same per-group submission sequence the shard produced.
        for r in range(2):
            for t in tenants:
                if groups_of[t] != gid:
                    continue
                rid = rids.get(t, 0) + 1
                rids[t] = rid
                solo.submit_to_all(make_request(t, rid, b"w%d" % r))
        want = len(sharded_digests[gid][1])
        assert solo.scheduler.run_until(
            lambda: all(
                len(nd.app.ledger) >= want for nd in solo.nodes.values()
            ),
            max_time=600.0,
        )
        solo_digests = {
            nid: tuple(d.proposal.digest() for d in node.app.ledger)[:want]
            for nid, node in sorted(solo.nodes.items())
        }
        assert solo_digests == sharded_digests[gid], gid


def test_four_groups_one_fleet_beats_four_private_fleets():
    """THE acceptance gate: identical committed cert work, strictly fewer
    and larger launches through the one shared fleet than through four
    private ones — the deployment win sharding is paying for."""
    metrics = Metrics(InMemoryProvider())
    shard = ShardedCluster(4, n=4, seed=2, metrics=metrics)
    tenants = [f"tenant-{i}" for i in range(16)]
    _run_workload(shard, tenants, per_tenant=2, height=1)

    workload = shard.cert_workload()
    assert sum(len(b) for b in workload.values()) >= 4
    shared = shard.drive_shared_fleet(window=0.1, workload=workload)
    private = shard.drive_private_fleets(window=0.01, workload=workload)

    # Same bytes verified either way...
    assert shared["total_signatures"] == private["total_signatures"]
    # ...but the shared fleet fuses across groups: strictly fewer launches,
    assert shared["launches"] < private["launches"]
    # larger on average,
    assert (
        shared["total_signatures"] / shared["launches"]
        > private["total_signatures"] / private["launches"]
    )
    # with at least one launch actually serving 2+ groups, booked on the
    # pinned multi-group counter too.
    assert shared["multi_group_launches"] >= 1
    dump = metrics.provider.dump()
    assert dump[GROUPS_WAVE_MULTI_KEY]["value"] >= 1.0


def test_group_seed_derivation_is_injective_for_small_shards():
    seeds = {group_seed(s, i) for s in range(32) for i in range(8)}
    assert len(seeds) == 32 * 8


# --- the sweep scripts in sharded shape -------------------------------------


def _run_script(script, *argv):
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", script), *argv],
        capture_output=True, text=True, cwd=repo, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    return lines[:-1], lines[-1]


def test_ingress_sweep_script_multigroup():
    records, summary = _run_script(
        "ingress_sweep.py", "--count", "1", "--clients", "150",
        "--duration", "6", "--scenario", "flood", "--groups", "3",
    )
    assert summary["failed"] == 0 and summary["params"]["groups"] == 3
    (record,) = records
    assert record["ok"] and record["groups"] == 3
    assert sum(record["group_routed"].values()) == record["admitted"]


def test_chaos_sweep_script_groups():
    records, summary = _run_script(
        "chaos_sweep.py", "--start", "3", "--count", "1",
        "--steps", "4", "--groups", "2",
    )
    assert summary["failed"] == 0 and summary["params"]["groups"] == 2
    (record,) = records
    assert record["ok"]
    assert set(record["resolution"]) == {"group-0", "group-1"}
    assert len(set(record["resolution"].values())) == 1
