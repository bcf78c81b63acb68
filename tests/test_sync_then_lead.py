"""Sync, then lead: a replica that caught up by sync must not propose what
the cluster delivered without it.

The rotating deployment (leader handed on every 3 decisions, SmartBFT's
``DefaultConfig``) on the in-process cluster: every request goes to all four
replicas, one follower is cut off until the others are four decisions ahead,
comes back, catches up by sync, and the run goes on until it has led two more
turns.  Before the mend (``Controller._forget_synced``) the requests of the
decisions it skipped stayed in its pool, it proposed them again when its turn
came, and all four replicas delivered them twice.  The plain reference of a
run is the list of requests that were submitted, held apart from the program:
every replica's ledger must hold exactly that, once, in one order.

Parity model: reference test/basic_test.go TestFollowerStateTransfer:1051
crossed with TestLeaderRotation — upstream's test app prunes nothing because
its requests are submitted after the catch-up; here they are pooled before.
"""

import pytest

from consensus_tpu.testing import Cluster, make_request
from consensus_tpu.testing.app import unpack_batch
from consensus_tpu.utils.leader import get_leader_id
from consensus_tpu.wire import decode_view_metadata

N = 4
PER_LEADER = 3
BATCH = 5
CUT_FOR = 4  # decisions the others take while the follower is cut off
#: No timer of the request cascade fires inside the run: a forwarded copy
#: that lands past the pool's 5 s dedup horizon is ROADMAP R2, not this.
QUIET = {
    "request_batch_max_count": BATCH,
    "request_forward_timeout": 1000.0,
    "request_complain_timeout": 2000.0,
    "request_auto_remove_timeout": 4000.0,
}


def delivered(node) -> list:
    return [r for d in node.app.ledger for r in unpack_batch(d.proposal.payload)]


def leader_of(decision, n: int = N) -> int:
    md = decode_view_metadata(decision.proposal.metadata)
    return get_leader_id(
        md.view_id, n, tuple(range(1, n + 1)), leader_rotation=True,
        decisions_in_view=md.decisions_in_view, decisions_per_leader=PER_LEADER,
        blacklist=tuple(md.black_list),
    )


class Run:
    """The cluster, the reference (``submitted``) and a paced feed: one
    batch at a time, so that a sync ends between two decisions."""

    def __init__(self, sync_mode: str, n: int = N, batch: int = BATCH) -> None:
        self.batch = batch
        self.cluster = Cluster(
            n, leader_rotation=True, sync_mode=sync_mode,
            config_tweaks=dict(QUIET, decisions_per_leader=PER_LEADER,
                               request_batch_max_count=batch,
                               request_pool_size=max(400, 8 * batch)),
        )
        self.cluster.start()
        self.submitted: list = []

    def feed(self, node_ids=None) -> None:
        """One proposal's worth to every replica (the cut-off one too: a
        client's connection is not the replicas' network), then run until
        ``node_ids`` delivered it."""
        batch = [make_request("c", len(self.submitted) + i)
                 for i in range(self.batch)]
        self.submitted.extend(batch)
        for raw in batch:
            self.cluster.submit_to_all(raw)
        ids = node_ids or list(self.cluster.nodes)
        want = set(batch)
        assert self.cluster.scheduler.run_until(
            lambda: all(want <= set(delivered(self.cluster.nodes[i])) for i in ids),
            max_time=30.0,
        ), f"batch {len(self.submitted) // self.batch} was not delivered by {ids}"


@pytest.mark.parametrize("sync_mode", ["wire", "toy"])
@pytest.mark.parametrize("entry", ["do_sync", "deliver_checked"])
@pytest.mark.parametrize("follower", [2, 3, 4])
def test_synced_replica_leads_without_proposing_what_it_synced(
    follower, entry, sync_mode
):
    sync_then_lead(Run(sync_mode), follower, entry)


@pytest.mark.parametrize("entry", ["do_sync", "deliver_checked"])
@pytest.mark.parametrize("follower", [2, 6])
def test_synced_replica_leads_at_n7_with_hundreds_to_forget(follower, entry):
    """BASELINE configs[2]'s committee (n=7, f=2) on the shipped rotation,
    with batches large enough that one sync takes 200 requests out of the
    pool (the chip runs show it at 1,000 a batch: PERF.md section 6, PR 33)."""
    sync_then_lead(Run("wire", n=7, batch=40), follower, entry)


def sync_then_lead(run: Run, follower: int, entry: str) -> None:
    n, batch = len(run.cluster.nodes), run.batch
    cluster, node = run.cluster, run.cluster.nodes[follower]
    others = [i for i in cluster.nodes if i != follower]

    # The follower's first turn ends at decision 3 * follower: cut it off
    # there, so that nobody waits for it to lead while it is away.
    cut_at = PER_LEADER * follower
    while len(node.app.ledger) < cut_at:
        run.feed()
    cluster.network.disconnect(follower)
    for _ in range(CUT_FOR):
        run.feed(others)
    assert len(node.app.ledger) == cut_at
    assert all(len(cluster.nodes[i].app.ledger) == cut_at + CUT_FOR for i in others)
    controller = node.consensus.controller
    skipped = set(run.submitted[-CUT_FOR * batch:])
    assert controller.pool.count == len(skipped)  # they wait in its pool
    cluster.network.connect(follower)

    if entry == "deliver_checked":
        # A decision this replica already holds reaches its deliver guard
        # (the view changer's commit of a last decision, or a view deciding
        # while a sync's state fetch is still out): it syncs in place.
        last = node.app.ledger[-1]
        controller.deliver(last.proposal, last.signatures)
        assert len(node.app.ledger) == cut_at + CUT_FOR
        assert controller.pool.count == 0
        assert controller.sync_pool_removed == len(skipped)
    # The next decision's votes are two or more sequences past the
    # follower's view: _discover_if_sync_needed, then _do_sync.
    run.feed(others)
    assert cluster.scheduler.run_until(
        lambda: len(node.app.ledger) == len(cluster.nodes[others[0]].app.ledger)
        and not controller.health()["syncing"],
        max_time=30.0,
    ), "the follower did not catch up"
    rejoined_at = len(node.app.ledger)
    assert controller.pool.count == 0
    assert controller.synced_decisions == rejoined_at - cut_at
    assert controller.sync_pool_removed == (rejoined_at - cut_at) * batch
    # A copy of a synced request that arrives late is refused.
    errors: list = []
    controller.pool.submit(sorted(skipped)[0], errors.append)
    assert errors == ["request already exists"]

    # On until the follower has led two whole turns after it rejoined.
    led = 0
    while led < 2 * PER_LEADER:
        run.feed()
        led = sum(1 for d in node.app.ledger[rejoined_at:] if leader_of(d, n) == follower)
        assert len(run.submitted) <= 30 * n * batch, "the follower never led"
    cluster.scheduler.advance(2.0)  # anything stale would be proposed by now

    for i, replica in cluster.nodes.items():
        assert delivered(replica) == run.submitted, (
            f"replica {i} delivered {len(delivered(replica))} requests, "
            f"{len(set(delivered(replica)))} distinct, of {len(run.submitted)} submitted"
        )
        # through the normal path: no view change, nobody blacklisted
        last_md = decode_view_metadata(replica.app.ledger[-1].proposal.metadata)
        assert last_md.view_id == 0 and not last_md.black_list
    cluster.assert_ledgers_consistent()


@pytest.mark.parametrize("sync_mode", ["wire", "toy"])
@pytest.mark.parametrize("follower", [2, 3, 4])
def test_sync_that_ends_mid_decision_rejoins_before_its_own_turn(follower, sync_mode):
    """Under load a sync always ends while the others are mid-decision.  The
    replaced view used to start without that decision's pre-prepare, fall two
    sequences behind and sync again; when its own turn to lead came first,
    nobody proposed and the cluster waited out the 60 s heartbeat for a view
    change that blacklisted it (met on the chip: PERF.md section 6, PR 29).
    With ``Controller._keep_ahead`` the new view is handed what arrived for
    its sequence while it did not exist: one sync, no view change, and the
    follower leads its next turn."""
    run = Run(sync_mode)
    cluster, node = run.cluster, run.cluster.nodes[follower]
    others = [i for i in cluster.nodes if i != follower]
    # Everything pooled up front: the leaders propose back to back.
    total = 40
    run.submitted = [make_request("c", i) for i in range(total * BATCH)]
    for raw in run.submitted:
        cluster.submit_to_all(raw)
    cut_at = PER_LEADER * follower
    assert cluster.run_until_ledger(cut_at, max_time=30.0)
    cluster.network.disconnect(follower)
    assert cluster.run_until_ledger(cut_at + CUT_FOR, max_time=30.0, node_ids=others)
    cluster.network.connect(follower)
    assert cluster.scheduler.run_until(
        lambda: all(len(n.app.ledger) == total for n in cluster.nodes.values()),
        max_time=20.0,  # far under the request and heartbeat timers
    ), [len(n.app.ledger) for n in cluster.nodes.values()]
    cluster.scheduler.advance(2.0)

    controller = node.consensus.controller
    assert controller.syncs == 1 and controller.synced_decisions >= CUT_FOR
    for replica in cluster.nodes.values():
        assert delivered(replica) == run.submitted
        last_md = decode_view_metadata(replica.app.ledger[-1].proposal.metadata)
        assert last_md.view_id == 0 and not last_md.black_list
    rejoined_at = cut_at + controller.synced_decisions
    assert sum(1 for d in node.app.ledger[rejoined_at:]
               if leader_of(d) == follower) >= PER_LEADER
