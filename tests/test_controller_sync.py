"""Controller sync decision matrix unit tests with scripted collaborators.

Parity model: reference internal/bft/controller_test.go sync cases — the
matrix in controller.go:576-680: what the synchronizer returned (behind /
ahead / empty) crossed with what the state-fetch collected (agreeing /
failing / higher view).
"""

from consensus_tpu.core.controller import Controller
from consensus_tpu.config import Configuration
from consensus_tpu.core.batcher import Batcher
from consensus_tpu.core.collector import StateCollector
from consensus_tpu.core.pool import PoolOptions, RequestPool
from consensus_tpu.core.state import InFlightData, PersistedState, ProposalMaker
from consensus_tpu.runtime import SimScheduler
from consensus_tpu.testing import MemWAL
from consensus_tpu.testing.app import ByteInspector, pack_batch
from consensus_tpu.testing.app import TestApp as PortsApp
from consensus_tpu.types import Checkpoint, Decision, Proposal, Reconfig, SyncResponse
from consensus_tpu.wire import (
    StateTransferRequest,
    StateTransferResponse,
    ViewMetadata,
    decode_saved,
    encode_view_metadata,
)

NODES = (1, 2, 3, 4)


def proposal_at(view, seq, decisions=0, requests=None):
    """A decided proposal's shell; with ``requests`` its payload is a real
    batch, otherwise a placeholder no verifier can unpack."""
    md = ViewMetadata(view_id=view, latest_sequence=seq, decisions_in_view=decisions)
    payload = b"p%d" % seq if requests is None else pack_batch(requests)
    return Proposal(payload=payload, metadata=encode_view_metadata(md))


class ScriptedSynchronizer:
    def __init__(self):
        self.response = SyncResponse(latest=None, reconfig=Reconfig())
        self.calls = 0

    def sync(self):
        self.calls += 1
        return self.response


class RecordingVC:
    def __init__(self):
        self.informed = []
        self.messages = []
        self.view_messages = []

    def handle_message(self, sender, msg):
        self.messages.append((sender, msg))

    def handle_view_message(self, sender, msg):
        self.view_messages.append((sender, msg))

    def start_view_change(self, view, stop_view):
        pass

    def inform_new_view(self, view):
        self.informed.append(view)


class Harness:
    def __init__(self):
        self.sched = SimScheduler()
        self.cfg = Configuration(
            self_id=2, leader_rotation=False, decisions_per_leader=0,
            collect_timeout=1.0,
        )
        self.app = PortsApp(2, self)  # cluster duck-type below
        self.nodes = {}
        self.sent = []
        self.vc = RecordingVC()
        self.synchronizer = ScriptedSynchronizer()

        class CommStub:
            def __init__(self, outer):
                self.outer = outer

            def send_consensus(self, target, msg):
                self.outer.sent.append((target, msg))

            def send_transaction(self, target, raw):
                pass

            def nodes(self):
                return NODES

        in_flight = InFlightData()
        self.wal = MemWAL([])
        self.state = PersistedState(self.wal, in_flight, entries=[])
        self.checkpoint = Checkpoint()
        self.monitor = _MonitorStub()
        pool = RequestPool(self.sched, ByteInspector(), PoolOptions())
        self.controller = Controller(
            scheduler=self.sched,
            config=self.cfg,
            nodes=NODES,
            comm=CommStub(self),
            application=self.app,
            assembler=self.app,
            verifier=self.app,
            signer=self.app,
            synchronizer=self.synchronizer,
            pool=pool,
            batcher=Batcher(self.sched, pool, batch_max_count=10,
                            batch_max_bytes=10**6, batch_max_interval=0.05),
            leader_monitor=self.monitor,
            collector=StateCollector(self.sched, n=4, collect_timeout=1.0),
            state=self.state,
            in_flight=in_flight,
            checkpoint=self.checkpoint,
            proposer_builder=None,
            view_changer=self.vc,
        )
        self.controller._proposer_builder = ProposalMaker(
            state=self.state, view_factory=self._view_factory
        )

    # cluster duck-typing for TestApp
    def longest_ledger(self, *, exclude):
        return []

    def reconfig_of(self, proposal):
        return Reconfig()

    def _view_factory(self, **kw):
        from consensus_tpu.core.view import View

        return View(
            scheduler=self.sched, self_id=2, n=4, nodes=NODES,
            comm=_ViewCommStub(self), verifier=self.app, signer=self.app,
            state=self.state, decider=self.controller,
            failure_detector=_FDStub(), sync_requester=self.controller,
            checkpoint=self.checkpoint, decisions_per_leader=0, **kw,
        )

    def start(self, view=0, seq=1, dec=0):
        self.controller.start(view, seq, dec)

    def feed_state_responses(self, view, seq, senders=(1, 3)):
        for sender in senders:
            self.controller.process_message(
                sender, StateTransferResponse(view_num=view, sequence=seq)
            )


class _MonitorStub:
    def __init__(self):
        self.processed = []
        self.injected = []

    def change_role(self, role, view, leader):
        pass

    def close(self):
        pass

    def process_msg(self, sender, msg):
        self.processed.append((sender, msg))

    def inject_artificial_heartbeat(self, sender, msg):
        self.injected.append((sender, msg))

    def heartbeat_was_sent(self):
        pass


class _ViewCommStub:
    def __init__(self, outer):
        self.outer = outer

    def broadcast(self, msg):
        pass

    def send(self, target, msg):
        pass


class _FDStub:
    def complain(self, view, stop_view):
        pass


def test_sync_broadcasts_state_transfer_request():
    h = Harness()
    h.start()
    h.controller.sync()
    h.sched.advance(0.1)
    requests = [m for _, m in h.sent if isinstance(m, StateTransferRequest)]
    assert len(requests) == 3  # all peers, not self
    assert h.synchronizer.calls == 1


def test_sync_advancing_checkpoint_moves_sequence():
    # Synchronizer returns a decision ahead of us: checkpoint updates and
    # the next view starts after it.
    h = Harness()
    h.start()
    ahead = proposal_at(view=0, seq=5, decisions=4)
    h.synchronizer.response = SyncResponse(latest=Decision(proposal=ahead))
    h.controller.sync()
    h.sched.advance(0.05)
    h.feed_state_responses(view=0, seq=6)
    h.sched.advance(2.0)
    assert h.controller.latest_seq() == 5
    assert h.controller.curr_view is not None
    assert h.controller.curr_view.proposal_sequence == 6


def test_sync_discovering_higher_view_informs_view_changer_and_saves_record():
    # Peers agree the cluster is at view 3 one sequence past our latest
    # decision: a NewView record is persisted and the VC is informed.
    h = Harness()
    h.start()
    latest = proposal_at(view=0, seq=5, decisions=4)
    h.synchronizer.response = SyncResponse(latest=Decision(proposal=latest))
    h.controller.sync()
    h.sched.advance(0.05)
    h.feed_state_responses(view=3, seq=6)
    h.sched.advance(2.0)
    assert h.vc.informed == [3]
    from consensus_tpu.wire import SavedNewView

    saved = [decode_saved(e) for e in h.wal.entries]
    new_views = [s for s in saved if isinstance(s, SavedNewView)]
    assert new_views and new_views[-1].view_metadata.view_id == 3
    assert h.controller.curr_view_number == 3


def test_sync_timeout_with_nothing_new_restarts_current_view():
    h = Harness()
    h.start()
    before_view = h.controller.curr_view_number
    h.controller.sync()
    h.sched.advance(3.0)  # collector times out, nothing learned
    assert h.controller.curr_view_number == before_view
    assert h.controller.curr_view is not None
    assert not h.controller.curr_view.stopped


def test_sync_is_idempotent_while_running():
    h = Harness()
    h.start()
    h.controller.sync()
    h.sched.advance(0.01)
    h.controller.sync()  # second request while the first is collecting
    h.sched.advance(0.01)
    assert h.synchronizer.calls == 1


def test_sync_reconfig_routes_to_callback():
    seen = []
    h = Harness()
    h.controller._on_reconfig = seen.append
    h.start()
    h.synchronizer.response = SyncResponse(
        latest=None, reconfig=Reconfig(in_latest_decision=True, current_nodes=(1, 2, 3))
    )
    h.controller.sync()
    h.sched.advance(0.05)
    assert len(seen) == 1 and seen[0].current_nodes == (1, 2, 3)


def test_prune_in_flight_after_sync_past_it():
    h = Harness()
    h.start()
    # An in-flight proposal at seq 5; sync returns a decision at seq 5.
    h.controller.in_flight.store_proposal(proposal_at(view=0, seq=5))
    assert h.controller.in_flight.proposal() is not None
    h.synchronizer.response = SyncResponse(
        latest=Decision(proposal=proposal_at(view=0, seq=5, decisions=1))
    )
    h.controller.sync()
    h.sched.advance(0.05)
    h.feed_state_responses(view=0, seq=6)
    h.sched.advance(2.0)
    assert h.controller.in_flight.proposal() is None


def test_sync_repairs_stale_decisions_in_view():
    # A late-processed NewView can reset decisions-in-view to 0 while the
    # cluster kept deciding in the same view; the node then rejects every
    # proposal ("decisions-in-view N != 0") forever. Sync must repair the
    # counter from the checkpoint's own metadata even when the sequence has
    # not advanced.
    h = Harness()
    h.start(view=0, seq=6, dec=0)  # wrong: the view has decided 3 times
    latest = proposal_at(view=0, seq=5, decisions=2)
    h.checkpoint.set(latest, ())
    h.synchronizer.response = SyncResponse(latest=Decision(proposal=latest))
    h.controller.sync()
    h.sched.advance(0.05)
    h.feed_state_responses(view=0, seq=6)
    h.sched.advance(2.0)
    assert h.controller.curr_decisions_in_view == 3
    assert h.controller.curr_view_number == 0
    assert h.controller.curr_view.proposal_sequence == 6


def test_sync_does_not_clobber_fresh_view_decisions():
    # Fresh view after a view change: the latest decision belongs to an
    # OLDER view, so decisions-in-view legitimately starts at 0 and must
    # not be "repaired" from stale metadata.
    h = Harness()
    latest = proposal_at(view=0, seq=5, decisions=2)
    h.checkpoint.set(latest, ())
    h.controller.start(2, 6, 0)  # new view 2, decisions correctly 0
    h.synchronizer.response = SyncResponse(latest=Decision(proposal=latest))
    h.controller.sync()
    h.sched.advance(0.05)
    h.feed_state_responses(view=2, seq=6)
    h.sched.advance(2.0)
    assert h.controller.curr_decisions_in_view == 0


# --- table-driven routing + sync-interleaving families --------------------
#
# Parity model: reference internal/bft/controller_test.go message-routing
# assertions (which collaborator each wire message reaches, and what a
# leader vs a follower does with forwarded requests), plus the remaining
# sync interleavings not covered above.

import pytest

from consensus_tpu.testing import make_request
from consensus_tpu.types import Signature
from consensus_tpu.wire import (
    Commit,
    HeartBeat,
    HeartBeatResponse,
    NewView,
    PrePrepare,
    Prepare,
    SignedViewData,
    ViewChange,
)

_SIG = Signature(id=1, value=b"s")

#: (id, sender, message-factory, expected routing flags).  ``view`` = the
#: running View's handle_message; ``vc_view`` = view changer's passive wire
#: tap; ``vc`` = view changer's own protocol ingress; ``monitor`` = leader
#: monitor; ``heartbeat`` = artificial heartbeat injected (leader traffic
#: only); ``reply`` = a StateTransferResponse goes back to the sender.
ROUTING_TABLE = [
    ("preprepare-from-leader", 1,
     lambda: PrePrepare(view=0, seq=1, proposal=proposal_at(0, 1)),
     dict(view=True, vc_view=True, heartbeat=True)),
    ("prepare-from-leader", 1,
     lambda: Prepare(view=0, seq=1, digest="d"),
     dict(view=True, vc_view=True, heartbeat=True)),
    ("prepare-from-follower", 3,
     lambda: Prepare(view=0, seq=1, digest="d"),
     dict(view=True, vc_view=True, heartbeat=False)),
    ("commit-from-follower", 4,
     lambda: Commit(view=0, seq=1, digest="d", signature=_SIG),
     dict(view=True, vc_view=True, heartbeat=False)),
    ("view-change-vote", 3,
     lambda: ViewChange(next_view=1),
     dict(vc=True)),
    ("signed-view-data", 3,
     lambda: SignedViewData(raw_view_data=b"r", signer=3, signature=b"s"),
     dict(vc=True)),
    ("new-view", 1,
     lambda: NewView(),
     dict(vc=True)),
    ("heartbeat", 1,
     lambda: HeartBeat(view=0, seq=0),
     dict(monitor=True)),
    ("heartbeat-response", 3,
     lambda: HeartBeatResponse(view=2),
     dict(monitor=True)),
    ("state-transfer-request", 4,
     lambda: StateTransferRequest(),
     dict(reply=True)),
]


@pytest.mark.parametrize(
    "sender,factory,expect",
    [row[1:] for row in ROUTING_TABLE],
    ids=[row[0] for row in ROUTING_TABLE],
)
def test_message_routing(sender, factory, expect):
    h = Harness()
    h.start()
    view_seen = []
    h.controller.curr_view.handle_message = (
        lambda s, m: view_seen.append((s, m))
    )
    h.controller.process_message(sender, factory())
    assert bool(view_seen) == expect.get("view", False)
    assert bool(h.vc.view_messages) == expect.get("vc_view", False)
    assert bool(h.vc.messages) == expect.get("vc", False)
    assert bool(h.monitor.processed) == expect.get("monitor", False)
    assert bool(h.monitor.injected) == expect.get("heartbeat", False)
    replies = [
        (t, m) for t, m in h.sent if isinstance(m, StateTransferResponse)
    ]
    if expect.get("reply", False):
        assert replies and replies[0][0] == sender
    else:
        assert not replies


def test_stopped_controller_routes_nothing():
    h = Harness()
    h.start()
    h.controller.stop()
    h.vc.messages.clear()
    h.vc.view_messages.clear()
    h.controller.process_message(1, HeartBeat(view=0, seq=0))
    h.controller.process_message(3, ViewChange(next_view=1))
    assert not h.monitor.processed
    assert not h.vc.messages


#: Forwarded-request table: (id, start view, raw bytes, expect pooled).
#: View 0's leader is node 1; view 1's is node 2 (the harness self id), so
#: starting in view 1 makes us the leader.  Parity: reference
#: controller_test.go leader/follower forwarded-request cases.
FORWARD_TABLE = [
    ("follower-drops-forwarded", 0, make_request("cli", 1), False),
    ("leader-pools-forwarded", 1, make_request("cli", 2), True),
    ("leader-rejects-unverifiable", 1, b"garbage-no-separators", False),
]


@pytest.mark.parametrize(
    "view,raw,pooled_expected",
    [row[1:] for row in FORWARD_TABLE],
    ids=[row[0] for row in FORWARD_TABLE],
)
def test_forwarded_request_routing(view, raw, pooled_expected):
    h = Harness()
    h.start(view=view)
    pooled = []
    h.controller.pool.submit = lambda r, on_done=None: pooled.append(r)
    h.controller.handle_request(3, raw)
    assert bool(pooled) == pooled_expected
    if pooled_expected:
        assert pooled == [raw]


def test_sync_result_behind_checkpoint_changes_nothing():
    # The synchronizer answered with a decision OLDER than what we already
    # delivered: position must not move backwards.
    h = Harness()
    latest = proposal_at(view=0, seq=5, decisions=2)
    h.checkpoint.set(latest, ())
    h.start(view=0, seq=6, dec=3)
    h.synchronizer.response = SyncResponse(
        latest=Decision(proposal=proposal_at(view=0, seq=3, decisions=0))
    )
    h.controller.sync()
    h.sched.advance(0.05)
    h.feed_state_responses(view=0, seq=6)
    h.sched.advance(2.0)
    assert h.controller.latest_seq() == 5
    assert h.controller.curr_view.proposal_sequence == 6
    assert h.controller.curr_view_number == 0


def test_change_view_refuses_regression():
    h = Harness()
    h.start(view=2, seq=4, dec=0)
    running = h.controller.curr_view
    h.controller.change_view(1, 9, 0)
    assert h.controller.curr_view_number == 2
    assert h.controller.curr_view is running
    assert not running.stopped


def test_change_view_same_position_is_idempotent():
    h = Harness()
    h.start(view=0, seq=4, dec=1)
    running = h.controller.curr_view
    h.controller.change_view(0, 4, 1)
    assert h.controller.curr_view is running, (
        "an identical change_view must not tear down the running view"
    )


#: _deliver_checked guard table (controller.py:443-466): a delivery racing
#: a completed sync must not re-deliver — it syncs instead and advances the
#: checkpoint from the sync response.  Cases: (id, checkpointed seq or None,
#: delivered seq, sync-response factory, expect).
DELIVER_CHECKED_TABLE = [
    ("fresh-node-delivers", None, 1,
     lambda: SyncResponse(latest=None, reconfig=Reconfig()),
     dict(delivered=True, sync_calls=0, checkpoint_seq=1)),
    ("ahead-of-checkpoint-delivers", 5, 6,
     lambda: SyncResponse(latest=None, reconfig=Reconfig()),
     dict(delivered=True, sync_calls=0, checkpoint_seq=6)),
    ("equal-seq-syncs-instead", 5, 5,
     lambda: SyncResponse(
         latest=Decision(proposal=proposal_at(0, 7, 1)), reconfig=Reconfig()
     ),
     dict(delivered=False, sync_calls=1, checkpoint_seq=7)),
    ("behind-checkpoint-syncs-instead", 5, 3,
     lambda: SyncResponse(
         latest=Decision(proposal=proposal_at(0, 8, 1)), reconfig=Reconfig()
     ),
     dict(delivered=False, sync_calls=1, checkpoint_seq=8)),
    ("sync-learned-nothing-keeps-checkpoint", 5, 5,
     lambda: SyncResponse(latest=None, reconfig=Reconfig()),
     dict(delivered=False, sync_calls=1, checkpoint_seq=5)),
    ("sync-reconfig-propagates", 5, 4,
     lambda: SyncResponse(
         latest=Decision(proposal=proposal_at(0, 9, 1)),
         reconfig=Reconfig(in_latest_decision=True, current_nodes=(1, 2, 3)),
     ),
     dict(delivered=False, sync_calls=1, checkpoint_seq=9,
          reconfig_nodes=(1, 2, 3))),
]


@pytest.mark.parametrize(
    "checkpointed,delivered_seq,response_factory,expect",
    [row[1:] for row in DELIVER_CHECKED_TABLE],
    ids=[row[0] for row in DELIVER_CHECKED_TABLE],
)
def test_deliver_checked_guard(checkpointed, delivered_seq, response_factory, expect):
    h = Harness()
    if checkpointed is not None:
        h.checkpoint.set(proposal_at(view=0, seq=checkpointed, decisions=1), ())
        h.start(view=0, seq=checkpointed + 1, dec=1)
    else:
        h.start()
    h.synchronizer.response = response_factory()
    before_ledger = len(h.app.ledger)

    reconfig = h.controller.deliver(
        proposal_at(view=0, seq=delivered_seq, decisions=1), ()
    )

    delivered = len(h.app.ledger) > before_ledger
    assert delivered == expect["delivered"]
    assert h.synchronizer.calls == expect["sync_calls"]
    assert h.controller.latest_seq() == expect["checkpoint_seq"]
    assert reconfig.current_nodes == expect.get("reconfig_nodes", ())


def test_stray_state_response_without_sync_is_ignored():
    h = Harness()
    h.start()
    before = h.controller.curr_view
    h.feed_state_responses(view=5, seq=9, senders=(1, 3, 4))
    h.sched.advance(2.0)
    # No sync was in progress: the stray responses must not move the view.
    assert h.controller.curr_view is before
    assert h.controller.curr_view_number == 0
    assert h.vc.informed == []


# --- what a sync brought into the ledger leaves the pool -------------------
#
# A replica that caught up by sync still pools the requests of the decisions
# it skipped; with leader rotation it soon leads and would propose them
# again (tests/test_sync_then_lead.py has the whole scenario).  Both sync
# entries hand SyncResponse.synced to Controller._forget_synced.

def scripted_catch_up(h, first_seq, k, per_decision=3):
    """Pool the requests of ``k`` decisions from ``first_seq`` on plus two
    nobody ordered yet, and script a sync that brings those decisions."""
    requests = [make_request("cli", i) for i in range(k * per_decision + 2)]
    for raw in requests:
        h.controller.pool.submit(raw)
    synced = tuple(
        Decision(proposal=proposal_at(
            0, first_seq + j, first_seq + j - 1,
            requests[j * per_decision:(j + 1) * per_decision]))
        for j in range(k)
    )
    h.synchronizer.response = SyncResponse(
        latest=synced[-1] if synced else None, synced=synced)
    return requests[:k * per_decision], requests[k * per_decision:]


def enter_do_sync(h):
    h.controller.sync()
    h.sched.advance(0.05)


def enter_deliver_checked(h):
    proposal, signatures = h.checkpoint.get()
    h.controller.deliver(proposal, signatures)


SYNC_ENTRIES = [("do_sync", enter_do_sync), ("deliver_checked", enter_deliver_checked)]


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize(
    "enter", [e[1] for e in SYNC_ENTRIES], ids=[e[0] for e in SYNC_ENTRIES])
def test_sync_removes_the_synced_decisions_requests_from_the_pool(enter, k):
    h = Harness()
    h.checkpoint.set(proposal_at(view=0, seq=5, decisions=4), ())
    h.start(view=0, seq=6, dec=5)
    ordered, waiting = scripted_catch_up(h, first_seq=6, k=k)
    assert h.controller.pool.count == len(ordered) + len(waiting)

    enter(h)

    # At once: before the state fetch ends, before any view is started.
    assert h.controller.latest_seq() == 5 + k
    assert h.controller.pool.next_requests(100, 10**6) == waiting
    refused = []
    for raw in ordered:
        h.controller.pool.submit(raw, refused.append)
    assert refused == ["request already exists"] * len(ordered)
    health = h.controller.health()
    assert (health["syncs"], health["synced_decisions"],
            health["sync_pool_removed"]) == (1, k, len(ordered))


@pytest.mark.parametrize(
    "enter", [e[1] for e in SYNC_ENTRIES], ids=[e[0] for e in SYNC_ENTRIES])
def test_sync_that_advanced_nothing_removes_nothing(enter):
    h = Harness()
    h.checkpoint.set(proposal_at(view=0, seq=5, decisions=4), ())
    h.start(view=0, seq=6, dec=5)
    _, waiting = scripted_catch_up(h, first_seq=6, k=0)
    h.synchronizer.response = SyncResponse(
        latest=Decision(proposal=proposal_at(view=0, seq=5, decisions=4)))

    enter(h)

    assert h.controller.latest_seq() == 5
    assert h.controller.pool.next_requests(100, 10**6) == waiting
    health = h.controller.health()
    assert (health["syncs"], health["synced_decisions"],
            health["sync_pool_removed"]) == (1, 0, 0)


def test_synced_request_that_was_never_pooled_is_refused_when_it_arrives():
    # The listener was paused: the client's copy is still on its way when the
    # sync brings the decision that ordered it.
    h = Harness()
    h.start()
    late = make_request("cli", 77)
    brought = Decision(proposal=proposal_at(0, 1, requests=[late]))
    h.synchronizer.response = SyncResponse(latest=brought, synced=(brought,))
    enter_do_sync(h)
    refused = []
    h.controller.pool.submit(late, refused.append)
    assert refused == ["request already exists"]
    assert h.controller.health()["sync_pool_removed"] == 0


def test_unreadable_synced_proposal_keeps_the_others_out_of_the_pool(caplog):
    h = Harness()
    h.start()
    good = [make_request("cli", 1), make_request("cli", 2)]
    for raw in good:
        h.controller.pool.submit(raw)
    readable = Decision(proposal=proposal_at(0, 2, 1, requests=good))
    h.synchronizer.response = SyncResponse(
        latest=readable,
        synced=(Decision(proposal=proposal_at(view=0, seq=1)), readable))  # 1: no batch
    enter_do_sync(h)
    assert h.controller.pool.count == 0
    assert h.controller.health()["synced_decisions"] == 2


def test_synchronizer_that_does_not_report_what_it_fetched_is_logged(caplog):
    import logging

    h = Harness()
    h.start()
    h.synchronizer.response = SyncResponse(
        latest=Decision(proposal=proposal_at(view=0, seq=3, decisions=2)))
    with caplog.at_level(logging.WARNING, logger="consensus_tpu.controller"):
        enter_do_sync(h)
    assert any("did not report" in r.getMessage() for r in caplog.records)


def test_rotation_is_counted_in_health():
    h = Harness()
    h.cfg = Configuration(
        self_id=2, leader_rotation=True, decisions_per_leader=1, collect_timeout=1.0)
    h.controller._config = h.cfg
    h.start()
    assert h.controller.health()["leader_handovers"] == 0
    h.controller.decide(proposal_at(0, 1, requests=[make_request("cli", 1)]), (), ())
    assert h.controller.health()["leader_handovers"] == 1


# --- the hand-over is timed to the successor view's first pre-prepare ------


def rotating(self_turn_next: bool) -> "Harness":
    """Node 2 of 4 with a new leader after every decision, started so that
    the next decision hands the lead to node 2 itself or on to node 3."""
    h = Harness()
    h.controller._config = Configuration(
        self_id=2, leader_rotation=True, decisions_per_leader=1, collect_timeout=1.0)
    if self_turn_next:
        h.start(view=0, seq=1, dec=0)  # node 1 leads seq 1, node 2 seq 2
    else:
        h.start(view=0, seq=2, dec=1)  # node 2 leads seq 2, node 3 seq 3
    return h


def decide_next(h):
    seq = h.controller.curr_view.proposal_sequence
    h.controller.decide(
        proposal_at(0, seq, seq - 1, requests=[make_request("cli", seq)]), (), ())
    return seq + 1


def counters(h):
    health = h.controller.health()
    return health["leader_handovers"], health["handover_ns"], health["ahead_replayed"]


def test_a_static_leader_hands_nothing_over():
    h = Harness()
    h.start()
    for _ in range(3):
        decide_next(h)
        h.sched.advance(0.05)
    h.controller.broadcast(PrePrepare(view=0, seq=4, proposal=proposal_at(0, 4, 3)))
    assert counters(h) == (0, 0, 0)
    assert h.controller._handover_began is None


def test_follower_hand_over_ends_when_its_view_gets_the_new_leaders_pre_prepare():
    h = rotating(self_turn_next=False)
    nxt = decide_next(h)
    assert nxt == 3 and h.controller.leader_id() == 3
    h.sched.advance(0.02)
    # none of these is the pre-prepare the new view will take up
    for sender, msg in [(3, Prepare(view=0, seq=nxt, digest="d")),
                        (1, PrePrepare(view=0, seq=nxt, proposal=proposal_at(0, nxt, 2))),
                        (3, PrePrepare(view=0, seq=nxt + 1, proposal=proposal_at(0, nxt + 1, 3)))]:
        h.controller.process_message(sender, msg)
    assert counters(h) == (1, 0, 0)
    h.sched.advance(0.01)
    h.controller.process_message(
        3, PrePrepare(view=0, seq=nxt, proposal=proposal_at(0, nxt, 2)))
    assert counters(h) == (1, 30_000_000, 0)
    h.sched.advance(0.5)  # over: nothing more is added
    h.controller.process_message(
        3, PrePrepare(view=0, seq=nxt, proposal=proposal_at(0, nxt, 2)))
    assert counters(h) == (1, 30_000_000, 0)


def test_new_leaders_hand_over_ends_when_its_first_pre_prepare_goes_out():
    h = rotating(self_turn_next=True)
    nxt = decide_next(h)
    assert nxt == 2 and h.controller.i_am_the_leader()
    h.sched.advance(0.055)  # the sealing wait and the WAL append
    h.controller.broadcast(Prepare(view=0, seq=nxt, digest="d"))
    assert counters(h) == (1, 0, 0)
    h.controller.broadcast(PrePrepare(view=0, seq=nxt, proposal=proposal_at(0, nxt, 1)))
    assert counters(h) == (1, 55_000_000, 0)
    assert any(isinstance(m, PrePrepare) for _, m in h.sent)


def test_early_pre_prepare_is_replayed_into_the_successor_and_counted_once():
    h = rotating(self_turn_next=False)
    nxt = h.controller.curr_view.proposal_sequence + 1
    early = PrePrepare(view=0, seq=nxt, proposal=proposal_at(0, nxt, 2))
    h.controller.process_message(3, early)  # node 3 does not lead yet: kept
    assert counters(h) == (0, 0, 0)
    assert decide_next(h) == nxt
    successor = h.controller.curr_view
    h.sched.advance(0.004)  # the replay is the scheduler's next step
    assert successor is h.controller.curr_view
    handovers, ns, replayed = counters(h)
    assert (handovers, replayed) == (1, 1) and 0 <= ns <= 4_000_000
    assert h.controller._handover_began is None
    h.controller._replay_ahead(successor)  # nothing is left to hand over
    assert counters(h) == (handovers, ns, 1)


def test_hand_over_a_sync_went_past_ends_at_the_next_one():
    h = rotating(self_turn_next=False)
    decide_next(h)
    h.sched.advance(0.1)
    h.controller.change_view(0, 6, 5)  # a sync: the turn's proposal never came
    assert counters(h) == (1, 0, 0)
    decide_next(h)
    assert counters(h)[:2] == (2, 100_000_000)
    assert h.controller._handover_began is not None


# --- three-phase traffic ahead of the view is kept for its successor ------


def _commit(seq, sender):
    return Commit(view=0, seq=seq, digest="d", signature=Signature(id=sender, value=b"s"))


def test_keep_ahead_is_bounded_by_window_and_per_sequence_cap():
    from consensus_tpu.core.controller import _AHEAD_WINDOW

    h = Harness()
    h.start(view=0, seq=10, dec=9)
    # A static leader's running view buffers its next sequence itself.
    h.controller.process_message(3, _commit(11, 3))
    assert h.controller._ahead == {}
    h.controller._config = Configuration(
        self_id=2, leader_rotation=True, decisions_per_leader=3, collect_timeout=1.0)
    # With rotation the view is replaced every few decisions.  It takes its
    # own sequence: only later ones are kept.
    for seq in (9, 10, 11, 10 + _AHEAD_WINDOW, 11 + _AHEAD_WINDOW, 10**9):
        h.controller.process_message(3, _commit(seq, 3))
    assert sorted(h.controller._ahead) == [11, 10 + _AHEAD_WINDOW]
    for _ in range(100):  # one sender repeating itself cannot grow a bucket
        h.controller.process_message(4, _commit(11, 4))
    assert len(h.controller._ahead[11]) == 4 * len(NODES)
    # A stopped view (a sync is out) keeps its own sequence's traffic too.
    h.controller.curr_view.abort()
    h.controller.process_message(1, Prepare(view=0, seq=10, digest="d"))
    assert 10 in h.controller._ahead


def test_kept_messages_reach_the_view_that_replaces_the_stopped_one():
    h = Harness()
    h.start(view=0, seq=10, dec=9)
    h.controller.curr_view.abort()  # as _discover_if_sync_needed does
    early = [(1, PrePrepare(view=0, seq=12, proposal=proposal_at(0, 12, 11))),
             (3, Prepare(view=0, seq=12, digest="d")),
             (4, _commit(13, 4)),
             (3, Prepare(view=1, seq=12, digest="other-view"))]
    for sender, msg in early:
        h.controller.process_message(sender, msg)
    seen = []
    h.controller.change_view(0, 12, 11)  # the sync ended at 11
    h.controller.curr_view.handle_message = lambda s, m: seen.append((s, m))
    h.sched.advance(0.01)
    assert seen == early[:3]  # its sequence and the next, this view's only
    assert sorted(h.controller._ahead) == [13]  # kept for the view after it
    # Nothing is replayed into a view that was itself replaced meanwhile.
    h.controller.change_view(0, 13, 12)
    stale = h.controller.curr_view
    h.controller.change_view(0, 13, 0)
    stale.handle_message = lambda s, m: seen.append("stale")
    h.sched.advance(0.01)
    assert "stale" not in seen
