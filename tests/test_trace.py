"""Decision-lifecycle tracing (consensus_tpu/trace/): determinism,
completeness, overhead, and metrics parity.

The tracer is clocked by the injected Scheduler, so two cluster runs with
the same seed must export byte-identical span streams — that is the
property that makes a trace attached to a bug report replayable.  The
export must be a valid Chrome/Perfetto trace whose per-decision spans nest
correctly, and every committed sequence must carry a complete
pre-prepare -> prepare -> commit -> deliver chain.  With tracing disabled
(the default), the protocol must perform ZERO ring-buffer appends.
"""

import json

from consensus_tpu.config import TraceConfig
from consensus_tpu.metrics import (
    VERIFY_LAUNCH_BATCH_KEY,
    WAL_RECORDS_PER_FSYNC_KEY,
    InMemoryProvider,
    Metrics,
)
from consensus_tpu.testing.app import Cluster, make_request
from consensus_tpu.testing.faults import FaultPlan, SimulatedCrash
from consensus_tpu.trace import (
    NOOP_TRACER,
    Tracer,
    build_report,
    format_table,
    to_chrome_json,
    to_jsonl,
)

DECISIONS = 50


def _traced_tweaks(**extra):
    tweaks = {
        "trace": TraceConfig(enabled=True),
        "request_batch_max_count": 1,
        "request_batch_max_interval": 0.01,
    }
    tweaks.update(extra)
    return tweaks


def _run_cluster(seed=7, decisions=DECISIONS, **cluster_kwargs):
    cluster = Cluster(
        4, seed=seed, config_tweaks=_traced_tweaks(), **cluster_kwargs
    )
    cluster.start()
    for i in range(decisions):
        cluster.submit_to_all(make_request("trace", i))
    assert cluster.run_until_ledger(decisions)
    return cluster


# --- unit: the ring buffer -------------------------------------------------


def test_ring_buffer_wraps_without_unbounded_growth():
    t = Tracer(lambda: 0.0, capacity=16)
    for i in range(100):
        t.instant("unit", "tick", n=i)
    events = t.events()
    assert len(events) == 16  # bounded: old events evicted, not accumulated
    assert t.appended == 100
    assert t.dropped == 84
    # Oldest-first, and the survivors are exactly the newest 16.
    assert [ev[6]["n"] for ev in events] == list(range(84, 100))


def test_tracer_rejects_zero_capacity():
    try:
        Tracer(lambda: 0.0, capacity=0)
    except ValueError:
        return
    raise AssertionError("capacity=0 must be rejected")


def test_noop_tracer_never_appends():
    before = Tracer.total_appends
    NOOP_TRACER.begin("x", "y", seq=1)
    NOOP_TRACER.instant("x", "z")
    NOOP_TRACER.end("x", "y", seq=1)
    assert Tracer.total_appends == before
    assert NOOP_TRACER.events() == []
    assert not NOOP_TRACER.enabled


# --- determinism: same seed, byte-identical exports ------------------------


def test_same_seed_exports_byte_identical_span_streams():
    streams = []
    for _ in range(2):
        cluster = _run_cluster(seed=7)
        tracer = cluster.nodes[1].consensus.tracer
        streams.append(
            (to_chrome_json(tracer.events()), to_jsonl(tracer.events()))
        )
    assert streams[0][0] == streams[1][0], "Chrome export diverged"
    assert streams[0][1] == streams[1][1], "JSONL export diverged"


# --- export validity + span nesting + chain completeness -------------------


def test_chrome_export_valid_spans_nest_and_chains_complete():
    cluster = _run_cluster(seed=11)
    tracer = cluster.nodes[1].consensus.tracer
    doc = json.loads(to_chrome_json(tracer.events()))
    assert doc["displayTimeUnit"] == "ms"
    records = doc["traceEvents"]
    assert records, "empty trace"

    # Async span streams pair by (cat, id, name): walk each stream and
    # require strict b/e alternation ending balanced — that is what makes
    # the spans NEST correctly when Perfetto reassembles them.
    open_spans = {}
    for ev in records:
        ph = ev["ph"]
        if ph not in ("b", "e"):
            continue
        key = (ev["cat"], ev["id"], ev["name"])
        depth = open_spans.get(key, 0)
        if ph == "b":
            assert depth == 0, f"double-begin for {key}"
            open_spans[key] = 1
        else:
            assert depth == 1, f"end-without-begin for {key}"
            open_spans[key] = 0
        # Timestamps are microseconds on the sim clock: monotone per spec
        # is guaranteed by the scheduler; just require non-negative.
        assert ev["ts"] >= 0
    dangling = [k for k, d in open_spans.items() if d]
    assert not dangling, f"unclosed spans: {dangling}"

    # Every committed sequence has the complete phase chain.
    report = build_report(tracer.events())
    assert report["n_decisions"] == DECISIONS
    assert report["n_complete"] == DECISIONS
    seqs = sorted(seq for (seq, _view) in report["decisions"])
    assert seqs == list(range(1, DECISIONS + 1))
    for phase in ("pre_prepare", "prepare", "commit", "deliver"):
        stats = report["phase_percentiles"][phase]
        assert stats["n"] == DECISIONS
        assert stats["p50"] >= 0.0 and stats["p99"] >= stats["p50"]
    # The human-readable table renders every phase row.
    table = format_table(report)
    for phase in report["phase_percentiles"]:
        assert phase in table


def test_jsonl_export_one_valid_object_per_event():
    cluster = _run_cluster(seed=13, decisions=5)
    tracer = cluster.nodes[1].consensus.tracer
    lines = to_jsonl(tracer.events()).splitlines()
    assert len(lines) == len(tracer.events())
    for line in lines:
        obj = json.loads(line)
        assert obj["ph"] in ("B", "E", "i")
        assert isinstance(obj["ts"], float)


# --- critical path under pipelining ----------------------------------------


def test_critical_path_report_with_pipelined_decisions_in_flight():
    """The report's FIFO pool-admit -> batch-seal matching must stay exact
    when ``pipeline_depth > 1`` keeps several decisions in flight: every
    decision still gets a ``pool_wait``/``seal_wait`` attribution, seals
    never consume more admits than the leader recorded, and the chains all
    complete."""
    decisions = 24
    cluster = Cluster(
        4,
        seed=41,
        config_tweaks=_traced_tweaks(
            pipeline_depth=4,
            request_batch_max_count=2,
            request_batch_max_interval=0.005,
        ),
    )
    cluster.start()
    for i in range(decisions * 2):  # two requests per sealed batch
        cluster.submit_to_all(make_request("pipe", i))
    assert cluster.run_until_ledger(decisions, max_time=120.0)

    events = cluster.nodes[1].consensus.tracer.events()  # the static leader
    # The window genuinely overlapped: decision spans were concurrently
    # open, so FIFO matching ran against interleaved admits and seals.
    open_now = max_open = 0
    for ph, _track, name, _ts, _seq, _view, _args in events:
        if name == "decision":
            open_now += 1 if ph == "B" else -1
            max_open = max(max_open, open_now)
    assert max_open > 1, "depth=4 run never pipelined"

    report = build_report(events)
    assert report["n_decisions"] == decisions
    assert report["n_complete"] == decisions
    percentiles = report["phase_percentiles"]
    for phase in ("pool_wait", "seal_wait"):
        assert percentiles[phase]["n"] == decisions
        assert percentiles[phase]["p50"] >= 0.0
    for d in report["decisions"].values():
        assert d["phases"]["pool_wait"] >= 0.0
        assert d["phases"]["seal_wait"] >= 0.0
    admits = sum(
        1 for ev in events if ev[0] == "i" and ev[2] == "pool.admit"
    )
    sealed = sum(
        (ev[6] or {}).get("count", 1)
        for ev in events
        if ev[0] == "i" and ev[2] == "batch.seal"
    )
    assert sealed <= admits, "seals consumed admits that never happened"


# --- crash-matrix visibility ----------------------------------------------


def test_crash_trace_contains_fired_fault_instant():
    cluster = Cluster(4, seed=23, config_tweaks=_traced_tweaks())
    cluster.start()
    victim = cluster.nodes[2]
    point = "state.save.commit.pre"
    plan = FaultPlan(point, label="trace-visibility")
    victim.arm_fault_plan(plan)
    tracer = victim.consensus.tracer  # ref survives the node teardown

    for i in range(3):
        cluster.submit_to_all(make_request("crash", i))
    survivors = [1, 3, 4]
    assert cluster.run_until_ledger(1, node_ids=survivors)
    assert plan.fired == (point, 1)

    fired = [
        ev
        for ev in tracer.events()
        if ev[0] == "i" and ev[1] == "fault" and ev[2] == "fault.fired"
    ]
    assert len(fired) == 1
    assert fired[0][6] == {"point": point, "hit": 1}


# --- overhead guard: disabled tracing is allocation-free -------------------


def test_disabled_tracing_makes_zero_ring_appends():
    decisions = 200
    before = Tracer.total_appends
    cluster = Cluster(  # default config: TraceConfig(enabled=False)
        4,
        seed=31,
        config_tweaks={
            "request_batch_max_count": 1,
            "request_batch_max_interval": 0.01,
        },
    )
    cluster.start()
    assert cluster.nodes[1].consensus.tracer is NOOP_TRACER
    for i in range(decisions):
        cluster.submit_to_all(make_request("off", i))
    assert cluster.run_until_ledger(decisions)
    assert Tracer.total_appends == before, (
        "disabled tracing must never touch a ring buffer"
    )

    # Parity: the same schedule with tracing ON commits the same count —
    # instrumentation must not perturb the protocol.
    traced = _run_cluster(seed=31, decisions=decisions)
    assert len(traced.nodes[1].app.ledger) == decisions
    assert len(cluster.nodes[1].app.ledger) == decisions


# --- metrics parity: tracer and histograms see the same values -------------


def test_dump_keys_pinned_and_trace_feeds_same_values():
    # The documented key names are a contract; renaming breaks loudly here.
    assert VERIFY_LAUNCH_BATCH_KEY == "consensus_cross_slot_verify_batch"
    assert WAL_RECORDS_PER_FSYNC_KEY == "consensus_wal_records_per_fsync"

    provider = InMemoryProvider()
    cluster = Cluster(
        4,
        seed=17,
        config_tweaks=_traced_tweaks(),
        durability_window=0.02,  # group commit: records coalesce per fsync
    )
    cluster.nodes[1].metrics = Metrics(provider)
    cluster.start()
    for i in range(20):
        cluster.submit_to_all(make_request("par", i))
    assert cluster.run_until_ledger(20)

    tracer = cluster.nodes[1].consensus.tracer
    report = build_report(tracer.events())
    dump = provider.dump()
    assert VERIFY_LAUNCH_BATCH_KEY in dump
    assert WAL_RECORDS_PER_FSYNC_KEY in dump

    # verify.launch instants carry exactly what the histogram observed.
    assert report["verify_launch_sizes"] == (
        dump[VERIFY_LAUNCH_BATCH_KEY]["observations"]
    )
    # wal.fsync instants end on the same value the coalescing gauge holds.
    assert report["fsync_records"], "group-commit run must record fsyncs"
    assert report["fsync_records"][-1] == (
        dump[WAL_RECORDS_PER_FSYNC_KEY]["value"]
    )


def test_net_injected_event_keys_pinned_and_mirror_trace_instants():
    """The chaos engine's injected network events (testing/network.py) are
    triple-booked: the SimNetwork.injected counter, the pinned-key metrics
    counters, and per-event ``net.<kind>`` tracer instants.  All three
    must agree event-for-event, and the key names are a contract."""
    from collections import Counter

    from consensus_tpu.metrics import (
        NET_DROPPED_KEY,
        NET_DUPLICATED_KEY,
        NET_INJECTED_KEYS,
        NET_REORDERED_KEY,
        NET_REPLAYED_KEY,
    )
    from consensus_tpu.runtime.scheduler import SimScheduler
    from consensus_tpu.testing.network import INJECTED_EVENT_KINDS, SimNetwork

    assert NET_DROPPED_KEY == "net_injected_dropped"
    assert NET_DUPLICATED_KEY == "net_injected_duplicated"
    assert NET_REORDERED_KEY == "net_injected_reordered"
    assert NET_REPLAYED_KEY == "net_injected_replayed"
    assert NET_INJECTED_KEYS == tuple(
        f"net_injected_{kind}" for kind in INJECTED_EVENT_KINDS
    )

    provider = InMemoryProvider()
    sched = SimScheduler()
    net = SimNetwork(sched, seed=3)
    net.metrics = Metrics(provider).network
    tracer = Tracer(sched.now, capacity=8192)
    net.tracer = tracer
    net.register(1, lambda s, p, r: None)
    net.register(2, lambda s, p, r: None)
    net.set_loss(1, 2, 0.3)
    net.set_duplicate(1, 2, 0.3)
    net.set_reorder(1, 2, 0.3)
    net.set_replay(1, 2, 0.3)
    for i in range(300):
        net.send(1, 2, b"m%d" % i, is_request=True)
        sched.advance(0.002)
    sched.advance(1.0)

    assert sum(net.injected.values()) > 0, "seeded run must inject"
    dump = provider.dump()
    instants = Counter(
        ev[2] for ev in tracer.events() if ev[0] == "i" and ev[1] == "net"
    )
    for kind in INJECTED_EVENT_KINDS:
        assert dump[f"net_injected_{kind}"]["value"] == net.injected[kind]
        assert instants[f"net.{kind}"] == net.injected[kind]


# --- rotation and the pool removal after a sync show in a decision trace ---


def test_hand_over_spans_and_sync_forget_span_in_a_decision_trace():
    """With the source's rotation (every 3 decisions) each hand-over is a
    ``controller`` / ``handover`` span from the delivery that ended a turn to
    the next leader's first pre-prepare (sent, on that leader; taken up, on a
    follower), naming the turn's first sequence, the next leader and what was
    pooled; a replica that catches up by sync brackets the removal
    of the synced requests from its pool in ``controller`` / ``sync.forget``,
    inside neither ``sync`` (the synchronizer's call) nor a decision."""
    tweaks = _traced_tweaks(decisions_per_leader=3)
    cluster = Cluster(4, seed=11, leader_rotation=True, config_tweaks=tweaks)
    cluster.start()

    def decide(i, node_ids=None):
        height = len(cluster.nodes[1].app.ledger)
        cluster.submit_to_all(make_request("rot", i))
        assert cluster.run_until_ledger(height + 1, node_ids=node_ids)

    for i in range(12):  # the lagger's first turn (decisions 10-12) is over
        decide(i)
    lagger = cluster.nodes[4]
    cluster.network.disconnect(4)
    for i in range(12, 16):
        decide(i, node_ids=[1, 2, 3])
    cluster.network.connect(4)
    decide(16, node_ids=[1, 2, 3])
    assert cluster.scheduler.run_until(
        lambda: len(lagger.app.ledger) == 17, max_time=30.0)

    # one hand-over after every third decision: seq 4 on is node 2's, 7 on 3's, ...
    turns = [(4, 2), (7, 3), (10, 4), (13, 1), (16, 2)]
    for node_id in (1, 2, 3):  # node 1 leads the fourth turn, node 2 two others
        spans = [ev for ev in cluster.nodes[node_id].consensus.tracer.events()
                 if ev[1] == "controller" and ev[2] == "handover"]
        assert [ev[0] for ev in spans] == ["B", "E"] * len(turns)
        begins, ends = spans[0::2], spans[1::2]
        assert [(ev[4], ev[6]["leader"]) for ev in begins] == turns
        assert [ev[4] for ev in ends] == [seq for seq, _ in turns]
        # one request a decision and all delivered: nothing was pooled
        assert all(ev[6]["pooled"] == 0 for ev in begins)
        health = cluster.nodes[node_id].consensus.controller.health()
        assert health["leader_handovers"] == len(turns)
        assert health["handover_ns"] == sum(
            int((e[3] - b[3]) * 1e9) for b, e in zip(begins, ends)) > 0

    events = lagger.consensus.tracer.events()
    forget = [ev for ev in events if ev[1] == "controller" and ev[2] == "sync.forget"]
    assert [ev[0] for ev in forget] == ["B", "E"]
    assert forget[0][6] == {"decisions": 5}
    assert forget[1][6] == {"removed": 5, "requests": 5}
    order = [(ev[0], ev[2]) for ev in events
             if ev[1] == "controller" and ev[2] in ("sync", "sync.forget")]
    assert order == [("B", "sync"), ("E", "sync"),
                     ("B", "sync.forget"), ("E", "sync.forget")]
    health = lagger.consensus.controller.health()
    assert (health["syncs"], health["synced_decisions"],
            health["sync_pool_removed"]) == (1, 5, 5)
    # the span streams still export
    assert json.loads(to_chrome_json(events))["traceEvents"]
