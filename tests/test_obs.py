"""Cluster observability plane (consensus_tpu/obs/): determinism, detector
soundness, the flight recorder, exporters, kernel accounting, the pinned
metric-key registry, and the disabled-overhead guard.

The plane is pure observation over the deterministic simulation, so its
exports inherit the repo's replayability contract: a fixed-seed chaos run
must produce byte-identical JSONL sample series and Prometheus scrape
bodies across runs, byte-identical ledgers with the plane on or off, and a
golden-file-pinned Prometheus body for a fixed-seed 3-node run.  Each of
the five anomaly detectors must fire under a chaos schedule crafted to
show its symptom and stay silent on clean soaks.  A flight-recorder bundle
written at the moment the PR-5 sentinel bug violates quorum-cert must let
the loader reconstruct the failing node's last view/leader/in-flight state
WITHOUT re-running the schedule.  And, like tracing, the default-off plane
must take zero ring samples and install nothing on the nodes.
"""

import json
import os

import pytest

import consensus_tpu.core.view as view_mod
from consensus_tpu.config import ObsConfig
from consensus_tpu.metrics import (
    OBS_ANOMALY_KEYS,
    OBS_SAMPLES_KEY,
    PINNED_METRIC_KEYS,
    InMemoryProvider,
    Metrics,
)
from consensus_tpu.obs import (
    ClusterSampler,
    DetectorThresholds,
    KernelRegistry,
    instrumented_jit,
    load_flight_record,
    sample_to_prometheus,
    series_to_jsonl,
    sparkline,
)
from consensus_tpu.obs.detectors import ANOMALY_KINDS
from consensus_tpu.obs.export import (
    HEALTH_FIELDS,
    OPTIONAL_HEALTH_FIELDS,
    render_watch,
)
from consensus_tpu.obs.flightrec import FlightRecorder
from consensus_tpu.runtime.scheduler import SimScheduler
from consensus_tpu.testing.app import Cluster, make_request
from consensus_tpu.testing.chaos import ChaosAction, ChaosEngine, ChaosSchedule
from test_chaos_engine import SENTINEL_SCHEDULE

_GOLDEN = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "golden",
    "obs_prometheus_3node.txt",
)

#: Partitions node 4 away for 100 sim-seconds.  The isolated node shows the
#: stall/lag symptoms (pending work, frozen ledger, growing height gap) and
#: — after the heal — catches up through sync, whose appends grow the
#: ledger without verify launches: commit_stall + sync_lag +
#: verify_collapse, with the default thresholds.  The no-op loss actions
#: only pace the engine's request submissions.
PARTITION_SCHEDULE = ChaosSchedule(
    seed=11,
    n=4,
    actions=tuple(
        [ChaosAction(at=30.0, kind="partition", args={"group": (4,)})]
        + [
            ChaosAction(
                at=40.0 + 10.0 * i, kind="loss", args={"a": 1, "b": 2, "p": 0.0}
            )
            for i in range(8)
        ]
        + [ChaosAction(at=130.0, kind="heal")]
    ),
)

#: Crashes leaders 1, 2, 3 back to back: every crash forces a view change,
#: so within a widened window the view number churns (storm) and the leader
#: identity churns (flap).
CHURN_SCHEDULE = ChaosSchedule(
    seed=13,
    n=4,
    actions=(
        ChaosAction(at=30.0, kind="crash", args={"node": 1}),
        ChaosAction(at=45.0, kind="restart", args={"node": 1}),
        ChaosAction(at=50.0, kind="crash", args={"node": 2}),
        ChaosAction(at=65.0, kind="restart", args={"node": 2}),
        ChaosAction(at=70.0, kind="crash", args={"node": 3}),
        ChaosAction(at=85.0, kind="restart", args={"node": 3}),
        ChaosAction(at=90.0, kind="heal"),
    ),
)

CHURN_THRESHOLDS = DetectorThresholds(
    storm_views=3, storm_window=120.0, flap_changes=3, flap_window=120.0
)


def _obs_run(schedule, *, interval=5.0, thresholds=None, flight_dir=None):
    obs = ObsConfig(
        enabled=True, sample_interval=interval, detector_thresholds=thresholds
    )
    engine = ChaosEngine(schedule, obs=obs, flight_dir=flight_dir)
    result = engine.run()
    return engine, result


@pytest.fixture
def sentinel_bug():
    view_mod.SENTINEL_MISWIRED_QUORUM = True
    try:
        yield
    finally:
        view_mod.SENTINEL_MISWIRED_QUORUM = False


# --- determinism: same seed, byte-identical exports ------------------------


def test_same_seed_chaos_run_exports_byte_identical_series():
    exports = []
    for _ in range(2):
        engine, result = _obs_run(ChaosSchedule.generate(3, n=4, steps=8))
        assert result.ok, result.violation
        sampler = engine.cluster.sampler
        assert sampler is not None and sampler.taken > 0
        exports.append(
            (
                series_to_jsonl(sampler.samples()),
                sample_to_prometheus(sampler.last_sample()),
            )
        )
    assert exports[0][0] == exports[1][0], "JSONL sample series diverged"
    assert exports[0][1] == exports[1][1], "Prometheus export diverged"


def test_sampling_is_observationally_transparent():
    """The plane only reads: a fixed-seed chaos run must produce identical
    ledgers and an identical deterministic event log with obs on or off
    (the clean schedule fires no detectors, so no ANOMALY lines either)."""
    schedule = ChaosSchedule.generate(3, n=4, steps=8)
    plain = ChaosEngine(schedule).run()
    engine, observed = _obs_run(schedule)
    assert plain.ok and observed.ok
    assert observed.anomalies == ()  # clean soak: every detector silent
    assert observed.ledgers == plain.ledgers
    assert observed.event_log == plain.event_log
    # The closing sample backs ChaosResult.final_health for every node.
    assert set(observed.final_health) == {"1", "2", "3", "4"}
    for health in observed.final_health.values():
        # Required fields always; the optional guard surface only appears
        # on nodes carrying a wire_guard, which this clean run has none of.
        assert set(HEALTH_FIELDS) - set(OPTIONAL_HEALTH_FIELDS) <= set(health)
        assert not set(OPTIONAL_HEALTH_FIELDS) & set(health)
    # Per-node sample counters (pinned key) agree with the ring count.
    for node in engine.cluster.nodes.values():
        dump = node.metrics.provider.dump()
        assert dump[OBS_SAMPLES_KEY]["value"] == engine.cluster.sampler.taken


def test_quiet_cluster_soak_is_anomaly_free():
    engine, result = _obs_run(
        ChaosSchedule(seed=7, n=4, actions=()), interval=2.0
    )
    assert result.ok, result.violation
    assert result.anomalies == ()
    assert engine.cluster.sampler.anomaly_counts() == {}
    for health in result.final_health.values():
        assert health["running"] and health["view"] == 0


# --- detector soundness matrix ---------------------------------------------


def test_partition_schedule_fires_stall_lag_and_collapse_detectors():
    engine, result = _obs_run(PARTITION_SCHEDULE, interval=2.0)
    assert result.ok, result.violation  # detectors observe; nothing breaks
    counts = engine.cluster.sampler.anomaly_counts()
    assert {"commit_stall", "sync_lag", "verify_collapse"} <= set(counts)
    # Every firing is triple-booked: the anomalies list, the node's pinned
    # obs_anomaly_* counter, and an ANOMALY line in the event log.
    assert len(result.anomalies) == sum(counts.values())
    pinned = 0
    for node in engine.cluster.nodes.values():
        dump = node.metrics.provider.dump()
        pinned += sum(dump[key]["value"] for key in OBS_ANOMALY_KEYS)
    assert pinned == len(result.anomalies)
    assert b"ANOMALY commit_stall" in result.event_log
    # The isolated node is the one indicted.
    assert {a.node for a in result.anomalies} == {4}


def test_leader_churn_schedule_fires_storm_and_flap_detectors():
    engine, result = _obs_run(
        CHURN_SCHEDULE, interval=2.0, thresholds=CHURN_THRESHOLDS
    )
    assert result.ok, result.violation
    counts = engine.cluster.sampler.anomaly_counts()
    assert {"view_change_storm", "leader_flap"} <= set(counts)
    # Together with the partition schedule, the churn chaos run
    # (tests/test_membership.py fires membership_churn end-to-end), and the
    # ingress scenarios (tests/test_ingress.py fires admission_overload and
    # dedup_storm end-to-end), the full detector matrix fires.
    partition_kinds = {"commit_stall", "sync_lag", "verify_collapse"}
    churn_kinds = {"membership_churn"}
    ingress_kinds = {"admission_overload", "dedup_storm"}
    engine_kinds = {"engine_degraded"}  # tests/test_supervisor.py end-to-end
    # tests/test_obs.py wal-detector units + tests/test_storage_faults.py
    # fire the storage pair end-to-end.
    storage_kinds = {"wal_corruption", "wal_stall"}
    # tests/test_groups_2pc.py fires cross_group_stall end-to-end.
    groups_kinds = {"cross_group_stall"}
    # tests/test_net_hardening.py fires wire_abuse end-to-end (sim chaos
    # net_abuse arm + detector unit).
    wire_kinds = {"wire_abuse"}
    assert (partition_kinds | churn_kinds | ingress_kinds | engine_kinds
            | storage_kinds | groups_kinds | wire_kinds
            | set(counts) >= set(ANOMALY_KINDS))


def test_wal_corruption_and_stall_detectors_edge_trigger():
    from consensus_tpu.obs.detectors import DetectorBank

    bank = DetectorBank()

    def sample(t, fenced, degraded):
        h = {"running": True, "ledger": 1, "pool": 0}
        if fenced is not None:
            h["wal_fenced"] = fenced
        if degraded is not None:
            h["wal_degraded"] = degraded
        return [a.kind for a in bank.evaluate(t, {2: h})]

    # MemWAL node (no wal health fields): nothing fires, ever.
    assert sample(0.0, None, None) == []
    # Rising edges fire exactly once each.
    assert sample(1.0, True, False) == ["wal_corruption"]
    assert sample(2.0, True, False) == []  # latched while it holds
    assert sample(3.0, True, True) == ["wal_stall"]
    assert sample(4.0, True, True) == []
    # Falling edges clear the latch; the next rise refires.
    assert sample(5.0, False, False) == []
    assert sample(6.0, True, False) == ["wal_corruption"]
    # A restart that loses the file-backed WAL (fields vanish) discards the
    # latch instead of leaving it stuck.
    assert sample(7.0, None, None) == []
    assert sample(8.0, True, False) == ["wal_corruption"]


def test_detector_firings_are_deterministic():
    runs = []
    for _ in range(2):
        _, result = _obs_run(PARTITION_SCHEDULE, interval=2.0)
        runs.append([a.as_dict() for a in result.anomalies])
    assert runs[0] == runs[1]
    assert runs[0], "the partition schedule must fire at least one detector"


# --- flight recorder --------------------------------------------------------


def test_flight_recorder_reconstructs_sentinel_failure_without_rerun(
    sentinel_bug, tmp_path
):
    engine, result = _obs_run(
        SENTINEL_SCHEDULE, interval=2.0, flight_dir=str(tmp_path)
    )
    assert not result.ok
    assert result.flightrec_path is not None
    assert os.path.exists(result.flightrec_path)
    assert not os.path.exists(result.flightrec_path + ".tmp")  # atomic write
    violation = result.violation

    # Diagnosis from the bundle ALONE: no engine, no re-run.
    rec = load_flight_record(result.flightrec_path)
    assert rec.seed == SENTINEL_SCHEDULE.seed
    assert rec.reason == "invariant"
    assert "quorum-cert" in rec.detail and "quorum is 3" in rec.detail
    assert rec.triggers[0]["node"] == violation.node
    assert rec.triggers[0]["t"] == round(violation.sim_time, 6)

    # The failing node's last known state, scanned off the sample tail.
    health = rec.last_health(violation.node)
    assert health is not None
    assert health["view"] >= 1  # the crash forced a view change first
    assert health["leader"] not in (-1, 1)  # past the crashed view-0 leader
    assert health["in_flight"] >= 0
    assert health["ledger"] >= 1
    # The bundle carries the reproducer and the per-node metrics snapshot.
    doc = rec.schedule_doc
    assert doc["seed"] == SENTINEL_SCHEDULE.seed
    assert len(doc["actions"]) == len(SENTINEL_SCHEDULE.actions)
    metrics = rec.metrics_of(violation.node)
    assert metrics is not None and OBS_SAMPLES_KEY in metrics


def test_flight_recorder_crash_point_and_exception_seams(tmp_path):
    sched = SimScheduler()
    rec = FlightRecorder(seed=99, out_dir=str(tmp_path), clock=sched.now)
    rec.attach_scheduler(sched)

    rec.on_fault_fired("state.save.commit.pre", 1)
    first = load_flight_record(rec.path)
    assert first.reason == "crash-point"
    assert "state.save.commit.pre" in first.detail

    def boom():
        raise RuntimeError("kaput")

    sched.call_later(1.0, boom, name="boom")
    sched.advance(2.0)  # the swallowed exception must still reach the hook
    redumped = load_flight_record(rec.path)
    assert redumped.reason == "crash-point"  # first cause wins
    assert [t["reason"] for t in redumped.triggers] == [
        "crash-point",
        "unhandled-exception",
    ]
    assert "kaput" in redumped.triggers[1]["detail"]
    assert redumped.triggers[1]["t"] == 1.0  # sim clock, not wall clock


def test_flight_record_loader_rejects_unknown_version(tmp_path):
    path = tmp_path / "flightrec_0.json"
    path.write_text(json.dumps({"flightrec_version": 999}))
    with pytest.raises(ValueError, match="unsupported flightrec version"):
        load_flight_record(str(path))


# --- Prometheus golden file -------------------------------------------------


def _golden_sample():
    cluster = Cluster(
        3,
        seed=42,
        config_tweaks={
            "request_batch_max_count": 1,
            "request_batch_max_interval": 0.01,
        },
        obs=ObsConfig(enabled=True, sample_interval=1.0),
    )
    cluster.start()
    for i in range(5):
        cluster.submit_to_all(make_request("golden", i))
    cluster.scheduler.advance(30.0)
    assert len(cluster.nodes[1].app.ledger) == 5
    return cluster.sampler.last_sample()


def test_prometheus_export_matches_golden_file():
    """Byte-for-byte pin of the scrape body for a fixed-seed 3-node run.
    Regenerate deliberately (never blindly) with:
    python -c "from tests.test_obs import _regen_golden; _regen_golden()"
    """
    body = sample_to_prometheus(_golden_sample())
    with open(_GOLDEN, encoding="utf-8") as fh:
        assert body == fh.read()


def _regen_golden():
    from consensus_tpu.obs.export import write_prometheus

    write_prometheus(_GOLDEN, _golden_sample())


def test_prometheus_export_is_well_formed_and_sorted():
    body = sample_to_prometheus(_golden_sample())
    lines = body.splitlines()
    assert body.endswith("\n")
    families = []
    for line in lines:
        if line.startswith("# TYPE "):
            families.append(line.split()[2])
        else:
            name = line.partition("{")[0].partition(" ")[0]
            assert name == families[-1], "sample outside its family block"
            value = line.rpartition(" ")[2]
            float(value)  # every exported value parses
            assert not value.endswith(".0"), "integers export without .0"
    assert families == sorted(families)
    assert "obs_sample_time" in families
    for field in HEALTH_FIELDS:
        if field in OPTIONAL_HEALTH_FIELDS:
            continue  # emitted only when a wire_guard is attached
        assert f"obs_health_{field}" in families
    # Every node labeled on every health family.
    assert 'obs_health_ledger{node="1"} 5' in lines
    assert 'obs_health_ledger{node="3"} 5' in lines


# --- JSONL + sparkline exporters -------------------------------------------


def test_jsonl_series_is_canonical_sorted_compact_json():
    engine, _ = _obs_run(ChaosSchedule(seed=7, n=4, actions=()), interval=5.0)
    samples = engine.cluster.sampler.samples()
    lines = series_to_jsonl(samples).splitlines()
    assert len(lines) == len(samples)
    for line, sample in zip(lines, samples):
        assert line == json.dumps(
            sample, sort_keys=True, separators=(",", ":")
        )
        doc = json.loads(line)
        assert set(doc) == {"t", "i", "nodes", "anomalies"}


def test_sparkline_rendering():
    assert sparkline([]) == ""
    assert sparkline([3, 3, 3]) == "▁▁▁"  # flat series: all-low, no divide
    assert sparkline(range(8)) == "▁▂▃▄▅▆▇█"
    assert len(sparkline(range(100), width=10)) == 10
    # Most-recent window: the tail of the series is what renders.
    assert sparkline([0] * 99 + [1], width=2) == "▁█"


def test_render_watch_panel_covers_requested_fields():
    samples = [
        {
            "t": float(i),
            "i": i,
            "nodes": {
                "1": {"health": {"ledger": i, "pool": 0, "in_flight": 1}},
                "2": {"health": {"ledger": i + 1, "pool": 2, "in_flight": 0}},
            },
            "anomalies": [],
        }
        for i in range(4)
    ]
    panel = render_watch(samples)
    rows = panel.splitlines()
    assert len(rows) == 3
    for field, row in zip(("ledger", "pool", "in_flight"), rows):
        assert field in row
    assert rows[0].rstrip().endswith("4")  # annotated with the latest max


# --- kernel accounting ------------------------------------------------------


def test_instrumented_jit_counts_launches_compiles_and_retraces():
    import jax.numpy as jnp

    registry = KernelRegistry()
    fn = instrumented_jit(lambda x: x + 1, "unit.add", registry=registry)
    assert int(fn(jnp.arange(4))[0]) == 1  # transparent: same outputs
    fn(jnp.arange(4))
    stats = registry.stats("unit.add")
    assert stats.launches == 2
    assert stats.compiles == 1
    assert stats.retraces == 0
    fn(jnp.arange(8))  # new shape: a retrace, not a fresh kernel
    assert stats.launches == 3
    assert stats.compiles == 2
    assert stats.retraces == 1
    # Cost estimates are captured at first compile (CPU backend may omit
    # them; the probe must degrade to None, never raise).
    assert stats.flops is None or stats.flops >= 0.0
    snap = registry.snapshot()
    assert list(snap) == ["unit.add"]
    assert snap["unit.add"]["launches"] == 3
    assert registry.totals() == {"launches": 3, "compiles": 2, "retraces": 1}
    registry.reset()
    assert registry.snapshot() == {}


def test_signature_models_route_through_the_kernel_registry():
    """The module-level verify kernels must be wrapped, so the sidecar's
    ``health`` sees launches without any plumbing of its own."""
    from consensus_tpu.models import ed25519

    assert getattr(ed25519._verify_kernel, "__wrapped__", None) is not None
    assert ed25519._verify_kernel.__name__ == "instrumented_ed25519.verify"
    assert (
        ed25519._batch_verify_kernel.__name__
        == "instrumented_ed25519.batch_verify"
    )


# --- pinned metric-key registry (satellite) ---------------------------------


class _CountingProvider(InMemoryProvider):
    def __init__(self):
        super().__init__()
        self.created = []

    def new_counter(self, name, help="", label_names=()):
        self.created.append((name, "counter"))
        return super().new_counter(name, help, label_names)

    def new_gauge(self, name, help="", label_names=()):
        self.created.append((name, "gauge"))
        return super().new_gauge(name, help, label_names)

    def new_histogram(self, name, help="", label_names=()):
        self.created.append((name, "histogram"))
        return super().new_histogram(name, help, label_names)


def test_pinned_metric_registry_is_complete_and_duplicate_free():
    provider = _CountingProvider()
    Metrics(provider)
    dump = provider.dump()
    kinds_of = {}
    for name, kind in provider.created:
        kinds_of.setdefault(name, set()).add(kind)
    for key, description in PINNED_METRIC_KEYS.items():
        assert description, f"{key} needs a registry description"
        assert key in dump, f"pinned key {key} missing from a fresh dump"
        assert key in kinds_of, f"pinned key {key} never created by a bundle"
        assert len(kinds_of[key]) == 1, (
            f"pinned key {key} created as {sorted(kinds_of[key])}"
        )
    # Detector kinds and their pinned counters stay in lockstep.
    assert tuple(f"obs_anomaly_{kind}" for kind in ANOMALY_KINDS) == (
        OBS_ANOMALY_KEYS
    )


# --- disabled-overhead guard ------------------------------------------------


def test_disabled_obs_plane_samples_nothing_and_installs_nothing():
    before = ClusterSampler.total_samples
    cluster = Cluster(  # default: no obs config at all
        4,
        seed=31,
        config_tweaks={
            "request_batch_max_count": 1,
            "request_batch_max_interval": 0.01,
        },
    )
    assert cluster.sampler is None
    cluster.start()
    for i in range(20):
        cluster.submit_to_all(make_request("off", i))
    assert cluster.run_until_ledger(20)
    assert ClusterSampler.total_samples == before, (
        "a disabled plane must never take a ring sample"
    )
    assert all(node.metrics is None for node in cluster.nodes.values()), (
        "a disabled plane must not install metrics providers"
    )


def test_obs_config_validation_rejects_bad_knobs():
    with pytest.raises(ValueError, match="sample_interval"):
        Cluster(4, obs=ObsConfig(enabled=True, sample_interval=0.0))
    with pytest.raises(ValueError, match="ring_capacity"):
        ObsConfig(enabled=True, ring_capacity=0).validate()
    # Disabled configs are inert whatever the knobs say.
    cluster = Cluster(4, obs=ObsConfig(enabled=False, sample_interval=-1.0))
    assert cluster.sampler is None
