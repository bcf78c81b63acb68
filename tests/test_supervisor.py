"""Engine supervision: fault-classed breakers, the degrade ladder, and the
deterministic device-fault chaos matrix.

The gates:

* :class:`CircuitBreaker` is a pure state machine over an injected clock —
  closed -> open -> half-open -> closed, doubled backoff on a failed probe;
* :class:`EngineSupervisor` classifies launch faults (timeout / raise /
  wrong answer via the sampled host cross-check), serves every call from
  the best healthy rung, and re-promotes when the breaker closes — while a
  host twin exists, NO launch ever raises out of ``verify_batch``;
* ``engine_for_config(engine_supervision=True)`` wraps the configured
  engine over the :func:`degrade_ladder_configs` ladder;
* the device-fault chaos matrix: every fault class (hang / raise /
  verdict-flip) injected into every engine mode (strict, fused,
  randomized, 2-shard mesh, half-agg) yields ledgers and event logs
  byte-identical to the fault-free run of the same seed — acceleration is
  an optimization, never a soundness or liveness dependency;
* every degrade is triple-booked: one ``engine_degrade_total{reason}``
  child per injected fault, an ``engine_recovered_total`` bump per
  re-promotion, and the edge-triggered ``engine_degraded`` detector
  (silent on clean soaks).
"""

import dataclasses

import numpy as np
import pytest

from consensus_tpu.config import Configuration, ObsConfig
from consensus_tpu.metrics import (
    ENGINE_CROSSCHECK_KEY,
    ENGINE_CROSSCHECK_MISMATCH_KEY,
    ENGINE_DEGRADE_KEY,
    ENGINE_RECOVERED_KEY,
    ENGINE_RUNG_KEY,
    InMemoryProvider,
    Metrics,
)
from consensus_tpu.models import (
    ENGINE_HEALTH,
    FAULT_CLASSES,
    CircuitBreaker,
    EngineHealth,
    EngineSupervisor,
    HostTwin,
    LaunchTimeout,
)
from consensus_tpu.models.verifier import degrade_ladder_configs, engine_for_config


class _Scripted:
    """Engine whose next-call behavior is set by the test: raise
    ``fail_with``, or answer (optionally with every verdict flipped)."""

    def __init__(self):
        self.calls = 0
        self.host_calls = 0
        self.fail_with = None
        self.flip = False

    def _truth(self, sigs):
        return np.array([s == b"good" for s in sigs], dtype=bool)

    def verify_batch(self, msgs, sigs, keys):
        self.calls += 1
        if self.fail_with is not None:
            raise self.fail_with
        out = self._truth(sigs)
        return ~out if self.flip else out

    def verify_host(self, msgs, sigs, keys):
        self.host_calls += 1
        return self._truth(sigs)


_BATCH = ([b"m"] * 3, [b"good", b"bad", b"good"], [b"k"] * 3)
_WANT = [True, False, True]


def _sup(engine=None, **kw):
    engine = engine or _Scripted()
    kw.setdefault("backoff_initial", 2.0)
    kw.setdefault("metrics", Metrics(InMemoryProvider()))
    return engine, EngineSupervisor([engine], **kw)


# --- circuit breaker --------------------------------------------------------


def test_breaker_lifecycle_closed_open_halfopen_closed():
    b = CircuitBreaker(failure_threshold=1, backoff_initial=10.0)
    assert b.state == "closed"
    assert b.record_failure(now=100.0)  # threshold 1: opens immediately
    assert b.state == "open" and b.opened_count == 1
    assert not b.probe_due(105.0)  # backoff not elapsed
    assert b.state == "open"
    assert b.probe_due(110.0)
    assert b.state == "half_open"
    assert b.probe_due(110.0)  # half-open keeps granting the probe
    assert b.record_success(110.0)  # half-open -> closed edge
    assert b.state == "closed" and b.failures == 0


def test_breaker_failed_probe_reopens_with_doubled_backoff():
    b = CircuitBreaker(failure_threshold=1, backoff_initial=10.0, backoff_max=15.0)
    b.record_failure(0.0)
    assert b.probe_due(10.0)
    assert b.record_failure(10.0)  # failed probe: reopen
    assert b.state == "open"
    assert not b.probe_due(10.0 + 10.0)  # doubled (capped at 15), not 10
    assert b.probe_due(10.0 + 15.0)
    b.record_success(25.0)  # success resets the backoff to initial
    b.record_failure(30.0)
    assert b.probe_due(40.0)


def test_breaker_threshold_counts_failures_before_opening():
    b = CircuitBreaker(failure_threshold=3, backoff_initial=1.0)
    assert not b.record_failure(0.0)
    assert not b.record_failure(0.0)
    assert b.record_failure(0.0)
    assert b.state == "open"


def test_breaker_validation_is_loud():
    with pytest.raises(ValueError):
        CircuitBreaker(failure_threshold=0)
    with pytest.raises(ValueError):
        CircuitBreaker(backoff_initial=0.0)
    with pytest.raises(ValueError):
        CircuitBreaker(backoff_initial=10.0, backoff_max=5.0)


# --- shared engine health ---------------------------------------------------


def test_engine_health_reports_edges_only():
    h = EngineHealth()
    assert not h.suspect
    assert h.mark_suspect("launch_raise")  # clear -> suspect edge
    assert not h.mark_suspect("launch_raise")  # already suspect: no edge
    assert h.suspect and h.reason == "launch_raise"
    assert h.suspect_marks == 2
    assert h.clear()
    assert not h.clear()
    assert not h.suspect


def test_health_registry_shares_one_entry_per_engine():
    a, b = _Scripted(), _Scripted()
    ha = ENGINE_HEALTH.for_engine(a)
    assert ENGINE_HEALTH.for_engine(a) is ha
    assert ENGINE_HEALTH.for_engine(b) is not ha
    # Unweakrefable engines still get a (private) health entry instead of
    # an exception — metrics and health must never break the verify path.
    assert isinstance(ENGINE_HEALTH.for_engine([]), EngineHealth)


# --- host twin --------------------------------------------------------------


def test_host_twin_is_ground_truth_and_its_own_twin():
    eng = _Scripted()
    eng.flip = True  # device verdicts corrupted...
    twin = HostTwin(eng)
    assert list(twin.verify_batch(*_BATCH)) == _WANT  # ...twin uses host
    assert list(twin.verify_host(*_BATCH)) == _WANT
    assert twin.randomized is False


def test_host_twin_requires_a_host_path():
    class _DeviceOnly:
        def verify_batch(self, m, s, k):  # pragma: no cover - never called
            raise AssertionError

    with pytest.raises(ValueError, match="no host twin"):
        HostTwin(_DeviceOnly())


# --- supervisor: fault classes, ladder, re-promotion ------------------------


def test_supervisor_appends_host_twin_and_delegates_shape_attrs():
    eng = _Scripted()
    eng.pad_to = 64
    sup = EngineSupervisor([eng])
    assert sup.rung_count == 2 and isinstance(sup._rungs[-1], HostTwin)
    assert sup.pad_to == 64  # engine-shape attrs come from the PRIMARY rung
    with pytest.raises(AttributeError):
        sup._no_such_attr
    with pytest.raises(ValueError):
        EngineSupervisor([])


@pytest.mark.parametrize(
    "exc,reason",
    [
        (LaunchTimeout("hung device call"), "launch_timeout"),
        (RuntimeError("XLA launch failed"), "launch_raise"),
    ],
)
def test_launch_fault_degrades_to_host_and_repromotes(exc, reason):
    eng, sup = _sup()
    eng.fail_with = exc
    # Launch 1: fault -> degrade -> served by the host twin, no raise.
    assert list(sup.verify_batch(*_BATCH)) == _WANT
    assert sup.degraded and sup.rung == 1
    assert sup.breakers[reason].state == "open"
    eng.fail_with = None
    # Launch 2 (launch-count clock t=2 < retry 1+2): still host-served.
    assert list(sup.verify_batch(*_BATCH)) == _WANT
    assert sup.degraded and eng.calls == 1
    # Launch 3: backoff elapsed -> half-open probe succeeds -> re-promoted.
    assert list(sup.verify_batch(*_BATCH)) == _WANT
    assert not sup.degraded and sup.rung == 0 and eng.calls == 2
    assert sup.breakers[reason].state == "closed"
    assert not sup.health.suspect
    provider_dump = _provider_dump(sup)
    assert provider_dump[f"{ENGINE_DEGRADE_KEY}{{{reason}}}"]["value"] == 1
    assert provider_dump[ENGINE_RECOVERED_KEY]["value"] == 1
    assert provider_dump[ENGINE_RUNG_KEY]["value"] == 0


def _provider_dump(sup):
    # The bundle's instruments all live on one InMemoryProvider; reach it
    # through any instrument's owner (tests only).
    return sup._metrics.count_degrade._provider.dump()


def test_crosscheck_catches_wrong_answers_and_serves_host_verdict():
    eng, sup = _sup(crosscheck_interval=1)
    eng.flip = True
    out = sup.verify_batch(*_BATCH)
    # The host twin's answer is the one that leaves the call.
    assert list(out) == _WANT
    assert sup.degraded
    assert sup.breakers["wrong_answer"].state == "open"
    dump = _provider_dump(sup)
    assert dump[f"{ENGINE_DEGRADE_KEY}{{wrong_answer}}"]["value"] == 1
    assert dump[ENGINE_CROSSCHECK_KEY]["value"] == 1
    assert dump[ENGINE_CROSSCHECK_MISMATCH_KEY]["value"] == 1


def test_crosscheck_samples_every_kth_launch():
    eng, sup = _sup(crosscheck_interval=3)
    for _ in range(6):
        assert list(sup.verify_batch(*_BATCH)) == _WANT
    dump = _provider_dump(sup)
    assert dump[ENGINE_CROSSCHECK_KEY]["value"] == 2  # launches 3 and 6
    assert dump[ENGINE_CROSSCHECK_MISMATCH_KEY]["value"] == 0
    assert not sup.degraded


def test_failed_probe_doubles_backoff_without_double_booking():
    eng, sup = _sup()
    eng.fail_with = RuntimeError("persistent device loss")
    served = [list(sup.verify_batch(*_BATCH)) for _ in range(8)]
    assert all(out == _WANT for out in served)  # host twin masks every call
    assert sup.degraded and len(sup._degrade_stack) == 1  # never double-pushed
    eng.fail_with = None
    # Walk launches until the reopened breaker grants the next probe.
    for _ in range(8):
        assert list(sup.verify_batch(*_BATCH)) == _WANT
        if not sup.degraded:
            break
    assert not sup.degraded and sup.rung == 0
    assert sup.breakers["launch_raise"].state == "closed"


def test_no_raise_escapes_verify_while_a_host_twin_exists():
    eng, sup = _sup()
    for exc in (RuntimeError("x"), LaunchTimeout("y"), ValueError("z")):
        eng.fail_with = exc
        assert list(sup.verify_batch(*_BATCH)) == _WANT  # never raises
    # Without a host twin the last rung fails LOUD — never silently wrong
    # (and never spins: a bottom-rung LaunchTimeout re-raises too).
    class _NoHost:
        boom = RuntimeError("device loss")

        def verify_batch(self, m, s, k):
            raise self.boom

    bare_engine = _NoHost()
    bare = EngineSupervisor([bare_engine], append_host=True)  # nothing to append
    assert bare.rung_count == 1
    with pytest.raises(RuntimeError):
        bare.verify_batch(*_BATCH)
    bare_engine.boom = LaunchTimeout("wedged, no floor")
    with pytest.raises(LaunchTimeout):
        bare.verify_batch(*_BATCH)


def test_injected_clock_paces_the_breaker():
    t = [0.0]
    eng, sup = _sup(clock=lambda: t[0], backoff_initial=30.0)
    eng.fail_with = RuntimeError("boom")
    sup.verify_batch(*_BATCH)
    eng.fail_with = None
    sup.verify_batch(*_BATCH)
    assert sup.degraded  # no sim time elapsed: probe not due
    t[0] = 31.0
    sup.verify_batch(*_BATCH)
    assert not sup.degraded


def test_transition_hooks_and_rung_labels():
    class _Sharded(_Scripted):
        shard_count = 2

    eng, sup = _sup(engine=_Sharded())
    seen = []
    sup.on_transition.append(lambda kind, reason, rung: seen.append((kind, reason, rung)))
    assert sup.rung_label(0) == "_Sharded[2]"
    assert sup.rung_label(1) == "HostTwin"
    eng.fail_with = LaunchTimeout("wedge")
    sup.verify_batch(*_BATCH)
    eng.fail_with = None
    sup.verify_batch(*_BATCH)
    sup.verify_batch(*_BATCH)
    assert seen == [
        ("degrade", "launch_timeout", 1),
        ("recover", "launch_timeout", 0),
    ]


def test_fault_classes_are_the_pinned_label_order():
    assert FAULT_CLASSES == ("launch_timeout", "launch_raise", "wrong_answer")
    _, sup = _sup()
    assert set(sup.breakers) == set(FAULT_CLASSES)


# --- config routing ---------------------------------------------------------


def test_degrade_ladder_configs_walk_mesh_then_fusion_down():
    cfg = Configuration().with_(mesh_shards=2, device_prep=True)
    ladder = degrade_ladder_configs(cfg)
    assert [(c.mesh_shards, c.device_prep) for c in ladder] == [
        (2, True), (1, True), (1, False),
    ]
    assert degrade_ladder_configs(Configuration()) == [Configuration()]


def test_engine_for_config_routes_through_supervision():
    cfg = Configuration().with_(
        engine_supervision=True, engine_crosscheck_interval=4, mesh_shards=2,
    )
    sup = engine_for_config(cfg)
    assert isinstance(sup, EngineSupervisor)
    # 2-shard rung, single-device rung, host twin floor.
    assert sup.rung_count == 3 and isinstance(sup._rungs[-1], HostTwin)
    assert sup._crosscheck_interval == 4
    assert sup.rung_label(0).endswith("[2]")  # the 2-shard mesh engine
    assert sup.rung_label(1) == "Ed25519BatchVerifier"  # single-device rung
    plain = engine_for_config(Configuration())
    assert not isinstance(plain, EngineSupervisor)


def test_config_validates_crosscheck_requires_supervision():
    base = Configuration().with_(self_id=1)
    base.with_(engine_supervision=True, engine_crosscheck_interval=2).validate()
    with pytest.raises(ValueError, match="requires engine_supervision"):
        base.with_(engine_crosscheck_interval=2).validate()
    with pytest.raises(ValueError, match="engine_crosscheck_interval"):
        base.with_(
            engine_supervision=True, engine_crosscheck_interval=-1
        ).validate()


# --- device-fault chaos: schedules ------------------------------------------


def test_device_fault_schedules_are_deterministic_and_opt_in():
    from consensus_tpu.testing.chaos import DEVICE_FAULT_CLASSES, ChaosSchedule

    base = ChaosSchedule.generate(7, steps=12)
    assert ChaosSchedule.generate(7, steps=12, device_faults=False) == base, (
        "device_faults=False must consume no RNG: schedules replay unchanged"
    )
    s1 = ChaosSchedule.generate(7, steps=12, device_faults=True)
    assert s1 == ChaosSchedule.generate(7, steps=12, device_faults=True)
    assert s1.device_faults is True
    for seed in range(30):
        s = ChaosSchedule.generate(seed, steps=12, device_faults=True)
        for a in s.actions:
            if a.kind == "device_fault":
                assert a.args["fault"] in DEVICE_FAULT_CLASSES
                assert 1 <= a.args["launch"] <= 3
                return
    raise AssertionError("30 seeds of 12 steps must draw one device_fault")


def test_format_repro_carries_the_device_fault_flag():
    from consensus_tpu.testing.chaos import (
        ChaosEngine, ChaosSchedule, format_repro,
    )

    sched = ChaosSchedule.generate(3, steps=4)
    snippet = format_repro(ChaosEngine(sched).run())
    assert "device_faults=False," in snippet


def test_fault_injector_arms_fires_and_forwards_host_uninjected():
    from consensus_tpu.testing.chaos import FaultInjectingEngine

    eng = _Scripted()
    inj = FaultInjectingEngine(eng)
    inj.arm(1, "hang")
    inj.arm(2, "flip")
    with pytest.raises(ValueError, match="unknown device fault"):
        inj.arm(3, "melt")
    with pytest.raises(LaunchTimeout):
        inj.verify_batch(*_BATCH)
    assert list(inj.verify_batch(*_BATCH)) == [not v for v in _WANT]
    assert list(inj.verify_host(*_BATCH)) == _WANT  # host is ground truth
    assert list(inj.verify_batch(*_BATCH)) == _WANT  # disarmed again
    assert inj.fired == [(1, "hang"), (2, "flip")] and inj.pending == 0


# --- device-fault chaos: the byte-parity matrix ------------------------------

#: One fault per class, spread across launches so each degrade/recover
#: cycle completes before the next fault arms its launch.
_MATRIX_FAULTS = ((2, "hang"), (5, "raise"), (8, "flip"))
_MATRIX_SEED = 31


def _engine_modes():
    from consensus_tpu.models.fused import FusedEd25519BatchVerifier
    from consensus_tpu.parallel import ShardedEd25519Verifier, mesh_for_shards

    return {
        "strict": ("ed25519", None),
        "randomized": ("ed25519-batch", None),
        "halfagg": ("ed25519-halfagg", None),
        "fused": (
            "ed25519",
            lambda: FusedEd25519BatchVerifier(min_device_batch=10**9),
        ),
        "mesh2": (
            "ed25519",
            lambda: ShardedEd25519Verifier(
                mesh_for_shards(2), min_device_batch=10**9
            ),
        ),
    }


_CLEAN_RUNS: dict = {}


def _clean_run(mode):
    from consensus_tpu.testing.chaos import ChaosEngine, ChaosSchedule

    if mode not in _CLEAN_RUNS:
        crypto, factory = _engine_modes()[mode]
        sched = ChaosSchedule.generate(_MATRIX_SEED, n=4, steps=6)
        _CLEAN_RUNS[mode] = ChaosEngine(
            sched, crypto=crypto, engine_factory=factory
        ).run()
    return _CLEAN_RUNS[mode]


@pytest.mark.parametrize("mode", ["strict", "randomized", "halfagg", "fused", "mesh2"])
def test_device_fault_matrix_is_byte_identical_to_clean_run(mode):
    """Every fault class injected into every engine mode: the supervisor
    masks hang (launch timeout), raise (XLA failure), and flip (silent
    wrong answer, caught by the per-launch host cross-check) — ledgers AND
    the event log are byte-identical to the fault-free run, and each fault
    books exactly one ``engine_degrade_total{reason}``."""
    from consensus_tpu.testing.chaos import ChaosEngine, ChaosSchedule

    crypto, factory = _engine_modes()[mode]
    sched = ChaosSchedule.generate(_MATRIX_SEED, n=4, steps=6)
    eng = ChaosEngine(
        sched, crypto=crypto, engine_factory=factory,
        device_faults=_MATRIX_FAULTS,
    )
    res = eng.run()
    clean = _clean_run(mode)
    assert clean.ok, clean.violation
    assert res.ok, res.violation
    assert res.event_log == clean.event_log
    assert res.ledgers == clean.ledgers
    # All three faults actually fired on their armed launches...
    assert eng.fault_injector.fired == list(_MATRIX_FAULTS)
    assert eng.fault_injector.pending == 0
    # ...each booking exactly one degrade of its class, each recovered.
    dump = eng.engine_metrics.provider.dump()
    for reason in FAULT_CLASSES:
        assert dump[f"{ENGINE_DEGRADE_KEY}{{{reason}}}"]["value"] == 1, reason
    assert dump[ENGINE_RECOVERED_KEY]["value"] == 3
    assert dump[ENGINE_CROSSCHECK_MISMATCH_KEY]["value"] == 1  # the flip
    assert dump[ENGINE_RUNG_KEY]["value"] == 0  # re-promoted by run end
    assert not eng.supervisor.degraded
    assert all(b.state == "closed" for b in eng.supervisor.breakers.values())


def test_constructor_faults_imply_crypto_and_schedule_faults_arm_injector():
    from consensus_tpu.testing.chaos import ChaosEngine, ChaosSchedule

    eng = ChaosEngine(
        ChaosSchedule(seed=1, n=4, actions=()),
        device_faults=((1, "hang"),),
    )
    assert eng.crypto == "ed25519"  # device faults promote to real crypto
    # A schedule CARRYING device_fault actions arms the injector too.
    for seed in range(40):
        sched = ChaosSchedule.generate(seed, steps=10, device_faults=True)
        if any(a.kind == "device_fault" for a in sched.actions):
            assert ChaosEngine(sched).crypto == "ed25519"
            return
    raise AssertionError("40 seeds of 10 steps must draw one device_fault")


def test_generated_device_fault_schedule_runs_clean_and_replays():
    """End-to-end over the generated vocabulary (not constructor arming):
    a schedule that draws device_fault actions runs clean — the supervisor
    masks them — and byte-identically twice."""
    from consensus_tpu.testing.chaos import ChaosEngine, ChaosSchedule

    sched = None
    for seed in range(60):
        s = ChaosSchedule.generate(seed, n=4, steps=8, device_faults=True)
        if any(a.kind == "device_fault" for a in s.actions):
            sched = s
            break
    assert sched is not None
    e1 = ChaosEngine(sched)
    r1 = e1.run()
    assert r1.ok, r1.violation
    assert e1.fault_injector.fired, "the armed fault must actually fire"
    r2 = ChaosEngine(sched).run()
    assert r1.event_log == r2.event_log
    assert r1.ledgers == r2.ledgers


# --- device-fault chaos: observability --------------------------------------


def test_device_faults_fire_the_engine_degraded_detector():
    """Triple booking, end to end: the injected faults land as
    ``engine_degraded`` anomalies (ANOMALY lines in the event log, pinned
    per-node counters, sampler counts) while the run stays safe."""
    from consensus_tpu.testing.chaos import ChaosEngine, ChaosSchedule

    sched = ChaosSchedule.generate(_MATRIX_SEED, n=4, steps=6)
    eng = ChaosEngine(
        sched, obs=ObsConfig(enabled=True, sample_interval=2.0),
        device_faults=_MATRIX_FAULTS,
    )
    res = eng.run()
    assert res.ok, res.violation
    counts = eng.cluster.sampler.anomaly_counts()
    assert counts.get("engine_degraded", 0) >= 1
    assert b"ANOMALY engine_degraded" in res.event_log
    assert any(a.kind == "engine_degraded" for a in res.anomalies)
    dump = eng.engine_metrics.provider.dump()
    for reason in FAULT_CLASSES:
        assert dump[f"{ENGINE_DEGRADE_KEY}{{{reason}}}"]["value"] == 1
    assert dump[ENGINE_RECOVERED_KEY]["value"] == 3


def test_supervised_clean_soak_keeps_the_detector_silent():
    """A supervisor with no faults fired must never indict the engine: the
    detector is edge-triggered on DEGRADED, not on supervision being on."""
    from consensus_tpu.testing.chaos import ChaosEngine, ChaosSchedule

    sched = ChaosSchedule.generate(_MATRIX_SEED, n=4, steps=6)
    # Arm a fault on a launch the run never reaches: the supervisor is
    # installed and sampled, but stays at rung 0 throughout.
    eng = ChaosEngine(
        sched, obs=ObsConfig(enabled=True, sample_interval=2.0),
        device_faults=((10**6, "hang"),),
    )
    res = eng.run()
    assert res.ok, res.violation
    assert eng.fault_injector.fired == []
    assert "engine_degraded" not in eng.cluster.sampler.anomaly_counts()
    assert b"ANOMALY engine_degraded" not in res.event_log
    dump = eng.engine_metrics.provider.dump()
    assert dump[ENGINE_RECOVERED_KEY]["value"] == 0
    assert dump[ENGINE_RUNG_KEY]["value"] == 0
