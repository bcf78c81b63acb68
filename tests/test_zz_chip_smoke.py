"""CPU rehearsal of ``chip_smoke.py``'s served-path phases (rig, traffic,
"the device did it", verdicts) at n=4 / batch 64 with a DEVICE-PATH sidecar
on the CPU backend — the same code the chip runs at n=7 / batch 1000.

Nothing here is a chip result: the records say ``dry_run`` and
``platform: cpu``.  What the rehearsal pins is the check itself — it passes
when the sidecar's backend did every wave, and it FAILS (while the ledger
still grows on the replicas' host fallback) when the sidecar is taken away.

Subprocess-heavy and compiles the sidecar's two launch widths (512 lanes and
the half) on the CPU, and for a rig whose full wave is 1,024 lanes the three
of its ladder: named to sort last so it never displaces the rest of the
tier-1 suite inside its budget.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(tmp_path, emit=None, **kw):
    records = []

    def keep(record):
        records.append(record)
        if emit is not None:
            emit(record)

    result = chip_smoke.run_served_path(
        chip_smoke.DRY, str(tmp_path), 7, dry_run=True, emit=keep, **kw,
    )
    return result, {r["phase"]: r for r in records}


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """ONE sound rehearsal for the tests below, with the sidecar's ``health``
    read before the traffic and again once the verdict wave is through; then
    one NARROW wave of 64 lanes with every rejection class, sent the way the
    verdict wave was, with the verdicts it got and a third ``health``."""
    rig, health, narrow = [], [], {}

    def before_traffic(launcher):
        rig.append(launcher)
        health.append(launcher.sidecars["sc-0"].probe())

    def emit(record):
        if record["phase"] != "verdicts":
            return
        health.append(rig[0].sidecars["sc-0"].probe())
        from consensus_tpu.net.sidecar import SidecarVerifierClient

        spec = rig[0].spec
        wave, planted = chip_smoke._ed25519_wave(64, 11)
        client = SidecarVerifierClient(
            spec.sidecar_addresses()["sc-0"], auth_secret=spec.auth_secret,
            request_timeout=120.0)
        try:
            got = client.verify_batch(*wave)
        finally:
            client.close()
        narrow.update(wave=wave, planted=planted, got=got,
                      health=rig[0].sidecars["sc-0"].probe())

    result, phases = _run(tmp_path_factory.mktemp("chip_smoke"), emit=emit,
                          before_traffic=before_traffic)
    return result, phases, health, narrow


def test_rehearsal_passes_when_the_sidecar_backend_did_the_work(rehearsal):
    result, phases, _, _ = rehearsal
    assert result["ok"], phases
    assert result["device"]["platform"] == "cpu"
    assert all(r["dry_run"] for r in phases.values())
    size = chip_smoke.DRY
    rig, traffic, device, verdicts = (
        phases[k] for k in ("rig", "traffic", "device", "verdicts")
    )
    # Both launch widths, 512 lanes and the half, compiled before ready.
    assert rig["sidecar_lanes"] == 512 and rig["compiles_at_ready"] == 2
    assert traffic["replicas_delivered_all_exactly_once"]
    assert traffic["ledgers_identical"]
    assert traffic["decisions"] * size["batch"] >= size["requests"]
    assert traffic["leader_rotations_seen"] >= 3
    # Every signature the replicas sent was launched by the sidecar's
    # backend; nothing compiled after ready; nobody fell back.
    assert device["compiles_after_ready"] == 0
    assert (
        device["device_signatures"]
        == device["replica_signatures_sent"]
        == device["replica_signatures_served"]
        >= device["floor_committed_x_2f"]
    )
    assert device["client_fallbacks"] == 0 and device["suspect_clients"] == []
    assert device["degrade_count"] == 0 and device["sidecar_restarts"] == 0
    assert device["launches"] >= 1
    # The full-width wave with every rejection class, through the socket.
    assert verdicts["signatures"] == 512 and verdicts["rejected"] == 8
    assert verdicts["mismatches"] == [] and verdicts["launches"] == 1
    assert phases["teardown"]["ok"]


def test_sidecar_health_carries_the_flusher_ledger_and_it_only_grows(rehearsal):
    """``health["flusher"]``: every phase and counter of obs/kernels.py, as
    integers, cumulative (the warm-up waves are in the first reading already)
    and never decreasing; across the traffic every one of them moved but the
    fill buckets no flush fell into and the two counts of held flushes
    (never more of those than flushes), and the launches the kernel ledger
    counts are the flushes the coalescer counts."""
    from consensus_tpu.obs.kernels import FLUSHER_COUNTERS, FLUSHER_PHASES

    result, _, health, _ = rehearsal
    assert result["ok"] and len(health) == 2
    first, last = (h["flusher"] for h in health)
    assert set(first) == set(last) == set(FLUSHER_PHASES + FLUSHER_COUNTERS)
    assert all(type(v) is int for v in list(first.values()) + list(last.values()))
    assert all(last[k] >= first[k] >= 0 for k in first)
    # The two warm-up waves: 257 signatures for the full width, then the
    # smallest device wave for the half.  Both widths were compiled by the
    # flusher ahead of the first (the engine's compile_ahead): inside
    # engine_ns, outside the four verify.* phases.
    assert first["flushes"] == 2
    assert first["fill_le_75"] == 1 == first["fill_le_25"]
    assert first["verify.dispatch"] > 0
    assert first["engine_ns"] > sum(
        first[k] for k in FLUSHER_PHASES if k.startswith("verify."))
    buckets = [k for k in first if k.startswith("fill_le_")]
    # Only a learned burst is held for; the reach is booked with each hold.
    holds = ["hold_met", "hold_expired", "hold_reach_ns"]
    assert set(holds) < set(first) and set(holds) < set(FLUSHER_COUNTERS)
    assert (first["hold_reach_ns"] > 0) == (first["hold_met"] + first["hold_expired"] > 0)
    assert all(last[k] > first[k] for k in first if k not in buckets + holds)
    flushes = last["flushes"] - first["flushes"]
    assert flushes == sum(last[k] - first[k] for k in buckets)
    assert flushes == (health[1]["launches_after_ready"]
                       - health[0]["launches_after_ready"])
    assert last["submissions"] - first["submissions"] >= flushes
    held = sum(last[k] - first[k] for k in holds[:2])
    assert held <= flushes
    # A hold reaches at least the 2 ms floor and at most a launch.
    engine_ns = last["engine_ns"] - first["engine_ns"]
    assert 2_000_000 * held <= last["hold_reach_ns"] - first["hold_reach_ns"] <= (
        held * engine_ns)
    # The engine call is its four phases and little else.
    inside = sum(last[k] - first[k] for k in FLUSHER_PHASES if k.startswith("verify."))
    engine = last["engine_ns"] - first["engine_ns"]
    assert inside <= engine <= 1.05 * inside


def test_sidecar_books_follow_the_width_each_wave_rode(rehearsal):
    """Two widths compiled before ready and none after.  Up to the verdict
    wave: a burst cut short rode the half, a whole one (4 x 64 and the
    certificates) and the full-width verdict wave the whole;
    ``launches_by_lanes`` sums to the launches and ``device_lanes`` is the
    sum of the widths launched, not launches x 512; at ready both were zero
    though each width had run its warm-up.  Then the narrow wave: one launch
    of 256 lanes more, nothing compiled, and the narrow executable rejects
    exactly the planted lanes, as the host twin does."""
    from consensus_tpu.models import Ed25519BatchVerifier

    result, _, health, narrow = rehearsal
    assert result["ok"]
    ready, last = health
    assert ready["compiles"] == last["compiles"] == 2
    assert ready["compiles_after_ready"] == last["compiles_after_ready"] == 0
    assert ready["launches_by_lanes"] == {"256": 0, "512": 0}
    assert ready["device_lanes"] == 0 == ready["launches_after_ready"]
    by_lanes = last["launches_by_lanes"]
    assert set(by_lanes) == {"256", "512"} and by_lanes["512"] >= 1
    assert sum(by_lanes.values()) == last["launches_after_ready"]
    assert last["device_lanes"] == 256 * by_lanes["256"] + 512 * by_lanes["512"]
    assert last["device_signatures"] <= last["device_lanes"]
    assert last["lanes"] == 512  # the full width: what the harness sizes by

    after = narrow["health"]
    assert after["compiles"] == 2 and after["compiles_after_ready"] == 0
    assert after["launches_by_lanes"] == {
        "256": by_lanes["256"] + 1, "512": by_lanes["512"]}
    assert after["launches_after_ready"] == last["launches_after_ready"] + 1
    assert after["device_lanes"] == last["device_lanes"] + 256
    assert after["device_signatures"] == last["device_signatures"] + 64
    want = Ed25519BatchVerifier().verify_host(*narrow["wave"])
    assert [bool(v) for v in narrow["got"]] == want.tolist()
    assert sorted(narrow["planted"]) == [i for i, ok in enumerate(want) if not ok]
    assert len(narrow["planted"]) == 8


def test_a_rig_whose_quarter_is_256_lanes_compiles_three_widths_before_ready(
        tmp_path):
    """n=4 with 200 requests a proposal: the full wave is 1,024 lanes, so the
    sidecar's ladder is 256 / 512 / 1,024 (``launch_widths``: the n4 shape
    above keeps two).  All three compile before ready, each proved by the
    warm-up wave that selects it; then one wave for each rung through the
    socket: ``launches_by_lanes`` sums to ``launches_after_ready`` over three
    keys, ``device_lanes`` is the sum of the widths launched, nothing
    compiles, and every rung gives the host twin's verdicts."""
    from consensus_tpu.deploy import ClusterLauncher, ClusterSpec
    from consensus_tpu.models import Ed25519BatchVerifier
    from consensus_tpu.net.sidecar import SidecarVerifierClient
    from consensus_tpu.obs.kernels import FLUSHER_COUNTERS

    spec = ClusterSpec.generate(
        4, 1, str(tmp_path / "cluster"),
        config_overrides={"request_batch_max_count": 200}, hold_ports=True)
    assert spec.sidecar_wave_lanes() == 1024
    launcher = ClusterLauncher(spec)
    try:
        launcher.start(timeout=chip_smoke.DRY["start_timeout"])
        ready = launcher.sidecars["sc-0"].probe()
        assert ready["platform"] == "cpu" and ready["lanes"] == 1024
        assert ready["compiles"] == 3 and ready["compiles_after_ready"] == 0
        assert ready["launches_by_lanes"] == {"256": 0, "512": 0, "1024": 0}
        assert ready["launches_after_ready"] == 0 == ready["device_lanes"]
        # 513, 257 and 16 signatures: the smallest wave that needs each rung.
        flusher = ready["flusher"]
        assert "hold_reach_ns" in FLUSHER_COUNTERS and "hold_reach_ns" in flusher
        assert flusher["flushes"] == 3 == flusher["submissions"]
        assert (flusher["fill_le_75"], flusher["fill_le_50"],
                flusher["fill_le_25"], flusher["fill_le_100"]) == (1, 1, 1, 0)
        client = SidecarVerifierClient(
            spec.sidecar_addresses()["sc-0"], auth_secret=spec.auth_secret,
            request_timeout=120.0)
        try:
            for n, width in ((64, 256), (256, 256), (257, 512), (600, 1024)):
                wave, planted = chip_smoke._ed25519_wave(n, 35)
                want = Ed25519BatchVerifier().verify_host(*wave)
                assert [bool(v) for v in client.verify_batch(*wave)] == want.tolist()
                assert sorted(planted) == [i for i, ok in enumerate(want) if not ok]
        finally:
            client.close()
        last = launcher.sidecars["sc-0"].probe()
        assert last["compiles"] == 3 and last["compiles_after_ready"] == 0
        assert last["launches_by_lanes"] == {"256": 2, "512": 1, "1024": 1}
        assert sum(last["launches_by_lanes"].values()) == last["launches_after_ready"]
        assert last["device_lanes"] == 2 * 256 + 512 + 1024
        assert last["device_signatures"] == 64 + 256 + 257 + 600
        assert last["host_signatures"] == 0
    finally:
        launcher.stop()


def test_rehearsal_fails_when_the_sidecar_is_killed_before_traffic(tmp_path):
    """Replicas fall back to the host, the ledger still grows, every request
    still commits — and the check must fail all the same."""

    def kill_sidecar(launcher):
        # SIGSTOP-free, restart-free: the sidecar is simply gone.
        launcher.sidecars["sc-0"].restart_enabled = False
        launcher.kill_sidecar("sc-0")
        deadline = time.monotonic() + 10.0
        while launcher.sidecars["sc-0"].alive and time.monotonic() < deadline:
            time.sleep(0.05)

    result, phases = _run(tmp_path, before_traffic=kill_sidecar)
    assert not result["ok"]
    # The protocol never noticed ...
    assert phases["traffic"]["replicas_delivered_all_exactly_once"], phases
    assert phases["traffic"]["decisions"] >= 12
    # ... but the processes' own reports did.
    assert not phases["device"]["ok"]
    assert phases["device"]["client_fallbacks"] > 0
    assert phases["device"]["device_signatures"] != (
        phases["device"]["replica_signatures_sent"]
    ) or phases["device"]["platform"] is None


def test_chip_smoke_refuses_the_cpu(tmp_path):
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` exits non-zero and prints
    no result; so does a dry run that is not pinned to the CPU."""
    for env, argv in (
        (dict(os.environ, JAX_PLATFORMS="cpu"), []),
        ({k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"},
         ["--dry-run"]),
    ):
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py", "--out", str(tmp_path), *argv],
            cwd=_REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode != 0, proc.stdout + proc.stderr
        assert proc.stdout.strip() == ""


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script with nothing else of the repo beside it cannot run."""
    import shutil

    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert not [l for l in proc.stdout.splitlines() if '"ok": true' in l]


def test_last_line_is_exactly_ok_and_device():
    """The driver parses the last stdout line: ``ok`` and ``device`` with
    ``platform`` / ``kind`` (text) and ``count`` (whole number), no other
    key; and no line at all when no process reported a device."""
    line = chip_smoke.contract_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert json.loads(chip_smoke.contract_line(
        False, {"platform": "cpu", "kind": "cpu", "count": 8, "extra": 1}
    )) == {"ok": False,
           "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    assert chip_smoke.contract_line(False, None) is None
    assert chip_smoke.contract_line(
        False, {"platform": None, "kind": None, "count": None}) is None


def test_census_lane_table_covers_the_registry():
    """Every single-device key the engine registry builds has a census lane
    (strict / randomized x host-prep / device-prep, P-256), the half-agg
    certs ride both Ed25519 front-ends, and the default lane comes first."""
    from consensus_tpu.models.registry import ENGINE_REGISTRY

    lanes = chip_smoke.LANES
    assert next(iter(lanes)) == "ed25519.strict"
    covered = set()
    for env, curve, knobs, _check in lanes.values():
        covered.add((
            curve,
            "randomized" if knobs.get("batch_verify_mode") else "strict",
            bool(knobs.get("device_prep")),
            env.get("CTPU_MXU_LIMBS") == "1",
        ))
    for key in ENGINE_REGISTRY.keys():
        if key.topology != "single":
            continue
        if key.mxu and key.device_prep:
            continue  # the MXU flag lanes are censused on host-prep engines
        assert (key.curve, key.mode, key.device_prep, key.mxu) in covered, key
    assert {c for *_x, c in lanes.values()} == {"verdicts", "halfagg"}
    json.dumps({k: v[0] for k, v in lanes.items()})  # env flags are plain data
