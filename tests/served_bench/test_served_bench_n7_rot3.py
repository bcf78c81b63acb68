"""BASELINE configs[2] with the shipped rotation on, as the rig reads it: the
rotation reaches the overrides every one of the 7 replicas is started with,
nothing else of the static twin's file moved, and its cell is listed under
the metrics it reports and no others.  The paced twin of the cell was
measured and left out (PERF.md section 6, PR 33: its p95 spread 12.6% over
8 runs against a 6% bound); ``CELLS`` takes it back when it is steadied."""

import os

import pytest

from sb_limits import limit
from served_bench import rig, run, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ROT, STATIC = "ed25519-n7-b1000-rot3", "ed25519-n7-b1000"
#: cell -> (traffic, its n4 twin, the suffix of its per-layer metrics)
CELLS = {
    "n7-b1000-rot3.saturated": ("saturated", "n4-b100-rot3.saturated", ".sat"),
}
END_TO_END = {
    "n7-b1000-rot3.saturated": {"committed_tx_per_s", "setup_s"},
}
LAYERS = ("ordering.requests_per_decision", "wave.launches_per_decision",
          "wave.padded_lane_share", "kernel.verify_roofline", "device.idle_share")


def _cell(name):
    manifest = run.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == name)
    return (manifest, run.load_config(manifest, cell["config"]),
            traffic.load_traffic(cell["traffic"]), cell)


@limit(20)
@pytest.mark.parametrize("name", sorted(CELLS))
def test_rotation_reaches_the_overrides_all_seven_replicas_get(name, tmp_path):
    from consensus_tpu.deploy import ClusterSpec

    _, config, mix, cell = _cell(name)
    assert cell["config"] == ROT and cell["chips"] == 1
    assert cell["traffic"] == CELLS[name][0]
    assert os.path.exists(os.path.join(
        REPO, "served_bench", "traffic", cell["traffic"] + ".json"))
    size, _ = rig.sized(config, mix, dry_run=False)
    overrides = size["overrides"]
    assert overrides["leader_rotation"] is True
    assert overrides["decisions_per_leader"] == 3
    assert (size["n"], size["f"], size["batch"]) == (7, 2, 1000)
    # as rig.measure hands them on: every replica's Configuration rotates
    spec = ClusterSpec.generate(size["n"], 1, str(tmp_path), clients=size["clients"],
                                config_overrides=overrides)
    assert len(spec.node_ids()) == 7
    for node_id in spec.node_ids():
        cfg = spec.make_configuration(node_id)
        assert cfg.leader_rotation and cfg.decisions_per_leader == 3
        assert (cfg.request_batch_max_count, cfg.request_pool_size,
                cfg.request_batch_max_interval, cfg.pipeline_depth) == (1000, 4000, 0.05, 1)
        assert (cfg.request_forward_timeout, cfg.request_complain_timeout) == (2.0, 20.0)
    # the static twin's launch shapes: 8,192 lanes and the half of it
    assert spec.sidecar_wave_lanes() == 8192
    # the rehearsal keeps the rotation too
    dry, _ = rig.sized(config, mix, dry_run=True)
    assert dry["overrides"]["leader_rotation"] is True
    assert dry["overrides"]["decisions_per_leader"] == 3


@limit(20)
@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_is_listed_under_its_metrics_and_no_others(name):
    manifest, _, _, _ = _cell(name)
    _, twin, suffix = CELLS[name]
    cells = [w["name"] for w in manifest["workloads"]]
    reported = {m["name"] for m in manifest["end_to_end"]
                if name in m.get("workloads", cells)}
    assert reported == END_TO_END[name]
    layers = {m["name"] for m in manifest["per_layer"]
              if name in m.get("workloads", cells)}
    want = {layer + suffix for layer in LAYERS}
    if suffix == ".paced":
        want.add("driver.late_ms_p95.paced")
    assert layers == want
    # appended, after its n4 twin, to the lists that had the twin; the ten
    # flusher metrics keep the lists an accepted test pins
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        listed = m.get("workloads")
        if listed is not None and name in listed:
            assert twin in listed and listed.index(twin) < listed.index(name)
        elif listed is not None:
            assert twin not in listed, m["name"]


@limit(20)
def test_the_configuration_has_no_other_cell():
    manifest = run.load_manifest()
    assert sorted(w["name"] for w in manifest["workloads"]
                  if w["config"] == ROT) == sorted(CELLS)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert "n7-b1000-rot3.paced" not in m.get("workloads", [])


@limit(20)
def test_the_twin_differs_from_the_static_file_in_the_rotation_alone():
    manifest = run.load_manifest()
    rot, static = run.load_config(manifest, ROT), run.load_config(manifest, STATIC)
    entry = next(c for c in manifest["configs"] if c["name"] == ROT)
    assert entry["reduced"] == ["hosts", "message_delay_ms"] == list(rot["reduced"])
    assert rot["reduced"] == {k: static["reduced"][k] for k in rot["reduced"]}
    differing = {k for k in static["configuration"]
                 if static["configuration"][k] != rot["configuration"][k]}
    assert differing == {"leader_rotation", "decisions_per_leader"}
    assert set(rot["configuration"]) == set(static["configuration"])
    assert set(rot) == set(static)
    for key in set(static) - {"name", "source", "mapping", "configuration", "reduced"}:
        assert rot[key] == static[key], key
    assert "BASELINE.json configs[2]" in rot["source"]
    assert "LeaderRotation true, DecisionsPerLeader 3" in rot["source"]
    assert rot["source"] == entry["source"] and len(rot["source"]) <= 200
    assert "8,192" in rot["mapping"] and "4,096" in rot["mapping"]
    assert rot["guarantees"] == static["guarantees"]
    assert rot["checkpoint_sig_sets"] == static["checkpoint_sig_sets"]
