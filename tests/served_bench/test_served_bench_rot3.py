"""The rotating twin of the n4 deployment and its paced mix, as the rig reads
them: the source's rotation reaches the overrides the replicas are started
with, and nothing else of the static twin's configuration moved."""

import json
import os

import pytest

from sb_limits import limit
from served_bench import rig, run, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = {"n4-b100-rot3.saturated": "saturated", "n4-b100-rot3.paced": "paced-1600"}


def _cell(name):
    manifest = run.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == name)
    return (run.load_config(manifest, cell["config"]),
            traffic.load_traffic(cell["traffic"]), cell)


@limit(20)
@pytest.mark.parametrize("name", sorted(CELLS))
def test_rotation_reaches_the_overrides_the_replicas_get(name, tmp_path):
    from consensus_tpu.deploy import ClusterSpec

    config, mix, cell = _cell(name)
    assert cell["config"] == "ed25519-n4-b100-rot3" and cell["chips"] == 1
    assert cell["traffic"] == CELLS[name]
    size, _ = rig.sized(config, mix, dry_run=False)
    overrides = size["overrides"]
    assert overrides["leader_rotation"] is True
    assert overrides["decisions_per_leader"] == 3
    assert (size["n"], size["f"], size["batch"]) == (4, 1, 100)
    # as rig.measure hands them on: every replica's Configuration rotates
    spec = ClusterSpec.generate(size["n"], 1, str(tmp_path), clients=size["clients"],
                                config_overrides=overrides)
    for node_id in spec.node_ids():
        cfg = spec.make_configuration(node_id)
        assert cfg.leader_rotation and cfg.decisions_per_leader == 3
        assert (cfg.request_batch_max_count, cfg.request_pool_size,
                cfg.request_batch_max_interval, cfg.pipeline_depth) == (100, 400, 0.05, 1)
    # the rehearsal keeps the rotation too
    dry, _ = rig.sized(config, mix, dry_run=True)
    assert dry["overrides"]["leader_rotation"] is True
    assert dry["overrides"]["decisions_per_leader"] == 3


@limit(20)
def test_the_twin_differs_from_the_static_file_in_the_rotation_alone():
    manifest = run.load_manifest()
    rot = run.load_config(manifest, "ed25519-n4-b100-rot3")
    static = run.load_config(manifest, "ed25519-n4-b100")
    entry = next(c for c in manifest["configs"] if c["name"] == rot["name"])
    assert entry["reduced"] == ["hosts", "message_delay_ms"] == list(rot["reduced"])
    assert rot["reduced"] == {k: static["reduced"][k] for k in rot["reduced"]}
    differing = {k for k in static["configuration"]
                 if static["configuration"][k] != rot["configuration"][k]}
    assert differing == {"leader_rotation", "decisions_per_leader"}
    assert set(rot["configuration"]) == set(static["configuration"])
    for key in set(static) - {"name", "source", "mapping", "configuration", "reduced"}:
        assert rot[key] == static[key], key
    assert "LeaderRotation true, DecisionsPerLeader 3" in rot["source"]
    assert any("exactly-once" in g for g in rot["guarantees"])


@limit(20)
def test_paced_1600_is_an_open_mix_at_1600_requests_a_second():
    mix = traffic.load_traffic("paced-1600")
    assert mix["mode"] == "open" and mix["rate_per_s"] == 1600
    assert (mix["tick_s"], mix["warm_s"], mix["drain_timeout_s"]) == (0.01, 3, 60)
    with open(os.path.join(REPO, "served_bench", "traffic", "paced-2100.json"),
              encoding="utf-8") as fh:
        accepted = json.load(fh)
    assert set(mix) == set(accepted)
    config, _, _ = _cell("n4-b100-rot3.paced")
    size, sized_mix = rig.sized(config, mix, dry_run=False)
    assert sized_mix["rate_per_s"] == 1600
    # 3 s of warm-up and the 30 s window, all pre-signed before the window
    assert rig._presign_count(sized_mix, size, 30.0) == 1600 * 33
