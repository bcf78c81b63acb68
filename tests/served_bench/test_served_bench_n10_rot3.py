"""The deployment BASELINE.json names its metric at, as the rig reads it: ten
replicas (f=3), 1,000 requests a proposal, 256-byte bodies and the shipped
rotation reach the overrides every replica is started with; the full wave is
16,384 lanes, so the sidecar's ladder is 4,096 / 8,192 / 16,384; the
configuration is the n7 rotating twin's to the letter; the cell is listed
under the metrics it reports and no others; and the reader of the share of
signatures on the ladder's upper rungs reads ``signatures_by_lanes``."""

import json
import os

import pytest

from sb_limits import limit
from served_bench import readers, rig, run, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
N10, N7 = "ed25519-n10-b1000-rot3", "ed25519-n7-b1000-rot3"
CELL = "n10-b1000-rot3.saturated"
LAYERS = ("ordering.requests_per_decision", "wave.launches_per_decision",
          "wave.padded_lane_share", "kernel.verify_roofline", "device.idle_share")


def _cell():
    manifest = run.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    return (manifest, run.load_config(manifest, cell["config"]),
            traffic.load_traffic(cell["traffic"]), cell)


@limit(20)
def test_rotation_reaches_the_overrides_all_ten_replicas_get(tmp_path):
    from consensus_tpu.deploy import ClusterSpec
    from consensus_tpu.deploy.sidecar_main import launch_widths

    _, config, mix, cell = _cell()
    assert cell["config"] == N10 and cell["chips"] == 1
    assert cell["traffic"] == "saturated" and mix["mode"] == "closed"
    size, _ = rig.sized(config, mix, dry_run=False)
    overrides = size["overrides"]
    assert (size["n"], size["f"], size["batch"]) == (10, 3, 1000)
    assert size["body_bytes"] == 256
    spec = ClusterSpec.generate(size["n"], 1, str(tmp_path), clients=size["clients"],
                                config_overrides=overrides)
    assert spec.node_ids() == list(range(1, 11))
    for node_id in spec.node_ids():
        cfg = spec.make_configuration(node_id)
        assert cfg.leader_rotation and cfg.decisions_per_leader == 3
        assert (cfg.request_batch_max_count, cfg.request_pool_size,
                cfg.request_batch_max_interval, cfg.pipeline_depth) == (1000, 4000, 0.05, 1)
        assert (cfg.request_forward_timeout, cfg.request_complain_timeout) == (2.0, 20.0)
    # 10 x (1,000 + 10) signatures -> 16,384 lanes, and its ladder
    assert spec.sidecar_wave_lanes() == 16384
    assert launch_widths(spec.sidecar_wave_lanes()) == (4096, 8192, 16384)
    # the rehearsal keeps the rotation too
    dry, _ = rig.sized(config, mix, dry_run=True)
    assert dry["overrides"]["leader_rotation"] is True
    assert dry["overrides"]["decisions_per_leader"] == 3


@limit(20)
def test_a_window_cannot_use_up_what_is_presigned():
    """4 warm batches + 4,000 a second for 31 s: 128,000 signed requests of
    332 bytes, far over what the closed loop commits at n7 (2,850 a second,
    ledger PR 35); a run that uses them all up is void (``presign_exhausted``)."""
    from served_bench.traffic import RequestFactory

    _, config, mix, _ = _cell()
    size, _ = rig.sized(config, mix, dry_run=False)
    assert rig._presign_count(mix, size, 30.0) == 128_000
    raw = RequestFactory(7, size["clients"], size["body_bytes"]).make_many(2)
    assert [len(r) for r in raw] == [332, 332]


@limit(20)
def test_cell_is_listed_under_its_metrics_and_no_others():
    manifest, _, _, _ = _cell()
    cells = [w["name"] for w in manifest["workloads"]]
    reported = {m["name"] for m in manifest["end_to_end"]
                if CELL in m.get("workloads", cells)}
    assert reported == {"committed_tx_per_s", "setup_s"}
    layers = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", cells)}
    # the upper-rung share is not listed yet: only a benchmark PR can add a
    # per-layer metric (PERF.md section 7 (4)); it may then list this cell
    assert layers - {"wave.upper_rungs_signature_share.sat"} == {
        layer + ".sat" for layer in LAYERS}
    # appended last, after the n7 rotating cell, to each list that has it
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        listed = m.get("workloads")
        if listed is not None and CELL in listed:
            assert listed[-1] == CELL and "n7-b1000-rot3.saturated" in listed
    assert sorted(w["name"] for w in manifest["workloads"]
                  if w["config"] == N10) == [CELL]


@limit(20)
def test_the_configuration_is_the_n7_rotating_twins_but_for_n_f_and_the_body():
    manifest = run.load_manifest()
    n10, n7 = run.load_config(manifest, N10), run.load_config(manifest, N7)
    entry = next(c for c in manifest["configs"] if c["name"] == N10)
    assert entry["reduced"] == ["hosts", "message_delay_ms"] == list(n10["reduced"])
    assert n10["configuration"] == n7["configuration"]
    assert (n10["n"], n10["f"], n10["request_body_bytes"]) == (10, 3, 256)
    same = set(n7) - {"name", "source", "mapping", "reduced", "assumed", "n", "f",
                      "request_body_bytes", "checkpoint_sig_sets"}
    assert set(n10) == set(n7)
    for key in same:
        assert n10[key] == n7[key], key
    assert set(n10["assumed"]) == {"request_pool_size", "clients",
                                   "presign_tx_per_s", "offered_rate"}
    assert n10["source"] == entry["source"] and len(n10["source"]) <= 200
    assert "n=10,f=3" in n10["source"] and "256B" in n10["source"]
    assert "LeaderRotation true, DecisionsPerLeader 3" in n10["source"]
    for width in ("16,384", "8,192", "4,096"):
        assert width in n10["mapping"]
    assert "2f+1 = 7" in n10["checkpoint_sig_sets"]


def _ctx(first, last, signatures=(100, 9100), lanes=16384):
    return {"first": {"device_signatures": signatures[0], "signatures_by_lanes": first},
            "last": {"device_signatures": signatures[1], "signatures_by_lanes": last},
            "lanes": lanes}


@limit(20)
@pytest.mark.parametrize("first, last, lanes, want", [
    # 9,000 in the window: 2,000 at 4,096 lanes, 6,000 at 8,192, 1,000 at 16,384
    ({"4096": 100}, {"4096": 2100, "8192": 6000, "16384": 1000}, 16384, 700 / 9),
    # a key that first appears inside the window counts from zero
    ({"4096": 100, "8192": 0}, {"4096": 2100, "8192": 6000, "16384": 1000}, 16384,
     700 / 9),
    # the n7 ladder (2,048 / 4,096 / 8,192): the upper rungs are 4,096 and up
    ({}, {"2048": 3000, "4096": 5000, "8192": 1000}, 8192, 600 / 9),
    # the upper rungs carried nothing
    ({"4096": 100}, {"4096": 9100, "8192": 0, "16384": 0}, 16384, 0.0),
])
def test_the_upper_rungs_reader_reads_the_window(first, last, lanes, want):
    read = readers.load("upper_rungs_signature_share")
    assert read(_ctx(first, last, lanes=lanes)) == pytest.approx(want, rel=1e-12)


@limit(20)
def test_the_upper_rungs_reader_is_silent_where_there_is_nothing_to_read():
    read = readers.load("upper_rungs_signature_share")
    full = {"4096": 2000, "8192": 7000}
    # a program from before the counter (the parent of PR 37)
    bare = _ctx({}, full)
    del bare["first"]["signatures_by_lanes"], bare["last"]["signatures_by_lanes"]
    assert read(bare) is None
    assert read(dict(_ctx({}, full), first={}, last={})) is None
    # nothing launched in the window; no width known
    assert read(_ctx({}, {}, signatures=(5, 5))) is None
    assert read(_ctx({}, full, lanes=None)) is None
    # the metric's file names the reader
    with open(os.path.join(REPO, "served_bench", "metrics",
                           "wave.upper_rungs_signature_share.sat.json"),
              encoding="utf-8") as fh:
        assert json.load(fh) == {"reader": "upper_rungs_signature_share"}
