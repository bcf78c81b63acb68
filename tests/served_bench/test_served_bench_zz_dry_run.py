"""One CPU rehearsal of a cell at the DRY size (n=4, batch 64, a 512-lane
sidecar kernel on the CPU backend), shared by the tests below, then the
replay control on a second rig.  Nothing here is a chip result: the lines say
``platform: cpu`` and carry no device metric.

Starts processes and compiles (or loads) one kernel on the CPU, so it is named
to sort late, like the other rig rehearsals.
"""

import copy
import json
import os
import subprocess
import sys
import time

import pytest

from sb_limits import limit
from served_bench import judge, rig, run, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "n4-b100.saturated"


def _measure(tmp, **kw):
    manifest = run.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    config = run.load_config(manifest, cell["config"])
    mix = traffic.load_traffic(cell["traffic"])
    readings = rig.measure(config, mix, seed=2_147_483_777, seconds=15.0,
                           dry_run=True, out_dir=str(tmp),
                           t_start=time.monotonic(), **kw)
    return manifest, readings


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    return _measure(tmp_path_factory.mktemp("served_bench") / "run", trace=True)


@limit(900)
def test_the_last_line_has_exactly_the_contracts_keys(rehearsal):
    manifest, readings = rehearsal
    line, values = run.result_line(manifest, CELL, readings, False, [])
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"committed_tx_per_s", "setup_s"}
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert line["metrics"]["committed_tx_per_s"]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    assert all(set(c) == {"value", "limit"} for c in line["compared"].values())
    json.dumps(line)


@limit(900)
def test_a_traced_rehearsal_prints_counts_and_no_device_metric(rehearsal):
    manifest, readings = rehearsal
    line, _ = run.result_line(manifest, CELL, readings, True, [])
    assert "ordering.requests_per_decision.sat" in line["metrics"]
    assert "wave.padded_lane_share.sat" in line["metrics"]
    # The CPU backend writes no /device:TPU plane: nothing to read, so the
    # trace metrics, busy_s, window_s and the breakdown are left out.
    assert not any(name.startswith(("device.", "kernel."))
                   for name in line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert readings["trace_calls"]["started"]["ok"]
    assert readings["trace_reduced"]["summary"] is None


@limit(900)
def test_the_rest_of_a_run_with_the_timed_path_broken_underneath(rehearsal):
    manifest, readings = rehearsal
    assert judge.correct(judge.compare(readings))

    def broken(change):
        r = copy.copy(readings)
        change(r)
        return run.result_line(manifest, CELL, r, False, [])[0]

    def half_left_out(r):  # a replica delivered half of what was committed
        r["audits"] = copy.deepcopy(r["audits"])
        a = r["audits"][2]
        a["requests"] = a["distinct"] = a["requests"] // 2

    def answer_altered(r):  # one replica's ledger holds other bytes
        r["audits"] = copy.deepcopy(r["audits"])
        r["audits"][3]["digest"] = "0" * 64

    def verdict_altered(r):  # the device path waves one forgery through
        r["wave"] = dict(r["wave"], got=list(r["wave"]["got"]))
        r["wave"]["got"][next(iter(r["wave"]["planted"]))] = True

    def verdict_altered_in_the_window(r):  # ... among the replicas' own waves
        r["window_waves"] = copy.deepcopy(r["window_waves"])
        w = r["window_waves"][-1]
        w["got"][next(iter(w["planted"]))] = True

    # the window's verdict waves were all due, sent and answered: 15 s at one
    # per 5 s of the rehearsal
    waves = readings["window_waves"]
    assert len(waves) == 3 and all(w["got"] is not None for w in waves)
    assert all(len(w["planted"]) == 8 and len(w["got"]) == rig.VERDICT_LANES
               for w in waves)

    for change, number in ((half_left_out, "missing"),
                           (answer_altered, "ledgers_differ"),
                           (verdict_altered, "verdict_mismatches"),
                           (verdict_altered_in_the_window, "verdict_mismatches")):
        line = broken(change)
        assert line["correct"] is False, number
        assert line["compared"][number]["value"] > 0
    assert run.result_line(manifest, CELL, readings, False, ["cpu"])[0][
        "correct"] is False  # the orchestrator must hold no JAX backend


@limit(900)
def test_the_replay_control_breaks_exactly_once_delivery(tmp_path):
    _, readings = _measure(tmp_path / "run", trace=False, control="replay")
    compared = judge.compare(readings)
    assert compared["duplicated"]["value"] > 0
    assert not judge.correct(compared)


@limit(120)
def test_no_result_without_a_chip_or_without_the_program(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    argv = ["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]
    pinned = subprocess.run(
        [sys.executable, os.path.join(REPO, "served_bench", "run.py")] + argv,
        env=env, capture_output=True, text=True, timeout=100)
    assert pinned.returncode != 0 and pinned.stdout == ""
    # A directory that holds only BENCHMARK.json and the benchmark's paths.
    import shutil

    bare = tmp_path / "bare"
    shutil.copytree(os.path.join(REPO, "served_bench"), bare / "served_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), bare / "BENCHMARK.json")
    env.pop("JAX_PLATFORMS")
    env["PYTHONPATH"] = ""
    alone = subprocess.run(
        [sys.executable, str(bare / "served_bench" / "run.py")] + argv,
        env=env, cwd=str(bare), capture_output=True, text=True, timeout=100)
    assert alone.returncode != 0 and alone.stdout == ""
