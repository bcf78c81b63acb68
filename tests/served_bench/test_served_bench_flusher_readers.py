"""The five readers of the sidecar's flusher ledger (``health["flusher"]``,
consensus_tpu/obs/kernels.py) on a planted ``ctx``: the value each of the ten
metrics reads, and ``None`` where the program has no ledger (the parent of the
PR that brought it) or lacks a key."""

import json
import os

import pytest

from sb_limits import limit
from served_bench import readers

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1_000_000

#: The ledger at the window's first and last instant, 30 s apart: 100 flushes
#: of 240 submissions; the flusher waited for work a quarter of the window.
FIRST = {"wave.wait_work": 5_000 * MS, "wave.wait_window": 70 * MS,
         "wave.take": 3 * MS, "wave.deliver": 2 * MS,
         "verify.prepare": 900 * MS, "verify.layout": 100 * MS,
         "verify.dispatch": 40 * MS, "verify.await": 80_000 * MS,
         "verify.prepare_cpu": 700 * MS, "engine_ns": 81_100 * MS,
         "queue_wait_ns": 1_000 * MS, "submissions": 20, "flushes": 10,
         "fill_le_25": 10, "fill_le_50": 0, "fill_le_75": 0, "fill_le_100": 0}
GREW = {"wave.wait_work": 7_500 * MS, "wave.wait_window": 200 * MS,
        "wave.take": 30 * MS, "wave.deliver": 20 * MS,
        "verify.prepare": 2_000 * MS, "verify.layout": 500 * MS,
        "verify.dispatch": 300 * MS, "verify.await": 19_000 * MS,
        "verify.prepare_cpu": 1_500 * MS, "engine_ns": 21_900 * MS,
        "queue_wait_ns": 12_000 * MS, "submissions": 240, "flushes": 100,
        "fill_le_25": 30, "fill_le_50": 55, "fill_le_75": 10, "fill_le_100": 5}
EXPECTED = {
    "wave.starved_share": 25.0,            # 7.5 s of 30 s
    "wave.queue_wait_ms": 50.0,            # 12 s over 240 submissions
    "wave.underhalf_launch_share": 85.0,   # 30 + 55 of 100 flushes
    "hostprep.ms_per_launch": 25.0,        # 2.0 + 0.5 s over 100 flushes
    "hostprep.off_cpu_share": 25.0,        # 1.5 s on a core of 2.0 s
}
METRICS = [f"{name}.{suffix}" for name in EXPECTED for suffix in ("sat", "paced")]


def _ctx(first=FIRST, grew=GREW, without=()):
    last = {k: first[k] + grew[k] for k in first}
    first = {k: v for k, v in first.items() if k not in without}
    return {"first": {"launches_after_ready": 10, "flusher": first},
            "last": {"launches_after_ready": 110, "flusher": last},
            "counted_s": 30.0, "decisions": 50, "requests": 42_000,
            "trace": None, "late_s": [], "device_kind": "TPU v5 lite",
            "lanes": 8192}


def _reader(metric):
    with open(os.path.join(REPO, "served_bench", "metrics", metric + ".json"),
              encoding="utf-8") as fh:
        return readers.load(json.load(fh)["reader"])


@limit(20)
@pytest.mark.parametrize("metric", METRICS)
def test_each_metric_reads_its_difference_of_the_ledger(metric):
    value = _reader(metric)(_ctx())
    assert value == pytest.approx(EXPECTED[metric.rsplit(".", 1)[0]], rel=1e-12)


@limit(20)
@pytest.mark.parametrize("metric", METRICS)
def test_each_metric_is_left_out_where_there_is_nothing_to_read(metric):
    read = _reader(metric)
    # a program from before the ledger: health has no "flusher"
    bare = _ctx()
    del bare["first"]["flusher"], bare["last"]["flusher"]
    assert read(bare) is None
    # no health reply at the window's ends at all
    assert read(dict(_ctx(), first={}, last={})) is None
    # a ledger that lacks the keys this metric reads
    assert read(_ctx(without=set(FIRST) - {"fill_le_100"})) is None
    # nothing flushed, submitted or prepared inside the window, or a window
    # of no length: no ratio, and no division by zero
    still = _ctx(grew=dict.fromkeys(GREW, 0))
    still["counted_s"] = 0.0
    assert read(still) is None


@limit(20)
def test_the_manifest_gives_each_of_the_ten_its_layer_cells_and_source():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert list(entries)[-10:] == sorted(METRICS, key=lambda m: (
        m.endswith(".paced"), list(EXPECTED).index(m.rsplit(".", 1)[0])))
    for name in METRICS:
        m, sat = entries[name], name.endswith(".sat")
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert m["unit"] == ("ms" if "ms" in name else "%")
        assert m["layer"] == ("host prep" if name.startswith("hostprep.")
                              else "sidecar and wave former")
        assert m["moves"] == ("committed_tx_per_s" if sat
                              else "commit_latency_p95_ms")
        assert m["workloads"] == (["n7-b1000.saturated", "n4-b100.saturated"]
                                  if sat else ["n7-b1000.paced"])


@limit(20)
def test_the_closure_the_phases_make_over_a_window():
    """Not a metric, but what PERF.md checks on every traced run: the wave
    phases + the engine call account for the window, and the engine call for
    its four phases."""
    from served_bench.readers import flusher

    ctx = _ctx()
    wave = flusher.delta(ctx, "wave.wait_work", "wave.wait_window",
                         "wave.take", "wave.deliver")
    engine = flusher.delta(ctx, "engine_ns")
    inside = flusher.delta(ctx, "verify.prepare", "verify.layout",
                           "verify.dispatch", "verify.await")
    assert (wave + engine) / 1e9 == pytest.approx(29.65)
    assert engine - inside == 100 * MS
    assert flusher.delta(ctx, "no.such.phase") is None


@limit(20)
def test_the_reducer_names_a_gap_by_the_phase_that_covers_most_of_it():
    """Two launches of 80 ms, 170 ms apart.  On the flusher thread's line:
    ``verify.await`` around jax's own ``np.asarray`` event (which alone named
    every gap before the phases existed), then deliver, the wait for work,
    the window, take, prepare, layout, dispatch.  The 90 ms gap goes to the
    wait for work, its largest part, and not to ``np.asarray``."""
    from served_bench import tracing

    def line(t):  # a launch whose device work starts at t (ms)
        return [("verify.dispatch", t - 2, 1.5), ("verify.await", t - 0.5, 81.0),
                ("np.asarray_jax.Array_", t - 0.4, 80.8),
                ("wave.deliver", t + 80.6, 0.4), ("wave.wait_work", t + 81, 50.0),
                ("wave.wait_window", t + 131, 2.0), ("wave.take", t + 133, 1.0),
                ("verify.prepare", t + 134, 28.0), ("verify.layout", t + 162, 6.0)]

    host = [(name, int(s * MS), int(d * MS))
            for t in (10, 180, 350) for name, s, d in line(t)]
    ops = [("while.105", t * MS, 80 * MS) for t in (10, 180, 350)]
    modules = [("jit_verify_impl(1)", t * MS, 80 * MS) for t in (10, 180, 350)]
    s = tracing.reduce_events({"/device:TPU:0": ops}, host, (0, 440 * MS),
                              "verify", {"/device:TPU:0": modules})
    assert s["cycles"] == 2 and abs(s["busy_s"] - 0.16) < 1e-12
    assert s["idle_gaps"] == [["wave.wait_work", pytest.approx(0.18)]]
