"""The trace reducer on a hand-built event list, and the readers over it."""

from sb_limits import limit
from served_bench import peaks, tracing
from served_bench.readers import idle_share, padded_lane_share, verify_roofline

MS = 1_000_000


def _trace(first=50, span=(0, 750)):
    # Launches of 80 ms, each made of two operations, in pairs: 100 ms from
    # the first of a pair to the second, 200 ms on to the next pair.  The
    # host waits in np.asarray between pairs and prepares inside them.
    starts = [first, first + 100, first + 300, first + 400, first + 600]
    starts = [t for t in starts if t + 80 <= span[1]]
    ops, modules, host = [], [], []
    for t in starts:
        ops += [("while.1", t * MS, 60 * MS), ("fusion.2", (t + 60) * MS, 20 * MS)]
        modules.append(("jit_verify_impl(1)", t * MS, 80 * MS))
    for t in starts[1::2]:
        host.append(("np.asarray(jax.Array)", (t + 80) * MS, 115 * MS))
    for t in starts[0::2]:
        host.append(("prep", (t + 80) * MS, 20 * MS))
    return tracing.reduce_events(
        {"/device:TPU:0": ops}, host, (span[0] * MS, span[1] * MS), "verify",
        {"/device:TPU:0": modules})


@limit(20)
def test_the_window_is_cut_to_whole_launch_cycles():
    s = _trace()
    # five launches traced: four whole cycles, from the first start (50 ms)
    # to the last (650 ms); the last launch itself is outside
    assert s["whole_cycles"] and s["cycles"] == 4 and s["kernel_launches"] == 4
    assert abs(s["window_s"] - 0.6) < 1e-12 and abs(s["traced_span_s"] - 0.75) < 1e-12
    assert abs(s["busy_s"] - 0.32) < 1e-12 and abs(s["kernel_s"] - 0.32) < 1e-12
    assert s["busy_per_launch_s"] == [0.08, 0.08, 0.08]
    assert abs(s["longest_gap_s"] - 0.12) < 1e-12
    assert s["device_ops"][0] == ["while.1", 0.24]
    gaps = dict((k, v) for k, v in s["idle_gaps"])
    assert abs(gaps["np.asarray(jax.Array)"] - 0.24) < 1e-12
    assert abs(gaps["prep"] - 0.04) < 1e-12
    # Where the trace began among the launches does not move busy per cycle.
    for first in (0, 30, 95):
        t = _trace(first=first)
        assert abs(t["busy_s"] / t["cycles"] - 0.08) < 1e-12


@limit(20)
def test_a_trace_with_one_launch_falls_back_to_its_span():
    s = _trace(span=(0, 140))
    assert not s["whole_cycles"] and s["cycles"] == 0 and s["kernel_launches"] == 0
    assert abs(s["window_s"] - 0.14) < 1e-12 and abs(s["busy_s"] - 0.08) < 1e-12


@limit(20)
def test_overlapping_operations_are_not_counted_twice():
    assert tracing.union_ns([(0, 10), (5, 12), (20, 30), (25, 26)]) == 22
    two = tracing.reduce_events(
        {"a": [("x", 0, 10)], "b": [("x", 0, 30)]}, [], (0, 40), "")
    assert two["devices"] == 2 and abs(two["busy_s"] - 20e-9) < 1e-18
    assert not two["whole_cycles"]


@limit(20)
def test_readers_over_the_summary():
    ctx = {"trace": _trace(), "device_kind": "TPU v5 lite", "counted_s": 2.0,
           "first": {"device_signatures": 0, "device_lanes": 0,
                     "launches_after_ready": 0},
           "last": {"device_signatures": 30_000, "device_lanes": 81_920,
                    "launches_after_ready": 10}}
    # 80 ms busy per launch cycle x 10 launches in the 2 s counted = 40% busy
    assert abs(idle_share.read(ctx) - 60.0) < 1e-9
    # without the counters: the trace's own share over its whole cycles
    alone = dict(ctx, first={}, last={})
    assert abs(idle_share.read(alone) - 100 * (1 - 0.32 / 0.6)) < 1e-9
    assert abs(padded_lane_share.read(ctx) - 100 * (1 - 30_000 / 81_920)) < 1e-9
    share = verify_roofline.read(ctx)
    least, bound = peaks.least_seconds(4 * 3_000, "TPU v5 lite")
    assert bound == "flops" and abs(share - 100 * least / 0.32) < 1e-12
    assert 0 < share < 100


@limit(20)
def test_a_reader_with_nothing_to_read_returns_nothing():
    ctx = {"trace": None, "device_kind": "cpu", "first": {}, "last": {}}
    assert idle_share.read(ctx) is None
    assert verify_roofline.read(ctx) is None
    assert padded_lane_share.read(ctx) is None


@limit(20)
def test_an_unknown_device_is_an_error_not_a_default():
    try:
        peaks.peaks_for("TPU v9")
    except KeyError as exc:
        assert "TPU v9" in str(exc)
    else:
        raise AssertionError("an unknown device kind got peaks")
