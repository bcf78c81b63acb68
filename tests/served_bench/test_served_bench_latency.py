"""Quorum commit instants and due-time latency, on synthetic poll logs."""

from sb_limits import limit
from served_bench import poll, rig


@limit(20)
def test_quorum_count_is_what_f_plus_one_replicas_report():
    assert poll.quorum_count([10, 7, 7, 3], 2) == 7
    assert poll.quorum_count([10, 7, 7, 3], 3) == 7
    assert poll.quorum_count([10, 7, 7, 3], 4) == 3
    assert poll.quorum_count([5], 2) == 0


@limit(20)
def test_quorum_log_keeps_the_instants_the_count_rose():
    samples = [(0.0, [0, 0, 0, 0]), (0.1, [4, 0, 0, 0]), (0.2, [4, 4, 0, 0]),
               (0.3, [4, 4, 4, 4]), (0.4, [8, 8, 4, 4])]
    log = poll.quorum_log(samples, 2)
    assert log == [(0.2, 4), (0.4, 8)]
    assert poll.count_at(log, 0.19) == 0
    assert poll.count_at(log, 0.2) == 4
    assert poll.count_at(log, 9.0) == 8


@limit(20)
def test_due_time_latency_counts_the_wait_a_stall_imposes():
    # 10 requests/s; batches of 4 commit at 0.5, 1.0; then a stall: the next
    # commit comes at 3.0 and carries everything due meanwhile.
    log = [(0.5, 4), (1.0, 8), (3.0, 20)]
    due = [i / 10.0 for i in range(20)]
    latencies, failed = poll.due_latencies(log, 0, due, end_of_drain=5.0)
    assert failed == 0
    assert latencies[0] == 0.5 and latencies[3] == 0.5 - 0.3
    assert latencies[4] == 1.0 - 0.4
    assert latencies[8] == 3.0 - 0.8           # waited out the stall
    assert abs(latencies[19] - (3.0 - 1.9)) < 1e-9
    assert poll.percentile(latencies, 50) < poll.percentile(latencies, 95)
    assert abs(poll.percentile(latencies, 95) - (3.0 - 0.9)) < 1e-9


@limit(20)
def test_a_request_never_committed_fails_and_waits_to_the_end_of_the_drain():
    log = [(0.5, 4)]
    latencies, failed = poll.due_latencies(
        log, 2, [0.2, 0.3, 0.4, 0.5], end_of_drain=9.0)
    assert failed == 2
    assert latencies == [0.5 - 0.2, 0.5 - 0.3, 9.0 - 0.4, 9.0 - 0.5]


@limit(20)
def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert poll.percentile(values, 50) == 50
    assert poll.percentile(values, 95) == 95
    assert poll.percentile([7.0], 95) == 7.0


@limit(20)
def test_stalls_names_the_longest_commit_gap_and_the_replica_left_behind():
    samples = [(0.0, [0, 0, 0, 0]), (0.5, [4, 4, 4, 0]), (1.0, [8, 8, 4, 0]),
               (4.0, [8, 8, 8, 0]), (4.2, [12, 12, 12, 0])]
    s = poll.stalls(samples, 2)
    assert abs(s["longest_commit_gap_s"] - 3.2) < 1e-9 and s["gap_began_at"] == 1.0
    assert abs(s["longest_sweep_s"] - 3.0) < 1e-9
    assert s["most_behind_requests"] == [0, 0, 4, 12]


@limit(20)
def test_a_decision_time_is_the_median_gap_between_commits():
    samples = [(0.0, [0, 0, 0, 0]), (0.3, [4, 4, 0, 0]), (0.6, [8, 8, 4, 4]),
               (0.9, [12, 12, 8, 8]), (5.9, [16, 16, 16, 16])]  # then a stall
    assert abs(poll.median_commit_gap(samples, 2) - 0.3) < 1e-9
    assert poll.median_commit_gap(samples[:2], 2) == 0.0


@limit(20)
def test_the_windows_verdict_waves_are_due_mid_period():
    assert rig.window_wave_instants(30, 1.0) == [k + 0.5 for k in range(30)]
    assert rig.window_wave_instants(15, 5.0) == [2.5, 7.5, 12.5]
    assert rig.window_wave_instants(1.2, 1.0) == [0.5]
    assert rig.window_wave_instants(0.3, 1.0) == []


@limit(20)
def test_the_witness_closes_one_listener_for_four_decision_times(monkeypatch):
    calls, naps = [], []

    class Control:
        def try_call(self, op):
            calls.append(op)
            return {"ok": True}

    class Poller:  # a decision every 0.2 s
        samples = [(0.2 * k, [4 * k] * 4) for k in range(10)]

    monkeypatch.setattr(rig.time, "sleep", naps.append)
    window = {"t0": rig.time.monotonic() - 100.0}
    rig._listener_pause(Control(), Poller(), window, 2, seconds=30.0)
    assert calls == ["net_pause", "net_resume"]
    assert abs(window["witness"]["pause_s"] - 0.8) < 1e-9 and naps[-1] == window[
        "witness"]["pause_s"]
    assert window["witness"]["paused"] == window["witness"]["resumed"] == {"ok": True}
