"""Commit instants as a client sees them, and the arithmetic over them.

A poller thread asks every replica's control socket for ``health`` once per
:data:`POLL_PERIOD_S` and keeps, per sweep, the instant and each replica's
committed-request and decision counts.  A request counts as committed at the
first instant at which a reply quorum (f + 1 replicas) reports it.  The rest
is pure functions over that log, tested on synthetic logs.
"""

from __future__ import annotations

import threading
import time

#: One constant for all cells.  Each ``health`` call opens a connection and
#: runs on the replica's own interpreter, so this is kept coarse: 20 ms
#: against commit latencies of hundreds of milliseconds.
POLL_PERIOD_S = 0.02


def quorum_count(counts, quorum: int) -> int:
    """The count that at least ``quorum`` of the replicas have reached."""
    ranked = sorted(counts, reverse=True)
    return ranked[quorum - 1] if len(ranked) >= quorum else 0


def quorum_log(samples, quorum: int) -> list:
    """``[(t, quorum-committed count)]`` at the instants the count rose.
    ``samples`` is ``[(t, [count per replica])]`` in time order."""
    log, last = [], 0
    for t, counts in samples:
        now = quorum_count(counts, quorum)
        if now > last:
            log.append((t, now))
            last = now
    return log


def count_at(log, t: float) -> int:
    """Quorum-committed count at instant ``t`` (the last rise at or before)."""
    out = 0
    for when, count in log:
        if when > t:
            break
        out = count
    return out


def commit_times(log, first_rank: int, n: int) -> list:
    """Commit instant of the requests of send rank ``first_rank`` ..
    ``first_rank + n - 1`` (0-based: rank k is committed once the count is
    above k); ``None`` where the log never got there."""
    out, j = [], 0
    for k in range(first_rank, first_rank + n):
        while j < len(log) and log[j][1] <= k:
            j += 1
        out.append(log[j][0] if j < len(log) else None)
    return out


def median_commit_gap(samples, quorum: int) -> float:
    """Median time between two rises of the quorum count: a decision time."""
    rises = [t for t, _ in quorum_log(samples, quorum)]
    gaps = sorted(b - a for a, b in zip(rises, rises[1:]))
    return gaps[len(gaps) // 2] if gaps else 0.0


def stalls(samples, quorum: int) -> dict:
    """What a later reader needs to see a stall: the longest time between two
    rises of the quorum count, the longest time between two sweeps, and how
    far (in requests) each replica ever fell behind the most advanced one."""
    longest_gap, gap_at, last_rise, last_count = 0.0, None, None, 0
    longest_sweep, behind = 0.0, []
    prev_t = None
    for t, counts in samples:
        now = quorum_count(counts, quorum)
        if now > last_count:
            if last_rise is not None and t - last_rise > longest_gap:
                longest_gap, gap_at = t - last_rise, last_rise
            last_rise, last_count = t, now
        if prev_t is not None:
            longest_sweep = max(longest_sweep, t - prev_t)
        prev_t = t
        top = max(counts)
        behind = [max(b, top - c) for b, c in zip(behind or [0] * len(counts), counts)]
    return {"longest_commit_gap_s": longest_gap, "gap_began_at": gap_at,
            "longest_sweep_s": longest_sweep, "most_behind_requests": behind}


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list (q in 0..100)."""
    ranked = sorted(values)
    k = max(0, min(len(ranked) - 1, int(-(-q * len(ranked) // 100)) - 1))
    return ranked[k]


def due_latencies(log, first_rank: int, due_times, end_of_drain: float):
    """Seconds from each request's due instant to its quorum commit; a
    request never committed counts as failed and as waiting to the end of
    the drain.  Returns ``(latencies, failed)``."""
    commits = commit_times(log, first_rank, len(due_times))
    latencies, failed = [], 0
    for due, done in zip(due_times, commits):
        if done is None:
            failed += 1
            done = end_of_drain
        latencies.append(max(0.0, done - due))
    return latencies, failed


class Poller:
    """Sweeps the replicas' ``health`` until stopped.  ``low`` is the
    smallest committed count (flow control), ``samples`` the log."""

    def __init__(self, controls: dict, period: float = POLL_PERIOD_S) -> None:
        self._controls = controls
        self._period = period
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self.samples: list = []     # (t, [requests per replica])
        self.decisions: list = []   # (t, [ledger height per replica])
        self.leaders: list = []     # distinct leaders in the order first seen
        self.low = 0
        self.calls_failed = 0
        self._thread = threading.Thread(target=self._run, name="bench-poller",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10.0)

    def _run(self) -> None:
        last = {node_id: (0, 0) for node_id in self._controls}
        while not self._stop.is_set():
            t_next = time.monotonic() + self._period
            leader = None
            for node_id, control in self._controls.items():
                h = control.try_call("health")
                if h is None:
                    self.calls_failed += 1
                    continue
                last[node_id] = (int(h.get("requests", 0)), int(h.get("ledger", 0)))
                if leader is None:
                    leader = h.get("leader")
            t = time.monotonic()
            requests = [v[0] for v in last.values()]
            with self._lock:
                self.samples.append((t, requests))
                self.decisions.append((t, [v[1] for v in last.values()]))
                self.low = min(requests)
                if leader is not None and (not self.leaders or self.leaders[-1] != leader):
                    self.leaders.append(leader)
            self._stop.wait(max(0.0, t_next - time.monotonic()))

    def snapshot(self) -> tuple:
        with self._lock:
            return list(self.samples), list(self.decisions)
