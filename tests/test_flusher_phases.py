"""The flusher thread's phase ledger (obs/kernels.py ``phase`` / ``FLUSHER``)
and the coalescer that fills it (models/engine.py
``ThreadCoalescingVerifier._loop``): a fake engine with a planted sleep in
every phase, so each phase's nanoseconds must land under its own name and
the phases together must account for the thread's whole life.

No kernel is compiled here; the four ``verify.*`` phases of the real device
path are tested where the strict kernel is compiled anyway
(tests/test_crypto.py).  The ledger is process-wide, so every test reads
differences of two snapshots.
"""

import sys
import threading
import time

import numpy as np
import pytest

from consensus_tpu.models import ThreadCoalescingVerifier
from consensus_tpu.obs import kernels
from consensus_tpu.obs.kernels import (
    FLUSHER,
    FLUSHER_COUNTERS,
    FLUSHER_PHASES,
    PhaseLedger,
    phase,
)

MS = 1_000_000
WAVE = ("wave.wait_work", "wave.wait_window", "wave.take", "wave.deliver")
VERIFY = ("verify.prepare", "verify.layout", "verify.dispatch", "verify.await")


def _since(before: dict) -> dict:
    after = FLUSHER.snapshot()
    return {k: after[k] - before[k] for k in after}


class _PhasedEngine:
    """Stands in for the device path: sleeps ``planted[name]`` seconds in
    each ``verify.*`` phase, and ``outside`` seconds in none of them."""

    def __init__(self, planted: dict, outside: float = 0.0) -> None:
        self.planted, self.outside, self.calls = planted, outside, []
        self.threads = []  # the thread each call ran on

    def verify_batch(self, msgs, sigs, keys):
        self.threads.append(threading.get_ident())
        self.calls.append(len(msgs))
        for name in VERIFY:
            with phase(name, cpu=(name == "verify.prepare")):
                time.sleep(self.planted.get(name, 0.0))
        time.sleep(self.outside)
        return np.ones(len(msgs), dtype=bool)


class _SlowEdges(ThreadCoalescingVerifier):
    """A coalescer whose take and deliver steps are slow, so that their
    phases have something to show."""

    take_s = deliver_s = 0.0

    def _take_batch(self):
        time.sleep(self.take_s)
        return super()._take_batch()

    def _deliver(self, *args):
        time.sleep(self.deliver_s)
        return super()._deliver(*args)


class _Seen(ThreadCoalescingVerifier):
    """A coalescer that counts what a causal test waits to SEE before its
    next step: submissions that have joined the queue, windows the flusher
    has opened (it woke, or came back from a launch, to work), how many of
    them from idle, and how many bursts it has booked (one a flush from
    idle, after its verdicts are out)."""

    joined = windows = from_idle = learned = 0

    def _enqueue(self, items):
        super()._enqueue(items)
        with self._cv:
            self.joined += 1

    def _wait_window(self, idle, *args):
        self.windows += 1  # the flusher's alone, under ``_cv``
        self.from_idle += bool(idle)
        return super()._wait_window(idle, *args)

    def _learn(self, *args):
        super()._learn(*args)
        self.learned += 1


def _until(seen, timeout: float = 30.0) -> None:
    deadline = time.monotonic() + timeout
    while not seen():
        assert time.monotonic() < deadline, "never seen"
        time.sleep(0.0005)


def _wave(n: int):
    return [b"m"] * n, [b"s"] * n, [b"k"] * n


def test_every_phase_lands_under_its_own_name_and_they_close_the_threads_life():
    planted = {"wave.wait_work": 0.12, "wave.wait_window": 0.06,
               "wave.take": 0.04, "wave.deliver": 0.05,
               "verify.prepare": 0.05, "verify.layout": 0.04,
               "verify.dispatch": 0.04, "verify.await": 0.08}
    outside, rounds, slack = 0.04, 2, 30 * MS
    engine = _PhasedEngine(planted, outside)
    before = FLUSHER.snapshot()
    t_born = time.monotonic_ns()
    v = _SlowEdges(engine, window=planted["wave.wait_window"], max_batch=64,
                   hard_cap=64)
    v.take_s, v.deliver_s = planted["wave.take"], planted["wave.deliver"]
    for _ in range(rounds):
        time.sleep(planted["wave.wait_work"])  # the flusher has nothing to do
        assert v.verify_batch(*_wave(10)).all()
    v.close()
    lifetime = time.monotonic_ns() - t_born
    assert not v._thread.is_alive()
    got = _since(before)

    # Each phase holds its own planted sleep, rounds times over, and not
    # its neighbours': the least planted sleep is 40 ms, the slack 30 ms a
    # round (a sleep never returns early; a loaded machine wakes late).
    for name, seconds in planted.items():
        low = rounds * seconds * 1e9
        assert low <= got[name] < low + rounds * slack, (name, got[name])
    # The engine as the flusher sees it = its four phases + what it does
    # outside them.
    inside = sum(got[name] for name in VERIFY)
    assert got["engine_ns"] >= inside + rounds * outside * 1e9
    assert got["engine_ns"] < inside + rounds * (outside * 1e9 + slack)
    # Closure: the wave phases and the engine call account for the
    # thread's life, from before its birth to after its death.
    accounted = sum(got[name] for name in WAVE) + got["engine_ns"]
    assert 0.98 * lifetime <= accounted <= lifetime, (accounted, lifetime)
    # prepare slept: the thread held no core for nearly all of it.
    assert got["verify.prepare_cpu"] < 0.2 * got["verify.prepare"]
    # What was submitted: one submission a flush, each under a quarter full, each
    # waited out the window and the slow take.
    assert got["submissions"] == got["flushes"] == rounds == len(engine.calls)
    assert (got["fill_le_25"], got["fill_le_50"], got["fill_le_75"],
            got["fill_le_100"]) == (rounds, 0, 0, 0)
    waited = rounds * (planted["wave.wait_window"] + planted["wave.take"]) * 1e9
    assert waited <= got["queue_wait_ns"] < waited + rounds * slack


@pytest.mark.parametrize("signatures, bucket", [
    (1, 25), (25, 25), (26, 50), (50, 50), (51, 75), (75, 75), (76, 100),
    (100, 100),
])
def test_a_flush_is_counted_by_how_full_of_hard_cap_it_is(signatures, bucket):
    engine = _PhasedEngine({})
    v = ThreadCoalescingVerifier(engine, window=0.001, max_batch=100,
                                 hard_cap=100)
    before = FLUSHER.snapshot()
    assert v.verify_batch(*_wave(signatures)).all()
    v.close()
    got = _since(before)
    assert engine.calls == [signatures]
    assert {k: got[f"fill_le_{k}"] for k in (25, 50, 75, 100)} == {
        k: int(k == bucket) for k in (25, 50, 75, 100)}
    assert got["flushes"] == got["submissions"] == 1


def test_submissions_that_share_a_flush_each_count_their_own_wait():
    """Three callers, each started 40 ms after the one before it was seen in
    the queue, ride ONE flush (the window is a second): three submissions,
    one flush over half full, and a queue wait that is the sum of theirs
    (each from its own arrival to the end of the window), not the flush's."""
    window = 1.0
    engine = _PhasedEngine({})
    v = _Seen(engine, window=window, max_batch=1000, hard_cap=100)
    before = FLUSHER.snapshot()
    born = time.monotonic_ns()  # before the first is queued: the window ends later
    threads, seen = [], []
    for n in range(3):
        t = threading.Thread(target=lambda: v.verify_batch(*_wave(20)))
        t.start()
        threads.append(t)
        _until(lambda: v.joined > n)
        seen.append(time.monotonic_ns())  # after it was queued
        time.sleep(0.04)
    for t in threads:
        t.join(timeout=30.0)
        assert not t.is_alive()
    v.close()
    got = _since(before)
    assert engine.calls == [60]
    assert (got["submissions"], got["flushes"]) == (3, 1)
    assert got["fill_le_75"] == 1
    assert got["wave.wait_window"] >= window * 1e9
    # Each waited from its arrival (before it was seen) to the take (after
    # the window, which opened after the first was born): nominally
    # 1,000 + 960 + 920 ms, and under any load more than the flush's own.
    owed = sum(born + int(window * 1e9) - at for at in seen)
    assert got["queue_wait_ns"] >= owed
    assert got["queue_wait_ns"] > got["wave.wait_window"]


# -- the adaptive hold: ``window`` is its floor ------------------------------
#
# These tests are causal, not timed: a step follows from what the test has
# SEEN (a submission in the queue, the flusher's window open, a launch
# running), never from a sleep that was meant to be long enough.  The sleeps
# left are the nominal gaps between a burst's submitters, which a loaded
# machine may stretch: so the bursts that TEACH the flusher are spread twice
# as wide as the burst a test then sends (a hold reaches as far as the
# bursts it has seen were spread, plus one floor window).  Counts are exact;
# durations are bounded from below, and from above only by what a hold could
# have lasted: the reach the coalescer itself states (``_expectation``).


def _burst(v, k: int, gap: float, size: int = 10) -> None:
    """``k`` submitters, each on a thread of its own and each started
    ``gap`` seconds after the one before it was SEEN in the queue; returns
    once every one has its verdicts."""
    threads = []
    for _ in range(k):
        joined = v.joined
        t = threading.Thread(target=lambda: v.verify_batch(*_wave(size)))
        t.start()
        threads.append(t)
        _until(lambda: v.joined > joined)
        time.sleep(gap)
    for t in threads:
        t.join(timeout=30.0)
        assert not t.is_alive()


def _learned(k: int, launch: float, *, window: float = 0.002, gap: float = 0.0,
             hard_cap: int = 1000, size: int = 10, cut: bool = False):
    """A coalescer over an engine whose launch takes ``launch`` seconds,
    that has seen a lone wave (as the sidecar's warm-up is: it measures the
    launch time) and then two bursts of ``k``, ``gap`` seconds between
    neighbours (so (k - 1) gaps wide: keep that under ``launch``).  The floor
    cut the first (``cut``: always, the rest start once the head's launch
    runs); its tail queued while the head's launch ran and was counted with
    it, count and spread, so the second was held for."""
    engine = _PhasedEngine({"verify.await": launch})
    v = _Seen(engine, window=window, max_batch=hard_cap, hard_cap=hard_cap)
    assert v.verify_batch(*_wave(size)).all()
    if cut:
        head = threading.Thread(target=_burst, args=(v, 1, 0.0, size))
        head.start()
        _until(lambda: len(engine.calls) == 2)  # the head went alone
        _burst(v, k - 1, gap, size)
        head.join(timeout=30.0)
        assert not head.is_alive()
    else:
        _burst(v, k, gap, size)
    _burst(v, k, gap, size)
    assert sum(engine.calls) == (1 + 2 * k) * size
    _until(lambda: v.learned == v.from_idle)  # the last flush is booked too
    return v, engine


def _reach_ns(v) -> int:
    """What the next hold from idle may last, as the flusher will book it:
    read once the last flush whose verdicts are out is in its books."""
    _until(lambda: v.learned == v.from_idle)
    return int(v._expectation()[1] * 1e9)


@pytest.mark.parametrize("k, gap", [(4, 0.09), (7, 0.036)])
def test_a_staggered_burst_rides_one_flush_once_learned(k, gap):
    """The launch is 400 ms.  The bursts that teach are 180-270 ms wide; the
    burst sent then is k submitters half as far apart, 135 ms in all: OVER a
    quarter of the launch (a reach of 100 ms would cut it) and under the
    launch.  It rides ONE flush: the reach is what the flusher has seen a
    burst take, not a share of the launch."""
    launch = 0.4
    before = FLUSHER.snapshot()
    v, engine = _learned(k, launch=launch, gap=gap, cut=True)
    # Unlearned, the floor cut the first burst; the second was held for.
    lone, *rest = engine.calls
    assert lone == 10 and sum(rest) == 2 * 10 * k and len(rest) >= 3
    learning = _since(before)
    assert learning["hold_met"] + learning["hold_expired"] == 1
    assert v._expectation()[0] == k
    reach = _reach_ns(v)
    # Both bursts were at least (k - 2) gaps wide, and a launch bounds it.
    assert (k - 2) * gap * 1e9 + 2 * MS <= reach <= launch * 1e9
    n = len(engine.calls)
    before = FLUSHER.snapshot()
    windows = v.windows
    head = threading.Thread(target=_burst, args=(v, 1, gap / 2))
    head.start()
    _until(lambda: v.windows > windows)  # the flusher woke: it holds from here
    time.sleep(gap / 2)
    _burst(v, k - 1, gap / 2)
    head.join(timeout=30.0)
    assert not head.is_alive()
    v.close()
    got = _since(before)
    assert engine.calls[n:] == [10 * k]
    assert (got["flushes"], got["submissions"]) == (1, k)
    assert (got["hold_met"], got["hold_expired"]) == (1, 0)
    assert got["hold_reach_ns"] == reach  # booked once, the hold's own reach
    # The burst it rode was over a quarter of the launch wide ...
    count, spread, _ = v._recent[-1]
    assert count == k and spread > 0.25 * launch * 1e9
    # ... it held for the stragglers (the last was started k - 1 gaps after
    # the flusher was seen holding), and not to the end of what it may.
    assert (k - 1) * (gap / 2) * 1e9 <= got["wave.wait_window"] < launch * 1e9


def test_a_lone_submitter_pays_the_cap_a_bounded_number_of_times():
    """After bursts of 4 that were 120 ms wide, lone submissions: the first
    is released when the reach (those 120 ms and a floor window) runs out;
    each such hold counts as a burst of one, of no width, so the expectation
    decays and the later ones wait only the floor."""
    v, engine = _learned(4, launch=0.6, window=0.005, gap=0.04)
    n = len(engine.calls)
    waits, expired, reaches = [], [], []
    for _ in range(6):
        before = FLUSHER.snapshot()
        reach = _reach_ns(v)
        assert v.verify_batch(*_wave(10)).all()
        got = _since(before)
        waits.append(got["wave.wait_window"])
        expired.append(got["hold_expired"])
        reaches.append(got["hold_reach_ns"])
        assert got["hold_met"] == 0 and got["flushes"] == 1
        assert got["hold_reach_ns"] == reach * got["hold_expired"]
    v.close()
    assert waits[0] >= 125 * MS and expired[0] == 1
    # Bounded: at most 5 of the last 8 bursts have to be lone ones.
    assert 1 <= sum(expired) <= 5 and expired == sorted(expired, reverse=True)
    # The floor is 5 ms: two dozen of them fit under the reach.
    assert expired[-3:] == [0, 0, 0] and max(waits[-3:]) < 120 * MS
    assert reaches[-3:] == [0, 0, 0]
    assert min(waits) >= 5 * MS
    assert engine.calls[n:] == [10] * 6


@pytest.mark.parametrize("launch, gap, reach_is", [
    (0.001, 0.0, "the floor"), (0.4, 0.05, "the bursts'"), (0.2, 0.05, "the bursts'")])
def test_the_reach_follows_the_bursts_it_has_seen_under_the_measured_launch(
        launch, gap, reach_is):
    """A lone submitter after bursts of 3 waits as long as those bursts were
    wide (100 ms) and a floor window (10 ms) more, whatever the launch takes
    (200 ms, 400 ms: no share of it); under a 1 ms launch no hold can pay
    and it waits the floor."""
    v, engine = _learned(3, launch=launch, window=0.01, gap=gap)
    reach = _reach_ns(v)
    if reach_is == "the floor":
        assert reach == 10 * MS
    else:
        assert (2 * gap + 0.01) * 1e9 <= reach <= launch * 1e9
    before = FLUSHER.snapshot()
    assert v.verify_batch(*_wave(10)).all()
    v.close()
    got = _since(before)
    assert got["wave.wait_window"] >= reach
    # Under the floor there is nothing to hold for: no hold is counted.
    assert got["hold_expired"] == (1 if reach_is != "the floor" else 0)
    assert got["hold_reach_ns"] == got["hold_expired"] * reach
    assert got["hold_met"] == 0


@pytest.mark.parametrize("recent, want", [
    # (count, spread ms, launch ms) of the flushes from idle, oldest first
    ([], (1, 2.0)),                                       # nothing seen: the floor
    ([(1, 0, 40_000), (1, 0, 45), (1, 0, 25)], (1, 2.0)),  # the warm-up waves
    ([(7, 8.0, 29)] * 8, (7, 10.0)),          # n7 at 2,048 lanes: spread + a window
    ([(7, 8.0, 29)] * 5 + [(7, 28.0, 29)] * 3, (7, 10.0)),  # the upper median
    ([(7, 8.0, 29)] * 4 + [(7, 28.0, 29)] * 4, (7, 29.0)),  # ... never over a launch
    ([(7, 40.0, 29)] * 8, (7, 29.0)),
    ([(7, 40.0, 29)] * 4 + [(7, 40.0, 48)] * 4, (7, 29.0)),  # the LOWER median launch
    ([(7, 40.0, 29)] * 3 + [(7, 40.0, 48)] * 5, (7, 42.0)),
    ([(4, 3.0, 8)] * 8, (4, 5.0)),            # n4 at 512 lanes
    ([(4, 3.0, 1.5)] * 8, (4, 2.0)),          # never under the floor
    ([(4, 0.0, 8)] * 8, (4, 2.0)),
    ([(4, 3.0, 8)] * 3 + [(1, 0.0, 8)] * 5, (1, 2.0)),  # 5 lone flushes un-learn it
    ([(4, 3.0, 8)] * 4 + [(1, 0.0, 8)] * 4, (4, 5.0)),
])
def test_the_reach_is_the_seen_spread_and_a_window_between_the_floor_and_a_launch(
        recent, want):
    """``_expectation`` over planted books: (upper median count, reach) with
    reach = upper median spread + one window, at most the lower median
    launch, at least the window (2 ms here)."""
    v = ThreadCoalescingVerifier(_PhasedEngine({}), window=0.002, max_batch=100)
    try:
        v._recent.extend((c, int(s * MS), int(l * MS)) for c, s, l in recent)
        expected, reach = v._expectation()
    finally:
        v.close()
    assert expected == want[0]
    assert reach == pytest.approx(want[1] / 1e3, abs=1e-9)
    if recent:
        launches = sorted(l for _, _, l in recent)
        assert 0.002 <= reach <= max(0.002, launches[(len(recent) - 1) // 2] / 1e3)


@pytest.mark.parametrize("k, size, hard_cap, flushes", [
    (4, 40, 100, [80, 80]), (5, 10, 30, [30, 20])])
def test_a_burst_that_cannot_fit_hard_cap_flushes_without_holding(
        k, size, hard_cap, flushes):
    """Bursts the flusher has learned to expect (120-160 ms wide), of which
    hard_cap holds only 2 (or 3) submissions: with that many pending the
    next is not waited for, and what the launch left behind goes with the
    floor."""
    v, engine = _learned(k, launch=1.2, gap=0.04, hard_cap=hard_cap, size=size)
    assert max(engine.calls) <= hard_cap
    assert v._expectation()[0] == k
    reach = _reach_ns(v)
    assert reach >= (k - 1) * 40 * MS
    n = len(engine.calls)
    before = FLUSHER.snapshot()
    _burst(v, k, 0.005, size=size)
    v.close()
    got = _since(before)
    assert engine.calls[n:] == flushes
    assert (got["hold_met"], got["hold_expired"], got["hold_reach_ns"]) == (0, 0, 0)
    # The head waited for its neighbours (5 ms each), the tail not at all:
    # neither as long as a hold could have lasted.
    assert got["wave.wait_window"] < reach


@pytest.mark.parametrize("queued, floor_waits", [(3, 0), (1, 1)])
def test_submissions_queued_during_a_launch_never_wait_for_a_hold(
        queued, floor_waits):
    """Expecting bursts of 3 that were 300 ms wide, with a 50 ms floor and
    a 2 s launch (a hold may last 350 ms): what queues while a launch runs
    goes at once on its return if the expected burst is there, and with the
    floor if not."""
    v, engine = _learned(3, launch=2.0, window=0.05, gap=0.15)
    reach = _reach_ns(v)
    assert reach >= 350 * MS
    n = len(engine.calls)
    before = FLUSHER.snapshot()
    head = threading.Thread(target=_burst, args=(v, 3, 0.0))
    head.start()
    _until(lambda: len(engine.calls) > n)  # the head's launch is running
    _burst(v, queued, 0.0)
    head.join(timeout=30.0)
    assert not head.is_alive()
    v.close()
    got = _since(before)
    assert engine.calls[n:] == [30, 10 * queued]
    assert (got["hold_met"], got["hold_expired"], got["hold_reach_ns"]) == (0, 0, 0)
    # The head was all there within the floor and went at once, too: what
    # was waited is the floor's, nowhere near a hold's reach.
    assert floor_waits * 50 * MS <= got["wave.wait_window"] < reach


def test_close_ends_a_hold_at_once():
    v, engine = _learned(3, launch=1.2, window=0.01, gap=0.15)
    reach = _reach_ns(v)
    assert reach >= 310 * MS
    n = len(engine.calls)
    before = FLUSHER.snapshot()
    windows = v.windows
    lone = threading.Thread(target=_burst, args=(v, 1, 0.0))
    lone.start()
    _until(lambda: v.windows > windows)  # held: 310 ms is what the hold may last
    time.sleep(0.04)
    v.close()
    lone.join(timeout=30.0)
    got = _since(before)
    assert not lone.is_alive() and not v._thread.is_alive()
    assert engine.calls[n:] == [10]  # served all the same
    assert 40 * MS <= got["wave.wait_window"] < reach
    assert (got["hold_met"], got["hold_expired"], got["hold_reach_ns"]) == (0, 0, 0)


def test_the_phases_close_the_threads_life_with_holds_in_it():
    """Held time is ``wave.wait_window``'s: with a burst that was held for
    and a lone submitter whose hold ran out, the wave phases and the engine
    call still account for the thread's life."""
    before = FLUSHER.snapshot()
    t_born = time.monotonic_ns()
    v, engine = _learned(4, launch=1.2, gap=0.1)
    reach = _reach_ns(v)
    assert v.verify_batch(*_wave(10)).all()  # lone: its hold runs out
    v.close()
    lifetime = time.monotonic_ns() - t_born
    assert not v._thread.is_alive()
    got = _since(before)
    assert got["hold_met"] + got["hold_expired"] == 2
    assert got["hold_expired"] >= 1 and got["hold_reach_ns"] >= 2 * 300 * MS
    assert got["wave.wait_window"] >= reach >= 300 * MS
    accounted = sum(got[name] for name in WAVE) + got["engine_ns"]
    assert 0.98 * lifetime <= accounted <= lifetime, (accounted, lifetime)


# -- the early paths of ``verify_batch``: none of them reaches the flusher ----


def _flusher_untouched(v, before) -> bool:
    """Nothing was queued, flushed or held since ``before``, and the flusher
    booked nothing (the ``verify.*`` phases are the engine's, on whatever
    thread it runs).  Read before ``close``: the flusher's one long
    ``wave.wait_work`` is booked when it ends."""
    return (v.joined, v.windows) == (0, 0) and not any(
        ns for name, ns in _since(before).items() if not name.startswith("verify."))


def test_an_empty_submission_returns_at_once_with_no_flush():
    engine = _PhasedEngine({})
    v = _Seen(engine, window=0.001, max_batch=100, hard_cap=100)
    before = FLUSHER.snapshot()
    got = v.verify_batch([], [], [])
    assert got.shape == (0,) and got.dtype == bool
    assert _flusher_untouched(v, before) and engine.calls == []
    v.close()


def test_lists_of_unequal_length_are_refused_before_anything_is_queued():
    engine = _PhasedEngine({})
    v = _Seen(engine, window=0.001, max_batch=100, hard_cap=100)
    before = FLUSHER.snapshot()
    for lengths in [(3, 2, 3), (3, 3, 2), (0, 1, 1)]:
        msgs, sigs, keys = ([b"x"] * n for n in lengths)
        with pytest.raises(ValueError, match="length mismatch"):
            v.verify_batch(msgs, sigs, keys)
    assert _flusher_untouched(v, before) and engine.calls == []
    v.close()


def test_a_submission_under_bypass_below_is_verified_on_the_callers_thread():
    """The sidecar passes ``min_device_batch``: a wave the engine would
    route to its host path anyway pays no window, no hold and no launch
    slot, and the flusher ledger's ``submissions`` never sees it."""
    engine = _PhasedEngine({})
    v = _Seen(engine, window=0.5, max_batch=100, hard_cap=100, bypass_below=16)
    before = FLUSHER.snapshot()
    assert v.verify_batch(*_wave(15)).all()
    assert engine.calls == [15] and engine.threads == [threading.get_ident()]
    assert _flusher_untouched(v, before)
    assert v.verify_batch(*_wave(16)).all()  # at the bound it rides a flush
    assert engine.calls == [15, 16] and engine.threads[1] == v._thread.ident
    got = _since(before)
    assert (got["submissions"], got["flushes"], v.joined) == (1, 1, 1)
    v.close()


def test_a_flush_that_raises_is_still_counted_and_served_from_the_host():
    class Boom:
        def verify_batch(self, msgs, sigs, keys):
            time.sleep(0.02)
            raise RuntimeError("planted")

        def verify_host(self, msgs, sigs, keys):
            time.sleep(0.03)
            return np.ones(len(msgs), dtype=bool)

    v = ThreadCoalescingVerifier(Boom(), window=0.001, max_batch=8)
    before = FLUSHER.snapshot()
    assert v.verify_batch(*_wave(4)).all()
    v.close()
    got = _since(before)
    assert v.device_suspect
    assert got["flushes"] == 1 and 20 * MS <= got["engine_ns"] < 60 * MS
    assert got["wave.deliver"] >= 30 * MS  # the host serving is booked here


def test_phase_with_the_profiler_off_records_time_and_passes_errors_on():
    before = FLUSHER.snapshot()
    with phase("wave.take"):
        time.sleep(0.02)
    with pytest.raises(KeyError):
        with phase("wave.deliver"):
            time.sleep(0.01)
            raise KeyError("planted")
    got = _since(before)
    assert 20 * MS <= got["wave.take"] < 40 * MS
    assert 10 * MS <= got["wave.deliver"] < 30 * MS


def test_phase_without_jax_records_time_and_raises_nothing(monkeypatch):
    monkeypatch.setattr(kernels, "_ANNOTATION", None)  # resolve again
    monkeypatch.setitem(sys.modules, "jax.profiler", None)  # import fails
    before = FLUSHER.snapshot()
    with phase("verify.layout"):
        time.sleep(0.02)
    assert kernels._ANNOTATION is False
    assert 20 * MS <= _since(before)["verify.layout"] < 40 * MS


def test_phase_cpu_tells_a_thread_that_computes_from_one_that_waits():
    before = FLUSHER.snapshot()
    with phase("verify.prepare", cpu=True):
        time.sleep(0.05)
    slept = _since(before)
    assert slept["verify.prepare_cpu"] < 0.2 * slept["verify.prepare"]
    before = FLUSHER.snapshot()
    with phase("verify.prepare", cpu=True):
        end = time.thread_time_ns() + 50 * MS
        while time.thread_time_ns() < end:
            pass
    spun = _since(before)
    assert spun["verify.prepare_cpu"] >= 50 * MS
    assert spun["verify.prepare_cpu"] <= spun["verify.prepare"] + MS


def test_the_ledger_has_a_fixed_set_of_keys_and_loses_no_update():
    assert set(FLUSHER.snapshot()) == set(FLUSHER_PHASES + FLUSHER_COUNTERS)
    assert len(set(FLUSHER_PHASES + FLUSHER_COUNTERS)) == 20
    assert "hold_reach_ns" in FLUSHER_COUNTERS
    ledger = PhaseLedger(("a", "b"))
    with pytest.raises(KeyError):
        ledger.add("c", 1)  # a name nobody declared is a bug, not a new key
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(20_000):
                ledger.add("a", 1)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert ledger.snapshot() == {"a": 160_000, "b": 0}


def test_a_profiler_trace_names_the_flusher_threads_phases(tmp_path):
    """With a profiler running (as the benchmark's traced run has it:
    python tracer off, host tracer level 2) the phases are host events under
    their bare names, all on ONE thread's line: that is what lets a device
    trace name its idle gaps by phase."""
    import glob

    import jax
    from jax.profiler import ProfileData

    engine = _PhasedEngine({"verify.prepare": 0.004, "verify.await": 0.006})
    v = ThreadCoalescingVerifier(engine, window=0.003, max_batch=64, hard_cap=64)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(4):
            assert v.verify_batch(*_wave(10)).all()
            time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
        v.close()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in FLUSHER_PHASES:
                    lines.setdefault((plane.name, n), []).append(
                        (ev.name, ev.start_ns, ev.duration_ns))
    assert len(lines) == 1, list(lines)  # the flusher thread, and no other
    (events,) = lines.values()
    seen = {name for name, _, _ in events}
    assert seen >= {"wave.wait_window", "wave.take", "wave.deliver",
                    "verify.prepare", "verify.await"}
    # exclusive and sequential: no phase begins before the last one ended
    events.sort(key=lambda e: e[1])
    for (_, s0, d0), (_, s1, _) in zip(events, events[1:]):
        assert s1 >= s0 + d0
    awaits = [d for name, _, d in events if name == "verify.await"]
    assert len(awaits) >= 3 and min(awaits) >= 6 * MS
