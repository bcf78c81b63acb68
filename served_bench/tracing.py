"""From a profiler trace to busy time, kernel time and idle gaps.

:func:`reduce_events` is pure: it takes device events ``(name, start_ns,
duration_ns)`` per device and host events the same way, and returns the
summary the readers and the ``breakdown`` use.  :func:`read_xplane` turns an
``.xplane.pb`` into those lists with ``jax.profiler.ProfileData`` and so runs
only in the process that owns JAX (the sidecar wrapper).
"""

from __future__ import annotations

import glob
import os

#: Device lines that hold whole-program or step envelopes, not operations:
#: counting them would make every launch look busy from end to end twice.
ENVELOPE_LINES = ("XLA Modules", "Steps", "Framework Ops", "Framework Name Scope",
                  "Source code", "XLA TraceMe")
TOP = 10


def union_ns(intervals) -> int:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def whole_cycles(modules, kernel_match: str, window_ns: tuple):
    """The stretch of the trace made of whole launch cycles: from the start of
    the first launch of the verify program inside ``window_ns`` to the start
    of the last one.  Returns ``(start, end, starts)``, or ``None`` where the
    trace holds fewer than two launches."""
    w0, w1 = window_ns
    starts = sorted(s for name, s, d in modules
                    if kernel_match in name and w0 <= s and s + d <= w1)
    if len(starts) < 2:
        return None
    return starts[0], starts[-1], starts


def reduce_events(device_events: dict, host_events: list, window_ns: tuple,
                  kernel_match: str, module_events: dict = None) -> dict:
    """``device_events``: ``{device name: [(op name, start_ns, dur_ns)]}``;
    ``host_events``: ``[(name, start_ns, dur_ns)]``; ``window_ns``: the span
    of the trace ``(start, end)`` on the trace's clock; ``module_events``:
    like ``device_events`` for whole compiled programs (one event per launch),
    and ``kernel_match`` the substring that names the verify kernel's program
    among them.

    Where a trace begins and ends among the launches is chance, and with a
    launch of 78 ms about every 165 ms it moves a share taken over the raw
    span by tens of points.  So everything is taken over WHOLE LAUNCH CYCLES
    (:func:`whole_cycles`), per device: ``window_s`` is their length,
    ``cycles`` their number (= the launches that began in them),
    ``busy_s`` the union of operation intervals in them, ``kernel_s`` the
    union of the verify program's intervals.  ``busy_per_launch_s`` gives the
    least, the median and the most of one cycle's busy time.  A trace with
    fewer than two launches on a device falls back to its raw span
    (``whole_cycles`` false; ``cycles`` 0).  All are means over the devices.

    A gap is a stretch of the window in which no operation ran on a device,
    named by the host event that covers most of it."""
    busy, windows, per_op, gaps = [], [], {}, []
    kernel_ns, cycles, per_launch, whole = 0, 0, [], True
    for device, events in sorted(device_events.items()):
        modules = (module_events or {}).get(device) or []
        cut = whole_cycles(modules, kernel_match, window_ns)
        if cut is None:
            whole = False
            (w0, w1), starts = window_ns, []
        else:
            w0, w1, starts = cut
        windows.append(max(1, w1 - w0))
        spans = []
        for name, start, dur in events:
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            spans.append((s, e))
            per_op[name] = per_op.get(name, 0) + (e - s)
        spans = merged(spans)
        busy.append(sum(e - s for s, e in spans))
        edge = w0
        for s, e in spans:
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if w1 > edge:
            gaps.append((edge, w1))
        for c0, c1 in zip(starts, starts[1:]):
            per_launch.append(sum(min(e, c1) - max(s, c0) for s, e in spans
                                  if min(e, c1) > max(s, c0)))
        cycles += max(0, len(starts) - 1)
        source = modules if cut is not None else events
        kernel_ns += union_ns(
            (max(s, w0), min(s + d, w1)) for name, s, d in source
            if kernel_match in name and min(s + d, w1) > max(s, w0))
    n_dev = max(1, len(device_events))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = {}
    for s, e in gaps[: 4 * TOP]:
        best, best_overlap = "no_host_event", 0
        for name, hs, hd in host_events:
            overlap = min(e, hs + hd) - max(s, hs)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        named[best] = named.get(best, 0) + (e - s)
    per_launch.sort()
    return {
        "devices": len(device_events),
        "whole_cycles": whole,
        "cycles": cycles / n_dev if whole else 0,
        "window_s": sum(windows) / n_dev / 1e9,
        "traced_span_s": max(0, window_ns[1] - window_ns[0]) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "kernel_s": kernel_ns / n_dev / 1e9,
        "kernel_launches": cycles / n_dev if whole else 0,
        "busy_per_launch_s": [per_launch[0] / 1e9,
                              per_launch[len(per_launch) // 2] / 1e9,
                              per_launch[-1] / 1e9] if per_launch else None,
        "device_events": sum(len(v) for v in device_events.values()),
        "longest_gap_s": (gaps[0][1] - gaps[0][0]) / 1e9 if gaps else 0.0,
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(named.items(), key=lambda kv: -kv[1])[:TOP]],
    }


def short_name(name: str) -> str:
    """An event's name without the HLO text behind it: ``while.105`` from
    ``%while.105 = (s32[]{...}) while(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")[:64]


def find_xplane(trace_dir: str):
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    return hits[-1] if hits else None


def read_xplane(path: str, *, describe: bool = False) -> dict:
    """Device operation events per device plane, host events, and the span of
    the trace.  Device planes are the ``/device:TPU:<n>`` ones; of their lines
    the operation line (``XLA Ops``) where there is one, else every line that
    is not an envelope.  ``describe`` adds the planes' and lines' names and a
    few events of each, for a look by hand."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_events, module_events, host_events, layout = {}, {}, [], []
    lo, hi = None, None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        lines = list(plane.lines)
        if is_device:
            for ln in lines:
                if ln.name == "XLA Modules":
                    module_events[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in ln.events]
        if is_device:
            wanted = ([ln for ln in lines if ln.name == "XLA Ops"]
                      or [ln for ln in lines if ln.name not in ENVELOPE_LINES])
        elif plane.name.startswith("/host:"):
            wanted = lines
        else:
            wanted = []
        if describe:
            layout.append({"plane": plane.name, "lines": [
                {"line": ln.name,
                 "events": [[ev.name, ev.start_ns, ev.duration_ns]
                            for _, ev in zip(range(4), ln.events)]}
                for ln in lines]})
        for ln in wanted:
            for ev in ln.events:
                start, dur = int(ev.start_ns), int(ev.duration_ns)
                lo = start if lo is None else min(lo, start)
                hi = start + dur if hi is None else max(hi, start + dur)
                if is_device:
                    device_events.setdefault(plane.name, []).append(
                        (short_name(ev.name), start, dur))
                elif dur >= 100_000:  # host events under 0.1 ms name no gap
                    host_events.append((short_name(ev.name), start, dur))
    out = {"device_events": device_events, "module_events": module_events,
           "host_events": host_events, "span_ns": (lo, hi)}
    if describe:
        out["layout"] = layout
    return out
