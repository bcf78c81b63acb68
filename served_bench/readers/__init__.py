"""Per-layer metric readers, one small module each, found by name.

A metric's file under ``metrics/`` names its reader; ``read(ctx)`` returns the
number, or ``None`` where there is nothing to read (the harness then leaves
the metric out of the line).  ``ctx`` holds ``first`` / ``last`` (the sidecar's
counters at the window's first and last instant, ``counted_s`` apart),
``decisions`` and
``requests`` (committed inside the window, as a reply quorum reported them),
``trace`` (the reduced profiler window, or ``None``), ``late_s`` (how late the
injector sent each request of the window), ``device_kind`` and ``lanes``.
"""

import importlib


def load(name: str):
    return importlib.import_module(f"served_bench.readers.{name}").read


def delta(ctx: dict, key: str):
    first, last = ctx["first"].get(key), ctx["last"].get(key)
    if first is None or last is None:
        return None
    return last - first
