"""The rig's sidecar, unchanged, with a watcher thread beside it.

``python served_bench/traced_sidecar.py --config cluster.json --sidecar-id
sc-0`` starts one daemon thread and then calls
``consensus_tpu.deploy.sidecar_main.main()`` as it is.  The thread serves the
orchestrator, which holds no JAX backend, with what only the process that
owns the chip can read: the device's peak memory, and a profiler window
reduced to a small summary (served_bench/tracing.py).

Commands are files ``cmd-<n>.json`` in the directory named by
``SERVED_BENCH_CTL``, taken in order; each is answered by ``reply-<n>.json``.
Idle, the thread costs one ``stat`` per 20 ms.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

CTL_ENV = "SERVED_BENCH_CTL"
WATCH_PERIOD_S = 0.02


def _memory(_cmd) -> dict:
    import jax

    peaks = []
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"memory_peak_bytes": max(peaks) if peaks else 0}


def _trace_start(cmd) -> dict:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(cmd["dir"], profiler_options=options)
    return {"started": True}


def _trace_stop(_cmd) -> dict:
    import jax

    jax.profiler.stop_trace()
    return {"stopped": True}


def _reduce(cmd) -> dict:
    from served_bench import tracing

    path = tracing.find_xplane(cmd["dir"])
    if path is None:
        return {"summary": None, "why": "no .xplane.pb written"}
    raw = tracing.read_xplane(path)
    out = {"xplane_bytes": os.path.getsize(path)}
    if raw["device_events"]:
        out["summary"] = tracing.reduce_events(
            raw["device_events"], raw["host_events"], raw["span_ns"],
            cmd.get("kernel_match", ""), raw["module_events"])
    else:
        # No device plane (the CPU rehearsal): nothing to read.
        out["summary"] = None
        out["why"] = "the trace has no /device:TPU plane"
    return out


OPS = {"memory": _memory, "trace_start": _trace_start,
       "trace_stop": _trace_stop, "reduce": _reduce}


def _watch(ctl: str) -> None:
    n = 0
    while True:
        path = os.path.join(ctl, f"cmd-{n}.json")
        if not os.path.exists(path):
            time.sleep(WATCH_PERIOD_S)
            continue
        try:
            with open(path, encoding="utf-8") as fh:
                cmd = json.load(fh)
            reply = OPS[cmd["op"]](cmd)
            reply["ok"] = True
        except Exception as exc:  # the orchestrator reports it; keep serving
            reply = {"ok": False, "error": repr(exc)}
        tmp = os.path.join(ctl, f"reply-{n}.json.tmp")
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(reply, fh)
        os.replace(tmp, os.path.join(ctl, f"reply-{n}.json"))
        n += 1


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ctl = os.environ.get(CTL_ENV)
    if ctl:
        threading.Thread(target=_watch, args=(ctl,), name="bench-watcher",
                         daemon=True).start()
    from consensus_tpu.deploy import sidecar_main

    return sidecar_main.main()


if __name__ == "__main__":
    sys.exit(main())
