#!/usr/bin/env python3
"""served_bench/run.py — run one cell of BENCHMARK.json once.

    python3 served_bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts the rig (n replica processes + one sidecar that holds the chip), warms,
measures for ``--seconds``, audits, tears down, and prints ONE JSON object as
the last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (with ``--trace 1`` also ``breakdown``) and, last,
``compared``: every number that decided ``correct`` beside its limit.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics.

Without a TPU it prints no result and exits non-zero.  ``--dry-run`` (with
``JAX_PLATFORMS=cpu``) rehearses the same code at a tiny size on the CPU
backend: its line says ``platform: cpu`` and carries no device metric.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

#: Cluster directories (WALs, cluster.json, the trace) live here, inside the
#: checkout, and are removed when the run ends.
OUT_DIR = os.path.join(REPO, "served_bench_out")


def load_manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def load_config(manifest: dict, name: str) -> dict:
    entry = next(c for c in manifest["configs"] if c["name"] == name)
    with open(os.path.join(REPO, entry["file"]), encoding="utf-8") as fh:
        return json.load(fh)


def metrics_of(manifest: dict, kind: str, cell: str) -> list:
    """The metrics of ``kind`` that ``cell`` reports (no ``workloads`` key:
    every cell)."""
    return [m for m in manifest[kind]
            if cell in m.get("workloads", [cell])]


def parent_backends() -> list:
    """JAX backends THIS process initialised (must stay empty)."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    return sorted(getattr(bridge, "_backends", {})) if bridge else []


def end_to_end(readings: dict) -> tuple:
    """``({metric: value}, attempted, failed)`` from the poll log."""
    from served_bench import poll

    window, size = readings["window"], readings["size"]
    log = poll.quorum_log(readings["samples"], size["f"] + 1)
    first, last = window["first_rank"], window["last_rank"]
    values = {"setup_s": readings["setup_s"]}
    if readings["mode"] == "closed":
        committed = (poll.count_at(log, window["t1"])
                     - poll.count_at(log, window["t0"]))
        values["committed_tx_per_s"] = committed / readings["seconds"]
        done = poll.commit_times(log, first, last - first)
        failed = sum(1 for t in done if t is None)
    else:
        rate = float(readings["rate_per_s"])
        due = [window["s0"] + i / rate for i in range(first, last)]
        latencies, failed = poll.due_latencies(
            log, first, due, readings["end_of_drain"])
        values["commit_latency_p50_ms"] = 1000.0 * poll.percentile(latencies, 50)
        values["commit_latency_p95_ms"] = 1000.0 * poll.percentile(latencies, 95)
    return values, last - first, failed


def per_layer_context(readings: dict) -> dict:
    from served_bench import poll

    window, size = readings["window"], readings["size"]
    quorum = size["f"] + 1
    requests = poll.quorum_log(readings["samples"], quorum)
    decisions = poll.quorum_log(readings["decisions"], quorum)
    t0, t1 = window["t0"], window["t1"]
    reduced = readings.get("trace_reduced") or {}
    return {
        "first": window.get("sidecar_first") or {},
        "last": window.get("sidecar_last") or {},
        "counted_s": (window.get("sidecar_last_at", 0.0)
                      - window.get("sidecar_first_at", 0.0)),
        "requests": poll.count_at(requests, t1) - poll.count_at(requests, t0),
        "decisions": poll.count_at(decisions, t1) - poll.count_at(decisions, t0),
        "trace": reduced.get("summary"),
        "late_s": readings.get("late_s") or [],
        "device_kind": readings["device"]["kind"],
        "lanes": readings["sidecar"].get("lanes"),
    }


def result_line(manifest: dict, workload: str, readings: dict, trace: bool,
                backends: list) -> tuple:
    """The contract's last line for one run, and the end-to-end values (a
    traced run prints them on an earlier line, for the record only)."""
    from served_bench import judge, readers

    values, attempted, failed = end_to_end(readings)
    compared = judge.compare(readings)
    # Two more, about the harness itself: this process must hold no JAX
    # backend, and a closed loop that used up its pre-signed requests before
    # the window closed measured nothing (raise presign_tx_per_s).
    compared["orchestrator_backends"] = {"value": len(backends), "limit": 0}
    compared["presign_exhausted"] = {"value": int(readings["ran_dry"]), "limit": 0}

    device = dict(readings["device"])
    device["memory_peak_bytes"] = int(
        (readings.get("memory") or {}).get("memory_peak_bytes", 0))
    kind = "per_layer" if trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in manifest[kind]}
    metrics, breakdown = {}, None
    if trace:
        ctx = per_layer_context(readings)
        for m in metrics_of(manifest, "per_layer", workload):
            with open(os.path.join(HERE, "metrics", m["name"] + ".json"),
                      encoding="utf-8") as fh:
                reader = readers.load(json.load(fh)["reader"])
            value = reader(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        summary = ctx["trace"]
        if summary:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
    else:
        for m in metrics_of(manifest, "end_to_end", workload):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": units[m["name"]]}
    result = {"correct": judge.correct(compared), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result, values


def explain(readings: dict) -> None:
    """For a run that came out not correct: what each replica delivered, how
    the ledgers grew from just before the injector stopped (requests and
    heights per sweep, so the size of each late decision can be read off),
    and what the processes last said.  No control op returns a decision's
    contents, so WHICH requests a replica delivered twice cannot be had from
    outside the program (PERF.md section 7)."""
    window, err = readings["window"], sys.stderr
    told = {}
    for name, tail in readings.get("stderr_tails", {}).items():
        # (a vote for a sequence already decided here is logged as a warning
        # all through every run: "got ... at seq 652, ours is 654")
        loud = [ln for ln in tail if "INFO" not in ln and ", ours is " not in ln]
        for line in loud[-12:]:
            print(f"[{name}] {line.rstrip()[:300]}", file=err)
        # of the whole run: a replica that fell behind and caught up by sync
        told[name] = [ln.rstrip()[-160:] for ln in tail if "sync" in ln.lower()][:6]
    # sweeps in which one replica was two decisions or more behind another
    behind = [[round(t - window["t0"], 3), heights]
              for t, heights in readings["decisions"]
              if max(heights) - min(heights) >= 2]
    after = [[round(t - window["t1"], 3), counts, heights]
             for (t, counts), (_, heights)
             in zip(readings["samples"], readings["decisions"])
             if t >= window["t1"] - 0.5]
    delivered = {str(node): [a.get("requests"), a.get("decisions"), a.get("distinct")]
                 for node, a in readings["audits"].items()}
    # on standard output too, before the result line: the driver keeps
    # more of that stream's end
    print(json.dumps({"not_correct": {
        "sent": readings["sent"],
        "delivered_requests_decisions_distinct": delivered,
        "said_sync": {k: v for k, v in told.items() if v},
        "sweeps_two_decisions_apart": len(behind),
        "first_such_s_after_open_heights": behind[:4],
        "s_after_close_requests_heights": after[:40] + after[-3:]}}), flush=True)
    for name, lines in told.items():
        for line in lines[:3]:
            print(f"[{name}] said: {line}", file=err)
    print(f"sent {readings['sent']}; per replica [requests, decisions, distinct] "
          f"delivered: {delivered}; {len(behind)} sweeps with a replica two "
          f"decisions behind, first {behind[:2]}", file=err)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU rehearsal at a tiny size; not a chip result")
    ap.add_argument("--control", default="", choices=("", "replay"),
                    help="break one guarantee on purpose (the run must come "
                         "out not correct)")
    ap.add_argument("--witness", default="", choices=("", "listener_pause"),
                    help="a fault the guarantees must survive (PERF.md "
                         "section 7): the run has to come out correct")
    args = ap.parse_args(argv)

    pinned_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if not args.dry_run and pinned_cpu:
        print("served_bench: JAX_PLATFORMS=cpu — a cell needs the TPU (use "
              "--dry-run for the CPU rehearsal)", file=sys.stderr)
        return 2
    if args.dry_run and not pinned_cpu:
        print("served_bench: --dry-run rehearses on the CPU backend; run it "
              "with JAX_PLATFORMS=cpu so it cannot take the chip",
              file=sys.stderr)
        return 2
    try:
        import consensus_tpu.deploy  # noqa: F401  (the system under test)
    except ImportError as exc:
        print(f"served_bench: the program is not in this checkout: {exc}",
              file=sys.stderr)
        return 2

    from served_bench import judge, poll, rig, traffic

    manifest = load_manifest()
    cell = next((w for w in manifest["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        print(f"served_bench: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    config = load_config(manifest, cell["config"])
    mix = traffic.load_traffic(cell["traffic"])

    out_dir = os.path.join(OUT_DIR, f"{args.workload}.{args.seed}")
    try:
        readings = rig.measure(
            config, mix, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), dry_run=args.dry_run, out_dir=out_dir,
            t_start=T_START, control=args.control, witness=args.witness)
    except rig.NoDevice as exc:
        print(f"served_bench: {exc}", file=sys.stderr)
        return 2
    result, values = result_line(manifest, args.workload, readings,
                                 bool(args.trace), parent_backends())
    window = readings["window"]
    took = sorted(w["took_s"] for w in readings["window_waves"] if "took_s" in w)
    # Signatures each replica verified on its host per decision, beyond the one
    # check per admitted request: the quorum certificates (commit signatures
    # in batches under the client's bypass).
    clients = list(readings["clients"].values())
    decisions = max(1, max(int(a.get("decisions", 0))
                           for a in readings["audits"].values()))
    host_checked = (sum(c.get("bypassed", 0) for c in clients)
                    / max(1, len(clients)) - readings["sent"]) / decisions
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "dry_run": args.dry_run,
        "control": args.control, "witness": window.get("witness"),
        "end_to_end": values,
        "order_exact": judge.order_exact(readings),
        "leaders_seen": len(readings["leaders"]),
        "sent": readings["sent"], "first_rank": window["first_rank"],
        "last_rank": window["last_rank"],
        "poll_sweeps": len(readings["samples"]),
        "poll_calls_failed": readings["poll_calls_failed"],
        "sidecar_boot": readings["sidecar_boot"],
        "trace_calls": readings.get("trace_calls"),
        "trace": readings.get("trace_reduced"),
        "wall_s": time.monotonic() - T_START,
        "window_at": [window["t0"], window["t1"]],
        "host_checked_per_decision": host_checked,
        "stalls": poll.stalls(readings["samples"], readings["size"]["f"] + 1),
        "window_waves": {
            "due": len(readings["window_waves"]), "sent": len(took),
            "answered": sum(1 for w in readings["window_waves"]
                            if w["got"] is not None),
            "took_s_min_median_max": [took[0], took[len(took) // 2], took[-1]]
            if took else None},
    }, sort_keys=True), flush=True)
    if not result["correct"]:
        explain(readings)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
