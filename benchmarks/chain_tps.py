"""Chain throughput benchmark: n in-process replicas over real TCP sockets
with realtime schedulers, trivial crypto — the BASELINE.json "naive_chain
tx/sec" harness (reference examples/naive_chain/chain_test.go:71-98 is the
equivalent surface; the reference publishes no number).

Sweeps the decision-pipelining window: one cell per ``pipeline_depth``,
each reporting TPS plus p50/p99 decision latency (the leader's
``view_latency_batch_processing`` histogram — prepare/commit exchange per
decision).  Depth 1 is the legacy single-in-flight protocol and doubles as
the baseline; its cell also emits the historical ``naive_chain_tx_per_sec``
record.

Run: python benchmarks/chain_tps.py [n_replicas] [seconds] [depths-csv]
                                    [--trace out.json]
Prints one JSON line per depth plus a speedup summary line.  With
``--trace``, the leader runs with the decision tracer enabled: each cell
writes a Chrome/Perfetto trace (suffixed ``.d<depth>.json`` when sweeping
several depths), prints the critical-path phase-breakdown table, and emits
a machine-readable ``chain_tps_trace_summary`` JSON line (tps, latency
p50/p99, per-phase p50/p99).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # protocol-only bench: no device

from benchmarks._harness import start_feeder, start_replicas, teardown
from consensus_tpu.config import Configuration, TraceConfig
from consensus_tpu.metrics import InMemoryProvider, Metrics
from consensus_tpu.obs.export import render_watch
from consensus_tpu.obs.sampler import ClusterSampler
from consensus_tpu.testing.app import TestApp as PortsApp
from consensus_tpu.testing.app import make_request
from consensus_tpu.trace import build_report, format_table, write_chrome_trace


class _WatchCluster:
    """Duck-typed sampler target over the realtime harness: node 1's
    scheduler drives the ticks, the Holders supply app/running, and the
    leader's consensus + metrics are grafted on for the health fields."""

    def __init__(self, scheduler, nodes):
        self.scheduler = scheduler
        self.nodes = nodes


def _percentile(sorted_values: list[float], q: float) -> float:
    if not sorted_values:
        return 0.0
    idx = min(len(sorted_values) - 1, int(round(q * (len(sorted_values) - 1))))
    return sorted_values[idx]


def run_cell(
    n: int, duration: float, depth: int, trace_path: str | None = None,
    watch: bool = False,
) -> dict:
    """One sweep cell: a fresh cluster at ``pipeline_depth=depth``.

    Each replica persists to a real fsync-backed WAL and batches are kept
    small, so the cell is decision-rate-bound — the regime pipelining
    targets.  (Huge batches instead saturate the harness on per-request
    Python work, which no protocol change can recover.)  Only
    ``pipeline_depth`` varies between cells.
    """

    def make_config(node_id):
        return Configuration(
            self_id=node_id,
            leader_rotation=False,
            decisions_per_leader=0,
            request_batch_max_count=10,
            request_batch_max_interval=0.005,
            request_pool_size=2000,
            pipeline_depth=depth,
            # Only the leader is traced: the phase chains of interest all
            # live on node 1, and a follower's ring would just burn memory.
            trace=TraceConfig(
                enabled=trace_path is not None and node_id == 1,
                capacity=1 << 20,
            ),
        )

    wal_root = tempfile.mkdtemp(prefix=f"chain_tps_d{depth}_")

    def make_wal(node_id, scheduler):
        from consensus_tpu.wal import WriteAheadLog

        # Real fsyncs with the repo's group-commit window (identical in
        # every cell).  The window recovers nothing at depth-1
        # pipelining: with one slot in flight each
        # persist barrier just waits out the window.  The sweep measures
        # how much of that the in-flight window wins back.
        return WriteAheadLog.create(
            os.path.join(wal_root, str(node_id)),
            sync=True,
            group_commit_window=0.002,
            scheduler=scheduler,
        )

    provider = InMemoryProvider()
    cluster, replicas, comms, schedulers = start_replicas(
        n,
        PortsApp,
        make_config,
        leader_metrics=Metrics(provider),
        make_wal=make_wal,
    )

    sampler = None
    if watch:
        for nid, holder in cluster.nodes.items():
            holder.consensus = replicas[nid]
        cluster.nodes[1].metrics = replicas[1].metrics
        sampler = ClusterSampler(
            _WatchCluster(schedulers[1], cluster.nodes),
            interval=0.5,
            install_metrics=False,
        )
        sampler.start()

    leader = replicas[1]
    ledger = cluster.nodes[1].app.ledger
    stop, _exhausted = start_feeder(
        leader,
        (make_request("bench", i) for i in itertools.count()),
        inflight=1500,
    )

    def latencies() -> list[float]:
        try:
            return list(provider.observations("view_latency_batch_processing"))
        except Exception:
            return []

    # Warmup, then measure.
    time.sleep(2.0)
    start_blocks = len(ledger)
    start_tx = sum(int.from_bytes(d.proposal.payload[:4], "big") for d in ledger)
    start_lat = len(latencies())
    t0 = time.time()
    time.sleep(duration)
    elapsed = time.time() - t0
    end_blocks = len(ledger)
    end_tx = sum(int.from_bytes(d.proposal.payload[:4], "big") for d in ledger)
    window_lat = sorted(latencies()[start_lat:])
    stop.set()

    if sampler is not None:
        sampler.stop()
        print(f"# watch: depth={depth} ({sampler.taken} samples @ "
              f"{sampler.interval}s)", flush=True)
        print(render_watch(sampler.samples()), flush=True)

    trace_report = None
    if trace_path is not None:
        # Read the ring before teardown kills the components that feed it.
        tracer = replicas[1].tracer
        events = tracer.events()
        write_chrome_trace(trace_path, events, pid=1)
        trace_report = build_report(events)
        print(f"# trace: {trace_path} ({len(events)} events, "
              f"{tracer.dropped} dropped)", flush=True)
        print(format_table(trace_report), flush=True)

    teardown(replicas, comms, schedulers, cluster)
    shutil.rmtree(wal_root, ignore_errors=True)

    blocks = end_blocks - start_blocks
    if trace_report is not None:
        print(
            json.dumps({
                "metric": "chain_tps_trace_summary",
                "pipeline_depth": depth,
                "n": n,
                "trace_file": trace_path,
                "tps": round((end_tx - start_tx) / elapsed, 1),
                "decision_latency_p50_ms": round(
                    _percentile(window_lat, 0.50) * 1000, 2
                ),
                "decision_latency_p99_ms": round(
                    _percentile(window_lat, 0.99) * 1000, 2
                ),
                "decisions_traced": trace_report["n_decisions"],
                "complete_chains": trace_report["n_complete"],
                "phase_breakdown_ms": {
                    phase: {
                        "p50": round(stats["p50"] * 1000, 3),
                        "p99": round(stats["p99"] * 1000, 3),
                    }
                    for phase, stats in
                    trace_report["phase_percentiles"].items()
                },
            }),
            flush=True,
        )
    return {
        "metric": "chain_tps_pipeline_sweep",
        "pipeline_depth": depth,
        "value": round((end_tx - start_tx) / elapsed, 1),
        "unit": "tx/sec",
        "n": n,
        "f": (n - 1) // 3,
        "blocks_per_sec": round(blocks / elapsed, 1),
        "avg_batch": round((end_tx - start_tx) / max(1, blocks), 1),
        "decision_latency_p50_ms": round(
            _percentile(window_lat, 0.50) * 1000, 2
        ),
        "decision_latency_p99_ms": round(
            _percentile(window_lat, 0.99) * 1000, 2
        ),
    }


def _trace_path_for(base: str | None, depth: int, n_depths: int) -> str | None:
    if base is None:
        return None
    if n_depths == 1:
        return base
    root, ext = os.path.splitext(base)
    return f"{root}.d{depth}{ext or '.json'}"


def main() -> None:
    parser = argparse.ArgumentParser(
        description="naive_chain TPS sweep over pipeline depths"
    )
    parser.add_argument("n", nargs="?", type=int, default=4)
    parser.add_argument("seconds", nargs="?", type=float, default=10.0)
    parser.add_argument("depths", nargs="?", default="1,2,4,8")
    parser.add_argument(
        "--trace",
        metavar="OUT.json",
        default=None,
        help="write the leader's Chrome/Perfetto trace per depth and print "
        "the critical-path phase breakdown",
    )
    parser.add_argument(
        "--watch",
        action="store_true",
        help="sample cluster health during each cell and print terminal "
        "sparklines (ledger height, pool occupancy, in-flight depth)",
    )
    opts = parser.parse_args()
    n = opts.n
    duration = opts.seconds
    depths = [int(d) for d in str(opts.depths).split(",")]

    results = {}
    for depth in depths:
        cell = run_cell(
            n,
            duration,
            depth,
            trace_path=_trace_path_for(opts.trace, depth, len(depths)),
            watch=opts.watch,
        )
        results[depth] = cell
        print(json.dumps(cell), flush=True)
        if depth == 1:
            # The BASELINE.json metric: the legacy (depth-1) protocol.
            legacy = {
                "metric": "naive_chain_tx_per_sec",
                "value": cell["value"],
                "unit": "tx/sec",
                "n": cell["n"],
                "f": cell["f"],
                "blocks_per_sec": cell["blocks_per_sec"],
                "avg_batch": cell["avg_batch"],
            }
            print(json.dumps(legacy), flush=True)

    if 1 in results and 4 in results and results[1]["value"] > 0:
        print(
            json.dumps(
                {
                    "metric": "chain_tps_pipeline_speedup_depth4_vs_depth1",
                    "value": round(results[4]["value"] / results[1]["value"], 2),
                    "unit": "x",
                    "n": n,
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
