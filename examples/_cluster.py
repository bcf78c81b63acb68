"""An n-replica cluster in ONE process over real localhost TCP sockets with
realtime schedulers, plus the feeder and teardown around it: what
examples/fabric_orderer.py runs its embedder on.  (Real OS processes are
``consensus_tpu/deploy/``; the measured served path is ``served_bench/``.)
"""

from __future__ import annotations

import threading
from typing import Callable

from consensus_tpu.consensus import Consensus
from consensus_tpu.deploy.spec import free_ports
from consensus_tpu.net import TcpComm
from consensus_tpu.runtime import RealtimeScheduler
from consensus_tpu.sync import (
    LedgerDecisionStore,
    LedgerSynchronizer,
    SyncListener,
    SyncServer,
    TcpSyncTransport,
)
from consensus_tpu.testing.app import MemWAL
from consensus_tpu.types import Reconfig


class RealCluster:
    """App-level cluster state shared by the replicas' application ports."""

    def __init__(self):
        self.nodes = {}
        self.sync_listeners = {}

    def reconfig_of(self, proposal):
        return Reconfig()


class Holder:
    def __init__(self, app):
        self.app = app


def start_replicas(
    n: int,
    make_app: Callable[[int, RealCluster], object],
    make_config: Callable[[int], object],
):
    """Boot n replicas over TCP.  Returns (cluster, replicas, comms,
    schedulers).

    Each replica gets the real catch-up stack: a SyncServer/SyncListener
    serving its ledger plus a LedgerSynchronizer fetching verified chunks
    from peers over TCP.  The write-ahead log is the in-memory ``MemWAL``:
    no durability cost.
    """
    ports = free_ports(n)
    addrs = {i + 1: ("127.0.0.1", ports[i]) for i in range(n)}
    cluster = RealCluster()
    replicas, comms, schedulers = {}, {}, {}

    # Apps and their sync listeners come up first so every replica knows the
    # full sync-address map before its client is built.
    apps, stores, sync_addrs = {}, {}, {}
    for node_id in addrs:
        app = make_app(node_id, cluster)
        apps[node_id] = app
        cluster.nodes[node_id] = Holder(app)
        store = LedgerDecisionStore(app.ledger)
        stores[node_id] = store
        listener = SyncListener(SyncServer(store))
        cluster.sync_listeners[node_id] = listener
        sync_addrs[node_id] = listener.address

    for node_id in addrs:
        app = apps[node_id]
        rt = RealtimeScheduler()
        rt.start(thread_name=f"replica-{node_id}")
        schedulers[node_id] = rt

        def make_router(nid):
            def route(sender, payload, is_request):
                consensus = replicas.get(nid)
                if consensus is None:
                    return
                if is_request:
                    consensus.handle_request(sender, payload)
                else:
                    consensus.handle_message(sender, payload)

            return route

        comm = TcpComm(node_id, addrs, make_router(node_id), reconnect_backoff=0.05)
        comm.start()
        comms[node_id] = comm
        synchronizer = LedgerSynchronizer(
            node_id=node_id,
            store=stores[node_id],
            transport=TcpSyncTransport(
                node_id,
                {i: a for i, a in sync_addrs.items() if i != node_id},
            ),
            verifier=app,
            nodes=list(addrs),
            reconfig_of=cluster.reconfig_of,
        )
        consensus = Consensus(
            config=make_config(node_id),
            scheduler=rt,
            comm=comm,
            application=app,
            assembler=app,
            wal=MemWAL([]),
            signer=app,
            verifier=app,
            request_inspector=app.inspector,
            synchronizer=synchronizer,
        )
        consensus.start()
        replicas[node_id] = consensus

    return cluster, replicas, comms, schedulers


def start_feeder(leader, requests, *, inflight: int):
    """Feed ``requests`` (an iterable of raw request bytes or a generator)
    to the leader with semaphore backpressure on a daemon thread.  Returns
    (stop_event, exhausted: list[bool]); ``exhausted[0]`` turns True if the
    request stream ran dry before ``stop_event`` was set (a run that
    exhausts its stream mid-window reports less than the cluster orders)."""
    stop = threading.Event()
    exhausted = [False]

    def feeder():
        sem = threading.Semaphore(inflight)

        def release(err):
            sem.release()

        for raw in requests:
            if stop.is_set():
                return
            sem.acquire()
            leader.submit_request(raw, release)
        exhausted[0] = True

    threading.Thread(target=feeder, daemon=True).start()
    return stop, exhausted


def teardown(replicas, comms, schedulers, cluster):
    for consensus in replicas.values():
        consensus.stop()
    for comm in comms.values():
        comm.stop()
    for listener in cluster.sync_listeners.values():
        listener.close()
    cluster.sync_listeners.clear()
    for rt in schedulers.values():
        try:
            rt.stop(timeout=2.0)
        except RuntimeError:
            pass
