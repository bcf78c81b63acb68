"""One cell, once: start the rig, warm, measure a window, audit, tear down.

The rig start, the audits and the teardown are a copy of
``chip_smoke.py::run_served_path`` (phases 1-3 and teardown; PR 22 proved them
on the chip) — a copy, because the yardstick may not depend on a file later
PRs can edit.  What is new is the traffic loop with its timed window, the
poller, the watcher commands to the sidecar and the verdict wave against the
plain reference.  :func:`measure` returns raw readings; :mod:`judge` decides
``correct`` from them; :mod:`run` reduces them to the metrics.

The entry driven is the one an embedder calls: ``ClusterSpec.generate`` ->
``ClusterLauncher.start`` -> replica processes (file WAL, real localhost
sockets) -> ``SidecarVerifierClient`` -> the rig's sidecar holding the chip.
This process never initialises a JAX backend.
"""

from __future__ import annotations

import collections
import json
import math
import os
import shutil
import threading
import time

from served_bench import poll, reference, traffic
from served_bench.traced_sidecar import CTL_ENV

#: Transport id of the request injector (outside the replica ids).
INJECTOR_ID = 900
#: The CPU rehearsal's size, whatever the configuration: chip_smoke's DRY.
DRY = dict(n=4, batch=64, pool=256, clients=16, request_timeout=60.0,
           max_tx_per_s=400, rate_per_s=4.0, open_bypass_below=2,
           verdict_period_s=5.0)
#: Inside the timed window the harness is one more tenant of the sidecar: every
#: VERDICT_PERIOD_S it sends VERDICT_LANES signatures, 8 of them one of each
#: rejection class, through a client of the same kind as the replicas', so the
#: coalescer puts them into launches together with the replicas' own waves.
#: One constant for all cells: 64 lanes beside a decision's 416 signatures
#: still fit the smallest (512-lane) launch shape, and 64 signatures a second
#: are under 1% of what either deployment's replicas send.
VERDICT_PERIOD_S = 1.0
VERDICT_LANES = 64
#: The profiler window of a ``--trace 1`` run closes with the timed window and
#: is sized, before it opens, to hold about this many launches at the launch
#: rate the window has shown so far (at most TRACE_MAX_SECONDS).  The TPU plane
#: records every HLO operation (about 107,000 events per verify launch,
#: whatever ``tpu_trace_mode`` says) and ``stop_trace`` costs 5 s per traced
#: launch on a quiet host and 14-19 s beside the rig's processes, so the window
#: is sized in launches (it catches about two more than it asks for) and put at
#: the end, where ``stop_trace`` runs beside the drain.  A traced run has to end
#: within 360 s.
TRACE_LAUNCHES = 4
TRACE_MAX_SECONDS = 3.0
START_TIMEOUT_S = 900.0
#: Lines of each process's standard error kept for a run that comes out not
#: correct (the supervisor's own 60 cover a second of an n=4 run, and the
#: line that tells of a sync may be half a minute old).
STDERR_LINES = 20000


class NoDevice(RuntimeError):
    """The sidecar found no TPU: no result may be printed."""


class Watcher:
    """The orchestrator's side of served_bench/traced_sidecar.py."""

    def __init__(self, ctl_dir: str) -> None:
        self.ctl_dir = ctl_dir
        self._n = 0
        self._lock = threading.Lock()
        os.makedirs(ctl_dir, exist_ok=True)

    def call(self, op: str, timeout: float = 120.0, **kw) -> dict:
        with self._lock:
            n, self._n = self._n, self._n + 1
            tmp = os.path.join(self.ctl_dir, f"cmd-{n}.json.tmp")
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(dict(kw, op=op), fh)
            os.replace(tmp, os.path.join(self.ctl_dir, f"cmd-{n}.json"))
            reply_path = os.path.join(self.ctl_dir, f"reply-{n}.json")
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if os.path.exists(reply_path):
                    with open(reply_path, encoding="utf-8") as fh:
                        return json.load(fh)
                time.sleep(0.01)
        return {"ok": False, "error": f"watcher did not answer {op} in {timeout}s"}


def make_launcher(spec, ctl_dir: str):
    from consensus_tpu.deploy import ClusterLauncher

    wrapper = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traced_sidecar.py")

    class BenchLauncher(ClusterLauncher):
        """The rig's launcher; its sidecar is started through the wrapper."""

        def _sidecar_argv(self, sidecar_id: str) -> list:
            return [self.python, wrapper, "--config", self.spec.config_path,
                    "--sidecar-id", sidecar_id]

        def _make_supervisor(self, name, argv, control_addr, env):
            sup = super()._make_supervisor(name, argv, control_addr, env)
            sup._tail_lines = STDERR_LINES
            sup._tail = collections.deque(maxlen=STDERR_LINES)
            return sup

    launcher = BenchLauncher(spec)
    launcher._env[CTL_ENV] = ctl_dir
    return launcher


def sized(config: dict, mix: dict, dry_run: bool) -> tuple:
    """The configuration and the mix as they are run: the files' own values,
    or the rehearsal's where ``--dry-run`` says so."""
    overrides = dict(config["configuration"])
    size = dict(n=config["n"], clients=config["clients"],
                body_bytes=config["request_body_bytes"],
                request_timeout=config["sidecar_request_timeout"],
                bypass_below=config["sidecar_bypass_below"],
                max_tx_per_s=config["presign_tx_per_s"],
                verdict_period_s=VERDICT_PERIOD_S)
    if dry_run:
        size.update(n=DRY["n"], clients=DRY["clients"],
                    request_timeout=DRY["request_timeout"],
                    max_tx_per_s=DRY["max_tx_per_s"],
                    verdict_period_s=DRY["verdict_period_s"])
        if mix["mode"] == "open":
            # The CPU stand-in commits a few requests a second, so an open
            # loop it can follow seals batches far under the client's bypass:
            # lower the bypass, or nothing of the rehearsal reaches the
            # sidecar.
            mix = dict(mix, rate_per_s=DRY["rate_per_s"])
            size["bypass_below"] = DRY["open_bypass_below"]
            overrides["crypto_tpu_min_batch"] = DRY["open_bypass_below"]
        # The CPU backend stands in for the chip ~50x slower, so a decision
        # takes seconds: keep the request-forward timer (2 s shipped) out of
        # that range, or a forwarded copy lands past the pool's 5 s dedup
        # horizon and commits twice.
        overrides.update(request_batch_max_count=DRY["batch"],
                         request_pool_size=DRY["pool"],
                         request_forward_timeout=60.0,
                         request_complain_timeout=120.0)
    size["batch"] = overrides["request_batch_max_count"]
    size["f"] = (size["n"] - 1) // 3
    size["overrides"] = overrides
    return size, mix


def _presign_count(mix: dict, size: dict, seconds: float) -> int:
    if mix["mode"] == "open":
        # request i is due i / rate after the first: warm-up, then the window
        return int(mix["rate_per_s"] * (mix["warm_s"] + seconds))
    return int(mix["warm_batches"] * size["batch"]
               + size["max_tx_per_s"] * (seconds + 1.0))


def window_wave_instants(seconds: float, period: float) -> list:
    """When, after the window opens, each verdict wave is due: the middle of
    every whole or part period."""
    return [(k + 0.5) * period for k in range(math.ceil(seconds / period))
            if (k + 0.5) * period < seconds]


def _verdict_tenant(client, waves: list, instants: list, window: dict) -> None:
    """Send each wave when it is due and keep the verdicts beside it.  A wave
    the sidecar did not answer keeps ``got`` None: the judge counts all its
    lanes."""
    for wave, after in zip(waves, instants):
        time.sleep(max(0.0, window["t0"] + after - time.monotonic()))
        t = time.monotonic()
        try:
            wave["got"] = [bool(v) for v in client.verify_batch(*wave["wave"])]
        except Exception as exc:  # the judge counts it; go on
            wave["error"] = repr(exc)
        wave["at_s"], wave["took_s"] = after, time.monotonic() - t


def _listener_pause(control, poller, window: dict, quorum: int,
                    seconds: float) -> None:
    """The witness of PERF.md section 7: in the middle of the window one
    replica's listener is closed (the program's own chaos op ``net_pause``)
    for four decision times, so it falls three decisions behind and catches
    up by sync.  A fault the guarantees must survive."""
    time.sleep(max(0.0, window["t0"] + seconds / 2.0 - time.monotonic()))
    pause_s = max(0.3, 4.0 * poll.median_commit_gap(poller.samples, quorum))
    window["witness"] = {"pause_s": pause_s,
                         "paused": control.try_call("net_pause")}
    time.sleep(pause_s)
    window["witness"]["resumed"] = control.try_call("net_resume")


def measure(config: dict, mix: dict, *, seed: int, seconds: float, trace: bool,
            dry_run: bool, out_dir: str, t_start: float,
            control: str = "", witness: str = "") -> dict:
    """Run the cell once and return the raw readings.  ``control="replay"``
    breaks exactly-once delivery with the program's own path: the first chunk
    is sent again once the pool has forgotten it was delivered (5 s).
    ``witness="listener_pause"`` breaks nothing a deployment may not meet
    (:func:`_listener_pause`); such a run has to come out correct."""
    from consensus_tpu.deploy import ClusterSpec
    from consensus_tpu.deploy.control import ControlClient
    from consensus_tpu.deploy.sidecar_main import EXIT_NO_DEVICE
    from consensus_tpu.deploy.spec import free_ports
    from consensus_tpu.net import SidecarVerifierClient, TcpComm

    size, mix = sized(config, mix, dry_run)
    n, f, batch = size["n"], size["f"], size["batch"]
    readings: dict = {"size": {k: size[k] for k in ("n", "f", "batch", "clients")},
                      "mode": mix["mode"], "seconds": seconds, "seed": seed,
                      "rate_per_s": mix.get("rate_per_s")}
    cluster_dir = os.path.join(out_dir, "cluster")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    watcher = Watcher(os.path.join(out_dir, "ctl"))
    trace_dir = os.path.join(out_dir, "trace")

    # Pre-sign while the sidecar boots: both are set-up, neither waits.
    factory = traffic.RequestFactory(seed, size["clients"], size["body_bytes"])
    requests: list = []
    instants = window_wave_instants(seconds, size["verdict_period_s"])
    window_waves: list = []

    def presign() -> None:
        requests.extend(factory.make_many(_presign_count(mix, size, seconds)))
        for k in range(len(instants)):
            wave, planted = reference.verdict_wave(VERDICT_LANES, seed, k + 1)
            window_waves.append({"wave": wave, "planted": planted, "got": None})

    signer = threading.Thread(target=presign, name="bench-presign")
    signer.start()

    spec = ClusterSpec.generate(n, 1, cluster_dir, clients=size["clients"],
                                config_overrides=size["overrides"],
                                hold_ports=True)
    spec.key_namespace = factory.namespace
    spec.sidecar_request_timeout = size["request_timeout"]
    spec.sidecar_bypass_below = size["bypass_below"]
    launcher = make_launcher(spec, watcher.ctl_dir)
    comm = poller = verdict_client = None
    want_platform = "cpu" if dry_run else "tpu"
    try:
        # ---- rig ---------------------------------------------------------
        try:
            launcher.start(timeout=START_TIMEOUT_S)
        except (RuntimeError, TimeoutError) as exc:
            sup = launcher.sidecars.get("sc-0")
            if sup is not None and sup.exit_code == EXIT_NO_DEVICE and not dry_run:
                raise NoDevice(str(exc)) from exc
            raise
        sc = launcher.sidecars["sc-0"].probe() or {}
        readings["device"] = {"platform": sc.get("platform"),
                              "kind": sc.get("device_kind"),
                              "count": sc.get("device_count")}
        readings["sidecar_boot"] = {k: sc.get(k) for k in (
            "lanes", "cache_dir", "backend_secs", "warm_compile_secs", "compiles")}
        if sc.get("platform") != want_platform:
            raise NoDevice(f"sidecar reports platform {sc.get('platform')!r}, "
                           f"this run needs {want_platform!r}")
        signer.join()
        addresses = dict(spec.comm_addresses())
        addresses[INJECTOR_ID] = ("127.0.0.1", free_ports(1)[0])
        comm = TcpComm(INJECTOR_ID, addresses, lambda *a: None,
                       reconnect_backoff=0.05, auth_secret=spec.auth_secret,
                       send_queue_depth=len(requests) + batch)
        comm.start()
        controls = {r.node_id: ControlClient((r.host, r.control_port), timeout=5.0)
                    for r in spec.replicas}
        sidecar_control = launcher.sidecars["sc-0"].control
        poller = poll.Poller(controls)
        poller.start()
        node_ids = spec.node_ids()

        def send(lo: int, hi: int) -> None:
            for raw in requests[lo:hi]:
                for node_id in node_ids:
                    comm.send_transaction(node_id, raw)

        window: dict = {}
        verdict_client = SidecarVerifierClient(
            spec.sidecar_addresses()["sc-0"], auth_secret=spec.auth_secret,
            request_timeout=max(120.0, size["request_timeout"]))
        tenant = threading.Thread(
            target=_verdict_tenant, name="bench-verdicts",
            args=(verdict_client, window_waves, instants, window))
        tracer = None
        if trace:
            tracer = threading.Thread(
                target=_trace_window, name="bench-tracer",
                args=(watcher, sidecar_control, trace_dir, readings, seconds,
                      window))

        # ---- traffic -----------------------------------------------------

        def open_window(t0: float) -> None:
            window["t0"] = t0
            window["t1"] = t0 + seconds
            window["sidecar_first"] = sidecar_control.try_call("health") or {}
            window["sidecar_first_at"] = time.monotonic()
            readings["setup_s"] = t0 - t_start
            tenant.start()
            if tracer is not None:
                tracer.start()
            if witness == "listener_pause":
                threading.Thread(
                    target=_listener_pause, name="bench-witness", daemon=True,
                    args=(ControlClient((spec.replicas[1].host,
                                         spec.replicas[1].control_port),
                                        timeout=5.0),
                          poller, window, f + 1, seconds)
                ).start()

        if mix["mode"] == "closed":
            sent = _closed_loop(mix, size, requests, send, poller, window,
                                open_window, control=control)
            readings["late_s"] = []
        else:
            sent = _open_loop(mix, requests, send, window, open_window, readings,
                              control=control)
        window["sidecar_last"] = sidecar_control.try_call("health") or {}
        window["sidecar_last_at"] = time.monotonic()
        if tenant.ident is not None:
            tenant.join(timeout=max(120.0, size["request_timeout"]) + 5.0)
        readings["window_waves"] = window_waves
        readings["sent"] = sent
        readings["ran_dry"] = bool(window.get("ran_dry"))

        # ---- drain: everything sent commits everywhere, or the time-out --
        drain_deadline = time.monotonic() + mix["drain_timeout_s"]
        while poller.low < sent and time.monotonic() < drain_deadline:
            time.sleep(0.02)
        readings["end_of_drain"] = time.monotonic()
        # The count is of deliveries, so a request delivered twice brings it
        # to ``sent`` early.  The audit waits until the replicas agree and
        # have stood still for a few decision times, so that it reads what
        # each delivered in the end and not a ledger still growing.
        still_s = max(1.0, 4.0 * poll.median_commit_gap(poller.samples, f + 1))
        while time.monotonic() < drain_deadline:
            recent = [c for t, c in poller.samples[-200:]
                      if t >= time.monotonic() - still_s]
            if len(recent) >= 3 and all(c == [recent[0][0]] * n for c in recent):
                break
            time.sleep(0.05)
        poller.stop()
        samples, decisions = poller.snapshot()
        readings.update(samples=samples, decisions=decisions,
                        leaders=list(poller.leaders),
                        poll_calls_failed=poller.calls_failed, window=window)
        if tracer is not None:
            tracer.join(timeout=330.0)
        readings["memory"] = watcher.call("memory")

        # ---- audit: delivered exactly once, identically (phase 2) --------
        readings["audits"] = {
            node_id: control_.try_call("delivered") or {}
            for node_id, control_ in controls.items()}
        launcher.observe_invariants()
        readings["invariants_clean"] = bool(launcher.monitor.clean)
        readings["sent_requests"] = requests[:sent]

        # ---- the device did it (phase 3) ---------------------------------
        settled, seen, t_settle = 0, None, time.monotonic()
        while settled < 2 and time.monotonic() - t_settle < 30.0:
            now = (launcher.sidecars["sc-0"].probe() or {}).get("offered")
            settled = settled + 1 if now == seen else 0
            seen = now
            time.sleep(0.25)
        health = launcher.health()
        readings["sidecar"] = health.get("sc-0") or {}
        readings["clients"] = {
            name: (h or {}).get("sidecar") or {}
            for name, h in health.items() if name.startswith("replica-")}
        readings["sidecar_restarts"] = launcher.sidecars["sc-0"].restarts

        # ---- verdicts: one full-width wave with every rejection class, ----
        # ---- through the connection the window's waves went through -----
        lanes = int(readings["sidecar"].get("lanes") or 0)
        wave, planted = reference.verdict_wave(lanes, seed)
        got = verdict_client.verify_batch(*wave)
        after = launcher.sidecars["sc-0"].probe() or {}
        readings["wave"] = {
            "lanes": lanes, "planted": planted, "got": [bool(v) for v in got],
            "wave": wave,
            "device_signatures_after": after.get("device_signatures"),
            "compiles_after_ready": after.get("compiles_after_ready")}
        if trace:
            readings["trace_reduced"] = watcher.call(
                "reduce", timeout=240.0, dir=trace_dir,
                kernel_match=config.get("kernel_match", ""))
    finally:
        if poller is not None:
            poller.stop()
        if comm is not None:
            comm.stop()
        if verdict_client is not None:
            verdict_client.close()
        # What each process last wrote to its standard error, for a run that
        # comes out not correct (the supervisor keeps the last 60 lines).
        readings["stderr_tails"] = {
            sup.name: [str(line) for line in list(getattr(sup, "_tail", []))]
            for sup in list(launcher.replicas.values())
            + list(launcher.sidecars.values())}
        try:
            summary = launcher.stop()
            readings["teardown"] = {"ok": True, "orphans": summary["orphans"],
                                    "leaked_ports": summary["leaked_ports"]}
        except AssertionError as exc:
            readings["teardown"] = {"ok": False, "error": str(exc)}
        shutil.rmtree(out_dir, ignore_errors=True)
    return readings


def _trace_window(watcher: Watcher, sidecar_control, trace_dir: str,
                  readings: dict, seconds: float, window: dict) -> None:
    """A profiler window at the end of the timed one.  Its length is fixed
    before it opens (no stopping on what it sees): TRACE_LAUNCHES over the
    launch rate so far, at most TRACE_MAX_SECONDS and half the window."""
    t0, t1 = window["t0"], window["t1"]
    longest = min(TRACE_MAX_SECONDS, seconds / 2.0)
    time.sleep(max(0.0, t1 - longest - 0.5 - time.monotonic()))
    health = sidecar_control.try_call("health") or {}
    launches = (health.get("launches_after_ready", 0)
                - window["sidecar_first"].get("launches_after_ready", 0))
    rate = launches / max(1e-3, time.monotonic() - t0)
    length = min(longest, TRACE_LAUNCHES / rate) if rate > 0 else longest
    time.sleep(max(0.0, t1 - length - 0.15 - time.monotonic()))
    t_call = time.monotonic()
    started = watcher.call("trace_start", dir=trace_dir)
    t_open = time.monotonic()
    time.sleep(max(0.0, t1 - time.monotonic()))
    t_close = time.monotonic()
    stopped = watcher.call("trace_stop", timeout=300.0) if started.get("ok") else {}
    readings["trace_calls"] = {
        "started": started, "stopped": stopped, "launch_rate_per_s": rate,
        "asked_s": length, "traced_s": t_close - t_open,
        "start_call_s": t_open - t_call,
        "stop_call_s": time.monotonic() - t_close}


def _closed_loop(mix, size, requests, send, poller, window, open_window, *,
                 control: str) -> int:
    """chip_smoke's injector: at most ``outstanding_batches`` proposals' worth
    sent and not yet committed by the slowest replica, in chunks, so a full
    batch always waits while one is in flight and no pool parks or drops.
    The window opens at the commit instant that ends the warm batches."""
    batch = size["batch"]
    outstanding = mix["outstanding_batches"] * batch
    chunk = max(1, batch // mix["chunks_per_batch"])
    warm = mix["warm_batches"] * batch
    sent, replayed, checked = 0, False, 0
    t_delivered = None  # when every replica had delivered the first chunk
    while True:
        now = time.monotonic()
        if "t0" not in window:
            # the poll instant at which a quorum first reported the warm count
            while checked < len(poller.samples) and "t0" not in window:
                t, counts = poller.samples[checked]
                checked += 1
                if poll.quorum_count(counts, size["f"] + 1) >= warm:
                    open_window(t)
                    window["first_rank"] = sent  # first sent in the window
        elif now >= window["t1"]:
            break
        low = poller.low
        if control == "replay" and not replayed:
            if t_delivered is None and low >= chunk:
                t_delivered = now
            elif t_delivered is not None and now - t_delivered > 6.0:
                send(0, chunk)  # delivered > 5 s ago: the pool has forgotten
                replayed = True
        while sent < len(requests) and sent - low < outstanding:
            send(sent, sent + chunk)
            sent = min(len(requests), sent + chunk)
        if sent >= len(requests):
            window["ran_dry"] = True  # pre-signed too few: the run is void
            break
        time.sleep(0.005)
    window.setdefault("t0", now)
    window.setdefault("t1", now)
    window.setdefault("first_rank", sent)
    window["last_rank"] = sent
    return sent


def _open_loop(mix, requests, send, window, open_window, readings, *,
               control: str) -> int:
    """Constant rate: request ``i`` is due ``i / rate`` after the first,
    whatever the system does.  Each is timed from when it was due; how late
    it was really sent is kept beside it."""
    rate, tick = float(mix["rate_per_s"]), float(mix["tick_s"])
    s0 = time.monotonic() + 0.05
    t0 = s0 + mix["warm_s"]
    late, sent, total, replayed = [], 0, len(requests), False
    first_rank = min(total, math.ceil(mix["warm_s"] * rate))  # first due at t0
    while sent < total:
        now = time.monotonic()
        if "t0" not in window and now >= t0:
            open_window(t0)
            window["first_rank"] = first_rank
        if control == "replay" and not replayed and now - s0 > 8.0:
            send(0, int(rate * tick) + 1)  # due 8 s ago: long delivered, forgotten
            replayed = True
        due = min(total, int((now - s0) * rate) + 1) if now >= s0 else 0
        if due > sent:
            send(sent, due)
            done = time.monotonic()
            late.extend(done - (s0 + i / rate) for i in range(sent, due))
            sent = due
        time.sleep(max(0.0, tick - (time.monotonic() - now)))
    if "t0" not in window:
        open_window(t0)
        window["first_rank"] = first_rank
    window["last_rank"] = total
    window["s0"] = s0
    readings["late_s"] = late[first_rank:]
    # the window closes when its last request was due, not when it was sent
    time.sleep(max(0.0, window["t1"] - time.monotonic()))
    return sent
