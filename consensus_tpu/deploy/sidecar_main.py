"""Sidecar verifier process entry for the deployment rig.

``python -m consensus_tpu.deploy.sidecar_main --config cluster.json
--sidecar-id sc-K`` serves signature verification over authenticated TCP
(:class:`~consensus_tpu.net.sidecar.VerifySidecarServer`).  It is the ONE
process of a rig that may open a JAX backend — on a machine with a chip,
the process that holds it: replicas, the driver and the orchestrator are
pinned to the CPU by the launcher, the sidecar's platform is left to the
environment.

Boot order is the contract ``launcher.start`` relies on:

1. open the backend.  Unless ``JAX_PLATFORMS`` pins the process to the CPU
   (the tier-1 rigs), anything but a TPU is refused with one line and a
   non-zero exit — a sidecar that silently served from the host would let
   replicas commit happily while the chip did nothing;
2. build the engine the spec selects (``engine_for_config`` over
   ``spec.make_configuration``) behind the thread coalescer, with the
   ladder of launch widths the spec implies (:func:`launch_widths`): the
   full wave
   (:meth:`~consensus_tpu.deploy.spec.ClusterSpec.sidecar_wave_lanes`), the
   half of it, and the quarter where that is still a launch worth
   compiling.  A wave launches at the narrowest width that holds it (the
   device's time and the host's layout work follow the padded width, and
   most waves are under half the full one); an engine that launches at one
   width takes the full one, as before;
3. compile each width by pushing a warm-up wave that selects it through
   the coalescer, one after the other, so every compile — like every later
   launch — runs on the flusher thread and no two threads of this process
   ever compile at once.  Tracing and lowering are not compiling: the
   flusher compiles every width at the first wave, and while it loads the
   first width's executable from the persistent cache (the longest item of
   a warm start; no Python lock) a helper thread traces and lowers the
   later ones;
4. only then open the verify and control sockets and print ``ready``.

Whether small waves go to the host is the spec's existing decision
(``crypto_tpu_min_batch`` in ``config_overrides``).  A rig that wants a
host-only sidecar — the tier-1 rigs, the process-chaos soak — sets it
above any wave: such a sidecar opens no backend, compiles nothing, reports
``platform: "host"`` and never competes for the chip.

The control socket's ``health`` reports what a run needs to prove the
device did the work: ``platform`` / ``device_kind`` / ``device_count``, the
kernel ledger (launches, compiles, compiles since ready), signatures and
lanes launched on the device (``device_lanes``: the sum of the widths that
ran; ``launches_by_lanes`` / ``signatures_by_lanes``: how many launches
rode each width since ready, and how many signatures they carried;
``field_path_by_lanes``: whether each compiled width's field arithmetic runs
as Mosaic kernels or as XLA code), signatures served from the host, the
coalescer's ``device_suspect`` flag and its degrade count, the flusher
thread's phase ledger (``flusher``: nanoseconds per phase, queue wait and
flushes by fill, :mod:`consensus_tpu.obs.kernels`) — plus the wave
counters (offered/rejected) and ``engine_degraded`` the autoscaler reads,
and a ``degrade`` chaos arm that makes the engine wrapper report degraded
without changing verdicts.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import threading
import time

#: Exit code of a sidecar that found no TPU and was not pinned to the CPU,
#: or whose warm-up wave never came back from the device.
EXIT_NO_DEVICE = 3
#: Budget for the cold compile of one launch shape (the launcher's own
#: ``start`` deadline is usually the tighter bound).
WARMUP_TIMEOUT = 900.0


#: The narrowest quarter rung a ladder takes: a launch's fixed cost shows from
#: here down (PERF.md section 5: 13.3 us a lane at 256 lanes, 9.6 at 4,096),
#: so a narrower rung buys under a ms and costs a start one more executable.
_NARROWEST_QUARTER = 256


def launch_widths(lanes: int) -> tuple:
    """The launch widths a sidecar whose full wave is ``lanes`` compiles,
    ascending: the half and the whole, and the quarter where it is at least
    ``_NARROWEST_QUARTER`` lanes (2,048 / 4,096 / 8,192 at n=7 with 1,000
    requests a proposal, 256 / 512 at n=4 with 100)."""
    quarter = (lanes // 4,) if lanes // 4 >= _NARROWEST_QUARTER else ()
    return quarter + (lanes // 2, lanes)


class _CountingEngine:
    """Engine wrapper under the coalescer: books every wave by where it
    ran (a device launch, at the width the engine says it pads that wave
    to, or the engine's host path) and honors a chaos-armed degraded flag
    (verdicts never change — degraded is a health report, not a
    correctness state)."""

    def __init__(self, inner, *, min_device_batch: int, lanes: int) -> None:
        self._inner = inner
        self._min_device_batch = min_device_batch
        self._lanes = lanes
        self.offered = 0
        self.device_waves = 0
        self.device_signatures = 0
        self.device_lanes = 0
        self.launches_by_lanes: dict[int, int] = {}
        self.signatures_by_lanes: dict[int, int] = {}
        self.host_signatures = 0
        self.degraded = False
        self._compile_ahead = None
        self._lock = threading.Lock()

    def launch_width(self, n: int) -> int:
        """The width a device wave of ``n`` launches at, asked of the
        engine; one that does not say launches at the one width it was
        built with."""
        ask = getattr(self._inner, "launch_width", None)
        return self._lanes if ask is None else ask(n)

    def compile_ahead_of_next_wave(self, sizes) -> None:
        """Have the thread that brings the next wave — the coalescer's
        flusher — compile the width each wave of ``sizes`` rides before it
        launches that wave (the engine's ``compile_ahead``)."""
        self._compile_ahead = list(sizes)

    def verify_batch(self, messages, signatures, public_keys):
        sizes, self._compile_ahead = self._compile_ahead, None
        if sizes:
            try:
                self._inner.compile_ahead(sizes)
            except Exception:  # only a head start: the waves compile too
                logging.getLogger("consensus_tpu.deploy").exception(
                    "compiling ahead of the warm-up waves failed"
                )
        n = len(messages)
        with self._lock:
            self.offered += n
            if n >= self._min_device_batch:
                width = self.launch_width(n)
                self.device_waves += 1
                self.device_signatures += n
                self.device_lanes += width
                self.launches_by_lanes[width] = (
                    self.launches_by_lanes.get(width, 0) + 1
                )
                self.signatures_by_lanes[width] = (
                    self.signatures_by_lanes.get(width, 0) + n
                )
            else:
                self.host_signatures += n
        return self._inner.verify_batch(messages, signatures, public_keys)

    def verify_host(self, messages, signatures, public_keys):
        with self._lock:
            self.offered += len(messages)
            self.host_signatures += len(messages)
        return self._inner.verify_host(messages, signatures, public_keys)

    def counts(self) -> dict:
        with self._lock:
            return {
                "offered": self.offered,
                "device_waves": self.device_waves,
                "device_signatures": self.device_signatures,
                "device_lanes": self.device_lanes,
                "launches_by_lanes": dict(self.launches_by_lanes),
                "signatures_by_lanes": dict(self.signatures_by_lanes),
                "host_signatures": self.host_signatures,
            }

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _warm_wave(n: int):
    """``n`` honest signatures under one fixed key (content is irrelevant
    to the compile; they must only verify)."""
    from consensus_tpu.models import Ed25519Signer

    signer = Ed25519Signer(0, private_key_bytes=b"\x17" * 32)
    msgs = [b"ctpu/sidecar-warm/%d" % i for i in range(n)]
    return msgs, [signer.sign_raw(m) for m in msgs], [signer.public_bytes] * n


def _warm(coalescer, n: int):
    """One warm-up wave of ``n`` signatures through the flusher thread:
    ``None``, or what went wrong."""
    try:
        ok = coalescer.warm(*_warm_wave(n), timeout=WARMUP_TIMEOUT)
    except (TimeoutError, RuntimeError) as exc:
        return repr(exc)
    if ok.all() and not coalescer.device_suspect:
        return None
    return (
        f"all valid: {bool(ok.all())}, "
        f"device_suspect: {coalescer.device_suspect}"
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--sidecar-id", required=True)
    args = ap.parse_args()

    logging.basicConfig(
        level=logging.INFO,
        stream=sys.stderr,
        format=f"[{args.sidecar_id}] %(name)s %(levelname)s %(message)s",
    )

    from consensus_tpu.deploy.control import ControlServer
    from consensus_tpu.deploy.spec import ClusterSpec

    spec = ClusterSpec.load(args.config)
    me = spec.sidecar(args.sidecar_id)

    from consensus_tpu.models import ThreadCoalescingVerifier, engine_for_config
    from consensus_tpu.net.sidecar import VerifySidecarServer
    from consensus_tpu.obs.kernels import FLUSHER, KERNELS

    # --- the engine the spec selects, at the widths it implies -------------
    config = spec.make_configuration(spec.node_ids()[0])
    lanes = spec.sidecar_wave_lanes()
    widths = launch_widths(lanes)
    min_device_batch = config.crypto_tpu_min_batch
    #: The spec routes every wave to the host: no backend is opened and no
    #: kernel compiled, so this process never competes for the chip.
    host_only = min_device_batch > lanes

    device_report = {"platform": "host", "device_kind": "", "device_count": 0,
                     "cache_dir": ""}
    backend_secs = 0.0
    if not host_only:
        import jax

        from consensus_tpu.parallel.topology import apply_compile_cache

        cache_dir = apply_compile_cache()
        pinned_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
        t_boot = time.monotonic()  # wallclock-ok
        devices = jax.devices()
        backend_secs = time.monotonic() - t_boot  # wallclock-ok
        device_report = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "cache_dir": cache_dir,
        }
        if devices[0].platform != "tpu" and not pinned_cpu:
            print(
                f"{args.sidecar_id}: no TPU for this process (jax found "
                f"platform={devices[0].platform!r}) and JAX_PLATFORMS does "
                "not pin it to cpu — refusing to serve verification from "
                "the host",
                file=sys.stderr, flush=True,
            )
            return EXIT_NO_DEVICE

    engine = _CountingEngine(
        engine_for_config(config, pad_to=widths),
        min_device_batch=min_device_batch,
        lanes=lanes,
    )
    # hard_cap = the widest compiled shape: no launch can need another one.
    coalescer = ThreadCoalescingVerifier(
        engine,
        window=config.crypto_batch_window,
        max_batch=lanes,
        hard_cap=lanes,
        bypass_below=min_device_batch,
        name=f"{args.sidecar_id}-flusher",
    )

    # --- compile before ready, on the flusher thread ----------------------
    warm_secs = 0.0
    warmed: list[int] = []
    if not host_only:
        t0 = time.monotonic()  # wallclock-ok
        # The warm-up waves, widest first: for each width the smallest
        # device wave that needs it.  Each proves the width it rides; a
        # one-width engine rides the same every time: one wave.
        sizes: list[int] = []
        for below in (*reversed(widths[:-1]), 0):
            n = max(below + 1, min_device_batch)
            if not sizes or engine.launch_width(n) != engine.launch_width(sizes[-1]):
                sizes.append(n)
        if len(sizes) > 1:
            # Several widths: the first wave's flush compiles them all
            # before it launches, the later ones' trace and lowering hidden
            # under the first one's load (Ed25519BatchVerifier.compile_ahead).
            engine.compile_ahead_of_next_wave(sizes)
        failure = None
        for n in sizes:
            failure = _warm(coalescer, n)
            if failure is not None:
                break
            warmed.append(engine.launch_width(n))
        warm_secs = time.monotonic() - t0  # wallclock-ok
        if failure is not None:
            print(
                f"{args.sidecar_id}: warm-up wave did not come back valid "
                f"from the device ({failure}) — refusing to serve",
                file=sys.stderr, flush=True,
            )
            return EXIT_NO_DEVICE
    ready_ledger = KERNELS.totals()
    ready_counts = engine.counts()
    # The lane each compiled width's field arithmetic took (ops/mosaic25519.py).
    ask_path = getattr(engine, "field_path", None)
    field_paths = {
        str(width): ask_path(width) for width in warmed if ask_path is not None
    }
    logging.getLogger("consensus_tpu.deploy").info(
        "backend %s up in %.1fs, launch widths %s warm in %.1fs",
        device_report, backend_secs, warmed, warm_secs,
    )

    server = VerifySidecarServer(
        (me.host, me.port), coalescer, auth_secret=spec.auth_secret
    )
    server.start()

    stop_event = threading.Event()

    def _health(_request) -> dict:
        ledger = KERNELS.totals()
        counts = engine.counts()
        return {
            "ok": True,
            "role": "sidecar",
            "sidecar_id": args.sidecar_id,
            "pid": os.getpid(),
            **device_report,
            "lanes": lanes,
            "min_device_batch": min_device_batch,
            "backend_secs": round(backend_secs, 3),
            "warm_compile_secs": round(warm_secs, 3),
            "kernels": KERNELS.snapshot(),
            "launches": ledger["launches"],
            "compiles": ledger["compiles"],
            "compiles_after_ready": ledger["compiles"] - ready_ledger["compiles"],
            # Traffic only: the warm-up wave is booked apart.
            "launches_after_ready": ledger["launches"] - ready_ledger["launches"],
            "device_waves": counts["device_waves"] - ready_counts["device_waves"],
            "device_signatures": (
                counts["device_signatures"] - ready_counts["device_signatures"]
            ),
            "device_lanes": counts["device_lanes"] - ready_counts["device_lanes"],
            # How often a wave rode each compiled width, since ready.
            "launches_by_lanes": {
                str(width): n - ready_counts["launches_by_lanes"].get(width, 0)
                for width, n in sorted(counts["launches_by_lanes"].items())
            },
            # The signatures those waves carried, width by width.
            "signatures_by_lanes": {
                str(width): n - ready_counts["signatures_by_lanes"].get(width, 0)
                for width, n in sorted(counts["signatures_by_lanes"].items())
            },
            "field_path_by_lanes": field_paths,
            "host_signatures": counts["host_signatures"],
            "device_suspect": coalescer.device_suspect,
            "degrade_count": coalescer.health.suspect_marks,
            # What the flusher thread did with its time, and how full its
            # flushes were: cumulative since process start.
            "flusher": FLUSHER.snapshot(),
            # Autoscaler signals.
            "offered": counts["offered"],
            "rejected": 0,
            "engine_degraded": engine.degraded,
        }

    def _degrade(request) -> dict:
        engine.degraded = bool(request.get("degraded", True))
        return {"ok": True, "engine_degraded": engine.degraded}

    control = ControlServer(
        {
            "ping": lambda r: {"ok": True, "pid": os.getpid(),
                               "role": "sidecar",
                               "sidecar_id": args.sidecar_id},
            "health": _health,
            "degrade": _degrade,
            "exit": lambda r: (stop_event.set(), {"ok": True})[1],
        },
        host=me.host,
        port=me.control_port,
    )
    print(json.dumps({"ready": True, "sidecar_id": args.sidecar_id,
                      "pid": os.getpid(),
                      "platform": device_report["platform"]}),
          flush=True)

    while not stop_event.wait(0.5):
        pass

    server.stop()
    coalescer.close()
    control.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
