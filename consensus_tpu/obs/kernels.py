"""Device/kernel accounting: compiles, retraces, launches, and cost
estimates per jit entry point.

:func:`instrumented_jit` replaces the bare ``jax.jit(fn)`` at the module
level of the signature models (ed25519 verify / batch-verify, ecdsa-p256
verify).  The wrapper is transparent — same signature, same outputs — and
on every call records into the process-wide :data:`KERNELS` registry:

* ``launches``   — calls into the jitted function;
* ``compiles``   — jit cache growth observed across calls (via the private
  but long-stable ``_cache_size`` probe; gracefully 0 if it disappears);
* ``retraces``   — compiles beyond the first, i.e. shape/dtype churn;
* ``flops`` / ``bytes_accessed`` — XLA's cost estimates of the compiled
  executable, captured at first compile per kernel.  The call that grew
  the jit cache has traced, lowered and compiled the shape, and jax keeps
  all three: ``lower(...).compile()`` right after it hands back that very
  executable, so each shape is lowered ONCE.  (``Lowered.cost_analysis()``,
  read here before, never lowered twice either, but on the TPU backend it
  answers ``None`` — both numbers stayed empty on the chip — and on the
  CPU backend it converts the whole module again, seconds for a verify
  kernel.)

The registry is surfaced as the ``kernels`` block of the rig sidecar's
``health`` (``deploy/sidecar_main.py``), which served_bench's readers and
chip_smoke.py's lane census read.

jax is imported lazily inside the wrapper so importing consensus_tpu.obs
never drags in the accelerator stack (the sim plane must stay importable
on boxes without jax).

:func:`phase` and the :data:`FLUSHER` ledger say what the thread that feeds
the device does between launches: the sidecar's flusher thread
(``models/engine.py::ThreadCoalescingVerifier._loop`` and, under it,
``models/ed25519.py::Ed25519BatchVerifier.verify_batch``) cuts its life
into named phases, each a ``jax.profiler.TraceAnnotation`` on the
profiler's own clock (so a device trace names its idle gaps by phase) and
a sum of nanoseconds in the ledger, which the sidecar's ``health``
returns under ``flusher``.
"""

from __future__ import annotations

import threading
import time
from typing import Optional


class KernelStats:
    """Mutable per-kernel counters."""

    __slots__ = ("name", "launches", "compiles", "flops", "bytes_accessed")

    def __init__(self, name: str) -> None:
        self.name = name
        self.launches = 0
        self.compiles = 0
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None

    @property
    def retraces(self) -> int:
        return max(0, self.compiles - 1)

    def as_dict(self) -> dict:
        return {
            "launches": self.launches,
            "compiles": self.compiles,
            "retraces": self.retraces,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
        }


class KernelRegistry:
    """Process-wide map of kernel name -> :class:`KernelStats`."""

    def __init__(self) -> None:
        self._stats: dict[str, KernelStats] = {}

    def stats(self, name: str) -> KernelStats:
        st = self._stats.get(name)
        if st is None:
            st = self._stats[name] = KernelStats(name)
        return st

    def snapshot(self) -> dict:
        """``{kernel: {launches, compiles, retraces, flops, bytes_accessed}}``,
        sorted, JSON-ready.  Empty dict when nothing has launched."""
        return {
            name: self._stats[name].as_dict() for name in sorted(self._stats)
        }

    def totals(self) -> dict:
        snap = self.snapshot()
        return {
            "launches": sum(s["launches"] for s in snap.values()),
            "compiles": sum(s["compiles"] for s in snap.values()),
            "retraces": sum(s["retraces"] for s in snap.values()),
        }

    def reset(self) -> None:
        self._stats.clear()


#: The process-wide registry the sidecar's ``health`` snapshots.
KERNELS = KernelRegistry()


class TenantAccounting:
    """Per-tenant slice of the sidecar's kernel work: which tenant's
    signatures rode which share of the engine launches.

    The multi-tenant sidecar coalesces many tenants' submissions into one
    wave, so :data:`KERNELS` alone can no longer attribute device time to a
    tenant; the wave former reports each launch here instead.  ``waves``
    counts launches the tenant participated in (a shared wave counts once
    per PARTICIPANT, so summing waves over tenants exceeds engine launches
    exactly when coalescing is winning)."""

    def __init__(self) -> None:
        self._tenants: dict[str, dict] = {}

    def record_wave(self, tenant: str, signatures: int) -> None:
        t = self._tenants.get(tenant)
        if t is None:
            t = self._tenants[tenant] = {"waves": 0, "signatures": 0}
        t["waves"] += 1
        t["signatures"] += signatures

    def snapshot(self) -> dict:
        """``{tenant: {waves, signatures}}``, sorted, JSON-ready."""
        return {
            tenant: dict(self._tenants[tenant])
            for tenant in sorted(self._tenants)
        }

    def reset(self) -> None:
        self._tenants.clear()


#: Process-wide tenant accounting fed by the sidecar wave former.
TENANT_KERNELS = TenantAccounting()


class CompileCacheStats:
    """Hit/miss ledger for the in-process compiled-kernel memo
    (parallel/sharding.py ``compiled_kernel``).

    A *hit* means an engine construction reused an already-traced jit
    wrapper — the retrace storm a fleet restart or tenant churn would have
    paid; a *miss* is a fresh build (first construction of that
    ``(kernel, topology[, shape])`` key, or the memo disabled via
    ``CompileCacheConfig.enabled=False``).  Surfaced through the node
    metrics bundle as ``engine_compile_cache_{hits,misses}_total``.
    """

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0

    def record(self, *, hit: bool) -> None:
        if hit:
            self.hits += 1
        else:
            self.misses += 1

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses}

    def reset(self) -> None:
        self.hits = 0
        self.misses = 0


#: Process-wide compiled-kernel memo ledger (fed by parallel/sharding.py).
COMPILE_CACHE = CompileCacheStats()


#: The flusher thread's phases: exclusive, sequential, never nested.  The
#: ledger holds the nanoseconds spent in each, by ``time.monotonic_ns``.
FLUSHER_PHASES = (
    "wave.wait_work",    # _cv.wait() with nothing pending (starved)
    "wave.wait_window",  # the coalescing window, and a hold past it
    "wave.take",         # _take_batch and the list joins
    "wave.deliver",      # split, done.set() (a failed flush's host serving)
    "verify.prepare",    # Ed25519BatchVerifier._prepare
    "verify.layout",     # pack_wave + the ONE host->device copy
    "verify.dispatch",   # the _verify_kernel(...) call
    "verify.await",      # np.asarray(result): the wait for the launch
)
#: Counted beside them: thread CPU time inside ``verify.prepare``, the whole
#: of ``engine.verify_batch`` as the flusher sees it, what submissions waited
#: between ``_enqueue`` and ``_take_batch``, each flush by how full it was
#: of ``hard_cap`` (<= 25%, <= 50%, <= 75%, <= 100%), and the flushes the
#: wave former held past its window for the rest of a burst: those the
#: burst then filled (``hold_met``), those the hold ran out on
#: (``hold_expired``), and the nanoseconds each of the two MIGHT have lasted
#: (``hold_reach_ns``: over their count, the reach a hold).  The held time
#: itself is ``wave.wait_window``'s.
FLUSHER_COUNTERS = (
    "verify.prepare_cpu", "engine_ns", "queue_wait_ns", "submissions",
    "flushes", "fill_le_25", "fill_le_50", "fill_le_75", "fill_le_100",
    "hold_met", "hold_expired", "hold_reach_ns",
)


class PhaseLedger:
    """Process-wide sums under a fixed set of keys: integers, cumulative
    since process start, never decreasing."""

    def __init__(self, keys) -> None:
        self._lock = threading.Lock()
        self._sums = dict.fromkeys(keys, 0)

    def add(self, key: str, amount: int) -> None:
        with self._lock:
            self._sums[key] += amount

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._sums)


#: What the sidecar's flusher thread did with its time (sidecar ``health``
#: returns it under ``flusher``).
FLUSHER = PhaseLedger(FLUSHER_PHASES + FLUSHER_COUNTERS)

#: ``jax.profiler.TraceAnnotation``, resolved at the first :func:`phase`
#: (``None``: not yet; ``False``: no jax here).
_ANNOTATION = None


def _annotation_type():
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class phase:
    """``with phase(name):`` — one phase of the flusher thread.  Opens a
    ``jax.profiler.TraceAnnotation(name)`` (the bare name: a trace's idle
    gaps are grouped by it) and adds the elapsed ``time.monotonic_ns()`` to
    :data:`FLUSHER` under ``name``; with ``cpu=True`` also the elapsed
    ``time.thread_time_ns()`` under ``name + "_cpu"``, so the share of the
    phase in which the thread held no core can be read.  Without jax, or
    with no profiler running, it is the clock reads.  Use it on the thread
    that feeds the device only: a span on a thread that merely waits for
    that one would cover the same gaps and hide the phases."""

    __slots__ = ("_name", "_cpu", "_annotation", "_t0", "_cpu0")

    def __init__(self, name: str, *, cpu: bool = False) -> None:
        self._name = name
        self._cpu = cpu

    def __enter__(self) -> "phase":
        annotation = _annotation_type()
        self._annotation = annotation(self._name) if annotation else None
        if self._annotation is not None:
            self._annotation.__enter__()
        if self._cpu:
            self._cpu0 = time.thread_time_ns()
        self._t0 = time.monotonic_ns()  # wallclock-ok: a duration, not a timestamp
        return self

    def __exit__(self, *exc_info) -> None:
        elapsed = time.monotonic_ns() - self._t0  # wallclock-ok
        if self._cpu:
            FLUSHER.add(self._name + "_cpu", time.thread_time_ns() - self._cpu0)
        FLUSHER.add(self._name, elapsed)
        if self._annotation is not None:
            self._annotation.__exit__(*exc_info)


def _cache_size(jitted) -> int:
    try:
        return int(jitted._cache_size())
    except Exception:
        return 0


def _cost_number(analysis, key: str) -> Optional[float]:
    if not isinstance(analysis, dict):  # a backend may report no analysis
        return None
    v = analysis.get(key)
    return float(v) if v is not None else None


def kernel_lane_suffix() -> str:
    """``"_mxu"`` when the process runs the MXU field lane
    (``CTPU_MXU_LIMBS=1``), else ``""``.

    Engine modules append this to their ``instrumented_jit`` names at
    import time, so an MXU-lane run's launches/compiles/cost_analysis land
    under ``ed25519.verify_mxu`` etc. instead of overwriting the headline
    VPU ledger keys — the device A/B reads both side by side."""
    import os

    return "_mxu" if os.environ.get("CTPU_MXU_LIMBS", "") == "1" else ""


def instrumented_jit(
    fn, name: str, *, registry: Optional[KernelRegistry] = None, **jit_kwargs
):
    """``jax.jit(fn, **jit_kwargs)`` plus accounting under ``name``.  Behaves
    exactly like the jitted function; every failure inside the accounting is
    swallowed so instrumentation can never break a verify path.  Extra
    keyword arguments pass straight to ``jax.jit`` (the fused engines donate
    their input buffers).  Wrappers may share a ``name`` — stats accumulate
    into one bucket, which is how the shape-specialized fused aggregate
    graphs report as a single kernel."""
    import jax

    jitted = jax.jit(fn, **jit_kwargs)
    reg = registry if registry is not None else KERNELS

    def wrapper(*args, **kwargs):
        st = reg.stats(name)
        st.launches += 1
        before = _cache_size(jitted)
        out = jitted(*args, **kwargs)
        grew = _cache_size(jitted) - before
        if grew > 0:
            st.compiles += grew
            if st.flops is None:
                try:
                    # Cache hits all the way: the executable the call
                    # above built, and its own estimates.
                    analysis = (
                        jitted.lower(*args, **kwargs).compile().cost_analysis()
                    )
                    st.flops = _cost_number(analysis, "flops")
                    st.bytes_accessed = _cost_number(analysis, "bytes accessed")
                except Exception:
                    pass
        return out

    wrapper.__name__ = f"instrumented_{name}"
    wrapper.__wrapped__ = jitted
    return wrapper


__all__ = [
    "COMPILE_CACHE",
    "CompileCacheStats",
    "FLUSHER",
    "FLUSHER_COUNTERS",
    "FLUSHER_PHASES",
    "KERNELS",
    "KernelRegistry",
    "KernelStats",
    "PhaseLedger",
    "TENANT_KERNELS",
    "TenantAccounting",
    "instrumented_jit",
    "kernel_lane_suffix",
    "phase",
]
