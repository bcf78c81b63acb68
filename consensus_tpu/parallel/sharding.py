"""Device-mesh sharding of the crypto batch path.

The verification workload is pure data parallelism: every signature's
double-scalar multiplication is independent, so the natural multi-chip
layout is a mesh with the batch axis sharded across it.  Collectives only
appear at the reduction edge (the validity count / all-valid bit), where a
``psum`` rides the ICI.

Topologies come from :class:`~consensus_tpu.parallel.topology.MeshTopology`:
a 1-D ``(n,)`` spec (the ``mesh_shards=n`` sugar) builds the historical
``("batch",)`` mesh bit-for-bit, while an N-D spec such as ``(2, 4)`` names
its leading axes (``("slice", "batch")``) and shards the batch dimension
over the FULL axis tuple — the per-lane math, padding, and verdicts are
identical at equal device counts; only the device layout the runtime maps
onto the physical interconnect changes.

Two entry points:

* :func:`sharded_verify_fn` — ``shard_map`` of the kernel body over the
  mesh: each device verifies its batch shard; outputs stay sharded
  (gathered lazily by the host when read).
* :class:`ShardedEd25519Verifier` — drop-in
  :class:`~consensus_tpu.models.ed25519.Ed25519BatchVerifier` that pads the
  batch to a multiple of the mesh size and runs the sharded kernel.

Kernel construction rides an in-process ``(kernel, topology)`` ->
compiled-fn memo (:func:`compiled_kernel`): rebuilding an engine — fleet
restart, tenant churn, supervisor ladder reconstruction — reuses the
already-traced jit wrapper instead of paying a retrace storm, which the obs
kernel ledger's compile counter proves (tests/test_mesh.py).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from consensus_tpu.models.ecdsa_p256 import EcdsaP256BatchVerifier
from consensus_tpu.models.ed25519 import (
    Ed25519BatchVerifier,
    Ed25519RandomizedBatchVerifier,
    pack_wave,
    packed_verify_impl,
)
from consensus_tpu.models.fused import FusedEd25519BatchVerifier
from consensus_tpu.obs.kernels import (
    COMPILE_CACHE,
    instrumented_jit,
    kernel_lane_suffix,
)
from consensus_tpu.parallel.topology import (
    BATCH_AXIS,
    MeshTopology,
    engine_padded_size,
    mesh_padded_size,
)

#: Device-layout partition specs: the strict kernel takes ONE packed wave,
#: (129, batch) (models/ed25519.py ``pack_wave``) — batch is the trailing
#: axis.  These are the 1-D templates; :func:`_mesh_specs` widens the batch
#: entry to the full axis-name tuple for N-D topologies.
_IN_SPECS = (P(None, BATCH_AXIS),)


def _reduce_axes(mesh: Mesh):
    """The axis-name argument collectives reduce/gather over: the bare
    ``BATCH_AXIS`` on a 1-D mesh (bit-for-bit the historical graphs), the
    full name tuple on N-D topologies."""
    names = tuple(mesh.axis_names)
    return names[0] if len(names) == 1 else names


def _mesh_specs(mesh: Mesh, specs):
    """Widen 1-D spec templates to ``mesh``: every ``BATCH_AXIS`` entry
    becomes the full axis-name tuple, so the batch dimension is sharded
    across ALL mesh axes (row-major)."""
    names = tuple(mesh.axis_names)
    if names == (BATCH_AXIS,):
        return tuple(specs)
    return tuple(
        P(*[names if part == BATCH_AXIS else part for part in spec])
        for spec in specs
    )


# --- in-process compiled-kernel memo ----------------------------------------

_COMPILED_KERNELS: dict = {}


def _kernel_key(name: str, mesh: Mesh) -> tuple:
    return (
        name,
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def compiled_kernel(
    name: str,
    mesh: Mesh,
    builder: Callable[[], Callable],
    *,
    memo: bool = True,
) -> Callable:
    """The in-process ``(kernel, topology)`` -> compiled-fn memo.

    A jit wrapper's trace cache lives on the wrapper object, so an engine
    that builds a fresh wrapper per construction re-traces every compiled
    shape on rebuild even when XLA's persistent cache skips the backend
    compile.  Two engines over the same mesh run the same computation, so
    the wrapper itself is shared here instead — a rebuilt engine's warmup
    books ZERO new compiles in the kernel ledger.
    Hits/misses book into :data:`consensus_tpu.obs.kernels.COMPILE_CACHE`;
    ``memo=False`` (``CompileCacheConfig.enabled=False``) always builds
    fresh and books a miss.
    """
    if not memo:
        COMPILE_CACHE.record(hit=False)
        return builder()
    key = _kernel_key(name, mesh)
    fn = _COMPILED_KERNELS.get(key)
    if fn is None:
        COMPILE_CACHE.record(hit=False)
        fn = _COMPILED_KERNELS[key] = builder()
    else:
        COMPILE_CACHE.record(hit=True)
    return fn


def clear_compiled_kernels() -> None:
    """Drop every memoized kernel (tests; never needed in production)."""
    _COMPILED_KERNELS.clear()


def make_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over ``devices`` (default: all visible devices)."""
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), (BATCH_AXIS,))


def mesh_for_shards(n_shards: int, devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over the first ``n_shards`` visible devices — the
    ``Configuration.mesh_shards`` -> engine seam, now the 1-D special case
    of :meth:`MeshTopology.build_mesh`.  Fails loudly when the host exposes
    fewer devices than the config demands: silently shrinking the mesh
    would make the one compiled kernel shape depend on deploy-time
    topology."""
    if n_shards < 1:
        raise ValueError(f"mesh_shards must be >= 1, got {n_shards}")
    return MeshTopology((n_shards,)).build_mesh(devices)


class _MeshEngine:
    """Shared mesh plumbing for the sharded engines: topology coercion, the
    memoized kernel seam, and the wave-sizing surface the coalescers read.

    ``mesh`` may be a ``jax.sharding.Mesh`` or a :class:`MeshTopology`
    (built over the visible devices); ``compile_cache=False`` opts this
    engine out of the process-wide compiled-kernel memo."""

    def _init_mesh(
        self,
        mesh: Union[Mesh, MeshTopology, None],
        kernel_name: str,
        builder: Callable[[Mesh], Callable],
        in_specs,
        compile_cache: bool = True,
    ) -> None:
        if isinstance(mesh, MeshTopology):
            mesh = mesh.build_mesh()
        self.mesh = mesh if mesh is not None else make_mesh()
        self._compile_cache = bool(compile_cache)
        self._in_specs = _mesh_specs(self.mesh, in_specs)
        self._fn = compiled_kernel(
            kernel_name,
            self.mesh,
            lambda: builder(self.mesh),
            memo=self._compile_cache,
        )
        self._n_shards = int(self.mesh.devices.size)

    @property
    def shard_count(self) -> int:
        """Devices this engine spreads a batch across.  The engine
        supervisor's degrade ladder labels mesh rungs with it (an
        ``N-shard`` rung degrading to a ``1-shard`` rung reads as exactly
        that in logs/traces rather than two identical class names)."""
        return self._n_shards

    @property
    def preferred_wave_size(self) -> int:
        """The smallest padded wave that saturates the whole topology —
        every shard receives at least ``min_device_batch`` lanes, rounded
        through the engine's padding knobs.  The wave formers
        (models/engine.py) flush early once this many signatures are
        aboard: waiting longer adds latency without adding devices."""
        return engine_padded_size(
            self._n_shards * max(1, self._min_device_batch),
            self._n_shards,
            pad_to=self._pad_to,
        )

    def _put_sharded(self, device_args):
        return [
            jax.device_put(np.asarray(a), NamedSharding(self.mesh, spec))
            for a, spec in zip(device_args, self._in_specs)
        ]


def sharded_verify_fn(mesh: Mesh):
    """A jitted verify over ``mesh``: inputs sharded on the batch axis, plus
    a ``psum``-reduced valid count so the collective path is exercised."""
    axes = _reduce_axes(mesh)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=_mesh_specs(mesh, _IN_SPECS),
        out_specs=_mesh_specs(mesh, (P(BATCH_AXIS), P())),
    )
    def _shard(wave):
        ok = packed_verify_impl(wave)
        total = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), axes)
        return ok, total

    return instrumented_jit(_shard, "ed25519.sharded_verify" + kernel_lane_suffix())


class ShardedEd25519Verifier(_MeshEngine, Ed25519BatchVerifier):
    """Batch verifier that spreads the batch across a device mesh."""

    def __init__(
        self,
        mesh: Union[Mesh, MeshTopology, None] = None,
        *,
        compile_cache: bool = True,
        **kw,
    ) -> None:
        super().__init__(**kw)
        self._init_mesh(
            mesh, "ed25519.sharded_verify", sharded_verify_fn, _IN_SPECS,
            compile_cache,
        )

    def verify_batch(self, messages, signatures, public_keys) -> np.ndarray:
        n = len(messages)
        if not (n == len(signatures) == len(public_keys)):
            raise ValueError("batch length mismatch")
        if n == 0:
            return np.zeros(0, dtype=bool)
        if n < self._min_device_batch:
            return self._verify_host(messages, signatures, public_keys)
        # The base class's host-side preparation, packed at the mesh-aligned
        # width: one sharded copy.
        rows, host_ok = self._prepare(messages, signatures, public_keys)
        padded = engine_padded_size(
            n, self._n_shards, pad_to=self._pad_to
        )
        wave = pack_wave(rows, host_ok, padded)
        ok, _total = self._fn(*self._put_sharded([wave]))
        return np.asarray(ok)[:n]


# --- ECDSA-P256 sharding ---------------------------------------------------

#: Device-layout specs for the P-256 kernel (see models/ecdsa_p256.py):
#: limb/digit arrays lead with their vector axis, batch trails.
_P256_IN_SPECS = (
    P(None, BATCH_AXIS),  # qx
    P(None, BATCH_AXIS),  # qy
    P(None, BATCH_AXIS),  # u1 digits
    P(None, BATCH_AXIS),  # u2 digits
    P(None, BATCH_AXIS),  # r1
    P(None, BATCH_AXIS),  # r2
    P(BATCH_AXIS),        # has_r2
    P(BATCH_AXIS),        # host_ok
)


def sharded_p256_verify_fn(mesh: Mesh):
    """jitted ECDSA-P256 verify over ``mesh`` with a psum valid count."""
    from consensus_tpu.models.ecdsa_p256 import verify_impl as p256_verify_impl

    axes = _reduce_axes(mesh)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=_mesh_specs(mesh, _P256_IN_SPECS),
        out_specs=_mesh_specs(mesh, (P(BATCH_AXIS), P())),
    )
    def _shard(qx, qy, u1d, u2d, r1, r2, has_r2, host_ok):
        ok = p256_verify_impl(qx, qy, u1d, u2d, r1, r2, has_r2, host_ok)
        total = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), axes)
        return ok, total

    return instrumented_jit(_shard, "ecdsa_p256.sharded_verify" + kernel_lane_suffix())


class ShardedEcdsaP256Verifier(_MeshEngine, EcdsaP256BatchVerifier):
    """ECDSA-P256 batch verifier spread across a device mesh (reuses the
    base class's preparation/validation; only the launch path differs)."""

    def __init__(
        self,
        mesh: Union[Mesh, MeshTopology, None] = None,
        *,
        compile_cache: bool = True,
        **kw,
    ) -> None:
        super().__init__(**kw)
        self._init_mesh(
            mesh, "ecdsa_p256.sharded_verify", sharded_p256_verify_fn,
            _P256_IN_SPECS, compile_cache,
        )

    def verify_batch(self, messages, signatures, public_keys) -> np.ndarray:
        from consensus_tpu.models.ecdsa_p256 import pad_prepared, to_kernel_layout

        n = len(messages)
        if not (n == len(signatures) == len(public_keys)):
            raise ValueError("batch length mismatch")
        if n == 0:
            return np.zeros(0, dtype=bool)
        if n < self._min_device_batch:
            return self._verify_host(messages, signatures, public_keys)
        prepped = self._prepare(messages, signatures, public_keys)
        padded = engine_padded_size(
            n, self._n_shards, pad_to=self._pad_to
        )
        device_args = to_kernel_layout(*pad_prepared(prepped, padded))
        ok, _total = self._fn(*self._put_sharded(device_args))
        return np.asarray(ok)[:n]


# --- randomized Ed25519 batch verification over the mesh --------------------

#: Specs for the randomized-aggregate kernel (models/ed25519.py
#: batch_verify_impl): per-lane arrays shard on the batch axis, and the
#: fixed-base comb digits carry ONE (32, 1) column per shard — each shard
#: checks its own aggregate [u_s]B + Σ[zkᵢ](−Aᵢ) + Σ[zᵢ](−Rᵢ) = 0 against
#: its lanes' base-point scalar u_s.
_RAND_IN_SPECS = (
    P(None, BATCH_AXIS),  # y_r
    P(BATCH_AXIS),        # sign_r
    P(None, BATCH_AXIS),  # y_a
    P(BATCH_AXIS),        # sign_a
    P(None, BATCH_AXIS),  # zs_digits8: (32, n_shards), one column per shard
    P(None, BATCH_AXIS),  # zk_digits
    P(None, BATCH_AXIS),  # z_digits
    P(BATCH_AXIS),        # host_ok
)


def sharded_batch_verify_fn(mesh: Mesh):
    """jitted randomized-aggregate verify over ``mesh``.

    Point addition is not componentwise, so the per-shard accumulators can
    NOT be psum'd as coordinates; instead every shard runs an independent
    aggregate check over its own lane subset (each sound to 2^-128 —
    the conjunction is at least as strong as one whole-batch check), and
    the single ``psum`` tree-reduces the per-shard not-identity counts
    into the global verdict.  A padding-only shard contributes u_s = 0 and
    all-masked digits, so its accumulator is the identity and it votes ok.
    """
    from consensus_tpu.models.ed25519 import batch_verify_impl

    axes = _reduce_axes(mesh)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=_mesh_specs(mesh, _RAND_IN_SPECS),
        out_specs=_mesh_specs(mesh, (P(), P(BATCH_AXIS))),
    )
    def _shard(y_r, sign_r, y_a, sign_a, zs_digits8, zk_digits, z_digits, host_ok):
        eq_ok, valid = batch_verify_impl(
            y_r, sign_r, y_a, sign_a, zs_digits8, zk_digits, z_digits, host_ok
        )
        bad = jax.lax.psum(1 - eq_ok.astype(jnp.int32), axes)
        return bad == 0, valid

    return instrumented_jit(
        _shard, "ed25519.sharded_batch_verify" + kernel_lane_suffix()
    )


class ShardedEd25519RandomizedVerifier(_MeshEngine, Ed25519RandomizedBatchVerifier):
    """Randomized batch verifier whose aggregate check rides the mesh.

    Only the device aggregate changes: the bisection driver, transcript
    coefficients, host fallback, and strict-verifier floor are all
    inherited, so verdict semantics (including the SAFETY.md §7 torsion
    caveat) are exactly the single-device engine's.
    """

    def __init__(
        self,
        mesh: Union[Mesh, MeshTopology, None] = None,
        *,
        compile_cache: bool = True,
        **kw,
    ) -> None:
        super().__init__(**kw)
        self._init_mesh(
            mesh, "ed25519.sharded_batch_verify", sharded_batch_verify_fn,
            _RAND_IN_SPECS, compile_cache,
        )

    def _aggregate_device(self, idx, signatures, public_keys, scalars, zs):
        from consensus_tpu.models.ed25519 import (
            _prep_compressed,
            _signed_digits_int,
            _WINDOWS,
            _Z_WINDOWS,
            L,
        )

        m = len(idx)
        zk = [(z * scalars[i][1]) % L for z, i in zip(zs, idx)]
        y_r, sign_r, _ = _prep_compressed([bytes(signatures[i])[:32] for i in idx])
        y_a, sign_a, _ = _prep_compressed([bytes(public_keys[i]) for i in idx])
        zk_digits = np.array(
            [_signed_digits_int(v, _WINDOWS) for v in zk], dtype=np.int16
        ).T
        z_digits = np.array(
            [_signed_digits_int(z, _Z_WINDOWS) for z in zs], dtype=np.int16
        ).T
        zk_digits = (zk_digits + 8).astype(np.uint8)
        z_digits = (z_digits + 8).astype(np.uint8)
        host_ok = np.ones(m, dtype=bool)

        padded = engine_padded_size(
            m, self._n_shards, pad_to=self._pad_to
        )
        if padded != m:
            pad = padded - m
            y_r = np.pad(y_r, ((0, pad), (0, 0)))
            y_a = np.pad(y_a, ((0, pad), (0, 0)))
            sign_r = np.pad(sign_r, (0, pad))
            sign_a = np.pad(sign_a, (0, pad))
            zk_digits = np.pad(zk_digits, ((0, 0), (0, pad)), constant_values=8)
            z_digits = np.pad(z_digits, ((0, 0), (0, pad)), constant_values=8)
            host_ok = np.pad(host_ok, (0, pad))

        # Per-shard fixed-base scalars: lane j lives on shard j // per, so
        # u_s sums z·s over exactly that shard's live lanes.  Pad-only
        # shards get u_s = 0 (identity comb contribution).  Shard order is
        # the linear row-major device order on every topology, so the same
        # slicing covers 1-D and N-D meshes.
        per = padded // self._n_shards
        u_rows = np.zeros((self._n_shards, 32), dtype=np.uint8)
        for s in range(self._n_shards):
            u_s = 0
            for j in range(s * per, min((s + 1) * per, m)):
                u_s += zs[j] * scalars[idx[j]][0]
            u_rows[s] = np.frombuffer(
                (u_s % L).to_bytes(32, "little"), dtype=np.uint8
            )
        zs_digits8 = np.ascontiguousarray(u_rows.T)  # u's bytes ARE the comb's digits

        device_args = (
            np.ascontiguousarray(y_r.T),
            sign_r,
            np.ascontiguousarray(y_a.T),
            sign_a,
            zs_digits8,
            zk_digits,
            z_digits,
            host_ok,
        )
        eq_ok, valid = self._fn(*self._put_sharded(device_args))
        return bool(np.asarray(eq_ok)), list(np.asarray(valid)[:m])


# --- fused bytes-in -> verdict-out engines over the mesh ---------------------

#: Specs for the fused strict kernel (models/fused.py fused_verify_impl):
#: byte rows and SHA-512 block arrays all trail with the batch axis.
_FUSED_IN_SPECS = (
    P(None, BATCH_AXIS),              # sig_rows (64, batch)
    P(None, BATCH_AXIS),              # key_rows (32, batch)
    P(None, None, None, BATCH_AXIS),  # blocks (B, 16, 2, batch)
    P(BATCH_AXIS),                    # n_blocks
    P(BATCH_AXIS),                    # host_ok
)


def sharded_fused_verify_fn(mesh: Mesh):
    """jitted fused strict verify over ``mesh``: every shard runs the whole
    bytes-in → verdict-out front-end (SHA-512, mod-L reduction, canonical
    checks, digit recoding) on its own batch slice — the pipeline is pure
    data parallelism end to end, so the only collective is still the psum
    at the validity-count edge."""
    from consensus_tpu.models.fused import fused_verify_impl

    axes = _reduce_axes(mesh)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=_mesh_specs(mesh, _FUSED_IN_SPECS),
        out_specs=_mesh_specs(mesh, (P(BATCH_AXIS), P())),
    )
    def _shard(sig_rows, key_rows, blocks, n_blocks, host_ok):
        ok = fused_verify_impl(sig_rows, key_rows, blocks, n_blocks, host_ok)
        total = jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), axes)
        return ok, total

    return instrumented_jit(
        _shard, "ed25519.sharded_fused_verify" + kernel_lane_suffix()
    )


class ShardedFusedEd25519Verifier(_MeshEngine, FusedEd25519BatchVerifier):
    """Fused strict verifier that spreads the batch across a device mesh —
    ``Configuration.device_prep`` + a multi-device topology.  Verdicts are
    bit-identical to every other strict engine."""

    def __init__(
        self,
        mesh: Union[Mesh, MeshTopology, None] = None,
        *,
        compile_cache: bool = True,
        **kw,
    ) -> None:
        super().__init__(**kw)
        self._init_mesh(
            mesh, "ed25519.sharded_fused_verify", sharded_fused_verify_fn,
            _FUSED_IN_SPECS, compile_cache,
        )

    def verify_batch(self, messages, signatures, public_keys) -> np.ndarray:
        from consensus_tpu.models.fused import _pad_wave

        n = len(messages)
        if not (n == len(signatures) == len(public_keys)):
            raise ValueError("batch length mismatch")
        if n == 0:
            return np.zeros(0, dtype=bool)
        if n < self._min_device_batch:
            return self._verify_host(messages, signatures, public_keys)
        sig_rows, key_rows, blocks, n_blocks, host_ok = self._prepare_fused(
            messages, signatures, public_keys
        )
        padded = engine_padded_size(
            n, self._n_shards, pad_to=self._pad_to
        )
        sig_rows, key_rows, n_blocks, host_ok = _pad_wave(
            [sig_rows, key_rows, n_blocks, host_ok], n, padded
        )
        if padded != n:
            blocks = np.pad(blocks, ((0, 0),) * 3 + ((0, padded - n),))
        device_args = (
            np.ascontiguousarray(sig_rows.T),
            np.ascontiguousarray(key_rows.T),
            blocks,
            n_blocks,
            host_ok,
        )
        ok, _total = self._fn(*self._put_sharded(device_args))
        return np.asarray(ok)[:n]


__all__ = [
    "make_mesh",
    "mesh_for_shards",
    "compiled_kernel",
    "clear_compiled_kernels",
    "sharded_verify_fn",
    "sharded_batch_verify_fn",
    "sharded_p256_verify_fn",
    "sharded_fused_verify_fn",
    "ShardedEd25519Verifier",
    "ShardedEd25519RandomizedVerifier",
    "ShardedEcdsaP256Verifier",
    "ShardedFusedEd25519Verifier",
    "MeshTopology",
    "mesh_padded_size",
    "engine_padded_size",
    "BATCH_AXIS",
]
