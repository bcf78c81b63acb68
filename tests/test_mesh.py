"""Multi-chip sharded batch verification: the host-mesh tier-1 gate.

conftest.py forces ``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
so every test here runs the REAL shard_map/pjit lane on 8 virtual CPU
devices — no accelerator required.  The gates:

* ``engine_for_config`` selects the full engine matrix (2 curves x
  strict/randomized x single/sharded) from ``Configuration.mesh_shards``;
* sharded strict engines are EXACTLY parity with the single-device engines
  (same verdict array, invalid lanes isolated) — sharding changes launch
  topology, never verdicts (SAFETY.md §7);
* ``mesh_shards=1`` is bit-for-bit the seed behavior: a same-seed chaos
  schedule run through ``engine_for_config`` produces byte-identical
  ledgers and event logs vs the default engine construction;
* the randomized mesh lane (per-shard aggregate checks, verdict reduced
  with one psum) matches ground truth — slow-marked, its first compile on
  a host mesh runs minutes;
* 2-D named topologies (``mesh_topology=(2, 4)``) are exactly parity with
  the single-device engine on all 8 devices — geometry, like shard count,
  never changes verdicts;
* the engine registry resolves every advertised key and fails loud (with
  the curve-specific reason) on every unregistered cell;
* rebuilding an engine over the same topology books ZERO new compiles in
  the kernel ledger with the compile cache on, and >= 1 with it off — the
  retrace-storm regression gate.
"""

import dataclasses

import numpy as np
import pytest

from consensus_tpu.config import Configuration
from consensus_tpu.models import Ed25519BatchVerifier, Ed25519Signer
from consensus_tpu.models.verifier import engine_for_config
from consensus_tpu.parallel import (
    ShardedEcdsaP256Verifier,
    ShardedEd25519RandomizedVerifier,
    ShardedEd25519Verifier,
    engine_padded_size,
    mesh_for_shards,
)


def make_sigs(n, corrupt=()):
    signers = [Ed25519Signer(i, bytes([i + 1] * 32)) for i in range(4)]
    msgs, sigs, keys = [], [], []
    for i in range(n):
        s = signers[i % len(signers)]
        m = b"mesh-req-%d" % i
        msgs.append(m)
        sigs.append(s.sign_raw(m))
        keys.append(s.public_bytes)
    for i in corrupt:
        sigs[i] = bytes(64)
    return msgs, sigs, keys


# --- padding / mesh construction -------------------------------------------


def test_engine_padded_size_honours_knobs_and_shard_multiple():
    # pow2 doubling from the floor, then rounded up to a shard multiple
    assert engine_padded_size(5, 1) == 8
    assert engine_padded_size(13, 8) == 16
    assert engine_padded_size(9, 8) == 16
    # pad_to wins when it covers the batch
    assert engine_padded_size(5, 4, pad_to=12) == 12
    # a doubled size that is no shard multiple is rounded up to one
    assert engine_padded_size(10, 5) == 20


def test_mesh_for_shards_errors_are_loud():
    mesh = mesh_for_shards(8)
    assert mesh.devices.size == 8  # conftest's virtual host mesh
    with pytest.raises(ValueError, match="only 8 device"):
        mesh_for_shards(9)
    with pytest.raises(ValueError, match="mesh_shards"):
        mesh_for_shards(0)


def test_config_validates_mesh_shards():
    with pytest.raises(ValueError, match="mesh_shards"):
        Configuration(self_id=1, mesh_shards=0).validate()
    Configuration(self_id=1, mesh_shards=8).validate()


# --- engine selection matrix ------------------------------------------------


def test_engine_for_config_selects_the_full_matrix():
    from consensus_tpu.models.ecdsa_p256 import EcdsaP256BatchVerifier
    from consensus_tpu.models.ed25519 import Ed25519RandomizedBatchVerifier

    base = Configuration()
    assert type(engine_for_config(base)) is Ed25519BatchVerifier
    assert type(
        engine_for_config(dataclasses.replace(base, batch_verify_mode=True))
    ) is Ed25519RandomizedBatchVerifier
    assert type(engine_for_config(base, curve="p256")) is EcdsaP256BatchVerifier

    sharded = engine_for_config(dataclasses.replace(base, mesh_shards=4))
    assert type(sharded) is ShardedEd25519Verifier
    assert sharded.mesh.devices.size == 4
    rand = engine_for_config(
        dataclasses.replace(base, mesh_shards=2, batch_verify_mode=True)
    )
    assert type(rand) is ShardedEd25519RandomizedVerifier
    assert rand.mesh.devices.size == 2
    p256 = engine_for_config(
        dataclasses.replace(base, mesh_shards=8), curve="p256"
    )
    assert type(p256) is ShardedEcdsaP256Verifier

    with pytest.raises(ValueError, match="Ed25519-only"):
        engine_for_config(
            dataclasses.replace(base, batch_verify_mode=True), curve="p256"
        )
    with pytest.raises(ValueError, match="unknown curve"):
        engine_for_config(base, curve="ed448")


def test_engine_for_config_threads_pad_and_min_batch_knobs():
    cfg = dataclasses.replace(
        Configuration(), mesh_shards=8, crypto_tpu_min_batch=7
    )
    eng = engine_for_config(cfg, pad_to=64)
    assert eng._min_device_batch == 7
    assert eng._pad_to == 64


# --- exact parity: 8-way host mesh vs single device -------------------------


def test_sharded_strict_parity_on_8_way_host_mesh():
    """The tier-1 host-mesh gate: an engine selected through
    ``engine_for_config(mesh_shards=8)`` must return the EXACT verdict
    array of the single-device engine, on a batch that is not a multiple of
    the shard count and carries invalid lanes."""
    cfg = dataclasses.replace(
        Configuration(), mesh_shards=8, crypto_tpu_min_batch=1
    )
    sharded_engine = engine_for_config(cfg)
    assert isinstance(sharded_engine, ShardedEd25519Verifier)
    msgs, sigs, keys = make_sigs(13, corrupt=(3, 9))
    sharded = np.asarray(sharded_engine.verify_batch(msgs, sigs, keys))
    single = np.asarray(
        Ed25519BatchVerifier(min_device_batch=1).verify_batch(msgs, sigs, keys)
    )
    assert (sharded == single).all()
    assert list(np.flatnonzero(~sharded)) == [3, 9]


@pytest.mark.slow
def test_sharded_randomized_matches_ground_truth():
    """The randomized mesh lane: per-shard aggregate checks (shared
    doubling chain replicated, per-shard not-identity counts reduced with
    one psum) accept an all-valid batch and isolate a corrupt lane through
    the bisection driver.  Slow: the first sharded randomized compile on a
    virtual host mesh runs ~3 minutes."""
    eng = ShardedEd25519RandomizedVerifier(
        mesh_for_shards(2), min_device_batch=1
    )
    msgs, sigs, keys = make_sigs(8)
    assert np.asarray(eng.verify_batch(msgs, sigs, keys)).all()
    msgs, sigs, keys = make_sigs(8, corrupt=(5,))
    out = np.asarray(eng.verify_batch(msgs, sigs, keys))
    assert list(np.flatnonzero(~out)) == [5]


# --- mesh_shards=1 is bit-for-bit the seed ---------------------------------


def test_mesh_shards_one_chaos_run_is_bit_for_bit_identical():
    """Same-seed ledger/event-log parity: a chaos schedule run with the
    engine built by ``engine_for_config(mesh_shards=1)`` must be
    byte-identical to the default engine construction — flipping the config
    knob to 1 changes NOTHING."""
    from consensus_tpu.testing.chaos import ChaosEngine, ChaosSchedule

    schedule = ChaosSchedule.generate(31, n=4, steps=6)
    baseline = ChaosEngine(schedule, crypto="ed25519").run()
    cfg = dataclasses.replace(
        Configuration(), mesh_shards=1, crypto_tpu_min_batch=10**9
    )
    routed = ChaosEngine(
        schedule, crypto="ed25519",
        engine_factory=lambda: engine_for_config(cfg),
    ).run()
    assert baseline.ok and routed.ok
    assert routed.ledgers == baseline.ledgers
    assert routed.event_log == baseline.event_log


def test_chaos_engine_factory_requires_crypto_mode():
    from consensus_tpu.testing.chaos import ChaosEngine, ChaosSchedule

    with pytest.raises(ValueError, match="engine_factory requires"):
        ChaosEngine(
            ChaosSchedule(seed=1, n=4, actions=()),
            engine_factory=lambda: Ed25519BatchVerifier(),
        )


# --- topologies: parse/normalize sugar and 2-D meshes ------------------------


def test_topology_normalize_parse_and_sugar():
    from consensus_tpu.parallel import MeshTopology

    assert MeshTopology.parse("2x4").axes == (2, 4)
    assert MeshTopology.parse("8").axes == (8,)
    # mesh_shards=N is sugar for the 1-D topology (N,)
    assert MeshTopology.normalize(8) == MeshTopology((8,))
    assert MeshTopology.normalize(None) == MeshTopology((1,))
    assert MeshTopology.normalize("2x2").shard_count == 4
    assert MeshTopology((2, 4)).label == "2x4"
    assert MeshTopology((8,)).label == "8"
    with pytest.raises(ValueError, match="cannot parse topology"):
        MeshTopology.parse("2xbatch")
    with pytest.raises(ValueError, match="needs 16 devices"):
        MeshTopology((4, 4)).build_mesh()


def test_2d_topology_strict_parity_on_2x4_host_mesh():
    """A (2, 4) named 2-D mesh — tuple-of-axis batch sharding, psum over
    both axes — must return the EXACT verdict array of the single-device
    engine, same gate as the 1-D 8-way mesh above."""
    cfg = dataclasses.replace(
        Configuration(), mesh_topology=(2, 4), crypto_tpu_min_batch=1
    )
    eng = engine_for_config(cfg)
    assert isinstance(eng, ShardedEd25519Verifier)
    assert eng.mesh.devices.shape == (2, 4)
    assert eng.shard_count == 8
    msgs, sigs, keys = make_sigs(13, corrupt=(3, 9))
    sharded = np.asarray(eng.verify_batch(msgs, sigs, keys))
    single = np.asarray(
        Ed25519BatchVerifier(min_device_batch=1).verify_batch(msgs, sigs, keys)
    )
    assert (sharded == single).all()
    assert list(np.flatnonzero(~sharded)) == [3, 9]


def test_config_validates_mesh_topology():
    Configuration(self_id=1, mesh_shards=8, mesh_topology=(2, 4)).validate()
    with pytest.raises(ValueError, match="axes product must equal"):
        Configuration(self_id=1, mesh_shards=4, mesh_topology=(2, 4)).validate()
    with pytest.raises(ValueError, match="axes must all be >= 1"):
        Configuration(self_id=1, mesh_topology=(2, 0)).validate()


# --- engine registry: every advertised key resolves or fails loud ------------


def test_engine_registry_completeness_and_loud_failures():
    from consensus_tpu.models.registry import (
        ENGINE_REGISTRY,
        MODES,
        TOPOLOGIES,
        EngineKey,
        UnknownEngineError,
    )

    for key in ENGINE_REGISTRY.keys():
        assert key in ENGINE_REGISTRY
        assert callable(ENGINE_REGISTRY.builder(key))
    # Every cell of the advertised matrix — the mxu axis included — is
    # either registered or refuses with its named reason (the Ed25519-only
    # lanes; device_prep × randomized, removed in PR 22).
    for curve in ENGINE_REGISTRY.curves():
        for mode in MODES:
            for topo in TOPOLOGIES:
                for prep in (False, True):
                    for mxu in (False, True):
                        key = EngineKey(curve, mode, topo, prep, mxu)
                        if key in ENGINE_REGISTRY:
                            continue
                        with pytest.raises(UnknownEngineError) as exc:
                            ENGINE_REGISTRY.builder(key)
                        want = (
                            "strict-only"
                            if curve == "ed25519" and prep and mode == "randomized"
                            else "Ed25519-only"
                        )
                        assert want in str(exc.value)
    with pytest.raises(UnknownEngineError, match="unknown curve"):
        ENGINE_REGISTRY.builder(EngineKey(curve="ed448"))
    with pytest.raises(ValueError, match="already registered"):
        ENGINE_REGISTRY.register(
            EngineKey(), lambda topology, compile_cache, **kw: None
        )


def test_engine_registry_mxu_axis(monkeypatch):
    """The mxu key axis mirrors the CTPU_MXU_LIMBS environment: every
    registered ed25519 cell exists under mxu=True but refuses to BUILD unless the
    env var actually selects the lane (the traced graph would otherwise be
    VPU under an MXU label), `engine_key_for` derives the axis from the
    env, and the degrade ladder preserves it."""
    import dataclasses as _dc

    from consensus_tpu.models.registry import (
        ENGINE_REGISTRY,
        EngineKey,
        engine_key_for,
    )

    mxu_key = EngineKey("ed25519", "strict", "single", False, True)
    assert mxu_key in ENGINE_REGISTRY

    monkeypatch.delenv("CTPU_MXU_LIMBS", raising=False)
    with pytest.raises(RuntimeError, match="CTPU_MXU_LIMBS"):
        ENGINE_REGISTRY.build(mxu_key)
    assert engine_key_for(Configuration(self_id=1)).mxu is False

    monkeypatch.setenv("CTPU_MXU_LIMBS", "1")
    assert engine_key_for(Configuration(self_id=1)).mxu is True
    engine = ENGINE_REGISTRY.build(mxu_key)
    assert engine is not None

    # The degrade ladder never silently switches lanes: every rung of an
    # mxu key's ladder keeps mxu=True (and stays registered).
    fused_mesh = EngineKey("ed25519", "strict", "mesh", True, True)
    ladder = ENGINE_REGISTRY.degrade_keys(fused_mesh)
    assert len(ladder) == 3  # mesh -> single, fused -> host prep
    assert all(k.mxu for k in ladder)
    assert all(k in ENGINE_REGISTRY for k in ladder)


# --- compile cache: rebuilds book zero new compiles --------------------------


def test_engine_rebuild_books_zero_new_compiles_with_cache_on():
    """The retrace-storm regression gate: rebuilding the same sharded
    engine over the same topology (restart, degrade ladder, tenant churn)
    reuses the process-wide compiled-kernel memo, so the kernel ledger
    books ZERO new compiles on the second warmup.  With the cache disabled
    the rebuild re-traces (>= 1 new compile), proving the counter is
    live, not just flat."""
    from consensus_tpu.config import CompileCacheConfig
    from consensus_tpu.obs.kernels import COMPILE_CACHE, KERNELS
    from consensus_tpu.parallel.sharding import clear_compiled_kernels

    clear_compiled_kernels()
    cfg = dataclasses.replace(
        Configuration(), mesh_shards=8, crypto_tpu_min_batch=1
    )
    msgs, sigs, keys = make_sigs(8)

    engine_for_config(cfg).verify_batch(msgs, sigs, keys)
    booked = KERNELS.stats("ed25519.sharded_verify").compiles
    hits0 = COMPILE_CACHE.snapshot()["hits"]

    engine_for_config(cfg).verify_batch(msgs, sigs, keys)
    assert KERNELS.stats("ed25519.sharded_verify").compiles == booked
    assert COMPILE_CACHE.snapshot()["hits"] == hits0 + 1

    off = dataclasses.replace(
        cfg, compile_cache=CompileCacheConfig(enabled=False)
    )
    engine_for_config(off).verify_batch(msgs, sigs, keys)
    assert KERNELS.stats("ed25519.sharded_verify").compiles > booked


# --- slice-filling wave formation --------------------------------------------


def test_slice_wave_target_fills_whole_slices():
    from consensus_tpu.models.engine import _slice_wave_target

    class MeshEngine:
        shard_count = 4
        preferred_wave_size = 32

    class NoPreference:
        shard_count = 4
        preferred_wave_size = 0

    assert _slice_wave_target(MeshEngine(), 256) == 32
    assert _slice_wave_target(MeshEngine(), 16) == 16  # cap still wins
    assert _slice_wave_target(NoPreference(), 256) == 256
    # single-device engines keep the configured cap bit-for-bit
    assert _slice_wave_target(Ed25519BatchVerifier(), 256) == 256


def test_preferred_wave_size_is_a_whole_slice_multiple():
    eng = engine_for_config(
        dataclasses.replace(
            Configuration(), mesh_shards=8, crypto_tpu_min_batch=1
        )
    )
    assert eng.preferred_wave_size % eng.shard_count == 0
    assert eng.preferred_wave_size >= eng.shard_count
    assert Ed25519BatchVerifier(min_device_batch=5).preferred_wave_size == 8
