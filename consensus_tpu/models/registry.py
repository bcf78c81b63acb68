"""First-class engine registry: ``(curve, mode, topology, device_prep,
mxu)`` -> batch-engine builder.

``engine_for_config``'s routing used to be an if-ladder over four
orthogonal knobs; every new axis (curves, randomized lanes, fused
front-ends, mesh topologies) multiplied its branches.  The registry makes
the matrix explicit: each supported combination is REGISTERED under an
:class:`EngineKey`, lookups of unregistered keys fail loudly with the
curve-specific reason (randomized and fused lanes are Ed25519-only), and
the supervisor's degrade ladder (`degrade_ladder_configs`) is derived by
walking registered keys — mesh -> single device, then fused -> host prep —
instead of hand-rolled config surgery.

Builders are lazy: nothing here imports jax or the engine modules until a
key is actually built, so the registry (like the config plane) stays
importable on boxes without the accelerator stack.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, replace
from typing import Callable, Optional

#: The two verification modes an engine key can select.
MODES = ("strict", "randomized")
#: The two launch topologies: one device, or a device mesh (any shape —
#: the key deliberately abstracts over mesh GEOMETRY, which is per-replica
#: free and carried separately by the MeshTopology handed to the builder).
TOPOLOGIES = ("single", "mesh")


class UnknownEngineError(ValueError):
    """No engine is registered under the requested key (the message names
    the reason: unknown curve, Ed25519-only lane, or plain unregistered)."""


@dataclass(frozen=True)
class EngineKey:
    """One cell of the engine matrix.

    ``topology`` is the coarse launch class (``"single"`` vs ``"mesh"``) —
    mesh geometry ((8,) vs (2, 4)) never changes which engine CLASS runs,
    only the device layout, so it stays out of the key and rides the
    ``MeshTopology`` argument to the builder instead.
    """

    curve: str = "ed25519"
    mode: str = "strict"
    topology: str = "single"
    device_prep: bool = False
    #: The MXU field-arithmetic lane (``CTPU_MXU_LIMBS=1``).  Env-derived
    #: only — the lane is selected at trace time by the environment, so a
    #: config knob would let key and traced graph disagree; the key axis
    #: exists so the registry can refuse cells the lane does not cover
    #: (P-256) instead of silently falling back.
    mxu: bool = False

    def __post_init__(self) -> None:
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {self.mode!r}")
        if self.topology not in TOPOLOGIES:
            raise ValueError(
                f"topology must be one of {TOPOLOGIES}, got {self.topology!r}"
            )


class EngineRegistry:
    """Pluggable ``EngineKey`` -> builder map with loud lookup failures.

    A builder is ``fn(topology, compile_cache, **kw) -> engine`` where
    ``topology`` is a :class:`~consensus_tpu.parallel.topology.MeshTopology`
    (ignored by single-device builders), ``compile_cache`` opts into the
    process-wide compiled-kernel memo, and ``kw`` carries the padding knobs
    (``min_device_batch``, ``pad_to``).
    """

    def __init__(self) -> None:
        self._builders: dict[EngineKey, Callable] = {}

    def register(self, key: EngineKey, builder: Callable) -> None:
        if key in self._builders:
            raise ValueError(f"engine already registered under {key}")
        self._builders[key] = builder

    def __contains__(self, key: EngineKey) -> bool:
        return key in self._builders

    def keys(self) -> tuple:
        """Every registered key (stable registration order)."""
        return tuple(self._builders)

    def curves(self) -> tuple:
        seen = []
        for key in self._builders:
            if key.curve not in seen:
                seen.append(key.curve)
        return tuple(seen)

    def builder(self, key: EngineKey) -> Callable:
        b = self._builders.get(key)
        if b is None:
            raise UnknownEngineError(self._missing_reason(key))
        return b

    def _missing_reason(self, key: EngineKey) -> str:
        if key.curve not in self.curves():
            return f"unknown curve {key.curve!r}"
        if key.mxu and key.curve != "ed25519":
            return (
                "CTPU_MXU_LIMBS engines are Ed25519-only: a P-256 engine "
                "under an MXU key would silently run a lane no chip run "
                "ever checked — unset CTPU_MXU_LIMBS for P-256 engines"
            )
        if key.curve == "p256" and key.mode == "randomized":
            return "batch_verify_mode is Ed25519-only (no randomized P-256 lane)"
        if key.curve == "p256" and key.device_prep:
            return "device_prep is Ed25519-only (no fused P-256 front-end)"
        if key.device_prep and key.mode == "randomized":
            return (
                "device_prep is strict-only: the fused randomized lane "
                "compiled a new graph for every live wave size (minutes "
                "each on the chip) and was removed — use batch_verify_mode "
                "with host prep, or device_prep with strict verification"
            )
        return (
            f"no engine registered under {key} "
            f"(registered: {', '.join(str(k) for k in self.keys())})"
        )

    def build(
        self,
        key: EngineKey,
        topology=None,
        *,
        compile_cache: bool = True,
        **kw,
    ):
        return self.builder(key)(topology, compile_cache, **kw)

    def degrade_keys(self, key: EngineKey) -> list:
        """The best-first key ladder supervision degrades down from ``key``:
        mesh -> single device, then fused -> host prep, pruned to keys that
        are actually registered.  (The host twin is not a key — the
        supervisor appends it as the ladder's floor itself.)"""
        ladder = [key]
        cur = key
        if cur.topology == "mesh":
            cur = replace(cur, topology="single")
            ladder.append(cur)
        if cur.device_prep:
            cur = replace(cur, device_prep=False)
            ladder.append(cur)
        return [ladder[0]] + [k for k in ladder[1:] if k in self]


# --- the default matrix ------------------------------------------------------
#
# 2 curves x strict/randomized x single/mesh x host-prep/device-prep, minus
# the Ed25519-only lanes (randomized and fused have no P-256 counterpart)
# and minus device-prep x randomized (removed in PR 22): those cells stay
# UNREGISTERED and lookups explain why.


def _require_mxu_lane() -> None:
    """A builder registered under an ``mxu=True`` key promises a graph the
    process only traces when the environment selects the lane — building
    it without ``CTPU_MXU_LIMBS=1`` would hand back a silently-VPU engine
    under an MXU label, exactly the mislabeled A/B the registry exists to
    prevent."""
    if os.environ.get("CTPU_MXU_LIMBS", "") != "1":
        raise RuntimeError(
            "EngineKey.mxu=True but CTPU_MXU_LIMBS is not '1': the MXU "
            "lane is selected by the environment at trace time, so this "
            "build would trace the VPU lane under an MXU key — set "
            "CTPU_MXU_LIMBS=1 in the process environment first"
        )


def _ed25519_single(topology, compile_cache, *, randomized, fused, mxu=False, **kw):
    if mxu:
        _require_mxu_lane()
    if fused:
        from consensus_tpu.models.fused import FusedEd25519BatchVerifier as cls
    else:
        from consensus_tpu.models.ed25519 import (
            Ed25519BatchVerifier,
            Ed25519RandomizedBatchVerifier,
        )

        cls = (
            Ed25519RandomizedBatchVerifier if randomized else Ed25519BatchVerifier
        )
    return cls(**kw)


def _ed25519_mesh(topology, compile_cache, *, randomized, fused, mxu=False, **kw):
    if mxu:
        _require_mxu_lane()
    from consensus_tpu.parallel import sharding

    cls = {
        (False, False): sharding.ShardedEd25519Verifier,
        (True, False): sharding.ShardedEd25519RandomizedVerifier,
        (False, True): sharding.ShardedFusedEd25519Verifier,
    }[(randomized, fused)]
    return cls(topology, compile_cache=compile_cache, **kw)


def _p256_single(topology, compile_cache, **kw):
    from consensus_tpu.models.ecdsa_p256 import EcdsaP256BatchVerifier

    return EcdsaP256BatchVerifier(**kw)


def _p256_mesh(topology, compile_cache, **kw):
    from consensus_tpu.parallel.sharding import ShardedEcdsaP256Verifier

    return ShardedEcdsaP256Verifier(topology, compile_cache=compile_cache, **kw)


def _default_registry() -> EngineRegistry:
    from functools import partial

    reg = EngineRegistry()
    for mode in MODES:
        for fused in (False, True):
            randomized = mode == "randomized"
            if fused and randomized:
                continue  # UNREGISTERED: _missing_reason names the refusal
            for mxu in (False, True):
                reg.register(
                    EngineKey("ed25519", mode, "single", fused, mxu),
                    partial(
                        _ed25519_single,
                        randomized=randomized, fused=fused, mxu=mxu,
                    ),
                )
                reg.register(
                    EngineKey("ed25519", mode, "mesh", fused, mxu),
                    partial(
                        _ed25519_mesh,
                        randomized=randomized, fused=fused, mxu=mxu,
                    ),
                )
    # p256 x mxu stays UNREGISTERED; _missing_reason names the refusal.
    reg.register(EngineKey("p256", "strict", "single", False), _p256_single)
    reg.register(EngineKey("p256", "strict", "mesh", False), _p256_mesh)
    return reg


#: The process-wide registry ``engine_for_config`` routes through.
#: Embedders may ``register`` additional curves/lanes at startup.
ENGINE_REGISTRY = _default_registry()


def engine_key_for(config, curve: str = "ed25519") -> EngineKey:
    """The registry key a ``Configuration``'s crypto knobs select."""
    from consensus_tpu.parallel.topology import topology_for_config

    mesh = topology_for_config(config).shard_count > 1
    return EngineKey(
        curve=curve,
        mode=(
            "randomized"
            if bool(getattr(config, "batch_verify_mode", False))
            else "strict"
        ),
        topology="mesh" if mesh else "single",
        device_prep=bool(getattr(config, "device_prep", False)),
        # Env-derived on purpose (no config attr): the lane is chosen at
        # trace time by CTPU_MXU_LIMBS, so the key mirrors the env instead
        # of introducing a knob the traced graphs could contradict.
        mxu=os.environ.get("CTPU_MXU_LIMBS", "") == "1",
    )


__all__ = [
    "ENGINE_REGISTRY",
    "EngineKey",
    "EngineRegistry",
    "MODES",
    "TOPOLOGIES",
    "UnknownEngineError",
    "engine_key_for",
]
