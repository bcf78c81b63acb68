"""Batched signature-verification models built on :mod:`consensus_tpu.ops`."""

from consensus_tpu.models.ecdsa_p256 import EcdsaP256BatchVerifier
from consensus_tpu.models.ed25519 import (
    Ed25519BatchVerifier,
    Ed25519RandomizedBatchVerifier,
    L,
)
from consensus_tpu.models.engine import ThreadCoalescingVerifier
from consensus_tpu.models.supervisor import (
    ENGINE_HEALTH,
    FAULT_CLASSES,
    CircuitBreaker,
    EngineHealth,
    EngineHealthRegistry,
    EngineSupervisor,
    HostTwin,
    LaunchTimeout,
)
from consensus_tpu.models.fused import FusedEd25519BatchVerifier
from consensus_tpu.models.verifier import (
    EcdsaP256Signer,
    EcdsaP256VerifierMixin,
    Ed25519Signer,
    Ed25519VerifierMixin,
    commit_message,
    degrade_ladder_configs,
    engine_for_config,
    raw_message,
)

__all__ = [
    "EcdsaP256BatchVerifier",
    "EcdsaP256Signer",
    "EcdsaP256VerifierMixin",
    "Ed25519BatchVerifier",
    "Ed25519RandomizedBatchVerifier",
    "FusedEd25519BatchVerifier",
    "L",
    "ThreadCoalescingVerifier",
    "CircuitBreaker",
    "ENGINE_HEALTH",
    "EngineHealth",
    "EngineHealthRegistry",
    "EngineSupervisor",
    "FAULT_CLASSES",
    "HostTwin",
    "LaunchTimeout",
    "Ed25519Signer",
    "Ed25519VerifierMixin",
    "commit_message",
    "degrade_ladder_configs",
    "engine_for_config",
    "raw_message",
]
