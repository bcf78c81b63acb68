"""Ed25519-backed implementations of the consensus crypto ports.

The reference leaves ``Signer``/``Verifier`` entirely to the application
(Fabric brings MSP crypto).  This module ships a ready-made Ed25519 identity
layer whose *batch* verification paths run on the TPU engine
(:class:`consensus_tpu.models.ed25519.Ed25519BatchVerifier`), so a consensus
deployment gets the accelerated quorum verification without writing any
crypto:

* :class:`Ed25519Signer` — holds this replica's private key (host-side;
  secrets never leave the host), signs raw payloads and proposals.
* :class:`Ed25519VerifierMixin` — implements the four signature-verification
  methods of the ``Verifier`` port against a node-id -> public-key registry,
  draining ``verify_consenter_sigs_batch`` / ``verify_requests_batch``
  into single device batches.  Applications mix it in and add their
  proposal/request semantics (``verify_proposal``, ``requests_from_proposal``).

Message binding: a consenter signature covers
``b"ctpu/commit" + proposal-digest + len(aux) + aux``, so the signature
commits to both the proposal content and the auxiliary prepare-vouch list
(the blacklist redemption evidence, reference internal/bft/view.go:472-481).
"""

from __future__ import annotations

import struct
from typing import Mapping, Optional, Sequence

from consensus_tpu.api.deps import Signer, Verifier
from consensus_tpu.models.ed25519 import (
    Ed25519BatchVerifier,
    Ed25519RandomizedBatchVerifier,
)
from consensus_tpu.types import Proposal, QuorumCert, RequestInfo, Signature

_COMMIT_TAG = b"ctpu/commit"
_RAW_TAG = b"ctpu/raw"


def commit_message(proposal: Proposal, aux: bytes) -> bytes:
    digest = bytes.fromhex(proposal.digest())
    return _COMMIT_TAG + digest + struct.pack(">I", len(aux)) + aux


def raw_message(data: bytes) -> bytes:
    return _RAW_TAG + data


def engine_for_config(
    config, curve: str = "ed25519", *, metrics=None, pad_to=0
):
    """The batch engine matching a ``Configuration``'s crypto knobs
    (``batch_verify_mode``, ``crypto_tpu_min_batch``,
    ``mesh_shards`` / ``mesh_topology``, ``device_prep``), routed through
    the engine registry (:mod:`consensus_tpu.models.registry`): the config
    maps to an ``EngineKey`` and an unregistered key fails loudly with the
    curve-specific reason.  A multi-device topology — ``mesh_shards > 1``
    or a non-empty ``mesh_topology`` such as ``(2, 4)`` — selects the
    sharded engines from :mod:`consensus_tpu.parallel` over that device
    layout; ``mesh_shards = 1`` returns today's single-device engines
    bit-for-bit.  ``device_prep`` swaps in the fused bytes-in → verdict-out
    engines (:mod:`consensus_tpu.models.fused`) on either topology.  Every
    replica in a cluster must agree on the VERDICT-affecting knobs
    (``batch_verify_mode``, the curve) — verdict parity across replicas is
    a quorum-safety requirement; the topology knobs and ``device_prep``
    only change the launch layout and may differ per replica.

    ``config.compile_cache`` governs construction cost: the in-process
    compiled-kernel memo means rebuilding an engine over the same topology
    (restart, supervisor ladder, tenant churn) books zero new compiles in
    the kernel ledger.  Pass a node ``Metrics`` bundle
    as ``metrics`` to book this construction's memo hits/misses into the
    pinned ``engine_compile_cache_{hits,misses}_total`` counters.

    ``engine_supervision`` wraps the result in an
    :class:`~consensus_tpu.models.supervisor.EngineSupervisor` over the
    config's degrade ladder (:func:`degrade_ladder_configs`): fault-classed
    circuit breakers route launches down fused → unfused → host (and
    mesh → single device → host) and re-promote when the breaker
    closes.  Supervision, too, changes only WHERE work runs — never the
    verdict — so it is per-replica free.

    ``pad_to`` > 0 pins every device launch to that ONE padded shape (the
    engines' ``pad_to``): a server that knows its largest wave — the rig
    sidecar derives it from the cluster spec — compiles once before it
    serves and never mid-run.  A sequence of widths is a ladder: the strict
    single-device Ed25519 engine pads each wave to the narrowest width
    that holds it; every other engine launches at one width and takes the
    widest."""
    from consensus_tpu.obs.kernels import COMPILE_CACHE

    before = COMPILE_CACHE.snapshot()
    if not getattr(config, "engine_supervision", False):
        engine = _engine_for_config(config, curve, pad_to)
    else:
        from consensus_tpu.models.supervisor import EngineSupervisor

        rungs = [
            _engine_for_config(c, curve, pad_to)
            for c in degrade_ladder_configs(config)
        ]
        engine = EngineSupervisor(
            rungs,
            crosscheck_interval=int(
                getattr(config, "engine_crosscheck_interval", 0) or 0
            ),
            name=f"{curve}-engine",
        )
    if metrics is not None:
        after = COMPILE_CACHE.snapshot()
        metrics.engine.count_compile_cache_hits.add(
            after["hits"] - before["hits"]
        )
        metrics.engine.count_compile_cache_misses.add(
            after["misses"] - before["misses"]
        )
    return engine


def degrade_ladder_configs(config) -> list:
    """The best-first ``Configuration`` ladder supervision degrades down:
    as configured, then mesh → single device, then fused → unfused
    host-prep.  Derived by walking the engine registry's degrade keys
    (:meth:`~consensus_tpu.models.registry.EngineRegistry.degrade_keys`)
    and mapping each key transition back onto the config, so the ladder
    always mirrors what is actually registered.  (The host twin is not a
    config — the supervisor appends it as the ladder's floor itself.)"""
    from consensus_tpu.models.registry import ENGINE_REGISTRY, engine_key_for

    ladder = [config]
    keys = ENGINE_REGISTRY.degrade_keys(engine_key_for(config))
    for prev_key, next_key in zip(keys, keys[1:]):
        prev = ladder[-1]
        if prev_key.topology == "mesh" and next_key.topology == "single":
            ladder.append(prev.with_(mesh_shards=1, mesh_topology=()))
        elif prev_key.device_prep and not next_key.device_prep:
            ladder.append(prev.with_(device_prep=False))
    return ladder


def _engine_for_config(config, curve: str = "ed25519", pad_to=0):
    """The unsupervised engine routing (see :func:`engine_for_config`):
    config -> ``EngineKey`` -> registered builder."""
    from consensus_tpu.models.registry import (
        ENGINE_REGISTRY,
        EngineKey,
        engine_key_for,
    )
    from consensus_tpu.parallel.topology import topology_for_config

    cache = getattr(config, "compile_cache", None)
    key = engine_key_for(config, curve)
    if not isinstance(pad_to, int) and key != EngineKey(mxu=key.mxu):
        pad_to = max(pad_to)  # one launch width: the ladder's widest
    return ENGINE_REGISTRY.build(
        key,
        topology=topology_for_config(config),
        compile_cache=bool(getattr(cache, "enabled", True)),
        min_device_batch=config.crypto_tpu_min_batch,
        pad_to=pad_to,
    )


class Ed25519Signer(Signer):
    """This replica's signing identity (private key stays host-side),
    signing through the ``cryptography`` package (OpenSSL)."""

    def __init__(self, node_id: int, private_key_bytes: Optional[bytes] = None) -> None:
        from cryptography.hazmat.primitives import serialization
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )

        self.node_id = node_id
        if private_key_bytes is None:
            self._key = Ed25519PrivateKey.generate()
        else:
            self._key = Ed25519PrivateKey.from_private_bytes(private_key_bytes)
        self.public_bytes = self._key.public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw
        )
        self._sign_fn = self._key.sign

    def sign_raw(self, data: bytes) -> bytes:
        """Sign ``data`` exactly as given (no domain tag) — for embedders
        that bring their own message framing (e.g. client requests)."""
        return self._sign_fn(data)

    def sign(self, data: bytes) -> bytes:
        return self._sign_fn(raw_message(data))

    def sign_proposal(self, proposal: Proposal, aux: bytes = b"") -> Signature:
        return Signature(
            id=self.node_id,
            value=self._sign_fn(commit_message(proposal, aux)),
            msg=aux,
        )


class Ed25519VerifierMixin(Verifier):
    """Signature-verification half of the ``Verifier`` port, batched onto the
    device.  Subclasses provide the application half (proposal/request checks).
    """

    def __init__(
        self,
        public_keys: Mapping[int, bytes],
        *,
        engine: Optional[Ed25519BatchVerifier] = None,
        batch_verify_mode: bool = False,
    ) -> None:
        """``batch_verify_mode`` (Configuration.batch_verify_mode) selects
        the randomized aggregate-check engine as the default; an explicit
        ``engine`` wins, but passing a non-randomized engine together with
        the flag is a config contradiction and raises."""
        self._public_keys = dict(public_keys)
        if engine is None:
            engine = (
                Ed25519RandomizedBatchVerifier()
                if batch_verify_mode
                else Ed25519BatchVerifier()
            )
        elif batch_verify_mode and not getattr(engine, "randomized", False):
            raise ValueError(
                "batch_verify_mode=True requires a randomized engine "
                "(got %r)" % type(engine).__name__
            )
        self._engine = engine
        #: Consumed by api.deps facades (CryptoApp etc.) to decide whether
        #: the default multi-batch loop may coalesce through this verifier.
        self.batch_verify_enabled = bool(getattr(engine, "randomized", False))
        self._aggregator = None

    #: Half-aggregated quorum certs are Ed25519-only (the aggregator's MSM
    #: rides the Ed25519 shared-doubling kernel); the P-256 subclass
    #: overrides this back to False.
    supports_cert_aggregation = True

    @property
    def aggregator(self):
        """The lazily-built :class:`~consensus_tpu.models.aggregate.
        HalfAggregator` sharing this verifier's engine (same padding and
        device-threshold knobs, so cert checks route host/device exactly
        like the engine's own batches)."""
        if self._aggregator is None:
            from consensus_tpu.models.aggregate import HalfAggregator

            self._aggregator = HalfAggregator(engine=self._engine)
        return self._aggregator

    def set_public_keys(self, public_keys: Mapping[int, bytes]) -> None:
        """Swap the key registry (reconfiguration)."""
        self._public_keys = dict(public_keys)

    @property
    def engine(self):
        """The batch engine behind this verifier — lets applications fuse
        their own signature waves (e.g. client requests) into the same
        launch, provided they use THIS engine (SAFETY.md §7: never mix
        engines within one quorum cert's worth of verdicts)."""
        return self._engine

    def consenter_sig_triples(
        self, signatures: Sequence[Signature], proposal: Proposal
    ) -> tuple[list[bytes], list[bytes], list[bytes], list[bool]]:
        """The (messages, sigs, keys, known) arrays that
        :meth:`verify_consenter_sigs_batch` would launch — exposed so a
        caller can append them to a larger wave and run ONE engine call
        covering requests + consenter certs."""
        if isinstance(signatures, QuorumCert):
            raise ValueError(
                "consenter_sig_triples cannot flatten a half-aggregated "
                "QuorumCert into a strict-verification wave — route it "
                "through verify_aggregate_cert instead"
            )
        messages, sigs, keys = [], [], []
        known: list[bool] = []
        for sig in signatures:
            key = self._public_keys.get(sig.id)
            known.append(key is not None)
            messages.append(commit_message(proposal, sig.msg))
            sigs.append(sig.value)
            keys.append(key if key is not None else b"")
        return messages, sigs, keys, known

    # --- half-aggregated quorum certs (models/aggregate.py) --------------

    def aggregate_cert(
        self, proposal: Proposal, signatures: Sequence[Signature]
    ) -> Optional[QuorumCert]:
        if not self.supports_cert_aggregation:
            return None
        if isinstance(signatures, QuorumCert):
            return signatures
        sigs = list(signatures)
        if not sigs:
            return None
        messages, values, keys = [], [], []
        for sig in sigs:
            key = self._public_keys.get(sig.id)
            if key is None:
                return None
            messages.append(commit_message(proposal, sig.msg))
            values.append(sig.value)
            keys.append(key)
        agg, _bad = self.aggregator.aggregate(messages, values, keys)
        if agg is None:
            return None
        rs, s_agg = agg
        aux_table: list[bytes] = []
        aux_index: list[int] = []
        seen: dict[bytes, int] = {}
        for sig in sigs:
            idx = seen.get(sig.msg)
            if idx is None:
                idx = len(aux_table)
                seen[sig.msg] = idx
                aux_table.append(sig.msg)
            aux_index.append(idx)
        return QuorumCert(
            signer_ids=tuple(s.id for s in sigs),
            rs=tuple(rs),
            s_agg=s_agg,
            aux_table=tuple(aux_table),
            aux_index=tuple(aux_index),
        )

    def verify_aggregate_cert(
        self, cert: QuorumCert, proposal: Proposal
    ) -> Optional[list[bytes]]:
        if not self.supports_cert_aggregation or len(cert) == 0:
            return None
        messages, keys, aux = [], [], []
        for comp in cert:
            key = self._public_keys.get(comp.id)
            if key is None:
                return None
            messages.append(commit_message(proposal, comp.msg))
            keys.append(key)
            aux.append(comp.msg)
        try:
            ok = self.aggregator.verify(
                messages, list(cert.rs), cert.s_agg, keys
            )
        except ValueError:
            return None
        return aux if ok else None

    # --- single-signature paths (host) ----------------------------------

    def verify_consenter_sig(self, signature: Signature, proposal: Proposal) -> bytes:
        result = self.verify_consenter_sigs_batch([signature], proposal)[0]
        if result is None:
            raise ValueError(f"invalid consenter signature from {signature.id}")
        return result

    def verify_signature(self, signature: Signature) -> None:
        key = self._public_keys.get(signature.id)
        if key is None:
            raise ValueError(f"unknown signer {signature.id}")
        ok = self._engine.verify_batch(
            [raw_message(signature.msg)], [signature.value], [key]
        )
        if not ok[0]:
            raise ValueError(f"invalid signature from {signature.id}")

    # --- batch paths (device) --------------------------------------------

    def verify_consenter_sigs_batch(
        self, signatures: Sequence[Signature], proposal: Proposal
    ) -> list[Optional[bytes]]:
        if isinstance(signatures, QuorumCert):
            aux = self.verify_aggregate_cert(signatures, proposal)
            if aux is None:
                return [None] * len(signatures)
            return list(aux)
        messages, sigs, keys, known = self.consenter_sig_triples(
            signatures, proposal
        )
        ok = self._engine.verify_batch(messages, sigs, keys)
        return [
            signatures[i].msg if (known[i] and ok[i]) else None
            for i in range(len(signatures))
        ]

    def verify_consenter_sigs_multi_batch(
        self, groups: Sequence[tuple[Proposal, Sequence[Signature]]]
    ) -> list[list[Optional[bytes]]]:
        """Flatten every (proposal, quorum cert) group into ONE device batch
        — the per-item message array already lets signatures over different
        proposals share a launch, so a whole sync chunk verifies at the same
        kernel throughput as a single quorum.

        Half-aggregated groups verify one aggregate check per cert instead;
        mixing cert kinds in one call raises (contradiction guard — see the
        port default in api/deps.py)."""
        if groups:
            kinds = {isinstance(sigs, QuorumCert) for _, sigs in groups}
            if len(kinds) > 1:
                raise ValueError(
                    "verify_consenter_sigs_multi_batch: groups mix "
                    "half-aggregated QuorumCerts with full signature tuples "
                    "— cert modes contradict; partition the groups first"
                )
            if kinds == {True}:
                return [
                    self.verify_consenter_sigs_batch(cert, proposal)
                    for proposal, cert in groups
                ]
        messages, sigs, keys, known = [], [], [], []
        for proposal, cert in groups:
            for sig in cert:
                key = self._public_keys.get(sig.id)
                known.append(key is not None)
                messages.append(commit_message(proposal, sig.msg))
                sigs.append(sig.value)
                keys.append(key if key is not None else b"")
        if not messages:
            return [[] for _ in groups]
        ok = self._engine.verify_batch(messages, sigs, keys)
        out: list[list[Optional[bytes]]] = []
        i = 0
        for _, cert in groups:
            row: list[Optional[bytes]] = []
            for sig in cert:
                row.append(sig.msg if (known[i] and ok[i]) else None)
                i += 1
            out.append(row)
        return out

    def auxiliary_data(self, msg: bytes) -> bytes:
        return msg


class EcdsaP256Signer(Signer):
    """ECDSA-P256 replica identity (private key host-side); signatures are
    the framework's raw 64-byte r||s format."""

    def __init__(self, node_id: int, private_key=None) -> None:
        from cryptography.hazmat.primitives import hashes, serialization
        from cryptography.hazmat.primitives.asymmetric import ec

        self.node_id = node_id
        self._key = private_key or ec.generate_private_key(ec.SECP256R1())
        self._hash = ec.ECDSA(hashes.SHA256())
        self.public_bytes = self._key.public_key().public_bytes(
            serialization.Encoding.X962, serialization.PublicFormat.UncompressedPoint
        )

    def sign_raw(self, data: bytes) -> bytes:
        """Sign ``data`` exactly as given (no domain tag); returns the
        framework's raw 64-byte r||s format."""
        from consensus_tpu.models.ecdsa_p256 import raw_signature_from_der

        return raw_signature_from_der(self._key.sign(data, self._hash))

    _sign_raw = sign_raw  # backward-compat internal alias

    def sign(self, data: bytes) -> bytes:
        return self.sign_raw(raw_message(data))

    def sign_proposal(self, proposal: Proposal, aux: bytes = b"") -> Signature:
        return Signature(
            id=self.node_id,
            value=self._sign_raw(commit_message(proposal, aux)),
            msg=aux,
        )


class EcdsaP256VerifierMixin(Ed25519VerifierMixin):
    """Signature-verification half of the Verifier port over ECDSA-P256 —
    same registry/batching semantics as the Ed25519 mixin, different curve
    engine."""

    # Half-aggregation is Ed25519-only: the aggregate relation rides the
    # Ed25519 group law, there is no P-256 analogue here.
    supports_cert_aggregation = False

    def __init__(self, public_keys: Mapping[int, bytes], *, engine=None) -> None:
        from consensus_tpu.models.ecdsa_p256 import EcdsaP256BatchVerifier

        super().__init__(public_keys, engine=engine or EcdsaP256BatchVerifier())


__all__ = [
    "Ed25519Signer",
    "Ed25519VerifierMixin",
    "EcdsaP256Signer",
    "EcdsaP256VerifierMixin",
    "commit_message",
    "engine_for_config",
    "raw_message",
]
