"""Device topologies for the sharded batch engines.

:class:`MeshTopology` generalizes ``Configuration.mesh_shards`` from a 1-D
device count to a named N-D device mesh: ``MeshTopology((2, 4))`` lays the
first 8 visible devices out as a ``("slice", "batch")`` mesh, while
``MeshTopology((8,))`` — and the ``mesh_shards=8`` sugar that normalizes to
it — builds today's 1-D ``("batch",)`` mesh bit-for-bit.

The verification workload is pure data parallelism, so every kernel shards
its batch dimension over ALL mesh axes (``PartitionSpec`` with the full
axis-name tuple) and reduces with one ``psum`` over the same tuple; a 2-D
topology therefore changes only the device layout the runtime maps onto the
physical interconnect (which ICI links the reduction tree rides), never the
per-lane math or the verdict.  Multi-host awareness: ``jax.devices()``
enumerates the whole slice across processes, so the same spec builds the
same GLOBAL mesh on every host of a multi-host slice — partial meshes that
exclude another process's devices are rejected loudly rather than silently
degrading to a single-host layout.

This module is deliberately jax-free at import time (jax loads lazily inside
:meth:`MeshTopology.build_mesh` / :func:`apply_compile_cache`) so the config
plane and the engine registry can reason about topologies on boxes without
the accelerator stack.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Sequence, Union

#: The trailing mesh axis every kernel shards its batch dimension over (the
#: leading axes of an N-D topology join it via the full axis-name tuple).
BATCH_AXIS = "batch"

TopologySpec = Union["MeshTopology", int, str, Sequence[int], None]


def mesh_padded_size(n: int, n_shards: int, minimum: int = 8) -> int:
    """Pow-2 growth for compile-shape reuse, then rounded UP to a multiple
    of the mesh size — terminates for any shard count (a pure doubling loop
    never exits for non-power-of-two meshes)."""
    size = minimum
    while size < n:
        size *= 2
    size += (-size) % n_shards
    return size


def engine_padded_size(
    n: int,
    n_shards: int,
    *,
    pad_to: int = 0,
    minimum: int = 8,
) -> int:
    """Mesh-aligned padded batch size honouring the engine's padding knobs
    (``pad_to`` pins one compiled shape, anything wider grows by doubling),
    then rounded UP to a multiple of the mesh size so every shard gets an
    equal slice."""
    if pad_to >= n:
        return pad_to + (-pad_to) % n_shards
    return mesh_padded_size(n, n_shards, minimum)


def _default_axis_names(ndim: int) -> tuple:
    if ndim == 1:
        return (BATCH_AXIS,)
    if ndim == 2:
        return ("slice", BATCH_AXIS)
    return tuple(f"slice{i}" for i in range(ndim - 1)) + (BATCH_AXIS,)


@dataclass(frozen=True)
class MeshTopology:
    """A named device-mesh layout for the sharded engines.

    ``axes`` are per-axis device counts (product = total shard count);
    ``axis_names`` name them, defaulting to ``("batch",)`` for 1-D and
    ``("slice", "batch")`` for 2-D, so ``MeshTopology((n,))`` is exactly
    the mesh ``mesh_shards=n`` always built.
    """

    axes: tuple = (1,)
    axis_names: Optional[tuple] = None

    def __post_init__(self) -> None:
        axes = tuple(int(a) for a in self.axes)
        if not axes or any(a < 1 for a in axes):
            raise ValueError(
                f"topology axes must be a non-empty tuple of positive device "
                f"counts, got {self.axes!r}"
            )
        names = self.axis_names
        names = _default_axis_names(len(axes)) if names is None else tuple(names)
        if len(names) != len(axes) or len(set(names)) != len(names):
            raise ValueError(
                f"axis_names {names!r} must be distinct and match axes {axes!r}"
            )
        object.__setattr__(self, "axes", axes)
        object.__setattr__(self, "axis_names", names)

    # -- identity ----------------------------------------------------------

    @property
    def shard_count(self) -> int:
        """Total devices the topology spans (the batch is sharded this many
        ways regardless of how the axes factor it)."""
        count = 1
        for a in self.axes:
            count *= a
        return count

    @property
    def ndim(self) -> int:
        return len(self.axes)

    @property
    def label(self) -> str:
        """Canonical spelling — ``"8"`` for 1-D, ``"2x4"`` for 2-D — used in
        bench sweep keys, ``last_good`` JSON, and registry errors."""
        return "x".join(str(a) for a in self.axes)

    # -- construction ------------------------------------------------------

    @classmethod
    def parse(cls, text: str) -> "MeshTopology":
        """``"8"`` -> ``(8,)``; ``"2x4"`` -> ``(2, 4)`` (the CLI seam)."""
        try:
            axes = tuple(int(part) for part in str(text).split("x"))
        except ValueError:
            raise ValueError(
                f"cannot parse topology {text!r} (want e.g. '8' or '2x4')"
            ) from None
        return cls(axes)

    @classmethod
    def normalize(cls, spec: TopologySpec) -> "MeshTopology":
        """Coerce every accepted spelling to a :class:`MeshTopology`:
        ``None`` -> single device, int ``n`` (the ``mesh_shards`` sugar) ->
        ``(n,)``, a string via :meth:`parse`, a sequence of axis sizes
        verbatim."""
        if isinstance(spec, cls):
            return spec
        if spec is None:
            return cls((1,))
        if isinstance(spec, int):
            if spec < 1:
                raise ValueError(f"mesh_shards must be >= 1, got {spec}")
            return cls((spec,))
        if isinstance(spec, str):
            return cls.parse(spec)
        return cls(tuple(spec))

    def build_mesh(self, devices: Optional[Sequence] = None):
        """A ``jax.sharding.Mesh`` laying the first ``shard_count`` visible
        devices out as ``axes``.  1-D topologies build byte-identical meshes
        to the historical ``mesh_for_shards`` (same device order, same
        ``("batch",)`` axis name).  Fails loudly when the host exposes fewer
        devices than the spec demands — silently shrinking the mesh would
        make the compiled kernel shape depend on deploy-time topology — and
        when a multi-host slice would be partially covered (every process
        must participate in the same global mesh)."""
        import jax
        import numpy as np
        from jax.sharding import Mesh

        devices = list(devices if devices is not None else jax.devices())
        count = self.shard_count
        if len(devices) < count:
            raise ValueError(
                f"topology {self.label} needs {count} devices but only "
                f"{len(devices)} device(s) visible (set XLA_FLAGS="
                "--xla_force_host_platform_device_count for a host mesh, "
                "or shrink the topology)"
            )
        if jax.process_count() > 1 and count != len(devices):
            raise ValueError(
                f"topology {self.label} covers {count} of "
                f"{len(devices)} global devices on a "
                f"{jax.process_count()}-process slice; multi-host meshes "
                "must span the whole slice (every process participates)"
            )
        arr = np.array(devices[:count])
        if self.ndim > 1:
            arr = arr.reshape(self.axes)
        return Mesh(arr, self.axis_names)


def topology_for_config(config) -> MeshTopology:
    """The topology a ``Configuration`` selects: ``mesh_topology`` when set,
    else the ``mesh_shards`` 1-D sugar."""
    axes = tuple(getattr(config, "mesh_topology", ()) or ())
    if axes:
        return MeshTopology(axes)
    return MeshTopology.normalize(int(getattr(config, "mesh_shards", 1) or 1))


#: Where the persistent XLA cache lives when the environment names no
#: place: a FIXED path inside the checkout (the path is part of the cache
#: key, so a directory that moves — temp name, pid, timestamp — never hits).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def apply_compile_cache() -> str:
    """Turn on jax's persistent compilation cache — the ONE place this
    program decides where compiled kernels are kept, called by every entry
    point that compiles (the rig sidecar, tests/conftest.py,
    ``__graft_entry__``, ``chip_smoke.py``'s lane children).

    ``JAX_COMPILATION_CACHE_DIR`` wins: when it is set jax reads it by
    itself and this function sets NO directory, so an operator (or the
    machine the program runs on) places the cache from outside.  Otherwise
    the cache goes to :data:`DEFAULT_COMPILE_CACHE_DIR`.  Returns the
    directory in effect.  Idempotent."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    # Cache every entry regardless of serialized size: correctness work like
    # this repo's is dominated by many small-but-slow-to-trace kernels.
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


__all__ = [
    "BATCH_AXIS",
    "DEFAULT_COMPILE_CACHE_DIR",
    "MeshTopology",
    "apply_compile_cache",
    "engine_padded_size",
    "mesh_padded_size",
    "topology_for_config",
]
