"""The Mosaic kernels compile for a TPU v5e, without the chip.

libtpu's compiler is installed beside jax and compiles for a chip that is
described and not attached (``jax.experimental.topologies``): what Mosaic
would refuse on the chip — a block not aligned to the tiling, more VMEM
than a kernel may use — it refuses here.  Each of the six kernels alone at
2,048 lanes (a 2-step grid), and the whole verify program of a 2,048-lane
launch with its kernels wired in.  The
topology is described inside a fixture, so only the worker that runs this
file loads libtpu; the tests skip where it cannot be described.
"""

import re

import pytest

import jax
import jax.numpy as jnp

from consensus_tpu.ops import ed25519 as ed
from consensus_tpu.ops import mosaic25519 as mosaic


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu here, or it cannot describe a v5e
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # What is compiled for a described chip cannot be read back without it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


_KERNELS = {
    "mul": lambda a, b, c, d: mosaic.mul(a, b),
    "square": lambda a, b, c, d: mosaic.square(a),
    "double": lambda a, b, c, d: ed.double(ed.Point(a, b, c, d)),
    "double_xyz": lambda a, b, c, d: ed.double(ed.Point(a, b, c, d), need_t=False),
    "add": lambda a, b, c, d: ed.add(ed.Point(a, b, c, d), ed.Point(d, c, b, a)),
    "add_affine": lambda a, b, c, d: ed.add_affine(ed.Point(a, b, c, d), d, c, b),
}

_NAMES = ["mosaic25519_" + name for name in _KERNELS]


def _kernels_in(text: str) -> dict:
    """Kernel name -> times named in a compiled module's text (one name is
    not counted inside a longer one: ``double`` inside ``double_xyz``)."""
    return {
        name: len(re.findall(re.escape(name) + r"(?![a-z_])", text)) for name in _NAMES
    }


@pytest.mark.parametrize("name", list(_KERNELS))
def test_the_kernel_compiles_for_the_v5e_at_2048_lanes(one_chip, monkeypatch, name):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the dispatch's view
    x = jax.ShapeDtypeStruct((32, 16, 128), jnp.float32, sharding=one_chip)
    text = jax.jit(_KERNELS[name]).lower(x, x, x, x).compile().as_text()
    assert text.count("custom-call(") == 1
    named = _kernels_in(text)
    assert named["mosaic25519_" + name] > 0
    assert [n for n, count in named.items() if count] == ["mosaic25519_" + name]


def test_the_verify_program_compiles_for_the_v5e_at_2048_lanes(one_chip, monkeypatch):
    """The whole strict program of a 2,048-lane launch: its field and point
    ops are 84 kernels (PERF.md section 5), and all six kinds are among
    them; the rest is XLA's."""
    from consensus_tpu.models import ed25519 as model

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wave = jax.ShapeDtypeStruct((129, 2048), jnp.uint8, sharding=one_chip)
    text = jax.jit(model.packed_verify_impl).lower(wave).compile().as_text()
    assert text.count("custom-call(") == 84
    assert all(_kernels_in(text).values())
