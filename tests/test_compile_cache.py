"""Compile-cache placement: ONE function decides where jax's persistent
cache lives (consensus_tpu.parallel.topology.apply_compile_cache).  With
``JAX_COMPILATION_CACHE_DIR`` set no code sets a directory — the environment
places the cache from outside; without it the cache sits at the fixed
``<checkout>/.jax_cache``.  jax's config is process-global, so each case
runs in a fresh interpreter."""

import os
import re
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import jax
from consensus_tpu.parallel.topology import apply_compile_cache
before = jax.config.jax_compilation_cache_dir
returned = apply_compile_cache()
from consensus_tpu.config import Configuration
from consensus_tpu.models import engine_for_config
engine_for_config(Configuration(self_id=1))
import __graft_entry__
print(before, jax.config.jax_compilation_cache_dir, returned, sep="|")
"""


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=_REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip().splitlines()[-1].split("|")


def test_environment_places_the_cache(tmp_path):
    outside = str(tmp_path / "placed-from-outside")
    before, after, returned = _probe(outside)
    # jax read the variable by itself, and nothing in the repo touched it.
    assert before == after == returned == outside


def test_default_is_a_fixed_path_in_the_checkout():
    before, after, returned = _probe(None)
    assert before == "None"
    assert after == returned == os.path.join(_REPO, ".jax_cache")


def test_exactly_one_place_sets_the_directory():
    hits = []
    for root, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "__pycache__", "_archive")]
        for name in files:
            if not name.endswith((".py", ".sh")):
                continue
            path = os.path.join(root, name)
            if os.path.abspath(path) == os.path.abspath(__file__):
                continue
            with open(path, encoding="utf-8") as fh:
                if re.search(r"jax_compilation_cache_dir", fh.read()):
                    hits.append(os.path.relpath(path, _REPO))
    assert hits == ["consensus_tpu/parallel/topology.py"]
