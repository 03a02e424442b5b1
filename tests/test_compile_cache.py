"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a subprocess: turning the cache on is process-wide and
must not leak into the rest of the suite.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

PROBE = """
import json, jax, jax.numpy as jnp
from repro.launch import compile_cache
before = jax.config.jax_compilation_cache_dir
path = compile_cache.enable_compile_cache() if {enable} else None
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(4.0)).block_until_ready()
print(json.dumps({{"before": before, "path": path,
                   "config": jax.config.jax_compilation_cache_dir,
                   "default": str(compile_cache.DEFAULT_DIR)}}))
"""


def _probe(env_dir, *, enable=True, compile=False):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run(
        [sys.executable, "-c", PROBE.format(enable=enable, compile=compile)],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_env_dir_receives_compiled_programs(tmp_path):
    out = _probe(tmp_path, compile=True)
    assert out["path"] == out["config"] == str(tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_default_dir_is_fixed_and_ignored():
    out = _probe(None)
    assert out["path"] == out["config"] == out["default"]
    assert out["default"] == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_import_alone_changes_nothing(tmp_path, env_dir):
    out = _probe(tmp_path if env_dir else None, enable=False)
    assert out["path"] is None
    assert out["config"] == out["before"]
    assert out["config"] == (str(tmp_path) if env_dir else None)
