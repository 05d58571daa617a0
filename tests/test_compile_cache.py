"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, and otherwise to the fixed <checkout>/.jax_cache.  Each case runs in
its own process, because JAX opens its cache once per process."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[1]

_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable
path = enable()
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(3)).block_until_ready()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _run(env_dir, action):
    env = dict(os.environ, PYTHONPATH=str(CHECKOUT / "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _SCRIPT, action], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("placed", [True, False], ids=["env", "default"])
def test_compile_cache_location(placed, tmp_path):
    if placed:
        cache = tmp_path / "cache"
        got = _run(cache, "compile")
        assert got["path"] == got["config"] == str(cache)
        assert any(cache.iterdir()), "no cache entry was written"
    else:
        # Checked without compiling, so the test writes nothing into the
        # checkout.
        got = _run(None, "none")
        assert got["path"] == got["config"] == str(CHECKOUT / ".jax_cache")
