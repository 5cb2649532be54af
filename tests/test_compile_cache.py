"""``repro.compile_cache``: one fixed cache directory, never one made up.

Each case runs in a fresh interpreter, so the process-wide jax config of
the test worker is left alone.
"""
import json
import os
import subprocess
import sys

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")

_SCRIPT = r"""
import json, sys
import jax, jax.numpy as jnp
from repro import compile_cache
where = compile_cache.enable()
if sys.argv[1] == "compile":
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(8)).block_until_ready()
print(json.dumps({"enabled": where,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _run(mode, env_dir):
    env = dict(os.environ, PYTHONPATH=_SRC)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _SCRIPT, mode], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_goes_only_where_the_environment_says(tmp_path):
    cache = tmp_path / "cache"
    res = _run("compile", str(cache))
    assert res["enabled"] == res["config"] == str(cache)
    assert any(cache.iterdir())               # the compile landed there


def test_cache_defaults_to_a_fixed_path_in_the_checkout():
    from repro import compile_cache

    res = _run("describe", None)
    want = os.path.realpath(os.path.join(_SRC, "..", ".jax_cache"))
    assert res["enabled"] == res["config"] == want
    assert str(compile_cache.DEFAULT_DIR) == want
