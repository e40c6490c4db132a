"""Where the persistent compilation cache lands (launch/compile_cache.py).

Each case runs in a child process: the cache is process-wide JAX config,
and the child must start with a clean environment variable."""
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = """
import jax, jax.numpy as jnp
from repro.launch import compile_cache
path = compile_cache.enable()
print("PATH", path)
print("CONFIG", jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
"""


def _run(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", SCRIPT.format(compile=compile_)],
                         env=env, capture_output=True, text=True, timeout=120,
                         check=True).stdout
    return dict(line.split(" ", 1) for line in out.splitlines()
                if line.startswith(("PATH ", "CONFIG ")))


def test_env_var_places_the_cache(tmp_path):
    got = _run(tmp_path, compile_=True)
    assert got == {"PATH": str(tmp_path), "CONFIG": str(tmp_path)}
    assert any(tmp_path.iterdir()), "no cache entry written"


def test_default_is_the_checkout():
    got = _run(None, compile_=False)
    want = str(REPO / ".jax_cache")
    assert got == {"PATH": want, "CONFIG": want}
