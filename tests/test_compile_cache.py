"""Where the persistent compilation cache goes (``utils/compile_cache``)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

SCRIPT = """
import jax
from repro.utils.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("outside", [True, False], ids=["env", "checkout"])
def test_cache_dir(tmp_path, outside):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if outside:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    returned, configured = proc.stdout.split()
    if outside:
        # JAX reads the variable itself; the helper sets no other cache
        assert returned == configured == str(tmp_path)
    else:
        # one fixed path in the checkout, whatever the working directory
        assert returned == configured == os.path.join(ROOT, ".jax_cache")
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
