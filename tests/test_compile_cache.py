"""utils/compile_cache.py: the persistent compilation cache is placed
from OUTSIDE, one way — JAX_COMPILATION_CACHE_DIR when set, else
<checkout>/.jax_cache — with the zero thresholds applied either way, and
an unwritable directory is an error, not a silent uncached run.

Each case runs in a fresh interpreter: the cache directory is process-
wide JAX config and enable_persistent_cache is idempotent per process.
"""

import json
import os
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, jax
from kubernetes_tpu.utils.compile_cache import enable_persistent_cache
from kubernetes_tpu.solver.single_shot import SingleShotSolver
from kubernetes_tpu.solver.relax import RelaxSolver
try:
    SingleShotSolver(); RelaxSolver()  # solvers enable the cache themselves
    returned = enable_persistent_cache()
    print(json.dumps({
        "returned": returned,
        "dir": jax.config.jax_compilation_cache_dir,
        "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
        "min_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes,
    }))
except OSError as e:
    print(json.dumps({"error": type(e).__name__}))
"""


def _probe(cache_env: str | None) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=_REPO_ROOT, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_var_places_the_cache(tmp_path):
    target = str(tmp_path / "placed-from-outside")
    got = _probe(target)
    assert got["returned"] == got["dir"] == target
    assert os.path.isdir(target)
    # the thresholds apply in this branch too (it used to return early)
    assert got["min_secs"] == 0.0 and got["min_bytes"] == -1


def test_default_is_the_checkout(tmp_path):
    got = _probe(None)
    assert got["returned"] == got["dir"]
    assert got["dir"] == os.path.join(_REPO_ROOT, ".jax_cache")
    assert got["min_secs"] == 0.0 and got["min_bytes"] == -1


def test_unwritable_directory_raises(tmp_path):
    blocker = tmp_path / "a-file"
    blocker.write_text("not a directory")
    got = _probe(str(blocker / "cache"))
    assert got == {"error": "NotADirectoryError"}


def test_no_private_knob_left():
    """One way to place the cache: the old private variable and the
    cache_dir argument are gone."""
    import inspect

    from kubernetes_tpu.utils import compile_cache

    assert not inspect.signature(
        compile_cache.enable_persistent_cache
    ).parameters
    assert "KUBERNETES_TPU" not in inspect.getsource(compile_cache)
