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


_NAMES = """
import re, sys, jax, jax.numpy as jnp
from kubernetes_tpu.utils import compile_cache
compile_cache.enable_persistent_cache()
if sys.argv[2] == "keyed":
    compile_cache.key_on_op_names()

@jax.jit
def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.cumsum(x * 3 + 1)

text = f.lower(jnp.arange(64.0)).compile().as_text()
print(sorted({s for s in ("Score", "select") if s + "/" in text}))
"""


def _names(cache_dir: str, scope: str, mode: str) -> str:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": cache_dir}
    proc = subprocess.run(
        [sys.executable, "-c", _NAMES, scope, mode], cwd=_REPO_ROOT, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_a_warm_cache_serves_stale_scope_names_unless_keyed_on_them(tmp_path):
    """The hazard behind key_on_op_names: the cache key leaves op
    metadata out, so the same program under a renamed jax.named_scope
    comes back with the names it was first built with. serve --telemetry
    (whose device trace is read by name) keys on them; nothing else does."""
    cache = str(tmp_path / "cache")
    assert _names(cache, "Score", "plain") == "['Score']"  # fills the cache
    assert _names(cache, "select", "plain") == "['Score']"  # stale
    assert _names(cache, "select", "keyed") == "['select']"
    assert _names(cache, "Score", "keyed") == "['Score']"


def test_only_serve_telemetry_keys_on_names():
    import inspect

    from kubernetes_tpu import cli

    src = inspect.getsource(cli.cmd_serve)
    gate = src.index("if telemetry_on:")
    assert src.index("key_on_op_names()") > gate
    assert src.count("key_on_op_names()") == 1
    hits = subprocess.run(
        ["grep", "-rl", "key_on_op_names", os.path.join(_REPO_ROOT, "kubernetes_tpu")],
        capture_output=True, text=True,
    ).stdout.split()
    assert sorted(os.path.basename(h) for h in hits if h.endswith(".py")) == [
        "cli.py", "compile_cache.py",
    ]
