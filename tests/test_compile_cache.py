"""Placement of JAX's persistent compilation cache
(`utils.platform.enable_compile_cache`): `JAX_COMPILATION_CACHE_DIR`
verbatim when the environment sets it, else one fixed path inside the
checkout that every process agrees on — never a temp name, pid or time.
And the one cache that a run of these tests has for itself (conftest.py).
"""

import os
import subprocess
import sys
import tempfile

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
IN_CHECKOUT = os.path.join(REPO, ".jax_cache")

_PROBE = """
import sys
{pre}
from ray_tpu.utils.platform import enable_compile_cache
path = enable_compile_cache()
import jax
assert jax.config.jax_compilation_cache_dir == path, (
    jax.config.jax_compilation_cache_dir, path)
# a cached executable must carry this version's operation names
assert jax.config.jax_compilation_cache_include_metadata_in_key
print(path)
"""


def _driver_path(env_value, cwd, jax_first=False):
    """The path a fresh driver process settles on (helper called before or
    after its `import jax`)."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    out = subprocess.run(
        [sys.executable, "-c",
         _PROBE.format(pre="import jax" if jax_first else "")],
        env=env, cwd=cwd, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


@ray_tpu.remote
def _worker_path():
    import jax

    return (os.environ.get("JAX_COMPILATION_CACHE_DIR"),
            jax.config.jax_compilation_cache_dir)


def _worker_paths(monkeypatch, env_value):
    """(env, jax config) as a worker of a cluster started here sees them:
    the worker calls the helper itself at start-up, before user code."""
    if env_value is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_value)
    ray_tpu.init(num_cpus=1, num_tpu_chips=0, max_workers=2)
    try:
        return ray_tpu.get(_worker_path.remote(), timeout=120)
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("jax_first", [False, True])
def test_driver_uses_env_path_verbatim(tmp_path, jax_first):
    given = str(tmp_path / "placed from outside") + "/"   # kept as given
    assert _driver_path(given, str(tmp_path), jax_first) == given


def test_worker_uses_env_path_verbatim(tmp_path, monkeypatch):
    given = str(tmp_path / "placed-from-outside")
    assert _worker_paths(monkeypatch, given) == (given, given)


def test_unset_processes_agree_on_in_checkout_path(tmp_path, monkeypatch):
    other = tmp_path / "elsewhere"
    other.mkdir()
    paths = {_driver_path(None, str(tmp_path)),
             _driver_path(None, str(other), jax_first=True),
             *_worker_paths(monkeypatch, None)}
    assert paths == {IN_CHECKOUT}


def test_in_checkout_cache_is_not_committed():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


_SAME_PROGRAM = """
import os, sys
{pre}
from ray_tpu.utils.platform import enable_compile_cache
enable_compile_cache()
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

def program(x):
    with jax.named_scope("attn"):
        return jnp.tanh(x @ x.T).sum()

def reached_by(callers, x):
    return reached_by(callers - 1, x) if callers else jax.jit(program)(x)

reached_by(int(sys.argv[1]), jnp.ones((8, 8)))
text = jax.jit(program).lower(jnp.ones((8, 8))).compile().as_text()
assert "jit(program)/attn/tanh" in text      # the scopes stay in the names
print(len(os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"])))
"""


@pytest.mark.parametrize("jax_first", [False, True])
def test_a_program_reached_by_other_callers_is_found_in_the_cache(
        tmp_path, jax_first):
    """Two processes that reach the same jitted program through different
    call stacks (a replica's engine loop, a benchmark's reference check)
    share its cache entry: an operation's location is its own line, and its
    name still carries the named scopes the trace readers sum by."""
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    counts = []
    for callers in (0, 3):
        out = subprocess.run(
            [sys.executable, "-c",
             _SAME_PROGRAM.format(pre="import jax" if jax_first else ""),
             str(callers)],
            env=env, capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        counts.append(int(out.stdout.strip().splitlines()[-1]))
    assert counts[0] > 0 and counts[1] == counts[0]


def test_a_run_of_the_tests_compiles_a_program_once():
    """conftest.py gives a run of the tests one cache of its own, under the
    temporary directory and not in the checkout: a program that a second
    test (here, a second function object) builds again is read from it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.utils.platform import watch_compiles

    run_cache = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert jax.config.jax_compilation_cache_dir == run_cache
    assert os.path.dirname(run_cache) == tempfile.gettempdir()
    assert run_cache != IN_CHECKOUT

    def built_anew():
        def once_a_run(x):      # no other test's program: 23 x 29, PR 50
            return jnp.tanh(x @ x.T).sum() * 50.0

        return jax.jit(once_a_run)

    watch, said = watch_compiles(), []
    for _ in range(2):
        built_anew()(jnp.ones((23, 29))).block_until_ready()
        said.append((watch.last["fun"], watch.last["cache"]))
    assert said == [("once_a_run", "miss"), ("once_a_run", "hit")]
