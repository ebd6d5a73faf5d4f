"""The one rule for where compiled programs persist
(``utils/compile_cache.py``): the environment's directory when it names
one — untouched, no config call — and otherwise ONE fixed in-checkout
directory, whatever the process's cwd, shared by every process."""

import json
import os
import subprocess
import sys

from tensorflowonspark_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a child that records every jax.config.update the helper makes,
#: compiles one program, and reports where it was cached
CHILD = r"""
import json, os, sys
sys.path.insert(0, %(repo)r)
import jax
calls = []
real_update = jax.config.update
jax.config.update = lambda k, v: (calls.append(k), real_update(k, v))
from tensorflowonspark_tpu.utils import compile_cache
where = compile_cache.ensure_compile_cache()
jax.config.update = real_update
import jax.numpy as jnp
def tfos_cache_probe(x):
    return jnp.tanh(x * %(salt)s) @ x
jax.jit(tfos_cache_probe)(jnp.ones((8, 8))).block_until_ready()
d = jax.config.jax_compilation_cache_dir
print(json.dumps({
    "where": where, "config_dir": d, "updates": calls,
    "entries": sorted(f for f in os.listdir(d)
                      if f.startswith("jit_tfos_cache_probe-")
                      and f.endswith("-cache")),
}))
"""
#: ``entries`` are the probe program's own, by its name: the fixed
#: directory is shared with every test worker (``conftest.py`` calls
#: ``ensure_compile_cache``), and whatever another worker compiles
#: between two children lands there too


def _child(tmp_path, salt, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    env.pop(compile_cache.ENV_VAR, None)
    env.update(env_extra)
    proc = subprocess.run(
        [sys.executable, "-c", CHILD % {"repo": REPO, "salt": salt}],
        env=env, cwd=str(tmp_path),  # like an executor: a temp cwd
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_default_dir_is_inside_the_checkout_and_git_ignored():
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_env_var_is_respected_and_untouched(tmp_path):
    outside = tmp_path / "placed_from_outside"
    before = set(os.listdir(compile_cache.DEFAULT_DIR)) if os.path.isdir(
        compile_cache.DEFAULT_DIR) else set()
    got = _child(tmp_path, "1.25", **{compile_cache.ENV_VAR: str(outside)})
    assert got["where"] == got["config_dir"] == str(outside)
    assert got["updates"] == []  # JAX read the variable; no call in code
    assert got["entries"], "nothing was cached in the placed directory"
    # and nothing of THIS program was written anywhere else
    after = set(os.listdir(compile_cache.DEFAULT_DIR)) if os.path.isdir(
        compile_cache.DEFAULT_DIR) else set()
    assert not any(e in after - before for e in got["entries"])


def test_unset_uses_the_fixed_dir_from_any_cwd_and_second_process_hits(
        tmp_path):
    # a salt unique to this test keeps its program out of other caches
    first = _child(tmp_path, "3.0625")
    assert first["where"] == first["config_dir"] == compile_cache.DEFAULT_DIR
    assert first["updates"] == ["jax_compilation_cache_dir"]
    other_cwd = tmp_path / "elsewhere"
    other_cwd.mkdir()
    second = _child(other_cwd, "3.0625")
    assert second["config_dir"] == compile_cache.DEFAULT_DIR
    assert second["entries"] == first["entries"]  # it added none
