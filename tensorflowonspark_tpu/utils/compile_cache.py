"""Where this program keeps JAX's persistent compilation cache.

One rule, applied by every process that can own a chip (the cluster's
compute process, the serving CLI, ``chip_smoke.py``, the benchmark's
runners, the test session):

- ``JAX_COMPILATION_CACHE_DIR`` set in the environment — by the
  operator, the CI job or the machine image — means the cache is
  placed from OUTSIDE: JAX reads the variable itself, spawned children
  inherit it, and this module does nothing (set it empty to run with
  no cache at all).
- unset: one fixed directory inside the checkout, resolved from this
  package's ``__file__`` and git-ignored.  Never the cwd — executors
  ``chdir`` into per-run temp directories — and never a per-user or
  ``/tmp`` path: the directory is part of every cache key, so a cache
  that moves never hits.

Either way :func:`ensure_compile_cache` also installs
``telemetry.tracing.watch_jit``: from then on the process's traces,
lowerings, compiles (cache hit or miss) and backend creation are spans
of its tracer (trace ids ``jit`` and ``setup``).
"""

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def cache_dir():
    """The directory compiled programs persist in ('' = caching off).
    Pure path arithmetic — safe in a process that must not touch a JAX
    backend (a cluster driver, ``chip_smoke.py``'s parent)."""
    return os.environ.get(ENV_VAR, DEFAULT_DIR)


def ensure_compile_cache():
    """Point this process's JAX at :func:`cache_dir` and watch its
    compile pipeline (``tracing.watch_jit``); call before the first
    compile.  Places nothing when the environment already placed the
    cache.  Returns the directory in effect."""
    from tensorflowonspark_tpu.telemetry import tracing

    tracing.watch_jit()
    if ENV_VAR not in os.environ:
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return cache_dir()
