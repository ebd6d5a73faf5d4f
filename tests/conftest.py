"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of testing multi-node behavior with
multiple local processes on one box (reference: test/run_tests.sh boots a
2-worker local Spark Standalone cluster).  Here the stand-ins are:

- ``xla_force_host_platform_device_count=8`` — 8 virtual CPU devices in
  one process stand in for 8 TPU chips (mesh/sharding tests);
- multiprocessing executor backends stand in for Spark executors
  (cluster/data-plane tests).

These env vars MUST be set before the first ``import jax`` anywhere in the
test process, which is why they live at module import time in conftest.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never claim a chip
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent XLA compilation cache: the suite compiles hundreds of
# near-identical programs (every parity test rebuilds the same
# predictor/decoder shapes in a fresh jit closure), and the cache keys
# on HLO so the multi-second compiles dedup even WITHIN one cold run.
# Placed by the package's one rule (utils/compile_cache.py): the
# in-checkout directory keeps local rerun loops warm, and
# JAX_COMPILATION_CACHE_DIR overrides it (set empty to disable).
from tensorflowonspark_tpu.utils.compile_cache import (  # noqa: E402
    ensure_compile_cache,
)

ensure_compile_cache()
if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
    # JAX's stock threshold (1 s) caches almost nothing of this suite:
    # its programs are small and many.  At 0.2 s the repeats dedup —
    # measured 142 s -> 120 s cold on test_serving + test_paged_decode +
    # test_prefix_cache (CPU sandbox, PR 21); 0 s writes ~10x the
    # entries for no further gain.
    import jax  # noqa: E402

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)

# ISSUE 15: arm the runtime lock-order sanitizer when TFOS_LOCKSAN=1
# (the chaos CI lanes run this way).  Installed at conftest import so
# every lock the suite creates — serving scheduler, watchdog,
# _GradDrain, DcnLink, CheckpointWatcher, replica workers, health
# scrape, ledger — lands in the acquisition graph; the sessionfinish
# hook below fails the run if any lock-order cycle was observed.
from tensorflowonspark_tpu.analysis import locksan  # noqa: E402

locksan.install_if_enabled()


def pytest_sessionfinish(session, exitstatus):
    if not locksan.installed():
        return
    reps = locksan.reports()
    if reps:
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        lines = ["TFOS_LOCKSAN: %d potential deadlock(s) observed:"
                 % len(reps)]
        lines += [locksan.format_report(r) for r in reps]
        text = "\n".join(lines)
        if tr is not None:
            tr.write_line(text, red=True)
        else:
            print(text)
        session.exitstatus = 3
    else:
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        if tr is not None:
            tr.write_line(
                "TFOS_LOCKSAN: lock-order clean (%d locks instrumented, "
                "0 cycles)" % locksan._global.locks_created
            )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "spark: end-to-end tests against a real pyspark local-cluster "
        "(skipped when pyspark is not installed; CI runs them)",
    )
    config.addinivalue_line(
        "markers",
        "slow: multi-minute suites (cluster e2e, kernels, multi-process "
        "Gloo) — CI runs them in their own lane so the fast lane stays "
        "under its wall-clock cap; locally: -m 'not slow' for the "
        "quick signal, -m slow for the heavy one",
    )


def launch_two_workers(worker_src, tmp_path, extra_env=None, timeout=300):
    """Run a two-rank JAX-distributed worker script (used by the
    cross-process SP and PP tests): writes ``worker_src`` to disk,
    launches rank 0/1 with a fresh coordinator port, file-backed logs
    (a full PIPE would stall a chatty rank inside a collective), and a
    try/finally kill so a crashed rank never leaks its peer blocked in
    the Gloo handshake.  Asserts both exit 0 and returns their logs.
    """
    import socket
    import subprocess

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    script = tmp_path / "dist_worker.py"
    script.write_text(worker_src)
    env = dict(
        os.environ,
        TFOS_REPO=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        **(extra_env or {}),
    )
    logs = [tmp_path / ("rank%d.log" % r) for r in (0, 1)]
    handles = [open(p, "w") for p in logs]
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(r), str(port)],
            env=env,
            stdout=handles[r],
            stderr=subprocess.STDOUT,
            text=True,
        )
        for r in (0, 1)
    ]
    try:
        for p in procs:
            p.wait(timeout=timeout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
        for h in handles:
            h.close()
    outputs = [p.read_text() for p in logs]
    for r, p in enumerate(procs):
        assert p.returncode == 0, outputs[r][-2000:]
    return outputs
