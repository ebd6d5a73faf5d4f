"""What both chip-owning children share: claiming the device, counting
compiles, the profiler window, finding the per-layer readers by name,
and the result line."""

import importlib.util
import json
import os
import re
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULT_TAG = "BENCH_RESULT "
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def claim_device(chips, rehearse):
    """First JAX touch of the process: place the compile cache inside
    the checkout, then refuse any platform but the TPU (the CPU only in
    a test's rehearsal) and any host with fewer chips than the cell
    asks for."""
    from tensorflowonspark_tpu.utils.compile_cache import (
        ensure_compile_cache,
    )

    ensure_compile_cache()
    import jax

    # keep every program, however quick its compile: a run after the
    # first must find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    want = "cpu" if rehearse else "tpu"
    if info["platform"] != want:
        raise RuntimeError(
            "expected platform %r, JAX gave %s" % (want, info))
    if info["count"] < chips:
        raise RuntimeError(
            "the cell needs %d chip(s), JAX sees %d" % (
                chips, info["count"]))
    return info


class CompileMeter(object):
    """Counts this process's backend compiles and cache misses, from
    JAX's own monitoring events; ``inside(a, b)`` is how many compiles
    ended between two readings of ``count``."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory_peak_bytes():
    """Peak bytes in use on the fullest local chip, as the backend
    reports it (0 where it reports nothing, as on the CPU)."""
    import jax

    peak = 0
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class ProfileWindow(object):
    """The traced run's few seconds of profiler, in the process that
    holds the chips.  Host events other than ``TraceAnnotation`` spans
    are off: the Python tracer slows the host it measures."""

    def __init__(self, log_dir, seconds=1.5):
        self.log_dir = log_dir
        #: how much of the window's end the trace should hold
        self.seconds = seconds
        self.started_at = None
        self.stopped_at = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.started_at = time.monotonic()

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        self.stopped_at = time.monotonic()

    @property
    def running(self):
        return self.started_at is not None and self.stopped_at is None


def load_module(name):
    """The module ``metrics/<name>.py`` (a metric's name has dots, so
    no import statement reaches it)."""
    if not NAME.match(name):
        raise ValueError("bad metric name %r" % (name,))
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_metric_" + re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name):
    """The ``reduce(trace, counters, cell)`` of ``metrics/<name>.py``."""
    return load_module(name).reduce


def per_layer_metrics(entries, trace, counters, cell):
    """Run the cell's readers; one that finds nothing to read returns
    None and its metric is left out of the line."""
    out = {}
    for entry in entries:
        value = load_reader(entry["name"])(trace, counters, cell)
        if value is not None:
            out[entry["name"]] = {
                "value": float(value), "unit": entry["unit"]}
    return out


def checks_hold(checks):
    """True when every number compared is within its limit (one that
    is not finite never is).  ``run.py`` prints them, each beside its
    limit, as the run's last lines on standard error."""
    return all(
        c["value"] == c["value"] and c["value"] <= c["limit"]
        for c in checks.values()
    )


def emit(result):
    print(RESULT_TAG + json.dumps(result), flush=True)
