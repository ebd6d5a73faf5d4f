"""Set-up as spans (ISSUE 38): ``tracing.watch_jit`` turns JAX's own
trace / lower / compile events and the backend's creation into spans of
the process-wide tracer, and the benchmark's five ``setup_*`` readers
cut a run's set-up into phases from them."""

import json
import logging
import os
import subprocess
import sys
import time

import jax
import pytest
from jax import lax
from jax._src import monitoring

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.telemetry import tracing
from tensorflowonspark_tpu.utils.compile_cache import ensure_compile_cache

from benchmarks import setup_spans
from benchmarks.runners import common
from benchmarks.tests.test_run_e2e import REHEARSALS, ROOT, rehearse

READERS = ("setup_device_s", "setup_weights_s", "setup_trace_s",
           "setup_jit_traces", "setup_compile_s")


def _jit_spans(since, fun=None):
    return [s for s in telemetry.get_tracer().spans(trace="jit")
            if s["t0"] >= since
            and (fun is None or fun in (s.get("attrs") or {}).get("fun", ""))]


def test_one_outer_trace_counts_its_inner_traces():
    since = time.time()

    @jax.jit
    def inner_a(x):
        return lax.mul(x, x)

    @jax.jit
    def inner_b(x):
        return lax.add(x, x)

    @jax.jit
    def outer_of_two(x):
        return lax.sub(inner_a(x), inner_b(x))

    outer_of_two(jax.numpy.ones(3, jax.numpy.float32)).block_until_ready()
    traces = [s for s in _jit_spans(since) if s["name"] == "jit.trace"
              and s["attrs"]["fun"] == "outer_of_two"]
    assert len(traces) == 1 and traces[0]["attrs"]["nested"] == 2
    assert not [s for s in _jit_spans(since, "inner_")
                if s["name"] == "jit.trace"]
    for stage in ("jit.lower", "jit.compile"):
        got = [s for s in _jit_spans(since, "outer_of_two")
               if s["name"] == stage]
        assert len(got) == 1, stage
    (compile_span,) = [s for s in _jit_spans(since, "outer_of_two")
                       if s["name"] == "jit.compile"]
    assert compile_span["attrs"]["cache"] in ("hit", "miss", "off")
    assert compile_span["dur"] > 0


def test_installing_twice_registers_once():
    ensure_compile_cache()
    tracing.watch_jit()
    listeners = monitoring.get_event_time_span_listeners()
    assert listeners.count(tracing._on_stage_end) == 1
    logger = logging.getLogger(tracing._BACKEND_LOGGER)
    assert sum(isinstance(f, tracing._BackendInitFilter)
               for f in logger.filters) == 1


def test_with_the_tracer_off_nothing_is_recorded():
    tracer = telemetry.get_tracer()
    was = tracer.enabled
    since = time.time()
    tracer.set_enabled(False)
    try:
        jax.jit(lambda x: lax.mul(x, 3.0))(
            jax.numpy.ones(5, jax.numpy.float32)).block_until_ready()
    finally:
        tracer.set_enabled(was)
    assert _jit_spans(since) == []


_FRESH_PROCESS = r"""
import json, sys
sys.path.insert(0, %r)
from tensorflowonspark_tpu.utils.compile_cache import ensure_compile_cache
ensure_compile_cache()
import jax
from jax import lax
from jax._src import monitoring
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from tensorflowonspark_tpu import telemetry
jax.devices()
jax.jit(lambda x: lax.mul(x, 7.0))(jax.numpy.ones(4, jax.numpy.float32))
print(json.dumps(telemetry.get_tracer().spans()))
"""


def _fresh_process(cache_dir, **env):
    proc = subprocess.run(
        [sys.executable, "-c", _FRESH_PROCESS % ROOT], capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=cache_dir, **env))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_second_process_reads_the_executable_back(tmp_path):
    first = _fresh_process(str(tmp_path))
    second = _fresh_process(str(tmp_path))

    def caches(spans):
        return [s["attrs"]["cache"] for s in spans
                if s["name"] == "jit.compile"]

    assert caches(first) and set(caches(first)) == {"miss"}
    assert set(caches(second)) == {"hit"}
    (device,) = [s for s in second if s["name"] == "setup.device"]
    assert device["trace"] == "setup" and device["dur"] > 0
    assert device["attrs"] == {"platform": "cpu"}
    # the backend came up before the first program was traced
    assert device["t0"] + device["dur"] <= min(
        s["t0"] for s in second if s["trace"] == "jit")
    assert _fresh_process(str(tmp_path), TFOS_TELEMETRY="0") == []


@pytest.fixture(scope="module")
def rehearsals():
    """One tiny traced rehearsal of a serving and of a training cell."""
    out = {}
    for workload, kind in (("mistral7b-decode-closed", "serve"),
                           ("mistral7b-train-tp2dp2", "train")):
        with pytest.MonkeyPatch.context() as mp:
            # what is read here is the set-up, not liveness: a loaded
            # sandbox can starve an executor's heartbeat for 3 s
            mp.setenv("TFOS_HEARTBEAT_MISS_THRESHOLD", "30")
            proc, result = rehearse(ROOT, workload, REHEARSALS[kind],
                                    trace=1, seed=2 ** 31 + 38)
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = [ln for ln in proc.stderr.splitlines()
                if ln.startswith("setup phases: ")][-1]
        out[kind] = (result, json.loads(line[len("setup phases: "):]))
    return out


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_each_reader_reads_a_traced_rehearsal(rehearsals, kind):
    result, _ = rehearsals[kind]
    for name in READERS:
        assert name in result["metrics"], name
        assert result["metrics"][name]["value"] >= 0
    assert result["metrics"]["setup_jit_traces"]["unit"] == "count"
    assert result["metrics"]["setup_jit_traces"]["value"] >= 1


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_the_phases_tile_the_set_up(rehearsals, kind):
    _, got = rehearsals[kind]
    phases = got["phases"]
    want = {"serve": ["launch", "device", "weights", "warmup", "warm_in"],
            "train": ["launch", "device", "weights", "checked_steps",
                      "norms"]}[kind]
    assert list(phases) == want
    assert all(v >= 0 for v in phases.values())
    assert sum(phases.values()) == pytest.approx(got["setup_s"], abs=1e-6)
    jit, detail = got["jit"], got["detail"]
    # every trace, lowering and compile before the open lies in a phase
    assert sum(detail["trace_s_by_phase"].values()) == pytest.approx(
        jit["trace_s"], abs=1e-6)
    assert sum(detail["compile_s_by_phase"].values()) == pytest.approx(
        jit["compile_s"], abs=1e-6)
    assert jit["trace_s"] <= got["setup_s"]
    assert jit["compile_s"] <= got["setup_s"]
    assert sum(jit["compiles"].values()) >= 1


def _spans_at(t0, names):
    tracer = tracing.Tracer(enabled=True)
    for i, name in enumerate(names):
        tracer.add(name, t0 + i, 0.5, trace="engine")
    return tracer


@pytest.mark.parametrize("names", [
    [], ["engine.lifecycle", "engine.chunk", "dispatch"],
])
def test_without_set_up_spans_the_readers_read_nothing(monkeypatch, names):
    t_start = 1000.0
    monkeypatch.setattr(setup_spans, "run_spec",
                        lambda: {"t_start": t_start})
    monkeypatch.setattr(telemetry, "get_tracer",
                        lambda: _spans_at(t_start + 1, names))
    monkeypatch.setattr(setup_spans, "_memo", {})
    for name in READERS:
        assert common.load_reader(name)(None, {"setup_s": 30.0}, {}) is None


def test_without_a_spec_the_readers_read_nothing(monkeypatch):
    monkeypatch.setattr(setup_spans, "run_spec", lambda: None)
    for name in READERS:
        assert common.load_reader(name)(None, {"setup_s": 30.0}, {}) is None


def test_the_set_up_is_cut_where_the_spans_say(monkeypatch):
    t = 5000.0
    tracer = tracing.Tracer(enabled=True)
    tracer.add("setup.device", t + 2, 6, trace="setup", platform="tpu")
    tracer.add("jit.trace", t + 9, 1, trace="jit", fun="f", nested=4)
    tracer.add("jit.lower", t + 10, 0.5, trace="jit", fun="f", nested=3)
    tracer.add("jit.compile", t + 10.5, 2, trace="jit", fun="f",
               cache="hit")
    tracer.add("feed_wait", t + 14, 1, trace="step0")
    tracer.add("dispatch", t + 15, 3, trace="step0")
    tracer.add("dispatch", t + 20, 1, trace="step1")
    # after the open: the window's step and the reference's compiles
    tracer.add("dispatch", t + 30, 1, trace="step2")
    tracer.add("jit.compile", t + 40, 9, trace="jit", fun="ref",
               cache="miss")
    monkeypatch.setattr(setup_spans, "run_spec", lambda: {"t_start": t})
    monkeypatch.setattr(telemetry, "get_tracer", lambda: tracer)
    monkeypatch.setattr(setup_spans, "_memo", {})
    got = setup_spans.reading({"setup_s": 25.0})
    assert got["phases"] == {"launch": 2, "device": 6, "weights": 6,
                             "checked_steps": 5, "norms": 6}
    assert got["jit"]["trace_s"] == 1.5
    assert got["jit"]["compile_s"] == 2
    assert got["jit"]["traces"] == 8
    assert got["jit"]["compiles"] == {"hit": 1}
    read = {name: common.load_reader(name)(None, {"setup_s": 25.0}, {})
            for name in READERS}
    assert read == {"setup_device_s": 6, "setup_weights_s": 6,
                    "setup_trace_s": 1.5, "setup_jit_traces": 8,
                    "setup_compile_s": 2}


def test_a_serving_set_up_is_cut_at_each_job_s_first_pass(monkeypatch):
    t = 7000.0
    tracer = tracing.Tracer(enabled=True)
    tracer.add("setup.device", t + 1, 4, trace="setup", platform="tpu")
    # the warm-up job's passes, then the window's job: its chunk index
    # starts again at 0
    for at, chunk in ((9, 0), (10, 0), (12, 1), (13, 2), (20, 0), (21, 1),
                      (29, 5), (31, 6)):
        tracer.add("engine.lifecycle", t + at, 0.1, trace="engine",
                   chunk=chunk)
    tracer.add("jit.compile", t + 11, 3, trace="jit", fun="prefill",
               cache="miss")
    monkeypatch.setattr(setup_spans, "run_spec", lambda: {"t_start": t})
    monkeypatch.setattr(telemetry, "get_tracer", lambda: tracer)
    monkeypatch.setattr(setup_spans, "_memo", {})
    got = setup_spans.reading({"setup_s": 30.0})
    assert got["phases"] == {"launch": 1, "device": 4, "weights": 4,
                             "warmup": 11, "warm_in": 10}
    detail = setup_spans.detail(setup_spans.set_up_spans(t, t + 30), t,
                                t + 30)
    assert detail["compile_s_by_phase"]["warmup"] == 3


def test_without_setup_device_the_jit_spans_are_still_read(monkeypatch):
    t = 9000.0
    tracer = tracing.Tracer(enabled=True)
    tracer.add("jit.trace", t + 3, 2, trace="jit", fun="f", nested=9)
    tracer.add("jit.compile", t + 5, 1, trace="jit", fun="f", cache="off")
    monkeypatch.setattr(setup_spans, "run_spec", lambda: {"t_start": t})
    monkeypatch.setattr(telemetry, "get_tracer", lambda: tracer)
    monkeypatch.setattr(setup_spans, "_memo", {})
    read = {name: common.load_reader(name)(None, {"setup_s": 20.0}, {})
            for name in READERS}
    assert read == {"setup_device_s": None, "setup_weights_s": None,
                    "setup_trace_s": 2, "setup_jit_traces": 10,
                    "setup_compile_s": 1}


@pytest.mark.parametrize("oldest_ended", [-1.0, 1.0])
def test_a_ring_that_dropped_its_set_up_reads_nothing(monkeypatch,
                                                       oldest_ended):
    t = 11000.0
    tracer = tracing.Tracer(enabled=True, max_spans=4)
    tracer.add("engine.chunk", t - 3, 0.5, trace="engine")
    tracer.add("engine.chunk", t + oldest_ended - 0.5, 0.5, trace="engine")
    tracer.add("setup.device", t + 2, 4, trace="setup", platform="tpu")
    tracer.add("jit.trace", t + 7, 2, trace="jit", fun="f", nested=0)
    tracer.add("jit.compile", t + 9, 1, trace="jit", fun="f", cache="hit")
    assert tracer.dropped_spans == 1
    monkeypatch.setattr(setup_spans, "run_spec", lambda: {"t_start": t})
    monkeypatch.setattr(telemetry, "get_tracer", lambda: tracer)
    monkeypatch.setattr(setup_spans, "_memo", {})
    got = setup_spans.value({"setup_s": 20.0}, "jit", "traces")
    # the oldest span kept ended before the set-up began: what dropped
    # is older still, so nothing of the set-up is lost
    assert got == (1 if oldest_ended < 0 else None)


_BACKEND_LOG = r"""
import json, logging, sys
sys.path.insert(0, %r)
from tensorflowonspark_tpu.telemetry import tracing
tracing.watch_jit()
seen = []
class Keep(logging.Handler):
    def emit(self, record):
        if record.name == tracing._BACKEND_LOGGER:
            seen.append([record.levelno, record.msg])
logging.getLogger().addHandler(Keep())
if %r:
    logging.getLogger().setLevel(logging.DEBUG)
import jax
jax.devices()
from tensorflowonspark_tpu import telemetry
print(json.dumps([seen, telemetry.get_tracer().spans(name="setup.device")]))
"""


@pytest.mark.parametrize("root_debug", [False, True])
def test_the_backend_records_still_say_what_the_watch_reads(root_debug):
    """The ``setup.device`` span rests on two DEBUG records of
    ``jax._src.xla_bridge``: this fails when JAX rewords them.  The
    watch sets that logger to DEBUG, and its filter hands the handlers
    only what the root logger's level lets through, as it is NOW."""
    proc = subprocess.run(
        [sys.executable, "-c", _BACKEND_LOG % (ROOT, root_debug)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    seen, device = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(device) == 1 and device[0]["attrs"] == {"platform": "cpu"}
    debug = [msg for level, msg in seen if level == logging.DEBUG]
    if root_debug:
        assert tracing._BACKEND_START in debug
        assert tracing._BACKEND_END in debug
    else:
        assert debug == []


def test_chip_smoke_reports_no_compiles_with_telemetry_off():
    import chip_smoke

    tracer = telemetry.get_tracer()
    was = tracer.enabled
    tracer.set_enabled(False)
    try:
        report = chip_smoke.compile_report()
    finally:
        tracer.set_enabled(was)
    assert report == {"compile_sec": None, "cache_hits": None,
                      "cache_misses": None}
    assert set(chip_smoke.compile_report()) == set(report)
