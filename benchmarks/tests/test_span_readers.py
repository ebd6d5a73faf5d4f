"""The readers of the program's own spans (``program_spans`` and the
metrics built on it), on hand-made spans laid over the small recorded
trace: the ring is filled through the program's tracer at chosen
moments, and the profiler session's start is given, not read from a
capture (one test reads a real CPU capture for that)."""

import copy
import glob
import json
import os
import tempfile

import pytest

from benchmarks import program_spans, trace_reduce
from benchmarks.runners.common import load_module, load_reader
from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.telemetry import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
#: Unix ns at which the made-up profiler session began
ORIGIN = 1790000000 * 10 ** 9

SERVE = ["host_ms_per_chunk.serve", "slot_occupancy.serve",
         "queue_wait_ms.serve", "idle_unattributed_share.serve"]
TRAIN = ["host_ms_per_step.train", "h2d_ms.train",
         "idle_unattributed_share.train"]


@pytest.fixture
def trace():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        return json.load(f)


@pytest.fixture
def ring(monkeypatch):
    """The process-wide tracer, emptied; ``put(name, start_ms, end_ms,
    **attrs)`` records a span at milliseconds of the trace's clock."""
    monkeypatch.setattr(program_spans, "session_start_ns", lambda: ORIGIN)
    tracer = telemetry.get_tracer()
    tracer.set_enabled(True)
    tracer.clear()

    def put(name, start_ms, end_ms, trace=None, **attrs):
        tracer.add(name, (ORIGIN + start_ms * 1e6) / 1e9,
                   (end_ms - start_ms) / 1e3, trace=trace, **attrs)

    yield put
    tracer.clear()


def serving_spans(put):
    """Three passes of the engine over the trace's three programs (at
    50.42, 58.98 and 67.68 ms): each pull covers the trace's
    ``bench.source`` annotation of that pass."""
    for k, (pull, chunk, live) in enumerate([
            ((48.50, 51.30), 50.43, 3), ((57.84, 59.97), 58.99, 4),
            ((66.53, 68.71), 67.69, 2)]):
        put("engine.admit", pull[0] - 0.01, pull[1] + 0.01,
            trace="engine", chunk=7 + k)
        put("engine.pull", *pull, trace="engine", chunk=7 + k)
        put("engine.chunk", chunk, chunk + 0.42, trace="engine",
            chunk=7 + k, live=live, slots=4)
        put("engine.chunk.wait", chunk + 0.02, chunk + 0.40,
            trace="engine", chunk=7 + k)
    for start, dur in ((50.5, 0.2), (58.0, 0.4), (66.6, 3.0), (40.0, 9.0)):
        put("queue_wait", start, start + dur, trace="req")
    put("engine.consume", 52.0, 57.0, trace="engine", chunk=7)


def training_trace(trace):
    """The same trace as a training run's: the programs renamed to the
    trainer's step, and no ``bench.source`` (the training runner has
    none inside its window)."""
    trace = copy.deepcopy(trace)
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if line["name"] == trace_reduce.MODULES_LINE:
                for ev in line["events"]:
                    ev[0] = "jit_train_step(123)"
            elif plane["name"] == trace_reduce.HOST_PLANE:
                line["events"] = [
                    ev for ev in line["events"] if ev[0] != "bench.source"]
    return trace


def training_spans(put, early_ms=0.5):
    """Three steps: each ``dispatch`` starts ``early_ms`` before its
    step program does."""
    for n, program in enumerate((50.4195, 58.9834, 67.6816)):
        step = "step%d" % n
        start = program - early_ms
        put("feed_wait", start - 0.3, start - 0.1, trace=step)
        put("h2d", start - 0.1, start - 0.1 + 0.05 * (n + 1), trace=step)
        put("dispatch", start, start + 0.6, trace=step)
        put("train.callback", start + 0.7, program + 0.9, trace=step)


def covered_ns(trace, spans_ms):
    """Idle nanoseconds of the trace's window that the spans cover,
    reckoned independently: the union of every span cut to every idle
    interval."""
    window = trace_reduce.window_of(trace)
    edges = [window[0]]
    for s, e in trace_reduce.busy(trace)[0]["intervals"]:
        edges += [s, e]
    edges.append(window[1])
    pieces = []
    for i in range(0, len(edges), 2):
        for a, b in spans_ms:
            lo, hi = max(edges[i], a * 1e6), min(edges[i + 1], b * 1e6)
            if hi > lo:
                pieces.append((lo, hi))
    idle = sum(edges[i + 1] - edges[i] for i in range(0, len(edges), 2))
    return trace_reduce.union_seconds(pieces)[0] * 1e9, idle


def test_serving_readers_on_synthetic_spans(trace, ring):
    serving_spans(ring)
    got = {name: load_reader(name)(trace, {}, {}) for name in SERVE}
    # waits end at 50.83, 59.39 and start again at 59.01, 67.71
    assert got["host_ms_per_chunk.serve"] == pytest.approx(
        (59.01 - 50.83 + 67.71 - 59.39) / 2, abs=1e-3)
    assert got["slot_occupancy.serve"] == pytest.approx(75.0)
    # the wait that began before the window is not the window's
    assert got["queue_wait_ms.serve"] == pytest.approx(0.4, abs=1e-3)
    covered, idle = covered_ns(trace, [
        (48.49, 51.31), (57.83, 59.98), (66.52, 68.72), (52.0, 57.0),
        (50.5, 50.7), (58.0, 58.4), (66.6, 69.6), (40.0, 49.0)])
    assert got["idle_unattributed_share.serve"] == pytest.approx(
        100.0 * (1.0 - covered / idle), abs=0.01)
    assert 20.0 < got["idle_unattributed_share.serve"] < 60.0


def test_training_readers_on_synthetic_spans(trace, ring):
    trace = training_trace(trace)
    training_spans(ring)
    got = {name: load_reader(name)(trace, {}, {}) for name in TRAIN}
    # dispatch n ends 0.1 ms after program n starts; callback n-1 ends
    # 0.9 ms after program n-1 starts
    assert got["host_ms_per_step.train"] == pytest.approx(
        ((58.9834 + 0.1) - (50.4195 + 0.9)
         + (67.6816 + 0.1) - (58.9834 + 0.9)) / 2, abs=1e-3)
    # the first h2d starts before the window; the others take 0.10, 0.15
    assert got["h2d_ms.train"] == pytest.approx(0.125, abs=1e-3)
    assert 0.0 <= got["idle_unattributed_share.train"] < 100.0


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_none_without_a_device_plane(trace, ring, name):
    serving_spans(ring)
    training_spans(ring)
    trace["planes"] = [p for p in trace["planes"]
                       if p["name"] == trace_reduce.HOST_PLANE]
    assert load_reader(name)(trace, {}, {}) is None


@pytest.mark.parametrize("name", SERVE)
def test_none_when_a_source_lies_outside_every_pull(
        trace, ring, monkeypatch, name):
    serving_spans(ring)
    assert load_reader(name)(trace, {}, {}) is not None
    # the ring 5 ms off the trace's clock
    monkeypatch.setattr(program_spans, "session_start_ns",
                        lambda: ORIGIN + 5 * 10 ** 6)
    assert load_reader(name)(trace, {}, {}) is None


@pytest.mark.parametrize("early_ms,holds", [
    (0.5, True),        # launched half a millisecond into the span
    (-1.0, True),       # the device plane's lead: reads as 1 ms before
    (-3.0, False),      # too far before any dispatch
    (60.0, False),      # later than 50 ms after it
])
def test_step_programs_follow_their_dispatch(
        trace, ring, early_ms, holds):
    trace = training_trace(trace)
    training_spans(ring, early_ms=early_ms)
    assert (program_spans.checked(trace) is not None) == holds
    if not holds:
        assert [load_reader(name)(trace, {}, {}) for name in TRAIN] == [
            None] * len(TRAIN)


def test_none_with_an_empty_ring_or_a_parents_tracer(
        trace, ring, monkeypatch):
    # telemetry off leaves the ring empty
    assert program_spans.checked(trace) is None
    serving_spans(ring)
    assert program_spans.checked(trace) is not None
    # a trace with nothing the ring also saw is not trusted
    bare = training_trace(trace)
    for plane in bare["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                ev[0] = ev[0].replace("jit_train_step", "jit_other")
    assert program_spans.checked(bare) is None
    # a parent commit's tracer keeps no start on the profiler's clock
    monkeypatch.undo()
    monkeypatch.delattr(tracing, "profile_start_ns")
    assert program_spans.session_start_ns() is None
    assert load_reader("slot_occupancy.serve")(trace, {}, {}) is None


def test_session_start_is_read_from_the_runs_own_capture(
        tmp_path, monkeypatch):
    import time

    import jax

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert program_spans.session_start_ns() is None
    before = time.time_ns()
    jax.profiler.start_trace(str(tmp_path / "bench_run_x" / "trace"))
    after = time.time_ns()
    jax.profiler.stop_trace()
    assert glob.glob(str(tmp_path / "bench_run_x" / "trace" / "plugins"
                         / "profile" / "*" / "*.xplane.pb"))
    assert before <= program_spans.session_start_ns() <= after


def test_new_metrics_are_declared_with_their_cells():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in SERVE + TRAIN:
        entry = declared[name]
        assert entry["source"] == "program_counter"
        assert entry["workloads"] == [
            "mistral7b-decode-closed" if name.endswith(".serve")
            else "mistral7b-train-tp2dp2"]
        assert callable(load_module(name).reduce)
