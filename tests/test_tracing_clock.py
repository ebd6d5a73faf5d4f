"""The tracer's one clock and the hot loops' phase spans (ISSUE 26).

A span's start is on the clock ``jax.profiler`` stamps its events with,
so the same region read from the ring and from a profiler capture
agrees; the serving engine's pass and the train loop's step are covered
by one span per phase; ``tracing.attribute`` says which span covers
each piece of a set of intervals.
"""

import glob
import os
import subprocess
import sys

import numpy as np
import pytest

from tensorflowonspark_tpu import serving, telemetry
from tensorflowonspark_tpu.telemetry import tracing
from tensorflowonspark_tpu.telemetry.tracing import Tracer

TINY = {
    "vocab_size": 64, "num_layers": 2, "num_heads": 2, "head_dim": 8,
    "embed_dim": 16, "mlp_dim": 32, "max_seq_len": 96, "dtype": "float32",
}

#: the phases of one pass of ``ServingEngine.serve`` that may not
#: overlap one another (``engine.pull`` lies inside ``engine.admit``,
#: ``engine.chunk.wait`` inside ``engine.chunk``)
PASS_PHASES = ("engine.lifecycle", "engine.admit", "engine.yielded",
               "engine.chunk", "engine.consume")


@pytest.fixture(autouse=True)
def _telemetry_on():
    telemetry.set_enabled(True)
    telemetry.get_tracer().set_enabled(True)
    yield
    telemetry.set_enabled(True)
    telemetry.get_tracer().set_enabled(True)


# ----------------------------------------------------------------------
# one clock: the ring against a profiler capture
# ----------------------------------------------------------------------


def _capture(tmp_path, body):
    """Run ``body()`` under a ``jax.profiler`` session; the capture's
    ``tfos.*`` annotations as ``[(name, Unix ns of the start)]``."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    start = tracing.profile_start_ns(path)
    assert start is not None
    return [
        (ev.name, start + ev.start_ns)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
        if ev.name.startswith("tfos.")
    ]


def _an_hour_old(tracer):
    # the tracer as an hour of running leaves it when nothing looked
    # at the wall clock meanwhile: the anchor an hour back, and the two
    # clocks 7 ms apart since (what 2 ppm of drift makes of an hour)
    tracer._anchored_ns -= 3600 * 10 ** 9
    tracer._wall_less_mono_ns += 7 * 10 ** 6


@pytest.mark.parametrize("age", [None, _an_hour_old],
                         ids=["fresh", "after_a_long_run"])
def test_span_and_its_annotation_start_together(tmp_path, age):
    tr = Tracer(enabled=True)

    def body():
        if age is not None:
            age(tr)
        for i in range(5):
            with tr.span("clock_probe", trace="t", i=i):
                sum(range(2000))

    events = _capture(tmp_path, body)
    starts = sorted(ns for name, ns in events if name == "tfos.clock_probe")
    spans = tr.spans(name="clock_probe")
    assert len(starts) == len(spans) == 5
    for span, ns in zip(spans, starts):
        assert abs(span["t0"] * 1e9 - ns) < 100e3, (span, ns)


def test_now_follows_the_wall_clock_and_measures_durations():
    import time

    tr = Tracer(enabled=True)
    _an_hour_old(tr)
    a = tr.now()
    assert abs(a - time.time()) < 1e-3
    time.sleep(0.02)
    assert 0.02 <= tr.now() - a < 0.2


def test_disabled_returns_the_null_span_and_enters_no_annotation(
        monkeypatch):
    entered = []
    monkeypatch.setattr(
        tracing, "_annotation",
        lambda name: entered.append(name) or tracing._NULL_SPAN)
    off = Tracer(enabled=False)
    assert off.span("x", trace="t") is tracing._NULL_SPAN
    with off.span("x"):
        pass
    off.add("y", off.now(), 0.1)
    assert entered == [] and off.spans() == []
    on = Tracer(enabled=True)
    with on.span("x"):
        pass
    assert entered == ["tfos.x"]


def test_telemetry_imports_and_traces_without_jax():
    code = (
        "import sys\n"
        "from tensorflowonspark_tpu import telemetry\n"
        "tr = telemetry.Tracer(enabled=True)\n"
        "with tr.span('x'):\n"
        "    pass\n"
        "assert [s['name'] for s in tr.spans()] == ['x']\n"
        "assert 'jax' not in sys.modules\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


# ----------------------------------------------------------------------
# the serving engine's pass
# ----------------------------------------------------------------------


def _gen_predict(max_new=6, extra=None):
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import transformer as tr

    model = tr.Transformer(tr.TransformerConfig(**TINY))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    cfg = dict(TINY, mode="generate", max_new_tokens=max_new,
               pad_multiple=16, **(extra or {}))
    return tr.serving_builder(jax.tree.map(np.asarray, params), cfg)


def _end(span):
    return span["t0"] + span["dur"]


@pytest.mark.parametrize("watchdog", [None, 30.0],
                         ids=["watchdog_off", "watchdog_on"])
def test_engine_pass_is_one_span_per_phase(watchdog):
    predict = _gen_predict(max_new=6, extra={"chunk_size": 2})
    rng = np.random.RandomState(13)
    rows = [{"prompt": rng.randint(0, 64, (n,)).astype(np.int32)}
            for n in (5, 9, 4, 7, 6)]
    tracer = telemetry.get_tracer()
    tracer.clear()
    out = list(serving.predict_rows(
        predict, rows, {"prompt": "tokens"}, batch_size=2,
        schedule="continuous", watchdog_timeout=watchdog,
    ))
    assert len(out) == len(rows)
    engine = tracer.spans(trace="engine")
    tid = {s["tid"] for s in engine}
    assert len(tid) == 1, "every phase is taken on the scheduler thread"
    assert {s["name"] for s in engine} == set(PASS_PHASES) | {
        "engine.pull", "engine.chunk.wait"}

    # the phases follow one another and never overlap (a microsecond
    # of slack: start and duration are rounded apart)
    phases = sorted((s for s in engine if s["name"] in PASS_PHASES),
                    key=lambda s: s["t0"])
    for a, b in zip(phases, phases[1:]):
        assert _end(a) <= b["t0"] + 1e-6, (a, b)

    # one pass: lifecycle, admit, the rows handed out, chunk, consume,
    # in that order, all tagged with the pass's chunk index
    chunks = [s for s in engine if s["name"] == "engine.chunk"]
    assert len(chunks) >= 3
    for chunk in chunks:
        idx = chunk["attrs"]["chunk"]
        of_pass = [s["name"] for s in phases
                   if s["attrs"]["chunk"] == idx
                   and s["name"] != "engine.yielded"]
        assert of_pass == ["engine.lifecycle", "engine.admit",
                           "engine.chunk", "engine.consume"], of_pass
        assert 0 < chunk["attrs"]["live"] <= chunk["attrs"]["slots"] == 2
        wait, = [s for s in engine if s["name"] == "engine.chunk.wait"
                 and s["attrs"]["chunk"] == idx]
        assert chunk["t0"] <= wait["t0"]
        assert _end(wait) <= _end(chunk) + 1e-6
        # the per-request copies repeat the chunk's one interval
        copies = [s for s in tracer.spans(name="decode_chunk")
                  if s["attrs"]["chunk"] == idx]
        assert len(copies) == chunk["attrs"]["live"]
        assert all(c["t0"] == chunk["t0"] and c["dur"] == chunk["dur"]
                   for c in copies)

    # every pull lies inside the admit pass that made it (the policy
    # is "block": rows are pulled as slots free up)
    admits = [s for s in engine if s["name"] == "engine.admit"]
    pulls = [s for s in engine if s["name"] == "engine.pull"]
    assert len(pulls) == len(rows) + 1      # the last finds the end
    for pull in pulls:
        assert any(a["t0"] <= pull["t0"] and _end(pull) <= _end(a) + 1e-6
                   for a in admits), pull

    # the rows handed out: one engine.yielded each
    assert len([s for s in engine
                if s["name"] == "engine.yielded"]) == len(rows)

    # queue_wait is on the tracer's clock at both ends: it ends inside
    # the admit pass that took the request
    for qw in tracer.spans(name="queue_wait"):
        assert any(a["t0"] <= _end(qw) <= _end(a) + 1e-6 for a in admits)


# ----------------------------------------------------------------------
# the train loop's step
# ----------------------------------------------------------------------


class _Feed(object):
    """DataFeed stand-in: ``n`` batches, then the end of the feed."""

    def __init__(self, n, rows):
        self.left, self.rows, self.done, self.commits = n, rows, False, 0

    def should_stop(self):
        return self.done

    def next_batch(self, batch_size):
        if self.left <= 0:
            self.done = True
            return []
        self.left -= 1
        return self.rows

    def commit_partitions(self):
        self.commits += 1

    def terminate(self):
        pass


def test_train_step_is_one_trace_of_phase_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    import optax

    from tensorflowonspark_tpu import checkpoint as ckpt
    from tensorflowonspark_tpu.models import mlp as mlp_model
    from tensorflowonspark_tpu.parallel import dp, sharding as sh
    from tensorflowonspark_tpu.parallel.mesh import build_mesh

    model = mlp_model.MNISTNet(hidden=16, num_classes=4)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8)))["params"]
    trainer = dp.SyncTrainer(
        mlp_model.loss_fn(model), optax.adam(1e-3), mesh=build_mesh(),
        rules=sh.RULES_DP, has_aux=True,
    )
    state = trainer.create_state(params)
    rng = np.random.RandomState(0)
    rows = [(rng.randn(8).astype(np.float32), i % 4) for i in range(8)]
    tracer = telemetry.get_tracer()
    tracer.clear()
    seen = []
    cp = ckpt.Checkpointer(tmp_path / "ck")
    trainer.train_on_feed(
        state, _Feed(4, rows), batch_size=8, log_every=0,
        preprocess=lambda rs: (np.stack([r[0] for r in rs]),
                               np.asarray([r[1] for r in rs], np.int32)),
        metrics_callback=lambda step, m: seen.append(float(m["loss"])),
        checkpointer=cp, checkpoint_every=2,
    )
    cp.close()
    assert len(seen) == 4
    for n in range(4):
        spans = sorted(tracer.spans(trace="step%d" % n),
                       key=lambda s: s["t0"])
        names = [s["name"] for s in spans]
        want = ["feed_wait", "h2d", "dispatch", "train.callback"]
        if n % 2:
            want.append("train.checkpoint")
        assert names == want, (n, names)
        for a, b in zip(spans, spans[1:]):
            assert _end(a) <= b["t0"] + 1e-6, (a, b)


# ----------------------------------------------------------------------
# tracing.attribute
# ----------------------------------------------------------------------


def _span(name, t0, dur):
    return {"name": name, "t0": t0, "dur": dur}


SPANS = [
    _span("outer", 10.0, 4.0),          # 10..14
    _span("inner", 11.0, 1.0),          # 11..12, inside outer
    _span("twin", 11.0, 1.0),           # the same interval, recorded later
    _span("mark", 13.0, 0.0),           # a mark covers nothing
    _span("late", 15.0, 1.0),           # 15..16
]


@pytest.mark.parametrize("intervals,want", [
    # the innermost span wins its part, the outer one keeps the rest
    ([(10.5, 12.5)], {"outer": 1.0, "inner": 1.0}),
    # what no span covers is unattributed, on both sides of a span
    ([(14.5, 16.5)], {"unattributed": 1.0, "late": 1.0}),
    ([(20.0, 21.0)], {"unattributed": 1.0}),
    # several intervals add up; a mark takes nothing; an empty
    # interval gives nothing
    ([(9.0, 10.0), (12.5, 13.5), (13.5, 13.5)],
     {"unattributed": 1.0, "outer": 1.0}),
    ([], {}),
], ids=["innermost_wins", "uncovered", "nothing_covers", "sum_and_marks",
        "empty"])
def test_attribute(intervals, want):
    got = tracing.attribute(intervals, SPANS)
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in intervals))
