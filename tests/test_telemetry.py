"""Fleet telemetry plane tests (ISSUE 7 tentpole).

Covers the metrics registry (concurrency, histogram bucket math vs
numpy percentiles, snapshot/delta, the disabled-mode null fast path),
span tracing (id propagation, Chrome-trace JSON round trip), the
serving engine's connected per-request traces (admission → queue wait
→ prefill [prefix-hit labeled] → decode chunks → emit), the chaos
markers (shed / watchdog / restart events appear as spans), the
profiler hook's graceful degradation, and cluster aggregation — a
2-process heartbeat-piggyback test over the reservation server with a
driver-side ``TFCluster.metrics()`` merge.
"""

import json
import multiprocessing
import threading
import time

import numpy as np
import pytest

from tensorflowonspark_tpu import serving, serving_engine, telemetry
from tensorflowonspark_tpu.telemetry import registry as registry_mod
from tensorflowonspark_tpu.telemetry.tracing import Tracer

TINY = {
    "vocab_size": 64, "num_layers": 2, "num_heads": 2, "head_dim": 8,
    "embed_dim": 16, "mlp_dim": 32, "max_seq_len": 96, "dtype": "float32",
}


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


def _rows(lens, vocab=64, seed=13):
    rng = np.random.RandomState(seed)
    return [
        {"prompt": rng.randint(0, vocab, (n,)).astype(np.int32)}
        for n in lens
    ]


def sa_wrap(hist_snapshot):
    """Wrap one histogram snapshot as a full registry snapshot."""
    return {"counters": {}, "gauges": {},
            "histograms": {"h": hist_snapshot}}


@pytest.fixture(autouse=True)
def _telemetry_on():
    """Every test starts from an enabled, clean default registry and
    tracer (other suites may have left state behind)."""
    telemetry.set_enabled(True)
    telemetry.get_registry().reset()
    telemetry.get_tracer().clear()
    yield
    telemetry.set_enabled(True)


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------


class TestRegistry:
    def test_counter_concurrency_exact(self):
        reg = registry_mod.MetricsRegistry(enabled=True)
        c = reg.counter("x")
        h = reg.histogram("h")

        def worker():
            for _ in range(5000):
                c.inc()
                h.observe(0.001)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 40000
        assert h.count == 40000

    def test_accessors_memoize_and_type_check(self):
        reg = registry_mod.MetricsRegistry(enabled=True)
        assert reg.counter("a") is reg.counter("a")
        with pytest.raises(ValueError, match="is a Counter"):
            reg.gauge("a")

    def test_snapshot_plain_dicts_json_roundtrip(self):
        reg = registry_mod.MetricsRegistry(enabled=True)
        reg.counter("c").inc(3)
        reg.gauge("g").set(1.5)
        reg.histogram("h").observe(0.02)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["counters"]["c"] == 3
        assert snap["gauges"]["g"] == 1.5
        assert snap["histograms"]["h"]["count"] == 1

    def test_snapshot_delta(self):
        reg = registry_mod.MetricsRegistry(enabled=True)
        c = reg.counter("c")
        h = reg.histogram("h")
        c.inc(5)
        for _ in range(10):
            h.observe(0.01)
        base = reg.snapshot()
        c.inc(2)
        for _ in range(10):
            h.observe(0.5)
        d = registry_mod.snapshot_delta(reg.snapshot(), base)
        assert d["counters"]["c"] == 2
        assert d["histograms"]["h"]["count"] == 10
        # the delta's percentile sees ONLY the new observations
        assert d["histograms"]["h"]["p50"] == pytest.approx(0.5, rel=0.3)

    def test_histogram_percentiles_vs_numpy(self):
        reg = registry_mod.MetricsRegistry(enabled=True)
        h = reg.histogram("lat")
        vals = np.random.RandomState(0).gamma(2.0, 0.05, 8000)
        for v in vals:
            h.observe(v)
        for q in (50, 90, 99):
            # bucket ratio is 1.25; interpolation lands well inside
            assert h.percentile(q) == pytest.approx(
                float(np.percentile(vals, q)), rel=0.15
            ), q
        snap = h.snapshot()
        assert snap["p99"] == pytest.approx(h.percentile(99))
        assert registry_mod.histogram_percentile(snap, 50) == (
            pytest.approx(h.percentile(50))
        )

    def test_merge_snapshots_sums_and_recomputes(self):
        a = registry_mod.MetricsRegistry(enabled=True)
        b = registry_mod.MetricsRegistry(enabled=True)
        a.counter("rows").inc(10)
        b.counter("rows").inc(32)
        for v in (0.01, 0.02):
            a.histogram("lat").observe(v)
        for v in (0.4, 0.5):
            b.histogram("lat").observe(v)
        m = telemetry.merge_snapshots([a.snapshot(), b.snapshot()])
        assert m["counters"]["rows"] == 42
        assert m["histograms"]["lat"]["count"] == 4
        assert m["histograms"]["lat"]["min"] == pytest.approx(0.01)
        assert m["histograms"]["lat"]["max"] == pytest.approx(0.5)
        assert m["histograms"]["lat"]["p99"] == pytest.approx(0.5, rel=0.3)

    def test_histogram_sum_exact_through_delta_and_merge(self):
        # ISSUE 10 satellite: the exact running sum (never rounded,
        # never bucket-derived) threads through snapshot, delta, and
        # merge — means are exact everywhere
        vals_a = [0.0123456789, 0.987654321, 1.5e-4, 3.14159]
        vals_b = [0.5, 0.25, 0.125]

        def sum(vals):  # noqa: A001 - the histogram's own arithmetic:
            # a left-to-right running float add (builtin sum() is
            # compensated since Python 3.12 and differs in the last bit)
            total = 0.0
            for v in vals:
                total += v
            return total

        a = registry_mod.MetricsRegistry(enabled=True)
        b = registry_mod.MetricsRegistry(enabled=True)
        for v in vals_a:
            a.histogram("h").observe(v)
        for v in vals_b:
            b.histogram("h").observe(v)
        sa = a.snapshot()["histograms"]["h"]
        assert sa["sum"] == sum(vals_a)  # bit-exact
        assert sa["mean"] == sum(vals_a) / len(vals_a)
        # delta: only the new observations' exact sum
        base = a.snapshot()
        extra = [0.777, 0.001]
        for v in extra:
            a.histogram("h").observe(v)
        d = registry_mod.snapshot_delta(a.snapshot(), base)
        dh = d["histograms"]["h"]
        assert dh["sum"] == pytest.approx(sum(extra), rel=0, abs=1e-15)
        assert dh["mean"] == pytest.approx(
            sum(extra) / 2, rel=0, abs=1e-15
        )
        # merge: exact sum of sums
        m = telemetry.merge_snapshots([sa_wrap(sa), b.snapshot()])
        mh = m["histograms"]["h"]
        assert mh["sum"] == sum(vals_a) + sum(vals_b)
        assert mh["mean"] == (sum(vals_a) + sum(vals_b)) / 7


class TestDisabledFastPath:
    def test_null_singletons_no_allocation(self):
        reg = registry_mod.MetricsRegistry(enabled=False)
        # every accessor returns the SAME shared null object: the
        # disabled path allocates nothing and retains nothing
        assert reg.counter("a") is registry_mod.NULL_COUNTER
        assert reg.counter("b") is registry_mod.NULL_COUNTER
        assert reg.gauge("g") is registry_mod.NULL_GAUGE
        assert reg.histogram("h") is registry_mod.NULL_HISTOGRAM
        reg.counter("a").inc(5)
        reg.histogram("h").observe(1.0)
        assert reg.snapshot() == {
            "counters": {}, "gauges": {}, "histograms": {}
        }

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer(enabled=False)
        span = tr.span("x", trace="t")
        # shared null context manager — one object for every call
        assert span is tr.span("y")
        with span:
            pass
        tr.add("z", 0.0, 1.0)
        tr.mark("m")
        assert tr.spans() == []

    def test_set_enabled_flips_registry_and_tracer(self):
        telemetry.set_enabled(False)
        assert telemetry.get_registry().counter("q") is (
            registry_mod.NULL_COUNTER
        )
        assert not telemetry.get_tracer().enabled
        telemetry.set_enabled(True)
        assert telemetry.get_registry().counter("q") is not (
            registry_mod.NULL_COUNTER
        )


# ----------------------------------------------------------------------
# tracing
# ----------------------------------------------------------------------


class TestTracer:
    def test_parent_and_trace_propagation(self):
        tr = Tracer(enabled=True)
        with tr.span("outer", trace="req1"):
            with tr.span("inner"):
                pass
        inner, outer = tr.spans()[0], tr.spans()[1]
        assert inner["name"] == "inner"
        assert inner["trace"] == "req1"  # inherited
        assert inner["parent"] == outer["id"]
        assert outer["dur"] >= inner["dur"]

    def test_attrs_and_filtering(self):
        tr = Tracer(enabled=True)
        with tr.span("prefill", trace="req0") as sp:
            sp.set("prefix_hit", True)
        tr.mark("shed", trace="req1", request_index=1)
        assert tr.spans(name="prefill")[0]["attrs"]["prefix_hit"] is True
        assert tr.spans(trace="req1")[0]["name"] == "shed"

    def test_chrome_trace_json_round_trip(self, tmp_path):
        tr = Tracer(enabled=True)
        with tr.span("step", trace="step0", batches=2):
            time.sleep(0.001)
        path = tr.save(str(tmp_path / "trace.json"))
        with open(path) as f:
            loaded = json.load(f)  # loadable as chrome://tracing input
        assert isinstance(loaded["traceEvents"], list)
        ev = loaded["traceEvents"][0]
        assert ev["name"] == "step"
        assert ev["ph"] == "X"
        assert ev["dur"] >= 1000  # microseconds
        assert ev["args"]["trace"] == "step0"
        assert ev["args"]["batches"] == 2
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)

    def test_bounded_store(self):
        tr = Tracer(enabled=True, max_spans=10)
        for i in range(50):
            tr.mark("m%d" % i)
        spans = tr.spans()
        assert len(spans) == 10
        assert spans[-1]["name"] == "m49"

    def test_dropped_spans_counted(self):
        # ISSUE 10 satellite: the bounded store's silent evictions are
        # visible — the tracer counts them and publishes into the
        # registry (tracing.dropped_spans) so truncated traces don't
        # read as "nothing happened"
        telemetry.set_enabled(True)
        base = telemetry.get_registry().counter(
            "tracing.dropped_spans"
        ).value
        tr = Tracer(enabled=True, max_spans=10)
        for i in range(10):
            tr.mark("m%d" % i)
        assert tr.dropped_spans == 0  # full but nothing evicted yet
        for i in range(7):
            tr.mark("x%d" % i)
        assert tr.dropped_spans == 7
        assert telemetry.get_registry().counter(
            "tracing.dropped_spans"
        ).value == base + 7
        # the counter rides snapshot() like any other metric
        snap = telemetry.get_registry().snapshot()
        assert snap["counters"]["tracing.dropped_spans"] >= 7


# ----------------------------------------------------------------------
# serving: connected request traces + shared latency histogram
# ----------------------------------------------------------------------


class TestServingTraces:
    def test_connected_request_trace(self):
        # acceptance: ONE continuous-schedule request produces a
        # connected trace admission → prefill → decode chunks → emit
        predict = _gen_predict(max_new=6, extra={"chunk_size": 2})
        rows = _rows([5, 9, 4, 7])
        tracer = telemetry.get_tracer()
        tracer.clear()
        out = list(serving.predict_rows(
            predict, rows, {"prompt": "tokens"}, batch_size=2,
            schedule="continuous",
        ))
        assert len(out) == len(rows)
        req0 = tracer.spans(trace="req0")
        names = [s["name"] for s in req0]
        for expected in (
            "admission", "queue_wait", "prefill", "decode_chunk", "emit"
        ):
            assert expected in names, (expected, names)
        # decode chunks carry the chunk index; the request saw several
        chunks = [s for s in req0 if s["name"] == "decode_chunk"]
        assert len(chunks) >= 2
        assert all("chunk" in s["attrs"] for s in chunks)

    def test_prefix_hit_spans_labeled(self):
        # admits served from the radix prefix cache mark their
        # prefill span prefix_hit=True with the cached token count
        predict = _gen_predict(
            max_new=4,
            extra={"chunk_size": 2, "prefix_cache": True,
                   "prefix_block": 4},
        )
        rng = np.random.RandomState(3)
        shared = rng.randint(0, 64, (12,)).astype(np.int32)
        rows = [
            {"prompt": np.concatenate(
                [shared, rng.randint(0, 64, (3,)).astype(np.int32)]
            )}
            for _ in range(4)
        ]
        tracer = telemetry.get_tracer()
        tracer.clear()
        list(serving.predict_rows(
            predict, rows, {"prompt": "tokens"}, batch_size=2,
            schedule="continuous",
        ))
        prefills = tracer.spans(name="prefill")
        assert prefills, "no prefill spans recorded"
        hits = [s for s in prefills if s["attrs"].get("prefix_hit")]
        assert hits, "no prefix-hit labeled prefill span"
        assert hits[0]["attrs"]["prefix_tokens"] >= 4

    def test_static_and_continuous_share_latency_histogram(self):
        predict = _gen_predict(max_new=4, extra={"chunk_size": 2})
        rows = _rows([5, 9, 4, 7])
        base = serving.latency_histogram().snapshot()
        stats_static = {}
        list(serving.predict_rows(
            predict, [dict(r) for r in rows], {"prompt": "tokens"},
            batch_size=2, stats=stats_static,
        ))
        mid = serving.latency_histogram().snapshot()
        stats_cont = {}
        list(serving.predict_rows(
            predict, rows, {"prompt": "tokens"}, batch_size=2,
            schedule="continuous", stats=stats_cont,
        ))
        # both schedules observed one latency per request into the
        # SAME histogram, and both mirror stats["latency_sec"]
        s_static = serving.latency_summary(since=base)
        assert s_static["count"] >= len(rows)
        s_cont = serving.latency_summary(since=mid)
        assert s_cont["count"] == len(rows)
        assert len(stats_static["latency_sec"]) == len(rows)
        assert len(stats_cont["latency_sec"]) == len(rows)
        assert s_cont["p99_ms"] >= s_cont["p50_ms"] > 0

    def test_engine_counters_published(self):
        predict = _gen_predict(max_new=4, extra={"chunk_size": 2})
        reg = telemetry.get_registry()
        before = reg.snapshot()["counters"]
        list(serving.predict_rows(
            predict, _rows([5, 9, 4]), {"prompt": "tokens"},
            batch_size=2, schedule="continuous",
        ))
        after = reg.snapshot()["counters"]

        def delta(name):
            return after.get(name, 0) - before.get(name, 0)

        assert delta("serving.admitted") == 3
        assert delta("serving.completed") == 3
        assert delta("serving.chunks") >= 1


class _WedgeOnce:
    def __init__(self, at_chunk, hang_sec):
        self.at_chunk = at_chunk
        self.hang_sec = hang_sec
        self.fired = 0

    def __call__(self, chunk_index):
        if self.fired == 0 and chunk_index >= self.at_chunk:
            self.fired += 1
            time.sleep(self.hang_sec)


class TestChaosSpans:
    """Chaos assertion (ISSUE 7): watchdog / shed / restart events
    surface as spans in the trace."""

    def test_watchdog_events_appear_as_spans(self):
        predict = _gen_predict(max_new=8, extra={"chunk_size": 2})
        # warm the prefill buckets + chunk program so only the wedge
        # (not a cold compile) can trip the 0.25s watchdog
        list(serving.predict_rows(
            predict, _rows([4, 7, 5, 9]), {"prompt": "tokens"},
            batch_size=2, schedule="continuous",
        ))
        tracer = telemetry.get_tracer()
        tracer.clear()
        stats = {}
        eng = serving_engine.ServingEngine(
            predict, {"prompt": "tokens"}, num_slots=2,
            watchdog_timeout=0.25,
            wedge_fn=_WedgeOnce(at_chunk=2, hang_sec=1.0), stats=stats,
        )
        out = list(eng.serve(_rows([4, 7, 5])))
        assert stats["watchdog_fires"] >= 1
        assert len(out) == 3
        fires = tracer.spans(name="watchdog_fire")
        assert len(fires) == stats["watchdog_fires"]
        recovers = tracer.spans(name="watchdog_recover")
        assert len(recovers) == stats["recovered"] >= 1
        assert telemetry.get_registry().snapshot()["counters"][
            "serving.watchdog_fires"
        ] >= 1

    def test_shed_events_appear_as_spans(self):
        predict = _gen_predict(max_new=4, extra={"chunk_size": 2})
        tracer = telemetry.get_tracer()
        tracer.clear()
        stats = {}
        eng = serving_engine.ServingEngine(
            predict, {"prompt": "tokens"}, num_slots=2, queue_depth=1,
            policy="reject", on_error="record", stats=stats,
        )
        out = list(eng.serve(_rows([5] * 12)))
        assert len(out) == 12
        assert stats["shed"] >= 1
        sheds = tracer.spans(name="shed")
        assert len(sheds) == stats["shed"]
        assert all("request_index" in s["attrs"] for s in sheds)

    def test_restart_events_appear_as_spans(self):
        from tensorflowonspark_tpu.cluster import cluster as cl
        from tensorflowonspark_tpu.cluster import reservation

        tracer = telemetry.get_tracer()
        tracer.clear()
        server = reservation.Server(1)
        monitor = cl.ClusterMonitor(
            server, [{"executor_id": 5}], elastic=True
        )
        server.liveness.beat(5, generation=2)
        monitor._poll()
        assert monitor.restart_events == 2
        marks = tracer.spans(name="executor_restart")
        assert len(marks) == 1
        assert marks[0]["attrs"]["executor_id"] == 5
        assert marks[0]["attrs"]["generation"] == 2
        assert telemetry.get_registry().snapshot()["counters"][
            "cluster.restart_events"
        ] == 2


# ----------------------------------------------------------------------
# profiler hook (tensorboard.py satellite)
# ----------------------------------------------------------------------


class TestProfilerHook:
    def test_graceful_noop_when_unsupported(self, monkeypatch):
        import jax

        from tensorflowonspark_tpu import tensorboard as tb

        def boom(*a, **kw):
            raise RuntimeError("no profiler in this build")

        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        assert tb.start_profile("/tmp/nowhere") is None

    def test_step_budget_stops_trace(self, monkeypatch, tmp_path):
        import jax

        from tensorflowonspark_tpu import tensorboard as tb

        calls = []
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda d, **kw: calls.append(("start", d)),
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: calls.append(("stop",))
        )
        sess = tb.start_profile(str(tmp_path), num_steps=3)
        assert sess is not None
        assert sess.step(2) is True
        # module-level feeder reaches the active session
        tb.profile_step(1)
        assert ("stop",) in calls
        sess.stop()  # idempotent
        assert calls.count(("stop",)) == 1

    def test_env_hook(self, monkeypatch, tmp_path):
        import jax

        from tensorflowonspark_tpu import tensorboard as tb

        monkeypatch.setattr(
            jax.profiler, "start_trace", lambda d, **kw: None
        )
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        monkeypatch.setenv(tb.PROFILE_DIR_ENV, str(tmp_path))
        monkeypatch.setenv(tb.PROFILE_STEPS_ENV, "2")
        sess = tb.maybe_start_profile_from_env()
        assert sess is not None
        assert sess.remaining == 2
        assert str(tmp_path) in sess.log_dir
        sess.stop()

    def test_env_hook_absent(self, monkeypatch):
        from tensorflowonspark_tpu import tensorboard as tb

        monkeypatch.delenv(tb.PROFILE_DIR_ENV, raising=False)
        assert tb.maybe_start_profile_from_env() is None


# ----------------------------------------------------------------------
# cluster aggregation
# ----------------------------------------------------------------------


def _node_process(addr, eid, amount):
    """Child-process body: build a registry, count work, ship the
    snapshot on a heartbeat (what the node-side publisher + supervisor
    heartbeater pipeline does in production)."""
    from tensorflowonspark_tpu.cluster import reservation
    from tensorflowonspark_tpu.telemetry import MetricsRegistry

    reg = MetricsRegistry(enabled=True)
    reg.counter("worker.rows").inc(amount)
    reg.histogram("worker.step_sec").observe(0.01 * (eid + 1))
    client = reservation.Client(tuple(addr))
    client.heartbeat(eid, metrics=reg.snapshot(), host="node%d" % eid)
    client.close()


class TestClusterAggregation:
    def test_two_process_aggregation_over_reservation_server(self):
        # acceptance: TFCluster.metrics() in a multi-process test
        # returns merged snapshots from >= 2 node processes
        from tensorflowonspark_tpu.cluster import cluster as cl
        from tensorflowonspark_tpu.cluster import reservation

        server = reservation.Server(2)
        addr = server.start()
        try:
            ctx = multiprocessing.get_context("spawn")
            procs = [
                ctx.Process(
                    target=_node_process, args=(list(addr), eid, amount)
                )
                for eid, amount in ((0, 10), (1, 32))
            ]
            for p in procs:
                p.start()
            for p in procs:
                p.join(timeout=60)
                assert p.exitcode == 0
            # raw wire op: a remote observer's view
            executors, liveness = reservation.Client(addr).get_metrics()
            assert set(executors) == {"0", "1"}
            assert executors["0"]["metrics"]["counters"][
                "worker.rows"
            ] == 10
            assert set(liveness) == {"0", "1"}
            # driver-side merge through the cluster handle
            handle = cl.TFCluster(
                engine=None,
                cluster_meta={"id": "t", "elastic": False},
                cluster_info=[
                    {"executor_id": 0}, {"executor_id": 1}
                ],
                server=server,
                job_handle=None,
                input_mode=cl.InputMode.SPARK,
                queues=[],
            )
            view = handle.metrics(include_ledger=False)
            assert set(view["executors"]) == {0, 1}
            for eid in (0, 1):
                rec = view["executors"][eid]
                assert rec["metrics"]["counters"]["worker.rows"] in (
                    10, 32
                )
                assert rec["heartbeat_age"] >= 0.0
                assert rec["compute_alive"] is True
            fleet = view["fleet"]
            assert fleet["counters"]["worker.rows"] == 42
            assert fleet["histograms"]["worker.step_sec"]["count"] == 2
        finally:
            server.stop()

    def test_node_publisher_writes_manager_kv(self):
        class FakeMgr:
            def __init__(self):
                self.kv = {}

            def set(self, k, v):
                self.kv[k] = v

        reg = registry_mod.MetricsRegistry(enabled=True)
        reg.counter("n").inc(7)
        mgr = FakeMgr()
        pub = telemetry.NodePublisher(mgr, interval=60, registry=reg)
        assert pub.publish_once()
        assert mgr.kv["metrics"]["counters"]["n"] == 7

    def test_start_node_publisher_disabled_returns_none(self):
        telemetry.set_enabled(False)
        try:
            assert telemetry.start_node_publisher(object()) is None
        finally:
            telemetry.set_enabled(True)

    def test_heartbeater_metrics_fn_failure_is_bare_beat(self):
        # a raising metrics_fn must not break liveness
        from tensorflowonspark_tpu.cluster import reservation

        server = reservation.Server(1)
        addr = server.start()
        try:
            hb = reservation.Heartbeater(
                addr, 3, metrics_fn=lambda: 1 / 0
            )
            hb.beat_once()
            assert server.liveness.last_seen(3) is not None
            assert server.metrics.snapshot() == {}
            hb.stop()
        finally:
            server.stop()
