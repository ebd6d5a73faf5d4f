"""Flight-recorder + forensics-analyzer tests (ISSUE 11): dump
triggers per fault kind, bundle contents, rate limits, kv index
publication, critical-path math, clock-aligned explain reports, and
the chaos e2e — a ``wedge_dispatch`` + ``kill_leader`` plan must
produce dumps whose ``explain`` report names the injected fault kinds
and the affected executor."""

import json
import os

import numpy as np
import pytest

from tensorflowonspark_tpu import forensics, serving, telemetry
from tensorflowonspark_tpu.telemetry import blackbox as blackbox_mod
from tensorflowonspark_tpu.telemetry.blackbox import FlightRecorder
from tensorflowonspark_tpu.telemetry.journal import Event, EventJournal
from tensorflowonspark_tpu.telemetry.tracing import Tracer
from tensorflowonspark_tpu.testing import chaos

pytestmark = pytest.mark.forensics


def _recorder(tmp_path, executor=None, **kw):
    j = EventJournal(executor=executor, enabled=True)
    tr = Tracer(enabled=True, journal=j)
    kw.setdefault("min_interval", 0.0)
    rec = FlightRecorder(
        journal=j, tracer=tr, dump_dir=str(tmp_path), **kw
    ).start()
    return j, tr, rec


# ----------------------------------------------------------------------
# dump triggers
# ----------------------------------------------------------------------


@pytest.mark.parametrize("kind", sorted(blackbox_mod.DUMP_TRIGGERS))
def test_every_trigger_kind_dumps(tmp_path, kind):
    j, _tr, rec = _recorder(tmp_path, executor=2)
    j.emit(kind, severity="warn")
    assert len(rec.dumps) == 1
    assert rec.dumps[0]["reason"] == kind
    bundle = blackbox_mod.load_dump(rec.dumps[0]["path"])
    assert bundle["reason"] == kind
    assert bundle["executor"] == 2
    assert bundle["trigger"]["kind"] == kind
    rec.stop()


def test_page_severity_always_dumps_and_info_never(tmp_path):
    j, _tr, rec = _recorder(tmp_path)
    j.emit("emit", trace="req3")                    # routine: no dump
    j.emit("some_novel_alert", severity="page")     # page: dumps
    assert [d["reason"] for d in rec.dumps] == ["some_novel_alert"]
    rec.stop()


def test_mark_to_dump_path_is_end_to_end(tmp_path):
    # the full production chain: a fault site calls tracer.mark ->
    # journal event -> recorder listener -> bundle on disk
    j, tr, rec = _recorder(tmp_path, executor=1)
    tr.mark("watchdog_fire", trace="serve", severity="page", chunk=5)
    assert len(rec.dumps) == 1
    bundle = blackbox_mod.load_dump(rec.dumps[0]["path"])
    assert bundle["trigger"]["attrs"]["chunk"] == 5
    # the mark itself is in the bundle's rings, both as event and span
    assert any(e["kind"] == "watchdog_fire" for e in bundle["events"])
    assert any(s["name"] == "watchdog_fire" for s in bundle["spans"])
    rec.stop()


def test_rate_limit_and_cap(tmp_path):
    j, _tr, rec = _recorder(tmp_path, min_interval=3600.0, max_dumps=2)
    j.emit("watchdog_fire", severity="warn")
    j.emit("watchdog_fire", severity="warn")  # inside the interval
    assert len(rec.dumps) == 1
    j.emit("swap_rollback", severity="page")  # different kind: dumps
    assert len(rec.dumps) == 2
    j.emit("executor_dead", severity="page")  # over the cap
    assert len(rec.dumps) == 2
    assert rec.registry.counter("blackbox.dumps_suppressed").value >= 2
    rec.stop()


def test_bundle_contents_and_clock_anchor(tmp_path):
    j, tr, rec = _recorder(tmp_path)
    with tr.span("step", trace="t1"):
        with tr.span("dispatch", trace="t1"):
            pass
    j.emit("restart", severity="warn", restart=1)
    bundle = blackbox_mod.load_dump(rec.dumps[0]["path"])
    assert bundle["format"] == blackbox_mod.BUNDLE_FORMAT
    assert bundle["pid"] == os.getpid()
    # span starts are absolute (Unix seconds, the events' clock): the
    # bundle carries no epoch to add to them
    assert "clock" not in bundle
    assert all(
        abs(s["t0"] - bundle["time"]) < 60.0 for s in bundle["spans"]
    )
    assert {s["name"] for s in bundle["spans"]} >= {"step", "dispatch"}
    assert "counters" in bundle["metrics"]
    rec.stop()


def test_old_bundle_with_epoch_wall_loads_onto_absolute_starts(tmp_path):
    # a dump written before span starts were absolute: relative t0
    # plus clock.epoch_wall; forensics moves them as it loads
    path = tmp_path / "old.json"
    path.write_text(json.dumps({
        "format": blackbox_mod.BUNDLE_FORMAT, "executor": 0, "pid": 1,
        "events": [], "clock": {"epoch_wall": 1000.0},
        "spans": [{"name": "step", "trace": "t", "id": 1, "t0": 2.5,
                   "dur": 0.1, "tid": 1}],
    }))
    src, = forensics.load_sources([str(path)])
    assert src["spans"][0]["t0"] == pytest.approx(1002.5)
    ev, = [e for e in forensics.merged_chrome([str(path)])["traceEvents"]
           if e.get("ph") == "X"]
    assert ev["ts"] == pytest.approx(1002.5e6)


def test_load_dump_rejects_non_bundles(tmp_path):
    p = tmp_path / "not_a_bundle.json"
    p.write_text(json.dumps({"hello": 1}))
    with pytest.raises(ValueError, match="bundle"):
        blackbox_mod.load_dump(str(p))


def test_attach_kv_publishes_dump_index(tmp_path):
    class _Mgr(object):
        def __init__(self):
            self.kv = {}

        def set(self, key, value):
            self.kv[key] = value

    mgr = _Mgr()
    j, _tr, rec = _recorder(tmp_path, executor=3)
    rec.attach_kv(mgr)
    j.emit("watchdog_fire", severity="page")
    index = mgr.kv["blackbox_dumps"]
    assert len(index) == 1
    assert index[0]["reason"] == "watchdog_fire"
    assert index[0]["executor"] == 3
    assert os.path.exists(index[0]["path"])
    rec.stop()


def test_install_respects_kill_switch(monkeypatch):
    monkeypatch.setenv(blackbox_mod.BLACKBOX_ENV, "0")
    assert blackbox_mod.install() is None


# ----------------------------------------------------------------------
# critical path
# ----------------------------------------------------------------------


def _span(name, sid, t0, dur, parent=None, trace="t1"):
    s = {"name": name, "id": sid, "t0": t0, "dur": dur, "tid": 1,
         "trace": trace}
    if parent is not None:
        s["parent"] = parent
    return s


def test_critical_path_descends_into_last_ending_child():
    spans = [
        _span("step", 1, 0.0, 1.0),
        _span("feed", 2, 0.0, 0.2, parent=1),
        _span("dispatch", 3, 0.3, 0.7, parent=1),   # ends last: on path
        _span("h2d", 4, 0.35, 0.1, parent=3),
        _span("device", 5, 0.5, 0.5, parent=3),     # ends last: on path
    ]
    cp = forensics.critical_path(spans)
    assert [l["name"] for l in cp["path"]] == ["step", "dispatch",
                                               "device"]
    assert cp["total_sec"] == pytest.approx(1.0)
    # exclusive contributions: step 0.3, dispatch 0.2, device 0.5
    assert cp["path"][0]["self_sec"] == pytest.approx(0.3)
    assert cp["path"][1]["self_sec"] == pytest.approx(0.2)
    assert cp["path"][2]["self_sec"] == pytest.approx(0.5)
    assert cp["dominant_phase"] == "device"


def test_critical_path_ignores_marks_and_handles_empty():
    assert forensics.critical_path([])["path"] == []
    marks_only = [_span("watchdog_fire", 1, 0.5, 0.0)]
    assert forensics.critical_path(marks_only)["path"] == []


def test_busiest_trace_is_a_request_never_the_scheduler():
    # the scheduler's own trace holds every pass of the job: the most
    # span time by far, and no request's story
    spans = [_span("engine.chunk", i, float(i), 0.9, trace="engine")
             for i in range(1, 9)]
    spans.append(_span("prefill", 20, 1.0, 0.5, trace="req7"))
    assert forensics._busiest_trace(spans) == "req7"


# ----------------------------------------------------------------------
# timeline alignment + explain
# ----------------------------------------------------------------------


def test_build_timeline_applies_offsets_and_dedups():
    sources = [
        {"path": "a", "executor": 0, "pid": 10, "offset": 0.0,
         "events": [Event("restart", ts=100.0, seq=1, pid=10,
                          executor=0, severity="warn").to_dict()],
         "spans": []},
        # executor 1's clock runs 5s ahead; its event REALLY happened
        # first — only the -5s offset reveals that
        {"path": "b", "executor": 1, "pid": 11, "offset": -5.0,
         "events": [Event("watchdog_fire", ts=104.0, seq=1, pid=11,
                          executor=1, severity="page").to_dict()],
         "spans": []},
        # the same executor-0 event again (journal export + dump both
        # present): deduped
        {"path": "c", "executor": 0, "pid": 10, "offset": 0.0,
         "events": [Event("restart", ts=100.0, seq=1, pid=10,
                          executor=0, severity="warn").to_dict()],
         "spans": []},
    ]
    tl = forensics.build_timeline(sources)
    assert [e["kind"] for e in tl] == ["watchdog_fire", "restart"]
    assert tl[0]["t"] == pytest.approx(99.0)
    # an explicit offsets map overrides the per-source one
    tl2 = forensics.build_timeline(sources, offsets={1: 0.0})
    assert [e["kind"] for e in tl2] == ["restart", "watchdog_fire"]


def test_explain_names_fault_and_executor_from_dump(tmp_path):
    import time

    j, tr, rec = _recorder(tmp_path, executor=4)
    with tr.span("step", trace="t9"):
        with tr.span("dispatch", trace="t9"):
            time.sleep(0.02)
    tr.mark("leader_failover", trace="hier", severity="page",
            dead_member=4)
    report = forensics.explain([str(tmp_path)])
    assert report["incident"]["fault_kind"] == "kill_leader"
    assert report["incident"]["trigger"] == "leader_failover"
    assert report["incident"]["executor"] == 4
    assert report["critical_path"]["path"]
    assert report["critical_path"]["dominant_phase"] == "dispatch"
    text = forensics.render_report(report)
    assert "kill_leader" in text
    assert "executor 4" in text
    rec.stop()


def test_explain_reads_cluster_journal_export(tmp_path):
    export = {
        "events": [
            Event("executor_restart", ts=50.0, seq=1, pid=1,
                  executor=2, severity="warn").to_dict(),
            Event("executor_dead", ts=60.0, seq=2, pid=1, executor=2,
                  severity="page",
                  attrs={"reason": "no heartbeat"}).to_dict(),
        ],
        "clocks": {"2": {"offset": -1.5, "rtt": 0.01}},
    }
    p = tmp_path / "journal_export.json"
    p.write_text(json.dumps(export))
    report = forensics.explain([str(p)])
    # the ClockSync offset in the export is applied
    assert report["timeline"][0]["t"] == pytest.approx(48.5)
    assert report["incident"]["fault_kind"] == "kill"
    assert report["incident"]["executor"] == 2
    assert report["executors"] == [2]


def test_cli_explain_writes_report_and_trace(tmp_path, capsys):
    j, tr, rec = _recorder(tmp_path / "dumps", executor=0)
    with tr.span("step", trace="t1"):
        pass
    tr.mark("watchdog_fire", trace="serve", severity="page")
    out_txt = tmp_path / "report.txt"
    out_trace = tmp_path / "merged.json"
    rc = forensics.main([
        "explain", str(tmp_path / "dumps"),
        "--out", str(out_txt), "--trace", str(out_trace),
    ])
    assert rc == 0
    assert "wedge_dispatch" in out_txt.read_text()
    merged = json.loads(out_trace.read_text())
    assert any(
        e["name"] == "step" for e in merged["traceEvents"]
    )
    assert "incident forensics" in capsys.readouterr().out
    rec.stop()


# ----------------------------------------------------------------------
# SLO alert history (satellite): page alert -> history + dump
# ----------------------------------------------------------------------


def test_page_alert_dumps_and_lands_in_alert_history(tmp_path):
    from tensorflowonspark_tpu.telemetry.health import HealthPlane

    jr = telemetry.get_journal()
    rec = FlightRecorder(
        journal=jr, tracer=telemetry.get_tracer(),
        dump_dir=str(tmp_path), min_interval=0.0,
    ).start()
    try:
        reg = telemetry.get_registry()
        plane = HealthPlane.local(
            interval=3600,  # scrape manually
            slo=[{"name": "always-fires", "metric": "bb.latency_sec",
                  "stat": "p99", "op": "<", "threshold": 1e-12,
                  "window": 300, "severity": "page"}],
        )
        reg.histogram("bb.latency_sec").observe(0.5)
        plane.scrape_once()
        status = plane.status()
        hist = status["alert_history"]
        assert hist and hist[-1]["rule"] == "always-fires"
        assert hist[-1]["state"] == "firing"
        assert hist[-1]["t"] > 0
        # the page-severity alert_firing mark triggered a dump
        assert any(
            d["reason"] == "alert_firing" for d in rec.dumps
        )
        plane.stop()
    finally:
        rec.stop()


# ----------------------------------------------------------------------
# the chaos e2e: wedge_dispatch + kill_leader -> dumps -> explain
# ----------------------------------------------------------------------


TINY = {
    "vocab_size": 64, "num_layers": 1, "num_heads": 2, "head_dim": 8,
    "embed_dim": 16, "mlp_dim": 32, "max_seq_len": 64,
    "dtype": "float32",
}


def test_incident_e2e_wedge_and_kill_leader(tmp_path, monkeypatch):
    """The acceptance e2e: a chaos plan wedges a serving dispatch AND
    kills the hierarchical DCN leader; both faults must land in
    flight-recorder dumps whose ``explain`` report names the injected
    fault kinds, the triggering event, the affected executor, and a
    clock-aligned timeline with a computed critical path."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models import transformer as tr_mod
    from tensorflowonspark_tpu.parallel import hier_ps, ps

    plan = chaos.ChaosPlan().wedge_dispatch(1, hang_sec=1.0)
    plan.kill_leader(at_window=2)
    plan.save(tmp_path / "plan.json")
    monkeypatch.setenv(chaos.TFOS_CHAOS_PLAN,
                       str(tmp_path / "plan.json"))

    jr = telemetry.get_journal()
    jr.clear()
    jr.set_identity(1)  # this process plays executor 1
    dump_dir = tmp_path / "dumps"
    rec = FlightRecorder(
        journal=jr, tracer=telemetry.get_tracer(),
        dump_dir=str(dump_dir), min_interval=0.0,
    ).start()
    try:
        # -- fault 1: the wedged serving dispatch -----------------------
        model = tr_mod.Transformer(tr_mod.TransformerConfig(**TINY))
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        predict = tr_mod.serving_builder(
            jax.tree.map(np.asarray, params),
            dict(TINY, mode="generate", max_new_tokens=6,
                 pad_multiple=16, chunk_size=2),
        )
        rng = np.random.RandomState(7)
        rows = [
            {"prompt": rng.randint(0, 64, (n,)).astype(np.int32)}
            for n in (4, 6, 5)
        ]
        out = list(serving.predict_rows(
            predict, rows, {"prompt": "tokens"}, batch_size=2,
            schedule="continuous", watchdog_timeout=0.25,
        ))
        assert len(out) == len(rows)  # recovery dropped nothing

        # -- fault 2: the killed DCN leader -----------------------------
        TARGET = np.asarray([1.0, -2.0, 0.5, 3.0], np.float32)

        def quad_loss(p, batch):
            del batch
            return jnp.sum((p["w"] - TARGET) ** 2)

        shard = ps.ParamServerShard()
        _, port = shard.start("127.0.0.1", 0)
        try:
            trainer = hier_ps.HierTrainer(
                quad_loss, ["127.0.0.1:%d" % port],
                optimizer=("sgd", {"learning_rate": 0.05}),
                push_every=2, members=(0, 1), member_id=0,
                fault_fn=chaos.hier_leader_fault_fn(),
            )
            trainer.init({"w": np.zeros(4, np.float32)})
            for _ in range(30):
                trainer.step(None)
            trainer.drain()
            trainer.stop()
        finally:
            shard.stop()

        # -- both faults left dumps -------------------------------------
        reasons = {d["reason"] for d in rec.dumps}
        assert "watchdog_fire" in reasons
        assert "leader_failover" in reasons

        # -- and the explain report reconstructs the incident -----------
        report = forensics.explain([str(dump_dir)])
        assert report["incident"]["trigger"] == "watchdog_fire"
        assert report["incident"]["fault_kind"] == "wedge_dispatch"
        assert report["incident"]["executor"] == 1
        fault_kinds = {
            forensics.FAULT_MAP.get(ev["kind"])
            for ev in report["faults"]
        }
        assert {"wedge_dispatch", "kill_leader"} <= fault_kinds
        # clock-aligned causal ordering: the wedge preceded the kill
        ts = [e["t"] for e in report["timeline"]]
        assert ts == sorted(ts)
        kinds_in_order = [e["kind"] for e in report["timeline"]
                          if e["kind"] in forensics.FAULT_KINDS]
        assert kinds_in_order.index("watchdog_fire") < (
            kinds_in_order.index("leader_failover")
        )
        # the critical path names real serving work
        cp = report["critical_path"]
        assert cp["path"] and cp["total_sec"] > 0
        text = forensics.render_report(report)
        assert "wedge_dispatch" in text
        assert "executor 1" in text
    finally:
        rec.stop()
        jr.set_identity(None)
        jr.clear()
