"""Event-journal tests (ISSUE 11): ring bounds, severity split, JSONL
rotation, the Tracer.mark -> Event bridge, shipping cursors, the
heartbeat piggyback + NTP-style clock-offset estimation, and the
clock-aligned Chrome-trace merge."""

import json
import os
import threading
import time

import pytest

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.cluster import reservation
from tensorflowonspark_tpu.telemetry import journal as journal_mod
from tensorflowonspark_tpu.telemetry.journal import Event, EventJournal
from tensorflowonspark_tpu.telemetry.tracing import Tracer, merge_traces

pytestmark = pytest.mark.forensics


# ----------------------------------------------------------------------
# ring bounds + severity split
# ----------------------------------------------------------------------


def test_ring_bound_and_dropped_counter():
    j = EventJournal(max_events=8, enabled=True)
    for i in range(20):
        j.emit("tick", i=i)
    evs = j.events()
    assert len(evs) == 8
    # the newest survive
    assert [e.attrs["i"] for e in evs] == list(range(12, 20))
    assert j.dropped_events == 12


def test_fault_ring_survives_info_flood():
    # the whole point of the severity split: routine traffic can never
    # evict the fault record an incident analysis needs
    j = EventJournal(max_events=4, enabled=True)
    j.emit("watchdog_fire", severity="page", chunk=3)
    for i in range(100):
        j.emit("emit", i=i)
    fire = j.events(kind="watchdog_fire")
    assert len(fire) == 1 and fire[0].severity == "page"
    assert len(j.events(severity="info")) == 4


def test_unknown_severity_normalizes_to_warn():
    assert Event("x", severity="catastrophic").severity == "warn"
    assert Event("x", severity="info").severity == "info"


def test_disabled_journal_stores_nothing():
    j = EventJournal(enabled=False)
    assert j.emit("x") is None
    assert j.events() == []


def test_filters_and_counts():
    j = EventJournal(enabled=True)
    j.emit("a", trace="t1")
    j.emit("b", severity="warn", trace="t1")
    j.emit("a", trace="t2")
    assert j.count("a") == 2
    assert j.count("b", severity="warn") == 1
    assert [e.kind for e in j.events(trace="t1")] == ["a", "b"]
    assert [e.kind for e in j.tail(1)] == ["a"]


# ----------------------------------------------------------------------
# JSONL persistence + rotation
# ----------------------------------------------------------------------


def test_jsonl_rotation_and_load(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    j = EventJournal(path=path, max_bytes=600, max_files=3, enabled=True)
    for i in range(60):
        j.emit("tick", severity="warn", i=i)
    # rotation happened and the live file stayed under the bound
    assert os.path.exists(path + ".1")
    assert os.path.getsize(path) <= 600
    loaded = journal_mod.load_journal(path)
    # rotated generations come back oldest-first, seq-ordered, and the
    # newest event is always retained
    seqs = [e.seq for e in loaded]
    assert seqs == sorted(seqs)
    assert loaded[-1].attrs["i"] == 59
    # the oldest generation past max_files is deleted, so retention is
    # bounded — some prefix may be gone
    assert len(loaded) <= 60


def test_load_journal_skips_torn_lines(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(Event("ok", seq=1).to_dict()) + "\n")
        f.write('{"kind": "torn", "ts": 1.0, "se\n')
    evs = journal_mod.load_journal(path)
    assert [e.kind for e in evs] == ["ok"]


def test_event_dict_round_trip():
    ev = Event("swap_rollback", executor=3, severity="page",
               trace="swap", attrs={"step": 7})
    back = Event.from_dict(json.loads(json.dumps(ev.to_dict())))
    assert (back.kind, back.executor, back.severity, back.trace,
            back.attrs, back.seq, back.pid) == (
        ev.kind, ev.executor, ev.severity, ev.trace, ev.attrs, ev.seq,
        ev.pid,
    )


# ----------------------------------------------------------------------
# the mark -> event bridge
# ----------------------------------------------------------------------


def test_mark_bridges_to_journal_with_fidelity():
    j = EventJournal(executor=5, enabled=True)
    tr = Tracer(enabled=True, journal=j)
    tr.mark("watchdog_fire", trace="serve", severity="page",
            attrs={"chunk": 3}, inflight=2)
    ev, = j.events()
    assert ev.kind == "watchdog_fire"
    assert ev.severity == "page"
    assert ev.trace == "serve"
    assert ev.executor == 5
    assert ev.attrs == {"chunk": 3, "inflight": 2}
    # the span record carries the same mark for old consumers
    sp, = tr.spans(name="watchdog_fire")
    assert sp["severity"] == "page"
    assert sp["attrs"] == {"chunk": 3, "inflight": 2}
    assert sp["dur"] == 0.0


def test_spans_do_not_emit_events():
    j = EventJournal(enabled=True)
    tr = Tracer(enabled=True, journal=j)
    with tr.span("prefill", trace="req0"):
        pass
    assert j.events() == []
    assert tr.count("prefill") == 1


def test_disabled_tracer_does_not_bridge():
    j = EventJournal(enabled=True)
    tr = Tracer(enabled=False, journal=j)
    tr.mark("watchdog_fire", severity="page")
    assert j.events() == []


def test_global_tracer_bridges_to_global_journal():
    jr = telemetry.get_journal()
    before = jr.count("journal_bridge_probe")
    telemetry.get_tracer().mark("journal_bridge_probe", severity="warn")
    assert jr.count("journal_bridge_probe") == before + 1


# ----------------------------------------------------------------------
# listeners + shipping cursor
# ----------------------------------------------------------------------


def test_listeners_fire_and_raisers_are_contained():
    j = EventJournal(enabled=True)
    seen = []

    def bad(ev):
        raise RuntimeError("listener boom")

    j.add_listener(bad)
    j.add_listener(seen.append)
    ev = j.emit("restart", severity="warn")
    assert seen == [ev]
    j.remove_listener(seen.append)
    j.emit("restart", severity="warn")
    assert len(seen) == 1


def test_drain_unshipped_cursor_semantics():
    j = EventJournal(enabled=True)
    for i in range(5):
        j.emit("tick", i=i)
    first = j.drain_unshipped(limit=3)
    assert [e.attrs["i"] for e in first] == [0, 1, 2]
    second = j.drain_unshipped(limit=10)
    assert [e.attrs["i"] for e in second] == [3, 4]
    assert j.drain_unshipped() == []
    j.emit("tick", i=5)
    assert [e.attrs["i"] for e in j.drain_unshipped()] == [5]


# ----------------------------------------------------------------------
# clock-offset estimation
# ----------------------------------------------------------------------


def test_estimate_offset_recovers_known_skew():
    # a node whose clock runs 5s AHEAD of the server: its t0/t1 are
    # server time + 5, so the estimated offset (to ADD to node stamps
    # to reach server time) must be ~-5
    skew, rtt = 5.0, 0.2
    server_now = 1000.0
    t0 = server_now + skew
    server_time = server_now + rtt / 2.0  # symmetric path
    t1 = t0 + rtt
    offset, got_rtt = reservation.estimate_offset(t0, server_time, t1)
    assert offset == pytest.approx(-skew, abs=1e-9)
    assert got_rtt == pytest.approx(rtt)


def test_clock_sync_picks_min_rtt_sample():
    cs = reservation.ClockSync()
    cs.update(1, offset=0.9, rtt=0.5)    # congested sample, bad offset
    cs.update(1, offset=0.1, rtt=0.01)   # clean exchange
    cs.update(1, offset=0.7, rtt=0.3)
    assert cs.offset(1) == pytest.approx(0.1)
    snap = cs.snapshot()
    assert snap["1"]["rtt"] == pytest.approx(0.01)
    assert cs.offset(2) is None
    cs.update(2, offset="junk", rtt="junk")  # unparseable: ignored
    assert cs.offset(2) is None


# ----------------------------------------------------------------------
# server-side EventStore
# ----------------------------------------------------------------------


def test_event_store_dedups_by_pid_seq_and_stamps_executor():
    store = reservation.EventStore(max_events=100)
    evs = [Event("restart", seq=i, pid=10).to_dict() for i in (1, 2)]
    assert store.extend(3, evs) == 2
    # a re-shipped frame (heartbeat retry) adds nothing
    assert store.extend(3, evs) == 0
    # the same seq from a RESTARTED process (new pid) is a new event
    assert store.extend(3, [Event("restart", seq=1, pid=11).to_dict()]) == 1
    out = store.snapshot()
    assert len(out) == 3
    assert all(e["executor"] == 3 for e in out)


def test_event_store_is_bounded_and_time_ordered():
    store = reservation.EventStore(max_events=4)
    for i in range(10):
        store.extend(0, [Event("tick", seq=i + 1, ts=100.0 - i).to_dict()])
    out = store.snapshot()
    assert len(out) == 4
    assert [e["ts"] for e in out] == sorted(e["ts"] for e in out)
    assert store.snapshot(limit=2) == out[-2:]


# ----------------------------------------------------------------------
# heartbeat piggyback e2e (real server, real sockets)
# ----------------------------------------------------------------------


def test_heartbeat_ships_events_and_clock_sample():
    server = reservation.Server(1)
    addr = server.start()
    try:
        j = EventJournal(executor=0, enabled=True)
        j.emit("restart", severity="warn", restart=1)
        j.emit("leader_elected", leader=0)
        hb = reservation.Heartbeater(
            addr, 0, interval=0.05,
            events_fn=lambda: [e.to_dict() for e in j.drain_unshipped()],
        )
        hb.beat_once()   # first beat: ships events, takes clock sample
        hb.beat_once()   # second beat: reports the sample
        events, clocks = reservation.Client(addr).get_journal()
        kinds = {e["kind"] for e in events}
        assert {"restart", "leader_elected"} <= kinds
        assert all(e["executor"] == 0 for e in events)
        # same-host clocks: offset ~0, rtt tiny but positive
        assert "0" in clocks
        assert abs(clocks["0"]["offset"]) < 1.0
        assert clocks["0"]["rtt"] >= 0.0
        # a re-beat does not duplicate (drained + server-side dedup)
        hb.beat_once()
        events2, _ = reservation.Client(addr).get_journal()
        assert len(events2) == len(events)
        hb.stop(farewell=False)
    finally:
        server.stop()


def test_heartbeat_retains_events_across_a_failed_beat():
    # events handed to a beat that never reached the server must ride
    # the next successful one
    server = reservation.Server(1)
    addr = server.start()
    try:
        shipped = [False]

        def events_fn():
            if shipped[0]:
                return None
            shipped[0] = True
            return [Event("restart", seq=7, pid=42).to_dict()]

        hb = reservation.Heartbeater(
            ("127.0.0.1", 1), 0, interval=0.05,  # nothing listens here
            events_fn=events_fn,
        )
        with pytest.raises(Exception):
            hb.beat_once()
        assert [e["seq"] for e in hb._event_backlog] == [7]
        # the server comes back: the retained event ships with the
        # next beat even though events_fn has nothing new
        hb.server_addr = tuple(addr)
        hb._client = None
        hb.beat_once()
        assert hb._event_backlog == []
        events, _ = reservation.Client(addr).get_journal()
        assert any(
            e["kind"] == "restart" and e["seq"] == 7 for e in events
        )
        hb.stop(farewell=False)
    finally:
        server.stop()


def test_server_attaches_driver_journal_to_fleet_store():
    # driver-side events (the monitor's executor_dead verdict) ride no
    # heartbeat; the server bridges its own process's journal in
    server = reservation.Server(1)
    server.start()
    try:
        server.attach_local_journal()
        telemetry.get_tracer().mark(
            "executor_dead", severity="page", executor_id=2,
        )
        evs = [
            e for e in server.events.snapshot()
            if e["kind"] == "executor_dead"
        ]
        assert evs and evs[-1]["executor"] == -1
        assert evs[-1]["attrs"]["executor_id"] == 2
    finally:
        server.stop()
    # detached on stop: further marks don't land
    n = len(server.events.snapshot())
    telemetry.get_tracer().mark("executor_dead", severity="page")
    assert len(server.events.snapshot()) == n


def test_cluster_monitor_metrics_carries_clock_offset():
    from tensorflowonspark_tpu.cluster.cluster import ClusterMonitor

    server = reservation.Server(1)
    addr = server.start()
    try:
        hb = reservation.Heartbeater(addr, 0, interval=0.05)
        hb.beat_once()
        hb.beat_once()  # the second beat reports the first's sample
        mon = ClusterMonitor(server, [])
        per = mon.metrics()
        assert "clock_offset" in per[0]
        assert abs(per[0]["clock_offset"]) < 1.0
        hb.stop(farewell=False)
    finally:
        server.stop()


# ----------------------------------------------------------------------
# NodePublisher journal mirror + supervisor cursor
# ----------------------------------------------------------------------


class _FakeMgr(object):
    def __init__(self):
        self.kv = {}

    def set(self, key, value):
        self.kv[key] = value

    def get(self, key):
        return self.kv.get(key)


def test_node_publisher_mirrors_journal_into_kv():
    from tensorflowonspark_tpu.telemetry.aggregate import NodePublisher

    j = EventJournal(enabled=True)
    mgr = _FakeMgr()
    pub = NodePublisher(mgr, journal=j)
    assert pub.publish_journal() is False  # nothing to publish yet
    j.emit("watchdog_fire", severity="page")
    assert pub.publish_journal() is True
    rec = mgr.kv["journal_events"]
    assert rec["pid"] == os.getpid()
    assert rec["events"][0]["kind"] == "watchdog_fire"
    # unchanged journal -> no re-publish churn
    assert pub.publish_journal() is False
    j.emit("restart", severity="warn")
    assert pub.publish_journal() is True
    assert len(mgr.kv["journal_events"]["events"]) == 2


def test_supervisor_event_cursor_resets_on_new_pid():
    from tensorflowonspark_tpu.cluster.supervisor import Supervisor

    sup = object.__new__(Supervisor)
    sup._journal_cursor = (0, 0)

    class _Ctx(object):
        executor_id = 4

    sup.ctx = _Ctx()
    sup.mgr = _FakeMgr()
    # the supervisor's own journal is the GLOBAL one; isolate by
    # draining it first so this test only sees the kv events
    telemetry.get_journal().drain_unshipped(limit=10 ** 6)
    sup.mgr.set("journal_events", {
        "pid": 10,
        "events": [Event("restart", seq=1, pid=10).to_dict(),
                   Event("restart", seq=2, pid=10).to_dict()],
    })
    out = sup._node_events() or []
    kv_events = [e for e in out if e.get("pid") == 10]
    assert len(kv_events) == 2
    assert all(e["executor"] == 4 for e in kv_events)
    # same frame again: cursor filters it
    assert not [
        e for e in (sup._node_events() or []) if e.get("pid") == 10
    ]
    # a RESPAWNED compute process (fresh pid) resets the cursor
    sup.mgr.set("journal_events", {
        "pid": 11, "events": [Event("restart", seq=1, pid=11).to_dict()],
    })
    out = sup._node_events() or []
    assert [e for e in out if e.get("pid") == 11]


# ----------------------------------------------------------------------
# clock-aligned Chrome-trace merge (satellite)
# ----------------------------------------------------------------------


def _skewed_trace(skew, n=4, step=0.010):
    """A Chrome trace whose ts embed a wall-clock skew (microseconds)."""
    events = []
    for i in range(n):
        events.append({
            "name": "step", "ph": "X",
            "ts": round((100.0 + skew + i * step) * 1e6, 3),
            "dur": round(step / 2 * 1e6, 3),
            "pid": os.getpid(), "tid": 1, "args": {},
        })
    return {"traceEvents": events}


def test_merge_traces_aligns_and_orders():
    # executor 1's clock runs 3s ahead; without alignment its events
    # all land after executor 0's, interleaved wrongly
    a = _skewed_trace(0.0)
    b = _skewed_trace(3.0)
    merged = merge_traces([
        (a, 0.0, "executor0"),
        (b, -3.0, "executor1"),   # ClockSync offset: add -3s
    ])
    xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 8
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts)
    # after alignment the two executors' steps interleave pairwise
    pids = [e["pid"] for e in xs]
    assert pids[:2] in ([0, 1], [1, 0])
    # metadata rows name both processes, pids are distinct per part
    names = {
        (e["pid"], e["args"]["name"])
        for e in merged["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert names == {(0, "executor0"), (1, "executor1")}


def test_tracer_export_carries_process_and_thread_metadata():
    tr = Tracer(enabled=True, journal=EventJournal(enabled=True))
    tr.process_name = "executor7"
    with tr.span("step"):
        pass
    out = tr.export_chrome()
    metas = [e for e in out["traceEvents"] if e["ph"] == "M"]
    assert {"process_name", "thread_name"} == {m["name"] for m in metas}
    assert metas[0]["args"]["name"] == "executor7"
    tid = threading.get_ident()
    assert any(
        m["name"] == "thread_name" and m["tid"] == tid for m in metas
    )


def test_span_start_is_on_the_wall_clock():
    tr = Tracer(enabled=True, journal=EventJournal(enabled=True))
    before = time.time()
    with tr.span("step"):
        time.sleep(0.01)
    sp, = tr.spans(name="step")
    # the absolute start: the journal events' clock, no epoch to add
    assert before - 1e-3 <= sp["t0"] <= time.time() - 0.01 + 1e-3
