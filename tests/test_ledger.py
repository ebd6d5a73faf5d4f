"""Cost-attribution plane tests (ISSUE 14 tentpole).

Covers the usage ledger (per-request resource rows, per-tenant
aggregation under the reserved "tenant" input, the space-saving
heavy-hitter sketch, snapshot/delta/merge, the registry mirror that
rides the heartbeat piggyback), the fleet-wide request tracing (the
router-minted trace id threading router → replica → engine span
chains, continued across a replica death), latency exemplars on the
shared histogram + the forensics p99 pull, the ``/usage`` exposition
route, and the ACCEPTANCE e2e: a 2-replica fleet run at 2x admission
capacity with a mid-decode ``kill_replica`` whose merged trace is
connected and clock-aligned, whose ledger token totals exactly match
the emitted outputs, whose chip-second rows sum to the measured decode
wall time, and whose ``/usage`` response round-trips the strict
OpenMetrics parser.
"""

import json
import os
import urllib.request

import numpy as np
import pytest

from tensorflowonspark_tpu import serving, serving_engine, telemetry
from tensorflowonspark_tpu.fleet.router import FleetRouter
from tensorflowonspark_tpu.telemetry import ledger as ledger_mod
from tensorflowonspark_tpu.telemetry import registry as registry_mod
from tensorflowonspark_tpu.testing import chaos

from test_fleet import (  # noqa: F401 - shared fakes/fixtures
    TINY,
    FakePredict,
    _gen_predict,
    _prompts,
    _same_tokens,
)


@pytest.fixture()
def fresh_ledger():
    led = ledger_mod.get_ledger()
    led.enabled_override = None
    led.reset()
    yield led
    led.enabled_override = None
    led.reset()


def _run_engine(rows, mapping, **opts):
    eng = serving_engine.ServingEngine(
        FakePredict(chunk=2, max_new=4), mapping, None, 2,
        on_error="record", **opts
    )
    return eng, list(eng.serve([dict(r) for r in rows]))


# ----------------------------------------------------------------------
# the space-saving sketch
# ----------------------------------------------------------------------


class TestSpaceSaving:
    def test_exact_under_capacity(self):
        sk = ledger_mod.SpaceSaving(capacity=4)
        for key, w in [("a", 5), ("b", 3), ("a", 2), ("c", 1)]:
            sk.add(key, w)
        assert sk.estimate("a") == (7.0, 0.0)
        assert sk.estimate("b") == (3.0, 0.0)
        assert sk.top() == [("a", 7.0, 0.0), ("b", 3.0, 0.0),
                            ("c", 1.0, 0.0)]

    def test_eviction_inherits_min_count_as_error(self):
        sk = ledger_mod.SpaceSaving(capacity=2)
        sk.add("a", 10)
        sk.add("b", 2)
        sk.add("c", 1)  # evicts b (min=2): count 3, err 2
        est, err = sk.estimate("c")
        assert est == 3.0 and err == 2.0
        # the space-saving guarantee: true count within [est-err, est]
        assert est - err <= 1 <= est

    def test_heavy_hitter_survives_churn(self):
        # any key with true weight > total/capacity is guaranteed
        # tracked — the algorithm's defining property
        sk = ledger_mod.SpaceSaving(capacity=4)
        for i in range(200):
            sk.add("heavy", 2.0)
            sk.add("noise-%d" % i, 1.0)
        assert "heavy" in dict(
            (k, c) for k, c, _e in sk.top()
        )
        est, err = sk.estimate("heavy")
        assert est - err <= 400.0 <= est

    def test_zero_and_negative_weights_ignored(self):
        sk = ledger_mod.SpaceSaving(capacity=2)
        sk.add("a", 0.0)
        sk.add("a", -1.0)
        assert sk.total == 0.0 and len(sk) == 0


# ----------------------------------------------------------------------
# ledger core: rows, tenant aggregation, bounds, snapshot algebra
# ----------------------------------------------------------------------


class TestUsageLedger:
    def test_row_and_tenant_totals_agree(self, fresh_ledger):
        led = fresh_ledger
        led.open("r1", tenant="acme", tokens_in=10, wire_bytes=40,
                 prefix_tokens_saved=8, queue_wait_sec=0.5)
        led.charge("r1", chip_sec=0.25, page_sec=1.5)
        led.charge("r1", chip_sec=0.25, page_sec=1.5)
        led.close("r1", tokens_out=6, latency_sec=1.0)
        row = led.row("r1")
        t = led.tenants()["acme"]
        for field in ledger_mod.FIELDS:
            assert row[field] == t[field], field
        assert t == {
            "requests": 1, "tokens_in": 10, "tokens_out": 6,
            "queue_wait_sec": 0.5, "chip_sec": 0.5,
            "prefill_chip_sec": 0.0, "page_sec": 3.0,
            "prefix_tokens_saved": 8, "wire_bytes": 40,
        }

    def test_set_if_unset_and_reclose_delta(self, fresh_ledger):
        # the fleet pattern: router opens with the user-facing prompt,
        # the replica engine re-opens with prompt+committed (ignored),
        # closes with its continuation count, the router re-closes
        # with the merged total — the aggregate lands on the final
        # value exactly once
        led = fresh_ledger
        led.open("r1", tenant="acme", tokens_in=10)       # router
        led.open("r1", tenant="acme", tokens_in=14)       # engine B
        led.close("r1", tokens_out=4)                     # engine B
        led.close("r1", tokens_out=9)                     # router
        t = led.tenants()["acme"]
        assert t["tokens_in"] == 10
        assert t["tokens_out"] == 9
        assert t["requests"] == 1

    def test_settle_is_one_shot_and_rid_recycles(self, fresh_ledger):
        led = fresh_ledger
        led.settle("req0", tenant="a", tokens_in=5, chip_sec=0.1,
                   tokens_out=3, latency_sec=0.2)
        # a NEW job reusing the engine-local rid must get a FRESH row,
        # never a delta against the previous job's closed row
        led.settle("req0", tenant="a", tokens_in=7, chip_sec=0.2,
                   tokens_out=2, latency_sec=0.1)
        t = led.tenants()["a"]
        assert t["requests"] == 2
        assert t["tokens_in"] == 12
        assert t["tokens_out"] == 5
        assert round(t["chip_sec"], 6) == 0.3

    def test_default_tenant_when_absent(self, fresh_ledger):
        led = fresh_ledger
        led.record("r1", tokens_in=3, tokens_out=2)
        assert ledger_mod.DEFAULT_TENANT in led.tenants()

    def test_rows_bounded_closed_evict_open_survive(self):
        led = ledger_mod.UsageLedger(max_rows=4)
        led.open("open-1", tenant="a", tokens_in=1)
        for i in range(8):
            led.record("r%d" % i, tenant="a", tokens_in=1, tokens_out=1)
        assert len(led.rows()) <= 4
        assert led.rows_evicted == 5
        assert led.row("open-1") is not None  # open rows never evict
        # totals survive row eviction (aggregates fold incrementally)
        assert led.tenants()["a"]["tokens_out"] == 8

    def test_eviction_conserves_chip_seconds(self):
        # the soak harness's exactness probe sums rows() PLUS the
        # evicted remainder: charge a known chip total through a tiny
        # table and assert conservation holds after LRU eviction
        led = ledger_mod.UsageLedger(max_rows=4)
        for i in range(12):
            led.settle("r%d" % i, tenant="a", tokens_in=1,
                       tokens_out=1, chip_sec=0.25)
        assert len(led.rows()) <= 4
        assert led.rows_evicted == 8
        retained = sum(r["chip_sec"] for r in led.rows())
        assert retained + led.evicted_totals["chip_sec"] == (
            pytest.approx(12 * 0.25)
        )
        assert led.snapshot()["evicted_totals"]["chip_sec"] == (
            pytest.approx(led.evicted_totals["chip_sec"])
        )

    def test_closed_rid_reopen_folds_prior_charges(self):
        # open() on a CLOSED rid mints a fresh row (re-used trace id =
        # a new request incarnation); the prior incarnation's charges
        # must move to the remainder, not vanish from the ledger
        led = ledger_mod.UsageLedger(max_rows=64)
        led.settle("r1", tokens_in=2, tokens_out=3, chip_sec=0.5)
        led.open("r1", tokens_in=4)
        assert led.row("r1")["chip_sec"] == 0.0
        assert led.evicted_totals["chip_sec"] == pytest.approx(0.5)
        assert led.evicted_totals["tokens_out"] == 3

    def test_reset_rewinds_evicted_remainder(self):
        led = ledger_mod.UsageLedger(max_rows=1)
        for i in range(3):
            led.settle("r%d" % i, tokens_in=1, chip_sec=0.1)
        assert led.evicted_totals["chip_sec"] > 0
        led.reset()
        assert led.evicted_totals["chip_sec"] == 0.0
        assert led.rows_evicted == 0

    def test_tenant_table_bounded_folds_into_other(self):
        led = ledger_mod.UsageLedger(max_tenants=3)
        for i in range(6):
            led.record("r%d" % i, tenant="t%d" % i,
                       tokens_in=i + 1, tokens_out=0)
        tenants = led.tenants()
        assert len(tenants) <= 3 + 1  # table bound + __other__
        assert ledger_mod.OVERFLOW_TENANT in tenants
        assert led.tenants_folded > 0
        # nothing lost: the fold preserves the fleet-wide totals
        total_in = sum(v["tokens_in"] for v in tenants.values())
        assert total_in == sum(range(1, 7))

    def test_snapshot_delta_and_merge(self, fresh_ledger):
        led = fresh_ledger
        led.record("r1", tenant="a", tokens_in=4, tokens_out=2)
        base = led.snapshot()
        led.record("r2", tenant="a", tokens_in=6, tokens_out=3)
        led.record("r3", tenant="b", tokens_in=1, tokens_out=1)
        delta = ledger_mod.snapshot_delta(led.snapshot(), base)
        assert delta["tenants"]["a"]["tokens_in"] == 6
        assert delta["tenants"]["a"]["requests"] == 1
        assert delta["tenants"]["b"]["tokens_out"] == 1
        merged = ledger_mod.merge_usage([base, delta])
        for f in ledger_mod.FIELDS:
            assert merged["tenants"]["a"][f] == \
                led.snapshot()["tenants"]["a"][f], f

    def test_mirror_counters_ride_the_fleet_merge(self, fresh_ledger):
        # per-tenant totals publish as usage.<field>.<tenant> counters
        # — the heartbeat piggyback ships registry snapshots, the
        # normal counter merge sums them, and tenants_from_snapshot
        # recovers the per-tenant table on the far side
        led = fresh_ledger
        reg = telemetry.get_registry()
        name = "usage.tokens_out.mirror-t"
        base = reg.snapshot()["counters"].get(name, 0)
        led.record("r1", tenant="mirror-t", tokens_in=5, tokens_out=7)
        snap = reg.snapshot()
        assert snap["counters"][name] - base == 7
        merged = telemetry.merge_snapshots([snap, snap])
        tenants = ledger_mod.tenants_from_snapshot(merged)
        assert tenants["mirror-t"]["tokens_out"] == 2 * (base + 7)

    def test_disabled_mode_is_a_noop(self, fresh_ledger):
        led = fresh_ledger
        led.enabled_override = False
        led.record("r1", tenant="a", tokens_in=5, tokens_out=7)
        led.charge("r1", chip_sec=1.0)
        assert led.rows() == []
        assert led.tenants() == {}
        led.enabled_override = None

    def test_usage_openmetrics_round_trips_strict_parser(
        self, fresh_ledger
    ):
        led = fresh_ledger
        led.record("r1", tenant="acme", tokens_in=10, tokens_out=5)
        led.record("r2", tenant="beta.io", tokens_in=2, tokens_out=1)
        text = ledger_mod.usage_openmetrics(led.tenants())
        fams = telemetry.parse_openmetrics(text)
        sample = dict(
            (labels["tenant"], v)
            for _n, labels, v in fams["usage_tokens_out"]["samples"]
        )
        # tenant label sanitized (no dots) but cardinality-bounded
        assert sample == {"acme": 5.0, "beta_io": 1.0}


# ----------------------------------------------------------------------
# histogram exemplars
# ----------------------------------------------------------------------


class TestExemplars:
    def test_observe_with_exemplar_and_tail_pull(self):
        h = registry_mod.Histogram("t.lat")
        for v, ref in [(0.001, "fast"), (0.2, "slow-1"), (0.25, "slow-2")]:
            for _ in range(10):
                h.observe(v)
            h.observe(v, exemplar=ref)
        snap = h.snapshot()
        assert snap["exemplars"]
        tail = registry_mod.tail_exemplars(snap, 99)
        assert tail and tail[0]["ref"] == "slow-2"
        assert all(e["value"] >= 0.2 for e in tail)

    def test_delta_drops_stale_exemplar_buckets(self):
        h = registry_mod.Histogram("t.lat")
        h.observe(0.5, exemplar="old-tail")
        base = h.snapshot()
        h.observe(0.001, exemplar="new-fast")
        delta = telemetry.snapshot_delta(
            {"histograms": {"t.lat": h.snapshot()}},
            {"histograms": {"t.lat": base}},
        )["histograms"]["t.lat"]
        refs = [e[2]["ref"] for e in delta.get("exemplars", [])]
        assert refs == ["new-fast"]  # the old bucket saw no traffic

    def test_merge_keeps_newest_exemplar_per_bucket(self):
        h1 = registry_mod.Histogram("t.lat")
        h2 = registry_mod.Histogram("t.lat")
        h1.observe(0.1, exemplar="first")
        h2.observe(0.1, exemplar="second")
        s1, s2 = h1.snapshot(), h2.snapshot()
        s1["exemplars"][0][2]["ts"] = 1.0
        s2["exemplars"][0][2]["ts"] = 2.0
        merged = telemetry.merge_snapshots([
            {"histograms": {"t.lat": s1}},
            {"histograms": {"t.lat": s2}},
        ])["histograms"]["t.lat"]
        assert [e[2]["ref"] for e in merged["exemplars"]] == ["second"]


# ----------------------------------------------------------------------
# engine integration: tenant validation + attribution (fake decoder)
# ----------------------------------------------------------------------


class TestEngineLedger:
    MAPPING = {"prompt": "tokens", "tenant": "tenant"}

    def _rows(self, tenants, lens=None, vocab=50, seed=3):
        lens = lens or [4 + i for i in range(len(tenants))]
        rows = _prompts(lens, vocab=vocab, seed=seed)
        for r, t in zip(rows, tenants):
            r["tenant"] = t
        return rows

    def test_tenant_totals_match_outputs_and_chip_sums_to_wall(
        self, fresh_ledger
    ):
        rows = self._rows(["a", "b", "a", "b", "a"])
        eng, out = _run_engine(rows, self.MAPPING)
        assert all("error" not in o for o in out)
        tenants = fresh_ledger.tenants()
        assert tenants["a"]["requests"] == 3
        assert tenants["b"]["requests"] == 2
        # token totals exactly match the emitted outputs (max_new=4,
        # no eos in the fake's vocab semantics)
        emitted = sum(
            int(o.get("generated_len", np.asarray(o["generated"]).size))
            for o in out
        )
        assert (tenants["a"]["tokens_out"] + tenants["b"]["tokens_out"]
                == emitted)
        assert (tenants["a"]["tokens_in"] + tenants["b"]["tokens_in"]
                == sum(r["prompt"].size for r in rows))
        # chip-second rows sum back to the engine's measured decode
        # wall time — exactly (same instrument, apportioned by share)
        chip = sum(r["chip_sec"] for r in fresh_ledger.rows())
        assert chip == pytest.approx(
            eng.stats["decode_wall_sec"], rel=1e-9
        )
        assert eng.stats["tokens_out"] == emitted

    def test_bad_tenant_is_typed_on_continuous(self, fresh_ledger):
        for bad in ("", 7, None):
            rows = self._rows(["ok", bad])
            _eng, out = _run_engine(rows, self.MAPPING)
            rec = out[1]["error"]
            assert rec["kind"] == "bad_tenant"
            assert rec["request_index"] == 1
            assert repr(bad) in rec["message"]

    def test_bad_tenant_raises_naming_request_on_continuous(self):
        rows = self._rows(["ok", ""])
        eng = serving_engine.ServingEngine(
            FakePredict(chunk=2, max_new=4), self.MAPPING, None, 2,
            on_error="raise",
        )
        with pytest.raises(
            serving_engine.RequestValidationError, match="request 1"
        ) as ei:
            list(eng.serve([dict(r) for r in rows]))
        assert ei.value.kind == "bad_tenant"

    def test_bad_tenant_is_typed_on_static(self):
        predict = lambda batch: {"y": batch["x"]}  # noqa: E731
        rows = [{"x": np.zeros((2,)), "tenant": "ok"},
                {"x": np.zeros((2,)), "tenant": 3.5}]
        out = list(serving.predict_rows(
            predict, rows, {"x": "x", "tenant": "tenant"},
            batch_size=2, on_error="record",
        ))
        assert "error" not in out[0]
        assert out[1]["error"]["kind"] == "bad_tenant"
        assert out[1]["error"]["request_index"] == 1

    def test_static_rows_land_in_ledger(self, fresh_ledger):
        predict = lambda batch: {"y": batch["x"]}  # noqa: E731
        rows = [{"x": np.zeros((3,)), "tenant": "acme"} for _ in range(4)]
        list(serving.predict_rows(
            predict, rows, {"x": "x", "tenant": "tenant"}, batch_size=2,
        ))
        t = fresh_ledger.tenants()["acme"]
        assert t["requests"] == 4

    def test_caller_supplied_trace_id_rides_the_spans(self, fresh_ledger):
        tracer = telemetry.get_tracer()
        tracer.clear()
        rows = self._rows(["a", "a"])
        mapping = dict(self.MAPPING, trace="trace_id")
        for i, r in enumerate(rows):
            r["trace"] = "my-trace-%d" % i
        _eng, out = _run_engine(rows, mapping)
        assert all("error" not in o for o in out)
        kinds = [s["name"] for s in tracer.spans(trace="my-trace-1")]
        for expected in ("admission", "prefill", "decode_chunk", "emit"):
            assert expected in kinds, kinds
        assert fresh_ledger.row("my-trace-0") is not None

    def test_bad_trace_value_is_typed(self):
        rows = self._rows(["a"])
        rows[0]["trace"] = 12  # not a string
        mapping = dict(self.MAPPING, trace="trace_id")
        _eng, out = _run_engine(rows, mapping)
        assert out[0]["error"]["kind"] == "bad_trace"


# ----------------------------------------------------------------------
# fleet integration (fake decoders): trace minting + attribution
# ----------------------------------------------------------------------


def _fleet_router(n=2, slots=2, **kw):
    kw.setdefault("poll_sec", 0.01)
    return FleetRouter(
        None, {"prompt": "tokens", "tenant": "tenant"}, replicas=n,
        num_slots=slots,
        predict_factory=lambda: FakePredict(chunk=4, max_new=8), **kw
    )


class TestFleetLedger:
    def _rows(self, n=6, seed=7):
        rows = _prompts([5 + (i % 4) for i in range(n)], seed=seed)
        for i, r in enumerate(rows):
            r["tenant"] = "t%d" % (i % 2)
        return rows

    def test_fleet_trace_spans_connected_and_totals_exact(
        self, fresh_ledger
    ):
        tracer = telemetry.get_tracer()
        tracer.clear()
        rows = self._rows()
        router = _fleet_router()
        out = list(router.serve([dict(r) for r in rows]))
        router.close()
        assert len(out) == len(rows)
        # one minted trace per request, and the ENGINE's span chain
        # rides it (the PR 7 chain joins the router's trace)
        rid0 = router.stats["trace_ids"][0]
        kinds = [s["name"] for s in tracer.spans(trace=rid0)]
        for expected in ("fleet_admission", "fleet_dispatch",
                         "admission", "queue_wait", "prefill",
                         "decode_chunk", "emit"):
            assert expected in kinds, kinds
        # per-tenant token totals match the emitted outputs exactly
        tenants = fresh_ledger.tenants()
        emitted = sum(
            int(o.get("generated_len", np.asarray(o["generated"]).size))
            for o in out
        )
        assert sum(
            v["tokens_out"] for v in tenants.values()
        ) == emitted
        chip = sum(r["chip_sec"] for r in fresh_ledger.rows())
        assert chip == pytest.approx(
            router.stats["decode_wall_sec"], rel=1e-9
        )

    def test_kill_replica_continues_the_same_trace(
        self, fresh_ledger, tmp_path
    ):
        from tensorflowonspark_tpu.telemetry import journal as jm

        tracer = telemetry.get_tracer()
        tracer.clear()
        rows = self._rows(n=8, seed=11)
        plan = chaos.ChaosPlan().kill_replica(1, at_chunk=1)
        os.environ[chaos.TFOS_CHAOS_PLAN] = plan.save(
            str(tmp_path / "plan.json")
        )
        j = jm.get_journal()
        n_dead = len(j.events(kind="replica_dead"))
        try:
            router = _fleet_router()
            out = list(router.serve([dict(r) for r in rows]))
            router.close()
        finally:
            del os.environ[chaos.TFOS_CHAOS_PLAN]
        assert len(out) == len(rows)
        assert router.stats["replica_deaths"] == 1
        # the fleet_redispatch mark carries the request's trace id
        # (satellite: fault marks name the requests they touched)
        red = [e for e in j.events(kind="fleet_redispatch")]
        assert red
        ev = red[-1]
        rid = ev.attrs["trace_id"]
        assert ev.trace == rid
        assert rid in router.stats["trace_ids"].values()
        dead = j.events(kind="replica_dead")[n_dead:]
        assert dead and dead[-1].attrs["request_ids"]
        assert dead[-1].attrs["trace_ids"]
        # the SAME trace carries prefill spans on BOTH replica worker
        # threads: the re-dispatch continued it
        prefills = [
            s for s in tracer.spans(trace=rid) if s["name"] == "prefill"
        ]
        assert len(prefills) >= 2
        assert len({s["tid"] for s in prefills}) == 2
        # the ledger row saw the re-dispatch and the totals stay exact
        assert fresh_ledger.row(rid)["redispatches"] >= 1
        chip = sum(r["chip_sec"] for r in fresh_ledger.rows())
        assert chip == pytest.approx(
            router.stats["decode_wall_sec"], rel=1e-9
        )

    def test_status_carries_per_replica_cost_rows(self, fresh_ledger):
        router = _fleet_router()
        out = list(router.serve([dict(r) for r in self._rows()]))
        assert len(out) == 6
        status = router.health_status()
        costs = status["costs"]
        assert set(costs) == {0, 1}
        assert sum(c["tokens_out"] for c in costs.values()) == 6 * 8
        assert all("chip_sec" in c for c in costs.values())
        router.close()


# ----------------------------------------------------------------------
# /usage exposition + forensics exemplar pull
# ----------------------------------------------------------------------


class TestUsageRoute:
    def test_usage_routes_json_and_openmetrics(self, fresh_ledger):
        fresh_ledger.record(
            "r1", tenant="acme", tokens_in=10, tokens_out=5,
            latency_sec=0.1,
        )
        plane = telemetry.HealthPlane.local(interval=0.05,
                                            straggler=False)
        plane.scrape_once()
        srv = plane.serve(port=0)
        try:
            with urllib.request.urlopen(
                srv.url + "/usage", timeout=10
            ) as resp:
                fams = telemetry.parse_openmetrics(
                    resp.read().decode("utf-8")
                )
            tenants = {
                labels["tenant"]
                for _n, labels, _v in fams["usage_requests"]["samples"]
            }
            assert "acme" in tenants
            with urllib.request.urlopen(
                srv.url + "/usage?format=json", timeout=10
            ) as resp:
                j = json.loads(resp.read().decode("utf-8"))
            assert j["tenants"]["acme"]["tokens_out"] >= 5
            assert j["top"]
        finally:
            plane.stop()


class TestForensicsExemplars:
    def _bundle(self, tmp_path):
        from tensorflowonspark_tpu.telemetry import blackbox as bb

        h = registry_mod.Histogram("serving.request_latency_sec")
        for _ in range(20):
            h.observe(0.01)
        h.observe(0.8, exemplar="flt1-req3")
        spans = [
            {"name": "prefill", "trace": "flt1-req3", "id": 1,
             "t0": 100.0, "dur": 0.1, "tid": 1},
            {"name": "decode_chunk", "trace": "flt1-req3", "id": 2,
             "parent": 1, "t0": 100.02, "dur": 0.7, "tid": 1},
            {"name": "emit", "trace": "other", "id": 3,
             "t0": 100.0, "dur": 0.9, "tid": 1},
        ]
        bundle = {
            "format": bb.BUNDLE_FORMAT, "executor": 0, "pid": 1234,
            "events": [{
                "ts": 100.0, "seq": 1, "executor": 0, "pid": 1234,
                "severity": "page", "kind": "watchdog_fire",
                "trace": "serve", "attrs": {},
            }],
            "spans": spans,
            "metrics": {"histograms": {
                "serving.request_latency_sec": h.snapshot(),
            }},
        }
        path = tmp_path / "dump.json"
        path.write_text(json.dumps(bundle))
        return str(path)

    def test_explain_names_the_p99_request(self, tmp_path):
        from tensorflowonspark_tpu import forensics

        report = forensics.explain([self._bundle(tmp_path)])
        exes = report["p99_exemplars"]
        assert exes and exes[0]["ref"] == "flt1-req3"
        # the critical path prefers the exemplar's trace over the
        # busiest-trace heuristic ("other" carries more span time)
        assert report["critical_path"]["trace"] == "flt1-req3"
        text = forensics.render_report(report)
        assert "flt1-req3" in text

    def test_explain_request_pin_and_trace_filter(self, tmp_path):
        from tensorflowonspark_tpu import forensics

        path = self._bundle(tmp_path)
        report = forensics.explain([path], request="other")
        assert report["critical_path"]["trace"] == "other"
        merged = forensics.merged_chrome([path], request="flt1-req3")
        names = {
            e["name"] for e in merged["traceEvents"]
            if e.get("ph") == "X"
        }
        assert names == {"prefill", "decode_chunk"}


# ----------------------------------------------------------------------
# pipeline surface
# ----------------------------------------------------------------------


class TestTenantColParam:
    def test_tfmodel_grows_set_tenant_col(self):
        from tensorflowonspark_tpu.pipeline import TFModel

        m = TFModel({"export_dir": "/tmp/x"})
        assert m.setTenantCol("customer") is m
        assert m.getTenantCol() == "customer"
        args = m.merge_args_params()
        assert args.tenant_col == "customer"


# ----------------------------------------------------------------------
# ACCEPTANCE e2e (real tiny transformer, 2 replicas, kill mid-decode)
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def accept_predicts():
    _params, predict = _gen_predict(max_new=6, extra={"chunk_size": 2})
    return [predict, predict.make_replica()]


class TestAcceptanceE2E:
    def test_kill_replica_trace_ledger_usage(self, accept_predicts,
                                             tmp_path):
        # 2 replicas at ~2x a single engine's admission capacity
        # (slots 2 + queue 4 = 6; offer 12), one kill_replica
        # mid-decode — ISSUE 14 acceptance (a)+(b)+(c)
        from tensorflowonspark_tpu.telemetry import journal as jm

        led = ledger_mod.get_ledger()
        led.enabled_override = None
        led.reset()
        tracer = telemetry.get_tracer()
        tracer.clear()
        rows = _prompts([6, 9, 5, 13, 8, 4, 7, 11, 6, 9, 5, 13],
                        vocab=64, seed=31)
        for i, r in enumerate(rows):
            r["tenant"] = "tenant-%d" % (i % 3)
        plan = chaos.ChaosPlan().kill_replica(1, at_chunk=1)
        os.environ[chaos.TFOS_CHAOS_PLAN] = plan.save(
            str(tmp_path / "plan.json")
        )
        it = iter(accept_predicts)
        try:
            router = FleetRouter(
                None, {"prompt": "tokens", "tenant": "tenant"},
                replicas=2, num_slots=2,
                predict_factory=lambda: next(it), poll_sec=0.01,
            )
            out = list(router.serve([dict(r) for r in rows]))
            router.close()
        finally:
            del os.environ[chaos.TFOS_CHAOS_PLAN]
        assert len(out) == len(rows)
        assert all("error" not in o for o in out)
        assert router.stats["replica_deaths"] == 1
        assert router.stats["redispatched"] >= 1

        # -- (a) connected, clock-aligned merged trace ----------------
        # pick a re-dispatched request that was IN FLIGHT at death
        # (tokens committed on the dead replica): its trace carries a
        # prefill on BOTH replica worker threads
        j = jm.get_journal()
        run_rids = set(router.stats["trace_ids"].values())
        rid = spans = None
        for ev in reversed(j.events(kind="fleet_redispatch")):
            cand = ev.attrs["trace_id"]
            if cand not in run_rids:
                continue
            cand_spans = tracer.spans(trace=cand)
            if len({
                s["tid"] for s in cand_spans if s["name"] == "prefill"
            }) == 2:
                rid, spans = cand, cand_spans
                break
        assert rid is not None, "no in-flight re-dispatch found"
        prefill_tids = [
            s["tid"] for s in spans if s["name"] == "prefill"
        ]
        assert len(set(prefill_tids)) == 2  # both replica workers
        # split the request's spans per replica worker thread, skew
        # replica B's clock by -5s, and hand merge_traces the +5s
        # offset — the PR 11 alignment must restore causal order
        dead_tid = prefill_tids[0]      # first prefill: the replica
        skew = 5.0                      # that later died
        parts = []
        for label, tids in (
            ("replica-dead", {dead_tid}),
            ("survivors", set(s["tid"] for s in spans) - {dead_tid}),
        ):
            evs = [
                {"name": s["name"], "ph": "X",
                 "ts": round((s["t0"] - (0.0 if label == "replica-dead"
                                         else skew)) * 1e6, 3),
                 "dur": round(s["dur"] * 1e6, 3),
                 "pid": 0, "tid": s["tid"],
                 "args": {"trace": rid}}
                for s in spans if s["tid"] in tids
            ]
            parts.append((
                {"traceEvents": evs}, 0.0 if label == "replica-dead"
                else skew, label,
            ))
        merged = telemetry.merge_traces(parts)
        xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
        # connected: the one trace covers both replicas' chains
        assert {e["args"]["trace"] for e in xs} == {rid}
        assert len({e["pid"] for e in xs}) == 2
        # monotonic after alignment: merge order == true causal order
        ts = [e["ts"] for e in xs]
        assert ts == sorted(ts)
        names_in_order = [e["name"] for e in xs]
        # the dead replica's prefill comes before the surviving
        # replica's re-dispatched prefill, which precedes the emit
        first_prefill = names_in_order.index("prefill")
        second_prefill = names_in_order.index(
            "prefill", first_prefill + 1
        )
        assert first_prefill < second_prefill
        assert second_prefill < len(names_in_order)

        # -- (b) ledger totals match outputs; chip-sec sums to wall ---
        tenants = led.tenants()
        emitted = sum(
            int(o.get("generated_len", np.asarray(o["generated"]).size))
            for o in out
        )
        assert sum(v["tokens_out"] for v in tenants.values()) == emitted
        per_tenant_emitted = {}
        for i, o in enumerate(out):
            t = "tenant-%d" % (i % 3)
            per_tenant_emitted[t] = per_tenant_emitted.get(t, 0) + int(
                o.get("generated_len", np.asarray(o["generated"]).size)
            )
        for t, tok in per_tenant_emitted.items():
            assert tenants[t]["tokens_out"] == tok, t
        chip = sum(r["chip_sec"] for r in led.rows())
        wall = router.stats["decode_wall_sec"]
        assert wall > 0
        assert abs(chip - wall) / wall < 0.05  # the 5% acceptance bar
        assert led.row(rid)["redispatches"] >= 1

        # -- (c) /usage round-trips the strict OpenMetrics parser -----
        plane = telemetry.HealthPlane.local(interval=0.05,
                                            straggler=False)
        plane.scrape_once()
        srv = plane.serve(port=0)
        try:
            with urllib.request.urlopen(
                srv.url + "/usage", timeout=10
            ) as resp:
                fams = telemetry.parse_openmetrics(
                    resp.read().decode("utf-8")
                )
            tenant_labels = {
                labels["tenant"]
                for _n, labels, _v in fams["usage_tokens_out"]["samples"]
            }
            assert {"tenant-0", "tenant-1", "tenant-2"} <= tenant_labels
        finally:
            plane.stop()

        # the p99 exemplar machinery saw this run: tail buckets of the
        # shared latency histogram name concrete fleet traces
        snap = telemetry.get_registry().histogram(
            serving_engine.LATENCY_METRIC
        ).snapshot()
        tail = telemetry.tail_exemplars(snap, 99)
        assert tail and any(
            e["ref"].startswith("flt") or e["ref"].startswith("req")
            or e["ref"].startswith("sj") for e in tail
        )
