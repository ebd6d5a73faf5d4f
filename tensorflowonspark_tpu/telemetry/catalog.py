"""The metric-name CATALOG: one table every metric name answers to.

Every counter/gauge/histogram the stack creates is re-typed as a
string literal at its call site — nothing stops a typo'd
``"serving.admited"`` from silently forking a new time series that no
SLO rule, dashboard, or doc row will ever find.  This module is the
contract registry that closes that hole (ISSUE 15):

- :data:`METRICS` is the exhaustive per-metric table — name, kind
  (counter/gauge/histogram), owning subsystem, one-line meaning.
- :data:`DYNAMIC_PREFIXES` names the families whose full names are
  minted at runtime (the per-tenant ``usage.*`` mirror counters).
- ``docs/observability.md``'s "Built-in metrics" table is GENERATED
  from this table (:func:`render_markdown`, between the
  ``metric-table:begin/end`` markers) and drift-tested in the CI lint
  lane (:func:`check_docs`) — the doc can never silently disagree
  with the catalog.
- The tfoslint rule **TFOS004** (``analysis/lint.py``) checks every
  literal metric name at a ``counter(...)``/``gauge(...)``/
  ``histogram(...)`` call site against this catalog, so a new metric
  must land here (and therefore in the docs) in the same diff that
  creates it.

The reserved serving-input columns live in
:mod:`tensorflowonspark_tpu.serving_engine` (``RESERVED_INPUTS``);
:data:`RESERVED_INPUT_COLUMNS` mirrors the *names* here so the linter
and the docs can read them without importing the jax-heavy serving
stack (equality of the two tuples is asserted in
``tests/test_analysis.py``).

CLI::

    python -m tensorflowonspark_tpu.telemetry.catalog --check docs/observability.md
    python -m tensorflowonspark_tpu.telemetry.catalog --write docs/observability.md
"""

import collections

#: The reserved request-row input columns, one constant each —
#: import-light twins of ``serving_engine.BUDGET_INPUT`` /
#: ``DEADLINE_INPUT`` / ``TENANT_INPUT`` / ``TRACE_INPUT`` for the
#: telemetry layer (which must never pull the jax-heavy serving
#: stack).  ``serving_engine.RESERVED_INPUTS`` re-exports exactly
#: :data:`RESERVED_INPUT_COLUMNS` (asserted in
#: tests/test_analysis.py).
BUDGET_COLUMN = "max_new"          # per-request token budget
DEADLINE_COLUMN = "deadline_sec"   # per-request deadline (seconds)
TENANT_COLUMN = "tenant"           # usage-ledger attribution key
TRACE_COLUMN = "trace_id"          # fleet-minted trace id

RESERVED_INPUT_COLUMNS = (
    BUDGET_COLUMN, DEADLINE_COLUMN, TENANT_COLUMN, TRACE_COLUMN,
)

Metric = collections.namedtuple("Metric", "name kind source desc")

_C, _G, _H = "counter", "gauge", "histogram"


def _m(kind, source, *pairs):
    return [Metric(name, kind, source, desc) for name, desc in pairs]


#: the exhaustive metric table, grouped by subsystem prefix
METRICS = tuple(
    # --- serving engine (serving_engine.py + static predict_rows) ---
    _m(_C, "ServingEngine",
       ("serving.admitted", "requests past admission validation"),
       ("serving.completed", "requests emitted with output tokens"),
       ("serving.errors", "typed per-request error records"),
       ("serving.shed", "requests shed by the admission policy"),
       ("serving.expired", "deadline cancellations"),
       ("serving.degraded", "budgets shrunk by the degrade policy"),
       ("serving.chunks", "decode chunks dispatched"),
       ("serving.watchdog_fires", "wedged chunk syncs abandoned"),
       ("serving.recovered", "requests re-admitted after a watchdog teardown"),
       ("serving.prefix_hit_admits", "admits served from the radix cache"),
       ("serving.swaps", "weight swaps installed"),
       ("serving.swap_commits", "probation windows closed clean"),
       ("serving.swap_rollbacks", "swaps rolled back inside the window"),
       ("serving.drained", "requests returned as typed drained records"),
       ("serving.moe_assignments",
        "(token, expert) choices of the decode steps' live rows over "
        "the sigmoid-routed layers"),
       ("serving.moe_local_assignments",
        "of those, choices of an expert this program holds"),
       ("serving.moe_experts_hit",
        "held experts chosen by at least one live row, summed over "
        "decode steps and layers"),
       ("serving.attn_read_tokens",
        "positions a chunk's first decode step reads, summed over "
        "layers (index keys included)"),
       ("serving.attn_context_tokens",
        "live positions (prompt + answer so far) of the requests in "
        "flight at a chunk, summed over layers"))
    + _m(_H, "ServingEngine",
         ("serving.request_latency_sec",
          "submit→emit latency, BOTH schedules (the authoritative "
          "p50/p99 source; carries trace-id exemplars)"),
         ("serving.ttft_sec",
          "submit→first-token latency (the number the prefill/decode "
          "split bounds; trace-id exemplars)"),
         ("serving.queue_wait_sec", "admission-queue wait"))
    + _m(_G, "ServingEngine",
         ("serving.weight_generation", "live weight generation tag"),
         ("serving.kv_bank_bytes_ring",
          "bytes of the contiguous KV banks that are rings (layers "
          "whose window is shorter than the bank)"),
         ("serving.kv_bank_bytes_whole",
          "bytes of the contiguous KV banks of full length"),
         ("serving.kv_bank_bytes_unringed",
          "bytes whole banks on every layer would hold"))
    + _m(_C, "hot_swap.CheckpointWatcher",
         ("serving.checkpoints_quarantined",
          "serving exports rejected by the validation pipeline"))
    # --- fleet router (fleet/router.py) ---
    + _m(_C, "fleet.FleetRouter",
         ("fleet.dispatched", "requests handed to a replica"),
         ("fleet.redispatched", "in-flight work re-dispatched off a dead replica"),
         ("fleet.completed", "requests emitted fleet-wide"),
         ("fleet.shed", "fleet-level admission sheds (spill-before-shed)"),
         ("fleet.affinity_hits", "prefix-affinity dispatches that hit their replica"),
         ("fleet.replica_deaths", "replica worker deaths observed"),
         ("fleet.evictions", "slow replicas routed around"),
         ("fleet.readmissions", "probed replicas re-admitted"))
    + _m(_C, "fleet.FleetRouter remediation verbs",
         ("fleet.replicas_spawned", "replicas added by scale_up"),
         ("fleet.replicas_retired", "replicas drained away by scale_down"))
    + _m(_G, "fleet.FleetRouter",
         ("fleet.live_replicas", "replicas currently taking dispatch"))
    # --- radix prefix cache (prefix_cache.py) ---
    + _m(_C, "radix prefix cache",
         ("prefix_cache.hits", "cached-prefix admit hits"),
         ("prefix_cache.misses", "cold admits"),
         ("prefix_cache.tokens_saved", "prompt tokens not re-prefilled"),
         ("prefix_cache.evictions", "cold leaves evicted under the HBM budget"))
    + _m(_G, "radix prefix cache",
         ("prefix_cache.bytes_used", "device bytes held by committed blocks"))
    # --- training loop (parallel/dp.py) ---
    + _m(_C, "SyncTrainer.train_on_feed",
         ("train.steps", "optimizer steps taken"))
    + _m(_H, "SyncTrainer.train_on_feed",
         ("train.step_sec", "per-step wall time"),
         ("train.feed_wait_sec", "feed-starvation wait per step"),
         ("train.h2d_sec", "host→device transfer (straggler phase series)"),
         ("train.dispatch_sec", "step dispatch (straggler phase series)"))
    + _m(_G, "Transformer (traced for training)",
         ("train.residual_shards",
          "token shards of the residual stream between blocks in the "
          "training step last traced: the model axis's size where the "
          "stream is split over it, else 1"))
    # --- parameter-server wire (parallel/ps.py) ---
    + _m(_C, "PSClient",
         ("ps.bytes_sent", "exact frame bytes onto the wire"),
         ("ps.bytes_recv", "exact frame bytes off the wire (delta replies)"),
         ("ps.round_trips", "push/pull round trips"))
    + _m(_H, "PSClient / AsyncTrainer drain",
         ("ps.round_trip_sec", "wire round-trip latency"),
         ("ps.grad_readback_sec", "device→host gradient readback"))
    # --- hierarchical PS (parallel/hier_ps.py) ---
    + _m(_C, "HierTrainer + DcnLink",
         ("hier.ici_steps", "on-device psum+apply steps"),
         ("hier.dcn_windows", "compressed delta windows pushed over DCN"),
         ("hier.dcn_dedup", "windows the exactly-once ledger dropped"),
         ("hier.leader_failovers", "pod-leader re-elections"))
    + _m(_G, "HierTrainer",
         ("hier.leader", "this member's leadership flag"))
    + _m(_H, "DcnLink",
         ("hier.dcn_readback_sec", "delta device→host readback"),
         ("hier.dcn_push_sec", "DCN push wall time"))
    # --- data plane (data/feed.py, data/shm_ring.py) ---
    + _m(_C, "DataFeed",
         ("feed.wire_bytes", "feed payload bytes (twin of wire_stats())"),
         ("feed.wire_records", "wire records decoded"),
         ("feed.wire_rows", "rows decoded"))
    + _m(_C, "ShmRing",
         ("ring.push_records", "records pushed into the shm ring"),
         ("ring.push_bytes", "bytes pushed into the shm ring"),
         ("ring.pop_records", "records popped off the shm ring"),
         ("ring.pop_bytes", "bytes popped off the shm ring"))
    # --- cluster lifecycle (cluster/supervisor.py, cluster/cluster.py) ---
    + _m(_C, "supervisor + driver monitor",
         ("cluster.restarts", "compute-process restarts (supervisor-side)"),
         ("cluster.restart_events", "restarts observed by the driver monitor"))
    + _m(_G, "supervisor heartbeat",
         ("cluster.generation", "rendezvous generation on the beat"))
    # --- health plane (telemetry/health.py) ---
    + _m(_C, "HealthPlane / SloEngine / StragglerDetector",
         ("health.scrapes", "scrape→store→evaluate rounds"),
         ("health.alerts_fired", "SLO alert fire transitions"),
         ("health.alerts_resolved", "SLO alert resolve transitions"),
         ("health.stragglers_flagged", "executors flagged as stragglers"),
         ("health.stragglers_cleared", "straggler hints expired clean"),
         ("health.profile_captures", "auto-triggered profile captures"))
    + _m(_G, "HealthPlane / supervisor beat",
         ("health.alerts_active", "currently-firing alerts"),
         ("health.straggler", "per-node straggler hint flag (beat-side)"))
    # --- telemetry substrate itself ---
    + _m(_C, "Tracer bounded store",
         ("tracing.dropped_spans", "spans evicted by the bounded ring"))
    + _m(_C, "EventJournal",
         ("journal.events", "typed events appended"),
         ("journal.dropped_events", "events evicted from a severity ring"))
    + _m(_C, "blackbox.FlightRecorder",
         ("blackbox.dumps", "dump bundles frozen to disk"),
         ("blackbox.dumps_suppressed", "triggers rate-limited away"))
    # --- lock-order sanitizer (analysis/locksan.py, ISSUE 15) ---
    + _m(_C, "analysis.locksan",
         ("locksan.locks", "instrumented locks created"),
         ("locksan.cycles", "potential-deadlock cycles reported"))
    # --- remediation engine (remediation/engine.py, ISSUE 16) ---
    + _m(_C, "remediation.RemediationEngine",
         ("remediation.decisions", "policy intents that reached the audit log"),
         ("remediation.actions_executed", "actuator verbs actually invoked"),
         ("remediation.actions_suppressed",
          "intents stopped by a cooldown or rate limit"),
         ("remediation.actions_deferred",
          "intents parked by the deploy-conflict rule"))
    + _m(_G, "remediation.RemediationEngine",
         ("remediation.budget_remaining",
          "global action budget left before hands-off"))
    # --- cost-model planner (planner/, ISSUE 18) ---
    + _m(_C, "planner.cost.calibrate",
         ("planner.calibrations", "calibration probe passes run"))
    + _m(_H, "planner.cost.calibrate",
         ("planner.calibration_sec", "micro-bench probe pass wall time"))
    + _m(_C, "planner.plan",
         ("planner.candidates", "lattice points priced by the cost model"),
         ("planner.pruned", "lattice points rejected by a legality validator"))
    + _m(_H, "planner.plan",
         ("planner.plan_sec", "enumerate+price+choose wall time"))
    + _m(_C, "planner.LivePlanner",
         ("planner.replans", "live re-plans applied through an actuator"),
         ("planner.replan_suppressed",
          "sustained triggers suppressed by a cooldown"))
    # --- live re-planner sensors (serving_engine.py, ISSUE 18) ---
    + _m(_H, "ServingEngine admission",
         ("serving.prompt_tokens",
          "admitted prompt length (the prompt-mix drift sensor)"))
    + _m(_G, "ServingEngine paged pool",
         ("serving.pool_pages", "physical page-pool size"),
         ("serving.pool_pages_used",
          "pages currently held (occupancy = used / size)"))
)

#: families whose full names are minted at runtime — a literal name
#: under one of these prefixes is catalog-clean without its own row
DYNAMIC_PREFIXES = {
    "usage.":
        "per-tenant usage-ledger mirror counters "
        "(``usage.<field>.<tenant>``, bounded tenant set — "
        "telemetry/ledger.py)",
}

#: full-name set for O(1) membership checks (the linter's view)
NAMES = frozenset(m.name for m in METRICS)

_BEGIN = "<!-- metric-table:begin (generated by telemetry/catalog.py — edit the catalog, not this table) -->"
_END = "<!-- metric-table:end -->"


def known(name):
    """True when ``name`` is catalog-clean: an exact row or a
    registered dynamic family."""
    return name in NAMES or any(
        name.startswith(p) for p in DYNAMIC_PREFIXES
    )


def duplicates():
    """Catalog self-check: names declared twice (tested empty)."""
    seen, dups = set(), []
    for m in METRICS:
        if m.name in seen:
            dups.append(m.name)
        seen.add(m.name)
    return dups


def render_markdown():
    """The generated "Built-in metrics" doc table (one row per
    metric, plus one per dynamic family), marker lines included."""
    lines = [_BEGIN, "| metric | kind | source | meaning |", "|---|---|---|---|"]
    for m in METRICS:
        lines.append("| `%s` | %s | %s | %s |" % (m.name, m.kind, m.source, m.desc))
    for prefix in sorted(DYNAMIC_PREFIXES):
        lines.append(
            "| `%s*` | counter | dynamic family | %s |"
            % (prefix, DYNAMIC_PREFIXES[prefix])
        )
    lines.append(_END)
    return "\n".join(lines)


def _split_doc(text, path):
    try:
        head, rest = text.split(_BEGIN, 1)
        table, tail = rest.split(_END, 1)
    except ValueError:
        raise SystemExit(
            "%s: metric-table markers missing (%r ... %r) — "
            "regenerate with --write" % (path, _BEGIN, _END)
        )
    return head, table, tail


def check_docs(path):
    """Drift test: the doc's generated region must byte-match the
    catalog rendering.  Returns [] when clean, else human-readable
    drift lines."""
    with open(path) as f:
        text = f.read()
    _head, table, _tail = _split_doc(text, path)
    want = render_markdown()
    got = _BEGIN + table + _END
    if got.strip() == want.strip():
        return []
    want_l = set(want.strip().splitlines())
    got_l = set(got.strip().splitlines())
    drift = ["catalog row missing from doc: %s" % l
             for l in sorted(want_l - got_l)]
    drift += ["doc row not in catalog: %s" % l
              for l in sorted(got_l - want_l)]
    return drift or ["metric table differs (ordering)"]


def write_docs(path):
    """Regenerate the doc's metric table in place."""
    with open(path) as f:
        text = f.read()
    head, _table, tail = _split_doc(text, path)
    with open(path, "w") as f:
        f.write(head + render_markdown() + tail)


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m tensorflowonspark_tpu.telemetry.catalog",
        description="metric-catalog docs generation / drift check",
    )
    ap.add_argument("--check", metavar="DOC", help="fail on doc drift")
    ap.add_argument("--write", metavar="DOC", help="regenerate the doc table")
    args = ap.parse_args(argv)
    dups = duplicates()
    if dups:
        print("catalog declares duplicate metrics: %s" % ", ".join(dups))
        return 1
    if args.write:
        write_docs(args.write)
        print("%s: metric table regenerated (%d metrics)"
              % (args.write, len(METRICS)))
    if args.check:
        drift = check_docs(args.check)
        if drift:
            print("%s: metric table DRIFTED from telemetry/catalog.py:"
                  % args.check)
            for line in drift:
                print("  " + line)
            print("fix: python -m tensorflowonspark_tpu.telemetry."
                  "catalog --write %s" % args.check)
            return 1
        print("%s: metric table matches the catalog (%d metrics)"
              % (args.check, len(METRICS)))
    if not args.check and not args.write:
        print(render_markdown())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
