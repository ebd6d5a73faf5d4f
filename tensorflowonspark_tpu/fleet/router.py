"""Fleet router: bounded admission + pluggable dispatch over replicas.

One level above the engine's admission plane (PR 4), the router is the
fleet's: a bounded **fleet admission queue** with the same
``block | reject | degrade`` shedding vocabulary, except that pressure
first **spills to a sibling replica** — a single engine only ever sees
traffic the router already sized to its slots + queue bound, so no
engine-level shed fires while a sibling has room.

Dispatch policies (:data:`DISPATCH_POLICIES`, pluggable by callable):

- ``least_loaded`` — fewest router-assigned requests per weight, fed
  by the replicas' lock-light ``load()`` snapshots (the same fields
  ``/status`` exposes per engine);
- ``prefix_affinity`` — block-granular prompt fingerprints
  (:func:`~tensorflowonspark_tpu.prefix_cache.fingerprint` — the
  radix cache's own key math) routed by rendezvous hashing, so a
  shared prefix consistently lands on the replica whose radix cache
  already holds it; under imbalance (target backlog more than
  ``imbalance`` ahead of the least loaded) it falls back to
  least-loaded (an ``affinity_spill``);
- ``weighted_rr`` — deterministic smooth weighted round-robin;
- ``random`` — seeded uniform pick (what ``prefix_affinity`` is compared with).

**Replica death** re-dispatches committed-token-safe: the dead
replica's wreckage (finished-but-unemitted rows, per-request committed
tokens — see ``Replica._wreckage``) re-enters the fleet queue with the
dead replica in each request's excluded set; greedy continuations from
``prompt + committed`` are token-identical to an undisturbed run (the
same invariant the engine watchdog's recovery pins down).  A **slow**
replica is routed around (latency-EWMA vs the fleet median), kept on
probe traffic, and re-admitted after N clean probe rounds.  Every
action is a typed journal event (``replica_dead`` / ``fleet_redispatch``
/ ``replica_evicted`` / ``replica_readmitted`` / ``fleet_shed`` —
tracer marks auto-bridge into the PR 11 journal).

Rolling deploys (fleet/deploy.py) run as a state machine stepped by
the router's scheduling loop — drain one replica, hot-swap it, gate on
its post-swap health, re-admit, next.

See docs/serving.md "Fleet routing & rolling deploys".
"""

import collections
import itertools
import logging
import queue as queue_mod
import threading
import time

import numpy as np

from tensorflowonspark_tpu import serving_engine, telemetry
from tensorflowonspark_tpu.fleet.replica import ReplicaSet
from tensorflowonspark_tpu.prefix_cache import fingerprint
from tensorflowonspark_tpu.telemetry import ledger as ledger_mod

logger = logging.getLogger(__name__)

#: internal row column carrying each request's (possibly resumed)
#: token budget into the replica engines — added to the engine-level
#: input mapping unless the caller already mapped a budget column
FLEET_BUDGET_COL = "__fleet_max_new__"

#: internal row column carrying each request's fleet-minted TRACE id
#: into the replica engines (mapped to ``serving_engine.TRACE_INPUT``
#: unless the caller already mapped a trace column): the engine's
#: ``admission → queue_wait → prefill → decode_chunk×N → emit`` span
#: chain then joins the router's trace, and a re-dispatch after
#: ``kill_replica`` CONTINUES the same trace on the surviving replica
#: — ``telemetry.merge_traces`` renders one connected, causally
#: ordered story per request across replicas/processes (ISSUE 14).
FLEET_TRACE_COL = "__fleet_trace__"

#: per-process router sequence: trace ids are ``flt<router>-req<fid>``
#: so rows in the process-wide usage ledger never collide across
#: routers/jobs
_ROUTER_SEQ = itertools.count(1)

#: error-record kinds that re-raise under ``on_error="raise"`` (the
#: replica engines always run in record mode; the router restores
#: fail-fast semantics for genuine request faults).  Policy records
#: (shed / deadline / drained / replica_lost) never raise.
_RAISE_KINDS = frozenset({
    "missing_input", "bad_dtype", "bad_shape", "empty_prompt",
    "too_long", "bad_budget", "bad_deadline", "admit", "predict",
})


def _mix(fp, rid):
    """Deterministic 64-bit rendezvous score for (fingerprint,
    replica) — stable across processes (no salted ``hash``)."""
    x = (int(fp) ^ (int(rid) * 0x9E3779B97F4A7C15)) & 0xFFFFFFFFFFFFFFFF
    x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9 & 0xFFFFFFFFFFFFFFFF
    x = (x ^ (x >> 27)) * 0x94D049BB133111EB & 0xFFFFFFFFFFFFFFFF
    return x ^ (x >> 31)


def _median(vals):
    vals = sorted(vals)
    n = len(vals)
    if not n:
        return None
    return vals[n // 2] if n % 2 else 0.5 * (
        vals[n // 2 - 1] + vals[n // 2]
    )


# ----------------------------------------------------------------------
# dispatch policies
# ----------------------------------------------------------------------


def _least_loaded(router, req, candidates):
    return min(
        candidates,
        key=lambda r: (
            router._assigned_count(r.replica_id)
            / router._weight(r.replica_id),
            r.replica_id,
        ),
    )


def _weighted_rr(router, req, candidates):
    """Smooth weighted round-robin (the nginx algorithm):
    deterministic, proportional to weights, no bursts."""
    cw = router._rr_current
    total = 0.0
    for r in candidates:
        w = router._weight(r.replica_id)
        cw[r.replica_id] = cw.get(r.replica_id, 0.0) + w
        total += w
    best = max(candidates, key=lambda r: (cw[r.replica_id], -r.replica_id))
    cw[best.replica_id] -= total
    return best


def _random(router, req, candidates):
    return candidates[router._rng.randint(len(candidates))]


def _prefix_affinity(router, req, candidates):
    """Rendezvous-hash the prompt fingerprint over every ROUTABLE
    replica (stable while membership is stable — one replica's death
    only remaps its own keys), then dispatch there unless its backlog
    runs more than ``imbalance`` ahead of the least-loaded candidate
    (or it has no room / is excluded) — then spill to least-loaded."""
    fp = req["fingerprint"]
    if fp is None:
        return _least_loaded(router, req, candidates)
    routable = [
        r for r in router.replicas
        if r.alive and r.state == "live"
    ] or candidates
    target = max(
        routable, key=lambda r: _mix(fp, r.replica_id)
    )
    floor = min(
        router._assigned_count(r.replica_id) for r in candidates
    )
    if (target in candidates
            and router._assigned_count(target.replica_id) - floor
            <= router.imbalance):
        router.stats["affinity_hits"] += 1
        router._m["affinity_hits"].inc()
        return target
    router.stats["affinity_spills"] += 1
    with router._pressure_lock:
        router._spill_times.append(router._clock())
    return _least_loaded(router, req, candidates)


#: name -> policy callable ``(router, req, candidates) -> Replica``;
#: FleetRouter also accepts a bare callable of the same shape
DISPATCH_POLICIES = {
    "least_loaded": _least_loaded,
    "prefix_affinity": _prefix_affinity,
    "weighted_rr": _weighted_rr,
    "random": _random,
}


class FleetRouter(object):
    """Route a request stream over a :class:`ReplicaSet` (see module
    docstring).  :meth:`serve` mirrors the engine contract: dict rows
    in, output rows/typed records out, in fleet input order.

    Args:
      predict: generation predictor (``serving_builder`` — replicas
        beyond the first come from ``predict.make_replica()``); may be
        None when ``replica_set`` is given.
      input_mapping: ``{column: input_name}`` — the USER mapping;
        the router adds its internal budget column for the replica
        engines unless a budget column is already mapped.
      output_mapping: optional ``{output_name: column}`` rename,
        applied router-side (replica engines emit raw outputs).
      replicas: replica count (or pass a prebuilt ``replica_set``
        whose engines were built with :meth:`engine_input_mapping`).
      num_slots / chunk / replica_queue_depth / engine_opts
        / predict_factory / poll_sec: forwarded to
        :class:`ReplicaSet` / :class:`Replica`.
      policy: FLEET admission policy — ``block`` (backpressure the
        source), ``reject`` (typed shed records past the fleet queue
        bound), ``degrade`` (shrink token budgets against the fleet
        backlog) — pressure spills across replicas first; a single
        engine never sheds while a sibling has room.
      dispatch: dispatch-policy name (:data:`DISPATCH_POLICIES`) or a
        callable ``(router, req, candidates) -> Replica``.
      queue_depth: fleet admission queue bound (default: the summed
        replica capacity — so total in-system tops out at ~2x what
        the replicas can hold, the engine's own 2x-slots spirit).
      degrade_floor: minimum per-request budget under ``degrade``.
      on_error: ``"record"`` (typed records, the fleet default) or
        ``"raise"`` (request faults re-raise naming the fleet index).
      replica_weights: optional {replica_id: weight} for
        ``weighted_rr`` / ``least_loaded``.
      imbalance: affinity fallback threshold (default
        ``max(2, num_slots)`` assigned requests ahead of the least
        loaded).
      affinity_width: fingerprint width in tokens (default the
        canonical :data:`~tensorflowonspark_tpu.prefix_cache.
        FINGERPRINT_TOKENS`).
      slow_factor / min_slow_sec / suspect_rounds / probe_every /
        readmit_rounds: straggler policy — a live replica whose
        completion-latency EWMA exceeds ``max(min_slow_sec,
        slow_factor * fleet median)`` for ``suspect_rounds``
        consecutive completions is routed around; it then receives
        one probe request every ``probe_every`` dispatches and
        re-admits after ``readmit_rounds`` consecutive clean probes.
      stats: optional dict filled with fleet counters.
      clock / seed / poll_sec: determinism knobs.
    """

    def __init__(self, predict, input_mapping, output_mapping=None, *,
                 replicas=2, num_slots=4, chunk=None,
                 replica_queue_depth=None, engine_opts=None,
                 predict_factory=None, replica_set=None,
                 policy="block", dispatch="least_loaded",
                 queue_depth=None, degrade_floor=1, on_error="record",
                 replica_weights=None, imbalance=None,
                 affinity_width=None, slow_factor=4.0,
                 min_slow_sec=0.05, suspect_rounds=2, probe_every=8,
                 readmit_rounds=3, readmit_gate=None, stats=None,
                 clock=None, seed=0, poll_sec=0.05,
                 pressure_window=30.0):
        if policy not in serving_engine.POLICIES:
            raise ValueError(
                "fleet policy must be one of {0}, got {1!r}".format(
                    serving_engine.POLICIES, policy
                )
            )
        if on_error not in serving_engine.ON_ERROR:
            raise ValueError(
                "on_error must be one of {0}, got {1!r}".format(
                    serving_engine.ON_ERROR, on_error
                )
            )
        if callable(dispatch):
            self._dispatch_policy = dispatch
            self.dispatch_name = getattr(
                dispatch, "__name__", "custom"
            )
        else:
            if dispatch not in DISPATCH_POLICIES:
                raise ValueError(
                    "dispatch must be a callable or one of {0}, got "
                    "{1!r}".format(
                        sorted(DISPATCH_POLICIES), dispatch
                    )
                )
            self._dispatch_policy = DISPATCH_POLICIES[dispatch]
            self.dispatch_name = dispatch
        self.user_mapping = dict(input_mapping)
        self.output_mapping = output_mapping
        self.user_budget_col = next(
            (c for c in input_mapping
             if input_mapping[c] == serving_engine.BUDGET_INPUT), None
        )
        self.budget_col = self.user_budget_col or FLEET_BUDGET_COL
        self.user_trace_col = next(
            (c for c in input_mapping
             if input_mapping[c] == serving_engine.TRACE_INPUT), None
        )
        self.trace_col = self.user_trace_col or FLEET_TRACE_COL
        self.tenant_col = next(
            (c for c in input_mapping
             if input_mapping[c] == serving_engine.TENANT_INPUT), None
        )
        self._trace_prefix = "flt%d" % next(_ROUTER_SEQ)
        self._ledger = ledger_mod.get_ledger()
        self.policy = policy
        self.on_error = on_error
        self.degrade_floor = max(1, int(degrade_floor))
        if replica_set is None:
            replica_set = ReplicaSet(
                predict, replicas,
                self.engine_input_mapping(input_mapping),
                num_slots=num_slots, chunk=chunk,
                queue_depth=replica_queue_depth,
                engine_opts=engine_opts,
                predict_factory=predict_factory,
            )
        self.replica_set = replica_set.start()
        self.replicas = replica_set.replicas
        self._completions = replica_set.completions
        eng0 = self.replicas[0].engine
        self.prompt_col = eng0.prompt_col
        self.max_new = int(eng0.max_new)
        self._eos_id = eng0.eos_id
        # the user-facing generated_len rule (engine _emit_len, minus
        # the router's internal budget column)
        self._user_emit_len = (
            self._eos_id is not None
            or self.user_budget_col is not None
            or policy == "degrade"
        )
        self.queue_depth = (
            max(1, int(queue_depth)) if queue_depth is not None
            else sum(r.capacity() for r in self.replicas)
        )
        # affinity stickiness: fall back to least-loaded only when the
        # target runs a full replica-capacity ahead of the least
        # loaded (the per-replica ROOM bound already backstops
        # overload — a tighter default would degrade affinity to
        # least-loaded under every burst and forfeit the cache hits)
        self.imbalance = (
            int(eng0.num_slots) + int(eng0.queue_depth)
            if imbalance is None else max(0, int(imbalance))
        )
        self.affinity_width = affinity_width
        self.slow_factor = float(slow_factor)
        self.min_slow_sec = float(min_slow_sec)
        self.suspect_rounds = max(1, int(suspect_rounds))
        self.probe_every = max(1, int(probe_every))
        self.readmit_rounds = max(1, int(readmit_rounds))
        #: optional quality gate on re-admission (a
        #: :class:`~tensorflowonspark_tpu.telemetry.health.
        #: CleanRoundsSensor`): a replica with enough clean probe
        #: rounds still waits until the HEALTH PLANE has seen N
        #: consecutive clean rounds fleet-wide — quality-gated, not
        #: timer-gated (ROADMAP 3 residual)
        self.readmit_gate = readmit_gate
        self._gate_blocked = {}   # rid -> True while gate holds it
        self._weights = dict(replica_weights or {})
        self._rr_current = {}
        self._rng = np.random.RandomState(int(seed))
        self._clock = clock if clock is not None else time.monotonic
        self._poll = float(poll_sec)
        # scheduling state
        self._queue = collections.deque()   # fids awaiting dispatch
        self._reqs = {}                     # fid -> request record
        self._assigned = collections.defaultdict(set)  # rid -> fids
        self._finished = {}
        self._emit_next = 0
        self._n_in = 0
        self._exhausted = False
        self._dispatch_count = 0
        self._lat_ewma = {}
        self._suspect = collections.defaultdict(int)
        self._clean = collections.defaultdict(int)
        self._deploy = None
        self.deploy_history = []
        self.stats = stats if stats is not None else {}
        self.stats.update({
            "latency_sec": {}, "done_at": {}, "dispatched": 0,
            "completed": 0, "errors": 0, "shed": 0, "expired": 0,
            "degraded": 0, "drained": 0, "redispatched": 0,
            "replica_deaths": 0, "quarantined": 0, "affinity_hits": 0,
            "affinity_spills": 0, "evicted": 0, "readmitted": 0,
            "scaled_up": 0, "scaled_down": 0,
            "replicas": len(self.replicas),
            "dispatch_policy": self.dispatch_name,
            "fleet_policy": policy,
            # fleet request id -> minted trace id (ISSUE 14): how a
            # caller (or test) pulls the merged trace of a specific
            # request after the run
            "trace_ids": {},
        })
        self._tracer = telemetry.get_tracer()
        reg = telemetry.get_registry()
        self._m = {
            name: reg.counter("fleet." + name)
            for name in (
                "dispatched", "redispatched", "completed", "shed",
                "affinity_hits", "replica_deaths", "evictions",
                "readmissions",
            )
        }
        self._m_live = reg.gauge("fleet.live_replicas")
        self._m_live.set(len(self.replicas))
        self._m_spawned = reg.counter("fleet.replicas_spawned")
        self._m_retired = reg.counter("fleet.replicas_retired")
        self._t0 = self._clock()
        # windowed admission-pressure statistic (ISSUE 16 satellite):
        # occupancy samples + shed/spill event times over the last
        # ``pressure_window`` seconds, so autoscaling decisions and
        # operators (/status) read the same number
        self.pressure_window = max(1.0, float(pressure_window))
        self._occupancy_samples = collections.deque()  # (t, occupancy)
        self._shed_times = collections.deque()
        self._spill_times = collections.deque()
        # pressure() is read off-thread (remediation sensors, /status
        # scrapes) while the serve pass appends — guard the deques
        self._pressure_lock = threading.Lock()
        # /status provider (weakref-bound like the engine's: a
        # finished router must never pin its replicas' decoders)
        import weakref

        from tensorflowonspark_tpu.telemetry import health as _health

        _ref = weakref.ref(self)

        def _fleet_status():
            rt = _ref()
            return (
                {"finished": True} if rt is None
                else rt.health_status()
            )

        _health.register_status_provider("fleet", _fleet_status)

    # -- small helpers ---------------------------------------------------

    def _weight(self, rid):
        return float(self._weights.get(rid, 1.0)) or 1.0

    def _assigned_count(self, rid):
        return len(self._assigned[rid])

    def outstanding_of(self, rid):
        """``(request_ids, trace_ids)`` currently assigned to replica
        ``rid`` — what fleet-action journal events attach so the
        forensics timeline connects the action to the requests it
        touched (ISSUE 14 satellite)."""
        fids = sorted(self._assigned[rid])
        return fids, [self.stats["trace_ids"].get(f) for f in fids]

    def _note_pressure(self):
        """One admission-pressure sample per serve pass (bounded by
        the window — trimmed on both sample and read)."""
        now = self._clock()
        with self._pressure_lock:
            self._occupancy_samples.append(
                (now, len(self._queue) / float(self.queue_depth))
            )
            horizon = now - self.pressure_window
            for dq in (self._occupancy_samples, self._shed_times,
                       self._spill_times):
                while dq and (dq[0][0]
                              if dq is self._occupancy_samples
                              else dq[0]) < horizon:
                    dq.popleft()

    def pressure(self):
        """The windowed admission-pressure statistic (ISSUE 16
        satellite): queue occupancy (now / mean / peak over the last
        ``pressure_window`` seconds) plus shed and affinity-spill
        rates over the same window.  Rides ``/status`` (fleet
        provider) so the autoscaling policy and an operator read the
        SAME number; also the sensor behind the remediation engine's
        spawn/retire decisions."""
        now = self._clock()
        horizon = now - self.pressure_window
        with self._pressure_lock:
            occ = [v for (t, v) in self._occupancy_samples
                   if t >= horizon]
            sheds = sum(1 for t in self._shed_times if t >= horizon)
            spills = sum(1 for t in self._spill_times if t >= horizon)
        occ_now = len(self._queue) / float(self.queue_depth)
        return {
            "window_sec": self.pressure_window,
            "occupancy": round(occ_now, 4),
            "occupancy_mean": round(
                sum(occ) / len(occ), 4
            ) if occ else round(occ_now, 4),
            "occupancy_peak": round(max(occ), 4) if occ else round(
                occ_now, 4
            ),
            "queued": len(self._queue),
            "queue_depth": self.queue_depth,
            "shed_per_sec": round(sheds / self.pressure_window, 4),
            "spill_per_sec": round(spills / self.pressure_window, 4),
            "free_slots": sum(
                max(0, self._room(r)) for r in self.replicas
                if r.alive and r.state == "live"
            ),
        }

    def health_status(self):
        """Fleet summary for ``/status``: routing policy, per-replica
        load snapshots, and the deploy state."""
        return {
            "pressure": self.pressure(),
            "replicas": len(self.replicas),
            "live": sum(
                1 for r in self.replicas
                if r.alive and r.state == "live"
            ),
            "dispatch": self.dispatch_name,
            "policy": self.policy,
            "queued": len(self._queue),
            "queue_depth": self.queue_depth,
            "outstanding": sum(
                len(v) for v in self._assigned.values()
            ),
            "completed": self.stats["completed"],
            "shed": self.stats["shed"],
            "replica_deaths": self.stats["replica_deaths"],
            "deploy": (
                self._deploy.status if self._deploy is not None
                else (self.deploy_history[-1]
                      if self.deploy_history else None)
            ),
            "loads": self.replica_set.load(),
            # per-replica cost rows (ISSUE 14): what each replica
            # burned and produced so far — decode chip-seconds,
            # tokens emitted, prefix tokens saved
            "costs": {
                r.replica_id: {
                    "state": r.state,
                    "chip_sec": round(float(
                        r.stats.get("decode_wall_sec", 0.0)
                    ), 6),
                    "tokens_out": int(r.stats.get("tokens_out", 0)),
                    "completed": int(r.stats.get("completed", 0)),
                    "prefix_tokens_saved": int(
                        r.stats.get("prefix_tokens_saved", 0)
                    ),
                }
                for r in self.replicas
            },
        }

    def load(self):
        """Fleet-level load: summed free slots / queue depths over
        live replicas plus the router's own backlog."""
        live = [r.load() for r in self.replicas if r.alive]
        return {
            "replicas": len(self.replicas),
            "live": len(live),
            "free_slots": sum(s["free_slots"] for s in live),
            "in_flight": sum(s["in_flight"] for s in live),
            "queued": (
                sum(s["queued"] for s in live) + len(self._queue)
            ),
            "queue_depth": self.queue_depth,
        }

    def engine_input_mapping(self, input_mapping=None):
        """The ENGINE-level mapping the replicas must be built with:
        the user mapping plus the router's internal budget column
        (resumed re-dispatches carry reduced budgets through it) and
        its internal trace column (the fleet-minted request trace id
        every engine span then rides — ISSUE 14)."""
        m = dict(input_mapping or self.user_mapping)
        if not any(v == serving_engine.BUDGET_INPUT
                   for v in m.values()):
            m[FLEET_BUDGET_COL] = serving_engine.BUDGET_INPUT
        if not any(v == serving_engine.TRACE_INPUT
                   for v in m.values()):
            m[FLEET_TRACE_COL] = serving_engine.TRACE_INPUT
        return m

    # -- admission -------------------------------------------------------

    def _shed(self, fid, rid, why):
        self.stats["shed"] += 1
        self._m["shed"].inc()
        with self._pressure_lock:
            self._shed_times.append(self._clock())
        # the mark rides the REQUEST's trace and names it in attrs
        # (ISSUE 14 satellite: fleet actions connect to the requests
        # they touched, not just a generic trace="fleet")
        self._tracer.mark(
            "fleet_shed", trace=rid, severity="warn",
            request_index=fid, trace_id=rid,
            queue_depth=self.queue_depth,
        )
        self._ledger.close(rid, tokens_out=0)
        self._finished[fid] = serving_engine.error_record(
            "shed", fid, why
        )

    def _rid_of(self, fid, row):
        """The request's fleet trace id: minted here unless the caller
        mapped its own :data:`~tensorflowonspark_tpu.serving_engine.
        TRACE_INPUT` column with a usable value."""
        if self.user_trace_col is not None and isinstance(row, dict):
            v = row.get(self.user_trace_col)
            if isinstance(v, str) and v:
                return v
        return "%s-req%d" % (self._trace_prefix, fid)

    def _admit(self, row):
        fid = self._n_in
        self._n_in += 1
        rid = self._rid_of(fid, row)
        self.stats["trace_ids"][fid] = rid
        if self.policy == "reject":
            # spill-before-shed: free replica room is admission
            # capacity too (the refill runs before dispatch, so
            # counting only queue_depth would shed requests a sibling
            # replica was about to take — the engine _refill's rule,
            # fleet-wide)
            cap = self.queue_depth + sum(
                max(0, self._room(r)) for r in self.replicas
                if r.alive and r.state == "live"
            )
            if len(self._queue) >= cap:
                self._shed(
                    fid, rid,
                    "request {0} shed: fleet admission queue full "
                    "({1} waiting, depth {2}, policy 'reject')".format(
                        fid, len(self._queue), self.queue_depth
                    ),
                )
                return
        budget = self.max_new
        if self.user_budget_col is not None:
            try:
                budget = max(
                    1, min(int(row[self.user_budget_col]), self.max_new)
                )
            except (KeyError, TypeError, ValueError):
                pass  # the engine's validation names the bad column
        if self.policy == "degrade":
            backlog = len(self._queue)
            if backlog > self.queue_depth:
                shrunk = max(
                    self.degrade_floor,
                    (budget * self.queue_depth) // backlog,
                )
                if shrunk < budget:
                    budget = shrunk
                    self.stats["degraded"] += 1
        prompt = None
        fp = None
        try:
            prompt = np.asarray(row[self.prompt_col], np.int32).ravel()
            fp = fingerprint(
                prompt, self.affinity_width
            ) if self.affinity_width else fingerprint(prompt)
        except Exception:  # noqa: BLE001 - validation is the engine's
            pass
        tenant = None
        if self.tenant_col is not None:
            v = row.get(self.tenant_col) if isinstance(row, dict) else None
            if isinstance(v, str) and v:
                tenant = v  # junk values: the engine names the error
        self._reqs[fid] = {
            "row": row, "prompt": prompt, "budget": budget,
            "committed": [], "excluded": set(), "replica": None,
            "fingerprint": fp, "submit": self._clock(),
            "sent_at": None, "redispatches": 0,
            "rid": rid, serving_engine.TENANT_INPUT: tenant,
        }
        # open the cost row at FLEET admission with the user-facing
        # prompt size: a later re-dispatch re-admits prompt+committed
        # engine-side, and the ledger's set-if-unset keeps this value
        self._ledger.open(
            rid, tenant=tenant,
            tokens_in=int(prompt.shape[0]) if prompt is not None else None,
        )
        self._tracer.mark(
            "fleet_admission", trace=rid, request_index=fid,
            trace_id=rid,
        )
        self._queue.append(fid)

    def _room(self, replica):
        return replica.capacity() - self._assigned_count(
            replica.replica_id
        )

    def _pull(self, it):
        """Source pull per fleet admission policy (the engine's
        vocabulary, one level up — see class docstring)."""
        if self._exhausted:
            return
        if self.policy == "block":
            # backpressure: pull no faster than the fleet can place —
            # at most the summed free room of routable replicas.  Per
            # PASS the pull is bounded by the live replica count: a
            # slow (paced) source would otherwise hold the control
            # loop inside next(it) accumulating an artificial burst,
            # stalling completions and skewing dispatch
            live = [
                r for r in self.replicas
                if r.alive and r.state == "live"
            ]
            room = sum(max(0, self._room(r)) for r in live)
            budget = max(1, len(live))
            while budget and len(self._queue) < room:
                try:
                    row = next(it)
                except StopIteration:
                    self._exhausted = True
                    return
                self._admit(row)
                budget -= 1
            return
        # reject/degrade: every available request has arrived — drain
        # the source; _admit sheds or shrinks against the backlog
        while True:
            try:
                row = next(it)
            except StopIteration:
                self._exhausted = True
                return
            self._admit(row)

    # -- dispatch --------------------------------------------------------

    def _candidates(self, req):
        live = [
            r for r in self.replicas
            if r.alive and r.state == "live"
            and r.replica_id not in req["excluded"]
        ]
        cands = [r for r in live if self._room(r) > 0]
        if cands or live:
            return cands
        # every live replica excluded or none left live: fall back to
        # routed-around replicas (serve slow rather than drop), then
        # clear the excluded set (a re-dispatch loop must not wedge on
        # a fully-excluded fleet)
        around = [
            r for r in self.replicas
            if r.alive and r.state == "routed_around"
            and r.replica_id not in req["excluded"]
            and self._room(r) > 0
        ]
        if around:
            return around
        retry = [
            r for r in self.replicas
            if r.alive and r.state in ("live", "routed_around")
            and self._room(r) > 0
        ]
        if retry:
            req["excluded"].clear()
        return retry

    def _probe_target(self, req):
        """Every ``probe_every``-th dispatch goes to a routed-around
        replica (lowest id with room) so its recovery is observable —
        the re-admission signal."""
        if self._dispatch_count % self.probe_every:
            return None
        for r in self.replicas:
            if (r.alive and r.state == "routed_around"
                    and r.replica_id not in req["excluded"]
                    and self._room(r) > 0):
                return r
        return None

    def _dispatch(self):
        while self._queue:
            fid = self._queue[0]
            req = self._reqs[fid]
            if req["committed"] and len(req["committed"]) >= req["budget"]:
                # the dead replica already committed the full budget —
                # nothing left to decode
                self._queue.popleft()
                self._finalize_resumed_complete(fid, req)
                continue
            if not any(
                r.alive and r.state in ("live", "routed_around")
                for r in self.replicas
            ):
                self._queue.popleft()
                self.stats["errors"] += 1
                self._ledger.close(
                    req["rid"], tokens_out=len(req["committed"])
                )
                self._finished[fid] = serving_engine.error_record(
                    "replica_lost", fid,
                    "request {0}: no live replica remains in the "
                    "fleet".format(fid),
                    tokens_done=len(req["committed"]),
                    partial=req["committed"],
                )
                self._reqs.pop(fid, None)
                continue
            target = self._probe_target(req)
            if target is None:
                cands = self._candidates(req)
                if not cands:
                    return  # all routable replicas full: wait
                target = self._dispatch_policy(self, req, cands)
            self._queue.popleft()
            self._send(fid, req, target)

    def _send(self, fid, req, replica):
        rid = replica.replica_id
        row = dict(req["row"])
        committed = req["committed"]
        if committed:
            row[self.prompt_col] = np.concatenate([
                req["prompt"],
                np.asarray(committed, np.int32),
            ])
        row[self.budget_col] = req["budget"] - len(committed)
        # the fleet trace id rides the row into the replica engine:
        # its whole span chain joins this request's trace, and a
        # re-dispatch CONTINUES the same trace on the next replica
        row[self.trace_col] = req["rid"]
        req["replica"] = rid
        req["sent_at"] = self._clock()
        self._assigned[rid].add(fid)
        self._dispatch_count += 1
        self.stats["dispatched"] += 1
        self._m["dispatched"].inc()
        if self._tracer.enabled:
            self._tracer.add(
                "fleet_dispatch", self._tracer.now(), 0.0,
                trace=req["rid"], replica=rid, request_index=fid,
                resumed_tokens=len(committed),
            )
        replica.dispatch(fid, row)

    # -- completion / death handling -------------------------------------

    def _collect(self):
        block = bool(self._queue or self._reqs)
        try:
            ev = self._completions.get(
                timeout=self._poll if block else 0.0
            )
        except queue_mod.Empty:
            return
        while True:
            self._handle(ev)
            try:
                ev = self._completions.get_nowait()
            except queue_mod.Empty:
                return

    def _handle(self, ev):
        kind = ev[0]
        if kind == "done":
            _, rid, fid, out = ev
            self._assigned[rid].discard(fid)
            req = self._reqs.pop(fid, None)
            if req is None:
                return
            self._observe_latency(rid, req)
            self._finalize(fid, req, out, rid)
        elif kind == "dead":
            _, rid, wreck = ev
            self._on_death(rid, wreck)
        elif kind == "quarantine":
            _, rid, wreck = ev
            self._on_quarantine(rid, wreck)
        # "stopped" needs no action (clean close)

    def _on_death(self, rid, wreck):
        replica = self.replicas[rid]
        n_redisp = len(wreck["committed"]) + len(wreck["queued"])
        self.stats["replica_deaths"] += 1
        self._m["replica_deaths"].inc()
        self._m_live.set(
            sum(1 for r in self.replicas if r.alive)
        )
        # the affected requests ride the mark's attrs (ISSUE 14
        # satellite): the journal/forensics timeline can connect this
        # fleet action to the requests it touched
        touched = sorted(
            set(wreck["committed"]) | set(wreck["queued"])
            | set(wreck["finished"])
        )
        self._tracer.mark(
            "replica_dead", trace="fleet", severity="page",
            replica=rid, error=str(replica.error),
            finished=len(wreck["finished"]), redispatching=n_redisp,
            request_ids=touched,
            trace_ids=[
                self.stats["trace_ids"].get(f) for f in touched
            ],
        )
        logger.warning(
            "fleet: replica %d died (%s); delivering %d finished "
            "row(s), re-dispatching %d request(s)", rid,
            replica.error, len(wreck["finished"]), n_redisp,
        )
        self._requeue_wreckage(rid, wreck)

    def _on_quarantine(self, rid, wreck):
        """A replica contained a DEVICE error: quarantine it via the
        evict verb (probe traffic only while it rebuilds and proves
        itself) and continue its in-flight requests
        committed-token-safe on a survivor — each request's merged
        trace carries straight on, the same re-dispatch invariant the
        death path pins."""
        replica = self.replicas[rid]
        self.replica_set.evict(rid)
        self._suspect[rid] = 0
        self._clean[rid] = 0
        self.stats["quarantined"] += 1
        self.stats["evicted"] += 1
        self._m["evictions"].inc()
        n_redisp = len(wreck["committed"]) + len(wreck["queued"])
        touched = sorted(
            set(wreck["committed"]) | set(wreck["queued"])
            | set(wreck["finished"])
        )
        self._tracer.mark(
            "replica_quarantined", trace="fleet", severity="page",
            replica=rid, error=str(replica.error),
            finished=len(wreck["finished"]), redispatching=n_redisp,
            request_ids=touched,
            trace_ids=[
                self.stats["trace_ids"].get(f) for f in touched
            ],
        )
        logger.warning(
            "fleet: replica %d quarantined on device error (%s); "
            "delivering %d finished row(s), re-dispatching %d "
            "request(s) on survivors", rid, replica.error,
            len(wreck["finished"]), n_redisp,
        )
        self._requeue_wreckage(rid, wreck)

    def _requeue_wreckage(self, rid, wreck):
        """Deliver a wrecked replica's finished rows and re-dispatch
        the rest (committed-token-safe) — shared by the death and
        quarantine paths."""
        # finished-but-unemitted rows are real results — deliver
        for fid, out in sorted(wreck["finished"].items()):
            self._assigned[rid].discard(fid)
            req = self._reqs.pop(fid, None)
            if req is not None:
                self._finalize(fid, req, out, rid)
        # in-flight work re-dispatches from its committed tokens,
        # queued work from scratch — wrecked replica excluded
        resumed = []
        for fid, committed in wreck["committed"].items():
            req = self._reqs.get(fid)
            if req is None:
                continue
            req["committed"] = req["committed"] + [
                int(t) for t in committed
            ]
            resumed.append(fid)
        for fid in wreck["queued"]:
            if fid in self._reqs:
                resumed.append(fid)
        # anything the router still counts against the dead replica
        # but the wreckage missed (defensive) re-dispatches too
        for fid in sorted(self._assigned.pop(rid, set())):
            if fid in self._reqs and fid not in resumed:
                resumed.append(fid)
        for fid in sorted(set(resumed)):
            req = self._reqs[fid]
            req["excluded"].add(rid)
            req["replica"] = None
            req["redispatches"] += 1
            self.stats["redispatched"] += 1
            self._m["redispatched"].inc()
            self._ledger.redispatch(req["rid"])
            # the mark rides the request's OWN trace (the re-dispatch
            # is one hop of that request's story), naming it in attrs
            self._tracer.mark(
                "fleet_redispatch", trace=req["rid"], severity="warn",
                request_index=fid, trace_id=req["rid"],
                from_replica=rid,
                tokens_committed=len(req["committed"]),
            )
        self._queue.extendleft(sorted(set(resumed), reverse=True))

    # -- straggler policy ------------------------------------------------

    def _observe_latency(self, rid, req):
        if req["sent_at"] is None:
            return
        lat = self._clock() - req["sent_at"]
        prev = self._lat_ewma.get(rid)
        self._lat_ewma[rid] = (
            lat if prev is None else 0.5 * prev + 0.5 * lat
        )
        replica = self.replicas[rid]
        others = [
            v for r2, v in self._lat_ewma.items()
            if r2 != rid and self.replicas[r2].alive
        ]
        med = _median(others)
        if med is None:
            return
        threshold = max(self.min_slow_sec, self.slow_factor * med)
        if replica.state == "live":
            if self._lat_ewma[rid] > threshold:
                self._suspect[rid] += 1
                if self._suspect[rid] >= self.suspect_rounds:
                    self.replica_set.evict(rid)
                    self._suspect[rid] = 0
                    self._clean[rid] = 0
                    self.stats["evicted"] += 1
                    self._m["evictions"].inc()
                    outstanding = sorted(self._assigned[rid])
                    self._tracer.mark(
                        "replica_evicted", trace="fleet",
                        severity="warn", replica=rid,
                        ewma_sec=round(self._lat_ewma[rid], 4),
                        fleet_median_sec=round(med, 4),
                        request_ids=outstanding,
                        trace_ids=[
                            self.stats["trace_ids"].get(f)
                            for f in outstanding
                        ],
                    )
                    logger.warning(
                        "fleet: routing around slow replica %d "
                        "(ewma %.3fs vs fleet median %.3fs)",
                        rid, self._lat_ewma[rid], med,
                    )
            else:
                self._suspect[rid] = 0
        elif replica.state == "routed_around":
            if lat <= threshold:
                self._clean[rid] += 1
                if self._clean[rid] >= self.readmit_rounds:
                    gate = self.readmit_gate
                    if gate is not None:
                        gate.poll()
                        if not gate.ready():
                            # quality gate holds the re-admission:
                            # enough clean PROBE rounds, but the
                            # health plane has not yet seen N clean
                            # rounds fleet-wide.  Journal once per
                            # blocked streak; keep probing.
                            if not self._gate_blocked.get(rid):
                                self._gate_blocked[rid] = True
                                self._tracer.mark(
                                    "readmit_gated", trace="fleet",
                                    severity="warn", replica=rid,
                                    clean_probe_rounds=self._clean[
                                        rid],
                                    clean_health_rounds=gate.streak,
                                    required_rounds=gate.rounds,
                                )
                                logger.info(
                                    "fleet: re-admission of replica "
                                    "%d gated on health plane (%d/%d "
                                    "clean rounds)", rid, gate.streak,
                                    gate.rounds,
                                )
                            return
                    if self._gate_blocked.pop(rid, None):
                        self._tracer.mark(
                            "readmit_cleared", trace="fleet",
                            replica=rid,
                            clean_health_rounds=(
                                gate.streak if gate is not None
                                else None
                            ),
                        )
                    self.replica_set.readmit(rid)
                    self._clean[rid] = 0
                    self._lat_ewma[rid] = lat
                    self.stats["readmitted"] += 1
                    self._m["readmissions"].inc()
                    self._tracer.mark(
                        "replica_readmitted", trace="fleet",
                        replica=rid,
                    )
                    logger.info(
                        "fleet: re-admitted replica %d after %d "
                        "clean probe round(s)", rid,
                        self.readmit_rounds,
                    )
            else:
                self._clean[rid] = 0

    # -- finalize --------------------------------------------------------

    def _finalize_resumed_complete(self, fid, req):
        """A re-dispatched request whose committed tokens already
        cover its budget: synthesize the final row without decoding
        anything (the tokens were committed pre-death)."""
        fill = self._eos_id if self._eos_id is not None else 0
        arr = np.full((self.max_new,), fill, np.int32)
        toks = req["committed"][:self.max_new]
        arr[:len(toks)] = toks
        out = {"generated": arr,
               "generated_len": np.int32(min(req["budget"],
                                             len(toks)))}
        req["committed"] = []
        self._reqs.pop(fid, None)
        self._finalize(fid, req, out, None)

    def _finalize(self, fid, req, out, rid):
        committed = req["committed"]
        if "error" in out:
            rec = dict(out["error"])
            rec["request_index"] = fid
            if rid is not None:
                rec["replica"] = rid
            if committed:
                rec["partial"] = committed + list(rec.get("partial", []))
                rec["tokens_done"] = len(rec["partial"])
            if self.on_error == "raise" and rec["kind"] in _RAISE_KINDS:
                raise serving_engine.RequestError(
                    "fleet request {0} failed on replica {1}: "
                    "{2}".format(fid, rid, rec["message"]),
                    kind=rec["kind"], request_index=fid,
                )
            if rec["kind"] in ("deadline",):
                self.stats["expired"] += 1
            elif rec["kind"] in ("drained",):
                self.stats["drained"] += 1
            else:
                self.stats["errors"] += 1
            self._ledger.close(
                req["rid"], tokens_out=rec.get("tokens_done", 0),
                latency_sec=self._clock() - req["submit"],
            )
            self._finished[fid] = {"error": rec}
            return
        if committed:
            # reassemble: committed prefix + the resumed continuation
            # (token-identical to an undisturbed greedy run — the
            # watchdog-recovery invariant, fleet-wide)
            gen = np.asarray(out["generated"], np.int32).ravel()
            merged = np.concatenate([
                np.asarray(committed, np.int32), gen
            ])[:self.max_new]
            fill = self._eos_id if self._eos_id is not None else 0
            if merged.shape[0] < self.max_new:
                merged = np.concatenate([
                    merged,
                    np.full((self.max_new - merged.shape[0],), fill,
                            np.int32),
                ])
            out = dict(out, generated=merged)
            if "generated_len" in out:
                out["generated_len"] = np.int32(
                    len(committed) + int(out["generated_len"])
                )
        # the AUTHORITATIVE emitted-token count for the cost row: the
        # merged committed+continuation length (the replica engine's
        # earlier close only saw its own continuation) — per-tenant
        # token totals then match the emitted outputs exactly
        if "generated_len" in out:
            tokens_out = int(out["generated_len"])
        else:
            tokens_out = len(committed) + self.max_new
        if not self._user_emit_len:
            out.pop("generated_len", None)
        out = serving_engine.apply_output_mapping(
            out, self.output_mapping
        )
        now = self._clock()
        self.stats["completed"] += 1
        self.stats["latency_sec"][fid] = now - req["submit"]
        self.stats["done_at"][fid] = now - self._t0
        self._m["completed"].inc()
        self._ledger.close(
            req["rid"], tokens_out=tokens_out,
            latency_sec=now - req["submit"],
        )
        self._finished[fid] = out

    def _drain_ready(self):
        while self._emit_next in self._finished:
            yield self._finished.pop(self._emit_next)
            self._emit_next += 1

    # -- remediation verbs (ISSUE 16) ------------------------------------

    def deploy_active(self):
        """True while a rolling deploy is mid-step — the remediation
        engine's conflict rule reads this (never fight a deploy)."""
        return self._deploy is not None and not self._deploy.finished

    def set_policy(self, policy):
        """Flip the fleet admission policy at runtime (``_pull`` and
        ``_admit`` consult ``self.policy`` every pass, so the flip
        takes effect on the next serve pass).  The remediation
        engine's degrade-on-page actuator; returns the PRIOR policy
        so the caller can restore it on resolve."""
        if policy not in serving_engine.POLICIES:
            raise ValueError(
                "fleet policy must be one of {0}, got {1!r}".format(
                    serving_engine.POLICIES, policy
                )
            )
        prior, self.policy = self.policy, policy
        self.stats["fleet_policy"] = policy
        if policy != prior:
            self._tracer.mark(
                "fleet_policy_changed", trace="fleet",
                policy=policy, prior=prior,
            )
        return prior

    def scale_up(self):
        """Spawn one replica (ReplicaSet.spawn) and route to it
        immediately — the autoscaling / capacity-restore actuator.
        Returns the new replica id."""
        r = self.replica_set.spawn()
        self.stats["replicas"] = len(self.replicas)
        self.stats["scaled_up"] += 1
        self._m_spawned.inc()
        self._m_live.set(sum(
            1 for x in self.replicas
            if x.alive and x.state == "live"
        ))
        self._tracer.mark(
            "replica_spawned", trace="fleet",
            replica_id=r.replica_id, replicas=len(self.replicas),
        )
        return r.replica_id

    def scale_down(self, replica_id=None):
        """Retire one live replica: drain it (no new traffic; its
        in-flight work completes and the collect path drains it back
        to the queue on close) and close it.  Picks the least-loaded
        live replica when ``replica_id`` is None; refuses to retire
        the last live replica.  Returns the retired id, or None when
        nothing is retirable."""
        live = [
            r for r in self.replicas
            if r.alive and r.state == "live"
        ]
        if len(live) <= 1:
            return None
        if replica_id is None:
            r = min(
                live, key=lambda x: (
                    self._assigned_count(x.replica_id), x.replica_id
                )
            )
        else:
            r = self.replicas[replica_id]
            if not (r.alive and r.state == "live"):
                return None
        rid = r.replica_id
        fids, trace_ids = self.outstanding_of(rid)
        self.replica_set.drain(rid)
        # the STOP sentinel queues BEHIND any dispatched rows: the
        # worker finishes in-flight work ("done" completions flow
        # normally), then posts "stopped" and exits
        r.close()
        self.stats["scaled_down"] += 1
        self._m_retired.inc()
        self._m_live.set(sum(
            1 for x in self.replicas
            if x.alive and x.state == "live"
        ))
        self._tracer.mark(
            "replica_retired", trace="fleet", severity="warn",
            replica_id=rid, request_ids=fids, trace_ids=trace_ids,
        )
        return rid

    # -- rolling deploys -------------------------------------------------

    def start_rolling_deploy(self, params=None, step=None,
                             step_dir=None, **opts):
        """Arm a zero-downtime rolling deploy, advanced by the serve
        loop one state-machine step per pass (fleet/deploy.py).
        Returns the :class:`~tensorflowonspark_tpu.fleet.deploy.
        RollingDeploy` (poll ``.status``)."""
        from tensorflowonspark_tpu.fleet.deploy import RollingDeploy

        if self._deploy is not None and not self._deploy.finished:
            raise RuntimeError(
                "a rolling deploy is already in progress "
                "({0})".format(self._deploy.status)
            )
        self._deploy = RollingDeploy(
            params=params, step=step, step_dir=step_dir, **opts
        )
        return self._deploy

    def _deploy_step(self):
        if self._deploy is None:
            return
        if self._deploy.step_machine(self):
            self.deploy_history.append(self._deploy.status)
            self._deploy = None

    # -- the routing loop ------------------------------------------------

    def serve(self, rows):
        """Route ``rows`` over the fleet; yields output rows / typed
        records in fleet input order.  Replicas keep running after the
        stream ends (warm caches, pending deploys) — close them via
        :meth:`close` / the :func:`predict_rows_fleet` wrapper.

        ``serve`` is re-entrant: each call opens a fresh stream over
        the same warm fleet (the soak harness serves load in waves,
        probing invariants between streams)."""
        it = iter(rows)
        self._exhausted = False
        while True:
            self._deploy_step()
            self._pull(it)
            self._note_pressure()
            self._dispatch()
            self._collect()
            for r in self._drain_ready():
                yield r
            if (self._exhausted and not self._reqs
                    and not self._queue):
                if self._deploy is not None:
                    # a deploy armed mid-stream finishes against idle
                    # replicas before the generator returns
                    while self._deploy is not None:
                        self._deploy_step()
                        time.sleep(self._poll / 5.0)
                for r in self._drain_ready():
                    yield r
                self._roll_up_stats()
                return

    def _roll_up_stats(self):
        per = {}
        for r in self.replicas:
            per[r.replica_id] = dict(r.stats)
            per[r.replica_id]["state"] = r.state
        self.stats["per_replica"] = per
        for key in ("admitted", "prefix_hits", "prefix_tokens_saved",
                    "swaps", "swap_commits", "rollbacks",
                    "swap_requeued", "watchdog_fires", "tokens_out"):
            self.stats[key] = sum(
                int(s.get(key, 0)) for s in per.values()
            )
        # fleet decode wall time: summed per-replica (each replica owns
        # its chip — the ledger's chip-second rows sum back to this)
        self.stats["decode_wall_sec"] = sum(
            float(s.get("decode_wall_sec", 0.0)) for s in per.values()
        )

    def close(self, timeout=30.0):
        self.replica_set.close(timeout=timeout)


def predict_rows_fleet(predict, rows, input_mapping,
                       output_mapping=None, num_slots=4, *, replicas,
                       stats=None, on_error="raise", queue_depth=None,
                       policy="block", watchdog_timeout=None,
                       default_deadline=None,
                       replica_policy="least_loaded",
                       fleet_queue_depth=None, chunk=None):
    """The fleet twin of ``predict_rows(schedule="continuous")``
    (serving.py routes here when ``replicas > 1``): N in-process
    engine replicas behind a :class:`FleetRouter`.  Same contract —
    dict rows in, rows/typed records out in input order — with the
    engine-level overload knobs applied per replica and the admission
    policy applied FLEET-level (spill before shed)."""
    engine_opts = {}
    if watchdog_timeout is not None:
        engine_opts["watchdog_timeout"] = watchdog_timeout
    if default_deadline is not None:
        engine_opts["default_deadline"] = default_deadline
    router = FleetRouter(
        predict, input_mapping, output_mapping,
        replicas=int(replicas), num_slots=num_slots, chunk=chunk,
        replica_queue_depth=queue_depth, engine_opts=engine_opts,
        policy=policy, dispatch=replica_policy,
        queue_depth=fleet_queue_depth, on_error=on_error,
        stats=stats,
    )
    try:
        for r in router.serve(rows):
            yield r
    finally:
        router.close()
