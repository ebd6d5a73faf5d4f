"""Replica set: N serving engines with per-replica lifecycle.

A :class:`Replica` is one continuous-batching engine
(:class:`~tensorflowonspark_tpu.serving_engine.ServingEngine`) plus
the plumbing that makes it routable: a bounded feed queue, a worker
thread driving the engine's scheduling loop, submit/emit bookkeeping
that pairs fleet request ids with the engine's input-order output
stream, and post-mortem wreckage collection so the router can
re-dispatch a dead replica's work from its committed tokens.

A :class:`ReplicaSet` owns N of them.  For tests and single-host
deployments the replicas are in-process ``ServingEngine`` workers
(each with its OWN :class:`~tensorflowonspark_tpu.models.transformer.
SlotDecoder` and its own radix prefix cache — ``serving_builder``
predictors expose ``make_replica()`` exactly for this); for executor
fleets the same duck-typed seam (``engine_factory``) fits an
executor-resident engine proxied over the reservation wire — the
router only ever touches ``dispatch`` / ``load`` / the completion
queue, never the engine internals.

The replica feed uses the engine's **source heartbeat protocol**
(:meth:`ServingEngine._pull_one`): between arrivals the feed yields
``None`` so an idle engine still runs its lifecycle pass (hot-swap
requests land on drained replicas — what rolling deploys need) and a
busy engine never blocks decode waiting on the queue.
"""

import logging
import queue as queue_mod
import threading

from tensorflowonspark_tpu import serving_engine

logger = logging.getLogger(__name__)

#: feed-queue sentinel: the replica finishes in-flight work and exits
_STOP = object()

#: replica lifecycle states (router-managed; see fleet/router.py):
#: ``live`` receives traffic, ``routed_around`` only probe traffic (a
#: slow replica working off its backlog), ``draining`` none (a rolling
#: deploy quiescing it), ``dead`` is terminal for the in-process shape
#: (an executor fleet would respawn through the supervisor).
STATES = ("live", "routed_around", "draining", "dead")


class ReplicaKilled(RuntimeError):
    """A chaos ``kill_replica`` fault fired inside this replica's
    decode dispatch — the in-process stand-in for a replica
    process/chip death mid-decode (testing/chaos.py)."""


class ReplicaDeviceError(RuntimeError):
    """A DEVICE error inside this replica's engine (an XLA runtime
    fault on a mesh-sharded program, or the chaos ``device_error``
    stand-in).  Unlike :class:`ReplicaKilled` — terminal — the
    replica's host-side scheduler survived: it posts its wreckage for
    committed-token-safe re-dispatch, QUARANTINES (the router's evict
    verb routes around it), rebuilds its engine (the predictor caches
    the compiled decoder, so this is cheap), and serves probe traffic
    until clean rounds re-admit it."""


#: exception type names treated as device errors when they surface
#: inside a replica's serve loop (jaxlib's runtime error classes are
#: matched by NAME so the containment works without importing jaxlib
#: internals)
_DEVICE_ERROR_NAMES = ("XlaRuntimeError", "JaxRuntimeError",
                      "InternalError")

#: engine stats keys that must stay CUMULATIVE across a quarantine
#: rebuild (a fresh engine resets the shared stats dict in place; the
#: ledger invariant — per-request rows summing to the fleet's decode
#: wall — needs the pre-quarantine spend preserved)
_CUMULATIVE_STATS = (
    "admitted", "completed", "chunks", "errors", "shed", "expired",
    "degraded", "watchdog_fires", "recovered", "request_wire_bytes",
    "prefix_hits", "prefix_tokens_saved", "evictions",
    "pressure_evictions", "swaps", "swap_requeued", "drained",
    "decode_wall_sec", "tokens_out", "prefill_wall_sec",
    "prefill_watchdog_fires", "prefill_worker_deaths",
    "prefill_restarts", "leases_reaped",
)


def _is_device_error(exc):
    """Does ``exc`` look like a device/runtime fault (quarantinable)
    rather than a scheduler bug or chaos kill (terminal)?"""
    if isinstance(exc, ReplicaDeviceError):
        return True
    return type(exc).__name__ in _DEVICE_ERROR_NAMES


class Replica(object):
    """One routable serving engine (see module docstring).

    Args:
      replica_id: stable int id (chaos plans and journal events name
        replicas by it).
      predict: this replica's OWN generation predictor (fresh jitted
        programs + radix cache — see ``serving_builder`` /
        ``make_replica``).
      input_mapping: the ENGINE-level mapping (the router builds it:
        user mapping + its internal budget column).
      completions: the router's shared completion queue; the worker
        posts ``("done", rid, fid, row)``, ``("dead", rid, wreck)``
        and ``("stopped", rid)`` tuples.
      num_slots / chunk / queue_depth / engine_opts: forwarded to
        :class:`ServingEngine` (policy is always ``block`` — fleet
        admission sheds BEFORE any single engine would, so the engine
        itself never rejects).
      engine_factory: override building the engine (the executor-
        resident seam; default builds an in-process ServingEngine).
      fault_fn: chunk-dispatch fault hook (chaos ``kill_replica`` /
        ``slow_replica``); defaults to the plan's
        :func:`~tensorflowonspark_tpu.testing.chaos.replica_fault_fn`.
      device: optional ``jax.Device`` the worker pins as default, so
        this replica's weights, KV pools and programs live on its own
        chip (:class:`ReplicaSet` hands out one per local device).
      poll_sec: idle feed-poll interval (the heartbeat cadence — also
        how often an IDLE replica runs its lifecycle pass).
    """

    def __init__(self, replica_id, predict, input_mapping, completions,
                 *, num_slots=4, chunk=None, queue_depth=None,
                 engine_opts=None, engine_factory=None, fault_fn=None,
                 device=None, poll_sec=0.02):
        self.replica_id = int(replica_id)
        self.predict = predict
        self.state = "live"
        self.error = None
        if device is not None and getattr(predict, "mesh", None) is not None:
            # a TP-sharded predictor owns its device placement: its
            # committed mesh shardings (weights, KV pool) span several
            # devices, and pinning a single default device would fight
            # GSPMD.  The router above neither knows nor cares — the
            # replica surface is unchanged.
            device = None
        self.device = device
        self._poll_sec = float(poll_sec)
        self._completions = completions
        self._q = queue_mod.Queue()
        self._submitted = []   # fleet id per engine input index
        self._emitted = 0
        self.stats = {}
        if fault_fn is None:
            from tensorflowonspark_tpu.testing import chaos

            fault_fn = chaos.replica_fault_fn(self.replica_id)
        opts = dict(engine_opts or {})
        if fault_fn is not None:
            opts["wedge_fn"] = fault_fn
        if engine_factory is None:
            engine_factory = serving_engine.ServingEngine
        # construction knobs kept so a quarantined replica can rebuild
        # its engine in place (_rebuild_engine)
        self._engine_factory = engine_factory
        self._input_mapping = input_mapping
        self._num_slots = num_slots
        self._chunk = chunk
        self._queue_depth = queue_depth
        self._opts = opts
        self.engine = self._build_engine()
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name="fleet-replica-%d" % self.replica_id,
        )

    def _build_engine(self):
        """Build this replica's engine, under its default-device
        context when pinned (decoder state — slot caches, weights —
        must live on the replica's device; the context is thread-local
        so construction and serving both enter it explicitly)."""
        def build():
            return self._engine_factory(
                self.predict, self._input_mapping, None,
                self._num_slots, chunk=self._chunk,
                queue_depth=self._queue_depth, policy="block",
                on_error="record", stats=self.stats, **self._opts
            )

        if self.device is not None:
            import jax

            with jax.default_device(self.device):
                return build()
        return build()

    def _rebuild_engine(self):
        """Rebuild the engine after a quarantined device error.  The
        predictor caches its SlotDecoder, so the rebuilt engine reuses
        the compiled programs; the decoder's slots reset (freeing the
        quarantined incarnation's pages), the submit/emit pairing
        restarts with the fresh engine's input numbering, and the
        counters a fresh engine zeroes in the shared stats dict are
        restored cumulatively (the fleet ledger invariant — rows
        summing to decode wall — spans incarnations)."""
        prior = {
            k: v for k, v in self.stats.items()
            if k in _CUMULATIVE_STATS
            and isinstance(v, (int, float)) and not isinstance(v, bool)
        }
        dec = getattr(self.engine, "decoder", None)
        reset = getattr(dec, "reset", None)
        if reset is not None:
            try:
                reset()
            except Exception:  # noqa: BLE001 - a broken decoder must
                logger.warning(  # not stop the quarantine rebuild
                    "replica %d: decoder reset failed during "
                    "quarantine rebuild", self.replica_id,
                    exc_info=True,
                )
        self._submitted = []
        self._emitted = 0
        self.engine = self._build_engine()
        for k, v in prior.items():
            cur = self.stats.get(k)
            if isinstance(cur, (int, float)) and not isinstance(
                    cur, bool):
                self.stats[k] = cur + v

    # -- lifecycle ------------------------------------------------------

    def start(self):
        if self._thread.ident is None:  # idempotent
            self._thread.start()
        return self

    def close(self):
        """Ask the worker to finish in-flight work and exit (the
        engine drains its slots, then the feed's STOP ends it)."""
        self._q.put(_STOP)

    def join(self, timeout=30.0):
        self._thread.join(timeout=timeout)
        return not self._thread.is_alive()

    @property
    def alive(self):
        return self.state != "dead"

    # -- routing surface ------------------------------------------------

    def dispatch(self, fid, row):
        """Hand one prepared engine row to this replica's feed."""
        self._q.put((int(fid), row))

    def load(self):
        """The router's placement signal: the engine's lock-light
        :meth:`~tensorflowonspark_tpu.serving_engine.ServingEngine.
        load` snapshot plus the rows parked in the replica feed that
        the engine has not pulled yet."""
        snap = self.engine.load()
        snap["queued"] += self._q.qsize()
        snap["replica"] = self.replica_id
        snap["state"] = self.state
        return snap

    def capacity(self):
        """Requests this replica can hold (slots + engine queue bound)
        — the router never assigns beyond it (spill-before-shed)."""
        return int(self.engine.num_slots) + int(self.engine.queue_depth)

    # -- the worker -----------------------------------------------------

    def _source(self):
        """The engine feed: rows as they arrive, ``None`` heartbeats
        between arrivals (never blocking a busy engine — decode chunks
        keep their cadence), blocking ``poll_sec`` at a time when the
        engine is idle so an idle replica still runs its lifecycle
        pass (pending hot-swaps apply)."""
        while True:
            try:
                # _slot_req read from the engine's own scheduler
                # thread (the source runs inside serve()) — safe
                if self.engine._slot_req:
                    item = self._q.get_nowait()
                else:
                    item = self._q.get(timeout=self._poll_sec)
            except queue_mod.Empty:
                yield None
                continue
            if item is _STOP:
                return
            fid, row = item
            self._submitted.append(fid)
            yield row

    def _run(self):
        while True:
            serve = self.engine.serve(self._source())
            if self.device is not None:
                import jax

                with jax.default_device(self.device):
                    status = self._drive(serve)
            else:
                status = self._drive(serve)
            if status != "quarantine":
                return
            # contained device error: rebuild the engine in place and
            # keep serving (probe traffic while routed around; full
            # traffic again once clean rounds re-admit the replica)
            try:
                self._rebuild_engine()
            except BaseException as e:  # noqa: BLE001 - rebuild
                self.state = "dead"   # failure IS a death
                self.error = e
                logger.warning(
                    "fleet replica %d: quarantine rebuild failed, "
                    "replica is dead: %s", self.replica_id, e,
                )
                self._completions.put((
                    "dead", self.replica_id,
                    {"finished": {}, "committed": {}, "queued": []},
                ))
                return

    def _drive(self, serve):
        try:
            for out in serve:
                fid = self._submitted[self._emitted]
                self._emitted += 1
                self._completions.put(
                    ("done", self.replica_id, fid, out)
                )
        except BaseException as e:  # noqa: BLE001 - death is a message
            if _is_device_error(e):
                # the host-side scheduler survived a device fault:
                # quarantine instead of dying — wreckage still posts
                # (the router re-dispatches it on a survivor), but the
                # replica will rebuild and serve probe traffic
                self.state = "routed_around"
                self.error = e
                logger.warning(
                    "fleet replica %d quarantined on device error: %s",
                    self.replica_id, e,
                )
                self._completions.put(
                    ("quarantine", self.replica_id, self._wreckage())
                )
                return "quarantine"
            self.state = "dead"
            self.error = e
            logger.warning(
                "fleet replica %d died: %s", self.replica_id, e
            )
            self._completions.put(
                ("dead", self.replica_id, self._wreckage())
            )
            return "dead"
        self._completions.put(("stopped", self.replica_id))
        return "stopped"

    def _wreckage(self):
        """Post-mortem accounting a dead replica owes the router
        (host-side scheduler state survives the death of the decode
        dispatch, like a driver outliving its device):

        - ``finished``: fleet id -> output row — requests the engine
          COMPLETED but had not emitted yet (held in its reorder
          buffer behind an earlier request); their tokens are real,
          the router delivers them as-is;
        - ``committed``: fleet id -> committed token list — requests
          in flight (or engine-queued after a prior requeue) at
          death; the router re-dispatches each from these tokens
          (greedy continuations are token-identical — the same
          invariant the engine's own watchdog recovery pins);
        - ``queued``: fleet ids never pulled from the feed (plus any
          the engine consumed but finished nowhere) — re-dispatched
          from scratch.
        """
        eng = self.engine
        finished = {}
        committed = {}
        queued = []
        accounted = set()
        for idx, row in eng._finished.items():
            if idx < len(self._submitted):
                finished[self._submitted[idx]] = row
                accounted.add(idx)
        for req in list(eng._slot_req.values()) + list(eng._pending):
            idx = req["idx"]
            if idx < len(self._submitted):
                committed[self._submitted[idx]] = [
                    t for t in (req["out"] or []) if isinstance(t, int)
                ]
                accounted.add(idx)
            # the chip/page-seconds this request accrued HERE flush to
            # its ledger row now (the engine's terminal points never
            # run for a dead replica's in-flight work): the spend was
            # real, and the surviving replica's row continues it —
            # per-request rows keep summing to the fleet's measured
            # decode wall time (ISSUE 14 acceptance)
            try:
                eng._ledger_settle(req, close=False)
            except Exception as e:  # noqa: BLE001
                # accounting must never break wreckage collection —
                # but a broken ledger should not stay invisible either
                # (surfaced by the ISSUE 15 tfoslint sweep)
                logger.debug(
                    "wreckage ledger flush failed for %r: %s",
                    req.get("rid"), e,
                )
        saw_stop = False
        while True:
            try:
                item = self._q.get_nowait()
            except queue_mod.Empty:
                break
            if item is _STOP:
                saw_stop = True
            else:
                queued.append(item[0])
        if saw_stop:
            # a close() raced the fault: keep the stop order so a
            # quarantined replica's rebuilt loop still honors it
            self._q.put(_STOP)
        # engine indices consumed but accounted nowhere (lost between
        # pull and admit) re-dispatch from scratch
        for idx in range(self._emitted, len(self._submitted)):
            if idx not in accounted:
                queued.append(self._submitted[idx])
        return {
            "finished": finished, "committed": committed,
            "queued": queued,
        }


class ReplicaSet(object):
    """N replicas of one generation predictor (see module docstring).

    Args:
      predict: a generation predictor (``serving_builder(mode=
        "generate")``).  Replica 0 serves it directly; replicas 1..N-1
        are built from ``predict.make_replica()`` (their own jitted
        programs + radix caches).  Pass ``predict_factory`` instead to
        control construction (tests with fake decoders).
      n: replica count.
      input_mapping: engine-level mapping (see :class:`Replica`).
      completions: the router's completion queue (built here when the
        set is used standalone).
      num_slots / chunk / queue_depth / engine_opts / poll_sec:
        per-replica engine knobs, forwarded to :class:`Replica`.

    On a host with several local devices replica ``i`` is pinned to
    ``jax.local_devices()[i % len]`` — one replica per chip.  Left to
    JAX's default placement every replica would land on device 0 and
    the other chips would idle.
    """

    def __init__(self, predict, n, input_mapping, *, completions=None,
                 predict_factory=None, num_slots=4, chunk=None,
                 queue_depth=None, engine_opts=None, poll_sec=0.02):
        import jax

        n = int(n)
        if n < 1:
            raise ValueError("need at least one replica, got %d" % n)
        self.completions = (
            completions if completions is not None else queue_mod.Queue()
        )
        devs = jax.local_devices()
        if len(devs) < 2:
            devs = None
        # construction knobs kept for spawn(): the autoscaling verb
        # (ISSUE 16) builds late replicas exactly like the initial set
        self._predict = predict
        self._predict_factory = predict_factory
        self._input_mapping = input_mapping
        self._num_slots = num_slots
        self._chunk = chunk
        self._queue_depth = queue_depth
        self._engine_opts = engine_opts
        self._devs = devs
        self._poll_sec = poll_sec
        predicts = []
        for i in range(n):
            if predict_factory is not None:
                predicts.append(predict_factory())
            elif i == 0:
                predicts.append(predict)
            else:
                predicts.append(self._replica_predict(n))
        self.replicas = [
            self._build(i, predicts[i]) for i in range(n)
        ]

    def _replica_predict(self, n):
        factory = getattr(self._predict, "make_replica", None)
        if factory is None:
            raise ValueError(
                "fleet serving with {0} replicas needs a "
                "predictor exposing make_replica() (transformer."
                "serving_builder generation predictors do) — "
                "each replica must own its decoder; this "
                "predictor has none".format(n)
            )
        return factory()

    def _build(self, rid, predict):
        devs = self._devs
        return Replica(
            rid, predict, self._input_mapping, self.completions,
            num_slots=self._num_slots, chunk=self._chunk,
            queue_depth=self._queue_depth,
            engine_opts=self._engine_opts,
            device=devs[rid % len(devs)] if devs else None,
            poll_sec=self._poll_sec,
        )

    def spawn(self):
        """Build, append, and START one more replica (its id is the
        next list index — the router shares this list, so the new
        replica is routable the moment this returns).  The autoscale /
        capacity-restore actuator (ISSUE 16); construction mirrors the
        initial set (``predict_factory`` when given, else
        ``predict.make_replica()``)."""
        rid = len(self.replicas)
        if self._predict_factory is not None:
            predict = self._predict_factory()
        else:
            predict = self._replica_predict(rid + 1)
        r = self._build(rid, predict)
        self.replicas.append(r)
        return r.start()

    def __len__(self):
        return len(self.replicas)

    def __iter__(self):
        return iter(self.replicas)

    def __getitem__(self, rid):
        return self.replicas[rid]

    def start(self):
        for r in self.replicas:
            r.start()
        return self

    def live(self):
        """Replicas currently accepting routed traffic."""
        return [r for r in self.replicas if r.state == "live"]

    def load(self):
        """Per-replica load snapshots, the ``/status`` fleet view."""
        return [r.load() for r in self.replicas]

    # per-replica lifecycle verbs (the router drives these; they are
    # also the operator surface)
    def drain(self, rid):
        """Stop routing to ``rid`` (rolling deploys quiesce through
        this); in-flight work finishes normally."""
        if self.replicas[rid].state != "dead":
            self.replicas[rid].state = "draining"

    def evict(self, rid):
        """Route around ``rid`` (a straggler working off its backlog
        still completes what it holds, and receives probe traffic)."""
        if self.replicas[rid].state != "dead":
            self.replicas[rid].state = "routed_around"

    def readmit(self, rid):
        """Return ``rid`` to full routing."""
        if self.replicas[rid].state != "dead":
            self.replicas[rid].state = "live"

    def close(self, join=True, timeout=30.0):
        for r in self.replicas:
            if r.alive:
                r.close()
        if join:
            for r in self.replicas:
                r.join(timeout=timeout)
