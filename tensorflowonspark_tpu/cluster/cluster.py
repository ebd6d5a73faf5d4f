"""High-level cluster API: turn an executor fleet into a TPU cluster.

Re-designed from the reference's ``TFCluster.py`` (reference:
tensorflowonspark/TFCluster.py).  ``run()`` launches the user's
``main_fun(args, ctx)`` on every executor, coordinates startup through the
rendezvous server, and returns a :class:`TPUCluster` handle with
``train`` / ``inference`` / ``shutdown`` — the same lifecycle contract as
the reference (reference: TFCluster.py:215-383, :63-115, :117-205).

Design changes for the TPU build:

- engine-agnostic: works over :class:`~tensorflowonspark_tpu.engine.Engine`
  (LocalEngine processes or a SparkContext adapter) instead of being
  welded to Spark RDD operations;
- shutdown is driver-direct: every node manager is reachable over TCP, so
  the driver posts end-of-feed sentinels and collects errors itself
  instead of scheduling a racy per-executor shutdown job (the reference's
  approach could strand a worker if two shutdown tasks landed on one
  executor, reference: TFCluster.py:174-176);
- the cluster handle knows the JAX coordination plan (coordinator address
  + process ranks), replacing TF_CONFIG.
"""

import itertools
import logging
import os
import threading
import time
import uuid

from tensorflowonspark_tpu.cluster import manager, node, reservation
from tensorflowonspark_tpu.cluster.marker import PartitionStart

logger = logging.getLogger(__name__)


class DeadExecutorError(RuntimeError):
    """A cluster node was declared dead by the heartbeat liveness plane.

    Raised from the driver's feed loop within seconds of the death (the
    reference's only signal was the 600s feed timeout).  The message
    names the executor id, host, and diagnosis; ``executor_id`` carries
    the id programmatically."""

    def __init__(self, message, executor_id=None):
        super(DeadExecutorError, self).__init__(message)
        self.executor_id = executor_id


class ClusterMonitor(object):
    """Driver-side liveness watcher over the rendezvous server's
    heartbeat registry.

    Polls ``server.liveness`` (in-process — the server lives on the
    driver) every half heartbeat-interval:

    - ``elastic=False``: the first dead executor becomes a permanent
      failure; :meth:`check` raises :class:`DeadExecutorError` naming
      the node, enriched with the node's error-queue traceback when one
      is reachable.
    - ``elastic=True``: a death opens a recovery window
      (``recovery_timeout`` seconds).  A generation bump or resumed
      beats close it (counted in ``restart_events`` — the feed loop's
      cue to requeue uncommitted partitions); an executor still dead
      past the window becomes a permanent failure.
    """

    def __init__(self, server, cluster_info, elastic=False,
                 recovery_timeout=120.0, error_peek=None):
        self.server = server
        self.cluster_info = cluster_info
        self.elastic = bool(elastic)
        self.recovery_timeout = float(recovery_timeout)
        self.error = None
        self.dead_executor_id = None
        #: total per-executor generation bumps observed (monotonic)
        self.restart_events = 0
        #: straggler hints pushed by the fleet health plane
        #: (telemetry/health.py) — newest per executor; ops tooling and
        #: the supervisor surface read these alongside the error state
        self.health_hints = {}
        self._by_id = {n["executor_id"]: n for n in cluster_info}
        self._first_dead = {}
        self._known_gen = {}
        self._error_peek = error_peek  # fn(node_meta) -> str | None
        #: silence is not judged before this monotonic time (see _tick)
        self._blind_until = 0.0
        self._stop = threading.Event()
        self._thread = None

    def start(self):
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="cluster-monitor"
        )
        self._thread.start()
        return self

    @property
    def interval(self):
        return self.server.liveness.interval

    def _run(self):
        period = self.interval / 2.0
        last = time.monotonic()
        while not self._stop.wait(period):
            now = time.monotonic()
            self._tick(now - last - period)
            last = now
            try:
                self._poll()
            except Exception:  # noqa: BLE001 - monitor must not die quiet
                logger.warning("cluster monitor poll failed", exc_info=True)
            if self.error is not None:
                return

    def _tick(self, overslept):
        """Account for a stall of THIS process.  When the monitor itself
        lost more than a heartbeat interval — the whole host frozen
        (initialising a TPU stalls every process of the VM for ~6 s,
        measured on a v5e host, CHANGES.md PR 21), a long GC pause, a
        suspended laptop — the beats of that span sit unread in the
        server's socket, or were never sent by nodes frozen alongside.
        Silence seen through a stall is not evidence of death: judge it
        again only after a full deadline of running normally."""
        if overslept > self.interval:
            self._blind_until = (
                time.monotonic() + self.server.liveness.deadline
            )
            logger.warning(
                "cluster monitor was stalled for %.1fs; not judging "
                "heartbeat silence for the next %.1fs",
                overslept, self.server.liveness.deadline,
            )

    def _poll(self):
        snapshot = self.server.liveness.snapshot()
        for eid_s, rec in snapshot.items():
            eid = int(eid_s)
            known = self._known_gen.get(eid, 0)
            if rec["generation"] > known:
                self.restart_events += rec["generation"] - known
                self._known_gen[eid] = rec["generation"]
                logger.info(
                    "monitor: executor %d reborn at generation %d",
                    eid, rec["generation"],
                )
                # driver-side restart marker: chaos/ops tooling reads
                # restarts out of the trace alongside watchdog/shed
                # events (tests/test_telemetry.py)
                from tensorflowonspark_tpu import telemetry

                telemetry.get_registry().counter(
                    "cluster.restart_events"
                ).inc(rec["generation"] - known)
                telemetry.get_tracer().mark(
                    "executor_restart", trace="executor%d" % eid,
                    severity="warn",
                    executor_id=eid, generation=rec["generation"],
                )
        dead = self.server.liveness.dead()
        now = time.monotonic()
        for eid in list(self._first_dead):
            if eid not in dead:
                logger.info("monitor: executor %d recovered", eid)
                self._first_dead.pop(eid)
        for eid, diag in dead.items():
            if diag.get("silent") and now < self._blind_until:
                continue
            if not self.elastic:
                self._fail(eid, diag)
                return
            first = self._first_dead.setdefault(eid, now)
            if now - first > self.recovery_timeout:
                diag = dict(
                    diag,
                    reason="{0}; no recovery within the {1:.0f}s elastic "
                    "window".format(diag["reason"], self.recovery_timeout),
                )
                self._fail(eid, diag)
                return

    def _fail(self, eid, diag):
        node_meta = self._by_id.get(eid, {})
        msg = (
            "executor {0} (host {1}, {2}:{3}) declared dead: {4} "
            "[last heartbeat {5:.1f}s ago, generation {6}]".format(
                eid,
                diag.get("host") or node_meta.get("host", "?"),
                node_meta.get("job_name", "?"),
                node_meta.get("task_index", "?"),
                diag["reason"],
                diag["age"],
                diag.get("generation", 0),
            )
        )
        # enrich with the node's own traceback when reachable — the
        # user should see WHY it died, not just THAT it died
        if self._error_peek is not None and node_meta:
            try:
                err = self._error_peek(node_meta)
            except Exception:  # noqa: BLE001 - node likely unreachable
                err = None
            if err:
                msg += "\nlast error from executor {0}:\n{1}".format(eid, err)
        logger.error("cluster monitor: %s", msg)
        # page-severity journal event: the forensics plane's
        # dead-executor trigger (the driver-side flight recorder dumps
        # on it, telemetry/blackbox.py)
        from tensorflowonspark_tpu import telemetry

        telemetry.get_tracer().mark(
            "executor_dead", trace="executor%d" % eid, severity="page",
            executor_id=eid, reason=diag["reason"],
            host=diag.get("host") or node_meta.get("host", "?"),
        )
        self.error = msg
        self.dead_executor_id = eid

    def check(self):
        """Raise :class:`DeadExecutorError` if a permanent failure was
        detected; no-op otherwise.  Feed loops call this every poll."""
        if self.error is not None:
            raise DeadExecutorError(self.error, self.dead_executor_id)

    def note_straggler(self, hint):
        """Record a health-plane straggler hint against this monitor —
        advisory (nothing is killed): the fleet keeps running while
        the flagged node is profiled and the operator decides."""
        self.health_hints[hint["executor"]] = dict(hint)
        logger.warning(
            "monitor: health plane flagged executor %s as a straggler "
            "(dominant phase %r, +%.3fs/step vs the fleet)",
            hint.get("executor"), hint.get("phase"),
            hint.get("excess_sec", 0.0),
        )

    def clear_straggler(self, executor_id):
        """Drop a recovered executor's straggler hint (the health
        plane's ``on_straggler_cleared`` mirror of
        :meth:`note_straggler`)."""
        if self.health_hints.pop(int(executor_id), None) is not None:
            logger.info(
                "monitor: health plane cleared the straggler flag on "
                "executor %s", executor_id,
            )

    def metrics(self):
        """Per-executor telemetry snapshots merged with liveness (the
        in-process half of ``TFCluster.metrics()`` — usable on a bare
        monitor too).  Returns ``{executor_id: {"metrics": snapshot?,
        "metrics_age": secs?, "heartbeat_age": secs?, "generation",
        "compute_alive", "host"}}``."""
        store = self.server.metrics.snapshot()
        liveness = self.server.liveness.snapshot()
        clocks = self.server.clocks.snapshot()
        per = {}
        for eid_s in set(store) | set(liveness):
            rec = {}
            s = store.get(eid_s)
            if s is not None:
                rec["metrics"] = s["metrics"]
                rec["metrics_age"] = s["age"]
            lv = liveness.get(eid_s)
            if lv is not None:
                rec["heartbeat_age"] = lv["age"]
                rec["generation"] = lv["generation"]
                rec["compute_alive"] = lv["compute_alive"]
                rec["host"] = lv["host"]
            clk = clocks.get(eid_s)
            if clk is not None:
                # seconds to ADD to this executor's wall timestamps to
                # land them on the driver clock (reservation.ClockSync)
                rec["clock_offset"] = clk["offset"]
                rec["clock_rtt"] = clk["rtt"]
            per[int(eid_s)] = rec
        return per

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)


class InputMode(object):
    """Modes for feeding data to the compute processes
    (reference: TFCluster.py:43-46)."""

    #: User fn reads its own data (e.g. TFRecords/arrays from GCS/HDFS).
    #: Name kept for API parity with the reference.
    TENSORFLOW = 0
    #: The engine (Spark or local) pushes partitions of data to the nodes.
    SPARK = 1


class _HandleStatus(object):
    """Adapter exposing a JobHandle's failure as the status-dict interface
    ``Server.await_reservations`` polls (reference kept a global
    ``tf_status`` dict, TFCluster.py:40,178-183)."""

    def __init__(self, handle):
        self._handle = handle

    def get(self, key, default=None):
        if key == "error":
            return self._handle.error
        return default

    def __getitem__(self, key):
        return self.get(key)


class TPUCluster(object):
    """Handle to a running cluster (reference: TFCluster.py:48-212)."""

    def __init__(
        self,
        engine,
        cluster_meta,
        cluster_info,
        server,
        job_handle,
        input_mode,
        queues,
        owns_engine=False,
        driver_ps=(),
        monitor=None,
    ):
        self.engine = engine
        self.cluster_meta = cluster_meta
        self.cluster_info = cluster_info
        self.server = server
        self.job_handle = job_handle
        self.input_mode = input_mode
        self.queues = queues
        self._owns_engine = owns_engine
        self._driver_ps = list(driver_ps)
        self.cluster_id = cluster_meta["id"]
        self.elastic = bool(cluster_meta.get("elastic", False))
        #: liveness watcher (started by run(); None in bare-handle tests)
        self.monitor = monitor
        #: fleet health plane (started by start_health_plane(); stopped
        #: by shutdown())
        self.health = None
        #: remediation engine (started by start_remediation())
        self.remediation = None
        self._profile_seq = itertools.count(1)

    # -- data plane ----------------------------------------------------

    def train(self, data, num_epochs=1, feed_timeout=600, qname="input"):
        """Feed a dataset to the cluster for training
        (reference: TFCluster.py:63-94).

        Args:
          data: an engine-native dataset (a Spark RDD/DataFrame for
            :class:`~tensorflowonspark_tpu.engine.SparkEngine` — fed in
            place via ``foreachPartition``, rows never transit the
            driver, reference: TFCluster.py:90-94), OR a list of
            partitions where each partition is a row list or a zero-arg
            callable returning rows (callables are generated on the
            executors — the lazy large-dataset path for LocalEngine).
          num_epochs: epochs are fed by re-running the feed job — no
            driver-side copies (the reference built one
            ``sc.union([rdd] * num_epochs)`` job, TFCluster.py:90-93;
            same data motion, per-epoch jobs here).
        """
        assert self.input_mode == InputMode.SPARK, (
            "train() requires InputMode.SPARK"
        )
        assert num_epochs >= 1
        feed_fn = node.train(
            self.cluster_info, self.cluster_meta, feed_timeout, qname
        )
        if self.engine.is_native_dataset(data):
            # native datasets are fed in place by the engine; the
            # partition-requeue path needs driver-held partitions, so
            # elastic recovery here relies on the engine's own task
            # retries + checkpoint resume (documented limitation)
            logger.info("feeding native dataset x %d epochs", num_epochs)
            for _ in range(num_epochs):
                self.engine.run_data_job(feed_fn, data)
                self._check_monitor()
            return
        # normalize once so generators of partitions and one-shot
        # iterator partitions survive multi-epoch re-feeding (callables
        # stay lazy — they regenerate rows on the executor every epoch)
        data = [p if callable(p) else list(p) for p in data]
        logger.info(
            "feeding %d partitions x %d epochs", len(data), num_epochs
        )
        for epoch in range(num_epochs):
            if self.elastic:
                self._feed_epoch_elastic(feed_fn, data, epoch, feed_timeout)
            else:
                self._run_feed_monitored(feed_fn, data)

    # -- fault-tolerant feeding ---------------------------------------

    def _check_monitor(self):
        if self.monitor is not None:
            self.monitor.check()

    def _run_feed_monitored(self, feed_fn, partitions):
        """Run one feed job while watching the liveness plane: a dead
        executor fails the feed in seconds (with a diagnosis naming the
        node) instead of wedging until feed_timeout."""
        if self.monitor is None:
            self.engine.run_job(feed_fn, partitions)
            return
        handle = self.engine.run_job_async(feed_fn, partitions)
        while not handle.done():
            self.monitor.check()
            time.sleep(min(0.2, self.monitor.interval / 2.0))
        handle.wait(timeout=0)

    def _feed_epoch_elastic(self, feed_fn, partitions, epoch, feed_timeout):
        """Feed one epoch with partition requeue: every partition leads
        with a PartitionStart marker feeding the per-node ledger; after
        a restart event, partitions not committed by a checkpoint are
        fed again (at-least-once — see docs/fault_tolerance.md)."""
        pending = {
            "e{0}p{1}".format(epoch, i): p
            for i, p in enumerate(partitions)
        }
        seen_restarts = (
            self.monitor.restart_events if self.monitor is not None else 0
        )
        max_rounds = 1 + int(self.cluster_meta.get("max_restarts", 3))
        for round_no in range(max_rounds):
            if round_no:
                logger.warning(
                    "elastic requeue round %d: re-feeding %d "
                    "uncommitted partition(s): %s",
                    round_no, len(pending), sorted(pending),
                )
            wrapped = [
                _with_partition_marker(pid, p)
                for pid, p in sorted(pending.items())
            ]
            handle = self.engine.run_job_async(feed_fn, wrapped)
            while not handle.done():
                self._check_monitor()
                time.sleep(0.2)
            try:
                handle.wait(timeout=0)
            except RuntimeError:
                # a feed task died mid-restart (e.g. it saw the dead
                # incarnation's error queue); if a rebirth explains it,
                # the requeue below re-feeds — otherwise it's real
                if self.monitor is None:
                    raise
                if not self._await_restart_signal(seen_restarts):
                    raise
                logger.warning(
                    "feed job failed during an elastic restart; "
                    "requeuing uncommitted partitions", exc_info=True,
                )
            committed = self._ledger_committed()
            pending = {
                pid: p for pid, p in pending.items() if pid not in committed
            }
            if not pending:
                return
            # a rebirth releases blocked feeders BEFORE it re-registers
            # under the new generation, so the feed round can complete
            # a beat ahead of the restart signal — settle briefly before
            # concluding nothing happened (concluding wrongly would skip
            # the requeue and silently drop the reset partitions)
            if not self._await_restart_signal(seen_restarts):
                logger.info(
                    "epoch %d: %d partition(s) delivered but not yet "
                    "checkpoint-committed (no restart occurred)",
                    epoch, len(pending),
                )
                return
            seen_restarts = self.monitor.restart_events
        logger.warning(
            "elastic requeue budget exhausted with %d partition(s) "
            "still uncommitted: %s", len(pending), sorted(pending),
        )

    def _await_restart_signal(self, seen_restarts, window=None):
        """True if a restart event beyond ``seen_restarts`` surfaces
        within the settle window; re-raises via check() if the monitor
        declared a permanent failure meanwhile."""
        if self.monitor is None:
            return False
        window = (
            max(2.0, 4.0 * self.monitor.interval) if window is None else window
        )
        deadline = time.monotonic() + window
        while True:
            self.monitor.check()
            if self.monitor.restart_events > seen_restarts:
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.1)

    def _ledger_committed(self):
        """Union of checkpoint-committed partition ids across workers."""
        committed = set()
        for n in self.cluster_info:
            if n["job_name"] not in ("worker", "chief", "master"):
                continue
            try:
                m = self._connect(n)
                committed.update(m.ledger("committed")._getvalue())
            except Exception:  # noqa: BLE001 - node mid-restart: its
                logger.warning(  # partitions simply stay pending
                    "unable to read partition ledger of executor %d",
                    n["executor_id"], exc_info=True,
                )
        return committed

    def train_stream(self, batches, feed_timeout=600, qname="input"):
        """Feed an unbounded stream of partition micro-batches.

        The DStream role (reference: TFCluster.py:83-85 ``foreachRDD``
        + examples/mnist/estimator/mnist_spark_streaming.py): each item
        of ``batches`` is a list of partitions fed as one job.  The
        stream ends when the iterator is exhausted or when someone posts
        STOP on the reservation server (reference:
        examples/utils/stop_streaming.py; here
        ``examples/utils/stop_cluster.py`` or
        ``reservation.Client(addr).request_stop()``).
        """
        assert self.input_mode == InputMode.SPARK, (
            "train_stream() requires InputMode.SPARK"
        )
        fed = 0
        feed_fn = node.train(
            self.cluster_info, self.cluster_meta, feed_timeout, qname
        )
        for partitions in batches:
            if self.server.stop_requested:
                logger.info(
                    "stop requested after %d stream batches; ending feed", fed
                )
                break
            if self.engine.is_native_dataset(partitions):
                # a stream of RDDs — the foreachRDD contract
                # (reference: TFCluster.py:83-85)
                self.engine.run_data_job(feed_fn, partitions)
            else:
                self.engine.run_job(
                    feed_fn,
                    [p if callable(p) else list(p) for p in partitions],
                )
            fed += 1
        logger.info("stream feed complete after %d batches", fed)
        return fed

    def train_dstream(self, dstream, feed_timeout=600, qname="input"):
        """Hook a Spark DStream: each micro-batch RDD is fed in place as
        it arrives (reference: TFCluster.py:83-85 ``foreachRDD`` +
        examples/mnist/estimator/mnist_spark_streaming.py).  Call
        ``ssc.start()`` afterwards; stop feeding with
        ``reservation.Client(addr).request_stop()`` (reference:
        examples/utils/stop_streaming.py) or by stopping the context.

        Test-coverage note: upstream pyspark 4 removed DStreams
        entirely, so REAL-DStream coverage only executes on pyspark<4
        (tests/test_spark_real.py gates on it); the ``foreachRDD``
        contract itself is covered everywhere via duck-typed streams
        and DataFrame micro-batches.  On pyspark>=4 prefer
        :meth:`train_stream` (an iterator of micro-batches) or
        Structured Streaming's ``foreachBatch`` pointed at
        ``train_stream``'s feed path.
        """
        assert self.input_mode == InputMode.SPARK, (
            "train_dstream() requires InputMode.SPARK"
        )
        feed_fn = node.train(
            self.cluster_info, self.cluster_meta, feed_timeout, qname
        )
        server = self.server
        engine = self.engine

        def _each_rdd(rdd):
            if server.stop_requested:
                logger.info("stop requested; skipping stream micro-batch")
                return
            if engine.is_native_dataset(rdd):
                # through the engine so DataFrame micro-batches
                # normalize and engine-side instrumentation applies
                engine.run_data_job(feed_fn, rdd)
            else:
                # duck-typed RDD on an engine without a native dataset
                # type (e.g. LocalEngine tests)
                rdd.foreachPartition(feed_fn)

        dstream.foreachRDD(_each_rdd)

    def inference(self, data, feed_timeout=600, qname="input", lazy=False):
        """Feed data for inference and return results
        (reference: TFCluster.py:96-115).

        Args:
          data: engine-native dataset or partition list (see
            :meth:`train`).
          lazy: return results without materializing them on the driver:
            a lazy result RDD for a native Spark dataset (the
            reference's exact contract — ``mapPartitions``, evaluated
            when acted on) or a per-partition generator for
            LocalEngine.  Default eager: a flat result list.
        """
        assert self.input_mode == InputMode.SPARK, (
            "inference() requires InputMode.SPARK"
        )
        feed_fn = node.inference(
            self.cluster_info, self.cluster_meta, feed_timeout, qname
        )
        if self.engine.is_native_dataset(data):
            result = self.engine.map_partitions_native(feed_fn, data)
            if lazy:
                return result
            return result.collect()
        data = [p if callable(p) else list(p) for p in data]
        if lazy:
            return self.engine.run_job_lazy(feed_fn, data)
        return self.engine.run_job(feed_fn, data, collect=True)

    # -- lifecycle -----------------------------------------------------

    def shutdown(self, grace_secs=0, timeout=259200):
        """Stop the cluster and propagate any compute errors
        (reference: TFCluster.py:117-205; see module docstring for the
        driver-direct redesign).

        Args:
          grace_secs: seconds to wait after end-of-feed so chiefs can
            finish exporting models (reference: TFCluster.py:125).
          timeout: overall watchdog, default 3 days like the reference's
            SIGALRM guard (reference: TFCluster.py:136-144).
        """
        deadline = time.monotonic() + timeout
        if self.remediation is not None:
            self.remediation.stop()
            self.remediation = None
        if self.health is not None:
            self.health.stop()
            from tensorflowonspark_tpu.telemetry import health as _health

            _health.unregister_status_provider("ledger")
            self.health = None
        if self.monitor is not None:
            self.monitor.stop()
        workers = [
            n
            for n in self.cluster_info
            if n["job_name"] in ("worker", "chief", "master")
        ]
        services = [
            n for n in self.cluster_info if n["job_name"] in ("ps", "evaluator")
        ]

        if self.input_mode == InputMode.TENSORFLOW:
            # Workers run user fns in the foreground and set their state to
            # 'stopped' on return; poll for that (the reference polled the
            # Spark statusTracker for remaining tasks, TFCluster.py:154-169).
            self._await_worker_states(workers, deadline)
        else:
            # Post the end-of-feed sentinel on every *input* queue of every
            # worker (reference did this in a per-executor job,
            # TFSparkNode.py:595-605).  The error queue must never carry a
            # sentinel — a None at its head would mask a late failure from
            # _peek_error — and the output queue's consumers are the feed
            # tasks, which have already drained their exact result counts.
            feed_queues = [
                q for q in self.queues if q not in ("error", "output")
            ]
            for w in workers:
                m = self._connect(w)
                for qname in feed_queues:
                    try:
                        m.get_queue(qname).put(None, block=True)
                    except Exception:  # noqa: BLE001 - role may lack queue
                        logger.warning(
                            "unable to post end-of-feed sentinel on "
                            "queue %r of executor %d",
                            qname, w["executor_id"], exc_info=True,
                        )
            # Wait for each worker's compute process to report completion
            # ('compute_state' set by _compute_process_main) instead of the
            # reference's blind grace_secs sleep (TFCluster.py:125):
            # post-feed work like the chief's serving export always
            # finishes, and finished clusters shut down immediately.  The
            # wait window is max(grace_secs, 60s) — a wedged compute
            # process delays shutdown by at most that; raise grace_secs
            # above 60 for exports that legitimately take longer.
            self._await_compute_done(
                workers, min(deadline, time.monotonic() + max(grace_secs, 60))
            )

        # error check: peek-and-requeue per node so later checks still see
        # the failure (reference: TFSparkNode.py:612-618, TFCluster.py:178-183)
        errors = []
        for n in self.cluster_info:
            err = self._peek_error(n)
            if err:
                errors.append((n["executor_id"], err))

        # stop tensorboard (best effort, same-host signal)
        self._stop_tensorboard()

        # release ps/evaluator control loops (reference: TFCluster.py:186-194)
        for s in services:
            try:
                m = self._connect(s)
                m.get_queue("control").put(None, block=True)
            except Exception:  # noqa: BLE001 - node may be gone already
                logger.warning(
                    "unable to post shutdown to %s:%d (executor %d)",
                    s["job_name"],
                    s["task_index"],
                    s["executor_id"],
                    exc_info=True,
                )

        # the start job completes once every foreground task returns
        if self.job_handle is not None:
            remaining = max(5.0, deadline - time.monotonic())
            try:
                self.job_handle.wait(timeout=remaining)
            except TimeoutError:
                logger.warning("cluster start job did not complete in time")
            except RuntimeError as e:
                errors.append(("start-job", str(e)))

        for w in workers:
            try:
                self._connect(w).set("state", "stopped")
            except Exception:  # noqa: BLE001 - node gone: state moot, but
                logger.warning(  # the diagnosis must not vanish with it
                    "unable to mark executor %d stopped during shutdown",
                    w["executor_id"], exc_info=True,
                )

        for shard in self._driver_ps:
            shard.stop()
        self.server.stop()
        if self._owns_engine:
            self.engine.stop()
        if errors:
            raise RuntimeError(
                "cluster shutdown detected failures:\n"
                + "\n".join(
                    "executor {0}: {1}".format(eid, err) for eid, err in errors
                )
            )
        logger.info("cluster shutdown complete")

    def _await_compute_done(self, workers, deadline):
        pending = {w["executor_id"]: w for w in workers}
        conns = {}  # one manager connection per worker, reused across polls
        while pending:
            for eid, w in list(pending.items()):
                try:
                    m = conns.get(eid)
                    if m is None:
                        m = conns[eid] = self._connect(w)
                    state = m.get("compute_state")._getvalue()
                except Exception:  # noqa: BLE001 - transient: reconnect and
                    conns.pop(eid, None)  # retry until the deadline
                    continue
                if state in ("finished", "failed"):
                    pending.pop(eid)
            if not pending:
                return
            if time.monotonic() > deadline:
                logger.warning(
                    "compute processes on executors %s did not report "
                    "completion within the grace window; proceeding with "
                    "shutdown",
                    sorted(pending),
                )
                return
            time.sleep(0.2)

    def _await_worker_states(self, workers, deadline):
        pending = {w["executor_id"] for w in workers}
        by_id = {w["executor_id"]: w for w in workers}
        while pending:
            for eid in list(pending):
                try:
                    m = self._connect(by_id[eid])
                    if str(m.get("state")._getvalue()) == "stopped":
                        pending.discard(eid)
                # tfoslint: disable=TFOS005(liveness probe: a node mid-restart answers on a later pass; the deadline below bounds the loop)
                except Exception:  # noqa: BLE001 - node may be mid-restart
                    pass
            if not pending:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    "timed out waiting for workers {0} to finish".format(
                        sorted(pending)
                    )
                )
            time.sleep(1)

    def _connect(self, node_meta):
        return manager.connect(
            tuple(node_meta["addr"]), bytes.fromhex(node_meta["authkey"])
        )

    def _peek_error(self, node_meta):
        import queue as _queue_mod

        try:
            m = self._connect(node_meta)
            q = m.get_queue("error")
            err = q.get(block=False)
            q.task_done()
            q.put(err)
            return err
        except _queue_mod.Empty:
            return None
        except Exception:  # noqa: BLE001 - unreachable node: no error to
            logger.warning(  # report, but say WHICH node was unreachable
                "unable to check error queue of executor %d (%s:%d)",
                node_meta["executor_id"],
                node_meta["job_name"],
                node_meta["task_index"],
                exc_info=True,
            )
            return None

    def _stop_tensorboard(self):
        import os
        import signal

        from tensorflowonspark_tpu.utils.net import get_ip_address

        me = get_ip_address()
        for n in self.cluster_info:
            if n.get("tb_pid"):
                if n["host"] == me:
                    try:
                        os.kill(n["tb_pid"], signal.SIGTERM)
                    except OSError:
                        pass
                else:
                    logger.info(
                        "tensorboard on %s pid %d exits with its executor",
                        n["host"],
                        n["tb_pid"],
                    )

    def metrics(self, include_ledger=True):
        """Driver-side fleet telemetry view (docs/observability.md).

        Pulls every executor's newest registry snapshot out of the
        reservation server's :class:`~tensorflowonspark_tpu.cluster.reservation.MetricsStore`
        (snapshots arrive piggybacked on heartbeats), merges in the
        liveness fields (heartbeat age, generation, compute_alive) and
        — with ``include_ledger`` — each worker's partition-ledger
        committed/pending counts, then folds everything into ONE fleet
        snapshot via
        :func:`~tensorflowonspark_tpu.telemetry.aggregate.merge_snapshots`.

        Returns ``{"executors": {executor_id: {...}}, "fleet": merged
        snapshot, "restart_events": int, "generation": int}``.  Works
        in-process against the driver-resident server; a remote
        observer gets the same data through
        ``reservation.Client(addr).get_metrics()``.
        """
        from tensorflowonspark_tpu.telemetry import aggregate

        per = (
            self.monitor.metrics() if self.monitor is not None
            else ClusterMonitor(
                self.server, self.cluster_info
            ).metrics()
        )
        if include_ledger:
            for n in self.cluster_info:
                if n["job_name"] not in ("worker", "chief", "master"):
                    continue
                eid = n["executor_id"]
                try:
                    m = self._connect(n)
                    rec = per.setdefault(eid, {})
                    rec["ledger"] = {
                        "committed": len(
                            m.ledger("committed")._getvalue()
                        ),
                        "pending": len(m.ledger("pending")._getvalue()),
                    }
                # tfoslint: disable=TFOS005(metrics snapshot stays partial for a node mid-restart; nothing to recover here)
                except Exception:  # noqa: BLE001 - node mid-restart /
                    pass  # gone: its snapshot simply lacks the ledger
        view = aggregate.fleet_view(per)
        view["restart_events"] = (
            self.monitor.restart_events if self.monitor is not None else 0
        )
        view["generation"] = self.server.generation
        # the SLO engine's bounded alert HISTORY (fired + resolved,
        # ISSUE 11 satellite): what paged during a window that already
        # cleared, visible without the HTTP surface
        if self.health is not None and self.health.slo is not None:
            view["fleet"]["alert_history"] = (
                self.health.slo.alert_history()
            )
        return view

    def journal(self, limit=None):
        """The fleet's typed-event record (ISSUE 11): every executor's
        journal events shipped over the heartbeat piggyback into the
        reservation server's EventStore, merged time-ordered, plus the
        per-executor clock offsets that align them onto the driver
        clock.  Returns ``{"events": [event dicts], "clocks":
        {executor: {"offset", "rtt"}}}`` — exactly what ``python -m
        tensorflowonspark_tpu.forensics explain`` consumes (pass
        ``json.dump`` output of this, or a flight-recorder bundle)."""
        return {
            "events": self.server.events.snapshot(limit=limit),
            "clocks": self.server.clocks.snapshot(),
        }

    def collect_dumps(self, dest=None):
        """Collect every node's flight-recorder dump index (ISSUE 11):
        reads each worker's ``blackbox_dumps`` kv (published by the
        recorder on every dump — telemetry/blackbox.py) through the
        existing manager connections.  Returns ``{executor_id: [dump
        record dicts]}``; with ``dest``, bundle files reachable on
        this host are also copied there (LocalEngine clusters share
        the filesystem; remote fleets ship paths for out-of-band
        collection)."""
        out = {}
        for n in self.cluster_info:
            try:
                m = self._connect(n)
                recs = m.get("blackbox_dumps")
                if hasattr(recs, "_getvalue"):
                    recs = recs._getvalue()
            except Exception:  # noqa: BLE001 - node mid-restart/gone
                continue
            if not isinstance(recs, list) or not recs:
                continue
            out[n["executor_id"]] = recs
        if dest is not None:
            import shutil

            os.makedirs(dest, exist_ok=True)
            for eid, recs in out.items():
                for rec in recs:
                    path = rec.get("path")
                    if path and os.path.exists(path):
                        try:
                            shutil.copy2(path, dest)
                        except OSError:
                            logger.warning(
                                "unable to copy dump %s", path,
                                exc_info=True,
                            )
        return out

    # -- fleet health plane (ISSUE 10; docs/observability.md) ----------

    def start_health_plane(self, port=None, slo=None, interval=None,
                           window=None, straggler=True,
                           straggler_opts=None, profile_steps=20,
                           profile_dir=None):
        """Start the standing fleet health plane over this cluster.

        Scrapes the monitor's per-executor telemetry (the heartbeat-
        piggyback path — no new connections to the nodes) every
        ``interval`` seconds into windowed time series, evaluates the
        ``slo`` rules (anything
        :func:`~tensorflowonspark_tpu.telemetry.health.load_rules`
        accepts), auto-diagnoses stragglers (MAD outliers over
        per-executor step/feed/wire series, attributed to their
        dominant phase), and — when a straggler is flagged — fires the
        PR 7 profiler hook on THAT node only (a ``profile_request`` kv
        its NodePublisher picks up; ``profile_dir`` defaults to
        ``/tmp/tfos_health_profiles/<cluster_id>``).

        ``port`` (0 = ephemeral) additionally starts the HTTP
        exposition surface: ``/metrics`` (OpenMetrics), ``/healthz``
        (flips 503 on a dead executor), ``/status`` (fleet JSON).
        Returns the :class:`~tensorflowonspark_tpu.telemetry.health.
        HealthPlane`; :meth:`shutdown` stops it.
        """
        from tensorflowonspark_tpu.telemetry import health as _health

        if self.health is not None:
            return self.health
        monitor = self.monitor or ClusterMonitor(
            self.server, self.cluster_info
        )
        if profile_dir is None:
            import tempfile

            profile_dir = "{0}/tfos_health_profiles/{1}".format(
                tempfile.gettempdir(), self.cluster_id
            )

        def on_straggler(hint):
            monitor.note_straggler(hint)
            self._request_profile(
                hint["executor"], steps=profile_steps,
                log_dir=profile_dir, hint=hint,
            )

        def on_straggler_cleared(eid):
            monitor.clear_straggler(eid)
            self._clear_health_hint(eid)

        plane = _health.HealthPlane(
            monitor.metrics,
            interval=interval,
            window=window,
            slo=slo,
            straggler=straggler,
            straggler_opts=straggler_opts,
            on_straggler=on_straggler,
            on_straggler_cleared=on_straggler_cleared,
            liveness_fn=self.server.liveness.health,
            journal_fn=self.journal,
        )
        _health.register_status_provider("ledger", self._ledger_status)
        plane.start()
        if port is not None:
            plane.serve(port=port)
        self.health = plane
        return plane

    def _ledger_status(self):
        """Per-worker committed/pending partition counts for
        ``/status`` (the same numbers ``metrics(include_ledger=True)``
        merges in)."""
        out = {}
        for n in self.cluster_info:
            if n["job_name"] not in ("worker", "chief", "master"):
                continue
            try:
                m = self._connect(n)
                out[str(n["executor_id"])] = {
                    "committed": len(m.ledger("committed")._getvalue()),
                    "pending": len(m.ledger("pending")._getvalue()),
                }
            except Exception:  # noqa: BLE001 - node mid-restart
                out[str(n["executor_id"])] = {"unreachable": True}
        return out

    def _request_profile(self, executor_id, steps=20, log_dir=None,
                         hint=None):
        """Ask ONE node to capture a device profile: write a sequenced
        ``profile_request`` into its manager kv — its NodePublisher
        (telemetry/aggregate.py) starts the PR 7
        ``tensorboard.start_profile`` hook and acks into
        ``profile_state``.  Also records the straggler hint in the
        node's kv so its logs/heartbeats can surface it."""
        node_meta = next(
            (n for n in self.cluster_info
             if n["executor_id"] == int(executor_id)), None,
        )
        if node_meta is None:
            logger.warning(
                "profile request for unknown executor %s", executor_id
            )
            return None
        req = {
            "seq": next(self._profile_seq),
            "log_dir": log_dir,
            "steps": int(steps) if steps else None,
        }
        try:
            m = self._connect(node_meta)
            m.set("profile_request", req)
            if hint is not None:
                m.set("health_hint", dict(hint))
        except Exception:  # noqa: BLE001 - node mid-restart: the hint
            logger.warning(  # stands, the capture is lost
                "unable to deliver profile request to executor %s",
                executor_id, exc_info=True,
            )
            return None
        logger.info(
            "profile request %d delivered to executor %s (%s, %s steps)",
            req["seq"], executor_id, log_dir, steps,
        )
        return req

    def _clear_health_hint(self, executor_id):
        """Erase a recovered node's ``health_hint`` kv so its
        supervisor stops flagging ``health.straggler`` on the beat —
        the recovery mirror of :meth:`_request_profile`'s hint
        write."""
        node_meta = next(
            (n for n in self.cluster_info
             if n["executor_id"] == int(executor_id)), None,
        )
        if node_meta is None:
            return
        try:
            m = self._connect(node_meta)
            m.set("health_hint", None)
        except Exception:  # noqa: BLE001 - node mid-restart: its
            logger.warning(  # stale flag clears on the next rebirth
                "unable to clear health hint on executor %s",
                executor_id, exc_info=True,
            )

    # -- remediation verbs (ISSUE 16) ----------------------------------

    def _compute_node(self, executor_id):
        return next(
            (n for n in self.cluster_info
             if n["executor_id"] == int(executor_id)
             and n["job_name"] in ("worker", "chief", "master")),
            None,
        )

    def hold_executor(self, executor_id, reason=None):
        """Elastic shrink (the remediation engine's straggler
        actuator): write a ``remediation_hold`` into the node's kv —
        its supervisor quiesces the compute process, bumps the gang
        generation so the survivors re-rendezvous at reduced width,
        and parks (heartbeating, registered, NOT training) until
        :meth:`release_executor`.  Requires ``elastic=True``.
        Returns True when the hold was delivered."""
        if not self.elastic:
            raise RuntimeError(
                "hold_executor needs an elastic cluster (the shrink "
                "is a supervised re-rendezvous)"
            )
        node_meta = self._compute_node(executor_id)
        if node_meta is None:
            logger.warning(
                "hold request for unknown executor %s", executor_id
            )
            return False
        try:
            m = self._connect(node_meta)
            m.set("remediation_hold", {
                "reason": str(reason or "remediation"),
                "t": time.time(),
            })
        except Exception:  # noqa: BLE001 - node mid-restart
            logger.warning(
                "unable to deliver hold to executor %s",
                executor_id, exc_info=True,
            )
            return False
        from tensorflowonspark_tpu import telemetry

        telemetry.get_tracer().mark(
            "remediation_hold_set", trace="cluster", severity="warn",
            executor_id=int(executor_id), reason=reason,
        )
        if self.monitor is not None:
            # a held node reports compute_alive (state 'held'), but
            # give the transition the same grace as a restart so the
            # kill→held window never reads as a death
            self.monitor.clear_straggler(int(executor_id))
        return True

    def release_executor(self, executor_id):
        """Elastic grow: clear the node's ``remediation_hold`` — its
        supervisor claims the next generation and respawns, and the
        gang re-rendezvouses back to full width.  Returns True when
        the release was delivered."""
        node_meta = self._compute_node(executor_id)
        if node_meta is None:
            return False
        try:
            m = self._connect(node_meta)
            m.set("remediation_hold", None)
        except Exception:  # noqa: BLE001 - node mid-restart
            logger.warning(
                "unable to deliver release to executor %s",
                executor_id, exc_info=True,
            )
            return False
        from tensorflowonspark_tpu import telemetry

        telemetry.get_tracer().mark(
            "remediation_hold_cleared", trace="cluster",
            executor_id=int(executor_id),
        )
        return True

    def start_remediation(self, router=None, policies=None,
                          guardrails=None, interval=None, **overrides):
        """Wire and START the audited remediation engine over this
        cluster's live planes (requires :meth:`start_health_plane`
        first — the engine reads its SLO cursor and straggler hints).
        Returns the running :class:`~tensorflowonspark_tpu.
        remediation.engine.RemediationEngine` (also kept on
        ``self.remediation``; ``stop()`` it before shutdown)."""
        if self.health is None:
            raise RuntimeError(
                "start_remediation needs the health plane — call "
                "start_health_plane(...) first"
            )
        from tensorflowonspark_tpu import remediation as _remediation

        eng = _remediation.wire(
            self.health, router=router, cluster=self,
            policies=policies, guardrails=guardrails,
            interval=(
                self.health.interval if interval is None
                else float(interval)
            ),
            **overrides
        )
        self.remediation = eng
        return eng.start()

    def tensorboard_url(self):
        """URL of the cluster's tensorboard, if one was launched
        (reference: TFCluster.py:207-212)."""
        for n in self.cluster_info:
            if n.get("tb_port"):
                return "http://{0}:{1}".format(n["host"], n["tb_port"])
        return None

    @property
    def coordinator(self):
        """JAX coordination address (chief/worker:0) for this cluster."""
        _, coordinator, _ = node.build_cluster_spec(self.cluster_info)
        return coordinator


#: Reference-parity alias (the reference called its handle TFCluster);
#: ``TFCluster.metrics()`` in docs refers to this class.
TFCluster = TPUCluster


def _with_partition_marker(pid, partition):
    """Prefix a partition with its PartitionStart marker (lazily for
    callable partitions — the rows still never transit the driver)."""
    if callable(partition):
        def gen():
            return itertools.chain([PartitionStart(pid)], iter(partition()))

        return gen
    return [PartitionStart(pid)] + list(partition)


def run(
    engine,
    map_fun,
    args=None,
    num_executors=None,
    num_ps=0,
    tensorboard=False,
    input_mode=InputMode.SPARK,
    log_dir=None,
    driver_ps_nodes=False,
    master_node=None,
    reservation_timeout=600,
    queues=("input", "output", "error"),
    eval_node=False,
    num_chips_per_node=None,
    name="tpucluster",
    elastic=False,
    max_restarts=3,
    heartbeat_interval=None,
    recovery_timeout=120.0,
    profile_dir=None,
    profile_steps=None,
    plan=None,
    plan_hint=None,
):
    """Start a cluster over an executor fleet (reference: TFCluster.py:215-383).

    Args:
      engine: an :class:`~tensorflowonspark_tpu.engine.Engine`, a live
        ``SparkContext`` (wrapped automatically), or an int (number of
        local executor processes to launch).
      map_fun: user function ``main_fun(args, ctx)``.
      args: opaque user args handed through to ``map_fun``.
      num_executors: total nodes; defaults to ``engine.num_executors``.
      num_ps: number of parameter-server nodes (reference: TFCluster.py:224).
      tensorboard: launch tensorboard on chief/worker:0.
      input_mode: :class:`InputMode`.
      log_dir: event-log directory.
      driver_ps_nodes: host the ``num_ps`` parameter-server shards in
        the *driver* process instead of dedicating executors
        (reference: TFCluster.py:296-314 ran PS threads on the driver);
        every executor then runs a worker, and
        ``ctx.cluster_spec['ps']`` points at the driver's shard
        addresses.
      master_node: job name for a dedicated chief (e.g. ``'chief'``)
        (reference: TFCluster.py:233).
      reservation_timeout: startup barrier timeout seconds
        (reference: TFCluster.py:216 default 600).
      queues: data queues to create on worker nodes.
      eval_node: dedicate one node as ``'evaluator'``
        (reference: TFCluster.py:236).
      num_chips_per_node: TPU chips visible per node (replaces the
        reference's ``num_gpus``-via-resources allocation).  Required
        when several compute executors share one TPU host (executors x
        chips must tile the host's four chips; the processes then form
        one slice through ``ctx.initialize_distributed()``) — leaving
        it unset there raises
        :class:`~tensorflowonspark_tpu.cluster.tpu_info.ChipLayoutError`.
      elastic: treat worker death as a recoverable event: the node's
        supervisor respawns the compute process under a new rendezvous
        generation, survivors park/respawn at the re-rendezvous barrier,
        training resumes from the last complete checkpoint (the
        ``train_on_feed(checkpointer=...)`` hook), and uncommitted feed
        partitions are requeued.  Default False: a dead worker fails
        the feed fast with a diagnosis naming the node (still a huge
        improvement over the reference's 600s feed-timeout silence).
        See docs/fault_tolerance.md.
      max_restarts: per-node restart budget under ``elastic``.
      heartbeat_interval: seconds between node heartbeats (default
        ``reservation.HEARTBEAT_INTERVAL``; liveness declares a node
        dead after 3 missed intervals).
      recovery_timeout: under ``elastic``, seconds a dead node may take
        to come back before the failure is permanent.
      profile_dir: capture a ``jax.profiler`` device trace from every
        compute process into ``profile_dir/<pid>`` (exported via
        ``TFOS_PROFILE_DIR`` — compute processes inherit the driver's
        environment; a build without the profiler no-ops gracefully,
        see tensorboard.start_profile and docs/observability.md).
      profile_steps: stop each capture after this many train steps
        (None = capture until the compute process exits).
      plan: ``"auto"`` runs the cost-model planner for the training
        workload (docs/autotune.md) and ships the chosen cadence
        (``push_every`` / ``max_inflight``) to every node via
        ``cluster_meta["plan"]`` — ``map_fun`` reads it off
        ``ctx.cluster_meta`` instead of hand-setting the knobs.  The
        decision is journaled (``planner_decision``) so ``forensics
        explain`` answers "why this cadence".
      plan_hint: workload facts for the planner (``batch``,
        ``seq_len``, ``dcn_gbs``, model dims — see
        ``planner.DEFAULT_HINT``).
    """
    from tensorflowonspark_tpu.engine import Engine, LocalEngine, SparkEngine

    if profile_dir:
        import os as _os

        from tensorflowonspark_tpu import tensorboard as _tb

        _os.environ[_tb.PROFILE_DIR_ENV] = str(profile_dir)
        if profile_steps:
            _os.environ[_tb.PROFILE_STEPS_ENV] = str(int(profile_steps))

    owns_engine = False
    if isinstance(engine, int):
        # validate BEFORE constructing the engine: raising later would
        # leak the executor processes we just spawned
        if num_executors is not None and num_executors > engine:
            raise ValueError(
                "num_executors ({0}) exceeds the engine's executor count "
                "({1}); the startup barrier would wait forever".format(
                    num_executors, engine
                )
            )
        engine = LocalEngine(engine)
        owns_engine = True
    elif not isinstance(engine, Engine) and hasattr(engine, "parallelize"):
        engine = SparkEngine(engine)

    if num_executors is None:
        num_executors = engine.num_executors
    if num_executors > engine.num_executors:
        # Only authoritative counts may hard-fail: Spark under dynamic
        # allocation reports the spark.executor.instances *default*, not
        # the real fleet (the reference never validated this at all —
        # its reservation_timeout was the only guard, TFCluster.py:216).
        msg = (
            "num_executors ({0}) exceeds the engine's reported executor "
            "count ({1}); the startup barrier would wait forever".format(
                num_executors, engine.num_executors
            )
        )
        if engine.num_executors_exact:
            raise ValueError(msg)
        logger.warning(
            "%s — proceeding anyway (count is not authoritative; the "
            "reservation timeout of %ds is the backstop)",
            msg,
            reservation_timeout,
        )

    # driver-hosted PS consumes no executors (reference: TFCluster.py:
    # 296-314); shards start only after validation so a failed run()
    # can't leak their sockets/threads.
    use_driver_ps = driver_ps_nodes and num_ps > 0
    num_ps_exec = 0 if use_driver_ps else num_ps

    # validate cluster composition (reference: TFCluster.py:246-253)
    num_special = (
        num_ps_exec + (1 if master_node else 0) + (1 if eval_node else 0)
    )
    num_workers = num_executors - num_special
    if num_workers < 0 or (num_workers == 0 and master_node is None):
        raise ValueError(
            "num_executors ({0}) must cover {1} ps + {2} master + {3} "
            "evaluator nodes and at least one worker".format(
                num_executors,
                num_ps,
                1 if master_node else 0,
                1 if eval_node else 0,
            )
        )

    template = node._cluster_template(
        num_executors, num_ps_exec, master_node=master_node, eval_node=eval_node
    )
    logger.info("cluster template: %s", template)

    driver_ps = []
    driver_ps_addrs = []
    if use_driver_ps:
        from tensorflowonspark_tpu.parallel.ps import ParamServerShard
        from tensorflowonspark_tpu.utils.net import get_ip_address

        host = get_ip_address()
        for _ in range(num_ps):
            shard = ParamServerShard()
            _, port = shard.start("")
            driver_ps.append(shard)
            driver_ps_addrs.append("{0}:{1}".format(host, port))
        logger.info("driver-hosted ps shards at %s", driver_ps_addrs)

    server = reservation.Server(
        num_executors, heartbeat_interval=heartbeat_interval
    )
    server_addr = server.start()
    # driver-side fault events (the monitor's executor_dead verdict)
    # never ride a heartbeat — bridge this process's journal into the
    # fleet EventStore so TPUCluster.journal() carries the driver's
    # view of an incident too (executor -1 = the driver)
    server.attach_local_journal()

    cluster_meta = {
        "id": "{0}-{1}".format(name, uuid.uuid4().hex[:8]),
        "cluster_template": template,
        "num_executors": num_executors,
        "default_fs": engine.default_fs,
        "server_addr": list(server_addr),
        "reservation_timeout": reservation_timeout,
        "queues": list(queues),
        "num_chips_per_node": num_chips_per_node,
        "driver_ps_addrs": driver_ps_addrs,
        "elastic": bool(elastic),
        "max_restarts": int(max_restarts),
        "heartbeat_interval": heartbeat_interval,
    }
    if plan == "auto":
        # cost-model cadence planning (ISSUE 18): the chosen
        # push_every/max_inflight ride cluster_meta to every node;
        # map_fun reads ctx.cluster_meta["plan"]["chosen"] instead of
        # hand-setting the DCN knobs
        from tensorflowonspark_tpu import planner as _planner

        hint = dict(plan_hint or {})
        p = _planner.plan(
            model_config=hint.pop("model_config", None),
            workload="train", hint=hint,
        )
        cluster_meta["plan"] = p.summary()
        logger.info("planner: train cadence %s", p.summary()["chosen"])
    elif plan is not None:
        raise ValueError(
            "plan must be 'auto' or None, got {0!r}".format(plan)
        )

    # async start job: one blocking task per executor
    # (reference: TFCluster.py:316-334 daemon thread)
    mapfn = node.run(
        map_fun,
        args,
        cluster_meta,
        input_mode,
        log_dir=log_dir,
        tensorboard=tensorboard,
    )
    start_partitions = [[i] for i in range(num_executors)]
    handle = engine.run_job_async(mapfn, start_partitions)

    # startup barrier on the driver (reference: TFCluster.py:338)
    try:
        cluster_info = server.await_reservations(
            status=_HandleStatus(handle), timeout=reservation_timeout
        )
        # fail fast, by name, on a layout whose compute processes would
        # fight over a host's chips (every node refuses it too, from
        # the same cluster_info, before spawning its compute process)
        from tensorflowonspark_tpu.cluster import tpu_info

        tpu_info.check_chip_layout(cluster_info, num_chips_per_node)
    except Exception:
        for shard in driver_ps:
            shard.stop()
        server.stop()
        if owns_engine:
            engine.stop()
        raise

    # Duplicate registrations are deduplicated at the source: the
    # rendezvous store is idempotent per executor_id (reservation.py
    # Reservations.add), so unlike the reference no late duplicate-node
    # check is needed here (reference: TFCluster.py:355-370).
    for n in sorted(cluster_info, key=lambda x: x["executor_id"]):
        logger.info(
            "node: executor_id=%d %s:%d on %s",
            n["executor_id"],
            n["job_name"],
            n["task_index"],
            n["host"],
        )

    cluster = TPUCluster(
        engine,
        cluster_meta,
        cluster_info,
        server,
        handle,
        input_mode,
        list(queues),
        owns_engine=owns_engine,
        driver_ps=driver_ps,
    )
    cluster.monitor = ClusterMonitor(
        server,
        cluster_info,
        elastic=elastic,
        recovery_timeout=recovery_timeout,
        error_peek=cluster._peek_error,
    ).start()
    if tensorboard:
        url = cluster.tensorboard_url()
        if url:
            logger.info("TensorBoard running at: %s", url)
    return cluster
