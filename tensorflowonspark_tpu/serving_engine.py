"""Overload-safe online serving around the slot scheduler.

The continuous-batching scheduler shipped with
:mod:`tensorflowonspark_tpu.serving` was fail-stop: one malformed
request raised out of the scheduling loop and killed every in-flight
request, the request queue was unbounded, no request carried a
deadline, and a wedged device dispatch hung the caller forever.  The
reference stack leans on its runtime for exactly this class of
recovery (TensorFlow §4.4 fault tolerance), and TF-Replicator's lesson
— keep the failure-handling *policy* in the framework layer, not user
code — is what PR 1 applied to training.  This module is the serving
counterpart:

- **admission control** — a bounded request queue with three
  load-shedding policies: ``block`` (pull no faster than slots free —
  classic backpressure on the row source), ``reject`` (requests past
  the queue bound return a typed *shed record* immediately), and
  ``degrade`` (every request is accepted but its token budget shrinks
  proportionally to the backlog, down to ``degrade_floor``);
- **poison isolation** — schema/shape/dtype validation at admission
  plus per-request error capture around the slot prefill, so with
  ``on_error="record"`` a bad row yields an *error record* at its
  input position instead of killing the batch (``on_error="raise"``
  keeps fail-fast semantics but names the request index and the
  offending column);
- **per-request deadlines** — a row column mapped to the reserved
  input :data:`DEADLINE_INPUT` (or the engine-level
  ``default_deadline``) bounds each request's submit→finish wall
  time; an expired lane is *cancelled* between decode chunks
  (:meth:`SlotDecoder.cancel` — neighbors are untouched, nothing
  recompiles) and returns a ``deadline`` record carrying the tokens
  it did complete;
- **decode watchdog** — the chunk sync (the engine's only
  synchronizing device call) runs on a watchdog thread under
  ``watchdog_timeout``; a wedged dispatch is abandoned, the slot
  table is torn down, and every in-flight request is re-admitted
  from its already-committed tokens.  The committed prefix is
  preserved and (greedy) recovered outputs are token-identical for
  unaffected requests, because the re-admitted prompt+prefix prefill
  recreates exactly the context the lost decode step saw;
- **serving lifecycle** (ISSUE 8 / :mod:`tensorflowonspark_tpu.
  hot_swap`) — a :class:`~tensorflowonspark_tpu.hot_swap.
  CheckpointWatcher` (``watcher=`` / ``checkpoint_dir=``) hot-swaps
  validated new weight generations in between decode chunks with
  zero dropped requests: in-flight requests quiesce through the SAME
  teardown/re-admit path the watchdog uses (planned swaps, not just
  wedges), the previous weights stay resident until
  ``rollback_window`` clean requests commit the swap, and a
  post-install canary failure or probation error spike rolls back
  automatically.  :meth:`ServingEngine.drain` reuses the admission
  gate for graceful shutdown.

Every shed/expired/poisoned request is *accounted*: it occupies its
input-order position in the output stream as a typed record (see
:func:`error_record`), so the engine never drops a request silently
and never deadlocks — the chaos e2e in tests/test_chaos_serving.py
drives all three fault families at 2x offered load.

Deterministic fault injection lives in
:mod:`tensorflowonspark_tpu.testing.chaos` (``wedge_dispatch`` plans,
``poison_row``, ``slow_consumer``); the engine picks a planned wedge
up from the ``TFOS_CHAOS_PLAN`` env var exactly like the training-side
heartbeat hooks do.
"""

import logging
import queue as queue_mod
import threading
import time

import numpy as np

from tensorflowonspark_tpu import telemetry
from tensorflowonspark_tpu.prefix_cache import pages_for_tokens
from tensorflowonspark_tpu.telemetry import catalog as _catalog

logger = logging.getLogger(__name__)

#: Shared request-latency histogram name: BOTH schedules (static
#: predict_rows batches and this engine) observe submit→finish wall
#: time here, so p50/p99 report identical semantics everywhere
#: (ISSUE 7 satellite; bench + CLI source their percentiles from it).
LATENCY_METRIC = "serving.request_latency_sec"


def latency_histogram():
    """The process-wide request-latency histogram (see
    :data:`LATENCY_METRIC`)."""
    return telemetry.get_registry().histogram(LATENCY_METRIC)


def latency_summary(since=None):
    """p50/p99/count of the shared request-latency histogram, in ms.

    ``since`` is a prior ``latency_histogram().snapshot()`` — pass it
    to scope the summary to one job/bench window (the histogram is
    cumulative across jobs).  Returns zeros when telemetry is disabled
    or nothing was observed.

    **This histogram is the authoritative percentile source** (docs/
    serving.md "Latency accounting").  Consumers that also keep raw
    per-request lists (``stats["latency_sec"]``, all there is under
    ``TFOS_TELEMETRY=0``) interpolate differently — a raw
    list nearest-rank percentile vs the histogram's within-bucket
    linear interpolation — so the two agree only to the geometric
    bucket width (ratio 1.25, ~±12%; parity-tested at that tolerance
    in tests/test_serving_engine.py).  Report from here unless
    telemetry is off.
    """
    snap = latency_histogram().snapshot()
    if since:
        snap = telemetry.snapshot_delta(
            {"histograms": {LATENCY_METRIC: snap}},
            {"histograms": {LATENCY_METRIC: since}},
        )["histograms"][LATENCY_METRIC]
    return {
        "count": int(snap.get("count", 0)),
        "p50_ms": round(
            1e3 * telemetry.histogram_percentile(snap, 50), 3
        ),
        "p99_ms": round(
            1e3 * telemetry.histogram_percentile(snap, 99), 3
        ),
    }


#: Time-to-first-token histogram name: the continuous engine observes
#: submit→first-token wall here (stamped when the admit's unresolved
#: device scalar first resolves).  TTFT is the number the
#: prefill/decode disaggregation exists to bound — docs/serving.md
#: "Disaggregated prefill/decode & TP sharding".
TTFT_METRIC = "serving.ttft_sec"


def ttft_histogram():
    """The process-wide time-to-first-token histogram (see
    :data:`TTFT_METRIC`)."""
    return telemetry.get_registry().histogram(TTFT_METRIC)


def ttft_summary(since=None):
    """p50/p99/count of the TTFT histogram, in ms — the
    :func:`latency_summary` contract (``since`` scopes to a window;
    zeros when telemetry is off)."""
    snap = ttft_histogram().snapshot()
    if since:
        snap = telemetry.snapshot_delta(
            {"histograms": {TTFT_METRIC: snap}},
            {"histograms": {TTFT_METRIC: since}},
        )["histograms"][TTFT_METRIC]
    return {
        "count": int(snap.get("count", 0)),
        "p50_ms": round(
            1e3 * telemetry.histogram_percentile(snap, 50), 3
        ),
        "p99_ms": round(
            1e3 * telemetry.histogram_percentile(snap, 99), 3
        ),
    }

#: reserved input name: a row column mapped to it carries that
#: request's token budget — the scheduler evicts the row after
#: ``min(max_new, budget)`` tokens even when no eos arrives
BUDGET_INPUT = "max_new"

#: reserved input name: a row column mapped to it carries that
#: request's deadline in SECONDS from submission; an expired request
#: is cancelled between chunks and returns a ``deadline`` record
DEADLINE_INPUT = "deadline_sec"

#: reserved input name: a row column mapped to it carries that
#: request's TENANT key — the usage ledger (telemetry/ledger.py)
#: attributes the request's resources (chip-seconds, page-seconds,
#: tokens, wire bytes) to it.  Validated at admission on BOTH
#: schedules: a non-string or empty value is a typed error naming the
#: request index and the offending value.  Requests without a mapped
#: tenant land on :data:`~tensorflowonspark_tpu.telemetry.ledger.
#: DEFAULT_TENANT`.
TENANT_INPUT = "tenant"

#: reserved input name: a row column mapped to it carries the
#: request's TRACE id.  The fleet router mints one per request at
#: fleet admission and threads it through dispatch → replica feed →
#: this engine, so the engine's span chain (admission → queue_wait →
#: prefill → decode_chunk×N → emit) joins the router's trace — and a
#: re-dispatch after a replica death continues the SAME trace on the
#: surviving replica (docs/observability.md "Cost attribution & usage
#: ledger").  Unmapped requests trace as ``req<N>`` exactly as
#: before.
TRACE_INPUT = "trace_id"

#: THE consolidated reserved-input contract (ISSUE 15): every column
#: name the serving surface claims for itself, in one tuple.  The
#: tfoslint rule TFOS004 flags any of these spelled as a raw literal
#: elsewhere; the import-light twin the telemetry layer reads is
#: ``telemetry.catalog.RESERVED_INPUT_COLUMNS`` — the assert below
#: keeps the two registries from ever drifting.
RESERVED_INPUTS = (
    BUDGET_INPUT, DEADLINE_INPUT, TENANT_INPUT, TRACE_INPUT,
)

assert RESERVED_INPUTS == _catalog.RESERVED_INPUT_COLUMNS, (
    "serving_engine.RESERVED_INPUTS drifted from "
    "telemetry.catalog.RESERVED_INPUT_COLUMNS: %r != %r"
    % (RESERVED_INPUTS, _catalog.RESERVED_INPUT_COLUMNS)
)

#: admission policies (see module docstring)
POLICIES = ("block", "reject", "degrade")

#: per-request failure policies
ON_ERROR = ("raise", "record")


class ServingError(Exception):
    """Base for serving-engine failures."""


class RequestError(ServingError, ValueError):
    """A problem scoped to ONE request.  Carries the failure ``kind``
    (a short slug, see :func:`error_record`) and the request's input
    index so callers can always name the poisoned row."""

    def __init__(self, message, kind="request", request_index=None):
        super(RequestError, self).__init__(message)
        self.kind = kind
        self.request_index = request_index


class RequestValidationError(RequestError):
    """Admission-time validation failure (missing column, bad
    shape/dtype, oversized prompt, bad budget/deadline value)."""


class WatchdogTimeout(ServingError):
    """The decode watchdog gave up on a wedged chunk dispatch."""


def error_record(kind, request_index, message, tokens_done=0,
                 partial=None):
    """The typed record a failed/shed/expired request yields at its
    input-order position.  Consumers distinguish records from normal
    rows by the single ``"error"`` key::

        {"error": {"kind": "deadline", "request_index": 3,
                   "message": "...", "tokens_done": 2,
                   "partial": [17, 4]}}

    ``kind`` is one of: ``missing_input`` / ``bad_dtype`` /
    ``bad_shape`` / ``empty_prompt`` / ``too_long`` / ``bad_budget``
    / ``bad_deadline`` / ``bad_tenant`` / ``bad_trace`` (validation),
    ``admit`` / ``predict``
    (per-request capture), ``shed`` (admission control), ``deadline``
    (expiry — carries the committed ``partial`` tokens), ``drained``
    (a graceful :meth:`ServingEngine.drain` stopped admissions or
    deadline-cancelled the lane — carries committed tokens too).
    """
    rec = {
        "kind": str(kind),
        "request_index": int(request_index),
        "message": str(message),
        "tokens_done": int(tokens_done),
    }
    if partial is not None:
        rec["partial"] = [int(t) for t in partial]
    return {"error": rec}


def validate_tenant(row, idx, tenant_col):
    """Shared tenant-key validation for BOTH schedules: the reserved
    :data:`TENANT_INPUT` column must hold a non-empty string (numpy
    str scalars normalize); anything else is a typed
    :class:`RequestValidationError` (kind ``bad_tenant``) naming the
    request index and the offending value."""
    v = row[tenant_col]
    if isinstance(v, np.str_):
        v = str(v)
    if isinstance(v, bytes):
        try:
            v = v.decode("utf-8")
        except UnicodeDecodeError:
            v = None
    if not isinstance(v, str) or not v:
        raise RequestValidationError(
            "request {0}: tenant column {1!r} must hold a non-empty "
            "string tenant key, got {2!r}".format(
                idx, tenant_col, row[tenant_col]
            ),
            kind="bad_tenant", request_index=idx,
        )
    return v


def apply_output_mapping(out, output_mapping):
    """Rename predictor outputs to row columns; unknown names fail
    fast (a CALLER config error — never converted to a record)."""
    if not output_mapping:
        return out
    missing = [n for n in output_mapping if n not in out]
    if missing:
        raise KeyError(
            "output_mapping names {0} not produced by the predictor "
            "(outputs: {1})".format(missing, sorted(out))
        )
    return {col: out[name] for name, col in output_mapping.items()}


class _DispatchWatchdog(object):
    """Runs the engine's synchronizing device call on a worker thread
    so a wedged dispatch can be timed out instead of hanging the
    scheduler forever.

    On timeout the watchdog is *abandoned*: the dispatched callable is
    expected to consult :attr:`abandoned` after any injected fault
    gate and skip the real device call, so a stale thread never
    touches the decoder concurrently with the replacement watchdog
    (the chaos wedge does exactly this).  A dispatch wedged INSIDE the
    runtime keeps its daemon thread parked — recovery of the python
    scheduler still proceeds; freeing the device itself is the
    supervisor layer's job (docs/fault_tolerance.md).
    """

    def __init__(self):
        self._in = queue_mod.Queue()
        self._out = queue_mod.Queue()
        self.abandoned = False
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="serving-watchdog"
        )
        self._thread.start()

    def _run(self):
        while True:
            fn = self._in.get()
            if fn is None:
                return
            try:
                self._out.put(("ok", fn()))
            except BaseException as e:  # noqa: BLE001 - relayed to caller
                self._out.put(("err", e))

    def call(self, fn, timeout):
        """Run ``fn()`` on the worker; raise :class:`WatchdogTimeout`
        (and abandon the worker) when no result lands in time."""
        self._in.put(fn)
        try:
            kind, val = self._out.get(timeout=timeout)
        except queue_mod.Empty:
            self.abandoned = True
            raise WatchdogTimeout(
                "decode chunk dispatch produced no result within "
                "{0:.1f}s; abandoning the dispatch".format(timeout)
            )
        if kind == "err":
            raise val
        return val

    def close(self):
        if not self.abandoned:
            self._in.put(None)


class ServingEngine(object):
    """Overload-safe continuous serving over a generation predictor.

    Wraps :class:`~tensorflowonspark_tpu.models.transformer.SlotDecoder`
    (via the predictor's ``make_slot_decoder`` factory) with the
    admission/deadline/poison/watchdog machinery described in the
    module docstring.  :meth:`serve` is a generator: feed it an
    iterable of dict rows, get output rows back in INPUT order, with
    typed records occupying the positions of failed/shed/expired
    requests.

    Args:
      predict: generation predictor exposing ``make_slot_decoder``
        (``transformer.serving_builder(mode="generate")``).
      input_mapping: ``{column: input_name}``; exactly one column must
        map to a ragged prompt input, optionally one each to
        :data:`BUDGET_INPUT`, :data:`DEADLINE_INPUT`,
        :data:`TENANT_INPUT` (usage-ledger attribution) and
        :data:`TRACE_INPUT` (an explicit request trace id — the fleet
        router threads its minted ids through this).
      output_mapping: optional ``{output_name: column}`` rename.
      num_slots: in-flight KV-cache slots.
      chunk: decode steps per dispatch (None = predictor default).
      queue_depth: bounded admission queue (default ``2 * num_slots``).
      policy: ``"block" | "reject" | "degrade"``.
      degrade_floor: minimum per-request budget under ``degrade``.
      default_deadline: seconds; applied to rows without a mapped
        deadline column (None = no deadline).
      watchdog_timeout: seconds; bounds every chunk sync (None = no
        watchdog — zero thread overhead).
      on_error: ``"raise"`` (fail fast, error names the request) or
        ``"record"`` (poison isolation — bad rows become records).
      wedge_fn: test hook ``fn(chunk_index)`` invoked before every
        chunk dispatch; defaults to the chaos plan's wedge
        (:func:`tensorflowonspark_tpu.testing.chaos.serving_wedge_fn`),
        which is None unless ``TFOS_CHAOS_PLAN`` orders one.
      stats: optional dict filled with scheduling counters (see
        :meth:`serve`).
      clock: monotonic clock override (tests).
      watcher: a :class:`~tensorflowonspark_tpu.hot_swap.
        CheckpointWatcher` — newly published checkpoints it validates
        hot-swap in between decode chunks with zero dropped requests
        (docs/serving.md "Live weight swap & rollback").
      checkpoint_dir: convenience — builds a watcher over this
        step-numbered export root (``publish_for_serving`` layout);
        the engine then owns (and closes) it.
      checkpoint_poll_sec: watcher poll interval for
        ``checkpoint_dir``.
      rollback_window: clean completed requests the new generation
        must serve before the previous weights are released; a
        device-side error or watchdog fire inside the window rolls
        back automatically.
      swap_canary: run the decoder's single-forward canary right
        after a swap installs; a failure rolls back on the spot and
        quarantines the checkpoint.
    """

    def __init__(self, predict, input_mapping, output_mapping=None,
                 num_slots=8, *, chunk=None, queue_depth=None,
                 policy="block", degrade_floor=1, default_deadline=None,
                 watchdog_timeout=None, on_error="raise", wedge_fn=None,
                 stats=None, clock=None, watcher=None,
                 checkpoint_dir=None, checkpoint_poll_sec=5.0,
                 rollback_window=8, swap_canary=True, disaggregate=None):
        if policy not in POLICIES:
            raise ValueError(
                "policy must be one of {0}, got {1!r}".format(
                    POLICIES, policy
                )
            )
        if on_error not in ON_ERROR:
            raise ValueError(
                "on_error must be one of {0}, got {1!r}".format(
                    ON_ERROR, on_error
                )
            )
        factory = getattr(predict, "make_slot_decoder", None)
        if factory is None:
            raise ValueError(
                "continuous serving requires a generation predictor "
                "exposing make_slot_decoder (see transformer."
                "serving_builder with mode='generate'); this predictor "
                "has none"
            )
        column_padding = getattr(predict, "column_padding", None) or {}
        prompt_cols = [
            c for c in input_mapping if input_mapping[c] in column_padding
        ]
        if len(prompt_cols) != 1:
            raise ValueError(
                "continuous scheduling needs exactly one ragged prompt "
                "column in input_mapping; got {0}".format(prompt_cols)
            )
        self.predict = predict
        self.input_mapping = dict(input_mapping)
        self.output_mapping = output_mapping
        self.prompt_col = prompt_cols[0]
        self.budget_col = next(
            (c for c in input_mapping
             if input_mapping[c] == BUDGET_INPUT), None
        )
        self.deadline_col = next(
            (c for c in input_mapping
             if input_mapping[c] == DEADLINE_INPUT), None
        )
        self.tenant_col = next(
            (c for c in input_mapping
             if input_mapping[c] == TENANT_INPUT), None
        )
        self.trace_col = next(
            (c for c in input_mapping
             if input_mapping[c] == TRACE_INPUT), None
        )
        self.policy = policy
        self.on_error = on_error
        self.degrade_floor = max(1, int(degrade_floor))
        self.default_deadline = (
            None if default_deadline is None else float(default_deadline)
        )
        self.watchdog_timeout = (
            None if watchdog_timeout is None else float(watchdog_timeout)
        )
        self.num_slots = int(num_slots)
        self.queue_depth = (
            max(1, int(queue_depth)) if queue_depth is not None
            else max(1, 2 * self.num_slots)
        )
        self.decoder = (
            factory(self.num_slots) if chunk is None
            else factory(self.num_slots, chunk)
        )
        # prefill/decode disaggregation (docs/serving.md "Disaggregated
        # prefill/decode & TP sharding"): admits run through a
        # PrefillWorker's OWN jitted program and hand their finished KV
        # to the chunked decoder as a zero-copy block-table exchange
        # (SlotDecoder.adopt).  Explicit arg wins; else the predictor's
        # serving_builder `disaggregate` knob — which is how a fleet
        # replica built through the engine_factory seam turns it on
        # with zero router changes.
        if disaggregate is None:
            disaggregate = bool(getattr(predict, "disaggregate", False))
        self.disaggregate = bool(disaggregate)
        if self.disaggregate:
            from tensorflowonspark_tpu.serving_disagg import (
                PrefillWorker, PrefillWorkerDead,
            )

            # memoized on the decoder: the predictor caches its
            # SlotDecoder across engines, and the worker's jit cache
            # must survive engine rebuilds the same way the decoder's
            # compiled programs do (watchdog recovery, repeated
            # predict_rows calls)
            worker = getattr(self.decoder, "_prefill_worker", None)
            if worker is None:
                worker = PrefillWorker(self.decoder)
                self.decoder._prefill_worker = worker
            else:
                # the chaos plan env is read per ENGINE (like wedge_fn
                # just below), not per memoized worker — a plan
                # advertised between predict_rows calls must reach the
                # cached worker.  Only arm an UNARMED worker: its
                # prefill counter is monotonic across restarts, so
                # re-resolving an armed hook (fresh spent-set, `>=`
                # matching) would re-fire every already-spent fault on
                # the next engine rebuild (quarantine recovery).
                from tensorflowonspark_tpu.testing import chaos

                if chaos.load_plan() is None:
                    worker._fault = None
                elif worker._fault is None:
                    worker._fault = chaos.prefill_fault_fn()
            self._prefill_worker = worker
            # the CONTAINED prefill faults (_admit_free falls back to
            # the unified path): a dead worker, or a supervised
            # dispatch the watchdog abandoned
            self._prefill_fault_exc = (WatchdogTimeout, PrefillWorkerDead)
        else:
            self._prefill_worker = None
            self._prefill_fault_exc = ()
        if (self._prefill_worker is not None
                and self.watchdog_timeout is not None):
            # supervise the prefill dispatch with its own abandonable
            # watchdog (the PR 4 pattern extended to the prefill side)
            # and bound how long its handoff leases may stay in
            # flight: generous vs the dispatch timeout so the serve
            # loop's deadline reaper only ever fires on leases whose
            # supervised owner ALSO vanished (e.g. chaos leak_lease)
            self._prefill_watchdog = _DispatchWatchdog()
            if self._prefill_worker.lease_deadline_sec is None:
                self._prefill_worker.lease_deadline_sec = (
                    4.0 * self.watchdog_timeout
                )
        else:
            self._prefill_watchdog = None
        self.max_new = self.decoder.max_new_tokens
        self.eos_id = self.decoder.eos_id
        self._fill = self.eos_id if self.eos_id is not None else 0
        # generated_len is emitted whenever ANY truncation machinery is
        # live (eos stops, budgets, degrade) — the static path's rule,
        # extended by the degrade policy
        self._emit_len = (
            self.eos_id is not None or self.budget_col is not None
            or policy == "degrade"
        )
        self._clock = clock if clock is not None else time.monotonic
        if wedge_fn is None:
            from tensorflowonspark_tpu.testing import chaos

            wedge_fn = chaos.serving_wedge_fn()
        self._wedge = wedge_fn
        self._watchdog = (
            _DispatchWatchdog() if self.watchdog_timeout is not None
            else None
        )
        # live weight hot-swap plane (hot_swap.py / docs/serving.md
        # "Live weight swap & rollback")
        self.rollback_window = max(1, int(rollback_window))
        self.swap_canary = bool(swap_canary)
        self._own_watcher = False
        if watcher is None and checkpoint_dir:
            from tensorflowonspark_tpu import hot_swap

            watcher = hot_swap.CheckpointWatcher(
                checkpoint_dir, poll_interval=float(checkpoint_poll_sec)
            )
            self._own_watcher = True
        self.watcher = watcher
        if self.watcher is not None:
            if not callable(getattr(self.decoder, "swap_weights", None)):
                if self._own_watcher:
                    self.watcher.close()
                raise ValueError(
                    "live weight hot-swap needs a decoder exposing "
                    "swap_weights/snapshot_weights (transformer."
                    "serving_builder generation decoders do); this "
                    "predictor's decoder has none"
                )
            # bind the live param census so the watcher's validation
            # stage can reject mis-shaped checkpoints off the hot path
            if (getattr(self.watcher, "expect", None) is None
                    and callable(getattr(self.decoder, "param_spec",
                                         None))):
                self.watcher.expect = self.decoder.param_spec()
        self._swap_request = None
        self._prev_weights = None    # (snapshot, WeightSet) in probation
        self._probation_clean = 0
        self._probation_errors = 0
        self._draining = False
        self._drain_deadline_at = None
        self.stats = stats if stats is not None else {}
        self.stats.update({
            "latency_sec": {}, "done_at": {}, "admitted": 0,
            "chunks": 0, "chunk_size": self.decoder.chunk_size,
            "completed": 0, "errors": 0, "shed": 0, "expired": 0,
            "degraded": 0, "watchdog_fires": 0, "recovered": 0,
            # wire accounting (docs/data_plane.md): prompt bytes of
            # admitted requests as they cross to the device — int32
            # today; narrower token dtypes would show up here
            "request_wire_bytes": 0,
            # cross-request reuse counters (docs/serving.md "Prefix
            # cache & speculative decoding"): prefix-cache hits /
            # prompt tokens not re-prefilled / blocks evicted, and
            # draft-model accept accounting.  Per-JOB deltas — the
            # decoder's prefix cache and counters are shared across
            # jobs, so the engine snapshots them here and subtracts.
            "prefix_hits": 0, "prefix_tokens_saved": 0, "evictions": 0,
            "pressure_evictions": 0,
            "spec_accepted": 0, "spec_proposed": 0, "spec_accept_rate": 0.0,
            # serving lifecycle (docs/serving.md "Live weight swap &
            # rollback"): applied swaps / committed (survived the
            # probation window) / automatic rollbacks / in-flight
            # requests requeued across swaps / per-swap transaction
            # wall times / requests drained by drain(), and the live
            # weight generation tag
            "swaps": 0, "swap_commits": 0, "rollbacks": 0,
            "swap_requeued": 0, "swap_latency_sec": [], "drained": 0,
            # per-transition audit trail: {"event": "swap"|"rollback",
            # "step": ..., "requeued": {request idx: committed tokens
            # at the transition}} — what the swap-under-load e2e uses
            # to assert committed prefixes survive token-identically
            "swap_events": [],
            "weight_generation": int(getattr(
                self.decoder, "weight_generation", 0
            )),
            # paged KV plane (docs/serving.md "Paged KV & int4"):
            # which layout this decoder serves; pool gauges fold in
            # via _update_reuse_stats when the layout is paged
            "kv_layout": getattr(self.decoder, "kv_layout",
                                 "contiguous"),
            # cost attribution (docs/observability.md "Cost
            # attribution & usage ledger"): summed decode-chunk wall
            # time (the denominator the ledger's per-request
            # chip-second rows must sum back to) and tokens emitted
            # by completed requests
            "decode_wall_sec": 0.0, "tokens_out": 0,
            # disaggregation plane (docs/serving.md "Disaggregated
            # prefill/decode & TP sharding"): whether admits run
            # through a PrefillWorker, summed prefill-dispatch wall
            # (the ledger's prefill_chip_sec denominator), and
            # per-request submit→first-token wall — the raw-list
            # fallback mirroring latency_sec (serving.ttft_sec is the
            # authoritative percentile source)
            "disaggregated": self.disaggregate,
            "prefill_wall_sec": 0.0, "ttft_sec": {},
            # prefill fault containment (docs/fault_tolerance.md
            # "Disaggregated serving failure modes"): supervised
            # prefill dispatches abandoned / worker deaths contained /
            # worker rebuilds, and orphaned handoff leases the pool
            # reaper reclaimed (by owner after a fault, by deadline
            # from the serve loop)
            "prefill_watchdog_fires": 0, "prefill_worker_deaths": 0,
            "prefill_restarts": 0, "leases_reaped": 0,
        })
        self._reuse_base = dict(self._decoder_reuse_stats())
        # telemetry: metrics resolved ONCE (null singletons when
        # disabled — the hot path then costs nothing), spans per
        # request under trace id "req<idx>" (docs/observability.md)
        reg = telemetry.get_registry()
        self._tracer = telemetry.get_tracer()
        # usage ledger (telemetry/ledger.py): per-request resource
        # rows charged once per admit + once per decode CHUNK — far
        # off the per-token path; no-ops when telemetry is disabled
        from tensorflowonspark_tpu.telemetry import ledger as _ledger_mod

        self._ledger = _ledger_mod.get_ledger()
        # page-seconds currency: pages at the decoder's paged-KV page
        # size, else the radix block width, else the canonical
        # fingerprint block (prefix_cache.pages_for_tokens)
        from tensorflowonspark_tpu import prefix_cache as _pc

        pc = getattr(self.decoder, "prefix_cache", None)
        self._page_tokens = int(
            getattr(self.decoder, "_page_tokens", 0)
            or (pc.block_tokens if pc is not None else 0)
            or _pc.FINGERPRINT_TOKENS
        )
        # always-on flight recorder (ISSUE 11): watchdog fires and
        # swap rollbacks below freeze the recent rings into a dump
        # bundle (telemetry/blackbox.py; None when disabled)
        from tensorflowonspark_tpu.telemetry import blackbox as _blackbox

        _blackbox.install()
        self._m_lat = reg.histogram(LATENCY_METRIC)
        self._m_ttft = reg.histogram(TTFT_METRIC)
        self._m_queue_wait = reg.histogram("serving.queue_wait_sec")
        self._m = {
            name: reg.counter("serving." + name)
            for name in (
                "admitted", "completed", "errors", "shed", "expired",
                "degraded", "chunks", "watchdog_fires", "recovered",
                "prefix_hit_admits", "swaps", "swap_commits",
                "swap_rollbacks", "drained",
            )
        }
        self._m_chunk = {
            name: reg.counter("serving." + name)
            for name in ("moe_assignments", "moe_local_assignments",
                         "moe_experts_hit", "attn_read_tokens",
                         "attn_context_tokens")
        }
        self._m_gen = reg.gauge("serving.weight_generation")
        self._m_gen.set(self.stats["weight_generation"])
        # live re-planner sensors (ISSUE 18): admitted prompt lengths
        # feed the prompt-mix trigger; the paged-pool occupancy gauges
        # feed the kv_pages trigger — both readable fleet-wide through
        # the health plane's TimeSeriesStore
        self._m_prompt_tokens = reg.histogram("serving.prompt_tokens")
        self._m_pool = reg.gauge("serving.pool_pages")
        self._m_pool_used = reg.gauge("serving.pool_pages_used")
        # what the contiguous key/value banks hold, by kind: windowed
        # layers' rings beside whole banks (SlotDecoder.kv_bank_bytes;
        # nothing for pages, latent rows and tests' fakes)
        bank_bytes = getattr(self.decoder, "kv_bank_bytes", None)
        for kind, held in ((bank_bytes() if bank_bytes else None)
                           or {}).items():
            reg.gauge("serving.kv_bank_bytes_" + kind).set(int(held))
            self.stats["kv_bank_bytes_" + kind] = int(held)
        # scalar knob retunes, queued by request_retune() and applied
        # between decode chunks on the scheduling pass (ISSUE 18: the
        # live re-planner's safe seam for non-geometry knobs)
        self._retune_request = {}
        # on-demand device profiling: serving_builder config keys
        # profile_dir/profile_steps ride the predictor; decode chunks
        # count as steps (tensorboard.start_profile is a graceful
        # no-op on builds without the profiler)
        self._profile = None
        prof = getattr(predict, "profile", None)
        if prof and prof.get("dir"):
            from tensorflowonspark_tpu import tensorboard

            self._profile = tensorboard.start_profile(
                prof["dir"], prof.get("steps")
            )
        # scheduler state
        self._pending = []      # validated, waiting for a slot
        self._slot_req = {}     # slot -> in-flight request record
        self._rids = {}         # input idx -> trace id (emit marks)
        self._finished = {}     # input idx -> output row / record
        self._emit_next = 0
        self._n_in = 0
        self._exhausted = False
        self._idle_source = False
        self._chunk_index = 0
        self._t0 = self._clock()
        # fleet health plane: this engine's compact state rides the
        # /status exposition (telemetry/health.py; latest engine wins
        # the "serving" slot).  Registered through a weakref — every
        # continuous job builds an engine, and the provider registry
        # must never keep a finished job's decoder (and its params)
        # alive
        import weakref

        from tensorflowonspark_tpu.telemetry import health as _health

        _ref = weakref.ref(self)

        def _serving_status():
            eng = _ref()
            return (
                {"finished": True} if eng is None
                else eng.health_status()
            )

        _health.register_status_provider("serving", _serving_status)

    def load(self):
        """Lock-light load snapshot — the fleet router's placement
        signal (docs/serving.md "Fleet routing & rolling deploys").

        Plain host ints read straight off the scheduler state: no
        locks, no device syncs, and NO telemetry-registry traffic
        (the router polls this at dispatch rate; with telemetry
        disabled the call allocates nothing beyond the returned dict
        — asserted in tests/test_fleet.py).  ``/status`` exposes the
        same fields per engine via :meth:`health_status`.
        """
        in_flight = len(self._slot_req)
        slots = int(getattr(self.decoder, "num_slots", self.num_slots))
        pc = getattr(self.decoder, "prefix_cache", None)
        return {
            "slots": slots,
            "free_slots": max(0, slots - in_flight),
            "in_flight": in_flight,
            "queued": len(self._pending),
            "queue_depth": self.queue_depth,
            "prefix_blocks": len(pc) if pc is not None else 0,
            "weight_generation": self.stats["weight_generation"],
            "draining": self._draining,
        }

    def health_status(self):
        """Compact serving summary for the health plane's ``/status``
        route: live load (the same fields :meth:`load` snapshots for
        the fleet router), shed/deadline/watchdog accounting, and the
        weight-swap lifecycle state."""
        pc = getattr(self.decoder, "prefix_cache", None)
        return {
            "slots": getattr(self.decoder, "num_slots", None),
            "free_slots": max(
                0, int(getattr(self.decoder, "num_slots",
                               self.num_slots)) - len(self._slot_req)
            ),
            "in_flight": len(self._slot_req),
            "queued": len(self._pending),
            "queue_depth": self.queue_depth,
            "prefix_blocks": len(pc) if pc is not None else 0,
            "policy": self.policy,
            "draining": self._draining,
            "admitted": self.stats["admitted"],
            "completed": self.stats["completed"],
            "shed": self.stats["shed"],
            "expired": self.stats["expired"],
            "errors": self.stats["errors"],
            "watchdog_fires": self.stats["watchdog_fires"],
            "weight_generation": self.stats["weight_generation"],
            "swaps": self.stats["swaps"],
            "rollbacks": self.stats["rollbacks"],
            # cost row (ISSUE 14): what this engine burned and
            # produced — the fleet router surfaces one per replica
            # on /status
            "usage": {
                "chip_sec": round(self.stats["decode_wall_sec"], 6),
                "prefill_chip_sec": round(
                    self.stats["prefill_wall_sec"], 6
                ),
                "tokens_out": self.stats["tokens_out"],
                "prefix_tokens_saved": self.stats["prefix_tokens_saved"],
            },
        }

    # -- cross-request reuse accounting --------------------------------

    def _decoder_reuse_stats(self):
        """The decoder's cumulative reuse counters (prefix cache +
        speculative accepts); zeros for decoders without the surface
        (test fakes, older builders)."""
        fn = getattr(self.decoder, "reuse_stats", None)
        return fn() if callable(fn) else {}

    def _update_reuse_stats(self):
        """Fold the decoder's reuse counters into ``stats`` as
        per-job deltas (the decoder outlives the job)."""
        cur = self._decoder_reuse_stats()
        base = self._reuse_base
        for key in ("prefix_hits", "prefix_tokens_saved", "evictions",
                    "spec_accepted", "spec_proposed"):
            if key in cur:
                self.stats[key] = int(cur[key]) - int(base.get(key, 0))
        # paged-pool gauges are point-in-time occupancy, not
        # counters — surface the current values, no delta
        for key in ("pool_pages", "pool_pages_used",
                    "pool_pages_shared", "pool_pages_free"):
            if key in cur:
                self.stats[key] = int(cur[key])
        if "pool_pages" in cur:
            self._m_pool.set(int(cur["pool_pages"]))
            self._m_pool_used.set(int(cur.get("pool_pages_used", 0)))
        prop = self.stats.get("spec_proposed", 0)
        self.stats["spec_accept_rate"] = (
            self.stats.get("spec_accepted", 0) / float(prop)
            if prop else 0.0
        )

    # -- admission ------------------------------------------------------

    def _rid_of(self, row, idx):
        """The request's trace id: the mapped :data:`TRACE_INPUT`
        column when it carries a usable string (the fleet router's
        minted id — lenient here; :meth:`_validate` rejects junk with
        a typed error), else the engine-local ``req<idx>``."""
        if self.trace_col is not None and isinstance(row, dict):
            v = row.get(self.trace_col)
            if isinstance(v, str) and v:
                return v
        return "req%d" % idx

    def _validate(self, row, idx, rid=None):
        """Admission-time request validation; returns the request
        record or raises :class:`RequestValidationError` naming the
        request index and the offending column."""
        for col in sorted(self.input_mapping):
            if col not in row:
                raise RequestValidationError(
                    "request {0} is missing input column {1!r} (mapped "
                    "to predictor input {2!r}); present columns: "
                    "{3}".format(
                        idx, col, self.input_mapping[col],
                        sorted(row) if isinstance(row, dict) else type(row),
                    ),
                    kind="missing_input", request_index=idx,
                )
        try:
            prompt = np.asarray(row[self.prompt_col])
        except Exception as e:  # noqa: BLE001 - anything non-arrayable
            raise RequestValidationError(
                "request {0}: prompt column {1!r} is not array-like: "
                "{2}".format(idx, self.prompt_col, e),
                kind="bad_dtype", request_index=idx,
            )
        if prompt.dtype.kind not in "iu":
            raise RequestValidationError(
                "request {0}: prompt column {1!r} must hold integer "
                "token ids, got dtype {2}".format(
                    idx, self.prompt_col, prompt.dtype
                ),
                kind="bad_dtype", request_index=idx,
            )
        if prompt.ndim != 1:
            raise RequestValidationError(
                "request {0}: prompt column {1!r} must be 1-D, got "
                "shape {2}".format(idx, self.prompt_col, prompt.shape),
                kind="bad_shape", request_index=idx,
            )
        if prompt.shape[0] == 0:
            raise RequestValidationError(
                "request {0}: prompt column {1!r} is empty".format(
                    idx, self.prompt_col
                ),
                kind="empty_prompt", request_index=idx,
            )
        n = int(prompt.shape[0])
        if n + self.max_new > self.decoder.cache_len:
            raise RequestValidationError(
                "request {0}: prompt ({1} tokens) + max_new_tokens "
                "({2}) exceeds the engine cache_len={3}".format(
                    idx, n, self.max_new, self.decoder.cache_len
                ),
                kind="too_long", request_index=idx,
            )
        budget = self.max_new
        if self.budget_col is not None:
            try:
                budget = int(row[self.budget_col])
            except (TypeError, ValueError) as e:
                raise RequestValidationError(
                    "request {0}: budget column {1!r} is not an "
                    "integer: {2}".format(idx, self.budget_col, e),
                    kind="bad_budget", request_index=idx,
                )
            budget = max(1, min(budget, self.max_new))
        deadline = self.default_deadline
        if self.deadline_col is not None:
            try:
                deadline = float(row[self.deadline_col])
            except (TypeError, ValueError) as e:
                raise RequestValidationError(
                    "request {0}: deadline column {1!r} is not a "
                    "number: {2}".format(idx, self.deadline_col, e),
                    kind="bad_deadline", request_index=idx,
                )
        tenant = validate_tenant(
            row, idx, self.tenant_col
        ) if self.tenant_col is not None else None
        if self.trace_col is not None:
            tv = row[self.trace_col]
            if not isinstance(tv, str) or not tv:
                raise RequestValidationError(
                    "request {0}: trace column {1!r} must hold a "
                    "non-empty string trace id, got {2!r}".format(
                        idx, self.trace_col, tv
                    ),
                    kind="bad_trace", request_index=idx,
                )
        now = self._clock()
        return {
            "idx": idx,
            "rid": rid if rid is not None else self._rid_of(row, idx),
            TENANT_INPUT: tenant,
            "prompt": prompt.astype(np.int32, copy=False),
            "budget": budget,
            "eos_at": None,
            "out": None,
            "submit": now,
            # the same moment on the tracer's clock: where the
            # request's queue_wait span starts
            "t_submit": self._tracer.now(),
            "deadline_at": None if deadline is None else now + deadline,
        }

    def _record(self, idx, kind, message, tokens_done=0, partial=None):
        self._finished[idx] = error_record(
            kind, idx, message, tokens_done=tokens_done, partial=partial
        )

    def _ledger_settle(self, req, tokens_out=None, latency_sec=None,
                       close=True):
        """ONE ledger crossing per request: admission fields
        (tenant/tokens_in/wire/prefix/queue-wait) and decode cost
        (chip/page-seconds) accrue lock-free on the engine-local
        request record (:meth:`_admit_free` / :meth:`_run_chunk`) and
        settle here at the terminal point.  ``close=False`` is the
        fleet replica's WRECKAGE flush — a dead replica's spend stays
        attributed while the surviving replica continues the row
        (fleet/replica.py)."""
        self._ledger.settle(
            req["rid"], tenant=req.get(TENANT_INPUT),
            tokens_in=len(req["prompt"]),
            wire_bytes=req.pop("wire_bytes_acc", 0),
            prefix_tokens_saved=req.pop("prefix_saved_acc", 0),
            queue_wait_sec=req.pop("queue_wait_acc", 0.0),
            chip_sec=req.pop("chip_sec", 0.0),
            prefill_chip_sec=req.pop("prefill_chip_sec", 0.0),
            page_sec=req.pop("page_sec", 0.0),
            tokens_out=tokens_out, latency_sec=latency_sec,
            close=close,
        )

    def _ledger_close(self, req, tokens_out, latency_sec=None):
        self._ledger_settle(
            req, tokens_out=tokens_out, latency_sec=latency_sec
        )

    def _pull_one(self, it):
        """Pull + validate ONE row from the source; returns a request,
        or None when the source is exhausted.  Invalid rows become
        records (``on_error="record"``) and pulling continues.

        A source may yield ``None`` as a **heartbeat** ("no request
        available right now" — fleet replica feeds do this between
        arrivals, see fleet/replica.py): the pull returns empty
        WITHOUT marking the source exhausted, and the scheduler
        proceeds to its next decode chunk / lifecycle pass instead of
        blocking.  The source is expected to pace itself (block until
        a row arrives) whenever the engine is otherwise idle."""
        while not self._exhausted:
            try:
                with self._tracer.span(
                    "engine.pull", trace="engine",
                    chunk=self._chunk_index,
                ):
                    row = next(it)
            except StopIteration:
                self._exhausted = True
                return None
            if row is None:
                self._idle_source = True
                return None
            idx = self._n_in
            self._n_in += 1
            rid = self._rid_of(row, idx)
            self._rids[idx] = rid
            try:
                with self._tracer.span("admission", trace=rid):
                    return self._validate(row, idx, rid)
            except RequestValidationError as e:
                if self.on_error == "raise":
                    raise
                self.stats["errors"] += 1
                self._m["errors"].inc()
                self._ledger.settle(rid, tokens_out=0)
                self._record(idx, e.kind, e)
        return None

    def _refill(self, it):
        """Policy-dependent queue refill.

        ``block`` pulls nothing here — requests are pulled one per
        free slot at admission time, so the source iterator itself is
        the backpressure.  ``reject``/``degrade`` drain the source
        eagerly (every available request has *arrived*): ``reject``
        keeps ``queue_depth`` waiting and sheds the rest as typed
        records; ``degrade`` accepts everything and lets admission
        shrink budgets against the backlog.  A draining engine pulls
        nothing — admissions stopped."""
        if self.policy == "block" or self._draining:
            return
        # a free slot is admission capacity too: the refill runs just
        # before _admit_free, so counting only queue_depth would shed
        # requests a slot was about to take
        cap = self.queue_depth + len(self.decoder.free_slots())
        while not self._exhausted:
            if self.policy == "reject" and len(self._pending) >= cap:
                req = self._pull_one(it)
                if req is None:
                    return
                self.stats["shed"] += 1
                self._m["shed"].inc()
                self._tracer.mark(
                    "shed", trace=req["rid"], severity="warn",
                    request_index=req["idx"], trace_id=req["rid"],
                    queue_depth=self.queue_depth,
                )
                self._ledger_close(req, tokens_out=0)
                self._record(
                    req["idx"], "shed",
                    "request {0} shed: admission queue full "
                    "({1} waiting, depth {2}, policy 'reject')".format(
                        req["idx"], len(self._pending), self.queue_depth
                    ),
                )
                continue
            req = self._pull_one(it)
            if req is None:
                return
            self._pending.append(req)

    def _expire_pending(self):
        """Queued requests whose deadline passed before a slot freed
        expire in place (typed record, nothing dispatched)."""
        now = self._clock()
        keep = []
        for req in self._pending:
            if req["deadline_at"] is not None and now > req["deadline_at"]:
                self.stats["expired"] += 1
                self._m["expired"].inc()
                # a watchdog/swap-requeued request may already carry
                # committed tokens — the record keeps them
                committed = [t for t in (req["out"] or [])
                             if isinstance(t, int)]
                self._ledger_close(
                    req, tokens_out=len(committed),
                    latency_sec=now - req["submit"],
                )
                self._record(
                    req["idx"], "deadline",
                    "request {0} expired after {1:.3f}s waiting for a "
                    "slot (deadline {2:.3f}s)".format(
                        req["idx"], now - req["submit"],
                        req["deadline_at"] - req["submit"],
                    ),
                    tokens_done=len(committed), partial=committed,
                )
            else:
                keep.append(req)
        self._pending = keep

    def _admit_free(self, it):
        """Admit into every free slot: queued requests first, then
        (``block``) straight from the source.  A request whose slot
        prefill raises becomes an ``admit`` record (``on_error=
        "record"``) instead of killing the batch.  Returns True when
        at least one request was consumed (admitted OR recorded) —
        the scheduler's progress signal."""
        progressed = False
        for slot in self.decoder.free_slots():
            if self._draining:
                # only requeued IN-FLIGHT work (resume_prompt) may
                # re-enter a draining engine; fresh admissions stopped
                req = (
                    self._pending.pop(0)
                    if self._pending
                    and "resume_prompt" in self._pending[0] else None
                )
            else:
                req = self._pending.pop(0) if self._pending else (
                    self._pull_one(it) if self.policy == "block"
                    else None
                )
            if req is None:
                return progressed
            progressed = True
            if self.policy == "degrade" and "resume_prompt" not in req:
                # never re-shrink a watchdog-recovered request: its
                # committed prefix already counts against the budget
                backlog = len(self._pending)
                if backlog > self.queue_depth:
                    # backlog pressure gives back the cheapest memory
                    # FIRST: cold prefix-cache branches (unpinned LRU
                    # leaves, down to half the cache budget) are
                    # evicted before any request's token budget is
                    # shrunk — hot shared prefixes survive, and the
                    # freed HBM belongs to the slot table again
                    pc = getattr(self.decoder, "prefix_cache", None)
                    if pc is not None:
                        self.stats["pressure_evictions"] += pc.evict_cold(
                            pc.mem_budget_bytes // 2
                        )
                    shrunk = max(
                        self.degrade_floor,
                        (req["budget"] * self.queue_depth) // backlog,
                    )
                    if shrunk < req["budget"]:
                        req["budget"] = shrunk
                        self.stats["degraded"] += 1
                        self._m["degraded"].inc()
            prompt = req.get("resume_prompt", req["prompt"])
            rid = req["rid"]
            wait = self._clock() - req["submit"]
            self._m_queue_wait.observe(wait)
            if self._tracer.enabled:
                # queue wait ended the instant this admit pass reached
                # the request — record the interval just spent waiting
                self._tracer.add(
                    "queue_wait", req["t_submit"],
                    self._tracer.now() - req["t_submit"], trace=rid,
                )
            try:
                # admit is a single ASYNC dispatch; the first token
                # comes back as an unsynchronized device scalar,
                # resolved at the next chunk boundary.  Disaggregated
                # engines split it: the PrefillWorker's own program
                # runs the prompt, then adopt() hands the finished KV
                # to the decoder as a block-table exchange — the
                # request's trace id crosses both spans, so prefill
                # and decode merge into one story per request.
                t_admit0 = time.perf_counter()
                if self._prefill_worker is not None:
                    handoff = None
                    with self._tracer.span("prefill", trace=rid) as sp:
                        sp.set("disaggregated", True)
                        try:
                            handoff = self._prefill_dispatch(
                                prompt, rid
                            )
                        except self._prefill_fault_exc as e:
                            # contained prefill fault (worker died or
                            # its dispatch wedged past the watchdog):
                            # reap the orphaned lease, rebuild the
                            # worker, and re-prefill through the
                            # UNIFIED path — inside the same span, so
                            # the request's original trace id carries
                            # the whole recovery, and token-identical
                            # (the faulted prefill never drew an rng
                            # key or touched the donated cache)
                            self._contain_prefill_fault(e, rid)
                            first = self.decoder.admit(slot, prompt)
                            cached = int(getattr(
                                self.decoder,
                                "last_admit_cached_tokens", 0,
                            ))
                            sp.set("prefill_recovered", True)
                        else:
                            cached = int(handoff.cached_tokens)
                        sp.set("prefix_hit", cached > 0)
                        if cached:
                            sp.set("prefix_tokens", cached)
                            self._m["prefix_hit_admits"].inc()
                    if handoff is not None:
                        try:
                            with self._tracer.span("handoff", trace=rid):
                                first = self.decoder.adopt(slot, handoff)
                        except Exception:
                            # the abandon path: an un-adopted handoff
                            # must never leak its pool pages
                            self._prefill_worker.abandon(handoff)
                            raise
                        # zero-copy invariant: adoption is one state
                        # scatter, never a KV-copy program
                        assert int(getattr(
                            self.decoder, "last_adopt_dispatches", 1
                        )) == 1, "KV copy dispatched on the handoff path"
                else:
                    with self._tracer.span("prefill", trace=rid) as sp:
                        first = self.decoder.admit(slot, prompt)
                        sp.set("prompt_tokens", int(len(prompt)))
                        bucket_of = getattr(
                            self.decoder, "bucket_len", None)
                        if bucket_of is not None:
                            bucket = int(bucket_of(len(prompt)))
                            sp.set("bucket", bucket)
                            attn_of = getattr(
                                self.decoder, "prefill_attn", None)
                            if attn_of is not None:
                                sp.set("attn", attn_of(bucket))
                            pairs_of = getattr(
                                self.decoder, "prefill_pairs", None)
                            for kind, n in (
                                    pairs_of(bucket) if pairs_of else {}
                            ).items():
                                sp.set("attn_pairs_" + kind, n)
                        cached = int(getattr(
                            self.decoder, "last_admit_cached_tokens", 0
                        ))
                        sp.set("prefix_hit", cached > 0)
                        if cached:
                            sp.set("prefix_tokens", cached)
                            self._m["prefix_hit_admits"].inc()
                # prefill cost component (ledger prefill_chip_sec):
                # host wall of the prefill dispatch(es) — async, so
                # this is dispatch wall, not device occupancy; the
                # split-out field is what lets a disaggregated
                # engine's two programs attribute separately
                t_admit = time.perf_counter() - t_admit0
                self.stats["prefill_wall_sec"] += t_admit
                if self._ledger.enabled:
                    req["prefill_chip_sec"] = req.get(
                        "prefill_chip_sec", 0.0
                    ) + t_admit
            except Exception as e:  # noqa: BLE001 - per-request capture
                if self.on_error == "raise":
                    raise RequestError(
                        "request {0}: admission failed: {1}".format(
                            req["idx"], e
                        ),
                        kind="admit", request_index=req["idx"],
                    ) from e
                self.stats["errors"] += 1
                self._m["errors"].inc()
                if self._prev_weights is not None:
                    # a device-side failure inside the rollback window
                    # counts against the new generation (handled at
                    # the next scheduling pass)
                    self._probation_errors += 1
                self._ledger_close(req, tokens_out=0)
                self._record(req["idx"], "admit", e)
                continue  # the slot stays free for the next request
            committed = req["out"] or []
            req["out"] = list(committed) + [first]
            req["admit_len"] = int(len(prompt))
            req["admit_out"] = len(committed)
            self.stats["admitted"] += 1
            self._m["admitted"].inc()
            self._m_prompt_tokens.observe(float(len(prompt)))
            self.stats["request_wire_bytes"] += int(
                getattr(prompt, "nbytes", 0)
            )
            # usage-ledger stashes, settled in ONE ledger call at the
            # request's terminal point (_ledger_settle).  A watchdog/
            # swap REQUEUE keeps its original submit time, so its
            # "wait" includes decode already charged as chip time —
            # skip the queue-wait accrual for those.
            if self._ledger.enabled:
                req["wire_bytes_acc"] = req.get(
                    "wire_bytes_acc", 0
                ) + int(getattr(prompt, "nbytes", 0))
                if cached:
                    req["prefix_saved_acc"] = req.get(
                        "prefix_saved_acc", 0
                    ) + cached
                if "resume_prompt" not in req:
                    req["queue_wait_acc"] = req.get(
                        "queue_wait_acc", 0.0
                    ) + wait
            self._slot_req[slot] = req
        return progressed

    # -- prefill supervision / containment (docs/fault_tolerance.md
    # "Disaggregated serving failure modes") -------------------------

    def _prefill_dispatch(self, prompt, rid):
        """Run the disaggregated prefill, supervised by the prefill
        watchdog when one is armed.  ``rid`` stamps the pool handoff
        lease owner, so a fault mid-handoff is attributable and the
        lease reapable by owner.  A wedged dispatch that wakes after
        abandonment aborts itself (``abandoned_fn``) before touching
        the rng stream or the donated cache."""
        worker = self._prefill_worker
        wd = self._prefill_watchdog
        if wd is None:
            return worker.prefill(prompt, owner=rid)
        return wd.call(
            lambda: worker.prefill(
                prompt, owner=rid, abandoned_fn=lambda: wd.abandoned
            ),
            self.watchdog_timeout,
        )

    def _contain_prefill_fault(self, exc, rid):
        """A prefill died or wedged mid-handoff: reap its orphaned
        pool lease (refcounts balanced — the lease held exactly one
        reference per page), journal the fault at page severity (the
        flight recorder dumps), and rebuild the worker.  The caller
        re-prefills the stranded request through the unified path
        under its original trace id."""
        dead = not isinstance(exc, WatchdogTimeout)
        kind = (
            "prefill_worker_dead" if dead else "prefill_watchdog_fire"
        )
        if dead:
            self.stats["prefill_worker_deaths"] += 1
        else:
            self.stats["prefill_watchdog_fires"] += 1
        pool = getattr(self.decoder, "page_pool", None)
        reaped = []
        if pool is not None:
            reaped = pool.reap_orphans(owner=rid)
            self.stats["leases_reaped"] += len(reaped)
        pages = sum(r["pages"] for r in reaped)
        logger.warning(
            "prefill containment (%s) for request %s: %s — reaped %d "
            "lease(s) / %d page(s); re-prefilling through the "
            "unified path", kind, rid, exc, len(reaped), pages,
        )
        self._tracer.mark(
            kind, trace=rid, severity="page", error=str(exc),
            leases_reaped=len(reaped), pages_reclaimed=pages,
        )
        self.restart_prefill_worker(reason=kind)

    def restart_prefill_worker(self, reason="operator"):
        """Rebuild the PrefillWorker (and its watchdog) in place —
        the containment path's actuator, also exposed to the
        remediation engine's ``restart_prefill`` verb.  The compiled
        prefill program carries over (the fault fired before the
        dispatch, never inside it: an abandoned thread aborts at the
        fault gate), as do the chaos fault hook and its fired-entry
        state, so spent faults don't re-fire on the rebuilt worker."""
        old = self._prefill_worker
        if old is None:
            return None
        from tensorflowonspark_tpu.serving_disagg import PrefillWorker

        worker = PrefillWorker(
            self.decoder, fault_fn=old._fault,
            lease_deadline_sec=old.lease_deadline_sec,
        )
        worker._jit = old._jit
        worker._prefills = old._prefills
        self.decoder._prefill_worker = worker
        self._prefill_worker = worker
        if self.watchdog_timeout is not None:
            # never reuse a possibly-abandoned watchdog: its wedged
            # thread may still post a stale result
            if self._prefill_watchdog is not None:
                self._prefill_watchdog.close()  # no-op when abandoned
            self._prefill_watchdog = _DispatchWatchdog()
        self.stats["prefill_restarts"] += 1
        self._tracer.mark(
            "prefill_restart", trace="serve", severity="warn",
            reason=reason,
        )
        return worker

    def _maybe_reap(self):
        """Deadline sweep of the page pool's handoff leases, once per
        scheduling pass: a lease past its deadline has an owner that
        vanished without the supervised path noticing (chaos
        ``leak_lease``, a crashed caller) — reclaim it and journal at
        page severity, one ``lease_reaped`` event per lease."""
        pool = getattr(self.decoder, "page_pool", None)
        if pool is None:
            return
        reap = getattr(pool, "reap_orphans", None)
        if reap is None:
            return
        for r in reap():
            self.stats["leases_reaped"] += 1
            self._tracer.mark(
                "lease_reaped", trace="serve", severity="page",
                owner=r["owner"], lease=r["lease"], pages=r["pages"],
                age_sec=round(r["age_sec"], 3),
            )

    # -- decode + recovery ---------------------------------------------

    def _step_chunk(self, idx):
        """Dispatch chunk ``idx`` and wait for its tokens, under the
        watchdog when there is one; None when the watchdog fired (state
        already recovered).  The synchronising half is the span
        ``engine.chunk.wait``: from the moment the chunk had been
        dispatched (read on whichever thread dispatched it) until the
        scheduler holds the tokens."""
        wedge = self._wedge
        wd = self._watchdog
        tracer = self._tracer
        dispatch_chunk = getattr(self.decoder, "dispatch_chunk", None)
        dispatched = []

        def step():
            if wedge is not None:
                wedge(idx)
            if wd is not None and wd.abandoned:
                # the scheduler timed this dispatch out while the
                # fault gate held it; never touch the decoder from
                # the stale thread
                return None
            # a decoder with no split (tests' fakes): the whole step
            # is the wait
            split = dispatch_chunk is not None
            pending = dispatch_chunk() if split else None
            dispatched.append(tracer.now())
            return (self.decoder.resolve_chunk(pending) if split
                    else self.decoder.step_chunk())

        try:
            toks = step() if wd is None else wd.call(
                step, self.watchdog_timeout)
        except WatchdogTimeout as e:
            logger.warning("serving watchdog: %s — recovering "
                           "%d in-flight request(s)", e,
                           len(self._slot_req))
            self._recover()
            return None
        tracer.add(
            "engine.chunk.wait", dispatched[0],
            tracer.now() - dispatched[0], trace="engine", chunk=idx,
        )
        return toks

    def _kv_read_attrs(self):
        """What the chunk about to be dispatched reads of the KV
        banks, for its ``engine.chunk`` span and ``stats``: ``attn``
        (what the decoder's chunk program attends with),
        ``kv_read_tokens`` (positions per layer its first step reads,
        from this scheduler's own record of every request in flight),
        ``kv_bank_tokens`` (what the banks hold) and, summed over the
        layers, ``attn_read_tokens`` against ``attn_context_tokens``
        (positions read, index keys included, against positions live),
        and ``kv_read_ring`` / ``kv_read_whole``: the positions read
        summed over the layers that keep rings and over those whose
        banks are whole.  Nothing for a decoder that does not say
        (tests' fakes)."""
        reads = getattr(self.decoder, "kv_read_tokens", None)
        if reads is None:
            return {}
        live = [
            (req["admit_len"], len(req["out"]) - req["admit_out"])
            for req in self._slot_req.values()
        ]
        read, bank = reads(live)
        attrs = {"attn": self.decoder.attn_impl,
                 "kv_read_tokens": read, "kv_bank_tokens": bank}
        over_layers = getattr(self.decoder, "attn_read_tokens", None)
        if over_layers is not None:
            attrs["attn_read_tokens"], attrs["attn_context_tokens"] = (
                over_layers(live))
        by_kind = getattr(self.decoder, "kv_read_by_kind", None)
        for kind, n in (by_kind(live) if by_kind else {}).items():
            attrs["kv_read_" + kind] = n
        return attrs

    def _run_chunk(self):
        """One decode chunk under the watchdog; returns a
        ``(tokens [B, T], valid [B])`` pair — row ``r``'s tokens are
        ``tokens[r, :valid[r]]`` — or None when the watchdog fired
        (state already recovered).  SlotDecoder chunks return the
        pair natively (speculative chunks accept a VARIABLE token
        count per slot); bare ``[B, T]`` blocks from legacy/test
        decoders normalize to fully-valid rows."""
        idx = self._chunk_index
        self._chunk_index += 1
        kv = self._kv_read_attrs()
        self.stats.update(kv)
        t_chunk0 = time.perf_counter()
        with self._tracer.span(
            "engine.chunk", trace="engine", chunk=idx,
            live=len(self._slot_req), slots=self.num_slots, **kv
        ) as chunk_span:
            toks = self._step_chunk(idx)
            if toks is None:
                return None
            self.stats["chunks"] += 1
            self._m["chunks"].inc()
            # the expert counts the chunk program returned with its
            # tokens (decoders with sigmoid-routed layers only)
            moe = getattr(self.decoder, "last_chunk_counts", None) or {}
            for name, n in moe.items():
                chunk_span.set(name, n)
            for name, counter in self._m_chunk.items():
                counter.inc(moe.get(name, kv.get(name, 0)))
            if self._profile is not None:
                self._profile.step()
        dur = time.perf_counter() - t_chunk0
        self.stats["decode_wall_sec"] += dur
        if self._tracer.enabled:
            # one dispatch serves every in-flight lane: attribute the
            # SAME interval (engine.chunk's, timed once) to each
            # request's trace so a single request's trace stays
            # connected admission→…→emit
            for req in self._slot_req.values():
                self._tracer.add(
                    "decode_chunk", chunk_span.t0, chunk_span.dur,
                    trace=req["rid"], chunk=idx,
                )
        if self._slot_req and self._ledger.enabled:
            # cost attribution: the chunk's wall time apportioned by
            # live slot share (the per-request rows sum back to the
            # measured decode wall time), and the KV occupancy
            # integral — pages held × chunk duration — as
            # page-seconds (docs/observability.md).  Accrued on the
            # engine-LOCAL request record (plain float adds, no
            # locks) and flushed to the ledger ONCE at the request's
            # terminal point (:meth:`_ledger_flush`) — per-chunk
            # ledger traffic would be the one place this plane could
            # tax the decode cadence.
            share = dur / len(self._slot_req)
            for req in self._slot_req.values():
                ctx = req.get("admit_len", len(req["prompt"])) + len(
                    req["out"] or ()
                )
                req["chip_sec"] = req.get("chip_sec", 0.0) + share
                req["page_sec"] = req.get("page_sec", 0.0) + (
                    pages_for_tokens(ctx, self._page_tokens) * dur
                )
        self._update_reuse_stats()
        if isinstance(toks, tuple):
            return toks
        return toks, None

    def _teardown_and_requeue(self, mark_event):
        """The PR 4 teardown/re-admit mechanism, shared by the
        watchdog (unplanned wedges) and the hot-swap path (PLANNED
        generation changes): every in-flight request's committed
        prefix is preserved, appended to its prompt, and the pair
        re-prefills into a fresh slot — greedy decode resumes exactly
        where the last *synchronized* chunk left it (the lost chunk's
        tokens and any unresolved first-token scalar are dropped).
        Re-admitted requests go to the FRONT of the queue in input
        order; their deadlines keep running."""
        inflight = sorted(
            self._slot_req.values(), key=lambda r: r["idx"]
        )
        self._slot_req.clear()
        self.decoder.reset()
        for req in inflight:
            committed = [t for t in (req["out"] or [])
                         if isinstance(t, int)]
            req["out"] = committed
            req["resume_prompt"] = (
                np.concatenate(
                    [req["prompt"],
                     np.asarray(committed, np.int32)]
                ) if committed else req["prompt"]
            )
            self._tracer.mark(
                mark_event, trace=req["rid"],
                severity=(
                    "warn" if mark_event == "watchdog_recover" else "info"
                ),
                request_index=req["idx"], trace_id=req["rid"],
                tokens_committed=len(committed),
            )
        self._pending[:0] = inflight
        return inflight

    def _recover(self):
        """Tear the engine down after a wedged dispatch and re-admit
        every in-flight request from its already-committed tokens
        (:meth:`_teardown_and_requeue` — token-identical
        continuations, the same masked-prefill invariant the
        continuous/static parity tests pin down)."""
        self.stats["watchdog_fires"] += 1
        self._m["watchdog_fires"].inc()
        if self._prev_weights is not None:
            # a wedge inside the probation window counts against the
            # new generation — roll back at the next scheduling pass
            self._probation_errors += 1
        self._tracer.mark(
            "watchdog_fire", trace="serve", severity="page",
            inflight=len(self._slot_req), chunk=self._chunk_index - 1,
        )
        recovered = self._teardown_and_requeue("watchdog_recover")
        self.stats["recovered"] += len(recovered)
        for _ in recovered:
            self._m["recovered"].inc()
        self._watchdog = _DispatchWatchdog()

    # -- live weight swap / rollback (hot_swap.py) ---------------------

    def request_swap(self, params, step=None, draft_params=None):
        """Queue a MANUAL weight swap (no watcher needed — tests,
        benches, in-process republish).  Applied between decode
        chunks at the next scheduling pass, with the same quiesce /
        canary / rollback contract as a watcher-discovered swap."""
        if not callable(getattr(self.decoder, "swap_weights", None)):
            raise ValueError(
                "live weight hot-swap needs a decoder exposing "
                "swap_weights/snapshot_weights (transformer."
                "serving_builder generation decoders do); this "
                "predictor's decoder has none"
            )
        from tensorflowonspark_tpu import hot_swap

        self._swap_request = hot_swap.WeightSet(
            self.stats["weight_generation"] + 1 if step is None
            else step,
            "<request_swap>", params, draft_params=draft_params,
        )

    def _set_generation(self):
        gen = int(getattr(self.decoder, "weight_generation", 0))
        self.stats["weight_generation"] = gen
        self._m_gen.set(gen)
        return gen

    # -- live scalar retunes (ISSUE 18) --------------------------------

    #: the knobs request_retune may change: host-side scalars whose
    #: swap needs no quiesce — geometry (slots, kv_pages, chunk_size)
    #: goes through the hot-swap/quiesce seam instead
    RETUNABLE = ("watchdog_timeout", "default_deadline", "queue_depth")

    def request_retune(self, **knobs):
        """Queue scalar knob changes; applied between decode chunks
        at the next scheduling pass (the live re-planner's engine
        seam).  Unknown knobs raise immediately — a retune must never
        silently no-op."""
        bad = sorted(set(knobs) - set(self.RETUNABLE))
        if bad:
            raise ValueError(
                "retunable engine knobs are {0}; got {1}".format(
                    self.RETUNABLE, bad
                )
            )
        self._retune_request.update(knobs)

    def _maybe_retune(self):
        """Apply queued scalar retunes between chunks, one journal
        event per applied batch (forensics: 'why did the config
        change?' — the re-planner's evidence rides the replan event;
        this one records the application point)."""
        if not self._retune_request:
            return
        knobs, self._retune_request = self._retune_request, {}
        applied = {}
        for name, value in knobs.items():
            old = getattr(self, name)
            if name == "queue_depth":
                value = max(1, int(value))
            elif value is not None:
                value = float(value)
            setattr(self, name, value)
            if name == "watchdog_timeout":
                self._watchdog = (
                    _DispatchWatchdog() if value is not None else None
                )
                if self._prefill_worker is not None:
                    self._prefill_watchdog = (
                        _DispatchWatchdog() if value is not None
                        else None
                    )
            applied[name] = {"old": old, "new": value}
        self._tracer.mark(
            "engine_retune", trace="planner", severity="info",
            knobs=applied,
        )

    def _quarantine(self, w, kind, message):
        if self.watcher is not None and w.path != "<request_swap>":
            self.watcher.quarantine_step(w, kind, message)

    def _maybe_swap(self):
        """One scheduling-pass check of the lifecycle plane: roll
        back first if the probation window accumulated errors, then
        apply at most one pending swap.  Runs between chunks only —
        never concurrently with a dispatch."""
        if self._prev_weights is not None and self._probation_errors:
            self._rollback(
                "{0} device-side error(s)/wedge(s) within the first "
                "{1} requests of the new generation".format(
                    self._probation_errors, self.rollback_window
                )
            )
        if self._draining:
            return  # a draining engine is shutting down; don't churn
        w, self._swap_request = self._swap_request, None
        if w is None and self.watcher is not None:
            w = self.watcher.poll()
        if w is not None:
            self._apply_swap(w)

    def _apply_swap(self, w):
        """The swap transaction, between decode chunks: quiesce
        in-flight requests through the watchdog teardown/re-admit
        path (admissions queue behind the bounded admission plane
        meanwhile — the drain gate), install the new generation
        (re-quantized on ingest for int8 deployments), run the
        post-install canary, and arm the rollback window.  The
        previous weights stay RESIDENT until the window closes."""
        t0 = time.perf_counter()
        with self._tracer.span("swap", trace="swap", step=w.step):
            requeued = self._teardown_and_requeue("swap_requeue")
            self.stats["swap_requeued"] += len(requeued)
            self.stats["swap_events"].append({
                "event": "swap", "step": w.step,
                "requeued": {r["idx"]: len(r["out"]) for r in requeued},
            })
            snapshot = self.decoder.snapshot_weights()
            try:
                self.decoder.swap_weights(w.params, w.draft_params)
            except Exception as e:  # noqa: BLE001 - typed quarantine
                # a mismatch that slipped past (or never saw) the
                # watcher's validation: nothing was installed, serving
                # continues on the old generation
                logger.warning("hot-swap: install of step %s refused: "
                               "%s", w.step, e)
                self._quarantine(w, "shape_mismatch", e)
                return
            ok = True
            if self.swap_canary:
                try:
                    ok = self.decoder.canary_check() is not False
                except Exception:  # noqa: BLE001 - canary is a verdict
                    ok = False
            if not ok:
                self.decoder.restore_weights(snapshot)
                self.stats["rollbacks"] += 1
                self._m["swap_rollbacks"].inc()
                self._quarantine(
                    w, "canary_failed",
                    "post-install canary failed for step {0}; rolled "
                    "back to the previous generation".format(w.step),
                )
                self._tracer.mark(
                    "swap_rollback", trace="swap", severity="page",
                    step=w.step, reason="canary_failed",
                )
                self._set_generation()
                return
        self._prev_weights = (snapshot, w)
        self._probation_clean = 0
        self._probation_errors = 0
        self.stats["swaps"] += 1
        self._m["swaps"].inc()
        dt = time.perf_counter() - t0
        self.stats["swap_latency_sec"].append(round(dt, 6))
        gen = self._set_generation()
        self._tracer.mark(
            "swap_apply", trace="swap", step=w.step, generation=gen,
            requeued=len(requeued), latency_sec=round(dt, 6),
        )
        logger.info(
            "hot-swap: step %s serving as generation %d (%d in-flight "
            "requeued, %.1fms)", w.step, gen, len(requeued), 1e3 * dt,
        )

    def _note_clean_completion(self):
        """A completed request under probation; ``rollback_window``
        of them commit the swap (previous weights released)."""
        if self._prev_weights is None:
            return
        self._probation_clean += 1
        if self._probation_clean >= self.rollback_window:
            _snapshot, w = self._prev_weights
            self._prev_weights = None
            self.stats["swap_commits"] += 1
            self._m["swap_commits"].inc()
            self._tracer.mark(
                "swap_commit", trace="swap", step=w.step,
                clean_requests=self._probation_clean,
            )

    def _rollback(self, why):
        """Automatic rollback: re-quiesce in-flight requests (their
        committed prefixes — possibly spanning both generations —
        are preserved), restore the resident previous weights, and
        quarantine the offending step so the watcher never re-offers
        it."""
        snapshot, w = self._prev_weights
        self._prev_weights = None
        self._probation_errors = 0
        requeued = self._teardown_and_requeue("swap_requeue")
        self.stats["swap_requeued"] += len(requeued)
        self.stats["swap_events"].append({
            "event": "rollback", "step": w.step,
            "requeued": {r["idx"]: len(r["out"]) for r in requeued},
        })
        self.decoder.restore_weights(snapshot)
        self.stats["rollbacks"] += 1
        self._m["swap_rollbacks"].inc()
        self._quarantine(
            w, "rollback",
            "rolled back from step {0}: {1}".format(w.step, why),
        )
        gen = self._set_generation()
        self._tracer.mark(
            "swap_rollback", trace="swap", severity="page",
            step=w.step, generation=gen, reason=why,
        )
        logger.warning(
            "hot-swap: rolled back step %s -> generation %d (%s)",
            w.step, gen, why,
        )

    # -- graceful drain ------------------------------------------------

    def drain(self, deadline=None):
        """Begin a graceful drain: admissions STOP (block-policy
        sources are no longer pulled; queued requests that never got
        a slot return typed ``drained`` records at their positions),
        in-flight requests run to completion, and past ``deadline``
        seconds the stragglers are cancelled between chunks with
        typed records carrying their committed tokens.  The
        :meth:`serve` generator then finishes even if the source has
        more rows.  This is the same quiesce machinery the hot-swap
        path runs for the length of one swap transaction
        (:meth:`_apply_swap`) — drain simply never re-opens the
        gate."""
        self._draining = True
        if deadline is not None:
            self._drain_deadline_at = self._clock() + float(deadline)

    def _drain_pending(self):
        """Queued requests that never reached a slot exit as typed
        ``drained`` records; watchdog/swap-requeued IN-FLIGHT work
        (``resume_prompt``) stays — it re-admits so committed tokens
        are never lost."""
        keep = []
        for req in self._pending:
            if "resume_prompt" in req:
                keep.append(req)
                continue
            self.stats["drained"] += 1
            self._m["drained"].inc()
            self._ledger_close(req, tokens_out=0)
            self._record(
                req["idx"], "drained",
                "request {0} drained: engine stopped admissions "
                "before a slot freed".format(req["idx"]),
                tokens_done=0, partial=[],
            )
        self._pending = keep

    def _drain_cancel_slots(self, now):
        """Drain-deadline expiry: cancel every in-flight lane with a
        typed record carrying its committed tokens (the slot-level
        cancellation path — neighbors would be unaffected, nothing
        recompiles)."""
        for slot, req in list(self._slot_req.items()):
            committed = [t for t in req["out"] if isinstance(t, int)]
            self.stats["drained"] += 1
            self._m["drained"].inc()
            self._ledger_close(
                req, tokens_out=len(committed),
                latency_sec=now - req["submit"],
            )
            self._record(
                req["idx"], "drained",
                "request {0} cancelled by drain deadline; {1} "
                "token(s) completed".format(req["idx"], len(committed)),
                tokens_done=len(committed), partial=committed,
            )
            self.decoder.cancel(slot)
            del self._slot_req[slot]

    # -- consume / finalize --------------------------------------------

    def _consume(self, req, chunk_row):
        """Fold a slot's chunk tokens into its request; True when the
        request completed (first eos, or its budget).  The trailing
        element of ``out`` may be the admit dispatch's unresolved
        device scalar — resolving it here is the sync the chunk pull
        already paid for."""
        out = req["out"]
        if out and not isinstance(out[-1], int):
            last = int(np.asarray(out[-1]))
            out[-1] = last
            if "ttft" not in req:
                # first-token latency, stamped where the admit's async
                # device scalar actually resolves — the number the
                # prefill/decode split is designed to bound, with the
                # trace id as the histogram exemplar
                ttft = self._clock() - req["submit"]
                req["ttft"] = ttft
                self.stats["ttft_sec"][req["idx"]] = ttft
                self._m_ttft.observe(ttft, exemplar=req["rid"])
            if self.eos_id is not None and last == self.eos_id:
                req["eos_at"] = len(out) - 1
        for t in (() if chunk_row is None else chunk_row):
            if req["eos_at"] is not None or len(out) >= req["budget"]:
                break
            out.append(int(t))
            if self.eos_id is not None and int(t) == self.eos_id:
                req["eos_at"] = len(out) - 1
        return req["eos_at"] is not None or len(out) >= req["budget"]

    def _finalize(self, req, t_done):
        arr = np.full((self.max_new,), self._fill, np.int32)
        toks = req["out"][:self.max_new]
        arr[:len(toks)] = toks
        gen_len = (
            req["eos_at"] if req["eos_at"] is not None else req["budget"]
        )
        out = {"generated": arr}
        if self._emit_len:
            out["generated_len"] = np.int32(gen_len)
        self._finished[req["idx"]] = apply_output_mapping(
            out, self.output_mapping
        )
        lat = t_done - req["submit"]
        self.stats["completed"] += 1
        self.stats["tokens_out"] += int(gen_len)
        self.stats["latency_sec"][req["idx"]] = lat
        self.stats["done_at"][req["idx"]] = t_done - self._t0
        self._m["completed"].inc()
        # the latency observation carries the request's TRACE id as
        # its exemplar: a p99 bucket then names a concrete request
        # whose merged trace `forensics explain` can pull (ISSUE 14)
        self._m_lat.observe(lat, exemplar=req["rid"])
        self._ledger_close(req, tokens_out=int(gen_len), latency_sec=lat)
        self._note_clean_completion()

    def _expire_slot(self, slot, req, now):
        """Cancel an expired in-flight lane between chunks; neighbors
        keep decoding undisturbed and nothing recompiles."""
        committed = [t for t in req["out"] if isinstance(t, int)]
        self.stats["expired"] += 1
        self._m["expired"].inc()
        self._tracer.mark(
            "deadline_cancel", trace=req["rid"],
            severity="warn",
            request_index=req["idx"], trace_id=req["rid"],
            tokens_done=len(committed),
        )
        self._ledger_close(
            req, tokens_out=len(committed),
            latency_sec=now - req["submit"],
        )
        self._record(
            req["idx"], "deadline",
            "request {0} cancelled after {1:.3f}s (deadline "
            "{2:.3f}s); {3} token(s) completed".format(
                req["idx"], now - req["submit"],
                req["deadline_at"] - req["submit"], len(committed),
            ),
            tokens_done=len(committed), partial=committed,
        )
        self.decoder.cancel(slot)
        del self._slot_req[slot]

    def _drain_ready(self):
        """Stream completed rows in input order as soon as the head of
        the reorder buffer is ready."""
        while self._emit_next in self._finished:
            self._tracer.mark(
                "emit",
                trace=self._rids.pop(
                    self._emit_next, "req%d" % self._emit_next
                ),
            )
            yield self._finished.pop(self._emit_next)
            self._emit_next += 1

    # -- the scheduling loop -------------------------------------------

    def _emit_ready(self, chunk):
        """Yield the rows that are ready, in input order.  The time
        suspended at each ``yield`` is the consumer's: the span
        ``engine.yielded``, recorded after the fact so that it is never
        the parent of a span the consumer opens on this thread."""
        tracer = self._tracer
        for r in self._drain_ready():
            t0 = tracer.now()
            yield r
            tracer.add(
                "engine.yielded", t0, tracer.now() - t0,
                trace="engine", chunk=chunk,
            )

    def serve(self, rows):
        """Run the engine over ``rows``; yields output rows/records in
        input order.  Fills ``self.stats`` with ``latency_sec`` /
        ``done_at`` (per completed request), ``admitted`` / ``chunks``
        / ``completed`` counters, and the robustness counters
        ``errors`` / ``shed`` / ``expired`` / ``degraded`` /
        ``watchdog_fires`` / ``recovered``."""
        it = iter(rows)
        tracer = self._tracer
        try:
            while True:
                # one pass: every phase is a span of the trace
                # "engine", tagged with the pass's chunk index
                chunk = self._chunk_index
                # lifecycle plane first: probation rollback, then at
                # most one validated swap per pass — both run between
                # chunks, never concurrently with a dispatch
                with tracer.span("engine.lifecycle", trace="engine",
                                 chunk=chunk):
                    self._maybe_swap()
                    self._maybe_retune()
                    self._maybe_reap()
                    self._refill(it)
                    self._expire_pending()
                    if self._draining:
                        self._drain_pending()
                with tracer.span("engine.admit", trace="engine",
                                 chunk=chunk):
                    progressed = self._admit_free(it)
                yield from self._emit_ready(chunk)
                if not self._slot_req:
                    if self._draining:
                        # drained: nothing in flight, nothing may be
                        # admitted — the job is over regardless of
                        # what the source still holds
                        yield from self._emit_ready(chunk)
                        return
                    if self._pending or not self._exhausted:
                        if progressed:
                            # every admit this pass failed into records
                            # (on_error="record"); requests are still
                            # being consumed — keep scheduling
                            continue
                        if self._idle_source:
                            # the source is alive but momentarily dry
                            # (it yielded a None heartbeat — a fleet
                            # replica feed between arrivals); it paces
                            # itself by blocking, so looping back to
                            # the lifecycle pass is not a spin
                            self._idle_source = False
                            continue
                        # nothing in flight, nothing consumable: only
                        # reachable with zero slots; guard against an
                        # impossible-progress spin
                        raise RuntimeError(
                            "continuous scheduler cannot make progress "
                            "(no slots available)"
                        )
                    yield from self._emit_ready(chunk)
                    return
                block = self._run_chunk()
                if block is None:
                    continue  # watchdog fired; state already recovered
                toks, valid = block
                with tracer.span("engine.consume", trace="engine",
                                 chunk=chunk):
                    t_chunk = self._clock()
                    for slot, req in list(self._slot_req.items()):
                        row = (
                            toks[slot] if valid is None
                            else toks[slot][:int(valid[slot])]
                        )
                        if self._consume(req, row):
                            self._finalize(req, t_chunk)
                            self.decoder.evict(slot)
                            del self._slot_req[slot]
                        elif (req["deadline_at"] is not None
                              and t_chunk > req["deadline_at"]):
                            self._expire_slot(slot, req, t_chunk)
                    if (self._draining
                            and self._drain_deadline_at is not None
                            and t_chunk > self._drain_deadline_at):
                        self._drain_cancel_slots(t_chunk)
                yield from self._emit_ready(chunk)
        finally:
            self._update_reuse_stats()
            if self._profile is not None:
                self._profile.stop()
            if self._watchdog is not None:
                self._watchdog.close()
            if self._prefill_watchdog is not None:
                self._prefill_watchdog.close()
            if self._own_watcher and self.watcher is not None:
                self.watcher.close()
