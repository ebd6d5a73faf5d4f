"""Single-host batch serving path + CLI.

Re-designed from the reference's JVM serving stack — ``TFModel.scala``
(per-executor singleton ``SavedModelBundle`` cache + Row→Tensor→Row
conversion, reference: src/main/scala/com/yahoo/tensorflowonspark/
TFModel.scala:24-29,51-239,257-281) and the ``Inference.scala`` CLI
(reference: Inference.scala:27-79).  The TPU equivalents:

- a *serving export* is an orbax params directory plus ``metadata.json``
  written by :func:`tensorflowonspark_tpu.checkpoint.save_for_serving`
  (the SavedModel role);
- the "graph" half of a SavedModel is a **predictor builder**: a plain
  function ``builder(params, config) -> predict`` where
  ``predict(batch: dict[str, np.ndarray]) -> dict[str, np.ndarray]``.
  It is named in the export metadata as ``model_ref``
  (``"pkg.module:attr"``) so a bare export directory is self-describing
  the way a SavedModel is, or passed directly as a callable;
- batches are padded to a fixed ``batch_size`` so the jitted predict
  compiles once (XLA static shapes), then outputs are truncated — the
  TFMU-friendly version of the reference's per-batch ``session.run``;
- the CLI reads TFRecords through the native codec
  (:mod:`tensorflowonspark_tpu.data.tfrecord` backed by
  ``native/tfrecord_codec.cc``) and writes JSON lines, mirroring
  ``Inference --export_dir --input --schema_hint --input_mapping
  --output_mapping --output`` (reference: Inference.scala:30-44).

Run the CLI with ``python -m tensorflowonspark_tpu.serving ...``.
"""

import importlib
import itertools
import json
import logging
import os
import time

import numpy as np

from tensorflowonspark_tpu import serving_engine
# re-exported robustness surface (see serving_engine / docs/serving.md
# "Robustness & overload") + the shared latency accounting (ISSUE 7:
# BOTH schedules observe submit→finish into ONE telemetry histogram,
# so p50/p99 report identical semantics — docs/observability.md)
from tensorflowonspark_tpu.serving_engine import (  # noqa: F401
    LATENCY_METRIC,
    RequestError,
    RequestValidationError,
    ServingEngine,
    ServingError,
    WatchdogTimeout,
    error_record,
    latency_histogram,
    latency_summary,
)

logger = logging.getLogger(__name__)

#: Per-process predictor cache keyed by (export_dir, builder digest) —
#: the reference cached one SavedModelBundle per executor JVM
#: (TFModel.scala:24-29,257-263) / one session per python worker
#: (pipeline.py:492-496).
_PREDICTOR_CACHE = {}


def _builder_key(builder):
    """Content digest of a builder callable, stable across pickling —
    ``id()`` would miss on every per-job unpickled copy and can collide
    after GC address reuse."""
    if builder is None:
        return None
    import hashlib

    try:
        import cloudpickle as _cp

        return hashlib.sha256(_cp.dumps(builder)).hexdigest()
    except Exception:  # noqa: BLE001 - unpicklable builder: don't cache
        return object()  # unique → never a cache hit


def resolve_ref(ref):
    """Resolve a ``"pkg.module:attr"`` reference string to the object."""
    module_name, _, attr = ref.partition(":")
    if not module_name or not attr:
        raise ValueError(
            "model_ref must look like 'pkg.module:attr', got {0!r}".format(ref)
        )
    obj = importlib.import_module(module_name)
    for part in attr.split("."):
        obj = getattr(obj, part)
    return obj


def with_preprocess(predict, preprocess):
    """Fuse an ON-DEVICE preprocess stage in front of ``predict``.

    ``preprocess`` (a callable or a
    :func:`~tensorflowonspark_tpu.data.preprocess.make_preprocess`
    kwargs dict) is jitted and applied to the assembled batch before
    the predictor — so rows kept in their narrow wire dtype (uint8
    pixels) cross the host→device link narrow and widen in HBM
    (docs/data_plane.md), instead of the host pre-inflating the batch
    to float32.  Predictor batch-shape attributes (``column_padding``,
    ``pad_multiple``, ``pad_cap``, ``make_slot_decoder``) are carried
    over; note the continuous schedule drives ``make_slot_decoder``
    directly, so a preprocess stage applies to the STATIC schedule and
    non-generation predictors.
    """
    import jax

    from tensorflowonspark_tpu.data import preprocess as pp_mod

    pre = jax.jit(pp_mod.resolve_preprocess(preprocess))

    def wrapped(batch):
        return predict(pre(batch))

    for attr in (
        "column_padding", "pad_multiple", "pad_cap", "make_slot_decoder"
    ):
        if hasattr(predict, attr):
            setattr(wrapped, attr, getattr(predict, attr))
    return wrapped


def _preprocess_key(preprocess):
    """Cache-key component for a preprocess argument: dict specs key by
    their (sorted) contents, callables by content digest."""
    if preprocess is None:
        return None
    if isinstance(preprocess, dict):
        return json.dumps(preprocess, sort_keys=True, default=str)
    return _builder_key(preprocess)


def load_predictor(export_dir, builder=None, use_cache=True,
                   preprocess=None, config_overrides=None):
    """Load a serving export and return its ``predict`` callable.

    Args:
      export_dir: directory written by
        :func:`~tensorflowonspark_tpu.checkpoint.save_for_serving`.
      builder: optional ``builder(params, config) -> predict`` override;
        defaults to the export metadata's ``model_ref``.
      use_cache: reuse a previously built predictor for the same export
        (the per-process singleton the reference kept,
        TFModel.scala:257-263).
      preprocess: optional on-device preprocess fused in front of the
        predictor (see :func:`with_preprocess`) — a callable or a
        ``make_preprocess`` kwargs dict.  Defaults to the export
        metadata's ``"preprocess"`` key, so an export can declare its
        own wire contract ("ship me uint8, I widen on device"):
        ``save_for_serving(..., extra_metadata={"preprocess":
        {"scale": 1/255}})``.  Pass ``False`` to disable even the
        metadata-declared stage (the caller widens on the host).
      config_overrides: optional dict laid over the export metadata's
        ``model_config`` before the builder runs — deployment-time
        knobs that don't belong in the export (prefix-cache sizing,
        ``draft_config`` toggles, ``chunk_size``...).  Exposed on the
        Spark pipeline as ``TFModel.setModelConfig`` (pipeline.py).
    """
    key = (
        os.path.abspath(os.fspath(export_dir)),
        _builder_key(builder),
        _preprocess_key(preprocess),
        json.dumps(config_overrides, sort_keys=True, default=str)
        if config_overrides else None,
    )
    if use_cache and key in _PREDICTOR_CACHE:
        return _PREDICTOR_CACHE[key]

    from tensorflowonspark_tpu.checkpoint import load_for_serving

    params, meta = load_for_serving(export_dir)
    if builder is None:
        ref = meta.get("model_ref")
        if not ref:
            raise ValueError(
                "export {0} has no model_ref metadata and no builder was "
                "given; write it via save_for_serving(..., extra_metadata="
                "{{'model_ref': 'pkg.module:builder'}})".format(export_dir)
            )
        builder = resolve_ref(ref)
    model_config = dict(meta.get("model_config") or {})
    if config_overrides:
        model_config.update(config_overrides)
    predict = builder(params, model_config)
    if preprocess is None:
        preprocess = meta.get("preprocess")
    if preprocess is not None and preprocess is not False:
        predict = with_preprocess(predict, preprocess)
    if use_cache:
        _PREDICTOR_CACHE[key] = predict
    return predict


# ----------------------------------------------------------------------
# batched row prediction (Row -> device array -> Row, the
# batch2tensors/tensors2batch role, TFModel.scala:51-239)
# ----------------------------------------------------------------------


def _stack_column(values, column=None):
    """Stack uniform rows into one batch array.  Ragged rows used to
    die deep inside ``np.stack`` with a shapeless error; now the
    ValueError NAMES the offending rows — the common trip-wire is the
    speculative generation predictor, which takes uniform-length
    batches only (no ``column_padding`` — see docs/inference.md
    "Speculative decoding")."""
    arrs = [np.asarray(v) for v in values]
    shapes = {a.shape for a in arrs}
    if len(shapes) > 1:
        majority = max(shapes, key=lambda s: sum(
            1 for a in arrs if a.shape == s
        ))
        ragged = [
            (i, a.shape) for i, a in enumerate(arrs)
            if a.shape != majority
        ][:8]
        raise ValueError(
            "cannot stack ragged rows for input {0}: batch majority "
            "shape is {1} but row(s) {2} differ.  This predictor "
            "declares no padding for this input — uniform-length rows "
            "only (speculative generation serving is the usual case; "
            "see docs/inference.md)".format(
                repr(column) if column else "batch", majority, ragged
            )
        )
    return np.stack(arrs)


def _stack_ragged_left(values, pad_value, multiple=1, cap=None):
    """Stack ragged 1-D rows by LEFT-padding to the batch max length
    (rounded up to ``multiple`` — shape BUCKETING, so the jitted
    generate program retraces once per bucket instead of once per
    unique prompt length); returns ``(stacked [n, max_len],
    pad_counts [n] int32)``.  Left-padding keeps every row's real
    tokens ending at the same position, so the compiled decode scan
    starts uniformly (the model masks the pad slots via
    ``pad_start``).  ``cap`` bounds the BUCKETED length (generation
    predictors set it to ``max_seq_len - max_new_tokens``): rounding
    up must never push prompts that fit past the cache capacity; a
    row genuinely longer than ``cap`` still stacks at its own length
    and fails downstream with the model's capacity error."""
    arrs = [np.asarray(v) for v in values]
    if any(a.ndim != 1 for a in arrs):
        raise ValueError(
            "ragged padding supports 1-D token rows; got shapes %s"
            % ([a.shape for a in arrs],)
        )
    raw_max = max(a.shape[0] for a in arrs)
    max_len = ((raw_max + multiple - 1) // multiple) * multiple
    if cap is not None:
        max_len = max(raw_max, min(max_len, int(cap)))
    pads = np.asarray([max_len - a.shape[0] for a in arrs], np.int32)
    out = np.full((len(arrs), max_len), pad_value, arrs[0].dtype)
    for i, a in enumerate(arrs):
        if a.shape[0]:
            out[i, max_len - a.shape[0]:] = a
    return out, pads


def predict_rows(
    predict,
    rows,
    input_mapping,
    output_mapping=None,
    batch_size=128,
    pad_to_batch=True,
    schedule="static",
    stats=None,
    on_error="raise",
    queue_depth=None,
    policy="block",
    watchdog_timeout=None,
    default_deadline=None,
    checkpoint_dir=None,
    watcher=None,
    rollback_window=8,
    replicas=1,
    replica_policy="least_loaded",
    fleet_queue_depth=None,
):
    """Run ``predict`` over dict-rows; yields output dict-rows.

    Args:
      predict: ``fn(batch: dict) -> dict`` of batched arrays.
      rows: iterable of dict rows.
      input_mapping: ``{column: input_name}`` — which row columns feed
        which predictor inputs (reference: TFParams.scala:27-33).
      output_mapping: ``{output_name: column}`` for the emitted rows;
        defaults to the predictor's own output names.
      batch_size: rows per predict call (reference default 128,
        TFParams.scala:14-18); in continuous mode, the number of
        in-flight KV-cache SLOTS.  ``"auto"`` reads the planner's
        chosen slot count off ``predict.plan`` (predictors built with
        ``config={"auto": ...}`` — docs/autotune.md); ``schedule=
        "auto"`` likewise picks continuous when the predictor
        supports it.
      pad_to_batch: zero-pad the final short batch so the jitted
        predict never sees a new shape (outputs are truncated back).
      schedule: ``"static"`` (fixed-size batches — every row in a
        batch pays the batch's full decode) or ``"continuous"``
        (in-flight batching for GENERATION predictors: finished rows
        are evicted and queued rows admitted into the freed KV-cache
        slots between chunked decode scans; requires a predictor
        exposing ``make_slot_decoder``, see
        ``transformer.serving_builder(mode="generate")`` and
        docs/serving.md).
      stats: optional dict the continuous scheduler fills with
        per-request latency accounting (``latency_sec`` in input
        order, plus admitted/evicted and robustness counters).
        Cross-request reuse counters
        land here too: ``prefix_hits`` / ``prefix_tokens_saved`` /
        ``evictions`` / ``pressure_evictions`` when the export enables
        the prefix cache, and ``spec_accepted`` / ``spec_proposed`` /
        ``spec_accept_rate`` when a draft model drives speculative
        chunks (docs/serving.md "Prefix cache & speculative
        decoding").  Exports with ``kv_layout: "paged"`` additionally
        report ``kv_layout`` and the page-pool occupancy gauges
        (``pool_pages`` / ``pool_pages_used`` / ``pool_pages_shared``
        — docs/serving.md "Paged KV & int4").
      on_error: ``"raise"`` (fail fast; admission errors name the
        request index and offending column) or ``"record"`` (poison
        isolation: a bad row yields a typed error record at its input
        position instead of killing the batch — see
        :func:`serving_engine.error_record` and docs/serving.md
        "Robustness & overload").
      queue_depth / policy / watchdog_timeout / default_deadline:
        continuous-only overload knobs, forwarded to
        :class:`~tensorflowonspark_tpu.serving_engine.ServingEngine`
        (bounded admission queue with ``block | reject | degrade``
        shedding, per-request deadlines, and the decode watchdog).
      checkpoint_dir / watcher / rollback_window: continuous-only
        LIFECYCLE knobs (docs/serving.md "Live weight swap &
        rollback"): a step-numbered export root (``publish_for_
        serving`` layout) or a pre-built
        :class:`~tensorflowonspark_tpu.hot_swap.CheckpointWatcher`
        arms validated live weight hot-swap between decode chunks —
        zero dropped requests, previous weights resident until
        ``rollback_window`` clean requests, automatic rollback on
        canary failure or a post-swap error spike.
      replicas / replica_policy / fleet_queue_depth: FLEET knobs
        (continuous only — docs/serving.md "Fleet routing & rolling
        deploys").  ``replicas > 1`` serves the job through a
        :class:`~tensorflowonspark_tpu.fleet.router.FleetRouter` over
        N engine replicas (each with its own slot decoder and radix
        cache, ``batch_size`` slots apiece): ``replica_policy`` picks
        the dispatch policy (``least_loaded`` / ``prefix_affinity`` /
        ``weighted_rr`` / ``random``), ``policy`` becomes the
        FLEET-level admission policy (pressure spills to a sibling
        replica before any single engine sheds), and
        ``fleet_queue_depth`` bounds the fleet admission queue.
        Outputs stay token-identical to a single-engine run and in
        input order; a replica death mid-decode re-dispatches its
        in-flight requests from their committed tokens.
    """
    # engine-side planner picks (ISSUE 18): a predictor built with
    # config={"auto": ...} carries predict.plan — "auto" here reads
    # the chosen slot count / schedule off it instead of a hand-set
    # number (zero knobs end to end)
    if batch_size == "auto" or schedule == "auto":
        chosen = (getattr(predict, "plan", None) or {}).get("chosen", {})
        if batch_size == "auto":
            batch_size = int(chosen.get("batch_size") or 128)
        if schedule == "auto":
            schedule = (
                "continuous"
                if hasattr(predict, "make_slot_decoder") else "static"
            )
    if schedule not in ("static", "continuous"):
        raise ValueError(
            "schedule must be 'static' or 'continuous', got %r"
            % (schedule,)
        )
    if on_error not in serving_engine.ON_ERROR:
        raise ValueError(
            "on_error must be one of %s, got %r"
            % (serving_engine.ON_ERROR, on_error)
        )
    if int(replicas or 1) > 1:
        if schedule != "continuous":
            raise ValueError(
                "replicas > 1 needs schedule='continuous' — the fleet "
                "router dispatches over slot-scheduler engines (see "
                "docs/serving.md)"
            )
        if checkpoint_dir is not None or watcher is not None:
            raise ValueError(
                "checkpoint_dir/watcher are single-engine lifecycle "
                "knobs; fleet weight changes go through rolling "
                "deploys (FleetRouter.start_rolling_deploy — see "
                "docs/serving.md 'Fleet routing & rolling deploys')"
            )
        from tensorflowonspark_tpu.fleet.router import predict_rows_fleet

        for r in predict_rows_fleet(
            predict, rows, input_mapping, output_mapping, batch_size,
            replicas=int(replicas), stats=stats, on_error=on_error,
            queue_depth=queue_depth, policy=policy,
            watchdog_timeout=watchdog_timeout,
            default_deadline=default_deadline,
            replica_policy=replica_policy,
            fleet_queue_depth=fleet_queue_depth,
        ):
            yield r
        return
    if schedule == "continuous":
        for r in _predict_rows_continuous(
            predict, rows, input_mapping, output_mapping, batch_size,
            stats, on_error=on_error, queue_depth=queue_depth,
            policy=policy, watchdog_timeout=watchdog_timeout,
            default_deadline=default_deadline,
            checkpoint_dir=checkpoint_dir, watcher=watcher,
            rollback_window=rollback_window,
        ):
            yield r
        return
    if (policy != "block" or queue_depth is not None
            or watchdog_timeout is not None
            or default_deadline is not None
            or checkpoint_dir is not None or watcher is not None):
        raise ValueError(
            "queue_depth/policy/watchdog_timeout/default_deadline/"
            "checkpoint_dir/watcher are continuous-schedule knobs; "
            "the static schedule has no admission queue or swap plane "
            "(see docs/serving.md)"
        )
    cols = sorted(input_mapping)
    buf = []  # ("ok", row) | ("rec", error_record) entries, input order
    n_seen = 0
    # static-schedule latency accounting: a request's latency is
    # submit (pulled from the source) → its row emitted — the SAME
    # semantics the continuous engine reports, observed into the
    # shared histogram (serving_engine.LATENCY_METRIC) and mirrored
    # into stats["latency_sec"] like the continuous scheduler's
    lat_hist = latency_histogram()
    submit_t = {}
    if stats is not None:
        stats.setdefault("latency_sec", {})
    # cost attribution (docs/observability.md "Cost attribution &
    # usage ledger"): the static schedule records one ledger row per
    # request too — tenant (reserved TENANT_INPUT column, validated
    # like the continuous path), tokens in/out, latency.  Rows key by
    # a per-job prefix so the process-wide ledger never collides
    # across jobs.
    from tensorflowonspark_tpu.telemetry import ledger as _ledger_mod

    _ledger = _ledger_mod.get_ledger()
    _job = "sj%d-" % next(_STATIC_JOB_SEQ)
    tenant_col = next(
        (c for c in input_mapping
         if input_mapping[c] == serving_engine.TENANT_INPUT), None
    )
    prompt_cols = [
        c for c in input_mapping
        if input_mapping[c] in (
            getattr(predict, "column_padding", None) or {}
        )
    ]
    tenants = {}
    # generation predictors declare ragged columns (prompts of varying
    # length) via ``predict.column_padding = {input_name: pad_value}``;
    # those stack left-padded and ship a ``<input>_pad`` count column
    # the model uses to mask the pad slots
    column_padding = getattr(predict, "column_padding", None) or {}

    def _assemble(chunk_rows, n_pad):
        batch = {}
        for c in cols:
            name = input_mapping[c]
            values = [r[c] for r in chunk_rows]
            if name in column_padding:
                batch[name], batch[name + "_pad"] = _stack_ragged_left(
                    values, column_padding[name],
                    getattr(predict, "pad_multiple", 1),
                    cap=getattr(predict, "pad_cap", None),
                )
            else:
                batch[name] = _stack_column(values, column=name)
        n = len(chunk_rows)
        if pad_to_batch and n < n_pad:
            batch = {
                k: np.concatenate(
                    [v, np.zeros((n_pad - n,) + v.shape[1:], v.dtype)]
                )
                for k, v in batch.items()
            }
        return batch

    def _predict_batch(chunk_rows):
        out = predict(_assemble(chunk_rows, batch_size))
        return {
            k: np.asarray(v)[:len(chunk_rows)] for k, v in out.items()
        }

    def _flush(chunk):
        ok = [(i, row) for i, (tag, row, _) in enumerate(chunk)
              if tag == "ok"]
        per_row = {}
        out = None
        if ok:
            try:
                out = _predict_batch([row for _, row in ok])
            except Exception as e:  # noqa: BLE001 - poison isolation
                if on_error == "raise":
                    raise
                # a poisoned row can kill batch ASSEMBLY (ragged
                # shapes) or the predict call itself; isolate it by
                # re-running each row alone (same padded batch shape,
                # so nothing recompiles) and record only the rows
                # that individually fail
                logger.warning(
                    "batch of %d rows failed (%s); isolating "
                    "per-row", len(ok), e,
                )
                for pos, row in ok:
                    idx = chunk[pos][2]
                    try:
                        per_row[pos] = ("out", _predict_batch([row]))
                    except Exception as re:  # noqa: BLE001
                        per_row[pos] = ("rec", serving_engine.error_record(
                            "predict", idx,
                            "request {0} failed in predict: "
                            "{1}".format(idx, re),
                        ))
        ok_pos = {p: i for i, (p, _) in enumerate(ok)}
        for pos, (tag, payload, _idx) in enumerate(chunk):
            if tag == "rec":
                yield _idx, payload
            elif out is not None:
                i = ok_pos[pos]
                yield _idx, _apply_output_mapping(
                    {k: v[i] for k, v in out.items()}, output_mapping
                )
            else:
                kind, o = per_row[pos]
                if kind == "rec":
                    yield _idx, o
                else:
                    yield _idx, _apply_output_mapping(
                        {k: v[0] for k, v in o.items()}, output_mapping
                    )

    def _emit(flushed):
        for idx, r in flushed:
            rid = _job + "req%d" % idx
            t_sub = submit_t.pop(idx, None)
            lat = None
            if t_sub is not None:
                lat = time.monotonic() - t_sub
                # the trace-id exemplar rides the shared histogram so
                # tail buckets name a concrete request (ISSUE 14)
                lat_hist.observe(lat, exemplar=rid)
                if stats is not None:
                    stats["latency_sec"][idx] = lat
            if _ledger.enabled:
                toks_out = 0
                if isinstance(r, dict) and "error" not in r:
                    if "generated_len" in r:
                        toks_out = int(np.asarray(r["generated_len"]))
                    elif "generated" in r:
                        toks_out = int(np.asarray(r["generated"]).size)
                _ledger.record(
                    rid, tenant=tenants.pop(idx, None),
                    tokens_in=tokens_in.pop(idx, 0),
                    tokens_out=toks_out, latency_sec=lat,
                )
            yield r

    tokens_in = {}
    for row in rows:
        idx = n_seen
        n_seen += 1
        submit_t[idx] = time.monotonic()
        try:
            tenant = _validate_static_row(
                row, idx, input_mapping, tenant_col
            )
            buf.append(("ok", row, idx))
            if _ledger.enabled and isinstance(row, dict):
                if tenant is not None:
                    tenants[idx] = tenant
                if prompt_cols:
                    try:
                        tokens_in[idx] = int(
                            np.asarray(row[prompt_cols[0]]).size
                        )
                    # tfoslint: disable=TFOS005(tokens_in accounting is best-effort; a ragged cell must never fail the request)
                    except Exception:  # noqa: BLE001 - accounting only
                        pass
        except serving_engine.RequestValidationError as e:
            if on_error == "raise":
                raise
            buf.append((
                "rec", serving_engine.error_record(e.kind, idx, e), idx
            ))
        if len(buf) == batch_size:
            for r in _emit(_flush(buf)):
                yield r
            buf = []
    if buf:
        for r in _emit(_flush(buf)):
            yield r


#: per-process static-job sequence (ledger row namespacing)
_STATIC_JOB_SEQ = itertools.count(1)


def _validate_static_row(row, idx, input_mapping, tenant_col=None):
    """Static-schedule admission validation: every mapped input column
    must be present — a missing key used to surface as a bare
    ``KeyError`` from deep inside the batch flush; now the error names
    the request index and the missing column at admission.  A mapped
    reserved ``tenant`` column is validated here too (the SAME rule as
    the continuous engine: non-empty string, typed ``bad_tenant``
    error naming the request index and offending value)."""
    for col in sorted(input_mapping):
        if col not in row:
            raise serving_engine.RequestValidationError(
                "request {0} is missing input column {1!r} (mapped to "
                "predictor input {2!r}); present columns: {3}".format(
                    idx, col, input_mapping[col],
                    sorted(row) if isinstance(row, dict) else type(row),
                ),
                kind="missing_input", request_index=idx,
            )
    if tenant_col is not None:
        return serving_engine.validate_tenant(row, idx, tenant_col)
    return None


def _apply_output_mapping(out, output_mapping):
    if not output_mapping:
        return out
    missing = [n for n in output_mapping if n not in out]
    if missing:
        raise KeyError(
            "output_mapping names {0} not produced by the predictor "
            "(outputs: {1})".format(missing, sorted(out))
        )
    return {col: out[name] for name, col in output_mapping.items()}


#: reserved input names (re-exported from serving_engine): a row
#: column mapped to BUDGET_INPUT carries that request's token budget
#: (evicted after ``min(max_new, budget)`` tokens even without eos);
#: one mapped to DEADLINE_INPUT carries its deadline in seconds; one
#: mapped to TENANT_INPUT carries its tenant key for the usage ledger
#: (validated on BOTH schedules — non-string/empty values are typed
#: ``bad_tenant`` errors naming the request); TRACE_INPUT carries an
#: explicit request trace id (the fleet router mints one per request
#: when the caller doesn't)
BUDGET_INPUT = serving_engine.BUDGET_INPUT
DEADLINE_INPUT = serving_engine.DEADLINE_INPUT
TENANT_INPUT = serving_engine.TENANT_INPUT
TRACE_INPUT = serving_engine.TRACE_INPUT


def _predict_rows_continuous(predict, rows, input_mapping,
                             output_mapping, num_slots, stats,
                             on_error="raise", queue_depth=None,
                             policy="block", watchdog_timeout=None,
                             default_deadline=None, checkpoint_dir=None,
                             watcher=None, rollback_window=8):
    """Continuous in-flight batching over a generation predictor.

    The scheduling loop lives in
    :class:`~tensorflowonspark_tpu.serving_engine.ServingEngine` (the
    overload-safe serving layer: bounded admission queue with
    ``block | reject | degrade`` shedding, per-request deadlines with
    slot-level cancellation, poison isolation via ``on_error``, and a
    decode watchdog with in-flight recovery — see docs/serving.md
    "Robustness & overload").  A request queue feeds ``num_slots``
    KV-cache slots; decode runs in compiled chunks
    (:class:`~tensorflowonspark_tpu.models.transformer.SlotDecoder`),
    and BETWEEN chunks finished rows (first eos, the row's budget, or
    an expired deadline) are evicted and queued prompts admitted into
    the freed lanes — so a short row never pays a long neighbor's
    decode.  Rows are yielded in INPUT order (completion order is
    recorded in ``stats``); outputs are token-identical to the static
    ``generate`` path per request (parity-tested)."""
    engine = serving_engine.ServingEngine(
        predict, input_mapping, output_mapping, num_slots,
        queue_depth=queue_depth, policy=policy,
        default_deadline=default_deadline,
        watchdog_timeout=watchdog_timeout, on_error=on_error,
        stats=stats, checkpoint_dir=checkpoint_dir, watcher=watcher,
        rollback_window=rollback_window,
    )
    for r in engine.serve(rows):
        yield r


def infer_output_schema(predict, sample_row, input_mapping,
                        output_mapping=None):
    """Derive the output DataFrame schema of ``predict`` by running ONE
    row through :func:`predict_rows` — at EXPORT time, so the schema
    can be written into the serving metadata
    (``save_for_serving(..., output_schema=...)``) and the
    distributed transform never has to run its legacy one-row probe
    job (which evaluates the predictor over a whole partition-0 batch
    and throws the results away — a full compiled decode, twice, for
    generation exports; see pipeline.TFModel._transform_native).

    Returns an interchange field list ``[(column, type_str), ...]``.
    """
    from tensorflowonspark_tpu.pipeline import _infer_output_type

    out = next(iter(predict_rows(
        predict, [sample_row], input_mapping, output_mapping,
        batch_size=1,
    )))
    return [(name, _infer_output_type(out[name])) for name in sorted(out)]


# ----------------------------------------------------------------------
# CLI (Inference.scala equivalent)
# ----------------------------------------------------------------------


def _parse_mapping(text):
    """Accept JSON (``{"col":"x"}``) or ``col=x,col2=y`` shorthand."""
    text = text.strip()
    if text.startswith("{"):
        return json.loads(text)
    out = {}
    for part in text.split(","):
        k, _, v = part.partition("=")
        if not _:
            raise ValueError("mapping entries must be key=value: " + part)
        out[k.strip()] = v.strip()
    return out


def _json_default(o):
    if isinstance(o, np.ndarray):
        return o.tolist()
    if isinstance(o, (np.floating, np.integer)):
        return o.item()
    if isinstance(o, bytes):
        return o.decode("utf-8", "replace")
    raise TypeError("not JSON serializable: {0}".format(type(o)))


def main(argv=None):
    """Batch-inference CLI (reference: Inference.scala:27-79): load a
    serving export, read TFRecords, write predictions as JSON lines."""
    import argparse

    p = argparse.ArgumentParser(
        prog="tensorflowonspark_tpu.serving",
        description="Batch inference over TFRecords with a serving export",
    )
    p.add_argument("--export_dir", required=True,
                   help="serving export directory (save_for_serving output)")
    p.add_argument("--input", required=True,
                   help="TFRecord file or directory of shards")
    p.add_argument("--schema_hint", default=None,
                   help="struct<name:type,...> schema for the input records")
    p.add_argument("--input_mapping", required=True,
                   help="JSON or col=input,... mapping of record columns "
                        "to predictor inputs")
    p.add_argument("--output_mapping", default=None,
                   help="JSON or output=col,... mapping of predictor "
                        "outputs to result columns")
    p.add_argument("--output", required=True,
                   help="output directory for JSON-line part files")
    p.add_argument("--batch_size", type=int, default=128)
    p.add_argument("--schedule", choices=("static", "continuous"),
                   default="static",
                   help="batching schedule: 'static' fixed-size "
                        "batches, or 'continuous' in-flight batching "
                        "for generation exports (slot-level KV-cache "
                        "scheduler; batch_size = in-flight slots — "
                        "see docs/serving.md)")
    p.add_argument("--on_error", choices=serving_engine.ON_ERROR,
                   default="raise",
                   help="per-request failure policy: 'raise' fails "
                        "fast naming the request, 'record' isolates "
                        "poison rows as typed error records")
    p.add_argument("--policy", choices=serving_engine.POLICIES,
                   default="block",
                   help="continuous admission policy under overload: "
                        "block (backpressure), reject (shed past the "
                        "queue bound), degrade (shrink token budgets "
                        "against the backlog)")
    p.add_argument("--queue_depth", type=int, default=None,
                   help="continuous admission-queue bound "
                        "(default 2x slots)")
    p.add_argument("--watchdog_timeout", type=float, default=None,
                   help="seconds before a wedged decode chunk is "
                        "abandoned and in-flight requests are "
                        "re-admitted from their committed tokens")
    p.add_argument("--deadline", type=float, default=None,
                   help="default per-request deadline in seconds "
                        "(expired requests return a typed record "
                        "with their partial tokens)")
    p.add_argument("--checkpoint_dir", default=None,
                   help="step-numbered serving-export root "
                        "(publish_for_serving layout) to watch for "
                        "live weight hot-swaps during the job "
                        "(continuous schedule only)")
    p.add_argument("--checkpoint_poll", type=float, default=5.0,
                   help="seconds between checkpoint_dir scans")
    p.add_argument("--rollback_window", type=int, default=8,
                   help="clean requests a swapped-in generation must "
                        "serve before the previous weights are "
                        "released (automatic rollback inside it)")
    p.add_argument("--replicas", type=int, default=1,
                   help="serve through a fleet of N engine replicas "
                        "behind the router (continuous schedule only; "
                        "batch_size slots per replica — see "
                        "docs/serving.md 'Fleet routing & rolling "
                        "deploys')")
    p.add_argument("--replica_policy", default="least_loaded",
                   choices=("least_loaded", "prefix_affinity",
                            "weighted_rr", "random"),
                   help="fleet dispatch policy: least_loaded (replica "
                        "load snapshots), prefix_affinity (shared "
                        "prompt prefixes land on the replica whose "
                        "radix cache holds them), weighted_rr, random")
    p.add_argument("--fleet_queue_depth", type=int, default=None,
                   help="fleet admission-queue bound (default: the "
                        "summed replica capacity)")
    p.add_argument("--tp", type=int, default=None,
                   help="tensor-parallel degree: shard weights and the "
                        "paged KV pool over a {'model': N} mesh (the "
                        "whole predictor becomes ONE logical replica "
                        "spanning N chips; see docs/serving.md "
                        "'Disaggregated prefill/decode & TP sharding')")
    p.add_argument("--disaggregate", action="store_true",
                   help="split prefill and decode into separate jitted "
                        "programs with a zero-copy paged-KV handoff "
                        "(needs kv_layout='paged'; bounds TTFT/p99 "
                        "under mixed prompt lengths)")
    args = p.parse_args(argv)

    from tensorflowonspark_tpu.data import interchange
    from tensorflowonspark_tpu.utils.compile_cache import (
        ensure_compile_cache,
    )

    ensure_compile_cache()
    rows, schema = interchange.load_tfrecords(
        args.input, schema=args.schema_hint
    )
    logger.info("loaded %d rows (schema: %s)", len(rows),
                interchange.format_schema(schema))
    overrides = {}
    if args.tp:
        overrides["tp"] = args.tp
    if args.disaggregate:
        overrides["disaggregate"] = True
    predict = load_predictor(
        args.export_dir, config_overrides=overrides or None
    )
    input_mapping = _parse_mapping(args.input_mapping)
    output_mapping = (
        _parse_mapping(args.output_mapping) if args.output_mapping else None
    )

    from tensorflowonspark_tpu.utils import fs as fs_utils

    fs_utils.makedirs(args.output)
    out_path = fs_utils.join(args.output, "part-00000.jsonl")
    count = 0
    sched_stats = {}
    lat_base = latency_histogram().snapshot()
    with fs_utils.open_file(out_path, "w") as f:
        kwargs = {}
        if args.schedule == "continuous":
            kwargs = dict(
                queue_depth=args.queue_depth, policy=args.policy,
                watchdog_timeout=args.watchdog_timeout,
                default_deadline=args.deadline,
                rollback_window=args.rollback_window,
            )
            if args.replicas > 1:
                kwargs.update(
                    replicas=args.replicas,
                    replica_policy=args.replica_policy,
                    fleet_queue_depth=args.fleet_queue_depth,
                )
            if args.checkpoint_dir:
                from tensorflowonspark_tpu import hot_swap

                kwargs["watcher"] = hot_swap.CheckpointWatcher(
                    args.checkpoint_dir,
                    poll_interval=args.checkpoint_poll,
                )
        for out_row in predict_rows(
            predict, rows, input_mapping, output_mapping,
            args.batch_size, schedule=args.schedule, stats=sched_stats,
            on_error=args.on_error, **kwargs
        ):
            f.write(json.dumps(out_row, default=_json_default) + "\n")
            count += 1
    shed = sched_stats.get("shed", 0) + sched_stats.get("expired", 0)
    if shed or sched_stats.get("errors"):
        logger.warning(
            "robustness: %d shed/expired, %d error record(s), "
            "%d watchdog fire(s)", shed,
            sched_stats.get("errors", 0),
            sched_stats.get("watchdog_fires", 0),
        )
    if sched_stats.get("swaps") or sched_stats.get("rollbacks"):
        logger.info(
            "lifecycle: %d weight swap(s) (%d committed, %d rolled "
            "back), %d in-flight request(s) requeued across swaps, "
            "serving generation %d",
            sched_stats.get("swaps", 0),
            sched_stats.get("swap_commits", 0),
            sched_stats.get("rollbacks", 0),
            sched_stats.get("swap_requeued", 0),
            sched_stats.get("weight_generation", 0),
        )
    # p50/p99 come from the SHARED telemetry histogram, scoped to this
    # run — identical semantics on both schedules (the old code
    # computed continuous-only percentiles from a raw list)
    summ = latency_summary(since=lat_base)
    if summ["count"]:
        logger.info(
            "%s schedule: %d request(s)%s, per-request latency "
            "p50=%.1fms p99=%.1fms",
            args.schedule, summ["count"],
            " over %d chunks" % sched_stats["chunks"]
            if sched_stats.get("chunks") else "",
            summ["p50_ms"], summ["p99_ms"],
        )
    logger.info("wrote %d predictions to %s", count, out_path)
    return count


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    main()
