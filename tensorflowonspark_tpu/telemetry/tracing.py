"""Structured span tracing with id propagation + Chrome-trace export.

A *span* is one timed region with a name, a trace id (the request /
step it belongs to), a span id, and the enclosing span's id — enough
to reconstruct the tree.  Two recording styles:

- ``with tracer.span("prefill", trace="req3", prefix_hit=True): ...``
  — context-managed, parent id propagated through a thread-local
  stack;
- ``tracer.add("decode_chunk", t0, dur, trace="req3", chunk=2)`` —
  post-hoc, for hot loops that time once and attribute the SAME
  interval to several traces (the serving engine labels one chunk
  dispatch onto every in-flight request's trace this way); ``t0`` is
  a reading of :meth:`Tracer.now`, the one clock call sites use.

**One clock.**  A span's ``t0`` is seconds on the clock
``jax.profiler`` stamps its events with — the Unix epoch, what
``time.time_ns()`` reads (checked on the CPU and on a TPU v5e: an
exported ``.xplane.pb`` holds its events relative to the session's
start and that start, in Unix ns, as ``profile_start_time`` on its
``Task Environment`` plane; :func:`profile_start_ns` reads it).  ``dur``
comes from the monotonic clock.  A context-managed span also enters a
``jax.profiler.TraceAnnotation`` named ``tfos.<span name>``, so a
profiler capture (``tensorboard.start_profile``) shows the scheduler
beside the device, and the same region can be read from the ring and
from the capture.  :func:`attribute` says which span covers each piece
of a set of intervals (the device's idle gaps, say).

:func:`watch_jit` puts JAX's own set-up in the same ring: the backend's
creation (``setup.device``) and every outermost trace, lowering and
compile (``jit.trace`` / ``jit.lower`` / ``jit.compile``), with JAX's own
starts and ends.

Export is Chrome-trace JSON (``{"traceEvents": [...]}``) loadable in
``chrome://tracing`` / Perfetto; ``ts`` is Unix microseconds.

Disabled mode (``TFOS_TELEMETRY=0`` or ``set_enabled(False)``):
``span()`` returns a shared null context manager and ``add`` is a
no-op — nothing allocates, nothing is retained.
"""

import collections
import itertools
import json
import logging
import os
import sys
import threading
import time

from tensorflowonspark_tpu.telemetry import registry as _registry

#: Bounded span store per tracer: keeps the newest spans, drops the
#: oldest — a serving process must never grow without bound.
MAX_SPANS = int(os.environ.get("TFOS_TRACE_MAX_SPANS", "20000"))


class _NullSpan(object):
    """Shared no-op context manager for disabled tracing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):
        pass


_NULL_SPAN = _NullSpan()

#: how long :meth:`Tracer.now` runs on the monotonic clock before it
#: reads the wall clock again: a stepped wall clock (the profiler
#: follows it) is followed within this
ANCHOR_REFRESH_NS = 1000000000


def _annotation(name):
    """A ``jax.profiler.TraceAnnotation`` for ``name`` — the null span
    in a process that has not imported jax: it has no profiler to
    annotate for, and nothing is imported on its behalf."""
    profiler = sys.modules.get("jax.profiler")
    return _NULL_SPAN if profiler is None else profiler.TraceAnnotation(name)


class _SpanCtx(object):
    """Live span context: records on ``__exit__``, after which ``t0``
    and ``dur`` hold what was recorded."""

    __slots__ = ("_tracer", "name", "trace", "attrs", "_p0", "_id",
                 "_parent", "_annotation", "t0", "dur")

    def __init__(self, tracer, name, trace, attrs):
        self._tracer = tracer
        self.name = name
        self.trace = trace
        self.attrs = attrs

    def set(self, key, value):
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value
        return self

    def __enter__(self):
        tr = self._tracer
        stack = tr._stack()
        if self.trace is None and stack:
            self.trace = stack[-1][0]
        self._parent = stack[-1][1] if stack else None
        self._id = next(tr._ids)
        stack.append((self.trace, self._id))
        self._annotation = _annotation("tfos." + self.name)
        self._annotation.__enter__()
        self._p0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        p1 = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        tr = self._tracer
        stack = tr._stack()
        if stack:
            stack.pop()
        self.t0 = tr._on_wall(self._p0)
        self.dur = (p1 - self._p0) * 1e-9
        tr._record(
            self.name, self.trace, self._id, self._parent,
            self.t0, self.dur, self.attrs,
        )
        return False


class Tracer(object):
    """Bounded in-process span store (see module docstring)."""

    def __init__(self, enabled=None, max_spans=None, journal=None):
        self._enabled = (
            _registry._env_enabled() if enabled is None else bool(enabled)
        )
        self._spans = collections.deque(
            maxlen=max_spans if max_spans else MAX_SPANS
        )
        self._ids = itertools.count(1)
        self._local = threading.local()
        #: spans evicted by the bounded store (ISSUE 10 satellite):
        #: truncation must be *visible* — a trace missing its oldest
        #: spans without this counter reads as "nothing happened"
        self.dropped_spans = 0
        self._m_dropped = None
        self._anchor()
        #: journal every mark() bridges into (ISSUE 11): None = the
        #: process-wide default, resolved lazily; pass an explicit
        #: EventJournal to isolate (tests)
        self._journal = journal
        #: Chrome-trace process label (merge_traces/export metadata);
        #: defaults to "pid<pid>"
        self.process_name = None

    # -- enable/disable -------------------------------------------------

    @property
    def enabled(self):
        return self._enabled

    def set_enabled(self, flag):
        self._enabled = bool(flag)

    # -- the clock ------------------------------------------------------

    def _anchor(self):
        """Read the wall clock between two readings of the monotonic
        one, and keep the tightest of three tries: a read the
        scheduler interrupted would move every start by as much."""
        best = None
        for _ in range(3):
            p0 = time.perf_counter_ns()
            wall = time.time_ns()
            p1 = time.perf_counter_ns()
            if best is None or p1 - p0 < best[0]:
                best = (p1 - p0, wall - (p0 + p1) // 2, p1)
        self._wall_less_mono_ns = best[1]
        self._anchored_ns = best[2]

    def _on_wall(self, mono_ns):
        """Seconds on the profiler's clock of a ``perf_counter_ns``
        reading."""
        if mono_ns - self._anchored_ns > ANCHOR_REFRESH_NS:
            self._anchor()
        return (mono_ns + self._wall_less_mono_ns) * 1e-9

    def now(self):
        """Seconds on the profiler's clock (see the module docstring):
        the start of every span, and the one clock a call site of
        :meth:`add` reads.  Between two looks at the wall clock (at
        most ``ANCHOR_REFRESH_NS`` apart) it advances with the
        monotonic clock, so the difference of two readings is a
        duration."""
        return self._on_wall(time.perf_counter_ns())

    # -- recording ------------------------------------------------------

    def _stack(self):
        s = getattr(self._local, "stack", None)
        if s is None:
            s = self._local.stack = []
        return s

    def span(self, name, trace=None, **attrs):
        """Context manager timing a region.  ``trace`` names the
        request/step the span belongs to (inherited from the enclosing
        span when omitted); extra kwargs become span attributes."""
        if not self._enabled:
            return _NULL_SPAN
        return _SpanCtx(self, name, trace, attrs or None)

    def add(self, name, t0, dur, trace=None, **attrs):
        """Record an already-measured interval (``t0`` a reading of
        :meth:`now`, ``dur`` seconds)."""
        if not self._enabled:
            return
        self._record(
            name, trace, next(self._ids), None, t0, dur, attrs or None
        )

    def mark(self, name, trace=None, severity="info", attrs=None,
             **extra):
        """Record an instantaneous event (zero-duration span) — shed /
        watchdog / restart markers the chaos tests assert on.

        ISSUE 11: marks carry an explicit ``severity``
        (info/warn/page) and a structured attrs dict (``attrs`` merges
        with keyword extras), and every mark auto-bridges into the
        tracer's :class:`~tensorflowonspark_tpu.telemetry.journal.
        EventJournal` — the fault sites instrumented since PR 7 become
        typed journal events with no new call-site code.  The span
        record and Chrome export keep their old shape for existing
        consumers (severity rides along as one more field/arg)."""
        if not self._enabled:
            return
        merged = dict(attrs) if attrs else {}
        if extra:
            merged.update(extra)
        self._record(
            name, trace, next(self._ids), None, self.now(),
            0.0, merged or None, severity=severity,
        )
        j = self._journal
        if j is None:
            from tensorflowonspark_tpu.telemetry import journal as _journal

            j = _journal.get_journal()
        try:
            j.emit(
                name, severity=severity, trace=trace,
                attrs=merged or None,
            )
        except Exception:  # noqa: BLE001 - the mark already landed;
            pass  # journalling must never break the instrumented path

    def _record(self, name, trace, span_id, parent, t0, dur, attrs,
                severity=None):
        if len(self._spans) == self._spans.maxlen:
            # the deque is about to silently evict its oldest span —
            # count it into the registry so truncation shows up in
            # snapshot() / the fleet view (tracing.dropped_spans)
            self.dropped_spans += 1
            if self._m_dropped is None:
                self._m_dropped = _registry.get_registry().counter(
                    "tracing.dropped_spans"
                )
            self._m_dropped.inc()
        rec = {
            "name": name,
            "trace": trace,
            "id": span_id,
            "t0": t0,
            "dur": dur,
            "tid": threading.get_ident(),
        }
        if parent is not None:
            rec["parent"] = parent
        if attrs:
            rec["attrs"] = attrs
        if severity is not None:
            rec["severity"] = severity
        self._spans.append(rec)

    # -- introspection / export -----------------------------------------

    def spans(self, name=None, trace=None):
        """Snapshot of recorded spans, optionally filtered."""
        out = list(self._spans)
        if name is not None:
            out = [s for s in out if s["name"] == name]
        if trace is not None:
            out = [s for s in out if s.get("trace") == trace]
        return out

    def count(self, name, trace=None):
        """Number of recorded spans matching the filter — the
        assertion primitive for MUST-NOT-FIRE contracts (e.g. the
        hierarchical PS plane's zero-``grad_readback`` invariant,
        tests/test_hier_ps.py) without materializing the span list."""
        return sum(
            1 for s in self._spans
            if s["name"] == name
            and (trace is None or s.get("trace") == trace)
        )

    def clear(self):
        self._spans.clear()

    def export_chrome(self, trace=None):
        """Chrome-trace / Perfetto JSON object.  Spans map to complete
        ('X') events with ``ts`` in Unix microseconds (a viewer shows
        them from the trace's first event); the trace id rides
        ``args.trace`` and the span tree rides ``args.parent``.  Also carries ``process_name`` /
        ``thread_name`` metadata ('M') events — appended AFTER the
        spans, so old consumers indexing ``traceEvents[0]`` still see
        the first span — keeping a merged multi-executor trace
        (:func:`merge_traces`) row-named.

        ``trace`` filters the export to ONE trace id — the shape the
        cost-attribution plane hands to :func:`merge_traces` to render
        a single request's fleet-wide story (ISSUE 14)."""
        pid = os.getpid()
        pname = self.process_name or "pid{0}".format(pid)
        events = []
        meta = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": pname},
        }]
        tids = []
        spans = list(self._spans)
        if trace is not None:
            spans = [s for s in spans if s.get("trace") == trace]
        for s in spans:
            if s["tid"] not in tids:
                tids.append(s["tid"])
                meta.append({
                    "name": "thread_name", "ph": "M", "pid": pid,
                    "tid": s["tid"],
                    "args": {"name": "thread-{0}".format(s["tid"])},
                })
            args = dict(s.get("attrs") or {})
            if s.get("trace") is not None:
                args["trace"] = s["trace"]
            if s.get("parent") is not None:
                args["parent"] = s["parent"]
            if s.get("severity") is not None:
                args["severity"] = s["severity"]
            events.append({
                "name": s["name"],
                "ph": "X",
                "ts": round(s["t0"] * 1e6, 3),
                "dur": round(s["dur"] * 1e6, 3),
                "pid": pid,
                "tid": s["tid"],
                "args": args,
            })
        return {"traceEvents": events + meta, "displayTimeUnit": "ms"}

    def save(self, path):
        """Write the Chrome-trace JSON; returns ``path``."""
        with open(path, "w") as f:
            json.dump(self.export_chrome(), f)
        return path


def merge_traces(parts):
    """Merge per-executor Chrome traces into ONE Perfetto-loadable
    file, applying the estimated clock offsets (ISSUE 11 satellite).

    ``parts`` is a list of ``(trace, offset_sec, label)`` tuples (or
    dicts with ``trace``/``offset``/``label`` keys): ``trace`` is a
    Chrome-trace object (``{"traceEvents": [...]}``, as
    :meth:`Tracer.export_chrome` produces), ``offset_sec`` is the
    seconds to ADD to that executor's timestamps to land them on the
    reference (driver) clock (``ClockSync.offset`` — see
    cluster/reservation.py), and ``label`` names the merged trace's
    process row (overriding any ``process_name`` metadata).

    Colliding pids across parts are remapped (part index becomes the
    pid) so two executors that happen to share an OS pid never
    interleave rows.  Non-metadata events come back time-sorted —
    causally ordered across executors once the offsets are right."""
    events = []
    meta = []
    for i, part in enumerate(parts):
        if isinstance(part, dict):
            trace = part.get("trace") or {}
            offset = float(part.get("offset", 0.0) or 0.0)
            label = part.get("label")
        else:
            trace, offset = part[0], float(part[1] or 0.0)
            label = part[2] if len(part) > 2 else None
        shift_us = offset * 1e6
        named = False
        for ev in (trace or {}).get("traceEvents", []):
            ev = dict(ev, pid=i)
            if ev.get("ph") == "M":
                if ev.get("name") == "process_name":
                    if label is not None:
                        ev["args"] = {"name": label}
                    named = True
                meta.append(ev)
                continue
            if "ts" in ev:
                ev["ts"] = round(ev["ts"] + shift_us, 3)
            events.append(ev)
        if not named and label is not None:
            meta.append({
                "name": "process_name", "ph": "M", "pid": i, "tid": 0,
                "args": {"name": label},
            })
    events.sort(key=lambda e: e.get("ts", 0.0))
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def attribute(intervals, spans):
    """Which span covers each piece of ``intervals``: ``{span name or
    "unattributed": seconds}``, summing to the intervals' total.

    ``intervals`` are ``(start, end)`` pairs in seconds on the spans'
    clock (the device's idle gaps from a profiler capture, moved by
    :func:`profile_start_ns`); ``spans`` are records as
    :meth:`Tracer.spans` returns them.  Every interval is cut where a
    span begins or ends, and each piece goes to the innermost span
    that covers it — the shortest; of equals, the one recorded first.
    Marks (zero duration) cover nothing."""
    intervals = [iv for iv in intervals if iv[1] > iv[0]]
    if not intervals:
        return {}
    first = min(iv[0] for iv in intervals)
    last = max(iv[1] for iv in intervals)
    timed = [
        (s["t0"], s["t0"] + s["dur"], s["name"])
        for s in spans
        if s["dur"] > 0.0 and s["t0"] < last and s["t0"] + s["dur"] > first
    ]
    total = {}
    for start, end in intervals:
        over = [sp for sp in timed if sp[0] < end and sp[1] > start]
        cuts = sorted({start, end} | {
            t for sp in over for t in sp[:2] if start < t < end})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            inside = [sp for sp in over if sp[0] <= mid <= sp[1]]
            name = (
                min(inside, key=lambda sp: sp[1] - sp[0])[2]
                if inside else "unattributed"
            )
            total[name] = total.get(name, 0.0) + (b - a)
    return total


def profile_start_ns(xplane_path):
    """Unix nanoseconds at which the profiler session that wrote
    ``xplane_path`` began, or None where the file does not say.  An
    exported capture holds every event relative to this moment, so a
    span of the ring starts ``t0 * 1e9 - profile_start_ns(path)``
    nanoseconds into it."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
            return None if start is None else int(start)
    return None


#: JAX's compile pipeline as it reports itself on ``jax.monitoring``
#: (``jax/_src/dispatch.py``, JAX 0.9): a scalar at each stage's entry
#: (its start), then a time span at its end, both with ``fun_name``
_JIT_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}
#: persistent-cache events fired inside ``compile_or_get_cached``, on
#: the compiling thread: the request used the cache (a miss unless a
#: hit follows), and the executable was read back
_CACHE_STATES = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
#: what ``jax/_src/xla_bridge.py`` logs (at DEBUG) round each backend's
#: creation — TPU init, on a chip
_BACKEND_LOGGER = "jax._src.xla_bridge"
_BACKEND_START = "Initializing backend '%s'"
_BACKEND_END = "Backend '%s' initialized"

_WATCH_LOCK = threading.Lock()
_WATCHING = False
_watch_local = threading.local()


def _open_stages():
    """This thread's stages that have begun and not ended, outermost
    first: ``[stage, inner traces, cache]`` each."""
    stages = getattr(_watch_local, "stages", None)
    if stages is None:
        stages = _watch_local.stages = []
    return stages


def _on_stage_entry(event, value, **_):
    stage = _JIT_STAGES.get(event)
    if stage is not None and get_tracer().enabled:
        _open_stages().append([stage, 0, "off"])


def _on_cache_event(event, **_):
    state = _CACHE_STATES.get(event)
    if state is None:
        return
    for frame in reversed(_open_stages()):
        if frame[0] == "jit.compile":
            frame[2] = state
            return


def _on_stage_end(event, start, end, fun_name=None, **_):
    stage = _JIT_STAGES.get(event)
    tracer = get_tracer()
    if stage is None or not tracer.enabled:
        return
    stages = _open_stages()
    at = next((i for i in range(len(stages) - 1, -1, -1)
               if stages[i][0] == stage), None)
    if at is None:
        return  # its entry came before the watch, or with tracing off
    _, nested, cache = stages[at]
    del stages[at:]
    if stage == "jit.trace" and stages:
        # a trace inside another trace, or inside a lowering (rules
        # lowered through traced Python): counted by the stage that
        # holds it, never a span of its own (a step holds thousands)
        stages[-1][1] += 1 + nested
        return
    attrs = {"fun": fun_name, "nested": nested}
    if stage == "jit.compile":
        attrs["cache"] = cache
    tracer.add(stage, start, end - start, trace="jit", **attrs)


class _BackendInitFilter(logging.Filter):
    """Turns the backend-creation records of ``jax._src.xla_bridge``
    into ``setup.device`` spans, one a platform, and lets through to the
    handlers only the records the logger would have let through without
    the watch: its own level where it had one, else its parents' level
    as it is now."""

    def __init__(self, logger):
        super(_BackendInitFilter, self).__init__()
        self.logger = logger
        self.own = logger.level
        self._started = {}

    def filter(self, record):
        tracer = get_tracer()
        if tracer.enabled and record.msg in (_BACKEND_START, _BACKEND_END):
            platform = str(record.args[0])
            if record.msg == _BACKEND_START:
                self._started[platform] = tracer.now()
            elif record.msg == _BACKEND_END and platform in self._started:
                t0 = self._started.pop(platform)
                tracer.add("setup.device", t0, tracer.now() - t0,
                           trace="setup", platform=platform)
        floor = self.own or self.logger.parent.getEffectiveLevel()
        return record.levelno >= floor


def watch_jit():
    """Record JAX's own set-up as spans of the process-wide tracer, from
    now on; idempotent (one registration a process).

    - ``jit.trace``: each OUTERMOST trace of a program's Python;
    - ``jit.lower``: each lowering to MLIR;
    - ``jit.compile``: each ``compile_or_get_cached``, with ``cache``
      — ``hit`` (the executable was read back), ``miss`` (the cache
      was asked and compiled) or ``off`` (no cache);
    - ``setup.device``: each backend's creation, with ``platform``,
      from the two DEBUG records ``jax._src.xla_bridge`` logs round it
      (JAX has no monitoring event there).  To have them made, the
      watch sets that ONE logger's level to DEBUG; a filter lets through
      to the handlers only what the logger would have let through
      without it (its own level before the watch, else its parents'),
      so the logs read as before.  A JAX that rewords the two records,
      or a logger set above DEBUG later, loses this span alone
      (``tests/test_setup_spans.py`` fails on the first).

    Each ``jit.*`` span has ``fun`` and ``nested``: the traces that ran
    inside it on its thread (inner ``jit`` calls; Python traced while a
    rule lowers), counted there and never spans of their own, so the
    sum over the spans of ``nested``, plus one a ``jit.trace``, is every
    trace JAX reported.  Trace ids ``jit`` and ``setup``; starts and ends are JAX's own,
    Unix seconds, so the spans land on the profiler's timeline beside
    every other.  Nothing is paid on a warm call of a compiled program
    (JAX reports nothing there); with the tracer disabled the listeners
    return at once.  Installed by
    :func:`~tensorflowonspark_tpu.utils.compile_cache.ensure_compile_cache`,
    which every chip owner calls before its first program."""
    global _WATCHING
    with _WATCH_LOCK:
        if _WATCHING:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_on_stage_entry)
        monitoring.register_event_listener(_on_cache_event)
        monitoring.register_event_time_span_listener(_on_stage_end)
        logger = logging.getLogger(_BACKEND_LOGGER)
        logger.addFilter(_BackendInitFilter(logger))
        logger.setLevel(logging.DEBUG)
        _WATCHING = True


_GLOBAL = None
_GLOBAL_LOCK = threading.Lock()


def get_tracer():
    """The process-wide default tracer (same enable story as the
    default registry)."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_LOCK:
            if _GLOBAL is None:
                _GLOBAL = Tracer()
    return _GLOBAL
