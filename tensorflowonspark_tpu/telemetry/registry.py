"""Process-wide metrics registry: counters, gauges, bucket histograms.

Design constraints (ISSUE 7 tentpole):

- **low overhead** — a counter ``inc`` is one lock acquire + integer
  add; a histogram ``observe`` is one ``bisect`` + two adds.  Metrics
  are resolved by name ONCE and cached by the instrumented object, so
  the hot path never touches the registry dict;
- **lock-light** — one small lock per *metric* (never a global lock on
  the observe path; the registry-level lock only guards name→metric
  resolution);
- **plain-dict export** — ``snapshot()`` returns JSON-serializable
  dicts so a snapshot can ride a heartbeat frame to the reservation
  server unchanged (telemetry/aggregate.py), and ``snapshot_delta``
  subtracts two snapshots for per-job / per-bench-window accounting;
- **zero-cost-when-disabled** — a disabled registry hands out shared
  NULL singletons whose mutators are ``pass``: no allocation, no lock,
  nothing retained (tests/test_telemetry.py pins the identity).

Histograms use FIXED geometric buckets (ratio 1.25 spanning
``1e-5 .. ~460`` seconds by default) so two processes' histograms merge
bucket-wise without resampling; ``p50``/``p99`` are interpolated within
the hit bucket — error is bounded by the 25% bucket width and measured
far tighter against numpy percentiles in tests/test_telemetry.py.
"""

import bisect
import os
import threading
import time

#: Env kill-switch: ``TFOS_TELEMETRY=0`` disables the default registry
#: and tracer at import time (docs/observability.md "Overhead budget").
TELEMETRY_ENV = "TFOS_TELEMETRY"


def _env_enabled():
    return os.environ.get(TELEMETRY_ENV, "1").lower() not in (
        "0", "false", "off", "no",
    )


# ----------------------------------------------------------------------
# metric types
# ----------------------------------------------------------------------


class Counter(object):
    """Monotonic counter.  ``inc`` is thread-safe (per-metric lock)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n=1):
        with self._lock:
            self._value += n

    add = inc

    @property
    def value(self):
        return self._value


class Gauge(object):
    """Last-write-wins scalar (queue depths, cache bytes, ages)."""

    __slots__ = ("name", "_value")

    def __init__(self, name):
        self.name = name
        self._value = 0.0

    def set(self, v):
        self._value = float(v)

    @property
    def value(self):
        return self._value


def default_buckets():
    """Geometric latency buckets: 1e-5s .. ~460s at ratio 1.25 (88
    upper bounds).  Fixed so histograms from different processes merge
    bucket-wise (telemetry/aggregate.py)."""
    out = []
    b = 1e-5
    for _ in range(88):
        out.append(b)
        b *= 1.25
    return out


class Histogram(object):
    """Fixed-bucket histogram with interpolated percentiles.

    ``observe(v)`` finds the bucket via ``bisect`` and bumps its count
    under the metric lock; ``percentile(q)`` interpolates linearly
    inside the bucket the q-th observation falls in (values above the
    top bound clamp to it).  ``snapshot()`` exports plain dicts
    including the NONZERO ``[upper_bound, count]`` pairs, which is what
    cross-process merging and delta subtraction operate on.
    """

    __slots__ = (
        "name", "bounds", "_counts", "_count", "_sum", "_min", "_max",
        "_lock", "_exemplars",
    )

    def __init__(self, name, buckets=None):
        self.name = name
        self.bounds = sorted(float(b) for b in (buckets or default_buckets()))
        # one overflow bucket past the top bound
        self._counts = [0] * (len(self.bounds) + 1)
        self._count = 0
        self._sum = 0.0
        self._min = None
        self._max = None
        self._lock = threading.Lock()
        # bucket index -> (ref, value, wall ts): the newest observation
        # per bucket that carried an exemplar reference (ISSUE 14 —
        # trace ids, so a tail-latency bucket names the exact request
        # whose merged trace explains it).  Bounded by the fixed bucket
        # count; last-write-wins within a bucket.
        self._exemplars = {}

    def observe(self, v, exemplar=None):
        """Record ``v``; ``exemplar`` optionally attaches a reference
        (a trace id) to ``v``'s bucket — retained newest-per-bucket so
        tail buckets always name a concrete offending request."""
        v = float(v)
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += v
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v
            if exemplar is not None:
                self._exemplars[i] = (str(exemplar), v, time.time())

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def percentile(self, q):
        """Interpolated q-th percentile (q in [0, 100]); 0.0 when
        empty."""
        with self._lock:
            counts = list(self._counts)
            total = self._count
        return _percentile_from_counts(counts, self.bounds, total, q)

    def snapshot(self):
        with self._lock:
            counts = list(self._counts)
            total, s = self._count, self._sum
            lo, hi = self._min, self._max
            exemplars = dict(self._exemplars)
        out = {
            "count": total,
            # the EXACT running sum (never rounded, never re-derived
            # from buckets): means stay exact — not bucket-interpolated
            # — through snapshot_delta, merge_snapshots, and the
            # OpenMetrics `_sum` line (ISSUE 10 satellite)
            "sum": s,
            "min": lo,
            "max": hi,
            "p50": _percentile_from_counts(counts, self.bounds, total, 50),
            "p99": _percentile_from_counts(counts, self.bounds, total, 99),
            # NONZERO buckets as [lower, upper, count] triples (upper
            # None for the overflow bucket): carrying both edges keeps
            # percentile interpolation exact on sparse snapshots,
            # deltas, and cross-process merges
            "buckets": [
                [
                    self.bounds[i - 1] if i > 0 else 0.0,
                    self.bounds[i] if i < len(self.bounds) else None,
                    c,
                ]
                for i, c in enumerate(counts)
                if c
            ],
        }
        if exemplars:
            # [[lower, upper, {"ref", "value", "ts"}], ...] — the same
            # bucket-edge convention as the count triples, so deltas
            # and merges can align them without re-deriving bounds
            out["exemplars"] = [
                [
                    self.bounds[i - 1] if i > 0 else 0.0,
                    self.bounds[i] if i < len(self.bounds) else None,
                    {"ref": ref, "value": val, "ts": ts},
                ]
                for i, (ref, val, ts) in sorted(exemplars.items())
            ]
        if total:
            out["mean"] = s / total
        return out


def _percentile_from_counts(counts, bounds, total, q):
    """Shared percentile rule over ``[count-per-bucket]`` arrays —
    used by live histograms, snapshot deltas, and cross-process merges
    so every surface reports identical semantics."""
    if not total:
        return 0.0
    rank = max(1.0, (q / 100.0) * total)
    seen = 0
    for i, c in enumerate(counts):
        if not c:
            continue
        if seen + c >= rank:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else bounds[-1]
            frac = (rank - seen) / c
            return lo + (hi - lo) * min(1.0, max(0.0, frac))
        seen += c
    return bounds[-1]


def histogram_percentile(snapshot, q):
    """Percentile from a histogram *snapshot* (or a snapshot delta /
    cross-process merge): same interpolation as the live metric,
    operating on the ``[lower, upper, count]`` bucket triples."""
    if not snapshot or not snapshot.get("count"):
        return 0.0
    triples = snapshot.get("buckets") or []
    total = int(snapshot["count"])
    rank = max(1.0, (q / 100.0) * total)
    seen = 0
    result = 0.0
    for lo, hi, c in triples:
        top = lo if hi is None else hi  # overflow clamps to its edge
        result = top
        if not c:
            continue
        if seen + c >= rank:
            frac = (rank - seen) / c
            return lo + (top - lo) * min(1.0, max(0.0, frac))
        seen += c
    return result


def tail_exemplars(snapshot, q=99):
    """Exemplars from the buckets at/above the ``q``-th percentile of
    a histogram snapshot (or delta/merge) — "name me a request that
    actually lives in the p99 tail", heaviest bucket first.  Returns
    ``[{"ref", "value", "ts", "bucket_lo", "bucket_hi"}]`` (empty when
    the histogram recorded no exemplars).  The forensics analyzer uses
    the top entry to pull the exact merged trace of a tail request
    (ISSUE 14 — docs/observability.md "Cost attribution & usage
    ledger")."""
    if not snapshot:
        return []
    p = histogram_percentile(snapshot, q)
    out = []
    for lo, hi, ex in snapshot.get("exemplars", []) or []:
        top = lo if hi is None else hi
        if top >= p:
            out.append(dict(ex, bucket_lo=lo, bucket_hi=hi))
    out.sort(key=lambda e: -e["value"])
    return out


# ----------------------------------------------------------------------
# null objects: the disabled-mode fast path
# ----------------------------------------------------------------------


class _NullCounter(object):
    __slots__ = ()
    name = "<disabled>"
    value = 0

    def inc(self, n=1):
        pass

    add = inc


class _NullGauge(object):
    __slots__ = ()
    name = "<disabled>"
    value = 0.0

    def set(self, v):
        pass


class _NullHistogram(object):
    __slots__ = ()
    name = "<disabled>"
    count = 0
    sum = 0.0

    def observe(self, v, exemplar=None):
        pass

    def percentile(self, q):
        return 0.0

    def snapshot(self):
        return {"count": 0, "sum": 0.0, "buckets": []}


#: Shared singletons a disabled registry hands out — accessor calls
#: allocate NOTHING (identity-asserted in tests/test_telemetry.py).
NULL_COUNTER = _NullCounter()
NULL_GAUGE = _NullGauge()
NULL_HISTOGRAM = _NullHistogram()


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------


class MetricsRegistry(object):
    """Name → metric store.  Accessors are get-or-create and memoized;
    instrumented objects should resolve their metrics ONCE (at
    ``__init__``) and keep the references — the per-call cost is then
    only the metric's own lock."""

    def __init__(self, enabled=None):
        self._enabled = _env_enabled() if enabled is None else bool(enabled)
        self._metrics = {}
        self._lock = threading.Lock()

    # -- enable/disable -------------------------------------------------

    @property
    def enabled(self):
        return self._enabled

    def enable(self):
        self._enabled = True

    def disable(self):
        self._enabled = False

    # -- accessors ------------------------------------------------------

    def _get(self, name, cls, *args):
        if not self._enabled:
            return _NULLS[cls]
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, *args)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise ValueError(
                    "metric {0!r} is a {1}, not a {2}".format(
                        name, type(m).__name__, cls.__name__
                    )
                )
            return m

    def counter(self, name):
        return self._get(name, Counter)

    def gauge(self, name):
        return self._get(name, Gauge)

    def histogram(self, name, buckets=None):
        return self._get(name, Histogram, buckets)

    # -- export ---------------------------------------------------------

    def snapshot(self):
        """Plain-dict export: ``{"counters": {name: int}, "gauges":
        {name: float}, "histograms": {name: hist-snapshot}}`` — small,
        JSON-serializable, heartbeat-frame-sized."""
        with self._lock:
            metrics = list(self._metrics.items())
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for name, m in metrics:
            if isinstance(m, Counter):
                out["counters"][name] = m.value
            elif isinstance(m, Gauge):
                out["gauges"][name] = m.value
            else:
                out["histograms"][name] = m.snapshot()
        return out

    def reset(self):
        """Drop every metric (tests / per-bench-window isolation)."""
        with self._lock:
            self._metrics.clear()


_NULLS = {
    Counter: NULL_COUNTER,
    Gauge: NULL_GAUGE,
    Histogram: NULL_HISTOGRAM,
}


def snapshot_delta(cur, base):
    """``cur - base`` over two :meth:`MetricsRegistry.snapshot` dicts:
    counters subtract, histogram counts/sums/buckets subtract
    (percentiles recomputed over the delta), gauges keep ``cur``'s
    value.  The per-job / per-window accounting primitive (the serving
    bench uses it to report a run's p50/p99 from the shared
    histogram)."""
    base = base or {}
    out = {"counters": {}, "gauges": dict(cur.get("gauges", {})),
           "histograms": {}}
    bc = base.get("counters", {})
    for name, v in cur.get("counters", {}).items():
        out["counters"][name] = v - bc.get(name, 0)
    bh = base.get("histograms", {})
    for name, h in cur.get("histograms", {}).items():
        b = bh.get(name)
        if not b or not b.get("count"):
            out["histograms"][name] = dict(h)
            continue
        base_counts = {
            (lo, hi): c for lo, hi, c in b.get("buckets", [])
        }
        triples = []
        for lo, hi, c in h.get("buckets", []):
            dc = c - base_counts.get((lo, hi), 0)
            if dc:
                triples.append([lo, hi, dc])
        d = {
            "count": h.get("count", 0) - b.get("count", 0),
            "sum": h.get("sum", 0.0) - b.get("sum", 0.0),
            "buckets": triples,
        }
        if h.get("exemplars"):
            # keep only exemplars whose bucket saw traffic in this
            # window — a stale reference from before the base snapshot
            # would mislead the window's tail analysis
            live = {(lo, hi) for lo, hi, _c in triples}
            ex = [e for e in h["exemplars"] if (e[0], e[1]) in live]
            if ex:
                d["exemplars"] = ex
        d["p50"] = histogram_percentile(d, 50)
        d["p99"] = histogram_percentile(d, 99)
        if d["count"]:
            d["mean"] = d["sum"] / d["count"]
        out["histograms"][name] = d
    return out


# ----------------------------------------------------------------------
# process-global default
# ----------------------------------------------------------------------

_GLOBAL = None
_GLOBAL_LOCK = threading.Lock()


def get_registry():
    """The process-wide default registry every built-in surface
    publishes into (serving engine, slot decoder, prefix cache, PS
    client, feed plane, supervisor)."""
    global _GLOBAL
    if _GLOBAL is None:
        with _GLOBAL_LOCK:
            if _GLOBAL is None:
                _GLOBAL = MetricsRegistry()
    return _GLOBAL


def enabled():
    return get_registry().enabled


def set_enabled(flag):
    """Flip the default registry AND tracer (tests, an
    instrumented-vs-disabled comparison).  Note: objects that cached a
    NULL metric while disabled keep the null — set the flag before
    constructing the surfaces you want measured."""
    reg = get_registry()
    if flag:
        reg.enable()
    else:
        reg.disable()
    from tensorflowonspark_tpu.telemetry import tracing

    tracing.get_tracer().set_enabled(flag)
